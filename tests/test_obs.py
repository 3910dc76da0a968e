"""Flight recorder: metrics registry, Chrome-trace tracer, compression
observatory — units plus end-to-end integration.

The integration tests drive the real training loop / supervised fault
drill with observability ON and assert the three artifacts the run must
produce: a valid Chrome-trace JSON with spans from both the training and
the ckpt-drain threads, a metrics JSONL stream with step percentiles and
queue-depth samples, and per-snapshot ``obs_i*.json`` records whose byte
totals match the manifest payload sizes *exactly*.  The overhead guard
holds the enabled-vs-disabled step wall within the DESIGN.md §11 budget.
"""

import json
import statistics
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointManager
from repro.data.tokens import DataConfig, TokenPipeline
from repro.foresight import guideline
from repro.obs import metrics as obs_metrics
from repro.obs import observatory
from repro.obs import trace as obs_trace
from repro.train import elastic, faults
from repro.train import loop as loop_lib
from repro.train import supervisor as sup


@pytest.fixture(autouse=True)
def _obs_off_after():
    """Every test leaves the process-global registry/tracer disabled, no
    matter how it exits — other test files must keep their zero-overhead
    no-op path."""
    yield
    obs_metrics.disable()
    obs_trace.disable()
    obs_trace.clear()


# ------------------------------------------------------- metrics (unit) --


class TestMetrics:
    def test_counter_and_gauge(self):
        r = obs_metrics.Registry()
        r.enable()
        c = r.counter("x")
        c.inc()
        c.inc(2)
        assert c.value == 3
        assert r.counter("x") is c  # get-or-create
        g = r.gauge("q")
        g.set(7)
        g.set(2.5)
        assert g.value == 2.5
        assert r.snapshot()["counters"]["x"] == 3
        assert r.snapshot()["gauges"]["q"] == 2.5

    def test_disabled_registry_is_noop(self):
        r = obs_metrics.Registry()  # never enabled
        r.counter("c").inc(5)
        r.gauge("g").set(9)
        r.histogram("h").observe(1.0)
        r.event("e", step=1)
        assert r.counter("c").value == 0
        assert r.gauge("g").value == 0.0
        assert r.histogram("h").count == 0
        assert r.events() == []
        assert r.export_snapshot() is None

    def test_histogram_percentiles_nearest_rank(self):
        r = obs_metrics.Registry()
        r.enable()
        h = r.histogram("h", size=1000)
        for v in range(1, 101):
            h.observe(float(v))
        p = h.percentiles()
        assert p["count"] == 100
        assert p["min"] == 1.0 and p["max"] == 100.0
        assert p["mean"] == pytest.approx(50.5)
        assert p["p50"] == 50.0 and p["p90"] == 90.0 and p["p99"] == 99.0

    def test_histogram_ring_buffer_wraps(self):
        """Percentiles come from the newest ``size`` samples; count and
        min/max track the whole stream."""
        r = obs_metrics.Registry()
        r.enable()
        h = r.histogram("h", size=10)
        for v in range(1, 101):
            h.observe(float(v))
        p = h.percentiles()
        assert p["count"] == 100
        assert p["min"] == 1.0  # full-stream min survives eviction
        assert p["p50"] == 95.0  # nearest-rank over the 91..100 window
        assert p["p99"] == 100.0

    def test_events_and_jsonl_sink(self, tmp_path):
        sink = tmp_path / "m.jsonl"
        r = obs_metrics.Registry()
        r.enable(sink)
        r.event("boom", step=3, why="test")
        r.event("boom", step=4)
        r.export_snapshot(step=4)
        assert r.counter("boom").value == 2  # events bump the counter
        assert [e["step"] for e in r.events("boom")] == [3, 4]
        lines = [json.loads(x) for x in sink.read_text().splitlines()]
        assert [x["kind"] for x in lines] == ["event", "event", "metrics"]
        assert lines[0]["name"] == "boom" and lines[0]["why"] == "test"
        assert lines[2]["counters"]["boom"] == 2
        r.disable()

    def test_event_buffer_bounded(self):
        r = obs_metrics.Registry(max_events=5)
        r.enable()
        for i in range(9):
            r.event("e", i=i)
        assert len(r.events()) == 5
        assert r.counter("e").value == 9  # the counter never drops
        assert "dropped" in r.summary()

    def test_summary_renders(self):
        r = obs_metrics.Registry()
        r.enable()
        r.counter("ckpt.retry").inc()
        r.gauge("depth").set(2)
        r.histogram("step_s").observe(0.5)
        s = r.summary()
        assert "ckpt.retry" in s and "depth" in s and "p99" in s
        assert "(nothing recorded)" in obs_metrics.Registry().summary()

    def test_thread_safety(self):
        r = obs_metrics.Registry()
        r.enable()
        c = r.counter("c")
        h = r.histogram("h", size=64)

        def work():
            for i in range(1000):
                c.inc()
                h.observe(float(i))

        ts = [threading.Thread(target=work) for _ in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert c.value == 4000
        assert h.count == 4000


# --------------------------------------------------------- trace (unit) --


def _validate_chrome_trace(doc: dict) -> None:
    """The subset of the Chrome-trace schema the viewers require: a
    traceEvents list whose entries carry name/ph/pid/tid, complete events
    with non-negative ts/dur, metadata events naming their thread."""
    assert isinstance(doc.get("traceEvents"), list) and doc["traceEvents"]
    for ev in doc["traceEvents"]:
        assert {"name", "ph", "pid", "tid"} <= set(ev), ev
        if ev["ph"] == "X":
            assert ev["ts"] >= 0 and ev["dur"] >= 0, ev
        elif ev["ph"] == "M":
            assert ev["name"] == "thread_name"
            assert ev["args"]["name"]


class TestTrace:
    def test_span_records_complete_event(self):
        tr = obs_trace.Tracer()
        tr.enable()
        with tr.span("work", step=3):
            pass
        (ev,) = tr.events
        assert ev["name"] == "work" and ev["ph"] == "X"
        assert ev["dur"] >= 0 and ev["args"] == {"step": 3}

    def test_disabled_span_is_shared_noop(self):
        tr = obs_trace.Tracer()
        s1 = tr.span("a")
        s2 = tr.span("b", x=1)
        assert s1 is s2  # one shared null object, zero allocation
        with s1:
            pass
        assert tr.events == []

    def test_bounded_buffer_drops(self):
        tr = obs_trace.Tracer(max_events=3)
        tr.enable()
        for i in range(5):
            with tr.span(f"s{i}"):
                pass
        assert len(tr.events) == 3
        assert tr.dropped == 2

    def test_export_two_threads_two_tracks(self, tmp_path):
        tr = obs_trace.Tracer()
        tr.enable()
        with tr.span("main.work"):
            pass

        def worker():
            with tr.span("bg.work"):
                pass

        t = threading.Thread(target=worker, name="bg-thread")
        t.start()
        t.join()
        doc = json.loads(tr.export(tmp_path / "t.json").read_text())
        _validate_chrome_trace(doc)
        by_name = {e["name"]: e for e in doc["traceEvents"] if e["ph"] == "X"}
        assert by_name["main.work"]["tid"] != by_name["bg.work"]["tid"]
        tnames = {e["args"]["name"] for e in doc["traceEvents"]
                  if e["ph"] == "M"}
        assert "bg-thread" in tnames


# --------------------------------------------------- observatory (unit) --


class TestObservatory:
    def test_build_doc_totals_and_ratios(self):
        recs = [
            {"leaf": 0, "codec": "arena-sz", "raw_bytes": 1000,
             "stored_bytes": 250},
            {"leaf": 1, "codec": "raw", "raw_bytes": 100, "stored_bytes": 100},
        ]
        doc = observatory.build_doc(12, recs, retries=2)
        assert doc["schema"] == observatory.SCHEMA
        assert doc["step"] == 12 and doc["retries"] == 2
        assert doc["total_raw_bytes"] == 1100
        assert doc["total_stored_bytes"] == 350
        assert doc["ratio"] == pytest.approx(1100 / 350, abs=1e-3)
        assert doc["records"][0]["ratio"] == 4.0  # annotated per record

    def test_obs_name_sorts_like_step_dirs(self):
        assert observatory.obs_name(7) == "obs_i000000007.json"
        names = [observatory.obs_name(s) for s in (2, 10, 100)]
        assert names == sorted(names)
        assert not observatory.obs_name(7).endswith(".bin")  # never a
        # corruption-drill victim (faults.corrupt_snapshot globs *.bin)

    def test_read_obs_tolerates_garbage(self, tmp_path):
        assert observatory.read_obs(tmp_path) is None  # no file at all
        (tmp_path / "obs_i000000001.json").write_text("{not json")
        assert observatory.read_obs(tmp_path) is None
        (tmp_path / "obs_i000000001.json").write_text(
            json.dumps({"schema": "other/v9"}))
        assert observatory.read_obs(tmp_path) is None

    def test_run_trajectory_and_feedback(self, tmp_path):
        ratios = [2.0, 2.5, 3.0, 3.01]
        for i, r in enumerate(ratios):
            d = tmp_path / f"step_{i * 3:09d}"
            d.mkdir()
            doc = observatory.build_doc(i * 3, [
                {"leaf": 0, "codec": "sz", "raw_bytes": 3000,
                 "stored_bytes": int(3000 / r)}])
            (d / observatory.obs_name(i * 3)).write_text(json.dumps(doc))
        traj = observatory.run_trajectory(tmp_path)
        assert [t["step"] for t in traj] == [0, 3, 6, 9]
        assert traj[0]["codecs"] == ["sz"]
        fb = guideline.rate_quality_feedback(traj, window=4)
        assert fb["n"] == 4
        assert fb["latest_ratio"] == traj[-1]["ratio"]
        assert not fb["stalled"]  # 2.0 -> ~3.0 is a real trend
        # a flat tail reads as stalled — the loosen-the-bound trigger
        fb2 = guideline.rate_quality_feedback(traj[-2:], window=2)
        assert fb2["stalled"]
        assert guideline.rate_quality_feedback([]) == {
            "n": 0, "latest_ratio": None, "mean_ratio": None,
            "trend": None, "stalled": False}


# ------------------------------------------------ micro-run integration --


@jax.jit
def _micro_step(state, batch):
    # scalar regression against a per-step target (same harness as the
    # supervisor drill): cheap to compile, loss is a pure function of
    # (w, step) so an exact replay reproduces it bitwise
    t = jnp.float32(jnp.asarray(batch["tokens"]).mean()) / 100.0

    def loss_fn(w):
        return jnp.mean((w - t) ** 2)

    loss, g = jax.value_and_grad(loss_fn)(state["w"])
    return {"w": state["w"] - 0.1 * g}, {"loss": loss}


def _micro_builder():
    def builder(mesh_shape, global_batch):
        mesh = elastic.make_degraded_mesh(mesh_shape)
        pipe = TokenPipeline(DataConfig(vocab=100, seq_len=8,
                                        global_batch=global_batch, seed=2))
        return sup.Trainer(
            mesh=mesh, mesh_shape=dict(mesh_shape),
            global_batch=global_batch, train_step=_micro_step,
            pipeline=pipe, put_batch=None, shardings=None,
            make_state=lambda: {"w": jnp.zeros((4,), jnp.float32)})

    return builder


def _manifest_stored_bytes(manifest: dict) -> int:
    total = 0
    for meta in manifest["leaves"]:
        shards = meta.get("shards")
        if isinstance(shards, list) and shards and "stored_bytes" in shards[0]:
            total += sum(b["stored_bytes"] for b in shards)
        else:
            total += meta["stored_bytes"]
    return total


class TestMicroRun:
    def test_five_step_run_exports_trace_and_jsonl(self, tmp_path):
        """The CI smoke: a 5-step run with obs on produces a
        schema-valid Chrome trace with training-thread and drain-thread
        tracks, and a metrics JSONL with step_s percentiles and
        queue-depth gauges."""
        jsonl = tmp_path / "metrics.jsonl"
        obs_metrics.enable(jsonl)
        obs_trace.enable()
        ckpt = CheckpointManager(tmp_path / "ckpt", async_save=True)
        pipe = TokenPipeline(DataConfig(vocab=100, seq_len=8,
                                        global_batch=4, seed=0))
        lcfg = loop_lib.LoopConfig(total_steps=5, ckpt_every=2, log_every=2)
        _, res = loop_lib.run(_micro_step, {"w": jnp.zeros((4,), jnp.float32)},
                              pipe, ckpt, lcfg)
        assert res.final_step == 5
        obs_metrics.export_snapshot(final=True)
        doc = json.loads(
            obs_trace.export(tmp_path / "trace_run.json").read_text())
        _validate_chrome_trace(doc)

        def tids(name):
            return {e["tid"] for e in doc["traceEvents"]
                    if e.get("name") == name}

        assert len(tids("train.step")) == 1
        assert tids("ckpt.drain.save")  # drain-thread spans present
        assert tids("train.step").isdisjoint(tids("ckpt.drain.save"))
        tnames = {e["args"]["name"] for e in doc["traceEvents"]
                  if e["ph"] == "M"}
        assert "ckpt-drain" in tnames

        lines = [json.loads(x) for x in jsonl.read_text().splitlines()]
        mlines = [x for x in lines if x["kind"] == "metrics"]
        assert len(mlines) >= 2  # log_every heartbeats + the final export
        h = mlines[-1]["hists"]["train.step_s"]
        assert h["count"] >= 5 and h["p50"] > 0 and h["p99"] >= h["p50"]
        assert "ckpt.queue_depth" in mlines[-1]["gauges"]
        assert "ckpt.in_flight" in mlines[-1]["gauges"]

    def test_observatory_sidecar_matches_manifest_exactly(self, tmp_path):
        """Every surviving snapshot carries an obs record whose stored
        totals equal BOTH the manifest's accounting and the bytes actually
        on disk — and observatory=False writes no sidecar."""
        ckpt = CheckpointManager(tmp_path / "a", async_save=False)
        ckpt.save(3, {"w": np.arange(64, dtype=np.float32)})
        d = tmp_path / "a" / "step_000000003"
        obs_doc = observatory.read_obs(d)
        assert obs_doc is not None and obs_doc["step"] == 3
        manifest = json.loads((d / "MANIFEST.json").read_text())
        on_disk = sum(f.stat().st_size for f in d.glob("*.bin"))
        assert obs_doc["total_stored_bytes"] == \
            _manifest_stored_bytes(manifest) == on_disk
        assert obs_doc["total_raw_bytes"] == 64 * 4
        # the sidecar is advisory: deleting it must not affect restore
        next(d.glob("obs_i*.json")).unlink()
        state, _ = ckpt.restore(3, state_like={"w": np.zeros(64, np.float32)})
        np.testing.assert_array_equal(state["w"], np.arange(64))

        off = CheckpointManager(tmp_path / "b", async_save=False,
                                observatory=False)
        off.save(3, {"w": np.arange(64, dtype=np.float32)})
        assert not list((tmp_path / "b" / "step_000000003").glob("obs_*"))


class TestSupervisedDrill:
    def test_drill_produces_all_flight_recorder_artifacts(self, tmp_path):
        """The acceptance scenario: a fault-injected supervised run with
        metrics + tracing on yields (1) retry and quarantine counter
        increments, (2) a Chrome trace with training-, drain- and
        supervisor-phase spans, (3) event lines for the whole casualty
        sequence in the JSONL, and (4) obs sidecars whose byte totals
        exactly match each manifest, aggregating into a readable
        rate-quality trajectory."""
        jsonl = tmp_path / "metrics.jsonl"
        obs_metrics.enable(jsonl)
        obs_trace.enable()
        retry0 = obs_metrics.counter("ckpt.retry").value
        quar0 = obs_metrics.counter("ckpt.quarantine").value

        plan = faults.FaultPlan.from_events([
            faults.FaultEvent(step=4, kind="drain_io", count=1),
            faults.FaultEvent(step=7, kind="corrupt_payload", mode="bitflip",
                              seed=11),
            faults.FaultEvent(step=7, kind="pod_loss"),
        ])
        inj = faults.FaultInjector(plan, ckpt_dir=tmp_path / "ckpt")
        ckpt = CheckpointManager(tmp_path / "ckpt", async_save=True,
                                 write_bytes=inj.write_bytes,
                                 retry_backoff_s=0.01)
        inj.manager = ckpt  # corrupt-newest waits out in-flight saves
        cfg = sup.SupervisorConfig(total_steps=15, ckpt_every=3,
                                   drain_deadline_s=10.0, grow_back_after=3)
        _, res = sup.run_supervised(_micro_builder(), {"data": 1}, 4, ckpt,
                                    cfg, injector=inj, log=lambda s: None)
        assert res.final_step == 15
        assert inj.log == [(4, "drain_io"), (7, "corrupt_payload"),
                           (7, "pod_loss")]
        obs_metrics.export_snapshot(final=True)

        # (1) the transient write and the corrupt snapshot both counted
        assert obs_metrics.counter("ckpt.retry").value > retry0
        assert obs_metrics.counter("ckpt.quarantine").value > quar0

        # (2) trace: training track, drain track, supervisor phases
        doc = json.loads(
            obs_trace.export(tmp_path / "trace_supervised.json").read_text())
        _validate_chrome_trace(doc)
        names = {e["name"] for e in doc["traceEvents"]}
        for want in ("train.step", "ckpt.save", "ckpt.drain.save",
                     "ckpt.restore", "supervisor.quiesce",
                     "supervisor.restore", "supervisor.grow_back"):
            assert want in names, want
        train_tids = {e["tid"] for e in doc["traceEvents"]
                      if e.get("name") == "train.step"}
        drain_tids = {e["tid"] for e in doc["traceEvents"]
                      if e.get("name") == "ckpt.drain.save"}
        assert train_tids and drain_tids and \
            train_tids.isdisjoint(drain_tids)
        tnames = {e["args"]["name"] for e in doc["traceEvents"]
                  if e["ph"] == "M"}
        assert "ckpt-drain" in tnames

        # (3) JSONL: the casualty sequence is reconstructible from events,
        # and the final metrics line has percentiles + queue-depth gauges
        lines = [json.loads(x) for x in jsonl.read_text().splitlines()]
        enames = {x["name"] for x in lines if x["kind"] == "event"}
        for want in ("ckpt.retry", "ckpt.corruption", "ckpt.quarantine",
                     "train.fault", "supervisor.casualty",
                     "supervisor.shrink", "supervisor.grow"):
            assert want in enames, want
        final = [x for x in lines if x["kind"] == "metrics"][-1]
        h = final["hists"]["train.step_s"]
        assert h["count"] >= 15 and h["p99"] >= h["p50"] > 0
        assert "ckpt.queue_depth" in final["gauges"]

        # (4) every surviving snapshot's obs record matches its manifest
        # byte-for-byte, and the run aggregates into a trajectory
        step_dirs = sorted((tmp_path / "ckpt").glob("step_*"))
        assert step_dirs
        for d in step_dirs:
            obs_doc = observatory.read_obs(d)
            assert obs_doc is not None, d
            manifest = json.loads((d / "MANIFEST.json").read_text())
            on_disk = sum(f.stat().st_size for f in d.glob("*.bin"))
            assert obs_doc["total_stored_bytes"] == \
                _manifest_stored_bytes(manifest) == on_disk, d
        traj = observatory.run_trajectory(tmp_path / "ckpt")
        assert [t["step"] for t in traj] == \
            [int(d.name.split("_")[1]) for d in step_dirs]
        fb = guideline.rate_quality_feedback(traj)
        assert fb["n"] == len(traj)
        assert fb["latest_ratio"] == traj[-1]["ratio"] > 0


# ------------------------------------------------------- overhead guard --


@jax.jit
def _dense_step(state, batch):
    # big enough that one step is O(ms) — the quantity the guard bounds is
    # relative overhead, and µs-scale steps would drown it in timer noise
    t = jnp.float32(jnp.asarray(batch["tokens"]).mean()) / 100.0

    def loss_fn(w):
        return jnp.mean((w @ w - t) ** 2)

    loss, g = jax.value_and_grad(loss_fn)(state["w"])
    return {"w": state["w"] - 1e-3 * g}, {"loss": loss}


def _timed_block(tmp_path, tag: str, steps: int = 40) -> list:
    ckpt = CheckpointManager(tmp_path / f"ck_{tag}", async_save=False)
    pipe = TokenPipeline(DataConfig(vocab=100, seq_len=8, global_batch=4,
                                    seed=3))
    lcfg = loop_lib.LoopConfig(total_steps=steps, ckpt_every=10**9,
                               log_every=0)
    _, res = loop_lib.run(_dense_step,
                          {"w": jnp.zeros((192, 192), jnp.float32)},
                          pipe, ckpt, lcfg)
    return res.step_s[5:]  # drop per-block warmup samples


def test_overhead_guard(tmp_path):
    """Enabled observability must stay within 3% of the disabled step wall
    (plus a 100 µs timer-noise floor).  Alternating blocks + medians keep
    the comparison robust to background load on shared CI runners."""
    obs_metrics.disable()
    obs_trace.disable()
    _timed_block(tmp_path, "warm", steps=10)  # jit compile, page-in
    dis: list = []
    en: list = []
    for trial in range(3):
        obs_metrics.disable()
        obs_trace.disable()
        dis.extend(_timed_block(tmp_path, f"d{trial}"))
        obs_metrics.enable()
        obs_trace.enable()
        en.extend(_timed_block(tmp_path, f"e{trial}"))
    obs_metrics.disable()
    obs_trace.disable()
    obs_trace.clear()
    med_d = statistics.median(dis)
    med_e = statistics.median(en)
    assert med_e <= med_d * 1.03 + 1e-4, \
        f"obs overhead: disabled p50 {med_d * 1e3:.3f}ms -> " \
        f"enabled p50 {med_e * 1e3:.3f}ms"
