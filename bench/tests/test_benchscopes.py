"""The wire decoder and the stage reduction (``bench/xplane_scopes.py``): on
hand-made planes, on the trace recorded before the program had spans or
scopes (``roundtrip64.xplane.pb``), and on one recorded on a TPU v5e with
them (``roundtrip64_scoped.xplane.pb``, ``bench/record_trace.py``: a 64^3
field through the four phases of SZ, then of ZFP)."""

from pathlib import Path

import pytest

from bench import cells, loop, run, trace_reduce
from bench import xplane_scopes as xs
from bench.xplane_scopes import Event, Line, Plane

DATA = Path(__file__).parent / "data"
OLD = DATA / "roundtrip64.xplane.pb"
SCOPED = DATA / "roundtrip64_scoped.xplane.pb"


@pytest.fixture(scope="module")
def old_planes():
    return xs.read_planes(str(OLD))


@pytest.fixture(scope="module")
def scoped():
    return xs.read(str(SCOPED))


def test_decoder_agrees_with_profile_data_event_for_event(old_planes):
    from jax.profiler import ProfileData

    theirs = list(ProfileData.from_file(str(OLD)).planes)
    assert [p.name for p in old_planes] == [p.name for p in theirs]
    for mine, plane in zip(old_planes, theirs):
        for line, other in zip(mine.lines, plane.lines):
            assert line.name == other.name
            assert [(e.name, e.start_ns, e.duration_ns) for e in line.events] == \
                [(e.name, e.start_ns, e.duration_ns) for e in other.events]
    (tpu,) = [p for p in old_planes if p.name == "/device:TPU:0"]
    (ops,) = [line for line in tpu.lines if line.name == trace_reduce.OPS_LINE]
    assert len(ops.events) == 5730
    assert ops.events[0].tf_op == "jit(_pad)/pad:"


def test_decoded_planes_read_as_the_harness_reads_them(old_planes):
    """Every number the harness reports comes out the same from the decode."""
    theirs = trace_reduce.read(str(OLD))
    mine = xs.summarize(old_planes)
    s = mine.summary
    assert (s.spans, s.busy, s.op_ns) == (theirs.spans, theirs.busy, theirs.op_ns)
    assert s.top_ops() == theirs.top_ops() and s.idle_gaps() == theirs.idle_gaps()
    w = loop.Window(phase_s={"compress": 0.5, "fetch": 0.25, "upload": 1.0, "decompress": 0.4},
                    seconds=2.5, snapshots=1, ops=2, raw_bytes=2 * 64**3 * 4,
                    stream_bytes=300_000, counted={}, kept={})
    peak = cells.peak("TPU v5 lite")
    for codec in ("tpu-sz", "tpu-zfp"):
        a, b = (run.Context(codec, w.raw_bytes, w.stream_bytes, w.phase_s, t, peak) for t in (s, theirs))
        for m in cells.load_benchmark()["per_layer"]:
            assert cells.reader(m["name"])(a) == cells.reader(m["name"])(b), m["name"]
    # no program spans and no scopes before the program had them: all unstaged
    assert mine.program == [] and mine.idle_gaps() == theirs.idle_gaps()
    assert mine.unstaged_share("compress") == pytest.approx(1.0)
    assert set(xs.metrics(mine, w.raw_bytes)) == {"unstaged_share.compress", "unstaged_share.decompress"}


@pytest.mark.parametrize("tf_op, stage", [
    ("jit(fused_compress)/bitpack.compact/jit(searchsorted)/vmap()/while/body/gather:", "bitpack.compact"),
    ("jit(fused_compress)/jit(_fused_encode)/sz_fused_encode/pallas_call:", "sz_fused_encode"),
    ("jit(compress)/sz.predict/jit(pack_codes)/bitpack.pack/add:", "bitpack.pack"),
    ("jit(gather)/gather:", xs.UNSTAGED),
    ("", xs.UNSTAGED),
])
def test_stage_is_innermost_listed_name(tf_op, stage):
    assert xs.stage_of(tf_op) == stage


def test_attribute_splits_the_busy_union():
    """A loop [0, 100) whose body op [10, 40) is staged; an op [90, 120)
    overlapping the loop's end; a lone op [200, 210)."""
    ops = [(0, 100, "unstaged"), (10, 40, "bitpack.compact"), (90, 120, "sz_fused_encode"),
           (200, 210, "unstaged")]
    got = xs.attribute(ops)
    assert got == {"unstaged": [(0, 10), (40, 90), (200, 210)], "bitpack.compact": [(10, 40)],
                   "sz_fused_encode": [(90, 120)]}
    total = sum(e - s for iv in got.values() for s, e in iv)
    assert total == sum(e - s for s, e in trace_reduce.merge((s, e) for s, e, _ in ops))


def _hand_planes():
    host = Plane("/host:CPU", [Line("main", [
        Event("bench.compress:vx", 0, 100, ""), Event("repro/api.compress", 2, 96, ""),
        Event("repro/sync.total_bits", 60, 30, ""), Event("bench.upload:vx", 200, 100, ""),
        Event("repro/bitpack.from_storage", 205, 90, ""), Event("repro/bitpack.extend", 210, 82, "")])])
    dev = Plane("/device:TPU:0", [
        Line(trace_reduce.OPS_LINE, [Event("%fusion.1", 10, 40, "jit(f)/bitpack.compact/gather:"),
                                     Event("%sz_fused_encode.1", 50, 5, "jit(f)/sz_fused_encode/pallas_call:"),
                                     Event("%copy", 55, 5, ""), Event("%copy.2", 95, 5, "jit(f)/copy:"),
                                     Event("%copy.1", 270, 10, "")]),
        Line(trace_reduce.MODULES_LINE, [Event("jit_f(1)", 0, 300, "")])])
    return [host, dev]


def test_hand_made_stages_spans_and_gaps():
    st = xs.summarize(_hand_planes())
    assert st.split("compress") == {"bitpack.compact": pytest.approx(40e-9),
                                    "sz_fused_encode": pytest.approx(5e-9),
                                    "unstaged": pytest.approx(10e-9)}
    assert st.unstaged_share("compress") == pytest.approx(10 / 55)
    assert st.unstaged_share("upload") == pytest.approx(1.0)
    assert [p.name for p in st.in_phases()] == ["api.compress", "sync.total_bits",
                                                "bitpack.from_storage", "bitpack.extend"]
    assert st.syncs_per_op() == 1.0
    assert st.program_s("upload") == {"bitpack.from_storage": pytest.approx(90e-9),
                                      "bitpack.extend": pytest.approx(82e-9)}
    gaps = st.idle_gaps()
    old = st.summary.idle_gaps()
    assert [g[1] for g in gaps] == [g[1] for g in old]
    assert [g[0] for g in gaps] == ["between spans", "compress:vx/sync.total_bits",
                                    "upload:vx/bitpack.extend", "compress:vx/api.compress"]
    assert [g[0] for g in old] == ["between spans", "compress:vx", "upload:vx", "compress:vx"]
    m = xs.metrics(st, 10**9)
    assert m["sz.compact_gbps"] == pytest.approx(1e9 / 40e-9 / 1e9)
    assert m["host_syncs_per_op"] == 1.0 and "sz.pack_gbps" not in m


# Stages the 64^3 round trip runs on the chip: SZ's fused path, ZFP's fused kernels.
RAN = {"bitpack.compact", "sz_fused_encode", "sz_fused.disassemble", "sz_fused_decode",
       "zfp_fused_encode", "zfp_fused_decode"}


def test_scoped_trace_stages_sum_to_busy(scoped):
    theirs = trace_reduce.read(str(SCOPED))
    s = scoped.summary
    assert (s.spans, s.busy, s.op_ns) == (theirs.spans, theirs.busy, theirs.op_ns)
    assert "jit_fused_compress/sz_fused_encode.1" in s.op_ns  # kernel names reach the op names
    ran = {stage for phase in loop.PHASES for stage in scoped.split(phase)}
    assert RAN <= ran
    for stage in RAN:
        assert sum(scoped.stage_s(phase, stage) for phase in loop.PHASES) > 0, stage
    for phase in loop.PHASES:
        assert sum(scoped.split(phase).values()) == pytest.approx(scoped.summary.busy_s(phase), rel=0.01)


def test_scoped_trace_program_spans(scoped):
    spans = scoped.summary.spans
    assert [s.phase for s in spans] == list(loop.PHASES) * 2  # SZ, then ZFP
    assert scoped.program and scoped.in_phases() == scoped.program
    for p in scoped.program:  # every program span nests inside a harness span
        assert any(s.start <= p.start and p.end <= s.end for s in spans), p
    sz_op = (spans[0].start, spans[3].end)
    syncs = [p for p in scoped.program if p.name.startswith("sync.") and sz_op[0] <= p.start < sz_op[1]]
    assert syncs
    assert {p.name for p in scoped.program} == {
        "api.compress", "api.decompress", "sync.total_bits", "sync.copy", "bitpack.to_storage",
        "bitpack.from_storage", "bitpack.extend", "zfp.carve", "zfp.uncarve"}


def test_scoped_trace_gaps_and_metrics(scoped):
    gaps, old = scoped.idle_gaps(10**6), scoped.summary.idle_gaps(10**6)
    assert [g[1] for g in gaps] == [g[1] for g in old]
    assert all(g[0] == o[0] or g[0].startswith(o[0] + "/") for g, o in zip(gaps, old))
    assert any("/" in g[0] for g in gaps)
    m = xs.metrics(scoped, 2 * 64**3 * 4)
    assert {"sz.compact_gbps", "sz.disassemble_gbps", "unstaged_share.compress",
            "unstaged_share.decompress", "host_syncs_per_op"} <= set(m)
    assert m["host_syncs_per_op"] == 2.0  # 4 in the SZ operation, 0 in the ZFP one
    assert all(v > 0 for v in m.values())
