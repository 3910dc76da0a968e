"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch minicpm-2b \
        --steps 1000 --batch 32 --seq 512 --ckpt-dir /ckpt \
        [--smoke] [--grad-comp] [--lossy-ckpt]

On a real fleet this binary runs per-host under the cluster scheduler
(jax.distributed.initialize picks up the coordination env); in-container it
drives the same code path on the host mesh. The loop resumes from the
newest checkpoint automatically; SIGTERM checkpoints and exits cleanly.
"""

from __future__ import annotations

import argparse
from pathlib import Path
from typing import Optional

import jax
import jax.numpy as jnp

from repro import compile_cache
from repro.checkpoint.manager import CheckpointManager, CodecPolicy
from repro.configs import registry
from repro.data.tokens import DataConfig, TokenPipeline
from repro.dist.collectives import GradCompressionConfig
from repro.launch.mesh import make_host_mesh
from repro.models.spec import param_count
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.train import loop as loop_lib
from repro.train import step as step_lib


def _leaf_entries(state, min_bytes: int):
    """(key, leaf) pairs of the float leaves worth snapshotting."""
    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
        if not hasattr(leaf, "dtype") or not jnp.issubdtype(leaf.dtype, jnp.floating):
            continue
        if leaf.ndim < 1 or leaf.nbytes < min_bytes:
            continue
        out.append((jax.tree_util.keystr(path), leaf))
    return out


def build_insitu_hook(mesh, out_dir: str, eb: float, min_bytes: int = 1 << 20,
                      arena: bool = True, overlap: bool = True, slots: int = 2):
    """Snapshot hook for ``loop_lib.LoopConfig.snapshot_hook``: compress
    every float leaf >= ``min_bytes`` shard-locally (halo-exchanged TPU-SZ)
    and persist the streams through the checkpoint manager.  The raw leaves
    never gather to host — only compressed bytes cross the PCIe/DCN
    boundary, the paper's in-situ snapshot story applied to training state.

    ``arena=True`` (default) is the **arena-batched** path: 3-D
    TILE-aligned replicated leaves batch through the fused tile kernel
    (``dist.insitu.plan_kernel_buckets`` -> ``arena.szk_compress_bucket``,
    codec ``arena-szk``); everything else flattens and size-buckets into
    megabatches (``dist.insitu.plan_arena``).  The hook compiles **one
    function per bucket signature, not per leaf** — a snapshot issues
    O(#buckets) launches, one halo permute and one pmax per flat bucket.
    Arena-ineligible leaves (non-leading-dim partitions) fall back to the
    legacy per-leaf path, logged once.  ``arena=False`` is that per-leaf
    path for every leaf — the PR-4 format, kept restorable and selectable
    (``--insitu-per-leaf``).

    ``overlap=True`` (default) makes snapshots **zero-stall**: each bucket
    compresses into a snapshot-owned (staged, donated) device buffer, the
    hook hands *deferred* host fetches (``PendingHostArena``) to the
    manager's background drain queue and returns immediately — the compress
    launches, the D2H copies, the payload encode, and the disk writes all
    hide behind the next train steps.  A two-slot pool
    (``arena.SnapshotSlots``) bounds in-flight device buffers: the hook
    only blocks when ``slots`` snapshots are still draining.  The persisted
    bytes are identical to ``overlap=False`` (the PR-5 synchronous wall,
    kept selectable via ``--insitu-sync``).  The returned hook exposes
    ``hook.wait()`` (drain everything; the loop calls it on exit) and
    ``hook.manager`` / ``hook.slots`` for tests and benchmarks."""
    from repro.core import arena as arena_core
    from repro.dist import insitu

    snap = CheckpointManager(out_dir, keep_last=2, async_save=overlap,
                             max_in_flight=slots)
    pool = arena_core.SnapshotSlots(slots) if (overlap and arena) else None
    _c_launch = obs_metrics.counter("snapshot.launches")
    compiled: dict = {}  # leaf key -> jitted per-leaf compress (or None)
    cache: dict = {"sig": None, "kbuckets": [], "buckets": [], "fns": [],
                   "legacy": []}

    def _spec(leaf):
        return getattr(getattr(leaf, "sharding", None), "spec", None)

    def _legacy_compress(key, leaf, fields) -> None:
        if key not in compiled:
            try:
                insitu.check_eligible(leaf, "sz", mesh, _spec(leaf))
            except (NotImplementedError, ValueError) as e:
                # composed-axis / non-divisible / oversized leaves — say so
                # once instead of silently shrinking the snapshot.  Only the
                # eligibility checks are caught: a compile error below
                # propagates and fails the run.
                print(f"  in-situ snapshot: skipping {key}: {e}")
                compiled[key] = None
                return
            compiled[key] = jax.jit(lambda a, _s=_spec(leaf): insitu.sharded_compress(
                a, "sz", mesh, _s, eb=eb))
        if compiled[key] is None:
            return
        fields[key] = insitu.to_host(compiled[key](leaf))

    def _replan(named) -> None:
        entries = []
        for key, leaf in named:
            spec = _spec(leaf)
            entries.append((key, leaf.shape, leaf.dtype,
                            spec if spec is not None else jax.sharding.PartitionSpec()))
        kbuckets, rest = insitu.plan_kernel_buckets(entries, mesh)
        buckets, skipped = insitu.plan_arena(rest, mesh)
        for key, why in skipped:
            print(f"  in-situ snapshot: {key} not arena-eligible ({why}); "
                  "using the per-leaf path")
        # one compiled function per bucket *signature* — reused for every
        # later snapshot of the same state tree
        fns = [jax.jit(lambda *ls, _b=b: insitu.sharded_compress_arena(
            list(ls), _b, mesh, eb)) for b in buckets]
        cache.update(kbuckets=kbuckets, buckets=buckets, fns=fns,
                     legacy=[k for k, _ in skipped])

    def hook(step: int, state) -> None:
        named = _leaf_entries(state, min_bytes)
        fields = {}
        acquired = False
        try:
            if arena:
                sig = tuple((k, tuple(l.shape), str(l.dtype)) for k, l in named)
                if cache["sig"] != sig:
                    _replan(named)
                    cache["sig"] = sig
                by_key = dict(named)
                if pool is not None:
                    pool.acquire()  # backpressure: <= `slots` arenas on device
                    acquired = True
                for k, b in enumerate(cache["kbuckets"]):
                    # dispatch-only span: the launch is async, so this
                    # times bucket dispatch, not the kernel itself
                    with obs_trace.span("snapshot.bucket", kind="szk",
                                        bucket=k, n_fields=len(b.names)):
                        a = arena_core.szk_compress_bucket(
                            [by_key[nm] for nm in b.names], b, eb)
                        fields[f"karena{k:03d}"] = (
                            arena_core.to_host_async(a, b,
                                                     codec=arena_core.CODEC_SZK)
                            if overlap else
                            arena_core.to_host(a, b,
                                               codec=arena_core.CODEC_SZK))
                    _c_launch.inc()
                for k, (b, fn) in enumerate(zip(cache["buckets"], cache["fns"])):
                    with obs_trace.span("snapshot.bucket", kind="flat",
                                        bucket=k, n_fields=len(b.names)):
                        stream = fn(*[by_key[nm] for nm in b.names])
                        fields[f"arena{k:03d}"] = (
                            insitu.arena_to_host_async(stream) if overlap
                            else insitu.arena_to_host(stream))
                    _c_launch.inc()
                for key in cache["legacy"]:
                    _legacy_compress(key, by_key[key], fields)
                    _c_launch.inc()
            else:
                for key, leaf in named:
                    _legacy_compress(key, leaf, fields)
                    _c_launch.inc()
            if not fields:
                if acquired:
                    pool.release()
                return
            n_leaves = sum(len(v.names) if hasattr(v, "names") else 1
                           for v in fields.values())
            extra = {"eb": eb, "n_fields": n_leaves, "arena": bool(arena)}
            if overlap:
                release = pool.release if acquired else (lambda *_: None)

                def _done(s, _n=n_leaves, _g=len(fields), _rel=release):
                    _rel(s)  # slot recycles only after the drain finished
                    res = snap.last_result
                    ratio = (f", {res.ratio:.2f}x on-device compression"
                             if res is not None and res.step == s else "")
                    print(f"  in-situ snapshot step {s}: {_n} fields in "
                          f"{_g} payload groups drained in background{ratio}")

                snap.save(step, fields, extra=extra, on_complete=_done)
                acquired = False  # the drain queue now owns the release
            else:
                snap.save(step, fields, extra=extra)
                res = snap.wait()
                print(f"  in-situ snapshot step {step}: {n_leaves} fields in "
                      f"{len(fields)} payload groups, "
                      f"{res.ratio:.2f}x on-device compression")
        except BaseException:
            if acquired:
                pool.release()
            raise

    hook.wait = snap.wait
    hook.manager = snap
    hook.slots = pool
    return hook


def _setup_obs(args) -> Optional[Path]:
    """Wire --metrics-dir / --trace into the process-global observability
    layer.  Returns the output dir (None when observability is off)."""
    if args.metrics_dir is None and not args.trace:
        return None
    out = Path(args.metrics_dir if args.metrics_dir is not None
               else args.ckpt_dir)
    out.mkdir(parents=True, exist_ok=True)
    # metrics always come on with observability (the registry is the cheap
    # half); the JSONL sink only attaches when --metrics-dir names a home
    obs_metrics.enable(out / "metrics.jsonl" if args.metrics_dir is not None
                       else None)
    if args.trace:
        obs_trace.enable()
    return out


def _finish_obs(out: Optional[Path], args, tag: str) -> None:
    """End-of-run export: final metrics line + human summary, and the
    Chrome-trace JSON (one track per thread — open in chrome://tracing)."""
    if out is None:
        return
    obs_metrics.export_snapshot(final=True)
    print(obs_metrics.summary())
    if args.trace:
        p = obs_trace.export(out / f"trace_{tag}.json")
        print(f"  trace written to {p} ({len(obs_trace.TRACER.events)} spans)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(registry.ARCH_IDS), required=True)
    ap.add_argument("--smoke", action="store_true", help="reduced config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--schedule", default=None, help="cosine|wsd (default per arch)")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--grad-comp", action="store_true")
    ap.add_argument("--lossy-ckpt", action="store_true")
    ap.add_argument("--insitu-snapshot", action="store_true",
                    help="at every checkpoint, also compress the large state "
                         "leaves *on their devices* (halo-exchanged TPU-SZ "
                         "per shard, dist.insitu) into <ckpt-dir>/fields")
    ap.add_argument("--insitu-eb", type=float, default=1e-3,
                    help="ABS error bound for --insitu-snapshot")
    ap.add_argument("--insitu-per-leaf", action="store_true",
                    help="disable arena batching for --insitu-snapshot: one "
                         "launch + one stream file per leaf (the legacy "
                         "PR-4 format) instead of one per size bucket")
    ap.add_argument("--insitu-sync", action="store_true",
                    help="disable snapshot overlap for --insitu-snapshot: "
                         "block the loop for the full compress + D2H + "
                         "disk-write wall at every snapshot (the PR-5 "
                         "behavior) instead of draining in the background")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--supervise", action="store_true",
                    help="run under train.supervisor.run_supervised: detected "
                         "faults quiesce the checkpoint drain, shrink the "
                         "mesh, restore the newest *valid* snapshot, resume, "
                         "and grow back — instead of crashing the run")
    ap.add_argument("--fault-seed", type=int, default=None,
                    help="with --supervise: inject the canonical seeded "
                         "fault drill (train.faults.FaultPlan.drill)")
    ap.add_argument("--fault-plan", default=None,
                    help="with --supervise: JSON fault plan file "
                         "(FaultPlan.to_json) — exact replay of a prior run")
    ap.add_argument("--fault-lost-pods", type=int, default=0)
    ap.add_argument("--fault-lost-data-rows", type=int, default=0)
    ap.add_argument("--drain-deadline", type=float, default=30.0,
                    help="seconds the supervisor waits for the checkpoint "
                         "drain to quiesce after a fault")
    ap.add_argument("--grow-back-after", type=int, default=None,
                    help="degraded-mesh steps before resharding back onto "
                         "the full mesh (default: stay degraded)")
    ap.add_argument("--metrics-dir", default=None,
                    help="enable run-wide telemetry (repro.obs): counters, "
                         "gauges, step_s/queue-depth histograms exported as "
                         "JSONL lines into <dir>/metrics.jsonl, plus an "
                         "end-of-run summary")
    ap.add_argument("--trace", action="store_true",
                    help="record nested span timers and write Chrome-trace "
                         "JSON (trace_*.json, one track per thread) into "
                         "--metrics-dir (or --ckpt-dir)")
    args = ap.parse_args(argv)
    compile_cache.enable()

    obs_out = _setup_obs(args)
    if args.supervise:
        try:
            return _main_supervised(args)
        finally:
            _finish_obs(obs_out, args, tag="supervised")

    cfg = registry.get_config(args.arch, smoke=args.smoke)
    model = registry.build_model(cfg)
    mesh = make_host_mesh()
    schedule = args.schedule or ("wsd" if args.arch == "minicpm-2b" else "cosine")
    scfg = step_lib.TrainStepConfig(
        peak_lr=args.lr, warmup_steps=max(args.steps // 20, 1),
        total_steps=args.steps, schedule=schedule,
        microbatches=args.microbatches,
        grad_comp=GradCompressionConfig(enabled=args.grad_comp),
    )
    print(f"{cfg.name}: {param_count(model.specs())/1e6:.1f}M params on "
          f"{mesh.devices.size} devices, schedule={schedule}")

    pipe = TokenPipeline(DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                                    global_batch=args.batch))
    extra = {}
    if cfg.family in ("vlm", "audio"):
        from repro.data.tokens import frontend_stub

        kind = "vlm" if cfg.family == "vlm" else "audio"
        extra[("prefix" if kind == "vlm" else "frames")] = jnp.asarray(
            frontend_stub(cfg, args.batch, 0, kind), jnp.bfloat16)

    with jax.set_mesh(mesh):
        state = step_lib.init_state(model, mesh, jax.random.key(0), step_cfg=scfg)
        extra_keys = tuple(extra)
        _, jit_step, _ = step_lib.build_train_step(model, mesh, step_cfg=scfg,
                                                   extra_keys=extra_keys)
        b0 = pipe.batch_at(0)
        batch_abs = {k: jax.ShapeDtypeStruct(v.shape, jnp.int32) for k, v in b0.items()}
        for k, v in extra.items():
            batch_abs[k] = jax.ShapeDtypeStruct(v.shape, v.dtype)
        step = jit_step(batch_abs)

        policy = CodecPolicy(mode="sz_pwrel", eb=1e-4) if args.lossy_ckpt else CodecPolicy()
        ckpt = CheckpointManager(args.ckpt_dir, policy=policy)
        hook = (build_insitu_hook(mesh, f"{args.ckpt_dir}/fields", args.insitu_eb,
                                  arena=not args.insitu_per_leaf,
                                  overlap=not args.insitu_sync)
                if args.insitu_snapshot else None)

        def put(b):
            return {**{k: jnp.asarray(v) for k, v in b.items()}, **extra}

        state, res = loop_lib.run(
            step, state, pipe, ckpt,
            loop_lib.LoopConfig(total_steps=args.steps, ckpt_every=args.ckpt_every,
                                snapshot_hook=hook),
            put_batch=put)
    print(f"done at step {res.final_step}; loss {res.losses[0]:.3f} -> {res.losses[-1]:.3f}"
          f"{' (preempted)' if res.preempted else ''}")
    _finish_obs(obs_out, args, tag="train")
    return 0


def _main_supervised(args) -> int:
    """--supervise: the elastic fault drill / supervised production loop."""
    import functools

    # lazy: the supervisor pulls in faults/elastic; keep the plain path lean
    from repro.train import faults as faults_lib
    from repro.train import supervisor as sup

    cfg = registry.get_config(args.arch, smoke=args.smoke)
    if cfg.family not in ("dense", "moe", "ssm", "hybrid"):
        raise SystemExit("--supervise currently drives token-LM families only "
                         f"(got {cfg.family})")
    model = registry.build_model(cfg)
    mesh = make_host_mesh()
    full_shape = dict(mesh.shape)
    schedule = args.schedule or ("wsd" if args.arch == "minicpm-2b" else "cosine")
    if args.grad_comp and (args.fault_lost_pods or args.fault_lost_data_rows):
        # ef state carries an (n_pods, ...) leading axis — it cannot be
        # restored across a pod-count change (DESIGN.md §10, out of scope)
        raise SystemExit("--supervise with mesh shrink requires grad_comp "
                         "disabled (per-pod error-feedback state does not "
                         "survive a pod-count change)")
    scfg = step_lib.TrainStepConfig(
        peak_lr=args.lr, warmup_steps=max(args.steps // 20, 1),
        total_steps=args.steps, schedule=schedule,
        microbatches=args.microbatches,
        grad_comp=GradCompressionConfig(enabled=args.grad_comp),
    )
    print(f"{cfg.name}: {param_count(model.specs())/1e6:.1f}M params on "
          f"{mesh.devices.size} devices (supervised), schedule={schedule}")

    injector = None
    if args.fault_plan is not None:
        plan = faults_lib.FaultPlan.from_json(Path(args.fault_plan).read_text())
    elif args.fault_seed is not None:
        plan = faults_lib.FaultPlan.drill(
            args.fault_seed, args.steps, args.ckpt_every,
            lost_pods=args.fault_lost_pods,
            lost_data_rows=args.fault_lost_data_rows)
    else:
        plan = None
    if plan is not None:
        injector = faults_lib.FaultInjector(plan, ckpt_dir=args.ckpt_dir)
        print(f"  fault plan: {plan.to_json()}")

    policy = CodecPolicy(mode="sz_pwrel", eb=1e-4) if args.lossy_ckpt else CodecPolicy()
    ckpt = CheckpointManager(
        args.ckpt_dir, policy=policy,
        write_bytes=injector.write_bytes if injector else None,
        fetch_hook=injector.fetch_hook if injector else None)
    if injector is not None:
        injector.manager = ckpt  # deterministic corrupt-newest under async

    builder = functools.partial(
        sup.make_trainer, model, vocab=cfg.vocab, seq_len=args.seq,
        step_cfg=scfg,
        insitu_dir=f"{args.ckpt_dir}/fields" if args.insitu_snapshot else None,
        insitu_eb=args.insitu_eb, insitu_overlap=not args.insitu_sync)
    scfg_sup = sup.SupervisorConfig(
        total_steps=args.steps, ckpt_every=args.ckpt_every,
        drain_deadline_s=args.drain_deadline,
        grow_back_after=args.grow_back_after)
    _, res = sup.run_supervised(builder, full_shape, args.batch, ckpt,
                                scfg_sup, injector=injector)
    shrinks = [t for t in res.transitions if t.kind == "shrink"]
    grows = [t for t in res.transitions if t.kind == "grow"]
    print(f"done at step {res.final_step}; {len(shrinks)} shrink / "
          f"{len(grows)} grow transition(s), "
          f"{sum(t.quarantined for t in shrinks)} snapshot(s) quarantined; "
          f"loss {res.loss_trace[0][1]:.3f} -> {res.loss_trace[-1][1]:.3f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
