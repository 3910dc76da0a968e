import os

if __name__ == "__main__":
    # Entry-point only: forcing 512 host devices must happen before jax
    # initializes, and must NOT leak into processes that merely import this
    # module for collective_bytes / run_cell (tests, costrun, benchmarks).
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=512 "
        # XLA:CPU's while-loop-invariant-code-motion hoists a *wholesale f32
        # convert* of the bf16 remat-carry stash out of the backward loop
        # (trading 2x stash memory to avoid per-iteration converts — sensible
        # for CPU caches, catastrophic for HBM accounting). The TPU pipeline is
        # driven by an HBM-aware scheduler instead; disabling the pass here
        # makes the CPU dry-run's memory_analysis() faithful to the TPU target.
        "--xla_disable_hlo_passes=while-loop-invariant-code-motion "
        + os.environ.get("XLA_FLAGS", "")
    )

"""Multi-pod dry-run: prove the distribution config is coherent without
hardware.

For every (architecture x input shape x mesh) cell this lowers + compiles
the real train_step / serve_step against ShapeDtypeStruct inputs on the
production mesh (16x16 single-pod, 2x16x16 multi-pod), prints
``memory_analysis()`` (fits?) and ``cost_analysis()`` (FLOPs/bytes), parses
the post-SPMD HLO for collective bytes, and writes one JSON per cell into
``experiments/dryrun/`` for the roofline analysis.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen1.5-110b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""

import argparse
import json
import logging
import re
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import registry
from repro.dist import sharding
from repro.launch.mesh import make_production_mesh
from repro.models import layers as L
from repro.obs import metrics as obs_metrics
from repro.train import step as step_lib

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"

_log = logging.getLogger("repro.launch.dryrun")


def _ensure_cli_logging() -> None:
    """CLI entry points keep their human-readable output by routing the
    ``repro.launch`` logger to stderr; library callers (tests, costrun)
    inherit whatever handler config the host process set up."""
    root = logging.getLogger("repro.launch")
    if not root.handlers:
        h = logging.StreamHandler()
        h.setFormatter(logging.Formatter("%(message)s"))
        root.addHandler(h)
        root.setLevel(logging.INFO)

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "c128": 16}

_SHAPE_RE = re.compile(r"(pred|s8|u8|s16|u16|bf16|f16|s32|u32|f32|s64|u64|f64|c64|c128)\[([\d,]*)\]")


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Sum result-shape bytes of every collective op in the (SPMD) HLO."""
    out = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        # result types appear left of '= <space> op-name('
        m = re.search(r"=\s*((?:\([^)]*\))|(?:\S+))\s+(" + "|".join(_COLLECTIVES) + r")\(", s)
        if not m:
            continue
        type_str, op = m.group(1), m.group(2)
        nbytes = 0
        for dt, dims in _SHAPE_RE.findall(type_str):
            n = 1
            if dims:
                for d in dims.split(","):
                    n *= int(d)
            nbytes += n * _DTYPE_BYTES[dt]
        out[op] += nbytes
    return out


def _decode_cache_abs(model, cfg, shape, codec, batch):
    return {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
            for k, v in model.cache_spec(batch, shape.seq_len, codec).items()}


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             rules=sharding.DEFAULT_RULES, verbose: bool = True,
             grad_comp: bool = False) -> dict:
    cfg = registry.get_config(arch)
    shape = registry.SHAPES[shape_name]
    ok, why = registry.supports(cfg, shape)
    mesh_name = "multi" if multi_pod else "single"
    cell = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
            "kind": shape.kind, "seq_len": shape.seq_len,
            "global_batch": shape.global_batch}
    if not ok:
        cell["status"] = "skipped"
        cell["skip_reason"] = why
        return cell

    mesh = make_production_mesh(multi_pod=multi_pod)
    model = registry.build_model(cfg)
    t0 = time.time()
    try:
        with jax.set_mesh(mesh):
            if shape.kind == "train":
                from repro.dist.collectives import GradCompressionConfig

                # napkin for the microbatch count: per-microbatch live set =
                # remat layer-boundary checkpoints (L*S*d*2B) + one layer's
                # attention residuals (h_local * S^2 * 6B materialized path, or
                # S*chunk*6B flash path) + MLP residuals. Budget ~6 GiB.
                dp = mesh.shape.get("pod", 1) * mesh.shape["data"]
                tp = mesh.shape.get("model", 1)
                b_local = max(shape.global_batch // dp, 1)
                h_loc = cfg.n_heads // tp if cfg.n_heads % tp == 0 else cfg.n_heads
                dff_loc = cfg.d_ff // tp if cfg.d_ff % tp == 0 else cfg.d_ff
                s = shape.seq_len
                attn_quad = h_loc * (s * s if s <= 8192 else s * 2048) * 6
                per_elem = (cfg.n_layers * s * cfg.d_model * 2
                            + attn_quad + s * (dff_loc * 6 + cfg.d_model * 20))
                k = 1
                while per_elem * b_local / k > 6e9 and k < b_local:
                    k *= 2
                scfg = step_lib.TrainStepConfig(
                    grad_comp=GradCompressionConfig(enabled=grad_comp and multi_pod),
                    microbatches=k,
                    param_dtype=jnp.bfloat16,
                )
                cell["microbatches"] = k
                extra = ()
                if cfg.family == "vlm":
                    extra = ("prefix",)
                elif cfg.family == "audio":
                    extra = ("frames",)
                _, jit_step, (state_abs, _) = step_lib.build_train_step(
                    model, mesh, rules, scfg, extra_keys=extra)
                batch_abs = registry.input_specs(cfg, shape)
                lowered = jit_step(batch_abs).lower(state_abs, batch_abs)
            else:
                codec = L.KVCodecConfig(
                    "blockfloat8" if shape.name == "long_500k" else "none")
                if shape.kind == "prefill":
                    # prefill lowers the full forward pass (logits over S)
                    extra = ()
                    if cfg.family == "vlm":
                        extra = ("prefix",)
                    elif cfg.family == "audio":
                        extra = ("frames",)
                    p_abs = step_lib.abstract_params(model.specs(), jnp.bfloat16)
                    axes = step_lib.logical_axes(model.specs())
                    p_shard = sharding.tree_shardings(axes, p_abs, mesh, rules)
                    batch_abs = registry.input_specs(cfg, shape)

                    def prefill(params, batch):
                        extras = [batch[k] for k in extra]
                        logits = model.forward(params, batch["tokens"], *extras)
                        # serving semantic: prefill materializes the KV state
                        # and only the LAST position's logits feed sampling —
                        # keeping (B, S, V) alive is pure waste (§Perf)
                        return logits[:, -1, :]

                    lowered = jax.jit(
                        prefill,
                        in_shardings=(p_shard, jax.tree.map(
                            lambda s: sharding.batch_sharding(mesh, len(s.shape)), batch_abs)),
                        out_shardings=sharding.batch_sharding(mesh, 2),
                    ).lower(p_abs, batch_abs)
                else:  # decode
                    _, jit_step, (p_abs, _) = step_lib.build_serve_step(
                        model, mesh, rules, codec)
                    cache_abs = _decode_cache_abs(model, cfg, shape, codec,
                                                  shape.global_batch)
                    ins = registry.input_specs(cfg, shape)
                    lowered = jit_step(cache_abs).lower(
                        p_abs, cache_abs, ins["token"], ins["index"])

            compiled = lowered.compile()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
        coll = collective_bytes(hlo)
        n_dev = mesh.devices.size
        cell.update({
            "status": "ok",
            "compile_s": round(time.time() - t0, 1),
            "n_devices": n_dev,
            "flops_per_device": cost.get("flops", 0.0),
            "bytes_accessed_per_device": cost.get("bytes accessed", 0.0),
            "collective_bytes_per_device": coll,
            "collective_total": sum(coll.values()),
            "memory": {
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "alias_bytes": mem.alias_size_in_bytes,
                "code_bytes": mem.generated_code_size_in_bytes,
            },
        })
        peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
        cell["peak_bytes_per_device"] = int(peak)
        cell["fits_16gb"] = bool(peak < 16 * 2**30)
        if shape.kind == "train":
            # cross-pod gradient wire accounting, with vs without the
            # compressed hop (paper thesis applied to the DCN: the savings
            # figure is what justifies the int8 wire format)
            from repro.dist.collectives import (GradCompressionConfig,
                                                pod_hop_device_bytes,
                                                wire_bytes_per_param)
            from repro.models.spec import param_count

            n_params = param_count(model.specs())
            n_pods = mesh.shape.get("pod", 1)
            gc_off = GradCompressionConfig(enabled=False)
            gc_on = GradCompressionConfig(enabled=True)
            bpp_off = wire_bytes_per_param(gc_off)
            bpp_on = wire_bytes_per_param(gc_on)
            dev_off = pod_hop_device_bytes(gc_off, n_params, n_pods)
            dev_on = pod_hop_device_bytes(gc_on, n_params, n_pods)
            cell["grad_wire"] = {
                "params": n_params,
                "n_pods": n_pods,
                # per-crossing wire format (pod-count-independent)
                "bytes_per_param": {"off": bpp_off, "on": bpp_on},
                "format_savings_x": round(bpp_off / bpp_on, 2),
                # aggregate per-device DCN bytes at this topology
                "device_hop_bytes": {"off": dev_off, "on": dev_on},
                "device_savings_x": round(dev_off / dev_on, 2) if dev_on else None,
                "grad_comp_lowered": bool(grad_comp and multi_pod),
            }
        # one structured record per cell into the shared metrics JSONL
        # stream (no-op unless repro.obs is enabled, e.g. via --metrics-dir)
        obs_metrics.event(
            "dryrun.cell", arch=arch, shape=shape_name, mesh=mesh_name,
            status="ok", compile_s=cell["compile_s"],
            flops_per_device=cell["flops_per_device"],
            bytes_accessed_per_device=cell["bytes_accessed_per_device"],
            peak_bytes_per_device=cell["peak_bytes_per_device"],
            fits_16gb=cell["fits_16gb"],
            collective_total=cell["collective_total"])
        if verbose:
            _log.info(
                "[%s x %s x %s] OK in %ss  flops/dev=%.3e  peak/dev=%.2fGiB  "
                "coll=%.1fMiB", arch, shape_name, mesh_name, cell["compile_s"],
                cell["flops_per_device"], peak / 2**30,
                sum(coll.values()) / 2**20)
            _log.info("  memory_analysis: %s", cell["memory"])
            _log.info("  cost_analysis: flops=%.3e bytes=%.3e",
                      cell["flops_per_device"], cell["bytes_accessed_per_device"])
            _log.info("  collective_bytes/dev: %s",
                      "  ".join(f"{k}={v/2**20:.2f}MiB" for k, v in coll.items()))
            if "grad_wire" in cell:
                gw = cell["grad_wire"]
                _log.info(
                    "  grad wire (%.1fM params, %d pods): format %s->%.3f "
                    "B/param (%sx); per-device hop %.1fMiB -> %.1fMiB "
                    "(%sx, lowered=%s)", gw["params"] / 1e6, gw["n_pods"],
                    gw["bytes_per_param"]["off"], gw["bytes_per_param"]["on"],
                    gw["format_savings_x"], gw["device_hop_bytes"]["off"] / 2**20,
                    gw["device_hop_bytes"]["on"] / 2**20, gw["device_savings_x"],
                    gw["grad_comp_lowered"])
    except Exception as e:  # noqa: BLE001 — report, don't crash the sweep
        cell["status"] = "error"
        cell["error"] = f"{type(e).__name__}: {e}"
        cell["traceback"] = traceback.format_exc()[-2000:]
        obs_metrics.event("dryrun.error", arch=arch, shape=shape_name,
                          mesh=mesh_name, error=cell["error"])
        if verbose:
            _log.error("[%s x %s x %s] FAILED: %s",
                       arch, shape_name, mesh_name, cell["error"])
    return cell


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(registry.ARCH_IDS))
    ap.add_argument("--shape", choices=list(registry.SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="single")
    ap.add_argument("--all", action="store_true", help="sweep every cell")
    ap.add_argument("--grad-comp", action="store_true",
                    help="enable compressed cross-pod gradient hop")
    ap.add_argument("--out", default=str(OUT_DIR))
    ap.add_argument("--metrics-dir", default=None,
                    help="also append per-cell records to DIR/metrics.jsonl")
    args = ap.parse_args(argv)

    _ensure_cli_logging()
    if args.metrics_dir is not None:
        mdir = Path(args.metrics_dir)
        mdir.mkdir(parents=True, exist_ok=True)
        obs_metrics.enable(mdir / "metrics.jsonl")

    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)

    archs = list(registry.ARCH_IDS) if args.all or not args.arch else [args.arch]
    shapes = list(registry.SHAPES) if args.all or not args.shape else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                cell = run_cell(arch, shape, mp, grad_comp=args.grad_comp)
                tag = f"{arch.replace('/', '_')}__{shape}__{'multi' if mp else 'single'}"
                if args.grad_comp:
                    tag += "__gradcomp"
                (out_dir / f"{tag}.json").write_text(json.dumps(cell, indent=2))
                if cell["status"] == "error":
                    failures += 1
    _log.info("dry-run complete; %d failures", failures)
    if obs_metrics.enabled():
        obs_metrics.export_snapshot(final=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
