import os

if __name__ == "__main__":
    # entry-point only — see the matching guard in dryrun.py
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=512 "
        "--xla_disable_hlo_passes=while-loop-invariant-code-motion "
        + os.environ.get("XLA_FLAGS", "")
    )

"""Honest roofline costing (companion to dryrun.py).

XLA's HloCostAnalysis counts while-loop bodies ONCE regardless of trip
count (verified empirically — flops(L=2 scan) == flops(L=8 scan)), so the
production dry-run's flops/bytes/collectives wildly undercount scanned
models. This runner derives per-cell costs that are correct by
construction:

  1. lower the *same* step with the layer scan UNROLLED at n_layers in
     {1, 2} (repro.models.flags.costing), flash/linear-attention chunk
     loops widened to one trip — every op is then visible to the cost
     model exactly once per execution;
  2. per-layer cost = c(2) - c(1); fixed cost = 2*c(1) - c(2);
     extrapolate linearly to the real depth;
  3. train cells: the optimizer update is costed separately (it runs once
     per step, the fwd+bwd runs `microbatches` times):
         total = k * [fb(1) + (L-1) * dfb] + opt(L)
  4. linear-time archs (rwkv6, hymba) at 32k prefill are costed at
     T_c = 4096 (single linear-attention chunk) and scaled by T/T_c —
     exact for every linear-in-T op; hymba's 3 *global* attention layers
     are quadratic in T, so their share is undercounted ~(T/T_c)x;
     documented in EXPERIMENTS.md §Roofline (< 15% of that cell's flops).

AOT lowering never allocates, so the unrolled full-attention tensors
(e.g. (B, H, 32k, 32k) f32) are shape metadata only.

Writes experiments/costrun/<arch>__<shape>__<mesh>.json.
"""

import argparse
import json
import logging
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import registry
from repro.dist import sharding
from repro.launch.dryrun import _ensure_cli_logging, collective_bytes
from repro.launch.mesh import make_production_mesh
from repro.models import flags
from repro.models import layers as L
from repro.obs import metrics as obs_metrics
from repro.train import step as step_lib

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "costrun"

_log = logging.getLogger("repro.launch.costrun")

LINEAR_FAMILIES = {"ssm", "hybrid"}


def _cost_of(lowered) -> dict:
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "collective": float(sum(coll.values())),
    }


L_LO, L_HI = 2, 4  # L=1 lowers hit special-case fusions; 2->4 is stable


def _combine(c_lo: dict, c_hi: dict, layers: int, mult: float = 1.0) -> dict:
    """Linear-in-depth extrapolation with non-negativity clamps (XLA's
    fusion choices can make byte counts mildly non-monotone)."""
    out = {}
    for k in c_lo:
        d = max((c_hi[k] - c_lo[k]) / (L_HI - L_LO), 0.0)
        base = max(c_lo[k] - d * L_LO, 0.0)
        out[k] = (base + d * layers) * mult
    return out


def _scaled_cfg(cfg, n_layers: int):
    kw = {"n_layers": n_layers}
    if cfg.n_encoder_layers:
        kw["n_encoder_layers"] = n_layers
    return cfg.scaled(**kw)


def _lower_train(cfg, shape, mesh, rules, batch: int):
    model = registry.build_model(cfg)
    extra = ("prefix",) if cfg.family == "vlm" else (
        ("frames",) if cfg.family == "audio" else ())
    scfg = step_lib.TrainStepConfig(microbatches=1, param_dtype=jnp.bfloat16)
    _, jit_step, (state_abs, _) = step_lib.build_train_step(
        model, mesh, rules, scfg, extra_keys=extra)
    batch_abs = dict(registry.input_specs(cfg, shape, batch_override=batch))
    return jit_step(batch_abs).lower(state_abs, batch_abs)


def _lower_opt(cfg, mesh, rules):
    from repro.optim import adamw

    model = registry.build_model(cfg)
    p_abs = step_lib.abstract_params(model.specs(), jnp.bfloat16)
    axes = step_lib.logical_axes(model.specs())
    p_shard = sharding.tree_shardings(axes, p_abs, mesh, rules)
    opt_abs = {"m": jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), p_abs),
               "v": jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), p_abs),
               "step": jax.ShapeDtypeStruct((), jnp.int32)}

    def upd(params, opt, grads):
        return adamw.apply_updates(params, opt, grads, jnp.float32(1e-4))

    g_abs = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), p_abs)
    return jax.jit(upd).lower(p_abs, opt_abs, g_abs)


def _lower_prefill(cfg, shape, mesh, rules, batch: int, seq: int):
    model = registry.build_model(cfg)
    extra = ("prefix",) if cfg.family == "vlm" else (
        ("frames",) if cfg.family == "audio" else ())
    p_abs = step_lib.abstract_params(model.specs(), jnp.bfloat16)
    axes = step_lib.logical_axes(model.specs())
    p_shard = sharding.tree_shardings(axes, p_abs, mesh, rules)
    batch_abs = {"tokens": jax.ShapeDtypeStruct((batch, seq), jnp.int32)}
    if cfg.family == "vlm":
        batch_abs["prefix"] = jax.ShapeDtypeStruct((batch, cfg.prefix_len, cfg.d_model), jnp.bfloat16)
    if cfg.family == "audio":
        batch_abs["frames"] = jax.ShapeDtypeStruct((batch, cfg.encoder_len, cfg.d_model), jnp.bfloat16)

    def prefill(params, b):
        extras = [b[k] for k in extra]
        return model.forward(params, b["tokens"], *extras)

    return jax.jit(
        prefill,
        in_shardings=(p_shard, jax.tree.map(
            lambda s: sharding.batch_sharding(mesh, len(s.shape)), batch_abs)),
    ).lower(p_abs, batch_abs)


def _lower_decode(cfg, shape, mesh, rules):
    model = registry.build_model(cfg)
    codec = L.KVCodecConfig("blockfloat8" if shape.name == "long_500k" else "none")
    _, jit_step, (p_abs, _) = step_lib.build_serve_step(model, mesh, rules, codec)
    cache_abs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                 for k, v in model.cache_spec(shape.global_batch, shape.seq_len, codec).items()}
    ins = registry.input_specs(cfg, shape)
    return jit_step(cache_abs).lower(p_abs, cache_abs, ins["token"], ins["index"])


def run_cell(arch: str, shape_name: str, multi_pod: bool) -> dict:
    cfg = registry.get_config(arch)
    shape = registry.SHAPES[shape_name]
    ok, why = registry.supports(cfg, shape)
    mesh_name = "multi" if multi_pod else "single"
    cell = {"arch": arch, "shape": shape_name, "mesh": mesh_name, "kind": shape.kind}
    if not ok:
        cell.update(status="skipped", skip_reason=why)
        return cell
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = sharding.DEFAULT_RULES
    t0 = time.time()
    try:
        # linear archs cost long prefills at T_c=4096 and scale linearly
        seq = shape.seq_len
        mult = 1.0
        if shape.kind in ("train", "prefill") and cfg.family in LINEAR_FAMILIES and seq > 4096:
            mult = seq / 4096.0
            seq = 4096
        flags.costing(True, seq_len=seq)
        with jax.set_mesh(mesh):
            if shape.kind == "train":
                # same microbatch napkin as the production dry-run
                dp = mesh.shape.get("pod", 1) * mesh.shape["data"]
                b_local = max(shape.global_batch // dp, 1)
                tp = mesh.shape.get("model", 1)
                h_loc = cfg.n_heads // tp if cfg.n_heads % tp == 0 else cfg.n_heads
                dff_loc = cfg.d_ff // tp if cfg.d_ff % tp == 0 else cfg.d_ff
                s = shape.seq_len
                attn_quad = h_loc * (s * s if s <= 8192 else s * 2048) * 6
                per_elem = (cfg.n_layers * s * cfg.d_model * 2
                            + attn_quad + s * (dff_loc * 6 + cfg.d_model * 20))
                k = 1
                while per_elem * b_local / k > 6e9 and k < b_local:
                    k *= 2
                micro_batch = max(shape.global_batch // k, dp)
                import dataclasses as _dc

                shp = _dc.replace(shape, seq_len=seq)
                c1 = _cost_of(_lower_train(_scaled_cfg(cfg, L_LO), shp, mesh, rules, micro_batch))
                c2 = _cost_of(_lower_train(_scaled_cfg(cfg, L_HI), shp, mesh, rules, micro_batch))
                o1 = _cost_of(_lower_opt(_scaled_cfg(cfg, L_LO), mesh, rules))
                o2 = _cost_of(_lower_opt(_scaled_cfg(cfg, L_HI), mesh, rules))
                opt = _combine(o1, o2, cfg.n_layers)
                full = _combine(c1, c2, cfg.n_layers, mult)
                # fwd+bwd repeats k times; the optimizer update runs once
                # (clamp: XLA fuses the fused-step better than opt alone,
                # so the subtraction can go mildly negative on bytes)
                total = {key: k * max(full[key] - opt[key], 0.0) + opt[key]
                         for key in full}
                cell["microbatches"] = k
            elif shape.kind == "prefill":
                c1 = _cost_of(_lower_prefill(_scaled_cfg(cfg, L_LO), shape, mesh, rules,
                                             shape.global_batch, seq))
                c2 = _cost_of(_lower_prefill(_scaled_cfg(cfg, L_HI), shape, mesh, rules,
                                             shape.global_batch, seq))
                total = _combine(c1, c2, cfg.n_layers, mult)
            else:
                c1 = _cost_of(_lower_decode(_scaled_cfg(cfg, L_LO), shape, mesh, rules))
                c2 = _cost_of(_lower_decode(_scaled_cfg(cfg, L_HI), shape, mesh, rules))
                total = _combine(c1, c2, cfg.n_layers)
        cell.update(status="ok", compile_s=round(time.time() - t0, 1),
                    n_devices=mesh.devices.size,
                    flops_per_device=total["flops"],
                    bytes_per_device=total["bytes"],
                    collective_bytes_per_device=total["collective"],
                    t_scale=mult)
        obs_metrics.event("costrun.cell", arch=arch, shape=shape_name,
                          mesh=mesh_name, status="ok",
                          compile_s=cell["compile_s"],
                          flops_per_device=total["flops"],
                          bytes_per_device=total["bytes"],
                          collective_bytes_per_device=total["collective"],
                          t_scale=mult)
        _log.info("[%s x %s x %s] cost ok in %ss flops/dev=%.3e "
                  "bytes/dev=%.3e coll/dev=%.3e", arch, shape_name, mesh_name,
                  cell["compile_s"], total["flops"], total["bytes"],
                  total["collective"])
    except Exception as e:  # noqa: BLE001
        cell.update(status="error", error=f"{type(e).__name__}: {e}",
                    traceback=traceback.format_exc()[-1500:])
        obs_metrics.event("costrun.error", arch=arch, shape=shape_name,
                          mesh=mesh_name, error=cell["error"])
        _log.error("[%s x %s x %s] COST FAILED: %s",
                   arch, shape_name, mesh_name, cell["error"])
    finally:
        flags.costing(False)
    return cell


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(registry.ARCH_IDS))
    ap.add_argument("--shape", choices=list(registry.SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi"], default="single")
    ap.add_argument("--metrics-dir", default=None,
                    help="also append per-cell records to DIR/metrics.jsonl")
    args = ap.parse_args(argv)
    _ensure_cli_logging()
    if args.metrics_dir is not None:
        mdir = Path(args.metrics_dir)
        mdir.mkdir(parents=True, exist_ok=True)
        obs_metrics.enable(mdir / "metrics.jsonl")
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    archs = [args.arch] if args.arch else list(registry.ARCH_IDS)
    shapes = [args.shape] if args.shape else list(registry.SHAPES)
    fails = 0
    for arch in archs:
        for shape in shapes:
            cell = run_cell(arch, shape, args.mesh == "multi")
            tag = f"{arch}__{shape}__{cell['mesh']}"
            (OUT_DIR / f"{tag}.json").write_text(json.dumps(cell, indent=1))
            fails += cell["status"] == "error"
    if obs_metrics.enabled():
        obs_metrics.export_snapshot(final=True)
    return 1 if fails else 0


if __name__ == "__main__":
    sys.exit(main())
