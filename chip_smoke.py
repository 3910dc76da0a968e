#!/usr/bin/env python3
"""Smoke of the main path on one TPU chip, through the entry points users call.

    python3 chip_smoke.py              # nyx, hacc, serve + kernel proof, 1 chip
    python3 chip_smoke.py --chips 4    # sharded in-situ round trip on 4 chips

Phases (each prints its checks, then compile seconds, run seconds and the
device's ``peak_bytes_in_use``; data generation is timed on its own line):

* ``nyx``   six 512^3 float32 Nyx-like fields, all resident on the device.
  ``tpu-zfp`` (``get_compressor``, fused kernel) compresses every whole field
  at rate 8 and ``baryon_density`` at rates 2 and 4 too; streams and decodes
  must equal ``repro.core.zfp`` bit for bit on the same device.  ``tpu-sz``
  (fused kernel) runs on each field's 256^3 corner at the loosest and the
  tightest bound of ``benchmarks/guideline_bench.py``: the error bound must
  hold and the stream must equal ``pack_codes(tile_major_flatten(
  lorenzo3d_quantize_ref(x)))``, its decode ``lorenzo3d_reconstruct_ref``.
  Cut: SZ at 256^3, not 512^3 — the packer's int32 bit offsets cap one SZ
  call below 2^26 values.
* ``hacc``  six 1-D HACC-like particle fields (grid 256: 16.7M particles)
  through both compressors' 1-D paths: the SZ error bound, and ZFP streams
  bitwise equal to the core coder's.  Cut: grid 256, not the paper's count.
* ``serve`` starcoder2-3b at its published widths (30 layers, random bf16
  weights from ``--seed``) behind one ``ServingEngine`` with the
  ``blockfloat8`` paged KV pool: 8 requests, 32-token prompts, 16 greedy new
  tokens each, 4 slots, max_len 64 — every request must complete.  One
  ``ops.kvc_attention`` decode step at those widths must match
  ``ref.kvc_decode_attention_ref`` within bf16 tolerance.  Cut: the model's
  sliding window is not in its config.
* ``proof`` the jitted encode and decode of each codec and the engine's
  decode step must lower to programs holding ``tpu_custom_call`` — no phase
  can pass in Pallas interpret mode.

``--chips 4`` runs only the sharded in-situ phase: a 256^3 Nyx field sharded
along z over four devices goes through ``insitu.sharded_compress`` /
``sharded_decompress`` (SZ kernel backend and ZFP); both round trips must
equal the single-device ones on device 0 bit for bit, and the shards must
sit on four distinct devices.

The script refuses to run unless JAX's first device is a TPU.  Any failed
check raises; the last line of standard output is a JSON object naming the
device, printed only when every phase passed.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

NYX_RATES_ALL = (8,)
NYX_RATES_BD = (2, 4, 8)  # benchmarks/guideline_bench.py ZFP_SWEEPS
HACC_RATE = 8
# HACC SZ bounds: fig4b's middle position bound (benchmarks/rate_distortion.py)
# and the same ~2e-5 share of the velocity range
HACC_EB = {"x": 0.005, "y": 0.005, "z": 0.005, "vx": 0.5, "vy": 0.5, "vz": 0.5}
KVC_TOL = 2e-2  # bf16 query/output (tests/test_kernels.py::test_bf16_query)


class _CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (or fetching from
    the persistent cache), summed from its own compile events."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.total = 0.0
        self.cache_hits = 0  # programs the persistent cache supplied
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.total += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class Phase:
    """Prints one phase's lines and times it: compile seconds from
    :class:`_CompileClock`, run seconds = wall - compile - generation."""

    def __init__(self, name: str, clock: _CompileClock):
        self.name, self.clock = name, clock
        self.gen_s = 0.0
        self.checks = 0

    def __enter__(self):
        self.t0, self.c0 = time.perf_counter(), self.clock.total
        return self

    def log(self, msg: str) -> None:
        print(f"[{self.name}] {msg}", flush=True)

    def check(self, ok: bool, msg: str) -> None:
        if not ok:
            raise AssertionError(f"[{self.name}] check failed: {msg}")
        self.checks += 1
        self.log(f"check ok: {msg}")

    def generated(self, seconds: float, what: str) -> None:
        self.gen_s += seconds
        self.log(f"generate_s={seconds:.3f} ({what})")

    def __exit__(self, exc_type, *_):
        if exc_type is not None:
            return False
        import jax

        compile_s = self.clock.total - self.c0
        run_s = time.perf_counter() - self.t0 - compile_s - self.gen_s
        stats = jax.devices()[0].memory_stats() or {}
        self.log(f"compile_s={compile_s:.3f} run_s={run_s:.3f} "
                 f"peak_bytes={stats.get('peak_bytes_in_use')} checks={self.checks}")
        return False


def _equal(a, b) -> bool:
    """Bitwise equality of two arrays, on the device that holds them."""
    import jax.numpy as jnp

    return a.shape == b.shape and a.dtype == b.dtype and bool(jnp.array_equal(a, b))


def _to_device(fields: dict) -> dict:
    import jax

    out = {k: jax.device_put(v) for k, v in fields.items()}
    jax.block_until_ready(list(out.values()))
    return out


def _sz_bounds(field: str) -> tuple:
    from benchmarks.guideline_bench import SZ_SWEEPS

    ebs = [c["eb"] for c in SZ_SWEEPS[field]]
    return max(ebs), min(ebs)


def _check_zfp(ph, comp, x, rate, label):
    """``comp`` (the fused kernel on a TPU) against the core coder
    (``repro.core.zfp``) on the same input and device, bit for bit."""
    import jax.numpy as jnp

    from repro.core.api import get_compressor

    core = get_compressor("tpu-zfp", backend="core")
    r, w = comp.compress(x, rate=rate), core.compress(x, rate=rate)
    ph.check(r.meta["backend"] == "kernel", f"zfp {label} rate={rate}: kernel backend")
    got, want = r.payload["parts"], w.payload["parts"]
    same = len(got) == len(want) and all(
        _equal(g.words, c.words) and _equal(g.emax, c.emax) and _equal(g.gtops, c.gtops)
        for g, c in zip(got, want))
    ph.check(same, f"zfp {label} rate={rate}: words/emax/gtops == core zfp, bitwise")
    xr = comp.decompress(r)
    ph.check(_equal(xr, core.decompress(w)) and bool(jnp.isfinite(xr).all()),
             f"zfp {label} rate={rate}: decode == core decode, bitwise, finite "
             f"(CR {r.ratio:.2f})")


def phase_nyx(clock, n=512, sz_box=(256, 256, 256), fields=None, seed=0,
              backend="auto", rates_all=NYX_RATES_ALL, rates_bd=NYX_RATES_BD):
    import jax.numpy as jnp

    from repro.core import bitpack
    from repro.core.api import get_compressor
    from repro.data import cosmo
    from repro.kernels import ref, sz_fused

    with Phase("nyx", clock) as ph:
        t = time.perf_counter()
        host = cosmo.nyx_fields(n=n, seed=42 + seed)
        if fields is not None:
            host = {k: host[k] for k in fields}
        ph.generated(time.perf_counter() - t, f"{len(host)} fields at {n}^3 on host")
        t = time.perf_counter()
        dev = _to_device(host)
        del host
        ph.log(f"resident: {len(dev)} fields, "
               f"{sum(v.nbytes for v in dev.values()) / 2**30:.3f} GiB "
               f"(transfer {time.perf_counter() - t:.3f} s)")

        zc = get_compressor("tpu-zfp", backend=backend)
        for name, x in dev.items():
            rates = rates_bd if name == "baryon_density" else rates_all
            for rate in rates:
                _check_zfp(ph, zc, x, rate, f"{name} {n}^3")

        sc = get_compressor("tpu-sz", backend=backend)
        bz, by, bx = sz_box
        for name, x in dev.items():
            corner = x[:bz, :by, :bx]
            for eb in _sz_bounds(name):
                r = sc.compress(corner, eb=eb)
                label = f"sz {name} {bz}x{by}x{bx} eb={eb:g}"
                ph.check(r.meta["backend"] == "kernel", f"{label}: kernel backend")
                delta = ref.lorenzo3d_quantize_ref(corner, eb)
                want = bitpack.pack_codes(sz_fused.tile_major_flatten(delta))
                got = r.payload["kpacked"]
                ph.check(_equal(got.words, want.words) and _equal(got.widths, want.widths)
                         and int(got.total_bits) == int(want.total_bits),
                         f"{label}: stream == pack_codes(lorenzo3d_quantize_ref), bitwise")
                xr = sc.decompress(r)
                err = float(jnp.max(jnp.abs(xr - corner)))
                ph.check(err <= eb * (1 + 1e-5),
                         f"{label}: max|x^-x| {err:.6g} <= eb (CR {r.ratio:.2f})")
                ph.check(_equal(xr, ref.lorenzo3d_reconstruct_ref(delta, r.payload["eb_i"])),
                         f"{label}: decode == lorenzo3d_reconstruct_ref, bitwise")


def phase_hacc(clock, grid=256, seed=0, backend="auto", rate=HACC_RATE):
    import jax.numpy as jnp

    from repro.core.api import get_compressor
    from repro.data import cosmo

    with Phase("hacc", clock) as ph:
        t = time.perf_counter()
        snap = cosmo.hacc_particles(grid=grid, seed=7 + seed)
        ph.generated(time.perf_counter() - t,
                     f"{len(snap.fields)} fields of {snap.fields['x'].size} particles")
        dev = _to_device(snap.fields)
        del snap
        sc = get_compressor("tpu-sz", backend=backend)
        zc = get_compressor("tpu-zfp", backend=backend)
        for name, x in dev.items():
            eb = HACC_EB[name]
            r = sc.compress(x, eb=eb)
            xr = sc.decompress(r)
            err = float(jnp.max(jnp.abs(xr - x)))
            ph.check(r.meta["was_1d"] and xr.shape == x.shape and err <= eb * (1 + 1e-5),
                     f"sz {name} 1-D ({x.size}): max|x^-x| {err:.6g} <= eb={eb:g} "
                     f"(CR {r.ratio:.2f})")
            _check_zfp(ph, zc, x, rate, f"{name} 1-D ({x.size})")


def phase_serve(clock, arch="starcoder2-3b", smoke=False, n_requests=8,
                prompt_len=32, max_new=16, slots=4, max_len=64, seed=0,
                attention="auto"):
    """Returns the drained engine (the proof phase lowers its decode step)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import registry
    from repro.kernels import ops, ref
    from repro.models.spec import init_params, param_count
    from repro.serving.engine import EngineConfig, Request, ServingEngine

    with Phase("serve", clock) as ph:
        cfg = registry.get_config(arch, smoke=smoke)
        model = registry.build_model(cfg)
        params = init_params(model.specs(), jax.random.key(seed), jnp.bfloat16)
        jax.block_until_ready(params)
        ph.log(f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
               f"{cfg.n_heads} heads / {cfg.n_kv_heads} kv, vocab {cfg.vocab}, "
               f"{param_count(model.specs()) / 1e9:.3f}B params bf16")
        eng = ServingEngine(model, params, EngineConfig(
            batch_slots=slots, max_len=max_len, codec="blockfloat8", paged=True,
            greedy=True, attention=attention))
        rng = np.random.default_rng(seed)
        reqs = [Request(uid=u, prompt=[int(t) for t in rng.integers(1, cfg.vocab, prompt_len)],
                        max_new_tokens=max_new) for u in range(n_requests)]
        for r in reqs:
            eng.submit(r)
        done = eng.run_until_drained()
        ph.check(done.drained and all(len(r.out_tokens) == max_new for r in reqs),
                 f"{n_requests} requests x {max_new} new tokens completed, engine drained "
                 f"({eng.ticks} ticks, KV pool {eng.cache_nbytes()} bytes)")

        b, h, d = slots, cfg.n_heads, cfg.d_model // cfg.n_heads
        ks = jax.random.split(jax.random.key(seed + 1), 5)
        q = jax.random.normal(ks[0], (b, h, d), jnp.bfloat16)
        kc = jax.random.randint(ks[1], (b, max_len, h, d), -127, 128, jnp.int8)
        vc = jax.random.randint(ks[2], (b, max_len, h, d), -127, 128, jnp.int8)
        ksc = jax.random.uniform(ks[3], (b, max_len, h), jnp.float32, 1e-3, 2e-2)
        vsc = jax.random.uniform(ks[4], (b, max_len, h), jnp.float32, 1e-3, 2e-2)
        index = jnp.asarray(np.linspace(0, max_len - 1, b).astype(np.int32))
        got = ops.kvc_attention(q, kc, ksc, vc, vsc, index).astype(jnp.float32)
        want = ref.kvc_decode_attention_ref(q, kc, ksc, vc, vsc, index).astype(jnp.float32)
        diff = float(jnp.max(jnp.abs(got - want)))
        tol = KVC_TOL * (1.0 + float(jnp.max(jnp.abs(want))))
        ph.check(bool(jnp.isfinite(got).all()) and diff <= tol,
                 f"kvc_attention ({b},{h},{d}) x {max_len}: max|diff| {diff:.3g} "
                 f"<= {tol:.3g} vs kvc_decode_attention_ref")
    return eng


def kernel_proof(eng, sz_box=(256, 256, 256), zfp_n=512, rate=8) -> dict:
    """Which programs hold a compiled Pallas kernel (``tpu_custom_call``):
    the jitted encode and decode of each codec at the smoke's sizes, and the
    engine's decode step."""
    import jax
    import jax.numpy as jnp

    from repro.core import bitpack, zfp
    from repro.kernels import ops

    x = jax.ShapeDtypeStruct(sz_box, jnp.float32)
    n = 1
    for s in sz_box:
        n *= s
    packed = bitpack.PackedCodes(
        jax.ShapeDtypeStruct((n + 2,), jnp.uint32),
        jax.ShapeDtypeStruct((n // bitpack.BLOCK,), jnp.uint8),
        jax.ShapeDtypeStruct((), jnp.int32), n)
    eb_i = jax.ShapeDtypeStruct((), jnp.float32)
    xz = jax.ShapeDtypeStruct((zfp_n,) * 3, jnp.float32)
    nb = zfp.n_blocks_for(xz.shape)
    wpb = zfp.payload_words(rate)
    cz = zfp.ZFPCompressed(jax.ShapeDtypeStruct((nb, wpb), jnp.uint32),
                           jax.ShapeDtypeStruct((nb,), jnp.uint8),
                           jax.ShapeDtypeStruct((nb, zfp.N_GROUPS), jnp.uint8),
                           xz.shape, rate)
    programs = {
        "sz_encode": jax.jit(lambda a: ops.sz_compress_kernel(a, 1.0)[0]).lower(x),
        "sz_decode": jax.jit(lambda p, e: ops.sz_decompress_kernel(
            p, sz_box, sz_box, e)).lower(packed, eb_i),
        "zfp_encode": jax.jit(lambda a: ops.zfp_compress_kernel(a, rate)).lower(xz),
        "zfp_decode": jax.jit(ops.zfp_decompress_kernel).lower(cz),
        "serve_decode_step": eng.lower_decode_step(),
    }
    return {k: "tpu_custom_call" in low.as_text() for k, low in programs.items()}


def phase_proof(clock, eng, **sizes):
    with Phase("proof", clock) as ph:
        for name, ok in kernel_proof(eng, **sizes).items():
            ph.check(ok, f"{name} lowers to a tpu_custom_call (compiled Pallas kernel)")


def phase_insitu4(clock, n=256, box=None, seed=0, n_dev=4, eb=None, rate=8):
    """Sharded in-situ compression of an ``n``^3 field (or its ``box``
    corner) over ``n_dev`` devices along z, bitwise against the
    single-device round trips on device 0."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as PS

    from repro import compat
    from repro.core.api import get_compressor
    from repro.data import cosmo
    from repro.dist import insitu

    with Phase("insitu4", clock) as ph:
        t = time.perf_counter()
        host = cosmo.nyx_fields(n=n, seed=42 + seed)["baryon_density"]
        if box is not None:
            host = np.ascontiguousarray(host[:box[0], :box[1], :box[2]])
        ph.generated(time.perf_counter() - t, f"baryon_density {host.shape} on host")
        eb = eb if eb is not None else _sz_bounds("baryon_density")[0]
        mesh = compat.make_mesh((n_dev,), ("z",))
        spec = PS("z", None, None)
        xs = jax.device_put(host, NamedSharding(mesh, spec))
        x0 = jax.device_put(host, jax.devices()[0])
        nz, ny, nx = host.shape
        ph.log(f"mesh {dict(mesh.shape)}: shards of {nz // n_dev}x{ny}x{nx}")
        cases = [  # (codec, sharded_compress kwargs, single-device compressor)
            ("sz", dict(eb=eb, backend="kernel"), get_compressor("tpu-sz", backend="kernel")),
            ("zfp", dict(rate=rate), get_compressor("tpu-zfp")),
        ]
        for codec, kw, single in cases:
            label = f"{codec} {kw.get('backend', 'auto')}"
            st = insitu.sharded_compress(xs, codec, mesh, spec, **kw)
            y = insitu.sharded_decompress(st, mesh)
            devs = {s.device for s in y.addressable_shards}
            ph.check(len(devs) == n_dev and len(y.sharding.device_set) == n_dev,
                     f"{label}: decoded shards on {len(devs)} distinct devices "
                     f"{sorted(str(d) for d in devs)}")
            words = st.words
            ph.check(len({s.device for s in words.addressable_shards}) == n_dev,
                     f"{label}: stream shards on {n_dev} distinct devices")
            ref = single.decompress(single.compress(
                x0, **{k: v for k, v in kw.items() if k != "backend"}))
            ph.check(np.array_equal(np.asarray(y), np.asarray(ref)),
                     f"{label}: sharded round trip == single-device round trip "
                     "on device 0, bitwise")
            if codec == "sz":
                err = float(np.abs(np.asarray(y) - host).max())
                ph.check(err <= eb * (1 + 1e-5), f"{label}: max|x^-x| {err:.6g} <= eb={eb:g}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded in-situ phase on four chips")
    ap.add_argument("--seed", type=int, default=0, help="data and weight seed")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found platform {platform!r}", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devices)} device(s)",
              file=sys.stderr)
        return 2

    from repro import compile_cache

    cache_dir = compile_cache.enable()
    clock = _CompileClock()
    kind = devices[0].device_kind
    print(f"device: {platform} {kind} x{len(devices)}; compile cache {cache_dir}", flush=True)
    if args.chips == 4:
        phase_insitu4(clock, seed=args.seed)
    else:
        phase_nyx(clock, seed=args.seed)
        phase_hacc(clock, seed=args.seed)
        eng = phase_serve(clock, seed=args.seed)
        phase_proof(clock, eng)
    print(f"total compile_s={clock.total:.3f} persistent_cache_hits={clock.cache_hits}",
          flush=True)
    print(json.dumps({"ok": True, "device": {"platform": platform, "kind": kind,
                                             "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
