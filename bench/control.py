"""Readings that set the check's limits, several seeds in one process.

    python bench/control.py --workload nyx256.sz_tight --seeds 11 12 13          # control
    python bench/control.py --workload nyx256.sz_tight --seeds 21 22 --program   # program

By default the plain reference, computed in bfloat16 (the precision below
the configuration's float32), takes the program's place in the harness: its
encoder makes the host streams and its float32 decoder decodes them, and
the same check judges them.  It has to come out not correct.  With
``--program`` the program runs instead, on the same short window.  Each
seed's numbers are printed as one JSON line.  Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


class ReferenceDriver:
    """The four phases done by the plain reference in ``dtype``."""

    fetch_shape_depends_on_data = False

    def __init__(self, mix: dict, dtype):
        self.codec, self.dtype = mix["codec"], dtype

    def _fmt(self, x) -> str:
        import jax

        return "tiled" if x.ndim == 3 and jax.default_backend() == "tpu" else "global"

    def compress(self, x, params: dict):
        from bench.reference import sz as rsz
        from bench.reference import zfp as rzfp

        if self.codec == "tpu-sz":
            fmt = self._fmt(x)
            return x.shape, {"fmt": fmt, "parts": rsz.encode_host(x, params["eb"], fmt, self.dtype)}
        stream = rzfp.encode_host(x, params["rate"], self.dtype)
        return x.shape, {"fmt": "fixed-rate", "rate": params["rate"], "parts": [stream]}

    def fetch(self, r):
        return r[1]

    def upload(self, r, host):
        return r[0], host

    def decompress(self, r):
        from bench.reference import sz as rsz
        from bench.reference import zfp as rzfp

        shape, host = r
        if self.codec == "tpu-sz":
            return rsz.decode_host(host["parts"], shape, host["fmt"])
        return rzfp.decode_host(host["parts"][0], shape, host["rate"])


def readings(cell, seeds, seconds: float, program: bool):
    import jax.numpy as jnp

    from bench import run

    wrap = None if program else (lambda _driver: ReferenceDriver(cell.mix, jnp.bfloat16))
    for seed in seeds:
        r = run.run_cell(cell, seed, seconds, False, time.perf_counter(), wrap=wrap)
        yield {"seed": seed, "correct": r["correct"], "checks": r["checks"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--program", action="store_true")
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench import cells

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    cell = cells.load_cell(args.workload)
    for line in readings(cell, args.seeds, args.seconds, args.program):
        print(json.dumps({"workload": args.workload, "program": args.program, **line}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
