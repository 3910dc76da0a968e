"""Record the small trace the CPU tests of ``trace_reduce`` read.

    python bench/record_trace.py --out chiprun_out/trace64

On a TPU: one 64^3 Nyx-like field (``baryon_density``) goes once through
the four phases of each codec (SZ at eb=3, ZFP at rate 8) under the
profiler, with the harness's spans; the ``.xplane.pb`` is copied to
``<out>/roundtrip64.xplane.pb`` and its reduction printed.  Not part of a
benchmark run.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench import cells, codecs, data, loop, trace_reduce

    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2
    base = cells.load_cell("nyx256.sz_tight")
    config = dict(base.config, grid=64, fields=["baryon_density"])
    fields = data.generate(config, 64)
    runs = []
    for mix in ({"codec": "tpu-sz", "params": {"*": {"eb": 3.0}}},
                {"codec": "tpu-zfp", "params": {"*": {"rate": 8}}}):
        cell = dataclasses.replace(base, config=config, mix=mix)
        driver = codecs.make(mix)
        jobs = loop.jobs(cell, fields)
        loop.warm_up(driver, jobs)
        runs.append((driver, jobs))
    log_dir = tempfile.mkdtemp(prefix="bench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    for driver, jobs in runs:
        loop.warm_up(driver, jobs)
    jax.profiler.stop_trace()
    src = trace_reduce.find_xplane(log_dir)
    os.makedirs(args.out, exist_ok=True)
    dst = os.path.join(args.out, "roundtrip64.xplane.pb")
    shutil.copy(src, dst)
    shutil.rmtree(log_dir, ignore_errors=True)
    s = trace_reduce.read(dst)
    print(json.dumps({"spans": len(s.spans), "window_s": s.window_s, "busy_s": s.busy_s(),
                      "idle": {p: s.idle_share(p) for p in loop.PHASES},
                      "top_ops": s.top_ops(), "idle_gaps": s.idle_gaps()}))
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(dst).planes:
        lines = {line.name: sum(1 for _ in line.events) for line in plane.lines}
        print(plane.name, lines)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
