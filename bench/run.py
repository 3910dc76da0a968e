"""One run of one benchmark cell on the chip.

    python bench/run.py --workload nyx256.sz_tight --seed 7 --seconds 10 --trace 0

Set-up (counted in ``setup_s``, from process start): the cell's snapshot is
made on the device from ``--seed``, every program the window runs is
compiled (or loaded from the persistent cache in ``<checkout>/.jax_cache``)
by one untimed snapshot.  The window then runs whole snapshots for
``--seconds`` (``loop.window``).  Afterwards the peak device memory is read
and the kept outputs are compared with the plain reference (``check``).

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` records a
profiler trace of the window and reports the per-layer metrics, the device's
busy and window seconds, and a breakdown.  The last line of standard output
is one JSON object; the compared numbers, each with its limit, are the last
lines of standard error and the last key of that object.  Exits 2, printing
no result, unless JAX's devices are TPUs and as many as the cell asks for.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"


@dataclasses.dataclass
class Context:
    """What a per-layer reader (``bench/metrics/<name>.py``) reads."""

    codec: str
    raw_bytes: int
    stream_bytes: int
    phase_s: dict
    trace: object  # trace_reduce.TraceSummary, or None
    peak: dict


def _say(msg: str) -> None:
    print(msg, flush=True)


def run_cell(cell, seed: int, seconds: float, trace: bool, t0: float, wrap=None) -> dict:
    """Set up, time and check one cell; returns the result object.  ``wrap``
    (tests only) may replace the codec driver with one that breaks it."""
    import jax
    import numpy as np

    from bench import cells, check, codecs, data, loop, trace_reduce

    counter = loop.CompileCounter()
    dev = jax.devices()[0]
    _say(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
         f"compile cache {jax.config.jax_compilation_cache_dir}")

    fields = data.generate(cell.config, seed)
    jobs = loop.jobs(cell, fields)
    del fields
    driver = codecs.make(cell.mix)
    if wrap is not None:
        driver = wrap(driver)
    loop.warm_up(driver, jobs)
    setup_s = time.perf_counter() - t0
    _say(f"setup_s={setup_s} programs compiled={counter.compiles} "
         f"loaded={counter.cache_hits} compile_s={counter.compile_s}")

    before = counter.programs
    summary = None
    if trace:
        log_dir = tempfile.mkdtemp(prefix="bench_trace_")
        try:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(log_dir, profiler_options=opts)
            win = loop.window(driver, jobs, seconds, np.random.default_rng(seed))
            jax.profiler.stop_trace()
            summary = trace_reduce.read(trace_reduce.find_xplane(log_dir))
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)
    else:
        win = loop.window(driver, jobs, seconds, np.random.default_rng(seed))
    window_compiles = counter.programs - before
    peak_bytes = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    _say(f"window: {win.seconds} s, {win.snapshots} snapshots, {win.ops} field operations; "
         f"programs compiled or loaded inside the window: {window_compiles}")
    _say(f"peak_bytes_in_use={peak_bytes}")
    del driver

    codec = cell.mix["codec"]
    per_field = {}
    for job in jobs:
        if job.name not in win.kept:
            continue
        host, decoded = win.kept.pop(job.name)
        per_field[job.name] = check.field(codec, job.x, host, decoded,
                                          win.counted[job.name], job.params)
        _say(f"check {job.name}: {per_field[job.name]}")
    correct, checks, failed = check.verdict(codec, per_field, list(cell.config["fields"]))

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak_bytes}
    result = {"correct": correct, "attempted": win.ops, "failed": failed}
    if summary is not None:
        ctx = Context(codec, win.raw_bytes, win.stream_bytes, win.phase_s, summary,
                      cells.peak(dev.device_kind))
        metrics = {}
        for m in cell.per_layer:
            value = cells.reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=summary.busy_s(), window_s=summary.window_s)
        result.update(metrics=metrics, device=device,
                      breakdown={"device_ops": summary.top_ops(), "idle_gaps": summary.idle_gaps()})
    else:
        e2e = loop.end_to_end(win, setup_s)
        result.update(metrics={m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                               for m in cell.end_to_end}, device=device)
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # the persistent compilation cache lives at a fixed path in the checkout
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench import cells
    from repro import compile_cache

    compile_cache.enable()
    # cache every program, so that a second run compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = cells.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)", file=sys.stderr)
        return 2
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), T0)
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']} (limit {c['limit']})", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
