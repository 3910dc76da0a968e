"""Host cost of one ``repro.obs.trace.span`` enter/exit, in three states:
everything off, the obs tracer on, and a JAX profiler recording.

    PYTHONPATH=src python -m benchmarks.span_cost [--n 200000]

Prints one JSON object of nanoseconds per span, loop included (median of
5 repeats).  A host measurement: it says what a span costs the calling
thread, not what a device does.
"""

from __future__ import annotations

import argparse
import json
import statistics
import tempfile
import time


def _ns_per_span(span, n: int) -> float:
    best = []
    for _ in range(5):
        t = time.perf_counter_ns()
        for _ in range(n):
            with span("bench.probe"):
                pass
        best.append((time.perf_counter_ns() - t) / n)
    return statistics.median(best)


def measure(n: int) -> dict[str, float]:
    import jax

    from repro.obs import trace as obs_trace

    tr = obs_trace.Tracer(max_events=n * 5 + 1)
    out = {"off": _ns_per_span(tr.span, n)}
    tr.enable()
    out["tracer_on"] = _ns_per_span(tr.span, n)
    tr.disable()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # as bench/run.py traces: no Python call tracing
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            out["profiler_on"] = _ns_per_span(tr.span, n)
        finally:
            jax.profiler.stop_trace()
    out["device"] = jax.devices()[0].platform
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n", type=int, default=200_000)
    print(json.dumps(measure(ap.parse_args(argv).n)))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
