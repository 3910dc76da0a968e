"""repro.obs — run-wide telemetry: metrics, tracing, and the compression
observatory (DESIGN.md §11).

Deliberately stdlib-only (no jax, no numpy): importing or updating an
instrument can never pull in device state or add a sync, and the disabled
path is a single attribute check per call.

  * :mod:`repro.obs.metrics`     — counters / gauges / ring-buffer
    histograms in a process-global registry, JSONL export + summary();
  * :mod:`repro.obs.trace`       — nested span timers, Chrome-trace JSON,
    one track per thread, forwarded as ``repro/<name>`` into a recording
    JAX profiler's trace; the names of the codec path's device stages;
  * :mod:`repro.obs.observatory` — per-snapshot per-bucket compression
    records beside the manifest, run-level rate-quality trajectory.
"""

from repro.obs import metrics, observatory, trace
from repro.obs.metrics import (counter, disable, enable, enabled, event,
                               export_snapshot, gauge, histogram, summary)
from repro.obs.trace import span

__all__ = [
    "metrics", "trace", "observatory",
    "counter", "gauge", "histogram", "event",
    "enable", "disable", "enabled", "export_snapshot", "summary", "span",
]
