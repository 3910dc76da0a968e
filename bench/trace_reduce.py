"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer metrics
read.

* Device work: the events of each TPU plane's ``XLA Ops`` line; busy time
  is the union of their intervals (per chip, then averaged over chips).  An
  op is named ``<program>/<instruction>`` after the ``XLA Modules`` event
  it starts in; control-flow containers (``while``, ``conditional``,
  ``call``) count towards busy time but not as ops of their own, since the
  ops of their bodies are listed too.
* Harness spans: host events named ``bench.<phase>:<field>``
  (``loop.field_op``), on the same clock as the device events.
* The traced window runs from the first span's start to the last span's
  end.  Idle gaps are the stretches of the window with no device op; each
  is named by the span its midpoint falls in.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from collections import defaultdict

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
CONTAINERS = ("while", "conditional", "call")
HOST_PLANE = "/host:"
SPAN_PREFIX = "bench."


@dataclasses.dataclass(frozen=True)
class Span:
    phase: str
    field: str
    start: float  # ns
    end: float

    @property
    def label(self) -> str:
        return f"{self.phase}:{self.field}"


def merge(intervals) -> list[tuple[float, float]]:
    """Union of (start, end) intervals, sorted and disjoint."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def overlap(a, b) -> float:
    """Total length of the intersection of two merged interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


@dataclasses.dataclass
class TraceSummary:
    spans: list[Span]
    busy: list[list[tuple[float, float]]]  # merged device intervals, per chip
    op_ns: dict[str, float]  # device time per op name, summed over chips

    @property
    def window(self) -> tuple[float, float]:
        return min(s.start for s in self.spans), max(s.end for s in self.spans)

    @property
    def window_s(self) -> float:
        w0, w1 = self.window
        return (w1 - w0) / 1e9

    def phase_intervals(self, phase: str) -> list[tuple[float, float]]:
        return merge((s.start, s.end) for s in self.spans if s.phase == phase)

    def span_s(self, phase: str) -> float:
        return sum(e - s for s, e in self.phase_intervals(phase)) / 1e9

    def busy_s(self, phase: str | None = None) -> float:
        """Device busy seconds inside the spans of ``phase`` (or the whole
        window), averaged over chips."""
        inside = [self.window] if phase is None else self.phase_intervals(phase)
        if not self.busy:
            return 0.0
        return sum(overlap(b, inside) for b in self.busy) / len(self.busy) / 1e9

    def idle_share(self, phase: str) -> float | None:
        span = self.span_s(phase)
        return None if span <= 0 else 1.0 - self.busy_s(phase) / span

    def top_ops(self, k: int = 10) -> list[list]:
        ranked = sorted(self.op_ns.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns / 1e9] for name, ns in ranked]

    def idle_gaps(self, k: int = 10) -> list[list]:
        """The ``k`` longest idle stretches of the window (over all chips),
        each named by the harness span its midpoint falls in."""
        w0, w1 = self.window
        spans = sorted(self.spans, key=lambda s: s.start)
        starts = [s.start for s in spans]
        gaps = []
        for busy in self.busy:
            edges = [(w0, w0)] + [(max(s, w0), min(e, w1)) for s, e in busy if e > w0 and s < w1]
            edges.append((w1, w1))
            for (_, prev_end), (nxt, _) in zip(edges, edges[1:]):
                if nxt > prev_end:
                    gaps.append((nxt - prev_end, (prev_end + nxt) / 2))
        gaps.sort(reverse=True)
        out = []
        for length, mid in gaps[:k]:
            i = bisect.bisect_right(starts, mid) - 1
            label = spans[i].label if i >= 0 and spans[i].end >= mid else "between spans"
            out.append([label, length / 1e9])
        return out


def _op_names(lines) -> list[tuple[float, float, str]]:
    """(start, end, "<program>/<instruction>") of every device op."""
    modules = sorted((float(e.start_ns), float(e.start_ns) + float(e.duration_ns),
                      e.name.split("(")[0])
                     for line in lines if line.name == MODULES_LINE for e in line.events)
    starts = [m[0] for m in modules]
    out = []
    for line in lines:
        if line.name != OPS_LINE:
            continue
        for e in line.events:
            s = float(e.start_ns)
            i = bisect.bisect_right(starts, s) - 1
            program = modules[i][2] if i >= 0 and modules[i][1] >= s else "?"
            op = e.name.split(" = ")[0].lstrip("%")
            out.append((s, s + float(e.duration_ns), f"{program}/{op}"))
    return out


def summarize(planes) -> TraceSummary:
    """``planes``: the ``ProfileData.planes`` of a trace (or objects alike:
    ``name``, ``lines`` of ``name``/``events``; events with ``name``,
    ``start_ns``, ``duration_ns``)."""
    spans: list[Span] = []
    busy = []
    op_ns: dict[str, float] = defaultdict(float)
    for plane in planes:
        if plane.name.startswith(DEVICE_PLANE):
            ops = _op_names(list(plane.lines))
            busy.append(merge((s, e) for s, e, _ in ops))
            for s, e, name in ops:
                if not name.split("/", 1)[1].startswith(CONTAINERS):
                    op_ns[name] += e - s
        elif plane.name.startswith(HOST_PLANE):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        phase, _, field = e.name[len(SPAN_PREFIX):].partition(":")
                        s = float(e.start_ns)
                        spans.append(Span(phase, field, s, s + float(e.duration_ns)))
    if not spans:
        raise ValueError("trace holds no harness spans (bench.<phase>:<field>)")
    return TraceSummary(spans, busy, dict(op_ns))


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"want one .xplane.pb under {log_dir}, found {paths}")
    return paths[0]


def read(path: str) -> TraceSummary:
    from jax.profiler import ProfileData

    return summarize(ProfileData.from_file(path).planes)
