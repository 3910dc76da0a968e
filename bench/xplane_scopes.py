"""Device stages and program spans from a profiler trace's wire format.

``jax.profiler.ProfileData`` gives each event a name and times, but not the
stats of its metadata, where the device op's ``tf_op`` (its full scope path,
``jit(fused_compress)/bitpack.compact/jit(searchsorted)/...``) lives.  This
module decodes the ``.xplane.pb`` itself, with a schema of the few
``xplane.proto`` fields it reads, and attributes device time to the stages
the program names (``repro.obs.trace.STAGE_SCOPES`` and ``KERNEL_NAMES``):

* an op belongs to the innermost listed scope or kernel name on its
  ``tf_op`` path, else it is *unstaged*;
* each instant of device busy time goes to the op that started last among
  those running (a loop's body before the loop), so per phase the stages'
  seconds and the unstaged seconds sum to ``TraceSummary.busy_s(phase)``;
* program spans are the host events named ``repro/<name>``
  (``obs.trace.span`` while a profiler records); an idle gap is labelled
  ``<harness span>/<innermost program span>`` where one covers its middle.

The decoded planes have the shape ``trace_reduce.summarize`` reads, so the
harness's own numbers come out of the same decode unchanged.

    python bench/xplane_scopes.py --xplane trace.xplane.pb
    python bench/xplane_scopes.py --workload nyx256.sz_tight --seed 7 --seconds 10

The second form runs a cell's warm-up and one traced window the way
``bench/run.py --trace 1`` does, and prints the stage split, the program
spans per phase, the relabelled idle gaps and the stage metrics as one JSON
object (it checks no output: that is ``bench/run.py``'s).
"""

from __future__ import annotations

import argparse
import bisect
import dataclasses
import heapq
import json
import sys
from collections import defaultdict
from functools import cache
from pathlib import Path
from typing import NamedTuple

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import trace_reduce  # noqa: E402
from repro.obs.trace import KERNEL_NAMES, PROFILER_PREFIX, STAGE_SCOPES  # noqa: E402

UNSTAGED = "unstaged"
TF_OP = "tf_op"

# (message, [(field, number, type, repeated, message type)]) from tsl's
# xplane.proto; fields not listed are skipped by the parser.
_SCHEMA = (
    ("XStat", [("metadata_id", 1, "int64", False, None), ("str_value", 5, "string", False, None),
               ("ref_value", 7, "uint64", False, None)]),
    ("XEvent", [("metadata_id", 1, "int64", False, None), ("offset_ps", 2, "int64", False, None),
                ("duration_ps", 3, "int64", False, None)]),
    ("XLine", [("name", 2, "string", False, None), ("timestamp_ns", 3, "int64", False, None),
               ("events", 4, "message", True, "XEvent")]),
    ("XEventMetadata", [("id", 1, "int64", False, None), ("name", 2, "string", False, None),
                        ("stats", 5, "message", True, "XStat")]),
    ("XStatMetadata", [("id", 1, "int64", False, None), ("name", 2, "string", False, None)]),
    ("EventMetadataEntry", [("key", 1, "int64", False, None),
                            ("value", 2, "message", False, "XEventMetadata")]),
    ("StatMetadataEntry", [("key", 1, "int64", False, None),
                           ("value", 2, "message", False, "XStatMetadata")]),
    ("XPlane", [("name", 2, "string", False, None), ("lines", 3, "message", True, "XLine"),
                ("event_metadata", 4, "message", True, "EventMetadataEntry"),
                ("stat_metadata", 5, "message", True, "StatMetadataEntry")]),
    ("XSpace", [("planes", 1, "message", True, "XPlane")]),
)


@cache
def _xspace_class():
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    F = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(name="bench_xplane.proto", package="bench_xplane",
                                            syntax="proto3")
    for msg, fields in _SCHEMA:
        m = fd.message_type.add(name=msg)
        for name, number, typ, repeated, ref in fields:
            f = m.field.add(name=name, number=number,
                            type=getattr(F, f"TYPE_{typ.upper()}"),
                            label=F.LABEL_REPEATED if repeated else F.LABEL_OPTIONAL)
            if ref:
                f.type_name = f".bench_xplane.{ref}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(pool.FindMessageTypeByName("bench_xplane.XSpace"))


class Event(NamedTuple):
    name: str
    start_ns: float
    duration_ns: float
    tf_op: str  # "" where the op's metadata has none


@dataclasses.dataclass
class Line:
    name: str
    events: list[Event]


@dataclasses.dataclass
class Plane:
    name: str
    lines: list[Line]


def decode(data: bytes) -> list[Plane]:
    """Every plane, line and event of a serialized ``XSpace``.  Times are
    whole nanoseconds (``timestamp_ns + offset_ps // 1000``), as
    ``ProfileData`` gives them."""
    space = _xspace_class().FromString(data)
    planes = []
    for p in space.planes:
        stat_names = {m.key: m.value.name for m in p.stat_metadata}
        meta = {}
        for m in p.event_metadata:
            tf_op = ""
            for st in m.value.stats:
                if stat_names.get(st.metadata_id) == TF_OP:
                    tf_op = st.str_value or stat_names.get(st.ref_value, "")
            meta[m.key] = (m.value.name, tf_op)
        lines = []
        for line in p.lines:
            events = []
            for e in line.events:
                name, tf_op = meta.get(e.metadata_id, ("", ""))
                events.append(Event(name, float(line.timestamp_ns + e.offset_ps // 1000),
                                    float(e.duration_ps // 1000), tf_op))
            lines.append(Line(line.name, events))
        planes.append(Plane(p.name, lines))
    return planes


def read_planes(path: str) -> list[Plane]:
    return decode(Path(path).read_bytes())


def stage_of(tf_op: str, names=frozenset(STAGE_SCOPES + KERNEL_NAMES)) -> str:
    """Innermost listed scope or kernel name on an op's ``tf_op`` path
    (``<scope>/<scope>/.../<op>:<type>``), else :data:`UNSTAGED`."""
    path = tf_op.rpartition(":")[0] if ":" in tf_op else tf_op
    for part in reversed(path.split("/")):
        if part in names:
            return part
    return UNSTAGED


def attribute(ops) -> dict[str, list[tuple[float, float]]]:
    """``ops``: (start, end, stage).  Splits the union of the ops' intervals
    among the stages: each instant goes to the op that started last among
    those running then.  Returns each stage's merged intervals."""
    ops = sorted(ops)
    bounds = sorted({t for s, e, _ in ops for t in (s, e)})
    segs: dict[str, list] = defaultdict(list)
    heap: list = []
    j = 0
    for t0, t1 in zip(bounds, bounds[1:]):
        while j < len(ops) and ops[j][0] <= t0:
            s, e, stage = ops[j]
            heapq.heappush(heap, (-s, j, e, stage))
            j += 1
        while heap and heap[0][2] <= t0:
            heapq.heappop(heap)
        if heap:
            segs[heap[0][3]].append((t0, t1))
    return {stage: trace_reduce.merge(iv) for stage, iv in segs.items()}


@dataclasses.dataclass(frozen=True)
class ProgramSpan:
    name: str  # without the ``repro/`` prefix
    start: float  # ns
    end: float


@dataclasses.dataclass
class Stages:
    summary: trace_reduce.TraceSummary  # the harness's reduction, of the same planes
    program: list[ProgramSpan]
    stages: list[dict[str, list[tuple[float, float]]]]  # per chip: stage -> merged intervals

    def stage_s(self, phase: str, stage: str) -> float:
        """Device seconds of ``stage`` inside the spans of ``phase``,
        averaged over chips (as ``TraceSummary.busy_s``)."""
        if not self.stages:
            return 0.0
        inside = self.summary.phase_intervals(phase)
        return sum(trace_reduce.overlap(ch.get(stage, []), inside)
                   for ch in self.stages) / len(self.stages) / 1e9

    def split(self, phase: str) -> dict[str, float]:
        names = sorted({s for ch in self.stages for s in ch})
        return {s: v for s in names if (v := self.stage_s(phase, s)) > 0}

    def unstaged_share(self, phase: str) -> float | None:
        busy = self.summary.busy_s(phase)
        return None if busy <= 0 else self.stage_s(phase, UNSTAGED) / busy

    def in_phases(self) -> list[ProgramSpan]:
        """Program spans whose start lies inside a harness span."""
        spans = sorted(self.summary.spans, key=lambda s: s.start)
        starts = [s.start for s in spans]
        out = []
        for p in self.program:
            i = bisect.bisect_right(starts, p.start) - 1
            if i >= 0 and spans[i].end >= p.start:
                out.append(p)
        return out

    def program_s(self, phase: str) -> dict[str, float]:
        """Host seconds per program span name inside the spans of ``phase``."""
        inside = self.summary.phase_intervals(phase)
        out: dict[str, float] = defaultdict(float)
        for p in self.program:
            t = trace_reduce.overlap([(p.start, p.end)], inside)
            if t > 0:
                out[p.name] += t / 1e9
        return dict(out)

    def syncs_per_op(self) -> float | None:
        ops = sum(1 for s in self.summary.spans if s.phase == "compress")
        syncs = sum(1 for p in self.in_phases() if p.name.startswith("sync."))
        return None if ops == 0 else syncs / ops

    def idle_gaps(self, k: int = 10) -> list[list]:
        """``TraceSummary.idle_gaps`` with ``/<innermost program span>``
        after the harness label where a program span covers the middle."""
        w0, w1 = self.summary.window
        spans = sorted(self.summary.spans, key=lambda s: s.start)
        starts = [s.start for s in spans]
        gaps = []
        for busy in self.summary.busy:
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
            inner = [p for p in self.program if p.start <= mid <= p.end]
            if inner:
                label += "/" + max(inner, key=lambda p: p.start).name
            out.append([label, length / 1e9])
        return out


def summarize(planes) -> Stages:
    summary = trace_reduce.summarize(planes)
    program, stages = [], []
    for plane in planes:
        if plane.name.startswith(trace_reduce.DEVICE_PLANE):
            stages.append(attribute(
                (e.start_ns, e.start_ns + e.duration_ns, stage_of(e.tf_op))
                for line in plane.lines if line.name == trace_reduce.OPS_LINE
                for e in line.events))
        elif plane.name.startswith(trace_reduce.HOST_PLANE):
            program += [ProgramSpan(e.name[len(PROFILER_PREFIX):], e.start_ns,
                                    e.start_ns + e.duration_ns)
                        for line in plane.lines for e in line.events
                        if e.name.startswith(PROFILER_PREFIX)]
    program.sort(key=lambda p: p.start)
    return Stages(summary, program, stages)


def read(path: str) -> Stages:
    return summarize(read_planes(path))


# Stage metrics: (phase, stage) whose device seconds divide the raw bytes.
STAGE_GBPS = {
    "sz.compact_gbps": ("compress", "bitpack.compact"),
    "sz.disassemble_gbps": ("decompress", "sz_fused.disassemble"),
    "sz.pack_gbps": ("compress", "bitpack.pack"),
    "sz.unpack_gbps": ("decompress", "bitpack.unpack"),
}


def metrics(st: Stages, raw_bytes: int) -> dict[str, float]:
    """The stage-level per-layer numbers of one traced window (GB = 1e9 B;
    shares in %); a number whose stage or spans the trace lacks is left out."""
    out = {}
    for name, (phase, stage) in STAGE_GBPS.items():
        s = st.stage_s(phase, stage)
        if s > 0:
            out[name] = raw_bytes / s / 1e9
    for phase in ("compress", "decompress"):
        share = st.unstaged_share(phase)
        if share is not None:
            out[f"unstaged_share.{phase}"] = 100.0 * share
    syncs = st.syncs_per_op()
    if syncs:
        out["host_syncs_per_op"] = syncs
    return out


def report(st: Stages, raw_bytes: int | None = None) -> dict:
    phases = sorted({s.phase for s in st.summary.spans})
    out = {
        "window_s": st.summary.window_s, "busy_s": st.summary.busy_s(),
        "stages_s": {p: st.split(p) for p in phases},
        "busy_in_phase_s": {p: st.summary.busy_s(p) for p in phases},
        "program_s": {p: st.program_s(p) for p in phases},
        "device_ops": st.summary.top_ops(), "idle_gaps": st.idle_gaps(),
    }
    if raw_bytes is not None:
        out["metrics"] = metrics(st, raw_bytes)
    return out


def _traced_window(workload: str, seed: int, seconds: float, out_dir: str | None) -> dict:
    """Set up ``workload`` and trace one window as ``bench/run.py`` does."""
    import os
    import shutil
    import tempfile

    from bench import run

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(run.CACHE_DIR)
    import jax
    import numpy as np

    from bench import cells, codecs, data, loop
    from repro import compile_cache

    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = cells.load_cell(workload)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"xplane_scopes: {workload} needs a TPU; JAX found {dev.platform}")
    jobs = loop.jobs(cell, data.generate(cell.config, seed))
    driver = codecs.make(cell.mix)
    loop.warm_up(driver, jobs)
    log_dir = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        win = loop.window(driver, jobs, seconds, np.random.default_rng(seed))
        jax.profiler.stop_trace()
        path = trace_reduce.find_xplane(log_dir)
        if out_dir:
            os.makedirs(out_dir, exist_ok=True)
            shutil.copy(path, os.path.join(out_dir, f"{workload}.xplane.pb"))
        out = report(read(path), win.raw_bytes)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    out.update(workload=workload, seed=seed, snapshots=win.snapshots, ops=win.ops,
               phase_s=win.phase_s,
               memory_peak_bytes=(dev.memory_stats() or {}).get("peak_bytes_in_use"))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--xplane", help="reduce this recorded trace")
    ap.add_argument("--workload", help="trace one window of this cell on the chip")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", help="copy the window's .xplane.pb here")
    args = ap.parse_args(argv)
    if (args.xplane is None) == (args.workload is None):
        ap.error("give one of --xplane and --workload")
    if args.xplane:
        out = report(read(args.xplane))
    else:
        out = _traced_window(args.workload, args.seed, args.seconds, args.out)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
