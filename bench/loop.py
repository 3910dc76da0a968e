"""The closed loop the window times, and the end-to-end arithmetic.

A snapshot is the configuration's fields in their order; each field goes
through compress, fetch, upload and decompress (one
``jax.profiler.TraceAnnotation`` span per phase, named
``bench.<phase>:<field>``).  No snapshot starts after the window's seconds
have run out; every number counts whole field operations only.
"""

from __future__ import annotations

import dataclasses
import time
from collections import defaultdict

import jax
import numpy as np

from bench import cells, codecs

PHASES = ("compress", "fetch", "upload", "decompress")
SPAN_PREFIX = "bench."


class CompileCounter:
    """Programs JAX compiled or loaded from its persistent cache, counted from
    its own monitoring events (as ``chip_smoke._CompileClock`` sums them)."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.compiles = 0
        self.cache_hits = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == self.COMPILE:
            self.compiles += 1
            self.compile_s += duration

    def _on_event(self, event, **_):
        if event == self.CACHE_HIT:
            self.cache_hits += 1

    @property
    def programs(self) -> int:
        return self.compiles + self.cache_hits


@dataclasses.dataclass
class Job:
    """One field of the snapshot, compressed whole by one call."""

    name: str
    x: jax.Array
    params: dict
    raw_bytes: int


def jobs(cell: cells.Cell, fields: dict) -> list[Job]:
    return [Job(name, fields[name], cells.field_params(cell.mix, name), int(fields[name].size) * 4)
            for name in cell.config["fields"]]


def field_op(driver, job: Job, phase_s: dict):
    """Run one field through the four phases; returns (host stream, decoded
    field, stream bytes)."""
    t = time.perf_counter()
    with jax.profiler.TraceAnnotation(f"{SPAN_PREFIX}compress:{job.name}"):
        result = driver.compress(job.x, job.params)
    t1 = time.perf_counter()
    with jax.profiler.TraceAnnotation(f"{SPAN_PREFIX}fetch:{job.name}"):
        host = driver.fetch(result)
    t2 = time.perf_counter()
    with jax.profiler.TraceAnnotation(f"{SPAN_PREFIX}upload:{job.name}"):
        rebuilt = driver.upload(result, host)
    del result
    t3 = time.perf_counter()
    with jax.profiler.TraceAnnotation(f"{SPAN_PREFIX}decompress:{job.name}"):
        decoded = driver.decompress(rebuilt)
    t4 = time.perf_counter()
    for p, dt in zip(PHASES, (t1 - t, t2 - t1, t3 - t2, t4 - t3)):
        phase_s[p] += dt
    return host, decoded, codecs.host_nbytes(host)


@dataclasses.dataclass
class Window:
    phase_s: dict
    seconds: float
    snapshots: int
    ops: int
    raw_bytes: int
    stream_bytes: int
    counted: dict  # field -> stream bytes of every operation on it
    kept: dict  # field -> (host stream, decoded field) of the sampled operation


def warm_up(driver, jobs_: list[Job]) -> None:
    """Compile every program the window runs, untimed: one whole field
    operation, and, where the fetch's shapes depend on the data (the SZ
    stream's length), the compress and fetch of every other field."""
    field_op(driver, jobs_[0], dict.fromkeys(PHASES, 0.0))
    if driver.fetch_shape_depends_on_data:
        for job in jobs_[1:]:
            driver.fetch(driver.compress(job.x, job.params))


def window(driver, jobs_: list[Job], seconds: float, rng: np.random.Generator) -> Window:
    """Closed loop over snapshots for ``seconds``.  For each field one
    operation is kept for the check, drawn uniformly (reservoir of one)."""
    phase_s = dict.fromkeys(PHASES, 0.0)
    counted = defaultdict(list)
    kept: dict = {}
    raw = stream = ops = snaps = 0
    t0 = time.perf_counter()
    while snaps == 0 or time.perf_counter() - t0 < seconds:
        for job in jobs_:
            host, decoded, nbytes = field_op(driver, job, phase_s)
            ops += 1
            raw += job.raw_bytes
            stream += nbytes
            counted[job.name].append(nbytes)
            if rng.random() * len(counted[job.name]) < 1.0:
                kept[job.name] = (host, decoded)
            del host, decoded
        snaps += 1
    return Window(phase_s, time.perf_counter() - t0, snaps, ops, raw, stream,
                  dict(counted), kept)


def end_to_end(win: Window, setup_s: float) -> dict[str, float]:
    """The cell's end-to-end numbers from one window (GB = 1e9 bytes)."""
    return {
        "compress_gbps": win.raw_bytes / win.phase_s["compress"] / 1e9,
        "decompress_gbps": win.raw_bytes / win.phase_s["decompress"] / 1e9,
        "host_roundtrip_gbps": win.raw_bytes / win.seconds / 1e9,
        "compression_ratio": win.raw_bytes / win.stream_bytes,
        "setup_s": setup_s,
    }
