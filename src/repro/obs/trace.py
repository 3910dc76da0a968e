"""Nested span timers emitting Chrome-trace/Perfetto-compatible JSON.

Usage::

    from repro.obs import trace
    trace.enable()
    with trace.span("snapshot.dispatch", step=120):
        ...
    trace.export("trace_run.json")   # open in chrome://tracing / Perfetto

Every span becomes one complete ("ph": "X") event with microsecond
``ts``/``dur`` relative to ``enable()``; events carry the recording
thread's ``tid``, so the exported file renders **one track per thread** —
the training thread's ``train.step`` spans and the ckpt-drain thread's
``ckpt.drain.save`` spans land on separate rows of the same timeline, and
nesting within a track is inferred from containment (standard
Chrome-trace semantics).  Thread names are attached via "M" (metadata)
events at export time.

Profiler forwarding: whenever a JAX profiler session is recording, every
span also lands in the profiler's trace as a ``TraceMe`` named
``repro/<name>`` (arguments attached as its stats), on the clock the device
events share.  No flag: the profiler being on is the switch.  The fixed
``repro/`` prefix is how a trace reduction tells program spans from JAX's
own host events.  jax is bound lazily, on the first span after it was
imported, so importing this module stays stdlib-only.

Device stages are named at trace time instead: :func:`scope` wraps
``jax.named_scope`` for the names in :data:`STAGE_SCOPES`, which then head
the ``tf_op`` path of every device op the stage emits; Pallas kernels carry
the names in :data:`KERNEL_NAMES` (``pallas_call(name=...)``).  Both lists
live here, so a reduction reads one place.

Cost contract: with the tracer disabled and no profiler recording, ``span``
hands back a shared no-op span after one attribute check and one
``TraceMe.is_enabled()`` call (zero allocation); an enabled tracer takes
two ``perf_counter`` calls plus one dict append under a lock — never a
device sync (DESIGN.md §11).  The event buffer is bounded (default 200k
spans); overflow increments a drop counter instead of growing without
limit.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from pathlib import Path
from typing import Any, Optional

__all__ = ["Tracer", "TRACER", "span", "scope", "enable", "disable",
           "enabled", "export", "clear", "PROFILER_PREFIX", "STAGE_SCOPES",
           "KERNEL_NAMES"]

PROFILER_PREFIX = "repro/"

# Device stages of the codec path (``jax.named_scope`` via :func:`scope`).
STAGE_SCOPES = (
    "bitpack.compact",       # bitpack.compact_streams: per-block payloads -> dense stream
    "bitpack.pack",          # bitpack.pack_codes
    "bitpack.unpack",        # bitpack.unpack_codes
    "sz_fused.disassemble",  # sz_fused._disassemble_stream: dense stream -> block rows
    "sz.predict",            # sz.compress: quantize + Lorenzo residual
    "sz.reconstruct",        # sz.decompress: prefix sums + dequantize
)

# ``pallas_call(name=...)`` of every kernel under ``repro.kernels``.
KERNEL_NAMES = (
    "sz_fused_encode", "sz_fused_encode_batched",
    "sz_fused_decode", "sz_fused_decode_batched",
    "zfp_fused_encode", "zfp_fused_decode", "zfp3d_transform",
    "lorenzo3d_quantize", "lorenzo3d_reconstruct", "kvc_decode_attention",
)

_TRACEME = None  # jaxlib's TraceMe class, bound once jax has been imported


def _recording():
    """jaxlib's ``TraceMe`` class while a JAX profiler session records, else
    None.  Never imports jax: a process that has not loaded it runs no
    profiler."""
    global _TRACEME
    if _TRACEME is None:
        mod = sys.modules.get("jaxlib._profiler")
        if mod is None:
            return None
        _TRACEME = mod.TraceMe
    return _TRACEME if _TRACEME.is_enabled() else None


def scope(name: str):
    """``jax.named_scope(name)`` for a device stage of :data:`STAGE_SCOPES`
    (compile-time metadata only: the ops it traces carry ``name`` in their
    ``tf_op`` path; no runtime cost)."""
    if name not in STAGE_SCOPES:
        raise ValueError(f"unknown stage scope {name!r}; have {STAGE_SCOPES}")
    import jax

    return jax.named_scope(name)


class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _Span:
    __slots__ = ("_tr", "_name", "_args", "_t0", "_prof")

    def __init__(self, tr: "Tracer", name: str, args: dict):
        self._tr = tr
        self._name = name
        self._args = args

    def __enter__(self):
        tm = _recording()
        self._prof = None if tm is None else tm(PROFILER_PREFIX + self._name, **self._args)
        if self._prof is not None:
            self._prof.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self._prof is not None:
            self._prof.__exit__(*exc)
        tr = self._tr
        end = time.perf_counter()
        tid = threading.get_ident()
        ev = {
            "name": self._name, "ph": "X", "pid": tr._pid, "tid": tid,
            "ts": (self._t0 - tr._t0) * 1e6,
            "dur": (end - self._t0) * 1e6,
        }
        if self._args:
            ev["args"] = self._args
        tr._record(ev, tid)
        return False


class Tracer:
    def __init__(self, max_events: int = 200_000):
        self._lock = threading.Lock()
        self._enabled = False
        self._events: list[dict] = []
        self._dropped = 0
        self._max_events = int(max_events)
        self._t0 = time.perf_counter()
        self._pid = os.getpid()
        self._threads: dict[int, str] = {}

    # -------------------------------------------------------- recording --
    def span(self, name: str, **args: Any):
        """Context manager timing one nested region on the calling thread
        (and naming it ``repro/<name>`` in a recording profiler's trace)."""
        if self._enabled:
            return _Span(self, name, args)
        tm = _recording()
        return _NULL_SPAN if tm is None else tm(PROFILER_PREFIX + name, **args)

    def _record(self, ev: dict, tid: int) -> None:
        with self._lock:
            if len(self._events) >= self._max_events:
                self._dropped += 1
                return
            self._events.append(ev)
            if tid not in self._threads:
                self._threads[tid] = threading.current_thread().name

    # -------------------------------------------------------- lifecycle --
    def enable(self) -> None:
        with self._lock:
            self._events.clear()
            self._threads.clear()
            self._dropped = 0
            self._t0 = time.perf_counter()
            self._pid = os.getpid()
        self._enabled = True

    def disable(self) -> None:
        self._enabled = False

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self._threads.clear()
            self._dropped = 0

    @property
    def events(self) -> list[dict]:
        with self._lock:
            return list(self._events)

    @property
    def dropped(self) -> int:
        return self._dropped

    # ----------------------------------------------------------- export --
    def export(self, path: str | Path) -> Path:
        """Write ``{"traceEvents": [...]}`` Chrome-trace JSON: thread-name
        metadata first, then every recorded span."""
        with self._lock:
            events = list(self._events)
            threads = dict(self._threads)
        meta = [
            {"name": "thread_name", "ph": "M", "pid": self._pid, "tid": tid,
             "args": {"name": tname}}
            for tid, tname in sorted(threads.items())
        ]
        doc = {"traceEvents": meta + events, "displayTimeUnit": "ms"}
        p = Path(path)
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(json.dumps(doc))
        return p


TRACER = Tracer()


def span(name: str, **args: Any):
    return TRACER.span(name, **args)


def enable() -> None:
    TRACER.enable()


def disable() -> None:
    TRACER.disable()


def enabled() -> bool:
    return TRACER._enabled


def export(path: str | Path) -> Path:
    return TRACER.export(path)


def clear() -> None:
    TRACER.clear()
