"""Arithmetic shared by the per-layer readers (``ctx`` is ``run.Context``)."""

from __future__ import annotations


def idle_percent(ctx, phase: str):
    if ctx.trace is None:
        return None
    share = ctx.trace.idle_share(phase)
    return None if share is None else 100.0 * share


def hbm_percent(ctx, codec: str, phase: str):
    """Least bytes the phase must move (the raw field and its stream) over
    what the chip's HBM moves in the device time spent inside the phase."""
    if ctx.trace is None or ctx.codec != codec:
        return None
    busy = ctx.trace.busy_s(phase)
    if busy <= 0:
        return None
    return 100.0 * (ctx.raw_bytes + ctx.stream_bytes) / (ctx.peak["hbm_bytes_per_s"] * busy)


def link_gbps(ctx, phase: str):
    t = ctx.phase_s.get(phase, 0.0)
    return None if t <= 0 else ctx.stream_bytes / t / 1e9
