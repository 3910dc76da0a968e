"""Device-resident stream arena: whole-pytree snapshot compression in
O(#dtype-buckets) kernel launches instead of O(#leaves).

The per-leaf snapshot path (PR 4) compresses a training state leaf by leaf:
one jitted dispatch per leaf, one host round-trip per variable-length stream
to learn its ``used`` word count, one D2H copy per leaf.  For realistic
pytrees with hundreds of small parameters the coder is a rounding error —
dispatch and sync overhead dominate snapshot latency (FZ-GPU's observation,
applied to our snapshot hook).  This module removes all three O(#leaves)
terms:

  1. **flatten + size-bucket**: every float leaf flattens to a 1-D row and
     lands in a bucket keyed by its padded row length ``P`` (``BLOCK`` times
     the next power of two of its block count, so arbitrary pytrees
     collapse into O(log max-size) buckets);
  2. **one launch per bucket**: the bucket's rows stack into a ``[B, P]``
     megabatch; quantize + 1-D Lorenzo + zigzag + width + word-level pack
     run batched over the row axis (``bitpack.pack_codes_rows``).  Rows are
     padded with zero *codes* (masked before packing), so each row's stream
     is **byte-identical** to the per-leaf coder on the unpadded leaf.
     (Same-shape TILE-aligned 3-D *field* buckets route through the fused
     Pallas analogue instead — :func:`szk_compress_bucket` over
     ``kernels.sz_fused.fused_compress_batched``, persisted as codec
     ``arena-szk``.  It emits the tile-blocked stream, so it can never
     serve this flat path.);
  3. **one scan, one sync**: every row's variable-length words compact into
     one contiguous uint32 arena on the device: an exclusive scan over
     per-row word counts, then dense log-step shifts
     (``bitpack.compact_streams``).  Per-leaf
     ``(offset, used)`` descriptors live in a small sidecar array; the only
     host sync per snapshot is one ``used_total`` readback followed by one
     D2H copy of the arena slice.

Prediction is 1-D over the flattened leaf (row-major), so per-leaf streams
equal ``sz.compress(leaf.reshape(-1), eb)`` — the HACC layout of the paper,
traded for batchability exactly like GPU-SZ trades global prediction for
blocking.  ``dist.insitu`` wraps the same row codec in ``shard_map`` with a
batched halo exchange so partitioned leaves keep true left borders (one
collective per bucket, not per leaf).

ZFP is fixed-rate, so its arena needs no scan at all: the carved 4^3 blocks
of every leaf concatenate into one coder call and leaf ``l`` owns words
``[ranges[l] * wpb, ranges[l + 1] * wpb)`` analytically.

The host format (:class:`HostArena`) persists through
``checkpoint.manager`` as **one** ``arena_sNNN.bin`` per shard plus a
descriptor index in the manifest — replacing O(#leaves) ``leaf_i_sNNN.bin``
files; the legacy per-leaf format remains restorable (DESIGN.md §8).
"""

from __future__ import annotations

import dataclasses
import json
import threading
from functools import lru_cache, partial
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitpack
from repro.core import sz as sz_core
from repro.core import zfp as zfp_core
from repro.obs import trace as obs_trace

# Megabatch element budget per bucket launch: stacking multiplies every
# intermediate by the row count, so an unbounded bucket would OOM a device
# the per-leaf loop fits on (same posture as api.VMAP_ELEM_BUDGET).  Buckets
# larger than this split into chunks — still O(buckets) launches.
ROW_ELEM_BUDGET = 1 << 26

CODEC_SZ = "arena-sz"
CODEC_ZFP = "arena-zfp"
# Tile-blocked kernel streams (3-D TILE-aligned leaves batched through
# ``kernels.sz_fused.fused_compress_batched``): same arena + sidecar layout
# as CODEC_SZ, but each row is the *tile-major* stream of the 3-D tile
# coder, so restore decodes through the kernel path instead of the flat
# 1-D inverse Lorenzo.
CODEC_SZK = "arena-szk"


# ------------------------------------------------------------- planning ----


@dataclasses.dataclass(frozen=True)
class Bucket:
    """One size bucket of a snapshot plan: the (B, P) launch signature plus
    the per-leaf descriptor sidecar (all static)."""

    padded: int  # P: row length, a BLOCK multiple (power-of-two blocks)
    names: tuple  # leaf names (tree key paths)
    shapes: tuple  # original leaf shapes
    dtypes: tuple  # original leaf dtype names (restore casts back)
    ns: tuple  # flat element counts

    @property
    def rows(self) -> int:
        return len(self.names)

    @property
    def nbytes_raw(self) -> int:
        return sum(int(np.prod(s)) * np.dtype(d).itemsize
                   for s, d in zip(self.shapes, self.dtypes))


def row_length(n: int) -> int:
    """Bucket key: pad ``ceil(n / BLOCK)`` blocks to the next power of two.
    Geometric buckets bound both the padding waste (< 2x) and the bucket
    count (O(log max-leaf-size)), which is what makes launches-per-snapshot
    O(buckets) instead of O(distinct leaf sizes)."""
    nb = -(-n // bitpack.BLOCK)
    return bitpack.BLOCK << max(0, (nb - 1).bit_length())


def split_budget(group: list, row_len: int, elem_budget: int):
    """Split one bucket's entry list into megabatch chunks of at most
    ``max(1, elem_budget // row_len)`` rows — the shared chunking rule for
    every bucket planner (here and ``dist.insitu.plan_arena``), so the
    memory-budget math lives in exactly one place."""
    chunk = max(1, elem_budget // row_len)
    for s in range(0, len(group), chunk):
        yield group[s : s + chunk]


def plan_buckets(entries: Sequence[tuple], elem_budget: int = ROW_ELEM_BUDGET) -> list[Bucket]:
    """Group leaf descriptors ``(name, shape, dtype)`` into size buckets.

    Deterministic (insertion order within a bucket, buckets by ascending
    ``P``); buckets whose megabatch would exceed ``elem_budget`` elements
    split into chunks, so the launch count stays O(buckets) while no single
    launch oversubscribes device memory.
    """
    by_p: dict[int, list[tuple]] = {}
    for name, shape, dtype in entries:
        n = int(np.prod(shape)) if len(shape) else 1
        by_p.setdefault(row_length(n), []).append(
            (str(name), tuple(shape), str(np.dtype(dtype)), n))
    out = []
    for p in sorted(by_p):
        for sub in split_budget(by_p[p], p, elem_budget):
            out.append(Bucket(p, tuple(e[0] for e in sub), tuple(e[1] for e in sub),
                              tuple(e[2] for e in sub), tuple(e[3] for e in sub)))
    return out


def plan_for_tree(tree: Any, elem_budget: int = ROW_ELEM_BUDGET) -> list[Bucket]:
    """Bucket plan over every floating-point leaf of a pytree (keyed by
    ``jax.tree_util.keystr`` paths, the snapshot-hook naming)."""
    entries = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        if hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype, jnp.floating):
            entries.append((jax.tree_util.keystr(path), np.shape(leaf), leaf.dtype))
    return plan_buckets(entries, elem_budget)


# ----------------------------------------------------------- device side ---


@partial(jax.tree_util.register_dataclass,
         data_fields=("arena", "widths", "offsets", "counts", "total_bits",
                      "eb_i", "used"),
         meta_fields=("ns", "padded"))
@dataclasses.dataclass
class SZArena:
    """One bucket's compressed megabatch (a pytree; descriptors static).

    Row ``b``'s stream is ``arena[offsets[b] : offsets[b] + counts[b]]`` —
    byte-identical to ``bitpack.to_storage`` of the per-leaf coder on the
    same flat leaf.  ``used`` is the single scalar the host reads back
    before the one D2H copy of the arena slice."""

    arena: jax.Array  # uint32[capacity] contiguous streams, zeros past used
    widths: jax.Array  # uint8[B, P // BLOCK] block-width sidecar
    offsets: jax.Array  # int32[B] word offset of each row's stream
    counts: jax.Array  # int32[B] true payload words per row
    total_bits: jax.Array  # int32[B] per-row PackedCodes accounting
    eb_i: jax.Array  # float32[B] per-row internal (guarded) bounds
    used: jax.Array  # int32[] total arena words in use
    ns: tuple  # static: per-row flat element counts
    padded: int  # static: P


def _row_mask(padded: int, n: jax.Array) -> jax.Array:
    return jnp.arange(padded, dtype=jnp.int32)[None, :] < n[:, None]


def sz_encode_rows(rows: jax.Array, n: jax.Array, eb, capacity: int, *,
                   absmax=None, exchange=None):
    """Core batched row codec: f32[B, P] left-justified rows -> the arena
    pieces ``(arena, widths, offsets, counts, total_bits, eb_i, used)``.

    ``absmax``/``exchange`` are the distribution hooks: ``dist.insitu``
    passes the pmax-reduced global |x|max per row (so every shard derives
    the same bound) and a callable ``exchange(last) -> prev`` that ships
    each row's last real quantum one shard rightward — **one** collective
    for the whole bucket, replacing the per-leaf halo permute.  The
    defaults — masked local max, zero border — are the single-device
    semantics of ``sz.compress`` on the flat leaf.
    """
    mask = _row_mask(rows.shape[1], n)
    x = jnp.where(mask, rows.astype(jnp.float32), 0.0)
    if absmax is None:
        absmax = jnp.max(jnp.abs(x), axis=1)
    eb_i = sz_core.internal_bound(absmax, eb)  # [B]
    q = jnp.round(x / (2.0 * eb_i[:, None])).astype(jnp.int32)
    q = jnp.where(mask, q, 0)
    prev = None
    if exchange is not None:
        last = jnp.take_along_axis(q, jnp.maximum(n - 1, 0)[:, None], axis=1)
        prev = exchange(last)  # [B, 1] from the left shard (zeros at edge)
    if prev is None:
        prev = jnp.zeros((rows.shape[0], 1), jnp.int32)
    shifted = jnp.concatenate([prev.astype(jnp.int32), q[:, :-1]], axis=1)
    delta = jnp.where(mask, q - shifted, 0)  # 1-D Lorenzo, zeroed padding
    buf, counts, widths, total_bits = bitpack.pack_codes_rows(delta, n)
    arena, offsets, used = bitpack.compact_streams(buf, counts, capacity)
    return arena, widths, offsets, counts, total_bits, eb_i, used


def sz_decode_rows(arena: jax.Array, widths: jax.Array, offsets: jax.Array,
                   counts: jax.Array, eb_i: jax.Array, *, carry=None,
                   n=None) -> jax.Array:
    """Inverse of :func:`sz_encode_rows`: arena + sidecars -> f32[B, P] rows
    (entries past each row's ``n`` are meaningless; callers slice).

    ``carry`` is the reconstruction-side distribution hook: a callable
    receiving the per-row inclusive totals ``[B, 1]`` after the local
    cumsum (taken at index ``n - 1``, so ``n`` is required with it) and
    returning the exclusive cross-shard prefix to add — one log-step scan
    for the whole bucket; int32 associativity makes local-cumsum + carry
    bitwise equal to the global cumsum.  ``None`` is the single-device
    case.
    """
    padded = widths.shape[1] * bitpack.BLOCK
    j = jnp.arange(padded + 2, dtype=jnp.int32)
    idx = offsets[:, None] + j[None, :]
    vals = arena[jnp.clip(idx, 0, arena.shape[0] - 1)]
    buf = jnp.where(j[None, :] < counts[:, None], vals, jnp.uint32(0))
    delta = bitpack.unpack_codes_rows(buf, widths)
    q = jnp.cumsum(delta, axis=1)
    if carry is not None:
        totals = jnp.take_along_axis(q, jnp.maximum(n - 1, 0)[:, None], axis=1)
        q = q + carry(totals)
    return q.astype(jnp.float32) * (2.0 * eb_i[:, None])


def _stack_rows(leaves: Sequence[jax.Array], ns: Sequence[int], padded: int) -> jax.Array:
    rows = [jnp.pad(jnp.asarray(leaf).astype(jnp.float32).reshape(-1),
                    (0, padded - n)) for leaf, n in zip(leaves, ns)]
    return jnp.stack(rows)


def sz_capacity(ns: Sequence[int]) -> int:
    """Static worst-case arena words for a bucket: each row stores at most
    ``min(2 * sum(width), n + 2)`` words (see ``bitpack.pack_codes_rows``)."""
    return int(sum(min(2 * 32 * (-(-n // bitpack.BLOCK)), n + 2) for n in ns))


@partial(jax.jit, static_argnames=("ns", "padded"))
def _sz_compress_bucket(leaves: tuple, eb, ns: tuple, padded: int) -> SZArena:
    rows = _stack_rows(leaves, ns, padded)
    n = jnp.asarray(ns, jnp.int32)
    arena, widths, offsets, counts, total_bits, eb_i, used = sz_encode_rows(
        rows, n, eb, sz_capacity(ns))
    return SZArena(arena, widths, offsets, counts, total_bits, eb_i, used,
                   tuple(ns), padded)


@partial(jax.jit, static_argnames=("ns", "padded"))
def _stage_rows(leaves: tuple, ns: tuple, padded: int) -> jax.Array:
    return _stack_rows(leaves, ns, padded)


def _donate_staging() -> bool:
    # CPU ignores donation of the staging buffer (shape never aliases an
    # output) and warns about it; accelerators recycle it into the arena.
    return jax.default_backend() != "cpu"


def _sz_encode_staged(rows: jax.Array, eb, ns: tuple, padded: int) -> SZArena:
    n = jnp.asarray(ns, jnp.int32)
    arena, widths, offsets, counts, total_bits, eb_i, used = sz_encode_rows(
        rows, n, eb, sz_capacity(ns))
    return SZArena(arena, widths, offsets, counts, total_bits, eb_i, used,
                   tuple(ns), padded)


@lru_cache(maxsize=None)
def _sz_encode_staged_jit(donate: bool):
    return jax.jit(_sz_encode_staged, static_argnames=("ns", "padded"),
                   donate_argnums=(0,) if donate else ())


def sz_compress_bucket(leaves: Sequence[jax.Array], bucket: Bucket, eb, *,
                       staged: bool = False) -> SZArena:
    """One launch: compress a bucket's leaves into a device arena.  The jit
    cache key is the bucket signature ``(ns, P)`` — a snapshot recompiles
    per bucket, never per leaf.

    ``staged=True`` is the overlapped-snapshot variant: the megabatch is
    first staged into a snapshot-owned ``[B, P]`` buffer (one jitted stack,
    which *copies* the leaves — so the sources may be mutated or donated by
    the next train step the moment this returns), and that buffer is
    **donated** into the encode, letting XLA recycle its memory into the
    arena outputs instead of keeping both alive for the lifetime of the
    snapshot slot.  Both variants produce byte-identical arenas."""
    if staged:
        rows = _stage_rows(tuple(leaves), bucket.ns, bucket.padded)
        return _sz_encode_staged_jit(_donate_staging())(
            rows, jnp.float32(eb), bucket.ns, bucket.padded)
    return _sz_compress_bucket(tuple(leaves), jnp.float32(eb), bucket.ns, bucket.padded)


@partial(jax.jit, static_argnames=("ns", "padded"))
def _sz_decompress_bucket(a: SZArena, ns: tuple, padded: int) -> tuple:
    rows = sz_decode_rows(a.arena, a.widths, a.offsets, a.counts, a.eb_i)
    return tuple(rows[b, : ns[b]] for b in range(len(ns)))


def sz_decompress_bucket(a: SZArena, bucket: Bucket) -> list[jax.Array]:
    """One launch: decode a bucket arena back to its (flat f32) leaves;
    callers reshape/cast via the bucket descriptors."""
    flats = _sz_decompress_bucket(a, a.ns, a.padded)
    return [f.reshape(s).astype(d) for f, s, d in
            zip(flats, bucket.shapes, bucket.dtypes)]


# ------------------------------------------------- kernel (tile) buckets ----


@jax.jit
def _stage_rows_3d(leaves: tuple) -> jax.Array:
    return jnp.stack([jnp.asarray(x).astype(jnp.float32) for x in leaves])


def _szk_encode_staged(x: jax.Array, eb, interpret: bool) -> SZArena:
    from repro.kernels import sz_fused as _szf  # lazy: core -> kernels only on use

    absmax = jnp.max(jnp.abs(x), axis=(1, 2, 3))
    # Per-row guarded bound from the row's own |x|max — identical to
    # ``lorenzo3d.guarded_eb`` on the TILE-aligned (hence unpadded) field,
    # so each row's stream matches ``ops.sz_compress_kernel`` bit for bit.
    eb_i = sz_core.internal_bound(absmax, eb)
    arena, widths, offsets, counts, total_bits, used = _szf.fused_compress_batched(
        x, eb_i, interpret=interpret)
    n = int(np.prod(x.shape[1:]))
    return SZArena(arena, widths, offsets, counts, total_bits, eb_i, used,
                   (n,) * x.shape[0], n)


@lru_cache(maxsize=None)
def _szk_encode_staged_jit(donate: bool):
    return jax.jit(_szk_encode_staged, static_argnames=("interpret",),
                   donate_argnums=(0,) if donate else ())


def szk_compress_bucket(leaves: Sequence[jax.Array], bucket: Bucket, eb, *,
                        interpret: Optional[bool] = None) -> SZArena:
    """One batched fused-kernel launch for a shape-uniform bucket of 3-D
    TILE-aligned leaves (``kernels.sz_fused.fused_compress_batched``):
    row ``b``'s arena slice is byte-identical to the tile-blocked stream of
    ``kernels.ops.sz_compress_kernel(leaf_b, eb)``.

    The stack into the ``[B, Z, Y, X]`` megabatch is itself the snapshot's
    staging copy (sources may be mutated or donated the moment this
    returns) and is donated into the encode, mirroring the staged flat
    path."""
    from repro.kernels import default_interpret

    assert len(set(bucket.shapes)) == 1, "kernel buckets are shape-uniform"
    x = _stage_rows_3d(tuple(leaves))
    return _szk_encode_staged_jit(_donate_staging())(
        x, jnp.float32(eb), default_interpret(interpret))


def szk_decompress_bucket(a: SZArena, bucket: Bucket, *,
                          interpret: Optional[bool] = None) -> list[jax.Array]:
    """One batched launch: decode a kernel-bucket arena back to its 3-D
    leaves (inverse of :func:`szk_compress_bucket`)."""
    from repro.kernels import default_interpret
    from repro.kernels import sz_fused as _szf

    shape = tuple(bucket.shapes[0])
    rows = _szf.fused_decompress_batched(a.arena, a.widths, shape, a.eb_i,
                                         interpret=default_interpret(interpret))
    return [rows[b].astype(d) for b, d in enumerate(bucket.dtypes)]


# -------------------------------------------------------------- ZFP arena --


@partial(jax.tree_util.register_dataclass,
         data_fields=("words", "emax", "gtops"),
         meta_fields=("ranges", "rate"))
@dataclasses.dataclass
class ZFPArena:
    """Fixed-rate arena: every leaf's 4^3 blocks coded in one call.  Leaf
    ``l`` owns block rows ``[ranges[l], ranges[l+1])`` and therefore arena
    words ``[ranges[l] * wpb, ranges[l+1] * wpb)`` — offsets are analytic,
    no scan, no sidecar."""

    words: jax.Array  # uint32[NB * wpb] flat contiguous streams
    emax: jax.Array  # uint8[NB]
    gtops: jax.Array  # uint8[NB, 10]
    ranges: tuple  # static: per-leaf block starts, len = n_leaves + 1
    rate: int  # static


def zfp_ranges(shapes: Sequence[tuple]) -> tuple:
    starts = [0]
    for s in shapes:
        starts.append(starts[-1] + zfp_core.n_blocks_for(s))
    return tuple(starts)


@partial(jax.jit, static_argnames=("shapes", "rate"))
def _zfp_compress_bucket(leaves: tuple, shapes: tuple, rate: int) -> ZFPArena:
    blocks = jnp.concatenate([zfp_core._carve_blocks(x.astype(jnp.float32))
                              for x in leaves])
    u, emax, gtops = zfp_core.blocks_transform(blocks)
    words = zfp_core.encode_words(u, gtops, rate)
    return ZFPArena(words.reshape(-1), emax, gtops.astype(jnp.uint8),
                    zfp_ranges(shapes), rate)


def zfp_compress_bucket(leaves: Sequence[jax.Array], rate: int) -> ZFPArena:
    """One launch: fixed-rate compress any number of 3-D leaves.  Each
    leaf's slice is byte-identical to ``zfp.compress(leaf, rate)``."""
    shapes = tuple(tuple(np.shape(x)) for x in leaves)
    return _zfp_compress_bucket(tuple(leaves), shapes, rate)


def zfp_leaf_view(a: ZFPArena, i: int, shape) -> zfp_core.ZFPCompressed:
    """Descriptor-based view of leaf ``i``'s stream inside the arena."""
    b0, b1 = a.ranges[i], a.ranges[i + 1]
    wpb = zfp_core.payload_words(a.rate)
    return zfp_core.from_words(a.words[b0 * wpb : b1 * wpb],
                               a.emax[b0:b1], a.gtops[b0:b1], shape, a.rate)


@partial(jax.jit, static_argnames=("shapes", "rate"))
def _zfp_decompress_bucket(a: ZFPArena, shapes: tuple, rate: int) -> tuple:
    wpb = zfp_core.payload_words(rate)
    blocks = zfp_core.blocks_from_stream(a.words.reshape(-1, wpb), a.emax,
                                         a.gtops, rate)
    out = []
    for i, s in enumerate(shapes):
        b0, b1 = a.ranges[i], a.ranges[i + 1]
        out.append(zfp_core._uncarve_blocks(blocks[b0:b1], s))
    return tuple(out)


def zfp_decompress_bucket(a: ZFPArena, shapes: Sequence[tuple]) -> list[jax.Array]:
    """One launch: decode every leaf of a fixed-rate arena."""
    return list(_zfp_decompress_bucket(a, tuple(tuple(s) for s in shapes), a.rate))


# -------------------------------------------------------------- host side --


@dataclasses.dataclass
class HostArena:
    """Host-side view of one bucket's arena: the compacted word buffer plus
    the per-leaf descriptor sidecar, per shard.  Deliberately *not* a
    registered pytree — ``checkpoint.manager`` treats it as a single leaf
    and persists one ``arena_iNNNNN_sNNN.bin`` per shard (DESIGN.md §8).

    ``grid`` is the flat-axis shard count (1 on the single-device path);
    shard ``s`` holds row ``b``'s local stream at ``offsets[s][b]``, and
    restore stitches the per-shard residual segments before one global
    inverse Lorenzo — identical to the per-leaf ``insitu.host_decode``."""

    codec: str  # CODEC_SZ (the variable-rate format needing descriptors)
    names: tuple
    shapes: tuple
    dtypes: tuple
    ns: tuple
    padded: int
    grid: int  # shards over the flat axis
    halo: bool  # rows saw true left borders at shard seams
    eb_i: list  # per-row internal bounds (global, shard-invariant)
    shards: list  # per shard: {"arena", "widths", "offsets", "counts", "total_bits"}

    @property
    def nbytes_raw(self) -> int:
        return sum(int(np.prod(s)) * np.dtype(d).itemsize
                   for s, d in zip(self.shapes, self.dtypes))

    def nbytes_stored(self) -> int:
        """Stored bytes including the descriptor sidecars (widths, offsets,
        counts, total_bits), not just the word arena — the same quantity
        the manager's payload writer charges, so ratio regressions in the
        sidecar layout stay visible."""
        return sum(int(np.asarray(a).nbytes) for sh in self.shards
                   for a in sh.values())

    def accounting(self) -> dict:
        """Observatory record skeleton for this bucket (DESIGN.md §11):
        everything known at encode time — codec, field count, error-bound
        range, launch count, raw bytes.  The checkpoint manager's drain
        thread fills in the stored-bytes/timing half when it persists the
        payloads, so the two sides of the record come from the same pass."""
        rec = {
            "kind": "arena", "codec": self.codec,
            "n_fields": len(self.names),
            "launches": 1,  # the whole bucket compressed in one launch
            "shards": len(self.shards),
            "raw_bytes": int(self.nbytes_raw),
        }
        ebs = [float(e) for e in self.eb_i]
        if ebs:
            rec["eb_min"] = min(ebs)
            rec["eb_max"] = max(ebs)
        return rec


def payload_encode(blobs: dict) -> bytes:
    """Named arrays -> one self-describing byte payload (json header +
    concatenated array bytes).  The single wire format for every compressed
    shard payload (arena shards here, per-leaf streams in ``dist.insitu``)."""
    header, parts = {}, []
    for name in sorted(blobs):
        a = np.asarray(blobs[name])
        b = a.tobytes()
        header[name] = {"dtype": str(a.dtype), "shape": list(a.shape), "len": len(b)}
        parts.append(b)
    hdr = json.dumps(header).encode()
    return len(hdr).to_bytes(4, "little") + hdr + b"".join(parts)


def payload_decode(payload: bytes) -> dict:
    """Inverse of :func:`payload_encode`.  A short buffer (torn write,
    truncated file) is rejected up front with a clear error instead of
    surfacing as an opaque numpy reshape failure mid-decode — the
    checkpoint manager turns this into a ``SnapshotCorruptionError``."""
    if len(payload) < 4:
        raise ValueError(f"truncated payload: {len(payload)} bytes, "
                         "header length missing")
    hlen = int.from_bytes(payload[:4], "little")
    if 4 + hlen > len(payload):
        raise ValueError(f"truncated payload: header needs {4 + hlen} bytes, "
                         f"have {len(payload)}")
    header = json.loads(payload[4 : 4 + hlen])
    need = 4 + hlen + sum(int(m["len"]) for m in header.values())
    if len(payload) < need:
        raise ValueError(f"truncated payload: arrays need {need} bytes, "
                         f"have {len(payload)}")
    off = 4 + hlen
    out = {}
    for name in sorted(header):
        m = header[name]
        a = np.frombuffer(payload[off : off + m["len"]],
                          np.dtype(m["dtype"])).reshape(m["shape"])
        out[name] = a.copy() if a.ndim else a.reshape(())[()]
        off += m["len"]
    return out


def to_host(a: SZArena, bucket: Bucket, halo: bool = True,
            codec: str = CODEC_SZ) -> HostArena:
    """Pull a (single-shard) device arena to host: **one** scalar readback
    (``used``) followed by **one** D2H copy of the live arena slice — the
    per-leaf path needed both per leaf."""
    # span wraps the sync that was already mandatory — tracing adds none
    with obs_trace.span("arena.to_host", n_fields=len(bucket.names)):
        used = int(a.used)  # the single host sync
        shard = {
            "arena": np.asarray(a.arena[:used]),  # the single D2H copy
            "widths": np.asarray(a.widths),
            "offsets": np.asarray(a.offsets, np.int32),
            "counts": np.asarray(a.counts, np.int32),
            "total_bits": np.asarray(a.total_bits, np.int32),
        }
    return HostArena(codec, bucket.names, bucket.shapes, bucket.dtypes,
                     bucket.ns, a.padded, 1, halo,
                     [float(v) for v in np.asarray(a.eb_i)], [shard])


class PendingHostArena:
    """Deferred :class:`HostArena`: a thread-safe fetch-once handle.

    The overlapped snapshot path hands these to the checkpoint manager's
    drain thread instead of materialized host arenas, so the training
    thread never blocks on the per-bucket ``used`` readback or the arena
    D2H — ``result()`` performs them (exactly once, caching value or
    error) on whichever thread first asks.  The handle keeps the device
    arena alive until resolved; drop it after ``result()`` so the slot's
    device memory can be recycled."""

    def __init__(self, fetch: Callable[[], HostArena], names: tuple = ()):
        self._fetch = fetch
        self.names = tuple(names)  # leaf names, for accounting before fetch
        self._lock = threading.Lock()
        self._result: Optional[HostArena] = None
        self._error: Optional[BaseException] = None
        self._done = False

    def result(self) -> HostArena:
        with self._lock:
            if not self._done:
                try:
                    self._result = self._fetch()
                except BaseException as e:  # cached: every caller sees it
                    self._error = e
                finally:
                    self._fetch = None  # release the device-arena closure
                    self._done = True
            if self._error is not None:
                raise self._error
            return self._result


def to_host_async(a: SZArena, bucket: Bucket, halo: bool = True,
                  codec: str = CODEC_SZ) -> PendingHostArena:
    """Non-blocking :func:`to_host`: enqueue D2H transfers of the sidecar
    arrays (and the ``used`` scalar) behind the compression launch and
    return a handle.  Nothing here waits on the device — the one readback
    that *must* sync (``used``, which sizes the arena slice) happens inside
    ``result()``, typically on the manager's drain thread several train
    steps later, by which point the copies have long landed."""
    for arr in (a.used, a.widths, a.offsets, a.counts, a.total_bits, a.eb_i):
        arr.copy_to_host_async()
    return PendingHostArena(lambda: to_host(a, bucket, halo, codec),
                            names=bucket.names)


class SnapshotSlots:
    """Bounded pool of in-flight device snapshot buffers (default 2: one
    draining, one filling).  ``acquire()`` blocks the snapshot hook — i.e.
    the training thread — when every slot is occupied, which is the
    backpressure that keeps device memory for snapshots at
    O(slots x arena), not O(outstanding snapshots).  ``release()`` accepts
    (and ignores) positional args so it can be passed directly as the
    manager's ``on_complete`` callback."""

    def __init__(self, slots: int = 2):
        self.slots = int(slots)
        self._sem = threading.BoundedSemaphore(self.slots)
        self._lock = threading.Lock()
        self._in_flight = 0

    def acquire(self) -> None:
        self._sem.acquire()
        with self._lock:
            self._in_flight += 1

    def release(self, *_args) -> None:
        with self._lock:
            self._in_flight -= 1
        self._sem.release()

    @property
    def in_flight(self) -> int:
        with self._lock:
            return self._in_flight


def leaf_stream(h: HostArena, b: int, shard: int = 0) -> dict:
    """Leaf ``b``'s stream slice + sidecar on shard ``shard`` — the
    byte-identity surface (equals ``bitpack.to_storage`` of the per-leaf
    coder on the same flat row segment)."""
    sh = h.shards[shard]
    off, cnt = int(sh["offsets"][b]), int(sh["counts"][b])
    n_loc = int(h.ns[b]) // h.grid
    nb = -(-n_loc // bitpack.BLOCK) if n_loc else 0
    return {
        "words": sh["arena"][off : off + cnt],
        "widths": sh["widths"][b][:nb],
        "total_bits": int(sh["total_bits"][b]),
        "n": n_loc,
    }


def host_meta(h: HostArena) -> dict:
    """Manifest entry for a :class:`HostArena` leaf: the descriptor index
    (sidecars live in the binary payloads, descriptors in the manifest)."""
    return {
        "codec": h.codec,
        "arena": {
            "names": list(h.names),
            "shapes": [list(s) for s in h.shapes],
            "dtypes": list(h.dtypes),
            "ns": list(h.ns),
            "padded": h.padded,
            "grid": h.grid,
            "halo": bool(h.halo),
            "eb_i": list(h.eb_i),
        },
    }


def host_restore(meta: dict, payloads: list) -> dict:
    """Rebuild + decode every leaf of an arena bucket from its manifest
    descriptor index and per-shard payload bytes, without a mesh: stitch
    each leaf's per-shard residual segments, then run the global 1-D
    inverse Lorenzo — bitwise equal to the sharded decode for halo arenas
    (and to ``sz.decompress`` of the per-leaf flat stream).  Returns
    ``{name: np.ndarray}``."""
    info = meta["arena"]
    grid = int(info["grid"])
    if len(payloads) != grid:
        # same posture as the manager's shard-coverage check: a sparse
        # manifest must never leak a partial buffer through a decoded leaf
        raise IOError(f"arena leaf has {len(payloads)} shard payloads, "
                      f"needs {grid}")
    shards = [payload_decode(p) for p in payloads]
    if meta.get("codec") == CODEC_SZK:
        return _host_restore_szk(info, shards)
    out = {}
    for b, name in enumerate(info["names"]):
        n = int(info["ns"][b])
        n_loc = n // grid
        segs = []
        for sh in shards:
            off, cnt = int(sh["offsets"][b]), int(sh["counts"][b])
            nb = -(-n_loc // bitpack.BLOCK)
            packed = bitpack.from_storage(sh["arena"][off : off + cnt],
                                          sh["widths"][b][:nb], n_loc,
                                          int(sh["total_bits"][b]))
            segs.append(np.asarray(bitpack.unpack_codes(packed)))
        if not info["halo"]:
            # zero-border segments reconstruct shard-locally
            q = np.concatenate([np.cumsum(s, dtype=np.int32) for s in segs])
        else:
            # halo'd segments stitch into the global residual first; int32
            # wraparound matches the device cumsum bitwise
            q = np.cumsum(np.concatenate(segs) if grid > 1 else segs[0],
                          dtype=np.int32)
        x = q.astype(np.float32) * np.float32(2.0 * info["eb_i"][b])
        shape = tuple(info["shapes"][b])
        out[name] = x[:n].reshape(shape).astype(np.dtype(info["dtypes"][b]))
    return out


def _host_restore_szk(info: dict, shards: list) -> dict:
    """Kernel-bucket (``arena-szk``) restore: each row is the tile-major
    stream of the 3-D tile coder, decoded through the kernel XLA fallback —
    mesh-free, any backend, byte-compatible with the fused TPU path."""
    from repro.kernels import ops as kops  # lazy: core -> kernels only on use

    if int(info["grid"]) != 1:
        raise IOError(f"arena-szk leaves are replicated-only; got grid={info['grid']}")
    sh = shards[0]
    out = {}
    for b, name in enumerate(info["names"]):
        n = int(info["ns"][b])
        shape = tuple(info["shapes"][b])
        nb = n // bitpack.BLOCK  # TILE-aligned rows have only full blocks
        off, cnt = int(sh["offsets"][b]), int(sh["counts"][b])
        packed = bitpack.from_storage(sh["arena"][off : off + cnt],
                                      sh["widths"][b][:nb], n,
                                      int(sh["total_bits"][b]))
        x = kops.sz_decompress_kernel(packed, shape, shape,
                                      np.float32(info["eb_i"][b]), path="xla")
        out[name] = np.asarray(x).astype(np.dtype(info["dtypes"][b]))
    return out


# ------------------------------------------------------------ accounting ---


def arena_nbytes(a: SZArena) -> int:
    """True stored bytes across the bucket (sum of per-row accounting)."""
    bits = np.asarray(a.total_bits, np.int64)
    return int(np.sum((bits + 7) // 8))


def compression_ratio(a: SZArena, bucket: Bucket) -> float:
    return bucket.nbytes_raw / max(arena_nbytes(a), 1)
