"""Block-adaptive fixed-width bit packing — the TPU replacement for cuSZ's
warp-level Huffman stage.

Huffman coding is branchy and serial; the TPU VPU wants uniform lane work.
Quantization codes produced by the Lorenzo stage cluster tightly around zero,
so a per-block fixed width (8-bit header per block) recovers most of the
entropy-coding win while remaining fully vectorizable:

  * codes are zigzag-mapped to unsigned,
  * each block of ``BLOCK`` codes is packed at ``ceil(log2(max+1))`` bits,
  * a code of width ``w <= 32`` starting at bit offset ``p`` spans at most
    the two adjacent words ``p >> 5`` and ``(p >> 5) + 1``, so packing is
    exactly **two** shift/OR scatter-adds (bit positions never collide, so
    add == OR) over a worst-case-sized uint32 buffer, and unpacking is two
    gathers — not one pass per bit,
  * the *actual* compressed size is ``total_bits`` — the storage layer slices
    the buffer before writing (device buffers must be static-shaped in JAX).

Byte-traffic accounting (B/pt, worst-case-buffer writes included; ``br`` is
the achieved bitrate in bits/value):

  ========================  ==========================================
  stage                     HBM traffic per point
  ========================  ==========================================
  pack: read codes          4 B
  pack: 2 scatter-adds      2 x 4 B buffer write + 2 x 4 B read-modify
  unpack: 2 gathers         ~2 x br/8 B read (compressed words)
  unpack: write codes       4 B
  ========================  ==========================================

The seed implementation made **32** full-array scatter passes (one per bit);
the word-level formulation above does the same work in 2, an O(16x)
pass-count reduction.  The fused kernel path (``repro.kernels.sz_fused``)
eliminates the intermediate int32 code array entirely — see that module.

All arithmetic is int32/uint32; callers must keep ``n * 32 < 2**31`` per call
(the top-level API chunks large fields into partitions, mirroring the paper's
8 x 2^27 HACC partitioning).
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import trace as obs_trace

# §Perf iteration on the packer itself: per-block max-width is outlier
# sensitive, so smaller blocks adapt better. Measured on GRF density at a
# pk-gate-passing bound: 1024 -> 7.40 bpv, 128 -> 5.83, 64 -> 5.48 (header
# 8/64 = 0.125 bpv already charged). 64 is the sweet spot.
BLOCK = 64  # codes per packing block
_WIDTH_BITS = 8  # per-block header width charged to the bitstream


@partial(jax.tree_util.register_dataclass, data_fields=("words", "widths", "total_bits"),
         meta_fields=("n",))
@dataclasses.dataclass
class PackedCodes:
    """Bitstream produced by :func:`pack_codes` (a pytree; ``n`` is static)."""

    words: jax.Array  # uint32[capacity_words] worst-case sized buffer
    widths: jax.Array  # uint8[n_blocks] per-block code width (0..32)
    total_bits: jax.Array  # int32[] true payload size incl. headers
    n: int  # static: number of codes packed


def zigzag(v: jax.Array) -> jax.Array:
    """Map signed int32 -> unsigned so small magnitudes get small codes."""
    v = v.astype(jnp.int32)
    return ((v << 1) ^ (v >> 31)).astype(jnp.uint32)


def unzigzag(u: jax.Array) -> jax.Array:
    u = u.astype(jnp.uint32)
    return ((u >> 1).astype(jnp.int32)) ^ (-(u & 1).astype(jnp.int32))


def bitlength(u: jax.Array) -> jax.Array:
    """Exact integer bit length of uint32 (0 -> 0). No float round-off."""
    u = u.astype(jnp.uint32)
    w = jnp.zeros(u.shape, jnp.int32)
    v = u
    for s in (16, 8, 4, 2, 1):
        m = v >= jnp.uint32(1 << s)
        w = w + m.astype(jnp.int32) * s
        v = jnp.where(m, v >> s, v)
    return w + (v > 0).astype(jnp.int32)


def code_mask(w: jax.Array) -> jax.Array:
    """uint32 mask of the low ``w`` bits, exact for w in [0, 32]."""
    w = w.astype(jnp.int32)
    shift = (32 - jnp.maximum(w, 1)).astype(jnp.uint32)  # in [0, 31]
    return jnp.where(w == 0, jnp.uint32(0), jnp.uint32(0xFFFFFFFF) >> shift)


def or_sum(v: jax.Array, axis: int) -> jax.Array:
    """OR-reduce (keepdims) uint32 values whose set bits never collide, as
    an int32 sum: the Pallas TPU lowering reduces signed integers only, and
    disjoint bits add without carries, so the sum *is* the OR."""
    s = jnp.sum(jax.lax.bitcast_convert_type(v, jnp.int32), axis=axis, keepdims=True)
    return jax.lax.bitcast_convert_type(s, jnp.uint32)


def _block_layout(n: int, block: int) -> tuple[int, int]:
    n_blocks = -(-n // block)
    padded = n_blocks * block
    return n_blocks, padded


def exclusive_cumsum(x: jax.Array, axis: int = -1) -> jax.Array:
    """Exclusive prefix sum along ``axis`` (the stream-offset primitive every
    compaction in this codebase derives from)."""
    return jnp.cumsum(x, axis=axis) - x


def compact_streams(rows: jax.Array, counts: jax.Array, capacity: int):
    """Concatenate variable-length streams into one dense word arena.

    ``rows`` is ``uint32[R, W]`` — R streams, each dense from word 0 and
    ``counts[r] <= W`` words long (words past a row's count are ignored).
    Returns ``(words, offsets, used)``: ``words`` is ``uint32[capacity]``
    with stream ``r`` occupying ``words[offsets[r] : offsets[r] +
    counts[r]]`` back-to-back in row order (zeros from ``used =
    counts.sum()`` on), no per-stream host sync.

    The compaction is order-preserving log-step shifting over the flat
    ``R * W`` words, with no gather and no search: word ``j < counts[r]``
    of row ``r`` must move left by its row's displacement ``d[r] = r * W -
    offsets[r]`` (the invalid words before it), which never decreases
    along the array.  For each bit ``k`` of the largest possible
    displacement, every valid word whose ``d`` has bit ``k`` set moves
    left by ``2**k`` — a dense pull of the array shifted by a static
    ``2**k`` and a select, carrying the word and its ``d`` (``-1`` marks
    an empty slot).  Least significant bit first, with non-decreasing
    displacements, two valid words never land on one slot: their gap
    after the low bits exceeds what the low bits of ``d`` took off it.
    Each pass is one elementwise sweep at HBM speed, ``ceil(log2(R * W))``
    of them; a gather of the same words runs at a small fraction of that
    on the TPU.

    This is the single compaction shared by the fused-kernel stream
    assembler (``kernels.sz_fused``, rows = per-block payloads) and the
    snapshot arena (``core.arena`` / ``dist.insitu``, rows = per-leaf
    worst-case buffers).
    """
    with obs_trace.scope("bitpack.compact"):
        n_rows, width = rows.shape
        counts = counts.astype(jnp.int32)
        offsets = exclusive_cumsum(counts)
        used = jnp.sum(counts)
        shift = jnp.arange(n_rows, dtype=jnp.int32) * width - offsets  # d[r]
        valid = jnp.arange(width, dtype=jnp.int32)[None, :] < counts[:, None]
        disp = jnp.where(valid, shift[:, None], -1).reshape(-1)
        words = rows.reshape(-1)
        for k in range((max(n_rows - 1, 0) * width).bit_length()):
            step = 1 << k
            nxt_w = jnp.concatenate([words[step:], jnp.zeros((step,), words.dtype)])
            nxt_d = jnp.concatenate([disp[step:], jnp.full((step,), -1, jnp.int32)])
            pull = (nxt_d >= 0) & ((nxt_d >> k) & 1 == 1)
            keep = (disp >> k) & 1 == 0  # false for an empty slot (-1)
            words = jnp.where(pull, nxt_w, words)
            disp = jnp.where(pull, nxt_d, jnp.where(keep, disp, -1))
        words = jnp.pad(words, (0, max(capacity - words.shape[0], 0)))[:capacity]
        i = jnp.arange(capacity, dtype=jnp.int32)
        words = jnp.where(i < used, words, jnp.uint32(0))
    return words, offsets, used


@partial(jax.jit, static_argnames=("block",))
def pack_codes(codes: jax.Array, block: int = BLOCK) -> PackedCodes:
    """Pack signed int32 ``codes`` (flat) into a block-adaptive bitstream."""
    n = codes.shape[0]
    if n * 32 >= 2**31:
        raise ValueError(f"pack_codes: n={n} too large for int32 bit offsets; chunk the field")
    with obs_trace.scope("bitpack.pack"):
        return _pack_codes(codes, block)


def _pack_codes(codes: jax.Array, block: int) -> PackedCodes:
    n = codes.shape[0]
    n_blocks, padded = _block_layout(n, block)
    u = zigzag(codes)
    u = jnp.pad(u, (0, padded - n))
    ub = u.reshape(n_blocks, block)

    width = jnp.max(bitlength(ub), axis=1)  # int32[n_blocks]
    block_bits = width * block
    base = exclusive_cumsum(block_bits)  # int32

    # Absolute bit position of bit 0 of every code.
    idx_in_block = jnp.arange(padded, dtype=jnp.int32) % block
    blk = jnp.arange(padded, dtype=jnp.int32) // block
    w_per = width[blk]
    pos0 = base[blk] + idx_in_block * w_per

    capacity = n + 2  # worst case: 32 bits/code => n words; +2 slack
    buf = jnp.zeros((capacity,), jnp.uint32)
    # Word-level packing: code bits [pos0, pos0+w) span at most the two
    # adjacent words pos0>>5 and (pos0>>5)+1.  Each code has bitlength <= its
    # block width w (so u < 2**w), which makes the split exact with plain
    # shifts: the low word takes u << (pos0 & 31) (uint32 truncation drops
    # exactly the straddling bits), the high word takes the remainder.
    # Padded codes (index >= n) have u == 0, so they contribute nothing and
    # need no mask; their (possibly out-of-range) indices are dropped.
    off = (pos0 & 31).astype(jnp.uint32)
    word0 = pos0 >> 5
    lo = u << off
    # u >> (32 - off) for off in [0, 31]; the two-step shift keeps every
    # shift amount in [0, 31] (single >>32 is undefined), and off == 0
    # correctly yields 0 (the code fits entirely in word0).
    hi = (u >> 1) >> (jnp.uint32(31) - off)
    buf = buf.at[word0].add(lo, mode="drop")
    buf = buf.at[word0 + 1].add(hi, mode="drop")

    total_bits = jnp.sum(block_bits) + jnp.int32(n_blocks * _WIDTH_BITS)
    return PackedCodes(buf, width.astype(jnp.uint8), total_bits, n)


@partial(jax.jit, static_argnames=("block",))
def unpack_codes(packed: PackedCodes, block: int = BLOCK) -> jax.Array:
    """Inverse of :func:`pack_codes`; returns int32[n]."""
    with obs_trace.scope("bitpack.unpack"):
        return _unpack_codes(packed, block)


def _unpack_codes(packed: PackedCodes, block: int) -> jax.Array:
    n = packed.n
    n_blocks, padded = _block_layout(n, block)
    width = packed.widths.astype(jnp.int32)
    block_bits = width * block
    base = exclusive_cumsum(block_bits)

    idx_in_block = jnp.arange(padded, dtype=jnp.int32) % block
    blk = jnp.arange(padded, dtype=jnp.int32) // block
    w_per = width[blk]
    pos0 = base[blk] + idx_in_block * w_per

    # Word-level unpacking: two gathers (the lo/hi words every code spans)
    # instead of one gather per bit.
    cap = packed.words.shape[0]
    off = (pos0 & 31).astype(jnp.uint32)
    word0 = jnp.clip(pos0 >> 5, 0, cap - 1)
    word1 = jnp.clip((pos0 >> 5) + 1, 0, cap - 1)
    lo = packed.words[word0] >> off
    # words[word1] << (32 - off); two-step shift so off == 0 yields 0.
    hi = (packed.words[word1] << 1) << (jnp.uint32(31) - off)
    mask = code_mask(w_per)
    u = (lo | hi) & mask
    return unzigzag(u[:n])


def pack_codes_rows(codes: jax.Array, n: jax.Array, block: int = BLOCK):
    """Batched :func:`pack_codes` over ``codes: int32[B, P]`` rows (P a
    ``block`` multiple) — one dispatch packs a whole megabatch of streams.

    Row ``b`` holds a stream of ``n[b]`` real codes left-justified in the
    row; the caller must have zeroed entries at index >= ``n[b]`` (zero
    codes contribute nothing to any block payload, so the packed stream is
    **byte-identical** to ``pack_codes(codes[b, :n[b]])`` — trailing
    all-zero blocks have width 0 and add no payload words).

    Returns ``(rows, counts, widths, total_bits)``:
      * ``rows``       uint32[B, P + 2] worst-case buffers, payload dense
                       from word 0 (the :func:`compact_streams` contract),
      * ``counts``     int32[B] true payload words per row,
      * ``widths``     uint8[B, P // block] block widths (``widths[b,
                       :ceil(n[b]/block)]`` equals the per-stream header),
      * ``total_bits`` int32[B] per-stream ``PackedCodes.total_bits``
                       (headers charged for ``ceil(n[b]/block)`` blocks
                       only, matching the per-leaf accounting).
    """
    bsz, padded = codes.shape
    if padded % block:
        raise ValueError(f"pack_codes_rows: row length {padded} not a {block} multiple")
    if padded * 32 >= 2**31:
        raise ValueError(f"pack_codes_rows: P={padded} too large for int32 bit offsets")
    n = n.astype(jnp.int32)
    n_blocks = padded // block
    u = zigzag(codes)
    ub = u.reshape(bsz, n_blocks, block)

    width = jnp.max(bitlength(ub), axis=2)  # int32[B, n_blocks]
    block_bits = width * block
    base = exclusive_cumsum(block_bits, axis=1)

    idx_in_block = jnp.arange(padded, dtype=jnp.int32) % block
    # per-code block values via repeat, not a [B, P] gather — XLA CPU lowers
    # the broadcast-in-dim ~2.5x faster and TPU avoids the gather unit
    w_per = jnp.repeat(width, block, axis=1)  # [B, P]
    pos0 = jnp.repeat(base, block, axis=1) + idx_in_block[None, :] * w_per

    capacity = padded + 2  # per-row worst case, as in pack_codes
    buf = jnp.zeros((bsz, capacity), jnp.uint32)
    off = (pos0 & 31).astype(jnp.uint32)
    word0 = pos0 >> 5
    lo = u << off
    hi = (u >> 1) >> (jnp.uint32(31) - off)
    rows_idx = jnp.arange(bsz, dtype=jnp.int32)[:, None]
    buf = buf.at[rows_idx, word0].add(lo, mode="drop")
    buf = buf.at[rows_idx, word0 + 1].add(hi, mode="drop")

    # Stored words per row: nominally 2*sum(width) (= ceil(64w/32) per
    # block), but capped at n + 2 exactly like ``to_storage`` slicing a
    # ``pack_codes`` buffer — a partial tail block charges the stream
    # layout 64*w bits, yet every bit past the last real code is zero and
    # real codes are <= 32 bits each, so words beyond n + 2 are always
    # zero and the per-leaf format never stores them.
    counts = jnp.minimum(2 * jnp.sum(width, axis=1), n + 2)
    nb_real = (n + block - 1) // block
    total_bits = jnp.sum(block_bits, axis=1) + nb_real * jnp.int32(_WIDTH_BITS)
    return buf, counts, width.astype(jnp.uint8), total_bits


def unpack_codes_rows(rows: jax.Array, widths: jax.Array, block: int = BLOCK) -> jax.Array:
    """Inverse of :func:`pack_codes_rows`: per-row dense payload buffers +
    block widths -> int32[B, P] codes (zeros beyond each row's real length,
    same two-gather word-level recipe as :func:`unpack_codes`)."""
    bsz, cap = rows.shape
    width = widths.astype(jnp.int32)  # [B, n_blocks]
    padded = width.shape[1] * block
    block_bits = width * block
    base = exclusive_cumsum(block_bits, axis=1)

    idx_in_block = jnp.arange(padded, dtype=jnp.int32) % block
    w_per = jnp.repeat(width, block, axis=1)  # repeat, not gather (as above)
    pos0 = jnp.repeat(base, block, axis=1) + idx_in_block[None, :] * w_per

    off = (pos0 & 31).astype(jnp.uint32)
    word0 = jnp.clip(pos0 >> 5, 0, cap - 1)
    word1 = jnp.clip((pos0 >> 5) + 1, 0, cap - 1)
    lo = jnp.take_along_axis(rows, word0, axis=1) >> off
    hi = (jnp.take_along_axis(rows, word1, axis=1) << 1) << (jnp.uint32(31) - off)
    u = (lo | hi) & code_mask(w_per)
    return unzigzag(u)


def packed_nbytes(packed: PackedCodes) -> jax.Array:
    """True storage bytes of the stream (payload + block headers)."""
    return (packed.total_bits + 7) // 8


def to_storage(packed: PackedCodes) -> dict[str, np.ndarray]:
    """Host-side: slice the worst-case buffer down to the real payload."""
    with obs_trace.span("bitpack.to_storage"):
        with obs_trace.span("sync.total_bits"):
            bits = int(packed.total_bits)
        n_words = (bits - int(packed.widths.shape[0]) * _WIDTH_BITS + 31) // 32
        with obs_trace.span("sync.copy", what="words"):
            words = np.asarray(packed.words[:n_words])
        with obs_trace.span("sync.copy", what="widths"):
            widths = np.asarray(packed.widths)
        return {"words": words, "widths": widths, "n": np.asarray(packed.n)}


def from_storage(words, widths, n: int, total_bits=None) -> PackedCodes:
    """Rebuild a :class:`PackedCodes` from its true-payload storage slice
    (inverse of :func:`to_storage`): zero-extend the sliced words back to
    the worst-case ``n + 2`` capacity the unpackers expect.  The shared
    rebuild for the checkpoint reader, ``dist.insitu`` and ``core.arena``
    host paths."""
    with obs_trace.span("bitpack.from_storage"):
        words = np.asarray(words, np.uint32)
        widths = np.asarray(widths, np.uint8)
        if total_bits is None:
            total_bits = int(np.sum(widths.astype(np.int64)) * BLOCK
                             + widths.shape[0] * _WIDTH_BITS)
        with obs_trace.span("bitpack.extend"):
            wfull = np.zeros(n + 2, np.uint32)
            wfull[: len(words)] = words
        return PackedCodes(jnp.asarray(wfull), jnp.asarray(widths),
                           jnp.int32(total_bits), n)
