"""Pallas TPU kernel: single-pass fused TPU-SZ encode/decode.

The unfused kernel path (``lorenzo3d`` + ``bitpack``) round-trips the int32
residual array through HBM between the prediction and packing stages:

  =============================  =================================
  stage                          HBM traffic per point
  =============================  =================================
  quantize+Lorenzo kernel        read f32 4 B + write i32 4 B
  pack: read codes               4 B
  pack: 2 scatter-adds           ~1 B (compressed words, r/m/w)
  -----------------------------  ---------------------------------
  total                          ~13 B/pt
  =============================  =================================

This module fuses dual-quantization + 3-D Lorenzo residual + zigzag +
per-block width computation + word-level packing into **one VMEM tile
pass**: the int32 residuals never exist in HBM.  Per (8, 64, 128) tile the
kernel emits 1024 width headers and the packed payload words of the tile's
1024 64-code blocks; :func:`bitpack.compact_streams` then concatenates the
per-block payloads into the dense global stream by log-step shifts (block
payloads are word-aligned because ``BLOCK * w = 64w`` bits is always a
whole number of uint32 words):

  =============================  =================================
  stage                          HBM traffic per point
  =============================  =================================
  fused kernel                   read f32 4 B + write words 4 B
                                 (worst-case static buffer; real
                                 payload is ~bitrate/8 B)
  stream assembly                ~24 B per pass: word and
  (log2(n) dense shift passes)   displacement read twice (here and
                                 shifted) and written once
  -----------------------------  ---------------------------------
  total                          ~8 B/pt kernel + ~24 x log2(n)
                                 B/pt assembly (~580 B/pt at 256^3,
                                 all of it streaming at HBM speed)
  =============================  =================================

Bitstream layout: identical to ``bitpack.pack_codes`` applied to the
**tile-major** flattening of the residual field (tiles in raster order, each
tile's (8, 64, 128) codes flattened C-order).  The XLA fallback path in
``kernels.ops`` uses exactly that recipe, so fused and fallback streams are
byte-identical and mutually decodable.

In-kernel packing is scatter-free: a code of width ``w`` at in-block bit
offset ``i*w`` spans at most two of the block's 64 payload words, so the
payload is a one-hot-masked sum over codes (a dense VPU reduction, no
VMEM scatter).  Decode inverts it with the transposed one-hot (gather-free).

The kernels compile for TPU (``tests/test_tpu_compile.py`` compiles each for
a v5e); elsewhere they run in Pallas interpret mode, which is how the
byte-identity tests run.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import bitpack
from repro.kernels import default_interpret
from repro.kernels import lorenzo3d as _lor
from repro.obs import trace as obs_trace

TILE = _lor.TILE  # (8, 64, 128)
CODES_PER_TILE = TILE[0] * TILE[1] * TILE[2]  # 65536
BLOCKS_PER_TILE = CODES_PER_TILE // bitpack.BLOCK  # 1024
# Per-block payload is at most 2 * 32 = 64 words (width <= 32).
WORDS_PER_BLOCK = 64


def _grid(padded_shape: tuple[int, ...]) -> tuple[int, int, int]:
    z, y, x = padded_shape
    tz, ty, tx = TILE
    assert z % tz == 0 and y % ty == 0 and x % tx == 0, "pad to TILE first"
    return z // tz, y // ty, x // tx


def tile_major_flatten(a: jax.Array) -> jax.Array:
    """(Z, Y, X) -> flat codes in tile-major order (the kernel bitstream
    order): tiles in raster order, each tile flattened C-order."""
    gz, gy, gx = _grid(a.shape)
    tz, ty, tx = TILE
    t = a.reshape(gz, tz, gy, ty, gx, tx).transpose(0, 2, 4, 1, 3, 5)
    return t.reshape(-1)


def tile_major_unflatten(flat: jax.Array, padded_shape: tuple[int, ...]) -> jax.Array:
    """Inverse of :func:`tile_major_flatten`."""
    gz, gy, gx = _grid(padded_shape)
    tz, ty, tx = TILE
    t = flat.reshape(gz, gy, gx, tz, ty, tx).transpose(0, 3, 1, 4, 2, 5)
    return t.reshape(padded_shape)


# ------------------------------------------------------------- encode -----
#
# In-kernel layout: a tile's 65536 codes as a (512, 128) lane-dense array —
# row r holds the C-order codes r*128 .. r*128+127, i.e. block 2r in lanes
# 0..63 and block 2r+1 in lanes 64..127 (BLOCK = 64 = half a lane row).  The
# per-block widths are (R, 1) columns ``wa`` / ``wb`` for the two halves, and
# a block's payload words land in the same half of the same row, so the
# (512, 128) word output *is* the (1024, 64) per-block payload matrix.  No
# in-kernel reshape ever splits the lane dimension (the TPU lowering has no
# such shape cast).

ROWS = CODES_PER_TILE // 128  # 512 rows of two 64-code blocks


def _half_layout(shape):
    """(lane iota, upper-half mask, in-block code index) for a row-pair array."""
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    upper = lane >= bitpack.BLOCK
    return lane, upper, lane & (bitpack.BLOCK - 1)


def _row_layout(wa: jax.Array, wb: jax.Array, shape):
    """Per-code (width, lo-word index, bit offset) of the row-pair layout:
    ``i * w = 32 * wlo + off`` for in-block code ``i`` of width ``w``."""
    lane, upper, i = _half_layout(shape)
    w = jnp.where(upper, wb, wa)
    bitpos = i * w
    return lane, upper, w, bitpos >> 5, (bitpos & 31).astype(jnp.uint32)


def _pack_rows(u: jax.Array, wa: jax.Array, wb: jax.Array) -> jax.Array:
    """Pack uint32[R, 128] row-pair codes into uint32[R, 128] payload words
    (block 2r's words in lanes 0..63 of row r, block 2r+1's in 64..127;
    dense from word 0, words >= 2*width are zero).

    Scatter-free: each code contributes to at most two words of its block
    (see ``bitpack.pack_codes``), realised per payload word ``j`` as a
    one-hot-masked lane reduction over each half.  The word loop is a
    ``fori_loop`` so the live intermediates stay at a few [R, 128] arrays
    (unrolled, the 64 iterations' temporaries overflow scoped VMEM).
    """
    lane, upper, _, wlo, off = _row_layout(wa, wb, u.shape)
    lo = u << off
    hi = (u >> 1) >> (jnp.uint32(31) - off)  # u >> (32 - off), 0 at off == 0
    zero = jnp.uint32(0)

    def word(j, out):
        # Bit positions never collide, so OR-ing == bit placement.
        contrib = jnp.where(wlo == j, lo, zero) | jnp.where(wlo + 1 == j, hi, zero)
        col_a = bitpack.or_sum(jnp.where(upper, zero, contrib), axis=1)
        col_b = bitpack.or_sum(jnp.where(upper, contrib, zero), axis=1)
        return out | jnp.where(lane == j, col_a, zero) | jnp.where(
            lane == bitpack.BLOCK + j, col_b, zero)

    return jax.lax.fori_loop(0, WORDS_PER_BLOCK, word, jnp.zeros(u.shape, jnp.uint32))


def _unpack_rows(words: jax.Array, wa: jax.Array, wb: jax.Array) -> jax.Array:
    """Inverse of :func:`_pack_rows` (gather-free: each payload word is
    broadcast across its half and one-hot-selected per code)."""
    lane, upper, w, wlo, off = _row_layout(wa, wb, words.shape)
    zero = jnp.uint32(0)

    def word(j, acc):
        # word j of each half, broadcast across its half: a one-hot lane
        # reduction (a lane slice at a traced offset does not lower)
        sel = bitpack.or_sum(jnp.where(lane == j, words, zero), axis=1)
        sel_b = bitpack.or_sum(jnp.where(lane == bitpack.BLOCK + j, words, zero), axis=1)
        wj = jnp.where(upper, sel_b, sel)
        w_lo, w_hi = acc
        return (w_lo | jnp.where(wlo == j, wj, zero),
                w_hi | jnp.where(wlo + 1 == j, wj, zero))

    init = (jnp.zeros(words.shape, jnp.uint32), jnp.zeros(words.shape, jnp.uint32))
    w_lo, w_hi = jax.lax.fori_loop(0, WORDS_PER_BLOCK, word, init)
    u = (w_lo >> off) | ((w_hi << 1) << (jnp.uint32(31) - off))
    return u & bitpack.code_mask(w)


def _pair_rows(a: jax.Array) -> jax.Array:
    """[nb, BLOCK] per-block rows -> [ceil(nb/2), 128] row-pair layout."""
    nb = a.shape[0]
    return jnp.pad(a, ((0, nb % 2), (0, 0))).reshape(-1, 2 * bitpack.BLOCK)


def _pair_widths(width: jax.Array):
    w = jnp.pad(width, (0, width.shape[0] % 2)).reshape(-1, 2)
    return w[:, 0:1], w[:, 1:2]


def _pack_blocks(u: jax.Array, width: jax.Array) -> jax.Array:
    """Pack uint32[nb, BLOCK] codes into uint32[nb, WORDS_PER_BLOCK] payload
    words: the kernel's row-pair packer (:func:`_pack_rows`) on the same
    blocks, two per row."""
    wa, wb = _pair_widths(width)
    out = _pack_rows(_pair_rows(u), wa, wb)
    return out.reshape(-1, WORDS_PER_BLOCK)[: u.shape[0]]


def _unpack_blocks(words: jax.Array, width: jax.Array) -> jax.Array:
    """Inverse of :func:`_pack_blocks` (via :func:`_unpack_rows`)."""
    wa, wb = _pair_widths(width)
    out = _unpack_rows(_pair_rows(words), wa, wb)
    return out.reshape(-1, bitpack.BLOCK)[: words.shape[0]]


def _encode_tile(eb, x, words_ref, widths_ref):
    """Shared tile body: quantize + 3-D Lorenzo + zigzag + width + pack one
    (8, 64, 128) f32 tile into its payload (512, 128) and width (2, 512)
    output blocks."""
    inv2eb = 1.0 / (2.0 * eb)
    q = jnp.round(x * inv2eb).astype(jnp.int32)
    u = bitpack.zigzag(_lor.lorenzo_residual(q)).reshape(ROWS, 128)
    _, upper, _ = _half_layout(u.shape)
    bl = bitpack.bitlength(u)
    wa = jnp.max(jnp.where(upper, 0, bl), axis=1, keepdims=True)
    wb = jnp.max(jnp.where(upper, bl, 0), axis=1, keepdims=True)
    words_ref[...] = _pack_rows(u, wa, wb).reshape(words_ref.shape)
    # widths leave as (2, 512) rows (row h = half h of every row pair): the
    # lane-dense transpose of the (512, 2) column pair
    lane = jax.lax.broadcasted_iota(jnp.int32, u.shape, 1)
    cols = jnp.where(lane == 0, wa, jnp.where(lane == 1, wb, 0))
    widths_ref[...] = cols.T[0:2].reshape(widths_ref.shape)


def _fused_encode_kernel(eb_ref, x_ref, words_ref, widths_ref):
    _encode_tile(eb_ref[0, 0], x_ref[...], words_ref, widths_ref)


def _fused_encode_kernel_batched(eb_ref, x_ref, words_ref, widths_ref):
    # batched grid: leading dim-1 block axis carries the batch row; the
    # per-row error bounds sit whole in SMEM, indexed by the same grid axis,
    # so one compiled kernel serves every row of the megabatch
    _encode_tile(eb_ref[pl.program_id(0), 0], x_ref[0], words_ref, widths_ref)


def _widths_from_rows(rows: jax.Array) -> jax.Array:
    """(n_tiles, 2, ROWS) kernel width rows -> int32[n_blocks] block order
    (block 2r + h of a tile is row h, lane r).  A flat gather: the
    equivalent transpose to a minor dimension of 2 takes the TPU compiler
    about 90 s at 256^3."""
    k = jnp.arange(rows.size, dtype=jnp.int32)
    tile, b = k // BLOCKS_PER_TILE, k % BLOCKS_PER_TILE
    return rows.reshape(-1)[tile * BLOCKS_PER_TILE + (b % 2) * ROWS + b // 2]


def _widths_to_rows(width: jax.Array) -> jax.Array:
    """Inverse of :func:`_widths_from_rows` (the same flat gather)."""
    k = jnp.arange(width.size, dtype=jnp.int32)
    tile, rest = k // BLOCKS_PER_TILE, k % BLOCKS_PER_TILE
    src = tile * BLOCKS_PER_TILE + 2 * (rest % ROWS) + rest // ROWS
    return width[src].reshape(-1, 2, ROWS)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _fused_encode(x: jax.Array, eb_i: jax.Array, interpret: bool | None = None):
    """One fused pass: f32 (Z, Y, X) -> per-block payload words + widths.

    Returns (uint32[n_blocks, WORDS_PER_BLOCK], int32[n_blocks]) in
    tile-major block order.  Residuals never leave VMEM.
    """
    gz, gy, gx = _grid(x.shape)
    n_tiles = gz * gy * gx
    eb_arr = jnp.asarray(eb_i, jnp.float32).reshape(1, 1)
    tidx = lambda i, j, k, gy=gy, gx=gx: i * gy * gx + j * gx + k
    words, widths = pl.pallas_call(
        _fused_encode_kernel,
        out_shape=(
            jax.ShapeDtypeStruct((n_tiles * ROWS, 128), jnp.uint32),
            jax.ShapeDtypeStruct((n_tiles, 2, ROWS), jnp.int32),
        ),
        grid=(gz, gy, gx),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec(TILE, lambda i, j, k: (i, j, k)),
        ],
        out_specs=(
            pl.BlockSpec((ROWS, 128), lambda i, j, k: (tidx(i, j, k), 0)),
            pl.BlockSpec((1, 2, ROWS), lambda i, j, k: (tidx(i, j, k), 0, 0)),
        ),
        name="sz_fused_encode",
        interpret=default_interpret(interpret),
    )(eb_arr, x)
    return (words.reshape(-1, WORDS_PER_BLOCK), _widths_from_rows(widths))


def _assemble_stream(block_words: jax.Array, width: jax.Array, n: int) -> bitpack.PackedCodes:
    """Concatenate per-block payloads into the dense global stream.

    Produces a ``PackedCodes`` byte-identical to ``bitpack.pack_codes`` on
    the tile-major flat residuals: block payloads are word-aligned, so the
    dense stream is one :func:`bitpack.compact_streams` call (exclusive
    scan of per-block word counts, then dense log-step shifts — no gather,
    no bit arithmetic).
    """
    # capacity n + 2 matches pack_codes' worst-case buffer exactly
    words, _, _ = bitpack.compact_streams(block_words, 2 * width, n + 2)
    total_bits = jnp.sum(width * bitpack.BLOCK) + jnp.int32(width.shape[0] * bitpack._WIDTH_BITS)
    return bitpack.PackedCodes(words, width.astype(jnp.uint8), total_bits, n)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_compress(x: jax.Array, eb_i: jax.Array,
                   interpret: bool | None = None) -> bitpack.PackedCodes:
    """Fused-kernel SZ encode of a TILE-padded f32 field.  The returned
    stream is byte-identical to the XLA fallback
    (``pack_codes(tile_major_flatten(lorenzo3d_quantize(x)))``)."""
    n = x.size
    if n * 32 >= 2**31:
        raise ValueError(f"fused_compress: n={n} too large for int32 bit offsets; chunk the field")
    block_words, width = _fused_encode(x, eb_i, interpret=interpret)
    return _assemble_stream(block_words, width, n)


# ----------------------------------------------------- batched / arena -----


@functools.partial(jax.jit, static_argnames=("interpret",))
def _fused_encode_batched(x: jax.Array, eb_i: jax.Array, interpret: bool | None = None):
    """Batched fused encode: (B, Z, Y, X) TILE-padded rows + per-row bounds
    -> per-block payload words/widths for **all** rows in one launch (grid
    gains a leading batch axis; rows never sync with the host)."""
    bsz = x.shape[0]
    gz, gy, gx = _grid(x.shape[1:])
    n_tiles = gz * gy * gx
    eb_arr = jnp.asarray(eb_i, jnp.float32).reshape(bsz, 1)
    tidx = lambda b, i, j, k, gz=gz, gy=gy, gx=gx: ((b * gz + i) * gy + j) * gx + k
    words, widths = pl.pallas_call(
        _fused_encode_kernel_batched,
        out_shape=(
            jax.ShapeDtypeStruct((bsz * n_tiles * ROWS, 128), jnp.uint32),
            jax.ShapeDtypeStruct((bsz * n_tiles, 2, ROWS), jnp.int32),
        ),
        grid=(bsz, gz, gy, gx),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1,) + TILE, lambda b, i, j, k: (b, i, j, k)),
        ],
        out_specs=(
            pl.BlockSpec((ROWS, 128), lambda b, i, j, k: (tidx(b, i, j, k), 0)),
            pl.BlockSpec((1, 2, ROWS), lambda b, i, j, k: (tidx(b, i, j, k), 0, 0)),
        ),
        name="sz_fused_encode_batched",
        interpret=default_interpret(interpret),
    )(eb_arr, x)
    return (words.reshape(-1, WORDS_PER_BLOCK), _widths_from_rows(widths))


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_compress_batched(x: jax.Array, eb_i: jax.Array, interpret: bool | None = None):
    """Arena-batched fused SZ encode: (B, Z, Y, X) rows -> one contiguous
    uint32 word arena holding every row's stream back-to-back.

    Returns ``(arena, widths, offsets, counts, total_bits, used)`` with
    ``arena[offsets[b] : offsets[b] + counts[b]]`` **byte-identical** to
    ``fused_compress(x[b], eb_i[b])``'s true payload (``to_storage``
    words) — all rows' tiles run under one batched grid and compact in
    the same device program (:func:`bitpack.compact_streams`);
    nothing about the layout needs a per-row host round-trip.
    """
    bsz = x.shape[0]
    n = int(np.prod(x.shape[1:]))
    if n * 32 >= 2**31:
        raise ValueError(f"fused_compress_batched: row n={n} too large; chunk the field")
    block_words, width = _fused_encode_batched(x, eb_i, interpret=interpret)
    nb = n // bitpack.BLOCK  # blocks per row (rows are TILE-padded => full)
    # Full blocks: 2*sum(width) <= n per row, so no n+2 truncation can occur
    # and the arena capacity is exactly the sum of per-row worst cases.
    arena, block_offsets, used = bitpack.compact_streams(
        block_words, 2 * width, bsz * (n + 2))
    width_rows = width.reshape(bsz, nb)
    offsets = block_offsets.reshape(bsz, nb)[:, 0]
    counts = 2 * jnp.sum(width_rows, axis=1)
    total_bits = (jnp.sum(width_rows, axis=1) * jnp.int32(bitpack.BLOCK)
                  + jnp.int32(nb * bitpack._WIDTH_BITS))
    return arena, width_rows.astype(jnp.uint8), offsets, counts, total_bits, used


# ------------------------------------------------------------- decode -----


def _decode_tile(eb, words, width_rows):
    """Shared tile body: unpack + unzigzag + 3-fold prefix sum + dequantize
    one tile's (512, 128) payload and (2, 512) widths back to its
    (8, 64, 128) f32 block."""
    cols = width_rows.T  # (512, 2): the two halves' widths per row pair
    u = _unpack_rows(words, cols[:, 0:1], cols[:, 1:2])
    q = bitpack.unzigzag(u).reshape(TILE)
    for axis in range(3):
        q = _lor.prefix_sum(q, axis)
    return q.astype(jnp.float32) * (2.0 * eb)


def _fused_decode_kernel(eb_ref, words_ref, widths_ref, out_ref):
    out_ref[...] = _decode_tile(eb_ref[0, 0], words_ref[...], widths_ref[0])


def _fused_decode_kernel_batched(eb_ref, words_ref, widths_ref, out_ref):
    out_ref[...] = _decode_tile(eb_ref[pl.program_id(0), 0], words_ref[...],
                                widths_ref[0]).reshape(out_ref.shape)


def _disassemble_stream(packed: bitpack.PackedCodes) -> tuple[jax.Array, jax.Array]:
    """Dense global stream -> per-block payload rows (inverse of
    :func:`_assemble_stream`; one XLA gather)."""
    width = packed.widths.astype(jnp.int32)
    return _gather_blocks(packed.words, width), width


def _gather_blocks(words: jax.Array, width: jax.Array) -> jax.Array:
    """Block payload rows (uint32[n_blocks, WORDS_PER_BLOCK], zero beyond
    ``2 * width`` words) gathered out of back-to-back dense ``words``."""
    with obs_trace.scope("sz_fused.disassemble"):
        wcount = 2 * width
        base = bitpack.exclusive_cumsum(wcount)
        j = jnp.arange(WORDS_PER_BLOCK, dtype=jnp.int32)
        idx = base[:, None] + j[None, :]
        vals = words[jnp.clip(idx, 0, words.shape[0] - 1)]
        return jnp.where(j[None, :] < wcount[:, None], vals, jnp.uint32(0))


@functools.partial(jax.jit, static_argnames=("padded_shape", "interpret"))
def fused_decompress(packed: bitpack.PackedCodes, padded_shape: tuple[int, ...],
                     eb_i: jax.Array, interpret: bool | None = None) -> jax.Array:
    """Fused-kernel SZ decode: unpack + unzigzag + 3-fold prefix sum +
    dequant in one VMEM tile pass (int32 codes never reach HBM)."""
    gz, gy, gx = _grid(padded_shape)
    n_tiles = gz * gy * gx
    block_words, width = _disassemble_stream(packed)
    words_c = block_words.reshape(n_tiles * ROWS, 128)
    widths_c = _widths_to_rows(width)
    eb_arr = jnp.asarray(eb_i, jnp.float32).reshape(1, 1)
    return pl.pallas_call(
        _fused_decode_kernel,
        out_shape=jax.ShapeDtypeStruct(padded_shape, jnp.float32),
        grid=(gz, gy, gx),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((ROWS, 128), lambda i, j, k, gy=gy, gx=gx: (i * gy * gx + j * gx + k, 0)),
            pl.BlockSpec((1, 2, ROWS), lambda i, j, k, gy=gy, gx=gx: (i * gy * gx + j * gx + k, 0, 0)),
        ],
        out_specs=pl.BlockSpec(TILE, lambda i, j, k: (i, j, k)),
        name="sz_fused_decode",
        interpret=default_interpret(interpret),
    )(eb_arr, words_c, widths_c)


@functools.partial(jax.jit, static_argnames=("padded_shape", "interpret"))
def fused_decompress_batched(arena: jax.Array, widths: jax.Array,
                             padded_shape: tuple[int, ...], eb_i: jax.Array,
                             interpret: bool | None = None) -> jax.Array:
    """Inverse of :func:`fused_compress_batched`: the contiguous word arena
    + per-row block widths -> (B, Z, Y, X) f32 rows in one batched launch.

    Rows live back-to-back in the arena, so the global exclusive scan of
    per-block word counts *is* the per-block offset table — the whole arena
    disassembles with one gather, no per-row bookkeeping.
    """
    bsz = widths.shape[0]
    gz, gy, gx = _grid(padded_shape)
    n_tiles = gz * gy * gx
    width = widths.reshape(-1).astype(jnp.int32)  # [B * blocks_per_row]
    block_words = _gather_blocks(arena, width)

    words_c = block_words.reshape(bsz * n_tiles * ROWS, 128)
    widths_c = _widths_to_rows(width)
    eb_arr = jnp.asarray(eb_i, jnp.float32).reshape(bsz, 1)
    tidx = lambda b, i, j, k, gz=gz, gy=gy, gx=gx: ((b * gz + i) * gy + j) * gx + k
    return pl.pallas_call(
        _fused_decode_kernel_batched,
        out_shape=jax.ShapeDtypeStruct((bsz,) + tuple(padded_shape), jnp.float32),
        grid=(bsz, gz, gy, gx),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((ROWS, 128), lambda b, i, j, k: (tidx(b, i, j, k), 0)),
            pl.BlockSpec((1, 2, ROWS), lambda b, i, j, k: (tidx(b, i, j, k), 0, 0)),
        ],
        out_specs=pl.BlockSpec((1,) + TILE, lambda b, i, j, k: (b, i, j, k)),
        name="sz_fused_decode_batched",
        interpret=default_interpret(interpret),
    )(eb_arr, words_c, widths_c)
