"""Pallas TPU kernel: single-pass fused TPU-ZFP encode/decode.

``zfp3d`` fuses stages 1-3 (block-float + lifting + negabinary + header) but
still writes the uint32 coefficient planes — 4 B/pt, a full copy of the
input — back to HBM for the XLA coder to re-read.  This module extends that
kernel with the plane-parallel word-level embedded coder from
``repro.core.zfp`` so the whole compression pipeline runs in one VMEM tile
pass and only the ``rate``-bit stream (+ 11 header bytes per 64 values)
leaves the chip:

  =============================  ==================================
  stage                          HBM traffic per point
  =============================  ==================================
  unfused: transform kernel      read f32 4 B + write u32 coefs 4 B
  unfused: XLA coder             read coefs 4 B + write rate/8 B
  -----------------------------  ----------------------------------
  unfused total                  ~12 + rate/8 B/pt
  fused encode kernel            read f32 4 B + write rate/8 B
  fused decode kernel            read rate/8 B + write f32 4 B
  =============================  ==================================

(The 4x4x4 block carve outside the kernel is an f32 transpose shared by all
paths; see DESIGN.md §3.)

The coder body is *the same code* as the XLA path: the kernel calls
``zfp_core._transform_cm`` / ``_encode_words_cm`` / ``_extract_coeffs`` /
``_inverse_cm`` — elementwise, static row slice/permutation and row-roll
jnp in the coefficient-major layout (coefficients and bit planes on
sublanes, blocks on lanes) that Pallas traces into the kernel — so the
three paths (core / xla / fused) emit byte-identical streams by
construction.  The only formulation difference is the decode word fetch:
the XLA path gathers each plane's 3 stream words along the word axis,
while the kernel (no dynamic gathers on the VPU) selects them with a
one-hot masked OR over the block's ``wpb`` words — ``wpb`` is static
(``ceil((rate*64 - 58) / 32)`` = ``2*rate - 1`` words per block, the 58-bit
header living outside the word array), so this is an unrolled
O(words-per-block) loop.

Kernel operands are coefficient-major too: blocks (64, NB) f32, words
(wpb, NB) u32, emax (1, NB) i32, gtops (10, NB) i32 — lane-dense, with
``BLOCKS_PER_TILE`` blocks on the lanes of each grid step.  The
``ZFPCompressed`` stream format stays block-major; the wrappers transpose.

The kernels compile for TPU (``tests/test_tpu_compile.py`` compiles each for
a v5e); elsewhere they run in Pallas interpret mode, which is how the
byte-identity tests run.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import zfp as zfp_core
from repro.kernels import default_interpret

BLOCKS_PER_TILE = 256  # blocks on the lanes of one grid step
N_GROUPS = zfp_core.N_GROUPS


def _lanes(rows: int):
    return pl.BlockSpec((rows, BLOCKS_PER_TILE), lambda i: (0, i))


def _fused_encode_kernel(blocks_ref, words_ref, emax_ref, gtops_ref, *, rate):
    u, emax, gtops = zfp_core._transform_cm(blocks_ref[...])
    words_ref[...] = zfp_core._encode_words_cm(u, gtops, rate)
    emax_ref[...] = emax
    gtops_ref[...] = gtops


@functools.partial(jax.jit, static_argnames=("rate", "interpret"))
def fused_compress_cm(blocks: jax.Array, rate: int, interpret: bool | None = None):
    """One fused pass on coefficient-major blocks: f32 (64, NB) -> (words
    u32[wpb, NB], emax i32[1, NB], gtops i32[10, NB]).  NB must be a
    BLOCKS_PER_TILE multiple (pad in ops.py); coefficients never leave VMEM."""
    nb = blocks.shape[1]
    assert nb % BLOCKS_PER_TILE == 0, "pad block count first (ops.py)"
    wpb = zfp_core.payload_words(rate)
    return pl.pallas_call(
        functools.partial(_fused_encode_kernel, rate=rate),
        out_shape=(
            jax.ShapeDtypeStruct((wpb, nb), jnp.uint32),
            jax.ShapeDtypeStruct((1, nb), jnp.int32),
            jax.ShapeDtypeStruct((N_GROUPS, nb), jnp.int32),
        ),
        grid=(nb // BLOCKS_PER_TILE,),
        in_specs=[_lanes(64)],
        out_specs=(_lanes(wpb), _lanes(1), _lanes(N_GROUPS)),
        name="zfp_fused_encode",
        interpret=default_interpret(interpret),
    )(blocks)


def _fused_decode_kernel(words_ref, emax_ref, gtops_ref, blocks_ref, *, rate):
    budget = rate * 64 - zfp_core._HEADER_BITS
    words = words_ref[...]  # (wpb, T)
    gtops = gtops_ref[...]
    OFF, keep = zfp_core._plane_offsets_cm(gtops, budget)
    w0 = OFF >> 5
    # One-hot fetch of the 3 words each plane payload spans (no dynamic
    # gathers on the VPU; wpb is static so the loop unrolls).
    zero = jnp.uint32(0)
    g0 = g1 = g2 = jnp.zeros(OFF.shape, jnp.uint32)
    for k in range(words.shape[0]):
        wk = words[k:k + 1]
        g0 = g0 | jnp.where(w0 == k, wk, zero)
        g1 = g1 | jnp.where(w0 + 1 == k, wk, zero)
        g2 = g2 | jnp.where(w0 + 2 == k, wk, zero)
    u = zfp_core._extract_coeffs(g0, g1, g2, OFF, keep, gtops)
    blocks_ref[...] = zfp_core._inverse_cm(u, emax_ref[...])


@functools.partial(jax.jit, static_argnames=("rate", "interpret"))
def fused_decompress_cm(words: jax.Array, emax: jax.Array, gtops: jax.Array,
                        rate: int, interpret: bool | None = None) -> jax.Array:
    """Inverse fused pass on coefficient-major operands: words u32[wpb, NB],
    emax i32[1, NB], gtops i32[10, NB] -> f32 blocks (64, NB).  The
    coefficient planes are reconstructed and inverted entirely in VMEM."""
    nb = words.shape[1]
    assert nb % BLOCKS_PER_TILE == 0, "pad block count first (ops.py)"
    wpb = zfp_core.payload_words(rate)
    assert words.shape[0] == wpb, f"stream has {words.shape[0]} words/block, rate {rate} needs {wpb}"
    return pl.pallas_call(
        functools.partial(_fused_decode_kernel, rate=rate),
        out_shape=jax.ShapeDtypeStruct((64, nb), jnp.float32),
        grid=(nb // BLOCKS_PER_TILE,),
        in_specs=[_lanes(wpb), _lanes(1), _lanes(N_GROUPS)],
        out_specs=_lanes(64),
        name="zfp_fused_decode",
        interpret=default_interpret(interpret),
    )(words, emax.astype(jnp.int32), gtops.astype(jnp.int32))


def fused_compress_blocks(blocks: jax.Array, rate: int,
                          interpret: bool | None = None):
    """Block-major wrapper of :func:`fused_compress_cm`: (NB, 4, 4, 4) f32
    blocks -> (words u32[NB, wpb], emax i32[NB], gtops i32[NB, 10])."""
    words, emax, gtops = fused_compress_cm(blocks.reshape(-1, 64).T, rate,
                                           interpret=interpret)
    return words.T, emax[0], gtops.T


def fused_decompress_blocks(words: jax.Array, emax: jax.Array,
                            gtops: jax.Array, rate: int,
                            interpret: bool | None = None) -> jax.Array:
    """Block-major wrapper of :func:`fused_decompress_cm`: stream + headers
    -> (NB, 4, 4, 4) f32 blocks."""
    b = fused_decompress_cm(words.T, emax.astype(jnp.int32)[None, :],
                            gtops.astype(jnp.int32).T, rate, interpret=interpret)
    return b.T.reshape(-1, 4, 4, 4)


def fused_compress_arena(blocks: jax.Array, rate: int,
                         interpret: bool | None = None):
    """Arena-batched fused ZFP encode: the concatenated 4^3 blocks of any
    number of leaves -> one **flat contiguous** uint32 word arena (plus the
    emax/gtops header sidecars) in a single launch.

    ZFP is fixed-rate, so the arena layout needs no scan and no host sync:
    a leaf owning block rows ``[b0, b1)`` owns arena words ``[b0 * wpb,
    b1 * wpb)`` analytically (``wpb = payload_words(rate)``), and each
    leaf's slice is byte-identical to its per-leaf
    :func:`fused_compress_blocks` stream — the batch grid axis already
    walks blocks, so batching leaves is pure concatenation.
    """
    words, emax, gtops = fused_compress_blocks(blocks, rate, interpret=interpret)
    return words.reshape(-1), emax, gtops


def fused_decompress_arena(arena: jax.Array, emax: jax.Array, gtops: jax.Array,
                           rate: int, interpret: bool | None = None) -> jax.Array:
    """Inverse of :func:`fused_compress_arena`: flat word arena + header
    sidecars -> (NB, 4, 4, 4) f32 blocks, one launch for every leaf."""
    wpb = zfp_core.payload_words(rate)
    return fused_decompress_blocks(arena.reshape(-1, wpb), emax, gtops, rate,
                                   interpret=interpret)
