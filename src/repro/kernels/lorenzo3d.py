"""Pallas TPU kernel: fused dual-quantization + 3-D Lorenzo residual
(TPU-SZ stages 1-2, the compression hot loop).

Tiling: the field is carved into (8, 64, 128) VMEM tiles — the (64, 128)
trailing face is lane-aligned (8x128 VREG lanes, f32 tile 256 KiB), and the
leading 8 planes give the VPU long contiguous runs. Prediction is *per
tile* (resets at tile borders) — exactly GPU-SZ's independent-block design
(paper §V-A observes the resulting rate penalty; our roofline pass measures
it at < 2% for 64^3+ fields).

The residual uses roll+iota-select instead of pad/concat so every op is a
lane-local shift — no scatter, no gather, MXU untouched; this kernel is
purely VPU + DMA and its roofline term is HBM bandwidth.

Byte-traffic accounting (B/pt; ``br`` = achieved bits/value, ~5 at the
paper's best-fit configs):

  =====================================  ============================
  pipeline stage                         HBM traffic per point
  =====================================  ============================
  this kernel (quantize+Lorenzo)         4 read + 4 write  = 8
  + bitpack.pack_codes (2 scatter-adds)  4 read + ~br/8    = ~5
  unfused encode total                   ~13
  fused encode (kernels.sz_fused)        ~9 worst case, ~5.9 effective
  =====================================  ============================

On the unfused path this kernel is therefore ~60% of encode traffic; the
fused kernel subsumes it and never materializes the int32 residuals, so
prefer ``sz_fused``/``ops.sz_compress_kernel(path="fused")`` on TPU and
keep this kernel as the XLA/interpret fallback and as the oracle the
byte-identity tests compare against.

The *effective* error bound (user bound minus the f32 roundoff guard, see
repro.core.sz) is data-dependent, so it arrives as a runtime SMEM scalar —
one compiled kernel serves every (field, eb) pair.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import default_interpret

TILE = (8, 64, 128)


def guarded_eb(x: jax.Array, eb) -> jax.Array:
    """Internal bound: user eb shrunk for f32 quantize/dequantize roundoff
    (the shared policy in :func:`repro.core.sz.internal_bound`)."""
    from repro.core import sz

    return sz.internal_bound(jnp.max(jnp.abs(x)), eb)


def lorenzo_residual(q: jax.Array) -> jax.Array:
    """3-D Lorenzo residual of an int32 tile (prediction resets at the tile
    border): one backward difference per axis, as roll + iota select."""
    d = q
    for axis in range(3):
        rolled = jnp.roll(d, 1, axis=axis)
        idx = jax.lax.broadcasted_iota(jnp.int32, d.shape, axis)
        d = d - jnp.where(idx == 0, 0, rolled)
    return d


def prefix_sum(d: jax.Array, axis: int) -> jax.Array:
    """Inclusive int32 prefix sum along ``axis`` of a tile, in
    ceil(log2(n)) shifted adds (roll + iota mask) — the Pallas TPU lowering
    has no cumsum.  Bit-exact against ``jnp.cumsum``: int32 adds wrap modulo
    2^32 whatever order they run in."""
    n = d.shape[axis]
    idx = jax.lax.broadcasted_iota(jnp.int32, d.shape, axis)
    s = 1
    while s < n:
        d = d + jnp.where(idx >= s, jnp.roll(d, s, axis=axis), 0)
        s *= 2
    return d


def _lorenzo_kernel(eb_ref, x_ref, delta_ref):
    inv2eb = 1.0 / (2.0 * eb_ref[0, 0])
    q = jnp.round(x_ref[...] * inv2eb).astype(jnp.int32)
    delta_ref[...] = lorenzo_residual(q)


@functools.partial(jax.jit, static_argnames=("interpret",))
def lorenzo3d_quantize(x: jax.Array, eb_i: jax.Array,
                       interpret: bool | None = None) -> jax.Array:
    """f32 (Z, Y, X) -> int32 Lorenzo residuals, tile-blocked. ``eb_i`` is
    the *guarded* bound (see guarded_eb). Shape must be TILE-padded."""
    z, y, w = x.shape
    tz, ty, tw = TILE
    assert z % tz == 0 and y % ty == 0 and w % tw == 0, "pad to TILE first"
    grid = (z // tz, y // ty, w // tw)
    eb_arr = jnp.asarray(eb_i, jnp.float32).reshape(1, 1)
    return pl.pallas_call(
        _lorenzo_kernel,
        out_shape=jax.ShapeDtypeStruct(x.shape, jnp.int32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec(TILE, lambda i, j, k: (i, j, k)),
        ],
        out_specs=pl.BlockSpec(TILE, lambda i, j, k: (i, j, k)),
        name="lorenzo3d_quantize",
        interpret=default_interpret(interpret),
    )(eb_arr, x)


def _reconstruct_kernel(eb_ref, delta_ref, out_ref):
    d = delta_ref[...]
    for axis in range(3):
        d = prefix_sum(d, axis)
    out_ref[...] = d.astype(jnp.float32) * (2.0 * eb_ref[0, 0])


@functools.partial(jax.jit, static_argnames=("interpret",))
def lorenzo3d_reconstruct(delta: jax.Array, eb_i: jax.Array,
                          interpret: bool | None = None) -> jax.Array:
    """Inverse: per-tile 3-fold prefix sum + dequantization (decompression)."""
    z, y, w = delta.shape
    tz, ty, tw = TILE
    assert z % tz == 0 and y % ty == 0 and w % tw == 0
    grid = (z // tz, y // ty, w // tw)
    eb_arr = jnp.asarray(eb_i, jnp.float32).reshape(1, 1)
    return pl.pallas_call(
        _reconstruct_kernel,
        out_shape=jax.ShapeDtypeStruct(delta.shape, jnp.float32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec(TILE, lambda i, j, k: (i, j, k)),
        ],
        out_specs=pl.BlockSpec(TILE, lambda i, j, k: (i, j, k)),
        name="lorenzo3d_reconstruct",
        interpret=default_interpret(interpret),
    )(eb_arr, delta)
