"""Pallas TPU kernel: fused ZFP block stage — block-floating-point alignment
+ exact integer lifting transform + negabinary + per-group significance
(TPU-ZFP stages 1-3 + header derivation, the compression hot loop).

Tiling: 256 4x4x4 blocks per grid step, coefficient-major (blocks on the
lanes): in tile (64, 256) f32 (64 KiB), out tiles (64, 256) u32 + (1, 256)
emax + (10, 256) group tops. All VPU work:

* the block exponent uses the IEEE bit trick ((bits >> 23) & 0xff) instead
  of frexp — branch-free and exactly what the CUDA kernel does;
* 2^(Q - e) is constructed directly in exponent bits (exact powers of two,
  no transcendental);
* the lifting shift-add sequence vectorizes over the 256-block lanes, each
  axis's four coefficient quarters gathered by a static row permutation;
* group significance = 10 static row-run maxes (groups are a compile-time
  property of the 4x4x4 sequency layout).

Stages 1-3 are ``repro.core.zfp._transform_cm`` itself — the one shared
implementation of the bit-exact arithmetic that the core coder and the
fused encode kernel (``repro.kernels.zfp_fused``) also run.

This kernel backs the ``xla`` ZFP path: the embedded coding runs outside in
the word-level jnp coder (``repro.core.zfp.encode_words``), which costs one
HBM round-trip of the u32 coefficient planes.  The ``fused`` path
(``repro.kernels.zfp_fused``) extends this kernel with the same coder traced
in VMEM so the planes never leave the chip (see DESIGN.md §3 on the
header-hoisted schedule and why Huffman-style data-dependent-width stages
don't go on the VPU).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import zfp as zfp_core
from repro.kernels import default_interpret

BLOCKS_PER_TILE = 256


def _zfp_kernel(blocks_ref, u_ref, emax_ref, gtops_ref):
    u, emax, gtops = zfp_core._transform_cm(blocks_ref[...], zfp_core._IDENTITY)
    u_ref[...] = u
    emax_ref[...] = emax
    gtops_ref[...] = gtops


@functools.partial(jax.jit, static_argnames=("interpret",))
def zfp3d_transform_cm(blocks: jax.Array, interpret: bool | None = None):
    """Coefficient-major stages 1-3: f32 (64, NB) index-order blocks ->
    (u32 negabinary coefs (64, NB) [index order], emax i32 (1, NB), group
    top planes i32 (10, NB)).  NB must be a BLOCKS_PER_TILE multiple."""
    nb = blocks.shape[1]
    assert nb % BLOCKS_PER_TILE == 0, "pad block count first (ops.py)"
    t = BLOCKS_PER_TILE
    lanes = lambda rows: pl.BlockSpec((rows, t), lambda i: (0, i))
    return pl.pallas_call(
        _zfp_kernel,
        out_shape=(
            jax.ShapeDtypeStruct((64, nb), jnp.uint32),
            jax.ShapeDtypeStruct((1, nb), jnp.int32),
            jax.ShapeDtypeStruct((zfp_core.N_GROUPS, nb), jnp.int32),
        ),
        grid=(nb // t,),
        in_specs=[lanes(64)],
        out_specs=(lanes(64), lanes(1), lanes(zfp_core.N_GROUPS)),
        name="zfp3d_transform",
        interpret=default_interpret(interpret),
    )(blocks)


def zfp3d_transform(blocks: jax.Array, interpret: bool | None = None):
    """(NB, 4, 4, 4) f32 -> (u32 negabinary coefs [NB, 64] index order,
    emax i32[NB], per-group top planes i32[NB, 10]): the block-major view
    of :func:`zfp3d_transform_cm`."""
    u, emax, gtops = zfp3d_transform_cm(blocks.reshape(-1, 64).T, interpret=interpret)
    return u.T, emax[0], gtops.T
