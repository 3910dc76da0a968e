"""Jitted public wrappers around the Pallas kernels: padding/carving to tile
multiples, path dispatch, and integration with the repro.core bitstream
layer.  Every kernel compiles on TPU and runs in Pallas interpret mode
elsewhere (``repro.kernels.default_interpret``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitpack
from repro.core import zfp as zfp_core
from repro.kernels import kvc_attention as _kvc
from repro.kernels import lorenzo3d as _lor
from repro.kernels import sz_fused as _szf
from repro.kernels import zfp3d as _zfp
from repro.kernels import zfp_fused as _zfpf


# ------------------------------------------------------------- TPU-SZ -----


def _resolve_sz_path(path: str) -> str:
    """``fused`` = single-pass Pallas encode/decode (the TPU production
    path); ``xla`` = lorenzo3d kernel + word-level bitpack (the non-TPU /
    interpret fallback).  Both emit byte-identical tile-major streams."""
    if path == "auto":
        return "fused" if jax.default_backend() == "tpu" else "xla"
    if path not in ("fused", "xla"):
        raise ValueError(f"unknown SZ kernel path {path!r}; want fused|xla|auto")
    return path


def sz_compress_kernel(x: jax.Array, eb: float, path: str = "auto", eb_i=None):
    """Kernel-path SZ compress of a 3-D field: returns (PackedCodes,
    padded_shape, eb_i). Tile-blocked prediction (GPU-SZ blocking); the
    bitstream is the tile-major layout shared by both paths.

    ``eb_i`` overrides the internally-derived guarded bound — the sharded
    in-situ path (``repro.dist.insitu``) passes the bound computed from the
    *global* |x|max (via pmax) so every shard quantizes on the same grid;
    without the override each shard would derive a different bound from its
    local max and the per-shard streams would disagree with the
    single-device stream."""
    tz, ty, tw = _lor.TILE
    pads = [(0, (-s) % t) for s, t in zip(x.shape, (tz, ty, tw))]
    xp = jnp.pad(x, pads)
    if eb_i is None:
        eb_i = _lor.guarded_eb(xp, eb)
    if _resolve_sz_path(path) == "fused":
        packed = _szf.fused_compress(xp, eb_i)
    else:
        delta = _lor.lorenzo3d_quantize(xp, eb_i)
        packed = bitpack.pack_codes(_szf.tile_major_flatten(delta))
    return packed, xp.shape, eb_i


def sz_decompress_kernel(packed, padded_shape, orig_shape, eb_i, path: str = "auto") -> jax.Array:
    if _resolve_sz_path(path) == "fused":
        xr = _szf.fused_decompress(packed, tuple(padded_shape), eb_i)
    else:
        flat = bitpack.unpack_codes(packed)
        delta = _szf.tile_major_unflatten(flat, tuple(padded_shape))
        xr = _lor.lorenzo3d_reconstruct(delta, eb_i)
    return xr[tuple(slice(0, s) for s in orig_shape)]


# ------------------------------------------------------------ TPU-ZFP -----


def _pad_lanes(a: jax.Array, tile: int) -> jax.Array:
    """Pad a coefficient-major (rows, NB) operand to a ``tile`` multiple of
    blocks (zero blocks: emax 0, no payload)."""
    pad = (-a.shape[1]) % tile
    return jnp.pad(a, ((0, 0), (0, pad))) if pad else a


def zfp_transform_kernel(x: jax.Array):
    """Kernel-path ZFP stages 1-3 on a 3-D field: returns (u in sequency
    order, emax u8, gtops i32) matching repro.core.zfp.block_transform."""
    cm = zfp_core._carve_cm(x.astype(jnp.float32))
    nb = cm.shape[1]
    u, emax, gtops = _zfp.zfp3d_transform_cm(_pad_lanes(cm, _zfp.BLOCKS_PER_TILE))
    u = u[zfp_core.PERM, :nb].T  # sequency order (permutation stays jnp)
    return u, emax[0, :nb].astype(jnp.uint8), gtops[:, :nb].T


def _resolve_zfp_path(path: str) -> str:
    """``fused`` = single-pass Pallas encode/decode (``kernels.zfp_fused``,
    the TPU production path); ``xla`` = zfp3d transform kernel + the
    word-level jnp coder.  All paths (incl. ``repro.core.zfp``) emit
    byte-identical streams."""
    if path == "auto":
        return "fused" if jax.default_backend() == "tpu" else "xla"
    if path not in ("fused", "xla"):
        raise ValueError(f"unknown ZFP kernel path {path!r}; want fused|xla|auto")
    return path


def zfp_compress_kernel(x: jax.Array, rate: int, path: str = "auto") -> zfp_core.ZFPCompressed:
    """Kernel-path fixed-rate ZFP compress of a 3-D field.  Returns the same
    ``ZFPCompressed`` pytree as ``repro.core.zfp.compress`` — byte-identical
    ``words``/``emax``/``gtops`` on every path."""
    zfp_core.payload_words(rate)  # validates the rate
    cm = zfp_core._carve_cm(x.astype(jnp.float32))
    nb = cm.shape[1]
    if _resolve_zfp_path(path) == "fused":
        words, emax, gtops = _zfpf.fused_compress_cm(
            _pad_lanes(cm, _zfpf.BLOCKS_PER_TILE), rate)
    else:
        u, emax, gtops = _zfp.zfp3d_transform_cm(
            _pad_lanes(cm, _zfp.BLOCKS_PER_TILE))
        words = zfp_core._encode_words_cm(u[zfp_core.PERM], gtops, rate)
    return zfp_core.ZFPCompressed(
        words[:, :nb].T, emax[0, :nb].astype(jnp.uint8),
        gtops[:, :nb].T.astype(jnp.uint8), x.shape, rate)


def zfp_decompress_kernel(c: zfp_core.ZFPCompressed, path: str = "auto") -> jax.Array:
    """Kernel-path decode of :func:`zfp_compress_kernel` output (also reads
    ``repro.core.zfp.compress`` streams — same layout)."""
    if _resolve_zfp_path(path) == "fused":
        nb = c.words.shape[0]
        t = _zfpf.BLOCKS_PER_TILE
        blocks = _zfpf.fused_decompress_cm(
            _pad_lanes(c.words.T, t), _pad_lanes(c.emax.astype(jnp.int32)[None, :], t),
            _pad_lanes(c.gtops.astype(jnp.int32).T, t), c.rate)
        return zfp_core._uncarve_cm(blocks[:, :nb], c.shape)
    return zfp_core.decompress(c)


# ---------------------------------------------- compressed-KV attention ----


def kvc_attention(q: jax.Array, k_codes, k_scale, v_codes, v_scale, index):
    """Fused dequant+attention decode step; pads cache to SEQ_CHUNK.
    q: (B, H, D) — repeat GQA heads before calling. ``index`` is a scalar
    shared position or a (B,) per-slot position vector (continuous
    batching: each lane attends to its own cache[0..index[b]])."""
    s = k_codes.shape[1]
    pad = (-s) % _kvc.SEQ_CHUNK
    if pad:
        zc = ((0, 0), (0, pad), (0, 0), (0, 0))
        zs = ((0, 0), (0, pad), (0, 0))
        k_codes = jnp.pad(k_codes, zc)
        v_codes = jnp.pad(v_codes, zc)
        k_scale = jnp.pad(k_scale, zs)
        v_scale = jnp.pad(v_scale, zs)
    return _kvc.kvc_decode_attention(q, k_codes, k_scale, v_codes, v_scale,
                                     jnp.asarray(index))
