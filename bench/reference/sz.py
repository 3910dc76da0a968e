"""Plain reference of the ``tpu-sz`` stream (error-bounded SZ, ABS mode).

The format, as the codec documents it:

* internal bound ``eb_i = eb * (0.995 - clip(|x|max / eb * 2^-22, 0, 0.25))``;
* prequantization ``q = round(x / (2 eb_i))`` (round half to even), then the
  exact integer 3-D Lorenzo residual of ``q`` (a backward difference along
  each axis, zero before the first plane);
* ``tiled`` streams (the fused kernels) pad the field with zeros to
  (8, 64, 128) tiles, restart prediction at every tile, quantize by the
  reciprocal ``x * (1 / (2 eb_i))`` and order the codes tile-major;
  ``global`` streams (the XLA path, and every 1-D field) predict over the
  whole array in C order; a 1-D field of N values is zero-padded to a cube
  of side ``ceil(N^(1/3))`` per partition of 2^27 values;
* codes are zigzag-mapped and packed in blocks of 64 at the block's bit
  width, LSB first; a block of width ``w`` takes exactly ``2w`` words, so
  the stream is ``2 * sum(w)`` words plus one width byte per block.

Decoding is the inverse: unpack (on the host), one inclusive prefix sum per
axis, and ``q * (2 eb_i)``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

TILE = (8, 64, 128)
BLOCK = 64
PARTITION = 1 << 27


def bound(absmax, eb, dtype=jnp.float32):
    """The internal bound the stream quantizes with (in ``dtype``)."""
    eb = jnp.asarray(eb, dtype)
    kappa = jnp.clip(absmax.astype(dtype) / eb * jnp.asarray(2.0**-22, dtype), 0.0, 0.25)
    return eb * (jnp.asarray(0.995, dtype) - kappa.astype(dtype))


def cube_side(n: int) -> int:
    return max(4, int(math.ceil(n ** (1 / 3))))


def coded_shapes(shape: tuple[int, ...], fmt: str) -> list[tuple[int, ...]]:
    """Shape of each coded array a field of ``shape`` becomes."""
    if len(shape) == 1:
        n = shape[0]
        return [(cube_side(min(PARTITION, n - s)),) * 3 for s in range(0, n, PARTITION)]
    if fmt == "tiled":
        return [tuple(s + (-s) % t for s, t in zip(shape, TILE))]
    return [tuple(shape)]


def coded_parts(x: jax.Array, fmt: str) -> list[jax.Array]:
    """The arrays the stream codes, zero-padded as the format says."""
    if x.ndim == 1:
        out = []
        for s, shp in zip(range(0, x.shape[0], PARTITION), coded_shapes(x.shape, fmt)):
            p = x[s:s + PARTITION]
            out.append(jnp.pad(p, (0, math.prod(shp) - p.shape[0])).reshape(shp))
        return out
    shp = coded_shapes(x.shape, fmt)[0]
    return [jnp.pad(x, [(0, t - s) for s, t in zip(x.shape, shp)])]


def _tiles(a: jax.Array) -> jax.Array:
    z, y, x = a.shape
    tz, ty, tx = TILE
    return a.reshape(z // tz, tz, y // ty, ty, x // tx, tx).transpose(0, 2, 4, 1, 3, 5)


def _untiles(t: jax.Array) -> jax.Array:
    gz, gy, gx, tz, ty, tx = t.shape
    return t.transpose(0, 3, 1, 4, 2, 5).reshape(gz * tz, gy * ty, gx * tx)


def _difference(a: jax.Array, axes) -> jax.Array:
    for ax in axes:
        first = jnp.zeros_like(jax.lax.slice_in_dim(a, 0, 1, axis=ax))
        prev = jax.lax.slice_in_dim(a, 0, a.shape[ax] - 1, axis=ax)
        a = a - jnp.concatenate([first, prev], axis=ax)
    return a


def _prefix_sum(a: jax.Array, axis: int) -> jax.Array:
    """Inclusive prefix sum along ``axis`` by log-step shifted adds (exact in
    int32; ``jnp.cumsum`` on the TPU is a reduce-window over the whole axis,
    quadratic in its length)."""
    n, step = a.shape[axis], 1
    while step < n:
        zeros = jnp.zeros_like(jax.lax.slice_in_dim(a, 0, step, axis=axis))
        a = a + jnp.concatenate([zeros, jax.lax.slice_in_dim(a, 0, n - step, axis=axis)], axis=axis)
        step *= 2
    return a


def _bitlength(u: jax.Array) -> jax.Array:
    return 32 - jax.lax.clz(u).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("fmt", "dtype"))
def encode_codes(xc: jax.Array, eb, fmt: str, dtype=jnp.float32):
    """Coded array -> (codes int32[n], widths uint8[n/64], eb_i float32)."""
    xd = xc.astype(dtype)
    eb_i = bound(jnp.max(jnp.abs(xd)), eb, dtype)
    if fmt == "tiled":
        q = jnp.round(xd * (jnp.asarray(1.0, dtype) / (2 * eb_i))).astype(jnp.int32)
        codes = _difference(_tiles(q), (3, 4, 5)).reshape(-1)
    else:
        q = jnp.round(xd / (2 * eb_i)).astype(jnp.int32)
        codes = _difference(q, range(q.ndim)).reshape(-1)
    u = ((codes << 1) ^ (codes >> 31)).astype(jnp.uint32)
    nb = -(-u.shape[0] // BLOCK)
    u = jnp.pad(u, (0, nb * BLOCK - u.shape[0])).reshape(nb, BLOCK)
    widths = jnp.max(_bitlength(u), axis=1).astype(jnp.uint8)
    return codes, widths, eb_i.astype(jnp.float32)


def _positions(widths: np.ndarray):
    """Bit position of every code, (n_blocks, 64) int64, and each block's
    width."""
    w = np.asarray(widths).astype(np.int64)
    return (64 * (np.cumsum(w) - w))[:, None] + np.arange(BLOCK, dtype=np.int64)[None, :] * w[:, None], w


def unpack(words: np.ndarray, widths: np.ndarray, n: int) -> np.ndarray:
    """Stream words -> int32[n] codes, on the host."""
    pos, w = _positions(widths)
    ext = np.concatenate([np.asarray(words, np.uint32), np.zeros((2,), np.uint32)])
    j = np.clip(pos >> 5, 0, ext.shape[0] - 2)
    off = (pos & 31).astype(np.uint32)
    del pos
    u = ext[j] >> off
    u |= np.where(off == 0, np.uint32(0), ext[j + 1] << ((32 - off) & 31))
    mask = np.where(w == 0, 0, np.uint32(0xFFFFFFFF) >> (32 - np.maximum(w, 1)).astype(np.uint32))
    u = (u & mask.astype(np.uint32)[:, None]).reshape(-1)[:n]
    return (u >> 1).astype(np.int32) ^ -(u & 1).astype(np.int32)


def pack(codes, widths: np.ndarray) -> np.ndarray:
    """int32 codes + widths -> the stream's ``2 * sum(widths)`` words, on the
    host.  The codes that share a word hold disjoint bits, so the word is
    their sum, which ``np.bincount`` adds exactly (float64, under 2^53)."""
    pos, w = _positions(widths)
    n_words = 2 * int(w.sum())
    c = np.asarray(codes)
    u = ((c << 1) ^ (c >> 31)).astype(np.uint32)
    u = np.pad(u, (0, pos.size - c.size)).reshape(pos.shape).astype(np.uint64)
    off = (pos & 31).astype(np.uint64)
    j = (pos >> 5).reshape(-1)
    del pos
    lo = ((u << off) & 0xFFFFFFFF).reshape(-1)
    hi = (u >> (32 - off)).reshape(-1)
    words = (np.bincount(j, weights=lo, minlength=n_words + 2)
             + np.bincount(j + 1, weights=hi, minlength=n_words + 2))
    return words[:n_words].astype(np.uint32)


@functools.partial(jax.jit, static_argnames=("shape", "fmt"))
def reconstruct(codes: jax.Array, eb_i, shape: tuple[int, ...], fmt: str) -> jax.Array:
    """int32 codes -> the decoded coded array (float32)."""
    if fmt == "tiled":
        gz, gy, gx = (s // t for s, t in zip(shape, TILE))
        d = codes.reshape(gz, gy, gx, *TILE)
        for ax in (3, 4, 5):
            d = _prefix_sum(d, ax)
        q = _untiles(d)
    else:
        q = codes.reshape(shape)
        for ax in range(len(shape)):
            q = _prefix_sum(q, ax)
    return q.astype(jnp.float32) * (2.0 * jnp.asarray(eb_i, jnp.float32))


def stream_nbytes(widths: np.ndarray) -> int:
    """Bytes of one host stream part: words, widths, ``n`` (int64) and
    ``eb_i`` (float32)."""
    return 8 * int(np.sum(widths.astype(np.int64))) + widths.size + 8 + 4


def encode_host(x: jax.Array, eb, fmt: str, dtype=jnp.float32) -> list[dict]:
    """A whole field -> host stream parts, as the reference would store them
    (used by the control and the tests; the benchmark's check compares the
    program's parts against :func:`encode_codes`)."""
    parts = []
    for xc in coded_parts(x, fmt):
        codes, widths, eb_i = encode_codes(xc, eb, fmt, dtype)
        widths = np.asarray(widths)
        parts.append({"words": pack(codes, widths), "widths": widths,
                      "n": np.asarray(codes.shape[0]), "eb_i": np.asarray(eb_i)})
    return parts


def decode_host(parts: list[dict], shape: tuple[int, ...], fmt: str) -> jax.Array:
    """Host stream parts -> the decoded field of ``shape`` (float32)."""
    outs = []
    for p, shp in zip(parts, coded_shapes(shape, fmt)):
        codes = unpack(p["words"], p["widths"], int(p["n"]))
        outs.append(reconstruct(jnp.asarray(codes), p["eb_i"], shp, fmt))
    return crop(outs, shape)


def crop(outs: list[jax.Array], shape: tuple[int, ...]) -> jax.Array:
    if len(shape) == 1:
        flat = [o.reshape(-1)[:min(PARTITION, shape[0] - i * PARTITION)]
                for i, o in enumerate(outs)]
        return jnp.concatenate(flat) if len(flat) > 1 else flat[0]
    return outs[0][tuple(slice(0, s) for s in shape)]
