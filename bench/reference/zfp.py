"""Plain reference of the ``tpu-zfp`` stream (fixed-rate ZFP).

The format, as the codec documents it:

* the field (3-D; a 1-D field of N values is viewed as ``(N/64, 8, 8)``
  after zero padding to a multiple of 64) is edge-padded to multiples of 4
  and cut into 4x4x4 blocks in C order of the block grid; inside a block
  the value at offsets (a, b, c) along the field's axes is coefficient
  ``16a + 4b + c``;
* block floating point: ``e`` is the exponent of the block's largest
  magnitude (``max|x| < 2^e``, clipped to [-100, 127]) and the values
  become ``round(x * 2^(25 - e))`` as int32;
* ZFP's integer lifting transform along c, then b, then a; the negabinary
  map ``(i + 0xAAAAAAAA) ^ 0xAAAAAAAA``; coefficients sorted by total
  degree a+b+c (ties by (a, b, c)), which form ten groups;
* header: ``emax = e + 128`` (0 for an all-zero block) and, per group, the
  highest bit length of its coefficients (``gtops``): 58 bits;
* payload: ``rate * 64 - 58`` bits in ``ceil(that / 32)`` words, LSB first:
  bit planes 31 down to 0, and within a plane groups 0 to 9; a group takes
  part in plane p if ``p < gtops[group]`` and then contributes one bit of
  each of its coefficients, in sequency order; bits past the budget are
  dropped.

Decoding reads the kept bits back and inverts every stage
(``ints * 2^(e - 25)``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

Q = 25
EMAX_BIAS = 128
N_GROUPS = 10
HEADER_BITS = 8 + 5 * N_GROUPS
NB_MASK = 0xAAAAAAAA
CHUNK_BLOCKS = 1 << 17  # blocks per device call of the reference

_ABC = [(t // 16, (t // 4) % 4, t % 4) for t in range(64)]
SEQ = np.asarray(sorted(range(64), key=lambda t: (sum(_ABC[t]), _ABC[t])), np.int32)
INV_SEQ = np.argsort(SEQ).astype(np.int32)
GROUP = np.asarray([sum(_ABC[t]) for t in SEQ], np.int32)  # group of sequency slot s
SIZES = np.bincount(GROUP, minlength=N_GROUPS).astype(np.int32)
RANK = np.asarray([s - int(np.argmax(GROUP == GROUP[s])) for s in range(64)], np.int32)


def words_per_block(rate: int) -> int:
    return (rate * 64 - HEADER_BITS + 31) // 32


def view3d(shape: tuple[int, ...]) -> tuple[int, ...]:
    """The 3-D shape the codec codes a field of ``shape`` as."""
    if len(shape) == 1:
        return (-(-shape[0] // 64), 8, 8)
    return tuple(shape)


def grid(shape3: tuple[int, ...]) -> tuple[int, int, int]:
    return tuple(-(-s // 4) for s in shape3)


def chunk_layers(shape3: tuple[int, ...]) -> int:
    """Block layers (along axis 0) per reference call: the largest divisor
    of the layer count that keeps a call within ``CHUNK_BLOCKS`` blocks."""
    gz, gy, gx = grid(shape3)
    per = gy * gx
    best = 1
    for d in range(1, gz + 1):
        if gz % d == 0 and d * per <= max(CHUNK_BLOCKS, per):
            best = d
    return best


def stream_nbytes(n_blocks: int, rate: int) -> int:
    """Bytes of the host stream: payload words, emax and gtops."""
    return n_blocks * (4 * words_per_block(rate) + 1 + N_GROUPS)


def padded_field(x: jax.Array) -> jax.Array:
    """The 3-D array whose blocks the stream codes (edge padded)."""
    if x.ndim == 1:
        x = jnp.pad(x, (0, (-x.shape[0]) % 64)).reshape(view3d(x.shape))
    return jnp.pad(x, [(0, (-s) % 4) for s in x.shape], mode="edge")


def _carve(x: jax.Array) -> jax.Array:
    """(4cz, 4gy, 4gx) -> (64, cz*gy*gx): row 16a+4b+c, blocks in C order."""
    z, y, w = x.shape
    b = x.reshape(z // 4, 4, y // 4, 4, w // 4, 4).transpose(1, 3, 5, 0, 2, 4)
    return b.reshape(64, -1)


def _uncarve(b: jax.Array, shape3) -> jax.Array:
    z, y, w = shape3
    t = b.reshape(4, 4, 4, z // 4, y // 4, w // 4).transpose(3, 0, 4, 1, 5, 2)
    return t.reshape(shape3)


def _pow2(k: jax.Array, dtype) -> jax.Array:
    k = jnp.clip(k, -126, 127)
    return jax.lax.bitcast_convert_type(((k + 127).astype(jnp.uint32)) << 23,
                                        jnp.float32).astype(dtype)


def _fwd_lift(x, y, z, w):
    # ZFP's forward lifting step, exact in int32
    x += w; x >>= 1; w -= x
    z += y; z >>= 1; y -= z
    x += z; x >>= 1; z -= x
    w += y; w >>= 1; y -= w
    w += y >> 1; y -= w >> 1
    return x, y, z, w


def _inv_lift(x, y, z, w):
    y += w >> 1; w -= y >> 1
    y += w; w <<= 1; w -= y
    z += x; x <<= 1; x -= z
    y += z; z <<= 1; z -= y
    w += x; x <<= 1; x -= w
    return x, y, z, w


def _lift(v: jax.Array, axis: int, step) -> jax.Array:
    parts = [jax.lax.index_in_dim(v, i, axis, keepdims=False) for i in range(4)]
    return jnp.stack(step(*parts), axis=axis)


def _transform(blocks: jax.Array, dtype):
    """(64, T) float blocks -> (sequency coefficients uint32[64, T],
    emax int32[T], gtops int32[10, T])."""
    b = blocks.astype(dtype)
    maxabs = jnp.max(jnp.abs(b), axis=0).astype(jnp.float32)
    _, e = jnp.frexp(maxabs)
    e = jnp.clip(e, -100, 127).astype(jnp.int32)
    nonzero = maxabs > 0
    ints = jnp.round(b * _pow2(Q - e, dtype)[None, :]).astype(jnp.int32)
    v = ints.reshape(4, 4, 4, -1)
    for axis in (2, 1, 0):  # c, b, a
        v = _lift(v, axis, _fwd_lift)
    u = ((v.reshape(64, -1).astype(jnp.uint32) + jnp.uint32(NB_MASK))
         ^ jnp.uint32(NB_MASK))
    useq = u[SEQ]
    blen = 32 - jax.lax.clz(useq).astype(jnp.int32)
    gtops = jnp.stack([jnp.max(jnp.where((GROUP == g)[:, None], blen, 0), axis=0)
                       for g in range(N_GROUPS)])
    gtops = jnp.where(nonzero[None, :], gtops, 0)
    emax = jnp.where(nonzero, e + EMAX_BIAS, 0)
    return useq, emax, gtops


def _plane_layout(gtops: jax.Array, p, budget: int):
    """Bit position (64, T) of every sequency slot's plane-``p`` bit and
    whether the stream keeps it."""
    sizes = jnp.asarray(SIZES)[:, None]
    present = p < gtops  # (10, T)
    before = jnp.sum(sizes * jnp.maximum(gtops - 1 - p, 0), axis=0)  # earlier planes
    run = sizes * present
    woff = jnp.cumsum(run, axis=0) - run  # earlier groups of this plane
    pos = before[None, :] + woff[GROUP] + jnp.asarray(RANK)[:, None]
    keep = present[GROUP] & (pos < budget)
    return pos, keep


def _encode_words(useq: jax.Array, gtops: jax.Array, rate: int) -> jax.Array:
    budget = rate * 64 - HEADER_BITS
    wpb = words_per_block(rate)

    def plane(p, words):
        pos, keep = _plane_layout(gtops, p, budget)
        bit = (useq >> p.astype(jnp.uint32)) & jnp.uint32(1)
        val = jnp.where(keep, bit << (pos & 31).astype(jnp.uint32), jnp.uint32(0))
        widx = pos >> 5
        return words + jnp.stack([jnp.sum(jnp.where(widx == k, val, jnp.uint32(0)), axis=0)
                                  for k in range(wpb)])

    return jax.lax.fori_loop(0, 32, plane, jnp.zeros((wpb, useq.shape[1]), jnp.uint32))


def _decode_coeffs(words: jax.Array, gtops: jax.Array, rate: int) -> jax.Array:
    budget = rate * 64 - HEADER_BITS

    def plane(p, useq):
        pos, keep = _plane_layout(gtops, p, budget)
        widx = pos >> 5
        word = sum(jnp.where(widx == k, words[k][None, :], jnp.uint32(0))
                   for k in range(words.shape[0]))
        bit = (word >> (pos & 31).astype(jnp.uint32)) & jnp.uint32(1)
        return useq | jnp.where(keep, bit << p.astype(jnp.uint32), jnp.uint32(0))

    return jax.lax.fori_loop(0, 32, plane, jnp.zeros((64, words.shape[1]), jnp.uint32))


@functools.partial(jax.jit, static_argnames=("rate", "dtype"))
def encode_chunk(x: jax.Array, rate: int, dtype=jnp.float32):
    """(4cz, Y, X) padded chunk -> (words uint32[T, wpb], emax uint8[T],
    gtops uint8[T, 10]), blocks in C order."""
    useq, emax, gtops = _transform(_carve(x), dtype)
    words = _encode_words(useq, gtops, rate)
    return words.T, emax.astype(jnp.uint8), gtops.T.astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("rate", "shape3"))
def decode_chunk(words: jax.Array, emax: jax.Array, gtops: jax.Array, rate: int,
                 shape3: tuple[int, ...]) -> jax.Array:
    """Inverse of :func:`encode_chunk`: -> float32 array of ``shape3``."""
    g = gtops.astype(jnp.int32).T
    useq = _decode_coeffs(words.T, g, rate)
    u = useq[INV_SEQ]
    v = ((u ^ jnp.uint32(NB_MASK)) - jnp.uint32(NB_MASK)).astype(jnp.int32).reshape(4, 4, 4, -1)
    for axis in (0, 1, 2):  # a, b, c
        v = _lift(v, axis, _inv_lift)
    em = emax.astype(jnp.int32)
    scale = jnp.where(em > 0, _pow2(em - EMAX_BIAS - Q, jnp.float32), 0.0)
    return _uncarve(v.reshape(64, -1).astype(jnp.float32) * scale[None, :], shape3)


def chunks(shape: tuple[int, ...]):
    """(z0, z1, b0, b1) of each reference call: plane and block ranges of
    the padded 3-D view."""
    shape3 = tuple(s + (-s) % 4 for s in view3d(shape))
    cz = chunk_layers(shape3)
    gz, gy, gx = grid(shape3)
    per = gy * gx
    for lz in range(0, gz, cz):
        yield 4 * lz, 4 * (lz + cz), lz * per, (lz + cz) * per


def encode_host(x: jax.Array, rate: int, dtype=jnp.float32) -> dict:
    """A whole field -> the host stream (control and tests)."""
    xp = padded_field(x)
    outs = [encode_chunk(xp[z0:z1], rate, dtype) for z0, z1, _, _ in chunks(x.shape)]
    return {k: np.concatenate([np.asarray(o[i]) for o in outs])
            for i, k in enumerate(("words", "emax", "gtops"))}


def decode_host(stream: dict, shape: tuple[int, ...], rate: int) -> jax.Array:
    """Host stream -> the decoded field of ``shape``."""
    shape3 = tuple(s + (-s) % 4 for s in view3d(shape))
    outs = []
    for z0, z1, b0, b1 in chunks(shape):
        outs.append(decode_chunk(jnp.asarray(stream["words"][b0:b1]),
                                 jnp.asarray(stream["emax"][b0:b1]),
                                 jnp.asarray(stream["gtops"][b0:b1]), rate,
                                 (z1 - z0,) + shape3[1:]))
    return crop(jnp.concatenate(outs) if len(outs) > 1 else outs[0], shape)


def crop(x3: jax.Array, shape: tuple[int, ...]) -> jax.Array:
    if len(shape) == 1:
        return x3.reshape(-1)[:shape[0]]
    return x3[tuple(slice(0, s) for s in shape)]
