"""TPU-ZFP: fixed-rate transform compression of 3-D fields (cuZFP's evaluated
mode, re-derived for TPU).

Per 4x4x4 block, faithfully following ZFP's stages:
  1. block-floating-point: align to the block max exponent, convert to
     signed fixed point with ``Q`` fractional bits (exact integers),
  2. the exact integer *lifting* decorrelating transform along each axis
     (ZFP's fwd_lift / inv_lift shift-add sequences — bit-exact inverses),
  3. negabinary mapping so sign information lives in high bit planes,
  4. coefficients permuted to sequency order (total-degree sort),
  5. fixed-rate **embedded** truncation: bits are emitted in significance
     order (bit plane major, sequency group minor) until the per-block
     budget ``rate * 64`` bits is exhausted.

TPU adaptation (vs cuZFP): ZFP's group-testing coder interleaves per-bit
significance *tests* into the stream — a serial, branchy per-block loop that
is hostile to the TPU VPU. We hoist the same information into a per-block
header instead: the top occupied bit plane of each of the 10 sequency groups
(5 bits x 10 groups + 8-bit emax = 58 header bits, charged to the budget).
Given the header, the entire bit schedule (which (plane, group) emits where)
is a pure function of per-block integers, so encode and decode become
data-independent word assembly over bit positions — exactly the uniform
lane work the VPU wants. This recovers ZFP's per-coefficient adaptivity
(high-sequency coefficients with leading zeros cost nothing) without any
data-dependent branching.

The coder itself is **plane-parallel and word-level** (DESIGN.md §3): all 32
bit planes are processed at once as stream items instead of one serial pass
per plane.  Each plane's significant bits form a <= 64-bit payload
(``_plane_payloads``); the payload's placement is a pure function of the
header, so the ``rate*64``-bit stream is assembled with O(words-per-block)
masked shift/OR sums (``encode_words``) and read back with three word
gathers per plane (``decode_words``) — no per-bit-plane scatter/gather passes, and
no data-dependent control flow.  The emitted stream is bit-identical to the
original 32-pass formulation (tests pin embedded seed-reference streams).

The advertised rate is exact: every block consumes ``rate*64`` bits, so
CR = 32/rate precisely, matching cuZFP's fixed-rate contract.

Note the lifting transform is implemented with *integer shift-adds on the
VPU*, not as an MXU matmul: the lifted transform includes floor-shifts, so
the exact-integer form (required for bit-exact inversion) is not a linear
map. Recorded in DESIGN.md §3.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitpack
from repro.obs import trace as obs_trace

Q = 25  # fixed-point fractional bits; transform growth (< 2^3) keeps int32 safe
_NBMASK_VAL = 0xAAAAAAAA  # python int: jnp scalars are built per-call so the
# negabinary helpers stay usable inside Pallas bodies (a module-level device
# array would be a captured constant, which pallas_call rejects)
_EMAX_BIAS = 128  # stored emax = e + bias; 0 reserved for all-zero blocks
N_GROUPS = 10  # sequency groups: total degree i+j+k in 0..9
_HEADER_BITS = 8 + 5 * N_GROUPS  # emax + per-group top plane
BLOCK_SIDE = 4  # ZFP block edge; also the shard-seam alignment quantum


def shard_extent_aligned(extent: int, n_shards: int) -> bool:
    """Whether a field dimension of ``extent`` per shard may be partitioned
    into ``n_shards`` equal shards without changing the stream.

    ZFP's 4x4x4 blocks are self-contained (no cross-block prediction), so a
    partitioned field carves exactly the blocks the single-device coder
    carves *iff* every seam falls on a block boundary — i.e. the per-shard
    extent is a multiple of :data:`BLOCK_SIDE` whenever the axis is actually
    split.  A misaligned seam would make both neighbors edge-pad a block the
    single-device coder fills with real data, silently changing ``emax`` and
    the stream; ``repro.dist.insitu`` therefore *rejects* misaligned shards
    instead of approximating (DESIGN.md §7).  The global tail may stay
    ragged on non-partitioned axes — edge padding there is shard-local and
    identical to the single-device padding.
    """
    return n_shards <= 1 or extent % BLOCK_SIDE == 0


def _perm3() -> np.ndarray:
    """Sequency (total-degree) order over the 4x4x4 block, x fastest."""
    coords = [(i, j, k) for k in range(4) for j in range(4) for i in range(4)]
    idx = np.arange(64)
    key = sorted(idx, key=lambda t: (sum(coords[t]), coords[t][::-1]))
    return np.asarray(key, np.int32)


PERM = _perm3()
IPERM = np.argsort(PERM).astype(np.int32)

_COORDS = [(i, j, k) for k in range(4) for j in range(4) for i in range(4)]
GROUP_SIZES = np.bincount([sum(_COORDS[p]) for p in PERM], minlength=N_GROUPS)
GROUP_OF_COEF = np.asarray([sum(_COORDS[p]) for p in PERM], np.int32)  # (64,)
_gstart = np.concatenate([[0], np.cumsum(GROUP_SIZES)[:-1]])
RANK_IN_GROUP = np.asarray(
    [i - _gstart[GROUP_OF_COEF[i]] for i in range(64)], np.int32
)


@partial(jax.tree_util.register_dataclass, data_fields=("words", "emax", "gtops"),
         meta_fields=("shape", "rate"))
@dataclasses.dataclass
class ZFPCompressed:
    """Fixed-rate compressed field (a pytree; shape/rate are static)."""

    words: jax.Array  # uint32[n_blocks, words_per_block] embedded bitstream
    emax: jax.Array  # uint8[n_blocks] biased block exponent (0 = zero block)
    gtops: jax.Array  # uint8[n_blocks, 10] per-sequency-group top bit plane
    shape: tuple[int, ...]  # static original shape
    rate: int  # static bits/value


def _fwd_lift4(x, y, z, w):
    """ZFP fwd_lift on the four lanes of a length-4 transform axis."""
    x = x + w
    x = x >> 1
    w = w - x
    z = z + y
    z = z >> 1
    y = y - z
    x = x + z
    x = x >> 1
    z = z - x
    w = w + y
    w = w >> 1
    y = y - w
    w = w + (y >> 1)
    y = y - (w >> 1)
    return x, y, z, w


def _inv_lift4(x, y, z, w):
    """Exact inverse of :func:`_fwd_lift4` (ZFP inv_lift)."""
    y = y + (w >> 1)
    w = w - (y >> 1)
    y = y + w
    w = w << 1
    w = w - y
    z = z + x
    x = x << 1
    x = x - z
    y = y + z
    z = z << 1
    z = z - y
    w = w + x
    x = x << 1
    x = x - w
    return x, y, z, w


def fwd_lift(v: jax.Array) -> jax.Array:
    """ZFP forward lift along the last axis (length 4), exact int32."""
    return jnp.stack(_fwd_lift4(v[..., 0], v[..., 1], v[..., 2], v[..., 3]), axis=-1)


def inv_lift(v: jax.Array) -> jax.Array:
    """Exact inverse of :func:`fwd_lift` (ZFP inv_lift)."""
    return jnp.stack(_inv_lift4(v[..., 0], v[..., 1], v[..., 2], v[..., 3]), axis=-1)


def _lift3d(blocks: jax.Array) -> jax.Array:
    """Reference 3-D lift on (n, 4, 4, 4) blocks (the coder runs the same
    arithmetic coefficient-major, :func:`_fwd_lift_cm`)."""
    b = blocks
    for axis in (3, 2, 1):
        b = jnp.moveaxis(fwd_lift(jnp.moveaxis(b, axis, -1)), -1, axis)
    return b


def _inv_lift3d(blocks: jax.Array) -> jax.Array:
    b = blocks
    for axis in (1, 2, 3):  # reverse order of the forward pass
        b = jnp.moveaxis(inv_lift(jnp.moveaxis(b, axis, -1)), -1, axis)
    return b


def exact_exp2(k: jax.Array) -> jax.Array:
    """Exact 2^k for integer k in [-126, 127], built in IEEE exponent bits.
    (XLA's exp2 is a polynomial approximation — exp2(23.0) != 8388608 on
    CPU — which breaks block-float exactness; this never does.)"""
    k = jnp.clip(k.astype(jnp.int32), -126, 127)
    return jax.lax.bitcast_convert_type(((k + 127).astype(jnp.uint32)) << 23, jnp.float32)


def negabinary(i: jax.Array) -> jax.Array:
    u = i.astype(jnp.uint32)
    m = jnp.uint32(_NBMASK_VAL)
    return (u + m) ^ m


def inv_negabinary(u: jax.Array) -> jax.Array:
    m = jnp.uint32(_NBMASK_VAL)
    return ((u ^ m) - m).astype(jnp.int32)


def _bitlength32(u: jax.Array) -> jax.Array:
    w = jnp.zeros(u.shape, jnp.int32)
    v = u.astype(jnp.uint32)
    for s in (16, 8, 4, 2, 1):
        m = v >= jnp.uint32(1 << s)
        w = w + m.astype(jnp.int32) * s
        v = jnp.where(m, v >> s, v)
    return w + (v > 0).astype(jnp.int32)


def _carve_blocks(x: jax.Array) -> jax.Array:
    """(X,Y,Z) -> (n_blocks, 4, 4, 4) with edge padding (ZFP pads blocks)."""
    return _carve_cm(x).T.reshape(-1, 4, 4, 4)


def _uncarve_blocks(xb: jax.Array, shape) -> jax.Array:
    return _uncarve_cm(xb.reshape(-1, 64).T, shape)


# ------------------------------------------ coefficient-major layout -----
#
# Every coder stage below runs *coefficient-major* (CM): the 64 coefficients
# of a block (or its 32 bit planes) lie along axis 0 and the blocks along
# axis 1.  On the TPU that puts blocks on the 128 vector lanes, so per-block
# header arithmetic is lane-dense, and every reordering of coefficients is a
# static row (sublane) permutation or roll — the Pallas kernels
# (``repro.kernels.zfp_fused``) trace these same functions in VMEM, so the
# core, xla and fused paths emit identical streams by construction.  Row
# ``r = 16a + 4b + c`` of a block holds its value at in-block coordinates
# (a, b, c) of the field's axes (0, 1, 2): "index order".


def _carve_cm(x: jax.Array) -> jax.Array:
    """(X,Y,Z) field -> (64, n_blocks) CM index-order blocks (edge padded).

    Row ``16a + 4b + c`` is the stride-4 sub-lattice ``x[a::4, b::4, c::4]``
    — 64 strided slices.  (A reshape to ``(..., Z/4, 4)`` + transpose would
    materialize arrays whose minor dimension is 4, which the TPU pads to a
    128-lane tile: 32x the field, more than a chip holds at 512^3.)"""
    with obs_trace.span("zfp.carve"):
        pads = [(0, (-s) % 4) for s in x.shape]
        xp = jnp.pad(x, pads, mode="edge")
        return jnp.stack([xp[r // 16::4, (r // 4) % 4::4, r % 4::4].reshape(-1)
                          for r in range(64)])


def _uncarve_cm(b: jax.Array, shape) -> jax.Array:
    """Inverse of :func:`_carve_cm` (strided stores; crops the edge padding)."""
    with obs_trace.span("zfp.uncarve"):
        padded = tuple(s + ((-s) % 4) for s in shape)
        grid = tuple(s // 4 for s in padded)
        xp = jnp.zeros(padded, b.dtype)
        for r in range(64):
            xp = xp.at[r // 16::4, (r // 4) % 4::4, r % 4::4].set(b[r].reshape(grid))
        return xp[tuple(slice(0, s) for s in shape)]


_IDENTITY = tuple(range(64))


def _digit_order(stride: int) -> tuple[int, ...]:
    """Rows grouped by their digit along the lift axis of ``stride``: the
    four 16-row quarters are that axis's x, y, z, w lanes, aligned row for
    row (stable sort keeps the other two coordinates in the same order)."""
    return tuple(sorted(_IDENTITY, key=lambda r: (r // stride) % 4))


_LIFT_ORDER = {s: _digit_order(s) for s in (1, 4, 16)}  # [16] is identity


def _reorder(a: jax.Array, order, want) -> jax.Array:
    """Static row permutation: ``a``'s row ``i`` holds coefficient
    ``order[i]``; return the rows of coefficients ``want`` (contiguous runs
    become one slice each, so this lowers to sublane copies, not a gather)."""
    order, want = tuple(int(r) for r in order), tuple(int(r) for r in want)
    if order == want:
        return a
    pos = {r: i for i, r in enumerate(order)}
    parts, i = [], 0
    while i < len(want):
        j = i + 1
        while j < len(want) and pos[want[j]] == pos[want[j - 1]] + 1:
            j += 1
        parts.append(a[pos[want[i]]:pos[want[i]] + (j - i)])
        i = j
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


def _lift_quarters(a: jax.Array, step) -> jax.Array:
    q = a.shape[0] // 4
    return jnp.concatenate(step(*(a[k * q:(k + 1) * q] for k in range(4))), axis=0)


def _fwd_lift_cm(a: jax.Array, out_order=_IDENTITY) -> jax.Array:
    """:func:`_lift3d` on CM index-order rows; returns rows in ``out_order``
    (``PERM`` lands the coefficients directly in sequency order)."""
    order = _IDENTITY
    for stride in (1, 4, 16):  # block axes 2, 1, 0 — as _lift3d's 3, 2, 1
        a = _reorder(a, order, _LIFT_ORDER[stride])
        order = _LIFT_ORDER[stride]
        a = _lift_quarters(a, _fwd_lift4)
    return _reorder(a, order, out_order)


def _inv_lift_cm(a: jax.Array, in_order=_IDENTITY) -> jax.Array:
    """Inverse of :func:`_fwd_lift_cm`: rows held in ``in_order`` -> CM
    index-order rows."""
    order = in_order
    for stride in (16, 4, 1):
        a = _reorder(a, order, _LIFT_ORDER[stride])
        order = _LIFT_ORDER[stride]
        a = _lift_quarters(a, _inv_lift4)
    return _reorder(a, order, _IDENTITY)


def _transform_cm(b: jax.Array, order=PERM):
    """Stages 1-4 on CM index-order f32 blocks (64, T): -> (u uint32[64, T]
    negabinary coefficients with rows in ``order``, emax int32[1, T] (biased
    exponent, 0 = all-zero block), gtops int32[10, T]).

    The block exponent comes from the IEEE exponent bits of max|x| and the
    scale 2^(Q - e) is built in exponent bits — exact and branch-free.  After
    the clip to [-100, 127] the exponent equals ``frexp``'s (they differ only
    for subnormal maxima, which both clip to -100)."""
    maxabs = jnp.max(jnp.abs(b), axis=0, keepdims=True)  # (1, T)
    bits = jax.lax.bitcast_convert_type(maxabs, jnp.uint32)
    e_biased = ((bits >> 23) & jnp.uint32(0xFF)).astype(jnp.int32)
    e = jnp.clip(e_biased - 126, -100, 127)  # frexp convention: maxabs < 2^e
    nonzero = maxabs > 0.0
    ints = jnp.round(b * exact_exp2(Q - e)).astype(jnp.int32)
    u = negabinary(_fwd_lift_cm(ints, order))
    lens = _reorder(_bitlength32(u), order, PERM)  # groups = static row runs
    gtops = jnp.concatenate(
        [jnp.max(lens[s0:s0 + int(sz)], axis=0, keepdims=True)
         for s0, sz in zip(_FIXED_START, GROUP_SIZES)], axis=0)
    gtops = gtops * nonzero.astype(jnp.int32)
    emax = jnp.where(nonzero, e + _EMAX_BIAS, 0)
    return u, emax, gtops


def _inverse_cm(u: jax.Array, emax: jax.Array, order=PERM) -> jax.Array:
    """Invert stages 1-4: negabinary coefficients (rows in ``order``) +
    emax int32[1, T] -> CM index-order f32 blocks (64, T)."""
    ints = _inv_lift_cm(inv_negabinary(u), order)
    e = emax - _EMAX_BIAS
    scale = jnp.where(emax > 0, exact_exp2(e - Q), 0.0)
    return ints.astype(jnp.float32) * scale


def block_transform(x: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Stages 1-4: float blocks -> (negabinary sequency coeffs, emax, gtops)."""
    return _transform_rows(_carve_cm(x.astype(jnp.float32)))


def blocks_transform(blocks: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Stages 2-4 on already-carved (n, 4, 4, 4) blocks — the entry point
    the arena path batches over (the concatenated blocks of many leaves are
    just more rows; per-block outputs are independent).  Returns
    (u uint32[n, 64] sequency order, emax uint8[n], gtops int32[n, 10])."""
    return _transform_rows(blocks.reshape(-1, 64).T)


def _transform_rows(cm: jax.Array):
    u, emax, gtops = _transform_cm(cm)
    return u.T, emax[0].astype(jnp.uint8), gtops.T


def _schedule_offsets(gtops: jax.Array) -> jax.Array:
    """Exclusive bit offsets of every (plane, group) stream item.

    Stream order: plane 31 -> 0 (major), group 0 -> 9 (minor). Item (p, g)
    present iff p < gtops[:, g], contributing GROUP_SIZES[g] bits. Returns
    int32[n_blocks, 32*10] exclusive prefix sums — a pure function of the
    header, identical for encoder and decoder.  (Reference form of the
    schedule; the coder below consumes the factored per-plane form — the
    closed-form ``OFF``/``keep`` from :func:`_plane_offsets` plus the
    accumulated within-plane group offsets in :func:`_plane_payloads` —
    whose ``OFF[j] + woff[j, g]`` equals this.)
    """
    n = gtops.shape[0]
    planes = jnp.arange(31, -1, -1, dtype=jnp.int32)  # stream-major order
    present = planes[None, :, None] < gtops[:, None, :]  # (n, 32, 10)
    sizes = jnp.asarray(GROUP_SIZES, jnp.int32)[None, None, :]
    contrib = jnp.where(present, sizes, 0).reshape(n, 32 * N_GROUPS)
    cum = jnp.cumsum(contrib, axis=1)
    return cum - contrib


# --------------------------- plane-parallel word-level embedded coder -----
#
# Stream items are (plane, group) bit runs, plane 31 -> 0 major, group 0 -> 9
# minor.  The coder factors the flat schedule into a per-plane layout: plane
# j (stream-major, encoding bit plane p = 31 - j) owns a payload of
# ``pw[j] = sum_g w[j, g] <= 64`` bits, with group g's run at within-plane
# offset ``woff[j, g]``.  Every quantity is a pure function of the gtops
# header, so encoder and decoder derive identical layouts (DESIGN.md §3).
# All arrays are CM: stream-major plane j is row j of a (32, T) array.


def _code_mask(w: jax.Array) -> jax.Array:
    """uint32 mask of the low ``w`` bits, exact for w in [0, 32]."""
    w = w.astype(jnp.int32)
    shift = (32 - jnp.maximum(w, 1)).astype(jnp.uint32)  # in [0, 31]
    return jnp.where(w == 0, jnp.uint32(0), jnp.uint32(0xFFFFFFFF) >> shift)


def _plane_rows() -> jax.Array:
    """Stream-major plane index j as a (32, 1) column (iota: no captured
    constant, so Pallas bodies can call it)."""
    return jax.lax.broadcasted_iota(jnp.int32, (32, 1), 0)


def _plane_offsets_cm(gtops: jax.Array, budget: int):
    """Header-derived plane placement, in closed form (no prefix scans).

    Group g is present in stream-major plane j (bit plane p = 31 - j) iff
    ``p < gtops[g]``, i.e. ``gtops[g] + j - 32 >= 0``, and the number of
    *earlier* planes it occupies is ``max(0, gtops[g] + j - 32)``.  Summing
    sizes over groups therefore gives both the plane's global exclusive bit
    offset and its payload width without any cumulative scan:

    OFF   int32[32, T]  global exclusive bit offset of plane j's payload
    keep  int32[32, T]  payload bits surviving the ``budget`` truncation
    """
    j = _plane_rows()
    off = jnp.zeros((32, gtops.shape[1]), jnp.int32)
    pw = jnp.zeros_like(off)
    for g in range(N_GROUPS):
        t = gtops[g:g + 1] + j - 32  # (32, T)
        sz = int(GROUP_SIZES[g])
        off = off + sz * jnp.maximum(t, 0)
        pw = pw + sz * (t >= 0).astype(jnp.int32)
    keep = jnp.clip(budget - off, 0, pw)
    return off, keep


def _plane_offsets(gtops: jax.Array, budget: int):
    """Block-major view of :func:`_plane_offsets_cm`: int32[n, 32] each."""
    off, keep = _plane_offsets_cm(gtops.astype(jnp.int32).T, budget)
    return off.T, keep.T


def _mask64(keep: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(lo, hi) uint32 masks keeping the low ``keep`` bits of a 64-bit field."""
    return _code_mask(jnp.minimum(keep, 32)), _code_mask(jnp.clip(keep - 32, 0, 32))


# (row distance j, mask of the bits whose index has bit j clear)
_TRANSPOSE_STAGES = ((16, 0x0000FFFF), (8, 0x00FF00FF), (4, 0x0F0F0F0F),
                     (2, 0x33333333), (1, 0x55555555))


def _bit_transpose32(a: jax.Array, inverse: bool = False) -> jax.Array:
    """Coefficient words <-> plane words: a 32x32 bit-matrix transpose over
    axis 0 of ``a`` uint32[32, T].

    Forward: ``out[j] bit c == a[c] bit (31 - j)`` — row j is stream-major
    bit plane ``31 - j`` of the 32 coefficient rows, LSB = coefficient 0.
    Five butterfly stages (Hacker's Delight 7-3); stage j pairs rows r and
    r ^ j with a roll along axis 0 and swaps bit j of the row index with
    bit j of the bit index, *plus* the row flip that the plane order needs —
    so the plane reversal is folded into the network and no reversed slice
    is ever taken.  ``inverse=True`` runs the inverse stages.
    """
    row = jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)
    for j, mask in _TRANSPOSE_STAGES:
        m = jnp.uint32(mask)
        nm = jnp.uint32(~mask & 0xFFFFFFFF)
        sj = jnp.uint32(j)
        up = jnp.roll(a, -j, axis=0)  # row r sees row r + j
        dn = jnp.roll(a, j, axis=0)  # row r sees row r - j
        low = (row & j) == 0
        if inverse:
            a = jnp.where(low, (up & m) | ((a << sj) & nm), ((a >> sj) & m) | (dn & nm))
        else:
            a = jnp.where(low, ((a >> sj) & m) | (up & nm), (dn & m) | ((a << sj) & nm))
    return a


# In sequency order the 10 groups split exactly at bit 32: groups 0-4 fill
# coefficients 0..31 and groups 5-9 fill 32..63, so the *uncompacted* plane
# bit-matrix is two clean 32x32 transposes of the coefficient words.
_FIXED_START = tuple(int(s) for s in _gstart)  # (0,1,4,10,20,32,44,54,60,63)
assert _FIXED_START[5] == 32


def _plane_words(u: jax.Array) -> tuple[jax.Array, jax.Array]:
    """uint32[n, 64] sequency coefficients -> (W0, W1) uint32[n, 32]:
    ``W0[:, j] bit c`` = bit plane ``31 - j`` (stream-major) of coefficient
    ``c``; W1 likewise for coefficients 32..63."""
    return _bit_transpose32(u[:, :32].T).T, _bit_transpose32(u[:, 32:].T).T


def _coef_words(w0: jax.Array, w1: jax.Array) -> jax.Array:
    """Inverse of :func:`_plane_words`."""
    return jnp.concatenate([_bit_transpose32(w0.T, inverse=True),
                            _bit_transpose32(w1.T, inverse=True)], axis=0).T


def _group_widths(gtops: jax.Array, g: int) -> jax.Array:
    """int32[32, T]: bits group ``g`` contributes to each stream-major plane
    (its size when present, else 0) — a pure function of the header."""
    present = gtops[g:g + 1] + _plane_rows() >= 32  # p = 31 - j < gtops[g]
    return jnp.where(present, jnp.int32(int(GROUP_SIZES[g])), 0)


def _plane_payloads(u: jax.Array, gtops: jax.Array):
    """Assemble every plane's <= 64-bit compacted payload at once.

    ``u``: uint32[64, T] CM negabinary coefficients in sequency order.
    Returns (plo, phi) uint32[32, T]: plane j's payload bits [0, 32) and
    [32, 64).  A group's run is its coefficients' plane-j bits in rank
    order; a bit set at plane p implies bitlength > p, i.e. the group is
    present — so absent groups contribute zero runs with no masking.  Runs
    are sliced from the transposed plane bit-matrix at static offsets and
    compacted to the header-derived within-plane offsets (accumulated group
    widths); a run spans at most two of the payload's words (run offset +
    run width <= 64), so compaction is a masked shift/OR sum over the 10
    sequency segments.
    """
    w0, w1 = _bit_transpose32(u[:32]), _bit_transpose32(u[32:])
    plo = jnp.zeros(w0.shape, jnp.uint32)
    phi = jnp.zeros(w0.shape, jnp.uint32)
    woff = jnp.zeros(w0.shape, jnp.int32)
    for g in range(N_GROUPS):
        src = w0 if _FIXED_START[g] < 32 else w1
        s0 = jnp.uint32(_FIXED_START[g] & 31)
        run = (src >> s0) & _code_mask(jnp.int32(int(GROUP_SIZES[g])))
        o1 = (woff & 31).astype(jnp.uint32)
        in_hi = woff >= 32
        lo_c = run << o1
        hi_c = (run >> 1) >> (jnp.uint32(31) - o1)  # run >> (32 - o1); 0 at o1 == 0
        plo = plo | jnp.where(in_hi, jnp.uint32(0), lo_c)
        phi = phi | jnp.where(in_hi, lo_c, hi_c)
        woff = woff + _group_widths(gtops, g)
    return plo, phi


def _encode_words_cm(u: jax.Array, gtops: jax.Array, rate: int) -> jax.Array:
    """CM encode: (u uint32[64, T] sequency order, gtops int32[10, T]) ->
    uint32[wpb, T] stream words.  Pure elementwise/slice/roll jnp, so the
    fused Pallas kernel (``repro.kernels.zfp_fused``) traces the *same*
    code in VMEM and the streams agree across paths by construction."""
    budget = rate * 64 - _HEADER_BITS
    wpb = (budget + 31) // 32
    OFF, keep = _plane_offsets_cm(gtops, budget)
    plo, phi = _plane_payloads(u, gtops)
    mlo, mhi = _mask64(keep)
    plo = plo & mlo
    phi = phi & mhi
    sh = (OFF & 31).astype(jnp.uint32)
    w0 = OFF >> 5  # first word the plane payload touches
    c0 = plo << sh
    c1 = ((plo >> 1) >> (jnp.uint32(31) - sh)) | (phi << sh)
    c2 = (phi >> 1) >> (jnp.uint32(31) - sh)
    rows = []
    for k in range(wpb):
        # Bit positions are globally disjoint, so OR-ing == bit placement.
        contrib = (
            jnp.where(w0 == k, c0, jnp.uint32(0))
            | jnp.where(w0 + 1 == k, c1, jnp.uint32(0))
            | jnp.where(w0 + 2 == k, c2, jnp.uint32(0))
        )
        rows.append(bitpack.or_sum(contrib, axis=0))
    return jnp.concatenate(rows, axis=0)


@partial(jax.jit, static_argnames=("rate",))
def encode_words(u: jax.Array, gtops: jax.Array, rate: int) -> jax.Array:
    """Word-level embedded encode: (u uint32[n, 64], gtops [n, 10]) ->
    uint32[n, wpb] stream.

    Bit-identical to the reference per-plane formulation (tests pin seed
    streams).  Plane payloads land word-aligned-or-straddling, so each plane
    touches at most 3 of the block's words; the stream is a masked shift/OR
    sum over the 32 planes per word — O(words-per-block) vector passes, no
    scatter.
    """
    return _encode_words_cm(u.T, gtops.astype(jnp.int32).T, rate).T


def _extract_coeffs(g0: jax.Array, g1: jax.Array, g2: jax.Array,
                    OFF: jax.Array, keep: jax.Array, gtops: jax.Array) -> jax.Array:
    """Shared CM decode tail: the 3 fetched words per plane (uint32[32, T]
    each) -> uint32[64, T] sequency-order coefficients.  Pure elementwise/
    slice/roll jnp (reused inside the fused Pallas decode kernel, which
    fetches the words without gathers).
    """
    sh = (OFF & 31).astype(jnp.uint32)
    plo = (g0 >> sh) | ((g1 << 1) << (jnp.uint32(31) - sh))
    phi = (g1 >> sh) | ((g2 << 1) << (jnp.uint32(31) - sh))
    mlo, mhi = _mask64(keep)
    plo = plo & mlo
    phi = phi & mhi
    # Extract each group's run from its compacted plane payload, place it at
    # the group's static offset in the plane bit-matrix, then transpose the
    # matrix back into per-coefficient words.
    w0m = jnp.zeros(plo.shape, jnp.uint32)
    w1m = jnp.zeros(plo.shape, jnp.uint32)
    woff = jnp.zeros(plo.shape, jnp.int32)
    for g in range(N_GROUPS):
        o1 = (woff & 31).astype(jnp.uint32)
        in_hi = woff >= 32
        base_lo = jnp.where(in_hi, phi, plo)
        base_hi = jnp.where(in_hi, jnp.uint32(0), phi)
        run = ((base_lo >> o1) | ((base_hi << 1) << (jnp.uint32(31) - o1)))
        wg = _group_widths(gtops, g)
        run = run & _code_mask(wg)
        if _FIXED_START[g] < 32:
            w0m = w0m | (run << jnp.uint32(_FIXED_START[g]))
        else:
            w1m = w1m | (run << jnp.uint32(_FIXED_START[g] - 32))
        woff = woff + wg
    return jnp.concatenate([_bit_transpose32(w0m, inverse=True),
                            _bit_transpose32(w1m, inverse=True)], axis=0)


def _decode_words_cm(words: jax.Array, gtops: jax.Array, rate: int) -> jax.Array:
    """CM decode: uint32[wpb, T] stream + int32[10, T] header ->
    uint32[64, T] sequency-order coefficients.  Each plane's <= 64-bit
    payload spans at most 3 stream words, fetched with three gathers along
    the word axis (a word index past the block only ever holds bits beyond
    the budget, which ``keep`` masks, so clipping it is exact)."""
    budget = rate * 64 - _HEADER_BITS
    OFF, keep = _plane_offsets_cm(gtops, budget)
    lim = words.shape[0] - 1
    w0 = OFF >> 5
    g0, g1, g2 = (jnp.take_along_axis(words, jnp.clip(w0 + k, 0, lim), axis=0)
                  for k in range(3))
    return _extract_coeffs(g0, g1, g2, OFF, keep, gtops)


@partial(jax.jit, static_argnames=("rate",))
def decode_words(words: jax.Array, gtops: jax.Array, rate: int) -> jax.Array:
    """Inverse of :func:`encode_words`: stream -> uint32[n, 64] sequency-order
    negabinary coefficients (exactly the bits the budget admitted)."""
    return _decode_words_cm(words.T, gtops.astype(jnp.int32).T, rate).T


def n_blocks_for(shape) -> int:
    """Number of 4^3 blocks :func:`_carve_blocks` produces for ``shape`` —
    the analytic per-leaf block count the fixed-rate arena layout keys on."""
    nb = 1
    for s in shape:
        nb *= -(-s // BLOCK_SIDE)
    return nb


def from_words(words, emax, gtops, shape, rate: int) -> ZFPCompressed:
    """Descriptor-based stream view: rebuild a :class:`ZFPCompressed` from a
    flat contiguous word slice (an arena slice) plus its header sidecars —
    fixed rate means the slice bounds are analytic (``n_blocks_for(shape) *
    payload_words(rate)`` words), no scan or sidecar offsets needed."""
    wpb = payload_words(rate)
    words = jnp.asarray(words, jnp.uint32).reshape(-1, wpb)
    return ZFPCompressed(words, jnp.asarray(emax, jnp.uint8),
                         jnp.asarray(gtops, jnp.uint8), tuple(shape), rate)


def payload_words(rate: int) -> int:
    """Stream words per block at ``rate`` bits/value (header inside budget)."""
    budget = rate * 64 - _HEADER_BITS
    if budget <= 0:
        raise ValueError(f"rate={rate} leaves no payload after the {_HEADER_BITS}-bit header")
    return (budget + 31) // 32


@partial(jax.jit, static_argnames=("rate",))
def compress(x: jax.Array, rate: int) -> ZFPCompressed:
    """Fixed-rate compress a 3-D float32 field at ``rate`` bits/value."""
    assert x.ndim == 3, "TPU-ZFP operates on 3-D fields; reshape first (see api.py)"
    payload_words(rate)  # validates the rate
    u, emax, gtops = _transform_cm(_carve_cm(x.astype(jnp.float32)))
    words = _encode_words_cm(u, gtops, rate)
    return ZFPCompressed(words.T, emax[0].astype(jnp.uint8),
                         gtops.T.astype(jnp.uint8), x.shape, rate)


def _blocks_from_coeffs(u: jax.Array, emax: jax.Array) -> jax.Array:
    """Invert stages 1-4: sequency-order coefficients uint32[n, 64] + emax
    -> f32 blocks (n, 4, 4, 4)."""
    b = _inverse_cm(u.T, emax.astype(jnp.int32)[None, :])
    return b.T.reshape(-1, 4, 4, 4)


def _decode_cm(c: ZFPCompressed) -> jax.Array:
    """Stream -> CM index-order f32 blocks (64, n_blocks)."""
    u = _decode_words_cm(c.words.T, c.gtops.astype(jnp.int32).T, c.rate)
    return _inverse_cm(u, c.emax.astype(jnp.int32)[None, :])


def blocks_from_stream(words: jax.Array, emax: jax.Array, gtops: jax.Array,
                       rate: int) -> jax.Array:
    """Decode a stream back to float32 blocks (n, 4, 4, 4) — the inverse of
    stages 1-5 given the per-block header arrays."""
    return _blocks_from_coeffs(decode_words(words, gtops, rate), emax)


@jax.jit
def decompress(c: ZFPCompressed) -> jax.Array:
    return _uncarve_cm(_decode_cm(c), c.shape)


def compressed_nbytes(c: ZFPCompressed) -> int:
    n_blocks = c.words.shape[0]
    return (n_blocks * c.rate * 64 + 7) // 8  # headers inside the budget


def compression_ratio(c: ZFPCompressed, n_values: int | None = None) -> float:
    """CR against the *original* value count.  ``c.shape`` is the (possibly
    padded) 3-D shape the coder saw; callers that reshaped a 1-D/2-D field
    pass the pre-reshape element count so padding doesn't inflate the ratio.
    """
    raw = 4.0 * (float(np.prod(c.shape)) if n_values is None else float(n_values))
    return raw / float(compressed_nbytes(c))
