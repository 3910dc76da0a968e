"""In-situ sharded field compression: run TPU-SZ / TPU-ZFP *where the field
lives*, one shard per device, with a halo exchange closing the seams.

The paper's premise is that cosmology fields should be compressed at
simulation scale on the accelerator that produced them — not gathered to
host first.  This module is that path for mesh-sharded fields:

* the field partition comes from ``dist.sharding`` specs
  (:func:`repro.dist.sharding.field_spec` by default, or whatever spec the
  array already carries);
* each shard's order-1 Lorenzo predictor sees its **true left neighbors**:
  before differencing a partitioned axis, the running intermediate's last
  face ships one shard rightward via ``lax.ppermute`` — exactly one
  collective-permute per partitioned face.  Mesh-edge shards keep the
  implicit zero plane (the single-device boundary condition), and
  non-partitioned axes skip the permute entirely;
* the only other collectives are a scalar ``pmax`` (so every shard derives
  the same internal error bound from the *global* |x|max — f32 max is exact
  under any reduction grouping) and, on decompression, a log-step
  Hillis-Steele ``ppermute`` scan that turns local prefix sums into the
  global inverse-Lorenzo cumsum (int32 addition is associative even under
  wraparound, so the carry formulation is *bitwise* equal to the
  single-device cumsum);
* coefficient/residual data never leaves its device: the encode is
  shard-local (``repro.core`` formulation or the ``repro.kernels.ops``
  kernel paths), and the compiled program contains **no all-gather of the
  raw field** — pinned by an HLO assertion in ``tests/test_insitu.py``.

The invariant all of this buys (and the 8-device battery enforces):
``sharded_decompress(sharded_compress(x))`` is **bitwise identical** to the
single-device ``decompress(compress(x))`` round-trip, and the per-shard
streams reassemble on host without the mesh (:func:`host_decode`), which is
what lets ``checkpoint.manager`` restore them onto a *different* mesh.

ZFP needs no halo — its 4x4x4 blocks are self-contained — but it does need
every seam on a block boundary; misaligned shards are rejected
(:func:`repro.core.zfp.shard_extent_aligned`, DESIGN.md §7).

Composed-axis partitions (one field dim over a tuple of mesh axes) are not
supported: the halo shift of a composed index needs a carry-propagating
permute chain.  Shard over a single mesh axis per dim (``FIELD_RULES``
already does).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as PS

from repro.core import arena as arena_core
from repro.core import bitpack
from repro.core import sz as sz_core
from repro.core import zfp as zfp_core
from repro.dist import sharding as shardlib
from repro.obs import trace as obs_trace


# ------------------------------------------------------------ partition ----


def partition_layout(shape: Sequence[int], spec, mesh) -> tuple:
    """Normalize a PartitionSpec into a per-field-dim mesh-axis layout.

    Returns a tuple of length ``len(shape)`` whose entries are a mesh axis
    name (the dim is split over it) or ``None`` (replicated / absent /
    size-1 axis).  Composed tuples raise ``NotImplementedError`` (module
    docstring); non-divisible partitions raise ``ValueError``.
    """
    sizes = dict(mesh.shape)
    entries = list(spec) + [None] * (len(shape) - len(spec))
    if len(entries) > len(shape):
        raise ValueError(f"spec {spec} has more entries than field rank {len(shape)}")
    out = []
    for dim, ent in zip(shape, entries):
        if isinstance(ent, (tuple, list)):
            if len(ent) > 1:
                raise NotImplementedError(
                    f"composed-axis field partition {ent} unsupported: the halo "
                    "shift of a composed shard index needs a carry-propagating "
                    "permute chain; shard each field dim over a single mesh axis")
            ent = ent[0] if ent else None
        if ent is None or sizes.get(ent, 1) <= 1:
            out.append(None)
            continue
        n = sizes[ent]
        if dim % n:
            raise ValueError(f"dim {dim} not divisible by mesh axis {ent!r} ({n})")
        out.append(ent)
    return tuple(out)


def _local_shape(shape, layout, sizes) -> tuple:
    return tuple(d // (sizes[a] if a else 1) for d, a in zip(shape, layout))


def _grid(layout, sizes) -> tuple:
    return tuple(sizes[a] if a else 1 for a in layout)


def _stack_axes(layout) -> tuple:
    """Partitioned mesh axes in field-dim order — the composed leading axis
    the per-shard streams stack over (row-major, matching np.ndindex of the
    grid)."""
    return tuple(a for a in layout if a is not None)


# ----------------------------------------------------------- collectives ---


def _ring_perm(n: int) -> list:
    """One-face-rightward halo ring: shard ``i`` sends to ``i + 1``; shard 0
    has no source pair, so ``ppermute`` zero-fills it — the mesh-edge shard
    keeps the zero border for free."""
    return [(i, i + 1) for i in range(n - 1)]


def _scan_perms(n: int) -> list:
    """Hillis-Steele inclusive-scan schedule: ``(offset, perm)`` steps where
    ``perm`` ships shard ``i``'s partial to ``i + offset`` (receivers below
    the offset get zeros).  After all log2(n) steps every shard holds the
    inclusive prefix of the per-shard totals."""
    out, off = [], 1
    while off < n:
        out.append((off, [(i, i + off) for i in range(n - off)]))
        off *= 2
    return out


class _LaxOps:
    """The real collectives, valid inside a fully-manual shard_map region.
    Tests substitute a stacked-array mock (same two methods) to exercise
    the halo machinery on CPU without a multi-device mesh."""

    @staticmethod
    def ppermute(x, axis_name, perm):
        return jax.lax.ppermute(x, axis_name, perm)

    @staticmethod
    def pmax(x, axis_names):
        return jax.lax.pmax(x, axis_names)


def halo_exchange(layout, sizes, ops=_LaxOps):
    """Border-override hook for :func:`repro.core.sz.lorenzo_residual`:
    ship the intermediate's last face one shard rightward along each
    partitioned axis (one collective-permute per face); ``None`` for
    non-partitioned axes keeps the zero border and skips the permute."""

    def exchange(field_axis, last_plane):
        name = layout[field_axis]
        if name is None or sizes[name] <= 1:
            return None
        return ops.ppermute(last_plane, name, _ring_perm(sizes[name]))

    return exchange


def carry_exchange(layout, sizes, ops=_LaxOps):
    """Reconstruction-side hook for :func:`repro.core.sz.lorenzo_reconstruct`:
    given the shard's inclusive total face after the local cumsum, return
    the carry (the exclusive cross-shard scan of those totals) via the
    log-step ppermute schedule."""

    def exchange(field_axis, total_plane):
        name = layout[field_axis]
        if name is None or sizes[name] <= 1:
            return None
        inc = total_plane
        for _off, perm in _scan_perms(sizes[name]):
            inc = inc + ops.ppermute(inc, name, perm)
        return inc - total_plane  # exclusive prefix of left-shard totals

    return exchange


# -------------------------------------------------------------- streams ----


@partial(jax.tree_util.register_dataclass,
         data_fields=("words", "widths", "total_bits", "eb"),
         meta_fields=("shape", "layout", "grid", "halo", "backend"))
@dataclasses.dataclass
class ShardedSZStream:
    """Per-shard TPU-SZ streams stacked on a leading shard axis (a pytree;
    everything but the arrays is static)."""

    words: jax.Array  # uint32[n_shards, cap] worst-case packed buffers
    widths: jax.Array  # uint8[n_shards, n_blocks]
    total_bits: jax.Array  # int32[n_shards]
    eb: jax.Array  # float32[] internal bound (global, pmax-derived)
    shape: tuple  # global field shape
    layout: tuple  # per-dim mesh axis name or None
    grid: tuple  # shards per field dim (np.ndindex order == stack order)
    halo: bool  # predictor saw true neighbors (vs zero borders)
    backend: str  # "core" (global Lorenzo + halo) | "kernel" (tile-blocked)


@partial(jax.tree_util.register_dataclass,
         data_fields=("words", "emax", "gtops"),
         meta_fields=("shape", "layout", "grid", "rate"))
@dataclasses.dataclass
class ShardedZFPStream:
    """Per-shard fixed-rate TPU-ZFP streams on a leading shard axis."""

    words: jax.Array  # uint32[n_shards, n_blocks, words_per_block]
    emax: jax.Array  # uint8[n_shards, n_blocks]
    gtops: jax.Array  # uint8[n_shards, n_blocks, 10]
    shape: tuple
    layout: tuple
    grid: tuple
    rate: int


def stream_nbytes(stream) -> int:
    """True stored bytes across all shards (the ratio-accounting figure)."""
    if isinstance(stream, ShardedSZStream):
        bits = np.asarray(stream.total_bits, np.int64)
        return int(np.sum((bits + 7) // 8))
    n_shards, n_blocks = stream.words.shape[:2]
    return int(n_shards) * ((int(n_blocks) * stream.rate * 64 + 7) // 8)


def compression_ratio(stream) -> float:
    raw = 4.0 * float(np.prod(stream.shape))
    return raw / max(stream_nbytes(stream), 1)


# ------------------------------------------------------------- compress ----


def _shard_map(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         axis_names=frozenset(mesh.axis_names), check_vma=False)


def _resolve_spec(field, mesh, spec):
    if spec is None:
        spec = getattr(getattr(field, "sharding", None), "spec", None)
    if spec is None:
        spec = shardlib.field_spec(np.shape(field), mesh)
    return spec


def check_eligible(field, codec: str, mesh, spec=None, *,
                   backend: str = "auto") -> tuple:
    """Every check :func:`sharded_compress` makes before it traces, on the
    field's shape and partition alone: raises ``NotImplementedError`` /
    ``ValueError`` for a field it cannot compress and returns ``(layout,
    local_shape)`` otherwise.  Callers that skip ineligible fields run this
    first, so an error raised while *compiling* an eligible one (a kernel
    the backend cannot lower) propagates instead of being mistaken for an
    ineligible field."""
    spec = _resolve_spec(field, mesh, spec)
    sizes = dict(mesh.shape)
    layout = partition_layout(np.shape(field), spec, mesh)
    local = _local_shape(np.shape(field), layout, sizes)
    if codec == "sz":
        if backend not in ("auto", "core", "kernel"):
            raise ValueError(f"unknown SZ backend {backend!r}; want core|kernel")
        n = int(np.prod(local))
        if n * 32 >= 2**31:
            raise ValueError(f"SZ shard of {n} values overflows the packer's "
                             "int32 bit offsets; chunk the field")
        if backend == "kernel":
            from repro.kernels import lorenzo3d as _lor

            if len(local) != 3:
                raise ValueError("SZ kernel backend operates on 3-D fields")
            # every local extent must be a tile multiple — partitioned axes
            # because per-tile prediction must not straddle the seam, and
            # non-partitioned axes because the stream/decode contract here
            # carries no padded shape (ops pads internally, but a padded
            # per-shard stream would be undecodable from `local` alone)
            for ext, ax, tile in zip(local, layout, _lor.TILE):
                if ext % tile:
                    raise ValueError(
                        f"SZ kernel backend: shard extent {ext} (axis {ax!r}) "
                        f"not a multiple of the {_lor.TILE} tile")
    elif codec == "zfp":
        if len(local) != 3:
            raise ValueError("ZFP operates on 3-D fields; reshape first "
                             "(the HACC 1-D layout is (N/64, 8, 8))")
        for ext, ax in zip(local, layout):
            if not zfp_core.shard_extent_aligned(ext, sizes.get(ax, 1) if ax else 1):
                raise ValueError(
                    f"ZFP shard extent {ext} on axis {ax!r} not a multiple of "
                    f"{zfp_core.BLOCK_SIDE}: a seam inside a 4^3 block would "
                    "change the stream (DESIGN.md §7)")
    else:
        raise ValueError(f"unknown codec {codec!r}; want sz|zfp")
    return layout, local


def sharded_compress(field, codec: str, mesh, spec=None, *, eb=None,
                     rate: Optional[int] = None, halo: bool = True,
                     backend: str = "auto", path: str = "auto"):
    """Compress a mesh-sharded field shard-locally; no host gather.

    ``codec`` is ``"sz"`` (error-bounded, needs ``eb=``) or ``"zfp"``
    (fixed-rate, needs ``rate=``).  ``spec`` defaults to the array's own
    ``NamedSharding`` spec, else :func:`repro.dist.sharding.field_spec`.

    SZ backends:
      * ``"core"`` (default off-TPU) — global-Lorenzo formulation with the
        halo exchange; bitwise equal to ``repro.core.sz`` round-trips.
      * ``"kernel"`` — the tile-blocked ``repro.kernels.ops`` path
        (``path=fused|xla|auto``); prediction resets at tile borders, so no
        halo is needed, but every partitioned shard extent must be a
        multiple of the (8, 64, 128) tile.  Bitwise equal to the
        single-device kernel path.
    ``halo=False`` (core backend only) keeps the zero border at every seam —
    the *wrong* stream the ISSUE's seam test demonstrates against; it decodes
    shard-locally but its stitched global reconstruction violates the bound.

    ZFP ``backend`` mirrors ``repro.core.api`` (``auto`` = kernel on TPU,
    core elsewhere); all ZFP paths emit byte-identical streams.
    """
    field = jnp.asarray(field)
    layout, local = check_eligible(field, codec, mesh, spec, backend=backend)
    spec = _resolve_spec(field, mesh, spec)
    sizes = dict(mesh.shape)
    stack = _stack_axes(layout)
    in_spec = PS(*layout)
    out_stack = PS(stack) if stack else PS()

    if codec == "sz":
        if eb is None:
            raise ValueError("SZ requires eb=")
        if backend == "auto":
            backend = "core"
        if backend == "kernel":
            from repro.kernels import ops as kops

        def body(x):
            x = x.astype(jnp.float32)
            m = jnp.max(jnp.abs(x))
            if stack:
                m = _LaxOps.pmax(m, stack)
            eb_i = sz_core.internal_bound(m, eb)
            if backend == "kernel":
                packed, _, _ = kops.sz_compress_kernel(x, eb, path=path, eb_i=eb_i)
            else:
                q = jnp.round(x / (2.0 * eb_i)).astype(jnp.int32)
                ex = halo_exchange(layout, sizes) if halo else None
                delta = sz_core.lorenzo_residual(q, exchange=ex)
                packed = bitpack.pack_codes(delta.reshape(-1))
            return (packed.words[None], packed.widths[None],
                    packed.total_bits[None], eb_i)

        words, widths, bits, eb_i = _shard_map(
            body, mesh, (in_spec,),
            (out_stack, out_stack, out_stack, PS()))(field)
        return ShardedSZStream(words, widths, bits, eb_i, field.shape, layout,
                               _grid(layout, sizes),
                               bool(halo) if backend == "core" else True, backend)

    if codec == "zfp":
        if rate is None:
            raise ValueError("ZFP requires rate=")
        use_kernel = backend == "kernel" or (
            backend == "auto" and jax.default_backend() == "tpu")

        def zbody(x):
            if use_kernel:
                from repro.kernels import ops as kops

                c = kops.zfp_compress_kernel(x.astype(jnp.float32), rate, path=path)
            else:
                c = zfp_core.compress(x.astype(jnp.float32), rate)
            return c.words[None], c.emax[None], c.gtops[None]

        words, emax, gtops = _shard_map(
            zbody, mesh, (in_spec,), (out_stack, out_stack, out_stack))(field)
        return ShardedZFPStream(words, emax, gtops, field.shape, layout,
                                _grid(layout, sizes), rate)

    raise ValueError(f"unknown codec {codec!r}; want sz|zfp")


def sharded_decompress(stream, mesh) -> jax.Array:
    """Inverse of :func:`sharded_compress` on the same mesh: per-shard
    decode + the carry scan, returning the global field sharded by the
    original partition spec.  Bitwise equal to the single-device
    ``decompress(compress(x))`` when the stream was built with ``halo=True``.
    """
    sizes = dict(mesh.shape)
    layout = stream.layout
    local = _local_shape(stream.shape, layout, sizes)
    stack = _stack_axes(layout)
    in_stack = PS(stack) if stack else PS()
    out_spec = PS(*layout)
    n_local = int(np.prod(local))

    if isinstance(stream, ShardedSZStream):
        def body(words, widths, bits, eb_i):
            packed = bitpack.PackedCodes(words[0], widths[0], bits[0], n_local)
            if stream.backend == "kernel":
                from repro.kernels import ops as kops

                return kops.sz_decompress_kernel(packed, local, local, eb_i)
            delta = bitpack.unpack_codes(packed).reshape(local)
            ex = carry_exchange(layout, sizes) if stream.halo else None
            q = sz_core.lorenzo_reconstruct(delta, exchange=ex)
            return q.astype(jnp.float32) * (2.0 * eb_i)

        return _shard_map(body, mesh, (in_stack, in_stack, in_stack, PS()),
                          out_spec)(stream.words, stream.widths,
                                    stream.total_bits, stream.eb)

    # mirror the compress-side backend selection: all ZFP paths read each
    # other's streams, so decode independently picks the fused kernel on TPU
    zfp_kernel = jax.default_backend() == "tpu"

    def zbody(words, emax, gtops):
        c = zfp_core.ZFPCompressed(words[0], emax[0], gtops[0], local, stream.rate)
        if zfp_kernel:
            from repro.kernels import ops as kops

            return kops.zfp_decompress_kernel(c)
        return zfp_core.decompress(c)

    return _shard_map(zbody, mesh, (in_stack, in_stack, in_stack),
                      out_spec)(stream.words, stream.emax, stream.gtops)


# ----------------------------------------------------------- stream arena --


@partial(jax.tree_util.register_dataclass,
         data_fields=("arena", "widths", "offsets", "counts", "total_bits",
                      "eb_i", "used"),
         meta_fields=("names", "shapes", "dtypes", "ns", "padded_loc",
                      "axis", "grid", "halo"))
@dataclasses.dataclass
class ShardedSZArena:
    """Per-shard stream arenas for one snapshot bucket, stacked on a leading
    shard axis (a pytree; every descriptor is static).

    Each shard compacted its rows' variable-length streams into one local
    uint32 arena with one exclusive scan; shard ``s``'s stream for row
    ``b`` is ``arena[s, offsets[s, b] : offsets[s, b] + counts[s, b]]`` —
    byte-identical to the per-leaf ``sharded_compress`` stream of the same
    flat leaf (and, with ``halo``, to the single-device ``sz.compress``
    stream of the whole flat leaf, per shard segment)."""

    arena: jax.Array  # uint32[g, cap_loc]
    widths: jax.Array  # uint8[g, B, P_loc // 64]
    offsets: jax.Array  # int32[g, B]
    counts: jax.Array  # int32[g, B]
    total_bits: jax.Array  # int32[g, B]
    eb_i: jax.Array  # float32[B] global pmax-derived bounds
    used: jax.Array  # int32[g] live words per shard arena
    names: tuple
    shapes: tuple  # original leaf shapes
    dtypes: tuple
    ns: tuple  # global flat element counts
    padded_loc: int  # P_loc, per-shard row length
    axis: Optional[str]  # mesh axis the flat rows are split over (or None)
    grid: int  # shards
    halo: bool


@dataclasses.dataclass(frozen=True)
class ArenaBucket:
    """A size bucket of arena-eligible leaves sharing one flat partition
    (``axis``/``grid``) and one per-shard row length ``padded_loc``."""

    names: tuple
    shapes: tuple
    dtypes: tuple
    ns: tuple
    padded_loc: int
    axis: Optional[str]
    grid: int

    @property
    def rows(self) -> int:
        return len(self.names)

    @property
    def nbytes_raw(self) -> int:
        return sum(int(np.prod(s)) * np.dtype(d).itemsize
                   for s, d in zip(self.shapes, self.dtypes))


def _flat_axis(shape, spec, mesh) -> Optional[str]:
    """Mesh axis a leaf's row-major flattening is contiguously split over,
    or ``None`` for replicated leaves.  Only leading-dim single-axis
    partitions qualify: flattening an axis-0 split keeps every shard a
    contiguous flat segment, so the 1-D halo is exact; any other partition
    interleaves flat segments and the leaf is not arena-eligible (the
    caller falls back to the per-leaf path)."""
    layout = partition_layout(shape, spec, mesh)
    if any(a is not None for a in layout[1:]):
        raise NotImplementedError(
            f"arena path needs leading-dim (or replicated) partitions; "
            f"layout {layout} interleaves the flat order")
    return layout[0] if layout else None


def plan_arena(entries: Sequence[tuple], mesh,
               elem_budget: int = arena_core.ROW_ELEM_BUDGET):
    """Bucket arena-eligible leaves: ``entries`` are ``(name, shape, dtype,
    spec)``; returns ``(buckets, skipped)`` where ``skipped`` is a list of
    ``(name, reason)`` for leaves the arena cannot batch (non-leading-dim
    partitions, non-divisible dims, oversized rows) — those stay on the
    per-leaf path."""
    sizes = dict(mesh.shape)
    groups: dict[tuple, list] = {}
    skipped = []
    for name, shape, dtype, spec in entries:
        n = int(np.prod(shape)) if len(shape) else 1
        try:
            axis = _flat_axis(shape, spec, mesh)
        except (NotImplementedError, ValueError) as e:
            skipped.append((str(name), str(e)))
            continue
        g = sizes.get(axis, 1) if axis else 1
        if g <= 1:
            axis, g = None, 1
        n_loc = n // g
        p_loc = arena_core.row_length(n_loc)
        if p_loc * 32 >= 2**31:
            skipped.append((str(name), f"row n={n_loc} too large for int32 bit offsets"))
            continue
        groups.setdefault((axis, g, p_loc), []).append(
            (str(name), tuple(shape), str(np.dtype(dtype)), n))
    buckets = []
    for (axis, g, p_loc) in sorted(groups, key=lambda k: (k[0] or "", k[1], k[2])):
        for sub in arena_core.split_budget(groups[(axis, g, p_loc)], p_loc,
                                           elem_budget):
            buckets.append(ArenaBucket(
                tuple(e[0] for e in sub), tuple(e[1] for e in sub),
                tuple(e[2] for e in sub), tuple(e[3] for e in sub),
                p_loc, axis, g))
    return buckets, skipped


def plan_kernel_buckets(entries: Sequence[tuple], mesh,
                        elem_budget: int = arena_core.ROW_ELEM_BUDGET):
    """Carve out the leaves the *fused tile kernel* should batch: 3-D,
    TILE-aligned, replicated (no partitioned dim), small enough for the
    kernel's int32 bit offsets.  Returns ``(buckets, rest)`` — shape-uniform
    :class:`repro.core.arena.Bucket` groups (``padded == n``: tile rows
    carry no pad) for :func:`repro.core.arena.szk_compress_bucket`, plus
    the remaining entries to feed :func:`plan_arena`.  These leaves would
    be flat-arena-eligible too, but the tile-blocked coder is the field
    path of the paper (and of ``kernels.ops``), so it wins the route."""
    from repro.kernels import lorenzo3d as _lor  # lazy: TILE only

    tz, ty, tx = _lor.TILE
    groups: dict[tuple, list] = {}
    rest = []
    for name, shape, dtype, spec in entries:
        shape_t = tuple(int(s) for s in shape)
        n = int(np.prod(shape_t)) if shape_t else 1
        ok = (len(shape_t) == 3 and n * 32 < 2**31
              and shape_t[0] % tz == 0 and shape_t[1] % ty == 0
              and shape_t[2] % tx == 0)
        if ok:
            try:
                layout = partition_layout(shape_t, spec, mesh)
            except (NotImplementedError, ValueError):
                layout = None
            ok = layout is not None and all(a is None for a in layout)
        if not ok:
            rest.append((name, shape, dtype, spec))
            continue
        groups.setdefault(shape_t, []).append(
            (str(name), shape_t, str(np.dtype(dtype)), n))
    buckets = []
    for shape_t in sorted(groups):
        n = int(np.prod(shape_t))
        for sub in arena_core.split_budget(groups[shape_t], n, elem_budget):
            buckets.append(arena_core.Bucket(
                n, tuple(e[0] for e in sub), tuple(e[1] for e in sub),
                tuple(e[2] for e in sub), tuple(e[3] for e in sub)))
    return buckets, rest


def sharded_compress_arena(leaves: Sequence[jax.Array], bucket: ArenaBucket,
                           mesh, eb, halo: bool = True) -> ShardedSZArena:
    """Compress a bucket of flat-contiguously-sharded leaves into per-shard
    stream arenas — **one** launch, **one** halo ppermute, **one** pmax for
    the whole bucket (the per-leaf path issued each per leaf).

    Jit-friendly: wrap in ``jax.jit`` keyed on the bucket signature (the
    snapshot hook compiles one function per bucket, not per leaf)."""
    axis, g = bucket.axis, bucket.grid
    p_loc = bucket.padded_loc
    ns_loc = tuple(n // g for n in bucket.ns)
    cap_loc = arena_core.sz_capacity(ns_loc)
    rows = []
    for leaf, n_loc in zip(leaves, ns_loc):
        seg = jnp.asarray(leaf).astype(jnp.float32).reshape(g, n_loc)
        rows.append(jnp.pad(seg, ((0, 0), (0, p_loc - n_loc))))
    stacked = jnp.stack(rows)  # [B, g, P_loc]; shard boundaries pre-padded

    def body(xs):
        xs = xs[:, 0]  # [B, P_loc] local rows
        n_arr = jnp.asarray(ns_loc, jnp.int32)
        mask = arena_core._row_mask(p_loc, n_arr)
        am = jnp.max(jnp.where(mask, jnp.abs(xs), 0.0), axis=1)
        ex = None
        if axis is not None:
            am = _LaxOps.pmax(am, (axis,))
            if halo:
                # the per-leaf halo hook, specialized to the flat axis: the
                # [B, 1] last-quantum plane ships one shard right in ONE
                # permute for the whole bucket
                hx = halo_exchange((axis,), {axis: g})
                ex = lambda last: hx(0, last)
        ar, widths, offsets, counts, tb, eb_i, used = arena_core.sz_encode_rows(
            xs, n_arr, eb, cap_loc, absmax=am, exchange=ex)
        return (ar[None], widths[None], offsets[None], counts[None],
                tb[None], eb_i, used[None])

    stack = PS(axis) if axis else PS()
    ar, widths, offsets, counts, tb, eb_i, used = _shard_map(
        body, mesh, (PS(None, axis, None) if axis else PS(),),
        (stack, stack, stack, stack, stack, PS(), stack))(stacked)
    return ShardedSZArena(ar, widths, offsets, counts, tb, eb_i, used,
                          bucket.names, bucket.shapes, bucket.dtypes,
                          bucket.ns, p_loc, axis, g,
                          bool(halo) if axis else True)


def sharded_decompress_arena(stream: ShardedSZArena, mesh) -> list[jax.Array]:
    """Inverse of :func:`sharded_compress_arena` on a mesh: per-shard
    batched unpack + local cumsum, one log-step carry scan per bucket, then
    scatter the rows back into leaves (original shapes/dtypes).  Bitwise
    equal to the single-device flat round-trip for halo arenas."""
    axis, g = stream.axis, stream.grid
    ns_loc = tuple(n // g for n in stream.ns)

    def body(ar, widths, offsets, counts, eb_i):
        n_arr = jnp.asarray(ns_loc, jnp.int32)
        carry = None
        if axis is not None and stream.halo:
            # the per-leaf carry hook (log-step scan), one for the bucket
            cx = carry_exchange((axis,), {axis: g})
            carry = lambda totals: cx(0, totals)
        rows = arena_core.sz_decode_rows(ar[0], widths[0], offsets[0],
                                         counts[0], eb_i, carry=carry, n=n_arr)
        return rows[None]  # [1, B, P_loc]

    stack = PS(axis) if axis else PS()
    rows = _shard_map(
        body, mesh, (stack, stack, stack, stack, PS()),
        PS(axis, None, None) if axis else PS())(
        stream.arena, stream.widths, stream.offsets, stream.counts, stream.eb_i)
    out = []
    for b, (shape, dtype, n_loc) in enumerate(
            zip(stream.shapes, stream.dtypes, ns_loc)):
        flat = rows[:, b, :n_loc].reshape(-1)  # shard segments are contiguous
        out.append(flat.reshape(shape).astype(dtype))
    return out


def arena_to_host(stream: ShardedSZArena) -> arena_core.HostArena:
    """Pull a sharded bucket arena to host: one readback of the per-shard
    ``used`` vector, then one D2H copy of the live arena slab (sliced to
    ``max(used)`` columns) — O(1) host syncs per bucket vs O(#leaves x
    #shards) on the per-leaf path."""
    # the span wraps the one mandatory readback — tracing adds no sync
    with obs_trace.span("insitu.arena_to_host", n_fields=len(stream.names),
                        grid=int(stream.grid)):
        used = np.asarray(stream.used, np.int64)  # the single readback
        max_used = int(used.max()) if used.size else 0
        slab = np.asarray(stream.arena[:, :max_used])  # the single D2H copy
    widths = np.asarray(stream.widths)
    offsets = np.asarray(stream.offsets, np.int32)
    counts = np.asarray(stream.counts, np.int32)
    tb = np.asarray(stream.total_bits, np.int32)
    shards = [{
        "arena": slab[s, : int(used[s])].copy(),
        "widths": widths[s],
        "offsets": offsets[s],
        "counts": counts[s],
        "total_bits": tb[s],
    } for s in range(stream.grid)]
    return arena_core.HostArena(
        arena_core.CODEC_SZ, stream.names, stream.shapes, stream.dtypes,
        stream.ns, stream.padded_loc * stream.grid, stream.grid, stream.halo,
        [float(v) for v in np.asarray(stream.eb_i)], shards)


def arena_to_host_async(stream: ShardedSZArena) -> arena_core.PendingHostArena:
    """Non-blocking :func:`arena_to_host`: enqueue D2H transfers of the
    descriptor sidecars behind the bucket launch and return a
    :class:`repro.core.arena.PendingHostArena` whose ``result()`` performs
    the one ``used``-vector readback + slab copy — on the manager's drain
    thread, not the training thread."""
    for arr in (stream.used, stream.widths, stream.offsets, stream.counts,
                stream.total_bits, stream.eb_i):
        arr.copy_to_host_async()
    return arena_core.PendingHostArena(lambda: arena_to_host(stream),
                                       names=stream.names)


# ------------------------------------------------------------ host side ----


@dataclasses.dataclass
class HostShardedStream:
    """Host-side view of a sharded stream: per-shard compressed payloads +
    index slices, no raw field.  Deliberately *not* a registered pytree —
    ``checkpoint.manager`` treats it as a single leaf and persists each
    shard with its existing ``leaf_i_sNNN.bin`` writer."""

    codec: str  # "insitu-sz" | "insitu-zfp"
    shape: tuple  # global field shape
    local_shape: tuple
    grid: tuple  # shards per field dim (np.ndindex order == stack order)
    halo: bool
    backend: str
    params: dict  # {"eb_i": float} | {"rate": int}
    shards: list  # [(((start, stop), ...), {name: np.ndarray}), ...]

    @property
    def nbytes_raw(self) -> int:
        return int(np.prod(self.shape)) * 4

    def accounting(self) -> dict:
        """Observatory record skeleton for this in-situ field (DESIGN.md
        §11): encode-time facts — codec, backend, shard grid, the error
        bound or rate it was compressed with, raw bytes.  The checkpoint
        manager adds stored bytes + wall when it persists the shards."""
        rec = {
            "kind": "insitu", "codec": self.codec, "backend": self.backend,
            "launches": 1,  # one sharded compress launch per field
            "shards": len(self.shards),
            "raw_bytes": int(self.nbytes_raw),
        }
        if "eb_i" in self.params:
            rec["eb_min"] = rec["eb_max"] = float(self.params["eb_i"])
        if "rate" in self.params:
            rec["rate"] = int(self.params["rate"])
        return rec


def _shard_indices(shape, grid):
    local = tuple(s // g for s, g in zip(shape, grid))
    for pos in np.ndindex(*grid):
        yield tuple((p * l, (p + 1) * l) for p, l in zip(pos, local))


def to_host(stream) -> HostShardedStream:
    """Pull a device stream to host — compressed bytes only, sliced to their
    true payload per shard (the ``bitpack.to_storage`` contract)."""
    grid = stream.grid
    local = tuple(s // g for s, g in zip(stream.shape, grid))
    if isinstance(stream, ShardedSZStream):
        words = np.asarray(stream.words)
        widths = np.asarray(stream.widths)
        bits = np.asarray(stream.total_bits)
        shards = []
        for s, idx in enumerate(_shard_indices(stream.shape, grid)):
            n_words = (int(bits[s]) - widths.shape[1] * 8 + 31) // 32
            shards.append((idx, {"words": words[s, :n_words].copy(),
                                 "widths": widths[s].copy(),
                                 "total_bits": np.int32(bits[s])}))
        return HostShardedStream(
            "insitu-sz", stream.shape, local, grid, stream.halo, stream.backend,
            {"eb_i": float(np.asarray(stream.eb))}, shards)
    words = np.asarray(stream.words)
    emax = np.asarray(stream.emax)
    gtops = np.asarray(stream.gtops)
    shards = [(idx, {"words": words[s].copy(), "emax": emax[s].copy(),
                     "gtops": gtops[s].copy()})
              for s, idx in enumerate(_shard_indices(stream.shape, grid))]
    return HostShardedStream(
        "insitu-zfp", stream.shape, local, grid, True, "any",
        {"rate": int(stream.rate)}, shards)


def host_decode(hss: HostShardedStream) -> np.ndarray:
    """Reassemble + decode a host stream without the mesh (the elastic
    restore path): stitch per-shard residual/coefficient planes, then run
    the *global* inverse — bitwise equal to both the sharded and the
    single-device decode for halo streams."""
    shape = tuple(hss.shape)
    if hss.codec == "insitu-zfp":
        out = np.empty(shape, np.float32)
        rate = int(hss.params["rate"])
        for idx, blobs in hss.shards:
            local = tuple(e - s for s, e in idx)
            c = zfp_core.ZFPCompressed(
                jnp.asarray(blobs["words"]), jnp.asarray(blobs["emax"]),
                jnp.asarray(blobs["gtops"]), local, rate)
            out[tuple(slice(s, e) for s, e in idx)] = np.asarray(zfp_core.decompress(c))
        return out
    eb_i = jnp.float32(hss.params["eb_i"])
    if hss.backend == "kernel" or not hss.halo:
        # tile-blocked / zero-border streams decode shard-locally
        out = np.empty(shape, np.float32)
        for idx, blobs in hss.shards:
            local = tuple(e - s for s, e in idx)
            packed = _rebuild_packed(blobs, int(np.prod(local)))
            if hss.backend == "kernel":
                from repro.kernels import ops as kops

                x = kops.sz_decompress_kernel(packed, local, local, eb_i)
            else:
                delta = bitpack.unpack_codes(packed).reshape(local)
                x = sz_core.lorenzo_reconstruct(delta).astype(jnp.float32) * (2.0 * eb_i)
            out[tuple(slice(s, e) for s, e in idx)] = np.asarray(x)
        return out
    delta = np.empty(shape, np.int32)
    for idx, blobs in hss.shards:
        local = tuple(e - s for s, e in idx)
        packed = _rebuild_packed(blobs, int(np.prod(local)))
        delta[tuple(slice(s, e) for s, e in idx)] = np.asarray(
            bitpack.unpack_codes(packed)).reshape(local)
    q = sz_core.lorenzo_reconstruct(jnp.asarray(delta))
    return np.asarray(q.astype(jnp.float32) * (2.0 * eb_i))


# One wire format for every compressed shard payload (per-leaf streams here,
# bucket arenas in ``core.arena``): json header + concatenated array bytes.
shard_payload_encode = arena_core.payload_encode
shard_payload_decode = arena_core.payload_decode


def host_stream_meta(hss: HostShardedStream) -> dict:
    """Manifest entry fields for a :class:`HostShardedStream` leaf."""
    return {
        "shape": list(hss.shape),
        "dtype": "float32",
        "codec": hss.codec,
        "insitu": {"local_shape": list(hss.local_shape),
                   "grid": list(hss.grid), "halo": bool(hss.halo),
                   "backend": hss.backend, "params": hss.params},
    }


def host_restore(meta: dict, payloads: list) -> np.ndarray:
    """Rebuild + decode from manifest metadata and per-shard payload bytes
    (what ``checkpoint.manager.restore`` read back), without the mesh."""
    info = meta["insitu"]
    shape = tuple(meta["shape"])
    grid = tuple(info["grid"])
    n_shards = int(np.prod(grid))
    if len(payloads) != n_shards:
        # same posture as the manager's sharded-leaf coverage check: a
        # sparse manifest (partial write, single process of a multi-process
        # mesh) must never leak np.empty through the stitched field
        raise IOError(f"insitu leaf has {len(payloads)} shard payloads, "
                      f"grid {grid} needs {n_shards}")
    shards = [(idx, shard_payload_decode(p))
              for idx, p in zip(_shard_indices(shape, grid), payloads)]
    hss = HostShardedStream(meta["codec"], shape, tuple(info["local_shape"]),
                            grid, bool(info["halo"]), info["backend"],
                            dict(info["params"]), shards)
    return host_decode(hss)


def _rebuild_packed(blobs: dict, n: int) -> bitpack.PackedCodes:
    return bitpack.from_storage(blobs["words"], blobs["widths"], n,
                                int(blobs["total_bits"]))
