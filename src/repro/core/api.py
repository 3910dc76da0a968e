"""Unified compressor registry + pytree (de)compression.

This is the surface the rest of the framework uses: checkpointing, gradient
collectives, the serving KV cache, CBench sweeps and the benchmarks all go
through ``get_compressor(name)``.

Modes (paper §II-A):
  * ``abs``     — error-bounded, |x̂ - x| <= eb           (TPU-SZ)
  * ``pw_rel``  — pointwise relative via log transform    (TPU-SZ, Liang'18)
  * ``rate``    — fixed rate, exact bits/value            (TPU-ZFP)
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitpack, sz, transforms, zfp
from repro.obs import trace as obs_trace

MAX_CHUNK = 1 << 24  # elements per SZ packing call (int32 bit-offset safety)

# Stacked-input element budget per vmapped call: vmapping multiplies every
# intermediate by the batch size, so an unbounded stack of 2^27-element HACC
# partitions would OOM a device the sequential loop fits on.  2^26 f32
# elements (~256 MB input) keeps the dispatch win for the small-partition
# regimes where dispatch actually dominates.
VMAP_ELEM_BUDGET = 1 << 26


def _vmap_chunks(keys: list[tuple], elem_budget: int):
    """Shared grouping for chunked-vmap batching: group part indices by
    ``key`` (whose first element is the part's shape) and split each group
    into sublists small enough for one vmapped dispatch.  Both compressors'
    compress and decompress paths drive their batching off this."""
    by_key: dict[tuple, list[int]] = {}
    for i, k in enumerate(keys):
        by_key.setdefault(k, []).append(i)
    for key, idxs in by_key.items():
        chunk = max(1, elem_budget // max(int(np.prod(key[0])), 1))
        for s in range(0, len(idxs), chunk):
            yield idxs[s : s + chunk]


def _tree_stack(group: list) -> Any:
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *group)


def _tree_row(batched: Any, j: int) -> Any:
    return jax.tree_util.tree_map(lambda a: a[j], batched)


def _batched_apply(items: list, keys: list[tuple], budget: int, fn) -> list:
    """Apply jit-able ``fn`` per item with chunked-vmap batching: group the
    items by ``keys`` (:func:`_vmap_chunks`), stack each group, run one
    vmapped dispatch, and slice the rows back into a per-item list (so
    payload layouts and wire formats are unchanged).  Shared by both
    compressors' compress and decompress paths."""
    out: list[Any] = [None] * len(items)
    for sub in _vmap_chunks(keys, budget):
        if len(sub) == 1:
            out[sub[0]] = fn(items[sub[0]])
            continue
        batched = jax.vmap(fn)(_tree_stack([items[i] for i in sub]))
        for j, i in enumerate(sub):
            out[i] = _tree_row(batched, j)
    return out


@dataclasses.dataclass(frozen=True)
class CompressionResult:
    """Host-facing record: payload pytree + exact storage accounting."""

    payload: Any
    nbytes: int
    raw_nbytes: int
    meta: dict[str, Any]

    @property
    def ratio(self) -> float:
        return self.raw_nbytes / max(self.nbytes, 1)

    @property
    def bitrate(self) -> float:
        """Bits per value: compressed bits over the f32 value count."""
        return 8.0 * self.nbytes / max(self.raw_nbytes / 4.0, 1.0)


class SZCompressor:
    """TPU-SZ front end. Accepts 1-D/2-D/3-D fields; 1-D fields are reshaped
    to the paper's 3-D partitions before prediction (§IV-B4).

    ``backend`` selects the encode/decode engine for 3-D fields:
      * ``core``   — global-Lorenzo XLA path (best compression ratio; the
                     default off-TPU),
      * ``kernel`` — the fused single-pass Pallas pipeline from
                     ``repro.kernels.sz_fused`` (tile-blocked prediction,
                     GPU-SZ style; fastest on TPU, where residuals never
                     touch HBM),
      * ``auto``   — ``kernel`` on TPU, ``core`` elsewhere.
    Non-3-D fields always use the core path (the 1-D partitioning already
    reshapes to 3-D cubes, but their sides are not tile-multiples)."""

    name = "tpu-sz"

    VMAP_ELEM_BUDGET = VMAP_ELEM_BUDGET  # per-class override point (tests)

    def __init__(self, block_size: int | None = None, reshape_1d: bool = True,
                 backend: str = "auto"):
        if backend not in ("auto", "core", "kernel"):
            raise ValueError(f"unknown SZ backend {backend!r}; want auto|core|kernel")
        self.block_size = block_size
        self.reshape_1d = reshape_1d
        self.backend = backend

    def _use_kernel(self, x: jax.Array) -> bool:
        if x.ndim != 3 or self.block_size is not None:
            return False
        if self.backend == "kernel":
            return True
        return self.backend == "auto" and jax.default_backend() == "tpu"

    def _canonical(self, x: jax.Array) -> tuple[jax.Array, dict]:
        if x.ndim == 1 and self.reshape_1d:
            parts = transforms.partition_1d(x)
            shaped = []
            for p in parts:
                side = int(np.ceil(len(p) ** (1 / 3)))
                side = max(4, side)
                shaped.append(transforms.to_3d(p, (side, side, side)))
            return shaped, {"orig_len": x.shape[0], "was_1d": True}
        return [x], {"orig_len": int(np.prod(x.shape)), "was_1d": False}

    def _compress_parts(self, parts: list[jax.Array], eb) -> tuple[list, int]:
        """Compress all partitions with vmapped dispatches (grouped/chunked
        by :func:`_batched_apply`) instead of one jit call per partition."""
        comp = _batched_apply(parts, [(p.shape,) for p in parts],
                              self.VMAP_ELEM_BUDGET,
                              lambda p: sz.compress(p, eb, self.block_size))
        # per-part total_bits are int32; sum on host in int64 (many
        # partitions can exceed 2**31 bits combined)
        with obs_trace.span("sync.total_bits"):
            nbits = int(np.sum([np.asarray(c.packed.total_bits, np.int64) for c in comp]))
        return comp, nbits

    def _decompress_parts(self, parts_c: list) -> list[jax.Array]:
        """Mirror of :meth:`_compress_parts` for the read path: one vmapped
        dispatch per distinct (shape, block_size) group of partitions."""
        return _batched_apply(parts_c, [(c.shape, c.block_size) for c in parts_c],
                              self.VMAP_ELEM_BUDGET, sz.decompress)

    def compress(self, x: jax.Array, eb: float | None = None, pw_rel: float | None = None,
                 **_: Any) -> CompressionResult:
        with obs_trace.span("api.compress", codec=self.name):
            return self._compress(x, eb, pw_rel)

    def _compress(self, x: jax.Array, eb, pw_rel) -> CompressionResult:
        raw = int(np.prod(x.shape)) * 4
        side_bits = 0
        meta: dict[str, Any] = {"mode": "abs", "eb": eb}
        signs = None
        if pw_rel is not None:
            t = transforms.log_forward(x)
            x, signs = t.logs, t.signs
            eb = transforms.pwrel_to_abs(pw_rel)
            side_bits = transforms.sign_channel_bits(int(np.prod(x.shape)))
            meta = {"mode": "pw_rel", "pw_rel": pw_rel, "eb_log": eb}
        if eb is None:
            raise ValueError("SZ requires eb= (ABS) or pw_rel=")
        if self._use_kernel(x):
            from repro.kernels import ops as kops

            packed, padded_shape, eb_i = kops.sz_compress_kernel(x, eb)
            with obs_trace.span("sync.total_bits"):
                nbits = int(packed.total_bits) + side_bits
            payload = {"kernel": True, "kpacked": packed, "padded_shape": padded_shape,
                       "eb_i": eb_i, "signs": signs, "shape": x.shape,
                       "orig_len": int(np.prod(x.shape)), "was_1d": False}
            meta.update({"was_1d": False, "backend": "kernel"})
            return CompressionResult(payload, (nbits + 7) // 8, raw, meta)
        parts, shape_meta = self._canonical(x)
        comp, nbits = self._compress_parts(parts, eb)
        nbits += side_bits
        payload = {"parts": comp, "signs": signs, "shape": x.shape, **shape_meta}
        meta.update(shape_meta)
        return CompressionResult(payload, (nbits + 7) // 8, raw, meta)

    def decompress(self, r: CompressionResult) -> jax.Array:
        with obs_trace.span("api.decompress", codec=self.name):
            return self._decompress(r)

    def _decompress(self, r: CompressionResult) -> jax.Array:
        if r.payload.get("kernel"):
            from repro.kernels import ops as kops

            x = kops.sz_decompress_kernel(r.payload["kpacked"], r.payload["padded_shape"],
                                          r.payload["shape"], r.payload["eb_i"])
        else:
            parts = self._decompress_parts(r.payload["parts"])
            if r.payload["was_1d"]:
                flats = [transforms.from_3d(p, min(transforms.HACC_PARTITION,
                                                   r.payload["orig_len"] - i * transforms.HACC_PARTITION))
                         for i, p in enumerate(parts)]
                x = jnp.concatenate(flats)[: r.payload["orig_len"]]
            else:
                x = parts[0].reshape(r.payload["shape"])
        if r.meta["mode"] == "pw_rel":
            t = transforms.LogTransformed(x, r.payload["signs"], jnp.float32(0))
            x = transforms.log_inverse(t)
        return x


class ZFPCompressor:
    """TPU-ZFP front end (fixed-rate). 1-D fields are partitioned to the
    paper's HACC layout and go through the (N/64) x 8 x 8 reshape per
    partition (§IV-B4); 2-D fields get a trailing unit axis.

    ``backend`` selects the encode/decode engine (mirroring ``SZCompressor``):
      * ``core``   — the pure-XLA word-level coder in ``repro.core.zfp``
                     (the default off-TPU),
      * ``kernel`` — the fused single-pass Pallas pipeline from
                     ``repro.kernels.zfp_fused`` (block-float + lifting +
                     negabinary + header + embedded packing in one VMEM
                     pass; fastest on TPU, where the coefficient planes
                     never touch HBM),
      * ``auto``   — ``kernel`` on TPU, ``core`` elsewhere.
    All backends emit byte-identical ``words``/``emax``/``gtops`` streams
    and decode each other's payloads.

    Accounting: ``raw_nbytes`` (and hence ``ratio``/``bitrate``) always uses
    the *original* pre-reshape element count — the zero padding the 1-D/2-D
    reshapes introduce is charged to the compressed size, not the input.
    """

    name = "tpu-zfp"

    VMAP_ELEM_BUDGET = VMAP_ELEM_BUDGET  # per-class override point (tests)

    def __init__(self, reshape_1d: bool = True, backend: str = "auto"):
        if backend not in ("auto", "core", "kernel"):
            raise ValueError(f"unknown ZFP backend {backend!r}; want auto|core|kernel")
        self.reshape_1d = reshape_1d
        self.backend = backend

    def _use_kernel(self) -> bool:
        if self.backend == "kernel":
            return True
        return self.backend == "auto" and jax.default_backend() == "tpu"

    def _canonical(self, x: jax.Array) -> tuple[list[jax.Array], dict]:
        if x.ndim == 1:
            # Paper §IV-B4: cuZFP on HACC uses (N/64) x 8 x 8 partitions.
            # The coder is 3-D only, so the reshape is mandatory;
            # ``reshape_1d=False`` just skips the HACC partitioning.
            parts = transforms.partition_1d(x) if self.reshape_1d else [x]
            shaped = [transforms.to_3d(p, (-(-p.shape[0] // 64), 8, 8)) for p in parts]
            return shaped, {"orig_len": x.shape[0], "was_1d": True}
        if x.ndim == 2:
            x = x[:, :, None]
        return [x], {"orig_len": int(np.prod(x.shape)), "was_1d": False}

    def _compress_parts(self, parts: list[jax.Array], rate: int) -> list:
        """Chunked-vmap batching over same-shape partitions via
        :func:`_batched_apply` (shared with ``SZCompressor``).  The kernel
        backend dispatches per partition (a Pallas grid already walks the
        whole field)."""
        if self._use_kernel():
            from repro.kernels import ops as kops

            return [kops.zfp_compress_kernel(p, rate) for p in parts]
        return _batched_apply(parts, [(p.shape,) for p in parts],
                              self.VMAP_ELEM_BUDGET,
                              lambda p: zfp.compress(p, rate))

    def _decompress_parts(self, parts_c: list) -> list[jax.Array]:
        if self._use_kernel():
            from repro.kernels import ops as kops

            return [kops.zfp_decompress_kernel(c) for c in parts_c]
        return _batched_apply(parts_c, [(c.shape, c.rate) for c in parts_c],
                              self.VMAP_ELEM_BUDGET, zfp.decompress)

    def compress(self, x: jax.Array, rate: int | None = None, **_: Any) -> CompressionResult:
        with obs_trace.span("api.compress", codec=self.name):
            return self._compress(x, rate)

    def _compress(self, x: jax.Array, rate: int | None) -> CompressionResult:
        if rate is None:
            raise ValueError("ZFP requires rate= (bits/value)")
        raw = int(np.prod(x.shape)) * 4  # original count: padding not charged
        orig_shape = x.shape
        parts, shape_meta = self._canonical(x)
        comp = self._compress_parts(parts, rate)
        nbytes = sum(zfp.compressed_nbytes(c) for c in comp)
        backend = "kernel" if self._use_kernel() else "core"
        payload = {"parts": comp, "orig_shape": orig_shape, **shape_meta}
        return CompressionResult(payload, nbytes, raw,
                                 {"mode": "rate", "rate": rate, "backend": backend,
                                  **shape_meta})

    def decompress(self, r: CompressionResult) -> jax.Array:
        with obs_trace.span("api.decompress", codec=self.name):
            return self._decompress(r)

    def _decompress(self, r: CompressionResult) -> jax.Array:
        parts = self._decompress_parts(r.payload["parts"])
        orig = r.payload["orig_shape"]
        if r.payload["was_1d"]:
            flats = [p.reshape(-1) for p in parts]
            return jnp.concatenate(flats)[: orig[0]]
        x = parts[0]
        if len(orig) == 2:
            return x[:, :, 0]
        return x


_REGISTRY: dict[str, Callable[..., Any]] = {
    "tpu-sz": SZCompressor,
    "tpu-zfp": ZFPCompressor,
}


def get_compressor(name: str, **kwargs: Any):
    if name not in _REGISTRY:
        raise KeyError(f"unknown compressor {name!r}; have {sorted(_REGISTRY)}")
    return _REGISTRY[name](**kwargs)


def available() -> list[str]:
    return sorted(_REGISTRY)
