"""What decides ``correct``: the program's output against the plain reference.

For every field of the configuration, one field operation of the window,
drawn from the seed, is kept: its host stream and its decoded field after
the host round trip.  Once the window has closed, with the bytes counted for
every operation on the field:

* ``stream_diff``  codes, widths, bounds (SZ) or words, ``emax``, ``gtops``
  (ZFP) of the host stream that differ from the reference encoder's on the
  same field, plus one for every stream whose length differs; limit 0;
* ``decode_diff``  decoded values (bit patterns) that differ from the
  reference decoder's output on the same host stream; limit 0;
* ``bytes_diff``   |bytes counted for the field - bytes of the reference's
  stream for it|, summed; limit 0;
* ``err_over_eb``  (SZ) max |reference decode of the host stream - field| /
  eb; limit 1, the configuration's guarantee.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import sz as rsz
from bench.reference import zfp as rzfp

LIMITS = {
    "tpu-sz": {"stream_diff": 0, "decode_diff": 0, "bytes_diff": 0, "err_over_eb": 1.0},
    "tpu-zfp": {"stream_diff": 0, "decode_diff": 0, "bytes_diff": 0},
}


def _bits(a: jax.Array) -> jax.Array:
    return jax.lax.bitcast_convert_type(a.astype(jnp.float32), jnp.uint32)


def _decode_diff(got: jax.Array, want: jax.Array) -> int:
    if got.shape != want.shape:
        return int(want.size)
    return int(jnp.sum(_bits(got) != _bits(want)))


def sz_field(x: jax.Array, host: dict, decoded: jax.Array, eb: float) -> dict:
    """One SZ field against the reference."""
    fmt = host["fmt"]
    xs, shapes = rsz.coded_parts(x, fmt), rsz.coded_shapes(x.shape, fmt)
    diff, ref_bytes, outs = abs(len(host["parts"]) - len(xs)), 0, []
    for xc, shp, p in zip(xs, shapes, host["parts"]):
        codes, widths, eb_i = rsz.encode_codes(xc, eb, fmt)
        wref = np.asarray(widths)
        ref_bytes += rsz.stream_nbytes(wref)
        n = int(p["n"])
        if n != codes.shape[0] or p["widths"].shape != wref.shape:
            diff += codes.shape[0]
            outs = None
            continue
        diff += int(np.sum(p["widths"] != wref))
        diff += int(p["words"].size != 2 * int(np.sum(wref.astype(np.int64))))
        diff += int(np.float32(p["eb_i"]).view(np.uint32) != np.asarray(eb_i).view(np.uint32))
        got = rsz.unpack(p["words"], p["widths"], n)
        diff += int(np.sum(got != np.asarray(codes)))
        if outs is not None:
            outs.append(rsz.reconstruct(jnp.asarray(got), p["eb_i"], shp, fmt))
    if outs is None or len(outs) != len(xs):
        return {"stream_diff": diff, "decode_diff": int(x.size), "ref_bytes": ref_bytes,
                "err_over_eb": math.inf}
    ref = rsz.crop(outs, x.shape)
    err = float(jnp.max(jnp.abs(ref - x))) / eb
    return {"stream_diff": diff, "decode_diff": _decode_diff(decoded, ref),
            "ref_bytes": ref_bytes, "err_over_eb": err}


def zfp_field(x: jax.Array, host: dict, decoded: jax.Array, rate: int) -> dict:
    """One ZFP field against the reference."""
    shape3 = tuple(s + (-s) % 4 for s in rzfp.view3d(x.shape))
    nb = math.prod(rzfp.grid(shape3))
    ref_bytes = rzfp.stream_nbytes(nb, rate)
    wpb = rzfp.words_per_block(rate)
    p = host["parts"][0] if len(host["parts"]) == 1 else None
    if (p is None or p["words"].shape != (nb, wpb) or p["emax"].shape != (nb,)
            or p["gtops"].shape != (nb, rzfp.N_GROUPS)):
        return {"stream_diff": nb * wpb, "decode_diff": int(x.size), "ref_bytes": ref_bytes}
    xp = rzfp.padded_field(x)
    diff, outs = 0, []
    for z0, z1, b0, b1 in rzfp.chunks(x.shape):
        want = rzfp.encode_chunk(xp[z0:z1], rate)
        got = [jnp.asarray(p[k][b0:b1]) for k in ("words", "emax", "gtops")]
        diff += sum(int(jnp.sum(g != w)) for g, w in zip(got, want))
        outs.append(rzfp.decode_chunk(*got, rate, (z1 - z0,) + shape3[1:]))
    ref = rzfp.crop(jnp.concatenate(outs) if len(outs) > 1 else outs[0], x.shape)
    return {"stream_diff": diff, "decode_diff": _decode_diff(decoded, ref), "ref_bytes": ref_bytes}


def field(codec: str, x: jax.Array, host: dict, decoded: jax.Array, counted: list[int],
          params: dict) -> dict:
    """Numbers of one field: its kept host stream and decoded field, and the
    bytes counted for every operation on it in the window."""
    out = (sz_field(x, host, decoded, params["eb"]) if codec == "tpu-sz"
           else zfp_field(x, host, decoded, params["rate"]))
    ref = out.pop("ref_bytes")
    out["bytes_diff"] = sum(abs(c - ref) for c in counted)
    return out


def verdict(codec: str, per_field: dict[str, dict], fields: list[str]) -> tuple[bool, dict, int]:
    """(correct, {number: {"value", "limit"}}, fields that failed)."""
    limits = LIMITS[codec]
    agg = {k: 0 for k in limits}
    failed = 0
    for name in fields:
        nums = per_field.get(name)
        if nums is None:  # a field the window never finished
            failed += 1
            agg["stream_diff"] += 1
            continue
        failed += any(nums[k] > lim for k, lim in limits.items())
        for k in limits:
            agg[k] = max(agg[k], nums[k]) if k == "err_over_eb" else agg[k] + nums[k]
    checks = {k: {"value": agg[k], "limit": limits[k]} for k in limits}
    return failed == 0 and all(agg[k] <= limits[k] for k in limits), checks, failed
