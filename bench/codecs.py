"""The program's codecs, driven through the entry points users call.

Each driver has the four phases the window times, for one whole field:

* ``compress``   ``get_compressor(codec).compress`` -> payload ready on the
  device;
* ``fetch``      payload -> stream bytes in host memory (SZ: the program's
  ``bitpack.to_storage`` and the internal bound; ZFP: ``jax.device_get`` of
  ``words``/``emax``/``gtops``, as ``dist.insitu.to_host`` does);
* ``upload``     host bytes -> payload rebuilt on the device (SZ:
  ``bitpack.from_storage``/``sz.from_stream``; ZFP: ``jnp.asarray``) and
  ready;
* ``decompress`` ``get_compressor(codec).decompress`` -> field ready.

A host stream is ``{"fmt": ..., "parts": [dict of numpy arrays, ...]}``;
its size is the bytes of those arrays: everything the decoder needs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitpack
from repro.core import sz as core_sz
from repro.core import zfp as core_zfp
from repro.core.api import CompressionResult, get_compressor


def host_nbytes(host: dict) -> int:
    return sum(int(a.nbytes) for part in host["parts"] for a in part.values())


def _rebuilt(r: CompressionResult, **payload) -> CompressionResult:
    return CompressionResult(dict(r.payload, **payload), r.nbytes, r.raw_nbytes, r.meta)


class SZ:
    codec = "tpu-sz"
    fetch_shape_depends_on_data = True  # to_storage slices the stream to its length

    def __init__(self):
        self.comp = get_compressor(self.codec)

    @staticmethod
    def _streams(r: CompressionResult):
        if r.payload.get("kernel"):
            return "tiled", [(r.payload["kpacked"], r.payload["eb_i"])]
        return "global", [(c.packed, c.eb) for c in r.payload["parts"]]

    def compress(self, x: jax.Array, params: dict) -> CompressionResult:
        r = self.comp.compress(x, eb=params["eb"])
        jax.block_until_ready(self._streams(r)[1])
        return r

    def fetch(self, r: CompressionResult) -> dict:
        fmt, streams = self._streams(r)
        parts = [dict(bitpack.to_storage(packed), eb_i=np.asarray(eb_i))
                 for packed, eb_i in streams]
        return {"fmt": fmt, "parts": parts}

    def upload(self, r: CompressionResult, host: dict) -> CompressionResult:
        ps = host["parts"]
        if r.payload.get("kernel"):
            p = ps[0]
            out = _rebuilt(r, kpacked=bitpack.from_storage(p["words"], p["widths"], int(p["n"])),
                           eb_i=jnp.asarray(p["eb_i"]))
        else:
            out = _rebuilt(r, parts=[
                core_sz.from_stream(p["words"], p["widths"], int(p["n"]), p["eb_i"], c.shape,
                                    block_size=c.block_size)
                for p, c in zip(ps, r.payload["parts"])])
        jax.block_until_ready(self._streams(out)[1])
        return out

    def decompress(self, r: CompressionResult) -> jax.Array:
        return jax.block_until_ready(self.comp.decompress(r))


class ZFP:
    codec = "tpu-zfp"
    fetch_shape_depends_on_data = False

    def __init__(self):
        self.comp = get_compressor(self.codec)

    @staticmethod
    def _arrays(r: CompressionResult):
        return [(c.words, c.emax, c.gtops) for c in r.payload["parts"]]

    def compress(self, x: jax.Array, params: dict) -> CompressionResult:
        r = self.comp.compress(x, rate=params["rate"])
        jax.block_until_ready(self._arrays(r))
        return r

    def fetch(self, r: CompressionResult) -> dict:
        parts = [dict(zip(("words", "emax", "gtops"), jax.device_get(a))) for a in self._arrays(r)]
        return {"fmt": "fixed-rate", "parts": parts}

    def upload(self, r: CompressionResult, host: dict) -> CompressionResult:
        out = _rebuilt(r, parts=[
            core_zfp.ZFPCompressed(jnp.asarray(p["words"]), jnp.asarray(p["emax"]),
                                   jnp.asarray(p["gtops"]), c.shape, c.rate)
            for p, c in zip(host["parts"], r.payload["parts"])])
        jax.block_until_ready(self._arrays(out))
        return out

    def decompress(self, r: CompressionResult) -> jax.Array:
        return jax.block_until_ready(self.comp.decompress(r))


DRIVERS = {SZ.codec: SZ, ZFP.codec: ZFP}


def make(mix: dict):
    return DRIVERS[mix["codec"]]()
