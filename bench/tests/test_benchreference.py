"""The plain references reproduce the program's streams and decodes bit for
bit (small sizes, CPU), and their bfloat16 control departs from them."""

import json

import jax.numpy as jnp
import numpy as np
import pytest

from bench import cells, data
from bench.reference import sz as rsz
from bench.reference import zfp as rzfp
from repro.core import bitpack
from repro.core.api import get_compressor

HACC = json.loads((cells.BENCH / "configs" / "hacc.json").read_text())


@pytest.fixture(scope="module")
def fields():
    nyx = dict(cells.load_cell("nyx256.sz_tight").config, grid=32)
    hacc = dict(HACC, grid=32)
    return {**data.generate(nyx, 3), **{"h" + k: v for k, v in data.generate(hacc, 4).items()}}


def _sz_host(packed, eb_i):
    return dict(bitpack.to_storage(packed), eb_i=np.asarray(eb_i))


@pytest.mark.parametrize("name,eb", [("baryon_density", 3.0), ("vx", 2e5), ("hx", 0.005), ("hvz", 0.5)])
def test_sz_global_matches_program(fields, name, eb):
    x = fields[name]
    comp = get_compressor("tpu-sz", backend="core")
    r = comp.compress(x, eb=eb)
    parts = [_sz_host(c.packed, c.eb) for c in r.payload["parts"]]
    for xc, p in zip(rsz.coded_parts(x, "global"), parts):
        codes, widths, eb_i = rsz.encode_codes(xc, eb, "global")
        assert np.array_equal(np.asarray(widths), p["widths"])
        assert np.asarray(eb_i) == p["eb_i"]
        words = np.asarray(rsz.pack(codes, widths))[:p["words"].size]
        assert np.array_equal(words, p["words"])
        assert np.array_equal(np.asarray(rsz.unpack(p["words"], p["widths"], int(p["n"]))),
                              np.asarray(codes))
        assert rsz.stream_nbytes(p["widths"]) == sum(a.nbytes for a in p.values())
    ref = rsz.decode_host(parts, x.shape, "global")
    assert np.array_equal(np.asarray(ref), np.asarray(comp.decompress(r)))
    assert float(jnp.max(jnp.abs(ref - x))) <= eb


def test_sz_tiled_matches_kernel_path(fields):
    x = fields["temperature"][:8, :, :]  # padded to one (8, 64, 128) tile
    comp = get_compressor("tpu-sz", backend="kernel")
    r = comp.compress(x, eb=200.0)
    p = _sz_host(r.payload["kpacked"], r.payload["eb_i"])
    (xc,) = rsz.coded_parts(x, "tiled")
    assert xc.shape == (8, 64, 128)
    codes, widths, eb_i = rsz.encode_codes(xc, 200.0, "tiled")
    assert np.array_equal(np.asarray(widths), p["widths"]) and np.asarray(eb_i) == p["eb_i"]
    assert np.array_equal(np.asarray(rsz.unpack(p["words"], p["widths"], int(p["n"]))),
                          np.asarray(codes))
    ref = rsz.decode_host([p], x.shape, "tiled")
    assert np.array_equal(np.asarray(ref), np.asarray(comp.decompress(r)))


@pytest.mark.parametrize("name", ["baryon_density", "vz", "hx", "hvx"])
def test_zfp_matches_program(fields, name):
    x = fields[name]
    comp = get_compressor("tpu-zfp", backend="core")
    r = comp.compress(x, rate=8)
    (c,) = r.payload["parts"]
    got = rzfp.encode_host(x, 8)
    for k in ("words", "emax", "gtops"):
        assert np.array_equal(got[k], np.asarray(getattr(c, k))), k
    assert sum(a.nbytes for a in got.values()) == rzfp.stream_nbytes(c.emax.size, 8)
    ref = rzfp.decode_host(got, x.shape, 8)
    assert np.array_equal(np.asarray(ref), np.asarray(comp.decompress(r)))


def test_zfp_chunks_cover_the_field():
    for shape in ((32, 32, 32), (40, 12, 20), (32768,)):
        spans = list(rzfp.chunks(shape))
        assert spans[0][0] == 0 and spans[0][2] == 0
        assert all(a[1] == b[0] and a[3] == b[2] for a, b in zip(spans, spans[1:]))
        shape3 = tuple(s + (-s) % 4 for s in rzfp.view3d(shape))
        assert spans[-1][1] == shape3[0]
        assert spans[-1][3] == np.prod(rzfp.grid(shape3))


def test_bf16_control_departs(fields):
    x = fields["baryon_density"]
    bad = rsz.encode_host(x, 3.0, "global", jnp.bfloat16)
    assert float(jnp.max(jnp.abs(rsz.decode_host(bad, x.shape, "global") - x))) > 3.0 * 3
    good, worse = rzfp.encode_host(x, 8), rzfp.encode_host(x, 8, jnp.bfloat16)
    assert np.sum(good["words"] != worse["words"]) > 0
