"""The device generators at small sizes: deterministic in the seed, inside
the configurations' value ranges, HACC particles in rank-major order."""

import json

import numpy as np
import pytest

from bench import cells, data

HACC = json.loads((cells.BENCH / "configs" / "hacc.json").read_text())


@pytest.fixture(scope="module")
def nyx():
    config = dict(cells.load_cell("nyx256.sz_tight").config, grid=32)
    return config, data.generate(config, 2**31 + 5)


@pytest.fixture(scope="module")
def hacc():
    config = dict(HACC, grid=32)
    return config, data.generate(config, 7)


def test_nyx_deterministic_and_seeded(nyx):
    config, a = nyx
    b = data.generate(config, 2**31 + 5)
    c = data.generate(config, 6)
    for name in config["fields"]:
        assert a[name].shape == (32, 32, 32) and a[name].dtype == np.float32
        assert np.array_equal(np.asarray(a[name]), np.asarray(b[name]))
        assert not np.array_equal(np.asarray(a[name]), np.asarray(c[name]))


def test_nyx_table_ii_ranges(nyx):
    config, f = nyx
    for name in config["fields"]:
        x = np.asarray(f[name])
        lo, hi = config["ranges"][name]
        assert np.isfinite(x).all() and x.min() >= lo and x.max() <= hi, name
    assert np.asarray(f["baryon_density"]).max() == pytest.approx(1e5, rel=1e-6)
    assert np.abs(np.asarray(f["vx"])).max() == pytest.approx(0.8e8, rel=1e-6)
    assert np.asarray(f["temperature"]).min() >= 1e2


def test_hacc_ranges_and_rank_major(hacc):
    config, f = hacc
    n = 32**3
    box, vmax = config["box"], config["velocity_max"]
    pos = [np.asarray(f[k]) for k in ("x", "y", "z")]
    for name in config["fields"]:
        assert f[name].shape == (n,) and np.isfinite(np.asarray(f[name])).all()
    for p in pos:
        assert p.min() >= 0.0 and p.max() <= box
    for k in ("vx", "vy", "vz"):
        assert np.abs(np.asarray(f[k])).max() <= vmax
    rx, ry, rz = config["ranks"]
    idx = [np.clip(np.floor(p / (box / r)).astype(int), 0, r - 1) for p, r in zip(pos, (rx, ry, rz))]
    rank = (idx[0] * ry + idx[1]) * rz + idx[2]
    assert np.all(np.diff(rank) >= 0)
    assert len(np.unique(rank)) > rx * ry * rz // 2


def test_hacc_deterministic(hacc):
    config, a = hacc
    b = data.generate(config, 7)
    assert all(np.array_equal(np.asarray(a[k]), np.asarray(b[k])) for k in config["fields"])


def test_stable_order_matches_numpy():
    from bench.data.hacc import _stable_order

    key = np.random.default_rng(0).integers(0, 256, 5000).astype(np.int32)
    assert np.array_equal(np.asarray(_stable_order(key, 8)), np.argsort(key, kind="stable"))


def test_halo_share():
    config = HACC
    from bench.data.hacc import _halo_count

    lo, hi = config["halo_members"]
    # the mean of the m^-2 law on [20, 3000] is ~101 members
    assert 1.25 * 1e6 / 110 < _halo_count(10**6, lo, hi, config["mass_slope"]) < 1.25 * 1e6 / 90 + 16
