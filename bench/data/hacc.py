"""HACC-like particle snapshot (the model of
``repro.data.cosmo.hacc_particles``, on the device, vectorised).

A share of the particles sits in halos whose member counts follow a power
law; each halo has an NFW-like radial profile, a bulk velocity and a
virial-scaled dispersion.  The rest is a Zel'dovich-displaced lattice
background.  Particles are ordered rank-major over the 8x8x4 decomposition
of the box, as GenericIO stores them.  The seed's key translates the
periodic box by one offset before that ordering (another view of the same
realization).  Every per-particle quantity is kept
as one 1-D array per axis: an (N, 3) array would be padded to 128 lanes on
the TPU.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.data.grf import spectrum


def _halo_count(n_in: int, lo: float, hi: float, slope: float) -> int:
    """Halos drawn: the expected count for ``n_in`` members, plus a quarter."""
    a, b = slope + 1.0, slope + 2.0
    norm = math.log(hi / lo) if abs(a) < 1e-12 else (hi**a - lo**a) / a
    first = math.log(hi / lo) if abs(b) < 1e-12 else (hi**b - lo**b) / b
    return int(1.25 * n_in / (first / norm)) + 16


def _halos(config: dict, ks, n_in: int):
    n = config["grid"]
    box, vel_max = config["box"], config["velocity_max"]
    lo, hi = config["halo_members"]
    a = config["mass_slope"] + 1.0
    n_halos = _halo_count(n_in, lo, hi, config["mass_slope"])
    # member counts by inverse CDF; particles map to halos by searchsorted
    # over the cumulative counts (the last halo is truncated)
    u = jax.random.uniform(ks[0], (n_halos,))
    mass = jnp.floor((lo**a + u * (hi**a - lo**a)) ** (1.0 / a)).astype(jnp.int32)
    h = jnp.searchsorted(jnp.cumsum(mass), jnp.arange(n_in, dtype=jnp.int32), side="right")
    h = jnp.minimum(h, n_halos - 1)
    scale = (mass[h].astype(jnp.float32) / 20.0) ** (1.0 / 3.0)
    r_s = 0.10 * (box / n) * scale
    uu = jax.random.uniform(ks[1], (n_in,), minval=0.05, maxval=1.0)
    r = jnp.minimum(r_s * (uu**-0.6 - 1.0 + 0.05), 8.0 * r_s)
    d = [jax.random.normal(jax.random.fold_in(ks[2], i), (n_in,)) for i in range(3)]
    norm = jnp.sqrt(d[0] ** 2 + d[1] ** 2 + d[2] ** 2) + 1e-12
    pos, vel = [], []
    for i in range(3):
        center = jax.random.uniform(jax.random.fold_in(ks[3], i), (n_halos,), maxval=box)
        bulk = jax.random.normal(jax.random.fold_in(ks[4], i), (n_halos,)) * (0.15 * vel_max)
        pos.append(jnp.mod(center[h] + r * d[i] / norm, box))
        disp = jax.random.normal(jax.random.fold_in(ks[5], i), (n_in,))
        vel.append(bulk[h] + disp * (0.02 * vel_max) * scale)
    return pos, vel


def _background(config: dict, ks, n_field: int):
    # Zel'dovich displacement of a random subset of lattice sites: each site
    # is drawn with a probability a little above the share needed and the
    # first n_field drawn, in lattice order, are kept; the potential is made
    # on a power-of-two grid and read at each site's nearest cell
    n = config["grid"]
    total = n**3
    box, vel_max = config["box"], config["velocity_max"]
    cell = box / n
    draw = jax.random.uniform(ks[6], (total,)) < (n_field / total + config["site_margin"])
    slot = jnp.cumsum(draw.astype(jnp.int32)) - 1
    slot = jnp.where(draw & (slot < n_field), slot, n_field)
    sel = jnp.zeros((n_field,), jnp.int32).at[slot].set(
        jnp.arange(total, dtype=jnp.int32), mode="drop")
    site = [sel // (n * n), (sel // n) % n, sel % n]
    pg = config["potential_grid"]
    cidx = ((site[0] * pg // n) * pg + site[1] * pg // n) * pg + site[2] * pg // n
    phi_k = spectrum(ks[7], pg, config["potential_slope"])
    pos, vel = [], []
    for i, k1 in enumerate((jnp.fft.fftfreq(pg)[:, None, None], jnp.fft.fftfreq(pg)[None, :, None],
                            jnp.fft.rfftfreq(pg)[None, None, :])):
        dd = jnp.fft.irfftn(phi_k * (2j * jnp.pi * k1), s=(pg, pg, pg)).reshape(-1)
        dv = dd[cidx] / jnp.maximum(jnp.std(dd), 1e-12)
        pos.append(jnp.mod((site[i].astype(jnp.float32) + 0.5) * cell + 1.5 * cell * dv, box))
        noise = jax.random.normal(jax.random.fold_in(ks[8], i), (n_field,))
        vel.append(0.25 * vel_max * dv + noise * (0.02 * vel_max))
    return pos, vel


def _stable_order(key: jax.Array, bits: int) -> jax.Array:
    """Stable argsort of small non-negative keys: one stable partition per
    key bit, least significant first (no sort primitive)."""
    order = jnp.arange(key.shape[0], dtype=jnp.int32)
    for b in range(bits):
        bit = (key[order] >> b) & 1
        zeros = jnp.cumsum(1 - bit)
        dest = jnp.where(bit == 0, zeros - 1, zeros[-1] + jnp.cumsum(bit) - 1)
        order = jnp.zeros_like(order).at[dest].set(order, unique_indices=True)
    return order


def fields(config: dict, key: jax.Array, seed_key: jax.Array) -> dict[str, jax.Array]:
    total = config["grid"] ** 3
    box, vel_max = config["box"], config["velocity_max"]
    n_in = int(config["halo_fraction"] * total)
    ks = jax.random.split(key, 9)
    hpos, hvel = _halos(config, ks, n_in)
    fpos, fvel = _background(config, ks, total - n_in)
    shift = jax.random.uniform(seed_key, (3,), maxval=box)
    pos = [jnp.mod(jnp.concatenate([a, b]) + shift[i], box) for i, (a, b) in enumerate(zip(hpos, fpos))]
    vel = [jnp.clip(jnp.concatenate([a, b]), -vel_max, vel_max) for a, b in zip(hvel, fvel)]
    ranks = config["ranks"]
    idx = [jnp.clip(jnp.floor(p / (box / r)).astype(jnp.int32), 0, r - 1)
           for p, r in zip(pos, ranks)]
    rank = (idx[0] * ranks[1] + idx[1]) * ranks[2] + idx[2]
    order = _stable_order(rank, (math.prod(ranks) - 1).bit_length())
    return {name: c[order].astype(jnp.float32) for name, c in zip(config["fields"], pos + vel)}
