"""Nyx-like snapshot (the model of ``repro.data.cosmo.nyx_fields``, on the
device): log-normal densities and a temperature from Gaussian random fields
with P(k) ~ k^slope, velocities from a smoother field, all scaled into the
configuration's value ranges.  The fields are made one after another (an
optimization barrier orders them), so one field's temporaries are live at a
time.  The seed's key rolls every field by one periodic offset (the
fields are periodic, so a roll is another view of the same realization)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.data.grf import grf


def fields(config: dict, key: jax.Array, seed_key: jax.Array) -> dict[str, jax.Array]:
    n = config["grid"]
    slope = config["spectral_slope"]
    out = {}
    for i, (name, sigma) in enumerate(config["log_normal_sigma"].items()):
        lo, hi = config["ranges"][name]
        f = jnp.exp(sigma * grf(jax.random.fold_in(key, i), n, slope))
        f = f / jnp.max(f) * hi
        out[name], key = jax.lax.optimization_barrier(
            (jnp.clip(f, lo, hi if name == "temperature" else None), key))
    for i, name in enumerate(config["velocity_fields"]):
        hi = config["ranges"][name][1]
        g = grf(jax.random.fold_in(key, 10 + i), n, slope + config["velocity_slope_offset"])
        out[name], key = jax.lax.optimization_barrier(
            (g / jnp.maximum(jnp.max(jnp.abs(g)), 1e-12) * 0.8 * hi, key))
    shift = tuple(jax.random.randint(seed_key, (3,), 0, n))
    return {k: jnp.roll(v, shift, axis=(0, 1, 2)).astype(jnp.float32) for k, v in out.items()}
