"""Device generators of the benchmark's snapshots.

A configuration names its generator (``"generator": "nyx"`` is
``bench/data/nyx.py``); each module has ``fields(config, key, seed_key)``,
which is traced once under ``jax.jit`` and returns the snapshot's named
fields.  ``key`` draws the realization, the same for every seed
(``config["realization"]``); ``seed_key`` draws a periodic offset that moves
it: the same values in another place, so the work a run does is the same
whatever its seed.
"""

from __future__ import annotations

import importlib

import jax


def seed_key(seed: int) -> jax.Array:
    """A key from any non-negative whole number (the driver's seeds pass
    2**31): the low 32 bits seed it, the next 32 are folded in."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def generate(config: dict, seed: int) -> dict[str, jax.Array]:
    """The configuration's fields, made on the default device in one call."""
    mod = importlib.import_module(f"bench.data.{config['generator']}")
    make = jax.jit(lambda r, k: mod.fields(config, r, k))
    out = make(jax.random.key(config["realization"]), seed_key(seed))
    jax.block_until_ready(out)
    return {name: out[name] for name in config["fields"]}
