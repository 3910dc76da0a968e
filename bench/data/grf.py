"""Gaussian random fields on the device (shared by the generators)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def wavenumber(n: int) -> jax.Array:
    """|k| on the (n, n, n//2+1) half-spectrum grid, in cycles per cell."""
    kx = jnp.fft.fftfreq(n)[:, None, None]
    ky = jnp.fft.fftfreq(n)[None, :, None]
    kz = jnp.fft.rfftfreq(n)[None, None, :]
    return jnp.sqrt(kx**2 + ky**2 + kz**2)


def spectrum(key: jax.Array, n: int, slope: float) -> jax.Array:
    """Half-spectrum of white noise shaped to P(k) ~ k^slope, DC removed."""
    k = wavenumber(n)
    amp = jnp.where(k > 0, jnp.where(k > 0, k, 1.0) ** (slope / 2.0), 0.0)
    white = jnp.fft.rfftn(jax.random.normal(key, (n, n, n), jnp.float32))
    return white * amp


def grf(key: jax.Array, n: int, slope: float) -> jax.Array:
    """Real-space field with P(k) ~ k^slope and unit variance (float32)."""
    f = jnp.fft.irfftn(spectrum(key, n, slope), s=(n, n, n))
    return f / jnp.maximum(jnp.std(f), 1e-12)
