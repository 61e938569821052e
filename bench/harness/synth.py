"""ERA5-like fields made on the device from a seed.

A port to ``jax.numpy`` of the closed form of ``data/weather.py``: every
channel of a sample is a sum of ``n_modes`` smooth waves over the grid,

    f = sum_m a_m sin(fla_m * 2 pi la / lat + flo_m * 2 pi lo / lon
                      + phase_m + t) / sqrt(n_modes),  then f + 0.1 f^2,

with a_m ~ N(0, 1), fla_m in 1..4, flo_m in 1..6 and phase_m ~ U(0, 2 pi)
drawn per (sample, channel).  The target is the same sample ``horizon``
phase steps later, plus Gaussian noise.  The coefficients come from JAX's
own generator, so the values differ from the NumPy original's; the
statistics are the same.  A whole batch is one jitted call.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def key_for(seed: int, *stream: int) -> jax.Array:
    """A PRNG key from any whole-number seed (also past 32 bits) and a
    stream of integers that names what the key is for."""
    words = np.random.SeedSequence([int(seed), *map(int, stream)]
                                   ).generate_state(2)
    return jax.random.wrap_key_data(np.asarray(words, np.uint32),
                                    impl="threefry2x32")


def _fields(key, lat: int, lon: int, channels: int, n_modes: int,
            t: jax.Array) -> jax.Array:
    ka, kla, klo, kph = jax.random.split(key, 4)
    shape = (channels, n_modes)
    amp = jax.random.normal(ka, shape, jnp.float32)
    fla = jax.random.randint(kla, shape, 1, 5).astype(jnp.float32)
    flo = jax.random.randint(klo, shape, 1, 7).astype(jnp.float32)
    phs = jax.random.uniform(kph, shape, jnp.float32, 0.0, 2 * math.pi)
    la = 2 * math.pi * jnp.arange(lat, dtype=jnp.float32) / lat
    lo = 2 * math.pi * jnp.arange(lon, dtype=jnp.float32) / lon
    a = fla[None] * la[:, None, None]                       # [lat, C, M]
    b = flo[None] * lo[:, None, None] + (phs + t)[None]     # [lon, C, M]
    # sin(a + b) = sin a cos b + cos a sin b, summed over the modes
    hi = jax.lax.Precision.HIGHEST
    f = (jnp.einsum("icm,jcm->ijc", jnp.sin(a) * amp, jnp.cos(b),
                    precision=hi)
         + jnp.einsum("icm,jcm->ijc", jnp.cos(a) * amp, jnp.sin(b),
                      precision=hi)) / math.sqrt(n_modes)
    return f + 0.1 * f * f


@partial(jax.jit, static_argnames=("lat", "lon", "channels", "n_modes",
                                   "batch"))
def make_batch(key, *, lat: int, lon: int, channels: int, n_modes: int,
               batch: int, t_target: float, noise: float
               ) -> Tuple[jax.Array, jax.Array]:
    """fields, target: [batch, lat, lon, channels] float32."""
    keys = jax.random.split(key, batch + 1)
    one = partial(_fields, lat=lat, lon=lon, channels=channels,
                  n_modes=n_modes)
    x = jax.vmap(lambda k: one(k, t=jnp.float32(0.0)))(keys[:batch])
    y = jax.vmap(lambda k: one(k, t=jnp.float32(t_target)))(keys[:batch])
    y = y + noise * jax.random.normal(keys[batch], y.shape, jnp.float32)
    return x, y


def host_batch(seed: int, stream: int, cfg: Dict, traffic: Dict,
               batch: int) -> Dict[str, np.ndarray]:
    """One batch made on the device and copied to host memory."""
    x, y = make_batch(
        key_for(seed, stream), lat=cfg["lat"], lon=cfg["lon"],
        channels=cfg["channels"], n_modes=traffic["n_modes"], batch=batch,
        t_target=traffic["horizon"] * traffic["dt_phase"],
        noise=traffic["noise"])
    return {"fields": np.asarray(x), "target": np.asarray(y)}
