"""Corpus, calibration sample and query pool, made on the device from a seed.

One general generator serves every configuration: clustered vectors whose
shape comes from the configuration's ``data`` block (cluster count, centre
kind, per-row noise spread, optional clipping at zero, per-row magnitude).
The same seed gives the same arrays on every run and every platform
(threefry bits, elementwise float32 arithmetic in one jitted call).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def key_from_seed(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, also one wider than 32 bits."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32((seed >> 32) & 0xFFFFFFFF))


@functools.partial(jax.jit, static_argnames=(
    "n", "dim", "n_clusters", "center", "nonneg"))
def _clustered(key, *, n, dim, n_clusters, center, nonneg, center_scale,
               noise, spread_lo, spread_hi, mag_lo, mag_hi):
    kc, ka, ks, ke, km = jax.random.split(key, 5)
    if center == "unit_normal":
        c = jax.random.normal(kc, (n_clusters, dim), jnp.float32)
        c = c / jnp.linalg.norm(c, axis=1, keepdims=True)
    elif center == "uniform":
        c = center_scale * jax.random.uniform(kc, (n_clusters, dim), jnp.float32)
    else:
        raise ValueError(f"unknown centre kind {center!r}")
    assign = jax.random.randint(ka, (n,), 0, n_clusters)
    spread = jax.random.uniform(ks, (n, 1), jnp.float32, spread_lo, spread_hi)
    x = c[assign] + noise * spread * jax.random.normal(ke, (n, dim), jnp.float32)
    if nonneg:
        x = jnp.maximum(x, 0.0)
    return x * jax.random.uniform(km, (n, 1), jnp.float32, mag_lo, mag_hi)


def corpus(data: dict, seed: int, n: int, dim: int) -> jax.Array:
    """``[n, dim]`` float32 rows on the default device."""
    return _clustered(
        key_from_seed(seed), n=n, dim=dim, n_clusters=int(data["n_clusters"]),
        center=data["center"], nonneg=bool(data["nonneg"]),
        center_scale=float(data.get("center_scale", 1.0)),
        noise=float(data["noise"]),
        spread_lo=float(data["noise_spread"][0]),
        spread_hi=float(data["noise_spread"][1]),
        mag_lo=float(data["magnitude"][0]), mag_hi=float(data["magnitude"][1]))


@functools.partial(jax.jit, static_argnames=("n_q",))
def _queries(key, x, *, n_q, noise):
    kr, kn = jax.random.split(key)
    rows = jax.random.randint(kr, (n_q,), 0, x.shape[0])
    q = x[rows] + noise * jax.random.normal(kn, (n_q, x.shape[1]), jnp.float32)
    return q, rows


def query_pool(x: jax.Array, seed: int, n_q: int, noise: float) -> np.ndarray:
    """``[n_q, dim]`` float32 host queries: corpus rows plus Gaussian noise
    (the form of ``repro.data.synthetic.queries_from_corpus``)."""
    key = jax.random.fold_in(key_from_seed(seed), np.uint32(0x9E3779B9))
    q, _ = _queries(key, x, n_q=n_q, noise=float(noise))
    return np.asarray(q)
