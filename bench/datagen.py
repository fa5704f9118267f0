"""Corpus, calibration sample and query pool, made on the device from a seed.

One general generator serves every configuration: clustered vectors whose
shape comes from the configuration's ``data`` block (cluster count, centre
kind, per-row noise spread, optional clipping at zero, per-row magnitude).
The same seed gives the same arrays on every run and every platform
(threefry bits, elementwise float32 arithmetic in one jitted call).

A configuration whose ``data`` sets ``block_rows`` B is made block by block
instead: block j, rows [j B, (j + 1) B), comes from ``fold_in(key, j)`` and
the cluster centres from a key no block depends on, so every row is a
function of (seed, B) alone, whatever device makes it.  Such a corpus never
exists whole: ``Rows`` hands out its blocks and any set of its rows.
"""

from __future__ import annotations

import collections
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


def _centres(kc, *, dim, n_clusters, center, center_scale):
    if center == "unit_normal":
        c = jax.random.normal(kc, (n_clusters, dim), jnp.float32)
        return c / jnp.linalg.norm(c, axis=1, keepdims=True)
    if center == "uniform":
        return center_scale * jax.random.uniform(kc, (n_clusters, dim), jnp.float32)
    raise ValueError(f"unknown centre kind {center!r}")


def _rows(c, ka, ks, ke, km, *, n, nonneg, noise, spread_lo, spread_hi, mag_lo, mag_hi):
    assign = jax.random.randint(ka, (n,), 0, c.shape[0])
    spread = jax.random.uniform(ks, (n, 1), jnp.float32, spread_lo, spread_hi)
    x = c[assign] + noise * spread * jax.random.normal(ke, (n, c.shape[1]), jnp.float32)
    if nonneg:
        x = jnp.maximum(x, 0.0)
    return x * jax.random.uniform(km, (n, 1), jnp.float32, mag_lo, mag_hi)


@functools.partial(jax.jit, static_argnames=(
    "n", "dim", "n_clusters", "center", "nonneg"))
def _clustered(key, *, n, dim, n_clusters, center, nonneg, center_scale, **shape):
    kc, ka, ks, ke, km = jax.random.split(key, 5)
    c = _centres(kc, dim=dim, n_clusters=n_clusters, center=center,
                 center_scale=center_scale)
    return _rows(c, ka, ks, ke, km, n=n, nonneg=nonneg, **shape)


@functools.partial(jax.jit, static_argnames=(
    "n", "dim", "n_clusters", "center", "nonneg"))
def _block(key, j, *, n, dim, n_clusters, center, nonneg, center_scale, **shape):
    kc, kb = jax.random.split(key)
    c = _centres(kc, dim=dim, n_clusters=n_clusters, center=center,
                 center_scale=center_scale)
    ka, ks, ke, km = jax.random.split(jax.random.fold_in(kb, j), 4)
    return _rows(c, ka, ks, ke, km, n=n, nonneg=nonneg, **shape)


def _params(data: dict) -> dict:
    return dict(
        n_clusters=int(data["n_clusters"]), center=data["center"],
        nonneg=bool(data["nonneg"]), center_scale=float(data.get("center_scale", 1.0)),
        noise=float(data["noise"]),
        spread_lo=float(data["noise_spread"][0]),
        spread_hi=float(data["noise_spread"][1]),
        mag_lo=float(data["magnitude"][0]), mag_hi=float(data["magnitude"][1]))


def corpus(data: dict, seed: int, n: int, dim: int) -> jax.Array:
    """``[n, dim]`` float32 rows on the default device, in one call."""
    return _clustered(key_from_seed(seed), n=n, dim=dim, **_params(data))


def corpus_block(data: dict, seed: int, n: int, dim: int, j: int, device=None) -> jax.Array:
    """Block ``j`` of a block-keyed corpus: rows [j B, min((j + 1) B, n)) as
    ``[B, dim]`` float32 on ``device``, rows past ``n`` zero."""
    b = int(data["block_rows"])
    if not 0 <= j * b < n:
        raise IndexError(f"block {j} of {b} rows lies outside {n} rows")
    key = key_from_seed(seed)
    if device is not None:
        key = jax.device_put(key, device)
    x = _block(key, np.uint32(j), n=b, dim=dim, **_params(data))
    if (j + 1) * b > n:
        x = x.at[n - j * b:].set(0.0)
    return x


class Rows:
    """The corpus of one configuration and seed, handed out by block.

    Block j is rows [j B, (j + 1) B) of B = ``data.block_rows``, made anew on
    ``devices[j % len(devices)]`` each time it is asked for, so the corpus
    is never whole anywhere.  Without ``block_rows`` the corpus is one block
    of all ``n`` rows, made by ``corpus`` and kept while this object lives.
    ``devices`` are the cell's chips (default: the first device)."""

    def __init__(self, data: dict, seed: int, n: int, dim: int, devices=None):
        self.data, self.seed, self.n, self.dim = data, int(seed), int(n), int(dim)
        self.block_rows = int(data.get("block_rows", self.n))
        self.devices = list(devices) if devices else jax.devices()[:1]
        self._whole = None

    @property
    def n_blocks(self) -> int:
        return -(-self.n // self.block_rows)

    def device_of(self, j: int):
        return self.devices[j % len(self.devices)]

    def block(self, j: int, device=None) -> jax.Array:
        """Block ``j``, ``[block_rows, dim]``, rows past ``n`` zero; on
        ``device`` where given, else on ``device_of(j)``."""
        if "block_rows" not in self.data:
            if self._whole is None:
                self._whole = corpus(self.data, self.seed, self.n, self.dim)
            return self._whole
        return corpus_block(self.data, self.seed, self.n, self.dim, j,
                            device if device is not None else self.device_of(j))

    def take(self, idx) -> np.ndarray:
        """Rows ``idx`` (any order, repeats allowed) as ``[len(idx), dim]``
        float32 on the host."""
        idx = np.asarray(idx, np.int64)
        if len(idx) and (idx.min() < 0 or idx.max() >= self.n):
            raise IndexError("row index outside the corpus")
        out = np.empty((len(idx), self.dim), np.float32)
        pending = collections.deque()
        for j in np.unique(idx // self.block_rows):
            sel = np.flatnonzero(idx // self.block_rows == j)
            local = np.zeros(1 << (len(sel) - 1).bit_length(), np.int32)  # few shapes
            local[:len(sel)] = idx[sel] - j * self.block_rows
            pending.append((sel, _gather(self.block(int(j)), local)))
            # A few blocks in flight per device: queued blocks hold memory.
            if len(pending) > 2 * len(self.devices):
                sel, rows = pending.popleft()
                out[sel] = np.asarray(rows)[:len(sel)]
        for sel, rows in pending:
            out[sel] = np.asarray(rows)[:len(sel)]
        return out


@jax.jit
def _gather(x, local):
    return x[local]


@functools.partial(jax.jit, static_argnames=("n_q", "n"))
def _query_rows(key, *, n_q, n):
    kr, _ = jax.random.split(key)
    return jax.random.randint(kr, (n_q,), 0, n)


@functools.partial(jax.jit, static_argnames=("n_q",))
def _query_noise(key, xr, *, n_q, noise):
    _, kn = jax.random.split(key)
    return xr + noise * jax.random.normal(kn, (n_q, xr.shape[1]), jnp.float32)


def query_pool(rows: Rows, seed: int, n_q: int, noise: float) -> np.ndarray:
    """``[n_q, dim]`` float32 host queries: corpus rows plus Gaussian noise
    (the form of ``repro.data.synthetic.queries_from_corpus``), the rows
    fetched by index."""
    key = jax.random.fold_in(key_from_seed(seed), np.uint32(0x9E3779B9))
    idx = np.asarray(_query_rows(key, n_q=n_q, n=rows.n))
    return np.asarray(_query_noise(key, rows.take(idx), n_q=n_q, noise=float(noise)))


def sample(data: dict, seed: int, n: int, dim: int) -> np.ndarray:
    """The first ``n`` rows of the corpus of ``seed`` (fit()'s calibration
    sample), on the host."""
    rows = Rows(data, seed, n, dim)
    return np.concatenate([np.asarray(rows.block(j)) for j in range(rows.n_blocks)])[:n]
