"""Randomized Hadamard Transform (RHDH) — the paper's data-oblivious rotation.

R = (1/sqrt(d')) * H * D   with D = diag(rademacher signs), H Walsh-Hadamard,
d' = next power of two >= d.  The sign stream is derived from a 64-bit seed
stored in the .mvec header; the paper uses ChaCha20, we use JAX's threefry
counter PRNG which is equally platform-deterministic (documented deviation,
DESIGN.md §2).

TPU adaptation (DESIGN.md §2): instead of the O(d log d) butterfly network —
which is a long chain of serial VPU shuffles on TPU — we exploit the Kronecker
factorization H_{ab} = H_a (x) H_b:   (H_a (x) H_b) vec(X) = vec(H_a X H_b)
for the row-major reshape X of the input.  Two dense matmuls against small
Hadamard factors (<= 256x256) run at MXU rate; for d'=1024 this is
2*d'*(a+b) = 2*1024*64 FLOPs — 64x fewer than a full d'^2 rotation and far
better utilization than log2(d')=10 serial butterfly stages.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np


def next_pow2(d: int) -> int:
    p = 1
    while p < d:
        p <<= 1
    return p


@functools.lru_cache(maxsize=None)
def hadamard_matrix(n: int) -> np.ndarray:
    """Sylvester Walsh-Hadamard matrix H_n (entries ±1), n a power of two."""
    assert n & (n - 1) == 0 and n > 0, f"n={n} must be a power of two"
    h = np.array([[1.0]], dtype=np.float32)
    while h.shape[0] < n:
        h = np.block([[h, h], [h, -h]])
    return h.astype(np.float32)


def _split_pow2(dp: int) -> Tuple[int, int]:
    """Split d' = a*b with a, b powers of two, a <= b, both near sqrt(d')."""
    lg = dp.bit_length() - 1
    a = 1 << (lg // 2)
    b = dp // a
    return a, b


def rademacher_signs(seed: int, d_pad: int) -> jnp.ndarray:
    """Deterministic ±1 diagonal from the 64-bit index seed.

    Resolved at TRACE time, always: the jax.random samplers are internally
    jitted, so when this runs under an outer trace (every compiled rotate
    stage) they would otherwise be staged into the program as live PRNG
    primitives instead of folding to the concrete sign vector the seed
    pins.  ensure_compile_time_eval forces the eager path, so the stage
    jaxpr sees only a ±1 constant — same bits, no random_* primitives
    (repro.analysis INV-NO-HOST-IN-TRACE).

    The sign stream is part of the .mvec format: a file stores only the
    seed.  JAX 0.5 switched threefry's default bit layout
    (``jax_threefry_partitionable``), which would give every stored seed a
    new diagonal; the layout every existing file was written with is pinned
    here.
    """
    with jax.ensure_compile_time_eval(), jax.threefry_partitionable(False):
        key = jax.random.key(np.uint32(seed & 0xFFFFFFFF))
        key = jax.random.fold_in(key, np.uint32((seed >> 32) & 0xFFFFFFFF))
        return jax.random.rademacher(key, (d_pad,), dtype=jnp.float32)


@functools.partial(jax.jit, static_argnames=())
def fwht(x: jnp.ndarray) -> jnp.ndarray:
    """Walsh-Hadamard transform of the last axis (length must be a power of 2).

    Kronecker-factored: reshape (..., a, b), apply H_a on axis -2 and H_b on
    axis -1.  Unnormalized (multiply by 1/sqrt(d') for the orthogonal version).
    """
    d = x.shape[-1]
    a, b = _split_pow2(d)
    ha = jnp.asarray(hadamard_matrix(a))
    hb = jnp.asarray(hadamard_matrix(b))
    xr = x.reshape(x.shape[:-1] + (a, b))
    # H symmetric: H_a X H_b via two einsums (MXU-friendly contractions).
    # HIGHEST: the TPU default runs an f32 matmul as one bf16 pass, which
    # would move coordinates across Lloyd-Max boundaries and change codes.
    hi = jax.lax.Precision.HIGHEST
    y = jnp.einsum("ij,...jk->...ik", ha, xr, precision=hi)
    y = jnp.einsum("...ik,kl->...il", y, hb, precision=hi)
    return y.reshape(x.shape)


def pad_to_pow2(x: jnp.ndarray, d_pad: int) -> jnp.ndarray:
    d = x.shape[-1]
    if d == d_pad:
        return x
    pad = [(0, 0)] * (x.ndim - 1) + [(0, d_pad - d)]
    return jnp.pad(x, pad)


def rhdh_apply(x: jnp.ndarray, seed: int, *, normalized: bool = True) -> jnp.ndarray:
    """Apply the seeded Hadamard rotation to the last axis; output has d' dims.

    normalized=True  -> R = (1/sqrt(d')) H D: orthogonal, preserves norms and
                        inner products exactly (up to f32 rounding).
    normalized=False -> Z = H D x: the QUANTIZER-SPACE transform.  For a unit
                        input each coordinate is a ±-signed sum of the entries,
                        Var = ||x||^2, i.e. ~N(0,1) on the unit sphere — this is
                        the paper's "after scaling by sqrt(d')" convention that
                        makes the precomputed N(0,1) Lloyd-Max tables valid.
                        All scores pick up a uniform d' factor, which leaves
                        every metric's ranking unchanged.
    """
    d_pad = next_pow2(x.shape[-1])
    signs = rademacher_signs(seed, d_pad)
    xp = pad_to_pow2(x, d_pad) * signs
    y = fwht(xp)
    if normalized:
        y = y * np.float32(1.0 / np.sqrt(d_pad))
    return y


def rhdh_inverse(y: jnp.ndarray, seed: int, d_orig: int) -> jnp.ndarray:
    """Inverse rotation: x = D H y / sqrt(d') truncated to the original dim."""
    d_pad = y.shape[-1]
    signs = rademacher_signs(seed, d_pad)
    x = fwht(y) * (1.0 / np.sqrt(d_pad)).astype(np.float32) * signs
    return x[..., :d_orig]
