"""Pallas TPU kernel: binarized coarse-scan proxies (cascade stage 1).

ROADMAP's raw-speed path to 10M+ vectors per device is a training-free
binarized pre-filter in front of the 4-bit rescore ("From HNSW to
Information-Theoretic Binarization", PAPERS.md): the RHDH rotation already
conditions coordinates toward N(0,1), so a per-dimension sign bit (or the
two-bit Lloyd-Max code, the "crumb") is derivable from the packed nibbles
with no data pass — exactly the MonaVec contract.

Two proxies, both INTEGER-valued (DESIGN.md §11):

  * **sign**: proxy = -hamming(q_bits, v_bits).  The kernel XORs packed
    sign bytes and popcounts with a SWAR tree (shifts/ands/adds only — no
    ``lax.population_count``, which has no guaranteed Mosaic lowering, and
    no per-lane gather).  Hamming distance — not agreement count — is the
    accumulated quantity because a zero PAD byte XORs to 0 and contributes
    exactly 0, so k-padding is free, mirroring the nibble kernel's
    zero-plane padding argument.
  * **crumb**: proxy = sum_i L(cq_i) * L(cv_i) with the symmetric level
    map L(c) = 2c - 3 in {-3,-1,1,3}.  The codes are stored as two SIGN
    PLANES (hi bit plane then lo bit plane, each packed 8 dims/byte), and
    with c = 2h + l the product expands to a popcount identity per dim:

        L(a)L(b) = 16 h_a h_b + 8 h_a l_b + 8 l_a h_b + 4 l_a l_b
                   - 12 h_a - 6 l_a - 12 h_b - 6 l_b + 9

    so the pairwise part is four weighted AND+popcount passes (the same
    SWAR tree as the sign kernel), and the remaining terms are rank-1
    corrections — a per-row and a per-query popcount plus the constant
    ``9 d'`` — applied identically on both dispatch paths.

Because both proxies are exact integer arithmetic (associative), the
Pallas kernel and the chunked jnp mirror below are bit-identical BY
CONSTRUCTION for any block configuration — the property the cascade tests
pin.  The mirrors chunk the corpus rows through ``lax.map`` so the scan
never materializes an [b, n, d'/8] intermediate at 1M rows, and popcount
via a uint32 bitcast + ``lax.population_count`` (an order of magnitude
faster than the byte-wise SWAR tree under XLA, and exactly equal: both
count the same bits).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .nibble_dot import GRID_PARAMS


def _popcount8(x: jnp.ndarray) -> jnp.ndarray:
    """SWAR popcount (values 0..8) of byte values held in an int32 array —
    the kernel-body form (Mosaic-safe: 32-bit shifts/ands/adds only)."""
    x = x - ((x >> 1) & 0x55)
    x = (x & 0x33) + ((x >> 2) & 0x33)
    return (x + (x >> 4)) & 0x0F


def _to_u32(x: jnp.ndarray) -> jnp.ndarray:
    """Bitcast the trailing byte axis to uint32 words ([..., w] -> [..., w/4]),
    zero-padding to a multiple of 4 bytes first (zero bytes carry 0 bits).

    The mirrors bitcast BEFORE broadcasting query against corpus: XOR/AND
    then run on 4x fewer elements and XLA fuses the popcount-sum into the
    same loop, instead of materializing an [b, n, d'/8] uint8 intermediate
    (measured ~50x on the 45k x 1024 scan)."""
    w = x.shape[-1]
    wp = -(-w // 4) * 4
    if wp != w:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, wp - w)])
    return jax.lax.bitcast_convert_type(
        x.reshape(x.shape[:-1] + (wp // 4, 4)), jnp.uint32)


def _pc_sum(x32: jnp.ndarray) -> jnp.ndarray:
    """Exact popcount-sum over the trailing uint32-word axis (int32)."""
    return jnp.sum(jax.lax.population_count(x32).astype(jnp.int32), axis=-1)


def _popcount_sum(x: jnp.ndarray) -> jnp.ndarray:
    """Exact popcount-sum over the trailing byte axis (int32)."""
    return _pc_sum(_to_u32(x))


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


# ---------------------------------------------------------------------------
# Sign proxy: XOR + popcount.
# ---------------------------------------------------------------------------

def _sign_hamming_kernel(cbits_ref, qbits_ref, out_ref):
    """One (bq, bn) int32 hamming tile, accumulating over packed-byte blocks."""
    k = pl.program_id(2)

    # Widen before any bit op: Mosaic has no 8-bit vector XOR/AND/shift.
    cbits = cbits_ref[...].astype(jnp.int32)        # [bn, bk] bytes 0..255
    qbits = qbits_ref[...].astype(jnp.int32)        # [bq, bk]
    x = jnp.bitwise_xor(qbits[:, None, :], cbits[None, :, :])
    part = jnp.sum(_popcount8(x), axis=-1)          # [bq, bn]

    @pl.when(k == 0)
    def _init():
        out_ref[...] = part

    @pl.when(k > 0)
    def _acc():
        out_ref[...] += part


@functools.partial(
    jax.jit, static_argnames=("block_q", "block_n", "block_k", "interpret")
)
def sign_hamming_raw(
    cbits: jnp.ndarray,      # [n, d'/8] uint8 — packed corpus sign bits
    qbits: jnp.ndarray,      # [b, d'/8] uint8 — packed query sign bits
    *,
    block_q: int = 8,
    block_n: int = 256,
    block_k: int = 128,
    interpret: bool = True,
) -> jnp.ndarray:
    """Hamming distances [b, n] (int32).  Shapes must tile evenly (the
    wrapper in ops.py pads); zero pad bytes contribute exactly 0."""
    n, dk = cbits.shape
    b, dk2 = qbits.shape
    assert dk == dk2
    assert n % block_n == 0 and b % block_q == 0 and dk % block_k == 0, (
        f"shapes ({b},{n},{dk}) must tile by ({block_q},{block_n},{block_k})"
    )
    grid = (b // block_q, n // block_n, dk // block_k)

    return pl.pallas_call(
        _sign_hamming_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, block_k), lambda i, j, k: (j, k)),
            pl.BlockSpec((block_q, block_k), lambda i, j, k: (i, k)),
        ],
        out_specs=pl.BlockSpec((block_q, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, n), jnp.int32),
        compiler_params=GRID_PARAMS,
        interpret=interpret,
    )(cbits, qbits)


def sign_hamming_jnp(
    cbits: jnp.ndarray,      # [n, d'/8] uint8
    qbits: jnp.ndarray,      # [b, d'/8] uint8
    *,
    row_chunk: int = 65536,
) -> jnp.ndarray:
    """jnp mirror of the sign kernel: bit-identical (integer arithmetic is
    exact under any evaluation order).  Corpus rows stream through lax.map
    in fixed-size chunks so the XOR intermediate stays [b, chunk, d'/8]."""
    n = cbits.shape[0]
    b = qbits.shape[0]
    c32 = _to_u32(cbits)                            # [n, w] uint32
    q32 = _to_u32(qbits)                            # [b, w] uint32
    w = c32.shape[-1]

    def one(c):
        return _pc_sum(jnp.bitwise_xor(q32[:, None, :], c[None, :, :]))

    if n <= row_chunk:
        return one(c32)
    n_pad = _round_up(n, row_chunk)
    chunks = jnp.pad(c32, ((0, n_pad - n), (0, 0)))
    chunks = chunks.reshape(n_pad // row_chunk, row_chunk, w)
    out = jax.lax.map(one, chunks)                  # [nc, b, chunk]
    return jnp.moveaxis(out, 0, 1).reshape(b, n_pad)[:, :n]


# ---------------------------------------------------------------------------
# Crumb proxy: plane AND + popcount with rank-1 corrections.
# ---------------------------------------------------------------------------

def _crumb_corrections(
    chi: jnp.ndarray,        # [n, d'/8] uint8 — corpus hi plane
    clo: jnp.ndarray,        # [n, d'/8] uint8 — corpus lo plane
    qhi: jnp.ndarray,        # [b, d'/8] uint8 — query hi plane
    qlo: jnp.ndarray,        # [b, d'/8] uint8 — query lo plane
    dim: int,
) -> jnp.ndarray:
    """The rank-1 part of the popcount identity, broadcast to [b, n] int32:
    ``9 d' - 12 pc(qhi) - 6 pc(qlo) - 12 pc(chi) - 6 pc(clo)``.  Computed
    by ONE shared function so both dispatch paths add identical integers;
    zero pad rows/bytes popcount to 0, so padding never perturbs it."""
    row = 12 * _popcount_sum(chi) + 6 * _popcount_sum(clo)        # [n]
    qc = 12 * _popcount_sum(qhi) + 6 * _popcount_sum(qlo)         # [b]
    return (9 * dim - qc)[:, None] - row[None, :]


def _crumb_cross_kernel(chi_ref, clo_ref, qhi_ref, qlo_ref, out_ref):
    """One (bq, bn) int32 tile of the pairwise term: four weighted
    AND+popcount passes over the plane bytes (zero pad bytes AND to 0)."""
    k = pl.program_id(2)
    i32 = jnp.int32                                 # widened, as above
    chi, clo = chi_ref[...].astype(i32), clo_ref[...].astype(i32)   # [bn, bk]
    qhi, qlo = qhi_ref[...].astype(i32), qlo_ref[...].astype(i32)   # [bq, bk]

    def pc(a):
        return jnp.sum(_popcount8(a), axis=-1)

    part = (16 * pc(qhi[:, None, :] & chi[None, :, :])
            + 8 * pc(qhi[:, None, :] & clo[None, :, :])
            + 8 * pc(qlo[:, None, :] & chi[None, :, :])
            + 4 * pc(qlo[:, None, :] & clo[None, :, :]))          # [bq, bn]

    @pl.when(k == 0)
    def _init():
        out_ref[...] = part

    @pl.when(k > 0)
    def _acc():
        out_ref[...] += part


@functools.partial(
    jax.jit,
    static_argnames=("dim", "block_q", "block_n", "block_k", "interpret"),
)
def crumb_affinity_raw(
    chi: jnp.ndarray,        # [n, d'/8] uint8 — corpus hi plane
    clo: jnp.ndarray,        # [n, d'/8] uint8 — corpus lo plane
    qhi: jnp.ndarray,        # [b, d'/8] uint8 — query hi plane
    qlo: jnp.ndarray,        # [b, d'/8] uint8 — query lo plane
    *,
    dim: int,
    block_q: int = 8,
    block_n: int = 256,
    block_k: int = 128,
    interpret: bool = True,
) -> jnp.ndarray:
    """Crumb affinities [b, n] (int32): the Pallas kernel accumulates the
    pairwise AND-popcount term; the rank-1 corrections are added outside
    the grid (they are per-row/per-query, not per-tile)."""
    n, dk = chi.shape
    b = qhi.shape[0]
    assert clo.shape == chi.shape and qlo.shape == qhi.shape == (b, dk)
    assert n % block_n == 0 and b % block_q == 0 and dk % block_k == 0, (
        f"shapes ({b},{n},{dk}) must tile by ({block_q},{block_n},{block_k})"
    )
    grid = (b // block_q, n // block_n, dk // block_k)

    corpus_spec = pl.BlockSpec((block_n, block_k), lambda i, j, k: (j, k))
    query_spec = pl.BlockSpec((block_q, block_k), lambda i, j, k: (i, k))
    cross = pl.pallas_call(
        _crumb_cross_kernel,
        grid=grid,
        in_specs=[corpus_spec, corpus_spec, query_spec, query_spec],
        out_specs=pl.BlockSpec((block_q, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, n), jnp.int32),
        compiler_params=GRID_PARAMS,
        interpret=interpret,
    )(chi, clo, qhi, qlo)
    return cross + _crumb_corrections(chi, clo, qhi, qlo, dim)


def crumb_affinity_jnp(
    chi: jnp.ndarray,        # [n, d'/8] uint8
    clo: jnp.ndarray,        # [n, d'/8] uint8
    qhi: jnp.ndarray,        # [b, d'/8] uint8
    qlo: jnp.ndarray,        # [b, d'/8] uint8
    *,
    dim: int,
    row_chunk: int = 65536,
) -> jnp.ndarray:
    """jnp mirror of the crumb kernel (bit-identical: exact popcounts and
    exact int32 sums on both paths).  Same chunked-row streaming as the
    sign mirror; the two corpus planes travel concatenated per chunk."""
    n = chi.shape[0]
    b = qhi.shape[0]
    chi32, clo32 = _to_u32(chi), _to_u32(clo)       # [n, w] uint32
    qhi32, qlo32 = _to_u32(qhi), _to_u32(qlo)       # [b, w] uint32
    w = chi32.shape[-1]

    def one(c):
        ch, cl = c[:, :w], c[:, w:]
        return (16 * _pc_sum(qhi32[:, None, :] & ch[None, :, :])
                + 8 * _pc_sum(qhi32[:, None, :] & cl[None, :, :])
                + 8 * _pc_sum(qlo32[:, None, :] & ch[None, :, :])
                + 4 * _pc_sum(qlo32[:, None, :] & cl[None, :, :]))

    both = jnp.concatenate([chi32, clo32], axis=-1)  # [n, 2 w]
    if n <= row_chunk:
        cross = one(both)
    else:
        n_pad = _round_up(n, row_chunk)
        chunks = jnp.pad(both, ((0, n_pad - n), (0, 0)))
        chunks = chunks.reshape(n_pad // row_chunk, row_chunk, 2 * w)
        out = jax.lax.map(one, chunks)              # [nc, b, chunk]
        cross = jnp.moveaxis(out, 0, 1).reshape(b, n_pad)[:, :n]
    return cross + _crumb_corrections(chi, clo, qhi, qlo, dim)
