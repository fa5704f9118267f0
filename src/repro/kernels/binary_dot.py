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
    PLANES (hi bit plane then lo bit plane, each packed 8 dims/byte), so
    with c = 2h + l a level is 4h + 2l - 3.  The kernel is a small-integer
    matrix product on the MXU: each corpus tile's planes are unpacked once
    into int8 levels (eight [bn, d'/8] slabs, one per bit position) and
    dotted against the whole query batch's levels with int32
    accumulation; |proxy| <= 9 d' fits int32 with room.

The sign mirror XORs and popcounts like its kernel.  The crumb mirror
computes the same integers by another route, the plane popcount identity

        L(a)L(b) = 16 h_a h_b + 8 h_a l_b + 8 l_a h_b + 4 l_a l_b
                   - 12 h_a - 6 l_a - 12 h_b - 6 l_b + 9

four weighted AND+popcount passes plus rank-1 corrections (a per-row and
a per-query popcount and the constant ``9 d'``), so kernel and mirror are
two independent formulas for one integer.  Integer arithmetic is exact
in any order, so they agree bit for bit at any block configuration --
the property the cascade tests pin.  The mirrors chunk the corpus rows
through ``lax.map`` so the scan never materializes an [b, n, d'/8]
intermediate at 1M rows, and popcount via a uint32 bitcast +
``lax.population_count`` (an order of magnitude faster than the
byte-wise SWAR tree under XLA, and exactly equal: both count the same
bits).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .nibble_dot import GRID_PARAMS

# The crumb kernel's grid has no accumulating axis.
TILE_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel"))


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
# Crumb proxy: an int8 level dot on the MXU.
# ---------------------------------------------------------------------------

# The byte axis of each bit plane is contracted in one tile, padded to whole
# lanes; the rows of a corpus tile are the largest of these that fits VMEM.
LANE = 128
_BLOCK_N = (2048, 1024, 512)
# Mosaic's default scoped VMEM on a v5e.
_VMEM_BUDGET = 16 * 1024 * 1024


def crumb_query_levels(qplanes: jnp.ndarray) -> jnp.ndarray:
    """[b, d'/4] query planes (hi || lo) -> [8, b, d'/8] int8 levels.

    Plane t holds L = 4h + 2l - 3 of dims 8j + t (bit t of byte j, the
    little-endian packing of ``binary.query_crumb_planes``): the order in
    which the kernel unpacks a corpus tile."""
    dkp = qplanes.shape[-1] // 2
    q = qplanes.astype(jnp.int32)
    hi, lo = q[:, :dkp], q[:, dkp:]
    t = jnp.arange(8, dtype=jnp.int32)[:, None, None]
    lev = 4 * ((hi[None] >> t) & 1) + 2 * ((lo[None] >> t) & 1) - 3
    return lev.astype(jnp.int8)


def crumb_blocks(b: int, n: int, dkp: int) -> tuple:
    """(block_q, block_n) for a [b, n] crumb scan over dkp plane bytes.

    One query block holds the whole batch up to 256 rows, so each corpus
    tile is unpacked once per batch of 256.  block_n is the largest row
    tile whose buffers fit the scoped VMEM (never wider than the corpus
    rounded to whole lanes)."""
    bq = min(256, _round_up(b, 8))

    def vmem(bn):
        planes = 2 * 2 * bn * dkp                   # hi, lo; double-buffered
        levels = 2 * 8 * bq * dkp                   # query levels, int8
        out = 2 * 4 * bq * bn                       # int32 tile, double-buffered
        unpack = (2 * 4 + 4 + 1) * bn * dkp         # widened planes, one level
        acc = 2 * 4 * bq * bn                       # running sum and one dot
        return planes + levels + out + unpack + acc

    fits = [bn for bn in _BLOCK_N if vmem(bn) <= _VMEM_BUDGET] or [_BLOCK_N[-1]]
    return bq, min(fits[0], _round_up(n, LANE))


def _crumb_level_kernel(hi_ref, lo_ref, qlev_ref, out_ref):
    """One (bq, bn) int32 tile: the corpus tile's bit planes unpacked into
    int8 levels, eight MXU dots against the query levels."""
    hi = hi_ref[...].astype(jnp.int32)              # [bn, dkp] bytes 0..255
    lo = lo_ref[...].astype(jnp.int32)              # widened, as above
    acc = None
    for t in range(8):
        lev = (4 * ((hi >> t) & 1) + 2 * ((lo >> t) & 1) - 3).astype(jnp.int8)
        part = jax.lax.dot_general(
            qlev_ref[t], lev, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32)       # [bq, bn]
        acc = part if acc is None else acc + part
    out_ref[...] = acc


@functools.partial(
    jax.jit, static_argnames=("block_q", "block_n", "interpret"))
def crumb_affinity_raw(
    ccodes: jnp.ndarray,     # [n, 2 dkp] uint8 — corpus planes (hi || lo)
    qlev: jnp.ndarray,       # [8, b, dkp] int8 — crumb_query_levels
    *,
    block_q: int,
    block_n: int,
    interpret: bool = True,
) -> jnp.ndarray:
    """Crumb affinities [b, n] (int32), sum_i L(cq_i) L(cv_i).

    dkp must be a multiple of LANE and b of block_q (the wrapper in ops.py
    pads both, with zero bytes and level-0 queries, which contribute 0).
    n need not divide by block_n: the last row tile is ragged, and the
    columns it computes beyond n are never written."""
    n, w = ccodes.shape
    _, b, dkp = qlev.shape
    assert qlev.shape[0] == 8 and w == 2 * dkp and dkp % LANE == 0
    assert b % block_q == 0, f"batch {b} must tile by {block_q}"
    grid = (pl.cdiv(n, block_n), b // block_q)

    return pl.pallas_call(
        _crumb_level_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, dkp), lambda j, i: (j, 0)),     # hi plane
            pl.BlockSpec((block_n, dkp), lambda j, i: (j, 1)),     # lo plane
            pl.BlockSpec((8, block_q, dkp), lambda j, i: (0, i, 0)),
        ],
        out_specs=pl.BlockSpec((block_q, block_n), lambda j, i: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, n), jnp.int32),
        compiler_params=TILE_PARAMS,
        interpret=interpret,
    )(ccodes, ccodes, qlev)


# ---------------------------------------------------------------------------
# Crumb mirror: plane AND + popcount with rank-1 corrections.
# ---------------------------------------------------------------------------

def _crumb_corrections(
    chi: jnp.ndarray,        # [n, d'/8] uint8 — corpus hi plane
    clo: jnp.ndarray,        # [n, d'/8] uint8 — corpus lo plane
    qhi: jnp.ndarray,        # [b, d'/8] uint8 — query hi plane
    qlo: jnp.ndarray,        # [b, d'/8] uint8 — query lo plane
    dim: int,
) -> jnp.ndarray:
    """The rank-1 part of the popcount identity, broadcast to [b, n] int32:
    ``9 d' - 12 pc(qhi) - 6 pc(qlo) - 12 pc(chi) - 6 pc(clo)``."""
    row = 12 * _popcount_sum(chi) + 6 * _popcount_sum(clo)        # [n]
    qc = 12 * _popcount_sum(qhi) + 6 * _popcount_sum(qlo)         # [b]
    return (9 * dim - qc)[:, None] - row[None, :]


def crumb_affinity_jnp(
    chi: jnp.ndarray,        # [n, d'/8] uint8
    clo: jnp.ndarray,        # [n, d'/8] uint8
    qhi: jnp.ndarray,        # [b, d'/8] uint8
    qlo: jnp.ndarray,        # [b, d'/8] uint8
    *,
    dim: int,
    row_chunk: int = 65536,
) -> jnp.ndarray:
    """jnp mirror of the crumb kernel by the popcount identity: the same
    int32 proxies as the kernel's level dot, by an independent formula.
    Same chunked-row streaming as the sign mirror; the two corpus planes
    travel concatenated per chunk."""
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
