"""Pallas TPU kernel: gathered candidate-set scoring (IVF/HNSW hot path).

The non-brute backends (paper §3.4.2/§3.4.3) score per-query CANDIDATE subsets
of the corpus: query ``b`` is scored only against rows ``cand[b, :]``.  The
full-corpus ``nibble_dot`` kernel cannot express this (its packed operand is
shared by every query), so this kernel scores pre-gathered per-query candidate
matrices ``[b, mc, bytes]`` directly from packed nibbles/crumbs — the candidate
gather stays in the uint8 packed domain (preserving the paper's 8× memory
edge), and the compare-select dequant is fused into the dot so no
``[b, mc, d']`` f32 tensor ever materializes.

Structure shared with ``nibble_dot`` (DESIGN.md §2): compare-select dequant
(no VPU gather, centroids as immediates), deinterleaved query planes (no
minor-dim shuffle), fixed accumulation order over packed-dim blocks.

The per-(query, candidate-tile, k-tile) computation lives in ``_nibble_tile``
/ ``_crumb_tile`` and is shared VERBATIM by the kernel body and by the
pure-jnp mirrors (``gather_nibble_dot_jnp`` / ``gather_crumb_dot_jnp``), which
iterate the exact same (b-chunk, m-tile, k-tile) grid in the same order.  That
makes the non-kernel path bit-identical to the interpret-mode kernel — the
property the ``use_kernel`` contract tests assert on IVF/HNSW search results.

VMEM (defaults bb=8, bm=256, bk=256 packed bytes):
  gathered  8*256*256          = 512 KiB
  deq lo/hi 2 * 8*256*256*4    =   4 MiB (transient, per select tree)
  planes    2 * 8*256*4        =  16 KiB
  out       8*256*4            =   8 KiB      -> well under 16 MiB VMEM.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .nibble_dot import F32_DOT, GRID_PARAMS, _TABLE2, _TABLE4, _dequant_select


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def gather_blocks(b: int, m: int, dk: int) -> Tuple[int, int, int]:
    """Block sizes (bb, bm, bk) for a [b, m, dk] gathered-candidate scoring.

    A pure function of the operand shape: the kernel wrapper AND the jnp
    mirror both derive their tiling from here, which is what keeps the two
    paths bit-identical (same tile shapes -> same dot reductions).
    """
    bb = b if b < 8 else 8
    bm = _round_up(m, 8) if m < 256 else 256
    bk = min(256, _round_up(dk, 128))
    return bb, bm, bk


def _nibble_tile(g: jnp.ndarray, q_even: jnp.ndarray, q_odd: jnp.ndarray) -> jnp.ndarray:
    """One candidate tile for one query: [bm, bk] uint8 × 2×[bk] f32 -> [bm].

    Nibble 2i is the low half of byte i, nibble 2i+1 the high half, so
    ``deq(lo) @ q_even + deq(hi) @ q_odd`` is the exact dot product.
    """
    g = g.astype(jnp.int32)     # Mosaic has no 8-bit vector shifts or masks
    lo = g & 0xF
    hi = g >> 4
    part = jnp.dot(_dequant_select(lo, _TABLE4), q_even, **F32_DOT)
    part += jnp.dot(_dequant_select(hi, _TABLE4), q_odd, **F32_DOT)
    return part


def _crumb_tile(g: jnp.ndarray, q0, q1, q2, q3) -> jnp.ndarray:
    """2-bit variant: four crumbs per byte, four deinterleaved planes."""
    g = g.astype(jnp.int32)
    part = jnp.zeros((g.shape[0],), jnp.float32)
    for shift, q in ((0, q0), (2, q1), (4, q2), (6, q3)):
        codes = (g >> shift) & 0x3
        part += jnp.dot(_dequant_select(codes, _TABLE2), q, **F32_DOT)
    return part


# Batched over the in-block query chunk: [bb, bm, bk] × [bb, bk] -> [bb, bm].
_nibble_tile_b = jax.vmap(_nibble_tile)
_crumb_tile_b = jax.vmap(_crumb_tile)


def _gather_nibble_kernel(g_ref, q_even_ref, q_odd_ref, out_ref):
    """One (bb, bm) output tile, accumulating over the packed-dim grid axis."""
    kt = pl.program_id(2)
    part = _nibble_tile_b(g_ref[...], q_even_ref[...], q_odd_ref[...])

    @pl.when(kt == 0)
    def _init():
        out_ref[...] = part

    @pl.when(kt > 0)
    def _acc():
        out_ref[...] += part


def _gather_crumb_kernel(g_ref, q0_ref, q1_ref, q2_ref, q3_ref, out_ref):
    kt = pl.program_id(2)
    part = _crumb_tile_b(g_ref[...], q0_ref[...], q1_ref[...], q2_ref[...],
                         q3_ref[...])

    @pl.when(kt == 0)
    def _init():
        out_ref[...] = part

    @pl.when(kt > 0)
    def _acc():
        out_ref[...] += part


@functools.partial(
    jax.jit, static_argnames=("block_b", "block_m", "block_k", "interpret")
)
def gather_nibble_dot_raw(
    gathered: jnp.ndarray,   # [b, mc, d'/2] uint8 — per-query candidate rows
    q_even: jnp.ndarray,     # [b, d'/2] f32 — rotated query dims 0,2,4,...
    q_odd: jnp.ndarray,      # [b, d'/2] f32 — rotated query dims 1,3,5,...
    *,
    block_b: int = 8,
    block_m: int = 256,
    block_k: int = 256,
    interpret: bool = True,
) -> jnp.ndarray:
    """Raw scores [b, mc]: row ``gathered[b, i]`` against query ``b``.

    Shapes must tile evenly (wrapper in ops.py pads).  interpret=True runs the
    kernel body on CPU for validation; on TPU pass interpret=False.
    """
    b, m, dk = gathered.shape
    assert q_even.shape == (b, dk) and q_odd.shape == (b, dk)
    assert b % block_b == 0 and m % block_m == 0 and dk % block_k == 0, (
        f"shapes ({b},{m},{dk}) must tile by ({block_b},{block_m},{block_k})"
    )
    grid = (b // block_b, m // block_m, dk // block_k)

    return pl.pallas_call(
        _gather_nibble_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, block_m, block_k), lambda i, j, k: (i, j, k)),
            pl.BlockSpec((block_b, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_b, block_k), lambda i, j, k: (i, k)),
        ],
        out_specs=pl.BlockSpec((block_b, block_m), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, m), jnp.float32),
        compiler_params=GRID_PARAMS,
        interpret=interpret,
    )(gathered, q_even, q_odd)


@functools.partial(
    jax.jit, static_argnames=("block_b", "block_m", "block_k", "interpret")
)
def gather_crumb_dot_raw(
    gathered: jnp.ndarray,   # [b, mc, d/4] uint8
    q_planes: jnp.ndarray,   # [4, b, d/4] f32
    *,
    block_b: int = 8,
    block_m: int = 256,
    block_k: int = 128,
    interpret: bool = True,
) -> jnp.ndarray:
    b, m, dk = gathered.shape
    assert q_planes.shape == (4, b, dk)
    assert b % block_b == 0 and m % block_m == 0 and dk % block_k == 0
    grid = (b // block_b, m // block_m, dk // block_k)

    return pl.pallas_call(
        _gather_crumb_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, block_m, block_k), lambda i, j, k: (i, j, k)),
        ] + [
            pl.BlockSpec((block_b, block_k), lambda i, j, k: (i, k))
            for _ in range(4)
        ],
        out_specs=pl.BlockSpec((block_b, block_m), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, m), jnp.float32),
        compiler_params=GRID_PARAMS,
        interpret=interpret,
    )(gathered, q_planes[0], q_planes[1], q_planes[2], q_planes[3])


# ---------------------------------------------------------------------------
# Pure-jnp mirrors: the non-kernel production path (XLA-fused on CPU/GPU).
# Same tile function, same grid order as the kernel -> bit-identical output.
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("block_b", "block_m", "block_k"))
def gather_nibble_dot_jnp(
    gathered: jnp.ndarray,
    q_even: jnp.ndarray,
    q_odd: jnp.ndarray,
    *,
    block_b: int = 8,
    block_m: int = 256,
    block_k: int = 256,
) -> jnp.ndarray:
    b, m, dk = gathered.shape
    assert b % block_b == 0 and m % block_m == 0 and dk % block_k == 0
    brows = []
    for i in range(b // block_b):
        bs = slice(i * block_b, (i + 1) * block_b)
        cols = []
        for j in range(m // block_m):
            ms = slice(j * block_m, (j + 1) * block_m)
            acc = jnp.zeros((block_b, block_m), jnp.float32)
            for kt in range(dk // block_k):
                ks = slice(kt * block_k, (kt + 1) * block_k)
                acc = acc + _nibble_tile_b(
                    gathered[bs, ms, ks], q_even[bs, ks], q_odd[bs, ks]
                )
            cols.append(acc)
        brows.append(jnp.concatenate(cols, axis=1))
    return jnp.concatenate(brows, axis=0)


@functools.partial(jax.jit, static_argnames=("block_b", "block_m", "block_k"))
def gather_crumb_dot_jnp(
    gathered: jnp.ndarray,
    q_planes: jnp.ndarray,
    *,
    block_b: int = 8,
    block_m: int = 256,
    block_k: int = 128,
) -> jnp.ndarray:
    b, m, dk = gathered.shape
    assert b % block_b == 0 and m % block_m == 0 and dk % block_k == 0
    brows = []
    for i in range(b // block_b):
        bs = slice(i * block_b, (i + 1) * block_b)
        cols = []
        for j in range(m // block_m):
            ms = slice(j * block_m, (j + 1) * block_m)
            acc = jnp.zeros((block_b, block_m), jnp.float32)
            for kt in range(dk // block_k):
                ks = slice(kt * block_k, (kt + 1) * block_k)
                acc = acc + _crumb_tile_b(
                    gathered[bs, ms, ks],
                    q_planes[0, bs, ks], q_planes[1, bs, ks],
                    q_planes[2, bs, ks], q_planes[3, bs, ks],
                )
            cols.append(acc)
        brows.append(jnp.concatenate(cols, axis=1))
    return jnp.concatenate(brows, axis=0)
