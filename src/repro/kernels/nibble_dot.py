"""Pallas TPU kernel: asymmetric 4-bit nibble dot product (the paper's hot path).

Paper §3.7: the scoring kernel unpacks nibbles, looks up the 16 Lloyd-Max
centroids, multiplies by the f32 query and accumulates — on CPU this is an AVX2
``_mm256_permutevar8x32_ps`` LUT plus FMA chains.

TPU adaptation (DESIGN.md §2):
  * **no per-lane gather** on the VPU -> the 16-entry table lookup becomes a
    compare-select tree: ``vals = sum_k table[k] * (codes == k)``.  The 16
    centroids are compiled into the kernel as immediates, exactly like the
    paper compiles its tables into the binary.
  * **deinterleaved query trick**: instead of interleaving lo/hi nibbles back
    into position (an awkward minor-dim shuffle on TPU), the wrapper splits the
    rotated query into even/odd coordinate planes once per batch;  the kernel
    computes ``q_even @ deq(lo)^T + q_odd @ deq(hi)^T`` — two MXU matmuls, no
    shuffle.  This preserves the exact dot product because nibble 2i is the
    low half of byte i and nibble 2i+1 the high half.
  * the reduction over packed-dim blocks accumulates f32 in a fixed grid order
    (k innermost) -> bitwise-deterministic for a fixed block configuration,
    mirroring the paper's fixed SIMD reduction order.

VMEM tiling: default blocks (bq=128, bn=256, bk=256 packed bytes = 512 dims):
  packed   256*256           =  64 KiB
  deq lo/hi 2 * 256*512*4    =   1 MiB
  queries  2 * 128*256*4     = 256 KiB
  out      128*256*4         = 128 KiB      -> ~1.5 MiB, well under 16 MiB VMEM.
MXU alignment: all matmul dims are multiples of (8,128) f32 tiles.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import lloydmax

# Every scan kernel in this package runs a (query-block, row-block,
# packed-dim-block) grid: the first two axes are independent, the last one
# accumulates into the resident output tile.
GRID_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))

# Full f32 products on the MXU.  Mosaic's default runs an f32 dot as one
# bf16 pass, which reorders near-tied neighbours against every other
# platform's answer for the same codes.
F32_DOT = dict(preferred_element_type=jnp.float32,
               precision=jax.lax.Precision.HIGHEST)

# The frozen Lloyd-Max tables, baked in as Python floats (immediates).
# Shared with the gathered candidate-scan kernel (gather_dot.py) so every
# scan path dequantizes through the exact same values.
_TABLE4: Tuple[float, ...] = tuple(float(v) for v in lloydmax.CENTROIDS_4BIT)
_TABLE2: Tuple[float, ...] = tuple(float(v) for v in lloydmax.CENTROIDS_2BIT)


def _dequant_select(codes: jnp.ndarray, table: Tuple[float, ...]) -> jnp.ndarray:
    """Compare-select dequantization: no gather, pure VPU select tree.

    Fixed summation order over the table -> deterministic.  Value-identical
    to ``lloydmax.dequantize`` (a single table term is selected; adding the
    zero terms is exact), which is what lets the full-scan and gathered-scan
    kernels share it with the pure-jnp references.
    """
    vals = jnp.zeros(codes.shape, jnp.float32)
    for k, ck in enumerate(table):
        vals += jnp.where(codes == k, jnp.float32(ck), jnp.float32(0.0))
    return vals


def _nibble_dot_kernel(packed_ref, q_even_ref, q_odd_ref, out_ref, *, n_k: int):
    """One (bq, bn) output tile, accumulating over the packed-dim grid axis."""
    k = pl.program_id(2)

    # Widen before any bit op: Mosaic has no 8-bit vector shifts or masks.
    packed = packed_ref[...].astype(jnp.int32)      # [bn, bk] bytes 0..255
    lo = packed & 0xF                               # nibble 2i   (dims 0,2,4,..)
    hi = packed >> 4                                # nibble 2i+1 (dims 1,3,5,..)
    deq_lo = _dequant_select(lo, _TABLE4)           # [bn, bk] f32
    deq_hi = _dequant_select(hi, _TABLE4)

    q_even = q_even_ref[...]                        # [bq, bk] f32
    q_odd = q_odd_ref[...]

    part = jnp.dot(q_even, deq_lo.T, **F32_DOT)
    part += jnp.dot(q_odd, deq_hi.T, **F32_DOT)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = part

    @pl.when(k > 0)
    def _acc():
        out_ref[...] += part


@functools.partial(
    jax.jit, static_argnames=("block_q", "block_n", "block_k", "interpret")
)
def nibble_dot_raw(
    packed: jnp.ndarray,     # [n, d'/2] uint8
    q_even: jnp.ndarray,     # [b, d'/2] f32 — rotated query dims 0,2,4,...
    q_odd: jnp.ndarray,      # [b, d'/2] f32 — rotated query dims 1,3,5,...
    *,
    block_q: int = 128,
    block_n: int = 256,
    block_k: int = 256,
    interpret: bool = True,
) -> jnp.ndarray:
    """Raw (un-adjusted) scores [b, n] = <q_rot, dequant(packed)>.

    Shapes must tile evenly (wrapper in ops.py pads).  interpret=True runs the
    kernel body on CPU for validation; on TPU pass interpret=False.
    """
    n, dk = packed.shape
    b, dk2 = q_even.shape
    assert dk == dk2 and q_odd.shape == q_even.shape
    assert n % block_n == 0 and b % block_q == 0 and dk % block_k == 0, (
        f"shapes ({b},{n},{dk}) must tile by ({block_q},{block_n},{block_k})"
    )
    grid = (b // block_q, n // block_n, dk // block_k)

    return pl.pallas_call(
        functools.partial(_nibble_dot_kernel, n_k=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, block_k), lambda i, j, k: (j, k)),
            pl.BlockSpec((block_q, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_q, block_k), lambda i, j, k: (i, k)),
        ],
        out_specs=pl.BlockSpec((block_q, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, n), jnp.float32),
        compiler_params=GRID_PARAMS,
        interpret=interpret,
    )(packed, q_even, q_odd)


def _crumb_dot_kernel(packed_ref, q0_ref, q1_ref, q2_ref, q3_ref, out_ref):
    """2-bit variant: four crumbs per byte, four deinterleaved query planes."""
    k = pl.program_id(2)
    packed = packed_ref[...].astype(jnp.int32)      # widened, as above
    part = jnp.zeros((q0_ref.shape[0], packed.shape[0]), jnp.float32)
    for shift, q_ref in ((0, q0_ref), (2, q1_ref), (4, q2_ref), (6, q3_ref)):
        codes = (packed >> shift) & 0x3
        deq = _dequant_select(codes, _TABLE2)
        part += jnp.dot(q_ref[...], deq.T, **F32_DOT)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = part

    @pl.when(k > 0)
    def _acc():
        out_ref[...] += part


@functools.partial(
    jax.jit, static_argnames=("block_q", "block_n", "block_k", "interpret")
)
def crumb_dot_raw(
    packed: jnp.ndarray,   # [n, d/4] uint8
    q_planes: jnp.ndarray,  # [4, b, d/4] f32 — query dims {4i, 4i+1, 4i+2, 4i+3}
    *,
    block_q: int = 128,
    block_n: int = 256,
    block_k: int = 128,
    interpret: bool = True,
) -> jnp.ndarray:
    n, dk = packed.shape
    _, b, dk2 = q_planes.shape
    assert dk == dk2
    assert n % block_n == 0 and b % block_q == 0 and dk % block_k == 0
    grid = (b // block_q, n // block_n, dk // block_k)

    return pl.pallas_call(
        _crumb_dot_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, block_k), lambda i, j, k: (j, k)),
            pl.BlockSpec((block_q, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_q, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_q, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_q, block_k), lambda i, j, k: (i, k)),
        ],
        out_specs=pl.BlockSpec((block_q, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, n), jnp.float32),
        compiler_params=GRID_PARAMS,
        interpret=interpret,
    )(packed, q_planes[0], q_planes[1], q_planes[2], q_planes[3])
