"""Sharded top-k retrieval: the MonaVec scan over a device mesh.

Decomposition (the standard MIPS-over-partitions scheme; DESIGN.md §3):

  1. the corpus (packed codes + qnorms) is split into contiguous row shards
     along the mesh data axes (``partition.py``);
  2. every shard scores its rows against the replicated rotated queries with
     the SAME kernels the single-device scan uses (``repro.kernels``),
     adjusts by metric, masks padding rows to -inf, and takes a LOCAL
     stable top-k;
  3. local winners are offset to global ids, all-gathered in shard order,
     and re-top-k'd — also stable.

Because shards are contiguous and both top-k stages are stable
(``jax.lax.top_k``: lower index wins ties), the merged (scores, ids) are
identical to the single-device scan on any mesh shape — bit-identical ids,
and scores equal to the last ulp (each row's dot product is computed by the
same kernel on the same bytes; sharding only removes rows from a block, it
never re-associates a row's reduction).

``scan_topk_pjit`` / ``scan_topk_f32`` are the jit'd single-logical-array
references (GSPMD partitions the matmul if the inputs are sharded);
``make_scan_topk_shardmap`` / ``make_scan_topk_f32_shardmap`` build the
explicitly-collective shard_map versions whose communication is exactly one
all-gather of [b, S*k] candidates instead of the full [b, n] score matrix.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import binary as bin_mod
from repro.core.scoring import adjust_scores, score_f32, topk
from repro.kernels.ops import score_raw
from repro.launch.mesh import data_axes

from .partition import data_axis_size, pad_rows, shard_sizes

#: repro.analysis coverage hook (DESIGN.md §10): the shard_map scan factories'
#: outputs run as the engine's ``shard_scan`` / ``cascade_shard_scan`` plan
#: stages; the determinism auditor's grid must capture both.
PLAN_STAGES = ("make_scan_topk_shardmap", "make_cascade_topk_shardmap")


# ---------------------------------------------------------------------------
# Single-logical-array references (jit / pjit).
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("metric", "k", "bits", "n4_dims"))
def scan_topk_pjit(
    q_rot: jnp.ndarray,      # [b, d'] rotated f32 queries (encode_query output)
    packed: jnp.ndarray,     # [n, bytes] packed corpus codes
    qnorms: jnp.ndarray,     # [n] f32 dequantized-vector norms
    *,
    metric: str = "cosine",
    k: int = 10,
    bits: int = 4,
    n4_dims: int = 0,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Reference quantized scan: (scores [b,k], global indices [b,k]).

    Runs as one jit program over the full logical arrays; under `with mesh:`
    and sharded inputs GSPMD partitions it, which is the implicit-parallelism
    baseline the shard_map factories are validated against.
    """
    raw = score_raw(packed, q_rot, bits=bits, n4_dims=n4_dims)
    return topk(adjust_scores(raw, qnorms, metric), k)


@functools.partial(jax.jit, static_argnames=("metric", "k"))
def scan_topk_f32(
    queries: jnp.ndarray,    # [b, d] raw queries
    corpus: jnp.ndarray,     # [n, d] f32 corpus
    *,
    metric: str = "dot",
    k: int = 10,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Exact f32 scan reference (the accuracy ceiling): (scores, indices)."""
    return topk(score_f32(queries, corpus, metric), k)


# ---------------------------------------------------------------------------
# shard_map factories: explicit local-scan + cross-shard merge.
# ---------------------------------------------------------------------------

def _mesh_data_info(mesh):
    """(axes tuple, total shard count) for the corpus partition."""
    return data_axes(mesh), data_axis_size(mesh)


def _shard_index(axes, mesh) -> jnp.ndarray:
    """Row-major linear shard index over the data axes (matches the
    concatenation order of all_gather over the same axis tuple)."""
    idx = jnp.int32(0)
    for a in axes:
        idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
    return idx


def _merge_topk(vals: jnp.ndarray, gids: jnp.ndarray, axes, k: int):
    """All-gather per-shard candidates (shard order) and re-top-k.

    Shard order == global-id order (contiguous partition), and lax.top_k is
    stable, so ties resolve exactly as in the single-device scan.
    """
    vg = jax.lax.all_gather(vals, axes, axis=1, tiled=True)   # [b, S*k_local]
    gg = jax.lax.all_gather(gids, axes, axis=1, tiled=True)
    vv, mi = jax.lax.top_k(vg, k)
    return vv, jnp.take_along_axis(gg, mi, axis=1)


def make_scan_topk_shardmap(
    mesh,
    *,
    metric: str = "cosine",
    k: int = 10,
    bits: int = 4,
    n4_dims: int = 0,
    use_kernel: Optional[bool] = None,
    interpret: Optional[bool] = None,
    n_valid: Optional[int] = None,
    on_trace=None,
    with_mask: bool = False,
):
    """Build fn(q_rot, packed, qnorms) -> (scores [b,k], global ids [b,k])
    scanning corpus shards along the mesh data axes.

    The returned fn accepts the full logical corpus (replicated or already
    sharded); shard_map's in_specs reshard it row-contiguously, padding first
    so every shard is equal-size.  Pass n_valid when the corpus is ALREADY
    padded (ShardedMonaVec) so the padding mask still knows the true row
    count.  ``on_trace`` (if given) runs once per jit trace — the engine's
    plan cache hangs its retrace counter on it (DESIGN.md §7).
    ``with_mask=True`` makes the fn take a fourth argument — an [n] boolean
    row-admissibility mask, sharded alongside the corpus (padding rows are
    masked False) and applied with the padding sentinel BEFORE the local
    top-k, so filtered shards merge exactly like unfiltered ones.  Results
    are identical to scan_topk_pjit (slots with no admissible row surface
    as -inf for the caller to sentinel-convert).
    """
    axes, n_shards = _mesh_data_info(mesh)

    @jax.jit
    def call(q_rot, packed, qnorms, mask=None):
        if on_trace is not None:
            on_trace()
        n = packed.shape[0] if n_valid is None else n_valid
        per, n_pad = shard_sizes(n, n_shards)
        k_local = min(k, per)
        packed_p = pad_rows(packed, n_pad)
        qnorms_p = pad_rows(qnorms, n_pad, fill=1.0)

        def local_scan(q, pk, qn, *rest):
            # pk [per, bytes], qn [per] — this shard's contiguous row block.
            gid0 = _shard_index(axes, mesh) * per
            raw = score_raw(pk, q, bits=bits, n4_dims=n4_dims,
                            use_kernel=use_kernel, interpret=interpret)
            s = adjust_scores(raw, qn, metric)
            gids = gid0 + jnp.arange(per, dtype=jnp.int32)
            ok = gids[None, :] < n                          # padding sentinel
            if rest:
                ok = ok & rest[0][None, :]                  # row admissibility
            s = jnp.where(ok, s, -jnp.inf)
            v, li = jax.lax.top_k(s, k_local)               # local stable top-k
            return _merge_topk(v, jnp.take(gids, li), axes, k)

        in_specs = [P(), P(axes, None), P(axes)]
        operands = [q_rot, packed_p, qnorms_p]
        if with_mask:
            in_specs.append(P(axes))
            operands.append(pad_rows(mask, n_pad, fill=False))
        return jax.shard_map(
            local_scan, mesh=mesh,
            in_specs=tuple(in_specs),
            out_specs=(P(), P()),
            check_vma=False,
        )(*operands)

    return call


def make_cascade_topk_shardmap(
    mesh,
    *,
    metric: str = "cosine",
    k: int = 10,
    bits: int = 4,
    n4_dims: int = 0,
    use_kernel: Optional[bool] = None,
    interpret: Optional[bool] = None,
    n_valid: Optional[int] = None,
    on_trace=None,
    with_mask: bool = False,
    kind: str = bin_mod.SIGN,
    m: int = 320,
):
    """Binarized-cascade variant of make_scan_topk_shardmap (DESIGN.md §11):
    fn(q_rot, packed, qnorms, ccodes[, mask]) -> (scores [b,k], gids [b,k]).

    Each shard runs the WHOLE cascade locally on its contiguous row block —
    integer coarse proxy, survivor top-m (padding and admissibility masks
    fused BEFORE selection, so filtered shards spend their full budget on
    admissible rows), gathered 4-bit rescore — then local top-k and the
    same stable all-gather merge as the plain scan.  Dead survivor slots
    surface as -inf for the caller to sentinel-convert (exactly the
    with_mask contract of the plain factory).
    """
    axes, n_shards = _mesh_data_info(mesh)

    @jax.jit
    def call(q_rot, packed, qnorms, ccodes, mask=None):
        if on_trace is not None:
            on_trace()
        n = packed.shape[0] if n_valid is None else n_valid
        per, n_pad = shard_sizes(n, n_shards)
        m_local = min(m, per)
        k_local = min(k, per, m_local)
        packed_p = pad_rows(packed, n_pad)
        qnorms_p = pad_rows(qnorms, n_pad, fill=1.0)
        ccodes_p = pad_rows(ccodes, n_pad)

        def local_scan(q, pk, qn, cc, *rest):
            gid0 = _shard_index(axes, mesh) * per
            gids = gid0 + jnp.arange(per, dtype=jnp.int32)
            live = gids < n                                 # padding sentinel
            if rest:
                live = live & rest[0]                       # row admissibility
            proxy = bin_mod.coarse_scan_stage(
                q, cc, kind=kind, use_kernel=use_kernel, interpret=interpret)
            # |proxy| <= 9 d'; d' recovers from the plane width (d'/8 bytes
            # per sign plane, two planes for crumb).
            d_rot = cc.shape[-1] * (8 if kind == bin_mod.SIGN else 4)
            cand = bin_mod.survivor_topk_stage(proxy, live, m=m_local,
                                               vbound=9 * d_rot)
            s = bin_mod.gathered_rescore_stage(
                q, pk, qn, cand, bits=bits, n4_dims=n4_dims, metric=metric,
                use_kernel=use_kernel, interpret=interpret)
            s = jnp.where(cand >= 0, s, -jnp.inf)           # dead survivors
            v, si = jax.lax.top_k(s, k_local)               # local stable top-k
            wrow = jnp.take_along_axis(cand, si, axis=1)
            wgid = jnp.where(wrow >= 0, gid0 + wrow, 0)
            return _merge_topk(v, wgid, axes, k)

        in_specs = [P(), P(axes, None), P(axes), P(axes, None)]
        operands = [q_rot, packed_p, qnorms_p, ccodes_p]
        if with_mask:
            in_specs.append(P(axes))
            operands.append(pad_rows(mask, n_pad, fill=False))
        return jax.shard_map(
            local_scan, mesh=mesh,
            in_specs=tuple(in_specs),
            out_specs=(P(), P()),
            check_vma=False,
        )(*operands)

    return call


def make_scan_topk_f32_shardmap(
    mesh,
    *,
    metric: str = "dot",
    k: int = 10,
):
    """f32 variant of make_scan_topk_shardmap: fn(queries, corpus).

    Every score_f32 metric is row-local on the corpus side (per-row norms /
    squared norms), so sharding rows never changes a score's value.
    """
    axes, n_shards = _mesh_data_info(mesh)

    @jax.jit
    def call(queries, corpus):
        n = corpus.shape[0]
        per, n_pad = shard_sizes(n, n_shards)
        k_local = min(k, per)
        corpus_p = pad_rows(corpus, n_pad)

        def local_scan(q, c):
            gid0 = _shard_index(axes, mesh) * per
            s = score_f32(q, c, metric)
            gids = gid0 + jnp.arange(per, dtype=jnp.int32)
            s = jnp.where(gids[None, :] < n, s, -jnp.inf)
            v, li = jax.lax.top_k(s, k_local)
            return _merge_topk(v, jnp.take(gids, li), axes, k)

        return jax.shard_map(
            local_scan, mesh=mesh,
            in_specs=(P(), P(axes, None)),
            out_specs=(P(), P()),
            check_vma=False,
        )(queries, corpus_p)

    return call
