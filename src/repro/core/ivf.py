"""IvfFlat backend (paper §3.4.2): metric-aware k-means + inverted lists.

The single opt-in TRAINED component (paper Table 1): Lloyd's algorithm over the
corpus.  Metric awareness:
  * cosine — centroids L2-normalized after every mean update (direction is the
    representative, magnitude irrelevant);
  * dot/L2 — raw means.

Clustering runs in ROTATED f32 space: the rotation is orthogonal, so cluster
geometry is identical to input space, and query/centroid scoring then shares
the rotated query with the packed scan.  Deterministic: seeded farthest-point
init, fixed iteration count, stable argmin tie-breaks.

The probe scan (DESIGN.md §5) runs over PACKED bytes end to end: the CSR
(order, offsets) arrays are staged on device once at build/load, per-query
candidates assemble as a vectorized ragged-concat into a tight fixed-shape
[b, max_cand] matrix (-1 tail), and scoring goes through
``ops.score_gathered`` — compare-select dequant fused into the dot, never a
``[b, max_cand, d']`` f32 materialization.  The allowlist masks scores
before the top-k (§3.5 pre-filter).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops
from . import quantize as qz
from .allowlist import NEG, Allowlist
from .scoring import topk
from .standardize import COSINE, L2, prepare


#: repro.analysis coverage hook (DESIGN.md §10): pure plan stages exported
#: here; the determinism auditor's grid must capture each one.
PLAN_STAGES = ("search_stage",)


# Full f32 products: a TPU runs a default-precision f32 matmul as one bf16
# pass, which would train different centroids than any other platform.
_HI = jax.lax.Precision.HIGHEST


def _assign(x: jnp.ndarray, cents: jnp.ndarray, metric: str) -> jnp.ndarray:
    """Nearest centroid per row.  argmin/argmax are stable (lowest index)."""
    xc = jnp.matmul(x, cents.T, precision=_HI)
    if metric == L2:
        d2 = (
            jnp.sum(x * x, axis=1, keepdims=True)
            - 2.0 * xc
            + jnp.sum(cents * cents, axis=1)[None, :]
        )
        return jnp.argmin(d2, axis=1)
    return jnp.argmax(xc, axis=1)


@functools.partial(jax.jit, static_argnames=("n_clusters", "metric", "iters"))
def _kmeans(x: jnp.ndarray, init: jnp.ndarray, *, n_clusters: int, metric: str, iters: int):
    """Fixed-iteration Lloyd's; empty clusters keep their previous centroid."""

    def step(cents, _):
        a = _assign(x, cents, metric)
        one_hot = jax.nn.one_hot(a, n_clusters, dtype=x.dtype)      # [n, k]
        sums = jnp.matmul(one_hot.T, x, precision=_HI)              # [k, d]
        counts = jnp.sum(one_hot, axis=0)[:, None]                  # [k, 1]
        means = sums / jnp.maximum(counts, 1.0)
        new = jnp.where(counts > 0, means, cents)
        if metric == COSINE:
            new = new / jnp.maximum(jnp.linalg.norm(new, axis=1, keepdims=True), 1e-12)
        return new, None

    cents, _ = jax.lax.scan(step, init, None, length=iters)
    return cents, _assign(x, cents, metric)


def _seeded_init(x: np.ndarray, k: int, seed: int, metric: str) -> np.ndarray:
    """Deterministic farthest-point (k-means++-style, greedy) initialization."""
    rng = np.random.RandomState(seed & 0x7FFFFFFF)
    n = x.shape[0]
    first = int(rng.randint(n))
    chosen = [first]
    if metric == L2:
        d = np.sum((x - x[first]) ** 2, axis=1)
    else:
        d = 1.0 - x @ x[first] / (np.linalg.norm(x, axis=1) * np.linalg.norm(x[first]) + 1e-12)
    for _ in range(k - 1):
        nxt = int(np.argmax(d))  # deterministic: greedy farthest, stable argmax
        chosen.append(nxt)
        if metric == L2:
            d = np.minimum(d, np.sum((x - x[nxt]) ** 2, axis=1))
        else:
            d = np.minimum(
                d, 1.0 - x @ x[nxt] / (np.linalg.norm(x, axis=1) * np.linalg.norm(x[nxt]) + 1e-12)
            )
    return x[np.asarray(chosen)]


def search_stage(
    q_rot, centroids, order, offsets, packed, qnorms, allow_mask, *,
    k, nprobe, max_cand, metric, bits, n4_dims, use_kernel, interpret,
):
    """Fixed-shape probe + gathered scan + pre-filtered top-k — the jitted
    body exposed as a pure PLAN STAGE (the engine composes it with query
    rotation and the segment merge into one compiled SearchPlan, DESIGN.md
    §7; every array rides in as an argument, never a trace constant).

    Candidate assembly is a vectorized ragged-concat straight off the CSR
    (order, offsets) arrays: output slot j of query b belongs to the probed
    cell whose cumulative length first exceeds j (a searchsorted), at offset
    ``j - cum[cell-1]`` within it.  This fills ``max_cand`` = the sum of the
    nprobe largest cell sizes (the tight per-query bound, valid candidates
    contiguous in probe order, -1 tail) with no per-query host loop and no
    O(nlist * max_cell) padded table — a skewed clustering costs padding
    proportional to the skew of the probed cells only.
    """
    cs = jnp.matmul(q_rot, centroids.T, precision=_HI)
    if metric == L2:
        cs = cs - 0.5 * jnp.sum(centroids * centroids, axis=1)[None, :]
    _, probe = topk(cs, nprobe)                           # [b, nprobe]
    lens = (offsets[1:] - offsets[:-1])[probe]            # [b, nprobe]
    cum = jnp.cumsum(lens, axis=1)                        # [b, nprobe]
    width = max(max_cand, k)   # tiny corpus: keep the [b, k] output contract
    slot = jnp.arange(width, dtype=offsets.dtype)         # [width]
    cell = jax.vmap(
        lambda c: jnp.searchsorted(c, slot, side="right")
    )(cum)                                                # [b, width]
    cell_c = jnp.minimum(cell, nprobe - 1)
    prev = jnp.where(cell_c > 0,
                     jnp.take_along_axis(cum, jnp.maximum(cell_c - 1, 0), axis=1),
                     0)
    src = jnp.take_along_axis(offsets[probe], cell_c, axis=1) + (slot[None] - prev)
    valid = slot[None] < cum[:, -1:]
    cand = jnp.where(valid, order[jnp.minimum(src, order.shape[0] - 1)], -1)
    scores = ops.score_gathered(
        packed, q_rot, cand, bits=bits, n4_dims=n4_dims, qnorms=qnorms,
        metric=metric, allow_mask=allow_mask, use_kernel=use_kernel,
        interpret=interpret,
    )
    vals, pos = topk(scores, min(k, cand.shape[1]))
    rows = jnp.take_along_axis(cand, pos, axis=1)
    # Same no-result contract as HNSW: any NEG slot (padding, or fewer than k
    # allowed candidates) is marked -1, never a real row.
    return vals, jnp.where(vals > NEG, rows, -1)


@dataclasses.dataclass
class IvfFlatIndex:
    enc: qz.Encoded
    ids: np.ndarray                 # [n] external ids
    centroids: jnp.ndarray          # [nlist, d'] rotated f32
    order: np.ndarray               # [n] row permutation grouping clusters
    offsets: np.ndarray             # [nlist+1] CSR offsets into ``order``
    nlist: int
    # CSR staged on device (int32) once per index — build AND load — so the
    # jit'd candidate assembly never re-uploads or loops per search call.
    order_j: jnp.ndarray = dataclasses.field(init=False, repr=False)
    offsets_j: jnp.ndarray = dataclasses.field(init=False, repr=False)

    def __post_init__(self):
        self.order_j = jnp.asarray(self.order, jnp.int32)
        self.offsets_j = jnp.asarray(self.offsets, jnp.int32)

    @staticmethod
    def build(
        vectors: jnp.ndarray,
        *,
        ids: Optional[np.ndarray] = None,
        metric: str = COSINE,
        seed: int = 0x6D6F6E61,
        bits: int = 4,
        std=None,
        nlist: int = 64,
        train_iters: int = 25,
    ) -> "IvfFlatIndex":
        n = vectors.shape[0]
        enc = qz.encode(vectors, metric=metric, seed=seed, bits=bits, std=std)
        # Cluster in rotated f32 space (normalized rotation: unit geometry).
        prepared = prepare(jnp.asarray(vectors, jnp.float32), metric, std)
        from .rhdh import rhdh_apply

        rot = rhdh_apply(prepared, seed, normalized=False)
        init = jnp.asarray(_seeded_init(np.asarray(rot), nlist, seed, metric))
        cents, assign = _kmeans(rot, init, n_clusters=nlist, metric=metric, iters=train_iters)
        assign = np.asarray(assign)
        order = np.argsort(assign, kind="stable").astype(np.int64)
        counts = np.bincount(assign, minlength=nlist)
        offsets = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)
        if ids is None:
            ids = np.arange(n, dtype=np.uint64)
        return IvfFlatIndex(
            enc=enc, ids=np.asarray(ids, dtype=np.uint64), centroids=cents,
            order=order, offsets=offsets, nlist=nlist,
        )

    def max_candidates(self, nprobe: int) -> int:
        """Sum of the ``nprobe`` largest cell sizes — the tight fixed shape
        of the per-query candidate matrix (part of the engine's plan key)."""
        counts = np.asarray(self.offsets[1:] - self.offsets[:-1])
        return int(np.sort(counts)[::-1][:nprobe].sum())

    def search(
        self,
        queries: jnp.ndarray,
        k: int,
        *,
        nprobe: int = 8,
        allow: Optional[Allowlist] = None,
        where_mask=None,
        use_kernel: Optional[bool] = None,
        interpret: Optional[bool] = None,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Probe the nprobe nearest cells and scan their lists with the packed
        gathered-candidate scan (``ops.score_gathered``): candidates stay
        4/2-bit until the fused dequant-dot, the allowlist masks scores before
        the top-k, and the whole rotate->probe->scan->top-k is one cached
        SearchPlan per (shape bucket, nprobe, k) — repro.engine, DESIGN.md §7.
        ``use_kernel``/``interpret`` dispatch exactly like ``score_packed``
        (None = kernel on TPU, jnp elsewhere).  Always exactly ``k`` columns:
        slots with no admissible candidate come back with id
        0xFFFFFFFFFFFFFFFF and a NEG score (the HNSW sentinel contract).
        """
        from .. import engine
        return engine.search_backend(
            self, None, queries, k, allow=allow, where_mask=where_mask,
            use_kernel=use_kernel, interpret=interpret, nprobe=nprobe,
        )
