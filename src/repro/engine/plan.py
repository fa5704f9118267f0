"""Compiled search plans + the shape-bucketed plan cache (DESIGN.md §7).

Every MonaVec search — static or mutated, any backend, sharded or not — is
executed through a ``SearchPlan``: a cached pipeline of compiled stages
covering the entire query path (rotate/encode the query -> per-segment
packed or gathered scans -> tombstone/allowlist mask -> segment merge ->
stable top-k -> sentinel marking), keyed by

    (backend fingerprint incl. segment signature, shape bucket, k,
     resolved kernel dispatch, normalized backend knobs)

so serving traffic re-dispatches in O(dict lookup) instead of re-tracing.
Incoming batches are padded up to power-of-two buckets (``shape_bucket``,
floored at 8 — the kernels' block_q granularity); pad queries are masked to
NEG before the top-k and sliced off after, so the bucketed execution is
**bit-identical** to the same plan's full-bucket run and, on the BruteForce
paths, to the eager per-segment oracle at the raw batch size — the same
guarantee style as the dist merge (§3) and the gathered scan (§5): ids
exact, scores to the last ulp.

Three rules make the compile cache sound (full rationale: DESIGN.md §7):

* every ARRAY (packed codes, qnorms, CSR, graph tables, masks, perm) is an
  argument of a stage, never a closure constant — XLA constant-folds
  captured arrays and the folded arithmetic need not be bit-identical to
  the runtime op sequence;
* everything that IS baked into a trace (segment seeds, metric, bit mode,
  std scalars, static graph params, shapes) is part of the fingerprint, so
  two indexes share a plan only when the traced program is truly identical
  — which is also what makes plan reuse across same-shape tenants safe;
* stage boundaries confine floating-point arithmetic exactly where the
  reference computations have op boundaries — whole-pipeline fusion is NOT
  value-preserving (rotation fused into a tiny dot re-associates the
  reduction; the L2 adjustment contracts to an FMA under jit).
"""

from __future__ import annotations

import collections
import dataclasses
import hashlib
import logging
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import binary as bin_mod
from repro.core import bruteforce as bf_mod
from repro.core import hnsw as hnsw_mod
from repro.core import ivf as ivf_mod
from repro.core import predicate as pred
from repro.core import quantize as qz
from repro.core import segments as seg
from repro.core.allowlist import NEG, Allowlist
from repro.core.metadata import MetaStore
from repro.core.rhdh import rhdh_apply
from repro.core.scoring import adjust_scores, topk
from repro.core.standardize import DOT, prepare
from repro.kernels import ops

_LOG = logging.getLogger("repro.engine.plan")

# Stage-capture hook (repro.analysis, DESIGN.md §10): when installed, every
# plan-stage invocation reports (backend kind, stage name, UN-jitted stage
# function, concrete args) before dispatching to the compiled stage.  The
# determinism auditor uses this to jax.make_jaxpr exactly the programs the
# engine compiles — same factories, same operands — instead of a parallel
# hand-maintained stage list that could drift.  Costs one ``is not None``
# check per stage call when uninstalled.
_STAGE_OBSERVER: Optional[Callable[[str, str, Callable, tuple], None]] = None


def set_stage_observer(
    observer: Optional[Callable[[str, str, Callable, tuple], None]],
) -> Optional[Callable[[str, str, Callable, tuple], None]]:
    """Install (or clear, with None) the stage-capture hook; returns the
    previous observer so callers can restore it.  Plans built while an
    observer is installed keep reporting through the module-level slot, so
    clearing the hook also silences previously-built cached plans."""
    global _STAGE_OBSERVER
    prev = _STAGE_OBSERVER
    _STAGE_OBSERVER = observer
    return prev


def shape_bucket(b: int) -> int:
    """Power-of-two batch bucket — the plan cache's shape key.

    Floored at 8, the kernels' block_q/row-chunk granularity: every scoring
    path in the repo computes rows in 8-query tiles, so executing at a
    multiple of 8 keeps the tile decomposition — and therefore every row's
    reduction order — independent of the incoming batch size.
    """
    p = 8
    while p < max(b, 1):
        p <<= 1
    return p


# ---------------------------------------------------------------------------
# Cache + keying.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PlanKey:
    fingerprint: tuple            # backend + segment signature (trace-static)
    bucket: int                   # padded batch size
    k: int
    dispatch: Tuple[bool, bool]   # resolved (use_kernel, interpret)
    knobs: tuple                  # normalized backend knobs, sorted items


@dataclasses.dataclass
class PlanStats(obs.DeltaStats):
    """Counters for the serving loop: cache hits/misses and actual jit
    traces (a trace == one XLA compile; the acceptance criterion 'repeated
    same-bucket searches incur zero retraces' is asserted on ``traces``).
    ``snapshot``/``since`` come from the shared obs.DeltaStats mixin; the
    same counts also flow into the process-wide metrics registry as
    ``plan_cache.{hits,misses,traces,evictions}``."""

    hits: int = 0
    misses: int = 0
    traces: int = 0
    evictions: int = 0


@dataclasses.dataclass
class SearchPlan:
    """A compiled, reusable execution of one search configuration."""

    key: PlanKey
    fn: Callable   # (q_pad, q_valid, live, perm, where_args, *arrays) -> (vals, pos)


def plan_key_digest(key: PlanKey) -> str:
    """Short stable fingerprint of a PlanKey (debug logs, trace attrs)."""
    return hashlib.sha256(repr(key).encode()).hexdigest()[:12]


class PlanCache:
    """PlanKey -> SearchPlan: LRU with hit/miss/trace/eviction accounting.

    Bounded because mutation churn mints new fingerprints (every add() or
    compact() changes the segment signature, DESIGN.md §7), so a long-lived
    serving process would otherwise accumulate superseded plans — and their
    compiled executables — forever.  ``maxsize`` plans is far above any
    steady-state working set (tenants × buckets × k values × knobs).

    Every event lands twice: in ``stats`` (the cheap in-object PlanStats
    serving windows diff against) and in the process-wide metrics registry
    (``plan_cache.*`` counters + size/capacity gauges, DESIGN.md §9).
    Evictions are no longer silent: each one counts, updates the size
    gauge, and logs the evicted key's fingerprint at DEBUG.
    """

    def __init__(self, maxsize: int = 256) -> None:
        self._plans: "collections.OrderedDict[PlanKey, SearchPlan]" = \
            collections.OrderedDict()
        self.maxsize = maxsize
        self.stats = PlanStats()
        self._publish_gauges()

    def _publish_gauges(self) -> None:
        obs.set_gauge("plan_cache.size", len(self._plans))
        obs.set_gauge("plan_cache.capacity", self.maxsize)
        for c in ("hits", "misses", "traces", "evictions"):
            obs.inc(f"plan_cache.{c}", 0)   # pre-register: snapshots always
            #   carry the full counter family, even all-zero

    def get_or_build(self, key: PlanKey, builder: Callable[[], SearchPlan]) -> SearchPlan:
        plan = self._plans.get(key)
        if plan is not None:
            self._plans.move_to_end(key)
            self.stats.hits += 1
            obs.inc("plan_cache.hits")
            return plan
        self.stats.misses += 1
        obs.inc("plan_cache.misses")
        plan = builder()
        self._plans[key] = plan
        while len(self._plans) > self.maxsize:
            old_key, _ = self._plans.popitem(last=False)   # least-recently-used
            self.stats.evictions += 1
            obs.inc("plan_cache.evictions")
            if _LOG.isEnabledFor(logging.DEBUG):
                _LOG.debug(
                    "plan cache evicted %s (bucket=%d k=%d knobs=%s)",
                    plan_key_digest(old_key), old_key.bucket, old_key.k,
                    dict(old_key.knobs))
        self._publish_gauges()
        return plan

    def clear(self) -> None:
        self._plans.clear()
        self.stats = PlanStats()
        self._publish_gauges()

    def __len__(self) -> int:
        return len(self._plans)


_CACHE = PlanCache()


def plan_cache() -> PlanCache:
    """The process-wide plan cache (shared across indexes and tenants)."""
    return _CACHE


# ---------------------------------------------------------------------------
# Fingerprints: everything the trace bakes in.
# ---------------------------------------------------------------------------

def _std_sig(std: Any) -> Optional[tuple]:
    return None if std is None else (float(std.mean), float(std.inv_std))


def _enc_sig(enc: qz.Encoded) -> tuple:
    return (enc.n, enc.seed, enc.bits, enc.n4_dims, enc.dim, enc.dim_pad,
            _std_sig(enc.std), enc.perm is not None, enc.coarse)


_BACKEND_KNOBS = {
    "BruteForceIndex": frozenset({"rescore_mult"}),
    "IvfFlatIndex": frozenset({"nprobe"}),
    "HnswIndex": frozenset({"ef"}),
}


def _validate_knobs(backend: Any, kwargs: dict) -> None:
    kind = type(backend).__name__
    allowed = _BACKEND_KNOBS.get(kind, frozenset())
    unknown = sorted(set(kwargs) - allowed)
    if unknown:
        raise TypeError(
            f"unexpected search kwargs for the {kind} backend: {unknown}")


def _normalize_knobs(backend: Any, extras: Sequence[Any], kwargs: dict,
                     k: int, tuned: Any = None) -> dict:
    """Fill defaults and clamp exactly like the pre-engine search paths, so
    the normalized knobs are part of the plan key (nprobe=min(nprobe,nlist);
    the HNSW beam auto-widens to max(ef, k)).

    Default resolution (DESIGN.md §12): an EXPLICIT per-call kwarg always
    wins; otherwise a persisted autotune result (``tuned.knobs``) supplies
    the default; otherwise the engine's built-in default.  Passing the knob
    as ``None`` means "not given" on every rung of that ladder.

    BruteForce: ``rescore_mult=r > 0`` selects the binarized cascade with a
    rescore budget of m = r*k survivors per segment.  When every segment
    would rescore all of its rows (m >= n_s for all s) the knob normalizes
    AWAY and the plan IS the plain full-scan plan — which is exactly how
    the m=n cascade is bit-identical to the full 4-bit scan (the exactness
    pin in tests/test_cascade.py)."""
    tuned_knobs = {} if tuned is None else dict(getattr(tuned, "knobs", {}))
    kind = type(backend).__name__
    if kind == "IvfFlatIndex":
        nprobe = kwargs.get("nprobe")
        if nprobe is None:
            nprobe = tuned_knobs.get("nprobe", 8)
        return {"nprobe": min(int(nprobe), backend.nlist)}
    if kind == "HnswIndex":
        ef = kwargs.get("ef")
        if ef is None:
            ef = tuned_knobs.get("ef", 64)
        return {"ef": max(int(ef), k)}
    if kind == "BruteForceIndex":
        rm = kwargs.get("rescore_mult")
        if rm is None:
            rm = tuned_knobs.get("rescore_mult")
        rm = 0 if rm is None else int(rm)
        if rm < 0:
            raise ValueError(f"rescore_mult must be >= 0, got {rm}")
        if rm == 0:
            return {}
        encs = [backend.enc] + [s.enc for s in extras]
        if any(e.ccodes is None for e in encs):
            raise ValueError(
                "rescore_mult requires an index built with a binarized "
                "coarse code (MonaVec.build(..., coarse='sign'|'crumb'))")
        if rm * k >= max(e.n for e in encs):
            return {}   # full rescore everywhere == the full scan
        return {"rescore_mult": rm}
    return {}


def _boost_knobs(backend: Any, extras: Sequence[Any], knobs: dict, k: int,
                 mult: int) -> dict:
    """Scale the candidate budget by a boost-curve multiplier (DESIGN.md §12).

    Applied AFTER normalization and BEFORE plan keying, on selective
    filtered queries only: IVF probes more lists (clamped to nlist), the
    cascade widens its survivor budget (re-checking the full-scan collapse).
    The HNSW beam is not boosted — ef gates graph traversal before the live
    mask is known, and the tuned ef already meets the unfiltered target.
    Boosted knobs mint ordinary plan keys, so the extra plans are bounded by
    the multiplier ladder.
    """
    if mult <= 1 or not knobs:
        return knobs
    kind = type(backend).__name__
    if kind == "IvfFlatIndex":
        return {"nprobe": min(knobs["nprobe"] * int(mult), backend.nlist)}
    if kind == "BruteForceIndex" and "rescore_mult" in knobs:
        rm = knobs["rescore_mult"] * int(mult)
        encs = [backend.enc] + [s.enc for s in extras]
        if rm * k >= max(e.n for e in encs):
            return {}   # boosted into a full rescore == the full scan
        return {"rescore_mult": rm}
    return knobs


def resolve_knobs(backend: Any, state: Any, k: int, *, tuned: Any = None,
                  **kwargs: Any) -> dict:
    """The exact knobs a search with these arguments would run with.

    Same validation + normalization as ``search_backend`` (explicit kwarg >
    persisted tuned knob > engine default; nprobe clamped to nlist, ef
    auto-widened to k, rescore_mult collapsed to the full scan when the
    budget covers every segment) — surfaced so callers can SEE silent
    clamping instead of wondering why nprobe=64 behaves like nprobe=16.
    Selectivity boosting is per-query, so it is not included here.
    """
    _validate_knobs(backend, kwargs)
    extras = state.extras if state is not None else []
    return dict(_normalize_knobs(backend, extras, kwargs, k, tuned=tuned))


def _fingerprint(backend: Any, extras: Sequence[Any], knobs: dict) -> tuple:
    kind = type(backend).__name__
    segs = (_enc_sig(backend.enc),) + tuple(_enc_sig(s.enc) for s in extras)
    head: tuple = (kind, backend.enc.metric, segs)
    if kind == "IvfFlatIndex":
        head += ((backend.nlist, backend.max_candidates(knobs["nprobe"])),)
    elif kind == "HnswIndex":
        head += ((backend.m, backend.entry_point, backend.max_level,
                  int(backend.neighbors0.shape[1])),)
    return head


# ---------------------------------------------------------------------------
# Plan compilation.
# ---------------------------------------------------------------------------

def _rotate(q: jnp.ndarray, *, metric: str, std: Any, seed: int,
            perm: Optional[jnp.ndarray]) -> jnp.ndarray:
    """encode_query as a trace-safe stage: same prepare + RHDH as the corpus,
    with the v7 permutation riding in as an array ARGUMENT."""
    prepared = prepare(q.astype(jnp.float32), metric, std)
    rot = rhdh_apply(prepared, seed, normalized=False)
    if perm is not None:
        rot = rot[..., perm]
    return rot


def _build_plan(backend: Any, extras: Sequence[Any], *, key: PlanKey,
                knobs: dict,
                cache: PlanCache,
                where: Optional[pred.Predicate] = None) -> SearchPlan:
    """Compile one plan: a pipeline of per-plan jitted STAGES driven by a
    plain-Python closure.

    The stage boundaries are load-bearing for bit-identity: XLA may fuse a
    query rotation into a downstream (especially tiny) matmul and
    re-associate the reduction, so the rotation, each floating-point scan,
    and the candidate-set search each compile as their own stage — matching
    the op boundaries of the reference/oracle computations exactly — while
    the mask/concat/merge/top-k finalizer (which performs NO float
    arithmetic, only selection and data movement, and is therefore exact
    under any fusion) compiles as one stage on top.  Each stage bumps the
    cache's trace counter at trace time, so a plan-cache hit provably costs
    zero retraces.
    """
    kind = type(backend).__name__
    enc0 = backend.enc
    metric, bits, n4 = enc0.metric, enc0.bits, enc0.n4_dims
    std = enc0.std
    seeds = (enc0.seed,) + tuple(s.enc.seed for s in extras)
    seg_ns = (enc0.n,) + tuple(s.enc.n for s in extras)
    base_n, n_total = seg_ns[0], sum(seg_ns)
    k = key.k
    use_kernel, interpret = key.dispatch
    stats = cache.stats

    def marked(fn, stage):
        """jit(fn) with the trace counter attached (runs once per trace) and
        the analysis stage-capture hook on the call path (module docstring:
        one None-check per call when no observer is installed).  The
        program is named after its stage, so a profiler trace shows it as
        the module ``jit_monavec_<stage>``."""
        def wrapper(*args):
            stats.traces += 1
            obs.inc("plan_cache.traces")
            return fn(*args)
        wrapper.__name__ = wrapper.__qualname__ = f"monavec_{stage}"
        jitted = jax.jit(wrapper)

        def run(*args):
            if _STAGE_OBSERVER is not None:
                _STAGE_OBSERVER(kind, stage, fn, args)
            return jitted(*args)
        return run

    def staged(stage, fn):
        """Host-side per-stage span (DESIGN.md §9): wraps the CALL to a
        compiled stage — the span never enters the traced function, so
        instrumentation cannot perturb the compiled program.  Recorded
        under an active QueryTrace and in a profiler trace; no histogram,
        since on an accelerator the call only enqueues the stage (its
        device time is the ``jit_monavec_<stage>`` module's)."""
        span_name = f"stage:{stage}"

        def run(*args):
            with obs.timed_span(span_name):
                return fn(*args)
        return run

    def make_rot(seed):
        return marked(lambda q, perm: _rotate(q, metric=metric, std=std,
                                              seed=seed, perm=perm), "rotate")

    # Predicate mask stage (DESIGN.md §8): pure boolean algebra over the
    # live mask and the flattened (column keys, constant keys) operands —
    # no float arithmetic, so exact under any fusion.  The stage function
    # depends only on the predicate STRUCTURE (which is in the plan key),
    # never on its constants, so plans are shared across constant values.
    where_stage = None if where is None else staged(
        "predicate_mask", marked(pred.build_stage_fn(where), "predicate_mask"))

    def masked_live(live, where_args):
        return live if where_stage is None else where_stage(live, *where_args)

    def make_scan():
        # Raw dot compiles as its own stage; the metric adjustment runs
        # EAGERLY (op-by-op), exactly like the reference scoring: under jit
        # XLA contracts the L2 multiply+subtract into an FMA and the result
        # is no longer bit-identical to the eager op sequence the oracles
        # (and the pre-engine search paths) compute.
        raw_fn = marked(lambda q_rot, packed: bf_mod.scan_stage(
            q_rot, packed, bits=bits, n4_dims=n4, use_kernel=use_kernel,
            interpret=interpret), "scan")
        if metric == DOT:
            return lambda q_rot, packed, qnorms: raw_fn(q_rot, packed)
        return lambda q_rot, packed, qnorms: adjust_scores(
            raw_fn(q_rot, packed), qnorms, metric)

    rot_stages = [staged("rotate", make_rot(s)) for s in seeds]

    if kind == "BruteForceIndex" and "rescore_mult" in knobs:
        # Binarized cascade (DESIGN.md §11): coarse_scan -> survivor_topk ->
        # gathered_rescore per segment, then one selection-only finalizer.
        # The coarse proxy is INTEGER (bit-identical on every dispatch path);
        # the only float stages are the rotation and the gathered 4-bit
        # rescore — the same score_gathered the IVF/HNSW paths compile.  The
        # live mask (tombstones & allowlist & predicate) gates SURVIVOR
        # SELECTION, so filtered queries spend their whole rescore budget on
        # admissible rows (§3.5: filters must not lose candidates).
        coarse_kind = enc0.coarse
        m = knobs["rescore_mult"] * k
        seg_ms = tuple(min(m, n) for n in seg_ns)
        m_total = sum(seg_ms)
        offsets = [0] + np.cumsum(seg_ns).tolist()

        coarse_stages = [staged("coarse_scan", marked(
            lambda q_rot, ccodes: bin_mod.coarse_scan_stage(
                q_rot, ccodes, kind=coarse_kind, use_kernel=use_kernel,
                interpret=interpret), "coarse_scan")) for _ in seeds]

        def make_surv(m_i):
            return staged("survivor_topk", marked(
                lambda proxy, live_s: bin_mod.survivor_topk_stage(
                    proxy, live_s, m=m_i, vbound=9 * enc0.dim_pad),
                "survivor_topk"))
        surv_stages = [make_surv(m_i) for m_i in seg_ms]

        rescore_stages = [staged("gathered_rescore", marked(
            lambda q_rot, packed, qnorms, cand:
            bin_mod.gathered_rescore_stage(
                q_rot, packed, qnorms, cand, bits=bits, n4_dims=n4,
                metric=metric, use_kernel=use_kernel, interpret=interpret),
            "gathered_rescore")) for _ in seeds]

        n_segs = len(seeds)

        def fin(q_valid, *cols):
            # Selection and data movement only (exact under any fusion):
            # dead survivors already carry NEG from score_gathered and -1
            # in the position columns.
            scores = cols[0] if n_segs == 1 else \
                jnp.concatenate(cols[:n_segs], axis=1)
            gpos = cols[n_segs] if n_segs == 1 else \
                jnp.concatenate(cols[n_segs:], axis=1)
            scores = jnp.where(q_valid[:, None], scores, NEG)
            if m_total < k:   # k > budget: sentinel-pad to the [b, k] contract
                scores = jnp.pad(scores, ((0, 0), (0, k - m_total)),
                                 constant_values=NEG)
                gpos = jnp.pad(gpos, ((0, 0), (0, k - m_total)),
                               constant_values=-1)
            vals, sel = topk(scores, k)
            pos = jnp.take_along_axis(gpos, sel, axis=1)
            return vals, jnp.where(vals > NEG, pos, -1)
        finalize = staged("finalize", marked(fin, "finalize"))

        def fn(q, q_valid, live, perm, where_args, *seg_arrays):
            live = masked_live(live, where_args)
            score_cols, pos_cols = [], []
            for i in range(n_segs):
                off, n_i = offsets[i], seg_ns[i]
                packed, qnorms, ccodes = seg_arrays[3 * i: 3 * i + 3]
                q_rot = rot_stages[i](q, perm)
                proxy = coarse_stages[i](q_rot, ccodes)
                cand = surv_stages[i](proxy, live[off: off + n_i])
                score_cols.append(rescore_stages[i](q_rot, packed, qnorms,
                                                    cand))
                pos_cols.append(jnp.where(cand >= 0, cand + off, -1))
            return finalize(q_valid, *(score_cols + pos_cols))

        return SearchPlan(key=key, fn=fn)

    if kind == "BruteForceIndex":
        scan_stages = [staged("scan", make_scan()) for _ in seeds]

        def fin(q_valid, live, *cols):
            scores = cols[0] if len(cols) == 1 else jnp.concatenate(cols, axis=1)
            scores = jnp.where(live[None, :], scores, NEG)
            scores = jnp.where(q_valid[:, None], scores, NEG)
            if n_total < k:    # k > n: sentinel-pad to the full [b, k] contract
                scores = jnp.pad(scores, ((0, 0), (0, k - n_total)),
                                 constant_values=NEG)
            vals, pos = topk(scores, k)
            return vals, jnp.where(vals > NEG, pos, -1)
        finalize = staged("finalize", marked(fin, "finalize"))

        def fn(q, q_valid, live, perm, where_args, *seg_arrays):
            live = masked_live(live, where_args)
            cols = [scan_stages[i](rot_stages[i](q, perm),
                                   seg_arrays[2 * i], seg_arrays[2 * i + 1])
                    for i in range(len(seeds))]
            return finalize(q_valid, live, *cols)

        return SearchPlan(key=key, fn=fn)

    # Candidate-set backends: one compiled main-scan stage (the same jit
    # body the pre-engine paths ran), brute-force side-scan stages for the
    # extra segments, and an exact merge/finalize stage.
    if kind == "IvfFlatIndex":
        nprobe = knobs["nprobe"]
        max_cand = backend.max_candidates(nprobe)
        main = staged("main", marked(
            lambda q_rot, centroids, order, offsets, packed, qnorms,
            live0: ivf_mod.search_stage(
                q_rot, centroids, order, offsets, packed, qnorms,
                live0, k=k, nprobe=nprobe, max_cand=max_cand,
                metric=metric, bits=bits, n4_dims=n4,
                use_kernel=use_kernel, interpret=interpret), "main"))
        n_head = 3
    elif kind == "HnswIndex":
        ef = knobs["ef"]
        entry, max_level = backend.entry_point, backend.max_level
        main = staged("main", marked(
            lambda q_rot, nbr0, nbr_hi, packed, qnorms, live0:
            hnsw_mod.search_stage(
                q_rot, packed, qnorms, nbr0, nbr_hi, live0,
                entry=entry, ef=ef, k=k, metric=metric, bits=bits,
                n4_dims=n4, max_level=max_level,
                use_kernel=use_kernel, interpret=interpret), "main"))
        n_head = 2
    else:
        raise TypeError(f"no plan builder for backend {kind}")

    # Closures capture COUNTS, never the Segment objects: a superseded plan
    # sitting in the LRU must not pin old segments' quantized arrays.
    n_extras = len(extras)
    scan_stages = [staged("scan", make_scan()) for _ in range(n_extras)]

    def merge(q_valid, live, main_vals, main_pos, *side_cols):
        if side_cols:
            cols = [jnp.where(live[off: off + n][None, :], c, NEG)
                    for c, off, n in zip(
                        side_cols,
                        np.cumsum((base_n,) + seg_ns[1:-1]).tolist(),
                        seg_ns[1:])]
            side = cols[0] if len(cols) == 1 else jnp.concatenate(cols, axis=1)
            main_vals, main_pos = seg.merge_stage(
                main_vals, main_pos, side, base_n, k)
        vals = jnp.where(q_valid[:, None], main_vals, NEG)
        return vals, jnp.where(vals > NEG, main_pos, -1)
    finalize = staged("merge", marked(merge, "merge"))

    def fn(q, q_valid, live, perm, where_args, *arrays):
        live = masked_live(live, where_args)
        head, seg_arrays = arrays[:n_head], arrays[n_head:]
        q_rot0 = rot_stages[0](q, perm)
        main_vals, main_pos = main(q_rot0, *head, seg_arrays[0],
                                   seg_arrays[1], live[:base_n])
        side_cols = [scan_stages[i](rot_stages[i + 1](q, perm),
                                    seg_arrays[2 * (i + 1)],
                                    seg_arrays[2 * (i + 1) + 1])
                     for i in range(n_extras)]
        return finalize(q_valid, live, main_vals, main_pos, *side_cols)

    return SearchPlan(key=key, fn=fn)


def _bind_arrays(backend: Any, extras: Sequence[Any],
                 with_codes: bool = False) -> tuple:
    """Per-call array operands, in the plan function's positional order.

    ``with_codes`` (cascade plans) appends each segment's packed coarse
    codes after its (packed, qnorms) pair — arrays stay stage ARGUMENTS."""
    kind = type(backend).__name__
    head: tuple = ()
    if kind == "IvfFlatIndex":
        head = (backend.centroids, backend.order_j, backend.offsets_j)
    elif kind == "HnswIndex":
        head = (jnp.asarray(backend.neighbors0),
                jnp.asarray(backend.neighbors_hi) if backend.max_level else None)
    segs: list = []
    for enc in [backend.enc] + [s.enc for s in extras]:
        if with_codes:
            segs.extend((enc.packed, enc.qnorms, enc.ccodes))
        else:
            segs.extend((enc.packed, enc.qnorms))
    return head + tuple(segs)


# ---------------------------------------------------------------------------
# Execution: the one search entry point every backend routes through.
# ---------------------------------------------------------------------------

def _phase(backend: str, stage: str, /, **attrs: Any):
    """One host phase of a search (DESIGN.md §9).  prepare -> plan_lookup
    -> execute -> sync -> finish partition a search's host time in the
    ``engine.stage_us{backend,stage}`` histograms, and each is a span (a
    QueryTrace child, and ``monavec.<stage>`` in a profiler trace)."""
    return obs.timed_span(stage, histogram="engine.stage_us",
                          labels={"backend": backend, "stage": stage},
                          attrs=attrs or None)


def search_backend(
    backend: Any,
    state: Any,                  # SegmentedState or None (= static index)
    queries: jnp.ndarray,
    k: int,
    *,
    allow: Optional[Allowlist] = None,
    where: Optional[pred.Predicate] = None,
    meta: Optional[MetaStore] = None,
    where_mask: Optional[np.ndarray] = None,
    use_kernel: Optional[bool] = None,
    interpret: Optional[bool] = None,
    tuned: Any = None,
    **kwargs,
) -> Tuple[np.ndarray, np.ndarray]:
    """Bucketed compiled-plan search: (scores [b,k], external ids [b,k]).

    Exactly ``k`` columns always; inadmissible slots carry SENTINEL_ID/NEG.
    Bit-identical to the pre-engine per-path implementations (the oracle
    suites in tests/ pin this), with the whole pipeline compiled once per
    (fingerprint, bucket, k, dispatch, knobs) and reused across calls —
    and across same-shape tenants.

    Filtering (DESIGN.md §8): ``where=`` is a structured predicate over
    ``meta``'s columns, compiled as a mask stage fused with the tombstone/
    allowlist live mask — its STRUCTURE joins the fingerprint, its
    constants (and the column key planes) ride as dynamic arguments, so
    repeated predicate shapes hit the cache with zero retrace.
    ``where_mask=`` is the already-computed [n_total] boolean row mask for
    callers that evaluated a predicate themselves; it is ANDed host-side
    (the live mask is a dynamic argument, so no new plan is minted).

    ``tuned=`` (a ``repro.tune.TuneResult``) supplies knob DEFAULTS and,
    when it carries a boost curve, the per-query selectivity boost on
    filtered searches (DESIGN.md §12).
    """
    kind = type(backend).__name__
    with _phase(kind, "prepare"):
        _validate_knobs(backend, kwargs)
        extras = state.extras if state is not None else []
        knobs = _normalize_knobs(backend, extras, kwargs, k, tuned=tuned)
        use_kernel, interpret = ops.resolve_dispatch(use_kernel, interpret)

        q = jnp.atleast_2d(jnp.asarray(queries))
        b = int(q.shape[0])
        bucket = shape_bucket(b)
        obs.inc("engine.searches", **{"backend": kind})
        obs.inc("engine.query_rows", b, **{"backend": kind})

        base_n = backend.enc.n
        n_total = int(base_n + sum(s.enc.n for s in extras))
        if state is not None:
            live = seg.live_mask(state, allow, base_n)
        elif allow is not None:
            mask = np.asarray(allow.mask, dtype=bool)
            if mask.shape[0] != base_n:
                raise ValueError(
                    f"allowlist mask covers {mask.shape[0]} rows but the "
                    f"index has {base_n}; build it from the index ids")
            live = mask
        else:
            live = np.ones(base_n, dtype=bool)

        boost = None if tuned is None else getattr(tuned, "boost", None)
        filtered = where is not None or where_mask is not None
        # Denominator of the selectivity ratio: live∩allowed rows BEFORE
        # the caller's filter — "1% selectivity" means 1% of what an
        # unfiltered search of this index would rank.
        pre_filter_n = (int(np.count_nonzero(live))
                        if boost is not None and filtered and knobs else 0)

        if where_mask is not None:
            wm = np.asarray(where_mask, dtype=bool)
            if wm.shape != (n_total,):
                raise ValueError(
                    f"where_mask covers {wm.shape} rows but the index has "
                    f"{n_total}")
            live = np.asarray(live, dtype=bool) & wm

        where_sig = None
        where_args: tuple = ()
        if where is not None:
            if meta is None or not meta:
                raise ValueError(
                    "where= requires an index built with metadata columns")
            if meta.n_rows != n_total:
                raise ValueError(
                    f"metadata has {meta.n_rows} rows but the index has "
                    f"{n_total}")
            pred.validate(where, meta)
            where_sig = pred.structure(where, meta)
            where_args = tuple(
                jnp.asarray(a) for a in pred.flatten_args(where, meta))

        # Selectivity-aware candidate budgets (DESIGN.md §12): on filtered
        # searches of a boost-tuned index, measure how selective the filter
        # is (exact popcount, cached per predicate structure+constants) and
        # widen nprobe / rescore_mult via the tuned curve BEFORE plan keying
        # — the fix for filtered recall collapsing at 1% selectivity.
        if boost is not None and filtered and knobs and pre_filter_n > 0:
            if where is not None:
                from repro.tune.selectivity import estimate_matches
                matched = estimate_matches(where, meta, live)
            else:
                matched = int(np.count_nonzero(live))
            mult = boost.multiplier(matched / pre_filter_n)
            if mult > 1:
                knobs = _boost_knobs(backend, extras, knobs, k, mult)
                obs.inc("engine.boost_applied",
                        **{"backend": kind, "mult": str(mult)})

        fingerprint = _fingerprint(backend, extras, knobs)
        if where_sig is not None:
            fingerprint = fingerprint + (("where", where_sig),)
        key = PlanKey(
            fingerprint=fingerprint,
            bucket=bucket, k=k, dispatch=(use_kernel, interpret),
            knobs=tuple(sorted(knobs.items())),
        )
        if bucket != b:
            q = jnp.pad(q, ((0, bucket - b), (0, 0)))
        q_valid = jnp.asarray(np.arange(bucket) < b)
        perm = None if backend.enc.perm is None else jnp.asarray(backend.enc.perm)

    with _phase(kind, "plan_lookup") as sp:
        misses_before = _CACHE.stats.misses
        plan = _CACHE.get_or_build(
            key, lambda: _build_plan(backend, extras, key=key, knobs=knobs,
                                     cache=_CACHE, where=where))
        if sp is not None:
            sp.attrs.update(plan=plan_key_digest(key), bucket=bucket, k=k,
                            hit=_CACHE.stats.misses == misses_before)

    with _phase(kind, "execute", backend=kind, rows=b, bucket=bucket):
        vals, pos = plan.fn(q, q_valid, jnp.asarray(live), perm, where_args,
                            *_bind_arrays(backend, extras,
                                          with_codes="rescore_mult" in knobs))
    # The device->host transfer is where outstanding async device work
    # completes: this span/histogram carries the actual device latency.
    with _phase(kind, "sync"):
        vals = np.asarray(vals)[:b]
        pos = np.asarray(pos)[:b]
    with _phase(kind, "finish"):
        ids = (backend.ids if not extras else
               np.concatenate([backend.ids] + [s.ids for s in extras]))
        ids = seg.rows_to_ids(pos, ids)
    return vals, ids


def search_sharded(index: Any, queries: jnp.ndarray, k: int, *,
                   where_mask: Optional[np.ndarray] = None,
                   rescore_mult: Optional[int] = None,
                   tuned: Any = None,
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """The shard_map scan as a cached plan: same bucketing, same counters,
    same [b, k] sentinel-padded contract as the single-device engines.

    ``where_mask`` is an [n] boolean row-admissibility mask (a compiled
    predicate's output, or any caller-built filter), sharded alongside the
    corpus and applied BEFORE the local top-k — slots with no admissible
    row come back as SENTINEL_ID / NEG exactly like the single-device
    filtered path.

    ``rescore_mult=r > 0`` selects the binarized cascade INSIDE each shard
    (coarse proxy -> local survivor top-m -> gathered 4-bit rescore -> local
    top-k), normalized exactly like the single-device knob: when m = r*k
    covers the whole corpus the knob drops away and the plan is the plain
    sharded scan (the m=n bit-identity pin)."""
    with _phase("ShardedMonaVec", "prepare"):
        q = jnp.atleast_2d(jnp.asarray(queries))
        b = int(q.shape[0])
        bucket = shape_bucket(b)
        enc = index.enc
        k_eff = min(k, index.n)
        masked = where_mask is not None
        if masked:
            where_mask = np.asarray(where_mask, dtype=bool)
            if where_mask.shape != (index.n,):
                raise ValueError(
                    f"where_mask covers {where_mask.shape} rows but the index "
                    f"has {index.n}")
        if rescore_mult is None and tuned is not None:
            rescore_mult = dict(getattr(tuned, "knobs", {})).get("rescore_mult")
        rm = 0 if rescore_mult is None else int(rescore_mult)
        if rm < 0:
            raise ValueError(f"rescore_mult must be >= 0, got {rm}")
        boost = None if tuned is None else getattr(tuned, "boost", None)
        if boost is not None and masked and rm > 0 and index.n > 0:
            # Sharded corpora are static (no tombstones): selectivity is the
            # mask's exact popcount over the whole corpus.
            mult = boost.multiplier(
                int(np.count_nonzero(where_mask)) / index.n)
            if mult > 1:
                rm *= int(mult)
                obs.inc("engine.boost_applied",
                        **{"backend": "ShardedMonaVec", "mult": str(mult)})
        if rm > 0 and enc.ccodes is None:
            raise ValueError(
                "rescore_mult requires an index built with a binarized coarse "
                "code (MonaVec.build(..., coarse='sign'|'crumb'))")
        if rm * k_eff >= index.n:
            rm = 0              # full rescore everywhere == the full scan
        cascade = rm > 0
        # Content-keyed like search_backend — the plan must not retain the
        # index: the closure holds only scalars + the (small, long-lived)
        # mesh, arrays ride in as arguments, and same-config corpora on one
        # mesh share plans.
        key = PlanKey(
            fingerprint=("ShardedMonaVec", id(index.mesh), index.n,
                         _enc_sig(enc), enc.metric, masked),
            bucket=bucket, k=k_eff, dispatch=(None, None),
            knobs=(("rescore_mult", rm),) if cascade else (),
        )
        n_shards = int(getattr(index.mesh, "size", 1))
        obs.inc("engine.searches", **{"backend": "ShardedMonaVec"})
        obs.inc("engine.query_rows", b, **{"backend": "ShardedMonaVec"})
        if bucket != b:
            q = jnp.pad(q, ((0, bucket - b), (0, 0)))
        perm = None if enc.perm is None else jnp.asarray(enc.perm)

    def build() -> SearchPlan:
        from repro.dist.retrieval import (make_cascade_topk_shardmap,
                                          make_scan_topk_shardmap)
        stats = _CACHE.stats

        def on_trace() -> None:
            stats.traces += 1
            obs.inc("plan_cache.traces")

        mesh = index.mesh
        metric, std, seed = enc.metric, enc.std, enc.seed
        if cascade:
            scan = make_cascade_topk_shardmap(
                mesh, metric=metric, k=k_eff, bits=enc.bits,
                n4_dims=enc.n4_dims, n_valid=index.n, on_trace=on_trace,
                with_mask=masked, kind=enc.coarse, m=rm * k_eff)
        else:
            scan = make_scan_topk_shardmap(
                mesh, metric=metric, k=k_eff, bits=enc.bits,
                n4_dims=enc.n4_dims, n_valid=index.n, on_trace=on_trace,
                with_mask=masked)
        stage = "cascade_shard_scan" if cascade else "shard_scan"

        def raw(q_pad, packed, qnorms, ccodes, perm, mask):
            # Eager rotation: the exact op sequence of qz.encode_query.
            q_rot = _rotate(q_pad, metric=metric, std=std, seed=seed,
                            perm=perm)
            args = (q_rot, packed, qnorms)
            if ccodes is not None:
                args += (ccodes,)
            if mask is not None:
                args += (mask,)
            if _STAGE_OBSERVER is not None:
                _STAGE_OBSERVER("ShardedMonaVec", stage, scan, args)
            with mesh:
                return scan(*args)

        return SearchPlan(key=key, fn=raw)

    with _phase("ShardedMonaVec", "plan_lookup") as sp:
        plan = _CACHE.get_or_build(key, build)
        if sp is not None:
            sp.attrs.update(plan=plan_key_digest(key), shards=n_shards)
    with _phase("ShardedMonaVec", "execute", shards=n_shards, rows=b):
        vals, gidx = plan.fn(q, enc.packed, enc.qnorms,
                             enc.ccodes if cascade else None, perm,
                             jnp.asarray(where_mask) if masked else None)
    with _phase("ShardedMonaVec", "sync"):
        vals = np.asarray(vals)[:b]
        gidx = np.asarray(gidx)
    with _phase("ShardedMonaVec", "finish"):
        ids = index.ids[gidx[:b]]
        if masked or cascade:
            # Filtered shards (and cascade shards with dead survivor slots)
            # surface inadmissible slots as -inf; convert to the engine-wide
            # sentinel contract (NEG score, SENTINEL_ID id).
            bad = ~np.isfinite(vals)
            vals = np.where(bad, NEG, vals).astype(vals.dtype)
            ids = np.where(bad, seg.SENTINEL_ID, ids)
        if k_eff < k:   # k > n: sentinel-pad to the full [b, k] contract
            vals = np.pad(vals, ((0, 0), (0, k - k_eff)), constant_values=NEG)
            ids = np.pad(ids, ((0, 0), (0, k - k_eff)),
                         constant_values=seg.SENTINEL_ID)
    return vals, ids


# ---------------------------------------------------------------------------
# The searcher handle.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Searcher:
    """A bound (index, k, dispatch, knobs) handle: ``searcher(queries)``.

    Produced by ``MonaVec.searcher(...)`` / ``ShardedMonaVec.searcher(...)``;
    plans resolve through the shared cache on every call, so a searcher is
    always consistent with the index's CURRENT mutation state (add/delete/
    compact simply select a different plan).  ``warmup()`` pre-compiles the
    plan for a bucket so serving never pays the trace inside a measured or
    latency-sensitive window.
    """

    index: object
    k: int = 10
    use_kernel: Optional[bool] = None
    interpret: Optional[bool] = None
    knobs: dict = dataclasses.field(default_factory=dict)
    where: Optional[pred.Predicate] = None
    # Extra metric labels, e.g. (("namespace", ns), ("collection", name))
    # from TenantRegistry.searcher: when set, every call counts one
    # ``tenancy.requests`` and lands in the ``tenancy.search_us`` histogram /
    # ``tenancy.errors`` counter under those labels (per-namespace serving
    # metrics, DESIGN.md §9).
    labels: tuple = ()

    def __call__(self, queries: jnp.ndarray, *,
                 allow: Optional[Allowlist] = None,
                 ) -> Tuple[np.ndarray, np.ndarray]:
        kw = dict(self.knobs)
        if self.use_kernel is not None:
            kw["use_kernel"] = self.use_kernel
        if self.interpret is not None:
            kw["interpret"] = self.interpret
        if allow is not None:
            kw["allow"] = allow
        if self.where is not None:
            kw["where"] = self.where
        if not self.labels:
            return self.index.search(queries, self.k, **kw)
        labels = dict(self.labels)
        obs.inc("tenancy.requests", **labels)
        try:
            with obs.timed_span("tenant_search",
                                histogram="tenancy.search_us", labels=labels):
                return self.index.search(queries, self.k, **kw)
        except Exception:
            obs.inc("tenancy.errors", kind="search", **labels)
            raise

    def warmup(self, batch_size: int = 1) -> "Searcher":
        enc = self.index.enc if hasattr(self.index, "enc") else \
            self.index.backend.enc
        bucket = shape_bucket(batch_size)
        self(np.zeros((bucket, enc.dim), dtype=np.float32))
        return self
