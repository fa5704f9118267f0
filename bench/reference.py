"""Plain reference for MonaVec's search semantics, and the comparison that decides ``correct``.

It imports nothing of the program and takes nothing the program made: it
encodes the corpus itself, from the same raw vectors and the configuration's
rotation seed, and judges the answers the measured window returned.

Semantics (the configuration states them): the corpus row x is prepared
(cosine: unit norm; l2: (x - mean) * inv_std with fit()'s scalars), padded
to d' = next power of two, multiplied by the seeded +-1 diagonal and the
unnormalized Walsh-Hadamard matrix, and each coordinate is coded by the
frozen 4-bit Lloyd-Max boundaries (code = number of boundaries <= y).  A
query gets the same rotation without coding.  The score of a row is
q . deq (dot), q . deq / |deq| (cosine) or q . deq - |deq|^2 / 2 (l2), and
the answer is the k best rows, ties to the lower row.  The crumb cascade
first keeps the m = rescore_mult * k rows of highest crumb proxy
sum_i L(cq_i) L(cv_i), L(c) = 2c - 3, with cv = code >> 2 and cq the 2-bit
Lloyd-Max code of the rotated query, proxy ties to the lower row.

A float32 program cannot place a coordinate that lies within its rounding
of a boundary: either neighbouring code is admissible there.  The reference
therefore keeps, for every row, the interval [L, U] of scores (and of
proxies) over every admissible coding, and measures how far each returned
answer lies outside what some admissible coding allows:

  * score error: the distance of the returned score from [L, U] of its row;
  * rank error: how far the k-th best lower bound among rows that surely
    survive lies above the returned row's upper bound.

Both are divided by the query's score scale (|q| |deq| plus |deq|^2 / 2 for
l2, |q| for cosine), and ``answer_gap`` is the largest over the checked
queries.  A returned row that is out of range, repeated, out of score order
or (cascade) cannot be a survivor at all counts as STRUCTURAL.

Work is split in two tiers so that a million rows fit in the run's time.
The device tier rotates and codes every row in float32 (HIGHEST), scores it
with a generous error margin, and computes the integer proxy intervals
exactly; it only nominates candidate rows.  The host tier recomputes the
candidates and the returned rows exactly, in float64.
"""

from __future__ import annotations

import functools
import itertools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST

# The frozen Lloyd-Max tables of the .mvec format (float32 values).
CENTROIDS4 = np.array([
    -2.7325895709929284, -2.0690172265288570, -1.6180463860193035,
    -1.2562311973447498, -0.9423404564848586, -0.6567591185308426,
    -0.3880482994892674, -0.1283950298507978, 0.1283950298507979,
    0.3880482994892679, 0.6567591185308430, 0.9423404564848593,
    1.2562311973447489, 1.6180463860192993, 2.0690172265288647,
    2.7325895709929156], np.float32).astype(np.float64)
BOUNDARIES4 = np.array([
    -2.4008033987608925, -1.8435318062740804, -1.4371387916820266,
    -1.0992858269148043, -0.7995497875078506, -0.5224037090100551,
    -0.2582216646700326, 0.0, 0.2582216646700329, 0.5224037090100555,
    0.7995497875078512, 1.0992858269148040, 1.4371387916820240,
    1.8435318062740820, 2.4008033987608899], np.float32).astype(np.float64)
BOUNDARIES2 = np.array([-0.9815988215677121, 0.0, 0.9815988215677122],
                       np.float32).astype(np.float64)

#: A float32 program's rotated coordinate lies within EPS64 * |x'| of the
#: exact one (its error is some 1e-7 |x'|); nearer a boundary, both codes
#: are admissible.
EPS64 = 1e-5
#: The device tier's own float32 coding may differ from float64 inside
#: EPS64 plus its error: its ambiguity band is wider.
EPS32 = 2e-5
#: Relative error margin of the device tier's float32 scores.
MARGIN32 = 1e-4
#: The reading of an answer that no admissible coding allows at all.
STRUCTURAL = 10.0
#: Candidates per query the host tier will recompute before giving up.
MAX_CANDIDATES = 20000


def next_pow2(d: int) -> int:
    return 1 << max(0, (int(d) - 1).bit_length())


def signs(seed: int, d_pad: int) -> np.ndarray:
    """The +-1 diagonal of the rotation, as the .mvec format derives it from
    its 64-bit seed (threefry, the pre-JAX-0.5 bit layout)."""
    with jax.threefry_partitionable(False):
        key = jax.random.key(np.uint32(seed & 0xFFFFFFFF))
        key = jax.random.fold_in(key, np.uint32((seed >> 32) & 0xFFFFFFFF))
        s = jax.random.rademacher(key, (d_pad,), dtype=jnp.float32)
    return np.asarray(s, np.float64)


def hadamard(n: int) -> np.ndarray:
    h = np.ones((1, 1))
    while h.shape[0] < n:
        h = np.block([[h, h], [h, -h]])
    return h


class Semantics(NamedTuple):
    metric: str
    d_pad: int
    signs: np.ndarray          # [d'] float64
    mean: float                # l2 standardization (0 and 1 otherwise)
    inv_std: float
    had: np.ndarray            # [d', d'] unnormalized Walsh-Hadamard


def semantics(cfg: dict, calibration: Optional[np.ndarray] = None) -> Semantics:
    """The reference's reading of a configuration; ``calibration`` is the
    sample fit() standardizes from (l2 with ``fit`` only)."""
    d_pad = next_pow2(cfg["dim"])
    mean, inv_std = 0.0, 1.0
    if cfg.get("fit"):
        sample = np.asarray(calibration, np.float64)
        mean, inv_std = float(sample.mean()), 1.0 / max(float(sample.std()), 1e-12)
    return Semantics(cfg["metric"], d_pad, signs(cfg["rotation_seed"], d_pad),
                     mean, inv_std, hadamard(d_pad))


# ---------------------------------------------------------------------------
# float64 host tier
# ---------------------------------------------------------------------------

def prepare64(x: np.ndarray, sem: Semantics) -> np.ndarray:
    x = np.asarray(x, np.float64)
    if sem.metric == "cosine":
        return x / np.maximum(np.linalg.norm(x, axis=1, keepdims=True), 1e-12)
    if sem.metric == "l2":
        return (x - sem.mean) * sem.inv_std
    return x


def rotate64(x: np.ndarray, sem: Semantics) -> np.ndarray:
    """Prepared, padded, signed and Hadamard-rotated rows (float64)."""
    xp = prepare64(x, sem)
    pad = np.zeros((len(xp), sem.d_pad))
    pad[:, :xp.shape[1]] = xp
    return (pad * sem.signs) @ sem.had


def code_options(y: np.ndarray, scale: np.ndarray, bounds: np.ndarray, eps: float):
    """Nominal code, the other admissible code, and where it is admissible."""
    code = np.searchsorted(bounds, y, side="right")
    near = np.argmin(np.abs(y[..., None] - bounds), axis=-1)
    amb = np.abs(y - bounds[near]) < eps * scale[..., None]
    alt = np.where(y >= bounds[near], near, near + 1)
    return code, alt, amb


def _score(q: np.ndarray, deq: np.ndarray, metric: str) -> np.ndarray:
    raw = deq @ q
    if metric == "cosine":
        return raw / np.maximum(np.linalg.norm(deq, axis=-1), 1e-12)
    if metric == "l2":
        return raw - 0.5 * np.sum(deq * deq, axis=-1)
    return raw


def exact_bounds(q_rot: np.ndarray, x_rows: np.ndarray, sem: Semantics):
    """[L, U] of each row's score over every admissible coding, and |deq|."""
    y = rotate64(x_rows, sem)
    scale = np.linalg.norm(prepare64(x_rows, sem), axis=1)
    code, alt, amb = code_options(y, scale, BOUNDARIES4, EPS64)
    deq = CENTROIDS4[code]
    lo = _score(q_rot, deq, sem.metric)
    hi = lo.copy()
    for r in np.flatnonzero(amb.any(axis=1)):
        dims = np.flatnonzero(amb[r])
        if len(dims) > 14:
            raise RuntimeError(f"{len(dims)} ambiguous coordinates in one row")
        combos = np.array(list(itertools.product((0, 1), repeat=len(dims))), bool)
        d = np.repeat(deq[r][None], len(combos), axis=0)
        d[:, dims] = np.where(combos, CENTROIDS4[alt[r, dims]], deq[r, dims])
        s = _score(q_rot, d, sem.metric)
        lo[r], hi[r] = s.min(), s.max()
    return lo, hi, np.linalg.norm(deq, axis=1)


def query_crumbs(q_rot: np.ndarray, scale: np.ndarray):
    """Crumb levels of the rotated queries (float64) and, where a coordinate
    lies within the program's rounding of a 2-bit boundary, the level step
    to the other admissible code (0 elsewhere)."""
    code, alt, amb = code_options(q_rot, scale, BOUNDARIES2, EPS64)
    lq = 2 * code - 3
    step = np.where(amb, np.abs((2 * alt - 3) - lq), 0)
    return lq.astype(np.int8), step.astype(np.int8)


# ---------------------------------------------------------------------------
# float32 device tier
# ---------------------------------------------------------------------------

def _dot(a, b):
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())), precision=HIGHEST,
                               preferred_element_type=jnp.float32)


def _idot(a, b):
    """Exact integer a @ b.T (|entries| <= 9, so int8 operands hold them)."""
    return jax.lax.dot_general(a.astype(jnp.int8), b.astype(jnp.int8),
                               (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.int32)


def _bf16_round(v):
    """float32 -> the nearest bfloat16 value (ties to even), as float32.

    Integer arithmetic on the bits, so that no compiler may fold the
    rounding away as it may a float32 -> bfloat16 -> float32 convert pair."""
    u = jax.lax.bitcast_convert_type(v, jnp.uint32)
    u = (u + jnp.uint32(0x7FFF) + ((u >> 16) & jnp.uint32(1))) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(u, jnp.float32)


def _dot16(a, b):
    """a @ b.T of bfloat16-valued operands, products exact, float32 sums."""
    return jax.lax.dot_general(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                               (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def dot_high(a, b):
    """a @ b.T at ``Precision.HIGH`` (three bf16 passes), one step below the
    float32 at HIGHEST that the configurations state.

    On a TPU this is XLA's own HIGH, the path a program would switch to.
    Elsewhere XLA computes every precision in float32, so the three passes
    are spelled out (hi*hi + hi*lo + lo*hi, split to nearest, float32 sums);
    on the v5e that spelling reads 4 to 20 times nearer float32 than XLA's
    HIGH does (PERF.md)."""
    if jax.default_backend() == "tpu":
        return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                                   precision=jax.lax.Precision.HIGH,
                                   preferred_element_type=jnp.float32)

    def split(v):
        hi = _bf16_round(v)
        return hi, _bf16_round(v - hi)
    ah, al = split(a)
    bh, bl = split(b)
    return _dot16(ah, bh) + _dot16(ah, bl) + _dot16(al, bh)


def _adjust(raw, dn, d2, metric):
    if metric == "cosine":
        return raw / jnp.maximum(dn, 1e-12)[None, :]
    if metric == "l2":
        return raw - 0.5 * d2[None, :]
    return raw


@functools.partial(jax.jit, static_argnames=("metric", "control", "k"))
def _device_chunk(x, q_rot, q_raw, lq, lq_step, sgn, had, mean, inv_std,
                  c4, b4, *, metric, control, k):
    x = x.astype(jnp.float32)
    if metric == "cosine":
        xp = x / jnp.maximum(jnp.linalg.norm(x, axis=1, keepdims=True), 1e-12)
    elif metric == "l2":
        xp = (x - mean) * inv_std
    else:
        xp = x
    scale = jnp.linalg.norm(xp, axis=1)
    xp = jnp.pad(xp, ((0, 0), (0, had.shape[0] - xp.shape[1])))
    y = jnp.dot(xp * sgn, had, precision=HIGHEST)

    code = jnp.zeros(y.shape, jnp.int32)
    dist = jnp.full(y.shape, jnp.inf, jnp.float32)
    near = jnp.zeros(y.shape, jnp.int32)
    for i in range(b4.shape[0]):
        code = code + (y >= b4[i]).astype(jnp.int32)
        di = jnp.abs(y - b4[i])
        near = jnp.where(di < dist, i, near)
        dist = jnp.minimum(dist, di)
    amb = dist < EPS32 * scale[:, None]
    alt = jnp.where(y >= b4[near], near, near + 1)
    deq, deq_alt = c4[code], c4[alt]
    d2 = jnp.sum(deq * deq, axis=1)
    dn = jnp.sqrt(d2)

    s32 = _adjust(_dot(q_rot, deq), dn, d2, metric)
    qn = jnp.linalg.norm(q_rot, axis=1)[:, None]
    step = jnp.where(amb, jnp.abs(deq_alt - deq), 0.0)
    dsq = jnp.sum(jnp.where(amb, jnp.abs(deq_alt ** 2 - deq ** 2), 0.0), axis=1)
    dnum = _dot(jnp.abs(q_rot), step)
    if metric == "cosine":
        size = qn
        m_amb = 2.0 * (dnum / dn[None, :] + jnp.abs(s32) * dsq[None, :] / d2[None, :])
    elif metric == "l2":
        size = qn * dn[None, :] + 0.5 * d2[None, :]
        m_amb = dnum + 0.5 * dsq[None, :]
    else:
        size = qn * dn[None, :]
        m_amb = dnum
    margin = MARGIN32 * size + m_amb

    # Crumb proxies, exact integers: nominal levels plus the widest the
    # ambiguous corpus and query coordinates can move them.
    lc = 2 * (code >> 2) - 3
    dc = jnp.where(amb, 2 * (alt >> 2) - 3 - lc, 0)
    lq32 = lq.astype(jnp.int32)
    base = _idot(lq32, lc)
    qp, qm = jnp.maximum(lq32, 0), jnp.maximum(-lq32, 0)
    dp, dm = jnp.maximum(dc, 0), jnp.maximum(-dc, 0)
    widen_q = _idot(lq_step.astype(jnp.int32), jnp.abs(lc) + jnp.abs(dc))
    p_lo = base - _idot(qp, dm) - _idot(qm, dp) - widen_q
    p_hi = base + _idot(qp, dp) + _idot(qm, dm) + widen_q

    # Exact float scores of the raw vectors, for recall@k only: this
    # chunk's k best, merged over chunks by the caller.
    if metric == "cosine":
        xn = x / jnp.maximum(jnp.linalg.norm(x, axis=1, keepdims=True), 1e-12)
        exact = _dot(q_raw, xn)
    elif metric == "l2":
        exact = 2.0 * _dot(q_raw, x) - jnp.sum(x * x, axis=1)[None, :]
    else:
        exact = _dot(q_raw, x)
    ex_v, ex_i = jax.lax.top_k(exact, k)
    out = dict(s32=s32, margin=margin, p_lo=p_lo, p_hi=p_hi)
    if control:
        out["p_nom"] = base
        out["s_ctrl"] = _adjust(dot_high(q_rot, deq), dn, d2, metric)
    return out, ex_v, ex_i


@functools.partial(jax.jit, static_argnames=("m", "k"))
def _survivor_bounds(p_lo, p_hi, *, m, k):
    """Rows that surely / possibly rank among the m best proxies (ties to the
    lower row), given per-row proxy intervals."""
    def one_side(a, b):
        # rows j with b_j above the m-th largest a, or tied at it and early
        # enough in row order that fewer than m rows can precede them.
        am = jax.lax.top_k(a, m)[0][:, -1:]
        above = jnp.sum((a > am).astype(jnp.int32), axis=1, keepdims=True)
        tie = (a == am).astype(jnp.int32)
        before = jnp.cumsum(tie, axis=1) - tie
        return (b > am) | ((b == am) & (above + before < m))
    sure = one_side(p_hi, p_lo)
    possible = one_side(p_lo, p_hi)
    return sure, possible


@functools.partial(jax.jit, static_argnames=("k",))
def _candidates(s32, margin, sure, *, k):
    lower = jnp.where(sure, s32 - margin, -jnp.inf)
    t = jax.lax.top_k(lower, k)[0][:, -1:]
    return sure & (s32 + margin >= t)


class Verdict(NamedTuple):
    answer_gap: float
    score_error: float
    rank_error: float
    structural: int
    recall: float
    candidates: int
    score_error_mean: float             # mean over every returned answer
    control_gap: Optional[float]
    control_score_error: Optional[float]
    control_score_mean: Optional[float]


def _chunks(n: int, rows: int):
    n_chunks = -(-n // rows)
    size = -(-n // n_chunks)
    return [(i, min(i + size, n)) for i in range(0, n, size)], size


def check(x_dev, q: np.ndarray, got_scores: np.ndarray, got_ids: np.ndarray,
          sem: Semantics, *, k: int, m: Optional[int], control: bool = False,
          chunk_rows: int = 65536) -> Verdict:
    """Judge the answers ``(got_scores, got_ids)`` [nq, k] to queries ``q``.

    ``x_dev`` is the corpus as the program was given it (on the device);
    ``m`` is the cascade's survivor count (None: the full scan).  With
    ``control`` the reference also answers the queries itself at
    ``Precision.HIGH`` and reports how that answer is judged.
    """
    n = int(x_dev.shape[0])
    nq = len(q)
    q_prep = prepare64(q, sem)
    q_rot = rotate64(q, sem)
    q_scale = np.linalg.norm(q_prep, axis=1)
    lq, lq_step = query_crumbs(q_rot, q_scale)
    args = [jnp.asarray(q_rot, jnp.float32), jnp.asarray(q, jnp.float32),
            jnp.asarray(lq), jnp.asarray(lq_step),
            jnp.asarray(sem.signs, jnp.float32),
            jnp.asarray(sem.had, jnp.float32),
            jnp.float32(sem.mean), jnp.float32(sem.inv_std),
            jnp.asarray(CENTROIDS4, jnp.float32), jnp.asarray(BOUNDARIES4, jnp.float32)]

    bounds, size = _chunks(n, chunk_rows)
    parts, ex_v, ex_i = [], [], []
    for a, b in bounds:
        xc = x_dev[a:b]
        if b - a < size:
            xc = jnp.pad(xc, ((0, size - (b - a)), (0, 0)))
        out, v, j = _device_chunk(xc, *args, metric=sem.metric, control=control, k=k)
        parts.append({key: arr[:, :b - a] for key, arr in out.items()})
        v = jnp.where(j < b - a, v, -jnp.inf)      # padded rows never count
        ex_v.append(np.asarray(v))
        ex_i.append(np.asarray(j) + a)
    dev = {key: jnp.concatenate([p[key] for p in parts], axis=1) for key in parts[0]}
    del parts
    ex_v, ex_i = np.concatenate(ex_v, axis=1), np.concatenate(ex_i, axis=1)
    exact_top = np.take_along_axis(
        ex_i, np.argsort(-ex_v, axis=1, kind="stable")[:, :k], axis=1)

    cascade = m is not None and m < n
    if cascade:
        sure, possible = _survivor_bounds(dev["p_lo"], dev["p_hi"], m=m, k=k)
    else:
        sure = possible = jnp.ones((nq, n), bool)
    cand = np.asarray(_candidates(dev["s32"], dev["margin"], sure, k=k))
    possible_h = np.asarray(possible)
    sure_h = np.asarray(sure)

    ctrl_scores = ctrl_ids = None
    if control:
        if cascade:
            surv = jax.lax.top_k(dev["p_nom"], m)[1]
            s = jnp.take_along_axis(dev["s_ctrl"], surv, axis=1)
            v, j = jax.lax.top_k(s, k)
            ctrl_ids, ctrl_scores = np.asarray(jnp.take_along_axis(surv, j, 1)), np.asarray(v)
        else:
            v, i = jax.lax.top_k(dev["s_ctrl"], k)
            ctrl_scores, ctrl_ids = np.asarray(v), np.asarray(i)
    del dev

    answers = [(got_scores, got_ids)] + ([(ctrl_scores, ctrl_ids)] if control else [])
    readings = []
    n_cand = 0
    rows_needed = [np.union1d(np.flatnonzero(cand[i]),
                              np.concatenate([np.asarray(ids[i], np.int64) for _, ids in answers]))
                   for i in range(nq)]
    for i in range(nq):
        if len(rows_needed[i]) > MAX_CANDIDATES:
            raise RuntimeError(f"query {i}: {len(rows_needed[i])} candidate rows")
    for which, (scores, ids) in enumerate(answers):
        worst_s = worst_r = 0.0
        structural = 0
        errs = []
        for i in range(nq):
            ids_i = np.asarray(ids[i], np.int64)
            sc_i = np.asarray(scores[i], np.float64)
            if (ids_i.min() < 0 or ids_i.max() >= n or len(set(ids_i)) != k
                    or not np.all(np.isfinite(sc_i)) or np.any(np.diff(sc_i) > 0)
                    or not possible_h[i, ids_i].all()):
                structural += 1
                continue
            rows = rows_needed[i]
            if which == 0:
                n_cand += len(rows)
            lo, hi, dn = exact_bounds(q_rot[i], np.asarray(x_dev[jnp.asarray(rows)]), sem)
            qn = np.linalg.norm(q_rot[i])
            size = qn if sem.metric == "cosine" else qn * dn.max() + (
                0.5 * dn.max() ** 2 if sem.metric == "l2" else 0.0)
            pos = np.searchsorted(rows, ids_i)
            err = np.maximum(np.maximum(lo[pos] - sc_i, sc_i - hi[pos]), 0.0)
            sure_lo = lo[sure_h[i, rows]]
            t = np.sort(sure_lo)[-k] if len(sure_lo) >= k else -np.inf
            rank = np.maximum(t - hi[pos], 0.0)
            worst_s = max(worst_s, float(err.max() / size))
            errs.append(err / size)
            worst_r = max(worst_r, float(rank.max() / size))
        gap = STRUCTURAL if structural else max(worst_s, worst_r)
        mean = float(np.mean(np.concatenate(errs))) if errs else STRUCTURAL
        readings.append((gap, worst_s, worst_r, structural, mean))
    recall = float(np.mean([len(set(np.asarray(got_ids[i], np.int64)) & set(exact_top[i])) / k
                            for i in range(nq)]))
    gap, s_err, r_err, structural, mean = readings[0]
    ctrl = readings[1] if control else (None,) * 5
    return Verdict(gap, s_err, r_err, structural, recall, n_cand, mean,
                   ctrl[0], ctrl[1], ctrl[4])
