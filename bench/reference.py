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

Work is split in two tiers so that tens of millions of rows fit in the
run's time.  The device tier rotates and codes every row in float32
(HIGHEST), scores it with a generous error margin, and computes the integer
proxy intervals exactly; it only nominates candidate rows.  It streams the
corpus chunk by chunk, on the devices that make its blocks, so no device and
not the host ever holds the corpus or an [nq, n] array.  The host tier
recomputes the candidates and the returned rows exactly, in float64.
"""

from __future__ import annotations

import collections
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


@functools.partial(jax.jit, static_argnames=("top", "k"))
def _thresholds(out, valid, a, *, top, k):
    """Pass 1 over one chunk: the k best lower score bounds (the full scan's
    threshold) and the ``top`` best of each proxy bound, with their rows,
    ties to the lower row.  Columns from ``valid`` on are padding."""
    cols = jnp.arange(out["s32"].shape[1])[None, :]
    ok = cols < valid
    rows = (a + cols).astype(jnp.int32)
    res = {}
    if "p_hi" not in out:
        res["lower"] = _top(jnp.where(ok, out["s32"] - out["margin"], -jnp.inf), rows, k)
    for key in ("p_hi", "p_lo", "p_nom"):
        if key in out:
            res[key] = _top(jnp.where(ok, out[key], jnp.iinfo(jnp.int32).min), rows, top)
    if "s_ctrl" in out:
        res["s_ctrl"] = _top(jnp.where(ok, out["s_ctrl"], -jnp.inf), rows, k)
    return res


def _top(v, rows, m):
    v, i = jax.lax.top_k(v, min(m, v.shape[1]))
    return v, jnp.take_along_axis(jnp.broadcast_to(rows, (v.shape[0], rows.shape[1])), i, 1)


@functools.partial(jax.jit, static_argnames=("m",))
def _merge(v0, r0, v1, r1, *, m):
    """The m best of two lists, each best first with ties to the lower row,
    where every row of the first lies below every row of the second."""
    v, i = jax.lax.top_k(jnp.concatenate([v0, v1], 1), min(m, v0.shape[1] + v1.shape[1]))
    return v, jnp.take_along_axis(jnp.concatenate([r0, r1], 1), i, 1)


def _one_side(b, am, cut, rows):
    """Rows whose b lies above ``am``, the m-th best of a over all rows, or
    at it and no later than ``cut``, the row in m-th place when a is ranked
    with ties to the lower row: no more than m rows can then rank before
    them, as a count of the tied rows before each row in row order says."""
    return (b > am) | ((b == am) & (rows <= cut))


@functools.partial(jax.jit, static_argnames=("k",))
def _sure_lower(out, valid, a, thr, *, k):
    """Pass 2 (cascade) over one chunk: the k best lower score bounds among
    rows that surely survive."""
    cols = jnp.arange(out["s32"].shape[1])[None, :]
    rows = (a + cols).astype(jnp.int32)
    sure = (cols < valid) & _one_side(out["p_lo"], thr["am_hi"], thr["cut_hi"], rows)
    return _top(jnp.where(sure, out["s32"] - out["margin"], -jnp.inf), rows, k)


@jax.jit
def _gather_ctrl(out, valid, a, surv, best):
    """Pass 2 (cascade control) over one chunk: the control's scores at its
    survivor rows that lie in this chunk, kept in ``best``."""
    size = out["s_ctrl"].shape[1]
    inside = (surv >= a) & (surv < a + valid)
    g = jnp.take_along_axis(out["s_ctrl"], jnp.clip(surv - a, 0, size - 1), 1)
    return jnp.maximum(best, jnp.where(inside, g, -jnp.inf))


@jax.jit
def _candidates(out, valid, a, t, thr, ids):
    """Last pass over one chunk: the rows whose upper score bound reaches
    the query's threshold ``t`` among rows that surely survive, as little-
    endian bits, and whether each answered row surely / possibly survives."""
    size = out["s32"].shape[1]
    cols = jnp.arange(size)[None, :]
    rows = (a + cols).astype(jnp.int32)
    sure = cols < valid
    inside = (ids >= a) & (ids < a + valid)
    local = jnp.clip(ids - a, 0, size - 1)
    if thr is None:
        sure_at = possible_at = inside
    else:
        sure = sure & _one_side(out["p_lo"], thr["am_hi"], thr["cut_hi"], rows)
        lo_at = jnp.take_along_axis(out["p_lo"], local, 1)
        hi_at = jnp.take_along_axis(out["p_hi"], local, 1)
        sure_at = inside & _one_side(lo_at, thr["am_hi"], thr["cut_hi"], ids)
        possible_at = inside & _one_side(hi_at, thr["am_lo"], thr["cut_lo"], ids)
    cand = sure & (out["s32"] + out["margin"] >= t)
    return jnp.packbits(cand, axis=1, bitorder="little"), sure_at, possible_at


class _Best:
    """The m best values per query with their rows, best first, ties to the
    lower row: one running list per device (its chunks come in row order),
    merged on the host."""

    def __init__(self, m: int):
        self.m, self.by_device = m, {}

    def add(self, device, vr) -> None:
        cur = self.by_device.get(device)
        self.by_device[device] = vr if cur is None else _merge(*cur, *vr, m=self.m)

    def result(self):
        v = np.concatenate([np.asarray(v) for v, _ in self.by_device.values()], 1)
        r = np.concatenate([np.asarray(r) for _, r in self.by_device.values()], 1)
        order = np.lexsort((r, -v.astype(np.float64)), axis=1)[:, :self.m]
        return np.take_along_axis(v, order, 1), np.take_along_axis(r, order, 1)


def _bit_rows(packed: np.ndarray, a: int):
    """(query, row) of every set bit of little-endian packed rows from ``a``."""
    qi, byte = np.nonzero(packed)
    bits = np.unpackbits(packed[qi, byte][:, None], axis=1, bitorder="little")
    qq, bb = np.nonzero(bits)
    return qi[qq], a + 8 * byte[qq].astype(np.int64) + bb


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


def chunks(source, chunk_rows: int):
    """([(a, b)], size): row ranges of at most ``chunk_rows`` rows of equal
    padded ``size``, each inside one block of ``source`` (a ``datagen.Rows``)."""
    b0 = source.block_rows
    size = -(-b0 // -(-b0 // chunk_rows))
    return [(j * b0 + a, min(j * b0 + a + size, (j + 1) * b0, source.n))
            for j in range(source.n_blocks) for a in range(0, b0, size)
            if j * b0 + a < source.n], size


def _pass(source, bounds, size, run, done=None):
    """``run(device, a, b, x)`` on every chunk [a, b) of rows ``x``, padded to
    ``size`` rows, on its block's device, then ``done(a, result)`` in chunk
    order.  A few chunks are in flight per device: queued work holds memory."""
    blk = None
    pending = collections.deque()

    def finish():
        a, res = pending.popleft()
        jax.block_until_ready(res)
        if done is not None:
            done(a, res)
    for a, b in bounds:
        j = a // source.block_rows
        if blk is None or blk[0] != j:
            blk = (j, source.block(j))
        la = a - j * source.block_rows
        xc = blk[1][la:la + (b - a)]
        if b - a < size:
            xc = jnp.pad(xc, ((0, size - (b - a)), (0, 0)))
        pending.append((a, run(source.device_of(j), a, b, xc)))
        if len(pending) > 2 * len(source.devices):
            finish()
    while pending:
        finish()


def check(source, q: np.ndarray, got_scores: np.ndarray, got_ids: np.ndarray,
          sem: Semantics, *, k: int, m: Optional[int], control: bool = False,
          chunk_rows: int = 65536) -> Verdict:
    """Judge the answers ``(got_scores, got_ids)`` [nq, k] to queries ``q``.

    ``source`` (a ``datagen.Rows``) makes the corpus as the program was
    given it, chunk by chunk on the device; ``m`` is the cascade's survivor
    count (None: the full scan).  With ``control`` the reference also
    answers the queries itself at ``Precision.HIGH`` and reports how that
    answer is judged.

    The device tier streams the corpus in chunks and keeps per query only
    order statistics and sparse row lists, never an [nq, n] array: pass 1
    the k best lower score bounds and exact scores (recall), and the m best
    of each proxy bound; in the cascade, pass 2 the k best lower bounds
    among rows that surely survive (and the control's survivor scores); a
    last pass the candidate rows.  The order statistics are exact, so the
    verdict is that of one pass over all rows at once.
    """
    n = int(source.n)
    nq = len(q)
    cascade = m is not None and m < n
    q_prep = prepare64(q, sem)
    q_rot = rotate64(q, sem)
    q_scale = np.linalg.norm(q_prep, axis=1)
    lq, lq_step = query_crumbs(q_rot, q_scale)
    host_args = [q_rot.astype(np.float32), np.asarray(q, np.float32), lq, lq_step,
                 sem.signs.astype(np.float32), sem.had.astype(np.float32),
                 np.float32(sem.mean), np.float32(sem.inv_std),
                 CENTROIDS4.astype(np.float32), BOUNDARIES4.astype(np.float32)]
    on_device = {}

    def chunk_out(device, xc):
        if device not in on_device:
            on_device[device] = jax.device_put(host_args, device)
        return _device_chunk(xc, *on_device[device], metric=sem.metric,
                             control=control, k=k)

    bounds, size = chunks(source, chunk_rows)
    top = m if cascade else k
    best = {key: _Best(top) for key in ("p_hi", "p_lo", "p_nom")}
    best.update(lower=_Best(k), s_ctrl=_Best(k), exact=_Best(k))

    def pass1(device, a, b, xc):
        out, v, j = chunk_out(device, xc)
        v = jnp.where(j < b - a, v, -jnp.inf)      # padded rows never count
        best["exact"].add(device, (v, j + a))
        keys = ("p_hi", "p_lo", "p_nom") if cascade else ("s_ctrl",)
        res = _thresholds({key: out[key] for key in ("s32", "margin") + keys if key in out},
                          b - a, a, top=top, k=k)
        for key, vr in res.items():
            best[key].add(device, vr)
        return res
    _pass(source, bounds, size, pass1)
    exact_top = best["exact"].result()[1]

    thr = None
    ctrl_scores = ctrl_ids = None
    if cascade:
        thr = {}
        for side, key in (("hi", "p_hi"), ("lo", "p_lo")):
            v, r = best[key].result()
            thr["am_" + side], thr["cut_" + side] = v[:, -1:], r[:, -1:]
        surv = best["p_nom"].result()[1] if control else None
        lower, ctrl_best = _Best(k), {}

        def pass2(device, a, b, xc):
            out = chunk_out(device, xc)[0]
            res = _sure_lower(out, b - a, a, jax.device_put(thr, device), k=k)
            lower.add(device, res)
            if control:
                ctrl_best[device] = _gather_ctrl(
                    out, b - a, a, jax.device_put(surv, device),
                    ctrl_best.get(device, jax.device_put(
                        np.full(surv.shape, -np.inf, np.float32), device)))
            return res
        _pass(source, bounds, size, pass2)
        t = lower.result()[0][:, -1:]
        if control:
            s = np.max(np.stack([np.asarray(v) for v in ctrl_best.values()]), axis=0)
            v, j = jax.lax.top_k(jnp.asarray(s), k)
            ctrl_ids = np.take_along_axis(surv, np.asarray(j), 1).astype(np.int64)
            ctrl_scores = np.asarray(v)
    else:
        t = best["lower"].result()[0][:, -1:]
        if control:
            ctrl_scores, ctrl_ids = best["s_ctrl"].result()
            ctrl_ids = ctrl_ids.astype(np.int64)

    answers = [(got_scores, got_ids)] + ([(ctrl_scores, ctrl_ids)] if control else [])
    all_ids = np.concatenate([np.asarray(ids, np.int64) for _, ids in answers], axis=1)
    ids_dev = np.clip(all_ids, -1, n).astype(np.int32)
    cand_rows = [[] for _ in range(nq)]
    n_found = np.zeros(nq, np.int64)
    sure_at = np.zeros(all_ids.shape, bool)
    possible_at = np.zeros(all_ids.shape, bool)

    def collect(a, res):
        bits, s_at, p_at = (np.asarray(r) for r in res)
        qi, rows = _bit_rows(bits, a)
        np.add.at(n_found, qi, 1)
        if n_found.max() > MAX_CANDIDATES:
            raise RuntimeError(f"query {int(n_found.argmax())}: more than "
                               f"{MAX_CANDIDATES} candidate rows")
        for i in np.unique(qi):
            cand_rows[i].append(rows[qi == i])
        sure_at[...] |= s_at
        possible_at[...] |= p_at

    def last(device, a, b, xc):
        out = chunk_out(device, xc)[0]
        return _candidates(out, b - a, a, jax.device_put(t, device),
                           None if thr is None else jax.device_put(thr, device),
                           jax.device_put(ids_dev, device))
    _pass(source, bounds, size, last, collect)

    readings = []
    n_cand = 0
    rows_needed = [np.union1d(np.concatenate(cand_rows[i] or [np.zeros(0, np.int64)]),
                              all_ids[i][(all_ids[i] >= 0) & (all_ids[i] < n)])
                   for i in range(nq)]
    for i in range(nq):
        if len(rows_needed[i]) > MAX_CANDIDATES:
            raise RuntimeError(f"query {i}: {len(rows_needed[i])} candidate rows")
    fetch = np.unique(np.concatenate(rows_needed))
    x_fetch = source.take(fetch)
    sure_rows = [np.union1d(np.concatenate(cand_rows[i] or [np.zeros(0, np.int64)]),
                            all_ids[i][sure_at[i]]) for i in range(nq)]
    col = 0
    for which, (scores, ids) in enumerate(answers):
        worst_s = worst_r = 0.0
        structural = 0
        errs = []
        for i in range(nq):
            ids_i = np.asarray(ids[i], np.int64)
            sc_i = np.asarray(scores[i], np.float64)
            if (ids_i.min() < 0 or ids_i.max() >= n or len(set(ids_i)) != k
                    or not np.all(np.isfinite(sc_i)) or np.any(np.diff(sc_i) > 0)
                    or not possible_at[i, col:col + k].all()):
                structural += 1
                continue
            rows = rows_needed[i]
            if which == 0:
                n_cand += len(rows)
            lo, hi, dn = exact_bounds(q_rot[i], x_fetch[np.searchsorted(fetch, rows)], sem)
            qn = np.linalg.norm(q_rot[i])
            size = qn if sem.metric == "cosine" else qn * dn.max() + (
                0.5 * dn.max() ** 2 if sem.metric == "l2" else 0.0)
            pos = np.searchsorted(rows, ids_i)
            err = np.maximum(np.maximum(lo[pos] - sc_i, sc_i - hi[pos]), 0.0)
            sure_lo = lo[np.isin(rows, sure_rows[i])]
            t_i = np.sort(sure_lo)[-k] if len(sure_lo) >= k else -np.inf
            rank = np.maximum(t_i - hi[pos], 0.0)
            worst_s = max(worst_s, float(err.max() / size))
            errs.append(err / size)
            worst_r = max(worst_r, float(rank.max() / size))
        gap = STRUCTURAL if structural else max(worst_s, worst_r)
        mean = float(np.mean(np.concatenate(errs))) if errs else STRUCTURAL
        readings.append((gap, worst_s, worst_r, structural, mean))
        col += k
    recall = float(np.mean([len(set(np.asarray(got_ids[i], np.int64)) & set(exact_top[i])) / k
                            for i in range(nq)]))
    gap, s_err, r_err, structural, mean = readings[0]
    ctrl = readings[1] if control else (None,) * 5
    return Verdict(gap, s_err, r_err, structural, recall, n_cand, mean,
                   ctrl[0], ctrl[1], ctrl[4])
