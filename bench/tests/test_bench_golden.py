"""Readings of configurations without ``data.block_rows``, pinned.

``golden_readings.json`` holds what the harness read before the corpus could
be made block by block and before the reference streamed it: the sha256 of a
corpus and a query pool, and every field of the verdict of ``reference.check``
on answers made here by plain float64 code (full scan and crumb cascade, with
the control on and off, answers with a swapped and an outside row, and a
corpus of near-duplicate rows with hundreds of candidates).  The
streamed reference keeps exact order statistics, so every reading is equal to
the last bit.
"""

import hashlib
import json
from pathlib import Path

import numpy as np
import pytest

from bench import datagen, reference

GOLDEN = json.loads((Path(__file__).parent / "golden_readings.json").read_text())
SEED = 2**31 + 12345
K = 10
COSINE_DATA = {"n_clusters": 8, "center": "unit_normal", "center_scale": 1.0,
               "noise": 0.25, "noise_spread": [0.3, 1.5], "nonneg": False,
               "magnitude": [1.0, 1.0], "query_noise": 0.15}
L2_DATA = {"n_clusters": 8, "center": "uniform", "center_scale": 0.1, "noise": 0.03,
           "noise_spread": [0.5, 1.5], "nonneg": True, "magnitude": [0.5, 1.5]}
DENSE_DATA = dict(COSINE_DATA, noise=0.004, n_clusters=4)


def sha(a) -> str:
    return hashlib.sha256(np.ascontiguousarray(np.asarray(a)).tobytes()).hexdigest()


def nominal(x, q, sem, k, m):
    """The k best rows by the nominal 4-bit score (float64), among the m
    best by the nominal crumb proxy in the cascade; ties to the lower row."""
    y = reference.rotate64(np.asarray(x), sem)
    code = np.searchsorted(reference.BOUNDARIES4, y, side="right")
    deq = reference.CENTROIDS4[code]
    q_rot = reference.rotate64(q, sem)
    s = np.stack([reference._score(qr, deq, sem.metric) for qr in q_rot])
    if m is not None:
        lq = 2 * np.searchsorted(reference.BOUNDARIES2, q_rot, side="right") - 3
        proxy = lq @ (2 * (code >> 2) - 3).T
        rows = np.broadcast_to(np.arange(len(y)), proxy.shape)
        keep = np.lexsort((rows, -proxy), axis=1)[:, :m]
        s_keep = np.take_along_axis(s, keep, 1)
        ids = np.take_along_axis(keep, np.argsort(-s_keep, axis=1, kind="stable")[:, :k], 1)
    else:
        ids = np.argsort(-s, axis=1, kind="stable")[:, :k]
    return np.take_along_axis(s, ids, 1).astype(np.float32), ids.astype(np.int64)


def test_corpus_and_query_pool_are_unchanged():
    rows = datagen.Rows(COSINE_DATA, SEED, 600, 100)
    assert sha(rows.block(0)) == GOLDEN["corpus"]["tiny"]
    assert sha(datagen.corpus(COSINE_DATA, SEED, 600, 100)) == GOLDEN["corpus"]["tiny"]
    assert sha(datagen.query_pool(rows, SEED, 48, COSINE_DATA["query_noise"])) == \
        GOLDEN["query_pool"]["tiny"]
    assert sha(datagen.Rows(L2_DATA, 7, 700, 100).block(0)) == GOLDEN["corpus"]["l2"]


CASES = {  # name: (metric, data, corpus seed, pool seed, pool noise, m, altered)
    "cosine-scan": ("cosine", COSINE_DATA, 7, 9, 0.01, None, False),
    "l2-scan": ("l2", L2_DATA, 7, 9, 0.01, None, False),
    "l2-cascade40": ("l2", L2_DATA, 7, 9, 0.01, 40, False),
    "cosine-cascade20": ("cosine", COSINE_DATA, 7, 9, 0.01, 20, False),
    "l2-cascade40-altered": ("l2", L2_DATA, 7, 9, 0.01, 40, True),
    # Near-duplicate rows: hundreds of candidates inside the margins.
    "cosine-dense-scan": ("cosine", DENSE_DATA, 11, 12, 0.002, None, False),
    "cosine-dense-cascade40": ("cosine", DENSE_DATA, 11, 12, 0.002, 40, False),
}


@pytest.mark.parametrize("control", [False, True], ids=["", "control"])
@pytest.mark.parametrize("case", list(CASES))
def test_verdict_is_unchanged(case, control):
    metric, data, seed, pool_seed, noise, m, altered = CASES[case]
    rows = datagen.Rows(data, seed, 700, 100)
    calib = datagen.sample(data, 8, 512, 100) if metric == "l2" else None
    q = datagen.query_pool(rows, pool_seed, 16, noise)
    sem = reference.semantics({"dim": 100, "rotation_seed": 1836019297, "metric": metric,
                               "fit": calib is not None}, calib)
    scores, ids = nominal(rows.block(0), q, sem, K, m)
    if altered:
        ids[3, 0], ids[3, 1] = ids[3, 1], ids[3, 0]
        ids[5, -1] = np.setdiff1d(np.arange(700), ids[5])[-1]
    v = reference.check(rows, q, scores, ids, sem, k=K, m=m, control=control,
                        chunk_rows=256)
    want = GOLDEN["verdicts"][case + ("-control" if control else "")]
    assert {f: repr(getattr(v, f)) for f in v._fields} == want


@pytest.mark.parametrize("m", [None, 40], ids=["scan", "cascade"])
def test_verdict_does_not_depend_on_chunking(m):
    """Block-keyed, with a short last block and short last chunks of a
    block: the order statistics are exact, so the chunking changes nothing."""
    data = dict(DENSE_DATA, block_rows=301)
    rows = datagen.Rows(data, 11, 1000, 100)
    q = datagen.query_pool(rows, 12, 16, 0.002)
    sem = reference.semantics({"dim": 100, "rotation_seed": 1836019297, "metric": "cosine",
                               "fit": None})
    x = np.concatenate([np.asarray(rows.block(j)) for j in range(rows.n_blocks)])[:1000]
    scores, ids = nominal(x, q, sem, K, m)
    ids[2, 0], ids[2, 1] = ids[2, 1], ids[2, 0]
    got = [reference.check(rows, q, scores, ids, sem, k=K, m=m, control=True, chunk_rows=c)
           for c in (1000, 128, 37)]
    assert got[0] == got[1] == got[2]
    assert got[0].candidates > 16 * K and got[0].control_gap > 0
