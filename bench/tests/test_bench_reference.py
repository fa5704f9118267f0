"""The float64 reference against the engine at a tiny size, kernels in
interpret mode: cosine, l2 with fit()'s standardization, and the crumb
cascade.  The engine's answers read as correct, the Precision.HIGH control's and an
altered answer's do not."""

import numpy as np
import pytest

from bench import datagen, reference
from repro.core import MonaVec
from repro.core import quantize as qz

K = 10
CFG = {"dim": 100, "rotation_seed": 1836019297}
DATA = {"n_clusters": 8, "center": "uniform", "center_scale": 0.1, "noise": 0.03,
        "noise_spread": [0.5, 1.5], "nonneg": True, "magnitude": [0.5, 1.5]}


def _case(metric, coarse):
    x = datagen.corpus(DATA, 7, 700, CFG["dim"])
    calib = np.asarray(datagen.corpus(DATA, 8, 512, CFG["dim"])) if metric == "l2" else None
    std = MonaVec.fit(calib) if calib is not None else None
    idx = MonaVec.build(x, metric=metric, seed=CFG["rotation_seed"], std=std, coarse=coarse)
    q = datagen.query_pool(x, 9, 16, 0.01)
    knobs = {"rescore_mult": 4} if coarse else {}
    scores, ids = idx.search(q, K, use_kernel=True, interpret=True, **knobs)
    sem = reference.semantics(dict(CFG, metric=metric, fit=calib is not None), calib)
    return x, q, scores, ids.astype(np.int64), sem, (4 * K if coarse else None), idx


CASES = [("cosine", None), ("l2", None), ("l2", "crumb")]


@pytest.mark.parametrize("metric, coarse", CASES)
def test_engine_reads_correct_and_control_does_not(metric, coarse):
    x, q, scores, ids, sem, m, _ = _case(metric, coarse)
    v = reference.check(x, q, scores, ids, sem, k=K, m=m, control=True,
                        chunk_rows=256)
    assert v.structural == 0
    assert v.answer_gap < 1e-6
    assert v.control_gap > 3 * max(v.answer_gap, 1e-7)


@pytest.mark.parametrize("metric, coarse", CASES)
def test_an_altered_answer_is_caught(metric, coarse):
    x, q, scores, ids, sem, m, _ = _case(metric, coarse)
    swapped = ids.copy()
    swapped[3, 0], swapped[3, 1] = ids[3, 1], ids[3, 0]     # scores now disagree
    assert reference.check(x, q, scores, swapped, sem, k=K, m=m).answer_gap > 1e-3
    outsider = ids.copy()
    far = np.setdiff1d(np.arange(700), ids[5])[-1]
    outsider[5, -1] = far
    scores2 = scores.copy()
    v = reference.check(x, q, scores2, outsider, sem, k=K, m=m)
    assert v.answer_gap > 1e-3


@pytest.mark.parametrize("metric", ["cosine", "l2"])
def test_reference_codes_are_the_programs_off_the_boundaries(metric):
    x, _, _, _, sem, _, idx = _case(metric, None)
    xh = np.asarray(x)
    y = reference.rotate64(xh, sem)
    code, _, amb = reference.code_options(
        y, np.linalg.norm(reference.prepare64(xh, sem), axis=1),
        reference.BOUNDARIES4, reference.EPS64)
    prog = np.asarray(qz.unpack_4bit(idx.backend.enc.packed))
    assert np.array_equal(code[~amb], prog[~amb])
