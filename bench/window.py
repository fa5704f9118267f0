"""The measured window: one closed-loop caller, and the arithmetic over it.

The caller sends the next batch only when the previous answer is on the
host (the search returns numpy arrays after the device->host copy), the
embedded deployment: an agent or a RAG pipeline calls ``search`` and waits.
Every call in the window counts: the rate is all queries answered over the
whole window, the percentiles are over every call.
"""

from __future__ import annotations

import contextlib
import math
import time
from typing import Callable, List, NamedTuple, Sequence

import numpy as np


class Call(NamedTuple):
    batch: int          # index into the query pool's batches
    t0: float
    t1: float
    scores: np.ndarray
    ids: np.ndarray


def rate(work: float, seconds: float) -> float:
    if seconds <= 0:
        raise ValueError("an empty window has no rate")
    return work / seconds


def percentile(samples: Sequence[float], p: float) -> float:
    """Nearest-rank p-th percentile over all samples."""
    if not samples:
        raise ValueError("no samples")
    s = sorted(samples)
    return s[max(0, math.ceil(p / 100.0 * len(s)) - 1)]


def run(search: Callable, batches: List[np.ndarray], seconds: float,
        span: Callable = None, clock: Callable[[], float] = time.perf_counter):
    """Call ``search`` on the pool's batches in turn until ``seconds`` have
    passed; the last call started inside the window finishes it.  ``span``
    (a name -> context manager factory) marks host spans for the trace.
    Returns the answered calls, the calls that raised, and the window's
    length in seconds."""
    span = span or (lambda name: contextlib.nullcontext())
    calls: List[Call] = []
    failed: List[BaseException] = []
    start = clock()
    end = start + seconds
    i = 0
    t1 = start
    while t1 < end:
        with span("bench.prepare"):
            j = i % len(batches)
            q = batches[j]
        t0 = clock()
        try:
            with span("bench.search"):
                scores, ids = search(q)
        except Exception as e:  # a failed request counts, the window goes on
            failed.append(e)
            t1 = clock()
            i += 1
            continue
        t1 = clock()
        with span("bench.collect"):
            calls.append(Call(j, t0, t1, scores, ids))
        i += 1
    return calls, failed, t1 - start


def summary(calls: List[Call], window_s: float, batch: int) -> dict:
    lat = [c.t1 - c.t0 for c in calls]
    return {
        "calls": len(calls),
        "queries": len(calls) * batch,
        "window_s": window_s,
        "qps": rate(len(calls) * batch, window_s),
        "latency_p50_ms": 1e3 * percentile(lat, 50),
        "latency_p99_ms": 1e3 * percentile(lat, 99),
        "latency_max_ms": 1e3 * max(lat),
    }


def repeat_mismatches(calls: List[Call]) -> int:
    """Calls whose answer differs in any byte from the first answer to the
    same batch (the paper's within-build determinism)."""
    first = {}
    bad = 0
    for c in calls:
        ref = first.setdefault(c.batch, c)
        if ref is not c and (c.ids.tobytes() != ref.ids.tobytes()
                             or c.scores.tobytes() != ref.scores.tobytes()):
            bad += 1
    return bad
