"""The device's idle time in a traced window, split by the program's spans.

The program writes its engine phases into the profiler's trace as
``monavec.<phase>`` host spans, on the device's clock.  Each call of the
window (a ``bench.search`` span) is split by them into four parts:

- ``entry``: inside ``bench.search`` but outside every engine phase (the
  searcher handle, tenancy, ``MonaVec.search``);
- ``engine``: ``monavec.prepare``, ``monavec.plan_lookup``, ``monavec.finish``;
- ``dispatch``: ``monavec.execute`` (the stages' enqueue and the eager ops
  between stages; the ``monavec.stage:*`` spans nest inside it and count
  once);
- ``sync``: ``monavec.sync`` (waiting for the answer and copying it to the
  host).

Time in which no "XLA Ops" event runs on a device is idle, and each idle
instant inside a call goes to the part it falls in.  Idle time outside every
call is the harness's own (``outside``); the five add up to the window's
idle time.  A span that lies inside two parts' spans counts once, in the
first of sync, dispatch, engine.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from bench import trace as trace_mod

Intervals = List[Tuple[int, int]]

SEARCH_SPAN = "bench.search"
PREFIX = "monavec."
#: Engine phases by part, in the order a doubly covered instant is claimed.
PARTS = {
    "sync": ("monavec.sync",),
    "dispatch": ("monavec.execute",),
    "engine": ("monavec.prepare", "monavec.plan_lookup", "monavec.finish"),
}


def intersect(a: Intervals, b: Intervals) -> Intervals:
    """Intersection of two merged, sorted interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a: Intervals, b: Intervals) -> Intervals:
    """``a`` less ``b``; both merged and sorted."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > s:
                out.append((s, b[k][0]))
            s = max(s, b[k][1])
            k += 1
        if s < e:
            out.append((s, e))
    return out


def length(a: Intervals) -> int:
    return sum(e - s for s, e in a)


def idle_parts(trace: dict) -> Optional[Dict[str, float]]:
    """Idle nanoseconds per device (the mean over devices) in each part and
    outside every call, the window's idle total, and the number of calls.
    None where the trace has no ``monavec.*`` span, no call or no device."""
    w0, w1 = trace_mod.window_of(trace)
    host = trace["host"]
    if not trace["device"] or not any(n.startswith(PREFIX) for n, _, _ in host):
        return None
    calls = sum(1 for n, s, _ in host if n == SEARCH_SPAN and w0 <= s < w1)
    if not calls:
        return None

    def spans(names) -> Intervals:
        return trace_mod.union([(max(s, w0), min(s + d, w1)) for n, s, d in host
                                if n in names and s < w1 and s + d > w0])

    search = spans((SEARCH_SPAN,))
    where: Dict[str, Intervals] = {}
    claimed: Intervals = []
    for part, names in PARTS.items():
        where[part] = subtract(intersect(spans(names), search), claimed)
        claimed = trace_mod.union(claimed + where[part])
    where["entry"] = subtract(search, claimed)

    out = dict.fromkeys(list(where) + ["idle"], 0.0)
    for events in trace["device"].values():
        busy = trace_mod.union([(max(s, w0), min(s + d, w1)) for _, s, d in events
                                if min(s + d, w1) > max(s, w0)])
        idle = subtract([(w0, w1)], busy)
        out["idle"] += length(idle)
        for part, ivals in where.items():
            out[part] += length(intersect(idle, ivals))
    n_dev = len(trace["device"])
    out = {k: v / n_dev for k, v in out.items()}
    out["outside"] = out["idle"] - sum(out[p] for p in where)
    out["calls"] = calls
    return out


def idle_ms_per_call(trace: dict, part: str) -> Optional[float]:
    """A per-layer reading: idle milliseconds per call in ``part``."""
    parts = idle_parts(trace)
    return None if parts is None else parts[part] / parts["calls"] / 1e6
