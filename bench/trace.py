"""Profiler trace of the traced window, and its reduction to metrics.

The reduction works on a plain form of the trace, so that a test can hand it
one: ``{"device": {device_name: [(op, start_ns, dur_ns), ...]},
"host": [(name, start_ns, dur_ns), ...]}``.  ``load`` turns a profiler
``.xplane.pb`` into that form: every event of each device plane's "XLA Ops"
line, under its op name (the HLO text's name before " = "), and every
event of the host planes.
"""

from __future__ import annotations

import collections
from pathlib import Path
from typing import Dict, List, Tuple

import numpy as np

Event = Tuple[str, int, int]

#: Host span around the whole traced window (written by the harness).
WINDOW_SPAN = "bench.window"


def op_name(hlo: str) -> str:
    """``%nibble_dot_raw.1 = f32[...] custom-call(...)`` -> ``nibble_dot_raw.1``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def load(trace_dir: Path) -> dict:
    """The plain form of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(str(files[-1]))
    device: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    device.setdefault(plane.name, []).extend(
                        (op_name(e.name), int(e.start_ns), int(e.duration_ns))
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, int(e.start_ns), int(e.duration_ns))
                            for e in line.events)
    return {"device": device, "host": host}


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merged, sorted [start, end) intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def window_of(trace: dict) -> Tuple[int, int]:
    spans = [(s, s + d) for name, s, d in trace["host"] if name == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"the trace has no {WINDOW_SPAN!r} span")
    return spans[-1]


def summarize(trace: dict) -> dict:
    """Busy and idle time per device inside the window, time per device op,
    and the idle gaps attributed to the innermost host span they fall in."""
    w0, w1 = window_of(trace)
    window_ns = w1 - w0
    busy, ops = [], collections.Counter()
    idle_by = collections.Counter()
    host = [(name, s, d) for name, s, d in trace["host"] if name != WINDOW_SPAN]
    h_start = np.array([s for _, s, _ in host], np.int64)
    h_dur = np.array([d for _, _, d in host], np.int64)
    for events in trace["device"].values():
        ivals = []
        for name, s, d in events:
            s2, e2 = max(s, w0), min(s + d, w1)
            if e2 > s2:
                ivals.append((s2, e2))
                ops[name] += e2 - s2
        merged = union(ivals)
        busy.append(sum(e - s for s, e in merged))
        edges = [w0] + [t for iv in merged for t in iv] + [w1]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge <= gs:
                continue
            mid = (gs + ge) // 2
            inside = np.flatnonzero((h_start <= mid) & (mid < h_start + h_dur))
            label = (host[inside[np.argmin(h_dur[inside])]][0] if len(inside)
                     else "no host span")
            idle_by[label] += ge - gs
    n_dev = max(len(busy), 1)
    busy_ns = sum(busy) / n_dev
    return {
        "window_s": window_ns / 1e9,
        "busy_s": busy_ns / 1e9,
        "devices": len(busy),
        "ops_s": {k: v / 1e9 / n_dev for k, v in ops.items()},
        "idle_s": {k: v / 1e9 / n_dev for k, v in idle_by.items()},
    }


def kernel_events(trace: dict, kernel: str) -> List[Event]:
    """Device events inside the window of the op ``kernel`` (``kernel`` or
    ``kernel.<n>``: the name XLA gives the kernel's custom call)."""
    w0, w1 = window_of(trace)
    return [(name, s, d) for events in trace["device"].values()
            for name, s, d in events
            if (name == kernel or name.startswith(kernel + ".")) and w0 <= s < w1]


def breakdown(summary: dict) -> dict:
    """The ten device ops that took most time and the ten host spans that
    held the longest idle time, in seconds."""
    def top(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {"device_ops": top(summary["ops_s"]), "idle_gaps": top(summary["idle_s"])}
