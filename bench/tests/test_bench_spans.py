"""The device's idle time split by the program's spans, on a hand-built trace."""

import importlib.util

import pytest

from bench import spans, trace
from bench.tests.conftest import ROOT

# Window 0..2000 ns.  Call 1 [100, 900) runs every engine phase, with two
# stage spans nested inside execute; call 2 [1000, 1500) has no engine span.
# Device ops: [350, 450) in execute, [520, 700) in sync, [1200, 1300) in
# call 2, and one straddling the window's end.
TRACE = {
    "device": {"/device:TPU:0": [
        ("nibble_dot_raw.1", 350, 100), ("fusion", 520, 180),
        ("fusion", 1200, 100), ("top_k", 1900, 200)]},
    "host": [("bench.window", 0, 2000),
             ("bench.prepare", 0, 100), ("bench.search", 100, 800),
             ("monavec.tenant_search", 120, 760),
             ("monavec.prepare", 150, 100), ("monavec.plan_lookup", 250, 50),
             ("monavec.execute", 300, 200),
             ("monavec.stage:rotate", 320, 60), ("monavec.stage:scan", 400, 80),
             ("monavec.sync", 500, 300), ("monavec.finish", 800, 50),
             ("bench.collect", 900, 100), ("bench.search", 1000, 500)],
}
# Idle ns: entry [100,150) + [850,900) + call 2's 400; engine 100 + 50 + 50;
# dispatch [300,350) + [450,500); sync [500,520) + [700,800); outside
# [0,100) + [900,1000) + [1500,1900).
EXPECTED = {"entry": 500, "engine": 200, "dispatch": 100, "sync": 120, "outside": 600}
METRICS = [f"idle_{part}_ms.{kind}" for part in ("entry", "engine", "dispatch", "sync")
           for kind in ("batch", "query")]


def _reader(name):
    spec = importlib.util.spec_from_file_location(name, ROOT / "bench" / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_interval_arithmetic():
    a = [(0, 10), (20, 30), (40, 50)]
    b = [(5, 25), (45, 60)]
    assert spans.intersect(a, b) == [(5, 10), (20, 25), (45, 50)]
    assert spans.subtract(a, b) == [(0, 5), (25, 30), (40, 45)]
    assert spans.subtract([(0, 10)], [(2, 3), (4, 5), (9, 12)]) == [(0, 2), (3, 4), (5, 9)]
    assert spans.subtract(a, []) == a and spans.intersect(a, []) == []
    assert spans.length(a) == 30


def test_parts_of_each_call():
    p = spans.idle_parts(TRACE)
    assert p["calls"] == 2
    for part, ns in EXPECTED.items():
        assert p[part] == pytest.approx(ns), part


def test_parts_add_up_to_the_windows_idle_time():
    p = spans.idle_parts(TRACE)
    s = trace.summarize(TRACE)
    idle_ns = (s["window_s"] - s["busy_s"]) * 1e9
    assert p["idle"] == pytest.approx(idle_ns)
    assert sum(p[part] for part in EXPECTED) == pytest.approx(idle_ns)


def test_nested_stage_spans_count_once():
    """Stage spans, and an execute nested in another, add nothing to the
    part of the execute around them."""
    tr = dict(TRACE, host=TRACE["host"] + [("monavec.execute", 310, 150),
                                           ("monavec.stage:finalize", 480, 15)])
    assert spans.idle_parts(tr) == spans.idle_parts(TRACE)


def test_doubly_covered_time_counts_once():
    # A sync span inside execute claims its time from dispatch.
    tr = dict(TRACE, host=TRACE["host"] + [("monavec.sync", 300, 40)])
    p = spans.idle_parts(tr)
    assert p["sync"] == pytest.approx(EXPECTED["sync"] + 40)
    assert p["dispatch"] == pytest.approx(EXPECTED["dispatch"] - 40)
    assert p["outside"] == pytest.approx(EXPECTED["outside"])


def test_a_call_without_engine_spans_is_all_entry():
    """Call 2 has no engine span: all its idle time is entry.  Without its
    ``bench.search`` span the same time is the harness's own."""
    full = spans.idle_parts(TRACE)
    tr = dict(TRACE, host=[e for e in TRACE["host"] if e != ("bench.search", 1000, 500)])
    p = spans.idle_parts(tr)
    assert p["calls"] == full["calls"] - 1
    assert full["entry"] - p["entry"] == pytest.approx(400)
    assert p["outside"] - full["outside"] == pytest.approx(400)
    for part in ("engine", "dispatch", "sync"):
        assert p[part] == full[part]


def test_two_devices_give_the_mean():
    tr = dict(TRACE, device=dict(TRACE["device"], **{"/device:TPU:1": []}))
    p = spans.idle_parts(tr)
    # The second device idles the whole window: call 1 adds its busy 280 ns
    # to execute and sync, call 2 its 100 ns to entry, the end 100 ns outside.
    assert p["dispatch"] == pytest.approx((100 + 200) / 2)
    assert p["sync"] == pytest.approx((120 + 300) / 2)
    assert p["entry"] == pytest.approx((500 + 600) / 2)
    assert p["outside"] == pytest.approx((600 + 700) / 2)


@pytest.mark.parametrize("name", METRICS)
def test_each_reader(name):
    part = name.split("_")[1]
    ctx = {"trace": TRACE}
    assert _reader(name)(ctx) == pytest.approx(EXPECTED[part] / 2 / 1e6)


@pytest.mark.parametrize("case", ["no_engine_spans", "no_device", "no_calls"])
def test_readers_find_nothing_to_read(case):
    """A program that writes no ``monavec.*`` span into the profiler's
    trace, a trace without a device, or a window without a call."""
    if case == "no_engine_spans":
        tr = dict(TRACE, host=[e for e in TRACE["host"] if not e[0].startswith("monavec.")])
    elif case == "no_device":
        tr = dict(TRACE, device={})
    else:
        tr = dict(TRACE, host=[e for e in TRACE["host"] if e[0] != "bench.search"])
    assert spans.idle_parts(tr) is None
    for name in METRICS:
        assert _reader(name)({"trace": tr}) is None
