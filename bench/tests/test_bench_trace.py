"""Trace reduction on a hand-built trace, and the kernels' least times."""

import importlib.util
import json

import pytest

from bench import trace
from bench.tests.conftest import ROOT

PEAKS = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_per_s": 819e9}

# Window 0..1000 ns.  Device ops: [100, 300) and [200, 400) overlap, [600, 700),
# and one op straddling the window's end.  Host spans cover the idle gaps.
TRACE = {
    "device": {"/device:TPU:0": [
        ("fusion.1", 100, 200), ("nibble_dot_raw.1", 200, 200),
        ("nibble_dot_raw.1", 600, 100), ("slice.2", 700, 0), ("top_k", 950, 100),
        ("nibble_dot_raw_x", 300, 10), ("early", -50, 40)]},
    "host": [("bench.window", 0, 1000), ("bench.prepare", 0, 100),
             ("bench.search", 90, 900), ("PjitFunction(fn)", 420, 150)],
}


def test_union():
    assert trace.union([(5, 6), (1, 3), (2, 4), (4, 5)]) == [(1, 6)]
    assert trace.union([]) == []


def test_busy_idle_and_attribution():
    s = trace.summarize(TRACE)
    assert s["window_s"] == pytest.approx(1e-6)
    busy_ns = (400 - 100) + (700 - 600) + (1000 - 950)
    assert s["busy_s"] == pytest.approx(busy_ns / 1e9)
    assert s["ops_s"]["nibble_dot_raw.1"] == pytest.approx(300e-9)
    assert s["ops_s"]["top_k"] == pytest.approx(50e-9)
    # gaps: [0,100) mid 50 -> prepare; [400,600) mid 500 -> PjitFunction
    # (innermost); [700,950) mid 825 -> search.
    assert s["idle_s"] == pytest.approx({"bench.prepare": 100e-9,
                                         "PjitFunction(fn)": 200e-9,
                                         "bench.search": 250e-9})
    b = trace.breakdown(s)
    assert b["idle_gaps"][0] == ["bench.search", pytest.approx(250e-9)]
    assert len(b["device_ops"]) == 4


def test_kernel_events_inside_the_window():
    ev = trace.kernel_events(TRACE, "nibble_dot_raw")
    assert [d for _, _, d in ev] == [200, 100]


def test_op_name_is_the_hlo_name():
    hlo = ("%slice.2 = f32[256,1000000]{1,0} slice(f32[256,1000192]{1,0} "
           "%nibble_dot_raw.1), slice={[0:256], [0:1000000]}")
    assert trace.op_name(hlo) == "slice.2"
    assert trace.op_name("%nibble_dot_raw.1 = f32[8,8] custom-call()") == "nibble_dot_raw.1"
    assert trace.op_name("fusion") == "fusion"


def _reader(name):
    spec = importlib.util.spec_from_file_location(name, ROOT / "bench" / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_scan_least_time_at_known_shapes():
    m = _reader("nibble_dot_roofline")
    # 45,056 x 1024, b=256: 2*256*45056*1024 / 197e12 (compute-bound)
    assert m.least_seconds(256, 45056, 1024, PEAKS) == pytest.approx(2 * 256 * 45056 * 1024 / 197e12)
    # b=8: bytes win: (45056*512 + 4*45056 + 4*8*1024) / 819e9
    assert m.least_seconds(8, 45056, 1024, PEAKS) == pytest.approx(
        (45056 * 512 + 4 * 45056 + 32768) / 819e9)


def test_coarse_least_time_at_known_shapes():
    m = _reader("crumb_dot_roofline")
    assert m.least_seconds(256, 1_000_000, 1024, PEAKS) == pytest.approx(
        2 * 256 * 1e6 * 1024 / 393e12)
    assert m.least_seconds(8, 1_000_000, 1024, PEAKS) == pytest.approx(
        (1e6 * 256 + 8 * 256) / 819e9)


def test_roofline_reader_share():
    m = _reader("nibble_dot_roofline")
    ctx = {"trace": TRACE, "peaks": PEAKS,
           "cell": {"bucket": 256, "n": 45056, "rows_per_chip": 45056, "d_pad": 1024}}
    least = m.least_seconds(256, 45056, 1024, PEAKS)
    assert m.read(ctx) == pytest.approx(100 * 2 * least / 300e-9)


@pytest.mark.parametrize("name", ["nibble_dot_roofline", "crumb_dot_roofline"])
def test_roofline_counts_the_rows_one_event_scans(name):
    """Sharded over four chips, each chip's event scans a quarter of the
    corpus: the share is that of one chip's rows, not of all n."""
    m = _reader(name)
    kernel = {"nibble_dot_roofline": "nibble_dot_raw",
              "crumb_dot_roofline": "crumb_affinity_raw"}[name]
    trace = json.loads(json.dumps(TRACE).replace("nibble_dot_raw", kernel))
    one = {"bucket": 256, "n": 45056, "rows_per_chip": 45056, "d_pad": 1024}
    four = dict(one, n=4 * 45056)
    assert m.read({"trace": trace, "peaks": PEAKS, "cell": four}) == pytest.approx(
        m.read({"trace": trace, "peaks": PEAKS, "cell": one}))
    assert m.read({"trace": trace, "peaks": PEAKS, "cell": dict(four, rows_per_chip=4 * 45056)}) \
        == pytest.approx(4 * m.read({"trace": trace, "peaks": PEAKS, "cell": one}), rel=1e-3)
