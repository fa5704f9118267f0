"""BENCHMARK.json: names, units, cross references, and files found by name."""

import json

import pytest

from bench import manifest
from bench.tests.conftest import ROOT

KEYS = {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}


@pytest.fixture(scope="module")
def man():
    return manifest.load(ROOT)


def test_top_level_keys(man):
    assert set(man) == KEYS
    assert man["command"] == ["python3", "bench/run.py"] and man["paths"] == ["bench"]
    assert 1 <= man["run_seconds"] <= 51


def test_no_problems(man):
    assert manifest.problems(man, ROOT) == []


@pytest.mark.parametrize("bad, fragment", [
    ("bad name", "bad name"), ("x/y", "bad name"), ("a" * 65, "bad name")])
def test_bad_names_are_found(man, bad, fragment):
    broken = json.loads(json.dumps(man))
    broken["per_layer"][0]["name"] = bad
    assert any(fragment in p for p in manifest.problems(broken, ROOT))


def test_bad_unit_is_found(man):
    broken = json.loads(json.dumps(man))
    broken["end_to_end"][0]["unit"] = "queries per second"
    assert any("bad unit" in p for p in manifest.problems(broken, ROOT))


def test_every_metric_lists_exactly_the_cells_that_report_it(man):
    for m in man["per_layer"]:
        moved = next(e for e in man["end_to_end"] if e["name"] == m["moves"])
        for cell in m["workloads"]:
            assert manifest.reports(moved, cell, man["end_to_end"])
    for w in man["workloads"]:
        e2e = manifest.metrics_for(man, w["name"], "end_to_end")
        assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
        assert manifest.metrics_for(man, w["name"], "per_layer")


def test_every_configuration_has_a_cell_and_its_file(man):
    for c in man["configs"]:
        cfg = manifest.config(man, ROOT, c["name"])
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert "answer_gap" in cfg["limits"]
    assert {c["name"] for c in man["configs"]} == {w["config"] for w in man["workloads"]}


def test_every_reader_loads_and_is_silent_on_an_empty_trace(man):
    empty = {"trace": {"device": {}, "host": [("bench.window", 0, 10)]},
             "summary": {"busy_s": 0.0, "window_s": 1e-8, "devices": 0},
             "engine": {}, "peaks": {}, "cell": {}}
    for m in man["per_layer"]:
        assert manifest.reader(man, ROOT, m["name"])(empty) is None


def test_chips_and_seconds_fit_the_check(man):
    assert all(w["chips"] in (1, 4) for w in man["workloads"])
    runs = 2 + 14 * 24
    assert runs * (man["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


@pytest.mark.parametrize("extra", [{"loop": "open"}, {"clients": 4}])
def test_a_traffic_key_the_window_cannot_run_is_refused(tmp_path, extra):
    (tmp_path / "bench" / "traffic").mkdir(parents=True)
    mix = json.loads((ROOT / "bench" / "traffic" / "scan_b256.json").read_text())
    (tmp_path / "bench" / "traffic" / "odd.json").write_text(json.dumps(dict(mix, **extra)))
    with pytest.raises(ValueError, match="unknown keys"):
        manifest.traffic({"paths": ["bench"]}, tmp_path, "odd")


@pytest.mark.parametrize("n, d_pad, memory, rows", [
    (45056, 1024, 16909336064, 45056),          # agnews45k: one build
    (1000000, 1024, 16909336064, 131072),       # gist1m: chunks
    (600, 128, None, 600),                      # no memory statistic: one build
])
def test_encode_rows_follow_the_device_memory(n, d_pad, memory, rows):
    from bench import system
    assert system.encode_rows(n, d_pad, memory) == rows
