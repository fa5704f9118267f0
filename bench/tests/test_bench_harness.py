"""The harness end to end on the CPU: a throwaway cell built only from files
written to a temp dir, the faults that must turn ``correct`` false, and the
exit without a chip."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench import run as bench_run
from bench.tests.conftest import ROOT

TINY_CFG = {
    "name": "tiny", "n": 600, "dim": 100, "metric": "cosine", "bits": 4,
    "backend": "bruteforce", "coarse": "crumb", "rotation_seed": 1836019297,
    "metadata": None, "fit": None,
    "data": {"n_clusters": 8, "center": "unit_normal", "center_scale": 1.0,
             "noise": 0.25, "noise_spread": [0.3, 1.5], "nonneg": False,
             "magnitude": [1.0, 1.0], "query_noise": 0.15},
    "reduced": [], "limits": {"answer_gap": 1e-6}}
READER = '''
def read(ctx):
    return float(ctx["engine"].get("execute", (0.0, 0))[1])
'''


def _tree(tmp_path, knobs):
    """A checkout holding one new cell: its config, traffic and metric are
    new files, found by name; the harness code is the repo's."""
    (tmp_path / "tb" / "configs").mkdir(parents=True)
    (tmp_path / "tb" / "traffic").mkdir()
    (tmp_path / "tb" / "metrics").mkdir()
    (tmp_path / "src").symlink_to(ROOT / "src")
    (tmp_path / "tb" / "configs" / "tiny.json").write_text(json.dumps(TINY_CFG))
    (tmp_path / "tb" / "traffic" / "mix16.json").write_text(json.dumps(
        {"batch": 16, "k": 10, "knobs": knobs,
         "pool_batches": 3, "check_queries": 24, "trace_seconds": 0.3}))
    (tmp_path / "tb" / "metrics" / "calls_seen.py").write_text(READER)
    (tmp_path / "tb" / "peaks.json").write_text((ROOT / "bench" / "peaks.json").read_text())
    man = {"command": ["python3", "tb/run.py"], "paths": ["tb"], "run_seconds": 1,
           "configs": [{"name": "tiny", "source": "test", "file": "tb/configs/tiny.json",
                        "reduced": [], "why": "test"}],
           "workloads": [{"name": "tiny.mix16", "config": "tiny", "traffic": "mix16",
                          "chips": 1, "why": "test"}],
           "end_to_end": [{"name": "qps", "unit": "queries/s", "better": "higher",
                           "bound": 0.1, "source": "host_clock"},
                          {"name": "setup_s", "unit": "s", "better": "lower",
                           "bound": 0.25, "source": "host_clock"}],
           "per_layer": [{"name": "calls_seen", "unit": "calls", "better": "higher",
                          "source": "program_span", "layer": "engine", "moves": "qps"}]}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    return tmp_path


def _run(root, trace=0):
    args = bench_run.parse(["--workload", "tiny.mix16", "--seed", str(2**31 + 12345),
                            "--seconds", "0.3", "--trace", str(trace)])
    return bench_run.run(args, root=root, require_tpu=False)


@pytest.mark.parametrize("knobs", [{}, {"rescore_mult": 4}], ids=["scan", "cascade"])
def test_throwaway_cell_from_new_files(tmp_path, monkeypatch, knobs):
    from bench import system
    if knobs:       # a device too small for one build: the encode goes in chunks
        monkeypatch.setattr(system, "device_memory", lambda: 600 * 128 * 4)
    root = _tree(tmp_path, knobs)
    res = _run(root)
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"qps", "setup_s"}
    assert list(res)[-1] == "checks"
    traced = _run(root, trace=1)
    assert traced["correct"] is True
    assert traced["metrics"]["calls_seen"]["value"] == traced["attempted"]
    assert {"busy_s", "window_s"} <= set(traced["device"])


def _alter_answer(vals, ids):
    ids[0, 0] = ids[0, -1]


def _alter_score(vals, ids):
    vals[0, 0] += 1e-3 * abs(vals[0, 0])


def _half_batch(vals, ids):
    h = len(ids) // 2
    ids[h:2 * h], vals[h:2 * h] = ids[:h].copy(), vals[:h].copy()


@pytest.mark.parametrize("fault", [_alter_answer, _alter_score, _half_batch],
                         ids=["answer_altered", "score_altered", "half_batch"])
def test_a_broken_timed_path_reads_not_correct(tmp_path, monkeypatch, fault):
    import repro.engine as engine
    real = engine.search_backend

    def broken(*args, **kwargs):
        vals, ids = real(*args, **kwargs)
        vals, ids = np.array(vals), np.array(ids)
        fault(vals, ids)
        return vals, ids

    monkeypatch.setattr(engine, "search_backend", broken)
    res = _run(_tree(tmp_path, {}))
    assert res["correct"] is False
    assert res["checks"]["answer_gap"]["value"] > res["checks"]["answer_gap"]["limit"]


def test_exits_non_zero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
                        "agnews45k.scan_b256", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode == bench_run.EXIT_NO_CHIP
    assert p.stdout.strip() == ""
