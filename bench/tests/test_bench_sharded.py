"""A corpus row-sharded over the cell's chips: block-keyed generation, the
per-chip build, a throwaway sharded cell run end to end (four CPU devices,
in a child process: ``four_devices.py``), and a streamed reference whose
device arrays stay the size of a chunk."""

import json
import os
import subprocess
import sys
import tracemalloc

import jax
import numpy as np
import pytest

from bench import datagen, reference, system
from bench import run as bench_run
from repro.core import MonaVec
from repro.dist.sharded_index import ShardedMonaVec
from bench.tests import test_bench_harness as harness
from bench.tests.conftest import ROOT


@pytest.fixture(scope="module")
def four(tmp_path_factory):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4").strip()
    p = subprocess.run([sys.executable, str(ROOT / "bench" / "tests" / "four_devices.py"),
                        str(tmp_path_factory.mktemp("four"))],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("group", ["one", "two", "four"])
def test_block_corpus_is_the_same_on_any_devices(four, group):
    b = four["blocks"]
    assert b[group] == b["default"]
    assert len(b[group + "_devices"]) == {"one": 1, "two": 2, "four": 4}[group]


def test_block_rows_by_index_and_by_prefix(four):
    b = four["blocks"]
    assert b["take_equal"] and b["prefix_equal"] and b["tail_zero"]


@pytest.mark.parametrize("metric", ["cosine", "l2"])
def test_per_chip_codes_equal_one_device_encode(four, metric):
    c = four["codes"][metric]
    assert c["packed_equal"] and c["qnorms_equal"]
    assert c["rows_per_device"] == [160] * 4 and c["n"] == 640


@pytest.mark.parametrize("mode", ["scan", "cascade"])
def test_reference_verdict_is_the_same_on_four_devices(four, mode):
    r = four["reference"][mode]
    assert r["equal"] and r["one"]["answer_gap"] > 1e-3 and r["one"]["control_gap"] > 0


@pytest.mark.parametrize("trace", [0, 1])
def test_sharded_cell_from_new_files(four, trace):
    r = four["runs"][f"trace{trace}"]
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert r["device"]["count"] == 4
    assert set(r["metrics"]) == ({"qps", "setup_s"} if trace == 0 else {"calls_seen"})


@pytest.mark.parametrize("fault", ["_alter_answer", "_alter_score", "_half_batch",
                                   "no_exchange"])
def test_a_broken_sharded_path_reads_not_correct(four, fault):
    r = four["runs"][fault]
    assert r["correct"] is False
    assert r["checks"]["answer_gap"]["value"] > r["checks"]["answer_gap"]["limit"]


def test_shards_must_split_the_rows_evenly(four):
    assert "evenly" in four["codes"]["uneven"]


def _sharded_cfg():
    return dict(harness.TINY_CFG, coarse=None, n=640,
                data=dict(harness.TINY_CFG["data"], block_rows=64))


@pytest.mark.parametrize("blocked", [True, False], ids=["block_rows", "whole"])
def test_block_rows_selects_the_sharded_build(blocked):
    """A block-keyed corpus is built sharded over its devices (here one);
    any other corpus by one MonaVec build, as before."""
    cfg = _sharded_cfg()
    if not blocked:
        cfg["data"] = harness.TINY_CFG["data"]
    index = system.build(cfg, datagen.Rows(cfg["data"], 1, 640, 100)).get(
        system.TOKEN, system.COLLECTION)
    assert isinstance(index, ShardedMonaVec) is blocked
    assert isinstance(index, MonaVec) is not blocked


def test_sharded_build_holds_the_codes_once(monkeypatch):
    """While it builds, a chip holds one buffer of its codes and the few
    blocks in flight: never its pieces and their concatenation at once."""
    n, b = 4096, 256
    cfg = dict(_sharded_cfg(), n=n, data=dict(harness.TINY_CFG["data"], block_rows=b))
    real = system._put
    before = {id(a) for a in jax.live_arrays()}
    seen = []

    def watched(buf, enc, *args, **kwargs):
        seen.append(sum(a.nbytes for a in jax.live_arrays()
                        if a.dtype == np.uint8 and id(a) not in before))
        return real(buf, enc, *args, **kwargs)

    monkeypatch.setattr(system, "_put", watched)
    index = system.build_sharded(cfg, datagen.Rows(cfg["data"], 1, n, 100))
    row = index.enc.packed.shape[1]
    assert len(seen) == n // b
    # The buffer, the blocks in flight (2 x shards + 1) and the one encoding.
    assert max(seen) <= (n + 4 * b) * row < 2 * n * row


def test_one_shard_cell_reads_correct(tmp_path):
    """The sharded path on one device: the shard_map scan over a one-chip mesh."""
    root = harness._tree(tmp_path, {})
    (root / "tb" / "configs" / "tiny.json").write_text(json.dumps(_sharded_cfg()))
    res = harness._run(root)
    assert res["correct"] is True and res["attempted"] > 0


N, DIM, NQ, BLOCK, CHUNK = 1 << 18, 16, 16, 8192, 2048
DATA = {"n_clusters": 8, "center": "uniform", "center_scale": 0.1, "noise": 0.03,
        "noise_spread": [0.5, 1.5], "nonneg": True, "magnitude": [0.5, 1.5],
        "block_rows": BLOCK}


@pytest.mark.parametrize("m", [None, 40], ids=["scan", "cascade"])
def test_streamed_reference_holds_no_corpus_sized_array(monkeypatch, m):
    """While the reference runs, the device holds no more than one block and
    a few [nq, chunk] arrays, and the host never [nq, n] bytes."""
    rows = datagen.Rows(DATA, 3, N, DIM)
    q = datagen.query_pool(rows, 3, NQ, 0.01)
    sem = reference.semantics({"dim": DIM, "rotation_seed": 1836019297, "metric": "cosine",
                               "fit": None})
    fake = np.tile(np.arange(10, dtype=np.int64), (NQ, 1))
    scores = np.tile(np.linspace(1.0, 0.9, 10, dtype=np.float32), (NQ, 1))
    real = reference._device_chunk
    before = {id(a) for a in jax.live_arrays()}
    seen = []

    def watched(*args, **kwargs):
        seen.append(sum(a.size for a in jax.live_arrays() if id(a) not in before))
        return real(*args, **kwargs)

    monkeypatch.setattr(reference, "_device_chunk", watched)
    reference.check(rows, q, scores, fake, sem, k=10, m=m, chunk_rows=CHUNK)  # compiles
    seen.clear()
    tracemalloc.start()
    try:
        v = reference.check(rows, q, scores, fake, sem, k=10, m=m, chunk_rows=CHUNK)
        peak = tracemalloc.get_traced_memory()[1]
    finally:
        tracemalloc.stop()
    passes = 2 if m is None else 3
    assert len(seen) == passes * N // CHUNK
    # One block and a few chunks' worth of [nq, chunk] arrays; [nq, n] is 64x that.
    assert max(seen) <= BLOCK * DIM + 8 * NQ * CHUNK < NQ * N // 8
    assert peak < NQ * N // 2, peak         # a bool [nq, n] mask is NQ * N bytes
    assert v.answer_gap > 1e-3              # the made-up answers are wrong


def test_run_records_host_peak_rss(tmp_path):
    root = harness._tree(tmp_path, {})
    harness._run(root)
    rec = json.loads(next((root / "tb" / "out" / "tiny.mix16").glob("*-t0.json")).read_text())
    assert rec["host_peak_rss_bytes"] > 0
    assert bench_run.host_peak_rss() >= rec["host_peak_rss_bytes"]


def test_chip_probe_rehearses_on_the_cpu(tmp_path):
    """The chip probe's whole path, on one CPU device at a few thousand rows:
    a block-keyed corpus on one shard through ``bench/run.py``."""
    p = subprocess.run([sys.executable, str(ROOT / "bench" / "tests" / "chip_probe.py"),
                        "--rehearse", "--rows", "4096", "--seeds", str(2**31 + 99),
                        "--seconds", "0.5", "--batch", "32", "--out", str(tmp_path)],
                       env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
                       text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    (line,) = json.loads((tmp_path / "probe.json").read_text())
    assert line["correct"] is True and line["chips"] == 1 and line["n"] == 4096
    assert line["reference_s"] > 0 and "build" in line["setup_phases_s"]
    assert line["host_peak_rss_bytes"] >= line["host_peak_rss_bytes_at_start"] > 0
