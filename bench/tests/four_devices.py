"""The sharded path of the harness on four CPU devices.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
        python bench/tests/four_devices.py <scratch dir>

The device count is fixed when JAX starts, so ``test_bench_sharded.py`` runs
this in a child process and reads the JSON object it prints last: whether a
block-keyed corpus comes out the same however its blocks are spread over the
devices, whether a per-chip build gives the codes of a one-device encode (and
refuses rows that do not split evenly over the chips),
whether the reference's verdict is the same with its chunks on one device or
four, and what ``bench/run.py`` reads for a throwaway sharded cell, sound and with
each planted fault: an answer or a score altered where it is produced, half
the batch answered with the other half's answers, and the exchange between
chips left out.
"""

import hashlib
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from bench import datagen, system  # noqa: E402
from bench.tests import test_bench_harness as harness  # noqa: E402
from repro.core import quantize as qz  # noqa: E402

DATA = dict(harness.TINY_CFG["data"], block_rows=64)
SHARDED_CFG = dict(harness.TINY_CFG, name="tiny4", n=640, coarse=None, data=DATA)
SEED = 2**31 + 4321


def sha(a) -> str:
    return hashlib.sha256(np.ascontiguousarray(np.asarray(a)).tobytes()).hexdigest()


def block_corpus() -> dict:
    """The whole block corpus as one device makes it, and as 2 and 4 devices
    make it block by block, and by ``take``."""
    devs = jax.devices()
    out = {}
    for name, group in (("default", None), ("one", devs[:1]), ("two", devs[:2]),
                        ("four", devs)):
        rows = datagen.Rows(DATA, SEED, 600, 100, group)
        blocks = [rows.block(j) for j in range(rows.n_blocks)]
        out[name] = sha(np.concatenate([np.asarray(b) for b in blocks])[:600])
        out[name + "_devices"] = sorted({str(next(iter(b.devices()))) for b in blocks})
    rows = datagen.Rows(DATA, SEED, 600, 100, devs)
    whole = np.concatenate([np.asarray(rows.block(j)) for j in range(rows.n_blocks)])[:600]
    idx = np.random.default_rng(0).permutation(600)[:250]
    out["take_equal"] = bool(np.array_equal(rows.take(idx), whole[idx]))
    shorter = datagen.Rows(DATA, SEED, 300, 100, devs[:2])
    out["prefix_equal"] = bool(np.array_equal(
        np.concatenate([np.asarray(shorter.block(j)) for j in range(shorter.n_blocks)])[:300],
        whole[:300]))
    out["tail_zero"] = bool(np.all(np.asarray(rows.block(rows.n_blocks - 1))[600 % 64:] == 0))
    return out


def sharded_codes() -> dict:
    """Codes and norms of the per-chip build against one encode of all rows."""
    out = {}
    for metric in ("cosine", "l2"):
        cfg = dict(SHARDED_CFG, metric=metric)
        rows = datagen.Rows(DATA, SEED, cfg["n"], cfg["dim"], jax.devices())
        std = None
        if metric == "l2":
            std = system.fit(datagen.sample(DATA, 7, 200, cfg["dim"]))
        index = system.build_sharded(cfg, rows, std)
        whole = jnp.concatenate([rows.block(j, jax.devices()[0])
                                 for j in range(rows.n_blocks)])[:cfg["n"]]
        ref = qz.encode(whole, metric=metric, bits=4, seed=cfg["rotation_seed"], std=std)
        out[metric] = {
            "packed_equal": bool(np.array_equal(np.asarray(index.enc.packed),
                                                np.asarray(ref.packed))),
            "qnorms_equal": bool(np.array_equal(np.asarray(index.enc.qnorms),
                                                np.asarray(ref.qnorms))),
            "rows_per_device": sorted(int(s.data.shape[0])
                                      for s in index.enc.packed.addressable_shards),
            "n": index.n,
        }
    try:        # 642 rows do not split over four shards
        system.build(dict(SHARDED_CFG, n=642),
                     datagen.Rows(DATA, SEED, 642, 100, jax.devices()))
        out["uneven"] = "built"
    except ValueError as e:
        out["uneven"] = str(e)
    return out


def spread_reference() -> dict:
    """The reference's verdict with the corpus's chunks on one device and
    spread over four: equal, field by field."""
    from bench import reference
    from bench.tests.test_bench_golden import nominal
    out = {}
    for m in (None, 40):
        sem = reference.semantics(dict(SHARDED_CFG, fit=None))
        one = datagen.Rows(DATA, SEED, 640, 100, jax.devices()[:1])
        x = np.concatenate([np.asarray(one.block(j)) for j in range(one.n_blocks)])[:640]
        q = datagen.query_pool(one, SEED, 16, 0.01)
        scores, ids = nominal(x, q, sem, 10, m)
        ids[1, 0], ids[1, 1] = ids[1, 1], ids[1, 0]
        v = [reference.check(datagen.Rows(DATA, SEED, 640, 100, devs), q, scores, ids, sem,
                             k=10, m=m, control=True, chunk_rows=48)._asdict()
             for devs in (jax.devices()[:1], jax.devices())]
        out["scan" if m is None else "cascade"] = {"equal": v[0] == v[1], "one": v[0]}
    return out


def tree(base: Path, name: str) -> Path:
    """A checkout holding one sharded four-chip cell, made of new files only."""
    root = harness._tree(base / name, {})
    (root / "tb" / "configs" / "tiny.json").write_text(json.dumps(SHARDED_CFG))
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["workloads"][0]["chips"] = 4
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    return root


def cell_runs(base: Path) -> dict:
    import repro.engine as engine
    from repro.dist import retrieval
    out = {}
    root = tree(base, "sound")
    for t in (0, 1):
        res = harness._run(root, trace=t)
        out[f"trace{t}"] = {"correct": res["correct"], "failed": res["failed"],
                            "attempted": res["attempted"], "metrics": res["metrics"],
                            "device": res["device"], "checks": res["checks"]}
    real = engine.search_sharded
    for fault in (harness._alter_answer, harness._alter_score, harness._half_batch):
        def broken(*args, fault=fault, **kwargs):
            vals, ids = real(*args, **kwargs)
            vals, ids = np.array(vals), np.array(ids)
            fault(vals, ids)
            return vals, ids
        engine.search_sharded = broken
        try:
            res = harness._run(tree(base, fault.__name__))
        finally:
            engine.search_sharded = real
        out[fault.__name__] = {"correct": res["correct"], "checks": res["checks"]}
    # The exchange between chips left out: each chip's own top-k stands as
    # the answer (shard_map then hands back the first chip's).
    merge = retrieval._merge_topk
    retrieval._merge_topk = lambda vals, gids, axes, k: (vals, gids)
    engine.plan_cache().clear()
    try:
        res = harness._run(tree(base, "no_exchange"))
    finally:
        retrieval._merge_topk = merge
        engine.plan_cache().clear()
    out["no_exchange"] = {"correct": res["correct"], "checks": res["checks"]}
    return out


def main(base: str) -> int:
    if jax.device_count() != 4:
        raise SystemExit(f"needs 4 devices, JAX sees {jax.device_count()}")
    base = Path(base)
    print(json.dumps({"blocks": block_corpus(), "codes": sharded_codes(),
                      "reference": spread_reference(), "runs": cell_runs(base)}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
