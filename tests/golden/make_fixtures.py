"""Regenerate the golden `.mvec` fixtures + SHA-256 digests.

    PYTHONPATH=src python tests/golden/make_fixtures.py

The fixtures pin the paper's §3.8 byte-identity claim: building the same
index from the same inputs must produce the same file, byte for byte
(jax threefry + Lloyd-Max codes are platform-deterministic; f32 norms and
IVF centroids can differ in the last ulp across XLA versions and devices,
DESIGN.md §3).
`tests/test_mvec_golden.py` asserts (a) the checked-in bytes still hash to
`digests.json`, (b) `load → save` reproduces them exactly, and (c) a fresh
build reproduces them exactly.  Regenerate ONLY on a deliberate format
change, and say so in the commit message.
"""

from __future__ import annotations

import hashlib
import json
import os

import jax.numpy as jnp
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def _data(n: int, dim: int, seed: int) -> np.ndarray:
    # Plain RandomState gaussians: stable across numpy versions by contract.
    return np.random.RandomState(seed).randn(n, dim).astype(np.float32)


def build_v6_bruteforce():
    """Minimal v6: BruteForce, cosine, pure 4-bit."""
    from repro.core import MonaVec
    return MonaVec.build(_data(32, 16, 100), metric="cosine", seed=7)


def build_v7_perm_bruteforce():
    """v7: mixed 4/2-bit with the persisted variance permutation."""
    from repro.core import BruteForceIndex, MonaVec
    from repro.core import quantize as qz
    from repro.core.rhdh import rhdh_apply
    from repro.core.standardize import prepare
    x = _data(24, 16, 101) * np.exp(-np.arange(16) / 4).astype(np.float32)
    rot = rhdh_apply(prepare(jnp.asarray(x), "cosine"), 7, normalized=False)
    perm = qz.variance_permutation(rot)
    enc = qz.encode_mixed(jnp.asarray(x), metric="cosine", seed=7,
                          avg_bits=3.0, perm=perm)
    return MonaVec(BruteForceIndex(enc=enc, ids=np.arange(24, dtype=np.uint64)))


def build_v8_segmented_ivf():
    """v8: IVF base + two add() segments + tombstones in base and extras."""
    from repro.core import MonaVec
    idx = MonaVec.build(_data(20, 16, 102), metric="l2", index="ivf",
                        seed=7, nlist=3, train_iters=5)
    idx.add(_data(6, 16, 103))
    idx.add(_data(4, 16, 104))
    idx.delete([2, 5, 21, 27])
    return idx


def build_v9_meta_bruteforce():
    """v9: metadata columns (i64 / f64 / interned str) over a mutated index —
    per-segment value blocks, vocab grown by add(), tombstones present."""
    from repro.core import MonaVec
    idx = MonaVec.build(
        _data(20, 16, 105), metric="cosine", seed=7,
        meta={"price": np.arange(20, dtype=np.int64) * 3 - 10,
              "score": np.arange(20, dtype=np.float64) / 4 - 2.0,
              "cat": np.array(["red", "green", "blue", "red"] * 5)})
    idx.add(_data(6, 16, 106),
            meta={"price": np.arange(6, dtype=np.int64) + 100,
                  "score": np.linspace(-1.0, 1.0, 6).astype(np.float64),
                  "cat": np.array(["green", "violet"] * 3)})
    idx.delete([3, 8, 22])
    return idx


def build_v10_coarse_bruteforce():
    """v10: per-segment coarse CODE blocks (crumb planes) over a mutated
    index WITH metadata — the COARSE_KIND and HAS_META header bytes are
    both set, and every segment (base + one add()) persists its code."""
    from repro.core import MonaVec
    idx = MonaVec.build(
        _data(20, 16, 107), metric="cosine", seed=7, coarse="crumb",
        meta={"price": np.arange(20, dtype=np.int64) * 2 - 5,
              "cat": np.array(["red", "green"] * 10)})
    idx.add(_data(6, 16, 108),
            meta={"price": np.arange(6, dtype=np.int64) + 50,
                  "cat": np.array(["blue", "red"] * 3)})
    idx.delete([1, 4, 21])
    return idx


def build_v11_tuned_ivf():
    """v11: TUNE block (knobs + ladder + boost curve) over an IVF index
    with metadata — autotuned with seeded sample queries against the exact
    quantized oracle, smallest-rung tie-break, so the persisted envelope is
    byte-stable (DESIGN.md §12)."""
    from repro.core import MonaVec
    idx = MonaVec.build(
        _data(24, 16, 109), metric="cosine", index="ivf", seed=7, nlist=3,
        train_iters=5,
        meta={"price": np.arange(24, dtype=np.int64) - 6,
              "cat": np.array(["red", "green", "blue"] * 8)})
    idx.autotune(recall_target=0.9, k=4, n_queries=8, seed=11)
    return idx


FIXTURES = {
    "v6_bruteforce.mvec": build_v6_bruteforce,
    "v7_perm_bruteforce.mvec": build_v7_perm_bruteforce,
    "v8_segmented_ivf.mvec": build_v8_segmented_ivf,
    "v9_meta_bruteforce.mvec": build_v9_meta_bruteforce,
    "v10_coarse_bruteforce.mvec": build_v10_coarse_bruteforce,
    "v11_tuned_ivf.mvec": build_v11_tuned_ivf,
}


def main() -> None:
    digests = {}
    for name, builder in FIXTURES.items():
        path = os.path.join(HERE, name)
        builder().save(path)
        digests[name] = hashlib.sha256(open(path, "rb").read()).hexdigest()
        print(f"{name}: {digests[name]}")
    with open(os.path.join(HERE, "digests.json"), "w") as fh:
        json.dump(digests, fh, indent=2, sort_keys=True)
        fh.write("\n")


if __name__ == "__main__":
    main()
