#!/usr/bin/env python3
"""Smoke run of MonaVec's search path on a TPU, through the user entry points.

    python chip_smoke.py               # one chip: phases 1-10 below
    python chip_smoke.py --four-chip   # four chips: sharded vs single-device
    python chip_smoke.py --rehearse    # any backend: print every phase's
                                       # numbers, never claim a chip run

Deployment: the paper's AG News shape (45,056 x 1024 cosine, 4-bit), data
from ``repro.data.synthetic`` at a fixed seed, k=10, served through
``MonaVec.build`` -> ``TenantRegistry.searcher`` as ``launch/serve.py`` does.
Every answer is checked on the host in float64 numpy, never with a jnp
reference (a default-precision TPU matmul is one bf16 pass):

  * overlap: top-10 ids against a quantized-space oracle -- the index's own
    codes dequantized and scored exactly, so only kernel arithmetic differs;
  * recall@10: against the exact cosine top-10 of the float corpus, held to
    a floor set from the CPU rehearsal at the same seed and shape.

Phases: 1 device and dispatch, 2 BruteForce at b=256 and b=8, 3 filtered
(10% selectivity), 4 mutated (add 10%, delete every 17th id), 5 IVF,
6 crumb cascade, 7 HNSW (reduced to 8,192 rows: its build is a host loop),
8 save -> load, 9 within-build determinism, 10 the golden ``.mvec``
fixtures rebuilt on this device (reported, not gated).

The script runs in one process and starts no other.  Any failed check
raises, so the process exits non-zero and never prints the final line.
Without a TPU it exits non-zero before any work.  Seconds printed here are
smoke observations (first call includes compilation), not benchmark
metrics.  The last line of a passing run is exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import hashlib
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

from repro.core import Eq, MonaVec, TenantRegistry  # noqa: E402
from repro.core import lloydmax, rhdh  # noqa: E402
from repro.core import quantize as qz  # noqa: E402
from repro.core.bruteforce import BruteForceIndex  # noqa: E402
from repro.data.synthetic import embedding_corpus, queries_from_corpus  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch import compile_cache  # noqa: E402

SEED = 0
N_AGNEWS, DIM, K = 45_056, 1024, 10     # configs/retrieval.py agnews_45k
N_GLOVE = 1_179_648                     # configs/retrieval.py glove_1m rows
N_HNSW = 8_192
BATCH = 256
TOKEN = "smoke"
OUT = ROOT / "chiprun_out" / "smoke"

# recall@10 floors: the CPU rehearsal (`python chip_smoke.py --rehearse`,
# seed 0, same shapes) minus 0.01.
REHEARSAL_RECALL = {
    "bruteforce_b256": 0.8625,
    "bruteforce_b8": 0.825,
    "ivf": 0.4980,
    "cascade": 0.8527,
    "hnsw": 0.5628,
}
MIN_OVERLAP = 0.99


class SmokeFailure(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def timed(fn, *args):
    t0 = time.perf_counter()
    out = fn(*args)
    return out, time.perf_counter() - t0


# ---------------------------------------------------------------------------
# float64 host references
# ---------------------------------------------------------------------------

def _unit(x: np.ndarray) -> np.ndarray:
    x = np.asarray(x, np.float64)
    return x / np.maximum(np.linalg.norm(x, axis=1, keepdims=True), 1e-12)


def _topk_rows(scores: np.ndarray, k: int) -> np.ndarray:
    """Row indices of the k best scores per query, ties to the lower row."""
    return np.argsort(-scores, axis=1, kind="stable")[:, :k]


def _rotate64(q: np.ndarray, enc: qz.Encoded) -> np.ndarray:
    """Cosine prepare + the seeded Hadamard rotation, in float64."""
    x = _unit(q)
    xp = np.zeros((len(x), enc.dim_pad))
    xp[:, :x.shape[1]] = x
    signs = np.asarray(rhdh.rademacher_signs(enc.seed, enc.dim_pad), np.float64)
    return (xp * signs) @ rhdh.hadamard_matrix(enc.dim_pad).astype(np.float64)


def _dequant64(enc: qz.Encoded) -> np.ndarray:
    check(enc.bits == 4 and enc.perm is None, "oracle covers plain 4-bit codes")
    packed = np.asarray(enc.packed)
    codes = np.empty((packed.shape[0], packed.shape[1] * 2), np.uint8)
    codes[:, 0::2] = packed & 0xF
    codes[:, 1::2] = packed >> 4
    return np.asarray(lloydmax.CENTROIDS_4BIT, np.float64)[codes]


def quantized_scores(index: MonaVec, q: np.ndarray) -> np.ndarray:
    """Adjusted cosine scores [b, n_total] of every row, segment by segment
    (each segment has its own rotation seed), from the index's own codes."""
    encs = [index.backend.enc] + [s.enc for s in index.mut.extras]
    parts = []
    for enc in encs:
        check(enc.metric == "cosine", "oracle covers the cosine metric")
        deq = _dequant64(enc)
        qn = np.maximum(np.linalg.norm(deq, axis=1), 1e-12)
        parts.append((_rotate64(q, enc) @ deq.T) / qn[None, :])
    return np.concatenate(parts, axis=1)


def oracle_ids(index: MonaVec, q: np.ndarray, admit: np.ndarray) -> np.ndarray:
    s = quantized_scores(index, q)
    s[:, ~admit] = -np.inf
    return index.ids[_topk_rows(s, K)]


def exact_ids(vectors: np.ndarray, ids: np.ndarray, q: np.ndarray,
              admit: np.ndarray) -> np.ndarray:
    s = _unit(q) @ _unit(vectors).T
    s[:, ~admit] = -np.inf
    return ids[_topk_rows(s, K)]


def overlap(got: np.ndarray, want: np.ndarray) -> float:
    return float(np.mean([len(set(g) & set(w)) / K for g, w in zip(got, want)]))


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

class Smoke:
    def __init__(self, rehearse: bool):
        self.rehearse = rehearse
        self.first_call_s = 0.0
        self.reg = TenantRegistry()
        self.recalls = {}

    def searcher(self, name: str, **knobs):
        search = self.reg.searcher(TOKEN, name, k=K, **knobs)
        if self.rehearse:
            # Off the chip, the gathered scan's jnp mirror unrolls its tile
            # grid and takes minutes to compile at b=256; each query's
            # answer does not depend on its batch, so serve 8 at a time.
            search = _in_batches_of_8(search)
        return search

    def serve(self, label: str, name: str, q: np.ndarray, **knobs):
        """Two calls through the registry's bound searcher; both must agree."""
        search = self.searcher(name, **knobs)
        (s1, i1), t1 = timed(search, q)
        (s2, i2), t2 = timed(search, q)
        self.first_call_s += t1
        check(np.array_equal(i1, i2) and s1.tobytes() == s2.tobytes(),
              f"{label}: a repeated call changed the answer")
        say(f"{label}: b={len(q)} first call {t1:.3f}s (compile included), "
            f"second call {t2:.3f}s [smoke observation]")
        return s1, i1

    def gate_overlap(self, label: str, got, want) -> None:
        ov = overlap(got, want)
        say(f"{label}: top-{K} overlap with the float64 quantized oracle "
            f"{ov:.4f} (min {MIN_OVERLAP})")
        check(ov >= MIN_OVERLAP, f"{label}: overlap {ov:.4f} < {MIN_OVERLAP}")

    def gate_recall(self, label: str, key: str, got, want) -> None:
        rec = overlap(got, want)
        self.recalls[key] = rec
        floor = REHEARSAL_RECALL[key] - 0.01
        say(f"{label}: recall@{K} vs exact float64 {rec:.4f} (floor {floor:.4f})")
        if not self.rehearse:
            check(rec >= floor, f"{label}: recall {rec:.4f} < floor {floor:.4f}")

    def run(self) -> None:
        corpus = embedding_corpus(SEED, N_AGNEWS, DIM)
        q = queries_from_corpus(corpus, SEED + 1, BATCH)
        bucket = np.arange(N_AGNEWS, dtype=np.int64) % 10
        all_rows = np.ones(N_AGNEWS, bool)
        exact = exact_ids(corpus, np.arange(N_AGNEWS, dtype=np.uint64), q, all_rows)

        # 2. BruteForce, static.
        index, t = timed(lambda: MonaVec.build(corpus, metric="cosine",
                                               meta={"bucket": bucket}))
        say(f"bruteforce: built {N_AGNEWS}x{DIM} 4-bit in {t:.2f}s")
        self.reg.put(TOKEN, "agnews", index)
        want = oracle_ids(index, q, all_rows)
        for b, key in ((BATCH, "bruteforce_b256"), (8, "bruteforce_b8")):
            _, ids = self.serve(f"bruteforce static b={b}", "agnews", q[:b])
            self.gate_overlap(f"bruteforce static b={b}", ids, want[:b])
            self.gate_recall(f"bruteforce static b={b}", key, ids, exact[:b])

        # 3. Filtered, 10% selectivity.
        _, ids = self.serve("filtered", "agnews", q, where=Eq("bucket", 0))
        self.gate_overlap("filtered bucket==0", ids,
                          oracle_ids(index, q, bucket == 0))
        check(np.all(np.isin(ids, index.ids[bucket == 0])),
              "filtered: a row outside the predicate was returned")

        # 4. Mutated: add 10% new rows, delete every 17th id.
        add_n = N_AGNEWS // 10
        delta = embedding_corpus(SEED + 2, add_n, DIM)
        self.reg.add(TOKEN, "agnews", delta,
                     meta={"bucket": np.arange(add_n, dtype=np.int64) % 10})
        n_del = self.reg.delete(TOKEN, "agnews", index.ids[::17])
        say(f"mutated: +{add_n} rows, {n_del} deleted, live "
            f"{index.n_live}/{index.n_total}")
        live = np.concatenate(index._live_masks())
        scores_mut, ids_mut = self.serve("mutated", "agnews", q)
        self.gate_overlap("mutated", ids_mut, oracle_ids(index, q, live))
        rec = overlap(ids_mut, exact_ids(np.concatenate([corpus, delta]),
                                         index.ids, q, live))
        say(f"mutated: recall@{K} vs exact float64 {rec:.4f} (reported)")

        # 5. IVF.
        ivf, t = timed(lambda: MonaVec.build(corpus, metric="cosine",
                                             index="ivf", nlist=128))
        say(f"ivf: built nlist=128 in {t:.2f}s")
        self.reg.put(TOKEN, "agnews_ivf", ivf)
        _, ids = self.serve("ivf nprobe=16", "agnews_ivf", q, nprobe=16)
        self.gate_recall("ivf nprobe=16", "ivf", ids, exact)

        # 6. Cascade: crumb coarse scan, rescore 32*k survivors.
        casc, t = timed(lambda: MonaVec.build(corpus, metric="cosine",
                                              coarse="crumb"))
        say(f"cascade: built crumb codes in {t:.2f}s")
        self.reg.put(TOKEN, "agnews_cascade", casc)
        _, ids = self.serve("cascade rescore_mult=32", "agnews_cascade", q,
                            rescore_mult=32)
        self.gate_recall("cascade rescore_mult=32", "cascade", ids, exact)

        # 7. HNSW, reduced.
        sub = corpus[:N_HNSW]
        q_sub = queries_from_corpus(sub, SEED + 3, BATCH)
        hnsw, t = timed(lambda: MonaVec.build(sub, metric="cosine",
                                              index="hnsw", m=16,
                                              ef_construction=64))
        say(f"hnsw: REDUCED to n={N_HNSW} (host-loop build) -- built m=16 "
            f"ef_construction=64 in {t:.2f}s")
        self.reg.put(TOKEN, "agnews_hnsw", hnsw)
        _, ids = self.serve("hnsw", "agnews_hnsw", q_sub)
        self.gate_recall("hnsw", "hnsw", ids, exact_ids(
            sub, np.arange(N_HNSW, dtype=np.uint64), q_sub,
            np.ones(N_HNSW, bool)))

        # 8. save -> load round trip of the mutated index.
        OUT.mkdir(parents=True, exist_ok=True)
        path = OUT / "agnews_mutated.mvec"
        index.save(str(path))
        self.reg.put(TOKEN, "agnews_loaded", MonaVec.load(str(path)))
        s_l, i_l = self.serve("loaded", "agnews_loaded", q)
        check(np.array_equal(i_l, ids_mut) and s_l.tobytes() == scores_mut.tobytes(),
              "save -> load changed ids or score bytes")
        say(f"save -> load: {path.stat().st_size} bytes, ids and score bytes "
            "identical")

        # 9. Within-build determinism: the same batch twice, byte-identical
        # (the second call re-runs the cached plan).
        s_a, i_a = self.searcher("agnews")(q)
        s_b, i_b = self.searcher("agnews")(q)
        check(np.array_equal(i_a, i_b) and s_a.tobytes() == s_b.tobytes(),
              "determinism: the same batch gave different bytes")
        check(np.array_equal(i_a, ids_mut) and s_a.tobytes() == scores_mut.tobytes(),
              "determinism: a fresh searcher gave different bytes")
        say("determinism: repeated batches byte-identical")

        # 10. Golden fixtures rebuilt here (reported, not gated).
        golden_finding()
        say(f"first-call seconds, all phases: {self.first_call_s:.3f} "
            "(compile included) [smoke observation]")
        if self.rehearse:
            say("rehearsal recalls: " + json.dumps(self.recalls, sort_keys=True))


def _in_batches_of_8(search):
    def run(q):
        parts = [search(q[i:i + 8]) for i in range(0, len(q), 8)]
        return (np.concatenate([p[0] for p in parts]),
                np.concatenate([p[1] for p in parts]))
    return run


def golden_finding() -> None:
    from repro.core import mvec_format as fmt
    from tests.golden import make_fixtures as gold

    digests = json.loads((ROOT / "tests/golden/digests.json").read_text())
    OUT.mkdir(parents=True, exist_ok=True)
    for name, build in gold.FIXTURES.items():
        path = OUT / name
        build().save(str(path))
        got = path.read_bytes()
        if hashlib.sha256(got).hexdigest() == digests[name]:
            say(f"golden {name}: sha256 matches")
            continue
        first = _first_difference(fmt.load(str(path)),
                                  fmt.load(str(ROOT / "tests/golden" / name)))
        say(f"golden {name}: sha256 DIFFERS; first differing block: {first} "
            "(finding, not gated)")


def _first_difference(a, b) -> str:
    """Name of the first block of two loaded .mvec files that differs."""
    def raw(x):
        return None if x is None else np.asarray(x).tobytes()

    pairs = [("packed", a.enc.packed, b.enc.packed),
             ("qnorms", a.enc.qnorms, b.enc.qnorms),
             ("ccodes", a.enc.ccodes, b.enc.ccodes),
             ("ids", a.ids, b.ids)]
    for i, (ea, eb) in enumerate(zip(a.extras, b.extras)):
        pairs += [(f"segment {i + 1} packed", ea.enc.packed, eb.enc.packed),
                  (f"segment {i + 1} qnorms", ea.enc.qnorms, eb.enc.qnorms)]
    pairs.append(("index data", a.index_data, b.index_data))
    for name, x, y in pairs:
        if raw(x) != raw(y):
            return name
    return "metadata or tune block"


# ---------------------------------------------------------------------------
# four chips: sharded BruteForce vs single-device search of the same index
# ---------------------------------------------------------------------------

def four_chip(reg: TenantRegistry) -> None:
    import jax

    from repro.launch.mesh import make_local_mesh

    corpus, t = timed(lambda: embedding_corpus(SEED, N_GLOVE, DIM))
    say(f"four-chip: corpus {N_GLOVE}x{DIM} generated in {t:.2f}s")
    # Encode in row chunks: the one-device encode of all rows at once holds
    # several f32 copies of the corpus.  Encoding is row-wise, so the codes
    # are those of a single build.
    t0 = time.perf_counter()
    chunk = 131_072
    encs = [qz.encode(jax.numpy.asarray(corpus[i:i + chunk]), metric="cosine")
            for i in range(0, N_GLOVE, chunk)]
    enc = dataclasses.replace(
        encs[0], packed=jax.numpy.concatenate([e.packed for e in encs]),
        qnorms=jax.numpy.concatenate([e.qnorms for e in encs]))
    index = MonaVec(BruteForceIndex(enc=enc, ids=np.arange(N_GLOVE, dtype=np.uint64)))
    say(f"four-chip: encoded {N_GLOVE} rows on one device in "
        f"{time.perf_counter() - t0:.2f}s ({enc.packed.nbytes} code bytes)")
    q = queries_from_corpus(corpus, SEED + 1, BATCH)
    del corpus

    reg.put(TOKEN, "glove", index)
    single = reg.searcher(TOKEN, "glove", k=K)
    (s1, i1), t1 = timed(single, q)
    say(f"four-chip: single-device first call {t1:.3f}s [smoke observation]")

    mesh = make_local_mesh()
    sharded = index.shard(mesh)
    reg.put(TOKEN, "glove_sharded", sharded)
    search = reg.get(TOKEN, "glove_sharded").searcher(k=K)
    (s4, i4), t4 = timed(search, q)
    say(f"four-chip: sharded first call {t4:.3f}s [smoke observation]")
    check(np.array_equal(i1, i4), "sharded ids differ from single-device ids")
    check(s1.tobytes() == s4.tobytes(),
          "sharded scores differ from single-device scores")
    say(f"four-chip: ids and score bytes identical over {len(q)} queries")

    rows = {}
    for arr in (sharded.enc.packed, sharded.enc.qnorms):
        for shard in arr.addressable_shards:
            rows.setdefault(shard.device.id, set()).add(shard.data.shape[0])
    per = sharded.enc.packed.shape[0] // len(jax.devices())
    check(sorted(rows) == sorted(d.id for d in jax.devices()),
          f"shards are not on every device: {sorted(rows)}")
    check(all(r == {per} for r in rows.values()),
          f"uneven shards: {rows} (want {per} rows each)")
    say(f"four-chip: {per} padded rows on each of devices {sorted(rows)}")


# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only sharded-vs-single BruteForce on 4 chips")
    ap.add_argument("--rehearse", action="store_true",
                    help="run the one-chip phases on any backend and print "
                         "their numbers; never reports a chip run")
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    dev = devices[0]
    want = 4 if args.four_chip else 1
    if not args.rehearse and (dev.platform != "tpu" or len(devices) < want):
        print(f"chip_smoke: needs {want} TPU device(s); JAX found "
              f"{len(devices)} {dev.platform!r} device(s)", file=sys.stderr)
        return 1
    if not args.rehearse:
        say(f"compilation cache: {compile_cache.enable()}")

    # 1. Device and dispatch.
    dispatch = ops.resolve_dispatch(None, None)
    say(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}; dispatch (use_kernel, interpret)={dispatch}")
    if not args.rehearse:
        check(dispatch == (True, False),
              f"default dispatch is {dispatch}, not the compiled kernel")

    t0 = time.perf_counter()
    if args.four_chip:
        four_chip(TenantRegistry())
    else:
        Smoke(args.rehearse).run()
    say(f"wall {time.perf_counter() - t0:.2f}s [smoke observation]")
    if args.rehearse:
        say("rehearsal finished: not a chip run")
        return 3
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
