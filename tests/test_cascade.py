"""Oracle-backed cascade test suite (DESIGN.md §11; paper §3.6's
memory-bandwidth cascade made testable).

Four layers of pinning, from bit-exact to statistical:

  1. **Mirror identity** — the Pallas coarse kernels (interpret mode) and
     their jnp mirrors produce the SAME int32 proxy for every metric x
     bit-width x coarse kind.  Integer proxies make this equality exact by
     construction; this is the dispatch contract every other test rides on.
  2. **Exactness pin** — at m = n the cascade IS the full scan: the
     survivor stage enumerates every live row in ascending order, the
     gathered rescore of that enumeration reproduces the packed full-scan
     scores, and the engine collapses ``rescore_mult * k >= n`` (and
     ``rescore_mult=0``) to the plain plan, bit for bit.
  3. **Recall floor** — at real budgets (m = 2k/4k/8k) the crumb cascade's
     top-k overlaps the full scan's top-k above a deterministic floor, on
     static, mutated, and sharded lifecycles (fixed seeds end to end, so
     the floors are replayable numbers, not flaky statistics).
  4. **Edge contract** — fewer live rows than k sentinel-pads exactly like
     the full scan; the ``rescore_mult`` knob is rejected with a precise
     error on backends/indexes that cannot honor it.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import MonaVec, SENTINEL_ID
from repro.core import binary
from repro.core import quantize as qz
from repro.core.allowlist import NEG
from repro.data import synthetic as syn
from repro.kernels import binary_dot, ops

K = 10


def _corpus(n, dim, seed=41):
    return syn.embedding_corpus(seed, n, dim)


def _queries(corpus, b, seed=141):
    return np.asarray(syn.queries_from_corpus(corpus, seed, b))


def _recall(got_ids, want_ids):
    """Mean per-row overlap |got ∩ want| / k (the bench's recall@10)."""
    return float(np.mean([
        len(set(g.tolist()) & set(w.tolist())) / len(w)
        for g, w in zip(got_ids, want_ids)]))


# ---------------------------------------------------------------------------
# 1. Kernel / jnp mirror bit-identity
# ---------------------------------------------------------------------------

class TestCoarseMirrorBitIdentity:
    """The integer proxy is identical between the Pallas kernel body
    (interpret mode — the exact arithmetic Mosaic compiles) and the jnp
    mirror, across every metric x bit-layout x coarse kind the engine can
    build.  Equality is ==, not allclose: the proxies are int32."""

    BITS_CFG = [("4bit", {"bits": 4}), ("2bit", {"bits": 2}),
                ("mixed", {"avg_bits": 3.0})]

    @pytest.mark.parametrize("metric", ["cosine", "l2", "dot"])
    @pytest.mark.parametrize("bname,bkw",
                             BITS_CFG, ids=[c[0] for c in BITS_CFG])
    @pytest.mark.parametrize("kind", ["sign", "crumb"])
    def test_kernel_matches_jnp(self, metric, bname, bkw, kind):
        x = _corpus(96, 32, seed=7)
        idx = MonaVec.build(x, metric=metric, coarse=kind, **bkw)
        enc = idx.backend.enc
        q_rot = qz.encode_query(jnp.asarray(_queries(x, 5, seed=9)), enc)
        ref = binary.coarse_scan_stage(q_rot, enc.ccodes, kind=kind,
                                       use_kernel=False)
        ker = binary.coarse_scan_stage(q_rot, enc.ccodes, kind=kind,
                                       use_kernel=True, interpret=True)
        assert ref.dtype == jnp.int32 and ker.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(ker))

    @pytest.mark.parametrize("kind", ["sign", "crumb"])
    def test_odd_shapes_pad_identically(self, kind):
        """Row/batch padding in the dispatch wrapper must never leak into
        the visible [b, n] proxy (257 rows, 3 queries — nothing divides the
        kernel tiles)."""
        x = _corpus(257, 16, seed=11)
        idx = MonaVec.build(x, metric="cosine", coarse=kind)
        enc = idx.backend.enc
        q_rot = qz.encode_query(jnp.asarray(_queries(x, 3, seed=13)), enc)
        ref = binary.coarse_scan_stage(q_rot, enc.ccodes, kind=kind,
                                       use_kernel=False)
        ker = binary.coarse_scan_stage(q_rot, enc.ccodes, kind=kind,
                                       use_kernel=True, interpret=True)
        assert ref.shape == (3, 257)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(ker))

    # (b, n, d'): every batch the blocks treat differently (one 8-row block,
    # a full 256-row block, two blocks), rows no row tile divides (257,
    # 1,000) and rows several do (4,096), and a plane narrower than a lane
    # (d'=16: 2 bytes), exactly one lane (d'=1024: 128 bytes) and wider.
    CRUMB_SHAPES = [(1, 257, 16), (3, 1000, 2048), (8, 4096, 16),
                    (256, 1000, 16), (300, 257, 1024), (300, 4096, 2048),
                    (1, 4096, 1024), (8, 257, 2048), (256, 4096, 1024)]

    @staticmethod
    def _crumb_pair(ccodes, qplanes):
        """(kernel in interpret mode, popcount mirror) on raw plane bytes."""
        ker = ops.crumb_coarse_raw(ccodes, qplanes, use_kernel=True,
                                   interpret=True)
        dkp = ccodes.shape[1] // 2
        mirror = binary_dot.crumb_affinity_jnp(
            ccodes[:, :dkp], ccodes[:, dkp:], qplanes[:, :dkp],
            qplanes[:, dkp:], dim=8 * dkp)
        assert ker.dtype == mirror.dtype == jnp.int32
        assert ker.shape == mirror.shape == (qplanes.shape[0], ccodes.shape[0])
        return np.asarray(ker), np.asarray(mirror)

    @pytest.mark.parametrize("b,n,dim", CRUMB_SHAPES,
                             ids=[f"b{b}-n{n}-d{d}" for b, n, d in CRUMB_SHAPES])
    def test_crumb_level_dot_equals_popcount_mirror(self, b, n, dim):
        """The kernel's int8 level dot and the mirror's AND + popcount
        identity are two formulas for one integer: == at every tiling."""
        rng = np.random.default_rng([b, n, dim])
        ccodes = jnp.asarray(rng.integers(0, 256, (n, dim // 4), np.uint8))
        qplanes = jnp.asarray(rng.integers(0, 256, (b, dim // 4), np.uint8))
        ker, mirror = self._crumb_pair(ccodes, qplanes)
        assert (ker == mirror).all()

    @pytest.mark.parametrize("q_byte,c_byte", [(0, 0), (0, 255), (255, 0),
                                               (255, 255)])
    def test_crumb_extremes(self, q_byte, c_byte):
        """All-zero planes are level -3 in every dim, all-ones +3: equal
        planes read exactly +9 d', opposite ones -9 d' (the level map, its
        sign, and no int8 overflow)."""
        b, n, dim = 3, 257, 2048
        ccodes = jnp.full((n, dim // 4), c_byte, jnp.uint8)
        qplanes = jnp.full((b, dim // 4), q_byte, jnp.uint8)
        ker, mirror = self._crumb_pair(ccodes, qplanes)
        want = 9 * dim if q_byte == c_byte else -9 * dim
        assert (ker == want).all() and (mirror == want).all()


# ---------------------------------------------------------------------------
# 2. m = n exactness pin
# ---------------------------------------------------------------------------

class TestExactnessPin:
    """m = n removes the cascade's only approximation (the survivor cut),
    so every remaining stage must reproduce the full scan exactly."""

    def test_stage_cascade_at_m_equals_n_is_full_scan(self):
        """Survivors at m = n enumerate every live row ascending (then -1),
        and the gathered rescore of that enumeration reproduces the packed
        full-scan scores on the live columns (gathered-scan tiling reduces
        in a different order than the full scan, so scores match to the
        harness's ulp tolerance — the id enumeration is exact)."""
        x = _corpus(200, 32)
        idx = MonaVec.build(x, metric="cosine", coarse="crumb")
        idx.delete([3, 17, 99])
        enc = idx.backend.enc
        live = np.asarray(~idx.mut.base_tombs)
        q_rot = qz.encode_query(jnp.asarray(_queries(x, 4)), enc)

        proxy = binary.coarse_scan_stage(q_rot, enc.ccodes, kind="crumb",
                                         use_kernel=False)
        cand = binary.survivor_topk_stage(proxy, jnp.asarray(live), m=200,
                                          vbound=9 * enc.dim_pad)
        want_rows = np.where(live)[0]
        got = np.asarray(cand)
        for row in got:
            np.testing.assert_array_equal(row[:want_rows.size], want_rows)
            assert np.all(row[want_rows.size:] == -1)

        rescored = np.asarray(binary.gathered_rescore_stage(
            q_rot, enc.packed, enc.qnorms, cand, bits=enc.bits,
            n4_dims=enc.n4_dims, metric="cosine", use_kernel=False))
        full = np.asarray(ops.score_packed(q_rot, enc, use_kernel=False))
        np.testing.assert_allclose(rescored[:, :want_rows.size],
                                   full[:, want_rows], rtol=2e-5, atol=2e-6)
        assert np.all(rescored[:, want_rows.size:] <= NEG)

    def test_rescore_mult_collapse_equals_plain_search(self):
        """rescore_mult * k >= n normalizes to the PLAIN plan — same
        fingerprint, same scores, same ids, no coarse pass at all."""
        x = _corpus(300, 32)
        idx = MonaVec.build(x, metric="cosine", coarse="sign")
        q = _queries(x, 6)
        s0, i0 = idx.search(q, k=K)
        s1, i1 = idx.search(q, k=K, rescore_mult=10_000)
        np.testing.assert_array_equal(i0, i1)
        np.testing.assert_array_equal(s0, s1)

    def test_rescore_mult_zero_is_plain_search(self):
        x = _corpus(300, 32)
        idx = MonaVec.build(x, metric="l2", coarse="crumb")
        q = _queries(x, 4)
        s0, i0 = idx.search(q, k=K)
        s1, i1 = idx.search(q, k=K, rescore_mult=0)
        np.testing.assert_array_equal(i0, i1)
        np.testing.assert_array_equal(s0, s1)


class TestSurvivorOracle:
    """Deterministic twin of the hypothesis suite (test_cascade_props):
    ``survivor_topk_stage`` equals the stable-top-m numpy oracle EXACTLY on
    a seeded grid that forces the hard regimes — heavy ties, sparse live
    masks, m > n, all-dead rows — so the survivor contract is exercised
    even where hypothesis is unavailable (same split as lifecycle_harness)."""

    VB = 64

    def _check(self, proxy, live, m, vbound=None):
        from tests.cascade_harness import survivor_oracle
        got = np.asarray(binary.survivor_topk_stage(
            jnp.asarray(proxy), jnp.asarray(live), m=m, vbound=vbound))
        np.testing.assert_array_equal(got, survivor_oracle(proxy, live, m))

    @pytest.mark.parametrize("seed", range(8))
    def test_seeded_grid(self, seed):
        rng = np.random.RandomState(seed)
        n = int(rng.randint(1, 48))
        m = int(rng.randint(1, n + 5))
        proxy = rng.randint(-self.VB, self.VB + 1,
                            size=(3, n)).astype(np.int32)
        live = rng.rand(n) < rng.rand()
        self._check(proxy, live, m, vbound=self.VB)
        self._check(proxy, live, m)                  # default VBOUND_MAX

    def test_heavy_ties_and_all_dead(self):
        rng = np.random.RandomState(99)
        proxy = rng.randint(-1, 2, size=(2, 30)).astype(np.int32)
        self._check(proxy, rng.rand(30) < 0.8, 12, vbound=self.VB)
        self._check(proxy, np.zeros(30, bool), 12, vbound=self.VB)
        self._check(proxy, np.ones(30, bool), 30, vbound=self.VB)   # m = n


# ---------------------------------------------------------------------------
# 3. Recall floors vs the full-scan oracle
# ---------------------------------------------------------------------------

class TestCascadeRecall:
    """Crumb cascade vs the full 4-bit scan's own top-k (the quantity the
    acceptance bound pins: the cascade can only lose rows the coarse proxy
    misranks).  All inputs are seed-fixed, so the floors below are
    deterministic replays with margin, not statistical hopes.  Floors rise
    with the budget because survivors at m2 > m1 are a SUPERSET of the
    survivors at m1 (top-m by proxy is monotone in m)."""

    FLOORS = {2: 0.55, 4: 0.70, 8: 0.80}

    def _assert_recall(self, idx, q, rm, floor):
        ids_full = idx.search(q, k=K)[1]
        ids_casc = idx.search(q, k=K, rescore_mult=rm)[1]
        rec = _recall(ids_casc, ids_full)
        assert rec >= floor, (rm, rec, floor)
        return rec

    @pytest.mark.parametrize("rm", sorted(FLOORS))
    def test_static(self, rm):
        x = _corpus(4000, 64)
        idx = MonaVec.build(x, metric="cosine", coarse="crumb")
        self._assert_recall(idx, _queries(x, 8), rm, self.FLOORS[rm])

    @pytest.mark.parametrize("rm", sorted(FLOORS))
    def test_mutated(self, rm):
        """add() segments derive their own codes; delete() tombstones must
        never surface through the survivor cut."""
        x = _corpus(3000, 64)
        idx = MonaVec.build(x, metric="cosine", coarse="crumb")
        idx.add(_corpus(600, 64, seed=43))
        idx.delete(list(range(0, 3000, 7)) + list(range(3000, 3060)))
        q = _queries(x, 8)
        self._assert_recall(idx, q, rm, self.FLOORS[rm])
        ids = idx.search(q, k=K, rescore_mult=rm)[1]
        dead = set(range(0, 3000, 7)) | set(range(3000, 3060))
        assert not (set(ids.ravel().tolist()) - {int(SENTINEL_ID)}) & dead

    @pytest.mark.parametrize("rm", sorted(FLOORS))
    def test_sharded(self, rm):
        """The shard_map cascade (local coarse -> local survivors -> local
        rescore -> exact cross-shard merge) meets the same floors."""
        from repro.dist.sharded_index import ShardedMonaVec
        x = _corpus(4000, 64)
        idx = MonaVec.build(x, metric="cosine", coarse="crumb")
        sharded = ShardedMonaVec.shard(idx)
        q = _queries(x, 8)
        ids_full = idx.search(q, k=K)[1]
        ids_casc = sharded.search(q, k=K, rescore_mult=rm)[1]
        rec = _recall(ids_casc, ids_full)
        assert rec >= self.FLOORS[rm], (rm, rec)

    def test_budget_monotonicity(self):
        """Bigger budget, never-worse overlap with the full scan — the
        survivor-superset property made visible end to end."""
        x = _corpus(4000, 64)
        idx = MonaVec.build(x, metric="cosine", coarse="crumb")
        q = _queries(x, 8)
        recs = [self._assert_recall(idx, q, rm, 0.0) for rm in (2, 4, 8)]
        assert recs == sorted(recs), recs


# ---------------------------------------------------------------------------
# 4. Edge contracts: sentinel padding + knob validation
# ---------------------------------------------------------------------------

class TestSentinelPadding:
    def test_fewer_live_rows_than_k(self):
        """5 live rows, k = 10, cascade budget m = 20 < n: every live row
        survives the cut, so the result equals the full scan exactly —
        5 real ids then SENTINEL_ID / NEG padding, exactly k columns (ids
        exact; scores to the gathered-scan ulp tolerance)."""
        x = _corpus(60, 32)
        idx = MonaVec.build(x, metric="cosine", coarse="crumb")
        idx.delete(list(range(55)))
        q = _queries(x, 3)
        s, ids = idx.search(q, k=K, rescore_mult=2)
        assert ids.shape == (3, K) and s.shape == (3, K)
        for row_s, row_i in zip(s, ids):
            real = row_i[row_i != SENTINEL_ID]
            assert sorted(real.tolist()) == [55, 56, 57, 58, 59]
            assert np.all(row_i[5:] == SENTINEL_ID)
            assert np.all(row_s[5:] <= NEG)
        s0, i0 = idx.search(q, k=K)
        np.testing.assert_array_equal(ids, i0)
        np.testing.assert_allclose(s, s0, rtol=2e-5, atol=2e-6)

    def test_exactly_k_real_results_at_tight_budget(self):
        """With n live >> k the cascade must return k REAL ids (the
        survivor stage always yields m >= k live candidates)."""
        x = _corpus(500, 32)
        idx = MonaVec.build(x, metric="cosine", coarse="sign")
        s, ids = idx.search(_queries(x, 4), k=K, rescore_mult=2)
        assert not np.any(ids == SENTINEL_ID)
        assert np.all(s > NEG)


class TestKnobValidation:
    def test_rejected_on_ivf(self):
        x = _corpus(64, 16)
        idx = MonaVec.build(x, metric="cosine", index="ivf", nlist=4,
                            train_iters=3)
        with pytest.raises(TypeError, match="unexpected search kwargs"):
            idx.search(_queries(x, 2), k=5, rescore_mult=2)

    def test_rejected_on_hnsw(self):
        x = _corpus(64, 16)
        idx = MonaVec.build(x, metric="cosine", index="hnsw", m=4,
                            ef_construction=16)
        with pytest.raises(TypeError, match="unexpected search kwargs"):
            idx.search(_queries(x, 2), k=5, rescore_mult=2)

    def test_requires_coarse_codes(self):
        x = _corpus(64, 16)
        idx = MonaVec.build(x, metric="cosine")          # no coarse=
        with pytest.raises(ValueError, match="binarized coarse code"):
            idx.search(_queries(x, 2), k=5, rescore_mult=2)

    def test_negative_rejected(self):
        x = _corpus(64, 16)
        idx = MonaVec.build(x, metric="cosine", coarse="sign")
        with pytest.raises(ValueError, match="rescore_mult must be >= 0"):
            idx.search(_queries(x, 2), k=5, rescore_mult=-1)

    def test_unknown_coarse_kind_rejected_at_build(self):
        with pytest.raises(ValueError, match="unknown coarse kind"):
            MonaVec.build(_corpus(32, 16), metric="cosine", coarse="trit")

    def test_coarse_requires_bruteforce(self):
        with pytest.raises(ValueError, match="requires the bruteforce"):
            MonaVec.build(_corpus(64, 16), metric="cosine", index="ivf",
                          nlist=4, train_iters=3, coarse="sign")
