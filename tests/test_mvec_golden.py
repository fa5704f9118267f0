"""Golden-file determinism for the `.mvec` format (paper §3.8, DESIGN.md §6).

Three layers of byte-identity, each pinned against checked-in fixtures:
  1. fixture integrity — the committed bytes hash to `golden/digests.json`;
  2. `load → save` is the identity on every supported version (6/7/8);
  3. a fresh build from the same inputs reproduces the committed bytes —
     the paper's "same inputs, same file, any platform" claim, which until
     now had zero golden coverage.

Plus the truncation/garbage bugfix: every prefix of a valid file and every
garbage-tailed file must raise ValueError naming the short block —
previously `np.frombuffer` either crashed with an opaque message or
silently misparsed short reads.
"""

import hashlib
import json
import os
import struct

import numpy as np
import pytest

from repro.core import MonaVec
from repro.core import mvec_format as fmt
from tests.golden import make_fixtures as gold

GOLD = os.path.join(os.path.dirname(os.path.abspath(__file__)), "golden")

with open(os.path.join(GOLD, "digests.json")) as fh:
    DIGESTS = json.load(fh)


def _sha(b: bytes) -> str:
    return hashlib.sha256(b).hexdigest()


class TestGoldenFixtures:
    @pytest.mark.parametrize("name", sorted(DIGESTS))
    def test_fixture_integrity(self, name):
        raw = open(os.path.join(GOLD, name), "rb").read()
        assert _sha(raw) == DIGESTS[name], f"checked-in fixture {name} changed"

    @pytest.mark.parametrize("name", sorted(DIGESTS))
    def test_load_save_is_identity(self, name, tmp_path):
        """save(load(f)) == f byte-for-byte, version preserved."""
        src = os.path.join(GOLD, name)
        out = str(tmp_path / "resaved.mvec")
        MonaVec.load(src).save(out)
        raw_in = open(src, "rb").read()
        raw_out = open(out, "rb").read()
        assert raw_in == raw_out
        assert raw_out[4] == raw_in[4]          # VERSION byte round-trips

    @pytest.mark.parametrize("name", sorted(gold.FIXTURES))
    def test_rebuild_reproduces_digest(self, name, tmp_path):
        """§3.8: the same inputs build the same file, byte for byte."""
        out = str(tmp_path / "rebuilt.mvec")
        gold.FIXTURES[name]().save(out)
        assert _sha(open(out, "rb").read()) == DIGESTS[name]

    @pytest.mark.parametrize("name", sorted(gold.FIXTURES))
    def test_rebuild_reproduces_codes(self, name, tmp_path):
        """The quantized and coarse codes of every segment of a fresh build
        equal the committed file's: a stored seed means one sign stream."""
        out = str(tmp_path / "rebuilt.mvec")
        gold.FIXTURES[name]().save(out)
        got, want = fmt.load(out), fmt.load(os.path.join(GOLD, name))
        assert len(got.extras) == len(want.extras)
        pairs = [(got.enc, want.enc)] + [
            (a.enc, b.enc) for a, b in zip(got.extras, want.extras)]
        for a, b in pairs:
            np.testing.assert_array_equal(np.asarray(a.packed), np.asarray(b.packed))
            assert (a.ccodes is None) == (b.ccodes is None)
            if a.ccodes is not None:
                np.testing.assert_array_equal(np.asarray(a.ccodes),
                                              np.asarray(b.ccodes))

    def test_sign_stream_is_pinned(self):
        """The seed -> diagonal map every committed file was written with,
        independent of JAX's default threefry bit layout."""
        from repro.core.rhdh import rademacher_signs
        signs = np.asarray(rademacher_signs(7, 16)).astype(int).tolist()
        assert signs == [1, 1, -1, 1, -1, 1, 1, -1, -1, -1, 1, -1, -1, 1, 1, 1]

    def test_versions_as_committed(self):
        assert open(os.path.join(GOLD, "v6_bruteforce.mvec"), "rb").read()[4] == 6
        assert open(os.path.join(GOLD, "v7_perm_bruteforce.mvec"), "rb").read()[4] == 7
        assert open(os.path.join(GOLD, "v8_segmented_ivf.mvec"), "rb").read()[4] == 8
        assert open(os.path.join(GOLD, "v9_meta_bruteforce.mvec"), "rb").read()[4] == 9
        assert open(os.path.join(GOLD, "v10_coarse_bruteforce.mvec"), "rb").read()[4] == 10
        assert open(os.path.join(GOLD, "v11_tuned_ivf.mvec"), "rb").read()[4] == 11

    def test_v9_meta_survives_roundtrip(self, tmp_path):
        """The v9 fixture's columns load with exact values and survive a
        search: the metadata block is data, not decoration."""
        idx = MonaVec.load(os.path.join(GOLD, "v9_meta_bruteforce.mvec"))
        assert idx.meta is not None
        assert idx.meta.schema == (("price", "i64"), ("score", "f64"),
                                   ("cat", "str"))
        assert idx.meta.n_rows == idx.n_total == 26
        np.testing.assert_array_equal(
            idx.meta["price"].values[:3], np.array([-10, -7, -4]))
        assert idx.meta["cat"].vocab == ["red", "green", "blue", "violet"]


    def test_v10_coarse_survives_roundtrip(self):
        """The v10 fixture's CODE blocks load on every segment, and the
        persisted bytes equal a fresh derivation from the packed codes —
        the 'v10 is a cache' clause of DESIGN.md §11."""
        from repro.core import binary
        idx = MonaVec.load(os.path.join(GOLD, "v10_coarse_bruteforce.mvec"))
        enc = idx.backend.enc
        assert enc.coarse == "crumb" and enc.ccodes is not None
        assert all(s.enc.ccodes is not None for s in idx.mut.extras)
        for e in [enc] + [s.enc for s in idx.mut.extras]:
            rederived = binary.derive_codes(
                e.packed, bits=e.bits, n4_dims=e.n4_dims,
                dim_pad=e.dim_pad, kind="crumb")
            np.testing.assert_array_equal(np.asarray(e.ccodes), rederived)
        # The loaded codes are live: a cascade search runs and returns k ids.
        q = np.random.RandomState(5).randn(3, 16).astype(np.float32)
        scores, ids = idx.search(q, k=4, rescore_mult=2)
        assert ids.shape == (3, 4)

    def test_v11_tune_survives_roundtrip(self):
        """The v11 fixture's TUNE block loads as a full TuneResult — chosen
        knobs, the swept ladder with its measured recalls, and the boost
        curve — and the tuned knob is what resolved_knobs() serves by
        default (DESIGN.md §12)."""
        idx = MonaVec.load(os.path.join(GOLD, "v11_tuned_ivf.mvec"))
        t = idx.tuned
        assert t is not None and t.met_target
        assert (t.recall_target, t.k, t.n_queries, t.seed) == (0.9, 4, 8, 11)
        assert t.knobs == {"nprobe": 3}
        assert [r.value for r in t.ladder["nprobe"]] == [1, 2, 3]
        recalls = [r.recall for r in t.ladder["nprobe"]]
        assert recalls == sorted(recalls) and recalls[-1] == 1.0
        assert t.boost is not None and len(t.boost.points) >= 1
        assert idx.resolved_knobs(4) == {"nprobe": 3}

    def test_unknown_version_names_highest_supported(self, tmp_path):
        """Bugfix regression: the unknown-version error must tell the user
        the highest version this build reads, not just echo the bad byte."""
        raw = bytearray(open(os.path.join(GOLD, "v6_bruteforce.mvec"), "rb").read())
        raw[4] = 99
        p = str(tmp_path / "future.mvec")
        with open(p, "wb") as fh:
            fh.write(bytes(raw))
        with pytest.raises(ValueError, match=r"version 99.*highest supported "
                                             r"version is 11"):
            fmt.load(p)


class TestSaveLoadFixedPoint:
    """build → save → load → save is a fixed point for fresh indexes of
    every backend, mutated and not."""

    @pytest.mark.parametrize("index,kw", [
        ("bruteforce", {}),
        ("ivf", {"nlist": 4, "train_iters": 5}),
        ("hnsw", {"m": 4, "ef_construction": 24}),
    ])
    @pytest.mark.parametrize("mutate", [False, True])
    def test_fixed_point(self, index, kw, mutate, tmp_path):
        rng = np.random.RandomState(11)
        idx = MonaVec.build(rng.randn(18, 8).astype(np.float32),
                            metric="cosine", index=index, **kw)
        if mutate:
            idx.add(rng.randn(5, 8).astype(np.float32))
            idx.delete([0, 19])
        p1, p2 = str(tmp_path / "a.mvec"), str(tmp_path / "b.mvec")
        idx.save(p1)
        MonaVec.load(p1).save(p2)
        raw1 = open(p1, "rb").read()
        assert raw1 == open(p2, "rb").read()
        assert raw1[4] == (8 if mutate else 6)


class TestTruncationFuzz:
    """`mvec_format.load` on damaged files: explicit ValueError naming the
    short block at EVERY truncation offset, never an np.frombuffer misparse."""

    @pytest.mark.parametrize("name", ["v6_bruteforce.mvec",
                                      "v8_segmented_ivf.mvec",
                                      "v9_meta_bruteforce.mvec",
                                      "v10_coarse_bruteforce.mvec",
                                      "v11_tuned_ivf.mvec"])
    def test_every_truncation_offset_raises(self, name, tmp_path):
        raw = open(os.path.join(GOLD, name), "rb").read()
        p = str(tmp_path / "cut.mvec")
        for cut in range(len(raw)):
            with open(p, "wb") as fh:
                fh.write(raw[:cut])
            with pytest.raises(ValueError):
                fmt.load(p)

    def test_truncation_error_names_the_block(self, tmp_path):
        raw = open(os.path.join(GOLD, "v6_bruteforce.mvec"), "rb").read()
        p = str(tmp_path / "cut.mvec")
        with open(p, "wb") as fh:          # cut inside the VECTORS payload
            fh.write(raw[:fmt.HEADER_LEN + 8 + 10])
        with pytest.raises(ValueError, match="truncated.*vectors"):
            fmt.load(p)
        with open(p, "wb") as fh:          # header alone is also short
            fh.write(raw[:20])
        with pytest.raises(ValueError, match="header"):
            fmt.load(p)

    def test_garbage_tail_rejected(self, tmp_path):
        raw = open(os.path.join(GOLD, "v8_segmented_ivf.mvec"), "rb").read()
        p = str(tmp_path / "tail.mvec")
        with open(p, "wb") as fh:
            fh.write(raw + b"\xde\xad\xbe\xef")
        with pytest.raises(ValueError, match="garbage tail"):
            fmt.load(p)

    def test_garbage_inside_index_blob_rejected(self, tmp_path):
        """Junk hidden INSIDE the INDEX_DATA region (blob length prefix
        inflated to cover it) passes the file-level EOF check — the backend
        blob readers must reject it themselves."""
        rng = np.random.RandomState(33)
        idx = MonaVec.build(rng.randn(16, 8).astype(np.float32),
                            metric="cosine", index="ivf", nlist=2,
                            train_iters=3)
        p = str(tmp_path / "ivf.mvec")
        idx.save(p)
        raw = open(p, "rb").read()
        blob_len = len(fmt.load(p).index_data)
        pos = len(raw) - blob_len - 8              # blob is the final section
        assert struct.unpack("<Q", raw[pos:pos + 8])[0] == blob_len
        junk = b"\xde\xad\xbe\xef"
        doctored = (raw[:pos] + struct.pack("<Q", blob_len + len(junk))
                    + raw[pos + 8:] + junk)
        with open(p, "wb") as fh:
            fh.write(doctored)
        fmt.load(p)                                 # file-level parse passes
        with pytest.raises(ValueError, match="garbage tail"):
            MonaVec.load(p)                         # blob reader rejects

    def test_oversized_length_prefix_rejected(self, tmp_path):
        """A corrupt block length that claims more bytes than the file has
        must error, not frombuffer whatever is left."""
        raw = bytearray(open(os.path.join(GOLD, "v6_bruteforce.mvec"), "rb").read())
        raw[fmt.HEADER_LEN:fmt.HEADER_LEN + 8] = struct.pack("<Q", 1 << 40)
        p = str(tmp_path / "huge.mvec")
        with open(p, "wb") as fh:
            fh.write(bytes(raw))
        with pytest.raises(ValueError, match="truncated"):
            fmt.load(p)
