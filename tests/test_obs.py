"""repro.obs: the observability contract (DESIGN.md §9).

The two load-bearing guarantees, both asserted here:

  1. *Bit-identity* — a metrics-enabled or actively-traced search returns
     bytes identical to a disabled one (host-side timers wrap compiled
     calls, they never enter a traced function).
  2. *Deterministic snapshot shape* — metric names, label sets, and
     histogram bucket edges are fixed; the edge ladders are pinned as
     golden tuples, so changing them is a visible schema change.

Plus the registry semantics everything else leans on: counter/gauge/
histogram behavior, label isolation, kind/edge conflicts, Prometheus
rendering, trace-span nesting, PlanCache eviction accounting, and the
shared DeltaStats mixin.
"""

import dataclasses
import json
import logging

import jax
import numpy as np
import pytest

from repro import engine, obs
from repro.core import MonaVec, TenantRegistry
from repro.engine.plan import PlanCache, PlanKey, SearchPlan, plan_key_digest
from repro.obs.registry import MetricsRegistry


def _index(n=64, dim=16, seed=0):
    rng = np.random.RandomState(seed)
    return MonaVec.build(rng.randn(n, dim).astype(np.float32), metric="cosine")


# ---------------------------------------------------------------------------
# Golden edge ladders: part of the committed snapshot schema.
# ---------------------------------------------------------------------------

class TestGoldenEdges:
    def test_latency_edges_pinned(self):
        assert obs.DEFAULT_LATENCY_EDGES_US == (
            1, 2.5, 5, 10, 25, 50, 100, 250, 500,
            1_000, 2_500, 5_000, 10_000, 25_000, 50_000,
            100_000, 250_000, 500_000,
            1_000_000, 2_500_000, 5_000_000, 10_000_000,
        )

    def test_count_edges_pinned(self):
        assert obs.DEFAULT_COUNT_EDGES == (
            1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024)

    def test_edges_travel_with_snapshot(self):
        reg = MetricsRegistry()
        reg.histogram("lat").observe(3.0)
        snap = reg.snapshot()
        assert snap["histograms"]["lat"]["edges"] == \
            list(obs.DEFAULT_LATENCY_EDGES_US)


# ---------------------------------------------------------------------------
# Registry semantics.
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_counter_and_label_isolation(self):
        reg = MetricsRegistry()
        reg.counter("req").inc()
        reg.counter("req", ns="a").inc(2)
        reg.counter("req", ns="b").inc(5)
        snap = reg.snapshot()["counters"]
        assert snap == {"req": 1, 'req{ns="a"}': 2, 'req{ns="b"}': 5}

    def test_label_order_is_canonical(self):
        reg = MetricsRegistry()
        reg.counter("m", b="2", a="1").inc()
        reg.counter("m", a="1", b="2").inc()   # same series, any kwarg order
        assert reg.snapshot()["counters"] == {'m{a="1",b="2"}': 2}

    def test_gauge_overwrites(self):
        reg = MetricsRegistry()
        reg.gauge("depth").set(3)
        reg.gauge("depth").set(7)
        assert reg.snapshot()["gauges"] == {"depth": 7.0}

    def test_histogram_bucketing_is_le(self):
        """counts[i] tallies v <= edges[i]: an observation ON an edge lands
        in that edge's bucket (bisect_left), above the last edge overflows."""
        reg = MetricsRegistry()
        h = reg.histogram("h", edges=(1, 10, 100))
        for v in (0.5, 1.0, 1.5, 10.0, 99.0, 1e9):
            h.observe(v)
        assert h.counts == [2, 2, 1, 1]
        assert h.count == 6
        assert h.min == 0.5 and h.max == 1e9
        assert h.total == pytest.approx(0.5 + 1.0 + 1.5 + 10.0 + 99.0 + 1e9)

    def test_histogram_quantile(self):
        reg = MetricsRegistry()
        h = reg.histogram("h", edges=(1, 10, 100))
        for v in [0.5] * 50 + [50.0] * 49 + [1e9]:
            h.observe(v)
        assert h.quantile(0.5) == 1       # upper edge of the median's bucket
        assert h.quantile(0.99) == 100
        assert h.quantile(1.0) == 1e9     # +Inf bucket reports observed max

    def test_unsorted_edges_rejected(self):
        with pytest.raises(ValueError, match="sorted"):
            MetricsRegistry().histogram("h", edges=(10, 1))

    def test_kind_conflict_rejected(self):
        reg = MetricsRegistry()
        reg.counter("m")
        with pytest.raises(ValueError, match="already registered as a"):
            reg.gauge("m")
        with pytest.raises(ValueError, match="already registered as a"):
            reg.histogram("m")

    def test_edge_conflict_rejected(self):
        reg = MetricsRegistry()
        reg.histogram("h", edges=(1, 2))
        reg.histogram("h", edges=(1, 2))   # same edges: fine
        with pytest.raises(ValueError, match="already registered with edges"):
            reg.histogram("h", edges=(1, 3))

    def test_empty_histogram_snapshot(self):
        reg = MetricsRegistry()
        reg.histogram("h", edges=(1,))
        snap = reg.snapshot()["histograms"]["h"]
        assert snap["count"] == 0
        assert snap["min"] is None and snap["max"] is None

    def test_snapshot_json_roundtrips(self):
        reg = MetricsRegistry()
        reg.counter("c", x="1").inc()
        reg.histogram("h", edges=(1, 2)).observe(1.5)
        assert json.loads(reg.snapshot_json()) == reg.snapshot()

    def test_reset(self):
        reg = MetricsRegistry()
        reg.counter("c").inc()
        reg.reset()
        assert reg.snapshot() == {"counters": {}, "gauges": {},
                                  "histograms": {}}


class TestPrometheus:
    def test_exposition_format(self):
        reg = MetricsRegistry()
        reg.counter("plan_cache.hits").inc(3)
        reg.gauge("queue.depth", ns="a").set(2)
        h = reg.histogram("stage.us", edges=(1, 2.5), stage="scan")
        h.observe(0.5)
        h.observe(2.0)
        h.observe(99.0)
        text = reg.to_prometheus()
        lines = text.splitlines()
        assert "# TYPE plan_cache_hits counter" in lines
        assert "plan_cache_hits 3" in lines
        assert 'queue_depth{ns="a"} 2' in lines
        # Cumulative buckets, +Inf last, then _sum/_count.
        assert 'stage_us_bucket{stage="scan",le="1"} 1' in lines
        assert 'stage_us_bucket{stage="scan",le="2.5"} 2' in lines
        assert 'stage_us_bucket{stage="scan",le="+Inf"} 3' in lines
        assert 'stage_us_count{stage="scan"} 3' in lines
        assert text.endswith("\n")


class TestSnapshotArithmetic:
    def test_counter_deltas_and_family_total(self):
        reg = MetricsRegistry()
        reg.counter("req", ns="a").inc(2)
        before = reg.snapshot()
        reg.counter("req", ns="a").inc(3)
        reg.counter("req", ns="b").inc(1)   # new key counts from zero
        delta = obs.counter_deltas(reg.snapshot(), before)
        assert delta == {'req{ns="a"}': 3, 'req{ns="b"}': 1}
        assert obs.counter_total(delta, "req") == 4
        assert obs.counter_total(delta, "re") == 0   # no prefix false-match

    def test_render_key(self):
        assert obs.render_key("m", ()) == "m"
        assert obs.render_key("m", (("a", "1"), ("b", "2"))) == \
            'm{a="1",b="2"}'


class TestEnableToggle:
    def test_disabled_helpers_are_noops(self):
        before = obs.registry().snapshot()
        prev = obs.enable(False)
        try:
            obs.inc("test_obs.should_not_exist")
            obs.observe("test_obs.should_not_exist_h", 1.0)
            with obs.timed_span("t", histogram="test_obs.should_not_exist_h2"):
                pass
            snap = obs.registry().snapshot()
            assert "test_obs.should_not_exist" not in snap["counters"]
            assert "test_obs.should_not_exist_h" not in snap["histograms"]
            assert "test_obs.should_not_exist_h2" not in snap["histograms"]
            assert obs.counter_deltas(snap, before) == \
                {k: 0 for k in before["counters"]}
        finally:
            obs.enable(prev)


# ---------------------------------------------------------------------------
# Tracing.
# ---------------------------------------------------------------------------

class TestTrace:
    def test_span_nesting(self):
        with obs.trace("query", batch=4) as tr:
            with obs.span("outer"):
                with obs.span("inner", stage="scan"):
                    pass
            with obs.span("sibling"):
                pass
        d = tr.to_dict()
        assert d["name"] == "query" and d["attrs"] == {"batch": 4}
        assert [c["name"] for c in d["children"]] == ["outer", "sibling"]
        assert d["children"][0]["children"][0]["name"] == "inner"
        assert d["children"][0]["children"][0]["attrs"] == {"stage": "scan"}
        # finish() closed everything.
        assert all(c["duration_us"] is not None for c in d["children"])

    def test_trace_restores_outer_trace(self):
        assert obs.current_trace() is None
        with obs.trace("a") as ta:
            assert obs.current_trace() is ta
            with obs.trace("b") as tb:
                assert obs.current_trace() is tb
            assert obs.current_trace() is ta
        assert obs.current_trace() is None

    def test_exception_marks_span_and_unwinds(self):
        with obs.trace("q") as tr:
            with pytest.raises(RuntimeError):
                with obs.span("boom"):
                    raise RuntimeError("x")
            sp = obs.span("after")
            with sp:
                pass
        d = tr.to_dict()
        assert [c["name"] for c in d["children"]] == ["boom", "after"]
        assert d["children"][0]["attrs"]["error"] == "RuntimeError"
        assert d["children"][1]["children"] == []   # no nesting under boom

    def test_timed_span_is_free_when_idle(self):
        """No active trace + no histogram -> the shared null CM."""
        prev = obs.enable(False)
        try:
            cm = obs.timed_span("x", histogram="h")
        finally:
            obs.enable(prev)
        cm2 = obs.span("y") if obs.current_trace() is None else None
        assert cm is (cm2 if cm2 is not None else cm)
        with cm as sp:
            assert sp is None

    def test_timed_span_feeds_histogram(self):
        before = obs.registry().snapshot()
        with obs.timed_span("x", histogram="test_obs.span_us",
                            labels={"stage": "s"}):
            pass
        snap = obs.registry().snapshot()
        h = snap["histograms"]['test_obs.span_us{stage="s"}']
        base = before["histograms"].get(
            'test_obs.span_us{stage="s"}', {"count": 0})
        assert h["count"] == base["count"] + 1

    def test_render_lists_tree(self):
        with obs.trace("q") as tr:
            with obs.span("child", k=10):
                pass
        text = tr.render()
        assert text.splitlines()[0].startswith("q ")
        assert "  child" in text and "k=10" in text

    def test_tracer_samples_one_in_n(self):
        tr = obs.Tracer(sample_every=2)
        captured = []
        for i in range(5):
            with tr.maybe(f"call{i}") as t:
                if t is not None:
                    captured.append(i)
        assert captured == [0, 2, 4]
        names = [t.root.name for t in tr.drain()]
        assert names == ["call0", "call2", "call4"]
        assert tr.drain() == []

    def test_tracer_disabled_and_bounded(self):
        tr = obs.Tracer(sample_every=0)
        with tr.maybe("x") as t:
            assert t is None
        assert tr.drain() == []
        tr = obs.Tracer(sample_every=1, keep=2)
        for i in range(5):
            with tr.maybe(f"c{i}"):
                pass
        assert len(tr.drain()) == 2


# ---------------------------------------------------------------------------
# Bit-identity: instrumentation never changes results.
# ---------------------------------------------------------------------------

class TestBitIdentity:
    def test_enabled_disabled_and_traced_searches_identical(self, tmp_path):
        idx = _index(n=96, dim=24, seed=3)
        rng = np.random.RandomState(7)
        q = rng.randn(5, 24).astype(np.float32)

        vals_on, ids_on = idx.search(q, k=10)
        prev = obs.enable(False)
        try:
            vals_off, ids_off = idx.search(q, k=10)
        finally:
            obs.enable(prev)
        with obs.trace("bit-identity"):
            vals_tr, ids_tr = idx.search(q, k=10)
        with jax.profiler.trace(str(tmp_path)):
            vals_pr, ids_pr = idx.search(q, k=10)

        for vals, ids in ((vals_off, ids_off), (vals_tr, ids_tr),
                          (vals_pr, ids_pr)):
            assert np.asarray(vals_on).tobytes() == np.asarray(vals).tobytes()
            assert np.asarray(ids_on).tobytes() == np.asarray(ids).tobytes()

    def test_trace_captures_engine_stages(self):
        idx = _index(n=64, dim=16, seed=5)
        q = np.random.RandomState(1).randn(3, 16).astype(np.float32)
        idx.search(q, k=5)                      # warm the plan outside
        with obs.trace("q") as tr:
            idx.search(q, k=5)
        children = tr.to_dict()["children"]
        assert [c["name"] for c in children] == ENGINE_PHASES
        execute = children[ENGINE_PHASES.index("execute")]
        assert [c["name"] for c in execute["children"]] == [
            "stage:rotate", "stage:scan", "stage:finalize"]


#: The engine's host phases, in the order one search runs them.
ENGINE_PHASES = ["prepare", "plan_lookup", "execute", "sync", "finish"]


def _stage_histograms():
    return {k: h["count"] for k, h in obs.registry().snapshot()["histograms"].items()
            if k.startswith("engine.stage_us")}


class TestEnginePhases:
    def test_phase_histograms_partition_the_search(self):
        """Every engine phase feeds ``engine.stage_us``; the plan stages
        inside ``execute`` feed no histogram."""
        idx = _index(n=64, dim=16, seed=5)
        q = np.random.RandomState(1).randn(3, 16).astype(np.float32)
        idx.search(q, k=5)
        before = _stage_histograms()
        idx.search(q, k=5)
        grew = {k for k, n in _stage_histograms().items() if n > before.get(k, 0)}
        assert grew == {
            f'engine.stage_us{{backend="BruteForceIndex",stage="{p}"}}'
            for p in ENGINE_PHASES}

    def test_profiler_trace_carries_phases_and_named_stages(self, tmp_path):
        """One search under ``jax.profiler.trace`` writes each phase as a
        ``monavec.*`` span, in order and inside the caller's annotation, with
        the stage spans and their ``monavec_<stage>`` programs inside
        ``execute`` — also with metrics off and no QueryTrace active."""
        from jax.profiler import ProfileData

        idx = _index(n=64, dim=16, seed=5)
        q = np.random.RandomState(1).randn(3, 16).astype(np.float32)
        idx.search(q, k=5)
        prev = obs.enable(False)
        try:
            with jax.profiler.trace(str(tmp_path)):
                with jax.profiler.TraceAnnotation("caller"):
                    idx.search(q, k=5)
        finally:
            obs.enable(prev)
        [path] = tmp_path.rglob("*.xplane.pb")
        events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                  for plane in ProfileData.from_file(str(path)).planes
                  for line in plane.lines for e in line.events]

        def spans(name):
            return [(s, t) for n, s, t in events if n == name]

        [(c0, c1)] = spans("caller")
        phases = [spans(obs.PROFILER_PREFIX + p) for p in ENGINE_PHASES]
        assert all(len(p) == 1 for p in phases)
        phases = [p[0] for p in phases]
        assert c0 <= phases[0][0] and phases[-1][1] <= c1
        for (_, end), (start, _) in zip(phases, phases[1:]):
            assert end <= start
        e0, e1 = phases[ENGINE_PHASES.index("execute")]
        for stage in ("rotate", "scan", "finalize"):
            [(s0, s1)] = spans(f"{obs.PROFILER_PREFIX}stage:{stage}")
            assert e0 <= s0 and s1 <= e1
            program = spans(f"PjitFunction(monavec_{stage})")
            assert program and all(s0 <= a and b <= s1 for a, b in program)
        assert not spans("PjitFunction(wrapper)")


# ---------------------------------------------------------------------------
# Per-namespace labels through TenantRegistry.
# ---------------------------------------------------------------------------

class TestNamespaceLabels:
    def test_label_isolation_across_namespaces(self):
        reg = TenantRegistry()
        reg.put("team-a", "docs", _index(seed=1))
        reg.put("team-b", "docs", _index(seed=2))
        sa = reg.searcher("team-a", "docs", k=5)
        sb = reg.searcher("team-b", "docs", k=5)
        q = np.random.RandomState(0).randn(2, 16).astype(np.float32)

        before = obs.registry().snapshot()
        sa(q)
        sa(q)
        sb(q)
        delta = obs.counter_deltas(obs.registry().snapshot(), before)
        key_a = 'tenancy.requests{collection="docs",namespace="team-a"}'
        key_b = 'tenancy.requests{collection="docs",namespace="team-b"}'
        assert delta[key_a] == 2
        assert delta[key_b] == 1
        hists = obs.registry().snapshot()["histograms"]
        ha = hists['tenancy.search_us{collection="docs",namespace="team-a"}']
        hb = hists['tenancy.search_us{collection="docs",namespace="team-b"}']
        assert ha["count"] >= 2 and hb["count"] >= 1

    def test_rejection_counts_error(self):
        reg = TenantRegistry()
        reg.put("team-a", "docs", _index(seed=1))
        before = obs.registry().snapshot()
        with pytest.raises(KeyError):
            reg.get("team-a", "nope")
        delta = obs.counter_deltas(obs.registry().snapshot(), before)
        assert obs.counter_total(delta, "tenancy.errors") == 1


# ---------------------------------------------------------------------------
# PlanCache eviction accounting (satellite).
# ---------------------------------------------------------------------------

def _dummy_key(i):
    return PlanKey(fingerprint=("test", i), bucket=8, k=10,
                   dispatch=(False, False), knobs=())


class TestPlanCacheEvictions:
    def test_eviction_counts_and_gauges(self, caplog):
        cache = PlanCache(maxsize=2)
        before = obs.registry().snapshot()
        with caplog.at_level(logging.DEBUG, logger="repro.engine.plan"):
            for i in range(3):
                cache.get_or_build(_dummy_key(i),
                                   lambda: SearchPlan(_dummy_key(i), None))
        assert cache.stats.evictions == 1
        assert cache.stats.misses == 3
        assert len(cache) == 2
        delta = obs.counter_deltas(obs.registry().snapshot(), before)
        assert delta["plan_cache.evictions"] == 1
        assert delta["plan_cache.misses"] == 3
        gauges = obs.registry().snapshot()["gauges"]
        assert gauges["plan_cache.size"] == 2.0
        assert gauges["plan_cache.capacity"] == 2.0
        # The DEBUG log names the evicted key by digest (key 0 was LRU).
        assert plan_key_digest(_dummy_key(0)) in caplog.text

    def test_lru_order_hit_refreshes(self):
        cache = PlanCache(maxsize=2)
        cache.get_or_build(_dummy_key(0), lambda: SearchPlan(_dummy_key(0), None))
        cache.get_or_build(_dummy_key(1), lambda: SearchPlan(_dummy_key(1), None))
        cache.get_or_build(_dummy_key(0), lambda: SearchPlan(_dummy_key(0), None))
        cache.get_or_build(_dummy_key(2), lambda: SearchPlan(_dummy_key(2), None))
        assert cache.stats.hits == 1 and cache.stats.evictions == 1
        # Key 1 (least recently used) was the one evicted.
        assert cache.get_or_build(
            _dummy_key(0), lambda: SearchPlan(_dummy_key(0), None)) is not None
        assert cache.stats.misses == 3   # key 0 still cached

    def test_plan_key_digest_stable(self):
        d = plan_key_digest(_dummy_key(0))
        assert len(d) == 12 and int(d, 16) >= 0
        assert d == plan_key_digest(_dummy_key(0))
        assert d != plan_key_digest(_dummy_key(1))


# ---------------------------------------------------------------------------
# DeltaStats mixin (satellite: shared by PlanStats and BatcherStats).
# ---------------------------------------------------------------------------

class TestDeltaStats:
    def test_generic_snapshot_since(self):
        @dataclasses.dataclass
        class S(obs.DeltaStats):
            a: int = 0
            b: int = 0

        s = S(a=5, b=2)
        before = s.snapshot()
        s.a += 3
        s.b += 1
        d = s.since(before)
        assert (d.a, d.b) == (3, 1)
        assert (before.a, before.b) == (5, 2)   # snapshot is a copy

    def test_type_mismatch_rejected(self):
        @dataclasses.dataclass
        class A(obs.DeltaStats):
            x: int = 0

        @dataclasses.dataclass
        class B(obs.DeltaStats):
            x: int = 0

        with pytest.raises(TypeError):
            A().since(B())

    def test_reexported_from_engine(self):
        assert engine.DeltaStats is obs.DeltaStats
        assert engine.PlanStats().since(engine.PlanStats()).hits == 0
