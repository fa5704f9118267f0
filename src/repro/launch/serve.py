"""Retrieval serving launcher: the service layer as a batched offline loop.

The paper ships FastAPI/REST; in this offline runtime the same contract is a
pure function: token -> namespace -> collection -> top-k.  This CLI builds
(or loads) a .mvec index and serves deterministic batched query traffic
through the query-execution engine (DESIGN.md §7): the serving loop holds a
bound handle

    search = reg.searcher(token, "default", k=10)   # == index.searcher(k=10)
    search.warmup(batch_size)      # compile the plan OUTSIDE the timed window
    scores, ids = search(queries)  # every call: plan-cache hit, zero retrace

so each phase runs one untimed warm-up batch (jit trace + compile) before
the measured batches, and reports the engine's plan-cache hits/misses/
retraces alongside QPS — the measured number is serving throughput, not
compile time.

    PYTHONPATH=src python -m repro.launch.serve --n 50000 [--index hnsw]
    PYTHONPATH=src python -m repro.launch.serve --load corpus.mvec
    PYTHONPATH=src python -m repro.launch.serve --n 200000 --shard
    PYTHONPATH=src python -m repro.launch.serve --n 20000 --mutate --compact
    PYTHONPATH=src python -m repro.launch.serve --n 20000 --micro-batch 8
    PYTHONPATH=src python -m repro.launch.serve --n 50000 --index ivf \
        --autotune --recall-target 0.95

--autotune runs the training-free autotuner (DESIGN.md §12) after build or
load: seeded sample queries drawn from the corpus are swept against an exact
full-scan oracle over the SAME quantized segments, and the cheapest knob
rung meeting --recall-target becomes the serving default (every phase report
prints the resolved knobs).  With --save the tuned knobs persist as the
.mvec v11 TUNE block and reload as defaults.

--shard serves the BruteForce scan through repro.dist: the corpus is split
over every local device and each batch runs the shard_map scan + cross-shard
merge (identical results to the single-device path, by construction).

--mutate exercises the segmented lifecycle endpoints (DESIGN.md §6) through
the tenant registry — the offline analogue of the paper's POST /add,
DELETE /ids, POST /compact routes: after the initial query phase it add()s
a delta batch, delete()s a stride of ids, re-serves (scans now cover base +
extra segments with tombstones masked pre-top-k), and with --compact
rewrites the live rows into one segment and serves a final phase.

--micro-batch R splits every batch into R separate requests and serves them
through the engine's MicroBatcher: requests are coalesced per (namespace,
collection, k, where, hybrid?) group and executed as ONE bucketed plan call
— the multi-tenant serving shape, with bit-identical per-request results.

--filter-every N attaches a ``bucket = row % N`` metadata column at build
time and serves an extra phase with ``where=Eq("bucket", 0)`` (selectivity
1/N) through the compiled predicate stage (DESIGN.md §8): the report shows
the filtered phase hitting the SAME plan cache — the predicate mask is a
fused stage, not a separate pass, so repeat filtered batches are zero-
retrace just like unfiltered ones.

Observability (DESIGN.md §9): every phase report is read back out of the
process-wide metrics registry (plan-cache counters, per-stage latency
histograms, per-namespace request counts) rather than ad-hoc counters;

--metrics-json PATH   write the full registry snapshot (counters, gauges,
                      per-stage latency histograms with their deterministic
                      bucket edges) as JSON on exit;
--metrics-prom PATH   the same snapshot in Prometheus text exposition;
--trace-sample N      trace every Nth served batch end to end (plan lookup
                      -> per-stage dispatch -> merge/top-k -> batcher
                      scatter-back) and dump the span trees per phase.
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro import engine, obs
from repro.core import Eq, MonaVec, TenantRegistry
from repro.data.synthetic import embedding_corpus, queries_from_corpus
from repro.launch import compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=50_000)
    ap.add_argument("--dim", type=int, default=1024)
    ap.add_argument("--index", default="bruteforce",
                    choices=["bruteforce", "ivf", "hnsw"])
    ap.add_argument("--load", default=None, help="serve an existing .mvec file")
    ap.add_argument("--save", default=None)
    ap.add_argument("--batches", type=int, default=10)
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--token", default=None, help="tenant token (standalone mode)")
    ap.add_argument("--mutate", action="store_true",
                    help="run the add/delete/compact lifecycle phases after "
                         "the initial query phase (DESIGN.md §6)")
    ap.add_argument("--add-n", type=int, default=None,
                    help="rows to add() in the mutation phase "
                         "(default: 10%% of the corpus)")
    ap.add_argument("--delete-every", type=int, default=17,
                    help="delete() every Nth id in the mutation phase")
    ap.add_argument("--compact", action="store_true",
                    help="compact() after the mutation phase and re-serve")
    ap.add_argument("--shard", action="store_true",
                    help="shard the corpus over all local devices (bruteforce)")
    ap.add_argument("--filter-every", type=int, default=0, metavar="N",
                    help="attach a bucket=row%%N metadata column and serve a "
                         "filtered phase with where=Eq('bucket', 0) — "
                         "selectivity 1/N through the compiled predicate "
                         "stage (DESIGN.md §8)")
    ap.add_argument("--metrics-json", default=None, metavar="PATH",
                    help="write the metrics-registry snapshot (DESIGN.md §9) "
                         "as JSON on exit")
    ap.add_argument("--metrics-prom", default=None, metavar="PATH",
                    help="write the metrics snapshot in Prometheus text "
                         "exposition format on exit")
    ap.add_argument("--trace-sample", type=int, default=0, metavar="N",
                    help="trace every Nth served batch and dump its span "
                         "tree (0 = off)")
    ap.add_argument("--micro-batch", type=int, default=0, metavar="R",
                    help="serve each batch as R coalesced requests through "
                         "the engine MicroBatcher (0 = direct searcher)")
    ap.add_argument("--coarse", default="off", choices=["off", "sign", "crumb"],
                    help="attach a binarized coarse code at build time "
                         "(DESIGN.md §11; persisted as .mvec v10 with --save; "
                         "with --load, derives codes for a pre-v10 file) — "
                         "unlocks --rescore-mult")
    ap.add_argument("--rescore-mult", type=int, default=0, metavar="R",
                    help="serve through the binarized cascade: coarse-scan "
                         "all rows, rescore only the top R*k survivors with "
                         "the 4-bit kernel (0 = full scan; requires --coarse "
                         "or a v10 .mvec)")
    ap.add_argument("--autotune", action="store_true",
                    help="run the training-free autotuner (DESIGN.md §12) "
                         "after build/load: seeded sample queries vs an "
                         "exact oracle pick the cheapest backend knob "
                         "meeting --recall-target; the tuned knobs become "
                         "the serving defaults (persisted with --save as "
                         ".mvec v11)")
    ap.add_argument("--recall-target", type=float, default=0.95,
                    metavar="R", help="autotune recall@k target (default "
                    "0.95; requires --autotune)")
    ap.add_argument("--use-kernel", default="auto", choices=["auto", "on", "off"],
                    help="scoring dispatch: auto = Pallas kernel on TPU / "
                         "pure-jnp elsewhere; on/off force it (all backends)")
    ap.add_argument("--interpret", action="store_true",
                    help="run the Pallas kernel in interpret mode (validation)")
    args = ap.parse_args()
    use_kernel = {"auto": None, "on": True, "off": False}[args.use_kernel]
    interpret = True if args.interpret else None
    if args.interpret and use_kernel is None:
        use_kernel = True   # interpret mode validates the KERNEL body; off-TPU
                            # dispatch would otherwise skip it silently
    if args.interpret and use_kernel is False:
        raise SystemExit("--interpret requires the kernel path "
                         "(drop --use-kernel off)")
    if args.use_kernel == "on" and not args.interpret:
        import jax
        if jax.default_backend() != "tpu":
            # Off-TPU the forced kernel could only run in interpret mode;
            # emulation QPS must never be reported as kernel QPS.
            raise SystemExit(
                f"--use-kernel on needs a TPU (backend is "
                f"{jax.default_backend()!r}); add --interpret to validate the "
                f"kernel body off-TPU")

    if args.shard and not args.load and args.index != "bruteforce":
        # Fail before the (possibly minutes-long) index build, not after.
        raise SystemExit("--shard requires --index bruteforce "
                         "(or a bruteforce .mvec via --load)")
    if args.shard and (use_kernel is not None or interpret is not None):
        # The shard_map scan carries its own dispatch; don't pretend to
        # force a path we would silently ignore.
        raise SystemExit("--use-kernel/--interpret do not apply to --shard")
    if args.shard and args.mutate:
        # ShardedMonaVec is a static row partition; mutate on the unsharded
        # index, compact, then shard the result.
        raise SystemExit("--mutate does not apply to --shard (compact first)")
    if args.coarse != "off" and not args.load and args.index != "bruteforce":
        raise SystemExit("--coarse requires --index bruteforce")
    if args.rescore_mult and args.coarse == "off" and not args.load:
        raise SystemExit("--rescore-mult requires --coarse sign|crumb "
                         "(or a v10 .mvec via --load)")
    if args.rescore_mult and args.micro_batch:
        # MicroBatcher groups by (namespace, collection, k, where); per-
        # request knobs would split its coalescing contract.
        raise SystemExit("--rescore-mult does not apply to --micro-batch")
    print(f"[serve] compilation cache: {compile_cache.enable()}")

    if args.load:
        index = MonaVec.load(args.load)
        corpus = None
        print(f"[serve] loaded {args.load}: n={index.backend.enc.n} "
              f"metric={index.backend.enc.metric}")
        if args.filter_every and (index.meta is None or "bucket" not in
                                  getattr(index.meta, "columns", {})):
            raise SystemExit("--filter-every needs a 'bucket' metadata "
                             "column; the loaded .mvec has none (build one "
                             "with --filter-every --save)")
        if args.coarse != "off":
            try:
                index.enable_coarse(args.coarse)   # no-op on a v10 file
            except TypeError as e:
                raise SystemExit(f"--coarse: {e}")
            print(f"[serve] coarse codes attached (kind={args.coarse})")
        if args.rescore_mult and index.backend.enc.ccodes is None:
            raise SystemExit("--rescore-mult: the loaded .mvec carries no "
                             "coarse codes; add --coarse sign|crumb to "
                             "derive them at load time")
    else:
        corpus = embedding_corpus(0, args.n, args.dim)
        kw = {"nlist": 128} if args.index == "ivf" else (
            {"m": 16, "ef_construction": 64} if args.index == "hnsw" else {})
        meta = ({"bucket": np.arange(args.n, dtype=np.int64)
                 % args.filter_every}
                if args.filter_every else None)
        t0 = time.time()
        coarse = None if args.coarse == "off" else args.coarse
        index = MonaVec.build(corpus, metric="cosine", index=args.index,
                              meta=meta, coarse=coarse, **kw)
        print(f"[serve] built {args.index} over {args.n}x{args.dim} "
              f"in {time.time() - t0:.1f}s"
              + (f" (+ bucket metadata column, {args.filter_every} values)"
                 if meta else "")
              + (f" (+ {coarse} coarse codes)" if coarse else ""))

    if args.autotune:
        # Training-free knob selection (DESIGN.md §12): seeded corpus-drawn
        # sample queries vs an exact full-scan oracle over the SAME
        # quantized segments; the chosen knobs ride on index.tuned and
        # become the defaults for every phase below.
        t0 = time.time()
        index.autotune(recall_target=args.recall_target, k=args.k)
        tr = index.tuned
        print(f"[serve] autotune: knobs={tr.knobs or '{} (full scan)'} "
              f"met_target={tr.met_target} "
              f"(recall@{tr.k} >= {tr.recall_target}, "
              f"{tr.n_queries} sample queries, {time.time() - t0:.1f}s)"
              + (f"; boost curve over {len(tr.boost.points)} selectivity "
                 f"breakpoints" if tr.boost is not None else ""))

    if args.save and (not args.load or args.autotune):
        # A loaded index is only re-saved when --autotune gave it new knobs
        # to persist (the v11 TUNE block); --mutate saves again at the end.
        index.save(args.save)
        print(f"[serve] saved {args.save}")

    if args.shard:
        import jax
        try:
            index = index.shard()
        except TypeError as e:
            raise SystemExit(f"--shard: {e}")
        print(f"[serve] sharded {index.n} rows over {jax.device_count()} "
              f"local device(s) (shard_map scan + cross-shard merge)")
        dim = index.enc.dim
    else:
        dim = index.backend.enc.dim

    reg = TenantRegistry()
    ns = reg.put(args.token, "default", index)
    print(f"[serve] namespace={ns!r}")

    batcher = (engine.MicroBatcher(reg, use_kernel=use_kernel,
                                   interpret=interpret)
               if args.micro_batch else None)
    tracer = obs.Tracer(sample_every=args.trace_sample)

    def phase_queries(b: int) -> np.ndarray:
        if corpus is not None:
            return queries_from_corpus(corpus, 100 + b, args.batch_size)
        rng = np.random.RandomState(100 + b)
        return rng.randn(args.batch_size, dim).astype(np.float32)

    def serve_batch(search, q: np.ndarray, where=None) -> None:
        if batcher is not None:
            # Split the batch into R requests and let the engine coalesce
            # them back into one bucketed plan execution per group.
            parts = np.array_split(q, min(args.micro_batch, len(q)))
            tickets = [batcher.submit(args.token, "default", p, k=args.k,
                                      where=where)
                       for p in parts]
            batcher.flush()
            for t in tickets:
                t.result()
        else:
            search(q)

    def run_phase(label: str, where=None) -> None:
        # The serving loop holds ONE bound searcher per phase; mutation
        # phases pick up the index's new segment signature automatically.
        knobs = ({"rescore_mult": args.rescore_mult}
                 if args.rescore_mult else {})
        if args.shard:   # sharded scan has its own shard_map dispatch
            search = reg.get(args.token, "default").searcher(k=args.k,
                                                             where=where,
                                                             **knobs)
        else:
            search = reg.searcher(args.token, "default", k=args.k,
                                  where=where,
                                  use_kernel=use_kernel, interpret=interpret,
                                  **knobs)
        live_idx = reg.get(args.token, "default")
        if hasattr(live_idx, "resolved_knobs"):
            # The exact knobs this phase runs with, after tuned-default
            # resolution and the engine's clamps (DESIGN.md §12) — sharded
            # indexes carry tuned defaults but resolve per call instead.
            resolved = live_idx.resolved_knobs(args.k, **knobs)
            print(f"[serve] {label}: knobs={resolved or '{} (full scan)'}"
                  + (" (tuned)" if getattr(live_idx, "tuned", None) is not None
                     else ""))
        # Untimed warm-up: the first batch of a phase pays jit trace +
        # compile; measured QPS must not include it (at small --batches the
        # old numbers were dominated by compile time).
        serve_batch(search, phase_queries(0), where)
        # The phase report reads the shared metrics registry (DESIGN.md §9):
        # plan-cache counters and batcher coalescing, diffed over the
        # measured window — the same numbers --metrics-json exports.
        before = obs.registry().snapshot()
        total, t0 = 0, time.time()
        for b in range(args.batches):
            q = phase_queries(b)
            with tracer.maybe(f"batch:{label}", phase=label, batch=b,
                              rows=len(q)):
                serve_batch(search, q, where)
            total += len(q)
        dt = time.time() - t0
        d = obs.counter_deltas(obs.registry().snapshot(), before)
        print(f"[serve] {label}: {total} queries in {dt:.2f}s -> "
              f"{total / dt:.0f} QPS "
              f"(deterministic: rerun reproduces identical ids)")
        line = (f"[serve] {label}: plan cache "
                f"hits={obs.counter_total(d, 'plan_cache.hits')} "
                f"misses={obs.counter_total(d, 'plan_cache.misses')} "
                f"retraces={obs.counter_total(d, 'plan_cache.traces')} "
                f"evictions={obs.counter_total(d, 'plan_cache.evictions')} "
                f"(measured window, post-warm-up)")
        if batcher is not None:
            line += (f"; micro-batch: "
                     f"{obs.counter_total(d, 'batcher.requests')} requests "
                     f"-> {obs.counter_total(d, 'batcher.executions')} "
                     f"plan executions")
        print(line)
        for tr in tracer.drain():
            print(f"[trace] sampled span tree ({label}):")
            for ln in tr.render().splitlines():
                print(f"[trace]   {ln}")

    run_phase("static")

    if args.filter_every:
        # Filtered serving phase (DESIGN.md §8): same plan cache, the
        # predicate compiles in as a fused mask stage — the report's
        # retrace count shows the filter costs ONE extra trace total,
        # not one per batch.
        live = reg.get(args.token, "default")
        frac = float(np.mean(live.meta["bucket"].values == 0))
        print(f"[serve] filter: where=Eq('bucket', 0) selects "
              f"~{100.0 * frac:.1f}% of rows")
        run_phase("filtered", where=Eq("bucket", 0))

    if args.mutate:
        # The paper's service-layer mutation routes, as registry calls.
        live = reg.get(args.token, "default")
        add_n = args.add_n if args.add_n is not None else max(1, live.n_total // 10)
        rng = np.random.RandomState(7)
        delta = rng.randn(add_n, dim).astype(np.float32)
        delta_meta = ({"bucket": np.arange(add_n, dtype=np.int64)
                       % args.filter_every}
                      if args.filter_every else None)
        t0 = time.time()
        new_ids = reg.add(args.token, "default", delta, meta=delta_meta)
        print(f"[serve] add: {len(new_ids)} rows quantized into segment "
              f"ordinal {live.mut.next_ordinal - 1} in {time.time() - t0:.2f}s")
        victims = live.ids[::args.delete_every]
        n_del = reg.delete(args.token, "default", victims)
        print(f"[serve] delete: {n_del} rows tombstoned "
              f"(live {live.n_live}/{live.n_total})")
        run_phase("mutated")
        if args.compact:
            t0 = time.time()
            reclaimed = reg.compact(args.token, "default")
            print(f"[serve] compact: reclaimed {reclaimed} rows into one "
                  f"segment in {time.time() - t0:.2f}s")
            run_phase("compacted")
        if args.save:
            live.save(args.save)
            print(f"[serve] saved mutated index to {args.save} "
                  f"(multi-segment layout)" if not live.mut.is_static
                  else f"[serve] saved {args.save}")

    # Final observability export (DESIGN.md §9): the whole run's registry —
    # per-stage latency histograms with their deterministic bucket edges,
    # plan-cache hit/miss/trace/eviction counters, per-namespace request
    # counts, batcher coalescing — as JSON and/or Prometheus text.
    if args.metrics_json:
        with open(args.metrics_json, "w") as f:
            json.dump(obs.registry().snapshot(), f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"[serve] wrote metrics snapshot to {args.metrics_json}")
    if args.metrics_prom:
        with open(args.metrics_prom, "w") as f:
            f.write(obs.registry().to_prometheus())
        print(f"[serve] wrote Prometheus exposition to {args.metrics_prom}")


if __name__ == "__main__":
    main()
