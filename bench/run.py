"""MonaVec on-chip benchmark: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  A run finds the cell in BENCHMARK.json, checks
that JAX sees a TPU with the cell's chips (else it exits 2 before any work),
keeps JAX's compilation cache in ``<checkout>/.jax_cache``, builds the cell's
index from the seed through ``MonaVec`` and a ``TenantRegistry``, warms the
one shape its traffic uses, and then, for ``--seconds``, calls the bound
searcher in a closed loop.  ``--trace 1`` runs a shorter window (the traffic
mix's ``trace_seconds``) under the profiler and reports the per-layer
metrics instead of the end-to-end ones.

After the window the answers are judged against the plain reference
(``bench/reference.py``).  The last lines on standard error are each number
compared and its limit; the last line on standard output is the result:
``{"correct", "attempted", "failed", "metrics", "device"[, "breakdown"],
"checks"}``.  Per-call samples go to ``bench/out/<cell>/``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
EXIT_NO_CHIP = 2


class NoChip(RuntimeError):
    pass


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def device_info(chips: int, require_tpu: bool) -> dict:
    import jax
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX sees "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def memory(chips: int, key: str = "peak_bytes_in_use") -> int:
    """A device memory statistic on the fullest of the cell's chips."""
    import jax
    return int(max((d.memory_stats() or {}).get(key, 0) for d in jax.devices()[:chips]))


class Cell:
    """One cell made ready to measure: the index built and registered, its
    query pool drawn, its one shape warmed."""

    def __init__(self, root: Path, name: str, seed: int, lap=None):
        import numpy as np

        from bench import datagen, manifest, system
        lap = lap or (lambda name: None)
        self.man = manifest.load(root)
        self.wl = manifest.workload(self.man, name)
        self.cfg = manifest.config(self.man, root, self.wl["config"])
        self.mix = manifest.traffic(self.man, root, self.wl["traffic"])
        self.seed = seed
        cfg, mix = self.cfg, self.mix
        x = self.corpus()
        x.block_until_ready()
        lap("corpus")
        self.calibration = None
        std = None
        if cfg.get("fit"):
            self.calibration = np.asarray(datagen.corpus(
                cfg["data"], cfg["fit"]["calibration_seed"],
                cfg["fit"]["sample_rows"], cfg["dim"]))
            std = system.fit(self.calibration)
        lap("fit")
        self.reg = system.build(cfg, x, std)
        lap("build")
        self.k, self.batch = int(mix["k"]), int(mix["batch"])
        self.search = system.searcher(self.reg, self.k, mix["knobs"])
        pool = datagen.query_pool(x, seed, self.batch * int(mix["pool_batches"]),
                                  cfg["data"]["query_noise"])
        del x       # the deployment holds its codes, not the raw corpus
        self.batches = [pool[i:i + self.batch] for i in range(0, len(pool), self.batch)]
        lap("queries")
        for _ in range(2):
            self.search(self.batches[0])
        lap("warm")
        rm = mix["knobs"].get("rescore_mult")
        self.m = None if not rm else int(rm) * self.k
        self.bucket = max(8, 1 << (self.batch - 1).bit_length())

    def corpus(self):
        """The raw corpus, made anew from the seed (on the device)."""
        from bench import datagen
        return datagen.corpus(self.cfg["data"], self.seed, self.cfg["n"], self.cfg["dim"])

    def free_program(self) -> None:
        del self.search, self.reg

    def judge(self, calls, seed: int, control: bool = False):
        """Sample answered queries from the seed and judge their answers."""
        import numpy as np

        from bench import reference
        first = {}
        for c in calls:
            first.setdefault(c.batch, c)
        rows = [(b, r) for b in sorted(first) for r in range(self.batch)]
        rng = np.random.default_rng([seed, 0xC0FFEE])
        take = min(int(self.mix["check_queries"]), len(rows))
        pick = [rows[i] for i in sorted(rng.choice(len(rows), take, replace=False))]
        q = np.stack([self.batches[b][r] for b, r in pick])
        scores = np.stack([first[b].scores[r] for b, r in pick])
        ids = np.stack([first[b].ids[r] for b, r in pick])
        sem = reference.semantics(self.cfg, self.calibration)
        return reference.check(self.corpus(), q, scores, ids.astype(np.int64), sem,
                               k=self.k, m=self.m, control=control)


def checks_of(cfg: dict, verdict, failed: int, mismatches: int) -> dict:
    lim = cfg["limits"]
    return {
        "failed_calls": {"value": failed, "limit": 0},
        "repeat_mismatches": {"value": mismatches, "limit": 0},
        "answer_gap": {"value": verdict.answer_gap, "limit": lim["answer_gap"]},
    }


def run(args, root: Path = ROOT, require_tpu: bool = True) -> dict:
    """One run; returns the result object (the stdout line).  Without
    ``require_tpu`` (the harness's own tests) any backend serves and the
    compilation cache is left as the caller has it."""
    if require_tpu:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
    sys.path.insert(0, str(root / "src"))
    sys.path.insert(0, str(root))
    from bench import manifest

    # Seconds since process start at the end of each step of set-up.
    phases = {}
    lap = lambda name: phases.__setitem__(name, time.perf_counter() - T_START)  # noqa: E731
    man = manifest.load(root)
    wl = manifest.workload(man, args.workload)
    import jax
    lap("import_jax")
    device = device_info(int(wl["chips"]), require_tpu)
    lap("devices")
    if require_tpu:
        from repro.launch import compile_cache
        jax.config.update("jax_compilation_cache_dir", compile_cache.enable())

    from bench import system, window
    lap("import_program")
    cell = Cell(root, args.workload, args.seed, lap)
    traced = bool(args.trace)
    seconds = min(args.seconds, float(cell.mix["trace_seconds"])) if traced else args.seconds
    from repro import obs
    traces0 = obs.registry().snapshot()["counters"].get("plan_cache.traces", 0)
    stages0 = system.engine_stage_sums()
    setup_s = time.perf_counter() - T_START

    out_dir = root / man["paths"][0] / "out" / args.workload
    trace_dir = out_dir / f"trace-{args.seed}"
    if traced:
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
        span = jax.profiler.TraceAnnotation
    else:
        span = None
    with (jax.profiler.TraceAnnotation("bench.window") if traced
          else contextlib.nullcontext()):
        calls, failed, window_s = window.run(cell.search, cell.batches, seconds, span)
    if traced:
        jax.profiler.stop_trace()
    retraces = obs.registry().snapshot()["counters"].get("plan_cache.traces", 0) - traces0
    stages1 = system.engine_stage_sums()
    device["memory_peak_bytes"] = memory(int(wl["chips"]))
    in_use = memory(int(wl["chips"]), "bytes_in_use")
    cell.free_program()

    stats = window.summary(calls, window_s, cell.batch) if calls else {}
    mismatches = window.repeat_mismatches(calls)
    t_ref = time.perf_counter()
    verdict = cell.judge(calls, args.seed) if calls else None
    ref_s = time.perf_counter() - t_ref
    if verdict is None:
        checks = {"failed_calls": {"value": len(failed), "limit": 0}}
    else:
        checks = checks_of(cell.cfg, verdict, len(failed), mismatches)
    correct = bool(calls) and all(c["value"] <= c["limit"] for c in checks.values())

    result = {"correct": correct, "attempted": len(calls) + len(failed),
              "failed": len(failed), "metrics": {}, "device": device}
    e2e_values = dict(stats, setup_s=setup_s)
    if not traced:
        for m in manifest.metrics_for(man, args.workload, "end_to_end"):
            if m["name"] in e2e_values:
                result["metrics"][m["name"]] = {"value": e2e_values[m["name"]],
                                                "unit": m["unit"]}
    else:
        from bench import trace
        tr = trace.load(trace_dir)
        summ = trace.summarize(tr)
        device["busy_s"], device["window_s"] = summ["busy_s"], summ["window_s"]
        peaks = json.loads((root / man["paths"][0] / "peaks.json").read_text())["devices"]
        if device["kind"] not in peaks and require_tpu:
            raise KeyError(f"no peaks for device kind {device['kind']!r} in peaks.json")
        ctx = {"trace": tr, "summary": summ, "peaks": peaks.get(device["kind"]),
               "engine": {s: (v[0] - stages0.get(s, (0.0, 0))[0],
                              v[1] - stages0.get(s, (0.0, 0))[1])
                          for s, v in stages1.items()},
               "cell": {"bucket": cell.bucket, "n": cell.cfg["n"], "k": cell.k,
                        "m": cell.m, "d_pad": 1 << (cell.cfg["dim"] - 1).bit_length()}}
        for m in manifest.metrics_for(man, args.workload, "per_layer"):
            v = manifest.reader(man, root, m["name"])(ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        result["breakdown"] = trace.breakdown(summ)
    result["checks"] = checks

    out_dir.mkdir(parents=True, exist_ok=True)
    record = {"workload": args.workload, "seed": args.seed, "trace": args.trace,
              "setup_s": setup_s, "setup_phases_s": phases,
              "bytes_in_use_after_window": in_use,
              "reference_s": ref_s, "retraces_in_window": retraces,
              "window": stats, "latencies_ms": [1e3 * (c.t1 - c.t0) for c in calls],
              "verdict": None if verdict is None else verdict._asdict(),
              "result": result}
    (out_dir / f"{args.seed}-t{args.trace}.json").write_text(json.dumps(record))
    say = lambda s: print(s, file=sys.stderr, flush=True)  # noqa: E731
    say(f"[bench] {args.workload} seed={args.seed} setup_s={setup_s:.3f} "
        f"phases={ {k: round(v, 3) for k, v in phases.items()} } "
        f"window={stats} retraces_in_window={retraces} reference_s={ref_s:.3f}")
    if verdict is not None:
        say(f"[bench] recall@{cell.k} vs exact float search {verdict.recall:.4f}; "
            f"score_error {verdict.score_error:.3e} rank_error {verdict.rank_error:.3e} "
            f"structural {verdict.structural} candidates {verdict.candidates}")
    for name, c in checks.items():
        say(f"[check] {name} {c['value']!r} limit {c['limit']!r}")
    return result


def main(argv=None) -> int:
    args = parse(argv)
    try:
        result = run(args)
    except NoChip as e:
        print(f"[bench] {e}", file=sys.stderr)
        return EXIT_NO_CHIP
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
