"""Readings that set a cell's limits: the program's and the control's.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds <a,b,...>

For every seed, in one process: build the cell, run a short window at the
cell's own load, and judge its answers as a benchmark run does (the
program's reading); then let the plain reference answer the same queries at
``Precision.HIGH``, one precision step below the float32 at HIGHEST the
configuration states, and judge that answer the same way (the control's
reading).  Each is held to the configuration's committed limit, as
``bench/run.py`` holds a run: the program has to read correct and the
control not.  The limit of ``answer_gap`` lies between the largest program
reading and the smallest control reading.  Benchmark runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    import jax

    from bench import run as bench_run
    from bench import window
    device = bench_run.device_info(1, require_tpu=True)
    from repro.launch import compile_cache
    jax.config.update("jax_compilation_cache_dir", compile_cache.enable())
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        cell = bench_run.Cell(ROOT, args.workload, seed)
        calls, failed, _ = window.run(cell.search, cell.batches, args.seconds)
        cell.free_program()
        v = cell.judge(calls, seed, control=True)
        limit = cell.cfg["limits"]["answer_gap"]
        row = {"seed": seed, "program": v.answer_gap, "control": v.control_gap,
               "limit": limit, "program_correct": v.answer_gap <= limit,
               "control_correct": v.control_gap <= limit,
               "program_mean": v.score_error_mean, "control_mean": v.control_score_mean,
               "control_score_error": v.control_score_error,
               "score_error": v.score_error, "rank_error": v.rank_error,
               "structural": v.structural, "recall": v.recall, "calls": len(calls),
               "failed": len(failed), "seconds": time.perf_counter() - t0}
        rows.append(row)
        print(json.dumps(row), flush=True)
        del cell
    print(json.dumps({"workload": args.workload, "device": device,
                      "program_max": max(r["program"] for r in rows),
                      "control_min": min(r["control"] for r in rows)}))
    return 0 if all(r["program_correct"] and not r["control_correct"] for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
