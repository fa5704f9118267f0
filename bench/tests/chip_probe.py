"""What a corpus row-sharded over the chips costs the harness, on the chip.

    python3 bench/tests/chip_probe.py --seeds 101,202 --seconds 10

Runs ``wiki35m_probe.json`` (not a cell of BENCHMARK.json) through
``bench/run.py``'s ``run`` with the ``scan_b256`` traffic (or, with
``--batch``, that mix at another batch size), one process per
seed, from a checkout of its own under ``--out`` that holds only that cell.
Where four chips are visible the whole corpus goes on four shards; else one
chip's share (a quarter of the rows) on one shard; ``--rows`` sets another
row count.  Each seed's line gives the set-up and its phases, the
reference's time, the peak device memory on the fullest chip, the host's
peak resident set once JAX holds the chips and at the end, and whether the
program answered correctly.  Where the program fails, the line carries the
error, and the reference is timed on its own over the same corpus.  The
lines go to standard output and to ``<out>/probe.json``.  ``--rehearse``
runs on whatever JAX sees (the CPU) instead of a TPU.

The probe goes away once ``wiki35m.sharded_scan_b256`` is a cell.
"""

from __future__ import annotations

import argparse
import json
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
HERE = Path(__file__).resolve().parent
CELL = "wiki35m_probe.scan_b256"


def tree(base: Path, cfg: dict, chips: int, batch: int) -> Path:
    """A checkout whose BENCHMARK.json holds the probe's one cell: the
    ``scan_b256`` mix, at another batch where ``batch`` is not 256."""
    pb = base / "pb"
    for sub in ("configs", "traffic"):
        (pb / sub).mkdir(parents=True, exist_ok=True)
    (pb / "configs" / "wiki35m_probe.json").write_text(json.dumps(cfg))
    (pb / "peaks.json").write_text((ROOT / "bench" / "peaks.json").read_text())
    mix = json.loads((ROOT / "bench" / "traffic" / "scan_b256.json").read_text())
    (pb / "traffic" / "scan_b256.json").write_text(json.dumps(dict(mix, batch=batch)))
    if not (base / "src").exists():
        (base / "src").symlink_to(ROOT / "src")
    man = {"command": ["python3", "pb/run.py"], "paths": ["pb"], "run_seconds": 10,
           "configs": [{"name": "wiki35m_probe", "source": cfg["source"],
                        "file": "pb/configs/wiki35m_probe.json", "reduced": [],
                        "why": "probe"}],
           "workloads": [{"name": CELL, "config": "wiki35m_probe", "traffic": "scan_b256",
                          "chips": chips, "why": "probe"}],
           "end_to_end": [{"name": "qps", "unit": "queries/s", "better": "higher",
                           "bound": 0.1, "source": "host_clock"},
                          {"name": "setup_s", "unit": "s", "better": "lower",
                           "bound": 0.25, "source": "host_clock"}],
           "per_layer": []}
    (base / "BENCHMARK.json").write_text(json.dumps(man))
    return base


def child(seed: int, seconds: float, base: Path, batch: int, rows=None,
          rehearse=False) -> dict:
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import run as bench_run     # its clock starts here
    import gc
    import traceback

    import jax
    import numpy as np

    cfg = json.loads((HERE / "wiki35m_probe.json").read_text())
    chips = 4 if len(jax.devices()) >= 4 else 1
    cfg["n"] = rows or cfg["n"] * chips // 4     # else one chip's share of the rows
    out = {"seed": seed, "chips": chips, "n": cfg["n"], "batch": batch, "seconds": seconds,
           "host_peak_rss_bytes_at_start": bench_run.host_peak_rss()}
    root = tree(base, cfg, chips, batch)
    laps = {}
    cell = bench_run.Cell

    def lapped(root, name, seed, lap):      # keeps the set-up's phases if it fails
        def both(step):
            laps[step] = time.perf_counter() - bench_run.T_START
            lap(step)
        return cell(root, name, seed, both)
    bench_run.Cell = lapped
    args = bench_run.parse(["--workload", CELL, "--seed", str(seed),
                            "--seconds", str(seconds), "--trace", "0"])
    try:
        res = bench_run.run(args, root=root, require_tpu=not rehearse)
        rec = json.loads((root / "pb" / "out" / CELL / f"{seed}-t0.json").read_text())
        out.update(correct=res["correct"], attempted=res["attempted"], failed=res["failed"],
                   device=res["device"], metrics=res["metrics"], checks=res["checks"],
                   setup_s=rec["setup_s"], setup_phases_s=rec["setup_phases_s"],
                   reference_s=rec["reference_s"], window=rec["window"],
                   verdict=rec["verdict"], host_peak_rss_bytes=rec["host_peak_rss_bytes"])
    except bench_run.NoChip:
        raise
    except Exception as e:  # a failure inside the program is the finding
        out.update(error=repr(e)[:3000], trace=traceback.format_exc()[-6000:],
                   setup_phases_s=laps,
                   seconds_to_error=time.perf_counter() - bench_run.T_START,
                   memory_peak_bytes=bench_run.memory(chips),
                   host_peak_rss_bytes=bench_run.host_peak_rss())
    gc.collect()
    if out.get("verdict") is None:
        from bench import datagen, reference
        rows = datagen.Rows(cfg["data"], seed, cfg["n"], cfg["dim"], jax.devices()[:chips])
        t0 = time.perf_counter()
        q = datagen.query_pool(rows, seed, 128, cfg["data"]["query_noise"])
        out["pool_s"] = time.perf_counter() - t0
        ids = np.tile(np.arange(10, dtype=np.int64), (len(q), 1))
        scores = np.tile(np.linspace(1.0, 0.9, 10, dtype=np.float32), (len(q), 1))
        t0 = time.perf_counter()
        v = reference.check(rows, q, scores, ids, reference.semantics(cfg), k=10, m=None)
        out["reference_alone_s"] = time.perf_counter() - t0
        out["reference_alone_candidates"] = v.candidates
        out["host_peak_rss_bytes"] = bench_run.host_peak_rss()
        out["memory_peak_bytes_after_reference"] = bench_run.memory(chips)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", default="101,202")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--rows", type=int, default=None)
    ap.add_argument("--out", default=str(ROOT / "chiprun_out" / "probe"))
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--child", type=int, default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    out = Path(args.out)
    if args.child is not None:
        print(json.dumps(child(args.child, args.seconds, out / "tree", args.batch,
                               args.rows, args.rehearse)), flush=True)
        return 0
    lines = []
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        p = subprocess.run([sys.executable, __file__, "--child", str(seed), "--seconds",
                            str(args.seconds), "--out", args.out, "--batch", str(args.batch)]
                           + (["--rows", str(args.rows)] if args.rows else [])
                           + (["--rehearse"] if args.rehearse else []),
                           capture_output=True, text=True, timeout=1500)
        last = p.stdout.strip().splitlines()[-1:] or ["{}"]
        try:
            line = json.loads(last[0])
        except json.JSONDecodeError:
            line = {}
        line.update(seed=seed, returncode=p.returncode, wall_s=time.perf_counter() - t0,
                    stderr_tail=p.stderr[-4000:])
        lines.append(line)
        print(json.dumps(line), flush=True)
    out.mkdir(parents=True, exist_ok=True)
    (out / "probe.json").write_text(json.dumps(lines, indent=1))
    return 0 if all(line.get("returncode") == 0 for line in lines) else 1


if __name__ == "__main__":
    sys.exit(main())
