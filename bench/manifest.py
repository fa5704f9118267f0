"""BENCHMARK.json and the files it names.

Everything a cell needs is found by name: the configuration through its
``file``, the traffic mix as ``<bench>/traffic/<traffic>.json`` and each
per-layer metric's reader as ``<bench>/metrics/<metric name>.py``.  Adding a
cell, a configuration, a traffic mix or a metric adds files and entries and
edits none.
"""

from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path
from typing import List

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def load(root: Path) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def bench_dir(man: dict, root: Path) -> Path:
    return Path(root) / man["paths"][0]


def workload(man: dict, name: str) -> dict:
    for w in man["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(man: dict, root: Path, name: str) -> dict:
    for c in man["configs"]:
        if c["name"] == name:
            return json.loads((Path(root) / c["file"]).read_text())
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


#: What a traffic mix may set: one closed-loop caller sends batches of
#: ``batch`` queries for the top ``k`` with ``knobs``, cycling a pool of
#: ``pool_batches`` batches; ``check_queries`` answers are judged, and a
#: traced run measures ``trace_seconds``.
TRAFFIC_KEYS = {"batch", "k", "knobs", "pool_batches", "check_queries", "trace_seconds"}


def traffic(man: dict, root: Path, name: str) -> dict:
    mix = json.loads((bench_dir(man, root) / "traffic" / f"{name}.json").read_text())
    unknown = set(mix) - TRAFFIC_KEYS
    if unknown or TRAFFIC_KEYS - set(mix):
        raise ValueError(f"traffic {name!r}: unknown keys {sorted(unknown)}, missing "
                         f"{sorted(TRAFFIC_KEYS - set(mix))}; the window runs one "
                         "closed-loop caller")
    return mix


def reports(metric: dict, cell: str, end_to_end: List[dict]) -> bool:
    """Whether ``cell`` reports ``metric``: listed, or (no list) the cell
    reports the end-to-end metric it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    if "moves" in metric:
        moved = next(m for m in end_to_end if m["name"] == metric["moves"])
        return reports(moved, cell, end_to_end)
    return True


def metrics_for(man: dict, cell: str, kind: str) -> List[dict]:
    return [m for m in man[kind] if reports(m, cell, man["end_to_end"])]


def reader(man: dict, root: Path, metric: str):
    path = bench_dir(man, root) / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + re.sub(r"\W", "_", metric), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def problems(man: dict, root: Path) -> List[str]:
    """What in the manifest breaks the naming and cross-reference rules."""
    out = []
    cells = {w["name"] for w in man["workloads"]}
    configs = {c["name"] for c in man["configs"]}
    every = (man["configs"] + man["workloads"] + man["end_to_end"] + man["per_layer"])
    names = [x["name"] for x in every]
    for n in names + [w["config"] for w in man["workloads"]] + [
            w["traffic"] for w in man["workloads"]] + [
            r for c in man["configs"] for r in c["reduced"]]:
        if not NAME.match(n):
            out.append(f"bad name {n!r}")
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = [x["name"] for x in man[kind]]
        if len(seen) != len(set(seen)):
            out.append(f"duplicate names in {kind}")
    for m in man["end_to_end"] + man["per_layer"]:
        if not UNIT.match(m["unit"]):
            out.append(f"bad unit {m['unit']!r} of {m['name']}")
        for c in m.get("workloads", []):
            if c not in cells:
                out.append(f"{m['name']} lists unknown cell {c!r}")
    e2e = {m["name"]: m for m in man["end_to_end"]}
    for m in man["per_layer"]:
        if m["moves"] not in e2e:
            out.append(f"{m['name']} moves unknown metric {m['moves']!r}")
        for c in m.get("workloads", []):
            if not reports(e2e[m["moves"]], c, man["end_to_end"]):
                out.append(f"{m['name']} is listed in {c}, which does not report "
                           f"{m['moves']}")
        if not (bench_dir(man, root) / "metrics" / f"{m['name']}.py").is_file():
            out.append(f"no reader for {m['name']}")
    pairs = [(w["config"], w["traffic"]) for w in man["workloads"]]
    if len(pairs) != len(set(pairs)):
        out.append("a (config, traffic) pair appears twice")
    for w in man["workloads"]:
        if w["config"] not in configs:
            out.append(f"{w['name']} names unknown config {w['config']!r}")
        if not (bench_dir(man, root) / "traffic" / f"{w['traffic']}.json").is_file():
            out.append(f"no traffic file for {w['traffic']!r}")
        if not any(reports(m, w["name"], man["end_to_end"]) and m["name"] != "setup_s"
                   for m in man["end_to_end"]):
            out.append(f"{w['name']} reports no end-to-end metric besides setup_s")
        if not any(reports(m, w["name"], man["end_to_end"]) for m in man["per_layer"]):
            out.append(f"{w['name']} reports no per-layer metric")
    used = {w["config"] for w in man["workloads"]}
    for c in man["configs"]:
        if c["name"] not in used:
            out.append(f"configuration {c['name']} has no cell")
        if not (Path(root) / c["file"]).is_file():
            out.append(f"no file for configuration {c['name']}")
    return out
