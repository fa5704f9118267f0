"""The system under test: a configuration's MonaVec index behind a TenantRegistry.

This is the only module of the harness that imports the program (``repro``).
``build`` turns a configuration and its corpus into a registered collection;
``searcher`` binds the handle the measured window calls, exactly as a serving
loop holds one per (tenant, collection).
"""

from __future__ import annotations

import dataclasses
import re

import jax.numpy as jnp
import numpy as np

from repro.core import MonaVec, TenantRegistry
from repro.core import quantize as qz
from repro.core.bruteforce import BruteForceIndex

TOKEN = "bench"
COLLECTION = "corpus"


def _metadata(spec, n: int):
    if not spec:
        return None
    cols = {}
    for name, col in spec.items():
        if col["kind"] != "row_mod":
            raise ValueError(f"unknown metadata column kind {col['kind']!r}")
        cols[name] = np.arange(n, dtype=np.int64) % int(col["mod"])
    return cols


def fit(sample) -> object:
    """The paper's fit(): global scalar standardization from a sample."""
    return MonaVec.fit(sample)


#: Float32 copies of its padded corpus that one ``MonaVec.build`` holds at
#: its peak, with room: agnews45k's build peaks at 973 MB for 185 MB of
#: rows, and gist1m's 1M rows asked for 19.4 GB of a 16.9 GB v5e and
#: failed (PERF.md).
BUILD_COPIES = 6


def device_memory():
    """Bytes the default device holds (None where JAX does not say)."""
    import jax
    return (jax.devices()[0].memory_stats() or {}).get("bytes_limit")


def encode_rows(n: int, d_pad: int, memory) -> int:
    """Rows one encode may take: all ``n`` where one build fits the device's
    memory, else the largest power of two whose encode takes a quarter."""
    per_row = BUILD_COPIES * 4 * d_pad
    if memory is None or n * per_row <= memory:
        return n
    return 1 << max(0, (int(memory) // 4 // per_row).bit_length() - 1)


def build(cfg: dict, x, std=None) -> TenantRegistry:
    """Build the configuration's index over ``x`` and register it: one
    ``MonaVec.build`` where it fits the device, else the same encode in row
    chunks."""
    if cfg["backend"] != "bruteforce":
        raise ValueError(f"unsupported backend {cfg['backend']!r}")
    n = int(x.shape[0])
    d_pad = 1 << (int(x.shape[1]) - 1).bit_length()
    chunk = encode_rows(n, d_pad, device_memory())
    meta = _metadata(cfg.get("metadata"), n)
    if chunk >= n:
        index = MonaVec.build(
            x, metric=cfg["metric"], bits=cfg["bits"], seed=cfg["rotation_seed"],
            std=std, meta=meta, coarse=cfg.get("coarse"))
    else:
        # Encoding is row by row, so the chunks' codes are those of one
        # build.
        if meta is not None:
            raise ValueError("chunked builds carry no metadata columns")
        encs = [qz.encode(x[i:i + chunk], metric=cfg["metric"], bits=cfg["bits"],
                          seed=cfg["rotation_seed"], std=std)
                for i in range(0, n, chunk)]
        enc = dataclasses.replace(
            encs[0], packed=jnp.concatenate([e.packed for e in encs]),
            qnorms=jnp.concatenate([e.qnorms for e in encs]))
        index = MonaVec(BruteForceIndex(enc=enc, ids=np.arange(n, dtype=np.uint64)))
        if cfg.get("coarse"):
            index.enable_coarse(cfg["coarse"])
    reg = TenantRegistry()
    reg.put(TOKEN, COLLECTION, index)
    return reg


def searcher(reg: TenantRegistry, k: int, knobs: dict):
    """The bound search handle the window drives: ``search(q) -> (scores, ids)``."""
    return reg.searcher(TOKEN, COLLECTION, k=k, **knobs)


def engine_stage_sums() -> dict:
    """Summed host microseconds and call counts of the engine's stage
    histograms (``engine.stage_us{stage=...}``), by stage name."""
    from repro import obs
    out: dict = {}
    for key, h in obs.registry().snapshot()["histograms"].items():
        if not key.startswith("engine.stage_us"):
            continue
        stage = re.search(r'stage="([^"]*)"', key).group(1)
        s, c = out.get(stage, (0.0, 0))
        out[stage] = (s + h["sum"], c + h["count"])
    return out
