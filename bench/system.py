"""The system under test: a configuration's MonaVec index (or, for a
block-keyed corpus, ShardedMonaVec row-sharded over the cell's chips)
behind a TenantRegistry.

This is the only module of the harness that imports the program (``repro``).
``build`` turns a configuration and its corpus into a registered collection;
``searcher`` binds the handle the measured window calls, exactly as a serving
loop holds one per (tenant, collection).
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core import MonaVec, TenantRegistry
from repro.core import quantize as qz
from repro.core.bruteforce import BruteForceIndex
from repro.dist import partition
from repro.dist.sharded_index import ShardedMonaVec

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
    return (jax.devices()[0].memory_stats() or {}).get("bytes_limit")


def encode_rows(n: int, d_pad: int, memory) -> int:
    """Rows one encode may take: all ``n`` where one build fits the device's
    memory, else the largest power of two whose encode takes a quarter."""
    per_row = BUILD_COPIES * 4 * d_pad
    if memory is None or n * per_row <= memory:
        return n
    return 1 << max(0, (int(memory) // 4 // per_row).bit_length() - 1)


def build(cfg: dict, rows, std=None) -> TenantRegistry:
    """Build the configuration's index over the corpus ``rows`` (a
    ``datagen.Rows``) and register it: row-sharded over the corpus's devices
    (the cell's chips) where the configuration's corpus is block-keyed
    (``data.block_rows``); else one ``MonaVec.build`` where it fits the
    device, else the same encode in row chunks."""
    if cfg["backend"] != "bruteforce":
        raise ValueError(f"unsupported backend {cfg['backend']!r}")
    if "block_rows" in cfg["data"]:
        index = build_sharded(cfg, rows, std)
    else:
        index = _build_one(cfg, rows.block(0), std)
    reg = TenantRegistry()
    reg.put(TOKEN, COLLECTION, index)
    return reg


@functools.partial(jax.jit, static_argnames=("rows",), donate_argnums=(0,))
def _put(buf, enc, src, dst, *, rows):
    """``rows`` rows of the codes and norms ``enc`` from row ``src``, written
    into the chip's buffers ``buf`` at row ``dst`` in place."""
    return tuple(jax.lax.dynamic_update_slice_in_dim(
        b, jax.lax.dynamic_slice_in_dim(e, src, rows), dst, 0) for b, e in zip(buf, enc))


def build_sharded(cfg: dict, rows, std=None) -> ShardedMonaVec:
    """Each of the corpus's devices, one shard each, makes the blocks of its
    own row range, encodes them there and writes their codes into one buffer
    of its rows; the buffers become the global arrays that
    ``ShardedMonaVec.shard`` wraps.  No device and not the host ever holds
    the whole corpus, and each chip holds its codes once."""
    devices, n = rows.devices, int(cfg["n"])
    shards = len(devices)
    if n % shards:
        raise ValueError(f"n = {n} rows do not split evenly over {shards} shards")
    if cfg.get("coarse") or cfg.get("metadata"):
        raise ValueError("sharded builds carry no coarse codes and no metadata columns")
    mesh = Mesh(np.asarray(devices).reshape(shards, 1), ("data", "model"))
    per, b = n // shards, rows.block_rows
    bufs = [None] * shards
    pending = collections.deque()
    # Blocks in turn over the shards, so that every chip encodes at once.
    spans = [[(j, max(s * per, j * b), min((s + 1) * per, (j + 1) * b))
              for j in range(s * per // b, -(-(s + 1) * per // b))] for s in range(shards)]
    for step in range(max(len(sp) for sp in spans)):
        for s, dev in enumerate(devices):
            if step >= len(spans[s]):
                continue
            j, lo, hi = spans[s][step]
            enc = qz.encode(rows.block(j, dev), metric=cfg["metric"], bits=cfg["bits"],
                            seed=cfg["rotation_seed"], std=std)
            codes = (enc.packed, enc.qnorms)
            if bufs[s] is None:
                bufs[s] = tuple(jnp.zeros((per,) + c.shape[1:], c.dtype, device=dev)
                                for c in codes)
            bufs[s] = _put(bufs[s], codes, np.int32(lo - j * b), np.int32(lo - s * per),
                           rows=hi - lo)
            pending.append(codes)
            if len(pending) > 2 * shards:     # queued blocks hold memory
                jax.block_until_ready(pending.popleft())
    del pending
    enc = dataclasses.replace(enc, **{
        name: jax.make_array_from_single_device_arrays(
            (n,) + c[0].shape[1:], partition.corpus_sharding(mesh, c[0].ndim), list(c))
        for name, c in zip(("packed", "qnorms"), zip(*bufs))})
    return ShardedMonaVec.shard(enc, mesh)


def _build_one(cfg: dict, x, std=None):
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
    return index


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
