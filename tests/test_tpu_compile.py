"""Ahead-of-time compiles of the search-path Pallas kernels for a TPU v5e.

Interpret mode runs a kernel body through the Pallas interpreter, which
accepts 8-bit vector arithmetic, unaligned slices and any VMEM footprint.
Mosaic (the TPU kernel compiler) does not.  These tests lower every kernel
entry point through the ``ops.py`` wrappers with ``use_kernel=True,
interpret=False`` -- so the real padding and block choice are exercised --
and compile it for one chip of a described (not attached) ``v5e:2x2``
topology, at the paper's AG News shape (45,056 rows, d'=1024), and the
crumb coarse scan also at GIST-1M's 1,000,000 rows.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library at a time, and every test worker
imports this file.  All compiles stay in this one file for the same reason.
"""

from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

N_ROWS = 45_056      # AG News corpus rows (configs/retrieval.py agnews_45k)
GIST_ROWS = 1_000_000  # GIST-1M rows (bench/configs/gist1m.json), d' = 1024
DIM = 1024           # rotated dim d'
BATCHES = (8, 256)   # single-query-ish and the benchmark batch

# Candidate widths the gathered scan sees in serving: HNSW entry point (1),
# HNSW upper-layer neighbors (m=16), HNSW level-0 neighbors (2m=32), the
# cascade's rescore budget (32 * k=10), and an IVF probe set (nprobe=16 of
# nlist=128 cells over 45k rows, ~352 rows per cell).
GATHER_WIDTHS = (1, 16, 32, 320, 5_632)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of these tests.
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means "no TPU compiler"
            jax.config.update("jax_enable_compilation_cache", was_enabled)
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


def _compile_text(fn, sharding, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _custom_call_names(text: str) -> list:
    """Names of the compiled module's custom calls (``%name = ... custom-call(``)."""
    return re.findall(r"%([\w.-]+) = [^\n]*? custom-call\(", text)


def _assert_crumb_kernel_named(text: str) -> None:
    """bench/metrics/crumb_dot_roofline.py finds the kernel's device op by
    this name: the custom call takes it from its jitted function."""
    names = _custom_call_names(text)
    assert any(nm.startswith("crumb_affinity_raw.") for nm in names), names


@pytest.mark.parametrize("b", BATCHES)
@pytest.mark.parametrize("bits", [4, 2])
def test_full_scan_compiles(one_chip, bits, b):
    """nibble_dot (4-bit) and crumb_dot (2-bit) full-corpus scans."""
    fn = functools.partial(ops.score_raw, bits=bits, use_kernel=True,
                           interpret=False)
    text = _compile_text(fn, one_chip,
                         ((N_ROWS, DIM * bits // 8), jnp.uint8),
                         ((b, DIM), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("b", BATCHES)
@pytest.mark.parametrize("kind", ["sign", "crumb"])
def test_coarse_scan_compiles(one_chip, kind, b):
    """binary_dot sign-hamming and crumb-affinity cascade proxies."""
    if kind == "sign":
        fn, width = ops.sign_coarse_raw, DIM // 8
    else:
        fn, width = ops.crumb_coarse_raw, DIM // 4
    fn = functools.partial(fn, use_kernel=True, interpret=False)
    text = _compile_text(fn, one_chip,
                         ((N_ROWS, width), jnp.uint8),
                         ((b, width), jnp.uint8))
    assert "tpu_custom_call" in text
    if kind == "crumb":
        _assert_crumb_kernel_named(text)


@pytest.mark.parametrize("b", BATCHES)
def test_crumb_coarse_compiles_at_gist_shape(one_chip, b):
    """The crumb level-dot kernel over GIST-1M's crumb codes: 1M rows that
    no row tile divides, so the last tile is ragged."""
    fn = functools.partial(ops.crumb_coarse_raw, use_kernel=True,
                           interpret=False)
    text = _compile_text(fn, one_chip,
                         ((GIST_ROWS, DIM // 4), jnp.uint8),
                         ((b, DIM // 4), jnp.uint8))
    _assert_crumb_kernel_named(text)


@pytest.mark.parametrize("mc", GATHER_WIDTHS)
@pytest.mark.parametrize("bits", [4, 2])
def test_gathered_scan_compiles(one_chip, bits, mc):
    """gather_dot nibble/crumb candidate-set scans (IVF, HNSW, cascade)."""
    fn = functools.partial(ops.score_gathered_raw, bits=bits, use_kernel=True,
                           interpret=False)
    text = _compile_text(fn, one_chip,
                         ((N_ROWS, DIM * bits // 8), jnp.uint8),
                         ((256, DIM), jnp.float32),
                         ((256, mc), jnp.int32))
    assert "tpu_custom_call" in text
