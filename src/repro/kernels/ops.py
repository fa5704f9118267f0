"""Jit'd public wrappers around the Pallas kernels.

``score_packed`` is the production scoring entry point for FULL-corpus scans:
it handles padding to block multiples, the deinterleaved-query trick, metric
adjustment, and backend dispatch (Pallas kernel on TPU / interpret-mode
validation on CPU / pure-jnp fallback that lowers cleanly under pjit on any
backend — the analogue of the paper's runtime SIMD dispatch, §3.7).

``score_gathered`` is the same contract for CANDIDATE-SET scans (IVF probe
lists, HNSW frontiers; DESIGN.md §5): per-query row subsets scored directly
from the packed bytes, with the allowlist and validity masks applied before
any top-k.  Its non-kernel path mirrors the kernel's tile decomposition
exactly, so use_kernel=False and use_kernel=True/interpret=True return
bit-identical scores — the property the backend contract tests pin down.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import quantize as qz
from repro.core.allowlist import NEG
from repro.core.scoring import adjust_scores
from . import binary_dot, gather_dot, nibble_dot, ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_dispatch(
    use_kernel: Optional[bool], interpret: Optional[bool]
) -> tuple:
    """Resolve the (use_kernel, interpret) pair exactly like score_packed:
    kernel on TPU, pure-jnp elsewhere; interpret mode only for validation."""
    if use_kernel is None:
        use_kernel = _on_tpu()
    if interpret is None:
        interpret = not _on_tpu()
    return use_kernel, interpret


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def deinterleave_query(q_rot: jnp.ndarray, ways: int) -> jnp.ndarray:
    """[b, d] -> [ways, b, d/ways]: plane p holds dims p, p+ways, p+2*ways, ..."""
    b, d = q_rot.shape
    return q_rot.reshape(b, d // ways, ways).transpose(2, 0, 1)


def nibble_score_raw(
    packed: jnp.ndarray,
    q_rot: jnp.ndarray,
    *,
    use_kernel: Optional[bool] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Raw 4-bit scores [b, n]; pads to tile multiples and unpads the result.

    Dispatch (the paper's runtime-SIMD-dispatch analogue, §3.7): the Pallas
    kernel on TPU; elsewhere the pure-jnp reference (XLA-fused) — interpret
    mode executes the kernel body per grid cell in python and is for
    VALIDATION, not throughput.
    """
    use_kernel, interpret = resolve_dispatch(use_kernel, interpret)
    if not use_kernel:
        return ref.nibble_dot_ref(packed, q_rot)

    n, dk = packed.shape
    b = q_rot.shape[0]
    planes = deinterleave_query(q_rot, 2)             # [2, b, dk]

    bq = min(128, _round_up(b, 8))
    bn = min(256, _round_up(n, 128))
    bk = min(256, dk)
    b_pad, n_pad = _round_up(b, bq), _round_up(n, bn)
    # k padding is safe: padded query planes are zero, so centroid(0) bytes
    # contribute exactly 0.  n/b padding is sliced off below.
    packed_p = jnp.pad(packed, ((0, n_pad - n), (0, 0)))
    planes_p = jnp.pad(planes, ((0, 0), (0, b_pad - b), (0, 0)))
    out = nibble_dot.nibble_dot_raw(
        packed_p, planes_p[0], planes_p[1],
        block_q=bq, block_n=bn, block_k=bk, interpret=interpret,
    )
    return out[:b, :n]


def crumb_score_raw(
    packed: jnp.ndarray,
    q_rot: jnp.ndarray,
    *,
    use_kernel: Optional[bool] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Raw 2-bit scores [b, n]."""
    use_kernel, interpret = resolve_dispatch(use_kernel, interpret)
    if not use_kernel:
        return ref.crumb_dot_ref(packed, q_rot)

    n, dk = packed.shape
    b = q_rot.shape[0]
    planes = deinterleave_query(q_rot, 4)             # [4, b, dk]
    bq = min(128, _round_up(b, 8))
    bn = min(256, _round_up(n, 128))
    bk = min(128, dk)
    b_pad, n_pad = _round_up(b, bq), _round_up(n, bn)
    packed_p = jnp.pad(packed, ((0, n_pad - n), (0, 0)))
    planes_p = jnp.pad(planes, ((0, 0), (0, b_pad - b), (0, 0)))
    out = nibble_dot.crumb_dot_raw(
        packed_p, planes_p,
        block_q=bq, block_n=bn, block_k=bk, interpret=interpret,
    )
    return out[:b, :n]


def score_raw(
    packed: jnp.ndarray,
    q_rot: jnp.ndarray,
    *,
    bits: int,
    n4_dims: int = 0,
    use_kernel: Optional[bool] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Raw (un-adjusted) scores [b, n] for any bit mode, from raw arrays.

    The single bit-mode dispatch point — score_packed and the sharded scan
    (repro.dist.retrieval) both go through here, so the packed layout is
    interpreted identically on every path.
    """
    if bits == 4:
        return nibble_score_raw(packed, q_rot, use_kernel=use_kernel,
                                interpret=interpret)
    if bits == 2:
        return crumb_score_raw(packed, q_rot, use_kernel=use_kernel,
                               interpret=interpret)
    if bits == 3:  # mixed [4-bit | 2-bit]
        b4 = n4_dims // 2
        raw4 = nibble_score_raw(packed[:, :b4], q_rot[:, :n4_dims],
                                use_kernel=use_kernel, interpret=interpret)
        raw2 = crumb_score_raw(packed[:, b4:], q_rot[:, n4_dims:],
                               use_kernel=use_kernel, interpret=interpret)
        return raw4 + raw2
    raise ValueError(f"unsupported bits={bits}")


def score_packed(
    q_rot: jnp.ndarray,
    enc: qz.Encoded,
    *,
    use_kernel: Optional[bool] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Metric-adjusted scores [b, n] for an Encoded corpus (any bit mode)."""
    raw = score_raw(enc.packed, q_rot, bits=enc.bits, n4_dims=enc.n4_dims,
                    use_kernel=use_kernel, interpret=interpret)
    return adjust_scores(raw, enc.qnorms, enc.metric)


# ---------------------------------------------------------------------------
# Binarized coarse-scan proxies (cascade stage 1; DESIGN.md §11).
# ---------------------------------------------------------------------------

def sign_coarse_raw(
    cbits: jnp.ndarray,      # [n, d'/8] uint8 — packed corpus sign bits
    qbits: jnp.ndarray,      # [b, d'/8] uint8 — packed query sign bits
    *,
    use_kernel: Optional[bool] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Hamming distances [b, n] (int32); pads to tile multiples and unpads.

    Both dispatch paths are bit-identical by construction (integer
    arithmetic); zero pad bytes XOR to 0 and contribute exactly 0.
    """
    use_kernel, interpret = resolve_dispatch(use_kernel, interpret)
    if not use_kernel:
        return binary_dot.sign_hamming_jnp(cbits, qbits)

    n, dk = cbits.shape
    b = qbits.shape[0]
    bq = min(8, _round_up(b, 8))
    bn = min(256, _round_up(n, 128))
    bk = min(128, dk)        # dk is a power of two (d' = pow2 >= 8), so bk | dk
    b_pad, n_pad = _round_up(b, bq), _round_up(n, bn)
    cbits_p = jnp.pad(cbits, ((0, n_pad - n), (0, 0)))
    qbits_p = jnp.pad(qbits, ((0, b_pad - b), (0, 0)))
    out = binary_dot.sign_hamming_raw(
        cbits_p, qbits_p,
        block_q=bq, block_n=bn, block_k=bk, interpret=interpret,
    )
    return out[:b, :n]


def crumb_coarse_raw(
    ccodes: jnp.ndarray,     # [n, d'/4] uint8 — corpus crumb planes (hi || lo)
    qplanes: jnp.ndarray,    # [b, d'/4] uint8 — query crumb planes (hi || lo)
    *,
    use_kernel: Optional[bool] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Crumb affinities [b, n] (int32) from plane-packed codes.

    Both byte arrays carry the hi bit plane then the lo bit plane, each
    d'/8 bytes (binary.derive_codes / binary.query_crumb_planes layout).
    The kernel takes the query as int8 levels; pad queries are level 0 and
    pad plane bytes meet level-0 query dims, so padding contributes 0.
    """
    use_kernel, interpret = resolve_dispatch(use_kernel, interpret)
    n, w = ccodes.shape
    b = qplanes.shape[0]
    dkp = w // 2
    if not use_kernel:
        return binary_dot.crumb_affinity_jnp(
            ccodes[:, :dkp], ccodes[:, dkp:], qplanes[:, :dkp], qplanes[:, dkp:],
            dim=dkp * 8)

    kp = _round_up(dkp, binary_dot.LANE)
    if kp != dkp:        # d' < 1024: widen each plane to whole lanes
        ccodes = jnp.pad(ccodes.reshape(n, 2, dkp),
                         ((0, 0), (0, 0), (0, kp - dkp))).reshape(n, 2 * kp)
    bq, bn = binary_dot.crumb_blocks(b, n, kp)
    qlev = binary_dot.crumb_query_levels(qplanes)
    qlev = jnp.pad(qlev, ((0, 0), (0, _round_up(b, bq) - b), (0, kp - dkp)))
    out = binary_dot.crumb_affinity_raw(
        ccodes, qlev, block_q=bq, block_n=bn, interpret=interpret)
    return out[:b]


# ---------------------------------------------------------------------------
# Gathered candidate-set scoring (IVF probe lists, HNSW frontiers).
# ---------------------------------------------------------------------------

def _pad_gathered(gathered, planes, bb, bm, bk):
    """Pad [b, m, dk] bytes + [p, b, dk] planes to block multiples.

    k-padding is safe (padded plane entries are zero, so any byte contributes
    exactly 0); b/m padding is sliced off by the caller.  Both dispatch paths
    pad identically — a precondition of their bit-identity.
    """
    b, m, dk = gathered.shape
    b_pad, m_pad, k_pad = _round_up(b, bb), _round_up(m, bm), _round_up(dk, bk)
    gathered = jnp.pad(gathered, ((0, b_pad - b), (0, m_pad - m), (0, k_pad - dk)))
    planes = jnp.pad(planes, ((0, 0), (0, b_pad - b), (0, k_pad - dk)))
    return gathered, planes


def _gather_nibble_raw(
    gathered: jnp.ndarray,   # [b, mc, d/2] uint8 — pre-gathered candidate rows
    q_rot: jnp.ndarray,      # [b, d] rotated f32 queries
    use_kernel: bool,
    interpret: bool,
) -> jnp.ndarray:
    b, mc, dk = gathered.shape
    planes = deinterleave_query(q_rot, 2)             # [2, b, dk]
    bb, bm, bk = gather_dot.gather_blocks(b, mc, dk)
    gathered_p, planes_p = _pad_gathered(gathered, planes, bb, bm, bk)
    if use_kernel:
        out = gather_dot.gather_nibble_dot_raw(
            gathered_p, planes_p[0], planes_p[1],
            block_b=bb, block_m=bm, block_k=bk, interpret=interpret,
        )
    else:
        out = gather_dot.gather_nibble_dot_jnp(
            gathered_p, planes_p[0], planes_p[1],
            block_b=bb, block_m=bm, block_k=bk,
        )
    return out[:b, :mc]


def _gather_crumb_raw(
    gathered: jnp.ndarray,   # [b, mc, d/4] uint8
    q_rot: jnp.ndarray,
    use_kernel: bool,
    interpret: bool,
) -> jnp.ndarray:
    b, mc, dk = gathered.shape
    planes = deinterleave_query(q_rot, 4)             # [4, b, dk]
    bb, bm, bk = gather_dot.gather_blocks(b, mc, dk)
    bk = min(bk, 128)
    gathered_p, planes_p = _pad_gathered(gathered, planes, bb, bm, bk)
    if use_kernel:
        out = gather_dot.gather_crumb_dot_raw(
            gathered_p, planes_p,
            block_b=bb, block_m=bm, block_k=bk, interpret=interpret,
        )
    else:
        out = gather_dot.gather_crumb_dot_jnp(
            gathered_p, planes_p,
            block_b=bb, block_m=bm, block_k=bk,
        )
    return out[:b, :mc]


def score_gathered_raw(
    packed: jnp.ndarray,     # [n, bytes] packed corpus
    q_rot: jnp.ndarray,      # [b, d'] rotated f32 queries
    cand: jnp.ndarray,       # [b, mc] row indices (callers clamp/mask -1 pads)
    *,
    bits: int,
    n4_dims: int = 0,
    use_kernel: Optional[bool] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Raw (un-adjusted) scores [b, mc] of row ``cand[b, i]`` vs query ``b``.

    The single bit-mode dispatch point for candidate-set scans — the IVF probe
    scan and the HNSW beam both go through here, so gathered packed bytes are
    interpreted identically on every path (the ``score_raw`` invariant,
    extended to per-query row subsets).  The gather itself stays uint8.
    """
    use_kernel, interpret = resolve_dispatch(use_kernel, interpret)
    gathered = jnp.take(packed, cand, axis=0)         # [b, mc, bytes] uint8
    if bits == 4:
        return _gather_nibble_raw(gathered, q_rot, use_kernel, interpret)
    if bits == 2:
        return _gather_crumb_raw(gathered, q_rot, use_kernel, interpret)
    if bits == 3:  # mixed [4-bit | 2-bit]
        b4 = n4_dims // 2
        raw4 = _gather_nibble_raw(gathered[:, :, :b4], q_rot[:, :n4_dims],
                                  use_kernel, interpret)
        raw2 = _gather_crumb_raw(gathered[:, :, b4:], q_rot[:, n4_dims:],
                                 use_kernel, interpret)
        return raw4 + raw2
    raise ValueError(f"unsupported bits={bits}")


def score_gathered(
    packed: jnp.ndarray,
    q_rot: jnp.ndarray,
    cand: jnp.ndarray,       # [b, mc] row indices, -1 = padding
    valid: Optional[jnp.ndarray] = None,   # [b, mc] bool; default cand >= 0
    *,
    bits: int,
    n4_dims: int = 0,
    qnorms: Optional[jnp.ndarray] = None,  # [n]; with metric -> adjusted scores
    metric: Optional[str] = None,
    allow_mask: Optional[jnp.ndarray] = None,  # [n] bool allowlist (pre-top-k)
    use_kernel: Optional[bool] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Scores [b, mc] for per-query candidate sets, masked BEFORE any top-k.

    ``-1`` sentinel rows (CSR padding), disallowed rows, and ``valid=False``
    rows all come back as NEG, so a stable top-k over the result honors the
    §3.5 pre-filter guarantee.  With ``qnorms``+``metric`` the scores are
    metric-adjusted; otherwise raw dot products.
    """
    valid_ = cand >= 0 if valid is None else valid
    cand_c = jnp.maximum(cand, 0)
    scores = score_gathered_raw(packed, q_rot, cand_c, bits=bits,
                                n4_dims=n4_dims, use_kernel=use_kernel,
                                interpret=interpret)
    if qnorms is not None:
        assert metric is not None, "metric required to adjust scores"
        scores = adjust_scores(scores, jnp.take(qnorms, cand_c, axis=0), metric)
    if allow_mask is not None:
        valid_ = valid_ & jnp.take(allow_mask, cand_c, axis=0)
    return jnp.where(valid_, scores, NEG)
