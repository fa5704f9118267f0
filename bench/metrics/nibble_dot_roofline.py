"""Roofline share of the full-scan kernel (kernels/nibble_dot).

The least time counts the work of the operation, not of its implementation:
2 b n d' multiply-adds at the bf16 peak (no float32 peak is published, so
the bf16 one is the ceiling), or reading n d'/2 code bytes, 4 n bytes of
norms and 4 b d' bytes of queries at the HBM peak, whichever is longer.
Share = events x least time / summed device time of the kernel's events.
"""

from bench import trace

KERNEL = "nibble_dot_raw"


def least_seconds(b: int, n: int, d_pad: int, peaks: dict) -> float:
    flops = 2.0 * b * n * d_pad
    moved = n * d_pad / 2 + 4 * n + 4 * b * d_pad
    return max(flops / peaks["bf16_flops"], moved / peaks["hbm_bytes_per_s"])


def read(ctx):
    ev = trace.kernel_events(ctx["trace"], KERNEL)
    if not ev:
        return None
    c = ctx["cell"]
    least = least_seconds(c["bucket"], c["n"], c["d_pad"], ctx["peaks"])
    return 100.0 * len(ev) * least / (sum(d for _, _, d in ev) / 1e9)
