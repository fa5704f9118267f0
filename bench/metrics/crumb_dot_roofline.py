"""Roofline share of the crumb coarse-scan kernel (kernels/binary_dot).

The 2-bit levels fit int8, so the least time is 2 b n d' operations at the
int8 peak, or reading n d'/4 crumb bytes and b d'/4 query bytes at the HBM
peak, whichever is longer, where n is the rows one event scans: a corpus
sharded over the cell's chips runs one event per chip over its
``rows_per_chip`` rows.  Share = events x least time / summed device time of
the kernel's events.
"""

from bench import trace

KERNEL = "crumb_affinity_raw"


def least_seconds(b: int, n: int, d_pad: int, peaks: dict) -> float:
    ops = 2.0 * b * n * d_pad
    moved = n * d_pad / 4 + b * d_pad / 4
    return max(ops / peaks["int8_ops"], moved / peaks["hbm_bytes_per_s"])


def read(ctx):
    ev = trace.kernel_events(ctx["trace"], KERNEL)
    if not ev:
        return None
    c = ctx["cell"]
    least = least_seconds(c["bucket"], c["rows_per_chip"], c["d_pad"], ctx["peaks"])
    return 100.0 * len(ev) * least / (sum(d for _, _, d in ev) / 1e9)
