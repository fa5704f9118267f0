"""Milliseconds per call in which the device is idle, inside
``monavec.execute``: the enqueue of the plan's stages and the eager ops
between them (bench/spans.py)."""

from bench import spans


def read(ctx):
    return spans.idle_ms_per_call(ctx["trace"], "dispatch")
