"""Milliseconds per call in which the device is idle, inside ``monavec.sync``:
waiting for the answer and copying it to the host (bench/spans.py)."""

from bench import spans


def read(ctx):
    return spans.idle_ms_per_call(ctx["trace"], "sync")
