"""Milliseconds per call in which the device is idle, inside the engine's own
host phases, ``monavec.prepare``, ``monavec.plan_lookup`` and
``monavec.finish`` (bench/spans.py)."""

from bench import spans


def read(ctx):
    return spans.idle_ms_per_call(ctx["trace"], "engine")
