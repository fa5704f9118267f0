"""Milliseconds per call in which the device is idle, inside a call (a
``bench.search`` span) but outside every engine phase: the searcher handle,
tenancy and ``MonaVec.search`` (bench/spans.py)."""

from bench import spans


def read(ctx):
    return spans.idle_ms_per_call(ctx["trace"], "entry")
