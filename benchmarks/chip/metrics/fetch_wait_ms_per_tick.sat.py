"""Verdict fetch, the wait for the device (jax.block_until_ready on the
tick's verdict buffers): ``tick/fetch/wait`` span seconds per ingest
call, in ms."""
from benchmarks.chip.trace_reduce import span_seconds


def read(ctx):
    s = ctx["trace"] and span_seconds(ctx["trace"], "tick/fetch/wait")
    return None if s is None or not ctx["ticks"] else s * 1e3 / ctx["ticks"]
