"""Verdict fetch (bulk device_get of the tick's verdict buffers):
``tick/fetch`` span seconds per ingest call, in ms.  The span holds the
wait for the device as well as the copy."""
from benchmarks.chip.trace_reduce import span_seconds


def read(ctx):
    s = ctx["trace"] and span_seconds(ctx["trace"], "tick/fetch")
    return None if s is None or not ctx["ticks"] else s * 1e3 / ctx["ticks"]
