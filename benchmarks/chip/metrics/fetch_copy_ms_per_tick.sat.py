"""Verdict fetch, the copy of the finished tick's verdict buffers to the
host (device_get): ``tick/fetch/copy`` span seconds per ingest call, in
ms."""
from benchmarks.chip.trace_reduce import span_seconds


def read(ctx):
    s = ctx["trace"] and span_seconds(ctx["trace"], "tick/fetch/copy")
    return None if s is None or not ctx["ticks"] else s * 1e3 / ctx["ticks"]
