"""Verdict fetch, the release of the finished flows' slots (their verdicts
gathered, then one FlowTable.free per slot): ``tick/evict`` span seconds
per ingest call, in ms."""
from benchmarks.chip.trace_reduce import span_seconds


def read(ctx):
    s = ctx["trace"] and span_seconds(ctx["trace"], "tick/evict")
    return None if s is None or not ctx["ticks"] else s * 1e3 / ctx["ticks"]
