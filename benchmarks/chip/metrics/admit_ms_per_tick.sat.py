"""Host admission (FlowTableServer._route_tick, FlowTable): ``tick/admit``
span seconds in the traced window, per ingest call, in ms."""
from benchmarks.chip.trace_reduce import span_seconds


def read(ctx):
    s = ctx["trace"] and span_seconds(ctx["trace"], "tick/admit")
    return None if s is None or not ctx["ticks"] else s * 1e3 / ctx["ticks"]
