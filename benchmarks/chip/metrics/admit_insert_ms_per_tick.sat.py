"""Host admission, the placement of new flows (retired and spill
membership, FlowTable.insert_batch's bucket probing, spill placement):
``tick/admit/insert`` span seconds per ingest call, in ms."""
from benchmarks.chip.trace_reduce import span_seconds


def read(ctx):
    s = ctx["trace"] and span_seconds(ctx["trace"], "tick/admit/insert")
    return None if s is None or not ctx["ticks"] else s * 1e3 / ctx["ticks"]
