"""Host admission, the lookup of the tick's flows (np.unique over the flow
ids, FlowTable.lookup_batch): ``tick/admit/lookup`` span seconds per
ingest call in the traced window, in ms."""
from benchmarks.chip.trace_reduce import span_seconds


def read(ctx):
    s = ctx["trace"] and span_seconds(ctx["trace"], "tick/admit/lookup")
    return None if s is None or not ctx["ticks"] else s * 1e3 / ctx["ticks"]
