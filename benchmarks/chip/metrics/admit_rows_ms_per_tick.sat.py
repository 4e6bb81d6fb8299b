"""Host admission, the admitted slots' rows (_admit_batch: host metadata
writes, padding, the copy to the device, the admit_rows call):
``tick/admit/rows`` span seconds per ingest call, in ms."""
from benchmarks.chip.trace_reduce import span_seconds


def read(ctx):
    s = ctx["trace"] and span_seconds(ctx["trace"], "tick/admit/rows")
    return None if s is None or not ctx["ticks"] else s * 1e3 / ctx["ticks"]
