"""Tick dispatch, the copy of the packed tick to the device (jnp.asarray
of the slot and packet arrays): ``tick/dispatch/put`` span seconds per
ingest call, in ms."""
from benchmarks.chip.trace_reduce import span_seconds


def read(ctx):
    s = ctx["trace"] and span_seconds(ctx["trace"], "tick/dispatch/put")
    return None if s is None or not ctx["ticks"] else s * 1e3 / ctx["ticks"]
