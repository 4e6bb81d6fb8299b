"""Load generator: the traced window less the ``tick/ingest`` spans in
it (the harness and its traffic generator between ingest calls), per
ingest call, in ms."""
from benchmarks.chip.trace_reduce import span_seconds


def read(ctx):
    t = ctx["trace"]
    s = t and span_seconds(t, "tick/ingest")
    if s is None or not ctx["ticks"]:
        return None
    return (t.window_s - s) * 1e3 / ctx["ticks"]
