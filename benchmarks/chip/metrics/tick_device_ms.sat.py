"""Device: device time of the ``tick_step`` program per ingest call, in
ms, from the trace (executions of ``jit_tick_step`` on the ``XLA
Modules`` line)."""
PROGRAM = r"^jit_tick_step\b"


def read(ctx):
    t = ctx["trace"]
    if t is None or not ctx["ticks"]:
        return None
    s = t.program_seconds(PROGRAM)
    return s * 1e3 / ctx["ticks"] if s > 0 else None
