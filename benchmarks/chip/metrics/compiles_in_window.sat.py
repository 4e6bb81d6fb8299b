"""Tick dispatch: programs lowered (compiled, or loaded from the
persistent cache) inside the window, from JAX's monitoring events."""

def read(ctx):
    return ctx["compiles_in_window"]
