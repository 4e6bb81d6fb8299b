"""Tick dispatch: jitted device calls per ingest call, from the server's
``ServerStats`` counters over the window."""

def read(ctx):
    st = ctx["stats"]
    return None if not st.get("ticks") else st["dispatches"] / st["ticks"]
