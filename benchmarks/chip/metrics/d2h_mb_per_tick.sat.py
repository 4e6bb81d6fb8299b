"""Verdict fetch: bytes the server copied from the device to the host
per ingest call, in MB (1e6 B), from the server's ``d2h_bytes`` counter
over the window."""

def read(ctx):
    st = ctx["stats"]
    b, ticks = st.get("d2h_bytes"), st.get("ticks")
    return None if b is None or not ticks else b / ticks / 1e6
