"""Device: table rows the fused tick gathered and wrote back per ingest
call (its padded column count), from the server's ``tick_rows`` counter
over the window; a server without the counter reads nothing."""

def read(ctx):
    st = ctx["stats"]
    rows, ticks = st.get("tick_rows"), st.get("ticks")
    return None if rows is None or not ticks else rows / ticks
