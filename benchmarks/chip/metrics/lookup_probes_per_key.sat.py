"""Host admission: hash buckets examined per flow key looked up
(FlowTable.lookup_batch's bucket probe), from the server's
``lookup_probes`` and ``lookup_keys`` counters over the window; 1.0
when every key resolves, or stops, in its home bucket."""

def read(ctx):
    st = ctx["stats"]
    p, keys = st.get("lookup_probes"), st.get("lookup_keys")
    return None if p is None or not keys else p / keys
