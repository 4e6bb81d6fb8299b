"""Host admission: hash buckets examined per new flow (FlowTable
inserts), from the server's ``insert_probes`` and ``flows_seen``
counters over the window."""

def read(ctx):
    st = ctx["stats"]
    p, flows = st.get("insert_probes"), st.get("flows_seen")
    return None if p is None or not flows else p / flows
