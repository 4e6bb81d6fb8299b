"""repro.obs: registry semantics, span tracing, the ``SPLIDT_OBS=0``
no-op contract, and live-metrics parity.

The parity tests are the acceptance bar from the paper's evaluation:
every number the live registry reports (recirc overhead, TTD
quantiles, dispatch counts) must be *recomputable offline* from the
raw :class:`StreamVerdicts` plus the replayable packet stream — exact
equality for counters, same-bucket equality for latencies."""
import json
import urllib.request

import numpy as np
import pytest

from repro import obs
from repro.core.inference import Engine, EngineOptions
from repro.flows.synthetic import make_packet_stream
from repro.obs import (
    Histogram,
    MetricRegistry,
    MetricsReporter,
    exp_edges,
)
from repro.serve import FlowTableServer, ServerStats, StreamVerdicts
from repro.serve.flowtable import TTD_EDGES


# ---------------------------------------------------------------------------
# MetricRegistry primitives
# ---------------------------------------------------------------------------
def test_counter_monotonic():
    reg = MetricRegistry()
    c = reg.counter("x_total", "doc")
    c.inc()
    c.inc(41)
    assert c.value == 42
    with pytest.raises(ValueError):
        c.inc(-1)
    # get-or-create returns the same live object
    assert reg.counter("x_total") is c


def test_gauge_set_add():
    g = MetricRegistry().gauge("x")
    g.set(2.5)
    g.add(-0.5)
    assert g.value == 2.0


def test_histogram_bucketing():
    h = Histogram("h", edges=[1.0, 10.0, 100.0])
    h.record(0.5)                       # below first edge
    h.record_many([1.0, 5.0, 50.0, 1e9])  # edge goes RIGHT (1.0 -> [1,10))
    assert [int(c) for c in h.counts] == [1, 2, 1, 1]
    assert h.total == 5
    assert h.bucket_of(0.0) == 0 and h.bucket_of(1.0) == 1
    assert h.bucket_of(float("inf")) == 3
    assert h.quantile(0.5) == 10.0      # upper edge of the median bucket
    assert h.quantile(1.0) == float("inf")
    assert np.isnan(Histogram("e", edges=[1.0]).quantile(0.5))


def test_histogram_rejects_bad_edges():
    for bad in ([], [3.0, 1.0], [1.0, 1.0]):
        with pytest.raises(ValueError):
            Histogram("h", edges=bad)
    with pytest.raises(ValueError):
        MetricRegistry().histogram("h")  # first use must pass edges


def test_exp_edges():
    e = exp_edges(0.001, 1000.0, 7)
    assert len(e) == 7
    assert e[0] == pytest.approx(0.001) and e[-1] == pytest.approx(1000.0)
    ratios = [b / a for a, b in zip(e, e[1:])]
    assert max(ratios) == pytest.approx(min(ratios))
    with pytest.raises(ValueError):
        exp_edges(0.0, 1.0, 4)


def test_label_identity():
    reg = MetricRegistry()
    a = reg.counter("d_total", labels={"backend": "fused"})
    b = reg.counter("d_total", labels={"backend": "pallas"})
    assert a is not b
    a.inc(3)
    # label order must not matter for identity
    c = reg.counter("d_total", labels={"backend": "fused"})
    assert c.value == 3
    snap = reg.snapshot()
    assert snap["counters"]['d_total{backend="fused"}']["value"] == 3


def test_snapshot_delta():
    reg = MetricRegistry()
    c = reg.counter("c_total")
    h = reg.histogram("h", edges=[1.0, 2.0])
    c.inc(5)
    h.record(0.5)
    before = reg.snapshot()
    c.inc(2)
    h.record(1.5)
    d = MetricRegistry.delta(before, reg.snapshot())
    assert d["counters"]["c_total"]["value"] == 2
    assert d["histograms"]["h"]["counts"] == [0, 1, 0]
    assert d["histograms"]["h"]["total"] == 1


def test_prometheus_exposition():
    reg = MetricRegistry()
    reg.counter("pkts_total", "packets").inc(7)
    reg.gauge("load").set(0.25)
    h = reg.histogram("lat_seconds", "latency", edges=[0.1, 1.0])
    h.record_many([0.05, 0.5, 5.0])
    text = reg.to_prometheus()
    assert "# TYPE pkts_total counter" in text
    assert "pkts_total 7" in text
    assert "load 0.25" in text
    # histogram buckets are cumulative and end at +Inf
    assert 'lat_seconds_bucket{le="0.1"} 1' in text
    assert 'lat_seconds_bucket{le="1"} 2' in text
    assert 'lat_seconds_bucket{le="+Inf"} 3' in text
    assert "lat_seconds_count 3" in text
    # JSON exposition round-trips
    assert json.loads(reg.to_json())["counters"]["pkts_total"]["value"] == 7


def test_global_registry_swap():
    mine = MetricRegistry()
    prev = obs.set_registry(mine)
    try:
        assert obs.get_registry() is mine
    finally:
        obs.set_registry(prev)
    assert obs.get_registry() is prev


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------
def test_span_nesting_and_tree():
    prev = obs.set_enabled(True)
    obs.reset_spans()
    try:
        for _ in range(3):
            with obs.span("tick"):
                with obs.span("tick/pack"):
                    pass
                with obs.span("tick/dispatch"):
                    pass
        tree = obs.span_tree()
    finally:
        obs.set_enabled(prev)
        obs.reset_spans()
    assert "tick" in tree and "tick/pack" in tree
    # re-entry aggregates into one node, not three
    assert "       3 calls" in tree
    assert obs.span_tree() == "(no spans recorded)"


def test_null_span_is_shared_singleton():
    prev = obs.set_enabled(False)
    try:
        assert not obs.enabled()
        # the whole disabled path: one shared object, no allocation
        assert obs.span("a") is obs.span("b")
        with obs.span("a"):
            pass
        assert obs.span_tree() == "(no spans recorded)"
    finally:
        obs.set_enabled(prev)


def test_null_span_with_trace_args_is_shared():
    """Trace arguments do not open a way round the no-op: a disabled
    ``span(name, tick=n)`` is the same shared object."""
    prev = obs.set_enabled(False)
    try:
        assert obs.span("tick/ingest", tick=7) is obs.span("a")
        with obs.span("tick/ingest", tick=8):
            pass
        assert obs.span_tree() == "(no spans recorded)"
    finally:
        obs.set_enabled(prev)


def test_enabled_span_passes_trace_args(monkeypatch):
    """An enabled span hands its keyword arguments to the profiler
    annotation under the span's own name."""
    from repro.obs import trace
    seen = []

    class Annotation:
        def __init__(self, name, **args):
            seen.append((name, args))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(trace, "_ANNOTATION", Annotation)
    prev = obs.set_enabled(True)
    try:
        with obs.span("tick/ingest", tick=3):
            with obs.span("tick/stamp"):
                pass
    finally:
        obs.set_enabled(prev)
        obs.reset_spans()
    assert seen == [("tick/ingest", {"tick": 3}), ("tick/stamp", {})]


# ---------------------------------------------------------------------------
# reporter
# ---------------------------------------------------------------------------
def test_reporter_jsonl(tmp_path):
    reg = MetricRegistry()
    reg.counter("n_total").inc(9)
    path = tmp_path / "metrics.jsonl"
    rep = MetricsReporter(str(path), registry=reg, interval_s=3600.0)
    rep.dump_once()
    reg.counter("n_total").inc(1)
    rep.close()  # close flushes one final line
    lines = [json.loads(x) for x in path.read_text().splitlines()]
    assert [x["seq"] for x in lines] == [0, 1]
    assert lines[0]["counters"]["n_total"]["value"] == 9
    assert lines[1]["counters"]["n_total"]["value"] == 10


def test_reporter_http_scrape():
    reg = MetricRegistry()
    reg.counter("scraped_total").inc(4)
    rep = MetricsReporter(None, registry=reg, http_port=0)
    try:
        url = f"http://127.0.0.1:{rep.http_port}/metrics"
        body = urllib.request.urlopen(url, timeout=10).read().decode()
    finally:
        rep.close()
    assert "scraped_total 4" in body


# ---------------------------------------------------------------------------
# serving integration: no-op contract + live parity
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def obs_setup(trained_pdt):
    pdt, _, tr = trained_pdt
    return Engine.from_model(pdt), tr


def _serve(eng, tr, *, ticks=61, **kw):
    stream = make_packet_stream(tr, seed=29, profile="steady")
    srv = FlowTableServer(eng, n_buckets=32, bucket_size=4, **kw)
    parts = [srv.ingest(b) for b in stream.ticks(ticks)]
    parts.append(srv.flush())
    return StreamVerdicts.concat(parts), srv


def test_obs_disabled_is_bit_identical(obs_setup):
    """SPLIDT_OBS=0 must not change a single result bit or stats field
    (counters are product behaviour; only *timing* is switchable)."""
    eng, tr = obs_setup
    prev = obs.set_enabled(True)
    try:
        v_on, s_on = _serve(eng, tr)
        obs.set_enabled(False)
        v_off, s_off = _serve(eng, tr)
    finally:
        obs.set_enabled(prev)
    for f in ("flow_id", "labels", "recircs", "exit_partition"):
        np.testing.assert_array_equal(getattr(v_on, f), getattr(v_off, f))
    for f in ServerStats.FIELDS:  # INCLUDING dispatches
        assert getattr(s_on.stats, f) == getattr(s_off.stats, f), f
    # the registry views agree too (recirc overhead is counter-derived)
    g = "serve_recirc_overhead"
    assert (s_on.registry.gauge(g).value
            == s_off.registry.gauge(g).value)


def test_obs_enabled_overhead_bounded(obs_setup):
    """Coarse perf bar: instrumented serving stays within a small
    constant factor of the no-op path.  Wide tolerance — shared CI
    boxes are noisy — but it still catches a per-packet Python loop or
    an accidental device sync sneaking into the record path."""
    import time
    eng, tr = obs_setup

    def best_of(n, on):
        prev = obs.set_enabled(on)
        try:
            _serve(eng, tr)  # warm compile caches outside the clock
            times = []
            for _ in range(n):
                t0 = time.perf_counter()
                _serve(eng, tr)
                times.append(time.perf_counter() - t0)
        finally:
            obs.set_enabled(prev)
        return min(times)

    off = best_of(3, False)
    on = best_of(3, True)
    assert on <= 4.0 * off + 0.25  # generous: noise + span bookkeeping


@pytest.mark.parametrize("te", ["fused", "legacy"])
@pytest.mark.parametrize("impl", ["fused", "pallas"])
def test_live_metrics_parity(obs_setup, te, impl):
    """Every live number is recomputable offline from the raw verdicts
    plus the replayable stream: exact counters, same-bucket latencies.
    This is the paper's <0.05% recirc-overhead metric made auditable."""
    eng, tr = obs_setup
    stream = make_packet_stream(tr, seed=29, profile="steady")
    srv = FlowTableServer(eng, n_buckets=32, bucket_size=4,
                          tick_engine=te, options=EngineOptions(impl=impl))

    offline_ttd = Histogram("offline_ttd", edges=TTD_EDGES)
    first: dict[int, float] = {}
    now = -np.inf
    packets = 0
    parts = []

    def record_offline(v, now):
        ttd = now - np.asarray([first[f] for f in v.flow_id], np.float64)
        offline_ttd.record_many(ttd)

    for b in stream.ticks(61):
        packets += b.n_packets
        now = max(now, float(b.arrival.max()))
        for f, t in zip(b.flow_id.tolist(), b.arrival.tolist()):
            first.setdefault(f, t)  # arrivals are non-decreasing
        v = srv.ingest(b)
        record_offline(v, now)
        parts.append(v)
    v = srv.flush()
    record_offline(v, now)
    parts.append(v)
    verdicts = StreamVerdicts.concat(parts)

    reg = srv.registry
    # -- exact counters ------------------------------------------------
    recircs = int(np.asarray(verdicts.recircs, np.int64).sum())
    assert reg.counter("serve_recircs_total").value == recircs
    assert reg.counter("serve_packets_total").value == packets
    assert reg.counter("serve_verdicts_total").value == verdicts.n_flows
    assert reg.counter("serve_dispatches_total").value == srv.stats.dispatches
    assert srv.stats.dispatches > 0
    # -- derived gauge: the paper's recirc-overhead metric -------------
    assert (reg.gauge("serve_recirc_overhead").value
            == recircs / packets)
    # -- latency histogram: identical buckets, same-bucket quantiles ---
    live = reg.histogram("serve_ttd_seconds", edges=TTD_EDGES)
    assert live.total == verdicts.n_flows  # every verdict got a TTD
    np.testing.assert_array_equal(live.counts, offline_ttd.counts)
    for q in (0.5, 0.99):
        assert live.quantile(q) == offline_ttd.quantile(q)
    # -- recirc histogram mirrors the verdict distribution -------------
    rh = reg.snapshot()["histograms"]["serve_recircs_per_flow"]
    assert rh["total"] == verdicts.n_flows
    assert rh["sum"] == pytest.approx(float(recircs))
