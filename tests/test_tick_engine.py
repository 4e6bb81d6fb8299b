"""The fused tick engine's perf contract: O(1) device dispatches per
ingest tick, regardless of how many packet ranks the tick packs or how
many drain rounds the hop loop needs.  Wall-clock on shared boxes is
noisy; dispatch counts are deterministic, so this is the regression
bar the cost model's call/sync terms justify."""
import numpy as np
import pytest

from repro.core.inference import Engine, EngineOptions
from repro.flows.synthetic import PacketBatch, make_packet_stream
from repro.serve import FlowTableServer, StreamVerdicts
from repro.tuning import (
    TICK_ENGINES,
    ShapeInfo,
    choose_tick_engine,
    choose_tick_plan,
    estimate_tick_us,
    tick_work_terms,
)

P = 3


@pytest.fixture(scope="module")
def tick_setup(trained_pdt):
    pdt, _, tr = trained_pdt
    eng = Engine.from_model(pdt)
    stream = make_packet_stream(tr, seed=23, profile="steady")
    return eng, tr, stream


def _whole_flow_ticks(tr, flows_per_tick):
    """Ticks delivering each flow's ENTIRE packet train at once — the
    deepest rank chains a tick can have (rank count = flow length)."""
    order = np.argsort(tr.lengths)[::-1]
    for at in range(0, order.size, flows_per_tick):
        sel = order[at:at + flows_per_tick]
        fid = np.concatenate(
            [np.full(int(tr.lengths[i]), i, np.int64) for i in sel])
        flen = tr.lengths[fid].astype(np.int32)
        pkts = np.concatenate(
            [tr.packets[i, :int(tr.lengths[i])] for i in sel])
        arr = np.arange(fid.size, dtype=np.float64)
        yield PacketBatch(fid, flen, pkts.astype(np.float32), arr)


def _dispatch_deltas(srv, batches):
    deltas = []
    for b in batches:
        before = srv.stats.dispatches
        srv.ingest(b)
        deltas.append(srv.stats.dispatches - before)
    return deltas


# ---------------------------------------------------------------------------
# the perf bar: constant dispatches per tick
# ---------------------------------------------------------------------------
def test_fused_tick_dispatches_constant(tick_setup):
    """Fused ticks cost at most 2 dispatches (admission scatter + tick
    step) no matter the rank depth: a tick of 1-packet ranks and a tick
    holding whole flows (rank depth = max flow length, every window
    boundary + full drain inside) must count the same."""
    eng, tr, stream = tick_setup
    # shallow ticks: stream order, small tick => few ranks
    srv = FlowTableServer(eng, n_buckets=64, bucket_size=8,
                          tick_engine="fused")
    shallow = _dispatch_deltas(srv, stream.ticks(64))
    # deep ticks: whole flows per tick => rank depth = flow length
    srv2 = FlowTableServer(eng, n_buckets=64, bucket_size=8,
                           tick_engine="fused")
    deep = _dispatch_deltas(srv2, _whole_flow_ticks(tr, 16))
    assert max(shallow) <= 2 and max(deep) <= 2
    # identical bound on wildly different tick shapes — O(1) dispatches
    assert max(deep) <= max(shallow) + 0  # deep ticks cost no extra calls
    assert set(shallow) | set(deep) <= {1, 2}


def test_legacy_tick_dispatches_grow_with_ranks(tick_setup):
    """The baseline the fused engine replaces: per-rank fold dispatches
    plus per-drain-round hop dispatches, so whole-flow ticks cost far
    more calls than shallow ticks — the O(ranks + drains) shape the
    cost model's legacy branch charges for."""
    eng, tr, stream = tick_setup
    srv = FlowTableServer(eng, n_buckets=64, bucket_size=8,
                          tick_engine="legacy")
    shallow = _dispatch_deltas(srv, stream.ticks(64))
    srv2 = FlowTableServer(eng, n_buckets=64, bucket_size=8,
                           tick_engine="legacy")
    deep = _dispatch_deltas(srv2, _whole_flow_ticks(tr, 16))
    assert max(deep) > max(shallow)
    assert max(deep) > 2 * max(1, min(shallow))


def test_fused_tick_dispatches_independent_of_drain_rounds(tick_setup):
    """Flows shorter than P packets drain multiple empty trailing
    windows in one tick; the fused engine's in-jit while_loop keeps the
    dispatch count at <= 2 anyway."""
    eng, _, _ = tick_setup
    srv = FlowTableServer(eng, n_buckets=8, bucket_size=4,
                          tick_engine="fused")
    # single-packet flows: window [0,1) completes on the only packet and
    # partitions 1..P-1 are all empty => P-1 drain rounds inside the jit
    from repro.core.features import PKT_NFIELDS
    fid = np.arange(12, dtype=np.int64)
    batch = PacketBatch(fid, np.ones(12, np.int32),
                        np.zeros((12, PKT_NFIELDS), np.float32),
                        np.arange(12, dtype=np.float64))
    before = srv.stats.dispatches
    v = srv.ingest(batch)
    assert srv.stats.dispatches - before <= 2
    assert v.n_flows == 12  # every flow drained to a verdict in-tick


# ---------------------------------------------------------------------------
# cost model: tick-shape terms route the engines
# ---------------------------------------------------------------------------
def _shape(eng, B=512):
    return ShapeInfo.from_engine(eng, None, B=B, W=1)


def test_tick_work_terms_shapes(tick_setup):
    eng, _, _ = tick_setup
    shape = _shape(eng)
    from repro.tuning import candidate_plans
    plan = candidate_plans(shape, compact=False)[0]
    from repro.tuning.costmodel import TERMS
    t = {name: i for i, name in enumerate(TERMS)}
    legacy = tick_work_terms(shape, plan, ranks=8, tick_engine="legacy")
    fused = tick_work_terms(shape, plan, ranks=8, tick_engine="fused")
    # legacy pays one call per rank + hop and one sync per hop round;
    # fused pays a constant call+sync budget
    assert legacy[t["call"]] > fused[t["call"]]
    assert legacy[t["sync"]] > fused[t["sync"]]
    assert fused[t["call"]] == pytest.approx(2.0)
    assert fused[t["sync"]] == pytest.approx(1.0)
    with pytest.raises(ValueError):
        tick_work_terms(shape, plan, tick_engine="looped")


def test_tick_estimate_scaling(tick_setup):
    """Legacy's estimate must grow with rank depth; fused's dispatch
    overhead must stay flat (only the fold work term grows)."""
    eng, _, _ = tick_setup
    shape = _shape(eng)
    from repro.tuning import candidate_plans
    plan = candidate_plans(shape, compact=False)[0]
    legacy = [estimate_tick_us(shape, plan, ranks=r, tick_engine="legacy")
              for r in (1, 8, 64)]
    fused = [estimate_tick_us(shape, plan, ranks=r, tick_engine="fused")
             for r in (1, 8, 64)]
    assert legacy[0] < legacy[1] < legacy[2]
    # dispatch overhead: the fused/legacy gap widens with rank count
    assert (legacy[2] - fused[2]) > (legacy[0] - fused[0])
    assert all(f < l for f, l in zip(fused, legacy))


def test_choose_tick_engine_prefers_fused_on_cpu(tick_setup):
    """On CPU, dispatch overhead dominates — auto must route fused for
    any realistic rank depth, which is what tick_engine='auto' uses."""
    eng, _, _ = tick_setup
    shape = _shape(eng)
    for ranks in (1, 4, 32):
        assert choose_tick_engine(shape, ranks=ranks) == "fused"
    engine, plan = choose_tick_plan(shape, ranks=4)
    assert engine in TICK_ENGINES
    assert plan.backend in ("fused", "pallas")


def test_server_auto_resolves_tick_engine(tick_setup):
    eng, _, stream = tick_setup
    srv = FlowTableServer(eng, n_buckets=16, bucket_size=4)
    assert srv.tick_engine in ("fused", "legacy")  # "auto" resolved
    assert srv.tick_engine == "fused"  # CPU: dispatch overhead dominates
    with pytest.raises(ValueError):
        FlowTableServer(eng, tick_engine="warp")


# ---------------------------------------------------------------------------
# engines are interchangeable: identical verdicts, identical stats
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("impl", ["fused", "pallas"])
def test_tick_engines_bit_identical(tick_setup, impl):
    eng, tr, stream = tick_setup
    outs = {}
    for te in ("fused", "legacy"):
        srv = FlowTableServer(
            eng, n_buckets=32, bucket_size=4, tick_engine=te,
            options=EngineOptions(impl=impl))
        parts = [srv.ingest(b) for b in stream.ticks(97)]
        parts.append(srv.flush())
        outs[te] = (StreamVerdicts.concat(parts), srv.stats)
    a, sa = outs["fused"]
    b, sb = outs["legacy"]
    oa, ob = np.argsort(a.flow_id), np.argsort(b.flow_id)
    np.testing.assert_array_equal(a.flow_id[oa], b.flow_id[ob])
    np.testing.assert_array_equal(a.labels[oa], b.labels[ob])
    np.testing.assert_array_equal(a.recircs[oa], b.recircs[ob])
    np.testing.assert_array_equal(a.exit_partition[oa],
                                  b.exit_partition[ob])
    # same admission story: EVERY stats field except the engine-dependent
    # ones (device calls and copies, the engines' whole difference)
    # agrees, the hash probes included
    from repro.serve import ServerStats
    for f in ServerStats.FIELDS:
        if f in ServerStats.ENGINE_DEPENDENT:
            continue
        assert getattr(sa, f) == getattr(sb, f), f
    assert sa.dispatches < sb.dispatches  # the whole point


def test_tick_engines_stats_agree_under_spill_and_timeout(tick_setup):
    """The stats-drift audit bar: a tiny table (constant spill traffic)
    plus an aggressive timeout (eviction sentinels) exercises every
    counter-update path — fused and legacy must still agree on all
    stats fields, including the spill-run dispatches both engines now
    count identically."""
    eng, tr, stream = tick_setup
    from repro.serve import ServerStats
    outs = {}
    for te in ("fused", "legacy"):
        srv = FlowTableServer(eng, n_buckets=2, bucket_size=2,
                              tick_engine=te, timeout=0.005)
        parts = [srv.ingest(b) for b in stream.ticks(131)]
        parts.append(srv.flush())
        outs[te] = (StreamVerdicts.concat(parts), srv.stats)
    a, sa = outs["fused"]
    b, sb = outs["legacy"]
    assert sa.spilled > 0          # the tiny table forced the host path
    assert sa.evicted > 0          # the timeout fired
    oa, ob = np.argsort(a.flow_id), np.argsort(b.flow_id)
    np.testing.assert_array_equal(a.flow_id[oa], b.flow_id[ob])
    np.testing.assert_array_equal(a.labels[oa], b.labels[ob])
    for f in ServerStats.FIELDS:
        if f in ServerStats.ENGINE_DEPENDENT:
            continue
        assert getattr(sa, f) == getattr(sb, f), f
    assert sa.dispatches < sb.dispatches
    # a placed key examines one bucket or more, a refused key all 2
    assert sa.insert_probes >= sa.flows_seen - sa.spilled + 2 * sa.spilled


# ---------------------------------------------------------------------------
# transfer and probe counters
# ---------------------------------------------------------------------------
def test_fused_tick_copies_five_verdict_rows_per_slot(tick_setup):
    """Each fused tick that serves resident packets copies its five
    (N,) int32 verdict buffers to the host, and nothing else does while
    no flow spills."""
    eng, tr, stream = tick_setup
    srv = FlowTableServer(eng, n_buckets=64, bucket_size=8,
                          tick_engine="fused")
    N = srv.table.capacity
    for b in stream.ticks(97):
        before = srv.stats.d2h_bytes
        srv.ingest(b)
        assert srv.stats.d2h_bytes - before == 5 * 4 * N
    assert srv.stats.spilled == 0


@pytest.fixture(scope="module")
def probe_runs(tick_setup):
    """The same stream through an 8 x 4 table on each tick engine:
    (stats per engine, the unique flow ids summed over its ticks)."""
    eng, tr, stream = tick_setup
    ticks = list(stream.ticks(97))
    stats = {}
    for te in ("fused", "legacy"):
        srv = FlowTableServer(eng, n_buckets=8, bucket_size=4,
                              tick_engine=te)
        for b in ticks:
            srv.ingest(b)
        stats[te] = srv.stats
    n_keys = sum(np.unique(np.asarray(b.flow_id)).size for b in ticks)
    return stats, n_keys


def test_insert_probes_agree_across_engines(probe_runs):
    """The hash probes depend on admission alone: the same stream gives
    the same count in both engines, at least one bucket per placed flow
    and more once buckets overflow into their neighbours."""
    stats, _ = probe_runs
    sa, sb = stats["fused"], stats["legacy"]
    assert sa.insert_probes == sb.insert_probes
    assert sa.insert_probes > sa.flows_seen - sa.spilled
    assert sa.d2h_bytes != sb.d2h_bytes     # engine-dependent, by design


def test_lookup_probes_agree_across_engines(probe_runs):
    """Each tick looks its unique flow ids up once; the buckets those
    lookups examine depend on admission alone, so both engines count
    the same, at least one bucket per key and more once keys have
    overflowed their home buckets."""
    stats, n_keys = probe_runs
    sa, sb = stats["fused"], stats["legacy"]
    assert sa.lookup_keys == sb.lookup_keys == n_keys
    assert sa.lookup_probes == sb.lookup_probes > sa.lookup_keys
    assert sa.registry.counter("serve_lookup_probes_total").value == \
        sa.lookup_probes
