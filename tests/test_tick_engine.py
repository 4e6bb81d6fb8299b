"""The tick engine's contracts: O(1) device dispatches per ingest tick,
regardless of how many packet ranks the tick packs or how many drain
rounds the hop loop needs (wall-clock on shared boxes is noisy;
dispatch counts are deterministic), and verdicts equal to
``Engine.run`` on the rebuilt windows on either walk backend."""
import dataclasses
import itertools

import numpy as np
import pytest

from repro.core.inference import Engine, EngineOptions
from repro.flows.synthetic import PacketBatch, make_packet_stream
from repro.flows.windows import window_packets
from repro.serve import FlowTableServer, StreamVerdicts

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
    srv = FlowTableServer(eng, n_buckets=64, bucket_size=8)
    shallow = _dispatch_deltas(srv, stream.ticks(64))
    # deep ticks: whole flows per tick => rank depth = flow length
    srv2 = FlowTableServer(eng, n_buckets=64, bucket_size=8)
    deep = _dispatch_deltas(srv2, _whole_flow_ticks(tr, 16))
    assert max(shallow) <= 2 and max(deep) <= 2
    # identical bound on wildly different tick shapes — O(1) dispatches
    assert max(deep) <= max(shallow) + 0  # deep ticks cost no extra calls
    assert set(shallow) | set(deep) <= {1, 2}


def test_fused_tick_dispatches_independent_of_drain_rounds(tick_setup):
    """Flows shorter than P packets drain multiple empty trailing
    windows in one tick; the fused engine's in-jit while_loop keeps the
    dispatch count at <= 2 anyway."""
    eng, _, _ = tick_setup
    srv = FlowTableServer(eng, n_buckets=8, bucket_size=4)
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


@pytest.mark.parametrize("removed", ["auto", "legacy"])
def test_server_rejects_removed_tick_engines(tick_setup, removed):
    """One tick engine: the keyword accepts its name alone, and the
    removed router and per-rank engine raise instead of falling
    through."""
    eng, _, _ = tick_setup
    FlowTableServer(eng, n_buckets=16, bucket_size=4, tick_engine="fused")
    with pytest.raises(ValueError, match="fused"):
        FlowTableServer(eng, n_buckets=16, bucket_size=4,
                        tick_engine=removed)


# ---------------------------------------------------------------------------
# verdicts: the live tick == the batch walk, on either walk backend
# ---------------------------------------------------------------------------
def _assert_matches_run(v, want, fids=None):
    """Each verdict equals ``Engine.run``'s for its flow, once per
    flow; ``fids``, if given, is the set of flows that must have one."""
    fid = np.asarray(v.flow_id)
    assert np.unique(fid).size == fid.size             # one verdict a flow
    if fids is not None:
        assert set(map(int, fid)) == set(map(int, fids))
    np.testing.assert_array_equal(v.labels, np.asarray(want.labels)[fid])
    np.testing.assert_array_equal(v.recircs, np.asarray(want.recircs)[fid])
    np.testing.assert_array_equal(
        v.exit_partition, np.asarray(want.exit_partition)[fid])


@pytest.mark.parametrize("impl", ["fused", "pallas"])
def test_fused_tick_matches_engine_run(tick_setup, impl):
    eng, tr, stream = tick_setup
    want = eng.run(window_packets(tr, P), with_trace=False)
    srv = FlowTableServer(eng, n_buckets=32, bucket_size=4,
                          options=EngineOptions(impl=impl))
    parts = [srv.ingest(b) for b in stream.ticks(97)]
    parts.append(srv.flush())
    _assert_matches_run(StreamVerdicts.concat(parts), want,
                        range(tr.n_flows))
    assert srv.stats.flows_seen == srv.stats.verdicts == tr.n_flows


def test_tick_engines_stats_agree_under_spill_and_timeout(tick_setup):
    """The stats-drift audit bar: a tiny table (constant spill traffic)
    plus an aggressive timeout (eviction sentinels) exercises every
    counter-update path.  The counters agree with the verdicts emitted,
    and every verdict that is not a timeout sentinel equals
    ``Engine.run``'s."""
    eng, tr, stream = tick_setup
    want = eng.run(window_packets(tr, P), with_trace=False)
    srv = FlowTableServer(eng, n_buckets=2, bucket_size=2, timeout=0.005)
    parts = [srv.ingest(b) for b in stream.ticks(131)]
    parts.append(srv.flush())
    v = StreamVerdicts.concat(parts)
    sa = srv.stats
    assert sa.spilled > 0          # the tiny table forced the host path
    assert sa.evicted > 0          # the timeout fired
    assert sa.verdicts == v.n_flows == sa.flows_seen == tr.n_flows
    assert v.n_unterminated >= sa.evicted
    exited = np.asarray(v.exit_partition) >= 0
    assert exited.any()
    _assert_matches_run(StreamVerdicts(
        v.flow_id[exited], v.labels[exited], v.recircs[exited],
        v.exit_partition[exited]), want)
    # a placed key examines one bucket or more, a refused key all 2
    assert sa.insert_probes >= sa.flows_seen - sa.spilled + 2 * sa.spilled


# ---------------------------------------------------------------------------
# transfer and probe counters
# ---------------------------------------------------------------------------
def test_fused_tick_copies_five_verdict_rows_per_column(tick_setup):
    """Each fused tick that serves resident packets copies five (C,)
    int32 verdict rows to the host, C the tick's distinct resident
    flows padded to the power-of-two ladder (floor 64), however large
    the table; nothing else is copied while no flow spills."""
    eng, tr, stream = tick_setup
    srv = FlowTableServer(eng, n_buckets=64, bucket_size=8)
    done = set()
    for b in stream.ticks(97):
        live = np.setdiff1d(np.unique(b.flow_id), list(done))
        C = max(64, 1 << int(live.size - 1).bit_length())
        before, rows = srv.stats.d2h_bytes, srv.stats.tick_rows
        v = srv.ingest(b)
        done.update(map(int, v.flow_id))
        assert srv.stats.tick_rows - rows == C
        assert srv.stats.d2h_bytes - before == 5 * 4 * C
        assert C < srv.table.capacity
    assert srv.stats.spilled == 0
    assert srv.registry.counter("serve_tick_rows_total").value == \
        srv.stats.tick_rows


@pytest.fixture(scope="module")
def probe_runs(tick_setup):
    """The same stream through a 16 x 4 table on each walk backend:
    (stats per backend, the unique flow ids summed over its ticks).
    Buckets overflow into their neighbours but no flow spills: the
    spill walk compiles per batch shape, slow on interpreted Pallas,
    and ``test_tick_engines_stats_agree_under_spill_and_timeout``
    counts the probes of refused keys."""
    eng, tr, stream = tick_setup
    ticks = list(stream.ticks(97))
    stats = {}
    for impl in ("fused", "pallas"):
        srv = FlowTableServer(eng, n_buckets=16, bucket_size=4,
                              options=EngineOptions(impl=impl))
        for b in ticks:
            srv.ingest(b)
        stats[impl] = srv.stats
    n_keys = sum(np.unique(np.asarray(b.flow_id)).size for b in ticks)
    return stats, n_keys


def test_insert_probes_agree_across_engines(probe_runs):
    """The hash probes depend on admission alone: the same stream gives
    the same count on both walk backends, at least one bucket per
    placed flow and more once buckets overflow into their neighbours."""
    stats, _ = probe_runs
    sa, sb = stats["fused"], stats["pallas"]
    assert sa.insert_probes == sb.insert_probes
    assert sa.insert_probes > sa.flows_seen - sa.spilled
    assert sa.as_dict() == sb.as_dict()   # no counter reads the backend


def test_lookup_probes_agree_across_engines(probe_runs):
    """Each tick looks its unique flow ids up once; the buckets those
    lookups examine depend on admission alone, so both walk backends
    count the same, at least one bucket per key and more once keys have
    overflowed their home buckets."""
    stats, n_keys = probe_runs
    sa, sb = stats["fused"], stats["pallas"]
    assert sa.lookup_keys == sb.lookup_keys == n_keys
    assert sa.lookup_probes == sb.lookup_probes > sa.lookup_keys
    assert sa.registry.counter("serve_lookup_probes_total").value == \
        sa.lookup_probes


# ---------------------------------------------------------------------------
# the working-set tick: columns, not table rows, carry the rank scan
# ---------------------------------------------------------------------------
def _late_tail_ticks(tr):
    """Whole flows whose flow_len undercounts their packets: after the
    last counted packet (the verdict) copies of it keep arriving, in
    the same tick and in the next, beside a new flow each tick."""
    for t, i in enumerate(range(0, 24, 2)):
        fid = np.concatenate([
            np.full(int(tr.lengths[i]) + 3, i, np.int64),
            np.full(int(tr.lengths[i + 1]), i + 1, np.int64),
            np.full(2, max(i - 2, 0), np.int64)])
        pkts = np.concatenate([
            tr.packets[i, :int(tr.lengths[i])],
            np.repeat(tr.packets[i, int(tr.lengths[i]) - 1][None], 3, 0),
            tr.packets[i + 1, :int(tr.lengths[i + 1])],
            tr.packets[max(i - 2, 0), :2]])
        yield PacketBatch(fid, tr.lengths[fid].astype(np.int32),
                          pkts.astype(np.float32),
                          1e3 * t + np.arange(fid.size, dtype=np.float64))


def _one_flow_ticks(tr):
    """One whole flow per tick: on a one-slot table each tick's flow
    takes the slot the previous tick's flow freed."""
    for i in range(16):
        fid = np.full(int(tr.lengths[i]), i, np.int64)
        yield PacketBatch(fid, tr.lengths[fid].astype(np.int32),
                          tr.packets[i, :fid.size].astype(np.float32),
                          1e3 * i + np.arange(fid.size, dtype=np.float64))


def _short(tr):
    """The dataset cut to 1..P-1 packets a flow: every flow ends with
    empty windows that drain inside the tick."""
    short = np.minimum(tr.lengths, 1 + np.arange(tr.n_flows) % (P - 1))
    return dataclasses.replace(tr, lengths=short.astype(tr.lengths.dtype))


def _parity_case(name, tr):
    """(dataset the verdicts come from, ticks, table shape)."""
    if name == "whole_flows":          # ranks R = flow length
        return tr, list(_whole_flow_ticks(tr, 16)), dict(n_buckets=64)
    if name == "late_packets":         # the retired guard
        return tr, list(_late_tail_ticks(tr)), dict(n_buckets=2)
    if name == "recycled_slot":
        return tr, list(_one_flow_ticks(tr)), dict(n_buckets=1,
                                                   bucket_size=1)
    sd = _short(tr)                    # "short_flows": the drain
    stream = make_packet_stream(sd, seed=5, profile="steady")
    return sd, list(stream.ticks(61)), dict(n_buckets=64)


@pytest.mark.parametrize("impl", ["fused", "pallas"])
@pytest.mark.parametrize("case", ["whole_flows", "late_packets",
                                  "recycled_slot", "short_flows"])
def test_working_set_tick_parity(tick_setup, case, impl):
    """The tick gives the verdicts of ``Engine.run`` on the rebuilt
    windows, flow for flow, on either walk backend, and a retired row
    holds no packet past its verdict."""
    eng, tr, _ = tick_setup
    ds, ticks, table = _parity_case(case, tr)
    table.setdefault("bucket_size", 4)
    want = eng.run(window_packets(ds, P), with_trace=False)
    srv = FlowTableServer(eng, options=EngineOptions(impl=impl), **table)
    parts = []
    for b in ticks:
        parts.append(srv.ingest(b))
        # a finished flow's row stops where its verdict came: packets
        # past it never fold (the retired guard)
        st = srv._tstate
        done = np.asarray(st.retired) == 1
        np.testing.assert_array_equal(np.asarray(st.pkts_seen)[done],
                                      np.asarray(st.win_hi)[done])
    parts.append(srv.flush())
    served = {int(f) for b in ticks for f in b.flow_id}
    _assert_matches_run(StreamVerdicts.concat(parts), want, served)


def test_recircs_mirror_tracks_device_under_spill_and_timeout(tick_setup):
    """The host ``_recircs`` mirror, written only at each tick's
    columns, equals the device's ``recircs`` at every resident slot
    after every tick, on a tiny table (spill) with an aggressive
    timeout (evictions)."""
    eng, _, stream = tick_setup
    srv = FlowTableServer(eng, n_buckets=2, bucket_size=2, timeout=0.005)
    N = srv.table.capacity
    n_rec = 0
    for b in itertools.islice(stream.ticks(131), 24):
        srv.ingest(b)
        res = srv.table.key >= 0
        dev = np.asarray(srv._tstate.recircs)[:N]
        np.testing.assert_array_equal(srv._recircs[res], dev[res])
        n_rec += int(dev[res].sum())
    assert srv.stats.spilled > 0 and srv.stats.evicted > 0
    assert n_rec > 0                   # resident flows did recirculate
