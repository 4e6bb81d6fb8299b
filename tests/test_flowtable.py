"""Live flow-table serving: the per-packet streaming engine must be
bit-identical to the offline batch walk — incremental folds vs rebuilt
windows (docs/PARITY.md), hash-bucket overflow vs the host spill path,
and mid-stream eviction sentinels all included."""
import functools

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.inference import Engine, EngineOptions, EngineResult
from repro.flows.synthetic import PacketBatch, make_packet_stream
from repro.flows.windows import window_bounds, window_packets
from repro.kernels import ref as kref
from repro.kernels.feature_window import (
    feature_update_finalize_pallas,
    feature_update_pallas,
)
from repro.serve import FlowTableServer, StreamVerdict, StreamVerdicts
from repro.testing.hypothesis_compat import given, settings, strategies as st

P = 3


@pytest.fixture(scope="module")
def serve_setup(trained_pdt):
    pdt, _, tr = trained_pdt
    eng = Engine.from_model(pdt)
    wp = window_packets(tr, P)
    full = eng.run(wp, with_trace=False)
    stream = make_packet_stream(tr, seed=11, profile="steady")
    return eng, tr, wp, full, stream


def _serve_all(srv, stream, tick):
    parts = [srv.ingest(b) for b in stream.ticks(tick)]
    parts.append(srv.flush())
    return StreamVerdicts.concat(parts)


def _assert_verdicts_match(v, full, n_flows):
    assert v.n_flows == n_flows
    assert np.unique(v.flow_id).size == n_flows  # one verdict per flow
    order = np.argsort(v.flow_id)
    np.testing.assert_array_equal(v.labels[order], np.asarray(full.labels))
    np.testing.assert_array_equal(v.recircs[order],
                                  np.asarray(full.recircs))
    np.testing.assert_array_equal(v.exit_partition[order],
                                  np.asarray(full.exit_partition))


# ---------------------------------------------------------------------------
# incremental fold == rebuilt window (the kernel-level parity clause)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_incremental_fold_matches_rebuilt_window(serve_setup, impl):
    """Folding a window one packet at a time must reproduce the
    all-at-once window registers bit for bit — including the padding
    packets, which a correct fold treats as no-ops."""
    eng, tr, wp, _, _ = serve_setup
    dev = eng.dev
    B, _, W, _ = wp.shape
    for w in range(P):
        win = jnp.asarray(wp[:, w])            # (B, W, F)
        sid = jnp.zeros(B, jnp.int32)
        op = dev.slot_op[sid]
        fld = dev.slot_field[sid]
        prd = dev.slot_pred[sid]
        init = dev.slot_init[sid]
        want = kref.feature_window_ref(win, op, fld, prd, init)
        acc, seen = kref.feature_state_init(op)
        for t in range(W):
            if impl == "ref":
                acc, seen = kref.feature_update_ref(
                    win[:, t], op, fld, prd, acc, seen)
            else:
                acc, seen = feature_update_pallas(
                    win[:, t], op, fld, prd, acc, seen)
        got = kref.feature_finalize_ref(acc, seen, op, init)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_fused_update_finalize_matches_composition(serve_setup, impl):
    """The tick-step kernel fuses fold and finalize into one pass; its
    registers AND its carried (acc, seen) must be bit-identical to the
    two-call composition at every packet position — otherwise the tick
    engine would drift from the batch walk's window registers."""
    eng, tr, wp, _, _ = serve_setup
    dev = eng.dev
    B, _, W, _ = wp.shape
    for w in range(P):
        win = jnp.asarray(wp[:, w])
        sid = jnp.zeros(B, jnp.int32)
        op = dev.slot_op[sid]
        fld = dev.slot_field[sid]
        prd = dev.slot_pred[sid]
        init = dev.slot_init[sid]
        acc, seen = kref.feature_state_init(op)
        for t in range(W):
            wa, ws = kref.feature_update_ref(win[:, t], op, fld, prd,
                                             acc, seen)
            want = kref.feature_finalize_ref(wa, ws, op, init)
            if impl == "ref":
                a2, s2, regs = kref.feature_update_finalize_ref(
                    win[:, t], op, fld, prd, init, acc, seen)
            else:
                a2, s2, regs = feature_update_finalize_pallas(
                    win[:, t], op, fld, prd, init, acc, seen)
            np.testing.assert_array_equal(np.asarray(a2), np.asarray(wa))
            np.testing.assert_array_equal(np.asarray(s2), np.asarray(ws))
            np.testing.assert_array_equal(np.asarray(regs),
                                          np.asarray(want))
            acc, seen = a2, s2


# ---------------------------------------------------------------------------
# end-to-end: streamed verdicts == batch engine
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("impl", ["fused", "pallas"])
def test_stream_matches_batch_engine(serve_setup, impl):
    eng, tr, _, full, stream = serve_setup
    srv = FlowTableServer(eng, n_buckets=8, bucket_size=4,
                          options=EngineOptions(impl=impl))
    v = _serve_all(srv, stream, tick=53)
    _assert_verdicts_match(v, full, tr.n_flows)
    assert srv.stats.packets == stream.n_packets
    assert v.n_unterminated == full.n_unterminated


def test_auto_options_resolve_plan(serve_setup):
    eng, tr, _, full, stream = serve_setup
    srv = FlowTableServer(eng, options=EngineOptions(impl="auto"))
    v = _serve_all(srv, stream, tick=200)
    _assert_verdicts_match(v, full, tr.n_flows)
    assert v.plan is not None
    assert v.plan.backend in ("fused", "pallas")


def test_flowtable_rejects_non_walk_backend(serve_setup):
    eng = serve_setup[0]
    with pytest.raises(ValueError, match="walk backend"):
        FlowTableServer(eng, options=EngineOptions(impl="looped"))


# ---------------------------------------------------------------------------
# hash-bucket overflow: spill to host, never drop a flow
# ---------------------------------------------------------------------------
def test_bucket_overflow_spills_without_dropping(serve_setup):
    eng, tr, _, full, stream = serve_setup
    # 4 slots for dozens of concurrent flows: most of the stream must
    # take the spill path, and verdicts must still be bit-identical
    srv = FlowTableServer(eng, n_buckets=2, bucket_size=2)
    v = _serve_all(srv, stream, tick=97)
    assert srv.stats.spilled > 0
    assert srv.stats.peak_resident > srv.table.capacity
    _assert_verdicts_match(v, full, tr.n_flows)


# ---------------------------------------------------------------------------
# eviction before window-complete: -1 sentinels, mid-stream
# ---------------------------------------------------------------------------
def test_flush_mid_window_emits_sentinels(serve_setup):
    eng, tr, _, full, stream = serve_setup
    srv = FlowTableServer(eng, n_buckets=8, bucket_size=4)
    half = stream.slice(0, stream.n_packets // 2)
    v1 = srv.ingest(half)
    v2 = srv.flush()
    v = StreamVerdicts.concat([v1, v2])
    # flushed flows never exited: label and exit_partition both -1
    assert v2.n_flows > 0
    assert (v2.labels == -1).all()
    assert (v2.exit_partition == -1).all()
    assert v2.n_unterminated == v2.n_flows
    # flows that DID complete in the half-stream match the batch run
    done = v1.flow_id[np.asarray(v1.exit_partition) >= 0]
    if done.size:
        full_by_id = {int(i): (int(full.labels[i]), int(full.recircs[i]),
                               int(full.exit_partition[i]))
                      for i in done}
        for j in range(v1.n_flows):
            fid = int(v1.flow_id[j])
            if fid in full_by_id:
                assert (int(v1.labels[j]), int(v1.recircs[j]),
                        int(v1.exit_partition[j])) == full_by_id[fid]
    # every flow of the half-stream got exactly one verdict
    assert np.unique(v.flow_id).size == v.n_flows


def test_timeout_eviction_emits_sentinels(serve_setup):
    eng, tr, _, _, stream = serve_setup
    srv = FlowTableServer(eng, n_buckets=8, bucket_size=4, timeout=1e-12)
    first = stream.slice(0, 64)
    srv.ingest(first)
    # a later tick whose arrivals are far past every resident flow
    last = stream.slice(stream.n_packets - 8, stream.n_packets)
    v = srv.ingest(last)
    assert srv.stats.evicted > 0
    evicted = np.asarray(v.exit_partition) < 0
    assert evicted.any()
    assert (np.asarray(v.labels)[evicted] == -1).all()


def test_late_packets_for_retired_flow_are_dropped(serve_setup):
    eng, tr, _, full, stream = serve_setup
    srv = FlowTableServer(eng, n_buckets=8, bucket_size=4)
    v = _serve_all(srv, stream, tick=111)
    n = v.n_flows
    # replaying the whole stream: every flow is retired, nothing folds
    replay = [srv.ingest(b) for b in stream.ticks(111)]
    replay.append(srv.flush())
    again = StreamVerdicts.concat(replay)
    assert again.n_flows == 0
    assert n == tr.n_flows


# ---------------------------------------------------------------------------
# adversarial tick shapes: deep rank chains, mid-tick hops, slot reuse
# ---------------------------------------------------------------------------
def _flow_batch(tr, sel, t0=0.0, extra_tail=0):
    """One tick delivering each selected flow IN FULL (rank depth = flow
    length), optionally followed by ``extra_tail`` duplicate copies of
    the first flow's last packet — late arrivals past flow_len."""
    sel = list(sel)
    fid = np.concatenate(
        [np.full(int(tr.lengths[i]), i, np.int64) for i in sel])
    pkts = np.concatenate(
        [tr.packets[i, :int(tr.lengths[i])] for i in sel])
    if extra_tail:
        i = sel[0]
        last = tr.packets[i, int(tr.lengths[i]) - 1][None]
        fid = np.concatenate([fid, np.full(extra_tail, i, np.int64)])
        pkts = np.concatenate([pkts, np.repeat(last, extra_tail, axis=0)])
    flen = tr.lengths[fid].astype(np.int32)
    arr = t0 + np.arange(fid.size, dtype=np.float64)
    return PacketBatch(fid, flen, pkts.astype(np.float32), arr)


def _assert_subset_matches(v, full, fids):
    assert sorted(map(int, v.flow_id)) == sorted(map(int, fids))
    for j in range(v.n_flows):
        i = int(v.flow_id[j])
        assert int(v.labels[j]) == int(full.labels[i]), i
        assert int(v.recircs[j]) == int(full.recircs[i]), i
        assert int(v.exit_partition[j]) == int(full.exit_partition[i]), i


@pytest.mark.parametrize("impl", ["fused", "pallas"])
def test_whole_flow_ticks_recycle_slots(serve_setup, impl):
    """Capacity-ONE table fed whole flows: every tick completes its
    resident flow mid-tick (the deepest rank chain possible), frees the
    slot, and the next tick's flow recycles it; the companion flow
    spills to the host each round.  Both paths must match the batch
    walk bit for bit."""
    eng, tr, _, full, _ = serve_setup
    fids = list(range(24))
    srv = FlowTableServer(eng, n_buckets=1, bucket_size=1, rank_floor=1,
                          options=EngineOptions(impl=impl))
    parts = [srv.ingest(_flow_batch(tr, (i, i + 1), t0=1e3 * i))
             for i in range(0, 24, 2)]
    parts.append(srv.flush())
    v = StreamVerdicts.concat(parts)
    assert srv.stats.spilled > 0          # capacity 1: companions spill
    _assert_subset_matches(v, full, fids)


@pytest.mark.parametrize("impl", ["fused", "pallas"])
def test_interleaved_boundary_hops_mid_tick(serve_setup, impl):
    """Round-robin interleave of 16 flows in ONE tick: every window
    boundary, hop, and drain round lands mid-tick, with many flows
    completing in the same rank — the worst case for the in-jit hop
    bookkeeping, on either walk backend."""
    eng, tr, _, full, _ = serve_setup
    sel = list(range(40, 56))
    maxlen = max(int(tr.lengths[i]) for i in sel)
    fid_rows, pkt_rows = [], []
    for j in range(maxlen):
        for i in sel:
            if j < int(tr.lengths[i]):
                fid_rows.append(i)
                pkt_rows.append(tr.packets[i, j])
    fid = np.asarray(fid_rows, np.int64)
    batch = PacketBatch(fid, tr.lengths[fid].astype(np.int32),
                        np.asarray(pkt_rows, np.float32),
                        np.arange(fid.size, dtype=np.float64))
    srv = FlowTableServer(eng, n_buckets=4, bucket_size=4,
                          options=EngineOptions(impl=impl))
    v = StreamVerdicts.concat([srv.ingest(batch), srv.flush()])
    _assert_subset_matches(v, full, sel)


@pytest.mark.parametrize("impl", ["fused", "pallas"])
def test_late_packets_cannot_corrupt_recycled_slot(serve_setup, impl):
    """A flow completes mid-tick, duplicate tail packets of it keep
    arriving in the SAME tick (must not fold into anything), then a new
    flow takes the freed slot next tick while yet more late packets of
    the retired flow arrive — they must not fold into the new tenant."""
    eng, tr, _, full, _ = serve_setup
    a, b = 3, 5
    srv = FlowTableServer(eng, n_buckets=1, bucket_size=1,
                          options=EngineOptions(impl=impl))
    v1 = srv.ingest(_flow_batch(tr, [a], extra_tail=3))
    assert v1.n_flows == 1                # a completed despite the dups
    t2 = _flow_batch(tr, [b], t0=1e6, extra_tail=0)
    late = _flow_batch(tr, [a], t0=2e6).pkts[-2:]
    t2 = PacketBatch(
        np.concatenate([t2.flow_id, np.full(2, a, np.int64)]),
        np.concatenate([t2.flow_len,
                        tr.lengths[[a, a]].astype(np.int32)]),
        np.concatenate([t2.pkts, late]),
        np.arange(t2.flow_id.size + 2, dtype=np.float64) + 1e6)
    v2 = srv.ingest(t2)
    v = StreamVerdicts.concat([v1, v2, srv.flush()])
    _assert_subset_matches(v, full, [a, b])


# ---------------------------------------------------------------------------
# padding-leak property: ticks/capacity/impl must never change verdicts
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=1)
def _property_setup():
    # @given-wrapped tests can't take pytest fixtures (the hypothesis
    # fallback shim erases the signature), so the property builds its
    # own small trained engine once
    from repro.core.partition import train_partitioned_dt
    from repro.flows.synthetic import make_dataset
    from repro.flows.windows import window_features
    ds = make_dataset("d2", n_flows=72, seed=9, max_len=48)
    pdt = train_partitioned_dt(window_features(ds, P), ds.labels,
                               partition_sizes=[2, 2, 2], k=3)
    eng = Engine.from_model(pdt)
    full = eng.run(window_packets(ds, P), with_trace=False)
    return eng, ds, full


@settings(max_examples=3, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_flowtable_padding_never_leaks(seed):
    """Mirror of tests/test_streaming.py's property: rank batches are
    padded to a power-of-two ladder with dummy-row scatters, so any
    padding leak would corrupt a resident flow's registers.  Random
    tick sizes, table capacities, arrival profiles, and backends must
    all reproduce the batch verdicts exactly."""
    eng, tr, full = _property_setup()
    rng = np.random.default_rng(seed)
    profile = ("steady", "bursty")[int(rng.integers(0, 2))]
    stream = make_packet_stream(tr, seed=int(rng.integers(1 << 16)),
                                profile=profile)
    srv = FlowTableServer(
        eng,
        n_buckets=int(rng.integers(1, 9)),
        bucket_size=int(rng.integers(1, 5)),
        options=EngineOptions(
            impl=("fused", "pallas")[int(rng.integers(0, 2))]),
        rank_floor=int(rng.integers(1, 65)),
    )
    v = _serve_all(srv, stream, tick=int(rng.integers(1, 300)))
    _assert_verdicts_match(v, full, tr.n_flows)


# ---------------------------------------------------------------------------
# result-type contract + stream generator
# ---------------------------------------------------------------------------
def test_stream_verdicts_share_engine_result_contract():
    # the unified surface: one field contract across batch and stream
    for name in ("labels", "recircs", "exit_partition", "plan"):
        assert name in EngineResult.__dataclass_fields__
        assert name in StreamVerdicts.__dataclass_fields__
    assert StreamVerdict is StreamVerdicts
    e = StreamVerdicts.empty()
    assert e.n_flows == 0 and e.n_unterminated == 0
    one = StreamVerdicts(np.array([7], np.int64), np.array([2], np.int32),
                         np.array([1], np.int32), np.array([-1], np.int32))
    cat = StreamVerdicts.concat([e, one, one])
    assert cat.n_flows == 2 and cat.n_unterminated == 2
    assert StreamVerdicts.concat([]).n_flows == 0


def test_packet_stream_is_replayable_and_ordered(serve_setup):
    _, tr, _, _, _ = serve_setup
    a = make_packet_stream(tr, seed=5, profile="bursty")
    b = make_packet_stream(tr, seed=5, profile="bursty")
    np.testing.assert_array_equal(a.arrival, b.arrival)
    np.testing.assert_array_equal(a.flow_id, b.flow_id)
    np.testing.assert_array_equal(a.pkts, b.pkts)
    assert (np.diff(a.arrival) >= 0).all()
    # per-flow packet order is preserved under the arrival interleave
    for fid in np.unique(a.flow_id)[:5]:
        rows = a.pkts[a.flow_id == fid]
        lo, hi = window_bounds(int(rows.shape[0]), 1)[0]
        assert (lo, hi) == (0, rows.shape[0])
    ticks = list(a.ticks(37))
    assert all(isinstance(t, PacketBatch) for t in ticks)
    assert sum(t.n_packets for t in ticks) == a.n_packets
    with pytest.raises(ValueError):
        next(a.ticks(0))
