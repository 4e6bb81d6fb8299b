"""The chip benchmark's traffic: template flows and the cyclic re-keyed
replay."""
import numpy as np
import pytest

from benchmarks.chip import traffic


def _sched(seed=2**31 + 17, pool=48, concurrency=96.0):
    rng = np.random.default_rng(np.random.SeedSequence([1, seed]))
    fl = traffic.make_flows("d2", pool, rng)
    return traffic.Schedule(fl, concurrency, rng)


def test_flows_follow_the_generator_contract():
    rng = np.random.default_rng(5)
    fl = traffic.make_flows("d1", 400, rng)
    assert fl.lengths.min() >= 12 and fl.lengths.max() <= 192
    assert 30 <= np.median(fl.lengths) <= 50
    assert set(np.unique(fl.labels)) <= set(range(19))
    first = fl.pkts[:, 0]
    assert (first[:, traffic.PKT_IAT] == 0).all()
    assert ((first[:, traffic.PKT_FLAGS].astype(int) & traffic.FLAG_SYN)
            > 0).all()
    for i in range(20):
        L = fl.lengths[i]
        assert (fl.pkts[i, :L, traffic.PKT_VALID] == 1).all()
        assert (fl.pkts[i, L:] == 0).all()
        assert (np.diff(fl.pkts[i, :L, traffic.PKT_TS]) >= 0).all()


def test_same_seed_same_traffic():
    a, b = _sched(), _sched()
    x, y = a.batch(1000, 9000), b.batch(1000, 9000)
    for u, v in zip(x, y):
        np.testing.assert_array_equal(u, v)
    c = _sched(seed=2**31 + 18).batch(1000, 9000)
    assert not np.array_equal(x.flow_id, c.flow_id)


def test_replay_keeps_per_flow_order_and_fresh_keys():
    s = _sched()
    n = s.ramp_pkts + 5 * s.cycle_pkts
    b = s.batch(0, n)
    order = np.argsort(b.flow_id, kind="stable")
    fid, j = b.flow_id[order], b.pkt_index[order]
    start = np.r_[True, fid[1:] != fid[:-1]]
    grp = np.cumsum(start) - 1
    # within each instance the packets come in their flow's order,
    # starting at 0, each exactly once
    rank = np.arange(fid.size) - np.nonzero(start)[0][grp]
    np.testing.assert_array_equal(j, rank)
    # each instance is one template's flow under a key used once
    tm = traffic.template_of(fid, s.M)
    np.testing.assert_array_equal(
        b.pkts[order], s.flows.pkts[tm, j])
    np.testing.assert_array_equal(b.flow_len[order], s.flows.lengths[tm])
    cycles = fid // s.M
    per_tmpl = {}
    for t, c in zip(tm[start], cycles[start]):
        per_tmpl.setdefault(int(t), []).append(int(c))
    assert all(len(set(c)) == len(c) for c in per_tmpl.values())
    assert max(len(c) for c in per_tmpl.values()) >= 5
    # arrivals never go back
    assert (np.diff(b.arrival) >= 0).all()


def test_batches_concatenate():
    s = _sched()
    a, m, e = 7, s.ramp_pkts + 123, s.ramp_pkts + 3 * s.cycle_pkts + 5
    whole = s.batch(a, e)
    parts = [s.batch(a, m), s.batch(m, e)]
    for k, u in enumerate(whole):
        np.testing.assert_array_equal(u, np.concatenate([p[k] for p in parts]))


def test_steady_concurrency():
    s = _sched(pool=64, concurrency=256.0)
    lo = s.ramp_pkts + 2 * s.cycle_pkts
    b = s.batch(0, lo + s.cycle_pkts)
    # flows in flight at stream position p: started before p, last
    # packet at or after p
    first = {}
    last = {}
    for i, f in enumerate(b.flow_id):
        first.setdefault(int(f), i)
        last[int(f)] = i
    f0 = np.asarray(list(first.values()))
    f1 = np.asarray([last[k] for k in first])
    for p in (lo, lo + s.cycle_pkts // 2):
        live = int(((f0 <= p) & (f1 >= p)).sum())
        assert 0.8 * 256 <= live <= 1.2 * 256


@pytest.mark.parametrize("tick", [64, 300, 2048])
def test_max_rank_bounds_every_steady_tick(tick):
    s = _sched()
    bound = s.max_rank(tick)
    a = s.ramp_pkts
    for lo in range(a, a + 3 * s.cycle_pkts, tick):
        f = s.batch(lo, lo + tick).flow_id
        assert np.unique(f, return_counts=True)[1].max() <= bound
