"""``FlowTable``'s arrays as its only index: the bucket probe of
``lookup_batch`` against a plain dict, and ``insert_batch``'s placement
and probe counts against the dict-indexed table it replaced, kept
verbatim below, under random insert / free / lookup churn."""
import numpy as np
import pytest

from repro.serve import FlowTable
from repro.serve.flowtable import _mix64


class _DictTable:
    """The dict-indexed ``FlowTable`` as it was before its arrays became
    the index: the model every placement and lookup is checked against."""

    def __init__(self, n_buckets: int, bucket_size: int):
        self.n_buckets = n_buckets
        self.bucket_size = bucket_size
        self.capacity = n_buckets * bucket_size
        self.key = np.full(self.capacity, -1, np.int64)
        self._slot_of: dict[int, int] = {}

    def lookup_batch(self, keys):
        keys = np.asarray(keys, np.int64)
        get = self._slot_of.get
        return np.fromiter((get(int(k), -1) for k in keys), np.int64,
                           count=keys.size)

    def _insert_at(self, key, b0):
        for probe in range(self.n_buckets):
            b = (b0 + probe) % self.n_buckets
            base = b * self.bucket_size
            free = np.nonzero(
                self.key[base:base + self.bucket_size] == -1)[0]
            if free.size:
                slot = base + int(free[0])
                self.key[slot] = key
                self._slot_of[key] = slot
                return slot, probe + 1
        return -1, self.n_buckets

    def insert_batch(self, keys):
        keys = np.asarray(keys, np.int64)
        homes = _mix64(keys) % np.uint64(self.n_buckets)
        out = np.empty(keys.size, np.int64)
        probes = 0
        for i in range(keys.size):
            out[i], n = self._insert_at(int(keys[i]), int(homes[i]))
            probes += n
        return out, probes

    def free(self, slot):
        key = int(self.key[slot])
        del self._slot_of[key]
        self.key[slot] = -1


def _home(table, key) -> int:
    return int(_mix64(np.int64(key)) % np.uint64(table.n_buckets))


def _assert_overflow_counts(t: FlowTable) -> None:
    """``_over`` recomputed from the resident keys' placements."""
    over = np.zeros(t.n_buckets, np.int64)
    for slot in np.nonzero(t.key >= 0)[0]:
        b0 = _home(t, t.key[slot])
        assert t._home[slot] == b0
        for p in range((slot // t.bucket_size - b0) % t.n_buckets):
            over[(b0 + p) % t.n_buckets] += 1
    np.testing.assert_array_equal(t._over, over)


def _keys_with_home(t, home, n, start=0):
    out, k = [], start
    while len(out) < n:
        if _home(t, k) == home:
            out.append(k)
        k += 1
    return out


# ---------------------------------------------------------------------------
# churn against the dict model
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("shape", [(1, 1), (2, 2), (3, 2), (8, 4), (64, 8)])
def test_churn_matches_the_dict_model(shape, seed):
    """Random rounds of inserts (some refused by a full table), frees and
    lookups of resident, freed and never-seen keys: every slot, probe
    count and lookup equals the dict-indexed table's; the overflow
    counts equal those recomputed from the placements."""
    rng = np.random.default_rng(seed)
    t, ref = FlowTable(*shape), _DictTable(*shape)
    cap = t.capacity
    nxt = 0
    for _ in range(40):
        new = np.arange(nxt, nxt + int(rng.integers(0, cap + 2)),
                        dtype=np.int64) * 7919
        nxt += new.size
        got, ref_got = t.insert_batch(new), ref.insert_batch(new)
        np.testing.assert_array_equal(got[0], ref_got[0])
        assert got[1] == ref_got[1]
        live = np.nonzero(t.key >= 0)[0]
        for slot in rng.permutation(live)[:int(rng.integers(0, live.size + 1))]:
            t.free(int(slot))
            ref.free(int(slot))
        np.testing.assert_array_equal(t.key, ref.key)
        assert t.resident == len(ref._slot_of)
        _assert_overflow_counts(t)
        asked = rng.permutation(np.r_[rng.integers(0, nxt + 5, 3 * cap) * 7919,
                                      t.key[t.key >= 0]]).astype(np.int64)
        slots, probes = t.lookup_batch(asked)
        np.testing.assert_array_equal(slots, ref.lookup_batch(asked))
        assert asked.size <= probes <= asked.size * t.n_buckets
        for k in asked[:4]:
            s = ref._slot_of.get(int(k))
            assert t.lookup(int(k)) == s


# ---------------------------------------------------------------------------
# the cases a probe can get wrong
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("shape", [(1, 1), (2, 2), (4, 2)])
def test_full_table_refuses_and_an_absent_lookup_ends(shape):
    t = FlowTable(*shape)
    slots, probes = t.insert_batch(np.arange(t.capacity, dtype=np.int64))
    assert (slots >= 0).all() and t.resident == t.capacity
    over = t._over.copy()
    slots, probes = t.insert_batch(np.asarray([10**6], np.int64))
    assert slots[0] == -1 and probes == t.n_buckets
    np.testing.assert_array_equal(t._over, over)     # refusal leaves no trace
    assert t.insert(10**6 + 1) is None
    got, probes = t.lookup_batch(np.asarray([10**6, 10**6 + 1], np.int64))
    assert (got == -1).all() and probes <= 2 * t.n_buckets
    got, _ = t.lookup_batch(np.arange(t.capacity, dtype=np.int64))
    assert sorted(got.tolist()) == list(range(t.capacity))


def test_overflowed_key_is_found_after_a_home_slot_is_freed():
    """Three keys of one home bucket in a table of 2-slot buckets: the
    third overflows; freeing a home slot must not hide it (a probe that
    stopped at a free slot would miss it), and freeing it clears the
    home bucket's overflow count, so an absent key stops at home."""
    t = FlowTable(4, 2)
    home = 1
    k1, k2, k3, k4, absent = _keys_with_home(t, home, 5)
    s1, s2, s3 = (t.insert(k) for k in (k1, k2, k3))
    assert s1 // 2 == s2 // 2 == home and s3 // 2 == home + 1
    assert t._over[home] == 1
    t.free(s1)
    assert t.lookup(k3) == s3
    got, probes = t.lookup_batch(np.asarray([k3, k1], np.int64))
    assert got.tolist() == [s3, -1] and probes == 2 + 2
    assert t.insert(k4) == s1                        # first free slot
    t.free(s3)
    assert t._over.tolist() == [0, 0, 0, 0]
    got, probes = t.lookup_batch(np.asarray([absent, k3], np.int64))
    assert got.tolist() == [-1, -1] and probes == 2
    _assert_overflow_counts(t)


def test_probe_wraps_past_the_last_bucket():
    t = FlowTable(3, 1)
    k1, k2 = _keys_with_home(t, 2, 2)
    assert t.insert(k1) == 2 and t.insert(k2) == 0
    assert t._over.tolist() == [0, 0, 1]
    assert t.lookup(k2) == 0
    t.free(2)
    assert t.lookup(k2) == 0 and t._over.tolist() == [0, 0, 1]
    t.free(0)
    assert t._over.tolist() == [0, 0, 0] and t.resident == 0


def test_home_keys_take_one_probe_each_and_free_slots_refuse_a_free():
    t = FlowTable(16, 8)
    keys = np.arange(40, dtype=np.int64)
    slots, probes = t.insert_batch(keys)
    assert probes == keys.size                       # no bucket filled up
    got, probes = t.lookup_batch(keys)
    np.testing.assert_array_equal(got, slots)
    assert probes == keys.size
    t.free(int(slots[0]))
    with pytest.raises(KeyError):
        t.free(int(slots[0]))
    assert t.resident == keys.size - 1
