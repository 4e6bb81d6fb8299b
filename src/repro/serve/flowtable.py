"""Device-resident flow table: per-packet streaming inference.

The paper's data plane keeps per-flow feature registers in a fixed
register pool, updates them on EVERY packet, and runs the active
subtree when a window boundary passes (paper §3.1, Fig. 4).  The batch
engine (``core.inference``) scores complete flow windows after the
fact; this module is the live analogue — the ROADMAP's "millions of
users, heavy traffic" direction:

  * a **hash-indexed slot table** (``FlowTable``) admits flows into a
    fixed pool of ``n_buckets * bucket_size`` slots (bucketed hashing
    with linear bucket probing — the register-pool analogue of
    ``kernels.dispatch``'s capacity blocks: a static capacity bound
    with data-dependent routing).  When every probe fails the flow
    falls back to a host-side spill store instead of being dropped.
    Admission is vectorized: one NumPy group-by over the tick's flow
    ids, one ``lookup_batch`` (a bucket probe of the slot-key array)
    and one ``insert_batch`` over the tick's unique flows — no
    per-packet python loop;
  * the **tick engine** (``kernels.tick_step``) holds ALL per-flow
    serving state on device — window registers and the walk metadata
    (``sid``, partition, window bounds, packets seen, recircs, retired
    bit) — and processes one whole tick in ONE jitted dispatch: a
    ``lax.scan`` over packet ranks, each rank a fused
    fold→finalize→traverse (window-complete slots hop through
    ``core.inference._hop_update`` in the same dispatch that folded
    them), with empty trailing windows drained by an in-jit bounded
    ``while_loop``, all on the tick's own rows gathered once and
    written back once.  Verdicts come back per column in one bulk
    ``device_get`` per tick;
  * **timeout eviction** emits mid-stream verdicts for idle flows with
    the ``-1`` sentinel convention (labels / exit_partition), keeping
    the accumulated recirculation count.

``FlowTableServer.ingest(packets) -> StreamVerdicts`` is the entry
point; packets arrive as arrival-ordered ticks (see
``flows.synthetic.make_packet_stream``).  Within a tick, packets are
processed in per-slot "ranks" (the r-th packet of each flow), so every
device scatter addresses each slot at most once and per-flow arrival
order — the reduction order the parity contract pins — is preserved.
Rank batches are padded to a power-of-two capacity ladder (a dummy
table row absorbs the padding) so jit compiles a handful of shapes,
not one per tick.  ``ServerStats.dispatches`` counts jitted device
calls: a tick issues at most 2 (admission scatter + tick step)
regardless of rank count or drain rounds — the deterministic perf bar
``tests/test_tick_engine.py`` pins.

Execution knobs come from :class:`repro.core.inference.EngineOptions`:
``impl`` picks the fold/traverse kernels (``fused`` = dense jnp,
``pallas`` = the Pallas scatter-update + SID-dispatched traverse;
``auto``/``tuned`` resolve a ``repro.tuning.Plan`` for the table
shape), ``block_b`` the Pallas block size.  Every route is
bit-identical to ``Engine.run`` on the offline windows — the flow
table can only change *when* a verdict is computed, never its value.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.features import PKT_IAT, PKT_NFIELDS
from repro.core.inference import Engine, EngineOptions
from repro.flows.windows import window_bounds
from repro.kernels import tick_step as _tick
from repro.kernels.dt_traverse import BLOCK_B
from repro.obs import MetricRegistry, exp_edges, span

#: Histogram bucket edges (docs/OBSERVABILITY.md catalogues the
#: metrics).  TTD is measured in STREAM time — the packet arrival
#: clock of the replayed ``PacketStream`` — so two replays of the same
#: stream land every verdict in the same bucket, deterministically.
TTD_EDGES = tuple(exp_edges(1e-3, 1e4, 15))
RECIRC_EDGES = (0.5, 1.5, 2.5, 4.5, 8.5, 16.5, 32.5)
WINDOW_EDGES = (1.5, 2.5, 3.5, 4.5, 6.5, 8.5, 12.5, 16.5)


# ---------------------------------------------------------------------------
# results — same field contract as core.inference.EngineResult
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class StreamVerdicts:
    """Verdicts emitted by one ``ingest``/``flush`` call.

    Field contract matches :class:`repro.core.inference.EngineResult`
    (``labels`` / ``recircs`` / ``exit_partition`` int32 with ``-1``
    sentinels, ``plan``, ``n_unterminated``) plus ``flow_id`` — stream
    verdicts arrive in completion order, not batch order, so each row
    names its flow.
    """
    flow_id: np.ndarray          # (n,) int64 flow key per verdict
    labels: np.ndarray           # (n,) int32; -1 = never took an exit action
    recircs: np.ndarray          # (n,) int32 partition transitions
    exit_partition: np.ndarray   # (n,) int32; -1 sentinel as above
    plan: "object | None" = None  # repro.tuning.Plan when routing resolved one

    @property
    def n_flows(self) -> int:
        return int(self.flow_id.shape[0])

    @property
    def n_unterminated(self) -> int:
        """Flows evicted or flushed without an exit action (-1 rows)."""
        return int(np.count_nonzero(np.asarray(self.exit_partition) < 0))

    @classmethod
    def empty(cls, plan=None) -> "StreamVerdicts":
        return cls(np.empty(0, np.int64), np.empty(0, np.int32),
                   np.empty(0, np.int32), np.empty(0, np.int32), plan=plan)

    @classmethod
    def concat(cls, parts) -> "StreamVerdicts":
        """Concatenate per-tick verdicts (keeps the first non-None plan)."""
        parts = list(parts)
        if not parts:
            return cls.empty()
        plan = next((p.plan for p in parts if p.plan is not None), None)
        return cls(
            np.concatenate([p.flow_id for p in parts]),
            np.concatenate([p.labels for p in parts]),
            np.concatenate([p.recircs for p in parts]),
            np.concatenate([p.exit_partition for p in parts]),
            plan=plan)


#: Singular alias — the per-flow row type and the batch share one shape.
StreamVerdict = StreamVerdicts


class _VerdictAccum:
    """Batched verdict builder: array chunks in, one pre-sized copy out.

    Callers append whole arrays per event batch (tick completions,
    evictions, spill runs) rather than per flow; ``build`` allocates the
    final arrays once from the accumulated count.
    """

    def __init__(self):
        self._chunks: list[tuple] = []
        self.n = 0

    def add(self, fid, label, rec, exitp, first_ts: float = np.inf) -> None:
        self.add_batch(np.asarray([fid], np.int64),
                       np.asarray([label], np.int32),
                       np.asarray([rec], np.int32),
                       np.asarray([exitp], np.int32),
                       np.asarray([first_ts], np.float64))

    def add_batch(self, fids, labels, recs, exitps, first_ts=None) -> None:
        fids = np.asarray(fids, np.int64)
        if not fids.size:
            return
        if first_ts is None:
            first_ts = np.full(fids.size, np.inf, np.float64)
        self._chunks.append((fids, np.asarray(labels, np.int32),
                             np.asarray(recs, np.int32),
                             np.asarray(exitps, np.int32),
                             np.asarray(first_ts, np.float64)))
        self.n += int(fids.size)

    def first_ts(self) -> np.ndarray:
        """First-packet arrival per accumulated verdict (TTD input)."""
        if not self._chunks:
            return np.empty(0, np.float64)
        return np.concatenate([c[4] for c in self._chunks])

    def build(self, plan) -> StreamVerdicts:
        fid = np.empty(self.n, np.int64)
        lab = np.empty(self.n, np.int32)
        rec = np.empty(self.n, np.int32)
        exp = np.empty(self.n, np.int32)
        at = 0
        for f, l, r, e, _ in self._chunks:
            fid[at:at + f.size] = f
            lab[at:at + f.size] = l
            rec[at:at + f.size] = r
            exp[at:at + f.size] = e
            at += f.size
        return StreamVerdicts(fid, lab, rec, exp, plan=plan)


# ---------------------------------------------------------------------------
# host hash index (bucketed, linear bucket probing, never drops)
# ---------------------------------------------------------------------------
def _mix64(x: np.ndarray) -> np.ndarray:
    """splitmix64 finaliser — cheap, well-mixed bucket hashing."""
    x = np.asarray(x).astype(np.uint64)
    with np.errstate(over="ignore"):
        x = x + np.uint64(0x9E3779B97F4A7C15)
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        return x ^ (x >> np.uint64(31))


class FlowTable:
    """Fixed-capacity hash index over the device slot array.

    ``capacity = n_buckets * bucket_size`` slots; a flow key hashes to
    a home bucket and takes the first free slot there, probing
    subsequent buckets (wrapping) on overflow — the data-plane analogue
    is a multi-way register hash table.  ``insert`` returns ``None``
    only when the WHOLE table is full; the server then spills to the
    host instead of dropping the flow.

    The arrays are the whole index: ``key`` (slot -> key, ``-1`` free;
    keys are non-negative), ``_home`` (slot -> its key's home bucket)
    and ``_over`` (bucket -> resident keys whose insertion probed past
    it).  A lookup walks from the home bucket and stops at the key or
    at a bucket with ``_over == 0``: a free slot proves nothing, since
    the key may have overflowed before that slot was freed.  The batch
    forms serve one tick's UNIQUE flows in a single call —
    :meth:`lookup_batch` probes every key's bucket row at once, one
    round per bucket step; :meth:`insert_batch` stays sequential
    because each insert's placement depends on the previous one's
    occupancy.
    """

    def __init__(self, n_buckets: int, bucket_size: int):
        if n_buckets <= 0 or bucket_size <= 0:
            raise ValueError("n_buckets and bucket_size must be positive")
        self.n_buckets = n_buckets
        self.bucket_size = bucket_size
        self.capacity = n_buckets * bucket_size
        self.key = np.full(self.capacity, -1, np.int64)   # -1 = free slot
        self._home = np.zeros(self.capacity, np.int32)
        self._over = np.zeros(n_buckets, np.int32)
        self.resident = 0

    def _homes(self, keys: np.ndarray) -> np.ndarray:
        return (_mix64(keys) % np.uint64(self.n_buckets)).astype(np.int64)

    def lookup(self, key: int) -> int | None:
        slots, _ = self.lookup_batch(np.asarray([key], np.int64))
        return None if slots[0] < 0 else int(slots[0])

    def lookup_batch(self, keys: np.ndarray) -> tuple[np.ndarray, int]:
        """(slot per key, ``-1`` where absent; the buckets examined).

        Each round compares every unresolved key with its current
        bucket's row; a key that misses moves to the next bucket only
        if that row was overflowed by some resident key, so absent keys
        mostly stop at home and no key examines more than
        ``n_buckets`` buckets.
        """
        keys = np.asarray(keys, np.int64)
        out = np.full(keys.size, -1, np.int64)
        rows = self.key.reshape(self.n_buckets, self.bucket_size)
        idx = np.arange(keys.size)
        b = self._homes(keys)
        probes = 0
        for _ in range(self.n_buckets):
            if not idx.size:
                break
            probes += idx.size
            # keys are unique in the table: at most one match per row
            hit_row, col = np.divmod(np.flatnonzero(
                np.take(rows, b, axis=0) == keys[:, None]),
                self.bucket_size)
            out[idx[hit_row]] = b[hit_row] * self.bucket_size + col
            go = np.take(self._over, b) > 0
            go[hit_row] = False
            idx, keys, b = idx[go], keys[go], (b[go] + 1) % self.n_buckets
        return out, probes

    def _insert_at(self, key: int, b0: int) -> tuple[int, int]:
        """(slot or ``-1``, buckets examined) for one key; each full
        bucket it passes counts it in ``_over``."""
        for probe in range(self.n_buckets):
            b = (b0 + probe) % self.n_buckets
            base = b * self.bucket_size
            row = self.key[base:base + self.bucket_size].tolist()
            if -1 in row:
                slot = base + row.index(-1)
                self.key[slot] = key
                self._home[slot] = b0
                self.resident += 1
                return slot, probe + 1
            self._over[b] += 1
        self._over -= 1                 # refused: it passed every bucket
        return -1, self.n_buckets

    def insert(self, key: int) -> int | None:
        slot, _ = self._insert_at(int(key), int(self._homes(key)))
        return None if slot < 0 else slot

    def insert_batch(self, keys: np.ndarray) -> tuple[np.ndarray, int]:
        """Insert keys in order: (slot per key, ``-1`` where full; the
        buckets examined, one per bucket visited and ``n_buckets`` for
        a key the full table refused)."""
        keys = np.asarray(keys, np.int64)
        out = np.empty(keys.size, np.int64)
        probes = 0
        for i, (k, b0) in enumerate(zip(keys.tolist(),
                                        self._homes(keys).tolist())):
            out[i], n = self._insert_at(k, b0)
            probes += n
        return out, probes

    def free(self, slot: int) -> None:
        if self.key.item(slot) < 0:
            raise KeyError(f"slot {slot} is free")
        b0 = self._home.item(slot)
        for p in range((slot // self.bucket_size - b0) % self.n_buckets):
            self._over[(b0 + p) % self.n_buckets] -= 1
        self.key[slot] = -1
        self.resident -= 1


@dataclasses.dataclass
class _SpillFlow:
    """Host fallback for flows the hash table could not place.

    Packets are buffered and the completed flow runs through the batch
    engine's full-window walk — bit-identical verdicts (the parity
    contract makes incremental vs rebuilt windows indistinguishable),
    just computed late.  A spilled flow evicted before completion never
    ran a hop, so it reports zero recirculations with its sentinels.
    """
    length: int
    rows: list = dataclasses.field(default_factory=list)
    last_ts: float = -np.inf
    first_ts: float = np.inf


def _counter_stat(metric: str, doc: str) -> property:
    """A ServerStats field backed by a registry counter.

    The setter only accepts the ``stats.field += n`` idiom (counters
    are monotonic), which is the only way the server writes them.
    """
    def _get(self):
        return self.registry.counter(metric, doc).value

    def _set(self, value):
        c = self.registry.counter(metric, doc)
        c.inc(int(value) - c.value)

    return property(_get, _set, doc=doc)


class ServerStats:
    """Live integer counters for one server — a thin view.

    Since the obs PR the numbers live in the server's
    :class:`repro.obs.MetricRegistry` (``serve_*`` metrics); this
    class keeps the attribute API (``srv.stats.dispatches`` etc.) as
    properties over the registry, so stats appear in Prometheus/JSONL
    exposition for free.  ``ServerStats()`` with no argument gets a
    private registry.
    """

    FIELDS = ("packets", "flows_seen", "verdicts", "spilled", "evicted",
              "peak_resident", "ticks", "dispatches", "d2h_bytes",
              "insert_probes", "lookup_probes", "lookup_keys", "tick_rows")

    def __init__(self, registry: MetricRegistry | None = None):
        self.registry = registry if registry is not None else MetricRegistry()

    packets = _counter_stat(
        "serve_packets_total", "packets ingested (resident + spilled)")
    flows_seen = _counter_stat(
        "serve_flows_total", "distinct flows admitted or spilled")
    verdicts = _counter_stat(
        "serve_verdicts_total", "verdicts emitted (incl. sentinels)")
    spilled = _counter_stat(
        "serve_spilled_total", "flows that fell back to the host store")
    evicted = _counter_stat(
        "serve_evicted_total", "timeout evictions (mid-stream sentinels)")
    ticks = _counter_stat(
        "serve_ticks_total", "ingest calls served")
    dispatches = _counter_stat(
        "serve_dispatches_total", "jitted device calls issued (not syncs)")
    d2h_bytes = _counter_stat(
        "serve_d2h_bytes_total", "bytes copied device -> host")
    insert_probes = _counter_stat(
        "serve_insert_probes_total", "hash buckets examined by inserts")
    lookup_probes = _counter_stat(
        "serve_lookup_probes_total", "hash buckets examined by lookups")
    lookup_keys = _counter_stat(
        "serve_lookup_keys_total", "flow keys looked up in the table")
    tick_rows = _counter_stat(
        "serve_tick_rows_total",
        "table rows the tick gathered and wrote back")

    @property
    def peak_resident(self):
        """Max concurrent flows (slots + spill)."""
        return int(self.registry.gauge("serve_peak_resident").value)

    @peak_resident.setter
    def peak_resident(self, value):
        self.registry.gauge(
            "serve_peak_resident",
            "max concurrent flows (slots + spill)").set(value)

    def as_dict(self) -> dict:
        return {f: getattr(self, f) for f in self.FIELDS}

    def __repr__(self) -> str:
        inner = ", ".join(f"{k}={v}" for k, v in self.as_dict().items())
        return f"ServerStats({inner})"


# ---------------------------------------------------------------------------
# execution resolution
# ---------------------------------------------------------------------------
def _pow2_cap(n: int, floor: int) -> int:
    """Smallest power-of-two >= n (>= floor) — the tick's rank/column
    capacity ladder, so jit sees a handful of shapes per table."""
    cap = max(int(floor), 1)
    while cap < n:
        cap *= 2
    return cap


def _resolve_exec(engine: Engine, opt: EngineOptions, capacity: int):
    """EngineOptions -> (pallas?, block_b, plan) for the serving steps.

    ``auto``/``tuned`` resolve a walk-backend ``Plan`` for the table's
    shape through ``repro.tuning`` (no probe windows exist yet, so
    ``tuned`` degrades to the cost model); only the plan's backend and
    ``block_b`` apply — per-hop batches are already survivor-compacted
    by construction, so the compaction knob is inert here.
    """
    plan = opt.plan
    impl = opt.impl or engine.impl
    if plan is None and impl in ("auto", "tuned"):
        from repro.tuning import ShapeInfo, get_plan
        shape = ShapeInfo.from_engine(engine, None, B=capacity, W=1)
        plan = get_plan(engine, None, impl=impl, shape=shape,
                        backends=("fused", "pallas"), compact=False)
    if plan is not None:
        if plan.backend not in ("fused", "pallas"):
            raise ValueError(
                "flow-table serving requires a walk backend (fused or "
                f"pallas); plan backend {plan.backend!r} syncs per hop")
        return plan.backend == "pallas", plan.block_b, plan
    if impl == "ref":
        impl = "fused"
    if impl not in ("fused", "pallas"):
        raise ValueError(
            "flow-table serving requires a walk backend (fused or "
            f"pallas); got impl={impl!r}")
    return impl == "pallas", opt.block_b or BLOCK_B, None


# ---------------------------------------------------------------------------
# the server
# ---------------------------------------------------------------------------
class FlowTableServer:
    """Per-packet streaming inference behind a resident flow table.

    ``ingest`` consumes arrival-ordered packet ticks
    (``flows.synthetic.PacketBatch``) and returns the
    :class:`StreamVerdicts` that completed during the tick; ``flush``
    evicts everything still resident (``-1`` sentinels for flows whose
    stream ended mid-window).  With ``timeout`` set, flows idle longer
    than ``timeout`` seconds of stream time are evicted at tick
    boundaries the same way.

    Each tick runs as one jitted tick step for the whole rank loop and
    hop drain (``kernels.tick_step``).  ``tick_engine`` accepts only
    ``"fused"``, that engine's name; any other value raises.

    Each flow key is served exactly once: after its verdict (exit,
    flush, or timeout) the key is retired and late packets for it are
    dropped.  The retired set grows with the number of completed flows;
    callers running unbounded streams should recreate the server
    per epoch.
    """

    def __init__(self, engine: Engine, *, n_buckets: int = 64,
                 bucket_size: int = 8, timeout: float | None = None,
                 options: EngineOptions | None = None,
                 rank_floor: int = 64, tick_engine: str = "fused",
                 registry: MetricRegistry | None = None):
        self.engine = engine
        self.options = options or EngineOptions()
        self.timeout = timeout
        self.table = FlowTable(n_buckets, bucket_size)
        self.P = engine.tables.n_partitions
        self.S = engine.ret.n_subtrees
        self._rank_floor = int(rank_floor)
        self._pallas, self._block_b, self._plan = _resolve_exec(
            engine, self.options, self.table.capacity)
        if tick_engine != "fused":
            raise ValueError(f"unknown tick_engine {tick_engine!r}; "
                             "the one tick engine is 'fused'")
        # spilled flows run the batch walk; pin the same backend family
        self._spill_options = EngineOptions(
            impl="pallas" if self._pallas else "fused",
            block_b=self._block_b if self._pallas else None)

        N = self.table.capacity
        self._dummy = N                       # padding scatters land here
        # each server gets a private registry unless the caller shares
        # one; ServerStats is a view over it (serve_* counters/gauge)
        self.registry = registry if registry is not None else MetricRegistry()
        self.stats = ServerStats(self.registry)
        self._m_ttd = self.registry.histogram(
            "serve_ttd_seconds",
            "stream-time packet-arrival -> verdict latency (TTD)",
            edges=TTD_EDGES)
        self._m_recirc_hist = self.registry.histogram(
            "serve_recircs_per_flow",
            "recirculations accumulated per emitted verdict",
            edges=RECIRC_EDGES)
        self._m_windows = self.registry.histogram(
            "serve_windows_per_verdict",
            "partition windows visited per verdict (recircs + 1)",
            edges=WINDOW_EDGES)
        self._m_recircs = self.registry.counter(
            "serve_recircs_total",
            "recirculations summed over emitted verdicts")
        self._m_overhead = self.registry.gauge(
            "serve_recirc_overhead",
            "recirculations per ingested packet (paper bar: < 0.0005)")
        self._m_resident = self.registry.gauge(
            "serve_resident_flows",
            "concurrent flows currently held (slots + host spill)")
        self._now = -np.inf                   # stream clock: max arrival seen
        self._first_ts = np.full(N, np.inf, np.float64)
        self._last_ts = np.full(N, -np.inf, np.float64)
        self._recircs = np.zeros(N, np.int32)
        self._spill: dict[int, _SpillFlow] = {}
        self._retired: set[int] = set()
        # everything else lives on device (kernels.tick_step);
        # _recircs is the host mirror, refreshed at each tick's columns
        # from its bulk verdict fetch (flush/timeout sentinels read it);
        # no other row changes on the device
        self._tstate = _tick.init_tick_state(engine.dev, N + 1, self.P)

    # -- admission ------------------------------------------------------
    @property
    def resident_flows(self) -> int:
        """Concurrent flows currently held (slots + host spill)."""
        return self.table.resident + len(self._spill)

    def _evict(self, slot: int) -> None:
        self._retired.add(int(self.table.key[slot]))
        self.table.free(slot)

    def _route_tick(self, fid: np.ndarray, flen: np.ndarray) -> np.ndarray:
        """Vectorized admission: one group-by over the tick's flow ids.

        Returns a per-packet routing code: a slot index (``>= 0``),
        ``-2`` for the host spill store, ``-1`` for retired-flow drops.
        Unique flows are looked up / inserted in one batch call each;
        new flows insert in first-packet order — the exact occupancy
        evolution of the old per-packet loop, since within a tick every
        lookup of an already-inserted flow hits and order cannot matter
        for hits.  Admitted slots are re-initialised in one batch
        (``_admit_batch``); ``flows_seen`` counts once from the masks.
        """
        with span("tick/admit/lookup"):
            uniq, first_idx, inv = np.unique(fid, return_index=True,
                                             return_inverse=True)
            code, probes = self.table.lookup_batch(uniq)
            self.stats.lookup_keys += int(uniq.size)
            self.stats.lookup_probes += probes
            miss = np.nonzero(code < 0)[0]
        with span("tick/admit/insert"):
            admit = None
            if miss.size:
                keys = uniq[miss]
                retired = np.fromiter(
                    (int(k) in self._retired for k in keys),
                    np.bool_, count=keys.size)
                spilled = np.fromiter(
                    (int(k) in self._spill for k in keys),
                    np.bool_, count=keys.size)
                code[miss[retired]] = -1
                code[miss[spilled]] = -2
                new = miss[~retired & ~spilled]
                if new.size:
                    new = new[np.argsort(first_idx[new], kind="stable")]
                    lens = flen[first_idx[new]]
                    slots, probes = self.table.insert_batch(uniq[new])
                    ok = slots >= 0
                    code[new] = np.where(ok, slots, -2)
                    for j in np.nonzero(~ok)[0]:   # table full: host spill
                        self._spill[int(uniq[new[j]])] = _SpillFlow(
                            length=max(int(lens[j]), 1))
                    self.stats.spilled += int(np.count_nonzero(~ok))
                    self.stats.flows_seen += int(new.size)
                    self.stats.insert_probes += probes
                    if ok.any():
                        admit = slots[ok], lens[ok]
            routed = code[inv]
        if admit is not None:
            with span("tick/admit/rows"):
                self._admit_batch(*admit)
        return routed

    def _admit_batch(self, slots: np.ndarray, lengths: np.ndarray) -> None:
        """Initialise newly admitted slots (recycled slots carry the
        previous tenant's state/SID) — one device call per tick."""
        slots = np.asarray(slots, np.int64)
        lengths = np.maximum(np.asarray(lengths, np.int64), 1)
        self._last_ts[slots] = -np.inf
        self._first_ts[slots] = np.inf        # new tenant: fresh TTD clock
        cap, padded = self._pad_slots(slots)
        plen = np.ones(cap, np.int32)
        plen[:slots.size] = lengths
        self._tstate = _tick.admit_rows(
            self._tstate, jnp.asarray(padded), jnp.asarray(plen),
            self.engine.dev)
        self.stats.dispatches += 1

    # -- ingest ---------------------------------------------------------
    def ingest(self, batch) -> StreamVerdicts:
        """Fold one tick of packet arrivals; return completed verdicts.

        Every host statement of the call lies under exactly one leaf
        span, and the children of a span run one
        after another (docs/OBSERVABILITY.md draws the tree):
        ``tick/stamp`` opens twice, before admission (the batch as
        arrays, counters, stream clock) and after it (spill rows,
        arrival stamps).
        """
        with span("tick/ingest", tick=self.stats.ticks + 1):
            with span("tick/stamp"):
                fid = np.asarray(batch.flow_id, np.int64)
                flen = np.asarray(batch.flow_len, np.int64)
                pk = np.asarray(batch.pkts, np.float32)
                arr = np.asarray(batch.arrival, np.float64)
                n = int(fid.shape[0])
                self.stats.packets += n
                self.stats.ticks += 1
                if n:
                    self._now = max(self._now, float(arr.max()))
                out = _VerdictAccum()

            # route every packet: resident slot, spill store, or retired-drop
            with span("tick/admit"):
                slot_pk = (self._route_tick(fid, flen) if n
                           else np.empty(0, np.int64))

            with span("tick/stamp"):
                self.stats.peak_resident = max(self.stats.peak_resident,
                                               self.resident_flows)
                spill_rows = np.nonzero(slot_pk == -2)[0]
                for i in spill_rows:
                    f = self._spill[int(fid[i])]
                    f.rows.append(pk[i])
                    ts = float(arr[i])
                    f.last_ts = max(f.last_ts, ts)
                    f.first_ts = min(f.first_ts, ts)
                res_rows = np.nonzero(slot_pk >= 0)[0]
                if res_rows.size:
                    slots = slot_pk[res_rows]
                    np.minimum.at(self._first_ts, slots, arr[res_rows])
                    np.maximum.at(self._last_ts, slots, arr[res_rows])
                    pkts = pk[res_rows]

            if res_rows.size:
                self._process_resident_fused(slots, pkts, out)
            with span("tick/spill"):
                self._run_spilled_complete(out)
            if self.timeout is not None and n:
                with span("tick/timeout"):
                    self._evict_timeouts(float(arr.max()), out)
            with span("tick/finish"):
                self.stats.verdicts += out.n
                return self._finish(out)

    def flush(self) -> StreamVerdicts:
        """End of stream: evict every resident flow with sentinels."""
        out = _VerdictAccum()
        with span("tick/spill"):
            self._run_spilled_complete(out)
        live = np.nonzero(self.table.key >= 0)[0]
        if live.size:
            neg = np.full(live.size, -1, np.int32)
            out.add_batch(self.table.key[live], neg,
                          self._recircs[live], neg, self._first_ts[live])
            for slot in live:
                self._evict(int(slot))
        for key in list(self._spill):
            out.add(key, -1, 0, -1, self._spill[key].first_ts)
            del self._spill[key]
            self._retired.add(key)
        self.stats.verdicts += out.n
        return self._finish(out)

    def _finish(self, out: _VerdictAccum) -> StreamVerdicts:
        """Build the tick's verdicts and fold them into the registry.

        Everything here is derived from the verdicts themselves plus
        the stream clock, so it is deterministic across replays — the
        live-parity tests recompute each value offline from the raw
        :class:`StreamVerdicts`.
        """
        v = out.build(self._plan)
        if v.n_flows:
            rec = np.asarray(v.recircs, np.int64)
            self._m_recircs.inc(int(rec.sum()))
            self._m_recirc_hist.record_many(rec)
            self._m_windows.record_many(rec + 1)
            ttd = np.float64(self._now) - out.first_ts()
            self._m_ttd.record_many(ttd[np.isfinite(ttd)])
        pkts = self.stats.packets
        self._m_overhead.set(
            self._m_recircs.value / pkts if pkts else 0.0)
        self._m_resident.set(self.resident_flows)
        return v

    # -- device plumbing ------------------------------------------------
    def _pad_slots(self, s: np.ndarray) -> tuple[int, np.ndarray]:
        cap = _pow2_cap(s.size, self._rank_floor)
        slots = np.full(cap, self._dummy, np.int32)
        slots[:s.size] = s
        return cap, slots

    def _to_host(self, arrays) -> list[np.ndarray]:
        """The arrays as numpy, counting the bytes copied off the device
        (the batch walk's results arrive already copied)."""
        host = [np.asarray(a) for a in jax.device_get(arrays)]
        self.stats.d2h_bytes += sum(a.nbytes for a in host)
        return host

    @staticmethod
    def _rank_decompose(slots: np.ndarray):
        """(order, sorted slots, group id, rank) for one tick.

        Rank r = the r-th packet of a flow within the tick: every rank
        addresses each slot at most once (unique-scatter), and rank
        order preserves per-flow arrival order (stable argsort) — the
        reduction order the parity contract pins.
        """
        order = np.argsort(slots, kind="stable")
        ss = slots[order]
        new_grp = np.r_[True, ss[1:] != ss[:-1]]
        grp_start = np.nonzero(new_grp)[0]
        grp_id = np.cumsum(new_grp) - 1
        rank = np.arange(ss.size) - grp_start[grp_id]
        return order, ss, grp_id, rank

    def _process_resident_fused(self, slots, pkts, out) -> None:
        """One jitted dispatch for the whole tick, one bulk fetch.

        The tick's packets are packed rank-major into ``(R, C)`` arrays
        (column = the flow's group index, constant across ranks), with
        ``cols`` the slot of each column and ``n_pkts`` its packet
        count; both axes are padded to the power-of-two ladder so jit
        compiles a handful of shapes, and padding columns address the
        dummy row.  The retired-flow guard, IAT window reset, fold,
        completion hop, and empty-window drain all run inside
        ``kernels.tick_step`` on the columns' rows, and the verdicts
        come back per column (5 x C int32).  ``tick/fetch/wait`` waits
        for the device before the copy (``tick/fetch/copy``), traced
        or not, so a trace tells the two apart.
        """
        with span("tick/pack"):
            order, ss, grp_id, rank = self._rank_decompose(slots)
            R = _pow2_cap(int(rank.max()) + 1, 1)
            n_col = int(grp_id[-1]) + 1
            C = _pow2_cap(n_col, self._rank_floor)
            cols = np.full(C, self._dummy, np.int32)
            cols[grp_id] = ss
            n_pkts = np.bincount(grp_id, minlength=C).astype(np.int32)
            pkt_rc = np.zeros((R, C, PKT_NFIELDS), np.float32)
            pkt_rc[rank, grp_id] = pkts[order]
        with span("tick/dispatch"):
            with span("tick/dispatch/put"):
                cols_d = jnp.asarray(cols)
                n_pkts_d = jnp.asarray(n_pkts)
                pkt_d = jnp.asarray(pkt_rc)
            with span("tick/dispatch/call"):
                self._tstate, res = _tick.tick_step(
                    self._tstate, cols_d, n_pkts_d, pkt_d, self.engine.dev,
                    n_subtrees=self.S, pallas=self._pallas,
                    block_b=self._block_b)
                self.stats.dispatches += 1
                self.stats.tick_rows += C
        with span("tick/fetch"):
            with span("tick/fetch/wait"):
                jax.block_until_ready(res)
            with span("tick/fetch/copy"):
                vm, vl, vr, ve, rec = self._to_host(res)
        with span("tick/evict"):
            # host mirror (flush/timeout): only the tick's columns can
            # have changed on the device
            self._recircs[cols[:n_col]] = rec[:n_col]
            done = np.nonzero(vm)[0]
            if done.size:
                slot = cols[done]
                out.add_batch(self.table.key[slot], vl[done], vr[done],
                              ve[done], self._first_ts[slot])
                for s in slot:
                    self._evict(int(s))

    # -- host fallbacks -------------------------------------------------
    def _run_spilled_complete(self, out: _VerdictAccum) -> None:
        """Run completed spilled flows through the batch walk."""
        done = [key for key, f in self._spill.items()
                if len(f.rows) >= f.length]
        if not done:
            return
        P = self.P
        all_bounds = {key: window_bounds(self._spill[key].length, P)
                      for key in done}
        # pad both the flows axis and the window axis to the pow2
        # ladder: batch rows are independent in the walk (the
        # zero-filled tail is discarded below) and padded packets carry
        # valid=0, as in ``window_packets``.  Without this, every
        # distinct spill-batch shape is a fresh XLA compile — a
        # spill-heavy stream (tiny table) racks up one executable per
        # tick and can OOM the compiler.
        w_max = _pow2_cap(max(hi - lo for b in all_bounds.values()
                              for lo, hi in b), 1)
        cap = _pow2_cap(len(done), 1)
        wp = np.zeros((cap, P, w_max, PKT_NFIELDS), np.float32)
        for idx, key in enumerate(done):
            rows = np.stack(self._spill[key].rows)
            for w, (lo, hi) in enumerate(all_bounds[key]):
                if hi <= lo:
                    continue
                win = rows[lo:hi].copy()
                win[0, PKT_IAT] = 0.0
                wp[idx, w, :hi - lo] = win
        res = self.engine.run(wp, with_trace=False,
                              options=self._spill_options)
        # the batch walk is a jitted device call like any tick step
        self.stats.dispatches += 1
        labels, recircs, exit_p = self._to_host(
            (res.labels, res.recircs, res.exit_partition))
        n = len(done)
        first = np.asarray([self._spill[k].first_ts for k in done],
                           np.float64)
        out.add_batch(np.asarray(done, np.int64), labels[:n], recircs[:n],
                      exit_p[:n], first)
        for key in done:
            del self._spill[key]
            self._retired.add(key)

    def _evict_timeouts(self, now: float, out: _VerdictAccum) -> None:
        stale = np.nonzero((self.table.key >= 0)
                           & (now - self._last_ts > self.timeout))[0]
        if stale.size:
            neg = np.full(stale.size, -1, np.int32)
            out.add_batch(self.table.key[stale], neg,
                          self._recircs[stale], neg, self._first_ts[stale])
            for slot in stale:
                self._evict(int(slot))
            self.stats.evicted += int(stale.size)
        for key, f in list(self._spill.items()):
            if now - f.last_ts > self.timeout:
                out.add(key, -1, 0, -1, f.first_ts)
                del self._spill[key]
                self._retired.add(key)
                self.stats.evicted += 1
