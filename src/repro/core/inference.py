"""The partitioned-inference engine (paper Fig. 4, TPU-native).

Orchestrates the two data-plane phases per partition window:
  1. Feature Collection & Engineering — fill the k registers for each
     flow's active subtree (``kernels.ops``);
  2. Subtree Model Prediction — range-mark the registers and emit the
     action (next SID or exit class).
Between partitions the engine performs the "recirculation": SID update +
register reset, counted per flow for the bandwidth model.

Execution is unified behind the :class:`ExecutionBackend` protocol —
one device-resident partition walk (:func:`partition_walk`, a single
jitted ``jax.lax.scan`` over partitions) parameterised by the per-stage
step function:

* **fused** — dense jnp step (``ops.fused_step``): per-flow gathers of
  the SID-keyed tables, everything in one XLA computation.
* **pallas** (interpret mode off-TPU) — the Pallas kernels behind the
  in-jit SID dispatch (``ops.fused_step_pallas``):
  flows are argsorted/scattered into SID-homogeneous capacity blocks
  *inside* jit, so the MoE-style grouping costs zero host round trips
  and the walk still crosses the device→host boundary exactly once per
  batch.
* **looped** — host-side Python loop with a per-partition sync; the
  benchmark baseline and the per-op dispatch point.

All backends share :class:`EngineResult` semantics and must agree with
:meth:`PartitionedDT.predict` (the offline numpy oracle) — and, since
``kernels.ref.ordered_wsum`` pinned the reduction order, they agree
bit-exactly; property tests enforce this for every backend.  A flow
that never takes an exit action reports ``-1`` sentinels (labels and
exit partition) rather than masquerading as class 0 at partition 0;
``EngineResult.n_unterminated`` counts them.

Every backend also accepts ``compact=True``: early-exit compaction of
the recirculation walk (``kernels.compaction``) — after each hop only
the surviving flows are carried through feature-window rebuild +
traversal, via static power-of-two capacity buckets in-jit (walk
backends) or host fancy-indexing (looped).  Bit-identical to the dense
walk; ``compact=False`` remains the reference path.

Backend selection: ``Engine.run(win_pkts, impl=...)`` or the engine's
``impl=`` field; see :func:`get_backend` for the selection matrix.
``impl="auto"`` routes through the analytical cost model and
``impl="tuned"`` through the cached empirical autotuner
(``repro.tuning``) — both resolve a ``Plan`` (backend, Pallas
``block_b``, compaction + ladder floor) for the batch shape at hand and
attach it to ``EngineResult.plan``.  docs/ARCHITECTURE.md has the
end-to-end tour; docs/PARITY.md states the bit-exactness contract that
makes routing a pure speed decision.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.partition import PartitionedDT
from repro.core.range_tables import RangeExecTables, pack_range_exec
from repro.core.tables import PackedTables, pack_tables
from repro.kernels import compaction, ops
from repro import obs


@dataclasses.dataclass
class EngineResult:
    labels: np.ndarray           # (B,) predicted class per flow; -1 if the
                                 #     flow never took an exit action
    recircs: np.ndarray          # (B,) partition transitions (control pkts)
    exit_partition: np.ndarray   # (B,) exit hop per flow; -1 sentinel as above
    regs_trace: list[np.ndarray] # per-partition register snapshots
    plan: "object | None" = None # repro.tuning.Plan when impl="auto"/"tuned"
                                 # resolved the backend; None for forced impls

    @property
    def n_unterminated(self) -> int:
        """Flows that never took an exit action (``-1`` sentinels).

        Non-zero only for corrupt/truncated models (e.g. depth-truncated
        DSE candidates whose final partition still routes to a SID) —
        a trained :class:`PartitionedDT` exits every flow by the last
        partition.  Surfaced so callers can distinguish "class 0 at
        partition 0" from "the walk fell off the end".
        """
        return int(np.count_nonzero(np.asarray(self.exit_partition) < 0))


# one partition stage (defined next to DeviceTables; re-exported here
# because backends and the streaming scheduler type against it)
StepFn = ops.StepFn


# ---------------------------------------------------------------------------
# engine options — every execution knob in one frozen bag
# ---------------------------------------------------------------------------

_IMPLS = (None, "auto", "tuned", "ref", "fused", "pallas", "looped")


@dataclasses.dataclass(frozen=True)
class EngineOptions:
    """All engine execution knobs, in one frozen value.

    ``Engine.run`` / ``run_looped`` / ``run_streaming`` and the serving
    layer (``repro.serve``) all accept ``options=EngineOptions(...)``;
    each entry point reads the knobs that apply to it and ignores the
    rest (e.g. ``Engine.run`` never micro-batches, so ``micro_batch``
    is inert there).  The legacy per-call keywords (``impl=``,
    ``compact=``, ``mesh=``, ...) still work but emit a
    ``DeprecationWarning`` and cannot be mixed with ``options=``.

    ===============  =====================================================
    knob             meaning
    ===============  =====================================================
    impl             backend request: ``None`` (engine default), a fixed
                     backend (``fused``/``ref``/``pallas``/``looped``),
                     ``"auto"`` (cost model) or ``"tuned"`` (autotune
                     cache) — see ``repro.tuning``
    plan             a pre-resolved ``repro.tuning.Plan``; wins over
                     ``impl``/``compact``/``block_b`` (the plan already
                     carries all three)
    compact          early-exit compaction: True/False pinned, or
                     ``"auto"`` (the routing plan decides)
    compact_floor    smallest capacity bucket of the compaction ladder
    block_b          Pallas flow-block rows (None = kernel default;
                     only read when the resolved backend is pallas)
    micro_batch      streaming/serving chunk size (flows per dispatch)
    inflight         streaming pipeline depth (chunks in flight)
    mesh             ``jax.sharding.Mesh`` to shard the flow axis over
    ===============  =====================================================
    """
    impl: str | None = None
    plan: "object | None" = None
    compact: bool | str = False
    compact_floor: int = compaction.COMPACT_FLOOR
    block_b: int | None = None
    micro_batch: int = 4096
    inflight: int = 2
    mesh: "object | None" = None

    def __post_init__(self):
        if self.impl not in _IMPLS:
            raise ValueError(f"unknown impl {self.impl!r}; options: "
                             + ", ".join(str(i) for i in _IMPLS))
        if self.compact not in (True, False, "auto"):
            raise ValueError(
                f"compact must be True, False or 'auto', got {self.compact!r}")
        if self.compact_floor <= 0:
            raise ValueError("compact_floor must be positive")
        if self.block_b is not None and self.block_b <= 0:
            raise ValueError("block_b must be positive")
        if self.micro_batch <= 0:
            raise ValueError("micro_batch must be positive")
        if self.inflight <= 0:
            raise ValueError("inflight must be positive")

    def replace(self, **changes) -> "EngineOptions":
        """``dataclasses.replace`` as a method (frozen-friendly)."""
        return dataclasses.replace(self, **changes)


#: Sentinel distinguishing "legacy keyword not passed" from any real
#: value (None is meaningful for several knobs).
_UNSET = object()


def _legacy_options(options: EngineOptions | None, legacy: dict,
                    *, stacklevel: int = 3) -> EngineOptions:
    """Fold explicitly-passed legacy keywords into an EngineOptions.

    The deprecation shim shared by ``Engine.run``/``run_looped``/
    ``run_streaming`` and ``repro.serve.streaming``: legacy keywords
    still work (every pre-EngineOptions call site keeps its behaviour)
    but warn once per call site, and mixing them with ``options=`` is
    an error rather than a silent precedence rule.
    """
    passed = {key: v for key, v in legacy.items() if v is not _UNSET}
    if not passed:
        return options if options is not None else EngineOptions()
    if options is not None:
        raise ValueError(
            "pass options=EngineOptions(...) OR legacy keyword(s) "
            f"({', '.join(sorted(passed))}), not both")
    warnings.warn(
        "keyword(s) " + ", ".join(sorted(passed)) + " are deprecated; "
        "use options=EngineOptions(...) instead",
        DeprecationWarning, stacklevel=stacklevel)
    return EngineOptions(**passed)


def _walk_init(B: int) -> tuple[jnp.ndarray, ...]:
    """Initial flow-walk carry: ``(sid, done, labels, recircs, exit_p)``.

    ``labels`` / ``exit_partition`` start at the ``-1`` sentinel so a
    flow that never takes an exit action (non-terminating: corrupt
    tables, depth-truncated DSE candidates) is distinguishable from a
    legitimate class-0 verdict at partition 0.
    """
    return (
        jnp.zeros(B, jnp.int32),            # sid: all flows start at root
        jnp.zeros(B, jnp.bool_),            # done
        jnp.full(B, -1, jnp.int32),         # labels (sentinel)
        jnp.zeros(B, jnp.int32),            # recircs
        jnp.full(B, -1, jnp.int32),         # exit_partition (sentinel)
    )


def _hop_update(carry, p, action, S: int):
    """Shared recirculation bookkeeping for one hop (dense or compacted).

    ``action`` slots belonging to already-``done`` flows may carry any
    value (the compacted step leaves ``-1`` there) — everything is
    masked by ``active``.
    """
    sid, done, labels, recircs, exit_p = carry
    is_exit = action >= S
    active = ~done
    exiting = active & is_exit
    labels = jnp.where(exiting, action - S, labels)
    exit_p = jnp.where(exiting, p, exit_p)
    done = done | exiting
    cont = active & ~is_exit
    # recirculation: one control packet per transition, SID register
    # update; feature registers are rebuilt from scratch next window
    recircs = recircs + cont.astype(jnp.int32)
    sid = jnp.where(cont, action, sid)
    return sid, done, labels, recircs, exit_p


def _partition_walk(
    win_pkts: jnp.ndarray,       # (B, P, W, PKT_NFIELDS)
    dev: ops.DeviceTables,
    *,
    n_subtrees: int,
    with_trace: bool = False,
    step: StepFn = ops.fused_step,
    compact: bool = False,
    compact_floor: int = compaction.COMPACT_FLOOR,
):
    """Device-resident partition walk: scan partitions, carry flow state.

    Returns ``(labels, recircs, exit_partition, regs)`` — all int32
    except ``regs`` (P, B, k) f32, which is ``None`` unless
    ``with_trace``.  Actions ``>= n_subtrees`` exit with class
    ``action - n_subtrees``; smaller actions recirculate to that SID; a
    flow still active after the last partition keeps the ``-1``
    sentinels.  ``step`` is the backend's per-partition stage (dense jnp
    or Pallas kernels); the walk itself is backend-agnostic.

    With ``compact=True`` the walk early-exit-compacts between hops
    (``kernels.compaction``): survivors are gathered into the smallest
    power-of-two capacity bucket that fits them, the step runs on that
    prefix only, and verdicts scatter back to the original flow slots.
    Bit-identical to the dense walk; the register trace differs only in
    that exited flows report zero registers for the hops they skipped.
    """
    if compact:
        return _compacted_walk(win_pkts, dev, n_subtrees=n_subtrees,
                               with_trace=with_trace, step=step,
                               floor=compact_floor)
    B, P = win_pkts.shape[0], win_pkts.shape[1]
    S = n_subtrees

    def body(carry, xs):
        p, pkts = xs
        regs, action = step(pkts, carry[0], dev)
        return _hop_update(carry, p, action, S), (
            regs if with_trace else None)

    xs = (jnp.arange(P, dtype=jnp.int32), jnp.swapaxes(win_pkts, 0, 1))
    (sid, done, labels, recircs, exit_p), regs = jax.lax.scan(
        body, _walk_init(B), xs)
    return labels, recircs, exit_p, regs


def _compacted_walk(
    win_pkts: jnp.ndarray,       # (B, P, W, PKT_NFIELDS)
    dev: ops.DeviceTables,
    *,
    n_subtrees: int,
    with_trace: bool,
    step: StepFn,
    floor: int = compaction.COMPACT_FLOOR,
):
    """Early-exit-compacted walk: unrolled hops, shrinking active buffer.

    Hop 0 runs dense (every flow is active at the root); each later hop
    runs the step only on the compacted survivor prefix, in the smallest
    capacity bucket that fits (``lax.switch`` over a static power-of-two
    ladder ``(0, floor, 2*floor, …, B)`` — see ``kernels.compaction``).
    Unrolled rather than scanned because the per-hop buffer capacity is
    data-dependent; P is small (2-4 partitions), so the trace stays
    cheap.
    """
    B, P = win_pkts.shape[0], win_pkts.shape[1]
    caps = compaction.bucket_caps(B, floor)
    carry = _walk_init(B)
    trace = []
    for p in range(P):
        pkts = win_pkts[:, p]
        if p == 0:
            regs, action = step(pkts, carry[0], dev)
        else:
            regs, action = compaction.compacted_step(
                pkts, carry[0], carry[1], dev, step=step, caps=caps,
                with_regs=with_trace)
        carry = _hop_update(carry, p, action, n_subtrees)
        if with_trace:
            trace.append(regs)
    _, _, labels, recircs, exit_p = carry
    return labels, recircs, exit_p, (jnp.stack(trace) if with_trace
                                     else None)


_WALK_STATIC = ("n_subtrees", "with_trace", "step", "compact",
                "compact_floor")

partition_walk = jax.jit(_partition_walk, static_argnames=_WALK_STATIC)

# Older name (step defaults to the dense jnp stage) — kept for callers
# that predate the backend layer.
fused_partition_walk = partition_walk


# ---------------------------------------------------------------------------
# execution backends
# ---------------------------------------------------------------------------
@runtime_checkable
class ExecutionBackend(Protocol):
    """One engine execution strategy.

    Implementations must produce identical :class:`EngineResult`s (the
    shared correctness oracle is ``PartitionedDT.predict`` +
    ``kernels.ref``); they differ only in how the partition walk
    executes.  ``step`` is the jit-traceable per-partition stage for
    walk-based backends, or ``None`` when the backend does not run the
    shared walk (looped).
    """
    name: str
    step: StepFn | None

    def run(self, engine: "Engine", win_pkts: np.ndarray, *,
            with_trace: bool = True, compact: bool = False,
            compact_floor: int = compaction.COMPACT_FLOOR
            ) -> EngineResult: ...


def _record_walk(exit_p: np.ndarray, P: int, *, compact: bool,
                 compact_floor: int) -> None:
    """Per-hop survivor counts — and, when compacting, the capacity
    bucket each hop padded its survivors to — derived HOST-side from
    the already-fetched exit partitions.  A flow exiting at partition
    ``e`` is live for hops ``0..e``, so the survivor count entering
    hop ``p`` is ``B - |{exits < p}|``; no extra device work or syncs.
    """
    reg = obs.get_registry()
    B = int(exit_p.shape[0])
    exits = np.bincount(exit_p[exit_p >= 0], minlength=P)
    survivors = B - np.concatenate(([0], np.cumsum(exits)[:P - 1]))
    caps = compaction.bucket_caps(B, compact_floor) if compact else None
    for p in range(P):
        s = int(survivors[p])
        reg.counter(
            "engine_hop_survivors_total",
            "flows still walking when each hop starts",
            labels={"hop": str(p)}).inc(s)
        if caps is not None:
            cap = next(c for c in caps if c >= s)
            reg.counter(
                "engine_compact_bucket_total",
                "capacity-ladder bucket the hop's survivors padded to",
                labels={"hop": str(p), "cap": str(cap)}).inc()


@dataclasses.dataclass(frozen=True)
class WalkBackend:
    """Fully-jitted walk: ONE device→host transfer per batch.

    ``fused`` and ``pallas`` are both instances of this — they share the
    scan, the carry semantics, and the single ``jax.device_get``; only
    the per-partition ``step`` differs.
    """
    name: str
    step: StepFn

    def run(self, engine: "Engine", win_pkts: np.ndarray, *,
            with_trace: bool = True, compact: bool = False,
            compact_floor: int = compaction.COMPACT_FLOOR) -> EngineResult:
        P = engine._check_windows(win_pkts)
        with obs.span("engine/dispatch"):
            labels, recircs, exit_p, regs = partition_walk(
                jnp.asarray(win_pkts[:, :P]), engine.dev,
                n_subtrees=engine.ret.n_subtrees, with_trace=with_trace,
                step=self.step, compact=compact,
                compact_floor=compact_floor)
            obs.get_registry().counter(
                "engine_dispatches_total", "jitted walk calls issued",
                labels={"backend": self.name}).inc()
        with obs.span("engine/fetch"):
            # ONE device->host transfer for the whole batch
            labels, recircs, exit_p, regs = jax.device_get(
                (labels, recircs, exit_p, regs))
        _record_walk(np.asarray(exit_p), P, compact=compact,
                     compact_floor=compact_floor)
        trace = [] if regs is None else [regs[p] for p in range(P)]
        return EngineResult(labels, recircs, exit_p, trace)


@dataclasses.dataclass(frozen=True)
class LoopedBackend:
    """Host-side per-partition loop (one device→host sync per hop).

    Kept as the benchmark baseline and the per-op dispatch point: each
    hop calls ``ops.feature_window`` / ``ops.dt_traverse`` with the
    engine's per-op impl, so individual kernels can be exercised in
    isolation.
    """
    name: str = "looped"
    step: None = None

    @staticmethod
    def _op_impl(impl: str) -> str:
        if impl in ("pallas", "auto"):
            return impl
        return "ref"

    def run(self, engine: "Engine", win_pkts: np.ndarray, *,
            with_trace: bool = True, compact: bool = False,
            compact_floor: int = compaction.COMPACT_FLOOR) -> EngineResult:
        # compact_floor is a capacity-ladder knob; the looped backend
        # compacts by exact host fancy-indexing, so it has no ladder
        del compact_floor
        B = win_pkts.shape[0]
        P = engine._check_windows(win_pkts)
        impl = self._op_impl(engine.impl)
        S = engine.ret.n_subtrees
        k = engine.ret.k
        # the loop's carry lives on the HOST: one upload (sid + packets)
        # and one fetch (regs + action, or action alone) per hop — the
        # per-partition np.asarray/jnp.asarray ping-pong that used to mix
        # numpy and jnp mask arithmetic is gone
        sid = np.zeros(B, dtype=np.int32)
        done = np.zeros(B, dtype=bool)
        # int32 to match the walk backends: verdicts from any backend
        # concatenate without silent upcasts; -1 sentinels as in the walk
        labels = np.full(B, -1, dtype=np.int32)
        recircs = np.zeros(B, dtype=np.int32)
        exit_partition = np.full(B, -1, dtype=np.int32)
        regs_trace: list[np.ndarray] = []

        reg_obs = obs.get_registry()
        for p in range(P):
            reg_obs.counter(
                "engine_hop_survivors_total",
                "flows still walking when each hop starts",
                labels={"hop": str(p)}).inc(int(B - done.sum()))
            # host-side early-exit compaction: the looped analogue of the
            # walk backends' capacity buckets is plain fancy indexing
            rows = np.nonzero(~done)[0] if compact and p else np.arange(B)
            if rows.size:
                dense = rows.size == B
                pkts = jnp.asarray(win_pkts[:, p] if dense
                                   else win_pkts[rows, p])
                sid_d = jnp.asarray(sid[rows])
                regs_d = ops.feature_window(pkts, sid_d, engine.tables,
                                            impl=impl)
                action_d = ops.dt_traverse(regs_d, sid_d, engine.ret,
                                           impl=impl)
                reg_obs.counter(
                    "engine_dispatches_total",
                    "jitted walk calls issued",
                    labels={"backend": "looped"}).inc(2)
                if with_trace:
                    regs_h, action_h = jax.device_get((regs_d, action_d))
                else:
                    action_h = jax.device_get(action_d)
            if with_trace:
                if B and rows.size == B:
                    regs_trace.append(regs_h)
                else:
                    full = np.zeros((B, k), dtype=np.float32)
                    if rows.size:
                        full[rows] = regs_h
                    regs_trace.append(full)
            if not rows.size:
                continue
            action = np.full(B, -1, dtype=np.int32)
            action[rows] = action_h
            is_exit = action >= S
            active = ~done
            exiting = active & is_exit
            labels[exiting] = action[exiting] - S
            exit_partition[exiting] = p
            done |= exiting
            cont = active & ~is_exit
            recircs[cont] += 1           # one control packet per transition
            # "recirculation": update SID register, reset feature registers
            sid = np.where(cont, action, sid).astype(np.int32)
        return EngineResult(labels, recircs, exit_partition, regs_trace)


FUSED_BACKEND = WalkBackend(name="fused", step=ops.fused_step)
PALLAS_BACKEND = WalkBackend(name="pallas", step=ops.fused_step_pallas)
LOOPED_BACKEND = LoopedBackend()

_BACKENDS: dict[str, ExecutionBackend] = {
    "fused": FUSED_BACKEND,
    "pallas": PALLAS_BACKEND,
    "looped": LOOPED_BACKEND,
}


@functools.lru_cache(maxsize=None)
def pallas_backend(block_b: int = ops.BLOCK_B) -> WalkBackend:
    """Pallas walk backend with a tuned ``block_b`` (cached per size,
    so jit/streaming caches keyed on the step function stay warm).
    ``pallas_backend(BLOCK_B) is PALLAS_BACKEND``."""
    if block_b == ops.BLOCK_B:
        return PALLAS_BACKEND
    return WalkBackend(name=f"pallas[bb={block_b}]",
                       step=ops.pallas_step(block_b))


def backend_for_plan(plan) -> ExecutionBackend:
    """Resolve a :class:`repro.tuning.Plan` to its execution backend."""
    if plan.backend == "pallas":
        return pallas_backend(plan.block_b)
    return _BACKENDS[plan.backend]


def get_backend(impl: str = "auto", shape=None) -> ExecutionBackend:
    """Backend selection matrix (see docs/ARCHITECTURE.md):

    ==========  =====================================================
    impl        backend
    ==========  =====================================================
    auto        with ``shape`` (a ``repro.tuning.ShapeInfo``): the
                cost model's argmin backend for that workload;
                without: pallas on TPU, fused elsewhere (legacy
                platform default)
    tuned       resolved by ``Engine.run`` / ``run_streaming`` via the
                autotune cache; rejected here (needs an engine +
                batch to probe)
    fused, ref  fused (dense jnp walk)
    pallas      pallas (Pallas kernels + in-jit SID dispatch;
                interpret mode off-TPU)
    looped      looped (host loop, per-partition sync)
    ==========  =====================================================
    """
    if impl == "tuned":
        raise ValueError(
            "impl='tuned' is shape-dependent; use Engine.run / "
            "run_streaming (they resolve it through repro.tuning)")
    if impl == "auto":
        if shape is not None:
            from repro.tuning import choose_plan
            return backend_for_plan(choose_plan(shape))
        impl = "pallas" if ops._on_tpu() else "fused"
    if impl == "ref":
        impl = "fused"
    try:
        return _BACKENDS[impl]
    except KeyError:
        raise ValueError(
            f"unknown impl {impl!r}; options: auto, tuned, ref, "
            + ", ".join(sorted(_BACKENDS))) from None


@dataclasses.dataclass
class Engine:
    tables: PackedTables
    ret: RangeExecTables
    impl: str = "auto"
    _dev: ops.DeviceTables | None = dataclasses.field(
        default=None, repr=False, compare=False)

    @classmethod
    def from_model(cls, pdt: PartitionedDT, impl: str = "auto") -> "Engine":
        return cls(tables=pack_tables(pdt), ret=pack_range_exec(pdt), impl=impl)

    @property
    def dev(self) -> ops.DeviceTables:
        """Device-resident MAT programs (uploaded once, then cached)."""
        if self._dev is None:
            self._dev = ops.device_tables(self.tables, self.ret)
        return self._dev

    def _check_windows(self, win_pkts: np.ndarray) -> int:
        P = win_pkts.shape[1]
        if P < self.tables.n_partitions:
            raise ValueError("fewer windows than partitions")
        return self.tables.n_partitions

    # ------------------------------------------------------------------
    # unified entry point
    # ------------------------------------------------------------------
    def run(self, win_pkts: np.ndarray, *, with_trace: bool = True,
            options: EngineOptions | None = None,
            impl: "str | None | object" = _UNSET,
            compact: "bool | str | object" = _UNSET) -> EngineResult:
        """``win_pkts``: (B, p, W, PKT_NFIELDS) from ``window_packets``.

        Execution knobs arrive as ``options=EngineOptions(...)``
        (``impl=``/``compact=`` remain as deprecated shims):

        * ``options.plan`` (a pre-resolved ``repro.tuning.Plan``) wins
          outright — backend, ``block_b`` and compaction come from it;
        * otherwise ``options.impl`` (falling back to the engine's
          default): a fixed backend name dispatches straight to
          :func:`get_backend`; ``"auto"`` routes through the cost model
          (``repro.tuning.costmodel``) using this batch's shape;
          ``"tuned"`` routes through the autotune cache
          (``repro.tuning.autotune``) — first call on a new (shape,
          host) times a cost-model shortlist, later calls are a lookup.

        Whenever a :class:`repro.tuning.Plan` decided the route it is
        attached as ``EngineResult.plan``.  ``compact=True`` enables
        early-exit compaction between hops, ``"auto"`` lets the plan
        decide (identical verdicts either way; the dense
        ``compact=False`` path remains the reference).  All backends
        are bit-identical, so routing can only change speed, never
        results.
        """
        opt = _legacy_options(options, {"impl": impl, "compact": compact})
        if opt.plan is not None:
            return self._run_plan(opt.plan, win_pkts, with_trace)
        impl = opt.impl or self.impl
        if impl in ("auto", "tuned") or opt.compact == "auto":
            from repro.tuning import get_plan
            plan = get_plan(self, win_pkts, impl=impl, compact=opt.compact)
            return self._run_plan(plan, win_pkts, with_trace)
        if impl == "pallas" and opt.block_b is not None:
            backend = pallas_backend(opt.block_b)
        else:
            backend = get_backend(impl)
        return backend.run(self, win_pkts, with_trace=with_trace,
                           compact=bool(opt.compact),
                           compact_floor=opt.compact_floor)

    def _run_plan(self, plan, win_pkts: np.ndarray,
                  with_trace: bool) -> EngineResult:
        res = backend_for_plan(plan).run(
            self, win_pkts, with_trace=with_trace,
            compact=plan.compact, compact_floor=plan.compact_floor)
        res.plan = plan
        return res

    # ------------------------------------------------------------------
    # streaming path (batches far beyond one device batch)
    # ------------------------------------------------------------------
    def run_streaming(self, win_pkts: np.ndarray, *,
                      options: EngineOptions | None = None,
                      micro_batch=_UNSET,
                      mesh=_UNSET,
                      impl=_UNSET,
                      inflight=_UNSET,
                      compact=_UNSET) -> EngineResult:
        """Chunk ``win_pkts`` into fixed-size padded micro-batches and
        run each through a walk backend; with ``options.mesh`` the
        micro-batch fans out across the mesh's flow-batch axis via
        ``shard_map``.  ``options.compact`` early-exit-compacts each
        chunk's walk; ``options.impl="auto"``/``"tuned"`` resolve the
        chunk's plan through ``repro.tuning``.  Legacy keywords are
        deprecated shims for ``options=``.  See
        ``repro.serve.streaming``."""
        opt = _legacy_options(options, {
            "micro_batch": micro_batch, "mesh": mesh,
            "impl": impl, "inflight": inflight, "compact": compact})
        from repro.serve.streaming import run_streaming
        return run_streaming(self, win_pkts, options=opt)

    # ------------------------------------------------------------------
    # looped path (per-partition host sync; per-op dispatch + baseline)
    # ------------------------------------------------------------------
    def run_looped(self, win_pkts: np.ndarray, *, with_trace: bool = True,
                   options: EngineOptions | None = None,
                   compact=_UNSET) -> EngineResult:
        opt = _legacy_options(options, {"compact": compact})
        return LOOPED_BACKEND.run(self, win_pkts, with_trace=with_trace,
                                  compact=bool(opt.compact))
