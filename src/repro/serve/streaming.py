"""Streaming batch scheduler for the partitioned-DT walk backends.

The data-plane story (DESIGN.md §4) is millions of concurrent flows over
a FIXED register pool; the TPU serving analogue is an unbounded flow
stream over a FIXED device batch.  This module chunks arbitrarily large
flow batches into fixed-size micro-batches, pads the ragged tail with
invalid packets (valid = 0 — the same padding the windowing pipeline
emits), and pushes each chunk through a fully-jitted partition walk:

  * every micro-batch has the SAME (mb, P, W, F) shape — including the
    padded tail — so XLA compiles the walk exactly once and replays it
    per chunk;
  * any walk backend works (``impl="fused"`` or ``"pallas"`` — the
    in-jit SID dispatch keeps the Pallas path streamable; ``"looped"``
    is rejected because it syncs per partition).  ``impl="auto"`` /
    ``"tuned"`` route through ``repro.tuning`` with the *chunk* shape
    (B = micro_batch, n_devices from the mesh) — the chunk, not the
    unbounded stream, is what executes;
  * with a ``mesh``, each micro-batch fans out across the mesh's
    data-parallel axes via ``shard_map`` — the walk is per-flow, so no
    collectives are needed and scaling is embarrassingly parallel;
  * each chunk's packet buffer is dropped once its walk is dispatched,
    so the device holds at most ``inflight + 1`` chunks of packets (the
    buffer is not donated: no output of the walk could reuse it);
  * results land in preallocated host arrays — one device→host
    transfer per micro-batch, none per partition.

**Inflight pipelining.**  jax dispatch is asynchronous: ``walk(batch)``
returns device futures immediately.  The scheduler keeps up to
``inflight`` chunks un-collected, so while the device crunches chunk i
the host is already slicing/padding/uploading chunk i+1; memory
high-water is ``inflight`` micro-batches of packets plus their verdict
buffers, NOT the full stream.  ``inflight=1`` collects each chunk
before dispatching the next (the fully synchronous PR 1 behaviour);
raising it past 2–3 only helps when host staging time rivals device
compute time.

``run_streaming`` is the closed-batch entry point (numpy in → verdicts
out); ``stream_batches`` is the open-stream form that consumes an
iterator of flow batches, for callers that never materialise the full
workload.

Shape/dtype conventions (shared with ``core.inference``): packet
windows are f32 ``(B, P, W, PKT_NFIELDS)``; verdict arrays are int32
``(B,)`` with ``-1`` sentinels for flows that never exit (see
``docs/PARITY.md``); padded rows are all-zero packets (valid=0) whose
verdicts are sliced off before they reach the caller.
"""
from __future__ import annotations

import functools
import math
from typing import Iterable, Iterator

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from repro import obs
from repro.core.inference import (
    _UNSET,
    Engine,
    EngineOptions,
    EngineResult,
    ExecutionBackend,
    StepFn,
    _legacy_options,
    _partition_walk,
    _record_walk,
    backend_for_plan,
    get_backend,
    pallas_backend,
    partition_walk,
)
from repro.distributed.sharding import flow_batch_devices, flow_batch_spec
from repro.kernels.compaction import COMPACT_FLOOR
from repro.kernels.dispatch import pad_axis0, round_up


def _walk_backend(engine: Engine, impl: str | None) -> ExecutionBackend:
    backend = get_backend(impl or engine.impl)
    if backend.step is None:
        raise ValueError(
            f"streaming requires a jitted walk backend (fused or pallas); "
            f"impl={backend.name!r} syncs the host every partition")
    return backend


def _resolve_backend(engine: Engine, opt: EngineOptions, mb: int, win_pkts):
    """Pick the chunk's walk backend; returns (backend, compact,
    compact_floor, plan).  A pre-resolved ``opt.plan`` wins outright;
    fixed impls go straight to :func:`get_backend` (honouring
    ``opt.block_b`` for pallas); ``auto``/``tuned`` (or
    ``compact="auto"``) resolve a ``repro.tuning.Plan`` for the CHUNK
    shape — B is the micro-batch, ``n_devices`` the mesh's
    data-parallel extent — with candidates restricted to the
    streamable walk backends."""
    if opt.plan is not None:
        plan = opt.plan
        backend = backend_for_plan(plan)
        if backend.step is None:
            raise ValueError(
                f"streaming requires a jitted walk backend (fused or "
                f"pallas); plan backend {plan.backend!r} syncs the host "
                "every partition")
        return backend, plan.compact, plan.compact_floor, plan
    impl = opt.impl or engine.impl
    if impl not in ("auto", "tuned") and opt.compact != "auto":
        if impl == "pallas" and opt.block_b is not None:
            backend = pallas_backend(opt.block_b)
        else:
            backend = _walk_backend(engine, impl)
        return backend, bool(opt.compact), opt.compact_floor, None
    from repro.tuning import ShapeInfo, get_plan
    mesh = opt.mesh
    n_dev = flow_batch_devices(mesh) if mesh is not None else 1
    shape = ShapeInfo.from_engine(engine, win_pkts, B=mb, n_devices=n_dev)
    plan = get_plan(engine, win_pkts, impl=impl, shape=shape,
                    backends=("fused", "pallas"), compact=opt.compact,
                    streaming=True)
    return (backend_for_plan(plan), plan.compact, plan.compact_floor, plan)


def _single_device_walk(n_subtrees: int, step: StepFn,
                        compact: bool = False, floor: int = COMPACT_FLOOR):
    """(batch, dev) -> (labels, recircs, exit_partition).  No caching
    needed: partition_walk is already jitted at module level, and its
    compile cache keys on the same static (n_subtrees, step, compact,
    compact_floor) args."""
    return lambda batch, dev: partition_walk(
        batch, dev, n_subtrees=n_subtrees, with_trace=False, step=step,
        compact=compact, compact_floor=floor)[:3]


@functools.lru_cache(maxsize=None)
def _sharded_walk(mesh, n_subtrees: int, step: StepFn,
                  compact: bool = False, floor: int = COMPACT_FLOOR):
    """shard_map'd walk: the flow axis splits over the mesh's
    data-parallel axes; the device tables replicate.  The walk carries
    no cross-flow state, so the body needs no collectives — and with
    ``compact`` each shard counts its own survivors and picks its own
    capacity bucket (the switch index is shard-local data, no sync)."""
    spec = flow_batch_spec(mesh)

    def body(batch, dev):
        labels, recircs, exit_p, _ = _partition_walk(
            batch, dev, n_subtrees=n_subtrees, with_trace=False, step=step,
            compact=compact, compact_floor=floor)
        return labels, recircs, exit_p

    # check_vma=False: the body is collective-free by construction, and
    # pallas_call (the pallas backend's step) has no replication rule
    sharded = jax.shard_map(body, mesh=mesh,
                            in_specs=(spec, PartitionSpec()),
                            out_specs=(spec, spec, spec),
                            check_vma=False)
    return jax.jit(sharded)


def microbatches(n: int, micro_batch: int) -> Iterator[tuple[int, int]]:
    """Yield ``[lo, hi)`` bounds covering ``n`` flows in fixed chunks."""
    if micro_batch <= 0:
        raise ValueError("micro_batch must be positive")
    for i in range(math.ceil(n / micro_batch)):
        yield i * micro_batch, min((i + 1) * micro_batch, n)


def run_streaming(
    engine: Engine,
    win_pkts: np.ndarray,        # (B, p, W, PKT_NFIELDS), B unbounded
    *,
    options: EngineOptions | None = None,
    micro_batch=_UNSET,
    mesh=_UNSET,
    impl=_UNSET,
    inflight=_UNSET,
    compact=_UNSET,
) -> EngineResult:
    """Streaming inference over a batch larger than one device batch.

    Equivalent to ``engine.run(win_pkts, with_trace=False)`` for any
    ``B``, ``micro_batch``, backend, mesh, and pipelining depth
    (property-tested, including the padded ragged tail); memory
    high-water is ``inflight`` micro-batches, not ``B``.  Knobs arrive
    as ``options=EngineOptions(...)`` (the bare keywords are deprecated
    shims).  With ``options.mesh`` the micro-batch is rounded up to a
    multiple of the mesh's data-parallel device count and each chunk
    executes sharded over the flow axis.  ``compact=True`` runs each
    chunk's walk with early-exit compaction (``kernels.compaction``) —
    identical verdicts, less work per hop once flows start exiting;
    ``compact="auto"`` lets the routing plan decide.

    ``impl="auto"`` / ``"tuned"`` resolve a ``repro.tuning.Plan`` for
    the chunk shape (backend + ``block_b`` + compaction), restricted to
    the streamable walk backends; the plan lands on the returned
    result's ``.plan`` (a pre-resolved ``options.plan`` is used as-is).

    ``inflight`` chunks are dispatched before the first result is
    pulled, so host staging of chunk i+1 overlaps device compute of
    chunk i (jax dispatch is async); ``inflight=1`` restores the fully
    synchronous PR 1 behaviour.
    """
    opt = _legacy_options(options, {
        "micro_batch": micro_batch, "mesh": mesh,
        "impl": impl, "inflight": inflight, "compact": compact})
    P = engine._check_windows(win_pkts)
    B = win_pkts.shape[0]
    mesh, inflight = opt.mesh, opt.inflight
    mb = opt.micro_batch
    if mesh is not None:
        mb = round_up(mb, flow_batch_devices(mesh))
    backend, cpt, floor, plan = _resolve_backend(engine, opt, mb, win_pkts)
    if mesh is not None:
        walk = _sharded_walk(mesh, engine.ret.n_subtrees, backend.step,
                             cpt, floor)
    else:
        walk = _single_device_walk(engine.ret.n_subtrees, backend.step,
                                   cpt, floor)

    # int32 throughout with the walk's -1 sentinels as the fill value:
    # per-batch results concatenate (stream_batches) without upcasts,
    # and an unwritten row can never masquerade as a class-0 verdict
    labels = np.full(B, -1, dtype=np.int32)
    recircs = np.zeros(B, dtype=np.int32)
    exit_partition = np.full(B, -1, dtype=np.int32)
    pending: list[tuple[int, int, tuple]] = []

    reg = obs.get_registry()
    chunk_counter = reg.counter(
        "stream_chunks_total", "micro-batches dispatched by run_streaming",
        labels={"backend": backend.name})

    def collect(keep: int) -> None:
        while len(pending) > keep:
            lo, hi, fut = pending.pop(0)
            with obs.span("stream/fetch"):
                lab, rec, exi = jax.device_get(fut)
            labels[lo:hi] = lab[:hi - lo]
            recircs[lo:hi] = rec[:hi - lo]
            exit_partition[lo:hi] = exi[:hi - lo]

    # every chunk has the SAME (mb, P, W, F) shape — even when B < mb —
    # so XLA compiles the walk once for the whole stream, whatever batch
    # sizes the producer emits
    for lo, hi in microbatches(B, mb):
        m = hi - lo
        if m == mb:
            # full chunk: upload straight from the caller's tensor
            batch = jnp.asarray(win_pkts[lo:hi, :P], dtype=jnp.float32)
        else:
            # ragged tail: pad with invalid packets (all-zero rows)
            batch = jnp.asarray(pad_axis0(
                np.ascontiguousarray(win_pkts[lo:hi, :P], dtype=np.float32),
                mb))
        with obs.span("stream/dispatch"):
            pending.append((lo, hi, walk(batch, engine.dev)))
            chunk_counter.inc()
            reg.counter("engine_dispatches_total",
                        "jitted walk calls issued",
                        labels={"backend": backend.name}).inc()
        collect(inflight - 1)
    collect(0)
    _record_walk(exit_partition, P, compact=cpt, compact_floor=floor)
    return EngineResult(labels, recircs, exit_partition, [], plan=plan)


def stream_batches(
    engine: Engine,
    batches: Iterable[np.ndarray],
    *,
    options: EngineOptions | None = None,
    micro_batch=_UNSET,
    mesh=_UNSET,
    impl=_UNSET,
    inflight=_UNSET,
    compact=_UNSET,
) -> Iterator[EngineResult]:
    """Open-stream form: one :class:`EngineResult` per incoming batch.

    Each batch is micro-batched independently, so producers can hand
    over whatever flow counts the capture pipeline emits; the compiled
    walk is shared across all of them as long as ``(p, W)`` match.
    """
    opt = _legacy_options(options, {
        "micro_batch": micro_batch, "mesh": mesh,
        "impl": impl, "inflight": inflight, "compact": compact})
    for batch in batches:
        yield run_streaming(engine, batch, options=opt)
