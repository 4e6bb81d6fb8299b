#!/usr/bin/env python3
"""Bring-up check of the SpliDT serving path on a TPU.

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # four chips: the sharded stream only

One process drives the chip through the entry points a user calls, at
deployment size, on data generated from fixed seeds:

* model   — the d2 (2,3,2) k=4 model ``benchmarks/bench_serve.py``
  serves, trained on 32,768 flows with the numpy trainer and again with
  ``trainer="jax"`` on the chip; the two must agree node for node;
* batch   — ``Engine.run`` on 65,536 test flows with ``impl="fused"``,
  ``"pallas"`` and ``"pallas"`` + ``compact=True``, then
  ``Engine.run_streaming`` (pallas);
* serve   — ``FlowTableServer`` over a 2^20-slot table (131,072 buckets
  x 8), ``tick_engine="fused"``, ``impl`` fused and pallas: the 65,536
  test flows as one seeded packet stream (about 3.3 M packets) at
  concurrency 16,384, in 32,768-packet ticks, then ``flush()``;
* sharded — with ``--chips 4`` only: ``Engine.run_streaming`` over
  ``make_flow_mesh(4)`` against the same stream on one device.

Every verdict (label, recirculations, exit partition) must be
bit-identical to the numpy oracle ``PartitionedDT.predict(...,
return_trace=True)``; serving must spill nothing and leave no flow
unterminated; every Pallas program must hold its kernel
(``tpu_custom_call`` in the compiled program).  Times and counts printed
on the way are information, not metrics.  The last line of standard
output is ``{"ok": true, "device": {...}}``.  Without a TPU, or without
the rest of the repository beside it, the script exits non-zero before
any phase runs.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from typing import NamedTuple

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
PARTITIONS = (2, 3, 2)
K = 4
STREAM_SEED = 7


@dataclasses.dataclass(frozen=True)
class Sizes:
    n_flows: int        # dataset flows: a third trains, the rest is served
    n_buckets: int      # flow table: n_buckets * bucket_size slots
    bucket_size: int
    concurrency: float  # mean flows in flight in the packet stream
    tick: int           # packets per ingest call
    micro_batch: int    # run_streaming chunk (flows)


FULL = Sizes(n_flows=98_304, n_buckets=131_072, bucket_size=8,
             concurrency=16_384.0, tick=32_768, micro_batch=16_384)


def log(msg: str) -> None:
    print(msg, flush=True)


class Setup(NamedTuple):
    pdt: object          # repro.core.partition.PartitionedDT
    engine: object       # repro.core.inference.Engine
    test: object         # repro.flows.synthetic.FlowDataset (served flows)
    win_pkts: np.ndarray  # (n_test, P, W, F) windows of the served flows
    oracle: tuple        # (labels, recircs, exit_partition) from predict


def _same_model(a, b) -> None:
    if len(a.subtrees) != len(b.subtrees):
        raise AssertionError(
            f"trainers disagree: {len(a.subtrees)} vs {len(b.subtrees)} "
            "subtrees")
    for x, y in zip(a.subtrees, b.subtrees):
        ctx = f"subtree sid={x.sid}"
        if ((x.sid, x.partition) != (y.sid, y.partition)
                or x.leaf_next_sid != y.leaf_next_sid
                or x.leaf_label != y.leaf_label):
            raise AssertionError(f"{ctx}: routing differs between trainers")
        for name in ("feature", "threshold", "left", "right", "value"):
            np.testing.assert_array_equal(
                getattr(x.tree, name), getattr(y.tree, name),
                err_msg=f"{ctx}: Tree.{name} differs between trainers")


def _match(what: str, labels, recircs, exit_partition, oracle) -> None:
    """Bit-exact verdicts against the numpy oracle, or raise."""
    for name, got, want in zip(("labels", "recircs", "exit_partition"),
                               (labels, recircs, exit_partition), oracle):
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(want),
            err_msg=f"{what}: {name} differ from PartitionedDT.predict")
    log(f"{what}: {len(oracle[0])} verdicts bit-identical to the oracle")


def phase_model(sizes: Sizes, *, jax_trainer: bool = True) -> Setup:
    """Train the served model (and, with ``jax_trainer``, check the
    jitted trainer against it); build the oracle's verdicts."""
    from repro.core.inference import Engine
    from repro.core.partition import train_partitioned_dt
    from repro.flows.synthetic import make_dataset
    from repro.flows.windows import window_features, window_packets

    t0 = time.perf_counter()
    ds = make_dataset("d2", n_flows=sizes.n_flows)
    train, test = ds.split(frac=1 / 3)
    P = len(PARTITIONS)
    X_train = window_features(train, P)
    kw = dict(partition_sizes=list(PARTITIONS), k=K, n_classes=ds.n_classes)
    pdt = train_partitioned_dt(X_train, train.labels, **kw)
    log(f"model: d2 {PARTITIONS} k={K}, {len(pdt.subtrees)} subtrees, "
        f"{train.n_flows} training flows ({time.perf_counter() - t0:.1f} s)")
    if jax_trainer:
        t0 = time.perf_counter()
        _same_model(pdt, train_partitioned_dt(X_train, train.labels,
                                              trainer="jax", **kw))
        log(f"model: trainer='jax' node-for-node identical to the numpy "
            f"trainer ({time.perf_counter() - t0:.1f} s)")
    oracle = pdt.predict(window_features(test, P), return_trace=True)
    accuracy = float(np.mean(oracle[0] == test.labels))
    log(f"oracle: {test.n_flows} test flows, accuracy {accuracy:.4f}")
    return Setup(pdt, Engine.from_model(pdt), test, window_packets(test, P),
                 tuple(np.asarray(a) for a in oracle))


def phase_batch(setup: Setup, sizes: Sizes) -> None:
    """The batch walk and the streaming scheduler against the oracle."""
    from repro.core.inference import EngineOptions

    eng = setup.engine
    for name, opt in (("fused", EngineOptions(impl="fused")),
                      ("pallas", EngineOptions(impl="pallas")),
                      ("pallas+compact",
                       EngineOptions(impl="pallas", compact=True))):
        t0 = time.perf_counter()
        res = eng.run(setup.win_pkts, with_trace=False, options=opt)
        _match(f"Engine.run[{name}]", res.labels, res.recircs,
               res.exit_partition, setup.oracle)
        log(f"  first call incl. compile {time.perf_counter() - t0:.1f} s")
    opt = EngineOptions(impl="pallas", micro_batch=sizes.micro_batch)
    t0 = time.perf_counter()
    res = eng.run_streaming(setup.win_pkts, options=opt)
    _match("Engine.run_streaming[pallas]", res.labels, res.recircs,
           res.exit_partition, setup.oracle)
    log(f"  first call incl. compile {time.perf_counter() - t0:.1f} s")


def phase_serve(setup: Setup, sizes: Sizes) -> None:
    """Live serving through the 2^20-slot flow table, fused and pallas."""
    from repro.core.inference import EngineOptions
    from repro.flows.synthetic import make_packet_stream
    from repro.serve import FlowTableServer, StreamVerdicts
    from repro.tuning import ShapeInfo, choose_tick_engine, get_plan

    eng = setup.engine
    stream = make_packet_stream(setup.test, seed=STREAM_SEED,
                                profile="steady",
                                concurrency=sizes.concurrency)
    capacity = sizes.n_buckets * sizes.bucket_size
    log(f"serve: {stream.n_flows} flows, {stream.n_packets} packets, "
        f"{capacity} slots, ticks of {sizes.tick} packets")
    # what impl="auto" / tick_engine="auto" would pick for this table
    # (the TPU rows of the cost model are estimates, so both are pinned)
    shape = ShapeInfo.from_engine(eng, None, B=capacity, W=1)
    plan = get_plan(eng, None, impl="auto", shape=shape,
                    backends=("fused", "pallas"), compact=False)
    log(f"serve: auto would pick impl={plan.backend} block_b={plan.block_b} "
        f"tick_engine={choose_tick_engine(shape, backend=plan.backend, block_b=plan.block_b)}")
    order_want = np.arange(stream.n_flows)
    for impl in ("fused", "pallas"):
        srv = FlowTableServer(eng, n_buckets=sizes.n_buckets,
                              bucket_size=sizes.bucket_size,
                              tick_engine="fused",
                              options=EngineOptions(impl=impl))
        t0 = time.perf_counter()
        parts = [srv.ingest(batch) for batch in stream.ticks(sizes.tick)]
        parts.append(srv.flush())
        wall = time.perf_counter() - t0
        v = StreamVerdicts.concat(parts)
        order = np.argsort(v.flow_id, kind="stable")
        np.testing.assert_array_equal(
            v.flow_id[order], order_want,
            err_msg=f"serve[{impl}]: not exactly one verdict per flow")
        st = srv.stats
        if v.n_unterminated or st.spilled or st.evicted:
            raise AssertionError(
                f"serve[{impl}]: {v.n_unterminated} unterminated, "
                f"{st.spilled} spilled, {st.evicted} evicted (want 0)")
        _match(f"FlowTableServer[{impl}]", v.labels[order], v.recircs[order],
               v.exit_partition[order], setup.oracle)
        log(f"  ticks={st.ticks} dispatches/tick={st.dispatches / st.ticks:.3f} "
            f"peak_resident={st.peak_resident} wall incl. compile "
            f"{wall:.1f} s")


def pallas_programs(setup: Setup, sizes: Sizes) -> dict:
    """Compiled text of every Pallas program the one-chip phases ran,
    keyed by the entry point that dispatches it."""
    import jax

    from repro.core.inference import PALLAS_BACKEND, partition_walk
    from repro.core.features import PKT_NFIELDS
    from repro.kernels import tick_step
    from repro.kernels.ops import BLOCK_B

    eng = setup.engine
    S, P = eng.ret.n_subtrees, eng.tables.n_partitions
    f32 = np.float32

    def windows(n):
        return jax.ShapeDtypeStruct((n,) + setup.win_pkts[:, :P].shape[1:],
                                    f32)

    walk = dict(n_subtrees=S, with_trace=False, step=PALLAS_BACKEND.step)
    lowered = {
        "Engine.run[pallas]": partition_walk.lower(
            windows(setup.test.n_flows), eng.dev, compact=False, **walk),
        "Engine.run[pallas+compact]": partition_walk.lower(
            windows(setup.test.n_flows), eng.dev, compact=True, **walk),
        "Engine.run_streaming[pallas]": partition_walk.lower(
            windows(sizes.micro_batch), eng.dev, **walk),
    }
    n = sizes.n_buckets * sizes.bucket_size + 1
    state = jax.eval_shape(tick_step.init_tick_state, eng.dev, n, P)
    # the tick shape most of the stream packs into: 16 packet ranks over
    # a column per flow in the tick
    ranks, cols = 16, sizes.tick // 2
    lowered["FlowTableServer[pallas] tick"] = tick_step.tick_step.lower(
        state, jax.ShapeDtypeStruct((ranks, cols), np.int32),
        jax.ShapeDtypeStruct((ranks, cols, PKT_NFIELDS), f32), eng.dev,
        n_subtrees=S, pallas=True, block_b=BLOCK_B)
    return {name: low.compile().as_text() for name, low in lowered.items()}


def phase_sharded(setup: Setup, sizes: Sizes, n_devices: int) -> dict:
    """The streaming walk sharded over a ``n_devices`` flow mesh against
    the same stream on one device; returns the sharded program's text."""
    import jax
    from jax.sharding import NamedSharding

    from repro.core.inference import PALLAS_BACKEND, EngineOptions
    from repro.distributed.sharding import flow_batch_spec
    from repro.launch.mesh import make_flow_mesh
    from repro.serve.streaming import _sharded_walk

    eng = setup.engine
    mesh = make_flow_mesh(n_devices)
    opt = EngineOptions(impl="pallas", micro_batch=sizes.micro_batch)
    single = eng.run_streaming(setup.win_pkts, options=opt)
    _match("Engine.run_streaming[pallas, 1 device]", single.labels,
           single.recircs, single.exit_partition, setup.oracle)
    t0 = time.perf_counter()
    sharded = eng.run_streaming(setup.win_pkts, options=opt.replace(mesh=mesh))
    _match(f"Engine.run_streaming[pallas, mesh of {n_devices}]",
           sharded.labels, sharded.recircs, sharded.exit_partition,
           setup.oracle)
    log(f"  first call incl. compile {time.perf_counter() - t0:.1f} s")
    P = eng.tables.n_partitions
    chunk = jax.ShapeDtypeStruct(
        (sizes.micro_batch,) + setup.win_pkts[:, :P].shape[1:], np.float32,
        sharding=NamedSharding(mesh, flow_batch_spec(mesh)))
    walk = _sharded_walk(mesh, eng.ret.n_subtrees, PALLAS_BACKEND.step)
    return {f"Engine.run_streaming[pallas, mesh of {n_devices}]":
            walk.lower(chunk, eng.dev).compile().as_text()}


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------
def require_tpu(n_chips: int) -> dict:
    """The device as JAX reports it; exits non-zero off the chip."""
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU: jax.devices()[0] is {dev.platform!r} "
                 f"({dev.device_kind}); this check runs on the chip only")
    if len(devices) < n_chips:
        sys.exit(f"chip_smoke: --chips {n_chips} needs {n_chips} devices, "
                 f"JAX sees {len(devices)}")
    log(f"device_kind={dev.device_kind} count={len(devices)} "
        f"jax={jax.__version__}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def import_repro() -> None:
    """Import the package from ``src/`` beside this script, or raise."""
    src = os.path.join(ROOT, "src")
    sys.path.insert(0, src)
    import repro
    if os.path.dirname(os.path.abspath(repro.__file__)) != os.path.join(
            src, "repro"):
        raise ImportError(f"repro came from {repro.__file__}, not {src}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded stream and what it is "
                         "compared with")
    args = ap.parse_args()
    device = require_tpu(args.chips)
    import_repro()
    from repro.compile_cache import use_compile_cache
    log(f"compile cache: {use_compile_cache(ROOT)}")

    t_start = time.perf_counter()
    sizes = FULL
    if args.chips == 1:
        setup = phase_model(sizes)
        phase_batch(setup, sizes)
        phase_serve(setup, sizes)
        programs = pallas_programs(setup, sizes)
    else:
        setup = phase_model(sizes, jax_trainer=False)
        programs = phase_sharded(setup, sizes, args.chips)
    for name, text in programs.items():
        if "tpu_custom_call" not in text:
            raise AssertionError(f"{name}: no Pallas kernel in the compiled "
                                 "program (interpreted?)")
        log(f"{name}: {text.count('tpu_custom_call')} tpu_custom_call")
    log(f"all phases passed in {time.perf_counter() - t_start:.1f} s")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
