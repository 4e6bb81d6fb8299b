"""The served path's spans and counters as the chip benchmark reads them:
a small fused server ingests a few dozen ticks under the JAX profiler
(CPU, no Python tracer), the trace is reduced with ``trace_reduce`` and
handed to the per-layer readers this tree of spans feeds."""
import glob
import json
import os
import time

import jax
import numpy as np
import pytest

from benchmarks.chip import harness, trace_reduce
from repro import obs
from repro.core.inference import Engine, EngineOptions
from repro.flows.synthetic import make_packet_stream
from repro.serve import FlowTableServer

from chipbench_tiny import REPO

# span -> the span it lies in, for one fused ingest call
SPAN_TREE = {
    "tick/ingest": None,
    "tick/stamp": "tick/ingest",
    "tick/admit": "tick/ingest",
    "tick/admit/lookup": "tick/admit",
    "tick/admit/insert": "tick/admit",
    "tick/admit/rows": "tick/admit",
    "tick/pack": "tick/ingest",
    "tick/dispatch": "tick/ingest",
    "tick/dispatch/put": "tick/dispatch",
    "tick/dispatch/call": "tick/dispatch",
    "tick/fetch": "tick/ingest",
    "tick/fetch/wait": "tick/fetch",
    "tick/fetch/copy": "tick/fetch",
    "tick/evict": "tick/ingest",
    "tick/spill": "tick/ingest",
    "tick/timeout": "tick/ingest",
    "tick/finish": "tick/ingest",
}
LEAVES = sorted(set(SPAN_TREE) - set(SPAN_TREE.values()))
NEW_READERS = [
    "admit_lookup_ms_per_tick.sat", "admit_insert_ms_per_tick.sat",
    "admit_rows_ms_per_tick.sat", "dispatch_put_ms_per_tick.sat",
    "fetch_wait_ms_per_tick.sat", "fetch_copy_ms_per_tick.sat",
    "evict_ms_per_tick.sat", "outside_ingest_ms_per_tick.sat",
    "d2h_mb_per_tick.sat", "probes_per_insert.sat",
]
TICK_PKTS = 1024
SPEC = json.load(open(os.path.join(REPO, "BENCHMARK.json")))


@pytest.fixture(scope="module")
def traced(trained_pdt, tmp_path_factory):
    """A 2^12-slot fused server (a timeout set, so ``tick/timeout``
    opens) traced over every tick of a replayed stream but the first."""
    pdt, _, tr = trained_pdt
    srv = FlowTableServer(Engine.from_model(pdt), n_buckets=512,
                          bucket_size=8, tick_engine="fused", timeout=1e9,
                          options=EngineOptions(impl="fused"))
    ticks = list(make_packet_stream(tr, seed=31, profile="steady",
                                    concurrency=256).ticks(TICK_PKTS))
    srv.ingest(ticks[0])                     # compiles outside the trace
    st0 = srv.stats.as_dict()
    out = str(tmp_path_factory.mktemp("spans"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    prev = obs.set_enabled(True)
    try:
        jax.profiler.start_trace(out, profiler_options=opts)
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            for b in ticks[1:]:
                srv.ingest(b)
        jax.profiler.stop_trace()
    finally:
        obs.set_enabled(prev)
        obs.reset_spans()
    st1 = srv.stats.as_dict()
    path, = glob.glob(os.path.join(out, "**", "*.xplane.pb"),
                      recursive=True)
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    summ = trace_reduce.summarize_planes(pd.planes)
    ingest_args = [dict(ev.stats) for pl in pd.planes for line in pl.lines
                   for ev in line.events if ev.name == "tick/ingest"]
    return dict(summ=summ, n_ticks=len(ticks) - 1, N=srv.table.capacity,
                ingest_args=ingest_args,
                stats={k: st1[k] - st0[k] for k in st1})


def _by_name(summ):
    out = {}
    for name, a, b in summ.host_spans:
        out.setdefault(name, []).append((a, b))
    return {k: sorted(v) for k, v in out.items()}


def test_every_span_of_the_tree_is_named(traced):
    spans = _by_name(traced["summ"])
    assert set(spans) == set(SPAN_TREE)
    n = traced["n_ticks"]
    assert len(spans["tick/ingest"]) == n
    assert len(spans["tick/stamp"]) == 2 * n    # before and after admission


def test_children_nest_in_their_parent_and_never_overlap(traced):
    spans = _by_name(traced["summ"])
    children = {}
    for name, parent in SPAN_TREE.items():
        if parent is None:
            continue
        for a, b in spans[name]:
            inside = [p for p in spans[parent] if p[0] <= a and b <= p[1]]
            assert len(inside) == 1, (name, a, b)
            children.setdefault(inside[0], []).append((a, b, name))
    for parent, kids in children.items():
        kids.sort()
        for (_, b0, n0), (a1, _, n1) in zip(kids, kids[1:]):
            assert b0 <= a1, (n0, n1)


def test_leaf_phases_cover_the_ingest_call(traced):
    spans = _by_name(traced["summ"])
    total = sum(b - a for a, b in spans["tick/ingest"])
    leaves = sum(b - a for name in LEAVES for a, b in spans[name])
    assert 0.95 * total <= leaves <= total


def test_each_ingest_call_carries_its_tick(traced):
    ticks = [args["tick"] for args in traced["ingest_args"]]
    assert len(ticks) == traced["n_ticks"]
    assert len(set(ticks)) == len(ticks)


def _ctx(summ, ticks, stats):
    return {"trace": summ, "ticks": ticks, "stats": stats}


@pytest.mark.parametrize("metric", NEW_READERS)
def test_new_reader_reads_this_trace_and_nothing_else(traced, metric):
    """A number from the traced server; ``None``, and no error, from an
    empty context and from a program without these spans and counters."""
    read = harness.load_reader(REPO, metric)
    v = read(_ctx(traced["summ"], traced["n_ticks"], traced["stats"]))
    assert v is not None and np.isfinite(v) and v > 0
    assert read(_ctx(None, 0, {})) is None
    old_spans = trace_reduce.Summary(
        traced["summ"].window, traced["summ"].ops,
        [s for s in traced["summ"].host_spans
         if s[0] in ("tick/admit", "tick/pack", "tick/dispatch",
                     "tick/fetch", "tick/spill")])
    old_stats = {k: v for k, v in traced["stats"].items()
                 if k not in ("d2h_bytes", "insert_probes")}
    assert read(_ctx(old_spans, traced["n_ticks"], old_stats)) is None


def test_counter_readers_read_exact_counts(traced):
    ctx = _ctx(traced["summ"], traced["n_ticks"], traced["stats"])
    d2h = harness.load_reader(REPO, "d2h_mb_per_tick.sat")(ctx)
    assert d2h == 5 * 4 * traced["N"] / 1e6
    probes = harness.load_reader(REPO, "probes_per_insert.sat")(ctx)
    assert probes >= 1.0


def test_traced_harness_run_reads_every_host_metric(tiny_root):
    """Through the harness, as the chip benchmark runs it: every span and
    counter metric of the cell is read on the CPU."""
    cell = SPEC["workloads"][0]["name"]
    r = harness.run(tiny_root, cell, 2**31 + 4099, 1.0, True,
                    t_start=time.perf_counter(), require_tpu=False)
    assert r["correct"] is True
    host = {m["name"] for m in SPEC["per_layer"]
            if m["source"] in ("program_span", "program_counter")
            and cell in m.get("workloads", [cell])}
    assert set(NEW_READERS) <= host <= set(r["metrics"])
