"""Live serving throughput: the flow-table server on replayed streams.

The batch engine answers "how fast can we score windows we already
have"; this answers the deployment question — sustained packets/sec
through :class:`repro.serve.FlowTableServer` with verdicts emitted
in-stream.  Rows are written to ``BENCH_serve.json`` (override with the
BENCH_SERVE_JSON env var) alongside the CSV, one per
``<profile>/<impl>/t<tick>`` cell:

* ``pkts_per_s`` — sustained ingest throughput over the whole replay
  (all ticks + flush, steady-state: jit warm-up excluded by a priming
  replay on a stream prefix);
* ``verdict_p50_ms`` / ``verdict_p99_ms`` — per-verdict serving
  latency.  A verdict's latency is the wall time of the ingest call
  that emitted it: the time the caller waited on the serving step for
  that answer (arrival-queueing time is a property of the replayed
  trace, not of the server, so it is excluded on purpose);
* ``dispatches_per_tick`` — jitted device calls per ingest tick.  The
  tick engine's contract is O(1) (admission + tick step).  Box timings
  are noisy — this is the deterministic column;
* ``max_resident_flows`` — peak concurrent flows held (table slots +
  host spill), the memory high-water mark;
* ``spilled`` / ``evicted`` — how often the hash table overflowed to
  the host and how many flows timed out mid-stream.

The fused walk backend sweeps tick sizes (64/256/1024) — bigger ticks
amortise the fixed two dispatches over more packets, which is the whole
perf story on dispatch-bound hosts.  Both arrival profiles (``steady``,
``bursty``) run so the tail latency rows capture burst behaviour, not
just the uniform-arrival best case.  Alongside the timing rows, each
timed cell's full ``MetricRegistry.snapshot()`` (TTD histogram, recirc
overhead, dispatch counters — see ``docs/OBSERVABILITY.md``) lands in
``METRICS_serve.json`` (override: METRICS_SERVE_JSON env var), schema-
checked in CI by ``tools/check_metrics.py``.  Verdict parity is not
re-checked
here — ``tests/test_flowtable.py`` and ``tests/test_tick_engine.py``
hold every cell bit-identical to the batch walk."""
from __future__ import annotations

import json
import os
import time

import numpy as np

from benchmarks.common import Row, dataset, splidt_model

JSON_PATH_ENV = "BENCH_SERVE_JSON"
DEFAULT_JSON_PATH = "BENCH_serve.json"
METRICS_PATH_ENV = "METRICS_SERVE_JSON"
DEFAULT_METRICS_PATH = "METRICS_serve.json"

P = 3
TICK_SWEEP = (64, 256, 1024)


def _write_json(results: list[dict], mode: str) -> str:
    import jax
    path = os.environ.get(JSON_PATH_ENV, DEFAULT_JSON_PATH)
    payload = {
        "bench": "serve",
        "mode": mode,
        "jax_backend": jax.default_backend(),
        "n_devices": len(jax.devices()),
        "cpu_count": os.cpu_count(),
        "results": results,
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    return path


def _write_metrics(cells: dict, mode: str) -> str:
    """One ``MetricRegistry.snapshot()`` per timed grid cell — the
    observability artifact next to the timing rows.  CI schema-checks
    it (``tools/check_metrics.py``): every cell must carry the TTD
    histogram, the recirc-overhead gauge, and the dispatch counter."""
    path = os.environ.get(METRICS_PATH_ENV, DEFAULT_METRICS_PATH)
    with open(path, "w") as f:
        json.dump({"bench": "serve", "mode": mode, "cells": cells}, f,
                  indent=2)
        f.write("\n")
    return path


def _replay(make_server, stream, tick: int):
    """Replay the stream; return (seconds, verdict latencies, server)."""
    srv = make_server()
    lat: list[float] = []
    t_total = 0.0
    for batch in stream.ticks(tick):
        t0 = time.perf_counter()
        v = srv.ingest(batch)
        dt = time.perf_counter() - t0
        t_total += dt
        lat.extend([dt] * v.n_flows)
    t0 = time.perf_counter()
    v = srv.flush()
    dt = time.perf_counter() - t0
    t_total += dt
    lat.extend([dt] * v.n_flows)
    return t_total, np.asarray(lat), srv


def run(quick: bool = True, smoke: bool = False):
    from repro.core.inference import Engine, EngineOptions
    from repro.flows.synthetic import ARRIVAL_PROFILES, make_packet_stream
    from repro.serve import FlowTableServer

    if smoke:
        n_flows, base_tick, buckets = 96, 64, 8
    elif quick:
        n_flows, base_tick, buckets = 1200, 256, 32
    else:
        n_flows, base_tick, buckets = 4000, 1024, 64

    pdt = splidt_model("d2", (2, 3, 2), 4, n_flows=n_flows)
    eng = Engine.from_model(pdt)
    _, tr, _ = dataset("d2", n_flows)

    rows: list[Row] = []
    results: list[dict] = []
    metrics_cells: dict[str, dict] = {}
    impls = ("fused", "pallas")
    # grid: the fused walk backend sweeps tick sizes; pallas runs at
    # the base tick only
    sweep = (base_tick, 4 * base_tick) if smoke else TICK_SWEEP
    for profile in ARRIVAL_PROFILES:
        stream = make_packet_stream(tr, seed=7, profile=profile)
        for impl in impls:
            ticks = sweep if impl == "fused" else (base_tick,)
            for tick in ticks:
                def make_server(impl=impl):
                    return FlowTableServer(
                        eng, n_buckets=buckets, bucket_size=8,
                        options=EngineOptions(impl=impl))
                # prime jit caches with an untimed replay so the timed
                # pass is steady-state — the capacity ladder keeps the
                # (rank, width) shapes shared, but only a full pass
                # visits the deep rank chains late in the stream
                _replay(make_server, stream, tick)

                secs, lat, srv = _replay(make_server, stream, tick)
                stats = srv.stats
                pkts_s = stats.packets / secs if secs > 0 else float("inf")
                p50 = float(np.percentile(lat, 50) * 1e3)
                p99 = float(np.percentile(lat, 99) * 1e3)
                dpt = stats.dispatches / max(stats.ticks, 1)
                name = f"serve/{profile}/{impl}/t{tick}"
                metrics_cells[name] = srv.registry.snapshot()
                rows.append(Row(
                    name, secs / max(stats.verdicts, 1) * 1e6,
                    f"pkts_per_s={pkts_s:.0f};p50_ms={p50:.2f};"
                    f"p99_ms={p99:.2f};disp_per_tick={dpt:.2f};"
                    f"peak_resident={stats.peak_resident}"))
                results.append({
                    "name": name,
                    "profile": profile,
                    "impl": impl,
                    "n_flows": stats.flows_seen,
                    "n_packets": stats.packets,
                    "tick": tick,
                    "pkts_per_s": round(pkts_s, 1),
                    "verdict_p50_ms": round(p50, 3),
                    "verdict_p99_ms": round(p99, 3),
                    "dispatches_per_tick": round(dpt, 3),
                    "max_resident_flows": stats.peak_resident,
                    "spilled": stats.spilled,
                    "evicted": stats.evicted,
                })
    mode = "smoke" if smoke else ("quick" if quick else "full")
    path = _write_json(results, mode)
    rows.append(Row("serve/json", 0.0, f"path={path};rows={len(results)}"))
    mpath = _write_metrics(metrics_cells, mode)
    rows.append(Row("serve/metrics", 0.0,
                    f"path={mpath};cells={len(metrics_cells)}"))
    return rows


if __name__ == "__main__":
    import sys
    for row in run(quick="--full" not in sys.argv,
                   smoke="--smoke" in sys.argv):
        print(row.csv())
