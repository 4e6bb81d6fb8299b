"""Engine throughput across execution backends + streaming schedulers.

Rows (flows/sec):
  * ``engine/looped``   — per-partition host sync (baseline)
  * ``engine/fused``    — single jitted scan, dense jnp step
  * ``engine/pallas``   — same scan, Pallas kernels + in-jit SID
                          dispatch (interpret mode off-TPU, so absolute
                          numbers are only meaningful on TPU; the row is
                          a correctness-path smoke signal elsewhere)
  * ``engine/streaming``          — fused walk over fixed micro-batches
  * ``engine/streaming_sharded``  — same, shard_map'd over all devices
                                    (emitted when >1 device is visible,
                                    e.g. XLA_FLAGS=--xla_force_host_
                                    platform_device_count=8; on a
                                    single-device mesh the speedup
                                    fields are null — a speedup vs
                                    itself is meaningless)
  * ``engine/fused@B=...``        — batch-size sweep of the fused walk
  * ``engine/compact/<profile>/<backend>`` — early-exit compaction
    (``compact=True``) vs the dense walk, on the three exit-rate
    profile workloads (front / uniform / back-loaded; see
    ``flows.synthetic.make_profile_dataset``); ``speedup_vs_dense`` and
    the realised per-partition ``exit_frac`` land in the JSON
  * ``engine/auto/<S>/<B>/<profile>`` — cost-model routing
    (``impl="auto"``, ``repro.tuning``) over the (small-S, large-S) x
    (small-B, large-B) x exit-profile grid: each cell times the forced
    backends AND the auto route, records the chosen plan, the
    measured-best fixed backend, ``auto_vs_best`` (>= ~1.0 within
    noise means the router did its job), and the cost-model estimate
    per backend (``est``) so crossover points are readable straight
    from the JSON.  Off-TPU the pallas column is interpret mode and
    only measured at small B (compile cost unrolls with the grid);
    the cost model knows this and routes around it.  The S axis labels
    the *requested* partition depths (``ps``); realized ``S`` is
    data-dependent and recorded per row — at full dataset sizes the
    largeS config reaches S≈25-33 on uniform/back workloads, while
    FRONT-loaded profiles inherently collapse to S≈1-2 regardless of
    depth (nearly every flow exits in partition 0, so later partitions
    retain no subtrees; read those cells by their recorded ``S``, not
    the label).
  * ``engine/tuned`` — the cached empirical autotuner
    (``impl="tuned"``): cold-call latency (probe + persist), warm
    cached-hit throughput, the winning plan, and a bit-exactness check
    against the backend it routed to

Besides the CSV rows, results are dumped to ``BENCH_engine.json``
(override with the BENCH_ENGINE_JSON env var) so the perf trajectory is
tracked across PRs; CI uploads the smoke run as a workflow artifact.

Note on sharded speedup: the walk is embarrassingly parallel over
flows, so sharded/single tracks the number of physical cores XLA's
single-device intra-op parallelism leaves idle.  On a 2-core container
the single-device walk already saturates the socket and the ratio is
~1.1x; on hosts with >= 8 cores (or real multi-accelerator meshes) it
exceeds 1.5x.  ``cpu_count`` lands in the JSON for exactly this reason.
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

from benchmarks.common import (
    Row, dataset, profile_dataset, profile_model, splidt_model, timed,
    timed_min,
)
from repro.core.inference import Engine, EngineOptions
from repro.flows.synthetic import EXIT_PROFILES
from repro.flows.windows import window_packets
from repro.serve.streaming import run_streaming

JSON_PATH_ENV = "BENCH_ENGINE_JSON"
DEFAULT_JSON_PATH = "BENCH_engine.json"
METRICS_PATH_ENV = "METRICS_ENGINE_JSON"
DEFAULT_METRICS_PATH = "METRICS_engine.json"


def _tiled_windows(te, p: int, n_flows: int) -> np.ndarray:
    """Tile the test split's window tensor up to ``n_flows`` flows."""
    wp = window_packets(te, p)
    reps = -(-n_flows // wp.shape[0])
    return np.tile(wp, (reps, 1, 1, 1))[:n_flows]


def _write_json(results: list[dict], mode: str) -> str:
    import jax
    path = os.environ.get(JSON_PATH_ENV, DEFAULT_JSON_PATH)
    payload = {
        "bench": "engine",
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


def _write_metrics(mode: str) -> str:
    """Snapshot the engine-side observability registry accumulated over
    the whole bench run (per-hop survivors, compaction bucket occupancy,
    dispatch counts — see ``docs/OBSERVABILITY.md``)."""
    from repro import obs
    path = os.environ.get(METRICS_PATH_ENV, DEFAULT_METRICS_PATH)
    with open(path, "w") as f:
        json.dump({"bench": "engine", "mode": mode,
                   "registry": obs.get_registry().snapshot()}, f, indent=2)
        f.write("\n")
    return path


def run(quick: bool = True, smoke: bool = False):
    import jax
    from repro import obs

    # fresh registry: the artifact carries exactly this run's walks
    obs.set_registry(obs.MetricRegistry())

    rows: list[Row] = []
    results: list[dict] = []

    def add(name: str, us: float, B: int, **extra):
        flows_per_s = B / (us / 1e6)
        derived = f"flows_per_s={flows_per_s:.0f};B={B}"
        for key, val in extra.items():
            derived += f";{key}={val}"
        rows.append(Row(name, us, derived))
        results.append({"name": name, "us_per_call": round(us, 1),
                        "flows_per_s": round(flows_per_s), "B": B, **extra})

    name, p, k = "d2", 3, 4
    # smoke: small dataset; otherwise the DEFAULT n_flows so the
    # lru_cache hit is shared with the other bench modules
    if smoke:
        _, _, te = dataset(name, n_flows=400)
        pdt = splidt_model(name, (3,) * p, k, n_flows=400)
    else:
        _, _, te = dataset(name)
        pdt = splidt_model(name, (3,) * p, k)
    eng = Engine.from_model(pdt, impl="ref")

    B = 256 if smoke else (10_000 if quick else 100_000)
    wp = _tiled_windows(te, p, B)
    repeat = 1 if smoke else 3

    _, us_loop = timed(lambda: eng.run_looped(wp, with_trace=False),
                       repeat=repeat)
    add("engine/looped", us_loop, B)

    _, us_fused = timed(lambda: eng.run(wp, with_trace=False), repeat=repeat)
    add("engine/fused", us_fused, B, speedup_vs_looped=round(
        us_loop / us_fused, 2))

    # pallas walk: interpret mode off-TPU unrolls the grid at trace time,
    # so cap the batch to keep compile time sane on CPU
    Bp = min(B, 256 if smoke else 2048)
    wpp = wp[:Bp]
    _, us_pal = timed(lambda: eng.run(wpp, with_trace=False,
                            options=EngineOptions(impl="pallas")),
                      repeat=repeat)
    add("engine/pallas", us_pal, Bp, interpret=int(
        jax.default_backend() != "tpu"))

    mb = 128 if smoke else 4096
    _, us_stream = timed(
        lambda: run_streaming(eng, wp, options=EngineOptions(micro_batch=mb)), repeat=repeat)
    add("engine/streaming", us_stream, B, micro_batch=mb)

    from repro.serve.mesh import flow_batch_devices, make_flow_mesh
    mesh = make_flow_mesh()
    n_mesh = flow_batch_devices(mesh)
    # the sharded path prefers a larger micro-batch (each chunk
    # splits n_devices ways, so per-device slices stay cache-resident
    # where a single device's working set would spill); measure the
    # single-device baseline at BOTH sizes and report the speedup
    # against the best single-device config, so the tracked metric
    # can't flatter sharding by picking a degraded baseline
    mb_s = mb if smoke else 8192
    us_base = us_stream
    if mb_s != mb:
        _, us_base = timed(
            lambda: run_streaming(eng, wp,
                                  options=EngineOptions(micro_batch=mb_s)),
            repeat=repeat)
        add(f"engine/streaming@mb={mb_s}", us_base, B, micro_batch=mb_s)
    _, us_shard = timed(
        lambda: run_streaming(eng, wp, options=EngineOptions(
            micro_batch=mb_s, mesh=mesh)),
        repeat=repeat)
    # a 1-device mesh shards against itself: the "speedup" would be pure
    # timer noise around 1.0, so record null rather than a number
    # downstream dashboards would read as signal
    add("engine/streaming_sharded", us_shard, B, micro_batch=mb_s,
        n_devices=n_mesh,
        speedup_vs_single=(
            None if n_mesh < 2
            else round(min(us_stream, us_base) / us_shard, 2)),
        speedup_vs_single_same_mb=(
            None if n_mesh < 2 else round(us_base / us_shard, 2)))

    # batch sweep: how the fused walk's flows/sec scales with B
    sweep = [256] if smoke else ([1_000, 10_000] if quick
                                 else [10_000, 100_000])
    for Bs in sweep:
        wps = wp[:Bs] if Bs <= B else _tiled_windows(te, p, Bs)
        _, us = timed(lambda: eng.run(wps, with_trace=False), repeat=repeat)
        add(f"engine/fused@B={Bs}", us, Bs)

    # ------------------------------------------------------------------
    # early-exit compaction: exit-rate profile x walk backend
    # ------------------------------------------------------------------
    # Compaction's payoff is entirely a function of WHEN flows exit, so
    # it is measured on the three profile workloads rather than the d2
    # model above (whose exits cluster in the later partitions).  The
    # dense (compact=False) run of the SAME model/windows is the
    # baseline; `exit_frac` records the realised per-partition exit
    # rates so the speedup can be read against the workload shape.
    # Caveat (see module docstring on pallas): off-TPU the pallas rows
    # run in interpret mode — smoke-signal only.
    n_prof = 400 if smoke else 2500
    Bc = 256 if smoke else (20_000 if quick else 50_000)
    Bcp = 256 if smoke else 1024          # pallas interpret-mode cap
    for profile in EXIT_PROFILES:
        pdt_c = profile_model(profile, n_prof)
        _, te_c = profile_dataset(profile, n_prof).split()
        wp_c = _tiled_windows(te_c, 3, Bc)
        eng_c = Engine.from_model(pdt_c, impl="ref")
        dense, us_dense = timed(lambda: eng_c.run(wp_c, with_trace=False),
                                repeat=repeat)
        exit_frac = [round(float(np.mean(dense.exit_partition == q)), 3)
                     for q in range(pdt_c.n_partitions)]
        add(f"engine/compact/{profile}/dense", us_dense, Bc,
            exit_frac=exit_frac)
        _, us_comp = timed(
            lambda: eng_c.run(wp_c, with_trace=False,
                              options=EngineOptions(compact=True)),
            repeat=repeat)
        add(f"engine/compact/{profile}/fused", us_comp, Bc,
            exit_frac=exit_frac,
            speedup_vs_dense=round(us_dense / us_comp, 2))
        # pallas rows run a smaller slice (interpret-mode compile cost),
        # so their exit_frac is recomputed on that slice; the dense
        # pallas baseline is emitted too, otherwise the tracked speedup
        # ratio could stay flat while both sides regress
        wp_cp = wp_c[:Bcp]
        interp = int(jax.default_backend() != "tpu")
        pd_res, us_pd = timed(
            lambda: eng_c.run(wp_cp, with_trace=False,
                              options=EngineOptions(impl="pallas")),
            repeat=repeat)
        exit_frac_p = [round(float(np.mean(pd_res.exit_partition == q)), 3)
                       for q in range(pdt_c.n_partitions)]
        add(f"engine/compact/{profile}/pallas_dense", us_pd, Bcp,
            exit_frac=exit_frac_p, interpret=interp)
        _, us_pc = timed(
            lambda: eng_c.run(wp_cp, with_trace=False,
                              options=EngineOptions(impl="pallas",
                                                    compact=True)),
            repeat=repeat)
        add(f"engine/compact/{profile}/pallas", us_pc, Bcp,
            exit_frac=exit_frac_p, interpret=interp,
            speedup_vs_dense=round(us_pd / us_pc, 2))

    # ------------------------------------------------------------------
    # cost-model auto-routing: (S, B, profile) grid
    # ------------------------------------------------------------------
    # The acceptance bar for impl="auto": beat or match the best FIXED
    # backend within benchmark noise in every cell.  Forced rows are
    # measured in the same process right before the auto row, so cache
    # warmth is identical; `auto_vs_best` is best_fixed_us / auto_us
    # (>= 1.0 means auto won; ~0.6+ is within this box's noise band).
    from repro.tuning import Plan, ShapeInfo, estimate_us

    on_tpu = jax.default_backend() == "tpu"
    Bs_small = 256 if smoke else 512
    Bs_large = 512 if smoke else (8192 if quick else 32768)
    pallas_cap = Bs_small if not on_tpu else Bs_large
    for S_name, ps in (("smallS", (2, 2, 2)), ("largeS", (4, 4, 4))):
        for profile in EXIT_PROFILES:
            pdt_a = profile_model(profile, n_prof, ps=ps)
            _, te_a = profile_dataset(profile, n_prof).split()
            for B_name, Bv in (("smallB", Bs_small), ("largeB", Bs_large)):
                wp_a = _tiled_windows(te_a, len(ps), Bv)
                eng_a = Engine.from_model(pdt_a)
                # auto_vs_best is the tracked acceptance metric, so
                # every entry in `fixed` uses the SAME estimator
                # (common.timed_min), with the fused/auto pair
                # additionally interleaved (A/B/A/B) so load drift
                # between their timing windows cancels
                rounds = max(repeat, 2)
                fixed: dict[str, float] = {}
                run_fused = lambda: eng_a.run(
                    wp_a, with_trace=False,
                    options=EngineOptions(impl="fused"))
                run_auto = lambda: eng_a.run(
                    wp_a, with_trace=False,
                    options=EngineOptions(impl="auto"))
                res_a = run_auto()                       # warm both paths
                run_fused()
                t_f, t_a = [], []
                for _ in range(rounds):
                    t0 = time.perf_counter(); run_fused()
                    t_f.append((time.perf_counter() - t0) * 1e6)
                    t0 = time.perf_counter(); run_auto()
                    t_a.append((time.perf_counter() - t0) * 1e6)
                fixed["fused"], us_auto = min(t_f), min(t_a)
                if Bv <= pallas_cap:
                    fixed["pallas"] = timed_min(
                        lambda: eng_a.run(
                            wp_a, with_trace=False,
                            options=EngineOptions(impl="pallas")),
                        rounds=rounds)
                if B_name == "smallB":      # host-sync path: too slow to
                    fixed["looped"] = timed_min(   # time at large B
                        lambda: eng_a.run_looped(wp_a, with_trace=False),
                        rounds=rounds)
                shape = ShapeInfo.from_engine(eng_a, wp_a)
                est = {b: round(estimate_us(shape, Plan(backend=b)))
                       for b in ("looped", "fused", "pallas")}
                best = min(fixed, key=fixed.get)
                add(f"engine/auto/{S_name}/{B_name}/{profile}", us_auto, Bv,
                    S=shape.S, ps=list(ps), chosen=res_a.plan.backend,
                    chosen_block_b=res_a.plan.block_b,
                    best_fixed=best,
                    auto_vs_best=round(fixed[best] / us_auto, 2),
                    fixed_us={b: round(v, 1) for b, v in fixed.items()},
                    est=est)

    # ------------------------------------------------------------------
    # cached empirical autotuner (impl="tuned")
    # ------------------------------------------------------------------
    import tempfile

    from repro.tuning.autotune import CACHE_ENV

    with tempfile.TemporaryDirectory() as td:
        tune_path = os.path.join(td, "autotune.json")
        old = os.environ.get(CACHE_ENV)
        os.environ[CACHE_ENV] = tune_path
        try:
            Bt = 256 if smoke else 4096
            wpt = wp[:Bt]
            t0 = time.perf_counter()
            cold = eng.run(wpt, with_trace=False,
                           options=EngineOptions(impl="tuned"))
            cold_us = (time.perf_counter() - t0) * 1e6
            _, us_tuned = timed(
                lambda: eng.run(wpt, with_trace=False,
                                options=EngineOptions(impl="tuned")),
                repeat=repeat)
            warm = eng.run(wpt, with_trace=False,
                           options=EngineOptions(impl="tuned"))
            # tuned must be bit-identical to the backend it routed to
            forced = eng.run(wpt, with_trace=False,
                             options=EngineOptions(impl=warm.plan.backend))
            exact = bool(
                np.array_equal(warm.labels, forced.labels)
                and np.array_equal(warm.recircs, forced.recircs)
                and np.array_equal(warm.exit_partition,
                                   forced.exit_partition))
            add("engine/tuned", us_tuned, Bt,
                plan=warm.plan.describe(), source=warm.plan.source,
                cold_call_us=round(cold_us, 1),
                bit_identical_to_routed=exact,
                cold_source=cold.plan.source)
        finally:
            if old is None:
                os.environ.pop(CACHE_ENV, None)
            else:
                os.environ[CACHE_ENV] = old

    mode = "smoke" if smoke else ("quick" if quick else "full")
    path = _write_json(results, mode)
    mpath = _write_metrics(mode)
    import sys
    print(f"# bench_engine: wrote {path}", file=sys.stderr)
    print(f"# bench_engine: wrote {mpath}", file=sys.stderr)
    return rows
