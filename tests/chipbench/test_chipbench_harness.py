"""CPU rehearsal of the chip benchmark: every cell end to end at a tiny
size (JAX on the CPU, Pallas interpreted), the result line's schema, the
check failing on planted faults, the chip entry refusing the CPU, and a
cell, traffic mix and metric added as files only."""
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from benchmarks.chip import harness

from chipbench_tiny import BENCH, REPO, make_root

SPEC = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
CELLS = [w["name"] for w in SPEC["workloads"]]
SEED = 2**31 + 4099


def _run(root, cell, trace=False, seconds=1.0, **kw):
    return harness.run(root, cell, SEED, seconds, trace,
                       t_start=time.perf_counter(), require_tpu=False, **kw)


def _schema(r, cell, trace):
    assert set(r) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert list(r)[-1] == "check"
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    dev = r["device"]
    assert dev["platform"] == "cpu" and dev["count"] == 1
    assert isinstance(dev["memory_peak_bytes"], int)
    units = {m["name"]: m["unit"] for m in
             SPEC["end_to_end"] + SPEC["per_layer"]}
    for name, m in r["metrics"].items():
        assert m["unit"] == units[name]
        assert np.isfinite(m["value"])
    if not trace:
        want = {m["name"] for m in SPEC["end_to_end"]
                if cell in m.get("workloads", [cell])}
        assert set(r["metrics"]) == want
        assert all(m["value"] > 0 for m in r["metrics"].values())
    for k, v in r["check"].items():
        assert set(v) == {"value", "limit"}
    json.loads(json.dumps(r))


@pytest.mark.parametrize("cell", CELLS)
def test_cell_end_to_end_on_cpu(tiny_root, cell):
    _schema(_run(tiny_root, cell), cell, trace=False)


def test_traced_run_reads_per_layer_metrics(tiny_root):
    cell = CELLS[0]
    r = _run(tiny_root, cell, trace=True)
    _schema(r, cell, trace=True)
    names = {m["name"] for m in SPEC["per_layer"]
             if cell in m.get("workloads", [cell])}
    assert set(r["metrics"]) <= names
    # host-side readings exist on the CPU; device ones need the chip
    assert "dispatches_per_tick.sat" in r["metrics"]
    assert r["metrics"]["compiles_in_window.sat"]["value"] == 0
    assert r["device"]["window_s"] > 0
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}


def test_planted_wrong_reference_verdict_fails(tiny_root):
    def plant(want):
        want[0, 0] = want[0, 0] + 1          # template 0's label
    r = _run(tiny_root, CELLS[0], plant=plant)
    assert r["correct"] is False
    assert r["check"]["wrong_verdicts"]["value"] > 0


def _fault_state_unchanged(monkeypatch):
    from repro.kernels import tick_step as tick
    real = tick.tick_step

    def stuck(state, *a, **kw):
        _, res = real(state, *a, **kw)
        return state, tuple(np.zeros_like(r) for r in res[:1]) + res[1:]
    monkeypatch.setattr(tick, "tick_step", stuck)


def _fault_half_batch(monkeypatch):
    from repro.serve import FlowTableServer
    real = FlowTableServer.ingest

    def half(self, b):
        keep = slice(0, None, 2)
        return real(self, type(b)(*(x[keep] for x in b)))
    monkeypatch.setattr(FlowTableServer, "ingest", half)


def _fault_answer_altered(monkeypatch):
    from repro.serve import FlowTableServer
    real = FlowTableServer.ingest

    def altered(self, b):
        v = real(self, b)
        v.labels = np.where(v.labels >= 0, v.labels + 1, v.labels)
        return v
    monkeypatch.setattr(FlowTableServer, "ingest", altered)


@pytest.mark.parametrize("fault", [_fault_state_unchanged, _fault_half_batch,
                                   _fault_answer_altered])
def test_broken_timed_path_is_not_correct(tiny_root, monkeypatch, fault):
    fault(monkeypatch)
    r = _run(tiny_root, CELLS[0], seconds=3.0)
    assert r["correct"] is False and r["failed"] > 0


def test_entry_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         CELLS[0], "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=REPO, capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert not out.stdout.strip()


def test_only_the_benchmark_files_are_not_enough(tmp_path):
    """Beside nothing but BENCHMARK.json and the benchmark's paths, the
    run has no program to serve and exits non-zero."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    for p in SPEC["paths"]:
        shutil.copytree(os.path.join(REPO, p), tmp_path / p,
                        ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode != 0
    assert not out.stdout.strip()


def test_new_config_traffic_and_metric_are_files_only(tmp_path):
    """A later PR adds a configuration, a mix and a per-layer metric as
    new files plus entries; no existing file changes."""
    root = make_root(str(tmp_path))
    chip = os.path.join(root, "benchmarks", "chip")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    first = spec["configs"][0]
    with open(os.path.join(root, first["file"])) as f:
        cfg = json.load(f)
    cfg["name"] = "dummy-cfg"
    with open(os.path.join(chip, "configs", "dummy-cfg.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(chip, "traffic", "dummy-mix.json"), "w") as f:
        json.dump({"arrivals": "saturate", "pool": 24, "concurrency": 48,
                   "max_tick": 96}, f)
    with open(os.path.join(chip, "metrics", "dummy_ticks.py"), "w") as f:
        f.write("def read(ctx):\n    return float(ctx['ticks'])\n")
    spec["configs"].append(dict(first, name="dummy-cfg",
                                file="benchmarks/chip/configs/dummy-cfg.json"))
    spec["workloads"].append({"name": "dummy-cell", "config": "dummy-cfg",
                              "traffic": "dummy-mix", "chips": 1,
                              "why": "dummy"})
    for m in spec["end_to_end"]:
        if m["name"] == "pkts_per_s":
            m["workloads"].append("dummy-cell")
    spec["per_layer"].append({"name": "dummy_ticks", "unit": "ticks",
                              "better": "higher", "source": "host_clock",
                              "layer": "load generator",
                              "moves": "pkts_per_s",
                              "workloads": ["dummy-cell"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    r = _run(root, "dummy-cell")
    assert r["correct"] and set(r["metrics"]) == {"pkts_per_s", "setup_s"}
    r = _run(root, "dummy-cell", trace=True)
    assert r["correct"] and r["metrics"]["dummy_ticks"]["value"] > 0


def test_open_loop_backlog_shows_the_knee(tiny_root):
    """The sweep's reading: at a rate far below capacity nothing is left
    due at the window's end; far above it the backlog grows."""
    cell = harness.Served(tiny_root, CELLS[0], SEED,
                          t_start=time.perf_counter(), require_tpu=False)
    slow = cell.window(1.0, 200.0)
    fast = cell.window(1.0, 5e7)
    assert slow["backlog_pkts"] <= 1 and slow["calls"]
    assert fast["backlog_pkts"] > 1e6
    lat, orphans = cell.latencies(fast)
    assert orphans == 0 and lat.size and (lat > 0).all()
