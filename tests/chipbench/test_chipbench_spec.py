"""``BENCHMARK.json`` against the benchmark's own rules: the keys and
names it may hold, and a file for every configuration, mix and metric."""
import json
import os
import re

import pytest

from chipbench_tiny import REPO

SPEC = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "benchmarks/chip/run.py"]
    assert 1 <= SPEC["run_seconds"] <= 51
    for p in SPEC["paths"]:
        assert os.path.isdir(os.path.join(REPO, p))
    assert len(json.dumps(SPEC)) < 64 * 1024


def test_configs_have_files_and_cells():
    cells = SPEC["workloads"]
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"])
        assert c["file"].startswith("benchmarks/chip/")
        with open(os.path.join(REPO, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"]
        assert set(c["reduced"]) == set(cfg.get("reduced", {}))
        assert any(w["config"] == c["name"] for w in cells)


def test_cells():
    names = [w["name"] for w in SPEC["workloads"]]
    assert len(set(names)) == len(names)
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(set(pairs)) == len(pairs)
    cfgs = {c["name"] for c in SPEC["configs"]}
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in cfgs and w["chips"] == 1
        assert 1 <= len(w["why"]) <= 200
        assert os.path.exists(os.path.join(
            REPO, "benchmarks/chip/traffic", w["traffic"] + ".json"))


def test_metrics():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    cells = {w["name"] for w in SPEC["workloads"]}
    for m in SPEC["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= m["bound"] <= 0.25
    assert e2e["setup_s"]["bound"] == 0.25
    reports = {c: {n for n, m in e2e.items()
                   if c in m.get("workloads", [c])} for c in cells}
    layers = {}
    for m in SPEC["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["source"] in SOURCES and m["better"] in {"lower", "higher"}
        assert os.path.exists(os.path.join(
            REPO, "benchmarks/chip/metrics", m["name"] + ".py"))
        for c in m["workloads"]:
            assert m["moves"] in reports[c]
            layers.setdefault(c, set()).add(m["name"])
    for c in cells:
        assert "setup_s" in reports[c] and len(reports[c]) >= 2
        assert layers.get(c)
    names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(set(names)) == len(names)


@pytest.mark.parametrize("m", SPEC["per_layer"], ids=lambda m: m["name"])
def test_roofline_and_peak_shares_are_named(m):
    if m["name"].split(".")[0].endswith("_roofline") or "mfu" in m["name"]:
        assert m["unit"] == "%" and m["better"] == "higher"
