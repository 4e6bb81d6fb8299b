"""``lookup_probes_per_key.sat`` as the chip benchmark reads it: a number
from the tiny cell's traced run on the CPU, and nothing, without error,
from a program whose server has no lookup counters."""
import json
import os
import time

import pytest

from benchmarks.chip import harness

from chipbench_tiny import REPO

METRIC = "lookup_probes_per_key.sat"
SPEC = json.load(open(os.path.join(REPO, "BENCHMARK.json")))


@pytest.fixture(scope="module")
def traced_run(tiny_root):
    cell = SPEC["workloads"][0]["name"]
    return harness.run(tiny_root, cell, 2**31 + 14, 1.0, True,
                       t_start=time.perf_counter(), require_tpu=False)


def test_reads_a_number_on_the_tiny_cell(traced_run):
    assert traced_run["correct"] is True
    assert 1.0 <= traced_run["metrics"][METRIC]["value"] < 2.0


@pytest.mark.parametrize("stats, want", [
    ({"lookup_probes": 1170, "lookup_keys": 1000}, 1.17),
    ({"lookup_probes": 0, "lookup_keys": 0}, None),
    ({"insert_probes": 14, "flows_seen": 10}, None),
    ({}, None),
])
def test_reads_the_counters_and_nothing_else(stats, want):
    read = harness.load_reader(REPO, METRIC)
    assert read({"trace": None, "ticks": 0, "stats": stats}) == want


def test_is_listed_for_the_saturating_cell():
    m, = [m for m in SPEC["per_layer"] if m["name"] == METRIC]
    assert m["layer"] == "host admission" and m["moves"] == "pkts_per_s"
    assert m["workloads"] == [SPEC["workloads"][0]["name"]]
