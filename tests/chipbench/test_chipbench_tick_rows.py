"""``tick_rows_per_tick.sat`` as the chip benchmark reads it: a number
from the tiny cell's traced run on the CPU, and nothing, without error,
from a program whose server has no ``tick_rows`` counter."""
import json
import os
import time

import pytest

from benchmarks.chip import harness

from chipbench_tiny import REPO, TINY_CFG, TINY_MIX

METRIC = "tick_rows_per_tick.sat"
SPEC = json.load(open(os.path.join(REPO, "BENCHMARK.json")))


@pytest.fixture(scope="module")
def traced_run(tiny_root):
    cell = SPEC["workloads"][0]["name"]
    return harness.run(tiny_root, cell, 2**31 + 17, 1.0, True,
                       t_start=time.perf_counter(), require_tpu=False)


def test_reads_the_ticks_columns_on_the_tiny_cell(traced_run):
    """Rows per call: the tick's columns on the power-of-two ladder
    (floor 64), at most a tick's packets, never the table."""
    assert traced_run["correct"] is True
    rows = traced_run["metrics"][METRIC]["value"]
    slots = TINY_CFG["n_buckets"] * TINY_CFG["bucket_size"]
    assert 64 <= rows <= TINY_MIX["max_tick"] < slots


def test_verdict_fetch_is_five_int32_rows_per_tick_row(traced_run):
    """The tick's verdict fetch scales with its rows, not the table:
    ``d2h_mb_per_tick.sat`` reads 5 x 4 B per row the tick touched."""
    m = traced_run["metrics"]
    assert m["d2h_mb_per_tick.sat"]["value"] == pytest.approx(
        5 * 4 * m[METRIC]["value"] / 1e6, rel=1e-12)


@pytest.mark.parametrize("stats, want", [
    ({"tick_rows": 3 * 32768, "ticks": 3}, 32768.0),
    ({"tick_rows": 0, "ticks": 0}, None),
    ({"d2h_bytes": 655360, "ticks": 1}, None),
    ({}, None),
])
def test_reads_the_counter_and_nothing_else(stats, want):
    read = harness.load_reader(REPO, METRIC)
    assert read({"trace": None, "ticks": 0, "stats": stats}) == want


def test_is_listed_for_the_saturating_cell():
    m, = [m for m in SPEC["per_layer"] if m["name"] == METRIC]
    assert m["layer"] == "device" and m["moves"] == "pkts_per_s"
    assert m["source"] == "program_counter" and m["unit"] == "rows"
    assert m["workloads"] == [SPEC["workloads"][0]["name"]]
