"""Trace reduction and byte counts of the chip benchmark, on hand-built
traces, a trace recorded on the chip, and hand-computed counts."""
import gzip
import os
from types import SimpleNamespace as NS

import numpy as np
import pytest

from benchmarks.chip import roofline, trace_reduce

from chipbench_tiny import BENCH


def _ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start, duration_ns=dur,
              stats=list(stats.items()))


def _planes():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev("bench/window", 1000, 10000),
        _ev("tick/admit", 1000, 2000),
        _ev("tick/pack", 3000, 1000),
        _ev("tick/dispatch", 4000, 500),
        _ev("tick/fetch", 4500, 3500),
        _ev("tick/admit", 9000, 1500),
    ])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[
            _ev("jit_admit_rows(7)", 500, 1000),
            _ev("jit_tick_step(9)", 4600, 3000),
            _ev("jit_tick_step(9)", 12000, 100)]),
        NS(name="XLA Ops", events=[
            _ev("fusion.1", 500, 1000, hlo_module="jit_admit_rows"),
            _ev("custom-call.2", 4600, 1000, hlo_module="jit_tick_step",
                long_name="_update_finalize_kernel"),
            _ev("fusion.3", 5400, 1200, hlo_module="jit_tick_step"),
            _ev("custom-call.4", 7000, 600, hlo_module="jit_tick_step",
                long_name="dt_traverse"),
            _ev("fusion.9", 12000, 100, hlo_module="jit_tick_step"),
        ])])
    return [host, dev]


def test_window_busy_and_idle_by_hand():
    s = trace_reduce.summarize_planes(_planes())
    assert s.window == (1000.0, 11000.0)
    assert s.window_s == pytest.approx(1e-5)
    # ops inside [1000, 11000): [1000,1500) clipped, [4600,6600) merged,
    # [7000,7600); fusion.9 lies outside
    assert s.busy_intervals("/device:TPU:0") == [
        (1000.0, 1500.0), (4600.0, 6600.0), (7000.0, 7600.0)]
    assert s.busy_s == pytest.approx(3100e-9)
    gaps = dict(s.idle_gaps())
    # [1500,4600): mid 3050 in tick/pack; [6600,7000): mid 6800 in
    # tick/fetch; [7600,11000): mid 9300 in tick/admit
    assert gaps == pytest.approx({"tick/pack": 3100e-9, "tick/fetch": 400e-9,
                                  "tick/admit": 3400e-9})
    # programs from the modules line: jit_admit_rows clipped to
    # [1000,1500), the second jit_tick_step outside the window
    assert s.program_seconds(r"^jit_tick_step\b") == pytest.approx(3000e-9)
    assert s.program_seconds(r"^jit_admit_rows\b") == pytest.approx(500e-9)
    assert s.op_seconds("hlo_module=jit_tick_step") == pytest.approx(2800e-9)
    assert s.op_seconds("update_finalize") == pytest.approx(1000e-9)
    assert s.op_seconds("dt_traverse") == pytest.approx(600e-9)
    assert s.top_ops(2) == [["fusion.3", pytest.approx(1200e-9)],
                            ["custom-call.2", pytest.approx(1000e-9)]]
    assert trace_reduce.span_seconds(s, "tick/admit") == pytest.approx(
        3500e-9)
    assert trace_reduce.span_seconds(s, "tick/spill") is None


def test_no_window_span_is_an_error():
    planes = _planes()
    planes[0].lines[0].events.pop(0)
    with pytest.raises(ValueError, match="bench/window"):
        trace_reduce.summarize_planes(planes)


# ---------------------------------------------------------------------------
# byte counts
# ---------------------------------------------------------------------------
def test_byte_counts_by_hand():
    # a packet: 24 B row + 4 B subtree id + k=4 registers of (f32, i32)
    # read and written: 24 + 4 + 2*4*8 = 92
    assert roofline.fold_bytes(10, 4) == 920
    # a hop: 4 f32 registers + subtree id + action = 24
    assert roofline.traverse_bytes(3, 4) == 72
    # a new flow at k=4, P=3: 4*8 + 7*4 + 2*3*4 = 84, the TickState row
    assert roofline.admit_bytes(2, 4, 3) == 168
    model = {"k": 2, "partition_sizes": [1, 1], "subtrees": [
        {"feature": [0, -1, -1]}, {"feature": [1, 0, -1, -1, -1]}]}
    # per subtree 4*2*4 = 32 B of slot tables; internal node 8 B, leaf 4 B
    assert roofline.model_table_bytes(model) == (32 + 8 + 8) + (32 + 16 + 12)
    parts = roofline.work_bytes(model, 10, 3, 2, 1)
    assert parts == {"fold": 10 * (24 + 4 + 2 * 2 * 8),
                     "traverse": 3 * (8 + 8), "admit": 2 * (16 + 28 + 16),
                     "tables": 108, "total": parts["total"]}
    assert parts["total"] == sum(v for k, v in parts.items() if k != "total")


def test_exit_packets_by_hand():
    lengths = np.asarray([12, 14, 13])
    # windows at P=3: L=12 -> [0,4) [4,8) [8,12); L=14 -> [0,4) [4,8)
    # [8,14); L=13 -> [0,4) [4,8) [8,13)
    verdicts = np.asarray([[1, 0, 0], [2, 2, 2], [0, 1, 1]])
    last, hop = roofline.exit_packets(verdicts, lengths, 3)
    np.testing.assert_array_equal(last, [3, 13, 7])
    assert sorted(np.nonzero(hop[0])[0]) == [3]
    assert sorted(np.nonzero(hop[1])[0]) == [3, 7, 13]
    assert sorted(np.nonzero(hop[2])[0]) == [3, 7]


def test_peaks_table():
    p = roofline.peak("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        roofline.peak("TPU v9 imaginary")


# ---------------------------------------------------------------------------
# a trace recorded on the chip
# ---------------------------------------------------------------------------
CHIP_TRACE = os.path.join(BENCH, "testdata", "tick_trace.xplane.pb.gz")


def test_recorded_chip_trace():
    """A short window of the cell recorded on one TPU v5e: the readers'
    program and kernel patterns find their events, and every number the
    reduction gives lies inside the window."""
    from jax.profiler import ProfileData
    with gzip.open(CHIP_TRACE, "rb") as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    s = trace_reduce.summarize_planes(pd.planes)
    assert s.devices == ["/device:TPU:0"]
    assert 0 < s.busy_s < s.window_s
    tick = s.program_seconds(r"^jit_tick_step\b")
    fold = s.op_seconds("update_finalize")
    walk = s.op_seconds("dt_traverse")
    assert 0 < fold < tick and 0 < walk < tick
    assert tick <= s.busy_s
    gaps = dict(s.idle_gaps())
    assert set(gaps) <= {"tick/admit", "tick/pack", "tick/dispatch",
                         "tick/fetch", "tick/spill", "host:other"}
    assert sum(gaps.values()) == pytest.approx(s.window_s - s.busy_s)
