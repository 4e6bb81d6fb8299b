"""The main-path Pallas kernels compile for a TPU v5e, at deployment
shapes, without a chip attached.

Each test compiles one kernel (or the jitted program that holds it) for
a described ``v5e:2x2`` topology and asserts that the kernel is really
in the compiled program (``tpu_custom_call``) — a program that quietly
took the Pallas interpreter compiles too, but runs nothing on the chip.
Two shapes per kernel:

* ``d2`` — the d2 (2,3,2) k=4 model ``chip_smoke.py`` serves: S=34
  subtrees, T=L=8, windows of W=65 packets, a 65,536-flow batch and a
  16,384-column serving tick;
* ``dse_top`` — the top of the DSE range (``core.dse.SearchSpace``:
  k=6, subtree depth 10 so L=T=1024), one-partition windows of the
  longest d2 flow (W=192), a 65,536-flow batch.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and the test workers all
import every test file.
Code that asks ``ops._on_tpu()`` at trace time is steered per test with
``monkeypatch``.
"""
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.features import PKT_NFIELDS
from repro.kernels import ops
from repro.kernels import tick_step as tick
from repro.kernels.dispatch import dispatch_dt_traverse
from repro.kernels.feature_window import (
    feature_update_finalize_pallas,
    feature_update_pallas,
    feature_window_pallas,
)


class Shape(NamedTuple):
    S: int      # subtrees
    k: int      # feature slots
    T: int      # thresholds per slot
    L: int      # leaves per subtree
    W: int      # packets per window
    P: int      # partitions
    B: int      # flows per batch
    C: int      # flow columns per serving tick


SHAPES = {
    "d2": Shape(S=34, k=4, T=8, L=8, W=65, P=3, B=65_536, C=16_384),
    "dse_top": Shape(S=256, k=6, T=1024, L=1024, W=192, P=1, B=65_536,
                     C=65_536),
}
TABLE_SLOTS = 1 << 20
TICK_RANKS = 16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler, or the library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    """Make trace-time ``ops._on_tpu()`` answer True: the programs below
    are compiled for the chip, so their kernels must not interpret."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _tables(sh, s: Shape) -> ops.DeviceTables:
    f32, i32 = jnp.float32, jnp.int32
    return ops.DeviceTables(
        slot_op=_spec(sh, (s.S, s.k), i32),
        slot_field=_spec(sh, (s.S, s.k), i32),
        slot_pred=_spec(sh, (s.S, s.k), i32),
        slot_init=_spec(sh, (s.S, s.k), f32),
        thresholds=_spec(sh, (s.S, s.k, s.T), f32),
        leaf_lo=_spec(sh, (s.S, s.L, s.k), i32),
        leaf_hi=_spec(sh, (s.S, s.L, s.k), i32),
        leaf_action=_spec(sh, (s.S, s.L), i32),
        leaf_valid=_spec(sh, (s.S, s.L), i32),
    )


def _assert_kernel_compiled(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_feature_window_compiles(one_chip, name):
    s = SHAPES[name]
    rows = [_spec(one_chip, (s.B, s.k), jnp.int32)] * 3
    _assert_kernel_compiled(
        lambda *a: feature_window_pallas(*a, interpret=False),
        _spec(one_chip, (s.B, s.W, PKT_NFIELDS), jnp.float32), *rows,
        _spec(one_chip, (s.B, s.k), jnp.float32))


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_feature_update_compiles(one_chip, name):
    s = SHAPES[name]
    rows = [_spec(one_chip, (s.C, s.k), jnp.int32)] * 3
    _assert_kernel_compiled(
        lambda *a: feature_update_pallas(*a, interpret=False),
        _spec(one_chip, (s.C, PKT_NFIELDS), jnp.float32), *rows,
        _spec(one_chip, (s.C, s.k), jnp.float32),
        _spec(one_chip, (s.C, s.k), jnp.int32))


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_feature_update_finalize_compiles(one_chip, name):
    s = SHAPES[name]
    rows = [_spec(one_chip, (s.C, s.k), jnp.int32)] * 3
    _assert_kernel_compiled(
        lambda *a: feature_update_finalize_pallas(*a, interpret=False),
        _spec(one_chip, (s.C, PKT_NFIELDS), jnp.float32), *rows,
        _spec(one_chip, (s.C, s.k), jnp.float32),
        _spec(one_chip, (s.C, s.k), jnp.float32),
        _spec(one_chip, (s.C, s.k), jnp.int32))


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_dispatch_dt_traverse_compiles(one_chip, name):
    s = SHAPES[name]
    dev = _tables(one_chip, s)
    _assert_kernel_compiled(
        lambda regs, sid, dev: dispatch_dt_traverse(
            regs, sid, dev.thresholds, dev.leaf_lo, dev.leaf_hi,
            dev.leaf_action, dev.leaf_valid, interpret=False,
            block_b=ops.BLOCK_B),
        _spec(one_chip, (s.B, s.k), jnp.float32),
        _spec(one_chip, (s.B,), jnp.int32), dev)


def test_tick_step_pallas_compiles(one_chip, on_tpu):
    """The fused serving tick at a 2^20-slot table: fold+finalize and
    the SID-dispatched traverse both run as kernels."""
    s = SHAPES["d2"]
    n1 = TABLE_SLOTS + 1
    f32, i32 = jnp.float32, jnp.int32
    col = _spec(one_chip, (n1,), i32)
    state = tick.TickState(
        acc=_spec(one_chip, (n1, s.k), f32),
        seen=_spec(one_chip, (n1, s.k), i32),
        sid=col, part=col, win_lo=col, win_hi=col, pkts_seen=col,
        recircs=col, retired=col,
        bounds=_spec(one_chip, (n1, s.P, 2), i32))
    compiled = tick.tick_step.lower(
        state, _spec(one_chip, (TICK_RANKS, s.C), i32),
        _spec(one_chip, (TICK_RANKS, s.C, PKT_NFIELDS), f32),
        _tables(one_chip, s), n_subtrees=s.S, pallas=True,
        block_b=ops.BLOCK_B).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2
    # the benchmark finds the kernels in a chip trace by these names
    assert "feature_update_finalize_pallas" in text
    assert "dt_traverse_pallas" in text
    # the table lives on the device whole: ~84 B per slot at k=4, P=3
    assert compiled.memory_analysis().argument_size_in_bytes > 80 * n1


@pytest.mark.parametrize("compact", [False, True])
def test_pallas_partition_walk_compiles(one_chip, on_tpu, compact):
    """The batch walk ``Engine.run(impl="pallas")`` dispatches, dense
    and early-exit compacted."""
    from repro.core.inference import partition_walk
    s = SHAPES["d2"]
    compiled = partition_walk.lower(
        _spec(one_chip, (s.B, s.P, s.W, PKT_NFIELDS), jnp.float32),
        _tables(one_chip, s), n_subtrees=s.S, with_trace=True,
        step=ops.fused_step_pallas, compact=compact).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 2
