"""Mesh-sharded streaming (subprocess: 8 fake CPU devices): the
shard_map'd partition walk over the flow-batch axis must be
indistinguishable from the single-device fused run — including uneven
final micro-batches, micro-batches that don't divide the device count,
and any pipelining depth.  Sharding is part of the bit-exactness contract
(docs/PARITY.md): a per-flow walk has no cross-shard reductions, so
shard count can never change bits."""
from tests.conftest import run_subprocess

_SETUP = """
import numpy as np, jax
from repro.core.inference import Engine
from repro.core.partition import train_partitioned_dt
from repro.flows.synthetic import make_dataset
from repro.flows.windows import window_features, window_packets
from repro.launch.mesh import make_flow_mesh
from repro.serve.streaming import run_streaming

ds = make_dataset("d2", n_flows=600)
tr, _ = ds.split()
Xw = window_features(tr, 3)
pdt = train_partitioned_dt(Xw, tr.labels, partition_sizes=[2, 3, 2], k=4)
wp = window_packets(tr, 3)
eng = Engine.from_model(pdt)
full = eng.run(wp, with_trace=False)
mesh = make_flow_mesh()
assert len(jax.devices()) == 8, jax.devices()

def check(res):
    np.testing.assert_array_equal(res.labels, full.labels)
    np.testing.assert_array_equal(res.recircs, full.recircs)
    np.testing.assert_array_equal(res.exit_partition, full.exit_partition)
"""


def test_sharded_parity_and_ragged_tails():
    """Sharded == single-device for micro-batches that leave an uneven
    final chunk, don't divide the 8-device mesh (rounded up in-scheduler),
    or exceed B entirely."""
    code = _SETUP + """
B = wp.shape[0]
for mb in (64, B - 1, 10_000, 96, 50):   # 50 -> rounded up to 56
    check(run_streaming(eng, wp, micro_batch=mb, mesh=mesh))
print("ok", B)
"""
    assert "ok" in run_subprocess(code, devices=8)


def test_sharded_donation_on_off():
    """The sharded walk keeps its packet buffers (no output could alias
    them, so there is no donation knob to turn on or off), and
    in-flight chunks on the mesh stay exact at any pipelining depth."""
    code = _SETUP + """
import pytest
with pytest.raises(TypeError):
    run_streaming(eng, wp, micro_batch=128, mesh=mesh, donate=True)
check(run_streaming(eng, wp, micro_batch=128, mesh=mesh))
check(run_streaming(eng, wp, micro_batch=128, mesh=mesh, inflight=1))
check(run_streaming(eng, wp, micro_batch=128, mesh=mesh, inflight=4))
print("ok")
"""
    assert "ok" in run_subprocess(code, devices=8)


def test_sharded_outputs_actually_sharded():
    """The walk must fan out: run the shard_map'd walk directly and
    assert its outputs span all 8 devices (not a degenerate 1-device
    execution)."""
    code = _SETUP + """
import jax.numpy as jnp
from repro.core.inference import FUSED_BACKEND
from repro.serve.streaming import _sharded_walk
walk = _sharded_walk(mesh, eng.ret.n_subtrees, FUSED_BACKEND.step)
P = eng.tables.n_partitions
batch = jnp.asarray(wp[:128, :P], jnp.float32)
labels, _, _ = walk(batch, eng.dev)
assert len(labels.sharding.device_set) == 8, labels.sharding
print("ok")
"""
    assert "ok" in run_subprocess(code, devices=8)


def test_sharded_compact_walk():
    """Early-exit compaction under shard_map: each shard argsorts its
    own survivors and picks its own capacity bucket (data-dependent
    lax.switch per shard, no collectives) — verdicts bit-identical to
    the single-device dense run, fused and pallas steps alike."""
    code = _SETUP + """
for mb in (64, 96):
    check(run_streaming(eng, wp, micro_batch=mb, mesh=mesh, compact=True))
res = run_streaming(eng, wp[:160], micro_batch=64, mesh=mesh,
                    impl="pallas", compact=True)
np.testing.assert_array_equal(res.labels, full.labels[:160])
np.testing.assert_array_equal(res.recircs, full.recircs[:160])
np.testing.assert_array_equal(res.exit_partition, full.exit_partition[:160])
print("ok")
"""
    assert "ok" in run_subprocess(code, devices=8)


def test_sharded_pallas_backend():
    """The in-jit SID dispatch composes with shard_map: the Pallas walk
    (interpret mode) streams sharded and stays bit-identical."""
    code = _SETUP + """
res = run_streaming(eng, wp[:160], micro_batch=64, mesh=mesh, impl="pallas")
np.testing.assert_array_equal(res.labels, full.labels[:160])
np.testing.assert_array_equal(res.recircs, full.recircs[:160])
np.testing.assert_array_equal(res.exit_partition, full.exit_partition[:160])
print("ok")
"""
    assert "ok" in run_subprocess(code, devices=8)
