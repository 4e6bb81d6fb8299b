"""Sharding resolution + multi-device behaviours (subprocess: 8 fake
devices) : elastic restore across mesh sizes, compressed psum under
shard_map, pipeline parallelism, and a miniature dry-run."""
import numpy as np
import pytest

from repro.configs import ARCHS, get_arch
from repro.distributed import pspec
from repro.models import model_zoo
from tests.conftest import run_subprocess


# ---------------------------------------------------------------------------
# spec resolution (no devices needed)
# ---------------------------------------------------------------------------
def test_divisibility_fallback():
    from repro.distributed.pspec import ParamDef, resolve_spec
    d = ParamDef((4, 64), ("kv", "head_dim"))
    spec = resolve_spec(d, {"data": 16, "model": 16})
    assert spec[0] is None          # 4 kv heads can't shard over 16
    d2 = ParamDef((64, 128), ("heads", "mlp"))
    spec2 = resolve_spec(d2, {"data": 16, "model": 16})
    assert spec2 == ("model", "model") or tuple(spec2) == ("model", "model")


@pytest.mark.parametrize("arch_id", sorted(ARCHS))
def test_param_and_spec_trees_align(arch_id):
    """Every arch: ParamDef tree resolves to same-structure spec tree and
    every spec's sharded dims divide exactly (full configs, abstract)."""
    import jax
    cfg = get_arch(arch_id)
    defs = model_zoo.get_model(cfg).param_defs(cfg)
    sds = pspec.abstract_params(defs)
    specs = pspec.resolve_specs(defs, {"data": 16, "model": 16})
    n_checked = 0

    def check(s, spec):
        nonlocal n_checked
        sizes = {"data": 16, "model": 16}
        for dim, entry in zip(s.shape, tuple(spec)):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            total = int(np.prod([sizes[a] for a in axes]))
            assert dim % total == 0, (arch_id, s.shape, spec)
            n_checked += 1

    jax.tree.map(check, sds, specs, is_leaf=lambda x: x is None)
    assert n_checked > 0


def test_batch_spec_rules():
    code = """
import jax
from jax.sharding import AxisType
from repro.distributed.sharding import batch_spec, cache_spec
from repro.configs import get_arch
mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,)*2)
cfg = get_arch("tinyllama-1.1b")
s = batch_spec(mesh, (8, 128))
assert tuple(s)[0] in (("data",), "data"), s
s1 = batch_spec(mesh, (1, 65536))      # batch=1 -> sequence parallelism
assert tuple(s1)[1] == "data", s1
cs = cache_spec(mesh, (22, 8, 8192, 4, 64), cfg)
assert tuple(cs)[1] in (("data",), "data"), cs
print("ok")
"""
    assert "ok" in run_subprocess(code, devices=8)


def test_abstract_mesh_follows_set_mesh():
    """``models/layers.py`` reads the ambient mesh through
    ``get_abstract_mesh()``; on current JAX ``jax.set_mesh`` installs it
    for the block and restores the empty mesh after."""
    import jax
    from repro.launch.mesh import make_host_mesh
    assert jax.sharding.get_abstract_mesh().empty
    with jax.set_mesh(make_host_mesh()):
        inside = jax.sharding.get_abstract_mesh()
        assert dict(inside.shape) == {"data": 1, "model": 1}
    assert jax.sharding.get_abstract_mesh().empty


# ---------------------------------------------------------------------------
# multi-device subprocess tests
# ---------------------------------------------------------------------------
def test_elastic_restore_across_mesh_sizes(tmp_path):
    code = f"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P, AxisType
from repro.train import checkpoint as ckpt_lib
from repro.train.optimizer import AdamW

params = {{"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}}
opt = AdamW(lr=0.1)
state = opt.init(params)

mesh8 = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
sh8 = jax.tree.map(lambda x: jax.device_put(
    x, NamedSharding(mesh8, P("data") if x.ndim else P())), state)
ckpt_lib.save(r"{tmp_path}/step_1", sh8)

# restore onto a 4-device mesh, then a 2-device mesh
for n in (4, 2):
    mesh = jax.make_mesh((n,), ("data",),
                         axis_types=(AxisType.Auto,))
    shardings = jax.tree.map(
        lambda x: NamedSharding(mesh, P("data") if hasattr(x, "ndim") and x.ndim else P()),
        state)
    restored, _ = ckpt_lib.restore(r"{tmp_path}/step_1", shardings)
    np.testing.assert_array_equal(
        np.asarray(restored.params["w"]), np.asarray(params["w"]))
    assert len(restored.params["w"].sharding.device_set) == n
print("ok")
"""
    assert "ok" in run_subprocess(code, devices=8)


def test_compressed_psum_shard_map():
    code = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P, AxisType
from repro.distributed.compression import compressed_psum

mesh = jax.make_mesh((4,), ("pod",), axis_types=(AxisType.Auto,))
g = jnp.asarray(np.random.default_rng(0).normal(size=(4, 32)), jnp.float32)

def f(g_local):
    out, err = compressed_psum(g_local[0], "pod")
    return out[None], err[None]

out, err = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("pod"),
                                 out_specs=(P("pod"), P("pod"))))(g)
ref = g.mean(axis=0)
got = np.asarray(out)[0]
rel = np.abs(got - np.asarray(ref)).max() / np.abs(ref).max()
assert rel < 0.05, rel
print("ok", rel)
"""
    assert "ok" in run_subprocess(code, devices=8)


def test_pipeline_parallel_matches_sequential():
    code = """
import jax, jax.numpy as jnp, numpy as np
from repro.distributed.pipeline import make_stage_mesh, pipeline_forward

S, M, d = 4, 6, 16
mesh = make_stage_mesh(S)
rng = np.random.default_rng(0)
Ws = jnp.asarray(rng.normal(size=(S, d, d)) / np.sqrt(d), jnp.float32)
mbs = jnp.asarray(rng.normal(size=(M, 8, d)), jnp.float32)

def stage_fn(W, x):
    return jnp.tanh(x @ W)

pipe = jax.jit(pipeline_forward(stage_fn, mesh))
with jax.set_mesh(mesh):
    out = pipe(Ws, mbs)

ref = mbs
for s in range(S):
    ref = jnp.tanh(ref @ Ws[s])
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
print("ok")
"""
    assert "ok" in run_subprocess(code, devices=8)


def test_mini_dryrun_on_8_devices():
    """Guards the dry-run plumbing (build_cell/lower/compile/roofline)
    without 512 devices: reduced config, 2x4 mesh."""
    code = """
import dataclasses, jax
from jax.sharding import AxisType
import repro.launch.dryrun as dr
from repro.configs import get_arch
from repro.configs.base import ShapeCfg

mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,)*2)
cfg = get_arch("tinyllama-1.1b").reduced()
shape = ShapeCfg("t", 64, 8, "train")
compiled, tl, tc, defs, _, _ = dr.lower_compile(cfg, shape, mesh, unroll=False)
ma = compiled.memory_analysis()
assert ma.argument_size_in_bytes > 0
ca = compiled.cost_analysis()
assert ca.get("flops", 0) > 0
from repro.analysis.roofline import parse_collectives
st = parse_collectives(compiled.as_text())
print("ok", sum(st.counts.values()) >= 0)

# decode cell too
shape_d = ShapeCfg("d", 128, 8, "decode")
compiled, *_ = dr.lower_compile(cfg, shape_d, mesh, unroll=False)
print("ok decode")
"""
    out = run_subprocess(code, devices=8, timeout=900)
    assert "ok decode" in out
