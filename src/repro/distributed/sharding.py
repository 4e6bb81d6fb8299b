"""Whole-program sharding resolution: params, optimizer state, batches,
and KV/state caches onto a mesh (DP/TP/FSDP/EP/SP).

Parameter/optimizer shardings come from the ParamDef logical axes
(``pspec.resolve_specs``).  Activations/batches/caches are resolved here
by dimension-role heuristics that encode the design in DESIGN.md §5:

  * batch dims ride ("pod", "data") when divisible;
  * head dims ride "model";
  * long sequence/cache dims ride "model" for decode (flash-decode
    style KV split) and "data" when the batch axis is unusable
    (long_500k batch=1 -> sequence parallelism).
"""
from __future__ import annotations


import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.distributed import pspec
from repro.launch.mesh import mesh_shape_dict
from repro.train.optimizer import TrainState


def _div(n: int, size: int) -> bool:
    return size > 0 and n % size == 0


def batch_axes(mesh) -> tuple[str, ...]:
    from repro.models import layers as L
    names = mesh.axis_names
    return tuple(a for a in L.BATCH_AXES if a in names)


def batch_spec(mesh, shape: tuple[int, ...]) -> P:
    """Shard the leading (global-batch) dim over ("pod","data")."""
    sizes = mesh_shape_dict(mesh)
    axes = batch_axes(mesh)
    total = int(np.prod([sizes[a] for a in axes]))
    if shape and _div(shape[0], total):
        return P(axes, *([None] * (len(shape) - 1)))
    # batch=1 (long-context): shard the largest long axis over "data"
    spec: list = [None] * len(shape)
    for i, d in sorted(enumerate(shape), key=lambda t: -t[1]):
        if i == 0:
            continue
        if _div(d, sizes.get("data", 1)) and d >= sizes.get("data", 1) * 8:
            spec[i] = "data"
            break
    return P(*spec)


def cache_spec(mesh, shape: tuple[int, ...], cfg: ArchConfig,
               opt: bool = True) -> P:
    """KV/state cache sharding.

    Heuristic roles by dim size: batch (== global_batch) -> dp axes;
    a dim equal to n_kv/n_heads (or B*H products) -> "model"; the long
    seq dim -> "model" if batch sharded else "data" (SP).
    """
    sizes = mesh_shape_dict(mesh)
    dp = batch_axes(mesh)
    dp_total = int(np.prod([sizes[a] for a in dp])) if dp else 1
    model = sizes.get("model", 1)
    spec: list = [None] * len(shape)
    if not shape:
        return P()
    # caches arrive with the stacked-layer dim in front; recognise it by
    # value (known per-arch layer counts) and never shard it
    lead = cfg.moe.first_dense_layers if cfg.moe else 0
    layer_counts = {cfg.n_layers, cfg.n_layers - lead, cfg.enc_layers}
    if cfg.shared_attn_every:
        layer_counts.add(cfg.n_layers // cfg.shared_attn_every)
    layer_counts.discard(0)
    used_model = used_seq = used_batch = False
    # pass 1: batch + head dims.  Head dims take the "model" axis with
    # priority over long sequence dims when the arch's head count
    # divides it: a window-sliced (sliding-window decode) or ring cache
    # then stays shard-local, where a seq-sharded cache would force a
    # gather for any dynamic slice (§Perf, zamba2 long_500k).
    head_sizes = {cfg.n_heads, cfg.n_kv_heads}
    for i, d in enumerate(shape):
        if i == 0 and len(shape) >= 3 and d in layer_counts:
            continue   # stacked layer dim
        if not used_batch and _div(d, dp_total) and d >= dp_total and i <= 1:
            spec[i] = dp
            used_batch = True
            continue
        if (opt and not used_model and i >= 2 and d in head_sizes
                and cfg.sliding_window and _div(d, model)):
            spec[i] = "model"
            used_model = True
            used_seq = True   # window slice must stay shard-local
    # pass 2: remaining model-axis candidates (latent dims, long seq)
    for i, d in enumerate(shape):
        if spec[i] is not None or (i == 0 and len(shape) >= 3
                                   and d in layer_counts):
            continue
        if (not used_model and d >= model and _div(d, model)
                and d <= max(cfg.n_heads, cfg.d_model) and i >= 2):
            spec[i] = "model"
            used_model = True
            continue
        if not used_seq and d >= 4096 and i >= 1:
            ax = "model" if not used_model and _div(d, model) else (
                "data" if not used_batch and _div(d, sizes.get("data", 1))
                else None)
            if ax:
                spec[i] = ax
                used_seq = used_model = True
            continue
    return P(*spec)


def train_state_shardings(cfg: ArchConfig, mesh, defs=None):
    """NamedSharding tree for a TrainState (params + mu/nu mirrored)."""
    from repro.models import model_zoo
    defs = defs or model_zoo.get_model(cfg).param_defs(cfg)
    specs = pspec.resolve_specs(defs, mesh_shape_dict(mesh))
    named = jax.tree.map(lambda s: NamedSharding(mesh, s), specs)
    scalar = NamedSharding(mesh, P())
    return TrainState(step=scalar, params=named, mu=named, nu=named)


def tree_shardings(mesh, tree, spec_fn):
    """Map ShapeDtypeStruct tree -> NamedSharding tree via spec_fn(shape)."""
    return jax.tree.map(
        lambda x: NamedSharding(mesh, spec_fn(tuple(x.shape))), tree)


def batch_shardings(cfg: ArchConfig, mesh, batch_sds):
    return tree_shardings(mesh, batch_sds, lambda s: batch_spec(mesh, s))


def cache_shardings(cfg: ArchConfig, mesh, cache_sds):
    return tree_shardings(mesh, cache_sds,
                          lambda s: cache_spec(mesh, s, cfg))
