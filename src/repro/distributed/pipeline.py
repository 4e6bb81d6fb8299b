"""GPipe-style pipeline parallelism via shard_map + collective_permute.

For meshes beyond (pod, data, model) — the 1000+-node regime where a
third intra-pod axis pays off — layers are divided into S stages along a
"stage" mesh axis and microbatches stream through with the standard
GPipe schedule: S + M - 1 ticks, activations handed to the next stage by
``jax.lax.ppermute`` each tick.

This module is self-contained (used by its own tests and the scaling
example, not by the assigned dry-run mesh, which is 2-axis by spec).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_forward(
    stage_fn: Callable,     # (stage_params, x) -> x
    mesh: Mesh,
    stage_axis: str = "stage",
):
    """Returns fn(stacked_stage_params, microbatches) -> outputs.

    stacked_stage_params: leaves with leading dim = n_stages, sharded
    one-stage-per-device along ``stage_axis``.
    microbatches: (M, mb, ...) — all microbatches enter at stage 0.
    """
    n_stages = mesh.shape[stage_axis]

    def per_device(params, mbs):
        # params: this stage's params (leading stage dim of size 1)
        params = jax.tree.map(lambda a: a[0], params)
        stage = jax.lax.axis_index(stage_axis)
        M = mbs.shape[0]
        ticks = n_stages + M - 1
        buf = jnp.zeros_like(mbs[0])                     # current activation
        outs = jnp.zeros_like(mbs)                       # stage S-1 results

        def tick(t, carry):
            buf, outs = carry
            mb_idx = t - stage
            # stage 0 ingests a fresh microbatch on ticks [0, M)
            fresh = jnp.take(mbs, jnp.clip(mb_idx, 0, M - 1), axis=0)
            x = jnp.where(stage == 0, fresh, buf)
            active = (mb_idx >= 0) & (mb_idx < M)
            y = stage_fn(params, x)
            y = jnp.where(active, y, buf)
            # last stage records its finished microbatch
            outs = jnp.where(
                (stage == n_stages - 1) & active,
                outs.at[jnp.clip(mb_idx, 0, M - 1)].set(y), outs)
            # hand activations downstream (ring permute; wraparound value
            # at stage 0 is ignored -- it reads from mbs)
            nxt = jax.lax.ppermute(
                y, stage_axis,
                [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return nxt, outs

        buf, outs = jax.lax.fori_loop(0, ticks, tick, (buf, outs))
        # only the last stage holds real outputs; sum-broadcast them so
        # the replicated out_spec is truthful on every device
        return jax.lax.psum(outs, stage_axis)

    return jax.shard_map(
        per_device, mesh=mesh,
        in_specs=(P(stage_axis), P()),
        out_specs=P(),
        check_vma=False)


def make_stage_mesh(n_stages: int, data: int = 1):
    import jax as _jax
    from jax.sharding import AxisType
    return _jax.make_mesh((n_stages, data), ("stage", "data"),
                          axis_types=(AxisType.Auto,) * 2)
