"""Early-exit compaction for the recirculation walk.

SpliDT's recirculation overhead is tiny because classification
confidence is front-loaded: most flows exit in the first partitions
(paper §4.4; pForest makes the same observation for multi-phase random
forests).  The dense partition walk ignores that and pays the full
feature-window rebuild + traversal for all B flows at every hop, even
when 95% are already ``done``.

This module compacts the walk between hops while keeping every shape
static (jit-safe), using the same MoE expert-capacity style as
``kernels.dispatch``:

  * ``compact_perm`` — argsort-on-``done`` (stable, so surviving flows
    keep their original relative order) + a prefix count of survivors;
  * ``bucket_caps`` — a fixed ladder of power-of-two capacities
    ``(0, floor, 2*floor, ..., B)`` chosen at trace time;
  * ``compacted_step`` — ``lax.switch`` over the ladder: the branch for
    the smallest capacity that fits the survivor count gathers that
    prefix of flows, runs the backend's per-partition step on the small
    buffer, and scatters actions (and optionally registers) back to the
    original flow slots.

Why a ladder and not the exact survivor count: jit needs static shapes,
so the per-hop buffer size must come from a finite set chosen at trace
time.  The power-of-two ladder bounds the wasted capacity at <2x the
survivor count (bucket ``2^i*floor`` serves counts in
``(2^(i-1)*floor, 2^i*floor]``) while keeping the ``lax.switch`` branch
count at ``log2(B/floor) + 2`` — every branch is compiled once, and the
data-dependent part is just the branch index.  The ``floor`` (default
:data:`COMPACT_FLOOR`, tunable via ``compact_floor=`` /
``repro.tuning``) sets the smallest non-empty bucket: below it the
gather/scatter overhead dominates the step, so finer rungs cannot pay
for themselves.

Correctness does not depend on the bucket choice: a too-large bucket
merely drags some already-``done`` flows through the step, and their
actions are masked out by the walk's ``active`` bookkeeping.  The step
functions are per-flow (no cross-flow reductions), so gathering a
subset produces bit-identical per-flow results — the compacted walk is
bit-identical to the dense walk and to ``PartitionedDT.predict``
(``docs/PARITY.md`` states the full contract).

The capacity-0 branch skips the step entirely, so a batch whose flows
have all exited pays nothing for the remaining hops.

Shape/dtype conventions: ``pkts`` f32 ``(B, W, PKT_NFIELDS)``, ``sid``
int32 ``(B,)``, ``done`` bool ``(B,)``, registers f32 ``(B, k)``,
actions int32 ``(B,)`` with ``-1`` in unvisited slots.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.kernels.ops import StepFn

# Default smallest non-empty bucket.  Matches the Pallas dispatch block
# (kernels.dt_traverse.BLOCK_B): shrinking below one flow block cannot
# reduce the Pallas grid further, and on the dense path the gather /
# scatter overhead dominates the step below ~this size.
COMPACT_FLOOR = 128


def bucket_caps(n_flows: int, floor: int = COMPACT_FLOOR) -> tuple[int, ...]:
    """Static capacity ladder ``(0, floor, 2*floor, ..., n_flows)``.

    Strictly increasing, ends exactly at ``n_flows`` (the full batch is
    always representable, so no survivor count can overflow the ladder);
    the leading 0 is the "everyone exited" fast path.  An empty batch
    gets the degenerate ladder ``(0,)``.
    """
    if n_flows < 0:
        raise ValueError(f"n_flows must be non-negative, got {n_flows}")
    if floor <= 0:
        raise ValueError(f"floor must be positive, got {floor}")
    if n_flows == 0:
        return (0,)
    caps = [0]
    c = floor
    while c < n_flows:
        caps.append(c)
        c *= 2
    caps.append(n_flows)
    return tuple(caps)


def compact_perm(done: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Survivor-first permutation + survivor count.

    ``argsort`` on the ``done`` flags (stable: False < True) moves every
    surviving flow into the prefix while preserving original order; the
    prefix length is ``B - sum(done)``.  Both are device values — no
    host sync, so compaction composes with the fully-jitted walk and
    ``shard_map`` (each shard counts its own survivors).
    """
    B = done.shape[0]
    perm = jnp.argsort(done, stable=True)
    # splint: allow[R001]: int32 survivor count — exact, order-invariant
    n_active = (B - jnp.sum(done.astype(jnp.int32))).astype(jnp.int32)
    return perm, n_active


def compacted_step(
    pkts: jnp.ndarray,        # (B, W, PKT_NFIELDS) one partition's windows
    sid: jnp.ndarray,         # (B,) int32 active subtree per flow
    done: jnp.ndarray,        # (B,) bool
    dev: ops.DeviceTables,
    *,
    step: StepFn,
    caps: tuple[int, ...],
    with_regs: bool = False,
) -> tuple[jnp.ndarray | None, jnp.ndarray]:
    """Run ``step`` on the compacted survivor prefix only.

    Returns ``(regs, action)`` with full-batch shapes: ``action`` (B,)
    int32 carries ``-1`` in slots the step did not visit (all masked by
    ``done`` downstream), and ``regs`` (B, k) f32 — survivors' registers
    scattered back, zeros elsewhere — or ``None`` when ``with_regs`` is
    False.  Branch selection is data-dependent (`lax.switch`); every
    branch has static shapes, so the whole thing traces into one XLA
    computation.
    """
    B = sid.shape[0]
    k = int(dev.slot_op.shape[1])
    perm, n_active = compact_perm(done)
    idx = jnp.searchsorted(jnp.asarray(caps, jnp.int32), n_active,
                           side="left")

    def make_branch(cap: int):
        def branch(pkts, sid, done, perm):
            if cap == B and B:
                # full rung: nothing (or too little) has exited — run the
                # step dense and skip the gather/scatter round trip (the
                # step is per-flow, so this is bit-identical)
                regs_c, action = step(pkts, sid, dev)
                regs = (jnp.where(done[:, None], 0.0, regs_c)
                        if with_regs else None)
                return (regs, action) if with_regs else (action,)
            action = jnp.full((B,), -1, jnp.int32)
            regs = jnp.zeros((B, k), jnp.float32) if with_regs else None
            if cap > 0:
                take = perm[:cap]
                regs_c, act_c = step(pkts[take], sid[take], dev)
                action = action.at[take].set(act_c)
                if with_regs:
                    # capacity overhang rows (already-done flows dragged
                    # into the bucket) keep zero registers, so the trace
                    # depends only on the survivor set, not the bucket
                    live = (~done[take])[:, None]
                    regs = regs.at[take].set(jnp.where(live, regs_c, 0.0))
            return (regs, action) if with_regs else (action,)
        return branch

    out = jax.lax.switch(idx, [make_branch(c) for c in caps],
                         pkts, sid, done, perm)
    if with_regs:
        return out[0], out[1]
    return None, out[0]
