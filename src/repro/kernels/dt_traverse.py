"""Pallas TPU kernel: partitioned-subtree range-mark matching.

The Subtree Model Prediction phase (paper §3.1.2) as dense TPU compute.
Rather than pointer-chasing the tree (hostile to the VPU), we execute
the *range-marking* semantics the switch itself uses:

    marks  = #{threshold < register}   per slot     (compare + reduce)
    hit(l) = marks within leaf l's per-slot interval (dense match)
    action = first hit (TCAM priority encode)

Flows are grouped by SID outside the kernel but INSIDE jit
(``repro.kernels.dispatch``: argsort by SID, scatter each segment to a
capacity-padded block offset — MoE-dispatch style) and the grid
prefetches a ``block_sid`` map so each grid step streams ONE subtree's
threshold and leaf tables into VMEM alongside its flow block — the TPU
analogue of the switch activating one subtree's MAT entries per
pipeline pass.

Layout: the kernel walks the k slots in a static loop, so every
intermediate is a 2-D ``(Bb, T)`` or ``(Bb, L)`` tile with the
threshold/leaf axis on the 128-wide lanes (k on the lanes would pad
every tile 128/k-fold).  The wrapper lays the leaf tables out slot-major
``(S, k, L)`` and the per-leaf rows as ``(S, 1, L)``, so every block's
last two dimensions equal the array's, as Mosaic's (8, 128) tiling rule
requires.  The relayout is a transpose of the ``S * L * k`` leaf tables
per call, inside the same jit.

VMEM per step (double-buffered inputs plus a handful of live tiles):
at Bb=128 and T = L = 1024, the top of the DSE range (k <= 6, subtree
depth <= 10), each ``(Bb, T)`` / ``(Bb, L)`` tile is 512 KiB — a few
MiB in all, inside the 16 MiB scoped-VMEM default of a TPU v5e.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_B = 128


def _kernel(block_sid_ref, regs_ref, thr_ref, lo_ref, hi_ref, act_ref,
            valid_ref, out_ref):
    del block_sid_ref  # consumed by the index maps
    k = thr_ref.shape[1]
    L = lo_ref.shape[2]
    hit = valid_ref[0] > 0                                 # (1, L)
    for j in range(k):
        reg = regs_ref[:, j:j + 1]                         # (Bb, 1)
        thr = thr_ref[0, j:j + 1, :]                       # (1, T)
        # integer count: exact in any reduction order
        mark = (reg > thr).astype(jnp.int32).sum(axis=1, keepdims=True)
        hit = (hit & (mark >= lo_ref[0, j:j + 1, :])
               & (mark <= hi_ref[0, j:j + 1, :]))          # (Bb, L)
    lidx = jax.lax.broadcasted_iota(jnp.int32, hit.shape, 1)
    first = jnp.min(jnp.where(hit, lidx, L), axis=1, keepdims=True)
    # priority encode: the first hit's action; no hit selects nothing
    # and leaves the -1 sentinel
    out_ref[...] = jnp.max(jnp.where(lidx == first, act_ref[0], -1),
                           axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret", "block_b"))
def dt_traverse_pallas(
    block_sid: jnp.ndarray,    # (n_blocks,) int32: SID of each flow block
    regs: jnp.ndarray,         # (n_blocks*Bb, k) f32, grouped by SID
    thresholds: jnp.ndarray,   # (S, k, T) f32 (+inf padded)
    leaf_lo: jnp.ndarray,      # (S, L, k) int32
    leaf_hi: jnp.ndarray,      # (S, L, k) int32
    leaf_action: jnp.ndarray,  # (S, L) int32
    leaf_valid: jnp.ndarray,   # (S, L) int32 (0/1)
    *,
    interpret: bool = True,
    block_b: int = BLOCK_B,
) -> jnp.ndarray:
    """Returns action (n_blocks*Bb, 1) int32; -1 where no leaf matched."""
    nb = block_sid.shape[0]
    S, k, T = thresholds.shape
    L = leaf_lo.shape[1]
    bb = block_b
    assert regs.shape[0] == nb * bb, (regs.shape, nb, bb)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((bb, k), lambda i, bs: (i, 0)),
            pl.BlockSpec((1, k, T), lambda i, bs: (bs[i], 0, 0)),
            pl.BlockSpec((1, k, L), lambda i, bs: (bs[i], 0, 0)),
            pl.BlockSpec((1, k, L), lambda i, bs: (bs[i], 0, 0)),
            pl.BlockSpec((1, 1, L), lambda i, bs: (bs[i], 0, 0)),
            pl.BlockSpec((1, 1, L), lambda i, bs: (bs[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((bb, 1), lambda i, bs: (i, 0)),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nb * bb, 1), jnp.int32),
        interpret=interpret,
        name="dt_traverse_pallas",
    )(block_sid, regs, thresholds,
      jnp.swapaxes(leaf_lo, 1, 2), jnp.swapaxes(leaf_hi, 1, 2),
      leaf_action[:, None, :], leaf_valid[:, None, :])
