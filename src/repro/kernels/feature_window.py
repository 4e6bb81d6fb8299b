"""Pallas TPU kernel: windowed stateful feature accumulation.

The data-plane hot loop of SpliDT's Feature Collection & Engineering
phase (paper §3.1.1), adapted to TPU (DESIGN.md §2): instead of
per-packet register scatter, the pipeline delivers flow-major windows
``(B, W, fields)`` and the kernel performs the per-SID operator-selected
register update for a block of flows entirely in VMEM.

Grid: one step per flow block.  Per-flow op/field/pred rows are gathered
from the SID-indexed operator-selection tables *outside* the kernel
(tiny XLA gathers); the kernel does the O(B * W * k) reduction work.

Layout: the wrapper hands the kernel field-major packets ``(F, B, W)``
in blocks of up to ``BLOCK_B`` flow rows (fewer for long windows, see
:func:`window_block_rows`); each field is then a 2-D ``(Bb, W)`` tile
with the window on the lanes, and the kernel loops over the k slots.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import features as F
from repro.kernels.dispatch import pad_axis0, round_up
from repro.kernels.ref import ordered_wsum

BLOCK_B = 128
#: Budget on ``rows * W`` of one window-kernel block (see
#: :func:`window_block_rows`).
WINDOW_VMEM_ELEMS = 8192

_FLAG_PREDS = ((F.PRED_SYN, F.FLAG_SYN), (F.PRED_ACK, F.FLAG_ACK),
               (F.PRED_FIN, F.FLAG_FIN), (F.PRED_RST, F.FLAG_RST),
               (F.PRED_PSH, F.FLAG_PSH), (F.PRED_URG, F.FLAG_URG))


def _packet_mask_val(pkt, pred, field, k):
    """One packet per row: (mask (n, k) bool, val (n, k) f32).

    The per-packet slice of the window kernel's predicate/field logic —
    the same branchless ops, minus the W axis."""
    n = pkt.shape[0]
    valid = pkt[:, F.PKT_VALID] > 0                        # (n,)
    direc = pkt[:, F.PKT_DIR]
    flags = pkt[:, F.PKT_FLAGS].astype(jnp.int32)
    v = valid[:, None]
    mask = v & (pred == F.PRED_TRUE)
    mask |= v & (pred == F.PRED_FWD) & (direc[:, None] == 0)
    mask |= v & (pred == F.PRED_BWD) & (direc[:, None] == 1)
    for code, bit in _FLAG_PREDS:
        mask |= v & (pred == code) & ((flags[:, None] & bit) > 0)
    val = jnp.zeros((n, k), jnp.float32)
    for c in range(F.PKT_NFIELDS):
        val = jnp.where(field == c, pkt[:, c][:, None], val)
    return mask, val


def _kernel(pkts_ref, op_ref, field_ref, pred_ref, init_ref, out_ref):
    """One flow block: ``pkts_ref`` (F, Bb, W) field-major, the per-slot
    rows (Bb, k).  A static loop over the k slots keeps every
    intermediate a 2-D (Bb, W) tile with the window on the lanes —
    Mosaic cannot lay out the 3-D (Bb, W, k) bool broadcasts of the
    reference."""
    valid = pkts_ref[F.PKT_VALID] > 0                      # (Bb, W)
    direc = pkts_ref[F.PKT_DIR]
    flags = pkts_ref[F.PKT_FLAGS].astype(jnp.int32)
    Bb, W = valid.shape
    pos = jax.lax.broadcasted_iota(jnp.int32, (Bb, W), 1)
    neg_big = jnp.float32(-3.4e38)
    pos_big = jnp.float32(3.4e38)
    for j in range(op_ref.shape[1]):
        op = op_ref[:, j:j + 1]                            # (Bb, 1)
        p = pred_ref[:, j:j + 1]
        fsel = field_ref[:, j:j + 1]
        mask = valid & (p == F.PRED_TRUE)
        mask |= valid & (p == F.PRED_FWD) & (direc == 0)
        mask |= valid & (p == F.PRED_BWD) & (direc == 1)
        for code, bit in _FLAG_PREDS:
            mask |= valid & (p == code) & ((flags & bit) > 0)
        val = jnp.zeros((Bb, W), jnp.float32)
        for c in range(F.PKT_NFIELDS):
            val = jnp.where(fsel == c, pkts_ref[c], val)

        mf = mask.astype(jnp.float32)
        # same canonical left-to-right order as the jnp reference, so the
        # kernel's registers are bit-identical to training-time features
        count = ordered_wsum(mf, keepdims=True)
        total = ordered_wsum(val * mf, keepdims=True)
        sumsq = ordered_wsum(val * val * mf, keepdims=True)
        mx = jnp.max(jnp.where(mask, val, neg_big), axis=1, keepdims=True)
        mx = jnp.where(mx <= neg_big, 0.0, mx)
        mn = jnp.min(jnp.where(mask, val, pos_big), axis=1, keepdims=True)
        mn = jnp.where(mn >= pos_big, init_ref[:, j:j + 1], mn)

        first_i = jnp.min(jnp.where(mask, pos, W), axis=1, keepdims=True)
        last_i = jnp.max(jnp.where(mask, pos, -1), axis=1, keepdims=True)
        # branchless select-at-index: the one packet at the index (no
        # masked packet selects nothing and falls back to 0.0)
        first = jnp.max(jnp.where(pos == first_i, val, neg_big), axis=1,
                        keepdims=True)
        first = jnp.where(first_i < W, first, 0.0)
        last = jnp.max(jnp.where(pos == last_i, val, neg_big), axis=1,
                       keepdims=True)
        last = jnp.where(last_i >= 0, last, 0.0)

        out = jnp.zeros((Bb, 1), jnp.float32)
        out = jnp.where(op == F.OP_COUNT, count, out)
        out = jnp.where(op == F.OP_SUM, total, out)
        out = jnp.where(op == F.OP_MAX, mx, out)
        out = jnp.where(op == F.OP_MIN, mn, out)
        out = jnp.where(op == F.OP_LAST, last, out)
        out = jnp.where(op == F.OP_FIRST, first, out)
        out = jnp.where(op == F.OP_SUMSQ, sumsq, out)
        out_ref[:, j:j + 1] = out


def window_block_rows(W: int, block_b: int = BLOCK_B) -> int:
    """Flow rows per window-kernel block: ``block_b``, halved until a
    block's ``rows * W`` fits :data:`WINDOW_VMEM_ELEMS`.

    The unrolled ``ordered_wsum`` chains keep one lane-padded column
    live per window position, so scoped VMEM grows with ``rows * W``
    (a v5e compile refused 128 rows at W=256: 40 MiB of a 16 MiB
    limit).  Never below 8 rows, the sublane tile."""
    bb = block_b
    while bb > 8 and bb * W > WINDOW_VMEM_ELEMS:
        bb = max(8, bb // 16 * 8)
    return bb


@functools.partial(jax.jit, static_argnames=("interpret", "block_b"))
def feature_window_pallas(
    pkts: jnp.ndarray,        # (B, W, PKT_NFIELDS) f32
    slot_op: jnp.ndarray,     # (B, k) int32 (pre-gathered by SID)
    slot_field: jnp.ndarray,  # (B, k)
    slot_pred: jnp.ndarray,   # (B, k)
    slot_init: jnp.ndarray,   # (B, k) f32
    *,
    interpret: bool = True,
    block_b: int = BLOCK_B,
) -> jnp.ndarray:
    B, W, nf = pkts.shape
    k = slot_op.shape[1]
    bb = min(window_block_rows(W, block_b), B)
    Bp = round_up(B, bb)
    if Bp != B:
        pkts, slot_op, slot_field, slot_pred, slot_init = (
            pad_axis0(x, Bp)
            for x in (pkts, slot_op, slot_field, slot_pred, slot_init))
    row = pl.BlockSpec((bb, k), lambda i: (i, 0))
    out = pl.pallas_call(
        _kernel,
        grid=(Bp // bb,),
        in_specs=[pl.BlockSpec((nf, bb, W), lambda i: (0, i, 0)),
                  row, row, row, row],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((Bp, k), jnp.float32),
        interpret=interpret,
        name="feature_window_pallas",
    )(jnp.moveaxis(pkts, 2, 0), slot_op, slot_field, slot_pred, slot_init)
    return out[:B]


# ---------------------------------------------------------------------------
# incremental per-packet update step (flow-table serving)
# ---------------------------------------------------------------------------
#
# The live flow table folds ONE packet at a time into resident per-slot
# window state ``(acc, seen)`` instead of rebuilding the window — see
# ``kernels.ref.feature_update_ref`` (the dense oracle, whose docstring
# carries the bit-identity argument) and docs/PARITY.md.  This kernel
# is the blocked Pallas form of the same fold: the gathered state rows
# and the packet batch live in VMEM; the table-wide scatter
# (gather rows → update → ``.at[slots].set``) happens outside in jnp
# (``feature_update_at``), mirroring how ``dispatch_dt_traverse`` keeps
# the routing in XLA and the arithmetic in the kernel.


def _update_kernel(pkt_ref, op_ref, field_ref, pred_ref, acc_ref, seen_ref,
                   acc_out, seen_out):
    pkt = pkt_ref[...]                                     # (Bb, F)
    op = op_ref[...]                                       # (Bb, k)
    field = field_ref[...]
    pred = pred_ref[...]
    acc = acc_ref[...]
    seen = seen_ref[...]
    k = op.shape[1]

    mask, val = _packet_mask_val(pkt, pred, field, k)
    mf = mask.astype(jnp.float32)
    # identical op-by-op folds to feature_update_ref, so the Pallas and
    # dense paths stay bit-identical packet by packet
    additive = ((op == F.OP_COUNT) | (op == F.OP_SUM) | (op == F.OP_SUMSQ))
    contrib = jnp.where(op == F.OP_COUNT, mf,
                        jnp.where(op == F.OP_SUM, val * mf, val * val * mf))
    out = jnp.where(additive, acc + contrib, acc)
    out = jnp.where((op == F.OP_MAX) & mask, jnp.maximum(acc, val), out)
    out = jnp.where((op == F.OP_MIN) & mask, jnp.minimum(acc, val), out)
    out = jnp.where((op == F.OP_FIRST) & mask & (seen == 0), val, out)
    out = jnp.where((op == F.OP_LAST) & mask, val, out)
    acc_out[...] = out.astype(jnp.float32)
    seen_out[...] = seen | mask.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret", "block_b"))
def feature_update_pallas(
    pkt: jnp.ndarray,         # (B, PKT_NFIELDS) f32, ONE packet per row
    slot_op: jnp.ndarray,     # (B, k) int32 (pre-gathered by SID)
    slot_field: jnp.ndarray,  # (B, k)
    slot_pred: jnp.ndarray,   # (B, k)
    acc: jnp.ndarray,         # (B, k) f32 running window state
    seen: jnp.ndarray,        # (B, k) int32
    *,
    interpret: bool = True,
    block_b: int = BLOCK_B,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fold one packet per row into ``(acc, seen)``; returns new state.

    Padding rows (all-zero packets, valid = 0) pass their state through
    untouched up to signed zero — the same invariant the window kernel
    gives padded packets."""
    B, nf = pkt.shape
    k = slot_op.shape[1]
    bb = min(block_b, B)
    Bp = round_up(B, bb)
    if Bp != B:
        pkt, slot_op, slot_field, slot_pred, acc, seen = (
            pad_axis0(x, Bp)
            for x in (pkt, slot_op, slot_field, slot_pred, acc, seen))
    grid = (Bp // bb,)
    row = pl.BlockSpec((bb, k), lambda i: (i, 0))
    acc2, seen2 = pl.pallas_call(
        _update_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bb, nf), lambda i: (i, 0)),
                  row, row, row, row, row],
        out_specs=[row, row],
        out_shape=[jax.ShapeDtypeStruct((Bp, k), jnp.float32),
                   jax.ShapeDtypeStruct((Bp, k), jnp.int32)],
        interpret=interpret,
        name="feature_update_pallas",
    )(pkt, slot_op, slot_field, slot_pred, acc, seen)
    return acc2[:B], seen2[:B]


def _update_finalize_kernel(pkt_ref, op_ref, field_ref, pred_ref,
                            init_ref, acc_ref, seen_ref,
                            acc_out, seen_out, regs_out):
    """Fused fold + finalize: one VMEM pass per packet-rank.

    The tick engine (``kernels.tick_step``) hops a slot in the same
    dispatch that folded its window-completing packet, so the kernel
    emits the finalized registers alongside the new ``(acc, seen)`` —
    op-by-op identical to ``feature_update_ref`` followed by
    ``feature_finalize_ref``, so the fused path stays bit-identical to
    the two-step fold."""
    pkt = pkt_ref[...]                                     # (Bb, F)
    op = op_ref[...]                                       # (Bb, k)
    field = field_ref[...]
    pred = pred_ref[...]
    init = init_ref[...]
    acc = acc_ref[...]
    seen = seen_ref[...]
    k = op.shape[1]

    mask, val = _packet_mask_val(pkt, pred, field, k)
    mf = mask.astype(jnp.float32)
    additive = ((op == F.OP_COUNT) | (op == F.OP_SUM) | (op == F.OP_SUMSQ))
    contrib = jnp.where(op == F.OP_COUNT, mf,
                        jnp.where(op == F.OP_SUM, val * mf, val * val * mf))
    out = jnp.where(additive, acc + contrib, acc)
    out = jnp.where((op == F.OP_MAX) & mask, jnp.maximum(acc, val), out)
    out = jnp.where((op == F.OP_MIN) & mask, jnp.minimum(acc, val), out)
    out = jnp.where((op == F.OP_FIRST) & mask & (seen == 0), val, out)
    out = jnp.where((op == F.OP_LAST) & mask, val, out)
    out = out.astype(jnp.float32)
    seen2 = seen | mask.astype(jnp.int32)
    # finalize: the empty-window fallbacks of feature_finalize_ref
    empty = seen2 == 0
    regs = jnp.where((op == F.OP_MAX) & empty, 0.0, out)
    regs = jnp.where((op == F.OP_MIN) & empty, init, regs)
    regs = jnp.where(((op == F.OP_FIRST) | (op == F.OP_LAST)) & empty,
                     0.0, regs)
    acc_out[...] = out
    seen_out[...] = seen2
    regs_out[...] = regs.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("interpret", "block_b"))
def feature_update_finalize_pallas(
    pkt: jnp.ndarray,         # (B, PKT_NFIELDS) f32, ONE packet per row
    slot_op: jnp.ndarray,     # (B, k) int32 (pre-gathered by SID)
    slot_field: jnp.ndarray,  # (B, k)
    slot_pred: jnp.ndarray,   # (B, k)
    slot_init: jnp.ndarray,   # (B, k) f32 (MIN's empty-window fallback)
    acc: jnp.ndarray,         # (B, k) f32 running window state
    seen: jnp.ndarray,        # (B, k) int32
    *,
    interpret: bool = True,
    block_b: int = BLOCK_B,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fold one packet per row AND finalize: ``(acc2, seen2, regs)``.

    ``regs`` equals ``feature_finalize_ref(acc2, seen2, ...)`` bit for
    bit; rows whose window did not complete simply ignore it.  Padding
    rows pass state through untouched up to signed zero, as in
    :func:`feature_update_pallas`."""
    B, nf = pkt.shape
    k = slot_op.shape[1]
    bb = min(block_b, B)
    Bp = round_up(B, bb)
    if Bp != B:
        pkt, slot_op, slot_field, slot_pred, slot_init, acc, seen = (
            pad_axis0(x, Bp)
            for x in (pkt, slot_op, slot_field, slot_pred, slot_init,
                      acc, seen))
    grid = (Bp // bb,)
    row = pl.BlockSpec((bb, k), lambda i: (i, 0))
    acc2, seen2, regs = pl.pallas_call(
        _update_finalize_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bb, nf), lambda i: (i, 0)),
                  row, row, row, row, row, row],
        out_specs=[row, row, row],
        out_shape=[jax.ShapeDtypeStruct((Bp, k), jnp.float32),
                   jax.ShapeDtypeStruct((Bp, k), jnp.int32),
                   jax.ShapeDtypeStruct((Bp, k), jnp.float32)],
        interpret=interpret,
        name="feature_update_finalize_pallas",
    )(pkt, slot_op, slot_field, slot_pred, slot_init, acc, seen)
    return acc2[:B], seen2[:B], regs[:B]


def feature_update_at(
    acc_tab: jnp.ndarray,     # (N, k) f32 resident state table
    seen_tab: jnp.ndarray,    # (N, k) int32
    slots: jnp.ndarray,       # (n,) int32 UNIQUE row indices into the table
    pkt: jnp.ndarray,         # (n, PKT_NFIELDS)
    slot_op: jnp.ndarray,     # (n, k) — pre-gathered for each slot's SID
    slot_field: jnp.ndarray,
    slot_pred: jnp.ndarray,
    *,
    interpret: bool = True,
    block_b: int = BLOCK_B,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Scatter-update: fold one packet into each addressed table row.

    Gather the state rows, run the Pallas update step, scatter the new
    state back.  ``slots`` must address each real row at most once per
    call (the flow table's rank batches guarantee it); duplicate
    *padding* indices are safe — padded rows compute identical values,
    so the scatter is order-independent."""
    a2, s2 = feature_update_pallas(
        pkt, slot_op, slot_field, slot_pred, acc_tab[slots], seen_tab[slots],
        interpret=interpret, block_b=block_b)
    return acc_tab.at[slots].set(a2), seen_tab.at[slots].set(s2)
