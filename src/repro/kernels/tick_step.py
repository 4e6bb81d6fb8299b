"""Device-resident tick engine: one dispatch per serving tick.

The live flow table (``repro.serve.flowtable``) serves each tick —
every packet *rank* (the r-th packet of each flow in the tick) and
every hop-drain round — in ONE jitted call, with no host round trip
between ranks:

  * **state** (:class:`TickState`) is a device-resident pytree holding
    the per-slot window registers AND the per-flow walk metadata
    (``sid``, ``part``, ``win_lo`` / ``win_hi``, ``pkts_seen``,
    ``recircs``) plus a ``retired`` flag and
    the per-flow window ``bounds`` table.  Row ``N`` (one past the table
    capacity) is the dummy row every padded or masked scatter lands on;
  * **admission** (:func:`admit_rows`) re-initialises newly admitted
    slots in one scatter, computing ``flows.windows.window_bounds`` with
    in-jit int32 math (bit-for-bit the host formula);
  * **the tick step** (:func:`tick_step`) works on the tick's
    **working set**: its C columns, one per distinct slot of the tick.
    It gathers every per-slot field of those rows once, runs the rank
    loop as a ``lax.scan`` over the rank-major ``(R, C)`` packets on
    that ``(C, ...)`` carry (the table is never in the carry), and
    writes the rows back with one scatter per field at the end, so its
    indexed table traffic is one gather and one scatter of C rows
    whatever R is.  Each rank folds one packet per column (the
    incremental update of ``kernels.ref.feature_update_ref`` or the
    fused Pallas fold+finalize kernel), then hops every column whose
    window completed: finalize → subtree traverse → the walk's own
    ``core.inference._hop_update`` bookkeeping.  Empty trailing
    windows (flows shorter than P packets) drain inside an in-jit
    bounded ``lax.while_loop`` — the partition index strictly advances
    every round, so ``P`` is a static trip bound;
  * **verdicts** accumulate per column, ``(C,)`` each; the server
    issues one bulk ``device_get`` per tick (5 x C int32, not a
    table-sized buffer), maps columns to slots through ``cols`` and
    frees the finished slots host-side.

Parity (docs/PARITY.md §5): every per-row computation here is the
row-wise kernel math of the batch walk's incremental fold, finalize
and traverse — gathers, masks and the column layout route rows, they
never change values — so tick verdicts are bit-identical to
``Engine.run`` on rebuilt windows.  A column whose rank is empty, whose
flow already retired, or whose window did not complete keeps its row
(``where``); padding columns read the dummy row and write it back
unchanged, so the write-back's duplicate indices agree.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.features import PKT_IAT
from repro.core.inference import _hop_update
from repro.kernels import ops
from repro.kernels import ref as _ref
from repro.kernels.dispatch import dispatch_dt_traverse
from repro.kernels.feature_window import feature_update_finalize_pallas


class TickState(NamedTuple):
    """Device-resident per-slot serving state (``N + 1`` rows).

    The last row is the dummy row: padded admission rows and the tick's
    padding columns address it so every device op keeps a static shape.
    The tick's working set is a ``TickState`` of C rows.  ``bounds``
    caches each flow's per-partition window ``[lo, hi)`` so hops never
    need the host.
    """
    acc: jnp.ndarray        # (N+1, k) f32 running window registers
    seen: jnp.ndarray       # (N+1, k) int32 "matched yet" bits
    sid: jnp.ndarray        # (N+1,) int32 active subtree id
    part: jnp.ndarray       # (N+1,) int32 active partition index
    win_lo: jnp.ndarray     # (N+1,) int32 active window start (packets)
    win_hi: jnp.ndarray     # (N+1,) int32 active window end
    pkts_seen: jnp.ndarray  # (N+1,) int32 packets folded so far
    recircs: jnp.ndarray    # (N+1,) int32 partition transitions
    retired: jnp.ndarray    # (N+1,) int32 1 = verdict emitted this epoch
    bounds: jnp.ndarray     # (N+1, P, 2) int32 per-partition windows


@functools.partial(jax.jit, static_argnames=("n", "n_partitions"))
def init_tick_state(dev: ops.DeviceTables, n: int,
                    n_partitions: int) -> TickState:
    """Blank state for ``n`` rows (capacity + dummy), root SID 0."""
    op = jnp.broadcast_to(dev.slot_op[0][None, :], (n, dev.slot_op.shape[1]))
    acc, seen = _ref.feature_state_init(op)
    z = jnp.zeros(n, jnp.int32)
    return TickState(acc, seen, z, z, z, z, z, z, z,
                     jnp.zeros((n, n_partitions, 2), jnp.int32))


@jax.jit
def admit_rows(state: TickState, slots: jnp.ndarray,
               lengths: jnp.ndarray, dev: ops.DeviceTables) -> TickState:
    """Re-initialise newly admitted slots in one scatter.

    ``slots`` (m,) int32 row indices (dummy-padded; real entries are
    unique), ``lengths`` (m,) int32 flow lengths (padding rows carry 1,
    so every dummy duplicate computes identical values).  The window
    bounds replicate ``flows.windows.window_bounds`` in int32 — same
    floor-div/min formula, so the device plan is bit-identical to the
    host's.
    """
    P = state.bounds.shape[1]
    length = jnp.maximum(lengths.astype(jnp.int32), 1)
    base = jnp.maximum(length // P, 1)
    w = jnp.arange(P, dtype=jnp.int32)[None, :]
    lo = jnp.minimum(w * base[:, None], length[:, None])
    hi = jnp.minimum((w + 1) * base[:, None], length[:, None])
    hi = hi.at[:, P - 1].set(length)
    k = dev.slot_op.shape[1]
    a0, s0 = _ref.feature_state_init(
        jnp.broadcast_to(dev.slot_op[0][None, :], (slots.shape[0], k)))
    z = jnp.zeros(slots.shape[0], jnp.int32)
    return TickState(
        acc=state.acc.at[slots].set(a0),
        seen=state.seen.at[slots].set(s0),
        sid=state.sid.at[slots].set(z),
        part=state.part.at[slots].set(z),
        win_lo=state.win_lo.at[slots].set(lo[:, 0]),
        win_hi=state.win_hi.at[slots].set(hi[:, 0]),
        pkts_seen=state.pkts_seen.at[slots].set(z),
        recircs=state.recircs.at[slots].set(z),
        retired=state.retired.at[slots].set(z),
        bounds=state.bounds.at[slots].set(jnp.stack([lo, hi], axis=-1)),
    )


def _traverse(regs, sid_rows, dev, *, pallas: bool, block_b: int):
    """Subtree traversal for one hop round (dense gather or Pallas)."""
    if pallas:
        return dispatch_dt_traverse(
            regs, sid_rows, dev.thresholds, dev.leaf_lo, dev.leaf_hi,
            dev.leaf_action, dev.leaf_valid,
            interpret=not ops._on_tpu(), block_b=block_b)
    return _ref.dt_traverse_ref(
        regs, dev.thresholds[sid_rows], dev.leaf_lo[sid_rows],
        dev.leaf_hi[sid_rows], dev.leaf_action[sid_rows],
        dev.leaf_valid[sid_rows] > 0)


def _hop_round(ws: TickState, vm, vl, vr, ve, regs, complete, dev, *,
               n_subtrees: int, pallas: bool, block_b: int):
    """One hop for the columns whose ``complete`` bit is set.

    ``ws`` is the tick's working set (one row per column); ``regs``
    (C, k) are the finalized registers of the completing columns (the
    rest may carry anything — their traversal output is discarded by
    the ``complete`` masks).  Runs traverse + ``_hop_update``, records
    verdicts for exiting / fell-off-the-last-partition flows in the
    per-column buffers, advances the survivors' partition/window/SID,
    and returns the ``complete`` mask for the next drain round (flows
    whose new window is empty).
    """
    P = ws.bounds.shape[1]
    action = _traverse(regs, ws.sid, dev, pallas=pallas, block_b=block_b)
    carry = (ws.sid, ~complete, jnp.full(ws.sid.shape, -1, jnp.int32),
             ws.recircs, jnp.full(ws.sid.shape, -1, jnp.int32))
    sid2, done2, labels, rec2, exit_p = _hop_update(
        carry, ws.part, action, n_subtrees)
    exited = complete & done2
    fell = complete & ~done2 & (ws.part == P - 1)
    adv = complete & ~done2 & (ws.part < P - 1)
    newdone = exited | fell

    # verdicts: a column finishes at most once per tick (admission
    # precedes folding, so no within-tick reuse)
    vm = jnp.where(newdone, 1, vm)
    vl = jnp.where(newdone, labels, vl)     # -1 unless the flow exited
    vr = jnp.where(newdone, rec2, vr)
    ve = jnp.where(newdone, exit_p, ve)     # -1 unless the flow exited

    # survivors advance to the next partition's window; finished rows
    # keep their metadata (the host frees their slots after the fetch)
    new_part = jnp.where(adv, ws.part + 1, ws.part)
    nb = jnp.take_along_axis(
        ws.bounds, jnp.minimum(new_part, P - 1)[:, None, None],
        axis=1)[:, 0]                                        # (C, 2)
    a0, s0 = _ref.feature_state_init(dev.slot_op[sid2])
    c2 = complete[:, None]
    ws = ws._replace(
        acc=jnp.where(c2, a0, ws.acc),
        seen=jnp.where(c2, s0, ws.seen),
        sid=jnp.where(complete, sid2, ws.sid),
        part=new_part,
        win_lo=jnp.where(adv, nb[:, 0], ws.win_lo),
        win_hi=jnp.where(adv, nb[:, 1], ws.win_hi),
        recircs=jnp.where(complete, rec2, ws.recircs),
        retired=jnp.where(newdone, 1, ws.retired),
    )
    return ws, vm, vl, vr, ve, adv & (ws.win_lo == ws.win_hi)


#: fields the tick writes back; ``bounds`` is read-only in the tick
_WRITTEN = tuple(f for f in TickState._fields if f != "bounds")


@functools.partial(jax.jit,
                   static_argnames=("n_subtrees", "pallas", "block_b"))
def tick_step(state: TickState, cols: jnp.ndarray, n_pkts: jnp.ndarray,
              pkt_rc: jnp.ndarray, dev: ops.DeviceTables, *,
              n_subtrees: int, pallas: bool, block_b: int):
    """One ingest tick: fold every rank, hop every completed window.

    ``cols`` (C,) int32 holds the tick's distinct slots, one per column
    (padding columns address the dummy row), ``n_pkts`` (C,) int32 the
    column's packets in the tick (0 for padding) and ``pkt_rc``
    (R, C, F) those packets rank-major: rank r holds the r-th packet of
    each column's flow, the per-flow arrival order the parity contract
    pins.  The tick gathers its columns' rows once, scans the ranks on
    that ``(C, ...)`` working set, and writes the rows back in one
    scatter per field.  Returns the new state plus ``(verdict_mask,
    labels, recircs, exit_partition, recircs_snapshot)``, each ``(C,)``
    and indexed like ``cols``, for ONE bulk ``device_get``: columns
    with ``verdict_mask == 1`` finished this tick (exit or fell-off
    sentinels), ``recircs_snapshot`` is the columns' live recirculation
    counts for host-side flush/timeout sentinels.
    """
    P = state.bounds.shape[1]
    C = cols.shape[0]
    ws = TickState(*(a[cols] for a in state))
    v0 = (jnp.zeros(C, jnp.int32), jnp.full(C, -1, jnp.int32),
          jnp.zeros(C, jnp.int32), jnp.full(C, -1, jnp.int32))

    def rank_body(carry, xs):
        ws, vm, vl, vr, ve = carry
        r, pkt = xs
        # a flow that finished earlier this tick must not fold its late
        # packets (malformed flow_len) into the slot's state: the
        # retired bit is the device form of the host's key check
        live = (r < n_pkts) & (ws.retired == 0)
        pkt = jnp.where(live[:, None], pkt, 0.0)
        # window boundary clears the dependency chain (first-packet
        # IAT = 0), matching flows.windows.window_packets
        first = ws.pkts_seen == ws.win_lo
        pkt = pkt.at[:, PKT_IAT].set(jnp.where(first, 0.0, pkt[:, PKT_IAT]))
        op = dev.slot_op[ws.sid]
        fld = dev.slot_field[ws.sid]
        prd = dev.slot_pred[ws.sid]
        init = dev.slot_init[ws.sid]
        if pallas:
            acc2, seen2, regs = feature_update_finalize_pallas(
                pkt, op, fld, prd, init, ws.acc, ws.seen,
                interpret=not ops._on_tpu(), block_b=block_b)
        else:
            acc2, seen2 = _ref.feature_update_ref(
                pkt, op, fld, prd, ws.acc, ws.seen)
            regs = _ref.feature_finalize_ref(acc2, seen2, op, init)
        pkts_seen = ws.pkts_seen + live.astype(jnp.int32)
        ws = ws._replace(acc=jnp.where(live[:, None], acc2, ws.acc),
                         seen=jnp.where(live[:, None], seen2, ws.seen),
                         pkts_seen=pkts_seen)
        complete = live & (pkts_seen == ws.win_hi)

        # the window-completing hop rides the SAME dispatch as the fold
        # (regs already finalized above); drain rounds only ever see
        # empty windows, whose registers finalize from blank state
        ws, vm, vl, vr, ve, nxt = _hop_round(
            ws, vm, vl, vr, ve, regs, complete, dev,
            n_subtrees=n_subtrees, pallas=pallas, block_b=block_b)

        def drain_cond(c):
            return jnp.any(c[5]) & (c[6] < P)

        def drain_body(c):
            ws, vm, vl, vr, ve, comp, trip = c
            regs = _ref.feature_finalize_ref(
                ws.acc, ws.seen, dev.slot_op[ws.sid], dev.slot_init[ws.sid])
            ws, vm, vl, vr, ve, comp = _hop_round(
                ws, vm, vl, vr, ve, regs, comp, dev,
                n_subtrees=n_subtrees, pallas=pallas, block_b=block_b)
            return ws, vm, vl, vr, ve, comp, trip + 1

        # bounded: each round advances every completing flow's
        # partition, so at most P-1 iterations run (trip is a backstop)
        ws, vm, vl, vr, ve, _, _ = jax.lax.while_loop(
            drain_cond, drain_body,
            (ws, vm, vl, vr, ve, nxt, jnp.int32(0)))
        return (ws, vm, vl, vr, ve), None

    R = pkt_rc.shape[0]
    (ws, vm, vl, vr, ve), _ = jax.lax.scan(
        rank_body, (ws,) + v0, (jnp.arange(R, dtype=jnp.int32), pkt_rc))
    # padding columns all address the dummy row; none of them is ever
    # live, so each writes back the row it gathered and the duplicate
    # writes agree
    state = state._replace(**{f: getattr(state, f).at[cols].set(
        getattr(ws, f)) for f in _WRITTEN})
    return state, (vm, vl, vr, ve, ws.recircs)
