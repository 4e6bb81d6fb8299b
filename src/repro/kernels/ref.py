"""Pure-jnp oracles for every Pallas kernel.

These are the correctness references: simple, obviously-right
implementations with no tiling, used by tests (`assert_allclose` against
the kernels in interpret mode) and as the CPU fallback path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import features as F

# ---------------------------------------------------------------------------
# feature_window: windowed stateful feature accumulation
# ---------------------------------------------------------------------------

_UNROLL_W = 256


def ordered_wsum(x: jnp.ndarray, *, keepdims: bool = False) -> jnp.ndarray:
    """Strict left-to-right f32 sum over the window axis (axis 1).

    The canonical reduction order shared by the offline feature pipeline
    (``window_features``, 41-slot tensor), both engines' k-slot
    reduction, and the Pallas kernel.  A plain ``.sum(axis=1)`` lets XLA
    pick a shape-dependent summation tree, and a last-ulp difference can
    flip a flow sitting exactly on a learned threshold; chaining the
    adds pins the order for every (B, W, k) shape, so training-time
    features and runtime registers agree bit-exactly.  ``keepdims``
    keeps the summed axis at size 1 (the Pallas kernel sums 2-D
    ``(Bb, W)`` tiles into ``(Bb, 1)`` columns).
    """
    W = x.shape[1]
    if W <= _UNROLL_W:          # trace-time unroll: W-1 chained adds
        acc = x[:, 0:1]
        for w in range(1, W):
            acc = acc + x[:, w:w + 1]
    else:                       # same left-to-right order, rolled
        acc = jax.lax.fori_loop(
            1, W,
            lambda w, acc: acc + jax.lax.dynamic_slice_in_dim(x, w, 1, 1),
            x[:, 0:1])
    return acc if keepdims else acc[:, 0]


def _pred_mask(pkts: jnp.ndarray, pred: jnp.ndarray) -> jnp.ndarray:
    """pkts (B, W, F), pred (B, k) codes -> (B, W, k) bool."""
    valid = pkts[..., F.PKT_VALID] > 0                      # (B, W)
    direc = pkts[..., F.PKT_DIR]
    flags = pkts[..., F.PKT_FLAGS].astype(jnp.int32)
    p = pred[:, None, :]                                    # (B, 1, k)
    v = valid[:, :, None]
    out = v & (p == F.PRED_TRUE)
    out |= v & (p == F.PRED_FWD) & (direc[:, :, None] == 0)
    out |= v & (p == F.PRED_BWD) & (direc[:, :, None] == 1)
    for code, bit in ((F.PRED_SYN, F.FLAG_SYN), (F.PRED_ACK, F.FLAG_ACK),
                      (F.PRED_FIN, F.FLAG_FIN), (F.PRED_RST, F.FLAG_RST),
                      (F.PRED_PSH, F.FLAG_PSH), (F.PRED_URG, F.FLAG_URG)):
        out |= v & (p == code) & ((flags[:, :, None] & bit) > 0)
    return out


def _field_vals(pkts: jnp.ndarray, field: jnp.ndarray) -> jnp.ndarray:
    """pkts (B, W, F), field (B, k) codes -> (B, W, k) selected field."""
    f = field[:, None, :]
    out = jnp.zeros(pkts.shape[:2] + (field.shape[1],), pkts.dtype)
    for c in range(F.PKT_NFIELDS):
        out = jnp.where(f == c, pkts[..., c][:, :, None], out)
    return out


def feature_window_ref(
    pkts: jnp.ndarray,       # (B, W, PKT_NFIELDS)
    slot_op: jnp.ndarray,    # (B, k) per-flow op codes (pre-gathered by SID)
    slot_field: jnp.ndarray, # (B, k)
    slot_pred: jnp.ndarray,  # (B, k)
    slot_init: jnp.ndarray,  # (B, k)
) -> jnp.ndarray:
    """Branchless windowed register update; returns regs (B, k) f32."""
    mask = _pred_mask(pkts, slot_pred)                       # (B, W, k)
    val = _field_vals(pkts, slot_field)                      # (B, W, k)
    mf = mask.astype(jnp.float32)

    count = ordered_wsum(mf)
    total = ordered_wsum(val * mf)
    sumsq = ordered_wsum(val * val * mf)
    mx = jnp.where(mask, val, -jnp.inf).max(axis=1)
    mx = jnp.where(jnp.isfinite(mx), mx, 0.0)
    mn = jnp.where(mask, val, jnp.inf).min(axis=1)
    mn = jnp.where(jnp.isfinite(mn), mn, slot_init)
    W = pkts.shape[1]
    pos = jnp.arange(W, dtype=jnp.int32)[None, :, None]
    first_i = jnp.where(mask, pos, W).min(axis=1)
    last_i = jnp.where(mask, pos, -1).max(axis=1)
    any_ = mask.any(axis=1)
    first = jnp.where(any_, jnp.take_along_axis(
        val, jnp.minimum(first_i, W - 1)[:, None, :], axis=1)[:, 0, :], 0.0)
    last = jnp.where(any_, jnp.take_along_axis(
        val, jnp.maximum(last_i, 0)[:, None, :], axis=1)[:, 0, :], 0.0)

    op = slot_op
    out = jnp.zeros_like(total)
    out = jnp.where(op == F.OP_COUNT, count, out)
    out = jnp.where(op == F.OP_SUM, total, out)
    out = jnp.where(op == F.OP_MAX, mx, out)
    out = jnp.where(op == F.OP_MIN, mn, out)
    out = jnp.where(op == F.OP_LAST, last, out)
    out = jnp.where(op == F.OP_FIRST, first, out)
    out = jnp.where(op == F.OP_SUMSQ, sumsq, out)
    return out.astype(jnp.float32)


# ---------------------------------------------------------------------------
# feature_update: incremental per-packet window state (flow-table serving)
# ---------------------------------------------------------------------------
#
# The live flow table (repro.serve.flowtable) cannot rebuild a window
# from scratch on every packet, so the window reduction is re-expressed
# as a left fold over arrival order with per-slot state ``(acc, seen)``.
# Bit-identity with :func:`feature_window_ref` (docs/PARITY.md) follows
# from the reduction orders being the SAME chain:
#
#   * COUNT/SUM/SUMSQ: ``ordered_wsum`` is the left-to-right f32 chain
#     ``x0 + x1 + ...``; the fold computes ``0.0 + x0 + x1 + ...`` and
#     skips the trailing padding terms — both differences only map
#     ``-0.0`` to ``+0.0`` (``0.0 + x == x`` for every other f32), and
#     signed zeros compare equal everywhere downstream (thresholds,
#     ``assert_array_equal``);
#   * MAX/MIN are order-independent; the fold carries the same
#     ±inf "empty" sentinel the reference builds via where(mask);
#   * FIRST latches on the first masked packet, LAST overwrites on
#     every masked packet — exactly the reference's index selects;
#   * finalisation reproduces the reference's empty-window fallbacks
#     (MAX→0, MIN→slot_init, FIRST/LAST→0) from the ``seen`` bit.


def feature_state_init(slot_op: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Blank per-slot window state for the incremental fold.

    ``slot_op`` (n, k) op codes -> ``(acc (n, k) f32, seen (n, k)
    int32)``.  MAX/MIN start at the identity of their reduction (∓inf);
    every additive op starts at 0.0 (the same +0.0 the reference
    chain's padding terms produce).
    """
    acc = jnp.where(slot_op == F.OP_MAX, -jnp.inf,
                    jnp.where(slot_op == F.OP_MIN, jnp.inf, 0.0))
    return acc.astype(jnp.float32), jnp.zeros(slot_op.shape, jnp.int32)


def feature_update_ref(
    pkt: jnp.ndarray,        # (n, PKT_NFIELDS) ONE packet per flow/slot
    slot_op: jnp.ndarray,    # (n, k) per-slot op codes (gathered by SID)
    slot_field: jnp.ndarray, # (n, k)
    slot_pred: jnp.ndarray,  # (n, k)
    acc: jnp.ndarray,        # (n, k) f32 running state
    seen: jnp.ndarray,       # (n, k) int32 "any masked packet yet" bit
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fold one packet per row into the running window state.

    Invalid packets (valid = 0 — e.g. padding rows in a batched scatter
    update) leave the state unchanged up to signed zero, exactly like
    the reference chain's masked terms.  Returns the new ``(acc,
    seen)``.
    """
    mask = _pred_mask(pkt[:, None, :], slot_pred)[:, 0]      # (n, k)
    val = _field_vals(pkt[:, None, :], slot_field)[:, 0]     # (n, k)
    mf = mask.astype(jnp.float32)
    op = slot_op
    additive = ((op == F.OP_COUNT) | (op == F.OP_SUM) | (op == F.OP_SUMSQ))
    contrib = jnp.where(op == F.OP_COUNT, mf,
                        jnp.where(op == F.OP_SUM, val * mf, val * val * mf))
    out = jnp.where(additive, acc + contrib, acc)
    out = jnp.where((op == F.OP_MAX) & mask, jnp.maximum(acc, val), out)
    out = jnp.where((op == F.OP_MIN) & mask, jnp.minimum(acc, val), out)
    out = jnp.where((op == F.OP_FIRST) & mask & (seen == 0), val, out)
    out = jnp.where((op == F.OP_LAST) & mask, val, out)
    return out.astype(jnp.float32), seen | mask.astype(jnp.int32)


def feature_finalize_ref(
    acc: jnp.ndarray,        # (n, k) f32 folded state
    seen: jnp.ndarray,       # (n, k) int32
    slot_op: jnp.ndarray,    # (n, k)
    slot_init: jnp.ndarray,  # (n, k) f32 (MIN's empty-window fallback)
) -> jnp.ndarray:
    """Folded state -> registers, bit-identical to the rebuilt window."""
    op = slot_op
    empty = seen == 0
    out = jnp.where((op == F.OP_MAX) & empty, 0.0, acc)
    out = jnp.where((op == F.OP_MIN) & empty, slot_init, out)
    out = jnp.where(((op == F.OP_FIRST) | (op == F.OP_LAST)) & empty,
                    0.0, out)
    return out.astype(jnp.float32)


def feature_update_finalize_ref(pkt, slot_op, slot_field, slot_pred,
                                slot_init, acc, seen):
    """Fold one packet per row AND finalize: ``(acc2, seen2, regs)``.

    The composed oracle for the fused tick-step kernel
    (``kernels.feature_window.feature_update_finalize_pallas``): exactly
    :func:`feature_update_ref` followed by :func:`feature_finalize_ref`
    on the updated state.
    """
    acc2, seen2 = feature_update_ref(pkt, slot_op, slot_field, slot_pred,
                                     acc, seen)
    return acc2, seen2, feature_finalize_ref(acc2, seen2, slot_op, slot_init)


# ---------------------------------------------------------------------------
# dt_traverse: range-mark matching (grouped by SID outside the kernel)
# ---------------------------------------------------------------------------


def dt_traverse_ref(
    regs: jnp.ndarray,        # (B, k) feature registers
    thresholds: jnp.ndarray,  # (B, k, T) per-flow subtree thresholds (+inf pad)
    leaf_lo: jnp.ndarray,     # (B, L, k)
    leaf_hi: jnp.ndarray,     # (B, L, k)
    leaf_action: jnp.ndarray, # (B, L) int32, -1 padding
    leaf_valid: jnp.ndarray,  # (B, L) bool
) -> jnp.ndarray:
    """Range-marking execution; returns action (B,) int32."""
    marks = (regs[:, :, None] > thresholds).sum(axis=2).astype(jnp.int32)  # (B,k)
    m = marks[:, None, :]                                    # (B, 1, k)
    hit = (m >= leaf_lo) & (m <= leaf_hi)                    # (B, L, k)
    hit = hit.all(axis=2) & leaf_valid                       # (B, L)
    L = hit.shape[1]
    first = jnp.where(hit, jnp.arange(L, dtype=jnp.int32)[None, :],
                      L).min(axis=1)
    safe = jnp.minimum(first, L - 1)
    action = jnp.take_along_axis(leaf_action, safe[:, None], axis=1)[:, 0]
    return jnp.where(first < L, action, -1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# chunk_scan: gated linear recurrence (RWKV6 / Mamba2-SSD family)
# ---------------------------------------------------------------------------


def chunk_scan_ref(
    q: jnp.ndarray,      # (B, T, dk)
    k: jnp.ndarray,      # (B, T, dk)
    v: jnp.ndarray,      # (B, T, dv)
    decay: jnp.ndarray,  # (B, T, dk) in (0, 1]; per-channel data-dependent
    bonus: jnp.ndarray | None = None,   # (B, dk) RWKV6 "u" or None
    state: jnp.ndarray | None = None,   # (B, dk, dv) initial state
):
    """Naive per-token recurrence (the oracle).

        S_t = diag(decay_t) S_{t-1} + k_t^T v_t
        o_t = q_t (S_{t-1} + diag(bonus) k_t^T v_t)   [RWKV6 bonus form]
    With bonus=None: o_t = q_t S_t (GLA/SSD form).

    Returns (o (B, T, dv), final_state (B, dk, dv)).
    """
    B, T, dk = q.shape
    dv = v.shape[-1]
    if state is None:
        state = jnp.zeros((B, dk, dv), jnp.float32)

    def step(S, xs):
        qt, kt, vt, wt = xs
        kv = kt[:, :, None] * vt[:, None, :]                 # (B, dk, dv)
        if bonus is not None:
            o = jnp.einsum("bk,bkv->bv", qt, S + bonus[:, :, None] * kv)
            S = wt[:, :, None] * S + kv
        else:
            S = wt[:, :, None] * S + kv
            o = jnp.einsum("bk,bkv->bv", qt, S)
        return S, o

    xs = (q.transpose(1, 0, 2), k.transpose(1, 0, 2),
          v.transpose(1, 0, 2), decay.transpose(1, 0, 2))
    final, o = jax.lax.scan(step, state.astype(jnp.float32), xs)
    return o.transpose(1, 0, 2).astype(v.dtype), final


def chunk_scan_chunked_ref(q, k, v, decay, bonus=None, state=None, chunk: int = 64):
    """Chunked (parallel-within-chunk) formulation in plain jnp.

    Mathematically identical to :func:`chunk_scan_ref`; this mirrors the
    Pallas kernel's blocking so tests can separate "chunking math wrong"
    from "kernel plumbing wrong".  SpliDT connection: the chunk is the
    window, the carried state is the reused register set (DESIGN.md §2).
    """
    B, T, dk = q.shape
    dv = v.shape[-1]
    assert T % chunk == 0, "pad T to a chunk multiple"
    nC = T // chunk
    if state is None:
        state = jnp.zeros((B, dk, dv), jnp.float32)
    qc = q.reshape(B, nC, chunk, dk).astype(jnp.float32)
    kc = k.reshape(B, nC, chunk, dk).astype(jnp.float32)
    vc = v.reshape(B, nC, chunk, dv).astype(jnp.float32)
    wc = decay.reshape(B, nC, chunk, dk).astype(jnp.float32)

    logw = jnp.log(jnp.maximum(wc, 1e-38))
    # splint: allow[R001]: LM chunk-scan reference, not a SpliDT parity
    # surface (kernel parity is vs this ref, not a numpy oracle)
    cum = jnp.cumsum(logw, axis=2)                # inclusive cumulative log-decay
    total = cum[:, :, -1, :]                      # (B, nC, dk)

    def chunk_step(S, xs):
        qi, ki, vi, logwi, cumi, totali = xs      # (B, chunk, ...)
        # GLA form: kv_s reaches o_t with decay prod_{r=s+1..t} w_r (incl. w_t)
        # bonus form: o_t reads S_{t-1}, so the product excludes w_t
        cum_q = cumi if bonus is None else cumi - logwi
        # mid-chunk-centred reference halves the exponent dynamic range
        # (pairwise products only need differences of cum)
        mref = cumi[:, chunk // 2, :][:, None, :]
        q_in = qi * jnp.exp(jnp.clip(cum_q - mref, -45.0, 45.0))
        k_in = ki * jnp.exp(jnp.clip(mref - cumi, -45.0, 45.0))
        # keys folded into state need decay from s+1 .. end-of-chunk
        d_out = jnp.exp(totali[:, None, :] - cumi)
        att = jnp.einsum("btk,bsk->bts", q_in, k_in)
        if bonus is None:
            mask = jnp.tril(jnp.ones((chunk, chunk), bool))
        else:
            mask = jnp.tril(jnp.ones((chunk, chunk), bool), -1)  # strictly causal
        att = jnp.where(mask[None], att, 0.0)
        o_intra = jnp.einsum("bts,bsv->btv", att, vi)
        if bonus is not None:
            diag = jnp.einsum("btk,bk,btk->bt", qi, bonus, ki)
            o_intra = o_intra + diag[:, :, None] * vi
        # inter-chunk reads the carried state with the TRUE decay from
        # chunk start (uncentred; underflow to 0 is the correct limit)
        o_inter = jnp.einsum("btk,bkv->btv", qi * jnp.exp(cum_q), S)
        S = jnp.exp(totali)[:, :, None] * S + jnp.einsum(
            "btk,btv->bkv", ki * d_out, vi)
        return S, o_intra + o_inter

    xs = tuple(x.transpose(1, 0, 2, 3) for x in (qc, kc, vc, logw, cum)) + (
        total.transpose(1, 0, 2),)
    final, o = jax.lax.scan(chunk_step, state.astype(jnp.float32), xs)
    o = o.transpose(1, 0, 2, 3).reshape(B, T, dv)
    return o.astype(v.dtype), final
