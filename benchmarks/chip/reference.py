"""Plain reference for the chip benchmark's check: numpy, float32, no JAX.

It imports nothing of the program.  It reads the model only as plain
trees (``model.to_plain``: per subtree its partition, node arrays and
per-leaf routing and label), never the program's device tables, and it
computes window features from the template packets itself.

Semantics, as the paper's data plane and the program's parity contract
state them:

* a flow of ``L`` packets is cut into ``P`` windows of ``L // P``
  packets, the remainder going to the last one; the first packet of
  each window has its inter-arrival time cleared;
* a feature is ``(op, field, predicate)`` over the window's packets;
  sums run strictly left to right in float32, as the data plane folds
  them one packet at a time;
* subtree 0 sees window 0; each leaf exits with its class or routes to a
  subtree of the next partition, which sees the next window; each such
  hop is one recirculation.

``predict`` returns ``(label, recircs, exit_partition)`` per flow, with
``-1`` for a flow that never took an exit.  With ``dtype=bfloat16`` the
registers are rounded to bfloat16 after every fold step and before the
threshold compares: the control the check must reject.
"""
from __future__ import annotations

import numpy as np

PKT_TS, PKT_SIZE, PKT_DIR, PKT_FLAGS, PKT_IAT, PKT_VALID = range(6)

OP_COUNT, OP_SUM, OP_MAX, OP_MIN, OP_LAST, OP_SUMSQ, OP_FIRST = (
    1, 2, 3, 4, 5, 6, 7)
PRED_TRUE, PRED_FWD, PRED_BWD = 0, 1, 2
# predicate code -> flag bit
_PRED_FLAG = {3: 1, 4: 2, 5: 4, 6: 8, 7: 16, 8: 32}

# (op, field, predicate) of the 41 features, in feature-id order: the
# paper's CICFlowMeter-style set as the program numbers it
FEATURES = (
    (OP_COUNT, PKT_SIZE, PRED_TRUE), (OP_SUM, PKT_SIZE, PRED_TRUE),
    (OP_MAX, PKT_SIZE, PRED_TRUE), (OP_MIN, PKT_SIZE, PRED_TRUE),
    (OP_SUMSQ, PKT_SIZE, PRED_TRUE), (OP_FIRST, PKT_SIZE, PRED_TRUE),
    (OP_LAST, PKT_SIZE, PRED_TRUE),
    (OP_COUNT, PKT_SIZE, PRED_FWD), (OP_COUNT, PKT_SIZE, PRED_BWD),
    (OP_SUM, PKT_SIZE, PRED_FWD), (OP_SUM, PKT_SIZE, PRED_BWD),
    (OP_MAX, PKT_SIZE, PRED_FWD), (OP_MAX, PKT_SIZE, PRED_BWD),
    (OP_MIN, PKT_SIZE, PRED_FWD), (OP_MIN, PKT_SIZE, PRED_BWD),
    (OP_SUM, PKT_IAT, PRED_TRUE), (OP_MAX, PKT_IAT, PRED_TRUE),
    (OP_MIN, PKT_IAT, PRED_TRUE), (OP_SUMSQ, PKT_IAT, PRED_TRUE),
    (OP_SUM, PKT_IAT, PRED_FWD), (OP_SUM, PKT_IAT, PRED_BWD),
    (OP_MAX, PKT_IAT, PRED_FWD), (OP_MAX, PKT_IAT, PRED_BWD),
    (OP_COUNT, PKT_SIZE, 3), (OP_COUNT, PKT_SIZE, 4),
    (OP_COUNT, PKT_SIZE, 5), (OP_COUNT, PKT_SIZE, 6),
    (OP_COUNT, PKT_SIZE, 7), (OP_COUNT, PKT_SIZE, 8),
    (OP_SUM, PKT_SIZE, 3), (OP_SUM, PKT_SIZE, 7), (OP_MAX, PKT_SIZE, 4),
    (OP_FIRST, PKT_TS, PRED_TRUE), (OP_LAST, PKT_TS, PRED_TRUE),
    (OP_SUM, PKT_IAT, 3), (OP_MAX, PKT_IAT, 7),
    (OP_COUNT, PKT_SIZE, 7), (OP_COUNT, PKT_SIZE, 4),
    (OP_SUMSQ, PKT_SIZE, PRED_FWD), (OP_SUMSQ, PKT_SIZE, PRED_BWD),
    (OP_LAST, PKT_SIZE, PRED_BWD),
)
N_FEATURES = len(FEATURES)
F32_MAX = np.float32(np.finfo(np.float32).max)


def window_bounds(length: int, p: int) -> list[tuple[int, int]]:
    base = max(length // p, 1)
    return [(min(w * base, length),
             length if w == p - 1 else min((w + 1) * base, length))
            for w in range(p)]


def _bf16(x: np.ndarray) -> np.ndarray:
    """Round float32 to the nearest bfloat16 (ties to even), as float32."""
    b = np.ascontiguousarray(x, np.float32).view(np.uint32)
    keep = np.isfinite(x)
    r = ((b + np.uint32(0x7FFF) + ((b >> np.uint32(16)) & np.uint32(1)))
         & np.uint32(0xFFFF0000))
    return np.where(keep, r.view(np.float32), x).astype(np.float32)


def window_features(pkts: np.ndarray, lengths: np.ndarray, p: int,
                    fids, *, dtype: str = "float32") -> np.ndarray:
    """``(n, p, N_FEATURES)`` features of each flow's windows.

    Only the feature ids in ``fids`` are computed; the others are NaN
    (no tree reads them).  ``dtype="bfloat16"`` rounds every register
    to bfloat16 after each fold step.
    """
    n = pkts.shape[0]
    rnd = _bf16 if dtype == "bfloat16" else (lambda a: a)
    out = np.full((n, p, N_FEATURES), np.nan, np.float32)
    fids = sorted(int(f) for f in fids)
    if not fids:
        return out
    lengths = np.asarray(lengths, np.int64)
    ops = np.asarray([FEATURES[f][0] for f in fids])
    fld = np.asarray([FEATURES[f][1] for f in fids])
    prd = np.asarray([FEATURES[f][2] for f in fids])
    bounds = np.asarray([window_bounds(int(L), p) for L in lengths])  # n,p,2
    rows = np.arange(n)
    for w in range(p):
        lo, hi = bounds[:, w, 0], bounds[:, w, 1]
        width = int((hi - lo).max(initial=0))
        acc = np.where(ops == OP_MAX, -np.inf,
                       np.where(ops == OP_MIN, np.inf, 0.0))
        acc = np.broadcast_to(acc.astype(np.float32), (n, len(fids))).copy()
        seen = np.zeros((n, len(fids)), bool)
        for i in range(width):
            pos = lo + i
            live = pos < hi
            pk = pkts[rows, np.minimum(pos, pkts.shape[1] - 1)].copy()
            pk[~live] = 0.0
            if i == 0:
                pk[:, PKT_IAT] = 0.0
            valid = pk[:, PKT_VALID] > 0
            direc = pk[:, PKT_DIR]
            flags = pk[:, PKT_FLAGS].astype(np.int64)
            mask = np.zeros((n, len(fids)), bool)
            for c, code in enumerate(prd):
                if code == PRED_TRUE:
                    m = valid
                elif code == PRED_FWD:
                    m = valid & (direc == 0)
                elif code == PRED_BWD:
                    m = valid & (direc == 1)
                else:
                    m = valid & ((flags & _PRED_FLAG[int(code)]) > 0)
                mask[:, c] = m
            val = pk[:, fld]                                   # (n, k)
            mf = mask.astype(np.float32)
            acc = np.where(ops == OP_COUNT, rnd(acc + mf), acc)
            acc = np.where(ops == OP_SUM, rnd(acc + val * mf), acc)
            acc = np.where(ops == OP_SUMSQ, rnd(acc + val * val * mf), acc)
            acc = np.where((ops == OP_MAX) & mask, np.maximum(acc, val), acc)
            acc = np.where((ops == OP_MIN) & mask, np.minimum(acc, val), acc)
            acc = np.where((ops == OP_FIRST) & mask & ~seen, val, acc)
            acc = np.where((ops == OP_LAST) & mask, val, acc)
            seen |= mask
        empty = ~seen
        acc = np.where((ops == OP_MAX) & empty, np.float32(0.0), acc)
        acc = np.where((ops == OP_MIN) & empty, F32_MAX, acc)
        acc = np.where(((ops == OP_FIRST) | (ops == OP_LAST)) & empty,
                       np.float32(0.0), acc)
        out[:, w, fids] = rnd(acc.astype(np.float32))
    return out


def used_features(model: dict) -> set[int]:
    return {int(f) for st in model["subtrees"] for f in st["feature"]
            if f >= 0}


def _apply(st: dict, X: np.ndarray) -> np.ndarray:
    feature = np.asarray(st["feature"], np.int64)
    threshold = np.asarray(st["threshold"], np.float32)
    left = np.asarray(st["left"], np.int64)
    right = np.asarray(st["right"], np.int64)
    node = np.zeros(X.shape[0], np.int64)
    active = feature[node] >= 0
    while active.any():
        idx = np.nonzero(active)[0]
        nd = node[idx]
        go_left = X[idx, feature[nd]] <= threshold[nd]
        node[idx] = np.where(go_left, left[nd], right[nd])
        active = feature[node] >= 0
    return node


def predict(model: dict, X: np.ndarray):
    """Partitioned walk over per-window features ``X`` ``(n, p, N)``."""
    n = X.shape[0]
    sid = np.zeros(n, np.int64)
    done = np.zeros(n, bool)
    label = np.full(n, -1, np.int64)
    recircs = np.zeros(n, np.int64)
    exit_partition = np.full(n, -1, np.int64)
    P = len(model["partition_sizes"])
    for p in range(P):
        for st in model["subtrees"]:
            if st["partition"] != p:
                continue
            rows = np.nonzero(~done & (sid == st["sid"]))[0]
            if not rows.size:
                continue
            leaves = _apply(st, X[rows, p, :])
            nxt_map = {int(k): int(v) for k, v in st["leaf_next"].items()}
            lab_map = {int(k): int(v) for k, v in st["leaf_label"].items()}
            nxt = np.asarray([nxt_map.get(int(l), -1) for l in leaves])
            lab = np.asarray([lab_map[int(l)] for l in leaves])
            ex = nxt < 0
            done[rows[ex]] = True
            label[rows[ex]] = lab[ex]
            exit_partition[rows[ex]] = p
            cont = rows[~ex]
            sid[cont] = nxt[~ex]
            recircs[cont] += 1
    return label, recircs, exit_partition


def verdicts(model: dict, pkts: np.ndarray, lengths: np.ndarray, *,
             dtype: str = "float32") -> np.ndarray:
    """``(n, 3)`` int64 ``(label, recircs, exit_partition)`` per flow."""
    P = len(model["partition_sizes"])
    X = window_features(pkts, lengths, P, used_features(model), dtype=dtype)
    return np.stack(predict(model, X), axis=1)
