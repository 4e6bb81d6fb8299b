"""Bytes the serving algorithm needs, and the chip's peaks.

The counts follow the algorithm, not any kernel's block shapes, so every
implementation of the same work reads the same number, and each is a
least count (a roofline share computed from it cannot pass 100% unless
the time leaves out work):

* fold (per packet folded): the packet row, its slot's subtree id, and
  the slot's ``k`` registers (float32 value + int32 "seen" bit) read and
  written once;
* traverse (per hop, i.e. per completed window): the ``k`` finalized
  registers and the subtree id in, the leaf action out;
* admission (per new flow): the slot's state written once: registers,
  seven int32 walk fields, and ``P`` window bounds;
* tables (per tick): the subtrees' operator tables and trees read once.

Peaks come from ``peaks.json``, keyed by the device kind JAX reports; a
kind that is not in the table is an error.
"""
from __future__ import annotations

import json
import os

import numpy as np

PKT_ROW = 6 * 4
I32 = 4
F32 = 4
PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peak(kind: str, path: str = PEAKS) -> dict:
    with open(path) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in {path}")
    return table[kind]


def model_table_bytes(model: dict) -> int:
    """Per subtree: ``k`` (op, field, predicate, init) slots, and per
    node a feature id and threshold (internal) or an action (leaf)."""
    k = int(model["k"])
    total = 0
    for st in model["subtrees"]:
        feat = np.asarray(st["feature"])
        internal = int((feat >= 0).sum())
        total += 4 * k * I32 + internal * (I32 + F32) + (feat.size - internal) * I32
    return total


def fold_bytes(n_pkts: int, k: int) -> int:
    return int(n_pkts) * (PKT_ROW + I32 + 2 * k * (F32 + I32))


def traverse_bytes(n_hops: int, k: int) -> int:
    return int(n_hops) * (k * F32 + I32 + I32)


def admit_bytes(n_flows: int, k: int, n_partitions: int) -> int:
    return int(n_flows) * (k * (F32 + I32) + 7 * I32 + 2 * n_partitions * I32)


def work_bytes(model: dict, n_pkts: int, n_hops: int, n_new: int,
               n_ticks: int) -> dict:
    """Least bytes of a stretch of serving, by part and in total."""
    k = int(model["k"])
    P = len(model["partition_sizes"])
    parts = {"fold": fold_bytes(n_pkts, k),
             "traverse": traverse_bytes(n_hops, k),
             "admit": admit_bytes(n_new, k, P),
             "tables": int(n_ticks) * model_table_bytes(model)}
    parts["total"] = sum(parts.values())
    return parts


def exit_packets(model_verdicts: np.ndarray, lengths: np.ndarray,
                 n_partitions: int) -> tuple[np.ndarray, np.ndarray]:
    """Per template: the index of the packet at which its walk ends, and
    a ``(n, max_len)`` mask of the packets that complete a window the
    walk traverses (one hop each)."""
    from benchmarks.chip.reference import window_bounds
    n = lengths.shape[0]
    last = np.empty(n, np.int64)
    hop = np.zeros((n, int(lengths.max())), bool)
    for i in range(n):
        b = window_bounds(int(lengths[i]), n_partitions)
        e = int(model_verdicts[i, 2])
        e = n_partitions - 1 if e < 0 else e
        for w in range(e + 1):
            lo, hi = b[w]
            if hi > lo:
                hop[i, hi - 1] = True
        last[i] = b[e][1] - 1
    return last, hop
