"""Traffic for the chip benchmark: template flows and their cyclic replay.

Two parts, both pure numpy and both kept here so that no change to the
program can move the yardstick:

* ``make_flows`` draws labelled template flows.  The class profiles are
  built exactly as ``repro.flows.synthetic.make_dataset`` builds them
  from the dataset's own seed (so a configuration's classes are the
  program's d1/d2 classes); the flows themselves (labels, lognormal
  lengths, packet rows) are drawn vectorised from a separate stream, so
  a pool of thousands of templates costs milliseconds, not a Python
  loop per flow.
* ``Schedule`` replays a pool of ``M`` templates cyclically.  Template
  ``i`` starts once per cycle at a steady offset ``o_i`` and spreads its
  ``L_i`` packets evenly over a lifetime of ``concurrency / M`` cycles,
  so ``concurrency`` flows are in flight once the ramp is over.  The
  instance of template ``i`` that starts in cycle ``c`` carries the
  fresh flow key ``i + M * c``; its correct verdict is template ``i``'s.
  One cycle is sorted once; every later cycle is the same order with
  keys shifted by a vectorised add, so a window of any length costs the
  same host memory, and any range of stream positions can be rebuilt
  after the run (for the check and for latencies) without keeping it.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import numpy as np

# packet record layout (the program's ``repro.core.features`` columns)
PKT_TS, PKT_SIZE, PKT_DIR, PKT_FLAGS, PKT_IAT, PKT_VALID = range(6)
PKT_NFIELDS = 6
FLAG_SYN, FLAG_ACK, FLAG_FIN, FLAG_RST, FLAG_PSH, FLAG_URG = (
    1, 2, 4, 8, 16, 32)
N_PHASES = 3

# name -> (classes, seed of the class profiles), as the program's datasets
DATASETS = {"d1": (19, 0xD1), "d2": (4, 0xD2), "d3": (13, 0xD3)}

_DELTA_KEYS = ["size_mu", "size_sigma", "iat_scale", "p_bwd",
               "p_syn", "p_ack", "p_fin", "p_rst", "p_psh", "p_urg"]
_FLAG_KEYS = (("p_syn", FLAG_SYN), ("p_ack", FLAG_ACK), ("p_fin", FLAG_FIN),
              ("p_rst", FLAG_RST), ("p_psh", FLAG_PSH), ("p_urg", FLAG_URG))


def _base_phase(rng: np.random.Generator) -> dict:
    return dict(size_mu=rng.uniform(5.0, 6.5),
                size_sigma=rng.uniform(0.3, 0.8),
                iat_scale=10 ** rng.uniform(-4.0, -1.5),
                p_bwd=rng.uniform(0.2, 0.6),
                p_syn=0.02, p_ack=0.7, p_fin=0.02, p_rst=0.01, p_psh=0.3,
                p_urg=0.005)


def _perturb(ph: dict, rng: np.random.Generator, n_deltas: int) -> dict:
    d = dict(ph)
    for key in rng.choice(_DELTA_KEYS, size=n_deltas, replace=False):
        v = d[key]
        if key == "size_mu":
            d[key] = float(np.clip(v + rng.normal(0, 0.9), 4.0, 7.3))
        elif key == "size_sigma":
            d[key] = float(np.clip(v * rng.uniform(0.4, 2.5), 0.1, 1.5))
        elif key == "iat_scale":
            d[key] = float(np.clip(v * 10 ** rng.normal(0, 0.8), 1e-5, 1.0))
        else:
            d[key] = float(np.clip(v * rng.uniform(0.2, 4.0)
                                   + rng.uniform(0, 0.1), 0.0, 0.95))
    return d


def class_profiles(dataset: str) -> dict[str, np.ndarray]:
    """Per-(class, phase) behaviour parameters, each ``(C, N_PHASES)``.

    Classes are grouped in families that share the early phase and
    diverge in the middle and late phases, so later windows carry
    information the first one lacks.
    """
    n_classes, seed = DATASETS[dataset]
    rng = np.random.default_rng(seed)
    n_families = max(2, n_classes // 3)
    family_phase0 = [_base_phase(rng) for _ in range(n_families)]
    rows = []
    for c in range(n_classes):
        p0 = _perturb(family_phase0[c % n_families], rng, n_deltas=1)
        p1 = _perturb(p0, rng, n_deltas=3)
        p2 = _perturb(p1, rng, n_deltas=3)
        rows.append((p0, p1, p2))
    return {key: np.asarray([[ph[key] for ph in r] for r in rows])
            for key in _DELTA_KEYS}


class Flows(NamedTuple):
    pkts: np.ndarray      # (n, max_len, PKT_NFIELDS) f32, zero padded
    lengths: np.ndarray   # (n,) int32
    labels: np.ndarray    # (n,) int64


def make_flows(dataset: str, n: int, rng: np.random.Generator, *,
               len_median: float = 40.0, len_sigma: float = 0.7,
               min_len: int = 12, max_len: int = 192) -> Flows:
    """``n`` labelled flows of ``dataset``'s classes, drawn from ``rng``.

    Lengths are lognormal (median ``len_median``) clipped to
    ``[min_len, max_len]``; each flow's thirds follow its class's three
    phases; the first packet has IAT 0 and a SYN flag.
    """
    prof = class_profiles(dataset)
    n_classes = prof["size_mu"].shape[0]
    labels = rng.integers(0, n_classes, size=n)
    lengths = np.clip(np.exp(rng.normal(np.log(len_median), len_sigma,
                                        size=n)).astype(np.int64),
                      min_len, max_len).astype(np.int32)
    W = int(lengths.max())
    j = np.arange(W)[None, :]
    L = lengths[:, None].astype(np.int64)
    live = j < L
    phase = (j >= L // 3).astype(np.int64) + (j >= 2 * L // 3)
    par = {k: v[labels[:, None], phase] for k, v in prof.items()}
    shape = (n, W)
    sizes = np.clip(rng.lognormal(par["size_mu"], par["size_sigma"], shape),
                    40, 1500)
    iats = rng.exponential(par["iat_scale"], shape)
    iats[:, 0] = 0.0
    dirs = rng.random(shape) < par["p_bwd"]
    flags = np.zeros(shape, np.int64)
    for key, bit in _FLAG_KEYS:
        flags += (rng.random(shape) < par[key]) * bit
    flags[:, 0] |= FLAG_SYN
    iats = np.where(live, iats, 0.0)
    pkts = np.zeros((n, W, PKT_NFIELDS), np.float32)
    pkts[..., PKT_TS] = np.cumsum(iats, axis=1)
    pkts[..., PKT_SIZE] = sizes
    pkts[..., PKT_DIR] = dirs
    pkts[..., PKT_FLAGS] = flags
    pkts[..., PKT_IAT] = iats
    pkts[..., PKT_VALID] = 1.0
    pkts[~live] = 0.0
    return Flows(pkts, lengths, labels.astype(np.int64))


class Batch(NamedTuple):
    """One ingest call's packets; the fields ``FlowTableServer.ingest``
    reads (``flow_id``, ``flow_len``, ``pkts``, ``arrival``) plus the
    packet's index in its flow."""
    flow_id: np.ndarray
    flow_len: np.ndarray
    pkts: np.ndarray
    arrival: np.ndarray
    pkt_index: np.ndarray

    @property
    def n_packets(self) -> int:
        return int(self.flow_id.shape[0])


class Schedule:
    """Cyclic re-keyed replay of a template pool (see module doc).

    Stream position ``n`` is the ``n``-th packet in arrival order.
    Cycles ``0 .. ramp_cycles - 1`` hold only instances started at cycle
    0 or later, so concurrency climbs; from ``ramp_cycles`` on, every
    cycle holds ``cycle_pkts`` packets and concurrency is steady.
    """

    def __init__(self, flows: Flows, concurrency: float,
                 rng: np.random.Generator):
        fl = self.flows = flows
        M = self.M = fl.lengths.shape[0]
        self.lifetime = float(concurrency) / M        # in cycles
        offs = (rng.permutation(M) + rng.uniform(0.0, 1.0, M)) / M
        L = fl.lengths.astype(np.int64)
        tmpl = np.repeat(np.arange(M, dtype=np.int64), L)
        start = np.cumsum(L) - L
        j = np.arange(tmpl.size, dtype=np.int64) - start[tmpl]
        t = offs[tmpl] + self.lifetime * j / L[tmpl]
        wrap = np.floor(t).astype(np.int64)
        frac = t - wrap
        order = np.lexsort((j, tmpl, frac))
        self.b_tmpl = tmpl[order]
        self.b_j = j[order]
        self.b_wrap = wrap[order]
        self.b_frac = frac[order]
        self.b_len = fl.lengths[self.b_tmpl]
        self.b_pkts = fl.pkts[self.b_tmpl, self.b_j]
        self.cycle_pkts = int(tmpl.size)
        self.ramp_cycles = int(self.b_wrap.max())
        # ramp cycle g holds the entries whose instance started at >= 0
        self._ramp_idx = [np.nonzero(self.b_wrap <= g)[0]
                          for g in range(self.ramp_cycles)]
        self._ramp_start = np.cumsum(
            [0] + [ix.size for ix in self._ramp_idx]).astype(np.int64)

    @property
    def ramp_pkts(self) -> int:
        """Stream positions before concurrency is steady."""
        return int(self._ramp_start[-1])

    def _cycle_of(self, n: int) -> tuple[int, int]:
        """(cycle, offset within it) of stream position ``n``."""
        if n < self.ramp_pkts:
            g = int(np.searchsorted(self._ramp_start, n, side="right")) - 1
            return g, n - int(self._ramp_start[g])
        q, r = divmod(n - self.ramp_pkts, self.cycle_pkts)
        return self.ramp_cycles + q, r

    def _cycle_len(self, g: int) -> int:
        if g < self.ramp_cycles:
            return int(self._ramp_idx[g].size)
        return self.cycle_pkts

    def _index(self, g: int, lo: int, hi: int):
        if g < self.ramp_cycles:
            return self._ramp_idx[g][lo:hi]
        return slice(lo, hi)

    def batch(self, a: int, b: int) -> Batch:
        """Packets at stream positions ``[a, b)``."""
        parts = []
        g, off = self._cycle_of(a)
        n = a
        while n < b:
            take = min(b - n, self._cycle_len(g) - off)
            ix = self._index(g, off, off + take)
            wrap = self.b_wrap[ix]
            parts.append((self.b_tmpl[ix] + self.M * (g - wrap),
                          self.b_len[ix], self.b_pkts[ix],
                          g + self.b_frac[ix], self.b_j[ix]))
            n += take
            g, off = g + 1, 0
        if not parts:
            return Batch(np.empty(0, np.int64), np.empty(0, np.int32),
                         np.empty((0, PKT_NFIELDS), np.float32),
                         np.empty(0, np.float64), np.empty(0, np.int64))
        if len(parts) == 1:
            return Batch(*parts[0])
        return Batch(*(np.concatenate(c) for c in zip(*parts)))

    def max_rank(self, tick: int) -> int:
        """Most packets one flow can have among ``tick`` consecutive
        positions of the steady stream: ``1 + (tick - 1) // gap``, with
        ``gap`` the least distance between consecutive packets of one
        instance."""
        pos = (self.b_wrap * self.cycle_pkts
               + np.arange(self.cycle_pkts, dtype=np.int64))
        order = np.lexsort((self.b_j, self.b_tmpl))
        p = pos[order]
        same = self.b_tmpl[order][1:] == self.b_tmpl[order][:-1]
        gaps = (p[1:] - p[:-1])[same]
        gap = int(gaps.min()) if gaps.size else tick
        return 1 + (int(tick) - 1) // max(gap, 1)


def template_of(flow_id: np.ndarray, M: int) -> np.ndarray:
    """Template index of each instance key."""
    return np.asarray(flow_id, np.int64) % M


def pow2_at_least(n: int, floor: int = 1) -> int:
    return max(int(floor), 1 << max(0, math.ceil(math.log2(max(n, 1)))))
