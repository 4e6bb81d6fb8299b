"""Analytical cost model for the engine's execution backends.

The paper's DSE framework picks *model* shapes per hardware target; this
module does the same for the *execution* path.  PR 2/3 showed the
fastest backend flips with batch size B, subtree count S, compaction
profile, and device count — a one-line platform check (``pallas`` on
TPU, ``fused`` elsewhere) leaves that regime-dependence on the table,
exactly the way one-shot Leo/NetBeacon deployments cannot exploit
pForest-style per-phase switching.

The model is a per-hop work estimate in microseconds::

    cost(plan, shape) = fixed dispatch overhead
                      + sum over hops p of
                          feature-window rebuild (B_p * W * k)
                        + traversal               (backend-specific)
                        + routing overhead        (sort / sync / grid)

where ``B_p`` is the number of flow slots the hop actually processes:
the full batch for a dense walk, the compaction bucket capacity for a
compacted walk (driven by the shape's per-hop survivor profile).  The
backend-specific terms:

* **fused**  — dense per-flow gathers of the SID-keyed tables plus a
  dense range match: ``B_p * (k*T + 2*L*k + 2*L)`` gather traffic and
  ``B_p * (k*T + L*k)`` compare work, one jitted call per batch.
* **pallas** — the in-jit SID dispatch (argsort + scatter: ``B_p *
  log2(B_p)``) plus block-dense kernel work over the capacity bound
  ``ceil(B_p/block_b) + S`` blocks (``kernels.dispatch``), plus a
  per-grid-step launch cost that dominates in interpret mode (the
  grid is executed sequentially off-TPU).
* **looped** — the fused math plus a host sync and two dispatches per
  hop (the per-partition ``device_get``).

Coefficients are *fitted*, not guessed: :func:`fit_coefficients` solves
a non-negative least-squares over (work-term, measured-μs) samples, and
:func:`calibrate` collects those samples from micro-benchmarks of the
actual engine on the actual host.  The defaults baked into
:data:`DEFAULT_COEFFS` were fitted that way on the 2-core CPU dev
container (see ``benchmarks/bench_engine.py``); on a real TPU, run
:func:`calibrate` (or the autotuner, which measures end-to-end) rather
than trusting CPU-fitted constants.

The model is intentionally coarse — its job is *routing* (pick the
argmin backend, decide whether compaction pays), not prediction.  The
empirical autotuner (``repro.tuning.autotune``) uses it to shortlist
candidates before timing them, and replaces it entirely once a timed
winner is cached.

Doctest (shape-only, no timing — safe anywhere)::

    >>> from repro.tuning.costmodel import ShapeInfo, choose_plan
    >>> shape = ShapeInfo(B=4096, S=9, k=4, P=3, W=24, T=16, L=16)
    >>> plan = choose_plan(shape)
    >>> plan.backend in ("looped", "fused", "pallas")
    True
"""
from __future__ import annotations

import dataclasses
import math
from typing import TYPE_CHECKING, Iterable, Sequence

import numpy as np

from repro.kernels.compaction import COMPACT_FLOOR, bucket_caps
from repro.kernels.dispatch import capacity_blocks
from repro.kernels.dt_traverse import BLOCK_B

if TYPE_CHECKING:  # pragma: no cover
    from repro.core.inference import Engine

BACKENDS = ("looped", "fused", "pallas")

#: block_b candidates the model (and the tuner) consider for the pallas
#: step.  128 matches the kernel default (fp32 VPU lane tiling); smaller
#: blocks waste less capacity padding at small B / large S, larger ones
#: amortise per-block launch cost at large B.
BLOCK_B_CANDIDATES = (64, 128, 256)

#: Compaction-ladder floors the tuner sweeps for compact=True plans.
#: Smaller floors chase thinner survivor tails; below the Pallas block
#: size the gather/scatter overhead wins (see kernels.compaction).
COMPACT_FLOOR_CANDIDATES = (64, 128, 256)


@dataclasses.dataclass(frozen=True)
class ShapeInfo:
    """Everything the cost model needs to know about one workload.

    B          flows per batch (per *chunk* for streaming)
    S          total subtrees across all partitions (tables are SID-keyed)
    k          feature registers per flow
    P          partitions (recirculation hops)
    W          packets per window
    T          max thresholds per register slot (padded table width)
    L          max leaves per subtree (padded table height)
    n_devices  data-parallel shards the batch splits over (1 = single)
    survivors  optional per-hop active-flow fractions, ``survivors[p]``
               in (0, 1] = fraction of B still undecided entering hop p
               (``survivors[0]`` is always 1.0).  None = assume no early
               exits (conservative: compaction is modelled as pure
               overhead).
    """
    B: int
    S: int
    k: int
    P: int
    W: int
    T: int
    L: int
    n_devices: int = 1
    survivors: tuple[float, ...] | None = None

    def __post_init__(self):
        for f in ("B", "S", "k", "P", "W", "T", "L", "n_devices"):
            v = getattr(self, f)
            if v < (0 if f == "B" else 1):
                bound = "non-negative" if f == "B" else "positive"
                raise ValueError(f"{f} must be {bound}, got {v}")
        if self.survivors is not None and len(self.survivors) != self.P:
            raise ValueError(
                f"survivors must have one entry per hop "
                f"({self.P}), got {len(self.survivors)}")

    @classmethod
    def from_engine(cls, engine: "Engine", win_pkts=None, *,
                    B: int | None = None, W: int | None = None,
                    n_devices: int = 1,
                    survivors: Sequence[float] | None = None) -> "ShapeInfo":
        """Read (S, k, P, T, L) off an engine's packed tables.

        ``B``/``W`` come from ``win_pkts`` (B, P, W, F) when given
        (explicit ``B``/``W`` override); without windows BOTH must be
        passed — the packed tables do not record the window width, and
        guessing it would mis-scale the dominant feature-window cost
        term.
        """
        if win_pkts is not None:
            B = win_pkts.shape[0] if B is None else B
            W = int(win_pkts.shape[2]) if W is None else W
        elif B is None or W is None:
            raise ValueError("need win_pkts, or explicit B and W")
        ret = engine.ret
        return cls(B=int(B), S=int(ret.n_subtrees), k=int(ret.k),
                   P=int(engine.tables.n_partitions), W=int(W),
                   T=int(ret.max_thresholds), L=int(ret.max_leaves),
                   n_devices=int(n_devices),
                   survivors=None if survivors is None else tuple(survivors))

    def key(self) -> str:
        """Stable cache-key fragment (survivors excluded: the tuner keys
        on the static shape, not the data-dependent exit pattern)."""
        return (f"B{self.B}-S{self.S}-k{self.k}-P{self.P}-W{self.W}"
                f"-T{self.T}-L{self.L}-d{self.n_devices}")


@dataclasses.dataclass(frozen=True)
class Plan:
    """One resolved execution configuration.

    ``backend`` ∈ {looped, fused, pallas}; ``block_b`` only matters for
    pallas; ``compact``/``compact_floor`` configure the early-exit
    compaction ladder.  ``source`` records who decided ("costmodel",
    "timed", "cache", "forced") and ``est_us`` the model's estimate (or
    the measured time for timed/cache plans).
    """
    backend: str
    block_b: int = BLOCK_B
    compact: bool = False
    compact_floor: int = COMPACT_FLOOR
    source: str = "costmodel"
    est_us: float | None = None

    def __post_init__(self):
        if self.backend not in BACKENDS:
            raise ValueError(f"unknown backend {self.backend!r}; "
                             f"options {BACKENDS}")

    def describe(self) -> str:
        bits = [self.backend]
        if self.backend == "pallas":
            bits.append(f"block_b={self.block_b}")
        if self.compact:
            bits.append(f"compact(floor={self.compact_floor})")
        bits.append(f"source={self.source}")
        if self.est_us is not None:
            bits.append(f"~{self.est_us:.0f}us")
        return " ".join(bits)


# ---------------------------------------------------------------------------
# coefficients
# ---------------------------------------------------------------------------
#: Work-term names, in the order `work_terms` emits them.  Each
#: coefficient is μs per unit of its term.
TERMS = (
    "call",         # per jitted dispatch (fixed)
    "sync",         # per host<->device round trip (looped: one per hop)
    "fw",           # feature-window rebuild, per flow*W*k element
    "tr_dense",     # dense range-match + table gather, per flow*(kT+Lk)
    "tr_pallas",    # block-dense kernel work, per padded flow*(kT+Lk)
    "grid",         # per pallas grid step (launch; huge in interpret)
    "sort",         # per flow*log2(B) of in-jit argsort (dispatch/compact)
)


@dataclasses.dataclass(frozen=True)
class Coefficients:
    """μs-per-unit weights for each term in :data:`TERMS`."""
    call: float
    sync: float
    fw: float
    tr_dense: float
    tr_pallas: float
    grid: float
    sort: float

    def vector(self) -> np.ndarray:
        return np.array([getattr(self, t) for t in TERMS], dtype=np.float64)

    @classmethod
    def from_vector(cls, v: Sequence[float]) -> "Coefficients":
        return cls(**{t: float(x) for t, x in zip(TERMS, v)})


#: Fitted per backend family on the 2-core CPU dev container via
#: :func:`calibrate` over d2 models spanning S∈[13, 21], B∈[256, 4096]
#: (see ``benchmarks/bench_engine.py`` and
#: ``tests/test_tuning.py::test_default_coefficients_route_sanely``).
#: Notes on the CPU entries: the pallas row is the *interpret-mode*
#: path (its ``grid`` term is the per-block interpreter overhead that
#: keeps the router off pallas at scale off-TPU); looped's huge
#: ``call``/``sync`` reflect the eager per-op dispatch train of a
#: host-synced hop, not a single jitted launch.  The TPU entries are
#: *estimates* seeded from the known kernel economics (block-dense
#: traversal beats gather-heavy dense math; grid steps are pipelined,
#: not interpreted) — refit with :func:`calibrate` on real hardware
#: before trusting absolute numbers there.
DEFAULT_COEFFS: dict[str, dict[str, Coefficients]] = {
    "cpu": {
        "fused": Coefficients(call=550.0, sync=250.0, fw=8.5e-3,
                              tr_dense=4.8e-3, tr_pallas=4.8e-3,
                              grid=4000.0, sort=1.5e-3),
        "pallas": Coefficients(call=500.0, sync=250.0, fw=2e-3,
                               tr_dense=4.8e-3, tr_pallas=8e-3,
                               grid=30.0, sort=0.75),
        "looped": Coefficients(call=28000.0, sync=14000.0, fw=8e-2,
                               tr_dense=4.8e-3, tr_pallas=4.8e-3,
                               grid=4000.0, sort=1.5e-3),
    },
    "tpu": {
        "fused": Coefficients(call=30.0, sync=150.0, fw=2e-5,
                              tr_dense=1.2e-4, tr_pallas=1.2e-4,
                              grid=2.0, sort=5e-5),
        "pallas": Coefficients(call=30.0, sync=150.0, fw=8e-6,
                               tr_dense=1.2e-4, tr_pallas=3e-5,
                               grid=2.0, sort=5e-5),
        "looped": Coefficients(call=500.0, sync=300.0, fw=2e-5,
                               tr_dense=1.2e-4, tr_pallas=1.2e-4,
                               grid=2.0, sort=5e-5),
    },
}


def default_coefficients(backend: str) -> Coefficients:
    """Per-backend platform defaults (CPU-fitted / TPU-estimated).

    Each backend family gets its own weights because the terms mean
    different things per path: looped's "call" is a train of eager op
    dispatches, fused's is one jitted launch, and pallas off-TPU pays
    the interpreter per grid step.  A platform without a row is an
    error: another platform's weights would route on numbers that
    describe a different machine.
    """
    import jax
    platform = jax.default_backend()
    if platform not in DEFAULT_COEFFS:
        raise ValueError(
            f"no cost-model coefficients for platform {platform!r} "
            f"(rows: {', '.join(sorted(DEFAULT_COEFFS))}); pin a backend "
            "instead of impl='auto'")
    return DEFAULT_COEFFS[platform][backend]


# ---------------------------------------------------------------------------
# per-plan work terms
# ---------------------------------------------------------------------------
def _hop_rows(shape: ShapeInfo, plan: Plan) -> list[int]:
    """Flow slots each hop processes on ONE device shard.

    Dense walk: the full per-shard batch every hop.  Compacted walk:
    hop 0 is dense, later hops run the smallest capacity-ladder bucket
    that fits the surviving flows (``kernels.compaction.bucket_caps``),
    which is exactly what the compacted walk executes.  The looped
    backend compacts by host fancy-indexing, so its hop size is the
    survivor count itself.
    """
    Bd = -(-shape.B // shape.n_devices)          # per-shard batch
    surv = shape.survivors or (1.0,) * shape.P
    rows = []
    caps = bucket_caps(Bd, plan.compact_floor) if plan.compact else None
    for p in range(shape.P):
        n = Bd if p == 0 else int(math.ceil(surv[p] * Bd))
        if plan.compact and p > 0:
            if plan.backend == "looped":
                rows.append(n)
            else:
                rows.append(next(c for c in caps if c >= n))
        else:
            rows.append(Bd)
    return rows


def work_terms(shape: ShapeInfo, plan: Plan) -> np.ndarray:
    """Decompose one (shape, plan) into per-term work units.

    Returns a vector aligned with :data:`TERMS`; ``estimate_us`` is its
    dot product with a coefficient vector.  Kept separate so
    :func:`fit_coefficients` can build a design matrix from measured
    samples.
    """
    s, k = shape, shape.k
    unit = k * s.T + s.L * k                     # compare work per flow
    gather = k * s.T + 2 * s.L * k + 2 * s.L     # table rows pulled per flow
    w = dict.fromkeys(TERMS, 0.0)
    hops = _hop_rows(shape, plan)

    if plan.backend == "looped":
        # two dispatches (feature_window + dt_traverse) and one
        # device_get per hop; dense math on the survivor rows
        w["call"] = 2.0 * s.P
        w["sync"] = float(s.P)
        for n in hops:
            w["fw"] += n * s.W * k
            w["tr_dense"] += n * (unit + gather)
        return _vec(w)

    # walk backends: ONE dispatch per batch; compaction adds an in-jit
    # argsort per hop past the first
    w["call"] = 1.0
    sort_hops = range(1, s.P) if plan.compact else ()
    Bd = -(-s.B // s.n_devices)
    for p in sort_hops:
        w["sort"] += Bd * math.log2(max(Bd, 2))

    if plan.backend == "fused":
        for n in hops:
            w["fw"] += n * s.W * k
            w["tr_dense"] += n * (unit + gather)
        return _vec(w)

    # pallas: blocked feature kernel + SID dispatch + block-dense match
    bb = plan.block_b
    for n in hops:
        if n == 0:
            continue                             # drained ladder rung
        fw_blocks = -(-n // min(bb, max(n, 1)))
        nb = capacity_blocks(n, s.S, bb)
        w["fw"] += fw_blocks * min(bb, n) * s.W * k
        w["sort"] += n * math.log2(max(n, 2))    # sid argsort + scatter
        w["tr_pallas"] += nb * bb * unit
        w["grid"] += fw_blocks + nb
    return _vec(w)


def _vec(w: dict) -> np.ndarray:
    return np.array([w[t] for t in TERMS], dtype=np.float64)


def estimate_us(shape: ShapeInfo, plan: Plan,
                coeffs: Coefficients | None = None) -> float:
    """Model estimate (μs per batch) for running ``shape`` under ``plan``."""
    c = coeffs or default_coefficients(plan.backend)
    return float(work_terms(shape, plan) @ c.vector())


# ---------------------------------------------------------------------------
# serving tick estimate (the flow-table server's per-ingest shape)
# ---------------------------------------------------------------------------
#: Tick-engine families the flow-table server routes between: "fused"
#: runs the whole rank loop + hop drain inside one jitted tick step
#: (kernels.tick_step), "legacy" dispatches per rank and per drain
#: round with a host sync in between.
TICK_ENGINES = ("fused", "legacy")


def tick_work_terms(shape: ShapeInfo, plan: Plan, *, ranks: int = 4,
                    drains: float = 1.0,
                    tick_engine: str = "fused") -> np.ndarray:
    """Per-:data:`TERMS` work units for ONE flow-table ingest tick.

    ``shape.B`` is the padded rank width (slots touched per tick),
    ``shape.W`` should be 1 (the incremental fold sees one packet per
    slot per rank), ``ranks`` the tick's rank-chain depth (max packets
    of any one flow), and ``drains`` the expected extra hop rounds from
    empty trailing windows.  The per-rank *work* terms are identical
    for both tick engines — only the dispatch/sync pattern differs:

    * ``legacy`` — one admission reset + one fold call per rank + one
      hop call **and host sync** per traverse round;
    * ``fused``  — one admission scatter + ONE tick-step call + ONE
      bulk verdict fetch, whatever the rank count or drain depth.

    On a CPU host the ~0.5 ms ``call`` coefficient makes the fused tick
    the winner for every non-trivial tick; the term split keeps the
    decision honest if the coefficients are refit on hardware where
    dispatch is cheap and the scan's serialization might matter.
    """
    if tick_engine not in TICK_ENGINES:
        raise ValueError(f"unknown tick engine {tick_engine!r}; "
                         f"options {TICK_ENGINES}")
    s, k = shape, shape.k
    unit = k * s.T + s.L * k
    gather = k * s.T + 2 * s.L * k + 2 * s.L
    B = max(int(s.B), 1)
    hops = ranks + drains                        # traverse rounds / tick
    w = dict.fromkeys(TERMS, 0.0)
    if tick_engine == "legacy":
        w["call"] = 1.0 + ranks + hops
        w["sync"] = float(hops)
    else:
        w["call"] = 2.0
        w["sync"] = 1.0
    w["fw"] = float(ranks) * B * k               # one packet per fold
    if plan.backend == "pallas":
        bb = plan.block_b
        nb = capacity_blocks(B, s.S, bb)
        fw_blocks = -(-B // min(bb, B))
        w["grid"] = ranks * fw_blocks + hops * nb
        w["sort"] = hops * B * math.log2(max(B, 2))
        w["tr_pallas"] = hops * nb * bb * unit
    else:
        w["tr_dense"] = hops * B * (unit + gather)
    return _vec(w)


def estimate_tick_us(shape: ShapeInfo, plan: Plan, *, ranks: int = 4,
                     drains: float = 1.0, tick_engine: str = "fused",
                     coeffs: Coefficients | None = None) -> float:
    """Model estimate (μs per ingest tick) for the flow-table server."""
    c = coeffs or default_coefficients(plan.backend)
    return float(tick_work_terms(shape, plan, ranks=ranks, drains=drains,
                                 tick_engine=tick_engine) @ c.vector())


def choose_tick_engine(shape: ShapeInfo, *, ranks: int = 4,
                       drains: float = 1.0, backend: str = "fused",
                       block_b: int = BLOCK_B,
                       coeffs: Coefficients | None = None) -> str:
    """Pick fused-tick vs legacy per-rank serving for a table shape.

    Used by ``FlowTableServer(tick_engine="auto")`` once the walk
    backend/block size are resolved (``impl="auto"``/``"tuned"``).
    Pure arithmetic, ties go to fused (fewer dispatches can only help
    the tail).
    """
    plan = Plan(backend=backend, block_b=block_b)
    kw = dict(ranks=ranks, drains=drains, coeffs=coeffs)
    fused = estimate_tick_us(shape, plan, tick_engine="fused", **kw)
    legacy = estimate_tick_us(shape, plan, tick_engine="legacy", **kw)
    return "fused" if fused <= legacy else "legacy"


def choose_tick_plan(
    shape: ShapeInfo, *, ranks: int = 4, drains: float = 1.0,
    backends: Sequence[str] = ("fused", "pallas"),
    coeffs: dict[str, Coefficients] | None = None,
) -> tuple[str, Plan]:
    """Argmin (tick_engine, walk plan) for one serving tick shape.

    The serving analogue of :func:`choose_plan`: sweeps the walk
    backends × ``BLOCK_B_CANDIDATES`` × both tick engines and returns
    the cheapest combination — how the tick-shape estimate picks
    ``block_b`` for the table shape alongside the engine.
    """
    best = None
    best_us = float("inf")
    for te in TICK_ENGINES:
        for plan in candidate_plans(shape, backends=backends,
                                    compact=False):
            c = (coeffs or {}).get(plan.backend) if coeffs else None
            us = estimate_tick_us(shape, plan, ranks=ranks, drains=drains,
                                  tick_engine=te, coeffs=c)
            if us < best_us:
                best, best_us = (te, plan), us
    te, plan = best
    return te, dataclasses.replace(plan, source="costmodel",
                                   est_us=round(best_us, 1))


# ---------------------------------------------------------------------------
# plan enumeration + selection
# ---------------------------------------------------------------------------
def candidate_plans(
    shape: ShapeInfo,
    *,
    backends: Sequence[str] = BACKENDS,
    compact: bool | str | None = "auto",
    block_bs: Sequence[int] = BLOCK_B_CANDIDATES,
    compact_floors: Sequence[int] = COMPACT_FLOOR_CANDIDATES,
) -> list[Plan]:
    """Enumerate the configurations the router/tuner chooses between.

    ``compact`` — True/False pins compaction; "auto"/None explores both
    (the compact=True variants only when a survivor profile suggests
    early exits, or unconditionally for the tuner to measure).
    Compacted plans additionally sweep the capacity-ladder floor
    (``compact_floors``); the looped backend compacts by exact host
    indexing, so it gets a single compacted variant.  ``backends``
    restricts the search (streaming excludes "looped").
    """
    compacts: tuple[bool, ...]
    if compact in ("auto", None):
        compacts = (False, True)
    else:
        compacts = (bool(compact),)
    plans = []
    for backend in backends:
        if backend not in BACKENDS:
            raise ValueError(f"unknown backend {backend!r}")
        bbs = block_bs if backend == "pallas" else (BLOCK_B,)
        for bb in bbs:
            for cp in compacts:
                floors = (compact_floors if cp and backend != "looped"
                          else (COMPACT_FLOOR,))
                for fl in floors:
                    plans.append(Plan(backend=backend, block_b=bb,
                                      compact=cp, compact_floor=fl))
    return plans


def choose_plan(
    shape: ShapeInfo,
    *,
    backends: Sequence[str] = BACKENDS,
    compact: bool | str | None = False,
    coeffs: dict[str, Coefficients] | None = None,
) -> Plan:
    """Pick the argmin-cost plan for ``shape`` (``impl="auto"``).

    Pure arithmetic — never times anything, so it is safe on the hot
    path.  ``compact`` defaults to False here (the caller's explicit
    ``compact=`` wins); pass "auto" to let the model weigh compaction
    against the shape's survivor profile.
    """
    best, best_us = None, float("inf")
    for plan in candidate_plans(shape, backends=backends, compact=compact):
        c = (coeffs or {}).get(plan.backend) if coeffs else None
        us = estimate_us(shape, plan, c)
        if us < best_us:
            best, best_us = plan, us
    return dataclasses.replace(best, source="costmodel",
                               est_us=round(best_us, 1))


# ---------------------------------------------------------------------------
# fitting
# ---------------------------------------------------------------------------
def fit_coefficients(
    samples: Iterable[tuple[ShapeInfo, Plan, float]],
    *,
    base: Coefficients | None = None,
) -> Coefficients:
    """Non-negative least-squares fit of :data:`TERMS` weights.

    ``samples`` are (shape, plan, measured_us) triples.  Terms with no
    support in the design matrix (e.g. no compacted samples → no sort
    column) keep the ``base`` coefficient (platform default) instead of
    collapsing to 0, so a partial calibration never breaks routing for
    unmeasured configurations.  Non-negativity via projected iteration:
    solve lstsq over the supported columns, pin negative solutions to
    zero, re-solve the rest (a small NNLS).
    """
    samples = list(samples)
    if not samples:
        raise ValueError("need at least one calibration sample")
    A = np.stack([work_terms(s, p) for s, p, _ in samples])
    y = np.array([us for _, _, us in samples], dtype=np.float64)
    base_v = (base or default_coefficients("fused")).vector()
    x = np.where(A.any(axis=0), 0.0, base_v)     # unsupported -> base
    free = A.any(axis=0)                         # columns with support
    for _ in range(len(TERMS)):
        idx = np.nonzero(free)[0]
        if idx.size == 0:
            break
        sol, *_ = np.linalg.lstsq(A[:, idx], y, rcond=None)
        neg = sol < 0
        x[idx] = np.where(neg, 0.0, sol)
        if not neg.any():
            break
        free[idx[neg]] = False                   # pin to 0, re-solve rest
    return Coefficients.from_vector(x)


def calibrate(
    engine: "Engine",
    win_pkts,
    *,
    probe_sizes: Sequence[int] = (256, 1024),
    repeat: int = 2,
    include_pallas: bool = True,
) -> dict[str, Coefficients]:
    """Fit per-backend coefficients from micro-benchmarks of ``engine``.

    Times the fused walk at each probe size, the looped walk at the
    smallest, and (optionally) the pallas walk at the smallest — then
    fits one :class:`Coefficients` per backend family.  Returns a dict
    usable as ``choose_plan(..., coeffs=...)``.  Cheap by construction:
    a handful of sub-second probes, intended for the autotuner's first
    run on a new host, not the request path.
    """
    from repro.tuning.autotune import time_plan

    B = win_pkts.shape[0]
    sizes = sorted({min(s, B) for s in probe_sizes if s > 0})
    samples: dict[str, list] = {b: [] for b in BACKENDS}
    for n in sizes:
        shape = ShapeInfo.from_engine(engine, win_pkts, B=n)
        plan = Plan(backend="fused")
        samples["fused"].append(
            (shape, plan, time_plan(engine, win_pkts[:n], plan,
                                    repeat=repeat)))
    n0 = sizes[0]
    shape0 = ShapeInfo.from_engine(engine, win_pkts, B=n0)
    lp = Plan(backend="looped")
    samples["looped"].append(
        (shape0, lp, time_plan(engine, win_pkts[:n0], lp, repeat=repeat)))
    if include_pallas:
        pp = Plan(backend="pallas")
        samples["pallas"].append(
            (shape0, pp, time_plan(engine, win_pkts[:n0], pp,
                                   repeat=repeat)))
    return {b: fit_coefficients(ss, base=default_coefficients(b))
            for b, ss in samples.items() if ss}
