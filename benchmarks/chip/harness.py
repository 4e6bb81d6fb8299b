"""One run of one cell: set-up, measured window, check, one result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file found by its name in ``BENCHMARK.json``:

* ``benchmarks/chip/configs/<config>.json`` — model, table and engine;
* ``benchmarks/chip/traffic/<traffic>.json`` — pool, concurrency, tick
  size, and ``arrivals``: ``"saturate"`` (ticks of ``max_tick`` packets
  back to back) or ``"open"`` (every packet due at ``rate_pkts_per_s``,
  at most ``max_tick`` per call);
* ``benchmarks/chip/metrics/<metric>.py`` — a ``read(ctx)`` that returns
  the per-layer metric, or ``None`` when it finds nothing to read.

A run serves the cell through ``FlowTableServer.ingest``:

1. set-up (``setup_s``): templates and schedule from ``--seed``; the
   configuration's model (trained once, then cached); the server; one
   call per tick shape the window can produce, so nothing compiles in the
   window; then the ramp, ingesting the stream until concurrency is
   steady;
2. window: ``--seconds`` of serving, saturating or open-loop;
3. check: every verdict of the ramp and the window against the plain
   reference (``reference.py``) on the template it was replayed from, and
   every flow whose last packet was ingested must have exactly one
   verdict.

The last line of standard output is the result; the numbers compared
and their limits are the last lines of standard error and the last key
of the result.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import sys
import tempfile
import time

import numpy as np

from benchmarks.chip import model as model_lib
from benchmarks.chip import reference, roofline, trace_reduce, traffic

REL = "benchmarks/chip"
COMPILE_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
TRACE_CAP_S = 10.0     # a traced window is at most this long


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class NoChip(SystemExit):
    pass


# ---------------------------------------------------------------------------
# the benchmark's files
# ---------------------------------------------------------------------------
def load_cell(root: str, name: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; {sorted(cells)}")
    cell = cells[name]
    cfgs = {c["name"]: c for c in spec["configs"]}
    with open(os.path.join(root, cfgs[cell["config"]]["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(root, REL, "traffic", cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if name in m.get("workloads", [name] if m["moves"] in e2e_names
                              else [])]
    return dict(cell=cell, cfg=cfg, mix=mix, end_to_end=e2e, per_layer=layer)


def load_reader(root: str, metric: str):
    path = os.path.join(root, REL, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# the device
# ---------------------------------------------------------------------------
def require_chip(n_chips: int) -> dict:
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise NoChip(f"no TPU: jax.devices()[0] is {d.platform!r} "
                     f"({d.device_kind}); the benchmark runs on the chip only")
    if len(devs) < n_chips:
        raise NoChip(f"the cell needs {n_chips} chips, JAX sees {len(devs)}")
    roofline.peak(d.device_kind)        # an unknown chip is an error
    return device_info(n_chips)


def device_info(n_chips: int) -> dict:
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": n_chips}


def memory_peak(n_chips: int) -> int:
    import jax
    peaks = []
    for d in jax.devices()[:n_chips]:
        st = d.memory_stats() or {}
        peaks.append(int(st.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def use_compile_cache(root: str) -> str:
    """JAX's persistent cache at a fixed path inside the checkout; every
    program is cached, however quick its compile."""
    import jax
    path = os.path.join(os.path.abspath(root), REL, ".cache", "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileCounter:
    """Counts lowerings (every jit cache miss, compiled or loaded from the
    persistent cache) while ``on``."""

    def __init__(self):
        import jax
        self.on = False
        self.n = 0

        def listen(event, duration, **kw):
            if self.on and event == COMPILE_EVENT:
                self.n += 1
        jax.monitoring.register_event_duration_secs_listener(listen)


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------
def build_server(cfg: dict, plain: dict):
    from repro.core.inference import Engine, EngineOptions
    from repro.serve import FlowTableServer

    pdt = model_lib.from_plain(plain)
    eng = Engine.from_model(pdt)
    return FlowTableServer(eng, n_buckets=int(cfg["n_buckets"]),
                           bucket_size=int(cfg["bucket_size"]),
                           tick_engine=cfg["tick_engine"],
                           options=EngineOptions(impl=cfg["impl"]))


def tick_ladder(max_tick: int, max_rank: int, floor: int = 64):
    """Every ``(ranks, columns)`` tick shape of the server's power-of-two
    ladder that a tick of at most ``max_tick`` packets can pack to."""
    cols = []
    c = floor
    while c < traffic.pow2_at_least(max_tick, floor) * 2:
        cols.append(c)
        c *= 2
    ranks = []
    r = 1
    while r <= traffic.pow2_at_least(max_rank):
        ranks.append(r)
        r *= 2
    return [(r, c) for r in ranks for c in cols if r <= c]


def warm(srv, sched: traffic.Schedule, shapes) -> None:
    """One ingest per tick shape, of throwaway flows that complete in it.

    ``C`` fresh flows, the first with ``R`` packets and the rest with one,
    pack to ``(R, C)`` and admit ``C`` slots; each flow is as long as the
    packets it sends, so all finish and free their slots."""
    row = sched.b_pkts[:1]
    key = 1 << 62          # far above every instance key of the stream
    for R, C in shapes:
        ids = np.concatenate([np.full(R, key, np.int64),
                              key + 1 + np.arange(C - 1, dtype=np.int64)])
        lens = np.concatenate([np.full(R, R, np.int32),
                               np.ones(C - 1, np.int32)])
        key += C
        n = ids.size
        srv.ingest(traffic.Batch(ids, lens, np.repeat(row, n, axis=0),
                                 np.zeros(n), np.zeros(n, np.int64)))


class Verdicts:
    def __init__(self):
        self.parts = []

    def add(self, v) -> np.ndarray:
        if v.n_flows:
            self.parts.append(np.stack(
                [np.asarray(v.flow_id, np.int64),
                 np.asarray(v.labels, np.int64),
                 np.asarray(v.recircs, np.int64),
                 np.asarray(v.exit_partition, np.int64)], axis=1))
        return np.asarray(v.flow_id, np.int64)

    def array(self) -> np.ndarray:
        if not self.parts:
            return np.empty((0, 4), np.int64)
        return np.concatenate(self.parts)


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------
def window_saturate(srv, sched, pos, seconds, tick, out):
    calls = []
    t0 = time.perf_counter()
    end = t0 + seconds
    while True:
        b = sched.batch(pos, pos + tick)
        t_call = time.perf_counter()
        v = srv.ingest(b)
        t_ret = time.perf_counter()
        out.add(v)
        calls.append((pos, pos + tick, t_call, t_ret))
        pos += tick
        if t_ret >= end:
            break
    return t0, calls, pos, 0.0


def window_open(srv, sched, pos, seconds, tick, rate, out, keys_out):
    """Each call takes every packet due (at most ``tick``); when none is
    due the generator sleeps until the next one is.  Returns the calls
    and the generator's own host seconds (call to call, less sleep)."""
    calls = []
    w0 = pos
    t0 = time.perf_counter()
    end = t0 + seconds
    slept = gen = 0.0
    t_prev = t0
    while True:
        now = time.perf_counter()
        if now >= end:
            break
        due = w0 + int(math.floor((now - t0) * rate)) + 1
        n = min(due - pos, tick)
        if n <= 0:
            wait = max(0.0, t0 + (pos - w0) / rate - now)
            time.sleep(wait)
            slept += time.perf_counter() - now
            continue
        b = sched.batch(pos, pos + n)
        t_call = time.perf_counter()
        gen += t_call - t_prev - slept
        v = srv.ingest(b)
        t_ret = t_prev = time.perf_counter()
        slept = 0.0
        keys_out.append(out.add(v))
        calls.append((pos, pos + n, t_call, t_ret))
        pos += n
    return t0, calls, pos, gen


def latencies(sched, calls, keys, t0, w0, rate) -> tuple[np.ndarray, int]:
    """Per verdict: its call's return minus the due time of its flow's
    last packet in that call; and the count of verdicts whose flow sent
    nothing in the call that emitted them."""
    lat, orphans = [], 0
    for (a, b, _, t_ret), vk in zip(calls, keys):
        if not vk.size:
            continue
        bk = sched.batch(a, b).flow_id
        order = np.argsort(bk, kind="stable")
        sk = bk[order]
        hi = np.searchsorted(sk, vk, side="right") - 1
        found = (hi >= 0) & (sk[np.maximum(hi, 0)] == vk)
        orphans += int((~found).sum())
        last_pos = a + order[hi[found]]
        lat.append(t_ret - (t0 + (last_pos - w0) / rate))
    return (np.concatenate(lat) if lat else np.empty(0)), orphans


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------
def completed_keys(sched, n_pos: int, step: int = 1 << 20) -> np.ndarray:
    """Keys of every instance whose last packet lies before ``n_pos``."""
    keys = []
    for a in range(0, n_pos, step):
        b = sched.batch(a, min(a + step, n_pos))
        last = b.pkt_index == b.flow_len.astype(np.int64) - 1
        keys.append(b.flow_id[last])
    return np.concatenate(keys) if keys else np.empty(0, np.int64)


def check(verdicts: np.ndarray, want: np.ndarray, M: int,
          completed: np.ndarray, orphans: int = 0) -> dict:
    """Numbers compared, each ``[value, limit]``."""
    fid = verdicts[:, 0]
    uniq = np.unique(fid)
    dup = int(fid.size - uniq.size)
    got = verdicts[:, 1:]
    exp = want[traffic.template_of(fid, M)]
    wrong = int(np.any(got != exp, axis=1).sum())
    missing = int(np.setdiff1d(completed, uniq, assume_unique=False).size)
    return {"wrong_verdicts": [wrong, 0], "missing_verdicts": [missing, 0],
            "duplicate_verdicts": [dup, 0], "orphan_verdicts": [orphans, 0]}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
class Served:
    """A cell set up and ramped: the server, its traffic and the
    verdicts it has emitted so far."""

    def __init__(self, root: str, name: str, seed: int, *, t_start: float,
                 require_tpu: bool = True, spans: bool = False):
        spec = self.spec = load_cell(root, name)
        cfg, mix = self.cfg, self.mix = spec["cfg"], spec["mix"]
        self.n_chips = int(spec["cell"]["chips"])
        self.device = (require_chip(self.n_chips) if require_tpu
                       else device_info(self.n_chips))
        cache = use_compile_cache(root)
        from repro import obs
        obs.set_enabled(spans)
        self.counter = CompileCounter()
        setup = {}

        # -- data: templates and their replay
        t = time.perf_counter()
        rng = np.random.default_rng(
            np.random.SeedSequence([0x7EA1, int(seed)]))
        self.flows = traffic.make_flows(
            cfg["dataset"], int(mix["pool"]), rng,
            len_median=cfg["len_median"], len_sigma=cfg["len_sigma"],
            min_len=cfg["min_len"], max_len=cfg["max_len"])
        self.sched = traffic.Schedule(self.flows, float(mix["concurrency"]),
                                      rng)
        setup["data_s"] = time.perf_counter() - t

        # -- model and server
        t = time.perf_counter()
        self.plain, cached = model_lib.load(
            cfg, os.path.join(root, REL, ".cache", "models"))
        self.srv = build_server(cfg, self.plain)
        setup["model_s"] = time.perf_counter() - t

        # -- every tick shape the window can produce
        t = time.perf_counter()
        self.tick = int(mix["max_tick"])
        shapes = tick_ladder(self.tick, self.sched.max_rank(self.tick),
                             self.srv._rank_floor)
        self.counter.on = True
        warm(self.srv, self.sched, shapes)
        n_lowered = self.counter.n
        setup["compile_s"] = time.perf_counter() - t

        # -- ramp to steady concurrency
        t = time.perf_counter()
        self.out = Verdicts()
        self.pos = 0
        while self.pos < self.sched.ramp_pkts:
            b = min(self.tick, self.sched.ramp_pkts - self.pos)
            self.out.add(self.srv.ingest(
                self.sched.batch(self.pos, self.pos + b)))
            self.pos += b
        setup["ramp_s"] = time.perf_counter() - t
        setup["ramp_pkts"] = self.sched.ramp_pkts
        setup["ramp_lowered"] = self.counter.n - n_lowered
        self.setup_s = time.perf_counter() - t_start
        log(f"setup: {self.setup_s:.3f} s = " + ", ".join(
            f"{k} {v:.3f}" if isinstance(v, float) else f"{k} {v}"
            for k, v in setup.items())
            + f"; {len(shapes)} tick shapes, {n_lowered} lowered; model "
            + ("cached" if cached else "trained") + f"; compile cache {cache}")

    def window(self, seconds: float, rate: float | None = None) -> dict:
        """Serve ``seconds``: saturating when ``rate`` is None, else open
        loop at ``rate`` packets per second."""
        import jax
        st0 = dict(self.srv.stats.as_dict())
        n0 = self.counter.n
        w0 = self.pos
        keys = []
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            if rate is None:
                t0, calls, self.pos, gen_s = window_saturate(
                    self.srv, self.sched, self.pos, seconds, self.tick,
                    self.out)
            else:
                t0, calls, self.pos, gen_s = window_open(
                    self.srv, self.sched, self.pos, seconds, self.tick,
                    rate, self.out, keys)
        st1 = self.srv.stats.as_dict()
        in_window = [c for c in calls if c[3] <= t0 + seconds]
        pk_in = sum(b - a for a, b, _, _ in in_window)
        return dict(t0=t0, w0=w0, seconds=seconds, rate=rate, calls=calls,
                    keys=keys, gen_s=gen_s, pkts_in_window=pk_in,
                    compiles=self.counter.n - n0,
                    stats={k: st1[k] - st0[k] for k in st1},
                    backlog_pkts=(w0 + int(seconds * rate) - self.pos
                                  if rate is not None else 0))

    def latencies(self, w: dict) -> tuple[np.ndarray, int]:
        return latencies(self.sched, w["calls"], w["keys"], w["t0"],
                         w["w0"], w["rate"])


def run(root: str, name: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_tpu: bool = True,
        plant=None) -> dict:
    """One run of cell ``name``; returns the result object.

    ``plant`` (tests only) is called with the reference verdicts before
    the comparison, to plant a wrong expectation."""
    import jax

    cell = Served(root, name, seed, t_start=t_start, require_tpu=require_tpu,
                  spans=bool(trace))
    spec, mix = cell.spec, cell.mix
    rate = (None if mix["arrivals"] == "saturate"
            else float(mix["rate_pkts_per_s"]))
    seconds = min(float(seconds), TRACE_CAP_S) if trace else float(seconds)
    tmpdir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    if trace:
        # spans and device events only: the Python tracer's per-call
        # events would slow the host code the spans time
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tmpdir, profiler_options=opts)
    w = cell.window(seconds, rate)
    if trace:
        jax.profiler.stop_trace()

    # -- read the device's peak, free the program's state, then check
    peak_bytes = memory_peak(cell.n_chips)
    del cell.srv
    gc.collect()

    t = time.perf_counter()
    want = reference.verdicts(cell.plain, cell.flows.pkts, cell.flows.lengths)
    if plant is not None:
        plant(want)
    lat, orphans = (cell.latencies(w) if rate is not None
                    else (None, 0))
    got = cell.out.array()
    numbers = check(got, want, cell.sched.M,
                    completed_keys(cell.sched, cell.pos), orphans)
    ref_s = time.perf_counter() - t
    failed = sum(v for v, _ in numbers.values())
    attempted = int(got.shape[0]) + numbers["missing_verdicts"][0]
    correct = failed == 0 and attempted > 0

    pk_in = w["pkts_in_window"]
    info = {
        "calls": len(w["calls"]), "pkts_in_window": pk_in,
        "window_s": seconds, "backlog_pkts": w["backlog_pkts"],
        "delivered_pkts_per_s": pk_in / seconds,
        "compiles_in_window": w["compiles"], "reference_s": ref_s,
        "verdicts": int(got.shape[0]), "stats": w["stats"],
        "recirc_overhead": float(np.sum(want[traffic.template_of(
            got[:, 0], cell.sched.M), 1])) / max(cell.pos, 1),
    }
    if lat is not None and lat.size:
        info["latency_ms"] = {q: float(np.percentile(lat, q) * 1e3)
                              for q in (50, 90, 99)}
    log("window: " + json.dumps(info))

    metrics = {}
    if not trace:
        values = {"setup_s": cell.setup_s, "pkts_per_s": pk_in / seconds}
        if lat is not None and lat.size:
            values["verdict_p50_ms"] = float(np.percentile(lat, 50) * 1e3)
            values["verdict_p99_ms"] = float(np.percentile(lat, 99) * 1e3)
        for m in spec["end_to_end"]:
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": int(failed), "metrics": metrics,
              "device": dict(cell.device, memory_peak_bytes=peak_bytes)}

    if trace:
        import glob
        import shutil
        files = glob.glob(os.path.join(tmpdir, "**", "*.xplane.pb"),
                          recursive=True)
        summ = trace_reduce.summarize(files[0]) if files else None
        ctx = layer_context(summ, cell.plain, want, cell.sched, w,
                            cell.device)
        for m in spec["per_layer"]:
            v = load_reader(root, m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        if summ is not None:
            result["device"]["busy_s"] = summ.busy_s
            result["device"]["window_s"] = summ.window_s
            result["breakdown"] = {"device_ops": summ.top_ops(10),
                                   "idle_gaps": summ.idle_gaps(10)}
        shutil.rmtree(tmpdir, ignore_errors=True)
    result["check"] = {k: {"value": v, "limit": lim}
                       for k, (v, lim) in numbers.items()}
    for k, (v, lim) in numbers.items():
        log(f"check {k}: {v} (limit {lim})")
    return result


def layer_context(summ, plain, want, sched, w: dict, device: dict) -> dict:
    """What the per-layer readers read (see ``metrics/*.py``)."""
    P = len(plain["partition_sizes"])
    calls = w["calls"]
    ticks = len(calls)
    n_pkts = n_hops = n_new = 0
    if calls:
        a, b = calls[0][0], calls[-1][1]
        last, hop = roofline.exit_packets(want, sched.flows.lengths, P)
        for lo in range(a, b, 1 << 20):
            bt = sched.batch(lo, min(lo + (1 << 20), b))
            tm = traffic.template_of(bt.flow_id, sched.M)
            j = bt.pkt_index
            n_pkts += int((j <= last[tm]).sum())
            n_hops += int(hop[tm, j].sum())
            n_new += int((j == 0).sum())
    return {
        "trace": summ, "ticks": ticks, "stats": w["stats"],
        "compiles_in_window": w["compiles"],
        "peak": roofline.peak(device["kind"]) if device["platform"] == "tpu"
        else None,
        "bytes": roofline.work_bytes(plain, n_pkts, n_hops, n_new, ticks),
        "folded_pkts": n_pkts, "hops": n_hops, "new_flows": n_new,
        "tick_pkts": [b - a for a, b, _, _ in calls],
        "gen_s": w["gen_s"],
    }
