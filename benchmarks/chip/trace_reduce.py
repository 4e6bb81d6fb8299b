"""Reduce a JAX profiler trace to the benchmark's device numbers.

Input is the ``.xplane.pb`` the profiler writes, read with
``jax.profiler.ProfileData`` (no TensorBoard needed).  Output is a
:class:`Summary` of one traced window:

* the window itself: the host span ``bench/window`` that the harness
  opens around its measured loop;
* device busy time: the union of the intervals of device operations
  (the ``XLA Ops`` line of every device plane) inside the window,
  averaged over the devices that ran any;
* per-program device time: the executions on the ``XLA Modules`` line
  of each device plane (``jit_tick_step(...)``, ...), clipped to the
  window; a TPU trace's operations carry no module name of their own;
* per-operation device time (clipped to the window), keyed by the
  operation's name, and a pattern lookup over the name and its string
  stats for kernels (a Pallas kernel's operation is named after its
  function: ``%dt_traverse_pallas.9 = ... custom-call(...)``);
* idle gaps: the stretches of the window in which no device operation
  ran, each labelled by the ``tick/*`` span open on the host
  at its middle (``host:other`` when none was).  The ``tick/*`` spans
  of one ingest call run one after another, so at most one is open.

Device and host events of one ``.xplane.pb`` share the profiler's clock.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from collections import defaultdict

WINDOW_SPAN = "bench/window"
HOST_SPAN = re.compile(r"^tick/")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Op:
    name: str
    start_ns: float
    dur_ns: float
    text: str           # name plus string stats, for pattern lookups


@dataclasses.dataclass
class Summary:
    window: tuple[float, float]               # ns
    ops: dict[str, list[Op]]                  # device plane -> ops in window
    host_spans: list[tuple[str, float, float]]  # (name, start_ns, end_ns)
    modules: dict[str, list[Op]] = dataclasses.field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_intervals(self, device: str) -> list[tuple[float, float]]:
        lo, hi = self.window
        iv = sorted((max(o.start_ns, lo), min(o.start_ns + o.dur_ns, hi))
                    for o in self.ops[device])
        out: list[list[float]] = []
        for a, b in iv:
            if b <= a:
                continue
            if out and a <= out[-1][1]:
                out[-1][1] = max(out[-1][1], b)
            else:
                out.append([a, b])
        return [(a, b) for a, b in out]

    @property
    def devices(self) -> list[str]:
        return [d for d, ops in self.ops.items() if ops]

    @property
    def busy_s(self) -> float:
        """Union of device-op intervals, averaged over active devices."""
        devs = self.devices
        if not devs:
            return 0.0
        tot = sum(b - a for d in devs for a, b in self.busy_intervals(d))
        return tot * 1e-9 / len(devs)

    def op_seconds(self, pattern: str) -> float:
        """Device seconds of the operations whose text matches ``pattern``
        (summed over devices, then averaged over active devices)."""
        rx = re.compile(pattern)
        devs = self.devices
        if not devs:
            return 0.0
        tot = sum(o.dur_ns for d in devs for o in self.ops[d]
                  if rx.search(o.text))
        return tot * 1e-9 / len(devs)

    def program_seconds(self, pattern: str) -> float:
        """Device seconds of the program executions (``XLA Modules``
        line) whose name matches ``pattern``, averaged over active
        devices."""
        rx = re.compile(pattern)
        devs = self.devices
        if not devs:
            return 0.0
        tot = sum(m.dur_ns for d in devs for m in self.modules.get(d, ())
                  if rx.search(m.name))
        return tot * 1e-9 / len(devs)

    def top_ops(self, n: int = 10) -> list[list]:
        agg: dict[str, float] = defaultdict(float)
        devs = self.devices
        for d in devs:
            for o in self.ops[d]:
                agg[o.name] += o.dur_ns * 1e-9 / len(devs)
        return [[k, v] for k, v in
                sorted(agg.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> list[list]:
        """Idle seconds by the host span open at each gap's middle."""
        agg: dict[str, float] = defaultdict(float)
        devs = self.devices
        lo, hi = self.window
        spans = sorted(self.host_spans, key=lambda s: s[1])
        starts = [s[1] for s in spans]
        for d in devs:
            edges = [lo]
            for a, b in self.busy_intervals(d):
                edges.extend((a, b))
            edges.append(hi)
            for a, b in zip(edges[::2], edges[1::2]):
                if b <= a:
                    continue
                mid = 0.5 * (a + b)
                i = bisect.bisect_right(starts, mid) - 1
                # tick/* spans run one after another, never nested
                label = (spans[i][0] if i >= 0 and mid < spans[i][2]
                         else "host:other")
                agg[label] += (b - a) * 1e-9 / len(devs)
        return [[k, v] for k, v in
                sorted(agg.items(), key=lambda kv: -kv[1])[:n]]


def _text(ev) -> str:
    parts = [ev.name]
    for st in ev.stats:
        try:
            k, v = st
        except (TypeError, ValueError):
            continue
        if isinstance(v, str):
            parts.append(f"{k}={v}")
    return " ".join(parts)


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CUSTOM" not in name


def summarize_planes(planes) -> Summary:
    """Reduce planes (``ProfileData.planes`` or look-alikes with
    ``name``, ``lines``; lines with ``name``, ``events``; events with
    ``name``, ``start_ns``, ``duration_ns``, ``stats``)."""
    window = None
    host_spans = []
    ops: dict[str, list[Op]] = {}
    modules: dict[str, list[Op]] = {}
    for pl in planes:
        if is_device_plane(pl.name):
            by_line = {OPS_LINE: ops.setdefault(pl.name, []),
                       MODULES_LINE: modules.setdefault(pl.name, [])}
            for line in pl.lines:
                lst = by_line.get(line.name)
                if lst is None:
                    continue
                for ev in line.events:
                    lst.append(Op(ev.name, float(ev.start_ns),
                                  float(ev.duration_ns), _text(ev)))
            continue
        for line in pl.lines:
            for ev in line.events:
                if ev.name == WINDOW_SPAN:
                    s = float(ev.start_ns)
                    window = (s, s + float(ev.duration_ns))
                elif HOST_SPAN.match(ev.name):
                    s = float(ev.start_ns)
                    host_spans.append((ev.name, s, s + float(ev.duration_ns)))
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    lo, hi = window

    def clip(o: Op) -> Op:
        a, b = max(o.start_ns, lo), min(o.start_ns + o.dur_ns, hi)
        return Op(o.name, a, b - a, o.text)
    def inside(by_dev: dict[str, list[Op]]) -> dict[str, list[Op]]:
        return {d: [clip(o) for o in lst
                    if o.start_ns < hi and o.start_ns + o.dur_ns > lo]
                for d, lst in by_dev.items()}
    host_spans = [s for s in host_spans if s[1] < hi and s[2] > lo]
    return Summary(window, inside(ops), host_spans, inside(modules))


def summarize(path: str) -> Summary:
    from jax.profiler import ProfileData
    return summarize_planes(ProfileData.from_file(path).planes)


def span_seconds(summary: Summary, name: str) -> float | None:
    """Total host seconds of span ``name`` inside the window."""
    lo, hi = summary.window
    hits = [(a, b) for n, a, b in summary.host_spans if n == name]
    if not hits:
        return None
    return sum(min(b, hi) - max(a, lo) for a, b in hits) * 1e-9
