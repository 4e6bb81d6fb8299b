"""Nestable wall-clock spans that line up with device traces.

``span("tick/dispatch")`` times a host-side region and opens a
``jax.profiler.TraceAnnotation`` of the same name around it (on every
enabled span; the annotation costs little when no profiler session
records it), so in a profiler trace the host span lies on the same
clock as the device ops.  Keyword arguments
(``span("tick/ingest", tick=7)``) go to the annotation and come back
as the trace event's stats; the name stays as given.  Spans nest:
entering a span while another is open records the child under the
parent, and ``span_tree()`` renders the accumulated hierarchy.

The global switch is the ``SPLIDT_OBS`` environment variable (read
once at import; flip at runtime with :func:`set_enabled`).  When
disabled, :func:`span` returns one shared, reusable no-op context
manager — entering it is two trivial method calls with no allocation,
so instrumented hot loops cost nothing measurable.

Host timers (and therefore spans) measure nothing inside jit-traced
code — tracing runs once, execution happens later on device.  splint
rule R009 rejects any span entry or ``time.perf_counter`` call in
jit-reachable functions; keep instrumentation on the host side of
every dispatch.
"""
from __future__ import annotations

import os
import threading
import time
from typing import Dict, List, Optional

__all__ = [
    "SpanNode",
    "enabled",
    "reset_spans",
    "set_enabled",
    "span",
    "span_tree",
]

_ENABLED = os.environ.get("SPLIDT_OBS", "1") not in ("0", "false", "off")


def enabled() -> bool:
    """Is observability timing currently on?"""
    return _ENABLED


def set_enabled(on: bool) -> bool:
    """Flip the global switch; returns the previous value."""
    global _ENABLED
    prev = _ENABLED
    _ENABLED = bool(on)
    return prev


class SpanNode:
    """Aggregated timings for one span name at one nesting position.

    Re-entering the same name under the same parent accumulates into
    one node (``count`` calls, ``total_s`` seconds) rather than
    growing an unbounded list — a server alive for millions of ticks
    keeps a tree the size of its instrumentation, not its history.
    """

    __slots__ = ("name", "count", "total_s", "children")

    def __init__(self, name: str):
        self.name = name
        self.count = 0
        self.total_s = 0.0
        self.children: Dict[str, "SpanNode"] = {}

    def child(self, name: str) -> "SpanNode":
        node = self.children.get(name)
        if node is None:
            node = self.children[name] = SpanNode(name)
        return node

    def render(self, indent: int = 0) -> List[str]:
        lines = []
        if self.name:
            lines.append("%s%-28s %8d calls  %10.3f ms" % (
                "  " * indent, self.name, self.count,
                self.total_s * 1e3))
        for key in sorted(self.children):
            lines.extend(self.children[key].render(
                indent + (1 if self.name else 0)))
        return lines


class _SpanState(threading.local):
    def __init__(self):
        self.root = SpanNode("")
        self.stack: List[SpanNode] = []


_STATE = _SpanState()


class _Span:
    """Context manager for one timed region (enabled path)."""

    __slots__ = ("name", "args", "_t0", "_node", "_annot")

    def __init__(self, name: str, args: dict):
        self.name = name
        self.args = args
        self._t0 = 0.0
        self._node: Optional[SpanNode] = None
        self._annot = None

    def __enter__(self):
        parent = _STATE.stack[-1] if _STATE.stack else _STATE.root
        self._node = parent.child(self.name)
        _STATE.stack.append(self._node)
        annot = _trace_annotation()
        if annot is not None:
            self._annot = annot(self.name, **self.args)
            self._annot.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        if self._annot is not None:
            self._annot.__exit__(*exc)
            self._annot = None
        node = self._node
        node.count += 1
        node.total_s += dt
        _STATE.stack.pop()
        return False


class _NullSpan:
    """Shared no-op context — the whole disabled path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _NullSpan()
_UNRESOLVED = object()
_ANNOTATION = _UNRESOLVED


def _trace_annotation():
    """``jax.profiler.TraceAnnotation`` if jax is importable, else None.

    Resolved on the first enabled span and kept, so ``repro.obs`` stays
    importable without jax (the metrics half is pure numpy) and a
    missing profiler degrades to plain wall-clock spans.
    """
    global _ANNOTATION
    if _ANNOTATION is _UNRESOLVED:
        try:
            import jax
            _ANNOTATION = jax.profiler.TraceAnnotation
        except Exception:
            _ANNOTATION = None
    return _ANNOTATION


def span(name: str, **args):
    """Open a timed region.  ``with span("tick/admit"): ...``

    ``args`` become the trace annotation's stats
    (``span("tick/ingest", tick=n)``).  No-op (the shared null context,
    no span object) when observability is disabled.
    """
    if not _ENABLED:
        return _NULL
    return _Span(name, args)


def span_tree() -> str:
    """Render this thread's accumulated span hierarchy."""
    lines = _STATE.root.render()
    if not lines:
        return "(no spans recorded)"
    return "\n".join(lines)


def reset_spans() -> None:
    """Drop this thread's accumulated spans (tests, between runs)."""
    _STATE.root = SpanNode("")
    _STATE.stack = []
