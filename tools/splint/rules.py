"""The ~8 splint rules: this repo's contracts, as AST checks.

Each rule encodes one invariant the runtime test suite can only probe
pointwise (docs/ANALYSIS.md has the full rationale table):

  R001  ordered reductions only        docs/PARITY.md §1
  R002  no host sync under jit         docs/ARCHITECTURE.md (dispatch)
  R003  explicit dtypes                docs/PARITY.md §1 (f32 contract)
  R004  seeded RNG streams only        flows/synthetic.py convention
  R005  no legacy engine kwargs        EngineOptions (PR 6 deprecation)
  R006  no python branching on tracers ConcretizationError hazard
  R007  no donated-buffer reuse        donate_argnums semantics
  R008  -1 sentinel discipline         docs/PARITY.md §2
  R009  no host timing under jit       docs/OBSERVABILITY.md (R009)

Scoping: every rule skips the LM prototype tree
(``core.EXCLUDED_TREES``); R001 additionally restricts itself to the
parity-critical ``kernels/`` + ``fit/`` modules, and R005 skips the two
files that *implement* the deprecation shim.
"""
from __future__ import annotations

import ast
from typing import Iterator

from tools.splint import callgraph
from tools.splint.core import Diagnostic, Fix, LintContext, rule

# ---------------------------------------------------------------------------
# shared helpers
# ---------------------------------------------------------------------------


def _attr_chain(node: ast.AST) -> str:
    """Dotted name of an attribute chain ('jnp.sum'), '' if not one."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _walk_own(fn: ast.FunctionDef) -> Iterator[ast.AST]:
    """Walk a function body excluding nested function bodies (nested
    defs are visited on their own when reachable)."""
    stack: list[ast.AST] = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        yield node
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda)):
            stack.extend(ast.iter_child_nodes(node))


def _diag(ctx: LintContext, node: ast.AST, code: str, msg: str,
          fix: Fix | None = None) -> Diagnostic:
    return Diagnostic(ctx.path, node.lineno, node.col_offset, code, msg,
                      fix=fix)


# ---------------------------------------------------------------------------
# R001 — ordered reductions only in parity-critical modules
# ---------------------------------------------------------------------------

_R001_BANNED = {"jnp.sum", "jnp.dot", "jnp.cumsum", "jnp.matmul"}


@rule("R001", "ordered-reduction",
      "XLA-order reductions are banned in kernels/ and fit/: route f32 "
      "sums through kernels.ref.ordered_wsum / core.tree.class_sq_chain "
      "(docs/PARITY.md §1). Integer (exact) reductions may carry an "
      "allow pragma stating so.",
      applies=lambda ctx: ctx.in_tree("src/repro/kernels/",
                                      "src/repro/fit/"))
def check_r001(ctx: LintContext):
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Call):
            name = _attr_chain(node.func)
            if name in _R001_BANNED:
                yield _diag(
                    ctx, node, "R001",
                    f"`{name}` lets XLA pick the summation tree; use "
                    "kernels.ref.ordered_wsum / core.tree.class_sq_chain "
                    "for f32 reductions (PARITY.md §1), or suppress with "
                    "a reason if the reduction is integer-exact")


# ---------------------------------------------------------------------------
# R002 — no host sync inside jit-reachable code
# ---------------------------------------------------------------------------

_SHAPE_ATTRS = {"shape", "ndim", "size", "dtype"}
_STATIC_CALLS = {"len", "prod", "round", "min", "max", "range", "int",
                 "float", "bool", "abs", "sum"}


def _static_expr(node: ast.AST, static_names: set) -> bool:
    """Conservatively true when an expression is trace-time static
    (python scalars, shapes, static_argnames)."""
    if isinstance(node, ast.Constant):
        return True
    if isinstance(node, ast.Name):
        return node.id in static_names or node.id.isupper()
    if isinstance(node, ast.Attribute):
        if node.attr in _SHAPE_ATTRS:
            return True
        return _static_expr(node.value, static_names)
    if isinstance(node, ast.Subscript):
        return _static_expr(node.value, static_names)
    if isinstance(node, ast.BinOp):
        return (_static_expr(node.left, static_names)
                and _static_expr(node.right, static_names))
    if isinstance(node, ast.UnaryOp):
        return _static_expr(node.operand, static_names)
    if isinstance(node, ast.Call):
        # only *builtins* and np/math shape helpers are static; a method
        # call (x.sum()) on a traced array never is
        if isinstance(node.func, ast.Name):
            ok = node.func.id in _STATIC_CALLS
        else:
            ok = _attr_chain(node.func) in (
                "np.prod", "math.prod", "math.ceil", "math.floor",
                "np.ceil", "np.floor")
        return ok and all(_static_expr(a, static_names) for a in node.args)
    if isinstance(node, (ast.Tuple, ast.List)):
        return all(_static_expr(e, static_names) for e in node.elts)
    return False


_HOST_SYNC_CALLS = {"np.asarray", "np.array", "numpy.asarray",
                    "numpy.array", "onp.asarray", "jax.device_get"}


@rule("R002", "host-sync-under-jit",
      "Host synchronisation (.item()/.tolist(), float()/int()/bool() on "
      "traced values, np.asarray, jax.device_get) inside a @jax.jit "
      "function or a helper reachable from one forces a device round "
      "trip per call — the O(1)-dispatch bound (kernels/tick_step.py) "
      "dies silently.",
      applies=lambda ctx: True)
def check_r002(ctx: LintContext):
    graph = callgraph.build(ctx.tree)
    static_all = set().union(*graph.static_args.values()) \
        if graph.static_args else set()
    for name in sorted(graph.reachable):
        fn = graph.functions.get(name)
        if fn is None:
            continue
        statics = static_all | graph.static_args.get(name, set())
        for node in _walk_own(fn):
            if not isinstance(node, ast.Call):
                continue
            chain = _attr_chain(node.func)
            if (isinstance(node.func, ast.Attribute)
                    and node.func.attr in ("item", "tolist")
                    and not node.args):
                yield _diag(
                    ctx, node, "R002",
                    f"`.{node.func.attr}()` inside jit-reachable "
                    f"`{name}` blocks on the device; return the array "
                    "and sync once at the caller")
            elif chain in _HOST_SYNC_CALLS and node.args and \
                    not _static_expr(node.args[0], statics):
                yield _diag(
                    ctx, node, "R002",
                    f"`{chain}` on a traced value inside jit-reachable "
                    f"`{name}` is a host transfer; keep the hot path "
                    "device-resident (use jnp ops)")
            elif isinstance(node.func, ast.Name) and \
                    node.func.id in ("float", "int", "bool") and \
                    len(node.args) == 1 and \
                    not _static_expr(node.args[0], statics):
                yield _diag(
                    ctx, node, "R002",
                    f"`{node.func.id}(...)` on a possibly-traced value "
                    f"inside jit-reachable `{name}` concretises (host "
                    "sync or ConcretizationTypeError); use jnp casts, "
                    "or suppress with a reason if the argument is "
                    "static")


# ---------------------------------------------------------------------------
# R003 — explicit dtypes on jnp array constructors
# ---------------------------------------------------------------------------

#: constructor -> index of the positional dtype slot
_R003_CTORS = {"zeros": 1, "ones": 1, "full": 2, "arange": 3}


def _r003_fix(ctx: LintContext, node: ast.Call, ctor: str) -> Fix | None:
    """Mechanical fix: append the dtype jax would infer anyway, so the
    edit is semantics-preserving (x64 disabled, the repo default)."""
    if ctor in ("zeros", "ones"):
        dtype = "jnp.float32"
    elif ctor == "full":
        fill = node.args[1] if len(node.args) > 1 else None
        if isinstance(fill, ast.UnaryOp) and \
                isinstance(fill.op, (ast.USub, ast.UAdd)):
            fill = fill.operand          # -1 parses as USub(Constant(1))
        if not isinstance(fill, ast.Constant):
            return None
        v = fill.value
        dtype = ("jnp.bool_" if isinstance(v, bool) else
                 "jnp.int32" if isinstance(v, int) else
                 "jnp.float32" if isinstance(v, float) else None)
        if dtype is None:
            return None
    else:  # arange
        if not all(isinstance(a, ast.Constant) for a in node.args):
            return None
        dtype = ("jnp.float32" if any(
            isinstance(a.value, float) for a in node.args) else "jnp.int32")
    end_col = node.end_col_offset - 1      # just before the ')'
    return Fix(node.end_lineno, end_col, node.end_lineno, end_col,
               f", dtype={dtype}")


@rule("R003", "explicit-dtype",
      "jnp.zeros/ones/full/arange without a dtype inherit jax's "
      "platform/x64-flag defaults; a silent f32/f64 or i32/i64 drift "
      "breaks the bit-exactness contract (docs/PARITY.md §1). "
      "Autofixable: --fix inserts the dtype jax would infer today.",
      applies=lambda ctx: ctx.in_tree("src/repro/"))
def check_r003(ctx: LintContext):
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        chain = _attr_chain(node.func)
        if not chain.startswith("jnp."):
            continue
        ctor = chain[4:]
        slot = _R003_CTORS.get(ctor)
        if slot is None:
            continue
        if len(node.args) > slot:
            continue                       # dtype passed positionally
        if any(kw.arg == "dtype" for kw in node.keywords):
            continue
        yield _diag(
            ctx, node, "R003",
            f"`jnp.{ctor}(...)` without an explicit dtype — pin it "
            "(PARITY.md §1: no silent f32/f64 drift)",
            fix=_r003_fix(ctx, node, ctor))


# ---------------------------------------------------------------------------
# R004 — seeded SeedSequence streams only
# ---------------------------------------------------------------------------

_R004_ALLOWED = {"default_rng", "SeedSequence", "Generator", "BitGenerator",
                 "PCG64", "Philox", "SFC64"}


@rule("R004", "seeded-rng-only",
      "Legacy np.random global-state calls make runs irreproducible; "
      "src/repro derives every stream from a seeded "
      "np.random.default_rng(SeedSequence(...)) (flows/synthetic.py is "
      "the convention).",
      applies=lambda ctx: ctx.in_tree("src/repro/"))
def check_r004(ctx: LintContext):
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Attribute) and \
                _attr_chain(node.value) in ("np.random", "numpy.random"):
            if node.attr not in _R004_ALLOWED:
                yield _diag(
                    ctx, node, "R004",
                    f"`np.random.{node.attr}` uses the global RNG state; "
                    "derive a seeded stream via np.random.default_rng("
                    "SeedSequence(...)) as in flows/synthetic.py")
        if isinstance(node, ast.Call) and \
                _attr_chain(node.func).endswith("random.default_rng") and \
                not node.args and not node.keywords:
            yield _diag(
                ctx, node, "R004",
                "`default_rng()` with no seed is OS-entropy seeded "
                "(irreproducible); pass a seed or SeedSequence")


# ---------------------------------------------------------------------------
# R005 — no legacy engine kwargs outside the shim
# ---------------------------------------------------------------------------

_SHIM_FILES = ("src/repro/core/inference.py", "src/repro/serve/streaming.py")
_LEGACY_KWARGS = {"impl", "compact", "micro_batch", "inflight", "mesh"}
_ENGINE_ENTRY_POINTS = {"run", "run_streaming", "run_looped",
                        "stream_batches"}


def _r005_fix(ctx: LintContext, node: ast.Call,
              legacy: list[ast.keyword]) -> Fix | None:
    if any(kw.arg in (None, "options") for kw in node.keywords):
        # options= already present (the shim raises on mixing) or a
        # **kwargs splat that may itself carry legacy keys: hand-fix
        return None
    func = ctx.segment(node.func)
    if not func:
        return None
    parts = [ctx.segment(a) for a in node.args]
    for kw in node.keywords:
        if kw in legacy:
            continue
        parts.append(f"**{ctx.segment(kw.value)}" if kw.arg is None
                     else f"{kw.arg}={ctx.segment(kw.value)}")
    opts = ", ".join(f"{kw.arg}={ctx.segment(kw.value)}" for kw in legacy)
    parts.append(f"options=EngineOptions({opts})")
    return Fix(node.lineno, node.col_offset, node.end_lineno,
               node.end_col_offset, f"{func}({', '.join(parts)})")


@rule("R005", "no-legacy-engine-kwargs",
      "Engine.run/run_streaming/run_looped/stream_batches legacy "
      "keywords (impl=/compact=/micro_batch=/inflight=/mesh=) "
      "are a deprecation shim; new call sites pass "
      "options=EngineOptions(...). Autofixable with --fix.",
      applies=lambda ctx: ctx.path not in _SHIM_FILES)
def check_r005(ctx: LintContext):
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        callee = f.attr if isinstance(f, ast.Attribute) else (
            f.id if isinstance(f, ast.Name) else "")
        if callee not in _ENGINE_ENTRY_POINTS:
            continue
        legacy = [kw for kw in node.keywords if kw.arg in _LEGACY_KWARGS]
        if not legacy:
            continue
        names = ", ".join(sorted(kw.arg for kw in legacy))
        yield _diag(
            ctx, node, "R005",
            f"legacy engine kwarg(s) {names} on `.{callee}(...)` — pass "
            "options=EngineOptions(...) (the kwargs warn "
            "DeprecationWarning and will be removed)",
            fix=_r005_fix(ctx, node, legacy))


# ---------------------------------------------------------------------------
# R006 — no python branching on tracer values
# ---------------------------------------------------------------------------

def _contains_jnp_call(node: ast.AST) -> str | None:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call):
            chain = _attr_chain(sub.func)
            if chain.startswith(("jnp.", "jax.")):
                return chain
    return None


@rule("R006", "no-tracer-branch",
      "`if`/`while` on a jnp expression inside jit-reachable code either "
      "raises ConcretizationTypeError or (via static fallback) "
      "recompiles per distinct value; use lax.cond/lax.select/jnp.where "
      "(docs/ARCHITECTURE.md backend contract).",
      applies=lambda ctx: True)
def check_r006(ctx: LintContext):
    graph = callgraph.build(ctx.tree)
    for name in sorted(graph.reachable):
        fn = graph.functions.get(name)
        if fn is None:
            continue
        for node in _walk_own(fn):
            if isinstance(node, (ast.If, ast.While)):
                chain = _contains_jnp_call(node.test)
                if chain:
                    kind = "if" if isinstance(node, ast.If) else "while"
                    yield _diag(
                        ctx, node, "R006",
                        f"python `{kind}` on `{chain}(...)` inside "
                        f"jit-reachable `{name}` branches on a tracer; "
                        "use jax.lax.cond / jnp.where (or "
                        "lax.while_loop for loops)")


# ---------------------------------------------------------------------------
# R007 — donated buffers must not be reused after the donating call
# ---------------------------------------------------------------------------

def _stored_names(node: ast.AST) -> set[str]:
    return {n.id for n in ast.walk(node)
            if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Store)}


def _loaded_names(node: ast.AST) -> list[ast.Name]:
    return [n for n in ast.walk(node)
            if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load)]


@rule("R007", "donated-buffer-reuse",
      "An argument at a donate_argnums position is deleted by the "
      "donating call; reading the same name afterwards returns a "
      "deleted-buffer error (or stale data under some backends). "
      "Rebind the result instead.",
      applies=lambda ctx: True)
def check_r007(ctx: LintContext):
    graph = callgraph.build(ctx.tree)
    if not graph.donated:
        return
    bodies: list[list[ast.stmt]] = [ctx.tree.body]
    for node in ast.walk(ctx.tree):
        for field in ("body", "orelse", "finalbody"):
            sub = getattr(node, field, None)
            if isinstance(sub, list) and sub and \
                    isinstance(sub[0], ast.stmt) and sub is not ctx.tree.body:
                bodies.append(sub)
    for body in bodies:
        for i, stmt in enumerate(body):
            donated_here: dict[str, str] = {}      # var -> jitted fn name
            for call in ast.walk(stmt):
                if not isinstance(call, ast.Call) or \
                        not isinstance(call.func, ast.Name):
                    continue
                idxs = graph.donated.get(call.func.id)
                if not idxs:
                    continue
                for idx in idxs:
                    if idx < len(call.args) and \
                            isinstance(call.args[idx], ast.Name):
                        donated_here[call.args[idx].id] = call.func.id
            for var in _stored_names(stmt):
                donated_here.pop(var, None)        # x = f(x): rebound
            if not donated_here:
                continue
            for later in body[i + 1:]:
                if not donated_here:
                    break
                for load in _loaded_names(later):
                    fn_name = donated_here.get(load.id)
                    if fn_name:
                        yield Diagnostic(
                            ctx.path, load.lineno, load.col_offset, "R007",
                            f"`{load.id}` was donated to `{fn_name}` "
                            "(donate_argnums) and its buffer is gone; "
                            "use the call's result, or drop the "
                            "donation")
                for var in _stored_names(later):
                    donated_here.pop(var, None)


# ---------------------------------------------------------------------------
# R008 — -1 sentinel discipline for verdict-bearing arrays
# ---------------------------------------------------------------------------

_SENTINEL_NAMES = ("label", "verdict", "exit_part")


def _sentinel_name(name: str) -> bool:
    low = name.lower()
    return any(s in low for s in _SENTINEL_NAMES)


def _is_zero_fill(value: ast.AST) -> str | None:
    """'' for zeros(), 'full'/'where' when the fill/else value is 0."""
    if not isinstance(value, ast.Call):
        return None
    chain = _attr_chain(value.func)
    base = chain.rsplit(".", 1)[-1]
    if base == "zeros" and chain.split(".")[0] in ("jnp", "np", "numpy"):
        return "zeros"
    if base == "full" and len(value.args) > 1 and \
            isinstance(value.args[1], ast.Constant) and value.args[1].value == 0:
        return "full"
    if base == "where" and len(value.args) == 3 and \
            isinstance(value.args[2], ast.Constant) and value.args[2].value == 0:
        return "where"
    return None


@rule("R008", "sentinel-discipline",
      "Arrays carrying flow verdicts (labels / exit_partition) must "
      "initialise and fall back to the -1 sentinel, never 0 — a 0 "
      "fallback silently claims class 0 at partition 0 "
      "(docs/PARITY.md §2).",
      applies=lambda ctx: ctx.in_tree("src/repro/"))
def check_r008(ctx: LintContext):
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Assign):
            targets = [t.id for t in node.targets
                       if isinstance(t, ast.Name) and _sentinel_name(t.id)]
            kind = _is_zero_fill(node.value)
            if targets and kind:
                yield _diag(
                    ctx, node.value, "R008",
                    f"`{targets[0]}` initialised by `{kind}` to 0 — "
                    "verdict arrays start at the -1 sentinel "
                    "(PARITY.md §2); a 0 default silently claims "
                    "class 0")
        elif isinstance(node, ast.keyword) and node.arg and \
                _sentinel_name(node.arg) and \
                isinstance(node.value, ast.Constant) and node.value.value == 0:
            yield _diag(
                ctx, node.value, "R008",
                f"`{node.arg}=0` — verdict fields use the -1 sentinel "
                "for 'no verdict' (PARITY.md §2)")


# ---------------------------------------------------------------------------
# R009 — no host timers / obs spans inside jit-reachable code
# ---------------------------------------------------------------------------

_R009_TIMERS = {"time.time", "time.perf_counter", "time.perf_counter_ns",
                "time.monotonic", "time.monotonic_ns", "time.process_time",
                "perf_counter", "monotonic"}
_R009_SPANS = {"span", "obs.span", "trace.span", "obs.trace.span",
               "repro.obs.span"}


@rule("R009", "no-host-timing-under-jit",
      "time.time/time.perf_counter and repro.obs span() entries inside "
      "a @jax.jit function (or a helper reachable from one) run ONCE at "
      "trace time, not per call — the 'timing' silently measures "
      "tracing, and the span brackets nothing. Time and annotate at the "
      "dispatch site on the host (docs/OBSERVABILITY.md).",
      applies=lambda ctx: True)
def check_r009(ctx: LintContext):
    graph = callgraph.build(ctx.tree)
    for name in sorted(graph.reachable):
        fn = graph.functions.get(name)
        if fn is None:
            continue
        for node in _walk_own(fn):
            if not isinstance(node, ast.Call):
                continue
            chain = _attr_chain(node.func)
            if chain in _R009_TIMERS:
                yield _diag(
                    ctx, node, "R009",
                    f"`{chain}()` inside jit-reachable `{name}` reads "
                    "the host clock at TRACE time — it times tracing "
                    "once, not execution; hoist the timing to the "
                    "dispatch call site")
            elif chain in _R009_SPANS:
                yield _diag(
                    ctx, node, "R009",
                    f"obs span `{chain}(...)` inside jit-reachable "
                    f"`{name}` brackets trace time, not device "
                    "execution; open the span around the jitted CALL "
                    "instead (jax.named_scope is the in-trace marker)")
