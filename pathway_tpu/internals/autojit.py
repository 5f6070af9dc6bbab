"""Auto-jit execution tier: traceable UDF chains → one vectorized dispatch.

PR 2's shard checker classifies every sync ``pw.udf`` as jit-traceable /
vmappable / host-only and records the class on the expression
(``expr._shard_class``) "for future auto-jit" — this module cashes that in.
When the expression compiler assembles a map program, every output
expression whose tree is built from numeric columns, exact arithmetic and
traceable UDFs is *fused* into a single batched program: one dispatch per
engine batch for the whole chain, instead of one Python call per row per
UDF (the framework-vs-raw throughput tax, round-5 verdict #5).

Execution backends, strongest first:

- ``xla``  — the fused tree under ``jax.jit`` (x64 so Python float/int
  semantics carry over), with batch sizes padded to power-of-two buckets
  so streaming tick sizes never cause per-shape recompiles (the Ragged
  Paged Attention lesson: variable-shape work without a compile zoo).
  Operators hosting an XLA-backed program are marked ``device_bound`` so
  they ride the scheduler's pipelined device leg (engine/device_bridge.py).
- ``numpy`` — the same tree broadcast over numpy arrays. Bit-exact with
  the interpreter by construction (numpy elementwise IEEE ops are the
  same ops CPython uses), still one dispatch per batch.
- ``interp`` — the per-row interpreted path (the fallback fns the
  expression compiler builds anyway). Ground truth.

**Byte-identity with the interpreter is the invariant** — auto-jit may
never change results, only make them faster. Three mechanisms enforce it:

1. *Static exactness gating.* XLA CPU contracts ``a*b+c`` into an FMA
   (measured: 1-ulp divergence; no DebugOptions flag disables it), so any
   tree with compounding float arithmetic — or a UDF body we cannot prove
   free of it — is statically barred from the ``xla`` backend and runs on
   the ``numpy`` backend instead. Division inside UDF bodies likewise
   (XLA int division by zero is UB; Python raises → per-cell ERROR).
2. *Per-batch input guards.* Rows whose cells are not exactly the static
   dtype (Python ``int``/``float``/``bool``; no bigints past ±2^31, no
   ERROR/None) are split out and evaluated on the interpreted path, then
   spliced back — the fast path never sees a value it could mangle.
3. *Verify-then-trust.* A program's first live dispatch on each backend
   is compared cell-for-cell (type and value) against the interpreter; a
   mismatch demotes to the next backend, loudly, once. A UDF that fails
   tracing at execution time (data-dependent control flow the AST pass
   could not see) demotes the same way — ``PATHWAY_AUTO_JIT`` can
   therefore never change a pipeline's output, only its speed.

The tier is on by default; ``PATHWAY_AUTO_JIT=0`` disables it everywhere
(compilation, the PWT110 diagnostic wording, warmup, metrics report it
as disabled).
"""

from __future__ import annotations

import ast
import logging
import os
import threading
import types
import weakref
from typing import Any, Callable

import numpy as np

from pathway_tpu.internals import dtype as dt
from pathway_tpu.internals import expression as ex
from pathway_tpu.internals.error import ERROR

log = logging.getLogger("pathway_tpu.autojit")

# below this many clean rows a batch stays interpreted: array setup beats
# the per-row savings only past a handful of rows (same threshold as the
# compiler's numeric fast paths)
MIN_ROWS = 8
# |int| bound for fast-path cells: products of two guarded ints stay well
# inside int64, so a single multiply can never wrap (deeper int chains are
# bounded by the static op scan — see _body_traits)
INT_GUARD = 1 << 31
_BUCKET_MIN = 8

_ENABLE_VALUES_OFF = ("0", "false", "off", "no")


def autojit_enabled() -> bool:
    """The ``PATHWAY_AUTO_JIT`` escape hatch, honored everywhere (default
    on)."""
    return os.environ.get("PATHWAY_AUTO_JIT", "1").lower() \
        not in _ENABLE_VALUES_OFF


# ---------------------------------------------------------------------------
# tier-wide instrumentation (exported on /metrics + /status, shown by the
# StatsMonitor pipelining panel, reported by bench.py's framework leg)
# ---------------------------------------------------------------------------

_STATS_LOCK = threading.Lock()
_STATS = {
    "programs": 0,            # fused programs built this process
    "compiles": 0,            # XLA bucket compiles (distinct shapes walked)
    "demotions": 0,           # backend demotions (xla→numpy→interp)
    "device_dispatches": 0,   # batches dispatched through the XLA backend
    "vector_dispatches": 0,   # batches dispatched through the numpy backend
    "fallback_batches": 0,    # batches that fell back to the interpreter
}

# live fused programs, for pw.warmup() bucket walking and /status
_REGISTRY: "weakref.WeakSet[FusedProgram]" = weakref.WeakSet()


def _bump(key: str, n: int = 1) -> None:
    with _STATS_LOCK:
        _STATS[key] += n


def autojit_stats() -> dict:
    """Snapshot of the tier's counters plus the live-program backend mix."""
    with _STATS_LOCK:
        snap = dict(_STATS)
    backends: dict[str, int] = {}
    buckets = 0
    for prog in list(_REGISTRY):
        backends[prog.backend] = backends.get(prog.backend, 0) + 1
        buckets += len(prog._buckets)
    snap["enabled"] = autojit_enabled()
    snap["live_programs"] = backends
    snap["bucket_count"] = buckets
    return snap


def reset_stats() -> None:
    """Test hook: zero the counters (the registry drains by gc)."""
    with _STATS_LOCK:
        for k in _STATS:
            _STATS[k] = 0


# ---------------------------------------------------------------------------
# UDF classification + body traits
# ---------------------------------------------------------------------------

def _classification(expr: ex.ApplyExpression):
    """The recorded shard-checker class, computed lazily when the static
    check did not run (same attribute, so the two paths share the cache)."""
    cls = getattr(expr, "_shard_class", None)
    if cls is None:
        from pathway_tpu.internals.static_check.shard_check import classify_udf

        cls = classify_udf(expr._fn)
        expr._shard_class = cls
    return cls


def _body_traits(fn) -> dict:
    """Static scan of a UDF body for exactness hazards the classifier does
    not track: division (XLA int div-by-zero is UB; float differs from
    Python's raise), pow (libm vs XLA approximations), compounding float
    arithmetic (XLA CPU FMA contraction), numpy usage (numpy ufuncs
    reject tracers, so the body is host-vectorizable but not XLA-traceable),
    and truthiness constructs (``and``/``or``/chained comparisons return
    an OPERAND per Python semantics — arrays cannot reproduce that, and
    ``bool(array)`` raises, so they are barred rather than demoted noisily
    at runtime). ``opaque=True`` (no source) assumes every hazard."""
    from pathway_tpu.internals.static_check.shard_check import _function_node

    try:
        node = _function_node(fn)
    except Exception:
        node = None
    if node is None:
        return {"opaque": True, "division": True, "pow": True,
                "arith_ops": 99, "numpy": True, "math": True,
                "math_attrs": set(), "truthy": True, "node": None}
    division = pow_ = False
    arith = 0
    uses_np = uses_math = truthy = False
    math_attrs: set[str] = set()
    for n in ast.walk(node):
        if isinstance(n, (ast.BinOp, ast.AugAssign)):
            op = n.op
            if isinstance(op, (ast.Div, ast.FloorDiv, ast.Mod)):
                division = True
            elif isinstance(op, ast.Pow):
                pow_ = True
            if isinstance(op, (ast.Add, ast.Sub, ast.Mult, ast.Div,
                               ast.FloorDiv, ast.Mod, ast.Pow)):
                arith += 1
        elif isinstance(n, ast.Name) and n.id in ("np", "numpy"):
            uses_np = True
        elif isinstance(n, ast.Name) and n.id == "math":
            uses_math = True
        elif isinstance(n, ast.Attribute) and \
                isinstance(n.value, ast.Name) and n.value.id == "math":
            math_attrs.add(n.attr)
        elif isinstance(n, ast.BoolOp):
            truthy = True
        elif isinstance(n, ast.UnaryOp) and isinstance(n.op, ast.Not):
            truthy = True  # `not arr` calls bool(arr) — raises on arrays
        elif isinstance(n, ast.Compare) and len(n.ops) > 1:
            truthy = True  # a < b < c lowers to `and` on arrays
    return {"opaque": False, "division": division, "pow": pow_,
            "arith_ops": arith, "numpy": uses_np, "math": uses_math,
            "math_attrs": math_attrs, "truthy": truthy, "node": node}


# ---------------------------------------------------------------------------
# int-overflow bit bounds
# ---------------------------------------------------------------------------
# The interpreter computes on Python bigints; the fused path on int64.
# Byte-identity therefore requires a PROOF that no intermediate can leave
# int64 — verify-then-trust only sees the first batch, and a later batch
# overflowing silently (numpy int64 wraps without warning, XLA likewise)
# would be exactly the wrong-but-plausible failure the invariant exists to
# prevent. Bits here bound magnitude: value v has "b bits" iff |v| < 2^b.
# Guarded leaf cells are < 2^31 (INT_GUARD); every arithmetic node
# combines bounds (add/sub: max+1, mult: sum, floordiv/mod: left) and any
# node past 63 bits — or any construct whose bound is unknowable — bars
# the tree from fusing.

_INT_BITS_MAX = 63  # int64 holds |v| < 2^63


class _BitsUnknown(Exception):
    """Raised by the body walker at any construct it cannot bound."""


# float64 represents ints exactly only below 2^53: any int operand past
# that mixed with a float (arith OR comparison) diverges from Python's
# exact int/float semantics once promoted to float64
_FLOAT_EXACT_BITS = 53


def _check_float_mix(lk, lb, rk, rb) -> None:
    """Bar int/float mixing whose int side may exceed float64's exact
    integer range (Python converts/compares exactly; numpy/XLA round)."""
    if lk == "i" and rk == "f" and lb is not None \
            and lb > _FLOAT_EXACT_BITS:
        raise _BitsUnknown(f"int operand up to {lb} bits mixed with float")
    if rk == "i" and lk == "f" and rb is not None \
            and rb > _FLOAT_EXACT_BITS:
        raise _BitsUnknown(f"int operand up to {rb} bits mixed with float")


def _body_int_bits(node, params: dict) -> int | None:
    """Max int bits over every intermediate of a UDF body AST, or None
    when unprovable. ``params`` maps parameter names to the
    ``(kind, bits)`` of the argument tree feeding them."""
    seen_max = 0

    def mark(b: int) -> int:
        nonlocal seen_max
        seen_max = max(seen_max, b)
        if b > _INT_BITS_MAX:
            raise _BitsUnknown(f"intermediate needs {b} bits")
        return b

    def expr(n, env) -> tuple[str, int | None]:
        """(kind, bits): kind i/f/b; bits only for i."""
        if isinstance(n, ast.Constant):
            v = n.value
            if isinstance(v, bool):
                return "b", None
            if isinstance(v, int):
                return "i", mark(max(1, v.bit_length()))
            if isinstance(v, float):
                return "f", None
            raise _BitsUnknown(f"constant {type(v).__name__}")
        if isinstance(n, ast.Name):
            if n.id in env:
                k, b = env[n.id]
                return k, b
            raise _BitsUnknown(f"free name {n.id!r}")
        if isinstance(n, ast.BinOp):
            lk, lb = expr(n.left, env)
            rk, rb = expr(n.right, env)
            op = n.op
            if isinstance(op, ast.Div):
                _check_float_mix(lk, lb, rk, rb)
                return "f", None
            if "f" in (lk, rk):
                if isinstance(op, (ast.Add, ast.Sub, ast.Mult,
                                   ast.FloorDiv, ast.Mod)):
                    _check_float_mix(lk, lb, rk, rb)
                    return "f", None
                raise _BitsUnknown("float op")
            if lk != "i" or rk != "i":
                raise _BitsUnknown("non-numeric operand")
            if isinstance(op, (ast.Add, ast.Sub)):
                return "i", mark(max(lb, rb) + 1)
            if isinstance(op, ast.Mult):
                return "i", mark(lb + rb)
            if isinstance(op, ast.FloorDiv):
                # |a // b| <= |a| for |b| >= 1 (b == 0 raises -> fallback)
                return "i", mark(lb)
            if isinstance(op, ast.Mod):
                # |a % b| < |b| — bounded by the RIGHT operand; the left
                # bound would "prove" (-1 % (y*y)) * x safe at 33 bits
                # when it really needs ~93
                return "i", mark(rb)
            # NO bitwise ops: two's-complement breaks every magnitude
            # bound on negative operands (-1 & v == v, -8 ^ 8 == -16),
            # and a negative shift count raises in Python but is C-UB
            # vectorized — the sign is not tracked here, so none of
            # them can be bounded soundly
            raise _BitsUnknown(type(op).__name__)
        if isinstance(n, ast.UnaryOp):
            if isinstance(n.op, (ast.USub, ast.UAdd)):
                k, b = expr(n.operand, env)
                return k, (mark(b + 1) if k == "i" else b)
            if isinstance(n.op, ast.Not):
                expr(n.operand, env)
                return "b", None
            raise _BitsUnknown("invert")
        if isinstance(n, ast.IfExp):
            expr(n.test, env)
            tk, tb = expr(n.body, env)
            ek, eb = expr(n.orelse, env)
            if tk != ek:
                raise _BitsUnknown("mixed-kind conditional")
            if tk == "i":
                return "i", mark(max(tb, eb))
            return tk, None
        if isinstance(n, ast.Compare):
            lk, lb = expr(n.left, env)
            for c in n.comparators:
                rk, rb = expr(c, env)
                # Python compares int-vs-float EXACTLY; numpy/XLA promote
                # int64 to float64, which rounds past 2^53
                _check_float_mix(lk, lb, rk, rb)
                lk, lb = rk, rb
            return "b", None
        if isinstance(n, ast.Call):
            fname = None
            if isinstance(n.func, ast.Name):
                fname = n.func.id
            elif isinstance(n.func, ast.Attribute) and \
                    isinstance(n.func.value, ast.Name) and \
                    n.func.value.id == "math":
                fname = f"math.{n.func.attr}"
            args = [expr(a, env) for a in n.args]
            if fname == "abs" and args:
                return args[0]
            if fname == "float":
                return "f", None
            if fname == "int":
                # the guarded _pw_int cast raises past 2^62 (per-batch
                # fallback), so its RESULT is bounded even though its
                # float input is not
                return "i", mark(_INT_BITS_MAX - 1)
            if fname in ("math.sqrt", "math.fabs"):
                return "f", None
            raise _BitsUnknown(fname or "call")
        raise _BitsUnknown(type(n).__name__)

    env = dict(params)
    try:
        if isinstance(node, ast.Lambda):
            expr(node.body, env)
            return seen_max
        for stmt in node.body:
            if isinstance(stmt, ast.Return):
                if stmt.value is not None:
                    expr(stmt.value, env)
                return seen_max
            if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                    and isinstance(stmt.targets[0], ast.Name):
                env[stmt.targets[0].id] = expr(stmt.value, env)
            elif isinstance(stmt, ast.AugAssign) and \
                    isinstance(stmt.target, ast.Name):
                synthetic = ast.BinOp(
                    left=ast.Name(id=stmt.target.id, ctx=ast.Load()),
                    op=stmt.op, right=stmt.value)
                env[stmt.target.id] = expr(synthetic, env)
            elif isinstance(stmt, ast.Expr):
                continue  # docstring / bare expression
            else:
                raise _BitsUnknown(type(stmt).__name__)
        return seen_max
    except (_BitsUnknown, RecursionError):
        return None


# ---------------------------------------------------------------------------
# row-wise rewrite (the "vmap" arm): IfExp → where, math.* → exact xp.*
# ---------------------------------------------------------------------------

# math functions whose numpy/XLA counterparts are IEEE-exact matches of
# CPython's (sqrt is correctly rounded everywhere; fabs is a sign op).
# exp/log/sin/... are approximated differently per backend and would break
# byte-identity silently, so they are NOT mapped — bodies using them stay
# interpreted.
_EXACT_MATH = {"sqrt": "sqrt", "fabs": "_pw_fabs"}
_REWRITE_BUILTINS = {"abs", "float", "int"}


class _RowwiseRewriter(ast.NodeTransformer):
    """Rewrites the restricted per-scalar forms the classifier admits as
    "vmappable" into array-safe code over an ``xp`` namespace: scalar
    conditionals become ``_pw_where`` (with a trace-time branch-dtype
    equality check, since ``where`` promotes where Python picks per-row),
    ``math.sqrt``/``math.fabs`` become exact ``xp`` calls, ``float``/
    ``int`` casts become exact dtype casts. Anything else untranslatable
    marks the rewrite failed."""

    def __init__(self):
        self.ok = True
        # int() lowers to the range-guarded _pw_int, whose bounds check
        # cannot trace under jit (and an unguarded trunc-to-int64 of an
        # unbounded float would silently wrap) — numpy backend only
        self.no_xla = False

    def visit_IfExp(self, node):
        node = self.generic_visit(node)
        return ast.copy_location(
            ast.Call(func=ast.Name(id="_pw_where", ctx=ast.Load()),
                     args=[node.test, node.body, node.orelse], keywords=[]),
            node)

    def visit_Call(self, node):
        node = self.generic_visit(node)
        func = node.func
        if isinstance(func, ast.Attribute) and \
                isinstance(func.value, ast.Name) and func.value.id == "math":
            target = _EXACT_MATH.get(func.attr)
            if target is None:
                self.ok = False
                return node
            if target.startswith("_pw"):
                name = ast.Name(id=target, ctx=ast.Load())
            else:
                name = ast.Attribute(
                    value=ast.Name(id="xp", ctx=ast.Load()),
                    attr=target, ctx=ast.Load())
            return ast.copy_location(
                ast.Call(func=name, args=node.args, keywords=node.keywords),
                node)
        if isinstance(func, ast.Name):
            if func.id == "float":
                return ast.copy_location(
                    ast.Call(func=ast.Name(id="_pw_float", ctx=ast.Load()),
                             args=node.args, keywords=node.keywords), node)
            if func.id == "int":
                self.no_xla = True
                return ast.copy_location(
                    ast.Call(func=ast.Name(id="_pw_int", ctx=ast.Load()),
                             args=node.args, keywords=node.keywords), node)
            if func.id == "abs":
                return node  # __abs__ works on arrays and tracers alike
            self.ok = False
        return node


def body_fusable(fn) -> bool:
    """Cheap static screen for the DIAGNOSTICS (PWT110 wording): False
    when the body carries a hazard the tier will definitely refuse —
    opaque source, truthiness over operands, ``math.*`` without an
    IEEE-exact vector counterpart, ``pow``. The compiler applies the
    stricter dtype/int-overflow gates on top, so True means "expected to
    fuse", never a guarantee — the wording stays hedged accordingly."""
    try:
        traits = _body_traits(fn)
    except Exception:
        return False
    if traits["opaque"] or traits["truthy"] or traits["pow"]:
        return False
    if traits["math_attrs"] - set(_EXACT_MATH):
        return False
    return True


def _rewrite_namespace(xp) -> dict:
    """The helper namespace rewritten bodies run in. ``_pw_where`` rejects
    mixed-dtype branches at trace/broadcast time (Python's conditional is
    type-preserving per row; ``where`` would promote) — the rejection
    surfaces as a demotion, never a wrong value."""

    def _pw_where(c, a, b):
        aa, bb = xp.asarray(a), xp.asarray(b)
        if aa.dtype != bb.dtype:
            raise TypeError(
                "auto-jit: conditional branches have different dtypes "
                f"({aa.dtype} vs {bb.dtype}) — per-row type preservation "
                "cannot be vectorized")
        return xp.where(c, aa, bb)

    def _pw_float(x):
        return xp.asarray(x).astype(xp.float64)

    def _pw_int(x):
        # Python's int(float) is exact at any magnitude; int64 is not.
        # Out-of-range (or non-finite) inputs raise FloatingPointError so
        # the dispatcher falls back to the interpreter for THIS batch
        # without demoting the tier — same contract as a zero divisor.
        arr = xp.asarray(x)
        if bool(np.any(~np.isfinite(arr) | (np.abs(arr) >= float(1 << 62)))):
            raise FloatingPointError(
                "auto-jit: int() cast outside int64-exact range")
        return xp.trunc(arr).astype(xp.int64)

    def _pw_fabs(x):
        return xp.abs(xp.asarray(x).astype(xp.float64))

    return {"xp": xp, "_pw_where": _pw_where, "_pw_float": _pw_float,
            "_pw_int": _pw_int, "_pw_fabs": _pw_fabs}


def _rewrite_rowwise(fn) -> tuple[Callable[[Any], Callable], bool] | None:
    """``(build(xp) -> batched fn, no_xla)`` for a vmappable body, or
    None. The rewritten function is elementwise, so broadcasting the
    arrays through it IS the vmap of the scalar original (the admitted
    forms are straight-line scalar code — no shape-dependent behavior to
    diverge)."""
    from pathway_tpu.internals.static_check.shard_check import _function_node

    code = getattr(fn, "__code__", None)
    if code is None:
        return None
    closure_modules: dict[str, Any] = {}
    if code.co_freevars:
        # closure cells do not survive re-compilation, and freezing a
        # mutable cell would silently diverge from the live interpreter
        # path — EXCEPT cells holding module objects (a UDF defined
        # inside a function whose enclosing scope did `import math`):
        # modules are process singletons, so binding them is exact
        import types

        for name, cell in zip(code.co_freevars, fn.__closure__ or ()):
            try:
                val = cell.cell_contents
            except ValueError:  # empty cell
                return None
            if not isinstance(val, types.ModuleType):
                return None
            closure_modules[name] = val
    node = _function_node(fn)
    if node is None:
        return None
    rewriter = _RowwiseRewriter()
    if isinstance(node, ast.Lambda):
        new = rewriter.visit(
            ast.Expression(body=ast.Lambda(args=node.args, body=node.body)))
        if not rewriter.ok:
            return None
        mode, tree = "eval", new
    else:
        fndef = ast.FunctionDef(
            name=node.name, args=node.args, body=node.body,
            decorator_list=[], returns=None, type_params=[])
        new = rewriter.visit(ast.Module(body=[fndef], type_ignores=[]))
        if not rewriter.ok:
            return None
        mode, tree = "exec", new
    ast.fix_missing_locations(tree)
    try:
        compiled = compile(tree, f"<autojit:{code.co_filename}>", mode)
    except (SyntaxError, TypeError, ValueError):
        return None
    fn_globals = getattr(fn, "__globals__", {})

    def build(xp):
        ns = dict(fn_globals)
        ns.update(closure_modules)
        ns.update(_rewrite_namespace(xp))
        if mode == "eval":
            return eval(compiled, ns)  # noqa: S307 — our own rewritten AST
        exec(compiled, ns)  # noqa: S102
        return ns[node.name]

    return build, rewriter.no_xla


# ---------------------------------------------------------------------------
# expression-tree emitter
# ---------------------------------------------------------------------------

_KIND_BY_DTYPE = None  # {dtype: numpy kind char}, populated lazily


def _leaf_kind(dtype) -> str | None:
    global _KIND_BY_DTYPE
    if _KIND_BY_DTYPE is None:
        _KIND_BY_DTYPE = {dt.INT: "i", dt.FLOAT: "f", dt.BOOL: "b"}
    return _KIND_BY_DTYPE.get(dt.unoptionalize(dtype))


_NP_DTYPE = {"i": np.int64, "f": np.float64, "b": np.bool_}

# expression-level binary ops with IEEE-exact vector semantics. The
# division family is deliberately absent: a zero divisor raises in Python
# (→ per-cell ERROR) but yields inf/0 vectorized, and the interpreter's
# numeric fast path already owns those guards.
_BIN_ARITH = {"+", "-", "*"}
_BIN_CMP = {"<", "<=", ">", ">=", "==", "!="}


class _Tree:
    """One emitted output expression: ``build(xp) -> f(env) -> array`` over
    the group's leaf environment, plus the exactness metadata the backend
    gate needs."""

    __slots__ = ("build", "kind", "fdepth", "xla_ok", "has_udf", "labels",
                 "ibits")

    def __init__(self, build, kind, fdepth=0, xla_ok=True, has_udf=False,
                 labels=(), ibits=None):
        self.build = build
        self.kind = kind          # result numpy kind: i / f / b
        self.fdepth = fdepth      # chained float-arith depth (FMA risk at 2)
        self.xla_ok = xla_ok
        self.has_udf = has_udf
        self.labels = tuple(labels)
        # int-magnitude bound: |value| < 2^ibits, proven statically (None
        # for f/b results). The guard that keeps int64 from wrapping where
        # the interpreter would have promoted to bigint.
        self.ibits = ibits if kind == "i" else None


class _LeafMap:
    """Assigns stable env slots to column references (deduped by column)."""

    def __init__(self, ctx):
        self.ctx = ctx
        self.slots: dict[tuple[int, str], int] = {}
        self.refs: list[ex.ColumnReference] = []
        self.kinds: list[str] = []

    def slot(self, ref: ex.ColumnReference, kind: str) -> int:
        key = (id(ref.table), ref.name)
        pos = self.slots.get(key)
        if pos is None:
            pos = len(self.refs)
            self.slots[key] = pos
            self.refs.append(ref)
            self.kinds.append(kind)
        return pos

    def positions(self) -> list[int]:
        return [self.ctx.position(r) for r in self.refs]


def _emit(expr, leaves: _LeafMap) -> _Tree | None:
    """Recursive tree build; None marks the subtree non-fusable."""
    from pathway_tpu.internals.type_inference import infer_dtype

    if isinstance(expr, ex.IdExpression):
        return None
    if type(expr) is ex.ColumnReference:
        try:
            kind = _leaf_kind(infer_dtype(expr))
        except Exception:
            return None
        if kind is None:
            return None
        pos = leaves.slot(expr, kind)
        return _Tree(lambda xp, _p=pos: (lambda env: env[_p]), kind,
                     ibits=31)  # cells guarded to |v| < 2^31 at dispatch
    if isinstance(expr, ex.ConstExpression):
        v = expr._value
        tv = type(v)
        if tv is bool:
            kind = "b"
        elif tv is int:
            if not (-INT_GUARD < v < INT_GUARD):
                return None
            kind = "i"
        elif tv is float:
            kind = "f"
        else:
            return None
        return _Tree(lambda xp, _v=v: (lambda env: _v), kind,
                     ibits=max(1, v.bit_length()) if kind == "i" else None)
    if isinstance(expr, ex.UnaryExpression) and expr._op == "-":
        arg = _emit(expr._arg, leaves)
        if arg is None or arg.kind not in "if":
            return None
        return _Tree(
            # true negation, NOT `0 - x`: subtraction-from-zero turns
            # -0.0 into +0.0 where Python's unary minus keeps the sign
            lambda xp, _a=arg.build: (
                lambda env, _f=_a(xp): -_f(env)),
            arg.kind, arg.fdepth, arg.xla_ok, arg.has_udf, arg.labels,
            ibits=arg.ibits)
    if isinstance(expr, ex.BinaryExpression):
        op = expr._op
        if op not in _BIN_ARITH and op not in _BIN_CMP:
            return None
        lt = _emit(expr._left, leaves)
        rt = _emit(expr._right, leaves)
        if lt is None or rt is None:
            return None
        if lt.kind not in "if" or rt.kind not in "if":
            return None
        import operator

        py_op = {"+": operator.add, "-": operator.sub, "*": operator.mul,
                 "<": operator.lt, "<=": operator.le, ">": operator.gt,
                 ">=": operator.ge, "==": operator.eq,
                 "!=": operator.ne}[op]

        def build(xp, _l=lt.build, _r=rt.build, _o=py_op):
            lf, rf = _l(xp), _r(xp)
            return lambda env: _o(lf(env), rf(env))

        xla_ok = lt.xla_ok and rt.xla_ok
        ibits = None
        if {lt.kind, rt.kind} == {"i", "f"}:
            # int/float mixing (arith or comparison): Python converts and
            # compares EXACTLY; float64 promotion rounds past 2^53
            int_side = lt if lt.kind == "i" else rt
            if int_side.ibits is None or int_side.ibits > _FLOAT_EXACT_BITS:
                return None
        if op in _BIN_ARITH:
            kind = "f" if "f" in (lt.kind, rt.kind) else "i"
            fdepth = (max(lt.fdepth, rt.fdepth) + 1) if kind == "f" else 0
            if fdepth >= 2:
                xla_ok = False  # XLA CPU FMA contraction (1-ulp divergence)
            if kind == "i":
                ibits = (lt.ibits + rt.ibits if op == "*"
                         else max(lt.ibits, rt.ibits) + 1)
                if ibits > _INT_BITS_MAX:
                    return None  # could leave int64 where Python promotes
        else:
            kind, fdepth = "b", 0
        return _Tree(build, kind, fdepth, xla_ok,
                     lt.has_udf or rt.has_udf, lt.labels + rt.labels,
                     ibits=ibits)
    if isinstance(expr, ex.IfElseExpression):
        ct = _emit(expr._if, leaves)
        tt = _emit(expr._then, leaves)
        et = _emit(expr._else, leaves)
        if ct is None or tt is None or et is None or ct.kind != "b" \
                or tt.kind != et.kind or tt.kind not in "if":
            return None

        def build(xp, _c=ct.build, _t=tt.build, _e=et.build):
            cf, tf, ef = _c(xp), _t(xp), _e(xp)
            return lambda env: xp.where(cf(env), tf(env), ef(env))

        return _Tree(build, tt.kind, max(tt.fdepth, et.fdepth),
                     ct.xla_ok and tt.xla_ok and et.xla_ok,
                     ct.has_udf or tt.has_udf or et.has_udf,
                     ct.labels + tt.labels + et.labels,
                     ibits=(max(tt.ibits, et.ibits)
                            if tt.kind == "i" else None))
    if type(expr) is ex.ApplyExpression:  # excludes the async subclasses
        return _emit_apply(expr, leaves)
    return None


def _globals_fusable(fn, node) -> bool:
    """True iff every name the body loads resolves to a parameter, a
    local assignment, a builtin, or a MODULE global. Non-module globals
    (a tunable ``SCALE = 2.0``) are refused: the fused program would
    freeze them (globals-dict copy for rewritten bodies, trace-time
    baking under jit) while the interpreter fallback reads them live —
    and the classifier admits such bodies as traceable, so without this
    gate a mid-run mutation silently diverges. Modules are process
    singletons; attribute lookups on them stay live in the rewritten
    namespace."""
    if node is None:
        return False
    import builtins
    import types

    bound: set[str] = set()
    arg_obj = node.args
    for a in (list(arg_obj.posonlyargs) + list(arg_obj.args)
              + list(arg_obj.kwonlyargs)):
        bound.add(a.arg)
    for v in (arg_obj.vararg, arg_obj.kwarg):
        if v is not None:
            bound.add(v.arg)
    # only the BODY executes per call — decorators (`@pw.udf`) and
    # annotations resolve at def time, and a decorator name imported in
    # an enclosing function scope is invisible to fn.__globals__ without
    # being a runtime read at all
    body = node.body if isinstance(node.body, list) else [node.body]
    body_nodes = [x for stmt in body for x in ast.walk(stmt)]
    for n in body_nodes:
        if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Store):
            bound.add(n.id)
    fn_globals = getattr(fn, "__globals__", {}) or {}
    closure_names = set(getattr(fn, "__code__", None).co_freevars
                        if getattr(fn, "__code__", None) else ())
    for n in body_nodes:
        if not (isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load)):
            continue
        name = n.id
        if name in bound:
            continue
        if name in closure_names:
            continue  # module-only, enforced by _rewrite_rowwise / below
        if name in fn_globals:
            if not isinstance(fn_globals[name], types.ModuleType):
                return False
        elif not hasattr(builtins, name):
            return False
    if closure_names:
        # non-rewrite path: closure cells must also be module-valued
        for cell in getattr(fn, "__closure__", None) or ():
            try:
                if not isinstance(cell.cell_contents, types.ModuleType):
                    return False
            except ValueError:
                return False
    return True


def _emit_apply(expr: ex.ApplyExpression, leaves: _LeafMap) -> _Tree | None:
    from pathway_tpu.internals.type_inference import infer_dtype

    if getattr(expr, "_batch", False):
        return None  # batch UDFs already amortize dispatch (PR 4 path)
    cls = _classification(expr)
    if not cls.jit_eligible:
        return None
    args = [_emit(a, leaves) for a in expr._args]
    kwargs = {k: _emit(v, leaves) for k, v in expr._kwargs.items()}
    if any(a is None for a in args) or any(v is None for v in kwargs.values()):
        return None
    try:
        ret_kind = _leaf_kind(infer_dtype(expr))
    except Exception:
        ret_kind = None
    if ret_kind is None:
        # no declared return type (plain pw.apply): predict from the arg
        # kinds — arithmetic preserves kind, and a misprediction only
        # tightens a parent's gating or trips the dtype checks/verify,
        # never a silent wrong value
        arg_kinds = [t.kind for t in args] + [t.kind for t in
                                              kwargs.values()]
        if not arg_kinds:
            return None
        ret_kind = "f" if "f" in arg_kinds else (
            "i" if "i" in arg_kinds else "b")
    fn = expr._fn
    traits = _body_traits(fn)
    if traits["truthy"]:
        # and/or/chained-compare return an OPERAND per Python truthiness;
        # arrays cannot reproduce that (bool(array) raises) — interpreted
        return None
    if traits["pow"] and ret_kind == "i":
        return None  # int ** int grows past int64 unboundedly
    needs_rewrite = cls.kind == "vmappable" or (
        not traits["opaque"] and traits["math"])
    rewrite_no_xla = False
    if needs_rewrite:
        rewritten = _rewrite_rowwise(fn)
        if rewritten is None:
            return None
        body_build, rewrite_no_xla = rewritten
    else:
        def body_build(xp, _fn=fn):
            return _fn
    if not traits["opaque"] and not _globals_fusable(fn, traits["node"]):
        # the body reads a module-level name that is NOT a module: the
        # fused program would snapshot/bake its value while the
        # interpreter fallback reads it live — a mid-run mutation would
        # split a batch between stale and live values, and the
        # DeterministicMapOperator replay cache this fusion elides exists
        # precisely for such unverified-deterministic bodies
        return None
    # int-overflow proof (see _body_int_bits): the interpreter promotes to
    # bigint, int64 wraps — any int-involved body must bound every
    # intermediate within int64 or stay interpreted. An int() cast
    # ANYWHERE in the body forces the proof too: a predicted-float return
    # kind would otherwise skip it while _pw_int mints int64 values up to
    # 2^62 whose products wrap silently
    body_has_int_cast = traits["node"] is not None and any(
        isinstance(c, ast.Call) and isinstance(c.func, ast.Name)
        and c.func.id == "int" for c in ast.walk(traits["node"]))
    int_involved = ret_kind == "i" or body_has_int_cast or any(
        t.kind == "i" for t in args) or any(
        t.kind == "i" for t in kwargs.values())
    ibits = None
    if int_involved:
        node = traits["node"]
        if node is None:
            return None  # opaque body: unprovable
        arg_objs = list(node.args.posonlyargs) + list(node.args.args)
        if node.args.vararg or node.args.kwarg or len(arg_objs) < len(args):
            return None
        params = {a.arg: (t.kind, t.ibits)
                  for a, t in zip(arg_objs, args)}
        for k, t in kwargs.items():
            params[k] = (t.kind, t.ibits)
        ibits = _body_int_bits(node, params)
        if ibits is None:
            return None
        ibits = max(ibits, 1)
    # backend exactness gate for the body (see module doc): division/pow/
    # compounding-float-arith/numpy/math-use bar the XLA backend
    float_involved = ret_kind == "f" or any(
        t.kind == "f" for t in args) or any(
        t.kind == "f" for t in kwargs.values())
    xla_ok = not traits["division"] and not traits["pow"] \
        and not traits["numpy"] and not traits["math"] \
        and not rewrite_no_xla \
        and not (float_involved and traits["arith_ops"] >= 2)
    xla_ok = xla_ok and all(t.xla_ok for t in args) and all(
        t.xla_ok for t in kwargs.values())
    name = getattr(fn, "__name__", "<udf>")

    def build(xp, _args=tuple(args), _kwargs=dict(kwargs), _bb=body_build):
        f = _bb(xp)
        arg_fns = [t.build(xp) for t in _args]
        kw_fns = {k: t.build(xp) for k, t in _kwargs.items()}

        def run(env):
            return f(*[g(env) for g in arg_fns],
                     **{k: g(env) for k, g in kw_fns.items()})

        return run

    labels = (name,) + tuple(
        x for t in args for x in t.labels) + tuple(
        x for t in kwargs.values() for x in t.labels)
    return _Tree(build, ret_kind,
                 2 if (float_involved and traits["arith_ops"]) else 0,
                 xla_ok, True, labels,
                 ibits=ibits if ret_kind == "i" else None)


# ---------------------------------------------------------------------------
# the fused program
# ---------------------------------------------------------------------------

def _bucket(n: int) -> int:
    return max(_BUCKET_MIN, 1 << (n - 1).bit_length())


def _cells_equal(a, b) -> bool:
    if a is b:
        return True
    if type(a) is not type(b):
        return False
    try:
        if a == b:
            # == calls -0.0 equal to 0.0; byte-identity does not
            if type(a) is float and a == 0.0:
                import math as _math

                return _math.copysign(1.0, a) == _math.copysign(1.0, b)
            return True
        return a != a and b != b  # NaN == NaN for identity purposes
    except Exception:
        return False


def _api_moved(e: BaseException) -> bool:
    """An ImportError, or an AttributeError on a module: a library API
    moved under us. That is a bug to surface, never a reason to take a
    slower tier — outputs stay byte-identical there, so nobody would see
    it (``jax.experimental.enable_x64`` went exactly this way)."""
    return isinstance(e, ImportError) or (
        isinstance(e, AttributeError)
        and isinstance(getattr(e, "obj", None), types.ModuleType))


class FusedProgram:
    """One map program's fused output expressions (see module doc).

    ``dispatch(keys, rows, fallback_fns)`` returns the fused columns (in
    ``expr_idx`` order) or None when the whole batch must stay on the
    interpreted path. Rows whose cells fail the input guards are evaluated
    through ``fallback_fns`` (the interpreter) and spliced back, so a
    partially-dirty batch still vectorizes its clean majority.

    One program holds ALL the fusable expressions of a map — the leaf
    columns are extracted and guard-validated ONCE per batch, shared by
    both execution partitions: trees the exactness gate admits to XLA run
    under one ``jax.jit`` (one device dispatch per batch, the ``xla``
    partition), trees it bars (compounding float arithmetic, division
    bodies — see module doc) run broadcast over the same arrays on the
    ``numpy`` partition. A demotion collapses xla → numpy → interp for
    the whole program, loudly-once.
    """

    def __init__(self, expr_idx: list[int], trees: list[_Tree],
                 leaves: _LeafMap, label: str):
        self.expr_idx = list(expr_idx)
        self.leaf_pos = leaves.positions()
        self.leaf_kinds = list(leaves.kinds)
        self.label = label
        self._xla_part = [i for i, t in enumerate(trees) if t.xla_ok]
        self._np_part = [i for i, t in enumerate(trees) if not t.xla_ok]
        self.xla_ok = bool(self._xla_part)
        self._np_fn = self._build(np, trees)
        self._np_sub_fn = (self._build(np, [trees[i] for i in self._np_part])
                           if self._np_part else None)
        self._jit = None
        self._buckets: set[int] = set()
        self.backend = "numpy"
        self.verified = False
        self.dispatches = 0
        if self.xla_ok and autojit_enabled():
            self._arm_xla([trees[i] for i in self._xla_part])
        _REGISTRY.add(self)
        _bump("programs")

    @staticmethod
    def _build(xp, trees):
        fns = [t.build(xp) for t in trees]

        def fused(*arrays):
            return tuple(f(arrays) for f in fns)

        return fused

    def _arm_xla(self, xla_trees) -> None:
        """Probe the XLA partition under an abstract x64 trace; arm the
        jit only when the probe passes AND every output lands on a 64-bit
        dtype (a body casting to float32 would change cell values). Only
        a body that does not trace demotes. An ImportError, or an
        AttributeError on a module, is an API that moved under us: it
        raises instead of hiding behind the numpy tier."""
        import jax

        fused = self._build(jax.numpy, xla_trees)
        specs = [jax.ShapeDtypeStruct((_BUCKET_MIN,), _NP_DTYPE[k])
                 for k in self.leaf_kinds]
        try:
            with jax.enable_x64(True):
                out = jax.eval_shape(fused, *specs)
            if any(np.dtype(o.dtype) not in
                   (np.dtype(np.int64), np.dtype(np.float64),
                    np.dtype(np.bool_)) for o in out):
                raise TypeError(
                    f"non-64-bit output dtypes {[o.dtype for o in out]}")
        except Exception as e:
            if _api_moved(e):
                raise
            self._demote("numpy", f"XLA trace probe failed: {e!r}")
            return
        self._jit = jax.jit(fused)
        self.backend = "xla"

    # ------------------------------------------------------------------
    def _demote(self, to: str, reason: str) -> None:
        log.warning(
            "auto-jit: program %s demoted %s -> %s: %s (results are "
            "unaffected — the slower tier takes over)",
            self.label, self.backend, to, reason)
        self.backend = to
        self.verified = False
        self._jit = None if to != "xla" else self._jit
        _bump("demotions")

    # ------------------------------------------------------------------
    @staticmethod
    def _clean_col(col: list, k: str):
        """Typed array for an all-clean column, else None. The common case
        (homogeneous, in-range cells) validates at C speed — set(map(type))
        and ndarray reductions — with no per-row Python loop."""
        types = set(map(type, col))
        if k == "i":
            if types != {int}:
                return None
            try:
                arr = np.asarray(col, np.int64)
            except OverflowError:  # a bigint cell slipped past int64
                return None
            # min/max, not abs: np.abs(-2**63) wraps to itself (negative)
            # and would sneak the worst possible cell past the guard
            if int(arr.max(initial=0)) >= INT_GUARD \
                    or int(arr.min(initial=0)) <= -INT_GUARD:
                return None
            return arr
        if k == "f":
            return np.asarray(col, np.float64) if types == {float} else None
        return np.asarray(col, np.bool_) if types == {bool} else None

    def _split_rows(self, rows):
        """(live_idx, dead_idx, arrays): live_idx None means every row is
        clean (fast path, no index lists materialized); arrays is None
        when too few rows survive the cell guards."""
        cols = [[r[p] for r in rows] for p in self.leaf_pos]
        n = len(rows)
        kinds = self.leaf_kinds
        arrays = []
        for col, k in zip(cols, kinds):
            arr = self._clean_col(col, k)
            if arr is None:
                break
            arrays.append(arr)
        else:
            return None, (), arrays
        # a dirty column: per-row scan splits the batch so the clean
        # majority still vectorizes
        live: list[int] = []
        dead: list[int] = []
        for i in range(n):
            ok = True
            for col, k in zip(cols, kinds):
                v = col[i]
                tv = type(v)
                if k == "f":
                    if tv is not float:
                        ok = False
                        break
                elif k == "i":
                    if tv is not int or not (-INT_GUARD < v < INT_GUARD):
                        ok = False
                        break
                elif tv is not bool:
                    ok = False
                    break
            (live if ok else dead).append(i)
        if len(live) < MIN_ROWS:
            return live, dead, None
        try:
            arrays = [np.asarray([c[i] for i in live], _NP_DTYPE[k])
                      for c, k in zip(cols, kinds)]
        except Exception:
            return live, dead, None
        return live, dead, arrays

    def _run_backend(self, arrays, n_live: int, warm: bool = False):
        """Raw fused outputs as numpy arrays of length ``n_live``, in tree
        order. On the ``xla`` backend the two partitions share the SAME
        guarded arrays: one jitted device dispatch for the xla trees, one
        broadcast pass for the numpy-only trees."""
        if self.backend == "xla":
            import jax

            b = _bucket(n_live)
            padded = arrays
            if b != n_live:
                padded = [np.pad(a, (0, b - n_live), mode="edge")
                          for a in arrays]
            if b not in self._buckets:
                self._buckets.add(b)
                _bump("compiles")
            with jax.enable_x64(True):
                xla_outs = self._jit(*padded)
            if not warm:
                _bump("device_dispatches")
            merged: list = [None] * (len(self._xla_part)
                                     + len(self._np_part))
            for i, o in zip(self._xla_part, xla_outs):
                merged[i] = (np.asarray(o)[:n_live] if getattr(o, "ndim", 0)
                             else np.full(n_live, np.asarray(o)[()]))
            if self._np_sub_fn is not None:
                with np.errstate(divide="raise", over="raise",
                                 invalid="raise"):
                    np_outs = self._np_sub_fn(*arrays)
                if not warm:
                    _bump("vector_dispatches")
                for i, o in zip(self._np_part, np_outs):
                    merged[i] = (np.asarray(o) if getattr(o, "ndim", 0)
                                 else np.full(n_live, o))
            return merged
        with np.errstate(divide="raise", over="raise", invalid="raise"):
            outs = self._np_fn(*arrays)
        if not warm:
            _bump("vector_dispatches")
        return [np.asarray(o) if getattr(o, "ndim", 0)
                else np.full(n_live, o) for o in outs]

    def dispatch(self, keys, rows, fallback_fns):
        if self.backend == "interp" or not autojit_enabled():
            return None
        n = len(keys)
        if n < MIN_ROWS:
            return None
        live, dead, arrays = self._split_rows(rows)
        if arrays is None:
            _bump("fallback_batches")
            return None
        n_live = n if live is None else len(live)
        try:
            outs = self._run_backend(arrays, n_live)
            out_cols = [o.tolist() for o in outs]
        except FloatingPointError:
            # data-dependent (zero divisor / overflow in THIS batch):
            # interpret the batch, keep the tier armed
            _bump("fallback_batches")
            return None
        except Exception as e:
            # the runtime safety net: tracing/execution failed on real
            # data — demote loudly-once, results come from the fallback
            self._demote("numpy" if self.backend == "xla" else "interp",
                         f"dispatch failed: {e!r}")
            _bump("fallback_batches")
            return None
        if not self.verified:
            # verify-then-trust: the first live dispatch on each backend
            # is checked cell-for-cell against the interpreter
            if live is None:
                live_keys, live_rows = keys, rows
            else:
                live_keys = [keys[i] for i in live]
                live_rows = [rows[i] for i in live]
            expected = [fb(live_keys, live_rows) for fb in fallback_fns]
            for got_col, want_col in zip(out_cols, expected):
                for g, w in zip(got_col, want_col):
                    if not _cells_equal(g, w):
                        self._demote(
                            "numpy" if self.backend == "xla" else "interp",
                            f"first-batch verify mismatch: {g!r} != {w!r}")
                        _bump("fallback_batches")
                        return None
            self.verified = True
        self.dispatches += 1
        if not dead:
            return out_cols
        dead_keys = [keys[i] for i in dead]
        dead_rows = [rows[i] for i in dead]
        spliced = []
        for col, fb in zip(out_cols, fallback_fns):
            full: list = [None] * n
            fb_col = fb(dead_keys, dead_rows)
            for j, i in enumerate(live):
                full[i] = col[j]
            for j, i in enumerate(dead):
                full[i] = fb_col[j]
            spliced.append(full)
        return spliced

    # ------------------------------------------------------------------
    def warm(self, max_bucket: int | None = None) -> list[tuple]:
        """Walk the power-of-two buckets so no first-tick compile lands in
        serving latency (pw.warmup). Only the XLA backend compiles."""
        if self.backend != "xla" or self._jit is None:
            return []
        if max_bucket is None:
            try:
                max_bucket = int(os.environ.get(
                    "PATHWAY_AUTO_JIT_WARM_MAX", str(2048)))
            except ValueError:
                max_bucket = 2048
        out = []
        b = _BUCKET_MIN
        while b <= max_bucket:
            arrays = [np.ones(b, _NP_DTYPE[k]) for k in self.leaf_kinds]
            try:
                self._run_backend(arrays, b, warm=True)
            except FloatingPointError:
                pass  # data-dependent (ones hit a guard) — bucket compiled
            except Exception as e:
                self._demote("numpy", f"warmup dispatch failed: {e!r}")
                return out
            out.append(("autojit", (self.label, b)))
            b <<= 1
        return out


# ---------------------------------------------------------------------------
# compiler entry points
# ---------------------------------------------------------------------------

def fuse_program(exprs: list, ctx) -> list[FusedProgram]:
    """Fuse the traceable-UDF output expressions of one map program into
    ONE batched dispatch. Returns [] when the tier is off or nothing
    qualifies (a program with no eligible UDF keeps the interpreter's
    per-expression numeric fast paths — they already vectorize plain
    arithmetic).

    All fusable trees share one program — leaf extraction and the input
    guard run once per batch — with XLA-exact trees and numpy-only trees
    (compounding float arithmetic, division-bearing bodies — see the
    module doc) split into internal PARTITIONS, so one float chain cannot
    drag the whole program off the device tier."""
    if not autojit_enabled():
        return []
    leaves = _LeafMap(ctx)
    idx: list[int] = []
    trees: list[_Tree] = []
    for i, e in enumerate(exprs):
        if not isinstance(e, ex.ColumnExpression):
            continue
        try:
            t = _emit(e, leaves)
        except Exception:
            t = None
        if t is not None and t.has_udf:
            idx.append(i)
            trees.append(t)
    if not idx:
        return []
    # re-emit over a fresh leaf map so only the FUSED trees' columns are
    # extracted at dispatch (the probe map may have collected leaves of
    # trees that did not qualify)
    final = _LeafMap(ctx)
    trees = [_emit(exprs[i], final) for i in idx]
    if any(t is None for t in trees) or not final.refs:
        return []
    label = "+".join(sorted({x for t in trees for x in t.labels})
                     or {"<expr>"})
    try:
        return [FusedProgram(idx, trees, final, label)]
    except Exception as e:
        # a body that will not build must not break compilation of the
        # user's program — but a moved API is ours to hear about
        if _api_moved(e):
            raise
        log.warning("auto-jit: fusing %s failed at build (%r) — "
                    "interpreted path keeps the program", label, e)
        return []


def discard_programs(programs) -> None:
    """Back out FusedPrograms built by a lowering path that then bailed
    (runner._lower_map_split): drop them from the warmup registry and the
    ``programs`` counter so /metrics counts only programs that can ever
    dispatch."""
    for prog in programs or ():
        _REGISTRY.discard(prog)
        _bump("programs", -1)


def _contains_host_udf(expr) -> bool:
    stack = [expr]
    while stack:
        e = stack.pop()
        if type(e) is ex.ApplyExpression and not getattr(e, "_batch", False):
            if _classification(e).kind == "host":
                return True
        stack.extend(getattr(e, "_deps", ()))
    return False


def split_map_exprs(exprs: list) -> tuple[list[int], list[int]] | None:
    """WindVE-style host/device split for a map program: when a select
    carries BOTH fusable-UDF expressions and host-only-UDF expressions,
    return (device_idx, host_idx) so the lowering can split them into two
    operators — the device part rides the pipelined bridge leg while the
    host part steps on the host thread, overlapping host-only UDF time
    with device time instead of serializing it. None = keep one operator.
    """
    if not autojit_enabled():
        return None
    leaves = _LeafMap(_NullCtx())
    device_idx: list[int] = []
    host_idx: list[int] = []
    host_udf_seen = False
    for i, e in enumerate(exprs):
        t = None
        if isinstance(e, ex.ColumnExpression):
            try:
                t = _emit(e, leaves)
            except Exception:
                t = None
        if t is not None and t.has_udf:
            device_idx.append(i)
        else:
            host_idx.append(i)
            if isinstance(e, ex.ColumnExpression) and _contains_host_udf(e):
                host_udf_seen = True
    if not device_idx or not host_idx or not host_udf_seen:
        return None
    return device_idx, host_idx


class _NullCtx:
    """Position-free stand-in so split_map_exprs can emit without a
    compile context (positions are only needed at dispatch time)."""

    def position(self, ref):  # pragma: no cover — never dispatched
        return 0


# ---------------------------------------------------------------------------
# warmup hook
# ---------------------------------------------------------------------------

def warm_registered(max_bucket: int | None = None) -> list[tuple]:
    """Walk every live fused program's bucket ladder (pw.warmup)."""
    if not autojit_enabled():
        return []
    out: list[tuple] = []
    for prog in list(_REGISTRY):
        out.extend(prog.warm(max_bucket))
    return out
