"""Auto-jit execution tier (internals/autojit.py).

Contracts under test:

- **byte-identity**: PATHWAY_AUTO_JIT=1 and =0 produce identical captured
  streams across int/float/bool/str/None-able columns, including dirty
  cells (None, bigints past the guard, ERROR-producing rows) and
  data-dependent per-cell errors (negative sqrt, zero divisors);
- **fused-chain vs per-expr equivalence**: a chained composition fuses
  into ONE program with one device dispatch per batch and matches the
  expression-by-expression lowering cell for cell;
- **runtime demotion**: a program whose compiled form fails on real data
  (the untraceable-at-runtime class the AST pass cannot see) demotes
  loudly-once, bumps the counter, and the interpreted fallback keeps the
  output byte-identical; data-dependent FloatingPointError falls back
  per-batch WITHOUT demoting;
- **host/device map split**: a select carrying both fusable chains and
  host-only UDFs lowers to map_host/map_dev/ZipAligned, identical output;
- **warmup**: pw.warmup walks the power-of-two bucket ladder so a
  later run_batch adds no compiles (asserted compile counts);
- satellites: closure-over-module rewrite (import math in an enclosing
  scope), int-overflow proof bars unprovable trees, ZipAligned alignment
  asserts, stats/metrics surfaces.
"""

from __future__ import annotations

import logging
import math

import numpy as np
import pytest

import pathway_tpu as pw
from pathway_tpu.debug import table_from_rows
from pathway_tpu.internals import autojit
from pathway_tpu.internals import schema as sch
from pathway_tpu.internals.parse_graph import G
from pathway_tpu.internals.runner import GraphRunner


@pytest.fixture(autouse=True)
def _fresh():
    import gc

    G.clear()
    gc.collect()  # drain dead programs out of the weak registry
    autojit.reset_stats()
    yield
    G.clear()
    autojit.reset_stats()


# -- the UDF zoo: one of each class the tier handles ------------------------

@pw.udf
def boost(x: int) -> int:
    return x * 3 + 7


@pw.udf
def gate(y: float) -> float:
    return y if y < 0.75 else 0.75


@pw.udf
def mixf(x: int, y: float) -> float:
    return x * 0.0001 + y * 0.5


@pw.udf
def rootp(y: float) -> float:
    return math.sqrt(y) + 1.0


@pw.udf
def stepi(x: int) -> int:
    return (x % 7) + (x // 3)


@pw.udf
def cube(x: int) -> int:
    return x * x * x  # 93-bit bound: provably unfusable (bigint exact)


@pw.udf(deterministic=True)
def tag(x: int) -> str:
    return f"doc-{x % 97}"


def _run_events(build, jit: str, monkeypatch, min_rows: int | None = None):
    """Captured (key,row,time,diff) events for one mode, plus stats."""
    monkeypatch.setenv("PATHWAY_AUTO_JIT", jit)
    if min_rows is not None:
        monkeypatch.setattr(autojit, "MIN_ROWS", min_rows)
    G.clear()
    autojit.reset_stats()
    out = build()
    runner = GraphRunner()
    cap = runner.capture(out)
    runner.run_batch(n_workers=1)
    stats = autojit.autojit_stats()
    G.clear()
    return list(cap.events), stats


# ---------------------------------------------------------------------------
# byte-identity property suite across dtypes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2])
def test_identity_across_dtypes(monkeypatch, seed):
    """Randomized int/float/bool/str/Optional[int] columns, clean majority
    plus seeded dirty cells: ON == OFF cell for cell, and the ON run
    genuinely dispatched through the fused tier (non-vacuous)."""
    rng = np.random.default_rng(seed)
    n = 64
    rows = []
    for i in range(n):
        x = int(rng.integers(-10_000, 10_000))
        y = float(rng.random())
        b = bool(rng.integers(0, 2))
        s = f"w{int(rng.integers(0, 9))}"
        oi: int | None = int(rng.integers(0, 100))
        if i % 13 == 5:
            oi = None                     # None-able cell → fallback row
        if i % 17 == 9:
            x = 1 << 40                   # bigint past the 2^31 guard
        rows.append((x, y, b, s, oi, i // 16, 1))
    schema = sch.schema_from_types(x=int, y=float, b=bool, s=str,
                                   oi=int | None)

    def build():
        t = table_from_rows(schema, list(rows), is_stream=True)
        return t.select(
            sb=boost(t.x), sg=gate(t.y), sm=mixf(t.x, t.y),
            sn=rootp(t.y), st=stepi(t.x), sc=cube(t.x),
            tg=tag(t.x), keep=t.b, raw=t.s, opt=t.oi,
            pick=pw.if_else(t.b, t.y, 0.0))

    on, on_stats = _run_events(build, "1", monkeypatch)
    off, _ = _run_events(build, "0", monkeypatch)
    assert on == off
    assert on  # non-vacuous
    assert on_stats["programs"] >= 1
    assert (on_stats["device_dispatches"] + on_stats["vector_dispatches"]) > 0
    assert on_stats["demotions"] == 0


def test_identity_with_per_cell_errors(monkeypatch):
    """Data-dependent per-cell failures (negative sqrt → interpreter
    raises → ERROR cell) fall back per-batch and stay byte-identical —
    the FloatingPointError escape, not a demotion."""
    rows = [(float(i - 6) / 4.0, i // 16, 1) for i in range(32)]
    schema = sch.schema_from_types(y=float)

    def build():
        t = table_from_rows(schema, list(rows), is_stream=True)
        return t.select(sn=rootp(t.y), sg=gate(t.y))

    on, on_stats = _run_events(build, "1", monkeypatch)
    off, _ = _run_events(build, "0", monkeypatch)
    assert on == off
    assert on
    assert on_stats["demotions"] == 0
    # the first tick carries negative y → that batch fell back whole
    assert on_stats["fallback_batches"] >= 1


def test_identity_small_batches_stay_interpreted(monkeypatch):
    """Batches below MIN_ROWS never dispatch (array setup would cost more
    than it saves) and remain identical."""
    rows = [(i, float(i), i, 1) for i in range(6)]  # 1-row ticks
    schema = sch.schema_from_types(x=int, y=float)

    def build():
        t = table_from_rows(schema, list(rows), is_stream=True)
        return t.select(sb=boost(t.x), sg=gate(t.y))

    on, on_stats = _run_events(build, "1", monkeypatch)
    off, _ = _run_events(build, "0", monkeypatch)
    assert on == off
    assert on_stats["device_dispatches"] == 0
    assert on_stats["vector_dispatches"] == 0


# ---------------------------------------------------------------------------
# fused-chain vs per-expr equivalence
# ---------------------------------------------------------------------------

def test_fused_chain_matches_per_expr(monkeypatch):
    """A composed chain (UDF-of-UDF args) fuses into ONE program — a
    single dispatch per batch for the whole tree — and matches the
    select-per-stage lowering cell for cell."""
    rows = [(int(i), float(i) / 33.0, i // 32, 1) for i in range(128)]
    schema = sch.schema_from_types(x=int, y=float)

    def build_chain():
        t = table_from_rows(schema, list(rows), is_stream=True)
        return t.select(out=mixf(boost(t.x), gate(t.y)), extra=boost(t.x))

    def build_staged():
        t = table_from_rows(schema, list(rows), is_stream=True)
        t1 = t.select(sb=boost(t.x), sg=gate(t.y))
        return t1.select(out=mixf(t1.sb, t1.sg), extra=t1.sb)

    chain_on, chain_stats = _run_events(build_chain, "1", monkeypatch)
    chain_off, _ = _run_events(build_chain, "0", monkeypatch)
    staged_on, staged_stats = _run_events(build_staged, "1", monkeypatch)
    rows_of = lambda evs: sorted(tuple(r) for _, r, _, d in evs if d > 0)  # noqa: E731
    assert rows_of(chain_on) == rows_of(chain_off) == rows_of(staged_on)
    assert chain_stats["programs"] == 1
    # ONE guard pass per tick feeds both partitions: the xla partition
    # (extra=boost) and the numpy partition (out: compounding float
    # arithmetic is statically barred from XLA) each dispatch once
    n_ticks = 4
    assert chain_stats["device_dispatches"] in (0, n_ticks)
    assert (chain_stats["device_dispatches"]
            + chain_stats["vector_dispatches"]) == 2 * n_ticks
    # the staged version fuses each map separately — still identical
    assert staged_stats["programs"] == 2


# ---------------------------------------------------------------------------
# runtime demotion: the safety net for what static analysis cannot see
# ---------------------------------------------------------------------------

def _live_program():
    import gc

    gc.collect()  # only THIS test's runner should hold a live program
    progs = list(autojit._REGISTRY)
    assert len(progs) == 1
    return progs[0]


def test_runtime_demotion_loud_once_and_identical(monkeypatch, caplog):
    """A program whose compiled form fails on real data (data-dependent
    control flow the AST pass admitted) demotes loudly ONCE, bumps the
    counter, and the output is byte-identical to the interpreter."""
    rows = [(int(i), i // 16, 1) for i in range(64)]
    schema = sch.schema_from_types(x=int)

    def build():
        t = table_from_rows(schema, list(rows), is_stream=True)
        return t.select(sb=boost(t.x))

    off, _ = _run_events(build, "0", monkeypatch)

    monkeypatch.setenv("PATHWAY_AUTO_JIT", "1")
    G.clear()
    autojit.reset_stats()
    out = build()
    runner = GraphRunner()
    cap = runner.capture(out)
    prog = _live_program()

    def poisoned(*arrays):
        raise RuntimeError("data-dependent control flow reached a tracer")

    # poison BOTH compiled forms: xla fails → numpy fails → interp
    monkeypatch.setattr(prog, "_jit", poisoned, raising=False)
    monkeypatch.setattr(prog, "_np_fn", poisoned)
    monkeypatch.setattr(prog, "_np_sub_fn", None, raising=False)
    with caplog.at_level(logging.WARNING, logger="pathway_tpu.autojit"):
        runner.run_batch(n_workers=1)
    stats = autojit.autojit_stats()
    G.clear()

    assert list(cap.events) == off
    assert prog.backend == "interp"
    assert stats["demotions"] >= 1
    demote_logs = [r for r in caplog.records if "demoted" in r.message]
    # loudly-ONCE per backend hop, not once per batch (4 ticks ran)
    assert 1 <= len(demote_logs) <= 2


def test_moved_jax_api_raises_instead_of_demoting(monkeypatch):
    """An ImportError (or a missing module attribute) while arming the XLA
    tier is the installed JAX having dropped an API — it must raise. The
    old handler demoted at INFO, which kept every output byte-identical
    and hid a dead XLA tier for twenty PRs."""
    import sys

    import jax

    rows = [(int(i), 0, 1) for i in range(64)]
    schema = sch.schema_from_types(x=int)

    def lower():  # programs arm when run_batch lowers the graph
        G.clear()
        t = table_from_rows(schema, list(rows), is_stream=True)
        runner = GraphRunner()
        runner.capture(t.select(sb=boost(t.x)))
        runner.run_batch(n_workers=1)

    monkeypatch.setenv("PATHWAY_AUTO_JIT", "1")
    with monkeypatch.context() as m:
        m.delattr(jax, "enable_x64")  # AttributeError on a module
        with pytest.raises(AttributeError, match="enable_x64"):
            lower()
    with monkeypatch.context() as m:
        m.setitem(sys.modules, "jax", None)  # `import jax` → ImportError
        with pytest.raises(ImportError):
            lower()
    G.clear()
    assert autojit.autojit_stats()["demotions"] == 0


def test_untraceable_probe_still_demotes_at_warning(monkeypatch, caplog):
    """A body that genuinely does not trace stays a demotion to numpy —
    logged at WARNING like every other one."""
    import jax

    def refuses(*a, **kw):
        raise TypeError("tracer leaked into a host call")

    rows = [(int(i), 0, 1) for i in range(64)]
    schema = sch.schema_from_types(x=int)
    monkeypatch.setenv("PATHWAY_AUTO_JIT", "1")
    monkeypatch.setattr(jax, "eval_shape", refuses)
    G.clear()
    t = table_from_rows(schema, list(rows), is_stream=True)
    runner = GraphRunner()
    runner.capture(t.select(sb=boost(t.x)))
    with caplog.at_level(logging.WARNING, logger="pathway_tpu.autojit"):
        runner.run_batch(n_workers=1)
    G.clear()
    assert autojit.autojit_stats()["demotions"] == 1
    assert any("XLA trace probe failed" in r.message
               and r.levelno == logging.WARNING for r in caplog.records)


def test_verify_mismatch_demotes_and_keeps_interpreter_result(monkeypatch):
    """Verify-then-trust: a first-batch cell mismatch (simulated wrong
    compiled output) demotes and the interpreter's values win."""
    rows = [(int(i), i // 16, 1) for i in range(32)]
    schema = sch.schema_from_types(x=int)

    def build():
        t = table_from_rows(schema, list(rows), is_stream=True)
        return t.select(sb=boost(t.x))

    off, _ = _run_events(build, "0", monkeypatch)

    monkeypatch.setenv("PATHWAY_AUTO_JIT", "1")
    G.clear()
    autojit.reset_stats()
    out = build()
    runner = GraphRunner()
    cap = runner.capture(out)
    prog = _live_program()

    def wrong(*arrays):
        return (np.zeros_like(arrays[0]),)  # plausible dtype, wrong values

    monkeypatch.setattr(prog, "_jit", wrong, raising=False)
    monkeypatch.setattr(prog, "_np_fn", wrong)
    monkeypatch.setattr(prog, "_np_sub_fn", None, raising=False)
    runner.run_batch(n_workers=1)
    stats = autojit.autojit_stats()
    G.clear()
    assert list(cap.events) == off
    assert prog.backend == "interp"
    assert stats["demotions"] >= 1


def test_untraceable_body_never_fuses(monkeypatch):
    """A UDF body the classifier cannot admit (truthiness over operands —
    Python returns an OPERAND, arrays cannot) stays interpreted: no
    program, no demotion noise, identical output."""

    @pw.udf
    def sneaky(x: int) -> int:
        return x or 7  # BoolOp: returns an operand by truthiness

    rows = [(int(i % 3), i // 16, 1) for i in range(32)]
    schema = sch.schema_from_types(x=int)

    def build():
        t = table_from_rows(schema, list(rows), is_stream=True)
        return t.select(s=sneaky(t.x))

    on, on_stats = _run_events(build, "1", monkeypatch)
    off, _ = _run_events(build, "0", monkeypatch)
    assert on == off
    assert on_stats["programs"] == 0
    assert on_stats["demotions"] == 0


# ---------------------------------------------------------------------------
# host/device map split (WindVE-style overlap)
# ---------------------------------------------------------------------------

def test_map_split_lowering_and_identity(monkeypatch):
    """A select carrying both a fusable chain and a host-only UDF lowers
    into map_host + map_dev + ZipAligned, the device side marked
    device_bound, and the output matches the unsplit interpreted run."""
    rows = [(int(i), float(i) / 9.0, i // 16, 1) for i in range(64)]
    schema = sch.schema_from_types(x=int, y=float)

    def build():
        t = table_from_rows(schema, list(rows), is_stream=True)
        return t.select(sb=boost(t.x), sg=gate(t.y), tg=tag(t.x))

    monkeypatch.setenv("PATHWAY_AUTO_JIT", "1")
    G.clear()
    autojit.reset_stats()
    out = build()
    runner = GraphRunner()
    cap = runner.capture(out)
    names = {n.name: type(n.op).__name__ for n in runner.graph.nodes}
    assert any(k.startswith("map_host:") for k in names)
    assert any(k.startswith("map_dev:") for k in names)
    assert "ZipAlignedOperator" in names.values()
    dev = next(n for n in runner.graph.nodes
               if n.name.startswith("map_dev:"))
    assert getattr(dev.op, "device_bound", False)
    runner.run_batch(n_workers=1)
    on = list(cap.events)
    G.clear()

    off, _ = _run_events(build, "0", monkeypatch)
    assert on == off
    assert on


def test_no_split_without_host_udf(monkeypatch):
    """All-fusable selects keep ONE operator — the split only pays when
    there is host-only work to overlap."""
    rows = [(int(i), i // 16, 1) for i in range(32)]
    schema = sch.schema_from_types(x=int)
    monkeypatch.setenv("PATHWAY_AUTO_JIT", "1")
    G.clear()
    t = table_from_rows(schema, rows, is_stream=True)
    out = t.select(sb=boost(t.x), st=stepi(t.x))
    runner = GraphRunner()
    runner.capture(out)
    names = [n.name for n in runner.graph.nodes]
    assert not any(k.startswith(("map_host:", "map_dev:")) for k in names)


def test_zip_aligned_misalignment_raises():
    from pathway_tpu.engine.delta import Delta
    from pathway_tpu.engine.operators import ZipAlignedOperator

    op = ZipAlignedOperator(((0, 0), (1, 0)))
    left = Delta([(1, ("a",), 1)])
    right = Delta([(2, ("b",), 1)])
    with pytest.raises(RuntimeError, match="lost alignment"):
        op.step(0, [left, right])
    ok = op.step(0, [Delta([(1, ("a",), 1)]), Delta([(1, ("b",), 1)])])
    assert ok.entries == [(1, ("a", "b"), 1)]


# ---------------------------------------------------------------------------
# warmup walks the bucket ladder
# ---------------------------------------------------------------------------

def test_warmup_walks_buckets_then_serving_compiles_nothing(monkeypatch):
    """pw.warmup after building the runner compiles every power-of-two
    bucket (8..max); the subsequent run adds NO compiles — first-tick
    compile latency moved out of serving."""
    monkeypatch.setenv("PATHWAY_AUTO_JIT", "1")
    monkeypatch.setenv("PATHWAY_AUTO_JIT_WARM_MAX", "256")
    G.clear()
    autojit.reset_stats()
    rows = [(int(i), i // 100, 1) for i in range(200)]
    schema = sch.schema_from_types(x=int)
    t = table_from_rows(schema, rows, is_stream=True)
    out = t.select(sb=boost(t.x))
    runner = GraphRunner()
    cap = runner.capture(out)
    prog = _live_program()
    if prog.backend != "xla":  # CI without a usable jax backend
        pytest.skip("XLA backend unavailable for the fused program")
    warm = pw.warmup()
    entries = [e for e in warm["compiled"] if e[0] == "autojit"]
    # ladder 8,16,32,64,128,256 → 6 buckets, each counted as a compile
    assert len(entries) == 6
    assert autojit.autojit_stats()["compiles"] == 6
    runner.run_batch(n_workers=1)  # 100-row ticks → bucket 128 (walked)
    assert autojit.autojit_stats()["compiles"] == 6
    assert autojit.autojit_stats()["device_dispatches"] >= 1
    assert [r for _, r, _, d in cap.events if d > 0]
    G.clear()


def test_warmup_autojit_disabled_is_noop(monkeypatch):
    monkeypatch.setenv("PATHWAY_AUTO_JIT", "0")
    assert autojit.warm_registered() == []


# ---------------------------------------------------------------------------
# satellites
# ---------------------------------------------------------------------------

def test_closure_over_module_fuses(monkeypatch):
    """A UDF defined inside a function whose enclosing scope imported
    math still fuses (module-valued closure cells are process singletons
    — the regression that kept bench UDFs interpreted)."""
    def make_udf():
        import math  # noqa: F401 — deliberately shadows the module global

        @pw.udf
        def local_root(y: float) -> float:
            return math.sqrt(y) + 0.5

        return local_root

    local_root = make_udf()
    rows = [(float(i) / 7.0, i // 16, 1) for i in range(32)]
    schema = sch.schema_from_types(y=float)

    def build():
        t = table_from_rows(schema, list(rows), is_stream=True)
        return t.select(sn=local_root(t.y))

    on, on_stats = _run_events(build, "1", monkeypatch)
    off, _ = _run_events(build, "0", monkeypatch)
    assert on == off
    assert on_stats["programs"] == 1
    assert on_stats["vector_dispatches"] >= 1  # math body → numpy partition


def test_locally_imported_decorator_still_fuses(monkeypatch):
    """A UDF decorated via a name imported in the ENCLOSING function
    (`import pathway_tpu as pw2` inside a factory — the bench's shape)
    must fuse: decorators resolve at def time, not per call, so the
    global-read gate must only inspect the body."""
    def make_udf():
        import pathway_tpu as pw2

        @pw2.udf
        def triple(x: int) -> int:
            return x * 3 + 1

        return triple

    triple = make_udf()
    rows = [(int(i), i // 16, 1) for i in range(32)]
    schema = sch.schema_from_types(x=int)

    def build():
        t = table_from_rows(schema, list(rows), is_stream=True)
        return t.select(s=triple(t.x))

    on, on_stats = _run_events(build, "1", monkeypatch)
    off, _ = _run_events(build, "0", monkeypatch)
    assert on == off
    assert on_stats["programs"] == 1
    assert (on_stats["device_dispatches"] + on_stats["vector_dispatches"]) > 0


def test_non_module_closure_never_fuses(monkeypatch):
    """A UDF closing over a mutable value must NOT be frozen — the cell
    could change under the fused program's feet. It stays interpreted."""
    factor = [3]

    def make_udf():
        k = factor[0]

        @pw.udf
        def scaled(x: int) -> int:
            return x * k

        return scaled

    scaled = make_udf()
    rows = [(int(i), i // 16, 1) for i in range(32)]
    schema = sch.schema_from_types(x=int)

    def build():
        t = table_from_rows(schema, list(rows), is_stream=True)
        return t.select(s=scaled(t.x))

    on, on_stats = _run_events(build, "1", monkeypatch)
    off, _ = _run_events(build, "0", monkeypatch)
    assert on == off


def test_int_overflow_proof_bars_unprovable_trees(monkeypatch):
    """cube(x) needs 93 bits on guarded leaves — provably past int64, so
    the tree never fuses and Python bigint semantics hold exactly."""
    big = 2_000_000_000  # < 2^31: passes the cell guard
    rows = [(big, 0, 1)] * 16
    schema = sch.schema_from_types(x=int)

    def build():
        t = table_from_rows(schema, list(rows), is_stream=True)
        return t.select(c=cube(t.x))

    on, on_stats = _run_events(build, "1", monkeypatch)
    off, _ = _run_events(build, "0", monkeypatch)
    assert on == off
    assert on_stats["programs"] == 0  # nothing eligible fused
    got = [r[0] for _, r, _, d in on if d > 0]
    assert got == [big ** 3] * 16  # exact bigint, no int64 wrap


def test_mod_bound_uses_right_operand(monkeypatch):
    """|a % b| < |b|: the proof must bound modulo by the RIGHT operand.
    (-1 % y) is y-1, so (-1 % y) * x * x reaches ~2^93 from guarded
    leaves — a left-operand bound would 'prove' it safe at 63 bits and
    int64 would wrap silently on big inputs while the interpreter
    returns exact bigints."""

    @pw.udf
    def modmul(x: int, y: int) -> int:
        return (-1 % y) * x * x

    big = 2_000_000_000
    rows = [(big, big, 0, 1)] * 16
    schema = sch.schema_from_types(x=int, y=int)

    def build():
        t = table_from_rows(schema, list(rows), is_stream=True)
        return t.select(m=modmul(t.x, t.y))

    on, on_stats = _run_events(build, "1", monkeypatch)
    off, _ = _run_events(build, "0", monkeypatch)
    assert on == off
    assert on_stats["programs"] == 0  # provably past int64: never fuses
    got = [r[0] for _, r, _, d in on if d > 0]
    assert got == [(-1 % big) * big * big] * 16  # exact bigint


def test_unary_minus_preserves_negative_zero(monkeypatch):
    """-x must be true negation, not 0 - x: the latter turns -0.0 into
    +0.0, a byte divergence == cannot see."""
    rows = [(0.0 if i % 2 else float(i) / 8.0, i // 16, 1)
            for i in range(32)]
    schema = sch.schema_from_types(y=float)

    def build():
        t = table_from_rows(schema, list(rows), is_stream=True)
        return t.select(n=-gate(t.y))

    on, on_stats = _run_events(build, "1", monkeypatch)
    off, _ = _run_events(build, "0", monkeypatch)
    assert on == off
    assert (on_stats["device_dispatches"]
            + on_stats["vector_dispatches"]) > 0  # non-vacuous
    zeros = [r[0] for _, r, _, d in on if d > 0 and r[0] == 0.0]
    assert zeros and all(math.copysign(1.0, z) == -1.0 for z in zeros)


def test_split_bail_discards_phantom_programs(monkeypatch):
    """A probed-then-bailed host/device split (host side non-
    deterministic → the aligned zip cannot be used) must not leave its
    FusedPrograms in the stats: /metrics counts only programs that can
    dispatch."""

    @pw.udf  # NOT deterministic → host_nd → split bails
    def tag_nd(x: int) -> str:
        return f"t-{x % 5}"

    rows = [(int(i), i // 16, 1) for i in range(32)]
    schema = sch.schema_from_types(x=int)

    def build():
        t = table_from_rows(schema, list(rows), is_stream=True)
        return t.select(sb=boost(t.x), tg=tag_nd(t.x))

    on, on_stats = _run_events(build, "1", monkeypatch)
    off, _ = _run_events(build, "0", monkeypatch)
    assert on == off
    # ONE live program (the full map's) — the split's probe compile was
    # backed out when it bailed
    assert on_stats["programs"] == 1


_SCALE = 7.5  # non-module global: fused snapshots would go stale


def test_mixed_int_float_comparison_past_2_53_not_fused(monkeypatch):
    """Python compares int-vs-float exactly; numpy/XLA promote int64 to
    float64 and round past 2^53. A comparison whose int side can exceed
    53 bits must stay interpreted."""
    @pw.udf
    def past53lit(x: int) -> bool:
        return x * x > 4611686014132420608.0  # x*x provable to 62 bits

    big = 2147483647  # x*x = 2^62-ish, one past float64's exact range
    rows = [(big, 0, 1)] * 16
    schema = sch.schema_from_types(x=int)

    def build():
        t = table_from_rows(schema, list(rows), is_stream=True)
        return t.select(c=past53lit(t.x))

    on, on_stats = _run_events(build, "1", monkeypatch)
    off, _ = _run_events(build, "0", monkeypatch)
    assert on == off
    assert on_stats["programs"] == 0
    got = [r[0] for _, r, _, d in on if d > 0]
    assert got == [big * big > 4611686014132420608.0] * 16  # exact


def test_bitwise_ops_never_fuse(monkeypatch):
    """Two's complement defeats magnitude bounds on negatives:
    -1 & v == v, so (-1 & (x*x)) * x reaches ~2^93 from guarded leaves.
    Bitwise bodies stay interpreted."""

    @pw.udf
    def bitmul(x: int) -> int:
        return (-1 & (x * x)) * x

    big = 2147483647
    rows = [(big, 0, 1)] * 16
    schema = sch.schema_from_types(x=int)

    def build():
        t = table_from_rows(schema, list(rows), is_stream=True)
        return t.select(m=bitmul(t.x))

    on, on_stats = _run_events(build, "1", monkeypatch)
    off, _ = _run_events(build, "0", monkeypatch)
    assert on == off
    assert on_stats["programs"] == 0
    got = [r[0] for _, r, _, d in on if d > 0]
    assert got == [(-1 & (big * big)) * big] * 16  # exact bigint


def test_int_cast_products_not_fused_without_declared_int(monkeypatch):
    """int() casts mint int64 values up to 2^62 even in a body whose
    PREDICTED return kind is float — their products wrap. The cast must
    force the overflow proof regardless of the predicted kind."""
    rows = [(0.5 + i / 64.0, i // 16, 1) for i in range(32)]
    schema = sch.schema_from_types(y=float)

    def build():
        t = table_from_rows(schema, list(rows), is_stream=True)
        return t.select(v=pw.apply(
            lambda y: int(y * 1e17) * int(y * 1e17), t.y))

    on, on_stats = _run_events(build, "1", monkeypatch)
    off, _ = _run_events(build, "0", monkeypatch)
    assert on == off
    assert on_stats["programs"] == 0
    got = [r[0] for _, r, _, d in on if d > 0]
    assert got and all(isinstance(v, int) and v > (1 << 63) for v in got)


def test_non_module_global_read_not_fused(monkeypatch):
    """A body reading a module-level non-module name (a tunable) must
    stay interpreted: the fused program would freeze the value while the
    interpreter reads it live, and the nondet replay cache would be
    dropped for a body that is NOT verified deterministic."""

    @pw.udf
    def scaled(y: float) -> float:
        return y * _SCALE

    rows = [(float(i) / 9.0, i // 16, 1) for i in range(32)]
    schema = sch.schema_from_types(y=float)

    def build():
        t = table_from_rows(schema, list(rows), is_stream=True)
        return t.select(s=scaled(t.y))

    on, on_stats = _run_events(build, "1", monkeypatch)
    off, _ = _run_events(build, "0", monkeypatch)
    assert on == off
    assert on_stats["programs"] == 0
    # and the lowering kept the caching operator for the unverified body
    monkeypatch.setenv("PATHWAY_AUTO_JIT", "1")
    G.clear()
    t = table_from_rows(schema, list(rows), is_stream=True)
    out = t.select(s=scaled(t.y))
    runner = GraphRunner()
    runner.capture(out)
    ops = {type(n.op).__name__ for n in runner.graph.nodes}
    assert "DeterministicMapOperator" in ops
    G.clear()


def test_int64_min_cell_guarded(monkeypatch):
    """-2**63 is the adversarial guard cell: np.abs of it WRAPS (stays
    negative), so a magnitude check via abs would admit it to the fused
    path where the overflow proof assumed |v| < 2^31. It must be routed
    to the interpreter and stay byte-identical."""
    evil = -(1 << 63)
    rows = [(evil if i % 4 == 0 else i, i // 16, 1) for i in range(32)]
    schema = sch.schema_from_types(x=int)

    def build():
        t = table_from_rows(schema, list(rows), is_stream=True)
        return t.select(sb=boost(t.x))

    on, on_stats = _run_events(build, "1", monkeypatch)
    off, _ = _run_events(build, "0", monkeypatch)
    assert on == off
    got = {r[0] for _, r, _, d in on if d > 0}
    assert evil * 3 + 7 in got  # exact bigint arithmetic preserved


def test_stats_and_status_surfaces(monkeypatch):
    rows = [(int(i), i // 16, 1) for i in range(32)]
    schema = sch.schema_from_types(x=int)

    def build():
        t = table_from_rows(schema, list(rows), is_stream=True)
        return t.select(sb=boost(t.x))

    _, stats = _run_events(build, "1", monkeypatch)
    assert stats["enabled"] is True
    assert set(stats) >= {"programs", "compiles", "demotions",
                          "device_dispatches", "vector_dispatches",
                          "fallback_batches", "live_programs",
                          "bucket_count"}
    monkeypatch.setenv("PATHWAY_AUTO_JIT", "0")
    assert autojit.autojit_stats()["enabled"] is False
