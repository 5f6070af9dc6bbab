"""Reducers & groupby (reference: engine Reducer set, src/engine/reduce.rs:22)."""

import random

import numpy as np
import pytest

import pathway_tpu as pw
from pathway_tpu.engine import reducers as red
from pathway_tpu.engine.delta import row_fingerprint
from tests.utils import T, assert_table_equality_wo_index, rows_of


def _t():
    return T("""
    g | x | y
    a | 3 | 1.5
    a | 1 | 2.5
    b | 2 | 0.5
    """)


def test_basic_reducers():
    t = _t()
    r = t.groupby(t.g).reduce(
        t.g,
        s=pw.reducers.sum(t.x),
        n=pw.reducers.count(),
        mn=pw.reducers.min(t.x),
        mx=pw.reducers.max(t.x),
        av=pw.reducers.avg(t.y),
    )
    assert sorted(rows_of(r)) == [("a", 4, 2, 1, 3, 2.0), ("b", 2, 1, 2, 2, 0.5)]


def test_argmin_argmax():
    t = _t()
    r = t.groupby(t.g).reduce(
        t.g,
        lo=pw.reducers.argmin(t.x),
        hi=pw.reducers.argmax(t.x),
    )
    fetched_lo = t.ix(r.lo, context=r)
    fetched_hi = t.ix(r.hi, context=r)
    vals = r.select(r.g, lo_x=fetched_lo.x, hi_x=fetched_hi.x)
    assert sorted(rows_of(vals)) == [("a", 1, 3), ("b", 2, 2)]


def test_tuple_reducers():
    t = _t()
    r = t.groupby(t.g).reduce(
        t.g,
        st=pw.reducers.sorted_tuple(t.x),
    )
    assert sorted(rows_of(r)) == [("a", (1, 3)), ("b", (2,))]


def test_unique_any():
    t = T("""
    g | c
    a | 7
    a | 7
    b | 9
    """)
    r = t.groupby(t.g).reduce(t.g, u=pw.reducers.unique(t.c),
                              an=pw.reducers.any(t.c))
    assert sorted(rows_of(r)) == [("a", 7, 7), ("b", 9, 9)]


def test_ndarray_reducer():
    t = _t()
    r = t.groupby(t.g).reduce(t.g, arr=pw.reducers.ndarray(t.x))
    rows = dict(rows_of(r))
    assert sorted(rows["a"].tolist()) == [1, 3]


def test_earliest_latest():
    t = T("""
    g | x | _time
    a | 1 | 2
    a | 2 | 4
    a | 3 | 6
    """)
    r = t.groupby(t.g).reduce(
        t.g, e=pw.reducers.earliest(t.x), l=pw.reducers.latest(t.x))
    assert rows_of(r) == [("a", 1, 3)]


def test_stateful_single():
    t = T("""
    g | x
    a | 1
    a | 2
    b | 5
    """)

    def acc(state, x):
        return (state or 0) + x

    r = t.groupby(t.g).reduce(t.g, s=pw.reducers.stateful_single(acc, t.x))
    assert sorted(rows_of(r)) == [("a", 3), ("b", 5)]


def test_compound_reduce_expression():
    t = _t()
    r = t.groupby(t.g).reduce(
        t.g, z=pw.reducers.sum(t.x) * 10 + pw.reducers.count())
    assert sorted(rows_of(r)) == [("a", 42), ("b", 21)]


def test_incremental_retraction():
    t = T("""
    g | x | _time | _diff
    a | 1 | 2     | 1
    a | 2 | 4     | 1
    a | 1 | 6     | -1
    """)
    r = t.groupby(t.g).reduce(t.g, s=pw.reducers.sum(t.x),
                              mn=pw.reducers.min(t.x))
    assert rows_of(r) == [("a", 2, 2)]


def test_groupby_instance():
    t = T("""
    g | i | x
    a | 0 | 1
    a | 1 | 2
    b | 0 | 5
    """)
    r = t.groupby(t.g, instance=t.i).reduce(t.g, s=pw.reducers.sum(t.x))
    assert sorted(rows_of(r)) == [("a", 1), ("a", 2), ("b", 5)]


def test_global_reduce_empty_groups_vanish():
    t = T("""
    g | x | _time | _diff
    a | 1 | 2     | 1
    a | 1 | 4     | -1
    """)
    r = t.groupby(t.g).reduce(t.g, n=pw.reducers.count())
    assert rows_of(r) == []


def test_same_tick_net_zero_pair_invisible_to_order_sensitive_reducers():
    """A same-batch insert+delete of the same row must cancel BEFORE
    operators see it: earliest/latest would otherwise permanently record
    the deleted value (their canonical sort processes retractions first,
    so the uncancelled insert lands with no matching retraction), sinks
    would emit phantom events, and float sums would drift."""
    t = T("""
    g | v | _time | _diff
    a | 1 | 2     | 1
    a | 9 | 4     | 1
    a | 9 | 4     | -1
    """)
    r = t.groupby(t.g).reduce(
        t.g, last=pw.reducers.latest(t.v), s=pw.reducers.sum(t.v))
    assert sorted(rows_of(r)) == [("a", 1, 1)]

    # and the sink never observes the phantom value
    t2 = T("""
    g | v | _time | _diff
    a | 1 | 2     | 1
    a | 9 | 4     | 1
    a | 9 | 4     | -1
    """)
    from pathway_tpu.internals.runner import run_tables

    [cap] = run_tables(t2)
    assert all(row[1] != 9 for _k, row, _t, _d in cap.events)


def test_columnar_minmax_reducers_exact_under_retraction():
    """min/max ride the columnar operator as multiset side-state and stay
    exact through retractions, matching the row path."""
    import pathway_tpu as pw
    from pathway_tpu.debug import table_from_rows
    from pathway_tpu.engine.operators import ColumnarGroupByOperator
    from pathway_tpu.internals import schema as sch
    from pathway_tpu.internals.parse_graph import G
    from pathway_tpu.internals.runner import GraphRunner

    G.clear()
    rows = [
        ("a", 5, 0, 1), ("a", 2, 0, 1), ("a", 9, 2, 1),
        ("b", 7, 2, 1), ("a", 2, 4, -1), ("a", 9, 4, -1),
    ]
    t = table_from_rows(
        sch.schema_from_types(k=str, v=int), rows, is_stream=True)
    g = t.groupby(t.k).reduce(
        t.k, lo=pw.reducers.min(t.v), hi=pw.reducers.max(t.v),
        s=pw.reducers.sum(t.v))
    runner = GraphRunner()
    cap = runner.capture(g)
    assert any(isinstance(n.op, ColumnarGroupByOperator)
               for n in runner.graph.nodes)
    runner.run_batch(n_workers=1)
    snap = sorted(cap.snapshot().values())
    # after retracting 2 and 9, group a holds only 5
    assert snap == [("a", 5, 5, 5), ("b", 7, 7, 7)]
    G.clear()


def test_columnar_minmax_ignores_net_negative_counts():
    """A retraction arriving ahead of its insertion must not surface its
    value in min/max (row-path _MultisetState parity)."""
    import pathway_tpu as pw
    from pathway_tpu.debug import table_from_rows
    from pathway_tpu.internals import schema as sch
    from pathway_tpu.internals.parse_graph import G
    from pathway_tpu.internals.runner import GraphRunner

    G.clear()
    rows = [("a", 5, 0, 1), ("a", 7, 0, 1), ("a", 2, 0, -1)]
    t = table_from_rows(
        sch.schema_from_types(k=str, v=int), rows, is_stream=True)
    g = t.groupby(t.k).reduce(t.k, lo=pw.reducers.min(t.v))
    runner = GraphRunner()
    cap = runner.capture(g)
    runner.run_batch(n_workers=1)
    assert sorted(cap.snapshot().values()) == [("a", 5)]
    G.clear()


def test_columnar_argminmax_matches_row_path():
    """argmin/argmax ride the columnar operator; results (incl. key
    payloads, tiebreaks, retractions) must equal the row path."""
    import pathway_tpu as pw
    from pathway_tpu.debug import table_from_rows
    from pathway_tpu.engine.operators import (ColumnarGroupByOperator,
                                              GroupByOperator)
    from pathway_tpu.internals import runner as _runner
    from pathway_tpu.internals import schema as sch
    from pathway_tpu.internals.parse_graph import G
    from pathway_tpu.internals.runner import GraphRunner

    rows = [
        ("a", 5, "x", 0, 1), ("a", 9, "y", 0, 1), ("a", 9, "z", 2, 1),
        ("b", 1, "q", 2, 1), ("a", 9, "z", 4, -1),
    ]

    def run(force_row_path):
        G.clear()
        t = table_from_rows(
            sch.schema_from_types(k=str, v=int, tag=str), rows,
            is_stream=True)
        from pathway_tpu.internals import expression as ex

        g = t.groupby(t.k).reduce(
            t.k,
            best_tag=ex.ReducerExpression("argmax", t.v, t.tag),
            lo_key=pw.reducers.argmin(t.v),
        )
        runner = GraphRunner()
        cap = runner.capture(g)
        kinds = {type(n.op) for n in runner.graph.nodes}
        if force_row_path:
            assert GroupByOperator in kinds
        else:
            assert ColumnarGroupByOperator in kinds
        runner.run_batch(n_workers=1)
        out = sorted(cap.snapshot().values())
        G.clear()
        return out

    columnar = run(False)
    orig = _runner._columnar_groupby_spec
    _runner._columnar_groupby_spec = lambda *a, **k: None
    try:
        row = run(True)
    finally:
        _runner._columnar_groupby_spec = orig
    assert columnar == row
    # argmax of a: after retracting (9, z), tie between remaining 9=y
    assert columnar[0][1] == "y"


def test_array_sum_device_path_bitwise_matches_numpy(monkeypatch):
    """Big float32 ndarray columns reduce through the XLA segment-sum
    (operators._device_array_sums); the device result must be BITWISE
    equal to the per-row numpy path — the scan kernel accumulates each
    group's rows sequentially in the same canonical order, so no float
    tolerance is needed (and the n_workers byte-identity contract
    holds)."""
    from pathway_tpu.debug import table_from_rows
    from pathway_tpu.engine import operators as eng_ops
    from pathway_tpu.internals import schema as sch
    from pathway_tpu.internals.parse_graph import G
    from pathway_tpu.internals.runner import GraphRunner

    rng = np.random.default_rng(7)
    vecs = rng.standard_normal((300, 6)).astype(np.float32)
    # one group of pure -0.0 rows: the device seed must reproduce each
    # state's numpy start exactly (npsum keeps -0.0, sum's int-0 start
    # flips it to +0.0) — np.array_equal can't see the sign, so signbits
    # are compared below
    vecs[::7] = -0.0
    rows = [(f"g{i % 7}", vecs[i], (i % 3) * 2, 1) for i in range(300)]

    def run(device_min, n_workers=1):
        monkeypatch.setattr(eng_ops, "_ARRAY_SUM_DEVICE_MIN", device_min)
        # sharded workers see ~300/(3 ticks × n_workers) entries per tick;
        # drop the row gate so the 4-worker leg really drives the device
        # path instead of vacuously passing through the numpy loop
        monkeypatch.setattr(eng_ops, "_ARRAY_SUM_MIN_ROWS", 1)
        G.clear()
        t = table_from_rows(
            sch.schema_from_types(g=str, v=np.ndarray), rows,
            is_stream=True)
        # npsum (array_sum) AND plain sum() both ride the device path
        r = t.groupby(t.g).reduce(t.g, s=pw.reducers.npsum(t.v),
                                  s2=pw.reducers.sum(t.v))
        runner = GraphRunner()
        cap = runner.capture(r)
        runner.run_batch(n_workers=n_workers)
        out = {row[0]: (row[1], row[2]) for row in cap.snapshot().values()}
        G.clear()
        return out

    numpy_out = run(0)             # device path disabled
    device_out = run(1)            # every tick routes through XLA
    device_sharded = run(1, n_workers=4)
    assert set(numpy_out) == set(device_out) == set(device_sharded)

    def bitwise_equal(a, b):
        return a.dtype == b.dtype and a.tobytes() == b.tobytes()

    for g in numpy_out:
        for col in (0, 1):  # npsum and plain sum
            assert numpy_out[g][col].dtype == np.float32
            assert bitwise_equal(numpy_out[g][col], device_out[g][col]), \
                (g, col, numpy_out[g][col], device_out[g][col])
            assert bitwise_equal(numpy_out[g][col],
                                 device_sharded[g][col]), (g, col)


# ---------------------------------------------------------------------------
# order-keeping multiset states (engine/reducers.py) against the plain
# recomputation they replaced, kept here as the oracle
# ---------------------------------------------------------------------------

class _PlainMultiset:
    """The multiset and the ``emit`` bodies as they were before the states
    kept an order: every answer recomputed from the whole group."""

    def __init__(self, name, skip_nones=False):
        self.name = name
        self.skip_nones = skip_nones
        self.counts = {}
        self.values = {}

    def add(self, args, diff):
        fp = row_fingerprint(args)
        c = self.counts.get(fp, 0) + diff
        if c == 0:
            self.counts.pop(fp, None)
            self.values.pop(fp, None)
        else:
            self.counts[fp] = c
            self.values[fp] = args

    def iter_args(self):
        for fp, c in self.counts.items():
            for _ in range(max(c, 0)):
                yield self.values[fp]

    def emit(self):
        return getattr(self, "_" + self.name)()

    def _min(self):
        return min(v[0] for v in self.iter_args())

    def _max(self):
        return max(v[0] for v in self.iter_args())

    def _argmin(self):
        return min(self.iter_args(), key=lambda v: (v[0], (0, v[1])))[1]

    def _argmax(self):
        return max(self.iter_args(), key=lambda v: (v[0], (0, v[1])))[1]

    def _unique(self):
        vals = {row_fingerprint((v[0],)): v[0] for v in self.iter_args()}
        if len(vals) != 1:
            raise ValueError(
                "More than one distinct value passed to the unique reducer.")
        return next(iter(vals.values()))

    def _any(self):
        return self.values[min(self.counts)][0]

    def _sorted_tuple(self):
        vals = [v[0] for v in self.iter_args()]
        if self.skip_nones:
            vals = [v for v in vals if v is not None]
        return tuple(sorted(vals, key=red._sort_key))

    def _tuple(self):
        items = list(self.iter_args())
        items.sort(key=lambda v: red._sort_key(v[1]) if len(v) > 1 else 0)
        vals = [v[0] for v in items]
        if self.skip_nones:
            vals = [v for v in vals if v is not None]
        return tuple(vals)

    def _ndarray(self):
        return np.array(self._tuple())


def _outcome(state):
    """The answer, or the exception in its place: both are compared."""
    try:
        v = state.emit()
    except (TypeError, ValueError) as e:
        return ("raised", type(e), str(e))
    if isinstance(v, np.ndarray):
        return ("array", str(v.dtype), v.shape, v.tolist())
    return ("value", type(v), v)


def _reducer_domain(name, rnd, ties=True, odd=False):
    """Argument tuples for ``name``: many more than ``_ORDER_FROM``, so a
    state crosses into its ordered form. ``ties`` adds distinct rows that
    compare equal under the reducer's order, or are one row in two types
    (1 and 1.0): the answer then depends on arrival, in the state as in the
    plain recomputation. ``odd`` adds values no order can hold."""
    n = 90
    if name in ("min", "max"):
        dom = [(rnd.randrange(400),) for _ in range(n)] \
            + [(rnd.randrange(400) + 0.5,) for _ in range(10)]
        if ties:
            dom += [(float(v),) for v, in dom[:10]]
        if odd:
            dom += [("s1",), ("s2",), (float("nan"),), (None,)]
    elif name in ("argmin", "argmax"):
        dom = [(rnd.randrange(12), rnd.randrange(1000)) for _ in range(n)]
        if ties:
            dom += [(float(v), float(p)) for v, p in dom[:10]]
        if odd:
            dom += [(rnd.randrange(12), None), (3, "p"), (float("nan"), 1)]
    elif name == "unique":
        dom = [(7,)] * 30
        if ties:
            dom += [(7.0,)] + [(100 + i,) for i in range(n)]
    elif name == "any":
        dom = [("v%d" % i,) for i in range(n)]
    elif name == "sorted_tuple":
        dom = [(rnd.choice([None, rnd.randrange(2, 300), rnd.random(),
                            "s%d" % rnd.randrange(200),
                            (rnd.randrange(4), "t%d" % rnd.randrange(50))]),)
               for _ in range(n)]
        if ties:
            dom += [(True,), (1,), (1.0,)] + [(2**60 + i,) for i in range(4)]
        if odd:
            dom += [(float("nan"),)]
    else:  # tuple, ndarray: (value, the row's key as the engine passes it)
        vals = [rnd.choice([None, rnd.randrange(50), rnd.random()])
                for _ in range(n)]
        dom = [(v, (i << 64) + rnd.getrandbits(64))
               for i, v in enumerate(vals)]
        if ties:
            dom += [(v, 5) for v in vals[:6]]
        if odd:
            dom += [(1.5, float("nan"))]
    return dom


_MULTISET_CASES = [
    ("min", {}), ("max", {}), ("argmin", {}), ("argmax", {}), ("any", {}),
    ("unique", {}),
    ("sorted_tuple", {}), ("sorted_tuple", {"skip_nones": True}),
    ("tuple", {}), ("tuple", {"skip_nones": True}),
    ("ndarray", {}), ("ndarray", {"skip_nones": True}),
]


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize(
    "name,kwargs", _MULTISET_CASES,
    ids=[n + ("-skip_nones" if kw else "") for n, kw in _MULTISET_CASES])
def test_ordered_multiset_state_equals_plain_recomputation(
        name, kwargs, seed, monkeypatch):
    # an order kept from few entries on, in chunks small enough to split and
    # to empty here
    monkeypatch.setattr(red._MultisetState, "_ORDER_FROM", 32)
    monkeypatch.setattr(red._SortedTupleState, "_ORDER_FROM", 32)
    monkeypatch.setattr(red._Order, "_CHUNK", 4)
    rnd = random.Random(seed * 1000 + len(name))
    state = red.make_reducer_state(name, **kwargs)
    plain = _PlainMultiset(name, **kwargs)
    ops = []

    def step(args, diff):
        ops.append((args, diff))
        state.add(args, diff)
        plain.add(args, diff)
        assert _outcome(state) == _outcome(plain), (len(ops), args, diff)

    def drive(dom, steps):
        held = []
        for i in range(steps):
            if held and rnd.random() < 0.3:
                step(held.pop(rnd.randrange(len(held))), -1)
            else:
                args = rnd.choice(dom)
                # mostly insertions; duplicates; retractions that come
                # ahead of their insertions
                diff = rnd.choice([1, 1, 1, 1, 2, -1])
                held.extend([args] * diff)
                step(args, diff)
        # the group emptied
        for fp, c in list(plain.counts.items()):
            step(plain.values[fp], -c)
        assert not plain.counts and not state.counts
        assert state.is_empty()

    drive(_reducer_domain(name, rnd), 300)
    ordered_at = len(ops)
    assert state.rederived == 0
    # refilled, now with values among them that no order can hold
    drive(_reducer_domain(name, rnd, odd=True), 300)
    assert ordered_at

    # one multiset, two arrival orders: where no two distinct rows tie, the
    # answer is the multiset's alone
    dom = _reducer_domain(name, rnd, ties=False)
    ops = [(rnd.choice(dom), rnd.choice([1, 1, 2, -1])) for _ in range(200)]
    a = red.make_reducer_state(name, **kwargs)
    b = red.make_reducer_state(name, **kwargs)
    for args, d in ops:
        a.add(args, d)
    shuffled = ops[:]
    rnd.shuffle(shuffled)
    for args, d in shuffled:
        b.add(args, d)
    assert _outcome(a) == _outcome(b)


def test_ordered_state_tick_costs_its_rows_not_its_group(monkeypatch):
    """The statistics reduce's shape (one group; ``max`` of an int and
    ``tuple`` of a path ordered by the row's key): with 65,536 entries in
    the group, a tick of 64 adds computes 64 sort keys, not 65,536, and an
    ``emit`` with no change since the last builds nothing."""
    calls = [0]
    plain_sort_key = red._sort_key

    def counting_sort_key(v):
        calls[0] += 1
        return plain_sort_key(v)

    monkeypatch.setattr(red, "_sort_key", counting_sort_key)
    rnd = random.Random(26)
    n = 65_536
    keys = [rnd.getrandbits(128) for _ in range(n + 64)]
    paths = red.make_reducer_state("tuple")
    newest = red.make_reducer_state("max")
    for i in range(n):
        paths.add(("/docs/%06d.txt" % i, keys[i]), 1)
        newest.add((1_700_000_000 + i % 977,), 1)
    assert calls[0] == 0           # a group nobody has read keeps no order
    first = paths.emit()           # read once: walked, as a batch run is
    assert calls[0] == n and len(first) == n
    assert paths.emit() == first   # read again: the order derived, once
    assert calls[0] == 2 * n and newest.emit() == 1_700_000_976
    assert newest.emit() == 1_700_000_976
    calls[0] = 0
    for i in range(n, n + 64):
        paths.add(("/docs/%06d.txt" % i, keys[i]), 1)
        newest.add((1_800_000_000 + i,), 1)
    after = paths.emit()
    assert calls[0] == 64
    assert len(after) == n + 64 and newest.emit() == 1_800_000_000 + n + 63
    by_key = sorted(range(n + 64), key=lambda i: float(keys[i]))
    assert after == tuple("/docs/%06d.txt" % i for i in by_key)
    assert paths.emit() is after   # nothing changed: no pass, the same tuple
    # a retraction finds its entry by its stored key
    paths.add(("/docs/%06d.txt" % 7, keys[7]), -1)
    assert calls[0] == 64
    assert len(paths.emit()) == n + 63 and "/docs/000007.txt" not in paths.emit()
    assert paths.rederived == 0 and newest.rederived == 0
