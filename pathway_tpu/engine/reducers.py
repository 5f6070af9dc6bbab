"""Engine-side incremental reducers.

Rebuild of the reference's reducer set (src/engine/reduce.rs:22 — Count,
IntSum, FloatSum, ArraySum, Unique, Min, Max, ArgMin, ArgMax, Any,
SortedTuple, Tuple, Stateful, Earliest, Latest). Semigroup reducers
(count/sums) update in O(1). Order-dependent ones (min, max, argmin,
argmax, any, unique, sorted_tuple, tuple, ndarray) are one ordered multiset
(``_MultisetState``): a row's insertion or retraction costs O(log n)
comparisons in a group of n, its sort key is computed once, when it comes,
and ``emit`` reads the kept order: O(1) for an extreme, one C pass for a
tuple of the whole group, nothing where the group did not change. Nothing
walks the group at ``emit`` but a group under ``_ORDER_FROM`` entries or
read for the first time, where the walk is the cheaper way, and one whose
values cannot be ordered; ``rederived`` counts the walks that cost (and the
restores).

Each reducer is a factory producing per-group state objects with
``add(values, diff)`` and ``emit() -> value``.
"""

from __future__ import annotations

import math
from bisect import bisect_left
from itertools import chain
from operator import itemgetter
from typing import Any, Callable

import numpy as np

from pathway_tpu.engine.delta import row_fingerprint


class ReducerState:
    # slots that hold user callables (never serialized: a snapshot must
    # stay data-only for the restricted unpickler; fresh construction
    # re-binds them from the reducer spec)
    _CALLABLE_SLOTS = ("fn", "emit_fn")
    # slots that hold what load_state derives again from the rest
    _DERIVED_SLOTS: tuple = ()

    def add(self, args: tuple, diff: int) -> None:
        raise NotImplementedError

    def emit(self) -> Any:
        raise NotImplementedError

    def is_empty(self) -> bool:
        raise NotImplementedError

    def state_dict(self) -> dict:
        """Plain-data snapshot of this state (engine/persistence.py
        operator-state checkpoints): every ``__slots__`` value except the
        user callables and what ``load_state`` derives. Values are plain
        containers/scalars/ndarrays, so the restricted unpickler accepts
        them on restore."""
        out: dict[str, Any] = {}
        for cls in type(self).__mro__:
            for slot in getattr(cls, "__slots__", ()):
                if slot in self._CALLABLE_SLOTS \
                        or slot in self._DERIVED_SLOTS:
                    continue
                out[slot] = getattr(self, slot)
        return out

    def load_state(self, state: dict) -> None:
        """Restore a ``state_dict`` into a freshly-constructed state (the
        factory re-supplied any callables)."""
        for k, v in state.items():
            setattr(self, k, v)


class _CountState(ReducerState):
    __slots__ = ("n",)

    def __init__(self):
        self.n = 0

    def add(self, args, diff):
        self.n += diff

    def emit(self):
        return self.n

    def is_empty(self):
        return self.n == 0


class _SumState(ReducerState):
    __slots__ = ("n", "total")

    def __init__(self):
        self.n = 0
        self.total = 0

    def add(self, args, diff):
        self.n += diff
        v = args[0]
        if v is not None:
            self.total = self.total + diff * v

    def set_total(self, total, count: int) -> None:
        """Device segment-sum tick update (see _ArraySumState.set_total):
        ``total`` already continues this state's prior running total."""
        self.n += count
        self.total = total

    def emit(self):
        return self.total

    def is_empty(self):
        return self.n == 0


class _ArraySumState(ReducerState):
    __slots__ = ("n", "total")

    def __init__(self):
        self.n = 0
        self.total = None

    def add(self, args, diff):
        self.n += diff
        v = np.asarray(args[0])
        if self.total is None:
            self.total = diff * v
        else:
            self.total = self.total + diff * v

    def set_total(self, total, count: int) -> None:
        """Batched tick update from the device segment-sum kernel
        (operators.py ``_device_array_sums``): ``total`` is the NEW
        running total (the kernel was seeded with the prior one), so it
        replaces rather than adds."""
        self.n += count
        self.total = total

    def emit(self):
        return self.total

    def is_empty(self):
        return self.n == 0


class _Order:
    """Sorted keys and, beside each, the value it shows: parallel lists cut
    into chunks of at most ``2 * _CHUNK`` (``maxes`` holds each chunk's last
    key), so that an insertion moves a chunk's tail and not the group's.
    Copies of one key lie next to each other."""

    __slots__ = ("keys", "vals", "maxes")
    _CHUNK = 512

    def __init__(self, keys: list, vals: list):
        n = self._CHUNK
        self.keys = [keys[i:i + n] for i in range(0, len(keys), n)]
        self.vals = [vals[i:i + n] for i in range(0, len(vals), n)]
        self.maxes = [chunk[-1] for chunk in self.keys]

    def insert(self, k, v) -> None:
        keys, maxes = self.keys, self.maxes
        ci = bisect_left(maxes, k)
        if ci == len(keys):
            if not keys:
                keys.append([k])
                self.vals.append([v])
                maxes.append(k)
                return
            ci -= 1
            maxes[ci] = k
        chunk, vals = keys[ci], self.vals[ci]
        j = bisect_left(chunk, k)
        chunk.insert(j, k)
        vals.insert(j, v)
        if len(chunk) > 2 * self._CHUNK:
            half = len(chunk) // 2
            keys.insert(ci + 1, chunk[half:])
            self.vals.insert(ci + 1, vals[half:])
            del chunk[half:], vals[half:]
            maxes.insert(ci, chunk[-1])

    def _find(self, k) -> tuple[int, int]:
        """Where the first copy of ``k`` lies: of the very object that was
        put in."""
        ci = bisect_left(self.maxes, k)
        chunk = self.keys[ci] if ci < len(self.keys) else ()
        j = bisect_left(chunk, k)
        if j == len(chunk) or chunk[j] is not k:
            raise ValueError("entry not where its key says")
        return ci, j

    def remove(self, k) -> None:
        ci, j = self._find(k)
        chunk = self.keys[ci]
        del chunk[j], self.vals[ci][j]
        if not chunk:
            del self.keys[ci], self.vals[ci], self.maxes[ci]
        elif j == len(chunk):
            self.maxes[ci] = chunk[-1]

    def replace(self, k, v) -> None:
        ci, j = self._find(k)
        self.vals[ci][j] = v


class _MultisetState(ReducerState):
    """One ordered multiset of argument tuples; a subclass says how an
    entry is ordered (``_key``), what it shows (``_val``) and how the
    answer is read off the kept order (``_read``).

    The multiset itself is ``counts`` / ``values`` (fingerprint -> net
    count / latest argument tuple), exactly as snapshots have always held
    it; an entry keeps its place in those dicts while its count is
    non-zero, and that place (``seq``) breaks ties the way a stable sort
    over the dicts does. From ``_ORDER_FROM`` entries on the state keeps
    beside it ``_order``: the key of every entry that shows (``count > 0``),
    sorted, and the shown values in the same order; the tuple kinds hold
    an entry once per copy. The key is
    computed once, when the entry enters the order; a later change of its
    count finds it by bisection on the stored key (``_where``).

    Cost, n entries: ``add`` is one fingerprint, one ``_key`` call for a
    new entry, O(log n) comparisons and a C ``memmove`` inside one chunk;
    ``emit`` is O(1) for the extremes, ``any`` and ``unique``, and one C
    pass over the values for the tuple kinds, skipped while nothing
    changed since the last ``emit``.

    While ``_order is None`` no order is kept and ``emit`` walks: it
    recomputes the answer from the multiset (``_walk``; the ordered state
    gives the same answer, or the same exception at the same call), and
    ``add`` touches the multiset alone. That is the cheaper way for a group
    read once (a batch run, a closed window) and for one below
    ``_ORDER_FROM`` entries (the three matches of a query, built, read and
    retracted once, pay for no key they never compare). So the order is
    derived at the second ``emit`` that finds ``_ORDER_FROM`` entries or
    more, the first after a restore. Walking is also the only way where a
    key cannot be ordered: values not mutually comparable, a NaN, an entry
    the bisection no longer finds; such a state tries again when it has
    doubled, or been emptied. ``rederived`` counts what the kept order is
    there to avoid: a restore, and every ``emit`` but the first that walks
    ``_ORDER_FROM`` entries or more. Two values whose tuples collide in
    ``hash`` are one entry, as before.
    """

    _DERIVED_SLOTS = ("rederived", "_order", "_where", "_seq",
                      "_order_from", "_walked", "_emitted")
    __slots__ = ("counts", "values", "n") + _DERIVED_SLOTS

    # entries from which a group keeps its order: where a walk costs what
    # some twenty ordered ``add`` calls cost over plain ones (2-3 us each).
    # An extreme walks an entry in 0.05 us
    _ORDER_FROM = 1024

    def __init__(self):
        self.counts: dict[int, int] = {}
        self.values: dict[int, tuple] = {}
        self.n = 0
        self.rederived = 0
        self._order: _Order | None = None
        self._order_from = self._ORDER_FROM
        self._walked = False   # an emit has walked _ORDER_FROM or more

    # -- what a subclass defines -------------------------------------------
    def _key(self, args: tuple, seq: int):
        """The entry's place in the order (unique: it ends in ``seq`` or
        is the fingerprint), or None for an entry that never shows."""
        raise NotImplementedError

    def _val(self, args: tuple):
        return args[0]

    @staticmethod
    def _copies(count: int) -> int:
        """How often an entry stands in the order: once if it shows at all,
        except where the answer holds a value once per copy."""
        return 1 if count > 0 else 0

    def _read(self, order: _Order):
        raise NotImplementedError

    def _walk(self):
        """The answer recomputed from the whole multiset."""
        raise NotImplementedError

    # -- the multiset ------------------------------------------------------
    def add(self, args, diff):
        self.n += diff
        fp = row_fingerprint(args)
        counts = self.counts
        c0 = counts.get(fp, 0)
        c1 = c0 + diff
        if c1 == 0:
            counts.pop(fp, None)
            self.values.pop(fp, None)
        else:
            counts[fp] = c1
            self.values[fp] = args
        if not counts:
            # emptied: it walks until it has grown, and been read, again
            self._order = None
            self._order_from = self._ORDER_FROM
            self._walked = False
            return
        order = self._order
        if order is None:
            return
        where = self._where
        try:
            if c0 == 0:
                k = self._key(args, self._seq)
                self._seq += 1
                if c1 != 0:
                    where[fp] = k
            elif c1 == 0:
                k = where.pop(fp)
            else:
                k = where[fp]
            if k is None:
                return
            m0 = self._copies(c0)
            m1 = self._copies(c1)
            if not (m0 or m1):
                return
            # the value may be a newer one (1.0 for 1): every copy takes it
            v = self._val(args)
            if m0 == m1 == 1:
                order.replace(k, v)
            else:
                for _ in range(m0):
                    order.remove(k)
                for _ in range(m1):
                    order.insert(k, v)
        except (TypeError, ValueError):
            self._unordered()
            return
        self._emitted = None

    def emit(self):
        if self._order is None:
            if len(self.counts) >= self._order_from and self._walked:
                self._derive_order()
            if self._order is None:
                if len(self.counts) >= self._ORDER_FROM:
                    if self._walked:
                        self.rederived += 1
                    self._walked = True
                return self._walk()
        return self._read(self._order)

    def is_empty(self):
        return self.n == 0

    def load_state(self, state):
        super().load_state(state)
        # fingerprints are hash()-based and string hashes vary with the
        # process hash seed: a snapshot restored in a NEW process must
        # re-key its multiset with THIS process's fingerprints, or later
        # retractions would never find their entries
        counts, values = self.counts, self.values
        self.counts = {}
        self.values = {}
        for fp, args in values.items():
            nfp = row_fingerprint(args)
            self.counts[nfp] = counts[fp]
            self.values[nfp] = args
        # the snapshot holds the multiset alone, in the shape it always had:
        # the order is derived again when the group is next read
        self.rederived += 1
        self._walked = True

    def _derive_order(self) -> None:
        """Build the order from the multiset."""
        self._where = where = {}
        shown = []
        try:
            for seq, (fp, args) in enumerate(self.values.items()):
                k = where[fp] = self._key(args, seq)
                m = self._copies(self.counts[fp]) if k is not None else 0
                if m:
                    shown.append((k, self._val(args), m))
            shown.sort(key=itemgetter(0))
        except (TypeError, ValueError):
            self._unordered()
            return
        self._seq = len(where)
        self._order_from = self._ORDER_FROM
        self._order = _Order([k for k, _, m in shown for _ in range(m)],
                             [v for _, v, m in shown for _ in range(m)])
        self._emitted = None   # the tuple kinds' answer, until a change

    def _unordered(self) -> None:
        self._order = self._where = None
        self._order_from = max(2 * len(self.counts), self._ORDER_FROM)

    def iter_args(self):
        for fp, c in self.counts.items():
            v = self.values[fp]
            for _ in range(max(c, 0)):
                yield v


def _ordered(v):
    """``v``, refused where no order can hold it: a NaN compares false
    with everything, itself included."""
    if v != v:
        raise ValueError("NaN has no place in an order")
    return v


class _MinState(_MultisetState):
    __slots__ = ()

    # of equal values min() and max() both keep the one met first, in the
    # multiset's own order: hence seq ascending here and descending for
    # the states that read the order's last entry
    def _key(self, args, seq):
        return (_ordered(args[0]), seq)

    def _read(self, order):
        return order.vals[0][0] if order.vals else self._walk()

    def _walk(self):
        return min(v[0] for v in self.iter_args())


class _MaxState(_MultisetState):
    __slots__ = ()

    def _key(self, args, seq):
        return (_ordered(args[0]), -seq)

    def _read(self, order):
        return order.vals[-1][-1] if order.vals else self._walk()

    def _walk(self):
        return max(v[0] for v in self.iter_args())


class _ArgMinState(_MinState):
    __slots__ = ()

    # args = (cmp_value, payload); ties broken by payload for determinism
    def _key(self, args, seq):
        return (_ordered(args[0]), _ordered(args[1]), seq)

    def _val(self, args):
        return args[1]

    def _walk(self):
        best = min(self.iter_args(), key=lambda v: (v[0], _orderable(v[1])))
        return best[1]


class _ArgMaxState(_MaxState):
    __slots__ = ()

    def _key(self, args, seq):
        return (_ordered(args[0]), _ordered(args[1]), -seq)

    def _val(self, args):
        return args[1]

    def _walk(self):
        best = max(self.iter_args(), key=lambda v: (v[0], _orderable(v[1])))
        return best[1]


def _orderable(v):
    try:
        return (0, v)
    except Exception:  # pragma: no cover
        return (1, repr(v))


class _UniqueState(_MultisetState):
    __slots__ = ()

    _MANY = "More than one distinct value passed to the unique reducer."

    def _key(self, args, seq):
        return (row_fingerprint((args[0],)), seq)

    def _read(self, order):
        keys = order.keys
        if not keys or keys[0][0][0] != keys[-1][-1][0]:
            raise ValueError(self._MANY)
        return order.vals[-1][-1]

    def _walk(self):
        vals = {row_fingerprint((v[0],)): v[0] for v in self.iter_args()}
        if len(vals) != 1:
            raise ValueError(self._MANY)
        return next(iter(vals.values()))


class _AnyState(_MultisetState):
    __slots__ = ()

    # deterministic pick: smallest fingerprint (reference picks arbitrary
    # but deterministic per worker), of every entry the multiset holds, an
    # early retraction's too
    def _key(self, args, seq):
        return row_fingerprint(args)

    @staticmethod
    def _copies(count):
        return 1 if count else 0

    def _read(self, order):
        return order.vals[0][0] if order.vals else self._walk()

    def _walk(self):
        fp = min(self.counts)
        return self.values[fp][0]


def _sort_place(v, seq):
    k = _sort_key(v)
    if k[0] == 1:
        _ordered(k[1])
    return (k, seq)


class _SortedTupleState(_MultisetState):
    __slots__ = ("skip_nones",)

    # a tuple kind walks an entry in about 1 us: it sorts, with a key
    # function in Python
    _ORDER_FROM = 64

    def __init__(self, skip_nones=False):
        super().__init__()
        self.skip_nones = skip_nones

    def _key(self, args, seq):
        if self.skip_nones and args[0] is None:
            return None
        return _sort_place(args[0], seq)

    @staticmethod
    def _copies(count):
        return count if count > 0 else 0

    def _read(self, order):
        if self._emitted is None:
            self._emitted = tuple(chain.from_iterable(order.vals))
        return self._emitted

    def _walk(self):
        vals = [v[0] for v in self.iter_args()]
        if self.skip_nones:
            vals = [v for v in vals if v is not None]
        return tuple(sorted(vals, key=_sort_key))


class _TupleState(_SortedTupleState):
    """Tuple in insertion-order position — ordered by the sort column (args[1])."""

    __slots__ = ()

    def _key(self, args, seq):
        if self.skip_nones and args[0] is None:
            return None
        return _sort_place(args[1], seq) if len(args) > 1 else (0, seq)

    def _walk(self):
        items = list(self.iter_args())
        items.sort(key=lambda v: _sort_key(v[1]) if len(v) > 1 else 0)
        vals = [v[0] for v in items]
        if self.skip_nones:
            vals = [v for v in vals if v is not None]
        return tuple(vals)


class _NDArrayState(_TupleState):
    __slots__ = ()

    def _read(self, order):
        if self._emitted is None:
            self._emitted = np.array(tuple(chain.from_iterable(order.vals)))
        return self._emitted

    def _walk(self):
        return np.array(super()._walk())


def _sort_key(v):
    if v is None:
        return (0, 0)
    if isinstance(v, (bool, int, float, np.integer, np.floating)):
        return (1, float(v))
    if isinstance(v, str):
        return (2, v)
    if isinstance(v, (tuple, list)):
        # element-wise, not repr: (10, k) must sort after (5, k)
        return (3, tuple(_sort_key(x) for x in v))
    return (4, repr(v))


class _EarliestState(ReducerState):
    """First value by arrival stamp. Insertions arrive as (*vals, stamp);
    retractions arrive as (*vals, None) and cancel the most recent stamp of
    that value (per-value LIFO — the retraction corresponds to an earlier
    insertion of the same value)."""

    __slots__ = ("stamps", "values", "n")

    def __init__(self):
        self.stamps: dict[int, list] = {}   # value-fp -> sorted stamps
        self.values: dict[int, Any] = {}
        self.n = 0

    def add(self, args, diff):
        *vals, stamp = args
        fp = row_fingerprint(tuple(vals))
        self.n += diff
        if diff > 0:
            self.stamps.setdefault(fp, []).append(stamp)
            self.stamps[fp].sort()
            self.values[fp] = vals[0] if vals else None
        else:
            lst = self.stamps.get(fp)
            if lst:
                lst.pop()  # cancel the latest instance of this value
                if not lst:
                    del self.stamps[fp]
                    self.values.pop(fp, None)

    def emit(self):
        best_fp = min(self.stamps, key=lambda fp: self.stamps[fp][0])
        return self.values[best_fp]

    def is_empty(self):
        return self.n <= 0 or not self.stamps

    def load_state(self, state):
        super().load_state(state)
        # same cross-process re-keying as _MultisetState: add() computes
        # fp over the value tuple, so recompute from the stored value
        stamps, values = self.stamps, self.values
        self.stamps = {}
        self.values = {}
        for fp, v in values.items():
            nfp = row_fingerprint((v,))  # add() keys by the 1-value tuple
            self.stamps[nfp] = stamps[fp]
            self.values[nfp] = v


class _LatestState(_EarliestState):
    def emit(self):
        best_fp = max(self.stamps, key=lambda fp: self.stamps[fp][-1])
        return self.values[best_fp]


class _StatefulState(ReducerState):
    """User combine_fn over (state, rows) — reference's StatefulReducer
    (src/engine/reduce.rs Stateful{combine_fn}). Only supports additions;
    retraction raises like the reference does on append-only violation."""

    __slots__ = ("fn", "state", "n", "emit_fn")

    def __init__(self, fn: Callable, emit: Callable | None = None):
        self.fn = fn
        self.state = None
        self.n = 0
        self.emit_fn = emit

    def add(self, args, diff):
        if diff < 0:
            raise ValueError(
                "stateful reducer requires append-only input (got a deletion)"
            )
        self.n += diff
        self.state = self.fn(self.state, [args])

    def emit(self):
        # emit_fn: custom-accumulator result extraction (compute_result in
        # the reference's BaseCustomAccumulator protocol)
        if self.emit_fn is not None:
            return self.emit_fn(self.state)
        return self.state

    def is_empty(self):
        return self.n == 0


class _AvgState(_SumState):
    def emit(self):
        return self.total / self.n if self.n else math.nan


REDUCER_FACTORIES: dict[str, Callable[..., ReducerState]] = {
    "count": _CountState,
    "sum": _SumState,
    "int_sum": _SumState,
    "float_sum": _SumState,
    "array_sum": _ArraySumState,
    "avg": _AvgState,
    "min": _MinState,
    "max": _MaxState,
    "argmin": _ArgMinState,
    "argmax": _ArgMaxState,
    "unique": _UniqueState,
    "any": _AnyState,
    "sorted_tuple": _SortedTupleState,
    "tuple": _TupleState,
    "ndarray": _NDArrayState,
    "earliest": _EarliestState,
    "latest": _LatestState,
    "stateful": _StatefulState,
}


def make_reducer_state(name: str, **kwargs) -> ReducerState:
    return REDUCER_FACTORIES[name](**kwargs)
