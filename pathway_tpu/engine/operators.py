"""Incremental operators over diff-deltas.

Rebuild of the reference engine's operator set (``trait Graph``,
src/engine/graph.rs:664-1007, implemented in src/engine/dataflow.rs). Each
operator consumes consolidated input deltas for one timestamp and emits the
exact output delta — the differential-dataflow contract — but scheduled by a
host-side microbatch loop instead of timely progress tracking. Batched
columnar callables (numpy/XLA) do the per-batch math; there is no per-row
FFI in the hot path.

Conventions:
- every table is keyed: ≤1 live row per key,
- ``step(time, in_deltas)`` is called once per node per timestamp,
- map/filter callables receive ``(keys: list[Pointer], rows: list[tuple])``
  and return batch results (lists / numpy arrays).
"""

from __future__ import annotations

from typing import Any, Callable, Sequence

import numpy as np

from pathway_tpu.engine.delta import (
    Arrangement,
    Delta,
    row_fingerprint,
    upsert_delta,
)
from pathway_tpu.engine.reducers import (REDUCER_FACTORIES, _MultisetState,
                                         _orderable, make_reducer_state)
from pathway_tpu.internals.keys import (Pointer, canonical_shard_value,
                                        hash_values, mix_pointers)


class Exchange:
    """Per-input exchange contracts for sharded execution (reference:
    src/engine/dataflow/shard.rs — keys route to workers by hash; exchange
    pacts on arrange/join/group inputs). A spec is one of:

    - ``None``: no data movement — the input is processed on whichever
      worker currently holds each row (stateless operators),
    - ``Exchange.BY_KEY``: route each entry by its row key,
    - ``Exchange.GATHER``: send everything to worker 0 (operators whose
      state cannot be partitioned, e.g. fixpoint iteration),
    - ``Exchange.BROADCAST``: every worker (and every process, under a
      cluster) sees the complete input delta — the reference's
      ``.broadcast()`` on the external-index data stream
      (operators/external_index.rs:97) and gradual_broadcast's threshold
      stream,
    - a callable ``(key, row) -> routing value``: route by the hash of the
      returned value (join keys, group keys, instances).
    """

    BY_KEY = "by_key"
    GATHER = "gather"
    BROADCAST = "broadcast"


class SnapshotUnsupported(RuntimeError):
    """Raised by ``snapshot_state`` when an operator holds state it cannot
    capture as plain data (e.g. an external index without capture hooks).
    The streaming runtime disables snapshotting for the run — recovery
    falls back to full-WAL replay — instead of writing a checkpoint that
    silently misses state."""


class Operator:
    arity = 1
    # False for ops whose replicas share mutable state (e.g. one device
    # slab): their per-worker steps must not run on the thread pool
    parallel_safe = True
    # True for ops whose step dispatches accelerator work (device-resident
    # index add/search, traceable batch UDFs): with n_workers == 1 and
    # PATHWAY_DEVICE_INFLIGHT >= 2 the scheduler defers this op AND its
    # downstream closure to the device bridge so the next tick's host work
    # overlaps the dispatch (engine/device_bridge.py)
    device_bound = False
    # Consulted only for EXCHANGED inputs (the sharded merge points in
    # graph.py; spec-None inputs always pass through unmerged): False for
    # ops whose step() is exact on unconsolidated input — purely additive
    # state, or exact handling of same-tick insert/retract pairs. Ops
    # whose outputs feed sinks unfused (net-zero pairs would surface as
    # phantom events) keep the default.
    consolidate_inputs = True

    def step(self, time: int, in_deltas: list[Delta]) -> Delta:
        raise NotImplementedError

    def exchange_specs(self) -> list:
        """One exchange spec per input (see Exchange). Default: stateless —
        rows are processed wherever they already live."""
        return [None] * self.arity

    def replicate(self, n: int) -> list["Operator"]:
        """Return n worker replicas of this operator, self as worker 0.

        Must be called before any data has flowed (state empty), so a
        deepcopy clones configuration (closures are shared by reference —
        the copy module treats functions as atomic) with fresh state.
        """
        import copy

        return [self] + [copy.deepcopy(self) for _ in range(n - 1)]

    def on_time_advance(self, time: int) -> Delta:
        """Called for every committed timestamp (even with no input) so
        buffering operators (temporal behaviors) can release rows."""
        return Delta()

    def take_rederived(self) -> int:
        """Times since the last call that a reducer state of this operator
        derived its order from a whole group (engine/reducers.py
        ``_MultisetState.rederived``); the flight recorder sums them."""
        return 0

    def flush(self, time: int) -> Delta:
        """End-of-stream: release anything still held (the reference flushes
        buffers when the input frontier reaches +inf, operators/time_column.rs).
        Only called once, at the final flush tick."""
        return Delta()

    # -- operator-state checkpoints (engine/persistence.py snapshots) ------
    def snapshot_state(self):
        """Plain-data capture of this operator's accumulated state, or
        ``None`` for stateless operators (the default). The returned value
        must decode under the persistence layer's restricted unpickler:
        containers, scalars, ndarrays, Pointers — never classes or
        callables. Called by the Scheduler at a snapshot tick, with every
        device leg <= that tick resolved (state is a consistent cut).
        Raise :class:`SnapshotUnsupported` for state that cannot be
        captured — the runtime then disables snapshots loudly."""
        return None

    def restore_state(self, state) -> None:
        """Inverse of :meth:`snapshot_state`, called on a freshly-built
        operator before any data flows."""
        raise SnapshotUnsupported(
            f"{type(self).__name__} recorded no snapshot hook but a "
            "snapshot carries state for it — the graph changed between "
            "runs, or the snapshot is foreign")


class SourceOperator(Operator):
    """Fed externally by an input session; just passes its delta through."""

    arity = 0

    def __init__(self, name: str = "source"):
        self.name = name
        self.pending = Delta()

    def push(self, delta: Delta) -> None:
        self.pending.extend(delta.entries)

    def step(self, time, in_deltas):
        # consolidation here is load-bearing: a same-batch net-zero
        # (key,row) pair must cancel BEFORE operators/sinks see it —
        # order-sensitive reducers would otherwise record deleted values,
        # float sums drift, and sinks emit phantom insert/delete events
        out = self.pending.consolidate()
        self.pending = Delta()
        return out


class MapOperator(Operator):
    """Row-wise (batched) projection: select / expression tables
    (reference: expression_table, dataflow.rs:1258)."""

    def __init__(self, fn: Callable[[list, list], list]):
        self.fn = fn

    def step(self, time, in_deltas):
        delta = in_deltas[0]
        if not delta:
            return Delta()
        keys = delta.keys_list()
        rows = [r for _, r, _ in delta.entries]
        new_rows = self.fn(keys, rows)
        # contract: fn returns one TUPLE per row (compile_program and the
        # lowering's projections all do) — re-tupling was pure overhead
        return Delta([
            (k, nr, d)
            for (k, _, d), nr in zip(delta.entries, new_rows)
        ])


class ZipAlignedOperator(Operator):
    """Stateless zip of two 1:1 projections of the SAME upstream delta.

    Built by the lowering's auto-jit host/device map split
    (internals/runner.py): both inputs are MapOperators over one input
    node, so each tick they emit the same keys with the same diffs in the
    same order — the recombination needs no arrangements, just a
    positional merge per the column spec ((side, pos), ...) with side 0 =
    left row, 1 = right row. Alignment is asserted, not assumed: a key or
    diff mismatch means an engine invariant broke, and wrong-but-plausible
    output would be strictly worse than a crash."""

    arity = 2

    def __init__(self, spec: tuple):
        self.spec = tuple(spec)
        # the merge runs per row on the hot path: compile it once to a
        # C-level tuple build instead of interpreting the spec per cell
        cells = ", ".join(f"{'l' if side == 0 else 'r'}[{pos}]"
                          for side, pos in self.spec)
        self._combine = eval(  # noqa: S307 — generated from the int spec
            f"lambda l, r: ({cells}{',' if self.spec else ''})")

    def step(self, time, in_deltas):
        dl, dr = in_deltas
        if not dl and not dr:
            return Delta()
        if len(dl.entries) != len(dr.entries):
            raise RuntimeError(
                "auto-jit map split lost alignment: "
                f"{len(dl.entries)} host rows vs {len(dr.entries)} device "
                "rows in one tick")
        combine = self._combine
        out = []
        for (lk, lrow, ld), (rk, rrow, rd) in zip(dl.entries, dr.entries):
            if lk != rk or ld != rd:
                raise RuntimeError(
                    "auto-jit map split lost alignment: "
                    f"({lk!r}, {ld}) vs ({rk!r}, {rd})")
            out.append((lk, combine(lrow, rrow), ld))
        return Delta(out)


def _stable_row_fp(row: tuple) -> int:
    """Cross-process-stable row digest (hash_values: fixed blake2b salt)
    for cache keys that must survive a snapshot restore into a NEW
    interpreter — hash()-based row_fingerprint varies with the process
    hash seed for string cells. Costlier than hash() per novel row
    (hash_values memoizes repeats), but this keys only
    DeterministicMapOperator, which exists to cache NON-deterministic
    user fns — a path already dominated by the fn call itself; re-keying
    at restore (the cheaper pattern used for multiset reducers) is
    impossible here because the cache does not retain input rows."""
    return int(hash_values(*row))


class DeterministicMapOperator(MapOperator):
    """Map that caches outputs per key so retractions replay identical values
    even for non-deterministic fns (reference:
    map_named_with_consistent_deletions, dataflow/operators.rs:308)."""

    def __init__(self, fn):
        super().__init__(fn)
        self.cache: dict[tuple[Pointer, int], tuple] = {}

    def snapshot_state(self):
        # the cache IS semantics: retractions after restore must replay
        # the exact values the non-deterministic fn produced pre-crash.
        # Keys use the stable fingerprint, so they survive as-is.
        return {"cache": self.cache}

    def restore_state(self, state) -> None:
        self.cache = dict(state["cache"])

    def step(self, time, in_deltas):
        delta = in_deltas[0]
        if not delta:
            return Delta()
        out = Delta()
        to_eval = []
        for key, row, diff in delta.entries:
            ck = (key, _stable_row_fp(row))
            if diff < 0 and ck in self.cache:
                out.append(key, self.cache.pop(ck), diff)
            else:
                to_eval.append((key, row, diff, ck))
        if to_eval:
            keys = [k for k, _, _, _ in to_eval]
            rows = [r for _, r, _, _ in to_eval]
            new_rows = self.fn(keys, rows)
            for (key, _, diff, ck), nr in zip(to_eval, new_rows):
                nr = tuple(nr)
                if diff > 0:
                    self.cache[ck] = nr
                out.append(key, nr, diff)
        return out


class FilterOperator(Operator):
    def __init__(self, pred: Callable[[list, list], Sequence[bool]]):
        self.pred = pred

    def step(self, time, in_deltas):
        delta = in_deltas[0]
        if not delta:
            return Delta()
        keys = delta.keys_list()
        rows = [r for _, r, _ in delta.entries]
        mask = self.pred(keys, rows)
        return Delta([e for e, m in zip(delta.entries, mask) if m])


class ReindexOperator(Operator):
    """Re-key rows (with_id_from / reindex). New key computed from the row;
    collisions on the new key are a user error (like reference)."""

    def __init__(self, key_fn: Callable[[list, list], Sequence[Pointer]]):
        self.key_fn = key_fn

    def step(self, time, in_deltas):
        delta = in_deltas[0]
        if not delta:
            return Delta()
        keys = delta.keys_list()
        rows = [r for _, r, _ in delta.entries]
        new_keys = self.key_fn(keys, rows)
        return Delta([
            (nk, r, d) for (k, r, d), nk in zip(delta.entries, new_keys)
        ]).consolidate()


class FlattenOperator(Operator):
    """One row -> many rows (Table.flatten). fn(key,row) yields (new_key,new_row)."""

    def __init__(self, fn: Callable[[Pointer, tuple], list]):
        self.fn = fn

    def step(self, time, in_deltas):
        delta = in_deltas[0]
        out = Delta()
        for key, row, diff in delta.entries:
            for nk, nr in self.fn(key, row):
                out.append(nk, tuple(nr), diff)
        return out.consolidate()


class BinaryKeyOperator(Operator):
    """Generic key-aligned binary combiner.

    Covers concat/update_rows/intersect/difference/restrict/having and
    same-universe column zipping: maintains both input arrangements, and for
    every affected key recomputes ``combine(left_row|None, right_row|None)``
    before and after the delta, emitting the difference. This is the
    host analogue of DD's arrange-both-sides + per-key recompute
    (reference: concat/update_rows via engine union ops, dataflow.rs).
    """

    arity = 2

    def __init__(self, combine: Callable[[tuple | None, tuple | None], tuple | None]):
        self.combine = combine
        self.left = Arrangement()
        self.right = Arrangement()

    def exchange_specs(self):
        return [Exchange.BY_KEY, Exchange.BY_KEY]

    def snapshot_state(self):
        return {"left": self.left.rows, "right": self.right.rows}

    def restore_state(self, state) -> None:
        self.left.rows = dict(state["left"])
        self.right.rows = dict(state["right"])

    def step(self, time, in_deltas):
        dl, dr = in_deltas
        if not dl and not dr:
            return Delta()
        affected: dict[Pointer, None] = {}
        for k, _, _ in dl.entries:
            affected[k] = None
        for k, _, _ in dr.entries:
            affected[k] = None
        old_out: dict[Pointer, tuple | None] = {}
        for k in affected:
            old_out[k] = self.combine(self.left.get(k), self.right.get(k))
        self.left.update(dl)
        self.right.update(dr)
        out = Delta()
        for k in affected:
            new = self.combine(self.left.get(k), self.right.get(k))
            old = old_out[k]
            if old is not None and (new is None or
                                    row_fingerprint(old) != row_fingerprint(new)):
                out.append(k, old, -1)
            if new is not None and (old is None or
                                    row_fingerprint(old) != row_fingerprint(new)):
                out.append(k, new, 1)
        return out


class NAryConcatOperator(Operator):
    """Disjoint-key union of N inputs (Table.concat). Raises on key overlap
    unless ``update`` (last input wins — update_rows semantics)."""

    def __init__(self, n: int, combine_rows: Callable[[list], tuple | None],
                 update: bool = False):
        self.arity = n
        self.states = [Arrangement() for _ in range(n)]
        self.combine_rows = combine_rows
        self.update = update

    def exchange_specs(self):
        return [Exchange.BY_KEY] * self.arity

    def snapshot_state(self):
        return {"states": [st.rows for st in self.states]}

    def restore_state(self, state) -> None:
        for st, rows in zip(self.states, state["states"]):
            st.rows = dict(rows)

    def step(self, time, in_deltas):
        if not any(in_deltas):
            return Delta()
        affected: dict[Pointer, None] = {}
        for d in in_deltas:
            for k, _, _ in d.entries:
                affected[k] = None
        old = {k: self._combined(k) for k in affected}
        for st, d in zip(self.states, in_deltas):
            st.update(d)
        out = Delta()
        for k in affected:
            new = self._combined(k)
            o = old[k]
            if o is not None and (new is None or row_fingerprint(o) != row_fingerprint(new)):
                out.append(k, o, -1)
            if new is not None and (o is None or row_fingerprint(o) != row_fingerprint(new)):
                out.append(k, new, 1)
        return out

    def _combined(self, key):
        present = [st.get(key) for st in self.states]
        live = [r for r in present if r is not None]
        if not live:
            return None
        if len(live) > 1 and not self.update:
            raise KeyError(
                f"duplicate key {key!r} in concat of tables with overlapping "
                "universes; use update_rows or concat_reindex"
            )
        return self.combine_rows(present)


_ARRAY_SUM_DEVICE_MIN: int | None = None
# ticks smaller than this skip the device pre-pass outright (not worth
# the per-entry extract scan); tests lower it to exercise sharded runs
_ARRAY_SUM_MIN_ROWS = 64


def _array_sum_device_min() -> int:
    """Element-count threshold above which a tick's array_sum rows route
    through the XLA segment-sum kernel instead of per-row numpy adds
    (PATHWAY_ARRAY_SUM_DEVICE_MIN; 0 disables the device path)."""
    global _ARRAY_SUM_DEVICE_MIN
    if _ARRAY_SUM_DEVICE_MIN is None:
        import os

        _ARRAY_SUM_DEVICE_MIN = int(os.environ.get(
            "PATHWAY_ARRAY_SUM_DEVICE_MIN", 1 << 20))
    return _ARRAY_SUM_DEVICE_MIN


def _next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1)).bit_length()


_SEGSUM_FN = None


def _device_segsum_fn():
    """Jitted sequential segment-sum: input (G, M, D) of diff-weighted
    rows (row m of group g, zero-padded past the group's length), output
    (G, D) per-group totals.

    The reduction is a ``lax.scan`` over the M axis — per group, rows
    accumulate one at a time IN ORDER, exactly like the per-row numpy
    path (``total = total + diff * v``). Zero padding is exact under IEEE
    addition, so the result is BITWISE-identical to the sequential host
    loop — the device path does not weaken the n_workers ∈ {1, N}
    byte-identity contract the lowering's canonical sort establishes.
    (A plain one-hot matmul or ``segment_sum`` would be faster but
    reassociates the adds, making results depend on batch shape.)
    """
    global _SEGSUM_FN
    if _SEGSUM_FN is None:
        import jax
        import jax.numpy as jnp

        @jax.jit
        def segsum(padded, init):
            def body(acc, rows):
                return acc + rows, None

            acc, _ = jax.lax.scan(body, init,
                                  jnp.moveaxis(padded, 1, 0))
            return acc

        _SEGSUM_FN = segsum
    return _SEGSUM_FN


class GroupByOperator(Operator):
    """groupby().reduce() (reference: group_by_table, dataflow.rs:2904).

    ``group_fn(key,row) -> (group_key, group_vals)`` routes each input row to
    a group; ``reducer_specs`` is a list of
    ``(name, extract(key,row)->argtuple, kwargs)``. Emits per changed group a
    retraction of the old reduced row and the new one.
    """

    # a counter the flight recorder drains, no part of the state
    _snapshot_sanitizer_exempt = ("rederived",)

    def __init__(self, group_fn, reducer_specs,
                 force_order_sensitive: bool = False):
        self.group_fn = group_fn
        self.reducer_specs = reducer_specs
        self.rederived = 0
        self._multiset_idx = [
            i for i, (name, _, _) in enumerate(reducer_specs)
            if issubclass(REDUCER_FACTORIES[name], _MultisetState)]
        self.group_states: dict[Pointer, list] = {}   # gkey -> [states...]
        self.group_vals: dict[Pointer, tuple] = {}
        self.group_counts: dict[Pointer, int] = {}    # membership multiset size
        self.out = Arrangement()
        self.seq = 0
        # all other reducers are commutative multisets/semigroups — the
        # canonical sort below is pure overhead for them. The lowering
        # forces the sort for float sums (addition not associative: the
        # n_workers ∈ {1, N} identity contract needs a canonical order)
        self._order_sensitive = force_order_sensitive or any(
            name in ("earliest", "latest", "stateful")
            for name, _, _ in reducer_specs)
        # "sum" included: an ndarray-typed column summed via the plain
        # sum() reducer hits the same device path (the first-row probe
        # rejects scalar sums cheaply)
        self._array_sum_idx = [i for i, (name, _, _)
                               in enumerate(reducer_specs)
                               if name in ("array_sum", "sum")]

    def _device_array_sums(self, entries, routed):
        """Per-tick batched array_sum: one XLA dispatch per reducer for
        the whole tick instead of one numpy add per row (the reference
        keeps ndarray values on the CPU engine, src/engine/reduce.rs
        ArraySum; a TPU-first engine routes embedding-sized columns
        through the device). Returns {reducer_idx: {gkey: (total, count)}}
        for the reducers it handled; unhandled ones (mixed shapes,
        non-f32 dtypes, too small to pay for a dispatch) fall back to the
        per-row path."""
        threshold = _array_sum_device_min()
        if threshold <= 0:
            return {}
        handled: dict[int, dict] = {}
        for idx in self._array_sum_idx:
            name, extract, _kw = self.reducer_specs[idx]
            # probe the first row before scanning the whole tick: the
            # element count is already decidable from one row's shape
            first = np.asarray(extract(*entries[0][:2])[0])
            shape = first.shape
            if not shape:
                # scalar sum() column: the per-row path returns np.float32
                # scalars; the device path would emit 0-d ndarrays and the
                # output column's type would depend on tick size
                continue
            d = int(np.prod(shape))
            if first.dtype != np.float32 or len(entries) * d < threshold:
                continue
            arrs = [first]
            ok = True
            for key, row, _diff in entries[1:]:
                a = np.asarray(extract(key, row)[0])
                if a.dtype != np.float32 or a.shape != shape:
                    ok = False
                    break
                arrs.append(a)
            if not ok:
                continue
            try:
                import jax.numpy as jnp
            except Exception:  # pragma: no cover - jax always present
                return {}
            # rows per group, in entry order (canonically sorted by the
            # caller when float — accumulation order is part of the
            # byte-identity contract)
            group_rows: dict[Pointer, list[int]] = {}
            counts: dict[Pointer, int] = {}
            for i, (key, row, diff) in enumerate(entries):
                gkey = routed[i][0]
                group_rows.setdefault(gkey, []).append(i)
                counts[gkey] = counts.get(gkey, 0) + diff
            gkeys = list(group_rows)
            # a prior running total that is not float32 (e.g. float64 rows
            # accumulated by earlier small ticks) must keep its dtype —
            # fall back to the per-row path for this reducer
            priors = {}
            ok = True
            for gkey in gkeys:
                states = self.group_states.get(gkey)
                prior = states[idx].total if states is not None else None
                if prior is not None:
                    prior = np.asarray(prior)
                    if prior.dtype != np.float32 or prior.shape != shape:
                        ok = False
                        break
                priors[gkey] = prior
            if not ok:
                continue
            m_b = _next_pow2(max(len(v) for v in group_rows.values()))
            g_b = _next_pow2(len(gkeys))
            # pad with -0.0, the exact IEEE additive identity
            # (x + -0.0 == x bitwise for every x INCLUDING -0.0, whereas
            # x + 0.0 flips a -0.0 total to +0.0) — padding and seeding
            # must not perturb the byte-identity contract
            padded = np.full((g_b, m_b, d), -0.0, dtype=np.float32)
            # seed the scan with each group's RUNNING total: the kernel
            # then continues the exact sequential accumulation
            # ((T + v_a) + v_b), not T + (v_a + v_b) — reassociating
            # across the tick boundary would drift from the numpy path.
            # Fresh-group seed mirrors each state's numpy start exactly:
            # _ArraySumState begins at diff*v (no addition — seed -0.0,
            # the identity), _SumState begins at int 0 + diff*v (seed
            # +0.0, so a -0.0 first value flips to +0.0 as numpy does)
            fresh_zero = np.float32(-0.0 if name == "array_sum" else 0.0)
            init = np.full((g_b, d), fresh_zero, dtype=np.float32)
            for g, gkey in enumerate(gkeys):
                if priors[gkey] is not None:
                    init[g] = priors[gkey].reshape(-1)
                for p, i in enumerate(group_rows[gkey]):
                    diff = entries[i][2]
                    row_vec = arrs[i].reshape(-1)
                    padded[g, p] = row_vec if diff == 1 else diff * row_vec
            totals = np.asarray(_device_segsum_fn()(
                jnp.asarray(padded), jnp.asarray(init)))
            handled[idx] = {
                gkey: (totals[g].reshape(shape), counts[gkey])
                for g, gkey in enumerate(gkeys)}
        return handled

    def exchange_specs(self):
        # route rows to the worker owning their group (reference: group_by
        # exchanges by group key, dataflow.rs:2904)
        return [lambda key, row: self.group_fn(key, row)[0]]

    def snapshot_state(self):
        return {
            "groups": {gkey: [st.state_dict() for st in states]
                       for gkey, states in self.group_states.items()},
            "vals": self.group_vals,
            "counts": self.group_counts,
            "out": self.out.rows,
            "seq": self.seq,
        }

    def restore_state(self, state) -> None:
        self.group_states = {}
        for gkey, dicts in state["groups"].items():
            states = [make_reducer_state(name, **kw)
                      for name, _, kw in self.reducer_specs]
            for st, d in zip(states, dicts):
                st.load_state(d)
            self._collect_rederived(states)
            self.group_states[gkey] = states
        self.group_vals = dict(state["vals"])
        self.group_counts = dict(state["counts"])
        self.out.rows = dict(state["out"])
        self.seq = state["seq"]

    def _collect_rederived(self, states) -> None:
        for i in self._multiset_idx:
            st = states[i]
            if st.rederived:
                self.rederived += st.rederived
                st.rederived = 0

    def take_rederived(self):
        n, self.rederived = self.rederived, 0
        return n

    def step(self, time, in_deltas):
        delta = in_deltas[0]
        if not delta:
            return Delta()
        touched: dict[Pointer, None] = {}
        # canonical per-tick order (key, then retractions-first, then row):
        # order-sensitive reducers (earliest/latest stamps, stateful folds)
        # must not depend on arrival order, which sharded exchange permutes —
        # with a canonical order, n_workers ∈ {1, N} give identical results
        if self._order_sensitive:
            entries = sorted(
                delta.entries,
                key=lambda e: (int(e[0]), e[2], row_fingerprint(e[1])))
        else:
            entries = delta.entries
        routed = None
        device_sums: dict[int, dict] = {}
        if self._array_sum_idx and len(entries) >= _ARRAY_SUM_MIN_ROWS:
            routed = [self.group_fn(key, row) for key, row, _ in entries]
            device_sums = self._device_array_sums(entries, routed)
        for i, (key, row, diff) in enumerate(entries):
            gkey, gvals = routed[i] if routed is not None \
                else self.group_fn(key, row)
            states = self.group_states.get(gkey)
            if states is None:
                states = [make_reducer_state(name, **kw)
                          for name, _, kw in self.reducer_specs]
                self.group_states[gkey] = states
                self.group_vals[gkey] = gvals
                self.group_counts[gkey] = 0
            self.group_counts[gkey] += diff
            for ri, (st, (name, extract, _kw)) in enumerate(
                    zip(states, self.reducer_specs)):
                if ri in device_sums:
                    continue  # whole tick pre-summed on device below
                args = extract(key, row)
                if name in ("earliest", "latest"):
                    if diff > 0:
                        args = (*args, (time, self.seq))
                        self.seq += 1
                    else:
                        args = (*args, None)
                st.add(args, diff)
            touched[gkey] = None
        for ri, per_group in device_sums.items():
            for gkey, (total, count) in per_group.items():
                self.group_states[gkey][ri].set_total(total, count)
        out = Delta()
        rows = self.out.rows
        for gkey in touched:
            states = self.group_states[gkey]
            cur = rows.get(gkey)
            if self.group_counts.get(gkey, 0) <= 0:
                del self.group_states[gkey]
                self.group_vals.pop(gkey, None)
                self.group_counts.pop(gkey, None)
                if cur is not None:
                    out.append(gkey, cur, -1)
                    del rows[gkey]
                continue
            gvals = self.group_vals[gkey]
            new_row = (*gvals, *[st.emit() for st in states])
            self._collect_rederived(states)
            # compared by value, first difference first: a row may hold a
            # tuple of the whole group, which a fingerprint would walk
            if cur is not None:
                if _rows_equal(cur, new_row):
                    continue
                out.append(gkey, cur, -1)
            out.append(gkey, new_row, 1)
            rows[gkey] = new_row
        return out


_FASTJOIN = False  # False = not probed, None = unavailable, module = loaded
_FASTGROUP = False


def _get_fastjoin():
    """Native inner-join pass (native/fastjoin.cpp), built on first use;
    None when the toolchain is unavailable (pure-Python fallback)."""
    global _FASTJOIN
    if _FASTJOIN is False:
        try:
            from pathway_tpu.native.build import load_extension

            _FASTJOIN = load_extension("fastjoin")
        except Exception as e:
            import logging

            logging.getLogger("pathway_tpu").warning(
                "native join fast path unavailable (%s); using the "
                "pure-Python engine loops", e)
            _FASTJOIN = None
    return _FASTJOIN


def _get_fastgroup():
    """Native groupby gather/emit passes (native/fastgroup.cpp)."""
    global _FASTGROUP
    if _FASTGROUP is False:
        try:
            from pathway_tpu.native.build import load_extension

            _FASTGROUP = load_extension("fastgroup")
        except Exception as e:
            import logging

            logging.getLogger("pathway_tpu").warning(
                "native groupby fast path unavailable (%s); using the "
                "pure-Python engine loops", e)
            _FASTGROUP = None
    return _FASTGROUP


def _rows_equal(a, b) -> bool:
    """Value equality of two rows; fingerprint fallback for rows whose
    cells don't support plain == (ndarrays)."""
    try:
        return bool(a == b)
    except Exception:
        return row_fingerprint(a) == row_fingerprint(b)


class ColumnarGroupByOperator(Operator):
    """Columnar groupby for dictionary-encodable group keys with
    semigroup-sum reducers (count / integral sum / integral avg).

    The row path (GroupByOperator) pays per-row Python: a 128-bit hash per
    row for the group key plus a dict probe and a state-object method call
    per reducer. Here a tick's delta is processed as arrays: group values
    are interned to dense int codes (one dict probe per row, no hashing —
    the group key is hashed ONCE per distinct group ever seen), reducer
    state lives in numpy int64 arrays indexed by code (``np.add.at``
    scatter), and only the touched groups pay per-group Python at emit.
    Exact-retraction semantics are unchanged: all state updates are
    additive, so arbitrary insert/retract orders give identical state.

    Chosen by the lowering only when every reducer is in the columnar set,
    no reducer is order-sensitive, and the group values come from plain
    columns of hashable scalar dtype (internals/runner.py
    ``_columnar_groupby_spec``); everything else keeps GroupByOperator.
    Reference analogue: group_by_table (src/engine/dataflow.rs:2904).
    """

    _GROW = 1024
    _INT_GUARD = 1 << 62  # |sum| beyond this migrates to exact python ints
    consolidate_inputs = False  # purely additive array state

    # derived interning tables (typed-key and hashed-key -> dense code):
    # deliberately outside the snapshot — restore_state rebuilds them
    # from _gvals/_gkeys exactly as _codes constructs them, so the
    # coverage sanitizer must not demand their capture
    _snapshot_sanitizer_exempt = ("_intern", "_by_gkey")

    def __init__(self, gval_pos: list, reducer_cols: list):
        # gval_pos: row positions of the group-value columns
        # reducer_cols: [("count", None) | ("sum"|"avg"|"min"|"max", pos)]
        self.gval_pos = list(gval_pos)
        self.reducer_cols = list(reducer_cols)
        # (slot, code) -> exact python-int total for groups whose sums
        # left the int64 guard range (row-path _SumState is bigint-exact)
        self._big: dict = {}
        self._intern: dict = {}          # typed gval -> dense code
        self._by_gkey: dict = {}         # hashed gkey -> code (alias dedup)
        self._gvals: list[tuple] = []    # code -> group values
        self._gkeys: list[Pointer] = []  # code -> output key (hashed once)
        self._last: list = []            # code -> last emitted row | None
        self._cnt = np.zeros(0, np.int64)
        # value-bearing reducers share one extraction slot order (the C
        # gather returns one column per _val_pos entry; -1 extracts the
        # row key); sums/avgs additionally own an int64 state array,
        # min/max/argmin/argmax a per-group value-count multiset (exact
        # under retraction)
        self._val_slot: dict[int, int] = {}   # reducer -> cmp/value slot
        self._arg_slot: dict[int, int] = {}   # argminmax -> payload slot
        self._sum_slot: dict[int, int] = {}
        self._mm: dict[int, dict] = {}   # reducer idx -> {code: {val: n}}
        val_pos: list[int] = []
        for i, (kind, pos) in enumerate(reducer_cols):
            if kind == "count":
                continue
            if kind in ("argmin", "argmax"):
                cpos, ppos = pos
                self._val_slot[i] = len(val_pos)
                val_pos.append(cpos)
                self._arg_slot[i] = len(val_pos)
                val_pos.append(ppos)
                self._mm[i] = {}
                continue
            self._val_slot[i] = len(val_pos)
            val_pos.append(pos)
            if kind in ("sum", "avg"):
                self._sum_slot[i] = len(self._sum_slot)
            else:  # min / max
                self._mm[i] = {}
        self._sums = [np.zeros(0, np.int64) for _ in self._sum_slot]
        # native-pass parameter tables (see native/fastgroup.cpp)
        self._gp = tuple(self.gval_pos)
        self._val_pos = tuple(val_pos)
        self._kinds = tuple(
            0 if kind == "count" else (2 if kind == "avg" else 1)
            for kind, _ in reducer_cols)

    def exchange_specs(self):
        # route by the CANONICAL group value: the scheduler's route cache
        # memoizes value -> worker (a dict probe instead of a hash per
        # row), and canonicalization guarantees hash-equal values (1 vs
        # 1.0 vs np.int64(1) — which _add_group aliases into one group)
        # land on the same worker. Tuples route through hash_values, whose
        # encoding collapses the same equivalences element-wise.
        if len(self.gval_pos) == 1:
            p = self.gval_pos[0]
            return [lambda key, row: canonical_shard_value(row[p])]
        ps = self.gval_pos
        return [lambda key, row: tuple(row[p] for p in ps)]

    def snapshot_state(self):
        n = len(self._gvals)
        return {
            "gvals": self._gvals,
            "gkeys": self._gkeys,
            "last": self._last,
            "cnt": self._cnt[:n].copy(),
            "sums": [s[:n].copy() for s in self._sums],
            "big": self._big,
            "mm": self._mm,
        }

    def restore_state(self, state) -> None:
        self._gvals = [tuple(g) for g in state["gvals"]]
        self._gkeys = list(state["gkeys"])
        self._last = list(state["last"])
        n = len(self._gvals)
        self._cnt = np.asarray(state["cnt"], np.int64).copy()
        self._sums = [np.asarray(s, np.int64).copy() for s in state["sums"]]
        self._big = dict(state["big"])
        for i in self._mm:
            self._mm[i] = {c: dict(g)
                           for c, g in state["mm"].get(i, {}).items()}
        # the interning tables hold CLASS objects (typed keys) — never
        # serialized; rebuilt from the group values exactly as _codes
        # constructs them
        self._intern = {}
        self._by_gkey = {}
        for code in range(n):
            gvals = self._gvals[code]
            self._by_gkey[self._gkeys[code]] = code
            if len(self.gval_pos) == 1:
                v = gvals[0]
                tk = (v.__class__, v)
            else:
                tk = (tuple(v.__class__ for v in gvals), gvals)
            self._intern[tk] = code

    def _add_group(self, tkey, gvals: tuple) -> int:
        # alias via the hashed key: distinct typed representations of
        # hash-equal values (1 vs 1.0, np.int64(5) vs 5) must share a
        # group, exactly as the row path's hash_values keying does
        gkey = hash_values(*gvals)
        code = self._by_gkey.get(gkey)
        if code is not None:
            self._intern[tkey] = code
            return code
        code = len(self._gvals)
        self._intern[tkey] = code
        self._by_gkey[gkey] = code
        self._gvals.append(gvals)
        self._gkeys.append(gkey)
        self._last.append(None)
        if code >= self._cnt.shape[0]:
            self._cnt = np.concatenate(
                [self._cnt, np.zeros(self._GROW, np.int64)])
            self._sums = [np.concatenate([s, np.zeros(self._GROW, np.int64)])
                          for s in self._sums]
        return code

    def _codes(self, entries) -> np.ndarray:
        intern = self._intern
        get = intern.get
        add = self._add_group
        codes = np.empty(len(entries), np.int64)
        if len(self.gval_pos) == 1:
            p = self.gval_pos[0]
            for i, (_k, row, _d) in enumerate(entries):
                v = row[p]
                # typed key: bool-vs-int dict equality (True == 1) must not
                # merge groups the hash path keeps distinct
                tk = (v.__class__, v)
                c = get(tk)
                codes[i] = add(tk, (v,)) if c is None else c
        else:
            ps = self.gval_pos
            for i, (_k, row, _d) in enumerate(entries):
                gvals = tuple(row[p] for p in ps)
                tk = (tuple(v.__class__ for v in gvals), gvals)
                c = get(tk)
                codes[i] = add(tk, gvals) if c is None else c
        return codes

    def step(self, time, in_deltas):
        entries = in_deltas[0].entries
        if not entries:
            return Delta()
        n = len(entries)
        fg = _get_fastgroup()
        cols = None
        if fg is not None:
            codes_l, diffs_l, cols = fg.gather(
                entries, self._intern, self._add_group, self._gp,
                self._val_pos)
            codes = np.asarray(codes_l, np.int64)
            diffs = np.asarray(diffs_l, np.int64)
        else:
            codes = self._codes(entries)
            diffs = np.fromiter((e[2] for e in entries), np.int64, n)
        np.add.at(self._cnt, codes, diffs)
        touched = np.unique(codes)
        guard = self._INT_GUARD
        # min/max/argmin/argmax multisets: one dict update per entry
        # (exact retraction)
        for i, groups in self._mm.items():
            kind, pos = self.reducer_cols[i]
            if kind in ("argmin", "argmax"):
                cpos, ppos = pos
                if cols is not None:
                    cvals = cols[self._val_slot[i]]
                    pvals = cols[self._arg_slot[i]]
                else:
                    cvals = [e[1][cpos] for e in entries]
                    pvals = [e[0] if ppos < 0 else e[1][ppos]
                             for e in entries]
                vals = list(zip(cvals, pvals))
            else:
                vals = cols[self._val_slot[i]] if cols is not None else \
                    [e[1][pos] for e in entries]
            for c, v, d in zip(codes.tolist(), vals, diffs.tolist()):
                g = groups.get(c)
                if g is None:
                    g = groups[c] = {}
                nc = g.get(v, 0) + d
                if nc == 0:
                    del g[v]
                else:
                    g[v] = nc
        for i, slot in self._sum_slot.items():
            pos = self.reducer_cols[i][1]
            arr = self._sums[slot]
            vals = cols[self._val_slot[i]] if cols is not None else \
                [e[1][pos] for e in entries]
            try:
                col = np.asarray(vals, np.int64)
                # bound the whole tick's contribution so the int64 scatter
                # cannot wrap before the migration check runs
                fast = bool(np.abs(col).max(initial=0) < guard // (n + 1))
            except (TypeError, ValueError, OverflowError):
                fast = False  # None / non-int / giant cells
            if fast:
                np.add.at(arr, codes, col * diffs)
                if self._big:
                    # groups already migrated to exact python ints track
                    # their tick contribution here (their arr slot is dead)
                    big = self._big
                    for j, c in enumerate(codes.tolist()):
                        bk = (slot, c)
                        cur = big.get(bk)
                        if cur is not None:
                            big[bk] = cur + int(col[j]) * int(diffs[j])
                # inputs bounded by the guard and prior totals inside it,
                # so no wrap happened yet; migrate any group that just
                # left the guard range to exact python-int accumulation
                mx = self._sums[slot][touched]
                if np.abs(mx).max(initial=0) >= guard:
                    for c in touched[np.abs(mx) >= guard].tolist():
                        self._big.setdefault((slot, c), int(arr[c]))
            else:
                # exact slow path (mirrors _SumState: bigint, None adds
                # nothing); groups cross into _big when they outgrow int64
                big = self._big
                for c, v, d in zip(codes.tolist(), vals, diffs.tolist()):
                    if v is None:
                        continue
                    bk = (slot, c)
                    cur = big.get(bk)
                    if cur is not None:
                        big[bk] = cur + d * int(v)
                        continue
                    total = int(arr[c]) + d * int(v)
                    if -guard < total < guard:
                        arr[c] = total
                    else:
                        big[bk] = total
        # emit: gather touched-group state as C-batched lists, then one
        # pass over touched groups only (native when available)
        tl = touched.tolist()
        cnts = self._cnt[touched].tolist()
        pcols = []
        for i, (kind, _pos) in enumerate(self.reducer_cols):
            if kind == "count":
                pcols.append([])
            elif kind in ("min", "max"):
                groups = self._mm[i]
                agg = min if kind == "min" else max

                def mm_of(c, _g=groups, _agg=agg):
                    g = _g.get(c)
                    if not g:
                        return None
                    # net-negative counts (a retraction seen ahead of its
                    # insertion) are excluded, matching the row path's
                    # _MultisetState.iter_args max(c, 0) semantics
                    live = [v for v, cnt in g.items() if cnt > 0]
                    return _agg(live) if live else None

                pcols.append([mm_of(c) for c in tl])
            elif kind in ("argmin", "argmax"):
                groups = self._mm[i]
                agg = min if kind == "argmin" else max

                def am_of(c, _g=groups, _agg=agg):
                    g = _g.get(c)
                    if not g:
                        return None
                    # ties break by orderable payload, exactly the row
                    # path's _ArgMin/_ArgMaxState key functions
                    best = _agg(
                        ((cv, _orderable(pv), pv)
                         for (cv, pv), cnt in g.items() if cnt > 0),
                        default=None)
                    return best[2] if best is not None else None

                pcols.append([am_of(c) for c in tl])
            else:
                pcols.append(
                    self._sums[self._sum_slot[i]][touched].tolist())
        big = self._big
        if big:
            for i, (kind, _pos) in enumerate(self.reducer_cols):
                if kind not in ("sum", "avg"):
                    continue
                slot = self._sum_slot[i]
                col = pcols[i]
                for idx, c in enumerate(tl):
                    exact = big.get((slot, c))
                    if exact is not None:
                        col[idx] = exact
        if fg is not None:
            out = Delta()
            out.entries = fg.emit(tl, cnts, self._kinds, pcols,
                                  self._gvals, self._gkeys, self._last)
            return out
        out = Delta()
        append = out.entries.append
        last = self._last
        gkeys = self._gkeys
        gvals = self._gvals
        for idx, code in enumerate(tl):
            c = cnts[idx]
            if c <= 0:
                new = None
            else:
                red = [c if kind == "count"
                       else (pcols[i][idx] / c if kind == "avg"
                             else pcols[i][idx])
                       for i, (kind, _p) in enumerate(self.reducer_cols)]
                new = (*gvals[code], *red)
            old = last[code]
            if old == new:
                continue
            gkey = gkeys[code]
            if old is not None:
                append((gkey, old, -1))
            if new is not None:
                append((gkey, new, 1))
            last[code] = new
        return out


class JoinOperator(Operator):
    """Inner/left/right/outer join (reference: join_tables, dataflow.rs:2276).

    Exact on unconsolidated input: upserts and absent-row retractions are
    handled entry by entry, and a same-tick net-zero pair emits output
    pairs that cancel downstream.

    ``lkey_fn/rkey_fn`` extract the join key from a row; output id =
    hash(join-side ids) like the reference (result key sharded like the join
    key, dataflow.rs:2371-2379); outer 'ears' appear when a side has no
    match. For every affected join-key group the output set is recomputed
    before/after and differenced — correct under arbitrary retraction.
    """

    arity = 2
    # pure memo (lk, rk) -> mixed output pointer: every entry recomputes
    # to the same value via mix_pointers, so the coverage sanitizer must
    # not demand its capture (snapshot_state deliberately skips it)
    _snapshot_sanitizer_exempt = ("_mix_cache",)

    def __init__(self, mode: str, lkey_fn, rkey_fn,
                 out_fn: Callable[[Pointer | None, tuple | None, Pointer | None, tuple | None], tuple],
                 out_key_fn=None, left_id_only: bool = False,
                 out_spec: tuple | None = None,
                 lkey_pos: int | None = None, lkey_fb=None,
                 rkey_pos: int | None = None, rkey_fb=None):
        assert mode in ("inner", "left", "right", "outer")
        self.mode = mode
        self.lkey_fn = lkey_fn
        self.rkey_fn = rkey_fn
        self.out_fn = out_fn
        # C-friendly projection spec ((side, pos), ...) mirroring out_fn;
        # side 0 = left row, 1 = right row, 2 = key (pos 0 lk / 1 rk)
        self.out_spec = out_spec
        # plain-column join keys: the native pass extracts row[pos] inline
        # (fb(v, key) reproduces the lowering's _jkey for non-str/int cells)
        self.lkey_pos = lkey_pos
        self.lkey_fb = lkey_fb
        self.rkey_pos = rkey_pos
        self.rkey_fb = rkey_fb
        # default out key = mix(left id, right id): unique per pair, so the
        # bilinear delta path applies. A custom out_key_fn (join id from one
        # side) can collide across pairs — those joins keep the per-group
        # recompute path whose dict semantics dedupe collisions.
        self._bilinear = out_key_fn is None
        # only the inner bilinear fast path fuses same-tick retract+insert
        # pairs; other modes would forward an uncanceled net-zero pair to
        # sinks as phantom delete+insert events, so they keep consolidation
        self.consolidate_inputs = not (self._bilinear and mode == "inner")
        # live (lk, rk) pairs recur every tick in dimension joins: a dict
        # probe beats re-mixing 128-bit ints per emitted row
        self._mix_cache: dict = {}
        self.out_key_fn = out_key_fn or self._default_out_key
        self.left: dict[Any, dict[Pointer, tuple]] = {}
        self.right: dict[Any, dict[Pointer, tuple]] = {}
        self.left_id_only = left_id_only

    def exchange_specs(self):
        # both sides route by join key so each key group is wholly owned by
        # one worker (reference: join exchanges, dataflow.rs:2276)
        return [lambda k, r: self.lkey_fn(k, r),
                lambda k, r: self.rkey_fn(k, r)]

    def snapshot_state(self):
        # _mix_cache is a pure memo (rebuilds on demand) — never captured
        return {"left": self.left, "right": self.right}

    def restore_state(self, state) -> None:
        self.left = {jk: dict(g) for jk, g in state["left"].items()}
        self.right = {jk: dict(g) for jk, g in state["right"].items()}

    def _default_out_key(self, lkey, rkey, jk):
        ck = (lkey, rkey)
        p = self._mix_cache.get(ck)
        if p is None:
            p = mix_pointers(lkey, rkey)
            if len(self._mix_cache) < (1 << 20):
                self._mix_cache[ck] = p
        return p

    def _group_out(self, jk) -> dict[Pointer, tuple]:
        lg = self.left.get(jk) or {}
        rg = self.right.get(jk) or {}
        out: dict[Pointer, tuple] = {}
        if lg and rg:
            for lk, lrow in lg.items():
                for rk, rrow in rg.items():
                    out[self.out_key_fn(lk, rk, jk)] = self.out_fn(lk, lrow, rk, rrow)
        if self.mode in ("left", "outer") and lg and not rg:
            for lk, lrow in lg.items():
                out[self.out_key_fn(lk, None, jk)] = self.out_fn(lk, lrow, None, None)
        if self.mode in ("right", "outer") and rg and not lg:
            for rk, rrow in rg.items():
                out[self.out_key_fn(None, rk, jk)] = self.out_fn(None, None, rk, rrow)
        return out

    @staticmethod
    def _apply(index, jk, key, row, diff):
        grp = index.setdefault(jk, {})
        if diff > 0:
            grp[key] = row
        else:
            grp.pop(key, None)
            if not grp:
                index.pop(jk, None)

    def step(self, time, in_deltas):
        dl, dr = in_deltas
        if not dl and not dr:
            return Delta()
        if self._bilinear and self.mode == "inner":
            fj = _get_fastjoin()
            if fj is not None:
                return self._step_inner_native(fj, dl, dr)
        l_entries = [(self.lkey_fn(k, r), k, r, d) for k, r, d in dl.entries]
        r_entries = [(self.rkey_fn(k, r), k, r, d) for k, r, d in dr.entries]
        if self._bilinear:
            return self._step_bilinear(l_entries, r_entries)
        affected: dict[Any, None] = {}
        for jk, _, _, _ in l_entries:
            affected[jk] = None
        for jk, _, _, _ in r_entries:
            affected[jk] = None
        affected.pop(None, None)  # null join keys never match
        old = {jk: self._group_out(jk) for jk in affected}
        for jk, k, r, d in l_entries:
            if jk is not None:
                self._apply(self.left, jk, k, r, d)
        for jk, k, r, d in r_entries:
            if jk is not None:
                self._apply(self.right, jk, k, r, d)
        out = Delta()
        for jk in affected:
            new = self._group_out(jk)
            o = old[jk]
            for okey, orow in o.items():
                n = new.get(okey)
                if n is None or row_fingerprint(n) != row_fingerprint(orow):
                    out.append(okey, orow, -1)
            for okey, nrow in new.items():
                oo = o.get(okey)
                if oo is None or row_fingerprint(oo) != row_fingerprint(nrow):
                    out.append(okey, nrow, 1)
        return out.consolidate()

    def _emit_left(self, out, jk, lk, lrow, sign) -> None:
        """Output delta for one left row vs the CURRENT right state."""
        rg = self.right.get(jk)
        if rg:
            append = out.entries.append
            okey, ofn = self.out_key_fn, self.out_fn
            for rk, rrow in rg.items():
                append((okey(lk, rk, jk), ofn(lk, lrow, rk, rrow), sign))
        elif self.mode in ("left", "outer"):
            out.append(self.out_key_fn(lk, None, jk),
                       self.out_fn(lk, lrow, None, None), sign)

    def _emit_right(self, out, jk, rk, rrow, sign) -> None:
        lg = self.left.get(jk)
        if lg:
            append = out.entries.append
            okey, ofn = self.out_key_fn, self.out_fn
            for lk, lrow in lg.items():
                append((okey(lk, rk, jk), ofn(lk, lrow, rk, rrow), sign))
        elif self.mode in ("right", "outer"):
            out.append(self.out_key_fn(None, rk, jk),
                       self.out_fn(None, None, rk, rrow), sign)

    def _step_bilinear(self, l_entries, r_entries) -> Delta:
        """Exact incremental join delta: ΔL⋈R_old + L_new⋈ΔR (+ ear
        emptiness transitions for left/right/outer) — O(delta x matches)
        instead of recomputing every affected group (the DD join_core
        update rule the reference leans on, dataflow.rs:2276).

        State applies ENTRY BY ENTRY while the side's delta is processed,
        matching the recompute path's dict semantics exactly: an insert
        over a live row is an upsert (old outputs retracted first, no-op
        if the row is unchanged) and a retraction of an absent row emits
        nothing. Right state stays fixed during the ΔL pass (R_old) and
        left state is complete during the ΔR pass (L_new) — the bilinear
        split that makes the delta exact."""
        if self.mode == "inner":
            return self._step_bilinear_inner(l_entries, r_entries)
        out = Delta()
        left_ear = self.mode in ("left", "outer")
        right_ear = self.mode in ("right", "outer")
        fp = row_fingerprint
        # left-group emptiness transitions flip right-side ears; snapshot
        # before ΔL applies
        if right_ear:
            l_empty_old: dict[Any, bool] = {}
            for jk, _, _, _ in l_entries:
                if jk is not None and jk not in l_empty_old:
                    l_empty_old[jk] = jk not in self.left
        # ΔL against R_old, left state applied as we go
        for jk, lk, lrow, d in l_entries:
            if jk is None:
                continue
            lg = self.left.get(jk)
            cur = lg.get(lk) if lg else None
            if d > 0:
                if cur is not None:
                    if fp(cur) == fp(lrow):
                        continue  # duplicate upsert: outputs unchanged
                    self._emit_left(out, jk, lk, cur, -1)
                self._emit_left(out, jk, lk, lrow, 1)
                self._apply(self.left, jk, lk, lrow, 1)
            else:
                if cur is None:
                    continue  # retraction of an absent row: no-op
                self._emit_left(out, jk, lk, cur, -1)
                self._apply(self.left, jk, lk, lrow, -1)
        if right_ear:
            for jk, was_empty in l_empty_old.items():
                if (jk not in self.left) != was_empty:
                    rg = self.right.get(jk)
                    if rg:
                        sign = -1 if was_empty else 1
                        okey, ofn = self.out_key_fn, self.out_fn
                        for rk, rrow in rg.items():
                            out.append(okey(None, rk, jk),
                                       ofn(None, None, rk, rrow), sign)
        # ΔR against L_new, right state applied as we go
        if left_ear:
            r_empty_old: dict[Any, bool] = {}
            for jk, _, _, _ in r_entries:
                if jk is not None and jk not in r_empty_old:
                    r_empty_old[jk] = jk not in self.right
        for jk, rk, rrow, d in r_entries:
            if jk is None:
                continue
            rg = self.right.get(jk)
            cur = rg.get(rk) if rg else None
            if d > 0:
                if cur is not None:
                    if fp(cur) == fp(rrow):
                        continue
                    self._emit_right(out, jk, rk, cur, -1)
                self._emit_right(out, jk, rk, rrow, 1)
                self._apply(self.right, jk, rk, rrow, 1)
            else:
                if cur is None:
                    continue
                self._emit_right(out, jk, rk, cur, -1)
                self._apply(self.right, jk, rk, rrow, -1)
        # right-group emptiness transitions flip left-side ears (vs L_new)
        if left_ear:
            for jk, was_empty in r_empty_old.items():
                if (jk not in self.right) != was_empty:
                    lg = self.left.get(jk)
                    if lg:
                        sign = -1 if was_empty else 1
                        okey, ofn = self.out_key_fn, self.out_fn
                        for lk, lrow in lg.items():
                            out.append(okey(lk, None, jk),
                                       ofn(lk, lrow, None, None), sign)
        # NOT consolidated: emissions are exact multiset deltas already
        # (upserts skip unchanged rows; out keys are unique per pair), and
        # fingerprinting a dimension join's whole churn every tick was the
        # single largest cost in bench_etl. Exchange merges and captures
        # consolidate where it matters.
        return out

    def _one_side_inner(self, entries, my_index, other_index, flip):
        """One bilinear pass of the inner-mode fast path. Adjacent
        retract+insert of the same (jk, row-key) — the exact shape a
        groupby's churn arrives in — fuse into one upsert: one state scan
        and one output key per matched pair instead of two."""
        out_entries: list = []
        append = out_entries.append
        eq = _rows_equal
        okey, ofn = self.out_key_fn, self.out_fn
        i, n = 0, len(entries)
        while i < n:
            jk, k, row, d = entries[i]
            i += 1
            if jk is None:
                continue
            grp = my_index.get(jk)
            cur = grp.get(k) if grp else None
            if d > 0:
                if cur is not None:
                    if eq(cur, row):
                        continue  # duplicate upsert: outputs unchanged
                    og = other_index.get(jk)
                    if og:
                        for ok_, orow in og.items():
                            if flip:
                                key = okey(ok_, k, jk)
                                append((key, ofn(ok_, orow, k, cur), -1))
                                append((key, ofn(ok_, orow, k, row), 1))
                            else:
                                key = okey(k, ok_, jk)
                                append((key, ofn(k, cur, ok_, orow), -1))
                                append((key, ofn(k, row, ok_, orow), 1))
                    grp[k] = row
                else:
                    og = other_index.get(jk)
                    if og:
                        if flip:
                            for ok_, orow in og.items():
                                append((okey(ok_, k, jk),
                                        ofn(ok_, orow, k, row), 1))
                        else:
                            for ok_, orow in og.items():
                                append((okey(k, ok_, jk),
                                        ofn(k, row, ok_, orow), 1))
                    self._apply(my_index, jk, k, row, 1)
            else:
                if cur is None:
                    continue  # retraction of an absent row: no-op
                nxt = None
                if i < n:
                    jk2, k2, row2, d2 = entries[i]
                    if d2 > 0 and k2 == k and jk2 == jk:
                        nxt = row2
                        i += 1
                if nxt is not None:
                    if eq(cur, nxt):
                        continue  # value unchanged: no outputs, no state
                    og = other_index.get(jk)
                    if og:
                        for ok_, orow in og.items():
                            if flip:
                                key = okey(ok_, k, jk)
                                append((key, ofn(ok_, orow, k, cur), -1))
                                append((key, ofn(ok_, orow, k, nxt), 1))
                            else:
                                key = okey(k, ok_, jk)
                                append((key, ofn(k, cur, ok_, orow), -1))
                                append((key, ofn(k, nxt, ok_, orow), 1))
                    grp[k] = nxt
                else:
                    og = other_index.get(jk)
                    if og:
                        if flip:
                            for ok_, orow in og.items():
                                append((okey(ok_, k, jk),
                                        ofn(ok_, orow, k, cur), -1))
                        else:
                            for ok_, orow in og.items():
                                append((okey(k, ok_, jk),
                                        ofn(k, cur, ok_, orow), -1))
                    self._apply(my_index, jk, k, row, -1)
        return out_entries

    def _step_inner_native(self, fj, dl: Delta, dr: Delta) -> Delta:
        """Inner bilinear delta via the native pass (native/fastjoin.cpp).
        Raw delta entries go straight in when the join key is a plain
        column (lkey_pos); otherwise the pre-keyed 4-tuple list is built
        here and the C side skips extraction."""
        spec = self.out_spec
        ofn = self.out_fn if spec is None else None
        out = Delta()
        ext = out.entries.extend
        if dl.entries:
            if self.lkey_pos is not None:
                ext(fj.one_side_inner(
                    dl.entries, self.left, self.right, self._mix_cache,
                    mix_pointers, Pointer, ofn, spec, False,
                    self.lkey_pos, self.lkey_fb))
            else:
                les = [(self.lkey_fn(k, r), k, r, d)
                       for k, r, d in dl.entries]
                ext(fj.one_side_inner(
                    les, self.left, self.right, self._mix_cache,
                    mix_pointers, Pointer, ofn, spec, False, -1, None))
        if dr.entries:
            if self.rkey_pos is not None:
                ext(fj.one_side_inner(
                    dr.entries, self.right, self.left, self._mix_cache,
                    mix_pointers, Pointer, ofn, spec, True,
                    self.rkey_pos, self.rkey_fb))
            else:
                res = [(self.rkey_fn(k, r), k, r, d)
                       for k, r, d in dr.entries]
                ext(fj.one_side_inner(
                    res, self.right, self.left, self._mix_cache,
                    mix_pointers, Pointer, ofn, spec, True, -1, None))
        return out

    def _step_bilinear_inner(self, l_entries, r_entries) -> Delta:
        """Inner-mode bilinear delta: same exact-update rule as the generic
        path (ΔL vs R_old, then ΔR vs L_new) without ear bookkeeping, with
        upsert-pair fusion (see _one_side_inner). Pure-Python fallback for
        environments without the native pass."""
        out = Delta()
        if l_entries:
            out.entries.extend(
                self._one_side_inner(l_entries, self.left, self.right,
                                     flip=False))
        if r_entries:
            out.entries.extend(
                self._one_side_inner(r_entries, self.right, self.left,
                                     flip=True))
        return out


class DeduplicateOperator(Operator):
    """pw.Table.deduplicate (reference: deduplicate, dataflow.rs:3013):
    per instance keep one accepted value; ``acceptor(new, old) -> bool``
    decides replacement. Append-only w.r.t. input deletions (ignored)."""

    def __init__(self, instance_fn, value_fn, acceptor, full_row: bool = True):
        self.instance_fn = instance_fn
        self.value_fn = value_fn
        self.acceptor = acceptor
        self.state: dict[Any, tuple[Pointer, tuple]] = {}

    def snapshot_state(self):
        return {"state": self.state}

    def restore_state(self, state) -> None:
        self.state = {inst: (k, tuple(r))
                      for inst, (k, r) in state["state"].items()}

    def exchange_specs(self):
        # per-instance acceptance is order-sensitive: a single worker must
        # own each instance (reference: deduplicate exchanges by instance)
        return [lambda k, r: self.instance_fn(k, r)]

    def step(self, time, in_deltas):
        delta = in_deltas[0]
        out = Delta()
        # canonical per-tick order: acceptance is order-sensitive, and the
        # sharded exchange permutes same-tick arrival order — sorting by key
        # keeps results identical at any worker count (across ticks the
        # stream order still governs, as before)
        for key, row, diff in sorted(
                delta.entries, key=lambda e: int(e[0])):
            if diff <= 0:
                continue  # deduplicate consumes append-only streams
            inst = self.instance_fn(key, row)
            new_val = self.value_fn(key, row)
            cur = self.state.get(inst)
            if cur is None:
                accept = True
            else:
                old_val = self.value_fn(cur[0], cur[1])
                try:
                    accept = bool(self.acceptor(new_val, old_val))
                except Exception as e:
                    from pathway_tpu.internals.error import global_error_log

                    global_error_log().log(
                        f"deduplicate acceptor raised: {e!r}", "deduplicate")
                    accept = False
            if accept:
                gkey = hash_values("dedup", inst)
                if cur is not None:
                    out.append(gkey, cur[1], -1)
                self.state[inst] = (key, row)
                out.append(gkey, row, 1)
        return out.consolidate()


class OutputOperator(Operator):
    """Terminal capture: invokes callback(time, delta); passes delta through.

    Under operator-state snapshots (engine/persistence.py) it additionally
    tracks the CONSOLIDATED emitted state — key -> (row, net count) — so a
    restart restored from a snapshot can re-emit the covered prefix's
    visible state to fresh sinks, exactly as a full-WAL replay would have
    re-emitted it by reprocessing the prefix. Tracking is off (zero cost)
    unless the runtime enables it for a snapshotting run.
    """

    def __init__(self, callback: Callable[[int, Delta], None]):
        self.callback = callback
        self.track_emitted = False
        self.emitted: dict[Pointer, list] = {}  # key -> [row, net count]

    def replicate(self, n):
        # all workers funnel into the same sink: share the callback object
        # (a deepcopy of a bound method would clone its receiver and the
        # replica outputs would silently vanish into the copy)
        reps = [self]
        for _ in range(n - 1):
            r = OutputOperator(self.callback)
            r.track_emitted = self.track_emitted
            reps.append(r)
        return reps

    def step(self, time, in_deltas):
        delta = in_deltas[0]
        if delta:
            if self.track_emitted:
                self._track(delta)
            self.callback(time, delta)
        return delta

    def _track(self, delta: Delta) -> None:
        emitted = self.emitted
        for key, row, diff in delta.entries:
            cur = emitted.get(key)
            c = (cur[1] if cur is not None else 0) + diff
            if c <= 0:
                emitted.pop(key, None)
            elif diff > 0 or cur is None:
                emitted[key] = [row, c]
            else:
                cur[1] = c

    def snapshot_state(self):
        if not self.track_emitted:
            return None
        return {"emitted": {k: (tuple(r), c)
                            for k, (r, c) in self.emitted.items()}}

    def restore_state(self, state) -> None:
        self.track_emitted = True
        self.emitted = {k: [tuple(r), c]
                        for k, (r, c) in state["emitted"].items()}

    def emit_restored(self, time: int) -> None:
        """Push the restored consolidated state to the sink as one initial
        delta — the snapshot-mode stand-in for the output rows a full
        replay of the covered prefix would have re-emitted."""
        if self.emitted:
            self.callback(time, Delta(
                [(k, r, c) for k, (r, c) in self.emitted.items()]))

    def notify_time_end(self, time):
        pass


class StatefulArrangeOperator(Operator):
    """Materializes its input (identity + arrangement), for ix/debug reads."""

    def __init__(self):
        self.state = Arrangement()

    def exchange_specs(self):
        return [Exchange.BY_KEY]

    def snapshot_state(self):
        return {"rows": self.state.rows}

    def restore_state(self, state) -> None:
        self.state.rows = dict(state["rows"])

    def step(self, time, in_deltas):
        self.state.update(in_deltas[0])
        return in_deltas[0]


class SortOperator(Operator):
    """prev/next pointers within (instance, sort-key) order
    (reference: sort_table, dataflow.rs:1910; operators/prev_next.rs).

    Round-1 implementation recomputes neighbours for the affected instance
    on change — O(n log n) per touched instance, correct under retraction.
    """

    def __init__(self, key_fn, instance_fn):
        self.key_fn = key_fn
        self.instance_fn = instance_fn
        self.instances: dict[Any, dict[Pointer, Any]] = {}
        self.out = Arrangement()

    def exchange_specs(self):
        # prev/next neighbours are computed within an instance: one worker
        # must own each instance (reference: operators/prev_next.rs)
        return [lambda k, r: self.instance_fn(k, r)]

    def snapshot_state(self):
        return {"instances": self.instances, "out": self.out.rows}

    def restore_state(self, state) -> None:
        self.instances = {inst: dict(g)
                          for inst, g in state["instances"].items()}
        self.out.rows = dict(state["out"])

    def step(self, time, in_deltas):
        delta = in_deltas[0]
        if not delta:
            return Delta()
        touched: dict[Any, None] = {}
        removed: list[Pointer] = []
        for key, row, diff in delta.entries:
            inst = self.instance_fn(key, row)
            grp = self.instances.setdefault(inst, {})
            if diff > 0:
                grp[key] = self.key_fn(key, row)
            else:
                if key in grp:
                    grp.pop(key)
                    removed.append(key)
            touched[inst] = None
        out = Delta()
        for key in removed:
            # only retract if the key wasn't re-inserted (possibly under
            # another instance) in this same delta
            if not any(key in g for g in self.instances.values()):
                upsert_delta(self.out, key, None, out)
        for inst in touched:
            grp = self.instances.get(inst, {})
            order = sorted(grp.items(), key=lambda kv: (_sortable(kv[1]), int(kv[0])))
            for i, (key, _sk) in enumerate(order):
                prev_k = order[i - 1][0] if i > 0 else None
                next_k = order[i + 1][0] if i + 1 < len(order) else None
                upsert_delta(self.out, key, (prev_k, next_k), out)
        self.out.update(out)
        return out


def _sortable(v):
    if v is None:
        return (0, 0)
    if isinstance(v, (bool, int, float, np.integer, np.floating)):
        return (1, float(v))
    if isinstance(v, str):
        return (2, v)
    return (3, repr(v))


class GradualBroadcastOperator(Operator):
    """Throttled broadcast of a changing (lower, value, upper) triplet
    (reference: src/engine/dataflow/operators/gradual_broadcast.rs:1-490).

    Every target row gets an ``apx_value`` column approximating the
    broadcast value: keys below ``threshold = (value-lower)/(upper-lower)
    x KEY_MAX`` see ``upper``, the rest see ``lower``. When the value
    moves, only keys BETWEEN the old and new thresholds change — so a
    jittering broadcast scalar retracts O(moved fraction) of rows instead
    of all of them (apply_to_fragment from..to, gradual_broadcast.rs:
    421-460). Input 0: target rows; input 1: the triplet table (last
    insert wins, like the reference's broadcast stream).
    """

    arity = 2
    _KEY_SPACE = 1 << 128
    _MISSING = object()  # 'never emitted' sentinel (None is a legal apx)

    def __init__(self):
        self.rows: dict[Pointer, tuple] = {}
        self._sorted_keys: list[int] = []  # int(key), ascending
        self._by_int: dict[int, Pointer] = {}
        self.triplet: tuple | None = None
        self._threshold: int | None = None  # threshold of last emission
        self.emitted_apx: dict[Pointer, Any] = {}

    def snapshot_state(self):
        # emitted_apx may hold the _MISSING sentinel only transiently
        # (pop side) — live values are plain data
        return {"rows": self.rows, "triplet": self.triplet,
                "threshold": self._threshold,
                "emitted_apx": self.emitted_apx}

    def restore_state(self, state) -> None:
        self.rows = dict(state["rows"])
        self.triplet = state["triplet"]
        self._threshold = state["threshold"]
        self.emitted_apx = dict(state["emitted_apx"])
        self._sorted_keys = sorted(int(k) for k in self.rows)
        self._by_int = {int(k): k for k in self.rows}

    def exchange_specs(self):
        # rows shard by key; the triplet stream is broadcast so every
        # shard applies the same thresholds (reference: the broadcast
        # stream in gradual_broadcast.rs) — per-key apx values are
        # independent, so sharding is exact
        return [Exchange.BY_KEY, Exchange.BROADCAST]

    def _threshold_of(self, triplet) -> int:
        lower, value, upper = triplet
        try:
            span = upper - lower
            frac = 1.0 if span == 0 else (value - lower) / span
        except TypeError:
            frac = 1.0
        frac = min(1.0, max(0.0, float(frac)))
        return int(frac * self._KEY_SPACE)

    def _apx_of(self, key: Pointer) -> Any:
        lower, _value, upper = self.triplet
        return upper if int(key) < self._threshold else lower

    def _emit_upsert(self, out: Delta, key: Pointer, row: tuple) -> None:
        apx = self._apx_of(key)
        old = self.emitted_apx.get(key, self._MISSING)
        if old is self._MISSING:
            out.append(key, (*row, apx), 1)
            self.emitted_apx[key] = apx
        elif row_fingerprint((old,)) != row_fingerprint((apx,)):
            out.append(key, (*row, old), -1)
            out.append(key, (*row, apx), 1)
            self.emitted_apx[key] = apx

    def step(self, time, in_deltas):
        import bisect

        d_rows, d_thr = in_deltas
        out = Delta()
        old_triplet = self.triplet
        if d_thr:
            # canonical order: the broadcast merges parts in arbitrary
            # order; "last insert wins" must not depend on worker count
            for _k, row, diff in sorted(
                    d_thr.entries,
                    key=lambda e: (int(e[0]), e[2], row_fingerprint(e[1]))):
                if diff > 0:
                    self.triplet = (row[0], row[1], row[2])
        if d_rows:
            # canonical order: retractions before insertions per key (same
            # hazard GroupByOperator sorts for, operators.py:332 — an
            # update pair may arrive insert-first after exchange merging)
            for key, row, diff in sorted(
                    d_rows.entries,
                    key=lambda e: (int(e[0]), e[2], row_fingerprint(e[1]))):
                ik = int(key)
                if diff > 0:
                    if key not in self.rows:
                        bisect.insort(self._sorted_keys, ik)
                        self._by_int[ik] = key
                    self.rows[key] = row
                    if self.triplet is not None:
                        if self._threshold is None:
                            self._threshold = self._threshold_of(
                                self.triplet)
                        apx = self._apx_of(key)
                        out.append(key, (*row, apx), 1)
                        self.emitted_apx[key] = apx
                else:
                    if key in self.rows:
                        idx = bisect.bisect_left(self._sorted_keys, ik)
                        if (idx < len(self._sorted_keys)
                                and self._sorted_keys[idx] == ik):
                            self._sorted_keys.pop(idx)
                        self._by_int.pop(ik, None)
                    self.rows.pop(key, None)
                    old = self.emitted_apx.pop(key, self._MISSING)
                    if old is not self._MISSING:
                        out.append(key, (*row, old), -1)
        if d_thr and self.triplet is not None:
            new_thr = self._threshold_of(self.triplet)
            bounds_changed = (
                old_triplet is None
                or old_triplet[0] != self.triplet[0]
                or old_triplet[2] != self.triplet[2])
            old_thr = self._threshold
            self._threshold = new_thr
            if bounds_changed or old_thr is None:
                # lower/upper changed: every emitted apx may be stale
                for key, row in self.rows.items():
                    self._emit_upsert(out, key, row)
            elif new_thr != old_thr:
                # only the key band between the thresholds flips
                # (reference apply_to_fragment from..to,
                # gradual_broadcast.rs:421-460)
                lo, hi = min(old_thr, new_thr), max(old_thr, new_thr)
                i = bisect.bisect_left(self._sorted_keys, lo)
                j = bisect.bisect_left(self._sorted_keys, hi)
                for ik in self._sorted_keys[i:j]:
                    key = self._by_int[ik]
                    self._emit_upsert(out, key, self.rows[key])
        return out.consolidate()
