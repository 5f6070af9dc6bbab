"""Engine graph + microbatch scheduler.

Replaces timely's worker loop / progress tracking (reference:
run_with_new_dataflow_graph, src/engine/dataflow.rs:5430-5641). Scheduling
model: logical timestamps are totally ordered u64s (reference
src/engine/timestamp.rs:19); at each committed timestamp the scheduler
pushes source deltas through the nodes in topological order — every operator
sees its complete input delta for time t before producing output for t, which
is exactly the consistency guarantee timely's frontiers provide, obtained
here by construction of the microbatch loop.

Iteration (pw.iterate) nests a sub-graph run to fixpoint per outer
timestamp (reference: iterate, dataflow.rs:3668 — DD Variable with product
timestamps; here: delta-driven rounds until the feedback delta is empty).
"""

from __future__ import annotations

from typing import Callable

from pathway_tpu.engine.delta import Arrangement, Delta, row_fingerprint
from pathway_tpu.engine.operators import Exchange, Operator, SourceOperator
from pathway_tpu.engine.profiler import current_profiler
from pathway_tpu.internals.keys import Pointer, hash_values


class Node:
    __slots__ = ("id", "op", "inputs", "name", "trace", "error_log")

    def __init__(self, id: int, op: Operator, inputs: list["Node"], name: str = ""):
        self.id = id
        self.op = op
        self.inputs = inputs
        self.name = name
        self.trace = None  # user-frame Trace set by the lowering
        self.error_log = None  # scoped log set by the lowering

    def __repr__(self):
        return f"<Node {self.id} {self.name or type(self.op).__name__}>"


class EngineGraph:
    def __init__(self):
        self.nodes: list[Node] = []

    def add_node(self, op: Operator, inputs: list[Node] | None = None,
                 name: str = "") -> Node:
        node = Node(len(self.nodes), op, list(inputs or []), name)
        self.nodes.append(node)
        return node

    def add_source(self, name: str = "source") -> Node:
        return self.add_node(SourceOperator(name), [], name)


class CapturedStream:
    """Output capture: the list of (key, row, time, diff) a table produced.

    Mirrors the reference's capture_table_data (src/python_api.rs:3200) used
    by the test harness's assert_table_equality / assert_stream_equality.
    Capture is chunk-buffered: on_delta stores (time, entries) references
    (deltas are never mutated after emission) and the flat event list
    materializes on first read — the dataflow's hot loop must not pay for
    the harness's bookkeeping.
    """

    def __init__(self):
        from pathway_tpu.engine.locking import create_lock

        self._chunks: list[tuple[int, list]] = []
        self._events: list[tuple] = []  # flattened (key, row, time, diff)
        # guards the chunk buffer: pool-thread replicas share this capture,
        # and an unsynchronized detach could orphan a concurrent append
        # (one lock operation per TICK, not per row — off the hot path)
        self._lock = create_lock("CapturedStream._lock")

    @property
    def events(self) -> list[tuple]:
        with self._lock:
            chunks, self._chunks = self._chunks, []
        for time, entries in chunks:
            self._events.extend(
                [(key, row, time, diff)
                 for key, row, diff in entries])
        return self._events

    def on_delta(self, time: int, delta: Delta) -> None:
        if delta.entries:
            with self._lock:
                self._chunks.append((time, delta.entries))

    def snapshot(self) -> dict:
        state: dict = {}
        counts: dict = {}
        for key, row, time, diff in self.events:
            c = counts.get(key, 0) + diff
            counts[key] = c
            if c > 0:
                state[key] = row
            else:
                state.pop(key, None)
                counts.pop(key, None)
        return state

    def consolidated_events(self) -> list[tuple]:
        acc: dict[tuple, int] = {}
        order: dict[tuple, int] = {}
        for i, (key, row, time, diff) in enumerate(self.events):
            k = (key, row_fingerprint(row), time)
            if k not in acc:
                acc[k] = 0
                order[k] = i
            acc[k] += diff
        out = []
        for i, (key, row, time, diff) in enumerate(self.events):
            k = (key, row_fingerprint(row), time)
            if order.get(k) == i and acc[k] != 0:
                out.append((key, row, time, acc[k]))
        return out


class Scheduler:
    """Single-host microbatch driver for an EngineGraph.

    With ``n_workers > 1`` the scheduler runs the dataflow *sharded*: every
    node gets one operator replica per logical worker, rows are key-routed
    between workers at each stateful operator's exchange boundary
    (reference: timely worker threads + exchange pacts,
    src/engine/dataflow/shard.rs — shard = key & mask), and sources are
    partitioned by row key. Execution is bulk-synchronous per node per
    timestamp, so the per-time consistency guarantee is unchanged.
    """

    def __init__(self, graph: EngineGraph, n_workers: int = 1,
                 parallel_threads: bool | None = None, cluster=None,
                 device_inflight: int | None = None, recorder=None):
        self.graph = graph
        self.cluster = cluster
        # flight recorder (engine/flight_recorder.py): None or disabled is
        # the hot-path default — one branch per operator step, no
        # allocation; runtimes pass an enabled recorder when tracing /
        # monitoring surfaces want span data
        self.recorder = recorder
        if cluster is not None:
            # SPMD multi-process: n_workers is per-process; the global
            # worker space is P x T, owned in contiguous blocks
            # (reference: config.rs:108-120 — threads x processes)
            per_proc = max(1, int(n_workers))
            self.n_workers = per_proc * cluster.n_processes
            self.local_lo = cluster.process_id * per_proc
            self.local_hi = self.local_lo + per_proc
        else:
            self.n_workers = max(1, int(n_workers))
            self.local_lo = 0
            self.local_hi = self.n_workers
        if parallel_threads is None:
            import os

            parallel_threads = os.environ.get(
                "PATHWAY_WORKER_THREADS", "0") not in ("0", "", "false")
        # step worker replicas on a thread pool. State is disjoint per
        # replica so this is safe; it pays off only when operator work
        # releases the GIL (numpy/XLA-heavy columnar evaluators) — for
        # pure-Python row ops the GIL serializes it, which is why it is
        # opt-in (measured in bench.py bench_etl).
        self._local_n = self.local_hi - self.local_lo
        self._pool = None
        if parallel_threads and self._local_n > 1:
            from concurrent.futures import ThreadPoolExecutor

            self._pool = ThreadPoolExecutor(max_workers=self._local_n)
        from pathway_tpu.engine.locking import create_lock

        self._stats_lock = create_lock("Scheduler._stats_lock")
        # value -> worker memo per exchanged edge; bounded so
        # high-cardinality instance columns (user ids, session keys) do not
        # leak over a long streaming run: at the cap the edge's memo is
        # reset wholesale — O(1), and the hot values re-memoize immediately
        self._route_cache: dict[tuple[int, int], dict] = {}
        import os as _os

        try:
            self._route_cache_max = max(
                1024, int(_os.environ.get("PATHWAY_ROUTE_CACHE_MAX",
                                          str(1 << 16))))
        except ValueError:
            self._route_cache_max = 1 << 16
        self._topo = self._topo_sort()
        # LOCAL worker replicas per node (index = worker - local_lo);
        # replica 0 on process 0 is always node.op itself. Gather nodes
        # (unpartitionable state) keep one replica owned by global worker 0
        # — i.e. by process 0; other processes hold none.
        self._replicas: dict[int, list[Operator]] = {}
        self._gather: dict[int, bool] = {}
        for node in graph.nodes:
            specs = node.op.exchange_specs()
            gather = any(s == Exchange.GATHER for s in specs)
            self._gather[node.id] = gather
            if gather and isinstance(node.op, IterateOperator):
                # the gathered fixpoint still shards its inner rounds
                # across this process's workers
                node.op.inner_workers = self._local_n
            if gather:
                self._replicas[node.id] = (
                    [node.op] if self.local_lo == 0 else [])
            elif self.n_workers == 1:
                self._replicas[node.id] = [node.op]
            elif cluster is None:
                self._replicas[node.id] = node.op.replicate(self.n_workers)
            else:
                # each process replicates only its own block; replica
                # identity across processes is irrelevant (state disjoint)
                self._replicas[node.id] = node.op.replicate(
                    self._local_n)
        # snapshot-coverage sanitizer (engine/snapshot_sanitizer.py):
        # under PATHWAY_SNAPSHOT_SANITIZER=1 every replica whose class
        # overrides snapshot_state gets a mutation tracer; the snapshot
        # path below then diffs mutated attrs against the capture set
        from pathway_tpu.engine import snapshot_sanitizer as _snapsan

        if _snapsan.sanitizer_enabled():
            for reps in self._replicas.values():
                for op in reps:
                    _snapsan.track_operator(op)
        self.stats: dict[int, dict] = {
            n.id: {"insertions": 0, "retractions": 0,
                   "latency_ms": 0.0, "total_ms": 0.0}
            for n in graph.nodes
        }
        self.on_step: Callable[[int], None] | None = None
        # -- pipelined device legs (engine/device_bridge.py) ---------------
        # Device-bound operators (TPU-resident index add/search, traceable
        # batch UDFs like the JAX encoder embedder) and their downstream
        # closure form the per-tick "device leg"; with an in-flight window
        # >= 2 the leg runs on the bridge worker while the host thread
        # starts the next tick's host-side work. Single-worker,
        # single-process only: sharded/cluster execution keeps the
        # bulk-synchronous path (its exchanges are the consistency points).
        from pathway_tpu.engine.device_bridge import (DeviceBridge,
                                                      device_inflight_from_env)

        if device_inflight is None:
            device_inflight = device_inflight_from_env()
        self.device_inflight = max(1, int(device_inflight))
        self._bridge = None
        self._deferred_ids: frozenset[int] = frozenset()
        device_nodes = [n.id for n in graph.nodes
                        if getattr(n.op, "device_bound", False)]
        if (self.device_inflight >= 2 and self.n_workers == 1
                and cluster is None and device_nodes):
            self._deferred_ids = self._downstream_closure(device_nodes)
            self._bridge = DeviceBridge(self.device_inflight,
                                        recorder=self.recorder)
        # trace labeling: deferred-closure nodes are the device leg when
        # pipelining; synchronous mode still labels the device-bound
        # operators themselves so traces distinguish legs in both modes
        self._trace_device_ids = self._deferred_ids or frozenset(device_nodes)

    def _downstream_closure(self, roots: list[int]) -> frozenset[int]:
        """All nodes reachable from ``roots`` (inclusive) following output
        edges. Closed under successors, so every consumer of a deferred
        node's output is itself deferred — the device leg never feeds data
        back into the host leg of the same tick."""
        succs: dict[int, list[int]] = {n.id: [] for n in self.graph.nodes}
        for node in self.graph.nodes:
            for up in node.inputs:
                succs[up.id].append(node.id)
        seen = set(roots)
        frontier = list(roots)
        while frontier:
            nid = frontier.pop()
            for s in succs[nid]:
                if s not in seen:
                    seen.add(s)
                    frontier.append(s)
        return frozenset(seen)

    def resolve_barrier(self) -> None:
        """Wait for every in-flight device leg to resolve (no-op when
        pipelining is off). Must run before anything that reads engine
        state synchronously: end-of-stream flushes and output reads.
        Persistence commits do NOT barrier — they trail the resolved
        prefix via :meth:`commit_watermark` instead."""
        if self._bridge is not None:
            self._bridge.barrier()

    def wait_watermark(self, tick: int) -> int:
        """Block until the resolved-prefix watermark reaches ``tick``
        (synchronous mode: already there). Unlike :meth:`resolve_barrier`
        this waits ONLY on the watermark — it never drains legs beyond
        ``tick`` and it returns early (with the frozen watermark) when the
        bridge goes idle without reaching it. The snapshot pass uses it
        to obtain a consistent operator-state cut at exactly ``tick``."""
        if self._bridge is not None:
            return self._bridge.wait_watermark(tick)
        return tick

    # -- operator-state snapshots (engine/persistence.py) -----------------
    def graph_fingerprint(self) -> list:
        """Stable identity of the plan this scheduler runs: a snapshot
        taken by one process image must not restore into a different
        graph. Node ids are construction-ordered and operator CLASSES are
        program-determined; node *names* are not used — they embed
        process-global counters (table_0 vs table_1) that differ between
        otherwise identical runs."""
        return [(n.id, type(n.op).__name__,
                 tuple(up.id for up in n.inputs))
                for n in self.graph.nodes]

    def snapshot_operator_states(self) -> dict:
        """Per-node, per-replica plain-data state capture (None entries
        for stateless replicas are dropped node-wise). Caller guarantees
        the pipeline is quiescent at the snapshot tick (wait_watermark).
        Raises ``SnapshotUnsupported`` when any operator cannot
        capture."""
        from pathway_tpu.engine import snapshot_sanitizer as _snapsan

        states: dict[int, list] = {}
        for node in self.graph.nodes:
            per = [_snapsan.checked_snapshot(op)
                   for op in self._replicas[node.id]]
            if any(st is not None for st in per):
                states[node.id] = per
        return states

    def restore_operator_states(self, states: dict) -> None:
        """Load a snapshot's per-node states into the freshly-built
        replicas. Mismatched node ids / replica counts mean the program
        changed between runs — raise loudly (the WAL prefix the snapshot
        covers is compacted away; silently dropping state would produce
        wrong answers, not a slow restart)."""
        for nid, per in states.items():
            reps = self._replicas.get(int(nid))
            if reps is None:
                raise ValueError(
                    f"snapshot carries state for node {nid} which this "
                    "run's graph does not have — the pipeline changed "
                    "between runs; clear the persistence root to start "
                    "fresh")
            if len(per) != len(reps):
                raise ValueError(
                    f"snapshot for node {nid} has {len(per)} replica "
                    f"states but this run built {len(reps)} replicas "
                    "(n_workers changed between runs)")
            for op, st in zip(reps, per):
                if st is not None:
                    op.restore_state(st)

    def emit_restored_outputs(self, tick: int) -> None:
        """Re-emit every restored OutputOperator's consolidated state to
        its sink at ``tick`` — what full replay of the compacted prefix
        would have re-emitted by reprocessing it."""
        from pathway_tpu.engine.operators import OutputOperator

        for node in self.graph.nodes:
            for op in self._replicas[node.id]:
                if isinstance(op, OutputOperator):
                    op.emit_restored(tick)

    def enable_output_tracking(self) -> None:
        """Turn on consolidated emitted-state tracking on every output
        operator (required before any data flows in a snapshotting
        run)."""
        from pathway_tpu.engine.operators import OutputOperator

        for node in self.graph.nodes:
            for op in self._replicas[node.id]:
                if isinstance(op, OutputOperator):
                    op.track_emitted = True

    def commit_watermark(self, completed_tick: int) -> int:
        """The durability frontier for a persistence commit issued after
        ``completed_tick`` returned from :meth:`run_time`: with pipelining
        on, the bridge's resolved-prefix watermark (every leg <= it has
        retired — a checkpoint may cover exactly that prefix while later
        legs are still in flight); synchronously, the tick itself (it is
        fully processed the moment run_time returns)."""
        if self._bridge is not None:
            return min(self._bridge.resolved_watermark(), completed_tick)
        return completed_tick

    def set_watermark_listener(self, cb) -> None:
        """Observe every watermark advance (bridge-worker thread). No-op
        without a bridge — synchronous ticks already stamp progress
        inline."""
        if self._bridge is not None:
            self._bridge.on_advance = cb

    def bridge_inflight(self) -> dict | None:
        """The oldest unresolved device leg (tick + seconds since
        dispatch), None when idle or pipelining is off. Survives
        recording-off — stall post-mortems always get a name."""
        if self._bridge is not None:
            return self._bridge.inflight()
        return None

    def bridge_depth(self) -> int:
        """Device legs queued or running (0 when pipelining is off)."""
        return self._bridge.depth() if self._bridge is not None else 0

    def bridge_stats(self) -> dict | None:
        """Device-bridge instrumentation (None when pipelining is off)."""
        if self._bridge is not None:
            return self._bridge.stats()
        return None

    def take_device_error(self) -> BaseException | None:
        """A device-leg failure that no submit/barrier observed yet (e.g.
        the run was stopped externally and teardown drained the bridge
        without raising). Callers re-raise it after cleanup so pipelined
        mode never turns an operator/callback exception into a clean
        exit."""
        if self._bridge is not None:
            return self._bridge.error()
        return None

    def close(self) -> None:
        """Release the worker thread pool and drain the bridge (idempotent).
        The bridge object survives closure so post-run instrumentation
        (bench, /metrics snapshots) can still read its counters."""
        if self._bridge is not None:
            self._bridge.close()
        if self._pool is not None:
            self._pool.shutdown(wait=False)
            self._pool = None

    # -- sharding helpers ----------------------------------------------------
    def _route(self, spec, key, row) -> int:
        v = key if spec == Exchange.BY_KEY else spec(key, row)
        return self._route_value(v)

    def _route_value(self, v) -> int:
        if not isinstance(v, int):  # Pointer subclasses int
            v = hash_values(v)
        return int(v) % self.n_workers

    def push_source(self, node: Node, delta: Delta) -> None:
        """Feed a source node, partitioning rows across workers by key
        (the in-process analogue of per-worker source reads,
        reference src/connectors/mod.rs:400). Under a cluster, rows whose
        worker lives on another process are DROPPED — SPMD sources feed
        every process the identical stream and each keeps its shard;
        non-replicated sources forward shares explicitly first
        (partition_remote + the streaming tick exchange)."""
        reps = self._replicas[node.id]
        if self.cluster is None and len(reps) == 1:
            reps[0].push(delta)
            return
        n, lo, hi = self.n_workers, self.local_lo, self.local_hi
        parts: list[list] = [[] for _ in reps]
        for key, row, diff in delta.entries:
            w = int(key) % n
            if lo <= w < hi:
                parts[w - lo].append((key, row, diff))
        for rep, part in zip(reps, parts):
            if part:
                rep.push(Delta(part))

    def partition_remote(self, delta: Delta) -> dict[int, list]:
        """Split source entries by owning process (peer id -> entries) for
        single-reader sources whose rows must reach every process
        (reference: 'single reader forwards for non-partitioned sources',
        src/connectors/mod.rs ReadersQueryPurpose)."""
        if self.cluster is None:
            return {}
        per_proc = (self.local_hi - self.local_lo)
        out: dict[int, list] = {}
        for key, row, diff in delta.entries:
            p = (int(key) % self.n_workers) // per_proc
            if p != self.cluster.process_id:
                out.setdefault(p, []).append((key, row, diff))
        return out

    def _topo_sort(self) -> list[Node]:
        seen: dict[int, int] = {}
        order: list[Node] = []

        def visit(node: Node):
            state = seen.get(node.id, 0)
            if state == 2:
                return
            if state == 1:
                raise ValueError("cycle in engine graph (use iterate)")
            seen[node.id] = 1
            for up in node.inputs:
                visit(up)
            seen[node.id] = 2
            order.append(node)

        for node in self.graph.nodes:
            visit(node)
        return order

    def run_time(self, time: int, flush: bool = False) -> dict[int, Delta]:
        """Process one committed timestamp: sources already hold pending data.

        ``flush=True`` marks the end-of-stream tick: operators holding rows
        (temporal buffers) release them, and the releases propagate downstream
        within the same tick.
        """
        if self.n_workers == 1:
            if self._bridge is not None:
                return self._run_time_pipelined(time, flush)
            outputs: dict[int, Delta] = {}
            # request-tracking host-done stamp (engine/request_tracker.py):
            # in synchronous mode the "host leg" ends when the first
            # device-bound operator steps (no device nodes: after the
            # loop). Armed only while requests are actually in flight.
            requests = self._tracked_requests()
            host_pending = requests is not None
            prof = current_profiler()
            for node in self._topo:
                if host_pending and node.id in self._trace_device_ids:
                    requests.host_done(time)
                    host_pending = False
                in_deltas = [outputs.get(up.id, _EMPTY) for up in node.inputs]
                if prof is not None and node.id in self._trace_device_ids:
                    # sync mode has no bridge leg to measure: treat each
                    # device node's step as its own leg so cost-model
                    # dispatches inside are re-timed to the step's
                    # measured wall (engine/profiler.py)
                    import time as _time

                    prof.begin_leg(time)
                    t0 = _time.perf_counter()
                    try:
                        delta = self._step_op(node, node.op, time,
                                              in_deltas, flush)
                    except BaseException:
                        prof.end_leg(None)
                        raise
                    prof.end_leg((_time.perf_counter() - t0) * 1e3)
                else:
                    delta = self._step_op(node, node.op, time, in_deltas,
                                          flush)
                outputs[node.id] = delta
                self._count(node.id, delta)
            if host_pending:
                requests.host_done(time)
            if self.on_step is not None:
                self.on_step(time)
            return outputs
        return self._run_time_sharded(time, flush)

    def _tracked_requests(self):
        """The run's request tracker iff recording is on AND a request is
        mid-flight — one branch per tick otherwise."""
        rec = self.recorder
        if rec is not None and rec.enabled and rec.requests is not None \
                and rec.requests.active():
            return rec.requests
        return None

    def _run_time_pipelined(self, time: int, flush: bool):
        """One tick, split into a host leg (stepped now, on this thread)
        and a device leg (the deferred closure, submitted to the bridge).

        The leg closure captures this tick's ``outputs`` dict; host-leg
        deltas are complete before submission and the deferred closure is
        closed under successors, so the two threads never share a node.
        Steps observe ticks in order because the bridge worker is a single
        FIFO. ``flush=True`` (end of stream) is a hard barrier: everything
        must have retired before the caller tears down or reads results.
        """
        outputs: dict[int, Delta] = {}
        deferred: list[Node] = []
        for node in self._topo:
            if node.id in self._deferred_ids:
                deferred.append(node)
                continue
            in_deltas = [outputs.get(up.id, _EMPTY) for up in node.inputs]
            delta = self._step_op(node, node.op, time, in_deltas, flush)
            outputs[node.id] = delta
            self._count(node.id, delta)
        requests = self._tracked_requests()
        if requests is not None:
            # host leg complete; the device leg (bridge worker) resolves
            # the request downstream — the stamp that opens its stage
            requests.host_done(time)

        def leg() -> None:
            def _body() -> None:
                for node in deferred:
                    in_deltas = [outputs.get(up.id, _EMPTY)
                                 for up in node.inputs]
                    delta = self._step_op(node, node.op, time, in_deltas,
                                          flush)
                    outputs[node.id] = delta
                    self._count(node.id, delta)

            rec = self.recorder
            if rec is not None and rec.enabled:
                # jax.profiler.TraceAnnotation: XLA profiles show the same
                # tick boundaries as the framework's flight-recorder spans
                with rec.device_annotation(time):
                    _body()
            else:
                _body()

        self._bridge.submit(time, leg)
        if self.on_step is not None:
            self.on_step(time)
        if flush:
            self._bridge.barrier()
        return _PipelinedOutputs(self._bridge, outputs)

    def _step_op(self, node: Node, op: Operator, time: int,
                 in_deltas: list[Delta], flush: bool) -> Delta:
        import time as _time

        from pathway_tpu.internals.error import set_active_step_log

        # flight recorder: the disabled path is this one branch — no
        # allocation, no call (the overhead guard in tests/trace_canary.py
        # holds it under 2% per tick)
        rec = self.recorder
        recording = rec is not None and rec.enabled
        if recording:
            leg = "device" if node.id in self._trace_device_ids else "host"
            # inflight marker set BEFORE the step: a hung operator is
            # exactly the one the post-mortem must name
            rec.mark_op(time, node, leg)
        t0 = _time.perf_counter()
        set_active_step_log(node.error_log)
        try:
            delta = op.step(time, in_deltas)
            extra = op.on_time_advance(time)
            if extra:
                delta = Delta(delta.entries + extra.entries).consolidate()
            if flush:
                held = op.flush(time)
                if held:
                    delta = Delta(delta.entries + held.entries).consolidate()
        except Exception as e:
            from pathway_tpu.internals.trace import add_trace_note

            # annotate rather than wrap: the original exception type must
            # keep escaping pw.run() so user except-clauses still match
            # (reference: trace.py add_pathway_trace_note)
            add_trace_note(e, node.trace,
                           node.name or type(node.op).__name__)
            raise
        finally:
            set_active_step_log(None)
        # per-operator step latency (reference: OperatorStats latency via
        # Probers, src/engine/progress_reporter.rs:114 — feeds dashboard
        # and /metrics). Under sharding, replicas accumulate into one node;
        # the lock keeps += exact when replicas step on the thread pool.
        ms = (_time.perf_counter() - t0) * 1e3
        st = self.stats[node.id]
        with self._stats_lock:
            st["latency_ms"] = ms
            st["total_ms"] += ms
        if recording:
            rows_in = 0
            for d in in_deltas:
                rows_in += len(d.entries)
            # idle steps (no rows either way, sub-ms) are NOT recorded:
            # a quiescent streaming server ticks ~50x/s and every tick
            # steps every operator, so idle spans would flush the ring
            # (4096 events ~= 4 s of idle) and evict the spans of the
            # ticks that actually served requests — exactly the ones
            # post-mortems and the Perfetto request flows need
            if rows_in or delta.entries or ms >= 1.0:
                rec.record(time, node, leg, t0, ms, rows_in,
                           len(delta.entries), op.take_rederived())
            # cleared on success only: an operator that raised (or is
            # still raising through the bridge) stays named in the
            # in-flight slot for the post-mortem dump
            rec.clear_op()
        return delta

    def _count(self, node_id: int, delta: Delta) -> None:
        if delta:
            st = self.stats[node_id]
            ins = rets = 0
            # single pass, no intermediate list: this runs per node per
            # tick and the retraction branch is COMMON (incremental
            # groupby emits retract+insert pairs), so the old
            # sum + min + conditional-genexpr shape walked the entries
            # up to three times
            for _, _, d in delta.entries:
                if d >= 0:
                    ins += d
                else:
                    rets -= d
            st["insertions"] += ins
            st["retractions"] += rets

    def _run_time_sharded(self, time: int, flush: bool) -> dict[int, Delta]:
        n = self.n_workers
        lo, hi, L = self.local_lo, self.local_hi, self._local_n
        cl = self.cluster
        per_proc = L  # contiguous worker blocks of equal size per process
        outputs: dict[int, list[Delta]] = {}  # node.id -> per-LOCAL deltas
        # Coalesced exchange: nodes whose routing is computed wait here
        # (unstepped) so their cross-process rows share ONE frame per peer
        # — the per-node ("x", time, node.id) barrier round collapses to
        # one round per *level* of the topological order. A node whose
        # input is still pending forces a flush first (its send rows need
        # that input stepped), so batch boundaries follow the dependency
        # structure and are SPMD-deterministic; the batch ordinal in the
        # tag catches any skew.
        pending: list[dict] = []
        pending_ids: set[int] = set()
        batch_no = 0

        def finish_step(ctx) -> None:
            node, reps = ctx["node"], ctx["reps"]
            per_worker = ctx["per_worker"]
            if ctx["wm_node"] and ctx["wm_local"] is not None:
                reps[0]._advance_watermark_value(ctx["wm_local"])
            if self._pool is not None and reps[0].parallel_safe:
                outs = list(self._pool.map(
                    lambda w: self._step_op(node, reps[w], time,
                                            per_worker[w], flush),
                    range(L)))
            else:
                outs = [
                    self._step_op(node, reps[w], time, per_worker[w],
                                  flush)
                    for w in range(L)
                ]
            outputs[node.id] = outs
            for d in outs:
                self._count(node.id, d)

        def flush_exchange() -> None:
            nonlocal batch_no
            if not pending:
                return
            msgs = {
                p: {ctx["node"].id: {"rows": ctx["send"].get(p),
                                     "wm": ctx["wm_local"],
                                     "bcast": ctx["bcast"] or None}
                    for ctx in pending}
                for p in cl.peers
            }
            recv = cl.exchange(("x", time, batch_no), msgs)
            batch_no += 1
            for ctx in pending:
                node = ctx["node"]
                per_worker = ctx["per_worker"]
                consolidate = ctx["consolidate"]
                wm_local = ctx["wm_local"]
                for by_node in recv.values():
                    payload = by_node.get(node.id) if by_node else None
                    if payload is None:
                        continue
                    rows = payload.get("rows")
                    if rows:
                        for j, by_worker in rows.items():
                            routed = [[] for _ in range(L)]
                            for gw, ents in by_worker.items():
                                routed[gw - lo].extend(ents)
                            self._merge_routed(per_worker, routed, j,
                                               consolidate)
                    peer_bcast = payload.get("bcast")
                    if peer_bcast:
                        for j, ents in peer_bcast.items():
                            for w in range(L):
                                cur = per_worker[w][j]
                                base = cur.entries \
                                    if cur is not _EMPTY else []
                                merged = Delta(base + ents)
                                per_worker[w][j] = merged.consolidate() \
                                    if consolidate else merged
                    wm_local = _wm_max(wm_local, payload.get("wm"))
                ctx["wm_local"] = wm_local
                finish_step(ctx)
            pending.clear()
            pending_ids.clear()

        for node in self._topo:
            reps = self._replicas[node.id]
            if self._gather[node.id]:
                # gather reads its inputs' outputs AND runs its own
                # ("g", ...) round — resolve any pending batch first
                flush_exchange()
                outs = self._step_gather(node, reps, time, flush, outputs,
                                         L)
                outputs[node.id] = outs
                for d in outs:
                    self._count(node.id, d)
                continue
            if pending_ids and any(up.id in pending_ids
                                   for up in node.inputs):
                flush_exchange()
            op0 = reps[0] if reps else node.op
            specs = op0.exchange_specs()
            consolidate = op0.consolidate_inputs
            per_worker: list[list[Delta]] = [
                [_EMPTY] * len(node.inputs) for _ in range(L)]
            # remote shares: peer -> {input j -> {global worker -> entries}}
            send: dict[int, dict] = {}
            exchanged = False
            bcast: dict[int, list] = {}  # input j -> entries for peers
            for j, up in enumerate(node.inputs):
                parts = outputs.get(up.id) or [_EMPTY] * L
                spec = specs[j]
                if spec is None:
                    for w in range(L):
                        per_worker[w][j] = parts[w]
                    continue
                exchanged = True
                if spec == Exchange.BROADCAST:
                    # every local worker sees the complete delta; under
                    # a cluster the local share also goes to all peers
                    ents: list = []
                    for p in parts:
                        ents.extend(p.entries)
                    if cl is not None and ents:
                        bcast[j] = ents
                    if ents:
                        merged = Delta(list(ents))
                        if consolidate:
                            merged = merged.consolidate()
                        for w in range(L):
                            per_worker[w][j] = merged
                    continue
                routed = [[] for _ in range(L)]
                if spec == Exchange.BY_KEY:
                    for p in parts:
                        for e in p.entries:  # inline: keys are ints
                            gw = int(e[0]) % n
                            if lo <= gw < hi:
                                routed[gw - lo].append(e)
                            else:
                                send.setdefault(gw // per_proc, {}) \
                                    .setdefault(j, {}) \
                                    .setdefault(gw, []).append(e)
                else:
                    # non-int route values (instance columns etc.)
                    # repeat heavily tick after tick: memoize value ->
                    # worker per edge. Ints (already-uniform Pointers)
                    # route directly — % is cheaper than the cache
                    # probe — and tuples are per-row null sentinels
                    # that would never hit.
                    cache = self._route_cache.setdefault(
                        (node.id, j), {})
                    for p in parts:
                        for e in p.entries:
                            v = spec(e[0], e[1])
                            if isinstance(v, int):
                                gw = int(v) % n
                            elif isinstance(v, tuple):
                                gw = self._route_value(v)
                            else:
                                try:
                                    gw = cache.get(v)
                                except TypeError:  # unhashable
                                    gw = self._route_value(v)
                                else:
                                    if gw is None:
                                        gw = self._route_value(v)
                                        if len(cache) >= \
                                                self._route_cache_max:
                                            cache.clear()
                                        cache[v] = gw
                            if lo <= gw < hi:
                                routed[gw - lo].append(e)
                            else:
                                send.setdefault(gw // per_proc, {}) \
                                    .setdefault(j, {}) \
                                    .setdefault(gw, []).append(e)
                self._merge_routed(per_worker, routed, j, consolidate)
            # temporal operators share one watermark across workers
            # (global, like a timely frontier): advance it from every
            # process's pre-routing input before any replica releases
            # rows on it — the candidate scalar rides the exchange
            wm_local = None
            wm_node = bool(reps) and hasattr(reps[0], "_advance_watermark")
            if wm_node:
                for j, up in enumerate(node.inputs):
                    for p in outputs.get(up.id) or ():
                        wm_local = _wm_max(
                            wm_local, reps[0]._watermark_candidate(p))
            ctx = {"node": node, "reps": reps, "per_worker": per_worker,
                   "send": send, "bcast": bcast, "wm_local": wm_local,
                   "wm_node": wm_node, "consolidate": consolidate}
            if cl is not None and (exchanged or wm_node):
                pending.append(ctx)
                pending_ids.add(node.id)
            else:
                finish_step(ctx)
        flush_exchange()
        requests = self._tracked_requests()
        if requests is not None:
            # sharded execution is bulk-synchronous: the whole tick is
            # one host leg (device stage reads as 0 — honestly)
            requests.host_done(time)
        if self.on_step is not None:
            self.on_step(time)
        return _MergedOutputs(outputs)

    def exchange_rounds_per_tick(self) -> int:
        """Cluster BSP rounds one tick costs after exchange coalescing
        (static estimate from the graph, assuming a cluster is attached):
        exchanged/watermark nodes share one round per topological level;
        a gather node flushes the open batch and pays its own round."""
        rounds = 0
        pending: set[int] = set()
        for node in self._topo:
            if self._gather[node.id]:
                if pending:
                    rounds += 1
                    pending = set()
                rounds += 1
                continue
            reps = self._replicas[node.id]
            op0 = reps[0] if reps else node.op
            exchanged = any(s is not None for s in op0.exchange_specs())
            wm_node = bool(reps) and hasattr(reps[0], "_advance_watermark")
            if pending and any(up.id in pending for up in node.inputs):
                rounds += 1
                pending = set()
            if exchanged or wm_node:
                pending.add(node.id)
        return rounds + (1 if pending else 0)

    @staticmethod
    def _merge_routed(per_worker, routed, j, consolidate: bool = True) -> None:
        for w, ents in enumerate(routed):
            if not ents:
                continue
            cur = per_worker[w][j]
            merged = Delta(ents) if cur is _EMPTY else Delta(
                cur.entries + ents)
            per_worker[w][j] = merged.consolidate() if consolidate \
                else merged

    def _step_gather(self, node, reps, time, flush, outputs, L):
        """Gather node: one owner replica on (global) worker 0. Under a
        cluster every process ships its input entries to process 0 and the
        others emit nothing (the output lives where the state lives)."""
        ins_entries: list[list] = [[] for _ in node.inputs]
        for j, up in enumerate(node.inputs):
            for p in outputs.get(up.id) or ():
                ins_entries[j].extend(p.entries)
        cl = self.cluster
        if cl is not None:
            if cl.process_id == 0:
                recv = cl.exchange(("g", time, node.id),
                                   {p: None for p in cl.peers})
                for payload in recv.values():
                    if payload:
                        for j, ents in payload.items():
                            ins_entries[j].extend(ents)
            else:
                mine = {j: e for j, e in enumerate(ins_entries) if e}
                cl.exchange(("g", time, node.id),
                            {p: (mine if p == 0 else None)
                             for p in cl.peers})
                return [_EMPTY] * L
        if not reps:
            return [_EMPTY] * L
        ins = [Delta(e).consolidate() if e else _EMPTY
               for e in ins_entries]
        delta = self._step_op(node, reps[0], time, ins, flush)
        return [delta] + [_EMPTY] * (L - 1)


_EMPTY = Delta()


def _wm_max(a, b):
    """Max of two watermark candidates, tolerant of None and incomparable
    event-time types (the per-op _advance_watermark path swallows
    TypeError the same way — temporal_ops._gt)."""
    if b is None:
        return a
    if a is None:
        return b
    try:
        return b if b > a else a
    except TypeError:
        return a


class _PipelinedOutputs:
    """Lazy per-tick output view under pipelined execution: deferred-node
    deltas materialize on the bridge worker, so any read is a hard resolve
    barrier first. The streaming/batch drivers never read these (pure
    overlap); direct callers (tests, notebooks) get the synchronous-mode
    answer, just later."""

    __slots__ = ("_bridge", "_outputs")

    def __init__(self, bridge, outputs: dict[int, Delta]):
        self._bridge = bridge
        self._outputs = outputs

    def get(self, node_id: int, default: Delta | None = None) -> Delta | None:
        # default passes through verbatim (dict.get contract): a caller's
        # None-check must behave identically in pipelined and sync modes
        self._bridge.barrier()
        return self._outputs.get(node_id, default)

    def __getitem__(self, node_id: int) -> Delta:
        self._bridge.barrier()
        return self._outputs[node_id]

    def __contains__(self, node_id: int) -> bool:
        self._bridge.barrier()
        return node_id in self._outputs


class _MergedOutputs:
    """Lazy node-output view over per-worker deltas: merging every node's
    partitions each tick would be pure overhead (the streaming/batch drivers
    ignore run_time's return value), so partitions are concatenated and
    consolidated only for nodes a caller actually asks for — matching the
    consolidated per-op deltas the n_workers=1 path returns."""

    __slots__ = ("_per_worker",)

    def __init__(self, per_worker: dict[int, list[Delta]]):
        self._per_worker = per_worker

    def get(self, node_id: int, default: Delta = _EMPTY) -> Delta:
        outs = self._per_worker.get(node_id)
        if outs is None:
            return default
        entries: list = []
        for d in outs:
            entries.extend(d.entries)
        return Delta(entries).consolidate()

    def __getitem__(self, node_id: int) -> Delta:
        if node_id not in self._per_worker:
            raise KeyError(node_id)
        return self.get(node_id)

    def __contains__(self, node_id: int) -> bool:
        return node_id in self._per_worker


class IterateOperator(Operator):
    """Fixpoint iteration over a sub-graph.

    ``builder(graph, iter_sources, extra_sources) -> (iter_out_nodes, result_nodes)``
    builds the loop body. Per outer timestamp: feed full input state, run
    delta-driven rounds (the body is incremental across rounds — shrinking
    deltas near convergence, DD-style) until the feedback delta is empty or
    ``limit`` rounds passed; then emit the diff of the converged result
    against what was previously emitted.
    """

    def exchange_specs(self):
        # the fixpoint body may contain joins/groupbys over the whole
        # collection: per-shard fixpoints would be wrong (e.g. pagerank on a
        # subgraph), so iteration state is owned by one worker
        return [Exchange.GATHER] * self.arity

    def __init__(self, n_iterated: int, n_extra: int, builder, limit: int | None):
        self.arity = n_iterated + n_extra
        self.n_iterated = n_iterated
        self.n_extra = n_extra
        self.builder = builder
        self.limit = limit
        self.input_states = [Arrangement() for _ in range(self.arity)]
        self.emitted: list[Arrangement] = []
        self.n_results: int | None = None

    def snapshot_state(self):
        # the fixpoint re-runs per outer timestamp over the FULL input
        # state, so inputs + what was already emitted are the whole state
        return {"inputs": [st.rows for st in self.input_states],
                "emitted": [st.rows for st in self.emitted],
                "n_results": self.n_results}

    def restore_state(self, state) -> None:
        for st, rows in zip(self.input_states, state["inputs"]):
            st.rows = dict(rows)
        self.n_results = state["n_results"]
        if self.n_results is not None:
            self.emitted = []
            for rows in state["emitted"]:
                arr = Arrangement()
                arr.rows = dict(rows)
                self.emitted.append(arr)

    def step(self, time, in_deltas):
        if not any(in_deltas):
            return Delta()
        for st, d in zip(self.input_states, in_deltas):
            st.update(d)

        sub = EngineGraph()
        iter_sources = [sub.add_source(f"iter_{i}") for i in range(self.n_iterated)]
        extra_sources = [sub.add_source(f"extra_{i}") for i in range(self.n_extra)]
        iter_out_nodes, result_nodes = self.builder(sub, iter_sources, extra_sources)
        assert len(iter_out_nodes) == self.n_iterated
        if self.n_results is None:
            self.n_results = len(result_nodes)
            self.emitted = [Arrangement() for _ in range(self.n_results)]

        # the fixpoint state gathers to one owner, but the rounds INSIDE
        # run sharded across that process's workers (joins/groupbys in the
        # loop body exchange by key like any other pipeline) — the
        # owning scheduler passes its worker count down via inner_workers
        # fixpoint rounds read every node's outputs immediately — a
        # pipelined inner scheduler would barrier per round, so keep the
        # sub-graph synchronous (device_inflight=1)
        sched = Scheduler(sub, n_workers=getattr(self, "inner_workers", 1),
                          device_inflight=1)
        var_states = [Arrangement() for _ in range(self.n_iterated)]
        out_states = [Arrangement() for _ in range(self.n_iterated)]
        result_states = [Arrangement() for _ in range(self.n_results)]

        # round 0: feed full current input state
        for i, src in enumerate(iter_sources):
            full = self.input_states[i].as_delta()
            src.op.push(full)
            var_states[i].update(full)
        for j, src in enumerate(extra_sources):
            src.op.push(self.input_states[self.n_iterated + j].as_delta())

        try:
            rounds = 0
            while True:
                outputs = sched.run_time(rounds)
                for i, node in enumerate(iter_out_nodes):
                    out_states[i].update(outputs.get(node.id, _EMPTY))
                for i, node in enumerate(result_nodes):
                    result_states[i].update(outputs.get(node.id, _EMPTY))
                rounds += 1
                if self.limit is not None and rounds >= self.limit:
                    break
                # feedback delta = body output state - variable state
                converged = True
                for i in range(self.n_iterated):
                    fb = _state_diff(var_states[i], out_states[i])
                    if fb:
                        converged = False
                        iter_sources[i].op.push(fb)
                        var_states[i].update(fb)
                if converged:
                    break
        finally:
            sched.close()  # inner pool released even on a failing round
        out = Delta()
        self._result_offsets = []
        for i in range(self.n_results):
            fb = _state_diff(self.emitted[i], result_states[i])
            self._result_offsets.append((len(out.entries), len(fb.entries)))
            # tag rows with result index so the demux downstream can split
            for key, row, diff in fb.entries:
                out.append(key, (i, row), diff)
            self.emitted[i].update(fb)
        return out


class DemuxOperator(Operator):
    """Select the i-th tagged sub-stream of an IterateOperator output."""

    def __init__(self, index: int):
        self.index = index

    def step(self, time, in_deltas):
        delta = in_deltas[0]
        if not delta:
            return Delta()
        return Delta([
            (k, row, d) for k, (i, row), d in delta.entries if i == self.index
        ])


def _state_diff(old: Arrangement, new: Arrangement) -> Delta:
    out = Delta()
    for key, row in old.items():
        nrow = new.get(key)
        if nrow is None or row_fingerprint(nrow) != row_fingerprint(row):
            out.append(key, row, -1)
    for key, row in new.items():
        orow = old.get(key)
        if orow is None or row_fingerprint(orow) != row_fingerprint(row):
            out.append(key, row, 1)
    return out
