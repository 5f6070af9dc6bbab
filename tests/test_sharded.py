"""Sharded multi-worker dataflow execution (engine/graph.py Scheduler with
n_workers > 1): key-routed exchange at stateful operators, per-worker
source partitioning (reference: src/engine/dataflow/shard.rs — shard =
key & mask; exchange on arrange/join/group, dataflow.rs:2276,2904;
per-worker source reads, src/connectors/mod.rs:400).

The contract under test: results are byte-identical for n_workers ∈ {1, 8}
AND the work is actually partitioned (several workers hold disjoint
operator state)."""

from __future__ import annotations

import os

import pytest

import pathway_tpu as pw
from pathway_tpu.engine.delta import row_fingerprint
from pathway_tpu.engine.operators import (ColumnarGroupByOperator,
                                          JoinOperator)
from pathway_tpu.internals.runner import GraphRunner
from tests.utils import T

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

N_WORKERS = 8


def _run_n(tables, n_workers):
    runner = GraphRunner()
    caps = [runner.capture(t) for t in tables]
    runner.run_batch(n_workers=n_workers)
    return caps, runner


def _stream(cap):
    return sorted((k, row_fingerprint(r), t, d)
                  for k, r, t, d in cap.consolidated_events())


def _snap(cap):
    return {k: row_fingerprint(r) for k, r in cap.snapshot().items()}


def _pipeline():
    """groupby + join + filter over an update stream with retractions."""
    sales = T("""
    shop | item | qty | _time | _diff
    s0   | a    | 3   | 2     | 1
    s1   | a    | 1   | 2     | 1
    s2   | b    | 2   | 2     | 1
    s3   | b    | 5   | 4     | 1
    s4   | c    | 7   | 4     | 1
    s0   | a    | 3   | 6     | -1
    s5   | a    | 9   | 6     | 1
    s6   | d    | 2   | 6     | 1
    s7   | c    | 1   | 8     | 1
    """)
    info = T("""
    item | price
    a    | 10
    b    | 20
    c    | 30
    d    | 40
    e    | 50
    """)
    totals = sales.groupby(sales.item).reduce(
        sales.item,
        total_qty=pw.reducers.sum(sales.qty),
        n=pw.reducers.count(),
    )
    joined = totals.join(info, totals.item == info.item).select(
        totals.item, totals.total_qty, info.price,
        revenue=totals.total_qty * info.price,
    )
    big = joined.filter(joined.revenue >= 60)
    return sales, totals, joined, big


def test_groupby_join_identical_across_workers():
    caps1, _ = _run_n(list(_pipeline()), 1)
    capsN, _ = _run_n(list(_pipeline()), N_WORKERS)
    for c1, cN in zip(caps1, capsN):
        assert _stream(c1) == _stream(cN)
        assert _snap(c1) == _snap(cN)


def test_work_is_actually_partitioned():
    # enough distinct keys/groups that >1 of 8 workers must own state
    rows = "\n".join(f"u{i} | g{i % 16} | {i}" for i in range(64))
    t = T("user | grp | x\n" + rows)
    totals = t.groupby(t.grp).reduce(t.grp, s=pw.reducers.sum(t.x))
    joined = totals.join(t, totals.grp == t.grp).select(
        t.user, totals.s)
    _, runner = _run_n([joined], N_WORKERS)
    sched = runner._scheduler
    assert sched.n_workers == N_WORKERS

    def replicas_of(op_type):
        for node in runner.graph.nodes:
            if isinstance(node.op, op_type):
                return sched._replicas[node.id]
        raise AssertionError(f"no {op_type.__name__} node")

    greps = replicas_of(ColumnarGroupByOperator)
    assert len(greps) == N_WORKERS

    def live_groups(rep):
        return [gk for gk, code in rep._by_gkey.items()
                if rep._cnt[code] > 0]

    occupied = [rep for rep in greps if live_groups(rep)]
    assert len(occupied) >= 2, "groupby state not partitioned"
    all_groups = [g for rep in greps for g in live_groups(rep)]
    assert len(all_groups) == len(set(all_groups)) == 16, "shards overlap"

    jreps = replicas_of(JoinOperator)
    occupied_j = [rep for rep in jreps if rep.left or rep.right]
    assert len(occupied_j) >= 2, "join state not partitioned"


def test_source_rows_partitioned_across_workers():
    rows = "\n".join(f"k{i} | {i}" for i in range(32))
    t = T("k | x\n" + rows)
    out = t.select(t.k, y=t.x + 1)
    caps, runner = _run_n([out], N_WORKERS)
    assert len(caps[0].events) == 32
    sched = runner._scheduler
    src = next(n for n in runner.graph.nodes
               if type(n.op).__name__ == "SourceOperator")
    assert len(sched._replicas[src.id]) == N_WORKERS


def test_outer_join_with_nulls_sharded():
    left = T("""
    k  | v
    a  | 1
    b  | 2
    c  |
    """)
    right = T("""
    k  | w
    b  | 20
    d  | 40
    """)
    j = left.join_outer(right, left.k == right.k).select(
        lk=left.k, rk=right.k, v=left.v, w=right.w)
    caps1, _ = _run_n([j], 1)
    capsN, _ = _run_n([j], N_WORKERS)
    assert _stream(caps1[0]) == _stream(capsN[0])


def test_windowed_aggregation_sharded():
    t = T("""
    sensor | v | at | _time
    a      | 1 | 0  | 2
    b      | 2 | 1  | 2
    a      | 3 | 4  | 4
    b      | 4 | 5  | 4
    a      | 5 | 9  | 6
    b      | 6 | 12 | 8
    """)
    win = pw.temporal.windowby(
        t, t.at, window=pw.temporal.tumbling(4), instance=t.sensor,
    ).reduce(
        sensor=pw.this._pw_instance,
        start=pw.this._pw_window_start,
        s=pw.reducers.sum(pw.this.v),
    )
    caps1, _ = _run_n([win], 1)
    capsN, _ = _run_n([win], N_WORKERS)
    assert _snap(caps1[0]) == _snap(capsN[0])


def test_windowby_delay_behavior_sharded():
    # buffered release rides a global watermark shared across workers: the
    # per-tick emission stream (not just the final state) must match n=1
    t = T("""
    sensor | v | at | _time
    a      | 1 | 0  | 2
    b      | 2 | 1  | 2
    a      | 3 | 6  | 4
    b      | 4 | 7  | 4
    a      | 5 | 13 | 6
    """)
    win = pw.temporal.windowby(
        t, t.at, window=pw.temporal.tumbling(4), instance=t.sensor,
        behavior=pw.temporal.common_behavior(delay=4),
    ).reduce(
        sensor=pw.this._pw_instance,
        start=pw.this._pw_window_start,
        s=pw.reducers.sum(pw.this.v),
    )
    caps1, _ = _run_n([win], 1)
    capsN, _ = _run_n([win], N_WORKERS)
    assert _stream(caps1[0]) == _stream(capsN[0])


def test_iterate_gathers_and_matches():
    edges = T("""
    u | v
    a | b
    b | c
    c | a
    c | d
    d | a
    """)
    ranks = pw.stdlib.graphs.pagerank(edges, steps=15)
    caps1, _ = _run_n([ranks], 1)
    capsN, _ = _run_n([ranks], N_WORKERS)
    assert _snap(caps1[0]) == _snap(capsN[0])


def test_concat_and_distinct_universes_sharded():
    a = T("""
    k | x
    p | 1
    q | 2
    """)
    b = T("""
    k | x
    r | 3
    s | 4
    """)
    c = a.concat_reindex(b)
    caps1, _ = _run_n([c], 1)
    capsN, _ = _run_n([c], N_WORKERS)
    assert _snap(caps1[0]) == _snap(capsN[0])


def test_order_sensitive_ops_identical_across_workers():
    # dedup acceptance and earliest/latest tiebreaks use a canonical
    # per-tick order, so exchange partitioning cannot change results
    rows = "\n".join(f"r{i} | g | {i} | {2 * (1 + i // 6)}" for i in range(16))
    t = T("r | g | x | _time\n" + rows)
    ded = t.deduplicate(value=t.x, acceptor=lambda new, old: new > old)
    el = t.groupby(t.g).reduce(
        t.g, e=pw.reducers.earliest(t.x), l=pw.reducers.latest(t.x))
    caps1, _ = _run_n([ded, el], 1)
    capsN, _ = _run_n([ded, el], N_WORKERS)
    for c1, cN in zip(caps1, capsN):
        assert _stream(c1) == _stream(cN)


_MP_PROGRAM = """
import json
import os
import sys

import pathway_tpu as pw

class S(pw.Schema):
    shop: str
    item: str
    qty: int

class I(pw.Schema):
    item: str
    price: int

from pathway_tpu.debug import table_from_rows
from pathway_tpu.engine.multiproc import get_cluster
from pathway_tpu.internals.runner import GraphRunner

rows = []
for i in range(60):
    rows.append((f"s{i % 7}", f"i{i % 13}", i % 9, 2 * (i % 4), 1))
    if i % 11 == 0 and i > 0:
        rows.append(rows[i - 2][:3] + (2 * (i % 4) + 2, -1))
sales = table_from_rows(S, rows, is_stream=True)
info = table_from_rows(I, [(f"i{j}", 10 * (j + 1)) for j in range(13)])
totals = sales.groupby(sales.item).reduce(
    sales.item, qty=pw.reducers.sum(sales.qty), n=pw.reducers.count())
joined = totals.join(info, totals.item == info.item).select(
    totals.item, revenue=totals.qty * info.price)

runner = GraphRunner()
caps = [runner.capture(t) for t in (totals, joined)]
cl = get_cluster()
runner.run_batch(cluster=cl)
out = [sorted((int(k), repr(r), t, d)
              for k, r, t, d in c.consolidated_events()) for c in caps]
# run_batch executes one tick per distinct feed time (incl. 0) plus the
# end-of-stream flush tick — recorded so the test can pin the scheduler's
# STATIC round estimate against the rounds the cluster actually counted
_, feed_times = runner.static_feeds_by_time()
doc = {"caps": out,
       "transports": cl.transport_counts() if cl is not None else {},
       "stats": cl.stats if cl is not None else {},
       "ticks": len({0} | feed_times) + 1,
       "rounds_est": runner._scheduler.exchange_rounds_per_tick()}
with open(sys.argv[1], "w") as f:
    json.dump(doc, f)
"""


@pytest.mark.parametrize("transport,first_port",
                         [("tcp", 19310), ("shm", 19340)])
def test_multi_process_batch_matches_single(tmp_path, transport, first_port):
    """True multi-process execution (engine/multiproc.py): 2 OS processes
    exchange over the requested transport (raw TCP sockets, or the
    shared-memory slab ring with its socket doorbell); the union of their
    captured shards must equal the single-process result, the shards must
    be disjoint (state really partitioned across processes), and the
    forced transport must actually have carried the frames."""
    import json
    import subprocess
    import sys as _sys

    prog = tmp_path / "mp_prog.py"
    prog.write_text(_MP_PROGRAM)
    base_env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
                    PATHWAY_RUN_ID=f"mp-test-{transport}",
                    PATHWAY_EXCHANGE_TRANSPORT=transport)

    def run_procs(n: int, port: int) -> list[dict]:
        handles = []
        for pid in range(n):
            env = dict(base_env, PATHWAY_PROCESSES=str(n),
                       PATHWAY_PROCESS_ID=str(pid),
                       PATHWAY_THREADS="2",
                       PATHWAY_FIRST_PORT=str(port))
            handles.append(subprocess.Popen(
                [_sys.executable, str(prog), str(tmp_path / f"out_{n}_{pid}")],
                env=env, stderr=subprocess.PIPE, text=True))
        outs = []
        for h in handles:
            _, err = h.communicate(timeout=120)
            assert h.returncode == 0, err
        for pid in range(n):
            outs.append(json.loads(
                (tmp_path / f"out_{n}_{pid}").read_text()))
        return outs

    [single] = run_procs(1, first_port)
    shards = run_procs(2, first_port + 10)
    for doc in shards:
        assert doc["transports"] == {transport: 1}
        assert doc["stats"]["rows_out"] > 0
        # the static estimate (exchange_rounds_per_tick) re-states the
        # step loop's batching rules; this pins it to the rounds the
        # cluster ACTUALLY paid so the two copies cannot silently drift
        assert doc["rounds_est"] > 0
        assert doc["stats"]["rounds"] == doc["rounds_est"] * doc["ticks"]
        if transport == "shm":
            # the slab carried the payloads; sockets carried doorbells
            slab = (doc["stats"]["shm_bytes_out"]
                    + doc["stats"]["shm_bytes_in"])
            assert slab > doc["stats"]["bytes_out"]
    for cap_i in range(len(single["caps"])):
        merged = sorted(tuple(e) for s in shards
                        for e in s["caps"][cap_i])
        expect = sorted(tuple(e) for e in single["caps"][cap_i])
        assert merged == expect
        keys0 = {e[0] for e in shards[0]["caps"][cap_i]}
        keys1 = {e[0] for e in shards[1]["caps"][cap_i]}
        assert not (keys0 & keys1)
        assert keys0 and keys1


def test_external_index_sharded_queries_local_data_broadcast():
    """Index op under sharding (reference operators/external_index.rs:97 —
    data broadcast, queries local): results identical at n ∈ {1, 8}, the
    worker replicas share ONE index object (no per-worker slab copies),
    and several replicas answer queries (parallel answering)."""
    from pathway_tpu.engine.index_ops import ExternalIndexOperator
    from pathway_tpu.stdlib.indexing import DataIndex, TantivyBM25

    def build():
        docs = T("""
        text         | _time
        alpha_one    | 2
        beta_two     | 2
        gamma_three  | 4
        alpha_four   | 4
        """)
        rows = "\n".join(
            f"q{i} | {w} | 4" for i, w in enumerate(
                ["alpha_one", "beta_two", "gamma_three", "alpha_four"] * 4))
        queries = T("q | text | _time\n" + rows)
        index = DataIndex(docs, TantivyBM25(docs.text))
        res = index.query_as_of_now(queries.text, number_of_matches=1)
        return res.select(hit=res.text)

    caps1, _ = _run_n([build()], 1)
    capsN, runner = _run_n([build()], N_WORKERS)
    assert _stream(caps1[0]) == _stream(capsN[0])

    sched = runner._scheduler
    node = next(n for n in runner.graph.nodes
                if isinstance(n.op, ExternalIndexOperator))
    reps = sched._replicas[node.id]
    assert len(reps) == N_WORKERS
    # one shared index object across replicas; only replica 0 maintained it
    assert all(r.index is reps[0].index for r in reps)
    assert reps[0]._is_primary and not any(r._is_primary for r in reps[1:])
    answered = [r for r in reps if r.answers]
    assert len(answered) >= 2, "queries not answered in parallel"


def test_gradual_broadcast_sharded_matches_single():
    rows = T("k | x\n" + "\n".join(f"r{i} | {i}" for i in range(24)))
    thr = T("""
    lo | val | hi | _time
    0  | 5   | 10 | 2
    0  | 7   | 10 | 4
    """)
    out = rows._gradual_broadcast(thr, thr.lo, thr.val, thr.hi)
    caps1, _ = _run_n([out], 1)
    capsN, runner = _run_n([out], N_WORKERS)
    assert _stream(caps1[0]) == _stream(capsN[0])
    # rows are actually sharded now (no gather): several replicas hold rows
    from pathway_tpu.engine.operators import GradualBroadcastOperator

    sched = runner._scheduler
    node = next(n for n in runner.graph.nodes
                if isinstance(n.op, GradualBroadcastOperator))
    reps = sched._replicas[node.id]
    assert len(reps) == N_WORKERS
    assert sum(1 for r in reps if r.rows) >= 2


def test_iterate_inner_rounds_sharded():
    edges = T("""
    u | v
    a | b
    b | c
    c | a
    c | d
    d | a
    """)
    ranks = pw.stdlib.graphs.pagerank(edges, steps=15)
    runner = GraphRunner()
    cap = runner.capture(ranks)
    runner.run_batch(n_workers=N_WORKERS)
    from pathway_tpu.engine.graph import IterateOperator

    sched = runner._scheduler
    node = next(n for n in runner.graph.nodes
                if isinstance(n.op, IterateOperator))
    assert node.op.inner_workers == N_WORKERS
    # and the result still matches the single-worker run
    runner1 = GraphRunner()
    cap1 = runner1.capture(pw.stdlib.graphs.pagerank(T("""
    u | v
    a | b
    b | c
    c | a
    c | d
    d | a
    """), steps=15))
    runner1.run_batch(n_workers=1)
    assert _snap(cap) == _snap(cap1)


_MP_DYING = """
import os
import sys
import time

import pathway_tpu as pw
from pathway_tpu.debug import table_from_rows
from pathway_tpu.engine.multiproc import get_cluster
from pathway_tpu.internals.runner import GraphRunner

class S(pw.Schema):
    k: str
    x: int

rows = [(f"k{i}", i, 2 * (1 + i // 10), 1) for i in range(100)]
t = table_from_rows(S, rows, is_stream=True)
g = t.groupby(t.k).reduce(t.k, s=pw.reducers.sum(t.x))
runner = GraphRunner()
runner.capture(g)
if os.environ["PATHWAY_PROCESS_ID"] == "1" and "--die" in sys.argv:
    # simulate a crash after connecting but before finishing the run
    cl = get_cluster()
    time.sleep(0.3)
    os._exit(17)
runner.run_batch(cluster=get_cluster())
print("survived", flush=True)
"""


def test_cluster_peer_death_detected(tmp_path):
    """Failure detection (SURVEY §5): when one process of a cluster dies
    mid-run, its peers must FAIL (EOFError at the next exchange) rather
    than hang — the analogue of the reference's cross-worker panic
    propagation (dataflow.rs:5459-5601)."""
    import subprocess
    import sys as _sys

    prog = tmp_path / "dying.py"
    prog.write_text(_MP_DYING)
    env_base = dict(os.environ, JAX_PLATFORMS="cpu",
                    PYTHONPATH=REPO, PATHWAY_RUN_ID="mp-die")
    handles = []
    for pid in range(2):
        env = dict(env_base, PATHWAY_PROCESSES="2",
                   PATHWAY_PROCESS_ID=str(pid), PATHWAY_THREADS="1",
                   PATHWAY_FIRST_PORT="19710")
        args = [_sys.executable, str(prog)]
        if pid == 1:
            args.append("--die")
        handles.append(subprocess.Popen(args, env=env,
                                        stdout=subprocess.PIPE,
                                        stderr=subprocess.PIPE, text=True))
    out0, err0 = handles[0].communicate(timeout=60)
    out1, _err1 = handles[1].communicate(timeout=60)
    assert handles[1].returncode == 17          # the simulated crash
    assert handles[0].returncode != 0, out0     # peer fails, not hangs
    assert "survived" not in out0
    assert ("EOFError" in err0 or "Connection" in err0
            or "BrokenPipe" in err0 or "closed" in err0), err0[-500:]


def test_exchange_payload_wire_roundtrip():
    """The columnar exchange wire format must be lossless, including
    nested rows/bcast shapes and Pointer-keyed entries (engine/wire.py),
    and the frame must take the columnar kind for entry payloads."""
    from pathway_tpu.engine import wire
    from pathway_tpu.internals.keys import Pointer, hash_values

    ents = [(hash_values("a", i), (f"w{i}", i, None), 1 - 2 * (i % 2))
            for i in range(50)]
    payload = {"rows": {1: {3: ents}}, "wm": 7,
               "bcast": {0: ents[:3]}, "any": True}
    chunks, total, n_rows = wire.encode_frame(("x", 2, 0), payload)
    blob = b"".join(chunks)
    assert total == len(blob)
    assert blob[3] == wire.KIND_COLUMNAR
    assert n_rows == 50  # bcast and wm side-channels excluded
    tag, out, _ = wire.decode_frame(blob)
    assert tag == ("x", 2, 0)
    assert out == payload
    assert all(isinstance(e[0], Pointer) for e in out["rows"][1][3])
    # non-entry lists and scalars pass through untouched
    chunks2, _t, _n = wire.encode_frame("s", {"xs": [1, 2], "s": "x"})
    assert wire.decode_frame(b"".join(chunks2))[1] == \
        {"xs": [1, 2], "s": "x"}


def test_no_phantom_events_for_netzero_pairs_sharded():
    """A projection-collapsed net-zero pair must not surface phantom
    delete+insert events from a sharded join to subscribers."""
    t = T("""
    k | keep | drop | _time | _diff
    a | 1    | 10   | 2     | 1
    a | 1    | 10   | 4     | -1
    a | 1    | 11   | 4     | 1
    """)
    proj = t.select(t.k, t.keep)  # drops the changed column -> net-zero
    lex = T("""
    k | cat
    a | x
    """)
    for mode in ("join", "join_left", "join_outer"):
        j = getattr(proj, mode)(lex, proj.k == lex.k).select(
            proj.keep, lex.cat)
        caps1, _ = _run_n([j], 1)
        capsN, _ = _run_n([j], N_WORKERS)
        assert _stream(caps1[0]) == _stream(capsN[0]), mode


def test_tumbling_fast_path_matches_generic_assignment():
    """The arithmetic tumbling fast path must emit exactly what the
    generic flatten path does — pinned by comparing against
    sliding(hop=duration), which is semantically identical tumbling but
    takes the generic path (incl. retractions and negative times)."""
    t = T("""
    sensor | v | at  | _time | _diff
    a      | 1 | -7  | 2     | 1
    b      | 2 | 0   | 2     | 1
    a      | 3 | 4   | 4     | 1
    b      | 4 | 5   | 4     | 1
    a      | 3 | 4   | 6     | -1
    a      | 5 | 13  | 6     | 1
    """)

    def agg(win):
        return pw.temporal.windowby(
            t, t.at, window=win, instance=t.sensor,
        ).reduce(
            sensor=pw.this._pw_instance,
            start=pw.this._pw_window_start,
            end=pw.this._pw_window_end,
            s=pw.reducers.sum(pw.this.v),
        )

    for kw in ({}, {"offset": 3}, {"origin": -2}):
        fast = agg(pw.temporal.tumbling(4, **kw))
        generic = agg(pw.temporal.sliding(hop=4, duration=4, **kw))
        for n in (1, N_WORKERS):  # tuple-keyed sharding included
            caps, _ = _run_n([fast, generic], n)
            assert _stream(caps[0]) == _stream(caps[1]), (kw, n)
            assert _snap(caps[0]) == _snap(caps[1]), (kw, n)


def test_tumbling_fast_path_float_times():
    t = T("""
    v | at
    1 | 0.5
    2 | 3.9
    3 | 4.1
    """)
    win = pw.temporal.windowby(
        t, t.at + 0.0, window=pw.temporal.tumbling(2.0),
    ).reduce(start=pw.this._pw_window_start,
             s=pw.reducers.count())
    caps, _ = _run_n([win], 1)
    got = sorted(r for r in caps[0].snapshot().values())
    assert got == [(0.0, 1), (2.0, 1), (4.0, 1)]
