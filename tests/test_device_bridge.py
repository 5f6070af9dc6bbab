"""Pipelined device runtime (engine/device_bridge.py + scheduler legs).

Contracts under test:

- pipelined (PATHWAY_DEVICE_INFLIGHT >= 2) and synchronous execution
  produce byte-identical captured streams, for both the device-UDF path
  and the external-KNN-index path;
- backpressure bounds the number of in-flight ticks at the window, for
  any window size (property-style sweep);
- a device leg in flight does not trip the watchdog, and exceptions on
  the bridge worker re-raise (original type) on the host thread;
- crash → restart → replay stays exactly-once with a device leg in the
  pipeline (persistence commits sit behind the resolve barrier);
- satellites: bounded scheduler route cache, zero-copy embedder rows,
  pw.warmup / compilation cache wiring.
"""

from __future__ import annotations

import time as _time

import numpy as np
import pytest

import pathway_tpu as pw
from pathway_tpu.engine.delta import Delta, row_fingerprint
from pathway_tpu.engine.device_bridge import DeviceBridge
from pathway_tpu.engine.graph import CapturedStream, EngineGraph, Scheduler
from pathway_tpu.engine.operators import Operator, OutputOperator
from pathway_tpu.internals import schema as sch
from pathway_tpu.internals.parse_graph import G
from pathway_tpu.internals.runner import GraphRunner
from pathway_tpu.internals.keys import Pointer


@pytest.fixture(autouse=True)
def _fresh():
    G.clear()
    yield
    G.clear()


@pw.udf(batch=True, device=True, deterministic=True, return_type=float)
def _dev_square(xs):
    import jax.numpy as jnp

    return [float(v) for v in
            np.asarray(jnp.square(jnp.asarray(np.asarray(xs, np.float32))))]


def _run_udf_pipeline(monkeypatch, inflight: int):
    from pathway_tpu.debug import table_from_rows

    monkeypatch.setenv("PATHWAY_DEVICE_INFLIGHT", str(inflight))
    G.clear()
    schema = sch.schema_from_types(x=float)
    rows = [(float(i), i // 4, 1) for i in range(32)]
    # a same-stream retraction exercises the deferred leg's diff handling
    rows.append((5.0, 6, -1))
    rows.append((105.0, 6, 1))
    t = table_from_rows(schema, rows, is_stream=True)
    out = t.select(x=t.x, sq=_dev_square(t.x))
    runner = GraphRunner()
    cap = runner.capture(out)
    runner.run_batch(n_workers=1)
    stats = runner._scheduler.bridge_stats()
    G.clear()
    return cap.events, stats


def test_pipelined_udf_byte_identical_to_sync(monkeypatch):
    sync_events, sync_stats = _run_udf_pipeline(monkeypatch, 1)
    pipe_events, pipe_stats = _run_udf_pipeline(monkeypatch, 2)
    assert sync_stats is None  # inflight=1 never builds a bridge
    assert pipe_stats is not None and pipe_stats["legs_resolved"] > 0
    assert pipe_events == sync_events
    assert sync_events  # non-vacuous


def test_pipelined_knn_index_byte_identical_to_sync(monkeypatch):
    def run(inflight: int):
        from pathway_tpu.debug import table_from_rows
        from pathway_tpu.stdlib.indexing import (
            default_brute_force_knn_document_index,
        )

        monkeypatch.setenv("PATHWAY_DEVICE_INFLIGHT", str(inflight))
        G.clear()
        rng = np.random.default_rng(7)
        data_schema = sch.schema_from_types(v=np.ndarray)
        vecs = [rng.random(8, dtype=np.float32) for _ in range(20)]
        data = table_from_rows(
            data_schema, [(v, i // 5, 1) for i, v in enumerate(vecs)],
            is_stream=True)
        q_schema = sch.schema_from_types(qv=np.ndarray, k=int)
        queries = table_from_rows(
            q_schema, [(vecs[3] + 0.01, 4, 2, 1), (vecs[11] + 0.01, 4, 3, 1)],
            is_stream=True)
        index = default_brute_force_knn_document_index(
            data.v, data, dimensions=8)
        res = index.query_as_of_now(queries.qv, number_of_matches=queries.k)
        runner = GraphRunner()
        cap = runner.capture(res)
        runner.run_batch(n_workers=1)
        stats = runner._scheduler.bridge_stats()
        G.clear()
        return cap.events, stats

    sync_events, sync_stats = run(1)
    pipe_events, pipe_stats = run(2)
    assert sync_stats is None
    assert pipe_stats is not None and pipe_stats["legs_resolved"] > 0
    canon = lambda evs: [(k, row_fingerprint(r), t, d)  # noqa: E731
                         for k, r, t, d in evs]
    assert canon(pipe_events) == canon(sync_events)
    assert sync_events


# ---------------------------------------------------------------------------
# backpressure bounds in-flight ticks (property-style over window sizes)
# ---------------------------------------------------------------------------

class _SlowDeviceOp(Operator):
    device_bound = True

    def __init__(self, sleep_s: float):
        self.sleep_s = sleep_s

    def step(self, time, in_deltas):
        _time.sleep(self.sleep_s)
        return in_deltas[0]


def _drive_slow_graph(inflight: int, n_ticks: int, sleep_s: float = 0.01,
                      host_sleep_s: float = 0.0):
    g = EngineGraph()
    src = g.add_source("src")
    dev = g.add_node(_SlowDeviceOp(sleep_s), [src], "dev")
    cap = CapturedStream()
    g.add_node(OutputOperator(cap.on_delta), [dev], "capture")
    sched = Scheduler(g, n_workers=1, device_inflight=inflight)
    depths = []
    for t in range(1, n_ticks + 1):
        sched.push_source(src, Delta([(Pointer(t), (t,), 1)]))
        sched.run_time(t)
        if sched._bridge is not None:
            depths.append(sched._bridge.depth())
        if host_sleep_s:
            _time.sleep(host_sleep_s)  # simulated host-side work
    sched.resolve_barrier()
    stats = sched.bridge_stats()
    sched.close()
    return cap.events, stats, depths


@pytest.mark.parametrize("inflight", [2, 3, 5])
def test_backpressure_bounds_inflight_ticks(inflight):
    events, stats, depths = _drive_slow_graph(inflight, n_ticks=12)
    assert stats["legs_dispatched"] == 12
    assert stats["legs_resolved"] == 12
    # the property: at no point were more than `inflight` ticks in flight
    assert stats["max_depth"] <= inflight
    assert max(depths) <= inflight
    # and the window was actually used (the device is slower than the host)
    assert stats["max_depth"] >= 2
    # byte-identical to the synchronous run
    sync_events, sync_stats, _ = _drive_slow_graph(1, n_ticks=12)
    assert sync_stats is None
    assert events == sync_events


def test_bridge_overlap_is_observable():
    # a balanced pipeline (host work ≈ device work): most legs resolve
    # while the host thread is busy with a later tick, and the bridge's
    # counters make that visible. (With an idle host the bridge correctly
    # reports ~0 overlap: blocking in backpressure is not overlap.)
    _events, stats, _depths = _drive_slow_graph(
        2, n_ticks=10, sleep_s=0.01, host_sleep_s=0.015)
    assert stats["legs_overlapped"] > 0
    assert stats["overlap_ratio"] > 0


# ---------------------------------------------------------------------------
# failure propagation + barrier
# ---------------------------------------------------------------------------

class _BoomError(RuntimeError):
    pass


class _FailingDeviceOp(Operator):
    device_bound = True

    def __init__(self, fail_at_tick: int):
        self.fail_at_tick = fail_at_tick

    def step(self, time, in_deltas):
        if time == self.fail_at_tick:
            raise _BoomError(f"device fault at tick {time}")
        return in_deltas[0]


def test_device_leg_error_reraises_on_host_thread():
    g = EngineGraph()
    src = g.add_source("src")
    g.add_node(_FailingDeviceOp(fail_at_tick=2), [src], "dev")
    sched = Scheduler(g, n_workers=1, device_inflight=2)
    try:
        with pytest.raises(_BoomError):
            for t in range(1, 8):
                sched.push_source(src, Delta([(Pointer(t), (t,), 1)]))
                sched.run_time(t)
            sched.resolve_barrier()  # error surfaces here at the latest
    finally:
        sched.close()


def test_device_leg_error_surfaces_after_external_stop(monkeypatch):
    """A leg that fails right before an external stop must still escape
    pw.run(): teardown drains the bridge without raising, so the runtime
    re-raises the stored error after cleanup (review fix: the stop path
    previously returned success with the tick's outputs missing)."""
    import threading

    from pathway_tpu.engine import streaming as _streaming
    from pathway_tpu.testing.faults import hanging_subject

    monkeypatch.setenv("PATHWAY_DEVICE_INFLIGHT", "2")
    G.clear()
    release = threading.Event()
    subject = hanging_subject([{"x": 1.0}])  # one row, then hang

    t = pw.io.python.read(subject, schema=sch.schema_from_types(x=float),
                          autocommit_duration_ms=10)
    t = t.select(x=t.x, y=_dev_square(t.x))

    def exploding_sink(*a, **k):
        release.wait(10)  # hold the leg until the loop is stopped
        raise _BoomError("sink failure on the device leg")

    pw.io.subscribe(t, exploding_sink)
    box: dict = {}

    def run():
        try:
            pw.run()
        except BaseException as e:  # noqa: BLE001
            box["error"] = e

    th = threading.Thread(target=run, daemon=True)
    th.start()
    deadline = _time.monotonic() + 10.0
    rt = None
    while _time.monotonic() < deadline:
        live = list(_streaming._ACTIVE_RUNTIMES)
        if live and live[0].scheduler._bridge is not None \
                and live[0].scheduler._bridge.depth() > 0:
            rt = live[0]
            break
        _time.sleep(0.005)
    assert rt is not None, "device leg never started"
    rt.stop()  # external stop while the leg is still in flight
    release.set()
    th.join(15.0)
    assert not th.is_alive()
    assert isinstance(box.get("error"), _BoomError)


def test_take_device_error_after_drain_without_raise():
    """The exact swallow window the streaming fix closes: a leg fails,
    nothing submits or barriers afterwards, close() drains silently —
    take_device_error() must still hand the failure back for re-raise."""
    g = EngineGraph()
    src = g.add_source("src")
    g.add_node(_FailingDeviceOp(fail_at_tick=1), [src], "dev")
    sched = Scheduler(g, n_workers=1, device_inflight=2)
    sched.push_source(src, Delta([(Pointer(1), (1,), 1)]))
    sched.run_time(1)  # leg fails on the worker; nothing observes it
    sched.close()  # drain-without-raise (the teardown path)
    err = sched.take_device_error()
    assert isinstance(err, _BoomError)


def test_outputs_view_resolves_on_access():
    g = EngineGraph()
    src = g.add_source("src")
    dev = g.add_node(_SlowDeviceOp(0.05), [src], "dev")
    sched = Scheduler(g, n_workers=1, device_inflight=2)
    try:
        sched.push_source(src, Delta([(Pointer(1), (1,), 1)]))
        outputs = sched.run_time(1)
        # reading a deferred node's delta is a hard resolve barrier
        delta = outputs.get(dev.id)
        assert [e[:2] for e in delta.entries] == [(Pointer(1), (1,))]
        assert sched.bridge_stats()["legs_resolved"] == 1
    finally:
        sched.close()


# ---------------------------------------------------------------------------
# streaming: watchdog with a leg in flight; exactly-once under crash/replay
# ---------------------------------------------------------------------------

def test_watchdog_tick_with_device_leg_in_flight(monkeypatch):
    """A slow (but healthy) device leg must not trip the watchdog: the
    commit loop keeps ticking while legs resolve behind it."""
    from pathway_tpu.testing.faults import flaky_subject

    monkeypatch.setenv("PATHWAY_DEVICE_INFLIGHT", "2")
    G.clear()
    subject = flaky_subject([{"x": float(i)} for i in range(12)],
                            fail_after=0, fail_attempts=0, delay_s=0.01)

    @pw.udf(batch=True, device=True, deterministic=True, return_type=float)
    def slow_dev(xs):
        import jax.numpy as jnp

        _time.sleep(0.05)  # leg outlives several 10 ms commit ticks
        return [float(v) for v in
                np.asarray(jnp.asarray(np.asarray(xs, np.float32)) * 2.0)]

    t = pw.io.python.read(subject, schema=sch.schema_from_types(x=float),
                          autocommit_duration_ms=10)
    out = t.select(x=t.x, y=slow_dev(t.x))
    state = {}

    def on_change(key, row, time, is_addition):
        if is_addition:
            state[row["x"]] = row["y"]

    pw.io.subscribe(out, on_change)
    pw.run(watchdog=pw.WatchdogConfig(tick_deadline_s=20.0,
                                      poll_interval_s=0.05))
    assert state == {float(i): float(i) * 2.0 for i in range(12)}


@pytest.mark.parametrize("autojit", ["0", "1"])
def test_crash_replay_exactly_once_with_device_leg(monkeypatch, autojit):
    """The fault-tolerance contract with a device leg in the pipeline:
    a crash mid-stream, a backoff restart and a fresh-process replay all
    produce the baseline's exact state (persistence checkpoints sit
    behind the resolve barrier). Parametrized over PATHWAY_AUTO_JIT: with
    the tier ON the traceable scoring UDF fuses and its map joins the
    device leg (internals/autojit.py), so the crash points also cover an
    auto-jitted dispatch in flight."""
    from pathway_tpu.internals import autojit as autojit_mod
    from pathway_tpu.internals.retries import FixedDelayRetryStrategy
    from pathway_tpu.testing.faults import flaky_subject

    monkeypatch.setenv("PATHWAY_DEVICE_INFLIGHT", "2")
    monkeypatch.setenv("PATHWAY_AUTO_JIT", autojit)
    # ticks are tiny here: drop the dispatch floor so the fused program
    # actually executes under the crash points
    monkeypatch.setattr(autojit_mod, "MIN_ROWS", 1)
    autojit_mod.reset_stats()
    words = ["a", "b", "a", "c", "b", "a"]

    @pw.udf(batch=True, device=True, deterministic=True, return_type=int)
    def dev_len(ws):
        import jax.numpy as jnp

        arr = jnp.asarray(np.asarray([len(w) for w in ws], np.int32))
        return [int(v) for v in np.asarray(arr + 1)]

    @pw.udf
    def score(wl: int) -> int:
        return wl * 5 + 1

    def run_counts(subject, backend=None, policy=None):
        G.clear()
        t = pw.io.python.read(
            subject, schema=sch.schema_from_types(word=str),
            autocommit_duration_ms=10, persistent_id="devwords",
            connector_policy=policy)
        t = t.select(word=t.word, wl=dev_len(t.word))
        t = t.select(word=t.word, wl=score(t.wl))
        counts = t.groupby(t.word).reduce(
            word=t.word, c=pw.reducers.count(), wl=pw.reducers.max(t.wl))
        state = {}

        def on_change(key, row, time, is_addition):
            if is_addition:
                state[row["word"]] = (row["c"], row["wl"])
            elif state.get(row["word"]) == (row["c"], row["wl"]):
                del state[row["word"]]

        pw.io.subscribe(counts, on_change)
        cfg = None
        if backend is not None:
            cfg = pw.persistence.Config.simple_config(backend)
        pw.run(persistence_config=cfg)
        return state

    rows = [{"word": w} for w in words]
    baseline = run_counts(flaky_subject(rows, fail_after=0, fail_attempts=0))
    assert baseline == {"a": (3, 11), "b": (2, 11), "c": (1, 11)}

    backend = pw.persistence.Backend.mock()
    policy = pw.ConnectorPolicy(
        max_retries=3, retry_strategy=FixedDelayRetryStrategy(delay_ms=20))
    subject = flaky_subject(rows, fail_after=3, fail_attempts=2)
    state = run_counts(subject, backend=backend, policy=policy)
    assert state == baseline
    # the durable log replays to the same state on a fresh process-run
    replay = run_counts(flaky_subject(rows, fail_after=0, fail_attempts=0),
                        backend=backend)
    assert replay == baseline
    if autojit == "1":
        # non-vacuous: the fused program really dispatched under the
        # crash/restart/replay sequence
        stats = autojit_mod.autojit_stats()
        assert stats["programs"] >= 1
        assert (stats["device_dispatches"] + stats["vector_dispatches"]) > 0
        assert stats["demotions"] == 0


# ---------------------------------------------------------------------------
# satellites
# ---------------------------------------------------------------------------

def test_route_cache_cap_parses_env(monkeypatch):
    monkeypatch.setenv("PATHWAY_ROUTE_CACHE_MAX", "2048")
    g = EngineGraph()
    g.add_source("src")
    sched = Scheduler(g, n_workers=2, device_inflight=1)
    try:
        assert sched._route_cache_max == 2048
    finally:
        sched.close()
    monkeypatch.setenv("PATHWAY_ROUTE_CACHE_MAX", "not-a-number")
    sched = Scheduler(g, n_workers=2, device_inflight=1)
    try:
        assert sched._route_cache_max == 1 << 16  # tolerant fallback
    finally:
        sched.close()


def test_route_cache_cap_applied_in_sharded_run(monkeypatch):
    """End-to-end: a high-cardinality instance column routed across
    workers never grows any edge memo past the cap."""
    from pathway_tpu.debug import table_from_rows

    monkeypatch.setenv("PATHWAY_ROUTE_CACHE_MAX", "1024")
    G.clear()
    schema = sch.schema_from_types(k=str, x=int)
    rows = [(f"user-{i}", i, 0, 1) for i in range(1500)]
    t = table_from_rows(schema, rows, is_stream=True)
    counts = t.groupby(t.k).reduce(k=t.k, n=pw.reducers.count())
    runner = GraphRunner()
    runner.capture(counts)
    runner.run_batch(n_workers=2)
    sched = runner._scheduler
    assert all(len(c) <= sched._route_cache_max
               for c in sched._route_cache.values())
    G.clear()


def test_embedder_rows_are_zero_copy_views():
    from pathway_tpu.models.encoder import EncoderConfig, init_params
    from pathway_tpu.xpacks.llm.embedders import JaxEncoderEmbedder
    import jax

    cfg = EncoderConfig(vocab_size=64, hidden=16, layers=1, heads=2,
                        intermediate=32, max_len=32)
    emb = JaxEncoderEmbedder(
        config=cfg, params=init_params(jax.random.PRNGKey(0), cfg),
        max_len=32)
    rows = emb.__wrapped__(["hello world", "second doc", "third"])
    assert len(rows) == 3
    # one host transfer, zero-copy row views into it
    assert all(r.base is not None for r in rows)
    assert all(r.base is rows[0].base for r in rows)
    assert np.shares_memory(rows[0], rows[0].base)


def test_bucket_widths_cover_every_bucket():
    from pathway_tpu.models.encoder import EncoderConfig, init_params
    from pathway_tpu.xpacks.llm.embedders import JaxEncoderEmbedder
    import jax

    cfg = EncoderConfig(vocab_size=64, hidden=16, layers=1, heads=2,
                        intermediate=32, max_len=512)
    emb = JaxEncoderEmbedder(
        config=cfg, params=init_params(jax.random.PRNGKey(0), cfg),
        max_len=512)
    widths = emb.bucket_widths()
    assert len(widths) == 18  # the "~18 shapes" from the bucketing design
    # every bucket the padder can produce is in the warm set
    assert {emb._bucket(n) for n in range(1, 513)} == set(widths)


def test_warmup_compiles_bucket_shapes():
    from pathway_tpu.models.encoder import EncoderConfig, init_params
    from pathway_tpu.xpacks.llm.embedders import JaxEncoderEmbedder
    import jax

    cfg = EncoderConfig(vocab_size=64, hidden=16, layers=1, heads=2,
                        intermediate=32, max_len=48)
    emb = JaxEncoderEmbedder(
        config=cfg, params=init_params(jax.random.PRNGKey(0), cfg),
        max_len=48, max_batch_size=4)
    report = pw.warmup(emb)
    # autojit entries belong to fused programs other tests may have left
    # gc-pending in the weak registry — the encoder ladder is ours
    ladder = [e for e in report["compiled"] if e[0] != "autojit"]
    kinds = [k for k, _shape in ladder]
    assert kinds == ["encode"] * len(emb.bucket_widths())
    shapes = [s for _k, s in ladder]
    assert shapes == [(4, w) for w in emb.bucket_widths()]
    # warmed shapes serve without further compilation (smoke: runs fast)
    out = emb.embed_batch(["a b c", "d"])
    assert out.shape == (2, 16)


def test_warmup_fused_index_leaves_index_empty():
    from pathway_tpu.models.encoder import EncoderConfig, init_params
    from pathway_tpu.ops.knn import BruteForceKnnIndex, DeviceEmbeddingKnnIndex
    from pathway_tpu.xpacks.llm.embedders import JaxEncoderEmbedder
    import jax

    cfg = EncoderConfig(vocab_size=64, hidden=16, layers=1, heads=2,
                        intermediate=32, max_len=32)
    emb = JaxEncoderEmbedder(
        config=cfg, params=init_params(jax.random.PRNGKey(0), cfg),
        max_len=32, max_batch_size=4)
    index = DeviceEmbeddingKnnIndex(
        emb, BruteForceKnnIndex(16, reserved_space=64))
    report = pw.warmup(emb, index=index)
    assert [k for k, _ in report["compiled"] if k != "autojit"] \
        == ["fused_ingest"] * len(emb.bucket_widths())
    assert len(index) == 0  # scratch slots retracted
    # the warmed index still ingests + answers correctly
    index.add_batch([Pointer(1), Pointer(2)], ["hello world", "other doc"])
    (reply,) = index.search([(Pointer(9), "hello world", 1, None)])
    assert reply[0][0] == Pointer(1)


def test_warmup_full_slab_falls_back_and_flushes(monkeypatch):
    """Slab too full for scratch slots mid-sweep: earlier widths' scratch
    removals must still flush (no plain-scatter compile in the first live
    tick) and the remaining widths warm the plain encoder — the dispatch
    the live two-dispatch fallback actually uses."""
    from pathway_tpu.models.encoder import EncoderConfig, init_params
    from pathway_tpu.ops.knn import (BruteForceKnnIndex,
                                     DeviceEmbeddingKnnIndex,
                                     FusedIngestUnplaceable)
    from pathway_tpu.xpacks.llm.embedders import JaxEncoderEmbedder
    import jax

    cfg = EncoderConfig(vocab_size=64, hidden=16, layers=1, heads=2,
                        intermediate=32, max_len=32)
    emb = JaxEncoderEmbedder(
        config=cfg, params=init_params(jax.random.PRNGKey(0), cfg),
        max_len=32, max_batch_size=4)
    index = DeviceEmbeddingKnnIndex(
        emb, BruteForceKnnIndex(16, reserved_space=64))
    widths = emb.bucket_widths()
    real_fused = index._fused
    calls = {"n": 0}

    def fused_then_full(keys, params, ids, lens):
        calls["n"] += 1
        if calls["n"] > 1:  # second width onward: pretend the slab is full
            raise FusedIngestUnplaceable(
                "fused ingest cannot grow the slab (donated shape is "
                "pinned) — reserve capacity up front")
        return real_fused(keys, params, ids, lens)

    index._fused = fused_then_full
    report = pw.warmup(emb, index=index)
    kinds = [k for k, _ in report["compiled"] if k != "autojit"]
    assert kinds == ["fused_ingest"] + ["encode"] * (len(widths) - 1)
    # the width-1 scratch removals were flushed (dirty set drained), so
    # the first live ingest pays no plain-scatter compile for them
    assert not index.inner._dirty
    assert len(index) == 0


@pytest.fixture
def _restore_cache_dir():
    import jax

    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_env_var_set_leaves_jax_config_alone(
        monkeypatch, tmp_path, _restore_cache_dir):
    """JAX_COMPILATION_CACHE_DIR set: jax owns the directory — the program
    must set none in code (the driver places the cache from outside)."""
    import jax

    from pathway_tpu.models.encoder import EncoderConfig
    from pathway_tpu.xpacks.llm.embedders import JaxEncoderEmbedder

    jax.config.update("jax_compilation_cache_dir", "/sentinel/untouched")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "outer"))
    assert pw.enable_compilation_cache() == str(tmp_path / "outer")
    # every call site: the embedder constructor and pw.warmup
    emb = JaxEncoderEmbedder(config=EncoderConfig.tiny(), max_len=32)
    report = pw.warmup()
    assert report["cache_dir"] == str(tmp_path / "outer")
    assert jax.config.jax_compilation_cache_dir == "/sentinel/untouched"
    assert not (tmp_path / "outer").exists()  # nor created: not ours
    del emb


def test_compile_cache_default_is_the_fixed_in_checkout_path(
        monkeypatch, _restore_cache_dir):
    """Unset: on by default, at one fixed path inside the checkout — the
    path is part of the cache key, so it may never be a temp/pid/time
    name."""
    import pathlib

    import jax

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = pathlib.Path(pw.__file__).resolve().parent.parent / ".jax_cache"
    assert pw.enable_compilation_cache() == str(want)
    assert jax.config.jax_compilation_cache_dir == str(want)
    assert want.is_dir()
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    assert pw.enable_compilation_cache() == str(want)  # idempotent


def test_device_bridge_standalone_fifo_order():
    bridge = DeviceBridge(max_inflight=2)
    order = []
    for t in range(5):
        bridge.submit(t, lambda t=t: order.append(t))
    bridge.barrier()
    bridge.close()
    assert order == list(range(5))
    stats = bridge.stats()
    assert stats["legs_resolved"] == 5
    assert stats["depth"] == 0
