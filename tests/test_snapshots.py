"""Bounded-time crash recovery: operator-state snapshots + WAL compaction
(engine/persistence.py snapshot tier, engine/graph.py scheduler hooks,
engine/streaming.py snapshot pass).

Proves the PR-10 acceptance contract:
- a run restored from snapshot + WAL-suffix replay produces output
  byte-identical to full-WAL replay and to a clean synchronous run, at
  random crash points including the NEW snapshot/compaction boundaries
  (``persistence.snapshot.write``, ``persistence.compact.truncate``);
- a corrupt newest snapshot falls back one generation (the WAL keeps the
  suffix back to the oldest retained generation);
- compaction truncates exactly the covered prefix (``MockLog`` grows the
  same truncate API so this is unit-testable without a filesystem);
- a mid-log corrupt record (not just a torn tail) is detected by the
  per-record CRC and truncated at, loudly;
- clean shutdown of an idle stream writes no empty generations.
"""

from __future__ import annotations

import glob
import json
import os
import random
import shutil

import pytest

import pathway_tpu as pw
from pathway_tpu.internals.parse_graph import G
from pathway_tpu.testing import faults
from pathway_tpu.testing.faults import InjectedFault, flaky_subject

WORDS = ["a", "b", "a", "c", "b", "a"]


@pytest.fixture(autouse=True)
def _fresh():
    G.clear()
    faults.reset()
    yield
    G.clear()
    faults.reset()


def _rows(words):
    return [{"word": w} for w in words]


def _run_counts_with_device_leg(subject, *, inflight, monkeypatch,
                                backend=None, **run_kwargs):
    """Word-count pipeline with a traceable device UDF, so the snapshot
    pass exercises the watermark wait against a real bridge."""
    import numpy as np

    monkeypatch.setenv("PATHWAY_DEVICE_INFLIGHT", str(inflight))
    G.clear()

    @pw.udf(batch=True, device=True, deterministic=True, return_type=int)
    def dev_len(ws):
        import jax.numpy as jnp

        arr = jnp.asarray(np.asarray([len(w) for w in ws], np.int32))
        return [int(v) for v in np.asarray(arr)]

    t = pw.io.python.read(
        subject, schema=pw.schema_from_types(word=str),
        autocommit_duration_ms=10, persistent_id="snap-words")
    t = t.select(word=t.word, wl=dev_len(t.word))
    counts = t.groupby(t.word).reduce(word=t.word, c=pw.reducers.count())
    state: dict[str, int] = {}

    def on_change(key, row, time, is_addition):
        if is_addition:
            state[row["word"]] = row["c"]
        elif state.get(row["word"]) == row["c"]:
            del state[row["word"]]

    pw.io.subscribe(counts, on_change)
    cfg = None
    if backend is not None:
        cfg = pw.persistence.Config.simple_config(backend)
    pw.run(persistence_config=cfg, **run_kwargs)
    return state


def _as_bytes(state: dict) -> bytes:
    return json.dumps(sorted(state.items())).encode()


# ---------------------------------------------------------------------------
# log-level units: truncation + per-record CRC
# ---------------------------------------------------------------------------

def test_mocklog_truncate_drops_covered_records_in_place():
    from pathway_tpu.engine.persistence import MockLog

    store: dict = {}
    log = MockLog(store, "s")
    log.append(1, [("k1", ("a",), 1, None)])
    log.append(3, [("k2", ("b",), 1, None), ("k3", ("c",), 1, None)])
    log.append(5, [("k4", ("d",), 1, None)])
    alias = store["s"]  # other holders of the list must see the compaction
    assert log.truncate_to(3) == 3
    assert [t for t, _ in store["s"]] == [5]
    assert alias is store["s"]
    assert log.truncate_to(3) == 0  # idempotent: nothing left to drop


def test_snapshotlog_truncate_keeps_suffix_and_appends_continue(tmp_path):
    from pathway_tpu.engine.persistence import SnapshotLog

    path = str(tmp_path / "s.snap")
    log = SnapshotLog(path)
    log.append(1, [("k1", ("a",), 1, None)])
    log.append(4, [("k2", ("b",), 1, None)])
    log.append(6, [("k3", ("c",), 1, None)])
    assert log.truncate_to(4) == 2
    assert [t for t, _ in SnapshotLog(path).read_all()] == [6]
    # the log stays appendable after the atomic rewrite
    log.append(8, [("k4", ("d",), 1, None)])
    log.close()
    assert [t for t, _ in SnapshotLog(path).read_all()] == [6, 8]


def test_midlog_corruption_truncates_at_first_bad_record_loudly(
        tmp_path, caplog):
    """A corrupted record WITH records behind it is mid-log corruption:
    per-record CRC catches it before the unpickler, recovery truncates at
    the first bad record and says so at ERROR level (a torn tail stays a
    quiet warning)."""
    from pathway_tpu.engine.persistence import _HDR, _MAGIC, SnapshotLog

    path = str(tmp_path / "s.snap")
    log = SnapshotLog(path)
    log.append(1, [("k1", ("a",), 1, None)])
    log.append(2, [("k2", ("b",), 1, None)])
    log.append(3, [("k3", ("c",), 1, None)])
    log.close()
    with open(path, "rb") as f:
        data = bytearray(f.read())
    # flip a payload byte of the SECOND record
    pos = len(_MAGIC)
    length, _crc = _HDR.unpack_from(data, pos)
    second = pos + _HDR.size + length
    data[second + _HDR.size] ^= 0xFF
    with open(path, "wb") as f:
        f.write(bytes(data))
    import logging

    with caplog.at_level(logging.ERROR,
                         logger="pathway_tpu.engine.persistence"):
        records = SnapshotLog(path).read_all()
    assert [t for t, _ in records] == [1]  # truncated at the bad record
    assert any("mid-log" in r.message for r in caplog.records)


def test_append_corrupt_fault_point_writes_detectable_corruption(tmp_path):
    from pathway_tpu.engine.persistence import SnapshotLog

    path = str(tmp_path / "s.snap")
    log = SnapshotLog(path)
    log.append(1, [("k1", ("a",), 1, None)])
    action = faults.CorruptPayload(k=1)
    with faults.arm("persistence.append.corrupt", action):
        log.append(2, [("k2", ("b",), 1, None)])
    log.append(3, [("k3", ("c",), 1, None)])
    log.close()
    assert action.corrupted == 1
    # the corrupt record (and, mid-log, everything after it) is dropped —
    # never fed to the unpickler
    assert [t for t, _ in SnapshotLog(path).read_all()] == [1]


# ---------------------------------------------------------------------------
# driver-level: snapshot write, compaction, retention (mock backend —
# no filesystem needed, per the MockLog satellite)
# ---------------------------------------------------------------------------

def _driver_with_source(backend):
    from pathway_tpu.engine.persistence import PersistenceDriver
    from pathway_tpu.io._datasource import CallbackSource, Session

    driver = PersistenceDriver(pw.persistence.Config.simple_config(backend))
    src = CallbackSource(lambda: iter(()), pw.schema_from_types(x=int))
    src.persistent_id = "snap-unit"
    rec = driver.attach_source(src, Session())
    return driver, rec


def test_driver_snapshot_compacts_wal_and_manifests_coverage(monkeypatch):
    monkeypatch.setenv("PATHWAY_SNAPSHOT_KEEP_GENERATIONS", "1")
    backend = pw.persistence.Backend.mock()
    driver, rec = _driver_with_source(backend)
    for tick in (1, 2, 3):
        rec.push(f"k{tick}", (tick,), 1)
        driver.seal(tick)
        driver.commit(tick, watermark=tick)
    assert driver.wal_replayable_entries == 3
    assert driver.write_snapshot(3, {"nodes": {}}) is True
    # WAL truncated to the suffix past the (only) generation's tick
    assert backend._mock_store["snap-unit"] == []
    assert driver.wal_replayable_entries == 0
    assert driver.compactions_total == 1
    meta = backend._mock_snapshots[-1]
    assert meta["snapshot_tick"] == 3
    assert meta["sources"]["snap-unit"]["covered"] == 3
    # no-churn guard: the watermark did not advance -> no new generation
    assert driver.write_snapshot(3, {"nodes": {}}) is False
    assert len(backend._mock_snapshots) == 1
    # a fresh driver restores the snapshot tick as its durability frontier
    from pathway_tpu.engine.persistence import PersistenceDriver

    assert PersistenceDriver(
        pw.persistence.Config.simple_config(backend)).restore_time() == 3


def test_retention_truncates_only_to_oldest_kept_generation(monkeypatch):
    """KEEP_GENERATIONS=2: after generation N lands, the WAL keeps the
    suffix past generation N-1's tick — corrupt-N fallback to N-1 always
    finds its records."""
    monkeypatch.setenv("PATHWAY_SNAPSHOT_KEEP_GENERATIONS", "2")
    backend = pw.persistence.Backend.mock()
    driver, rec = _driver_with_source(backend)
    for tick in (1, 2, 3):
        rec.push(f"k{tick}", (tick,), 1)
        driver.seal(tick)
        driver.commit(tick, watermark=tick)
        assert driver.write_snapshot(tick, {"nodes": {}}) is True
    gens = [m["generation"] for m in backend._mock_snapshots]
    assert len(gens) == 2  # oldest pruned
    # WAL truncated to the OLDEST KEPT generation's tick (2), not 3 —
    # the tick-3 record is physically retained for gen-2 fallback, but a
    # normal-path restart (gen 3) replays nothing
    assert [t for t, _ in backend._mock_store["snap-unit"]] == [3]
    assert driver.wal_replayable_entries == 0


def test_corrupt_generation_never_occupies_a_retention_slot(monkeypatch):
    """A corrupt generation must not count toward KEEP_GENERATIONS: it
    would prune the valid fallback and truncate the WAL to a tick only
    the corrupt generation covers."""
    monkeypatch.setenv("PATHWAY_SNAPSHOT_KEEP_GENERATIONS", "2")
    backend = pw.persistence.Backend.mock()
    driver, rec = _driver_with_source(backend)
    for tick in (1, 2):
        rec.push(f"k{tick}", (tick,), 1)
        driver.seal(tick)
        driver.commit(tick, watermark=tick)
        assert driver.write_snapshot(tick, {"nodes": {}}) is True
    # corrupt generation 2's state blob in place (bit rot at rest)
    meta2 = backend._mock_snapshots[-1]
    assert meta2["generation"] == 2
    meta2["state"] = meta2["state"][:-1] + bytes(
        [meta2["state"][-1] ^ 0xFF])
    # a FRESH driver (no validity cache) writes generation 3
    from pathway_tpu.engine.persistence import PersistenceDriver

    d2 = PersistenceDriver(pw.persistence.Config.simple_config(backend))
    from pathway_tpu.io._datasource import CallbackSource, Session

    src = CallbackSource(lambda: iter(()), pw.schema_from_types(x=int))
    src.persistent_id = "snap-unit"
    rec2 = d2.attach_source(src, Session())
    # the prefix-replay protocol expects the reader to re-emit the two
    # covered entries first (skipped), then the genuinely new row
    rec2.push("k1", (1,), 1)
    rec2.push("k2", (2,), 1)
    rec2.push("k3", (3,), 1)
    d2.seal(3)
    d2.commit(3, watermark=3)
    assert d2.write_snapshot(3, {"nodes": {}}) is True
    kept = [m["generation"] for m in backend._mock_snapshots]
    assert kept == [1, 3]  # corrupt 2 pruned, VALID 1 kept as fallback
    # WAL truncated only to gen 1's tick: gen-1 fallback keeps records
    # (1, 3] — including tick 2, which only the corrupt gen covered
    assert [t for t, _ in backend._mock_store["snap-unit"]] == [2, 3]


def test_snapshot_skipped_cleanly_on_object_store_backends():
    """S3/azure backends keep WAL-only recovery: write_snapshot is a
    loud no-op, never an exception in the commit loop."""
    from pathway_tpu.engine.persistence import PersistenceDriver

    driver = PersistenceDriver.__new__(PersistenceDriver)
    driver.kind = "s3"
    driver.snapshots_supported = False
    driver._snapshot_warned = False
    driver.last_snapshot_tick = 0
    assert driver.write_snapshot(5, {"nodes": {}}) is False
    assert driver._snapshot_warned


# ---------------------------------------------------------------------------
# streaming-level recovery equivalence
# ---------------------------------------------------------------------------

def test_streaming_snapshot_restart_byte_identical(monkeypatch, tmp_path):
    """Restart restored from snapshot + suffix replay serializes to the
    identical subscriber state as the no-persistence baseline."""
    baseline = _run_counts_with_device_leg(
        flaky_subject(_rows(WORDS), fail_after=0, fail_attempts=0),
        inflight=2, monkeypatch=monkeypatch)
    assert baseline == {"a": 3, "b": 2, "c": 1}
    monkeypatch.setenv("PATHWAY_SNAPSHOT_EVERY_TICKS", "2")
    backend = pw.persistence.Backend.filesystem(str(tmp_path / "p"))
    first = _run_counts_with_device_leg(
        flaky_subject(_rows(WORDS), fail_after=0, fail_attempts=0,
                      delay_s=0.02),
        inflight=2, monkeypatch=monkeypatch, backend=backend)
    assert _as_bytes(first) == _as_bytes(baseline)
    snaps = glob.glob(str(tmp_path / "p" / "snapshots" / "*.json"))
    assert snaps, "no snapshot generation was written"
    state = _run_counts_with_device_leg(
        flaky_subject(_rows(WORDS), fail_after=0, fail_attempts=0),
        inflight=2, monkeypatch=monkeypatch, backend=backend)
    assert _as_bytes(state) == _as_bytes(baseline)


# every watermark/snapshot/compaction boundary the recovery path crosses
_SNAP_POINTS = ("bridge.leg.exec", "persistence.commit",
                "persistence.fsync", "persistence.snapshot.write",
                "persistence.compact.truncate")


def test_property_random_crash_points_snapshot_recovery(monkeypatch,
                                                        tmp_path):
    """Property test (seeded): for random crash points across the
    watermark AND snapshot/compaction boundaries, snapshot+suffix-replay
    recovery is byte-identical to the clean baseline — including crashes
    landing between snapshot-durable and WAL-truncate."""
    monkeypatch.setenv("PATHWAY_PERSISTENCE_WRITE_RETRIES", "0")
    baseline = _run_counts_with_device_leg(
        flaky_subject(_rows(WORDS), fail_after=0, fail_attempts=0),
        inflight=1, monkeypatch=monkeypatch)
    monkeypatch.setenv("PATHWAY_SNAPSHOT_EVERY_TICKS", "2")
    rng = random.Random(int(os.environ.get("SNAPSHOT_SWEEP_SEED", "7")))
    for round_i in range(5):
        backend = pw.persistence.Backend.filesystem(
            str(tmp_path / f"p{round_i}"))
        point = rng.choice(_SNAP_POINTS)
        k = rng.randint(1, 6)
        with faults.arm(point, faults.FailOnHit(k)):
            try:
                _run_counts_with_device_leg(
                    flaky_subject(_rows(WORDS), fail_after=0,
                                  fail_attempts=0, delay_s=0.02),
                    inflight=4, monkeypatch=monkeypatch, backend=backend,
                    terminate_on_error=True)
            except InjectedFault:
                pass  # the seeded crash
        faults.reset()
        state = _run_counts_with_device_leg(
            flaky_subject(_rows(WORDS), fail_after=0, fail_attempts=0),
            inflight=4, monkeypatch=monkeypatch, backend=backend)
        assert _as_bytes(state) == _as_bytes(baseline), \
            f"round {round_i}: {point!r} hit {k}"


@pytest.mark.parametrize("inflight", [1, 2, 4])
@pytest.mark.parametrize("point", ["persistence.snapshot.write",
                                   "persistence.compact.truncate"])
def test_crash_sweep_snapshot_points_byte_identical(point, inflight,
                                                    monkeypatch, tmp_path):
    """The PR-8 crash sweep extended to the snapshot tier: a crash at
    either snapshot/compaction boundary, at any in-flight depth, recovers
    byte-identical exactly-once. (snapshot.write: the generation does not
    exist yet — previous generation + full WAL recover; compact.truncate:
    the generation exists and covered WAL records are ignored.)"""
    monkeypatch.setenv("PATHWAY_PERSISTENCE_WRITE_RETRIES", "0")
    baseline = _run_counts_with_device_leg(
        flaky_subject(_rows(WORDS), fail_after=0, fail_attempts=0),
        inflight=1, monkeypatch=monkeypatch)
    monkeypatch.setenv("PATHWAY_SNAPSHOT_EVERY_TICKS", "2")
    backend = pw.persistence.Backend.filesystem(str(tmp_path / "p"))
    k = 1 + (len(point) + inflight) % 2
    with faults.arm(point, faults.FailOnHit(k)):
        try:
            _run_counts_with_device_leg(
                flaky_subject(_rows(WORDS), fail_after=0, fail_attempts=0,
                              delay_s=0.02),
                inflight=inflight, monkeypatch=monkeypatch,
                backend=backend, terminate_on_error=True)
        except InjectedFault:
            pass  # the crash (the point may not fire on quiet pacing)
    faults.reset()
    state = _run_counts_with_device_leg(
        flaky_subject(_rows(WORDS), fail_after=0, fail_attempts=0),
        inflight=inflight, monkeypatch=monkeypatch, backend=backend)
    assert _as_bytes(state) == _as_bytes(baseline)


def test_crash_between_snapshot_durable_and_wal_truncate(monkeypatch,
                                                         tmp_path):
    """The compaction edge: generation N is durable but the WAL still
    holds covered records. Restart must load N and IGNORE them (replaying
    them on top of restored state would double-count)."""
    monkeypatch.setenv("PATHWAY_PERSISTENCE_WRITE_RETRIES", "0")
    baseline = _run_counts_with_device_leg(
        flaky_subject(_rows(WORDS), fail_after=0, fail_attempts=0),
        inflight=1, monkeypatch=monkeypatch)
    monkeypatch.setenv("PATHWAY_SNAPSHOT_EVERY_TICKS", "2")
    backend = pw.persistence.Backend.filesystem(str(tmp_path / "p"))
    with faults.arm("persistence.compact.truncate", faults.FailOnHit(1)):
        try:
            _run_counts_with_device_leg(
                flaky_subject(_rows(WORDS), fail_after=0, fail_attempts=0,
                              delay_s=0.02),
                inflight=2, monkeypatch=monkeypatch, backend=backend,
                terminate_on_error=True)
        except InjectedFault:
            pass
    faults.reset()
    state = _run_counts_with_device_leg(
        flaky_subject(_rows(WORDS), fail_after=0, fail_attempts=0),
        inflight=2, monkeypatch=monkeypatch, backend=backend)
    assert _as_bytes(state) == _as_bytes(baseline)


def test_corrupt_newest_snapshot_falls_back_one_generation(monkeypatch,
                                                           tmp_path,
                                                           caplog):
    """Checksum-verified load: a corrupt newest generation falls back to
    N-1 (whose WAL suffix the retention window preserved) and recovers
    byte-identically, logging the fallback."""
    baseline = _run_counts_with_device_leg(
        flaky_subject(_rows(WORDS), fail_after=0, fail_attempts=0),
        inflight=1, monkeypatch=monkeypatch)
    monkeypatch.setenv("PATHWAY_SNAPSHOT_EVERY_TICKS", "2")
    monkeypatch.setenv("PATHWAY_SNAPSHOT_KEEP_GENERATIONS", "2")
    backend = pw.persistence.Backend.filesystem(str(tmp_path / "p"))
    _run_counts_with_device_leg(
        flaky_subject(_rows(WORDS), fail_after=0, fail_attempts=0,
                      delay_s=0.02),
        inflight=2, monkeypatch=monkeypatch, backend=backend)
    states = sorted(glob.glob(str(tmp_path / "p" / "snapshots" / "*.state")))
    assert len(states) >= 2, "test needs at least two generations"
    with open(states[-1], "r+b") as f:
        f.seek(16)
        b = f.read(1)
        f.seek(16)
        f.write(bytes([b[0] ^ 0xFF]))
    import logging

    with caplog.at_level(logging.ERROR,
                         logger="pathway_tpu.engine.persistence"):
        state = _run_counts_with_device_leg(
            flaky_subject(_rows(WORDS), fail_after=0, fail_attempts=0),
            inflight=2, monkeypatch=monkeypatch, backend=backend)
    assert _as_bytes(state) == _as_bytes(baseline)
    assert any("falling back one generation" in r.message
               for r in caplog.records)


def test_snapshot_suffix_replay_equals_full_wal_replay(monkeypatch,
                                                       tmp_path):
    """With compaction off, the same persistence root recovers two ways —
    snapshot+suffix vs full-WAL (PATHWAY_SNAPSHOT_RESTORE=0) — and the
    serialized states are byte-identical."""
    monkeypatch.setenv("PATHWAY_SNAPSHOT_EVERY_TICKS", "2")
    monkeypatch.setenv("PATHWAY_SNAPSHOT_COMPACT", "0")
    root = tmp_path / "p"
    backend = pw.persistence.Backend.filesystem(str(root))
    _run_counts_with_device_leg(
        flaky_subject(_rows(WORDS), fail_after=0, fail_attempts=0,
                      delay_s=0.02),
        inflight=2, monkeypatch=monkeypatch, backend=backend)
    assert glob.glob(str(root / "snapshots" / "*.json"))
    root2 = tmp_path / "p2"
    shutil.copytree(root, root2)
    empty = flaky_subject([], fail_after=99, fail_attempts=0)
    via_snapshot = _run_counts_with_device_leg(
        empty, inflight=2, monkeypatch=monkeypatch,
        backend=pw.persistence.Backend.filesystem(str(root)))
    monkeypatch.setenv("PATHWAY_SNAPSHOT_RESTORE", "0")
    empty2 = flaky_subject([], fail_after=99, fail_attempts=0)
    via_wal = _run_counts_with_device_leg(
        empty2, inflight=2, monkeypatch=monkeypatch,
        backend=pw.persistence.Backend.filesystem(str(root2)))
    assert _as_bytes(via_snapshot) == _as_bytes(via_wal)
    assert via_snapshot == {"a": 3, "b": 2, "c": 1}


def test_idle_shutdown_writes_no_empty_generation(monkeypatch, tmp_path):
    """Clean shutdown with no new durable data since the last snapshot
    must not churn a new generation (PersistenceDriver close-path
    guard)."""
    monkeypatch.setenv("PATHWAY_SNAPSHOT_EVERY_TICKS", "2")
    backend = pw.persistence.Backend.filesystem(str(tmp_path / "p"))
    _run_counts_with_device_leg(
        flaky_subject(_rows(WORDS), fail_after=0, fail_attempts=0,
                      delay_s=0.02),
        inflight=2, monkeypatch=monkeypatch, backend=backend)
    before = sorted(glob.glob(str(tmp_path / "p" / "snapshots" / "*.json")))
    assert before
    # rerun: the reader re-emits the identical prefix, all skipped — no
    # new durable entries, so no new generation
    _run_counts_with_device_leg(
        flaky_subject(_rows(WORDS), fail_after=0, fail_attempts=0),
        inflight=2, monkeypatch=monkeypatch, backend=backend)
    after = sorted(glob.glob(str(tmp_path / "p" / "snapshots" / "*.json")))
    assert after == before


# ---------------------------------------------------------------------------
# operator/index capture units
# ---------------------------------------------------------------------------

def test_multiset_reducer_state_rekeys_fingerprints_on_load():
    """Fingerprint-keyed reducer state must re-key on restore: string
    hash() varies with the process hash seed, so a snapshot restored in a
    new interpreter would otherwise never match later retractions. The
    fake foreign fingerprints below stand in for another process's."""
    from pathway_tpu.engine.delta import row_fingerprint
    from pathway_tpu.engine.reducers import _MaxState

    st = _MaxState()
    st.add(("apple",), 1)
    st.add(("pear",), 1)
    dumped = st.state_dict()
    # simulate a foreign hash seed: shift every stored fingerprint
    dumped["counts"] = {fp + 1: c for fp, c in dumped["counts"].items()}
    dumped["values"] = {fp + 1: v for fp, v in dumped["values"].items()}
    fresh = _MaxState()
    fresh.load_state(dumped)
    assert set(fresh.values) == {row_fingerprint(("apple",)),
                                 row_fingerprint(("pear",))}
    fresh.add(("pear",), -1)  # the retraction must find its entry
    assert fresh.emit() == "apple"


_RESTORE_IN_NEW_PROCESS = """
import json, pickle, sys
from pathway_tpu.engine import reducers
from pathway_tpu.engine.persistence import _safe_loads
from pathway_tpu.engine.reducers import make_reducer_state
# an order kept from few entries on, for every kind
reducers._MultisetState._ORDER_FROM = reducers._SortedTupleState._ORDER_FROM = 32
with open(sys.argv[1], "rb") as f:
    case = _safe_loads(f.read())
out = {}
for name, (kwargs, state, later) in case.items():
    st = make_reducer_state(name, **kwargs)
    st.load_state(state)
    answers = [repr(st.emit())]
    for args, diff in later:
        st.add(args, diff)
        answers.append(repr(st.emit()))
    out[name] = [answers, st.rederived, st._order is not None]
print(json.dumps(out))
"""


def test_ordered_multiset_states_restore_under_another_hash_seed(tmp_path):
    """``state_dict()`` through the restricted unpickler into a process
    with another string-hash seed: ``load_state`` re-keys the fingerprints
    and derives the order again, so the retractions that follow find their
    entries and every answer equals the writer's."""
    import pickle
    import subprocess
    import sys

    from pathway_tpu.engine.reducers import make_reducer_state

    words = ["w%03d" % ((i * 37) % 101) for i in range(60)]
    feeds = {
        "min": ({}, [((w,), 1) for w in words]),
        "max": ({}, [((w,), 1) for w in words]),
        "argmin": ({}, [((len(w) + i % 3, w), 1)
                        for i, w in enumerate(words)]),
        "argmax": ({}, [((i % 5, w), 1) for i, w in enumerate(words)]),
        # picked by fingerprint: ints, whose hash no seed moves
        "any": ({}, [((i * 7919,), 1) for i in range(60)]),
        "unique": ({}, [(("same",), 1)] + [((w,), -1) for w in words[:40]]),
        "sorted_tuple": ({"skip_nones": True},
                         [((w,), 1) for w in words] + [((None,), 1)]),
        "tuple": ({}, [((w, 1000 - i), 1) for i, w in enumerate(words)]
                  + [((words[0], 1000), 1)]),
        "ndarray": ({}, [((w, i * 7 % 60), 1)
                         for i, w in enumerate(words)]),
    }
    case, expected = {}, {}
    for name, (kwargs, feed) in feeds.items():
        st = make_reducer_state(name, **kwargs)
        for args, diff in feed:
            st.add(args, diff)
        # a copy: the state goes on below, and its dict is its own dicts
        state = pickle.loads(pickle.dumps(st.state_dict()))
        assert set(state) - {"skip_nones"} == {"counts", "values", "n"}
        # what follows in the new process: retractions of entries the
        # snapshot holds (the smallest and the largest among them), then
        # an insertion
        later = [(a, -1) for a, d in (feed[1:4] + feed[-3:-1]) if d > 0] \
            + [feed[5]]
        if name == "unique":
            later = [(feed[1][0], 1), (feed[1][0], -1)]
        case[name] = (kwargs, state, later)
        answers = [repr(st.emit())]
        for args, diff in later:
            st.add(args, diff)
            answers.append(repr(st.emit()))
        expected[name] = answers
    blob = tmp_path / "states.pickle"
    blob.write_bytes(pickle.dumps(case, protocol=pickle.HIGHEST_PROTOCOL))
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=repo, JAX_PLATFORMS="cpu",
               PYTHONHASHSEED="4242")
    res = subprocess.run(
        [sys.executable, "-c", _RESTORE_IN_NEW_PROCESS, str(blob)],
        env=env, capture_output=True, text=True, timeout=120)
    assert res.returncode == 0, res.stderr[-2000:]
    got = json.loads(res.stdout.strip().splitlines()[-1])
    for name, (answers, rederived, ordered) in got.items():
        assert answers == expected[name], name
        assert rederived == 1 and ordered, name


@pytest.mark.parametrize("padded", [False, True], ids=["small", "ordered"])
@pytest.mark.parametrize("name,kwargs,entries,pad,expect", [
    ("max", {}, [(("b",), 1), (("c",), 2), (("a",), 1)],
     lambda i: ("b%02d" % i,), "c"),
    ("min", {}, [(("b",), 1), (("c",), 2), (("a",), -1)],
     lambda i: ("b%02d" % i,), "b"),
    ("sorted_tuple", {"skip_nones": False},
     [((3,), 1), ((1,), 2), ((2,), 1)], lambda i: (2.5 + i / 1000,),
     (1, 1, 2, 3)),
    ("tuple", {"skip_nones": True},
     [(("x", 30), 2), ((None, 10), 1), (("y", 20), 1)],
     lambda i: ("p%02d" % i, 15 + i / 1000), ("y", "x", "x")),
])
def test_multiset_state_restores_from_the_shape_before_it_kept_order(
        name, kwargs, entries, pad, expect, padded):
    """A snapshot written before the states kept their order holds
    ``counts``, ``values``, ``n`` (and ``skip_nones``) under the writer's
    fingerprints; it restores to the same answers, also in a group large
    enough to be ordered, and the state still writes that shape."""
    from pathway_tpu.engine.reducers import REDUCER_FACTORIES, make_reducer_state

    padding = [(pad(i), 1) for i in range(REDUCER_FACTORIES[name]._ORDER_FROM)] \
        if padded else []
    held = entries[:2] + padding + entries[2:]
    old = {
        "counts": {1000 + i: c for i, (_, c) in enumerate(held)},
        "values": {1000 + i: args for i, (args, _) in enumerate(held)},
        "n": sum(c for _, c in held),
        **kwargs,
    }
    st = make_reducer_state(name, **kwargs)
    st.load_state(old)
    st.emit()   # a restored group of _ORDER_FROM or more is ordered when read
    assert (st._order is not None) == padded
    for args, c in padding:
        st.add(args, -c)
    assert st.emit() == expect
    assert st.state_dict().keys() == old.keys()


def test_buffer_operator_rekeys_held_rows_on_restore():
    from pathway_tpu.engine.delta import Delta, row_fingerprint
    from pathway_tpu.engine.temporal_ops import BufferOperator

    op = BufferOperator(threshold_fn=lambda k, r: 100,
                        time_fn=lambda k, r: 1)
    op.step(1, [Delta([("k1", ("x", 100), 1)])])  # held: threshold ahead
    assert op.held
    dumped = op.snapshot_state()
    dumped["held"] = {(k, fp + 1): v
                      for (k, fp), v in dumped["held"].items()}
    fresh = BufferOperator(threshold_fn=lambda k, r: 100,
                           time_fn=lambda k, r: 1)
    fresh.restore_state(dumped)
    assert set(fresh.held) == {("k1", row_fingerprint(("x", 100)))}
    # a retraction of the held row cancels it instead of leaking
    out = fresh.step(2, [Delta([("k1", ("x", 100), -1)])])
    assert not out.entries
    assert not fresh.held


def test_knn_index_snapshot_restores_search_identical():
    import numpy as np

    from pathway_tpu.internals.keys import Pointer
    from pathway_tpu.ops.knn import BruteForceKnnIndex

    rng = np.random.default_rng(3)
    idx = BruteForceKnnIndex(dimensions=16, reserved_space=64)
    keys = [Pointer(i) for i in range(40)]
    vecs = rng.standard_normal((40, 16)).astype(np.float32)
    idx.add_batch(keys, vecs, [{"tag": i % 2} for i in range(40)])
    queries = [(Pointer(1000 + i),
                rng.standard_normal(16).astype(np.float32), 3, None)
               for i in range(4)]
    want = idx.search(queries)
    state = idx.snapshot_state()
    fresh = BruteForceKnnIndex(dimensions=16, reserved_space=8)
    fresh.restore_state(state)
    got = fresh.search(queries)
    assert [tuple(r) for r in got] == [tuple(r) for r in want]
    assert fresh._filter_data[Pointer(3)] == {"tag": 1}


def test_unsupported_index_raises_snapshot_unsupported():
    from pathway_tpu.engine.index_ops import ExternalIndexOperator
    from pathway_tpu.engine.operators import SnapshotUnsupported

    class _NoHooks:
        def add(self, *a): ...

        def remove(self, *a): ...

        def search(self, *a):
            return []

    op = ExternalIndexOperator(_NoHooks(), data_vec_pos=0,
                               data_filter_pos=None, query_vec_pos=0,
                               query_limit_pos=None, query_filter_pos=None)
    with pytest.raises(SnapshotUnsupported):
        op.snapshot_state()


def test_stats_and_metrics_expose_snapshot_tier(monkeypatch):
    backend = pw.persistence.Backend.mock()
    driver, rec = _driver_with_source(backend)
    rec.push("k1", (1,), 1)
    driver.seal(1)
    driver.commit(1, watermark=1)
    driver.write_snapshot(1, {"nodes": {}})
    st = driver.stats()
    assert st["snapshot_tick"] == 1
    assert st["snapshot_generation"] == 1
    assert st["snapshots_total"] == 1
    assert st["snapshot_age_ticks"] == 0
    assert st["wal_replayable_entries"] == 0
    assert st["compactions_total"] == 1
