"""Checkpoint/resume (SURVEY §5 checkpoint; reference: src/persistence/ +
integration_tests/wordcount kill-and-recover harness, test_recovery.py:25)."""

from __future__ import annotations

import os
import signal
import subprocess
import sys
import textwrap
import time

import pytest

from tests.utils import wait_result_with_checker

import pathway_tpu as pw
from pathway_tpu.engine.persistence import SnapshotLog
from pathway_tpu.internals.parse_graph import G

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def fresh_graph():
    G.clear()
    yield
    G.clear()


# ---------------------------------------------------------------------------
# snapshot log
# ---------------------------------------------------------------------------

def test_snapshot_log_roundtrip(tmp_path):
    log = SnapshotLog(str(tmp_path / "s.snap"))
    log.append(1, [("k1", ("a",), 1, None)])
    log.append(2, [("k2", ("b",), 1, ("row", "f", 0.0, 0, True))])
    log.close()
    records = SnapshotLog(str(tmp_path / "s.snap")).read_all()
    assert len(records) == 2
    assert records[0] == (1, [("k1", ("a",), 1, None)])
    assert records[1][1][0][3] == ("row", "f", 0.0, 0, True)


def test_snapshot_log_truncated_tail(tmp_path):
    path = str(tmp_path / "s.snap")
    log = SnapshotLog(path)
    log.append(1, [("k1", ("a",), 1, None)])
    log.close()
    with open(path, "ab") as f:
        f.write(b"\x40\x00\x00\x00\x00\x00\x00\x00partial")  # crash mid-append
    records = SnapshotLog(path).read_all()
    assert len(records) == 1  # the torn record is dropped


def test_snapshot_log_append_after_torn_tail(tmp_path):
    """Appends after a torn record must stay readable (the torn bytes are
    truncated first), or the log stops making durable progress forever."""
    path = str(tmp_path / "s.snap")
    log = SnapshotLog(path)
    log.append(1, [("k1", ("a",), 1, None)])
    log.close()
    with open(path, "ab") as f:
        f.write(b"\x40\x00\x00\x00\x00\x00\x00\x00partial")
    log2 = SnapshotLog(path)
    log2.append(2, [("k2", ("b",), 1, None)])
    log2.close()
    records = SnapshotLog(path).read_all()
    assert [t for t, _ in records] == [1, 2]


def test_duplicate_persistent_id_rejected(tmp_path):
    from pathway_tpu.engine.persistence import PersistenceDriver
    from pathway_tpu.io._datasource import CallbackSource, Session

    cfg = pw.persistence.Config.simple_config(
        pw.persistence.Backend.filesystem(str(tmp_path / "p")))
    driver = PersistenceDriver(cfg)
    schema = pw.schema_from_types(x=int)
    s1 = CallbackSource(lambda: iter(()), schema)
    s1.persistent_id = "dup"
    s2 = CallbackSource(lambda: iter(()), schema)
    s2.persistent_id = "dup"
    driver.attach_source(s1, Session())
    with pytest.raises(ValueError, match="unique persistent_id"):
        driver.attach_source(s2, Session())


# ---------------------------------------------------------------------------
# in-process resume: python source (skip-N protocol)
# ---------------------------------------------------------------------------

def _run_counts(words: list[str], backend) -> dict[str, int]:
    """Stream `words`, persist via `backend`, return final word counts."""
    G.clear()

    class Subject(pw.io.python.ConnectorSubject):
        def run(self):
            for w in words:
                self.next(word=w)

    t = pw.io.python.read(
        Subject(), schema=pw.schema_from_types(word=str),
        autocommit_duration_ms=10, persistent_id="words")
    counts = t.groupby(t.word).reduce(word=t.word, c=pw.reducers.count())
    state: dict[str, int] = {}

    def on_change(key, row, time, is_addition):
        if is_addition:
            state[row["word"]] = row["c"]
        elif state.get(row["word"]) == row["c"]:
            del state[row["word"]]

    pw.io.subscribe(counts, on_change)
    pw.run(persistence_config=pw.persistence.Config.simple_config(backend))
    return state


def test_python_source_resume_mock_backend():
    backend = pw.persistence.Backend.mock()
    first = _run_counts(["a", "b", "a"], backend)
    assert first == {"a": 2, "b": 1}
    # restart: the source deterministically re-emits its prefix, plus new rows
    second = _run_counts(["a", "b", "a", "c", "b"], backend)
    assert second == {"a": 2, "b": 2, "c": 1}  # no double counting


def test_python_source_resume_filesystem_backend(tmp_path):
    backend = pw.persistence.Backend.filesystem(str(tmp_path / "pstate"))
    first = _run_counts(["x", "y"], backend)
    assert first == {"x": 1, "y": 1}
    assert os.path.exists(tmp_path / "pstate" / "streams" / "words.snap")
    second = _run_counts(["x", "y", "x"], backend)
    assert second == {"x": 2, "y": 1}


# ---------------------------------------------------------------------------
# in-process resume: fs source (seek protocol, file-granular offsets)
# ---------------------------------------------------------------------------

def _run_fs_counts(input_dir, backend) -> dict[str, int]:
    G.clear()
    t = pw.io.fs.read(str(input_dir), format="plaintext", mode="batch",
                      autocommit_duration_ms=10, persistent_id="fsrc")
    counts = t.groupby(t.data).reduce(w=t.data, c=pw.reducers.count())
    state: dict[str, int] = {}

    def on_change(key, row, time, is_addition):
        if is_addition:
            state[row["w"]] = row["c"]
        elif state.get(row["w"]) == row["c"]:
            del state[row["w"]]

    pw.io.subscribe(counts, on_change)
    pw.run(persistence_config=pw.persistence.Config.simple_config(backend))
    return state


def test_fs_source_resume_new_files(tmp_path):
    inp = tmp_path / "in"
    inp.mkdir()
    (inp / "a.txt").write_text("w1\nw2\n")
    (inp / "b.txt").write_text("w1\n")
    backend = pw.persistence.Backend.filesystem(str(tmp_path / "pstate"))
    first = _run_fs_counts(inp, backend)
    assert first == {"w1": 2, "w2": 1}
    # restart with one new file: completed files must not re-emit
    (inp / "c.txt").write_text("w2\nw3\n")
    second = _run_fs_counts(inp, backend)
    assert second == {"w1": 2, "w2": 2, "w3": 1}


def test_fs_source_resume_changed_file(tmp_path):
    inp = tmp_path / "in"
    inp.mkdir()
    (inp / "a.txt").write_text("old1\nold2\n")
    backend = pw.persistence.Backend.filesystem(str(tmp_path / "pstate"))
    first = _run_fs_counts(inp, backend)
    assert first == {"old1": 1, "old2": 1}
    # file rewritten between runs: replayed rows must be retracted
    (inp / "a.txt").write_text("new1\n")
    os.utime(inp / "a.txt", (time.time() + 5, time.time() + 5))
    second = _run_fs_counts(inp, backend)
    assert second == {"new1": 1}


# ---------------------------------------------------------------------------
# kill-and-recover wordcount (subprocess; tier-4 of SURVEY §4)
# ---------------------------------------------------------------------------

_WORDCOUNT = textwrap.dedent("""
    import sys
    import pathway_tpu as pw

    inp, pdir, out = sys.argv[1], sys.argv[2], sys.argv[3]
    t = pw.io.fs.read(inp, format="plaintext", mode="streaming",
                      autocommit_duration_ms=40, persistent_id="words")
    counts = t.groupby(t.data).reduce(word=t.data, c=pw.reducers.count())
    pw.io.fs.write(counts, out, format="csv")
    pw.run(persistence_config=pw.persistence.Config.simple_config(
        pw.persistence.Backend.filesystem(pdir)))
""")


def _read_counts(out_path) -> dict[str, int]:
    import csv

    state: dict[str, int] = {}
    try:
        with open(out_path, newline="") as f:
            for row in csv.DictReader(f):
                w, c, d = row["word"], int(row["c"]), int(row["diff"])
                if d > 0:
                    state[w] = c
                elif state.get(w) == c:
                    del state[w]
    except (FileNotFoundError, KeyError, ValueError):
        return {}
    return state


@pytest.mark.slow
def test_wordcount_kill_and_recover(tmp_path):
    inp = tmp_path / "in"
    inp.mkdir()
    pdir = str(tmp_path / "pstate")
    out = str(tmp_path / "out.csv")
    script = tmp_path / "wc.py"
    script.write_text(_WORDCOUNT)

    n_files, per_file = 6, 25
    expected: dict[str, int] = {}
    for i in range(3):  # first half of the input exists up-front
        words = [f"w{j % 7}" for j in range(per_file)]
        (inp / f"{i:03d}.txt").write_text("\n".join(words) + "\n")
        for w in words:
            expected[w] = expected.get(w, 0) + 1

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    proc = subprocess.Popen([sys.executable, str(script), str(inp), pdir, out],
                            env=env, cwd=REPO)
    try:
        wait_result_with_checker(lambda: _read_counts(out), 60)
        assert _read_counts(out), "no output before kill"
        proc.send_signal(signal.SIGKILL)  # crash mid-stream
        proc.wait()

        for i in range(3, n_files):  # rest of the input arrives after crash
            words = [f"w{j % 5}" for j in range(per_file)]
            (inp / f"{i:03d}.txt").write_text("\n".join(words) + "\n")
            for w in words:
                expected[w] = expected.get(w, 0) + 1

        proc = subprocess.Popen(
            [sys.executable, str(script), str(inp), pdir, out],
            env=env, cwd=REPO)
        wait_result_with_checker(
            lambda: _read_counts(out) == expected, 90, step=0.2)
        assert _read_counts(out) == expected

        # SECOND kill/recover cycle (the reference harness kills several
        # times, integration_tests/wordcount/test_recovery.py): crash the
        # recovered process, add more input, recover again — exactly-once
        # across repeated crashes
        proc.send_signal(signal.SIGKILL)
        proc.wait()
        for i in range(n_files, n_files + 2):
            words = [f"w{j % 3}" for j in range(per_file)]
            (inp / f"{i:03d}.txt").write_text("\n".join(words) + "\n")
            for w in words:
                expected[w] = expected.get(w, 0) + 1
        proc = subprocess.Popen(
            [sys.executable, str(script), str(inp), pdir, out],
            env=env, cwd=REPO)
        wait_result_with_checker(
            lambda: _read_counts(out) == expected, 90, step=0.2)
        assert _read_counts(out) == expected
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()


def test_snapshot_log_rejects_malicious_pickle(tmp_path):
    """Regression: snapshot decode is restricted — a crafted record on
    shared storage must raise, not execute code on resume."""
    import pickle
    import struct
    import zlib

    class Evil:
        def __reduce__(self):
            return (os.system, ("echo pwned > /tmp/pwned",))

    payload = pickle.dumps((1, [Evil()]))
    path = str(tmp_path / "s.snap")
    with open(path, "wb") as f:
        f.write(b"PWSNAP01")
        f.write(struct.pack("<QI", len(payload), zlib.crc32(payload)))
        f.write(payload)
    with pytest.raises(Exception, match="forbidden global"):
        SnapshotLog(path).read_all()


def test_snapshot_log_refuses_alien_format(tmp_path):
    """A file without the format magic must raise — NOT read as empty and
    then get truncated away by the next append."""
    path = str(tmp_path / "s.snap")
    with open(path, "wb") as f:
        f.write(b"some other tool's data that must survive")
    with pytest.raises(ValueError, match="not a PWSNAP01"):
        SnapshotLog(path).read_all()
    with pytest.raises(ValueError, match="not a PWSNAP01"):
        SnapshotLog(path).append(1, [("k", ("v",), 1, None)])
    with open(path, "rb") as f:
        assert f.read() == b"some other tool's data that must survive"


def test_snapshot_log_roundtrips_pandas_datetimes(tmp_path):
    """pd.Timestamp/Timedelta are the engine's host-side datetime values —
    the restricted decoder must admit them or resume self-poisons."""
    import pandas as pd

    path = str(tmp_path / "s.snap")
    log = SnapshotLog(path)
    row = (pd.Timestamp("2026-07-29 12:00"),
           pd.Timestamp("2026-07-29", tz="UTC"),
           pd.Timedelta(seconds=5))
    log.append(1, [("k", row, 1, None)])
    log.close()
    [(_, [(_, got, _, _)])] = SnapshotLog(path).read_all()
    assert got == row


def test_snapshot_log_crc_detects_corruption(tmp_path):
    """A bit-flipped record (and everything after it) is dropped instead of
    being decoded as garbage."""
    path = str(tmp_path / "s.snap")
    log = SnapshotLog(path)
    log.append(1, [("k1", ("a",), 1, None)])
    log.append(2, [("k2", ("b",), 1, None)])
    log.close()
    with open(path, "r+b") as f:
        f.seek(-3, os.SEEK_END)  # flip a byte inside the last payload
        b = f.read(1)
        f.seek(-1, os.SEEK_CUR)
        f.write(bytes([b[0] ^ 0xFF]))
    records = SnapshotLog(path).read_all()
    assert [t for t, _ in records] == [1]


def test_snapshot_log_roundtrips_engine_value_types(tmp_path):
    """The restricted decoder must still admit every legitimate engine
    value class: Pointer, Json, numpy arrays, datetimes."""
    import datetime

    import numpy as np

    from pathway_tpu.internals.json import Json
    from pathway_tpu.internals.keys import hash_values

    row = (hash_values("k"), Json({"a": [1, 2]}),
           np.arange(3.0), datetime.datetime(2026, 7, 29, 12, 0),
           datetime.timedelta(seconds=5), b"bytes", ("nested", 1.5))
    path = str(tmp_path / "s.snap")
    log = SnapshotLog(path)
    log.append(7, [(row[0], row, 1, None)])
    log.close()
    [(t, [(k, got, diff, off)])] = SnapshotLog(path).read_all()
    assert t == 7 and diff == 1 and k == row[0]
    assert isinstance(got[0], type(row[0])) and got[0] == row[0]
    assert got[1].value == {"a": [1, 2]}
    assert np.array_equal(got[2], row[2])
    assert got[3:] == row[3:]


# ---------------------------------------------------------------------------
# crash-recovery edges via the fault-injection harness (testing/faults.py)
# ---------------------------------------------------------------------------

def test_fsync_failure_mid_commit_leaves_loadable_log(tmp_path,
                                                      monkeypatch):
    """An fsync that dies mid-commit with the retry budget disabled must
    surface (the commit is not durable) while leaving the log loadable on
    the next start. (With the default budget a single fsync hiccup is
    retried instead — test_append_retries_* below.)"""
    from pathway_tpu.testing import faults

    monkeypatch.setenv("PATHWAY_PERSISTENCE_WRITE_RETRIES", "0")
    path = str(tmp_path / "s.snap")
    log = SnapshotLog(path)
    log.append(1, [("k1", ("a",), 1, None)])
    with faults.arm("persistence.fsync", faults.FailNTimes(1)):
        with pytest.raises(faults.InjectedFault):
            log.append(2, [("k2", ("b",), 1, None)])
    log.close()
    # record 1 is durable for sure; record 2 may or may not have reached
    # the platters — either way the log loads and stays appendable
    log2 = SnapshotLog(path)
    times = [t for t, _ in log2.read_all()]
    assert times in ([1], [1, 2])
    log2.append(3, [("k3", ("c",), 1, None)])
    log2.close()
    assert [t for t, _ in SnapshotLog(path).read_all()] == times + [3]


def test_torn_append_drops_tail_and_recovers(tmp_path, monkeypatch):
    """A crash between the record header and its payload (the torn-tail
    shape) with retries disabled is dropped on load, and later appends
    truncate it first."""
    from pathway_tpu.testing import faults

    monkeypatch.setenv("PATHWAY_PERSISTENCE_WRITE_RETRIES", "0")
    path = str(tmp_path / "s.snap")
    log = SnapshotLog(path)
    log.append(1, [("k1", ("a",), 1, None)])
    with faults.arm("persistence.append.torn", faults.FailNTimes(1)):
        with pytest.raises(faults.InjectedFault):
            log.append(2, [("k2", ("b",), 1, None)])
    log.close()
    assert [t for t, _ in SnapshotLog(path).read_all()] == [1]
    log2 = SnapshotLog(path)
    log2.append(3, [("k3", ("c",), 1, None)])
    log2.close()
    assert [t for t, _ in SnapshotLog(path).read_all()] == [1, 3]


def test_torn_commit_then_rerun_replays_exactly_once(tmp_path):
    """End to end: a commit torn by the armed fault point crashes the run;
    the rerun must drop the torn tail and still count every word exactly
    once."""
    from pathway_tpu.testing import faults

    backend = pw.persistence.Backend.filesystem(str(tmp_path / "pstate"))
    with faults.arm("persistence.append.torn", faults.FailOnHit(2)):
        try:
            _run_counts(["a", "b", "a", "c"], backend)
        except faults.InjectedFault:
            pass  # depending on pacing the fault may hit 0 or 1 commits
    faults.reset()
    state = _run_counts(["a", "b", "a", "c", "b"], backend)
    assert state == {"a": 2, "b": 2, "c": 1}


# ---------------------------------------------------------------------------
# per-partition offset antichains (reference: persistence/frontier.rs:12)
# ---------------------------------------------------------------------------

def test_offset_antichain_fold_and_merge():
    from pathway_tpu.engine.offsets import OffsetAntichain

    a = OffsetAntichain.from_entries([
        ("part", 0, 5), ("part", 1, 2), ("part", 0, 3),  # out of order
        ("row", "file", 0.0, 1, True),                    # non-partitioned
        None,
    ])
    assert a.to_dict() == {0: 5, 1: 2}
    assert a.is_past(0, 5) and a.is_past(0, 1) and not a.is_past(0, 6)
    assert not a.is_past(7, 0)
    b = OffsetAntichain({0: 4, 2: 9})
    assert a.merge(b).to_dict() == {0: 5, 1: 2, 2: 9}


class _PartitionedSource(pw.io.python.PythonSource):
    """Fake Kafka: N partitions of messages; resumes via seek_offsets."""

    def __init__(self, schema, partitions: dict[int, list[str]]):
        class _Subject(pw.io.python.ConnectorSubject):
            def run(self):
                pass

        super().__init__(_Subject(), schema)
        self.partitions = partitions
        self.resumed_from = None

    def seek_offsets(self, antichain) -> None:
        self.resumed_from = antichain

    def run(self, session) -> None:
        seq = 0
        for p, msgs in sorted(self.partitions.items()):
            start = 0
            if self.resumed_from is not None:
                last = self.resumed_from.get(p)
                if last is not None:
                    start = last + 1
            for off in range(start, len(msgs)):
                key, row = self.row_to_engine({"data": msgs[off]}, seq)
                seq += 1
                session.push(key, row, 1, offset=("part", p, off))


def test_partitioned_source_resumes_per_partition(tmp_path):
    """Commit a prefix with different progress per partition, then restart:
    the source must receive the exact per-partition frontier and re-read
    only past it — no duplicates, no loss, no prefix-replay assumption."""
    from pathway_tpu.engine.offsets import OffsetAntichain
    from pathway_tpu.engine.persistence import PersistenceDriver
    from pathway_tpu.internals import schema as sch
    from pathway_tpu.io._datasource import Session

    schema = sch.schema_from_types(data=str)
    storage = str(tmp_path / "snap")
    cfg = pw.persistence.Config(
        backend=pw.persistence.Backend.filesystem(storage))

    # ---- first run: partition 0 commits 2 entries, partition 1 commits 1
    src = _PartitionedSource(schema, {0: ["a0", "a1"], 1: ["b0"]})
    src.persistent_id = "pp"
    driver = PersistenceDriver(cfg)
    live = Session()
    rec = driver.attach_source(src, live)
    k, r = src.row_to_engine({"data": "a0"}, 0)
    rec.push(k, r, 1, offset=("part", 0, 0))
    k, r = src.row_to_engine({"data": "a1"}, 1)
    rec.push(k, r, 1, offset=("part", 0, 1))
    k, r = src.row_to_engine({"data": "b0"}, 2)
    rec.push(k, r, 1, offset=("part", 1, 0))
    driver.commit(1)
    driver.close()

    # ---- restart with MORE data in both partitions
    src2 = _PartitionedSource(
        schema, {0: ["a0", "a1", "a2"], 1: ["b0", "b1"]})
    src2.persistent_id = "pp"
    driver2 = PersistenceDriver(cfg)
    live2 = Session()
    rec2 = driver2.attach_source(src2, live2)
    # replay delivered the durable prefix
    replayed = [row[1][0] for row in live2.drain()]
    assert sorted(replayed) == ["a0", "a1", "b0"]
    # the source got the exact frontier
    assert src2.resumed_from == OffsetAntichain({0: 1, 1: 0})
    # live read continues strictly past it
    src2.run(rec2)
    fresh = [row[1][0] for row in live2.drain()]
    assert sorted(fresh) == ["a2", "b1"]
    driver2.close()


# ---------------------------------------------------------------------------
# transient-write retries (PR 8: internals/retries.py backoff + jitter)
# ---------------------------------------------------------------------------

def test_append_retries_transient_fsync_then_succeeds(tmp_path,
                                                      monkeypatch):
    """A transient fsync failure inside append is retried with backoff
    instead of surfacing — the record lands durably on a later attempt."""
    from pathway_tpu.testing import faults

    monkeypatch.setenv("PATHWAY_PERSISTENCE_RETRY_INITIAL_MS", "1")
    path = str(tmp_path / "s.snap")
    log = SnapshotLog(path)
    with faults.arm("persistence.fsync", faults.FailNTimes(2)):
        log.append(1, [("k1", ("a",), 1, None)])  # no raise: 2 < budget 3
    log.append(2, [("k2", ("b",), 1, None)])
    log.close()
    assert [t for t, _ in SnapshotLog(path).read_all()] == [1, 2]


def test_append_retry_truncates_torn_header_before_rewriting(tmp_path,
                                                             monkeypatch):
    """A retried torn append (header written, payload lost) must truncate
    the torn bytes before rewriting — the repaired log contains the
    record exactly once with nothing unreadable in between."""
    from pathway_tpu.testing import faults

    monkeypatch.setenv("PATHWAY_PERSISTENCE_RETRY_INITIAL_MS", "1")
    path = str(tmp_path / "s.snap")
    log = SnapshotLog(path)
    log.append(1, [("k1", ("a",), 1, None)])
    with faults.arm("persistence.append.torn", faults.FailNTimes(2)):
        log.append(2, [("k2", ("b",), 1, None)])
    log.append(3, [("k3", ("c",), 1, None)])
    log.close()
    records = SnapshotLog(path).read_all()
    assert [t for t, _ in records] == [1, 2, 3]
    # and the file holds no orphaned torn headers: total size is exactly
    # the three framed records behind the magic
    import struct as _struct

    expect = len(b"PWSNAP01") + sum(
        _struct.calcsize("<QI") + len(__import__("pickle").dumps(
            r, protocol=__import__("pickle").HIGHEST_PROTOCOL))
        for r in records)
    assert os.path.getsize(path) == expect


def test_s3_append_retries_transient_put(monkeypatch):
    """Object-store appends retry a transient PUT failure; the sequence
    number advances only after success (no gap in the prefix)."""
    from pathway_tpu.engine.persistence import S3SnapshotLog

    monkeypatch.setenv("PATHWAY_PERSISTENCE_RETRY_INITIAL_MS", "1")

    class _FlakyClient:
        def __init__(self):
            self.objects: dict[str, bytes] = {}
            self.failures = 2

        def list_objects(self, prefix):
            return [{"key": k} for k in self.objects if k.startswith(prefix)]

        def get_object(self, key):
            return self.objects[key]

        def put_object(self, key, body):
            if self.failures:
                self.failures -= 1
                raise ConnectionError("503 SlowDown")
            self.objects[key] = body

    client = _FlakyClient()
    log = S3SnapshotLog(client, "p", "src")
    log.append(1, [("k1", ("a",), 1, None)])
    log.append(2, [("k2", ("b",), 1, None)])
    records = S3SnapshotLog(client, "p", "src").read_all()
    assert [t for t, _ in records] == [1, 2]


def test_s3_append_retry_exhaustion_raises(monkeypatch):
    """A persistently-failing PUT exhausts the budget and re-raises the
    backend's own exception (the runtime escalates per
    terminate_on_error)."""
    from pathway_tpu.engine.persistence import S3SnapshotLog

    monkeypatch.setenv("PATHWAY_PERSISTENCE_WRITE_RETRIES", "1")
    monkeypatch.setenv("PATHWAY_PERSISTENCE_RETRY_INITIAL_MS", "1")

    class _DeadClient:
        def list_objects(self, prefix):
            return []

        def put_object(self, key, body):
            raise ConnectionError("bucket gone")

    log = S3SnapshotLog(_DeadClient(), "p", "src")
    with pytest.raises(ConnectionError, match="bucket gone"):
        log.append(1, [("k1", ("a",), 1, None)])
