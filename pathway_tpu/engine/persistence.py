"""Checkpoint/resume driver for the streaming runtime.

Rebuild of the reference's persistence stack (src/persistence/ —
``WorkerPersistentStorage`` tracker.rs:20, ``MetadataAccessor`` state.rs:20,
snapshot record/replay in src/connectors/snapshot.rs + mod.rs:215-368):
each source's parsed entries are appended to a durable **snapshot log**
together with the commit timestamp; on restart the driver replays every
logged entry into the source's session (state is rebuilt by re-running the
dataflow over the replayed prefix) and suppresses the first N live entries
the re-started reader emits, N being the number durably logged — the
"rewind then continue from stored offsets" protocol of the reference,
expressed as replay+skip so *any* deterministic reader gets exactly-once
input without a per-reader seek API.

The log is authoritative (no separate metadata file to keep consistent):
records are length-prefixed, CRC32-checksummed pickles decoded by a
RESTRICTED unpickler (class whitelist below — a snapshot written by an
attacker with access to shared storage must not execute code on resume),
fsynced per commit; a truncated or corrupted tail record (crash
mid-append, bit rot) is detected and dropped on load. This mirrors the
reference's rule that only data finalized at the last *committed* frontier
is recovered (state.rs:120-226) — the reference gets decode safety for
free from serde/bincode's data-only model; the Python build has to
enforce it explicitly.

Backends: ``filesystem`` (a directory of per-source logs) and ``mock``
(in-memory, state kept on the Backend object — the test double, like the
reference's mock metadata backend).
"""

from __future__ import annotations

import io
import logging
import os
import pickle
import struct
import time as _time
import zlib
from typing import Any, Callable

from pathway_tpu.engine.locking import blocking_call, create_lock

from pathway_tpu.testing import faults

logger = logging.getLogger(__name__)

_HDR = struct.Struct("<QI")  # payload length, CRC32(payload)
_MAGIC = b"PWSNAP01"  # format marker; bump the digit on layout changes
_STATE_MAGIC = b"PWOPSNAP1"  # operator-state snapshot blob marker

# Decode whitelist: data classes that legitimately appear inside logged
# (time, [(key, row, diff, offset), ...]) records — engine Values
# (internals/keys.Pointer, internals/json.Json, numpy arrays, datetimes)
# and plain containers. Anything else (os.system, builtins.eval,
# functools.partial, ...) is refused at load time.
_SAFE_GLOBALS = {
    ("builtins", n) for n in
    ("list", "tuple", "dict", "set", "frozenset", "bytearray", "complex")
} | {
    ("pathway_tpu.internals.keys", "Pointer"),
    ("pathway_tpu.internals.json", "Json"),
    ("datetime", "datetime"), ("datetime", "date"), ("datetime", "time"),
    ("datetime", "timedelta"), ("datetime", "timezone"),
    # the build's canonical datetime/duration value types host-side are
    # pandas Timestamp/Timedelta (internals/expressions/date_time.py)
    ("pandas._libs.tslibs.timestamps", "_unpickle_timestamp"),
    ("pandas._libs.tslibs.timestamps", "Timestamp"),
    ("pandas._libs.tslibs.timedeltas", "_timedelta_unpickle"),
    ("pandas._libs.tslibs.timedeltas", "Timedelta"),
    ("numpy", "ndarray"), ("numpy", "dtype"),
    ("numpy.core.multiarray", "_reconstruct"),
    ("numpy.core.multiarray", "scalar"),
    ("numpy.core.numeric", "_frombuffer"),
    ("numpy._core.multiarray", "_reconstruct"),
    ("numpy._core.multiarray", "scalar"),
    ("numpy._core.numeric", "_frombuffer"),
}


class ReadOnlyPersistenceError(RuntimeError):
    """A mutation (append/commit/truncate/compact/snapshot write) was
    attempted through a driver opened with ``read_only=True``. Raised by
    name so a replica that would otherwise corrupt its primary's WAL or
    snapshot generations dies loudly instead (engine/replica.py opens the
    primary's root exactly this way)."""


class FencedPrimaryError(RuntimeError):
    """A writer discovered that the persistence root's fencing epoch
    moved past its own: a replica was PROMOTED to primary while this
    process still believed it held the write lease (e.g. a SIGSTOPped
    primary resumed after failover). Raised by name — naming both
    epochs — before any byte lands in the WAL or a snapshot manifest,
    so a zombie primary self-demotes loudly instead of splicing a
    second timeline into the shared root (README "Write-path
    failover")."""

    def __init__(self, held_epoch: int, root_epoch: int, what: str):
        self.held_epoch = held_epoch
        self.root_epoch = root_epoch
        super().__init__(
            f"fenced primary: this writer holds fencing epoch "
            f"{held_epoch} but the persistence root is at epoch "
            f"{root_epoch} — a newer primary was promoted; refusing "
            f"{what} and self-demoting (restart this process as a "
            f"replica of the new primary)")


class _RestrictedUnpickler(pickle.Unpickler):
    def find_class(self, module, name):
        if (module, name) in _SAFE_GLOBALS:
            return super().find_class(module, name)
        raise pickle.UnpicklingError(
            f"snapshot log references forbidden global {module}.{name} — "
            "refusing to decode (possible tampering)")


def _safe_loads(payload: bytes):
    return _RestrictedUnpickler(io.BytesIO(payload)).load()


# ---------------------------------------------------------------------------
# snapshot/compaction knobs (cadence knobs live in engine/streaming.py)
# ---------------------------------------------------------------------------

def _keep_generations() -> int:
    """Snapshot generations retained (>= 1). The WAL is truncated only to
    the OLDEST retained generation's tick, so a corrupt newest snapshot
    can always fall back one generation and still find its suffix."""
    from pathway_tpu.internals.config import _env_int

    return max(1, _env_int("PATHWAY_SNAPSHOT_KEEP_GENERATIONS", 2))


def _compact_enabled() -> bool:
    """PATHWAY_SNAPSHOT_COMPACT=0 writes snapshots without truncating the
    WAL (the recovery-equivalence property tests compare snapshot+suffix
    replay against full-WAL replay over the same root)."""
    return os.environ.get("PATHWAY_SNAPSHOT_COMPACT", "1").lower() not in (
        "0", "false", "off", "no")


def _restore_enabled() -> bool:
    """PATHWAY_SNAPSHOT_RESTORE=0 ignores existing snapshots on startup
    (full-WAL replay — only sound while compaction is disabled or no
    snapshot was ever written)."""
    return os.environ.get("PATHWAY_SNAPSHOT_RESTORE", "1").lower() not in (
        "0", "false", "off", "no")


def _atomic_write_bytes(path: str, data: bytes) -> None:
    """tmp + fsync + rename, like flight_recorder.atomic_write_json but
    for a binary blob: a crash mid-write never leaves a truncated file at
    ``path`` and never clobbers a previous good one."""
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        with open(tmp, "wb") as f:
            f.write(data)
            f.flush()
            os.fsync(f.fileno())
        # crash edge between the data fsync and the rename: the tmp is
        # durable but invisible — recovery must fall back to the
        # previous good file at ``path``
        faults.hit("persistence.atomic.replace", path=str(path))
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


# ---------------------------------------------------------------------------
# transient-write retries (shared by the file and object-store logs)
# ---------------------------------------------------------------------------

# process-wide retry counter, exported on /metrics as
# ``pathway_tpu_persistence_write_retries`` (Prometheus counters are
# process-scoped by convention — several drivers in one process share it)
_retry_lock = create_lock("persistence._retry_lock")
_write_retries_total = 0


def write_retries_total() -> int:
    with _retry_lock:
        return _write_retries_total


def _retrying_write(body: Callable[[], None], what: str) -> None:
    """Run one durable write (append+fsync, object PUT), retrying
    transient failures with the shared exponential backoff + full jitter
    schedule (internals/retries.py). ``body`` must be safe to re-run from
    scratch: the file log truncates its torn tail before every attempt
    and object PUTs are atomic whole-object writes. Exhausting
    ``PATHWAY_PERSISTENCE_WRITE_RETRIES`` (default 3; 0 disables
    retries) re-raises the last error — the streaming commit loop then
    escalates it per ``terminate_on_error``."""
    from pathway_tpu.internals.config import _env_int

    global _write_retries_total
    budget = max(0, _env_int("PATHWAY_PERSISTENCE_WRITE_RETRIES", 3))
    strategy = None
    attempt = 0
    while True:
        try:
            body()
            return
        except Exception as e:
            if attempt >= budget:
                raise
            if strategy is None:
                from pathway_tpu.internals.retries import \
                    ExponentialBackoffRetryStrategy

                strategy = ExponentialBackoffRetryStrategy(
                    initial_delay_ms=max(1, _env_int(
                        "PATHWAY_PERSISTENCE_RETRY_INITIAL_MS", 50)),
                    backoff_factor=2.0,
                    max_delay_ms=max(1, _env_int(
                        "PATHWAY_PERSISTENCE_RETRY_MAX_MS", 2000)),
                    jitter=True)
            delay = strategy.delay_for_attempt(attempt)
            with _retry_lock:
                _write_retries_total += 1
            logger.warning(
                "transient persistence write failure (%s): %s: %s — "
                "retry %d/%d in %.3fs", what, type(e).__name__, e,
                attempt + 1, budget, delay)
            _time.sleep(delay)
            attempt += 1


class _WaitHistogram:
    """Fixed-bucket commit-wait histogram, Prometheus-exposed as
    ``pathway_tpu_commit_wait_ms`` — how long each durable commit (append
    + fsync/PUT incl. retries) held the loop."""

    BUCKETS_MS = (0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0,
                  1000.0)

    def __init__(self):
        self.counts = [0] * (len(self.BUCKETS_MS) + 1)
        self.sum_ms = 0.0
        self.count = 0

    def observe(self, ms: float) -> None:
        i = 0
        for b in self.BUCKETS_MS:
            if ms <= b:
                break
            i += 1
        self.counts[i] += 1
        self.sum_ms += ms
        self.count += 1

    def cumulative(self) -> list[tuple[float, int]]:
        """[(le, cumulative count)], +Inf last (exposition format)."""
        out: list[tuple[float, int]] = []
        cum = 0
        for b, c in zip(self.BUCKETS_MS, self.counts):
            cum += c
            out.append((b, cum))
        out.append((float("inf"), cum + self.counts[-1]))
        return out


def record_epoch(rec) -> int:
    """Fencing epoch a log record was written under. Records are
    ``(time, entries)`` tuples from roots that never saw a promotion
    (epoch 0 — every pre-failover root stays byte-compatible) or
    ``(time, entries, epoch)`` once a promotion bumped the root's
    epoch; unpack by index so both shapes read identically."""
    return int(rec[2]) if len(rec) > 2 else 0


class SnapshotLog:
    """Append-only framed, checksummed, restricted-pickle log of
    (time, entries[, epoch]) records (``epoch`` — the writer's fencing
    epoch — is stamped only when nonzero, keeping pre-failover logs
    byte-identical)."""

    def __init__(self, path: str):
        self.path = path
        os.makedirs(os.path.dirname(path), exist_ok=True)
        self._f = None

    def _scan(self) -> tuple[list[tuple[int, list]], int]:
        """(intact records, byte offset of the end of the last intact one).
        A torn tail record — crash mid-append — is excluded from both.
        Within one log, record epochs are non-decreasing (a promotion
        only ever bumps the root's epoch); a record whose epoch is
        BELOW its predecessor's is a fenced zombie's write that raced
        the fencing check — recovery truncates at it, loudly, keeping
        the single post-promotion timeline."""
        records: list = []
        if not os.path.exists(self.path):
            return records, 0
        with open(self.path, "rb") as f:
            data = f.read()
        if not data:
            return records, 0
        if len(data) < len(_MAGIC) and _MAGIC.startswith(data):
            # crash during the very first append, mid-magic: an empty log
            # with a torn tail, not an alien file
            return records, 0
        if not data.startswith(_MAGIC):
            # refuse to guess: silently reading an alien/older layout as
            # empty would wipe it on the next append
            raise ValueError(
                f"{self.path}: not a {_MAGIC.decode()} snapshot log — "
                "refusing to read or overwrite it")
        pos = len(_MAGIC)
        high_epoch = 0
        while pos + _HDR.size <= len(data):
            length, crc = _HDR.unpack_from(data, pos)
            end = pos + _HDR.size + length
            if end > len(data):
                # incomplete record: a torn tail (crash mid-append) — or
                # a corrupted LENGTH header mid-log, which is
                # indistinguishable byte-wise; say how much is dropped
                # either way (the next append truncates it)
                logger.warning(
                    "%s: incomplete record at byte %d (%d trailing "
                    "byte(s) dropped: torn tail, or a corrupt length "
                    "header hiding later records)", self.path, pos,
                    len(data) - pos)
                break
            payload = data[pos + _HDR.size:end]
            bad = zlib.crc32(payload) != crc
            if not bad:
                try:
                    rec = _safe_loads(payload)
                except pickle.UnpicklingError:
                    raise  # forbidden global = tampering, not a torn tail
                except Exception:
                    bad = True
            if not bad:
                epoch = record_epoch(rec)
                if epoch < high_epoch:
                    logger.error(
                        "%s: fenced-zombie write at byte %d — record at "
                        "tick %s carries fencing epoch %d below the "
                        "log's established epoch %d (a demoted primary "
                        "raced the fencing check) — truncating at it to "
                        "keep the single post-promotion timeline",
                        self.path, pos, rec[0], epoch, high_epoch)
                    break
                high_epoch = epoch
            if bad:
                # a CRC/decode failure on the LAST framed record is the
                # ordinary torn tail; one with more bytes behind it is
                # mid-log corruption (bit rot, partial overwrite) — the
                # per-record CRC catches it BEFORE the unpickler sees
                # garbage, and recovery truncates at the first bad
                # record, loudly, dropping whatever followed
                if end < len(data):
                    logger.error(
                        "%s: corrupt record at byte %d (mid-log, %d bytes "
                        "follow) — truncating the log at the first bad "
                        "record; %d later byte(s) of history are "
                        "unrecoverable and will be re-ingested live",
                        self.path, pos, len(data) - end, len(data) - pos)
                else:
                    logger.warning(
                        "%s: torn tail record at byte %d dropped (crash "
                        "mid-append)", self.path, pos)
                break
            records.append(rec)
            pos = end
        return records, pos

    def read_all(self) -> list[tuple[int, list]]:
        return self._scan()[0]

    def append(self, time: int, entries: list, epoch: int = 0) -> int:
        if self._f is None:
            # truncate any torn tail record before appending, or every later
            # record would sit behind unreadable bytes forever
            _records, valid = self._scan()
            self._f = open(self.path, "ab")
            if self._f.tell() != valid:
                self._f.truncate(valid)
                self._f.seek(valid)
            if valid == 0:
                self._f.write(_MAGIC)
        rec = (time, entries, epoch) if epoch else (time, entries)
        payload = pickle.dumps(rec, protocol=pickle.HIGHEST_PROTOCOL)
        crc = zlib.crc32(payload)
        if faults.armed("persistence.append.corrupt"):
            # test hook: flip payload bytes AFTER the CRC was computed —
            # the written record is a mid-log corruption _scan must catch
            mutable = bytearray(payload)
            faults.hit("persistence.append.corrupt", path=self.path,
                       time=time, payload=mutable)
            payload = bytes(mutable)
        start = self._f.tell()

        def _write() -> None:
            # re-entry after a failed attempt: truncate whatever the torn
            # attempt left (a header without its payload) before
            # rewriting, or every later record would sit behind
            # unreadable bytes. First attempt: size == start, a no-op.
            # The file is opened in append mode, so writes land at the
            # (possibly truncated-back) end regardless of seek position.
            self._f.truncate(start)
            self._f.seek(start)
            faults.hit("persistence.append", path=self.path, time=time)
            self._f.write(_HDR.pack(len(payload), crc))
            # fault point between header and payload: an armed action
            # aborts here leaving exactly the torn-tail record _scan
            # must drop
            faults.hit("persistence.append.torn", path=self.path, time=time)
            self._f.write(payload)
            self._f.flush()
            faults.hit("persistence.fsync", path=self.path, time=time)
            # fsync is a known-blocking call: the sanitizer asserts no
            # engine lock is held while the durability write stalls
            with blocking_call("persistence.fsync"):
                os.fsync(self._f.fileno())

        _retrying_write(_write, f"append to {self.path}")
        return _HDR.size + len(payload)

    def truncate_to(self, tick: int) -> int:
        """WAL compaction: atomically rewrite the log keeping only records
        with time > ``tick`` (the suffix a durable snapshot does not
        cover). Returns the number of ENTRIES dropped. Record times are
        monotone (commit watermarks), so the kept records are a
        contiguous byte suffix — copied verbatim, never re-pickled; only
        the dropped prefix (plus the first kept record) is decoded. The
        previous file is replaced only after the rewrite is fsynced, so a
        crash mid-compaction leaves either the old or the new log —
        never a partial one."""
        self.close()  # the append handle's position is about to be wrong
        if not os.path.exists(self.path):
            return 0
        with open(self.path, "rb") as f:
            data = f.read()
        if not data.startswith(_MAGIC):
            return 0  # alien/torn-magic file: _scan's rules own this case
        pos = len(_MAGIC)
        dropped = 0
        cut = None  # byte offset of the first KEPT record
        while pos + _HDR.size <= len(data):
            length, crc = _HDR.unpack_from(data, pos)
            end = pos + _HDR.size + length
            if end > len(data):
                break
            payload = data[pos + _HDR.size:end]
            if zlib.crc32(payload) != crc:
                break
            try:
                rec = _safe_loads(payload)
                t, entries = rec[0], rec[1]
            except Exception:
                break
            if t > tick:
                cut = pos
                break
            dropped += len(entries)
            pos = end
        if dropped == 0:
            return 0
        body = _MAGIC + (data[cut:] if cut is not None else b"")
        with blocking_call("persistence.compact"):
            _atomic_write_bytes(self.path, body)
        return dropped

    def truncate_after(self, tick: int) -> int:
        """Promotion-time suffix truncation — the inverse cut of
        :meth:`truncate_to`: atomically rewrite the log keeping only
        records with time <= ``tick``. The dead primary's final commit
        may have landed in SOME logs but not others (it died
        mid-commit); the promoted replica applied only complete ticks,
        so every record past its applied tick is an incomplete commit
        that must not survive into the new timeline. Returns entries
        dropped."""
        self.close()
        records, _valid = self._scan()
        kept = [r for r in records if r[0] <= tick]
        if len(kept) == len(records):
            return 0
        dropped = sum(len(r[1]) for r in records if r[0] > tick)
        body = bytearray(_MAGIC)
        for rec in kept:
            payload = pickle.dumps(rec, protocol=pickle.HIGHEST_PROTOCOL)
            body += _HDR.pack(len(payload), zlib.crc32(payload))
            body += payload
        with blocking_call("persistence.compact"):
            _atomic_write_bytes(self.path, bytes(body))
        return dropped

    def close(self) -> None:
        if self._f is not None:
            self._f.close()
            self._f = None


class S3SnapshotLog:
    """Object-per-commit snapshot log on S3-compatible storage: each
    append PUTs ``<prefix>/streams/<sid>/<seq:016d>`` containing one
    framed, checksummed record; restore lists the prefix and replays
    objects in key order. Object stores give atomic whole-object PUTs, so
    the torn-tail handling of the file log becomes 'skip a corrupt
    object' (reference: S3 metadata/stream backends,
    src/persistence/metadata_backends/ + connectors/snapshot.rs)."""

    def __init__(self, client, root_prefix: str, source_id: str):
        self.client = client
        self.prefix = "/".join(
            p for p in (root_prefix.strip("/"), "streams", source_id) if p)
        self._seq: int | None = None
        self._purged = False

    def read_all(self) -> list[tuple[int, list]]:
        """Contiguous durable prefix, stopping at the first gap or corrupt
        object — exactly SnapshotLog._scan's torn-tail rule. Skipping a
        hole would desynchronize the replay+skip resume protocol (the
        skip counter assumes the replayed records are a PREFIX of what
        the reader re-emits)."""
        records: list = []
        expect = 0
        objs = []
        for obj in sorted(self.client.list_objects(self.prefix + "/"),
                          key=lambda o: o["key"]):
            try:
                seq = int(obj["key"].rsplit("/", 1)[-1])
            except ValueError:
                continue  # foreign object under the prefix
            objs.append((seq, obj["key"]))
        for i, (seq, key) in enumerate(objs):
            if seq != expect:
                break  # gap: a later commit without its predecessor
            data = self.client.get_object(key)
            bad = (not data.startswith(_MAGIC)
                   or len(data) < len(_MAGIC) + _HDR.size)
            if not bad:
                length, crc = _HDR.unpack_from(data, len(_MAGIC))
                payload = data[len(_MAGIC) + _HDR.size:
                               len(_MAGIC) + _HDR.size + length]
                bad = len(payload) != length or zlib.crc32(payload) != crc
            if bad:
                # per-record CRC: a corrupt object with SUCCESSORS is
                # mid-sequence corruption, not an interrupted tail upload
                # — recovery still stops at the first bad record, loudly
                if i + 1 < len(objs):
                    logger.error(
                        "%s: corrupt snapshot object %s mid-sequence "
                        "(%d later object(s)) — truncating recovery at "
                        "the first bad record", self.prefix, key,
                        len(objs) - i - 1)
                break
            records.append(_safe_loads(payload))
            expect += 1
        self._seq = expect  # next append overwrites a torn slot
        return records

    def _next_seq(self) -> int:
        """Key listing only — no GETs/unpickling just to number an append
        (the records themselves are read once by the driver's cache).
        Appends after the CONTIGUOUS prefix: a torn/corrupt object's slot
        gets overwritten, matching read_all's prefix rule."""
        keys = set()
        for obj in self.client.list_objects(self.prefix + "/"):
            try:
                keys.add(int(obj["key"].rsplit("/", 1)[-1]))
            except ValueError:
                pass
        seq = 0
        while seq in keys:
            seq += 1
        return seq

    def _purge_stale_successors(self) -> None:
        """Delete objects at/past the next append slot before the first
        write of this session. After a mid-sequence corruption (or gap)
        truncated recovery, objects BEYOND the break are leftovers of the
        abandoned timeline — appending in front of them and crashing
        would let a later read_all splice those CRC-valid strays back
        into the replayed history."""
        for obj in list(self.client.list_objects(self.prefix + "/")):
            try:
                seq = int(obj["key"].rsplit("/", 1)[-1])
            except ValueError:
                continue
            if seq >= self._seq:
                self.client.delete_object(obj["key"])

    def append(self, time: int, entries: list, epoch: int = 0) -> int:
        if self._seq is None:
            self._seq = self._next_seq()
        if not self._purged:
            self._purged = True
            self._purge_stale_successors()
        # epoch accepted for log-API parity; object-store roots do not
        # support fencing (no atomic read-modify-write manifest), so the
        # driver keeps epoch 0 there and the record shape is unchanged
        rec = (time, entries, epoch) if epoch else (time, entries)
        payload = pickle.dumps(rec, protocol=pickle.HIGHEST_PROTOCOL)
        crc = zlib.crc32(payload)
        if faults.armed("persistence.append.corrupt"):
            mutable = bytearray(payload)
            faults.hit("persistence.append.corrupt", key=self.prefix,
                       time=time, payload=mutable)
            payload = bytes(mutable)
        body = _MAGIC + _HDR.pack(len(payload), crc) + payload
        key = f"{self.prefix}/{self._seq:016d}"

        def _put() -> None:
            faults.hit("persistence.s3.put", key=key, time=time)
            self.client.put_object(key, body)

        # whole-object PUTs are atomic, so a retry simply overwrites the
        # failed attempt's slot; _seq advances only after success
        _retrying_write(_put, f"PUT {key}")
        self._seq += 1
        return len(body)

    def close(self) -> None:
        pass


class MockLog:
    """In-memory log living on the Backend object, surviving re-runs that
    reuse the same ``pw.persistence.Backend.mock()`` instance. Grows the
    same truncate API as the file log so unit tests exercise snapshot
    compaction without a filesystem."""

    def __init__(self, store: dict, source_id: str):
        self._records = store.setdefault(source_id, [])

    def read_all(self) -> list[tuple[int, list]]:
        return list(self._records)

    def append(self, time: int, entries: list, epoch: int = 0) -> int:
        rec = (time, entries, epoch) if epoch else (time, entries)
        self._records.append(rec)
        # byte-threshold accounting parity with the durable logs
        return len(pickle.dumps(rec, protocol=pickle.HIGHEST_PROTOCOL))

    def truncate_to(self, tick: int) -> int:
        """Drop records covered by a durable snapshot (time <= tick);
        returns entries dropped. In-place slice assignment so every
        holder of the store's list sees the compaction."""
        dropped = sum(len(r[1]) for r in self._records if r[0] <= tick)
        if dropped:
            self._records[:] = [r for r in self._records if r[0] > tick]
        return dropped

    def truncate_after(self, tick: int) -> int:
        """Promotion-time suffix cut (SnapshotLog.truncate_after): drop
        records PAST ``tick`` — the dead primary's incomplete final
        commit; returns entries dropped."""
        kept = [r for r in self._records if r[0] <= tick]
        if len(kept) == len(self._records):
            return 0
        dropped = sum(len(r[1]) for r in self._records if r[0] > tick)
        self._records[:] = kept
        return dropped

    def close(self) -> None:
        pass


def scan_log_bytes(data: bytes,
                   expect_magic: bool) -> tuple[list[tuple[int, list]], int]:
    """Parse intact ``(time, entries)`` records from a (possibly partial)
    snapshot-log byte buffer. ``expect_magic`` is True when ``data``
    begins at byte 0 of the file (the magic header is consumed first).
    Returns ``(records, consumed)`` — ``consumed`` counts bytes of
    ``data`` consumed, magic included. Unlike :meth:`SnapshotLog._scan`,
    an incomplete or checksum-failing tail record is left UNconsumed
    rather than dropped: a live primary may still be mid-append, and the
    tailer (engine/replica.py) simply retries from the same offset on
    its next poll. A record whose fencing epoch regresses below its
    predecessor's (a fenced zombie's write) stops the scan there —
    permanently unconsumed; recovery truncates it (``SnapshotLog._scan``)
    and the tailer never applies it."""
    records: list = []
    pos = 0
    high_epoch = 0
    if expect_magic:
        if not data.startswith(_MAGIC):
            return records, 0  # header not fully written yet
        pos = len(_MAGIC)
    while pos + _HDR.size <= len(data):
        length, crc = _HDR.unpack_from(data, pos)
        end = pos + _HDR.size + length
        if end > len(data):
            break  # incomplete: the primary is mid-append — retry later
        payload = data[pos + _HDR.size:end]
        if zlib.crc32(payload) != crc:
            break  # not yet flushed fully (or corrupt): retry later
        try:
            rec = _safe_loads(payload)
        except Exception:
            break
        epoch = record_epoch(rec)
        if epoch < high_epoch:
            break  # fenced-zombie write: never apply, never consume
        high_epoch = epoch
        records.append(rec)
        pos = end
    return records, pos


class _ReadOnlyLog:
    """Log proxy handed out by a ``read_only=True`` driver: every read
    passes through; every mutation raises :class:`ReadOnlyPersistenceError`
    by name (defense in depth behind the driver-level guards)."""

    def __init__(self, inner):
        self._inner = inner
        self.path = getattr(inner, "path", None)

    def read_all(self):
        return self._inner.read_all()

    def append(self, time, entries, epoch=0):
        raise ReadOnlyPersistenceError(
            "append() on a read-only persistence root — a replica must "
            "never write to its primary's WAL")

    def truncate_to(self, tick):
        raise ReadOnlyPersistenceError(
            "truncate_to() on a read-only persistence root — a replica "
            "must never compact its primary's WAL")

    def truncate_after(self, tick):
        raise ReadOnlyPersistenceError(
            "truncate_after() on a read-only persistence root — a "
            "replica must never rewrite the primary's WAL tail")

    def close(self):
        self._inner.close()


class _RecordingSession:
    """Session proxy for a restarted source: buffers live entries (with
    their source offsets) for durable append at the next commit. For
    non-seekable sources it additionally drops the first ``skip`` live
    entries — those were replayed from the snapshot log (the reference's
    offset-continuation, expressed as replay+skip). Duck-types
    io._datasource.Session (push/drain/close/closed).

    **Durability seals**: the streaming loop stamps ``seal(tick)``
    immediately before draining the inner session for tick ``tick``, so
    every entry under a seal was drained — and therefore fully processed
    — by that tick. The commit loop then takes exactly the prefix sealed
    at ticks <= the bridge's resolved watermark: an entry becomes durable
    only once its tick provably retired, at any in-flight depth."""

    def __init__(self, inner, skip: int):
        self._inner = inner
        self._skip = skip
        self.pending: list = []  # (key, row, diff, offset)
        # (tick, cumulative pending length at seal time), tick-ascending.
        # The mutex serializes reader-thread pushes against the commit
        # loop's seal/take (a push between the take's slice and rebind
        # would otherwise be dropped from durability forever).
        self._seals: list[tuple[int, int]] = []
        # entries drained (processed) but not yet taken by a commit:
        # under QoS ingest budgeting (engine/qos.py) a tick's drain may
        # be PARTIAL, so seals must cover exactly the drained prefix —
        # pushed-but-undrained entries stay past the newest seal and get
        # sealed by the later tick that actually drains them (sealed ⊆
        # processed is preserved at any clip point)
        self._drained = 0
        self._mutex = create_lock("RecordingSession._mutex")
        self.closed = inner.closed
        self.stopping = inner.stopping
        self.recorder = getattr(inner, "recorder", None)

    @property
    def stop_requested(self) -> bool:
        return self.stopping.is_set()

    def sleep(self, seconds: float) -> bool:
        return self._inner.sleep(seconds)

    def push(self, key, row, diff: int = 1, offset=None) -> None:
        if self._skip > 0:
            self._skip -= 1
            return
        with self._mutex:
            # the inner push stays INSIDE the mutex: seal_drain drains
            # the inner session and seals pending atomically under it, so
            # an entry must never be recordable (pending) without being
            # drainable (inner) — a push split across the mutex boundary
            # could be sealed at tick t yet processed at t+1, and a
            # snapshot at t would cover it without containing it
            self.pending.append((key, row, diff, offset))
            self._inner.push(key, row, diff)

    def seal(self, tick: int) -> None:
        """Mark everything pushed so far as belonging to ``tick``'s drain
        (called right before the drain, so sealed ⊆ processed-by-tick).
        The full-commit path only (end-of-stream, sync callers): the
        streaming loop's drains all go through :meth:`seal_drain`, and at
        end of stream the re-drain loop has emptied the inner session, so
        sealing the whole pending list never covers an unprocessed
        entry."""
        with self._mutex:
            self._drained = len(self.pending)
            self._seal_locked(tick, self._drained)

    def _seal_locked(self, tick: int, n: int) -> None:
        if self._seals and self._seals[-1][1] == n:
            # idle tick: the existing seal already covers these
            # entries at an OLDER tick — keep it (re-stamping to the
            # newer tick would shrink what a frozen watermark may
            # commit); the list only grows when entries do
            return
        self._seals.append((tick, n))

    def seal_drain(self, tick: int, limit: int | None = None) -> list:
        """Atomically drain the inner session AND seal at ``tick`` under
        the push mutex, so *sealed at <= tick* equals *drained at <= tick*
        EXACTLY. The streaming loop uses this instead of seal-then-drain:
        an entry arriving between a separate seal and the drain would be
        processed at ``tick`` but sealed at ``tick+1`` — harmless for
        WAL-only replay, but fatal for operator-state snapshots (the
        snapshot cut at ``tick`` would already contain it while the WAL
        suffix past ``tick`` replays it again — a double count).

        ``limit`` clips the drain (QoS ingest budgeting): the seal then
        covers exactly the drained prefix — pending rows beyond it belong
        to no seal until a later tick drains them, so a deferred row can
        never be covered by a checkpoint before the engine processed it.
        Push order and drain order coincide (both append under the push
        path), so the drained prefix of the inner queue IS the prefix of
        ``pending``."""
        with self._mutex:
            entries = self._inner.drain(limit)
            self._drained += len(entries)
            self._seal_locked(tick, self._drained)
            return entries

    def take_sealed(self, watermark: int) -> list:
        """Remove and return every pending entry under a seal with tick
        <= ``watermark`` — the longest durable-eligible prefix."""
        with self._mutex:
            n = 0
            cut = 0
            for i, (tick, count) in enumerate(self._seals):
                if tick > watermark:
                    break
                n = count
                cut = i + 1
            if cut:
                self._seals = [(t, c - n) for t, c in self._seals[cut:]]
            if n == 0:
                return []
            entries, self.pending = self.pending[:n], self.pending[n:]
            self._drained -= n
            return entries

    def drain(self, limit: int | None = None) -> list:
        return self._inner.drain(limit)

    def close(self) -> None:
        self._inner.close()


def source_id(datasource) -> str:
    """Stable durable identity of a source (shared by the driver and the
    replica tailer — both sides of the WAL must agree on it)."""
    pid = getattr(datasource, "persistent_id", None)
    if pid:
        return str(pid)
    # `_uid` is a process-wide construction counter: stable only if the
    # program builds the same sources in the same order every run.
    logger.warning(
        "source %r has no persistent_id; falling back to construction "
        "order (%s-%s) — adding/reordering sources between runs will "
        "mismatch snapshot logs. Pass persistent_id= to the connector.",
        datasource.name, datasource.name, datasource._uid)
    return f"{datasource.name}-{datasource._uid}"


class PersistenceDriver:
    """Engine side of ``pw.persistence.Config`` (python half at
    pathway_tpu/persistence/__init__.py; reference equivalent
    persistence/__init__.py:12,89 + src/persistence/tracker.rs)."""

    # class-level defaults so partially-constructed drivers (tests build
    # them via __new__) still read as writable and unfenced
    read_only = False
    fencing_supported = False
    fencing_epoch = 0
    fenced_writes = 0

    def __init__(self, config, read_only: bool = False):
        self.config = config
        backend = config.backend
        self.kind = backend.kind
        # read-only open mode (engine/replica.py): every mutation —
        # commit/append, WAL truncation, snapshot write, generation
        # pruning — raises ReadOnlyPersistenceError by name, so a replica
        # can never damage the primary's durability state. Reads
        # (restore_time / load_snapshot / _records) are untouched.
        self.read_only = bool(read_only)
        self._s3 = None
        if self.kind == "s3":
            # native SigV4 client (io/s3/_client.py): snapshots become
            # objects under <bucket>/<prefix>/streams/<sid>/<seq>
            from pathway_tpu.io.s3._client import (S3Client,
                                                   client_from_settings,
                                                   split_bucket_prefix)

            settings = backend.options.get("bucket_settings")
            bucket, prefix = split_bucket_prefix(
                backend.path or "",
                getattr(settings, "bucket_name", None) if settings else None)
            if settings is not None:
                self._s3 = client_from_settings(settings, bucket=bucket)
            else:
                self._s3 = S3Client(bucket=bucket)  # env credential chain
            self.root = prefix
        elif self.kind == "azure":
            # Azure Blob via the in-repo SharedKey/SAS client; blob surface
            # duck-types S3Client so the object-per-commit log is shared
            from pathway_tpu.io.azure_blob import client_from_backend

            self._s3, self.root = client_from_backend(backend)
        elif self.kind == "filesystem":
            self.root = backend.path
            if not self.read_only:
                os.makedirs(os.path.join(self.root, "streams"),
                            exist_ok=True)
        elif self.kind == "mock":
            if not hasattr(backend, "_mock_store"):
                backend._mock_store = {}
            self.root = None
        else:
            raise ValueError(f"unknown persistence backend {self.kind!r}")
        self._backend = backend
        self._sessions: list[tuple[str, Any, Any]] = []  # (sid, log, rec_session)
        self._restore_time: int | None = None
        self._record_cache: dict[str, list] = {}  # sid → records (read once)
        self._attached_ids: set[str] = set()
        # -- commit instrumentation (read via stats(); /metrics + /status) --
        self.commits = 0                 # commit() calls
        self.commits_with_data = 0       # commits that appended >= 1 record
        self.entries_committed = 0
        self.last_commit_watermark = 0   # durability frontier (monotone)
        self.last_commit_tick = 0        # loop tick at the last commit
        self.last_inflight_at_commit = 0  # bridge depth when committing
        self.commit_wait = _WaitHistogram()
        # -- operator-state snapshots + WAL compaction ---------------------
        # (filesystem + mock backends; object stores keep WAL-only
        # recovery until they grow an atomic-manifest story)
        self.snapshots_supported = self.kind in ("filesystem", "mock")
        self._snap_dir = (os.path.join(self.root, "snapshots")
                          if self.kind == "filesystem" else None)
        self._loaded_snapshot: dict | None = None
        self._snapshot_probed = False
        self._snapshot_warned = False
        # generation validity cache: gens this driver wrote or whose
        # state blob already passed its checksum (re-verified at most
        # once per generation) vs gens known corrupt — retention must
        # never let a corrupt generation occupy a keep slot (it would
        # prune the valid fallback and truncate the WAL to a tick only
        # the corrupt generation covers)
        self._validated_gens: set[int] = set()
        self._corrupt_gens: set[int] = set()
        self.last_snapshot_tick = 0
        self.snapshot_generation = 0     # 0 = none yet; generations are 1-based
        self.snapshot_bytes = 0
        self.snapshots_total = 0         # written by THIS driver
        self.compactions_total = 0
        self.wal_replayable_entries = 0  # entries a restart would replay
        self.wal_bytes_since_snapshot = 0
        # durable entries NOT covered by the newest snapshot (freshly
        # committed ones plus a restart's replayed suffix): the
        # no-empty-churn guard — a snapshot is only worth writing while
        # this is non-zero
        self.wal_entries_uncovered = 0
        # per-source compact resume frontier, maintained on every commit:
        # entry/insert counts, per-file positions (fs offsets) and the
        # partition antichain — what the manifest stores so seek-capable
        # sources can continue past a COMPACTED prefix
        self._frontiers: dict[str, dict] = {}
        # -- write-path failover fencing (README "Write-path failover") ----
        # The root carries a monotone fencing epoch in an fsynced manifest
        # (<root>/epoch.json, PATHWAY_FLEET_EPOCH_PATH to override; mock
        # roots keep it on the Backend object). A writable driver ADOPTS
        # the existing epoch at open; promotion bumps it (claim_epoch);
        # every commit/snapshot first re-reads the manifest and raises
        # FencedPrimaryError when the root moved past this writer's epoch
        # — a zombie ex-primary self-demotes before any byte lands.
        # Object-store roots have no atomic read-modify-write manifest;
        # fencing stays off there (epoch 0, checks pass).
        self.fencing_supported = self.kind in ("filesystem", "mock")
        self.fenced_writes = 0
        self.fencing_epoch = self.read_epoch() if self.fencing_supported \
            else 0

    # -- fencing epoch (write-path failover) -------------------------------
    def epoch_path(self) -> str | None:
        """Filesystem path of the fencing-epoch manifest (None on
        non-file backends)."""
        if self.kind != "filesystem":
            return None
        return os.environ.get("PATHWAY_FLEET_EPOCH_PATH") \
            or os.path.join(self.root, "epoch.json")

    def read_epoch(self) -> int:
        """The root's current fencing epoch (0 = no promotion ever).
        The manifest is written atomically (tmp + fsync + replace), so
        a crash mid-bump leaves the previous epoch intact — never a
        torn manifest; an unreadable one is treated as epoch 0, loudly
        (fencing degrades open, it never bricks the root)."""
        if self.kind == "mock":
            return int(getattr(self._backend, "_mock_epoch", 0) or 0)
        path = self.epoch_path()
        if path is None:
            return 0
        import json

        try:
            with open(path) as f:
                meta = json.load(f)
            return int(meta.get("epoch", 0))
        except FileNotFoundError:
            return 0
        except Exception as e:
            logger.error(
                "unreadable fencing-epoch manifest %s (%s: %s) — "
                "treating the root as epoch 0 (fencing disabled until "
                "the manifest is rewritten)", path, type(e).__name__, e)
            return 0

    def claim_epoch(self, holder: str, min_epoch: int = 0) -> int:
        """Atomically bump the root's fencing epoch past every epoch any
        writer ever held (and past ``min_epoch``, the router's election
        hint) and adopt it — the promotion step that fences the dead
        (or SIGSTOP-zombied) primary out of the write path forever."""
        if self.read_only:
            raise ReadOnlyPersistenceError(
                "claim_epoch() on a read-only persistence root — flip "
                "the driver writable (promote) before claiming")
        if not self.fencing_supported:
            raise ValueError(
                f"fencing epochs are not supported on the {self.kind!r} "
                "persistence backend (no atomic manifest)")
        new = max(self.read_epoch() + 1, int(min_epoch))
        # fault point: a candidate dying INSIDE the claim must leave the
        # previous epoch manifest intact (the atomic write never ran)
        faults.hit("persistence.epoch.claim", holder=str(holder),
                   epoch=new)
        if self.kind == "mock":
            self._backend._mock_epoch = new
        else:
            import json

            meta = {"format": "pwepoch1", "epoch": new,
                    "holder": str(holder), "bumped_at": _time.time()}
            with blocking_call("persistence.epoch.claim"):
                _atomic_write_bytes(self.epoch_path(),
                                    json.dumps(meta).encode())
        self.fencing_epoch = new
        logger.warning(
            "fencing epoch bumped to %d by %r — every writer still "
            "holding an older epoch is fenced out of this root", new,
            holder)
        return new

    def check_fenced(self, what: str) -> None:
        """Refuse a durable write if the root's epoch moved past this
        writer's (a newer primary was promoted). Called at the top of
        every commit() and write_snapshot() — the fencing read happens
        BEFORE any byte of the write lands."""
        if not self.fencing_supported or self.read_only:
            return
        root_epoch = self.read_epoch()
        if root_epoch > self.fencing_epoch:
            self.fenced_writes += 1
            raise FencedPrimaryError(self.fencing_epoch, root_epoch, what)

    def promote(self, holder: str, complete_tick: int,
                min_epoch: int = 0) -> tuple[int, int]:
        """Flip a replica's read-only driver into the fleet's new
        writable primary: re-read the root fresh (the hydration-time
        caches are stale by now), bump+adopt the fencing epoch, and
        drop the dead primary's incomplete final commit — every record
        past ``complete_tick`` (the last COMPLETE tick the promoting
        replica applied; a mid-commit death leaves later records in
        SOME logs only). Returns ``(max_tick_seen, epoch)`` where
        ``max_tick_seen`` is the highest tick present in any log BEFORE
        the suffix cut — the new primary's time counter starts past it
        so a torn tick number is never reused."""
        if not self.fencing_supported:
            raise ValueError(
                f"promotion requires a filesystem (or mock) persistence "
                f"root, not {self.kind!r}")
        self.read_only = False
        if self.kind == "filesystem":
            os.makedirs(os.path.join(self.root, "streams"), exist_ok=True)
        # hydration-time caches were taken when this driver opened the
        # root read-only; the dead primary kept writing since
        self._record_cache.clear()
        self._restore_time = None
        self._snapshot_probed = False
        self._loaded_snapshot = None
        max_tick = self.restore_time()  # BEFORE the cut: torn ticks too
        epoch = self.claim_epoch(holder, min_epoch)
        dropped = 0
        for sid in self.list_source_ids():
            log = self._log_for(sid)
            if hasattr(log, "truncate_after"):
                dropped += log.truncate_after(complete_tick)
            log.close()
        if dropped:
            logger.warning(
                "promotion to epoch %d dropped %d entry(ies) of the dead "
                "primary's incomplete final commit (records past tick "
                "%d) — none were acknowledged-complete ticks", epoch,
                dropped, complete_tick)
            self._record_cache.clear()
            self._restore_time = None
        return max_tick, epoch

    # -- identity ----------------------------------------------------------
    def _source_id(self, datasource) -> str:
        return source_id(datasource)

    def _log_for(self, source_id: str):
        if self.kind == "mock":
            log = MockLog(self._backend._mock_store, source_id)
        elif self._s3 is not None:
            log = S3SnapshotLog(self._s3, self.root, source_id)
        else:
            log = SnapshotLog(os.path.join(self.root, "streams",
                                           source_id + ".snap"))
        return _ReadOnlyLog(log) if self.read_only else log

    def stream_path(self, source_id: str) -> str | None:
        """Filesystem path of a source's WAL (None on non-file backends)
        — the byte-level tail surface engine/replica.py polls."""
        if self.kind != "filesystem":
            return None
        return os.path.join(self.root, "streams", source_id + ".snap")

    def oldest_snapshot_tick(self) -> int | None:
        """Tick of the OLDEST retained snapshot generation (None when no
        generation exists). Compaction truncates every WAL to the suffix
        past exactly this tick, so it is the floor of what the log still
        contains — a replica whose applied tick is below it after a
        compaction rescan has provably missed records
        (engine/replica.py)."""
        metas = self._list_generations()
        if not metas:
            return None
        return min(int(m.get("tick", 0)) for m in metas)

    def list_source_ids(self) -> list[str]:
        """Every source id with a durable log under this root (the
        replica's tail set: a source whose id appears here is hydrated
        and tailed from the primary's WAL instead of read live)."""
        if self.kind == "mock":
            return sorted(self._backend._mock_store.keys())
        if self._s3 is not None:
            prefix = "/".join(p for p in (self.root.strip("/"), "streams")
                              if p) + "/"
            return sorted({
                obj["key"][len(prefix):].split("/", 1)[0]
                for obj in self._s3.list_objects(prefix)})
        streams = os.path.join(self.root, "streams")
        if not os.path.isdir(streams):
            return []
        return sorted(f[:-5] for f in os.listdir(streams)
                      if f.endswith(".snap"))

    # -- per-source resume frontier (manifest payload) ---------------------
    def _frontier(self, sid: str) -> dict:
        fr = self._frontiers.get(sid)
        if fr is None:
            fr = self._frontiers[sid] = {
                "entries": 0,   # durable entries, any diff (skip counter)
                "inserts": 0,   # durable insertions (fs key-seq counter)
                "files": {},    # fkey -> [mtime, rows, saw_last]
                "parts": {},    # partition -> max offset (antichain)
            }
        return fr

    @staticmethod
    def _frontier_fold(fr: dict, entries: list) -> None:
        """Fold durable entries' offset labels into the compact frontier —
        the summary the snapshot manifest stores so seek-capable sources
        can continue past a prefix whose WAL records were compacted."""
        files, parts = fr["files"], fr["parts"]
        for entry in entries:
            fr["entries"] += 1
            if entry[2] > 0:
                fr["inserts"] += 1
            offset = entry[3] if len(entry) > 3 else None
            if not isinstance(offset, tuple):
                continue
            if len(offset) == 3 and offset[0] == "part":
                _kind, p, o = offset
                cur = parts.get(p)
                if cur is None or o > cur:
                    parts[p] = o
            elif len(offset) == 5:
                kind, fkey, mtime, idx, is_last = offset
                fkey = str(fkey)
                if kind == "retract":
                    # the file changed and its old rows were retracted:
                    # forget the stale position (new rows re-populate)
                    files.pop(fkey, None)
                    continue
                st = files.get(fkey)
                if st is None or st[0] != mtime:
                    st = files[fkey] = [mtime, 0, False]
                st[1] = max(st[1], idx + 1)
                st[2] = bool(st[2] or is_last)

    # -- operator-state snapshots ------------------------------------------
    def _list_generations(self) -> list[dict]:
        """Manifest dicts of every on-disk generation, newest first. A
        manifest that fails to parse is skipped (and logged): the
        generation's state file without its manifest is an orphan from a
        crash mid-write, never a valid snapshot."""
        metas: list[dict] = []
        if self.kind == "mock":
            metas = list(getattr(self._backend, "_mock_snapshots", []))
        elif self._snap_dir and os.path.isdir(self._snap_dir):
            import json

            for fname in os.listdir(self._snap_dir):
                if not fname.endswith(".json"):
                    continue
                path = os.path.join(self._snap_dir, fname)
                try:
                    with open(path) as f:
                        meta = json.load(f)
                    meta["_manifest_path"] = path
                    metas.append(meta)
                except Exception as e:
                    logger.error(
                        "unreadable snapshot manifest %s (%s: %s) — "
                        "skipping that generation", path,
                        type(e).__name__, e)
        return sorted(metas, key=lambda m: m.get("generation", 0),
                      reverse=True)

    def _read_state_blob(self, meta: dict) -> bytes:
        if self.kind == "mock":
            data = meta["state"]
        else:
            with open(os.path.join(self._snap_dir,
                                   meta["state_file"]), "rb") as f:
                data = f.read()
        if not data.startswith(_STATE_MAGIC):
            raise ValueError("state file missing magic header")
        blob = data[len(_STATE_MAGIC):]
        if len(blob) != int(meta["state_bytes"]) \
                or zlib.crc32(blob) != int(meta["state_crc32"]):
            raise ValueError("state checksum mismatch (corrupt snapshot)")
        return blob

    def load_snapshot(self) -> dict | None:
        """Newest VALID snapshot generation (checksum-verified, decoded by
        the restricted unpickler), or None. A corrupt newest generation
        falls back one generation, loudly — the WAL keeps the suffix back
        to the oldest RETAINED generation, so the fallback replays more
        but recovers byte-identically."""
        if self._snapshot_probed:
            return self._loaded_snapshot
        self._snapshot_probed = True
        if not self.snapshots_supported or not _restore_enabled():
            return None
        for meta in self._list_generations():
            gen = int(meta.get("generation", 0))
            try:
                blob = self._read_state_blob(meta)
                payload = _safe_loads(blob)
            except Exception as e:
                logger.error(
                    "snapshot generation %d unreadable (%s: %s) — "
                    "falling back one generation", gen,
                    type(e).__name__, e)
                self._corrupt_gens.add(gen)
                continue
            self._validated_gens.add(gen)
            tick = int(meta["snapshot_tick"])
            self._loaded_snapshot = {
                "generation": gen, "tick": tick, "payload": payload,
                "sources": meta.get("sources") or {}}
            self.last_snapshot_tick = tick
            self.snapshot_generation = gen
            self.snapshot_bytes = len(blob)
            logger.info(
                "restored operator-state snapshot generation %d "
                "(tick %d, %d bytes) — replaying only the WAL suffix",
                gen, tick, len(blob))
            return self._loaded_snapshot
        return None

    def write_snapshot(self, tick: int, payload_obj) -> bool:
        """Durably record an operator-state snapshot at ``tick`` (all
        entries sealed <= tick are already committed by the caller), then
        compact: truncate each source's WAL to the suffix past the oldest
        RETAINED generation's tick and prune old generations. Write
        order — state file, then manifest (each atomic: tmp + fsync +
        rename), then truncation — makes every crash point safe: before
        the manifest, the generation does not exist; after it, covered
        WAL records are ignored on replay whether or not the truncation
        ran."""
        if self.read_only:
            raise ReadOnlyPersistenceError(
                "write_snapshot() on a read-only persistence root — a "
                "replica must never write snapshot generations")
        self.check_fenced("write_snapshot()")
        if not self.snapshots_supported:
            if not self._snapshot_warned:
                self._snapshot_warned = True
                logger.warning(
                    "operator-state snapshots are not supported on the "
                    "%r persistence backend — recovery stays full-WAL "
                    "replay (restart cost grows with history)", self.kind)
            return False
        if tick <= self.last_snapshot_tick:
            return False  # watermark did not advance: no empty churn
        blob = pickle.dumps(payload_obj, protocol=pickle.HIGHEST_PROTOCOL)
        # write-time proof the restricted unpickler accepts this snapshot:
        # a checkpoint that cannot load must never truncate the WAL
        _safe_loads(blob)
        existing = self._list_generations()
        gen = (int(existing[0].get("generation", 0)) + 1) if existing \
            else self.snapshot_generation + 1
        sources = {
            sid: {"covered": fr["entries"], "inserts": fr["inserts"],
                  "files": fr["files"],
                  "parts": [[p, o] for p, o in fr["parts"].items()]}
            for sid, fr in self._frontiers.items()}
        faults.hit("persistence.snapshot.write", tick=tick, generation=gen)
        meta = {"format": "pwsnapmeta1", "generation": gen,
                "snapshot_tick": tick, "state_bytes": len(blob),
                "state_crc32": zlib.crc32(blob), "sources": sources,
                "epoch": self.fencing_epoch,
                "wrote_at": _time.time()}
        if self.kind == "mock":
            meta["state"] = _STATE_MAGIC + blob
            snaps = getattr(self._backend, "_mock_snapshots", None)
            if snaps is None:
                snaps = self._backend._mock_snapshots = []
            snaps.append(meta)
        else:
            os.makedirs(self._snap_dir, exist_ok=True)
            state_file = f"{gen:08d}.state"
            meta["state_file"] = state_file
            with blocking_call("persistence.snapshot.write"):
                _atomic_write_bytes(
                    os.path.join(self._snap_dir, state_file),
                    _STATE_MAGIC + blob)
                from pathway_tpu.engine.flight_recorder import \
                    atomic_write_json

                atomic_write_json(
                    os.path.join(self._snap_dir, f"{gen:08d}.json"), meta)
        self.snapshot_generation = gen
        self.last_snapshot_tick = tick
        self.snapshots_total += 1
        self.snapshot_bytes = len(blob)
        self.wal_bytes_since_snapshot = 0
        self.wal_entries_uncovered = 0
        # every durable entry now sits in a record <= tick: a normal-path
        # restart replays nothing (records physically retained for the
        # generation-fallback window are filtered by the snapshot tick)
        self.wal_replayable_entries = 0
        self._validated_gens.add(gen)
        self._compact()
        return True

    def _gen_valid(self, meta: dict) -> bool:
        """Checksum-verify a generation at most once (this driver's own
        writes and load-time passes are pre-validated)."""
        gen = int(meta.get("generation", 0))
        if gen in self._validated_gens:
            return True
        if gen in self._corrupt_gens:
            return False
        try:
            self._read_state_blob(meta)
        except Exception as e:
            logger.error(
                "snapshot generation %d is corrupt (%s: %s) — excluded "
                "from retention (it must not shadow a valid fallback)",
                gen, type(e).__name__, e)
            self._corrupt_gens.add(gen)
            return False
        self._validated_gens.add(gen)
        return True

    def _compact(self) -> None:
        """Truncate WAL prefixes covered by the oldest retained VALID
        generation and prune everything else — corrupt generations never
        occupy a retention slot (keeping one would prune the real
        fallback and truncate the WAL to a tick only the corrupt
        generation covers). Runs strictly after the new generation is
        durable; a crash at any point here only costs replay time, never
        data."""
        if self.read_only:
            raise ReadOnlyPersistenceError(
                "_compact() on a read-only persistence root — a replica "
                "must never truncate the primary's WAL or prune its "
                "snapshot generations")
        gens = self._list_generations()
        valid = [m for m in gens if self._gen_valid(m)]
        kept = valid[:_keep_generations()]
        kept_ids = {id(m) for m in kept}
        if _compact_enabled() and kept:
            truncate_tick = int(kept[-1]["snapshot_tick"])
            faults.hit("persistence.compact.truncate", tick=truncate_tick)
            dropped_entries = 0
            for _sid, log, _rec in self._sessions:
                if hasattr(log, "truncate_to"):
                    dropped_entries += log.truncate_to(truncate_tick)
            if dropped_entries:
                self.compactions_total += 1
        for meta in gens:
            if id(meta) not in kept_ids:
                self._delete_generation(meta)

    def _delete_generation(self, meta: dict) -> None:
        if self.kind == "mock":
            try:
                self._backend._mock_snapshots.remove(meta)
            except ValueError:
                pass
            return
        # manifest first: a state file without a manifest is an inert
        # orphan, while a manifest without its state would be a loud
        # (checksum-failing) fallback on every restart
        for path in (meta.get("_manifest_path"),
                     os.path.join(self._snap_dir,
                                  meta.get("state_file", ""))
                     if meta.get("state_file") else None):
            if path:
                try:
                    os.unlink(path)
                except OSError:
                    pass

    # -- runtime API (called by StreamingRuntime) --------------------------
    def _records(self, sid: str) -> list:
        """Read (and cache) a source's log records — restore_time and
        attach_source both need them; unpickle only once per startup."""
        if sid not in self._record_cache:
            self._record_cache[sid] = self._log_for(sid).read_all()
        return self._record_cache[sid]

    def restore_time(self) -> int:
        """Last committed logical time across all logged sources (0 = fresh)."""
        if self._restore_time is not None:
            return self._restore_time
        snap = self.load_snapshot()
        last = snap["tick"] if snap is not None else 0
        for sid in self.list_source_ids():
            for rec in self._records(sid):
                last = max(last, rec[0])
        self._restore_time = last
        return last

    def attach_source(self, datasource, session, replay: bool = True):
        """Replay this source's durable prefix into ``session`` and return
        the recording proxy the live reader thread must push into.

        Two continuation protocols (reference: connectors/mod.rs:215-368 —
        ``rewind_from_disk_snapshot`` then continue from stored offsets):

        - **seekable** sources (define ``seek(replayed_entries)``) receive
          every replayed ``(key, row, diff, offset)`` and position their
          reader past the durable prefix themselves; nothing live is
          dropped. This is exact under reordering and file mutation.
        - otherwise the source is assumed to re-emit the identical entry
          sequence on restart, and the first N live pushes are dropped.

        ``replay=False`` — the promotion path (engine/streaming.py): the
        promoting replica's scheduler already holds the durable state
        (it tailed every complete tick), so nothing is pushed; only the
        resume frontier, the seek protocol and the skip counter are set
        up exactly as a restart would, so the new primary's readers
        continue past the durable prefix without double-applying it.
        """
        if self.read_only:
            raise ReadOnlyPersistenceError(
                "attach_source() on a read-only persistence root — a "
                "replica hydrates through engine/replica.py (tail-only), "
                "never through the recording/commit path")
        sid = self._source_id(datasource)
        if sid in self._attached_ids:
            raise ValueError(
                f"two persisted sources share the id {sid!r} — their snapshot "
                "logs would cross-replay into each other's tables. Give each "
                "connector a unique persistent_id.")
        self._attached_ids.add(sid)
        log = self._log_for(sid)
        snap = self.load_snapshot()
        snap_tick = snap["tick"] if snap is not None else 0
        src_meta = (snap["sources"].get(sid)
                    if snap is not None else None) or {}
        covered = int(src_meta.get("covered", 0))
        records = self._records(sid)
        if snap_tick:
            # records <= the snapshot tick are covered by restored
            # operator state. A crash between snapshot-durable and
            # WAL-truncate leaves them in the log — they are ignored
            # here, never replayed on top of the state that already
            # includes them.
            records = [r for r in records if r[0] > snap_tick]
        replayed: list = []
        for rec in records:
            for entry in rec[1]:
                key, row, diff = entry[0], entry[1], entry[2]
                offset = entry[3] if len(entry) > 3 else None
                if replay:
                    session.push(key, row, diff)
                replayed.append((key, row, diff, offset))
        self.wal_replayable_entries += len(replayed)
        self.wal_entries_uncovered += len(replayed)
        # resume frontier: continue from the manifest's compact summary,
        # then fold the replayed WAL suffix on top
        fr = self._frontier(sid)
        if src_meta:
            fr["entries"] = covered
            fr["inserts"] = int(src_meta.get("inserts", 0))
            fr["files"] = {k: list(v)
                           for k, v in (src_meta.get("files") or {}).items()}
            fr["parts"] = {p: o for p, o in (src_meta.get("parts") or [])}
        self._frontier_fold(fr, replayed)
        from pathway_tpu.engine.offsets import OffsetAntichain

        antichain = OffsetAntichain(fr["parts"]) if fr["parts"] else None
        if antichain and hasattr(datasource, "seek_offsets"):
            # partitioned source: continue each partition past its durable
            # frontier (reference OffsetAntichain, persistence/frontier.rs)
            datasource.seek_offsets(antichain)
            skip = 0
        elif covered and hasattr(datasource, "seek_snapshot"):
            # the prefix was compacted away: hand the source the MANIFEST
            # frontier (per-file positions, insert count) plus the raw
            # WAL suffix — it positions its reader without the entries
            datasource.seek_snapshot(
                {"files": fr["files"], "inserts": fr["inserts"]}, replayed)
            skip = 0
        elif hasattr(datasource, "seek") and not covered:
            datasource.seek(replayed)
            skip = 0
        else:
            if covered and hasattr(datasource, "seek"):
                import logging

                logging.getLogger(__name__).warning(
                    "source %r defines seek() but not seek_snapshot(); "
                    "its replay prefix was compacted by an operator-state "
                    "snapshot, so resume falls back to the prefix-skip "
                    "protocol (the reader is assumed to re-emit the "
                    "identical first %d entries).", sid,
                    covered + len(replayed))
            elif replayed or covered:
                import logging

                logging.getLogger(__name__).warning(
                    "resuming source %r with the prefix-replay protocol: the "
                    "reader is assumed to re-emit the identical first %d "
                    "entries on restart. Sources that re-read *current* "
                    "state (databases, compacted topics) need a seek() "
                    "implementation for exact resume.", sid,
                    covered + len(replayed))
            skip = covered + len(replayed)
        rec = _RecordingSession(session, skip=skip)
        self._sessions.append((sid, log, rec))
        return rec

    def seal(self, tick: int) -> None:
        """Stamp a durability seal on every recorded source (streaming
        loop, right before the tick's drain)."""
        for _sid, _log, rec in self._sessions:
            rec.seal(tick)

    def commit(self, time: int, watermark: int | None = None,
               inflight: int = 0) -> None:
        """Durably record entries whose processing is provably complete.

        ``watermark=None`` — synchronous callers and the end-of-stream
        flush: everything pushed so far is sealed at ``time`` and
        committed (the caller holds hard-barrier semantics: ``time`` is
        fully processed when this runs).

        With a watermark — the pipelined streaming loop: only entries
        sealed at ticks <= ``watermark`` (the device bridge's resolved
        prefix) are appended, in a record carrying the *watermark* tick.
        Either way the log invariant is the same: a record's presence
        implies its time was fully processed — now held exactly, at any
        in-flight depth, instead of by draining the bridge first.
        Transient backend write failures retry inside the log's append
        (``_retrying_write``)."""
        if self.read_only:
            raise ReadOnlyPersistenceError(
                "commit() on a read-only persistence root — a replica "
                "must never append to the primary's WAL")
        self.check_fenced("commit()")
        t0 = _time.perf_counter()
        if watermark is None:
            watermark = time
            self.seal(time)
        # fault point between reading the watermark and the durable
        # append: a crash here loses nothing (the sealed entries are
        # re-emitted by the reader on restart, never skipped)
        faults.hit("persistence.commit", time=time, watermark=watermark)
        wrote = False
        for sid, log, rec in self._sessions:
            entries = rec.take_sealed(watermark)
            if entries:
                nbytes = log.append(watermark, entries,
                                    self.fencing_epoch) or 0
                self.entries_committed += len(entries)
                self.wal_replayable_entries += len(entries)
                self.wal_entries_uncovered += len(entries)
                self.wal_bytes_since_snapshot += nbytes
                self._frontier_fold(self._frontier(sid), entries)
                wrote = True
        self.commits += 1
        self.last_commit_tick = max(self.last_commit_tick, time)
        self.last_commit_watermark = max(self.last_commit_watermark,
                                         watermark)
        self.last_inflight_at_commit = inflight
        if wrote:
            self.commits_with_data += 1
            self.commit_wait.observe((_time.perf_counter() - t0) * 1e3)

    def stats(self) -> dict:
        """Commit-watermark snapshot for /status and the dashboard."""
        return {
            "commits": self.commits,
            "commits_with_data": self.commits_with_data,
            "entries_committed": self.entries_committed,
            "watermark": self.last_commit_watermark,
            "lag_ticks": max(0, self.last_commit_tick
                             - self.last_commit_watermark),
            "inflight_at_commit": self.last_inflight_at_commit,
            "write_retries": write_retries_total(),
            "commit_wait_ms_sum": round(self.commit_wait.sum_ms, 3),
            "commit_wait_count": self.commit_wait.count,
            # -- snapshot / compaction tier --------------------------------
            "snapshot_tick": self.last_snapshot_tick,
            "snapshot_generation": self.snapshot_generation,
            "snapshots_total": self.snapshots_total,
            "snapshot_bytes": self.snapshot_bytes,
            "snapshot_age_ticks": max(0, self.last_commit_tick
                                      - self.last_snapshot_tick),
            "compactions_total": self.compactions_total,
            "wal_replayable_entries": self.wal_replayable_entries,
            # -- write-path failover fencing -------------------------------
            "fencing_epoch": self.fencing_epoch,
            "fenced_writes": self.fenced_writes,
        }

    def close(self) -> None:
        for _sid, log, _rec in self._sessions:
            log.close()
