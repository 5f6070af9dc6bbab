"""pw.io.fs — filesystem connector
(reference: python/pathway/io/fs + src/connectors/data_storage.rs
FilesystemReader:566, FileWriter:538). Formats: csv / json / plaintext /
binary / plaintext_by_file. Static mode reads eagerly.

Streaming mode over a directory, on Linux, waits on the kernel's change
notifications (inotify(7)) for the whole tree: after one full listing it
reads a file as soon as its writer closes it, it is renamed in or its times
change, and lists a directory as soon as it is made or renamed in. What
raises no such event is examined each ``refresh_interval_s``, as a poll
examines it: a file written to and still held open, a link made in the
tree, and every file reached through a symbolic or a second hard link
(whose content can change through a path outside the tree). Every other
streaming source polls: it lists and ``stat``s every file each
``refresh_interval_s``. So do a glob, a single file, a platform without
inotify, a tree the kernel will not watch (its watch limit) and a tree on
a network or FUSE filesystem, whose changes made on another host raise no
event here. Both push the same rows under the same offsets; deletions are
ignored."""

from __future__ import annotations

import csv as _csv
import ctypes
import errno
import functools
import json as _json
import os
import select
import stat
import struct
import sys
import time as _time
from pathlib import Path
from typing import Any, Callable

from pathway_tpu.internals import dtype as dt
from pathway_tpu.internals import schema as sch
from pathway_tpu.internals.json import Json
from pathway_tpu.internals.keys import hash_values
from pathway_tpu.internals.parse_graph import G
from pathway_tpu.internals.table import Plan, Table
from pathway_tpu.internals.universe import Universe
from pathway_tpu.io._datasource import (DataSource, Session,
                                        apply_connector_policy)


#: a pass that read more files than this (a backlog) records their count and
#: not each one's instants
_PASS_FILES_MAX = 64
#: and, while it reads, one ``connector.progress`` span every so many files
_PROGRESS_FILES = 256


def _join(directory: str, name: str) -> str:
    """``str(Path(directory) / name)`` for a ``directory`` in that form,
    without building the ``Path``."""
    return name if directory == "." else os.path.join(directory, name)


def _tree_files(top: str,
                before_scan: Callable[[str], None] | None = None,
                links: set[str] | None = None) -> list[str]:
    """The files under the directory ``top``, unordered: symbolic links to
    files included, directories (links to them not followed) descended, as
    ``Path.rglob`` and ``is_file`` find them. A regular file's type comes
    from its directory entry, with no ``stat``. ``top`` as
    ``str(Path(...))`` gives it, and so is every path returned.
    ``before_scan`` is called with each directory before it is read;
    ``links`` gathers the symbolic links among the files."""
    found, dirs = [], [top]
    while dirs:
        d = dirs.pop()
        if before_scan is not None:
            before_scan(d)
        try:
            entries = os.scandir(d)
        except OSError:
            continue  # gone, or not readable: as a glob skips it
        with entries:
            for entry in entries:
                # ``_join(d, entry.name)``, joined by ``scandir``
                path = entry.path if d != "." else entry.name
                if entry.is_dir(follow_symlinks=False):
                    dirs.append(path)
                elif entry.is_file():
                    found.append(path)
                    if links is not None and entry.is_symlink():
                        links.add(path)
    return found


def _in_pass_order(paths) -> list[str]:
    """``paths`` in the order a pass reads them: as their ``Path`` objects
    sort, component by component. With the separator made the lowest
    character the strings compare so (sorting the objects costs ten times
    more)."""
    return sorted(paths,
                  key=lambda f: os.path.normcase(f).replace(os.sep, "\0"))


def _list_files(path: str) -> list[str]:
    """What a pass over ``path`` examines: every file under a directory, a
    file, or a glob's matches, each as ``str(Path(...))`` gives it."""
    p = Path(path)
    if p.is_dir():
        return _in_pass_order(_tree_files(str(p)))
    if p.exists():
        return [str(p)]
    import glob

    return _in_pass_order(str(Path(f)) for f in glob.glob(path))


# inotify(7): the events a watch asks for and the bits of those it gets
_IN_MODIFY = 0x2
_IN_ATTRIB = 0x4
_IN_CLOSE_WRITE = 0x8
_IN_MOVED_FROM = 0x40
_IN_MOVED_TO = 0x80
_IN_CREATE = 0x100
_IN_DELETE = 0x200
_IN_MOVE_SELF = 0x800
_IN_Q_OVERFLOW = 0x4000
_IN_IGNORED = 0x8000
_IN_ONLYDIR = 0x1000000
_IN_ISDIR = 0x40000000
#: a file is complete when its writer closes it, it is renamed in or its
#: times are set; it is being written (or was made as a link) when it is
#: made or modified; it is gone when removed or renamed away; a directory is
#: new when it is made or renamed in; the root is lost when renamed away
_FILE_DONE = _IN_CLOSE_WRITE | _IN_MOVED_TO | _IN_ATTRIB
_FILE_OPEN = _IN_CREATE | _IN_MODIFY
_DIR_NEW = _IN_CREATE | _IN_MOVED_TO
_WATCH_MASK = (_FILE_DONE | _FILE_OPEN | _IN_DELETE | _IN_MOVED_FROM
               | _IN_MOVE_SELF | _IN_ONLYDIR)
#: ``struct inotify_event`` up to its name: wd, mask, cookie, len
_EVENT = struct.Struct("iIII")
#: statfs(2) ``f_type`` of the filesystems on which a change made on
#: another host (or by the host of a virtual machine's shared folder)
#: raises no event here: NFS, SMB, CIFS, SMB2, FUSE (sshfs, virtiofs,
#: bucket mounts), 9p, Ceph, AFS (two), Coda, NCP, Lustre, GPFS, OCFS2,
#: GFS2 and VirtualBox's shared folders
_REMOTE_FS = frozenset({
    0x6969, 0x517B, 0xFF534D42, 0xFE534D42, 0x65735546, 0x01021997,
    0x00C36400, 0x5346414F, 0x6B414653, 0x73757245, 0x564C, 0x0BD00BD0,
    0x47504653, 0x7461636F, 0x01161970, 0x786F4256})


@functools.cache
def _inotify():
    """libc's ``inotify_init1`` and ``inotify_add_watch``, and the
    ``f_type`` of a path's filesystem by ``statfs`` (None where it cannot
    be read); None where the platform has no inotify."""
    if not sys.platform.startswith("linux"):
        return None
    try:
        libc = ctypes.CDLL(None, use_errno=True)
        init, add, statfs = (libc.inotify_init1, libc.inotify_add_watch,
                             libc.statfs)
    except (OSError, AttributeError):
        return None
    init.argtypes, init.restype = [ctypes.c_int], ctypes.c_int
    add.argtypes = [ctypes.c_int, ctypes.c_char_p, ctypes.c_uint32]
    add.restype = ctypes.c_int
    statfs.argtypes = [ctypes.c_char_p, ctypes.c_void_p]
    statfs.restype = ctypes.c_int

    def fs_type(path: str) -> int | None:
        # ``struct statfs`` opens with its ``long f_type``; 256 bytes hold
        # the whole struct on every Linux ABI
        buf = ctypes.create_string_buffer(256)
        if statfs(os.fsencode(path), buf) != 0:
            return None
        return ctypes.c_long.from_buffer(buf).value & 0xFFFFFFFF

    return init, add, fs_type


class _TreeWatch:
    """One inotify descriptor watching a directory and every directory
    under it, as ``_tree_files`` walks them. The kernel names a directory
    by its watch; a path built from an event is only a hint, which the pass
    confirms with the ``stat`` it makes of every file it examines.

    Paths that no event may announce when complete are examined each
    ``interval_s``, as a poll would examine them: ``dirty``, made or
    written to since they were last examined and not yet closed or renamed
    in (a file its writer holds open, a link); ``polled``, for good, the
    files reached through a symbolic or a second hard link."""

    def __init__(self, root: str, fd: int, interval_s: float):
        # the form ``_list_files``' paths take, so that an event's path is
        # the key the listing gave the same file
        self.root = str(Path(root))
        self.fd = fd
        self.interval_s = interval_s
        self.dirs: dict[int, str] = {}  # watch descriptor -> directory
        self.root_wd = -1
        #: the kernel refused a watch, a directory lies on a remote
        #: filesystem, or the root was removed or renamed away: what lands
        #: there can no longer be followed by events
        self.broken = False
        self.dirty: set[str] = set()
        self.polled: set[str] = set()
        self.due = 0.0  # when ``dirty`` and ``polled`` are next examined
        self._poll = select.poll()
        self._poll.register(fd, select.POLLIN)

    @classmethod
    def open(cls, root: str, interval_s: float) -> "_TreeWatch | None":
        """A watch over the directory ``root``, watched itself, its
        directories from the first pass on; None where ``root`` is no
        directory, lies on a remote filesystem, or the kernel gives no
        descriptor or watch for it."""
        calls = _inotify()
        if calls is None or not os.path.isdir(root):
            return None
        fd = calls[0](os.O_NONBLOCK | os.O_CLOEXEC)
        if fd < 0:
            return None
        watch = cls(root, fd, interval_s)
        watch._add(watch.root)
        if watch.root_wd < 0:
            watch.close()
            return None
        return watch

    def close(self) -> None:
        os.close(self.fd)

    def _add(self, d: str) -> None:
        """Watch the directory ``d``; mark the watch ``broken`` where ``d``
        lies on a remote filesystem or the kernel refuses (the watch
        limit, ``ENOSPC``)."""
        if self.broken:
            return
        _init, add, fs_type = _inotify()
        if fs_type(d) in _REMOTE_FS:
            self.broken = True
            return
        wd = add(self.fd, os.fsencode(d), _WATCH_MASK)
        if wd >= 0:
            self.dirs[wd] = d
            if d == self.root:
                self.root_wd = wd
        elif ctypes.get_errno() not in (errno.ENOENT, errno.ENOTDIR):
            self.broken = True

    def whole_tree(self) -> tuple[set[str], list[str]]:
        """The news of a pass that lists the whole tree."""
        return set(), [self.root]

    def examined(self, files: set[str], dirs: list[str]
                 ) -> tuple[list[str], str]:
        """What a pass examines, in pass order, and its ``trigger``: the
        ``files`` an event named and every file under the directories
        ``dirs``, each directory watched before it is read (a file that
        lands in between is both listed and an event, and read once). The
        root's listing, the whole tree's, is a ``"listing"``."""
        for d in dirs:
            files.update(_tree_files(d, self._add, self.polled))
        trigger = "listing" if self.root in dirs else "watch"
        return _in_pass_order(files), trigger

    def wait(self) -> tuple[set[str], list[str]] | None:
        """Wait for events, up to ``interval_s`` or to when ``dirty`` and
        ``polled`` are due, and drain every pending one. Returns what the
        next pass examines, as ``examined`` takes it, or None where there
        is nothing."""
        pending = self.dirty or self.polled
        timeout_s = (max(0.0, self.due - _time.monotonic()) if pending
                     else self.interval_s)
        files: set[str] = set()
        dirs: list[str] = []
        if self._poll.poll(timeout_s * 1e3):
            if self._read_events(files, dirs) or self.broken:
                # events were lost: the listing examines every file as it
                # is now, and a change after it raises an event again
                self.dirty.clear()
                return self.whole_tree()
        if (self.dirty or self.polled) and _time.monotonic() >= self.due:
            files |= self.dirty | self.polled
            self.dirty.clear()
            self.due = _time.monotonic() + self.interval_s
        return (files, dirs) if files or dirs else None

    def _read_events(self, files: set[str], dirs: list[str]) -> bool:
        """Drain the descriptor: each event's path to ``files`` (to read
        now), ``dirs`` (to list now), ``dirty`` or ``polled``. Returns
        whether the kernel's event queue overflowed."""
        chunks = []
        while True:
            try:
                chunks.append(os.read(self.fd, 1 << 16))
            except BlockingIOError:
                break
        data = b"".join(chunks)
        overflow = False
        at = 0
        while at < len(data):
            wd, mask, _cookie, size = _EVENT.unpack_from(data, at)
            name = data[at + _EVENT.size:at + _EVENT.size + size]
            at += _EVENT.size + size
            if mask & _IN_Q_OVERFLOW:
                overflow = True
            elif mask & (_IN_IGNORED | _IN_MOVE_SELF):
                # a directory moved inside the tree is watched anew at its
                # new path (its ``IN_MOVED_TO``)
                if mask & _IN_IGNORED:
                    self.dirs.pop(wd, None)
                self.broken |= wd == self.root_wd
            elif wd in self.dirs:
                path = _join(self.dirs[wd], os.fsdecode(name.rstrip(b"\0")))
                if mask & _IN_ISDIR:
                    if mask & _DIR_NEW:
                        dirs.append(path)
                    continue
                if mask & (_IN_CREATE | _IN_MOVED_TO) \
                        and os.path.islink(path):
                    self.polled.add(path)
                if mask & _FILE_DONE:
                    files.add(path)
                    self.dirty.discard(path)
                elif mask & _FILE_OPEN:
                    if not (self.dirty or self.polled):
                        # examined an interval after it was first written
                        self.due = _time.monotonic() + self.interval_s
                    self.dirty.add(path)
                else:   # removed or renamed away
                    self.dirty.discard(path)
                    self.polled.discard(path)
        return overflow


def _parse_file(fpath: str, format: str, schema, with_metadata: bool,
                dsv_separator: str = ","):
    """Yield value-dicts for one file."""
    meta = None
    if with_metadata:
        st = os.stat(fpath)
        meta = Json({
            "path": fpath, "size": st.st_size,
            "modified_at": int(st.st_mtime), "created_at": int(st.st_ctime),
            "seen_at": int(_time.time()),
        })
    if format == "parquet":
        import pyarrow.parquet as pq

        rows = pq.read_table(fpath).to_pylist()
    else:
        # one format dispatcher for files and object stores alike
        from pathway_tpu.io.formats import parse_payload

        with open(fpath, "rb") as f:
            payload = f.read()
        rows = parse_payload(payload, format, schema,
                             dsv_separator=dsv_separator)
    for r in rows:
        if meta is not None:
            r["_metadata"] = meta
        yield r


def _schema_for(format: str, schema, with_metadata: bool):
    if schema is not None:
        if with_metadata and "_metadata" not in schema.column_names():
            schema = schema | sch.schema_from_types(_metadata=dt.JSON)
        return schema
    if format in ("plaintext", "plaintext_by_file"):
        base = sch.schema_from_types(data=dt.STR)
    elif format == "binary":
        base = sch.schema_from_types(data=dt.BYTES)
    else:
        raise ValueError(f"schema required for format {format!r}")
    if with_metadata:
        base = base | sch.schema_from_types(_metadata=dt.JSON)
    return base


class FsSource(DataSource):
    name = "fs"

    def __init__(self, path: str, format: str, schema, mode: str,
                 with_metadata: bool, refresh_interval_s: float = 0.5,
                 autocommit_duration_ms=1500, dsv_separator: str = ","):
        super().__init__(schema, autocommit_duration_ms)
        self.path = path
        self.format = format
        self.mode = mode
        self.with_metadata = with_metadata
        self.refresh_interval_s = refresh_interval_s
        self.dsv_separator = dsv_separator

    def seek(self, replayed: list) -> None:
        """Persistence continuation (engine/persistence.py attach_source):
        reconstruct per-file read state from the replayed snapshot entries
        so run() neither re-emits durably-logged rows nor misses the tail
        of a file whose rows were only partially committed before a crash.
        Mirrors the reference's rewind-then-continue-from-offsets protocol
        (src/connectors/mod.rs:215-368) with file-granular offsets."""
        state: dict[str, dict] = {}
        n_replayed_rows = 0
        for key, row, diff, offset in replayed:
            if diff < 0:
                # retraction of an earlier emission: drop ONE instance of the
                # key, from the originating file when the offset names it
                if offset:
                    targets = [state[offset[1]]] if offset[1] in state else []
                else:
                    targets = list(state.values())
                for st in targets:
                    for i in range(len(st["rows"]) - 1, -1, -1):
                        if st["rows"][i][0] == key:
                            del st["rows"][i]
                            break
                    else:
                        continue
                    break
                continue
            n_replayed_rows += 1
            if not offset:
                continue
            kind, fkey, mtime, idx, is_last = offset
            st = state.get(fkey)
            if st is None or st["mtime"] != mtime:
                st = state[fkey] = {"mtime": mtime, "rows": [], "last": False}
            st["rows"].append((key, row))
            st["last"] = bool(is_last)
        # continue the key-seq counter past every replayed insertion so new
        # rows never reuse a durably-logged key (keyless schemas hash seq)
        self._resume_seq = n_replayed_rows
        self._resume_seen = {}
        self._resume_emitted = {}
        self._resume_skip = {}
        for fkey, st in state.items():
            if not st["rows"]:
                continue
            self._resume_emitted[fkey] = list(st["rows"])
            if st["last"]:
                self._resume_seen[fkey] = st["mtime"]
            else:
                # logged rows are a prefix of the file at this mtime
                self._resume_skip[fkey] = (st["mtime"], len(st["rows"]))

    def seek_snapshot(self, state: dict, replayed: list) -> None:
        """Persistence continuation past a COMPACTED prefix
        (engine/persistence.py operator-state snapshots): the covered
        entries' (key, row) data is gone from the WAL, so per-file
        positions come from the manifest's compact frontier —
        ``state["files"]`` maps file -> [mtime, prefix_rows, saw_last] —
        and only the WAL *suffix* still arrives as raw entries.

        Limitation vs full :meth:`seek`: rows of snapshot-covered files
        cannot be retracted if such a file mutates after the restart
        (their data was compacted away) — covered files are assumed
        immutable, which is the same append-only assumption compaction
        itself rests on (README "Fault tolerance").
        """
        self._resume_seq = int(state.get("inserts", 0))
        self._resume_seen = {}
        self._resume_emitted = {}
        self._resume_skip = {}
        suffix_rows: dict[str, list] = {}
        for key, row, diff, offset in replayed:
            if diff > 0 and offset and len(offset) == 5 \
                    and offset[0] == "row":
                suffix_rows.setdefault(str(offset[1]), []).append((key, row))
        for fkey, st in (state.get("files") or {}).items():
            mtime, nrows, saw_last = st[0], int(st[1]), bool(st[2])
            if saw_last:
                self._resume_seen[fkey] = mtime
            else:
                # durable rows are a prefix of the file at this mtime:
                # continue past them (the frontier already folded any
                # suffix entries, so nrows includes both tiers)
                self._resume_skip[fkey] = (mtime, nrows)
            # best-effort retraction data: suffix rows only (prefix rows
            # were compacted — see the limitation above)
            if fkey in suffix_rows:
                self._resume_emitted[fkey] = suffix_rows[fkey]

    def _progress(self, rec, n_pass: int, done: tuple, now: float,
                  totals: tuple) -> tuple:
        """One ``connector.progress`` span of the pass ``n_pass``: the
        stretch from ``done`` (instant, thread CPU and the pass's
        ``totals`` at the span before it, or at the end of the listing) to
        ``now``. ``totals``: changed files, rows, and the seconds in
        ``stat``, the parser and the pushes. Returns the next ``done``."""
        t_done, cpu_done, before = done
        cpu = _time.thread_time()
        files, rows, stat_s, parse_s, push_s = (
            a - b for a, b in zip(totals, before))
        rec.span("connector.progress", t_done, now,
                 ("pass", self._uid, n_pass), files=files, rows=rows,
                 cpu_ms=(cpu - cpu_done) * 1e3, stat_ms=stat_s * 1e3,
                 parse_ms=parse_s * 1e3, push_ms=push_s * 1e3)
        return now, cpu, totals

    def run(self, session: Session) -> None:
        read = _ReadState(self, session)
        watch = (_TreeWatch.open(self.path, self.refresh_interval_s)
                 if self.mode == "streaming" else None)
        # the first pass lists the whole path; with a watch, each directory
        # is watched before it is read, so that a file landing during the
        # pass is listed or an event
        news = watch.whole_tree() if watch is not None else None
        try:
            while not session.stop_requested:
                self._pass(session, read, watch, news)
                if self.mode != "streaming":
                    return
                if watch is not None and watch.broken:
                    watch.close()
                    watch = None
                if watch is None:
                    if not session.sleep(self.refresh_interval_s):
                        return
                    continue
                # under QoS backpressure a pause after a pass as long as a
                # polling source's stretched sleep
                if session.backpressure.is_set() \
                        and not session.sleep(self.refresh_interval_s):
                    return
                news = None
                while news is None:
                    # a heartbeat and a stop check once a wait
                    if not session.sleep(0):
                        return
                    news = watch.wait()
        finally:
            if watch is not None:
                watch.close()

    def _pass(self, session: Session, read: "_ReadState",
              watch: _TreeWatch | None = None,
              news: tuple[set[str], list[str]] | None = None) -> None:
        """One pass: without a ``watch``, over the whole path as it lists
        now (``trigger`` ``"listing"``); with one, over what ``news`` names
        (``_TreeWatch.examined``)."""
        seen, emitted, resume_skip = read.seen, read.emitted, read.resume_skip
        seq = read.seq
        # flight recorder (engine/flight_recorder.py): one ``connector.pass``
        # span per pass while one records, and of a backlog's pass a
        # ``connector.progress`` every ``_PROGRESS_FILES`` files; off costs
        # this lookup and a few tests per pass and per changed file, and no
        # clock read
        rec = read.recorder
        n_pass = read.n_pass
        pushed = None
        if rec is not None and rec.enabled:
            # (st_mtime, perf_counter at its last push) of each of the
            # pass's first changed files
            pushed = []
            changed = n_rows = 0
            # seconds in ``stat`` (and the test that follows it, of every
            # listed file), in the parser and the row's key (a rewritten
            # file's retractions too), and inside the rows'
            # ``session.push``; the reader thread's own CPU
            stat_s = parse_s = push_s = 0.0
            cpu_pass = _time.thread_time()
            t_pass = _time.perf_counter()
        if watch is None:
            files, trigger, polled = _list_files(self.path), "listing", None
        else:
            files, trigger = watch.examined(*news)
            polled = watch.polled
        if pushed is not None:
            # ``done``: where the stretch a progress span covers began and
            # what the pass had counted by then
            t_listed = t_file = _time.perf_counter()
            done = (t_listed, cpu_pass, (0, 0, 0.0, 0.0, 0.0))
        for f in files:
            try:
                st = os.stat(f)
            except FileNotFoundError:
                continue  # gone since it was listed or its event queued
            if not stat.S_ISREG(st.st_mode):
                continue  # an event's path that now names no file
            if polled is not None and st.st_nlink > 1:
                polled.add(f)  # may change through a path outside the tree
            mtime = st.st_mtime
            fkey = f
            if fkey in seen and seen[fkey] == mtime:
                continue
            if pushed is not None:
                t_read = _time.perf_counter()
                stat_s += t_read - t_file
                t_push, pushing = 0.0, push_s
            skip = 0
            if fkey in resume_skip:
                r_mtime, r_count = resume_skip.pop(fkey)
                if r_mtime == mtime:
                    # continue a partially-committed file from its prefix
                    skip = r_count
            if skip == 0 and fkey in emitted:
                for key, row in emitted[fkey]:
                    session.push(key, row, -1, offset=("retract", fkey,
                                                       mtime, 0, False))
            seen[fkey] = mtime
            rows = list(emitted.get(fkey, [])) if skip else []
            # one-row lookahead keeps parsing streamed (no whole-file list)
            # while still flagging the final row's offset is_last
            parsed = _parse_file(f, self.format, self.schema,
                                 self.with_metadata, self.dsv_separator)
            idx = -1
            pending_values = None
            for values in parsed:
                idx += 1
                if pending_values is not None:
                    key, row = self.row_to_engine(pending_values, seq)
                    seq += 1
                    if pushed is not None:
                        t_push = _time.perf_counter()
                    session.push(key, row, 1,
                                 offset=("row", fkey, mtime, idx - 1, False))
                    if pushed is not None:
                        push_s += _time.perf_counter() - t_push
                        t_push = 0.0
                    rows.append((key, row))
                pending_values = values if idx >= skip else None
            if pending_values is not None:
                key, row = self.row_to_engine(pending_values, seq)
                seq += 1
                if pushed is not None:
                    t_push = _time.perf_counter()
                session.push(key, row, 1,
                             offset=("row", fkey, mtime, idx, True))
                rows.append((key, row))
            emitted[fkey] = rows
            if pushed is not None:
                # the file's last push ends where the file does: a file of
                # one row costs three clock reads
                t_file = _time.perf_counter()
                if t_push:
                    push_s += t_file - t_push
                parse_s += (t_file - t_read) - (push_s - pushing)
                changed += 1
                n_rows += max(0, idx + 1 - skip)
                if changed <= _PASS_FILES_MAX:
                    pushed.append((mtime, t_file))
                elif changed % _PROGRESS_FILES == 0:
                    done = self._progress(
                        rec, n_pass, done, t_file,
                        (changed, n_rows, stat_s, parse_s, push_s))
        read.seq = seq
        if pushed is not None:
            t_end = _time.perf_counter()
            stat_s += t_end - t_file
            if changed > max(_PASS_FILES_MAX, done[2][0]):
                self._progress(
                    rec, n_pass, done, t_end,
                    (changed, n_rows, stat_s, parse_s, push_s))
            # ``listed``: the paths the pass examined (a listing's every
            # file; a watch pass's event paths, new directories' files and
            # the paths due: written to and not closed, links)
            counts = {"listed": len(files), "changed": changed,
                      "rows": n_rows, "trigger": trigger,
                      "list_ms": (t_listed - t_pass) * 1e3,
                      "cpu_ms": (_time.thread_time() - cpu_pass) * 1e3,
                      "stat_ms": stat_s * 1e3,
                      "parse_ms": parse_s * 1e3,
                      "push_ms": push_s * 1e3}
            if changed <= _PASS_FILES_MAX:
                # a trickle of live files: each one's write and push
                # instants, for the time to its commit
                counts["files"] = pushed
            rec.span("connector.pass", t_pass, t_end,
                     ("pass", self._uid, n_pass), **counts)
            read.n_pass += 1


class _ReadState:
    """What one :meth:`FsSource.run` has read, across its passes: each
    file's ``st_mtime`` and rows, the partly committed files a resume
    continues, the key counter and the passes recorded."""

    def __init__(self, source: FsSource, session: Session):
        self.seen: dict[str, float] = dict(
            getattr(source, "_resume_seen", {}))
        self.emitted: dict[str, list] = dict(
            getattr(source, "_resume_emitted", {}))
        self.resume_skip: dict[str, tuple] = dict(
            getattr(source, "_resume_skip", {}))
        self.seq = getattr(source, "_resume_seq", 0)
        self.recorder = getattr(session, "recorder", None)
        self.n_pass = 0


def read(path: str, *, format: str = "plaintext", schema=None,
         mode: str = "streaming", csv_settings=None, json_field_paths=None,
         with_metadata: bool = False, autocommit_duration_ms: int | None = 1500,
         name: str | None = None, persistent_id: str | None = None,
         dsv_separator: str = ",", connector_policy=None, **kwargs) -> Table:
    the_schema = _schema_for(format, schema, with_metadata)
    if mode == "static":
        keys, rows = [], []
        seq = 0
        src = FsSource(path, format, the_schema, mode, with_metadata,
                       dsv_separator=dsv_separator)
        for f in _list_files(path):
            for values in _parse_file(f, format, the_schema, with_metadata,
                                      dsv_separator):
                key, row = src.row_to_engine(values, seq)
                seq += 1
                keys.append(key)
                rows.append(row)
        plan = Plan("static", keys=keys, rows=rows, times=None, diffs=None)
        return Table(plan, the_schema, Universe(), name=name or "fs_static")
    source = FsSource(path, format, the_schema, mode, with_metadata,
                      autocommit_duration_ms=autocommit_duration_ms,
                      dsv_separator=dsv_separator)
    source.persistent_id = persistent_id or name
    apply_connector_policy(source, {}, policy=connector_policy)
    return Table(Plan("input", datasource=source), the_schema, Universe(),
                 name=name or "fs_input")


def write(table: Table, filename: str, *, format: str = "json", name=None,
          **kwargs) -> None:
    """Append diffs to a file as CSV / JSONLines / Parquet with time/diff
    columns (reference FileWriter output format; parquet matching the
    DeltaTableWriter's columnar sink, data_storage.rs:2687)."""
    names = table.column_names()
    path = filename

    if format == "parquet":
        def binder(runner):
            import pyarrow as pa
            import pyarrow.parquet as pq

            os.makedirs(os.path.dirname(os.path.abspath(path)),
                        exist_ok=True)
            batches: list[dict] = []

            def callback(time, delta):
                for key, row, diff in delta.entries:
                    rec = dict(zip(names, row))
                    rec["time"] = time
                    rec["diff"] = diff
                    batches.append(rec)
                # parquet is not appendable: rewrite the file per commit
                # (small sinks; larger ones want the delta-table layout)
                pq.write_table(pa.Table.from_pylist(batches), path)

            runner.subscribe(table, callback)

        G.add_output(binder, table=table, sink="fs", format="parquet")
        return

    def binder(runner):
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        f = open(path, "w", newline="")
        if format == "csv":
            writer = _csv.writer(f)
            writer.writerow(names + ["time", "diff"])

            def callback(time, delta):
                for key, row, diff in delta.entries:
                    writer.writerow(list(row) + [time, diff])
                f.flush()
        else:
            def callback(time, delta):
                for key, row, diff in delta.entries:
                    rec = dict(zip(names, row))
                    rec["time"] = time
                    rec["diff"] = diff
                    f.write(_json.dumps(rec, default=str) + "\n")
                f.flush()

        runner.subscribe(table, callback)

    G.add_output(binder, table=table, sink="fs", format=format)
