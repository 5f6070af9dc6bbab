"""pw.io.fs — filesystem connector
(reference: python/pathway/io/fs + src/connectors/data_storage.rs
FilesystemReader:566, FileWriter:538). Formats: csv / json / plaintext /
binary / plaintext_by_file. Static mode reads eagerly; streaming mode polls
the directory for new/changed files."""

from __future__ import annotations

import csv as _csv
import json as _json
import os
import time as _time
from pathlib import Path
from typing import Any

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


def _list_files(path: str) -> list[Path]:
    p = Path(path)
    if p.is_dir():
        return sorted(f for f in p.rglob("*") if f.is_file())
    if p.exists():
        return [p]
    import glob

    return sorted(Path(f) for f in glob.glob(path))


def _parse_file(fpath: Path, format: str, schema, with_metadata: bool,
                dsv_separator: str = ","):
    """Yield value-dicts for one file."""
    meta = None
    if with_metadata:
        st = fpath.stat()
        meta = Json({
            "path": str(fpath), "size": st.st_size,
            "modified_at": int(st.st_mtime), "created_at": int(st.st_ctime),
            "seen_at": int(_time.time()),
        })
    if format == "parquet":
        import pyarrow.parquet as pq

        rows = pq.read_table(str(fpath)).to_pylist()
    else:
        # one format dispatcher for files and object stores alike
        from pathway_tpu.io.formats import parse_payload

        rows = parse_payload(fpath.read_bytes(), format, schema,
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
        seen: dict[str, float] = dict(getattr(self, "_resume_seen", {}))
        emitted: dict[str, list] = dict(getattr(self, "_resume_emitted", {}))
        resume_skip: dict[str, tuple] = dict(getattr(self, "_resume_skip", {}))
        seq = getattr(self, "_resume_seq", 0)
        # flight recorder (engine/flight_recorder.py): one ``connector.pass``
        # span per polling pass while one records, and of a backlog's pass
        # a ``connector.progress`` every ``_PROGRESS_FILES`` files; off
        # costs this lookup and a few tests per pass and per changed file,
        # and no clock read
        rec = getattr(session, "recorder", None)
        n_pass = 0
        while not session.stop_requested:
            pushed = None
            if rec is not None and rec.enabled:
                # (st_mtime, perf_counter at its last push) of each of the
                # pass's first changed files
                pushed = []
                changed = n_rows = 0
                # seconds in ``stat`` (and the test that follows it, of
                # every listed file), in the parser and the row's key (a
                # rewritten file's retractions too), and inside the rows'
                # ``session.push``; the reader thread's own CPU
                stat_s = parse_s = push_s = 0.0
                cpu_pass = _time.thread_time()
                t_pass = _time.perf_counter()
            files = _list_files(self.path)
            if pushed is not None:
                # ``done``: where the stretch a progress span covers began
                # and what the pass had counted by then
                t_listed = t_file = _time.perf_counter()
                done = (t_listed, cpu_pass, (0, 0, 0.0, 0.0, 0.0))
            for f in files:
                mtime = f.stat().st_mtime
                fkey = str(f)
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
                # one-row lookahead keeps parsing streamed (no whole-file
                # list) while still flagging the final row's offset is_last
                parsed = _parse_file(f, self.format, self.schema,
                                     self.with_metadata,
                                     self.dsv_separator)
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
                                     offset=("row", fkey, mtime, idx - 1,
                                             False))
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
                    # the file's last push ends where the file does: a file
                    # of one row costs three clock reads
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
            if pushed is not None:
                t_end = _time.perf_counter()
                stat_s += t_end - t_file
                if changed > max(_PASS_FILES_MAX, done[2][0]):
                    self._progress(
                        rec, n_pass, done, t_end,
                        (changed, n_rows, stat_s, parse_s, push_s))
                counts = {"listed": len(files), "changed": changed,
                          "rows": n_rows,
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
                n_pass += 1
            if self.mode != "streaming":
                return
            if not session.sleep(self.refresh_interval_s):
                return


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
