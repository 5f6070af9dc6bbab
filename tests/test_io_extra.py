"""pyfilesystem (fsspec-backed) connector + parquet fs format
(reference: python/pathway/io/pyfilesystem/__init__.py:142; parquet ~
DeltaTableWriter's columnar sink, data_storage.rs:2687)."""

from __future__ import annotations

import pytest

import pathway_tpu as pw
from pathway_tpu.internals.parse_graph import G
from tests.utils import rows_of


@pytest.fixture(autouse=True)
def fresh_graph():
    G.clear()
    yield
    G.clear()


def test_pyfilesystem_read_local(tmp_path):
    (tmp_path / "a.txt").write_bytes(b"alpha")
    (tmp_path / "sub").mkdir()
    (tmp_path / "sub" / "b.bin").write_bytes(b"\x00\x01beta")
    t = pw.io.pyfilesystem.read(f"file://{tmp_path}", mode="static",
                                with_metadata=True)
    got = sorted(rows_of(t), key=lambda r: r[0])
    assert [r[0] for r in got] == [b"\x00\x01beta", b"alpha"]
    metas = [r[1].value for r in got]
    assert metas[0]["path"].endswith("b.bin")
    assert metas[0]["size"] == 6


def test_pyfilesystem_read_memory_fs():
    import fsspec

    fs = fsspec.filesystem("memory")
    fs.pipe("/pwtest/x.txt", b"hello")
    fs.pipe("/pwtest/y.txt", b"world")
    try:
        t = pw.io.pyfilesystem.read(fs, path="/pwtest", mode="static")
        got = sorted(rows_of(t))
        assert got == [(b"hello",), (b"world",)]
    finally:
        fs.rm("/pwtest", recursive=True)


def test_pyfilesystem_streaming_picks_up_new_files(tmp_path):
    import threading
    import time

    (tmp_path / "a.txt").write_bytes(b"one")
    seen = []
    t = pw.io.pyfilesystem.read(f"file://{tmp_path}", mode="streaming",
                                refresh_interval=0.2)
    pw.io.subscribe(t, on_change=lambda key, row, time, is_addition:
                    seen.append((row["data"], is_addition)))

    def feed():
        time.sleep(1.0)
        (tmp_path / "b.txt").write_bytes(b"two")

    th = threading.Thread(target=feed, daemon=True)
    th.start()

    runner_th = threading.Thread(
        target=lambda: pw.run(), daemon=True)
    runner_th.start()
    deadline = time.time() + 10
    while time.time() < deadline:
        if {d for d, add in seen if add} == {b"one", b"two"}:
            break
        time.sleep(0.1)
    assert {d for d, add in seen if add} == {b"one", b"two"}


def test_parquet_write_read_roundtrip(tmp_path):
    t = pw.debug.table_from_markdown("""
    name  | qty
    alice | 3
    bob   | 5
    """)
    out = str(tmp_path / "out.parquet")
    pw.io.fs.write(t, out, format="parquet")
    pw.run()

    class S(pw.Schema):
        name: str
        qty: int
        time: int
        diff: int

    G.clear()
    back = pw.io.fs.read(out, format="parquet", schema=S, mode="static")
    got = sorted(rows_of(back))
    assert [(r[0], r[1], r[3]) for r in got] == [
        ("alice", 3, 1), ("bob", 5, 1)]


def test_s3_settings_and_native_client():
    """AwsS3Settings/MinIOSettings plumbing routes into the native SigV4
    client (no s3fs) — full protocol tests live in tests/test_s3.py."""
    s = pw.io.s3.AwsS3Settings(
        bucket_name="b", access_key="ak", secret_access_key="sk",
        endpoint="https://minio.local:9000", region="us-east-1")
    assert s.access_key == "ak" and s.secret_access_key == "sk"
    assert s.endpoint == "https://minio.local:9000"
    m = pw.io.minio.MinIOSettings(
        endpoint="minio.local:9000", bucket_name="b", access_key="ak",
        secret_access_key="sk")
    aws = m.create_aws_settings()
    assert aws.endpoint == "https://minio.local:9000"
    # constructing the streaming source touches no network
    t = pw.io.s3.read("s3://b/prefix", aws_s3_settings=s)
    assert "data" in t.column_names()


def test_elasticsearch_bulk_writer_local_double(tmp_path):
    """pw.io.elasticsearch posts real bulk NDJSON over HTTP — verified
    against an in-test server double (no client lib involved)."""
    import http.server
    import threading

    received = []

    class H(http.server.BaseHTTPRequestHandler):
        def do_POST(self):
            n = int(self.headers["Content-Length"])
            received.append((self.path, self.rfile.read(n).decode()))
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.end_headers()
            self.wfile.write(b'{"errors": false}')

        def log_message(self, *a):
            pass

    srv = http.server.HTTPServer(("127.0.0.1", 0), H)
    port = srv.server_address[1]
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        t = pw.debug.table_from_markdown("""
        word | n
        a    | 1
        b    | 2
        """)
        pw.io.elasticsearch.write(
            t, f"http://127.0.0.1:{port}",
            pw.io.elasticsearch.ElasticSearchAuth.apikey("k"),
            index_name="idx")
        pw.run()
    finally:
        srv.shutdown()
    assert received, "no bulk request arrived"
    path, body = received[0]
    assert path == "/_bulk"
    import json

    lines = [json.loads(l) for l in body.strip().splitlines()]
    actions = [l for l in lines if "index" in l]
    docs = [l for l in lines if "word" in l]
    assert all(a["index"]["_index"] == "idx" for a in actions)
    assert sorted(d["word"] for d in docs) == ["a", "b"]
    assert all(d["diff"] == 1 for d in docs)


def test_slack_send_alerts_posts_messages(monkeypatch):
    calls = []

    class _Resp:
        def raise_for_status(self):
            pass

    def fake_post(url, headers=None, json=None, **kw):
        calls.append((url, headers, json))
        return _Resp()

    import requests

    monkeypatch.setattr(requests, "post", fake_post)
    t = pw.debug.table_from_markdown("""
    msg
    alert_one
    alert_two
    """)
    pw.io.slack.send_alerts(t.msg, "C123", "xoxb-token")
    pw.run()
    assert len(calls) == 2
    url, headers, payload = calls[0]
    assert url.endswith("chat.postMessage")
    assert headers["Authorization"] == "Bearer xoxb-token"
    assert {c[2]["text"] for c in calls} == {"alert_one", "alert_two"}
    assert all(c[2]["channel"] == "C123" for c in calls)


def test_redpanda_delegates_to_kafka():
    import pathway_tpu.io.kafka as k
    import pathway_tpu.io.redpanda as rp

    assert rp.read.__module__ == "pathway_tpu.io.redpanda"
    # same plumbing object underneath
    assert rp._kafka is k


def test_http_write_retries_and_logs(caplog, tmp_path):
    """http sink retries with backoff and logs final failures instead of
    silently dropping events (regression: bare except-pass)."""
    import http.server
    import logging
    import threading

    attempts = []

    class H(http.server.BaseHTTPRequestHandler):
        def do_POST(self):
            n = int(self.headers["Content-Length"])
            self.rfile.read(n)
            attempts.append(1)
            if len(attempts) < 2:  # first attempt fails, retry succeeds
                self.send_response(503)
            else:
                self.send_response(200)
            self.end_headers()

        def log_message(self, *a):
            pass

    srv = http.server.HTTPServer(("127.0.0.1", 0), H)
    port = srv.server_address[1]
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        t = pw.debug.table_from_markdown("msg\nhello")
        pw.io.logstash.write(t, f"http://127.0.0.1:{port}", n_retries=3,
                             retry_delay_s=0.05)
        pw.run()
        assert len(attempts) == 2  # 503 then success
        # unreachable endpoint → logged error, no exception
        G.clear()
        t2 = pw.debug.table_from_markdown("msg\nboom")
        pw.io.http.write(t2, "http://127.0.0.1:9/never", n_retries=1,
                         retry_delay_s=0.01)
        with caplog.at_level(logging.ERROR):
            pw.run()
        assert any("delivery failed after 2" in r.message
                   for r in caplog.records)
    finally:
        srv.shutdown()


def test_gradual_broadcast_insert_before_retract_update():
    """Regression: an update pair arriving insert-first must not drop the
    key from operator state."""
    from pathway_tpu.engine.delta import Delta
    from pathway_tpu.engine.operators import GradualBroadcastOperator
    from pathway_tpu.internals.keys import hash_values

    op = GradualBroadcastOperator()
    k = hash_values("row")
    tk = hash_values("thr")
    op.step(0, [Delta([(k, ("old",), 1)]),
                Delta([(tk, (0.0, 10.0, 10.0), 1)])])
    # update delivered insert-first (exchange merging can permute order)
    out = op.step(1, [Delta([(k, ("new",), 1), (k, ("old",), -1)]),
                      Delta()])
    state = {}
    for key, row, d in out.entries:
        state[row] = state.get(row, 0) + d
    live = {r for r, c in state.items() if c > 0}
    assert live == {("new", 10.0)}, out.entries
    assert k in op.rows and op.rows[k] == ("new",)
    # a later threshold move must still update this row
    out2 = op.step(2, [Delta(), Delta([(tk, (0.0, 10.0, 10.0), -1),
                                       (tk, (0.0, 0.0, 10.0), 1)])])
    assert any(d > 0 and row == ("new", 0.0)
               for _, row, d in out2.entries)


def test_deltalake_write_read_roundtrip(tmp_path):
    """Dependency-free Delta protocol subset: parquet parts + ordered
    _delta_log JSON (reference: DeltaTableReader/Writer via delta-rs)."""
    import json as js

    root = str(tmp_path / "dt")
    t = pw.debug.table_from_markdown("""
    name  | qty | _time | _diff
    alice | 3   | 2     | 1
    bob   | 5   | 2     | 1
    alice | 3   | 4     | -1
    carol | 7   | 4     | 1
    """)
    pw.io.deltalake.write(t, root)
    pw.run()

    # the log is real Delta protocol: version 0 carries protocol+metaData
    log0 = (tmp_path / "dt" / "_delta_log" /
            f"{0:020d}.json").read_text().splitlines()
    actions = [js.loads(l) for l in log0]
    assert any("protocol" in a for a in actions)
    assert any("metaData" in a for a in actions)
    assert any("add" in a for a in actions)

    class S(pw.Schema):
        name: str
        qty: int

    G.clear()
    back = pw.io.deltalake.read(root, schema=S, mode="static")
    got = sorted(rows_of(back))
    # the retraction of alice applied during replay
    assert got == [("bob", 5), ("carol", 7)]


def test_deltalake_streaming_tails_new_versions(tmp_path):
    import threading
    import time

    root = str(tmp_path / "dt")
    # seed version 0 through the writer
    t = pw.debug.table_from_markdown("name\nseed")
    pw.io.deltalake.write(t, root)
    pw.run()
    G.clear()

    class S(pw.Schema):
        name: str

    seen = []
    live = pw.io.deltalake.read(root, schema=S, mode="streaming")
    pw.io.subscribe(live, on_change=lambda key, row, time, is_addition:
                    seen.append(row["name"]))

    def feed():
        time.sleep(1.2)
        G2 = []
        # write a NEW version with a fresh pipeline (append-only tail)
        import pathway_tpu as pw2
        from pathway_tpu.internals.parse_graph import G as PG

        # separate graph context: build + run a second writer run
        snapshot = list(PG.output_binders)
        t2 = pw2.debug.table_from_markdown("name\nlive_row")
        pw2.io.deltalake.write(t2, root)
        new_binders = [b for b in PG.output_binders
                       if b not in snapshot]
        from pathway_tpu.internals.runner import GraphRunner

        r = GraphRunner()
        for b in new_binders:
            b(r)
        r.run_batch()

    threading.Thread(target=feed, daemon=True).start()
    threading.Thread(target=lambda: pw.run(), daemon=True).start()
    deadline = time.time() + 10
    while time.time() < deadline and set(seen) != {"seed", "live_row"}:
        time.sleep(0.1)
    assert set(seen) == {"seed", "live_row"}


def test_deltalake_remove_actions_and_duplicates(tmp_path):
    """delta-rs interop semantics: 'remove' actions retract a part's rows;
    duplicate keyless rows stay distinct occurrences."""
    import json as js

    import pyarrow as pa
    import pyarrow.parquet as pq

    root = tmp_path / "dt"
    (root / "_delta_log").mkdir(parents=True)

    def commit(version, actions):
        p = root / "_delta_log" / f"{version:020d}.json"
        p.write_text("\n".join(js.dumps(a) for a in actions) + "\n")

    def part(name, rows):
        pq.write_table(pa.Table.from_pylist(rows), str(root / name))

    # v0: two identical keyless rows + one other
    part("p0.parquet", [{"name": "dup", "qty": 1, "time": 0, "diff": 1},
                        {"name": "dup", "qty": 1, "time": 0, "diff": 1},
                        {"name": "solo", "qty": 2, "time": 0, "diff": 1}])
    commit(0, [{"protocol": {"minReaderVersion": 1, "minWriterVersion": 2}},
               {"add": {"path": "p0.parquet", "size": 1,
                        "partitionValues": {}, "dataChange": True}}])
    # v1: a compaction-style rewrite — remove p0, re-add survivors only
    part("p1.parquet", [{"name": "dup", "qty": 1, "time": 1, "diff": 1}])
    commit(1, [{"remove": {"path": "p0.parquet", "dataChange": True}},
               {"add": {"path": "p1.parquet", "size": 1,
                        "partitionValues": {}, "dataChange": True}}])

    class S(pw.Schema):
        name: str
        qty: int

    t = pw.io.deltalake.read(str(root), schema=S, mode="static")
    got = sorted(rows_of(t))
    # after the rewrite exactly ONE dup row survives, solo is gone
    assert got == [("dup", 1)]

    # duplicates before any remove: both occurrences visible
    G.clear()
    (root / "_delta_log" / f"{1:020d}.json").unlink()
    t2 = pw.io.deltalake.read(str(root), schema=S, mode="static")
    got2 = sorted(rows_of(t2))
    assert got2 == [("dup", 1), ("dup", 1), ("solo", 2)]


def test_streaming_join_against_static_dimension(tmp_path):
    """Regression: streaming mode must feed static tables at startup — a
    live stream joined with a static dimension table produced zero rows
    (the batch path fed them, the streaming loop never did)."""
    import threading
    import time

    d = tmp_path / "orders"
    d.mkdir()
    (d / "a.jsonl").write_text('{"item": "widget", "qty": 2}\n')

    class Order(pw.Schema):
        item: str
        qty: int

    class Cat(pw.Schema):
        item: str
        cat: str

    orders = pw.io.fs.read(str(d), format="json", schema=Order,
                           mode="streaming")
    cats = pw.debug.table_from_rows(Cat, [("widget", "tools"),
                                          ("gizmo", "toys")])
    joined = orders.join(cats, orders.item == cats.item).select(
        orders.item, orders.qty, cats.cat)
    seen = []
    pw.io.subscribe(joined, on_change=lambda key, row, time, is_addition:
                    seen.append((row["item"], row["cat"], is_addition)))

    def feed():
        time.sleep(1.5)
        (d / "b.jsonl").write_text('{"item": "gizmo", "qty": 1}\n')

    threading.Thread(target=feed, daemon=True).start()
    threading.Thread(target=lambda: pw.run(), daemon=True).start()
    deadline = time.time() + 10
    while time.time() < deadline and len(seen) < 2:
        time.sleep(0.1)
    assert ("widget", "tools", True) in seen
    assert ("gizmo", "toys", True) in seen


def test_reference_convenience_wrappers():
    """Thin reference-surface wrappers: kafka simple_read/upstash settings,
    s3 DigitalOcean/Wasabi endpoints, postgres write_snapshot alias,
    gdrive metadata enrichment."""
    import pathway_tpu as pw

    # kafka: settings construction (no broker needed — inspect the source)
    t = pw.io.kafka.simple_read("srv:9092", "top", read_only_new=True)
    src = t._plan.params["datasource"]
    assert src.settings["bootstrap.servers"] == "srv:9092"
    assert src.settings["auto.offset.reset"] == "latest"
    t2 = pw.io.kafka.read_from_upstash("up:9092", "user", "pw", "top")
    s2 = t2._plan.params["datasource"].settings
    assert s2["security.protocol"] == "sasl_ssl"
    assert s2["sasl.mechanism"] == "SCRAM-SHA-256"

    @pw.io.kafka.check_raw_and_plaintext_only_kwargs
    def fake_write(table, **kwargs):
        return "ok"

    import pytest as _pytest

    with _pytest.raises(ValueError, match="key"):
        fake_write(None, format="json", key="k")
    assert fake_write(None, format="raw", key="k") == "ok"

    # s3 settings map to the provider endpoints
    do = pw.io.s3.DigitalOceanS3Settings(
        bucket_name="b", access_key="a", secret_access_key="s",
        region="ams3")
    assert do._as_aws().endpoint == "https://ams3.digitaloceanspaces.com"
    wa = pw.io.s3.WasabiS3Settings(
        bucket_name="b", access_key="a", secret_access_key="s",
        region="us-west-1")
    assert wa._as_aws().endpoint == "https://s3.us-west-1.wasabisys.com"

    # gdrive metadata enrichment
    meta = pw.io.gdrive.extend_metadata({"id": "f1", "name": "doc.txt"})
    assert meta["url"].endswith("/f1/")
    assert meta["path"] == "doc.txt"
    assert meta["status"] == pw.io.gdrive.STATUS_DOWNLOADED
    assert isinstance(meta["seen_at"], int)

    # postgres write_snapshot delegates to write(output_table_type=snapshot)
    try:
        import psycopg2  # noqa: F401
    except ImportError:
        with _pytest.raises(ImportError, match="psycopg2"):
            pw.io.postgres.write_snapshot(
                pw.debug.table_from_markdown("a\n1"), {}, "t", ["a"])


# ---------------------------------------------------------------------------
# the fs reader's progress through a backlog, as the flight recorder's spans
# (io/fs ``FsSource.run``; engine/flight_recorder.py)
# ---------------------------------------------------------------------------

def _recorded_pass(directory, n_files: int):
    """One pass of the fs source over ``n_files`` new one-passage files
    with a recorder on its session: (the ``connector.pass`` span, the
    ``connector.progress`` spans, the rows it pushed)."""
    import pathway_tpu.io.fs as fs
    from pathway_tpu.engine.flight_recorder import FlightRecorder
    from pathway_tpu.io._datasource import Session

    for i in range(n_files):
        (directory / f"doc{i:04d}.txt").write_text(f"passage number {i}")
    source = fs.FsSource(
        str(directory), "plaintext_by_file",
        fs._schema_for("plaintext_by_file", None, True), "static", True)
    session = Session()
    session.recorder = FlightRecorder()
    session.recorder.enabled = True
    source.run(session)
    spans = session.recorder.spans()
    (whole,) = [sp for sp in spans if sp[0] == "connector.pass"]
    assert whole[3] == ("pass", source._uid, 0)
    return (whole, [sp for sp in spans if sp[0] == "connector.progress"],
            session.drain())


def test_a_backlog_s_pass_shows_its_progress_every_256_files(tmp_path):
    whole, progress, pushed = _recorded_pass(tmp_path, 600)
    assert len(pushed) == 600
    assert [sp[5]["files"] for sp in progress] == [256, 256, 88]
    assert sum(sp[5]["files"] for sp in progress) == whole[5]["changed"]
    assert sum(sp[5]["rows"] for sp in progress) == whole[5]["rows"] == 600
    assert all(sp[3] == whole[3] for sp in progress)
    # one stretch after the other from the end of the listing to the end of
    # the pass, on the reader's thread
    listed = whole[1] + whole[5]["list_ms"] / 1e3
    assert progress[0][1] == pytest.approx(listed, abs=1e-9)
    for before, after in zip(progress, progress[1:]):
        assert before[2] == after[1]
    assert progress[-1][2] == whole[2]
    assert {sp[4] for sp in progress} == {whole[4]}
    # what a stretch's files cost, by where: never more than its wall time
    for sp in progress:
        counts, wall_ms = sp[5], (sp[2] - sp[1]) * 1e3
        assert set(counts) == {"files", "rows", "cpu_ms", "stat_ms",
                               "parse_ms", "push_ms"}
        assert min(counts.values()) >= 0
        assert counts["stat_ms"] + counts["parse_ms"] + counts["push_ms"] \
            <= wall_ms + 1e-6
    for count in ("stat_ms", "parse_ms", "push_ms"):
        assert sum(sp[5][count] for sp in progress) == pytest.approx(
            whole[5][count], rel=1e-9, abs=1e-9)
    assert "files" not in whole[5]   # a backlog: counts, not instants


def test_a_trickle_s_pass_writes_no_progress_and_carries_the_counts(
        tmp_path):
    whole, progress, pushed = _recorded_pass(tmp_path, 3)
    assert len(pushed) == 3 and progress == []
    counts = whole[5]
    assert counts["changed"] == 3 and len(counts["files"]) == 3
    assert {"cpu_ms", "stat_ms", "parse_ms", "push_ms", "list_ms"} \
        <= set(counts)
    wall_ms = (whole[2] - whole[1]) * 1e3
    assert counts["list_ms"] + counts["stat_ms"] + counts["parse_ms"] \
        + counts["push_ms"] == pytest.approx(wall_ms, abs=1e-6)
    assert 0 <= counts["cpu_ms"]
    # each live file's push instant lies inside the pass
    assert all(whole[1] <= push <= whole[2] for _m, push in counts["files"])


def test_a_pass_just_over_the_trickle_s_size_is_one_stretch(tmp_path):
    import pathway_tpu.io.fs as fs

    n = fs._PASS_FILES_MAX + 1
    assert n < fs._PROGRESS_FILES
    whole, progress, _pushed = _recorded_pass(tmp_path, n)
    assert [sp[5]["files"] for sp in progress] == [n]
    assert "files" not in whole[5]
