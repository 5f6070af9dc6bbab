"""Trace canary: the flight recorder's two load-bearing promises, proven
end to end (same pattern as pipelining_canary.py / watchdog_canary.py).

1. **Trace gate** — drive ``examples/streaming_etl.py``'s real graph with
   ``PATHWAY_TRACE_PATH`` and assert the written file is valid Chrome
   trace JSON: metadata-named host/device tracks, > 0 device-leg operator
   spans, every B properly closed by a matching E (a mis-nested file
   renders as garbage in Perfetto), user-frame attribution present.

2. **Overhead guard** — with tracing disabled, the recorder hook must add
   < 2% per-tick wall time versus no recorder at all (the disabled path
   is one branch per operator step), and a polling pass of the fs source
   must not read the clock at all. Measured on the same join + sliding
   window + groupby shape the streaming example runs, over many ticks,
   min-of-K to de-noise; the device UDF is left out and the bridge pinned
   synchronous so the comparison measures the scheduler hook, not XLA
   compile or thread-scheduling variance. The same holds for the stages
   the index and the embedder record of a search and an ingest call: with
   no recorder on, neither reads the clock nor writes a span.

Exits 0 iff both hold. Run: ``python tests/trace_canary.py``.
"""

from __future__ import annotations

import json
import os
import pathlib
import sys
import tempfile
import time


def _check_nesting(events) -> str | None:
    stacks: dict = {}
    for ev in events:
        if ev["ph"] == "B":
            stacks.setdefault(ev["tid"], []).append(ev["name"])
        elif ev["ph"] == "E":
            stack = stacks.setdefault(ev["tid"], [])
            if not stack:
                return f"E without B on tid {ev['tid']}: {ev['name']}"
            top = stack.pop()
            if top != ev["name"]:
                return f"mis-nested: E {ev['name']!r} closes B {top!r}"
    for tid, stack in stacks.items():
        if stack:
            return f"unclosed spans on tid {tid}: {stack}"
    return None


def check_trace_file() -> str | None:
    """Run the streaming example's graph with a trace path; return an
    error string or None."""
    from tests.pipelining_canary import _write_feed

    os.environ["PATHWAY_DEVICE_INFLIGHT"] = "2"
    import pathway_tpu as pw
    from examples.streaming_etl import build
    from pathway_tpu.engine import streaming as _streaming
    from pathway_tpu.internals.parse_graph import G

    G.clear()
    with tempfile.TemporaryDirectory() as td:
        root = pathlib.Path(td)
        orders_dir, cats_csv = _write_feed(root)
        out_csv = str(root / "out.csv")
        trace_path = str(root / "trace.json")
        build(orders_dir, cats_csv, out_csv)
        import threading

        def _run():
            pw.run(trace_path=trace_path)

        th = threading.Thread(target=_run, daemon=True)
        th.start()
        deadline = time.monotonic() + 30.0
        rt = None
        while time.monotonic() < deadline and rt is None:
            live = list(_streaming._ACTIVE_RUNTIMES)
            rt = live[0] if live else None
            time.sleep(0.05)
        if rt is None:
            return "runtime never started"
        # wait until device legs visibly resolved and the sink settled
        last_size = -1
        while time.monotonic() < deadline:
            stats = rt.scheduler.bridge_stats()
            size = os.path.getsize(out_csv) if os.path.exists(out_csv) else 0
            if stats and stats["legs_resolved"] > 0 and size > 0 \
                    and size == last_size:
                break
            last_size = size
            time.sleep(0.25)
        _streaming.stop_all()
        th.join(15.0)
        G.clear()
        if not os.path.exists(trace_path):
            return f"no trace written at {trace_path}"
        artifact = os.environ.get("PATHWAY_TRACE_ARTIFACT")
        if artifact:  # CI keeps the Perfetto-loadable file for inspection
            import shutil

            shutil.copyfile(trace_path, artifact)
        try:
            data = json.loads(pathlib.Path(trace_path).read_text())
        except json.JSONDecodeError as e:
            return f"trace is not valid JSON: {e}"
        events = data.get("traceEvents")
        if not isinstance(events, list) or not events:
            return "trace has no traceEvents"
        tracks = {e["args"]["name"] for e in events if e["ph"] == "M"}
        if not {"host leg", "device leg"} <= tracks:
            return f"missing track metadata: {tracks}"
        err = _check_nesting(events)
        if err:
            return err
        dev_ops = [e for e in events if e["ph"] == "B"
                   and e.get("cat") == "device"
                   and not e["name"].startswith("tick ")]
        if not dev_ops:
            return "no device-leg operator spans in the trace"
        framed = [e for e in events if e["ph"] == "B"
                  and "user_frame" in e.get("args", {})]
        if not any("streaming_etl.py" in e["args"]["user_frame"]
                   for e in framed):
            return "no span carries the example's user-frame attribution"
        print(f"trace gate OK: {len(events)} events, "
              f"{len(dev_ops)} device-leg spans, nesting valid")
        return None


def _etl_like_graph(n_rows: int, n_ticks: int):
    """The streaming example's shape as a batch feed: join against a
    dimension table + sliding-window aggregate, spread over many ticks."""
    import numpy as np

    import pathway_tpu as pw
    from pathway_tpu.debug import table_from_rows
    from pathway_tpu.internals.parse_graph import G
    from pathway_tpu.internals.runner import GraphRunner

    G.clear()

    class Order(pw.Schema):
        item: str
        qty: int
        price: float
        ts: int

    class Category(pw.Schema):
        item: str
        category: str

    rng = np.random.default_rng(0)
    items = rng.integers(0, 16, size=n_rows)
    orders = table_from_rows(
        Order, [(f"i{items[i]}", 1 + int(i) % 3, 2.5, 60 * i,
                 (i * n_ticks) // n_rows * 2, 1) for i in range(n_rows)],
        is_stream=True)
    cats = table_from_rows(
        Category, [(f"i{i}", f"cat{i % 3}") for i in range(16)])
    enriched = orders.join(cats, orders.item == cats.item).select(
        orders.qty, orders.ts, cats.category,
        revenue=orders.qty * orders.price)
    by_cat = enriched.windowby(
        enriched.ts, window=pw.temporal.sliding(hop=60, duration=300),
        instance=enriched.category).reduce(
        category=pw.this._pw_instance,
        revenue=pw.reducers.sum(pw.this.revenue),
        n=pw.reducers.count())
    runner = GraphRunner()
    runner.capture(by_cat)
    return runner


class _CountingClock:
    """``time`` with its ``perf_counter`` and ``thread_time`` counted."""

    def __init__(self):
        self.reads = 0

    def __getattr__(self, name):
        return getattr(time, name)

    def perf_counter(self):
        self.reads += 1
        return time.perf_counter()

    def thread_time(self):
        self.reads += 1
        return time.thread_time()


def fs_pass_clock_reads(directory, recording: bool) -> int:
    """``perf_counter`` and ``thread_time`` reads one polling pass of the
    fs source makes over ``directory`` (one file is added to it), with or
    without a recorder on its session: the ``connector.pass`` span may cost
    clock reads only while something records."""
    import pathway_tpu.io.fs as fs
    from pathway_tpu.engine.flight_recorder import FlightRecorder
    from pathway_tpu.io._datasource import Session

    (pathlib.Path(directory) / "canary.txt").write_text("one passage")
    source = fs.FsSource(
        str(directory), "plaintext_by_file",
        fs._schema_for("plaintext_by_file", None, False), "static", False)
    session = Session()
    if recording:
        session.recorder = FlightRecorder()
        session.recorder.enabled = True
    real, fs._time = fs._time, _CountingClock()
    try:
        source.run(session)
        reads = fs._time.reads
    finally:
        fs._time = real
    if not session.drain():
        raise AssertionError("the pass pushed nothing")
    if recording and not session.recorder.spans():
        raise AssertionError("the recorded pass wrote no span")
    return reads


def index_clock_reads(recording: bool) -> tuple[int, int]:
    """(clock reads, spans written) of one ingest call and one search of a
    text index (packer, fused dispatch, query embedding, scan) and one
    search of a vector index, with or without a live recorder: the stages
    they record may cost a clock read or a tuple only while one is on."""
    import pathway_tpu.ops.knn as knn
    import pathway_tpu.xpacks.llm.embedders as embedders
    from pathway_tpu.engine.flight_recorder import FlightRecorder
    from pathway_tpu.internals.keys import Pointer
    from pathway_tpu.models.encoder import EncoderConfig

    emb = embedders.JaxEncoderEmbedder(config=EncoderConfig.tiny(),
                                       ragged=True)
    index = knn.DeviceEmbeddingKnnIndex(
        emb, knn.BruteForceKnnIndex(64, reserved_space=64))
    # on as ``pw.run`` turns one on: live, so ``live_span`` finds it
    rec = FlightRecorder.from_env(auto_on=True) if recording \
        else FlightRecorder()
    clock = _CountingClock()
    real = knn._time, embedders._perf_counter
    knn._time, embedders._perf_counter = clock, clock.perf_counter
    try:
        index.add_batch([Pointer(1), Pointer(2)],
                        ["one passage", "another passage"])
        (hits,) = index.search([(Pointer(9), "one passage", 1, None)])
        vector = [1.0] + [0.0] * 63
        index.inner.search([(Pointer(9), vector, 1, None)])
    finally:
        knn._time, embedders._perf_counter = real
        rec.enabled = False
    if hits[0][0] != Pointer(1):
        raise AssertionError(f"the search answered {hits}")
    return clock.reads, len(rec.spans())


def check_overhead(attempts: int = 3) -> str | None:
    """tracing disabled must add < 2% per-tick wall time, and no clock
    read to a connector pass.

    A wall-clock ratio on a shared CI runner can blip past the budget on
    correlated noise (frequency scaling, a noisy neighbor spanning all
    trials of one mode); a genuine regression fails every attempt, so the
    gate passes on the first attempt under budget and only reports the
    failure after ``attempts`` independent measurements all exceed it."""
    with tempfile.TemporaryDirectory() as td:
        reads = fs_pass_clock_reads(td, recording=False)
    if reads:
        return (f"the fs source's pass read the clock {reads} times "
                f"with no recorder on its session")
    reads, spans = index_clock_reads(recording=False)
    if reads or spans:
        return (f"an ingest call and two searches read the clock {reads} "
                f"times and wrote {spans} spans with no recorder on")
    last = None
    for i in range(attempts):
        last = _measure_overhead()
        if last is None:
            return None
        print(f"overhead attempt {i + 1}/{attempts} over budget: {last}")
    return last


def _measure_overhead() -> str | None:
    from pathway_tpu.engine.flight_recorder import FlightRecorder
    from pathway_tpu.internals.parse_graph import G

    os.environ["PATHWAY_DEVICE_INFLIGHT"] = "1"  # no bridge-thread noise
    os.environ.pop("PATHWAY_TRACE_PATH", None)
    os.environ.pop("PATHWAY_FLIGHT_RECORDER", None)
    n_rows, n_ticks, trials = 4000, 120, 5

    def run_once(with_disabled_recorder: bool) -> float:
        runner = _etl_like_graph(n_rows, n_ticks)
        recorder = None
        if with_disabled_recorder:
            recorder = FlightRecorder()
            assert not recorder.enabled
        t0 = time.perf_counter()
        runner.run_batch(n_workers=1, recorder=recorder)
        dt = time.perf_counter() - t0
        G.clear()
        return dt

    run_once(False)  # warm caches/imports off the record
    run_once(True)
    # interleaved trials: thermal / allocator drift over the run must hit
    # both modes equally, or the guard measures the machine, not the hook
    base_ts, dis_ts = [], []
    for _ in range(trials):
        base_ts.append(run_once(False))
        dis_ts.append(run_once(True))
    base, disabled = min(base_ts), min(dis_ts)
    ratio = disabled / base
    print(f"overhead guard: baseline {base * 1e3:.1f}ms, "
          f"disabled-recorder {disabled * 1e3:.1f}ms over {n_ticks} ticks "
          f"(ratio {ratio:.4f})")
    if ratio > 1.02:
        return (f"tracing-disabled per-tick overhead {ratio:.4f}x "
                f"exceeds the 2% budget")
    return None


def main() -> int:
    for name, check in (("trace", check_trace_file),
                        ("overhead", check_overhead)):
        err = check()
        if err:
            print(f"FAIL [{name}]: {err}", file=sys.stderr)
            return 1
    print("OK: trace gate + overhead guard both hold")
    return 0


if __name__ == "__main__":
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
    sys.exit(main())
