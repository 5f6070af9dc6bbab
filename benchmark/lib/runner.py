"""One run of one cell: build, warm, measure for ``seconds``, check, report.

The phases, in order (all but the last two are set-up, and ``setup_s`` runs
from process start to the first instant of the window):

    start      interpreter, imports, JAX finding its devices
    files      the mix's documents, from the seed, as files
    embedder   weights on the device, tokenizer
    server     the dataflow, started; HTTP answering
    fill       the resident index brought to its configured size
    corpus     the mix's ``corpus`` documents indexed
    warmup     every shape the mix uses, compiled or loaded
    backlog    (mixes with one) released; ticks until nothing compiles
    settle     (open-loop mixes) the traffic itself, unmeasured
    window     the measurement
    checks     the plain reference and the guarantees

The function takes the platform it must find as an argument, as
``chip_smoke.run_smoke`` does; the command line always passes ``"tpu"``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import json
import os
import shutil
import tempfile
import threading
import time

import numpy as np

from benchmark.lib import check, costs, stats, traffic
from benchmark.lib import trace as tracelib
from benchmark.lib.hostsampler import HostSampler
from benchmark.lib.loadgen import OpenLoop, Sent
from benchmark.lib.record import JitLog, Run
from benchmark.lib.spec import Cell, SpecError
from benchmark.lib.stalls import StallWatch
from benchmark.lib.traffic import Event
from benchmark.lib.vector_store import System


class Refused(RuntimeError):
    """The run cannot be made here: wrong platform, too few chips."""


class Phases:
    """Seconds from process start at which each phase ended."""

    def __init__(self, t_start: float, log):
        self.t_start = t_start
        self.log = log
        self.ends: list[tuple[str, float]] = []

    def mark(self, name: str) -> None:
        now = time.perf_counter() - self.t_start
        prev = self.ends[-1][1] if self.ends else 0.0
        self.ends.append((name, now))
        self.log(f"phase {name}: {now - prev:.2f}s (at {now:.2f}s)")

    def durations(self) -> dict[str, float]:
        out, prev = {}, 0.0
        for name, end in self.ends:
            out[name] = end - prev
            prev = end
        return out


def _wait_for(pred, timeout_s: float, what: str, poll_s: float = 0.02):
    deadline = time.monotonic() + timeout_s
    while True:
        got = pred()
        if got:
            return got
        if time.monotonic() > deadline:
            raise TimeoutError(f"timed out after {timeout_s:.0f}s waiting "
                               f"for {what}")
        time.sleep(poll_s)


def _sleep_until(t: float) -> None:
    delay = t - time.perf_counter()
    if delay > 0:
        time.sleep(delay)


def _write_files(directory: str, names: list[str], texts: list[str]) -> None:
    for name, text in zip(names, texts):
        with open(os.path.join(directory, name), "w") as f:
            f.write(text)


#: threads that stage a backlog's files: creating a file is a handful of
#: system calls, slow ones on the chip's machine (0.31 ms a file on one
#: thread, 0.21 on four, no better on more: my chip run, PR 22)
STAGE_THREADS = 4


def _stage_backlog(workdir: str, texts: list[str], per_dir: int
                   ) -> tuple[list[str], list[str]]:
    """Write the backlog outside the watched path, ``per_dir`` files to a
    directory; returns (directories, file names in the order the connector
    will read them: sorted by path)."""
    from concurrent.futures import ThreadPoolExecutor

    root = os.path.join(workdir, "backlog")
    dirs, names, jobs = [], [], []
    with ThreadPoolExecutor(STAGE_THREADS) as pool:
        for d, base in enumerate(range(0, len(texts), per_dir)):
            path = os.path.join(root, f"backlog-{d:05d}")
            os.makedirs(path)
            part = [f"b{base + i:08d}.txt"
                    for i in range(min(per_dir, len(texts) - base))]
            jobs.append(pool.submit(_write_files, path, part,
                                    texts[base:base + len(part)]))
            dirs.append(path)
            names.extend(part)
        for job in jobs:
            job.result()
    return dirs, names


def _tick_edge(system, total: int, timeout_s: float = 60.0) -> None:
    """Return just after the index took another batch of rows. ``total`` is
    what it holds once the backlog is gone: a backlog that does not outlast
    the window cannot be measured."""
    rows = system.counters()["rows"]
    if rows >= total:
        raise RuntimeError(f"the backlog of {total} documents ran out before "
                           f"the window ended: the mix needs more")
    _wait_for(lambda: system.counters()["rows"] != rows, timeout_s,
              "the next ingest tick", poll_s=0.002)


def _edge(system, with_http: bool) -> dict:
    """The counters at a window edge; with ``with_http`` also what
    ``/v1/statistics`` says, stamped when its answer arrived."""
    out = {}
    if with_http:
        out["file_count"] = system.file_count()
    out.update(system.counters())
    return out


def _ask(system, text: str, k: int, doc: str | None) -> Sent:
    """One query over HTTP outside the window, in the shape of the load
    generator's records."""
    rec = Sent(Event(0.0, "query", text, k=k, doc=doc), time.perf_counter())
    rec.sent = rec.due
    try:
        hits = system.client.query(text, k=k)
        rec.hits = tuple(os.path.basename(h["metadata"]["path"])
                         for h in hits)
    except (OSError, ValueError, KeyError, TypeError) as e:
        rec.error = f"{type(e).__name__}: {e}"
    rec.done = time.perf_counter()
    return rec


def _spread_by_length(texts: list[str], n: int) -> list[int]:
    """``n`` indices into ``texts`` spread evenly over the length range."""
    order = sorted(range(len(texts)), key=lambda i: len(texts[i]))
    if len(order) <= n:
        return order
    return [order[round(j * (len(order) - 1) / (n - 1))] for j in range(n)]


class _Tracing:
    """The traced part of a window: the profiler, the host sampler and the
    marks that put both on one clock."""

    def __init__(self, directory: str):
        self.directory = directory
        self.sampler = HostSampler()
        self.t0 = self.t1 = 0.0

    def start(self) -> None:
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0   # a busy server's every call
        options.enable_hlo_proto = False  # the programs' text, megabytes
        jax.profiler.start_trace(self.directory, profiler_options=options)
        self.sampler.start()
        with jax.profiler.TraceAnnotation(
                tracelib.CLOCK_MARK,
                perf_counter_ns=time.perf_counter_ns()):
            pass
        with jax.profiler.TraceAnnotation(tracelib.BEGIN_MARK):
            pass
        self.t0 = time.perf_counter()

    def stop(self) -> None:
        import jax

        self.t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation(tracelib.END_MARK):
            pass
        self.sampler.stop()
        jax.profiler.stop_trace()

    def reduce(self, keep: str | None):
        import glob

        (path,) = glob.glob(os.path.join(
            self.directory, "plugins", "profile", "*", "*.xplane.pb"))
        if keep is not None:
            shutil.copy(path, keep)
        return tracelib.reduce_xplane(path), os.path.getsize(path)


@dataclasses.dataclass
class Session:
    """What a run of a cell works with, from the platform check to
    tear-down."""

    system: object
    jit: JitLog
    phases: Phases
    workdir: str
    devices: list
    device: dict                    # platform, kind, count as JAX reports
    cache_dir: str
    cache_entries_before: int

    def cache_entries(self) -> int:
        return len(os.listdir(self.cache_dir)) \
            if os.path.isdir(self.cache_dir) else 0


@contextlib.contextmanager
def session(cell: Cell, *, seed: int, expected_platform: str, t_start: float,
            flight_trace: str | None = None, log=print):
    """Check the platform, then make the cell's system (not yet started) with
    a working directory, the compile cache and the JIT log around it; stop
    and remove everything on the way out. Raises :class:`Refused` before
    building anything where JAX does not run on ``expected_platform`` with at
    least the cell's chips."""
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    log(f"platform={device['platform']} device_kind={device['kind']!r} "
        f"device_count={device['count']}")
    if device["platform"] != expected_platform:
        raise Refused(f"JAX runs on {device['platform']!r}, this run needs "
                      f"{expected_platform!r}")
    if device["count"] < cell.chips:
        raise Refused(f"the cell asks for {cell.chips} chips, JAX finds "
                      f"{device['count']}")

    import pathway_tpu as pw

    cache_dir = pw.enable_compilation_cache()
    jit = JitLog()
    jit.install()
    workdir = tempfile.mkdtemp(prefix="bench_")
    system = System(cell, seed, workdir, log=log,
                    flight_trace=flight_trace)
    s = Session(system, jit, Phases(t_start, log), workdir, devices, device,
                cache_dir, 0)
    s.cache_entries_before = s.cache_entries()
    log(f"compile_cache_dir={cache_dir} "
        f"entries_at_start={s.cache_entries_before}")
    try:
        yield s
    finally:
        system.stop()
        jit.uninstall()
        shutil.rmtree(workdir, ignore_errors=True)


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             expected_platform: str, t_start: float, out_dir: str,
             peaks: dict | None = None, keep_trace: bool = False,
             log=print) -> dict:
    """Run ``cell`` once and return the result line as a dict. ``peaks``
    stands in for the table's row only in a rehearsal off the chip;
    ``keep_trace`` leaves the profile of a traced run in ``out_dir``."""
    stem = os.path.join(out_dir, f"{cell.name}.seed{seed}.trace{int(trace)}")
    with session(cell, seed=seed, expected_platform=expected_platform,
                 t_start=t_start, log=log,
                 flight_trace=stem + ".flight.json" if trace else None) as s:
        if peaks is None:
            peaks = costs.peaks_for(s.device["kind"])
        os.makedirs(out_dir, exist_ok=True)
        line, detail = _measure(
            cell, s.system, seed=seed, seconds=seconds, trace=trace,
            phases=s.phases, jit=s.jit, workdir=s.workdir, devices=s.devices,
            device=s.device, peaks=peaks, log=log,
            keep=stem + ".xplane.pb" if keep_trace else None)
    jit = s.jit
    detail["compile_cache"] = {"dir": s.cache_dir, "entries": [
        s.cache_entries_before, s.cache_entries()], "hits": jit.cache_hits,
        "misses": jit.cache_misses}
    slow = [(name, round(d, 2)) for _t, stage, name, d in jit.events
            if stage == "backend_compile" and d >= 0.5]
    if slow:
        log(f"slowest compiles or loads from the cache (0.5 s or more): "
            f"{slow}")
    log(f"compile cache entries {s.cache_entries_before} -> "
        f"{s.cache_entries()} (hits {jit.cache_hits}, misses "
        f"{jit.cache_misses}; {len(jit.programs())} backend compiles or "
        f"loads in all)")
    _report_overhead(stem, trace, detail, log)
    with open(stem + ".json", "w") as f:
        json.dump({"result": line, **detail}, f, indent=1, default=str)
    return line


def _report_overhead(stem: str, trace: bool, detail: dict, log) -> None:
    """In a traced run, the end-to-end numbers beside those of the last
    untraced run of the same cell and seed: what the tracing costs."""
    if not trace:
        return
    try:
        with open(stem.replace(".trace1", ".trace0") + ".json") as f:
            plain = json.load(f)["end_to_end"]
    except (OSError, ValueError, KeyError):
        log("tracing overhead: no untraced run of this cell and seed here")
        return
    for name, value in detail["end_to_end"].items():
        if name in plain and plain[name]:
            log(f"tracing overhead {name}: traced {value:.4f} vs untraced "
                f"{plain[name]:.4f} ({(value / plain[name] - 1) * 100:+.1f}%)")


@dataclasses.dataclass
class Ready:
    """A system built, filled and warm, with the mix's documents."""

    corpus: list[str]
    docs: dict[str, str]            # file name -> text, every real document
    backlog_texts: list[str]
    backlog_names: list[str]        # in the order the connector reads them
    total_docs: int
    k: int | None
    spans: dict


def prepare(cell: Cell, system, *, seed: int, trace: bool, phases: Phases,
            jit: JitLog, workdir: str, log) -> Ready:
    """Set-up up to the point where traffic can start: files, embedder,
    server, fill, corpus, warm-up, and a released backlog that has stopped
    compiling."""
    mix, config = cell.traffic, cell.config
    if mix["vocab_words"] > config["serving"]["vocab_words"]:
        raise SpecError("the mix draws words the configuration's vocabulary "
                        "does not hold whole")
    phases.mark("start")
    queries, backlog = mix.get("queries"), mix.get("backlog")
    k = (queries or mix.get("after", {})).get("k")

    corpus = traffic.corpus_texts(mix, "corpus", seed) \
        if "corpus" in mix else []
    corpus_names = [f"c{i:07d}.txt" for i in range(len(corpus))]
    _write_files(system.corpus_dir, corpus_names, corpus)
    backlog_dirs, backlog_names, backlog_texts = [], [], []
    if backlog is not None:
        backlog_texts = traffic.corpus_texts(mix, "backlog", seed)
        backlog_dirs, backlog_names = _stage_backlog(
            workdir, backlog_texts, backlog["files_per_dir"])
    total_docs = len(corpus) + len(backlog_texts)
    phases.mark("files")

    system.make_embedder()
    phases.mark("embedder")
    system.start()
    spans: dict = {}
    if trace:
        system.instrument(spans)
    phases.mark("server")
    system.fill()
    phases.mark("fill")
    if corpus:
        _wait_for(lambda: system.file_count() >= len(corpus), 300,
                  f"{len(corpus)} corpus documents to be indexed",
                  poll_s=0.1)
    phases.mark("corpus")
    batch_max = mix["warm"].get("query_batch_max", 0)
    system.warm(
        k=k, query_batch_max=batch_max,
        query_texts=[traffic.cut_span(np.random.default_rng([seed, i]),
                                      corpus[i % len(corpus)], 8)
                     for i in range(batch_max)] if corpus else [])
    phases.mark("warmup")

    if backlog is not None:
        for d in backlog_dirs:
            os.rename(d, os.path.join(system.watched, os.path.basename(d)))
        _wait_for(lambda: system.counters()["rows"] > len(corpus), 120,
                  "the first backlog documents", poll_s=0.01)
        if k:
            system.warm_queries(backlog_texts[:1], k, 1)
        ticks, quiet_from = 0, 0
        while ticks < mix["warm"]["ticks"] \
                or ticks - quiet_from < mix["warm"]["quiet_ticks"]:
            n = len(jit.programs())
            _tick_edge(system, total_docs)
            ticks += 1
            if len(jit.programs()) != n:
                quiet_from = ticks
        log(f"backlog warm-up: {ticks} ticks, the last {ticks - quiet_from} "
            f"without a compile")
        phases.mark("backlog")
    return Ready(corpus, dict(zip(corpus_names, corpus)), backlog_texts,
                 backlog_names, total_docs, k, spans)


def _measure(cell: Cell, system, *, seed: int, seconds: float, trace: bool,
             phases: Phases, jit: JitLog, workdir: str, devices, device: dict,
             peaks: dict, log, keep: str | None) -> tuple[dict, dict]:
    mix, config = cell.traffic, cell.config
    ready = prepare(cell, system, seed=seed, trace=trace, phases=phases,
                    jit=jit, workdir=workdir, log=log)
    corpus, docs, k = ready.corpus, ready.docs, ready.k
    backlog_texts, backlog_names = ready.backlog_texts, ready.backlog_names
    total_docs, spans = ready.total_docs, ready.spans
    backlog = mix.get("backlog")
    open_loop = mix.get("queries") is not None \
        or mix.get("documents") is not None

    gen = None
    timeline, gc_log, watch = _RowsTimeline(system), _GcLog(), StallWatch()
    timeline.start()
    watch.start()
    gc.callbacks.append(gc_log)
    if open_loop:
        settle = float(mix["settle_s"])
        events = traffic.open_loop_schedule(
            mix, seed, settle + seconds, corpus,
            config["guarantees"]["visible_within_ms"] / 1e3)
        origin = time.perf_counter() + 0.25
        gen = OpenLoop(system.base_url, events, origin, system.live_dir,
                       system.stage_dir)
        gen.start()
        w0 = origin + settle
        _sleep_until(w0)
        before = _edge(system, backlog is not None)
        phases.mark("settle")
    else:
        _tick_edge(system, total_docs)
        before = _edge(system, True)
        w0 = time.perf_counter()
    setup_s = w0 - phases.t_start

    # -- the window -------------------------------------------------------------
    tracing, tracker_seen = None, {}
    if trace:
        trace_s = min(float(mix.get("trace_s", 5.0)), 0.6 * seconds)
        tracing = _Tracing(os.path.join(workdir, "profile"))
        _sleep_until(w0 + (seconds - trace_s) / 2)
        tracing.start()
        tracker = system.tracker()
        while time.perf_counter() < tracing.t0 + trace_s:
            time.sleep(0.5)
            _collect_requests(tracker, tracker_seen)
        tracing.stop()
        while time.perf_counter() < w0 + seconds:
            time.sleep(min(0.5, max(0.0, w0 + seconds
                                    - time.perf_counter())))
            _collect_requests(tracker, tracker_seen)
    _sleep_until(w0 + seconds)
    if open_loop:
        w1 = w0 + seconds
        after = _edge(system, backlog is not None)
    else:
        _tick_edge(system, total_docs)
        after = _edge(system, True)
        w1 = time.perf_counter()
    peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in devices[:max(cell.chips, 1)])
    in_window = jit.programs(w0, w1)
    if gen is not None:
        gen.join()
    timeline.stop()
    watch.stop()
    gc.callbacks.remove(gc_log)
    if trace:
        _collect_requests(system.tracker(), tracker_seen)
    phases.mark("window")

    # -- checks -----------------------------------------------------------------
    run = Run(cell=cell, t_start=phases.t_start, w0=w0, w1=w1, before=before,
              after=after, jit=jit,
              results=list(gen.results) if gen is not None else [],
              spans=spans)
    run.extras.update(setup_s=setup_s, peak_bytes=peak_bytes, peaks=peaks,
                      system=system, corpus_rows=len(corpus),
                      rows_timeline=timeline.changes,
                      gc_pauses=[p for p in gc_log.pauses if w0 <= p[0] <= w1],
                      setup_programs=len(jit.programs(t1=w0)))
    failures: list[str] = []
    info: dict = {}
    window_q = run.window_queries()
    attempted = len(window_q) + sum(
        1 for r in run.results if r.event.kind == "write"
        and w0 <= r.due < w1)
    failed_requests = [q for q in window_q if q.error is not None]
    failures += [f"request failed: {q.error}" for q in failed_requests[:5]]
    written_at = {r.event.doc: r.done for r in run.results
                  if r.event.kind == "write"}
    docs.update((r.event.doc, r.event.text) for r in run.results
                if r.event.kind == "write")
    visible_s = config["guarantees"]["visible_within_ms"] / 1e3
    wrong = check.first_hits_own(
        [q for q in window_q if q.event.doc is not None], "read-your-write")
    fails, info["reference_rank"] = check.first_hits_match_reference(
        system, [q for q in window_q if q.event.doc is None], docs,
        written_at, visible_s)
    wrong += fails
    if backlog is not None:
        ingested = after["file_count"] - before["file_count"]
        attempted += ingested
        drift = abs((after["rows"] - before["rows"]) - ingested)
        info["ingested"] = {"file_count": ingested,
                            "index_rows": after["rows"] - before["rows"]}
        log(f"ingested in the window: {json.dumps(info['ingested'])}")
        if drift > 0.05 * max(ingested, 1):
            failures.append(
                f"/v1/statistics counted {ingested} documents in the window "
                f"but the index grew by {after['rows'] - before['rows']}")
        # documents the index held before the window opened, spread over
        # the length range: each must come back first for its own text
        known = before["file_count"] - len(corpus)
        picks = _spread_by_length(backlog_texts[:known],
                                  mix["after"]["self_retrievals"])
        asked = [_ask(system, backlog_texts[i], k, backlog_names[i])
                 for i in picks]
        attempted += len(asked)
        failures += [f"request failed: {q.error}" for q in asked
                     if q.error is not None]
        failed_requests += [q for q in asked if q.error is not None]
        wrong += check.first_hits_own(asked, "self-retrieval")
    sample_from = backlog_texts[:before.get("file_count", 0)] \
        if backlog is not None else list(docs.values())
    n_sample = mix.get("after", {}).get("embedding_sample", 64)
    sample = [sample_from[i] for i in _spread_by_length(sample_from,
                                                        n_sample)]
    fails, cosines = check.embeddings_agree(system, sample)
    info.update(cosines)
    failures += fails
    failures += wrong[:10]
    if after["extents"] != before["extents"] or after["extents"] != 1:
        failures.append(f"the index has {after['extents']} extents "
                        f"({before['extents']} when the window opened): the "
                        f"reservation did not hold the run")
    if in_window:
        log(f"COMPILED IN THE WINDOW: {in_window}")
    failed = len(failed_requests) + len(wrong)
    phases.mark("checks")
    for f in failures:
        log(f"FAILED: {f}")
    # each number compared, beside its limit: last in the result line and on
    # standard error, which is what the driver keeps of a run that fails
    compared = {
        "min_cos": (info["min_cos"], system.reference.MIN_COS),
        "mean_cos": (info["mean_cos"], system.reference.MIN_MEAN_COS),
        "requests_failed": (len(failed_requests), 0),
        "first_hits_wrong": (len(wrong), 0),
        "extents": (after["extents"], 1)}
    if info["reference_rank"]["checked"]:
        compared["rank_deficit_max"] = (
            info["reference_rank"]["max_deficit"], check.RANK_TOLERANCE)
    if backlog is not None:
        compared["rows_off_file_count"] = (drift, 0.05 * max(ingested, 1))
    compared = {name: {"value": value, "limit": limit}
                for name, (value, limit) in compared.items()}

    # -- metrics ----------------------------------------------------------------
    end_to_end = {}
    for m in cell.end_to_end:
        value = m.read(run)
        if value is None:
            raise SpecError(f"end-to-end metric {m.name!r} found nothing "
                            f"to read in {cell.name!r}")
        end_to_end[m.name] = {"value": value, "unit": m.unit}
    log("end_to_end " + json.dumps({k_: v["value"]
                                    for k_, v in end_to_end.items()}))
    if run.extras["gc_pauses"]:
        by_gen = {g: [s_ for _t, g_, s_ in run.extras["gc_pauses"] if g_ == g]
                  for g in (0, 1, 2)}
        log("gc in the window: " + "; ".join(
            f"gen{g} {len(v)} collections, {sum(v) * 1e3:.0f} ms in all, "
            f"longest {max(v) * 1e3:.0f} ms" for g, v in by_gen.items() if v))
    # a stall of the whole process from the settling to the window's end,
    # with what stood still: in the line too, since of a run that fails the
    # driver keeps that and the end of standard error alone
    stalls = [dict(st, at=st["at"] - w0) for st in watch.stalls]
    for st in stalls:
        log(f"STALL of {st['gap_s']:.2f}s at {st['at']:+.2f}s of the "
            f"window: {st['verdict']}; " + json.dumps(
                {k_: st[k_] for k_ in ("process_cpu_s", "threads",
                                       "machine_core_s")}))
    late = [(q.sent - q.due) * 1e3 for q in window_q]
    if late:
        log(f"generator lateness ms: p50 {stats.percentile(late, 50):.3f} "
            f"p99 {stats.percentile(late, 99):.3f} max {max(late):.3f} "
            f"over {len(late)} queries")
    dev_out = dict(device, memory_peak_bytes=int(peak_bytes))
    detail = {"cell": cell.name, "seed": seed, "seconds": seconds,
              "phases": phases.durations(), "setup_s": setup_s,
              "end_to_end": {k_: v["value"] for k_, v in end_to_end.items()},
              "failures": failures, "checks": info,
              "compiled_in_window": in_window,
              "setup_programs": run.extras["setup_programs"],
              "jit_seconds": {s: jit.seconds(s) for s in JitLog.STAGES},
              "edges": {"before": before, "after": after},
              "rows_timeline": [(t - w0, n) for t, n in timeline.changes
                                if w0 - 1 <= t <= w1],
              "gc_full_in_window_ms": [s_ * 1e3 for _t, g, s_ in
                                       run.extras["gc_pauses"] if g == 2],
              "latencies_ms": [(q.done - q.due) * 1e3 for q in window_q
                               if q.error is None],
              "due_s": [q.due - w0 for q in window_q if q.error is None],
              "lateness_ms": late, "stalls": stalls}
    line = {"correct": not failures, "attempted": int(attempted),
            "failed": int(failed), "metrics": end_to_end, "device": dev_out}
    if stalls:
        line["stalls"] = stalls[:5]
    if not trace:
        line["compared"] = compared
        return line, detail

    # -- the traced run's own: per-layer metrics and the breakdown ---------------
    run.trace, size = tracing.reduce(keep)
    run.traced = (tracing.t0, tracing.t1)
    run.samples = tracing.sampler.samples
    run.requests = [r for r in tracker_seen.values()
                    if r["route"] == "/v1/retrieve"
                    and w0 <= r["t0"] and r["t0"] + r["e2e_ms"] / 1e3 <= w1]
    run.extras["tokenizer_alone"] = _tokenizer_alone(system, sample_from)
    log(f"trace: {size} bytes, window {run.trace.window_s:.3f}s, "
        f"{len(run.trace.devices)} device plane(s), "
        f"{len(run.samples)} host samples, {len(run.requests)} requests")
    for d in run.trace.devices:
        log(f"trace {d.name}: busy {d.busy_s:.4f}s; modules " + json.dumps(
            {n: [len(r), sum(r)] for n, r in sorted(d.modules.items())})
            + "; scopes " + json.dumps(dict(sorted(
                d.scopes.items(), key=lambda kv: -kv[1])[:16])))
    layers = {}
    for layer in cell.layers:
        value = layer.read(run)
        if value is not None:
            layers[layer.name] = {"value": value, "unit": layer.unit}
    log("per_layer " + json.dumps({k_: v["value"]
                                   for k_, v in layers.items()}))
    breakdown = {"device_ops": tracelib.top_ops(run.trace),
                 "idle_gaps": tracelib.idle_gaps_by_host(run.trace,
                                                         run.samples)}
    dev_out.update(busy_s=run.trace.busy_s, window_s=run.trace.window_s)
    detail.update(per_layer={k_: v["value"] for k_, v in layers.items()},
                  breakdown=breakdown,
                  request_stages_ms=_stage_table(run.requests))
    line.update(metrics=layers, breakdown=breakdown, compared=compared)
    return line, detail


class _RowsTimeline(threading.Thread):
    """When the index took each batch of documents, to a few milliseconds:
    the instants at which its count of rows changed, polled. With the
    instants the documents were written this gives how long each took to
    become retrievable; the program stamps no commit time of its own yet."""

    def __init__(self, system, interval_s: float = 0.01):
        super().__init__(daemon=True, name="bench-rows-timeline")
        self.system = system
        self.interval_s = interval_s
        self.changes: list[tuple[float, int]] = []
        self._halt = threading.Event()

    def run(self) -> None:
        rows = self.system.rows()
        while not self._halt.wait(self.interval_s):
            now = self.system.rows()
            if now != rows:
                rows = now
                self.changes.append((time.perf_counter(), rows))

    def stop(self) -> None:
        self._halt.set()
        self.join(timeout=5.0)


class _GcLog:
    """Every collection of the interpreter's garbage collector: (instant it
    ended, generation, seconds). A collection holds the GIL, so every
    thread of the one-process server waits for it."""

    def __init__(self):
        self.pauses: list[tuple[float, int, float]] = []
        self._t0 = 0.0

    def __call__(self, phase: str, info: dict) -> None:
        now = time.perf_counter()
        if phase == "start":
            self._t0 = now
        else:
            self.pauses.append((now, info["generation"], now - self._t0))


def _collect_requests(tracker, seen: dict) -> None:
    """The tracker keeps a ring of its last completed requests; read it
    often enough that none of a window's is lost."""
    if tracker is not None:
        for rec in tracker.trace_spans():
            seen[rec["request_id"]] = rec


def _stage_table(requests: list) -> dict:
    """Median of each of the tracker's stages over the window's queries."""
    if not requests:
        return {}
    return {stage: stats.median([r["stages"][stage] for r in requests])
            for stage in requests[0]["stages"]}


def _tokenizer_alone(system, texts: list[str]) -> dict:
    """The tokenizer alone on the cell's documents, outside the window."""
    texts = texts[:4096]
    if not texts:
        return {}
    t0 = time.perf_counter()
    system.embedder.tokenizer.batch(
        texts, max_len=system.config["serving"]["max_len"])
    return {"docs": len(texts), "seconds": time.perf_counter() - t0}
