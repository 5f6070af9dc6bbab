"""Arithmetic over the stages the program records inside a search, an
ingest call and a connector's pass.

Beside the spans :mod:`benchmark.lib.program_spans` lists, the flight
recorder's store holds (``pathway_tpu/engine/flight_recorder.py``; written
by ``ops/knn.py``, ``xpacks/llm/embedders.py`` and ``io/fs``):

    index.search        one search of the served index, the whole call;
                        ``queries``, and of the stages that are no span of
                        their own ``flush_rows``, ``prepare_ms`` (lock taken
                        -> the query matrix handed to the device),
                        ``rank_ms`` (slot -> key, filter, distance),
                        ``rounds`` (scans: 1 without a selective filter)
    search.embed        in it: the query's text to its embedding on the
                        host (tokenize, pack, the encoder's dispatch, the
                        blocking fetch); ``queries``
    search.scan         in it: first search program dispatched -> last
                        result fetched; ``queries``, ``fetch_k``,
                        ``extents``, ``dispatch_ms`` (until the jitted
                        calls returned)
    index.add_batch     one ingest call of the served index; ``docs``,
                        ``dispatches``, ``fused``
    embedder.pack       the packer, tokenizing included, on the ingest and
                        the query path; ``texts``, ``rows``, ``slots``,
                        ``tokens``
    embedder.tokenize   in it: the tokenizer's call; ``texts``, ``tokens``
    connector.pass      gains ``cpu_ms`` (the reader thread's own CPU, from
                        ``time.thread_time``), ``stat_ms`` (``stat`` and the
                        test after it, of every listed file), ``parse_ms``
                        (read, decode, key), ``push_ms`` (``session.push``)
    connector.progress  of a pass that reads a backlog, one every 256 files
                        and one for the rest; cause ``("pass", uid, n)`` of
                        its pass; ``files``, ``rows`` and the same four
                        times over its stretch

The spans of a search and of an ingest call carry the cause of the bridge
leg they ran in, ``("tick", n)``, which a request's record names too. Every
function returns None where the program records no such span (a program
from before it had them; an untraced run), and where the store no longer
holds the window's first spans: it is bounded, and the readers run after
the checks that follow the window. Whether it does is logged once a run.
"""

from __future__ import annotations

import json

from benchmark.lib.program_spans import (_median, _ms, _overlap, device_busy,
                                         recorder)


def _store(run) -> dict | None:
    """name -> the recorder's spans of that name, oldest first, read once
    a run."""
    if "stage_spans" not in run.extras:
        run.extras["stage_spans"] = _read_store(run)
    return run.extras["stage_spans"]


def _read_store(run) -> dict | None:
    rec = recorder(run)
    spans = rec.spans() if rec is not None else None
    if not spans:
        return None
    by_name: dict[str, list] = {}
    for sp in spans:
        by_name.setdefault(sp[0], []).append(sp)
    # spans are stored as they end: what ended after the oldest is there
    before = run.w0 - spans[0][2]
    print(f"stage_spans: the store holds {len(spans)} spans, the oldest "
          f"ended {before:.1f} s before the window's start: "
          + ("the window is whole" if before > 0 else
             "THE WINDOW'S FIRST SPANS ARE GONE, the stages are left out")
          + "; " + json.dumps({n: len(by_name[n]) for n in sorted(by_name)}),
          flush=True)
    if before <= 0:
        return None
    _log_progress(run, by_name)
    return by_name


def _by_cause(store: dict, name: str) -> dict:
    out: dict = {}
    for sp in store.get(name, ()):
        out.setdefault(sp[3], []).append(sp)
    return out


def _total_ms(spans) -> float:
    return sum(_ms(sp) for sp in spans)


# -- a search, by the request it served ---------------------------------------

def _request_causes(run, store: dict, traced: bool) -> list | None:
    """The tick's cause of each of the window's requests; ``traced``: of
    those whose tick's ``bridge.leg`` lies wholly inside the traced part
    (the requests ``request.leg_host_ms_p50`` is taken over)."""
    if not run.requests:
        return None
    causes = [("tick", r["tick"]) for r in run.requests]
    if not traced:
        return causes
    if run.traced is None:
        return None
    t0, t1 = run.traced
    legs = {sp[3]: sp for sp in store.get("bridge.leg", ())}
    return [c for c in causes if c in legs
            and t0 <= legs[c][1] and legs[c][2] <= t1]


def _request_spans(run, name: str, traced: bool = False) -> list | None:
    """Per request, the spans ``name`` of the request's tick."""
    store = _store(run)
    if store is None or name not in store:
        return None
    causes = _request_causes(run, store, traced)
    if causes is None:
        return None
    spans = _by_cause(store, name)
    return [spans[c] for c in causes if c in spans]


def span_ms_p50(run, name: str) -> float | None:
    """Median over the window's requests of the time inside the spans
    ``name`` of the request's tick."""
    groups = _request_spans(run, name)
    return _median(_total_ms(g) for g in groups) if groups else None


def span_host_ms_p50(run, name: str) -> float | None:
    """The same less the chip's busy time inside the spans (on the chip
    busiest there), over the requests whose leg lies in the traced part:
    the host's part of the stage."""
    groups, busy = _request_spans(run, name, traced=True), device_busy(run)
    if not groups or busy is None:
        return None
    return _median(
        sum(_ms(sp) - 1e3 * max(_overlap(dev, sp[1], sp[2]) for dev in busy)
            for sp in g) for g in groups)


def span_count_p50(run, name: str, count: str) -> float | None:
    """Median over the window's requests of the count ``count`` summed
    over the spans ``name`` of the request's tick."""
    groups = _request_spans(run, name)
    if not groups:
        return None
    return _median(sum((sp[5] or {}).get(count, 0.0) for sp in g)
                   for g in groups)


def search_self_ms_p50(run) -> float | None:
    """Median over the window's requests of ``index.search`` less the
    ``search.embed`` and ``search.scan`` inside it: its self time (the
    flush, the stack and the upload of the query matrix, the ranking)."""
    groups = _request_spans(run, "index.search")
    if not groups:
        return None
    store = _store(run)
    embeds, scans = (_by_cause(store, n) for n in ("search.embed",
                                                   "search.scan"))
    values = [_total_ms(g) - _total_ms(embeds.get(g[0][3], ()))
              - _total_ms(scans.get(g[0][3], ())) for g in groups]
    counts = [sp[5] or {} for g in groups for sp in g]
    packs, tokenizes = (_by_cause(store, n) for n in ("embedder.pack",
                                                      "embedder.tokenize"))
    inside = {n: _median(_total_ms(spans.get(g[0][3], ())) for g in groups)
              for n, spans in (("embedder.pack", packs),
                               ("embedder.tokenize", tokenizes))}
    print("stage_spans: index.search ms p50 "
          f"{_median(_total_ms(g) for g in groups):.3f} over {len(groups)} "
          "requests; of its self time, medians: " + json.dumps(
              {c: _median(k.get(c, 0) for k in counts)
               for c in ("prepare_ms", "rank_ms", "flush_rows", "rounds")})
          + "; inside search.embed, ms p50: " + json.dumps(inside),
          flush=True)
    return _median(values)


def leg_outside_search_ms_p50(run) -> float | None:
    """Median over the window's requests of the ``bridge.leg`` of the
    request's tick less the ``index.search`` spans inside it: the
    scheduler's stepping and the response's rows."""
    store = _store(run)
    if store is None or "index.search" not in store:
        return None
    causes = _request_causes(run, store, traced=False)
    if causes is None:
        return None
    legs = {sp[3]: sp for sp in store.get("bridge.leg", ())}
    searches = _by_cause(store, "index.search")
    return _median(_ms(legs[c]) - _total_ms(searches.get(c, ()))
                   for c in causes if c in legs)


# -- an ingest call, as a share of the window ---------------------------------

def _inside(spans, a: float, b: float) -> float:
    """Seconds of ``[a, b]`` inside ``spans`` (one thread's: disjoint)."""
    return sum(max(0.0, min(sp[2], b) - max(sp[1], a)) for sp in spans)


def window_share(run, name: str, less: str | None = None) -> float | None:
    """Percent of the window inside the spans ``name``, less the part the
    spans ``less`` (their children) cover. The same over the traced part
    is logged: the benchmark's own spans around these calls are read over
    that part alone."""
    store = _store(run)
    if store is None or name not in store:
        return None

    def share(a: float, b: float) -> float:
        inside = _inside(store[name], a, b)
        if less is not None:
            inside -= _inside(store.get(less, ()), a, b)
        return 100.0 * inside / (b - a)

    whole = share(run.w0, run.w1)
    what = name if less is None else f"{name} less {less}"
    print(f"stage_spans: {what}: {whole:.3f} % of the window"
          + (f", {share(*run.traced):.3f} % of its traced part"
             if run.traced is not None else ""), flush=True)
    return whole


# -- the connector's reader ---------------------------------------------------

def _in_window(run, spans) -> list:
    return [sp for sp in spans if run.w0 <= sp[1] and sp[2] <= run.w1]


def pass_cpu_share(run) -> float | None:
    """Percent of the wall time of the polling passes inside the window
    that their thread was on a CPU (``cpu_ms`` over the duration)."""
    store = _store(run)
    if store is None:
        return None
    passes = [sp for sp in _in_window(run, store.get("connector.pass", ()))
              if "cpu_ms" in (sp[5] or {})]
    wall = _total_ms(passes)
    if not wall:
        return None
    sums = {c: sum(sp[5].get(c, 0.0) for sp in passes)
            for c in ("cpu_ms", "list_ms", "stat_ms", "parse_ms", "push_ms")}
    print(f"stage_spans: connector.pass: {len(passes)} passes of "
          f"{wall / len(passes):.1f} ms in the mean; percent of their wall "
          "time: " + json.dumps({c: round(100.0 * v / wall, 2)
                                 for c, v in sums.items()}), flush=True)
    return 100.0 * sums["cpu_ms"] / wall


def _progress_sums(run, store: dict) -> dict | None:
    """Wall ms, files and the summed counts of the window's
    ``connector.progress`` spans."""
    spans = _in_window(run, store.get("connector.progress", ()))
    files = sum((sp[5] or {}).get("files", 0) for sp in spans)
    if not files:
        return None
    out = {"spans": len(spans), "wall_ms": _total_ms(spans), "files": files}
    for c in ("cpu_ms", "stat_ms", "parse_ms", "push_ms"):
        out[c] = sum((sp[5] or {}).get(c, 0.0) for sp in spans)
    return out


def _log_progress(run, store: dict) -> None:
    """The reader's progress in every cell whose window holds any: three
    metrics read it in the one cell whose backlog outlasts the window by
    the harness's rule; elsewhere this line is the record."""
    sums = _progress_sums(run, store)
    if sums is None:
        return
    wall = sums["wall_ms"]
    print(f"stage_spans: connector.progress: {sums['spans']} spans in the "
          f"window over {sums['files']} files, {wall / sums['files']:.4f} ms "
          "a file; percent of their wall time: " + json.dumps(
              {c: round(100.0 * sums[c] / wall, 2)
               for c in ("cpu_ms", "stat_ms", "parse_ms", "push_ms")}),
          flush=True)


def progress(run) -> dict | None:
    store = _store(run)
    return None if store is None else _progress_sums(run, store)


def file_ms_mean(run) -> float | None:
    """Wall time of the window's ``connector.progress`` spans over the
    files they read."""
    sums = progress(run)
    return sums["wall_ms"] / sums["files"] if sums else None


def progress_share(run, count: str) -> float | None:
    """Percent of the wall time of the window's ``connector.progress``
    spans that their count ``count`` (ms) makes up."""
    sums = progress(run)
    return 100.0 * sums[count] / sums["wall_ms"] if sums else None
