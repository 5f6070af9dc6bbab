"""Arithmetic the metric readers share. A reader is a file of its own
(``benchmark/end_to_end/<name>.py``, ``benchmark/layers/<name>.py``) that
says in one line what it reads; what several of them compute is here. Every
function returns None where the run holds nothing to read."""

from __future__ import annotations

import bisect

from benchmark.lib import costs, stats


def query_latency_ms(run, q: float) -> float | None:
    """Percentile ``q`` over the window's answered queries of (response
    received - instant the query was due)."""
    ms = [(r.done - r.due) * 1e3 for r in run.window_queries()
          if r.error is None]
    return stats.percentile(ms, q) if ms else None


def stage_p50_ms(run, stage: str) -> float | None:
    """Median of one request-tracker stage over the window's queries."""
    if not run.requests:
        return None
    return stats.median([r["stages"][stage] for r in run.requests])


def bridge_delta(run) -> dict | None:
    """DeviceBridge.stats() after - before the window."""
    a, b = run.before.get("bridge"), run.after.get("bridge")
    if not a or not b:
        return None
    return {key: b[key] - a[key] for key in
            ("legs_resolved", "legs_overlapped", "exec_ms", "queue_wait_ms")}


def bridge_overlap_ratio(run) -> float | None:
    d = bridge_delta(run)
    if not d or not d["legs_resolved"]:
        return None
    return d["legs_overlapped"] / d["legs_resolved"]


def bridge_leg_host_ms_mean(run) -> float | None:
    """``exec_ms`` per resolved leg: the bridge worker's host-clocked time
    in a leg, dispatch and any wait for the device included — not device
    time."""
    d = bridge_delta(run)
    if not d or not d["legs_resolved"]:
        return None
    return d["exec_ms"] / d["legs_resolved"]


def device_idle_share(run) -> float | None:
    """1 - union of operation intervals / traced window, on the chip that
    idles most, in percent."""
    return None if run.trace is None else 100.0 * run.trace.idle_share()


def compiles_in_window(run) -> float:
    """Backend compiles (loads from the persistent cache included) that
    ended inside the window."""
    return float(len(run.jit.programs(run.w0, run.w1)))


def span_share(run, name: str) -> float | None:
    """Percent of the traced part of the window spent inside the
    benchmark's span ``name``."""
    if run.traced is None or name not in run.spans:
        return None
    t0, t1 = run.traced
    inside = sum(min(e, t1) - max(s, t0) for s, e, _m in run.spans[name]
                 if e > t0 and s < t1)
    return 100.0 * inside / (t1 - t0)


def span_p50_ms(run, name: str) -> float | None:
    spans = run.spans_in(name)
    return stats.median([(e - s) * 1e3 for s, e, _m in spans]) \
        if spans else None


def module_runs(run, kernels: tuple[str, ...]) -> list[list[float]] | None:
    """Per chip, the device seconds of the executions of the first of
    ``kernels`` (names of benchmark.lib.trace.MODULE_PATTERNS) that ran in
    the traced window."""
    if run.trace is None:
        return None
    for kernel in kernels:
        runs = run.trace.module_seconds(kernel)
        if any(runs):
            return runs
    return None


def module_p50_ms(run, kernels: tuple[str, ...]) -> float | None:
    """Median device time of one execution, over the chips that ran it."""
    runs = module_runs(run, kernels)
    if runs is None:
        return None
    return 1e3 * stats.median([s for chip in runs for s in chip])


def scope_seconds(run, kernel: str, scope: str) -> float | None:
    """Device self seconds in the traced window of the operations under the
    named scope ``scope`` (``jax.named_scope``, or a nested function's
    ``jit(name)``) in the programs of ``kernel``: a name of
    benchmark.lib.trace.MODULE_PATTERNS or a pattern over module names. On
    the chip that spent most there; None where the trace holds no such
    operation (as every trace of the CPU backend, which keeps no scopes).
    The time a kernel's reader divides its least time by, where the kernel
    is part of a larger program."""
    if run.trace is None:
        return None
    return max(run.trace.scope_seconds(kernel, scope)) or None


def scan_roofline(run) -> float | None:
    """The scan kernel's share of its roofline, in percent: the least time
    the chip could take for one search of the established slab (the larger
    of operations over peak FLOP/s and bytes over peak bytes/s) over the
    median device time of the search program. At serving batch sizes the
    bytes bound it: it is the bandwidth bound."""
    p50 = module_p50_ms(run, ("scan",))
    searches = run.spans_in("index.search", run.traced)
    if p50 is None or not searches:
        return None
    batch = stats.median([m["queries"] for _s, _e, m in searches])
    flops, nbytes = run.extras["system"].scan_cost(int(round(batch)))
    share, _bound = costs.roofline(flops, nbytes, p50 / 1e3,
                                   run.extras["peaks"])
    return 100.0 * share


def encoder_roofline(run, kernels: tuple[str, ...]) -> float | None:
    """The encoder kernel's share of its roofline, in percent: the least
    time the chip could take for the shapes dispatched in the traced window
    (from the benchmark's span around the packer) over the device time of
    the programs that ran them."""
    runs = module_runs(run, kernels)
    packs = run.spans_in("pack", run.traced)
    if runs is None or not packs:
        return None
    system, peaks = run.extras["system"], run.extras["peaks"]
    least = 0.0
    for _s, _e, meta in packs:
        for shape in meta["shapes"]:
            flops, nbytes = system.encoder_cost(tuple(shape), meta["ragged"])
            least += max(flops / peaks["flops_per_s"],
                         nbytes / peaks["bytes_per_s"])
    measured = max(sum(chip) for chip in runs)
    return 100.0 * least / measured if measured else None


def visible_ms(run, q: float) -> float | None:
    """Percentile ``q`` of (index took the document - document written) over
    the window's live documents. The timeline starts before the first write,
    when the index holds the corpus alone, and the connector reads live
    documents in the order they were written: the n-th is in at the first
    change that leaves the index with the corpus and n more."""
    timeline = run.extras.get("rows_timeline")
    writes = sorted(r.done for r in run.results if r.event.kind == "write")
    if not timeline or not writes or "backlog" in run.cell.traffic:
        return None
    times = [t for t, _rows in timeline]
    rows = [n for _t, n in timeline]
    base = run.extras["corpus_rows"]
    delays = []
    for n, written in enumerate(writes, start=1):
        if not run.w0 <= written < run.w1:
            continue
        i = next((j for j in range(bisect.bisect_left(times, written),
                                   len(rows)) if rows[j] >= base + n), None)
        if i is not None:
            delays.append((times[i] - written) * 1e3)
    return stats.percentile(delays, q) if delays else None
