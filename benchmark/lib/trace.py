"""Reduction of a profiler trace (``*.xplane.pb``) to what the metrics read.

``jax.profiler.ProfileData`` gives planes, their lines, and events with a
start and a duration in nanoseconds. On a TPU every chip is a plane
``/device:TPU:<n>`` whose line ``XLA Ops`` holds one event per executed HLO
operation (nested where an operation contains others, as a ``while`` does)
and whose line ``XLA Modules`` holds one event per executed program, named
after the jitted function. Host threads are lines of the plane
``/host:CPU``, named after the thread; ``jax.profiler.TraceAnnotation``
spans of the benchmark land there under the name given, which is how the
traced window's edges and the host clock's offset get into the trace.

The CPU backend has no device plane: its operations are events of host
lines that carry an ``hlo_module`` stat. They are reduced the same way
under the plane name ``xla-cpu``, so that the reduction can be rehearsed
and tested without a chip. No number from such a plane is a device metric.

XLA names programs after the program's function names today; the patterns
that map them to kernels sit in :data:`MODULE_PATTERNS` alone.
"""

from __future__ import annotations

import bisect
import dataclasses
import re

#: kernel -> pattern over the name of an executed XLA module, which is
#: ``jit_<function>`` of the function the program jits. Stable names
#: (``jax.named_scope``) are the tracing issue's first item.
MODULE_PATTERNS: dict[str, str] = {
    # ops/knn.py _chunked_search.search
    "scan": r"^jit_search$",
    # ops/knn.py _fused_step_fns.step / step_i8 (encoder forward + scatter)
    "fused_ingest": r"^jit_step(_i8)?$",
    # xpacks/llm/embedders.py jax.jit(self.ragged_device_producer) and
    # jax.jit(self.device_producer): the plain encoder of the query path
    "encoder": r"^jit_(ragged_)?device_producer$",
}

BEGIN_MARK = "bench.trace.begin"
END_MARK = "bench.trace.end"
CLOCK_MARK = "bench.clock"

_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


@dataclasses.dataclass
class DeviceTrace:
    """One chip's share of the traced window. Seconds throughout; times are
    relative to the profile's own clock."""

    name: str
    busy_s: float                         # union of operation intervals
    ops: dict[str, float]                 # self seconds by "module/op"
    modules: dict[str, list[float]]       # seconds per execution, by module
    gaps: list[tuple[float, float]]       # idle intervals (start, end)


@dataclasses.dataclass
class Reduced:
    t0: float
    t1: float
    devices: list[DeviceTrace]
    host_spans: list[tuple[str, str, float, float]]  # thread, name, t0, t1
    clock_offset_s: float | None  # trace clock minus time.perf_counter()

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    @property
    def busy_s(self) -> float:
        """Averaged over the chips used."""
        return sum(d.busy_s for d in self.devices) / len(self.devices)

    def idle_share(self) -> float:
        """1 - busy/window on the chip that idles most."""
        return 1.0 - min(d.busy_s for d in self.devices) / self.window_s

    def module_seconds(self, kernel: str) -> list[list[float]]:
        """Per chip, the device seconds of every execution in the window of
        the modules :data:`MODULE_PATTERNS` maps to ``kernel``."""
        pat = re.compile(MODULE_PATTERNS[kernel])
        return [[s for name, runs in d.modules.items() if pat.match(name)
                 for s in runs] for d in self.devices]


def _op_label(name: str) -> str:
    """A TPU operation's event is named by its whole HLO line,
    ``%fusion.8 = (f32[8,128]{...}, ...) fusion(...)``: keep the name and
    the result's type without its layout."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name
    result = re.sub(r"\{[^}]*\}", "", rest.split(" ", 1)[0]) \
        if not rest.startswith("(") else "(tuple)"
    return f"{head.lstrip('%')} {result}"


def _module_base(name: str) -> str:
    """``jit_search(1234567)`` -> ``jit_search``."""
    return re.sub(r"\(\d+\)$", "", name).strip()


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[tuple[float, float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def _self_seconds(events: list[tuple[float, float, str]]) -> dict[str, float]:
    """Self time by name over properly nested events (start, end, name): an
    event's duration minus the part its direct children cover."""
    out: dict[str, float] = {}
    stack: list[list] = []   # [end, name, self_s]

    def close(top):
        out[top[1]] = out.get(top[1], 0.0) + max(top[2], 0.0)

    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        if stack:
            stack[-1][2] -= (min(e, stack[-1][0]) - s)
        stack.append([e, name, e - s])
    while stack:
        close(stack.pop())
    return out


def _reduce_device(name: str, ops: list[tuple[float, float, str]],
                   modules: list[tuple[float, float, str]], t0: float,
                   t1: float) -> DeviceTrace:
    """``ops`` and ``modules`` are (start, end, name) in seconds."""
    clipped = [(max(s, t0), min(e, t1), n) for s, e, n in ops
               if e > t0 and s < t1]
    busy = _union([(s, e) for s, e, _n in clipped])
    # name each operation by the program it ran in (the programs of one
    # chip do not overlap, so the last one started is the candidate)
    mods = sorted(modules)
    starts = [ms for ms, _me, _mn in mods]
    labelled = []
    for s, e, n in clipped:
        i = bisect.bisect_right(starts, s) - 1
        mod = _module_base(mods[i][2]) if i >= 0 and s < mods[i][1] else "?"
        labelled.append((s, e, f"{mod}/{_op_label(n)}"))
    runs: dict[str, list[float]] = {}
    for s, e, n in mods:
        if s >= t0 and e <= t1:
            runs.setdefault(_module_base(n), []).append(e - s)
    gaps, cur = [], t0
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < t1:
        gaps.append((cur, t1))
    return DeviceTrace(name, sum(e - s for s, e in busy),
                       _self_seconds(labelled), runs, gaps)


def _stats(event) -> dict:
    return {k: v for k, v in event.stats}


def reduce_xplane(path: str) -> Reduced:
    """Reduce the profile at ``path``. The window runs from the end of the
    :data:`BEGIN_MARK` span to the start of the :data:`END_MARK` span where
    the benchmark wrote them, else over everything a device did."""
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(path)
    host_spans: list[tuple[str, str, float, float]] = []
    marks: dict[str, list] = {}
    dev_ops: dict[str, list] = {}
    dev_mods: dict[str, list] = {}
    cpu_ops: list[tuple[float, float, str, str, int]] = []
    for plane in profile.planes:
        is_device = bool(_DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if is_device:
                target = {"XLA Ops": dev_ops,
                          "XLA Modules": dev_mods}.get(line.name)
                if target is None:
                    continue
                target.setdefault(plane.name, []).extend(
                    (ev.start_ns / 1e9, (ev.start_ns + ev.duration_ns) / 1e9,
                     ev.name) for ev in line.events)
                continue
            if not plane.name.startswith("/host:"):
                continue
            for ev in line.events:
                s = ev.start_ns / 1e9
                e = s + ev.duration_ns / 1e9
                if ev.name in (BEGIN_MARK, END_MARK, CLOCK_MARK):
                    marks.setdefault(ev.name, []).append((s, e, _stats(ev)))
                    continue
                if ev.name.startswith("end: "):
                    continue
                st = _stats(ev)
                if "hlo_module" in st:
                    cpu_ops.append((s, e, str(st.get("hlo_op", ev.name)),
                                    str(st["hlo_module"]),
                                    int(st.get("run_id", 0))))
                elif ev.duration_ns > 0:
                    host_spans.append((line.name, ev.name, s, e))
    if not dev_ops and cpu_ops:
        # the CPU backend: one module execution per (module, run_id)
        ops = [(s, e, n) for s, e, n, _m, _r in cpu_ops]
        by_run: dict[tuple, list] = {}
        for s, e, _n, m, r in cpu_ops:
            by_run.setdefault((m, r), []).append((s, e))
        dev_ops["xla-cpu"] = ops
        dev_mods["xla-cpu"] = [
            (min(s for s, _e in iv), max(e for _s, e in iv), m)
            for (m, _r), iv in by_run.items()]
    if not dev_ops:
        seen = {p.name: [ln.name for ln in p.lines] for p in profile.planes}
        raise ValueError(f"{path}: no operation ran on a device in this "
                         f"trace; its planes and lines are {seen}")
    everything = [iv for ops in dev_ops.values() for iv in ops]
    t0 = marks[BEGIN_MARK][0][1] if BEGIN_MARK in marks \
        else min(s for s, _e, _n in everything)
    t1 = marks[END_MARK][-1][0] if END_MARK in marks \
        else max(e for _s, e, _n in everything)
    offset = None
    if CLOCK_MARK in marks:
        s, _e, st = marks[CLOCK_MARK][0]
        if "perf_counter_ns" in st:
            offset = s - int(st["perf_counter_ns"]) / 1e9
    devices = [_reduce_device(name, dev_ops[name], dev_mods.get(name, []),
                              t0, t1) for name in sorted(dev_ops)]
    spans = [sp for sp in host_spans if sp[3] > t0 and sp[2] < t1]
    return Reduced(t0, t1, devices, spans, offset)


def top_ops(reduced: Reduced, n: int = 10) -> list[list]:
    """The device operations that took most self time, seconds averaged
    over the chips used."""
    total: dict[str, float] = {}
    for d in reduced.devices:
        for name, s in d.ops.items():
            total[name] = total.get(name, 0.0) + s / len(reduced.devices)
    return [[name, s] for name, s in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps_by_host(reduced: Reduced, samples: list[tuple[float, str]],
                      n: int = 10, min_gap_s: float = 1e-3) -> list[list]:
    """The idle time of the chip that idles most, by what the host was
    doing. ``samples`` are (time.perf_counter() seconds, label) readings of
    the host sampler; a gap takes the label seen most often inside it, or
    failing that the host span of the trace that overlaps it most. Gaps
    under ``min_gap_s`` (between the operations of one dispatch) are summed
    under one name."""
    dev = min(reduced.devices, key=lambda d: d.busy_s)
    times: list[float] = []
    labels: list[str] = []
    if samples and reduced.clock_offset_s is not None:
        samples = sorted(samples)
        times = [t + reduced.clock_offset_s for t, _l in samples]
        labels = [l for _t, l in samples]
    out: dict[str, float] = {}
    for s, e in dev.gaps:
        if e - s < min_gap_s:
            label = f"gaps under {min_gap_s * 1e3:g} ms"
        else:
            lo, hi = bisect.bisect_left(times, s), bisect.bisect_right(
                times, e)
            if hi > lo:
                seen: dict[str, int] = {}
                for lab in labels[lo:hi]:
                    seen[lab] = seen.get(lab, 0) + 1
                label = max(seen, key=seen.get)
            else:
                overlap: dict[str, float] = {}
                for thread, name, hs, he in reduced.host_spans:
                    o = min(e, he) - max(s, hs)
                    if o > 0:
                        key = f"{thread}:{name}"
                        overlap[key] = overlap.get(key, 0.0) + o
                label = max(overlap, key=overlap.get) if overlap \
                    else "no host span"
        out[label] = out.get(label, 0.0) + (e - s)
    return [[name, s] for name, s in
            sorted(out.items(), key=lambda kv: -kv[1])[:n]]
