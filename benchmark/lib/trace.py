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

Inside a program an operation is found by its **scope**. The compiler keeps,
for every HLO operation, JAX's name stack of the instruction it came from
(of a fusion: of its root), ``jit(step)/encoder.mlp/dot_general``: the jitted
function, every ``jax.named_scope`` and nested function around the call,
the primitive. On a TPU the profile carries it as the stat ``tf_op`` (with
a trailing ``:``) of the operation's *event metadata* in the plane, beside
the ``program_id`` of the program the operation belongs to, which is the
number in the name of that program's ``XLA Modules`` events
(``jit_search(4586580180248420438)``). ``ProfileData`` hands out neither
(it gives an event's own stats: offset, duration): :func:`_op_paths` reads
the metadata, and nothing else, from the file's protobuf wire format, and
an operation's event is joined to it by (program, name of the event), which
names one instruction.
The CPU backend's events carry no such path; its operations stay under
their module alone. An operation the compiler made itself (a ``copy-done``)
has none either.
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
#: the stats of an operation's event metadata that hold JAX's name stack and
#: the program the operation belongs to (found on the chip: PR 22's and PR
#: 27's recorded profiles)
_SCOPE_STAT = "tf_op"
_PROGRAM_STAT = "program_id"


@dataclasses.dataclass
class DeviceTrace:
    """One chip's share of the traced window. Seconds throughout; times are
    relative to the profile's own clock."""

    name: str
    busy_s: float                         # union of operation intervals
    #: self seconds by "module/path/op"; path, where the profile has one, is
    #: the operation's scope and primitive (``search.score/dot_general``)
    ops: dict[str, float]
    modules: dict[str, list[float]]       # seconds per execution, by module
    gaps: list[tuple[float, float]]       # idle intervals (start, end)
    #: self seconds by "module/scope", by "module" alone where an operation
    #: has no scope: they add up to the module's operations' self time
    scopes: dict[str, float] = dataclasses.field(default_factory=dict)


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

    def scope_seconds(self, kernel: str, scope: str) -> list[float]:
        """Per chip, the self seconds in the window of the operations under
        ``scope`` in the modules of ``kernel``: a name of
        :data:`MODULE_PATTERNS`, or a pattern over module names of the
        caller's own. ``scope`` is one name or several joined by ``/``, and
        is found wherever it stands in an operation's scope
        (``encoder.mlp`` in ``while/body/encoder.mlp``), since a loop or a
        nested function around a kernel is not the kernel's to know."""
        pat = re.compile(MODULE_PATTERNS.get(kernel, kernel))
        want = scope.split("/")

        def under(key: str) -> bool:
            module, _sep, path = key.partition("/")
            parts = path.split("/")
            return bool(pat.match(module)) and any(
                parts[i:i + len(want)] == want for i in range(len(parts)))

        return [sum(s for key, s in d.scopes.items() if under(key))
                for d in self.devices]


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


def _path(tf_op: str) -> str:
    """``jit(step)/jit(main)/encoder.mlp/dot_general:`` ->
    ``encoder.mlp/dot_general``: the name stack without the jitted function
    the program is named after."""
    parts = tf_op.partition(":")[0].split("/")
    if parts[0].startswith("jit("):
        parts = parts[1:]
    if parts and parts[0] == "jit(main)":
        parts = parts[1:]
    return "/".join(parts)


def _module_base(name: str) -> str:
    """``jit_search(1234567)`` -> ``jit_search``."""
    return re.sub(r"\(\d+\)$", "", name).strip()


def _program_id(name: str) -> int:
    """``jit_search(1234567)`` -> 1234567, the program's id; 0 without."""
    found = re.search(r"\((\d+)\)$", name)
    return int(found.group(1)) if found else 0


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
                   t1: float, paths: dict[tuple[int, str], str] | None = None
                   ) -> DeviceTrace:
    """``ops`` and ``modules`` are (start, end, name) in seconds; ``paths``
    gives the scope and primitive of an operation by (the id of the program
    it ran in, its event's name)."""
    paths = paths or {}
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
        inside = i >= 0 and s < mods[i][1]
        mod = _module_base(mods[i][2]) if inside else "?"
        path = paths.get((_program_id(mods[i][2]), n), "") if inside else ""
        labelled.append((s, e, (mod, path, _op_label(n))))
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
    by_op: dict[str, float] = {}
    by_scope: dict[str, float] = {}
    for (mod, path, op), sec in _self_seconds(labelled).items():
        scope = path.rpartition("/")[0]
        for total, key in ((by_op, (mod, path, op)),
                           (by_scope, (mod, scope))):
            key = "/".join(part for part in key if part)
            total[key] = total.get(key, 0.0) + sec
    return DeviceTrace(name, sum(e - s for s, e in busy), by_op, runs, gaps,
                       by_scope)


def _stats(event) -> dict:
    return {k: v for k, v in event.stats}


def _varint(buf, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) over one protobuf message: a varint's number,
    or the bytes of a length-delimited or fixed-width field as a view."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        wire = tag & 7
        if wire == 0:
            value, i = _varint(buf, i)
        else:
            if wire == 2:
                size, i = _varint(buf, i)
            elif wire in (1, 5):
                size = 8 if wire == 1 else 4
            else:
                raise ValueError(f"protobuf wire type {wire}")
            value, i = buf[i:i + size], i + size
        yield tag >> 3, value


def _op_paths(path: str) -> dict[str, dict[tuple[int, str], str]]:
    """Per device plane, :func:`_path` of every operation that has one, by
    (the operation's program id, the name of its events). Read from the
    file's wire format (tsl's ``xplane.proto``): ``XSpace.planes = 1``;
    ``XPlane.name = 2``, ``.event_metadata = 4`` and ``.stat_metadata = 5``
    (maps: key 1, value 2); ``XEventMetadata.name = 2``, ``.stats = 5``;
    ``XStatMetadata.name = 2``; ``XStat.metadata_id = 1``, ``.uint64_value =
    3``, ``.str_value = 5``, ``.ref_value = 7`` (the id of a stat metadata
    whose name is the string). A plane's lines, which hold the events and
    nearly all the bytes, are skipped whole."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: dict[str, dict[tuple[int, str], str]] = {}

    def entry(buf) -> tuple[int, dict]:
        """A map entry: (key, the value message's fields by number, the
        repeated ``stats`` as a list)."""
        key, message = 0, {}
        for num, value in _fields(buf):
            if num == 1:
                key = value
            elif num == 2:
                for n, v in _fields(value):
                    message.setdefault(n, []).append(v)
        return key, message

    for num, plane in _fields(space):
        if num != 1:
            continue
        name, events, stat_names = "", [], {}
        for n, value in _fields(plane):
            if n == 2:
                name = bytes(value).decode()
            elif n == 4:
                events.append(value)
            elif n == 5:
                key, message = entry(value)
                stat_names[key] = bytes(message[2][0]).decode() \
                    if 2 in message else ""
        if not _DEVICE_PLANE.match(name):
            continue
        found: dict[tuple[int, str], str] = {}
        for buf in events:
            _key, message = entry(buf)
            text, program = "", 0
            for raw in message.get(5, ()):
                fields = dict(_fields(raw))
                stat = stat_names.get(fields.get(1))
                if stat == _SCOPE_STAT:
                    text = bytes(fields[5]).decode() if 5 in fields \
                        else stat_names.get(fields.get(7), "")
                elif stat == _PROGRAM_STAT:
                    program = fields.get(3, 0)
            if text and 2 in message:
                found[program, bytes(message[2][0]).decode()] = _path(text)
        out[name] = found
    return out


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
    paths = _op_paths(path)
    devices = [_reduce_device(name, dev_ops[name], dev_mods.get(name, []),
                              t0, t1, paths.get(name))
               for name in sorted(dev_ops)]
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
