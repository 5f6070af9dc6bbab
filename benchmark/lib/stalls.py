"""A stall of the whole process, witnessed in every run.

The server, the load generator and the benchmark share one process and one
interpreter. Now and then all of its threads stand still for seconds (two
query runs of PR 27's 55, four ingest runs of PR 25's and 26's 65; PERF.md
section 7): the generator sends late, the tails jump, and a document written
just before is not retrievable in time, which ``correct`` refuses as it
must. An untraced run had nothing that said *what* stood still. This thread
does: it wakes ten times a second, and where a wake-up comes half a second
late or more, it says what the process, each thread of the interpreter and
the machine did meanwhile:

- one thread, or the process, on a CPU for about the whole gap: a thread
  held the interpreter (a call into C over millions of keys, a collection);
- threads runnable and waiting for a CPU, or cores stolen: the machine,
  busy with others;
- next to nothing of the process on a CPU: it was blocked in the kernel, or
  the machine itself stood still, which its own accounting tells apart
  where it can be read (idle time included, a running machine accounts a
  core-second a core a second).

A wake-up costs two clock reads, one line of ``/proc/stat`` and one small
file a thread of the interpreter (``/proc/self/task/<tid>/schedstat``:
nanoseconds on a CPU, nanoseconds runnable and waiting for one). The
sandbox of the chip's machine hands out neither file's numbers (zeros, no
file): there the process's own CPU time is the witness, and it suffices to
tell a thread that held the interpreter from a process that did not run.
"""

from __future__ import annotations

import os
import threading
import time

_TICKS = os.sysconf("SC_CLK_TCK")
_CORES = os.cpu_count() or 1


def _thread_times() -> dict[int, tuple[str, float, float]]:
    """native id -> (name, seconds on a CPU, seconds waiting for one) of
    the interpreter's threads; a thread that ends meanwhile, or a kernel
    without the file, is left out."""
    out = {}
    for t in threading.enumerate():
        try:
            with open(f"/proc/self/task/{t.native_id}/schedstat") as f:
                ran, waited = f.read().split()[:2]
        except (OSError, ValueError):
            continue
        out[t.native_id] = (t.name, int(ran) / 1e9, int(waited) / 1e9)
    return out


def _machine_times() -> tuple[float, float, float, float]:
    """Core-seconds of the whole machine since it started: busy (all but
    idle and waiting for I/O), waiting for I/O, stolen by the host, and in
    any state at all (a running machine accounts one a core a second)."""
    try:
        with open("/proc/stat") as f:
            v = [int(x) / _TICKS for x in f.readline().split()[1:9]]
    except (OSError, ValueError):
        return 0.0, 0.0, 0.0, 0.0
    v += [0.0] * (8 - len(v))
    return sum(v) - v[3] - v[4], v[4], v[7], sum(v)


def _snapshot() -> tuple:
    return (time.perf_counter(), time.process_time(), _thread_times(),
            _machine_times())


def describe(before: tuple, after: tuple) -> dict:
    """What happened between two snapshots that lie a stall apart."""
    gap = after[0] - before[0]
    threads = []
    for tid, (name, ran, waited) in after[2].items():
        _n, ran0, waited0 = before[2].get(tid, (name, 0.0, 0.0))
        threads.append([name, ran - ran0, waited - waited0])
    threads.sort(key=lambda t: -t[1])
    busy, iowait, steal, total = (a - b for a, b in zip(after[3], before[3]))
    process_cpu = after[1] - before[1]
    top = threads[0] if threads else ["", 0.0, 0.0]
    if top[1] >= 0.7 * gap:
        verdict = f"thread {top[0]} was on a CPU {top[1]:.2f}s of it: it " \
                  f"held the interpreter"
    elif process_cpu >= 0.7 * gap:
        verdict = f"the process was on a CPU {process_cpu:.2f}s of it: one " \
                  f"of its threads held the interpreter"
    elif max((t[2] for t in threads), default=0.0) >= 0.5 * gap \
            or steal >= 0.5 * gap:
        verdict = "threads were runnable and got no CPU: the machine"
    elif process_cpu >= 0.2 * gap:
        verdict = f"the process used {process_cpu:.2f}s of CPU, the " \
                  f"interpreter's threads {sum(t[1] for t in threads):.2f}s"
    elif not after[3][3]:
        verdict = "next to nothing of the process ran: blocked in the " \
                  "kernel, or the machine stood still (its accounting " \
                  "cannot be read here)"
    elif total < 0.25 * _CORES * gap:
        verdict = f"nothing ran: the machine's {_CORES} cores accounted " \
                  f"{total:.2f} of the {_CORES * gap:.1f} core-seconds a " \
                  f"running machine does: the machine itself stood still"
    else:
        verdict = "next to nothing of the process ran on a machine that " \
                  "went on: blocked in the kernel"
    return {"at": before[0], "gap_s": gap, "verdict": verdict,
            "process_cpu_s": process_cpu,
            "threads": [t for t in threads[:3] if t[1] + t[2] > 0],
            "machine_core_s": {"busy": busy, "iowait": iowait,
                               "steal": steal, "any_state": total}}


class StallWatch(threading.Thread):
    """From :meth:`start` to :meth:`stop`; ``stalls`` then holds
    :func:`describe` of every wake-up that came ``least_s`` late or more,
    ``at`` in ``time.perf_counter()`` seconds."""

    def __init__(self, interval_s: float = 0.1, least_s: float = 0.5):
        super().__init__(daemon=True, name="bench-stall-watch")
        self.interval_s = interval_s
        self.least_s = least_s
        self.stalls: list[dict] = []
        self._halt = threading.Event()

    def run(self) -> None:
        before = _snapshot()
        while not self._halt.wait(self.interval_s):
            after = _snapshot()
            if after[0] - before[0] - self.interval_s >= self.least_s:
                self.stalls.append(describe(before, after))
            before = after

    def stop(self) -> None:
        self._halt.set()
        self.join(timeout=5.0)
