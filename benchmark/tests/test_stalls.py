"""The witness of a stall: a thread that holds the interpreter through one
long call into C is caught and named; a quiet process reports nothing."""

from __future__ import annotations

import threading
import time

from benchmark.lib import stalls


def _hold_the_interpreter(seconds: float) -> None:
    """One call into C that never lets go of the interpreter."""
    t0 = time.perf_counter()
    sum(range(2_000_000))
    per_item = (time.perf_counter() - t0) / 2_000_000
    sum(range(int(seconds / per_item)))


def test_a_thread_that_holds_the_interpreter_is_named():
    watch = stalls.StallWatch(interval_s=0.05, least_s=0.3)
    watch.start()
    time.sleep(0.3)
    assert watch.stalls == []
    holder = threading.Thread(target=_hold_the_interpreter, args=(1.2,),
                              name="holder")
    t0 = time.perf_counter()
    holder.start()
    holder.join()
    time.sleep(0.2)
    watch.stop()
    assert len(watch.stalls) == 1
    (stall,) = watch.stalls
    assert t0 - 0.1 <= stall["at"] <= t0 + 0.3
    assert 0.5 < stall["gap_s"] < 5.0
    assert stall["threads"][0][0] == "holder"
    assert stall["threads"][0][1] >= 0.7 * stall["gap_s"]
    assert "thread holder" in stall["verdict"]
    assert "held the interpreter" in stall["verdict"]


def test_what_a_gap_is_put_down_to():
    cores = stalls._CORES

    def snap(t, cpu, threads, busy=0.0, steal=0.0):
        # a machine that goes on accounts a core-second a core a second
        return (t, cpu, threads, (busy, 0.0, steal, cores * t))

    idle = {1: ("commit", 5.0, 1.0), 2: ("src-fs-0", 9.0, 2.0)}
    starved = {1: ("commit", 5.0, 3.4), 2: ("src-fs-0", 9.05, 2.1)}
    got = stalls.describe(snap(10.0, 3.0, idle), snap(12.5, 3.05, starved))
    assert got["gap_s"] == 2.5 and "got no CPU" in got["verdict"]
    assert got["threads"][0][0] == "src-fs-0"
    got = stalls.describe(snap(10.0, 3.0, idle), snap(12.5, 3.02, idle))
    assert "went on: blocked in the kernel" in got["verdict"]
    assert got["threads"] == []
    got = stalls.describe(snap(10.0, 3.0, idle), snap(12.5, 4.0, idle))
    assert "the process used 1.00s of CPU" in got["verdict"]
    stolen = stalls.describe(snap(10.0, 3.0, idle),
                             snap(12.5, 3.0, idle, busy=1.0, steal=2.0))
    assert "got no CPU" in stolen["verdict"]
    assert stolen["machine_core_s"]["steal"] == 2.0
    # a machine whose own accounting stood still, idle time included
    paused = stalls.describe((10.0, 3.0, idle, (50.0, 1.0, 0.0, 400.0)),
                             (12.5, 3.25, idle, (50.0, 1.0, 0.0, 400.02)))
    assert "the machine itself stood still" in paused["verdict"]
    # the chip's machine (PR 27): no thread's times, zeros for the machine
    blind = ((10.0, 3.0, {}, (0.0,) * 4), (12.5, 3.25, {}, (0.0,) * 4))
    assert "cannot be read here" in stalls.describe(*blind)["verdict"]
    spun = stalls.describe(blind[0], (12.5, 5.4, {}, (0.0,) * 4))
    assert "the process was on a CPU 2.40s" in spun["verdict"]
    assert "held the interpreter" in spun["verdict"]
