"""What the host's threads were doing, sampled during the traced window.

The program has no spans of its own inside a tick yet, so the benchmark
cannot name a device idle gap by a program span. It samples instead: every
few milliseconds, for each thread that is not blocked in a wait, the
innermost frame that lies in the program or the benchmark. A reading is
(time.perf_counter(), label); the trace reducer puts the readings on the
profile's clock and names each gap by the label seen in it.
"""

from __future__ import annotations

import os
import sys
import threading
import time

# innermost frames that mean "blocked, holding nothing": a thread parked
# there is not what keeps the device waiting
_WAIT_FILES = ("threading.py", "selectors.py", "queue.py", "socket.py",
               "ssl.py", "subprocess.py")
_OWN = (os.sep + "pathway_tpu" + os.sep, os.sep + "benchmark" + os.sep)


def _label(thread_name: str, frame) -> str | None:
    """``thread:module.function`` of the innermost program frame of a
    running thread, None for a thread parked in a wait."""
    code = frame.f_code
    if os.path.basename(code.co_filename) in _WAIT_FILES:
        return None
    f = frame
    while f is not None:
        name = f.f_code.co_filename
        if any(part in name for part in _OWN):
            # engine/streaming.run, lib/runner._measure: two path parts,
            # since both trees have a runner.py
            module = os.path.splitext(os.sep.join(
                name.split(os.sep)[-2:]))[0]
            return f"{thread_name}:{module}.{f.f_code.co_name}"
        f = f.f_back
    return None


class HostSampler:
    """Samples from :meth:`start` to :meth:`stop`; ``samples`` then holds
    (perf_counter seconds, label) with the running threads' labels joined by
    `` + `` (at most two). The sampler's own thread and the one that started
    it (the runner, asleep until the window ends) are left out."""

    def __init__(self, interval_s: float = 0.004):
        self.interval_s = interval_s
        self.samples: list[tuple[float, str]] = []
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._skip: set[int] = set()

    def start(self) -> None:
        self._skip = {threading.get_ident()}
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="bench-host-sampler")
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)

    def _run(self) -> None:
        self._skip.add(threading.get_ident())
        while not self._stop.wait(self.interval_s):
            now = time.perf_counter()
            names = {t.ident: t.name for t in threading.enumerate()}
            running = []
            for ident, frame in sys._current_frames().items():
                if ident in self._skip:
                    continue
                lab = _label(names.get(ident, str(ident)), frame)
                if lab is not None:
                    running.append(lab)
            running.sort()
            label = " + ".join(running[:2]) + (" + ..." if len(running) > 2
                                               else "")
            self.samples.append((now, label or "every thread waiting"))
