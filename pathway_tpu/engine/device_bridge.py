"""Asynchronous device bridge: overlap host and device work across ticks.

The scheduler is bulk-synchronous per tick: with one thread, tokenization /
routing / pure-Python operators for tick t+1 cannot start until tick t's
encoder forward, slab scatter and top-k materialization have retired — the
TPU idles during host work and the host idles during device work (the
``framework_docs_per_s`` vs raw-kernel ``docs_per_s`` gap in bench.py).
WindVE (arxiv 2504.14941) shows a queue between the CPU stage and the
accelerator stage roughly doubles embedding throughput at equal hardware;
this module is that queue for the microbatch engine.

Model: each tick's *device leg* — the downstream closure of every
device-bound operator, stepped in topological order — is submitted as one
FIFO job ("leg") to a single worker thread. The host thread immediately
proceeds to the next tick's host-side work. Because legs are executed
strictly in tick order by one worker, every operator still observes its
ticks in order and per-tick consistency is unchanged; the overlap is purely
between tick t's device leg and tick t+1..t+K's host legs.

Guarantees:

- **Bounded in-flight window**: at most ``max_inflight`` legs (queued +
  running) exist at any moment; ``submit`` blocks (backpressure) when the
  window is full, so a slow device cannot be out-run by the host.
- **Hard barrier**: ``barrier()`` returns only when every submitted leg has
  resolved. Callers place it before anything that externalizes state —
  end-of-stream flush and reading a tick's outputs.
- **Resolved-prefix watermark**: because legs retire strictly in tick
  order, the tick of the last resolved leg is the longest *resolved
  prefix* of submitted work. ``resolved_watermark()`` exposes it as a
  monotone counter; a failed leg freezes it (the failed tick never
  enters the prefix). Persistence commits *up to the watermark* instead
  of draining the bridge (engine/streaming.py), so checkpoints trail the
  pipeline without collapsing it to depth 1.
- **Error propagation**: a leg that raises poisons the bridge; the pending
  queue is dropped (later ticks must not run on top of a failed one) and
  the *original* exception re-raises on the host thread at the next
  ``submit``/``barrier``, so user ``except`` clauses still match exactly as
  they do in synchronous mode.

The window is configured with ``PATHWAY_DEVICE_INFLIGHT`` (default 2 —
double buffering; ``1`` disables pipelining entirely).
"""

from __future__ import annotations

import os
import threading
import time as _time
from collections import deque
from typing import Callable

from pathway_tpu.engine.profiler import current_profiler
from pathway_tpu.testing import faults

# the running leg's [dispatches, token slots, real tokens, documents] of the
# fixed-shape ingest dispatches it made: the bridge worker's thread alone
# has one
_LEG = threading.local()


def note_ingest_dispatch(slots: int, tokens: int, docs: int) -> None:
    """Hook for the index's ingest (ops/knn.py ``add_batch``): one
    fixed-shape dispatch of ``slots`` token slots went to the device,
    ``tokens`` of them real (the rest padding), of ``docs`` documents. The
    leg that runs on this thread counts it and the bridge sums it when the
    leg retires (:meth:`DeviceBridge.stats`), which is how the ingest
    budget learns the documents that fill a dispatch (engine/qos.py
    ``DeviceBackpressure``). Ingest alone reports: a query's text goes
    through the same embedder, and ten tokens are no document. No-op off a
    bridge leg (one thread-local read)."""
    counts = getattr(_LEG, "counts", None)
    if counts is not None:
        counts[0] += 1
        counts[1] += slots
        counts[2] += tokens
        counts[3] += docs


def device_inflight_from_env() -> int:
    """The configured in-flight window (>=1); 1 means synchronous."""
    raw = os.environ.get("PATHWAY_DEVICE_INFLIGHT", "2")
    try:
        return max(1, int(raw))
    except ValueError:
        return 2


class DeviceBridge:
    """FIFO dispatch queue for per-tick device legs (see module doc)."""

    def __init__(self, max_inflight: int = 2, name: str = "device-bridge",
                 recorder=None):
        self.max_inflight = max(1, int(max_inflight))
        self.name = name
        # flight recorder (engine/flight_recorder.py): the ``bridge.wait``
        # and ``bridge.leg`` spans of every resolved leg and the in-flight
        # marker for post-mortems
        self.recorder = recorder
        self._current: tuple | None = None  # (tick, started_monotonic)
        # longest resolved prefix of submitted legs: the tick of the last
        # leg that retired cleanly (FIFO worker => strictly tick-ordered
        # resolution). 0 = nothing resolved yet; frozen on leg failure.
        self._watermark = 0
        # observer fired (outside the lock, on the worker thread) after
        # every watermark advance — the streaming runtime stamps commit
        # loop progress here so a slow-but-advancing device never reads
        # as a commit stall
        self.on_advance: Callable[[int], None] | None = None
        from pathway_tpu.engine.locking import create_condition

        self._cv = create_condition("DeviceBridge._cv")
        self._queue: deque = deque()  # (tick, fn, submitted_at)
        self._running = False
        self._error: BaseException | None = None
        self._closed = False
        self._thread: threading.Thread | None = None
        self._waiters = 0  # host threads blocked in submit/barrier
        # -- instrumentation (read via stats(); exported on /metrics) ------
        self.legs_dispatched = 0
        # submits that found the window full and had to wait: the device
        # (the legs' side) was slower than the host just then
        self.submits_blocked = 0
        self.legs_resolved = 0
        # legs that finished with no host thread waiting on the bridge at
        # any point of their execution: fully overlapped with host work
        self.legs_overlapped = 0
        self.queue_wait_ms = 0.0  # submit -> start, summed
        self.exec_ms = 0.0        # start -> finish, summed
        # what the resolved legs reported through ``note_ingest_dispatch``,
        # summed with ``exec_ms`` so that both are of the same legs
        # [dispatches, token slots, real tokens, documents]
        self._ingest = [0, 0, 0, 0]
        self.max_depth = 0

    # ------------------------------------------------------------------
    def depth(self) -> int:
        with self._cv:
            return len(self._queue) + (1 if self._running else 0)

    def submit(self, tick: int, fn: Callable[[], None]) -> None:
        """Enqueue one tick's device leg; blocks while the window is full.

        Raises the stored leg exception, if any — the host thread is the
        one that must observe device failures.
        """
        from pathway_tpu.engine.locking import assert_unlocked

        # submit blocks behind a full in-flight window: entering with an
        # engine lock held would stall every contender on a slow device
        assert_unlocked("DeviceBridge.submit")
        with self._cv:
            self._raise_if_error()
            if self._closed:
                raise RuntimeError("device bridge is closed")
            if self._thread is None:
                from pathway_tpu.engine.threads import spawn

                self._thread = spawn(self._work, name=self.name)
            if (len(self._queue) + (1 if self._running else 0)
                    >= self.max_inflight):
                self.submits_blocked += 1
            while (len(self._queue) + (1 if self._running else 0)
                   >= self.max_inflight):
                self._waiters += 1
                try:
                    self._cv.wait()
                finally:
                    self._waiters -= 1
                self._raise_if_error()
            self._queue.append((tick, fn, _time.perf_counter()))
            self.legs_dispatched += 1
            depth = len(self._queue) + (1 if self._running else 0)
            if depth > self.max_depth:
                self.max_depth = depth
            self._cv.notify_all()

    def barrier(self) -> None:
        """Block until every submitted leg has resolved; re-raise a leg
        failure. This is the hard consistency point before commits,
        flushes and output reads."""
        from pathway_tpu.engine.locking import assert_unlocked

        assert_unlocked("DeviceBridge.barrier")
        with self._cv:
            while (self._queue or self._running) and self._error is None:
                self._waiters += 1
                try:
                    self._cv.wait()
                finally:
                    self._waiters -= 1
            self._raise_if_error()

    def close(self, join_timeout: float = 10.0) -> None:
        """Drain remaining legs and stop the worker. Leg errors are NOT
        raised here (close runs in ``finally`` paths; errors surface via
        submit/barrier) — but they stay stored for a later barrier."""
        with self._cv:
            self._closed = True
            self._cv.notify_all()
            thread = self._thread
        if thread is not None:
            thread.join(join_timeout)

    def inflight(self) -> dict | None:
        """The leg currently executing: tick + seconds since it started
        (None when idle). The operator-level detail lives on the attached
        flight recorder; this survives even with recording off, so bench's
        hang paths can always report seconds-since-dispatch."""
        cur = self._current
        if cur is None:
            return None
        return {"tick": cur[0],
                "since_s": round(_time.monotonic() - cur[1], 3)}

    def wait_watermark(self, tick: int) -> int:
        """Block until the resolved watermark reaches ``tick``; re-raise a
        leg failure. Unlike :meth:`barrier` this does NOT wait for the
        queue to drain — it waits only for the durability frontier, and
        returns the (possibly short) frontier when the bridge goes idle
        or closed without reaching ``tick`` (callers treat < tick as
        'no consistent cut available — skip'). The snapshot pass is the
        caller: at cadence ticks the host thread has just submitted leg
        ``tick`` and submits nothing more until this returns, so reaching
        the watermark means every operator sits exactly at ``tick``."""
        from pathway_tpu.engine.locking import assert_unlocked

        assert_unlocked("DeviceBridge.wait_watermark")
        with self._cv:
            while self._watermark < tick and self._error is None:
                if not self._queue and not self._running:
                    break  # idle/closed: nothing left to advance it
                self._waiters += 1
                try:
                    self._cv.wait()
                finally:
                    self._waiters -= 1
            self._raise_if_error()
            return self._watermark

    def resolved_watermark(self) -> int:
        """Tick of the longest fully-resolved prefix of submitted legs
        (monotone; 0 before anything resolved). Every leg with tick <=
        the watermark has retired cleanly — the durability frontier the
        persistence commit loop trails."""
        with self._cv:
            return self._watermark

    def error(self) -> BaseException | None:
        """The stored leg failure, if any (without raising). Lets teardown
        paths that must not raise mid-cleanup (Scheduler.close → drain)
        still surface the failure afterwards."""
        with self._cv:
            return self._error

    def stats(self) -> dict:
        with self._cv:
            resolved = self.legs_resolved
            return {
                "max_inflight": self.max_inflight,
                "depth": len(self._queue) + (1 if self._running else 0),
                "resolved_watermark": self._watermark,
                "legs_dispatched": self.legs_dispatched,
                "submits_blocked": self.submits_blocked,
                "legs_resolved": resolved,
                "legs_overlapped": self.legs_overlapped,
                "overlap_ratio": (self.legs_overlapped / resolved
                                  if resolved else 0.0),
                "queue_wait_ms": round(self.queue_wait_ms, 3),
                "exec_ms": round(self.exec_ms, 3),
                "ingest_dispatches": self._ingest[0],
                "ingest_slots": self._ingest[1],
                "ingest_tokens": self._ingest[2],
                "ingest_docs": self._ingest[3],
                "max_depth": self.max_depth,
            }

    # ------------------------------------------------------------------
    def _raise_if_error(self) -> None:
        if self._error is not None:
            raise self._error

    def _work(self) -> None:
        while True:
            with self._cv:
                while not self._queue and not self._closed:
                    self._cv.wait()
                if not self._queue:  # closed and drained
                    self._running = False
                    self._cv.notify_all()
                    return
                tick, fn, submitted_at = self._queue.popleft()
                self._running = True
                self._current = (tick, _time.monotonic())
                # a host thread already blocked on us? then this leg is
                # (at least partially) serialized with host work
                waited_at_start = self._waiters > 0
                # legs in the window as this one starts, itself included
                depth = len(self._queue) + 1
            rec = self.recorder
            recording = rec is not None and rec.enabled
            if recording:
                rec.mark_leg(tick)
            # profiler leg context: kernel dispatches recorded while fn()
            # runs are buffered on this thread and re-timed to the leg's
            # MEASURED execute span at end_leg — the cost model's device
            # time comes from here, not from async call-site walls
            prof = current_profiler()
            if prof is not None:
                prof.begin_leg(tick)
            counts = _LEG.counts = [0, 0, 0, 0]
            started = _time.perf_counter()
            try:
                # fault points at the new watermark boundaries
                # (testing/faults.py): ``exec`` injects a device-leg
                # failure; ``resolved`` injects a crash between the leg's
                # work retiring and the watermark advancing — work done
                # but the durability frontier frozen, the edge the
                # crash-sweep suite must cover
                faults.hit("bridge.leg.exec", tick=tick)
                fn()
                faults.hit("bridge.leg.resolved", tick=tick)
            except BaseException as e:  # noqa: BLE001 — must cross threads
                if recording:
                    # poison carries the flight-recorder tail: the host
                    # thread re-raises this exact object, so the next
                    # "device leg failed" report names operator + frame
                    from pathway_tpu.engine.flight_recorder import \
                        attach_note

                    tail = rec.dump_tail()
                    if tail:
                        attach_note(
                            e, f"device leg poisoned at tick {tick}; "
                               f"flight recorder tail:\n{tail}")
                if prof is not None:
                    prof.end_leg(None)  # failed leg: no measured time
                with self._cv:
                    self._error = e
                    self._running = False
                    self._current = None
                    # later ticks must not execute on top of a failed one
                    self._queue.clear()
                    self._cv.notify_all()
                continue  # keep serving barrier wake-ups until close
            finished = _time.perf_counter()
            if prof is not None:
                prof.end_leg((finished - started) * 1e3)
            if recording:
                cause = ("tick", tick)
                rec.span("bridge.wait", submitted_at, started, cause,
                         depth=depth)
                rec.span("bridge.leg", started, finished, cause)
                rec.clear_leg()
            with self._cv:
                self.queue_wait_ms += (started - submitted_at) * 1e3
                self.exec_ms += (finished - started) * 1e3
                self._ingest = [a + b for a, b in zip(self._ingest, counts)]
                self.legs_resolved += 1
                if not waited_at_start and self._waiters == 0:
                    self.legs_overlapped += 1
                # legs resolve strictly in tick order, so this leg's tick
                # IS the longest resolved prefix
                self._watermark = tick
                self._running = False
                self._current = None
                self._cv.notify_all()
            on_advance = self.on_advance
            if on_advance is not None:
                try:
                    on_advance(tick)
                except Exception:  # observer must never poison the bridge
                    pass
