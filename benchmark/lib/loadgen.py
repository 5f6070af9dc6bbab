"""The open-loop load generator: one thread, one event loop.

Every event of a schedule (:mod:`benchmark.lib.traffic`) is sent when it is
due, whether or not earlier requests have come back, and a query is timed
from the instant it was *due*: a stall in the server then costs every
request queued behind it, and one in the generator is not read as a fast
server. How late each request left (sent - due) is kept beside its latency.
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import os
import threading
import time

import aiohttp

from benchmark.lib.traffic import Event


@dataclasses.dataclass
class Sent:
    """What became of one event. Times are ``time.perf_counter()`` seconds;
    ``done`` is when the whole response had been read. ``hits`` are the base
    names of the files returned, best first; ``error`` is set where the
    request failed (any status but 200 included)."""

    event: Event
    due: float
    sent: float = 0.0
    done: float = 0.0
    hits: tuple[str, ...] = ()
    error: str | None = None


class OpenLoop:
    """Runs ``events`` against ``base_url`` from ``origin`` (a
    ``perf_counter`` instant) on a thread of its own. Writes land in
    ``live_dir`` by rename from ``stage_dir``, so that the connector never
    reads half a file."""

    def __init__(self, base_url: str, events: list[Event], origin: float,
                 live_dir: str, stage_dir: str, timeout_s: float = 60.0):
        self.base_url = base_url
        self.events = events
        self.origin = origin
        self.live_dir = live_dir
        self.stage_dir = stage_dir
        self.timeout_s = timeout_s
        self.results: list[Sent] = []
        self.failure: BaseException | None = None
        self._thread = threading.Thread(target=self._main, daemon=True,
                                        name="bench-loadgen")

    def start(self) -> None:
        self._thread.start()

    def join(self) -> None:
        """Wait for the last response; re-raise what stopped the loop."""
        last = self.events[-1].due if self.events else 0.0
        self._thread.join(timeout=max(0.0, self.origin + last
                                      - time.perf_counter())
                          + self.timeout_s + 30.0)
        if self._thread.is_alive():
            raise TimeoutError("the load generator did not finish")
        if self.failure is not None:
            raise self.failure

    def _main(self) -> None:
        try:
            asyncio.run(self._run())
        except BaseException as e:  # noqa: BLE001 — handed to join()
            self.failure = e

    async def _run(self) -> None:
        timeout = aiohttp.ClientTimeout(total=self.timeout_s)
        connector = aiohttp.TCPConnector(limit=0)
        async with aiohttp.ClientSession(timeout=timeout,
                                         connector=connector) as session:
            tasks = []
            for ev in self.events:
                due = self.origin + ev.due
                # always yield: behind schedule, the requests already made
                # must still get to leave
                await asyncio.sleep(max(0.0, due - time.perf_counter()))
                rec = Sent(ev, due)
                self.results.append(rec)
                if ev.kind == "write":
                    self._write(rec)
                else:
                    tasks.append(asyncio.ensure_future(
                        self._query(session, rec)))
            if tasks:
                await asyncio.gather(*tasks)

    def _write(self, rec: Sent) -> None:
        rec.sent = time.perf_counter()
        tmp = os.path.join(self.stage_dir, rec.event.doc)
        with open(tmp, "w") as f:
            f.write(rec.event.text)
        os.rename(tmp, os.path.join(self.live_dir, rec.event.doc))
        rec.done = time.perf_counter()

    async def _query(self, session, rec: Sent) -> None:
        body = json.dumps({"query": rec.event.text, "k": rec.event.k,
                           "metadata_filter": None,
                           "filepath_globpattern": None}).encode()
        rec.sent = time.perf_counter()
        try:
            async with session.post(
                    self.base_url + "/v1/retrieve", data=body,
                    headers={"Content-Type": "application/json"}) as resp:
                raw = await resp.read()
                rec.done = time.perf_counter()
                if resp.status != 200:
                    rec.error = f"HTTP {resp.status}: {raw[:200]!r}"
                    return
            hits = json.loads(raw)
            rec.hits = tuple(os.path.basename(h["metadata"]["path"])
                             for h in hits)
        except (aiohttp.ClientError, asyncio.TimeoutError, ValueError,
                KeyError, TypeError) as e:
            rec.done = time.perf_counter()
            rec.error = f"{type(e).__name__}: {e}"

