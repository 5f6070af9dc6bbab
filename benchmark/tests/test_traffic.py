from __future__ import annotations

import asyncio
import os
import threading
import time

import numpy as np
import pytest

from benchmark.lib import traffic
from benchmark.lib.loadgen import OpenLoop
from benchmark.lib.traffic import Event

WORDS = {"dist": "geometric", "mean": 56, "shift": 2, "min": 3, "max": 120}
MIX = {
    "vocab_words": 4096,
    "queries": {"arrivals": {"process": "poisson", "rate_per_s": 20},
                "words": {"dist": "uniform", "min": 3, "max": 12}, "k": 3},
    "documents": {"arrivals": {"process": "even", "rate_per_s": 4},
                  "words": WORDS, "read_your_write": True},
}
CORPUS = traffic.make_texts(np.random.default_rng(1),
                            np.full(50, 30), 4096)


def test_lengths_follow_the_stated_distribution():
    lens = traffic.draw_lengths(np.random.default_rng(0), WORDS, 200_000)
    assert lens.min() == 3 and lens.max() == 120
    assert 50 < lens.mean() < 58            # mean 56 + 2, clipped at 120
    assert (lens == 120).mean() > 0.05      # the heavy tail is there
    uni = traffic.draw_lengths(np.random.default_rng(0),
                               {"dist": "uniform", "min": 3, "max": 12}, 10000)
    assert set(uni.tolist()) == set(range(3, 13))


def test_the_same_seed_gives_the_same_traffic_and_another_seed_other():
    a = traffic.open_loop_schedule(MIX, 7, 30.0, CORPUS, 1.0)
    b = traffic.open_loop_schedule(MIX, 7, 30.0, CORPUS, 1.0)
    c = traffic.open_loop_schedule(MIX, 8, 30.0, CORPUS, 1.0)
    assert a == b and a != c
    assert [e.due for e in a] == sorted(e.due for e in a)


def test_read_your_write_queries_are_among_the_stated_rate():
    events = traffic.open_loop_schedule(MIX, 3, 200.0, CORPUS, 1.0)
    writes = [e for e in events if e.kind == "write"]
    queries = [e for e in events if e.kind == "query"]
    ryw = [e for e in queries if e.doc is not None]
    # 4 documents/s at even 250 ms spacing
    assert len(writes) == 800
    assert np.allclose(np.diff([e.due for e in writes]), 0.25)
    # each followed visible_within_s later by a query for its own text
    by_doc = {e.doc: e for e in writes}
    assert all(q.due == pytest.approx(by_doc[q.doc].due + 1.0)
               and q.text == by_doc[q.doc].text for q in ryw)
    # 20 queries/s in all: 16/s Poisson + the 4/s read-your-write
    assert len(queries) / 200.0 == pytest.approx(20.0, rel=0.05)
    # the others are 3-12 word spans of indexed documents
    for q in queries:
        if q.doc is None:
            assert 3 <= len(q.text.split()) <= 12
            assert any(q.text in doc for doc in CORPUS)


def test_poisson_gaps_are_exponential_and_even_gaps_equal():
    rng = np.random.default_rng(0)
    due = traffic.arrival_times(rng, {"process": "poisson",
                                      "rate_per_s": 50}, 0.0, 400.0)
    gaps = np.diff(due)
    assert gaps.mean() == pytest.approx(0.02, rel=0.03)
    assert gaps.std() == pytest.approx(0.02, rel=0.05)   # CV 1: not even
    even = traffic.arrival_times(rng, {"process": "even", "rate_per_s": 4},
                                 0.0, 10.0)
    assert len(even) == 40 and np.allclose(np.diff(even), 0.25)


def _slow_server(service_s: float):
    """An HTTP server that answers /v1/retrieve one request at a time, each
    after ``service_s``; returns (port, stop)."""
    from aiohttp import web

    started = threading.Event()
    state = {}

    async def main():
        lock = asyncio.Lock()

        async def handle(request):
            await request.read()
            async with lock:
                await asyncio.sleep(service_s)
            return web.json_response(
                [{"text": "t", "metadata": {"path": "/x/a.txt"},
                  "dist": 0.0}])

        app = web.Application()
        app.router.add_post("/v1/retrieve", handle)
        runner = web.AppRunner(app)
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        state["port"] = site._server.sockets[0].getsockname()[1]
        state["stop"] = asyncio.Event()
        started.set()
        await state["stop"].wait()
        await runner.cleanup()

    loop = asyncio.new_event_loop()
    thread = threading.Thread(target=loop.run_until_complete, args=(main(),),
                              daemon=True)
    thread.start()
    assert started.wait(10)

    def stop():
        loop.call_soon_threadsafe(state["stop"].set)
        thread.join(10)
        assert not thread.is_alive()

    return state["port"], stop


def test_open_loop_times_from_the_due_instant_and_never_waits(tmp_path):
    """Five queries due 10 ms apart against a server that takes 50 ms each,
    one at a time: an open loop sends all five on schedule, and each pays
    the queue in front of it — the last about 250 ms from when it was due,
    not the 50 ms a closed loop would report."""
    port, stop = _slow_server(0.05)
    live, stage = tmp_path / "live", tmp_path / "stage"
    live.mkdir(), stage.mkdir()
    events = [Event(0.01 * i, "query", f"q{i}", k=3) for i in range(5)]
    events.append(Event(0.02, "write", "hello", doc="w.txt"))
    events.sort(key=lambda e: e.due)
    origin = time.perf_counter() + 0.2
    try:
        gen = OpenLoop(f"http://127.0.0.1:{port}", events, origin,
                       str(live), str(stage))
        gen.start()
        gen.join()
    finally:
        stop()
    queries = [r for r in gen.results if r.event.kind == "query"]
    assert [r.error for r in queries] == [None] * 5
    assert all(r.hits == ("a.txt",) for r in queries)
    for i, r in enumerate(queries):
        assert r.due == pytest.approx(origin + 0.01 * i)
        assert 0 <= r.sent - r.due < 0.02          # sent on schedule
        served = (r.done - r.due) * 1e3
        assert served >= 50 * (i + 1) - 10 * i - 5  # the queue is counted
    assert (queries[-1].done - queries[-1].due) > 0.2
    assert (live / "w.txt").read_text() == "hello" and not os.listdir(stage)
