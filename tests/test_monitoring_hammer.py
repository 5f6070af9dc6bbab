"""Monitoring-hammer regression test: N threads pounding ``/metrics`` +
``/status`` while ingest grows the paged store and the device bridge
pipelines at inflight=4.

Pins the PR-7 race class — ``DevicePagePool.stats()`` iterating allocator
dicts while the ingest path mutates them (RuntimeError: dictionary changed
size during iteration) — so it cannot recur: the monitoring threads read
the same live pool registry ``/metrics`` reads in production, through the
same HTTP server, while the main thread churns adds/removes through the
index lock and the bridge worker retires legs concurrently.
"""

from __future__ import annotations

import threading
import urllib.request

import numpy as np

from pathway_tpu.engine.device_bridge import DeviceBridge
from pathway_tpu.engine.http_server import MonitoringHttpServer
from pathway_tpu.internals.keys import Pointer

N_HAMMER_THREADS = 6
N_INGEST_BATCHES = 60
BATCH_ROWS = 96
DIM = 16


class _Node:
    def __init__(self, id, name):
        self.id = id
        self.name = name
        self.op = object()
        self.trace = None


class _Runtime:
    """The minimal runtime surface MonitoringHttpServer reads, wired to a
    REAL flight recorder and a REAL device bridge (the fake parts are only
    the graph shell)."""

    def __init__(self, bridge):
        from pathway_tpu.engine.flight_recorder import FlightRecorder

        class Sched:
            stats = {0: {"insertions": 0, "retractions": 0}}
            recorder = FlightRecorder()
            _bridge = bridge

            def bridge_stats(self):
                return bridge.stats()

        class Graph:
            nodes = [_Node(0, "ingest")]

        class Runner:
            graph = Graph()

        self.scheduler = Sched()
        self.runner = Runner()
        self.sessions = []


def test_monitoring_hammer_under_paged_ingest_and_pipelining():
    from pathway_tpu.ops.knn import BruteForceKnnIndex

    rng = np.random.default_rng(7)
    # the race class under test lives in the page allocator's dict
    # iteration
    index = BruteForceKnnIndex(dimensions=DIM, reserved_space=256,
                               page_rows=128)
    bridge = DeviceBridge(max_inflight=4, name="hammer-bridge")
    server = MonitoringHttpServer(_Runtime(bridge), port=0)
    server.start()
    stop = threading.Event()
    failures: list[BaseException] = []
    statuses: list[int] = []

    def hammer():
        base = f"http://127.0.0.1:{server.port}"
        while not stop.is_set():
            for path in ("/status", "/metrics", "/healthz"):
                try:
                    with urllib.request.urlopen(base + path,
                                                timeout=10) as resp:
                        statuses.append(resp.status)
                        resp.read()
                except Exception as e:  # noqa: BLE001 — collected, asserted
                    failures.append(e)
                    return

    threads = [threading.Thread(target=hammer, daemon=True,
                                name=f"hammer-{i}")
               for i in range(N_HAMMER_THREADS)]
    for t in threads:
        t.start()

    try:
        # ingest on the "commit loop" (this thread), device legs on the
        # bridge worker at inflight=4 — the two mutate the index/pool
        # while the hammer threads iterate its stats
        for batch in range(N_INGEST_BATCHES):
            keys = [Pointer(batch * BATCH_ROWS + i)
                    for i in range(BATCH_ROWS)]
            vecs = rng.standard_normal((BATCH_ROWS, DIM)).astype(
                np.float32)
            index.add_batch(keys, vecs)
            if batch % 3 == 2:
                # churn: free a third of the previous batch so pages
                # cycle through the free list, not just grow
                for k in keys[::3]:
                    index.remove(k)
            bridge.submit(batch + 1,
                          lambda n=len(keys): index.page_stats())
        bridge.barrier()
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10)
        bridge.close()
        server.stop()

    assert not failures, f"monitoring endpoint crashed under load: " \
                         f"{failures[:3]}"
    assert statuses, "hammer threads never completed a request"
    assert set(statuses) <= {200, 503}  # healthz may report degraded
    # the scenario actually exercised what it claims: growth happened and
    # the bridge pipelined
    st = index.page_stats()
    assert st["grow_events"] >= 1
    bs = bridge.stats()
    assert bs["legs_resolved"] == N_INGEST_BATCHES
    assert bs["max_depth"] >= 2
