"""Finding 1's evidence, as a count: how many distinct encoder shapes the
ticks of a backlog dispatch through the real connector and engine, on the
default (padded) embedder and on the ragged one. Each distinct shape is a
program the fused ingest compiles; the count says nothing about speed.
PERF.md quotes the count over 50 ticks (a 500k-document backlog, minutes on
the CPU); the test takes the ticks a 60k backlog gives."""

from __future__ import annotations

import tempfile
import time

from benchmark.lib import runner
from benchmark.lib.record import JitLog

from conftest import tiny_cell

TICKS = 50   # at most


def _distinct_shapes(ragged: bool) -> tuple[int, int, int]:
    """(ticks seen, distinct packed shapes, distinct rows-in-tick)."""
    from benchmark.lib.vector_store import System
    from pathway_tpu.internals.parse_graph import G

    G.clear()
    cell = tiny_cell("bge-small-10m.ingest-backlog")
    cell.config["serving"]["ragged"] = ragged
    cell.traffic["backlog"].update(docs=60000)
    cell.traffic["warm"].update(ticks=1, quiet_ticks=0)
    jit = JitLog()
    with tempfile.TemporaryDirectory() as workdir:
        system = System(cell, 5, workdir, log=lambda _m: None)
        try:
            ready = runner.prepare(
                cell, system, seed=5, trace=True, jit=jit, workdir=workdir,
                phases=runner.Phases(time.perf_counter(), lambda _m: None),
                log=lambda _m: None)
            # a span is recorded when its call returns, after the rows show
            while len(ready.spans.get("index.add_batch", ())) < TICKS \
                    and system.counters()["rows"] < ready.total_docs:
                time.sleep(0.05)
        finally:
            system.stop()
    ticks = ready.spans["index.add_batch"][:TICKS]
    t1 = ticks[-1][1]
    shapes = {tuple(shape) for _s, e, meta in ready.spans["pack"] if e <= t1
              for shape in meta["shapes"]}
    return len(ticks), len(shapes), len({m["rows"] for _s, _e, m in ticks})


def test_the_padded_path_meets_a_new_shape_nearly_every_tick():
    ticks, padded_shapes, tick_sizes = _distinct_shapes(ragged=False)
    ragged_ticks, ragged_shapes, _n = _distinct_shapes(ragged=True)
    print(f"\npadded: {ticks} ticks of backlog, rows-in-tick took "
          f"{tick_sizes} distinct values, {padded_shapes} distinct "
          f"fused-ingest shapes; ragged: {ragged_ticks} ticks, "
          f"{ragged_shapes} shapes")
    assert ticks >= 8 and ragged_ticks >= 8
    # the padded path compiles one program per (rows in tick, width), and
    # a connector gives a new number of rows nearly every tick
    assert padded_shapes >= 0.8 * ticks
    # the ragged path has its sequence-count buckets and no more
    assert ragged_shapes <= 6
