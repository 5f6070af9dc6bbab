"""A tick's ingest drain stays bounded while the device is the slower side
(engine/qos.py ``DeviceBackpressure``, the ingest budget of a runtime with
no QoS armed): ``submit`` blocks on a full bridge window, and without a
bound the next drain takes everything the source pushed meanwhile, so ticks
grow until one leg holds the whole backlog."""

from __future__ import annotations

import time as _time

import pytest

import pathway_tpu as pw
from pathway_tpu.engine import qos
from pathway_tpu.engine.device_bridge import DeviceBridge
from pathway_tpu.internals import schema as sch
from pathway_tpu.internals.parse_graph import G
from pathway_tpu.testing.faults import flaky_subject

ROWS = 3000
PER_ROW_S = 0.0005     # the "device": 2,000 rows/s
PUSH_EVERY_S = 0.0001  # the source: several times faster


@pytest.fixture(autouse=True)
def _fresh():
    G.clear()
    yield
    G.clear()


def _run(monkeypatch, rows: int, per_row_s: float, push_every_s: float):
    """Every batch the device UDF saw, and every row that came out."""
    monkeypatch.setenv("PATHWAY_DEVICE_INFLIGHT", "2")
    subject = flaky_subject([{"x": float(i)} for i in range(rows)],
                            fail_after=0, fail_attempts=0,
                            delay_s=push_every_s)
    batches: list[int] = []

    @pw.udf(batch=True, device=True, deterministic=True, return_type=float)
    def producer(xs):
        batches.append(len(xs))
        _time.sleep(per_row_s * len(xs))
        return [2.0 * x for x in xs]

    t = pw.io.python.read(subject, schema=sch.schema_from_types(x=float),
                          autocommit_duration_ms=10)
    out = t.select(x=t.x, y=producer(t.x))
    seen = {}

    def on_change(key, row, time, is_addition):
        if is_addition:
            seen[row["x"]] = row["y"]

    pw.io.subscribe(out, on_change)
    pw.run()
    return batches, seen


def test_ticks_stay_bounded_behind_a_slow_device(monkeypatch):
    batches, seen = _run(monkeypatch, ROWS, PER_ROW_S, PUSH_EVERY_S)
    # every row arrives, once
    assert seen == {float(i): 2.0 * i for i in range(ROWS)}
    assert sum(batches) == ROWS
    # unbounded, each tick holds what the source pushed during the leg
    # before the last, several times its rows, and the fourth or fifth
    # tick holds all that is left. Bounded, a leg lasts about LEG_TICKS
    # commit intervals (160 rows at 2,000 rows/s) once the window has been
    # found full, whatever the first ticks swallowed
    late = batches[len(batches) // 2:]
    assert len(batches) >= 12, batches
    assert max(late) <= 400, batches
    assert max(batches) < ROWS // 2, batches


def test_a_device_that_keeps_up_is_never_held_back(monkeypatch):
    """No leg outlasts a tick: the window is never full, the bound never
    arms, and each tick takes all there is."""
    blocked = []
    submit = DeviceBridge.submit

    def watched(self, tick, fn):
        submit(self, tick, fn)
        blocked.append(self.submits_blocked)

    monkeypatch.setattr(DeviceBridge, "submit", watched)
    batches, seen = _run(monkeypatch, 400, 0.0, PUSH_EVERY_S)
    assert len(seen) == 400 and sum(batches) == 400
    assert blocked and blocked[-1] == 0
    from pathway_tpu.engine import streaming

    assert all(rt._backpressure.ingest_row_budget() is None
               for rt in streaming.live_runtimes())


def test_bridge_counts_the_submits_that_waited():
    bridge = DeviceBridge(max_inflight=2)
    for tick in range(1, 6):
        bridge.submit(tick, lambda: _time.sleep(0.02))
    bridge.barrier()
    stats = bridge.stats()
    assert stats["resolved_watermark"] == 5
    # the first two found room; the rest waited for a leg to retire
    assert stats["submits_blocked"] == 3
    bridge.close()


def _look(limiter, tick, rows, *, watermark, exec_ms, blocked,
          deferred=True, queries=0, depth=None):
    bridge = {"resolved_watermark": watermark, "exec_ms": exec_ms,
              "submits_blocked": blocked}
    if depth is not None:
        bridge["depth"] = depth
    limiter.on_tick(tick, ingest_rows=rows, query_rows=queries,
                    deferred=deferred, bridge=bridge)
    return limiter.ingest_row_budget()


def test_the_bound_stands_from_the_first_submit_that_waits():
    """Three ticks after a release: two find room in the window, the third
    waits for the first leg, and that leg's cost sets the bound at once.
    Until the runtime's first ingest leg has retired nothing is known of
    the device's pace, and a drain takes ``FIRST_LEG_ROWS`` at most."""
    limiter = qos.DeviceBackpressure(0.05)
    leg_ms = qos.LEG_TICKS * 50.0
    assert limiter.ingest_row_budget() == qos.FIRST_LEG_ROWS
    assert _look(limiter, 1, 60, watermark=0, exec_ms=0.0,
                 blocked=0) == qos.FIRST_LEG_ROWS
    assert _look(limiter, 2, 60, watermark=0, exec_ms=0.0,
                 blocked=0) == qos.FIRST_LEG_ROWS
    # 60 rows took 420 ms: 7 ms a row
    assert _look(limiter, 3, 60, watermark=1, exec_ms=420.0,
                 blocked=1) == int(leg_ms / 7.0)
    # a submit that did not wait raises the bound by half while rows are
    # held back, but no further than a leg of LEG_TICKS intervals by the
    # readings (7 ms a row still), and a drain that left nothing behind
    # lifts it
    bound = limiter.ingest_row_budget()
    assert _look(limiter, 4, bound, watermark=3, exec_ms=1260.0,
                 blocked=1) == bound
    assert _look(limiter, 5, 10, watermark=4, exec_ms=1660.0, blocked=1,
                 deferred=False) is None


def test_a_leg_that_compiled_or_served_queries_is_no_reading():
    """The padded encoder path compiles nearly every tick: its legs are
    long whatever they hold, and a bound would shrink ticks without
    shortening them. Until a leg has given a reading a drain stays held to
    ``FIRST_LEG_ROWS``, as before the first leg retired."""
    limiter = qos.DeviceBackpressure(0.05)
    compile_s = [0.0]
    limiter._compile_s = lambda: compile_s[0]
    _look(limiter, 1, 500, watermark=0, exec_ms=0.0, blocked=0)
    _look(limiter, 2, 50, watermark=0, exec_ms=0.0, blocked=0, queries=4)
    # 550 of the first leg's 600 ms were XLA's: the submit waited for the
    # compiler, not for the device
    compile_s[0] += 0.55
    assert _look(limiter, 3, 50, watermark=1, exec_ms=600.0,
                 blocked=1) == qos.FIRST_LEG_ROWS
    assert limiter._cost.ms_per_row is None
    # a leg that served queries beside its rows is no cost sample either,
    # and with no sample a submit that waited bounds nothing
    assert _look(limiter, 4, 50, watermark=2, exec_ms=900.0,
                 blocked=2) == qos.FIRST_LEG_ROWS
    # 20 of this leg's 120 ms were a small program's compile: taken out,
    # 50 rows took 100 ms
    compile_s[0] += 0.02
    assert _look(limiter, 5, 50, watermark=3, exec_ms=1020.0,
                 blocked=3) == int(qos.LEG_TICKS * 50.0 / 2.0)


def test_a_first_leg_that_compiled_does_not_lift_the_hold():
    """A cold cache: the first ingest leg of one long document spends more
    of its time in the compiler than on the device and is no reading. With
    the hold lifted behind it the next drain took the whole backlog: one
    leg of 3,000 sections at 0.4 s each, and ``/v1/statistics`` timed out
    after 120 s (my chip run, PR 35: the run failed). The hold
    stands until the leg behind it, which compiles nothing, is read; a path
    whose every leg compiles is let go after ``UNREAD_LEGS`` of them."""
    limiter = qos.DeviceBackpressure(0.05)
    compile_s = [0.0]
    limiter._compile_s = lambda: compile_s[0]
    rows = qos.FIRST_LEG_ROWS
    assert _look(limiter, 1, 1, watermark=0, exec_ms=0.0, blocked=0,
                 depth=1) == rows
    assert _look(limiter, 2, rows, watermark=0, exec_ms=0.0, blocked=0,
                 depth=2) == rows
    # the first leg: one document, 430 ms on the device behind 740 ms of
    # compiles
    compile_s[0] += 0.74
    assert _look(limiter, 3, rows, watermark=1, exec_ms=1170.0, blocked=1,
                 depth=2) == rows
    # the second: eight documents in 3,440 ms, 430 ms a row: one row a leg
    assert _look(limiter, 4, rows, watermark=2, exec_ms=4610.0, blocked=2,
                 depth=2) == 1
    # every leg compiles: no reading ever, and no hold for good
    padded = qos.DeviceBackpressure(0.05)
    padded._compile_s = lambda: compile_s[0]
    for tick in range(1, qos.UNREAD_LEGS + 2):
        compile_s[0] += 0.55
        budget = _look(padded, tick, rows, watermark=tick - 1,
                       exec_ms=600.0 * (tick - 1), blocked=tick - 1)
        assert budget == (rows if tick <= qos.UNREAD_LEGS else None), tick
    assert padded._cost.ms_per_row is None


@pytest.mark.parametrize("leg_ms, first_bound", [
    (400.0, 8),     # 50 ms a row: eight rows fill a leg of 400 ms
    (16.0, 16),     # 2 ms a row would allow 200: twice the first leg's rows
])
def test_the_first_leg_s_cost_is_the_first_bound(leg_ms, first_bound):
    """Ticks that are slow on the host fill the window late: behind a
    device of 50 ms a row no submit waits for seconds, and unbounded ticks
    meanwhile pile up legs of a hundred rows (my chip run, PR 33). The first
    leg, held to ``FIRST_LEG_ROWS``, is a reading of the device's pace
    whether or not a submit waited for it; the bound then holds while a leg
    is still in flight behind the one submitted, grows by half when the
    device was idle, and is lifted once a drain leaves nothing behind."""
    limiter = qos.DeviceBackpressure(0.05)
    rows = qos.FIRST_LEG_ROWS
    assert _look(limiter, 1, rows, watermark=0, exec_ms=0.0, blocked=0,
                 depth=1) == rows
    assert _look(limiter, 2, rows, watermark=0, exec_ms=0.0, blocked=0,
                 depth=2) == rows
    # the first leg retired: 8 rows in ``leg_ms``
    assert _look(limiter, 3, rows, watermark=1, exec_ms=leg_ms, blocked=0,
                 depth=2) == first_bound
    # a leg still runs behind the one just submitted: the bound holds
    assert _look(limiter, 4, first_bound, watermark=2, exec_ms=2 * leg_ms,
                 blocked=0, depth=2) == first_bound
    # the device was idle when this leg was submitted: half as much again,
    # but no leg longer than LEG_TICKS intervals by the readings
    grown = min(first_bound + (first_bound + 1) // 2,
                int(qos.LEG_TICKS * 50.0 / (leg_ms / rows)))
    assert _look(limiter, 5, first_bound, watermark=4, exec_ms=4 * leg_ms,
                 blocked=0, depth=1) == grown
    # and nothing was left behind: no bound
    assert _look(limiter, 6, 3, watermark=5, exec_ms=5 * leg_ms, blocked=0,
                 depth=1, deferred=False) is None
    # a later release, the pace known: no first-leg rule again
    assert _look(limiter, 7, 500, watermark=6, exec_ms=6 * leg_ms,
                 blocked=0, depth=1) is None
