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


# ---------------------------------------------------------------------------
# a bound in force is never under the documents that fill one dispatch (the
# index's ingest reports its dispatches: device_bridge.note_ingest_dispatch)
# ---------------------------------------------------------------------------

def _next_fit(lengths, width):
    """Rows of ``width`` slots that next-fit in order packs ``lengths``
    into: the (real tokens, documents) of each."""
    rows = []
    for n in lengths:
        if not rows or rows[-1][0] + n > width:
            rows.append([0, 0])
        rows[-1][0] += n
        rows[-1][1] += 1
    return rows


def _one_row_a_dispatch(slots):
    return lambda docs: [(slots, tokens, n)
                         for tokens, n in _next_fit(docs, slots)]


def _ticks_behind_a_device(limiter, lengths, pack, *, dispatch_ms,
                           ticks=60, report=True):
    """Ticks of a backlog behind a device that is never idle: each drain
    takes what the limiter allows, ``pack`` says what dispatches the leg
    makes of those documents (``[(slots, real tokens, documents), ...]``),
    every dispatch costs ``dispatch_ms`` whatever it holds, and the look of
    a tick sees the leg of the tick before retired and its own submit wait.
    Returns each tick's ``(bound it drained under, its leg)``."""
    lengths = iter(lengths)
    sums = {"exec_ms": 0.0, "ingest_dispatches": 0, "ingest_slots": 0,
            "ingest_tokens": 0, "ingest_docs": 0}
    in_flight, seen = None, []
    for tick in range(1, ticks + 1):
        bound = limiter.ingest_row_budget()
        assert bound, (tick, bound)   # never 0, never none
        leg = pack([next(lengths) for _ in range(bound)])
        if in_flight is not None:
            sums["exec_ms"] += dispatch_ms * len(in_flight)
            if report:
                sums["ingest_dispatches"] += len(in_flight)
                sums["ingest_slots"] += sum(s for s, _t, _n in in_flight)
                sums["ingest_tokens"] += sum(t for _s, t, _n in in_flight)
                sums["ingest_docs"] += sum(n for _s, _t, n in in_flight)
        limiter.on_tick(tick, ingest_rows=bound, query_rows=0,
                        deferred=True, bridge=dict(
                            sums, resolved_watermark=tick - 1, depth=2,
                            submits_blocked=max(0, tick - 2)))
        in_flight = leg
        seen.append((bound, leg))
    return seen


@pytest.mark.parametrize("slots, tokens, dispatch_ms, settled", [
    # (a) a dispatch of 420 ms behind a leg of 400: two and a half
    # documents would fill it, so two a leg, where milliseconds a row read
    # a whole dispatch a document and held the leg to one for good
    (8192, 3276, 420.0, 2),
    # (b) a dispatch longer than a leg is still one dispatch, with the
    # documents that fill it: never 0, never none
    (8192, 3276, 900.0, 2),
    (4096, 400, 2500.0, 10),
    # (c) documents as long as a row, or longer than half of one: one a
    # dispatch, and it stays
    (8192, 8192, 420.0, 1),
    (8192, 5000, 420.0, 1),
])
def test_a_bound_is_one_dispatch_s_documents_at_the_least(
        slots, tokens, dispatch_ms, settled):
    limiter = qos.DeviceBackpressure(0.05)
    seen = _ticks_behind_a_device(
        limiter, iter(lambda: tokens, None), _one_row_a_dispatch(slots),
        dispatch_ms=dispatch_ms)
    assert [bound for bound, _leg in seen[-40:]] == [settled] * 40, seen
    assert limiter.rows_per_dispatch() == settled
    # milliseconds a row say less than one row a leg: the floor set it
    assert limiter.floored_looks >= (40 if settled > 1 else 0)
    # the same device with nothing reported: one row a tick, the parent's
    silent = qos.DeviceBackpressure(0.05)
    seen = _ticks_behind_a_device(
        silent, iter(lambda: tokens, None), _one_row_a_dispatch(slots),
        dispatch_ms=dispatch_ms, report=False)
    assert [bound for bound, _leg in seen[-40:]] == [1] * 40, seen
    assert silent.rows_per_dispatch() == 1


def test_a_leg_of_two_that_took_two_dispatches_leaves_the_bound_at_two():
    """(a) with documents of 0.2 to 0.75 of a dispatch's slots: every
    other pair does not share a row, its leg is two dispatches and reads a
    dispatch a document (840 ms for two), and the bound stays at two."""
    slots = 8192
    shares = (0.2, 0.75, 0.6, 0.5, 0.3, 0.45, 0.7, 0.35)
    lengths = [int(s * slots) for s in shares] * 40
    limiter = qos.DeviceBackpressure(0.05)
    seen = _ticks_behind_a_device(limiter, lengths,
                                  _one_row_a_dispatch(slots),
                                  dispatch_ms=420.0, ticks=120)
    late = seen[20:]
    assert {bound for bound, _leg in late} == {2}, seen
    assert {len(leg) for _bound, leg in late} == {1, 2}


def _parent_s_on_tick(self, tick, *, ingest_rows, query_rows, deferred,
                      bridge):
    """``DeviceBackpressure.on_tick`` as it stood before a bound had a floor
    but one row (PR 35's): a copy, to hold the budget of a leg of several
    dispatches to what it was, to the last digit."""
    self._unretired.append((tick, ingest_rows, query_rows))
    retired, clean = 0, True
    while self._unretired \
            and self._unretired[0][0] <= bridge["resolved_watermark"]:
        _tick, rows, queries = self._unretired.popleft()
        retired += rows
        self._legs_retired += rows > 0
        clean = clean and not queries
    compile_ms = (self._compile_s() - self._compile_s_seen) * 1e3
    exec_ms = bridge["exec_ms"] - self._exec_ms_seen
    steady = compile_ms <= 0.5 * exec_ms
    waited = bridge["submits_blocked"] != self._blocked_seen and steady
    self._exec_ms_seen += exec_ms
    self._blocked_seen = bridge["submits_blocked"]
    self._compile_s_seen += compile_ms / 1e3
    first = self._cost.ms_per_row is None
    if retired and clean and steady and exec_ms > compile_ms:
        dearest = max(self._cost.ms_per_row or 0.0,
                      (exec_ms - compile_ms) / retired)
        self._cost.sample(retired, exec_ms - compile_ms)
        self._readings += 1
        if self._readings <= qos.EARLY_READINGS:
            self._cost.ms_per_row = dearest
    self._paced = self._paced or self._readings > 0 \
        or self._legs_retired >= qos.UNREAD_LEGS
    rows = self._cost.rows_in(qos.LEG_TICKS * self.tick_interval_ms)
    if waited:
        if rows is not None:
            self._rows = max(1, rows) if self._rows is None else max(
                1, min(rows, qos._half_more(self._rows)))
    elif first and self._rows is None:
        if rows is not None:
            self._rows = max(1, min(rows, 2 * qos.FIRST_LEG_ROWS))
    elif bridge.get("depth", 0) > 1:
        pass
    elif self._rows is not None:
        if not deferred:
            self._rows = None
        else:
            grown = qos._half_more(self._rows)
            self._rows = grown if rows is None \
                else max(self._rows, min(grown, rows))


@pytest.mark.parametrize("report", [True, False])
@pytest.mark.parametrize("dispatch_ms, docs, slots, seed", [
    (47.0, 10, 2048, 1),     # eight dispatches a leg, ten documents each
    (47.0, 10, 2048, 2),
    (145.0, 4, 16384, 3),    # two a leg, four documents each
    (145.0, 4, 16384, 4),
])
def test_a_leg_of_several_dispatches_keeps_the_parent_s_budget(
        dispatch_ms, docs, slots, seed, report):
    """(d) Where milliseconds a row allow more than one dispatch's
    documents the floor is never met: 200 looks of a device that is mostly
    the slower side (now and then it is idle, a drain leaves nothing
    behind, a leg compiles or serves a query) give the budget the parent's
    arithmetic gives, with the report on and with it off."""
    import random

    rng = random.Random(seed)
    limiter, parent = qos.DeviceBackpressure(0.05), \
        qos.DeviceBackpressure(0.05)
    compile_s = [0.0]
    limiter._compile_s = parent._compile_s = lambda: compile_s[0]
    sums = {"exec_ms": 0.0, "ingest_dispatches": 0, "ingest_slots": 0,
            "ingest_tokens": 0, "ingest_docs": 0}
    blocked, in_flight = 0, None
    for tick in range(1, 201):
        budget = limiter.ingest_row_budget()
        assert budget == parent.ingest_row_budget(), tick
        deferred = rng.random() < 0.9
        # a drain that leaves nothing behind still fills a dispatch: one
        # document in a dispatch of ten is where the floor is met
        rows = (budget or rng.randint(docs, 120)) if deferred \
            else rng.randint(docs, max(docs, budget or 120))
        queries = 2 if rng.random() < 0.05 else 0
        if in_flight is not None:
            n = -(-in_flight // docs)       # whole dispatches
            sums["exec_ms"] += n * dispatch_ms * rng.uniform(0.9, 1.1)
            if rng.random() < 0.05:
                compile_s[0] += rng.choice((0.02, 0.9))
                sums["exec_ms"] += 1000.0 * rng.choice((0.02, 0.9))
            if report:
                sums["ingest_dispatches"] += n
                sums["ingest_slots"] += n * slots
                sums["ingest_tokens"] += sum(
                    int(rng.uniform(0.4, 1.0) * slots / docs)
                    for _ in range(in_flight))
                sums["ingest_docs"] += in_flight
        idle = rng.random() < 0.15
        blocked += not idle and rng.random() < 0.8
        look = dict(ingest_rows=rows, query_rows=queries, deferred=deferred,
                    bridge=dict(sums, resolved_watermark=tick - 1,
                                depth=1 if idle else 2,
                                submits_blocked=blocked))
        limiter.on_tick(tick, **look)
        _parent_s_on_tick(parent, tick, **look)
        in_flight = rows
    assert limiter._cost.ms_per_row == parent._cost.ms_per_row
    assert limiter.floored_looks == 0
    # slots over tokens: the packer leaves three slots in ten empty
    floor = limiter.rows_per_dispatch()
    assert (docs <= floor <= 2 * docs) if report else floor == 1


def _toy_embedder(width: int):
    import jax

    from pathway_tpu.models.encoder import EncoderConfig, init_params
    from pathway_tpu.xpacks.llm.embedders import JaxEncoderEmbedder

    cfg = EncoderConfig(vocab_size=64, hidden=16, layers=1, heads=2,
                        intermediate=32, max_len=width)
    return JaxEncoderEmbedder(
        config=cfg, params=init_params(jax.random.PRNGKey(0), cfg),
        max_len=width, ragged=True, ragged_max_seqs=1)


def _words(n_tokens: int) -> str:
    # the tokenizer frames a document's words with two tokens
    return " ".join("w" for _ in range(n_tokens - 2))


def test_the_real_packer_s_rows_are_three_fifths_full_at_two_a_leg():
    """(e) Documents of a sixteenth to three quarters of a row, uniform,
    one row a dispatch and a dispatch a whole leg (the sections of
    ``longcat-flash-embed.ingest-sections`` at a toy width), through
    ``pack_ragged``: at one document a leg a row is two fifths full; at
    the two that fill a dispatch three pairs in four share a row."""
    import random

    width = 128
    emb = _toy_embedder(width)
    rng = random.Random(37)
    filled = []

    def pack(docs):
        leg = [(args[0].size, int((args[1] >= 0).sum()), n_docs)
               for args, n_docs, _n_pad in emb.pack_ragged(
                   [_words(n) for n in docs])]
        assert sum(t for _s, t, _n in leg) == sum(docs)
        filled.extend(leg)
        return leg

    limiter = qos.DeviceBackpressure(0.05)
    seen = _ticks_behind_a_device(
        limiter, iter(lambda: rng.randint(width // 16, 3 * width // 4),
                      None),
        pack, dispatch_ms=420.0, ticks=400)
    # the mean of thirty lengths strays: a few ticks in a hundred are held
    # to three, fewer to one
    late = [bound for bound, _leg in seen[-300:]]
    assert set(late) <= {1, 2, 3} and late.count(2) >= 270, late
    rows = filled[-300:]
    assert all(s == width for s, _t, _n in rows)
    fill = sum(t for _s, t, _n in rows) / sum(s for s, _t, _n in rows)
    assert fill >= 0.6, fill
    # a quarter of the legs are two documents that did not share a row
    assert 1.1 < sum(len(leg) for _b, leg in seen[-300:]) / 300 < 1.45


def test_a_bridge_leg_sums_what_the_index_s_ingest_reported(monkeypatch):
    """(f) The ragged fused ingest, and the two-dispatch arm ``add_batch``
    falls through to, count each fixed-shape dispatch into the bridge leg
    that runs them, and the bridge sums a leg's counts when it retires,
    beside its ``exec_ms``. A query's text goes through the same
    ``encode_batch_device`` and adds nothing (ten tokens are no document:
    a floor of 819 sections a tick); off a leg the report goes nowhere."""
    from pathway_tpu.engine.device_bridge import note_ingest_dispatch
    from pathway_tpu.internals.keys import Pointer
    from pathway_tpu.ops import knn

    width = 32
    emb = _toy_embedder(width)
    index = knn.DeviceEmbeddingKnnIndex(
        emb, knn.BruteForceKnnIndex(16, reserved_space=64, metric="cos"))
    lengths = (20, 10, 20, 30, 5)              # rows of 30, 20, 30, 5
    texts = [_words(n) for n in lengths]
    keys = [Pointer(i) for i in range(len(texts))]

    def reported(stats):
        return tuple(stats[k] for k in qos._REPORTED)

    note_ingest_dispatch(width, 7, 1)          # no leg: nobody listens
    index.add_batch(keys, texts)               # nor off the bridge
    bridge = DeviceBridge(max_inflight=2)
    try:
        bridge.submit(1, lambda: None)
        bridge.barrier()
        assert reported(bridge.stats()) == (0, 0, 0, 0)
        bridge.submit(2, lambda: index.add_batch(keys, texts))
        bridge.barrier()
        assert index.fused_batches == 2 and index.fused_fallbacks == 0
        assert reported(bridge.stats()) == (4, 4 * width, 85, 5)
        # a query: the embedder's dispatch of its text is not ingest
        found = []
        bridge.submit(3, lambda: found.extend(
            index.search([(Pointer(99), texts[3], 1, None)])))
        bridge.submit(4, lambda: emb.encode_batch_device(texts[:2]))
        bridge.barrier()
        assert len(found) == 1 and found[0]
        assert reported(bridge.stats()) == (4, 4 * width, 85, 5)
        # the batch fits no one extent: the same chunks through
        # ``encode_batch_device``, reported by ``add_batch``
        fused = index._fused

        def unplaceable(*args, **kwargs):
            raise knn.FusedIngestUnplaceable("spans extents")

        index._fused = unplaceable
        bridge.submit(5, lambda: index.add_batch(keys, texts))
        bridge.barrier()
        index._fused = fused
        assert index.fused_fallbacks == 1
        assert reported(bridge.stats()) == (8, 8 * width, 170, 10)
    finally:
        bridge.close()
    # and the limiter reads one dispatch's documents from those sums
    limiter = qos.DeviceBackpressure(0.05)
    _look(limiter, 1, 10, watermark=0, exec_ms=0.0, blocked=0)
    limiter.on_tick(2, ingest_rows=10, query_rows=0, deferred=True,
                    bridge=dict(bridge.stats(), resolved_watermark=1))
    assert limiter.rows_per_dispatch() == int(width / 17.0)


def _run_reporting(monkeypatch, lengths, slots: int, dispatch_s: float,
                   report: bool, with_requests: bool = False):
    """As ``_run`` behind a device UDF that takes its batch in dispatches
    of ``slots`` token slots, next-fit, ``dispatch_s`` each whatever it
    holds, and (``report``) reports them as the index's ingest does. Row
    ``i`` is ``lengths[i]`` tokens long. Returns the batches, what came
    out, every look's budget and the limiter."""
    from pathway_tpu.engine.device_bridge import note_ingest_dispatch

    monkeypatch.setenv("PATHWAY_DEVICE_INFLIGHT", "2")
    subject = flaky_subject([{"x": float(i)} for i in range(len(lengths))],
                            fail_after=0, fail_attempts=0, delay_s=0.002)
    batches: list[int] = []

    @pw.udf(batch=True, device=True, deterministic=True, return_type=float)
    def producer(xs):
        batches.append(len(xs))
        for real, n in _next_fit([lengths[int(x)] for x in xs], slots):
            _time.sleep(dispatch_s)
            if report:
                note_ingest_dispatch(slots, real, n)
        return [2.0 * x for x in xs]

    t = pw.io.python.read(subject, schema=sch.schema_from_types(x=float),
                          autocommit_duration_ms=10)
    out = t.select(x=t.x, y=producer(t.x))
    seen = {}
    pw.io.subscribe(out, lambda key, row, time, is_addition:
                    seen.__setitem__(row["x"], row["y"]))
    looks, limiters = [], []
    on_tick = qos.DeviceBackpressure.on_tick

    def watched(self, tick, **look):
        on_tick(self, tick, **look)
        looks.append(self.ingest_row_budget())
        if self not in limiters:
            limiters.append(self)

    monkeypatch.setattr(qos.DeviceBackpressure, "on_tick", watched)
    pw.run()
    assert len(limiters) == 1
    return batches, seen, looks, limiters[0]


@pytest.mark.parametrize("report, rows_a_tick", [(True, 2), (False, 1)])
def test_a_dispatch_that_takes_a_leg_carries_the_rows_that_fill_it(
        monkeypatch, report, rows_a_tick):
    """(g) The whole runtime behind a device of 70 ms a dispatch, a leg
    being 8 intervals of 10 ms, rows of 0.3 to 0.7 of a dispatch of which
    every other pair shares one: ticks of two rows, and ``tick`` spans
    that say so. With the report silenced the first pair that did not share
    a dispatch read a dispatch a row, and the ticks came to one row each
    and stayed (the parent)."""
    from pathway_tpu.engine.flight_recorder import FlightRecorder

    ticks, span = [], FlightRecorder.span

    def kept(self, name, t0, t1, cause=None, **counts):
        if name == "tick":
            ticks.append(counts)
        span(self, name, t0, t1, cause, **counts)

    monkeypatch.setenv("PATHWAY_FLIGHT_RECORDER", "1")
    monkeypatch.setattr(FlightRecorder, "span", kept)
    lengths = [300, 400, 700, 500] * 10
    batches, seen, looks, limiter = _run_reporting(
        monkeypatch, lengths, 1000, 0.07, report)
    assert seen == {float(i): 2.0 * i for i in range(len(lengths))}
    assert sum(batches) == len(lengths)
    late = batches[len(batches) // 2:-1]
    assert late and set(late) == {rows_a_tick}, batches
    assert limiter.rows_per_dispatch() == rows_a_tick
    # the ``tick`` span carries the bound its drain was held to
    bounded = [t for t in ticks if t.get("bound") is not None]
    assert bounded and all(t["rows"] <= t["bound"] for t in bounded), ticks
    assert [t["bound"] for t in bounded if t["rows"]][-8:] \
        == [rows_a_tick] * 8


def test_no_bound_stands_where_the_device_keeps_up_beside_requests(
        monkeypatch):
    """(h) ``bge-small-10m.query-steady``'s regime: a document now and
    then through a device that retires it within the tick, reported as the
    index reports, and requests answered meanwhile. Once the first leg has
    been read no look leaves a bound, and the floor sets none."""
    import json
    import threading
    import urllib.request

    from pathway_tpu.engine import streaming
    from pathway_tpu.engine.device_bridge import note_ingest_dispatch
    from pathway_tpu.internals.runner import GraphRunner
    from pathway_tpu.io.http import PathwayWebserver, rest_connector
    from pathway_tpu.io.python import ConnectorSubject

    monkeypatch.setenv("PATHWAY_DEVICE_INFLIGHT", "2")

    answered = threading.Event()

    class _Docs(ConnectorSubject):
        def run(self) -> None:
            answered.wait(20.0)     # the documents beside the requests
            for i in range(40):
                _time.sleep(0.02)
                self.next(x=float(i))

    @pw.udf(batch=True, device=True, deterministic=True, return_type=float)
    def embed(xs):
        note_ingest_dispatch(512, 60 * len(xs), len(xs))
        return [2.0 * x for x in xs]

    ws = PathwayWebserver(host="127.0.0.1", port=0)
    queries, writer = rest_connector(
        webserver=ws, route="/q", schema=sch.schema_from_types(query=str),
        methods=("POST",), delete_completed_queries=True,
        autocommit_duration_ms=10)
    writer(queries.select(result=pw.apply(str.upper, queries.query)))
    docs = pw.io.python.read(_Docs(), schema=sch.schema_from_types(x=float),
                             autocommit_duration_ms=10)
    seen = []
    pw.io.subscribe(docs.select(y=embed(docs.x)),
                    lambda key, row, time, is_addition: seen.append(row))
    runner = GraphRunner()
    for binder in G.output_binders:
        binder(runner)
    rt = streaming.StreamingRuntime(runner, default_commit_ms=10)
    looks = []
    on_tick = qos.DeviceBackpressure.on_tick
    parent = qos.DeviceBackpressure(0.01)

    def watched(self, tick, **look):
        on_tick(self, tick, **look)
        _parent_s_on_tick(parent, tick, **look)
        looks.append((self.ingest_row_budget(), parent.ingest_row_budget(),
                      self.floored_looks, look["bridge"]["ingest_docs"]))

    monkeypatch.setattr(qos.DeviceBackpressure, "on_tick", watched)
    thread = threading.Thread(target=rt.run, daemon=True)
    thread.start()
    try:
        deadline = _time.monotonic() + 20.0
        while _time.monotonic() < deadline and not (
                ws._started.is_set() and ws.port):
            _time.sleep(0.01)
        answers = []
        while len(seen) < 40 and _time.monotonic() < deadline:
            req = urllib.request.Request(
                f"http://127.0.0.1:{ws.port}/q",
                data=json.dumps({"query": "q"}).encode(), method="POST",
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=30) as resp:
                answers.append(resp.read().decode())
            answered.set()
            _time.sleep(0.03)
    finally:
        rt.stop()
        thread.join(10.0)
    assert len(seen) == 40 and len(answers) >= 3
    assert set(answers) == {"Q"}
    # the parent's budget at every look: held to ``FIRST_LEG_ROWS`` until
    # the first leg is read, twice that at most until a drain has left
    # nothing behind, and none from then on
    budgets = [budget for budget, _parent, _floored, _docs in looks]
    assert budgets == [p for _budget, p, _floored, _docs in looks]
    lifted = budgets.index(None)
    assert set(budgets[:lifted]) <= {qos.FIRST_LEG_ROWS,
                                     2 * qos.FIRST_LEG_ROWS}
    # (a loaded machine may make one submit wait: a bound of hundreds of
    # rows for a look or two, the parent's too)
    late = budgets[lifted:]
    assert late.count(None) >= 0.9 * len(late), budgets
    assert all(b is None or b > 40 for b in late), budgets
    assert looks[-1][2] == 0 and looks[-1][3] >= 30   # reported, though
    assert rt._backpressure.rows_per_dispatch() == 8


@pytest.mark.parametrize("state, rows, floor, floored", [
    ("no limiter", None, None, None),   # the families are absent
    ("unread", 8, 1, 0),    # held to FIRST_LEG_ROWS, no pace read yet
    ("floored", 2, 2, 1),   # a dispatch of 420 ms, 3,276 tokens a row
    ("lifted", 0, 2, 1),    # a drain left nothing behind: no bound
])
def test_the_ingest_bound_and_its_floor_are_on_metrics(state, rows, floor,
                                                       floored):
    """``pathway_tpu_ingest_bound_rows``: the rows ``DeviceBackpressure``
    holds a drain to (0 while no bound stands),
    ``pathway_tpu_ingest_bound_floor_rows``: one dispatch's documents as
    reckoned, ``pathway_tpu_ingest_bound_floored_total``: the looks at
    which that floor set the bound."""
    from test_monitoring_http import (_FakeRuntime, _metrics_lines,
                                      _parse_samples)

    rt = _FakeRuntime()
    if state != "no limiter":
        limiter = rt._backpressure = qos.DeviceBackpressure(0.05)
        counts = dict(ingest_dispatches=0, ingest_slots=0, ingest_tokens=0,
                      ingest_docs=0)
        limiter.on_tick(1, ingest_rows=8, query_rows=0, deferred=True,
                        bridge=dict(counts, resolved_watermark=0, depth=2,
                                    submits_blocked=0, exec_ms=0.0))
        if state != "unread":
            counts = dict(ingest_dispatches=5, ingest_slots=5 * 8192,
                          ingest_tokens=8 * 3276, ingest_docs=8)
            limiter.on_tick(2, ingest_rows=8, query_rows=0, deferred=True,
                            bridge=dict(counts, resolved_watermark=1,
                                        depth=2, submits_blocked=1,
                                        exec_ms=5 * 420.0))
        if state == "lifted":
            limiter.on_tick(3, ingest_rows=1, query_rows=0, deferred=False,
                            bridge=dict(counts, resolved_watermark=2,
                                        depth=1, submits_blocked=1,
                                        exec_ms=6 * 420.0))
    lines = _metrics_lines(rt)
    vals = {f: v for f, _l, v in _parse_samples(lines)}
    typed = {ln.split()[2] for ln in lines if ln.startswith("# TYPE")}
    for family, value in (
            ("pathway_tpu_ingest_bound_rows", rows),
            ("pathway_tpu_ingest_bound_floor_rows", floor),
            ("pathway_tpu_ingest_bound_floored_total", floored)):
        assert vals.get(family) == value, family
        assert (family in typed) == (value is not None)
