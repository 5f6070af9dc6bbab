"""QoS control plane: the SLO measurement plane finally *acts*.

PR 6 built request-scoped tracing (engine/request_tracker.py: telescoping
stages, P² quantiles, burn rate over ``PATHWAY_SLO_E2E_MS``) and PR 14
made it fleet-wide — but nothing consumed it: under heavy ingest the
scheduler hands the device to maintenance work while query p50 blows
through the SLO. This module closes the loop with four mechanisms
(VectorLiteRAG's latency-aware resource partitioning between query and
index work; HedraRAG's coalescing of concurrent retrieval — PAPERS.md):

1. **Device-time budgeting** — each tick, the streaming loop asks
   :meth:`QosController.ingest_row_budget` how many ingest rows may ride
   this tick's device leg; the rest stay *in their sessions* and are
   drained on later ticks (the existing sealed-prefix machinery seals
   exactly what each tick drains, so deferral never touches durability or
   exactly-once — rows are delayed, never dropped). The budget is steered
   by a feedback loop (AIMD) over the tracker's burn rate and e2e p50:
   burning budget halves the ingest allowance down to a progress floor,
   a healthy window grows it back. ``PATHWAY_QOS_QUERY_BUDGET=<ms>``
   pins a fixed per-tick device-time reservation for queries instead
   (translated to rows via an EWMA of observed ingest cost per row,
   :class:`IngestCost`). A runtime with **no** QoS armed asks the same
   question of :class:`DeviceBackpressure`, whose answer is "all" until
   the device is the slower side: one budget interface
   (``ingest_row_budget`` / ``note_deferral`` / ``on_tick``), one cost
   model, two policies.

2. **Admission control** — a bounded queue ahead of the webserver's
   ``session.push``: when the depth cap is hit, or the burn rate crosses
   the shed threshold while the predicted wait already exceeds the
   query's deadline, the request is shed with a fast ``503`` +
   ``Retry-After`` instead of queueing into a certain SLO violation.
   Shedding is *visible, never silent*: every shed increments
   ``shed_total`` (and the 503 carries the request id). Sustained
   deferral also propagates backpressure to connector readers through
   the supervisor (their ``session.sleep`` stretches while the flag is
   up).

3. **Cross-request coalescing** — concurrent KNN queries that land in
   the same commit tick already batch into ONE kernel dispatch
   (engine/index_ops.py stacks the tick's queries into a single
   ``index.search`` call; per-request top-k is merged on the way out).
   The controller makes that observable: the operator reports every
   multi-query dispatch here, and the admission gate deliberately
   *admits* waiting queries together rather than spacing them, so
   concurrent arrivals share a dispatch instead of serializing.

4. **Fleet integration** — shed/deferral/budget state rides the PR-12
   control-channel heartbeats (engine/replica.py); the router
   (engine/router.py) steers load away from an endpoint that is
   actively shedding *before* its p95 degrades, and ``/fleet/status``
   shows per-endpoint QoS state.

Byte-identity invariant: with QoS on, the consolidated outputs for all
*admitted* traffic are identical to QoS-off — deferral shifts which tick
an ingest row rides (timestamps move), never its content, ordering
within a source, or its exactly-once accounting; shed queries never
enter the engine at all. tests/test_qos.py pins this as a property test.

Off by default: ``pw.run(qos=True)`` / ``PATHWAY_QOS=1`` arms it (the
controller needs the request tracker, so QoS implies the flight
recorder). PWT013 (internals/static_check) warns when an SLO target is
configured but the pipeline runs with QoS disabled — measuring without
acting.
"""

from __future__ import annotations

import collections
import os
import time as _time
import weakref

# live controller (weak: dies with its runtime). The coalescing hook in
# engine/index_ops.py and the bench/status surfaces read it out-of-band
# — one module-global probe per dispatch when QoS is off.
_LIVE: "weakref.ref[QosController] | None" = None


def install_controller(controller: "QosController | None") -> None:
    global _LIVE
    _LIVE = weakref.ref(controller) if controller is not None else None


def current_controller() -> "QosController | None":
    ref = _LIVE
    return ref() if ref is not None else None


def note_coalesced_dispatch(n_queries: int) -> None:
    """Hook for the external-index operator: ``n_queries`` as-of-now
    queries shared one kernel dispatch this tick. No-op without a live
    controller (the QoS-off hot path pays one global read)."""
    if _LIVE is None:
        return
    ctl = _LIVE()
    if ctl is not None:
        ctl.note_search_dispatch(n_queries)


def note_answer_coalesced(n_queries: int) -> None:
    """Hook for the semantic result cache (engine/result_cache.py):
    ``n_queries`` as-of-now queries were answered this tick WITHOUT a
    kernel dispatch — cache hits plus in-batch duplicate misses sharing
    one search. This extends PR 15's cross-request coalescing from "same
    tick" (one dispatch, many queries) to "same answer" (zero
    dispatches)."""
    if _LIVE is None:
        return
    ctl = _LIVE()
    if ctl is not None:
        ctl.note_answer_reuse(n_queries)


class QueryShedError(RuntimeError):
    """A query was refused at admission (queue full, or deadline-aware
    shedding under budget burn). The webserver maps it to a fast ``503``
    with ``Retry-After`` — the shed contract in README "QoS & admission
    control"."""

    def __init__(self, reason: str, retry_after_s: int):
        super().__init__(reason)
        self.reason = reason
        self.retry_after_s = max(1, int(retry_after_s))


def _env_truthy(name: str) -> bool | None:
    """Tri-state env flag: True/False when set, None when absent — the
    distinction PWT013's waiver path needs (an explicit ``PATHWAY_QOS=0``
    is a decision; an unset var is a default)."""
    raw = os.environ.get(name)
    if raw is None or raw == "":
        return None
    return raw not in ("0", "false", "no", "off")


class QosConfig:
    """Knobs (README "QoS & admission control" carries the table)."""

    def __init__(self, *,
                 query_budget_ms: float | None = None,
                 min_ingest_rows: int | None = None,
                 max_ingest_rows: int | None = None,
                 admission_queue: int | None = None,
                 deadline_ms: float | None = None,
                 shed_burn_threshold: float | None = None,
                 backpressure_factor: float | None = None):
        from pathway_tpu.internals.config import _env_float, _env_int

        def _env_budget() -> float | None:
            raw = os.environ.get("PATHWAY_QOS_QUERY_BUDGET", "")
            if raw in ("", "adaptive", "auto"):
                return None
            try:
                return max(0.0, float(raw))
            except ValueError:
                return None

        # fixed per-tick device-time reservation for query work (ms);
        # None = adaptive (the AIMD loop owns the partition)
        self.query_budget_ms = (query_budget_ms if query_budget_ms
                                is not None else _env_budget())
        # ingest progress floor: the budget never starves maintenance
        # below this many rows per tick, so a saturated query phase still
        # makes ingest progress (deferred ≠ dropped, and bounded delay)
        self.min_ingest_rows = max(1, min_ingest_rows if min_ingest_rows
                                   is not None else _env_int(
                                       "PATHWAY_QOS_MIN_INGEST_ROWS", 64))
        self.max_ingest_rows = max(
            self.min_ingest_rows,
            max_ingest_rows if max_ingest_rows is not None
            else _env_int("PATHWAY_QOS_MAX_INGEST_ROWS", 1 << 16))
        # bounded admission queue ahead of session.push
        self.admission_queue = max(1, admission_queue if admission_queue
                                   is not None else _env_int(
                                       "PATHWAY_QOS_ADMISSION_QUEUE", 256))
        # per-query deadline for deadline-aware shedding: a query whose
        # predicted completion exceeds this (while the error budget is
        # burning) gets the fast 503. 0 = derive 5x the SLO target —
        # the deadline is the client's patience, not the latency TARGET:
        # defaulting it to the SLO itself would shed nearly every query
        # the moment burn crosses 1, turning a degraded system into a
        # refusing one
        self.deadline_ms = (deadline_ms if deadline_ms is not None
                            else _env_float("PATHWAY_QOS_DEADLINE_MS", 0.0))
        # bounded wait for a full admission queue before the 503 (absorbs
        # a micro-burst; 0 = shed immediately). The wait shows up in the
        # request's admission_wait stage.
        self.admission_grace_ms = max(0.0, _env_float(
            "PATHWAY_QOS_ADMISSION_GRACE_MS", 0.0))
        # burn-based shedding needs statistical footing: with fewer
        # completed requests than this in the burn window, the gate only
        # sheds on queue depth (structural), never on burn — one
        # compile-time outlier in a 1-sample window reads as "100x the
        # error budget" and would wedge the gate shut (shed queries
        # never complete, so the window never heals)
        self.shed_min_samples = max(1, _env_int(
            "PATHWAY_QOS_SHED_MIN_SAMPLES", 16))
        self.shed_burn_threshold = (
            shed_burn_threshold if shed_burn_threshold is not None
            else _env_float("PATHWAY_QOS_SHED_BURN", 1.0))
        # session.sleep stretch while deferral backpressure is up
        self.backpressure_factor = max(1.0, backpressure_factor
                                       if backpressure_factor is not None
                                       else _env_float(
                                           "PATHWAY_QOS_BACKPRESSURE", 4.0))
        # bench/test knob: treat serving as always active so the ingest
        # partition applies even between query bursts (a pure-ingest
        # identity/deferral test needs the clip without driving HTTP
        # load; production leaves this off so ETL phases run unthrottled)
        self.always_budget = _env_truthy("PATHWAY_QOS_ALWAYS_BUDGET") \
            or False

    @classmethod
    def from_env(cls) -> "QosConfig":
        return cls()


class IngestCost:
    """Device milliseconds one ingest row costs, smoothed over the legs
    that retired: what turns a stretch of device time into a row
    allowance. Both ingest budgets reckon with it: the controller's fixed
    query reservation and :class:`DeviceBackpressure`'s leg length.

    The cost of a row is linear; the device's is a step, in dispatches of
    a fixed shape that take as long half empty as full. So it also keeps
    what a dispatch holds, where the ingest path reports it
    (``device_bridge.note_ingest_dispatch``): the token slots of a dispatch
    and the real tokens a document brought, and from them the documents
    that fill one dispatch (:meth:`rows_per_dispatch`), under which
    :class:`DeviceBackpressure` never holds a drain."""

    #: documents whose lengths make up the mean: a leg of one document is
    #: one reading of a length that differs tenfold, a leg of eighty is
    #: eighty
    RECENT_ROWS = 30

    def __init__(self):
        self.ms_per_row: float | None = None
        self.slots_per_dispatch: float | None = None
        self.tokens_per_row: float | None = None
        self._rows_counted = 0

    def sample(self, rows: int, device_ms: float) -> None:
        cost = device_ms / rows
        self.ms_per_row = cost if self.ms_per_row is None \
            else 0.8 * self.ms_per_row + 0.2 * cost

    def rows_in(self, ms: float) -> int | None:
        """Rows that fit ``ms`` of device time (None before a sample)."""
        if not self.ms_per_row:
            return None
        return int(ms / self.ms_per_row)

    def note_dispatches(self, dispatches: int, slots: int, tokens: int,
                        rows: int) -> None:
        """What retired ingest legs reported of their dispatches: how
        many, their token slots, and the real tokens of the ``rows``
        documents in them."""
        if dispatches <= 0 or rows <= 0 or tokens <= 0:
            return
        self.slots_per_dispatch = slots / dispatches
        kept = self._rows_counted
        self.tokens_per_row = (kept * (self.tokens_per_row or 0.0)
                               + tokens) / (kept + rows)
        self._rows_counted = min(kept + rows, self.RECENT_ROWS)

    def rows_per_dispatch(self) -> int:
        """Documents that fill one dispatch, by the mean of the recent
        ones' lengths; 1 behind a path that reported nothing."""
        if not self.tokens_per_row:
            return 1
        return max(1, int(self.slots_per_dispatch / self.tokens_per_row))


#: commit intervals a device leg lasts while :class:`DeviceBackpressure`
#: bounds the drain: long enough that the partial dispatch a tick ends in
#: is a small share of its leg, short enough that tick edges (freshness,
#: any reader of progress) keep falling several times a second. Swept on
#: the chip behind a decoder embedder of 70 ms a dispatch, 50 ms ticks
#: (PERF.md, PR 28): 1, 2, 4 intervals ingest 126-127 docs/s, 8 136, 16 139
#: with a leg's p95 at 0.94 s. Where one dispatch is a leg or longer the
#: cost of a row is all fixed cost and the linear reading falls under one:
#: the bound then stands at the documents that fill one dispatch
#: (``IngestCost.rows_per_dispatch``), and a leg is a dispatch or two
LEG_TICKS = 8
#: rows a drain takes at most before the device has retired its first
#: ingest leg: nothing is known of its pace yet, and the window lets two
#: legs pile up behind the first before a submit waits. Behind an embedder
#: of 135 ms a dispatch of three documents the three unbounded ticks after
#: a release held 130 documents, six seconds of legs with `/v1/statistics`
#: that far ahead of the index (my chip run, PR 33); a device that keeps up
#: retires its first leg within a tick or two and is held to this for as
#: long
FIRST_LEG_ROWS = 8
#: readings of a row's cost of which the dearest stands, before smoothing
EARLY_READINGS = 4
#: ingest legs that may retire without a reading of a row's cost (legs that
#: spent their time in XLA's compiler) before a drain is held to
#: ``FIRST_LEG_ROWS`` no longer: a path that compiles nearly every tick
#: gives no reading ever and is not to be held for good; a first leg that
#: compiled on a cold cache is followed by one that does not
UNREAD_LEGS = 4


#: ``DeviceBridge.stats()``'s sums of ``note_ingest_dispatch``'s reports, in
#: the order of ``IngestCost.note_dispatches``
_REPORTED = ("ingest_dispatches", "ingest_slots", "ingest_tokens",
             "ingest_docs")


def _half_more(rows: int) -> int:
    return rows + (rows + 1) // 2


class DeviceBackpressure:
    """The ingest budget of a runtime with no QoS armed: none, until the
    device is the slower side.

    ``DeviceBridge.submit`` blocks on a full window, and an unbounded
    drain then takes everything the connectors pushed meanwhile: behind a
    device three times slower than a reader each tick holds three times
    the rows of the one before, until one leg runs for minutes and no
    tick edge falls for as long. So a tick whose submit found the window
    full bounds the next drain to the rows the device retires in
    ``LEG_TICKS`` commit intervals, by the cost of the legs that have
    retired (:class:`IngestCost`; the first leg has retired by the first
    submit that waits, so the bound stands from the third tick after a
    release; until a leg of the runtime's life has given a reading of a
    row's cost a drain takes ``FIRST_LEG_ROWS`` at most, and that leg's
    cost is the first bound: the first leg that retires, or, where it
    spent its time in the compiler, one of the ``UNREAD_LEGS`` behind it).
    The rest stays in its session and rides later ticks, as
    under the controller's budget. A submit that found the device idle
    (no leg in flight but its own) raises the bound by half while rows
    are held back, up to a leg of ``LEG_TICKS`` intervals by the readings,
    and lifts it once a drain left nothing behind: a device that keeps up
    is never held back, and what a bound held back does not arrive as one
    tick.

    A bound in force is never under the documents that fill one dispatch
    (``IngestCost.rows_per_dispatch``, from what the ingest path reported
    of the retired legs' dispatches; 1 where nothing reports). Behind an
    embedder whose one dispatch of a row of 8,192 slots takes a whole leg,
    whether it holds one section or two, milliseconds a row read a
    dispatch a section, ``rows_in`` a leg came to 0 and every tick carried
    one section in a row three fifths empty (ledger, PR 35). Held to that
    floor a leg is one dispatch, or two where its documents did not share
    a row, however long a dispatch is. The floor lifts a bound by half a
    look at most, as a reading does: the mean of a first leg's one or two
    documents says little of the next ones' lengths. Where the linear
    reading is above the floor, which is wherever a leg holds several
    dispatches, the floor changes nothing.

    The time a leg spent in XLA's compiler is taken out of its cost, and
    a submit that waited for legs which spent most of their time there
    counts as one that did not wait: a compile says nothing of the pace
    at which the device takes rows, and a bound reckoned with it shrinks
    ticks without shortening their legs (the padded encoder path meets a
    new shape, and a compile, nearly every tick)."""

    def __init__(self, tick_interval_s: float):
        from pathway_tpu.engine.device_sanitizer import \
            install_compile_clock

        self.tick_interval_ms = max(1.0, tick_interval_s * 1e3)
        self._cost = IngestCost()
        self._rows: int | None = None
        # whether the device's pace is known (a leg's cost was read, or
        # ``UNREAD_LEGS`` ingest legs retired without a reading): before,
        # a drain is held to ``FIRST_LEG_ROWS``
        self._paced = False
        self._legs_retired = 0
        self._readings = 0
        # (tick, ingest rows, query rows) of the ticks whose legs have
        # not retired, and what the last look saw of the bridge
        self._unretired: collections.deque = collections.deque()
        self._exec_ms_seen = 0.0
        self._blocked_seen = 0
        # the bridge's sums of what the ingest path reported of its
        # dispatches (how many, slots, real tokens, documents), as last
        # seen
        self._reported_seen = (0, 0, 0, 0)
        # looks at which the floor, not the linear reading, set the bound
        self.floored_looks = 0
        # the bridge worker's compiles: the legs' own
        self._compile_s = install_compile_clock("device-bridge")
        self._compile_s_seen = self._compile_s()

    def ingest_row_budget(self) -> int | None:
        """Max ingest rows the next drain may take (None: all)."""
        if self._rows is None and not self._paced:
            return FIRST_LEG_ROWS
        return self._rows

    def rows_per_dispatch(self) -> int:
        """The floor of a bound: one dispatch's documents, as reckoned."""
        return self._cost.rows_per_dispatch()

    def note_deferral(self, n_rows: int) -> None:
        """Nothing to count: the drain's ``deferred`` flag comes back
        through :meth:`on_tick`."""

    def on_tick(self, tick: int, *, ingest_rows: int, query_rows: int,
                deferred: bool, bridge: dict) -> None:
        """One look, after tick ``tick``'s submit returned: ``bridge`` is
        ``DeviceBridge.stats()``."""
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
        # a leg which spent most of its time in XLA's compiler says nothing
        # of the device's pace: a submit that waited for it waited for the
        # compiler, and it is no reading of a row's cost
        steady = compile_ms <= 0.5 * exec_ms
        waited = bridge["submits_blocked"] != self._blocked_seen and steady
        self._exec_ms_seen += exec_ms
        self._blocked_seen = bridge["submits_blocked"]
        self._compile_s_seen += compile_ms / 1e3
        reported = tuple(bridge.get(k, 0) for k in _REPORTED)
        self._cost.note_dispatches(
            *(now - seen for now, seen in zip(reported,
                                              self._reported_seen)))
        self._reported_seen = reported
        first = self._cost.ms_per_row is None
        if retired and clean and steady and exec_ms > compile_ms:
            # the first readings are of a few rows each, and rows differ
            # (documents of 50 to 16,382 tokens): the dearest of them
            # stands until there are enough to smooth, so that the first
            # legs come out short rather than long
            dearest = max(self._cost.ms_per_row or 0.0,
                          (exec_ms - compile_ms) / retired)
            self._cost.sample(retired, exec_ms - compile_ms)
            self._readings += 1
            if self._readings <= EARLY_READINGS:
                self._cost.ms_per_row = dearest
        # a first leg that compiled (a cold cache) is no reading, and with
        # the hold lifted behind it the next drain took the whole backlog:
        # one leg of every document, no tick edge and no answer to a
        # request until it retired (3,000 sections of 0.4 s each behind
        # it, `/v1/statistics` timed out after 120 s: my chip run, PR 35)
        self._paced = self._paced or self._readings > 0 \
            or self._legs_retired >= UNREAD_LEGS
        rows = self._cost.rows_in(LEG_TICKS * self.tick_interval_ms)
        held = self._rows
        if waited:
            if rows is not None:
                # down at once, up by half at most: a reading can flatter
                # (short legs are cheap a row where a leg's host work
                # overlaps the next tick's), and a bound that jumped to it
                # made legs of three times the rows
                self._rows = max(1, rows) if self._rows is None else max(
                    1, min(rows, _half_more(self._rows)))
        elif first and self._rows is None:
            # the first reading of the device's pace, from a leg held to
            # ``FIRST_LEG_ROWS``: a leg of ``LEG_TICKS`` intervals by it,
            # but at most twice what that leg held
            if rows is not None:
                self._rows = max(1, min(rows, 2 * FIRST_LEG_ROWS))
        elif bridge.get("depth", 0) > 1:
            # the leg before this one had not retired when this one was
            # submitted: the device is not ahead of the ticks, though the
            # window (ticks that are slow on the host fill it late) has
            # room. The bound holds
            pass
        elif self._rows is not None:
            if not deferred:
                self._rows = None
            else:
                # half as much again, but no leg longer than ``LEG_TICKS``
                # intervals by the readings: where ticks are as slow on the
                # host as legs on the device the device is idle at many a
                # submit, and a bound that grew on that alone swung between
                # one leg's rows and three legs'
                grown = _half_more(self._rows)
                self._rows = grown if rows is None \
                    else max(self._rows, min(grown, rows))
        # a dispatch takes as long half empty as full: whatever milliseconds
        # a row say, a bound is one dispatch's documents at the least. Up
        # by half a look at most here too: the mean of a few documents'
        # lengths can flatter as a reading can (one document of 50 tokens
        # in a row of 16,384 slots), and each longer leg brings more
        floor = self._cost.rows_per_dispatch()
        if self._rows is not None and self._rows < floor:
            self._rows = min(floor, max(self._rows,
                                        _half_more(held or self._rows)))
            self.floored_looks += 1


class QosController:
    """One per streaming runtime (created iff QoS is armed). Thread
    crossings: the webserver's event loop calls :meth:`admit` /
    :meth:`finish_query`; the commit loop calls :meth:`ingest_row_budget`
    / :meth:`on_tick`; the device-bridge worker (via index_ops) calls
    :meth:`note_search_dispatch`; monitoring threads read
    :meth:`summary`. Counter math sits under one lock — every call is
    O(1) and far off the per-row hot path."""

    def __init__(self, config: QosConfig, tracker,
                 tick_interval_s: float = 0.1):
        from pathway_tpu.engine.locking import create_lock

        self.config = config
        self.tracker = tracker  # RequestTracker (never None: QoS implies it)
        self.slo_ms = tracker.slo_ms
        self.tick_interval_ms = max(1.0, tick_interval_s * 1e3)
        self._lock = create_lock("QosController._lock")
        # -- budgeting state ----------------------------------------------
        # adaptive ingest allowance (rows/tick); starts wide open and
        # only tightens once queries actually burn budget
        self._rows_per_tick = float(config.max_ingest_rows)
        # EWMA ingest device-cost (ms per row), learned from ticks that
        # carried ingest but no query work — translates a fixed
        # PATHWAY_QOS_QUERY_BUDGET (ms) into a row allowance
        self._cost = IngestCost()
        self._serving_active_until = 0.0
        self._last_count = 0
        # -- counters (exported: /metrics pathway_tpu_qos_*) ---------------
        self.shed_total = 0
        self.ingest_deferrals = 0      # (tick, source) pairs deferred
        self.deferred_rows_total = 0   # rows left for later ticks, summed
        self.coalesced_dispatches = 0  # kernel dispatches serving >1 query
        self.coalesced_queries = 0     # queries that shared a dispatch
        self.coalesced_answers = 0     # queries served with NO dispatch
        #                                (result-cache hits + dup misses)
        self.admitted_total = 0
        self._queue_depth = 0
        self.ticks_budgeted = 0
        self.backpressure_active = False

    # -- admission control (webserver event loop) --------------------------
    def admission_has_capacity(self) -> bool:
        """Uncounted capacity probe for the webserver's bounded grace
        loop — :meth:`admit` makes the final (counted) decision."""
        with self._lock:
            return self._queue_depth < self.config.admission_queue

    def admit(self, ingress_t: float) -> None:
        """Admit one query past the gate or raise :class:`QueryShedError`.
        Runs BEFORE ``session.push`` — a shed query never enters the
        engine (no row, no tick, no retraction), which is what keeps the
        byte-identity invariant trivial for shed traffic."""
        cfg = self.config
        with self._lock:
            depth = self._queue_depth
        if depth >= cfg.admission_queue:
            with self._lock:
                self.shed_total += 1
            raise QueryShedError(
                f"admission queue full ({depth}/{cfg.admission_queue})",
                self._retry_after_s(depth))
        burn = self.tracker.burn_rate()
        if burn > cfg.shed_burn_threshold \
                and self.tracker.window_size() >= cfg.shed_min_samples:
            deadline = cfg.deadline_ms or 5.0 * self.slo_ms
            waited_ms = (_time.perf_counter() - ingress_t) * 1e3
            predicted = waited_ms + self._predicted_e2e_ms(depth)
            if predicted > deadline:
                with self._lock:
                    self.shed_total += 1
                raise QueryShedError(
                    f"SLO burn {burn:.2f} > {cfg.shed_burn_threshold:.2f} "
                    f"and predicted latency {predicted:.1f} ms exceeds the "
                    f"{deadline:.1f} ms deadline",
                    self._retry_after_s(depth))
        with self._lock:
            self._queue_depth += 1
            self.admitted_total += 1
        self._serving_active_until = _time.monotonic() + 5.0

    def finish_query(self) -> None:
        """The admitted query's handler is returning (resolved, errored
        or disconnected) — its admission slot frees either way."""
        with self._lock:
            self._queue_depth = max(0, self._queue_depth - 1)

    def _predicted_e2e_ms(self, depth: int) -> float:
        """Expected service time for a query admitted NOW: the RECENT
        window's median (warmup-compile outliers must not inflate the
        prediction for hundreds of requests — the P² estimator converges
        too slowly for an admission decision) plus the queue ahead of it
        (queries coalesce per tick, so depth adds tick intervals, not
        full service times)."""
        p50 = None
        window_p50 = getattr(self.tracker, "window_p50_ms", None)
        if window_p50 is not None:
            p50 = window_p50()
        if p50 is None:
            qs = self.tracker.quantiles_ms()
            p50 = qs[0.5] if qs is not None else self.tick_interval_ms
        return p50 + depth * self.tick_interval_ms * 0.5

    def _retry_after_s(self, depth: int) -> int:
        """Honest Retry-After: the time for the current queue to clear at
        one batch per tick, at least one second."""
        ticks = depth / max(1.0, float(self.config.admission_queue)) + 1.0
        return max(1, round(ticks * self.tick_interval_ms / 1e3))

    # -- device-time budgeting (commit loop) -------------------------------
    def serving_active(self) -> bool:
        """Queries in flight or completed within the last couple of
        seconds — outside that, ingest runs unthrottled (a pure-ETL
        phase must not pay a latency tax for a QoS flag)."""
        if self.config.always_budget:
            return True
        with self._lock:
            if self._queue_depth > 0:
                return True
        return _time.monotonic() < self._serving_active_until

    def ingest_row_budget(self) -> int:
        """Max ingest rows this tick may drain. Called once per tick by
        the streaming loop, before draining non-serving sources.

        Outside a serving phase the partition relaxes GRADUALLY (x4 per
        tick, see :meth:`on_tick`) instead of snapping open: a backlog
        deferred while queries were in flight must drain over several
        bounded ticks, not ride one monster tick that stalls the next
        query burst behind seconds of catch-up work. The relaxed ceiling
        is ``max_ingest_rows``, never unlimited: with QoS armed it
        bounds any single tick's ingest batch (a connector bulk-pushing
        a million rows between ticks must not hand the next tick a
        million-row drain for the following query burst to queue
        behind)."""
        cfg = self.config
        if not self.serving_active():
            return max(cfg.min_ingest_rows,
                       min(cfg.max_ingest_rows, int(self._rows_per_tick)))
        if cfg.query_budget_ms is not None:
            # fixed partition: reserve query_budget_ms of the tick's
            # device time, spend the rest on ingest at the learned
            # per-row cost; before the first cost sample, fall back to
            # the adaptive allowance
            ingest_ms = max(0.0, self.tick_interval_ms
                            - cfg.query_budget_ms)
            rows = self._cost.rows_in(ingest_ms)
            if rows is not None:
                return max(cfg.min_ingest_rows,
                           min(cfg.max_ingest_rows, rows))
        return max(cfg.min_ingest_rows,
                   min(cfg.max_ingest_rows, int(self._rows_per_tick)))

    def note_deferral(self, n_rows: int) -> None:
        """One source's drain was clipped this tick, leaving ``n_rows``
        (approx.) to ride later ticks."""
        with self._lock:
            self.ingest_deferrals += 1
            self.deferred_rows_total += max(0, int(n_rows))

    def on_tick(self, *, ingest_rows: int, deferred: bool,
                tick_ms: float, device_ms: float | None = None,
                queries_in_tick: int = 0) -> None:
        """Per-tick feedback: update the cost model and steer the
        adaptive partition (AIMD — multiplicative decrease on budget
        burn, additive-ish increase when healthy)."""
        cfg = self.config
        with self._lock:
            self.ticks_budgeted += 1
            spent_ms = device_ms if device_ms is not None else tick_ms
            if ingest_rows > 0 and queries_in_tick == 0 and spent_ms > 0:
                # clean cost sample: this tick's (retired) device time
                # was all ingest. A zero device delta means the leg has
                # not resolved yet — no sample, never a zero-cost one.
                self._cost.sample(ingest_rows, spent_ms)
        if not self.serving_active():
            # no queries around: relax the partition back toward wide
            # open — GRADUALLY (x4 per tick), so the backlog deferred
            # during the serving phase drains in bounded ticks instead
            # of one monster batch (ingest_row_budget's contract)
            self._rows_per_tick = min(float(cfg.max_ingest_rows),
                                      self._rows_per_tick * 4.0)
            self.backpressure_active = False
            return
        burn = self.tracker.burn_rate()
        qs = self.tracker.quantiles_ms()
        p50 = qs[0.5] if qs is not None else None
        if burn > cfg.shed_burn_threshold \
                or (p50 is not None and p50 > self.slo_ms):
            self._rows_per_tick = max(float(cfg.min_ingest_rows),
                                      self._rows_per_tick * 0.5)
        elif burn < 0.5 * cfg.shed_burn_threshold \
                and (p50 is None or p50 < 0.75 * self.slo_ms):
            self._rows_per_tick = min(float(cfg.max_ingest_rows),
                                      self._rows_per_tick * 1.25 + 16.0)
        # backpressure to readers while the partition is actively
        # clipping drains: the supervisor stretches their poll sleeps
        self.backpressure_active = bool(
            deferred or self._rows_per_tick
            <= 2.0 * float(cfg.min_ingest_rows))

    # -- coalescing (device leg / operator step) ---------------------------
    def note_search_dispatch(self, n_queries: int) -> None:
        if n_queries < 2:
            return
        with self._lock:
            self.coalesced_dispatches += 1
            self.coalesced_queries += n_queries

    def note_answer_reuse(self, n_queries: int) -> None:
        """Queries served from the semantic result cache (or deduped
        against an identical in-batch miss) — answered with no device
        dispatch at all."""
        if n_queries < 1:
            return
        with self._lock:
            self.coalesced_answers += n_queries

    # -- surfaces ----------------------------------------------------------
    def query_budget_ms(self) -> float:
        """The current per-tick device-time reservation for query work,
        in ms (the exported gauge): the configured budget in fixed mode;
        in adaptive mode, the tick interval minus what the current row
        allowance would cost (0 until a cost sample exists or while the
        partition is wide open)."""
        cfg = self.config
        if cfg.query_budget_ms is not None:
            return cfg.query_budget_ms
        cost = self._cost.ms_per_row
        if cost is None or not self.serving_active():
            return 0.0
        ingest_ms = min(self.tick_interval_ms,
                        self._rows_per_tick * cost)
        return max(0.0, self.tick_interval_ms - ingest_ms)

    def queue_depth(self) -> int:
        with self._lock:
            return self._queue_depth

    def heartbeat_state(self) -> dict:
        """Compact QoS state for the PR-12 control-channel heartbeat —
        what the router needs to steer BEFORE p95 degrades."""
        return {
            "shedding": self.is_shedding(),
            "shed_total": self.shed_total,
            "ingest_deferrals": self.ingest_deferrals,
            "query_budget_ms": round(self.query_budget_ms(), 3),
            "admission_queue_depth": self.queue_depth(),
            "coalesced_answers": self.coalesced_answers,
        }

    def is_shedding(self) -> bool:
        """Actively refusing work: the admission queue is nearly full or
        the burn rate sits past the shed threshold (the router's
        steer-away signal)."""
        cfg = self.config
        with self._lock:
            depth = self._queue_depth
        if depth >= cfg.admission_queue:
            return True
        return self.serving_active() \
            and self.tracker.window_size() >= cfg.shed_min_samples \
            and self.tracker.burn_rate() > cfg.shed_burn_threshold

    def summary(self) -> dict:
        """/status.qos + the dashboard panel. Raw counters snapshot
        under the lock; derived values (query_budget_ms, shedding,
        serving_active) compute AFTER release — they re-acquire this
        same non-reentrant lock."""
        cfg = self.config
        with self._lock:
            out = {
                "enabled": True,
                "mode": ("fixed" if cfg.query_budget_ms is not None
                         else "adaptive"),
                "ingest_rows_per_tick": int(self._rows_per_tick),
                "ingest_ms_per_row": (
                    None if self._cost.ms_per_row is None
                    else round(self._cost.ms_per_row, 6)),
                "admission_queue_depth": self._queue_depth,
                "admission_queue_cap": cfg.admission_queue,
                "admitted_total": self.admitted_total,
                "shed_total": self.shed_total,
                "ingest_deferrals": self.ingest_deferrals,
                "deferred_rows_total": self.deferred_rows_total,
                "coalesced_dispatches": self.coalesced_dispatches,
                "coalesced_queries": self.coalesced_queries,
                "coalesced_answers": self.coalesced_answers,
                "backpressure_active": self.backpressure_active,
            }
        out["query_budget_ms"] = round(self.query_budget_ms(), 3)
        out["shedding"] = self.is_shedding()
        out["serving_active"] = self.serving_active()
        return out


def qos_enabled_from_env() -> bool | None:
    """Tri-state: the explicit ``PATHWAY_QOS`` decision, or None when
    unset (QoS defaults off; the None/False distinction feeds PWT013's
    waiver path)."""
    return _env_truthy("PATHWAY_QOS")


def resolve_qos(qos) -> QosConfig | None:
    """Normalize the ``pw.run(qos=...)`` argument: ``True`` /
    :class:`QosConfig` arm the controller, ``False`` disarms it
    explicitly, ``None`` defers to ``PATHWAY_QOS``."""
    if isinstance(qos, QosConfig):
        return qos
    if qos is True:
        return QosConfig.from_env()
    if qos is False:
        return None
    if qos is None:
        env = qos_enabled_from_env()
        return QosConfig.from_env() if env else None
    raise TypeError(
        f"qos= must be True, False, None or a QosConfig, got {qos!r}")
