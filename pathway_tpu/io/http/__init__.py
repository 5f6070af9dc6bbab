"""pw.io.http — REST server connector + streaming HTTP client.

Rebuild of the reference's rest_connector (python/pathway/io/http/_server.py:624
+ PathwayWebserver:329): each HTTP request becomes a row in a query table;
`response_writer` resolves the awaiting request when the pipeline emits the
row with the same key. This is the serving front door of the RAG stack
(SURVEY §3.3).
"""

from __future__ import annotations

import asyncio
import itertools
import json as _json
import os as _os
import threading
import time as _time
from typing import Any

from pathway_tpu.internals import dtype as dt
from pathway_tpu.internals import schema as sch
from pathway_tpu.internals.json import Json
from pathway_tpu.internals.keys import Pointer, hash_values
from pathway_tpu.internals.parse_graph import G
from pathway_tpu.internals.table import Plan, Table
from pathway_tpu.internals.universe import Universe
from pathway_tpu.engine.qos import QueryShedError
from pathway_tpu.io._datasource import (DataSource, Session,
                                         apply_connector_policy)


# -- request-id assignment (serving-path SLO tracing) -------------------------
# Every request entering the webserver gets an id at ingress — ADOPTED from an
# inbound X-Pathway-Request-Id header when the router (or a calling service)
# already named the query, minted fresh otherwise — echoed back in the
# X-Pathway-Request-Id response header and propagated (out of band — never
# inside engine rows) through the request tracker
# (engine/request_tracker.py, README "Serving SLO"; fleet propagation contract
# in engine/fleet_observability.py).

_rid_counter = itertools.count(1)
_rid_prefix: str | None = None


def _next_request_id() -> str:
    global _rid_prefix
    if _rid_prefix is None:
        _rid_prefix = _os.urandom(3).hex()
    return f"{_rid_prefix}-{next(_rid_counter):06d}"


_RID_MAX_LEN = 128
_RID_OK = frozenset(
    "abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789-_.:")


def _adopt_request_id(inbound: str | None) -> str:
    """Adopt the inbound ``X-Pathway-Request-Id`` (the fleet propagation
    contract: the router — or a calling service — already named this
    query, and one id must span every process it crosses) or mint a
    fresh one. Inbound ids are sanitized, not trusted: an id with
    characters outside the safe set, or past the length cap, would leak
    header junk into traces and metric labels — such requests get a
    local id instead (the response still carries the id actually
    used)."""
    if inbound:
        rid = inbound.strip()
        if rid and len(rid) <= _RID_MAX_LEN \
                and all(c in _RID_OK for c in rid):
            return rid
    return _next_request_id()


class RequestContext:
    """Ingress metadata handed to route handlers that accept a second
    positional argument: the assigned request id and the arrival stamp
    (perf_counter) taken before any parsing."""

    __slots__ = ("request_id", "ingress_t")

    def __init__(self, request_id: str, ingress_t: float):
        self.request_id = request_id
        self.ingress_t = ingress_t


def _accepts_ctx(handler) -> bool:
    """Does the handler take (payload, ctx)? Probed once at register time
    so plain single-argument handlers keep working unchanged."""
    import inspect

    try:
        sig = inspect.signature(handler)
    except (TypeError, ValueError):
        return False
    positional = 0
    for p in sig.parameters.values():
        if p.kind in (inspect.Parameter.POSITIONAL_ONLY,
                      inspect.Parameter.POSITIONAL_OR_KEYWORD):
            positional += 1
        elif p.kind == inspect.Parameter.VAR_POSITIONAL:
            return True
    return positional >= 2


class PathwayWebserver:
    """Shared aiohttp server; multiple rest_connectors can register routes
    (reference: _server.py:329 with OpenAPI docs at /_schema)."""

    def __init__(self, host: str = "0.0.0.0", port: int = 8080,
                 with_schema_endpoint: bool = True, with_cors: bool = False):
        self.host = host
        self.port = port
        self._routes: dict[tuple[str, str], Any] = {}
        # (method, route) -> "custom" | "raw"; keyed per method so two
        # connectors sharing a route cannot clobber each other's format
        self._formats: dict[tuple[str, str], str] = {}
        # (method, route) -> handler takes (payload, RequestContext)
        self._wants_ctx: dict[tuple[str, str], bool] = {}
        self._openapi: dict = {"openapi": "3.0.3",
                               "info": {"title": "pathway-tpu", "version": "1"},
                               "paths": {}}
        self._started = threading.Event()
        self._loop: asyncio.AbstractEventLoop | None = None
        self._thread: threading.Thread | None = None
        self.with_schema_endpoint = with_schema_endpoint
        # allow cross-origin requests (reference: aiohttp_cors with
        # allow-all defaults, _server.py:361-371; implemented here as
        # plain headers + OPTIONS preflight, no extra dependency)
        self.with_cors = with_cors

    def register(self, route: str, methods: tuple[str, ...], handler,
                 schema: type[sch.Schema] | None,
                 format: str = "custom") -> None:
        keys = [(m.upper(), route) for m in methods]
        for key in keys:  # validate every method before mutating any
            if self._formats.get(key, format) != format:
                raise ValueError(
                    f"route {key[0]} {route} is already registered with "
                    f"input format {self._formats[key]!r}; refusing to "
                    f"re-register it as {format!r}")
        wants_ctx = _accepts_ctx(handler)
        for key in keys:
            self._routes[key] = handler
            self._formats[key] = format
            self._wants_ctx[key] = wants_ctx
        if schema is not None:
            props = {
                c.name: {"type": _openapi_type(c.dtype)}
                for c in schema.columns().values()
            }
            self._openapi["paths"][route] = {
                m.lower(): {
                    "requestBody": {"content": {"application/json": {
                        "schema": {"type": "object", "properties": props}}}},
                    "responses": {"200": {"description": "ok"}},
                } for m in methods
            }

    def start(self) -> None:
        if self._thread is not None:
            return
        from aiohttp import web

        _CORS = {
            "Access-Control-Allow-Origin": "*",
            "Access-Control-Allow-Methods": "*",
            "Access-Control-Allow-Headers": "*",
        }

        async def dispatch(request):
            if self.with_cors and request.method == "OPTIONS":
                return web.Response(status=204, headers=_CORS)
            resp = await _dispatch_inner(request)
            if self.with_cors:
                resp.headers.update(_CORS)
            return resp

        async def _dispatch_inner(request):
            # ingress stamp BEFORE any parsing: the request id is born
            # here and the ingress_wait stage starts here
            t_ingress = _time.perf_counter()
            route_key = (request.method, request.path)
            handler = self._routes.get(route_key)
            if handler is None:
                if request.path == "/_schema" and self.with_schema_endpoint:
                    # reference serves yaml by default with ?format=json
                    # (_server.py:427-445)
                    fmt = request.query.get("format", "yaml")
                    if fmt == "json":
                        return web.json_response(self._openapi)
                    if fmt != "yaml":
                        return web.Response(
                            status=400,
                            text=f"Unknown format: {fmt!r}. Supported "
                                 "formats: 'json', 'yaml'")
                    try:
                        import yaml as _yaml

                        text = _yaml.safe_dump(self._openapi,
                                               sort_keys=False)
                    except ImportError:
                        return web.json_response(self._openapi)
                    return web.Response(status=200, text=text,
                                        content_type="text/x-yaml")
                return web.Response(status=404, text="no such route")
            rid = _adopt_request_id(
                request.headers.get("X-Pathway-Request-Id"))
            rid_header = {"X-Pathway-Request-Id": rid}
            try:
                fmt = self._formats.get(route_key, "custom")
                if fmt == "raw":
                    # raw format: the whole body IS the query value, for
                    # every method — a bodyless GET yields {'query': ''}
                    # (reference: _server.py:526-527 QUERY_SCHEMA_COLUMN)
                    payload = {"query": await request.text()}
                elif request.method in ("POST", "PUT", "PATCH"):
                    try:
                        payload = await request.json()
                        if not isinstance(payload, dict):
                            payload = {}
                    except Exception:
                        # reference custom-format semantics: unparseable
                        # body -> {}, missing required fields then 400
                        payload = {}
                    for param, value in request.query.items():
                        payload.setdefault(param, value)
                else:
                    payload = dict(request.query)
                if self._wants_ctx.get(route_key):
                    result = await handler(
                        payload, RequestContext(rid, t_ingress))
                else:
                    result = await handler(payload)
                if isinstance(result, (dict, list)):
                    return web.json_response(result, headers=rid_header)
                return web.Response(text=str(result), headers=rid_header)
            except _BadRequest as e:
                return web.Response(status=400, text=str(e),
                                    headers=rid_header)
            except QueryShedError as e:
                # QoS admission shed (engine/qos.py): a fast 503 with the
                # request id AND Retry-After — the unified 503 contract
                # (the router's unroutable/fleet-dead 503s carry the same
                # pair). Shedding is visible, never silent: the
                # controller already counted this query in shed_total.
                return web.Response(
                    status=503, text=f"query shed: {e.reason}",
                    headers={**rid_header,
                             "Retry-After": str(e.retry_after_s)})
            except Exception as e:
                return web.Response(status=500, text=repr(e),
                                    headers=rid_header)

        async def main():
            app = web.Application()
            app.router.add_route("*", "/{tail:.*}", dispatch)
            runner = web.AppRunner(app)
            await runner.setup()
            site = web.TCPSite(runner, self.host, self.port)
            await site.start()
            if self.port == 0:
                # ephemeral port requested: publish the bound one so
                # clients (tests, bench) can find the endpoint
                socks = getattr(site._server, "sockets", None)
                if socks:
                    self.port = socks[0].getsockname()[1]
            self._started.set()
            while True:
                await asyncio.sleep(3600)

        def run_loop():
            self._loop = asyncio.new_event_loop()
            asyncio.set_event_loop(self._loop)
            try:
                self._loop.run_until_complete(main())
            except Exception:
                self._started.set()

        from pathway_tpu.engine.threads import spawn

        self._thread = spawn(run_loop, name="webserver")
        self._started.wait(timeout=10)


class _BadRequest(ValueError):
    pass


def _openapi_type(d) -> str:
    from pathway_tpu.internals import dtype as dtm

    base = dtm.unoptionalize(d)
    if base is dtm.INT:
        return "integer"
    if base is dtm.FLOAT:
        return "number"
    if base is dtm.BOOL:
        return "boolean"
    return "string"


class RestSource(DataSource):
    name = "rest"
    # QoS admission control (engine/qos.py): the streaming runtime wires
    # the run's controller here when QoS is armed; None keeps the gate a
    # dead branch. Admission runs BEFORE session.push — a shed query
    # never enters the engine.
    qos = None
    # request-scoped tracing (engine/request_tracker.py): the streaming
    # runtime wires the run's tracker here when the flight recorder is on;
    # None keeps every stamp a dead branch
    request_tracker = None
    # replica mode (engine/replica.py): serving sources run LIVE on a
    # read replica — queries are per-process ephemeral ingress, never
    # tailed from the primary's WAL (the primary's own recorded query
    # stream is skipped; resolve() already ignores unknown keys)
    replica_serve_live = True

    def __init__(self, webserver: PathwayWebserver, route: str,
                 methods: tuple[str, ...], schema,
                 delete_completed_queries: bool,
                 autocommit_duration_ms=50, request_validator=None,
                 format: str = "custom", durable_ack: bool = False):
        # the period bounds a request's wait for a tick from above: its
        # push wakes the commit loop (the runtime fills ``Session.wake``
        # for a source that declares ``request_tracker``)
        super().__init__(schema, autocommit_duration_ms)
        self.webserver = webserver
        self.route = route
        self.methods = methods
        self.format = format
        self.delete_completed_queries = delete_completed_queries
        self.request_validator = request_validator
        self.pending: dict[Pointer, tuple[asyncio.AbstractEventLoop,
                                          asyncio.Event, list]] = {}
        # durable acknowledgement (write routes): a computed response is
        # parked here by tick and released only after the commit
        # watermark — i.e. the fsynced WAL — covers that tick, so an
        # HTTP 200 means the write survives SIGKILL (replayed on
        # restart, tailed by every replica). A durable-ack route is
        # necessarily primary state, so replicas TAIL it instead of
        # serving it live.
        self.durable_ack = durable_ack
        if durable_ack:
            self.replica_serve_live = False  # instance shadows class
        self._unacked: dict[int, list] = {}
        self._session: Session | None = None
        self._seq = 0
        from pathway_tpu.engine.locking import create_lock

        self._lock = create_lock("RestSource._lock")

    def run(self, session: Session) -> None:
        self._session = session

        async def handler(payload: dict, ctx=None):
            for col in self.schema.columns().values():
                if col.name not in payload:
                    if col.has_default_value:
                        payload[col.name] = col.default_value
                    else:
                        raise _BadRequest(
                            f"field {col.name!r} is required")
            if self.request_validator is not None:
                err = self.request_validator(payload)
                if err:
                    raise _BadRequest(str(err))
            # request-scoped span: the webserver-assigned id + ingress
            # stamp start it; the commit loop / scheduler / resolve add
            # their stamps; finish() in the finally aggregates (or drops
            # an unresolved span — client disconnect, handler error)
            tracker = self.request_tracker
            span = None
            if tracker is not None and ctx is not None:
                span = tracker.start(ctx.request_id, self.route,
                                     ctx.ingress_t)
            qos = self.qos
            admitted = False
            try:
                if span is not None:
                    # opens the admission_wait stage: everything from
                    # here to the enqueue stamp is time spent at the
                    # QoS gate (~0 with QoS off)
                    tracker.admission(span)
                if qos is not None:
                    # bounded grace for a full queue (absorbs a
                    # micro-burst without blocking the event loop);
                    # admit() makes the final counted decision and
                    # raises QueryShedError on shed — mapped to a fast
                    # 503 + Retry-After by the dispatcher above
                    grace_s = qos.config.admission_grace_ms / 1e3
                    if grace_s > 0:
                        t_gate = _time.perf_counter()
                        while not qos.admission_has_capacity() \
                                and _time.perf_counter() - t_gate \
                                < grace_s:
                            await asyncio.sleep(0.002)
                    qos.admit(ctx.ingress_t if ctx is not None
                              else _time.perf_counter())
                    admitted = True
                with self._lock:
                    self._seq += 1
                    seq = self._seq
                key, row = self.row_to_engine(payload, seq)
                key = hash_values("rest", self._uid, seq)
                loop = asyncio.get_event_loop()
                event = asyncio.Event()
                slot: list = [None]
                self.pending[key] = (loop, event, slot)
                if span is not None:
                    # registered BEFORE push: the commit loop may drain
                    # (and stamp tick pickup on) the row immediately
                    tracker.enqueued(span, key)
                session.push(key, row, 1)
                await event.wait()
                if self.delete_completed_queries:
                    session.push(key, row, -1)
                return slot[0]
            finally:
                if admitted:
                    qos.finish_query()
                if span is not None:
                    tracker.finish(span)

        self.webserver.register(self.route, self.methods, handler,
                                self.schema, format=self.format)
        self.webserver.start()
        # stay alive until the runtime requests stop (sources close when
        # run() returns; waiting on the session's stop event — not a
        # private never-set one — lets teardown actually join this thread)
        session.stopping.wait()

    def resolve(self, key: Pointer, value: Any) -> None:
        tracker = self.request_tracker
        if tracker is not None:
            # stamped before waking the handler so the response_write
            # stage starts at resolution, not at event delivery
            tracker.resolved(key)
        entry = self.pending.pop(key, None)
        if entry is None:
            return
        loop, event, slot = entry
        slot[0] = value
        loop.call_soon_threadsafe(event.set)

    # -- durable acknowledgement (engine/streaming.py commit loop) ----------
    def buffer_ack(self, time: int, key: Pointer, value: Any) -> None:
        """``durable_ack`` mode: park a computed response until the WAL
        covers its tick. Rows without a local waiter (a replica applying
        the primary's tailed write stream computes responses too) are
        dropped here — nothing to acknowledge, nothing to leak."""
        if key not in self.pending:
            return
        self._unacked.setdefault(int(time), []).append((key, value))

    def on_commit_watermark(self, watermark: int) -> None:
        """Release every parked response whose tick the fsynced WAL now
        covers. Called by the commit loop right after a successful
        ``persistence.commit`` — the same thread that buffers, so the
        dict needs no lock."""
        if not self._unacked:
            return
        for t in sorted(t for t in self._unacked if t <= watermark):
            for key, value in self._unacked.pop(t):
                self.resolve(key, value)

    # -- persistence resume protocol (engine/persistence.attach_source) -----
    def seek(self, replayed: list) -> None:
        # push-based source: the durable prefix replays from the WAL
        # (or the promoted replica already tailed it) and every live
        # HTTP request is NEW — there is nothing to re-emit, so nothing
        # to position past. Without this, the prefix-skip fallback
        # would silently drop the first len(replayed) live requests
        # after a restart or a promotion.
        return

    def seek_snapshot(self, state: dict, replayed: list) -> None:
        # same contract as seek(): the compacted prefix holds requests
        # whose responses were delivered long ago; live traffic is new
        return


def rest_connector(host: str | None = None, port: int | None = None, *,
                   webserver: PathwayWebserver | None = None,
                   route: str = "/", schema: type[sch.Schema] | None = None,
                   methods: tuple[str, ...] = ("POST",),
                   autocommit_duration_ms: int | None = 50,
                   keep_queries: bool | None = None,
                   delete_completed_queries: bool = False,
                   request_validator=None,
                   format: str | None = None,
                   documentation=None,
                   persistent_id: str | None = None,
                   durable_ack: bool = False) -> tuple[Table, Any]:
    """Returns (query_table, response_writer). ``format="custom"``
    parses the JSON body and merges URL query params, 400-ing on missing
    required fields; ``format="raw"`` takes the whole request body as the
    ``query`` column. With no explicit format, a schemaless endpoint
    infers ``raw`` (a plain-text POST yields ``{'query': body}``) and a
    schema-ful one infers ``custom``
    (reference: _server.py:50,525-535,733-736).

    ``autocommit_duration_ms`` is the longest a request waits for a
    commit tick, not what it waits on average: a request pushed here
    wakes the commit loop (engine/streaming.py), and the tick it wakes
    drains the serving sources alone, at once or, behind a device leg in
    flight, when that leg retires. The period's own ticks
    keep the cadence of ingest (the rows of every other source are
    drained, sealed and budgeted by them alone), and under a
    multi-process cluster, whose ticks are a lock-step exchange, a
    request rides them too.

    ``persistent_id`` records the route's rows in the WAL like any other
    persisted source — required for write routes whose state must
    survive restarts and be tailed by replicas. ``durable_ack`` holds
    each HTTP response until the commit watermark covers the request's
    tick: a 200 then *means* the write is fsynced in the WAL (replayed
    on restart, promoted with the fleet — the failover zero-loss
    guarantee quantifies over exactly these acknowledged writes). It
    also marks the route as primary state, so replicas tail it instead
    of serving it live."""
    if format is None:
        format = "raw" if schema is None else "custom"
    if format not in ("custom", "raw"):
        raise ValueError(f"unknown endpoint input format: {format!r} "
                         "(use 'custom' or 'raw')")
    if webserver is None:
        webserver = PathwayWebserver(host or "0.0.0.0", port or 8080)
    if schema is None:
        schema = sch.schema_from_types(query=dt.ANY)
    if format == "raw" and "query" not in schema.column_names():
        raise ValueError(
            "'raw' endpoint input format requires a 'query' column "
            "in the schema")
    source = RestSource(webserver, route, methods, schema,
                        delete_completed_queries,
                        autocommit_duration_ms=autocommit_duration_ms,
                        request_validator=request_validator,
                        format=format, durable_ack=durable_ack)
    if persistent_id is not None:
        source.persistent_id = persistent_id
    table = Table(Plan("input", datasource=source), schema, Universe(),
                  name=f"rest:{route}")

    def response_writer(response_table: Table) -> None:
        names = response_table.column_names()

        def binder(runner):
            def callback(time, delta):
                for key, row, diff in delta.entries:
                    if diff <= 0:
                        continue
                    if len(names) == 1:
                        value = row[0]
                    else:
                        value = dict(zip(names, row))
                    value = _jsonable(value)
                    if source.durable_ack:
                        # parked until the WAL covers this tick; the
                        # commit loop releases it (on_commit_watermark)
                        source.buffer_ack(time, key, value)
                    else:
                        source.resolve(key, value)

            runner.subscribe(response_table, callback)

        G.add_output(binder, table=response_table, sink="http.response",
                     format="json")

    return table, response_writer


def _jsonable(value):
    if isinstance(value, Json):
        return value.value
    if isinstance(value, dict):
        return {k: _jsonable(v) for k, v in value.items()}
    if isinstance(value, (list, tuple)):
        return [_jsonable(v) for v in value]
    if isinstance(value, Pointer):
        return str(value)
    import numpy as np

    if isinstance(value, np.ndarray):
        return value.tolist()
    if isinstance(value, (np.integer, np.floating)):
        return value.item()
    return value


# -- streaming HTTP client (reference: io/http/_streaming.py) ----------------

def read(url: str, *, schema=None, format: str = "json",
         autocommit_duration_ms: int | None = 1500, name=None,
         **kwargs) -> Table:
    """Stream the lines of ``url`` as rows. An ingest source:
    ``autocommit_duration_ms`` is the cadence at which its rows are
    committed (the runtime ticks at the smallest period of its sources);
    a row wakes no tick, unlike a ``rest_connector``'s request."""
    import urllib.request

    from pathway_tpu.io._datasource import CallbackSource

    if schema is None:
        schema = sch.schema_from_types(data=dt.ANY)

    def gen():
        with urllib.request.urlopen(url) as resp:
            for line in resp:
                line = line.decode().strip()
                if not line:
                    continue
                if format == "json":
                    yield _json.loads(line)
                else:
                    yield {"data": line}

    source = CallbackSource(gen, schema,
                            autocommit_duration_ms=autocommit_duration_ms,
                            name="http")
    apply_connector_policy(source, kwargs)
    return Table(Plan("input", datasource=source), schema, Universe(),
                 name=name or "http_input")


def write(table: Table, url: str, *, method: str = "POST", format: str = "json",
          name=None, n_retries: int = 0, retry_delay_s: float = 0.5,
          request_timeout_ms: int | None = None, **kwargs) -> None:
    """POST each diff as flat JSON with time/diff fields. Failures retry
    ``n_retries`` times with exponential backoff (the reference's output
    writer retry loop, src/retry.rs + OUTPUT_RETRIES, dataflow.rs:133)
    and are LOGGED on final failure — never silently dropped."""
    import logging
    import time as _time
    import urllib.request

    names = table.column_names()
    timeout = (request_timeout_ms / 1000.0) if request_timeout_ms else 10.0
    log = logging.getLogger(__name__)

    def binder(runner):
        def callback(time, delta):
            for key, row, diff in delta.entries:
                rec = dict(zip(names, row))
                rec.update({"time": time, "diff": diff})
                req = urllib.request.Request(
                    url, data=_json.dumps(_jsonable(rec)).encode(),
                    method=method,
                    headers={"Content-Type": "application/json"})
                for attempt in range(n_retries + 1):
                    try:
                        urllib.request.urlopen(req, timeout=timeout)
                        break
                    except Exception as e:
                        if attempt == n_retries:
                            log.error(
                                "http sink %s: delivery failed after %d "
                                "attempt(s): %s", url, attempt + 1, e)
                        else:
                            _time.sleep(retry_delay_s * (2 ** attempt))

        runner.subscribe(table, callback)

    G.add_output(binder, table=table, sink="http", format="json")
