"""What one run collected, as the per-layer readers see it, and the
listeners that collect the parts JAX itself reports."""

from __future__ import annotations

import dataclasses
import time
from typing import Any


class JitLog:
    """Every trace, lowering and backend compile of the process, with the
    ``perf_counter`` instant it ended at, from ``jax.monitoring``. A program
    loaded from the persistent cache still raises the backend-compile event
    (JAX counts the retrieval under it); ``cache_hits`` counts those."""

    STAGES = ("jaxpr_trace", "jaxpr_to_mlir_module", "backend_compile")

    def __init__(self):
        self.events: list[tuple[float, str, str, float]] = []
        self.cache_hits = 0
        self.cache_misses = 0

    def install(self) -> None:
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def uninstall(self) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)

    def _duration(self, event: str, duration: float, **kw) -> None:
        stage = event.rsplit("/", 1)[-1].removesuffix("_duration")
        if event.startswith("/jax/core/compile/") and stage in self.STAGES:
            self.events.append((time.perf_counter(), stage,
                                str(kw.get("fun_name", "?")), duration))

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def programs(self, t0: float = float("-inf"),
                 t1: float = float("inf")) -> list[str]:
        """Names of the programs whose backend compile ended in [t0, t1]."""
        return [name for t, stage, name, _d in self.events
                if stage == "backend_compile" and t0 <= t <= t1]

    def seconds(self, stage: str, t0: float = float("-inf"),
                t1: float = float("inf")) -> float:
        return sum(d for t, s, _n, d in self.events
                   if s == stage and t0 <= t <= t1)


@dataclasses.dataclass
class Run:
    """A finished run. Times are ``time.perf_counter()`` seconds unless a
    field says otherwise. A reader takes what it needs and returns None
    where that is missing (an untraced run has no ``trace``, a cell without
    queries no ``requests``)."""

    cell: Any                      # benchmark.lib.spec.Cell
    t_start: float                 # process start
    w0: float                      # the measured window
    w1: float
    before: dict                   # system.counters() at the window's edges
    after: dict
    jit: JitLog
    results: list = dataclasses.field(default_factory=list)  # loadgen.Sent
    #: request-tracker records (engine/request_tracker.py ``completed``) of
    #: the window's /v1/retrieve requests; traced runs only
    requests: list = dataclasses.field(default_factory=list)
    #: benchmark-side spans around calls into the program, by name:
    #: (t0, t1, meta); traced runs only
    spans: dict = dataclasses.field(default_factory=dict)
    trace: Any = None              # benchmark.lib.trace.Reduced
    traced: tuple[float, float] | None = None   # the traced part of w0..w1
    samples: list = dataclasses.field(default_factory=list)  # host sampler
    extras: dict = dataclasses.field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return self.w1 - self.w0

    def window_queries(self) -> list:
        return [r for r in self.results if r.event.kind == "query"
                and self.w0 <= r.due < self.w1]

    def spans_in(self, name: str, window: tuple[float, float] | None = None
                 ) -> list:
        lo, hi = window or (self.w0, self.w1)
        return [s for s in self.spans.get(name, ()) if lo <= s[0] and
                s[1] <= hi]
