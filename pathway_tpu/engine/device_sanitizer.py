"""Steady-state device-discipline sanitizer (PWT4xx's runtime twin).

The PWT4xx static pass (internals/static_check/perf_check.py) proves
properties of the *source*: no unbucketed dispatch, no hidden sync, no
implicit per-tick transfer. This module asserts the same contract about
the *execution*: once ``pw.warmup()`` has walked the bucket ladder and
declared **steady state**, a serving process must never compile another
XLA executable and never transfer host memory to the device implicitly —
either one is a silent latency cliff the static pass missed (a dynamic
dispatch the AST could not resolve, an unpinned batch dimension, a numpy
operand snuck in through a config path).

Mirrors ``engine/locking.py``'s env-armed pattern — zero overhead off:

- Default: nothing is registered, nothing is wrapped; every helper here
  is a cheap no-op behind one env check.
- ``PATHWAY_DEVICE_SANITIZER=1``: :func:`arm` (called by ``pw.warmup``)
  registers a JAX compile-event listener
  (``/jax/core/compile/backend_compile_duration`` — fires once per
  actual backend compile, never on cache hits). Compiles during the
  warmup window are counted as warmup. After
  :func:`declare_steady_state` (``pw.warmup`` calls it on completion)
  any further compile raises :class:`DeviceDisciplineViolation` naming
  the in-flight operator, tick, and user frame (via the flight
  recorder's live in-flight marker), and JAX's transfer guard is set to
  ``disallow`` so an implicit host→device operand transfer raises at
  the offending dispatch (explicit ``device_put`` / ``jnp.asarray``
  residency establishment stays legal — that is the fix, not the bug).
- ``PATHWAY_DEVICE_SANITIZER=report``: violations are recorded
  (:func:`violations`) and logged, never raised; the transfer guard
  uses ``log`` (C++ stderr lines) instead of ``disallow``.

Maintenance windows — slab growth, recovery, re-warming — are legal
compile sites: wrap them in :func:`suspend_steady_state`, which lifts
the guard for the block and restores it after. ``pw.warmup`` itself
suspends while it walks the ladder, so re-warming an armed process
counts as warmup, not violation.

Benches count compiles with the sanitizer OFF through
:func:`install_compile_counter`, which registers the same listener
purely as a counter (no env gate, no guard) — bench.py's per-leg
compile-count columns ride on it.
"""

from __future__ import annotations

import contextlib
import logging
import os
import threading

from pathway_tpu.engine.locking import create_lock

logger = logging.getLogger(__name__)

__all__ = [
    "DeviceDisciplineViolation", "arm", "declare_steady_state",
    "in_steady_state", "install_compile_clock", "install_compile_counter",
    "post_warmup_compiles",
    "sanitizer_enabled", "suspend_steady_state", "violations",
    "warmup_compiles",
]

#: the JAX monitoring event that fires once per actual backend compile
#: (cache hits — persistent or in-process — never emit it)
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def sanitizer_enabled() -> bool:
    """Truthy ``PATHWAY_DEVICE_SANITIZER`` arms the sanitizer. Checked at
    arm/declare time — a run toggles by env, and the disabled path stays
    a no-op behind this one check."""
    return os.environ.get("PATHWAY_DEVICE_SANITIZER", "").strip().lower() \
        in ("1", "true", "on", "yes", "report", "warn")


def _raise_on_violation() -> bool:
    return os.environ.get("PATHWAY_DEVICE_SANITIZER", "").strip().lower() \
        not in ("report", "warn")


class DeviceDisciplineViolation(RuntimeError):
    """A post-warmup XLA compile (or implicit transfer) landed inside the
    steady-state serving window — a latency cliff on a live tick that
    warmup was supposed to have eliminated."""


class _SanitizerState:
    """Process-wide bookkeeping. One instance per process; tests swap in
    a fresh one via :func:`_reset_for_tests` (the JAX listener is
    registered once per process and reads whatever state is current)."""

    def __init__(self):
        self.mutex = create_lock("device_sanitizer.state")
        self.armed = False
        self.steady = False
        self.warmup_compiles = 0
        self.post_warmup_compiles = 0
        self.total_compiles = 0
        self.total_compile_s = 0.0
        # the same by the name of the thread that compiled
        self.compile_s_by_thread: dict[str, float] = {}
        self.violation_log: list[dict] = []


_STATE = _SanitizerState()
# jax.monitoring offers no unregistration, so the listener is installed
# at most once per process and consults the live _STATE on every event
_LISTENER_INSTALLED = False


def _reset_for_tests() -> None:
    """Fresh counters/flags (unit tests only). Also drops any leftover
    transfer guard so one test's steady state cannot poison the next."""
    global _STATE
    _STATE = _SanitizerState()
    _set_transfer_guard("allow")


def _inflight_context() -> str:
    """``operator=... tick=... at <user frame>`` from the flight
    recorder's live in-flight marker, or a stub when nothing records."""
    try:
        from pathway_tpu.engine.flight_recorder import live_inflight

        info = live_inflight()
    except Exception:
        info = None
    if not info:
        return "no operator in flight (dispatch outside the engine loop?)"
    return (f"operator {info.get('operator')!r} "
            f"(class {info.get('op_class')}) tick={info.get('tick')} "
            f"at {info.get('user_frame')}")


def _record_violation(kind: str, message: str) -> None:
    with _STATE.mutex:
        _STATE.violation_log.append({"kind": kind, "message": message})
    if _raise_on_violation():
        raise DeviceDisciplineViolation(message)
    logger.error("device sanitizer: %s", message)


def violations() -> list[dict]:
    """Violations recorded so far (raise mode records before raising, so
    post-mortems and tests can read the full list either way)."""
    with _STATE.mutex:
        return list(_STATE.violation_log)


def warmup_compiles() -> int:
    """Backend compiles observed while armed but before steady state —
    the warmup window's legitimate ladder walk."""
    return _STATE.warmup_compiles


def post_warmup_compiles() -> int:
    """Backend compiles observed after :func:`declare_steady_state` —
    the number the serving canary gates at zero."""
    return _STATE.post_warmup_compiles


def in_steady_state() -> bool:
    return _STATE.steady


def _on_compile_event(event: str, duration: float, **_kw) -> None:
    """The one listener, installed once per process. Raising from here
    propagates to the dispatching call site (verified: the jit cache is
    unaffected and the next dispatch retries cleanly), which is exactly
    where the violation belongs."""
    if event != _COMPILE_EVENT:
        return
    with _STATE.mutex:
        _STATE.total_compiles += 1
        _STATE.total_compile_s += duration
        thread = threading.current_thread().name
        _STATE.compile_s_by_thread[thread] = \
            _STATE.compile_s_by_thread.get(thread, 0.0) + duration
        if not _STATE.armed:
            return
        if not _STATE.steady:
            _STATE.warmup_compiles += 1
            return
        _STATE.post_warmup_compiles += 1
    _record_violation(
        "post-warmup-compile",
        f"XLA backend compile ({duration * 1e3:.0f} ms) inside the "
        f"steady-state serving window: {_inflight_context()} — an "
        f"unwarmed shape reached a jitted kernel; bucket the dispatch "
        f"or extend pw.warmup's ladder (wrap legitimate maintenance "
        f"compiles in device_sanitizer.suspend_steady_state())")


def _install_listener() -> None:
    global _LISTENER_INSTALLED
    if _LISTENER_INSTALLED:
        return
    import jax

    jax.monitoring.register_event_duration_secs_listener(
        _on_compile_event)
    _LISTENER_INSTALLED = True


def install_compile_counter():
    """Register the compile listener purely as a counter (no env gate,
    no guard, nothing ever raises) and return a zero-arg callable
    yielding the process-lifetime backend-compile count. bench.py's
    per-leg compile columns diff it around each leg."""
    _install_listener()
    return lambda: _STATE.total_compiles


def install_compile_clock(thread: str | None = None):
    """As :func:`install_compile_counter`, but the callable yields the
    seconds spent in backend compiles so far: by the whole process, or
    with ``thread`` by the threads whose name holds it. A device leg's
    cost model (engine/qos.py) takes the bridge worker's out of the leg's
    time: what another thread compiled meanwhile (a warm-up of the query
    path beside the first ingest legs) is no part of a leg."""
    _install_listener()
    if thread is None:
        return lambda: _STATE.total_compile_s

    def on_thread() -> float:
        with _STATE.mutex:
            return sum(s for name, s in _STATE.compile_s_by_thread.items()
                       if thread in name)

    return on_thread


def _set_transfer_guard(mode: str) -> None:
    import jax

    jax.config.update("jax_transfer_guard_host_to_device", mode)


def arm() -> bool:
    """Install the compile listener and open the warmup window (compiles
    count as warmup until :func:`declare_steady_state`). Idempotent;
    no-op (returns False) unless ``PATHWAY_DEVICE_SANITIZER`` is set.
    ``pw.warmup`` calls this on entry."""
    if not sanitizer_enabled():
        return False
    _install_listener()
    with _STATE.mutex:
        _STATE.armed = True
        _STATE.steady = False
    _set_transfer_guard("allow")
    return True


def declare_steady_state() -> bool:
    """Close the warmup window: from here on, any backend compile is a
    violation and implicit host→device transfers are guarded
    (``disallow`` in raise mode, ``log`` in report mode). ``pw.warmup``
    calls this on completion; idempotent; no-op unless armed."""
    if not sanitizer_enabled():
        return False
    _install_listener()
    with _STATE.mutex:
        _STATE.armed = True
        _STATE.steady = True
    _set_transfer_guard("disallow" if _raise_on_violation() else "log")
    return True


@contextlib.contextmanager
def suspend_steady_state(why: str = ""):
    """Temporarily lift steady state for a legitimate maintenance window
    (slab growth, recovery, re-warming): compiles inside the block count
    as warmup, the transfer guard is dropped, and the previous state is
    restored on exit. Free when the sanitizer is off."""
    if not _STATE.steady:
        yield
        return
    logger.info("device sanitizer: steady state suspended%s",
                f" ({why})" if why else "")
    with _STATE.mutex:
        _STATE.steady = False
    _set_transfer_guard("allow")
    try:
        yield
    finally:
        declare_steady_state()
