"""XLA compile observability: the first call of every step program, and what
it spent.

``ModelRunner`` bounds the set of compiled programs with a bucket lattice
(pow2 batch/time/page buckets, see ``engine/runner.py``) — but the lattice is
data-dependent, so production traffic can still walk into shapes nothing
warmed up, and a recompile on the serving path is a silent multi-hundred-ms
stall (bench.py PR 2 had to add identical-dry-run warm-ups for exactly this
reason). No generic tool sees it: JAX compiles inside the dispatch call.

The :class:`CompileTracker` hangs off the runner and observes every dispatch
site *after* padding: the cache key is the padded bucket signature (program
kind + every static shape/arg the jit specializes on), so it tracks exactly
what XLA's own cache tracks. Detection is key-novelty: the first call of a key
makes one event, re-hits of a seen key emit nothing.

**What the event says.** ``reason`` is what the serving path felt: the first
call took ``DYN_COMPILE_THRESHOLD_MS`` or more (``new_shape``) or less
(``warm_cache``). It is no statement about a cache: on a v5e a first call out
of the persistent cache costs seconds. ``cache`` is that fact, from JAX's own
events, and beside it the call's parts. While a first call is open on a thread
(:class:`timed_dispatch` opens a :class:`FirstCall` only for a key its tracker
has not seen) one pair of ``jax.monitoring`` listeners, installed once a
process, adds what JAX reports on that thread to it:

- ``trace_ms`` / ``lower_ms`` / ``backend_ms``: tracing to a jaxpr, lowering
  it to MLIR, and the backend (XLA and Mosaic on a miss; the persistent
  cache's read and load on a hit). JAX reports a trace for every jitted
  function it traces *inside* another's too, each with its own duration, so a
  part is summed as self time: an interval that holds earlier ones counts
  without them, and the three add up to no more than the call's wall time.
- ``rest_ms``: the wall time less those three, never negative. None of JAX's
  events covers it: the cache key's hashing, pjit's own argument work, the
  transfer and the first execution.
- ``cache_read_ms`` (the cache's read, a part of ``backend_ms``),
  ``cache_saved_ms`` (what the program cost to compile when the entry was made,
  less the read: a warm run also says its cold cost), ``cache_hits``,
  ``cache_misses`` (requests to the cache that found nothing: JAX's own
  ``cache_misses`` event counts only entries *written*, and a program that
  compiled in under ``jax_persistent_cache_min_compile_time_secs`` writes
  none), ``modules`` (backend events: a dispatch may build several programs).
- ``cache``: ``hit`` (hits and no miss), ``miss``, ``off`` (a backend event
  and no request to the cache), ``none`` (no backend event: JAX had the
  executable in the process).
- ``store`` and ``store_read_ms``: what the runner's executable store
  (``dynamo_tpu/executable_store.py``) did for the call, which the runner says
  through :func:`note_store`. ``hit``: the compiled program was loaded from the
  store and nothing was traced, lowered or asked of JAX's cache: ``trace_ms``
  and ``lower_ms`` are 0, the load (read, decompress, deserialise, load onto
  the device: ``store_read_ms``) is the call's ``backend_ms``, and the call
  counts one ``cache_hits`` and one of ``modules``, with ``cache`` ``hit``:
  it found its program in a persistent cache, by another door. ``miss``: the
  store was asked and had no entry it could load (``store_read_ms`` is what
  asking cost, inside ``rest_ms``), and the other fields say what a first call
  says without a store. ``off``: no store (no cache directory, a process of
  several hosts, a runner built from a function no key can see into).
- ``t0_ns`` (``perf_counter_ns`` at entry: the clock of a STEP record) and
  ``in_step`` (the runner's word: the call was made inside an engine step, and
  not by a warm-up that drives the runner directly).

The event goes to two sinks: the tracker's ``sink`` (the engine's flight ring,
a ``compile`` record) and ``tracing.SPANS``, as a ``runner_first_call`` span
under the trace of the worker's bring-up (:attr:`CompileTracker.trace`).
"""

from __future__ import annotations

import logging
import os
import threading
import time
from typing import Any, Callable

from dynamo_tpu import tracing

logger = logging.getLogger(__name__)

_THRESHOLD_ENV = "DYN_COMPILE_THRESHOLD_MS"

#: reasons attached to compile events / the recompile counter.
REASON_NEW_SHAPE = "new_shape"
REASON_WARM_CACHE = "warm_cache"

COMPILE_KIND = "compile"
#: The flight record of a kept program that refused a later dispatch's arguments.
REFUSED_KIND = "program_refused"
#: The span a first call leaves in ``tracing.SPANS``, and its ``request_id``.
FIRST_CALL_SPAN = "runner_first_call"

TRACE, LOWER, BACKEND = range(3)
_PART_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": TRACE,
    "/jax/core/compile/jaxpr_to_mlir_module_duration": LOWER,
    "/jax/core/compile/backend_compile_duration": BACKEND,
}
_CACHE_READ_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_SAVED_EVENT = "/jax/compilation_cache/compile_time_saved_sec"
_CACHE_REQUEST_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def _default_threshold_ms() -> float:
    try:
        return float(os.environ.get(_THRESHOLD_ENV, "50"))
    except ValueError:
        return 50.0


# -- what JAX says of a first call ----------------------------------------------


class FirstCall:
    """The collector of one first call: what the listeners heard on the
    thread that made it, between :func:`open_first_call` and
    :func:`close_first_call`."""

    __slots__ = ("t0_ns", "parts_s", "modules", "cache_requests", "cache_hits",
                 "cache_read_s", "cache_saved_s", "store", "store_read_s", "_intervals")

    def __init__(self) -> None:
        self.parts_s = [0.0, 0.0, 0.0]
        self.modules = 0
        self.cache_requests = 0
        self.cache_hits = 0
        self.cache_read_s = 0.0
        self.cache_saved_s = 0.0
        self.store = "off"
        self.store_read_s = 0.0
        # Reported intervals that no later one holds yet, oldest first.
        self._intervals: list[tuple[float, float]] = []
        self.t0_ns = time.perf_counter_ns()

    def add_part(self, part: int, seconds: float) -> None:
        """One of JAX's three durations, reported as it ended: its self time
        goes to its part. The intervals it holds (a jitted function traced
        inside this one, an eager operation compiled while this one traced)
        were reported before it and have their self time already."""
        end = time.perf_counter()
        start = end - seconds
        held = 0.0
        intervals = self._intervals
        # Nested or apart, never straddling: an interval whose middle lies
        # after this one's start is inside it (the two stamps are a few
        # microseconds late, each by its own amount).
        while intervals and (intervals[-1][0] + intervals[-1][1]) / 2 > start:
            s, e = intervals.pop()
            held += e - s
        intervals.append((start, end))
        self.parts_s[part] += max(0.0, seconds - held)
        if part == BACKEND:
            self.modules += 1

    def add_store(self, store: str, read_s: float) -> None:
        """The executable store's word on this call. A hit is the backend's
        part of a call that asked JAX for nothing: one program, found."""
        self.store = store
        self.store_read_s += read_s
        if store == "hit":
            self.parts_s[BACKEND] += read_s
            self.modules += 1
            self.cache_requests += 1
            self.cache_hits += 1

    @property
    def cache(self) -> str:
        if not self.modules:
            return "none"
        if not self.cache_requests:
            return "off"
        return "hit" if self.cache_hits >= self.cache_requests else "miss"

    def fields(self, wall_ms: float) -> dict:
        """The event's share of this collector; ``rest_ms`` from the rounded
        parts, so that the four add up to ``wall_ms`` as printed."""
        trace_ms, lower_ms, backend_ms = (round(s * 1e3, 3) for s in self.parts_s)
        return {
            "trace_ms": trace_ms, "lower_ms": lower_ms, "backend_ms": backend_ms,
            "rest_ms": round(max(0.0, wall_ms - trace_ms - lower_ms - backend_ms), 3),
            "cache": self.cache,
            "cache_hits": self.cache_hits, "cache_misses": self.cache_requests - self.cache_hits,
            "cache_read_ms": round(self.cache_read_s * 1e3, 3),
            "cache_saved_ms": round(self.cache_saved_s * 1e3, 3),
            "store": self.store, "store_read_ms": round(self.store_read_s * 1e3, 3),
            "modules": self.modules, "t0_ns": self.t0_ns,
        }


class _Open(threading.local):
    """The first call open on this thread: the compile runs synchronously on
    the calling thread, and two replicas' engine threads share nothing."""

    call: FirstCall | None = None
    #: Programs JAX's persistent cache has handed this thread, open call or not.
    cache_hits: int = 0


_OPEN = _Open()
_install_lock = threading.Lock()
_installed = False


def _on_event(event: str, **_kw: Any) -> None:
    if event == _CACHE_HIT_EVENT:
        _OPEN.cache_hits += 1
    call = _OPEN.call
    if call is None:
        return
    if event == _CACHE_HIT_EVENT:
        call.cache_hits += 1
    elif event == _CACHE_REQUEST_EVENT:
        call.cache_requests += 1


def persistent_cache_hits() -> int:
    """How many programs JAX's persistent cache has handed this thread so far
    (the executable store asks before and after a compile: an executable that
    came out of the cache is not on every platform written out whole again)."""
    return _OPEN.cache_hits


def _on_duration(event: str, seconds: float, **_kw: Any) -> None:
    call = _OPEN.call
    if call is None:
        return
    part = _PART_EVENTS.get(event)
    if part is not None:
        call.add_part(part, seconds)
    elif event == _CACHE_READ_EVENT:
        call.cache_read_s += seconds
    elif event == _CACHE_SAVED_EVENT:
        call.cache_saved_s += seconds


def install_listeners() -> bool:
    """Hand JAX the pair of listeners, once a process (``jax.monitoring``
    keeps them for its lifetime); True for the call that did."""
    global _installed
    with _install_lock:
        if _installed:
            return False
        from jax import monitoring

        monitoring.register_event_listener(_on_event)
        monitoring.register_event_duration_secs_listener(_on_duration)
        _installed = True
        return True


def open_first_call() -> FirstCall:
    """A collector for this thread's dispatch (one at a time: no dispatch
    site runs inside another)."""
    call = _OPEN.call = FirstCall()
    return call


def close_first_call() -> None:
    _OPEN.call = None


def note_store(store: str, read_s: float) -> None:
    """The runner's executable store, on the first call open on this thread
    (none open: a key the tracker had seen, which says nothing)."""
    call = _OPEN.call
    if call is not None:
        call.add_store(store, read_s)


# -- the tracker -----------------------------------------------------------------


class CompileTracker:
    """Per-runner first-execution-per-shape tracker.

    Dispatch sites call :meth:`observe` with the program kind, the padded
    bucket signature, and the measured dispatch wall time. Thread-safe (the
    runner's ``io_lock`` already serializes dispatches, but the tracker does
    not rely on it).
    """

    def __init__(self, *, threshold_ms: float | None = None) -> None:
        install_listeners()
        self.threshold_ms = threshold_ms if threshold_ms is not None else _default_threshold_ms()
        #: The worker's bring-up (``launch``): a first call's span is recorded
        #: under its trace, as a child of its root. None: a trace of its own.
        self.trace: tracing.TraceContext | None = None
        self._lock = threading.Lock()
        self._seen: set[tuple] = set()
        self._counts: dict[tuple[str, str], int] = {}  # (program, reason) -> n
        self._events: list[dict] = []
        self._sink: Callable[..., Any] | None = None
        self._dispatches = 0
        #: Dispatches a kept compiled program refused (:meth:`refused`).
        self.refusals = 0

    def bind_sink(self, sink: Callable[..., Any] | None) -> "CompileTracker":
        """``sink(kind, **fields)`` receives compile events — wired to the
        worker's :class:`~dynamo_tpu.observability.flight.FlightRecorder`
        ``record`` method at bring-up."""
        self._sink = sink
        return self

    # -- observation -------------------------------------------------------

    def seen(self, program: str, key: tuple) -> bool:
        return (program, *key) in self._seen

    def observe(self, program: str, key: tuple, seconds: float, *,
                first_call: FirstCall | None = None, in_step: bool | None = None) -> dict | None:
        """Record one dispatch; returns the compile event dict when this was
        the key's first execution, else None. ``first_call`` is what JAX said
        of it (:class:`timed_dispatch` hands it over), ``in_step`` the
        runner's word on whether an engine step made the call: an event
        carries neither key where the caller gave none."""
        ms = seconds * 1e3
        with self._lock:
            self._dispatches += 1
            full_key = (program, *key)
            if full_key in self._seen:
                return None
            self._seen.add(full_key)
            reason = REASON_NEW_SHAPE if ms >= self.threshold_ms else REASON_WARM_CACHE
            self._counts[(program, reason)] = self._counts.get((program, reason), 0) + 1
            event = {
                "program": program,
                "bucket": list(key),
                "reason": reason,
                "wall_ms": round(ms, 3),
                "dispatch_index": self._dispatches,
            }
            if first_call is not None:
                event.update(first_call.fields(event["wall_ms"]))
            if in_step is not None:
                event["in_step"] = in_step
            self._events.append(event)
        self._emit(COMPILE_KIND, **event)
        start_mono = first_call.t0_ns / 1e9 if first_call is not None else None
        tracing.record_span(FIRST_CALL_SPAN, event["wall_ms"], trace=self.trace, start_mono=start_mono,
                            request_id=FIRST_CALL_SPAN, **event)
        return event

    def refused(self, program: str, key: tuple, error: Exception) -> None:
        """A compiled program kept for ``key`` refused a later dispatch's
        arguments, and the jitted function took the call: the ``dispatch_key``
        does not hold everything its program specialises on. One flight record
        a refusal, with the key and the refusal's first line."""
        with self._lock:
            self.refusals += 1
        message = str(error).splitlines()[0] if str(error) else type(error).__name__
        logger.warning("step program %s %s refused its arguments: %s", program, key, message)
        self._emit(REFUSED_KIND, program=program, bucket=list(key), error=message[:240])

    def _emit(self, kind: str, **fields: Any) -> None:
        sink = self._sink
        if sink is None:
            return
        try:
            sink(kind, **fields)
        except Exception:
            logger.exception("compile event sink failed")

    # -- introspection -----------------------------------------------------

    def counts(self) -> dict[tuple[str, str], int]:
        """Cumulative first-executions per (program, reason) — the source of
        truth behind ``dynamo_engine_recompiles_total`` (synced on scrape)."""
        with self._lock:
            return dict(self._counts)

    def events(self) -> list[dict]:
        with self._lock:
            return list(self._events)

    @property
    def total(self) -> int:
        with self._lock:
            return sum(self._counts.values())


class timed_dispatch:
    """Context manager timing one dispatch site for a tracker.

    >>> with timed_dispatch(tracker, "step", (b, t, n, h, lp_k)):
    ...     out = self._step_fn(...)

    A ``None`` tracker makes it a no-op, so call sites need no branching.
    ``seconds`` holds the block's wall time after a clean exit (0.0 after a
    raise, which the tracker does not see either). Only for a key the tracker
    has not seen does it open a :class:`FirstCall`; a seen key's dispatch is
    two clock reads and :meth:`CompileTracker.observe`.
    """

    __slots__ = ("tracker", "program", "key", "in_step", "seconds", "_t0", "_call")

    def __init__(self, tracker: CompileTracker | None, program: str, key: tuple, *,
                 in_step: bool | None = None) -> None:
        self.tracker = tracker
        self.program = program
        self.key = key
        self.in_step = in_step
        self.seconds = 0.0
        self._t0 = 0.0
        self._call: FirstCall | None = None

    def __enter__(self) -> "timed_dispatch":
        if self.tracker is not None and not self.tracker.seen(self.program, self.key):
            self._call = open_first_call()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        call, self._call = self._call, None
        if call is not None:
            close_first_call()
        if exc_type is not None:
            return
        self.seconds = time.perf_counter() - self._t0
        if self.tracker is not None:
            self.tracker.observe(self.program, self.key, self.seconds, first_call=call, in_step=self.in_step)
