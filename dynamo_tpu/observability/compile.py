"""XLA compile observability: first-execution-per-shape detection.

``ModelRunner`` bounds the set of compiled programs with a bucket lattice
(pow2 batch/time/page buckets, see ``engine/runner.py``) — but the lattice is
data-dependent, so production traffic can still walk into shapes nothing
warmed up, and a recompile on the serving path is a silent multi-hundred-ms
stall (bench.py PR 2 had to add identical-dry-run warm-ups for exactly this
reason). No generic tool sees it: JAX compiles inside the dispatch call.

The :class:`CompileTracker` hangs off the runner and observes every dispatch
site *after* padding: the cache key is the padded bucket signature (program
kind + every static shape/arg the jit specializes on), so it tracks exactly
what XLA's own cache tracks. Detection is key-novelty; the measured dispatch
wall time then classifies the first execution:

- ``new_shape`` — first execution AND slower than the compile threshold:
  a real tracing+compilation happened on the serving path.
- ``warm_cache`` — first execution in this process but fast: the program
  came out of a persistent/jit cache (or the model is small enough not to
  matter). Counted separately so dashboards can tell warm restarts from
  true recompile storms.

Re-hits of a seen key emit nothing — by construction one event per bucket.

A warn-once storm detector flags N slow compiles inside a trailing window of
M dispatches after a warm-up grace (the lattice legitimately fills during
the first traffic); a storm after warm-up means shapes are escaping the
lattice (e.g. a mis-sized ``prefill_bucket``) and every occurrence is a
production stall.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from collections import deque
from typing import Any, Callable

logger = logging.getLogger(__name__)

_THRESHOLD_ENV = "DYN_COMPILE_THRESHOLD_MS"

#: reasons attached to compile events / the recompile counter.
REASON_NEW_SHAPE = "new_shape"
REASON_WARM_CACHE = "warm_cache"


def _default_threshold_ms() -> float:
    try:
        return float(os.environ.get(_THRESHOLD_ENV, "50"))
    except ValueError:
        return 50.0


class CompileTracker:
    """Per-runner first-execution-per-shape tracker.

    Dispatch sites call :meth:`observe` with the program kind, the padded
    bucket signature, and the measured dispatch wall time. Thread-safe (the
    runner's ``io_lock`` already serializes dispatches, but the tracker does
    not rely on it).
    """

    def __init__(
        self,
        *,
        threshold_ms: float | None = None,
        storm_window: int = 64,
        storm_threshold: int = 8,
        warmup_dispatches: int = 32,
    ) -> None:
        self.threshold_ms = threshold_ms if threshold_ms is not None else _default_threshold_ms()
        self.storm_window = storm_window
        self.storm_threshold = storm_threshold
        self.warmup_dispatches = warmup_dispatches
        self._lock = threading.Lock()
        self._seen: set[tuple] = set()
        self._counts: dict[tuple[str, str], int] = {}  # (program, reason) -> n
        self._events: list[dict] = []
        self._sink: Callable[..., Any] | None = None
        self._dispatches = 0
        # Dispatch indices of slow (new_shape) compiles, for the storm window.
        self._slow_marks: deque[int] = deque(maxlen=max(1, storm_threshold))
        self.storm_warned = False
        # Cumulative seconds spent inside runner dispatch calls.
        self.dispatch_seconds_total = 0.0

    def bind_sink(self, sink: Callable[..., Any] | None) -> "CompileTracker":
        """``sink(kind, **fields)`` receives compile/storm events — wired to
        the worker's :class:`~dynamo_tpu.observability.flight.FlightRecorder`
        ``record`` method at bring-up."""
        self._sink = sink
        return self

    # -- observation -------------------------------------------------------

    def observe(self, program: str, key: tuple, seconds: float) -> dict | None:
        """Record one dispatch; returns the compile event dict when this was
        the key's first execution, else None."""
        ms = seconds * 1e3
        with self._lock:
            self._dispatches += 1
            dispatch_idx = self._dispatches
            self.dispatch_seconds_total += max(0.0, seconds)
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
                "dispatch_index": dispatch_idx,
            }
            self._events.append(event)
            storm = self._note_slow_locked(dispatch_idx) if reason == REASON_NEW_SHAPE else None
        self._emit(COMPILE_KIND, **event)
        if storm is not None:
            logger.warning(
                "recompile storm: %d compiles within the last %d dispatches "
                "(after %d warm-up dispatches) — shapes are escaping the bucket "
                "lattice; last program %r bucket %s",
                storm["compiles"], storm["window"], self.warmup_dispatches, program, key,
            )
            self._emit("compile_storm", **storm)
        return event

    def _note_slow_locked(self, dispatch_idx: int) -> dict | None:
        """Track a slow compile; returns a storm event once, when the last
        ``storm_threshold`` slow compiles all landed within ``storm_window``
        dispatches after the warm-up grace."""
        self._slow_marks.append(dispatch_idx)
        if (
            self.storm_warned
            or dispatch_idx <= self.warmup_dispatches
            or len(self._slow_marks) < self.storm_threshold
        ):
            return None
        if dispatch_idx - self._slow_marks[0] <= self.storm_window:
            self.storm_warned = True
            return {
                "compiles": len(self._slow_marks),
                "window": self.storm_window,
                "dispatch_index": dispatch_idx,
            }
        return None

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


COMPILE_KIND = "compile"


class timed_dispatch:
    """Context manager timing one dispatch site for a tracker.

    >>> with timed_dispatch(tracker, "step", (b, t, n, h, lp_k)):
    ...     out = self._step_fn(...)

    A ``None`` tracker makes it a no-op, so call sites need no branching.
    ``seconds`` holds the block's wall time after a clean exit (0.0 after a
    raise, which the tracker does not see either).
    """

    __slots__ = ("tracker", "program", "key", "seconds", "_t0")

    def __init__(self, tracker: CompileTracker | None, program: str, key: tuple) -> None:
        self.tracker = tracker
        self.program = program
        self.key = key
        self.seconds = 0.0
        self._t0 = 0.0

    def __enter__(self) -> "timed_dispatch":
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc_type is not None:
            return
        self.seconds = time.perf_counter() - self._t0
        if self.tracker is not None:
            self.tracker.observe(self.program, self.key, self.seconds)
