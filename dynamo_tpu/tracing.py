"""Tracing & profiling: JAX device traces (XPlane) + request-level spans.

Two complementary planes, mirroring the reference's tracing stack
(`logging.rs` tracing-subscriber spans + per-engine profilers):

- **Device**: :func:`device_trace` wraps `jax.profiler.start_trace` — dumps
  an XPlane/TensorBoard trace of everything the chip executed (XLA op
  timeline, HBM transfers, fusion view). ``annotate()`` adds named host-side
  regions (``engine.decode`` / ``engine.mixed`` per step) to the same
  timeline via TraceAnnotation, and :class:`StepClock` times the phases
  inside a step on ``perf_counter_ns`` whether or not a trace runs.
  Enable on any process with ``DYN_TRACE_DIR=/tmp/trace`` (traces the first
  ``DYN_TRACE_SECONDS``, default 5), or on demand on a worker:
  ``POST /debug/profile/{worker}?duration_ms=3000`` on the frontend. Every
  entry point goes through :func:`start_device_trace`, which alone sets the
  profiler's options (:func:`profiler_options`: no Python function tracer).
- **Request spans**: :class:`Span` measures one phase of one request and
  logs it as a structured JSONL record (``runtime/logging.py`` flattens the
  fields), giving grep-able per-request latency breakdowns without a
  collector service. Every finished span also lands in the per-process
  :class:`SpanBuffer` ring (:data:`SPANS`), queryable by request or trace id
  — the storage behind ``GET /debug/traces/{request_id}``.
- **Distributed trace identity**: :class:`TraceContext` carries a W3C
  ``traceparent``-compatible (trace_id, span_id) pair across process hops.
  The frontend mints (or ingests) it, the runtime transport forwards it on
  the wire (``runtime/codec.py`` REQUEST frames, optional ``trace`` field),
  and the disagg prefill queue/KV-transfer path rides it too — so spans
  emitted on the frontend, the router, the decode engine, and a remote
  prefill worker all share one ``trace_id`` and parent/child links.
"""

from __future__ import annotations

import contextlib
import contextvars
import gc
import logging
import os
import re
import secrets
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Any, Iterator

logger = logging.getLogger("dynamo.trace")

_lock = threading.Lock()
_active_dir: str | None = None
#: True from the moment a session is up until its stop begins: the window in
#: which a TraceAnnotation lands in the trace (none is built outside it).
_annotating = False


def trace_running() -> bool:
    return _active_dir is not None


def profiler_available() -> bool:
    """Whether this process can arm a device trace (jax.profiler present)."""
    try:
        import jax.profiler  # noqa: F401

        return hasattr(jax.profiler, "start_trace")
    except Exception:
        return False


def profile_max_ms() -> float:
    """Hard cap on one on-demand capture's duration (``DYN_PROFILE_MAX_MS``)."""
    try:
        return float(os.environ.get("DYN_PROFILE_MAX_MS", "10000"))
    except ValueError:
        return 10000.0


def profile_artifact_dir() -> str:
    """The root the on-demand captures' XPlane dumps land under (``DYN_PROFILE_DIR``)."""
    import tempfile

    return os.environ.get("DYN_PROFILE_DIR") or os.path.join(
        tempfile.gettempdir(), "dynamo-profiles"
    )


def profiler_options():
    """The one place the profiler session's options are set.

    The Python function tracer is off (``python_tracer_level=0``): with it on
    every Python call of every thread is recorded and the host being measured
    takes 3 to 5 times as long per step (1.8 -> 5.4 ms at 4 rows, 3.0 -> 15.4 ms
    at 48; PERF.md, PR 24). ``host_tracer_level=1`` is the lowest
    level at which ``TraceAnnotation`` (a level-1 TraceMe) still reaches the
    host plane; the device planes do not depend on either."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def start_device_trace(log_dir: str) -> bool:
    """Begin an XPlane trace (idempotent; one at a time per process)."""
    global _active_dir, _annotating
    import jax

    with _lock:
        if _active_dir is not None:
            return False
        with HOST_PAUSES.profiler("start"):
            jax.profiler.start_trace(log_dir, profiler_options=profiler_options())
        _active_dir = log_dir
        _annotating = True
    logger.info("device trace started -> %s", log_dir)
    return True


def stop_device_trace() -> str | None:
    global _active_dir, _annotating
    import jax

    with _lock:
        if _active_dir is None:
            return None
        _annotating = False  # writing the trace takes seconds: annotate nothing meanwhile
        with HOST_PAUSES.profiler("stop"):
            jax.profiler.stop_trace()
        path, _active_dir = _active_dir, None
    logger.info("device trace written -> %s", path)
    return path


@contextlib.contextmanager
def device_trace(log_dir: str) -> Iterator[None]:
    started = start_device_trace(log_dir)
    try:
        yield
    finally:
        if started:
            stop_device_trace()


_NO_ANNOTATION = contextlib.nullcontext()


def annotate(name: str):
    """Named region on the profiler timeline.

    A no-op context when no trace is active — callers can sit on hot paths
    (the engine step loop) without paying TraceAnnotation construction."""
    if not _annotating:
        return _NO_ANNOTATION
    import jax

    return jax.profiler.TraceAnnotation(name)


# -- phases of an engine step -------------------------------------------------

#: Inside ``EngineCore.step()``: the first five tile the record's ``wall_ms``;
#: ``record`` is the telemetry tail after it.
STEP_PHASES = ("sched", "build", "dispatch", "wait", "post", "record")
#: Between two steps (``engine/service.py``): they tile ``gap_ms``.
GAP_PHASES = ("handoff", "route", "intake", "no_work", "submit")
PHASES = STEP_PHASES + GAP_PHASES
(SCHED, BUILD, DISPATCH, WAIT, POST, RECORD,
 HANDOFF, ROUTE, INTAKE, NO_WORK, SUBMIT) = range(len(PHASES))
_PHASE_EVENTS = tuple(f"phase.{p}" for p in STEP_PHASES)  # never "engine.*": the reducer keeps those
_ZEROS = [0] * len(PHASES)


class _StepAnnotation:
    """The step's ``engine.*`` region; closes the phase region open inside it
    first, so the trace nests."""

    __slots__ = ("_clock", "_ann")

    def __init__(self, clock: "StepClock", ann) -> None:
        self._clock, self._ann = clock, ann

    def __enter__(self) -> "_StepAnnotation":
        return self

    def __exit__(self, *exc) -> None:
        self._clock._close_region()
        self._ann.__exit__(*exc)


class StepClock:
    """Phase marker of one engine: where a step's and a gap's time goes.

    ``mark(phase)`` ends the running phase and starts ``phase``: one
    ``perf_counter_ns`` and one add into a slot that lives as long as the
    engine (a phase entered twice in a step accumulates). Only while a device
    trace runs does a step phase also open a ``TraceAnnotation``
    ``phase.<name>``; the gap phases cross threads and awaits and are never
    annotated (their stamps map onto the trace through ``ann_ns``).

    One writer at a time: the step thread between :meth:`begin` and
    :meth:`end`, the service's event loop between ``end`` and ``begin`` — the
    two alternate (``run_in_executor`` is awaited), so no lock.
    """

    __slots__ = ("ns", "carried", "t0_ns", "ann_ns", "traced", "_phase", "_t", "_region", "_gap_open")

    def __init__(self) -> None:
        self.ns = list(_ZEROS)
        #: ``record`` of the step before and the gap since: they end when the
        #: running step begins, so its record carries them (as ``gap_ms``).
        self.carried = [0] * (len(PHASES) - RECORD)
        self.t0_ns = 0
        self.ann_ns = 0
        self.traced = False
        self._phase = HANDOFF
        self._t = time.perf_counter_ns()
        self._region = None
        #: A step has ended and none has begun since: what the marks add up
        #: is a gap. Not so before the first step or after one that raised.
        self._gap_open = False

    def begin(self) -> tuple[int, int]:
        """Step start: closes the gap, keeps it as ``carried``, opens ``sched``.
        Returns the start stamp and the gap's length, both in ns."""
        now = time.perf_counter_ns()
        ns = self.ns
        if self._gap_open:
            ns[self._phase] += now - self._t
            self.carried = ns[RECORD:]
        else:
            self.carried = _ZEROS[RECORD:]
        ns[:] = _ZEROS
        self.t0_ns = now
        self.ann_ns = 0
        self.traced = False
        self._gap_open = False
        self._phase, self._t = SCHED, now
        if self._region is not None:
            self._close_region()
        if _annotating:
            self._open_region(SCHED)
        return now, sum(self.carried[1:])

    def mark(self, phase: int) -> int:
        now = time.perf_counter_ns()
        self.ns[self._phase] += now - self._t
        self._phase, self._t = phase, now
        if self._region is not None:
            self._close_region()
        if _annotating and phase <= RECORD:
            self._open_region(phase)
        return now

    @property
    def in_step(self) -> bool:
        """Between :meth:`begin` and the step's ``record`` phase."""
        return self._phase < RECORD

    def mark_in_step(self, phase: int) -> None:
        """``mark`` for a callee that also runs outside steps (the runner,
        which a warm-up drives directly): only a step's phase gives way."""
        if self.in_step:
            self.mark(phase)

    def end(self) -> None:
        """Step end (after the flight record and the rest of the tail)."""
        self.mark(HANDOFF)
        self._gap_open = True

    def restart_gap(self) -> None:
        """A step that dispatched and recorded nothing: the gap starts anew."""
        self.end()
        self.ns[:] = _ZEROS

    def annotate(self, name: str):
        """The step's ``engine.*`` region, its entry stamped as ``ann_ns``:
        the k-th traced STEP record is the k-th such event of the trace, which
        puts ``perf_counter_ns`` and the trace's clock side by side."""
        if not _annotating:
            return _NO_ANNOTATION
        self._close_region()
        self.traced = True
        self.ann_ns = time.perf_counter_ns()
        return _StepAnnotation(self, annotate(name))

    def phases_us(self) -> dict[str, float]:
        """The running step's five phases so far and what it carries."""
        ns = self.ns[:RECORD] + self.carried
        return {p: round(v / 1e3, 1) for p, v in zip(PHASES, ns)}

    def _open_region(self, phase: int) -> None:
        import jax

        self._region = jax.profiler.TraceAnnotation(_PHASE_EVENTS[phase])

    def _close_region(self) -> None:
        region, self._region = self._region, None
        if region is not None:
            region.__exit__(None, None, None)


async def profile_for(seconds: float, log_dir: str) -> str | None:
    """Trace the next ``seconds`` of device work (the HTTP hook's body)."""
    import asyncio

    if not start_device_trace(log_dir):
        return None
    try:
        await asyncio.sleep(seconds)
    finally:
        path = stop_device_trace()  # stop even on cancellation, then propagate
    return path


def maybe_trace_from_env() -> None:
    """Start a bounded trace when DYN_TRACE_DIR is set (worker bring-up)."""
    log_dir = os.environ.get("DYN_TRACE_DIR")
    if not log_dir:
        return
    try:
        seconds = float(os.environ.get("DYN_TRACE_SECONDS", "5"))
    except ValueError:
        logger.warning("ignoring malformed DYN_TRACE_SECONDS=%r", os.environ["DYN_TRACE_SECONDS"])
        seconds = 5.0
    try:
        if not start_device_trace(log_dir):
            return
    except Exception:
        # Observability must never take the serving worker down.
        logger.exception("could not start device trace in %s", log_dir)
        return

    def stop_later() -> None:
        time.sleep(seconds)
        stop_device_trace()

    threading.Thread(target=stop_later, name="dyn-trace-stop", daemon=True).start()


# -- distributed trace identity ---------------------------------------------

_TRACEPARENT_RE = re.compile(r"^[0-9a-f]{2}-([0-9a-f]{32})-([0-9a-f]{16})-[0-9a-f]{2}$")


def _new_trace_id() -> str:
    return secrets.token_hex(16)


def _new_span_id() -> str:
    return secrets.token_hex(8)


@dataclass(frozen=True)
class TraceContext:
    """A W3C-trace-context-compatible (trace_id, span_id) pair.

    ``trace_id`` names the whole distributed request; ``span_id`` names the
    *current* span — a child span created under this context records it as
    ``parent_id``. The dict form (plain strings) is what rides msgpack/JSON
    hops: codec REQUEST frames, disagg queue tasks, KV-transfer chunks.
    """

    trace_id: str
    span_id: str
    #: The trace's root span in this system (the frontend's ``http_request``)
    #: and its wall-clock start: a hop that never saw the root can still name
    #: it as a parent and time "since the request came in". Empty / 0.0 on a
    #: context that came from a bare ``traceparent`` header.
    root_id: str = ""
    root_ts: float = 0.0

    @classmethod
    def new(cls) -> "TraceContext":
        return cls(trace_id=_new_trace_id(), span_id=_new_span_id())

    @classmethod
    def from_traceparent(cls, header: str | None) -> "TraceContext | None":
        if not header:
            return None
        m = _TRACEPARENT_RE.match(header.strip().lower())
        if m is None:
            return None
        return cls(trace_id=m.group(1), span_id=m.group(2))

    def to_traceparent(self) -> str:
        return f"00-{self.trace_id}-{self.span_id}-01"

    def to_dict(self) -> dict[str, Any]:
        doc: dict[str, Any] = {"trace_id": self.trace_id, "span_id": self.span_id}
        if self.root_id:
            doc.update(root_id=self.root_id, root_ts=self.root_ts)
        return doc

    @classmethod
    def from_dict(cls, obj: Any) -> "TraceContext | None":
        if not isinstance(obj, dict) or "trace_id" not in obj:
            return None
        return cls(trace_id=str(obj["trace_id"]), span_id=str(obj.get("span_id", "")),
                   root_id=str(obj.get("root_id", "")), root_ts=float(obj.get("root_ts") or 0.0))

    def under_root(self) -> "TraceContext":
        """This trace with its root span as the parent of what is recorded."""
        return TraceContext(self.trace_id, self.root_id or self.span_id, self.root_id, self.root_ts)


# -- span collection ----------------------------------------------------------


class SpanBuffer:
    """Bounded per-process ring of finished spans (thread-safe).

    Spans are plain dicts (see :meth:`Span._record`): name, trace/span/parent
    ids, request_id, wall + monotonic start, duration, status ok|error and
    the exception type on failure. ``GET /debug/traces/{request_id}`` fans
    out to every worker's buffer and assembles one timeline from the union.
    ``dropped`` counts the spans the ring has overwritten since the process
    started (or the last :meth:`clear`): a reader of a window compares it
    before and after to know whether the ring wrapped inside it.
    """

    def __init__(self, capacity: int = 4096) -> None:
        self._spans: deque[dict] = deque(maxlen=max(1, capacity))
        self._lock = threading.Lock()
        self.dropped = 0

    def record(self, span: dict) -> None:
        with self._lock:
            if len(self._spans) == self._spans.maxlen:
                self.dropped += 1
            self._spans.append(span)

    def query(self, *, request_id: str | None = None, trace_id: str | None = None) -> list[dict]:
        with self._lock:
            spans = list(self._spans)
        if request_id is not None:
            spans = [s for s in spans if s.get("request_id") == request_id]
        if trace_id is not None:
            spans = [s for s in spans if s.get("trace_id") == trace_id]
        return spans

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()
            self.dropped = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._spans)


def _buffer_capacity() -> int:
    try:
        return int(os.environ.get("DYN_SPAN_BUFFER", "4096"))
    except ValueError:
        return 4096


#: The per-process span ring every finished Span records into.
SPANS = SpanBuffer(_buffer_capacity())

#: The span currently open in this task/thread (contextvar: async-safe).
_CURRENT_SPAN: contextvars.ContextVar["Span | None"] = contextvars.ContextVar(
    "dynamo_current_span", default=None
)


def current_span() -> "Span | None":
    """The innermost open Span in the current task/thread, if any.

    Lets unrelated code — notably ``runtime/logging.py``'s log-record filter —
    stamp trace_id/span_id onto whatever happens inside a span without the
    span being threaded through call signatures.
    """
    return _CURRENT_SPAN.get()


class Span:
    """One timed phase of one request, logged as structured JSONL.

    >>> with Span("prefill", trace=ctx, request_id=rid, tokens=len(ids)):
    ...     ...

    Logs ``{"span": "prefill", "duration_ms": 12.3, "trace_id": ...,
    "span_id": ..., "parent_id": ..., "status": "ok", ...}`` at DEBUG (set
    ``DYN_LOG_LEVEL=DEBUG`` + ``DYN_LOGGING_JSONL=1`` to collect). A raise
    inside the block still records the span — ``status="error"`` with the
    exception type under ``error`` — and propagates. Every exit also lands
    the span in :data:`SPANS`.

    ``trace`` threads the distributed identity: the span's ``parent_id`` is
    the incoming context's span_id, and :attr:`context` is what downstream
    hops should receive (same trace_id, this span as parent).
    """

    __slots__ = (
        "name", "fields", "t0", "t_wall",
        "trace_id", "span_id", "parent_id", "status", "error_type",
        "_root", "_cv_token",
    )

    def __init__(self, name: str, *, trace: TraceContext | None = None, **fields: Any) -> None:
        self.name = name
        self.fields = fields
        if trace is not None:
            self.trace_id = trace.trace_id
            self.parent_id = trace.span_id or None
            self._root = (trace.root_id, trace.root_ts)
        else:
            self.trace_id = _new_trace_id()  # root of a fresh trace
            self.parent_id = None
            self._root = ("", 0.0)
        self.span_id = _new_span_id()
        self.status = "ok"
        self.error_type: str | None = None
        self.t0 = 0.0
        self.t_wall = 0.0
        self._cv_token: contextvars.Token | None = None

    @property
    def context(self) -> TraceContext:
        """The context downstream hops should inherit (this span as parent).
        A span that inherited no root is the root: read this after entering
        it, so its start rides along."""
        root_id, root_ts = self._root if self._root[0] else (self.span_id, self.t_wall)
        return TraceContext(self.trace_id, self.span_id, root_id, root_ts)

    def __enter__(self) -> "Span":
        self.t0 = time.perf_counter()
        self.t_wall = time.time()
        self._cv_token = _CURRENT_SPAN.set(self)
        return self

    def __exit__(self, exc_type, _exc, _tb) -> None:
        if self._cv_token is not None:
            try:
                _CURRENT_SPAN.reset(self._cv_token)
            except ValueError:
                # Exited in a different context than entered (the engine
                # service holds spans open across awaits); just clear.
                _CURRENT_SPAN.set(None)
            self._cv_token = None
        ms = (time.perf_counter() - self.t0) * 1e3
        if exc_type is not None:
            self.status = "error"
            self.error_type = exc_type.__name__
        extra = {
            "span": self.name, "duration_ms": round(ms, 3),
            "trace_id": self.trace_id, "span_id": self.span_id,
            "parent_id": self.parent_id, "status": self.status,
            **self.fields,
        }
        if self.error_type is not None:
            extra["error"] = self.error_type
            logger.warning("span %s failed after %.1fms", self.name, ms, extra=extra)
        else:
            logger.debug("span %s %.1fms", self.name, ms, extra=extra)
        self._record(ms)

    def _record(self, duration_ms: float) -> None:
        doc: dict[str, Any] = {
            "name": self.name,
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "start_ts": self.t_wall,
            "start_mono": self.t0,
            "duration_ms": round(duration_ms, 3),
            "status": self.status,
        }
        if self.error_type is not None:
            doc["error"] = self.error_type
        for k, v in self.fields.items():
            doc.setdefault(k, v)
        SPANS.record(doc)


def record_span(
    name: str,
    duration_ms: float,
    *,
    trace: TraceContext | None = None,
    start_ts: float | None = None,
    start_mono: float | None = None,
    status: str = "ok",
    **fields: Any,
) -> dict:
    """Record an already-measured phase as a finished span.

    For durations captured by existing instrumentation (the KV-wire
    gather/pack/wire phase clocks, queue-wait gaps computed from enqueue
    stamps) where wrapping the work in a ``with Span(...)`` block is not
    possible after the fact. The phase ended now unless a start is given, on
    the wall clock (``start_ts``) or on ``perf_counter`` (``start_mono``);
    the other clock's start follows from it. Returns the recorded span dict.
    """
    span = Span(name, trace=trace, **fields)
    now_wall, now_mono = time.time(), time.perf_counter()
    if start_mono is not None and start_ts is None:
        start_ts = now_wall - (now_mono - start_mono)
    elif start_ts is not None and start_mono is None:
        start_mono = now_mono - (now_wall - start_ts)
    span.t_wall = start_ts if start_ts is not None else now_wall - duration_ms / 1e3
    span.t0 = start_mono if start_mono is not None else now_mono - duration_ms / 1e3
    span.status = status
    logger.debug(
        "span %s %.1fms", name, duration_ms,
        extra={
            "span": name, "duration_ms": round(duration_ms, 3),
            "trace_id": span.trace_id, "span_id": span.span_id,
            "parent_id": span.parent_id, "status": status, **fields,
        },
    )
    span._record(duration_ms)
    return {
        "name": name, "trace_id": span.trace_id, "span_id": span.span_id,
        "parent_id": span.parent_id, "duration_ms": round(duration_ms, 3),
        "status": status, **fields,
    }


# -- host pauses --------------------------------------------------------------

#: A collection shorter than this is counted and leaves no span.
GC_SPAN_FLOOR_NS = 1_000_000
GC_GENERATIONS = 3


class HostPauseTracker:
    """What stopped the host, process-wide and on ``perf_counter_ns`` (the
    clock of :class:`StepClock` and of a STEP record's ``t0_ns``): the cyclic
    garbage collector, which holds the interpreter on whatever thread it runs,
    and the profiler's own start and stop.

    Every collection adds to the plain-integer counters by generation; one of
    :data:`GC_SPAN_FLOOR_NS` or more is also a pause. A pause is kept in
    :attr:`recent` as ``(t0_ns, dur_ns, cause, generation)`` for the engine's
    long-step lookup and becomes a ``host_pause`` span in :data:`SPANS`. The
    span is written by :meth:`flush`, never by the collector's callback: a
    collection can begin under any allocation, one made while holding the span
    ring's lock too.
    """

    def __init__(self) -> None:
        self.gc_count = [0] * GC_GENERATIONS
        self.gc_ns = [0] * GC_GENERATIONS
        self.recent: deque[tuple[int, int, str, int]] = deque(maxlen=64)
        #: ``perf_counter_ns`` at the start of the profiler call that is running (0: none).
        self.profiler_since_ns = 0
        self.installed = False
        #: Pauses whose span is not written yet (bounded: nobody may come to flush).
        self.pending: deque[tuple[int, int, dict]] = deque(maxlen=256)
        self._gc_t0 = 0
        self._gc_region = None

    def install(self) -> None:
        """Hook the collector (idempotent)."""
        if not self.installed:
            gc.callbacks.append(self._on_gc)
            self.installed = True

    def uninstall(self) -> None:
        """Leave ``gc.callbacks`` as :meth:`install` found it (tests)."""
        if self.installed:
            gc.callbacks.remove(self._on_gc)
            self.installed = False

    def _on_gc(self, phase: str, info: dict) -> None:
        # One collection runs at a time, so a start and its stop pair up.
        if phase == "start":
            if _annotating:
                import jax

                self._gc_region = jax.profiler.TraceAnnotation("host.gc")  # never "engine.*"
                self._gc_region.__enter__()
            self._gc_t0 = time.perf_counter_ns()
            return
        dur = time.perf_counter_ns() - self._gc_t0
        gen = info["generation"]
        self.gc_count[gen] += 1
        self.gc_ns[gen] += dur
        if self._gc_region is not None:
            region, self._gc_region = self._gc_region, None
            region.__exit__(None, None, None)
        if dur >= GC_SPAN_FLOOR_NS:
            self.note("gc", self._gc_t0, dur, generation=gen, collected=info["collected"],
                      uncollectable=info["uncollectable"])

    def note(self, cause: str, t0_ns: int, dur_ns: int, *, generation: int = -1, **fields: Any) -> None:
        """Keep one pause; its span waits for :meth:`flush`."""
        self.recent.append((t0_ns, dur_ns, cause, generation))
        if generation >= 0:
            fields["generation"] = generation
        fields.update(cause=cause, t0_ns=t0_ns, thread=threading.current_thread().name)
        self.pending.append((t0_ns, dur_ns, fields))

    @contextlib.contextmanager
    def profiler(self, what: str) -> Iterator[None]:
        """Round ``jax.profiler.start_trace`` / ``stop_trace``."""
        self.profiler_since_ns = t0 = time.perf_counter_ns()
        try:
            yield
        finally:
            self.profiler_since_ns = 0
            self.note("profiler", t0, time.perf_counter_ns() - t0, what=what)
            self.flush()

    def flush(self) -> None:
        """Write the kept pauses' spans (``request_id`` ``host_pause``, so that
        ``/debug/traces/host_pause`` lists them)."""
        while self.pending:
            try:
                t0_ns, dur_ns, fields = self.pending.popleft()
            except IndexError:  # another thread flushed it
                return
            record_span("host_pause", dur_ns / 1e6, start_mono=t0_ns / 1e9, request_id="host_pause", **fields)

    def overlap_ms(self, lo_ns: int, hi_ns: int) -> tuple[float, int, float]:
        """``(gc_ms, oldest generation among them or -1, profiler_ms)`` of the
        kept pauses, and of a profiler call still running, inside ``[lo_ns, hi_ns]``."""
        gc_ns = prof_ns = 0
        oldest = -1
        for t0, dur, cause, gen in tuple(self.recent):
            cover = min(hi_ns, t0 + dur) - max(lo_ns, t0)
            if cover <= 0:
                continue
            if cause == "gc":
                gc_ns += cover
                oldest = max(oldest, gen)
            else:
                prof_ns += cover
        since = self.profiler_since_ns
        if since:
            prof_ns += max(0, hi_ns - max(lo_ns, since))
        return gc_ns / 1e6, oldest, prof_ns / 1e6


#: The process's tracker. The profiler's pauses are always noted; the
#: collector's once :func:`install_host_pauses` has run.
HOST_PAUSES = HostPauseTracker()


def install_host_pauses() -> HostPauseTracker:
    """Hook the process's tracker to the collector (the first ``EngineCore`` calls it)."""
    HOST_PAUSES.install()
    return HOST_PAUSES


def uninstall_host_pauses() -> None:
    HOST_PAUSES.uninstall()


def trace_of(context: Any) -> TraceContext | None:
    """The TraceContext riding a runtime ``Context`` (or None)."""
    return TraceContext.from_dict(getattr(context, "trace", None))
