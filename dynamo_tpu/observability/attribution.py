"""Latency attribution: per-request critical-path budgets + the loss-cause
vocabulary behind fleet-wide time-loss accounting.

The telemetry planes record *what happened* — spans (``tracing.py``), flight
STEP records (``flight.py``), compile events (``compile.py``) — but none of
them answers the operator's first question: *where did this request's latency
go?* This module is the join:

- :func:`build_explain` folds one request's span timeline and the serving
  worker's flight ring into an **ordered critical-path budget** — queue,
  admission gate, onboard fetch, prefill, KV gather/pack/wire/scatter, decode
  compute vs. host gap vs. barrier-by-reason (the pinned
  :data:`~dynamo_tpu.engine.core.BARRIER_REASONS` vocabulary), recompiles —
  whose segments sum to within tolerance of the measured E2E latency. The
  residual is reported explicitly as ``unattributed``, never silently
  absorbed. Served at ``GET /debug/explain/{request_id}`` (frontend fan-out
  over the ``debug_explain`` worker endpoint, ``service.py``).
- :data:`LOSS_CAUSES` pins the label set of
  ``dynamo_engine_lost_time_seconds_total{worker,cause}`` — the fleet-wide
  aggregate the engine charges continuously (``EngineCore._charge_loss``) so
  ``/metrics`` answers "where does this fleet's time go" without a
  per-request query. The set is the barrier vocabulary plus the six
  engine-plane causes that exist outside a barrier step; a new barrier
  reason is a new loss cause by construction
  (``tools/check_barrier_reasons.py`` pins both ends).
"""

from __future__ import annotations

from typing import Any, Iterable

from dynamo_tpu.engine.core import BARRIER_REASONS

#: Loss causes that exist outside the overlap-barrier vocabulary: request
#: wait before admission ("queue": resource wait, "admission": quota gate),
#: steps that idled on a tier fetch, preemption work, XLA recompiles on the
#: serving path, and the residual host gap between dispatches.
EXTRA_LOSS_CAUSES = ("queue", "admission", "onboard_stall", "preempt", "recompile", "gap")

#: The pinned label set of dynamo_engine_lost_time_seconds_total{cause}.
LOSS_CAUSES = tuple(BARRIER_REASONS) + EXTRA_LOSS_CAUSES

#: Span names folded into each pre-decode segment of the explain budget.
_QUEUE_SPANS = ("engine_queue_wait", "prefill_queue_wait")
_ADMISSION_SPANS = ("engine_admission_wait",)
_ONBOARD_SPANS = ("engine_onboard_wait",)
_PREFILL_SPANS = ("prefill_exec",)
_KV_SPANS = ("kv_gather", "kv_pack", "kv_wire", "kv_scatter")


def _span_ms(spans: Iterable[dict], names: tuple[str, ...]) -> float:
    return sum(
        float(s.get("duration_ms") or 0.0) for s in spans if s.get("name") in names
    )


def _find_span(spans: list[dict], name: str) -> dict | None:
    hits = [s for s in spans if s.get("name") == name]
    if not hits:
        return None
    # Earliest wins: a retried hop records later duplicates.
    return min(hits, key=lambda s: s.get("start_ts") or 0.0)


def _latest_span(spans: list[dict], name: str) -> dict | None:
    hits = [s for s in spans if s.get("name") == name]
    if not hits:
        return None
    return max(hits, key=lambda s: s.get("start_ts") or 0.0)


def _steps_by_worker(step_docs: list[dict]) -> dict[str, list[dict]]:
    by_worker: dict[str, list[dict]] = {}
    for doc in step_docs:
        wid = str(doc.get("worker", ""))
        by_worker.setdefault(wid, []).extend(doc.get("steps", []))
    return by_worker


def build_explain(
    request_id: str,
    spans: list[dict],
    step_docs: list[dict] | None = None,
    *,
    tolerance_frac: float = 0.1,
) -> dict[str, Any] | None:
    """One request's ordered critical-path budget, or None without an anchor.

    ``spans`` is the deduped union of span docs for the request (frontend +
    every worker, as ``/debug/traces`` assembles); ``step_docs`` is the
    ``debug_explain`` fan-out result — per-worker
    ``{"worker", "steps", "compiles"}`` docs whose STEP/COMPILE records are
    windowed against the request's span bounds here. Decode-phase steps are
    taken from the single worker with the most steps inside the decode
    window (the engine that actually served the decode loop): flight records
    carry no request ids, so cross-worker records would double-charge the
    same wall-clock.
    """
    anchor = _find_span(spans, "http_request") or _find_span(spans, "engine_request")
    if anchor is None:
        return None
    e2e_ms = float(anchor.get("duration_ms") or 0.0)
    t_start = float(anchor.get("start_ts") or 0.0)
    t_end = t_start + e2e_ms / 1e3

    # In disagg the prefill worker serves the remote half through its OWN
    # engine, so the request's span union holds TWO engine_request /
    # engine_first_token / engine-wait sets under one id: the prefill-side
    # set nested inside remote_prefill + prefill_exec, and the decode-side
    # set after the remote window. The budget anchors on the decode engine
    # (latest start); prefill-side engine time is already covered by the
    # remote-prefill decomposition below.
    engine = _latest_span(spans, "engine_request") or anchor
    engine_ms = float(engine.get("duration_ms") or 0.0)
    first = _latest_span(spans, "engine_first_token")
    ttft_ms = min(float(first.get("duration_ms") or 0.0), engine_ms) if first else 0.0
    t_first = float(engine.get("start_ts") or t_start) + ttft_ms / 1e3

    remote_span = _find_span(spans, "remote_prefill")
    remote_ms = float(remote_span.get("duration_ms") or 0.0) if remote_span else 0.0
    r0 = float(remote_span.get("start_ts") or 0.0) if remote_span else 0.0
    r1 = r0 + remote_ms / 1e3

    def _outside_remote(s: dict) -> bool:
        if remote_span is None:
            return True
        mid = float(s.get("start_ts") or 0.0) + float(s.get("duration_ms") or 0.0) / 2e3
        return not (r0 <= mid <= r1)

    def _engine_side_ms(names: tuple[str, ...]) -> float:
        return _span_ms((s for s in spans if _outside_remote(s)), names)

    # Pre-decode segments are de-overlapped along the span hierarchy: the
    # decode operator's remote_prefill wait sits BEFORE the decode-side
    # engine_request and contains prefill_queue_wait + prefill_exec (which
    # itself contains the sender-side kv_gather/pack/wire) + kv_scatter, so
    # each nested span is charged once and only the uncovered slack of each
    # parent remains. Engine-side waits count only outside the remote window
    # (the prefill engine's own queue/admission waits ride remote compute).
    engine_queue_ms = _engine_side_ms(("engine_queue_wait",))
    prefill_queue_ms = _span_ms(spans, ("prefill_queue_wait",))
    queue_ms = engine_queue_ms + prefill_queue_ms
    admission_ms = _engine_side_ms(_ADMISSION_SPANS)
    onboard_ms = _engine_side_ms(_ONBOARD_SPANS)
    kv_ms = {name: _span_ms(spans, (name,)) for name in _KV_SPANS}
    prefill_exec_ms = _span_ms(spans, _PREFILL_SPANS)
    kv_sender_ms = kv_ms["kv_gather"] + kv_ms["kv_pack"] + kv_ms["kv_wire"]
    # Remote prefill compute = prefill_exec minus the transfer phases it
    # wraps; transfer_wait = the remote window's remaining slack (queue-task
    # pickup, KV-landed event propagation).
    remote_compute_ms = max(0.0, prefill_exec_ms - kv_sender_ms)
    remote_parts_ms = (
        prefill_queue_ms + remote_compute_ms + kv_sender_ms + kv_ms["kv_scatter"]
    )
    transfer_wait_ms = max(0.0, remote_ms - remote_parts_ms)
    # The wire path overlaps: the receiver scatters while the sender is
    # still streaming, and prefill_exec can run a beat past the remote
    # window. Concurrency must not bill twice — squeeze the remote-side
    # components proportionally into the measured remote window.
    if remote_span is not None and remote_parts_ms > remote_ms > 0.0:
        scale = remote_ms / remote_parts_ms
        prefill_queue_ms *= scale
        remote_compute_ms *= scale
        kv_ms = {k: v * scale for k, v in kv_ms.items()}
        queue_ms = engine_queue_ms + prefill_queue_ms
    # Local prefill: whatever of the engine-side TTFT the named waits don't
    # explain is time the step loop spent on prompt chunks + the first
    # decode dispatch (spans don't time local chunks individually).
    local_prefill_ms = max(
        0.0, ttft_ms - engine_queue_ms - admission_ms - onboard_ms,
    )
    prefill_ms = remote_compute_ms + local_prefill_ms

    # Decode split from the serving worker's STEP records in the decode
    # window (first token -> request end).
    decode_worker = ""
    compute_ms = 0.0
    gap_ms = 0.0
    barrier_ms: dict[str, float] = {}
    recompile_ms = 0.0
    steps_in_window = 0
    if step_docs:
        best: list[dict] = []
        for wid, steps in _steps_by_worker(step_docs).items():
            windowed = [
                s for s in steps if t_first <= float(s.get("ts") or 0.0) <= t_end
            ]
            if len(windowed) > len(best):
                best, decode_worker = windowed, wid
        steps_in_window = len(best)
        for s in best:
            wall = float(s.get("wall_ms") or 0.0)
            dispatch = float(s.get("dispatch_ms") or 0.0)
            # Mock/timing runners track no dispatch clock: their step wall
            # IS the model compute analog.
            compute = dispatch if dispatch > 0.0 else wall
            host = max(0.0, wall - compute)
            compute_ms += compute
            gap_ms += float(s.get("gap_ms") or 0.0)
            reason = s.get("barrier_reason") or ""
            if s.get("overlap_mode") == "barrier" and reason:
                barrier_ms[reason] = barrier_ms.get(reason, 0.0) + host
            else:
                gap_ms += host
        pre_compile_ms = 0.0
        post_compile_ms = 0.0
        for doc in step_docs:
            if str(doc.get("worker", "")) != decode_worker:
                continue
            for c in doc.get("compiles", []):
                if c.get("reason") == "warm_cache":
                    continue
                ts = float(c.get("ts") or 0.0)
                if t_start <= ts <= t_end:
                    if ts <= t_first:
                        pre_compile_ms += float(c.get("wall_ms") or 0.0)
                    else:
                        post_compile_ms += float(c.get("wall_ms") or 0.0)
        # Compile time happens inside a dispatch: carve it out of the window
        # it physically sat in — the decode-window share out of the measured
        # step compute, the remainder (typically the first-dispatch compile
        # riding the TTFT) out of the prefill segment — so it reports as its
        # own segment without double-charging the time it inflated.
        recompile_ms = min(post_compile_ms, compute_ms)
        compute_ms -= recompile_ms
        pre_compile_ms += post_compile_ms - recompile_ms
        recompile_prefill_ms = min(pre_compile_ms, prefill_ms)
        prefill_ms -= recompile_prefill_ms
        # Step records carry whole-step walls and inter-step gaps, which can
        # overhang the request's decode window (a window-edge step, or a
        # first step whose gap spans pre-request idle). Scale the decode
        # split down to the window so the overshoot never masquerades as
        # negative unattributed time. The prefill-side recompile share lives
        # outside the decode window and must not be squeezed with it.
        decode_window = max(0.0, engine_ms - ttft_ms)
        decode_total = compute_ms + gap_ms + recompile_ms + sum(barrier_ms.values())
        if decode_total > decode_window > 0.0:
            scale = decode_window / decode_total
            compute_ms *= scale
            gap_ms *= scale
            recompile_ms *= scale
            barrier_ms = {k: v * scale for k, v in barrier_ms.items()}
        elif decode_window == 0.0:
            compute_ms = gap_ms = recompile_ms = 0.0
            barrier_ms = {}
        recompile_ms += recompile_prefill_ms

    segments: list[dict[str, Any]] = []

    def seg(name: str, ms: float, **extra: Any) -> None:
        if ms > 0.0:
            segments.append({"name": name, "ms": round(ms, 3), **extra})

    seg("queue", queue_ms)
    seg("admission", admission_ms)
    seg("onboard", onboard_ms)
    seg("prefill", prefill_ms)
    for name in _KV_SPANS:
        seg(name, kv_ms[name])
    seg("transfer_wait", transfer_wait_ms)
    seg("decode_compute", compute_ms)
    seg("gap", gap_ms)
    for reason in sorted(barrier_ms, key=barrier_ms.get, reverse=True):
        seg(f"barrier:{reason}", barrier_ms[reason], reason=reason)
    seg("recompile", recompile_ms)
    # Frontend-side time around the engine span and the remote-prefill wait
    # (parse, route, flush).
    if anchor is not engine:
        seg("frontend", max(0.0, e2e_ms - engine_ms - remote_ms))

    attributed_ms = sum(s["ms"] for s in segments)
    unattributed_ms = round(e2e_ms - attributed_ms, 3)
    segments.append({"name": "unattributed", "ms": unattributed_ms})
    return {
        "request_id": request_id,
        "trace_id": anchor.get("trace_id", ""),
        "e2e_ms": round(e2e_ms, 3),
        "engine_ms": round(engine_ms, 3),
        "ttft_ms": round(ttft_ms, 3),
        "decode_ms": round(max(0.0, engine_ms - ttft_ms), 3),
        "decode_worker": decode_worker,
        "steps_in_window": steps_in_window,
        "segments": segments,
        "attributed_ms": round(attributed_ms, 3),
        "unattributed_ms": unattributed_ms,
        "coverage_frac": round(attributed_ms / e2e_ms, 4) if e2e_ms > 0 else 0.0,
        "within_tolerance": abs(unattributed_ms) <= tolerance_frac * e2e_ms,
    }
