"""The program's own spans, read for the per-layer metrics (source
``program_span``): the phases in the engine's STEP flight records, the clock
join that puts them on the device trace's timeline, and the request-path spans
of the process-wide ring.

A STEP record (``ctx["window"]["steps"]``) holds ``t0_ns`` (``perf_counter_ns``
at the step's start), ``ann_ns`` (at the entry of its ``engine.*`` annotation),
``traced`` and ``phases_us``: ``sched build dispatch wait post`` tile its
``wall_ms`` from ``t0_ns``; ``record`` (the telemetry tail of the step before)
and ``handoff route intake no_work submit`` (the gap since) end at ``t0_ns``.
A program that writes no such fields (the parent of the PR that added them)
gives every reader here nothing to read: they return ``None`` and raise nothing.
"""

from __future__ import annotations

from benchmark import stats, trace_reduce

STEP_PHASES = ("sched", "build", "dispatch", "wait", "post")
CARRIED_PHASES = ("record", "handoff", "route", "intake", "no_work", "submit")
#: What the host spends on one step that the device does not: everything but
#: the blocking read-back and the wait for a request.
HOST_PHASES = ("sched", "build", "dispatch", "post", "record", "handoff", "route", "intake", "submit")
BETWEEN_PHASES = ("handoff", "route", "intake", "submit")
REQUEST_SPANS = ("frontend_pre_engine", "engine_queue_wait", "engine_admission_wait", "engine_prefill",
                 "frontend_first_byte")
#: After the device program's end the host still copies the tokens back: the
#: mapped end of ``wait`` may trail it by this much (and lead it by the join's error).
WAIT_END_AFTER_US, WAIT_END_BEFORE_US = 300.0, 50.0


def phased_steps(ctx, kind: str | None = "decode", traced: bool | None = False) -> list[dict]:
    """The window's STEP records that carry phases: of one kind, and taken
    inside (``traced``) or outside the profiled seconds; ``None`` takes both."""
    return [s for s in ctx["window"]["steps"] if "phases_us" in s
            and (kind is None or s["step_kind"] == kind)
            and (traced is None or bool(s.get("traced")) == traced)]


def phases_ms(step: dict, names) -> float:
    return sum(step["phases_us"].get(n, 0.0) for n in names) / 1e3


def phase_p50_ms(ctx, names, *, traced: bool = False):
    """Median over the decode steps of the summed phases, in ms."""
    steps = phased_steps(ctx, "decode", traced)
    return stats.percentile([phases_ms(s, names) for s in steps], 50) if steps else None


def phase_table(ctx) -> dict:
    """p50 of every phase, by step kind, outside the profiled seconds (ms)."""
    out = {}
    for kind in ("decode", "mixed"):
        steps = phased_steps(ctx, kind, False)
        if steps:
            out[kind] = {"steps": len(steps), **{n: round(stats.percentile([phases_ms(s, (n,)) for s in steps], 50), 4)
                                                 for n in STEP_PHASES + CARRIED_PHASES}}
    return out


# -- one clock ------------------------------------------------------------------


def clock_join(ctx) -> dict | None:
    """``trace clock - perf_counter_ns``, once per traced step: the k-th traced
    STEP record's ``ann_ns`` against the k-th ``engine.*`` event of the trace.
    The median is the offset; the spread of the rest is the alignment error."""
    if ctx.get("trace") is None:
        return None
    if "_clock_join" in ctx:
        return ctx["_clock_join"]
    anns = trace_reduce.host_spans(ctx["trace"])
    recs = [s for s in ctx["window"]["steps"] if s.get("traced") and s.get("ann_ns")]
    pairs = min(len(anns), len(recs))  # a step that straddles the trace's end has no event
    join = None
    if pairs:
        offs = [anns[i][1] - recs[i]["ann_ns"] for i in range(pairs)]
        med = stats.percentile(offs, 50)
        dev = [abs(o - med) / 1e3 for o in offs]
        join = {"pairs": pairs, "annotations": len(anns), "traced_records": len(recs), "offset_ns": med,
                "spread_us": (stats.percentile(offs, 75) - stats.percentile(offs, 25)) / 1e3,
                "p99_dev_us": stats.percentile(dev, 99), "max_dev_us": max(dev)}
    ctx["_clock_join"] = join
    return join


def phase_intervals(steps: list[dict], offset_ns: float) -> list[tuple[str, float, float]]:
    """``(phase, start, end)`` on the trace clock, in time order. A phase that a
    step entered twice (the overlapped pipeline) is laid out once, at its sum."""
    out = []
    for s in steps:
        p, t0 = s["phases_us"], s["t0_ns"] + offset_ns
        for names, t in ((CARRIED_PHASES, t0 - sum(p.get(n, 0.0) for n in CARRIED_PHASES) * 1e3), (STEP_PHASES, t0)):
            for name in names:
                d = p.get(name, 0.0) * 1e3
                if d > 0:
                    out.append((name, t, t + d))
                t += d
    return out


def idle_by_phase(ctx) -> dict | None:
    """Seconds of the first device's idle time (the gaps between its busy
    intervals) under each mapped phase of the window's STEP records."""
    join = clock_join(ctx)
    steps = phased_steps(ctx, None, None)
    planes = trace_reduce.device_planes(ctx["trace"]) if join else []
    if not join or not steps or not planes:
        return None
    busy = trace_reduce.union(trace_reduce.busy_events(planes[0]))
    gaps = [(a_end, b_start) for (_, a_end), (b_start, _) in zip(busy, busy[1:])]
    ivs = phase_intervals(steps, join["offset_ns"])
    totals = {"unexplained": 0.0}
    i = 0
    for g0, g1 in gaps:
        left = g1 - g0
        while i < len(ivs) and ivs[i][2] <= g0:
            i += 1
        j = i
        while j < len(ivs) and ivs[j][1] < g1:
            cover = min(g1, ivs[j][2]) - max(g0, ivs[j][1])
            if cover > 0:
                totals[ivs[j][0]] = totals.get(ivs[j][0], 0.0) + cover
                left -= cover
            j += 1
        totals["unexplained"] += max(0.0, left)
    return {k: v / 1e9 for k, v in totals.items()}


def wait_end_check(ctx) -> dict | None:
    """Per traced decode step: mapped end of ``wait`` less the end of the step's
    device program (the host reads the result back when the program is done, so
    the two ends belong together). A step's program is the one that starts
    nearest the mapped start of its ``dispatch``, within half a step: the
    profiler's device plane can sit a millisecond or two off its host plane, so
    "the program that began inside the step" would pick the next step's."""
    join = clock_join(ctx)
    progs = sorted(ctx.get("step_programs", []), key=lambda p: p["start"])
    recs = [s for s in phased_steps(ctx, "decode", True) if s.get("ann_ns")]
    if not join or not progs or not recs:
        return None
    deltas, starts, i = [], [], 0
    for s in recs:
        t0 = s["t0_ns"] + join["offset_ns"]
        dispatch = t0 + phases_ms(s, ("sched", "build")) * 1e6
        while i + 1 < len(progs) and abs(progs[i + 1]["start"] - dispatch) <= abs(progs[i]["start"] - dispatch):
            i += 1
        if abs(progs[i]["start"] - dispatch) < phases_ms(s, STEP_PHASES) * 1e6 / 2:
            wait_end = t0 + phases_ms(s, ("sched", "build", "dispatch", "wait")) * 1e6
            deltas.append((wait_end - (progs[i]["start"] + progs[i]["dur"])) / 1e3)
            starts.append((progs[i]["start"] - dispatch) / 1e3)
    if not deltas:
        return None
    good = sum(1 for d in deltas if -WAIT_END_BEFORE_US <= d <= WAIT_END_AFTER_US)
    return {"steps": len(deltas), "within_pct": 100.0 * good / len(deltas),
            "delta_p50_us": stats.percentile(deltas, 50), "delta_p99_us": stats.percentile(deltas, 99),
            "program_start_after_dispatch_start_p50_us": stats.percentile(starts, 50)}


# -- the request path -------------------------------------------------------------


def request_spans(ctx) -> list[dict]:
    """Per request that came in inside the window: ``{span name: duration_ms}``
    of the request-path spans recorded under its trace id."""
    if "_request_spans" in ctx:
        return ctx["_request_spans"]
    from dynamo_tpu.tracing import SPANS

    steps = ctx["window"]["steps"]
    by_trace: dict[str, dict] = {}
    for s in SPANS.query() if steps else []:
        if s["name"] in REQUEST_SPANS:
            by_trace.setdefault(s["trace_id"], {})[s["name"]] = s
    lo, hi = (steps[0]["ts"], steps[-1]["ts"]) if steps else (0.0, 0.0)
    rows = [{n: s["duration_ms"] for n, s in spans.items()} for spans in by_trace.values()
            if "frontend_pre_engine" in spans and lo <= spans["frontend_pre_engine"]["start_ts"] <= hi]
    ctx["notes"]["request_spans"] = {"requests": len(rows), "ring_dropped": getattr(SPANS, "dropped", None)}
    ctx["_request_spans"] = rows
    return rows


def request_p50_ms(ctx, names):
    """Median over the window's requests of the summed spans (a request that
    lacks one of them, cancelled before it, is left out)."""
    vals = [sum(r[n] for n in names) for r in request_spans(ctx) if all(n in r for n in names)]
    return stats.percentile(vals, 50) if vals else None
