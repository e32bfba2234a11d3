"""From a profiler trace to numbers: the reduction every PR shares.

Works on a neutral form, so that it can be checked on a small recorded trace
without a chip: ``{"planes": [{"name", "lines": [{"name", "events": [[name,
start_ns, duration_ns], ...]}]}]}``. ``load_xplane`` makes that form from the
``.xplane.pb`` the JAX profiler writes.

Busy time is the *union* of the intervals in which an operation ran on the
device: a ``while`` that contains its body's fusions, or two overlapping
events, count once. (Summing durations, as ``tools/profile_1b_decode.py`` did,
read a busy share of 3.06.)
"""

from __future__ import annotations

import glob
import os

DEVICE_PREFIX = "/device:TPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: A step program is a module whose name carries this mark (the runner's
#: jitted ``_step*`` functions); transfers and small helpers do not.
STEP_MARK = "step"
#: Host spans the engine writes with ``dynamo_tpu.tracing.annotate``.
HOST_SPAN_PREFIX = "engine."
BETWEEN = "between_steps"


def op_name(name: str) -> str:
    """The trace prints an op as its whole HLO line ("%fusion.8 = bf16[...] fusion(...)"): keep its name."""
    return name.split(" = ", 1)[0].lstrip("%")


def load_xplane(trace_dir: str) -> dict:
    import jax

    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(files[-1])
    planes = []
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        lines = []
        for line in plane.lines:
            events = [[op_name(e.name), float(e.start_ns), float(e.duration_ns)] for e in line.events
                      if device or e.name.startswith(HOST_SPAN_PREFIX)]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def device_planes(trace: dict) -> list[dict]:
    return [p for p in trace["planes"] if p["name"].startswith(DEVICE_PREFIX)]


def line_events(plane: dict, line_name: str) -> list[list]:
    return [e for line in plane["lines"] if line["name"] == line_name for e in line["events"]]


def host_spans(trace: dict) -> list[list]:
    """[name, start_ns, duration_ns] of the engine's annotations, by start."""
    spans = [e for p in trace["planes"] if not p["name"].startswith("/device:")
             for line in p["lines"] for e in line["events"] if e[0].startswith(HOST_SPAN_PREFIX)]
    return sorted(spans, key=lambda e: e[1])


def busy_events(plane: dict) -> list[list]:
    """The plane's op events; every line's where it has no ops line."""
    return line_events(plane, OPS_LINE) or [ev for ln in plane["lines"] for ev in ln["events"]]


def union(events: list[list]) -> list[tuple[float, float]]:
    """Merged [start, end) intervals of the events, in ns."""
    out: list[tuple[float, float]] = []
    for s, e in sorted((ev[1], ev[1] + ev[2]) for ev in events if ev[2] > 0):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def window_of(trace: dict) -> tuple[float, float]:
    """First start and last end over the device planes' events, in ns."""
    evs = [e for p in device_planes(trace) for line in p["lines"] for e in line["events"]]
    if not evs:
        raise ValueError("no device events in the trace")
    return min(e[1] for e in evs), max(e[1] + e[2] for e in evs)


def busy_seconds(trace: dict) -> float:
    """Seconds in which an operation ran, averaged over the device planes."""
    planes = device_planes(trace)
    if not planes:
        raise ValueError("no device plane in the trace")
    return sum(sum(e - s for s, e in union(busy_events(p))) for p in planes) / len(planes) / 1e9


def exclusive_by_name(events: list[list]) -> dict[str, float]:
    """Seconds per operation name, each instant given to the innermost event
    that covers it (a ``while`` keeps only what its body does not cover)."""
    totals: dict[str, float] = {}
    stack: list[list] = []  # [name, end, child_ns]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, end, own = stack.pop()
            totals[name] = totals.get(name, 0.0) + own

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        if dur <= 0:
            continue
        close(start)
        end = start + dur
        if stack:  # the covered part of the parent is the child's, nested or overlapping
            stack[-1][2] -= min(end, stack[-1][1]) - start
        stack.append([name, end, end - start])
    close(float("inf"))
    return {k: v / 1e9 for k, v in totals.items()}


def top_device_ops(trace: dict, k: int = 10) -> list[list]:
    totals: dict[str, float] = {}
    planes = device_planes(trace)
    for p in planes:
        for name, s in exclusive_by_name(line_events(p, OPS_LINE)).items():
            totals[name] = totals.get(name, 0.0) + s / len(planes)
    return [[n, s] for n, s in sorted(totals.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps(trace: dict, k: int = 10) -> list[list]:
    """Idle seconds of the first device, summed by the host span that covers
    them; what no span covers is ``between_steps``."""
    planes = device_planes(trace)
    if not planes:
        return []
    p = planes[0]
    busy = union(busy_events(p))
    spans = host_spans(trace)
    totals: dict[str, float] = {}
    for (_, gap_start), (gap_end, _) in zip(busy, busy[1:]):
        left = gap_end - gap_start
        for name, s, d in spans:
            if s >= gap_end:
                break
            cover = min(gap_end, s + d) - max(gap_start, s)
            if cover > 0:
                totals[name] = totals.get(name, 0.0) + cover
                left -= cover
        if left > 0:
            totals[BETWEEN] = totals.get(BETWEEN, 0.0) + left
    return [[n, s / 1e9] for n, s in sorted(totals.items(), key=lambda kv: -kv[1])[:k]]


def step_programs(trace: dict) -> list[dict]:
    """The step programs of the first device, in order: ``{"start", "dur",
    "span"}`` (ns), ``span`` being the engine annotation open when it began."""
    planes = device_planes(trace)
    if not planes:
        return []
    mods = sorted((e for e in line_events(planes[0], MODULES_LINE) if STEP_MARK in e[0]),
                  key=lambda e: e[1])
    spans = host_spans(trace)
    out, i = [], 0
    for name, start, dur in mods:
        while i + 1 < len(spans) and spans[i + 1][1] <= start:
            i += 1
        span = None
        # The annotation opens before the dispatch and closes after the host
        # has read the result, so it contains the program's start.
        for cand in spans[max(0, i - 2): i + 1]:
            if cand[1] <= start < cand[1] + cand[2]:
                span = cand[0]
        out.append({"name": name, "start": start, "dur": dur, "span": span})
    return out


def step_gaps_ms(steps: list[dict]) -> list[float]:
    return [(b["start"] - (a["start"] + a["dur"])) / 1e6 for a, b in zip(steps, steps[1:])]
