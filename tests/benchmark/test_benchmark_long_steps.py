"""The three readers of the program's long steps and host pauses
(``benchmark/long_steps.py``), on planted spans over the steps and the trace of
``benchmark/data/small_phases.json``."""

import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent.parent
sys.path.insert(0, str(ROOT))

from benchmark import long_steps as ls  # noqa: E402
from benchmark import plugins  # noqa: E402
from benchmark import run as bench_run  # noqa: E402
from benchmark import trace_reduce as tr  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAMES = ("engine.long_step_lost_ms", "engine.long_step_named_pct", "service.gc_pause_ms")


@pytest.fixture
def data():
    return json.loads((ROOT / "benchmark" / "data" / "small_phases.json").read_text())


def long_step(ts, lost, cause, *, phase="wait", traced=False, rows=48):
    return {"name": "engine_long_step", "request_id": "engine_long_step", "start_ts": ts, "duration_ms": lost + 17.0,
            "expected_ms": 17.0, "lost_ms": lost, "phase": phase, "phase_ms": lost + 9.0, "cause": cause,
            "gc_ms": lost if cause == "gc" else 0.0, "gc_generation": 2 if cause == "gc" else -1, "profiler_ms": 0.0,
            "step_kind": "decode", "decode_rows": rows, "traced": traced, "t0_ns": 0, "seq": 0}


def pause(ts, t0_ns, ms, cause, *, generation=2, thread="asyncio_0", what=None):
    span = {"name": "host_pause", "request_id": "host_pause", "start_ts": ts, "duration_ms": ms, "cause": cause,
            "t0_ns": t0_ns, "thread": thread}
    span.update({"generation": generation, "collected": 5, "uncollectable": 0} if cause == "gc" else {"what": what})
    return span


@pytest.fixture
def ctx(data, monkeypatch):
    """What ``benchmark/run.py`` hands a reader: the file's six steps (``ts``
    1000.01 to 1000.0605) and its trace (trace clock = ``perf_counter_ns`` + 5 ms;
    the device idles 1034.3-1037.45, 1044.3-1047.95 and 1054.8-1057.95 ms)."""
    from dynamo_tpu import tracing

    ring = tracing.SpanBuffer(64)
    monkeypatch.setattr(tracing, "SPANS", ring)
    monkeypatch.setattr(tracing, "HOST_PAUSES", tracing.HostPauseTracker())
    for span in data["spans"]:
        ring.record(span)
    return {"window": {"steps": data["steps"]}, "trace": data["trace"],
            "step_programs": tr.step_programs(data["trace"]), "notes": {}}


def plant(spans):
    from dynamo_tpu import tracing

    for span in spans:
        tracing.SPANS.record(span)


def read(name, ctx):
    return plugins.load("layer_metrics", name).read(ctx)


def test_the_three_entries_are_counters_that_move_out_tok_s_in_the_four_throughput_cells():
    entries = {m["name"]: m for m in BENCH["per_layer"] if m["name"] in NAMES}  # by name: no position is asserted
    assert set(entries) == set(NAMES)
    for m in entries.values():
        assert (m["source"], m["moves"]) == ("program_counter", "out_tok_s") and "workloads" not in m
        assert (ROOT / "benchmark" / "layer_metrics" / f"{m['name']}.py").is_file()
    assert {n: (entries[n]["unit"], entries[n]["better"], entries[n]["layer"]) for n in NAMES} == {
        "engine.long_step_lost_ms": ("ms", "lower", "engine"), "engine.long_step_named_pct": ("%", "higher", "engine"),
        "service.gc_pause_ms": ("ms", "lower", "service")}
    cells = [w["name"] for w in BENCH["workloads"]
             if set(NAMES) <= {m["name"] for m in bench_run.cell_metrics(BENCH, "per_layer", w)}]
    out_tok_s = next(m for m in BENCH["end_to_end"] if m["name"] == "out_tok_s")["workloads"]
    assert cells == out_tok_s and len(cells) == 4 and "olmoe-1b-7b-int8.chat-steady" not in cells


def test_lost_ms_sums_the_windows_long_steps_and_leaves_the_profilers_out(ctx):
    plant([long_step(1000.02, 100.0, "gc"), long_step(1000.03, 60.0, "", phase="handoff", traced=True),
           long_step(1000.04, 40.0, "compile", phase="dispatch", rows=6), long_step(1000.05, 90.0, "profiler")])
    assert read("engine.long_step_lost_ms", ctx) == pytest.approx(200.0)
    assert read("engine.long_step_named_pct", ctx) == pytest.approx(70.0)  # gc + compile of gc + compile + unnamed
    note = ctx["notes"]["long_steps"]
    assert note["steps"] == 3 and note["profiler"] == {"steps": 1, "lost_ms": 90.0}
    assert note["lost_ms_by_phase"] == {"wait": 100.0, "handoff": 60.0, "dispatch": 40.0}
    assert note["lost_ms_by_cause"] == {"gc": 100.0, "unnamed": 60.0, "compile": 40.0}
    assert note["lost_pct_of_window"] == pytest.approx(100 * 200.0 / 50.5, rel=1e-3)  # the steps span 50.5 ms
    assert [(s["period_ms"], s["cause"], s["phase"], s["rows"], s["traced"]) for s in note["longest"]] == [
        (117.0, "gc", "wait", 48, False), (107.0, "profiler", "wait", 48, False),
        (77.0, "", "handoff", 48, True), (57.0, "compile", "dispatch", 6, False)]
    assert ctx["notes"]["long_steps_window"] == {"s": pytest.approx(0.0505), "ring_dropped": 0}


def test_with_the_tracker_and_no_long_step_the_readers_say_zero_and_a_hundred(ctx):
    assert read("engine.long_step_lost_ms", ctx) == 0.0
    assert read("engine.long_step_named_pct", ctx) == 100.0
    assert read("service.gc_pause_ms", ctx) == 0.0
    assert ctx["notes"]["long_steps"]["steps"] == 0 and ctx["notes"]["host_pauses"]["collections"] == 0
    plant([long_step(1000.02, 50.0, "")])  # everything lost and nothing named
    ctx.pop("_long_steps")
    assert read("engine.long_step_named_pct", ctx) == 0.0


def test_a_program_without_the_tracker_gives_nothing_and_raises_nothing(ctx, monkeypatch):
    from dynamo_tpu import tracing

    plant([long_step(1000.02, 100.0, "gc"), pause(1000.02, 1_010_000_000, 4.0, "gc")])
    monkeypatch.delattr(tracing, "HOST_PAUSES")  # the parent's dynamo_tpu/tracing.py
    assert [read(n, ctx) for n in NAMES] == [None, None, None]
    assert ctx["notes"] == {}


def test_a_span_is_the_windows_if_it_starts_between_the_first_and_the_last_step(ctx):
    plant([long_step(1000.0099, 10.0, ""), long_step(1000.01, 20.0, ""), long_step(1000.0605, 30.0, ""),
           long_step(1000.0606, 40.0, ""), pause(1000.0, 990_000_000, 7.0, "gc"), pause(1000.01, 1_000_000_000, 2.0, "gc"),
           pause(1000.07, 1_060_000_000, 9.0, "gc")])
    assert read("engine.long_step_lost_ms", ctx) == pytest.approx(50.0)
    assert read("service.gc_pause_ms", ctx) == pytest.approx(2.0)


def test_gc_pause_ms_sums_collections_and_puts_the_devices_idle_under_each_pause(ctx, data):
    ms = 1_000_000
    plant([pause(1000.025, 1030 * ms, 2.0, "gc"),  # trace clock 1035-1037 ms: inside the first idle gap
           pause(1000.035, 1038 * ms, 3.0, "gc", generation=1, thread="MainThread"),  # 1043-1046: 1.7 ms of the second
           pause(1000.045, 1049 * ms, 1.5, "profiler", what="stop"),  # 1054-1055.5: 0.7 ms of the third
           pause(1000.05, 1015 * ms, 1.0, "gc", generation=0)])  # 1020-1021: before the first program, under no gap
    assert read("service.gc_pause_ms", ctx) == pytest.approx(6.0)  # the profiler's is no collection
    note = ctx["notes"]["host_pauses"]
    assert note["collections"] == 3 and note["longest_ms"] == 3.0
    assert note["ms_by_generation"] == {"1": 3.0, "2": 2.0, "0": 1.0}
    assert note["count_by_generation"] == {"0": 1, "1": 1, "2": 1}
    assert note["ms_by_thread"] == {"asyncio_0": 3.0, "MainThread": 3.0}
    assert note["profiler"] == [{"what": "stop", "ms": 1.5}]
    assert note["device_idle_under_pause_s"] == {"gc": pytest.approx(3.7e-3), "profiler": pytest.approx(0.7e-3)}
    assert data["offset_ns"] == 5 * ms


def test_without_a_trace_the_note_has_no_idle_line_and_the_readers_flush_the_tracker(ctx):
    from dynamo_tpu import tracing

    ctx["trace"] = None
    tracing.HOST_PAUSES.note("gc", 1_030_000_000, 2_500_000, generation=2, collected=1, uncollectable=0)
    assert read("service.gc_pause_ms", ctx) == 0.0  # written now: its start_ts is today's, not the file's
    assert "device_idle_under_pause_s" not in ctx["notes"]["host_pauses"]
    assert not tracing.HOST_PAUSES.pending and len(tracing.SPANS.query(request_id="host_pause")) == 1
