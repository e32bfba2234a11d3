"""The readers of the program's own spans (``benchmark/program_spans.py`` and
the ``program_span`` metrics), on ``benchmark/data/small_phases.json``: six
decode STEP records, the device trace of the last four at a known clock
offset, and a handful of request spans (CPU, no chip)."""

import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import plugins, program_spans as ps, trace_reduce as tr  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
SPAN_METRICS = [m["name"] for m in BENCH["per_layer"] if m["source"] == "program_span"]


@pytest.fixture
def data():
    return json.loads((ROOT / "benchmark" / "data" / "small_phases.json").read_text())


@pytest.fixture
def ctx(data, monkeypatch):
    """What ``benchmark/run.py`` hands a reader, with the span ring planted."""
    from dynamo_tpu import tracing

    ring = tracing.SpanBuffer(64)
    for span in data["spans"]:
        ring.record(span)
    monkeypatch.setattr(tracing, "SPANS", ring)
    return {"window": {"steps": data["steps"]}, "trace": data["trace"],
            "step_programs": tr.step_programs(data["trace"]), "notes": {}}


def read(name, ctx):
    return plugins.load("layer_metrics", name).read(ctx)


def test_there_are_twelve_span_metrics_each_with_a_reader():
    assert len(SPAN_METRICS) == 12
    assert all((ROOT / "benchmark" / "layer_metrics" / f"{n}.py").is_file() for n in SPAN_METRICS)


def test_clock_join_recovers_the_offset_and_its_spread(ctx, data):
    join = ps.clock_join(ctx)
    assert join["pairs"] == 4 and join["offset_ns"] == data["offset_ns"]
    # the annotations jitter by +2, -2, +1, -1 us around the offset
    assert join["spread_us"] == pytest.approx(2.5) and join["max_dev_us"] == pytest.approx(2.0)


def test_a_step_cut_off_by_the_traces_end_is_left_out_of_the_join(ctx, data):
    data["trace"]["planes"][1]["lines"][0]["events"].pop()  # its annotation never closed inside the trace
    join = ps.clock_join(ctx)
    assert (join["pairs"], join["annotations"], join["traced_records"]) == (3, 3, 4)
    assert join["offset_ns"] == pytest.approx(data["offset_ns"], abs=2000)


def test_phases_map_onto_the_trace_clock_in_order(data):
    ivs = ps.phase_intervals(data["steps"], data["offset_ns"])
    assert all(a[2] <= b[1] + 1e-6 for a, b in zip(ivs, ivs[1:]))  # they tile: none overlaps the next
    first = data["steps"][0]
    assert ivs[0] == ("record", first["t0_ns"] + data["offset_ns"] - 1_000_000, first["t0_ns"] + data["offset_ns"] - 850_000)
    holes = [b[1] - a[2] for a, b in zip(ivs, ivs[1:]) if b[1] - a[2] > 1]
    assert holes == [pytest.approx(300_000)]  # the one stretch no phase covers, planted before step 4


def test_idle_explained_reads_the_planted_share_and_notes_idle_by_phase(ctx):
    # three gaps between programs of 3,150 us each under phases, + 300 us uncovered + 200 us of no_work
    assert read("device.idle_explained_pct", ctx) == pytest.approx(100 * 9450 / 9750)
    by_phase = ctx["notes"]["idle_by_phase_s"]
    assert by_phase["post"] == pytest.approx(1800e-6) and by_phase["wait"] == pytest.approx(450e-6)
    assert by_phase["unexplained"] == pytest.approx(300e-6) and by_phase["no_work"] == pytest.approx(200e-6)
    assert sum(by_phase.values()) == pytest.approx(9950e-6)
    assert ctx["notes"]["clock_join"]["pairs"] == 4
    check = ctx["notes"]["wait_end_vs_program_end"]  # 100 us after the program in three steps, 400 in the fourth
    assert check["steps"] == 4 and check["within_pct"] == 75.0 and check["delta_p50_us"] == pytest.approx(100.0)


def test_profiler_overhead_is_traced_host_time_less_untraced(ctx):
    assert read("runner.step_host_p50_ms", ctx) == pytest.approx(2.0)  # steps 0-1, outside the trace
    assert read("tracing.profiler_overhead_ms", ctx) == pytest.approx(1.0)  # steps 2-5 read 3.0
    table = ctx["notes"]["phase_p50_ms"]["decode"]
    assert table["steps"] == 2 and table["wait"] == 8.0 and table["submit"] == 0.45


@pytest.mark.parametrize("name, want", [
    ("engine.sched_build_p50_ms", 0.3), ("runner.dispatch_p50_ms", 0.3), ("engine.post_p50_ms", 0.4),
    ("engine.record_p50_ms", 0.15), ("service.between_steps_p50_ms", 0.85)])
def test_phase_metrics_read_the_untraced_decode_steps(ctx, name, want):
    assert read(name, ctx) == pytest.approx(want)


@pytest.mark.parametrize("name, want", [
    ("frontend.pre_engine_p50_ms", 2.0), ("engine.queue_wait_p50_ms", 32.0),
    ("engine.prefill_p50_ms", 420.0),  # two requests have the span: 400 and 440
    ("frontend.first_byte_p50_ms", 0.7)])
def test_request_metrics_read_the_requests_that_came_in_inside_the_window(ctx, name, want):
    assert read(name, ctx) == pytest.approx(want)
    assert ctx["notes"]["request_spans"] == {"requests": 3, "ring_dropped": 0}


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_no_trace_gives_none_or_a_number_and_no_error(ctx, name):
    """A rehearsal, or a trace that failed to load: nothing raises."""
    ctx["trace"], ctx["step_programs"] = None, []
    value = read(name, ctx)
    needs_trace = name in ("device.idle_explained_pct",)
    assert value is None if needs_trace else isinstance(value, float)


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_a_program_without_the_spans_gives_every_reader_nothing(ctx, name, monkeypatch):
    """The parent of the PR that added them: STEP records without phases, a
    ring without the request spans. Every reader returns None."""
    from dynamo_tpu import tracing

    for step in ctx["window"]["steps"]:
        for key in ("phases_us", "t0_ns", "ann_ns", "traced"):
            del step[key]
    monkeypatch.setattr(tracing, "SPANS", tracing.SpanBuffer(8))
    assert read(name, ctx) is None


def test_a_device_plane_that_runs_early_still_pairs_each_step_with_its_own_program(ctx, data):
    """On the chip the profiler's device plane sat 1-2 ms off its host plane, per
    session: a program then "starts" before its own step began on the host."""
    for line in data["trace"]["planes"][0]["lines"]:
        for ev in line["events"]:
            ev[1] -= 1_500_000
    ctx["step_programs"] = tr.step_programs(data["trace"])
    check = ps.wait_end_check(ctx)
    assert check["steps"] == 4 and check["delta_p50_us"] == pytest.approx(1600.0)
    assert check["program_start_after_dispatch_start_p50_us"] == pytest.approx(500.0 - 1500.0)
