"""The reduction from traces and client times to numbers (CPU, no chip)."""

import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import stats, trace_reduce as tr  # noqa: E402


@pytest.fixture(scope="module")
def trace():
    return json.loads((ROOT / "benchmark" / "data" / "small_trace.json").read_text())


def test_busy_is_a_union_not_a_sum(trace):
    # ops: while 100-400 with two fusions nested in it, 500-700 overlapping 650-800, 900-1000
    assert tr.busy_seconds(trace) == pytest.approx(700e-9)
    summed = sum(e[2] for e in tr.line_events(tr.device_planes(trace)[0], tr.OPS_LINE))
    assert summed == 910  # what summing durations would have read


def test_nested_event_is_not_counted_twice(trace):
    per_op = tr.exclusive_by_name(tr.line_events(tr.device_planes(trace)[0], tr.OPS_LINE))
    assert per_op["while.1"] == pytest.approx(140e-9)  # 300 less its body's 100 + 60
    assert per_op["bitcast_multiply_fusion.8"] == pytest.approx(100e-9)
    assert sum(per_op.values()) == pytest.approx(700e-9)  # every busy instant given to one op
    top = tr.top_device_ops(trace, 2)
    assert [n for n, _ in top] == ["fusion.a", "fusion.b"] and top[0][1] == pytest.approx(250e-9)


def test_idle_gaps_go_to_the_host_span_that_covers_them(trace):
    gaps = dict(tr.idle_gaps(trace))
    assert gaps == {"engine.decode": pytest.approx(50e-9), "engine.mixed": pytest.approx(70e-9),
                    tr.BETWEEN: pytest.approx(80e-9)}
    lo, hi = tr.window_of(trace)
    assert (hi - lo) == 915 and sum(gaps.values()) == pytest.approx(200e-9)


def test_step_programs_and_their_gaps(trace):
    steps = tr.step_programs(trace)
    assert [s["span"] for s in steps] == ["engine.decode", "engine.mixed", None]  # jit_convert is no step
    assert tr.step_gaps_ms(steps) == pytest.approx([100e-6, 100e-6])


@pytest.mark.parametrize("q, want", [(0, 1.0), (50, 2.5), (90, 3.7), (100, 4.0)])
def test_percentile_interpolates(q, want):
    assert stats.percentile([4.0, 1.0, 3.0, 2.0], q) == pytest.approx(want)


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_times_run_from_the_due_time_and_failures_lie_beyond():
    results = [
        {"due": 1.0, "sent": 1.002, "tokens": [1.5, 1.54, 1.60], "ok": True, "want": 3},
        {"due": 2.0, "sent": 2.4, "tokens": [2.9, 2.95], "ok": True, "want": 2},  # sent late: TTFT still from due
        {"due": 3.0, "sent": 3.0, "tokens": [3.2], "ok": True, "want": 4},  # fewer tokens than asked: failed
        {"due": 4.0, "sent": 4.0, "tokens": [], "ok": False, "want": 4},
        {"due": 5.0, "sent": 5.0, "tokens": [], "ok": False, "want": 4, "cancelled": True},  # cut before a token
    ]
    lat = stats.request_latencies(results)
    assert lat["failed"] == 2
    assert lat["ttft_ms"] == pytest.approx([500.0, 900.0, stats.FAILED_MS, stats.FAILED_MS])
    assert lat["gaps_ms"] == pytest.approx([40.0, 60.0, 50.0])
    assert lat["lateness_ms"] == pytest.approx([2.0, 400.0])
    assert stats.percentile(lat["ttft_ms"], 90) > 1e8  # two failures of four: the tail is a failure
    assert stats.tokens_in_window(results, 2.0) == 3


def test_a_closed_loop_pools_every_gap_that_ended_inside_the_window():
    results = [
        # begun in the lead-in, alive in the window: its gaps inside [0, 2) count, its first token does not
        {"due": -3.0, "sent": -3.0, "tokens": [-2.0, -0.01, 0.03, 0.08], "ok": True, "want": 4},
        {"due": 0.5, "sent": 0.5, "tokens": [0.9, 1.0, 2.1], "ok": True, "want": 3},  # the last gap ends outside
        {"due": 1.0, "sent": 1.0, "tokens": [1.9, 1.95], "ok": True, "want": 9, "cancelled": True},  # cut at the end
    ]
    lat = stats.request_latencies(results, window_s=2.0)
    assert lat["failed"] == 0
    assert lat["gaps_ms"] == pytest.approx([40.0, 50.0, 100.0, 50.0])
    assert lat["ttft_ms"] == pytest.approx([400.0, 900.0])
    assert len(stats.request_latencies(results)["gaps_ms"]) == 6  # an open loop follows its requests to their end
