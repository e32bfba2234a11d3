"""The seven readers of set-up's spans (``benchmark/setup_spans.py``), on planted
``runner_first_call`` and ``worker_bring_up`` spans before the six steps of
``benchmark/data/small_phases.json`` (``ts`` 1000.01 to 1000.0605)."""

import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent.parent
sys.path.insert(0, str(ROOT))

from benchmark import plugins  # noqa: E402
from benchmark import run as bench_run  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAMES = ("runner.first_calls_s", "runner.first_call_python_s", "runner.first_call_backend_s",
         "runner.first_call_rest_s", "runner.programs_first_called", "runner.cache_hit_pct", "engine.bring_up_s")


def first_call(ts, rows, t, pages, *, trace, lower, backend, rest, cache="hit", saved=9000.0, in_step=False):
    wall = trace + lower + backend + rest
    hit = cache == "hit"
    return {"name": "runner_first_call", "request_id": "runner_first_call", "trace_id": "b" * 32, "start_ts": ts,
            "duration_ms": wall, "wall_ms": wall, "program": "step", "bucket": [rows, t, pages, 1, 0, "pallas"],
            "reason": "new_shape", "trace_ms": trace, "lower_ms": lower, "backend_ms": backend, "rest_ms": rest,
            "cache": cache, "cache_hits": int(hit), "cache_misses": int(cache == "miss"),
            "cache_read_ms": 250.0 * hit, "cache_saved_ms": saved * hit, "modules": int(cache != "none"),
            "t0_ns": 0, "in_step": in_step}


def bring_up(ts, ms):
    return {"name": "worker_bring_up", "request_id": "worker_bring_up", "trace_id": "b" * 32, "start_ts": ts,
            "duration_ms": ms, "worker": "2a", "model": "toy"}


WARM = [first_call(900.0, 1, 1, 1, trace=400.0, lower=300.0, backend=500.0, rest=200.0),
        first_call(901.4, 8, 1, 2, trace=600.0, lower=300.0, backend=700.0, rest=400.0),
        first_call(903.4, 1, 64, 1, trace=1200.0, lower=500.0, backend=600.0, rest=700.0),
        first_call(906.4, 8, 64, 2, trace=1400.0, lower=700.0, backend=4000.0, rest=900.0, cache="miss"),
        # the outputs check met a shape the warm list lacks: an engine step made the call
        first_call(950.0, 2, 64, 1, trace=1000.0, lower=400.0, backend=500.0, rest=100.0, in_step=True)]


@pytest.fixture
def ring(monkeypatch):
    from dynamo_tpu import tracing

    ring = tracing.SpanBuffer(64)
    monkeypatch.setattr(tracing, "SPANS", ring)
    return ring


@pytest.fixture
def ctx(ring):
    data = json.loads((ROOT / "benchmark" / "data" / "small_phases.json").read_text())
    return {"window": {"steps": data["steps"]}, "notes": {}}


def read(name, ctx):
    return plugins.load("layer_metrics", name).read(ctx)


def test_the_seven_entries_are_counters_that_move_setup_s_and_list_no_cell():
    entries = {m["name"]: m for m in BENCH["per_layer"] if m["name"] in NAMES}  # by name: no position is asserted
    assert set(entries) == set(NAMES)
    for m in entries.values():
        assert (m["source"], m["moves"]) == ("program_counter", "setup_s") and "workloads" not in m
        assert (ROOT / "benchmark" / "layer_metrics" / f"{m['name']}.py").is_file()
    assert {n: (entries[n]["unit"], entries[n]["better"], entries[n]["layer"]) for n in NAMES} == {
        "runner.first_calls_s": ("s", "lower", "runner"), "runner.first_call_python_s": ("s", "lower", "runner"),
        "runner.first_call_backend_s": ("s", "lower", "runner"), "runner.first_call_rest_s": ("s", "lower", "runner"),
        "runner.programs_first_called": ("count", "lower", "runner"), "runner.cache_hit_pct": ("%", "higher", "runner"),
        "engine.bring_up_s": ("s", "lower", "engine")}
    for cell in BENCH["workloads"]:  # every cell reports setup_s, so every cell reads all seven
        assert set(NAMES) <= {m["name"] for m in bench_run.cell_metrics(BENCH, "per_layer", cell)}
    moved_before = [m["name"] for m in BENCH["per_layer"] if m["moves"] == "setup_s" and m["name"] not in NAMES]
    assert moved_before == []  # no per-layer metric moved setup_s before these


def test_the_seven_values_and_the_note(ctx, ring):
    for span in [bring_up(890.0, 6500.0), *WARM]:
        ring.record(span)
    got = {n: read(n, ctx) for n in NAMES}
    assert got == {
        "runner.first_calls_s": pytest.approx(15.4), "runner.first_call_python_s": pytest.approx(6.8),
        "runner.first_call_backend_s": pytest.approx(6.3), "runner.first_call_rest_s": pytest.approx(2.3),
        "runner.programs_first_called": 5.0, "runner.cache_hit_pct": pytest.approx(80.0), "engine.bring_up_s": pytest.approx(6.5)}
    assert got["runner.first_call_python_s"] + got["runner.first_call_backend_s"] + got["runner.first_call_rest_s"] \
        == pytest.approx(got["runner.first_calls_s"], abs=1e-3)
    note = ctx["notes"]["set_up"]
    assert note["by_kind"]["t1"] == {"programs": 2, "mean_s": 1.7, "mean_trace_s": 0.5, "mean_lower_s": 0.3,
                                     "mean_backend_s": 0.6, "mean_rest_s": 0.3, "mean_cache_read_s": 0.25}
    assert note["by_kind"]["chunk"]["programs"] == 3 and note["by_kind"]["chunk"]["mean_s"] == pytest.approx(4.0)
    assert [(c["bucket"][:3], c["s"], c["cache"]) for c in note["longest"]] == [
        ([8, 64, 2], 7.0, "miss"), ([1, 64, 1], 3.0, "hit"), ([8, 1, 2], 2.0, "hit"), ([2, 64, 1], 2.0, "hit"),
        ([1, 1, 1], 1.4, "hit")]
    assert note["longest"][0] == {"program": "step", "bucket": [8, 64, 2, 1, 0, "pallas"], "s": 7.0, "trace_s": 1.4,
                                  "lower_s": 0.7, "backend_s": 4.0, "rest_s": 0.9, "cache": "miss"}
    assert note["cache_saved_s"] == 36.0 and note["cache"] == {"hit": 4, "miss": 1, "off": 0, "none": 0}
    assert note["inside_steps"] == {"programs": 1, "s": 2.0, "buckets": [[2, 64, 1, 1, 0, "pallas"]]}
    assert note["modules"] == 5 and note["ring_dropped"] == 0


def test_a_first_call_after_the_windows_first_step_record_is_left_out(ctx, ring):
    inside = first_call(1000.02, 16, 1, 4, trace=100.0, lower=100.0, backend=5000.0, rest=100.0, cache="miss", in_step=True)
    at_the_edge = first_call(1000.01, 16, 1, 8, trace=100.0, lower=100.0, backend=100.0, rest=100.0)
    for span in [*WARM[:2], inside, at_the_edge, bring_up(1000.03, 100.0)]:
        ring.record(span)
    assert read("runner.first_calls_s", ctx) == pytest.approx(3.4) and read("runner.programs_first_called", ctx) == 2.0
    assert read("runner.cache_hit_pct", ctx) == 100.0
    assert read("engine.bring_up_s", ctx) is None  # a worker that came up inside the window is no set-up
    ctx["window"]["steps"] = []  # a window without a step: everything came before it
    ctx.pop("_set_up")
    assert read("runner.programs_first_called", ctx) == 4.0


def test_a_program_that_writes_no_such_span_gives_nothing_and_raises_nothing(ctx, ring):
    ring.record({"name": "host_pause", "request_id": "host_pause", "start_ts": 900.0, "duration_ms": 2.0})
    assert [read(n, ctx) for n in NAMES] == [None] * 7
    assert ctx["notes"] == {"set_up": {"ring_dropped": 0}}


def test_a_first_call_that_asked_no_cache_leaves_the_hit_share_out(ctx, ring):
    ring.record(first_call(900.0, 1, 1, 1, trace=400.0, lower=300.0, backend=500.0, rest=200.0, cache="off"))
    ring.record(first_call(902.0, 2, 1, 1, trace=0.0, lower=0.0, backend=0.0, rest=90.0, cache="none"))
    assert read("runner.cache_hit_pct", ctx) is None and read("runner.programs_first_called", ctx) == 2.0
    assert read("runner.first_call_rest_s", ctx) == pytest.approx(0.29)


def test_a_wrapped_ring_gives_nothing(ctx, monkeypatch):
    from dynamo_tpu import tracing

    small = tracing.SpanBuffer(4)
    monkeypatch.setattr(tracing, "SPANS", small)
    for span in [bring_up(890.0, 6500.0), *WARM]:  # six spans into a ring of four: set-up's oldest are gone
        small.record(span)
    assert small.dropped == 2 and [read(n, ctx) for n in NAMES] == [None] * 7
    assert ctx["notes"]["set_up"] == {"ring_dropped": 2}


def test_a_span_ring_without_the_counter_reads_as_not_wrapped(ctx, ring, monkeypatch):
    from benchmark import setup_spans

    class Bare:  # an older program's ring
        def query(self, **_kw):
            return list(WARM)

    from dynamo_tpu import tracing

    monkeypatch.setattr(tracing, "SPANS", Bare())
    assert setup_spans.ring_dropped() == 0 and read("runner.programs_first_called", ctx) == 5.0
