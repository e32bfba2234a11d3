"""``runner.first_call_stored_pct`` (ISSUE 52): of set-up's first calls, the share
loaded from the runner's executable store, on planted ``runner_first_call`` spans
before the six steps of ``benchmark/data/small_phases.json`` (``ts`` 1000.01 to
1000.0605), in the manner of ``test_benchmark_setup_spans.py``."""

import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent.parent
sys.path.insert(0, str(ROOT))

from benchmark import plugins  # noqa: E402
from benchmark import run as bench_run  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = "runner.first_call_stored_pct"


def first_call(ts, rows, t, *, store=None, read=0.0, in_step=False):
    """A store hit traced and lowered nothing, and its load is its backend part."""
    hit = store == "hit"
    trace, lower, backend, rest = (0.0, 0.0, read, 40.0) if hit else (500.0, 400.0, 300.0, 60.0 + read)
    span = {"name": "runner_first_call", "request_id": "runner_first_call", "trace_id": "b" * 32, "start_ts": ts,
            "duration_ms": trace + lower + backend + rest, "program": "step", "bucket": [rows, t, 2, 1, 0, "pallas"],
            "trace_ms": trace, "lower_ms": lower, "backend_ms": backend, "rest_ms": rest, "cache": "hit",
            "cache_hits": 1, "cache_misses": 0, "modules": 1, "in_step": in_step}
    if store is not None:
        span.update(store=store, store_read_ms=read)
    return span


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


def test_the_entry_is_a_counter_of_the_runner_that_moves_setup_s_in_every_cell():
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == NAME]
    assert entry == {"name": NAME, "unit": "%", "better": "higher", "source": "program_counter", "layer": "runner",
                     "moves": "setup_s"}
    assert (ROOT / "benchmark" / "layer_metrics" / f"{NAME}.py").is_file()
    for cell in BENCH["workloads"]:  # every cell reports setup_s
        assert NAME in {m["name"] for m in bench_run.cell_metrics(BENCH, "per_layer", cell)}


CASES = {
    "a warm run": ([("hit", 250.0)] * 4, 100.0),
    "a cold run": ([("miss", 0.05)] * 4, 0.0),
    "a process without a store": ([("off", 0.0)] * 3, 0.0),
    "a build that met three of its four programs": ([("hit", 250.0), ("hit", 300.0), ("miss", 0.05), ("hit", 200.0)], 75.0),
    "a program from before the store": ([(None, 0.0)] * 4, None),
    "no first call at all": ([], None),
}


@pytest.mark.parametrize("case", CASES)
def test_the_share_of_first_calls_loaded_from_the_store(case, ctx, ring):
    calls, want = CASES[case]
    for i, (store, ms) in enumerate(calls):
        ring.record(first_call(900.0 + i, 1 << i, 1, store=store, read=ms))
    assert read(NAME, ctx) == want


def test_a_store_hit_keeps_the_accepted_readers_whole(ctx, ring):
    """A warm run under the store: no Python seconds, the loads as the backend's,
    and the persistent-cache share still 100."""
    for i in range(4):
        ring.record(first_call(900.0 + i, 1 << i, 1, store="hit", read=250.0))
    assert read("runner.first_call_python_s", ctx) == 0.0 and read("runner.first_call_backend_s", ctx) == pytest.approx(1.0)
    assert read("runner.cache_hit_pct", ctx) == 100.0 and read("runner.programs_first_called", ctx) == 4.0
    assert read("runner.first_calls_s", ctx) == pytest.approx(1.16)


def test_a_first_call_inside_the_window_is_left_out_and_a_wrapped_ring_gives_nothing(ctx, monkeypatch, ring):
    ring.record(first_call(900.0, 1, 1, store="hit", read=250.0))
    ring.record(first_call(1000.02, 2, 1, store="miss", read=0.05, in_step=True))  # after the window's first STEP record
    assert read(NAME, ctx) == 100.0
    from dynamo_tpu import tracing

    small = tracing.SpanBuffer(2)
    monkeypatch.setattr(tracing, "SPANS", small)
    for i in range(3):
        small.record(first_call(900.0 + i, 1 << i, 1, store="hit", read=250.0))
    assert small.dropped == 1 and read(NAME, ctx) is None
