"""``engine.overlapped_steps_pct``: the reader of the STEP record's
``overlap_mode`` (CPU, no chip), on the records of
``benchmark/data/small_phases.json`` and on those of a served engine."""

import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import plugins  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = "engine.overlapped_steps_pct"


@pytest.fixture
def steps():
    return json.loads((ROOT / "benchmark" / "data" / "small_phases.json").read_text())["steps"]


def read(steps):
    return plugins.load("layer_metrics", NAME).read({"window": {"steps": steps}, "trace": None, "notes": {}})


def test_the_entry_is_a_counter_of_the_engine_layer_in_every_cell():
    entry = next(m for m in BENCH["per_layer"] if m["name"] == NAME)
    assert entry == {"name": NAME, "unit": "%", "better": "higher", "source": "program_counter",
                     "layer": "engine", "moves": "itl_p50_ms"}  # no list: every cell reports itl_p50_ms
    assert "workloads" not in next(m for m in BENCH["end_to_end"] if m["name"] == "itl_p50_ms")
    assert "engine" in {m["layer"] for m in BENCH["per_layer"] if m["name"] != NAME}  # a layer the benchmark names


@pytest.mark.parametrize("modes, want", [
    (["overlapped"] * 6, 100.0),
    (["barrier", "overlapped", "overlapped", "overlapped", "overlapped", "barrier"], 100.0 * 4 / 6),
    ([""] * 6, 0.0),  # a program that steps synchronously: the parent of the PR that made the pipelined loop the default
    ([None] * 6, 0.0),  # ... or one from before the field
], ids=["all-overlapped", "a-fill-and-a-drain", "synchronous", "no-field"])
def test_reads_the_share_of_the_steps_that_dispatched_under_the_step_before(steps, modes, want):
    for step, mode in zip(steps, modes, strict=True):
        step["attn_phase"] = "decode"
        if mode is not None:
            step["overlap_mode"] = mode
    assert read(steps) == pytest.approx(want)


def test_a_step_that_dispatched_nothing_is_not_counted(steps):
    for step in steps:
        step.update(attn_phase="decode", overlap_mode="overlapped")
    steps[-1].update(attn_phase="", overlap_mode="barrier", barrier_reason="drain")  # it only read the step in flight
    assert read(steps) == 100.0
    assert read([steps[-1]]) is None and read([]) is None


def test_on_the_records_of_a_served_engine_both_loops():
    from dynamo_tpu.engine.core import EngineConfig, EngineCore
    from dynamo_tpu.engine.runner import ModelRunner
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import PRESETS

    from tests.test_engine_core import greedy_request, run_to_completion

    cfg = PRESETS["test-tiny"]
    shares = {}
    for overlap in (True, False):
        runner = ModelRunner(cfg, llama.init_params(cfg, 0), num_pages=64, page_size=4, max_batch_size=8,
                             prefill_bucket=16, attn_impl="reference")
        core = EngineCore(runner, EngineConfig(num_pages=64, page_size=4, max_batch_size=8, max_seq_len=128,
                                               overlap=overlap))
        core.add_request(greedy_request([1, 2, 3, 4, 5], max_tokens=20))
        run_to_completion(core)
        shares[overlap] = read(core.flight.snapshot(kind="step"))
    # one fill to start the pipeline, every dispatch after it under the step before
    assert shares[False] == 0.0 and shares[True] == pytest.approx(100.0 * 19 / 20)
