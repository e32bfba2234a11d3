"""``engine.mixed_pad_pct``: the reader of the STEP record's ``step_tokens``
(CPU, no chip), on the records of ``benchmark/data/small_phases.json``."""

import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import plugins  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = "engine.mixed_pad_pct"


@pytest.fixture
def steps():
    return json.loads((ROOT / "benchmark" / "data" / "small_phases.json").read_text())["steps"]


def read(steps):
    return plugins.load("layer_metrics", NAME).read({"window": {"steps": steps}, "trace": None, "notes": {}})


def test_the_entry_is_a_counter_of_the_engine_layer_in_the_cells_that_report_out_tok_s():
    entry = next(m for m in BENCH["per_layer"] if m["name"] == NAME)
    out_tok_s = next(m for m in BENCH["end_to_end"] if m["name"] == "out_tok_s")
    assert entry == {"name": NAME, "unit": "%", "better": "lower", "source": "program_counter",
                     "layer": "engine", "moves": "out_tok_s", "workloads": out_tok_s["workloads"]}
    assert "engine" in {m["layer"] for m in BENCH["per_layer"] if m["name"] != NAME}  # a layer the benchmark names


def mixed(step, decode_rows, chunk_tokens, step_tokens=None, layout=None):
    step.update(step_kind="mixed", decode_rows=decode_rows, chunk_tokens=chunk_tokens)
    if step_tokens is not None:
        step.update(step_tokens=step_tokens, layout=layout)


@pytest.mark.parametrize("rows, want", [
    # the saturated cell's largest mixed step: 48 decode rows + a 64-token chunk in 64 x 64 positions, or in 64 + 64
    ([(48, 64, 64 * 64, "rows_x_t")], 100.0 * (1 - 112 / 4096)),
    ([(48, 64, 64 + 64, "split")], 100.0 * (1 - 112 / 128)),
    # longctx-decode: 5 decode rows + the chunk in 8 x 64, or in 8 + 64
    ([(5, 64, 8 * 64, "rows_x_t")], 100.0 * (1 - 69 / 512)),
    ([(5, 64, 8 + 64, "split")], 100.0 * (1 - 69 / 72)),
    # summed over the steps, not a mean of shares: (47 + 64 + 3 + 64) of (128 + 68)
    ([(47, 64, 128, "split"), (3, 64, 68, "split")], 100.0 * (1 - 178 / 196)),
    ([(64, 64, 128, "split")], 0.0),
])
def test_reads_the_padding_share_of_the_windows_mixed_steps(steps, rows, want):
    for step, row in zip(steps, rows):
        mixed(step, *row)
    for step in steps[len(rows):]:  # decode steps carry the field too and are not counted
        step.update(step_kind="decode", step_tokens=64, layout="rows_x_t")
    assert read(steps) == pytest.approx(want)


def test_a_program_without_the_field_gives_nothing(steps):
    """The parent of the PR that added ``step_tokens``: no STEP record has the key."""
    mixed(steps[0], 48, 64)
    assert not any("step_tokens" in s for s in steps)
    assert read(steps) is None
    assert read([]) is None


def test_a_window_without_a_mixed_step_gives_nothing(steps):
    for step in steps:
        step.update(step_kind="decode", step_tokens=4, layout="rows_x_t")
    assert read(steps) is None
