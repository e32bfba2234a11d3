"""``kernels.moe_widened_steps``: the reader of the STEP record's ``moe_path``
(CPU, no chip), on the records of ``benchmark/data/small_phases.json``."""

import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import plugins  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = "kernels.moe_widened_steps"


@pytest.fixture
def steps():
    return json.loads((ROOT / "benchmark" / "data" / "small_phases.json").read_text())["steps"]


def read(steps):
    return plugins.load("layer_metrics", NAME).read({"window": {"steps": steps}, "trace": None, "notes": {}})


def test_the_entry_is_a_counter_of_the_kernels_layer_in_every_cell():
    entry = next(m for m in BENCH["per_layer"] if m["name"] == NAME)
    assert entry == {"name": NAME, "unit": "count", "better": "lower", "source": "program_counter",
                     "layer": "kernels", "moves": "itl_p50_ms"}
    assert BENCH["per_layer"][-1]["name"] == NAME  # appended, nothing before it moved


@pytest.mark.parametrize("paths, want", [
    (["fused"] * 6, 0.0),
    (["widened"] * 6, 6.0),
    (["fused", "widened", "", "widened", "fused", ""], 2.0),  # "" is a step that ran no routed experts
    ([""] * 6, 0.0),
])
def test_counts_the_windows_widened_steps(steps, paths, want):
    assert len(steps) == len(paths)
    for step, path in zip(steps, paths):
        step["moe_path"] = path
    assert read(steps) == want


def test_a_program_without_the_label_gives_nothing(steps):
    """The parent of the PR that added ``moe_path``: no STEP record has the key."""
    assert not any("moe_path" in s for s in steps)
    assert read(steps) is None
    assert read([]) is None
