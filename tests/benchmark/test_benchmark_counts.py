"""Kernel byte and operation counts against values computed by hand."""

import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def _counts():
    from benchmark import plugins

    return plugins.load("kernel_counts", "moe_decode_step")


def _hf(name):
    from benchmark import serving

    return serving.load_config(ROOT / "benchmark" / "configs" / f"{name}.json")["hf"]


def test_olmoe_decode_step_bytes_by_hand():
    c = _counts()
    got = c.decode_step(_hf("olmoe-1b-7b-int8"), rows=3, contexts_total=3 * 500)
    touched = 64 * (1 - (1 - 8 / 64) ** 3)  # 21.125 experts a layer at 3 rows
    layer = 4 * 2048 * 2048 + 2048 * 64 * 2 + touched * 3 * 2048 * 1024
    want = 16 * layer + 2048 * 50304 + 3 * 2048 * 2 + 16 * 1500 * (2 * 16 * 128 * 2)
    assert got["experts_touched"] == pytest.approx(touched)
    assert got["bytes"] == pytest.approx(want)
    per_token = 16 * (4 * 2048 * 2048 + 2048 * 64 + 8 * 3 * 2048 * 1024) + 2048 * 50304
    assert got["flops"] == pytest.approx(2 * 3 * per_token + 16 * 1500 * 4 * 16 * 128)
    # 48 rows touch nearly every expert; a measured count overrides the formula
    assert c.decode_step(_hf("olmoe-1b-7b-int8"), rows=48, contexts_total=0)["experts_touched"] > 63.8
    assert c.decode_step(_hf("olmoe-1b-7b-int8"), rows=3, contexts_total=0, experts_touched=20)["experts_touched"] == 20


def test_least_time_names_its_bound():
    c = _counts()
    peaks = json.loads((ROOT / "benchmark" / "peaks.json").read_text())["TPU v5 lite"]
    t, bound = c.least_seconds({"bytes": 8.19e9, "flops": 1e9}, peaks)
    assert bound == "memory" and t == pytest.approx(0.01)
    assert c.least_seconds({"bytes": 1.0, "flops": 197e12}, peaks) == (pytest.approx(1.0), "compute")
