"""Traffic generation: the seed orders the work, it does not change it."""

import collections
import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import traffic  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
MIXES = sorted(p.stem for p in (ROOT / "benchmark" / "traffic").glob("*.json"))
CELLS = [w["name"] for w in BENCH["workloads"]]


def _cell_mix(cell):
    w = next(w for w in BENCH["workloads"] if w["name"] == cell)
    return traffic.load_mix(ROOT / "benchmark" / "traffic" / f"{w['traffic']}.json",
                            ROOT / "benchmark" / "cells" / f"{cell}.json")


def _mix(name):
    """The mix as the first cell on it runs it."""
    return _cell_mix(next(w["name"] for w in BENCH["workloads"] if w["traffic"] == name))


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_digest(name):
    a = traffic.generate(_mix(name), seed=3_000_000_019, seconds=51, vocab=50304)
    b = traffic.generate(_mix(name), seed=3_000_000_019, seconds=51, vocab=50304)
    assert traffic.digest(a) == traffic.digest(b)


@pytest.mark.parametrize("name", MIXES)
def test_two_seeds_same_schedule_other_ids(name):
    a = traffic.generate(_mix(name), seed=1, seconds=51, vocab=50304)
    b = traffic.generate(_mix(name), seed=2**31 + 5, seconds=51, vocab=50304)
    shape = lambda p: [(r["due"], len(r["prompt"]), r["max_tokens"], r["counted"]) for r in p["requests"]]  # noqa: E731
    assert shape(a) == shape(b)  # the same lengths at the same times: the schedule is the work
    assert all(x["prompt"] != y["prompt"] for x, y in zip(a["requests"], b["requests"]))
    assert traffic.digest(a) != traffic.digest(b)


@pytest.mark.parametrize("name", MIXES)
def test_another_schedule_seed_is_the_same_work_in_another_order(name):
    mix = _mix(name)
    a = traffic.generate(mix, seed=1, seconds=51, vocab=50304)
    b = traffic.generate({**mix, "schedule_seed": mix.get("schedule_seed", 0) + 1}, seed=1, seconds=51, vocab=50304)
    assert len(a["requests"]) == len(b["requests"])
    assert traffic.lengths(a, counted_only=False) == traffic.lengths(b, counted_only=False)
    if a["loop"] == "open":
        assert [r["due"] for r in a["requests"]] != [r["due"] for r in b["requests"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_file_lays_its_rate_and_warm_list_over_the_mix(cell, tmp_path):
    own = json.loads((ROOT / "benchmark" / "cells" / f"{cell}.json").read_text())
    mix = _cell_mix(cell)
    assert mix["warm"] == own["warm"] and "why" in own and mix["why"] != own["why"]
    if mix["loop"] == "open":
        assert mix["rate_rps"] == own["rate_rps"] and 0 < own["rate_rps"] < own["knee"]["rate_rps"]
    bad = tmp_path / "cell.json"
    bad.write_text(json.dumps({**own, "lengths_per_100": []}))  # the work is the mix's, never a cell's
    with pytest.raises(ValueError):
        traffic.load_mix(ROOT / "benchmark" / "traffic" / f"{mix_name(cell)}.json", bad)


def mix_name(cell):
    return next(w["traffic"] for w in BENCH["workloads"] if w["name"] == cell)


@pytest.mark.parametrize("cell", CELLS)
def test_a_full_window_stays_inside_the_warmed_programs(cell):
    mix = _cell_mix(cell)
    plan = traffic.generate(mix, seed=2**31 + 99, seconds=float(BENCH["run_seconds"]), vocab=50304)
    assert max(len(r["prompt"]) + r["max_tokens"] for r in plan["requests"]) <= mix["warm"]["max_context_tokens"]
    if mix["loop"] == "closed":
        assert mix["clients"] <= mix["warm"]["max_rows"]


def test_chat_table_is_the_issues_and_every_prefix_is_balanced():
    rows = _mix("chat-steady")["lengths_per_100"]
    prompts, outs = sorted(r[0] for r in rows), sorted(r[1] for r in rows)
    assert (prompts[49] + prompts[50]) / 2 == 384 and 1280 <= prompts[94] <= 1536
    assert prompts[0] >= 64 and prompts[-1] <= 2048 and all(p % 64 == 0 for p in prompts)
    assert 120 <= (outs[49] + outs[50]) / 2 <= 136 and outs[0] >= 16 and outs[-1] <= 384
    mean_p, mean_o = sum(prompts) / 100, sum(outs) / 100
    for n in (24, 28, 32, 48):  # what a window takes: the first n rows
        assert abs(sum(r[0] for r in rows[:n]) / n - mean_p) < 0.12 * mean_p
        assert abs(sum(r[1] for r in rows[:n]) / n - mean_o) < 0.12 * mean_o


def test_open_loop_count_and_window():
    mix = {**_mix("chat-steady"), "rate_rps": 1.5, "lead_in_s": 8}
    plan = traffic.generate(mix, seed=7, seconds=51, vocab=1000)
    counted = [r for r in plan["requests"] if r["counted"]]
    lead = [r for r in plan["requests"] if not r["counted"]]
    assert len(counted) == round(1.5 * 51) and len(lead) == 12
    assert all(0 <= r["due"] < 51 for r in counted) and all(-8 <= r["due"] < 0 for r in lead)
    assert [r["due"] for r in plan["requests"]] == sorted(r["due"] for r in plan["requests"])
    # The first N rows of the table, whatever the seed: a balanced prefix of the 100.
    rows = collections.Counter(map(tuple, mix["lengths_per_100"][: len(counted)]))
    assert collections.Counter((len(r["prompt"]), r["max_tokens"]) for r in counted) == rows
    assert all(len(r["prompt"]) % 64 == 0 for r in counted)


def test_closed_loop_offers_the_same_turns_whatever_the_seed():
    mix = _mix("decode-saturated")
    plans = [traffic.generate(mix, seed=s, seconds=51, vocab=1000) for s in (3, 4)]
    turns = [[[(len(p["requests"][i]["prompt"]), p["requests"][i]["max_tokens"]) for i in c] for c in p["clients"]]
             for p in plans]
    assert len(turns[0]) == mix["clients"] and all(len(c) == mix["requests_per_client"] for c in turns[0])
    assert turns[0] == turns[1]  # same lengths, same client, same turn: only the ids differ
    assert plans[0]["requests"][0]["prompt"] != plans[1]["requests"][0]["prompt"]
    firsts = sorted(c[0][1] for c in turns[0])
    assert firsts[0] < 60 and firsts[-1] > 400  # the first wave is cut by a ladder, spread wide


def test_prefixes_bursts_and_sessions_are_in_the_generator():
    base = {**_mix("chat-steady"), "rate_rps": 2.0}
    shared = traffic.generate({**base, "prefix_levels": [{"tokens": 32, "groups": 1}, {"tokens": 16, "groups": 2}]},
                              seed=5, seconds=10, vocab=1000)
    heads = {tuple(r["prompt"][:32]) for r in shared["requests"]}
    assert len(heads) == 1 and len({tuple(r["prompt"][32:48]) for r in shared["requests"]}) == 2
    bursty = traffic.generate({**base, "bursts": {"size": [8, 16], "within_s": 0.2, "every_s": [5, 10]}},
                              seed=5, seconds=30, vocab=1000)
    assert sum(r["counted"] for r in bursty["requests"]) == 60
    sess = traffic.generate({**base, "sessions": {"count": 3, "turns": [2, 3], "think_s": [1, 2]}},
                            seed=5, seconds=10, vocab=1000)
    follow = [r for r in sess["requests"] if r["after"] is not None]
    assert follow and all(r["prompt"][: len(sess["requests"][r["after"]]["prompt"])]
                          == sess["requests"][r["after"]]["prompt"] and r["think_s"] >= 1 for r in follow)


def test_schema_refuses_what_the_harness_cannot_run(tmp_path):
    doc = json.loads((ROOT / "benchmark" / "traffic" / "chat-steady.json").read_text())
    for bad in ({"router": {"mode": "kv", "replicas": 4}}, {"prebuilt": {"sessions": 32}}):
        p = tmp_path / "mix.json"
        p.write_text(json.dumps({**doc, **bad}))
        with pytest.raises(NotImplementedError):
            traffic.load_mix(p)
    p.write_text(json.dumps({**doc, "typo_key": 1}))
    with pytest.raises(ValueError):
        traffic.load_mix(p)
