"""BENCHMARK.json against the files it names, and a CPU rehearsal of the
run command at toy size."""

import json
import os
import pathlib
import re
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "benchmark"))
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
CATALOG = pathlib.Path("/opt/skills/guides/model-configs/architectures.jsonl")


def test_keys_names_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51 and isinstance(BENCH["run_seconds"], int)
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer") for x in BENCH[k]]
    assert all(NAME.match(n) for n in names) and len(set(names)) == len(names)
    assert len((ROOT / "BENCHMARK.json").read_bytes()) < 64 * 1024
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and all(0 < m["bound"] <= 0.1 for m in e2e.values())
    assert all(m["source"] in ("host_clock", "device_trace") for m in e2e.values())
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(1, len(BENCH["workloads"]) // 4)
    for w in BENCH["workloads"]:
        assert w["config"] in {c["name"] for c in BENCH["configs"]} and len(w["why"]) <= 200
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)


def test_every_name_has_a_file_of_its_own():
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).is_file() and c["file"].startswith("benchmark/")
    for w in BENCH["workloads"]:
        assert (ROOT / "benchmark" / "traffic" / f"{w['traffic']}.json").is_file()
        assert (ROOT / "benchmark" / "cells" / f"{w['name']}.json").is_file()
    for m in BENCH["per_layer"]:
        assert (ROOT / "benchmark" / "layer_metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
    for m in BENCH["end_to_end"]:
        assert (ROOT / "benchmark" / "end_to_end" / f"{m['name']}.py").is_file()


def test_each_cell_reports_setup_another_metric_and_a_layer_metric():
    import run as bench_run

    for w in BENCH["workloads"]:
        e2e = [m["name"] for m in bench_run.cell_metrics(BENCH, "end_to_end", w)]
        assert "setup_s" in e2e and len(e2e) >= 2
        per_layer = bench_run.cell_metrics(BENCH, "per_layer", w)
        assert per_layer and all(m["moves"] in e2e for m in per_layer)
    reports = {w["name"]: {m["name"] for m in bench_run.cell_metrics(BENCH, "end_to_end", w)} for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:  # a metric is listed only in cells that report what it moves
        assert all(m["moves"] in reports[c] for c in m.get("workloads", []))


CONFIG_FILES = sorted((ROOT / "benchmark" / "configs").glob("*.json"))


@pytest.mark.skipif(not CATALOG.exists(), reason="the catalog of public architectures is not on this machine")
@pytest.mark.parametrize("path", CONFIG_FILES, ids=lambda p: p.stem)
def test_configuration_keeps_the_published_numbers(path):
    doc = json.loads(path.read_text())
    reduced = list(doc["reduced_why"])
    for entry in BENCH["configs"]:
        if entry["file"] == f"benchmark/configs/{path.name}":
            assert entry["reduced"] == reduced and entry["source"] == doc["source"]
    rows = [json.loads(line) for line in CATALOG.read_text().splitlines()]
    row = next(r for r in rows if r["source_url"] == doc["source"])
    for key, value in row["config"].items():
        if key in reduced:
            assert doc[key] != value
        else:
            assert doc[key] == value, key
    forbidden = re.compile(r"(hidden|intermediate|latent|state|proj).*size|_dim$|_rank$|head|expan|experts_per_tok")
    assert not any(forbidden.search(k) for k in reduced)


@pytest.mark.parametrize("cell, trace", [(BENCH["workloads"][0]["name"], 0), (BENCH["workloads"][-1]["name"], 0),
                                         (BENCH["workloads"][0]["name"], 1)])
def test_cpu_rehearsal_of_the_run_command(cell, trace):
    """The whole control flow at toy size: exit 3, the device named as cpu,
    and no metric that only a device can give."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, *BENCH["command"][1:], "--workload", cell, "--seed", str(2**31 + 17),
         "--seconds", "3", "--trace", str(trace)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 3, proc.stderr[-2000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(last) >= {"correct", "attempted", "failed", "metrics", "device"} and last["correct"] is True
    assert last["device"]["platform"] == "cpu" and "busy_s" not in last["device"] and "breakdown" not in last
    assert last["failed"] == 0 and last["attempted"] > 0
    device_only = {m["name"] for k in ("end_to_end", "per_layer") for m in BENCH[k]
                   if m["source"] in ("device_trace", "program_span")}
    assert last["metrics"] and not device_only & set(last["metrics"])
    phases = [json.loads(l)["phase"] for l in proc.stdout.splitlines() if l.startswith('{"phase"')]
    assert phases[:5] == ["runtime_start", "weights", "server_and_kv_pool", "warm_up", "outputs_check"]
