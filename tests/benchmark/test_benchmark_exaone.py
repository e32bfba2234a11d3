"""K-EXAONE-236B-A23B's configuration (ISSUE 42): the file against what the
program makes of it, its plain reference against the program's whole forward
at the rehearsal's toy size, its decode-step byte count against hand arithmetic
and against the served tree, the new per-layer reader on made-up records (what
a program without the new keys writes gives it nothing to read), the cell's
entries and its traffic."""

import functools
import json
import pathlib
import statistics
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

NAME = "k-exaone-236b-a23b-ep8-int8"
CELL = f"{NAME}.longctx-reason"
MELLUM2 = "mellum2-12b-a2.5b-int8.longctx-decode"
SLIDING, FULL = "sliding_attention", "full_attention"
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
MB = 1e6


def _conf(rehearsal=False):
    from benchmark import serving

    return serving.load_config(ROOT / "benchmark" / "configs" / f"{NAME}.json", rehearsal=rehearsal)


def _counts():
    from benchmark import plugins

    return plugins.load("kernel_counts", "exaone_moe_decode_step")


def _reader(name):
    from benchmark import plugins

    return plugins.load("layer_metrics", name).read


# -- the file ---------------------------------------------------------------------------


def test_the_configuration_file_is_this_chips_share_and_the_overrides_change_nothing():
    import dataclasses

    from benchmark import serving
    from dynamo_tpu.models.config import ModelConfig

    conf = _conf()
    cfg = ModelConfig.from_hf(dict(conf["hf"]), name=NAME)
    assert serving.model_config(conf) == cfg  # the share stated a second time is the share from_hf read
    assert (cfg.num_layers, cfg.first_k_dense, cfg.num_experts, cfg.routed_experts, cfg.moe_expert_first) == (12, 1, 16, 128, 0)
    assert cfg.layer_types == (SLIDING, SLIDING, SLIDING, FULL) * 3 and cfg.sliding_window == 128
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.intermediate_size) == (6144, 64, 8, 128, 18432)
    assert (cfg.moe_intermediate_size, cfg.shared_expert_size, cfg.num_experts_per_token, cfg.vocab_size) == (2048, 2048, 8, 19200)
    assert cfg.moe_held_share and cfg.qk_norm == "head" and cfg.moe_scoring == "sigmoid" and cfg.moe_routed_scaling == 2.5
    doc = json.loads((ROOT / "benchmark" / "configs" / f"{NAME}.json").read_text())
    assert len(doc["layer_types"]) == len(doc["mlp_layer_types"]) == len(doc["sliding_windows"]) == 48  # whole, as published
    assert (doc["n_routed_experts_published"], doc["vocab_size_published"], doc["expert_share_chips"]) == (128, 153600, 8)
    assert set(doc["reduced_why"]) == {"num_hidden_layers", "num_experts", "vocab_size"}
    assert {"qk_norm", "rope", "residuals", "mtp", "weights", "kv_cache"} <= set(doc["assumed"])
    # uncut, the same keys give the published model
    whole = {**conf["hf"], "num_hidden_layers": 48, "num_experts": 128, "vocab_size": 153600}
    whole.pop("n_routed_experts_published")
    assert ModelConfig.from_hf(whole, name="whole").param_count() == pytest.approx(236.6e9, rel=1e-3)
    assert dataclasses.replace(cfg, moe_experts_total=128, moe_expert_first=0) == cfg


def test_weights_and_cache_bytes_summed_from_the_served_tree():
    """The deployment's arithmetic (ISSUE 42) against the shapes the program
    makes: 9.13 GB of weights, a full pool of 3.22 GB for 262,144 tokens and a
    window pool of 49 pages, 0.23 GB; one page-id space would be 12.9 GB."""
    import jax

    from benchmark import serving, weights
    from dynamo_tpu.models import llama

    conf = _conf()
    mc, eng = serving.model_config(conf), conf["serve"]["engine"]
    nbytes = lambda t: sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(t))  # noqa: E731
    tree = jax.eval_shape(lambda: weights.make_weights(mc, 0, quant="int8"))
    assert nbytes(tree) == pytest.approx(9.13e9, rel=5e-3)
    pages = eng["pool_tokens"] // eng["page_size"] + 1
    window = llama.window_pool_pages(mc, pages, eng["page_size"], eng["max_batch_size"], eng["chunk_prefill_tokens"])
    assert (pages, window) == (2049, 49)
    cache = nbytes(jax.eval_shape(lambda: llama.init_kv_cache(mc, pages, eng["page_size"], window_pages=window)))
    page = 128 * 8 * 128 * 2 * 2  # a page of K and V in one layer
    assert cache == (3 * 2049 + 9 * 49) * page and 3 * 2049 * page == pytest.approx(3.22e9, rel=2e-3)
    assert 9 * 49 * page == pytest.approx(0.231e9, rel=5e-3) and 12 * 2049 * page == pytest.approx(12.9e9, rel=2e-3)
    assert mc.kv_bytes_per_token() == 12288 and mc.kv_bytes_per_token(kind=SLIDING) == 36864
    assert nbytes(tree) + cache < 0.76 * 16.9e9  # about three quarters of the chip
    # the counts file against the tree: every weight outside the routed experts, and the head
    experts = sum(nbytes(tree["layers"][k]) for k in ("w_gate", "w_up", "w_down"))
    need = _counts().decode_step(conf["hf"], rows=8, contexts_total=0, experts_touched=16.0)
    scales_and_norms = nbytes(tree) - nbytes(tree["embed"]) - need["outside_experts_bytes"] - need["head_bytes"] - experts
    assert need["experts_bytes"] == pytest.approx(experts, rel=2e-3)  # (the tree's experts carry their bf16 scales)
    assert 0 <= scales_and_norms < 0.004 * nbytes(tree)  # what the count leaves out: scales, norms, the router's bias


@pytest.mark.parametrize("last", [47, 20, 9])
def test_reference_matches_program_forward_at_the_rehearsal_size(last):
    """One whole-sequence call of the program (48 tokens, the toy model of the
    rehearsal: sliding, sliding, sliding, full with a window of 8, a dense layer
    and three sparse ones, 4 of 16 experts held, rank 1) against the reference,
    at a position six windows in, one mid-way and one just past the first
    window; float32 both sides, 1e-4 of the logit range."""
    import jax
    import jax.numpy as jnp

    from benchmark import serving, weights
    from benchmark.reference import k_exaone_moe
    from dynamo_tpu.models import llama

    conf = _conf(rehearsal=True)
    cfg = serving.model_config(conf)
    assert cfg.dtype == "float32" and cfg.mixed_attention and (cfg.num_layers, cfg.sliding_window) == (4, 8)
    params = weights.make_weights(cfg, 2**31 + 5, quant="")
    toks = np.random.default_rng(3).integers(1, cfg.vocab_size, size=48)
    want = np.asarray(jax.jit(functools.partial(k_exaone_moe.forward, hf=conf["hf"]))(params, tokens=jnp.asarray(toks)))
    k, v = llama.init_kv_cache(cfg, 5, 16)
    pos = np.arange(48)
    slots = (1 + pos // 16) * 16 + pos % 16
    got = llama.forward(params, cfg, jnp.asarray(toks)[None], jnp.asarray(pos)[None], k, v, jnp.asarray([[1, 2, 3]]),
                        jnp.asarray(slots)[None], jnp.asarray([last]), attn_impl="reference")[0]
    assert np.abs(np.asarray(got)[0] - want[last]).max() < 1e-4 * np.abs(want).max()
    wrong = np.asarray(jax.jit(functools.partial(k_exaone_moe.forward, hf={**conf["hf"], "sliding_window": 2**20}))(
        params, tokens=jnp.asarray(toks)))
    assert np.abs(wrong[47] - want[47]).max() > 100 * 1e-4 * np.abs(want).max()  # the window is seen


# -- the counts -------------------------------------------------------------------------


def test_decode_step_bytes_by_hand():
    """ISSUE 42's arithmetic at the published sizes: at 8 rows and a mean live
    context of 24,300 tokens a decode step needs 2.39 GB of full-layer K and V,
    0.04 GB of window K and V, 2.13 GB of weights outside the routed experts,
    2.68 GB of experts (6.45 of 16 touched a layer) and 0.12 GB of head: 7.4 GB,
    9.0 ms at the HBM peak."""
    c, hf = _counts(), _conf()["hf"]
    got = c.decode_step(hf, rows=8, contexts_total=8 * 24300)
    attention = 2 * 6144 * 8192 + 2 * 6144 * 1024
    expert = 3 * 6144 * 2048
    assert attention == pytest.approx(113.25 * MB, rel=1e-3) and expert == pytest.approx(37.75 * MB, rel=1e-3)
    outside = 12 * attention + 11 * (expert + 6144 * 128 * 2) + 3 * 6144 * 18432
    assert got["outside_experts_bytes"] == outside and outside == pytest.approx(2.13e9, rel=2e-3)
    touched = 16 * (1 - (1 - 8 / 128) ** 8)
    assert got["experts_touched"] == pytest.approx(touched) and touched == pytest.approx(6.45, abs=0.01)
    assert got["experts_bytes"] == pytest.approx(11 * touched * expert) and got["experts_bytes"] == pytest.approx(2.68e9, rel=2e-3)
    full, window = 3 * 8 * 24300 * 4096, 9 * 8 * 128 * 4096
    assert got["cache_bytes_full"] == full and full == pytest.approx(2.39e9, rel=2e-3)
    assert got["cache_bytes"] == full + window and window == pytest.approx(0.0377e9, rel=2e-3)
    head = 6144 * 19200
    assert got["head_bytes"] == head and head == pytest.approx(0.118e9, rel=2e-3)
    assert got["bytes"] == pytest.approx(outside + got["experts_bytes"] + head + 8 * 6144 * 2 + full + window)
    assert 7.3e9 < got["bytes"] < 7.45e9 and full / got["bytes"] == pytest.approx(1 / 3, abs=0.02)
    peaks = json.loads((ROOT / "benchmark" / "peaks.json").read_text())["TPU v5 lite"]
    least, bound = c.least_seconds(got, peaks)
    assert bound == "memory" and 8.9e-3 < least < 9.1e-3
    # a count handed in takes the formula's place; bf16 weights double the matmul leaves only
    assert c.decode_step(hf, rows=8, contexts_total=0, experts_touched=16.0)["experts_bytes"] == 11 * 16 * expert
    wide = c.decode_step(hf, rows=1, contexts_total=0, weight_bytes=2.0, experts_touched=0.0)
    assert wide["bytes"] == pytest.approx(2 * outside - 11 * 6144 * 128 * 2 + 2 * head + 6144 * 2)
    one_space = 12 * 8 * 24300 * 4096  # what every layer full would read
    assert 1 - (full + window) / one_space == pytest.approx(0.746, abs=0.002)


def test_attention_and_experts_step_bytes_by_hand():
    c, hf = _counts(), _conf()["hf"]
    got = c.attention_step(hf, kv_tokens_full=194400, kv_tokens_window=1024, rows=8)
    assert got["cache_bytes"] == (3 * 194400 + 9 * 1024) * 4096 and got["cache_bytes_full"] == 3 * 194400 * 4096
    assert got["bytes"] == got["cache_bytes"] + 12 * 8 * 64 * 128 * 2 * 2
    chunk = c.attention_step(hf, kv_tokens_full=20000, kv_tokens_window=639, rows=1, new_tokens=512)
    assert chunk["bytes"] == (3 * 20000 + 9 * 639) * 4096 + 12 * 512 * 64 * 128 * 4
    assert chunk["flops"] == pytest.approx(4 * 64 * 128 * (512 * (3 * 20000 + 9 * 639) - 12 * 512 * 511 / 2))
    assert c.experts_step(hf, experts_touched_total=71) == {"bytes": 71 * 3 * 6144 * 2048, "choices_per_token": 8 * 11}
    assert c.layer_counts(hf) == (3, 9)  # of the 12 layers held, whatever the published lists' length


# -- the readers ------------------------------------------------------------------------


def _step(kind, seq, *, full=1520, window=24, released=0, rows=8, kv=(194400, 1024), moe=None, keys=True):
    rec = {"kind": "step", "seq": seq, "step_kind": kind, "decode_rows": rows if kind == "decode" else 0,
           "chunk_rows": 0, "chunk_tokens": 0, "traced": False, "attn_phase": "decode", "overlap_mode": "overlapped",
           "kv_tokens_full": kv[0], "kv_tokens_window": kv[1]}
    if keys:
        rec.update(full_pages_live=full, window_pages_live=window, window_pages_released=released)
    if moe:
        rec.update(dict(zip(("moe_choices", "moe_choices_zero", "moe_choices_held", "moe_experts_touched"), moe)))
    return rec


def _ctx(steps, conf=None):
    return {"conf": conf or _conf(), "window": {"steps": steps}, "trace": None, "peaks": None, "notes": {},
            "step_programs": [], "mean_context_tokens": 24300.0}


def test_kv_pool_saved_reader_reads_the_two_pools_and_nothing_from_a_program_without_them():
    read = _reader("engine.kv_pool_saved_pct")
    ctx = _ctx([_step("decode", 1, full=1500, window=20, released=1), _step("decode", 2, full=1540, window=28),
                _step("mixed", 3, full=9999, window=1)])
    # 3 full layers and 9 sliding ones: (3 x 3040 + 9 x 48) of 12 x 3040 pages
    assert read(ctx) == pytest.approx(100 * (1 - (3 * 3040 + 9 * 48) / (12 * 3040))) and 73 < read(ctx) < 75
    assert ctx["notes"]["kv_pools"] == {"steps": 2, "full_pages_live": 1520.0, "window_pages_live": 24.0,
                                        "window_pages_released": 1}
    assert read(_ctx([_step("decode", 1, keys=False)])) is None  # the parent's records
    assert read(_ctx([_step("decode", 1, window=0)])) is None  # a model that keeps one pool
    assert read(_ctx([_step("mixed", 1)])) is None
    from benchmark import serving

    mellum2 = serving.load_config(ROOT / "benchmark" / "configs" / "mellum2-12b-a2.5b-int8.json")
    assert 45 < read(_ctx([_step("decode", 1, full=210, window=60)], mellum2)) < 56  # 7 full and 21 sliding layers
    olmoe = serving.load_config(ROOT / "benchmark" / "configs" / "olmoe-1b-7b-int8.json")
    assert read(_ctx([_step("decode", 1)], olmoe)) is None  # no layer_types


def test_accepted_readers_find_this_cells_records():
    skipped, held = _reader("engine.window_kv_skipped_pct"), _reader("engine.moe_held_choice_pct")
    choices = 8 * 8 * 11
    steps = [_step("decode", i, moe=(choices, 0, choices // 8, 71)) for i in (1, 2)]
    ctx = _ctx(steps)
    assert skipped(ctx) == pytest.approx(100 * (1 - (3 * 194400 + 9 * 1024) / (12 * 194400))) and skipped(ctx) > 70
    assert held(ctx) == pytest.approx(12.5)
    assert ctx["notes"]["moe_held"]["experts_touched_per_layer"] == pytest.approx(71 / 12)
    read = _reader("kernels.decode_roofline_pct")
    assert read(_ctx(steps)) is None  # no trace, no number


def test_the_cells_entries():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, "longctx-reason", 1) and len(cell["why"]) <= 200
    # This cell's own entries, by name: what else lists the cell, how many cells there are and what the other
    # entries say is not this file's to hold (a later PR appends to those lists; B1 puts this cell on five more).
    metrics = {m["name"]: m for m in BENCH["per_layer"]}
    new = metrics["engine.kv_pool_saved_pct"]
    assert (new["source"], new["layer"], new["moves"], new["unit"], new["better"]) == (
        "program_counter", "engine", "itl_p50_ms", "%", "higher")
    assert {CELL, MELLUM2} <= set(new["workloads"])
    assert (ROOT / "benchmark" / "layer_metrics" / "engine.kv_pool_saved_pct.py").is_file()
    assert CELL in metrics["engine.moe_held_choice_pct"]["workloads"]
    e2e = {m["name"] for m in BENCH["end_to_end"] if CELL in m.get("workloads", [CELL])}
    assert e2e >= {"itl_p50_ms", "setup_s"}
    entry = next(c for c in BENCH["configs"] if c["name"] == NAME)
    assert entry["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert entry["file"] == f"benchmark/configs/{NAME}.json" and len(entry["why"]) <= 200


def test_the_traffic_is_the_issues_and_the_cell_warms_seventy_two_programs():
    from benchmark import serving, traffic

    mix = traffic.load_mix(ROOT / "benchmark" / "traffic" / "longctx-reason.json",
                           ROOT / "benchmark" / "cells" / f"{CELL}.json")
    rows = mix["lengths_per_100"]
    assert (mix["loop"], mix["clients"], mix["requests_per_client"], mix["lead_in_s"], mix["schedule_seed"]) == ("closed", 8, 4, 30, 42)
    assert mix["first_answer_share"] == [0.05, 1.0] and not mix["prefix_levels"] and len(rows) == 100
    prompts, outs = [p for p, _ in rows], [o for _, o in rows]
    assert all(p % 512 == 0 for p in prompts) and (min(prompts), max(prompts)) == (16384, 30720)
    assert (min(outs), max(outs)) == (1024, 2048) and len(set(outs)) == 100
    assert statistics.median(prompts) == 23552 and statistics.median(outs) == 1536
    for n in (8, 16, 32, 64):  # every prefix of the table is balanced (Halton points by rank)
        assert abs(statistics.mean(prompts[:n]) - 23552) < 1200 and abs(statistics.mean(outs[:n]) - 1536) < 90
    assert mix["warm"] == {"max_rows": 8, "max_context_tokens": 32768} and max(p + o for p, o in rows) <= 32768
    conf = _conf()
    eng = conf["serve"]["engine"]
    assert (eng["chunk_prefill_tokens"], eng["max_prefill_tokens"], eng["page_size"], eng["max_batch_size"]) == (512, 512, 128, 8)
    assert mix["clients"] * eng["max_seq_len"] <= eng["pool_tokens"] == 262144  # no preemption
    shapes = serving.warm_shapes(conf, mix["warm"])
    assert len(shapes) == 72 and {t for _, t, _ in shapes} == {1, 512} and max(n for _, _, n in shapes) == 256
    plan = traffic.generate(mix, seed=2**31 + 7, seconds=51.0, vocab=conf["hf"]["vocab_size"])
    ids = [t for r in plan["requests"][:8] for t in r["prompt"]]
    assert len(plan["clients"]) == 8 and 15000 < max(ids) < 19200  # the slice of the vocabulary
