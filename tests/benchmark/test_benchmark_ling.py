"""Ling-3.0-flash's configuration in the benchmark: its plain reference against
the program's whole forward at the rehearsal's toy size, the configuration's
keys against the catalog row and its byte count against the served tree, its
decode-step byte count against hand arithmetic at the published sizes, the two
per-layer readers its cell adds (on made-up records and a made-up trace: what a
program without the counters writes gives them nothing to read), and the
cell's entries (by name: no position in a list is asserted)."""

import functools
import json
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

NAME = "ling-3.0-flash-ep8-int8"
CELL = f"{NAME}.reason-saturated"
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CATALOG = pathlib.Path("/opt/skills/guides/model-configs/architectures.jsonl")
MB = 1e6
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def _conf(rehearsal=False):
    from benchmark import serving

    return serving.load_config(ROOT / "benchmark" / "configs" / f"{NAME}.json", rehearsal=rehearsal)


def _counts():
    from benchmark import plugins

    return plugins.load("kernel_counts", "ling_flash_decode_step")


def _reader(name):
    from benchmark import plugins

    return plugins.load("layer_metrics", name).read


# -- the reference ------------------------------------------------------------------


@pytest.mark.parametrize("last", [47, 20, 3])
def test_reference_matches_program_forward_at_the_rehearsal_size(last):
    """One whole-sequence call of the program (48 tokens as one chunk from a
    fresh slot; the toy model of the rehearsal: two periods of KDA, KDA, MLA, a
    leading dense FFN, the second of two routing groups held, one group and
    two choices a token, a shared expert) against the reference, which imports
    nothing of the program: the chunkwise form against the recurrence token by
    token, un-absorbed MLA against absorbed, one expert at a time against
    sorted rows. float32 both sides at ``highest`` precision: 1e-4 of the
    logit range."""
    import jax
    import jax.numpy as jnp

    from benchmark import serving, weights
    from benchmark.reference import ling_3_flash
    from dynamo_tpu.models import kda, llama

    conf = _conf(rehearsal=True)
    cfg = serving.model_config(conf)
    assert cfg.dtype == "float32" and (cfg.num_layers, cfg.recurrent_layers, cfg.cache_layers, cfg.first_k_dense) == (6, 4, 2, 1)
    assert (cfg.num_experts, cfg.routed_experts, cfg.moe_expert_first, cfg.moe_n_group, cfg.moe_topk_group) == (8, 16, 8, 2, 1)
    params = weights.make_weights(cfg, 2**31 + 11, quant="")
    toks = np.random.default_rng(3).integers(1, cfg.vocab_size, size=48)
    k, v = llama.init_kv_cache(cfg, 5, 16)
    state, conv = kda.init_state(cfg, 3)
    got = llama.forward(params, cfg, jnp.asarray(toks)[None], jnp.arange(48)[None], k, v, jnp.asarray([[1, 2, 3]]),
                        (16 + jnp.arange(48))[None], jnp.asarray([last]), recurrent=(state, conv, jnp.asarray([2])))[0][0]
    want = np.asarray(jax.jit(functools.partial(ling_3_flash.forward, hf=conf["hf"]))(params, tokens=jnp.asarray(toks)))
    assert np.abs(np.asarray(got) - want[last]).max() < 1e-4 * np.abs(want).max()


def test_reference_reads_the_served_int8_leaves_and_refuses_what_it_does_not_know():
    import jax
    import jax.numpy as jnp

    from benchmark import serving, weights
    from benchmark.reference import ling_3_flash

    conf = _conf(rehearsal=True)
    cfg = serving.model_config(conf)
    params = weights.make_weights(cfg, 5, quant="int8")
    kda_leaves, mla_leaves = params["kda_layers"], params["mla_layers"]
    assert {k for k, v in kda_leaves.items() if isinstance(v, dict)} == {"wq", "wk", "wv", "wo"}  # served int8
    assert {k for k, v in mla_leaves.items() if isinstance(v, dict)} == {"w_q", "w_kv_a", "wo_mla"}
    assert kda_leaves["wq"]["qw"].dtype == jnp.int8 and kda_leaves["wq"]["qw"].shape == (4, 64, 64)
    # What the general rule gives each new leaf (the configuration's ``assumed``).
    assert float(jnp.abs(kda_leaves["dt_bias"]).max()) == 0.0 and float(jnp.abs(kda_leaves["o_norm"] - 1).max()) == 0.0
    assert 0.3 < float(jnp.std(kda_leaves["conv_q"].astype(jnp.float32))) < 0.7  # normal x 4**-0.5
    assert 0.2 < float(jnp.std(kda_leaves["a_log"].astype(jnp.float32))) < 0.9  # normal x layers**-0.5
    assert 0.08 < float(jnp.std(kda_leaves["w_decay"].astype(jnp.float32))) < 0.18  # normal x 64**-0.5
    assert float(jnp.abs(params["layers"]["router_bias"]).max()) == 0.0
    logits = jax.jit(functools.partial(ling_3_flash.forward, hf=conf["hf"]))(params, tokens=jnp.arange(1, 17))
    assert logits.shape == (16, cfg.vocab_size) and bool(jnp.isfinite(logits).all())
    for edit, says in (({"score_function": "softmax"}, "sigmoid scores"), ({"topk_method": "greedy"}, "noaux_tc"),
                       ({"expert_swiglu_limit_list": [0, 0, 4]}, "no clamped SwiGLU")):
        with pytest.raises(ValueError, match=says):
            ling_3_flash.shape_of({**conf["hf"], **edit})
    import benchmark.reference.ling_3_flash as mod

    assert "dynamo_tpu" not in pathlib.Path(mod.__file__).read_text()  # nothing of the program


# -- the configuration file ---------------------------------------------------------------


@pytest.mark.skipif(not CATALOG.exists(), reason="the catalog of public architectures is not on this machine")
def test_the_files_unreduced_keys_are_the_catalog_rows():
    from benchmark import serving

    doc = json.loads((ROOT / "benchmark" / "configs" / f"{NAME}.json").read_text())
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines()) if r["source_url"] == doc["source"])
    entry = next(c for c in BENCH["configs"] if c["name"] == NAME)
    assert entry["reduced"] == ["num_hidden_layers", "num_experts"] and entry["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in entry["reduced"]:
            assert doc[key] != value
        else:
            assert doc[key] == value, key
    share = {"n_routed_experts_published", "expert_share_rank", "expert_share_chips"}
    assert set(doc) - set(row["config"]) - serving.OWN_KEYS == share
    assert (doc["num_hidden_layers"], doc["num_experts"], doc["n_routed_experts_published"], doc["expert_share_rank"],
            doc["expert_share_chips"]) == (18, 64, 512, 0, 8)
    assert len(doc["expert_swiglu_limit_list"]) == len(doc["share_expert_swiglu_limit_list"]) == 42  # whole, as published
    from dynamo_tpu.models.config import LING_3_FLASH_HF

    assert row["config"] == LING_3_FLASH_HF


def test_the_configuration_file_is_this_chips_share_and_its_bytes_are_the_trees():
    import jax

    from benchmark import serving, weights
    from dynamo_tpu.models import kda, llama
    from dynamo_tpu.models.config import ModelConfig

    conf = _conf()
    cfg = serving.model_config(conf)
    assert (cfg.num_layers, cfg.recurrent_layers, cfg.cache_layers, cfg.first_k_dense) == (18, 15, 3, 2)
    assert (cfg.num_experts, cfg.routed_experts, cfg.moe_expert_first, cfg.moe_n_group, cfg.moe_topk_group) == (64, 512, 0, 8, 4)
    assert cfg.moe_held_share and cfg.vocab_size == 157184 and cfg.shared_expert_size == 768
    assert cfg == ModelConfig.from_hf(dict(conf["hf"]), name=conf["name"])  # the overrides restate the share keys
    shapes = weights.tree_shapes(cfg)

    def nbytes(tree, name=None):
        if isinstance(tree, dict):
            return sum(nbytes(v, k) for k, v in tree.items())
        if name in weights.MATMUL_LEAVES:
            return tree.size + tree.size // tree.shape[-2] * 2  # int8 codes and a bf16 scale per output channel
        return tree.size * (4 if name == "router_bias" else 2)

    # ISSUE 40's arithmetic, counted again from the tree (the file's ``deployment``).
    assert nbytes(shapes["kda_layers"]) / 15 == pytest.approx(63.38 * MB, rel=1e-3)
    assert nbytes(shapes["mla_layers"]) / 3 == pytest.approx(36.26 * MB, rel=1e-3)
    assert nbytes(shapes["layers"]) / 16 == pytest.approx(386.55 * MB, rel=1e-3)
    assert nbytes(shapes["dense_layers"]) / 2 == pytest.approx(47.23 * MB, rel=1e-3)
    weights_bytes = nbytes(shapes)
    assert weights_bytes == pytest.approx(8.546e9, rel=1e-3)
    eng = conf["serve"]["engine"]
    state = sum(x.size * x.dtype.itemsize for x in jax.eval_shape(lambda: kda.init_state(cfg, eng["max_batch_size"] + 1)))
    assert state == 65 * cfg.state_bytes_per_slot() and state == pytest.approx(2.117e9, rel=1e-3)
    pool = sum(x.size * x.dtype.itemsize for x in jax.eval_shape(
        lambda: llama.init_kv_cache(cfg, eng["pool_tokens"] // eng["page_size"] + 1, eng["page_size"])))
    assert pool == pytest.approx(0.504e9, rel=1e-3)
    held = weights_bytes + state + pool
    assert held == pytest.approx(11.17e9, rel=1e-3) and held > 0.25 * 16e9  # 70% of the chip's memory
    toy = serving.load_config(ROOT / "benchmark" / "configs" / f"{NAME}.json", rehearsal=True)
    assert serving.model_config(toy).moe_expert_first == 8


# -- the needed bytes and operations, by hand -------------------------------------------


def test_decode_step_bytes_by_hand():
    """ISSUE 40's arithmetic at the published sizes: a KDA block 63.3 MB, an
    MLA block 36.2 MB, 5.90 MB an expert, 40.6 of 64 held experts touched at 64
    rows, 4.19 MB of state a row a KDA layer, 1,152 cache bytes a token an MLA
    layer, a 402 MB head: about 9.9 GB of which the state 42%, 12.1 ms at the
    HBM peak."""
    c, hf = _counts(), _conf()["hf"]
    got = c.decode_step(hf, rows=64, contexts_total=64 * 848)
    kda_int8, kda_bf16 = 4 * 2560 * 4096, 2560 * 4096 + 2 * 2560 * 32 + 3 * 4 * 4096 + 32 + 4096 + 128
    assert got["kda_block_bytes"] == kda_int8 + 2 * kda_bf16 and got["kda_block_bytes"] == pytest.approx(63.3 * MB, rel=2e-3)
    mla_int8, mla_bf16 = 2560 * 32 * 192 + 2560 * 576 + 4096 * 2560, 512 * 32 * 256 + 2560 * 32
    assert got["mla_block_bytes"] == mla_int8 + 2 * mla_bf16 and got["mla_block_bytes"] == pytest.approx(36.2 * MB, rel=2e-3)
    expert = 3 * 2560 * 768
    assert expert == pytest.approx(5.90 * MB, rel=1e-3) and 64 * expert == pytest.approx(377.5 * MB, rel=1e-3)
    outside = expert + 2560 * 512 * 2  # the shared expert is one expert's size; the router bf16
    assert got["routed_outside_experts_bytes"] == outside and got["dense_ffn_bytes"] == 3 * 2560 * 6144
    touched = 64 * (1 - (1 - 8 / 512) ** 64)
    assert got["experts_touched"] == pytest.approx(touched) and touched == pytest.approx(40.6, abs=0.05)
    assert got["experts_bytes"] == pytest.approx(16 * touched * expert) and got["experts_bytes"] == pytest.approx(3.84e9, rel=2e-3)
    state = 15 * 64 * 2 * (32 * 128 * 128 * 4 + 3 * 3 * 4096 * 2)
    assert got["state_bytes"] == state and state == pytest.approx(4.17e9, rel=2e-3)
    cache = 3 * 64 * 848 * 1152
    assert got["cache_bytes"] == cache and cache == pytest.approx(0.19e9, rel=2e-2)
    head = 2560 * 157184
    assert head == pytest.approx(402.4 * MB, rel=1e-3)
    weights_outside = 15 * got["kda_block_bytes"] + 3 * got["mla_block_bytes"] + 16 * outside + 2 * 3 * 2560 * 6144 + head
    assert got["bytes"] == pytest.approx(weights_outside + 16 * touched * expert + state + cache + 64 * 2560 * 2)
    assert weights_outside + 16 * touched * expert == pytest.approx(5.5e9, rel=1e-2)
    assert 9.7e9 < got["bytes"] < 10.0e9 and 0.40 < state / got["bytes"] < 0.44
    peaks = json.loads((ROOT / "benchmark" / "peaks.json").read_text())["TPU v5 lite"]
    least, bound = c.least_seconds(got, peaks)
    assert bound == "memory" and 11.9e-3 < least < 12.2e-3
    # a count handed in takes the formula's place; bf16 weights double the matmul leaves only
    assert c.decode_step(hf, rows=64, contexts_total=0, experts_touched=64.0)["bytes"] == pytest.approx(
        weights_outside + 16 * 64 * expert + state + 64 * 2560 * 2)
    wide = c.decode_step(hf, rows=1, contexts_total=0, weight_bytes=2.0, experts_touched=0.0)
    assert wide["bytes"] == pytest.approx(15 * 2 * (kda_int8 + kda_bf16) + 3 * 2 * (mla_int8 + mla_bf16)
                                          + 16 * (2 * expert + 2560 * 512 * 2) + 2 * 2 * 3 * 2560 * 6144 + 2 * head
                                          + state / 64 + 2560 * 2)


def test_state_attention_and_experts_step_bytes_by_hand():
    c, hf = _counts(), _conf()["hf"]
    got = c.state_step(hf, rows=64)
    assert got["state_bytes"] == 15 * 64 * 2 * 32 * 128 * 128 * 4 == 64 * 15 * 4194304
    assert got["bytes"] == got["state_bytes"] + 15 * 64 * 5 * 4096 * 4  # q, k, v, the decay in and the output out
    assert got["conv_bytes"] == 15 * 64 * 2 * 3 * 12288 * 2 and got["flops"] == 15 * 64 * 8 * 32 * 128 * 128
    attn = c.attention_step(hf, kv_tokens_full=55680, kv_tokens_window=0, rows=64)
    assert attn["cache_bytes"] == 3 * 55680 * 576 * 2  # the three MLA layers only
    assert attn["bytes"] == attn["cache_bytes"] + 3 * 64 * 32 * (512 + 64 + 512) * 2
    chunk = c.attention_step(hf, kv_tokens_full=2000, rows=1, new_tokens=64)
    assert chunk["flops"] == pytest.approx(3 * 2 * 32 * 1088 * (64 * 2000 - 64 * 63 / 2))
    assert c.experts_step(hf, experts_touched_total=650) == {"bytes": 650 * 3 * 2560 * 768, "choices_per_token": 8 * 16}


def test_decode_roofline_reader_takes_these_counts():
    """``kernels.decode_roofline_pct`` (a file of the accepted benchmark) loads
    the configuration's counts by name."""
    read = _reader("kernels.decode_roofline_pct")
    steps = [{"step_kind": "decode", "decode_rows": 64}] * 3
    ctx = {"conf": _conf(), "peaks": PEAKS, "trace": {}, "notes": {}, "window": {"steps": steps},
           "mean_context_tokens": 848.0, "step_programs": [{"span": "engine.decode", "dur": 20e6}] * 3}
    need = _counts().decode_step(_conf()["hf"], rows=64, contexts_total=64 * 848)["bytes"]
    assert read(ctx) == pytest.approx(100 * need / 819e9 / 20e-3)
    assert ctx["notes"]["decode_roofline"]["bound"] == "memory"


# -- the two new readers ------------------------------------------------------------------


def _step(kind, seq, *, state_rows=64, touched=650, traced=True, rows=64, chunk=0, kv=55680):
    rec = {"kind": "step", "seq": seq, "step_kind": kind, "decode_rows": rows, "chunk_rows": 1 if chunk else 0,
           "chunk_tokens": chunk, "traced": traced, "ann_ns": 1000 + seq, "t0_ns": 900 + seq, "overlap_mode": "overlapped",
           "kv_tokens_full": kv, "kv_tokens_window": 0, "moe_choices": rows * 8 * 16, "moe_experts_touched": touched}
    if state_rows is not None:
        rec.update(state_rows=state_rows, state_slots_live=64)
    return rec


def _trace(programs):
    """One device plane and one host line: per program (annotation name, start,
    dur, [(op name, offset, dur), ...]) in ns."""
    mods = [["jit__step(1)", s, d] for _, s, d, _ in programs]
    ops = [[name, s + off, dur] for _, s, _, evs in programs for name, off, dur in evs]
    anns = [[name, s - 50, d + 100] for name, s, d, _ in programs]
    return {"planes": [{"name": "/device:TPU:0", "lines": [{"name": "XLA Modules", "events": mods},
                                                           {"name": "XLA Ops", "events": ops}]},
                       {"name": "/host:CPU", "lines": [{"name": "exec", "events": anns}]}]}


def _ctx(steps, trace=None, conf=None):
    return {"conf": conf or _conf(), "peaks": PEAKS, "window": {"steps": steps}, "trace": trace, "notes": {}}


def test_kda_roofline_reader_takes_each_steps_own_rows():
    """Three traced decode steps and a mixed one: the kernel runs once a KDA
    layer (15 events a program); a step's needed bytes are its own
    ``state_rows``'; the kernel's events inside a mixed step's program are
    another step kind's."""
    read = _reader("kernels.kda_decode_roofline_pct")
    need = {rows: _counts().state_step(_conf()["hf"], rows=rows)["bytes"] for rows in (64, 32)}
    least = {rows: b / 819e9 * 1e9 for rows, b in need.items()}  # ns

    def kernel(total_ns):
        return [("kda_decode_step.4", 100 + 900_000 * i, total_ns / 15) for i in range(15)]

    programs = [
        ("engine.decode", 10_000, 17_000_000, kernel(least[64] * 2) + [("mla_paged_decode_attention.3", 5, 20)]),
        ("engine.mixed", 20_000_000, 20_000_000, kernel(9e6)),
        ("engine.decode", 45_000_000, 17_000_000, kernel(least[32] * 4)),
        ("engine.decode", 65_000_000, 17_000_000, kernel(least[64] * 2.5)),
    ]
    steps = [_step("decode", 1), _step("mixed", 2, chunk=64), _step("decode", 3, state_rows=32, rows=32), _step("decode", 4),
             _step("decode", 5, traced=False)]
    ctx = _ctx(steps, _trace(programs))
    assert read(ctx) == pytest.approx(40.0)  # median of 50, 25 and 40
    note = ctx["notes"]["kda_decode_roofline"]
    assert note["steps"] == 3 and note["events"] == 15 and note["state_rows"] == 64 and note["needed_bytes"] == need[64]
    # nothing to read: no trace; a program without the field (the parent's); a trace without the kernel;
    # a configuration whose counts have no state_step
    assert read(_ctx(steps)) is None
    assert read(_ctx([_step(s["step_kind"], s["seq"], state_rows=None) for s in steps], _trace(programs))) is None
    other = [(n, s, d, [("fusion.1", o, t) for _, o, t in evs]) for n, s, d, evs in programs]
    assert read(_ctx(steps, _trace(other))) is None
    from benchmark import serving

    joyai = serving.load_config(ROOT / "benchmark" / "configs" / "joyai-llm-flash-ep8-int8.json")
    assert read(_ctx(steps, _trace(programs), conf=joyai)) is None


def test_recurrent_state_share_reader():
    read = _reader("engine.recurrent_state_bytes_pct")
    c, hf = _counts(), _conf()["hf"]
    steps = [_step("decode", 1), _step("decode", 2, touched=670), _step("mixed", 3, chunk=64, state_rows=65)]
    ctx = _ctx(steps)
    step = c.decode_step(hf, rows=64, contexts_total=55680, experts_touched=660 / 16)
    state = c.state_step(hf, rows=64)
    want = 100 * (state["state_bytes"] + state["conv_bytes"]) / step["bytes"]
    assert read(ctx) == pytest.approx(want) and 30 < want < 50
    assert ctx["notes"]["recurrent_state"]["steps"] == 2 and ctx["notes"]["recurrent_state"]["state_rows"] == 64
    # a program that counts no experts: the even-routing formula stands
    bare = [{k: v for k, v in s.items() if not k.startswith("moe_")} for s in steps]
    even = c.decode_step(hf, rows=64, contexts_total=55680)
    assert read(_ctx(bare)) == pytest.approx(100 * (state["state_bytes"] + state["conv_bytes"]) / even["bytes"])
    # nothing to read: a program without state_rows (the parent's), a configuration without state_step
    assert read(_ctx([_step("decode", 1, state_rows=None)])) is None
    from benchmark import serving

    joyai = serving.load_config(ROOT / "benchmark" / "configs" / "joyai-llm-flash-ep8-int8.json")
    assert read(_ctx(steps, conf=joyai)) is None


def test_accepted_readers_find_this_cells_records():
    held, mla = _reader("engine.moe_held_choice_pct"), _reader("kernels.mla_decode_roofline_pct")
    steps = [_step("decode", 1), _step("decode", 2)]
    for s in steps:
        s["moe_choices_held"] = s["moe_choices"] // 8
    assert held(_ctx(steps)) == pytest.approx(12.5)
    need = _counts().attention_step(_conf()["hf"], kv_tokens_full=55680, rows=1, new_tokens=64)["bytes"]
    kernel = [("mla_paged_decode_attention.3", 100 + 50_000 * i, need / 819e9 * 1e9 / 3 * 2) for i in range(3)]
    programs = [("engine.decode", 10_000, 17_000_000, kernel), ("engine.decode", 30_000_000, 17_000_000, kernel)]
    assert mla(_ctx(steps, _trace(programs))) == pytest.approx(50.0)


# -- the cell --------------------------------------------------------------------------------


def test_the_cells_entries():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, "reason-saturated", 1) and len(cell["why"]) <= 200
    # This cell's own entries, by name: what else lists the cell, how many cells there are and what the other
    # entries say is not this file's to hold (a later PR appends to those lists).
    metrics = {m["name"]: m for m in BENCH["per_layer"]}
    for name, says in (("kernels.kda_decode_roofline_pct", ("device_trace", "kernels", "itl_p50_ms", "%", "higher")),
                       ("engine.recurrent_state_bytes_pct", ("program_counter", "engine", "itl_p50_ms", "%", "higher"))):
        m = metrics[name]
        assert (m["source"], m["layer"], m["moves"], m["unit"], m["better"]) == says and CELL in m["workloads"]
        assert (ROOT / "benchmark" / "layer_metrics" / f"{name}.py").is_file()
    for name in ("kernels.mla_decode_roofline_pct", "engine.moe_held_choice_pct"):
        assert CELL in metrics[name]["workloads"]
    e2e = {m["name"] for m in BENCH["end_to_end"] if CELL in m.get("workloads", [CELL])}
    assert e2e >= {"itl_p50_ms", "setup_s"}
    entry = next(c for c in BENCH["configs"] if c["name"] == NAME)
    assert entry["file"] == f"benchmark/configs/{NAME}.json" and len(entry["why"]) <= 200


def test_the_cell_warms_seventy_programs_and_never_preempts():
    from benchmark import serving, traffic

    mix = traffic.load_mix(ROOT / "benchmark" / "traffic" / "reason-saturated.json",
                           ROOT / "benchmark" / "cells" / f"{CELL}.json")
    rows = mix["lengths_per_100"]
    assert (mix["loop"], mix["clients"], mix["requests_per_client"]) == ("closed", 64, 6)
    assert mix["warm"] == {"max_rows": 64, "max_context_tokens": 2048} and max(p + o for p, o in rows) <= 1984
    eng = _conf()["serve"]["engine"]
    assert mix["clients"] * max(p + o for p, o in rows) <= eng["pool_tokens"] == 131072  # no preemption
    assert mix["clients"] <= eng["max_batch_size"]  # a state slot for every client beside the null slot
    assert len(serving.warm_shapes(_conf(), mix["warm"])) == 70
    plan = traffic.generate(mix, seed=2**31 + 7, seconds=51.0, vocab=_conf()["hf"]["vocab_size"])
    ids = [t for r in plan["requests"] for t in r["prompt"]]
    assert len(plan["clients"]) == 64 and max(ids) < 157184 and max(ids) > 120000  # the whole vocabulary
