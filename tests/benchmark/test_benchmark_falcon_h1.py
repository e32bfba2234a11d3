"""Falcon-H1-34B's configuration in the benchmark (one stage of an eight-stage
pipeline): its plain reference against the program's whole forward at the
rehearsal's toy size, the configuration's keys against the catalog row and its
byte count against the served tree, its decode-step byte count against hand
arithmetic at the published sizes, the reader its cell adds and the accepted
readers that reach the cell (on made-up records and a made-up trace: what a
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

NAME = "falcon-h1-34b-pp8-int8"
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

    return plugins.load("kernel_counts", "falcon_h1_decode_step")


def _reader(name):
    from benchmark import plugins

    return plugins.load("layer_metrics", name).read


# -- the reference ------------------------------------------------------------------


@pytest.mark.parametrize("last", [47, 20, 3])
def test_reference_matches_program_forward_at_the_rehearsal_size(last):
    """One whole-sequence call of the program (48 tokens as one chunk from a
    fresh slot, pages and a slot in each of the toy's three layers) against
    the reference, which imports nothing of the program: the chunked form
    against the recurrence token by token, paged GQA against full causal
    attention, the multipliers (made-up values at this size, each its own).
    float32 both sides at ``highest`` precision: 1e-4 of the logit range."""
    import jax
    import jax.numpy as jnp

    from benchmark import serving, weights
    from benchmark.reference import falcon_h1
    from dynamo_tpu.models import kda, llama

    conf = _conf(rehearsal=True)
    cfg = serving.model_config(conf)
    assert cfg.dtype == "float32" and (cfg.num_layers, cfg.recurrent_layers, cfg.cache_layers) == (3, 3, 3)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state_size, cfg.ssm_groups, cfg.key_multiplier) == (4, 16, 8, 2, 0.8)
    params = weights.make_weights(cfg, 2**31 + 11, quant="")
    toks = np.random.default_rng(3).integers(1, cfg.vocab_size, size=48)
    k, v = llama.init_kv_cache(cfg, 5, 16)
    state, conv = kda.init_state(cfg, 3)
    got = llama.forward(params, cfg, jnp.asarray(toks)[None], jnp.arange(48)[None], k, v, jnp.asarray([[1, 2, 3]]),
                        (16 + jnp.arange(48))[None], jnp.asarray([last]), recurrent=(state, conv, jnp.asarray([2])))[0][0]
    want = np.asarray(jax.jit(functools.partial(falcon_h1.forward, hf=conf["hf"]))(params, tokens=jnp.asarray(toks)))
    assert np.abs(np.asarray(got) - want[last]).max() < 1e-4 * np.abs(want).max()


def test_reference_reads_the_served_leaves_and_the_general_rule_gives_each_new_leaf_what_the_file_says():
    import jax
    import jax.numpy as jnp

    from benchmark import serving, weights
    from benchmark.reference import falcon_h1

    conf = _conf(rehearsal=True)
    cfg = serving.model_config(conf)
    params = weights.make_weights(cfg, 5, quant="int8")
    layers = params["layers"]
    # The attention and FFN projections and the head int8 under the names they have; the mixer's two bf16-class leaves plain.
    assert {k for k, v in layers.items() if isinstance(v, dict)} == {"wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"}
    assert isinstance(params["lm_head"], dict) and layers["wq"]["qw"].dtype == jnp.int8
    assert not {"w_ssm_in", "w_ssm_out"} & weights.MATMUL_LEAVES
    assert layers["w_ssm_in"].shape == (3, 64, 64 + 96 + 4) and layers["w_ssm_out"].shape == (3, 64, 64)
    # What the general rule gives each new leaf (the configuration's ``assumed``).
    f32 = lambda name: np.asarray(layers[name], np.float32)  # noqa: E731
    assert not f32("ssm_dt_bias").any() and not f32("ssm_conv_bias").any() and (f32("ssm_norm") == 1).all()
    assert 0.3 < f32("ssm_conv").std() < 0.7  # normal x 4**-0.5
    assert 0.25 < f32("ssm_a_log").std() < 0.95 and 0.25 < f32("ssm_d").std() < 0.95  # normal x layers**-0.5 (3 here, 9 served)
    assert 0.08 < f32("w_ssm_in").std() < 0.18  # normal x 64**-0.5
    logits = jax.jit(functools.partial(falcon_h1.forward, hf=conf["hf"]))(params, tokens=jnp.arange(1, 17))
    assert logits.shape == (16, cfg.vocab_size) and bool(jnp.isfinite(logits).all())
    # The FFN widened a block of columns at a time is the FFN widened whole.
    from benchmark.reference import common as c

    lp = jax.tree.map(lambda x: x[0], layers)
    x = jax.random.normal(jax.random.PRNGKey(1), (5, 64), jnp.float32)
    z = falcon_h1.shape_of(conf["hf"])
    whole = ((x @ c.widen(lp["w_up"])) * jax.nn.silu((x @ c.widen(lp["w_gate"])) * z["mlp"][0])) @ c.widen(lp["w_down"]) * z["mlp"][1]
    np.testing.assert_allclose(falcon_h1.ffn(x, lp, z), whole, atol=1e-5 * float(jnp.abs(whole).max()))
    with pytest.raises(ValueError, match="int8 or plain leaves only"):
        falcon_h1.ffn(x, {**lp, "w_gate": {"qw4": lp["w_gate"]["qw"], "scale": lp["w_gate"]["scale"]}}, z)


# -- the configuration file ---------------------------------------------------------------


@pytest.mark.skipif(not CATALOG.exists(), reason="the catalog of public architectures is not on this machine")
def test_the_files_unreduced_keys_are_the_catalog_rows():
    from benchmark import serving
    from dynamo_tpu.models.config import FALCON_H1_34B_HF

    doc = json.loads((ROOT / "benchmark" / "configs" / f"{NAME}.json").read_text())
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines()) if r["source_url"] == doc["source"])
    entry = next(c for c in BENCH["configs"] if c["name"] == NAME)
    assert row["name"] == "Falcon-H1-34B-Instruct" and row["config"] == FALCON_H1_34B_HF
    assert entry["reduced"] == ["num_hidden_layers"] == list(doc["reduced_why"]) and entry["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in entry["reduced"]:
            assert doc[key] != value
        else:
            assert doc[key] == value, key
    assert set(doc) - set(row["config"]) - serving.OWN_KEYS == {"num_hidden_layers_published", "pipeline_stages", "stage_rank"}
    assert (doc["num_hidden_layers"], doc["num_hidden_layers_published"], doc["pipeline_stages"], doc["stage_rank"]) == (9, 72, 8, 0)
    assert {"reduced_why", "assumed", "deployment", "serve", "rehearsal", "reference"} <= set(doc)


def test_the_configuration_file_is_one_stage_and_its_bytes_are_the_trees():
    import jax

    from benchmark import serving, weights
    from dynamo_tpu.models import kda, llama
    from dynamo_tpu.models.config import ModelConfig

    conf = _conf()
    cfg = serving.model_config(conf)
    assert (cfg.num_layers, cfg.recurrent_layers, cfg.cache_layers, cfg.vocab_size) == (9, 9, 9, 261120)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.intermediate_size) == (20, 4, 128, 21504)
    assert cfg == ModelConfig.from_hf(dict(conf["hf"]), name=conf["name"])  # the override restates mamba_n_heads
    assert conf["serve"]["model_overrides"] == {"ssm_heads": conf["hf"]["mamba_n_heads"]}
    shapes = weights.tree_shapes(cfg)

    def nbytes(tree, name=None):
        if isinstance(tree, dict):
            return sum(nbytes(v, k) for k, v in tree.items())
        if name in weights.MATMUL_LEAVES:
            return tree.size + tree.size // tree.shape[-2] * 2  # int8 codes and a bf16 scale per output channel
        return tree.size * 2

    # ISSUE 46's arithmetic, counted again from the tree (the file's ``deployment``).
    layers = shapes["layers"]
    assert nbytes({k: layers[k] for k in ("wq", "wk", "wv", "wo")}) / 9 == pytest.approx(31.46 * MB + 17408, rel=1e-4)
    assert nbytes({k: layers[k] for k in ("w_gate", "w_up", "w_down")}) / 9 == pytest.approx(330.30 * MB + 96256, rel=1e-4)
    assert nbytes({k: layers[k] for k in ("w_ssm_in", "w_ssm_out")}) / 9 == pytest.approx(136.64 * MB, rel=1e-4)
    assert nbytes(layers) / 9 == pytest.approx(498.5 * MB, rel=1e-3)
    assert nbytes(shapes["embed"], "embed") == pytest.approx(2.674e9, rel=1e-3)
    assert nbytes(shapes["lm_head"], "lm_head") == pytest.approx(1.338e9, rel=1e-3)
    weights_bytes = nbytes(shapes)
    assert weights_bytes == pytest.approx(8.50e9, rel=2e-3)
    eng = conf["serve"]["engine"]
    state = sum(x.size * x.dtype.itemsize for x in jax.eval_shape(lambda: kda.init_state(cfg, eng["max_batch_size"] + 1)))
    assert state == 65 * cfg.state_bytes_per_slot() == 65 * 9 * (4_194_304 + 30_720) and state == pytest.approx(2.47e9, rel=2e-3)
    pool = sum(x.size * x.dtype.itemsize for x in jax.eval_shape(
        lambda: llama.init_kv_cache(cfg, eng["pool_tokens"] // eng["page_size"] + 1, eng["page_size"])))
    assert pool == 1025 * 128 * 9 * 2048 and pool == pytest.approx(2.42e9, rel=2e-3)
    held = weights_bytes + state + pool
    assert held == pytest.approx(13.39e9, rel=2e-3) and held > 0.25 * 16e9  # 78% of the chip's memory
    toy = serving.model_config(_conf(rehearsal=True))
    assert (toy.num_layers, toy.ssm_heads, toy.dtype) == (3, 4, "float32")


# -- the needed bytes and operations, by hand -------------------------------------------


def test_decode_step_bytes_by_hand():
    """ISSUE 46's arithmetic at the published sizes: a layer's attention 31.46
    MB and FFN 330.30 MB int8, its mixer's two projections 136.64 MB bf16,
    8.39 MB of state a row a layer both ways, 2,048 K/V bytes a token a layer,
    a 1.34 GB head: about 11.7 GB of which the state 41%, 14.2 ms at the HBM
    peak."""
    c, hf = _counts(), _conf()["hf"]
    got = c.decode_step(hf, rows=64, contexts_total=64 * 850)
    attn, ffn = 5120 * (20 + 2 * 4) * 128 + 20 * 128 * 5120, 3 * 5120 * 21504
    proj = 5120 * (4096 + 5120 + 32) + 4096 * 5120
    plain = 5 * 5120 + 3 * 32 + 4096 + 2 * 5120
    assert attn == pytest.approx(31.46 * MB, rel=1e-3) and ffn == pytest.approx(330.30 * MB, rel=1e-4)
    assert got["mixer_proj_bytes"] == 2 * proj and 2 * proj == pytest.approx(136.64 * MB, rel=1e-4)
    assert got["layer_weight_bytes"] == attn + ffn + 2 * (proj + plain)
    state = 9 * 64 * 2 * (32 * 256 * 128 * 4 + 3 * 5120 * 2)
    assert got["state_bytes"] == state and state == pytest.approx(4.87e9, rel=2e-3)
    cache = 9 * 64 * 850 * 2048
    assert got["cache_bytes"] == cache and cache == pytest.approx(1.0e9, rel=1e-2)
    head = 5120 * 261120
    assert got["head_bytes"] == head and head == pytest.approx(1.337e9, rel=1e-3)
    assert got["bytes"] == pytest.approx(9 * got["layer_weight_bytes"] + state + cache + head + 64 * 5120 * 2)
    assert 11.6e9 < got["bytes"] < 11.8e9 and 0.40 < state / got["bytes"] < 0.43 and got["experts_touched"] == 0.0
    assert 0.50 < (state + 9 * 2 * proj) / got["bytes"] < 0.54  # the mixer, state and projections, is over half the step
    peaks = json.loads((ROOT / "benchmark" / "peaks.json").read_text())["TPU v5 lite"]
    least, bound = c.least_seconds(got, peaks)
    assert bound == "memory" and 14.1e-3 < least < 14.4e-3
    # bf16 weights double the int8 leaves and leave the mixer's projections at 2 bytes; a count of experts changes nothing
    wide = c.decode_step(hf, rows=1, contexts_total=0, weight_bytes=2.0, experts_touched=7.0)
    assert wide["bytes"] == pytest.approx(9 * 2 * (attn + ffn + proj + plain) + state / 64 + 2 * head + 5120 * 2)
    assert wide["mixer_proj_bytes"] == got["mixer_proj_bytes"]


def test_state_and_attention_step_bytes_by_hand():
    c, hf = _counts(), _conf()["hf"]
    got = c.state_step(hf, rows=64)
    assert got["state_bytes"] == 9 * 64 * 2 * 32 * 256 * 128 * 4 == 64 * 9 * 2 * 4_194_304
    assert got["bytes"] == got["state_bytes"] + 9 * 64 * (3 * 4096 + 2 * 2 * 256) * 4  # dt x, the decay, the output; B and C a group
    assert got["conv_bytes"] == 9 * 64 * 2 * 3 * 5120 * 2 and got["flops"] == 9 * 64 * 6 * 32 * 256 * 128
    attn = c.attention_step(hf, kv_tokens_full=54400, kv_tokens_window=0, rows=64)
    assert attn["cache_bytes"] == 9 * 54400 * 2 * 4 * 128 * 2  # every layer attends
    assert attn["bytes"] == attn["cache_bytes"] + 9 * 64 * 2 * 20 * 128 * 2
    chunk = c.attention_step(hf, kv_tokens_full=2000, rows=1, new_tokens=64)
    assert chunk["flops"] == pytest.approx(9 * 4 * 20 * 128 * (64 * 2000 - 64 * 63 / 2))


def test_the_counts_are_the_served_trees_leaves():
    """Every leaf of a served layer is in the count once: int8 leaves at a byte
    a code (scales apart, 0.02% of them), everything else at 2."""
    from benchmark import serving, weights

    cfg = serving.model_config(_conf())
    shapes = weights.tree_shapes(cfg)["layers"]
    int8 = sum(v.size for k, v in shapes.items() if k in weights.MATMUL_LEAVES) // 9
    plain = sum(v.size for k, v in shapes.items() if k not in weights.MATMUL_LEAVES) // 9
    got = _counts().decode_step(_conf()["hf"], rows=0, contexts_total=0)
    assert got["layer_weight_bytes"] == int8 + 2 * plain
    assert got["bytes"] == 9 * (int8 + 2 * plain) + 5120 * 261120


def test_decode_roofline_reader_takes_these_counts():
    """``kernels.decode_roofline_pct`` (a file of the accepted benchmark) loads
    the configuration's counts by name."""
    read = _reader("kernels.decode_roofline_pct")
    steps = [{"step_kind": "decode", "decode_rows": 64}] * 3
    ctx = {"conf": _conf(), "peaks": PEAKS, "trace": {}, "notes": {}, "window": {"steps": steps},
           "mean_context_tokens": 850.0, "step_programs": [{"span": "engine.decode", "dur": 20e6}] * 3}
    need = _counts().decode_step(_conf()["hf"], rows=64, contexts_total=64 * 850)["bytes"]
    assert read(ctx) == pytest.approx(100 * need / 819e9 / 20e-3)
    assert ctx["notes"]["decode_roofline"]["bound"] == "memory" and ctx["notes"]["decode_roofline"]["experts_touched"] == 0.0


# -- the new reader, and the accepted one the cell joins ------------------------------------------


def _step(kind, seq, *, state_rows=64, traced=True, rows=64, chunk=0, kv=54400):
    rec = {"kind": "step", "seq": seq, "step_kind": kind, "decode_rows": rows, "chunk_rows": 1 if chunk else 0,
           "chunk_tokens": chunk, "traced": traced, "ann_ns": 1000 + seq, "t0_ns": 900 + seq, "overlap_mode": "overlapped",
           "kv_tokens_full": kv, "kv_tokens_window": 0, "moe_choices": 0, "moe_experts_touched": 0}
    if state_rows is not None:
        rec.update(state_rows=state_rows, state_slots_live=64)
    return rec


from tests.benchmark.test_benchmark_ling import _trace  # noqa: E402  (a made-up device plane and host line from (annotation, start, dur, ops))


def _ctx(steps, trace=None, conf=None):
    return {"conf": conf or _conf(), "peaks": PEAKS, "window": {"steps": steps}, "trace": trace, "notes": {}}


def test_ssm_roofline_reader_takes_each_steps_own_rows():
    """Three traced decode steps and a mixed one: the kernel runs once a layer
    (9 events a program); a step's needed bytes are its own ``state_rows``';
    the kernel's events inside a mixed step's program are another step kind's."""
    read = _reader("kernels.ssm_decode_roofline_pct")
    need = {rows: _counts().state_step(_conf()["hf"], rows=rows)["bytes"] for rows in (64, 32)}
    least = {rows: b / 819e9 * 1e9 for rows, b in need.items()}  # ns

    def kernel(total_ns):
        return [("mamba_decode_step.4", 100 + 2_000_000 * i, total_ns / 9) for i in range(9)]

    programs = [
        ("engine.decode", 10_000, 19_000_000, kernel(least[64] * 2) + [("paged_decode_attention.3", 5, 20)]),
        ("engine.mixed", 20_000_000, 22_000_000, kernel(9e6)),
        ("engine.decode", 45_000_000, 19_000_000, kernel(least[32] * 4)),
        ("engine.decode", 65_000_000, 19_000_000, kernel(least[64] * 2.5)),
    ]
    steps = [_step("decode", 1), _step("mixed", 2, chunk=64), _step("decode", 3, state_rows=32, rows=32), _step("decode", 4),
             _step("decode", 5, traced=False)]
    ctx = _ctx(steps, _trace(programs))
    assert read(ctx) == pytest.approx(40.0)  # median of 50, 25 and 40
    note = ctx["notes"]["ssm_decode_roofline"]
    assert note["steps"] == 3 and note["events"] == 9 and note["state_rows"] == 64 and note["needed_bytes"] == need[64]
    # nothing to read: no trace; a program without the field (the parent's); a trace without the kernel
    # (Ling's has another); a configuration whose counts have no state_step
    assert read(_ctx(steps)) is None
    assert read(_ctx([_step(s["step_kind"], s["seq"], state_rows=None) for s in steps], _trace(programs))) is None
    other = [(n, s, d, [("kda_decode_step.4", o, t) for _, o, t in evs]) for n, s, d, evs in programs]
    assert read(_ctx(steps, _trace(other))) is None
    from benchmark import serving

    joyai = serving.load_config(ROOT / "benchmark" / "configs" / "joyai-llm-flash-ep8-int8.json")
    assert read(_ctx(steps, _trace(programs), conf=joyai)) is None


def test_recurrent_state_share_reader_takes_this_cells_records():
    """``engine.recurrent_state_bytes_pct`` (the accepted benchmark's file) on a
    dense model's records: no expert is counted, every layer's key tokens are."""
    read = _reader("engine.recurrent_state_bytes_pct")
    c, hf = _counts(), _conf()["hf"]
    steps = [_step("decode", 1), _step("decode", 2), _step("mixed", 3, chunk=64, state_rows=65)]
    ctx = _ctx(steps)
    step, state = c.decode_step(hf, rows=64, contexts_total=54400), c.state_step(hf, rows=64)
    want = 100 * (state["state_bytes"] + state["conv_bytes"]) / step["bytes"]
    assert read(ctx) == pytest.approx(want) and 40 < want < 43
    assert ctx["notes"]["recurrent_state"]["steps"] == 2 and ctx["notes"]["recurrent_state"]["state_rows"] == 64
    assert read(_ctx([_step("decode", 1, state_rows=None)])) is None  # a program without state_rows (the parent's)


# -- the cell --------------------------------------------------------------------------------


def test_the_cells_entries():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, "reason-saturated", 1) and len(cell["why"]) <= 200
    # This cell's own entries, by name: what else lists the cell, how many cells there are and what the other
    # entries say is not this file's to hold (a later PR appends to those lists).
    metrics = {m["name"]: m for m in BENCH["per_layer"]}
    for name, says in (("kernels.ssm_decode_roofline_pct", ("device_trace", "kernels", "itl_p50_ms", "%", "higher")),
                       ("engine.recurrent_state_bytes_pct", ("program_counter", "engine", "itl_p50_ms", "%", "higher"))):
        m = metrics[name]
        assert (m["source"], m["layer"], m["moves"], m["unit"], m["better"]) == says and CELL in m["workloads"]
        assert (ROOT / "benchmark" / "layer_metrics" / f"{name}.py").is_file()
    # The lists that accepted tests pin by equality do not name the cell: it reports itl_p50_ms and setup_s only.
    e2e = {m["name"] for m in BENCH["end_to_end"] if CELL in m.get("workloads", [CELL])}
    assert e2e == {"itl_p50_ms", "setup_s"}
    entry = next(c for c in BENCH["configs"] if c["name"] == NAME)
    assert entry["file"] == f"benchmark/configs/{NAME}.json" and len(entry["why"]) <= 200
    assert entry["source"] == "https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct/blob/main/config.json"


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
    assert len(plan["clients"]) == 64 and max(ids) < 261120 and max(ids) > 200000  # the whole vocabulary
