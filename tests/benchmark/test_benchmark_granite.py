"""granite-4.0-h-small's configuration in the benchmark (one stage of a
four-stage pipeline): its plain reference against the program's whole forward
at the rehearsal's toy size, the configuration's keys against the catalog row
and its byte count against the served tree, its decode-step byte count against
hand arithmetic at the published sizes, the accepted readers that reach the
cell (on made-up records and a made-up trace), and the cell's entries (by
name: no position in a list is asserted but the cell's own, which the
rehearsal test takes as the last)."""

import functools
import json
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

NAME = "granite-4.0-h-small-pp4-int8"
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

    return plugins.load("kernel_counts", "granite_hybrid_decode_step")


def _reader(name):
    from benchmark import plugins

    return plugins.load("layer_metrics", name).read


# -- the reference ------------------------------------------------------------------


@pytest.mark.parametrize("last", [47, 20, 3])
def test_reference_matches_program_forward_at_the_rehearsal_size(last):
    """One whole-sequence call of the program (48 tokens as one chunk from a
    fresh slot: the toy's one period of ``[mamba, mamba, attention, mamba]``,
    pages in its one attention layer, slots in its three Mamba layers, 8 heads
    of 16 channels side by side in one row of the state buffer) against the
    reference, which imports nothing of the program: the chunked form against
    the recurrence token by token in the published ``[P, N]`` orientation,
    paged GQA without RoPE at the config's own scale against full causal
    attention, the router's softmax over all renormalised against the softmax
    over the chosen logits, every multiplier a made-up value that is not 1.
    float32 both sides at ``highest`` precision: 1e-4 of the logit range."""
    import jax
    import jax.numpy as jnp

    from benchmark import serving, weights
    from benchmark.reference import granite_hybrid
    from dynamo_tpu.models import kda, llama

    conf = _conf(rehearsal=True)
    cfg = serving.model_config(conf)
    assert cfg.dtype == "float32" and (cfg.num_layers, cfg.recurrent_layers, cfg.cache_layers, cfg.period_attn_index) == (4, 3, 1, 2)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_heads_per_row, cfg.state_shapes()[0]) == (8, 16, 8, (1, 8, 128))
    params = weights.make_weights(cfg, 2**31 + 11, quant="")
    toks = np.random.default_rng(3).integers(1, cfg.vocab_size, size=48)
    k, v = llama.init_kv_cache(cfg, 5, 16)
    state, conv = kda.init_state(cfg, 3)
    got = llama.forward(params, cfg, jnp.asarray(toks)[None], jnp.arange(48)[None], k, v, jnp.asarray([[1, 2, 3]]),
                        (16 + jnp.arange(48))[None], jnp.asarray([last]), recurrent=(state, conv, jnp.asarray([2])))[0][0]
    want = np.asarray(jax.jit(functools.partial(granite_hybrid.forward, hf=conf["hf"]))(params, tokens=jnp.asarray(toks)))
    assert np.abs(np.asarray(got) - want[last]).max() < 1e-4 * np.abs(want).max()


def test_reference_reads_the_served_leaves_and_the_general_rule_gives_each_new_leaf_what_the_file_says():
    import jax
    import jax.numpy as jnp

    from benchmark import serving, weights
    from benchmark.reference import granite_hybrid

    conf = _conf(rehearsal=True)
    cfg = serving.model_config(conf)
    params = weights.make_weights(cfg, 5, quant="int8")
    # The attention layer's projections, the experts and the shared expert int8 under the names they have; the
    # mixers' two projections, the router and the tied embedding plain; no lm_head.
    assert set(params) == {"embed", "norm_f", "layers", "ssm_layers", "attn_layers"}
    assert {k for k, v in params["layers"].items() if isinstance(v, dict)} == {
        "w_gate", "w_up", "w_down", "w_shared_gate", "w_shared_up", "w_shared_down"}
    assert all(isinstance(params["attn_layers"][k], dict) for k in ("wq", "wk", "wv", "wo")) and params["attn_layers"]["wq"]["qw"].dtype == jnp.int8
    assert not {"w_ssm_in", "w_ssm_out", "router", "embed"} & weights.MATMUL_LEAVES
    mixers = params["ssm_layers"]
    assert mixers["w_ssm_in"].shape == (3, 64, 128 + 144 + 8) and mixers["w_ssm_out"].shape == (3, 128, 64)
    f32 = lambda name: np.asarray(mixers[name], np.float32)  # noqa: E731
    assert not f32("ssm_dt_bias").any() and not f32("ssm_conv_bias").any() and (f32("ssm_norm") == 1).all()
    assert 0.3 < f32("ssm_conv").std() < 0.7  # normal x 4**-0.5
    assert 0.25 < f32("ssm_a_log").std() < 0.95 and 0.25 < f32("ssm_d").std() < 0.95  # normal x mamba layers**-0.5 (3 here, 9 served)
    assert 0.08 < f32("w_ssm_in").std() < 0.18  # normal x 64**-0.5
    logits = jax.jit(functools.partial(granite_hybrid.forward, hf=conf["hf"]))(params, tokens=jnp.arange(1, 17))
    assert logits.shape == (16, cfg.vocab_size) and bool(jnp.isfinite(logits).all())
    # The tied head a slice of the vocabulary at a time is the head whole.
    x = jax.random.normal(jax.random.PRNGKey(1), (5, 64), jnp.float32)
    whole = x @ params["embed"].astype(jnp.float32).T
    np.testing.assert_allclose(granite_hybrid.tied_head(x, params["embed"]), whole, atol=1e-5 * float(jnp.abs(whole).max()))
    from benchmark.reference import common as c

    with pytest.raises(ValueError, match="int8 or plain leaves only"):
        lp = jax.tree.map(lambda a: a[0], params["layers"])
        granite_hybrid.ffn(x, {**lp, "w_shared_gate": {"qw4": lp["w_shared_gate"]["qw"], "scale": lp["w_shared_gate"]["scale"]}},
                           granite_hybrid.shape_of(conf["hf"]))
    assert c.F32 == jnp.float32


# -- the configuration file ---------------------------------------------------------------


@pytest.mark.skipif(not CATALOG.exists(), reason="the catalog of public architectures is not on this machine")
def test_the_files_unreduced_keys_are_the_catalog_rows():
    from benchmark import serving
    from dynamo_tpu.models.config import GRANITE_4_H_SMALL_HF

    doc = json.loads((ROOT / "benchmark" / "configs" / f"{NAME}.json").read_text())
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines()) if r["source_url"] == doc["source"])
    entry = next(c for c in BENCH["configs"] if c["name"] == NAME)
    assert row["name"] == "granite-4.0-h-small" and row["config"] == GRANITE_4_H_SMALL_HF
    assert entry["reduced"] == ["num_hidden_layers"] == list(doc["reduced_why"]) and entry["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in entry["reduced"]:
            assert doc[key] != value
        else:
            assert doc[key] == value, key
    assert set(doc) - set(row["config"]) - serving.OWN_KEYS == {"num_hidden_layers_published", "pipeline_stages", "stage_rank"}
    assert (doc["num_hidden_layers"], doc["num_hidden_layers_published"], doc["pipeline_stages"], doc["stage_rank"]) == (10, 40, 4, 0)
    assert len(doc["layer_types"]) == 40  # whole: from_hf and the reference read the first ten
    assert {"reduced_why", "assumed", "deployment", "serve", "rehearsal", "reference"} <= set(doc)


def test_the_configuration_file_is_one_stage_and_its_bytes_are_the_trees():
    import jax

    from benchmark import serving, weights
    from dynamo_tpu.models import kda, llama
    from dynamo_tpu.models.config import ModelConfig

    conf = _conf()
    cfg = serving.model_config(conf)
    assert (cfg.num_layers, cfg.recurrent_layers, cfg.cache_layers, cfg.vocab_size, cfg.tie_embeddings) == (10, 9, 1, 100352, True)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.num_experts, cfg.num_experts_per_token) == (32, 8, 128, 72, 10)
    assert cfg == ModelConfig.from_hf(dict(conf["hf"]), name=conf["name"])  # the override restates what layer_types says
    assert conf["serve"]["model_overrides"] == {"group_attn_index": conf["hf"]["layer_types"].index("attention")}
    shapes = weights.tree_shapes(cfg)

    def nbytes(tree, name=None):
        if isinstance(tree, dict):
            return sum(nbytes(v, k) for k, v in tree.items())
        if name in weights.MATMUL_LEAVES:
            return tree.size + tree.size // tree.shape[-2] * 2  # int8 codes and a bf16 scale per output channel
        return tree.size * 2

    # ISSUE 49's arithmetic, counted again from the tree (the file's ``deployment``).
    layers = shapes["layers"]
    assert nbytes({k: layers[k] for k in ("w_gate", "w_up", "w_down")}) / 10 == pytest.approx(679.5 * MB + 0.8 * MB, rel=1e-4)
    assert nbytes({k: layers[k] for k in ("w_shared_gate", "w_shared_up", "w_shared_down")}) / 10 == pytest.approx(18.9 * MB, rel=1e-3)
    assert nbytes({"router": layers["router"]}) / 10 == pytest.approx(0.59 * MB, rel=1e-2)
    assert nbytes({k: shapes["ssm_layers"][k] for k in ("w_ssm_in", "w_ssm_out")}) / 9 == pytest.approx(204.5 * MB, rel=1e-3)
    assert nbytes(shapes["attn_layers"]) == pytest.approx(41.96 * MB, rel=1e-3)
    mamba_layer, attn_layer = nbytes(layers) / 10 + nbytes(shapes["ssm_layers"]) / 9, nbytes(layers) / 10 + nbytes(shapes["attn_layers"])
    assert mamba_layer == pytest.approx(904.4 * MB, rel=1e-3) and attn_layer == pytest.approx(741.7 * MB, rel=1e-3)
    assert nbytes(shapes["embed"], "embed") == pytest.approx(0.822e9, rel=1e-3) and "lm_head" not in shapes
    weights_bytes = nbytes(shapes)
    assert weights_bytes == pytest.approx(9.70e9, rel=2e-3)
    eng = conf["serve"]["engine"]
    state = sum(x.size * x.dtype.itemsize for x in jax.eval_shape(lambda: kda.init_state(cfg, eng["max_batch_size"] + 1)))
    # 4,194,304 B of state a slot a layer exactly (two heads of 64 side by side: no lane padding); the conv state's
    # 66 rows of lanes held in 72 (whole sublane tiles): 55,296 B where the channels alone are 50,688.
    assert state == 65 * cfg.state_bytes_per_slot() == 65 * 9 * (4_194_304 + 55_296) and state == pytest.approx(2.49e9, rel=2e-3)
    pool = sum(x.size * x.dtype.itemsize for x in jax.eval_shape(
        lambda: llama.init_kv_cache(cfg, eng["pool_tokens"] // eng["page_size"] + 1, eng["page_size"])))
    assert pool == 1025 * 128 * 1 * 4096 and pool == pytest.approx(0.54e9, rel=1e-2)
    held = weights_bytes + state + pool
    assert held == pytest.approx(12.73e9, rel=2e-3) and held > 0.25 * 16e9  # 74% of the chip's memory
    toy = serving.model_config(_conf(rehearsal=True))
    assert (toy.num_layers, toy.ssm_heads, toy.dtype, toy.period_attn_index) == (4, 8, "float32", 2)


# -- the needed bytes and operations, by hand -------------------------------------------


def test_decode_step_bytes_by_hand():
    """ISSUE 49's arithmetic at the published sizes: the 72 experts of ten
    layers 6.8 GB (46%; 640 choices a layer leave none untouched), the Mamba
    state 4.83 GB both ways (33% with the conv state), the mixers' bf16
    projections 1.84 GB, the tied head 0.82, the shared experts and routers
    0.20, K/V of the one attention layer at a mean context of 850 tokens 0.22,
    its projections 0.04: 14.8 GB, 18.1 ms at the HBM peak."""
    c, hf = _counts(), _conf()["hf"]
    got = c.decode_step(hf, rows=64, contexts_total=64 * 850)
    expert, shared, router = 3 * 4096 * 768, 3 * 4096 * 1536, 4096 * 72
    proj = 4096 * (8192 + 8448 + 128) + 8192 * 4096
    plain = 5 * 8448 + 3 * 128 + 8192
    attn = 4096 * (32 + 2 * 8) * 128 + 32 * 128 * 4096
    touched = 72 * (1 - (62 / 72) ** 64)
    assert got["experts_touched"] == pytest.approx(touched) and 71.99 < touched < 72
    assert got["experts_bytes"] == pytest.approx(10 * touched * expert) and got["experts_bytes"] == pytest.approx(6.79e9, rel=2e-3)
    assert got["mixer_proj_bytes"] == 2 * proj and 9 * 2 * proj == pytest.approx(1.84e9, rel=2e-3)
    assert got["mixer_block_bytes"] == 2 * (proj + plain) and got["attention_block_bytes"] == attn == pytest.approx(41.9 * MB, rel=2e-3)
    assert got["ffn_outside_experts_bytes"] == shared + 2 * (router + 2 * 4096)
    state = 9 * 64 * 2 * (128 * 128 * 64 * 4 + 3 * 8448 * 2)
    assert got["state_bytes"] == state and 9 * 64 * 2 * 4_194_304 == pytest.approx(4.83e9, rel=1e-3)
    cache = 1 * 64 * 850 * 4096
    assert got["cache_bytes"] == cache and cache == pytest.approx(0.22e9, rel=2e-2)
    head = 2 * 4096 * 100352
    assert got["head_bytes"] == head and head == pytest.approx(0.82e9, rel=3e-3)
    assert got["bytes"] == pytest.approx(10 * got["ffn_outside_experts_bytes"] + got["experts_bytes"] + 9 * got["mixer_block_bytes"]
                                         + attn + state + cache + head + 64 * 4096 * 2)
    assert 14.7e9 < got["bytes"] < 14.9e9 and 0.45 < got["experts_bytes"] / got["bytes"] < 0.47
    assert 0.32 < state / got["bytes"] < 0.34 and cache / got["bytes"] < 0.02  # attention is 2% of the step with its projections
    peaks = json.loads((ROOT / "benchmark" / "peaks.json").read_text())["TPU v5 lite"]
    least, bound = c.least_seconds(got, peaks)
    assert bound == "memory" and 17.9e-3 < least < 18.2e-3
    # bf16 weights double the int8 leaves and leave the mixers' projections and the tied head at 2 bytes; a count of
    # experts replaces the formula
    wide = c.decode_step(hf, rows=1, contexts_total=0, weight_bytes=2.0, experts_touched=7.0)
    assert wide["bytes"] == pytest.approx(10 * (2 * shared + 2 * (router + 2 * 4096)) + 10 * 7 * 2 * expert + 9 * 2 * (proj + plain)
                                          + 2 * attn + state / 64 + head + 4096 * 2)
    assert wide["mixer_proj_bytes"] == got["mixer_proj_bytes"] and wide["head_bytes"] == got["head_bytes"]


def test_state_attention_and_experts_step_bytes_by_hand():
    c, hf = _counts(), _conf()["hf"]
    got = c.state_step(hf, rows=64)
    assert got["state_bytes"] == 9 * 64 * 2 * 128 * 128 * 64 * 4 == 64 * 9 * 2 * 4_194_304
    assert got["bytes"] == got["state_bytes"] + 9 * 64 * (2 * 8192 + 2 * 128) * 4  # x in, y out; B and C of the one group
    assert got["conv_bytes"] == 9 * 64 * 2 * 3 * 8448 * 2 and got["flops"] == 9 * 64 * 6 * 128 * 128 * 64
    attn = c.attention_step(hf, kv_tokens_full=54400, kv_tokens_window=0, rows=64)
    assert attn["cache_bytes"] == 1 * 54400 * 2 * 8 * 128 * 2  # one layer of the ten attends
    assert attn["bytes"] == attn["cache_bytes"] + 1 * 64 * 2 * 32 * 128 * 2
    chunk = c.attention_step(hf, kv_tokens_full=2000, rows=1, new_tokens=64)
    assert chunk["flops"] == pytest.approx(1 * 4 * 32 * 128 * (64 * 2000 - 64 * 63 / 2))
    experts = c.experts_step(hf, experts_touched_total=720)
    assert experts["bytes"] == 720 * 3 * 4096 * 768 and experts["choices_per_token"] == 100


def test_the_counts_are_the_served_trees_leaves():
    """Every leaf of a served layer is in the count once: int8 leaves at a byte
    a code (scales apart, 0.1% of them), everything else at 2; the embedding,
    which is also the head, once at 2."""
    from benchmark import serving, weights

    cfg = serving.model_config(_conf())
    shapes = weights.tree_shapes(cfg)
    int8 = sum(v.size for sub in ("layers", "attn_layers") for k, v in shapes[sub].items() if k in weights.MATMUL_LEAVES)
    plain = sum(v.size for sub in ("layers", "ssm_layers", "attn_layers") for k, v in shapes[sub].items() if k not in weights.MATMUL_LEAVES)
    got = _counts().decode_step(_conf()["hf"], rows=0, contexts_total=0, experts_touched=72)
    assert got["bytes"] == int8 + 2 * plain + 2 * shapes["embed"].size
    assert sum(x.size for x in __import__("jax").tree.leaves(shapes)) == int8 + plain + shapes["embed"].size + shapes["norm_f"].size


def test_decode_roofline_reader_takes_these_counts():
    """``kernels.decode_roofline_pct`` (a file of the accepted benchmark) loads
    the configuration's counts by name; it hands no count of experts over, so
    the formula's 72 of 72 stand."""
    read = _reader("kernels.decode_roofline_pct")
    steps = [{"step_kind": "decode", "decode_rows": 64}] * 3
    ctx = {"conf": _conf(), "peaks": PEAKS, "trace": {}, "notes": {}, "window": {"steps": steps},
           "mean_context_tokens": 850.0, "step_programs": [{"span": "engine.decode", "dur": 24e6}] * 3}
    need = _counts().decode_step(_conf()["hf"], rows=64, contexts_total=64 * 850)["bytes"]
    assert read(ctx) == pytest.approx(100 * need / 819e9 / 24e-3) and 70 < read(ctx) < 80
    assert ctx["notes"]["decode_roofline"]["bound"] == "memory" and ctx["notes"]["decode_roofline"]["experts_touched"] == pytest.approx(72, abs=0.01)


# -- the accepted readers the cell joins ------------------------------------------------------


def _step(kind, seq, *, state_rows=64, traced=True, rows=64, chunk=0, kv=54400):
    rec = {"kind": "step", "seq": seq, "step_kind": kind, "decode_rows": rows, "chunk_rows": 1 if chunk else 0,
           "chunk_tokens": chunk, "traced": traced, "ann_ns": 1000 + seq, "t0_ns": 900 + seq, "overlap_mode": "overlapped",
           "kv_tokens_full": kv, "kv_tokens_window": 0, "moe_choices": 0, "moe_experts_touched": 0, "moe_path": "fused"}
    if state_rows is not None:
        rec.update(state_rows=state_rows, state_slots_live=64)
    return rec


from tests.benchmark.test_benchmark_ling import _trace  # noqa: E402  (a made-up device plane and host line from (annotation, start, dur, ops))


def _ctx(steps, trace=None, conf=None):
    return {"conf": conf or _conf(), "peaks": PEAKS, "window": {"steps": steps}, "trace": trace, "notes": {}}


def test_ssm_roofline_reader_takes_this_cells_kernel_events():
    """``kernels.ssm_decode_roofline_pct`` (the accepted benchmark's file)
    selects the kernel's events by the name it has in this model's programs
    too, nine a decode step (one a Mamba layer), and takes the bytes from this
    configuration's ``state_step``."""
    read = _reader("kernels.ssm_decode_roofline_pct")
    need = _counts().state_step(_conf()["hf"], rows=64)["bytes"]
    least_ns = need / 819e9 * 1e9

    def kernel(total_ns):
        return [("mamba_decode_step.8" if i < 5 else "mamba_decode_step.9", 100 + 2_000_000 * i, total_ns / 9) for i in range(9)]

    programs = [("engine.decode", 10_000, 24_000_000, kernel(least_ns * 1.25) + [("paged_decode_attention.3", 5, 20)]),
                ("engine.mixed", 30_000_000, 26_000_000, kernel(9e6)),
                ("engine.decode", 60_000_000, 24_000_000, kernel(least_ns * 1.3))]
    steps = [_step("decode", 1), _step("mixed", 2, chunk=64, state_rows=65), _step("decode", 3)]
    ctx = _ctx(steps, _trace(programs))
    assert read(ctx) == pytest.approx((80.0 + 100 / 1.3) / 2)
    note = ctx["notes"]["ssm_decode_roofline"]
    assert note["steps"] == 2 and note["events"] == 9 and note["needed_bytes"] == need
    assert read(_ctx([_step(s["step_kind"], s["seq"], state_rows=None) for s in steps], _trace(programs))) is None  # the parent's records


def test_recurrent_state_share_reader_takes_this_cells_records():
    """``engine.recurrent_state_bytes_pct`` (the accepted benchmark's file) on
    this model's records: a model that holds all its experts counts none of
    them on the device, so the reader hands the counts no count and the
    formula's 72 of 72 stand; one attention layer's key tokens are counted."""
    read = _reader("engine.recurrent_state_bytes_pct")
    c, hf = _counts(), _conf()["hf"]
    steps = [_step("decode", 1), _step("decode", 2), _step("mixed", 3, chunk=64, state_rows=65)]
    ctx = _ctx(steps)
    step, state = c.decode_step(hf, rows=64, contexts_total=54400), c.state_step(hf, rows=64)
    want = 100 * (state["state_bytes"] + state["conv_bytes"]) / step["bytes"]
    assert read(ctx) == pytest.approx(want) and 32 < want < 34
    assert ctx["notes"]["recurrent_state"]["steps"] == 2 and ctx["notes"]["recurrent_state"]["experts_touched_per_layer"] == 0
    assert read(_ctx([_step("decode", 1, state_rows=None)])) is None  # a program without state_rows (the parent's)
    assert _reader("kernels.moe_widened_steps")(_ctx(steps)) == 0.0  # the whole-expert path: fused in every step


# -- the cell --------------------------------------------------------------------------------


def test_the_cells_entries():
    cell = BENCH["workloads"][-1]  # the last entry: the contract's rehearsal runs it
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (CELL, NAME, "reason-saturated", 1) and len(cell["why"]) <= 200
    metrics = {m["name"]: m for m in BENCH["per_layer"]}
    for name, says in (("kernels.ssm_decode_roofline_pct", ("device_trace", "kernels", "itl_p50_ms", "%", "higher")),
                       ("engine.recurrent_state_bytes_pct", ("program_counter", "engine", "itl_p50_ms", "%", "higher"))):
        m = metrics[name]
        assert (m["source"], m["layer"], m["moves"], m["unit"], m["better"]) == says and m["workloads"][-1] == CELL
    # The lists that accepted tests pin by equality do not name the cell: it reports itl_p50_ms and setup_s only.
    e2e = {m["name"] for m in BENCH["end_to_end"] if CELL in m.get("workloads", [CELL])}
    assert e2e == {"itl_p50_ms", "setup_s"}
    assert not [m["name"] for m in BENCH["per_layer"] if CELL in m.get("workloads", []) and m["name"] not in (
        "kernels.ssm_decode_roofline_pct", "engine.recurrent_state_bytes_pct")]
    entry = BENCH["configs"][-1]
    assert (entry["name"], entry["file"]) == (NAME, f"benchmark/configs/{NAME}.json") and len(entry["why"]) <= 200
    assert entry["source"] == "https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/main/config.json"
    assert len(BENCH["configs"]) == 8 and len(BENCH["workloads"]) == 9


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
    assert len(plan["clients"]) == 64 and max(ids) < 100352 and max(ids) > 90000  # the whole vocabulary
