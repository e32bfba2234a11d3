"""Solar-Open2-250B's configuration in the benchmark (one chip's share of an
EP-8 stage): its plain reference against the program's whole forward at the
rehearsal's toy size, the configuration's keys against the catalog row and its
byte count against the served tree, its decode-step byte count against hand
arithmetic at the published sizes, the accepted readers that reach the cell
(on made-up records), and the cell's entries, **by name and membership only**:
no position in a list, no length and no whole list is asserted."""

import functools
import json
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

NAME = "solar-open2-250b-ep8-int8"
CELL = f"{NAME}.reason-saturated"
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CATALOG = pathlib.Path("/opt/skills/guides/model-configs/architectures.jsonl")
MB = 1e6
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
JOINED = ("kernels.kda_decode_roofline_pct", "engine.recurrent_state_bytes_pct", "engine.moe_held_choice_pct")


def _conf(rehearsal=False):
    from benchmark import serving

    return serving.load_config(ROOT / "benchmark" / "configs" / f"{NAME}.json", rehearsal=rehearsal)


def _counts():
    from benchmark import plugins

    return plugins.load("kernel_counts", "solar_open2_decode_step")


def _reader(name):
    from benchmark import plugins

    return plugins.load("layer_metrics", name).read


# -- the reference ------------------------------------------------------------------


@pytest.mark.parametrize("last", [47, 20, 3])
def test_reference_matches_program_forward_at_the_rehearsal_size(last):
    """One whole-sequence call of the program (48 tokens as one chunk from a
    fresh slot: the toy's one period of ``[GQA, KDA, KDA, KDA]``, pages in its
    one attention layer, slots in its three KDA layers, the second of two
    shares of 5 experts held) against the reference, which imports nothing of
    the program: the chunkwise form against the recurrence token by token, the
    low-rank pairs, the softplus decay and a write strength in (0, 2), paged
    GQA without RoPE under its per-channel gate against full causal attention.
    float32 both sides at ``highest`` precision: 1e-4 of the logit range."""
    import jax
    import jax.numpy as jnp

    from benchmark import serving, weights
    from benchmark.reference import solar_open2
    from dynamo_tpu.models import kda, llama

    conf = _conf(rehearsal=True)
    cfg = serving.model_config(conf)
    assert cfg.dtype == "float32" and (cfg.num_layers, cfg.recurrent_layers, cfg.cache_layers, cfg.period_attn_index) == (4, 3, 1, 0)
    assert (cfg.num_experts, cfg.routed_experts, cfg.moe_expert_first, cfg.kda_low_rank, cfg.kda_beta_scale) == (5, 10, 5, 16, 2.0)
    params = weights.make_weights(cfg, 2**31 + 11, quant="")
    toks = np.random.default_rng(3).integers(1, cfg.vocab_size, size=48)
    k, v = llama.init_kv_cache(cfg, 5, 16)
    state, conv = kda.init_state(cfg, 3)
    got = llama.forward(params, cfg, jnp.asarray(toks)[None], jnp.arange(48)[None], k, v, jnp.asarray([[1, 2, 3]]),
                        (16 + jnp.arange(48))[None], jnp.asarray([last]), recurrent=(state, conv, jnp.asarray([2])))[0][0]
    want = np.asarray(jax.jit(functools.partial(solar_open2.forward, hf=conf["hf"]))(params, tokens=jnp.asarray(toks)))
    assert np.abs(np.asarray(got) - want[last]).max() < 1e-4 * np.abs(want).max()


def test_reference_reads_the_served_leaves_and_the_general_rule_gives_each_new_leaf_what_the_file_says():
    import jax
    import jax.numpy as jnp

    from benchmark import serving, weights
    from benchmark.reference import solar_open2

    conf = _conf(rehearsal=True)
    cfg = serving.model_config(conf)
    params = weights.make_weights(cfg, 5, quant="int8")
    assert set(params) == {"embed", "norm_f", "lm_head", "layers", "kda_layers", "attn_layers"}
    # The two blocks' four projections, the experts, the shared expert and the head int8 under the names they have;
    # the low-rank pairs, the write strength, both gates and the router plain.
    assert {k for k, v in params["layers"].items() if isinstance(v, dict)} == {
        "w_gate", "w_up", "w_down", "w_shared_gate", "w_shared_up", "w_shared_down"}
    for block in ("kda_layers", "attn_layers"):
        assert {k for k, v in params[block].items() if isinstance(v, dict)} == {"wq", "wk", "wv", "wo"}
    new = {"w_decay_a", "w_decay_b", "w_out_gate_a", "w_out_gate_b", "w_beta", "w_out_gate", "router"}
    assert not new & weights.MATMUL_LEAVES and isinstance(params["lm_head"], dict)
    kda = params["kda_layers"]
    f32 = lambda name: np.asarray(kda[name], np.float32)  # noqa: E731
    assert kda["w_decay_a"].shape == (3, 64, 16) and kda["w_out_gate_b"].shape == (3, 16, 64) and params["attn_layers"]["w_out_gate"].shape == (1, 64, 64)
    assert not f32("dt_bias").any() and (f32("o_norm") == 1).all() and not np.asarray(params["layers"]["router_bias"]).any()
    assert 0.08 < f32("w_decay_a").std() < 0.18 and 0.18 < f32("w_decay_b").std() < 0.32  # normal x 64**-0.5, x 16**-0.5
    assert 0.3 < f32("conv_q").std() < 0.7 and 0.2 < f32("a_log").std() < 1.2  # normal x 4**-0.5; x KDA layers**-0.5 (3 here, 6 served)
    logits = jax.jit(functools.partial(solar_open2.forward, hf=conf["hf"]))(params, tokens=jnp.arange(1, 17))
    assert logits.shape == (16, cfg.vocab_size) and bool(jnp.isfinite(logits).all())
    # With these weights the decay's input is about N(0, 1): the log-decay -exp(a_log) softplus(.) is about -0.8 a token.
    h = jax.random.normal(jax.random.PRNGKey(1), (4096, 64), jnp.float32)
    a = (h @ f32("w_decay_a")[0]) @ f32("w_decay_b")[0]
    assert 0.8 < float(a.std()) < 1.25 and -1.1 < float(-jax.nn.softplus(a).mean()) < -0.6
    with pytest.raises(ValueError, match="int8 or plain leaves only"):
        lp = jax.tree.map(lambda x: x[0], params["layers"])
        solar_open2.shared_expert(h[:5], {**lp, "w_shared_gate": {"qw4": lp["w_shared_gate"]["qw"], "scale": lp["w_shared_gate"]["scale"]}})


# -- the configuration file ---------------------------------------------------------------


@pytest.mark.skipif(not CATALOG.exists(), reason="the catalog of public architectures is not on this machine")
def test_the_files_unreduced_keys_are_the_catalog_rows():
    from benchmark import serving
    from dynamo_tpu.models.config import SOLAR_OPEN2_250B_HF

    doc = json.loads((ROOT / "benchmark" / "configs" / f"{NAME}.json").read_text())
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines()) if r["source_url"] == doc["source"])
    entry = next(c for c in BENCH["configs"] if c["name"] == NAME)
    assert row["name"] == "Solar-Open2-250B" and row["config"] == SOLAR_OPEN2_250B_HF
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts"] == list(doc["reduced_why"]) and entry["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in entry["reduced"]:
            assert doc[key] != value
        else:
            assert doc[key] == value, key
    assert set(doc) - set(row["config"]) - serving.OWN_KEYS == {"n_routed_experts_published", "expert_share_rank", "expert_share_chips"}
    assert (doc["num_hidden_layers"], doc["n_routed_experts"], doc["n_routed_experts_published"], doc["expert_share_chips"],
            doc["expert_share_rank"]) == (8, 40, 320, 8, 0)
    assert doc["gqa_layers"] == list(range(0, 48, 4)) and doc["linear_attn_config"] == row["config"]["linear_attn_config"]  # whole
    assert {"reduced_why", "assumed", "deployment", "serve", "rehearsal", "reference"} <= set(doc)
    assert {"decay", "rank", "gates", "write_strength", "router", "shared_expert", "attention", "state"} <= set(doc["assumed"])
    assert set(doc["serve"]["pinned_why"]) >= set(doc["serve"]["engine"]) - {"max_prefill_tokens"} | {"logprob_rel_limit", "model_overrides"}


def test_the_configuration_file_is_one_chips_share_and_its_bytes_are_the_trees():
    import jax

    from benchmark import serving, weights
    from dynamo_tpu.models import kda, llama
    from dynamo_tpu.models.config import ModelConfig

    conf = _conf()
    cfg = serving.model_config(conf)
    assert (cfg.num_layers, cfg.recurrent_layers, cfg.cache_layers, cfg.period_attn_index, cfg.vocab_size) == (8, 6, 2, 0, 196608)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.num_experts, cfg.routed_experts, cfg.num_experts_per_token) == (
        64, 8, 128, 40, 320, 8)
    # The overrides restate what from_hf reads: the share a second time, and a field the parent's ModelConfig lacks.
    assert cfg == ModelConfig.from_hf(dict(conf["hf"]), name=conf["name"])
    assert conf["serve"]["model_overrides"] == {"moe_experts_total": 320, "moe_expert_first": 0, "kda_low_rank": 128}
    assert conf["reference"] == "solar_open2" and conf["serve"]["kernel_counts"] == "solar_open2_decode_step"
    shapes = weights.tree_shapes(cfg)

    def nbytes(tree, name=None):
        if isinstance(tree, dict):
            return sum(nbytes(v, k) for k, v in tree.items())
        if name in weights.MATMUL_LEAVES:
            return tree.size + tree.size // tree.shape[-2] * 2  # int8 codes and a bf16 scale per output channel
        return tree.size * (4 if name == "router_bias" else 2)

    # ISSUE 53's arithmetic, counted again from the tree (the file's ``deployment``).
    layers, blocks, attn = shapes["layers"], shapes["kda_layers"], shapes["attn_layers"]
    assert nbytes({k: layers[k] for k in ("w_gate", "w_up", "w_down")}) / 8 == pytest.approx(629.68 * MB, rel=1e-4)
    assert nbytes({k: layers[k] for k in ("w_shared_gate", "w_shared_up", "w_shared_down")}) / 8 == pytest.approx(15.74 * MB, rel=1e-3)
    assert nbytes({"router": layers["router"]}) / 8 == pytest.approx(2.62 * MB, rel=1e-2) and nbytes(layers) / 8 == pytest.approx(648.06 * MB, rel=1e-4)
    assert nbytes({k: blocks[k] for k in ("wq", "wk", "wv", "wo")}) / 6 == pytest.approx(134.28 * MB, rel=1e-4)
    assert nbytes(blocks) / 6 == pytest.approx(141.30 * MB, rel=1e-4)  # the low-rank pairs, w_beta, filters and constants: 7.03 MB bf16
    assert nbytes({k: attn[k] for k in ("wq", "wk", "wv", "wo")}) / 2 == pytest.approx(75.53 * MB, rel=1e-4)
    assert nbytes({"w_out_gate": attn["w_out_gate"]}) / 2 == 4096 * 8192 * 2 and nbytes(attn) / 2 == pytest.approx(142.64 * MB, rel=1e-4)
    assert nbytes(layers) + nbytes(blocks) + nbytes(attn) == pytest.approx(6.32e9, rel=1e-3)
    assert nbytes(shapes["embed"], "embed") == pytest.approx(1.611e9, rel=1e-3) and nbytes(shapes["lm_head"], "lm_head") == pytest.approx(0.806e9, rel=1e-3)
    weights_bytes = nbytes(shapes)
    assert weights_bytes == pytest.approx(8.73e9, rel=1e-3)
    eng = conf["serve"]["engine"]
    state = sum(x.size * x.dtype.itemsize for x in jax.eval_shape(lambda: kda.init_state(cfg, eng["max_batch_size"] + 1)))
    assert state == 65 * cfg.state_bytes_per_slot() == 65 * 6 * (4_194_304 + 147_456) and state == pytest.approx(1.69e9, rel=2e-3)
    pool = sum(x.size * x.dtype.itemsize for x in jax.eval_shape(
        lambda: llama.init_kv_cache(cfg, eng["pool_tokens"] // eng["page_size"] + 1, eng["page_size"])))
    assert pool == 2 * 2 * 1025 * 128 * 1024 * 2 and pool == pytest.approx(1.07e9, rel=1e-2) and cfg.kv_bytes_per_token() == 8192
    held = weights_bytes + state + pool
    assert held == pytest.approx(11.5e9, rel=2e-3) and held > 10e9 and held > 0.25 * 16e9  # 72% of the chip's memory
    toy = serving.model_config(_conf(rehearsal=True))
    assert (toy.num_layers, toy.num_heads, toy.dtype, toy.period_attn_index, toy.kda_low_rank) == (4, 4, "float32", 0, 16)


def test_a_tree_without_the_new_field_refuses_the_file_by_the_fields_name():
    """What the parent commit does with this file: ``model_overrides`` names
    ``kda_low_rank``, which its ``ModelConfig`` does not have, so
    ``dataclasses.replace`` stops by the field's name (and before it
    ``from_hf``'s general path, in this tree, a config that carries
    ``linear_attn_config`` and reaches no branch)."""
    import dataclasses

    from dynamo_tpu.models.config import PRESETS, ModelConfig

    old = dataclasses.make_dataclass("Old", [(f.name, f.type, f) for f in dataclasses.fields(ModelConfig)
                                             if f.name not in ("kda_low_rank", "kda_decay", "kda_beta_scale", "attn_out_gate")], frozen=True)
    kept = {f.name: getattr(PRESETS["test-tiny"], f.name) for f in dataclasses.fields(old)}
    with pytest.raises(TypeError, match="kda_low_rank"):
        dataclasses.replace(old(**kept), **_conf()["serve"]["model_overrides"])
    with pytest.raises(ValueError, match="states linear_attn_config: linear-attention layers that no branch"):
        ModelConfig.from_hf({**_conf()["hf"], "model_type": "some_other_hybrid"}, name="t")


# -- the needed bytes and operations, by hand -------------------------------------------


def test_decode_step_bytes_by_hand():
    """ISSUE 53's arithmetic at the published sizes: the recurrent state read
    and written 6 x 64 x 4.19 MB x 2 = 3.22 GB (a third with the conv state),
    the touched experts (32.1 of 40 a layer) 4.04 GB (two fifths), the blocks'
    and FFNs' other weights 1.28 GB, the head 0.81, K/V of the two attention
    layers at a mean context of 850 tokens 0.45: 9.9 GB, 12.1 ms at the HBM peak."""
    c, hf = _counts(), _conf()["hf"]
    got = c.decode_step(hf, rows=64, contexts_total=64 * 850)
    expert, router = 3 * 4096 * 1280, 4096 * 320
    kda_matmul, attn_matmul = 4 * 4096 * 8192, 2 * 4096 * 8192 + 2 * 4096 * 1024
    kda_plain = 2 * (4096 * 128 + 128 * 8192) + 4096 * 64 + 3 * 4 * 8192 + 64 + 8192 + 128
    touched = 40 * (1 - (312 / 320) ** 64)
    assert got["experts_touched"] == pytest.approx(touched) and 32.0 < touched < 32.2
    assert got["experts_bytes"] == pytest.approx(8 * touched * expert) and got["experts_bytes"] == pytest.approx(4.04e9, rel=2e-3)
    assert got["kda_block_bytes"] == kda_matmul + 2 * kda_plain and got["attention_block_bytes"] == attn_matmul + 2 * 4096 * 8192
    assert got["ffn_outside_experts_bytes"] == expert + 2 * (router + 2 * 4096)
    state = 6 * 64 * 2 * (64 * 128 * 128 * 4 + 3 * 24576 * 2)
    assert got["state_bytes"] == state and 6 * 64 * 2 * 4_194_304 == pytest.approx(3.22e9, rel=1e-3)
    cache = 2 * 64 * 850 * 4096
    assert got["cache_bytes"] == cache and cache == pytest.approx(0.45e9, rel=2e-2)
    head = 4096 * 196608
    assert got["head_bytes"] == head and head == pytest.approx(0.805e9, rel=1e-3)
    other = 6 * got["kda_block_bytes"] + 2 * got["attention_block_bytes"] + 8 * got["ffn_outside_experts_bytes"]
    assert other == pytest.approx(1.28e9, rel=1e-2)
    assert got["bytes"] == pytest.approx(other + got["experts_bytes"] + state + cache + head + 64 * 4096 * 2)
    assert 9.85e9 < got["bytes"] < 9.95e9 and 0.40 < got["experts_bytes"] / got["bytes"] < 0.42
    assert 0.33 < state / got["bytes"] < 0.345 and cache / got["bytes"] < 0.05
    peaks = json.loads((ROOT / "benchmark" / "peaks.json").read_text())["TPU v5 lite"]
    least, bound = c.least_seconds(got, peaks)
    assert bound == "memory" and 12.0e-3 < least < 12.2e-3
    # bf16 weights double the int8 leaves and leave the gate, the pairs and the router at 2 bytes; a count of experts
    # replaces the formula; a file without the gate counts none
    wide = c.decode_step(hf, rows=1, contexts_total=0, weight_bytes=2.0, experts_touched=7.0)
    assert wide["bytes"] == pytest.approx(6 * (2 * kda_matmul + 2 * kda_plain) + 2 * (2 * attn_matmul + 2 * 4096 * 8192)
                                          + 8 * (2 * expert + 2 * (router + 2 * 4096)) + 8 * 7 * 2 * expert + state / 64 + 2 * head + 4096 * 2)
    assert c.decode_step({**hf, "use_gqa_gate": False}, rows=64, contexts_total=0)["attention_block_bytes"] == attn_matmul


def test_state_attention_and_experts_step_bytes_by_hand():
    c, hf = _counts(), _conf()["hf"]
    got = c.state_step(hf, rows=64)
    assert got["state_bytes"] == 6 * 64 * 2 * 64 * 128 * 128 * 4 == 64 * 6 * 2 * 4_194_304
    assert got["bytes"] == got["state_bytes"] + 6 * 64 * 5 * 8192 * 4  # q, k, v and the decay in, the output out
    assert got["conv_bytes"] == 6 * 64 * 2 * 3 * 24576 * 2 and got["flops"] == 6 * 64 * 8 * 64 * 128 * 128
    attn = c.attention_step(hf, kv_tokens_full=54400, kv_tokens_window=0, rows=64)
    assert attn["cache_bytes"] == 2 * 54400 * 2 * 8 * 128 * 2  # two layers of the eight attend
    assert attn["bytes"] == attn["cache_bytes"] + 2 * 64 * 2 * 64 * 128 * 2
    chunk = c.attention_step(hf, kv_tokens_full=2000, rows=1, new_tokens=64)
    assert chunk["flops"] == pytest.approx(2 * 4 * 64 * 128 * (64 * 2000 - 64 * 63 / 2))
    experts = c.experts_step(hf, experts_touched_total=256)
    assert experts["bytes"] == 256 * 3 * 4096 * 1280 and experts["choices_per_token"] == 64
    assert c.expected_experts_touched(40, 320, 8, 64) == pytest.approx(32.09, abs=0.01)


def test_the_counts_are_the_served_trees_leaves():
    """Every leaf of a served layer is in the count once: int8 leaves at a byte
    a code (scales apart, 0.1% of them), everything else at 2 (the selection
    bias, float32 and no parameter, left out); the head once."""
    import jax

    from benchmark import serving, weights

    cfg = serving.model_config(_conf())
    shapes = weights.tree_shapes(cfg)
    subs = ("layers", "kda_layers", "attn_layers")
    int8 = sum(v.size for sub in subs for k, v in shapes[sub].items() if k in weights.MATMUL_LEAVES)
    plain = sum(v.size for sub in subs for k, v in shapes[sub].items() if k not in weights.MATMUL_LEAVES and k != "router_bias")
    got = _counts().decode_step(_conf()["hf"], rows=0, contexts_total=0, experts_touched=40)
    assert got["bytes"] == int8 + 2 * plain + shapes["lm_head"].size
    assert sum(x.size for x in jax.tree.leaves(shapes)) == (int8 + plain + shapes["layers"]["router_bias"].size + shapes["lm_head"].size
                                                            + shapes["embed"].size + shapes["norm_f"].size)


def test_decode_roofline_reader_takes_these_counts():
    """``kernels.decode_roofline_pct`` (a file of the accepted benchmark) loads
    the configuration's counts by name; it hands no count of experts over, so
    the formula's 32.1 of 40 stand."""
    read = _reader("kernels.decode_roofline_pct")
    steps = [{"step_kind": "decode", "decode_rows": 64}] * 3
    ctx = {"conf": _conf(), "peaks": PEAKS, "trace": {}, "notes": {}, "window": {"steps": steps},
           "mean_context_tokens": 850.0, "step_programs": [{"span": "engine.decode", "dur": 16e6}] * 3}
    need = _counts().decode_step(_conf()["hf"], rows=64, contexts_total=64 * 850)["bytes"]
    assert read(ctx) == pytest.approx(100 * need / 819e9 / 16e-3) and 70 < read(ctx) < 80
    assert ctx["notes"]["decode_roofline"]["bound"] == "memory" and ctx["notes"]["decode_roofline"]["experts_touched"] == pytest.approx(32.09, abs=0.01)


# -- the accepted readers the cell joins ------------------------------------------------------


def _step(kind, seq, *, state_rows=64, traced=True, rows=64, chunk=0, kv=54400, touched=256):
    tokens = rows + chunk
    rec = {"kind": "step", "seq": seq, "step_kind": kind, "decode_rows": rows, "chunk_rows": 1 if chunk else 0,
           "chunk_tokens": chunk, "traced": traced, "ann_ns": 1000 + seq, "t0_ns": 900 + seq, "overlap_mode": "overlapped",
           "kv_tokens_full": kv, "kv_tokens_window": 0, "moe_choices": tokens * 64, "moe_choices_zero": 0,
           "moe_choices_held": tokens * 8, "moe_experts_touched": touched, "moe_extra_passes": 0, "moe_path": "fused"}
    if state_rows is not None:
        rec.update(state_rows=state_rows, state_slots_live=64)
    return rec


from tests.benchmark.test_benchmark_ling import _trace  # noqa: E402  (a made-up device plane and host line from (annotation, start, dur, ops))


def _ctx(steps, trace=None, conf=None):
    return {"conf": conf or _conf(), "peaks": PEAKS, "window": {"steps": steps}, "trace": trace, "notes": {}}


def test_kda_roofline_reader_takes_this_cells_kernel_events():
    """``kernels.kda_decode_roofline_pct`` (the accepted benchmark's file)
    selects the kernel's events by the name it has in this model's programs
    too, six a decode step (one a KDA layer, in the one loop behind the layer
    that attends), and takes the bytes from this configuration's ``state_step``
    at 64 heads."""
    read = _reader("kernels.kda_decode_roofline_pct")
    need = _counts().state_step(_conf()["hf"], rows=64)["bytes"]
    least_ns = need / 819e9 * 1e9

    def kernel(total_ns):
        return [("kda_decode_step.7", 100 + 2_000_000 * i, total_ns / 6) for i in range(6)]

    programs = [("engine.decode", 10_000, 16_000_000, kernel(least_ns * 1.25) + [("paged_decode_attention.3", 5, 20)]),
                ("engine.mixed", 30_000_000, 20_000_000, kernel(9e6)),
                ("engine.decode", 60_000_000, 16_000_000, kernel(least_ns * 1.3))]
    steps = [_step("decode", 1), _step("mixed", 2, chunk=64, state_rows=65), _step("decode", 3)]
    ctx = _ctx(steps, _trace(programs))
    assert read(ctx) == pytest.approx((80.0 + 100 / 1.3) / 2)
    note = ctx["notes"]["kda_decode_roofline"]
    assert note["steps"] == 2 and note["events"] == 6 and note["needed_bytes"] == need and need == pytest.approx(3.28e9, rel=1e-2)
    assert read(_ctx([_step(s["step_kind"], s["seq"], state_rows=None) for s in steps], _trace(programs))) is None  # the parent's records


def test_recurrent_state_and_held_choice_readers_take_this_cells_records():
    """``engine.recurrent_state_bytes_pct`` and ``engine.moe_held_choice_pct``
    (the accepted benchmark's files) on this model's records: the held experts
    some row chose are counted on the device (32 of 40 a layer), the key tokens
    of the two layers that attend on the host; 8 of a token's 64 choices land
    on the 40 of 320 experts held here."""
    read = _reader("engine.recurrent_state_bytes_pct")
    c, hf = _counts(), _conf()["hf"]
    steps = [_step("decode", 1), _step("decode", 2), _step("mixed", 3, chunk=64, state_rows=65)]
    ctx = _ctx(steps)
    step, state = c.decode_step(hf, rows=64, contexts_total=54400, experts_touched=32.0), c.state_step(hf, rows=64)
    want = 100 * (state["state_bytes"] + state["conv_bytes"]) / step["bytes"]
    assert read(ctx) == pytest.approx(want) and 25 < want < 45 and 33 < want < 35
    assert ctx["notes"]["recurrent_state"]["steps"] == 2 and ctx["notes"]["recurrent_state"]["experts_touched_per_layer"] == 32.0
    assert read(_ctx([_step("decode", 1, state_rows=None)])) is None  # a program without state_rows (the parent's)
    held = _ctx(steps)
    assert _reader("engine.moe_held_choice_pct")(held) == pytest.approx(12.5) and held["notes"]["moe_held"]["experts_touched_per_layer"] == 32.0
    assert _reader("kernels.moe_widened_steps")(_ctx(steps)) == 0.0  # the held experts through the grouped int8 kernel in every step


# -- the cell --------------------------------------------------------------------------------


def test_the_cells_entries():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (NAME, "reason-saturated", 1) and len(cell["why"]) <= 200
    metrics = {m["name"]: m for m in BENCH["per_layer"]}
    for name, says in (("kernels.kda_decode_roofline_pct", ("device_trace", "kernels", "itl_p50_ms", "%", "higher")),
                       ("engine.recurrent_state_bytes_pct", ("program_counter", "engine", "itl_p50_ms", "%", "higher")),
                       ("engine.moe_held_choice_pct", ("program_counter", "engine", "itl_p50_ms", "%", "lower"))):
        m = metrics[name]
        assert (m["source"], m["layer"], m["moves"], m["unit"], m["better"]) == says and CELL in m["workloads"]
    # The lists that accepted tests pin by equality do not name the cell: it reports itl_p50_ms and setup_s only.
    e2e = {m["name"] for m in BENCH["end_to_end"] if CELL in m.get("workloads", [CELL])}
    assert e2e == {"itl_p50_ms", "setup_s"}
    assert {m["name"] for m in BENCH["per_layer"] if CELL in m.get("workloads", [])} == set(JOINED)
    # ... and the metrics without a list read it as they read every cell that reports itl_p50_ms.
    from benchmark import run as bench_run

    reported = {m["name"] for m in bench_run.cell_metrics(BENCH, "per_layer", cell)}
    assert set(JOINED) | {"kernels.decode_roofline_pct", "model.decode_step_dev_ms", "device.idle_pct"} <= reported
    entry = next(c for c in BENCH["configs"] if c["name"] == NAME)
    assert entry["file"] == f"benchmark/configs/{NAME}.json" and len(entry["why"]) <= 200
    assert entry["source"] == "https://huggingface.co/upstage/Solar-Open2-250B/blob/main/config.json"
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts"]
    assert (ROOT / "benchmark" / "cells" / f"{CELL}.json").is_file() and (ROOT / "benchmark" / "reference" / "solar_open2.py").is_file()


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
    assert len(plan["clients"]) == 64 and max(ids) < 196608 and max(ids) > 180000  # the whole vocabulary
