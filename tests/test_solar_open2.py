"""Solar-Open2 (ISSUE 53; ``model_type`` ``solar_open2``): KDA layers in Kimi
Linear's own form (a softplus decay and a per-channel output gate through
low-rank pairs, a write strength in (0, 2)) three to one round a gated GQA
layer without RoPE that *opens* its period, sigmoid-routed experts of which a
share that is no power of two is held, beside a shared expert in every layer.
The config is read by its own keys and refuses by name what is not served; the
program agrees with the benchmark's plain reference
(``benchmark/reference/solar_open2.py``) on a whole prefill, on chunked
prefill then decode through pages and slots, on a mixed step with a padding
row, and at bf16 + int8 within a tolerance the weights one precision down
fail; the toy is served over ``/v1/completions`` through ``launch``; the eight
shares add up to the uncut layer. The slots themselves (admission, finish,
preemption, reuse, the refusals by name) are ``tests/test_hybrid_kda.py``'s
cases over the recurrent kinds, run from this file for this one (``kda-gqa``:
that file is the suite's longest and a file is one worker's); the kernels' 64-head shapes
``tests/test_pallas_kda.py``'s and ``tests/test_pallas_conv.py``'s."""

import dataclasses
import functools
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmark.reference import solar_open2 as ref  # noqa: E402
from dynamo_tpu.engine.runner import SPLIT, ModelRunner  # noqa: E402
from dynamo_tpu.models import kda, llama  # noqa: E402
from dynamo_tpu.models.config import PRESETS, SOLAR_OPEN2_250B_HF, TINY_SOLAR_OPEN2_HF, ModelConfig  # noqa: E402
from tests import test_hybrid_kda as slots  # noqa: E402  (the slot cases over the recurrent kinds)
from tests.test_mixed_attention import _distance  # noqa: E402  (max |served - reference| logprob over the largest |logit|)
from tests.test_shortcut_moe import _served_logprobs  # noqa: E402

CATALOG = pathlib.Path("/opt/skills/guides/model-configs/architectures.jsonl")
SHARE = {"num_hidden_layers": 8, "n_routed_experts": 40, "n_routed_experts_published": 320, "expert_share_rank": 0,
         "expert_share_chips": 8}
TOL = 1e-4
#: ``tests/test_hybrid_kda.py``'s weights for a KDA toy (a selection bias; in half the heads a slow decay, here through
#: the low-rank pair; write strengths towards 0 and 2), on a seed of this file's own.
_weights = functools.partial(slots._weights, seed=2**31 + 53)


def _toy(**edit) -> ModelConfig:
    return dataclasses.replace(ModelConfig.from_hf({**TINY_SOLAR_OPEN2_HF, **edit}, name="toy-solar"), dtype="float32")


#: This model as a fourth recurrent kind of the slot cases (registered in this process only: that file's own
#: parametrisation is made when it is imported and holds its three).
slots.KINDS["kda-gqa"] = dict(toy=_toy, weights=_weights, hf=TINY_SOLAR_OPEN2_HF, ref=ref, module=kda, layer="kda_attention")


def _reference(params, sequence, hf=TINY_SOLAR_OPEN2_HF):
    return np.asarray(jax.jit(functools.partial(ref.forward, hf=hf))(params, tokens=jnp.asarray(sequence)))


def _prefill_last_logits(params, cfg, toks):
    """``llama.forward`` over the whole sequence from position 0 in one call (a state of the call's own): the last token's logits."""
    n = len(toks)
    kc, vc = llama.init_kv_cache(cfg, n // 4 + 2, 4)
    out = llama.forward(params, cfg, jnp.asarray(toks)[None], jnp.arange(n)[None], kc, vc, jnp.arange(1, n // 4 + 1)[None],
                        (4 + jnp.arange(n))[None], jnp.asarray([n - 1]), attn_impl="reference")[0]
    return np.asarray(out, np.float32)[0]


# -- from_hf --------------------------------------------------------------------------


def test_from_hf_reads_the_published_keys():
    cfg = ModelConfig.from_hf(SOLAR_OPEN2_250B_HF, name="solar-open2-250b")
    assert (cfg.num_layers, cfg.recurrent_layers, cfg.cache_layers, cfg.layer_group_size, cfg.period_attn_index) == (48, 36, 12, 4, 0)
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.vocab_size) == (4096, 64, 8, 128, 196608)
    assert (cfg.kda_decay, cfg.kda_beta_scale, cfg.kda_low_rank, cfg.kda_conv_size, cfg.attn_out_gate) == ("softplus", 2.0, 128, 4, True)
    assert cfg.attn_type == "gqa" and cfg.rope_scaling == {"rope_type": "nope"} and not cfg.qk_norm and not cfg.ssm_heads
    assert (cfg.num_experts, cfg.routed_experts, cfg.num_experts_per_token, cfg.moe_intermediate_size, cfg.shared_expert_size) == (
        320, 320, 8, 1280, 1280)
    assert (cfg.moe_scoring, cfg.moe_router_bias, cfg.moe_norm_topk, cfg.moe_routed_scaling, cfg.moe_n_group, cfg.first_k_dense) == (
        "sigmoid", True, True, 1.0, 0, 0)
    assert not cfg.moe_held_share and not cfg.tie_embeddings and cfg.rms_eps == 1e-5 and cfg.max_position == 1048576
    # A KDA layer holds a slot and no pages: 64 heads of 128 x 128 float32 (4,194,304 B) and the last 3 inputs of
    # 24,576 conv channels, 192 rows of lanes (147,456 B in bf16); a GQA layer pages and no slot: 4,096 B a token.
    assert cfg.state_shapes() == ((64, 128, 128), (3, 192, 128))
    assert cfg.state_bytes_per_slot() == 36 * (4_194_304 + 147_456) and cfg.kv_bytes_per_token() == 12 * 4096
    # ISSUE 53's count: a routed expert 15.73 M, a KDA block about 137.8 M, a GQA block 109.1 M with its gate.
    expert = 3 * 4096 * 1280
    block = 4 * 4096 * 8192 + 2 * (4096 * 128 + 128 * 8192) + 4096 * 64 + 3 * 4 * 8192 + 64 + 8192 + 128
    attn = 2 * 4096 * 8192 + 2 * 4096 * 1024 + 4096 * 8192
    ffn = 320 * expert + 4096 * 320 + expert
    assert expert == pytest.approx(15.73e6, rel=1e-3) and block == pytest.approx(137.8e6, rel=2e-3) and attn == pytest.approx(109.1e6, rel=1e-3)
    assert cfg.param_count() == 48 * (ffn + 2 * 4096) + 36 * block + 12 * attn + 2 * 196608 * 4096 + 4096
    assert cfg.param_count() == pytest.approx(250e9, rel=2e-3)  # the published 250B
    active = cfg.param_count() - 48 * 312 * expert
    assert active == pytest.approx(15e9, rel=0.03)  # and 15B active: the shared expert is moe_intermediate_size wide
    # One chip's share of an EP-8 stage: 8 layers, 40 of 320 experts; gqa_layers stays whole.
    share = ModelConfig.from_hf({**SOLAR_OPEN2_250B_HF, **SHARE}, name="share")
    assert (share.num_layers, share.recurrent_layers, share.cache_layers, share.period_attn_index) == (8, 6, 2, 0)
    assert (share.num_experts, share.routed_experts, share.router_outputs, share.moe_expert_first, share.moe_held_share) == (40, 320, 320, 0, True)
    assert ModelConfig.from_hf({**SOLAR_OPEN2_250B_HF, **SHARE, "expert_share_rank": 7}, name="s").moe_expert_first == 280
    plain = ModelConfig.from_hf({**SOLAR_OPEN2_250B_HF, "use_gqa_gate": False, "kda_allow_neg_eigval": False}, name="plain")
    assert (plain.attn_out_gate, plain.kda_beta_scale) == (False, 1.0)
    tiny = PRESETS["test-tiny-solar-open2"]
    assert tiny == dataclasses.replace(ModelConfig.from_hf(TINY_SOLAR_OPEN2_HF, name="test-tiny-solar-open2"), dtype="float32")
    assert (tiny.num_layers, tiny.recurrent_layers, tiny.cache_layers, tiny.num_experts, tiny.routed_experts, tiny.moe_expert_first) == (
        8, 6, 2, 5, 10, 5)
    shapes = jax.eval_shape(lambda: llama.init_params(tiny, 0))
    assert set(shapes) == {"embed", "norm_f", "lm_head", "layers", "kda_layers", "attn_layers"}
    assert "wq" not in shapes["layers"] and shapes["layers"]["router"].shape == (8, 64, 10) and shapes["layers"]["w_gate"].shape[:2] == (8, 5)
    assert set(shapes["attn_layers"]) == {"wq", "wk", "wv", "wo", "w_out_gate"} and shapes["attn_layers"]["w_out_gate"].shape == (2, 64, 64)
    assert {"w_decay_a", "w_decay_b", "w_out_gate_a", "w_out_gate_b"} <= set(shapes["kda_layers"]) and not {
        "w_decay", "w_out_gate"} & set(shapes["kda_layers"])
    assert shapes["kda_layers"]["w_decay_a"].shape == (6, 64, 16) and shapes["kda_layers"]["w_out_gate_b"].shape == (6, 16, 64)
    assert tiny.param_count() == sum(x.size for x in jax.tree.leaves(shapes)) - 8 * 10  # the bias is no parameter
    # Ling's form is the default: its leaves and its count are what they were.
    ling = jax.eval_shape(lambda: llama.init_params(PRESETS["test-tiny-hybrid"], 0))
    assert {"w_decay", "w_out_gate"} <= set(ling["kda_layers"]) and "w_decay_a" not in ling["kda_layers"]


@pytest.mark.parametrize("edit, says", [
    ({"kda_use_full_proj": True}, "kda_use_full_proj True is not served for model_type 'solar_open2': only False"),
    ({"use_rope": True}, "use_rope True is not served"),
    ({"first_k_dense_replace": 1}, "first_k_dense_replace 1 is not served"),
    ({"gqa_layers": list(range(3, 48, 4))}, r"gqa_layers \[3, 7, 11, 15, 19, 23\] with gqa_interval 3 is not served: only range"),
    ({"gqa_layers": [0, 4, 8, 13] + list(range(16, 48, 4))}, r"gqa_layers with 'attention' at \[0, 4, 8, 13, 16, 20\] is not served"),
    ({"gqa_layers": []}, r"gqa_layers with 'attention' at \[\] is not served"),
    ({"gqa_interval": 5}, r"gqa_layers \[0, 4, 8, 12, 16, 20\] with gqa_interval 5 is not served"),
    ({"num_hidden_layers": 10}, r"num_hidden_layers 10 is not whole periods of 4 layers \(gqa_layers\)"),
    ({"linear_attn_config": {**SOLAR_OPEN2_250B_HF["linear_attn_config"], "num_kv_heads": 8}},
     "linear_attn_config.num_kv_heads 8 is not served for model_type 'solar_open2': only None"),
    ({"linear_attn_config": {**SOLAR_OPEN2_250B_HF["linear_attn_config"], "num_heads": 32}},
     "linear_attn_config num_heads 32 x head_dim 128 against num_attention_heads 64 x head_dim 128 is not served"),
    ({"scoring_func": "softmax"}, "scoring_func 'softmax' is not served"),
    ({"rope_scaling": {"rope_type": "yarn", "factor": 4.0}}, "rope_scaling {.*} is not served"),
    ({"n_routed_experts": 40, "n_routed_experts_published": 320, "expert_share_rank": 8}, r"experts \[320, 360\) lie outside the 320"),
    ({"model_type": "solar_open3"}, "model_type 'solar_open3' states linear_attn_config: linear-attention layers that no branch"),
], ids=["full-proj", "rope", "dense-layer", "attends-last", "ragged-periods", "no-attention", "interval", "broken-period", "kv-heads",
        "head-counts", "scores", "rope-scaling", "rank", "no-branch"])
def test_from_hf_refuses_by_name(edit, says):
    with pytest.raises(ValueError, match=says):
        ModelConfig.from_hf({**SOLAR_OPEN2_250B_HF, **edit}, name="t")


def test_the_loader_refuses_the_checkpoint_by_name(tmp_path):
    from dynamo_tpu.models.loader import load_model

    (tmp_path / "config.json").write_text(json.dumps(SOLAR_OPEN2_250B_HF))
    with pytest.raises(ValueError, match="model_type 'solar_open2': the architecture is served .* tensor names are not mapped"):
        load_model(tmp_path)


@pytest.mark.skipif(not CATALOG.exists(), reason="the catalog of public architectures is not on this machine")
def test_the_published_keys_are_the_catalog_rows():
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines()) if r["name"] == "Solar-Open2-250B")
    assert row["config"] == SOLAR_OPEN2_250B_HF


def test_reference_refuses_what_it_does_not_know_and_imports_nothing_of_the_program():
    for edit, says in (({"kda_use_full_proj": True}, "low-rank KDA gates"), ({"use_rope": True}, "attention without RoPE"),
                       ({"first_k_dense_replace": 1}, "routed FFNs in every layer"), ({"gqa_layers": [1, 5]}, "gqa_layers = range"),
                       ({"linear_attn_config": {**TINY_SOLAR_OPEN2_HF["linear_attn_config"], "num_kv_heads": 2}}, "one K/V a KDA head")):
        with pytest.raises(ValueError, match=says):
            ref.shape_of({**TINY_SOLAR_OPEN2_HF, **edit})
    source = pathlib.Path(ref.__file__).read_text()
    assert "dynamo_tpu" not in source and [ln for ln in source.splitlines() if ln.startswith(("import ", "from "))] == [
        "from __future__ import annotations", "import jax", "import jax.numpy as jnp", "from . import common as c"]
    # The form: a write strength past 1 somewhere, a log-decay that is a softplus (unbounded below), a gate a channel.
    z = ref.shape_of(TINY_SOLAR_OPEN2_HF)
    assert (z["beta"], z["gated"], z["period"], z["held"], z["routed"], z["first"]) == (2.0, True, 4, 5, 10, 5)


# -- the program against the plain reference -------------------------------------------------


def test_a_whole_prefill_agrees_with_the_reference():
    """One call of ``llama.forward`` over a 40-token sequence from position 0
    (no state handed in: a slot a row, all zeros, dropped with the call), every
    position's logits against the reference's whole forward pass: the chunkwise
    form over one 40-token chunk against the recurrence token by token, a slow
    decay in half the heads, write strengths towards 0 and 2, a selection bias,
    the second of two shares of 5 experts held. float32 both sides at
    ``highest``: the order of accumulation is what is left."""
    cfg = PRESETS["test-tiny-solar-open2"]
    params = _weights(cfg)
    toks = np.random.default_rng(3).integers(1, cfg.vocab_size, size=40)
    want = _reference(params, toks)
    assert np.abs(_prefill_last_logits(params, cfg, toks) - want[-1]).max() < TOL * np.abs(want).max()
    # The model's own parts are live in these weights: each one taken out moves the logits far.
    for leaf, stack in (("w_out_gate", "attn_layers"), ("w_out_gate_b", "kda_layers"), ("w_decay_b", "kda_layers"), ("w_beta", "kda_layers")):
        broken = {**params, stack: {**params[stack], leaf: jnp.zeros_like(params[stack][leaf])}}
        assert np.abs(_prefill_last_logits(broken, cfg, toks) - want[-1]).max() > 100 * TOL * np.abs(want).max(), leaf


@pytest.mark.parametrize("split", [True, False], ids=["split", "rectangle"])
def test_chunked_prefill_then_decode_through_pages_and_slots_agrees_with_the_reference(split):
    """Through ``EngineCore``: a prompt of 40 prefilled in chunks of 12 beside a
    decoding row (mixed steps: three chunk edges; the step's rows bucket holds a
    padding row), then 8 tokens decoded through the GQA layers' pages and the
    KDA layers' slots; logprobs at the served ids against the reference's whole
    forward pass. The STEP records read as Ling's do: the rows whose slot a step
    touched, the held-share counters, the key tokens of the layers that attend."""
    cfg = PRESETS["test-tiny-solar-open2"]
    params = _weights(cfg)
    prompt = np.random.default_rng(5).integers(1, cfg.vocab_size, size=40).tolist()
    entries, core = _served_logprobs(cfg, params, prompt, 8, chunk=12, split=split)
    sequence = prompt + [e["id"] for e in entries][:-1]
    assert len(entries) == 8 and _distance(entries, prompt, _reference(params, sequence)) < TOL
    steps = [s for s in core.flight.snapshot(kind="step") if s["layout"]]
    assert {"mixed", "decode"} <= {s["step_kind"] for s in steps}
    assert {s["layout"] for s in steps if s["step_kind"] == "mixed"} == ({SPLIT} if split else {"rows_x_t"})
    assert max(s["state_rows"] for s in steps) == 2 and all(s["state_rows"] == s["decode_rows"] + s["chunk_rows"] for s in steps)
    # HELD_COUNTS: 8 layers x 2 choices a real token (a pipelined step's record holds the step before's counts),
    # about half of them on the 5 of 10 experts held here, none on an identity expert.
    decodes = [s for s in steps if s["step_kind"] == "decode" and s["moe_choices"]]
    assert decodes and all(s["moe_choices"] % 16 == 0 for s in decodes)
    held = sum(s["moe_choices_held"] for s in decodes) / sum(s["moe_choices"] for s in decodes)
    assert 0.25 < held < 0.75 and all(s["moe_choices_zero"] == 0 for s in decodes)
    assert all(s["kv_tokens_full"] > 0 and s["kv_tokens_window"] == 0 for s in steps)  # one kind of layer that attends
    assert all(s["attn_phase"] in ("decode", "prefill") and s["attn_path"] == "fallback" for s in steps)  # no kernels on the CPU


def test_a_mixed_step_with_a_padding_row_agrees_with_the_reference():
    """The runner by hand: two sequences prefilled, then one step that decodes
    one of them beside a 12-token chunk of the other in a rows bucket of four
    (two padding rows: the null page, the null slot), on the split token axis;
    both rows' logits against the reference, and the padding rows leave every
    live slot as it was."""
    from benchmark.serving import null_batch

    cfg = PRESETS["test-tiny-solar-open2"]
    params = _weights(cfg)
    rng = np.random.default_rng(9)
    a, b = rng.integers(1, cfg.vocab_size, size=20), rng.integers(1, cfg.vocab_size, size=24)
    runner = ModelRunner(cfg, params, num_pages=32, page_size=4, max_batch_size=4, prefill_bucket=4, attn_impl="reference")
    pages = {1: np.arange(1, 9), 2: np.arange(9, 17)}

    def rows(batch, row, seq, slot, lo, hi, toks):
        n = hi - lo
        batch.tokens[row, :n] = toks[lo:hi]
        batch.positions[row, :n] = np.arange(lo, hi)
        batch.block_tables[row, :] = pages[seq]
        batch.slot_mapping[row, :n] = pages[seq][0] * 4 + np.arange(lo, hi)
        batch.last_token_index[row] = n - 1
        batch.num_new[row] = n
        batch.pos_limit[row] = 32
        batch.state_slots[row] = slot

    def batch_of(b_rows, t):
        batch = null_batch(b_rows, t, 8)
        batch.state_slots = np.zeros(b_rows, np.int32)
        return batch

    first = batch_of(2, 12)
    rows(first, 0, 1, 2, 0, 12, a)  # sequence a in slot 2, b in slot 1: slots are not row numbers
    rows(first, 1, 2, 1, 0, 12, b)
    runner.step(first, lp_k=4)
    second = batch_of(1, 12)
    rows(second, 0, 1, 2, 12, 19, a)  # a's last chunk is ragged: 7 real tokens, 5 padding positions
    runner.step(second, lp_k=4)
    before = [np.asarray(x) for x in runner.state]
    mixed = batch_of(4, 12)
    rows(mixed, 0, 1, 2, 19, 20, a)  # a decodes its 20th token
    rows(mixed, 2, 2, 1, 12, 24, b)  # b's second chunk, two rows further on; rows 1 and 3 are padding
    _, lp = runner.step(mixed, lp_k=4)
    assert runner.last_step_layout[0] == SPLIT
    want_a, want_b = _reference(params, a), _reference(params, b)
    for row, want in ((0, want_a[19]), (2, want_b[23])):
        z = want - want.max()
        ref_lp = z - np.log(np.exp(z).sum())
        np.testing.assert_allclose(lp["top_lps"][row], ref_lp[lp["top_ids"][row]], atol=TOL * np.abs(want).max())
    after = [np.asarray(x) for x in runner.state]
    slots = runner.state_slots
    for buf0, buf1 in zip(before, after):
        per_layer0, per_layer1 = buf0.reshape(cfg.recurrent_layers, slots, -1), buf1.reshape(cfg.recurrent_layers, slots, -1)
        np.testing.assert_array_equal(per_layer0[:, 3:], per_layer1[:, 3:])  # slots no row named
        assert (per_layer0[:, 1:3] != per_layer1[:, 1:3]).any()  # the two live ones moved


def test_bf16_and_int8_stay_within_a_tolerance_the_weights_one_precision_down_fail():
    """The benchmark's served form at toy size: bf16 activations, int8 matmul
    leaves (the gates, the low-rank pairs and the router stay bf16), a whole
    40-token prefill against the float32 reference on the same weights: the
    mean |logit difference| at the last position over the largest |logit|. Over
    five seeds sound readings are 0.012-0.074 and with the int8 leaves re-coded
    as int4 (``weights.requantize_int4``) 0.23-0.32; the tolerance 0.13 is their
    geometric middle. (The largest single difference is no statistic for a
    tolerance at this size: at 64 channels a rounding flips a router's choice
    on some seeds, Ling's toy as this one, and reads 0.04-0.6 sound.)"""
    from benchmark import weights

    cfg = dataclasses.replace(PRESETS["test-tiny-solar-open2"], dtype="bfloat16")
    sound, down = [], []
    for seed in range(5):
        params = weights.make_weights(cfg, 2**31 + 530 + seed, quant="int8")
        assert isinstance(params["kda_layers"]["wq"], dict) and not isinstance(params["kda_layers"]["w_decay_b"], dict)
        assert isinstance(params["attn_layers"]["wo"], dict) and params["attn_layers"]["w_out_gate"].dtype == jnp.bfloat16
        toks = np.random.default_rng(seed).integers(1, cfg.vocab_size, size=40)
        want = _reference(params, toks)[-1]
        distance = lambda tree: float(np.abs(_prefill_last_logits(tree, cfg, toks) - want).mean() / np.abs(want).max())  # noqa: E731
        sound.append(distance(params))
        down.append(distance(weights.requantize_int4(params)))
    assert max(sound) < 0.13 < min(down), (sound, down)


# -- the slots: admission, finish, reuse, preemption, the refusals by name ------------------------------


@pytest.mark.parametrize("case, args", [
    (slots.test_a_program_made_wrong_is_far_from_the_reference, {"broken": slots._no_carry}),
    (slots.test_a_program_made_wrong_is_far_from_the_reference, {"broken": slots._no_zeroing}),
    (slots.test_a_program_made_wrong_is_far_from_the_reference, {"broken": slots._by_row}),
    (slots.test_the_run_after_another_is_sound_unbroken, {}),
    (slots.test_two_sequences_that_swap_rows_keep_their_states, {}),
    (slots.test_slots_and_pages_are_sized_from_the_model, {}),
    (slots.test_a_preempted_sequences_second_run_equals_its_first, {}),
    (slots.test_a_model_with_recurrent_layers_never_matches_a_prefix, {}),
    (slots.test_page_transfer_and_the_kv_router_refuse_the_model_by_name, {}),
], ids=["no-carry-is-far", "no-zeroing-is-far", "by-row-is-far", "run-after-another", "swapped-rows", "sizes", "preemption",
        "no-prefix-match", "refusals"])
def test_the_slot_cases_of_the_recurrent_kinds_hold_for_this_one(case, args, request):
    """``tests/test_hybrid_kda.py``'s cases over the recurrent kinds, run here
    for KDA layers round a gated GQA layer: the three ways the slots are easy
    to get wrong each land far from the reference, a row that joins a running
    batch in a slot another sequence left is sound, rows that swap places keep
    their states, the buffers are sized from the model, a preempted sequence's
    second run equals its first, a prefix is never matched, and page transfer
    and the KV router refuse the model by name."""
    import inspect

    fixtures = {name: request.getfixturevalue(name) for name in inspect.signature(case).parameters if name in ("monkeypatch", "caplog")}
    case(kind="kda-gqa", **args, **fixtures)


# -- the share and the model ------------------------------------------------------------------


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """One routed layer of 40 experts, 8 choices a token, a selection bias, a
    shared expert, divided over eight holders of 5 experts each (no power of
    two, as 40 of 320 are not): what the model's layer gives on each holder (its
    experts' terms and the shared expert, which every holder computes whole),
    summed with the shared expert and everything outside the routed experts
    counted once, equals the uncut reference's layer. float32 both sides: 1e-5
    of the largest output."""
    hf = {**TINY_SOLAR_OPEN2_HF, "n_routed_experts": 40, "n_routed_experts_published": 40, "expert_share_rank": 0,
          "expert_share_chips": 1, "num_experts_per_tok": 8}
    whole = dataclasses.replace(ModelConfig.from_hf(hf, name="whole"), dtype="float32")
    assert (whole.num_experts, whole.routed_experts, whole.moe_held_share) == (40, 40, False)
    from benchmark import weights

    params = weights.make_weights(whole, 2**31 + 54, quant="")
    lp = jax.tree.map(lambda x: x[0], params["layers"])
    lp["router_bias"] = 0.3 * jax.random.normal(jax.random.PRNGKey(9), lp["router_bias"].shape, jnp.float32)
    h = jax.random.normal(jax.random.PRNGKey(3), (1, 48, 64), jnp.float32)
    z = ref.shape_of(hf)
    want = np.asarray(ref.ffn(h[0], lp, z))
    shared = np.asarray(ref.shared_expert(h[0], lp))
    mix = np.asarray(ref.route(h[0], lp, z))
    assert ((mix > 0).sum(axis=1) == 8).all() and np.allclose(mix.sum(axis=1), 1.0, atol=1e-6)
    unbiased = np.asarray(ref.route(h[0], {**lp, "router_bias": jnp.zeros_like(lp["router_bias"])}, z))
    assert ((mix > 0) != (unbiased > 0)).any()  # the bias chose otherwise somewhere, and is no part of a weight
    total, held_choices = np.zeros_like(want), 0
    for rank in range(8):
        share_hf = {**hf, "n_routed_experts": 5, "expert_share_rank": rank, "expert_share_chips": 8}
        share = dataclasses.replace(ModelConfig.from_hf(share_hf, name="share"), dtype="float32")
        assert share.moe_expert_first == 5 * rank and share.moe_held_share and share.router_outputs == 40
        mine = {**lp, **{k: lp[k][5 * rank: 5 * rank + 5] for k in ("w_gate", "w_up", "w_down")}}
        out, counts = llama._mlp_moe_held(mine, h, share, jnp.ones((1, 48), bool))
        np.testing.assert_allclose(out[0], ref.ffn(h[0], mine, ref.shape_of(share_hf)), atol=1e-5 * np.abs(want).max())
        total += np.asarray(out[0]) - shared
        held_choices += int(counts[2])
    np.testing.assert_allclose(total + shared, want, atol=1e-5 * np.abs(want).max())
    assert held_choices == 48 * 8  # every choice landed on exactly one holder


def test_the_chunk_rows_taken_at_once_follow_the_head_count():
    """The chunkwise form's ``[tokens, tokens, heads, key]`` temporaries stay
    where Ling's are: 4 rows at once at 32 heads, 2 at 64, 1 from 128."""
    assert [kda.chunk_rows_at_once(h) for h in (4, 32, 64, 128, 256)] == [32, 4, 2, 1, 1]
    assert kda.chunk_rows_at_once(32) == kda.CHUNK_ROWS_AT_ONCE


# -- the normal path: launch, frontend, EngineCore, ModelRunner, the pipelined loop -----------------


async def test_the_toy_is_served_over_http_and_agrees_with_the_reference():
    """``launch.serve_worker`` + ``serve_frontend`` (what ``--role local``
    brings up) on the toy: a 40-token prompt goes in over ``/v1/completions`` in
    chunks of 8 while another request decodes, then 6 tokens are decoded
    greedily through the GQA layers' pages and the KDA layers' slots. The
    logprob the server reports for each token against the reference's
    log-softmax of the same sequence: 1e-4 of the largest logit."""
    import asyncio

    import aiohttp

    from benchmark import serving

    cfg = PRESETS["test-tiny-solar-open2"]
    params = _weights(cfg)
    conf = {"name": "test-tiny-solar-open2", "serve": {"engine": {
        "page_size": 4, "chunk_prefill_tokens": 8, "max_prefill_tokens": 8, "max_batch_size": 4, "max_seq_len": 128,
        "pool_tokens": 512}}}
    handles = await serving.start(conf, cfg, params)
    try:
        await serving.wait_listed(handles)
        prompt = np.random.default_rng(11).integers(1, cfg.vocab_size, size=40).tolist()
        async with aiohttp.ClientSession() as s:
            beside = asyncio.ensure_future(s.post(handles["base"] + "/v1/completions", json={
                "model": conf["name"], "prompt": [3, 5, 7, 9], "max_tokens": 48, "temperature": 0, "nvext": {"ignore_eos": True}}))
            await asyncio.sleep(0.5)
            r = await s.post(handles["base"] + "/v1/completions", json={
                "model": conf["name"], "prompt": prompt, "max_tokens": 6, "temperature": 0, "logprobs": 1,
                "nvext": {"ignore_eos": True}})
            assert r.status == 200, await r.text()
            doc = await r.json()
            assert (await beside).status == 200
        core = handles["services"][0].core
        steps = core.flight.snapshot(kind="step")
        assert {"mixed", "decode"} <= {x["step_kind"] for x in steps} and max(x["state_slots_live"] for x in steps) == 2
        assert core.runner.recurrent and core.state_slots.live == 0  # both slots back at finish
        assert core.runner.k_cache.shape[0] == 2 and core.runner.state[0].shape[0] == 6 * 5  # pages for 2 layers, slots for 6
    finally:
        await serving.stop(handles)
    served = doc["choices"][0]["logprobs"]["token_logprobs"]
    assert len(served) == 6
    seq, worst, fwd = list(prompt), 0.0, jax.jit(functools.partial(ref.forward, hf=TINY_SOLAR_OPEN2_HF))
    for lp in served:
        toks = np.zeros(64, np.int32)
        toks[: len(seq)] = seq
        logits = np.asarray(fwd(params, tokens=jnp.asarray(toks)))[len(seq) - 1]
        z = logits - logits.max()
        worst = max(worst, abs(float(-np.log(np.exp(z).sum())) - lp) / float(np.abs(logits).max()))
        seq.append(int(logits.argmax()))
    assert worst < 1e-4
