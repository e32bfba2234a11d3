"""LongCat-Flash's layer (ISSUE 34): a shortcut-connected MoE layer with two MLA
sublayers and two dense FFNs, identity ("zero-compute") experts in the router,
and a held share of the routed experts. The config is read by its own keys, the
engine's chunked prefill and decode through the paged latent cache agree with
the benchmark's plain reference (and not with a reference made wrong in the
three ways the layer is easy to get wrong), the shares of the experts add up to
the uncut layer, and the step's counters reach the STEP record."""

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

from benchmark.reference import common as c  # noqa: E402
from benchmark.reference import longcat_flash as ref  # noqa: E402
from dynamo_tpu.engine.core import LOGPROBS_TOP_K, EngineConfig, EngineCore  # noqa: E402
from dynamo_tpu.engine.runner import SPLIT, ModelRunner  # noqa: E402
from dynamo_tpu.models import llama  # noqa: E402
from dynamo_tpu.models.config import PRESETS, ModelConfig  # noqa: E402
from dynamo_tpu.parallel import moe  # noqa: E402
from dynamo_tpu.protocols.common import PreprocessedRequest, SamplingOptions, StopConditions  # noqa: E402
from dynamo_tpu.runtime.engine import Context  # noqa: E402
from tests.test_mixed_attention import _distance  # noqa: E402  (max |served - reference| logprob over the largest |logit|)

CATALOG = pathlib.Path("/opt/skills/guides/model-configs/architectures.jsonl")
LONGCAT = json.loads((ROOT / "benchmark" / "configs" / "longcat-flash-chat-ep32-int8.json").read_text())
#: 2 double layers; 4 of 16 routed experts held (rank 1: ids 4-7) + 8 identities in a 24-way router, top-4, factor 6.
TOY_HF = {
    "attention_bias": False, "vocab_size": 256, "hidden_size": 64, "ffn_hidden_size": 128, "expert_ffn_hidden_size": 32,
    "num_layers": 2, "num_attention_heads": 4, "kv_lora_rank": 24, "q_lora_rank": 32, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "qk_nope_head_dim": 16, "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
    "routed_scaling_factor": 6, "n_routed_experts": 4, "n_routed_experts_published": 16, "expert_share_rank": 1,
    "max_position_embeddings": 512, "rms_norm_eps": 1e-5, "rope_theta": 10000, "attention_method": "MLA",
    "zero_expert_num": 8, "zero_expert_type": "identity", "moe_topk": 4,
}


def _toy(**edit) -> ModelConfig:
    return dataclasses.replace(ModelConfig.from_hf({**TOY_HF, **edit}, name="toy-scmoe"), dtype="float32")


def _weights(cfg, seed=2**31 + 34, bias=0.02):
    """The benchmark's weights (plain float32), with a router bias that changes choices."""
    from benchmark import weights

    params = weights.make_weights(cfg, seed, quant="")
    shape = params["layers"]["router_bias"].shape
    params["layers"]["router_bias"] = bias * jax.random.normal(jax.random.PRNGKey(7), shape, jnp.float32)
    return params


# -- from_hf --------------------------------------------------------------------


@pytest.mark.skipif(not CATALOG.exists(), reason="the catalog of public architectures is not on this machine")
def test_the_catalog_rows_keys_give_the_published_widths():
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines()) if r["source_url"] == LONGCAT["source"])
    cfg = ModelConfig.from_hf(dict(row["config"]), name="longcat")
    assert (cfg.num_layers, cfg.cache_layers, cfg.hidden_size, cfg.num_heads) == (28, 56, 6144, 64)
    assert (cfg.intermediate_size, cfg.moe_intermediate_size, cfg.vocab_size) == (12288, 2048, 131072)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim) == (1536, 512, 128, 64, 128)
    assert cfg.attn_type == "mla" and cfg.shortcut_moe and cfg.rope_theta == 1e7 and cfg.rms_eps == 1e-5
    assert (cfg.num_experts, cfg.routed_experts, cfg.moe_zero_experts, cfg.router_outputs) == (512, 512, 256, 768)
    assert (cfg.num_experts_per_token, cfg.moe_routed_scaling, cfg.moe_norm_topk, cfg.moe_scoring) == (12, 6.0, False, "softmax")
    assert cfg.mla_scale_q == pytest.approx(2.0) and cfg.mla_scale_kv == pytest.approx(12 ** 0.5)
    assert cfg.moe_held_share and cfg.moe_expert_first == 0  # identities: not every router output is an expert held here
    assert cfg.param_count() == pytest.approx(560.7e9, rel=1e-3)  # the published 560B
    assert cfg.kv_bytes_per_token() == 56 * (512 + 128) * 2


def test_the_configuration_file_is_this_chips_share():
    from benchmark import serving

    conf = serving.load_config(ROOT / "benchmark" / "configs" / "longcat-flash-chat-ep32-int8.json")
    cfg = serving.model_config(conf)
    assert (cfg.num_layers, cfg.cache_layers, cfg.num_experts, cfg.routed_experts, cfg.router_outputs) == (7, 14, 16, 512, 768)
    assert (cfg.moe_expert_first, cfg.vocab_size, cfg.max_position) == (0, 16384, 131072)
    # ISSUE 34's sizes: 1.264 GB a layer at one byte a matmul parameter, 17,920 cache bytes a token.
    assert cfg.kv_bytes_per_token() == 17920
    shapes = jax.eval_shape(lambda: llama.init_params(cfg, 0))
    int8 = {"w_q_a", "w_q_b", "w_kv_a", "wo_mla", "w_gate", "w_up", "w_down", "lm_head"}

    def nbytes(tree, name=None):
        if isinstance(tree, dict):
            return sum(nbytes(v, k) for k, v in tree.items())
        return tree.size * (1 if name in int8 else 4 if name == "router_bias" else 2)

    assert nbytes(shapes["layers"]) / 7 == pytest.approx(1.264e9, rel=2e-3)
    assert nbytes(shapes) == pytest.approx(9.15e9, rel=2e-3)
    assert cfg.param_count() == sum(x.size for x in jax.tree.leaves(shapes)) - 7 * 768  # the bias is no parameter


@pytest.mark.parametrize("edit, says", [
    ({"zero_expert_type": "copy"}, "zero_expert_type 'copy'"),
    ({"attention_method": "GQA"}, "attention_method 'GQA'"),
    ({"expert_share_rank": 4}, r"experts \[16, 20\) lie outside the 16"),
    ({"rope_scaling": {"rope_type": "yarn", "factor": 10}}, "rope_scaling"),
], ids=["zero-kind", "attention", "rank", "rope-scaling"])
def test_from_hf_refuses_by_name(edit, says):
    with pytest.raises(ValueError, match=says):
        ModelConfig.from_hf({**TOY_HF, **edit}, name="t")


def test_loader_refuses_the_unmapped_checkpoint_by_name(tmp_path):
    from dynamo_tpu.models.loader import load_model

    (tmp_path / "config.json").write_text(json.dumps(TOY_HF))
    with pytest.raises(ValueError, match="shortcut-MoE model"):
        load_model(tmp_path)


def test_cache_has_a_slab_per_attention_sublayer_and_the_second_is_written():
    cfg = _toy()
    params = _weights(cfg)
    k, v = llama.init_kv_cache(cfg, 6, 8)
    assert k.shape == (4, 6, 8, 24) and v.shape == (4, 6, 8, 128)
    t = 8
    out = llama.forward(params, cfg, jnp.arange(1, t + 1)[None], jnp.arange(t)[None], k, v, jnp.asarray([[2]]),
                        (16 + jnp.arange(t))[None], jnp.asarray([t - 1]))
    written = np.abs(np.asarray(out[1])).sum(axis=(2, 3))  # [slab, page]
    assert (written[:, 2] > 0).all() and written[:, [1, 3, 4, 5]].sum() == 0  # page 2 of all four slabs, nothing else


# -- the engine against the plain reference -------------------------------------


def _served_logprobs(cfg, params, prompt, n_out, *, chunk, overlap=True, split=True):
    """Through EngineCore: the prompt prefilled in ``chunk``-token chunks beside
    a decoding row (the split token axis, or with ``split=False`` the rows x
    tokens rectangle), then decoded through the paged latent cache; every
    generated token's logprob and its top 20."""
    page = 4
    runner = ModelRunner(cfg, params, num_pages=64, page_size=page, max_batch_size=2,
                         prefill_bucket=4, attn_impl="reference")
    runner._can_split = runner._can_split and split
    core = EngineCore(runner, EngineConfig(
        num_pages=64, page_size=page, max_batch_size=2, max_prefill_tokens=chunk, chunk_prefill_tokens=chunk,
        max_seq_len=128, enable_prefix_caching=False, overlap=overlap))

    def request(tokens, n, logprobs):
        return PreprocessedRequest(
            token_ids=list(tokens), sampling=SamplingOptions(temperature=0.0, logprobs=logprobs),
            stop=StopConditions(max_tokens=n, ignore_eos=True))

    core.add_request(request([7, 9, 11, 13], 40, None), Context())
    for _ in range(3):
        core.step()
    seq = core.add_request(request(prompt, n_out, LOGPROBS_TOP_K + 1), Context())
    entries = []
    while core.has_work and len(entries) < n_out:
        for s, out in core.step():
            if s is seq:
                entries.extend(out.logprobs or [])
    return entries, core


def _no_zero_experts(h, lp, z):
    return ref.held_experts_term(h, lp, ref.route(h, lp, z), z)


def _shortcut_joins_early(x, lp, pos, z):
    """The MoE's output added before the second sublayer instead of at the layer's end."""
    s0, s1 = lp["sub0"], lp["sub1"]
    a0 = x + ref.mla(c.rms_norm(x, s0["attn_norm"], z["eps"]), s0, pos, z)
    h0 = c.rms_norm(a0, s0["mlp_norm"], z["eps"])
    b0 = a0 + ref.dense_ffn(h0, s0) + ref.moe(h0, lp, z)
    a1 = b0 + ref.mla(c.rms_norm(b0, s1["attn_norm"], z["eps"]), s1, pos, z)
    return a1 + ref.dense_ffn(c.rms_norm(a1, s1["mlp_norm"], z["eps"]), s1)


#: The reference made wrong in the ways this layer is easy to get wrong: (patch target, replacement or hf edit).
WRONG_REFERENCES = {
    "zero-expert term dropped": ("moe", _no_zero_experts),
    "shortcut added before the second sublayer": ("layer", _shortcut_joins_early),
    "query scale left out": ("hf", {"mla_scale_q_lora": False}),
    "latent scale left out": ("hf", {"mla_scale_kv_lora": False}),
}


@pytest.fixture(scope="module")
def served():
    cfg = _toy()
    params = _weights(cfg)
    prompt = np.random.default_rng(5).integers(1, cfg.vocab_size, size=40).tolist()
    entries, core = _served_logprobs(cfg, params, prompt, 8, chunk=12)
    return cfg, params, prompt, entries, core


def _reference(params, sequence, hf=TOY_HF):
    return np.asarray(jax.jit(functools.partial(ref.forward, hf=hf))(params, tokens=jnp.asarray(sequence)))


def test_engine_chunked_prefill_and_decode_agree_with_the_reference(served):
    """A prompt of 40 prefilled in chunks of 12 beside a decoding row (mixed
    steps on the split token axis), 8 tokens decoded through the paged latent
    cache; the router's bias is not zero and the model holds ids 4-7 of 16
    experts beside 8 identities. Both sides float32 at ``highest`` matmul
    precision (conftest): what is left is the order of accumulation (absorbed
    MLA over paged chunks against per-head K and V over one whole sequence,
    sorted expert rows against one expert at a time), about 1e-6 of the logit
    range, so the tolerance is 1e-4."""
    cfg, params, prompt, entries, core = served
    sequence = prompt + [e["id"] for e in entries][:-1]
    assert len(entries) == 8 and _distance(entries, prompt, _reference(params, sequence)) < 1e-4
    steps = core.flight.snapshot(kind="step")
    kinds = {s["step_kind"] for s in steps}
    assert {"mixed", "decode"} <= kinds
    assert any(s["layout"] == SPLIT for s in steps if s["step_kind"] == "mixed")


@pytest.mark.parametrize("wrong", WRONG_REFERENCES.keys())
def test_a_reference_made_wrong_is_far_from_what_the_engine_serves(served, wrong, monkeypatch):
    """The same served sample against the reference with one piece of the
    layer wrong: each is off by more than a hundred times the tolerance, so
    the comparison sees the identity experts, where the shortcut joins, and
    each of the two scales."""
    cfg, params, prompt, entries, _ = served
    sequence = prompt + [e["id"] for e in entries][:-1]
    target, change = WRONG_REFERENCES[wrong]
    hf = TOY_HF
    if target == "hf":
        hf = {**TOY_HF, **change}
    else:
        monkeypatch.setattr(ref, target, change)
    assert _distance(entries, prompt, _reference(params, sequence, hf)) > 100 * 1e-4, wrong


# -- the shares add up ------------------------------------------------------------


def test_the_shares_of_the_experts_add_up_to_the_uncut_layer():
    """One expert layer, 16 routed experts + 8 identities, divided over four
    holders of 4 experts: the held experts' parts of all four shares, with the
    identity term (which every holder computes alike, where the token lives)
    counted once, equal the uncut reference's MoE, which holds all 16. The
    dense FFNs and the attention are outside the expert layer and whole on
    every holder. float32 both sides: tolerance 1e-5 of the largest output."""
    whole_hf = {**TOY_HF, "n_routed_experts": 16, "n_routed_experts_published": 16, "expert_share_rank": 0}
    whole = _toy(n_routed_experts=16, expert_share_rank=0)
    assert whole.moe_experts_total == 0 and whole.moe_held_share  # all routed experts held, identities beside them
    lp = jax.tree.map(lambda x: x[0], {k: v for k, v in _weights(whole)["layers"].items() if not k.startswith("sub")})
    h = jax.random.normal(jax.random.PRNGKey(3), (48, 64), jnp.float32)
    z = ref.shape_of(whole_hf)
    want = np.asarray(ref.moe(h, lp, z))
    zero = np.asarray(ref.zero_experts_term(h, ref.route(h, lp, z), z))
    assert np.abs(zero).max() > 0.1 * np.abs(want).max()  # the identity term is no rounding error here

    total, held_choices = np.zeros_like(want), 0
    for rank in range(4):
        share = _toy(expert_share_rank=rank)
        mine = {**lp, **{k: lp[k][4 * rank: 4 * rank + 4] for k in ("w_gate", "w_up", "w_down")}}
        out, counts = moe.moe_mlp_held(mine, h, num_experts_per_token=4, first=share.moe_expert_first,
                                       routed=share.routed_experts, routing=llama._routing_kwargs(share))
        # The share's own reference: the same part, from the same weights.
        share_hf = {**TOY_HF, "expert_share_rank": rank}
        np.testing.assert_allclose(out, ref.moe(h, mine, ref.shape_of(share_hf)), atol=1e-5 * np.abs(want).max())
        total += np.asarray(out) - zero
        held_choices += int(counts[2])
        assert int(counts[0]) == 48 * 4
    np.testing.assert_allclose(total + zero, want, atol=1e-5 * np.abs(want).max())
    mix = np.asarray(ref.route(h, lp, z))
    assert held_choices == int((mix[:, :16] > 0).sum()) and int(counts[1]) == int((mix[:, 16:] > 0).sum())


def test_counts_leave_padding_tokens_out_and_no_held_choice_means_no_pass():
    cfg = _toy()
    lp = jax.tree.map(lambda x: x[0], {k: v for k, v in _weights(cfg)["layers"].items() if not k.startswith("sub")})
    h = jax.random.normal(jax.random.PRNGKey(4), (32, 64), jnp.float32)
    kw = dict(num_experts_per_token=4, first=4, routed=16, routing=llama._routing_kwargs(cfg))
    valid = jnp.arange(32) < 20
    out, counts = moe.moe_mlp_held(lp, h, valid=valid, **kw)
    out_all, counts_all = moe.moe_mlp_held(lp, h, **kw)
    assert int(counts[0]) == 20 * 4 and int(counts_all[0]) == 32 * 4 and 0 < int(counts[2]) < int(counts_all[2])
    np.testing.assert_allclose(out[:20], out_all[:20], atol=1e-6)  # a token's result does not turn on its neighbours
    # A padding token keeps only its identity term (it is discarded anyway): no expert row was computed for it.
    z = ref.shape_of(TOY_HF)
    np.testing.assert_allclose(out[20:], ref.zero_experts_term(h, ref.route(h, lp, z), z)[20:], atol=1e-6)
    none, counts_none = moe.moe_mlp_held(lp, h, valid=jnp.zeros((32,), bool), **kw)
    assert counts_none.tolist() == [0, 0, 0, 0, 0]
    assert moe.held_rows_cap(64 * 12, 16, 768) == 128 and moe.held_rows_cap(128 * 12, 16, 768) == 128
    assert moe.held_rows_cap(4096 * 12, 16, 768) == 2048 and moe.held_rows_cap(8, 4, 24) == 16


def test_routing_far_from_even_takes_every_copys_rows_and_stays_exact(monkeypatch):
    """Every choice of every token on the held experts (a bias that pulls them
    there): four times the rows the usual pass takes, so the layer's ``cond``
    takes its other arm, the pass over every copy's rows (counted in
    ``moe_extra_passes``), and the result is still the reference's."""
    cfg = _toy()
    lp = jax.tree.map(lambda x: x[0], {k: v for k, v in _weights(cfg)["layers"].items() if not k.startswith("sub")})
    lp["router_bias"] = jnp.where((jnp.arange(24) >= 4) & (jnp.arange(24) < 8), 10.0, 0.0)
    h = jax.random.normal(jax.random.PRNGKey(5), (64, 64), jnp.float32)
    monkeypatch.setattr(moe, "held_rows_cap", lambda copies, held, outputs: 64)
    out, counts = moe.moe_mlp_held(lp, h, num_experts_per_token=4, first=4, routed=16, routing=llama._routing_kwargs(cfg))
    assert counts.tolist() == [256, 0, 256, 4, 1]
    np.testing.assert_allclose(out, ref.moe(h, lp, ref.shape_of(TOY_HF)), atol=1e-5 * float(jnp.abs(out).max()))


def test_held_experts_through_the_int8_kernel_match_the_widened_path(monkeypatch):
    """The fused grouped-matmul kernel (interpret mode) over the rows that
    landed here, from the stacked int8 experts by layer index, against the
    widened ``ragged_dot`` formulation of the same int8 weights: bf16 products
    both, the kernel scales its float32 accumulator where the widened path
    rounds ``qw * scale`` to bf16 first, so 2e-2 of the largest output."""
    from dynamo_tpu.models.quant import quantize_params

    monkeypatch.setenv("DYNAMO_PALLAS_INTERPRET", "1")
    cfg = dataclasses.replace(_toy(hidden_size=128, expert_ffn_hidden_size=128), dtype="bfloat16")
    layers = quantize_params(llama.init_params(cfg, 1), mode="int8")["layers"]
    assert moe.experts_path(layers) == "fused"
    h = jax.random.normal(jax.random.PRNGKey(6), (32, 128), jnp.float32).astype(jnp.bfloat16)
    kw = dict(num_experts_per_token=4, first=4, routed=16, routing=llama._routing_kwargs(cfg))
    xs, stack = moe.split_expert_stack(layers)
    top = {k: v for k, v in xs.items() if not k.startswith("sub")}
    for layer in range(2):
        lp = moe.join_expert_stack(jax.tree.map(lambda x: x[layer], top), stack, jnp.int32(layer))
        fused, counts = moe.moe_mlp_held(lp, h, **kw)
        monkeypatch.setenv("DYNAMO_MOE_DISPATCH", "capacity")  # experts_path: not the kernel
        plain = jax.tree.map(lambda x: x[layer], {k: v for k, v in layers.items() if not k.startswith("sub")})
        assert moe.experts_path(plain) == "widened"
        widened, counts_w = moe.moe_mlp_held(plain, h, **kw)
        monkeypatch.delenv("DYNAMO_MOE_DISPATCH")
        assert counts.tolist() == counts_w.tolist() and int(counts[2]) > 0
        scale = float(jnp.abs(widened.astype(jnp.float32)).max())
        np.testing.assert_allclose(fused.astype(jnp.float32), widened.astype(jnp.float32), atol=2e-2 * scale)


# -- the counters in the STEP record ----------------------------------------------


@pytest.mark.parametrize("overlap", [True, False], ids=["pipelined", "synchronous"])
def test_step_records_carry_the_expert_layers_counts(overlap):
    """Every program's counts land in exactly one record (a synchronous step's
    own, a pipelined step's predecessor's): summed over the run they are the
    real tokens x 4 choices x 2 layers, a third of them identities and a sixth
    held under even routing, and an MLA model counts its key tokens."""
    cfg = _toy()
    entries, core = _served_logprobs(cfg, _weights(cfg), list(range(1, 41)), 8, chunk=12, overlap=overlap)
    while core.has_work:
        core.step()
    steps = [s for s in core.flight.snapshot(kind="step")]
    tokens = 4 + 40 + 40 + 8 - 2  # both prompts and every decoded token but each row's last (never fed back)
    choices = sum(s["moe_choices"] for s in steps)
    assert choices == pytest.approx(tokens * 4 * 2, abs=2 * 4 * 2)
    zero, held = sum(s["moe_choices_zero"] for s in steps), sum(s["moe_choices_held"] for s in steps)
    assert 0.2 < zero / choices < 0.5 and 0.08 < held / choices < 0.3
    assert all(s["moe_experts_touched"] <= 4 * 2 and s["moe_choices_held"] <= s["moe_choices"] for s in steps)
    decodes = [s for s in steps if s["step_kind"] == "decode" and s["decode_rows"] == 2]
    assert decodes and all(s["kv_tokens_full"] > 0 and s["kv_tokens_window"] == 0 for s in decodes)
    assert not core.runner._moe_counts_pending or overlap


def test_a_model_without_a_share_or_identities_returns_no_counts():
    cfg = dataclasses.replace(PRESETS["test-tiny-moe"], dtype="float32")
    assert not cfg.moe_held_share
    runner = ModelRunner(cfg, llama.init_params(cfg, 0), num_pages=16, page_size=4, max_batch_size=2,
                         prefill_bucket=4, attn_impl="reference")
    assert not runner._moe_counted
    core = EngineCore(runner, EngineConfig(num_pages=16, page_size=4, max_batch_size=2, max_seq_len=32))
    core.add_request(PreprocessedRequest(token_ids=[1, 2, 3], sampling=SamplingOptions(temperature=0.0),
                                         stop=StopConditions(max_tokens=3, ignore_eos=True)), Context())
    while core.has_work:
        core.step()
    steps = core.flight.snapshot(kind="step")
    assert steps and all(s["moe_choices"] == 0 and s["moe_experts_touched"] == 0 for s in steps)
    # The plain layer body serves identity experts too (tests/test_held_share_plain.py has a share in it).
    zeros = dataclasses.replace(cfg, moe_zero_experts=2)
    k, v = llama.init_kv_cache(zeros, 4, 4)
    out = llama.forward(llama.init_params(zeros, 0), zeros, jnp.ones((1, 1), jnp.int32), jnp.zeros((1, 1), jnp.int32), k, v,
                        jnp.ones((1, 1), jnp.int32), jnp.full((1, 1), 4, jnp.int32), jnp.zeros((1,), jnp.int32),
                        moe_counts=True)
    assert out[3][0] == 1 * 2 * 2 and out[3][1] + out[3][2] == out[3][0]  # a token, two choices, two layers: held or identity
