"""A held share of the routed experts in the plain layer body (ISSUE 36;
JoyAI-LLM-Flash's layer, DeepSeek-V3's config keys): one latent-attention block
and one FFN a layer, a leading dense layer, a shared expert that every holder
computes whole, and this holder's experts of a sigmoid, bias-corrected router
that scores all of them. The config is read by its own keys, the engine's
chunked prefill and decode through the paged latent cache agree with the
benchmark's plain reference on the rectangle and on the split token axis (and
not with a reference made wrong), the shares add up to the uncut layer with the
shared expert counted once, the router and the counters match a count by hand,
and the dual scan finds its experts in the int8 stack."""

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
from benchmark.reference import joyai_llm_flash as ref  # noqa: E402
from dynamo_tpu.engine.runner import ROWS_X_T, SPLIT  # noqa: E402
from dynamo_tpu.models import llama  # noqa: E402
from dynamo_tpu.models.config import PRESETS, ModelConfig  # noqa: E402
from dynamo_tpu.parallel import moe  # noqa: E402
from tests.test_mixed_attention import _distance  # noqa: E402  (max |served - reference| logprob over the largest |logit|)
from tests.test_shortcut_moe import _served_logprobs  # noqa: E402

CATALOG = pathlib.Path("/opt/skills/guides/model-configs/architectures.jsonl")
CONFIG = ROOT / "benchmark" / "configs" / "joyai-llm-flash-ep8-int8.json"
JOYAI = json.loads(CONFIG.read_text())
#: A dense layer and two MoE layers; 4 of 16 routed experts held (rank 1: ids 4-7), top-4, one shared expert.
TOY_HF = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1, "head_dim": 8, "hidden_act": "silu",
    "hidden_size": 64, "intermediate_size": 128, "kv_lora_rank": 24, "max_position_embeddings": 512,
    "model_type": "joyai_llm_flash", "moe_intermediate_size": 32, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 4, "n_routed_experts_published": 16, "expert_share_rank": 1, "expert_share_chips": 4,
    "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 4, "num_experts_per_tok": 4,
    "num_hidden_layers": 3, "num_key_value_heads": 4, "num_nextn_predict_layers": 1, "q_lora_rank": 32,
    "qk_head_dim": 24, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "rms_norm_eps": 1e-6, "rope_interleave": True,
    "rope_scaling": None, "rope_theta": 32000000, "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 16, "vocab_size": 256,
}
TOL = 1e-4


def _toy(**edit) -> ModelConfig:
    return dataclasses.replace(ModelConfig.from_hf({**TOY_HF, **edit}, name="toy-held"), dtype="float32")


def _weights(cfg, seed=2**31 + 36, bias=0.05):
    """The benchmark's weights (plain float32), with a selection bias that changes choices."""
    from benchmark import weights

    params = weights.make_weights(cfg, seed, quant="")
    shape = params["layers"]["router_bias"].shape
    params["layers"]["router_bias"] = bias * jax.random.normal(jax.random.PRNGKey(7), shape, jnp.float32)
    return params


def _moe_layer(params, i=0):
    return jax.tree.map(lambda x: x[i], params["layers"])


# -- from_hf --------------------------------------------------------------------


@pytest.mark.skipif(not CATALOG.exists(), reason="the catalog of public architectures is not on this machine")
def test_the_catalog_rows_keys_give_the_published_model():
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines()) if r["source_url"] == JOYAI["source"])
    cfg = ModelConfig.from_hf(dict(row["config"]), name="joyai")
    assert (cfg.num_layers, cfg.cache_layers, cfg.hidden_size, cfg.num_heads, cfg.vocab_size) == (40, 40, 2048, 32, 129280)
    assert (cfg.intermediate_size, cfg.moe_intermediate_size, cfg.shared_expert_size, cfg.first_k_dense) == (7168, 768, 768, 1)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim) == (1536, 512, 128, 64, 128)
    assert cfg.attn_type == "mla" and cfg.rope_interleave and cfg.rope_theta == 3.2e7 and cfg.rope_scaling is None
    assert (cfg.num_experts, cfg.routed_experts, cfg.router_outputs, cfg.num_experts_per_token) == (256, 256, 256, 8)
    assert (cfg.moe_scoring, cfg.moe_router_bias, cfg.moe_norm_topk, cfg.moe_routed_scaling) == ("sigmoid", True, True, 2.5)
    assert (cfg.moe_n_group, cfg.moe_topk_group) == (0, 0)  # one group is no group limit: no group top-k in the program
    assert not cfg.moe_held_share and not cfg.shortcut_moe and not cfg.tie_embeddings and not cfg.shared_expert_gated
    assert cfg.param_count() == pytest.approx(48.9e9, rel=5e-3)  # the published 48B
    assert cfg.kv_bytes_per_token() == 40 * (512 + 128) * 2


def test_the_configuration_file_is_this_chips_share():
    from benchmark import serving

    conf = serving.load_config(CONFIG)
    cfg = serving.model_config(conf)
    assert (cfg.num_layers, cfg.first_k_dense, cfg.num_experts, cfg.routed_experts, cfg.router_outputs) == (40, 1, 32, 256, 256)
    assert (cfg.moe_expert_first, cfg.moe_experts_total, cfg.vocab_size, cfg.max_position) == (0, 256, 129280, 131072)
    assert cfg.moe_held_share and cfg.shared_expert_size == 768 and cfg.num_experts_per_token == 8
    assert cfg.kv_bytes_per_token() == 51200
    # The file states the share to the program a second time (serve.model_overrides: a program that does not
    # read the share keys then refuses the model instead of serving 32 experts as all): here the lines change nothing.
    assert cfg == ModelConfig.from_hf(dict(conf["hf"]), name=conf["name"])
    toy = serving.load_config(CONFIG, rehearsal=True)
    assert dataclasses.replace(ModelConfig.from_hf(dict(toy["hf"]), name=toy["name"]), dtype="float32") == serving.model_config(toy)
    shapes = jax.eval_shape(lambda: llama.init_params(cfg, 0))
    assert shapes["layers"]["router"].shape == (39, 2048, 256) and shapes["layers"]["router_bias"].shape == (39, 256)
    assert shapes["layers"]["w_gate"].shape == (39, 32, 2048, 768) and shapes["dense_layers"]["w_gate"].shape == (1, 2048, 7168)
    int8 = {"w_q_a", "w_q_b", "w_kv_a", "wo_mla", "w_gate", "w_up", "w_down", "w_shared_gate", "w_shared_up",
            "w_shared_down", "lm_head"}

    def nbytes(tree, name=None):
        if isinstance(tree, dict):
            return sum(nbytes(v, k) for k, v in tree.items())
        return tree.size * (1 if name in int8 else 4 if name == "router_bias" else 2)

    # ISSUE 36's sizes: 187.3 MB a MoE layer, 74.6 MB the dense layer, 8.17 GB in all.
    assert nbytes(shapes["layers"]) / 39 == pytest.approx(187.3e6, rel=2e-3)
    assert nbytes(shapes["dense_layers"]) == pytest.approx(74.6e6, rel=2e-3)
    assert nbytes(shapes) == pytest.approx(8.17e9, rel=2e-3)
    assert cfg.param_count() == sum(x.size for x in jax.tree.leaves(shapes)) - 39 * 256  # the bias is no parameter


@pytest.mark.parametrize("edit, says", [
    ({"moe_layer_freq": 2}, "moe_layer_freq 2 is not served"),
    ({"expert_share_rank": 4}, r"experts \[16, 20\) lie outside the 16"),
    ({"n_routed_experts": 0}, r"experts \[0, 0\) lie outside the 16"),
    ({"n_group": 2, "topk_group": 1}, "a held share of 4 experts splits a routing group of 8"),
], ids=["layer-freq", "rank", "none-held", "split-group"])
def test_from_hf_refuses_by_name(edit, says):
    with pytest.raises(ValueError, match=says):
        ModelConfig.from_hf({**TOY_HF, **edit}, name="t")


def test_from_hf_reads_the_share_and_keeps_whole_groups():
    cfg = _toy()
    assert (cfg.num_experts, cfg.moe_experts_total, cfg.moe_expert_first, cfg.router_outputs) == (4, 16, 4, 16)
    assert (cfg.moe_n_group, cfg.moe_topk_group, cfg.first_k_dense, cfg.shared_expert_size) == (0, 0, 1, 32)
    grouped = _toy(n_group=4, topk_group=2)  # a share of one whole group of four
    assert (grouped.moe_n_group, grouped.moe_topk_group, grouped.moe_held_share) == (4, 2, True)
    whole = _toy(n_routed_experts=16, expert_share_rank=0)
    assert whole.moe_experts_total == 0 and not whole.moe_held_share  # every published expert held: the plain dispatch
    held = PRESETS["test-tiny-v3-held"]
    assert held.moe_held_share and (held.num_experts, held.routed_experts, held.moe_expert_first) == (4, 16, 4)


# -- the engine against the plain reference -------------------------------------


def _reference(params, sequence, hf=TOY_HF):
    return np.asarray(jax.jit(functools.partial(ref.forward, hf=hf))(params, tokens=jnp.asarray(sequence)))


@pytest.fixture(scope="module", params=[True, False], ids=["split", "rectangle"])
def served(request):
    cfg = _toy()
    params = _weights(cfg)
    prompt = np.random.default_rng(5).integers(1, cfg.vocab_size, size=40).tolist()
    entries, core = _served_logprobs(cfg, params, prompt, 8, chunk=12, split=request.param)
    return params, prompt, entries, core, request.param


def test_engine_chunked_prefill_and_decode_agree_with_the_reference(served):
    """A prompt of 40 prefilled in chunks of 12 beside a decoding row (mixed
    steps, on the split token axis and on the rectangle), 8 tokens decoded
    through the paged latent cache; the selection bias is not zero and the
    model holds ids 4-7 of 16 experts. Logprobs at the served ids, not tokens.
    Both sides float32 at ``highest`` matmul precision (conftest): what is left
    is the order of accumulation (absorbed MLA over paged chunks against
    per-head K and V over one whole sequence, sorted expert rows against one
    expert at a time), about 1e-6 of the logit range, so the tolerance is 1e-4."""
    params, prompt, entries, core, split = served
    sequence = prompt + [e["id"] for e in entries][:-1]
    assert len(entries) == 8 and _distance(entries, prompt, _reference(params, sequence)) < TOL
    steps = core.flight.snapshot(kind="step")
    assert {"mixed", "decode"} <= {s["step_kind"] for s in steps}
    layouts = {s["layout"] for s in steps if s["step_kind"] == "mixed"}
    assert layouts == ({SPLIT} if split else {ROWS_X_T})


_REF_MOE = ref.moe


def _no_shared_expert(h, lp, z):
    return _REF_MOE(h, lp, z) - ref.shared_expert_term(h, lp)


def _not_renormalised(h, lp, z):
    s = jax.nn.sigmoid(h @ lp["router"].astype(c.F32))
    _, idx = jax.lax.top_k(s + lp["router_bias"].astype(c.F32), z["top_k"])
    rows = jnp.arange(h.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, idx].set(z["factor"] * s[rows, idx])


def _bias_in_the_weights(h, lp, z):
    s = jax.nn.sigmoid(h @ lp["router"].astype(c.F32)) + lp["router_bias"].astype(c.F32)
    w, idx = jax.lax.top_k(s, z["top_k"])
    rows = jnp.arange(h.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, idx].set(z["factor"] * w / w.sum(axis=-1, keepdims=True))


#: The reference made wrong in the ways this layer is easy to get wrong: (patch target, replacement or hf edit).
WRONG_REFERENCES = {
    "shared expert left out": ("moe", _no_shared_expert),
    "weights not renormalised over the k choices": ("route", _not_renormalised),
    "selection bias counted into the weights": ("route", _bias_in_the_weights),
    "scaling factor left out": ("hf", {"routed_scaling_factor": 1.0}),
    "another share's experts": ("hf", {"expert_share_rank": 2}),
}


@pytest.mark.parametrize("wrong", WRONG_REFERENCES.keys())
def test_a_reference_made_wrong_is_far_from_what_the_engine_serves(served, wrong, monkeypatch):
    """The same served sample against the reference with one piece of the
    layer wrong: each is off by more than a hundred times the tolerance."""
    params, prompt, entries, _, _ = served
    sequence = prompt + [e["id"] for e in entries][:-1]
    target, change = WRONG_REFERENCES[wrong]
    hf = TOY_HF
    if target == "hf":
        hf = {**TOY_HF, **change}
    else:
        monkeypatch.setattr(ref, target, change)
    assert _distance(entries, prompt, _reference(params, sequence, hf)) > 100 * TOL, wrong


# -- the shares add up ------------------------------------------------------------


def test_the_eight_shares_add_up_to_the_uncut_layer_with_the_shared_expert_once():
    """One MoE layer of 16 routed experts and a shared expert, divided over
    eight holders of 2 experts: what the model's layer gives on each holder
    (its experts' terms and the shared expert, which every holder computes
    whole), summed with the shared expert counted once, equals the uncut
    reference's layer, which holds all 16. float32 both sides: 1e-5 of the
    largest output."""
    whole_hf = {**TOY_HF, "n_routed_experts": 16, "n_routed_experts_published": 16, "expert_share_rank": 0}
    lp = _moe_layer(_weights(_toy(n_routed_experts=16, expert_share_rank=0)))
    h = jax.random.normal(jax.random.PRNGKey(3), (1, 48, 64), jnp.float32)
    want = np.asarray(ref.moe(h[0], lp, ref.shape_of(whole_hf)))
    shared = np.asarray(ref.shared_expert_term(h[0], lp))
    assert np.abs(shared).max() > 0.05 * np.abs(want).max()  # the shared expert is no rounding error here

    total, held_choices = np.zeros_like(want), 0
    for rank in range(8):
        share_hf = {**TOY_HF, "n_routed_experts": 2, "expert_share_rank": rank, "expert_share_chips": 8}
        share = _toy(**{k: share_hf[k] for k in ("n_routed_experts", "expert_share_rank", "expert_share_chips")})
        assert share.moe_expert_first == 2 * rank and share.moe_held_share
        mine = {**lp, **{k: lp[k][2 * rank: 2 * rank + 2] for k in ("w_gate", "w_up", "w_down")}}
        out, counts = llama._mlp_moe_held(mine, h, share, jnp.ones((1, 48), bool))
        # The share's own reference: the same part, from the same weights.
        np.testing.assert_allclose(out[0], ref.moe(h[0], mine, ref.shape_of(share_hf)), atol=1e-5 * np.abs(want).max())
        total += np.asarray(out[0]) - shared
        held_choices += int(counts[2])
        assert int(counts[0]) == 48 * 4 and int(counts[1]) == 0
    np.testing.assert_allclose(total + shared, want, atol=1e-5 * np.abs(want).max())
    assert held_choices == 48 * 4  # every choice landed on exactly one holder


# -- the router and the counters, by hand -------------------------------------------


def test_router_is_sigmoid_plus_bias_renormalised_times_the_factor():
    """``route_tokens`` as the layer calls it against the reference's router
    and against plain numpy, on a bias large enough to change the choice."""
    cfg = _toy()
    lp = _moe_layer(_weights(cfg, bias=0.3))
    h = jax.random.normal(jax.random.PRNGKey(4), (32, 64), jnp.float32)
    weights, topi = moe.route_tokens(lp, h, k=4, f32_logits=True, **llama._routing_kwargs(cfg))
    mix = np.zeros((32, 16), np.float32)
    np.put_along_axis(mix, np.asarray(topi), np.asarray(weights), axis=1)
    np.testing.assert_allclose(mix, ref.route(h, lp, ref.shape_of(TOY_HF)), atol=1e-6)
    s = 1.0 / (1.0 + np.exp(-np.asarray(h, np.float64) @ np.asarray(lp["router"], np.float64)))
    chosen = np.argsort(-(s + np.asarray(lp["router_bias"], np.float64)), axis=1)[:, :4]
    assert (np.sort(chosen, axis=1) == np.sort(np.asarray(topi), axis=1)).all()
    assert (np.sort(chosen, axis=1) != np.sort(np.argsort(-s, axis=1)[:, :4], axis=1)).any()  # the bias chose otherwise
    picked = np.take_along_axis(s, chosen, axis=1)
    np.testing.assert_allclose(np.sort(np.asarray(weights), axis=1), np.sort(2.5 * picked / picked.sum(1, keepdims=True), axis=1),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(weights).sum(axis=1), 2.5, rtol=1e-5)


def test_counters_match_a_count_by_hand_and_leave_padding_out():
    cfg = _toy()
    lp = _moe_layer(_weights(cfg))
    h = jax.random.normal(jax.random.PRNGKey(5), (32, 64), jnp.float32)
    valid = jnp.arange(32) < 20
    kw = dict(num_experts_per_token=4, first=4, routed=16, routing=llama._routing_kwargs(cfg))
    out, counts = moe.moe_mlp_held(lp, h, valid=valid, **kw)
    mine = np.asarray(ref.route(h, lp, ref.shape_of(TOY_HF)))[:20, 4:8] > 0
    assert counts.tolist() == [20 * 4, 0, int(mine.sum()), int(mine.any(axis=0).sum()), 0]
    assert 0 < int(counts[2]) < 20 * 4
    out_all, _ = moe.moe_mlp_held(lp, h, **kw)
    np.testing.assert_allclose(out[:20], out_all[:20], atol=1e-6)  # a token's result does not turn on its neighbours
    assert float(jnp.abs(out[20:]).max()) == 0.0  # a padding token lands nowhere: no identity outputs to give it a term
    assert moe.held_rows_cap(64 * 8, 32, 256) == 128 and moe.held_rows_cap(128 * 8, 32, 256) == 256


@pytest.mark.parametrize("overlap", [True, False], ids=["pipelined", "synchronous"])
def test_step_records_carry_the_plain_bodys_counts(overlap):
    """Every program's counts land in exactly one record: summed over the run
    they are the real tokens x 4 choices x the 2 MoE layers (the dense layer
    routes nothing), none on an identity, a quarter held under even routing."""
    cfg = _toy()
    _, core = _served_logprobs(cfg, _weights(cfg), list(range(1, 41)), 8, chunk=12, overlap=overlap)
    while core.has_work:
        core.step()
    steps = core.flight.snapshot(kind="step")
    tokens = 4 + 40 + 40 + 8 - 2  # both prompts and every decoded token but each row's last (never fed back)
    choices = sum(s["moe_choices"] for s in steps)
    assert choices == pytest.approx(tokens * 4 * 2, abs=2 * 4 * 2)
    assert sum(s["moe_choices_zero"] for s in steps) == 0
    assert 0.12 < sum(s["moe_choices_held"] for s in steps) / choices < 0.4
    assert all(s["moe_experts_touched"] <= 4 * 2 and s["moe_choices_held"] <= s["moe_choices"] for s in steps)
    assert all(s["moe_extra_passes"] == 0 for s in steps)  # near-even routing: the usual pass held every copy that landed here
    assert any(s["kv_tokens_full"] > 0 for s in steps if s["step_kind"] == "decode")
    assert not core.runner._moe_counts_pending or overlap


# -- the dual scan and the int8 stack ----------------------------------------------


# -- the routing bookkeeping against the sort, the gather and the scatter-add it replaced --------


def _held_by_sort(lp, x, *, num_experts_per_token, first, routed, routing, valid, passes):
    """``moe_mlp_held`` as it stood before ISSUE 39, kept as the plain
    reference: the copies that landed here sorted to the front by expert
    (``argsort``, ``bincount``), taken ``held_rows_cap`` rows at a pass in a
    loop whose trip count the routing decides, gathered, and added back by a
    scatter-add. ``passes`` gets every pass's ``(first row, rows, group sizes)``."""
    n, d = x.shape
    k = num_experts_per_token
    held = jax.tree.leaves(lp["w_gate"])[0].shape[-3]
    fused = moe.experts_path(lp) == "fused"
    weights, topi = moe.route_tokens(lp, x, k=k, f32_logits=True, **routing)
    local = topi - first
    here = (local >= 0) & (local < held) & valid[:, None]
    key = jnp.where(here, local, held).reshape(-1)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    sizes = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    n_here = ends[-1]
    is_zero = topi >= routed
    counts = jnp.stack([valid.sum() * k, (is_zero & valid[:, None]).sum(), n_here, (sizes > 0).sum()]).astype(jnp.int32)
    if lp["router"].shape[-1] > routed:
        out = x.astype(jnp.float32) * jnp.where(is_zero, weights, 0.0).sum(axis=-1)[:, None]
    else:
        out = jnp.zeros((n, d), jnp.float32)
    cap = moe.held_rows_cap(n * k, held, lp["router"].shape[-1])
    order = jnp.pad(order, (0, -(n * k) % cap))
    flat_w = weights.reshape(-1)
    if not fused:
        w_gate, w_up, w_down = moe._widen(lp, x.dtype)

    def one_pass(i, acc):
        lo = i * cap
        idx = jax.lax.dynamic_slice(order, (lo,), (cap,))
        tok = idx // k
        rows = x[tok]
        in_pass = jnp.clip(ends, lo, lo + cap) - jnp.clip(ends - sizes, lo, lo + cap)
        jax.debug.callback(lambda lo, rows, sizes: passes.append((int(lo), np.asarray(rows), np.asarray(sizes))),
                           lo, rows, in_pass)
        if fused:
            from dynamo_tpu.ops.pallas_moe import expert_ffn_int8

            down = expert_ffn_int8(rows, lp["w_gate"], lp["w_up"], lp["w_down"], in_pass, interpret=True)
        else:
            hidden = jax.nn.silu(jax.lax.ragged_dot(rows, w_gate, in_pass)) * jax.lax.ragged_dot(rows, w_up, in_pass)
            down = jax.lax.ragged_dot(hidden, w_down, in_pass)
        live = (lo + jnp.arange(cap)) < n_here
        term = jnp.where(live[:, None], down.astype(jnp.float32) * flat_w[idx][:, None], 0.0)
        return acc.at[tok].add(term)

    out = jax.lax.fori_loop(0, -(-n_here // cap), one_pass, out)
    return out.astype(x.dtype), counts


#: (tokens, routing, valid tokens or None for all, routed outputs, arm). Routing: ``even`` as the seed has it;
#: ``all_here`` every choice of every token on the four held experts (more copies than the usual pass takes:
#: the other arm of the ``cond``); ``none_here`` no choice on them. Routed outputs 12 of 16: four identity outputs.
#: Arms: ``ragged`` float32 ``ragged_dot``; ``fused`` the int8 kernel in interpret mode on bf16 tokens; ``sorted``
#: the form prefill-sized calls keep (``DENSE_COPIES`` 0), ``ragged_dot``.
PARITY_CASES = (
    [(tokens, how, None, 16, "ragged") for tokens in (1, 8, 64, 128) for how in ("even", "all_here", "none_here")]
    + [(8, "even", 5, 16, "ragged"), (8, "all_here", 5, 16, "ragged"), (64, "even", 40, 16, "ragged"),
       (64, "all_here", 40, 16, "ragged"), (64, "even", None, 12, "ragged"), (64, "all_here", 40, 12, "ragged"),
       (64, "even", None, 16, "fused"), (64, "all_here", None, 16, "fused"), (128, "even", None, 16, "fused"),
       (8, "even", 5, 16, "fused"), (64, "even", 40, 12, "fused"),
       (64, "even", 40, 12, "sorted"), (64, "all_here", None, 16, "sorted"), (8, "none_here", None, 16, "sorted")])


@pytest.mark.parametrize("tokens, how, n_valid, routed, arm", PARITY_CASES,
                         ids=["-".join(str(v) for v in case) for case in PARITY_CASES])
def test_the_held_layer_lays_its_copies_where_the_sort_did(tokens, how, n_valid, routed, arm, monkeypatch):
    """``moe_mlp_held`` against the algorithm it had (above): the rows handed
    to the experts (as far as a copy landed here) and the group sizes equal
    the reference's exactly, over all its passes; ``HELD_COUNTS`` exactly; the
    output to float32 rounding of a sum taken in another order: 2e-6 of the
    largest output in float32, and in bf16 (the fused arm rounds the float32
    sum once more) one unit in the last place, 2**-7 of it. Rank 1 of 4:
    ``first`` = 4."""
    from dynamo_tpu.models.quant import quantize_params
    from dynamo_tpu.ops import pallas_moe

    if arm == "fused":
        monkeypatch.setenv("DYNAMO_PALLAS_INTERPRET", "1")
        cfg = dataclasses.replace(_toy(hidden_size=128, moe_intermediate_size=128), dtype="bfloat16")
        lp = _moe_layer(quantize_params(llama.init_params(cfg, 1), mode="int8"))
        lp = {**lp, "router_bias": jnp.zeros_like(lp["router_bias"])}
    else:
        cfg = _toy()
        lp = _moe_layer(_weights(cfg))
    if arm == "sorted":
        monkeypatch.setattr(moe, "DENSE_COPIES", 0)
    assert moe.experts_path(lp) == ("fused" if arm == "fused" else "widened")
    mine = (jnp.arange(16) >= 4) & (jnp.arange(16) < 8)
    steer = {"even": 0.0, "all_here": 10.0, "none_here": -10.0}[how]
    lp = {**lp, "router_bias": lp["router_bias"] + steer * mine}
    x = jax.random.normal(jax.random.PRNGKey(tokens), (tokens, cfg.hidden_size), jnp.float32).astype(cfg.dtype)
    valid = jnp.arange(tokens) < (tokens if n_valid is None else n_valid)
    kw = dict(num_experts_per_token=4, first=4, routed=routed, routing=llama._routing_kwargs(cfg), valid=valid)

    ref_passes, seen = [], []
    want, want_counts = _held_by_sort(lp, x, passes=ref_passes, **kw)
    jax.effects_barrier()

    def keep(rows, sizes):
        jax.debug.callback(lambda rows, sizes: seen.append((np.asarray(rows), np.asarray(sizes))), rows, sizes)

    real_ffn, real_ragged = pallas_moe.expert_ffn_int8, jax.lax.ragged_dot

    def ffn(rows, gate, up, down, sizes, layer=None, **kwargs):
        keep(rows, sizes)
        return real_ffn(rows, gate, up, down, sizes, layer, **kwargs)

    def ragged(lhs, rhs, sizes, **kwargs):
        if lhs.shape[-1] == cfg.hidden_size:  # the gate's and the up's product: the rows as dispatched
            keep(lhs, sizes)
        return real_ragged(lhs, rhs, sizes, **kwargs)

    monkeypatch.setattr(pallas_moe, "expert_ffn_int8", ffn)
    monkeypatch.setattr(jax.lax, "ragged_dot", ragged)
    got, counts = moe.moe_mlp_held(lp, x, **kw)
    jax.effects_barrier()

    n_here = int(counts[2])
    # HELD_COUNTS: the reference's four, and whether the pass over every copy's rows ran
    assert counts.tolist() == want_counts.tolist() + [int(n_here > moe.held_rows_cap(tokens * 4, 4, 16))]
    assert {"even": 0 < n_here < tokens * 4 or tokens == 1, "all_here": n_here == int(valid.sum()) * 4,
            "none_here": n_here == 0}[how]
    if how == "all_here" and tokens * 4 > moe.held_rows_cap(tokens * 4, 4, 16):
        assert len(ref_passes) > 1 and int(counts[4]) == 1  # the reference needed its loop: more copies than a pass's rows
    assert len({s.tobytes() for _, s in seen}) == 1 and len({r.shape for r, _ in seen}) == 1  # one pass, whichever arm
    rows, sizes = seen[0]
    ref_passes.sort(key=lambda p: p[0])
    ref_rows = np.concatenate([r for _, r, _ in ref_passes]) if ref_passes else np.zeros((0, cfg.hidden_size))
    assert sizes.tolist() == (sum(s for _, _, s in ref_passes) if ref_passes else np.zeros(4, int)).tolist()
    assert int(sizes.sum()) == n_here and rows.shape[0] >= n_here
    assert (rows[:n_here] == ref_rows[:n_here]).all()
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if arm == "fused":
        np.testing.assert_allclose(got, want, rtol=2.0 ** -7, atol=2.0 ** -7 * np.abs(want).max())
    else:
        np.testing.assert_allclose(got, want, atol=2e-6 * max(np.abs(want).max(), 1e-30))
    if routed == 16:
        assert float(np.abs(got[int(valid.sum()):]).max(initial=0.0)) == 0.0  # a padding token lands nowhere


def test_the_dual_scan_finds_its_experts_in_the_int8_stack(monkeypatch):
    """The whole forward of a quantized toy (a dense layer, then two MoE
    layers that hold a share) with the fused grouped-matmul kernel in interpret
    mode, which reads the stacked int8 experts by a layer index counted from
    the first MoE layer, against the widened ``ragged_dot`` formulation of the
    same weights, rectangle and split token axis: bf16 products both, the
    kernel scales its float32 accumulator where the widened path rounds
    ``qw * scale`` to bf16 first, so 3e-2 of the largest logit."""
    from dynamo_tpu.models.quant import quantize_params

    monkeypatch.setenv("DYNAMO_PALLAS_INTERPRET", "1")
    cfg = dataclasses.replace(_toy(hidden_size=128, moe_intermediate_size=128), dtype="bfloat16")
    params = quantize_params(llama.init_params(cfg, 1), mode="int8")
    assert moe.experts_path(params["layers"]) == "fused" and "dense_layers" in params
    t = 16
    toks = np.random.default_rng(2).integers(1, cfg.vocab_size, size=t)

    def run(split):
        k, v = llama.init_kv_cache(cfg, 6, 8)
        if split is None:
            args = (jnp.asarray(toks)[None], jnp.arange(t)[None], k, v, jnp.asarray([[1, 2]]), (8 + jnp.arange(t))[None],
                    jnp.asarray([t - 1]))
        else:  # one decode slot (a padding row on the null page) and the chunk
            args = (jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.asarray(toks)]),
                    jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.arange(t)]), k, v, jnp.asarray([[0, 0], [1, 2]]),
                    jnp.concatenate([jnp.zeros((1,), jnp.int32), 8 + jnp.arange(t)]), jnp.asarray([0, t]))
        out = llama.forward(params, cfg, *args, attn_impl="reference", split=split, moe_counts=True)
        return np.asarray(out[0][-1], np.float32), out[3].tolist()

    fused, counts = run(None)
    fused_split, counts_split = run((1, 1, t))
    monkeypatch.setenv("DYNAMO_MOE_DISPATCH", "capacity")  # experts_path: not the kernel
    assert moe.experts_path(params["layers"]) == "widened"
    widened, counts_w = run(None)
    assert counts == counts_w == counts_split and counts[0] == t * 4 * 2 and counts[2] > 0
    scale = float(np.abs(widened).max())
    np.testing.assert_allclose(fused, widened, atol=3e-2 * scale)
    np.testing.assert_allclose(fused_split, fused, atol=3e-2 * scale)
