"""Granite-4.0-H (ISSUE 49; ``model_type`` ``granitemoehybrid``): Mamba-2
layers that stand alone, one GQA layer without RoPE a period, routed experts
beside a shared one in every layer, a tied head, four multipliers. The config
is read by its own keys and refuses by name what is not served; the
benchmark's plain reference is held to the published modelling code
(``transformers``' ``GraniteMoeHybridForCausalLM`` on copied toy weights, where
it imports); the toy is served over ``/v1/completions`` through ``launch``
with chunked prefill and decode and agrees with the reference. The slots
themselves (admission, finish, preemption, reuse, a row that joins a running
batch, the refusals by name) are ``tests/test_hybrid_kda.py``'s cases over the
recurrent kinds (``mamba2-alone``); the kernel's head shapes
``tests/test_pallas_mamba.py``'s."""

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

from benchmark.reference import granite_hybrid as ref  # noqa: E402
from dynamo_tpu.models import llama  # noqa: E402
from dynamo_tpu.models.config import GRANITE_4_H_SMALL_HF, PRESETS, TINY_GRANITE_HYBRID_HF, ModelConfig  # noqa: E402

CATALOG = pathlib.Path("/opt/skills/guides/model-configs/architectures.jsonl")
STAGE = {"num_hidden_layers": 10, "num_hidden_layers_published": 40, "pipeline_stages": 4, "stage_rank": 0}


def _weights(cfg, seed=2**31 + 49):
    from tests.test_hybrid_kda import _weights_granite

    return _weights_granite(cfg, seed)


def test_from_hf_reads_the_published_keys():
    cfg = ModelConfig.from_hf(GRANITE_4_H_SMALL_HF, name="granite-4.0-h-small")
    assert (cfg.num_layers, cfg.recurrent_layers, cfg.cache_layers, cfg.layer_group_size, cfg.period_attn_index) == (40, 36, 4, 10, 5)
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.vocab_size) == (4096, 32, 8, 128, 100352)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state_size, cfg.ssm_groups, cfg.ssm_conv_size) == (128, 64, 128, 1, 4)
    assert (cfg.ssm_inner, cfg.ssm_conv_dim, cfg.ssm_heads_per_row) == (8192, 8448, 2)
    assert (cfg.num_experts, cfg.num_experts_per_token, cfg.moe_intermediate_size, cfg.shared_expert_size) == (72, 10, 768, 1536)
    assert (cfg.moe_scoring, cfg.moe_norm_topk, cfg.moe_held_share, cfg.shared_expert_gated, cfg.first_k_dense) == (
        "softmax", True, False, False, 0)
    # No rotary embedding (the identity table), the softmax scale the config's own and not 128 ** -0.5, a tied head.
    assert cfg.rope_scaling == {"rope_type": "nope"} and cfg.attn_scale == 0.0078125 and cfg.tie_embeddings
    assert (cfg.embed_multiplier, cfg.lm_head_multiplier, cfg.residual_multiplier, cfg.rms_eps) == (12.0, 1 / 16, 0.22, 1e-5)
    assert cfg.ssm_multipliers == (1.0,) * 5 and (cfg.ssm_in_multiplier, cfg.ssm_out_multiplier, cfg.key_multiplier) == (1.0, 1.0, 1.0)
    # A Mamba layer holds a slot and no pages, the attention layer pages and no slot: 4,096 B of K/V a token an
    # attention layer; a 4,194,304 B state with two heads of 64 side by side on the lanes, and the conv state's 66
    # rows of lanes (8,448 channels, 50,688 B) held in 72, whole sublane tiles: 55,296 B.
    assert cfg.kv_bytes_per_token() == 4 * 4096 and cfg.state_bytes_per_slot() == 36 * (4_194_304 + 55_296)
    assert cfg.state_shapes() == ((64, 128, 128), (3, 72, 128))
    # ISSUE 49's count: 40 x 698.7 M of FFN, 36 x 102.2 M of mixer, 4 x 41.9 M of attention, 411.0 M tied.
    ffn = 72 * 3 * 4096 * 768 + 4096 * 72 + 3 * 4096 * 1536
    mixer = 4096 * (8192 + 8448 + 128) + 8192 * 4096 + 5 * 8448 + 3 * 128 + 8192
    attn = 2 * 4096 * 4096 + 2 * 4096 * 1024
    assert ffn == pytest.approx(698.7e6, rel=1e-3) and mixer == pytest.approx(102.2e6, rel=2e-3) and attn == pytest.approx(41.9e6, rel=2e-3)
    assert cfg.param_count() == 40 * (ffn + 2 * 4096) + 36 * mixer + 4 * attn + 100352 * 4096 + 4096
    assert cfg.param_count() == pytest.approx(32.2e9, rel=2e-3)
    # One of four equal pipeline stages, one whole period: the stage keys are read and checked; layer_types stays whole.
    stage = ModelConfig.from_hf({**GRANITE_4_H_SMALL_HF, **STAGE}, name="stage")
    assert (stage.num_layers, stage.recurrent_layers, stage.cache_layers, stage.period_attn_index) == (10, 9, 1, 5)
    tiny = PRESETS["test-tiny-granite-hybrid"]
    assert tiny == dataclasses.replace(ModelConfig.from_hf(TINY_GRANITE_HYBRID_HF, name="test-tiny-granite-hybrid"), dtype="float32")
    assert (tiny.num_layers, tiny.recurrent_layers, tiny.cache_layers, tiny.layer_group_size, tiny.period_attn_index) == (8, 6, 2, 4, 2)
    assert all(m != 1.0 for m in (tiny.embed_multiplier, tiny.lm_head_multiplier, tiny.residual_multiplier,
                                  tiny.attn_scale * tiny.head_dim**0.5))
    shapes = jax.eval_shape(lambda: llama.init_params(tiny, 0))
    assert set(shapes) == {"embed", "norm_f", "layers", "ssm_layers", "attn_layers"}  # tied: no lm_head
    assert "wq" not in shapes["layers"] and shapes["layers"]["router"].shape == (8, 64, 6)
    assert shapes["ssm_layers"]["w_ssm_in"].shape == (6, 64, 64 + 80 + 4) and shapes["attn_layers"]["wk"].shape == (2, 64, 32)
    assert tiny.param_count() == sum(x.size for x in jax.tree.leaves(shapes))


@pytest.mark.parametrize("edit, says", [
    ({"mamba_proj_bias": True}, "mamba_proj_bias True is not served"),
    ({"attention_bias": True}, "attention_bias True is not served"),
    ({"mamba_conv_bias": False}, "mamba_conv_bias False is not served"),
    ({"position_embedding_type": "rope"}, "position_embedding_type 'rope' is not served for model_type 'granitemoehybrid': only 'nope'"),
    ({"rope_scaling": {"rope_type": "yarn", "factor": 4.0}}, "rope_scaling {.*} is not served"),
    ({"mamba_n_heads": 96}, "mamba_n_heads 96 x mamba_d_head 64 is not mamba_expand x hidden_size 8192"),
    ({"mamba_n_groups": 3}, "mamba_n_heads 128 is not a multiple of mamba_n_groups 3"),
    ({"mamba_n_groups": 2}, "mamba_n_groups 2 is not served for model_type 'granitemoehybrid': only 1"),
    ({"num_local_experts": 0}, "num_local_experts 0 is not served"),
    ({"layer_types": ["mamba"] * 5 + ["full_attention"] + ["mamba"] * 34}, r"layer_types holds \['full_attention'\]"),
    ({"layer_types": ["mamba"] * 40}, r"layer_types with 'attention' at \[\] is not served"),
    ({"layer_types": (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 3 + ["mamba"] * 4 + ["attention"] + ["mamba"] * 5},
     r"layer_types with 'attention' at \[5, 15, 25, 34\] is not served"),
    ({**STAGE, "num_hidden_layers": 8, "num_hidden_layers_published": 32}, "num_hidden_layers 8 is not whole periods of 10 layers"),
    ({**STAGE, "pipeline_stages": 5}, "num_hidden_layers 10 x pipeline_stages 5 .* is not num_hidden_layers_published 40"),
    ({"hidden_act": "gelu"}, "hidden_act 'gelu' is not served"),
    ({"normalization_function": "layernorm"}, "normalization_function 'layernorm' is not served"),
], ids=["proj-bias", "attention-bias", "no-conv-bias", "rope", "rope-scaling", "heads", "groups", "two-groups", "dense-sibling",
        "layer-kind", "no-attention", "ragged-periods", "broken-period", "stages", "act", "norm"])
def test_from_hf_refuses_by_name(edit, says):
    with pytest.raises(ValueError, match=says):
        ModelConfig.from_hf({**GRANITE_4_H_SMALL_HF, **edit}, name="t")


def test_a_state_the_kernel_does_not_tile_is_refused_where_the_kernel_is_chosen(monkeypatch):
    """On a chip (``impl`` "pallas", no interpreter) a decode row never reaches
    ``recurrent_step`` on gathered rows, whatever the model's size: heads of 48
    channels fill no lane tile, alone or side by side."""
    from dynamo_tpu.models import mamba2

    monkeypatch.delenv("DYNAMO_PALLAS_INTERPRET", raising=False)
    cfg = ModelConfig.from_hf({**TINY_GRANITE_HYBRID_HF, "mamba_d_head": 48, "mamba_n_heads": 4, "hidden_size": 192, "num_attention_heads": 4,
                               "num_key_value_heads": 2}, name="t")
    assert cfg.ssm_heads_per_row == 1
    rows, g, hg, p, n = 2, 1, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state_size
    state = jnp.zeros((3, hg, n, p), jnp.float32)
    args = (state, jnp.asarray([1, 2]), jnp.asarray([False, True]), jnp.zeros((rows, 1, g, hg, p)), jnp.zeros((rows, 1, g, n)),
            jnp.zeros((rows, 1, g, n)), jnp.zeros((rows, 1, g, hg)), -jnp.ones((g, hg)))
    with pytest.raises(ValueError, match="mamba_d_head 48 .* is not a shape the decode kernel tiles"):
        mamba2._rows_update(*args, impl="pallas")
    y, new = mamba2._rows_update(*args, impl="reference")  # the CPU tests' toys keep the plain step
    assert y.shape == (rows, 1, g, hg, p) and new.shape == state.shape


def test_the_loader_refuses_the_checkpoint_by_name(tmp_path):
    from dynamo_tpu.models.loader import load_model

    (tmp_path / "config.json").write_text(json.dumps(GRANITE_4_H_SMALL_HF))
    with pytest.raises(ValueError, match="model_type 'granitemoehybrid': the architecture is served .* tensor names are not mapped"):
        load_model(tmp_path)


@pytest.mark.skipif(not CATALOG.exists(), reason="the catalog of public architectures is not on this machine")
def test_the_published_keys_are_the_catalog_rows():
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines()) if r["name"] == "granite-4.0-h-small")
    assert row["config"] == GRANITE_4_H_SMALL_HF


# -- the reference against the published modelling code ------------------------------------


def _to_torch_model(params, hf):
    """``GraniteMoeHybridForCausalLM`` at the toy's sizes with the served tree's
    float32 leaves copied in (a Linear's weight is the leaf transposed; the
    conv's ``[channels, 1, taps]`` the filter transposed; an expert's
    ``input_linear`` ``[2 f, d]`` the gate's leaf over the up's, transposed)."""
    torch = pytest.importorskip("torch")
    granite = pytest.importorskip("transformers.models.granitemoehybrid")
    config = granite.GraniteMoeHybridConfig(**{k: v for k, v in hf.items() if k != "model_type"}, attn_implementation="eager")
    with torch.no_grad():
        model = granite.GraniteMoeHybridForCausalLM(config).to(torch.float32).eval()
        t = lambda a: torch.from_numpy(np.array(a, np.float32))  # noqa: E731
        model.model.embed_tokens.weight.copy_(t(params["embed"]))
        model.model.norm.weight.copy_(t(params["norm_f"]))
        assert model.lm_head.weight is model.model.embed_tokens.weight and model.model.rotary_emb is None  # tied; nope
        seen = {"mamba": 0, "attention": 0}
        for i, layer in enumerate(model.model.layers):
            lp = jax.tree.map(lambda x: x[i], params["layers"])  # noqa: B023
            kind = hf["layer_types"][i]
            j, seen[kind] = seen[kind], seen[kind] + 1
            layer.input_layernorm.weight.copy_(t(lp["attn_norm"]))
            layer.post_attention_layernorm.weight.copy_(t(lp["mlp_norm"]))
            moe = layer.block_sparse_moe
            moe.router.layer.weight.copy_(t(lp["router"]).T)
            moe.input_linear.weight.copy_(torch.cat([t(lp["w_gate"]), t(lp["w_up"])], dim=-1).transpose(1, 2))
            moe.output_linear.weight.copy_(t(lp["w_down"]).transpose(1, 2))
            layer.shared_mlp.input_linear.weight.copy_(torch.cat([t(lp["w_shared_gate"]), t(lp["w_shared_up"])], dim=-1).T)
            layer.shared_mlp.output_linear.weight.copy_(t(lp["w_shared_down"]).T)
            if kind == "attention":
                ap = jax.tree.map(lambda x: x[j], params["attn_layers"])  # noqa: B023
                for mod, name in ((layer.self_attn.q_proj, "wq"), (layer.self_attn.k_proj, "wk"),
                                  (layer.self_attn.v_proj, "wv"), (layer.self_attn.o_proj, "wo")):
                    assert mod.bias is None
                    mod.weight.copy_(t(ap[name]).T)
                continue
            sp = jax.tree.map(lambda x: x[j], params["ssm_layers"])  # noqa: B023
            layer.mamba.norm.weight.copy_(t(sp["ssm_norm"]))
            layer.mamba.in_proj.weight.copy_(t(sp["w_ssm_in"]).T)
            layer.mamba.out_proj.weight.copy_(t(sp["w_ssm_out"]).T)
            layer.mamba.conv1d.weight.copy_(t(sp["ssm_conv"]).T[:, None, :])
            layer.mamba.conv1d.bias.copy_(t(sp["ssm_conv_bias"]))
            layer.mamba.dt_bias.copy_(t(sp["ssm_dt_bias"]))
            layer.mamba.A_log.copy_(t(sp["ssm_a_log"]))
            layer.mamba.D.copy_(t(sp["ssm_d"]))
    return model, torch


@pytest.mark.parametrize("tokens", [37, 16], ids=["ragged-chunks", "whole-chunks"])
def test_reference_agrees_with_the_published_modelling_code(tokens):
    """``benchmark/reference/granite_hybrid.py`` against
    ``GraniteMoeHybridForCausalLM`` (its ``torch_forward`` path: no fast
    kernels on this machine) on the same float32 toy weights: two periods of
    ``[mamba, mamba, attention, mamba]``, 6 experts top-3 beside a shared one,
    every multiplier a made-up value that is not 1, the mixers' constants
    live. The reference's equations are the published code's, not this
    repo's reading of them. The published path computes the recurrence in
    chunks of ``mamba_chunk_size`` 8 (37 tokens: four whole chunks and a padded
    one), the reference token by token. float32 both sides: the largest logit
    difference found is 1e-6 at logits up to 1; the limit is 1e-4."""
    cfg = PRESETS["test-tiny-granite-hybrid"]
    params = _weights(cfg)
    toks = np.random.default_rng(tokens).integers(1, cfg.vocab_size, size=tokens)
    model, torch = _to_torch_model(params, TINY_GRANITE_HYBRID_HF)
    with torch.no_grad():
        want = model(input_ids=torch.from_numpy(toks)[None], use_cache=False, logits_to_keep=0).logits[0].numpy()
    got = np.asarray(jax.jit(functools.partial(ref.forward, hf=TINY_GRANITE_HYBRID_HF))(params, tokens=jnp.asarray(toks)))
    assert want.shape == got.shape == (tokens, cfg.vocab_size) and np.abs(want).max() > 0.3
    assert np.abs(got - want).max() < 1e-4


def test_reference_refuses_what_it_does_not_know_and_imports_nothing_of_the_program():
    for edit, says in (({"position_embedding_type": "rope"}, "position_embedding_type"), ({"mamba_n_groups": 2}, "mamba_n_groups"),
                       ({"mamba_conv_bias": False}, "mamba_conv_bias"), ({"tie_word_embeddings": False}, "tie_word_embeddings"),
                       ({"mamba_n_heads": 3}, "against mamba_expand x hidden_size"),
                       ({"layer_types": ["mamba", "full_attention"] * 4}, "layer_types")):
        with pytest.raises(ValueError, match=says):
            ref.shape_of({**TINY_GRANITE_HYBRID_HF, **edit})
    assert "dynamo_tpu" not in pathlib.Path(ref.__file__).read_text()
    # The published rule (the k largest logits, softmax over those) is the softmax over all renormalised over the chosen.
    cfg = PRESETS["test-tiny-granite-hybrid"]
    lp = jax.tree.map(lambda x: x[0], _weights(cfg)["layers"])
    v = jax.random.normal(jax.random.PRNGKey(2), (9, 64), jnp.float32)
    got = np.asarray(ref.gates(v, lp, ref.shape_of(TINY_GRANITE_HYBRID_HF)))
    probs = np.asarray(jax.nn.softmax(v @ lp["router"], axis=-1))
    assert ((got > 0).sum(axis=1) == 3).all()
    np.testing.assert_allclose(got, np.where(got > 0, probs, 0) / np.where(got > 0, probs, 0).sum(axis=1, keepdims=True), atol=1e-6)


# -- the normal path: launch, frontend, EngineCore, ModelRunner, the pipelined loop -----------------


async def test_the_toy_is_served_over_http_and_agrees_with_the_reference():
    """``launch.serve_worker`` + ``serve_frontend`` (what ``--role local``
    brings up) on the toy with live mixer constants: a 40-token prompt goes in
    over ``/v1/completions`` in chunks of 8 while another request decodes,
    then 6 tokens are decoded greedily through the attention layers' pages and
    the Mamba layers' slots. The logprob the server reports for each token
    against the reference's log-softmax of the same sequence (float32,
    ``highest``; what is left is the order of accumulation, the chunked form
    against token by token): 1e-4 of the largest logit."""
    import asyncio

    import aiohttp

    from benchmark import serving

    cfg = PRESETS["test-tiny-granite-hybrid"]
    params = _weights(cfg)
    conf = {"name": "test-tiny-granite-hybrid", "serve": {"engine": {
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
        assert all(x["kv_tokens_full"] > 0 for x in steps if x["layout"])  # one attention layer's key tokens
    finally:
        await serving.stop(handles)
    served = doc["choices"][0]["logprobs"]["token_logprobs"]
    assert len(served) == 6
    # Greedy: the served ids are the reference's argmaxes, one token at a time.
    seq, worst, fwd = list(prompt), 0.0, jax.jit(functools.partial(ref.forward, hf=TINY_GRANITE_HYBRID_HF))
    for lp in served:
        toks = np.zeros(64, np.int32)
        toks[: len(seq)] = seq
        logits = np.asarray(fwd(params, tokens=jnp.asarray(toks)))[len(seq) - 1]
        z = logits - logits.max()
        worst = max(worst, abs(float(-np.log(np.exp(z).sum())) - lp) / float(np.abs(logits).max()))
        seq.append(int(logits.argmax()))
    assert worst < 1e-4
