"""Falcon-H1 (ISSUE 46; ``model_type`` ``falcon_h1``): a Mamba-2 mixer beside
GQA attention in every layer, muP multipliers throughout. The config is read
by its own keys and refuses by name what is not served (and the general
branch refuses a state-space config no branch reads); the benchmark's plain
reference is held to the published modelling code (``transformers``'
``FalconH1ForCausalLM`` on copied toy weights, where it imports); the toy is
served over ``/v1/completions`` through ``launch`` with chunked prefill and
decode and agrees with the reference. The slots themselves (admission, finish,
preemption, reuse, the refusals by name) are ``tests/test_hybrid_kda.py``'s
cases over both recurrent kinds; the kernel and the chunked form
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

from benchmark.reference import falcon_h1 as ref  # noqa: E402
from dynamo_tpu.models import llama  # noqa: E402
from dynamo_tpu.models.config import FALCON_H1_34B_HF, PRESETS, TINY_FALCON_H1_HF, ModelConfig  # noqa: E402

CATALOG = pathlib.Path("/opt/skills/guides/model-configs/architectures.jsonl")


def _weights(cfg, seed=2**31 + 46):
    from tests.test_hybrid_kda import _weights_h1

    return _weights_h1(cfg, seed)


def test_from_hf_reads_the_published_keys():
    cfg = ModelConfig.from_hf(FALCON_H1_34B_HF, name="falcon-h1-34b")
    assert (cfg.num_layers, cfg.recurrent_layers, cfg.cache_layers, cfg.layer_group_size) == (72, 72, 72, 0)
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.intermediate_size, cfg.vocab_size) == (
        5120, 20, 4, 128, 21504, 261120)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state_size, cfg.ssm_groups, cfg.ssm_conv_size) == (32, 128, 256, 2, 4)
    assert (cfg.ssm_inner, cfg.ssm_conv_dim) == (4096, 5120)
    assert (cfg.rope_theta, cfg.rope_scaling, cfg.rms_eps, cfg.tie_embeddings, cfg.attn_type) == (1e11, None, 1e-5, False, "gqa")
    assert (cfg.embed_multiplier, cfg.lm_head_multiplier, cfg.attn_in_multiplier, cfg.attn_out_multiplier, cfg.key_multiplier) == (
        5.656854249492381, 0.0078125, 1.0, 0.0375, 0.011048543456039804)
    assert (cfg.mlp_gate_multiplier, cfg.mlp_down_multiplier) == (0.1767766952966369, 0.011160714285714284)
    assert (cfg.ssm_in_multiplier, cfg.ssm_out_multiplier) == (0.25, 0.08838834764831845)
    assert cfg.ssm_multipliers == (0.3535533905932738, 0.25, 0.1767766952966369, 0.5, 0.3535533905932738)
    # A layer holds pages and a slot: 2,048 B of K/V a token, a 4,194,304 B state and a 30,720 B conv state.
    assert cfg.kv_bytes_per_token() == 72 * 2048 and cfg.state_bytes_per_slot() == 72 * (4_194_304 + 30_720)
    assert cfg.state_shapes() == ((32, 256, 128), (3, 40, 128))  # the conv's 5,120 channels in rows of 128 lanes
    layer = (cfg.param_count() - 2 * 261120 * 5120 - 5120) // 72
    assert layer == 31_457_280 + 330_301_440 + 68_351_072 + 2 * 5120 and cfg.param_count() == pytest.approx(33.6e9, rel=2e-3)
    # One of eight equal pipeline stages: the stage keys are read and checked.
    stage = ModelConfig.from_hf({**FALCON_H1_34B_HF, "num_hidden_layers": 9, "num_hidden_layers_published": 72,
                                 "pipeline_stages": 8, "stage_rank": 0}, name="stage")
    assert (stage.num_layers, stage.recurrent_layers, stage.cache_layers) == (9, 9, 9)
    tiny = PRESETS["test-tiny-falcon-h1"]
    assert tiny == dataclasses.replace(ModelConfig.from_hf(TINY_FALCON_H1_HF, name="test-tiny-falcon-h1"), dtype="float32")
    shapes = jax.eval_shape(lambda: llama.init_params(tiny, 0))
    assert set(shapes) == {"embed", "norm_f", "lm_head", "layers"}
    assert shapes["layers"]["w_ssm_in"].shape == (3, 64, 64 + 96 + 4) and shapes["layers"]["ssm_conv"].shape == (3, 4, 96)
    assert tiny.param_count() == sum(x.size for x in jax.tree.leaves(shapes))


@pytest.mark.parametrize("edit, says", [
    ({"mamba_norm_before_gate": True}, "mamba_norm_before_gate True is not served"),
    ({"mamba_rms_norm": False}, "mamba_rms_norm False is not served"),
    ({"mamba_conv_bias": False}, "mamba_conv_bias False is not served"),
    ({"mamba_proj_bias": True}, "mamba_proj_bias True is not served"),
    ({"projectors_bias": True}, "projectors_bias True is not served"),
    ({"attention_bias": True}, "attention_bias True is not served"),
    ({"mlp_bias": True}, "mlp_bias True is not served"),
    ({"attn_layer_indices": [0, 4]}, r"attn_layer_indices \[0, 4\] is not served"),
    ({"rope_scaling": {"rope_type": "yarn", "factor": 4.0}}, "rope_scaling {.*} is not served"),
    ({"mamba_d_ssm": 4608}, "mamba_n_heads 32 x mamba_d_head 128 is not mamba_d_ssm 4608"),
    ({"mamba_d_ssm": None, "mamba_expand": 2}, "mamba_n_heads 32 x mamba_d_head 128 is not mamba_d_ssm 10240"),
    ({"mamba_n_groups": 3}, "mamba_n_heads 32 is not a multiple of mamba_n_groups 3"),
    ({"hidden_act": "gelu"}, "hidden_act 'gelu' is not served"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings True is not served"),
    ({"num_hidden_layers": 9, "num_hidden_layers_published": 72, "pipeline_stages": 9},
     "num_hidden_layers 9 x pipeline_stages 9 .* is not num_hidden_layers_published 72"),
    ({"num_hidden_layers": 9, "num_hidden_layers_published": 72, "pipeline_stages": 8, "stage_rank": 8},
     r"pipeline_stages 8 \(stage_rank 8\)"),
    ({"ssm_multipliers": [1.0, 1.0]}, "ssm_multipliers .* expected 2 and 5 entries"),
], ids=["norm-before-gate", "no-rms-norm", "no-conv-bias", "proj-bias", "projectors-bias", "attention-bias", "mlp-bias", "attn-layer-indices",
        "rope-scaling", "d-ssm", "expand", "groups", "act", "tied", "stages", "stage-rank", "multipliers"])
def test_from_hf_refuses_by_name(edit, says):
    with pytest.raises(ValueError, match=says):
        ModelConfig.from_hf({**FALCON_H1_34B_HF, **edit}, name="t")


@pytest.mark.parametrize("model_type", ["llama", "bamba", "nemotron_h", None])
def test_a_state_space_config_no_branch_reads_is_refused_by_name(model_type):
    """The general branch would serve such a config as the GQA stack it also
    describes, silently (what the tree before ISSUE 46 did with this file)."""
    hf = {k: v for k, v in FALCON_H1_34B_HF.items() if k != "model_type"}
    with pytest.raises(ValueError, match=r"states mamba_chunk_size \(and 15 more mamba_\* / ssm_\* keys\): a state-space layer"):
        ModelConfig.from_hf({**hf, **({"model_type": model_type} if model_type else {})}, name="t")
    plain = {k: v for k, v in hf.items() if not k.startswith(("mamba_", "ssm_"))}
    assert ModelConfig.from_hf({**plain, "model_type": "llama"}, name="t").ssm_heads == 0


def test_the_loader_refuses_the_checkpoint_by_name(tmp_path):
    from dynamo_tpu.models.loader import load_model

    (tmp_path / "config.json").write_text(json.dumps(FALCON_H1_34B_HF))
    with pytest.raises(ValueError, match="model_type 'falcon_h1': the architecture is served .* tensor names are not mapped"):
        load_model(tmp_path)


@pytest.mark.skipif(not CATALOG.exists(), reason="the catalog of public architectures is not on this machine")
def test_the_published_keys_are_the_catalog_rows():
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines()) if r["name"] == "Falcon-H1-34B-Instruct")
    assert row["config"] == FALCON_H1_34B_HF


# -- the reference against the published modelling code ------------------------------------


def _to_torch_model(params, hf):
    """``FalconH1ForCausalLM`` at the toy's sizes with the served tree's
    float32 leaves copied in (a Linear's weight is the leaf transposed; the
    conv's ``[channels, 1, taps]`` the filter transposed)."""
    torch = pytest.importorskip("torch")
    falcon = pytest.importorskip("transformers.models.falcon_h1")
    config = falcon.FalconH1Config(**{k: v for k, v in hf.items() if k != "model_type"}, attn_implementation="eager")
    with torch.no_grad():
        model = falcon.FalconH1ForCausalLM(config).to(torch.float32).eval()
        t = lambda a: torch.from_numpy(np.array(a, np.float32))  # noqa: E731
        model.model.embed_tokens.weight.copy_(t(params["embed"]))
        model.model.final_layernorm.weight.copy_(t(params["norm_f"]))
        model.lm_head.weight.copy_(t(params["lm_head"]).T)
        for i, layer in enumerate(model.model.layers):
            lp = jax.tree.map(lambda x: x[i], params["layers"])  # noqa: B023
            for mod, name in ((layer.input_layernorm, "attn_norm"), (layer.pre_ff_layernorm, "mlp_norm"),
                              (layer.mamba.norm, "ssm_norm")):
                mod.weight.copy_(t(lp[name]))
            for mod, name in ((layer.self_attn.q_proj, "wq"), (layer.self_attn.k_proj, "wk"), (layer.self_attn.v_proj, "wv"),
                              (layer.self_attn.o_proj, "wo"), (layer.feed_forward.gate_proj, "w_gate"),
                              (layer.feed_forward.up_proj, "w_up"), (layer.feed_forward.down_proj, "w_down"),
                              (layer.mamba.in_proj, "w_ssm_in"), (layer.mamba.out_proj, "w_ssm_out")):
                assert mod.bias is None
                mod.weight.copy_(t(lp[name]).T)
            layer.mamba.conv1d.weight.copy_(t(lp["ssm_conv"]).T[:, None, :])
            layer.mamba.conv1d.bias.copy_(t(lp["ssm_conv_bias"]))
            layer.mamba.dt_bias.copy_(t(lp["ssm_dt_bias"]))
            layer.mamba.A_log.copy_(t(lp["ssm_a_log"]))
            layer.mamba.D.copy_(t(lp["ssm_d"]))
    return model, torch


@pytest.mark.parametrize("tokens", [37, 16], ids=["ragged-chunks", "whole-chunks"])
def test_reference_agrees_with_the_published_modelling_code(tokens):
    """``benchmark/reference/falcon_h1.py`` against ``FalconH1ForCausalLM``
    (its ``torch_forward`` path: no fast kernels on this machine) on the same
    float32 toy weights, every multiplier a made-up value of its own, the
    mixer's constants live: the reference's equations are the published
    code's, not this repo's reading of them. The published path computes the
    recurrence in chunks of ``mamba_chunk_size`` 8 (37 tokens: four whole
    chunks and a padded one), the reference token by token. float32 both
    sides: the largest logit difference found is 2e-6 at logits up to 2;
    the limit is 1e-4."""
    cfg = PRESETS["test-tiny-falcon-h1"]
    params = _weights(cfg)
    toks = np.random.default_rng(tokens).integers(1, cfg.vocab_size, size=tokens)
    model, torch = _to_torch_model(params, TINY_FALCON_H1_HF)
    with torch.no_grad():
        want = model(input_ids=torch.from_numpy(toks)[None], use_cache=False, logits_to_keep=0).logits[0].numpy()
    got = np.asarray(jax.jit(functools.partial(ref.forward, hf=TINY_FALCON_H1_HF))(params, tokens=jnp.asarray(toks)))
    assert want.shape == got.shape == (tokens, cfg.vocab_size) and np.abs(want).max() > 0.5
    assert np.abs(got - want).max() < 1e-4


def test_reference_refuses_what_it_does_not_know_and_imports_nothing_of_the_program():
    for edit, says in (({"mamba_norm_before_gate": True}, "mamba_norm_before_gate"), ({"mamba_rms_norm": False}, "mamba_rms_norm"),
                       ({"attn_layer_indices": [1]}, "attn_layer_indices"), ({"mamba_n_groups": 3}, "in 3 groups"),
                       ({"rope_scaling": {"rope_type": "yarn"}}, "no rope scaling")):
        with pytest.raises(ValueError, match=says):
            ref.shape_of({**TINY_FALCON_H1_HF, **edit})
    assert "dynamo_tpu" not in pathlib.Path(ref.__file__).read_text()


# -- the normal path: launch, frontend, EngineCore, ModelRunner, the pipelined loop -----------------


async def test_the_toy_is_served_over_http_and_agrees_with_the_reference():
    """``launch.serve_worker`` + ``serve_frontend`` (what ``--role local``
    brings up) on the toy with live mixer constants: a 40-token prompt goes in
    over ``/v1/completions`` in chunks of 8 while another request decodes,
    then 6 tokens are decoded greedily through pages and slots. The logprob
    the server reports for each token against the reference's log-softmax of
    the same sequence (float32, ``highest``; what is left is the order of
    accumulation): 1e-4 of the largest logit."""
    import aiohttp

    from benchmark import serving

    cfg = PRESETS["test-tiny-falcon-h1"]
    params = _weights(cfg)
    conf = {"name": "test-tiny-falcon-h1", "serve": {"engine": {
        "page_size": 4, "chunk_prefill_tokens": 8, "max_prefill_tokens": 8, "max_batch_size": 4, "max_seq_len": 128,
        "pool_tokens": 512}}}
    handles = await serving.start(conf, cfg, params)
    try:
        await serving.wait_listed(handles)
        prompt = np.random.default_rng(11).integers(1, cfg.vocab_size, size=40).tolist()
        async with aiohttp.ClientSession() as s:
            beside = s.post(handles["base"] + "/v1/completions", json={
                "model": conf["name"], "prompt": [3, 5, 7, 9], "max_tokens": 48, "temperature": 0, "nvext": {"ignore_eos": True}})
            import asyncio

            beside = asyncio.ensure_future(beside)
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
    finally:
        await serving.stop(handles)
    served = doc["choices"][0]["logprobs"]["token_logprobs"]
    assert len(served) == 6
    # Greedy: the served ids are the reference's argmaxes, one token at a time.
    seq, worst, fwd = list(prompt), 0.0, jax.jit(functools.partial(ref.forward, hf=TINY_FALCON_H1_HF))
    for lp in served:
        toks = np.zeros(64, np.int32)
        toks[: len(seq)] = seq
        logits = np.asarray(fwd(params, tokens=jnp.asarray(toks)))[len(seq) - 1]
        z = logits - logits.max()
        worst = max(worst, abs(float(-np.log(np.exp(z).sum())) - lp) / float(np.abs(logits).max()))
        seq.append(int(logits.argmax()))
    assert worst < 1e-4
