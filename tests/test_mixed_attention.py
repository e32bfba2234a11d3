"""Window and full attention layers in one model (ISSUE 26): the HF config is
read layer by layer, one layer scan serves both kinds, the engine's chunked
prefill and decode through the paged cache agree with the benchmark's plain
reference, and models served wrongly (every layer full, every layer windowed,
one RoPE for both kinds) do not. A model whose layers are all alike computes
what it computed before, bit for bit."""

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

from dynamo_tpu.engine.core import LOGPROBS_TOP_K, EngineConfig, EngineCore  # noqa: E402
from dynamo_tpu.engine.runner import ModelRunner  # noqa: E402
from dynamo_tpu.models import llama  # noqa: E402
from dynamo_tpu.models.config import FULL, PRESETS, SLIDING, ModelConfig  # noqa: E402
from dynamo_tpu.protocols.common import PreprocessedRequest, SamplingOptions, StopConditions  # noqa: E402
from dynamo_tpu.runtime.engine import Context  # noqa: E402

MELLUM2 = json.loads((ROOT / "benchmark" / "configs" / "mellum2-12b-a2.5b-int8.json").read_text())
YARN = MELLUM2["rope_parameters"][FULL]
WINDOW = 8
#: 4 layers w, w, w, f; window 8; 4 experts top-2 renormalised; 4 query and 2 KV heads; YaRN on the full layer.
TOY_HF = {
    "model_type": "mellum", "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
    "head_dim": 16, "num_attention_heads": 4, "num_key_value_heads": 2, "num_experts": 4,
    "num_experts_per_tok": 2, "norm_topk_prob": True, "num_hidden_layers": 4,
    "layer_types": [SLIDING, SLIDING, SLIDING, FULL], "mlp_layer_types": ["sparse"] * 4,
    "sliding_window": WINDOW, "use_sliding_window": True, "max_window_layers": 0, "vocab_size": 256,
    "max_position_embeddings": 512, "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
    "rope_parameters": {FULL: dict(YARN), SLIDING: {"rope_type": "default", "rope_theta": 500000}},
}


def _toy() -> ModelConfig:
    return dataclasses.replace(ModelConfig.from_hf(dict(TOY_HF), name="toy-mixed"), dtype="float32")


# -- from_hf --------------------------------------------------------------------


def test_published_config_parses_to_21_sliding_and_7_full_layers_with_two_ropes():
    hf = {k: v for k, v in MELLUM2.items() if k not in ("serve", "rehearsal", "assumed", "reduced_why")}
    cfg = ModelConfig.from_hf(hf, name="mellum2")
    assert cfg.num_layers == 28 and cfg.mixed_attention
    assert cfg.layer_types.count(SLIDING) == 21 and cfg.layer_types.count(FULL) == 7
    assert cfg.layer_types[:4] == (SLIDING, SLIDING, SLIDING, FULL)
    assert cfg.layer_windows()[:4] == (1024, 1024, 1024, 0) and cfg.sliding_window == 1024
    assert cfg.rope_of(SLIDING) == (500000.0, None)
    theta, scaling = cfg.rope_of(FULL)
    assert theta == 500000.0 and scaling["rope_type"] == "yarn" and scaling["factor"] == 16
    assert scaling["original_max_position_embeddings"] == 8192 and (scaling["beta_fast"], scaling["beta_slow"]) == (32, 1)
    assert scaling["attention_factor"] == pytest.approx(1.2772588722239782)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.hidden_size) == (32, 4, 128, 2304)
    assert (cfg.num_experts, cfg.num_experts_per_token, cfg.moe_intermediate_size) == (64, 8, 896)
    assert cfg.moe_norm_topk and not cfg.qk_norm and not cfg.tie_embeddings and cfg.vocab_size == 98304
    assert cfg.param_count() == pytest.approx(12.15e9, rel=1e-3)


@pytest.mark.parametrize("edit, window, kinds", [
    ({"layer_types": None, "rope_parameters": None, "rope_theta": 1e6}, 0, ()),  # max_window_layers 0: no layer, never all
    ({"layer_types": None, "rope_parameters": None, "rope_theta": 1e6, "max_window_layers": 4}, WINDOW, ()),
    ({"layer_types": None, "rope_parameters": None, "rope_theta": 1e6, "max_window_layers": None}, WINDOW, ()),
    ({"layer_types": None, "rope_parameters": None, "rope_theta": 1e6, "max_window_layers": 2}, 0, ()),
    ({"layer_types": [FULL] * 4}, 0, ()),
    ({"layer_types": [SLIDING] * 4}, WINDOW, ()),
    ({}, WINDOW, (SLIDING, SLIDING, SLIDING, FULL)),
], ids=["mwl0", "mwl-all", "mwl-none", "mwl-partial", "all-full", "all-sliding", "mixed"])
def test_window_and_layer_kinds_are_read_layer_by_layer(edit, window, kinds):
    hf = {k: v for k, v in {**TOY_HF, **edit}.items() if v is not None or k == "max_window_layers"}
    cfg = ModelConfig.from_hf(hf, name="t")
    assert (cfg.sliding_window, cfg.layer_types) == (window, kinds)
    assert cfg.layer_windows() == tuple(window if (not kinds or k == SLIDING) else 0 for k in (kinds or [0] * 4))


def test_a_uniform_window_preset_means_what_it_meant():
    cfg = ModelConfig.from_hf({"model_type": "mistral", "hidden_size": 64, "num_attention_heads": 4,
                               "num_hidden_layers": 2, "intermediate_size": 128, "vocab_size": 256,
                               "sliding_window": 4096, "rope_theta": 10000.0}, name="m")
    assert cfg.sliding_window == 4096 and not cfg.layer_types and cfg.rope_theta == 10000.0
    assert PRESETS["mistral-7b"].layer_windows() == (4096,) * 32 and not PRESETS["mistral-7b"].mixed_attention


@pytest.mark.parametrize("edit, says", [
    ({"layer_types": [SLIDING, "linear_attention", SLIDING, FULL]}, "linear_attention"),
    ({"layer_types": [SLIDING, FULL]}, "expected 4 entries"),
    ({"mlp_layer_types": ["sparse", "dense", "sparse", "sparse"]}, "dense"),
    ({"use_sliding_window": False}, "use_sliding_window is false"),
    ({"sliding_window": None}, "sliding_window is not set"),
    ({"rope_parameters": {FULL: dict(YARN)}}, "no entry for"),
    ({"rope_parameters": {FULL: {"rope_type": "yarn", "factor": 16}, SLIDING: {"rope_theta": 1e4}}}, "no rope_theta"),
], ids=["kind", "count", "mlp-kind", "gate-off", "no-window", "rope-missing", "rope-no-theta"])
def test_from_hf_refuses_by_name(edit, says):
    with pytest.raises(ValueError, match=says):
        ModelConfig.from_hf({**TOY_HF, **edit}, name="t")


def test_rope_parameters_without_rope_theta_is_never_theta_10000():
    flat = {**TOY_HF, "layer_types": None, "max_window_layers": None,
            "rope_parameters": {"rope_type": "default", "rope_theta": 500000}}
    flat = {k: v for k, v in flat.items() if v is not None}
    assert ModelConfig.from_hf(flat, name="t").rope_theta == 500000.0
    same = {**TOY_HF, "rope_parameters": {FULL: {"rope_theta": 2e5}, SLIDING: {"rope_theta": 2e5}}}
    cfg = ModelConfig.from_hf(same, name="t")  # one RoPE for both kinds: nothing kept by kind
    assert cfg.rope_theta == 2e5 and cfg.rope_parameters is None and cfg.mixed_attention


def test_loader_refuses_the_unmapped_checkpoint_by_name(tmp_path):
    from dynamo_tpu.models.loader import load_model

    (tmp_path / "config.json").write_text(json.dumps(TOY_HF))
    with pytest.raises(ValueError, match="mellum"):
        load_model(tmp_path)


# -- the engine against the plain reference -------------------------------------


def _weights(cfg, seed=2**31 + 29):
    from benchmark import weights

    return weights.make_weights(cfg, seed, quant="")


@dataclasses.dataclass(frozen=True)
class _EveryLayerWindowed(ModelConfig):
    def layer_windows(self):
        return (self.sliding_window,) * self.num_layers


def _served_logprobs(cfg, params, prompt, n_out, *, chunk):
    """Through EngineCore: the prompt prefilled in ``chunk``-token chunks beside
    a decoding row, then decoded through the paged cache; every generated
    token's logprob and its top 20."""
    page = 4
    runner = ModelRunner(cfg, params, num_pages=64, page_size=page, max_batch_size=2,
                         prefill_bucket=4, attn_impl="reference")
    core = EngineCore(runner, EngineConfig(
        num_pages=64, page_size=page, max_batch_size=2, max_prefill_tokens=chunk, chunk_prefill_tokens=chunk,
        max_seq_len=128, enable_prefix_caching=False))

    def request(tokens, n, logprobs):
        return PreprocessedRequest(
            token_ids=list(tokens), sampling=SamplingOptions(temperature=0.0, logprobs=logprobs),
            stop=StopConditions(max_tokens=n, ignore_eos=True))

    core.add_request(request([7, 9, 11, 13], 40, None), Context())
    for _ in range(3):
        core.step()
    seq = core.add_request(request(prompt, n_out, LOGPROBS_TOP_K + 1), Context())
    entries, kinds = [], set()
    while core.has_work and len(entries) < n_out:
        for s, out in core.step():
            if s is seq:
                entries.extend(out.logprobs or [])
        kinds.add(core.flight.snapshot(kind="step")[-1]["step_kind"])
    assert "mixed" in kinds and "decode" in kinds
    return entries, core


def _distance(entries, prompt, ref_logits):
    """max |served - reference| logprob over the reference's largest |logit|."""
    z = ref_logits - ref_logits.max(axis=-1, keepdims=True)
    ref_lp = z - np.log(np.exp(z).sum(axis=-1, keepdims=True))
    worst = 0.0
    for j, e in enumerate(entries):
        ids = [e["id"]] + [i for i, _ in e["top"]]
        got = np.asarray([e["logprob"]] + [lp for _, lp in e["top"]])
        worst = max(worst, float(np.abs(got - ref_lp[len(prompt) - 1 + j, ids]).max()))
    return worst / float(np.abs(ref_logits).max())


def test_engine_chunked_prefill_and_decode_agree_with_the_reference_and_wrong_models_do_not():
    """Contexts of five to six windows (prompt 40, 8 more decoded; window 8);
    chunks of 12 tokens straddle the window's edge. Both sides float32 at
    ``highest`` matmul precision (conftest): what is left is the order of
    accumulation (paged chunks against one whole sequence), about 1e-6 of the
    logit range, so the tolerance is 1e-4. Each wrong model is off by more
    than a hundred times that."""
    from benchmark.reference import mellum2

    cfg = _toy()
    params = _weights(cfg)
    prompt = np.random.default_rng(5).integers(1, cfg.vocab_size, size=40).tolist()
    entries, core = _served_logprobs(cfg, params, prompt, 8, chunk=12)
    sequence = prompt + [e["id"] for e in entries][:-1]
    ref = np.asarray(jax.jit(functools.partial(mellum2.forward, hf=TOY_HF))(params, tokens=jnp.asarray(sequence)))
    tol = 1e-4
    assert _distance(entries, prompt, ref) < tol
    steps = core.flight.snapshot(kind="step")
    assert all(s["kv_tokens_window"] <= s["kv_tokens_full"] for s in steps)
    assert any(s["kv_tokens_window"] < s["kv_tokens_full"] for s in steps if s["step_kind"] == "decode")
    wrong = {
        "every layer full": dataclasses.replace(cfg, sliding_window=10**6),
        "every layer windowed": _EveryLayerWindowed(**{f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}),
        "one rope": dataclasses.replace(cfg, rope_parameters={k: dict(TOY_HF["rope_parameters"][SLIDING])
                                                             for k in (SLIDING, FULL)}),
    }
    for name, bad in wrong.items():
        got, _ = _served_logprobs(bad, params, prompt, 8, chunk=12)
        seq_bad = prompt + [e["id"] for e in got][:-1]
        ref_bad = np.asarray(jax.jit(functools.partial(mellum2.forward, hf=TOY_HF))(params, tokens=jnp.asarray(seq_bad)))
        assert _distance(got, prompt, ref_bad) > 100 * tol, name


def test_a_model_without_a_windowed_layer_counts_no_key_tokens():
    """The count is a handful of numpy calls a step; a model whose layers are
    all full has nothing to compare, so its steps pay for none of it."""
    cfg = dataclasses.replace(PRESETS["test-tiny"], dtype="float32")
    assert not cfg.sliding_window
    entries, core = _served_logprobs(cfg, llama.init_params(cfg, 0), list(range(1, 21)), 4, chunk=12)
    steps = core.flight.snapshot(kind="step")
    assert len(entries) == 4 and steps
    assert all(s["kv_tokens_full"] == 0 and s["kv_tokens_window"] == 0 for s in steps)
    assert core.runner._kv_pending is None


def test_mixed_model_keeps_its_full_pages_and_releases_its_window_pages():
    """A pool per layer kind (ISSUE 42): the full layer reads the whole
    context, so no page of its pool goes; the sliding layers' pages go back to
    their own pool once they lie wholly behind the window of 8."""
    cfg = _toy()
    entries, core = _served_logprobs(cfg, _weights(cfg), list(range(1, 41)), 8, chunk=12)
    assert len(entries) == 8 and core.config.swa_free_pages and core.window_allocator is not None
    seq = type("S", (), {"tokens": list(range(48)), "num_cached": 47, "pages": list(range(1, 13)),
                         "window_pages": core.window_allocator.allocate(12), "committed_pages": 12})()
    before, window_before = core.allocator.num_free(), core.window_allocator.num_free()
    core._release_out_of_window(seq)
    assert seq.pages == list(range(1, 13)) and core.allocator.num_free() == before
    assert seq.window_pages[:10] == [0] * 10 and 0 not in seq.window_pages[10:]  # (48 - 8) // 4 pages behind the window
    assert core.window_allocator.num_free() == window_before + 10 and core.window_pages_released >= 10


# -- a model whose layers are all alike -----------------------------------------


def _old_forward(params, cfg, tokens, positions, k_cache, v_cache, block_tables, slot_mapping, last_token_index):
    """The GQA text path of ``llama.forward`` as it stood before mixed layers
    (PR 25): one RoPE, one window, the scan over the stacked weights alone."""
    from dynamo_tpu.models.quant import quant_matmul as qmm
    from dynamo_tpu.ops.attention import paged_attention, write_kv
    from dynamo_tpu.ops.norm import rms_norm
    from dynamo_tpu.ops.rope import apply_rope, rope_attention_factor, rope_frequencies
    from dynamo_tpu.parallel.moe import join_expert_stack, split_expert_stack

    b, t = tokens.shape
    nl, npages, ps = k_cache.shape[0], k_cache.shape[1], k_cache.shape[2]
    inv_freq = jnp.asarray(rope_frequencies(cfg.head_dim, theta=cfg.rope_theta, scaling=cfg.rope_scaling))
    attn_mscale = rope_attention_factor(cfg.rope_scaling) ** 2
    x = params["embed"][tokens]
    kf0 = k_cache.reshape(nl * npages, ps, k_cache.shape[3])
    vf0 = v_cache.reshape(nl * npages, ps, v_cache.shape[3])
    moe_layers, expert_stack = split_expert_stack(params["layers"], mesh=None)

    def layer_step(carry, lp):
        x, k_full, v_full, li = carry
        lp = join_expert_stack(lp, expert_stack, li)
        h = rms_norm(x, lp["attn_norm"], eps=cfg.rms_eps, plus_one=cfg.norm_plus_one)
        qp, kp, vp = qmm(h, lp["wq"]), qmm(h, lp["wk"]), qmm(h, lp["wv"])
        q = apply_rope(qp.reshape(b, t, cfg.num_heads, cfg.head_dim), positions, inv_freq)
        k = apply_rope(kp.reshape(b, t, cfg.num_kv_heads, cfg.head_dim), positions, inv_freq)
        v = vp.reshape(b, t, cfg.num_kv_heads, cfg.head_dim)
        if attn_mscale != 1.0:
            q = q * jnp.asarray(attn_mscale, q.dtype)
        k_full, v_full = write_kv(k_full, v_full, k, v, slot_mapping + li * (npages * ps))
        tables_l = block_tables + li * npages
        if cfg.sliding_window > 0:
            attn = paged_attention(q, k_full, v_full, tables_l, positions, impl="reference",
                                   sliding_window=cfg.sliding_window)
        else:
            attn = paged_attention(q, k_full, v_full, tables_l, positions, impl="reference")
        x = x + qmm(attn.reshape(b, t, cfg.q_dim), lp["wo"])
        h2 = rms_norm(x, lp["mlp_norm"], eps=cfg.rms_eps, plus_one=cfg.norm_plus_one)
        return (x + llama._mlp_moe(lp, h2, cfg, None), k_full, v_full, li + 1), None

    (x, k_out, v_out, _), _ = jax.lax.scan(layer_step, (x, kf0, vf0, jnp.int32(0)), moe_layers)
    x = rms_norm(x, params["norm_f"], eps=cfg.rms_eps, plus_one=cfg.norm_plus_one)
    last = jnp.take_along_axis(x, last_token_index[:, None, None], axis=1)[:, 0]
    return qmm(last, params["embed"].T, preferred_element_type=jnp.float32), k_out.reshape(k_cache.shape)


@pytest.mark.parametrize("window", [0, 8], ids=["full", "uniform-window"])
def test_all_alike_moe_model_is_bit_for_bit_the_old_forward(window):
    cfg = dataclasses.replace(PRESETS["test-tiny-moe"], sliding_window=window)
    params = llama.init_params(cfg, 3)
    t, ps = 24, 4
    tokens = np.random.default_rng(1).integers(1, cfg.vocab_size, size=(2, t)).astype(np.int32)
    pos = np.broadcast_to(np.arange(t, dtype=np.int32), (2, t))
    tables = 1 + np.arange(2 * t // ps, dtype=np.int32).reshape(2, -1)
    slots = np.take_along_axis(tables, pos // ps, axis=1) * ps + pos % ps
    args = (jnp.asarray(tokens), jnp.asarray(pos), *llama.init_kv_cache(cfg, 1 + 2 * t // ps, ps),
            jnp.asarray(tables), jnp.asarray(slots), jnp.asarray([t - 1, t // 2], jnp.int32))
    new, k_new, _ = jax.jit(functools.partial(llama.forward, cfg=cfg, attn_impl="reference"), static_argnames=())(
        params, tokens=args[0], positions=args[1], k_cache=args[2], v_cache=args[3], block_tables=args[4],
        slot_mapping=args[5], last_token_index=args[6])
    old, k_old = jax.jit(functools.partial(_old_forward, cfg=cfg))(params, tokens=args[0], positions=args[1],
                                                                    k_cache=args[2], v_cache=args[3],
                                                                    block_tables=args[4], slot_mapping=args[5],
                                                                    last_token_index=args[6])
    assert np.array_equal(np.asarray(new), np.asarray(old)) and np.array_equal(np.asarray(k_new), np.asarray(k_old))
