"""A hybrid stack (ISSUE 40; Ling-3.0-flash's ``bailing_hybrid``): delta-rule
linear-attention (KDA) layers whose per-sequence state lives in slots beside
the paged latent cache, one latent-attention layer a period, leading dense
FFNs, and a group-limited sigmoid router of which a whole group is held. The
config is read by its own keys and refuses by name what is not served; the
engine's chunked prefill and decode through slots and pages agree with the
benchmark's plain reference (and do not once the carry across a chunk edge,
the zeroing of a taken slot or the slot indirection is broken); the chunkwise
form is the decode step taken 64 times; rows that swap places keep their
states; a preempted sequence's second run equals its first; the eight shares
add up to the uncut layer with the groups on; and a model with recurrent
layers never matches a prefix and is refused by page transfer and the KV
router.

Since ISSUE 46 the slot tests run over **both recurrent kinds** (``KINDS``):
the KDA hybrid, whose recurrent layers hold a slot *instead of* pages, and a
model with a Mamba-2 mixer beside the GQA attention of every layer
(Falcon-H1's ``falcon_h1``, ``models/mamba2.py``), whose every layer holds a
slot *and* pages; the predicate they share is ``cfg.recurrent_layers``."""

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

from benchmark.reference import falcon_h1 as ref_h1  # noqa: E402
from benchmark.reference import granite_hybrid as ref_granite  # noqa: E402
from benchmark.reference import ling_3_flash as ref  # noqa: E402
from dynamo_tpu.engine.allocator import SlotAllocator  # noqa: E402
from dynamo_tpu.engine.core import EngineConfig, EngineCore  # noqa: E402
from dynamo_tpu.engine.runner import ROWS_X_T, SPLIT, ModelRunner, StepBatch  # noqa: E402
from dynamo_tpu.engine.sequence import SeqStatus  # noqa: E402
from dynamo_tpu.models import kda, llama, mamba2  # noqa: E402
from dynamo_tpu.models.config import (  # noqa: E402
    LING_3_FLASH_HF, PRESETS, TINY_FALCON_H1_HF, TINY_GRANITE_HYBRID_HF, TINY_HYBRID_HF, ModelConfig)
from dynamo_tpu.protocols.common import PreprocessedRequest, SamplingOptions, StopConditions  # noqa: E402
from dynamo_tpu.runtime.engine import Context  # noqa: E402
from tests.test_mixed_attention import _distance  # noqa: E402  (max |served - reference| logprob over the largest |logit|)
from tests.test_shortcut_moe import _served_logprobs  # noqa: E402

CATALOG = pathlib.Path("/opt/skills/guides/model-configs/architectures.jsonl")
TOL = 1e-4


def _toy(**edit) -> ModelConfig:
    return dataclasses.replace(ModelConfig.from_hf({**TINY_HYBRID_HF, **edit}, name="toy-hybrid"), dtype="float32")


def _weights(cfg, seed=2**31 + 40, bias=0.05):
    """The benchmark's weights (plain float32) with a selection bias that
    changes choices and, in half the heads of every KDA layer, a slow decay
    (alpha in about 0.95-0.999): a token is still felt chunks later, so a
    state that is not carried, not zeroed or not the row's own moves the logits."""
    from benchmark import weights
    from tools.kda_state_check import slow_decay

    params = weights.make_weights(cfg, seed, quant="")
    shape = params["layers"]["router_bias"].shape
    params["layers"]["router_bias"] = bias * jax.random.normal(jax.random.PRNGKey(7), shape, jnp.float32)
    layers = [slow_decay(jax.tree.map(lambda x: x[i], params["kda_layers"]), cfg, seed + i) for i in range(cfg.recurrent_layers)]
    params["kda_layers"] = jax.tree.map(lambda *xs: jnp.stack(xs), *layers)
    return params


def _toy_h1(**edit) -> ModelConfig:
    return dataclasses.replace(ModelConfig.from_hf({**TINY_FALCON_H1_HF, **edit}, name="toy-h1"), dtype="float32")


def _weights_h1(cfg, seed=2**31 + 46):
    """The benchmark's weights (plain float32) with the mixer's constants made
    live: in half the heads of every layer a small step (dt about 0.01-0.05
    against A = -1: a decay of 0.95-0.99 a token, so a token is still felt
    chunks later), a skip weight D and a conv bias that are not 1 and 0."""
    from benchmark import weights

    params = weights.make_weights(cfg, seed, quant="")
    _live_mixer(params["layers"], seed)
    return params


def _live_mixer(layers, seed):
    """The mixers' constants of a stack of layers made live, in place."""
    keys = jax.random.split(jax.random.PRNGKey(seed % 2**31), 3)
    shape = layers["ssm_dt_bias"].shape  # [layers, heads]
    slow = jnp.arange(shape[1])[None, :] < shape[1] // 2
    layers["ssm_dt_bias"] = jnp.where(slow, jax.random.uniform(keys[0], shape, jnp.float32, -4.6, -3.0), 0.0)
    layers["ssm_a_log"] = jnp.zeros(shape, jnp.float32)
    layers["ssm_d"] = jax.random.normal(keys[1], shape, jnp.float32)
    layers["ssm_conv_bias"] = 0.1 * jax.random.normal(keys[2], layers["ssm_conv_bias"].shape, jnp.float32)


def _toy_granite(**edit) -> ModelConfig:
    return dataclasses.replace(ModelConfig.from_hf({**TINY_GRANITE_HYBRID_HF, **edit}, name="toy-granite"), dtype="float32")


def _weights_granite(cfg, seed=2**31 + 49):
    """The benchmark's weights (plain float32) with the constants of the
    mixers that stand alone (``ssm_layers``) made live as ``_weights_h1``'s are."""
    from benchmark import weights

    params = weights.make_weights(cfg, seed, quant="")
    _live_mixer(params["ssm_layers"], seed)
    return params


#: The recurrent kinds: how a toy of each is made, its plain reference, and
#: the layer function whose slot handling the tests break. ``mamba2`` is the
#: mixer beside every layer's attention, ``mamba2-alone`` the mixer as a layer
#: of its own in periods with one GQA layer. (``tests/test_solar_open2.py`` adds a fourth, ``kda-gqa``, and runs
#: the cases below for it from its own file, so that this one, the longest of the suite, stays one worker's share.)
KINDS = {
    "kda": dict(toy=_toy, weights=_weights, hf=TINY_HYBRID_HF, ref=ref, module=kda, layer="kda_attention"),
    "mamba2": dict(toy=_toy_h1, weights=_weights_h1, hf=TINY_FALCON_H1_HF, ref=ref_h1, module=mamba2, layer="mamba_mixer"),
    "mamba2-alone": dict(toy=_toy_granite, weights=_weights_granite, hf=TINY_GRANITE_HYBRID_HF, ref=ref_granite, module=mamba2,
                         layer="mamba_mixer"),
}
both_kinds = pytest.mark.parametrize("kind", sorted(KINDS))


def _reference(params, sequence, hf=TINY_HYBRID_HF, kind="kda"):
    return np.asarray(jax.jit(functools.partial(KINDS[kind]["ref"].forward, hf=hf))(params, tokens=jnp.asarray(sequence)))


def _model(kind):
    k = KINDS[kind]
    cfg = k["toy"]()
    params = k["weights"](cfg)
    return cfg, params, functools.partial(_reference, params, hf=k["hf"], kind=kind)


# -- from_hf --------------------------------------------------------------------------


def test_from_hf_reads_the_published_keys():
    cfg = ModelConfig.from_hf({**LING_3_FLASH_HF, "num_hidden_layers": 18}, name="ling")
    assert (cfg.num_layers, cfg.recurrent_layers, cfg.cache_layers, cfg.layer_group_size) == (18, 15, 3, 6)
    assert (cfg.hidden_size, cfg.num_heads, cfg.head_dim, cfg.intermediate_size, cfg.vocab_size) == (2560, 32, 128, 6144, 157184)
    assert (cfg.attn_type, cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim) == (
        "mla", 0, 512, 128, 64, 128)
    assert cfg.rope_theta == 6e6 and cfg.rope_scaling is None and cfg.rms_eps == 1e-6
    assert (cfg.num_experts, cfg.routed_experts, cfg.num_experts_per_token, cfg.moe_intermediate_size) == (512, 512, 8, 768)
    assert (cfg.moe_scoring, cfg.moe_router_bias, cfg.moe_norm_topk, cfg.moe_routed_scaling) == ("sigmoid", True, True, 2.5)
    assert (cfg.moe_n_group, cfg.moe_topk_group, cfg.first_k_dense, cfg.shared_expert_size) == (8, 4, 2, 768)
    assert (cfg.kda_conv_size, cfg.kda_lower_bound) == (4, -5.0) and not cfg.moe_held_share
    assert cfg.kv_bytes_per_token() == 3 * (512 + 128) * 2
    assert cfg.state_bytes_per_slot() == 15 * (32 * 128 * 128 * 4 + 3 * 3 * 4096 * 2)
    held = ModelConfig.from_hf({**LING_3_FLASH_HF, "num_hidden_layers": 18, "num_experts": 64,
                                "n_routed_experts_published": 512, "expert_share_rank": 3}, name="share")
    assert (held.num_experts, held.routed_experts, held.moe_expert_first, held.moe_held_share) == (64, 512, 192, True)
    assert PRESETS["ling-3.0-flash-30l"] == ModelConfig.from_hf(
        {**LING_3_FLASH_HF, "num_hidden_layers": 30}, name="ling-3.0-flash-30l")
    assert "ling-3.0-flash" not in PRESETS  # the whole model's name waits for the clamped SwiGLU
    tiny = PRESETS["test-tiny-hybrid"]
    assert (tiny.num_layers, tiny.recurrent_layers, tiny.cache_layers, tiny.num_experts, tiny.routed_experts) == (6, 4, 2, 8, 16)
    shapes = jax.eval_shape(lambda: llama.init_params(tiny, 0))
    assert set(shapes) == {"embed", "norm_f", "lm_head", "layers", "dense_layers", "kda_layers", "mla_layers"}
    assert "wq" not in shapes["layers"] and "w_q" not in shapes["layers"] and shapes["layers"]["router"].shape == (5, 64, 16)
    assert shapes["kda_layers"]["wq"].shape == (4, 64, 64) and shapes["mla_layers"]["w_out_gate"].shape == (2, 64, 4)
    assert tiny.param_count() == sum(x.size for x in jax.tree.leaves(shapes)) - 5 * 16  # the bias is no parameter


@pytest.mark.parametrize("edit, says", [
    ({"num_hidden_layers": 42}, "expert_swiglu_limit_list is 4 at layer 35, a layer held here"),
    ({"num_hidden_layers": 36}, "expert_swiglu_limit_list is 4 at layer 35"),
    ({"expert_swiglu_limit_list": [0] * 17 + [4]}, "expert_swiglu_limit_list is 4 at layer 17, a layer held here"),
    ({"num_experts": 32, "n_routed_experts_published": 512}, "a held share of 32 experts splits a routing group of 64"),
    ({"num_experts": 64, "n_routed_experts_published": 512, "expert_share_rank": 8}, r"experts \[512, 576\) lie outside the 512"),
    ({"num_hidden_layers": 20}, "layer_group_size 6 over 20 layers is not served"),
    ({"first_k_dense_replace": 6}, "first_k_dense_replace 6 reaches the first latent-attention layer"),
    ({"use_kda_lora": True}, "use_kda_lora True is not served"),
    ({"value_norm": True}, "value_norm True is not served"),
    ({"gated_attention_proj_granularity_type": "element_wise"}, "gated_attention_proj_granularity_type 'element_wise' is not served"),
    ({"q_lora_rank": 1536}, "q_lora_rank 1536 is not served"),
    ({"score_function": "softmax"}, "score_function 'softmax' is not served"),
    ({"linear_silu": False}, "linear_silu False is not served"),
], ids=["limit-whole-model", "limit-36", "limit-held-layer", "split-group", "rank", "ragged-period", "dense-too-deep", "kda-lora",
        "value-norm", "gate-kind", "q-lora", "scores", "no-silu"])
def test_from_hf_refuses_by_name(edit, says):
    with pytest.raises(ValueError, match=says):
        ModelConfig.from_hf({**LING_3_FLASH_HF, "num_hidden_layers": 18, **edit}, name="t")


# -- (a) the engine against the plain reference ----------------------------------------


@pytest.fixture(scope="module", params=[(k, s) for k in sorted(KINDS) for s in (True, False)],
                ids=lambda p: f"{p[0]}-{'split' if p[1] else 'rectangle'}")
def served(request):
    kind, split = request.param
    cfg, params, reference = _model(kind)
    prompt = np.random.default_rng(5).integers(1, cfg.vocab_size, size=40).tolist()
    entries, core = _served_logprobs(cfg, params, prompt, 8, chunk=12, split=split)
    return cfg, reference, prompt, entries, core, split


def test_engine_chunked_prefill_and_decode_agree_with_the_reference(served):
    """A prompt of 40 prefilled in chunks of 12 beside a decoding row (mixed
    steps, on the split token axis and on the rectangle: three chunk edges),
    8 tokens decoded through slots and pages; a slow decay in half the heads,
    a selection bias, the second of two routing groups held and one group a
    token. Logprobs at the served ids against the reference's whole forward
    pass (the recurrence token by token over one sequence). Both sides
    float32 at ``highest`` matmul precision (conftest): what is left is the
    order of accumulation (the chunkwise form against token by token), about
    3e-6 of the logit range, so the tolerance is 1e-4. The same for the model
    with a mixer beside GQA attention in every layer (its chunked form against
    the reference's scan over tokens, pages and slots in every layer)."""
    cfg, reference, prompt, entries, core, split = served
    sequence = prompt + [e["id"] for e in entries][:-1]
    assert len(entries) == 8 and _distance(entries, prompt, reference(sequence)) < TOL
    steps = core.flight.snapshot(kind="step")
    assert {"mixed", "decode"} <= {s["step_kind"] for s in steps}
    assert {s["layout"] for s in steps if s["step_kind"] == "mixed"} == ({SPLIT} if split else {ROWS_X_T})
    assert max(s["state_rows"] for s in steps) == 2 and max(s["state_slots_live"] for s in steps) == 2
    assert all(s["state_rows"] == s["decode_rows"] + s["chunk_rows"] for s in steps if s["layout"])
    # (a model that holds all its experts counts no choices: ``moe_choices`` is the held-share layers')
    assert (sum(s["moe_choices"] for s in steps) > 0) == cfg.moe_held_share and core.runner.recurrent and not core.prefix_matching
    if cfg.ssm_heads:  # every layer attends: the one kind of GQA layer's key tokens are counted
        assert all(s["kv_tokens_full"] > 0 for s in steps if s["layout"])


def _no_carry(layer, lp, cfg, h, positions, valid, state, conv, slot_ids, **kw):
    """Every chunk starts from zeros: the state is not carried across a chunk edge."""
    return layer(lp, cfg, h, jnp.zeros_like(positions), valid, state, conv, slot_ids, **kw)


def _no_zeroing(layer, lp, cfg, h, positions, valid, state, conv, slot_ids, **kw):
    """No row is ever fresh: a taken slot keeps what the sequence before left in it."""
    return layer(lp, cfg, h, positions + 1, valid, state, conv, slot_ids, **kw)


def _by_row(layer, lp, cfg, h, positions, valid, state, conv, slot_ids, **kw):
    """The state of a row is looked up by its place in the step, not by its slot."""
    base = slot_ids - slot_ids % _SLOTS
    return layer(lp, cfg, h, positions, valid, state, conv, base + 1 + jnp.arange(slot_ids.shape[0]) % (_SLOTS - 1), **kw)


_SLOTS = 3  # max_batch_size 2 and the null slot


@both_kinds
@pytest.mark.parametrize("broken", [_no_carry, _no_zeroing, _by_row], ids=lambda f: f.__name__.strip("_"))
def test_a_program_made_wrong_is_far_from_the_reference(broken, kind, monkeypatch):
    """The same run with the layer broken in one of the three ways the slots
    are easy to get wrong, each more than a hundred times the tolerance off:
    the carry across a chunk edge, the zeroing of a slot that is taken over
    (the run's first sequence, finished by then, leaves its state behind in
    the slot the checked prompt is given), the slot indirection (the checked
    row is the step's second row in the mixed steps and its first once the
    other has finished)."""
    module, name = KINDS[kind]["module"], KINDS[kind]["layer"]
    monkeypatch.setattr(module, name, functools.partial(broken, getattr(module, name)))
    cfg, params, reference = _model(kind)
    prompt = np.random.default_rng(5).integers(1, cfg.vocab_size, size=40).tolist()
    entries = _run_after_another(cfg, params, prompt)
    sequence = prompt + [e["id"] for e in entries][:-1]
    assert _distance(entries, prompt, reference(sequence)) > 100 * TOL


def _run_after_another(cfg, params, prompt, n_out=8, chunk=12):
    """As ``_served_logprobs``, but a first sequence has run and finished in
    slot 1 before: the row beside the checked prompt then holds slot 1 again
    and the prompt slot 2, or the other way round."""
    from dynamo_tpu.engine.core import LOGPROBS_TOP_K

    runner = ModelRunner(cfg, params, num_pages=64, page_size=4, max_batch_size=2, prefill_bucket=4, attn_impl="reference")
    core = EngineCore(runner, EngineConfig(num_pages=64, page_size=4, max_batch_size=2, max_prefill_tokens=chunk,
                                           chunk_prefill_tokens=chunk, max_seq_len=128, enable_prefix_caching=False))

    def request(tokens, n, logprobs=None):
        return PreprocessedRequest(token_ids=list(tokens), sampling=SamplingOptions(temperature=0.0, logprobs=logprobs),
                                   stop=StopConditions(max_tokens=n, ignore_eos=True))

    core.add_request(request(range(20, 50), 6), Context())
    core.add_request(request(range(60, 90), 6), Context())
    while core.has_work:
        core.step()
    core.add_request(request([7, 9, 11, 13], 40), Context())
    for _ in range(3):
        core.step()
    seq = core.add_request(request(prompt, n_out, LOGPROBS_TOP_K + 1), Context())
    entries = []
    while core.has_work and len(entries) < n_out:
        for s, out in core.step():
            if s is seq:
                entries.extend(out.logprobs or [])
    return entries


@both_kinds
def test_the_run_after_another_is_sound_unbroken(kind):
    """The control of the test above: the same run, nothing broken, agrees
    (a row that joins a running batch, in a slot another sequence has left)."""
    cfg, params, reference = _model(kind)
    prompt = np.random.default_rng(5).integers(1, cfg.vocab_size, size=40).tolist()
    entries = _run_after_another(cfg, params, prompt)
    sequence = prompt + [e["id"] for e in entries][:-1]
    assert _distance(entries, prompt, reference(sequence)) < TOL


# -- (b) the chunk step is the decode step, 64 times -----------------------------------------


def _recurrence_inputs(seed, c=64, heads=4, key=16, value=32):
    rng = np.random.default_rng(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q, k = unit(rng.normal(size=(c, heads, key))), unit(rng.normal(size=(c, heads, key)))
    v = rng.normal(size=(c, heads, value))
    g = -5 * rng.uniform(size=(c, heads, key)) * np.asarray([1, 1e-2, 1, 1e-3])[None, :, None]  # fast and slow heads
    beta = rng.uniform(size=(c, heads))
    s0 = rng.normal(size=(heads, key, value))
    return [jnp.asarray(x, jnp.float32) for x in (s0, q, k, v, g, beta)]


def test_chunk_step_is_sixty_four_decode_steps_on_one_state():
    s0, q, k, v, g, beta = _recurrence_inputs(0)
    s, outs = s0, []
    for t in range(q.shape[0]):
        o, s = kda.recurrent_step(s, q[t], k[t], v[t], g[t], beta[t])
        outs.append(o)
    o_chunk, s_chunk = kda.chunk_step(s0, q, k, v, g, beta)
    np.testing.assert_allclose(o_chunk, jnp.stack(outs), atol=2e-5)
    np.testing.assert_allclose(s_chunk, s, atol=2e-5)
    # The fastest decay there is (g = -5 in every channel, 64 tokens: e**-320) neither overflows nor divides by zero.
    o_fast, s_fast = kda.chunk_step(s0, q, k, v, jnp.full_like(g, -5.0), beta)
    assert np.isfinite(np.asarray(o_fast)).all() and np.isfinite(np.asarray(s_fast)).all()
    # The reference's own recurrence (from zeros) says the same.
    o_ref = ref.delta_rule(q, k, v, jnp.exp(g), beta)
    np.testing.assert_allclose(kda.chunk_step(jnp.zeros_like(s0), q, k, v, g, beta)[0], o_ref, atol=2e-5)


# -- (d) slot ids, not row ids ------------------------------------------------------------


def _null_batch(b, t, n):
    from benchmark.serving import null_batch

    return null_batch(b, t, n)


@both_kinds
def test_two_sequences_that_swap_rows_keep_their_states(kind):
    """Two sequences decode side by side through the runner by hand; from one
    step to the next they swap rows. With their slot ids they read the same
    tokens as when each keeps its row; a step built like the benchmark's null
    batch (no slot ids: every row the null slot) touches neither."""
    cfg, params, _ = _model(kind)

    def run(swap: bool):
        runner = ModelRunner(cfg, params, num_pages=16, page_size=8, max_batch_size=2, prefill_bucket=4, attn_impl="reference")
        toks = {1: [5, 6, 7, 8, 9, 10, 11], 2: [50, 51, 52, 53, 54, 55, 56]}
        pages = {1: 1, 2: 2}
        out = {1: [], 2: []}
        for step in range(7):
            order = [2, 1] if swap and step % 2 else [1, 2]
            batch = _null_batch(2, 1, 1)
            batch.tokens[:, 0] = [toks[s][step] for s in order]
            batch.positions[:, 0] = step
            batch.block_tables[:, 0] = [pages[s] for s in order]
            batch.slot_mapping[:, 0] = [pages[s] * 8 + step for s in order]
            batch.pos_limit[:] = 8
            batch.state_slots = np.asarray(order, np.int32)
            got, lp = runner.step(batch, lp_k=4)
            for row, s in enumerate(order):
                out[s].append(lp["top_lps"][row])
            if step == 3:
                runner.step(_null_batch(2, 1, 1))  # padding rows: the null slot, whatever the live slots hold
        return out

    plain, swapped = run(False), run(True)
    for s in (1, 2):
        np.testing.assert_allclose(np.stack(swapped[s]), np.stack(plain[s]), atol=1e-5)
    assert np.abs(np.stack(plain[1]) - np.stack(plain[2])).max() > 1e-2  # two sequences, two states


@both_kinds
def test_slots_and_pages_are_sized_from_the_model(kind):
    """The runner's buffers take their shapes from the model: a slab of pages
    for every layer that attends (2 of the hybrid's 6; all 3 of the model with
    a mixer; 2 of the 8 of the model whose mixers stand alone), a slot's part
    for every recurrent layer (4 of 6; all 3; 6 of 8), the
    state float32 in the kind's own shape, and a slot's bytes as
    ``state_bytes_per_slot`` says."""
    cfg, params, _ = _model(kind)
    runner = ModelRunner(cfg, params, num_pages=8, page_size=4, max_batch_size=2, prefill_bucket=4, attn_impl="reference")
    want = {"kda": (6, 4, 2, (4, 16, 16), (3, 1, 3 * 64)), "mamba2": (3, 3, 3, (4, 8, 16), (3, 1, 64 + 2 * 2 * 8)),
            "mamba2-alone": (8, 6, 2, (4, 8, 16), (3, 1, 64 + 2 * 8)), "kda-gqa": (8, 6, 2, (4, 16, 16), (3, 1, 3 * 64))}[kind]
    state, conv = runner.state
    assert (cfg.num_layers, cfg.recurrent_layers, cfg.cache_layers, *cfg.state_shapes()) == want
    assert runner.recurrent and runner.state_slots == 3 and runner.k_cache.shape[0] == cfg.cache_layers
    assert state.shape == (cfg.recurrent_layers * 3, *want[3]) and state.dtype == jnp.float32
    assert conv.shape == (cfg.recurrent_layers * 3, *want[4]) and conv.dtype == jnp.float32  # the toy's dtype
    assert cfg.state_bytes_per_slot() == (state.nbytes + conv.nbytes) // 3
    assert (kda.init_state(cfg, 3)[0].shape, kda.init_state(cfg, 3)[1].shape) == (state.shape, conv.shape)
    assert not ModelRunner(PRESETS["test-tiny"], llama.init_params(PRESETS["test-tiny"], 0), num_pages=8, page_size=4,
                           max_batch_size=2, prefill_bucket=4, attn_impl="reference").recurrent


def test_slot_allocator_and_the_step_batchs_default():
    slots = SlotAllocator(4)
    assert (slots.total, slots.live) == (3, 0)
    taken = [slots.allocate() for _ in range(3)]
    assert taken == [1, 2, 3] and slots.live == 3
    with pytest.raises(RuntimeError, match="no free state slot"):
        slots.allocate()
    slots.release(2)
    assert slots.allocate() == 2
    for bad in (0, 4):
        with pytest.raises(ValueError, match="not a live slot"):
            slots.release(bad)
    slots.release(3)
    with pytest.raises(ValueError, match="not a live slot"):
        slots.release(3)
    assert "state_slots" in {f.name for f in dataclasses.fields(StepBatch)} and _null_batch(2, 1, 1).state_slots is None


# -- (e) preemption is recompute -----------------------------------------------------------


def _request(tokens, n):
    return PreprocessedRequest(token_ids=list(tokens), sampling=SamplingOptions(temperature=0.0),
                               stop=StopConditions(max_tokens=n, ignore_eos=True))


@both_kinds
def test_a_preempted_sequences_second_run_equals_its_first(kind):
    """Two sequences in a pool too small for both to finish: the later one is
    preempted (its slot goes back), waits, and starts again from its tokens in
    whatever slot it is given; what it emits in all equals what it emits alone
    in a pool that holds it."""
    cfg, params, _ = _model(kind)

    def run(num_pages, prompts):
        runner = ModelRunner(cfg, params, num_pages=num_pages, page_size=4, max_batch_size=2, prefill_bucket=4,
                             attn_impl="reference")
        core = EngineCore(runner, EngineConfig(num_pages=num_pages, page_size=4, max_batch_size=2, max_prefill_tokens=8,
                                               chunk_prefill_tokens=8, max_seq_len=64))
        seqs = [core.add_request(_request(p, 24), Context()) for p in prompts]
        emitted = {s.seq_id: [] for s in seqs}
        slots_seen = set()
        for _ in range(400):
            if not core.has_work:
                break
            for s, out in core.step():
                emitted[s.seq_id].extend(out.token_ids)
            slots_seen |= {s.state_slot for s in core.running + core.prefilling}
            assert all(s.state_slot == 0 for s in core.waiting)
        assert core.state_slots.live == 0 and all(s.status is SeqStatus.FINISHED for s in seqs)
        return [emitted[s.seq_id] for s in seqs], core

    a, b = list(range(3, 15)), list(range(40, 52))
    (tight_a, tight_b), core = run(17, [a, b])  # 16 usable pages of 4: two sequences of 36 tokens need 18
    assert core.num_preemptions >= 1
    (alone_a,), _ = run(33, [a])
    (alone_b,), _ = run(33, [b])
    assert tight_a == alone_a and tight_b == alone_b and len(tight_a) == 24


# -- prefix matching, page transfer, the KV router ------------------------------------------------


@both_kinds
def test_a_model_with_recurrent_layers_never_matches_a_prefix(kind, caplog, monkeypatch):
    """``enable_prefix_caching`` left on: the engine says once that matching
    is off, never calls ``match_prefix``, still commits pages (KV events go
    out), and a second request with the first one's prompt computes it all."""
    import logging

    cfg, params, _ = _model(kind)
    events = []
    runner = ModelRunner(cfg, params, num_pages=64, page_size=4, max_batch_size=2, prefill_bucket=4, attn_impl="reference")
    with caplog.at_level(logging.INFO, logger="dynamo_tpu.engine.core"):
        core = EngineCore(runner, EngineConfig(num_pages=64, page_size=4, max_batch_size=2, max_prefill_tokens=16,
                                               chunk_prefill_tokens=16, max_seq_len=64, enable_prefix_caching=True),
                          on_kv_event=events.append)
    assert sum("prefix matching is off" in r.message for r in caplog.records) == 1
    assert core.config.enable_prefix_caching and not core.prefix_matching
    monkeypatch.setattr(core.allocator, "match_prefix", lambda hashes: pytest.fail("match_prefix called"))
    prompt = list(range(1, 25))
    outs = []
    for _ in range(2):
        seq = core.add_request(_request(prompt, 4), Context())
        toks = []
        while core.has_work:
            for s, out in core.step():
                toks.extend(out.token_ids)
        outs.append(toks)
        assert seq.num_cached_at_start == 0
    assert outs[0] == outs[1] and any(e.stored for e in events)
    assert core.allocator.stats().hits == 0


@both_kinds
def test_page_transfer_and_the_kv_router_refuse_the_model_by_name(kind):
    import asyncio

    from dynamo_tpu.disagg.transfer import KvTransferService

    cfg, params, _ = _model(kind)
    name = cfg.name
    runner = ModelRunner(cfg, params, num_pages=16, page_size=4, max_batch_size=2, prefill_bucket=4, attn_impl="reference")
    core = EngineCore(runner, EngineConfig(num_pages=16, page_size=4, max_batch_size=2, max_seq_len=32))
    with pytest.raises(NotImplementedError, match=f"{name}: the KV transfer service is not served for a model with recurrent layers"):
        KvTransferService(core)
    from dynamo_tpu.disagg.prefill_worker import PrefillWorker

    class _Service:
        pass

    service = _Service()
    service.core = core
    with pytest.raises(NotImplementedError, match=f"{name}: a prefill worker .* is not served for a model with recurrent layers"):
        PrefillWorker(None, service)
    with pytest.raises(ValueError, match="spec_k 2 / decode_steps 1 are not served for a model with recurrent layers"):
        EngineCore(runner, EngineConfig(num_pages=16, page_size=4, max_batch_size=2, max_seq_len=32, spec_k=2))
    with pytest.raises(ValueError, match="spec_k 0 / decode_steps 2 are not served for a model with recurrent layers"):
        EngineCore(runner, EngineConfig(num_pages=16, page_size=4, max_batch_size=2, max_seq_len=32, decode_steps=2))
    with pytest.raises(NotImplementedError, match=f"{name}: speculative verify is not served"):
        runner.spec_step(_null_batch(2, 1, 1), 3)

    async def kv_routed():
        from dynamo_tpu import launch
        from dynamo_tpu.model_card import ModelDeploymentCard
        from dynamo_tpu.runtime.component import DistributedRuntime

        card = ModelDeploymentCard(name=name, tokenizer="byte", context_length=32, kv_page_size=4, router_mode="kv")
        spec = launch.WorkerSpec(model_config=cfg, card=card, params=params,
                                 engine_config=EngineConfig(num_pages=16, page_size=4, max_batch_size=2, max_seq_len=32))
        await launch.serve_worker(DistributedRuntime.detached(), spec)

    with pytest.raises(ValueError, match=f"{name}: router_mode 'kv' is not served for a model with recurrent layers"):
        asyncio.run(kv_routed())


# -- (f) the shares add up, with the groups on ----------------------------------------------------


def test_the_eight_shares_add_up_to_the_uncut_layer_with_groups_on():
    """One routed layer of 64 experts in 8 groups of 8, a token limited to 4
    groups and 8 choices, a shared expert, divided over eight holders of one
    whole group each: what the model's layer gives on each holder (its group's
    terms and the shared expert, which every holder computes whole), summed
    with the shared expert counted once, equals the uncut reference's layer.
    Group-limited routing over more than one group against a reference. float32
    both sides: 1e-5 of the largest output."""
    hf = {**TINY_HYBRID_HF, "num_experts": 64, "n_routed_experts_published": 64, "expert_share_rank": 0,
          "expert_share_chips": 1, "n_group": 8, "topk_group": 4, "num_experts_per_tok": 8}
    whole = dataclasses.replace(ModelConfig.from_hf(hf, name="whole"), dtype="float32")
    assert (whole.moe_n_group, whole.moe_topk_group, whole.moe_held_share) == (8, 4, False)
    from benchmark import weights

    params = weights.make_weights(whole, 2**31 + 41, quant="")
    lp = jax.tree.map(lambda x: x[0], params["layers"])
    lp["router_bias"] = 0.3 * jax.random.normal(jax.random.PRNGKey(9), lp["router_bias"].shape, jnp.float32)
    h = jax.random.normal(jax.random.PRNGKey(3), (1, 48, 64), jnp.float32)
    z = ref.shape_of(hf)
    want = np.asarray(ref.routed_ffn(h[0], lp, z))
    shared = np.asarray(ref.swiglu_of(h[0], lp, "w_shared_gate", "w_shared_up", "w_shared_down"))
    mix = np.asarray(ref.route(h[0], lp, z))
    groups_used = (mix.reshape(48, 8, 8) > 0).any(axis=2).sum(axis=1)
    assert (groups_used <= 4).all() and ((mix > 0).sum(axis=1) == 8).all() and groups_used.min() >= 1
    unlimited = np.asarray(ref.route(h[0], lp, {**z, "n_group": 1}))
    assert ((mix > 0) != (unlimited > 0)).any()  # the limit chose otherwise somewhere
    total, held_choices = np.zeros_like(want), 0
    for rank in range(8):
        share_hf = {**hf, "num_experts": 8, "expert_share_rank": rank, "expert_share_chips": 8}
        share = dataclasses.replace(ModelConfig.from_hf(share_hf, name="share"), dtype="float32")
        assert share.moe_expert_first == 8 * rank and share.moe_held_share and share.moe_n_group == 8
        mine = {**lp, **{k: lp[k][8 * rank: 8 * rank + 8] for k in ("w_gate", "w_up", "w_down")}}
        out, counts = llama._mlp_moe_held(mine, h, share, jnp.ones((1, 48), bool))
        np.testing.assert_allclose(out[0], ref.routed_ffn(h[0], mine, ref.shape_of(share_hf)), atol=1e-5 * np.abs(want).max())
        total += np.asarray(out[0]) - shared
        held_choices += int(counts[2])
    np.testing.assert_allclose(total + shared, want, atol=1e-5 * np.abs(want).max())
    assert held_choices == 48 * 8  # every choice landed on exactly one holder


# -- the catalog -------------------------------------------------------------------------------


@pytest.mark.skipif(not CATALOG.exists(), reason="the catalog of public architectures is not on this machine")
def test_the_published_keys_are_the_catalog_rows():
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines()) if r["name"] == "Ling-3.0-flash")
    assert row["config"] == LING_3_FLASH_HF
    cfg = ModelConfig.from_hf({**row["config"], "num_hidden_layers": 30}, name="ling-3.0-flash-30l")
    assert cfg == PRESETS["ling-3.0-flash-30l"]
    # 25 KDA + 5 MLA layers of the 42: the driver's count of 56M a layer outside the experts is
    # (35 x 59.6M + 7 x 39.1M) / 42 with the head-wise gate in both kinds.
    kda_block = 5 * 2560 * 4096 + 2 * 2560 * 32 + 3 * 4 * 4096 + 32 + 4096 + 128 + 2 * 2560
    assert kda_block == pytest.approx(52.6e6, rel=5e-3)
