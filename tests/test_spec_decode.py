"""Lossless speculative decoding on the mixed-step scheduler (ISSUE 6).

The contract under test: with ``spec_k > 0`` the engine emits *bit-identical*
token streams (and logprobs) to ``spec_k = 0`` — greedy and seeded, with
chunked prefill mixing into the same steps — because verification replays
the exact per-token sampling (same rng fold counter, same logits math) and
only commits the matching prefix. Also covered: the n-gram proposer, the
rng-fold-advances-once-per-emitted-token invariant, page rollback
accounting, and the non-contiguous verify routing in the attention layer.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.core import EngineConfig, EngineCore
from dynamo_tpu.engine.runner import ModelRunner
from dynamo_tpu.engine.spec import NgramProposer, build_proposer
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import PRESETS
from dynamo_tpu.ops.attention import paged_attention_reference
from dynamo_tpu.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)

PAGE = 4
_PARAMS = {}


def params_for(preset):
    if preset not in _PARAMS:
        _PARAMS[preset] = llama.init_params(PRESETS[preset], 0)
    return _PARAMS[preset]


def make_core(preset="test-tiny", *, spec_k=0, chunk=16, num_pages=96,
              max_batch=8, max_seq_len=256, params=None, cache_dtype=None,
              attn_impl="reference", **cfg_kw):
    cfg = PRESETS[preset]
    params = params if params is not None else params_for(preset)
    runner = ModelRunner(
        cfg, params, num_pages=num_pages, page_size=PAGE,
        max_batch_size=max_batch, prefill_bucket=16, attn_impl=attn_impl,
        cache_dtype=cache_dtype,
    )
    return EngineCore(runner, EngineConfig(
        num_pages=num_pages, page_size=PAGE, max_batch_size=max_batch,
        max_seq_len=max_seq_len, chunk_prefill_tokens=chunk, spec_k=spec_k,
        **cfg_kw,
    ))


def run_all(core, reqs, max_steps=300):
    """Drive to completion; returns ({seq_id: tokens}, {seq_id: logprobs})."""
    tokens, lps = {}, {}
    for req in reqs:
        seq = core.add_request(req)
        tokens[seq.seq_id] = []
        lps[seq.seq_id] = []
    steps = 0
    while core.has_work and steps < max_steps:
        for seq, out in core.step():
            tokens[seq.seq_id].extend(out.token_ids)
            if out.logprobs:
                lps[seq.seq_id].extend(out.logprobs)
        steps += 1
    assert not core.has_work, "engine did not drain"
    return tokens, lps


# -- proposer ---------------------------------------------------------------


def test_ngram_proposer_basic_lookup():
    # ...5 6 7 | 5 6 7 -> the trailing 3-gram recurs; propose what followed.
    p = NgramProposer()
    assert p.propose([5, 6, 7, 9, 11, 5, 6, 7], 3) == [9, 11, 5]


def test_ngram_proposer_prefers_longest_then_most_recent():
    p = NgramProposer()
    # Suffix [1, 2] occurs twice earlier; the most recent match (followed by
    # 8) must win over the older one (followed by 4).
    assert p.propose([1, 2, 4, 1, 2, 8, 9, 1, 2], 1) == [8]
    # A longer matching suffix beats a shorter, more recent one.
    assert p.propose([3, 1, 2, 5, 9, 9, 1, 2, 5], 1) == [9]


def test_ngram_proposer_caps_and_empties():
    p = NgramProposer()
    # Period-1 stream: every match is near the end, so the longest
    # truncated continuation wins (start=0 match -> 3 tokens follow it).
    assert p.propose([7, 7, 7, 7, 7, 7], 4) == [7, 7, 7]
    assert p.propose([7, 7, 7, 7], 0) == []
    assert p.propose([1], 4) == []  # too short to have an earlier match
    assert p.propose([1, 2, 3, 4], 4) == []  # no repetition at all
    # max_k caps the continuation even when more history is available.
    assert len(p.propose(list(range(8)) * 4, 3)) == 3


def test_build_proposer_factory():
    assert isinstance(build_proposer(), NgramProposer)
    with pytest.raises(ValueError):
        build_proposer("draft-model-7b")


# -- losslessness -----------------------------------------------------------


def _requests(vocab):
    """A mix that exercises verify + chunked prefill + seeded sampling."""
    return [
        # Periodic prompt: the drafter matches and verification accepts.
        PreprocessedRequest(
            token_ids=[5, 7, 5, 7, 5, 7, 9, 11],
            sampling=SamplingOptions(temperature=0.0),
            stop=StopConditions(max_tokens=20, ignore_eos=True),
        ),
        # Long prompt: chunked prefill rides the same spec dispatches.
        PreprocessedRequest(
            token_ids=[i % (vocab - 2) + 1 for i in range(40)],
            sampling=SamplingOptions(temperature=0.8, seed=42, logprobs=3),
            stop=StopConditions(max_tokens=12, ignore_eos=True),
        ),
        PreprocessedRequest(
            token_ids=[3, 3, 3, 3, 2, 1],
            sampling=SamplingOptions(temperature=0.7, seed=7),
            stop=StopConditions(max_tokens=12, ignore_eos=True),
        ),
    ]


@pytest.mark.parametrize("preset", ["test-tiny", "test-tiny-mla"])
@pytest.mark.parametrize("spec_k", [1, 3, 4])
def test_spec_decode_is_lossless(preset, spec_k):
    vocab = PRESETS[preset].vocab_size
    base_tok, base_lp = run_all(make_core(preset), _requests(vocab))
    core = make_core(preset, spec_k=spec_k)
    spec_tok, spec_lp = run_all(core, _requests(vocab))
    assert spec_tok == base_tok
    assert spec_lp == base_lp
    assert core.spec_tokens_proposed > 0  # the path actually engaged


def test_spec_decode_lossless_without_chunking():
    """chunk_prefill_tokens=0 (phase-exclusive prefill) still speculates on
    pure-decode steps — the spec path must not depend on mixed chunks."""
    vocab = PRESETS["test-tiny"].vocab_size
    base_tok, base_lp = run_all(make_core(chunk=0), _requests(vocab))
    core = make_core(chunk=0, spec_k=4)
    spec_tok, spec_lp = run_all(core, _requests(vocab))
    assert spec_tok == base_tok
    assert spec_lp == base_lp
    assert core.spec_tokens_proposed > 0


def test_spec_lossless_on_fp8_kv_cache(monkeypatch):
    """KV dtype is orthogonal to losslessness: with the SAME fp8 cache,
    spec_k>0 must still reproduce spec_k=0 bit-for-bit (every attention
    path upcasts fp8 storage identically). Also pins the launch-side
    DYN_KV_CACHE_DTYPE resolution that feeds ModelRunner(cache_dtype=...)."""
    from dynamo_tpu.launch import _kv_cache_dtype

    monkeypatch.setenv("DYN_KV_CACHE_DTYPE", "fp8")
    assert _kv_cache_dtype() == jnp.float8_e4m3fn
    monkeypatch.setenv("DYN_KV_CACHE_DTYPE", "bf16")
    assert _kv_cache_dtype() is None  # runner keeps its model-dtype default
    monkeypatch.setenv("DYN_KV_CACHE_DTYPE", "int4")
    with pytest.raises(ValueError):
        _kv_cache_dtype()

    vocab = PRESETS["test-tiny"].vocab_size
    base_core = make_core(cache_dtype=jnp.float8_e4m3fn)
    assert base_core.runner.k_cache.dtype == jnp.float8_e4m3fn
    base_tok, base_lp = run_all(base_core, _requests(vocab))
    spec_tok, spec_lp = run_all(
        make_core(spec_k=4, cache_dtype=jnp.float8_e4m3fn), _requests(vocab)
    )
    assert spec_tok == base_tok
    assert spec_lp == base_lp


# -- acceptance + rng fold discipline ---------------------------------------


def _flat_params():
    """Zeroed weights: every logit is identical, greedy argmax is always
    token 0, so generation is maximally repetitive — the drafter proposes
    [0, 0, ...] and verification must accept every draft."""
    return jax.tree.map(jnp.zeros_like, params_for("test-tiny"))


def test_acceptance_positive_on_repetitive_stream():
    core = make_core(spec_k=4, params=_flat_params())
    req = PreprocessedRequest(
        token_ids=[1, 2, 3, 4],
        sampling=SamplingOptions(temperature=0.0),
        stop=StopConditions(max_tokens=24, ignore_eos=True),
    )
    toks, _ = run_all(core, [req])
    assert toks[0] == [0] * 24
    assert core.spec_tokens_proposed > 0
    assert core.spec_tokens_accepted > 0
    # All-zero stream + always-argmax-0 target: every draft token accepted.
    assert core.spec_tokens_accepted == core.spec_tokens_proposed
    # The counters feed the flight recorder / metrics acceptance rate.
    assert core.spec_steps > 0


@pytest.mark.parametrize("overlap", [True, False], ids=["pipelined", "synchronous"])
def test_rng_fold_advances_once_per_emitted_token(overlap):
    """sample_steps handed to the verify dispatch must equal the number of
    tokens emitted so far — fold advances exactly once per emitted token,
    never per dispatch and never for rejected drafts. The pipelined loop reads
    a verify in flight before it composes on top of it (its acceptance decides
    every position after), and chains a verify out of a plain step in flight:
    at its dispatch the fold is the tokens the sequence holds plus the one the
    step in flight owes it."""
    core = make_core(spec_k=4, params=_flat_params(), overlap=overlap)
    calls = []
    site = "spec_step_async" if overlap else "spec_step"
    orig = getattr(core.runner, site)

    def spy(batch, verify_width, lp_k=0, **kw):
        calls.append((int(np.asarray(batch.sample_steps)[0]), seq.num_generated + core._adv(seq)[1]))
        return orig(batch, verify_width, lp_k=lp_k, **kw)

    setattr(core.runner, site, spy)
    seq = core.add_request(PreprocessedRequest(
        # A periodic prompt: the proposer drafts from it, and the seeded stream
        # rejects most of what it drafts (a step without a draft is no verify
        # in the pipelined loop, which then chains a plain step).
        token_ids=[1, 2, 3, 4] if not overlap else [1, 2, 1, 2, 1, 2, 1],
        sampling=SamplingOptions(temperature=0.9, seed=11),
        stop=StopConditions(max_tokens=16, ignore_eos=True),
    ))
    emitted = 0
    steps = 0
    while core.has_work and steps < 100:
        before = len(calls)
        outs = core.step()
        emitted += sum(len(o.token_ids) for _, o in outs)
        if len(calls) > before:
            fold, generated = calls[-1]
            assert fold == generated
            if not overlap:  # nothing in flight, nothing handed back yet: the tokens emitted before this step
                assert fold == emitted - sum(len(o.token_ids) for _, o in outs)
        steps += 1
    assert emitted == 16
    assert len(calls) > 0
    # Every emitted token advanced the fold exactly once: the final fold
    # counter the engine would use next equals the total emitted.
    assert seq.num_generated == emitted


def test_pages_released_after_spec_requests_finish():
    """Rejected-draft page rollback + normal teardown: nothing leaks."""
    core = make_core(spec_k=4)
    vocab = PRESETS["test-tiny"].vocab_size
    run_all(core, _requests(vocab))
    stats = core.allocator.stats()
    assert stats.active_pages == 0


def test_draft_len_respects_max_seq_len():
    """A request one token from its limit must not speculate past it."""
    core = make_core(spec_k=4, params=_flat_params(), max_seq_len=16)
    req = PreprocessedRequest(
        token_ids=[1, 2, 3, 4],
        sampling=SamplingOptions(temperature=0.0),
        stop=StopConditions(max_tokens=64, ignore_eos=True),
    )
    toks, _ = run_all(core, [req])
    assert len(toks[0]) == 12  # capped by max_seq_len, not max_tokens
    assert core.allocator.stats().active_pages == 0


# -- verify-path attention routing ------------------------------------------


def test_pallas_rejects_gappy_rows_without_flag(monkeypatch):
    from dynamo_tpu.ops.pallas_paged import paged_attention_pallas

    monkeypatch.setenv("DYNAMO_PALLAS_INTERPRET", "1")
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((1, 3, 4, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((9, 4, 128)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((9, 4, 128)), jnp.float32)
    tables = jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 8]], jnp.int32)
    gappy = jnp.asarray([[4, 6, 7]], jnp.int32)  # non-contiguous verify row
    with pytest.raises(ValueError, match="contiguous"):
        paged_attention_pallas(q, k, v, tables, gappy, scale=0.125)
    # Declaring non-contiguous routes to the multi-query decode kernel
    # (per-row causal mask — exact for gappy verify layouts) instead of
    # raising; its online softmax agrees with the reference to float
    # accumulation-order tolerance.
    out = paged_attention_pallas(
        q, k, v, tables, gappy, scale=0.125, contiguous_positions=False
    )
    want = paged_attention_reference(q, k, v, tables, gappy, scale=0.125)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_multi_token_verify_row_matches_per_position_decode_kernel():
    """The reference formulation the verify dispatch routes through agrees
    with the Pallas decode kernel (interpret mode) scored one position at a
    time — i.e. a K+1-wide verify row attends exactly as K+1 sequential
    decodes would."""
    from dynamo_tpu.ops.pallas_paged import decode_supported, paged_decode_attention

    rng = np.random.default_rng(1)
    b, t, n_heads, n_kv, hd, ps, pps = 2, 3, 4, 2, 64, 4, 8
    width = n_kv * hd
    num_pages = b * pps + 1
    k = jnp.asarray(rng.standard_normal((num_pages, ps, width)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((num_pages, ps, width)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((b, t, n_heads, hd)), jnp.float32)
    tables = jnp.asarray(
        1 + rng.permutation(num_pages - 1)[: b * pps].reshape(b, pps), jnp.int32
    )
    starts = np.asarray([9, 17])  # verify rows resume mid-sequence
    positions = jnp.asarray(starts[:, None] + np.arange(t)[None, :], jnp.int32)
    scale = hd**-0.5
    assert decode_supported(q[:, :1], k)

    whole = paged_attention_reference(q, k, v, tables, positions, scale=scale)
    per_pos = [
        paged_decode_attention(
            q[:, j:j + 1], k, v, tables, positions[:, j:j + 1],
            scale=scale, interpret=True,
        )
        for j in range(t)
    ]
    got = jnp.concatenate(per_pos, axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(whole), rtol=2e-5, atol=2e-5)


# -- kernel-path verify (ISSUE 7) -------------------------------------------


def _pin_kernel_block_shape(monkeypatch):
    """Pin the kernel's block partition to static values: _pages_per_block
    normally depends on the padded pages bucket, which can differ between a
    spec run (speculative pages allocated) and its spec_k=0 baseline at the
    same logical step — a different accumulation partition is a different
    float result. Bit-parity asserts need both runs on identical partitions."""
    import dynamo_tpu.ops.pallas_mla as pm
    import dynamo_tpu.ops.pallas_paged as pp

    monkeypatch.setenv("DYNAMO_PALLAS_INTERPRET", "1")
    monkeypatch.setenv("DYN_DECODE_SPLITS", "1")

    def pin(pps, ps, *a):
        return min(pps, 4)

    monkeypatch.setattr(pp, "_pages_per_block", pin)
    # pallas_mla binds the helper by name at import time.
    monkeypatch.setattr(pm, "_pages_per_block", pin)


@pytest.mark.parametrize("preset", ["test-tiny", "test-tiny-mla"])
def test_spec_decode_lossless_on_kernel_path(monkeypatch, preset):
    """spec_step dispatch reaches the Pallas kernel (multi-query verify
    rows) and stays bit-identical to the spec_k=0 baseline — tokens AND
    logprobs. chunk=0 so prompts dispatch identically in both runs (whole
    prefills via runner.step) and every decode/verify step is a kernel
    dispatch."""
    import dynamo_tpu.ops.pallas_paged as pp

    _pin_kernel_block_shape(monkeypatch)
    vocab = PRESETS[preset].vocab_size
    before = pp.fallback_snapshot()
    base_tok, base_lp = run_all(
        make_core(preset, spec_k=0, chunk=0, attn_impl="pallas"), _requests(vocab)
    )
    spec_core = make_core(preset, spec_k=3, chunk=0, attn_impl="pallas")
    spec_tok, spec_lp = run_all(spec_core, _requests(vocab))
    after = pp.fallback_snapshot()
    assert spec_core.spec_tokens_accepted > 0  # speculation actually engaged
    assert spec_tok == base_tok
    assert spec_lp == base_lp
    # Decode and verify must have run on the kernel, not the gather path.
    grew = [s for s in after if after[s] > before.get(s, 0)]
    bad = [s for s in grew
           if s.startswith(("decode:", "verify:", "mla_decode:", "mla_verify:"))]
    assert not bad, bad


def test_spec_chunked_verify_rides_kernel(monkeypatch):
    """chunk > 0: mixed steps widen verify batches to the chunk width; that
    still fits the kernel's T cap, so no verify fallback is recorded."""
    import dynamo_tpu.ops.pallas_paged as pp

    monkeypatch.setenv("DYNAMO_PALLAS_INTERPRET", "1")
    vocab = PRESETS["test-tiny"].vocab_size
    core = make_core(spec_k=3, chunk=16, attn_impl="pallas")
    before = pp.fallback_snapshot()
    toks, _ = run_all(core, _requests(vocab))
    after = pp.fallback_snapshot()
    assert core.spec_tokens_accepted > 0
    grew = [s for s in after if after[s] > before.get(s, 0)]
    assert not [s for s in grew if s.startswith(("verify:", "decode:"))], grew
    assert all(len(t) > 0 for t in toks.values())
