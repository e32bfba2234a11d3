"""Golden parity of chunked prefill vs whole-prompt prefill (ISSUE 2).

The mixed-step scheduler splits prompts into token-budget chunks; sampling
is suppressed for non-final chunks and the rng fold counter does not
advance on suppression, so the final chunk must sample exactly what a
whole-prompt prefill samples — tokens AND logprobs, greedy and seeded —
across chunk-boundary sizes, with prefix-cache resumes, preemption
mid-prompt, and multimodal rows (mm_slot_offset advancing across chunks).
"""

import numpy as np
import pytest

from dynamo_tpu.engine.core import EngineConfig, EngineCore
from dynamo_tpu.engine.runner import ModelRunner
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import PRESETS
from dynamo_tpu.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)

from tests.test_engine_core import greedy_reference, greedy_request, run_to_completion

CFG = PRESETS["test-tiny"]
PARAMS = llama.init_params(CFG, 0)
PAGE = 4


def make_core(chunk=4, num_pages=64, max_batch=8, max_prefill=256, **cfg_kw):
    config = EngineConfig(
        num_pages=num_pages, page_size=PAGE, max_batch_size=max_batch,
        max_prefill_tokens=max_prefill, max_seq_len=128,
        chunk_prefill_tokens=chunk, **cfg_kw,
    )
    runner = ModelRunner(
        CFG, PARAMS, num_pages=num_pages, page_size=PAGE,
        max_batch_size=max_batch, prefill_bucket=16, attn_impl="reference",
    )
    return EngineCore(runner, config)


@pytest.mark.parametrize("chunk", [3, 4, 5, 8, 11])
def test_chunked_equals_whole_prompt_across_chunk_sizes(chunk):
    """Chunk boundaries off/on page boundaries, mid-prompt and at the final
    token: every size must reproduce the whole-prompt greedy tokens.
    max_prefill_tokens == chunk forces chunking even with no decode rows."""
    prompt = [5, 6, 7, 8, 9, 10, 11, 3, 1, 4, 1, 5, 9]  # 13 tokens
    core = make_core(chunk=chunk, max_prefill=chunk)
    seq = core.add_request(greedy_request(prompt, max_tokens=6))
    outputs = run_to_completion(core)
    assert outputs[seq.seq_id] == greedy_reference(prompt, 6)
    assert seq.prefill_chunks >= -(-len(prompt) // chunk) - 1


def test_mixed_step_parity_with_running_decode():
    """Prompts admitted while decodes run are chunked at the budget and ride
    fused mixed steps; everyone stays token-exact, and no prefill-only step
    ever starves the running decodes."""
    core = make_core(chunk=4)
    p1 = [1, 2, 3, 4, 5]
    core.add_request(greedy_request(p1, max_tokens=16))
    outputs = {}
    for _ in range(3):  # prefill p1 + a couple of decode steps
        for seq, out in core.step():
            outputs.setdefault(seq.seq_id, []).extend(out.token_ids)
    p2 = list(range(7, 7 + 17))  # 17 tokens: 5 chunks of <=4
    p3 = [9, 8, 7, 6, 5, 4, 3]
    core.add_request(greedy_request(p2, max_tokens=5))
    core.add_request(greedy_request(p3, max_tokens=5))
    outputs = run_to_completion(core, outputs=outputs)
    assert outputs[0] == greedy_reference(p1, 16)
    assert outputs[1] == greedy_reference(p2, 5)
    assert outputs[2] == greedy_reference(p3, 5)
    assert core.mixed_steps > 0
    assert core.stall_violations == 0


def test_seeded_sampling_parity_chunked_vs_whole():
    """The rng fold counter must not advance on suppressed (non-final-chunk)
    samples: a seeded request generates the identical stream either way."""
    prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5]

    def run(chunk, max_prefill):
        core = make_core(chunk=chunk, max_prefill=max_prefill)
        req = PreprocessedRequest(
            token_ids=list(prompt),
            sampling=SamplingOptions(temperature=0.9, top_k=40, top_p=0.95, seed=1234),
            stop=StopConditions(max_tokens=8, ignore_eos=True),
        )
        seq = core.add_request(req)
        run_to_completion(core)
        return seq.tokens[len(prompt):]

    whole = run(chunk=0, max_prefill=256)
    for chunk in (3, 4, 7):
        assert run(chunk=chunk, max_prefill=chunk) == whole, f"chunk={chunk}"


def test_logprob_parity_chunked_vs_whole():
    """Reported logprobs (chosen + top-k) of the final-chunk sample and all
    decode steps match the whole-prompt run."""
    prompt = [3, 5, 7, 11, 13, 2, 4, 6, 8, 10]

    def run(chunk, max_prefill):
        core = make_core(chunk=chunk, max_prefill=max_prefill)
        core.add_request(PreprocessedRequest(
            token_ids=list(prompt),
            sampling=SamplingOptions(temperature=0.0, logprobs=4),
            stop=StopConditions(max_tokens=4, ignore_eos=True),
        ))
        toks, lps = [], []
        while core.has_work:
            for _seq, out in core.step():
                toks.extend(out.token_ids)
                if out.logprobs:
                    lps.extend(out.logprobs)
        return toks, lps

    toks_w, lps_w = run(chunk=0, max_prefill=256)
    toks_c, lps_c = run(chunk=4, max_prefill=4)
    assert toks_c == toks_w
    assert len(lps_c) == len(lps_w) == 4
    for ec, ew in zip(lps_c, lps_w):
        assert ec["id"] == ew["id"]
        np.testing.assert_allclose(ec["logprob"], ew["logprob"], rtol=1e-4, atol=1e-5)
        assert [tid for tid, _ in ec["top"]] == [tid for tid, _ in ew["top"]]
        np.testing.assert_allclose(
            [lp for _, lp in ec["top"]], [lp for _, lp in ew["top"]],
            rtol=1e-4, atol=1e-5,
        )


def test_prefix_cache_hit_then_chunked_resume():
    """A second request over a cached prefix starts its first chunk at the
    matched boundary (num_cached > 0) and continues chunked to parity."""
    prompt = list(range(1, 21))  # 20 tokens = 5 full pages
    core = make_core(chunk=4, max_prefill=4)
    core.add_request(greedy_request(prompt, max_tokens=2))
    run_to_completion(core)
    seq = core.add_request(greedy_request(prompt, max_tokens=3))
    outputs = run_to_completion(core)
    assert seq.num_cached_at_start >= PAGE  # hit at least one cached page
    assert seq.num_cached_at_start < len(prompt)  # but still had chunks to run
    assert outputs[seq.seq_id] == greedy_reference(prompt, 3)


def test_preemption_then_chunked_reprefill():
    """Page pressure preempts a sequence mid-stream; its resume (prompt +
    generated recompute) runs as budget chunks interleaved with the
    survivor's decode, and both streams stay token-exact."""
    core = make_core(chunk=4, num_pages=8, max_batch=2, enable_prefix_caching=False)
    p1, p2 = [1, 2, 3, 4, 5, 6], [11, 12, 13, 14]
    core.add_request(greedy_request(p1, max_tokens=10))
    core.add_request(greedy_request(p2, max_tokens=10))
    outputs = run_to_completion(core, max_steps=400)
    assert core.num_preemptions > 0, "test must exercise the preemption path"
    assert outputs[0] == greedy_reference(p1, 10)
    assert outputs[1] == greedy_reference(p2, 10)


def test_chunked_decode_steps_pipeline_interleave():
    """Chunked admission composes with the pipeline's decode_steps bursts
    (chained sub-dispatches): a step that carries a chunk dispatches no
    burst, then bursts resume; tokens stay exact."""
    core = make_core(chunk=4, decode_steps=4)
    p1 = [1, 2, 3, 4, 5]
    core.add_request(greedy_request(p1, max_tokens=12))
    outputs = {}
    for _ in range(3):
        for seq, out in core.step():
            outputs.setdefault(seq.seq_id, []).extend(out.token_ids)
    p2 = list(range(7, 7 + 13))
    core.add_request(greedy_request(p2, max_tokens=6))
    outputs = run_to_completion(core, outputs=outputs)
    assert outputs[0] == greedy_reference(p1, 12)
    assert outputs[1] == greedy_reference(p2, 6)


# -- multimodal: mm_slot_offset advancing across chunks ----------------------

VL_CFG = PRESETS["test-tiny-vl"]
IMG = VL_CFG.image_token_id


def _mm_payload(embeds: np.ndarray) -> dict:
    import base64

    return {
        "embeds_b64": base64.b64encode(
            np.ascontiguousarray(embeds, np.float32).tobytes()).decode(),
        "shape": list(embeds.shape),
        "dtype": "float32",
    }


def _vl_core(params, chunk, max_prefill=256):
    runner = ModelRunner(VL_CFG, params, num_pages=64, page_size=PAGE,
                         max_batch_size=4, prefill_bucket=16)
    return EngineCore(runner, EngineConfig(
        num_pages=64, page_size=PAGE, max_batch_size=4,
        max_prefill_tokens=max_prefill, max_seq_len=128,
        enable_prefix_caching=False, chunk_prefill_tokens=chunk,
    ))


def _vl_run(core, token_ids, mm, max_tokens=6):
    seq = core.add_request(PreprocessedRequest(
        token_ids=list(token_ids),
        sampling=SamplingOptions(temperature=0.0),
        stop=StopConditions(max_tokens=max_tokens, ignore_eos=True),
        mm_inputs=_mm_payload(mm),
    ))
    while not seq.is_finished:
        core.step()
    return seq.tokens[len(token_ids):]


@pytest.mark.parametrize("chunk", [3, 4, 6])
def test_multimodal_chunked_equals_whole(chunk):
    """Placeholders split across chunk boundaries: each chunk row's
    mm_slot_offset counts the placeholders already covered by earlier
    chunks, so later chunks inject the correct embedding rows. Chunked
    output must equal the whole-prompt run."""
    rng = np.random.default_rng(7)
    params = llama.init_params(VL_CFG, 0)
    # Placeholders land in different chunks for every parametrized size.
    prompt = [5, 6, IMG, IMG, 9, 10, 11, 12, 20, 21, 22, 23, 24, IMG, IMG, 25]
    mm = rng.standard_normal((4, VL_CFG.hidden_size)).astype(np.float32)

    whole = _vl_run(_vl_core(params, chunk=0), prompt, mm)
    chunked = _vl_run(_vl_core(params, chunk=chunk, max_prefill=chunk), prompt, mm)
    assert chunked == whole


def test_multimodal_chunk_rides_mixed_step_with_decode():
    """A multimodal prompt chunked while a text sequence decodes: the decode
    row keeps offset -1 (no substitution), the chunk rows advance theirs."""
    rng = np.random.default_rng(11)
    params = llama.init_params(VL_CFG, 0)
    prompt_mm = [5, 6, IMG, IMG, 9, 10, 11, 12, 20, 21, IMG, 22]
    mm = rng.standard_normal((3, VL_CFG.hidden_size)).astype(np.float32)

    whole = _vl_run(_vl_core(params, chunk=0), prompt_mm, mm)

    core = _vl_core(params, chunk=4)
    text = core.add_request(greedy_request([7, 8, 9, 10], max_tokens=14))
    for _ in range(3):
        core.step()
    text_solo_ref = list(text.tokens[4:])
    out_mm = _vl_run(core, prompt_mm, mm)
    while not text.is_finished:
        core.step()
    assert out_mm == whole
    assert core.mixed_steps > 0

    # The text neighbor is unaffected by sharing steps with the mm chunks.
    solo = _vl_core(params, chunk=4)
    ref = solo.add_request(greedy_request([7, 8, 9, 10], max_tokens=14))
    while not ref.is_finished:
        solo.step()
    assert text.tokens[4:] == ref.tokens[4:]
    assert text.tokens[4 : 4 + len(text_solo_ref)] == text_solo_ref
