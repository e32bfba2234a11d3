"""End-to-end engine core tests on the tiny model (CPU, 8 virtual devices).

Covers: greedy generation determinism vs a naive full-context reference,
prefix-cache reuse across requests, continuous batching of staggered arrivals,
preemption under page pressure, stop conditions, and KV event emission.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from dynamo_tpu.engine.core import EngineConfig, EngineCore
from dynamo_tpu.engine.runner import ModelRunner
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import PRESETS
from dynamo_tpu.protocols.common import (
    FinishReason,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.runtime.engine import Context

CFG = PRESETS["test-tiny"]
PARAMS = llama.init_params(CFG, 0)
PAGE = 4


def make_core(num_pages=64, max_batch=8, on_kv_event=None, **cfg_kw):
    config = EngineConfig(
        num_pages=num_pages, page_size=PAGE, max_batch_size=max_batch,
        max_prefill_tokens=256, max_seq_len=128, **cfg_kw,
    )
    runner = ModelRunner(
        CFG, PARAMS, num_pages=num_pages, page_size=PAGE,
        max_batch_size=max_batch, prefill_bucket=16, attn_impl="reference",
    )
    return EngineCore(runner, config, on_kv_event=on_kv_event)


def run_to_completion(core, max_steps=200, outputs=None):
    outputs = outputs if outputs is not None else {}
    for _ in range(max_steps):
        if not core.has_work:
            break
        for seq, out in core.step():
            outputs.setdefault(seq.seq_id, []).extend(out.token_ids)
            if out.finish_reason is not None:
                outputs.setdefault("finish", {})[seq.seq_id] = out.finish_reason
    return outputs


def greedy_reference(prompt, n_gen):
    """Naive full-recompute greedy decoding — ground truth for the engine."""
    tokens = list(prompt)
    num_pages = 64
    for _ in range(n_gen):
        t = len(tokens)
        pages = list(range(1, (t + PAGE - 1) // PAGE + 1))
        bt = np.zeros((1, len(pages)), np.int32)
        bt[0] = pages
        pos = np.arange(t, dtype=np.int32)[None]
        slots = np.asarray([[pages[i // PAGE] * PAGE + i % PAGE for i in range(t)]], np.int32)
        kc, vc = llama.init_kv_cache(CFG, num_pages, PAGE)
        logits, _, _ = llama.forward(
            PARAMS, CFG, jnp.asarray([tokens], jnp.int32), jnp.asarray(pos), kc, vc,
            jnp.asarray(bt), jnp.asarray(slots), jnp.asarray([t - 1], jnp.int32),
            attn_impl="reference",
        )
        tokens.append(int(jnp.argmax(logits[0])))
    return tokens[len(prompt):]


def greedy_request(prompt, max_tokens=8, **kw):
    return PreprocessedRequest(
        token_ids=list(prompt),
        sampling=SamplingOptions(temperature=0.0),
        stop=StopConditions(max_tokens=max_tokens, **kw),
    )


def test_greedy_matches_full_recompute():
    core = make_core()
    prompt = [5, 6, 7, 8, 9, 10, 11]
    core.add_request(greedy_request(prompt, max_tokens=6))
    outputs = run_to_completion(core)
    assert outputs[0] == greedy_reference(prompt, 6)


def test_batched_staggered_arrivals():
    core = make_core()
    p1, p2 = [1, 2, 3, 4, 5], [9, 8, 7]
    core.add_request(greedy_request(p1, max_tokens=5))
    # The pipelined loop reads a step's tokens while the next one runs: the
    # step that dispatches the prefill hands back nothing yet.
    assert core.step() == []  # prefill 1 dispatched
    core.add_request(greedy_request(p2, max_tokens=5))  # arrives mid-flight
    first = {s.seq_id: out.token_ids for s, out in core.step()}  # prefill 1 read, prefill 2 beside decode 1 dispatched
    assert list(first) == [0] and len(first[0]) == 1
    outputs = run_to_completion(core)
    assert first[0] + outputs[0] == greedy_reference(p1, 5)
    assert outputs[1] == greedy_reference(p2, 5)


def test_prefix_cache_reuse_across_requests():
    core = make_core()
    prompt = list(range(1, 13))  # 12 tokens = 3 full pages
    core.add_request(greedy_request(prompt, max_tokens=2))
    run_to_completion(core)
    seq = core.add_request(greedy_request(prompt, max_tokens=2))
    out2 = run_to_completion(core)
    # Second request must have matched cached prefix pages (2 full pages:
    # the 3rd is capped so the last prompt token's logits are computed).
    assert seq.num_cached_at_start == 8
    assert out2[seq.seq_id] == greedy_reference(prompt, 2)
    assert core.allocator.stats().hits >= 2


def test_stop_token_id():
    core = make_core()
    prompt = [5, 6, 7]
    ref = greedy_reference(prompt, 8)
    stop_at = ref[2]
    req = greedy_request(prompt, max_tokens=8, stop_token_ids=[stop_at])
    core.add_request(req)
    outputs = run_to_completion(core)
    # Ends at the first occurrence of the stop token (inclusive).
    assert outputs[0] == ref[: ref.index(stop_at) + 1]
    assert outputs["finish"][0] == FinishReason.STOP


def test_eos_and_ignore_eos():
    prompt = [5, 6, 7]
    ref = greedy_reference(prompt, 6)
    eos = ref[1]
    core = make_core(eos_token_ids=(eos,))
    core.add_request(greedy_request(prompt, max_tokens=6))
    outputs = run_to_completion(core)
    assert outputs["finish"][0] == FinishReason.STOP
    assert outputs[0] == ref[: ref.index(eos) + 1]

    core2 = make_core(eos_token_ids=(eos,))
    req = greedy_request(prompt, max_tokens=6, ignore_eos=True)
    core2.add_request(req)
    outputs2 = run_to_completion(core2)
    assert outputs2[0] == ref
    assert outputs2["finish"][0] == FinishReason.LENGTH


def test_preemption_under_page_pressure():
    # 7 usable pages; final footprints are 4+4 pages, so decode MUST preempt
    # one sequence and later resume it (recompute + continue) correctly.
    core = make_core(num_pages=8, max_batch=2, enable_prefix_caching=False)
    p1, p2 = [1, 2, 3, 4, 5, 6], [11, 12, 13, 14]
    core.add_request(greedy_request(p1, max_tokens=10))
    core.add_request(greedy_request(p2, max_tokens=10))
    outputs = run_to_completion(core, max_steps=400)
    assert core.num_preemptions > 0, "test must exercise the preemption path"
    assert outputs[0] == greedy_reference(p1, 10)
    assert outputs[1] == greedy_reference(p2, 10)


def test_decode_batch_with_early_finisher():
    # Three running seqs where seq0 finishes first: remaining rows must stay
    # correctly paired with their sequences (regression: mid-loop removal).
    core = make_core()
    prompts = [[1, 2], [3, 4, 5], [9, 8, 7, 6]]
    maxes = [2, 6, 6]
    for p, m in zip(prompts, maxes):
        core.add_request(greedy_request(p, max_tokens=m))
    outputs = run_to_completion(core)
    for i, (p, m) in enumerate(zip(prompts, maxes)):
        assert outputs[i] == greedy_reference(p, m), f"seq {i}"


def test_cancellation_mid_stream():
    core = make_core()
    ctx = Context()
    core.add_request(greedy_request([1, 2, 3], max_tokens=50), ctx)
    core.step()
    core.step()
    ctx.stop_generating()
    outputs = run_to_completion(core, max_steps=10)
    assert outputs["finish"][0] == FinishReason.CANCELLED
    assert not core.has_work


def test_kv_events_stored_then_removed():
    events = []
    core = make_core(num_pages=16, on_kv_event=events.append)
    prompt = list(range(1, 10))  # 9 tokens -> 2 full pages
    core.add_request(greedy_request(prompt, max_tokens=4))
    run_to_completion(core)
    stored = [s.block_hash for e in events for s in e.stored]
    # Prompt pages 1-2 plus pages filled during decode commit as they complete.
    assert len(stored) >= 2
    # Chained parents: first block has no parent, second's parent is first.
    all_stored = [s for e in events for s in e.stored]
    assert all_stored[0].parent_hash is None
    assert all_stored[1].parent_hash == all_stored[0].block_hash


def test_sampling_seed_determinism():
    def run():
        core = make_core()
        req = PreprocessedRequest(
            token_ids=[3, 1, 4, 1, 5],
            sampling=SamplingOptions(temperature=0.9, top_k=40, top_p=0.95, seed=1234),
            stop=StopConditions(max_tokens=8),
        )
        core.add_request(req)
        return run_to_completion(core)[0]

    a, b = run(), run()
    assert a == b and len(a) == 8


def test_reject_too_long_prompt():
    core = make_core()
    seq = core.add_request(greedy_request(list(range(200)), max_tokens=2))
    assert seq.is_finished and seq.finish_reason == FinishReason.LENGTH


def make_core_multi(decode_steps, num_pages=64, max_batch=8, **cfg_kw):
    config = EngineConfig(
        num_pages=num_pages, page_size=PAGE, max_batch_size=max_batch,
        max_prefill_tokens=256, max_seq_len=128, decode_steps=decode_steps, **cfg_kw,
    )
    runner = ModelRunner(
        CFG, PARAMS, num_pages=num_pages, page_size=PAGE,
        max_batch_size=max_batch, prefill_bucket=16, attn_impl="reference",
    )
    return EngineCore(runner, config)


def test_chained_sub_dispatches_match_single_step():
    # decode_steps=4 in the pipelined loop (the default): four chained
    # sub-dispatches a step must be token-identical to per-step decode.
    prompts = [[1, 2, 3, 4, 5], [9, 8, 7]]
    core = make_core_multi(decode_steps=4)
    for p in prompts:
        core.add_request(greedy_request(p, max_tokens=10))
    outputs = run_to_completion(core)
    for i, p in enumerate(prompts):
        assert outputs[i] == greedy_reference(p, 10), f"seq {i}"


def test_chained_sub_dispatches_stop_token_discards_overshoot():
    prompt = [5, 6, 7]
    ref = greedy_reference(prompt, 8)
    stop_at = ref[2]
    core = make_core_multi(decode_steps=4)
    core.add_request(greedy_request(prompt, max_tokens=8, stop_token_ids=[stop_at]))
    outputs = run_to_completion(core)
    assert outputs[0] == ref[: ref.index(stop_at) + 1]
    assert outputs["finish"][0] == FinishReason.STOP


def test_chained_sub_dispatches_odd_max_tokens():
    # max_tokens not a multiple of the burst size.
    prompt = [2, 4, 6]
    core = make_core_multi(decode_steps=4)
    core.add_request(greedy_request(prompt, max_tokens=6))
    outputs = run_to_completion(core)
    assert outputs[0] == greedy_reference(prompt, 6)
    assert outputs["finish"][0] == FinishReason.LENGTH


def test_pipelined_decode_midstream_admission():
    # A request admitted while a chained burst is in flight must be absorbed
    # cleanly (the overlap pipeline re-plans composition per step); both
    # sequences still match the greedy reference. decode_steps>1 pipelining
    # is served by the overlap path since the standalone burst pipeline
    # was folded into it.
    core = make_core_multi(decode_steps=4, overlap=True)
    p1, p2 = [1, 2, 3, 4, 5], [9, 8, 7]
    core.add_request(greedy_request(p1, max_tokens=12))
    # Fill the pipeline (prefill step + first dispatched burst + one chained).
    outputs = {}
    for _ in range(3):
        for seq, out in core.step():
            outputs.setdefault(seq.seq_id, []).extend(out.token_ids)
    assert core._inflight is not None
    core.add_request(greedy_request(p2, max_tokens=12))
    outputs = run_to_completion(core, outputs=outputs)
    assert outputs[0] == greedy_reference(p1, 12)
    assert outputs[1] == greedy_reference(p2, 12)


def test_pipelined_decode_cancellation_inflight():
    core = make_core_multi(decode_steps=4, overlap=True)
    ctx1, ctx2 = Context(), Context()
    core.add_request(greedy_request([1, 2, 3], max_tokens=40), ctx1)
    core.add_request(greedy_request([4, 5, 6], max_tokens=40), ctx2)
    outputs = {}
    for _ in range(3):
        for seq, out in core.step():
            outputs.setdefault(seq.seq_id, []).extend(out.token_ids)
    assert core._inflight is not None
    ctx1.stop_generating()
    outputs = run_to_completion(core, outputs=outputs)
    assert outputs["finish"][0] == FinishReason.CANCELLED
    # The surviving sequence still completes correctly.
    assert outputs[1] == greedy_reference([4, 5, 6], 40)
    assert core._inflight is None


def test_burst_overshoot_cannot_corrupt_live_pages():
    """Heterogeneous finish lines inside one burst of chained sub-dispatches:
    a sequence whose max_tokens ends mid-burst must not let the burst's
    overshoot KV writes land in live pages (the shortest row clamps the
    depth; the finish-line clamp masks the rest to the null page). Everyone stays
    token-exact vs the step-by-step greedy reference, including a follow-up
    request that reuses the short sequence's cached prefix."""
    core = make_core_multi(decode_steps=8)
    prompts = [[1, 2, 3, 4, 5], [9, 8, 7], [11, 12, 13, 14]]
    budgets = [3, 17, 9]  # finish lines at different points within/across bursts
    for p, mt in zip(prompts, budgets):
        core.add_request(greedy_request(p, max_tokens=mt))
    outputs = run_to_completion(core)
    for i, (p, mt) in enumerate(zip(prompts, budgets)):
        assert outputs[i] == greedy_reference(p, mt), f"seq {i}"

    # The short sequence's pages are prefix cache now; a request extending
    # its prompt must see uncorrupted KV (token-exact again).
    ext = prompts[0] + outputs[0][:2]
    core.add_request(greedy_request(ext, max_tokens=6))
    out2 = run_to_completion(core)
    assert out2[3] == greedy_reference(ext, 6)
