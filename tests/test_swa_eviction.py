"""Sliding-window page eviction: out-of-window KV pages are released while
the sequence keeps decoding, without changing a single output token.

A window-w model can never attend keys at positions <= q_pos - w, so pages
wholly below the window are dead weight (a 32k-context Mistral stream with
window 4k pins ~28k tokens of KV otherwise). Release must be invisible:
the block table keeps positional shape via the null page, whose (masked)
contents can't influence logits.
"""

import dataclasses

import numpy as np

from dynamo_tpu.engine.core import EngineConfig, EngineCore
from dynamo_tpu.engine.runner import ModelRunner
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import PRESETS
from dynamo_tpu.protocols.common import PreprocessedRequest, SamplingOptions, StopConditions
from dynamo_tpu.runtime.engine import Context

PAGE = 4
CFG = dataclasses.replace(PRESETS["test-tiny"], sliding_window=8)  # 2 pages of window
PARAMS = llama.init_params(CFG, 0)


def _core(swa_free: bool, num_pages=64, caching=True):
    runner = ModelRunner(CFG, PARAMS, num_pages=num_pages, page_size=PAGE,
                         max_batch_size=2, prefill_bucket=16, attn_impl="reference")
    return EngineCore(runner, EngineConfig(
        num_pages=num_pages, page_size=PAGE, max_batch_size=2,
        max_prefill_tokens=64, max_seq_len=128, decode_steps=2,
        swa_free_pages=swa_free, enable_prefix_caching=caching,
    ))


def _generate(core, n_gen=40, prompt=(3, 5, 7, 11, 13, 2, 4, 6)):
    seq = core.add_request(PreprocessedRequest(
        token_ids=list(prompt), sampling=SamplingOptions(temperature=0.0),
        stop=StopConditions(max_tokens=n_gen, ignore_eos=True),
    ), Context())
    toks = []
    live = []
    zeros = []
    while core.has_work:
        for s, out in core.step():
            toks.extend(out.token_ids)
        if seq.pages:  # cleared at finish
            live.append(sum(1 for p in seq.pages if p != 0))
            zeros.append(seq.pages.count(0))
    return toks, seq, (live, zeros)


def test_out_of_window_pages_release_without_changing_tokens():
    base_toks, _s, (base_live, base_zeros) = _generate(_core(swa_free=False))
    toks, _s2, (live, zeros) = _generate(_core(swa_free=True))
    assert toks == base_toks, "page release changed generated tokens"
    # Pages below the window were nulled out of the table during the run...
    assert max(zeros) > 0
    # ...bounded by the window: live pages stay at window + partial + slack
    # while the non-freeing run's footprint keeps growing.
    window_pages = CFG.sliding_window // PAGE
    assert live[-1] <= window_pages + 2
    assert base_live[-1] > live[-1]
    assert max(base_zeros) == 0


def test_stream_longer_than_the_pool_without_caching():
    """With prefix caching off, released pages go straight to the free
    list: a stream whose total context EXCEEDS the pool (48 tokens = 12
    pages vs 9 usable) completes with zero preemptions — impossible
    without the release."""
    core = _core(swa_free=True, num_pages=10, caching=False)
    toks, _seq, (live, _zeros) = _generate(core, n_gen=40)
    assert len(toks) == 40
    assert core.num_preemptions == 0
    assert max(live) <= 10  # never holds anywhere near 12 pages
    # Control: the same run without the release cannot fit the pool.
    ctrl = _core(swa_free=False, num_pages=10, caching=False)
    ctrl_toks, _s, _ = _generate(ctrl, n_gen=40)
    assert ctrl.num_preemptions > 0 or len(ctrl_toks) < 40


def test_released_pages_evictable_while_stream_still_running():
    """With caching on, released pages demote to refcount-0 prefix cache
    that a CONCURRENT request can evict — the long stream keeps decoding,
    nobody is preempted. Without the release those pages stay pinned by
    the running sequence and admission must preempt it."""
    def drive(swa_free):
        core = _core(swa_free=swa_free, num_pages=14)
        long_req = PreprocessedRequest(
            token_ids=[3, 5, 7, 11, 13, 2, 4, 6],
            sampling=SamplingOptions(temperature=0.0),
            stop=StopConditions(max_tokens=40, ignore_eos=True),
        )
        core.add_request(long_req, Context())
        for _ in range(12):  # long stream slides well past its window
            core.step()
        # Second request: needs more pages than the free list holds.
        core.add_request(PreprocessedRequest(
            token_ids=list(range(20, 36)),  # 4 pages of prompt
            sampling=SamplingOptions(temperature=0.0),
            stop=StopConditions(max_tokens=8, ignore_eos=True),
        ), Context())
        done = 0
        while core.has_work and done < 200:
            core.step()
            done += 1
        return core

    core = drive(swa_free=True)
    assert core.num_preemptions == 0, "demoted pages should satisfy admission"
    ctrl = drive(swa_free=False)
    assert ctrl.num_preemptions > 0, "control must actually be page-starved"


def test_mixed_window_and_full_layers_release_the_window_pool_alone():
    """A page pool per layer kind (ISSUE 42): the full layer reads the whole
    context, so its pool's pages stay while the sequence lives; the sliding
    layer's pages go back to their own pool behind the window, as the
    uniform-window model's one pool's do, and the tokens are what they are
    with nothing released."""
    from dynamo_tpu.models.config import FULL, SLIDING

    mixed = dataclasses.replace(CFG, layer_types=(SLIDING, FULL))

    def core_of(swa_free: bool):
        runner = ModelRunner(mixed, PARAMS, num_pages=64, page_size=PAGE, max_batch_size=2,
                             prefill_bucket=16, attn_impl="reference")
        return EngineCore(runner, EngineConfig(num_pages=64, page_size=PAGE, max_batch_size=2, max_prefill_tokens=64,
                                               max_seq_len=128, decode_steps=2, swa_free_pages=swa_free))

    core = core_of(True)
    window_zeros = []
    seq = core.add_request(PreprocessedRequest(
        token_ids=[3, 5, 7, 11, 13, 2, 4, 6], sampling=SamplingOptions(temperature=0.0),
        stop=StopConditions(max_tokens=40, ignore_eos=True)), Context())
    toks, zeros, live = [], [], []
    while core.has_work:
        for _s, out in core.step():
            toks.extend(out.token_ids)
        if seq.pages:
            zeros.append(seq.pages.count(0))
            window_zeros.append(seq.window_pages.count(0))
            live.append(len(seq.pages))
    assert len(toks) == 40 and max(zeros) == 0 and live[-1] >= (8 + 40) // PAGE - 1
    assert 0 < max(window_zeros) <= core.window_pages_released
    assert core.allocator.live == 0 and core.window_allocator.live == 0
    base, _seq, (_live, base_zeros) = _generate(core_of(False))
    assert base == toks and max(base_zeros) == 0
    _toks, _s, (_live, uniform_zeros) = _generate(_core(swa_free=True))
    assert max(uniform_zeros) > 0
