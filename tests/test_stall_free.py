"""Fast-tier stall-free invariant: the engine never runs a prefill-only
step while decodable sequences are running (ISSUE 2 CI guard).

Uses a stub runner (no jit, no model) so the scheduler's dispatch
composition is observable directly: every dispatch records its per-row
token counts, and ``EngineCore.last_step_info`` / ``stall_violations``
expose what the step carried. A future scheduler refactor that silently
reintroduces the prefill-XOR-decode behavior fails here in milliseconds.
"""

import numpy as np

from dynamo_tpu.engine.core import EngineConfig, EngineCore
from dynamo_tpu.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)

PAGE = 4


class StubCfg:
    vocab_size = 128
    image_token_id = None
    video_token_id = None
    mrope_section = None


class StubRunner:
    """Minimal ModelRunner stand-in: returns a fixed token for every row and
    records each dispatch's per-row new-token counts."""

    def __init__(self, num_pages=64, page_size=PAGE):
        self.num_pages = num_pages
        self.page_size = page_size
        self.cfg = StubCfg()
        self.dispatches: list[np.ndarray | None] = []  # num_new per dispatch

    def take_dispatch(self):
        return None

    def step(self, batch, lp_k=0):
        self.dispatches.append(None if batch.num_new is None
                               else np.asarray(batch.num_new))
        b = batch.tokens.shape[0]
        toks = np.full(b, 7, np.int32)
        if lp_k:
            zeros = np.zeros((b,), np.float32)
            return toks, (zeros, np.zeros((b, lp_k), np.int32),
                          np.zeros((b, lp_k), np.float32))
        return toks


def make_core(chunk, num_pages=64, max_batch=8, max_prefill=256, **cfg_kw):
    runner = StubRunner(num_pages=num_pages)
    return EngineCore(runner, EngineConfig(
        num_pages=num_pages, page_size=PAGE, max_batch_size=max_batch,
        max_prefill_tokens=max_prefill, max_seq_len=256,
        chunk_prefill_tokens=chunk, enable_prefix_caching=False, **cfg_kw,
    ))


def req(n_prompt, max_tokens=8, start=1):
    return PreprocessedRequest(
        token_ids=list(range(start, start + n_prompt)),
        sampling=SamplingOptions(temperature=0.0),
        stop=StopConditions(max_tokens=max_tokens, ignore_eos=True),
    )


def drive(core, inject=(), max_steps=500, check=True):
    """Step to completion, injecting (at_step, request) pairs; after every
    step assert the stall-free invariant via last_step_info."""
    pending = sorted(inject, key=lambda x: x[0], reverse=True)
    for i in range(max_steps):
        if not core.has_work and not pending:
            return i
        while pending and pending[-1][0] <= i:
            core.add_request(pending.pop()[1])
        info = dict(core.last_step_info)
        core.step()
        if check and core.last_step_info != info:  # dispatched mixed work
            got = core.last_step_info
            if got["chunk_rows"] and got["decodable"]:
                assert got["decode_rows"] == got["decodable"], (
                    f"step {i}: prefill chunks dispatched without the "
                    f"running decodes: {got}"
                )
    raise AssertionError("engine did not drain")


def test_stall_free_invariant_under_long_prefill():
    """Decodes running + a long prompt arriving: every dispatch that carries
    prefill chunks must also carry every decodable row."""
    core = make_core(chunk=4)
    for i in range(3):
        core.add_request(req(5, max_tokens=30, start=10 * i + 1))
    drive(core, inject=[(6, req(60, max_tokens=4, start=60))])
    assert core.mixed_steps > 0
    assert core.stall_violations == 0


def test_legacy_xor_mode_counts_violations():
    """chunk_prefill_tokens=0 restores phase-exclusive steps — and the
    violation counter proves the probe can see the difference."""
    core = make_core(chunk=0)
    for i in range(3):
        core.add_request(req(5, max_tokens=30, start=10 * i + 1))
    drive(core, inject=[(6, req(60, max_tokens=4, start=60))], check=False)
    assert core.mixed_steps == 0
    assert core.stall_violations > 0


def test_chunks_respect_budget_while_decoding():
    """With decodes running, no dispatch row computes more than the chunk
    budget; decode rows are always exactly 1 token."""
    chunk = 4
    core = make_core(chunk=chunk)
    core.add_request(req(5, max_tokens=40))
    drive(core, inject=[(3, req(57, max_tokens=2, start=100))])
    mixed = [d for d in core.runner.dispatches if d is not None and len(d) > 1]
    assert mixed, "scenario must produce fused dispatches"
    for d in mixed:
        assert d.max() <= chunk


def test_head_of_line_incremental_admission():
    """A prompt needing more pages than are currently free must admit
    incrementally as pages free up — not park at waiting[0] forever (the
    HOL fix) and not wedge the engine."""
    # 15 usable pages (page 0 is reserved); the decoder holds ~4 and the
    # 48-token prompt needs 12 at once — it can never have all 12 while
    # the decoder lives, so only chunked admission can start it.
    core = make_core(chunk=4, num_pages=16, max_batch=4)
    core.add_request(req(8, max_tokens=6))
    big = core.add_request(req(48, max_tokens=2, start=100))
    started_while_short_ran = False
    for _ in range(200):
        if not core.has_work:
            break
        core.step()
        if core.prefilling and any(not s.is_finished for s in [big]):
            if any(s.num_generated < 6 and s is not big for s in core.running):
                started_while_short_ran = True
    assert big.is_finished and big.finish_reason is not None
    assert big.finish_reason.value == "length"
    assert started_while_short_ran, "big prompt should start before the pool is idle"


def test_never_fitting_prompt_rejected_not_wedged():
    """A prompt that can never fit the page pool is rejected with an error
    finish instead of wedging the queue head."""
    core = make_core(chunk=4, num_pages=8, max_batch=4)
    seq = core.add_request(req(200, max_tokens=2))
    assert seq.is_finished
    # Engine still serves others.
    ok = core.add_request(req(5, max_tokens=3))
    for _ in range(50):
        if not core.has_work:
            break
        core.step()
    assert ok.is_finished and ok.finish_reason.value == "length"


def test_mid_prompt_sequence_not_decodable():
    """A sequence mid-chunk must never appear in a decode batch: its rows
    always come in via chunk scheduling (num_new set), and it only joins
    running after its final chunk."""
    core = make_core(chunk=4)
    seq = core.add_request(req(19, max_tokens=3))
    while core.prefilling or core.waiting:
        assert seq not in core.running
        core.step()
    assert seq in core.running or seq.is_finished
    assert seq.num_cached >= 19
