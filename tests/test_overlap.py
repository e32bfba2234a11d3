"""Overlapped execution pipeline (ISSUE 10, generalized by ISSUE 11).

The contract under test: the pipelined loop (``overlap=True``, the serving
loop since ISSUE 29) emits *bit-identical* token streams AND logprobs to the
synchronous step (``overlap=False``, the oracle) —
greedy and seeded, with chunked prefill interleaving, across late-detected
stops — because the depth-1 pipeline only changes WHEN tokens cross the
device->host boundary, never what was sampled: the chained step's input
tokens are the same values the host would have shipped, its rng fold
counter advances exactly as the synchronous loop's would, and a stop
detected one step late cancels the in-flight row (token discarded, pages
released) instead of emitting it.

ISSUE 11 erased the hot barriers, so the parity net now also pins the
newly chained compositions: mixed prefill+decode steps, penalized rows
(history written in-graph), ``spec_k>0`` (a verify chains its base token
in; the step after it takes its tokens from the host), and budget-clamped
final tokens (in-graph pos_limit mask instead of a host drain). Also
covered: barrier-reason accounting, the offload-batch async gather routing,
and the launch side: no name of the cascade arms the loop or keeps a verify
out of it.
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

PAGE = 4
_PARAMS = {}
_RUNNERS = {}


def params_for(preset):
    if preset not in _PARAMS:
        _PARAMS[preset] = llama.init_params(PRESETS[preset], 0)
    return _PARAMS[preset]


def make_core(preset="test-tiny", *, overlap=False, chunk=16, num_pages=96,
              max_batch=8, max_seq_len=256, eos=(), **cfg_kw):
    # One runner per preset, shared across tests and across the sync/overlap
    # runs of each parity pair: the jit caches live on the runner, so every
    # graph compiles once per preset for the whole module — and the parity
    # runs exercising the SAME compiled graphs is exactly the claim under
    # test (overlap changes when results move, not what is computed). A
    # fresh EngineCore re-owns the page pool; stale KV in recycled pages is
    # rewritten by prefill before anything attends to it.
    if preset not in _RUNNERS:
        _RUNNERS[preset] = ModelRunner(
            PRESETS[preset], params_for(preset), num_pages=num_pages,
            page_size=PAGE, max_batch_size=max_batch, prefill_bucket=16,
            attn_impl="reference",
        )
    return EngineCore(_RUNNERS[preset], EngineConfig(
        num_pages=num_pages, page_size=PAGE, max_batch_size=max_batch,
        max_seq_len=max_seq_len, chunk_prefill_tokens=chunk, overlap=overlap,
        eos_token_ids=tuple(eos), **cfg_kw,
    ))


def run_all(core, reqs, max_steps=400):
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


def _requests(vocab):
    """Greedy + seeded + logprobs + chunked prefill riding the same engine."""
    return [
        PreprocessedRequest(
            token_ids=[5, 7, 5, 7, 5, 7, 9, 11],
            sampling=SamplingOptions(temperature=0.0),
            stop=StopConditions(max_tokens=14, ignore_eos=True),
        ),
        # Long prompt: its chunked prefill forces pipeline barriers while
        # the first request decodes — the overlap path must re-fill after.
        PreprocessedRequest(
            token_ids=[i % (vocab - 2) + 1 for i in range(26)],
            sampling=SamplingOptions(temperature=0.8, seed=42, logprobs=3),
            stop=StopConditions(max_tokens=10, ignore_eos=True),
        ),
        PreprocessedRequest(
            token_ids=[3, 3, 3, 3, 2, 1],
            sampling=SamplingOptions(temperature=0.7, seed=7),
            stop=StopConditions(max_tokens=10, ignore_eos=True),
        ),
    ]


# -- bit parity --------------------------------------------------------------


@pytest.mark.parametrize("preset", ["test-tiny", "test-tiny-mla"])
def test_overlap_is_bit_identical(preset):
    vocab = PRESETS[preset].vocab_size
    base_tok, base_lp = run_all(make_core(preset), _requests(vocab))
    core = make_core(preset, overlap=True)
    over_tok, over_lp = run_all(core, _requests(vocab))
    assert over_tok == base_tok
    assert over_lp == base_lp
    assert core.overlap_step_counts["overlapped"] > 0  # the path engaged
    assert core.allocator.stats().active_pages == 0


def test_overlap_bit_identical_with_staggered_admission():
    """A request admitted mid-decode forces a drain barrier; the re-filled
    pipeline must keep every stream bit-identical."""
    vocab = PRESETS["test-tiny"].vocab_size

    def run(overlap):
        core = make_core(overlap=overlap)
        reqs = _requests(vocab)
        tokens = {}
        for req in reqs[:2]:
            seq = core.add_request(req)
            tokens[seq.seq_id] = []
        late_added = False
        steps = 0
        while core.has_work and steps < 400:
            if steps == 6 and not late_added:
                seq = core.add_request(reqs[2])
                tokens[seq.seq_id] = []
                late_added = True
            for seq, out in core.step():
                tokens[seq.seq_id].extend(out.token_ids)
            steps += 1
        assert not core.has_work
        return tokens, core

    base, _ = run(False)
    over, core = run(True)
    assert over == base
    assert core.overlap_step_counts["overlapped"] > 0
    assert core.allocator.stats().active_pages == 0
    # Barrier-reason observability (ISSUE 11): every armed STEP record
    # names its pipeline mode; barrier steps carry the condition that
    # forced them, and the engine aggregates the same per-reason counts.
    from dynamo_tpu.observability.flight import STEP

    steps = [r for r in core.flight.snapshot(kind=STEP) if r.get("overlap_mode")]
    assert steps, "no armed STEP records"
    barriers = [r for r in steps if r["overlap_mode"] == "barrier"]
    assert all(r.get("barrier_reason") for r in barriers)
    assert all("chained_rows" in r for r in steps)
    assert sum(core.overlap_barrier_counts.values()) == len(barriers)


# -- late-stop cancellation --------------------------------------------------


_STREAM_CACHE = {}


def _greedy_stream(preset="test-tiny", n=16, prompt=(5, 7, 5, 7, 9, 11)):
    """The model's deterministic greedy continuation of a fixed prompt."""
    key = (preset, n, tuple(prompt))
    if key not in _STREAM_CACHE:
        toks, _ = run_all(make_core(preset), [PreprocessedRequest(
            token_ids=list(prompt),
            sampling=SamplingOptions(temperature=0.0),
            stop=StopConditions(max_tokens=n, ignore_eos=True),
        )])
        _STREAM_CACHE[key] = toks[0]
    return _STREAM_CACHE[key]


def test_late_stop_cancels_inflight_row_no_leak_no_overrun():
    """A stop token detected one step behind the pipeline: the in-flight
    chained step has already computed the over-run token — it must never be
    emitted, and the rollback must release every page."""
    # This prompt's greedy stream repeats its first token six times and then
    # turns to a second: a token whose FIRST occurrence is a few steps in (the
    # stream of [5, 7, 5, 7, 9, 11] has none past index 3). The pipeline has
    # chained by then, so the stop is detected with a step in flight.
    prompt = [3, 3, 3, 3, 2, 1]
    stream = _greedy_stream(prompt=prompt)
    stop_tok = next(t for i, t in enumerate(stream) if stream.index(t) == i and i >= 4)
    req = lambda: PreprocessedRequest(  # noqa: E731
        token_ids=list(prompt),
        sampling=SamplingOptions(temperature=0.0),
        stop=StopConditions(max_tokens=16, ignore_eos=True,
                            stop_token_ids=[stop_tok]),
    )
    base_tok, _ = run_all(make_core(), [req()])
    core = make_core(overlap=True)
    over_tok, _ = run_all(core, [req()])
    assert over_tok == base_tok
    assert over_tok[0][-1] == stop_tok
    expected = stream[: stream.index(stop_tok) + 1]
    assert over_tok[0] == expected  # never the over-run token
    assert core.overlap_step_counts["overlapped"] > 0
    assert core.allocator.stats().active_pages == 0  # rollback leaked nothing


def test_late_eos_stop_parity_and_page_accounting():
    """Same cancellation via the EOS path, with other sequences surviving
    the barrier: their streams must continue bit-identically after the
    stopped row's rollback (rng-fold continuity across the drain)."""
    stream = _greedy_stream()
    eos = next(t for i, t in enumerate(stream) if stream.index(t) == i and i >= 3)
    eos_at = stream.index(eos)
    reqs = lambda: [  # noqa: E731
        PreprocessedRequest(
            token_ids=[5, 7, 5, 7, 9, 11],
            sampling=SamplingOptions(temperature=0.0),
            stop=StopConditions(max_tokens=20),
        ),
        PreprocessedRequest(
            token_ids=[3, 3, 3, 3, 2, 1],
            sampling=SamplingOptions(temperature=0.7, seed=7),
            stop=StopConditions(max_tokens=16, ignore_eos=True),
        ),
    ]
    base_tok, _ = run_all(make_core(eos=[eos]), reqs())
    core = make_core(overlap=True, eos=[eos])
    over_tok, _ = run_all(core, reqs())
    assert over_tok == base_tok
    assert over_tok[0][-1] == eos and len(over_tok[0]) == eos_at + 1
    assert len(over_tok[1]) == 16  # survivor ran to its own limit
    assert core.allocator.stats().active_pages == 0


# -- rng-fold discipline -----------------------------------------------------


def test_chained_dispatch_fold_counter_matches_sync(monkeypatch):
    """The chained step dispatches with ``sample_steps + 1`` — exactly the
    fold counter the synchronous loop would use after harvesting the
    in-flight token. Fold advances once per emitted token, never per
    dispatch."""
    core = make_core(overlap=True, chunk=0)
    calls = []
    orig = core.runner.step_async

    def spy(batch, lp_k=0, *, chain=False, chain_src=None):
        calls.append((bool(chain), int(np.asarray(batch.sample_steps)[0])))
        return orig(batch, lp_k=lp_k, chain=chain, chain_src=chain_src)

    monkeypatch.setattr(core.runner, "step_async", spy)
    seq = core.add_request(PreprocessedRequest(
        token_ids=[1, 2, 3, 4],
        sampling=SamplingOptions(temperature=0.9, seed=11),
        stop=StopConditions(max_tokens=12, ignore_eos=True),
    ))
    emitted = 0
    steps = 0
    while core.has_work and steps < 100:
        before = len(calls)
        outs = core.step()
        for chained, fold in calls[before:]:
            # Non-chained dispatch samples token number `emitted`; a chained
            # one samples token `emitted + 1` (the in-flight token between
            # them is harvested only afterwards).
            assert fold == emitted + (1 if chained else 0)
        emitted += sum(len(o.token_ids) for _, o in outs)
        steps += 1
    assert emitted == 12
    assert seq.num_generated == 12
    assert any(chained for chained, _ in calls)  # the pipeline actually chained


# -- newly chained compositions (ISSUE 11) -----------------------------------


@pytest.mark.parametrize("preset", ["test-tiny", "test-tiny-mla"])
def test_mixed_prefill_decode_interleave_chains(preset):
    """A long prompt admitted mid-decode: its chunked prefill rides the same
    overlapped steps as the decoding rows (per-row token sourcing), with
    every stream bit-identical and no 'prefill' barriers taken."""
    vocab = PRESETS[preset].vocab_size

    def run(overlap):
        core = make_core(preset, overlap=overlap, chunk=8)
        reqs = _requests(vocab)
        tokens, lps = {}, {}
        for req in reqs[:1] + reqs[2:]:
            seq = core.add_request(req)
            tokens[seq.seq_id] = []
            lps[seq.seq_id] = []
        steps = 0
        late_added = False
        while core.has_work and steps < 400:
            if steps == 3 and not late_added:
                seq = core.add_request(reqs[1])  # 26-token prompt: 4 chunks
                tokens[seq.seq_id] = []
                lps[seq.seq_id] = []
                late_added = True
            for seq, out in core.step():
                tokens[seq.seq_id].extend(out.token_ids)
                if out.logprobs:
                    lps[seq.seq_id].extend(out.logprobs)
            steps += 1
        assert not core.has_work
        return tokens, lps, core

    base_tok, base_lp, _ = run(False)
    over_tok, over_lp, core = run(True)
    assert over_tok == base_tok
    assert over_lp == base_lp
    counts = core.overlap_step_counts
    assert counts["overlapped"] > counts.get("barrier", 0)
    assert "prefill" not in core.overlap_barrier_counts  # chunks chained
    assert core.allocator.stats().active_pages == 0


def test_spec_k_chains_with_overlap():
    """overlap + spec_k compose: a verify rides the pipeline (its base
    token chained in from a plain step in flight) — bit-identical to the
    plain baseline with both speculation and chaining engaged."""
    reqs = lambda: [PreprocessedRequest(  # noqa: E731 - periodic prompt drafts well
        token_ids=[5, 7, 5, 7, 5, 7, 9, 11],
        sampling=SamplingOptions(temperature=0.0, logprobs=2),
        stop=StopConditions(max_tokens=12, ignore_eos=True),
    )]
    base_tok, base_lp = run_all(make_core(), reqs())
    core = make_core(overlap=True, spec_k=3)
    spec_tok, spec_lp = run_all(core, reqs())
    assert spec_tok == base_tok
    # The tokens and the alternatives' ids are the stream's; a logprob is the
    # verify program's, which scores four positions of a row in one forward
    # where the plain step scores one: its reductions tile otherwise, and a
    # value may differ in the last place (tests/test_spec_decode.py likewise).
    for got, want in zip(spec_lp[0], base_lp[0], strict=True):
        assert got["id"] == want["id"] and [t for t, _ in got["top"]] == [t for t, _ in want["top"]]
        np.testing.assert_allclose([got["logprob"]] + [lp for _, lp in got["top"]],
                                   [want["logprob"]] + [lp for _, lp in want["top"]], rtol=0, atol=1e-5)
    assert core.spec_tokens_proposed > 0  # speculation engaged
    assert core.overlap_step_counts["overlapped"] > 0  # and still pipelined


@pytest.mark.parametrize("temperature, seed", [(0.0, None), (0.8, 5)], ids=["greedy", "seeded"])
def test_the_step_after_a_verify_takes_its_tokens_from_the_host(temperature, seed):
    """A verify is harvested before anything is composed on it, so the
    accepted tokens are in ``s.tokens``: the dispatch after it chains nothing
    (no ``_chain_map`` entry, no chained row) and the streams are
    ``spec_k=0``'s."""
    reqs = lambda: [PreprocessedRequest(  # noqa: E731 - periodic prompts draft well
        token_ids=prompt,
        sampling=SamplingOptions(temperature=temperature, seed=seed),
        stop=StopConditions(max_tokens=14, ignore_eos=True),
    ) for prompt in ([5, 7, 5, 7, 5, 7, 9, 11], [3, 1, 3, 1, 3, 1, 3])]
    base_tok, _ = run_all(make_core(overlap=True), reqs())
    core = make_core(overlap=True, spec_k=3)
    spec_tok = {core.add_request(r).seq_id: [] for r in reqs()}
    after_a_verify = 0
    for _ in range(400):
        if not core.has_work:
            break
        verify_in_flight = core._inflight is not None and core._inflight.kind == "spec"
        for seq, out in core.step():
            spec_tok[seq.seq_id].extend(out.token_ids)
        if verify_in_flight and core.last_step_info.get("decode_rows"):
            after_a_verify += 1
            assert core.last_step_info["chained_rows"] == 0
            if core._inflight.kind == "spec":  # a verify again: it leaves no entry and no buffer either
                assert core._chain_map == {} and core.runner._chain_tokens is None
    assert not core.has_work and spec_tok == base_tok
    assert after_a_verify > 0 and core.spec_tokens_proposed > 0
    assert core.overlap_barrier_counts.get("spec", 0) >= after_a_verify  # a verify in flight is harvested first
    assert core.allocator.stats().active_pages == 0


def test_penalized_sampling_chains():
    """Penalized rows no longer barrier: the chained token's history count
    is written in-graph, so presence/frequency/repetition penalties see
    the same history the synchronous loop would."""
    req = lambda: PreprocessedRequest(  # noqa: E731
        token_ids=[5, 7, 5, 7, 9, 11],
        sampling=SamplingOptions(
            temperature=0.8, seed=3, frequency_penalty=0.5,
            presence_penalty=0.3, logprobs=2,
        ),
        stop=StopConditions(max_tokens=12, ignore_eos=True),
    )
    base_tok, base_lp = run_all(make_core(), [req()])
    core = make_core(overlap=True)
    over_tok, over_lp = run_all(core, [req()])
    assert over_tok == base_tok
    assert over_lp == base_lp
    assert core.overlap_step_counts["overlapped"] > 0  # penalties chained


@pytest.mark.parametrize("preset", ["test-tiny", "test-tiny-mla"])
def test_budget_clamped_final_token_chains(preset):
    """Rows one token from max_tokens used to force a drain (the chained
    write could overrun the page/pos budget); the in-graph pos_limit mask
    clamps it instead. A short row finishing mid-pipeline must not barrier
    the surviving rows or corrupt their streams."""
    vocab = PRESETS[preset].vocab_size
    reqs = lambda: [  # noqa: E731
        PreprocessedRequest(
            token_ids=[5, 7, 5, 7, 9, 11],
            sampling=SamplingOptions(temperature=0.0),
            stop=StopConditions(max_tokens=3, ignore_eos=True),  # ends in-pipe
        ),
        PreprocessedRequest(
            token_ids=[i % (vocab - 2) + 1 for i in range(9)],
            sampling=SamplingOptions(temperature=0.7, seed=13, logprobs=2),
            stop=StopConditions(max_tokens=14, ignore_eos=True),
        ),
    ]
    base_tok, base_lp = run_all(make_core(preset), reqs())
    core = make_core(preset, overlap=True)
    over_tok, over_lp = run_all(core, reqs())
    assert over_tok == base_tok
    assert over_lp == base_lp
    assert [len(t) for t in over_tok.values()] == [3, 14]  # exact budgets
    assert core.overlap_step_counts["overlapped"] > 0
    assert core.allocator.stats().active_pages == 0


def test_multistep_rides_the_chained_pipeline_under_overlap():
    """overlap + decode_steps>1: the burst is served as K chained
    sub-dispatches inside the unified pipeline (no 'multistep' barrier
    exists anymore) — bit-identically vs the one-token synchronous oracle,
    admission drains included, and with the sub-steps counted as chained
    rows."""
    reqs = lambda: [  # noqa: E731
        PreprocessedRequest(
            token_ids=[5, 7, 5, 7, 9, 11],
            sampling=SamplingOptions(temperature=0.0),
            stop=StopConditions(max_tokens=12, ignore_eos=True),
        ),
        PreprocessedRequest(
            token_ids=[3, 3, 3, 3, 2, 1],
            sampling=SamplingOptions(temperature=0.7, seed=7),
            stop=StopConditions(max_tokens=11, ignore_eos=True),
        ),
    ]
    base_tok, _ = run_all(make_core(decode_steps=4), reqs())
    core = make_core(overlap=True, decode_steps=4)
    over_tok = {}
    for req in reqs():
        over_tok[core.add_request(req).seq_id] = []
    max_chained = 0
    for _ in range(400):
        if not core.has_work:
            break
        for seq, out in core.step():
            over_tok[seq.seq_id].extend(out.token_ids)
        max_chained = max(max_chained, core.last_step_info.get("chained_rows", 0))
    assert not core.has_work
    assert over_tok == base_tok
    assert core.overlap_step_counts["overlapped"] > 0
    assert "multistep" not in core.overlap_barrier_counts
    # A burst step reports its sub-dispatches as chained rows: with 2 rows
    # and decode_steps=4 some step must chain more rows than the batch has.
    assert max_chained > 2
    assert core.allocator.stats().active_pages == 0


def test_multistep_chained_burst_deep_parity():
    """decode_steps sweep: the chained burst path must replay the
    synchronous oracle token-for-token at several depths, including depths
    that overshoot the rows' budgets (the clamp keeps every sub-step real)."""
    vocab = PRESETS["test-tiny"].vocab_size
    reqs = lambda: [  # noqa: E731
        PreprocessedRequest(
            token_ids=[i % (vocab - 2) + 1 for i in range(7)],
            sampling=SamplingOptions(temperature=0.8, seed=3),
            stop=StopConditions(max_tokens=17, ignore_eos=True),
        ),
        PreprocessedRequest(
            token_ids=[2, 4, 6],
            sampling=SamplingOptions(temperature=0.0),
            stop=StopConditions(max_tokens=5, ignore_eos=True),  # clamps k
        ),
        PreprocessedRequest(
            token_ids=[9, 9, 1, 1],
            sampling=SamplingOptions(temperature=0.5, seed=11),
            stop=StopConditions(max_tokens=13, ignore_eos=True),
        ),
    ]
    base_tok, _ = run_all(make_core(), reqs())
    for k in (2, 8):
        over_tok, _ = run_all(make_core(overlap=True, decode_steps=k), reqs())
        assert over_tok == base_tok, f"decode_steps={k} diverged"


def test_the_synchronous_oracle_emits_one_token_a_step_whatever_decode_steps_says():
    """``decode_steps`` counts the pipeline's chained sub-dispatches and
    nothing else: with ``overlap=False`` every output carries one token and
    the streams are ``decode_steps=1``'s, while the pipeline hands back four
    a call."""
    reqs = lambda: [  # noqa: E731
        PreprocessedRequest(
            token_ids=[5, 7, 5, 7, 9, 11],
            sampling=SamplingOptions(temperature=0.0),
            stop=StopConditions(max_tokens=12, ignore_eos=True),
        ),
        PreprocessedRequest(
            token_ids=[3, 3, 3, 3, 2, 1],
            sampling=SamplingOptions(temperature=0.7, seed=7, frequency_penalty=0.4),
            stop=StopConditions(max_tokens=9, ignore_eos=True),
        ),
    ]
    base_tok, _ = run_all(make_core(decode_steps=1), reqs())

    def sizes(core):
        tokens = {core.add_request(r).seq_id: [] for r in reqs()}
        per_output = []
        while core.has_work:
            for seq, out in core.step():
                tokens[seq.seq_id].extend(out.token_ids)
                per_output.append(len(out.token_ids))
        return tokens, per_output

    sync_tok, per_output = sizes(make_core(decode_steps=4))
    assert sync_tok == base_tok and set(per_output) == {1}
    assert not hasattr(make_core().runner, "multi_step")  # no program fuses a burst
    # The pipeline's burst is the knob's one meaning (rows with a penalty take one token a step).
    greedy = reqs()[:1]
    core = make_core(overlap=True, decode_steps=4)
    seq = core.add_request(greedy[0])
    per_call = []
    while core.has_work:
        per_call.append(sum(len(out.token_ids) for s, out in core.step() if s is seq))
    assert max(per_call) == 4 and sum(per_call) == 12


# -- chained constrained (JSON-mode) decode ----------------------------------


def _json_core(*, overlap, chunk=16, **cfg_kw):
    from dynamo_tpu.tokenizer import ByteTokenizer

    core = make_core(overlap=overlap, chunk=chunk, **cfg_kw)
    core.set_constraint_tokenizer(ByteTokenizer())
    return core


def _json_reqs(max_tokens=24):
    from dynamo_tpu.tokenizer import ByteTokenizer

    prompt = ByteTokenizer().encode("data: ", add_bos=False)
    return [
        PreprocessedRequest(
            token_ids=prompt,
            sampling=SamplingOptions(temperature=0.8, seed=1, json_mode=True),
            stop=StopConditions(max_tokens=max_tokens),
        ),
        # Plain greedy row sharing every batch with the constrained rows.
        PreprocessedRequest(
            token_ids=[5, 7, 9, 11],
            sampling=SamplingOptions(temperature=0.0),
            stop=StopConditions(max_tokens=10, ignore_eos=True),
        ),
        PreprocessedRequest(
            token_ids=prompt + prompt,
            sampling=SamplingOptions(
                temperature=0.7, seed=9, json_mode=True, logprobs=2
            ),
            stop=StopConditions(max_tokens=max_tokens),
        ),
    ]


@pytest.mark.parametrize("chunk", [16, 0])
def test_constrained_chained_decode_bit_identical(chunk):
    """JSON-mode rows ride the chained pipeline (lookahead mask groups
    resolve in-graph against the chained token) bit-identically — tokens
    AND logprobs — vs the sync masked loop, chunked and legacy prefill."""
    base_tok, base_lp = run_all(_json_core(overlap=False, chunk=chunk), _json_reqs())
    core = _json_core(overlap=True, chunk=chunk)
    over_tok, over_lp = run_all(core, _json_reqs())
    assert over_tok == base_tok
    assert over_lp == base_lp
    assert core.overlap_step_counts["overlapped"] > 0
    # With the lookahead enabled "constraint" never fires; residual cold
    # summaries surface as (self-curing) constraint_miss barriers instead.
    assert "constraint" not in core.overlap_barrier_counts
    assert core.constraint_mask_cache_hits > 0
    assert core.allocator.stats().active_pages == 0


def test_constrained_chained_forced_close_near_budget():
    """Tight max_tokens: budget_to_close force-closing must kick in at the
    same steps under overlap (the plan's successor masks are built at the
    row's post-emit remaining), keeping streams identical to the end."""
    for mt in (6, 9, 12):
        base_tok, base_lp = run_all(_json_core(overlap=False), _json_reqs(mt))
        core = _json_core(overlap=True)
        over_tok, over_lp = run_all(core, _json_reqs(mt))
        assert over_tok == base_tok, f"max_tokens={mt} diverged"
        assert over_lp == base_lp, f"max_tokens={mt} logprobs diverged"


def test_constraint_lookahead_disabled_barriers_every_step():
    """DYN_CONSTRAINT_LOOKAHEAD_TOKENS=0: constrained rows barrier with
    reason 'constraint' (the bench baseline) — still bit-identical."""
    base_tok, base_lp = run_all(_json_core(overlap=False), _json_reqs())
    core = _json_core(overlap=True, constraint_lookahead_tokens=0)
    over_tok, over_lp = run_all(core, _json_reqs())
    assert over_tok == base_tok
    assert over_lp == base_lp
    assert core.overlap_barrier_counts.get("constraint", 0) > 0
    assert "constraint_miss" not in core.overlap_barrier_counts


def test_overlap_off_never_touches_async_path(monkeypatch):
    """The oracle (``overlap=False``) is the synchronous step structurally:
    step_async is never called."""
    core = make_core(overlap=False)

    def boom(*a, **k):
        raise AssertionError("step_async called with overlap off")

    monkeypatch.setattr(core.runner, "step_async", boom)
    toks, _ = run_all(core, [PreprocessedRequest(
        token_ids=[5, 7, 5, 7, 9, 11],
        sampling=SamplingOptions(temperature=0.0),
        stop=StopConditions(max_tokens=6, ignore_eos=True),
    )])
    assert len(toks[next(iter(toks))]) == 6


# -- mock runner parity (the bench probe's engine) ---------------------------


def test_mock_runner_overlap_parity():
    from dynamo_tpu.mocker import MockRunner

    def run(overlap):
        runner = MockRunner(num_pages=128, page_size=16, realtime=False, d2h_us=500.0)
        core = EngineCore(runner, EngineConfig(
            num_pages=128, page_size=16, max_batch_size=8, max_seq_len=512,
            chunk_prefill_tokens=64, overlap=overlap, enable_prefix_caching=False,
        ))
        reqs = [
            PreprocessedRequest(
                token_ids=list(range(1, 33)),
                sampling=SamplingOptions(temperature=0.0),
                stop=StopConditions(max_tokens=24, ignore_eos=True),
            )
            for _ in range(3)
        ]
        tokens, _ = run_all(core, reqs)
        return tokens, core

    base, _ = run(False)
    over, core = run(True)
    assert over == base
    assert core.overlap_step_counts["overlapped"] > 0
    assert core.allocator.stats().active_pages == 0


# -- offload batching (satellite) --------------------------------------------


def test_offload_batch_prefers_async_gather():
    """KvBlockManager.offload_batch routes through read_pages_async when
    provided: one dispatched gather per batch, waited only at the tier puts."""
    from dynamo_tpu.blocks.manager import BlockManagerConfig, KvBlockManager

    reads = {"async_batches": [], "sync_batches": [], "per_page": 0}

    class Handle:
        def __init__(self, pages):
            self._pages = pages

        def wait(self):
            return [(np.zeros((1, 4, 8), np.float32),) * 2 for _ in self._pages]

    def read_pages_async(pages):
        reads["async_batches"].append(list(pages))
        return Handle(pages)

    def read_pages(pages):
        reads["sync_batches"].append(list(pages))
        return Handle(pages).wait()

    def read_page(pid):
        reads["per_page"] += 1
        return np.zeros((1, 4, 8), np.float32), np.zeros((1, 4, 8), np.float32)

    mgr = KvBlockManager(
        BlockManagerConfig(g2_capacity_blocks=16, null_storage=True),
        read_page=read_page, write_page=lambda *a: None,
    )
    mgr.offload_batch(
        [(100, 1), (101, 2), (102, 3), (100, 1)],  # one dup
        read_pages=read_pages, read_pages_async=read_pages_async,
    )
    assert reads["async_batches"] == [[1, 2, 3]]  # one batched gather, deduped
    assert reads["sync_batches"] == [] and reads["per_page"] == 0
    assert mgr.offloaded == 3


def test_core_flush_offloads_uses_runner_async_gather(monkeypatch):
    """The engine's flush routes deferred offloads through the runner's
    batched async gather — one dispatch per flush, not one per page."""
    core = make_core()
    calls = []
    orig = core.runner.read_pages_async

    def spy(pages):
        calls.append(list(pages))
        return orig(pages)

    monkeypatch.setattr(core.runner, "read_pages_async", spy)
    from dynamo_tpu.blocks.manager import BlockManagerConfig, KvBlockManager

    core.block_manager = KvBlockManager(
        BlockManagerConfig(g2_capacity_blocks=64, null_storage=True),
        read_page=core.runner.read_page, write_page=core.runner.write_page,
    )
    run_all(core, [PreprocessedRequest(
        token_ids=list(range(1, 18)),
        sampling=SamplingOptions(temperature=0.0),
        stop=StopConditions(max_tokens=8, ignore_eos=True),
    )])
    assert calls, "flush_offloads never used the async gather"
    assert core.block_manager.offloaded == sum(len(c) for c in calls)


# -- launch / config resolution ----------------------------------------------


def test_launch_serves_the_pipelined_loop_whatever_the_environment(monkeypatch):
    """The pipelined loop is the serving loop: no name of the cascade arms or
    disarms it, or says whether a verify rides it."""
    from dynamo_tpu.launch import WorkerSpec
    from dynamo_tpu.model_card import ModelDeploymentCard

    card = ModelDeploymentCard(
        name="test-tiny", context_length=256, kv_page_size=PAGE, eos_token_ids=[2],
    )
    monkeypatch.delenv("DYN_OVERLAP", raising=False)
    monkeypatch.delenv("DYN_WORKER_OVERLAP", raising=False)
    assert EngineConfig().overlap is True
    assert WorkerSpec._engine_cfg(card, {}).overlap is True
    monkeypatch.setenv("DYN_OVERLAP", "0")
    monkeypatch.setenv("DYN_WORKER_OVERLAP", "false")
    assert WorkerSpec._engine_cfg(card, {}).overlap is True  # read by nothing
    assert not hasattr(WorkerSpec._engine_cfg(card, {}), "overlap_spec")  # gone with its two names


def test_launch_resolves_constraint_lookahead(monkeypatch):
    from dynamo_tpu.launch import WorkerSpec
    from dynamo_tpu.model_card import ModelDeploymentCard

    card = ModelDeploymentCard(
        name="test-tiny", context_length=256, kv_page_size=PAGE, eos_token_ids=[2],
    )
    monkeypatch.delenv("DYN_CONSTRAINT_LOOKAHEAD_TOKENS", raising=False)
    assert WorkerSpec._engine_cfg(card, {}).constraint_lookahead_tokens == 32
    monkeypatch.setenv("DYN_CONSTRAINT_LOOKAHEAD_TOKENS", "0")
    assert WorkerSpec._engine_cfg(card, {}).constraint_lookahead_tokens == 0
    monkeypatch.setenv("DYN_CONSTRAINT_LOOKAHEAD_TOKENS", "64")
    assert WorkerSpec._engine_cfg(card, {}).constraint_lookahead_tokens == 64


def test_worker_settings_overlap_field(monkeypatch):
    from dynamo_tpu.config import load_worker_settings
    from dynamo_tpu.launch import parse_args

    assert not hasattr(load_worker_settings(env={"DYN_WORKER_OVERLAP": "1"}), "overlap")
    with pytest.raises(SystemExit):
        parse_args(["--role", "local", "--overlap"])  # the flag is gone with the knob
    assert not hasattr(load_worker_settings(env={}), "overlap_spec")
