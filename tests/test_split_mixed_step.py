"""A chunk step on one token axis (ISSUE 27): a decode row rides a chunk step
as one token position, not padded to the chunk. The split layout against the
rows x t rectangle for the same batch (dense GQA, an OLMoE-like MoE, a window +
full model), from both of the runner's entry points (ISSUE 29: the synchronous
``step`` and the pipelined loop's ``step_async`` call one program per layout),
the warm-up property (a null batch and a served step of the same buckets are
one program, chained or not), who keeps the rectangle, the row order of what
comes back, and the STEP record's ``step_tokens`` / ``layout``."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.core import EngineConfig, EngineCore
from dynamo_tpu.engine.runner import MAX_CHUNK_SLOTS, ROWS_X_T, SPLIT, ModelRunner, StepBatch, next_pow2
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import PRESETS

from benchmark import serving
from tests.test_engine_core import greedy_reference, greedy_request, run_to_completion
from tests.test_mixed_attention import _toy

PAGE = 4
MODELS = {
    "dense-gqa": PRESETS["test-tiny"],
    # OLMoE in small: every layer routed, top-2 of 4 without renormalisation, q/k normed flat.
    "olmoe-like": dataclasses.replace(PRESETS["test-tiny-moe"], name="olmoe-like", qk_norm="flat",
                                      moe_norm_topk=False, tie_embeddings=False),
    "window-and-full": _toy(),  # sliding x 3 + full, window 8, a RoPE each (tests/test_mixed_attention.py)
    # Latent attention (ISSUE 34): the decode slots and the chunk slots through models/mla._attend_paged.
    "mla": PRESETS["test-tiny-mla"],
    # Two MLA sublayers and two dense FFNs a layer, a share of the experts and identity experts (LongCat-Flash's layer).
    "shortcut-moe-mla": PRESETS["test-tiny-scmoe"],
}
#: Rows as (first position, new tokens): contexts on both sides of the toy's window of 8.
BATCHES = {
    "two-decodes-one-chunk": [(5, 1), (21, 1), (12, 7)],
    "chunk-between-decodes": [(9, 1), (3, 6), (30, 1), (0, 8), (17, 1)],
    "one-chunk-alone": [(10, 8)],
    "one-token-last-chunk": [(14, 1), (6, 1), (8, 5)],  # a prompt's 1-token tail rides as a decode slot
}


def runner_for(cfg, *, split: bool = True, attn_impl: str = "reference", **kw) -> ModelRunner:
    params = llama.init_params(cfg, 0)
    runner = ModelRunner(cfg, params, num_pages=96, page_size=PAGE, max_batch_size=8, prefill_bucket=16,
                         attn_impl=attn_impl, **kw)
    if not split:
        runner._can_split = False  # the rectangle for the same batch: what every step took before
    # A cache full of noise, the same in both runners: a row's context matters.
    shape, dt = runner.k_cache.shape, runner.k_cache.dtype
    runner.k_cache = jax.random.normal(jax.random.PRNGKey(1), shape, jnp.float32).astype(dt)
    runner.v_cache = jax.random.normal(jax.random.PRNGKey(2), shape, jnp.float32).astype(dt)
    return runner


def step_batch(rows, *, pages_per_row: int = 10, seed: int = 0, temperature: float = 0.0) -> StepBatch:
    b, t = len(rows), max(n for _, n in rows)
    rng = np.random.default_rng(seed)
    tokens, positions, slots = (np.zeros((b, t), np.int32) for _ in range(3))
    tables = 1 + np.arange(b * pages_per_row, dtype=np.int32).reshape(b, pages_per_row)
    for i, (start, n) in enumerate(rows):
        pos = np.arange(start, start + n)
        tokens[i, :n] = rng.integers(1, 200, n)
        positions[i, :n] = pos
        slots[i, :n] = tables[i][pos // PAGE] * PAGE + pos % PAGE
    f32 = lambda v: np.full(b, v, np.float32)  # noqa: E731
    return StepBatch(
        tokens=tokens, positions=positions, block_tables=tables, slot_mapping=slots,
        last_token_index=np.asarray([n - 1 for _, n in rows], np.int32), temperature=f32(temperature),
        top_k=np.zeros(b, np.int32), top_p=f32(1.0), seeds=np.arange(b, dtype=np.uint32) + 7,
        sample_steps=np.arange(b, dtype=np.int32), freq_pen=f32(0.0), pres_pen=f32(0.0),
        pos_limit=np.full(b, 1 << 20, np.int32), history=np.full((b, 1), -1, np.int32),
        num_new=np.asarray([n for _, n in rows], np.int32),
        # A model with a page pool per layer kind: a hand-built runner's pools are equal, so one table names both.
        window_block_tables=tables, window_slot_mapping=slots)


# -- the same step, both layouts, both entry points --------------------------------


def _sync(runner, batch, **kw):
    return runner.step(batch, **kw)


def _async(runner, batch, lp_k=0):
    """``step_async`` as the pipelined loop calls it, handed back in ``step``'s form."""
    toks, lp = runner.step_async(batch, lp_k=lp_k).result()
    return (toks[:, 0], lp) if lp_k else toks[:, 0]


LOOPS = {"step": _sync, "step_async": _async}


@pytest.mark.parametrize("loop", LOOPS.keys())
@pytest.mark.parametrize("rows", BATCHES.values(), ids=BATCHES.keys())
@pytest.mark.parametrize("model", MODELS.keys())
def test_split_layout_computes_what_the_rectangle_computes(model, rows, loop):
    """Sampled tokens, logprobs (the tolerance of tests/test_chunked_prefill.py)
    and every live page of the cache, split against rectangle: a decode row
    beside a chunk, two chunk slots, a lone chunk that keeps the rectangle."""
    cfg = MODELS[model]
    a, b = runner_for(cfg), runner_for(cfg, split=False)
    toks_a, lp_a = LOOPS[loop](a, step_batch(rows), lp_k=3)
    toks_b, lp_b = b.step(step_batch(rows), lp_k=3)
    assert a.last_step_layout[0] == (SPLIT if len(rows) > 1 else ROWS_X_T) and b.last_step_layout[0] == ROWS_X_T
    assert toks_a.shape == (len(rows),) and toks_a.tolist() == toks_b.tolist()
    for key in ("logprob", "top_lps"):
        np.testing.assert_allclose(lp_a[key], lp_b[key], rtol=1e-4, atol=1e-5)
    assert lp_a["top_ids"].tolist() == lp_b["top_ids"].tolist()
    for ca, cb in ((a.k_cache, b.k_cache), (a.v_cache, b.v_cache)):  # page 0 is the null page: padding lands there
        by_layer = lambda c: np.asarray(c).reshape(cfg.cache_layers, -1, *c.shape[2:])  # noqa: E731  (a mixed model's cache lies flat)
        np.testing.assert_allclose(by_layer(ca)[:, 1:], by_layer(cb)[:, 1:], rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("loop", LOOPS.keys())
@pytest.mark.parametrize("model", MODELS.keys())
def test_seeded_sampling_folds_row_by_row_as_in_the_rectangle(model, loop):
    rows = BATCHES["chunk-between-decodes"]
    a, b = runner_for(MODELS[model]), runner_for(MODELS[model], split=False)
    assert (LOOPS[loop](a, step_batch(rows, temperature=0.9)).tolist()
            == b.step(step_batch(rows, temperature=0.9)).tolist())


@pytest.mark.parametrize("rows", [BATCHES["two-decodes-one-chunk"], BATCHES["chunk-between-decodes"]],
                         ids=["one-chunk-slot", "two-chunk-slots"])
def test_a_split_step_chains_its_decode_rows_and_hands_on_a_chunk_rows_sample(rows):
    """Two dispatches of the pipelined loop on the split layout against the
    same two stepped synchronously with the host's tokens: the first leaves
    every row's sample in the chain buffer at its row index (a chunk row's
    too, which the program emits past the decode slots), the second gathers
    each decode row's input token from there, its host token a placeholder."""
    cfg = MODELS["dense-gqa"]
    a, b = runner_for(cfg), runner_for(cfg)
    first = step_batch(rows, temperature=0.9)
    toks = b.step(first)
    assert a.step_async(step_batch(rows, temperature=0.9)).result()[0][:, 0].tolist() == toks.tolist()
    assert np.asarray(a._chain_tokens).shape == (a._chain_width,)
    assert np.asarray(a._chain_tokens)[: len(rows)].tolist() == toks.tolist()
    # The step after: every row decodes on (a chunk row's prompt ended), beside one new chunk row.
    nxt = [(start + n, 1) for start, n in rows] + [(0, 6)]
    host = step_batch(nxt, seed=1, temperature=0.9)
    host.tokens[: len(rows), 0] = toks
    chained = step_batch(nxt, seed=1, temperature=0.9)
    chained.tokens[: len(rows), 0] = 0
    src = np.asarray(list(range(len(rows))) + [-1], np.int32)
    got = a.step_async(chained, chain=True, chain_src=src).result()[0][:, 0]
    assert a.last_step_layout[0] == SPLIT
    assert got.tolist() == b.step(host).tolist()
    for ca, cb in ((a.k_cache, b.k_cache), (a.v_cache, b.v_cache)):
        np.testing.assert_array_equal(np.asarray(ca)[:, 1:], np.asarray(cb)[:, 1:])


@pytest.mark.parametrize("before", ["text", "logit_mask", "lookahead"])
def test_a_text_step_chains_out_of_every_kind_of_dispatch_before_it(before):
    """Each kind of async dispatch that can precede a chained text step on one
    device (a text step; an explicit-argument step with a host-built
    ``logit_mask``, which chains nothing; one with lookahead mask groups, which
    chains) leaves its samples in the chain buffer at the one width, and the
    text step after it samples what the synchronous ``step`` samples when the
    host feeds the same tokens."""
    cfg = MODELS["dense-gqa"]
    a, b = runner_for(cfg), runner_for(cfg)
    rows = [(5, 1), (21, 1), (12, 1)]  # a rows bucket of 4 under a chain buffer of 8
    odd = np.zeros((len(rows), cfg.vocab_size), bool)
    odd[:, 1::2] = True
    src = np.arange(len(rows), dtype=np.int32)

    def batches(rows, toks=None, **kw):
        """The batch twice: for the pipelined runner (a chained row's token a placeholder), for the oracle."""
        mine, host = (step_batch(rows, temperature=0.9, **kw) for _ in range(2))
        if toks is not None:
            mine.tokens[:, 0], host.tokens[:, 0] = 0, toks
        return mine, host

    mine, host = batches(rows)
    if before == "logit_mask":
        mine.logit_mask = host.logit_mask = odd
    a.step_async(mine)
    toks = b.step(host)
    if before == "logit_mask":
        assert (toks % 2 == 1).all()
    if before == "lookahead":  # chained itself: a row whose gathered token is even samples under group 1, odd tokens only
        rows = [(start + 1, 1) for start, _ in rows]
        mine, host = batches(rows, toks, seed=1)
        mine.la_masks = np.stack([np.ones_like(odd), odd], axis=1)
        mine.la_groups = np.broadcast_to((np.arange(cfg.vocab_size) % 2 == 0).astype(np.int32), odd.shape).copy()
        host.logit_mask = np.where((toks % 2 == 0)[:, None], odd, True)
        a.step_async(mine, chain=True, chain_src=src)
        toks = b.step(host)
        assert (toks[np.flatnonzero(host.tokens[:, 0] % 2 == 0)] % 2 == 1).all()
    assert np.asarray(a._chain_tokens).shape == (a._chain_width,)
    assert np.asarray(a._chain_tokens)[: len(rows)].tolist() == toks.tolist()
    mine, host = batches([(start + 1, 1) for start, _ in rows], toks, seed=2)
    got = a.step_async(mine, chain=True, chain_src=src).result()[0][:, 0]
    assert a.last_step_layout == (ROWS_X_T, 4)
    assert got.tolist() == b.step(host).tolist()
    for ca, cb in ((a.k_cache, b.k_cache), (a.v_cache, b.v_cache)):
        np.testing.assert_array_equal(np.asarray(ca)[:, 1:], np.asarray(cb)[:, 1:])
    # One decode program whatever came before: the chain buffer's shape is no part of its key.
    assert a._step_packed_fn._cache_size() == 1


def test_tokens_come_back_in_the_batchs_row_order():
    """Row i of the result is row i of the batch wherever its slot sits: each
    row alone (a batch of one, no slot to confuse) samples the same token."""
    cfg, rows = MODELS["dense-gqa"], BATCHES["chunk-between-decodes"]
    full = step_batch(rows)
    together = runner_for(cfg).step(full)
    for i in range(len(rows)):
        one = StepBatch(**{f.name: (None if getattr(full, f.name) is None else getattr(full, f.name)[i: i + 1])
                           for f in dataclasses.fields(StepBatch)})
        n = rows[i][1]
        one.tokens, one.positions, one.slot_mapping = one.tokens[:, :n], one.positions[:, :n], one.slot_mapping[:, :n]
        assert runner_for(cfg).step(one)[0] == together[i], i


# -- the chunked kernel on one-query rows -------------------------------------------


@pytest.mark.parametrize("n_heads, n_kv, window", [(4, 4, None), (8, 2, None), (8, 2, 24)],
                         ids=["one-head-a-kv-head", "gqa-group-of-4", "gqa-windowed"])
def test_chunked_kernel_serves_one_query_rows(n_heads, n_kv, window):
    """What a decode slot of a split step asks of ``paged_prefill_attention``:
    T = 1, ``start = kv_len - 1``, the query block the whole one-token array
    (interpret mode; tests/test_chip_compile.py has Mosaic take the same call)."""
    from dynamo_tpu.ops.attention import paged_attention_reference
    from dynamo_tpu.ops.pallas_prefill import paged_prefill_attention

    from tests.test_pallas_prefill import _case

    q, k, v, tables, positions = _case(
        np.random.default_rng(0), b=5, t=1, n_heads=n_heads, n_kv=n_kv, head_dim=64, page_size=16,
        pages_per_seq=8, starts=[0, 17, 63, 100, 127])
    want = paged_attention_reference(q, k, v, tables, positions, scale=0.125, sliding_window=window or 0)
    got = paged_prefill_attention(q, k, v, tables, positions, scale=0.125, interpret=True,
                                  window=None if window is None else jnp.int32(window))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-2, atol=2e-2)


def test_split_step_on_the_kernels_in_interpret_mode(monkeypatch):
    """The whole split step with ``attn_impl="pallas"``: both attention calls
    of a layer go to the chunked kernel, none to the decode kernel."""
    from dynamo_tpu.ops import pallas_paged, pallas_prefill

    monkeypatch.setenv("DYNAMO_PALLAS_INTERPRET", "1")
    calls = {"prefill": [], "decode": 0}
    prefill, decode = pallas_prefill.paged_prefill_attention, pallas_paged.paged_decode_attention
    monkeypatch.setattr(pallas_prefill, "paged_prefill_attention",
                        lambda q, *a, **kw: calls["prefill"].append(q.shape[:2]) or prefill(q, *a, **kw))
    monkeypatch.setattr(pallas_paged, "paged_decode_attention",
                        lambda *a, **kw: calls.__setitem__("decode", calls["decode"] + 1) or decode(*a, **kw))
    cfg = dataclasses.replace(PRESETS["test-kernel"], num_layers=1)  # widths the kernels' predicate takes
    rows = BATCHES["two-decodes-one-chunk"]
    a = runner_for(cfg, attn_impl="pallas")
    b = runner_for(cfg, split=False)
    toks_a, lp_a = a.step(step_batch(rows), lp_k=3)
    toks_b, lp_b = b.step(step_batch(rows), lp_k=3)
    assert a.last_attn_dispatch == ("prefill", "pallas") and a.last_step_layout == (SPLIT, 4 + 8)
    assert calls == {"prefill": [(4, 1), (1, 8)], "decode": 0}  # one layer body, traced once
    assert toks_a.tolist() == toks_b.tolist()
    np.testing.assert_allclose(lp_a["logprob"], lp_b["logprob"], rtol=2e-3, atol=2e-3)


# -- the warm-up property ---------------------------------------------------------


@pytest.mark.parametrize("loop", LOOPS.keys())
@pytest.mark.parametrize("rows, null", [
    (BATCHES["two-decodes-one-chunk"], (4, 8, 16)),  # 3 rows -> 4, 7 tokens -> 8, 10 pages -> 16
    (BATCHES["one-token-last-chunk"], (4, 8, 16)),
    # A lone chunk row: the split would add a padding decode slot to its 8 tokens, so null batch and step keep [1, 8].
    (BATCHES["one-chunk-alone"], (1, 8, 16)),
], ids=["mixed", "mixed-with-a-1-token-tail", "pure-prefill"])
def test_a_null_batch_warms_the_program_a_served_step_runs(rows, null, loop):
    runner = runner_for(MODELS["dense-gqa"])
    runner.step(serving.null_batch(*null))  # what benchmark/serving.warm_up steps a program with: every row padding
    (warm,) = runner.compile_tracker.events()
    layout = (SPLIT, null[0] + null[1]) if null[0] > 1 else (ROWS_X_T, null[1])
    assert warm["bucket"][:3] == list(null) and (warm["bucket"][-2:] == [SPLIT, 1]) == (null[0] > 1)
    assert runner.last_step_layout == layout
    LOOPS[loop](runner, step_batch(rows))
    assert runner.compile_tracker.events() == [warm]  # no new key: the served step ran the warmed program
    assert runner._step_split_fn._cache_size() + runner._step_packed_fn._cache_size() == 1
    assert runner.last_step_layout == layout


def _jit_programs(runner) -> int:
    return runner._step_split_fn._cache_size() + runner._step_packed_fn._cache_size()


def test_an_overlapped_run_compiles_nothing_a_null_batch_warm_up_has_not():
    """After ``runner.step(null_batch(b, t, n))`` over a small lattice, as the
    benchmark warms a cell, the pipelined loop serves requests that cross a
    rows bucket (3 -> 5 rows) and mix chunk and decode steps, chained wherever
    it can, and neither traces a program nor records a dispatch key the
    warm-up has not: the chain buffer has one width whatever the bucket of the
    dispatch before, so it is no part of a program's key."""
    chunk = 16
    core = _core(MODELS["dense-gqa"], chunk=chunk)
    runner = core.runner
    lattice = [(b, t, n) for t in (1, chunk) for b in (1, 2, 4, 8) for n in (1, 2, 4, 8, 16)]
    for shape in lattice:
        runner.step(serving.null_batch(*shape))
    warm, programs = list(runner.compile_tracker.events()), _jit_programs(runner)
    assert len(warm) == len(lattice) == programs
    assert {e["program"] for e in warm} == {"step"}
    outputs: dict = {}
    for i in range(3):  # prompts in whole chunks, as the cells' are: the time axis is 1 or the chunk
        core.add_request(greedy_request(list(range(1 + i, 1 + i + chunk)), max_tokens=24))
    for _ in range(6):
        for seq, out in core.step():
            outputs.setdefault(seq.seq_id, []).extend(out.token_ids)
    for i in range(2):  # two more beside the three decoding: rows 3 -> 5, and their prompts in two chunks
        core.add_request(greedy_request(list(range(7 + i, 7 + i + 2 * chunk)), max_tokens=8))
    outputs = run_to_completion(core, outputs=outputs)
    assert [len(outputs[i]) for i in range(5)] == [24, 24, 24, 8, 8]
    steps = [s for s in core.flight.snapshot(kind="step") if s["attn_phase"]]
    assert max(s["decode_rows"] for s in steps) == 5
    assert any(s["step_kind"] == "mixed" and s["layout"] == SPLIT and s["chained_rows"] for s in steps)
    assert sum(s["overlap_mode"] == "overlapped" for s in steps) > len(steps) // 2
    assert runner.compile_tracker.events() == warm and _jit_programs(runner) == programs


@pytest.mark.parametrize("rows", [[(5, 1), (9, 1), (4, 1)], BATCHES["two-decodes-one-chunk"],
                                  BATCHES["chunk-between-decodes"], BATCHES["one-chunk-alone"]],
                         ids=["decode", "one-chunk-slot", "two-chunk-slots", "lone-chunk"])
def test_step_and_step_async_record_one_dispatch_key_for_a_shape(rows):
    a, b = runner_for(MODELS["dense-gqa"]), runner_for(MODELS["dense-gqa"])
    a.step(step_batch(rows))
    b.step_async(step_batch(rows)).result()
    (ea,), (eb,) = a.compile_tracker.events(), b.compile_tracker.events()
    assert (ea["program"], ea["bucket"]) == (eb["program"], eb["bucket"]) and ea["program"] == "step"
    assert a.last_step_layout == b.last_step_layout


def test_decode_steps_keep_their_program_and_key():
    runner = runner_for(MODELS["dense-gqa"])
    runner.step(step_batch([(5, 1), (9, 1), (4, 1)]))
    (event,) = runner.compile_tracker.events()
    assert event["bucket"] == [4, 1, 16, 1, 0, "reference", False, False, False]
    assert runner.last_step_layout == (ROWS_X_T, 4) and runner._step_split_fn._cache_size() == 0


def test_two_chunk_rows_take_two_chunk_slots():
    runner = runner_for(MODELS["dense-gqa"])
    runner.step(step_batch(BATCHES["chunk-between-decodes"]))  # 5 rows -> 8, two chunk rows, t 8
    assert runner.compile_tracker.events()[0]["bucket"][-2:] == [SPLIT, 2]
    assert runner.last_step_layout == (SPLIT, 8 + 2 * 8)


# -- who keeps the rectangle --------------------------------------------------------


def _masked(batch: StepBatch) -> StepBatch:
    batch.logit_mask = np.ones((batch.batch_size, 256), bool)
    return batch


def _mesh_runner():
    from dynamo_tpu.parallel.mesh import MeshPlan, make_mesh

    return runner_for(MODELS["dense-gqa"], mesh=make_mesh(MeshPlan(dp=4, tp=2), jax.devices()))


OUTSIDE = {
    "logit-mask": (lambda: runner_for(MODELS["dense-gqa"]),
                   lambda r: r.step(_masked(step_batch(BATCHES["two-decodes-one-chunk"]))), 4 * 8),
    "spec-verify": (lambda: runner_for(MODELS["dense-gqa"]),
                    lambda r: r.spec_step(step_batch(BATCHES["two-decodes-one-chunk"]), 2), 4 * 8),
    # A chunk row whose first token is chained (no engine composes one): only the rectangle gathers into it.
    "async-chained-chunk-row": (lambda: runner_for(MODELS["dense-gqa"]), lambda r: (
        r.step_async(step_batch([(5, 1), (21, 1), (12, 1)])).result(),
        r.step_async(step_batch(BATCHES["two-decodes-one-chunk"]), chain=True).result()), 4 * 8),
    "mesh": (_mesh_runner, lambda r: r.step(step_batch(BATCHES["two-decodes-one-chunk"])), 4 * 8),
    # A runner handed a forward of its own: only llama.forward is known to take the split token axis.
    "own-forward": (lambda: runner_for(MODELS["dense-gqa"], forward_fn=llama.forward),
                    lambda r: r.step(step_batch(BATCHES["two-decodes-one-chunk"])), 4 * 8),
    "more-chunk-rows-than-slots": (lambda: runner_for(MODELS["dense-gqa"]),
                                   lambda r: r.step(step_batch([(3, 1)] + [(2, 4)] * (MAX_CHUNK_SLOTS + 1))),
                                   next_pow2(MAX_CHUNK_SLOTS + 2) * 4),
    # two prompts' chunks and nothing decoding: 2 + 2 x 8 positions would be more than 2 x 8
    "nothing-to-save": (lambda: runner_for(MODELS["dense-gqa"]), lambda r: r.step(step_batch([(0, 8), (4, 6)])), 2 * 8),
}


@pytest.mark.parametrize("case", OUTSIDE.keys())
def test_steps_outside_the_class_keep_the_rectangle(case):
    make, dispatch, tokens = OUTSIDE[case]
    runner = make()
    dispatch(runner)
    assert runner.last_step_layout == (ROWS_X_T, tokens)
    assert runner._step_split_fn._cache_size() == 0
    assert all(SPLIT not in e["bucket"] for e in runner.compile_tracker.events())


# -- through the engine: the STEP record and the counters ----------------------------


def _core(cfg, chunk: int = 4, **engine) -> EngineCore:
    config = EngineConfig(num_pages=64, page_size=PAGE, max_batch_size=8, max_prefill_tokens=chunk,
                          max_seq_len=128, chunk_prefill_tokens=chunk, **engine)
    runner = ModelRunner(cfg, llama.init_params(cfg, 0), num_pages=64, page_size=PAGE, max_batch_size=8,
                         prefill_bucket=16, attn_impl="reference")
    return EngineCore(runner, config)


def _serve_two(core: EngineCore) -> dict:
    p1, p2 = [1, 2, 3, 4, 5], list(range(7, 7 + 17))
    core.add_request(greedy_request(p1, max_tokens=12))
    outputs: dict = {}
    for _ in range(4):
        for seq, out in core.step():
            outputs.setdefault(seq.seq_id, []).extend(out.token_ids)
    core.add_request(greedy_request(p2, max_tokens=5))  # 5 chunks of 4 beside the decoding row
    return run_to_completion(core, outputs=outputs)


@pytest.mark.parametrize("overlap", [True, False], ids=["pipelined", "synchronous"])
def test_step_records_say_layout_and_tokens_and_the_engine_counts_them(overlap):
    core = _core(MODELS["dense-gqa"], overlap=overlap)
    outputs = _serve_two(core)
    assert outputs[0] == greedy_reference([1, 2, 3, 4, 5], 12)
    assert outputs[1] == greedy_reference(list(range(7, 24)), 5)
    steps = [s for s in core.flight.snapshot(kind="step") if s["attn_phase"]]
    chunky = [s for s in steps if s["chunk_rows"]]
    mixed = [s for s in chunky if s["decode_rows"]]
    assert mixed and all(s["step_kind"] == "mixed" for s in mixed)
    # A prompt's 1-token tail: the synchronous step seats it as a decode row; the
    # pipelined loop, which schedules it while the chunk before it is in flight,
    # as a chunk row of one token. A decode program (T = 1) either way.
    tails = [s for s in chunky if s["chunk_tokens"] == 1]
    assert len(tails) == (2 if overlap else 0)
    assert all((s["layout"], s["step_tokens"]) == (ROWS_X_T, s["decode_rows"] + 1) for s in tails)
    mixed = [s for s in mixed if s not in tails]
    assert mixed and all(s["layout"] == SPLIT for s in mixed)
    # a decode row and a chunk row: 2 slots + the 4-token chunk, not 2 x 4; a lone chunk row keeps its [1, 4]
    assert all(s["step_tokens"] == 2 + 4 for s in mixed)
    lone = [s for s in chunky if not s["decode_rows"] and s not in tails]
    assert lone and all((s["layout"], s["step_tokens"]) == (ROWS_X_T, 4) for s in lone)
    assert all(s["decode_rows"] + s["chunk_tokens"] <= s["step_tokens"] for s in chunky)
    decodes = [s for s in steps if not s["chunk_rows"]]
    assert decodes and all(s["layout"] == ROWS_X_T and s["step_tokens"] == 1 for s in decodes[:2])
    assert core.chunk_steps_split == len(mixed) and core.chunk_steps_rows_x_t == len(lone) + len(tails)
    assert core.mixed_steps >= len(mixed)
    assert {s["overlap_mode"] for s in steps} == ({"overlapped", "barrier"} if overlap else {""})


def test_an_mla_engine_splits_its_chunk_steps_too():
    """Until ISSUE 34 an MLA model's chunk step was the padded rectangle; now a
    decode row beside a chunk is one position there as well."""
    core = _core(PRESETS["test-tiny-mla"])
    _serve_two(core)
    mixed = [s for s in core.flight.snapshot(kind="step") if s["chunk_tokens"] > 1 and s["decode_rows"]]
    assert mixed and all((s["layout"], s["step_tokens"]) == (SPLIT, 2 + 4) for s in mixed)
    assert core.chunk_steps_split == len(mixed)
