"""A chunk step on one token axis (ISSUE 27): a decode row rides a chunk step
as one token position, not padded to the chunk. The split layout against the
rows x t rectangle for the same batch (dense GQA, an OLMoE-like MoE, a window +
full model), the warm-up property (a null batch and a served step of the same
buckets are one program), who keeps the rectangle, the row order of what comes
back, and the STEP record's ``step_tokens`` / ``layout``."""

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
        num_new=np.asarray([n for _, n in rows], np.int32))


# -- the same step, both layouts --------------------------------------------------


@pytest.mark.parametrize("rows", BATCHES.values(), ids=BATCHES.keys())
@pytest.mark.parametrize("model", MODELS.keys())
def test_split_layout_computes_what_the_rectangle_computes(model, rows):
    """Sampled tokens, logprobs (the tolerance of tests/test_chunked_prefill.py)
    and every live page of the cache, split against rectangle."""
    cfg = MODELS[model]
    a, b = runner_for(cfg), runner_for(cfg, split=False)
    toks_a, lp_a = a.step(step_batch(rows), lp_k=3)
    toks_b, lp_b = b.step(step_batch(rows), lp_k=3)
    assert a.last_step_layout[0] == (SPLIT if len(rows) > 1 else ROWS_X_T) and b.last_step_layout[0] == ROWS_X_T
    assert toks_a.shape == (len(rows),) and toks_a.tolist() == toks_b.tolist()
    for key in ("logprob", "top_lps"):
        np.testing.assert_allclose(lp_a[key], lp_b[key], rtol=1e-4, atol=1e-5)
    assert lp_a["top_ids"].tolist() == lp_b["top_ids"].tolist()
    for ca, cb in ((a.k_cache, b.k_cache), (a.v_cache, b.v_cache)):  # page 0 is the null page: padding lands there
        np.testing.assert_allclose(np.asarray(ca)[:, 1:], np.asarray(cb)[:, 1:], rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("model", MODELS.keys())
def test_seeded_sampling_folds_row_by_row_as_in_the_rectangle(model):
    rows = BATCHES["chunk-between-decodes"]
    a, b = runner_for(MODELS[model]), runner_for(MODELS[model], split=False)
    assert a.step(step_batch(rows, temperature=0.9)).tolist() == b.step(step_batch(rows, temperature=0.9)).tolist()


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


@pytest.mark.parametrize("rows, null", [
    (BATCHES["two-decodes-one-chunk"], (4, 8, 16)),  # 3 rows -> 4, 7 tokens -> 8, 10 pages -> 16
    (BATCHES["one-token-last-chunk"], (4, 8, 16)),
    # A lone chunk row: the split would add a padding decode slot to its 8 tokens, so null batch and step keep [1, 8].
    (BATCHES["one-chunk-alone"], (1, 8, 16)),
], ids=["mixed", "mixed-with-a-1-token-tail", "pure-prefill"])
def test_a_null_batch_warms_the_program_a_served_step_runs(rows, null):
    runner = runner_for(MODELS["dense-gqa"])
    runner.step(serving.null_batch(*null))  # what benchmark/serving.warm_up steps a program with: every row padding
    (warm,) = runner.compile_tracker.events()
    layout = (SPLIT, null[0] + null[1]) if null[0] > 1 else (ROWS_X_T, null[1])
    assert warm["bucket"][:3] == list(null) and (warm["bucket"][-2:] == [SPLIT, 1]) == (null[0] > 1)
    assert runner.last_step_layout == layout
    runner.step(step_batch(rows))
    assert runner.compile_tracker.events() == [warm]  # no new key: the served step ran the warmed program
    assert runner._step_split_fn._cache_size() + runner._step_packed_fn._cache_size() == 1
    assert runner.last_step_layout == layout


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
    "async": (lambda: runner_for(MODELS["dense-gqa"]),
              lambda r: r.step_async(step_batch(BATCHES["two-decodes-one-chunk"])).result(), 4 * 8),
    "mesh": (_mesh_runner, lambda r: r.step(step_batch(BATCHES["two-decodes-one-chunk"])), 4 * 8),
    "mla": (lambda: runner_for(PRESETS["test-tiny-mla"]),
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


def _core(cfg, chunk: int = 4) -> EngineCore:
    config = EngineConfig(num_pages=64, page_size=PAGE, max_batch_size=8, max_prefill_tokens=chunk,
                          max_seq_len=128, chunk_prefill_tokens=chunk)
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


def test_step_records_say_layout_and_tokens_and_the_engine_counts_them():
    core = _core(MODELS["dense-gqa"])
    outputs = _serve_two(core)
    assert outputs[0] == greedy_reference([1, 2, 3, 4, 5], 12)
    assert outputs[1] == greedy_reference(list(range(7, 24)), 5)
    steps = [s for s in core.flight.snapshot(kind="step") if s["attn_phase"]]
    chunky = [s for s in steps if s["chunk_rows"]]
    mixed = [s for s in chunky if s["decode_rows"]]
    assert mixed and all(s["step_kind"] == "mixed" for s in mixed)
    assert all(s["layout"] == SPLIT for s in mixed)
    # a decode row and a chunk row: 2 slots + the 4-token chunk, not 2 x 4; a lone chunk row keeps its [1, 4]
    assert all(s["step_tokens"] == 2 + 4 for s in mixed)
    lone = [s for s in chunky if not s["decode_rows"]]
    assert lone and all((s["layout"], s["step_tokens"]) == (ROWS_X_T, 4) for s in lone)
    assert all(s["decode_rows"] + s["chunk_tokens"] <= s["step_tokens"] for s in chunky)
    decodes = [s for s in steps if not s["chunk_rows"]]
    assert decodes and all(s["layout"] == ROWS_X_T and s["step_tokens"] == 1 for s in decodes[:3])
    assert core.chunk_steps_split == len(mixed) and core.chunk_steps_rows_x_t == len(lone)
    assert core.mixed_steps >= len(mixed)


def test_an_mla_engine_counts_its_chunk_steps_as_padded():
    core = _core(PRESETS["test-tiny-mla"])
    _serve_two(core)
    chunky = [s for s in core.flight.snapshot(kind="step") if s["chunk_rows"]]
    assert chunky and all(s["layout"] == ROWS_X_T for s in chunky)
    assert any(s["step_tokens"] == 2 * 4 for s in chunky)  # two rows padded to the chunk
    assert core.chunk_steps_rows_x_t == len(chunky) and core.chunk_steps_split == 0
