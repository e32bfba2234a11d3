"""A page pool and a block table per layer kind (ISSUE 42): a model that mixes
window and full layers keeps its sliding layers' pages in a pool of their own,
a window of pages a row, and gives them back as they fall behind the window,
while the full layers' pool seats the context. Driven through ``EngineCore``
with a tiny ``exaone_moe`` (K-EXAONE's keys: three sliding layers of window 8
and a full one without RoPE, q/k head norm, a leading dense FFN, a shared
expert, 4 of 16 sigmoid-routed experts held) against
``benchmark/reference/k_exaone_moe.py`` in float32."""

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

from benchmark.reference import k_exaone_moe as ref  # noqa: E402
from dynamo_tpu.engine.allocator import OutOfPagesError, PageAllocator  # noqa: E402
from dynamo_tpu.engine.core import LOGPROBS_TOP_K, EngineConfig, EngineCore  # noqa: E402
from dynamo_tpu.engine.runner import ModelRunner  # noqa: E402
from dynamo_tpu.models import llama  # noqa: E402
from dynamo_tpu.models.config import FULL, PRESETS, SLIDING, ModelConfig  # noqa: E402
from dynamo_tpu.observability.flight import STEP_KEYS  # noqa: E402
from dynamo_tpu.protocols.common import PreprocessedRequest, SamplingOptions, StopConditions  # noqa: E402
from dynamo_tpu.runtime.engine import Context  # noqa: E402
from tests.test_mixed_attention import _distance  # noqa: E402  (max |served - reference| logprob over the largest |logit|)

CATALOG = pathlib.Path("/opt/skills/guides/model-configs/architectures.jsonl")
WINDOW, PAGE, CHUNK, ROWS = 8, 4, 12, 2
#: Layers s, s, s, f; window 8; a dense FFN then three sparse ones; 4 of 16 experts held (rank 1: ids 4-7), top-4.
TOY_HF = {
    "model_type": "exaone_moe", "first_k_dense_replace": 1, "head_dim": 16, "hidden_act": "silu", "hidden_size": 64,
    "intermediate_size": 128, "layer_types": [SLIDING, SLIDING, SLIDING, FULL], "max_position_embeddings": 512,
    "mlp_layer_types": ["dense", "sparse", "sparse", "sparse"], "moe_intermediate_size": 32,
    "mtp_layer_types": [FULL], "mtp_sliding_windows": [0], "n_group": 1, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_experts": 4, "n_routed_experts_published": 16, "expert_share_rank": 1,
    "expert_share_chips": 4, "num_experts_per_tok": 4, "num_hidden_layers": 4, "num_key_value_heads": 2,
    "num_nextn_predict_layers": 1, "num_shared_experts": 1, "rms_norm_eps": 1e-5,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"}, "routed_scaling_factor": 2.5,
    "scoring_func": "sigmoid", "sliding_window": WINDOW, "sliding_window_pattern": "LLLG",
    "sliding_windows": [WINDOW, WINDOW, WINDOW, 0], "tie_word_embeddings": False, "topk_group": 1, "vocab_size": 256,
}
TOL = 1e-4
#: What ``llama.window_pool_pages`` derives for the toy: 2 rows x (ceil((8 + 12) / 4) + 1) pages and the null page.
DERIVED = ROWS * (-(-(WINDOW + CHUNK) // PAGE) + 1) + 1


def _toy(**edit) -> ModelConfig:
    return dataclasses.replace(ModelConfig.from_hf({**TOY_HF, **edit}, name="toy-exaone"), dtype="float32")


@functools.cache
def _weights(seed=2**31 + 42, bias=0.05):
    """The benchmark's weights (plain float32), q/k norms and a selection bias that are not the identity."""
    from benchmark import weights

    params = weights.make_weights(_toy(), seed, quant="")
    for stack in ("layers", "dense_layers"):
        for i, name in enumerate(("q_norm", "k_norm")):
            shape = params[stack][name].shape
            params[stack][name] = 1.0 + 0.3 * jax.random.normal(jax.random.PRNGKey(11 + i), shape, jnp.float32)
    shape = params["layers"]["router_bias"].shape
    params["layers"]["router_bias"] = bias * jax.random.normal(jax.random.PRNGKey(7), shape, jnp.float32)
    return params


def _request(tokens, n, logprobs=None):
    return PreprocessedRequest(
        token_ids=list(tokens), sampling=SamplingOptions(temperature=0.0, logprobs=logprobs),
        stop=StopConditions(max_tokens=n, ignore_eos=True))


def _core(cfg=None, *, derived=True, pages=64, **engine) -> EngineCore:
    """An engine over the toy: ``derived`` gives the runner the chunk bound the
    serving path gives it (``launch.build_engine_service``), so the window pool
    is the derived one; without it the pools are equal."""
    cfg = cfg or _toy()
    params = _weights() if cfg.is_moe and cfg.layer_types else llama.init_params(cfg, 0)
    runner = ModelRunner(cfg, params, num_pages=pages, page_size=PAGE, max_batch_size=ROWS, prefill_bucket=4,
                         attn_impl="reference", window_chunk=CHUNK if derived else None)
    conf = dict(num_pages=pages, page_size=PAGE, max_batch_size=ROWS, max_prefill_tokens=CHUNK,
                chunk_prefill_tokens=CHUNK, max_seq_len=160, enable_prefix_caching=False)
    return EngineCore(runner, EngineConfig(**{**conf, **engine}))


def _serve(core, prompt, n_out, *, beside=True):
    """The prompt prefilled in chunks beside a decoding row, then decoded
    through the cache: every generated token's logprob and top 20, and the
    largest number of window pages live sequences held at a step's end."""
    if beside:
        core.add_request(_request([7, 9, 11, 13], 60), Context())
        for _ in range(3):
            core.step()
    seq = core.add_request(_request(prompt, n_out + 24, LOGPROBS_TOP_K + 1), Context())  # still live when read
    entries, held = [], 0
    while core.has_work and len(entries) < n_out:
        for s, out in core.step():
            if s is seq:
                entries.extend(out.logprobs or [])
        if core.window_allocator is not None:
            held = max(held, core.window_allocator.live)
    return entries[:n_out], seq, held


def _finish(core):
    while core.has_work:
        core.step()


def _reference(sequence, hf=TOY_HF):
    return np.asarray(jax.jit(functools.partial(ref.forward, hf=hf))(_weights(), tokens=jnp.asarray(sequence)))


PROMPT = np.random.default_rng(5).integers(1, 256, size=40).tolist()


# -- from_hf ------------------------------------------------------------------------


@pytest.mark.skipif(not CATALOG.exists(), reason="the catalog of public architectures is not on this machine")
def test_the_catalog_rows_keys_give_the_published_model():
    row = next(json.loads(line) for line in CATALOG.read_text().splitlines() if '"K-EXAONE-236B-A23B"' in line)
    cfg = ModelConfig.from_hf(dict(row["config"]), name="k-exaone")
    assert cfg.param_count() == pytest.approx(236.6e9, rel=1e-3)
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (48, 6144, 64, 8, 128)
    assert cfg.layer_types[:4] == (SLIDING, SLIDING, SLIDING, FULL) and cfg.layer_types.count(FULL) == 12
    assert cfg.sliding_window == 128 and cfg.mixed_attention and cfg.qk_norm == "head" and cfg.rms_eps == 1e-5
    assert (cfg.first_k_dense, cfg.intermediate_size, cfg.num_experts, cfg.moe_intermediate_size) == (1, 18432, 128, 2048)
    assert (cfg.num_experts_per_token, cfg.shared_expert_size, cfg.moe_scoring) == (8, 2048, "sigmoid")
    assert cfg.moe_norm_topk and cfg.moe_routed_scaling == 2.5 and cfg.moe_router_bias and not cfg.moe_held_share
    assert (cfg.moe_n_group, cfg.moe_topk_group) == (0, 0) and not cfg.tie_embeddings and cfg.vocab_size == 153600
    # One rope_parameters dict is the sliding layers'; a full layer's table is the identity.
    assert cfg.rope_of(SLIDING) == (1e6, None) and cfg.rope_of(FULL)[1] == {"rope_type": "nope"}
    from dynamo_tpu.ops.rope import rope_attention_factor, rope_frequencies

    assert not rope_frequencies(128, theta=1e6, scaling=cfg.rope_of(FULL)[1]).any()
    assert rope_attention_factor(cfg.rope_of(FULL)[1]) == 1.0
    # Cache bytes by kind: a token of context costs the 12 full layers, never the 36 sliding ones.
    assert cfg.kv_bytes_per_token() == 12 * 4096 and cfg.kv_bytes_per_token(kind=SLIDING) == 36 * 4096


def test_from_hf_reads_the_toy_and_a_list_that_runs_past_the_layers_held():
    cfg = _toy()
    assert cfg.layer_types == (SLIDING, SLIDING, SLIDING, FULL) and cfg.layer_windows() == (8, 8, 8, 0)
    assert (cfg.num_experts, cfg.routed_experts, cfg.moe_expert_first, cfg.shared_expert_size) == (4, 16, 4, 32)
    assert cfg.moe_held_share and cfg.first_k_dense == 1 and cfg.qk_norm == "head"
    # The published lists whole, the first layers held: what the benchmark's file does.
    longer = _toy(layer_types=TOY_HF["layer_types"] * 3, mlp_layer_types=["dense"] + ["sparse"] * 11,
                  sliding_windows=TOY_HF["sliding_windows"] * 3)
    assert longer == cfg
    one_kind = _toy(layer_types=[FULL] * 4, sliding_windows=[0] * 4, sliding_window_pattern=None)
    assert not one_kind.mixed_attention and one_kind.rope_parameters is None and one_kind.rope_theta == 1e6


@pytest.mark.parametrize("edit, says", [
    ({"mlp_layer_types": ["dense", "sparse", "dense", "sparse"]}, "mlp_layer_types"),
    ({"mlp_layer_types": ["sparse"] * 4}, "first_k_dense_replace"),
    ({"topk_group": 2}, "topk_group"),
    ({"rope_parameters": {"rope_theta": 1e6, "rope_type": "yarn", "factor": 4}}, "rope_type 'yarn'"),
    ({"sliding_windows": [8, 8, 0, 0]}, "sliding_windows"),
    ({"sliding_window_pattern": "LG"}, "sliding_window_pattern"),
    ({"layer_types": [SLIDING, SLIDING, SLIDING]}, "expected 4 entries"),
], ids=["dense-past-the-first", "no-dense-entry", "topk-group", "rope-type", "windows", "pattern", "short-list"])
def test_from_hf_refuses_by_name(edit, says):
    with pytest.raises(ValueError, match=says):
        ModelConfig.from_hf({**TOY_HF, **edit}, name="t")


def test_loader_refuses_the_unmapped_checkpoint_by_name(tmp_path):
    from dynamo_tpu.models.loader import load_model

    (tmp_path / "config.json").write_text(json.dumps(TOY_HF))
    with pytest.raises(ValueError, match="exaone_moe"):
        load_model(tmp_path)


# -- the pools ----------------------------------------------------------------------


@pytest.mark.parametrize("window, chunk, page, rows, pages, want", [
    (1024, 64, 128, 8, 321, 81),  # Mellum2's cell: 40,960 tokens, 81 window pages
    (128, 512, 128, 8, 2049, 49),  # K-EXAONE's: window + a chunk + the page being written, 8 rows, the null page
    (8, None, 4, 2, 64, 64),  # no bound on a row's step: as many as the full pool
    (10**6, 12, 4, 2, 64, 64),  # never more than the full pool
], ids=["mellum2", "k-exaone", "unbounded", "capped"])
def test_window_pool_is_derived(window, chunk, page, rows, pages, want):
    cfg = dataclasses.replace(_toy(), sliding_window=window)
    assert llama.window_pool_pages(cfg, pages, page, rows, chunk) == want


def test_cache_lays_a_pool_per_kind_end_to_end():
    cfg = _toy()
    k, _ = llama.init_kv_cache(cfg, 10, PAGE, window_pages=3)
    assert k.shape == (1, 1 * 10 + 3 * 3, PAGE, 2 * 16)
    assert llama.pool_layout(cfg, k.shape[1], 3) == ([10, 13, 16, 0], 10, 3)  # the full layer's pool first
    k, _ = llama.init_kv_cache(cfg, 10, PAGE)  # equal pools: one table can name both kinds
    assert k.shape == (1, 40, PAGE, 32) and llama.pool_layout(cfg, 40, None) == ([10, 20, 30, 0], 10, 10)
    with pytest.raises(ValueError, match="full pools"):
        llama.pool_layout(cfg, 10, 4)
    one_kind = dataclasses.replace(PRESETS["test-tiny"], sliding_window=8)
    assert llama.init_kv_cache(one_kind, 10, PAGE)[0].shape[:2] == (2, 10)  # all alike: [L, pages, ...] as before


@pytest.fixture(scope="module")
def served():
    core = _core()
    entries, seq, held = _serve(core, PROMPT, 30)
    return core, entries, seq, held


def test_engine_through_both_pools_agrees_with_the_reference(served):
    """A prompt of 40 prefilled in chunks of 12 beside a decoding row, then 30
    tokens decoded: contexts to 70 tokens, nearly nine windows of 8, through a
    window pool of 12 pages. Both sides float32 at ``highest`` matmul precision
    (conftest): what is left is the order of accumulation (paged chunks against
    one whole sequence, sorted expert rows against an expert at a time), about
    1e-6 of the logit range, so the tolerance is 1e-4."""
    core, entries, seq, _ = served
    assert core.runner.window_pages == DERIVED == 13 and core.window_allocator.num_pages == 13
    sequence = PROMPT + [e["id"] for e in entries][:-1]
    assert len(entries) == 30 and _distance(entries, PROMPT, _reference(sequence)) < TOL
    steps = core.flight.snapshot(kind="step")
    assert {"mixed", "decode"} <= {s["step_kind"] for s in steps}


def test_window_pages_go_back_behind_the_window_and_full_pages_stay(served):
    core, entries, seq, held = served
    # 69 tokens cached: 18 blocks. The full layers' pages all held; the window's first 15 given back.
    assert len(seq.pages) == len(seq.window_pages) == 18 and 0 not in seq.pages
    assert seq.window_pages[:15] == [0] * 15 and 0 not in seq.window_pages[15:]
    assert held <= DERIVED - 1 and core.window_pages_released >= 15
    steps = core.flight.snapshot(kind="step")
    assert set(STEP_KEYS) >= {"full_pages_live", "window_pages_live", "window_pages_released"}
    assert sum(s["window_pages_released"] for s in steps) == core.window_pages_released
    last = steps[-1]
    assert last["full_pages_live"] == core.allocator.live > last["window_pages_live"] == core.window_allocator.live > 0
    _finish(core)
    assert core.allocator.live == 0 and core.window_allocator.live == 0
    assert core.window_allocator.num_free() == DERIVED - 1 and core.num_preemptions == 0


@pytest.mark.parametrize("engine", [dict(derived=False, swa_free_pages=False), dict(derived=False),
                                    dict(overlap=False), dict(decode_steps=2), dict(enable_prefix_caching=True)],
                         ids=["release-off", "equal-pools", "synchronous", "bursts-of-2", "prefix-caching"])
def test_same_tokens_however_the_pools_are_kept(served, engine):
    _, entries, _, _ = served
    core = _core(**engine)
    got, seq, _ = _serve(core, PROMPT, 30)
    assert [e["id"] for e in got] == [e["id"] for e in entries]
    if not engine.get("swa_free_pages", True):
        assert core.window_pages_released == 0 and 0 not in seq.window_pages
    else:
        assert core.window_pages_released > 0
    _finish(core)
    assert core.allocator.live == 0 and core.window_allocator.live == 0


def test_speculative_verify_rolls_both_pools_back(served, monkeypatch):
    _, entries, _, _ = served
    monkeypatch.setenv("DYN_SPEC_PROPOSER", "ngram")
    core = _core(spec_k=2)
    if core._proposer is None:
        pytest.skip("no draft proposer is built in this environment")
    got, seq, _ = _serve(core, PROMPT, 30)
    assert [e["id"] for e in got][:30] == [e["id"] for e in entries]
    assert len(seq.pages) == len(seq.window_pages)
    _finish(core)
    assert core.allocator.live == 0 and core.window_allocator.live == 0


def test_a_one_kind_model_keeps_one_pool_and_its_records_read_nothing():
    cfg = _toy(layer_types=[SLIDING] * 4, sliding_windows=[WINDOW] * 4, sliding_window_pattern=None)
    core = _core(cfg)
    assert not core.runner.two_pool and core.window_allocator is None and core.runner.k_cache.shape[:2] == (4, 64)
    entries, seq, _ = _serve(core, PROMPT, 12)
    assert len(entries) == 12 and seq.window_pages == [] and 0 in seq.pages  # its one pool's pages go back
    steps = core.flight.snapshot(kind="step")
    assert all(s["window_pages_live"] == 0 and s["window_pages_released"] == 0 for s in steps)
    assert steps[-1]["full_pages_live"] == core.allocator.live > 0


def test_preemption_hands_both_pools_back_and_the_tokens_do_not_change(served):
    _, entries, _, _ = served
    core = _core()
    got, seq, _ = _serve(core, PROMPT, 10)
    more = []
    while core._inflight is not None:  # a row whose step is mid-air is never preempted by the scheduler either
        more += [t for s, out in core._drain_inflight() if s is seq for t in out.token_ids]
    mine = (sum(p != 0 for p in seq.pages), sum(p != 0 for p in seq.window_pages))
    held_full, held_window = core.allocator.live, core.window_allocator.live
    core._preempt(seq)
    assert seq.pages == [] and seq.window_pages == [] and core.num_preemptions == 1
    assert (core.allocator.live, core.window_allocator.live) == (held_full - mine[0], held_window - mine[1])
    while core.has_work and len(more) < 20:
        for s, out in core.step():
            if s is seq:
                more.extend(out.token_ids)
    assert [e["id"] for e in got][:10] + more[:20] == [e["id"] for e in entries][:30]


def test_prefix_rule_a_hit_needs_the_windows_last_pages_and_gives_the_same_tokens(served):
    """The same prompt three times through one engine with prefix caching on.
    The second finds its full pages and the window's last two pages cached
    (``ceil(8 / 4)``) and computes the tail alone; before the third the window
    pool's cache is dropped, so the full pages alone are no hit and the prompt
    is computed whole. The tokens are the first run's both times."""
    _, entries, _, _ = served
    want = [e["id"] for e in entries][:12]
    core = _core(enable_prefix_caching=True)
    first, seq, _ = _serve(core, PROMPT, 12, beside=False)
    _finish(core)
    assert [e["id"] for e in first] == want and seq.num_cached_at_start == 0
    second, seq2, _ = _serve(core, PROMPT, 12, beside=False)
    _finish(core)
    assert [e["id"] for e in second] == want
    assert seq2.num_cached_at_start == 36  # nine whole blocks of the 40-token prompt; the last token is computed
    assert core.window_allocator.clear_cache() > 0
    third, seq3, _ = _serve(core, PROMPT, 12, beside=False)
    assert [e["id"] for e in third] == want and seq3.num_cached_at_start == 0
    assert core.allocator.stats().hits >= 18  # the full pool matched both times: the rule is the window's


def test_out_of_pages_names_the_pool_and_takes_nothing():
    with pytest.raises(OutOfPagesError, match="window pool"):
        PageAllocator(4, PAGE, pool="window").allocate(5)
    core = _core(pages=64)
    seq = core.add_request(_request(PROMPT, 4), Context())
    core.step()
    before = (core.allocator.num_free(), len(seq.pages), len(seq.window_pages))
    with pytest.raises(OutOfPagesError, match="of the window pool, have"):
        core._grow(seq, DERIVED)  # more than the window pool has: the full pool's pages go back
    assert (core.allocator.num_free(), len(seq.pages), len(seq.window_pages)) == before
    with pytest.raises(OutOfPagesError, match="of the kv pool"):
        core._grow(seq, 64)


def test_what_moves_pages_across_layers_is_refused_by_name():
    core = _core()
    for call in (lambda: core.runner.read_page(1), lambda: core.runner.read_pages([1, 2]),
                 lambda: core.runner.write_pages([1], [np.zeros((4, PAGE, 32))], [np.zeros((4, PAGE, 32))])):
        with pytest.raises(NotImplementedError, match="page pool per layer kind"):
            call()
    from dynamo_tpu.disagg.transfer import KvTransferService

    with pytest.raises(NotImplementedError, match="page pool per layer kind"):
        KvTransferService(core)
    with pytest.raises(ValueError, match="offload tiers"):
        EngineCore(core.runner, core.config, block_manager=object())
    with pytest.raises(ValueError, match="swa_free_pages is off"):
        EngineCore(core.runner, dataclasses.replace(core.config, swa_free_pages=False))


def test_a_hand_built_batch_of_real_rows_without_window_tables_is_refused():
    """``StepBatch.window_block_tables`` defaults to None for the warm-up's null
    batches (every row the null page); rows that name pages must bring both tables."""
    from tests.test_split_mixed_step import step_batch

    runner = _core().runner
    real = step_batch([(0, 4), (3, 1)], pages_per_row=2)
    assert runner._pad(real).window_block_tables.any()
    with pytest.raises(ValueError, match="carries no window_block_tables"):
        runner._pad(dataclasses.replace(real, window_block_tables=None, window_slot_mapping=None))
    null = dataclasses.replace(real, block_tables=np.zeros_like(real.block_tables), window_block_tables=None,
                               slot_mapping=np.zeros_like(real.slot_mapping), window_slot_mapping=None)
    padded = runner._pad(null)
    assert not padded.window_block_tables.any() and padded.window_block_tables.shape == padded.block_tables.shape


def test_bench_counts_a_decode_steps_cache_bytes_by_kind():
    """``bench.decode_step_bytes``: a full layer reads the context, a sliding
    layer the pages its window reaches into (page 4, window 8: blocks 0-2 at
    context 11, 1-2 at 12, 1-3 at 13 and 14)."""
    import bench

    cfg, params = _toy(), _weights()
    weights_read = bench.decode_weight_bytes(params, cfg)
    got = bench.decode_step_bytes(params, cfg, 2, 10, 4, PAGE, cache_itemsize=4)
    full, sliding = cfg.kv_bytes_per_token(itemsize=4), cfg.kv_bytes_per_token(itemsize=4, kind=SLIDING)
    assert sliding == 3 * full
    assert got == weights_read + 2 * ((12 + 12 + 16 + 16) // 4 * full + (12 + 8 + 12 + 12) // 4 * sliding)
    long = bench.decode_step_bytes(params, cfg, 1, 400, 1, PAGE, cache_itemsize=4)  # context 401: blocks 98-100
    assert long == weights_read + 404 * full + 12 * sliding


def test_metrics_carry_the_second_pool():
    from dynamo_tpu.observability.metrics import EngineMetrics

    core = _core()
    _serve(core, PROMPT, 12)
    import asyncio

    text = asyncio.run(EngineMetrics(worker="w").bind_core(core).render()).decode()
    got = {line.split("{")[0]: float(line.rsplit(" ", 1)[1]) for line in text.splitlines()
           if line.startswith("dynamo_engine_window_pages")}
    assert got["dynamo_engine_window_pages_total"] == DERIVED - 1
    assert got["dynamo_engine_window_pages_released_total"] == core.window_pages_released > 0
    assert got["dynamo_engine_window_pages_active"] == core.window_allocator.live


# -- the share ----------------------------------------------------------------------


def test_the_eight_shares_add_up_to_the_uncut_layer_with_the_shared_expert_once():
    """One sparse layer of 16 routed experts and a shared expert, divided over
    eight holders of 2 experts: what the model's layer gives on each holder
    (its experts' terms and the shared expert, which every holder computes
    whole), summed with the shared expert counted once, equals the uncut
    reference's layer, which holds all 16. float32 both sides: 1e-5 of the
    largest output."""
    whole_hf = {**TOY_HF, "num_experts": 16, "n_routed_experts_published": 16, "expert_share_rank": 0}
    whole = _toy(num_experts=16, expert_share_rank=0)
    from benchmark import weights

    params = weights.make_weights(whole, 2**31 + 42, quant="")
    lp = jax.tree.map(lambda x: x[0], params["layers"])
    lp["router_bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(7), lp["router_bias"].shape, jnp.float32)
    h = jax.random.normal(jax.random.PRNGKey(3), (1, 48, 64), jnp.float32)
    want = np.asarray(ref.moe(h[0], lp, ref.shape_of(whole_hf)))
    shared = np.asarray(ref.shared_expert_term(h[0], lp))
    assert np.abs(shared).max() > 0.05 * np.abs(want).max()  # the shared expert is no rounding error here
    total, held_choices = np.zeros_like(want), 0
    for rank in range(8):
        share_hf = {**TOY_HF, "num_experts": 2, "expert_share_rank": rank, "expert_share_chips": 8}
        share = _toy(num_experts=2, expert_share_rank=rank, expert_share_chips=8)
        assert share.moe_expert_first == 2 * rank and share.moe_held_share
        mine = {**lp, **{k: lp[k][2 * rank: 2 * rank + 2] for k in ("w_gate", "w_up", "w_down")}}
        out, counts = llama._mlp_moe_held(mine, h, share, jnp.ones((1, 48), bool))
        np.testing.assert_allclose(out[0], ref.moe(h[0], mine, ref.shape_of(share_hf)), atol=1e-5 * np.abs(want).max())
        total += np.asarray(out[0]) - shared
        held_choices += int(counts[2])
    np.testing.assert_allclose(total + shared, want, atol=1e-5 * np.abs(want).max())
    assert held_choices == 48 * 4  # every choice landed on exactly one holder
