"""Pallas paged-attention decode kernel vs the XLA reference formulation.

Runs the kernel in interpret mode on CPU (bit-exact semantics, no TPU
needed); a TPU-marked variant compares on-device when a chip is present.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops.attention import paged_attention_reference
from dynamo_tpu.ops.pallas_paged import decode_supported, paged_decode_attention


def _random_case(rng, *, b, n_heads, n_kv, head_dim, page_size, pages_per_seq, max_len):
    width = n_kv * head_dim
    num_pages = b * pages_per_seq + 1
    k = jnp.asarray(rng.standard_normal((num_pages, page_size, width)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((num_pages, page_size, width)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((b, 1, n_heads, head_dim)), jnp.float32)
    # Distinct pages per sequence (page 0 reserved as null).
    tables = jnp.asarray(
        1 + rng.permutation(num_pages - 1)[: b * pages_per_seq].reshape(b, pages_per_seq),
        jnp.int32,
    )
    positions = jnp.asarray(rng.integers(0, max_len, (b, 1)), jnp.int32)
    return q, k, v, tables, positions


@pytest.mark.parametrize(
    "b,n_heads,n_kv,head_dim,pages_per_seq",
    [
        (4, 8, 2, 64, 8),   # llama-3.2-1b-like GQA, head_dim 64
        (2, 8, 8, 16, 4),   # MHA, small head_dim (interpret only)
        (3, 4, 1, 128, 16), # MQA, head_dim 128, non-pow2 batch
    ],
)
def test_decode_kernel_matches_reference(b, n_heads, n_kv, head_dim, pages_per_seq):
    rng = np.random.default_rng(0)
    page_size = 16
    q, k, v, tables, positions = _random_case(
        rng, b=b, n_heads=n_heads, n_kv=n_kv, head_dim=head_dim,
        page_size=page_size, pages_per_seq=pages_per_seq,
        max_len=page_size * pages_per_seq,
    )
    scale = head_dim**-0.5
    want = paged_attention_reference(q, k, v, tables, positions, scale=scale)
    got = paged_decode_attention(q, k, v, tables, positions, scale=scale, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


#: Page 8, rows of up to 20 pages (160 tokens), 4 query heads over 2 KV heads of 32.
HELD_PAGE, HELD_PAGES_PER_SEQ = 8, 20


def check_only_held_pages_move(monkeypatch, *, pages_per_block, positions, window=None, num_splits=1):
    """Drive the decode kernel over ``positions`` [B, T_q] with everything no
    query of a row can see poisoned: every pool page outside the rows' held
    ranges is NaN, every table entry outside them is out of range, and the
    interpreter hands out NaN for VMEM no copy wrote. The kernel contracts
    over whole blocks, so one copy of a page that is not held, or one ring row
    left as it was, turns the output NaN. The held range is worked out here by
    hand (from the window's first key to the farthest query, in pages) and the
    wrapper's helper must name the same pages; the output must be the
    reference's on every real query (a padding column under a window sees no
    key: finite, and discarded)."""
    import dynamo_tpu.ops.pallas_paged as pp
    from jax.experimental.pallas import tpu as pltpu

    monkeypatch.setattr(pp, "_pages_per_block", lambda pps, *a: min(pps, pages_per_block))
    positions = np.asarray(positions, np.int32)
    b, t_q = positions.shape
    page, pages_per_seq, n_heads, n_kv, hd = HELD_PAGE, HELD_PAGES_PER_SEQ, 4, 2, 32
    real = (positions > 0) | (np.arange(t_q)[None] == 0)
    last = positions.max(axis=1) // page
    oldest = np.where(real, positions, np.iinfo(np.int32).max).min(axis=1)
    first = np.zeros(b, np.int64) if window is None else np.maximum(oldest - int(window) + 1, 0) // page

    num_pages = 2 + b * pages_per_seq
    tables = np.full((b, pages_per_seq), 2**30, np.int32)
    held = np.zeros(num_pages, bool)
    for i in range(b):
        ids = 1 + i * pages_per_seq + np.arange(first[i], last[i] + 1)
        tables[i, first[i]: last[i] + 1] = ids
        held[ids] = True
    walk = pp.decode_walk(jnp.asarray(positions), page, pages_per_block, window)
    np.testing.assert_array_equal(np.asarray(walk.first_page), first)
    np.testing.assert_array_equal(np.asarray(walk.last_page), last)
    assert int(walk.pages_started) == held.sum()
    visited = int(jnp.sum(walk.blocks)) * pages_per_block
    assert held.sum() <= visited < held.sum() + 2 * b * pages_per_block  # at most a block's slots at either end

    rng = np.random.default_rng(int(positions.sum()) + pages_per_block)
    shape = (num_pages, page, n_kv * hd)
    k = np.where(held[:, None, None], rng.standard_normal(shape), np.nan).astype(np.float32)
    v = np.where(held[:, None, None], rng.standard_normal(shape), np.nan).astype(np.float32)
    q = jnp.asarray(rng.standard_normal((b, t_q, n_heads, hd)), jnp.float32)
    # The reference first and to its end: the TPU interpreter's callbacks run
    # JAX operations of their own, and deadlock against a dispatch from here.
    want = np.asarray(paged_attention_reference(
        q, jnp.nan_to_num(k), jnp.nan_to_num(v), jnp.asarray(np.where(tables == 2**30, 0, tables)),
        jnp.asarray(positions), scale=hd**-0.5, sliding_window=0 if window is None else window))
    paged_decode_attention.clear_cache()  # the pinned block size is read at trace time
    got = np.asarray(paged_decode_attention(
        q, jnp.asarray(k), jnp.asarray(v), jnp.asarray(tables), jnp.asarray(positions), scale=hd**-0.5,
        window=None if window is None else jnp.int32(window), num_splits=num_splits,
        interpret=pltpu.InterpretParams(uninitialized_memory="nan")))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got[real], want[real], rtol=2e-5, atol=2e-5)


def _tail_contexts(pages_per_block):
    """One block and a tail of 1 .. pages_per_block pages (the last is a second
    full block), the last page 5 of 8 tokens full."""
    block = pages_per_block * HELD_PAGE
    return [block + HELD_PAGE * k - 3 for k in range(1, pages_per_block + 1)]


#: (pages a block, context, splits): tails of every count at 4 and 8 pages a
#: block, alternately in one split and in three; a row inside its first page,
#: one that ends on a block's edge, one a token past it.
TAILS = [
    (ppb, ctx, 3 if n % 2 else 1) for ppb in (4, 8) for n, ctx in enumerate(_tail_contexts(ppb))
] + [(4, 1, 1), (4, 7, 3), (4, 32, 1), (4, 33, 3), (8, 64, 3), (8, 65, 1)]


@pytest.mark.parametrize("pages_per_block, context, num_splits", TAILS,
                         ids=[f"block{p}-ctx{c}-splits{s}" for p, c, s in TAILS])
def test_decode_kernel_tail_block_moves_only_held_pages(monkeypatch, pages_per_block, context, num_splits):
    """A row's last block copies the pages the row holds and nothing else: no
    slot past the row's last page is clamped to that page and fetched again.
    Row 0 is short and row 2 long, so the first ring slots hold mostly
    unwritten rows when the later rows' tails land in them."""
    check_only_held_pages_move(
        monkeypatch, pages_per_block=pages_per_block, num_splits=num_splits,
        positions=[[2], [context - 1], [149], [context - 1]])


@pytest.mark.parametrize("pages_per_block", [4, 8])
def test_decode_kernel_padding_and_verify_rows_move_only_held_pages(monkeypatch, pages_per_block):
    """A padding row (every position 0) holds page 0 alone; a gappy verify row
    holds up to its farthest token's page, whichever column that is."""
    check_only_held_pages_move(
        monkeypatch, pages_per_block=pages_per_block, num_splits=2,
        positions=[[0, 0, 0], [70, 73, 71], [0, 0, 0], [95, 0, 0], [38, 39, 40]])


def test_decode_kernel_tail_block_at_serving_widths():
    """pages_per_seq > pages_per_block at 8 query over 2 KV heads of 64 and
    page 16: rows that fill their blocks, end on a block's edge and end
    mid-block — the deep-block path every page-16 serving config hits at long
    context."""
    import dynamo_tpu.ops.pallas_paged as pp

    rng = np.random.default_rng(3)
    page_size, pages_per_seq = 16, 9
    # Force small blocks so multiple blocks + a ragged tail exist.
    orig = pp._pages_per_block
    pp._pages_per_block = lambda pps, ps, *a: 4  # bk=64; 9 pages -> 3 blocks, tail ragged
    try:
        q, k, v, tables, positions = _random_case(
            rng, b=3, n_heads=8, n_kv=2, head_dim=64,
            page_size=page_size, pages_per_seq=pages_per_seq,
            max_len=page_size * pages_per_seq,
        )
        positions = jnp.asarray([[143], [64], [127]], jnp.int32)  # full, block edge, mid
        scale = 0.125
        want = paged_attention_reference(q, k, v, tables, positions, scale=scale)
        got = paged_decode_attention(q, k, v, tables, positions, scale=scale, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-2, rtol=2e-2)
    finally:
        pp._pages_per_block = orig


def test_decode_kernel_fp8_cache():
    """Sub-2-byte KV caches upcast to bf16 inside the kernel; results stay
    close to the f32 reference (fp8 storage error only)."""
    rng = np.random.default_rng(5)
    q, k, v, tables, positions = _random_case(
        rng, b=2, n_heads=8, n_kv=2, head_dim=64, page_size=16, pages_per_seq=4, max_len=64,
    )
    k8 = k.astype(jnp.float8_e4m3fn)
    v8 = v.astype(jnp.float8_e4m3fn)
    scale = 0.125
    want = paged_attention_reference(q, k, v, tables, positions, scale=scale)
    got = paged_decode_attention(q, k8, v8, tables, positions, scale=scale, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=0.15, rtol=0.15)


def test_decode_kernel_length_one():
    """Position 0 (only the just-written token) must not read other pages."""
    rng = np.random.default_rng(1)
    q, k, v, tables, positions = _random_case(
        rng, b=2, n_heads=4, n_kv=2, head_dim=64, page_size=16,
        pages_per_seq=4, max_len=1,
    )
    positions = jnp.zeros_like(positions)
    scale = 0.125
    want = paged_attention_reference(q, k, v, tables, positions, scale=scale)
    got = paged_decode_attention(q, k, v, tables, positions, scale=scale, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_decode_supported_on_engine_layout():
    """The support predicate must accept the engine's flat [P, ps, W] cache."""
    q = jnp.zeros((2, 1, 32, 64))
    k = jnp.zeros((8, 16, 8 * 64))  # llama-3.2-1b: n_kv=8, hd=64 -> W=512
    assert decode_supported(q, k)
    k_bad = jnp.zeros((8, 16, 8 * 64 + 8))  # W not a head multiple
    assert not decode_supported(q, k_bad)


def test_forward_dispatches_to_kernel(monkeypatch):
    """models/llama.forward with attn_impl='pallas' must reach the kernel for
    decode shapes (guards against silent fallback to the gather formulation)."""
    import dynamo_tpu.ops.attention as attention_mod
    import dynamo_tpu.ops.pallas_paged as pp
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import PRESETS

    cfg = PRESETS["test-tiny"]  # n_kv=2, hd=16 -> W=32: not lane-aligned
    hits = []
    real = pp.paged_decode_attention

    def spy(*a, **kw):
        hits.append(1)
        return real(*a, interpret=True, **{k: v for k, v in kw.items() if k != "interpret"})

    monkeypatch.setattr(pp, "paged_decode_attention", spy)

    params = llama.init_params(cfg, 0)
    k_cache, v_cache = llama.init_kv_cache(cfg, num_pages=8, page_size=4)
    b = 2
    tokens = jnp.zeros((b, 1), jnp.int32)
    positions = jnp.ones((b, 1), jnp.int32)
    tables = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    slots = jnp.asarray([[1 * 4 + 1], [3 * 4 + 1]], jnp.int32)
    last = jnp.zeros((b,), jnp.int32)

    # W=32 is not 128-lane aligned: decode_supported is False, no kernel hit,
    # and the forward still runs via the reference path.
    logits, _, _ = llama.forward(
        params, cfg, tokens, positions, k_cache, v_cache, tables, slots, last,
        attn_impl="pallas",
    )
    assert logits.shape == (b, cfg.vocab_size)
    assert not hits

    # A lane-aligned config must hit the kernel.
    import dataclasses

    cfg2 = dataclasses.replace(cfg, num_kv_heads=2, head_dim=64, num_heads=4, dtype="float32")
    params2 = llama.init_params(cfg2, 0)
    k2, v2 = llama.init_kv_cache(cfg2, num_pages=8, page_size=4)
    llama.forward(
        params2, cfg2, tokens, positions, k2, v2, tables, slots, last,
        attn_impl="pallas",
    )
    assert hits


def _pin_small_blocks(monkeypatch):
    """Force 1-page compute blocks so a handful of pages spans many blocks
    (split-K boundaries become exercisable at test sizes)."""
    import dynamo_tpu.ops.pallas_paged as pp

    monkeypatch.setattr(pp, "_pages_per_block", lambda pps, ps, *a: 1)


@pytest.mark.parametrize("num_splits", [2, 4, 8])
def test_split_k_matches_reference_ragged(monkeypatch, num_splits):
    """Split-K partials + LSE combine vs reference across ragged lengths:
    a length shorter than one split's slice, lengths that leave tail splits
    completely empty, and length <= page_size."""
    _pin_small_blocks(monkeypatch)  # bk = page_size = 16; 8 pages -> 8 blocks
    rng = np.random.default_rng(7)
    q, k, v, tables, positions = _random_case(
        rng, b=4, n_heads=8, n_kv=2, head_dim=64, page_size=16,
        pages_per_seq=8, max_len=128,
    )
    # length 11 (single block — every later split empty), 101, 128 (full),
    # 16 (== page_size exactly).
    positions = jnp.asarray([[10], [100], [127], [15]], jnp.int32)
    scale = 0.125
    want = paged_attention_reference(q, k, v, tables, positions, scale=scale)
    got = paged_decode_attention(
        q, k, v, tables, positions, scale=scale, interpret=True,
        num_splits=num_splits,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_split_k_fp8_cache_through_combine(monkeypatch):
    """fp8 cache values must survive the per-split partials and the f32
    LSE combine (upcast happens inside each split's block loop)."""
    _pin_small_blocks(monkeypatch)
    rng = np.random.default_rng(11)
    q, k, v, tables, positions = _random_case(
        rng, b=2, n_heads=8, n_kv=2, head_dim=64, page_size=16,
        pages_per_seq=6, max_len=96,
    )
    positions = jnp.asarray([[95], [40]], jnp.int32)
    k8 = k.astype(jnp.float8_e4m3fn)
    v8 = v.astype(jnp.float8_e4m3fn)
    scale = 0.125
    want = paged_attention_reference(q, k, v, tables, positions, scale=scale)
    got = paged_decode_attention(
        q, k8, v8, tables, positions, scale=scale, interpret=True, num_splits=3,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=0.15, rtol=0.15)


def test_split_k_single_split_matches_unsplit():
    """num_splits=1 must be bitwise identical to the auto-chosen grid at
    batch >= 8 (the combine degenerates to acc / l exactly)."""
    rng = np.random.default_rng(13)
    q, k, v, tables, positions = _random_case(
        rng, b=8, n_heads=4, n_kv=2, head_dim=64, page_size=16,
        pages_per_seq=4, max_len=64,
    )
    scale = 0.125
    a = paged_decode_attention(q, k, v, tables, positions, scale=scale,
                               interpret=True, num_splits=1)
    b_ = paged_decode_attention(q, k, v, tables, positions, scale=scale,
                                interpret=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))


def test_multi_query_verify_rows_match_reference():
    """T_q > 1 gappy rows (speculative verify layout): per-row causal mask
    vs the reference's key_pos <= positions mask, including a padding row
    whose trailing columns carry position 0."""
    rng = np.random.default_rng(17)
    b, t_q, n_heads, n_kv, head_dim = 3, 4, 8, 2, 64
    page_size, pages_per_seq = 16, 4
    width = n_kv * head_dim
    num_pages = b * pages_per_seq + 1
    k = jnp.asarray(rng.standard_normal((num_pages, page_size, width)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((num_pages, page_size, width)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((b, t_q, n_heads, head_dim)), jnp.float32)
    tables = jnp.asarray(
        1 + rng.permutation(num_pages - 1)[: b * pages_per_seq].reshape(b, pages_per_seq),
        jnp.int32,
    )
    # Row 0: contiguous verify window; row 1: decode token + padding zeros
    # (mixed spec batch); row 2: full-width window ending at the last slot.
    positions = jnp.asarray(
        [[37, 38, 39, 40], [12, 0, 0, 0], [60, 61, 62, 63]], jnp.int32
    )
    scale = head_dim**-0.5
    want = paged_attention_reference(q, k, v, tables, positions, scale=scale)
    got = paged_decode_attention(q, k, v, tables, positions, scale=scale, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_multi_query_bitwise_matches_per_position_decode(monkeypatch):
    """Losslessness invariant: a T_q = K+1 verify row must score token t
    EXACTLY as a T_q = 1 decode of token t would (same block partition,
    same split count -> same accumulation order; the extra masked blocks a
    longer row walks contribute exact zeros)."""
    _pin_small_blocks(monkeypatch)
    rng = np.random.default_rng(19)
    b, t_q = 2, 3
    q, k, v, tables, _ = _random_case(
        rng, b=b, n_heads=8, n_kv=2, head_dim=64, page_size=16,
        pages_per_seq=6, max_len=96,
    )
    q = jnp.asarray(rng.standard_normal((b, t_q, 8, 64)), jnp.float32)
    positions = jnp.asarray([[50, 51, 52], [7, 8, 9]], jnp.int32)
    scale = 0.125
    multi = paged_decode_attention(
        q, k, v, tables, positions, scale=scale, interpret=True, num_splits=2,
    )
    for t in range(t_q):
        single = paged_decode_attention(
            q[:, t : t + 1], k, v, tables, positions[:, t : t + 1],
            scale=scale, interpret=True, num_splits=2,
        )
        np.testing.assert_array_equal(
            np.asarray(multi[:, t : t + 1]), np.asarray(single)
        )


def test_verify_dispatch_reaches_kernel_no_fallback(monkeypatch):
    """paged_attention_pallas with contiguous_positions=False and a
    supported shape must use the multi-query kernel and record no
    fallback (the spec-verify fast path)."""
    import dynamo_tpu.ops.pallas_paged as pp

    monkeypatch.setenv("DYNAMO_PALLAS_INTERPRET", "1")
    rng = np.random.default_rng(23)
    b, t_q = 2, 3
    q, k, v, tables, _ = _random_case(
        rng, b=b, n_heads=8, n_kv=2, head_dim=64, page_size=16,
        pages_per_seq=4, max_len=64,
    )
    q = jnp.asarray(rng.standard_normal((b, t_q, 8, 64)), jnp.float32)
    positions = jnp.asarray([[20, 22, 23], [5, 6, 8]], jnp.int32)  # gappy
    before = pp.fallback_snapshot()
    got = pp.paged_attention_pallas(
        q, k, v, tables, positions, scale=0.125, contiguous_positions=False,
    )
    after = pp.fallback_snapshot()
    assert not [s for s in after if s.startswith("verify") and after[s] != before.get(s, 0)]
    want = paged_attention_reference(q, k, v, tables, positions, scale=0.125)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_verify_fallback_recorded_for_unsupported_t(monkeypatch):
    """A verify batch wider than the VMEM row cap must fall back and be
    counted under the distinct 'verify' phase (not 'prefill')."""
    import dynamo_tpu.ops.pallas_paged as pp

    monkeypatch.setenv("DYNAMO_PALLAS_INTERPRET", "1")
    monkeypatch.setenv("DYN_VERIFY_T_MAX", "2")
    rng = np.random.default_rng(29)
    b, t_q = 1, 3
    q, k, v, tables, _ = _random_case(
        rng, b=b, n_heads=8, n_kv=2, head_dim=64, page_size=16,
        pages_per_seq=4, max_len=64,
    )
    q = jnp.asarray(rng.standard_normal((b, t_q, 8, 64)), jnp.float32)
    positions = jnp.asarray([[10, 12, 13]], jnp.int32)
    before = pp.fallback_snapshot()
    got = pp.paged_attention_pallas(
        q, k, v, tables, positions, scale=0.125, contiguous_positions=False,
    )
    after = pp.fallback_snapshot()
    verify_keys = [s for s in after if s.startswith("verify:")
                   and after[s] > before.get(s, 0)]
    assert verify_keys
    want = paged_attention_reference(q, k, v, tables, positions, scale=0.125)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_dma_ring_depth_env(monkeypatch):
    """Deeper DMA rings must not change results (slot assignment is a pure
    function of the global block index)."""
    rng = np.random.default_rng(31)
    q, k, v, tables, positions = _random_case(
        rng, b=3, n_heads=8, n_kv=2, head_dim=64, page_size=16,
        pages_per_seq=8, max_len=128,
    )
    scale = 0.125
    want = paged_attention_reference(q, k, v, tables, positions, scale=scale)
    for depth in ("2", "3", "6"):
        monkeypatch.setenv("DYN_DECODE_DMA_DEPTH", depth)
        # The ring depth is resolved at trace time; identical shapes would
        # otherwise reuse the previous depth's compiled program.
        paged_decode_attention.clear_cache()
        got = paged_decode_attention(
            q, k, v, tables, positions, scale=scale, interpret=True,
            num_splits=2,
        )
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
