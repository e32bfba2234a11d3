"""MLA decode Pallas kernel (ops/pallas_mla.py) vs the gather formulation.

The kernel is the single-chip decode hot path for DeepSeek-family MLA
models; the gather formulation (models/mla.py) is its bit-level reference.
Runs in Pallas interpret mode on CPU.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.ops.pallas_mla import mla_decode_supported, mla_paged_decode

# Kernel-geometry MLA config: r_kv lane-aligned (128), dr 64 — the V3 shape
# ratios at test scale.
CFG = ModelConfig(
    name="test-mla-kernel", vocab_size=256, hidden_size=128, num_layers=2,
    num_heads=4, num_kv_heads=4, head_dim=32, intermediate_size=128,
    rope_theta=10000.0, max_position=512, tie_embeddings=True, dtype="float32",
    attn_type="mla", q_lora_rank=0, kv_lora_rank=128,
    qk_nope_head_dim=32, qk_rope_head_dim=64, v_head_dim=32,
)


def _gather_reference(q_lat, q_rope, c_cache, r_cache, tables, positions, scale):
    """The gather formulation (same math as models/mla.py) for
    [B, T, H, r] queries, each token masked to its own horizon. Unused table
    entries (-1) read the row's first page: masked, and finite."""
    b, pages_per_seq = tables.shape
    s = pages_per_seq * c_cache.shape[1]
    flat = jnp.where(tables < 0, tables[:, :1], tables).reshape(-1)
    c_pages = c_cache[flat].reshape(b, s, -1)
    r_pages = r_cache[flat].reshape(b, s, -1)
    logits = (
        jnp.einsum("bthr,bsr->bths", q_lat, c_pages)
        + jnp.einsum("bthr,bsr->bths", q_rope, r_pages)
    ) * scale
    key_pos = jnp.arange(s)[None, None, None, :]
    logits = jnp.where(key_pos <= positions[:, :, None, None], logits, -1e30)
    return jnp.einsum("bths,bsr->bthr", jax.nn.softmax(logits, axis=-1), c_pages)


def test_supported_predicate():
    assert mla_decode_supported(128, 128)
    assert mla_decode_supported(512, 128)
    assert not mla_decode_supported(96, 128)  # latent off the lane grid
    assert not mla_decode_supported(512, 64)  # unpadded rope stream


def test_mla_kernel_matches_gather_formulation():
    rng = np.random.default_rng(0)
    b, page_size, pages_per_seq = 4, 8, 3
    r_kv, dr = CFG.kv_lora_rank, CFG.qk_rope_head_dim
    n_heads = CFG.num_heads
    num_pages = 1 + b * pages_per_seq

    c_cache = jnp.asarray(rng.standard_normal((num_pages, page_size, r_kv)), jnp.float32)
    r_cache = jnp.asarray(rng.standard_normal((num_pages, page_size, dr)), jnp.float32)
    tables = jnp.asarray(
        [[1 + i * pages_per_seq + j for j in range(pages_per_seq)] for i in range(b)],
        jnp.int32,
    )
    # Ragged real lengths per sequence (tail block exercise).
    lengths = [5, 8, 17, 24]
    positions = jnp.asarray([[n - 1] for n in lengths], jnp.int32)
    q_lat = jnp.asarray(rng.standard_normal((b, n_heads, r_kv)), jnp.float32)
    q_rope = jnp.asarray(rng.standard_normal((b, n_heads, dr)), jnp.float32)
    scale = (CFG.qk_nope_head_dim + dr) ** -0.5

    got = mla_paged_decode(
        q_lat, q_rope, c_cache, r_cache, tables, positions,
        scale=scale, interpret=True,
    )

    want = _gather_reference(q_lat[:, None], q_rope[:, None], c_cache, r_cache, tables, positions, scale)[:, 0]

    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-2, atol=2e-2)


#: Page 8 and blocks pinned to 6 pages: 48 tokens a block, rows of up to 14 pages.
TAIL_PAGE, TAIL_BLOCK_PAGES, TAIL_PAGES_PER_SEQ = 8, 6, 14
TAIL_LENGTHS = {
    "one_token": 1,
    "page_less_one": 7,
    "exactly_a_block": 48,
    "block_plus_one": 49,
    **{f"tail_of_{k}_pages": 48 + 8 * k - 3 for k in range(1, 7)},
}


@pytest.mark.parametrize("num_splits", [1, 2], ids=["splits1", "splits2"])
@pytest.mark.parametrize("t_q", [1, 3], ids=["decode", "verify3_gappy"])
@pytest.mark.parametrize("length", TAIL_LENGTHS.values(), ids=TAIL_LENGTHS.keys())
def test_tail_block_moves_and_contracts_only_held_pages(monkeypatch, length, t_q, num_splits):
    """A row's tail block copies the pages the row holds and nothing else,
    and no ring row that no copy wrote reaches a product: the interpreter
    hands out NaN for uninitialised VMEM, every pool page outside the rows'
    tables is NaN, and unused table entries are -1. Row 0 is short, so the
    first ring slots hold mostly unwritten rows when the later rows' tails
    land in them."""
    import dynamo_tpu.ops.pallas_mla as pm
    from jax.experimental.pallas import tpu as pltpu

    monkeypatch.setattr(pm, "_pages_per_block", lambda pps, *a: min(pps, TAIL_BLOCK_PAGES))
    rng = np.random.default_rng(length * 7 + t_q)
    page, pages_per_seq = TAIL_PAGE, TAIL_PAGES_PER_SEQ
    r_kv, dr, n_heads = CFG.kv_lora_rank, CFG.qk_rope_head_dim, CFG.num_heads
    lengths = [3, length, 100, length]
    b = len(lengths)
    num_pages = 2 + b * pages_per_seq

    tables = np.full((b, pages_per_seq), -1, np.int32)
    held = np.zeros(num_pages, bool)
    for i, n in enumerate(lengths):
        pages = -(-n // page)
        tables[i, :pages] = 1 + i * pages_per_seq + np.arange(pages)
        held[tables[i, :pages]] = True
    c_cache = np.where(held[:, None, None], rng.standard_normal((num_pages, page, r_kv)), np.nan)
    r_cache = np.where(held[:, None, None], rng.standard_normal((num_pages, page, dr)), np.nan)
    # Gappy, unordered query positions; the walk covers the farthest.
    offsets = {1: [1], 3: [6, 1, 3]}[t_q]
    positions = np.asarray([[max(n - o, 0) for o in offsets] for n in lengths], np.int32)
    q_lat = jnp.asarray(rng.standard_normal((b, t_q, n_heads, r_kv)), jnp.float32)
    q_rope = jnp.asarray(rng.standard_normal((b, t_q, n_heads, dr)), jnp.float32)
    operands = (
        q_lat, q_rope, jnp.asarray(c_cache, jnp.float32), jnp.asarray(r_cache, jnp.float32),
        jnp.asarray(tables), jnp.asarray(positions),
    )
    scale = (CFG.qk_nope_head_dim + dr) ** -0.5

    # The reference first and to its end: the TPU interpreter's callbacks run
    # JAX operations of their own, and deadlock against a dispatch from here.
    want = np.asarray(_gather_reference(*operands, scale))
    got = np.asarray(mla_paged_decode(
        *operands, scale=scale, num_splits=num_splits,
        interpret=pltpu.InterpretParams(uninitialized_memory="nan"),
    ))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


def test_full_mla_forward_kernel_vs_gather(monkeypatch):
    """End-to-end decode step through llama.forward: attn_impl="pallas"
    (kernel, interpret) must match attn_impl="reference" (gather)."""
    monkeypatch.setenv("DYNAMO_PALLAS_INTERPRET", "1")
    params = llama.init_params(CFG, 0)
    page_size, num_pages = 8, 16
    b = 2
    k_cache, v_cache = llama.init_kv_cache(CFG, num_pages, page_size)
    tables = jnp.asarray([[1, 2], [3, 4]], jnp.int32)

    # Prefill 8 tokens (gather path: T>1), then one decode step each way.
    t = 8
    tokens = jnp.asarray(np.arange(b * t).reshape(b, t) % CFG.vocab_size, jnp.int32)
    positions = jnp.tile(jnp.arange(t, dtype=jnp.int32)[None], (b, 1))
    slots = jnp.take_along_axis(tables, positions // page_size, axis=1) * page_size + positions % page_size
    last = jnp.full((b,), t - 1, jnp.int32)
    _, k_cache, v_cache = llama.forward(
        params, CFG, tokens, positions, k_cache, v_cache, tables, slots, last,
        attn_impl="reference",
    )

    def decode(impl):
        tok = jnp.asarray([[7], [9]], jnp.int32)
        pos = jnp.asarray([[t], [t]], jnp.int32)
        slot = jnp.take_along_axis(tables, pos // page_size, axis=1) * page_size + pos % page_size
        logits, _, _ = llama.forward(
            params, CFG, tok, pos, k_cache, v_cache, tables, slot,
            jnp.zeros((b,), jnp.int32), attn_impl=impl,
        )
        return np.asarray(logits)

    np.testing.assert_allclose(
        decode("pallas"), decode("reference"), rtol=2e-2, atol=2e-2
    )


def test_mla_kernel_under_tp_mesh(monkeypatch):
    """MLA decode kernel via shard_map on a (dp x tp) mesh: query heads
    shard, the latent cache replicates (MQA), output matches the
    single-device gather formulation."""
    from dynamo_tpu.ops.pallas_mla import mla_paged_decode_sharded
    from dynamo_tpu.parallel.mesh import MeshPlan, make_mesh

    rng = np.random.default_rng(2)
    b, page_size, pages_per_seq = 4, 8, 3
    r_kv, dr = CFG.kv_lora_rank, CFG.qk_rope_head_dim
    n_heads = CFG.num_heads  # 4: splits over tp=2
    num_pages = 1 + b * pages_per_seq
    c_cache = jnp.asarray(rng.standard_normal((num_pages, page_size, r_kv)), jnp.float32)
    r_cache = jnp.asarray(rng.standard_normal((num_pages, page_size, dr)), jnp.float32)
    tables = jnp.asarray(
        [[1 + i * pages_per_seq + j for j in range(pages_per_seq)] for i in range(b)],
        jnp.int32,
    )
    positions = jnp.asarray([[5], [11], [17], [23]], jnp.int32)
    q_lat = jnp.asarray(rng.standard_normal((b, n_heads, r_kv)), jnp.float32)
    q_rope = jnp.asarray(rng.standard_normal((b, n_heads, dr)), jnp.float32)
    scale = (CFG.qk_nope_head_dim + dr) ** -0.5

    mesh = make_mesh(MeshPlan(dp=2, tp=2), jax.devices()[:4])
    got = mla_paged_decode_sharded(
        q_lat, q_rope, c_cache, r_cache, tables, positions,
        mesh=mesh, scale=scale, interpret=True,
    )

    want = _gather_reference(q_lat[:, None], q_rope[:, None], c_cache, r_cache, tables, positions, scale)[:, 0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-2, atol=2e-2)
