"""MLA (DeepSeek latent attention): absorbed-vs-naive equivalence, paged
prefill/decode consistency, cache sizing, engine + HTTP integration.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import llama
from dynamo_tpu.models.config import PRESETS
from dynamo_tpu.models.mla import init_mla_params, lay_heads_major, mla_attention, mla_attention_naive, up_project
from dynamo_tpu.ops.rope import rope_frequencies

CFG = PRESETS["test-tiny-mla"]


def _layer_params(seed=0):
    stacked = init_mla_params(CFG, jax.random.PRNGKey(seed), jnp.float32, 1)
    return jax.tree.map(lambda x: x[0], stacked)


def test_absorbed_matches_naive():
    lp = _layer_params()
    rng = np.random.default_rng(0)
    B, T, PS, PAGES = 2, 12, 4, 8
    h = jnp.asarray(rng.standard_normal((B, T, CFG.hidden_size)), jnp.float32) * 0.3
    positions = jnp.tile(jnp.arange(T, dtype=jnp.int32)[None], (B, 1))
    inv_freq = jnp.asarray(rope_frequencies(CFG.qk_rope_head_dim, theta=CFG.rope_theta))

    want = mla_attention_naive(lp, CFG, h, positions, inv_freq)

    c_cache = jnp.zeros((PAGES, PS, CFG.kv_lora_rank), jnp.float32)
    r_cache = jnp.zeros((PAGES, PS, CFG.qk_rope_head_dim), jnp.float32)
    # seq 0 -> pages 1..3, seq 1 -> pages 4..6 (page 0 = null)
    tables = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
    slots = tables[:, :, None] * PS + jnp.arange(PS)[None, None, :]
    slots = slots.reshape(B, -1)[:, :T]
    got, _, _ = mla_attention(lp, CFG, h, positions, c_cache, r_cache, tables, slots, inv_freq)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4, rtol=2e-4)


# -- the per-head up-projections heads-major (ISSUE 41, models/mla.lay_heads_major) ----------

@pytest.mark.parametrize("tokens", [1, 8, 64, 128])
@pytest.mark.parametrize("heads", [4, 32, 64])
def test_up_projections_heads_major_equal_the_published_einsum(heads, tokens):
    """Both absorbed contractions at the published widths (latent 512, head
    128), bf16 operands: the heads-major leaves through ``up_project`` give
    the published ``einsum``'s values after the same bf16 rounding: the same
    products summed in float32 and rounded once, so the two differ only where
    the order of the sum carries a value across a rounding edge (one bf16 step,
    a few values in a thousand on this backend; bit for bit on the chip:
    ``tools/mla_decode_bench.py --side up``)."""
    rng = np.random.default_rng(heads * 1000 + tokens)
    r_kv, d = 512, 128
    published = {name: jnp.asarray(rng.standard_normal((2, r_kv, heads, d)) * r_kv ** -0.5, jnp.bfloat16)
                 for name in ("w_uk", "w_uv")}
    laid = lay_heads_major(published)
    assert sorted(laid) == ["w_uk_h", "w_uv_h"]
    assert laid["w_uk_h"].shape == (2, heads, d, r_kv) and laid["w_uv_h"].shape == (2, heads, r_kv, d)
    at = lambda tree, i: jax.tree.map(lambda a: a[i], tree)  # noqa: E731
    for name, width in (("w_uk", d), ("w_uv", r_kv)):
        x = jnp.asarray(rng.standard_normal((1, tokens, heads, width)), jnp.bfloat16)
        want = up_project(at(published, 1), name, x)
        got = up_project(at(laid, 1), name, x)
        assert got.dtype == want.dtype == jnp.bfloat16 and got.shape == want.shape
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        np.testing.assert_allclose(got, want, rtol=2.0 ** -7, atol=2.0 ** -10)
        assert (got != want).mean() < 0.01


def _layer_pair(cfg, seed: int, gated: bool):
    """One layer's leaves, published and as a runner lays them."""
    stacked = init_mla_params(cfg, jax.random.PRNGKey(seed), jnp.float32, 1)
    if gated:  # Ling's head-wise sigmoid gate on the output, before its projection
        gate = np.random.default_rng(seed).standard_normal((1, cfg.hidden_size, cfg.num_heads))
        stacked["w_out_gate"] = jnp.asarray(gate, jnp.float32) * 0.2
    return tuple(jax.tree.map(lambda x: x[0], tree) for tree in (stacked, lay_heads_major(stacked)))


def _whole_sequences(cfg, tokens: int, rows: int, seed: int):
    """``rows`` sequences of ``tokens`` tokens each on pages of their own, and empty caches."""
    rng = np.random.default_rng(seed)
    ps = 8
    pages_per_row = -(-tokens // ps)
    h = jnp.asarray(rng.standard_normal((rows, tokens, cfg.hidden_size)), jnp.float32) * 0.3
    positions = jnp.tile(jnp.arange(tokens, dtype=jnp.int32)[None], (rows, 1))
    tables = 1 + jnp.arange(rows * pages_per_row, dtype=jnp.int32).reshape(rows, pages_per_row)
    slots = (tables[:, :, None] * ps + jnp.arange(ps)[None, None, :]).reshape(rows, -1)[:, :tokens]
    c_cache = jnp.zeros((rows * pages_per_row + 1, ps, cfg.kv_lora_rank), jnp.float32)
    r_cache = jnp.zeros((rows * pages_per_row + 1, ps, cfg.qk_rope_head_dim), jnp.float32)
    return h, positions, c_cache, r_cache, tables, slots


INV_FREQ = jnp.asarray(rope_frequencies(CFG.qk_rope_head_dim, theta=CFG.rope_theta))


@pytest.mark.parametrize("gated", [False, True], ids=["plain", "gated-output"])
@pytest.mark.parametrize("tokens", [1, 8, 64, 512])
def test_attention_on_the_laid_tree_matches_naive_on_the_published(tokens, gated):
    """``mla_attention`` on the leaves an unsharded runner serves against the
    golden reference on the published leaves, whole sequences (a 512-token
    chunk takes the same heads-major ``einsum``: the form does not turn on the
    token count), and against itself on the published leaves."""
    lp, laid = _layer_pair(CFG, 3 + tokens, gated)
    assert "w_uk" not in laid and "w_uv" not in laid
    batch = _whole_sequences(CFG, tokens, 2, 3 + tokens)
    h, positions = batch[:2]
    got, c_got, r_got = mla_attention(laid, CFG, *batch, INV_FREQ)
    same, c_same, r_same = mla_attention(lp, CFG, *batch, INV_FREQ)
    np.testing.assert_allclose(np.asarray(got), np.asarray(same), atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(c_got), np.asarray(c_same))
    np.testing.assert_array_equal(np.asarray(r_got), np.asarray(r_same))
    if not gated:  # the reference has no gate
        want = mla_attention_naive(lp, CFG, h, positions, INV_FREQ)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-4, rtol=3e-4)


@pytest.mark.parametrize("gated", [False, True], ids=["plain", "gated-output"])
@pytest.mark.parametrize("split", [(3, 1, 8), (8, 2, 4)], ids=lambda s: "split-%d-%d-%d" % s)
def test_split_token_axis_on_the_laid_tree_matches_the_published(split, gated):
    """One token axis of decode slots and chunk slots (``llama.forward``'s
    ``split``): the laid tree against the published one, the same call."""
    nd, nc, tc = split
    t = nd + nc * tc
    lp, laid = _layer_pair(CFG, 17 + nd, gated)
    rng = np.random.default_rng(nd)
    h = jnp.asarray(rng.standard_normal((1, t, CFG.hidden_size)), jnp.float32) * 0.3
    ps, pages_per_row = 8, 2
    c_cache = jnp.asarray(rng.standard_normal(((nd + nc) * pages_per_row + 1, ps, CFG.kv_lora_rank)), jnp.float32) * 0.2
    r_cache = jnp.asarray(rng.standard_normal(((nd + nc) * pages_per_row + 1, ps, CFG.qk_rope_head_dim)), jnp.float32) * 0.2
    tables = 1 + jnp.arange((nd + nc) * pages_per_row, dtype=jnp.int32).reshape(nd + nc, pages_per_row)
    # a decode slot's one token at position 5; a chunk slot's tc tokens from position 2
    positions = jnp.concatenate([jnp.full((nd,), 5, jnp.int32), jnp.tile(2 + jnp.arange(tc, dtype=jnp.int32), nc)])[None]
    row_of = jnp.concatenate([jnp.arange(nd), nd + jnp.repeat(jnp.arange(nc), tc)])
    slots = (tables[row_of, positions[0] // ps] * ps + positions[0] % ps)[None]
    kw = dict(split=split, impl="reference")
    got, c_got, _ = mla_attention(laid, CFG, h, positions, c_cache, r_cache, tables, slots, INV_FREQ, **kw)
    want, c_want, _ = mla_attention(lp, CFG, h, positions, c_cache, r_cache, tables, slots, INV_FREQ, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(c_got), np.asarray(c_want))


def test_lay_heads_major_reaches_every_latent_stack_and_nothing_else():
    """The plain stack, a shortcut layer's two sublayers and a hybrid's
    ``mla_layers``; a GQA tree comes back as it went in."""
    for preset, at in (("test-tiny-mla", lambda p: [p["layers"]]),
                       ("test-tiny-scmoe", lambda p: [p["layers"]["sub0"], p["layers"]["sub1"]]),
                       ("test-tiny-hybrid", lambda p: [p["mla_layers"]])):
        cfg = PRESETS[preset]
        params = llama.init_params(cfg, 0)
        laid = lay_heads_major(params)
        for before, after in zip(at(params), at(laid)):
            assert "w_uk" in before and "w_uk" not in after and "w_uv" not in after
            l, r, h, dn = before["w_uk"].shape
            assert after["w_uk_h"].shape == (l, h, dn, r) and after["w_uv_h"].shape == (l, h, r, before["w_uv"].shape[-1])
            np.testing.assert_array_equal(np.asarray(after["w_uk_h"][0, 1]), np.asarray(before["w_uk"][0, :, 1, :]).T)
            np.testing.assert_array_equal(np.asarray(after["w_uv_h"][0, 1]), np.asarray(before["w_uv"][0, :, 1, :]))
            assert set(before) - set(after) == {"w_uk", "w_uv"} and set(after) - set(before) == {"w_uk_h", "w_uv_h"}
    gqa = llama.init_params(PRESETS["test-tiny"], 0)
    same = lay_heads_major(gqa)
    assert jax.tree.structure(same) == jax.tree.structure(gqa)
    assert all(a is b for a, b in zip(jax.tree.leaves(same), jax.tree.leaves(gqa)))


@pytest.mark.parametrize("sharded", [False, True], ids=["one-device", "tp-mesh"])
def test_runner_lays_its_tree_unless_it_has_a_mesh(sharded):
    """The lay-out and the form go together: an unsharded runner serves the
    heads-major leaves and keeps no published ones; a runner with a mesh lays
    nothing (the leaves shard by head as published, and the ring path and the
    golden reference never see a laid tree). The caller's tree is not touched."""
    from dynamo_tpu.engine.runner import ModelRunner

    params = llama.init_params(CFG, 2)
    mesh = None
    if sharded:
        from dynamo_tpu.parallel.mesh import MeshPlan, make_mesh

        mesh = make_mesh(MeshPlan(tp=2))
    runner = ModelRunner(CFG, params, num_pages=8, page_size=4, max_batch_size=2, mesh=mesh)
    served = runner.params["layers"]
    assert ("w_uk_h" in served, "w_uk" in served) == ((False, True) if sharded else (True, False))
    assert "w_uk" in params["layers"] and "w_uk_h" not in params["layers"]


def test_paged_decode_matches_prefill():
    """Prefill all-at-once vs prefill + one-token decode steps: same logits."""
    cfg = CFG
    params = llama.init_params(cfg, 1)
    PAGES, PS = 8, 4
    T = 10
    rng = np.random.default_rng(1)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, T)), jnp.int32)
    positions = jnp.arange(T, dtype=jnp.int32)[None]
    tables = jnp.asarray([[1, 2, 3]], jnp.int32)
    slots_full = (tables[:, :, None] * PS + jnp.arange(PS)[None, None, :]).reshape(1, -1)[:, :T]
    last = jnp.asarray([T - 1], jnp.int32)

    kc, vc = llama.init_kv_cache(cfg, PAGES, PS)
    logits_full, _, _ = llama.forward(
        params, cfg, tokens, positions, kc, vc, tables, slots_full, last
    )

    # incremental: prefill T-1 then decode the last token
    kc2, vc2 = llama.init_kv_cache(cfg, PAGES, PS)
    _, kc2, vc2 = llama.forward(
        params, cfg, tokens[:, : T - 1], positions[:, : T - 1], kc2, vc2,
        tables, slots_full[:, : T - 1], jnp.asarray([T - 2], jnp.int32),
    )
    logits_step, _, _ = llama.forward(
        params, cfg, tokens[:, T - 1 :], positions[:, T - 1 :], kc2, vc2,
        tables, slots_full[:, T - 1 :], jnp.asarray([0], jnp.int32),
    )
    np.testing.assert_allclose(
        np.asarray(logits_full), np.asarray(logits_step), atol=2e-3, rtol=2e-3
    )


def test_mla_cache_is_small():
    v3 = PRESETS["deepseek-v3-ep"]
    assert v3.attn_type == "mla"
    # latent(512) + lane-padded rope(128) per token per layer vs the GQA
    # stand-in (rope stream padded to one 128-lane tile for Mosaic DMA).
    assert v3.kv_bytes_per_token() == v3.num_layers * (512 + 128) * 2
    gqa_equiv = 2 * v3.num_layers * v3.kv_dim * 2
    assert v3.kv_bytes_per_token() * 25 < gqa_equiv  # still ~25x smaller

    kc, vc = llama.init_kv_cache(CFG, 4, 4)
    assert kc.shape == (CFG.num_layers, 4, 4, CFG.kv_lora_rank)
    assert vc.shape == (CFG.num_layers, 4, 4, max(CFG.qk_rope_head_dim, 128))


def test_mla_forward_on_tp_mesh():
    """MLA under GSPMD: tp-sharded heads produce single-device logits."""
    from dynamo_tpu.parallel.mesh import MeshPlan, make_mesh
    from dynamo_tpu.parallel.sharding import param_shardings

    cfg = CFG
    params = llama.init_params(cfg, 5)
    logits_ref = _tiny_forward(params, cfg)

    mesh = make_mesh(MeshPlan(tp=4))
    sh = param_shardings(mesh, params)
    placed = jax.tree.map(lambda x, s: jax.device_put(x, s), params, sh)
    logits_tp = _tiny_forward(placed, cfg)
    np.testing.assert_allclose(
        np.asarray(logits_ref), np.asarray(logits_tp), atol=2e-3, rtol=2e-3
    )


def _tiny_forward(params, cfg):
    PAGES, PS, T = 8, 4, 8
    tokens = jnp.arange(T, dtype=jnp.int32)[None] % cfg.vocab_size
    positions = jnp.arange(T, dtype=jnp.int32)[None]
    tables = jnp.asarray([[1, 2]], jnp.int32)
    slots = (tables[:, :, None] * PS + jnp.arange(PS)[None, None, :]).reshape(1, -1)[:, :T]
    kc, vc = llama.init_kv_cache(cfg, PAGES, PS)
    logits, _, _ = llama.forward(
        params, cfg, tokens, positions, kc, vc, tables, slots,
        jnp.asarray([T - 1], jnp.int32),
    )
    return logits


def test_mla_checkpoint_roundtrip(tmp_path):
    """params -> HF deepseek_v3 checkpoint (kv_b_proj packing) -> params."""
    from dynamo_tpu.models.loader import load_model, save_params

    params = llama.init_params(CFG, 7)
    save_params(tmp_path, CFG, params)
    cfg2, loaded = load_model(tmp_path, name=CFG.name, dtype=CFG.dtype)
    assert cfg2.attn_type == "mla"
    assert cfg2.kv_lora_rank == CFG.kv_lora_rank
    assert cfg2.q_lora_rank == CFG.q_lora_rank
    assert cfg2.qk_rope_head_dim == CFG.qk_rope_head_dim

    flat_a = jax.tree.leaves(jax.tree.map(np.asarray, params))
    flat_b = jax.tree.leaves(jax.tree.map(np.asarray, loaded))
    assert len(flat_a) == len(flat_b)
    for a, b in zip(flat_a, flat_b):
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32), atol=0, rtol=0)


async def test_mla_serving_end_to_end():
    import aiohttp

    from dynamo_tpu.launch import run_local

    handles = await run_local("test-tiny-mla", port=0, num_pages=64, max_batch_size=4)
    try:
        async with aiohttp.ClientSession() as s:
            r = await s.post(
                f"http://127.0.0.1:{handles['port']}/v1/completions",
                json={"model": "test-tiny-mla", "prompt": "hello", "max_tokens": 6},
            )
            doc = await r.json()
            assert r.status == 200, doc
            assert doc["usage"]["completion_tokens"] == 6
    finally:
        await handles["http"].stop()
        await handles["watcher"].close()
        for svc in handles["services"]:
            await svc.close()
        await handles["runtime"].close()
