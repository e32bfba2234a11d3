"""The int8 grouped-matmul kernel (ops/pallas_moe.py) and the predicate that
routes inputs to it (parallel/moe.experts_path).

Interpret mode on the CPU, as the other kernels' tests: the kernel against
today's ``_widen`` + ``ragged_dot`` on the same int8 leaves with a float32
reference beside both (the kernel may be no farther from it), the group
layouts that stress the visit lists, the stacked-weights form the model's scan
uses, and the inputs that must keep the XLA formulations bit for bit.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import llama
from dynamo_tpu.models.config import PRESETS
from dynamo_tpu.models.quant import quantize_leaf, quantize_leaf_int4, quantize_params
from dynamo_tpu.ops.pallas_moe import expert_ffn_int8, group_metadata, grouped_matmul_int8, row_tile
from dynamo_tpu.parallel import moe
from dynamo_tpu.parallel.mesh import MeshPlan, make_mesh

E, K_TOP, D = 64, 8, 256  # OLMoE's routing at a narrow hidden size (the contraction tiles the same way)


def _leaves(width: int, seed: int = 0, e: int = E, d: int = D) -> dict:
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    w = lambda k, shape: jax.random.normal(k, shape, jnp.float32) * shape[-2] ** -0.5  # noqa: E731
    return {
        "router": jax.random.normal(ks[0], (d, e), jnp.bfloat16),
        "w_gate": quantize_leaf(w(ks[1], (e, d, width))),
        "w_up": quantize_leaf(w(ks[2], (e, d, width))),
        "w_down": quantize_leaf(w(ks[3], (e, width, d))),
    }


def _f32(leaf: dict) -> jnp.ndarray:
    return leaf["qw"].astype(jnp.float32) * leaf["scale"].astype(jnp.float32)[..., None, :]


def _ffn_f32(x, lp, sizes):
    """The expert FFN on the dequantized weights, in float32 throughout."""
    x = x.astype(jnp.float32)
    gate = jax.nn.silu(jax.lax.ragged_dot(x, _f32(lp["w_gate"]), sizes))
    return jax.lax.ragged_dot(gate * jax.lax.ragged_dot(x, _f32(lp["w_up"]), sizes), _f32(lp["w_down"]), sizes)


def _ffn_widened(x, lp, sizes):
    """Today's formulation, as ``moe_mlp_dropless`` spells it."""
    w_gate, w_up, w_down = moe._widen(lp, x.dtype)
    gate = jax.nn.silu(jax.lax.ragged_dot(x, w_gate, sizes))
    return jax.lax.ragged_dot(gate * jax.lax.ragged_dot(x, w_up, sizes), w_down, sizes)


def _sorted_copies(copies: int, seed: int):
    """``copies // 8`` tokens, each on 8 distinct experts of 64, sorted by expert."""
    rng = np.random.default_rng(seed)
    n = copies // K_TOP
    flat = np.stack([rng.permutation(E)[:K_TOP] for _ in range(n)]).reshape(-1)
    x = rng.standard_normal((n, D)).astype(np.float32)
    xk = np.repeat(x, K_TOP, axis=0)[np.argsort(flat, kind="stable")]
    return jnp.asarray(xk, jnp.bfloat16), jnp.asarray(np.bincount(flat, minlength=E), jnp.int32)


def _rel(a, ref) -> float:
    a, ref = np.asarray(a, np.float32), np.asarray(ref, np.float32)
    return float(np.linalg.norm(a - ref) / np.linalg.norm(ref))


@pytest.mark.parametrize("width", [1024, 1408])
@pytest.mark.parametrize("copies", [32, 512, 2048])
def test_kernel_no_farther_from_float32_than_the_widened_path(copies, width):
    lp = _leaves(width, seed=width)
    xk, sizes = _sorted_copies(copies, seed=copies)
    if copies == 32:
        assert int((sizes == 0).sum()) >= E - 32  # experts with no rows are the common case at decode
    ref = _ffn_f32(xk, lp, sizes)
    fused = expert_ffn_int8(xk, lp["w_gate"], lp["w_up"], lp["w_down"], sizes, interpret=True)
    widened = _ffn_widened(xk, lp, sizes)
    assert fused.shape == widened.shape and fused.dtype == widened.dtype
    err_fused, err_widened = _rel(fused, ref), _rel(widened, ref)
    assert err_fused <= err_widened, (err_fused, err_widened)
    assert err_fused < 4e-3  # one bf16 rounding of the hidden and one of the output


@pytest.mark.parametrize("name,sizes", [
    ("one_expert_has_every_copy", [0] * 17 + [512] + [0] * 46),
    ("boundary_inside_a_row_tile", [100, 0, 0, 28, 1, 0, 127, 200, 56] + [0] * 55),
    ("every_expert_one_tile_each", [8] * 64),
    ("first_and_last_expert_only", [300] + [0] * 62 + [212]),
])
def test_group_layouts(name, sizes):
    lp = _leaves(1024, seed=3)
    sizes = jnp.asarray(sizes, jnp.int32)
    m = int(sizes.sum())
    xk = jax.random.normal(jax.random.PRNGKey(len(name)), (m, D), jnp.bfloat16)
    fused = expert_ffn_int8(xk, lp["w_gate"], lp["w_up"], lp["w_down"], sizes, interpret=True)
    ref = _ffn_f32(xk, lp, sizes)
    assert _rel(fused, ref) <= _rel(_ffn_widened(xk, lp, sizes), ref)
    # No row may hold another group's product: compare row by row.
    worst = np.abs(np.asarray(fused, np.float32) - np.asarray(ref)).max(axis=1) / np.abs(np.asarray(ref)).max()
    assert worst.max() < 2e-2, (name, int(worst.argmax()))


@pytest.mark.parametrize("copies", [24, 40, 200, 1032])
def test_rows_that_do_not_fill_a_tile_are_padded_and_cut(copies):
    """24 copies pad to a 32-row tile, 200 to four tiles of 64, 1032 to nine of 128."""
    lp = _leaves(1024, seed=5)
    rng = np.random.default_rng(copies)
    flat = np.sort(rng.integers(0, E, copies))
    sizes = jnp.asarray(np.bincount(flat, minlength=E), jnp.int32)
    xk = jnp.asarray(rng.standard_normal((copies, D)), jnp.bfloat16)
    fused = expert_ffn_int8(xk, lp["w_gate"], lp["w_up"], lp["w_down"], sizes, interpret=True)
    assert fused.shape == (copies, D)
    assert bool(jnp.isfinite(fused.astype(jnp.float32)).all())
    assert _rel(fused, _ffn_f32(xk, lp, sizes)) < 4e-3


def test_identical_padding_rows_land_on_the_same_experts(monkeypatch):
    """The runner pads a batch with copies of one row: they route alike, so a
    few experts hold many rows and most hold none."""
    lp = _leaves(1024, seed=7)
    x = jnp.tile(jax.random.normal(jax.random.PRNGKey(1), (1, D), jnp.bfloat16), (16, 1))
    without = _dropless(lp, x)
    monkeypatch.setenv("DYNAMO_PALLAS_INTERPRET", "1")  # interpret mode stands in for the TPU
    with_kernel = _dropless(lp, x)
    np.testing.assert_allclose(
        np.asarray(with_kernel, np.float32), np.asarray(without, np.float32), rtol=0.05, atol=0.05)
    assert np.allclose(np.asarray(with_kernel[0], np.float32), np.asarray(with_kernel[-1], np.float32))


def test_visit_lists_skip_empty_groups_and_cover_every_row():
    sizes = jnp.asarray([100, 0, 0, 20, 1, 0, 135], jnp.int32)
    offsets, gid, tile, n = group_metadata(sizes, 256, 128)
    n = int(n[0])
    assert n == 5  # group 0 in tile 0; 3, 4 in tile 0; 6 in tiles 0 and 1
    assert gid[:n].tolist() == [0, 3, 4, 6, 6] and tile[:n].tolist() == [0, 0, 0, 0, 1]
    assert gid[n:].tolist() == [6] * (gid.shape[0] - n) and tile[n:].tolist() == [1] * (gid.shape[0] - n)
    assert offsets.tolist() == [0, 100, 100, 100, 120, 121, 121, 256]
    assert int(group_metadata(jnp.zeros(7, jnp.int32), 128, 128)[3][0]) == 0


def test_stacked_weights_with_a_layer_index_equal_the_slice():
    layers = [_leaves(1024, seed=s, e=8) for s in (11, 12, 13)]
    stack = jax.tree.map(lambda *xs: jnp.stack(xs), *layers)
    sizes = jnp.asarray([3, 0, 17, 0, 0, 9, 2, 1], jnp.int32)
    xk = jax.random.normal(jax.random.PRNGKey(2), (32, D), jnp.bfloat16)
    for li, lp in enumerate(layers):
        got = grouped_matmul_int8(
            xk, (stack["w_gate"]["qw"], stack["w_up"]["qw"]), (lp["w_gate"]["scale"], lp["w_up"]["scale"]),
            sizes, jnp.int32(li), act="silu_mul", interpret=True)
        want = grouped_matmul_int8(
            xk, (lp["w_gate"]["qw"], lp["w_up"]["qw"]), (lp["w_gate"]["scale"], lp["w_up"]["scale"]),
            sizes, act="silu_mul", interpret=True)
        np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))


def test_tile_choices():
    assert [row_tile(m) for m in (8, 32, 40, 128, 512, 2048, 32768)] == [16, 32, 48, 128, 64, 128, 128]


# -- the predicate -----------------------------------------------------------


def _dropless(lp, x, mesh=None):
    return moe.moe_mlp_dropless(lp, x, num_experts_per_token=K_TOP, routing={"norm_topk": False}, mesh=mesh)


def _legacy_dropless(lp, x):
    """``moe_mlp_dropless`` as it stood before the kernel, line for line."""
    n, d = x.shape
    weights, topi = moe.route_tokens(lp, x, k=K_TOP, norm_topk=False)
    flat_e = topi.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    xk = jnp.repeat(x, K_TOP, axis=0)[order]
    group_sizes = jnp.bincount(flat_e, length=lp["router"].shape[-1]).astype(jnp.int32)
    down = _ffn_widened(xk, lp, group_sizes)
    rows = jnp.zeros_like(down).at[order].set(down)
    out = (rows.astype(jnp.float32) * weights.reshape(-1)[:, None]).reshape(n, K_TOP, d).sum(axis=1)
    return out.astype(x.dtype)


def _bf16_leaves(lp):
    return {**lp, **{n: _f32(lp[n]).astype(jnp.bfloat16) for n in moe._EXPERT_LEAVES}}


def _int4_leaves(lp):
    return {**lp, **{n: quantize_leaf_int4(_f32(lp[n])) for n in moe._EXPERT_LEAVES}}


def test_int8_leaves_take_the_kernel_where_it_runs(monkeypatch):
    lp = _leaves(1024, seed=9)
    x = jax.random.normal(jax.random.PRNGKey(3), (4, D), jnp.bfloat16)
    assert moe.experts_path(lp) == "widened"  # the CPU backend
    monkeypatch.setenv("DYNAMO_PALLAS_INTERPRET", "1")
    assert moe.experts_path(lp) == "fused"
    assert moe.experts_path(jax.tree.map(lambda a: jnp.stack([a, a]), lp)) == "fused"  # stacked layers read alike
    fn = lambda lp, x: moe.moe_mlp_dropless(lp, x, num_experts_per_token=K_TOP)  # noqa: E731
    assert "pallas_call" in str(jax.make_jaxpr(fn)(lp, x))
    got = fn(lp, x)
    monkeypatch.delenv("DYNAMO_PALLAS_INTERPRET")
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(fn(lp, x), np.float32), rtol=0.03, atol=0.03)


@pytest.mark.parametrize("case", ["cpu_int8", "bf16", "int4", "ep2", "tp2", "odd_width", "capacity_env", "dense_env"])
def test_other_inputs_keep_the_xla_path_bit_for_bit(case, monkeypatch):
    """Everything the predicate turns away lowers with no kernel and computes
    what it computed before, even where the kernel could run (the platform
    check is steered to "yes" for every case but the CPU one)."""
    lp, mesh = _leaves(1024, seed=21), None
    if case != "cpu_int8":
        monkeypatch.setattr(moe, "_kernel_platform", lambda: True)
    if case == "bf16":
        lp = _bf16_leaves(lp)
    elif case == "int4":
        lp = _int4_leaves(lp)
    elif case in ("ep2", "tp2"):
        axis = case[:2]
        mesh = make_mesh(MeshPlan(**{axis: 2}), jax.devices()[:2])
    elif case == "odd_width":
        lp = _leaves(1000, seed=21)
    elif case.endswith("_env"):
        monkeypatch.setenv("DYNAMO_MOE_DISPATCH", case.split("_")[0])
    assert moe.experts_path(lp, mesh=mesh) == "widened"
    assert moe.split_expert_stack(jax.tree.map(lambda a: a[None], lp), mesh=mesh)[1] is None
    x = jax.random.normal(jax.random.PRNGKey(4), (6, D), jnp.bfloat16)
    fn = lambda lp, x: _dropless(lp, x, mesh)  # noqa: E731
    text = str(jax.make_jaxpr(fn)(lp, x)) + jax.jit(fn).lower(lp, x).as_text()
    assert "pallas_call" not in text and "tpu_custom_call" not in text
    np.testing.assert_array_equal(
        np.asarray(fn(lp, x), np.float32), np.asarray(_legacy_dropless(lp, x), np.float32))


def test_dense_model_has_no_moe_path():
    dense = llama.init_params(PRESETS["test-tiny"], 0)
    assert moe.experts_path(dense["layers"]) == ""


# -- the model step ----------------------------------------------------------


def _forward(cfg, params, *, b=2, t=8, ps=4):
    tokens = jnp.asarray(np.random.default_rng(3).integers(0, cfg.vocab_size, (b, t)), jnp.int32)
    positions = jnp.tile(jnp.arange(t, dtype=jnp.int32)[None], (b, 1))
    tables = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    slots = jnp.take_along_axis(tables, positions // ps, axis=1) * ps + positions % ps
    kc, vc = llama.init_kv_cache(cfg, num_pages=8, page_size=ps)
    fwd = jax.jit(lambda p: llama.forward(
        p, cfg, tokens, positions, kc, vc, tables, slots, jnp.full((b,), t - 1, jnp.int32), attn_impl="reference")[0])
    return str(jax.make_jaxpr(fwd)(params)), np.asarray(fwd(params))


@pytest.mark.parametrize("first_k_dense", [0, 1], ids=["all_moe", "dense_first_layer"])
def test_forward_scans_the_stacked_experts_by_layer_index(first_k_dense, monkeypatch):
    """The whole forward with the kernel inside the layer scan (stacked int8
    experts, layer index from the carry, after a dense first layer too)
    against the same weights on the widened path."""
    cfg = dataclasses.replace(
        PRESETS["test-tiny-moe"], hidden_size=128, moe_intermediate_size=128, num_layers=3,
        first_k_dense=first_k_dense, dtype="bfloat16")
    params = quantize_params(llama.init_params(cfg, 0), mode="int8")
    jaxpr, want = _forward(cfg, params)
    assert "pallas_call" not in jaxpr
    monkeypatch.setenv("DYNAMO_PALLAS_INTERPRET", "1")
    jaxpr, got = _forward(cfg, params)
    assert "pallas_call" in jaxpr
    # Layers differ: a wrong index would read another layer's experts.
    np.testing.assert_allclose(got, want, rtol=0.05, atol=0.05)
    assert np.abs(got - want).max() < 0.25 * np.abs(want).std()
