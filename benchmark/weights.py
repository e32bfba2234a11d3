"""The served weights, made by the benchmark from ``--seed``.

One jitted program builds the whole tree on the device, directly in the form
it is served in (int8 matmul leaves with a bf16 scale per output channel,
bf16 elsewhere): no bf16 or float32 copy of the model ever exists. The tree's
*shape* is the program's (``llama.init_params`` under ``jax.eval_shape``);
every value comes from here, so the plain reference reads nothing the program
has made.
"""

from __future__ import annotations

import math

#: Leaves the program serves quantized (``dynamo_tpu/models/quant.py``
#: ``_MATMUL_LEAVES``; a test holds the two lists equal).
MATMUL_LEAVES = frozenset({
    "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
    "w_shared_gate", "w_shared_up", "w_shared_down", "lm_head",
    "w_q_a", "w_q_b", "w_q", "w_kv_a", "wo_mla",
})
#: Largest RNG transient, in elements: larger leaves are drawn slice by slice.
MAX_DRAW = 2**27


def tree_shapes(cfg):
    import jax

    from dynamo_tpu.models import llama

    return jax.eval_shape(lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))


def _fan_in(name: str, shape) -> int:
    # 4-D plain leaves are MLA's per-head up-projections [L, r_kv, H, d]:
    # the contraction runs over axis 1.
    return shape[1] if len(shape) == 4 and name not in MATMUL_LEAVES else shape[-2]


def make_weights(cfg, seed: int, *, quant: str = "int8"):
    """The weight tree for ``cfg`` from ``seed``; ``quant`` is "int8" or ""
    (plain, in the configuration's dtype: the CPU tests' tiny models)."""
    import jax
    import jax.numpy as jnp

    shapes = tree_shapes(cfg)

    def draw_int8(key, shape):
        def one(k, shp):
            bits = jax.random.bits(k, shp, jnp.uint8)
            return jnp.maximum(jax.lax.bitcast_convert_type(bits, jnp.int8), jnp.int8(-127))

        if len(shape) >= 3 and math.prod(shape) > MAX_DRAW:
            return jax.lax.map(lambda k: draw_int8(k, shape[1:]), jax.random.split(key, shape[0]))
        return one(key, shape)

    def build(seed_arr):
        root = jax.random.fold_in(jax.random.PRNGKey(0), seed_arr)
        idx = [0]

        def walk(tree, name):
            if isinstance(tree, dict):
                return {k: walk(tree[k], k) for k in sorted(tree)}
            key = jax.random.fold_in(root, idx[0])
            idx[0] += 1
            shape = tuple(tree.shape)
            if "norm" in name:
                return jnp.ones(shape, tree.dtype)
            if len(shape) == 1 or name.endswith("_bias"):
                return jnp.zeros(shape, tree.dtype)
            fan_in = _fan_in(name, shape)
            if quant == "int8" and name in MATMUL_LEAVES:
                # Uniform codes: a channel's largest |code| is 127, its range
                # +-fan_in**-0.5, as per-channel symmetric quantization gives.
                scale = jnp.full(shape[:-2] + shape[-1:], fan_in**-0.5 / 127.0, jnp.bfloat16)
                return {"qw": draw_int8(key, shape), "scale": scale}
            return (jax.random.normal(key, shape, jnp.float32) * fan_in**-0.5).astype(tree.dtype)

        return walk(shapes, None)

    # The seed is an argument, not a constant: one compiled program serves
    # every seed (the driver's seeds run past 2**31, so uint32).
    return jax.jit(build)(jnp.uint32(int(seed) % 2**32))


def requantize_int4(params, group_size: int = 128):
    """The same weights one precision down: each int8 leaf re-coded as the
    program's packed int4 (``qw4`` + a scale per group). The control of the
    outputs check, never a served configuration."""
    import jax
    import jax.numpy as jnp

    def leaf(x):
        qw, scale = x["qw"], x["scale"]
        d_in = qw.shape[-2]
        gs = math.gcd(d_in, group_size)
        q4 = jnp.clip(jnp.round(qw.astype(jnp.float32) * (7.0 / 127.0)), -7, 7).astype(jnp.int8)
        lo, hi = q4[..., 0::2, :], q4[..., 1::2, :]
        packed = ((hi.astype(jnp.uint8) << 4) | (lo.astype(jnp.uint8) & 0x0F)).astype(jnp.int8)
        s4 = jnp.broadcast_to((scale.astype(jnp.float32) * (127.0 / 7.0))[..., None, :],
                              qw.shape[:-2] + (d_in // gs, qw.shape[-1])).astype(jnp.bfloat16)
        return {"qw4": packed, "scale": s4}

    def one(x):
        big = x["qw"].ndim >= 3 and x["qw"].size > MAX_DRAW
        return jax.jit(lambda y: jax.lax.map(leaf, y) if big else leaf(y))(x)

    def walk(t):
        if isinstance(t, dict) and "qw" in t:
            return one(t)
        return {k: walk(v) for k, v in t.items()} if isinstance(t, dict) else t

    return walk(params)
