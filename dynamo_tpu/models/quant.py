"""Weight-only quantization for serving: int8 (per-channel) and packed int4
(group-wise).

Matmul weights are stored narrow and dequantized on the fly inside the
forward — XLA fuses the dequant expression into the matmul's operand read,
so HBM traffic for weights drops to 1 byte/elem (int8) or 0.5 byte/elem
(int4). The MXU still multiplies bf16; this is a bandwidth optimization,
which is exactly what decode is bound by.

Two leaf formats, distinguished by key:

- int8: ``{"qw": int8[..., d_in, d_out], "scale": bf16[..., d_out]}``.
  Per-output-channel symmetric; the scale commutes with the contraction, so
  ``quant_matmul`` applies it to the matmul *output* and the weight operand
  stays a bare int8→bf16 convert. Error ≤ 0.4% of each channel's range.
- int4: ``{"qw4": int8[..., d_in//2, d_out], "scale": bf16[..., G, d_out]}``
  plus an optional ``"qbias"`` (same shape as scale) for asymmetric imports
  (GGUF ``Q4_K``). Two nibbles per byte (element ``2i`` in the low nibble,
  ``2i+1`` in the high), group-wise scales along the *contraction* axis
  (``G = d_in // group_size`` groups). Group scales do NOT commute with the
  dot, so ``maybe_dequant`` expresses ``unpack * scale (+ bias)`` in-graph
  and relies on XLA operand fusion — the full-width tensor never
  round-trips HBM.

``maybe_dequant`` / ``quant_matmul`` are the single read-side accessors
(`models/llama.py`, `models/mla.py`, `parallel/moe.py`). Embeddings stay
bf16 (gathers, not matmuls); norms/biases/router are tiny and
accuracy-sensitive.

Role: the weight-quantized serving modes the reference gets from its
engines (vLLM/TRT-LLM quantized checkpoints, GGUF Q4-class wrapping); here
it's a params transform, so any checkpoint (safetensors/GGUF/random) can
serve quantized: ``--quantize int8|int4`` / ``BENCH_QUANT=int8|int4``. The
int4 group width is ``DYN_QUANT_GROUP_SIZE`` (default 128; GGUF Q4 imports
keep their native 32).
"""

from __future__ import annotations

import functools
import os
from typing import Any

import jax
import jax.numpy as jnp

# Leaves that are matmul weights, by name, at any nesting depth.
_MATMUL_LEAVES = frozenset(
    {
        "wq", "wk", "wv", "wo",
        "w_gate", "w_up", "w_down",
        "w_shared_gate", "w_shared_up", "w_shared_down",
        "lm_head",
        # MLA 2D projections (models/mla.py) — ~95% of its attention weight
        # bytes. The absorbed per-head tensors (w_uk/w_uv, 3-axis einsums)
        # stay bf16: their contraction axis is not the stored-scale axis.
        "w_q_a", "w_q_b", "w_q", "w_kv_a", "wo_mla",
    }
)

#: Modes accepted by quantize_params / init_params_quantized.
QUANT_MODES = ("int8", "int4")


def default_group_size() -> int:
    """int4 group width along the contraction axis (DYN_QUANT_GROUP_SIZE)."""
    return int(os.environ.get("DYN_QUANT_GROUP_SIZE", "128"))


def is_quantized(leaf: Any) -> bool:
    return isinstance(leaf, dict) and "scale" in leaf and ("qw" in leaf or "qw4" in leaf)


def _pick_group_size(d_in: int, group_size: int) -> int:
    """Largest even divisor of ``d_in`` that is ≤ the requested width.

    Group boundaries must align with nibble pairs (pairs run along d_in),
    so the width must be even; it must divide d_in so every group is full.
    """
    gs = min(group_size, d_in)
    while gs > 2 and (d_in % gs or gs % 2):
        gs -= 2 if gs % 2 == 0 else 1
    return max(gs, 2)


def pack_int4(q: jnp.ndarray) -> jnp.ndarray:
    """[..., d_in, O] int4-valued int8 → [..., d_in//2, O] packed bytes.

    Element ``2i`` lands in the low nibble of byte ``i``, ``2i+1`` in the
    high nibble. Values must be in [-8, 7].
    """
    lo = q[..., 0::2, :]
    hi = q[..., 1::2, :]
    return ((hi.astype(jnp.uint8) << 4) | (lo.astype(jnp.uint8) & 0x0F)).astype(jnp.int8)


def unpack_int4(packed: jnp.ndarray) -> jnp.ndarray:
    """[..., P, O] packed bytes → [..., 2P, O] int8 values in [-8, 7].

    Arithmetic shifts sign-extend the nibbles; the stack/reshape interleaves
    (lo, hi) back into row order — all cheap elementwise/layout ops XLA
    folds into the consuming dot's operand read.
    """
    b = packed.astype(jnp.int8)
    lo = jnp.left_shift(b, 4) >> 4  # sign-extended low nibble
    hi = b >> 4
    stacked = jnp.stack([lo, hi], axis=-2)  # [..., P, 2, O]
    return stacked.reshape(*packed.shape[:-2], packed.shape[-2] * 2, packed.shape[-1])


def quantize_leaf(w: jnp.ndarray, *, scale_dtype: Any = jnp.bfloat16) -> dict[str, jnp.ndarray]:
    """Symmetric per-output-channel int8: w[..., d_in, d_out]."""
    w32 = jnp.asarray(w, jnp.float32)
    amax = jnp.max(jnp.abs(w32), axis=-2)  # [..., d_out]
    # Round the scale to its stored width *before* quantizing so the quants
    # are optimal for the scale the dequant will actually use.
    scale = jnp.where(amax > 0, amax / 127.0, 1.0).astype(scale_dtype)
    q = jnp.clip(
        jnp.round(w32 / scale.astype(jnp.float32)[..., None, :]), -127, 127
    ).astype(jnp.int8)
    return {"qw": q, "scale": scale}


def quantize_leaf_int4(
    w: jnp.ndarray, *, group_size: int | None = None, scale_dtype: Any = jnp.bfloat16
) -> dict[str, jnp.ndarray]:
    """Symmetric group-wise packed int4: w[..., d_in, d_out].

    Groups of ``group_size`` consecutive input rows share one bf16 scale per
    output channel; quants clip to [-7, 7] (the -8 code is reserved for
    asymmetric imports so symmetric dequant stays sign-balanced).
    """
    d_in = w.shape[-2]
    if d_in % 2:
        raise ValueError(f"int4 packing needs an even contraction dim, got {d_in}")
    gs = _pick_group_size(d_in, group_size or default_group_size())
    groups = d_in // gs
    w32 = jnp.asarray(w, jnp.float32).reshape(*w.shape[:-2], groups, gs, w.shape[-1])
    amax = jnp.max(jnp.abs(w32), axis=-2)  # [..., G, d_out]
    scale = jnp.where(amax > 0, amax / 7.0, 1.0).astype(scale_dtype)
    q = jnp.clip(
        jnp.round(w32 / scale.astype(jnp.float32)[..., None, :]), -7, 7
    ).astype(jnp.int8)
    q = q.reshape(*w.shape[:-2], d_in, w.shape[-1])
    return {"qw4": pack_int4(q), "scale": scale}


def _dequant_int4(leaf: dict, dtype: Any) -> jnp.ndarray:
    """Packed int4 leaf → full-width expression (for XLA operand fusion)."""
    q = unpack_int4(leaf["qw4"])  # [..., d_in, O] int8
    scale = leaf["scale"]  # [..., G, O]
    groups = scale.shape[-2]
    d_in, d_out = q.shape[-2], q.shape[-1]
    qg = q.reshape(*q.shape[:-2], groups, d_in // groups, d_out).astype(dtype)
    w = qg * scale.astype(dtype)[..., :, None, :]
    if "qbias" in leaf:
        w = w + leaf["qbias"].astype(dtype)[..., :, None, :]
    return w.reshape(*q.shape[:-2], d_in, d_out)


def quantize_params(params: dict, *, mode: str = "int8") -> dict:
    """Return a params pytree with matmul weights replaced by quantized
    leaves (int8 per-channel or packed int4 group-wise)."""
    if mode in ("", "none", None):
        return params
    if mode not in QUANT_MODES:
        raise ValueError(
            f"unknown quantization mode {mode!r} (supported: {', '.join(QUANT_MODES)})"
        )
    q_leaf = quantize_leaf if mode == "int8" else quantize_leaf_int4

    def walk(tree: Any, name: str | None) -> Any:
        if isinstance(tree, dict) and not is_quantized(tree):
            return {k: walk(v, k) for k, v in tree.items()}
        if name in _MATMUL_LEAVES and not is_quantized(tree):
            return q_leaf(tree)
        return tree

    return walk(params, None)


def quant_matmul(x: jnp.ndarray, leaf: Any, *, preferred_element_type: Any | None = None) -> jnp.ndarray:
    """``x @ w`` for a possibly-quantized last-two-dims weight.

    For int8 leaves the per-output-channel scale is applied to the matmul
    *output* (it commutes with the contraction), so the weight operand is a
    bare int8→bf16 convert — which XLA fuses into the dot's operand read
    (weights stream from HBM at 1 byte/elem). Scaling the weight before the
    dot instead materializes a dequantized copy and loses the bandwidth win.

    int4 group scales vary along the contraction axis and do not commute;
    the dequant expression goes on the operand side and fuses into the read
    (0.5 byte/elem streamed).
    """
    if is_quantized(leaf):
        if "qw4" in leaf:
            return jnp.matmul(
                x, _dequant_int4(leaf, x.dtype), preferred_element_type=preferred_element_type
            )
        y = jnp.matmul(
            x, leaf["qw"].astype(x.dtype), preferred_element_type=preferred_element_type
        )
        return y * leaf["scale"].astype(y.dtype)
    return jnp.matmul(x, leaf, preferred_element_type=preferred_element_type)


def held_flat(y: jnp.ndarray) -> jnp.ndarray:
    """A projection's flat output ``[..., heads * dim]`` on its way to being
    split into heads: the same values, as an array the compiler has to lay
    out as it stands, so that the projection reads its weight where it lies.

    Without it the layout that RoPE and the attention kernel want for the
    small heads-major activation is pushed back through the reshape and the
    scale into the dot, and the compiler pays by re-laying the *weight*: in
    every layer of every step a slice of the layer's int8 matrix out of the
    stack and a transposed copy of it (Mellum2's ``wq``, 9.4 MB: 16 + 22 us a
    layer, 28 layers; LongCat-Flash's ``w_q_b``, 18.9 MB, twice a layer; the
    chip's times in PERF.md, PR 35). Held flat, the dot reads the stack by the
    layer's index, as ``wo`` and the FFN matrices always did, and what is
    transposed is the activation (64 KB at 8 rows). The values are untouched:
    the same int8 codes, convert, scale and accumulation.

    ``tools/step_relayouts.py <config>`` lists what a configuration's step
    programs still re-lay; ``tests/test_chip_compile.py`` holds the benchmark's
    three configurations to no copy of an int8 weight.
    """
    return jax.lax.optimization_barrier(y)


def maybe_dequant(leaf: Any, dtype: Any = jnp.bfloat16) -> jnp.ndarray:
    """The read-side accessor every matmul site goes through.

    For a quantized leaf, emits the dequant expression (``qw.astype * scale``
    for int8; unpack→scale→(+bias) for packed int4) — XLA fuses this into
    the consuming dot's operand so the dequantized tensor never round-trips
    HBM. Plain arrays pass through untouched.
    """
    if is_quantized(leaf):
        if "qw4" in leaf:
            return _dequant_int4(leaf, dtype)
        return leaf["qw"].astype(dtype) * leaf["scale"].astype(dtype)[..., None, :]
    return leaf




def init_params_quantized(cfg, rng: int | jax.Array = 0, *, mode: str = "int8") -> dict:
    """Random-init parameters directly in quantized form, never
    materializing the bf16/f32 tree.

    ``init_params`` + ``quantize_params`` peaks at full-precision model size
    plus f32 transients — an 8B-class model OOMs a 16 GB chip before the
    quantization that would have made it fit. Benchmarks need only
    identically-SHAPED (and finite) weights, so matmul leaves are generated
    directly in their quantized layout (int8 draws, or packed int4 bytes —
    each nibble uniform over the code range) with a constant fan-in scale,
    chunked along the stacked layer axis to bound the RNG's int32 transient;
    everything else follows ``init_params``'s shapes via ``jax.eval_shape``.
    """
    import math

    from dynamo_tpu.models import llama

    if mode in ("", "none", None):
        return llama.init_params(cfg, rng)
    if mode not in QUANT_MODES:
        raise ValueError(
            f"unknown quantization mode {mode!r} (supported: {', '.join(QUANT_MODES)})"
        )
    if isinstance(rng, int):
        rng = jax.random.PRNGKey(rng)
    shapes = jax.eval_shape(lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    max_chunk_elems = 2**28  # 1 GiB int32 RNG transient ceiling

    @functools.partial(jax.jit, static_argnames=("shape", "lo", "hi"))
    def _rand_int8(key, shape, lo=-127, hi=128):
        # ONE dispatch per leaf: lax.map over the stacked leading axis keeps
        # the RNG's int32 transient at one slice, and avoids the per-chunk
        # host round trips that dominate init.
        if len(shape) >= 3 and math.prod(shape) > max_chunk_elems:
            keys = jax.random.split(key, shape[0])
            return jax.lax.map(
                lambda k: jax.random.randint(k, shape[1:], lo, hi, jnp.int8),
                keys,
            )
        return jax.random.randint(key, shape, lo, hi, jnp.int8)

    def gen_int8(key, sds):
        fan_in = sds.shape[-2]
        scale = jnp.full(
            sds.shape[:-2] + sds.shape[-1:], (fan_in**-0.5) / 127.0, jnp.bfloat16
        )
        return {"qw": _rand_int8(key, tuple(sds.shape)), "scale": scale}

    def gen_int4(key, sds):
        d_in = sds.shape[-2]
        if d_in % 2:
            raise ValueError(f"int4 packing needs an even contraction dim, got {d_in}")
        gs = _pick_group_size(d_in, default_group_size())
        packed_shape = sds.shape[:-2] + (d_in // 2, sds.shape[-1])
        scale_shape = sds.shape[:-2] + (d_in // gs, sds.shape[-1])
        # Full-byte uniform draws: each nibble is uniform over [-8, 7], so
        # the packed bytes ARE a valid symmetric-ish int4 population.
        packed = _rand_int8(key, packed_shape, -128, 128)
        scale = jnp.full(scale_shape, (d_in**-0.5) / 7.0, jnp.bfloat16)
        return {"qw4": packed, "scale": scale}

    gen_quant = gen_int8 if mode == "int8" else gen_int4

    def gen_plain(key, name, sds):
        if "norm" in name:
            return jnp.ones(sds.shape, sds.dtype)
        if sds.ndim == 1:
            return jnp.zeros(sds.shape, sds.dtype)
        fan_in = sds.shape[-2]
        return (
            jax.random.normal(key, sds.shape, jnp.float32) * fan_in**-0.5
        ).astype(sds.dtype)

    idx = 0

    def walk(tree, name):
        nonlocal idx
        if isinstance(tree, dict):
            return {k: walk(v, k) for k, v in tree.items()}
        key = jax.random.fold_in(rng, idx)
        idx += 1
        if name in _MATMUL_LEAVES:
            return gen_quant(key, tree)
        return gen_plain(key, name, tree)

    return walk(shapes, None)
