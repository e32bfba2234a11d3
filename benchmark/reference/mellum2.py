"""Mellum 2 (JetBrains; ``model_type`` ``mellum``, config.json of
Mellum2-12B-A2.5B-Instruct): pre-norm decoder whose layers alternate two
kinds of attention, ``layer_types`` = sliding, sliding, sliding, full:

- a sliding layer: plain RoPE at its own theta; a query at p sees the keys at
  ``p - sliding_window < key <= p``;
- a full layer: YaRN RoPE (NTK-by-parts between ``beta_fast`` and
  ``beta_slow``, cos and sin scaled by ``attention_factor``); causal mask.

Both: q, k, v projections without bias, GQA softmax attention at scale
head_dim**-0.5 (K and V repeated over the query heads of their group), output
projection; then RMSNorm and 64 SwiGLU experts of which the router's softmax
picks 8, the 8 weights renormalised to sum 1 (``norm_topk_prob`` true).

Departures from the published description, each because config.json is silent:

- q and k are NOT normalised before RoPE (the config has no key for it);
- the multi-token-prediction head the model card mentions is left out (no key
  in config.json describes it): this is the next-token model only;
- the two halves of a head rotate together (``common.rope``): a checkpoint
  that interleaves pairs is the same map up to a fixed permutation of its
  weights, which random weights do not see.

``forward(params, hf, tokens)``: tokens i32[T] -> logits f32[T, vocab].
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from . import common as c

SLIDING, FULL = "sliding_attention", "full_attention"


def inv_freq_of(dim: int, p: dict) -> tuple[np.ndarray, float]:
    """Inverse frequencies [dim/2] of one ``rope_parameters`` entry and the
    factor its cos and sin carry (1 for plain RoPE)."""
    base = float(p["rope_theta"])
    plain = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    kind = p.get("rope_type", "default")
    if kind == "default":
        return plain, 1.0
    if kind != "yarn":
        raise ValueError(f"this reference has no rope type {kind!r}")
    factor, orig = float(p["factor"]), float(p["original_max_position_embeddings"])

    def turns_to_dim(turns: float) -> float:  # the dimension that makes `turns` rotations over `orig` positions
        return dim * math.log(orig / (turns * 2.0 * math.pi)) / (2.0 * math.log(base))

    low = max(math.floor(turns_to_dim(float(p["beta_fast"]))), 0)
    high = min(math.ceil(turns_to_dim(float(p["beta_slow"]))), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / max(high - low, 1e-3), 0.0, 1.0)
    # Dimensions that turn often keep their frequency (ramp 0); those that do
    # not complete a turn over the original context are interpolated (ramp 1).
    inv = plain / factor * ramp + plain * (1.0 - ramp)
    scale = p.get("attention_factor")
    return inv, float(0.1 * math.log(factor) + 1.0 if scale is None else scale)


def rope_by_angle(x, cos, sin):
    """[T, H, dim] rotated by per-position cos and sin [T, dim/2], halves together."""
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def masked_attention(q, k, v, mask, scale):
    """q [T, H, d], k and v [T, KV, d], mask bool[T, T] -> [T, H, d]. One group
    of query heads at a time, so a long sequence's scores fit beside the model."""
    t, heads, d = q.shape
    kv_heads = k.shape[1]
    qg = jnp.moveaxis(q.reshape(t, kv_heads, heads // kv_heads, d), 1, 0)  # [KV, T, G, d]

    def one(args):
        qh, kh, vh = args  # [T, G, d], [T, d], [T, d]: K and V repeated over the group
        s = jnp.einsum("tgd,sd->gts", qh, kh) * scale
        s = jnp.where(mask[None], s, -jnp.inf)
        return jnp.einsum("gts,sd->tgd", jax.nn.softmax(s, axis=-1), vh)

    out = jax.lax.map(one, (qg, jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)))  # [KV, T, G, d]
    return jnp.moveaxis(out, 0, 1).reshape(t, heads, d)


def forward(params, hf: dict, tokens):
    heads, kv_heads, hd = hf["num_attention_heads"], hf["num_key_value_heads"], hf["head_dim"]
    eps, window = hf["rms_norm_eps"], int(hf["sliding_window"])
    kinds = list(hf["layer_types"])
    if set(hf["mlp_layer_types"]) != {"sparse"} or set(kinds) - {SLIDING, FULL}:
        raise ValueError("this reference knows sparse layers of sliding or full attention only")
    t = tokens.shape[0]
    pos = jnp.arange(t)
    causal = pos[None, :] <= pos[:, None]
    masks = {FULL: causal, SLIDING: causal & (pos[None, :] > pos[:, None] - window)}
    angles = {}
    for kind in (SLIDING, FULL):
        inv, factor = inv_freq_of(hd, hf["rope_parameters"][kind])
        ang = pos.astype(c.F32)[:, None] * jnp.asarray(inv, c.F32)[None, :]
        angles[kind] = (jnp.cos(ang) * factor, jnp.sin(ang) * factor)
    x = params["embed"][tokens].astype(c.F32)

    def layer(x, xs):
        lp, sliding = xs
        cos = jnp.where(sliding, angles[SLIDING][0], angles[FULL][0])
        sin = jnp.where(sliding, angles[SLIDING][1], angles[FULL][1])
        mask = jnp.where(sliding, masks[SLIDING], masks[FULL])
        h = c.rms_norm(x, lp["attn_norm"], eps)
        q = rope_by_angle((h @ c.widen(lp["wq"])).reshape(t, heads, hd), cos, sin)
        k = rope_by_angle((h @ c.widen(lp["wk"])).reshape(t, kv_heads, hd), cos, sin)
        v = (h @ c.widen(lp["wv"])).reshape(t, kv_heads, hd)
        attn = masked_attention(q, k, v, mask, hd**-0.5)
        x = x + attn.reshape(t, heads * hd) @ c.widen(lp["wo"])
        h2 = c.rms_norm(x, lp["mlp_norm"], eps)
        return x + c.routed_experts(h2, lp, top_k=hf["num_experts_per_tok"],
                                    renormalize=bool(hf["norm_topk_prob"])), None

    x, _ = jax.lax.scan(layer, x, (params["layers"], jnp.asarray([k == SLIDING for k in kinds])))
    return c.lm_head(c.rms_norm(x, params["norm_f"], eps), params)
