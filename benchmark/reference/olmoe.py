"""OLMoE (arXiv:2409.02060; HF ``modeling_olmoe``): pre-norm decoder, full
multi-head attention with RMSNorm over the whole q and k projections before
RoPE, and in every layer 64 SwiGLU experts of which the router's softmax picks
8. ``norm_topk_prob`` false: the chosen weights are the raw softmax values.

``forward(params, hf, tokens)``: tokens i32[T] -> logits f32[T, vocab].
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import common as c


def forward(params, hf: dict, tokens):
    heads, kv_heads = hf["num_attention_heads"], hf["num_key_value_heads"]
    hd = hf["hidden_size"] // heads
    eps = hf["rms_norm_eps"]
    pos = jnp.arange(tokens.shape[0])
    inv_freq = c.rope_inv_freq(hd, hf["rope_theta"], hf.get("rope_scaling"))
    x = params["embed"][tokens].astype(c.F32)

    def layer(x, lp):
        h = c.rms_norm(x, lp["attn_norm"], eps)
        q = c.rms_norm(h @ c.widen(lp["wq"]), lp["q_norm"], eps).reshape(-1, heads, hd)
        k = c.rms_norm(h @ c.widen(lp["wk"]), lp["k_norm"], eps).reshape(-1, kv_heads, hd)
        v = (h @ c.widen(lp["wv"])).reshape(-1, kv_heads, hd)
        rep = heads // kv_heads
        q, k = c.rope(q, pos, inv_freq), jnp.repeat(c.rope(k, pos, inv_freq), rep, axis=1)
        attn = c.causal_attention(q, k, jnp.repeat(v, rep, axis=1), hd**-0.5)
        x = x + attn.reshape(-1, heads * hd) @ c.widen(lp["wo"])
        h2 = c.rms_norm(x, lp["mlp_norm"], eps)
        return x + c.routed_experts(h2, lp, top_k=hf["num_experts_per_tok"],
                                    renormalize=bool(hf.get("norm_topk_prob", False))), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    return c.lm_head(c.rms_norm(x, params["norm_f"], eps), params)
