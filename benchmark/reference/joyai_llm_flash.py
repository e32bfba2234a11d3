"""JoyAI-LLM-Flash (jdopensource; config.json of JoyAI-LLM-Flash, whose keys are
DeepSeek-V3's): a decoder of plain layers, one latent-attention block and one
FFN each. x the residual stream, RMS norms:

    a = x + MLA(RMS(x; g_in))
    y = a + FFN(RMS(a; g_post))

    MLA(h), token at position p (K and V materialised per head, not absorbed):
      q      = (RMS(h W_qa; g_q) W_qb) as [heads, nope + rope]
      kv     = h W_kva  (kv_lora_rank + rope);  c = RMS(kv[:rank]; g_kv)
      k_rope = RoPE(kv[rank:], p), one for all heads;  q_rope = RoPE(q[:, nope:], p)
      k_nope = c W_uk as [heads, nope];  v = c W_uv as [heads, v_head_dim]
      out    = concat_h softmax_causal((q_nope . k_nope + q_rope . k_rope) / sqrt(nope + rope)) v  W_o

    FFN(h), layers [0, first_k_dense_replace): SwiGLU of width intermediate_size
    FFN(h), every later layer:
      s = sigmoid(h W_r) over the n_routed_experts_published experts, in float32
      J = top-k of (s + b)                          # topk_method noaux_tc: b only selects
      w_j = routed_scaling_factor * s_j / sum_{i in J} s_i      # norm_topk_prob
      FFN(h) = SwiGLU_shared(h) + sum_{j in J} w_j SwiGLU_j(h)  # n_shared_experts of width moe_intermediate_size

Departures from the published description, each stated:

- the two halves of a rope head rotate together (``common.rope``); the
  checkpoint interleaves pairs (``rope_interleave``), the same map up to a
  fixed permutation of the weights, which weights from a seed do not see;
- the selection bias b (``router_bias`` of the served tree) is the published
  model's balancing bias; the benchmark's weights make it zero (the CPU tests
  give it values);
- ``n_group`` = ``topk_group`` = 1 is no group limit, and anything else is
  refused here; ``moe_layer_freq`` is 1;
- the multi-token-prediction module (``num_nextn_predict_layers``, layer index
  ``num_hidden_layers``) is not built: an optional draft head, no part of the
  model's own next-token distribution.

**The share.** Where the file states one (``n_routed_experts`` held here of
``n_routed_experts_published``, of rank ``expert_share_rank``), the served tree
holds experts ``[rank * held, (rank + 1) * held)`` only. The router still
scores every expert and the weights are normalised over all k choices; this
reference adds the held experts' terms and the shared expert (every chip
computes it whole), and leaves out what the other experts would add, as the
program does. That partial result goes on to the next layer.

Widened float32 copies are made a layer at a time (``lax.scan`` over the
stacked layers, the leading dense ones first) and an expert at a time.

``forward(params, hf, tokens)``: tokens i32[T] -> logits f32[T, vocab].
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import common as c
from .longcat_flash import held_experts_term, mla  # the same un-absorbed block (its two latent scales are 1 here) and expert sum


def shape_of(hf: dict) -> dict:
    """The sizes the equations use, by the config's own keys."""
    if hf.get("scoring_func") != "sigmoid" or hf.get("topk_method") != "noaux_tc" or not hf.get("norm_topk_prob"):
        raise ValueError("this reference knows sigmoid scores, noaux_tc selection and renormalised weights only")
    if (hf.get("n_group") or 1) != 1 or (hf.get("topk_group") or 1) != 1 or hf.get("moe_layer_freq", 1) != 1:
        raise ValueError("this reference knows one routing group and a MoE in every layer after the dense ones only")
    held = hf["n_routed_experts"]
    return dict(
        heads=hf["num_attention_heads"], rank=hf["kv_lora_rank"], nope=hf["qk_nope_head_dim"],
        rope=hf["qk_rope_head_dim"], dv=hf["v_head_dim"], eps=hf["rms_norm_eps"], top_k=hf["num_experts_per_tok"],
        held=held, routed=hf.get("n_routed_experts_published", held), first=hf.get("expert_share_rank", 0) * held,
        factor=float(hf.get("routed_scaling_factor", 1.0)), s_q=1.0, s_kv=1.0,
        inv_freq=c.rope_inv_freq(hf["qk_rope_head_dim"], float(hf["rope_theta"]), hf.get("rope_scaling")),
    )


def route(h, lp, z: dict):
    """Routing weights f32[T, published experts]: ``w_j`` at a token's chosen
    experts, 0 elsewhere."""
    s = jax.nn.sigmoid(h @ lp["router"].astype(c.F32))
    _, idx = jax.lax.top_k(s + lp["router_bias"].astype(c.F32), z["top_k"])
    rows = jnp.arange(h.shape[0])[:, None]
    chosen = s[rows, idx]
    return jnp.zeros_like(s).at[rows, idx].set(z["factor"] * chosen / chosen.sum(axis=-1, keepdims=True))


def swiglu_of(h, lp, gate: str, up: str, down: str):
    return c.swiglu(h, c.widen(lp[gate]), c.widen(lp[up]), c.widen(lp[down]))


def shared_expert_term(h, lp):
    return swiglu_of(h, lp, "w_shared_gate", "w_shared_up", "w_shared_down")


def moe(h, lp, z: dict):
    return shared_expert_term(h, lp) + held_experts_term(h, lp, route(h, lp, z), z)


def layer(x, lp, pos, z: dict, ffn):
    a = x + mla(c.rms_norm(x, lp["attn_norm"], z["eps"]), lp, pos, z)
    return a + ffn(c.rms_norm(a, lp["mlp_norm"], z["eps"]), lp)


def forward(params, hf: dict, tokens):
    z = shape_of(hf)
    pos = jnp.arange(tokens.shape[0])
    x = params["embed"][tokens].astype(c.F32)
    dense = lambda h, lp: swiglu_of(h, lp, "w_gate", "w_up", "w_down")  # noqa: E731
    if "dense_layers" in params:
        x, _ = jax.lax.scan(lambda x, lp: (layer(x, lp, pos, z, dense), None), x, params["dense_layers"])
    x, _ = jax.lax.scan(lambda x, lp: (layer(x, lp, pos, z, lambda h, lp: moe(h, lp, z)), None), x, params["layers"])
    return c.lm_head(c.rms_norm(x, params["norm_f"], z["eps"]), params)
