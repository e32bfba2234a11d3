"""K-EXAONE-236B-A23B (LGAI-EXAONE; ``model_type`` ``exaone_moe``, config.json of
K-EXAONE-236B-A23B): a pre-norm decoder whose layers alternate two kinds of
attention, ``layer_types`` = sliding, sliding, sliding, full (``LLLG``), over a
leading dense FFN and then sparse ones. x the residual stream, RMS norms:

    h = x + Attn(RMS(x; g_in))
    y = h + FFN(RMS(h; g_post))

    Attn(u), token at position p:
      q = RMS_head(u W_q) as [64 heads, 128];  k = RMS_head(u W_k), v = u W_v as [8 heads, 128]
      a sliding layer: q, k = RoPE(q, p), RoPE(k, p) (theta ``rope_parameters.rope_theta``, the whole head,
        halves together); the query sees the keys at  p - sliding_window < key <= p
      a full layer: no rotation; the query sees every key <= p
      softmax(q . k / sqrt(128)) v, the 8 query heads of a group on their one K and V head;  W_o

    FFN(u), layers [0, first_k_dense_replace): SwiGLU of width intermediate_size
    FFN(u), every later layer:
      s = sigmoid(u W_r) over the n_routed_experts_published experts, in float32
      J = the num_experts_per_tok largest of (s + b)                  # b only selects
      w_j = routed_scaling_factor * s_j / sum_{i in J} s_i            # norm_topk_prob
      FFN(u) = SwiGLU_shared(u) + sum_{j in J} w_j SwiGLU_j(u)        # num_shared_experts of width moe_intermediate_size

Set by the family's published description (EXAONE 4.0's hybrid attention, of
which ``exaone_moe`` is the MoE successor), because config.json has no key for
them: the per-head RMS norm of q and k, RoPE in the sliding layers only,
pre-norm residuals. Further departures, each stated:

- the two halves of a head rotate together; a checkpoint that interleaves pairs
  is the same map up to a fixed permutation of its weights, which weights from
  a seed do not see;
- the selection bias b (``router_bias`` of the served tree) is the published
  model's balancing bias; the benchmark's weights make it zero (the CPU tests
  give it values); ``n_group`` = ``topk_group`` = 1 is no group limit and
  anything else is refused here;
- the multi-token-prediction layer (``num_nextn_predict_layers``,
  ``mtp_layer_types``) is not built: the config gives it an attention kind and no
  FFN kind, and it is an optional draft head, no part of the next-token
  distribution.

**The share.** Where the file states one (``num_experts`` held here of
``n_routed_experts_published``, of rank ``expert_share_rank``), the served tree
holds experts ``[rank * held, (rank + 1) * held)`` only. The router still scores
every expert and the weights are normalised over all k choices; this reference
adds the held experts' terms and the shared expert (every chip computes it
whole), and leaves out what the other experts would add, as the program does.
That partial result goes on to the next layer. A sliced vocabulary is a smaller
vocabulary: the embedding and the head have ``vocab_size`` rows as run.

Widened float32 copies are made a layer at a time (``lax.scan`` over the
stacked layers, the leading dense ones first), an expert at a time, and
attention a group of query heads at a time.

``forward(params, hf, tokens)``: tokens i32[T] -> logits f32[T, vocab].
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import common as c
from .longcat_flash import held_experts_term  # one held expert widened at a time
from .mellum2 import masked_attention, rope_by_angle  # GQA under a mask, a group at a time; rotation by angle

SLIDING, FULL = "sliding_attention", "full_attention"


def shape_of(hf: dict) -> dict:
    """The sizes the equations use, by the config's own keys."""
    if hf.get("scoring_func") != "sigmoid" or not hf.get("norm_topk_prob"):
        raise ValueError("this reference knows sigmoid scores and renormalised weights only")
    if (hf.get("n_group") or 1) != 1 or (hf.get("topk_group") or 1) != 1:
        raise ValueError("this reference knows one routing group only")
    rope = hf["rope_parameters"]
    if rope.get("rope_type", "default") != "default":
        raise ValueError(f"this reference has no rope type {rope.get('rope_type')!r}")
    n, k_dense = hf["num_hidden_layers"], int(hf.get("first_k_dense_replace", 0))
    kinds = list(hf["layer_types"][:n])  # a file that serves the first layers keeps the published lists whole
    if (len(kinds) < n or set(kinds) - {SLIDING, FULL}
            or list(hf["mlp_layer_types"][:n]) != ["dense"] * k_dense + ["sparse"] * (n - k_dense)):
        raise ValueError("this reference knows sliding or full attention over leading dense FFNs and then sparse ones only")
    held = hf["num_experts"]
    return dict(
        heads=hf["num_attention_heads"], kv_heads=hf["num_key_value_heads"], hd=hf["head_dim"],
        eps=hf["rms_norm_eps"], window=int(hf["sliding_window"]), kinds=kinds, k_dense=k_dense,
        top_k=hf["num_experts_per_tok"], held=held, routed=hf.get("n_routed_experts_published", held),
        first=hf.get("expert_share_rank", 0) * held, factor=float(hf.get("routed_scaling_factor", 1.0)),
        inv_freq=c.rope_inv_freq(hf["head_dim"], float(rope["rope_theta"]), None),
    )


def route(u, lp, z: dict):
    """Routing weights f32[T, published experts]: ``w_j`` at a token's chosen
    experts, 0 elsewhere."""
    s = jax.nn.sigmoid(u @ lp["router"].astype(c.F32))
    _, idx = jax.lax.top_k(s + lp["router_bias"].astype(c.F32), z["top_k"])
    rows = jnp.arange(u.shape[0])[:, None]
    chosen = s[rows, idx]
    return jnp.zeros_like(s).at[rows, idx].set(z["factor"] * chosen / chosen.sum(axis=-1, keepdims=True))


def swiglu_of(u, lp, gate: str, up: str, down: str):
    return c.swiglu(u, c.widen(lp[gate]), c.widen(lp[up]), c.widen(lp[down]))


def shared_expert_term(u, lp):
    return swiglu_of(u, lp, "w_shared_gate", "w_shared_up", "w_shared_down")


def moe(u, lp, z: dict):
    return shared_expert_term(u, lp) + held_experts_term(u, lp, route(u, lp, z), z)


def attention(u, lp, sliding, pos, z: dict):
    """One layer's attention on the normed stream [T, d]; ``sliding`` a traced
    bool: the rotation (identity in a full layer) and the mask are selected."""
    t, heads, kv_heads, hd = u.shape[0], z["heads"], z["kv_heads"], z["hd"]
    ang = jnp.where(sliding, pos.astype(c.F32)[:, None] * jnp.asarray(z["inv_freq"], c.F32)[None, :], 0.0)
    causal = pos[None, :] <= pos[:, None]
    mask = causal & (~sliding | (pos[None, :] > pos[:, None] - z["window"]))
    q = c.rms_norm((u @ c.widen(lp["wq"])).reshape(t, heads, hd), lp["q_norm"], z["eps"])
    k = c.rms_norm((u @ c.widen(lp["wk"])).reshape(t, kv_heads, hd), lp["k_norm"], z["eps"])
    v = (u @ c.widen(lp["wv"])).reshape(t, kv_heads, hd)
    q, k = rope_by_angle(q, jnp.cos(ang), jnp.sin(ang)), rope_by_angle(k, jnp.cos(ang), jnp.sin(ang))
    return masked_attention(q, k, v, mask, hd**-0.5).reshape(t, heads * hd) @ c.widen(lp["wo"])


def layer(x, lp, sliding, pos, z: dict, ffn):
    h = x + attention(c.rms_norm(x, lp["attn_norm"], z["eps"]), lp, sliding, pos, z)
    return h + ffn(c.rms_norm(h, lp["mlp_norm"], z["eps"]), lp)


def forward(params, hf: dict, tokens):
    z = shape_of(hf)
    pos = jnp.arange(tokens.shape[0])
    sliding = jnp.asarray([k == SLIDING for k in z["kinds"]])
    x = params["embed"][tokens].astype(c.F32)
    dense = lambda u, lp: swiglu_of(u, lp, "w_gate", "w_up", "w_down")  # noqa: E731
    n = z["k_dense"]
    if n:
        x, _ = jax.lax.scan(lambda x, xs: (layer(x, xs[0], xs[1], pos, z, dense), None), x,
                            (params["dense_layers"], sliding[:n]))
    x, _ = jax.lax.scan(lambda x, xs: (layer(x, xs[0], xs[1], pos, z, lambda u, lp: moe(u, lp, z)), None), x,
                        (params["layers"], sliding[n:]))
    return c.lm_head(c.rms_norm(x, params["norm_f"], z["eps"]), params)
