"""Ling-3.0-flash (inclusionAI; config.json, ``model_type`` ``bailing_hybrid``):
a decoder of pre-norm residual blocks, x the residual stream, RMS norms
(``rms_norm_eps``):

    a = x + ATTN_i(RMS(x; g_in))
    y = a + FFN_i(RMS(a; g_post))

Layer i (from 0) has latent attention (MLA) if ``(i + 1) % layer_group_size == 0``
and Kimi Delta Attention (KDA, arXiv 2510.26692) otherwise; its FFN is a SwiGLU
of width ``intermediate_size`` for ``i < first_k_dense_replace`` and the routed
FFN after. Heads ``num_attention_heads`` of ``head_dim``.

    KDA(h), token t (no rotary embedding; every head its own key and value:
    ``num_kv_heads_for_linear_attn`` 0; no bias: ``use_qkv_bias`` false):
      q~ = h W_q;  k~ = h W_k;  v~ = h W_v                  # each [heads x head_dim]
      z_t = silu(sum_{j=0..3} w_j * z~_{t-3+j})             # ``short_conv_kernel_size`` 4, ``linear_silu``;
                                                            # per channel, zeros before the sequence's start
      q = l2norm(q) * head_dim**-0.5;  k = l2norm(k);  v as it is   # ``use_qk_norm``; ``value_norm`` false
      a_t = h W_a  [heads x head_dim]                        # full rank: ``no_kda_lora``
      g_t = kda_lower_bound * sigmoid(exp(A_log) * (a_t + dt_bias))   # ``kda_safe_gate``: g in (-5, 0)
      alpha_t = exp(g_t);  beta_t = sigmoid(h W_beta)        # A_log, beta: one value a head; dt_bias: one a channel
      S_t = (I - beta_t k_t k_t^T) diag(alpha_t) S_{t-1} + beta_t k_t v_t^T   # S [key x value] a head, float32, S_0 = 0
      o_t = S_t^T q_t
      out = (RMS_head(o_t; g_o) * sigmoid(h W_g)) W_o        # ``group_norm_size`` 1; W_g one value a head
                                                            # (``gated_attention_proj_granularity_type`` head_wise)

    MLA(h), token at position p (DeepSeek-V3's form, K and V materialised per head, not absorbed):
      q      = (h W_q) as [heads, nope + rope]               # ``q_lora_rank`` null
      kv     = h W_kva  (kv_lora_rank + rope);  c = RMS(kv[:rank]; g_kv)
      k_rope = RoPE(kv[rank:], p), one for all heads;  q_rope = RoPE(q[:, nope:], p)    # ``rope_theta``, no scaling
      k_nope = c W_uk as [heads, nope];  v = c W_uv as [heads, v_head_dim]
      o      = softmax_causal((q_nope . k_nope + q_rope . k_rope) / sqrt(nope + rope)) v
      out    = concat_h (o * sigmoid(h W_g)) W_o             # the same head-wise gate

    FFN(h), routed:
      s = sigmoid(h W_r) over the ``n_routed_experts_published`` (512) experts, in float32   # ``score_function``
      the experts lie in ``n_group`` groups; a group's score is the sum of its two largest s + b;
      the ``topk_group`` best groups stay                     # ``topk_method`` noaux_tc, ``moe_router_enable_expert_bias``
      J = the ``num_experts_per_tok`` largest of (s + b) among their experts
      w_j = routed_scaling_factor * s_j / sum_{i in J} s_i   # ``norm_topk_prob``; the unbiased scores
      FFN(h) = SwiGLU_shared(h) + sum_{j in J} w_j SwiGLU_j(h)   # ``num_shared_experts`` 1 of width
                                                            # ``moe_shared_expert_intermediate_size``

Departures and open points, each stated (the configuration file's ``assumed``):

- the l2 norm divides by ``sqrt(sum x^2 + 1e-6)`` (flash-linear-attention's
  ``l2norm``); the head norm's and every RMS norm's epsilon is ``rms_norm_eps``;
- the output gate is head-wise in both attention kinds (the key names a
  granularity and no layer kind);
- ``use_qk_norm`` is KDA's l2 norm; the MLA layer has no per-head norm beyond
  the latent's;
- the two halves of a rope head rotate together (``common.rope``); the
  checkpoint interleaves pairs (``rope_interleave``), the same map up to a
  fixed permutation of the weights, which weights from a seed do not see;
- the selection bias b (``router_bias`` of the served tree) is the published
  model's balancing bias; the benchmark's weights make it zero (the CPU tests
  give it values);
- a clamped SwiGLU (``expert_swiglu_limit_list`` / ``share_expert_swiglu_limit_list``
  non-zero) is not computed: a non-zero entry among the layers held is refused;
- the multi-token-prediction module (``num_nextn_predict_layers``) is not
  built: an optional draft head, no part of the model's next-token distribution.

**The share.** Where the file states one (``num_experts`` held here of
``n_routed_experts_published``, of rank ``expert_share_rank``), the served tree
holds experts ``[rank * held, (rank + 1) * held)`` only. The router still
scores every expert, limits the groups and normalises over all k choices; this
reference adds the held experts' terms and the shared expert (every chip
computes it whole), and leaves out what the other experts would add, as the
program does. That partial result goes on to the next layer.

The recurrence runs token by token (a ``lax.scan`` over time); widened float32
copies are made a layer at a time (a layer reads its leaves from the served
stacks by index: ``kda_layers`` and ``mla_layers`` the attention blocks by
kind, ``dense_layers`` and ``layers`` the norms and FFNs) and an expert at a time.

``forward(params, hf, tokens)``: tokens i32[T] -> logits f32[T, vocab].
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import common as c
from .longcat_flash import held_experts_term  # one held expert widened at a time


def shape_of(hf: dict) -> dict:
    """The sizes the equations use, by the config's own keys."""
    if hf.get("score_function") != "sigmoid" or hf.get("topk_method") != "noaux_tc" or not hf.get("norm_topk_prob"):
        raise ValueError("this reference knows sigmoid scores, noaux_tc selection and renormalised weights only")
    layers = hf["num_hidden_layers"]
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        if any((hf.get(key) or [])[:layers]):
            raise ValueError(f"this reference has no clamped SwiGLU: {key} is non-zero in a held layer")
    held = hf["num_experts"]
    return dict(
        layers=layers, group=hf["layer_group_size"], dense=hf.get("first_k_dense_replace", 0),
        heads=hf["num_attention_heads"], hd=hf["head_dim"], taps=hf["short_conv_kernel_size"],
        lower=float(hf["kda_lower_bound"]), rank=hf["kv_lora_rank"], nope=hf["qk_nope_head_dim"],
        rope=hf["qk_rope_head_dim"], dv=hf["v_head_dim"], eps=hf["rms_norm_eps"], top_k=hf["num_experts_per_tok"],
        held=held, routed=hf.get("n_routed_experts_published", held), first=hf.get("expert_share_rank", 0) * held,
        n_group=hf.get("n_group") or 1, topk_group=hf.get("topk_group") or 1,
        factor=float(hf.get("routed_scaling_factor", 1.0)),
        inv_freq=c.rope_inv_freq(hf["qk_rope_head_dim"], float(hf["rope_theta"]), hf.get("rope_scaling")),
    )


def l2norm(x):
    return x / jnp.sqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)


def conv_silu(x, filt):
    """z_t = silu(sum_j w_j z~_{t-taps+1+j}) per channel: x [T, W], filt [taps, W]."""
    taps, t = filt.shape[0], x.shape[0]
    full = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1]), c.F32), x])
    return jax.nn.silu(sum(full[j: j + t] * filt[j].astype(c.F32) for j in range(taps)))


def delta_rule(q, k, v, alpha, beta):
    """S_t = (I - beta k k^T) diag(alpha) S_{t-1} + beta k v^T, o_t = S_t^T q_t,
    token by token from S_0 = 0: q k alpha [T, H, K], v [T, H, V], beta [T, H]."""
    eye = jnp.eye(q.shape[-1], dtype=c.F32)

    def token(s, xs):
        q_t, k_t, v_t, a_t, b_t = xs
        forget = eye[None] - b_t[:, None, None] * k_t[:, :, None] * k_t[:, None, :]  # I - beta k k^T, a head
        s = jnp.einsum("hij,hjv->hiv", forget, a_t[:, :, None] * s) + b_t[:, None, None] * k_t[:, :, None] * v_t[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t)

    s0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), c.F32)
    _, o = jax.lax.scan(token, s0, (q, k, v, alpha, beta))
    return o


def kda(h, lp, z: dict):
    t, heads, hd = h.shape[0], z["heads"], z["hd"]
    stream = lambda w, f: conv_silu(h @ c.widen(lp[w]), lp[f]).reshape(t, heads, hd)  # noqa: E731
    q, k, v = stream("wq", "conv_q"), stream("wk", "conv_k"), stream("wv", "conv_v")
    q, k = l2norm(q) * hd**-0.5, l2norm(k)
    a = (h @ lp["w_decay"].astype(c.F32) + lp["dt_bias"].astype(c.F32)).reshape(t, heads, hd)
    g = z["lower"] * jax.nn.sigmoid(jnp.exp(lp["a_log"].astype(c.F32))[None, :, None] * a)
    beta = jax.nn.sigmoid(h @ lp["w_beta"].astype(c.F32))
    o = delta_rule(q, k, v, jnp.exp(g), beta)
    o = c.rms_norm(o, lp["o_norm"], z["eps"]) * jax.nn.sigmoid(h @ lp["w_out_gate"].astype(c.F32))[..., None]
    return o.reshape(t, heads * hd) @ c.widen(lp["wo"])


def mla(h, lp, pos, z: dict):
    t = h.shape[0]
    q = (h @ c.widen(lp["w_q"])).reshape(t, z["heads"], z["nope"] + z["rope"])
    kv = h @ c.widen(lp["w_kv_a"])
    lat = c.rms_norm(kv[:, : z["rank"]], lp["kv_norm"], z["eps"])
    k_rope = c.rope(kv[:, None, z["rank"]:], pos, z["inv_freq"])  # [T, 1, rope]: one key for all heads
    q_rope = c.rope(q[..., z["nope"]:], pos, z["inv_freq"])
    k_nope = jnp.einsum("tr,rhn->thn", lat, lp["w_uk"].astype(c.F32))
    v = jnp.einsum("tr,rhv->thv", lat, lp["w_uv"].astype(c.F32))
    qf = jnp.concatenate([q[..., : z["nope"]], q_rope], axis=-1)
    kf = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (t, z["heads"], z["rope"]))], axis=-1)
    out = c.causal_attention(qf, kf, v, (z["nope"] + z["rope"]) ** -0.5)
    out = out * jax.nn.sigmoid(h @ lp["w_out_gate"].astype(c.F32))[..., None]
    return out.reshape(t, z["heads"] * z["dv"]) @ c.widen(lp["wo_mla"])


def route(h, lp, z: dict):
    """Routing weights f32[T, published experts]: ``w_j`` at a token's chosen
    experts, 0 elsewhere."""
    s = jax.nn.sigmoid(h @ lp["router"].astype(c.F32))
    biased = s + lp["router_bias"].astype(c.F32)
    t, e, groups = s.shape[0], s.shape[1], z["n_group"]
    if groups > 1:
        per_group = biased.reshape(t, groups, e // groups)
        group_score = jax.lax.top_k(per_group, 2)[0].sum(axis=-1)  # [T, groups]
        _, best = jax.lax.top_k(group_score, z["topk_group"])
        stays = jnp.zeros((t, groups), bool).at[jnp.arange(t)[:, None], best].set(True)
        biased = jnp.where(jnp.repeat(stays, e // groups, axis=1), biased, -jnp.inf)
    _, idx = jax.lax.top_k(biased, z["top_k"])
    rows = jnp.arange(t)[:, None]
    chosen = s[rows, idx]
    return jnp.zeros_like(s).at[rows, idx].set(z["factor"] * chosen / chosen.sum(axis=-1, keepdims=True))


def swiglu_of(h, lp, gate: str, up: str, down: str):
    return c.swiglu(h, c.widen(lp[gate]), c.widen(lp[up]), c.widen(lp[down]))


def routed_ffn(h, lp, z: dict):
    return swiglu_of(h, lp, "w_shared_gate", "w_shared_up", "w_shared_down") + held_experts_term(h, lp, route(h, lp, z), z)


def forward(params, hf: dict, tokens):
    z = shape_of(hf)
    group, dense = z["group"], z["dense"]
    pos = jnp.arange(tokens.shape[0])
    x = params["embed"][tokens].astype(c.F32)
    at = lambda tree, i: jax.tree.map(lambda a: a[i], tree)  # noqa: E731

    def block(x, i, attention):
        """Layer ``i``: its norms and FFN from ``dense_layers`` / ``layers``."""
        if isinstance(i, int) and i < dense:
            lp, ffn = at(params["dense_layers"], i), lambda h, lp: swiglu_of(h, lp, "w_gate", "w_up", "w_down")
        else:
            lp, ffn = at(params["layers"], i - dense), lambda h, lp: routed_ffn(h, lp, z)
        a = x + attention(c.rms_norm(x, lp["attn_norm"], z["eps"]))
        return a + ffn(c.rms_norm(a, lp["mlp_norm"], z["eps"]), lp)

    kda_of = lambda i: (lambda h: kda(h, at(params["kda_layers"], i - i // group), z))  # noqa: E731
    for p in range(z["layers"] // group):
        lo, hi = p * group, p * group + group - 1  # KDA layers [lo, hi), the MLA layer hi
        for i in range(lo, min(hi, dense)):  # leading dense FFNs, a layer at a time
            x = block(x, i, kda_of(i))
        first = max(lo, min(hi, dense))
        x, _ = jax.lax.scan(lambda x, i: (block(x, i, kda_of(i)), None), x, jnp.arange(first, hi))
        x = block(x, hi, lambda h: mla(h, at(params["mla_layers"], p), pos, z))
    return c.lm_head(c.rms_norm(x, params["norm_f"], z["eps"]), params)
