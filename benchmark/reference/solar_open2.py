"""Solar-Open2-250B (upstage; config.json, ``model_type`` ``solar_open2``): a
decoder of pre-norm residual blocks, x the residual stream, RMS norms
(``rms_norm_eps``):

    a = x + ATTN_i(RMS(x; g_in))
    y = a + FFN_i(RMS(a; g_post))

Layer i (from 0) is gated GQA attention if ``i in gqa_layers`` (0, 4, 8, ...:
every ``gqa_interval + 1``-th, so a period is [GQA, KDA, KDA, KDA]) and Kimi
Delta Attention (KDA, arXiv 2510.26692, in that paper's own form) otherwise;
every layer's FFN is routed (``first_k_dense_replace`` 0).

    KDA(h), token t (``linear_attn_config``: ``num_heads`` heads of ``head_dim``,
    every head its own key and value: ``num_kv_heads`` null; no rotary embedding):
      q~ = h W_q;  k~ = h W_k;  v~ = h W_v                  # each [heads x head_dim]
      z_t = silu(sum_{j=0..3} w_j * z~_{t-3+j})             # ``short_conv_kernel_size`` 4, per channel,
                                                            # zeros before the sequence's start
      q = l2norm(q) * head_dim**-0.5;  k = l2norm(k);  v as it is
      a_t = (h F_a) F_b  [heads x head_dim]                  # ``kda_use_full_proj`` false: low rank, F_a [hidden x r],
                                                            # F_b [r x heads head_dim], r = head_dim
      g_t = -exp(A_log) * softplus(a_t + dt_bias)            # A_log one value a head, dt_bias one a channel
      alpha_t = exp(g_t);  beta_t = 2 sigmoid(h W_beta)      # ``kda_allow_neg_eigval``: beta in (0, 2), one a head
      S_t = (I - beta_t k_t k_t^T) diag(alpha_t) S_{t-1} + beta_t k_t v_t^T   # S [key x value] a head, float32, S_0 = 0
      o_t = S_t^T q_t
      out = (RMS_head(o_t; g_o) * sigmoid((h G_a) G_b)) W_o  # the gate a value a channel, G_a [hidden x r], G_b [r x heads head_dim]

    GQA(h), token at position p (``num_attention_heads`` query heads over
    ``num_key_value_heads`` K/V heads of ``head_dim``; ``use_rope`` false: no
    rotary embedding, ``rope_theta`` and ``partial_rotary_factor`` idle; no head norm):
      q = h W_q;  k = h W_k;  v = h W_v                      # query head j reads K/V head j // (heads / kv heads)
      o = softmax_causal(q . k / sqrt(head_dim)) v
      out = (concat_h o * sigmoid(h W_g)) W_o                # ``use_gqa_gate``: W_g [hidden x heads head_dim], a value a channel

    FFN(h):
      s = sigmoid(h W_r) over the ``n_routed_experts_published`` (320) experts, in float32
      J = the ``num_experts_per_tok`` largest of (s + b), no groups      # b: the selection bias
      w_j = routed_scaling_factor * s_j / sum_{i in J} s_i   # ``norm_topk_prob``; the unbiased scores
      FFN(h) = SwiGLU_shared(h) + sum_{j in J} w_j SwiGLU_j(h)   # ``n_shared_experts`` 1 of ``moe_intermediate_size``

Departures and open points, each stated (the configuration file's ``assumed``):

- the catalog row spells out no decay form: the softplus one is Kimi Linear's
  (the row has no ``kda_safe_gate`` / ``kda_lower_bound``, Ling's keys for the
  bounded form), and the rank of both low-rank pairs is that paper's, the
  head's width;
- both gates are a value a channel;
- the l2 norm divides by ``sqrt(sum x^2 + 1e-6)`` (flash-linear-attention's
  ``l2norm``); the head norm's and every RMS norm's epsilon is ``rms_norm_eps``;
- sigmoid scores with a selection bias and renormalised weights are
  DeepSeek-V3's convention for these key names; the bias b (``router_bias`` of
  the served tree) is the published model's balancing bias, and the
  benchmark's weights make it zero (the CPU tests give it values);
- the shared expert is ``moe_intermediate_size`` wide (250B in all and 15B
  active only so); ``intermediate_size`` is no layer's width.

**The share.** Where the file states one (``n_routed_experts`` held here of
``n_routed_experts_published``, of rank ``expert_share_rank``), the served tree
holds experts ``[rank * held, (rank + 1) * held)`` only. The router still
scores every expert and normalises over all k choices; this reference adds the
held experts' terms and the shared expert (every chip computes it whole), and
leaves out what the other experts would add, as the program does. That partial
result goes on to the next layer.

The recurrence runs token by token (a ``lax.scan`` over time); widened float32
copies are made a layer at a time (a layer reads its leaves from the served
stacks by index: ``kda_layers`` and ``attn_layers`` the blocks by kind,
``layers`` the norms and FFNs) and an expert at a time.

``forward(params, hf, tokens)``: tokens i32[T] -> logits f32[T, vocab].
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import common as c


def shape_of(hf: dict) -> dict:
    """The sizes the equations use, by the config's own keys."""
    if hf.get("kda_use_full_proj") or hf.get("use_rope") or hf.get("first_k_dense_replace") or not hf.get("norm_topk_prob", True):
        raise ValueError("this reference knows low-rank KDA gates, attention without RoPE, routed FFNs in every layer "
                         "and renormalised weights only")
    linear, layers, period = hf["linear_attn_config"], hf["num_hidden_layers"], hf["gqa_interval"] + 1
    if [i for i in hf["gqa_layers"] if i < layers] != list(range(0, layers, period)) or linear.get("num_kv_heads") is not None:
        raise ValueError("this reference knows gqa_layers = range(0, layers, gqa_interval + 1) and one K/V a KDA head only")
    held = hf["n_routed_experts"]
    return dict(
        layers=layers, period=period, heads=linear["num_heads"], hd=linear["head_dim"], taps=linear["short_conv_kernel_size"],
        attn_heads=hf["num_attention_heads"], kv_heads=hf["num_key_value_heads"], attn_hd=hf["head_dim"],
        beta=2.0 if hf.get("kda_allow_neg_eigval") else 1.0, gated=bool(hf.get("use_gqa_gate")),
        eps=hf["rms_norm_eps"], top_k=hf["num_experts_per_tok"], held=held,
        routed=hf.get("n_routed_experts_published", held), first=hf.get("expert_share_rank", 0) * held,
        factor=float(hf.get("routed_scaling_factor", 1.0)),
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
    f32 = lambda name: lp[name].astype(c.F32)  # noqa: E731
    stream = lambda w, f: conv_silu(h @ c.widen(lp[w]), lp[f]).reshape(t, heads, hd)  # noqa: E731
    q, k, v = stream("wq", "conv_q"), stream("wk", "conv_k"), stream("wv", "conv_v")
    q, k = l2norm(q) * hd**-0.5, l2norm(k)
    a = ((h @ f32("w_decay_a")) @ f32("w_decay_b") + f32("dt_bias")).reshape(t, heads, hd)
    g = -jnp.exp(f32("a_log"))[None, :, None] * jax.nn.softplus(a)
    beta = z["beta"] * jax.nn.sigmoid(h @ f32("w_beta"))
    o = delta_rule(q, k, v, jnp.exp(g), beta)
    gate = jax.nn.sigmoid((h @ f32("w_out_gate_a")) @ f32("w_out_gate_b"))
    return (c.rms_norm(o, lp["o_norm"], z["eps"]).reshape(t, heads * hd) * gate) @ c.widen(lp["wo"])


def attention(h, lp, z: dict):
    t, heads, kv, hd = h.shape[0], z["attn_heads"], z["kv_heads"], z["attn_hd"]
    q = (h @ c.widen(lp["wq"])).reshape(t, heads, hd)
    k, v = ((h @ c.widen(lp[name])).reshape(t, kv, hd) for name in ("wk", "wv"))
    rep = lambda a: jnp.repeat(a, heads // kv, axis=1)  # noqa: E731  query head j reads K/V head j // (heads / kv)
    out = c.causal_attention(q, rep(k), rep(v), hd**-0.5).reshape(t, heads * hd)
    if z["gated"]:
        out = out * jax.nn.sigmoid(h @ lp["w_out_gate"].astype(c.F32))
    return out @ c.widen(lp["wo"])


def route(h, lp, z: dict):
    """Routing weights f32[T, published experts]: ``w_j`` at a token's chosen
    experts, 0 elsewhere."""
    s = jax.nn.sigmoid(h @ lp["router"].astype(c.F32))
    _, idx = jax.lax.top_k(s + lp["router_bias"].astype(c.F32), z["top_k"])
    rows = jnp.arange(s.shape[0])[:, None]
    chosen = s[rows, idx]
    return jnp.zeros_like(s).at[rows, idx].set(z["factor"] * chosen / chosen.sum(axis=-1, keepdims=True))


def experts_term(h, lp, mix):
    """sum_j mix[:, j] SwiGLU_j(h) over the experts ``lp`` holds, one expert
    widened at a time: ``mix`` f32[T, held] is their columns of the routing weights."""
    def one(acc, xs):
        wg, wu, wd, m = xs
        return acc + m[:, None] * c.swiglu(h, c.widen(wg), c.widen(wu), c.widen(wd)), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h), (lp["w_gate"], lp["w_up"], lp["w_down"], mix.T))
    return out


def shared_expert(h, lp):
    return c.swiglu(h, c.widen(lp["w_shared_gate"]), c.widen(lp["w_shared_up"]), c.widen(lp["w_shared_down"]))


def ffn(h, lp, z: dict):
    return shared_expert(h, lp) + experts_term(h, lp, route(h, lp, z)[:, z["first"]: z["first"] + z["held"]])


def forward(params, hf: dict, tokens):
    z = shape_of(hf)
    period = z["period"]
    x = params["embed"][tokens].astype(c.F32)
    at = lambda tree, i: jax.tree.map(lambda a: a[i], tree)  # noqa: E731

    def block(x, i, mixer):
        """Layer ``i``: its norms and FFN from ``layers``, its attention or KDA block ``mixer``."""
        lp = at(params["layers"], i)
        a = x + mixer(c.rms_norm(x, lp["attn_norm"], z["eps"]))
        return a + ffn(c.rms_norm(a, lp["mlp_norm"], z["eps"]), lp, z)

    def one_period(x, p):
        x = block(x, p * period, lambda h: attention(h, at(params["attn_layers"], p), z))
        # The period's KDA layers, a layer at a time (the scan widens one layer's leaves at once).
        kda_layer = lambda x, j: (  # noqa: E731
            block(x, p * period + 1 + j, lambda h: kda(h, at(params["kda_layers"], p * (period - 1) + j), z)), None)
        x, _ = jax.lax.scan(kda_layer, x, jnp.arange(period - 1))
        return x, None

    x, _ = jax.lax.scan(one_period, x, jnp.arange(z["layers"] // period))
    return c.lm_head(c.rms_norm(x, params["norm_f"], z["eps"]), params)
