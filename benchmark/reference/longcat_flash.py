"""LongCat-Flash (meituan-longcat; config.json of LongCat-Flash-Chat): a decoder
of *double* layers with a shortcut-connected MoE. One layer, x the residual
stream, RMS norms, i in {0, 1}:

    a0 = x  + MLA_0(RMS(x;  g_in0))
    h0 = RMS(a0; g_post0)
    m  = MoE(h0)                      # the shortcut: joins only at the layer's end
    b0 = a0 + FFN_0(h0)               # dense SwiGLU of width ffn_hidden_size
    a1 = b0 + MLA_1(RMS(b0; g_in1))
    h1 = RMS(a1; g_post1)
    y  = a1 + FFN_1(h1) + m

    MLA_i(h), token at position p (K and V materialised per head, not absorbed):
      q      = (RMS(h W_qa; g_q) W_qb) as [heads, nope + rope] * s_q
      kv     = h W_kva  (kv_lora_rank + rope);  c = RMS(kv[:rank]; g_kv) * s_kv
      k_rope = RoPE(kv[rank:], p), one for all heads;  q_rope = RoPE(q[:, nope:], p)
      k_nope = c W_uk as [heads, nope];  v = c W_uv as [heads, v_head_dim]
      out    = concat_h softmax_causal((q_nope . k_nope + q_rope . k_rope) / sqrt(nope + rope)) v  W_o

    MoE(h): s = softmax(h W_r) over every router output, in float32
      J = top-k of (s + b);  w_j = routed_scaling_factor * s_j   (not renormalised)
      MoE(h) = sum_{j in J, j < routed} w_j SwiGLU_j(h)  +  (sum_{j in J, j >= routed} w_j) h

The router has ``n_routed_experts_published + zero_expert_num`` outputs: the
published routed experts, then the identity ("zero-compute") experts.

Stated here because config.json does not:

- ``mla_scale_q_lora`` / ``mla_scale_kv_lora`` say that the two scales exist,
  s_q = sqrt(hidden / q_lora_rank) and s_kv = sqrt(hidden / kv_lora_rank), not
  where. As in the published modeling code: after the two latent norms, on
  both parts of the query and on the KV latent, not on the rope key.
- the selection bias b (``router_bias`` of the served tree) is the published
  model's balancing bias; the benchmark's weights make it zero.
- the two halves of a rope head rotate together (``common.rope``).

**The share.** Where the file states one (``n_routed_experts`` held here of
``n_routed_experts_published``, of rank ``expert_share_rank``), the served tree
holds experts ``[rank * held, (rank + 1) * held)`` only. The router still
scores every output; this reference adds the held experts' terms and the
identity term, and leaves out what the other experts would add, as the
program does.

Widened float32 copies are made a layer at a time (``lax.scan`` over the
stacked layers) and an expert at a time.

``forward(params, hf, tokens)``: tokens i32[T] -> logits f32[T, vocab].
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import common as c


def shape_of(hf: dict) -> dict:
    """The sizes the equations use, by the config's own keys."""
    if hf.get("attention_method", "MLA") != "MLA" or (hf.get("zero_expert_num") and hf.get("zero_expert_type") != "identity"):
        raise ValueError("this reference knows MLA attention and identity zero experts only")
    held = hf["n_routed_experts"]
    d = hf["hidden_size"]
    return dict(
        d=d, heads=hf["num_attention_heads"], rank=hf["kv_lora_rank"], nope=hf["qk_nope_head_dim"],
        rope=hf["qk_rope_head_dim"], dv=hf["v_head_dim"], eps=hf["rms_norm_eps"], top_k=hf["moe_topk"],
        held=held, routed=hf.get("n_routed_experts_published", held), first=hf.get("expert_share_rank", 0) * held,
        factor=float(hf.get("routed_scaling_factor", 1.0)),
        s_q=(d / hf["q_lora_rank"]) ** 0.5 if hf.get("mla_scale_q_lora") else 1.0,
        s_kv=(d / hf["kv_lora_rank"]) ** 0.5 if hf.get("mla_scale_kv_lora") else 1.0,
        inv_freq=c.rope_inv_freq(hf["qk_rope_head_dim"], float(hf["rope_theta"]), hf.get("rope_scaling")),
    )


def mla(h, sp, pos, z: dict):
    t = h.shape[0]
    q = c.rms_norm(h @ c.widen(sp["w_q_a"]), sp["q_norm"], z["eps"]) @ c.widen(sp["w_q_b"])
    q = q.reshape(t, z["heads"], z["nope"] + z["rope"]) * z["s_q"]
    kv = h @ c.widen(sp["w_kv_a"])
    lat = c.rms_norm(kv[:, : z["rank"]], sp["kv_norm"], z["eps"]) * z["s_kv"]
    k_rope = c.rope(kv[:, None, z["rank"]:], pos, z["inv_freq"])  # [T, 1, rope]: one key for all heads
    q_rope = c.rope(q[..., z["nope"]:], pos, z["inv_freq"])
    k_nope = jnp.einsum("tr,rhn->thn", lat, sp["w_uk"].astype(c.F32))
    v = jnp.einsum("tr,rhv->thv", lat, sp["w_uv"].astype(c.F32))
    qf = jnp.concatenate([q[..., : z["nope"]], q_rope], axis=-1)
    kf = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (t, z["heads"], z["rope"]))], axis=-1)
    out = c.causal_attention(qf, kf, v, (z["nope"] + z["rope"]) ** -0.5)
    return out.reshape(t, z["heads"] * z["dv"]) @ c.widen(sp["wo_mla"])


def route(h, lp, z: dict):
    """Routing weights f32[T, router outputs]: ``w_j`` at a token's chosen
    outputs, 0 elsewhere."""
    s = jax.nn.softmax(h @ lp["router"].astype(c.F32), axis=-1)
    _, idx = jax.lax.top_k(s + lp["router_bias"].astype(c.F32), z["top_k"])
    rows = jnp.arange(h.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, idx].set(z["factor"] * s[rows, idx])


def held_experts_term(h, lp, mix, z: dict):
    """The held experts' part: one expert widened at a time."""
    def one(acc, xs):
        wg, wu, wd, m = xs
        return acc + m[:, None] * c.swiglu(h, c.widen(wg), c.widen(wu), c.widen(wd)), None

    mine = mix[:, z["first"]: z["first"] + z["held"]]
    out, _ = jax.lax.scan(one, jnp.zeros_like(h), (lp["w_gate"], lp["w_up"], lp["w_down"], mine.T))
    return out


def zero_experts_term(h, mix, z: dict):
    """The identity experts' part: the token times its weights on them."""
    return mix[:, z["routed"]:].sum(axis=-1, keepdims=True) * h


def moe(h, lp, z: dict):
    mix = route(h, lp, z)
    return held_experts_term(h, lp, mix, z) + zero_experts_term(h, mix, z)


def dense_ffn(h, sp):
    return c.swiglu(h, c.widen(sp["w_gate"]), c.widen(sp["w_up"]), c.widen(sp["w_down"]))


def layer(x, lp, pos, z: dict):
    s0, s1 = lp["sub0"], lp["sub1"]
    a0 = x + mla(c.rms_norm(x, s0["attn_norm"], z["eps"]), s0, pos, z)
    h0 = c.rms_norm(a0, s0["mlp_norm"], z["eps"])
    m = moe(h0, lp, z)
    b0 = a0 + dense_ffn(h0, s0)
    a1 = b0 + mla(c.rms_norm(b0, s1["attn_norm"], z["eps"]), s1, pos, z)
    h1 = c.rms_norm(a1, s1["mlp_norm"], z["eps"])
    return a1 + dense_ffn(h1, s1) + m


def forward(params, hf: dict, tokens):
    z = shape_of(hf)
    pos = jnp.arange(tokens.shape[0])
    x = params["embed"][tokens].astype(c.F32)
    x, _ = jax.lax.scan(lambda x, lp: (layer(x, lp, pos, z), None), x, params["layers"])
    return c.lm_head(c.rms_norm(x, params["norm_f"], z["eps"]), params)
