"""Falcon-H1 (tiiuae; config.json, ``model_type`` ``falcon_h1``; the published
``modeling_falcon_h1.py``: ``FalconH1DecoderLayer``, ``FalconH1Mixer.torch_forward``,
``FalconH1RMSNormGated``, ``FalconH1MLP``, ``compute_mup_vector``): a decoder
whose every layer runs a Mamba-2 mixer and GQA attention **side by side** on
one normed input and sums their outputs; x the residual stream, RMS norms
(``rms_norm_eps``), a muP multiplier wherever the published code has one:

    x0 = E[token] * embedding_multiplier
    u  = RMS(x; g_in)
    h  = x + MIX(u) * ssm_out_multiplier + ATT(u * attention_in_multiplier) * attention_out_multiplier
    y  = h + FFN(RMS(h; g_ff))
    logits = (RMS(x_L; g_f) W_head) * lm_head_multiplier

    ATT(v), token at position p (no bias, no q/k norm, no window):
      q = v W_q as [heads, head_dim];  k = (v W_k) * key_multiplier as [kv heads, head_dim];  v' = v W_v
      q, k = RoPE(., p) on the whole head                      # ``rope_theta``, no scaling
      o = softmax_causal(q . k / sqrt(head_dim)) v'            # K and V repeated over their group's query heads
      ATT = concat_h(o) W_o

    MIX(u) (Mamba-2; H = ``mamba_n_heads`` heads of P = ``mamba_d_head`` channels, N = ``mamba_d_state``,
    G = ``mamba_n_groups``: head h reads the B and C of group h // (H / G)):
      [z | x | B | C | dt] = ((u * ssm_in_multiplier) W_in) * mup   # mup: ``ssm_multipliers[0..4]`` by section;
                                                               # widths H P, H P, G N, G N, H
      xBC_t = silu(sum_{j=0..3} w_j * xBC~_{t-3+j} + bias)     # ``mamba_d_conv`` 4 taps per channel,
                                                               # zeros before the sequence's start
      dt = softplus(dt + dt_bias);  A = -exp(A_log)            # one value a head; no clamp (limits (0, inf))
      S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T               # S [P x N] a head, float32, S_0 = 0
      y_t = S_t C_t + D x_t
      y   = RMS_group(y * silu(z); g)                          # ``mamba_norm_before_gate`` false: the gate first,
                                                               # then a norm over each of the G groups of H P / G channels
      MIX = y W_out

    FFN(v) = ((v W_up) * silu((v W_gate) * mlp_multipliers[0])) W_down * mlp_multipliers[1]

Open points, each stated (the configuration file's ``assumed``): the two halves
of a rope head rotate together (``common.rope``), which is the published
``rotate_half``; ``mamba_chunk_size`` tiles the published kernels and changes
no mathematics, so the recurrence here runs token by token (a ``lax.scan``
over time, not a chunked form); ``mamba_d_ssm`` overrides ``mamba_expand``.
Settings the equations above do not cover are refused by name (``shape_of``).

The widest matrices (the FFN's) are widened to float32 a block of columns at
a time and the head a slice of the vocabulary at a time, so that the reference
fits beside the served model on its chip. Nothing is imported from the
program. ``forward(params, hf, tokens)``: tokens i32[T] -> logits f32[T, vocab].
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import common as c

#: Column blocks the FFN is widened in (3 x 5120 x 21,504 float32 whole is 1.3 GB a layer).
FFN_BLOCKS = 4


def shape_of(hf: dict) -> dict:
    """The sizes and multipliers the equations use, by the config's own keys."""
    for key, known in (("mamba_norm_before_gate", False), ("mamba_rms_norm", True), ("mamba_conv_bias", True), ("mamba_proj_bias", False),
                       ("projectors_bias", False), ("attention_bias", False), ("mlp_bias", False),
                       ("attn_layer_indices", None), ("hidden_act", "silu")):
        if hf.get(key, known) != known and (hf.get(key) or known):
            raise ValueError(f"this reference knows {key} {known!r} only, not {hf.get(key)!r}")
    heads, p, groups = hf["mamba_n_heads"], hf["mamba_d_head"], hf["mamba_n_groups"]
    inner = hf["mamba_d_ssm"] if hf.get("mamba_d_ssm") is not None else int(hf["mamba_expand"] * hf["hidden_size"])
    if heads * p != inner or heads % groups:
        raise ValueError(f"mamba_n_heads {heads} x mamba_d_head {p} against mamba_d_ssm {inner} in {groups} groups")
    gn = groups * hf["mamba_d_state"]
    mup = jnp.concatenate([jnp.full((n,), m, c.F32) for n, m in zip((inner, inner, gn, gn, heads), hf["ssm_multipliers"])])
    return dict(
        heads=hf["num_attention_heads"], kv_heads=hf["num_key_value_heads"], hd=hf["head_dim"], eps=hf["rms_norm_eps"],
        inv_freq=c.rope_inv_freq(hf["head_dim"], float(hf["rope_theta"]), hf.get("rope_scaling")),
        ssm_heads=heads, p=p, n=hf["mamba_d_state"], groups=groups, inner=inner, gn=gn, mup=mup,
        mlp=[float(m) for m in hf["mlp_multipliers"]],
        mult={k: float(hf[f"{k}_multiplier"]) for k in ("embedding", "lm_head", "attention_in", "attention_out",
                                                        "key", "ssm_in", "ssm_out")},
    )


def attention(v, lp, pos, z: dict):
    t, heads, kv, hd = v.shape[0], z["heads"], z["kv_heads"], z["hd"]
    q = c.rope((v @ c.widen(lp["wq"])).reshape(t, heads, hd), pos, z["inv_freq"])
    k = c.rope(((v @ c.widen(lp["wk"])) * z["mult"]["key"]).reshape(t, kv, hd), pos, z["inv_freq"])
    val = (v @ c.widen(lp["wv"])).reshape(t, kv, hd)
    rep = lambda a: jnp.repeat(a, heads // kv, axis=1)  # noqa: E731  query head h reads KV head h // (heads / kv)
    return c.causal_attention(q, rep(k), rep(val), hd**-0.5).reshape(t, heads * hd) @ c.widen(lp["wo"])


def conv_silu(x, filt, bias):
    """xBC_t = silu(sum_j w_j xBC~_{t-taps+1+j} + bias) per channel: x [T, W], filt [taps, W]."""
    taps, t = filt.shape[0], x.shape[0]
    full = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1]), c.F32), x])
    return jax.nn.silu(sum(full[j: j + t] * filt[j].astype(c.F32) for j in range(taps)) + bias)


def selective_scan(x, b, cc, dt, a, d):
    """S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T, y_t = S_t C_t + D x_t, token by
    token from S_0 = 0: x [T, H, P], b cc [T, H, N], dt [T, H], a d [H]."""

    def token(s, xs):
        x_t, b_t, c_t, dt_t = xs
        s = jnp.exp(dt_t * a)[:, None, None] * s + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return s, jnp.einsum("hpn,hn->hp", s, c_t) + d[:, None] * x_t

    _, y = jax.lax.scan(token, jnp.zeros((x.shape[1], x.shape[2], b.shape[2]), c.F32), (x, b, cc, dt))
    return y


def mixer(u, lp, z: dict):
    t, heads, p, n, groups, inner, gn = u.shape[0], z["ssm_heads"], z["p"], z["n"], z["groups"], z["inner"], z["gn"]
    f32 = lambda name: lp[name].astype(c.F32)  # noqa: E731
    proj = ((u * z["mult"]["ssm_in"]) @ f32("w_ssm_in")) * z["mup"]
    gate, xbc, dt = proj[:, :inner], proj[:, inner: 2 * inner + 2 * gn], proj[:, 2 * inner + 2 * gn:]
    xbc = conv_silu(xbc, lp["ssm_conv"], f32("ssm_conv_bias"))
    x = xbc[:, :inner].reshape(t, heads, p)
    per_head = lambda a: jnp.repeat(a.reshape(t, groups, n), heads // groups, axis=1)  # noqa: E731
    y = selective_scan(x, per_head(xbc[:, inner: inner + gn]), per_head(xbc[:, inner + gn:]),
                       jax.nn.softplus(dt + f32("ssm_dt_bias")), -jnp.exp(f32("ssm_a_log")), f32("ssm_d"))
    y = (y.reshape(t, inner) * jax.nn.silu(gate)).reshape(t, groups, inner // groups)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True) + z["eps"])
    return (y.reshape(t, inner) * f32("ssm_norm")) @ f32("w_ssm_out")


def _blocks(leaf, axis: int):
    """A matrix (int8 codes with a scale per output channel, or plain) cut
    into ``FFN_BLOCKS`` along ``axis``, a leading axis over the blocks; whole
    where the width does not divide."""
    if isinstance(leaf, dict) and "qw" not in leaf:
        raise ValueError("the reference reads int8 or plain leaves only")
    width = (leaf["qw"] if isinstance(leaf, dict) else leaf).shape[axis]
    nb = FFN_BLOCKS if width % FFN_BLOCKS == 0 else 1

    def cut(a, ax):
        shape = a.shape[:ax] + (nb, a.shape[ax] // nb) + a.shape[ax + 1:]
        return jnp.moveaxis(a.reshape(shape), ax, 0)

    if not isinstance(leaf, dict):
        return cut(leaf, axis)
    # The scale runs over the output channels: cut with the columns, whole with the rows.
    scale = cut(leaf["scale"], 0) if axis == 1 else jnp.broadcast_to(leaf["scale"], (nb, *leaf["scale"].shape))
    return {"qw": cut(leaf["qw"], axis), "scale": scale}


def ffn(v, lp, z: dict):
    def block(ws):
        wg, wu, wd = ws
        return ((v @ c.widen(wu)) * jax.nn.silu((v @ c.widen(wg)) * z["mlp"][0])) @ c.widen(wd)

    parts = jax.lax.map(block, (_blocks(lp["w_gate"], 1), _blocks(lp["w_up"], 1), _blocks(lp["w_down"], 0)))
    return parts.sum(axis=0) * z["mlp"][1]


def forward(params, hf: dict, tokens):
    z = shape_of(hf)
    pos = jnp.arange(tokens.shape[0])
    x = params["embed"][tokens].astype(c.F32) * z["mult"]["embedding"]

    def layer(x, lp):
        u = c.rms_norm(x, lp["attn_norm"], z["eps"])
        h = (x + mixer(u, lp, z) * z["mult"]["ssm_out"]
             + attention(u * z["mult"]["attention_in"], lp, pos, z) * z["mult"]["attention_out"])
        return h + ffn(c.rms_norm(h, lp["mlp_norm"], z["eps"]), lp, z), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    return c.lm_head(c.rms_norm(x, params["norm_f"], z["eps"]), params) * z["mult"]["lm_head"]
