"""Granite-4.0-H (ibm-granite; config.json, ``model_type`` ``granitemoehybrid``;
the published ``modeling_granitemoehybrid.py``: ``GraniteMoeHybridDecoderLayer``,
``GraniteMoeHybridMambaLayer.torch_forward``, ``GraniteMoeHybridRMSNormGated``,
``GraniteMoeHybridAttention``, ``GraniteMoeHybridTopKGating``,
``GraniteMoeHybridMoE``, ``GraniteMoeHybridMLP``): a decoder whose layer is,
by ``layer_types``, a Mamba-2 mixer that stands alone **or** GQA attention
without a rotary embedding, and whose every layer ends in routed experts
beside a shared one; x the residual stream, RMS norms (``rms_norm_eps``), r =
``residual_multiplier``:

    x0 = E[token] * embedding_multiplier
    h  = x + r * (MIX(RMS(x; g_in))  if layer_types[l] == "mamba"  else  ATT(RMS(x; g_in)))
    y  = h + r * (MOE(v) + SHARED(v)),  v = RMS(h; g_ff)
    logits = (RMS(x_L; g_f) E^T) / logits_scaling              # the head is the embedding (tied)

    ATT(u) (no bias, no q/k norm, no window, ``position_embedding_type`` "nope": nothing rotates):
      q = u W_q as [heads, hd];  k = u W_k, v' = u W_v as [kv heads, hd],  hd = hidden_size / heads
      o = softmax_causal(q . k * attention_multiplier) v'      # the scale is the config's, not hd ** -0.5
      ATT = concat_h(o) W_o

    MIX(u) (Mamba-2; H = ``mamba_n_heads`` heads of P = ``mamba_d_head`` channels = ``mamba_expand`` x hidden,
    N = ``mamba_d_state``, G = ``mamba_n_groups`` = 1: every head reads the one B and the one C):
      [z | xBC | dt] = u W_in                                  # widths H P, H P + 2 G N, H; no bias, no multiplier
      xBC_t = silu(sum_{j=0..3} w_j * xBC~_{t-3+j} + bias)     # ``mamba_d_conv`` 4 taps per channel, zeros before the start
      dt = softplus(dt + dt_bias);  A = -exp(A_log)            # one value a head; no clamp (limits (0, inf))
      S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T               # S [P x N] a head, float32, S_0 = 0
      y_t = S_t C_t + D x_t
      y   = RMS(y * silu(z); g)                                # the gate first, then ONE norm over all H P channels
      MIX = y W_out

    MOE(v): logits = v W_r (float32, no bias); the ``num_experts_per_tok`` largest logits; their softmax
      over those alone is the gate; sum_e gate_e (silu(v W_gate,e) * (v W_up,e)) W_down,e
      # (published: ``input_linear`` [2 f, d] a expert, its first half the gated one)
    SHARED(v) = (silu(v W_sg) * (v W_su)) W_sd at ``shared_intermediate_size``, ungated

Open points, each stated (the configuration file's ``assumed``):
``mamba_chunk_size`` tiles the published kernels and changes no mathematics, so
the recurrence here runs token by token (a ``lax.scan`` over time, the state in
the published ``[P, N]`` orientation, not the chunked form and not the
program's layout); ``rope_theta`` rotates nothing. Settings the equations
above do not cover are refused by name (``shape_of``).

A layer's weights are widened to float32 a layer at a time (runs of layers of
one kind are scanned), an expert one at a time and the tied head a slice of
the vocabulary at a time (the embedding whole in float32 would be 1.6 GB), so
that the reference fits beside the served model on its chip (under 1 GB of
transients; all ten layers unrolled took 6.3 GB and did not: PERF.md, PR 49). The served tree:
``layers`` holds every layer's two norms and FFN, ``ssm_layers`` the mixers
and ``attn_layers`` the attention blocks, each stacked in layer order.
Nothing is imported from the program. ``forward(params, hf, tokens)``: tokens
i32[T] -> logits f32[T, vocab].
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import common as c

#: Slices of the vocabulary the tied head is widened in.
HEAD_SLICES = 8


def shape_of(hf: dict) -> dict:
    """The sizes and multipliers the equations use, by the config's own keys."""
    for key, known in (("mamba_conv_bias", True), ("mamba_proj_bias", False), ("attention_bias", False),
                       ("position_embedding_type", "nope"), ("hidden_act", "silu"), ("normalization_function", "rmsnorm"),
                       ("tie_word_embeddings", True), ("mamba_n_groups", 1)):
        if hf.get(key, known) != known:
            raise ValueError(f"this reference knows {key} {known!r} only, not {hf.get(key)!r}")
    heads, p = hf["mamba_n_heads"], hf["mamba_d_head"]
    inner = int(hf["mamba_expand"] * hf["hidden_size"])
    if heads * p != inner:
        raise ValueError(f"mamba_n_heads {heads} x mamba_d_head {p} against mamba_expand x hidden_size {inner}")
    kinds = list(hf["layer_types"][: hf["num_hidden_layers"]])
    if len(kinds) < hf["num_hidden_layers"] or set(kinds) - {"mamba", "attention"}:
        raise ValueError(f"layer_types {sorted(set(kinds))} over {len(kinds)} entries")
    return dict(
        kinds=kinds, heads=hf["num_attention_heads"], kv_heads=hf["num_key_value_heads"],
        hd=hf["hidden_size"] // hf["num_attention_heads"], eps=hf["rms_norm_eps"], scale=float(hf["attention_multiplier"]),
        ssm_heads=heads, p=p, n=hf["mamba_d_state"], inner=inner, top_k=hf["num_experts_per_tok"],
        r=float(hf["residual_multiplier"]), embed=float(hf["embedding_multiplier"]), logits=float(hf["logits_scaling"]),
    )


def attention(u, lp, z: dict):
    t, heads, kv, hd = u.shape[0], z["heads"], z["kv_heads"], z["hd"]
    q = (u @ c.widen(lp["wq"])).reshape(t, heads, hd)
    k, v = ((u @ c.widen(lp[name])).reshape(t, kv, hd) for name in ("wk", "wv"))
    rep = lambda a: jnp.repeat(a, heads // kv, axis=1)  # noqa: E731  query head h reads KV head h // (heads / kv)
    return c.causal_attention(q, rep(k), rep(v), z["scale"]).reshape(t, heads * hd) @ c.widen(lp["wo"])


def conv_silu(x, filt, bias):
    """xBC_t = silu(sum_j w_j xBC~_{t-taps+1+j} + bias) per channel: x [T, W], filt [taps, W]."""
    taps, t = filt.shape[0], x.shape[0]
    full = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1]), c.F32), x])
    return jax.nn.silu(sum(full[j: j + t] * filt[j].astype(c.F32) for j in range(taps)) + bias)


def selective_scan(x, b, cc, dt, a, d):
    """S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T, y_t = S_t C_t + D x_t, token by
    token from S_0 = 0, S [H, P, N] as published: x [T, H, P], b cc [T, N] (one
    group), dt [T, H], a d [H]."""

    def token(s, xs):
        x_t, b_t, c_t, dt_t = xs
        s = jnp.exp(dt_t * a)[:, None, None] * s + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        return s, jnp.einsum("hpn,n->hp", s, c_t) + d[:, None] * x_t

    _, y = jax.lax.scan(token, jnp.zeros((x.shape[1], x.shape[2], b.shape[1]), c.F32), (x, b, cc, dt))
    return y


def mixer(u, lp, z: dict):
    t, heads, p, n, inner = u.shape[0], z["ssm_heads"], z["p"], z["n"], z["inner"]
    f32 = lambda name: lp[name].astype(c.F32)  # noqa: E731
    proj = u @ f32("w_ssm_in")
    gate, xbc, dt = proj[:, :inner], proj[:, inner: 2 * inner + 2 * n], proj[:, 2 * inner + 2 * n:]
    xbc = conv_silu(xbc, lp["ssm_conv"], f32("ssm_conv_bias"))
    y = selective_scan(xbc[:, :inner].reshape(t, heads, p), xbc[:, inner: inner + n], xbc[:, inner + n:],
                       jax.nn.softplus(dt + f32("ssm_dt_bias")), -jnp.exp(f32("ssm_a_log")), f32("ssm_d"))
    y = y.reshape(t, inner) * jax.nn.silu(gate)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True) + z["eps"])
    return (y * f32("ssm_norm")) @ f32("w_ssm_out")


def gates(v, lp, z: dict):
    """[T, E]: the softmax over a token's ``top_k`` largest router logits, at
    the experts that hold them; 0 elsewhere."""
    logits = v @ lp["router"].astype(c.F32)
    top, idx = jax.lax.top_k(logits, z["top_k"])
    return jnp.zeros_like(logits).at[jnp.arange(v.shape[0])[:, None], idx].set(jax.nn.softmax(top, axis=-1))


def ffn(v, lp, z: dict):
    """Every token through every expert, one expert widened at a time, mixed
    by its gate (0 for experts not chosen); then the shared expert."""

    def one(acc, xs):
        wg, wu, wd, m = xs
        return acc + m[:, None] * c.swiglu(v, c.widen(wg), c.widen(wu), c.widen(wd)), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(v), (lp["w_gate"], lp["w_up"], lp["w_down"], gates(v, lp, z).T))
    return routed + c.swiglu(v, c.widen(lp["w_shared_gate"]), c.widen(lp["w_shared_up"]), c.widen(lp["w_shared_down"]))


def tied_head(x, embed, slices: int = HEAD_SLICES):
    """[T, d] -> [T, vocab] against the embedding itself, a slice of the vocabulary at a time."""
    vocab, d = embed.shape
    if vocab % slices:
        return x @ embed.astype(c.F32).T
    out = jax.lax.map(lambda e: x @ e.astype(c.F32).T, embed.reshape(slices, vocab // slices, d))
    return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], vocab)


def forward(params, hf: dict, tokens):
    z = shape_of(hf)
    at = lambda tree, i: jax.tree.map(lambda a: a[i], tree)  # noqa: E731
    x = params["embed"][tokens].astype(c.F32) * z["embed"]

    def layer(kind: str, first: int, first_of_kind: int):
        """Layer ``first + j``, the ``first_of_kind + j``-th of its kind, on the stream."""
        blocks, run = (params["ssm_layers"], mixer) if kind == "mamba" else (params["attn_layers"], attention)

        def step(x, j):
            lp = at(params["layers"], first + j)
            h = x + z["r"] * run(c.rms_norm(x, lp["attn_norm"], z["eps"]), at(blocks, first_of_kind + j), z)
            return h + z["r"] * ffn(c.rms_norm(h, lp["mlp_norm"], z["eps"]), lp, z), None

        return step

    # Runs of layers of one kind, each a scan over its layers (a layer's leaves read from the stacks by index), so
    # that one layer's weights are widened at a time whatever the depth.
    seen, i = {"mamba": 0, "attention": 0}, 0
    while i < len(z["kinds"]):
        kind, n = z["kinds"][i], 1
        while i + n < len(z["kinds"]) and z["kinds"][i + n] == kind:
            n += 1
        x, _ = jax.lax.scan(layer(kind, i, seen[kind]), x, jnp.arange(n))
        seen[kind], i = seen[kind] + n, i + n
    return tied_head(c.rms_norm(x, params["norm_f"], z["eps"]), params["embed"]) / z["logits"]
