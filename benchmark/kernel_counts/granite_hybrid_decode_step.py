"""Bytes and operations one decode step *needs* (not what a program moves),
for a decoder whose layer is, by ``layer_types``, a Mamba-2 mixer that stands
alone (a slot of recurrent state, no pages) or GQA attention (pages, no slot),
and whose every layer ends in routed experts beside a shared one, under a tied
head (Granite-4.0-H; ``granitemoehybrid``'s config keys).

Per step of ``rows`` sequences with contexts ``contexts_total`` (one attention
layer's key tokens, summed over the rows):

- every weight outside the routed experts once: the attention layers' ``wq wk
  wv wo`` and every layer's shared expert at ``weight_bytes`` each; the router,
  the Mamba layers' two projections (``w_ssm_in``, ``w_ssm_out``), which the
  program serves in bf16 whatever the rest is served in, their filter, bias,
  per-head constants and gated norm, and a layer's two norms, at 2; **the
  tied head, which is the bf16 embedding itself, at 2 whatever
  ``weight_bytes`` says**;
- of the E routed experts of a layer, those some row chose (``experts_step``):
  with a router that spreads its choices evenly, E * (1 - (1 - k / E) ** rows)
  of them, three matrices each; ``experts_touched`` overrides the formula with
  a count (a layer's mean);
- **the recurrent state, read and written once a row a Mamba layer**
  (``state_step``): heads x state x head channels float32 each way, 4,194,304
  B a row a layer at 128 heads of 64 x 128, and the conv state (the last taps
  - 1 inputs of x, B and C, 2 bytes a value) each way beside it;
- K and V of the rows' contexts once an attention layer: 2 x kv heads x
  head_dim values of 2 bytes a token;
- the embedding rows of the input tokens.

Operations: 2 per weight a token meets (k experts, not E), 6 per state element
a Mamba layer (the decay, the rank-one update, the product with C) and the
attention's.

``attention_step`` gives the bytes and operations of the GQA attention kernel
alone, the attention layers held, from the key tokens one of them has to
visit (the STEP record's ``kv_tokens_full``); ``state_step`` those of the
mixer's decode kernel alone, all Mamba layers, from the rows whose slot the
step touched (``state_rows``): the state each way, and the kernel's small
inputs (``x``, and B and C of the one group; the decay and ``dt`` are a number
a head) and its output.
"""

from __future__ import annotations


def expected_experts_touched(experts: int, top_k: int, rows: float) -> float:
    return experts * (1.0 - (1.0 - top_k / experts) ** rows)


def _sizes(hf: dict) -> dict:
    d, heads, kv = hf["hidden_size"], hf["num_attention_heads"], hf["num_key_value_heads"]
    hd = d // heads
    h, p, n, g, taps = hf["mamba_n_heads"], hf["mamba_d_head"], hf["mamba_d_state"], hf["mamba_n_groups"], hf["mamba_d_conv"]
    kinds = list(hf["layer_types"][: hf["num_hidden_layers"]])
    inner = h * p
    conv_dim = inner + 2 * g * n
    return dict(
        d=d, heads=heads, kv=kv, hd=hd, layers=len(kinds), mamba_layers=kinds.count("mamba"),
        attn_layers=kinds.count("attention"), vocab=hf["vocab_size"],
        attn=d * (heads + 2 * kv) * hd + heads * hd * d,  # int8 leaves
        experts=hf["num_local_experts"], top_k=hf["num_experts_per_tok"], expert=3 * d * hf["intermediate_size"],
        shared=3 * d * hf["shared_intermediate_size"], router=d * hf["num_local_experts"],
        mixer_proj=d * (inner + conv_dim + h) + inner * d,  # bf16 leaves
        mixer_plain=(taps + 1) * conv_dim + 3 * h + inner,  # filter and bias, constants, gated norm
        inner=inner, group_values=2 * g * n, state=h * n * p, conv=(taps - 1) * conv_dim,
    )


def experts_step(hf: dict, *, experts_touched_total: float, weight_bytes: float = 1.0) -> dict:
    """What the routed experts of one step need, all layers: the three
    matrices of every expert some row chose. ``experts_touched_total`` is the
    distinct (layer, expert) pairs with a row. Also the (token, choice) pairs
    the step's routers make of one token."""
    z = _sizes(hf)
    return {"bytes": float(experts_touched_total * z["expert"] * weight_bytes),
            "choices_per_token": z["top_k"] * z["layers"]}


def state_step(hf: dict, *, rows: float) -> dict:
    """What the mixer's decode kernel of one step needs, all Mamba layers: each
    row's state read once and written once in float32; ``x`` in and the output
    out (float32, a head channel each) and the group's B and C.
    ``state_bytes`` is the state's part alone; ``conv_bytes`` the conv state
    each way, which the conv kernel moves, not this one."""
    z = _sizes(hf)
    state_bytes = z["mamba_layers"] * rows * 2 * z["state"] * 4
    io_bytes = z["mamba_layers"] * rows * (2 * z["inner"] + z["group_values"]) * 4
    return {"bytes": float(state_bytes + io_bytes), "state_bytes": float(state_bytes),
            "conv_bytes": float(z["mamba_layers"] * rows * 2 * z["conv"] * 2),
            "flops": float(z["mamba_layers"] * rows * 6 * z["state"])}


def attention_step(hf: dict, *, kv_tokens_full: float, kv_tokens_window: float = 0.0, rows: float,
                   new_tokens: float = 1.0) -> dict:
    """What the GQA attention kernel of one step needs, the attention layers
    held. ``kv_tokens_window`` is taken for the harness's sake and unused: no
    layer has a window. Operations: scores and the weighted sum, 4 x heads x
    head_dim a (query, key) pair; a chunk's queries see on average the visited
    keys less half the chunk (the causal triangle)."""
    del kv_tokens_window
    z = _sizes(hf)
    cache_bytes = z["attn_layers"] * kv_tokens_full * 2 * z["kv"] * z["hd"] * 2
    qo_bytes = z["attn_layers"] * rows * new_tokens * 2 * z["heads"] * z["hd"] * 2
    pairs = new_tokens * kv_tokens_full - rows * new_tokens * (new_tokens - 1) / 2
    return {"bytes": float(cache_bytes + qo_bytes), "cache_bytes": float(cache_bytes),
            "flops": float(z["attn_layers"] * 4 * z["heads"] * z["hd"] * pairs)}


def decode_step(hf: dict, *, rows: float, contexts_total: float, weight_bytes: float = 1.0,
                experts_touched: float | None = None) -> dict:
    z = _sizes(hf)
    touched = expected_experts_touched(z["experts"], z["top_k"], rows) if experts_touched is None else experts_touched
    experts = experts_step(hf, experts_touched_total=z["layers"] * touched, weight_bytes=weight_bytes)
    state = state_step(hf, rows=rows)
    attn = attention_step(hf, kv_tokens_full=contexts_total, rows=rows)
    ffn_outside = z["shared"] * weight_bytes + (z["router"] + 2 * z["d"]) * 2  # the shared expert; the router and the two norms
    mixer_block = (z["mixer_proj"] + z["mixer_plain"]) * 2
    head = z["d"] * z["vocab"] * 2  # the tied embedding, bf16
    nbytes = (z["layers"] * ffn_outside + experts["bytes"] + z["mamba_layers"] * mixer_block
              + z["attn_layers"] * z["attn"] * weight_bytes + state["state_bytes"] + state["conv_bytes"]
              + attn["cache_bytes"] + head + rows * z["d"] * 2)
    per_token = (z["layers"] * (z["shared"] + z["router"] + 2 * z["d"] + z["top_k"] * z["expert"])
                 + z["mamba_layers"] * (z["mixer_proj"] + z["mixer_plain"]) + z["attn_layers"] * z["attn"] + z["d"] * z["vocab"])
    return {"bytes": float(nbytes), "flops": float(2 * rows * per_token + attn["flops"] + state["flops"]),
            "experts_touched": float(touched), "experts_bytes": experts["bytes"],
            "state_bytes": state["state_bytes"] + state["conv_bytes"], "cache_bytes": float(attn["cache_bytes"]),
            "ffn_outside_experts_bytes": float(ffn_outside), "mixer_block_bytes": float(mixer_block),
            "mixer_proj_bytes": float(z["mixer_proj"] * 2), "attention_block_bytes": float(z["attn"] * weight_bytes),
            "head_bytes": float(head)}


def least_seconds(counts: dict, peaks: dict) -> tuple[float, str]:
    """Least time the chip could take, and which bound sets it."""
    t_mem = counts["bytes"] / peaks["hbm_bytes_per_s"]
    t_flop = counts["flops"] / peaks["bf16_flops_per_s"]  # the MXU multiplies bf16: int8 is widened
    return (t_mem, "memory") if t_mem >= t_flop else (t_flop, "compute")
