"""Bytes and operations one decode step *needs* (not what a program moves),
for a decoder whose every layer runs a Mamba-2 mixer beside GQA attention
(Falcon-H1; ``falcon_h1``'s config keys) and then a dense SwiGLU FFN: every
layer holds a slab of K/V pages *and* a slot of recurrent state.

Per step of ``rows`` sequences with contexts ``contexts_total`` (one layer's
key tokens, summed over the rows):

- every weight once: a layer's attention projections (``wq wk wv wo``) and its
  FFN (``w_gate w_up w_down``) and the head at ``weight_bytes`` each; the
  mixer's two projections (``w_ssm_in``, ``w_ssm_out``), which the program
  serves in bf16 whatever the rest is served in, its filter, bias, per-head
  constants and gated norm, and the layer's two norms, at 2;
- **the recurrent state, read and written once a row a layer**
  (``state_step``): heads x state x head channels float32 each way, 8.39 MB a
  row a layer at 32 heads of 256 x 128, and the conv state (the last taps - 1
  inputs of x, B and C, 2 bytes a value) each way beside it;
- K and V of the rows' contexts once a layer: 2 x kv heads x head_dim values
  of 2 bytes a token;
- the embedding rows of the input tokens.

Operations: 2 per weight a token meets, 6 per state element a layer (the
decay, the rank-one update, the product with C) and the attention's.

``attention_step`` gives the bytes and operations of the GQA attention kernel
alone, all layers, from the key tokens one layer has to visit (the STEP
record's ``kv_tokens_full``); ``state_step`` those of the mixer's decode
kernel alone, all layers, from the rows whose slot the step touched
(``state_rows``): the state each way, and the kernel's small inputs (``dt x``
and the decay a head channel, B and C a group) and its output.
"""

from __future__ import annotations


def _sizes(hf: dict) -> dict:
    d, heads, kv, hd = hf["hidden_size"], hf["num_attention_heads"], hf["num_key_value_heads"], hf["head_dim"]
    h, p, n, g, taps = hf["mamba_n_heads"], hf["mamba_d_head"], hf["mamba_d_state"], hf["mamba_n_groups"], hf["mamba_d_conv"]
    inner = h * p
    conv_dim = inner + 2 * g * n
    return dict(
        d=d, heads=heads, kv=kv, hd=hd, layers=hf["num_hidden_layers"], vocab=hf["vocab_size"],
        attn=d * (heads + 2 * kv) * hd + heads * hd * d, ffn=3 * d * hf["intermediate_size"],  # int8 leaves
        mixer_proj=d * (inner + conv_dim + h) + inner * d,  # bf16 leaves
        plain=(taps + 1) * conv_dim + 3 * h + inner + 2 * d,  # filter and bias, constants, norms
        inner=inner, group_values=2 * g * n, state=h * n * p, conv=(taps - 1) * conv_dim,
    )


def state_step(hf: dict, *, rows: float) -> dict:
    """What the mixer's decode kernel of one step needs, all layers: each
    row's state read once and written once in float32; ``dt x`` and the decay
    in and the output out (float32, a head channel each) and a group's B and
    C. ``state_bytes`` is the state's part alone; ``conv_bytes`` the conv
    state each way, which XLA moves, not the kernel."""
    z = _sizes(hf)
    state_bytes = z["layers"] * rows * 2 * z["state"] * 4
    io_bytes = z["layers"] * rows * (3 * z["inner"] + z["group_values"]) * 4
    return {"bytes": float(state_bytes + io_bytes), "state_bytes": float(state_bytes),
            "conv_bytes": float(z["layers"] * rows * 2 * z["conv"] * 2),
            "flops": float(z["layers"] * rows * 6 * z["state"])}


def attention_step(hf: dict, *, kv_tokens_full: float, kv_tokens_window: float = 0.0, rows: float,
                   new_tokens: float = 1.0) -> dict:
    """What the GQA attention kernel of one step needs, all layers.
    ``kv_tokens_window`` is taken for the harness's sake and unused: no layer
    has a window. Operations: scores and the weighted sum, 4 x heads x
    head_dim a (query, key) pair; a chunk's queries see on average the visited
    keys less half the chunk (the causal triangle)."""
    del kv_tokens_window
    z = _sizes(hf)
    cache_bytes = z["layers"] * kv_tokens_full * 2 * z["kv"] * z["hd"] * 2
    qo_bytes = z["layers"] * rows * new_tokens * 2 * z["heads"] * z["hd"] * 2
    pairs = new_tokens * kv_tokens_full - rows * new_tokens * (new_tokens - 1) / 2
    return {"bytes": float(cache_bytes + qo_bytes), "cache_bytes": float(cache_bytes),
            "flops": float(z["layers"] * 4 * z["heads"] * z["hd"] * pairs)}


def decode_step(hf: dict, *, rows: float, contexts_total: float, weight_bytes: float = 1.0,
                experts_touched: float | None = None) -> dict:
    """``experts_touched`` is taken for the harness's sake and unused: no layer routes."""
    del experts_touched
    z = _sizes(hf)
    state = state_step(hf, rows=rows)
    attn = attention_step(hf, kv_tokens_full=contexts_total, rows=rows)
    layer_bytes = (z["attn"] + z["ffn"]) * weight_bytes + (z["mixer_proj"] + z["plain"]) * 2
    nbytes = (z["layers"] * layer_bytes + state["state_bytes"] + state["conv_bytes"] + attn["cache_bytes"]
              + z["d"] * z["vocab"] * weight_bytes + rows * z["d"] * 2)
    per_token = z["layers"] * (z["attn"] + z["ffn"] + z["mixer_proj"] + z["plain"]) + z["d"] * z["vocab"]
    return {"bytes": float(nbytes), "flops": float(2 * rows * per_token + attn["flops"] + state["flops"]),
            "experts_touched": 0.0, "state_bytes": state["state_bytes"] + state["conv_bytes"],
            "cache_bytes": float(attn["cache_bytes"]), "layer_weight_bytes": float(layer_bytes),
            "mixer_proj_bytes": float(z["mixer_proj"] * 2), "head_bytes": float(z["d"] * z["vocab"] * weight_bytes)}


def least_seconds(counts: dict, peaks: dict) -> tuple[float, str]:
    """Least time the chip could take, and which bound sets it."""
    t_mem = counts["bytes"] / peaks["hbm_bytes_per_s"]
    t_flop = counts["flops"] / peaks["bf16_flops_per_s"]  # the MXU multiplies bf16: int8 is widened
    return (t_mem, "memory") if t_mem >= t_flop else (t_flop, "compute")
