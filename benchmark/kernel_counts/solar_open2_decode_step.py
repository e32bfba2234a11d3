"""Bytes and operations one decode step *needs* (not what a program moves),
for Solar-Open2's hybrid decoder (``solar_open2``'s config keys): the layers
``gqa_layers`` name are gated GQA attention without RoPE (``num_attention_heads``
query heads over ``num_key_value_heads`` K/V heads, a sigmoid gate a channel on
the output), every other one a delta-rule linear-attention layer (KDA,
``linear_attn_config``) whose state is a slot and whose decay input and output
gate come through low-rank pairs of rank ``head_dim``; every layer's FFN is a
routed MoE beside one shared expert; this chip may hold a share of the routed
experts.

Per step of ``rows`` sequences with contexts ``contexts_total`` (tokens, summed):

- every weight outside the routed experts once: a KDA block's four large
  projections (``wq wk wv wo``), a GQA block's four, the shared expert and the
  head at ``weight_bytes`` each; the KDA block's two low-rank pairs, its
  write-strength projection, filters and constants, the GQA block's gate
  (``w_out_gate`` [hidden x heads head_dim]), the router and the two norms a
  layer, which the program serves in bf16, at 2;
- of the experts held here, those some row chose (``experts_step``, from a
  count): with a router that spreads its choices evenly over all it scores,
  held * (1 - (1 - k / routed) ** rows) a layer, three matrices each;
  ``experts_touched`` overrides the formula with a count (a layer's mean);
- **the recurrent state, read and written once a row a KDA layer**
  (``state_step``): heads x key x value float32 each way, 4.19 MB a row a layer
  each way at 64 heads of 128 x 128, and the conv state (the last taps - 1 inputs of the
  three streams, 2 bytes a value) each way beside it;
- K and V of the rows' contexts once per GQA layer: 2 x K/V heads x head_dim
  values of 2 bytes a token;
- the embedding rows of the input tokens.

Operations: 2 per weight a token meets, k * held / routed expert FFNs a token
under even routing, 8 per state element a KDA layer (decay, two products with
the state, the rank-one update), and the attention's scores and weighted sum.

``attention_step`` gives the bytes and operations of the GQA attention kernel
alone, the GQA layers only, from the key tokens one layer has to visit (the
STEP record's ``kv_tokens_full``); ``state_step`` those of the KDA decode
kernel alone, all KDA layers, from the rows whose slot the step touched
(``state_rows``): the state each way, and q, k, v, the decay and the output.
"""

from __future__ import annotations


def expected_experts_touched(held: int, routed: int, top_k: int, rows: float) -> float:
    return held * (1.0 - (1.0 - top_k / routed) ** rows)


def _sizes(hf: dict) -> dict:
    d, heads, kv, hd = hf["hidden_size"], hf["num_attention_heads"], hf["num_key_value_heads"], hf["head_dim"]
    linear = hf["linear_attn_config"]
    kda_heads, kda_hd, taps = linear["num_heads"], linear["head_dim"], linear["short_conv_kernel_size"]
    layers = hf["num_hidden_layers"]
    attn_layers = len([i for i in hf["gqa_layers"] if i < layers])
    held = hf["n_routed_experts"]
    routed = hf.get("n_routed_experts_published", held)
    q, kda_q, rank = heads * hd, kda_heads * kda_hd, kda_hd
    return dict(
        d=d, heads=heads, kv=kv, hd=hd, layers=layers, attn_layers=attn_layers, kda_layers=layers - attn_layers,
        vocab=hf["vocab_size"], held=held, routed=routed, top_k=hf["num_experts_per_tok"], kda_q=kda_q,
        kda_matmul=4 * d * kda_q,  # int8 leaves
        # the two low-rank pairs, w_beta, the three filters, a_log, dt_bias, the head norm: bf16 leaves
        kda_plain=2 * (d * rank + rank * kda_q) + d * kda_heads + 3 * taps * kda_q + kda_heads + kda_q + kda_hd,
        attn_matmul=d * (q + 2 * kv * hd) + q * d,  # int8 leaves
        attn_plain=d * q if hf.get("use_gqa_gate") else 0,  # the gate, bf16
        expert=3 * d * hf["moe_intermediate_size"], shared=hf.get("n_shared_experts", 0) * 3 * d * hf["moe_intermediate_size"],
        router=d * routed, norms=2 * d, state=kda_heads * kda_hd * kda_hd, conv=(taps - 1) * 3 * kda_q,
    )


def experts_step(hf: dict, *, experts_touched_total: float, weight_bytes: float = 1.0) -> dict:
    """What the routed experts of one step need, all layers: the three
    matrices of every held expert some row chose. ``experts_touched_total`` is
    the distinct (layer, held expert) pairs with a row, as a STEP record's
    ``moe_experts_touched`` counts them. Also the (token, choice) pairs the
    step's routers make of one token, ``moe_choices`` a token."""
    z = _sizes(hf)
    return {"bytes": float(experts_touched_total * z["expert"] * weight_bytes),
            "choices_per_token": z["top_k"] * z["layers"]}


def state_step(hf: dict, *, rows: float) -> dict:
    """What the KDA decode kernel of one step needs, all KDA layers: each
    row's state read once and written once in float32, its q, k, v and decay
    in and its output out (float32, a few KB). ``state_bytes`` is the state's
    part alone; ``conv_bytes`` the conv state each way, which the conv kernel
    moves, not this one."""
    z = _sizes(hf)
    state_bytes = z["kda_layers"] * rows * 2 * z["state"] * 4
    io_bytes = z["kda_layers"] * rows * 5 * z["kda_q"] * 4
    return {"bytes": float(state_bytes + io_bytes), "state_bytes": float(state_bytes),
            "conv_bytes": float(z["kda_layers"] * rows * 2 * z["conv"] * 2),
            "flops": float(z["kda_layers"] * rows * 8 * z["state"])}


def attention_step(hf: dict, *, kv_tokens_full: float, kv_tokens_window: float = 0.0, rows: float,
                   new_tokens: float = 1.0) -> dict:
    """What the GQA attention kernel of one step needs, the GQA layers held.
    ``kv_tokens_window`` is taken for the harness's sake and unused: no layer
    has a window. Operations: scores and the weighted sum, 4 x heads x
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
    touched = (expected_experts_touched(z["held"], z["routed"], z["top_k"], rows)
               if experts_touched is None else experts_touched)
    experts = experts_step(hf, experts_touched_total=z["layers"] * touched, weight_bytes=weight_bytes)
    kda_block = z["kda_matmul"] * weight_bytes + z["kda_plain"] * 2
    attn_block = z["attn_matmul"] * weight_bytes + z["attn_plain"] * 2
    ffn_outside = z["shared"] * weight_bytes + (z["router"] + z["norms"]) * 2
    state = state_step(hf, rows=rows)
    attn = attention_step(hf, kv_tokens_full=contexts_total, rows=rows)
    head = z["d"] * z["vocab"] * weight_bytes
    nbytes = (z["kda_layers"] * kda_block + z["attn_layers"] * attn_block + z["layers"] * ffn_outside + experts["bytes"]
              + state["state_bytes"] + state["conv_bytes"] + attn["cache_bytes"] + head + rows * z["d"] * 2)
    per_token = (z["kda_layers"] * (z["kda_matmul"] + z["kda_plain"]) + z["attn_layers"] * (z["attn_matmul"] + z["attn_plain"])
                 + z["layers"] * (z["shared"] + z["router"] + z["norms"] + z["top_k"] * z["held"] / z["routed"] * z["expert"])
                 + z["d"] * z["vocab"])
    return {"bytes": float(nbytes), "flops": float(2 * rows * per_token + attn["flops"] + state["flops"]),
            "experts_touched": float(touched), "experts_bytes": experts["bytes"],
            "state_bytes": state["state_bytes"] + state["conv_bytes"], "cache_bytes": float(attn["cache_bytes"]),
            "kda_block_bytes": float(kda_block), "attention_block_bytes": float(attn_block),
            "ffn_outside_experts_bytes": float(ffn_outside), "head_bytes": float(head)}


def least_seconds(counts: dict, peaks: dict) -> tuple[float, str]:
    """Least time the chip could take, and which bound sets it."""
    t_mem = counts["bytes"] / peaks["hbm_bytes_per_s"]
    t_flop = counts["flops"] / peaks["bf16_flops_per_s"]  # the MXU multiplies bf16: int8 is widened
    return (t_mem, "memory") if t_mem >= t_flop else (t_flop, "compute")
