"""Bytes and operations one decode step *needs* (not what a program moves),
for a hybrid decoder (Ling-3.0-flash; ``bailing_hybrid``'s config keys): layers
in periods of ``layer_group_size``, each period's last layer latent attention
(MLA, no query low rank, a head-wise output gate), every other one a
delta-rule linear-attention layer (KDA) whose state is a slot; the first
``first_k_dense_replace`` FFNs dense, every later one a routed MoE beside one
shared expert; this chip may hold a share of the routed experts.

Per step of ``rows`` sequences with contexts ``contexts_total`` (tokens, summed):

- every weight outside the routed experts once: a KDA block's four large
  projections (``wq wk wv wo``), an MLA block's (``w_q``, ``w_kv_a``,
  ``wo_mla``), the shared expert, the dense FFNs and the head at
  ``weight_bytes`` each; the KDA block's decay projection, its two head-wise
  projections, filters and constants, the MLA block's ``w_uk`` / ``w_uv`` and
  gate, and the router, which the program serves in bf16, at 2;
- of the experts held here, those some row chose (``experts_step``, from a
  count): with a router that spreads its choices evenly over all it scores,
  held * (1 - (1 - k / routed) ** rows) a layer, three matrices each (group
  limits change which experts a token may choose, not how many; over many
  tokens the expectation is the same); ``experts_touched`` overrides the
  formula with a count (a layer's mean);
- **the recurrent state, read and written once a row a KDA layer**
  (``state_step``): heads x key x value float32 each way, 4.19 MB a row a layer
  at 32 heads of 128 x 128, and the conv state (the last taps - 1 inputs of the
  three streams, 2 bytes a value) each way beside it;
- the latent cache of the rows' contexts once per MLA layer:
  ``kv_lora_rank + qk_rope_head_dim`` values of 2 bytes a token (unpadded);
- the embedding rows of the input tokens.

Operations: 2 per weight a token meets, k * held / routed expert FFNs a token
under even routing, 8 per state element a KDA layer (decay, two products with
the state, the rank-one update), and the absorbed attention's.

``attention_step`` gives the bytes and operations of the MLA attention kernel
alone, the MLA layers only, from the key tokens one layer has to visit (the
STEP record's ``kv_tokens_full``); ``state_step`` those of the KDA decode
kernel alone, all KDA layers, from the rows whose slot the step touched
(``state_rows``): the state each way, and q, k, v, the decay and the output.
"""

from __future__ import annotations


def expected_experts_touched(held: int, routed: int, top_k: int, rows: float) -> float:
    return held * (1.0 - (1.0 - top_k / routed) ** rows)


def _sizes(hf: dict) -> dict:
    d, heads, hd = hf["hidden_size"], hf["num_attention_heads"], hf["head_dim"]
    r, dr, dn, dv = hf["kv_lora_rank"], hf["qk_rope_head_dim"], hf["qk_nope_head_dim"], hf["v_head_dim"]
    layers, group = hf["num_hidden_layers"], hf["layer_group_size"]
    held = hf["num_experts"]
    routed = hf.get("n_routed_experts_published", held)
    dense_layers = hf.get("first_k_dense_replace", 0)
    q, taps = heads * hd, hf["short_conv_kernel_size"]
    expert = 3 * d * hf["moe_intermediate_size"]
    return dict(
        d=d, heads=heads, hd=hd, q=q, r=r, dr=dr, layers=layers, mla_layers=layers // group,
        kda_layers=layers - layers // group, dense_layers=dense_layers, moe_layers=layers - dense_layers,
        vocab=hf["vocab_size"], held=held, routed=routed, top_k=hf["num_experts_per_tok"],
        kda_matmul=4 * d * q,  # int8 leaves
        kda_plain=d * q + 2 * d * heads + 3 * taps * q + heads + q + hd,  # bf16 leaves
        mla_matmul=d * heads * (dn + dr) + d * (r + dr) + heads * dv * d,  # int8 leaves
        mla_plain=r * heads * (dn + dv) + d * heads,  # w_uk, w_uv and the gate, bf16
        dense=3 * d * hf["intermediate_size"], expert=expert,
        shared=hf.get("num_shared_experts", 0) * 3 * d * (hf.get("moe_shared_expert_intermediate_size") or hf["moe_intermediate_size"]),
        router=d * routed, state=heads * hd * hd, conv=(taps - 1) * 3 * q,
    )


def experts_step(hf: dict, *, experts_touched_total: float, weight_bytes: float = 1.0) -> dict:
    """What the routed experts of one step need, all MoE layers: the three
    matrices of every held expert some row chose. ``experts_touched_total`` is
    the distinct (layer, held expert) pairs with a row, as a STEP record's
    ``moe_experts_touched`` counts them. Also the (token, choice) pairs the
    step's router makes of one token, ``moe_choices`` a token."""
    z = _sizes(hf)
    return {"bytes": float(experts_touched_total * z["expert"] * weight_bytes),
            "choices_per_token": z["top_k"] * z["moe_layers"]}


def state_step(hf: dict, *, rows: float) -> dict:
    """What the KDA decode kernel of one step needs, all KDA layers: each
    row's state read once and written once in float32, its q, k, v and decay
    in and its output out (float32, a few KB). ``state_bytes`` is the state's
    part alone; ``conv_bytes`` the conv state each way, which XLA moves, not
    the kernel."""
    z = _sizes(hf)
    state_bytes = z["kda_layers"] * rows * 2 * z["state"] * 4
    io_bytes = z["kda_layers"] * rows * 5 * z["q"] * 4
    return {"bytes": float(state_bytes + io_bytes), "state_bytes": float(state_bytes),
            "conv_bytes": float(z["kda_layers"] * rows * 2 * z["conv"] * 2),
            "flops": float(z["kda_layers"] * rows * 8 * z["state"])}


def decode_step(hf: dict, *, rows: float, contexts_total: float, weight_bytes: float = 1.0,
                experts_touched: float | None = None) -> dict:
    z = _sizes(hf)
    touched = (expected_experts_touched(z["held"], z["routed"], z["top_k"], rows)
               if experts_touched is None else experts_touched)
    experts = experts_step(hf, experts_touched_total=z["moe_layers"] * touched, weight_bytes=weight_bytes)
    kda_block = z["kda_matmul"] * weight_bytes + z["kda_plain"] * 2
    mla_block = z["mla_matmul"] * weight_bytes + z["mla_plain"] * 2
    routed_outside = z["shared"] * weight_bytes + z["router"] * 2
    state = state_step(hf, rows=rows)
    attn = attention_step(hf, kv_tokens_full=contexts_total, rows=rows)
    nbytes = (z["kda_layers"] * kda_block + z["mla_layers"] * mla_block + z["moe_layers"] * routed_outside
              + z["dense_layers"] * z["dense"] * weight_bytes + experts["bytes"]
              + state["state_bytes"] + state["conv_bytes"] + attn["cache_bytes"]
              + z["d"] * z["vocab"] * weight_bytes + rows * z["d"] * 2)
    per_token = (z["kda_layers"] * (z["kda_matmul"] + z["kda_plain"]) + z["mla_layers"] * (z["mla_matmul"] + z["mla_plain"])
                 + z["dense_layers"] * z["dense"]
                 + z["moe_layers"] * (z["shared"] + z["router"] + z["top_k"] * z["held"] / z["routed"] * z["expert"])
                 + z["d"] * z["vocab"])
    return {"bytes": float(nbytes), "flops": float(2 * rows * per_token + attn["flops"] + state["flops"]),
            "experts_touched": float(touched), "experts_bytes": experts["bytes"],
            "state_bytes": state["state_bytes"] + state["conv_bytes"], "cache_bytes": float(attn["cache_bytes"]),
            "kda_block_bytes": float(kda_block), "mla_block_bytes": float(mla_block),
            "routed_outside_experts_bytes": float(routed_outside), "dense_ffn_bytes": float(z["dense"] * weight_bytes)}


def attention_step(hf: dict, *, kv_tokens_full: float, kv_tokens_window: float = 0.0, rows: float,
                   new_tokens: float = 1.0) -> dict:
    """What the MLA attention kernel of one step needs, the MLA layers only.
    ``kv_tokens_window`` is taken for the harness's sake and unused: no layer
    has a window. Operations: scores against latent and rope key, weighted sum
    of the latent, 2 * heads * (2 * rank + rope) a (query, key) pair; a chunk's
    queries see on average the visited keys less half the chunk (the causal
    triangle)."""
    del kv_tokens_window
    z = _sizes(hf)
    cache_bytes = z["mla_layers"] * kv_tokens_full * (z["r"] + z["dr"]) * 2
    qo_bytes = z["mla_layers"] * rows * new_tokens * z["heads"] * (2 * z["r"] + z["dr"]) * 2
    pairs = new_tokens * kv_tokens_full - rows * new_tokens * (new_tokens - 1) / 2
    return {"bytes": float(cache_bytes + qo_bytes), "cache_bytes": float(cache_bytes),
            "flops": float(z["mla_layers"] * 2 * z["heads"] * (2 * z["r"] + z["dr"]) * pairs)}


def least_seconds(counts: dict, peaks: dict) -> tuple[float, str]:
    """Least time the chip could take, and which bound sets it."""
    t_mem = counts["bytes"] / peaks["hbm_bytes_per_s"]
    t_flop = counts["flops"] / peaks["bf16_flops_per_s"]  # the MXU multiplies bf16: int8 is widened
    return (t_mem, "memory") if t_mem >= t_flop else (t_flop, "compute")
