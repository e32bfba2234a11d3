"""Bytes and operations one decode step *needs* (not what a program moves),
for a decoder of plain layers with latent attention (MLA), ``first_k_dense_replace``
leading dense layers, and in every later layer a routed MoE beside one shared
expert (JoyAI-LLM-Flash; DeepSeek-V3's config keys); this chip may hold a share
of the routed experts.

Per step of ``rows`` sequences with contexts ``contexts_total`` (tokens, summed):

- every weight outside the routed experts once: a layer's MLA projections
  (``w_q_a``, ``w_q_b``, ``w_kv_a``, ``wo_mla``), the shared expert, the dense
  layers' FFN and the head at ``weight_bytes`` each; the per-head
  up-projections ``w_uk`` / ``w_uv`` and the router, which the program serves
  in bf16, at 2;
- of the experts held here, those some row chose (``experts_step``, from a
  count): with a router that spreads its choices evenly over all it scores,
  held * (1 - (1 - k / routed) ** rows) a layer, three matrices each;
  ``experts_touched`` overrides the formula with a count (a layer's mean). An
  expert held elsewhere needs nothing here;
- the latent cache of the rows' contexts once per layer:
  ``kv_lora_rank + qk_rope_head_dim`` values of 2 bytes a token (the *unpadded*
  width: a program that pads the rope stream to a lane tile moves more than is
  needed);
- the embedding rows of the input tokens.

Operations are those of the absorbed form (queries into latent space, scores
and weighted sum against the latent, ``w_uv`` on the way out): 2 per weight a
token meets, k * held / routed expert FFNs a token under even routing.

``attention_step`` gives the bytes and operations of the MLA attention kernel
alone, all layers, from the key tokens one layer has to visit (the STEP
record's ``kv_tokens_full``): latent and rope key once, queries in (latent and
rope parts), output out (latent space), 2 bytes each.
"""

from __future__ import annotations


def expected_experts_touched(held: int, routed: int, top_k: int, rows: float) -> float:
    return held * (1.0 - (1.0 - top_k / routed) ** rows)


def _sizes(hf: dict) -> dict:
    d, heads = hf["hidden_size"], hf["num_attention_heads"]
    r, dr, dn, dv, rq = (hf["kv_lora_rank"], hf["qk_rope_head_dim"], hf["qk_nope_head_dim"], hf["v_head_dim"],
                         hf["q_lora_rank"])
    held = hf["n_routed_experts"]
    routed = hf.get("n_routed_experts_published", held)
    dense_layers = hf.get("first_k_dense_replace", 0)
    expert = 3 * d * hf["moe_intermediate_size"]
    return dict(
        d=d, heads=heads, r=r, dr=dr, layers=hf["num_hidden_layers"], dense_layers=dense_layers,
        moe_layers=hf["num_hidden_layers"] - dense_layers, vocab=hf["vocab_size"],
        held=held, routed=routed, top_k=hf["num_experts_per_tok"],
        mla_matmul=d * rq + rq * heads * (dn + dr) + d * (r + dr) + heads * dv * d,  # int8 leaves
        mla_heads=r * heads * (dn + dv),  # w_uk and w_uv, bf16
        dense=3 * d * hf["intermediate_size"], expert=expert, shared=hf.get("n_shared_experts", 0) * expert,
        router=d * routed,
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


def decode_step(hf: dict, *, rows: float, contexts_total: float, weight_bytes: float = 1.0,
                experts_touched: float | None = None) -> dict:
    z = _sizes(hf)
    touched = (expected_experts_touched(z["held"], z["routed"], z["top_k"], rows)
               if experts_touched is None else experts_touched)
    experts = experts_step(hf, experts_touched_total=z["moe_layers"] * touched, weight_bytes=weight_bytes)
    attention = z["mla_matmul"] * weight_bytes + z["mla_heads"] * 2
    moe_outside = attention + z["shared"] * weight_bytes + z["router"] * 2
    dense_layer = attention + z["dense"] * weight_bytes
    attn = attention_step(hf, kv_tokens_full=contexts_total, rows=rows)
    nbytes = (z["moe_layers"] * moe_outside + z["dense_layers"] * dense_layer + experts["bytes"]
              + z["d"] * z["vocab"] * weight_bytes + rows * z["d"] * 2 + attn["cache_bytes"])
    per_token = (z["layers"] * (z["mla_matmul"] + z["mla_heads"]) + z["dense_layers"] * z["dense"]
                 + z["moe_layers"] * (z["shared"] + z["router"] + z["top_k"] * z["held"] / z["routed"] * z["expert"])
                 + z["d"] * z["vocab"])
    return {"bytes": float(nbytes), "flops": float(2 * rows * per_token + attn["flops"]),
            "experts_touched": float(touched), "experts_bytes": experts["bytes"],
            "cache_bytes": float(attn["cache_bytes"]), "outside_experts_bytes_per_moe_layer": float(moe_outside),
            "dense_layer_bytes": float(dense_layer)}


def attention_step(hf: dict, *, kv_tokens_full: float, kv_tokens_window: float = 0.0, rows: float,
                   new_tokens: float = 1.0) -> dict:
    """What the MLA attention kernel of one step needs, all layers.
    ``kv_tokens_window`` is taken for the harness's sake and unused: no layer
    has a window. Operations: scores against latent and rope key, weighted sum
    of the latent, 2 * heads * (2 * rank + rope) a (query, key) pair; a chunk's
    queries see on average the visited keys less half the chunk (the causal
    triangle)."""
    del kv_tokens_window
    z = _sizes(hf)
    cache_bytes = z["layers"] * kv_tokens_full * (z["r"] + z["dr"]) * 2
    qo_bytes = z["layers"] * rows * new_tokens * z["heads"] * (2 * z["r"] + z["dr"]) * 2
    pairs = new_tokens * kv_tokens_full - rows * new_tokens * (new_tokens - 1) / 2
    return {"bytes": float(cache_bytes + qo_bytes), "cache_bytes": float(cache_bytes),
            "flops": float(z["layers"] * 2 * z["heads"] * (2 * z["r"] + z["dr"]) * pairs)}


def least_seconds(counts: dict, peaks: dict) -> tuple[float, str]:
    """Least time the chip could take, and which bound sets it."""
    t_mem = counts["bytes"] / peaks["hbm_bytes_per_s"]
    t_flop = counts["flops"] / peaks["bf16_flops_per_s"]  # the MXU multiplies bf16: int8 is widened
    return (t_mem, "memory") if t_mem >= t_flop else (t_flop, "compute")
