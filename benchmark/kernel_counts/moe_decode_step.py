"""Bytes and operations one decode step *needs* (not what a program moves),
for a decoder with full-head attention and routed experts in every layer.

Per step of ``rows`` sequences with contexts ``contexts_total`` (tokens, summed):

- every weight outside the routed experts is read once: attention
  projections, router, the output head (weights at ``weight_bytes`` each; the
  router, which the program serves in bf16, at 2);
- of the E routed experts of a layer, those some row chose: with a router
  that spreads tokens evenly, E * (1 - (1 - k/E) ** rows) of them, three
  matrices each. ``experts_touched`` overrides the formula with a count;
- the cache of the rows' real contexts once: K and V (2 * kv_heads * head_dim), 2 bytes;
- the embedding rows of the input tokens.

Operations: 2 per weight a token meets (k experts, not E), plus attention's
scores and weighted sum over the context. A family with another attention or
expert layout (latent cache, shared experts, dense leading layers) brings a
file of its own.
"""

from __future__ import annotations


def expected_experts_touched(num_experts: int, top_k: int, rows: float) -> float:
    return num_experts * (1.0 - (1.0 - top_k / num_experts) ** rows)


def decode_step(hf: dict, *, rows: float, contexts_total: float, weight_bytes: float = 1.0,
                experts_touched: float | None = None) -> dict:
    d, layers, vocab = hf["hidden_size"], hf["num_hidden_layers"], hf["vocab_size"]
    heads = hf["num_attention_heads"]
    kv_heads = hf.get("num_key_value_heads", heads)
    hd = hf.get("head_dim") or d // heads
    e, k, width = hf["num_experts"], hf["num_experts_per_tok"], hf["intermediate_size"]
    attn_params = d * heads * hd * 2 + d * kv_heads * hd * 2
    cache_per_token = 2 * kv_heads * hd * 2
    touched = expected_experts_touched(e, k, rows) if experts_touched is None else experts_touched
    expert_params = 3 * d * width
    layer_bytes = attn_params * weight_bytes + d * e * 2 + touched * expert_params * weight_bytes
    nbytes = layers * layer_bytes + d * vocab * weight_bytes + rows * d * 2 + layers * contexts_total * cache_per_token
    per_token_params = layers * (attn_params + d * e + k * expert_params) + d * vocab
    flops = 2 * rows * per_token_params + layers * contexts_total * 4 * heads * hd
    return {"bytes": float(nbytes), "flops": float(flops), "experts_touched": float(touched)}


def least_seconds(counts: dict, peaks: dict) -> tuple[float, str]:
    """Least time the chip could take, and which bound sets it."""
    t_mem = counts["bytes"] / peaks["hbm_bytes_per_s"]
    t_flop = counts["flops"] / peaks["bf16_flops_per_s"]  # the MXU multiplies bf16: int8 is widened
    return (t_mem, "memory") if t_mem >= t_flop else (t_flop, "compute")
