"""Bytes and operations one decode step *needs* (not what a program moves),
for a decoder whose layers mix window and full attention (``layer_types``),
grouped-query heads, and routed experts in every layer.

As ``moe_decode_step`` (every weight outside the routed experts once; of a
layer's E experts those some row chose, E * (1 - (1 - k/E) ** rows) by the
even-routing formula; the input embeddings), with two differences:

- the cache term knows the layer's kind: a full layer reads K and V of the
  rows' whole contexts, a sliding layer of at most ``sliding_window`` tokens a
  row. ``decode_step`` is handed the contexts summed, so it takes the window
  at the *mean* context: min(mean, window) * rows. min is concave, so where
  some rows are under the window and some over it this overstates the needed
  bytes (Jensen's gap); in the cell that uses it every context is over the
  window after a prompt's first chunks, and the cache is under 4% of the
  step's bytes either way;
- widths are the grouped-query ones (``num_key_value_heads``, ``head_dim``)
  and one expert's width is ``moe_intermediate_size``.

``attention_step`` gives the bytes and operations of the attention kernels
alone, from the key tokens a layer of each kind has to visit (the STEP
record's ``kv_tokens_full`` and ``kv_tokens_window``).
"""

from __future__ import annotations

SLIDING = "sliding_attention"


def expected_experts_touched(num_experts: int, top_k: int, rows: float) -> float:
    return num_experts * (1.0 - (1.0 - top_k / num_experts) ** rows)


def layer_counts(hf: dict) -> tuple[int, int]:
    """(full layers, sliding layers)."""
    sliding = sum(1 for k in hf["layer_types"] if k == SLIDING)
    return hf["num_hidden_layers"] - sliding, sliding


def _widths(hf: dict) -> tuple[int, int, int, int]:
    heads = hf["num_attention_heads"]
    return hf["hidden_size"], heads, hf.get("num_key_value_heads", heads), hf.get("head_dim") or hf["hidden_size"] // heads


def decode_step(hf: dict, *, rows: float, contexts_total: float, weight_bytes: float = 1.0,
                experts_touched: float | None = None) -> dict:
    d, heads, kv_heads, hd = _widths(hf)
    layers, vocab = hf["num_hidden_layers"], hf["vocab_size"]
    e, k, width = hf["num_experts"], hf["num_experts_per_tok"], hf["moe_intermediate_size"]
    full, sliding = layer_counts(hf)
    attn_params = d * heads * hd * 2 + d * kv_heads * hd * 2
    touched = expected_experts_touched(e, k, rows) if experts_touched is None else experts_touched
    expert_params = 3 * d * width
    layer_bytes = attn_params * weight_bytes + d * e * 2 + touched * expert_params * weight_bytes
    windowed_total = min(contexts_total, rows * hf["sliding_window"])  # rows * min(mean context, window)
    attn = attention_step(hf, kv_tokens_full=contexts_total, kv_tokens_window=windowed_total, rows=rows)
    nbytes = layers * layer_bytes + d * vocab * weight_bytes + rows * d * 2 + attn["cache_bytes"]
    per_token_params = layers * (attn_params + d * e + k * expert_params) + d * vocab
    flops = 2 * rows * per_token_params + attn["flops"]
    return {"bytes": float(nbytes), "flops": float(flops), "experts_touched": float(touched),
            "cache_bytes": float(attn["cache_bytes"])}


def attention_step(hf: dict, *, kv_tokens_full: float, kv_tokens_window: float, rows: float,
                   new_tokens: float = 1.0) -> dict:
    """What the paged attention kernels of one step need, all layers: K and V
    of the visited tokens once (2 bytes each), the queries in and the output
    out (bf16). Operations: scores and weighted sum, 4 * heads * head_dim a
    (query, key) pair; a chunk's queries see on average the visited keys less
    half the chunk (the causal triangle)."""
    _, heads, kv_heads, hd = _widths(hf)
    full, sliding = layer_counts(hf)
    cache_bytes = (full * kv_tokens_full + sliding * kv_tokens_window) * 2 * kv_heads * hd * 2
    qo_bytes = (full + sliding) * rows * new_tokens * heads * hd * 2 * 2
    pairs = new_tokens * (full * kv_tokens_full + sliding * kv_tokens_window) \
        - (full + sliding) * rows * new_tokens * (new_tokens - 1) / 2
    return {"bytes": float(cache_bytes + qo_bytes), "cache_bytes": float(cache_bytes),
            "flops": float(4 * heads * hd * pairs)}


def least_seconds(counts: dict, peaks: dict) -> tuple[float, str]:
    """Least time the chip could take, and which bound sets it."""
    t_mem = counts["bytes"] / peaks["hbm_bytes_per_s"]
    t_flop = counts["flops"] / peaks["bf16_flops_per_s"]  # the MXU multiplies bf16: int8 is widened
    return (t_mem, "memory") if t_mem >= t_flop else (t_flop, "compute")
