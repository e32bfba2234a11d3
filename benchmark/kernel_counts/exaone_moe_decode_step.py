"""Bytes and operations one decode step *needs* (not what a program moves),
for a decoder whose layers mix window and full grouped-query attention
(``layer_types``), with ``first_k_dense_replace`` leading dense layers and in
every later layer a routed MoE beside one shared expert (K-EXAONE's
``exaone_moe`` keys); this chip may hold a share of the routed experts and a
slice of the vocabulary (``num_experts`` held of ``n_routed_experts_published``,
``vocab_size`` as run).

Per step of ``rows`` sequences with contexts ``contexts_total`` (tokens, summed):

- every weight outside the routed experts once: a layer's four attention
  projections, the shared expert, the dense layers' FFN and the head at
  ``weight_bytes`` each; the router, which the program serves in bf16, at 2;
- of the experts held here, those some row chose (``experts_step``, from a
  count): with a router that spreads its choices evenly over all it scores,
  held * (1 - (1 - k / routed) ** rows) a layer, three matrices each;
  ``experts_touched`` overrides the formula with a count (a layer's mean);
- the cache by layer kind: a full layer reads K and V of the rows' whole
  contexts, a sliding layer of at most ``sliding_window`` tokens a row
  (``decode_step`` is handed the contexts summed and takes the window at the
  mean context, rows * min(mean, window); in the cell that uses it every
  context is a hundred windows long);
- the embedding rows of the input tokens.

``attention_step`` gives the bytes and operations of the paged attention
kernels alone, all layers, from the key tokens a layer of each kind has to
visit (the STEP record's ``kv_tokens_full`` and ``kv_tokens_window``).
"""

from __future__ import annotations

SLIDING = "sliding_attention"


def expected_experts_touched(held: int, routed: int, top_k: int, rows: float) -> float:
    return held * (1.0 - (1.0 - top_k / routed) ** rows)


def layer_counts(hf: dict) -> tuple[int, int]:
    """(full layers, sliding layers)."""
    sliding = sum(1 for k in hf["layer_types"][: hf["num_hidden_layers"]] if k == SLIDING)
    return hf["num_hidden_layers"] - sliding, sliding


def _sizes(hf: dict) -> dict:
    d, heads, kv_heads, hd = hf["hidden_size"], hf["num_attention_heads"], hf["num_key_value_heads"], hf["head_dim"]
    held = hf["num_experts"]
    dense_layers = hf.get("first_k_dense_replace", 0)
    expert = 3 * d * hf["moe_intermediate_size"]
    return dict(
        d=d, heads=heads, kv_heads=kv_heads, hd=hd, layers=hf["num_hidden_layers"], dense_layers=dense_layers,
        moe_layers=hf["num_hidden_layers"] - dense_layers, vocab=hf["vocab_size"], held=held,
        routed=hf.get("n_routed_experts_published", held), top_k=hf["num_experts_per_tok"],
        attention=2 * d * heads * hd + 2 * d * kv_heads * hd, dense=3 * d * hf["intermediate_size"],
        expert=expert, shared=hf.get("num_shared_experts", 0) * expert, window=hf["sliding_window"],
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
    router = z["d"] * z["routed"]
    moe_outside = (z["attention"] + z["shared"]) * weight_bytes + router * 2
    dense_layer = (z["attention"] + z["dense"]) * weight_bytes
    attn = attention_step(hf, kv_tokens_full=contexts_total,
                          kv_tokens_window=min(contexts_total, rows * z["window"]), rows=rows)
    head = z["d"] * z["vocab"] * weight_bytes
    nbytes = (z["moe_layers"] * moe_outside + z["dense_layers"] * dense_layer + experts["bytes"] + head
              + rows * z["d"] * 2 + attn["cache_bytes"])
    per_token = (z["layers"] * z["attention"] + z["dense_layers"] * z["dense"]
                 + z["moe_layers"] * (z["shared"] + router + z["top_k"] * z["held"] / z["routed"] * z["expert"])
                 + z["d"] * z["vocab"])
    return {"bytes": float(nbytes), "flops": float(2 * rows * per_token + attn["flops"]),
            "experts_touched": float(touched), "experts_bytes": experts["bytes"], "head_bytes": float(head),
            "cache_bytes": float(attn["cache_bytes"]), "cache_bytes_full": float(attn["cache_bytes_full"]),
            "outside_experts_bytes": float(z["moe_layers"] * moe_outside + z["dense_layers"] * dense_layer)}


def attention_step(hf: dict, *, kv_tokens_full: float, kv_tokens_window: float, rows: float,
                   new_tokens: float = 1.0) -> dict:
    """What the paged attention kernels of one step need, all layers: K and V
    of the visited tokens once (2 bytes each), by layer kind; the queries in
    and the output out (bf16). Operations: scores and weighted sum, 4 * heads *
    head_dim a (query, key) pair; a chunk's queries see on average the visited
    keys less half the chunk (the causal triangle)."""
    z = _sizes(hf)
    full, sliding = layer_counts(hf)
    token = 2 * z["kv_heads"] * z["hd"] * 2
    cache_full = full * kv_tokens_full * token
    cache_bytes = cache_full + sliding * kv_tokens_window * token
    qo_bytes = (full + sliding) * rows * new_tokens * z["heads"] * z["hd"] * 2 * 2
    pairs = new_tokens * (full * kv_tokens_full + sliding * kv_tokens_window) \
        - (full + sliding) * rows * new_tokens * (new_tokens - 1) / 2
    return {"bytes": float(cache_bytes + qo_bytes), "cache_bytes": float(cache_bytes),
            "cache_bytes_full": float(cache_full), "flops": float(4 * z["heads"] * z["hd"] * pairs)}


def least_seconds(counts: dict, peaks: dict) -> tuple[float, str]:
    """Least time the chip could take, and which bound sets it."""
    t_mem = counts["bytes"] / peaks["hbm_bytes_per_s"]
    t_flop = counts["flops"] / peaks["bf16_flops_per_s"]  # the MXU multiplies bf16: int8 is widened
    return (t_mem, "memory") if t_mem >= t_flop else (t_flop, "compute")
