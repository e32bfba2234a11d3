"""Share of the key tokens that the windowed layers do not visit: 100 x (1 -
visited / visited were every layer full) over the window's decode steps, from
the STEP records' ``kv_tokens_full`` and ``kv_tokens_window`` and the
configuration's count of layers of each kind. A program without the fields, or
a configuration without ``layer_types``, gives nothing to read."""


def read(ctx):
    hf = ctx["conf"]["hf"]
    steps = [s for s in ctx["window"]["steps"] if s["step_kind"] == "decode" and s.get("kv_tokens_full")]
    if not steps or "layer_types" not in hf:
        return None
    sliding = sum(1 for k in hf["layer_types"] if k == "sliding_attention")
    full = len(hf["layer_types"]) - sliding
    visited = sum(full * s["kv_tokens_full"] + sliding * s["kv_tokens_window"] for s in steps)
    return 100.0 * (1.0 - visited / ((full + sliding) * sum(s["kv_tokens_full"] for s in steps)))
