"""The recurrent state's share of the bytes a decode step needs: 100 x
(state and conv state read and written once a touched row a KDA layer) /
(``decode_step`` of the configuration's counts), at the window's mean decode
step: its rows (``decode_rows``), the rows whose slot it touched
(``state_rows``), the contexts its attention layers visited
(``kv_tokens_full``) and, where the program counts them, the held experts some
row chose (``moe_experts_touched`` a MoE layer). It says how much of a decode
step the slot mechanism is. A program without ``state_rows``, or a
configuration whose counts have no ``state_step``, gives nothing to read."""
from benchmark import plugins


def read(ctx):
    steps = [s for s in ctx["window"]["steps"] if s["step_kind"] == "decode" and s.get("state_rows")]
    counts = plugins.load("kernel_counts", ctx["conf"]["serve"]["kernel_counts"])
    if not steps or not hasattr(counts, "state_step"):
        return None
    hf = ctx["conf"]["hf"]
    mean = lambda key: sum(s.get(key, 0) for s in steps) / len(steps)  # noqa: E731
    moe_layers = hf["num_hidden_layers"] - hf.get("first_k_dense_replace", 0)
    touched = mean("moe_experts_touched") / moe_layers
    step = counts.decode_step(hf, rows=mean("decode_rows"), contexts_total=mean("kv_tokens_full"),
                              weight_bytes=1.0 if ctx["conf"]["serve"]["quant"] == "int8" else 2.0,
                              experts_touched=touched or None)
    state = counts.state_step(hf, rows=mean("state_rows"))
    ctx["notes"]["recurrent_state"] = {"steps": len(steps), "state_rows": mean("state_rows"),
                                       "state_bytes": state["state_bytes"] + state["conv_bytes"],
                                       "step_bytes": step["bytes"], "experts_touched_per_layer": touched}
    return 100.0 * (state["state_bytes"] + state["conv_bytes"]) / step["bytes"]
