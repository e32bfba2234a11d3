"""Share of the router's (token, choice) pairs that landed on an expert this
chip holds: 100 x ``moe_choices_held`` / ``moe_choices`` summed over the
window's decode steps, from the STEP records (100 x held experts / router
outputs under even routing; the rest chose identities or experts held
elsewhere and cost nothing here). ``ctx["notes"]`` gets the distinct held
experts a layer touched in a step, the count the needed bytes turn on. A
program without the fields gives nothing to read."""


def read(ctx):
    steps = [s for s in ctx["window"]["steps"] if s["step_kind"] == "decode" and s.get("moe_choices")]
    if not steps:
        return None
    layers = ctx["conf"]["hf"].get("num_layers") or ctx["conf"]["hf"].get("num_hidden_layers") or 1
    ctx["notes"]["moe_held"] = {"steps": len(steps),
                                "experts_touched_per_layer": sum(s["moe_experts_touched"] for s in steps) / len(steps) / layers}
    return 100.0 * sum(s["moe_choices_held"] for s in steps) / sum(s["moe_choices"] for s in steps)
