"""Share of the router's (token, choice) pairs that chose an identity
("zero-compute") expert: 100 x ``moe_choices_zero`` / ``moe_choices`` summed
over the window's decode steps, from the STEP records (counted on the device
by the step programs of a model with such experts; 100 x zero experts /
router outputs under even routing). A program without the fields, or a model
without such experts (the counts stay 0), gives nothing to read."""


def read(ctx):
    steps = [s for s in ctx["window"]["steps"] if s["step_kind"] == "decode" and s.get("moe_choices")]
    if not steps:
        return None
    return 100.0 * sum(s["moe_choices_zero"] for s in steps) / sum(s["moe_choices"] for s in steps)
