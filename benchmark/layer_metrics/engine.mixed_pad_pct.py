"""Share of the token positions a mixed step's program computes that are
padding: 100 x (1 - real / computed) summed over the window's mixed steps,
where a step's real tokens are ``decode_rows + chunk_tokens`` of its STEP
flight record and the computed ones its ``step_tokens`` (rows x chunk where
every row is padded to the chunk; a position per decode row plus the chunk
where the token axis is split). A program without the field gives nothing to
read."""


def read(ctx):
    steps = [s for s in ctx["window"]["steps"] if s["step_kind"] == "mixed" and s.get("step_tokens")]
    if not steps:
        return None
    real = sum(s["decode_rows"] + s["chunk_tokens"] for s in steps)
    return 100.0 * (1.0 - real / sum(s["step_tokens"] for s in steps))
