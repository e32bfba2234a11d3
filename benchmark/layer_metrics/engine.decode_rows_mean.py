"""Mean decode rows per engine step in the window (flight records)."""


def read(ctx):
    steps = ctx["window"]["steps"]
    return sum(s["decode_rows"] for s in steps) / len(steps) if steps else None
