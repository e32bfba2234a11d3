"""Process start to the window's first second: runtime, weights, cache pool,
warm-up (compilation in a run that compiles), the outputs check, the lead-in."""


def read(ctx):
    return ctx["setup_s"]
