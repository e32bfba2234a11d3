"""Programs compiled inside the window: JAX's backend compiles, or the
runner's CompileTracker first-executions over its threshold, whichever is more."""


def read(ctx):
    w = ctx["window"]
    slow = sum(1 for e in w["tracker_new_shapes"] if e.get("reason") == "new_shape")
    return float(max(w["backend_compiles"], slow))
