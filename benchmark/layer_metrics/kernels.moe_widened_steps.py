"""Steps in the window whose routed experts took a widened formulation: the
int8 expert weights made bf16 in HBM before the matmuls (``moe_path`` of the
STEP flight record is ``"widened"``; ``"fused"`` is the int8 grouped-matmul
kernel, ``""`` a step that ran no routed experts). A program from before the
label gives nothing to read."""


def read(ctx):
    steps = ctx["window"]["steps"]
    if not any("moe_path" in s for s in steps):
        return None
    return float(sum(1 for s in steps if s.get("moe_path") == "widened"))
