"""Steps in the window whose attention left the Pallas kernels
(``attn_dispatch_counts`` keys other than ``:pallas``)."""


def read(ctx):
    return float(sum(n for k, n in ctx["window"]["attn_dispatch"].items() if not k.endswith(":pallas")))
