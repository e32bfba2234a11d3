"""The same share for ``paged_prefill_attention`` in mixed steps (one 64-token
chunk beside the decoding rows): needed bytes at the HBM peak over the kernel's
summed device time inside the step's program, median over the traced mixed
steps. A chunk's scores are compute, which this share does not count, and the
kernel walks a chunk row's context once per query block: both keep it low."""
from benchmark import attn_kernels


def read(ctx):
    return attn_kernels.roofline_pct(ctx, kernel="paged_prefill_attention", step_kind="mixed",
                                     note="attn_prefill_roofline")
