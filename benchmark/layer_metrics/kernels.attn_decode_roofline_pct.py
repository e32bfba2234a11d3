"""Least time for the bytes the paged decode attention needs in a decode step
(K and V of the key tokens each layer kind has to visit, queries in, output
out) at the chip's HBM peak, over the summed device time of the
``paged_decode_attention`` events inside that step's program; median over the
traced decode steps. Memory-bound by construction: operations are not counted."""
from benchmark import attn_kernels


def read(ctx):
    return attn_kernels.roofline_pct(ctx, kernel="paged_decode_attention", step_kind="decode",
                                     note="attn_decode_roofline")
