"""Least time for the bytes the paged MLA attention needs in a decode step
(latent and rope key of the key tokens each attention sublayer has to visit,
queries in, output out; ``attention_step`` of the configuration's counts) at
the chip's HBM peak, over the summed device time of the
``mla_paged_decode_attention`` events inside that step's program; median over
the traced decode steps. Memory-bound by construction: operations are not
counted. A program without the kernel, or without ``kv_tokens_full`` for an
MLA model, gives nothing to read."""
from benchmark import attn_kernels


def read(ctx):
    return attn_kernels.roofline_pct(ctx, kernel="mla_paged_decode_attention", step_kind="decode",
                                     note="mla_decode_roofline")
