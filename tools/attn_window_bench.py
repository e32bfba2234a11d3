#!/usr/bin/env python3
"""The windowed paged attention kernels alone, on the chip: device time of one
layer's call at several contexts, full against windowed.

    python3 tools/attn_window_bench.py [--rows 6] [--contexts 2048,4096,6144] [--window 1024]
    python3 tools/attn_window_bench.py --heads 16 --kv-heads 16 --rows 48 --contexts 256,512,896 --variants none

At Mellum2's head layout (32 query and 4 KV heads of 128, page 128, bf16
cache; ``--heads`` / ``--kv-heads`` give another, OLMoE's is 16 / 16) and
``--rows`` sequences all at one context, 28 calls chained in one
program (a layer stack's worth; the time printed is one call's): ``paged_decode_attention``
(T = 1), ``paged_prefill_attention`` with T = 1 (what a decode slot of a split
chunk step asks of the chunked kernel: the same bytes as the decode kernel's
call) and with one 64-token chunk a row, each with
``window=None`` (the unwindowed program), ``NO_WINDOW`` (a full layer inside
a mixed model's scan) and ``--window`` (``--variants`` picks among the three). A windowed walk visits the blocks that
hold the window, so its time must not grow with the context; the bytes each
call needs and the share of the HBM peak they come to are printed beside.
``--rehearse`` (or no TPU) runs tiny shapes in interpret mode and prints no time.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

HEAD_DIM, PAGE = 128, 128
LAYERS = 28


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--rows", type=int, default=6)
    ap.add_argument("--contexts", default="2048,4096,6144")
    ap.add_argument("--window", type=int, default=1024)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--kv-heads", type=int, default=4)
    ap.add_argument("--variants", default="none,no_window,window")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.ops.pallas_paged import NO_WINDOW, paged_decode_attention
    from dynamo_tpu.ops.pallas_prefill import paged_prefill_attention

    on_chip = jax.default_backend() == "tpu" and not args.rehearse
    contexts = [int(c) for c in args.contexts.split(",")] if on_chip else [512, 1024]
    window, rows, iters = (args.window, args.rows, args.iters) if on_chip else (256, 2, 1)
    hbm = json.loads((ROOT / "benchmark" / "peaks.json").read_text())["TPU v5 lite"]["hbm_bytes_per_s"]
    pages_per_seq = -(-max(contexts) // PAGE)
    rng = np.random.default_rng(0)
    width = args.kv_heads * HEAD_DIM
    variants = [(n, w) for n, w in (("none", None), ("no_window", NO_WINDOW), ("window", window))
                if n in args.variants.split(",")]
    dtype = jnp.bfloat16 if on_chip else jnp.float32
    cache = jnp.asarray(rng.standard_normal((rows * pages_per_seq + 1, PAGE, width)), dtype)
    tables = jnp.asarray(1 + np.arange(rows * pages_per_seq, dtype=np.int32).reshape(rows, pages_per_seq))
    table = []
    for kernel, t in ((paged_decode_attention, 1), (paged_prefill_attention, 1), (paged_prefill_attention, 64)):
        q = jnp.asarray(rng.standard_normal((rows, t, args.heads, HEAD_DIM)), dtype)
        for ctx in contexts:
            pos = jnp.asarray(np.broadcast_to(ctx - t + np.arange(t, dtype=np.int32), (rows, t)))
            for name, w in variants:
                def stack(q, k, v, bt, p, w=w):
                    # LAYERS calls in a row, each fed by the one before, in one
                    # program: the host's dispatch is paid once, not per call.
                    def layer(qc, _):
                        o = kernel(qc, k, v, bt, p, scale=HEAD_DIM**-0.5, interpret=not on_chip,
                                   window=None if w is None else jnp.int32(w))
                        return qc + (o * 1e-3).astype(qc.dtype), None

                    return jax.lax.scan(layer, q, None, length=LAYERS)[0]

                call = jax.jit(stack)
                out = jax.block_until_ready(call(q, cache, cache, tables, pos))
                assert bool(jnp.isfinite(out.astype(jnp.float32)).all())
                visited = ctx if w is None or w >= ctx else min(ctx, w + t - 1)
                need = rows * visited * 2 * width * 2 + 2 * rows * t * args.heads * HEAD_DIM * 2
                row = {"kernel": kernel.__name__, "t": t, "rows": rows, "context": ctx, "window": name,
                       "needed_bytes": need}
                if on_chip:
                    t0 = time.perf_counter()
                    for _ in range(iters):
                        out = call(q, cache, cache, tables, pos)
                    jax.block_until_ready(out)
                    ms = (time.perf_counter() - t0) / iters / LAYERS * 1e3
                    row.update(ms=round(ms, 4), hbm_share_pct=round(100 * need / hbm / (ms / 1e3), 1))
                print(json.dumps(row), flush=True)
                table.append(row)
    print(json.dumps({"attn_window_bench": "rehearsal: no time is a device time" if not on_chip else "v5e", "rows": len(table)}))
    return 0 if on_chip else 3


if __name__ == "__main__":
    sys.exit(main())
