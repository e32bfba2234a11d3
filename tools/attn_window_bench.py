#!/usr/bin/env python3
"""The paged attention kernels alone, on the chip: device time of one layer's
call at several contexts, full against windowed, beside the pages it moves.

    python3 tools/attn_window_bench.py [--rows 6] [--contexts 2048,4096,6144] [--window 1024] [--mix longctx-decode]
    python3 tools/attn_window_bench.py --heads 16 --kv-heads 16 --rows 48 --contexts 256,512,896 --variants none --mix decode-saturated
    python3 tools/attn_window_bench.py --heads 64 --kv-heads 8 --window 128 --rows 8 --contexts 16384 --mix longctx-reason --kernels decode

At Mellum2's head layout (32 query and 4 KV heads of 128, page 128, bf16
cache; ``--heads`` / ``--kv-heads`` give another: OLMoE's is 16 / 16,
K-EXAONE's 64 / 8 with a window of 128) and ``--rows`` sequences, 28 calls
chained in one program (a layer stack's worth; the time printed is one
call's): ``paged_decode_attention`` (T = 1), ``paged_prefill_attention`` with
T = 1 (what a decode slot of a split chunk step asks of the chunked kernel:
the same bytes as the decode kernel's call) and with one 64-token chunk a row
(``--kernels`` picks among ``decode,chunk1,chunk64``), each with
``window=None`` (the unwindowed program), ``NO_WINDOW`` (a full layer inside
a mixed model's scan) and ``--window`` (``--variants`` picks among the three).
``--contexts``: every row at that many tokens, so every row's walk ends on a
page's and a block's edge; ``--mix <traffic>`` adds ragged rows drawn as
``benchmark/traffic/<traffic>.json`` draws them (one of its requests a row, its
prompt plus a uniform share of its answer), so rows have tails and windows
straddle blocks as in the cell. A windowed walk visits the blocks that hold
the window, so its time must not grow with the context; the bytes each call
needs and the share of the HBM peak they come to are printed beside, and for
the decode kernel the pages its walk starts a copy for over the page slots of
the blocks it visits (``ops/pallas_paged.decode_walk``: a function of the
lengths alone). ``--rehearse`` (or no TPU) runs tiny shapes in interpret mode
and prints no time.
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
    ap.add_argument("--mix", default="", help="a traffic file's name: ragged rows drawn like that cell's")
    ap.add_argument("--window", type=int, default=1024)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--kv-heads", type=int, default=4)
    ap.add_argument("--variants", default="none,no_window,window")
    ap.add_argument("--kernels", default="decode,chunk1,chunk64")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.ops.pallas_paged import (
        NO_WINDOW, _dma_depth, _pages_per_block, decode_walk, paged_decode_attention)
    from dynamo_tpu.ops.pallas_prefill import paged_prefill_attention

    on_chip = jax.default_backend() == "tpu" and not args.rehearse
    rng = np.random.default_rng(args.seed)
    window, rows, iters = (args.window, args.rows, args.iters) if on_chip else (256, 3, 1)
    draws = {str(c): np.full(rows, int(c)) for c in (args.contexts.split(",") if on_chip else ["512", "1024"])}
    if args.mix:
        # A full batch at a moment of the window: each row one of the mix's
        # requests, somewhere in its answer (the rehearsal's rows are shorter).
        mix = np.asarray(json.loads((ROOT / "benchmark" / "traffic" / f"{args.mix}.json").read_text())["lengths_per_100"])
        prompt, answer = mix[rng.integers(0, len(mix), rows)].T
        lens = prompt + rng.random(rows) * answer
        draws[args.mix] = np.ceil(lens if on_chip else lens * 1024 / lens.max()).astype(np.int64)
    hbm = json.loads((ROOT / "benchmark" / "peaks.json").read_text())["TPU v5 lite"]["hbm_bytes_per_s"]
    pages_per_seq = -(-max(int(d.max()) for d in draws.values()) // PAGE)
    width = args.kv_heads * HEAD_DIM
    variants = [(n, w) for n, w in (("none", None), ("no_window", NO_WINDOW), ("window", window))
                if n in args.variants.split(",")]
    kernels = [(k, t) for n, k, t in (("decode", paged_decode_attention, 1), ("chunk1", paged_prefill_attention, 1),
                                      ("chunk64", paged_prefill_attention, 64)) if n in args.kernels.split(",")]
    dtype = jnp.bfloat16 if on_chip else jnp.float32
    ppb = _pages_per_block(pages_per_seq, PAGE, width, jnp.dtype(dtype).itemsize, _dma_depth())
    cache = jnp.asarray(rng.standard_normal((rows * pages_per_seq + 1, PAGE, width)), dtype)
    tables = jnp.asarray(1 + np.arange(rows * pages_per_seq, dtype=np.int32).reshape(rows, pages_per_seq))
    table = []
    for kernel, t in kernels:
        q = jnp.asarray(rng.standard_normal((rows, t, args.heads, HEAD_DIM)), dtype)
        for name, ctx in draws.items():
            ctx = np.maximum(ctx, t)
            pos = jnp.asarray(ctx[:, None] - t + np.arange(t, dtype=np.int32)[None], jnp.int32)
            for variant, w in variants:
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
                visited = ctx if w is None else np.minimum(ctx, w + t - 1)
                need = int(visited.sum()) * 2 * width * 2 + 2 * rows * t * args.heads * HEAD_DIM * 2
                row = {"kernel": kernel.__name__, "t": t, "rows": rows, "context": name, "tokens": int(ctx.sum()),
                       "window": variant, "needed_bytes": need}
                if kernel is paged_decode_attention:
                    walk = decode_walk(pos, PAGE, ppb, w)
                    row.update(pages_started=int(walk.pages_started), pages_in_visited_blocks=int(walk.blocks.sum()) * ppb)
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
