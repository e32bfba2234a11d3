#!/usr/bin/env python3
"""The latent-attention decode kernel alone, on the chip: device time of one
layer's call against the bytes it needs and the pages its block walk moves.

    python3 tools/mla_decode_bench.py [--heads 32,64] [--rows 64] [--lengths 256,768,769,1536,1984,mix]

At the two reason-saturated cells' shape (``--rows`` sequences x ``--heads``
query heads, latent 512 + rope 128 lanes, page 128, 16 pages a row, bf16
cache), 40 calls of ``mla_paged_decode`` chained in one program (a layer
stack's worth; the time printed is one call's). ``--lengths``: every row at
that many tokens, or ``mix``: drawn as ``benchmark/traffic/reason-saturated.json``
draws them (one of its requests a row, a prompt of 64-256 plus a uniform
share of its answer of 1,024-1,728). Beside the time: the bytes the call
needs (latent + rope key of the held tokens at the unpadded 1,152 B, queries
in, output out), the pages a walk moves if it fetches each held page once
(``ceil(len / page)`` summed: what the kernel does) and if it fills every
visited block (blocks x pages a block: a walk that clamps its tail, as the
GQA kernel's does), and each as a share of ``benchmark/peaks.json``'s HBM
peak (moved bytes count the rope stream's padded lanes, 1,280 B a token).
``--rehearse`` (or no TPU) runs tiny shapes in interpret mode and prints no time.

    python3 tools/mla_decode_bench.py --side up [--heads 32,64] [--tokens 64,128]

One layer's two per-head up-projections alone (``q_nope x w_uk``, ``out_lat x
w_uv``: no cache, no kernel between them, a barrier where it stands), 40 layers
scanned over the stacked leaves as the layer scan scans them: the published
lay-out (``[L, r_kv, H, d]``) beside the one an unsharded runner serves
(``models/mla.lay_heads_major``), through the model's own ``up_project``. Prints
us a layer and the share of the HBM peak on the two weights' one read (2 x
``r_kv x H x d`` x 2 bytes), and how far the two forms' outputs lie apart.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

R_KV, R_ROPE, ROPE_LANES, PAGE, PAGES_PER_SEQ = 512, 64, 128, 128, 16
LAYERS = 40


def up_side(args, on_chip: bool) -> list[dict]:
    """Both lay-outs at every ``--heads`` x ``--tokens``: one row each."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.models.mla import lay_heads_major, up_project

    def stack(x, leaves):
        def layer(x, lp):
            q_lat = up_project(lp, "w_uk", x)
            # the latent kernel stands here: float32 out of it, rounded before w_uv
            out_lat = jax.lax.optimization_barrier(q_lat.astype(jnp.float32))
            out = jax.lax.optimization_barrier(up_project(lp, "w_uv", out_lat.astype(x.dtype)))
            return x + out * jnp.asarray(1e-3, x.dtype), None

        return jax.lax.scan(layer, x, leaves)[0]

    call = jax.jit(stack)
    if on_chip:
        heads, tokens = [int(h) for h in args.heads.split(",")], [int(t) for t in args.tokens.split(",")]
        layers, r_kv, d, iters, dtype = LAYERS, R_KV, 128, args.iters, jnp.bfloat16
    else:
        heads, tokens, layers, r_kv, d, iters, dtype = [4], [1, 8], 2, 32, 16, 1, jnp.float32
    hbm = json.loads((ROOT / "benchmark" / "peaks.json").read_text())["TPU v5 lite"]["hbm_bytes_per_s"]
    rng = np.random.default_rng(args.seed)
    table = []
    for n_heads in heads:
        published = {name: jnp.asarray(rng.standard_normal((layers, r_kv, n_heads, d)) * r_kv ** -0.5, dtype)
                     for name in ("w_uk", "w_uv")}
        need = 2 * r_kv * n_heads * d * jnp.dtype(dtype).itemsize
        for t in tokens:
            x = jnp.asarray(rng.standard_normal((1, t, n_heads, d)), dtype)
            first = None
            for name, lay in (("published", lambda leaves: leaves), ("heads_major", lay_heads_major)):
                leaves = jax.block_until_ready(lay(published))
                out = np.asarray(jax.block_until_ready(call(x, leaves)), np.float32)
                assert np.isfinite(out).all()
                first = out if first is None else first
                row = {"side": "up", "form": name, "heads": n_heads, "tokens": t, "needed_bytes": need,
                       "max_abs_diff_to_first": float(np.abs(out - first).max())}
                if on_chip:
                    t0 = time.perf_counter()
                    for _ in range(iters):
                        got = call(x, leaves)
                    jax.block_until_ready(got)
                    s = (time.perf_counter() - t0) / iters / layers
                    row.update(us=round(s * 1e6, 2), needed_hbm_pct=round(100 * need / hbm / s, 1))
                print(json.dumps(row), flush=True)
                table.append(row)
    return table


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--side", choices=("kernel", "up"), default="kernel")
    ap.add_argument("--tokens", default="64,128", help="--side up: token positions of a decode and of a mixed step")
    ap.add_argument("--rows", type=int, default=64)
    ap.add_argument("--heads", default="32,64")
    ap.add_argument("--lengths", default="256,768,769,1536,1984,mix")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.ops.pallas_mla import mla_paged_decode
    from dynamo_tpu.ops.pallas_paged import _dma_depth, _pages_per_block

    on_chip = jax.default_backend() == "tpu" and not args.rehearse
    if args.side == "up":
        table = up_side(args, on_chip)
        print(json.dumps({"mla_decode_bench": "v5e" if on_chip else "rehearsal: no time is a device time",
                          "rows": len(table)}))
        return 0 if on_chip else 3
    if on_chip:
        rows, iters, layers, page, r_kv, lanes = args.rows, args.iters, LAYERS, PAGE, R_KV, ROPE_LANES
        heads, lengths = [int(h) for h in args.heads.split(",")], args.lengths.split(",")
        dtype = jnp.bfloat16
    else:
        rows, iters, layers, page, r_kv, lanes = 3, 1, 2, 8, 128, 128
        heads, lengths, dtype = [4], ["24", "49", "mix"], jnp.float32
    hbm = json.loads((ROOT / "benchmark" / "peaks.json").read_text())["TPU v5 lite"]["hbm_bytes_per_s"]
    mix = json.loads((ROOT / "benchmark" / "traffic" / "reason-saturated.json").read_text())["lengths_per_100"]
    itemsize = jnp.dtype(dtype).itemsize
    ppb = _pages_per_block(PAGES_PER_SEQ, page, r_kv + lanes, itemsize, _dma_depth())
    rng = np.random.default_rng(args.seed)
    num_pages = rows * PAGES_PER_SEQ + 1
    c_cache = jnp.asarray(rng.standard_normal((num_pages, page, r_kv)), dtype)
    r_cache = jnp.asarray(rng.standard_normal((num_pages, page, lanes)), dtype)
    tables = jnp.asarray(1 + np.arange(rows * PAGES_PER_SEQ, dtype=np.int32).reshape(rows, PAGES_PER_SEQ))
    scale = (128 + R_ROPE) ** -0.5

    def stack(ql, qr, cc, rc, bt, pos):
        # LAYERS calls in a row, each fed by the one before, in one program:
        # the host's dispatch is paid once, not per call.
        def layer(q, _):
            o = mla_paged_decode(q, qr, cc, rc, bt, pos, scale=scale, interpret=not on_chip)
            return q + (o * 1e-3).astype(q.dtype), None

        return jax.lax.scan(layer, ql, None, length=layers)[0]

    call = jax.jit(stack)
    table = []
    for n_heads in heads:
        q_lat = jnp.asarray(rng.standard_normal((rows, n_heads, r_kv)), dtype)
        q_rope = jnp.asarray(rng.standard_normal((rows, n_heads, lanes)), dtype)
        for spec in lengths:
            if spec == "mix":
                # A full batch at a moment of the window: each row one of the
                # mix's requests, somewhere in its answer.
                prompt, answer = np.asarray(mix)[rng.integers(0, len(mix), rows)].T
                lens = np.ceil((prompt + rng.random(rows) * answer) * (PAGES_PER_SEQ * page / 2048))
                lens = lens.astype(np.int64)  # the rehearsal's rows are shorter
            else:
                lens = np.full(rows, int(spec), np.int64)
            lens = np.clip(lens, 1, PAGES_PER_SEQ * page)
            pos = jnp.asarray(lens[:, None] - 1, jnp.int32)
            out = jax.block_until_ready(call(q_lat, q_rope, c_cache, r_cache, tables, pos))
            assert bool(jnp.isfinite(out.astype(jnp.float32)).all())
            need = int(lens.sum()) * (r_kv + R_ROPE) * itemsize + rows * n_heads * (2 * r_kv + lanes) * itemsize
            page_bytes = page * (r_kv + lanes) * itemsize
            held = int((-(-lens // page)).sum())
            by_block = int((-(-lens // (ppb * page))).sum()) * ppb
            row = {"rows": rows, "heads": n_heads, "lengths": spec, "tokens": int(lens.sum()),
                   "pages_per_block": ppb, "needed_bytes": need,
                   "pages_held": held, "pages_by_block": by_block}
            if on_chip:
                t0 = time.perf_counter()
                for _ in range(iters):
                    out = call(q_lat, q_rope, c_cache, r_cache, tables, pos)
                jax.block_until_ready(out)
                s = (time.perf_counter() - t0) / iters / layers
                row.update(us=round(s * 1e6, 2),
                           needed_hbm_pct=round(100 * need / hbm / s, 1),
                           held_pages_hbm_pct=round(100 * held * page_bytes / hbm / s, 1),
                           block_pages_hbm_pct=round(100 * by_block * page_bytes / hbm / s, 1))
            print(json.dumps(row), flush=True)
            table.append(row)
    print(json.dumps({"mla_decode_bench": "v5e" if on_chip else "rehearsal: no time is a device time",
                      "rows": len(table)}))
    return 0 if on_chip else 3


if __name__ == "__main__":
    sys.exit(main())
