#!/usr/bin/env python3
"""Time the routed experts of an int8 MoE stack on the attached TPU: the fused
grouped-matmul kernel (``ops/pallas_moe.py``) against ``_widen`` + ``ragged_dot``,
under ``lax.scan`` over stacked layers as the model step runs them.

    python tools/moe_kernel_bench.py [--copies 32,512,2048,32768] [--tiles tm:tn_gu:tn_d:tk,...]

One JSON line per (token copies, variant): milliseconds per layer, and for the
fused kernel the int8 GB/s over the experts that had rows. Fails off-TPU: a CPU
time is not a device number.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--copies", default="32,512,2048,32768")
    ap.add_argument("--tiles", default="", help="tm:tn_gate_up:tn_down:tk variants; 0 = the kernel's own choice")
    ap.add_argument("--layers", type=int, default=16)
    ap.add_argument("--experts", type=int, default=64)
    ap.add_argument("--topk", type=int, default=8)
    ap.add_argument("--hidden", type=int, default=2048)
    ap.add_argument("--width", type=int, default=1024)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--skip-widened", action="store_true")
    ap.add_argument("--padding", default="", help="padding tokens among a step's: time the whole layer, routed and masked")
    ap.add_argument("--rehearse", action="store_true", help="off-TPU: interpret mode, control flow only, no times")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["DYNAMO_PALLAS_INTERPRET"] = "1"  # the whole layer asks the program's own predicate for its path

    import jax
    import jax.numpy as jnp

    from dynamo_tpu.ops.pallas_moe import group_metadata, grouped_matmul_int8, row_tile
    from dynamo_tpu.parallel.moe import _widen

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(json.dumps({"error": f"needs a TPU, found {dev.platform}"}))
        return 2
    nl, e, d, f, k = args.layers, args.experts, args.hidden, args.width, args.topk

    @jax.jit
    def make(key):
        ks = jax.random.split(key, 6)
        q = lambda kk, shape: jax.random.randint(kk, shape, -127, 128, jnp.int8)  # noqa: E731
        s = lambda kk, shape: (jax.random.uniform(kk, shape) * 0.002 + 0.001).astype(jnp.bfloat16)  # noqa: E731
        return {
            "w_gate": {"qw": q(ks[0], (nl, e, d, f)), "scale": s(ks[1], (nl, e, f))},
            "w_up": {"qw": q(ks[2], (nl, e, d, f)), "scale": s(ks[3], (nl, e, f))},
            "w_down": {"qw": q(ks[4], (nl, e, f, d)), "scale": s(ks[5], (nl, e, d))},
        }

    stack = make(jax.random.PRNGKey(0))

    def routed(m, seed):
        """Sorted token copies and group sizes: m // k tokens, top-k of e uniformly."""
        key = jax.random.PRNGKey(seed)
        n = m // k
        topi = jax.vmap(lambda kk: jax.random.permutation(kk, e)[:k])(jax.random.split(key, n))
        flat = topi.reshape(-1)
        x = jax.random.normal(key, (n, d), jnp.bfloat16)
        xk = jnp.repeat(x, k, axis=0)[jnp.argsort(flat, stable=True)]
        return xk, jnp.bincount(flat, length=e).astype(jnp.int32)

    def fused(tiles):
        tm, tn_gu, tn_d, tk = tiles

        def run(stack, xk, sizes):
            def layer(carry, xs):
                li, acc = carry
                kw = dict(tm=tm or None, tk=tk or None, interpret=args.rehearse)
                h = grouped_matmul_int8(
                    xk, (stack["w_gate"]["qw"], stack["w_up"]["qw"]), (xs["w_gate"]["scale"], xs["w_up"]["scale"]),
                    sizes, li, act="silu_mul", tn=tn_gu or None, **kw)
                out = grouped_matmul_int8(
                    h, (stack["w_down"]["qw"],), (xs["w_down"]["scale"],), sizes, li, tn=tn_d or None, **kw)
                return (li + 1, acc + out.astype(jnp.float32)), None

            scales = {n: {"scale": stack[n]["scale"]} for n in stack}
            (_, acc), _ = jax.lax.scan(layer, (jnp.int32(0), jnp.zeros((xk.shape[0], d), jnp.float32)), scales)
            return acc

        return jax.jit(run)

    def widened(stack, xk, sizes):
        def layer(acc, lp):
            wg, wu, wd = _widen(lp, xk.dtype)
            gate = jax.nn.silu(jax.lax.ragged_dot(xk, wg, sizes))
            up = jax.lax.ragged_dot(xk, wu, sizes)
            return acc + jax.lax.ragged_dot(gate * up, wd, sizes).astype(jnp.float32), None

        acc, _ = jax.lax.scan(layer, jnp.zeros((xk.shape[0], d), jnp.float32), stack)
        return acc

    def timed(fn, *a):
        out = fn(*a)
        out.block_until_ready()
        t0 = time.perf_counter()
        for _ in range(args.iters):
            out = fn(*a)
        out.block_until_ready()
        # A CPU time is never written under a device metric's name.
        return (float("nan") if args.rehearse else (time.perf_counter() - t0) / args.iters * 1e3), out

    def whole_layer(m, padding):
        """``moe_mlp_dropless`` over the stacked layers on ``m // k`` tokens, the last ``padding`` of them one token."""
        from dynamo_tpu.parallel import moe

        n = m // k
        keys = jax.random.split(jax.random.PRNGKey(m + padding), 3)
        x = jax.random.normal(keys[0], (n, d), jnp.bfloat16)
        x = x.at[n - padding:].set(jax.random.normal(keys[1], (d,), jnp.bfloat16)) if padding else x
        layers = {"router": jax.random.normal(keys[2], (nl, d, e), jnp.bfloat16), **stack}
        path = moe.experts_path(layers)  # "fused" on a TPU: what the cells serve
        xs, experts = moe.split_expert_stack(layers)  # as ``llama.forward`` scans a stack of layers
        live = jnp.arange(n) < n - padding

        @functools.partial(jax.jit, static_argnames=("masked", "count"))
        def run(experts, xs, x, *, masked, count):
            def layer(carry, lp):
                li, x = carry
                lp = moe.join_expert_stack(lp, experts, li)
                out = moe.moe_mlp_dropless(lp, x, num_experts_per_token=k, valid=live if masked else None)
                visited = None
                if count:  # the experts whose group has a row: those the tokens in the matmuls chose
                    _, topi = moe.route_tokens(lp, x, k=k)
                    chosen = (topi[:, :, None] == jnp.arange(e)).any(axis=1) & (live[:, None] if masked else True)
                    visited = chosen.any(axis=0).sum()
                h = x.astype(jnp.float32) + out.astype(jnp.float32)  # the stream goes on, at a norm of one an entry
                return (li + 1, (h * jax.lax.rsqrt((h * h).mean(axis=-1, keepdims=True) + 1e-6)).astype(x.dtype)), visited

            (_, x), visited = jax.lax.scan(layer, (jnp.int32(0), x), xs)
            return (x, visited) if count else x

        for variant in ("routed", "masked"):
            masked = variant == "masked"
            ms, _ = timed(functools.partial(run, masked=masked, count=False), experts, xs, x)
            visited = run(experts, xs, x, masked=masked, count=True)[1]
            print(json.dumps({"copies": m, "tokens": n, "padding": padding, "variant": variant, "ms_per_layer": ms / nl,
                              "experts_visited_per_layer": float(visited.mean()), "layers": nl, "path": path}), flush=True)

    if args.padding:
        for m in (int(c) for c in args.copies.split(",")):
            for padding in (int(v) for v in args.padding.split(",")):
                whole_layer(m, padding)
        return 0

    variants = [(0, 0, 0, 0)] + [tuple(int(v) for v in t.split(":")) for t in args.tiles.split(",") if t]
    for m in (int(c) for c in args.copies.split(",")):
        xk, sizes = routed(m, seed=m)
        touched = int((sizes > 0).sum())
        ref = None
        if not args.skip_widened:
            ms, ref = timed(jax.jit(widened), stack, xk, sizes)
            print(json.dumps({"copies": m, "variant": "widened", "ms_per_layer": ms / nl, "experts_with_rows": touched}), flush=True)
        for tiles in variants:
            if tiles[0] and (tiles[0] > max(m, 16) or m % tiles[0]):
                continue
            try:
                ms, out = timed(fused(tiles), stack, xk, sizes)
            except Exception as err:  # a tile the compiler refuses is a finding, not a crash
                print(json.dumps({"copies": m, "variant": "fused", "tiles": tiles, "error": str(err)[:300]}), flush=True)
                continue
            tm = tiles[0] or row_tile(m)
            visits = int(group_metadata(sizes, -(-m // tm) * tm, tm)[3][0])
            line = {
                "copies": m, "variant": "fused", "tiles": tiles, "ms_per_layer": ms / nl,
                "experts_with_rows": touched, "visits": visits,
                "int8_gb_s_touched": touched * 3 * d * f / (ms / nl * 1e-3) / 1e9,
            }
            if ref is not None:
                line["max_diff_vs_widened"] = float(jnp.abs(out - ref).max() / jnp.abs(ref).max())
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
