#!/usr/bin/env python3
"""The outputs check over many seeds, sound and in lower precision, in one
process: the two readings a limit is set from ("How correct is decided").

    python3 benchmark/control.py --workload <cell> --seeds 12 --variants sound,fp8kv,int4w

``sound`` is the configuration as served. ``fp8kv`` switches on the program's
own fp8 KV cache (``DYN_KV_CACHE_DTYPE=fp8``). ``int4w`` re-codes the same
weights as the program's packed int4 and serves those; the reference always
reads the int8 weights. No timed window, no warm-up: one set-up per seed and
variant, a few step programs each. Run by hand on the chip.
"""

from __future__ import annotations

import argparse
import asyncio
import gc
import json
import os
import sys

import run as bench_run  # noqa: E402  (same directory)


def _free(*trees) -> None:
    import jax

    for leaf in jax.tree.leaves(trees):
        leaf.delete()
    gc.collect()


async def one(args, bench, cell, rehearsal, seed: int, variant: str) -> dict:
    from benchmark import correct, serving, weights

    args.seed = seed
    os.environ.pop("DYN_KV_CACHE_DTYPE", None)
    if variant == "fp8kv":
        os.environ["DYN_KV_CACHE_DTYPE"] = "fp8"

    def to_int4(params):  # the int8 tree goes as soon as its int4 re-coding exists: both do not fit
        import jax

        low = weights.requantize_int4(params)
        kept = {id(x) for x in jax.tree.leaves(low)}  # plain leaves are shared, not copied
        _free([x for x in jax.tree.leaves(params) if id(x) not in kept])
        return low

    state = await bench_run.bring_up(args, bench, cell, rehearsal, warm=False,
                                     transform=to_int4 if variant == "int4w" else None)
    conf, runner = state["conf"], state["core"].runner
    try:
        try:
            sample = await correct.serve_sample(state["service"], conf, seed, scale=state["check_scale"])
        finally:
            await serving.stop(state["handles"])
            os.environ.pop("DYN_KV_CACHE_DTYPE", None)
        params = state["params"]
        if variant == "int4w":  # the reference reads the weights as configured: make them again
            _free(runner.params, runner.k_cache, runner.v_cache, params)
            params = weights.make_weights(serving.model_config(conf), seed, quant=conf["serve"]["quant"])
        check = correct.score(conf, params, sample)
        bench_run.say(outputs_check=check)
    except Exception as e:  # a control that crashes has failed, and sets no upper end
        check = {"error": f"{type(e).__name__}: {e}"[:300]}
        params = state["params"]
    _free(runner.params, runner.k_cache, runner.v_cache, state["params"], params)
    return {"seed": seed, "variant": variant, **check}


async def amain(args) -> int:
    bench = json.loads((bench_run.ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    rehearsal = os.environ.get("JAX_PLATFORMS", "") == "cpu"
    rows = []
    for variant in args.variants.split(","):
        n = args.seeds if variant == "sound" else args.control_seeds
        for i in range(n):
            row = await one(args, bench, cell, rehearsal, args.first_seed + 7919 * i, variant)
            bench_run.say(control=row)
            rows.append(row)
    summary = {}
    for v in {r["variant"] for r in rows}:
        errs = [r["rel_err"] for r in rows if r["variant"] == v and "rel_err" in r]
        means = [r["mean_rel_err"] for r in rows if r["variant"] == v and "rel_err" in r]
        summary[v] = {"n": len(errs), "rel_err_min": min(errs, default=None), "rel_err_max": max(errs, default=None),
                      "mean_rel_err_min": min(means, default=None), "mean_rel_err_max": max(means, default=None),
                      "crashed": sum(1 for r in rows if r["variant"] == v and "error" in r)}
    print(json.dumps({"control_summary": summary}))
    return 0


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2500000003)
    ap.add_argument("--variants", default="sound,fp8kv,int4w")
    sys.exit(asyncio.run(amain(ap.parse_args())))
