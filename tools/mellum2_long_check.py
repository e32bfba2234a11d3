#!/usr/bin/env python3
"""The outputs check of a windowed configuration at lengths that cross its
window, and the controls that show the check can see the window and the RoPEs.

    python3 tools/mellum2_long_check.py [--workload <cell>] --seeds 3 --variants long,ragged,fp8kv,int4w,allw

``benchmark/correct.py`` checks prompts of 192 and 128 tokens, which never
reach a window of 1024, and that file is the benchmark's. This script uses the
benchmark's own ``run.bring_up`` and ``correct`` in its own process with
``correct.CHECKED`` set here to prompts of 2304 and 1536 tokens (``long``):
the live engine prefills them in 64-token chunks beside a decoding row and
decodes 4 tokens through the cache; the float32 reference scores the same
tokens. The same served sample is then scored against the reference made
wrong in two ways, which must come out over the limit:

- ``all_full``: the reference's sliding layers made full (window past every position);
- ``one_rope``: the sliding layers' plain RoPE in the full layers too.

``ragged`` serves three prompts whose lengths are no multiple of the chunk,
all offered at once beside a decoding row: where one prompt's tail leaves
budget, the next one's head shares the step, so the step has two chunk rows
(the two-chunk-slot program of ``runner.MAX_CHUNK_SLOTS``, which no cell of the
benchmark runs). Its rows name the chunk steps by layout and token positions.

``fp8kv`` and ``int4w`` are the two lower-precision controls of
``benchmark/control.py`` at the benchmark's own lengths (its int4 re-coding
keeps the int8 tree until the int4 one is whole, which a 12 GB model does not
fit beside; here each leaf goes as soon as it is re-coded). The KV pool is cut
to ``--pool-tokens`` in this process only, to leave room for the reference at
2,308 tokens. Run by hand on the chip; ``JAX_PLATFORMS=cpu`` rehearses at the
configuration's toy size with lengths cut by its check scale.

``allw`` (PR 49) is the control for a configuration whose residual stream its
plain leaves carry (granite-4.0-h-small's: ``int4w`` moves a tenth of it and
reads inside the sound readings): ``int4w``'s re-coding, and besides it every
plain bf16 matmul leaf (``PLAIN_MATMUL``: the embedding, which a tied model
also serves as its head, the router and a Mamba-2 mixer's two projections)
one precision down as well, to e4m3 with a float32 scale per output channel,
widened back to bf16 for the program. The cell's ``logprob_rel_limit`` lies
between the sound readings and this control's. ``--pool-tokens 0`` keeps the
cell's own pool.

``--where`` (PR 42) says where a reading comes from: for every compared token
the served logprobs against the float32 reference, and beside it the reference
against *itself* with its matmuls at bfloat16 precision on the same weights
and ids. A token at which the reference moves as far under rounding alone as
the served path lies from it is a token the sequence makes sensitive (a
near-tie in a router or under a sharp softmax upstream), not a fault of the
served path.
"""

from __future__ import annotations

import argparse
import asyncio
import collections
import gc
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "benchmark"))

import run as bench_run  # noqa: E402

LONG = [(2304, 4), (1536, 4)]
RAGGED = [(2290, 4), (1530, 4), (777, 4)]
SLIDING, FULL = "sliding_attention", "full_attention"


def _free(*trees) -> None:
    import jax

    for leaf in jax.tree.leaves(trees):
        if not leaf.is_deleted():
            leaf.delete()
    gc.collect()


def _to_int4_leaf_by_leaf(params):
    """``weights.requantize_int4`` one matmul leaf at a time, the int8 codes
    deleted as soon as their int4 form exists."""
    from benchmark import weights

    def walk(t):
        if isinstance(t, dict) and "qw" in t:
            low = weights.requantize_int4({"x": t})["x"]
            _free(t)
            return low
        return {k: walk(v) for k, v in t.items()} if isinstance(t, dict) else t

    return walk(params)


#: Plain bf16 leaves that are an operand of a matmul, with the axis their
#: output channel's scale is taken over (the embedding's row is a lookup and,
#: tied, the head's output channel; the others are [.., d_in, d_out]).
PLAIN_MATMUL = {"embed": -1, "router": -2, "w_ssm_in": -2, "w_ssm_out": -2}


def _plain_leaves_down(params):
    """Every ``PLAIN_MATMUL`` leaf re-coded one precision down: e4m3 (three
    bits of mantissa) with a float32 scale per output channel, widened back to
    the leaf's dtype; each leaf goes as soon as its re-coding exists. By
    ``lax.reduce_precision`` on values scaled to e4m3's largest normal of 240:
    a convert to a float8 type and back is a pair the TPU compiler removes,
    and the leaf comes back as it went (PERF.md, PR 49). Says how far each
    leaf moved (rms of the change over rms of the leaf: 0.026), so that a
    re-coding that did nothing shows."""
    import jax
    import jax.numpy as jnp

    def down(w, axis):
        x = w.astype(jnp.float32)
        top = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30)
        back = jax.lax.reduce_precision(x * (240.0 / top), exponent_bits=4, mantissa_bits=3) * (top / 240.0)
        return back.astype(w.dtype), jnp.sum(jnp.square(back - x)), jnp.sum(jnp.square(x))

    moved = {}

    def leaf(name, w, axis):
        if w.size <= 2**27:
            low, err, norm = jax.jit(lambda y: down(y, axis))(w)
        else:  # a slice of the leading axis at a time: the float32 transient of the whole leaf would not fit
            cut = (8, w.shape[0] // 8) + w.shape[1:] if w.ndim == 2 else w.shape
            low, err, norm = jax.jit(lambda y: jax.lax.map(lambda z: down(z, axis), y.reshape(cut)))(w)
            low = low.reshape(w.shape)
        moved[name] = round(float(jnp.sqrt(jnp.sum(err) / jnp.sum(norm))), 5)
        _free(w)
        return low

    def walk(t):
        return {k: leaf(k, v, PLAIN_MATMUL[k]) if k in PLAIN_MATMUL and not isinstance(v, dict) else walk(v)
                for k, v in t.items()} if isinstance(t, dict) else t

    low = walk(params)
    bench_run.say(plain_leaves_down={"rms_moved": moved})
    return low


TRANSFORMS = {"int4w": _to_int4_leaf_by_leaf, "allw": lambda params: _plain_leaves_down(_to_int4_leaf_by_leaf(params))}


def reference_variants(conf: dict) -> dict:
    hf = conf["hf"]
    one_rope = {**hf, "rope_parameters": {k: hf["rope_parameters"][SLIDING] for k in (SLIDING, FULL)}}
    return {"sound": conf, "all_full": {**conf, "hf": {**hf, "sliding_window": 2**30}},
            "one_rope": {**conf, "hf": one_rope}}


async def serve_together(service, conf: dict, seed: int, *, scale: float) -> dict:
    """``correct.serve_sample`` with every checked prompt offered at once, and
    a filler that decodes beside them to the end."""
    import numpy as np

    from benchmark import correct

    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 77]))
    sized = [(max(8, int(p * scale)), n) for p, n in correct.CHECKED]
    prompts = [rng.integers(1, conf["hf"]["vocab_size"], size=p).tolist() for p, _ in sized]
    filler = rng.integers(1, conf["hf"]["vocab_size"], size=max(8, int(64 * scale))).tolist()
    started = asyncio.Event()
    beside = asyncio.ensure_future(correct._served(service, filler, 160, logprobs=False, started=started))
    await started.wait()
    served = await asyncio.gather(*(correct._served(service, p, n, logprobs=True) for p, (_, n) in zip(prompts, sized)))
    await beside
    return {"served": served,
            "sequences": [p + [e["id"] for e in row][:-1] for p, row in zip(prompts, served)],
            "spans": [(len(p) - 1, len(row)) for p, row in zip(prompts, served)]}


def where(conf: dict, params, sample: dict) -> dict:
    """Per compared token, as shares of the largest reference logit: the served
    logprobs' distance from the float32 reference (largest, mean and mean
    signed over the ids the engine named) and the distance of the reference
    computed with bfloat16 matmuls from the float32 one over the same ids."""
    import functools
    import importlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import correct

    exact, absmax = correct.reference_logprobs(conf, params, sample["sequences"], sample["spans"])
    ref = importlib.import_module(f"benchmark.reference.{conf['reference']}")
    rounded = jax.jit(functools.partial(ref.forward, hf=conf["hf"]))
    tokens = []
    with jax.default_matmul_precision("bfloat16"):
        for i, (seq, (first, steps)) in enumerate(zip(sample["sequences"], sample["spans"])):
            toks = np.zeros(max(correct.PAD_TO, len(seq)), np.int32)
            toks[: len(seq)] = seq
            logits = np.asarray(rounded(params, tokens=jnp.asarray(toks))[first: first + steps], np.float32)
            z = logits - logits.max(axis=-1, keepdims=True)
            low = z - np.log(np.exp(z).sum(axis=-1, keepdims=True))
            for j, e in enumerate(sample["served"][i]):
                ids = [e["id"]] + [t for t, _ in e["top"]]
                got = np.asarray([e["logprob"]] + [lp for _, lp in e["top"]], np.float64)
                served, own = got - exact[i][j, ids], low[j, ids] - exact[i][j, ids]
                tokens.append({"sequence": i, "position": first + j,
                               "served_max": float(np.abs(served).max() / absmax),
                               "served_mean": float(np.abs(served).mean() / absmax),
                               "served_signed_mean": float(served.mean() / absmax),
                               "reference_bf16_max": float(np.abs(own).max() / absmax),
                               "reference_bf16_mean": float(np.abs(own).mean() / absmax)})
    return {"ref_logit_absmax": absmax, "tokens": tokens,
            "served_max": max(t["served_max"] for t in tokens),
            "reference_bf16_max": max(t["reference_bf16_max"] for t in tokens)}


async def one(args, bench, cell, rehearsal: bool, seed: int, variant: str) -> list[dict]:
    from benchmark import correct, serving, weights

    args.seed = seed
    os.environ.pop("DYN_KV_CACHE_DTYPE", None)
    if variant == "fp8kv":
        os.environ["DYN_KV_CACHE_DTYPE"] = "fp8"
    correct.CHECKED = {"long": LONG, "ragged": RAGGED}.get(variant, [(192, 4), (128, 4)])
    state = await bench_run.bring_up(args, bench, cell, rehearsal, warm=False,
                                     transform=TRANSFORMS.get(variant))
    conf, core = state["conf"], state["core"]
    try:
        serve = serve_together if variant == "ragged" else correct.serve_sample
        sample = await serve(state["service"], conf, seed, scale=state["check_scale"])
        steps = core.flight.snapshot(kind="step")
    finally:
        await serving.stop(state["handles"])
        os.environ.pop("DYN_KV_CACHE_DTYPE", None)
    runner, params = core.runner, state["params"]
    _free(runner.k_cache, runner.v_cache, getattr(runner, "state", ()))  # room for the reference's whole-sequence pass
    if variant in TRANSFORMS:  # the reference reads the weights as configured: make them again
        _free(runner.params, params)
        params = weights.make_weights(serving.model_config(conf), seed, quant=conf["serve"]["quant"])
    rows = []
    refs = reference_variants(conf) if variant == "long" else {"sound": conf}
    for ref_name, ref_conf in refs.items():
        check = correct.score(ref_conf, params, sample)
        rows.append({"seed": seed, "variant": variant, "reference": ref_name,
                     "prompts": [len(s) - n + 1 for s, (_, n) in zip(sample["sequences"], sample["spans"])],
                     "mixed_steps": sum(1 for s in steps if s["step_kind"] == "mixed"),
                     "chunk_steps": dict(collections.Counter(
                         f"{s['chunk_rows']}_chunk_rows.{s.get('layout', '')}.{s.get('step_tokens', 0)}"
                         for s in steps if s["chunk_rows"])),
                     "decode_steps": sum(1 for s in steps if s["step_kind"] == "decode"),
                     "attn_paths": sorted({s["attn_path"] for s in steps if s["attn_path"]}),
                     "moe_paths": sorted({s["moe_path"] for s in steps if s["moe_path"]}), **check})
        bench_run.say(long_check=rows[-1])
    if args.where:
        bench_run.say(where={"seed": seed, "variant": variant, **where(conf, params, sample)})
    _free(runner.params, params)
    return rows


async def amain(args) -> int:
    from benchmark import serving

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    rehearsal = os.environ.get("JAX_PLATFORMS", "") == "cpu"
    load = serving.load_config

    def load_with_small_pool(path, *, rehearsal=False):
        conf = load(path, rehearsal=rehearsal)
        if not rehearsal and args.pool_tokens:
            conf["serve"]["engine"]["pool_tokens"] = args.pool_tokens
        return conf

    serving.load_config = load_with_small_pool  # this process only: no file of the benchmark is edited
    rows = []
    for variant in args.variants.split(","):
        for i in range(args.seeds):
            rows += await one(args, bench, cell, rehearsal, args.first_seed + 7919 * i, variant)
    summary = {}
    for key in sorted({(r["variant"], r["reference"]) for r in rows}):
        errs = [r["rel_err"] for r in rows if (r["variant"], r["reference"]) == key]
        summary["/".join(key)] = {"n": len(errs), "rel_err_min": min(errs), "rel_err_max": max(errs),
                                  "limit": rows[0]["limit"], "ok": [r["ok"] for r in rows
                                                                     if (r["variant"], r["reference"]) == key]}
    print(json.dumps({"long_check_summary": summary}))
    return 0


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", default="mellum2-12b-a2.5b-int8.longctx-decode")
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2600000003)
    ap.add_argument("--variants", default="long,fp8kv,int4w")
    ap.add_argument("--pool-tokens", type=int, default=12288, help="0: the cell's own pool")
    ap.add_argument("--where", action="store_true", help="per compared token: served and bfloat16 reference against float32")
    os.environ.setdefault("DYN_FLIGHT_BUFFER", "65536")
    sys.exit(asyncio.run(amain(ap.parse_args())))
