#!/usr/bin/env python3
"""The expert layer alone: the program's held-share MoE block against the plain
reference's, at the configuration's published widths, part by part: the held
experts, the identity ("zero-compute") experts where the router has them, the
shared expert where the model has one.

    python3 tools/held_moe_check.py [--workload <cell>] --seeds 3 --tokens 384
    python3 tools/held_moe_check.py [--workload <cell>] --time 64,128 [--time-layers 12]

``benchmark/correct.py`` is weak on the held experts by construction: 2% of a
token's choices reach LongCat-Flash's 16 of 512 experts here and an eighth
JoyAI-LLM-Flash's 32 of 256, so a wrong expert kernel moves the logits little.
Here one MoE layer's weights are made from the seed (``benchmark/weights.py``),
``--tokens`` seeded unit-RMS hidden vectors go through the program's layer as
``llama.forward`` calls it (``llama._mlp_moe_held``: stacked int8 experts, the
fused kernel on the chip) and through the ``route`` / ``held_experts_term`` /
``zero_experts_term`` / ``shared_expert_term`` of the configuration's reference
in float32 at ``highest`` matmul precision. Compared, each as
max |program - reference| over max |reference|:

- ``held``: ``parallel/moe.moe_mlp_held`` told that no router output is an
  identity (``routed`` = all outputs: the same routing, no identity term)
  against ``held_experts_term``. In LongCat-Flash the held part is sixty times
  smaller than the identity part, so it cannot be read off the difference of
  two bf16 outputs;
- ``zero`` (a router with identity outputs): the layer's call with no token valid
  for the experts (the identity term is computed wherever the token lives)
  against ``zero_experts_term``;
- ``shared`` (a model with a shared expert): ``llama._shared_expert`` against
  ``shared_expert_term``;
- ``whole``: the layer's output against the sum of the parts there are.

Two controls must come out far from the reference: ``rolled`` (the program's
held experts shifted by one id: a kernel that reads the wrong group) and
``int4`` (the experts re-coded one precision down, ``weights.requantize_int4``).
The counts (choices, identity choices, held choices, experts touched) are
printed beside their even-routing expectations and the reference's own.
``LIMIT`` (0.02) stands between the sound readings on the chip and the int4
control's (PERF.md section 6, PR 34 and PR 36); the exit code is 1 where a
sound reading is over it or a control under it. Run by hand on the chip;
``JAX_PLATFORMS=cpu`` rehearses at the configuration's toy size.

``--time`` times the routed part alone instead (``moe_mlp_held``: router,
bookkeeping, the held experts, the combine; no shared expert): a scan over
``--time-layers`` MoE layers of the int8 stack as the layer scan hands them
over, at each of the given token counts, microseconds a layer (the median of
20 runs over the layers; ``--time-ops FILE`` also traces five runs and keeps the
device's time operation by operation). ``--time-caps a,b`` times it once more
for each number with the rows of the usual pass (``held_rows_cap``) set to it:
the expert kernel's row tile follows the pass's rows. To compare two trees, unpack the other under ``_scratch/``,
copy this file into its ``tools/`` and run both in one call.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
#: max |program - reference| / max |reference| a sound part may read, and a control must exceed.
LIMIT = 0.02
sys.path.insert(0, str(ROOT))


def program_layer(cfg, layers):
    """MoE layer 0 of the stack as the layer scan hands it to the expert layer."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.parallel.moe import join_expert_stack, split_expert_stack

    xs, stack = split_expert_stack(layers)
    top = {k: v for k, v in xs.items() if not k.startswith("sub")}
    return join_expert_stack(jax.tree.map(lambda x: x[0], top), stack, jnp.int32(0))


def program_routed(cfg, layers, h, *, valid, identities: bool):
    """``parallel/moe.moe_mlp_held`` as the layer calls it, without the shared
    expert. ``identities`` False: every router output counts as a routed
    expert (the same routing, no identity term)."""
    from dynamo_tpu.models.llama import _routing_kwargs
    from dynamo_tpu.parallel.moe import moe_mlp_held

    return moe_mlp_held(program_layer(cfg, layers), h, num_experts_per_token=cfg.num_experts_per_token,
                        first=cfg.moe_expert_first, routed=cfg.routed_experts if identities else cfg.router_outputs,
                        routing=_routing_kwargs(cfg), valid=valid)[0]


def check(conf: dict, seed: int, tokens: int) -> dict:
    import importlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import serving, weights
    from benchmark.reference import common as c
    from dynamo_tpu.models import llama
    from dynamo_tpu.parallel.moe import HELD_COUNTS

    ref = importlib.import_module(f"benchmark.reference.{conf['reference']}")
    cfg = serving.model_config(conf)
    cfg = dataclasses.replace(cfg, num_layers=1 + cfg.first_k_dense)  # one MoE layer
    params = weights.make_weights(cfg, seed, quant=conf["serve"]["quant"] if jax.default_backend() == "tpu" else "")
    layers = params["layers"]
    dt = jnp.dtype(cfg.dtype)
    h = jax.random.normal(jax.random.PRNGKey(seed % 2**31), (tokens, cfg.hidden_size), jnp.float32).astype(dt)
    everyone = jnp.ones((tokens,), bool)
    run = jax.jit(lambda layers, h, valid: llama._mlp_moe_held(program_layer(cfg, layers), h[None], cfg, valid[None]))
    run_routed = jax.jit(lambda layers, h, valid: program_routed(cfg, layers, h, valid=valid, identities=False))
    whole, counts = run(layers, h, everyone)
    whole = np.asarray(whole[0], np.float32)
    held_only = lambda layers: np.asarray(run_routed(layers, h, everyone), np.float32)  # noqa: E731

    z = ref.shape_of(conf["hf"])
    lp0 = jax.tree.map(lambda x: x[0], {k: v for k, v in layers.items() if not k.startswith("sub")})
    with jax.default_matmul_precision("highest"):
        hf32 = h.astype(c.F32)
        mix = jax.jit(lambda lp, x: ref.route(x, lp, z))(lp0, hf32)
        want_held = np.asarray(jax.jit(lambda lp, x, m: ref.held_experts_term(x, lp, m, z))(lp0, hf32, mix))
        want = want_held

    def far(got, want):
        return float(np.abs(got - want).max() / np.abs(want).max())

    rolled = {**layers, **{k: jax.tree.map(lambda x: jnp.roll(x, 1, axis=1), layers[k]) for k in ("w_gate", "w_up", "w_down")}}
    row = {"seed": seed, "tokens": tokens, "held": far(held_only(layers), want_held),
           "control_rolled_held": far(held_only(rolled), want_held), "held_absmax": float(np.abs(want_held).max())}
    if cfg.moe_zero_experts:
        # Nobody valid for the experts: what is left of the routed part is the identity term.
        zero_only = np.asarray(jax.jit(lambda layers, h: program_routed(
            cfg, layers, h, valid=jnp.zeros((tokens,), bool), identities=True))(layers, h), np.float32)
        with jax.default_matmul_precision("highest"):
            want_zero = np.asarray(ref.zero_experts_term(hf32, mix, z))
        row.update(zero=far(zero_only, want_zero), zero_absmax=float(np.abs(want_zero).max()))
        want = want + want_zero
    if cfg.shared_expert_size:
        shared = np.asarray(jax.jit(lambda layers, h: llama._shared_expert(program_layer(cfg, layers), h, cfg))(layers, h),
                            np.float32)
        with jax.default_matmul_precision("highest"):
            # (Ling-3.0-flash's reference has the same term inline in its ``routed_ffn``.)
            shared_term = getattr(ref, "shared_expert_term", lambda h, lp: ref.swiglu_of(
                h, lp, "w_shared_gate", "w_shared_up", "w_shared_down"))
            want_shared = np.asarray(jax.jit(shared_term)(hf32, lp0))
        row.update(shared=far(shared, want_shared), shared_absmax=float(np.abs(want_shared).max()))
        want = want + want_shared
    row["whole"] = far(whole, want)
    if isinstance(layers["w_gate"], dict):  # int8 as served: the one-precision-down control
        low = {**layers, **weights.requantize_int4({k: layers[k] for k in ("w_gate", "w_up", "w_down")})}
        row["control_int4_held"] = far(held_only(low), want_held)
    k, outputs = cfg.num_experts_per_token, cfg.router_outputs
    row["counts"] = dict(zip(HELD_COUNTS, (int(v) for v in np.asarray(counts))))
    row["even_routing"] = {"moe_choices": tokens * k, "moe_choices_zero": tokens * k * cfg.moe_zero_experts / outputs,
                           "moe_choices_held": tokens * k * cfg.num_experts / outputs}
    held_mix = np.asarray(mix)[:, z["first"]: z["first"] + z["held"]]
    row["reference_counts"] = {"moe_choices_zero": int((np.asarray(mix)[:, z["routed"]:] > 0).sum()),
                               "moe_choices_held": int((held_mix > 0).sum()),
                               "moe_experts_touched": int((held_mix > 0).any(axis=0).sum())}
    return row


def time_routed(conf: dict, seed: int, tokens: int, n_layers: int, caps: list[int], runs: int = 20, ops_to: str = "") -> dict:
    """Microseconds a layer of ``moe_mlp_held`` scanned over ``n_layers`` MoE
    layers, once for each of ``caps``: the rows of the usual pass
    (``held_rows_cap``) set to that number, 0 for the function's own.
    ``ops_to``: a file stem for the device's time operation by operation
    (``tools/step_ops_table.ops_table`` of five traced runs), one a cap."""
    import shutil
    import statistics
    import time

    import jax
    import jax.numpy as jnp

    from benchmark import serving, weights
    from dynamo_tpu.models.llama import _routing_kwargs
    from dynamo_tpu.parallel import moe

    cfg = serving.model_config(conf)
    cfg = dataclasses.replace(cfg, num_layers=n_layers + cfg.first_k_dense)
    params = weights.make_weights(cfg, seed, quant=conf["serve"]["quant"] if jax.default_backend() == "tpu" else "")
    path = moe.experts_path(params["layers"])
    xs, stack = moe.split_expert_stack(params["layers"])
    xs = {k: v for k, v in xs.items() if k in ("router", "router_bias", "w_gate", "w_up", "w_down")}
    del params
    h = jax.random.normal(jax.random.PRNGKey(seed % 2**31), (tokens, cfg.hidden_size), jnp.float32).astype(cfg.dtype)

    def routed_step(xs, stack, h):
        def layer(carry, lp):
            h, li = carry
            out, counts = moe.moe_mlp_held(
                moe.join_expert_stack(lp, stack, li), h, num_experts_per_token=cfg.num_experts_per_token,
                first=cfg.moe_expert_first, routed=cfg.routed_experts, routing=_routing_kwargs(cfg))
            return (h + out * 1e-3, li + 1), counts
        (h, _), counts = jax.lax.scan(layer, (h, jnp.int32(0)), xs)
        return h, counts.sum(axis=0)

    row = {"tokens": tokens, "layers": n_layers, "path": path, "caps": {}}
    own_cap = moe.held_rows_cap
    for cap in caps:
        moe.held_rows_cap = (lambda *_, cap=cap: cap) if cap else own_cap
        try:
            run = jax.jit(routed_step)
            counts = jax.block_until_ready(run(xs, stack, h))[1]
            took = []
            for _ in range(runs):
                t0 = time.perf_counter()
                jax.block_until_ready(run(xs, stack, h))
                took.append(time.perf_counter() - t0)
            if ops_to:
                sys.path.insert(0, str(ROOT / "tools"))
                from step_ops_table import ops_table

                trace_dir = ROOT / ".bench_work" / "moe_time_trace"
                shutil.rmtree(trace_dir, ignore_errors=True)
                jax.profiler.start_trace(str(trace_dir))
                for _ in range(5):
                    jax.block_until_ready(run(xs, stack, h))
                jax.profiler.stop_trace()
                pathlib.Path(ops_to).parent.mkdir(parents=True, exist_ok=True)
                pathlib.Path(f"{ops_to}-{tokens}-cap{cap}.json").write_text(json.dumps(ops_table(str(trace_dir))))
                shutil.rmtree(trace_dir, ignore_errors=True)
        finally:
            moe.held_rows_cap = own_cap
        row["caps"][str(cap)] = {"us_per_layer": statistics.median(took) / n_layers * 1e6, "min_us": min(took) / n_layers * 1e6,
                                 "counts": [int(v) for v in counts]}
    return row


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", default="longcat-flash-chat-ep32-int8.reason-saturated")
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3400000007)
    ap.add_argument("--tokens", type=int, default=384)
    ap.add_argument("--time", default="", help="time the routed part alone at these token counts (a,b,...) and stop")
    ap.add_argument("--time-layers", type=int, default=12)
    ap.add_argument("--time-caps", default="0", help="with --time: rows of the usual pass to force (a,b,...; 0: the function's own)")
    ap.add_argument("--time-ops", default="", help="with --time: write the traced per-operation tables to this stem")
    args = ap.parse_args()
    from benchmark import serving

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    rehearsal = os.environ.get("JAX_PLATFORMS", "") == "cpu"
    conf = serving.load_config(ROOT / entry["file"], rehearsal=rehearsal)
    import jax

    print(json.dumps({"platform": jax.default_backend(), "kind": jax.devices()[0].device_kind, "rehearsal": rehearsal}))
    if args.time:
        for tokens in (int(t) for t in args.time.split(",")):
            row = time_routed(conf, args.first_seed, tokens, args.time_layers, [int(c) for c in args.time_caps.split(",")],
                              ops_to="" if rehearsal else args.time_ops)
            print(json.dumps({"moe_time": {"workload": args.workload, **row}}), flush=True)
        return 0
    rows = []
    for i in range(args.seeds):
        rows.append(check(conf, args.first_seed + 7919 * i, args.tokens))
        print(json.dumps({"moe_check": rows[-1]}), flush=True)
    keys = [k for k in rows[0] if isinstance(rows[0][k], float)]
    summary = {k: [min(r[k] for r in rows), max(r[k] for r in rows)] for k in keys}
    ok = (all(summary[k][1] < LIMIT for k in ("held", "zero", "shared", "whole") if k in summary)
          and all(v[0] > LIMIT for k, v in summary.items() if k.startswith("control_")))
    print(json.dumps({"moe_check_summary": summary, "limit": LIMIT, "ok": ok}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
