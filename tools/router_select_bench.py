#!/usr/bin/env python3
"""The router's selection alone, on the chip: scores in, expert ids and
weights out, by ``lax.top_k`` and by passes of ``max``.

    python3 tools/router_select_bench.py [--shapes ling,longcat,joyai,exaone,olmoe] [--layers 1024] [--iters 10]

At the shapes the benchmark's cells route at (tokens x router outputs of a
full decode step: Ling 64 x 512 in 8 groups of which 4 stay, ranked by their
top two; LongCat 64 x 768, 12 taken; JoyAI 64 x 256; K-EXAONE 8 x 128; OLMoE
and Mellum2 48 x 64; ``--shapes`` also takes ``NxE[:k[:groups/kept]]``), each
form of ``parallel/moe.select_experts`` (``sort``: ``lax.top_k`` and a gather
for the weights; ``passes``: ``k`` passes of ``max``, the weight a one-hot
sum), ``--layers`` calls scanned in one program over as many draws of the
scores, as a layer scan runs them: us a call by the host's clock over
``--iters`` runs (a thousand calls a run: the host's 0.4 ms to dispatch a
program is then under half a microsecond a call). Where a group limit is on, ``three_sorts`` is the selection
as it stood before PR 43 (a ``top_k`` for each group's top two, one for the
groups with a scatter for their mask, one for the experts): the cost the
passes replace. Every form's ids and weights are compared with the first
form's over all draws, half of them rounded so that experts, groups and a
group's top two tie: ``equal`` says bit for bit. ``served`` is what
``parallel/moe.router_select`` says for the shape: its thresholds cite this
table (PERF.md, PR 43). Written to ``chiprun_out/router_select_bench.json``;
``--rehearse`` (or no TPU) runs tiny shapes and prints no time, exit 3.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

# name: tokens, router outputs, experts taken, groups, groups kept, how a group is ranked
SHAPES = {
    "ling": (64, 512, 8, 8, 4, "top2sum"),
    "longcat": (64, 768, 12, 0, 0, "max"),
    "joyai": (64, 256, 8, 0, 0, "max"),
    "exaone": (8, 128, 8, 0, 0, "max"),
    "olmoe": (48, 64, 8, 0, 0, "max"),
}


def three_sorts(scores, bias, *, k, n_group=0, topk_group=0, group_score="max"):
    """The selection of ``route_tokens`` as it stood before PR 43."""
    import jax
    import jax.numpy as jnp

    choice = scores + bias
    n, e = choice.shape
    grouped = choice.reshape(n, n_group, e // n_group)
    if group_score == "top2sum":
        gscore = jax.lax.top_k(grouped, min(2, e // n_group))[0].sum(-1)
    else:
        gscore = grouped.max(-1)
    _, gidx = jax.lax.top_k(gscore, topk_group)
    gmask = jnp.zeros_like(gscore, dtype=bool).at[jnp.arange(n)[:, None], gidx].set(True)
    choice = jnp.where(jnp.repeat(gmask, e // n_group, axis=1), choice, -jnp.inf)
    _, topi = jax.lax.top_k(choice, k)
    return jnp.take_along_axis(scores, topi, axis=1), topi


def parse_shape(spec: str) -> tuple:
    """``NxE[:k[:groups/kept]]`` (groups ranked by their top two) or a name of ``SHAPES``."""
    if spec in SHAPES:
        return SHAPES[spec]
    size, *rest = spec.split(":")
    n, e = (int(v) for v in size.split("x"))
    groups, kept = (int(v) for v in rest[1].split("/")) if len(rest) > 1 else (0, 0)
    return n, e, int(rest[0]) if rest else 8, groups, kept, "top2sum"


def bench(forms: dict, scores, bias, *, iters: int, timed: bool) -> list[dict]:
    """Each form of ``forms`` (name -> ``(scores [N, E], bias [E]) -> (weights,
    ids)``) scanned over the leading axis of ``scores``: whether its outputs
    equal the first form's, and us a call where ``timed``."""
    import jax
    import numpy as np

    rows, first = [], None
    for name, select in forms.items():
        def every(scores, bias, select=select):
            return jax.lax.scan(lambda _, s: (None, select(s, bias)), None, scores)[1]

        def stack(scores, bias, select=select):
            # What a layer scan does with the outputs: both are consumed, nothing is kept.
            def layer(carry, s):
                w, i = select(s, bias)
                return (carry[0] + w, carry[1] + i), None

            shape = jax.eval_shape(select, scores[0], bias)[0].shape
            zero = (jax.numpy.zeros(shape, jax.numpy.float32), jax.numpy.zeros(shape, jax.numpy.int32))
            return jax.lax.scan(layer, zero, scores)[0]

        out = [np.asarray(o) for o in jax.block_until_ready(jax.jit(every)(scores, bias))]
        first = out if first is None else first
        row = {"form": name, "equal": bool(all(np.array_equal(a, b) for a, b in zip(out, first)))}
        if timed:
            call = jax.jit(stack)
            jax.block_until_ready(call(scores, bias))
            t0 = time.perf_counter()
            for _ in range(iters):
                got = call(scores, bias)
            jax.block_until_ready(got)
            row["us"] = round((time.perf_counter() - t0) / iters / scores.shape[0] * 1e6, 2)
        rows.append(row)
    return rows


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--layers", type=int, default=1024)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.parallel.moe import router_select, select_experts

    on_chip = jax.default_backend() == "tpu" and not args.rehearse
    shapes = [(s, parse_shape(s)) for s in args.shapes.split(",")] if on_chip else [
        ("tiny-groups", (4, 32, 4, 4, 2, "top2sum")), ("tiny", (3, 16, 4, 0, 0, "max"))]
    layers, iters = (args.layers, args.iters) if on_chip else (2, 1)
    rng = np.random.default_rng(args.seed)
    table = []
    for name, (n, e, k, groups, kept, group_score) in shapes:
        scores = rng.random((layers, n, e))
        scores[1::2] = np.round(scores[1::2] * 8) / 8  # every other draw: ties in experts, groups, a group's top two
        bias = np.round(rng.standard_normal(e) * 0.4) / 4
        kw = dict(k=k, n_group=groups, topk_group=kept, group_score=group_score)
        forms = {"three_sorts": functools.partial(three_sorts, **kw)} if groups else {}
        forms.update({form: functools.partial(select_experts, form=form, **kw) for form in ("sort", "passes")})
        served = router_select(n, e, k)
        for row in bench(forms, jnp.asarray(scores, jnp.float32), jnp.asarray(bias, jnp.float32), iters=iters, timed=on_chip):
            row = {"shape": name, "tokens": n, "outputs": e, "k": k, "groups": groups, "kept": kept, **row,
                   "served": row["form"] == served}
            print(json.dumps(row), flush=True)
            table.append(row)
    ok = all(row["equal"] for row in table)
    verdict = {"router_select_bench": "v5e" if on_chip else "rehearsal: no time is a device time",
               "rows": len(table), "every_form_equal": ok}
    print(json.dumps(verdict))
    if on_chip:
        out = ROOT / "chiprun_out" / "router_select_bench.json"
        out.parent.mkdir(exist_ok=True)
        out.write_text(json.dumps({**verdict, "table": table}, indent=1))
    if not ok:
        return 1
    return 0 if on_chip else 3


if __name__ == "__main__":
    sys.exit(main())
