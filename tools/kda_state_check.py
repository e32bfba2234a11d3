#!/usr/bin/env python3
"""One recurrent layer alone, with a slow decay: the served layer's chunk steps
and decode steps through slots against the plain reference's layer, at the
configuration's published widths. A KDA layer (Ling's cell, the default) or,
since PR 46, the Mamba-2 mixer of a layer that runs one beside its attention
(``--workload falcon-h1-34b-pp8-int8.reason-saturated``: ``models/mamba2.mamba_mixer``
against the reference's ``mixer``, half the heads' step size cut so that they
decay by 0.97-0.993 a token) or, since PR 49, of a layer that is nothing else
(``--workload granite-4.0-h-small-pp4-int8.reason-saturated``: 128 heads of 64
channels, two side by side in a row of the state buffer) or, since PR 53, a KDA
layer in Kimi Linear's own form at 64 heads (``--workload
solar-open2-250b-ep8-int8.reason-saturated``: a softplus decay through a
low-rank pair, a write strength up to 2, here drawn towards both its ends).

    python3 tools/kda_state_check.py [--workload <cell>] --seeds 3 --rows 3 --prompt 192 --decode 8

``benchmark/correct.py`` is weak on the recurrent state's carry by
construction: the benchmark's weights draw the decay projection so that the
log-decay is about -2.5 a token, a state is forgotten within a few tokens, and
a state dropped at a chunk edge or read from another row's slot would pass.
Here one KDA layer's leaves are made from the seed (``benchmark/weights.py``)
and the decay is then set by hand: in half the heads ``w_decay`` is cut to a
tenth and ``dt_bias`` drawn in [-8.5, -4.6], so that ``alpha = exp(g)`` lies
in about [0.95, 0.999] and a token is still felt hundreds of tokens later; the
other heads keep what the seed gave. ``--rows`` sequences of seeded unit-RMS
hidden vectors go through ``models/kda.kda_attention`` as ``llama.forward``
calls it (bf16, int8 projections): the prompt in 64-token chunk steps (the
chunkwise form), then ``--decode`` decode steps (on the chip the Pallas kernel
``kda_decode_step``), the rows in slots that are not their row numbers and in
another row order every decode step. The same vectors go through the
reference's ``kda`` in float32 at ``highest`` matmul precision, token by token
over the whole sequence. Compared: max |served - reference| over max
|reference| of the layer's output, over the chunk steps and over the decode
steps.

Three controls must come out far from the reference: ``no_carry`` (the state
buffers zeroed between chunk steps: a carry lost at a chunk edge), ``no_zero``
(the run started in slots that hold another run's state, the first chunk at
position 64 instead of 0: a slot taken over without being zeroed) and
``by_row`` (the decode steps' slot ids taken as the row order they were
dispatched in first: a slot mixed up between rows).

``LIMIT`` (0.02): the served layer rounds its projections, its conv and its
gates to bf16 (2**-8 = 0.4% a value) and carries the state in float32; sound
readings on the chip are a few 1e-3 and the controls 0.1 and more (PERF.md
section 6, PR 40). The exit code is 1 where a sound reading is over it or a
control under it. Run by hand on the chip; ``JAX_PLATFORMS=cpu`` rehearses at
the configuration's toy size.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import importlib
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
#: max |served - reference| / max |reference| a sound run may read, and a control must exceed.
LIMIT = 0.02
sys.path.insert(0, str(ROOT))


def slow_decay(lp: dict, cfg, seed: int) -> dict:
    """Half the heads forget slowly: their decay input cut to a tenth (the full-rank projection's columns, or the
    second matrix's of a low-rank pair), their bias in [-8.5, -4.6]. Where the write strength reaches past 1
    (``kda_beta_scale``), its input is made three times as large, so that it comes near both of its ends."""
    import jax
    import jax.numpy as jnp

    heads, hd, slow = cfg.num_heads, cfg.head_dim, cfg.num_heads // 2
    scale = jnp.repeat(jnp.where(jnp.arange(heads) < slow, 0.1, 1.0), hd)
    bias = jax.random.uniform(jax.random.PRNGKey(seed % 2**31), (heads, hd), jnp.float32, -8.5, -4.6)
    bias = jnp.where(jnp.arange(heads)[:, None] < slow, bias, 0.0).reshape(-1)
    times = lambda name, by: (lp[name].astype(jnp.float32) * by).astype(lp[name].dtype)  # noqa: E731
    decay = "w_decay_b" if cfg.kda_low_rank else "w_decay"
    out = {**lp, decay: times(decay, scale), "dt_bias": bias.astype(lp["dt_bias"].dtype), "a_log": jnp.zeros_like(lp["a_log"])}
    return {**out, "w_beta": times("w_beta", 3.0)} if cfg.kda_beta_scale != 1.0 else out


def slow_steps(lp: dict, cfg, seed: int) -> dict:
    """A mixer's leaves with half the heads forgetting slowly: their ``dt_bias`` in [-6, -3.5] (a step of
    0.003-0.03 against A = -1: a decay of 0.97-0.997 a token), and a skip weight ``D`` of 1 in every head."""
    import jax
    import jax.numpy as jnp

    heads = cfg.ssm_heads
    bias = jax.random.uniform(jax.random.PRNGKey(seed % 2**31), (heads,), jnp.float32, -6.0, -3.5)
    bias = jnp.where(jnp.arange(heads) < heads // 2, bias, 0.0)
    return {**lp, "ssm_dt_bias": bias.astype(lp["ssm_dt_bias"].dtype), "ssm_a_log": jnp.zeros_like(lp["ssm_a_log"]),
            "ssm_d": jnp.ones_like(lp["ssm_d"])}


def check(conf: dict, seed: int, rows: int, prompt: int, decode: int, chunk: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import serving, weights
    from dynamo_tpu.models import kda, mamba2

    ref = importlib.import_module(f"benchmark.reference.{conf['reference']}")
    cfg = serving.model_config(conf)
    if cfg.ssm_heads:  # a mixer, beside every layer's attention or a layer of its own (``ssm_layers``): one layer's leaves
        one = dataclasses.replace(cfg, num_layers=1, vocab_size=256)
        tree = weights.make_weights(one, seed, quant=conf["serve"]["quant"])
        lp = slow_steps(jax.tree.map(lambda x: x[0], tree.get("ssm_layers", tree["layers"])), cfg, seed)
        served_layer, ref_layer = mamba2.mamba_mixer, ref.mixer
    else:
        one = dataclasses.replace(cfg, num_layers=cfg.layer_group_size, vocab_size=256)  # one period: its KDA layers' leaves
        lp = jax.tree.map(lambda x: x[0], weights.make_weights(one, seed, quant=conf["serve"]["quant"])["kda_layers"])
        lp = slow_decay(lp, cfg, seed)
        served_layer, ref_layer = kda.kda_attention, ref.kda
    dt = jnp.dtype(cfg.dtype)
    total = prompt + decode
    h = jax.random.normal(jax.random.PRNGKey((seed + 1) % 2**31), (rows, total, cfg.hidden_size), jnp.float32).astype(dt)
    z = ref.shape_of(conf["hf"])
    with jax.default_matmul_precision("highest"):
        want = np.stack([np.asarray(jax.jit(functools.partial(ref_layer, z=z))(h[r].astype(jnp.float32), lp)) for r in range(rows)])
    slots = rows + 2
    slot_of = np.arange(rows, 0, -1, dtype=np.int32) + 1  # row r in slot rows + 1 - r: never its row number
    layer = jax.jit(lambda lp, h, positions, valid, state, conv, slot_ids: served_layer(
        lp, cfg, h, positions, valid, state, conv, slot_ids), donate_argnames=("state", "conv"))

    def served(*, carry=True, start=0, by_row=False):
        state, conv = kda.init_state(one, slots)
        if start:  # the slots hold another run's state, and nothing says the sequence starts here
            state = state + 0.5
        outs = np.zeros((rows, total, cfg.hidden_size), np.float32)
        for lo in range(0, prompt, chunk):
            if not carry and lo:
                state, conv = jnp.zeros_like(state), jnp.zeros_like(conv)
            pos = jnp.broadcast_to(jnp.arange(lo, lo + chunk) + start, (rows, chunk))
            out, state, conv = layer(lp, h=h[:, lo: lo + chunk], positions=pos, valid=jnp.ones((rows, chunk), bool),
                                     state=state, conv=conv, slot_ids=jnp.asarray(slot_of))
            outs[:, lo: lo + chunk] = np.asarray(out, np.float32)
        for j in range(decode):
            order = np.roll(np.arange(rows), j + 1)  # another row order every step
            ids = slot_of[np.roll(np.arange(rows), 1)] if by_row else slot_of[order]
            t = prompt + j
            out, state, conv = layer(lp, h=h[order, t: t + 1], positions=jnp.full((rows, 1), t + start), valid=jnp.ones((rows, 1), bool),
                                     state=state, conv=conv, slot_ids=jnp.asarray(ids))
            outs[order, t] = np.asarray(out, np.float32)[:, 0]
        return outs

    top = float(np.abs(want).max())
    dist = lambda got, sl: float(np.abs(got[:, sl] - want[:, sl]).max()) / top  # noqa: E731
    got = served()
    row = {"seed": seed, "rows": rows, "prompt": prompt, "decode": decode, "reference_absmax": top,
           "chunks": dist(got, slice(0, prompt)), "decodes": dist(got, slice(prompt, total))}
    row["control_no_carry"] = dist(served(carry=False), slice(chunk, prompt))
    row["control_no_zero"] = dist(served(start=chunk), slice(0, prompt))
    if rows > 1:
        row["control_by_row"] = dist(served(by_row=True), slice(prompt + 1, total))
    return row


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", default="ling-3.0-flash-ep8-int8.reason-saturated")
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3400000040)
    ap.add_argument("--rows", type=int, default=3)
    ap.add_argument("--prompt", type=int, default=192)
    ap.add_argument("--decode", type=int, default=8)
    args = ap.parse_args()
    from benchmark import serving

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    rehearsal = os.environ.get("JAX_PLATFORMS", "") == "cpu"
    conf = serving.load_config(ROOT / entry["file"], rehearsal=rehearsal)
    chunk = conf["serve"]["engine"]["chunk_prefill_tokens"]
    import jax

    print(json.dumps({"platform": jax.default_backend(), "kind": jax.devices()[0].device_kind, "rehearsal": rehearsal}))
    rows = []
    for i in range(args.seeds):
        rows.append(check(conf, args.first_seed + 7919 * i, args.rows, args.prompt // chunk * chunk, args.decode, chunk))
        print(json.dumps({"kda_state_check": rows[-1], "workload": args.workload}), flush=True)
    keys = [k for k in rows[0] if isinstance(rows[0][k], float) and k != "reference_absmax"]
    summary = {k: [min(r[k] for r in rows), max(r[k] for r in rows)] for k in keys}
    ok = (all(summary[k][1] < LIMIT for k in ("chunks", "decodes"))
          and all(v[0] > LIMIT for k, v in summary.items() if k.startswith("control_")))
    print(json.dumps({"kda_state_check_summary": summary, "limit": LIMIT, "ok": ok}))
    return (3 if ok else 1) if rehearsal else (0 if ok else 1)


if __name__ == "__main__":
    sys.exit(main())
