"""Each plain reference against the program's own forward at toy sizes, the
served weight format against the program's, and the control of the outputs
check (the same weights one precision down must come out as not correct)."""

import asyncio
import functools
import json
import pathlib
import sys
import types

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "benchmark"))

CONFIGS = sorted(p.stem for p in (ROOT / "benchmark" / "configs").glob("*.json"))


def test_weight_format_is_the_programs():
    from benchmark import weights
    from dynamo_tpu.models import quant

    assert weights.MATMUL_LEAVES == quant._MATMUL_LEAVES


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("quant", ["int8", ""])
def test_reference_matches_program_forward(name, quant):
    import importlib

    import jax
    import jax.numpy as jnp

    from benchmark import serving, weights
    from dynamo_tpu.models import llama

    conf = serving.load_config(ROOT / "benchmark" / "configs" / f"{name}.json", rehearsal=True)
    mc = serving.model_config(conf)
    params = weights.make_weights(mc, 2**31 + 11, quant=quant)
    t, ps = 48, 16
    tokens = np.random.default_rng(0).integers(1, mc.vocab_size, size=t).astype(np.int32)
    ref = importlib.import_module(f"benchmark.reference.{conf['reference']}")
    want = np.asarray(jax.jit(functools.partial(ref.forward, hf=conf["hf"]))(params, tokens=jnp.asarray(tokens)))
    kc, vc = llama.init_kv_cache(mc, 1 + t // ps, ps)
    pos = np.arange(t, dtype=np.int32)
    tables = 1 + np.arange(t // ps, dtype=np.int32)[None]
    got = []
    for last in (t - 1, t // 2):  # the program returns one row of logits a call
        logits, _, _ = llama.forward(
            params, mc, jnp.asarray(tokens[None]), jnp.asarray(pos[None]), kc, vc, jnp.asarray(tables),
            jnp.asarray((tables[0][pos // ps] * ps + pos % ps)[None]), jnp.asarray([last], jnp.int32),
            attn_impl="reference")
        got.append(np.asarray(logits[0]))
    assert np.abs(got[0] - want[t - 1]).max() < 2e-4 * np.abs(want).max()
    assert np.abs(got[1] - want[t // 2]).max() < 2e-4 * np.abs(want).max()


@pytest.mark.parametrize("variant", ["fp8kv", "int4w"])
def test_control_comes_out_not_correct(variant):
    """Through the live engine at toy size: the sound run sits far inside the
    limit, the run one precision down far outside it."""
    import control
    import run as bench_run

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = bench["workloads"][0]
    args = types.SimpleNamespace(seed=0)

    async def both():
        sound = await control.one(args, bench, cell, True, 2**31 + 3, "sound")
        return sound, await control.one(args, bench, cell, True, 2**31 + 3, variant)

    sound, low = asyncio.run(both())
    assert sound["ok"] and sound["rel_err"] < 1e-4
    assert not low["ok"] and low["rel_err"] > 3 * sound["rel_err"] and low["rel_err"] > low["limit"]
