"""A projection's flat output held as the compiler must lay it out (ISSUE 35,
``models/quant.held_flat``) moves layouts and nothing else: with the barrier
taken out again (the parent's forward, line for line) every output of a step
is the same, bit for bit, on the tiny GQA, MLA and shortcut-MoE models, for the
decode step, the rows x t rectangle and the split token axis, int8 and plain."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import llama, mla
from dynamo_tpu.models.config import PRESETS
from dynamo_tpu.models.quant import held_flat, quantize_params

PAGE, PAGES_PER_ROW = 4, 10
#: Rows as (first position, new tokens).
PROGRAMS = {
    "decode": dict(rows=[(5, 1), (21, 1), (12, 1), (30, 1)], split=None),
    "rectangle": dict(rows=[(5, 8), (21, 8), (0, 8)], split=None),
    "split": dict(rows=[(9, 1), (30, 1), (17, 1), (8, 8)], split=(3, 1, 8)),
}


def _inputs(cfg, rows, split):
    rng = np.random.default_rng(35)
    b = len(rows)
    tables = 1 + np.arange(b * PAGES_PER_ROW, dtype=np.int32).reshape(b, PAGES_PER_ROW)
    per_row = []
    for i, (start, n) in enumerate(rows):
        pos = np.arange(start, start + n, dtype=np.int32)
        per_row.append((rng.integers(1, cfg.vocab_size, n).astype(np.int32), pos, tables[i][pos // PAGE] * PAGE + pos % PAGE))
    if split is None:
        tokens, positions, slots = (np.stack([r[k] for r in per_row]) for k in range(3))
        last = np.asarray([n - 1 for _, n in rows], np.int32)
    else:  # one token axis: a position per decode slot, then the chunk slot's
        tokens, positions, slots = (np.concatenate([r[k] for r in per_row]) for k in range(3))
        last = np.cumsum([n for _, n in rows]).astype(np.int32) - 1
    k_cache, v_cache = llama.init_kv_cache(cfg, 1 + b * PAGES_PER_ROW, PAGE)
    noise = lambda key, like: jax.random.normal(jax.random.PRNGKey(key), like.shape, jnp.float32).astype(like.dtype)  # noqa: E731
    return dict(tokens=jnp.asarray(tokens), positions=jnp.asarray(positions), k_cache=noise(1, k_cache),
                v_cache=noise(2, v_cache), block_tables=jnp.asarray(tables), slot_mapping=jnp.asarray(slots.astype(np.int32)),
                last_token_index=jnp.asarray(last))


def test_held_flat_is_the_identity():
    y = jax.random.normal(jax.random.PRNGKey(0), (3, 1, 64), jnp.float32).astype(jnp.bfloat16)
    assert np.array_equal(np.asarray(jax.jit(held_flat)(y), np.float32), np.asarray(y, np.float32))


@pytest.mark.parametrize("program", list(PROGRAMS))
@pytest.mark.parametrize("quant", ["int8", ""], ids=["int8", "plain"])
@pytest.mark.parametrize("preset", ["test-tiny", "test-tiny-mla", "test-tiny-scmoe"])
def test_forward_outputs_are_the_parents_bit_for_bit(preset, quant, program, monkeypatch):
    cfg = PRESETS[preset]
    params = llama.init_params(cfg, 35)
    if quant:
        params = quantize_params(params, mode=quant)
    spec = PROGRAMS[program]
    counted = {"moe_counts": True} if cfg.moe_held_share else {}
    kwargs = _inputs(cfg, spec["rows"], spec["split"])

    def run(barriers: bool):
        fn = jax.jit(functools.partial(llama.forward, cfg=cfg, attn_impl="reference", split=spec["split"], **counted))
        assert ("optimization_barrier" in fn.lower(params, **kwargs).as_text()) == barriers
        return fn(params, **kwargs)

    held = run(True)
    monkeypatch.setattr(llama, "held_flat", lambda y: y)  # the parent's layer body and MLA projection
    monkeypatch.setattr(mla, "held_flat", lambda y: y)
    plain = run(False)
    assert len(held) == len(plain) == (4 if counted else 3)
    for got, want in zip(held, plain):
        assert got.shape == want.shape and got.dtype == want.dtype
        assert np.array_equal(np.asarray(got.astype(jnp.float32)), np.asarray(want.astype(jnp.float32)))
