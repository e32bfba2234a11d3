"""The KDA decode kernel (``ops/pallas_kda.py``) in interpret mode against the
plain ``jax.numpy`` step (``models/kda.recurrent_step``): slots read through
their ids, a fresh row read as zeros, the state written back in place and no
other slot touched, at one block of heads and at several."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import kda
from dynamo_tpu.ops import pallas_kda


def _case(seed, rows, heads, key, value, slots):
    rng = np.random.default_rng(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    f = lambda x: jnp.asarray(x, jnp.float32)  # noqa: E731
    return dict(
        state=f(rng.normal(size=(slots, heads, key, value))),
        q=f(unit(rng.normal(size=(rows, heads, key)))), k=f(unit(rng.normal(size=(rows, heads, key)))),
        v=f(rng.normal(size=(rows, heads, value))), g=f(-5 * rng.uniform(size=(rows, heads, key)) ** 3),
        beta=f(rng.uniform(size=(rows, heads))))


@pytest.mark.parametrize("rows, heads, key, value, per_block", [
    (3, 4, 16, 128, 8),  # one block of heads, the toy's key width
    (5, 16, 128, 128, 8),  # two blocks at the published head size
    (2, 6, 8, 128, 4),  # heads that the block does not divide: the largest divisor within it (3)
], ids=["one-block", "two-blocks", "odd-heads"])
def test_kernel_matches_the_plain_step(monkeypatch, rows, heads, key, value, per_block):
    monkeypatch.setattr(pallas_kda, "HEADS_PER_BLOCK", per_block)
    slots = rows + 3
    c = _case(rows, rows, heads, key, value, slots)
    ids = jnp.asarray(np.random.default_rng(1).permutation(np.arange(1, slots))[:rows], jnp.int32)
    fresh = jnp.asarray(np.arange(rows) % 2 == 1)
    before = np.asarray(c["state"])
    s_in = jnp.where(fresh[:, None, None, None], 0.0, c["state"][ids])
    o_want, s_want = kda.recurrent_step(s_in, c["q"], c["k"], c["v"], c["g"], c["beta"])
    o_got, state = pallas_kda.kda_decode_step(c["state"], ids, fresh, c["q"], c["k"], c["v"], c["g"], c["beta"], interpret=True)
    np.testing.assert_allclose(o_got, o_want, atol=2e-6)
    np.testing.assert_allclose(np.asarray(state)[np.asarray(ids)], s_want, atol=2e-6)
    others = [i for i in range(slots) if i not in set(np.asarray(ids).tolist())]
    assert np.array_equal(np.asarray(state)[others], before[others])  # bit for bit: never read, never written


def test_a_row_that_neither_decays_nor_writes_leaves_its_slot_as_it_was():
    """What ``kda_attention`` hands over for a padding token: g = 0, beta = 0."""
    c = _case(7, 2, 4, 16, 128, 4)
    ids, fresh = jnp.asarray([2, 3], jnp.int32), jnp.zeros(2, bool)
    before = np.asarray(c["state"])
    _, state = pallas_kda.kda_decode_step(c["state"], ids, fresh, c["q"], c["k"], c["v"], jnp.zeros_like(c["g"]),
                                          jnp.zeros_like(c["beta"]), interpret=True)
    np.testing.assert_array_equal(np.asarray(state), before)


def test_supported_shapes(monkeypatch):
    monkeypatch.setattr(pallas_kda, "interpret_mode", lambda: False)
    assert pallas_kda.supported(128, 128) and pallas_kda.supported(16, 128)
    assert not pallas_kda.supported(16, 16) and not pallas_kda.supported(12, 128)
    assert pallas_kda._heads_block(32) == 8 and pallas_kda._heads_block(6) == 6 and pallas_kda._heads_block(12) == 6


def test_the_layer_takes_the_kernel_where_the_platform_runs_it(monkeypatch):
    """``kda_attention`` with ``impl="pallas"`` under the interpreter against
    the ``jax.numpy`` step, through slots, at a value width the kernel tiles."""
    import dataclasses

    from dynamo_tpu.models.config import PRESETS

    monkeypatch.setenv("DYNAMO_PALLAS_INTERPRET", "1")
    cfg = dataclasses.replace(PRESETS["test-tiny-hybrid"], hidden_size=64, num_heads=2, num_kv_heads=2, head_dim=128)
    lp = jax.tree.map(lambda x: x[0], kda.init_kda_params(cfg, jax.random.PRNGKey(0), jnp.float32, 1))
    h = jax.random.normal(jax.random.PRNGKey(1), (3, 1, 64), jnp.float32)
    state, conv = kda.init_state(cfg, 5, dtype=jnp.float32)
    state = state + jax.random.normal(jax.random.PRNGKey(2), state.shape)
    args = dict(positions=jnp.full((3, 1), 9), valid=jnp.asarray([[True], [True], [False]]), slot_ids=jnp.asarray([4, 2, 0]))
    want = kda.kda_attention(lp, cfg, h, state=state, conv=conv, impl="reference", **args)
    got = kda.kda_attention(lp, cfg, h, state=state, conv=conv, impl="pallas", **args)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-5)
