"""The KDA decode kernel (``ops/pallas_kda.py``) in interpret mode against the
plain ``jax.numpy`` step (``models/kda.recurrent_step``): slots read through
their ids, a fresh row read as zeros, the state written back in place and no
other slot touched, at every block of heads the VMEM budget can choose."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import kda
from dynamo_tpu.ops import pallas_kda


def _case(seed, rows, heads, key, value, slots, beta_scale=1.0):
    rng = np.random.default_rng(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    f = lambda x: jnp.asarray(x, jnp.float32)  # noqa: E731
    return dict(
        state=f(rng.normal(size=(slots, heads, key, value))),
        q=f(unit(rng.normal(size=(rows, heads, key)))), k=f(unit(rng.normal(size=(rows, heads, key)))),
        v=f(rng.normal(size=(rows, heads, value))), g=f(-5 * rng.uniform(size=(rows, heads, key)) ** 3),
        beta=f(beta_scale * rng.uniform(size=(rows, heads))))


def _budget(monkeypatch, block, key, value):
    """``STATE_VMEM`` at which ``block`` heads of ``key x value`` just fit, in and out and double-buffered."""
    monkeypatch.setattr(pallas_kda, "STATE_VMEM", 4 * block * 4 * key * value)


@pytest.mark.parametrize("rows, heads, key, value, fits, block", [
    (3, 4, 16, 128, 8, 4),  # every head in one block, the toy's key width
    (5, 16, 128, 128, 8, 8),  # two blocks at the published head size
    (2, 32, 128, 128, 8, 8),  # the published layer at each block the budget may be set to choose: four blocks a row,
    (2, 32, 128, 128, 16, 16),  # two,
    (2, 32, 128, 128, 32, 32),  # and the grid over rows only
    (2, 6, 8, 128, 4, 3),  # heads that the fitting block does not divide: the largest divisor within it
    (2, 12, 16, 128, 8, 6),  # a head count that is no power of two, in two blocks
    (3, 24, 16, 128, 64, 24),  # and in one
    (2, 64, 128, 128, 32, 32),  # Solar-Open2's 64 heads at the budget as served (8 MiB: 32 heads a block, two blocks a row)
    (2, 64, 128, 128, 16, 16),  # and at half of it: four
], ids=["one-block", "two-blocks", "published-8", "published-16", "published-32", "odd-heads", "twelve-heads", "twenty-four-heads",
        "sixty-four-heads", "sixty-four-heads-16"])
def test_kernel_matches_the_plain_step(monkeypatch, rows, heads, key, value, fits, block):
    _budget(monkeypatch, fits, key, value)
    assert pallas_kda.heads_block(heads, 4 * key * value) == block
    slots = rows + 3
    c = _case(rows, rows, heads, key, value, slots, beta_scale=2.0 if heads == 64 else 1.0)  # a write strength in (0, 2)
    ids = jnp.asarray(np.random.default_rng(1).permutation(np.arange(1, slots))[:rows], jnp.int32)
    fresh = jnp.asarray(np.arange(rows) % 2 == 1)
    before = np.asarray(c["state"])
    s_in = jnp.where(fresh[:, None, None, None], 0.0, c["state"][ids])
    o_want, s_want = kda.recurrent_step(s_in, c["q"], c["k"], c["v"], c["g"], c["beta"])
    # (not through the jitted wrapper: its cache would answer a second budget with the first one's block)
    o_got, state = pallas_kda.kda_decode_step.__wrapped__(c["state"], ids, fresh, c["q"], c["k"], c["v"], c["g"], c["beta"], interpret=True)
    np.testing.assert_allclose(o_got, o_want, atol=2e-6)
    np.testing.assert_allclose(np.asarray(state)[np.asarray(ids)], s_want, atol=2e-6)
    others = [i for i in range(slots) if i not in set(np.asarray(ids).tolist())]
    assert np.array_equal(np.asarray(state)[others], before[others])  # bit for bit: never read, never written


@pytest.mark.parametrize("fits", [1, 2, 4], ids=["a-head-a-step", "two-blocks", "one-block"])
def test_a_row_that_neither_decays_nor_writes_leaves_its_slot_as_it_was(monkeypatch, fits):
    """What ``kda_attention`` hands over for a padding token: g = 0, beta = 0."""
    _budget(monkeypatch, fits, 16, 128)
    c = _case(7, 2, 4, 16, 128, 4)
    ids, fresh = jnp.asarray([2, 3], jnp.int32), jnp.zeros(2, bool)
    before = np.asarray(c["state"])
    _, state = pallas_kda.kda_decode_step.__wrapped__(c["state"], ids, fresh, c["q"], c["k"], c["v"], jnp.zeros_like(c["g"]),
                                                      jnp.zeros_like(c["beta"]), interpret=True)
    np.testing.assert_array_equal(np.asarray(state), before)


def test_supported_shapes(monkeypatch):
    monkeypatch.setattr(pallas_kda, "interpret_mode", lambda: False)
    assert pallas_kda.supported(128, 128) and pallas_kda.supported(16, 128)
    assert not pallas_kda.supported(16, 16) and not pallas_kda.supported(12, 128)



def test_the_block_is_the_most_heads_the_budget_holds(monkeypatch):
    """The state's four buffers (a block in and out, each double-buffered)
    within ``STATE_VMEM`` (half of ``vmem_limit_bytes``): a divisor of
    the head count and, where heads share operands a group, a whole number of
    groups or a divisor of one."""
    head, block = 4 * 128 * 128, pallas_kda.heads_block
    assert block(32, head) == 32 and 4 * 32 * head <= pallas_kda.STATE_VMEM  # the published layer: the grid over rows only
    monkeypatch.setattr(pallas_kda, "STATE_VMEM", 4 * 16 * head)  # room for 16 heads
    assert (block(32, head), block(6, head), block(24, head), block(34, head)) == (16, 6, 12, 2)
    assert (block(32, head, 8), block(32, head, 16), block(64, head, 32)) == (16, 16, 16)  # two groups, one, half of one
    assert (block(24, head, 12), block(24, head, 6), block(20, head, 5), block(48, head, 3)) == (12, 12, 10, 12)
    assert block(12, 4 * head, 3) == 3  # 4 fit, which neither divides a group of 3 nor holds whole ones
    assert block(7, 32 * head) == 1  # a head that does not fit still goes alone


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
