"""The Mamba-2 decode kernel (``ops/pallas_mamba.py``) in interpret mode
against the plain ``jax.numpy`` step (``models/mamba2.recurrent_step``): slots
read through their ids, a fresh row read as zeros, the null slot, the state
written back in place and no other slot touched, at every block of heads the
VMEM budget can choose (inside a group, a whole group, several groups), for
heads of whole lane tiles and for narrower heads that lie side by side on the
lanes (two of 64 channels: one group of 128 heads, and two groups); and the chunked form (``models/mamba2.chunk_step``) over
any split of a sequence into chunks, the last one padded, against the
recurrence token by token."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import kda, mamba2
from dynamo_tpu.models.config import PRESETS
from dynamo_tpu.ops import pallas_kda, pallas_mamba


def _case(seed, rows, heads, groups, n, p, slots):
    rng = np.random.default_rng(seed)
    f = lambda x: jnp.asarray(x, jnp.float32)  # noqa: E731
    return dict(
        state=f(rng.normal(size=(slots, heads, n, p))), x=f(rng.normal(size=(rows, heads, p))),
        b=f(rng.normal(size=(rows, groups, n))), c=f(rng.normal(size=(rows, groups, n))),
        dt=f(rng.uniform(0.0, 0.7, size=(rows, heads)) ** 2), a=f(-rng.uniform(0.3, 3.0, size=heads)))


def _plain(case, ids, fresh):
    rows, heads, p = case["x"].shape
    groups, n = case["b"].shape[1:]
    hg = heads // groups
    s_in = jnp.where(fresh[:, None, None, None], 0.0, case["state"][ids]).reshape(rows, groups, hg, n, p)
    y, s = mamba2.recurrent_step(s_in, case["x"].reshape(rows, groups, hg, p), case["b"], case["c"],
                                 case["dt"].reshape(rows, groups, hg), case["a"].reshape(groups, hg))
    return y.reshape(rows, heads, p), s.reshape(rows, heads, n, p)


def _budget(monkeypatch, block, n, p):
    """The budget (``ops/pallas_kda.STATE_VMEM``) at which ``block`` heads of ``n x p`` just fit, in and out and double-buffered."""
    monkeypatch.setattr(pallas_kda, "STATE_VMEM", 4 * block * 4 * n * p)


@pytest.mark.parametrize("rows, heads, groups, n, p, fits, block, side", [
    (3, 4, 2, 8, 128, 2, 2, 1),  # a block a group (2 heads), the toy's state
    (2, 32, 2, 256, 128, 8, 8, 1),  # the published mixer at each block the budget may be set to choose: two blocks a group of 16,
    (2, 32, 2, 256, 128, 16, 16, 1),  # a block a group,
    (2, 32, 2, 256, 128, 32, 32, 1),  # and one block over both groups: the grid over rows only
    (2, 12, 2, 16, 128, 4, 3, 1),  # 6 heads a group, which 4 does not divide: the largest divisor within it
    (2, 12, 2, 16, 128, 16, 12, 1),  # a head count that is no power of two, both groups in one block
    (2, 24, 3, 16, 128, 16, 8, 1),  # three groups of 8: 16 would fit but does not divide 24, 12 would cut a group
    (2, 24, 4, 8, 128, 12, 12, 1),  # four groups of 6, two blocks of two groups each: the group by the grid position
    (3, 8, 1, 8, 16, 8, 8, 1),  # one group; channels narrower than a lane tile (the interpreter tiles nothing)
    # Heads narrower than the lanes, ``side`` of a group side by side in a buffer row (blocks count buffer rows):
    (2, 128, 1, 128, 64, 32, 32, 2),  # granite-4.0-h's mixer: 128 heads of 64 in one group, 64 rows of two, two blocks of 32 rows
    (3, 8, 2, 16, 64, 2, 2, 2),  # two groups of 4 heads of 64: two rows a group, a block a group
    (3, 8, 2, 16, 64, 1, 1, 2),  # a row a step: the group by the grid position
    (2, 8, 2, 16, 64, 4, 4, 2),  # both groups in one block: each row's group statically
    (3, 16, 2, 8, 16, 2, 2, 8),  # 8 heads of 16 side by side: a row a group
], ids=["toy", "published-8", "published-16", "published-32", "odd-heads", "twelve-heads", "three-groups", "two-groups-a-block",
        "one-group", "side2-published", "side2-two-groups", "side2-a-row-a-step", "side2-one-block", "side8"])
def test_kernel_matches_the_plain_step(monkeypatch, rows, heads, groups, n, p, fits, block, side):
    _budget(monkeypatch, fits, n, side * p)
    assert pallas_kda.heads_block(heads // side, 4 * n * p * side, max(heads // side // groups, 1)) == block
    slots = rows + 3
    c = _case(rows, rows, heads, groups, n, p, slots)
    ids = jnp.asarray(np.random.default_rng(1).permutation(np.arange(1, slots))[:rows], jnp.int32)
    fresh = jnp.asarray(np.arange(rows) % 2 == 1)
    before = np.asarray(c["state"])
    y_want, s_want = _plain(c, ids, fresh)
    laid = mamba2.lay_side_by_side(c["state"], side)
    assert laid.shape == (slots, heads // side, n, side * p)
    np.testing.assert_array_equal(mamba2.lay_by_head(laid, side), c["state"])  # the two maps are each other's inverse
    # (not through the jitted wrapper: its cache would answer a second budget with the first one's block)
    y_got, state = pallas_mamba.mamba_decode_step.__wrapped__(laid, ids, fresh, c["x"], c["b"], c["c"], c["dt"], c["a"],
                                                              interpret=True)
    state = np.asarray(mamba2.lay_by_head(state, side))
    np.testing.assert_allclose(y_got, y_want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(state[np.asarray(ids)], s_want, rtol=1e-6, atol=2e-6)
    others = [i for i in range(slots) if i not in set(np.asarray(ids).tolist())]
    assert np.array_equal(state[others], before[others])  # bit for bit: never read, never written


@pytest.mark.parametrize("fits, p", [(1, 128), (2, 128), (4, 128), (1, 64), (2, 64)],
                         ids=["a-head-a-step", "a-block-a-group", "one-block", "side2-a-row-a-step", "side2-one-block"])
def test_padding_rows_share_the_null_slot_and_leave_the_live_slots_alone(monkeypatch, fits, p):
    """What ``mamba_mixer`` hands over for padding rows: slot 0, ``dt = 0`` (no
    decay, no write), and ``fresh`` (their position is 0): the null slot reads
    as zeros and is written as zeros, whatever several rows do to it at once;
    a live row with ``dt = 0`` leaves its slot as it was, bit for bit. With
    heads of 128 channels and with two of 64 side by side."""
    side = 128 // p
    _budget(monkeypatch, fits, 8, 128)
    c = _case(7, 4, 4, 2, 8, p, 5)
    ids, fresh = jnp.asarray([0, 3, 0, 2], jnp.int32), jnp.asarray([True, False, True, False])
    dt = c["dt"].at[jnp.asarray([0, 2, 3])].set(0.0)
    before = np.asarray(c["state"])
    y, state = pallas_mamba.mamba_decode_step.__wrapped__(mamba2.lay_side_by_side(c["state"], side), ids, fresh, c["x"], c["b"],
                                                          c["c"], dt, c["a"], interpret=True)
    state = mamba2.lay_by_head(state, side)
    after = np.asarray(state)
    assert np.array_equal(after[[1, 2, 4]], before[[1, 2, 4]]) and not after[0].any() and not np.asarray(y)[[0, 2]].any()
    y_want, s_want = _plain({**c, "dt": dt}, ids, fresh)
    np.testing.assert_allclose(after[3], s_want[1], atol=2e-6)
    np.testing.assert_allclose(y, y_want, atol=1e-5)


def test_supported_shapes(monkeypatch):
    monkeypatch.setattr(pallas_mamba, "interpret_mode", lambda: False)
    assert pallas_mamba.supported(256, 128) and pallas_mamba.supported(8, 128)
    assert not pallas_mamba.supported(8, 16) and not pallas_mamba.supported(12, 128)
    # What the predicate is asked is the buffer's row: heads of 64 channels tile where two lie side by side, not alone.
    narrow = dataclasses.replace(PRESETS["test-tiny-falcon-h1"], ssm_heads=128, ssm_head_dim=64, ssm_state_size=128, ssm_groups=1)
    assert narrow.ssm_heads_per_row == 2 and narrow.state_shapes()[0] == (64, 128, 128)
    assert pallas_mamba.supported(*narrow.state_shapes()[0][1:]) and not pallas_mamba.supported(128, 64)
    odd = dataclasses.replace(narrow, ssm_heads=3, ssm_groups=3)  # a head a group: no two of a group to lay side by side
    assert odd.ssm_heads_per_row == 1 and not pallas_mamba.supported(*odd.state_shapes()[0][1:])


@pytest.mark.parametrize("cuts", [(64,), (13, 40, 11), (1, 1, 62), (7,) * 9 + (1,)], ids=["whole", "ragged", "ones-first", "sevens"])
def test_chunk_steps_over_any_split_are_the_recurrence_token_by_token(cuts):
    """64 tokens from a carried state, cut into chunks and each chunk padded
    to 16 more tokens with ``dt = 0`` (what ``mamba_mixer`` makes of padding):
    outputs and the last state against ``recurrent_step`` 64 times. Heads
    that forget within a few tokens beside heads that hardly forget."""
    rng = np.random.default_rng(3)
    t, g, r, n, p = 64, 2, 3, 8, 16
    f = lambda x: jnp.asarray(x, jnp.float32)  # noqa: E731
    x, b, c = f(rng.normal(size=(t, g, r, p))), f(rng.normal(size=(t, g, n))), f(rng.normal(size=(t, g, n)))
    dt = f(rng.uniform(0.001, 1.5, size=(t, g, r)) * np.asarray([1.0, 0.01, 3.0])[None, None, :])
    a, s0 = f(-rng.uniform(0.5, 16.0, size=(g, r))), f(rng.normal(size=(g, r, n, p)))
    s, want = s0, []
    for i in range(t):
        y, s = mamba2.recurrent_step(s, x[i], b[i], c[i], dt[i], a)
        want.append(y)
    got, carried, lo = [], s0, 0
    for width in cuts:
        pad = lambda z: jnp.concatenate([z[lo: lo + width], jnp.ones((16, *z.shape[1:]), z.dtype)])  # noqa: E731,B023
        dt_c = jnp.concatenate([dt[lo: lo + width], jnp.zeros((16, g, r))])
        y, carried = mamba2.chunk_step(carried, pad(x), pad(b), pad(c), dt_c, a)
        got.append(y[:width])
        lo += width
    assert lo == t
    want = jnp.stack(want)  # sums that cancel: the error is float32's at the largest output, not at each
    np.testing.assert_allclose(jnp.concatenate(got), want, atol=2e-5 * float(jnp.abs(want).max()))
    np.testing.assert_allclose(carried, s, atol=2e-5 * float(jnp.abs(s).max()))
    # The fastest decay there is (dt A = -48 a token) neither overflows nor divides by zero.
    y, s_fast = mamba2.chunk_step(s0, x, b, c, jnp.full_like(dt, 3.0), jnp.full_like(a, -16.0))
    assert np.isfinite(np.asarray(y)).all() and np.isfinite(np.asarray(s_fast)).all()


def test_the_mixer_takes_the_kernel_where_the_platform_runs_it(monkeypatch):
    """``mamba_mixer`` with ``impl="pallas"`` under the interpreter against
    the ``jax.numpy`` step, through slots (a live row, a fresh row, a padding
    row), at a head width the kernel tiles; then the same rows on the split
    token axis beside a chunk slot."""
    monkeypatch.setenv("DYNAMO_PALLAS_INTERPRET", "1")
    cfg = dataclasses.replace(PRESETS["test-tiny-falcon-h1"], ssm_heads=2, ssm_head_dim=128)
    lp = jax.tree.map(lambda x: x[0], mamba2.init_mamba_params(cfg, jax.random.PRNGKey(0), jnp.float32, 1))
    lp["ssm_dt_bias"] = jnp.asarray([-2.0, 0.5])
    h = jax.random.normal(jax.random.PRNGKey(1), (3, 1, 64), jnp.float32)
    state, conv = kda.init_state(cfg, 5, dtype=jnp.float32)
    state = state + jax.random.normal(jax.random.PRNGKey(2), state.shape)
    conv = conv + jax.random.normal(jax.random.PRNGKey(3), conv.shape)
    args = dict(positions=jnp.asarray([[9], [0], [0]]), valid=jnp.asarray([[True], [True], [False]]), slot_ids=jnp.asarray([4, 2, 0]))
    before = np.asarray(state)
    want = mamba2.mamba_mixer(lp, cfg, h, state=state, conv=conv, impl="reference", **args)
    got = mamba2.mamba_mixer(lp, cfg, h, state=jnp.array(before), conv=jnp.array(conv), impl="pallas", **args)  # the kernels donate them
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-5)
    assert np.array_equal(np.asarray(got[1])[[1, 3]], before[[1, 3]])  # slots no row names
    # One token axis: the three decode slots, then a chunk slot of 4 tokens (its last a padding token) in slot 1.
    chunk = jax.random.normal(jax.random.PRNGKey(4), (1, 4, 64), jnp.float32)
    flat = dict(positions=jnp.asarray([[9, 0, 0, 5, 6, 7, 0]]), valid=jnp.asarray([[True, True, False, True, True, True, False]]),
                slot_ids=jnp.asarray([4, 2, 0, 1]), split=(3, 1, 4))
    out, state2, conv2 = mamba2.mamba_mixer(lp, cfg, jnp.concatenate([h.reshape(1, 3, 64), chunk], axis=1),
                                            state=jnp.array(before), conv=jnp.array(conv), impl="pallas", **flat)
    np.testing.assert_allclose(out[0, :3], want[0][:, 0], atol=1e-5)
    alone = mamba2.mamba_mixer(lp, cfg, chunk, state=state, conv=conv, impl="reference", positions=jnp.asarray([[5, 6, 7, 0]]),
                               valid=jnp.asarray([[True, True, True, False]]), slot_ids=jnp.asarray([1]))
    np.testing.assert_allclose(out[0, 3:6], alone[0][0, :3], atol=1e-5)
    np.testing.assert_allclose(state2[1], alone[1][1], atol=1e-5)
    np.testing.assert_allclose(conv2[1], alone[2][1], atol=1e-6)


@pytest.mark.parametrize("kind", ["kda", "mamba", "conv.kda", "conv.mamba"])
def test_the_bench_rehearses_every_candidate_against_the_plain_step(kind):
    """``tools/state_kernel_bench.py`` at its toy shapes under the interpreter:
    the served kernel at two budgets, the bare copy / read / write of the
    same blocks and the XLA step, each scanned over the layers on one donated
    state: every kernel row equal to the plain step, the padding row's slot
    and the unnamed slots bit for bit, and no time printed off the chip. The
    conv rows (``--kinds conv``, PR 48): the flat buffer's gather and scatter
    they are checked against, the same on the tiled buffer, the served kernel
    and the bare copy of its blocks; the carried inputs are copies, so the
    buffers are equal bit for bit."""
    import importlib.util
    import pathlib

    path = pathlib.Path(__file__).resolve().parent.parent / "tools" / "state_kernel_bench.py"
    spec = importlib.util.spec_from_file_location("state_kernel_bench", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    shape = tool.TOY[kind]
    rows = tool.bench(kind, tool.candidates_of(kind, shape, [2, 4], "", None, interpret=True), shape,
                      seed=7, iters=1, timed=False, peak=0.0)
    if kind.startswith("conv"):
        assert [r["candidate"] for r in rows] == ["flat", "xla@tiled", "served", "copy@row"]
        assert all(r["kept"] and r["out_err"] < 1e-5 and r["state_err"] == 0 for r in rows[:3]), rows
        assert rows[2]["served"] and not any("us_call" in r or "error" in r for r in rows), rows
        return
    assert [r["candidate"] for r in rows] == ["xla", "served@2", "served@4", "copy@4", "read@4", "write@4"]
    kernels = [r for r in rows if "stream" not in r]
    assert all(r["kept"] and r["out_err"] < 1e-5 and r["state_err"] < 1e-5 for r in kernels), rows
    assert [r["block"] for r in kernels[1:]] == [2, 4] and not any("us_call" in r or "error" in r for r in rows), rows
