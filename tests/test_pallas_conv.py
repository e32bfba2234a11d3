"""The slot conv kernel (``ops/pallas_conv.py``) in interpret mode against
``models/kda.causal_conv`` on gathered rows, the semantics the conv buffer had
when it lay ``[slots, taps - 1, channels]``: slots read through their ids, a
fresh row read as zeros, a padding row's slot back as it was read, the shifted
inputs written in place and no other slot touched; then ``models/kda.slot_conv``
by each of its ways, and the host's mirror of the routing predicates."""

import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import kda
from dynamo_tpu.ops import pallas_conv


def _case(seed, rows, slots, taps, channels, dtype, bias, tokens=1, lanes=128):
    """Rows of ``tokens`` on permuted slots; row 1 fresh, rows 2 and 4 (where
    there are as many) padding rows on the null slot: a repeated id. Rows of
    several tokens are ragged: 0 to ``tokens`` of a row's tokens are valid."""
    rng = np.random.default_rng(seed)
    ids = rng.permutation(np.arange(1, slots))[:rows]
    n_valid, fresh = (np.arange(rows) * 2 + tokens) % (tokens + 1) if tokens > 1 else np.ones(rows, np.int32), np.zeros(rows, bool)
    fresh[1], n_valid[0] = True, tokens
    for r in (2, 4):
        if r < rows:
            ids[r], n_valid[r] = 0, 0
    lanes = lanes if channels % lanes == 0 else channels
    return dict(
        conv=jnp.asarray(rng.normal(size=(slots, taps - 1, channels // lanes, lanes)), dtype),
        ids=jnp.asarray(ids, jnp.int32), fresh=jnp.asarray(fresh), n_valid=jnp.asarray(n_valid),
        x=jnp.asarray(rng.normal(size=(rows, tokens, channels)), jnp.float32),
        filt=jnp.asarray(rng.normal(size=(taps, channels)) * 0.5, dtype),
        bias=jnp.asarray(rng.normal(size=channels) * 0.3, dtype) if bias else None)


def _plain(c):
    """``causal_conv`` on the gathered rows of the flat ``[slots, taps - 1, channels]`` buffer, scattered back."""
    flat = c["conv"].reshape(*c["conv"].shape[:2], c["x"].shape[-1])
    prev = jnp.where(c["fresh"][:, None, None], jnp.zeros((), flat.dtype), flat[c["ids"]])
    y, carried = kda.causal_conv(c["x"], prev, c["filt"], c["n_valid"], c["bias"])
    return y, flat.at[c["ids"]].set(carried.astype(flat.dtype))


@pytest.mark.parametrize("rows, slots, taps, channels, dtype, bias, tokens", [
    (6, 9, 4, 3 * 256, jnp.bfloat16, False, 1),  # KDA's three streams, no bias: 6 rows of 128 lanes
    (6, 9, 4, 5 * 128, jnp.bfloat16, True, 1),  # Mamba-2's x, B, C with a bias: a row count no tile of 16 divides
    (5, 7, 4, 12288, jnp.bfloat16, False, 1),  # Ling-3.0-flash's width
    (5, 7, 4, 5120, jnp.bfloat16, True, 1),  # Falcon-H1-34B's
    (3, 5, 4, 24576, jnp.bfloat16, False, 1),  # Solar-Open2-250B's: three streams of 64 heads, 192 rows of lanes
    (2, 4, 4, 24576, jnp.bfloat16, False, 64),  # and a served chunk's 64 tokens at that width
    (3, 5, 4, 384, jnp.float32, True, 1),  # the toys' dtype
    (4, 6, 2, 256, jnp.bfloat16, False, 1),  # one carried input
    (4, 6, 5, 256, jnp.float32, True, 1),  # four
    (3, 5, 4, 96, jnp.float32, True, 1),  # a width that is no multiple of 128: one row (the interpreter tiles nothing)
    (6, 9, 4, 3 * 256, jnp.bfloat16, False, 9),  # chunk rows, ragged: the loop over tokens behind the first taps - 1
    (6, 9, 4, 5 * 128, jnp.bfloat16, True, 2),  # fewer tokens than carried inputs
    (5, 7, 4, 256, jnp.float32, True, 3),  # as many
    (5, 7, 3, 256, jnp.bfloat16, True, 64),  # a served chunk's 64 tokens
], ids=["kda", "mamba-bias", "ling-width", "falcon-h1-width", "solar-open2-width", "chunk-solar-open2-width", "float32", "two-taps", "five-taps", "one-row",
        "chunk-kda", "chunk-two-tokens", "chunk-three-tokens", "chunk-64"])
def test_kernel_matches_the_conv_on_gathered_rows(rows, slots, taps, channels, dtype, bias, tokens):
    c = _case(rows * 7 + taps, rows, slots, taps, channels, dtype, bias, tokens)
    before = np.asarray(c["conv"].astype(jnp.float32)).reshape(slots, taps - 1, channels)
    y_want, conv_want = _plain(c)  # computed to its end before the kernel takes (and donates) the buffer
    y_want, conv_want = np.asarray(y_want), np.asarray(conv_want.astype(jnp.float32))
    tile = c["conv"].shape[2:]
    lay = lambda z: None if z is None else z.reshape(*z.shape[:-1], *tile)  # noqa: E731
    y, conv = pallas_conv.slot_conv_step(c["conv"], c["ids"], c["fresh"], c["n_valid"], lay(c["x"]), lay(c["filt"]),
                                         lay(c["bias"]), interpret=True)
    np.testing.assert_allclose(np.asarray(y).reshape(rows, tokens, channels), y_want, atol=2e-6, rtol=2e-6)
    got = np.asarray(conv.astype(jnp.float32)).reshape(slots, taps - 1, channels)
    ids, n_valid = np.asarray(c["ids"]), np.asarray(c["n_valid"])
    assert np.array_equal(got[ids[ids > 0]], conv_want[ids[ids > 0]])  # the carried inputs are copies: bit for bit
    others = np.setdiff1d(np.arange(slots), ids[n_valid > 0])  # the null slot among them: its padding rows wrote back what they read
    assert np.array_equal(got[others], before[others])
    if tokens == 1:
        assert np.array_equal(got[ids[0], :-1], before[ids[0], 1:])  # the shift
        assert not got[ids[1], :-1].any() and got[ids[1], -1].any()  # zeros behind a fresh row's one input


@pytest.mark.parametrize("way, t, impl, interpret", [
    ("kernel", 1, "pallas", True), ("refused-shape", 1, "pallas", False), ("reference", 1, "reference", True),
    ("chunk", 5, "pallas", True), ("chunk-reference", 5, "reference", True)])
def test_slot_conv_is_the_conv_on_gathered_rows_by_every_way(monkeypatch, way, t, impl, interpret):
    """``slot_conv`` on the tiled buffer: rows of one token and of a chunk
    (ragged: a row's ``n_valid`` tokens enter its slot) through the kernel,
    and through the gather where the kernel refuses the shape (a toy's 96
    lanes outside the interpreter) or the platform runs none."""
    monkeypatch.setattr(pallas_conv, "interpret_mode", lambda: interpret)
    channels = 96 if way == "refused-shape" else 256
    c = _case(11, 4, 6, 4, channels, jnp.float32, True, t)
    assert pallas_conv.supported(t, *c["conv"].shape[2:]) == (way != "refused-shape")
    y_want, conv_want = (np.asarray(z) for z in _plain(c))
    calls, step = [], pallas_conv.slot_conv_step
    monkeypatch.setattr(pallas_conv, "slot_conv_step", lambda *a, **kw: calls.append(1) or step(*a, **kw))
    y, conv = kda.slot_conv(jnp.array(c["conv"]), c["ids"], c["fresh"], c["x"], c["filt"], c["n_valid"], c["bias"], impl=impl)
    assert len(calls) == (impl == "pallas" and way != "refused-shape")
    assert conv.shape == c["conv"].shape and y.shape == (4, t, *c["conv"].shape[2:])
    np.testing.assert_allclose(np.asarray(y).reshape(4, t, channels), y_want, atol=2e-6, rtol=2e-6)
    np.testing.assert_array_equal(np.asarray(conv).reshape(6, 3, channels), conv_want)
    # The streams' heads out of the rows of lanes: whole rows sliced where they lie, or the flat slice.
    flat = np.asarray(y).reshape(4, t, channels)
    for first, heads, dim in ((0, 2, 32), (64, 1, 32)) + (((128, 1, 128), (0, 1, 256)) if channels == 256 else ()):
        np.testing.assert_array_equal(kda.conv_heads(y, first, heads, dim), flat[..., first: first + heads * dim].reshape(4, t, heads, dim))


@pytest.mark.parametrize("t, impl", [(1, "pallas"), (1, "reference"), (5, "pallas"), (5, "reference")],
                         ids=["kernel", "reference", "chunk", "chunk-reference"])
def test_slot_conv_fills_a_buffer_whose_rows_hold_more_than_the_channels(monkeypatch, t, impl):
    """A channel count whose rows of lanes are no whole sublane tiles
    (granite-4.0-h-small's 8,448 channels: 66 rows; here 1,152: 9) is held in
    rows rounded up to eights (``ModelConfig.state_shapes``): ``slot_conv``
    pads the rows' inputs, the filter and the bias with zeros, by the kernel
    and by the gather alike; outputs and the carried inputs of the real
    channels are the flat buffer's, the channels behind them stay zeros, and
    ``conv_heads`` reads the streams where they lie."""
    import dataclasses

    from dynamo_tpu.models.config import PRESETS

    cfg = dataclasses.replace(PRESETS["test-tiny-falcon-h1"], ssm_heads=8, ssm_head_dim=128, ssm_state_size=64, ssm_groups=1)
    channels, tile = cfg.ssm_conv_dim, cfg.state_shapes()[1]
    assert channels == 9 * 128 and tile == (3, 16, 128)
    monkeypatch.setattr(pallas_conv, "interpret_mode", lambda: True)
    c = _case(13, 4, 6, 4, channels, jnp.float32, True, t)
    y_want, conv_want = (np.asarray(z) for z in _plain(c))
    held = jnp.pad(c["conv"], ((0, 0), (0, 0), (0, 7), (0, 0)))  # the buffer as it is allocated: 16 rows, the last 7 zeros
    assert held.shape == (6, *tile)
    y, conv = kda.slot_conv(held, c["ids"], c["fresh"], c["x"], c["filt"], c["n_valid"], c["bias"], impl=impl)
    assert conv.shape == held.shape and y.shape == (4, t, 16, 128)
    np.testing.assert_allclose(np.asarray(y).reshape(4, t, -1)[..., :channels], y_want, atol=2e-6, rtol=2e-6)
    np.testing.assert_array_equal(np.asarray(conv).reshape(6, 3, -1)[..., :channels], conv_want)
    assert not np.asarray(conv)[:, :, 9:].any() and not np.asarray(y)[:, :, 9:].any()  # silu(0 + 0) behind the channels
    flat = np.asarray(y).reshape(4, t, -1)
    for first, heads, dim in ((0, 8, 128), (1024, 1, 64), (1088, 1, 64)):  # x, B, C
        np.testing.assert_array_equal(kda.conv_heads(y, first, heads, dim), flat[..., first: first + heads * dim].reshape(4, t, heads, dim))


@pytest.mark.parametrize("refused, decode_path", [(None, "pallas"), ("pallas_conv", "fallback"), ("pallas_kda", "fallback")],
                         ids=["every-kernel", "conv-refused", "state-refused"])
def test_the_step_record_says_fallback_when_a_decode_rows_conv_or_state_leaves_its_kernel(monkeypatch, refused, decode_path):
    """The host's mirror of the routing predicates (``runner._attn_dispatch``):
    a decode step of a model with recurrent layers is ``pallas`` only where
    the conv kernel and the state kernel both take its rows; a step of chunk
    rows alone is not judged by them."""
    import importlib

    from dynamo_tpu.engine.runner import ModelRunner
    from tests.test_hybrid_kda import _model, _null_batch

    monkeypatch.setenv("DYNAMO_PALLAS_INTERPRET", "1")
    if refused:  # the toy's 192 channels are one row of 192 lanes, its heads 16 x 16: neither tiles outside the interpreter
        monkeypatch.setattr(importlib.import_module(f"dynamo_tpu.ops.{refused}"), "interpret_mode", lambda: False)
    cfg, params, _ = _model("kda")
    runner = ModelRunner(cfg, params, num_pages=16, page_size=8, max_batch_size=2, prefill_bucket=4, attn_impl="pallas")
    batch = _null_batch(2, 1, 1)
    batch.tokens[:, 0], batch.block_tables[:, 0], batch.slot_mapping[:, 0], batch.pos_limit[:] = [5, 6], [1, 2], [8, 16], 8
    batch.state_slots = np.asarray([1, 2], np.int32)
    runner.step(batch)
    assert runner.last_attn_dispatch == ("decode", decode_path)
    chunk = _null_batch(1, 4, 1)
    chunk.tokens[0], chunk.positions[0], chunk.block_tables[:, 0], chunk.slot_mapping[0], chunk.pos_limit[:] = (
        [5, 6, 7, 8], np.arange(4), [1], 8 + np.arange(4), 8)
    chunk.state_slots = np.asarray([1], np.int32)
    runner.step(chunk)
    assert runner.last_attn_dispatch == ("prefill", "pallas")
