"""The windowed block walk of the paged kernels, in interpret mode on the CPU,
against ``paged_attention_reference(sliding_window=w)``: decode rows, gappy
multi-query verify rows and 64-token prefill chunks, at Mellum2's head layout
(32 query and 4 KV heads of 128) and page 128, for a window that is a whole
number of pages, one that is not, one larger than every context and one
smaller than the chunk. Contexts span several compute blocks, so rows start
their walk at different blocks and fall into different splits."""

import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops.attention import paged_attention, paged_attention_reference
from dynamo_tpu.ops.pallas_paged import NO_WINDOW, paged_decode_attention
from dynamo_tpu.ops.pallas_prefill import paged_prefill_attention

HEADS, KV, HD, PAGE = 32, 4, 128, 128
#: (name, window): page-aligned, not aligned, larger than any context, smaller than the chunk.
WINDOWS = [("aligned", 256), ("unaligned", 200), ("beyond", 5000), ("under-chunk", 40)]
CONTEXTS = [1500, 700, 2300, 130, 64, 1]
PAGES_PER_SEQ = 20


def _cache(seed=0):
    rng = np.random.default_rng(seed)
    b = len(CONTEXTS)
    shape = (b * PAGES_PER_SEQ + 1, PAGE, KV * HD)
    k = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    v = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    tables = 1 + np.arange(b * PAGES_PER_SEQ, dtype=np.int32).reshape(b, PAGES_PER_SEQ)
    return rng, k, v, jnp.asarray(tables)


def _rows(t, spans):
    """Positions [B, t]: row b holds ``spans[b]`` real tokens ending at its
    context's last position, padded with zeros."""
    pos = np.zeros((len(CONTEXTS), t), np.int32)
    for b, (ctx, n) in enumerate(zip(CONTEXTS, spans)):
        n = min(n, ctx)
        pos[b, :n] = ctx - n + np.arange(n)
    real = (pos > 0) | (np.arange(t)[None] == 0)
    return jnp.asarray(pos), real


def _check(got, want, real):
    assert bool(jnp.isfinite(got).all())  # padding columns included: nothing reaches the cache as NaN
    err = np.abs(np.asarray(got) - np.asarray(want))[real]
    assert err.max() < 2e-5


@pytest.mark.parametrize("name, window", WINDOWS, ids=[w[0] for w in WINDOWS])
@pytest.mark.parametrize("splits", [0, 3], ids=["auto-splits", "3-splits"])
def test_windowed_decode_rows(name, window, splits):
    rng, k, v, tables = _cache()
    pos, real = _rows(1, [1] * len(CONTEXTS))
    q = jnp.asarray(rng.standard_normal((len(CONTEXTS), 1, HEADS, HD)), jnp.float32)
    want = paged_attention_reference(q, k, v, tables, pos, sliding_window=window)
    got = paged_decode_attention(q, k, v, tables, pos, scale=HD**-0.5, interpret=True,
                                 window=jnp.int32(window), num_splits=splits)
    _check(got, want, real)


@pytest.mark.parametrize("name, window", WINDOWS, ids=[w[0] for w in WINDOWS])
def test_windowed_verify_rows(name, window):
    """T = 4 multi-query rows of 4, 2, 3, 1, 4, 1 real tokens (padding columns at position 0)."""
    rng, k, v, tables = _cache(1)
    pos, real = _rows(4, [4, 2, 3, 1, 4, 1])
    q = jnp.asarray(rng.standard_normal((len(CONTEXTS), 4, HEADS, HD)), jnp.float32)
    want = paged_attention_reference(q, k, v, tables, pos, sliding_window=window)
    got = paged_decode_attention(q, k, v, tables, pos, scale=HD**-0.5, interpret=True, window=jnp.int32(window))
    _check(got, want, real)


@pytest.mark.parametrize("name, window", WINDOWS, ids=[w[0] for w in WINDOWS])
def test_windowed_prefill_chunks(name, window):
    """A mixed step: 64-token chunks beside 1-token decode rows."""
    rng, k, v, tables = _cache(2)
    pos, real = _rows(64, [64, 1, 64, 64, 1, 1])
    q = jnp.asarray(rng.standard_normal((len(CONTEXTS), 64, HEADS, HD)), jnp.float32)
    want = paged_attention_reference(q, k, v, tables, pos, sliding_window=window)
    got = paged_prefill_attention(q, k, v, tables, pos, scale=HD**-0.5, interpret=True, window=jnp.int32(window))
    _check(got, want, real)


def test_no_window_value_is_full_attention_and_the_dispatch_takes_the_kernels(monkeypatch):
    """``NO_WINDOW`` (a full layer inside a scan that carries the window)
    computes what the unwindowed kernel computes, and ``paged_attention``
    sends windowed calls to the kernels: no ``sliding_window`` fallback."""
    from dynamo_tpu.ops import pallas_paged

    monkeypatch.setenv("DYNAMO_PALLAS_INTERPRET", "1")
    rng, k, v, tables = _cache(3)
    pos, real = _rows(1, [1] * len(CONTEXTS))
    q = jnp.asarray(rng.standard_normal((len(CONTEXTS), 1, HEADS, HD)), jnp.float32)
    full = paged_decode_attention(q, k, v, tables, pos, scale=HD**-0.5, interpret=True)
    got = paged_decode_attention(q, k, v, tables, pos, scale=HD**-0.5, interpret=True, window=jnp.int32(NO_WINDOW))
    _check(got, full, real)
    before = dict(pallas_paged.fallback_snapshot())
    via = paged_attention(q, k, v, tables, pos, impl="pallas", sliding_window=256)
    _check(via, paged_attention_reference(q, k, v, tables, pos, sliding_window=256), real)
    chunk_pos, chunk_real = _rows(64, [64, 1, 64, 64, 1, 1])
    qc = jnp.asarray(rng.standard_normal((len(CONTEXTS), 64, HEADS, HD)), jnp.float32)
    _check(paged_attention(qc, k, v, tables, chunk_pos, impl="pallas", sliding_window=jnp.int32(200)),
           paged_attention_reference(qc, k, v, tables, chunk_pos, sliding_window=200), chunk_real)
    assert pallas_paged.fallback_snapshot() == before


#: (name, pages a block, rows' positions [B, T_q], window, splits), at
#: ``tests/test_pallas_paged.py``'s page of 8 tokens: a block is 32 or 64 tokens.
HEADS_OF_WINDOWS = [
    # keys 46..69 of row 1: pages 5..8, blocks 1 and 2 of 4 pages; row 3's window ends a block
    ("straddles-a-block-edge", 4, [[2], [69], [149], [63]], 24, 1),
    ("straddles-a-block-edge-3-splits", 4, [[2], [69], [149], [63]], 24, 3),
    # keys 34..36: one page, the fifth, in block 1's first slot; row 3: the last slot of block 0
    ("inside-a-page", 4, [[2], [36], [149], [30]], 3, 1),
    ("inside-a-page-8", 8, [[2], [36], [149], [62]], 3, 3),
    # the head falls on every slot of an 8-page block in turn: first keys 71, 81, 95, 120
    ("heads-across-a-block", 8, [[110], [120], [134], [159]], 40, 1),
    ("one-page-windows", 8, [[7], [8], [71], [72]], 8, 3),
    ("no-window", 4, [[2], [69], [149], [100]], NO_WINDOW, 3),
    ("no-window-8", 8, [[2], [69], [149], [100]], NO_WINDOW, 1),
    ("window-past-the-context", 8, [[2], [69], [149], [100]], 1000, 1),
    ("padding-row", 4, [[0], [69], [0], [100]], 24, 1),
    # oldest real query 77 -> first key 54. A padding column (position 0) sees no key at all, not
    # even in the last row, whose walk visits block 0 and holds pages 2.. (4..) of it, not page 0
    ("verify-row", 4, [[77, 78, 80], [101, 0, 0], [0, 0, 0], [30, 31, 33], [45, 0, 0]], 24, 1),
    ("verify-row-8-3-splits", 8, [[77, 78, 80], [101, 0, 0], [0, 0, 0], [130, 131, 133], [60, 0, 62]], 24, 3),
]


@pytest.mark.parametrize("name, pages_per_block, positions, window, num_splits", HEADS_OF_WINDOWS,
                         ids=[c[0] for c in HEADS_OF_WINDOWS])
def test_windowed_walk_moves_only_held_pages(monkeypatch, name, pages_per_block, positions, window, num_splits):
    """A windowed row copies the pages from its window's first key to its
    farthest query and no other: not the slots of its first block under the
    window (the engine has released those pages of a window pool), not the
    slots of its last block past the row."""
    from tests.test_pallas_paged import check_only_held_pages_move

    check_only_held_pages_move(monkeypatch, pages_per_block=pages_per_block, positions=positions,
                               window=window, num_splits=num_splits)
