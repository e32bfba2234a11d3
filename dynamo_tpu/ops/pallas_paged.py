"""Pallas TPU paged-attention decode kernel (split-K, multi-query).

The HBM-bandwidth-bound hot loop of serving: for each decoding sequence,
attention must read that sequence's entire paged KV history once. This
kernel streams KV pages HBM -> VMEM with an N-deep ring of async DMAs and
computes online-softmax attention on the fly — the gathered K/V is never
materialized (the XLA reference formulation in ``ops/attention.py`` builds
a [B, S, n_kv, hd] gather per layer per step, which at batch 32 / 1k-token
contexts is tens of MB of extra HBM traffic per layer per decode step).

Design (fresh, built around the engine's page-major cache layout):

- Cache layout is the engine's flat ``[num_pages, page_size, n_kv * head_dim]``
  per layer (``ops/attention.py``): one page is a single contiguous
  ``page_size * n_kv * head_dim`` slab covering **all KV heads**, so each
  page needs exactly one DMA descriptor (~16 KB for Llama-3.2-1B) instead
  of one small copy per (head, page). DMA-descriptor issue rate, not
  bandwidth, is what limits a paged gather at page granularity — this
  layout is the difference between ~14 GB/s and saturating HBM.
- The trailing extent ``n_kv * head_dim`` is a multiple of 128 lanes for
  every serving config (8 x 64, 8 x 128, ...), satisfying Mosaic's DMA
  alignment even at head_dim 64 (Llama-3.2-1B) where a head-major layout
  cannot be sliced.
- **Multi-query rows** (speculative verify): the kernel accepts T_q >= 1
  query tokens per sequence, staged as ``[T_q * n_heads, W]`` block-diagonal
  strips. Causality is a per-ROW mask ``kpos <= position[b, t]`` — exact
  for gappy verify layouts, and for T_q = 1 it reduces bit-for-bit to the
  plain decode mask (``kpos < length``). A K+1-wide verify row therefore
  attends exactly as K+1 sequential decodes would, on the same DMA-
  pipelined path instead of the ~5x-slower XLA gather formulation.
- GQA is one **block-diagonal matmul**: row (t, h) carries head h's query
  in its own KV head's column strip, so ``scores = q_bd @ kv_slab.T``
  yields every (token, head) pair's logits against its KV head in a single
  MXU contraction (off-strip products are computed and discarded — MXU
  cycles are free in a DMA-bound kernel). The weighted-value product
  accumulates the full ``[T_q * n_heads, W]`` strip; the caller extracts
  each head's diagonal strip with one fused XLA gather at the end.
- **Split-K grid** ``(batch, num_splits)`` (Flash-Decoding style): each
  split walks its static slice of the sequence's page-block list carrying
  partial online-softmax state (m, l, acc) and writes per-split outputs;
  a small log-sum-exp combine (:func:`_lse_combine`) merges them. Split
  boundaries are functions of STATIC shapes only (pages bucket, page
  size, block size) — never of runtime lengths — so the per-row float
  accumulation order is identical whether a row is scored as a T_q = 1
  decode or inside a T_q = K+1 verify batch. ``num_splits`` is auto-chosen
  from batch x context (``DYN_DECODE_SPLITS`` overrides) so low-batch
  long-context decode keeps multiple DMA streams in flight instead of one
  sequential block walk per sequence.
- The DMA pipeline is an N-deep ring (``DYN_DECODE_DMA_DEPTH``, default
  4) **across grid steps**: while block g is being reduced, blocks
  g+1..g+depth-1 (possibly a later split's or sequence's) are in flight.
  Ring slot is a pure function of the global block index (a prefix count
  over earlier sequences and splits), so there is no mutable cross-step
  state and the kernel is interpret-mode exact.
- A row's walk copies only the pages some query of the row may see
  (:func:`decode_walk`): in its first block not the page slots under the
  window, in its last not the slots past its farthest token; the V rows of
  those slots are zeroed in VMEM and the block is contracted whole. What
  the walk counts by (a row's first and last page, its blocks, the blocks
  of the rows before) is worked out by the wrapper and prefetched
  (``docs/KERNELS.md``, "The walk's tail and the window's head").

Replaces the role of vLLM's paged-attention CUDA kernel in the reference
stack (SURVEY.md §2 row 30, §7 hard part (a); `lib/llm/src/kernels/` is the
reference's only first-party kernel code). See ``docs/KERNELS.md`` for the
full design note.

Tests: ``tests/test_pallas_paged.py`` (interpret mode on CPU vs the
reference formulation); ``tests_tpu/test_on_device.py`` (Mosaic-compiled
parity on the real chip).
"""

from __future__ import annotations

import functools
import logging
import os
import threading
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.attention import paged_attention_reference

logger = logging.getLogger(__name__)

NEG_INF = -1e30
LANES = 128
#: A window value that bounds nothing (past every position; ``pos - NO_WINDOW``
#: stays inside int32): a full-attention layer inside a scan that carries the
#: window as a per-layer scalar.
NO_WINDOW = 2**30

# Kernel-fallback observability: a config typo (odd GQA grouping, a page
# slab width off the 128-lane grid) silently costs ~5x decode throughput if
# the dispatch drops to the gather formulation. The dispatch runs at jit
# trace time, so each entry counts *compiled programs* that fell back (one
# per shape signature — exactly the "once per config" the operator needs),
# warns on first occurrence, and is exported by the frontend /metrics
# endpoint (frontend/metrics.py:FrontendMetrics.render). Phases: ``decode``
# (T == 1), ``verify`` (T > 1 gappy rows — speculative verify), ``prefill``
# (T > 1 contiguous), ``sliding_window``, ``mla_decode``/``mla_verify``.
FALLBACK_COUNTS: dict[str, int] = {}
_fallback_lock = threading.Lock()
_warned_signatures: set[str] = set()


def _record_fallback(phase: str, q: jnp.ndarray, k_cache: jnp.ndarray) -> None:
    sig = (
        f"{phase}:heads={q.shape[-2]},head_dim={q.shape[-1]},"
        f"slab_width={k_cache.shape[2]}"
    )
    with _fallback_lock:
        FALLBACK_COUNTS[sig] = FALLBACK_COUNTS.get(sig, 0) + 1
        warn = sig not in _warned_signatures
        _warned_signatures.add(sig)
    if warn:
        logger.warning(
            "paged-attention Pallas kernel does not support this shape, "
            "falling back to the XLA gather formulation (~5x slower %s): %s",
            phase,
            sig,
        )


def fallback_snapshot() -> dict[str, int]:
    """Race-free copy for metrics scrapes (trace threads mutate the dict)."""
    with _fallback_lock:
        return dict(FALLBACK_COUNTS)


def interpret_mode() -> bool:
    """DYNAMO_PALLAS_INTERPRET=1 runs every Pallas kernel (GQA decode,
    prefill flash, MLA decode) through the interpreter — CPU-executable, so
    multi-chip tests/dryruns cover the kernel path on a virtual mesh."""
    return os.environ.get("DYNAMO_PALLAS_INTERPRET", "") == "1"


def _dma_depth() -> int:
    """Ring depth of the KV DMA pipeline (slots per stream).

    Depth 2 is the classic double buffer; deeper rings keep more page
    blocks in flight across split/sequence boundaries, hiding the issue
    latency of short tail blocks. ``DYN_DECODE_DMA_DEPTH`` overrides
    (min 2). Resolved at trace time — a static program parameter."""
    try:
        depth = int(os.environ.get("DYN_DECODE_DMA_DEPTH", "4"))
    except ValueError:
        depth = 4
    return max(2, depth)


def _max_verify_t(n_heads: int, width: int) -> int:
    """Largest T_q the multi-query kernel accepts per row.

    The staged queries, accumulator, and m/l state all scale with
    ``R = T_q * n_heads`` rows of ``width`` lanes in VMEM; past this cap a
    verify batch (e.g. a mixed step whose prefill chunks widened T to the
    chunk size) falls back to the gather formulation — recorded under the
    ``verify`` phase. ``DYN_VERIFY_T_MAX`` overrides the default of 32."""
    try:
        cap = int(os.environ.get("DYN_VERIFY_T_MAX", "32"))
    except ValueError:
        cap = 32
    # q (2B) + acc (4B f32) rows must fit a ~4 MiB slice of scoped VMEM.
    vmem_cap = (4 * 2**20) // max(1, n_heads * width * 6)
    return max(1, min(cap, vmem_cap))


def _auto_num_splits(batch: int, max_blocks: int) -> int:
    """Split-K factor: sequence-axis parallelism for the grid.

    At batch >= 8 the batch grid dimension already keeps the DMA engines
    busy; below that, split the block walk so low-batch long-context decode
    exposes ~8 concurrent walks (Flash-Decoding's regime). Clamped to the
    static block count — an all-empty split is wasted grid real estate.
    ``DYN_DECODE_SPLITS`` overrides (resolved at trace time)."""
    env = os.environ.get("DYN_DECODE_SPLITS", "")
    if env:
        try:
            return max(1, min(int(env), max_blocks))
        except ValueError:
            pass
    if batch >= 8:
        return 1
    return max(1, min(max_blocks, 8 // max(1, batch)))


def _pages_per_block(
    pages_per_seq: int,
    page_size: int,
    width: int | None = None,
    itemsize: int = 2,
    dma_depth: int = 2,
) -> int:
    """Pages per compute block: target ~1024 tokens per block, capped by the
    kernel's scoped-VMEM budget.

    Deep blocks amortize the fori_loop/online-softmax overhead and batch
    more DMA issues per wait (measured +45% decode throughput vs 2-page
    blocks at serving shapes). But the ring-buffered K+V tiles
    (dma_depth slots x 2 streams x bk x width) live in scoped VMEM with a
    hard ~16 MiB limit — wide slabs (e.g. 16 kv-heads x 128 = 2048 lanes)
    blow it at the 1024-token target (observed: OLMoE decode failing AOT
    compile with "scoped vmem ... exceeded"), so when ``width`` is given
    the block shrinks to keep the tiles within an 8 MiB budget (deeper
    rings trade block depth for pipeline depth at constant VMEM). No
    divisibility requirement — a row's last block copies only the page slots
    the row holds (:func:`decode_walk`) and masks by position."""
    target = max(1, 1024 // page_size)
    if width is not None:
        budget = 8 * 2**20
        max_tokens = max(page_size, budget // (2 * dma_depth * width * itemsize))
        target = min(target, max(1, max_tokens // page_size))
    return max(1, min(pages_per_seq, target))


def _lse_combine(acc: jnp.ndarray, m: jnp.ndarray, l: jnp.ndarray, *, guard_empty: bool = False) -> jnp.ndarray:
    """Merge per-split online-softmax partials along the split axis.

    ``acc`` f32[B, S, R, W] (unnormalized weighted values), ``m``/``l``
    f32[B, S, R] (running max / normalizer). Returns f32[B, R, W].

    An empty split carries (m=NEG_INF, l=0, acc=0): its rescale factor
    ``exp(NEG_INF - M)`` underflows to exactly 0.0, so it contributes
    nothing — and with a single split the combine is exactly ``acc / l``
    (alpha = exp(0) = 1 and the singleton sums are identity), keeping the
    non-split decode path bit-identical."""
    m_max = jnp.max(m, axis=1, keepdims=True)  # [B, 1, R]
    alpha = jnp.exp(m - m_max)  # [B, S, R]
    denom = jnp.sum(alpha * l, axis=1)  # [B, R]
    num = jnp.sum(acc * alpha[..., None], axis=1)  # [B, R, W]
    if guard_empty:
        # Windowed walks only: a padding query column (position 0) of a row
        # whose walk starts past block 0 sees no key at all (l == 0 in every
        # split). Its output is discarded, but 0/0 must not reach the cache
        # through the null page.
        denom = jnp.where(denom > 0.0, denom, 1.0)
    return num / denom[..., None]


class DecodeWalk(NamedTuple):
    """What a call's block walk counts by, per row (each i32[B]): worked out
    once by the wrapper from the query positions and the window, prefetched by
    the kernel, and read by the tools and tests that count the pages a call
    moves."""

    first_page: jnp.ndarray  # first page some real query of the row may see
    last_page: jnp.ndarray  # page of the row's farthest query token
    first_block: jnp.ndarray  # first block the walk visits (blocks are absolute: page // pages_per_block)
    blocks: jnp.ndarray  # blocks the walk visits
    blocks_before: jnp.ndarray  # blocks of the rows before: the row's first global block

    @property
    def pages_started(self):
        """Pages the call copies: the held ones, each once."""
        return jnp.sum(self.last_page - self.first_page + 1)


def decode_walk(positions: jnp.ndarray, page_size: int, pages_per_block: int, window=None) -> DecodeWalk:
    """The walk of ``positions`` i32[B, T_q] (padding columns at position 0).

    A row holds pages ``first_page .. last_page``: from the oldest key its
    oldest real query may see (``first - window + 1``; page 0 without a window,
    and for ``NO_WINDOW``) to its farthest query token. Its walk visits the
    blocks that hold them, and in a visited block only held page slots are
    copied: ``pages_started`` of ``sum(blocks) * pages_per_block`` slots."""
    last_page = jnp.maximum(jnp.max(positions, axis=1), 0) // page_size
    if window is None:
        first_page = jnp.zeros_like(last_page)
    else:
        # The row's oldest real query: padding columns carry position 0 and
        # trail the real ones, so column 0 and the non-zero entries are the
        # candidates (a padding row is all zeros: it holds page 0).
        first_pos = jnp.minimum(
            positions[:, 0], jnp.min(jnp.where(positions > 0, positions, jnp.iinfo(jnp.int32).max), axis=1))
        first_page = jnp.maximum(first_pos - jnp.asarray(window, jnp.int32) + 1, 0) // page_size
    first_block = first_page // pages_per_block
    blocks = last_page // pages_per_block + 1 - first_block
    return DecodeWalk(first_page, last_page, first_block, blocks, jnp.cumsum(blocks) - blocks)


def _decode_kernel(
    # scalar prefetch (SMEM, shared by all grid steps): the walk (DecodeWalk)
    last_ref,  # i32[B] the row's last held page
    blocks_ref,  # i32[B] blocks the row's walk visits
    before_ref,  # i32[B] blocks of the rows before
    tables_ref,  # i32[B * pages_per_seq]
    qpos_ref,  # i32[B * t_q] absolute position of each query token
    *refs,
    # windowed only, three more prefetched scalars first:
    #   head_ref i32[B] the row's first held page
    #   lo_ref i32[B] the first block the row's walk visits
    #   window_ref i32[1] the window in tokens
    # then, always, the blocked operands and the scratch:
    #   q_ref [t_q * n_heads, W] block-diagonal queries, W = n_kv * head_dim
    #   k_hbm, v_hbm [P, page_size, W] in HBM/ANY (page-major, heads flattened)
    #   acc_ref f32[t_q * n_heads, W] — this (b, split)'s partial strip
    #   m_ref, l_ref f32[t_q * n_heads, LANES] — running max / normalizer
    #   k_buf, v_buf [dma_depth, block_tokens, W] VMEM ring
    #   k_sem, v_sem DMA sems [dma_depth]
    windowed: bool = False,
    batch: int,
    pages_per_seq: int,
    pages_per_block: int,
    page_size: int,
    blocks_per_split: int,
    t_q: int,
    n_heads: int,
    dma_depth: int,
):
    if windowed:
        head_ref, lo_ref, window_ref, *refs = refs
        window = window_ref[0]
    q_ref, k_hbm, v_hbm, acc_ref, m_ref, l_ref, k_buf, v_buf, k_sem, v_sem = refs
    b = pl.program_id(0)
    sp = pl.program_id(1)
    bk = pages_per_block * page_size  # tokens per compute block

    def lo_of(bb):
        # Windowed: the walk starts at the block that holds the row's first
        # held page; blocks wholly under the window are never visited.
        return lo_ref[bb] if windowed else 0

    def end_of(bb):  # one past the row's last block
        return lo_of(bb) + blocks_ref[bb]

    # Split sp walks block-in-sequence indices [first, first + nb_here).
    # Boundaries derive from the STATIC blocks_per_split, so a row's
    # accumulation order never depends on other rows' runtime lengths (a
    # windowed row's blocks simply fall in its last splits).
    lo, nb_total = lo_of(b), end_of(b)
    first = jnp.clip(sp * blocks_per_split, lo, nb_total)
    nb_here = jnp.clip(sp * blocks_per_split + blocks_per_split, lo, nb_total) - first

    # Ring slot is a pure function of the global block index (no mutable
    # cross-step state): VISITED blocks of earlier sequences plus of earlier
    # splits of this one. Splits partition each sequence's walk, so the
    # global order is plain (sequence, block-in-sequence) lexicographic.
    g0 = before_ref[b] + first - lo

    def held_slots(bb, ii):
        """Per page slot of row bb's block ii, whether its page is one of the
        row's: from the window's head to the row's last page. Starts and
        waits both go by it, for the row whose block it is, so a copy is
        waited on exactly where it was started; no table entry outside the
        held range is read."""
        base = ii * pages_per_block
        hi = last_ref[bb] - base
        held = [j <= hi for j in range(pages_per_block)]
        if windowed:
            lo_j = head_ref[bb] - base
            held = [jnp.logical_and(h, j >= lo_j) for j, h in enumerate(held)]
        return held

    def page_rows(j):
        return pl.ds(j * page_size, page_size)

    def page_copies(slot, bb, ii, j):
        page = tables_ref[bb * pages_per_seq + ii * pages_per_block + j]
        return (
            pltpu.make_async_copy(k_hbm.at[page], k_buf.at[slot, page_rows(j), :], k_sem.at[slot]),
            pltpu.make_async_copy(v_hbm.at[page], v_buf.at[slot, page_rows(j), :], v_sem.at[slot]),
        )

    def start_block(slot, bb, ii):
        for j, held in enumerate(held_slots(bb, ii)):

            @pl.when(held)
            def _():
                for copy in page_copies(slot, bb, ii, j):
                    copy.start()

    def land_block(slot, bb, ii):
        """Wait on the block's held pages. The ring rows of the others hold an
        earlier block's values or nothing yet, and 0 * NaN is NaN: their V
        rows are zeroed (their K rows only meet the mask's select)."""
        for j, held in enumerate(held_slots(bb, ii)):

            @pl.when(held)
            def _():
                for copy in page_copies(slot, bb, ii, j):
                    copy.wait()

            @pl.when(jnp.logical_not(held))
            def _():
                v_buf[slot, page_rows(j), :] = jnp.zeros((page_size, v_buf.shape[-1]), v_buf.dtype)

    def next_block(bb, ii):
        """Global-order successor of block (bb, ii): the sequence's next
        block, else the next sequence's first visited block. bb may walk past the last
        sequence — start_ahead guards on bb < batch before dereferencing."""
        advance = ii + 1 >= end_of(jnp.minimum(bb, batch - 1))
        nb = jnp.where(advance, bb + 1, bb)
        ni = jnp.where(advance, lo_of(jnp.minimum(bb + 1, batch - 1)), ii + 1)
        return nb, ni

    def start_ahead(slot, bb, ii):
        @pl.when(bb < batch)
        def _():
            start_block(slot, bb, ii)

    # The very first grid step primes ring slots 0..depth-2; every later
    # block is started depth-1 blocks ahead of its consumption by the body
    # that consumes block g - depth + 1 (empty splits consume no global
    # indices, so the lookahead chain passes through them untouched).
    @pl.when(jnp.logical_and(b == 0, sp == 0))
    def _():
        bb, ii = jnp.int32(0), jnp.int32(0) + lo_of(0)
        for g in range(dma_depth - 1):
            start_ahead(g % dma_depth, bb, ii)
            bb, ii = next_block(bb, ii)

    r_rows, width = q_ref.shape
    # Keep matmul operands in the cache dtype (bf16): the MXU multiplies
    # bf16 natively with f32 accumulation — an f32 formulation costs multiple
    # MXU passes AND a whole-block VPU astype per K/V block, which measured
    # ~3x slower than HBM DMA on v5e (the kernel must stay DMA-bound).
    q_bd = q_ref[...]  # [R, W] block-diagonal, pre-scaled, cache dtype

    # Row r scores query token r // n_heads: its causal horizon is that
    # token's own absolute position (per-row mask — exact for gappy verify
    # layouts; for t_q == 1 identical to the plain kpos < length mask).
    row_t = jax.lax.broadcasted_iota(jnp.int32, (r_rows, 1), 0) // n_heads
    qpos = jnp.zeros((r_rows, 1), jnp.int32)
    for tt in range(t_q):
        qpos = jnp.where(row_t == tt, qpos_ref[b * t_q + tt], qpos)
    if windowed:
        # The newest key a query does NOT see. A real query's window starts
        # at or past the row's first held page; a padding column (position 0)
        # would reach under it, to pages this walk never copied.
        under = jnp.maximum(qpos - window, head_ref[b] * page_size - 1)

    def body(i, carry):
        m, l, acc = carry
        ii = first + i  # block-in-sequence index
        g = g0 + i  # global block index
        slot = g % dma_depth
        # Start the block depth-1 ahead in the global walk; its ring slot's
        # previous occupant (block g - 1) was consumed last iteration.
        bb, nxt = b, ii
        for _ in range(dma_depth - 1):
            bb, nxt = next_block(bb, nxt)
        start_ahead((g + dma_depth - 1) % dma_depth, bb, nxt)

        land_block(slot, b, ii)

        k = k_buf[slot]  # [bk, W] cache dtype
        v = v_buf[slot]
        if k.dtype.itemsize < 2:  # fp8 cache: DMA at 1 B/elem, matmul in bf16
            k = k.astype(jnp.bfloat16)
            v = v.astype(jnp.bfloat16)
        # Block-diagonal q: row (t, h) only overlaps head h's KV strip, so
        # this one contraction is every (token, head)'s logits.
        s = jax.lax.dot_general(
            q_bd, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # f32[R, bk]
        kpos = ii * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos <= qpos  # per-row causal horizon
        if windowed:
            mask = jnp.logical_and(mask, kpos > under)
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))  # [R, 1]
        # Mask p explicitly: in an all-masked block s == m_new == NEG_INF
        # and exp(s - m_new) would be 1, corrupting l/acc. Where any real
        # key exists, where() selects exactly what exp(NEG_INF - m_new)
        # underflows to (0.0) — bit-identical to the unmasked formulation.
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = alpha * acc + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # f32[R, W]; row (t, h)'s answer lives in head h's strip
        return m_new, l_new, acc_new

    m0 = jnp.full((r_rows, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((r_rows, 1), jnp.float32)
    acc0 = jnp.zeros((r_rows, width), jnp.float32)
    m_fin, l_fin, acc_fin = jax.lax.fori_loop(0, nb_here, body, (m0, l0, acc0))
    # Unnormalized partials out; the host-side _lse_combine merges splits.
    # An empty split writes (NEG_INF, 0, 0) — annihilated by the combine.
    acc_ref[...] = acc_fin
    m_ref[...] = jnp.broadcast_to(m_fin, (r_rows, LANES))
    l_ref[...] = jnp.broadcast_to(l_fin, (r_rows, LANES))


def decode_kernel_supported(
    n_heads: int,
    head_dim: int,
    width: int,
    t_q: int = 1,
    *,
    interpret: bool = False,
) -> bool:
    """Pure-shape form of :func:`decode_supported` (no arrays needed —
    the engine's dispatch-path telemetry calls this from host code).

    Hardware requires even GQA grouping and a 128-lane-aligned page slab
    width; interpret mode (CPU tests / dryruns) relaxes only the lane
    alignment — Mosaic's DMA constraint, which the interpreter doesn't
    have. ``t_q`` > 1 (multi-query verify rows) is additionally capped by
    the VMEM row budget (:func:`_max_verify_t`)."""
    if width % head_dim != 0:
        return False
    n_kv = width // head_dim
    if n_heads % n_kv != 0:
        return False
    if not interpret and width % LANES != 0:
        return False
    return t_q <= _max_verify_t(n_heads, width)


def decode_supported(q: jnp.ndarray, k_cache: jnp.ndarray, *, interpret: bool = False) -> bool:
    """Shapes the decode/verify kernel handles for ``q [B, T, H, hd]``
    against the engine's flat page-major cache ``[P, page_size, W]`` with
    ``W = n_kv * head_dim`` (``models/llama.py:init_kv_cache``)."""
    n_heads, head_dim = q.shape[-2], q.shape[-1]
    t_q = q.shape[1] if q.ndim == 4 else 1
    return decode_kernel_supported(
        n_heads, head_dim, k_cache.shape[2], t_q, interpret=interpret
    )


@functools.partial(jax.jit, static_argnames=("scale", "interpret", "num_splits"))
def paged_decode_attention(
    q: jnp.ndarray,  # [B, T_q, n_heads, head_dim] (T_q = 1 decode, K+1 verify)
    k_cache: jnp.ndarray,  # [P, page_size, n_kv * head_dim] (page-major, flat)
    v_cache: jnp.ndarray,
    block_tables: jnp.ndarray,  # i32[B, pages_per_seq]
    positions: jnp.ndarray,  # i32[B, T_q] absolute position of each query token
    *,
    scale: float,
    interpret: bool = False,
    num_splits: int = 0,  # 0 = auto (_auto_num_splits / DYN_DECODE_SPLITS)
    window=None,  # i32 scalar (runtime value): keys older than pos - (window - 1) are not read
) -> jnp.ndarray:
    """Decode/verify paged attention; returns [B, T_q, n_heads, hd].

    ``window`` (None = full causal, today's program unchanged) is a runtime
    scalar, so one compiled program serves layers of different windows under
    a layer scan; a value past every position (``NO_WINDOW``) is full
    attention. A windowed row's block walk starts at the page that holds
    ``first query position - window + 1`` and the in-block mask adds
    ``kpos > position - window``: pages under the window cost no DMA, and
    their table entries are never read.

    Positions may be gappy per row (speculative verify batches, padding
    columns) — causality is per query token. Cache layout matches the
    engine exactly ([P, ps, W] flat slabs), so the layer-stacked cache can
    be passed as-is with per-layer offset tables."""
    b, t_q, n_heads, head_dim = q.shape
    num_pages, page_size, width = k_cache.shape
    n_kv = width // head_dim
    group = n_heads // n_kv
    pages_per_seq = block_tables.shape[1]
    depth = _dma_depth()
    ppb = _pages_per_block(pages_per_seq, page_size, width, k_cache.dtype.itemsize, depth)
    bk = ppb * page_size
    # Static upper bound on a sequence's block walk — split boundaries must
    # NOT depend on runtime lengths (bit-parity between T_q = 1 and verify).
    max_blocks = -(-(pages_per_seq * page_size) // bk)
    splits = num_splits if num_splits > 0 else _auto_num_splits(b, max_blocks)
    splits = max(1, min(splits, max_blocks))
    bps = -(-max_blocks // splits)

    kf, vf = k_cache, v_cache

    # Block-diagonal query staging: row t * n_heads + (kv * G + g) occupies
    # lane strip [kv*hd, (kv+1)*hd). One einsum against eye(n_kv); XLA
    # fuses it. Scale in f32, then store in the cache dtype so the kernel's
    # matmuls run at native MXU bf16 rate.
    q5 = q.astype(jnp.float32) * scale  # [B, T, H, hd]
    eye = jnp.eye(n_kv, dtype=jnp.float32)
    # Queries never drop below bf16 (an fp8 cache quantizes K/V storage, not
    # the live queries).
    q_dtype = k_cache.dtype if k_cache.dtype.itemsize >= 2 else jnp.bfloat16
    r_rows = t_q * n_heads
    q_bd = jnp.einsum(
        "btkgd,kK->btkgKd", q5.reshape(b, t_q, n_kv, group, head_dim), eye
    ).reshape(b, r_rows, width).astype(q_dtype)

    # What the walk counts by is worked out here, once: the kernel's scalar
    # core then does no division and no loop over earlier rows between the
    # copies. The walk covers the row's farthest query token (max, not last:
    # padding columns carry position 0); rows mask their own horizon.
    windowed = window is not None
    walk = decode_walk(positions, page_size, ppb, window)
    prefetch = [walk.last_page, walk.blocks, walk.blocks_before, block_tables.reshape(-1), positions.reshape(-1)]
    if windowed:
        prefetch += [walk.first_page, walk.first_block, jnp.asarray(window, jnp.int32).reshape(1)]

    q_spec = pl.BlockSpec((None, r_rows, width), lambda bb, ss, *_: (bb, 0, 0))
    acc_spec = pl.BlockSpec((None, None, r_rows, width), lambda bb, ss, *_: (bb, ss, 0, 0))
    ml_spec = pl.BlockSpec((None, None, r_rows, LANES), lambda bb, ss, *_: (bb, ss, 0, 0))
    kernel = functools.partial(
        _decode_kernel,
        batch=b,
        pages_per_seq=pages_per_seq,
        pages_per_block=ppb,
        page_size=page_size,
        blocks_per_split=bps,
        t_q=t_q,
        n_heads=n_heads,
        dma_depth=depth,
        windowed=windowed,
    )
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            # the walk's counts, flat block table, query positions (+ the window's head, window)
            num_scalar_prefetch=len(prefetch),
            grid=(b, splits),
            in_specs=[
                q_spec,
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=[acc_spec, ml_spec, ml_spec],
            scratch_shapes=[
                pltpu.VMEM((depth, bk, width), k_cache.dtype),
                pltpu.VMEM((depth, bk, width), v_cache.dtype),
                pltpu.SemaphoreType.DMA((depth,)),
                pltpu.SemaphoreType.DMA((depth,)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, splits, r_rows, width), jnp.float32),
            jax.ShapeDtypeStruct((b, splits, r_rows, LANES), jnp.float32),
            jax.ShapeDtypeStruct((b, splits, r_rows, LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")
        ),
        interpret=interpret,
    )(*prefetch, q_bd, kf, vf)
    out = _lse_combine(acc, m[..., 0], l[..., 0], guard_empty=windowed)  # [B, R, W]
    # Extract each row's diagonal strip: row (t, kv*G+g) reads lanes
    # [kv*hd, (kv+1)*hd). Fused einsum against the same eye.
    o6 = out.reshape(b, t_q, n_kv, group, n_kv, head_dim)
    o = jnp.einsum("btkgKd,kK->btkgd", o6, eye)
    return o.reshape(b, t_q, n_heads, head_dim).astype(q.dtype)


def paged_attention_pallas(
    q: jnp.ndarray,  # [B, T, n_heads, head_dim]
    k_cache: jnp.ndarray,  # [P, page_size, n_kv * head_dim] (flat page-major)
    v_cache: jnp.ndarray,
    block_tables: jnp.ndarray,
    positions: jnp.ndarray,
    *,
    scale: float,
    contiguous_positions: bool = True,
    window=None,  # runtime i32 scalar, None = full causal (see paged_decode_attention)
    chunked: bool = False,  # T == 1 rows of a split chunk step: the prefill kernel, as T > 1
) -> jnp.ndarray:
    """TPU dispatch: decode kernel for T == 1, prefill flash kernel for
    contiguous T > 1 (and for the one-query rows of a chunk step whose token
    axis is split, ``chunked``), the same decode kernel in multi-query form
    for gappy T > 1 (speculative verify), XLA gather formulation as the
    (counted, warned) fallback.

    The prefill kernel requires per-row contiguous positions
    (``positions[b, t] = start_b + t``) — true for every engine prefill,
    chunked or not. A T > 1 caller with gappy per-token positions (a
    speculative-verify batch) must pass ``contiguous_positions=False``:
    that routes to the multi-query decode kernel, whose per-row causal
    mask is exact for any position layout (and to the reference
    formulation only when the shape is outside the kernel's support).
    When ``positions`` is a concrete array (outside jit) the contiguity
    contract is verified for real; under tracing the declaration is
    trusted — it is static routing, a traced check would force compiling
    both kernels behind a cond."""
    if q.shape[1] > 1 and contiguous_positions and not isinstance(
        jnp.asarray(positions), jax.core.Tracer
    ):
        import numpy as np

        def _row_ok(row) -> bool:
            # A valid engine row is a contiguous run starting anywhere,
            # padded with trailing zeros (runner._pad fill) — position 0 can
            # legitimately appear only at the row start. Pure-padding rows
            # are all zeros.
            nz = np.nonzero(row)[0]
            last = int(nz[-1]) if nz.size else 0
            return bool(
                (np.diff(row[: last + 1]) == 1).all() and not row[last + 1:].any()
            )

        pos = np.asarray(positions)
        bad = [i for i in range(pos.shape[0]) if not _row_ok(pos[i])]
        if bad:
            raise ValueError(
                f"paged_attention_pallas: positions are not per-row contiguous "
                f"(rows {bad}); pass contiguous_positions=False for gappy "
                f"layouts (speculative verify, sliding window)"
            )
    interpret = interpret_mode()
    if q.shape[1] == 1 and not chunked:
        if decode_supported(q, k_cache, interpret=interpret):
            return paged_decode_attention(
                q, k_cache, v_cache, block_tables, positions, scale=scale,
                interpret=interpret, window=window,
            )
        _record_fallback("decode", q, k_cache)
    elif not contiguous_positions:
        # Speculative verify: gappy per-row positions, T = K+1 (or the
        # chunk width in a mixed step). The multi-query kernel's per-row
        # mask makes it exact here — the batched verify that used to pay
        # gather-path cost runs on the DMA-pipelined kernel.
        if decode_supported(q, k_cache, interpret=interpret):
            return paged_decode_attention(
                q, k_cache, v_cache, block_tables, positions, scale=scale,
                interpret=interpret, window=window,
            )
        _record_fallback("verify", q, k_cache)
    else:
        from dynamo_tpu.ops.pallas_prefill import (
            paged_prefill_attention,
            prefill_supported,
        )

        if prefill_supported(q, k_cache):
            return paged_prefill_attention(
                q, k_cache, v_cache, block_tables, positions, scale=scale,
                interpret=interpret, window=window,
            )
        _record_fallback("prefill", q, k_cache)
    if window is not None:
        # A windowed call the kernels refuse: counted under its own phase too,
        # as before the kernels took windows.
        _record_fallback("sliding_window", q, k_cache)
    return paged_attention_reference(
        q, k_cache, v_cache, block_tables, positions, scale=scale,
        sliding_window=0 if window is None else window,
    )
