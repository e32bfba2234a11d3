"""Pallas TPU chunked-prefill (flash) attention over the paged KV cache.

Prefill is the TTFT-critical phase: every query token of the chunk attends
causally to the sequence's full paged history (earlier chunks, prefix-cache
hits, or KV migrated from another worker) plus the chunk itself, which the
engine has already scattered into the cache before attention runs
(``models/llama.py:layer_step`` writes K/V first). The XLA reference
formulation (``ops/attention.py:paged_attention_reference``) materializes
the gathered K/V **and** the full ``[B, n_kv, g, T, S]`` f32 logits tensor
in HBM — at ISL 3000 that is hundreds of MB of HBM round-trips per layer.
This kernel is the flash formulation: KV pages stream HBM -> VMEM with
double-buffered async DMA, the T x S score tile lives only in VMEM, and the
online-softmax state (m, l, acc) is the only thing carried.

Design (shares the decode kernel's cache geometry, differs where the
bottleneck differs):

- Cache layout is the engine's flat ``[num_pages, page_size, W]`` with
  ``W = n_kv * head_dim`` — one page is one contiguous DMA slab covering
  all KV heads (see ``ops/pallas_paged.py`` for why this layout).
- Grid is ``(batch, q_blocks)``; each step owns a ``tq``-token query block
  of one sequence. Queries are staged by the caller as
  ``[n_kv, tq * group, head_dim]`` (t-major rows), so each KV head's group
  of query heads is one contiguous row block.
- Per step, a ``fori_loop`` walks the KV page-blocks this query block can
  see (**causal early exit**: the loop bound is
  ``cdiv(min(kv_len, start + (qi+1)*tq), block_tokens)``, so early query
  blocks never touch late pages). DMA is double-buffered within the step:
  block i+1 is in flight while block i is reduced.
- Compute is **per KV head** (a python-unrolled loop over ``n_kv``): head
  group ``kv``'s queries ``[tq*g, hd]`` contract against the slab's lane
  strip ``[bk, kv*hd:(kv+1)*hd]``. Unlike the decode kernel's
  block-diagonal trick (which wastes ``n_kv``x MXU flops — free when
  DMA-bound, not here: prefill attention is MXU-bound at long context),
  this does only the useful flops.
- Causality needs no position tensor in the kernel: prefill chunks are
  contiguous, so query ``row r`` of block ``qi`` has absolute position
  ``start + qi*tq + r // g`` — ``start`` (per-row chunk offset, scalar
  prefetch) is all it takes, and chunked prefill / prefix resumption are
  exact.

Replaces the prefill-phase attention kernels inside vLLM/TRT-LLM that the
reference wraps (SURVEY.md §2 row 30, §7 hard part (a)).

Tests: ``tests/test_pallas_prefill.py`` (interpret mode vs the reference
formulation, incl. chunked continuation); ``tests_tpu/test_on_device.py``
(Mosaic-compiled parity + perf on the real chip).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128


def _block_tokens(page_size: int, width: int) -> int:
    """KV tokens per compute block, budgeted against scoped VMEM (~16 MB):
    the double-buffered K+V slabs cost ``8 * bk * width`` bytes, capped at
    ~4 MB; at most 512 tokens (diminishing DMA-amortization returns)."""
    cap = (4 * 2**20) // (8 * width)
    pages = max(1, min(512, cap) // page_size)
    return pages * page_size


def _tq_for(group: int, t: int, n_kv: int, head_dim: int) -> int:
    """Query-block tokens, budgeted so the per-row VMEM state fits.

    Each score-tile row carries, per KV head, two lane-padded f32 [rows,1]
    softmax stats (~1 KB) plus f32 acc / bf16 q / f32 o strips (~10 bytes
    per head_dim lane); cap the total at ~4 MB, and at 256 rows (score
    tile size)."""
    per_row = n_kv * (1024 + 10 * head_dim)
    rows = max(group, min(256, (4 * 2**20) // per_row))
    tq = max(1, rows // group)
    if tq >= t:
        return t  # whole-array block: Mosaic allows any size
    # Partial blocks need tq * group (the sublane dim) divisible by 8.
    step = 8 // math.gcd(group, 8)
    return min(t, max(step, tq // step * step))


def _prefill_kernel(
    # scalar prefetch (SMEM)
    kv_lens_ref,  # i32[B] attendable keys per row (chunk included; >= 1)
    starts_ref,  # i32[B] absolute position of the row's first query token
    tables_ref,  # i32[B * pages_per_seq]
    *refs,
    # windowed only, one more prefetched scalar first: window_ref i32[1];
    # then, always, the blocked operands and the scratch:
    #   q_ref [n_kv, tq * g, hd] pre-scaled, cache dtype
    #   k_hbm, v_hbm [P, page_size, W] in HBM/ANY
    #   o_ref f32[n_kv, tq * g, hd]
    #   k_buf, v_buf [2, bk, W] VMEM; k_sem, v_sem
    windowed: bool = False,
    tq: int,
    group: int,
    pages_per_seq: int,
    pages_per_block: int,
    page_size: int,
):
    if windowed:
        window_ref, *refs = refs
        window = window_ref[0]
    q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, k_sem, v_sem = refs
    b = pl.program_id(0)
    qi = pl.program_id(1)
    bk = pages_per_block * page_size
    kv_len = jnp.maximum(kv_lens_ref[b], 1)
    start = starts_ref[b]
    # Causal bound: this query block's last token sits at absolute position
    # start + (qi+1)*tq - 1, so no key block past that is ever needed.
    kend = jnp.clip(start + (qi + 1) * tq, 1, kv_len)
    # Rows whose real span is shorter than the batch's T (mixed steps fuse
    # 1-token decode rows with chunk rows; their output past the span is
    # discarded) skip query blocks that hold no real token: the block's
    # first query sits at start + qi*tq, so past kv_len-1 there is nothing
    # to compute — and nothing to DMA (each skipped block saves the full
    # KV walk up to kv_len).
    has_work = start + qi * tq < kv_len
    end_block = pl.cdiv(kend, bk)
    # Windowed: this query block's first token sees no key older than
    # start + qi*tq - window + 1, so the walk begins at that key's block and
    # the blocks wholly under the window are never fetched.
    first_block = jnp.maximum(start + qi * tq - window + 1, 0) // bk if windowed else 0
    num_blocks = jnp.where(has_work, end_block - first_block, 0)
    # Clamp page lookups to the row's own used range (not just the table
    # width) so sentinel-filled table tails can never be dereferenced.
    last_page = jnp.maximum(kv_len - 1, 0) // page_size

    def page_index(i, j):
        idx = jnp.minimum(i * pages_per_block + j, last_page)
        return tables_ref[b * pages_per_seq + idx]

    def start_block(slot, i):
        for j in range(pages_per_block):
            page = page_index(i, j)
            rows = pl.ds(j * page_size, page_size)
            pltpu.make_async_copy(
                k_hbm.at[page], k_buf.at[slot, rows, :], k_sem.at[slot]
            ).start()
            pltpu.make_async_copy(
                v_hbm.at[page], v_buf.at[slot, rows, :], v_sem.at[slot]
            ).start()

    def wait_block(slot, i):
        for j in range(pages_per_block):
            page = page_index(i, j)
            rows = pl.ds(j * page_size, page_size)
            pltpu.make_async_copy(
                k_hbm.at[page], k_buf.at[slot, rows, :], k_sem.at[slot]
            ).wait()
            pltpu.make_async_copy(
                v_hbm.at[page], v_buf.at[slot, rows, :], v_sem.at[slot]
            ).wait()

    # The first DMA must not start for a skipped block: its semaphore would
    # never be waited here and would alias the next grid step's wait.
    @pl.when(num_blocks > 0)
    def _():
        start_block(0, first_block)

    n_kv, rows, hd = q_ref.shape
    q_all = q_ref[...]  # [n_kv, tq*g, hd] pre-scaled, cache dtype
    # Absolute position of each query row (t-major: row r is chunk token
    # r // g), shared by every KV head.
    qpos = (
        start
        + qi * tq
        + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // group
    )  # [rows, 1]

    def body(n, carry):
        # carry: per-KV-head (m [rows,1], l [rows,1], acc [rows,hd]) tuples —
        # a flat pytree, because Mosaic has no scatter for stacked updates.
        cur = n % 2
        i = first_block + n  # block-in-sequence index of the n-th visited block

        @pl.when(n + 1 < num_blocks)
        def _():
            start_block(1 - cur, i + 1)

        wait_block(cur, i)
        k = k_buf[cur]  # [bk, W]
        v = v_buf[cur]
        if k.dtype.itemsize < 2:  # fp8 cache: matmul in bf16
            k = k.astype(jnp.bfloat16)
            v = v.astype(jnp.bfloat16)
        kpos = i * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)  # [1, bk]
        mask = jnp.logical_and(kpos <= qpos, kpos < kv_len)  # [rows, bk]
        if windowed:
            mask = jnp.logical_and(mask, kpos > qpos - window)

        out = []
        for kv in range(n_kv):
            m, l, acc = carry[kv]
            ks = k[:, kv * hd : (kv + 1) * hd]  # [bk, hd] lane strip
            vs = v[:, kv * hd : (kv + 1) * hd]
            s = jax.lax.dot_general(
                q_all[kv], ks, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # f32[rows, bk]
            s = jnp.where(mask, s, NEG_INF)
            mk = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - mk)
            if windowed:
                # A later row of the query block can have the whole first
                # visited block under ITS window (s == mk == NEG_INF there,
                # exp gives 1): select the masked entries out explicitly.
                p = jnp.where(mask, p, 0.0)
            alpha = jnp.exp(m - mk)
            lk = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
            ak = alpha * acc + jax.lax.dot_general(
                p.astype(vs.dtype), vs, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            out.append((mk, lk, ak))
        return tuple(out)

    init = tuple(
        (
            jnp.full((rows, 1), NEG_INF, jnp.float32),
            jnp.zeros((rows, 1), jnp.float32),
            jnp.zeros((rows, hd), jnp.float32),
        )
        for _ in range(n_kv)
    )
    final = jax.lax.fori_loop(0, num_blocks, body, init)
    for kv in range(n_kv):
        _, l, acc = final[kv]
        # Skipped blocks carry l == 0 (no softmax mass): write zeros, not
        # 0/0 NaNs — the caller discards these rows either way, but NaNs
        # must never be produced where a debug check could trip on them.
        o_ref[kv] = jnp.where(l > 0.0, acc / jnp.maximum(l, 1e-30), 0.0)


def prefill_supported(q: jnp.ndarray, k_cache: jnp.ndarray) -> bool:
    """Same geometry contract as the decode kernel (shared predicate): even
    GQA grouping and a 128-lane-aligned page slab width. The decode
    kernel's multi-query T cap does NOT apply — this kernel tiles the
    query axis, so chunk width is unbounded."""
    from dynamo_tpu.ops.pallas_paged import decode_kernel_supported

    return decode_kernel_supported(q.shape[-2], q.shape[-1], k_cache.shape[2])


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_prefill_attention(
    q: jnp.ndarray,  # [B, T, n_heads, head_dim]
    k_cache: jnp.ndarray,  # [P, page_size, n_kv * head_dim]
    v_cache: jnp.ndarray,
    block_tables: jnp.ndarray,  # i32[B, pages_per_seq]
    positions: jnp.ndarray,  # i32[B, T] absolute position of each query token
    *,
    scale: float,
    interpret: bool = False,
    window=None,  # i32 scalar (runtime value); None = full causal, today's program
) -> jnp.ndarray:
    """Prefill-phase (T > 1) paged flash attention; returns [B, T, H, hd].

    ``window``: a query at p attends keys in ``(p - window, p]``; each query
    block's walk starts at the block holding its first token's oldest key.
    A runtime scalar, so a layer scan can carry one per layer
    (``pallas_paged.NO_WINDOW`` = full attention).

    ``positions`` rows must be contiguous (``positions[b, t] = start_b + t``
    for real tokens) — true for every engine prefill row, chunked or not,
    including mid-prompt continuations after a prefix-cache hit (start > 0)
    and the 1-token decode rows a mixed step fuses in (start = kv_len - 1:
    exactly one real query). Batch-padding rows and T-padding tails produce
    zeros/garbage the caller already discards (their logits are never
    gathered); query blocks wholly past a row's real span are skipped in
    the kernel, so short rows don't re-walk their KV history."""
    b, t, n_heads, head_dim = q.shape
    num_pages, page_size, width = k_cache.shape
    n_kv = width // head_dim
    group = n_heads // n_kv
    pages_per_seq = block_tables.shape[1]
    tq = _tq_for(group, t, n_kv, head_dim)
    bk = _block_tokens(page_size, width)
    ppb = bk // page_size
    qb = pl.cdiv(t, tq)

    kv_lens = jnp.max(positions, axis=1) + 1  # i32[B]; padding rows -> 1
    starts = positions[:, 0]

    q_dtype = k_cache.dtype if k_cache.dtype.itemsize >= 2 else jnp.bfloat16
    # Stage queries [B, n_kv, T*g, hd] t-major, pre-scaled, in cache dtype.
    qs = (q.astype(jnp.float32) * scale).reshape(b, t, n_kv, group, head_dim)
    qs = qs.transpose(0, 2, 1, 3, 4).reshape(b, n_kv, t * group, head_dim)
    qs = qs.astype(q_dtype)

    rows = tq * group
    q_spec = pl.BlockSpec(
        (None, n_kv, rows, head_dim), lambda bb, qq, *_: (bb, 0, qq, 0)
    )
    windowed = window is not None
    prefetch = [kv_lens, starts, block_tables.reshape(-1)]
    if windowed:
        prefetch.append(jnp.asarray(window, jnp.int32).reshape(1))
    kernel = functools.partial(
        _prefill_kernel,
        windowed=windowed,
        tq=tq,
        group=group,
        pages_per_seq=pages_per_seq,
        pages_per_block=ppb,
        page_size=page_size,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),  # kv_lens, starts, flat block table (+ window)
            grid=(b, qb),
            in_specs=[
                q_spec,
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((2, bk, width), k_cache.dtype),
                pltpu.VMEM((2, bk, width), v_cache.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, n_kv, t * group, head_dim), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")
        ),
        interpret=interpret,
    )(*prefetch, qs, k_cache, v_cache)
    o = out.reshape(b, n_kv, t, group, head_dim).transpose(0, 2, 1, 3, 4)
    return o.reshape(b, t, n_heads, head_dim).astype(q.dtype)
