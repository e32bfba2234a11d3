"""Pallas TPU decode kernel for MLA (DeepSeek latent attention).

In the absorbed formulation MLA decode IS multi-query attention: every
query head attends to ONE shared K/V stream — key ``[c ; k_rope]``
(latent width r_kv + rope width dr) and value ``c`` — so the paged cache
holds just ``r_kv + dr`` lanes per token (`models/mla.py`). The XLA gather
formulation materializes the gathered latents and reads them three times
per step (gather write, score einsum, output einsum). This
kernel streams each page from HBM exactly once — an N-deep DMA ring,
online softmax, accumulation in latent space — the same split-K,
multi-query structure as the GQA decode kernel (`pallas_paged.py`, whose
helpers it shares; see ``docs/KERNELS.md``), with two differences:

- TWO key streams per block: scores are ``q_lat @ c^T + q_rope @ r^T``
  (the rope part is a narrow 128-lane contraction riding the same DMA wave).
- The value IS the latent: ``acc += p @ c`` — no separate V stream at all,
  so HBM traffic per token is r_kv + dr bytes where GQA pays 2 * H_kv * hd.

A row's walk copies only the pages the row holds: its last block, where the
row holds fewer pages than a block has, starts and waits on those pages alone
and contracts over them in a branch compiled for that count
(``docs/KERNELS.md``, "The walk's tail").

Because MLA is already MQA, multi-query verify rows need no block-diagonal
staging: T_q query tokens per sequence are a plain ``[T_q * n_heads, r_kv]``
row stack, each row masked to its own token's causal horizon — speculative
verify batches run on this kernel instead of the gather formulation.

Reference counterpart: none — the reference outsources kernels to
vLLM/TRT-LLM (SURVEY.md §2 row 30); this is the TPU-native equivalent of
their MLA/MQA decode kernels (flash-MLA class).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.pallas_paged import (  # shared kernel helpers
    _auto_num_splits,
    _dma_depth,
    _lse_combine,
    _max_verify_t,
    _pages_per_block,
    interpret_mode,  # noqa: F401  (re-exported: models/mla.py imports it here)
)

NEG_INF = -1e30
LANES = 128


def mla_decode_supported(
    r_kv: int,
    r_width: int,
    t_q: int = 1,
    n_heads: int = 1,
    *,
    interpret: bool = False,
) -> bool:
    """Geometry the kernel handles: both streams lane-aligned (the rope
    stream is pre-padded to a 128-lane tile by ``mla_cache_widths`` —
    Mosaic cannot DMA sub-tile HBM slices). Interpret mode (CPU tests /
    dryruns) relaxes only the lane alignment. ``t_q`` > 1 (multi-query
    verify rows) is capped by the VMEM row budget."""
    if not interpret and (r_kv % LANES != 0 or r_width % LANES != 0):
        return False
    return t_q <= _max_verify_t(max(1, n_heads), r_kv + r_width)


def _mla_decode_kernel(
    # scalar prefetch (SMEM)
    pages_ref,  # i32[B] pages the row's walk holds (its farthest token's, >= 1)
    blocks_ref,  # i32[B] blocks the row's walk visits
    first_ref,  # i32[B] blocks of the rows before: the row's first global block
    tables_ref,  # i32[B * pages_per_seq]
    qpos_ref,  # i32[B * t_q] absolute position of each query token
    # blocked operands
    q_lat_ref,  # [t_q * n_heads, r_kv]  pre-scaled, cache dtype
    q_rope_ref,  # [t_q * n_heads, r_width]
    c_hbm,  # [P, page_size, r_kv] in HBM/ANY
    r_hbm,  # [P, page_size, r_width]
    acc_ref,  # f32[t_q * n_heads, r_kv] — this (b, split)'s partial
    m_ref,  # f32[t_q * n_heads, LANES]
    l_ref,  # f32[t_q * n_heads, LANES]
    # scratch
    c_buf,  # [dma_depth, block_tokens, r_kv] VMEM ring
    r_buf,  # [dma_depth, block_tokens, r_width]
    c_sem,
    r_sem,
    *,
    batch: int,
    pages_per_seq: int,
    pages_per_block: int,
    page_size: int,
    blocks_per_split: int,
    t_q: int,
    n_heads: int,
    dma_depth: int,
):
    b = pl.program_id(0)
    sp = pl.program_id(1)
    bk = pages_per_block * page_size

    nb_total = blocks_ref[b]
    # Static split boundaries (see pallas_paged._decode_kernel): a row's
    # accumulation order never depends on other rows' runtime lengths.
    first = sp * blocks_per_split
    nb_here = jnp.clip(nb_total - first, 0, blocks_per_split)
    g0 = first_ref[b] + jnp.minimum(first, nb_total)

    def held_pages(bb, ii):
        # Pages of row bb's block ii that the row holds: the whole block but
        # in the row's tail. Starts and waits both count by it, so a copy is
        # waited on exactly where it was started.
        return jnp.clip(pages_ref[bb] - ii * pages_per_block, 1, pages_per_block)

    def page_copies(slot, bb, ii, j):
        page = tables_ref[bb * pages_per_seq + ii * pages_per_block + j]
        rows = pl.ds(j * page_size, page_size)
        return (
            pltpu.make_async_copy(c_hbm.at[page], c_buf.at[slot, rows, :], c_sem.at[slot]),
            pltpu.make_async_copy(r_hbm.at[page], r_buf.at[slot, rows, :], r_sem.at[slot]),
        )

    def start_block(slot, bb, ii):
        held = held_pages(bb, ii)
        for j in range(pages_per_block):

            @pl.when(j < held)
            def _():
                for copy in page_copies(slot, bb, ii, j):
                    copy.start()

    def next_block(bb, ii):
        advance = ii + 1 >= blocks_ref[jnp.minimum(bb, batch - 1)]
        nb = jnp.where(advance, bb + 1, bb)
        ni = jnp.where(advance, 0, ii + 1)
        return nb, ni

    def start_ahead(slot, bb, ii):
        @pl.when(bb < batch)
        def _():
            start_block(slot, bb, ii)

    @pl.when(jnp.logical_and(b == 0, sp == 0))
    def _():
        bb, ii = jnp.int32(0), jnp.int32(0)
        for g in range(dma_depth - 1):
            start_ahead(g % dma_depth, bb, ii)
            bb, ii = next_block(bb, ii)

    r_rows, r_kv = q_lat_ref.shape
    q_lat = q_lat_ref[...]
    q_rope = q_rope_ref[...]

    # Row r scores query token r // n_heads against that token's own
    # causal horizon (multi-query verify rows; t_q == 1 reduces to the
    # plain decode mask).
    row_t = jax.lax.broadcasted_iota(jnp.int32, (r_rows, 1), 0) // n_heads
    qpos = jnp.zeros((r_rows, 1), jnp.int32)
    for tt in range(t_q):
        qpos = jnp.where(row_t == tt, qpos_ref[b * t_q + tt], qpos)

    def visit(i):
        """Start the copies of the block ``dma_depth - 1`` ahead of this
        split's block ``i``; return that block's ring slot and index."""
        ii = first + i
        g = g0 + i
        bb, nxt = b, ii
        for _ in range(dma_depth - 1):
            bb, nxt = next_block(bb, nxt)
        start_ahead((g + dma_depth - 1) % dma_depth, bb, nxt)
        return g % dma_depth, ii

    def attend(held, slot, ii, m, l, acc):
        """One online-softmax step over the first ``held`` (static) pages of
        the block in ``slot``: waits on those pages' copies and contracts over
        them alone. Ring rows no copy of this block wrote hold an earlier
        block's latents or nothing yet, and 0 * NaN is NaN."""
        for j in range(held):
            for copy in page_copies(slot, b, ii, j):
                copy.wait()
        tokens = pl.ds(0, held * page_size)
        c = c_buf[slot, tokens, :]  # [held * page_size, r_kv] cache dtype
        r = r_buf[slot, tokens, :]  # [held * page_size, r_width]
        if c.dtype.itemsize < 2:  # fp8 cache: DMA at 1 B/elem, matmul in bf16
            c = c.astype(jnp.bfloat16)
            r = r.astype(jnp.bfloat16)
        # MQA: one shared K stream; scores are the latent contraction plus
        # the narrow rope contraction (both MXU, f32 accumulation).
        s = jax.lax.dot_general(
            q_lat, c, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) + jax.lax.dot_general(
            q_rope, r, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # f32[R, held * page_size]
        kpos = ii * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos <= qpos
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        # Explicit p mask: an all-masked block (possible under per-row
        # horizons) has s == m_new == NEG_INF and exp(0) would corrupt l.
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        # The value IS the latent stream.
        acc_new = alpha * acc + jax.lax.dot_general(
            p.astype(c.dtype), c, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # f32[R, r_kv]
        return m_new, l_new, acc_new

    def full_block(i, carry):
        slot, ii = visit(i)
        return attend(pages_per_block, slot, ii, *carry)

    def put(m, l, acc):
        acc_ref[...] = acc
        m_ref[...] = jnp.broadcast_to(m, (r_rows, LANES))
        l_ref[...] = jnp.broadcast_to(l, (r_rows, LANES))

    # The blocks the row fills go through the loop; its last block, where the
    # row holds fewer pages than a block has, comes after it, in the one
    # branch that is compiled for that count of pages.
    tail_pages = pages_ref[b] - (nb_total - 1) * pages_per_block
    has_tail = (nb_here > 0) & (first + nb_here == nb_total) & (tail_pages < pages_per_block)
    n_full = nb_here - has_tail.astype(jnp.int32)
    m0 = jnp.full((r_rows, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((r_rows, 1), jnp.float32)
    acc0 = jnp.zeros((r_rows, r_kv), jnp.float32)
    put(*jax.lax.fori_loop(0, n_full, full_block, (m0, l0, acc0)))

    @pl.when(has_tail)
    def _():
        slot, ii = visit(n_full)
        for held in range(1, pages_per_block):

            @pl.when(tail_pages == held)
            def _():
                put(*attend(held, slot, ii, m_ref[:, :1], l_ref[:, :1], acc_ref[...]))


@functools.partial(jax.jit, static_argnames=("scale", "interpret", "num_splits"))
def mla_paged_decode(
    q_lat: jnp.ndarray,  # [B, T, n_heads, r_kv] or [B, n_heads, r_kv] (T = 1)
    q_rope: jnp.ndarray,  # [B, T, n_heads, r_width] or [B, n_heads, r_width]
    c_cache: jnp.ndarray,  # [P, page_size, r_kv] latent pages
    r_cache: jnp.ndarray,  # [P, page_size, r_width] rope-key pages
    block_tables: jnp.ndarray,  # i32[B, pages_per_seq]
    positions: jnp.ndarray,  # i32[B, T] absolute position of each query token
    *,
    scale: float,
    interpret: bool = False,
    num_splits: int = 0,  # 0 = auto (DYN_DECODE_SPLITS override)
) -> jnp.ndarray:
    """Paged MLA decode/verify; returns latent-space output
    f32[B, T, n_heads, r_kv] (3D in, 3D out for the T = 1 decode shape;
    callers apply the absorbed W_uv up-projection). Positions may be gappy
    per row — causality is per query token."""
    squeeze = q_lat.ndim == 3
    if squeeze:
        q_lat = q_lat[:, None]
        q_rope = q_rope[:, None]
    b, t_q, n_heads, r_kv = q_lat.shape
    num_pages, page_size, _ = c_cache.shape
    pages_per_seq = block_tables.shape[1]
    r_width = r_cache.shape[2]
    depth = _dma_depth()
    ppb = _pages_per_block(
        pages_per_seq, page_size, r_kv + r_width, c_cache.dtype.itemsize, depth
    )
    bk = ppb * page_size
    max_blocks = -(-(pages_per_seq * page_size) // bk)
    splits = num_splits if num_splits > 0 else _auto_num_splits(b, max_blocks)
    splits = max(1, min(splits, max_blocks))
    bps = -(-max_blocks // splits)

    # Walk covers the row's farthest token; rows mask their own horizon. What
    # the walk counts by is worked out here, once: the kernel's scalar core
    # then does no division and no loop over earlier rows between the copies.
    pages = jnp.maximum(jnp.max(positions, axis=1), 0) // page_size + 1
    blocks = -(-pages // ppb)
    first_block = jnp.cumsum(blocks) - blocks

    q_dtype = c_cache.dtype if c_cache.dtype.itemsize >= 2 else jnp.bfloat16
    r_rows = t_q * n_heads
    q_lat_s = (q_lat.astype(jnp.float32) * scale).astype(q_dtype).reshape(b, r_rows, r_kv)
    q_rope_s = (q_rope.astype(jnp.float32) * scale).astype(q_dtype).reshape(b, r_rows, r_width)

    kernel = functools.partial(
        _mla_decode_kernel,
        batch=b,
        pages_per_seq=pages_per_seq,
        pages_per_block=ppb,
        page_size=page_size,
        blocks_per_split=bps,
        t_q=t_q,
        n_heads=n_heads,
        dma_depth=depth,
    )
    acc_spec = pl.BlockSpec((None, None, r_rows, r_kv), lambda bb, ss, *_: (bb, ss, 0, 0))
    ml_spec = pl.BlockSpec((None, None, r_rows, LANES), lambda bb, ss, *_: (bb, ss, 0, 0))
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(b, splits),
            in_specs=[
                pl.BlockSpec((None, r_rows, r_kv), lambda bb, ss, *_: (bb, 0, 0)),
                pl.BlockSpec((None, r_rows, r_width), lambda bb, ss, *_: (bb, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=[acc_spec, ml_spec, ml_spec],
            scratch_shapes=[
                pltpu.VMEM((depth, bk, r_kv), c_cache.dtype),
                pltpu.VMEM((depth, bk, r_width), r_cache.dtype),
                pltpu.SemaphoreType.DMA((depth,)),
                pltpu.SemaphoreType.DMA((depth,)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, splits, r_rows, r_kv), jnp.float32),
            jax.ShapeDtypeStruct((b, splits, r_rows, LANES), jnp.float32),
            jax.ShapeDtypeStruct((b, splits, r_rows, LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # Each tail branch keeps score temporaries of its own: a chunk's
            # 512 query rows come to 18.3 MiB where the default scope is 16.
            vmem_limit_bytes=32 * 2**20,
        ),
        interpret=interpret,
        name="mla_paged_decode_attention",
    )(
        pages,
        blocks,
        first_block,
        block_tables.reshape(-1),
        positions.reshape(-1),
        q_lat_s,
        q_rope_s,
        c_cache,
        r_cache,
    )
    out = _lse_combine(acc, m[..., 0], l[..., 0])  # [B, R, r_kv]
    out = out.reshape(b, t_q, n_heads, r_kv)
    return out[:, 0] if squeeze else out


def mla_paged_decode_sharded(
    q_lat: jnp.ndarray,  # [B, T, n_heads, r_kv] or [B, n_heads, r_kv]
    q_rope: jnp.ndarray,
    c_cache: jnp.ndarray,
    r_cache: jnp.ndarray,
    block_tables: jnp.ndarray,
    positions: jnp.ndarray,
    *,
    mesh,
    scale: float,
    interpret: bool = False,
    num_splits: int = 0,
) -> jnp.ndarray:
    """MLA decode kernel under a device mesh: tp shards the QUERY heads,
    dp the batch; the latent/rope caches are replicated (MQA — every head
    reads the same stream; `parallel/sharding.cache_shardings` places the
    MLA cache replicated for exactly this reason). No collectives inside:
    each device streams the full cache once for its head slice — the same
    total HBM traffic as single-chip, split across chips' own HBM copies."""
    from jax.sharding import PartitionSpec as P

    batch_axis = "dp" if "dp" in mesh.axis_names else None
    tp_axis = "tp" if "tp" in mesh.axis_names else None
    if q_lat.ndim == 4:  # multi-query verify rows: heads on axis 2
        q_spec = P(batch_axis, None, tp_axis, None)
    else:
        q_spec = P(batch_axis, tp_axis, None)
    row_spec = P(batch_axis, None)

    def body(ql, qr, cc, rc, bt, pos):
        return mla_paged_decode(
            ql, qr, cc, rc, bt, pos, scale=scale, interpret=interpret,
            num_splits=num_splits,
        )

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(q_spec, q_spec, P(), P(), row_spec, row_spec),
        out_specs=q_spec,
        check_vma=False,  # pallas out_shape carries no vma metadata
    )(q_lat, q_rope, c_cache, r_cache, block_tables, positions)
