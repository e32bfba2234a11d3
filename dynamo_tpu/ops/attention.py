"""Paged attention over a block-table KV cache.

One op serves both phases: prefill is the ``T > 1`` case, decode the ``T = 1``
case, and prefix-cache reuse / chunked prefill fall out naturally because
queries always attend to the *paged* cache (which may hold tokens computed by
an earlier chunk, an earlier turn, or a different worker after KV migration)
rather than to an in-flight contiguous K/V tensor.

Layout (per layer): ``k_cache, v_cache: [num_pages, page_size, W]`` with
``W = n_kv * head_dim`` — **page-major, heads flattened into lanes**: one
page is one contiguous ``page_size x W`` slab covering every KV head. This
is the native layout of the Pallas decode kernel (``pallas_paged.py``): a
single large DMA per page (all heads at once) instead of one small DMA per
(head, page), a 128-lane-aligned padding-free TPU tiling even for head_dim
64, and no relayout copies anywhere on the hot path (per-head views are
reshapes of gathered intermediates only). A sequence's pages are
listed in its row of ``block_tables: i32[B, pages_per_seq]``; absolute token
position ``p`` lives at page ``block_tables[b, p // page_size]``, offset
``p % page_size``. Page 0 is a reserved null page: padding writes land there
and it is never allocated to a sequence.

Two implementations:

- :func:`paged_attention_reference` — pure-JAX gather formulation. Materializes
  the gathered K/V ``[B, S, n_kv, hd]`` per layer; fine for CPU CI and small
  contexts, memory-bound for long ones.
- a Pallas TPU kernel (``dynamo_tpu.ops.pallas_paged``) that streams pages
  from HBM into VMEM through an N-deep DMA ring and never materializes the
  gather. The kernel runs T = 1 decode, gappy T > 1 speculative-verify rows
  (multi-query block-diagonal form), and split-K sequence partitioning for
  low-batch long-context decode (selected automatically on TPU backends;
  see that module and ``docs/KERNELS.md``).

Reference capability being replaced: the paged-attention kernels inside vLLM /
TRT-LLM that the reference wraps (SURVEY.md §2 row 30, §7 hard part (a)).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

NEG_INF = -1e30  # large-but-finite: avoids NaN from (-inf) - (-inf) in masked softmax


def is_windowed(sliding_window) -> bool:
    """0 (or less) = full causal; a positive int or a traced i32 scalar bounds the keys."""
    return not isinstance(sliding_window, int) or sliding_window > 0


def gather_pages(cache: jnp.ndarray, block_tables: jnp.ndarray, n_kv: int) -> jnp.ndarray:
    """Gather per-sequence K or V: [pages, ps, W] x [B, N] -> [B, N*ps, kv, hd].

    The per-head split is a reshape of the *gathered* intermediate (layout
    chosen by XLA, fusable) — never of the cache itself.
    """
    b, n = block_tables.shape
    _, ps, w = cache.shape
    gathered = cache[block_tables.reshape(-1)]  # [B*N, ps, W]
    return gathered.reshape(b, n * ps, n_kv, w // n_kv)


def paged_attention_reference(
    q: jnp.ndarray,  # [B, T, n_heads, head_dim]
    k_cache: jnp.ndarray,  # [num_pages, page_size, n_kv * head_dim]
    v_cache: jnp.ndarray,
    block_tables: jnp.ndarray,  # i32[B, pages_per_seq]
    positions: jnp.ndarray,  # i32[B, T] absolute position of each query token
    *,
    scale: float | None = None,
    sliding_window=0,  # >0: keys older than q_pos - (w-1) are masked (an int, or a traced i32 scalar)
) -> jnp.ndarray:
    """Causal paged attention; returns [B, T, n_heads, head_dim].

    Key absolute position within a sequence is its index in the gathered page
    order; causal masking is ``key_pos <= query_pos``. Padding query rows
    produce garbage that callers discard (their logits are never gathered).
    """
    b, t, n_heads, head_dim = q.shape
    n_kv = k_cache.shape[2] // head_dim
    group = n_heads // n_kv
    if scale is None:
        scale = head_dim**-0.5

    k = gather_pages(k_cache, block_tables, n_kv)  # [B, S, n_kv, hd]
    v = gather_pages(v_cache, block_tables, n_kv)
    s = k.shape[1]
    if k.dtype.itemsize < 2:  # fp8 KV cache: matmuls run in the query dtype
        k = k.astype(q.dtype)
        v = v.astype(q.dtype)

    # GQA-native: fold query heads as [kv, group] and contract against the
    # un-repeated KV — no G-times materialization, f32 only as the einsum
    # accumulation type (no f32 copies of the gathered cache).
    qg = (q * scale).astype(q.dtype).reshape(b, t, n_kv, group, head_dim)
    logits = jnp.einsum("btkgd,bskd->bkgts", qg, k, preferred_element_type=jnp.float32)
    key_pos = jnp.arange(s, dtype=jnp.int32)
    mask = key_pos[None, None, :] <= positions[:, :, None]  # [B, T, S]
    if is_windowed(sliding_window):
        # HF window semantics: a query at p attends to keys in
        # [p - (w - 1), p] — the page pool still HOLDS older pages (parity
        # with vLLM's non-rolled paged SWA); masking alone preserves exact
        # logits. Out-of-window page reclamation is an allocator policy on
        # top, not an attention change.
        mask = mask & (key_pos[None, None, :] > positions[:, :, None] - sliding_window)
    logits = jnp.where(mask[:, None, None, :, :], logits, NEG_INF)
    weights = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgts,bskd->btkgd", weights.astype(v.dtype), v, preferred_element_type=jnp.float32)
    return out.reshape(b, t, n_heads, head_dim).astype(q.dtype)


def write_kv(
    k_cache: jnp.ndarray,  # [num_pages, page_size, n_kv * head_dim]
    v_cache: jnp.ndarray,
    new_k: jnp.ndarray,  # [B, T, n_kv, head_dim]
    new_v: jnp.ndarray,
    slot_mapping: jnp.ndarray,  # i32[B, T] flat slot = page_id * page_size + offset (0 for padding)
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Scatter new K/V into the paged cache; returns the updated cache arrays.

    Under jit with donated cache buffers this lowers to an in-place scatter.
    Padding tokens carry slot 0 (the null page) — harmless overlapping writes.
    Page-major layout makes this a plain row scatter: flat token slot indexes
    the leading [pages * ps] axis directly; the head flatten touches only the
    small new_k/new_v activations.
    """
    num_pages, page_size, w = k_cache.shape
    flat_shape = (num_pages * page_size, w)
    slots = slot_mapping.reshape(-1)
    nk = new_k.reshape(-1, w).astype(k_cache.dtype)  # [B*T, W]
    nv = new_v.reshape(-1, w).astype(v_cache.dtype)
    kf = k_cache.reshape(flat_shape).at[slots].set(nk)
    vf = v_cache.reshape(flat_shape).at[slots].set(nv)
    return kf.reshape(k_cache.shape), vf.reshape(v_cache.shape)


@functools.partial(jax.jit, static_argnames=("scale", "impl"))
def _dispatch(q, k_cache, v_cache, block_tables, positions, scale, impl):
    if impl == "pallas":
        from dynamo_tpu.ops.pallas_paged import paged_attention_pallas

        return paged_attention_pallas(q, k_cache, v_cache, block_tables, positions, scale=scale)
    return paged_attention_reference(q, k_cache, v_cache, block_tables, positions, scale=scale)


def default_impl() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "reference"


def paged_attention(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    block_tables: jnp.ndarray,
    positions: jnp.ndarray,
    *,
    scale: float | None = None,
    impl: str | None = None,
    contiguous_positions: bool = True,
    sliding_window=0,
    chunked: bool = False,
) -> jnp.ndarray:
    """Backend-dispatching paged attention (see module docstring).

    ``chunked`` says these rows belong to a chunk step whose token axis is
    split (``models/llama.forward``'s ``split``): its one-query rows take the
    chunked-prefill kernel too (``start = kv_len - 1``), not the decode kernel.

    ``sliding_window``: 0 = full causal; a positive int, or a traced i32
    scalar (a layer scan that carries one window per layer, with
    ``pallas_paged.NO_WINDOW`` for its full layers), bounds each query to its
    last ``sliding_window`` positions. Windowed calls take the same kernels
    under the same support predicates as full ones; the kernels skip the page
    blocks wholly under the window.

    ``contiguous_positions`` declares that every real row of ``positions``
    steps by exactly 1 (engine prefill, chunked or not). Callers with gappy
    per-row positions — speculative verify, sliding window — MUST pass
    False: the T > 1 Pallas prefill kernel derives its causal mask and KV
    lengths from row start/end only and silently computes wrong attention
    on gappy layouts. False routes T > 1 to the multi-query decode kernel
    instead, whose per-row causal mask is exact for any layout (reference
    formulation only when the shape is outside kernel support — counted
    under the ``verify`` fallback phase)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if impl is None:
        impl = default_impl()
    if impl == "reference":
        return paged_attention_reference(
            q, k_cache, v_cache, block_tables, positions, scale=scale,
            sliding_window=sliding_window,
        )
    from dynamo_tpu.ops.pallas_paged import paged_attention_pallas

    return paged_attention_pallas(
        q, k_cache, v_cache, block_tables, positions, scale=scale,
        contiguous_positions=contiguous_positions,
        window=sliding_window if is_windowed(sliding_window) else None,
        chunked=chunked,
    )


def paged_attention_sharded(
    q: jnp.ndarray,  # [B, T, n_heads, head_dim]
    k_cache: jnp.ndarray,  # [P, page_size, n_kv * head_dim]
    v_cache: jnp.ndarray,
    block_tables: jnp.ndarray,
    positions: jnp.ndarray,
    *,
    mesh,
    scale: float | None = None,
    impl: str | None = None,
    contiguous_positions: bool = True,
    sliding_window=0,
) -> jnp.ndarray:
    """Paged attention under a device mesh: tp shards heads, dp the batch.

    GSPMD cannot partition a ``pallas_call`` — left alone it replicates the
    operands (an all-gather of the whole KV cache) and runs the full kernel
    per device. This wrapper makes the production tp layout explicit with
    ``shard_map``: each device runs the kernel on its KV-head slice of the
    cache (``W_local = n_kv/tp * head_dim`` lanes) and its dp slice of the
    batch; no collectives anywhere — heads are embarrassingly parallel in
    attention, and the GQA q-head group moves with its KV head.

    Kernel-support predicates apply to the LOCAL shapes: pick tp so
    ``(n_kv/tp) * head_dim`` stays a multiple of 128 lanes.

    Reference counterpart: vLLM's paged kernels under tensor parallelism
    (SURVEY.md §7 hard parts (a)+(b) combined).
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    batch_axis = "dp" if "dp" in mesh.axis_names else None
    tp_axis = "tp" if "tp" in mesh.axis_names else None
    from jax.sharding import PartitionSpec as P

    q_spec = P(batch_axis, None, tp_axis, None)
    cache_spec = P(None, None, tp_axis)
    row_spec = P(batch_axis, None)

    # A window rides in as a replicated operand: under a layer scan it is a
    # traced per-layer scalar, which a closure over shard_map may not capture.
    extra = (jnp.asarray(sliding_window, jnp.int32).reshape(1),) if is_windowed(sliding_window) else ()

    def body(q, kc, vc, bt, pos, *w):
        return paged_attention(q, kc, vc, bt, pos, scale=scale, impl=impl,
                               contiguous_positions=contiguous_positions,
                               sliding_window=w[0][0] if w else 0)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(q_spec, cache_spec, cache_spec, row_spec, row_spec) + (P(None),) * len(extra),
        out_specs=q_spec,
        # pallas_call's out_shape carries no vma metadata; the body has no
        # cross-device communication to check anyway (heads/batch are
        # embarrassingly parallel here).
        check_vma=False,
    )(q, k_cache, v_cache, block_tables, positions, *extra)
