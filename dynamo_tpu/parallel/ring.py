"""Ring attention: sequence-parallel exact attention over the ``sp`` mesh axis.

For prompts too long for one chip's HBM/FLOPs, the sequence axis is sharded
across ``sp`` devices. Each device keeps its local Q shard and streams every
K/V shard through the ring: at step s it attends its Q against the K/V chunk
currently resident, folds the result into an online-softmax accumulator
(numerically identical to full attention), then rotates K/V to the next
device with ``ppermute`` over ICI. Compute and communication overlap; memory
per device stays O(T/sp).

The reference has no sequence/context parallelism at all (SURVEY.md §5 —
engines own attention and long context is handled by KV offload); this module
is a TPU-first capability addition per the build plan (§7 step 6).

Causality is handled by global position masking, so it composes with paged
prefill: pass the absolute positions of the Q and KV shards.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

NEG_INF = -1e30


def _chunk_attention(q, k, v, q_pos, kv_pos, scale):
    """Partial attention of q against one K/V chunk: returns (acc, m, l).

    K/V arrive with their native (possibly grouped) head count and are
    expanded here, locally — the ring rotates the compact GQA shards, not the
    query-head-inflated copies.

    acc: unnormalized weighted values [B, Tq, H, hd] (f32)
    m:   running max logit [B, H, Tq]
    l:   running sum of exp [B, H, Tq]
    """
    h, hkv = q.shape[2], k.shape[2]
    if h != hkv:
        k = jnp.repeat(k, h // hkv, axis=2)
        v = jnp.repeat(v, h // hkv, axis=2)
    logits = jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32) * scale, k.astype(jnp.float32))
    mask = kv_pos[:, None, None, :] <= q_pos[:, None, :, None]  # [B, 1, Tq, Ts]
    logits = jnp.where(mask, logits, NEG_INF)
    m = jnp.max(logits, axis=-1)  # [B, H, Tq]
    p = jnp.exp(logits - m[..., None])
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bhts,bshd->bthd", p, v.astype(jnp.float32))
    return acc, m, l


def _merge(acc1, m1, l1, acc2, m2, l2):
    """Merge two online-softmax partials."""
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)
    a2 = jnp.exp(m2 - m)
    acc = acc1 * a1.transpose(0, 2, 1)[..., None] + acc2 * a2.transpose(0, 2, 1)[..., None]
    l = l1 * a1 + l2 * a2
    return acc, m, l


def ring_attention_sharded(q, k, v, q_pos, kv_pos, *, axis_name: str, scale: float,
                           vary_axes: tuple[str, ...] | None = None):
    """Body to run under shard_map: local shards, full-sequence semantics.

    q:      [B, Tq_local, H, hd]      (local Q shard)
    k, v:   [B, Ts_local, Hkv, hd]    (local K/V shard, rotates around the ring)
    q_pos:  [B, Tq_local] global positions of the local Q shard
    kv_pos: [B, Ts_local] global positions of the local K/V shard (rotates too)
    """
    n = jax.lax.psum(1, axis_name)
    b, tq, h, _ = q.shape
    hd_v = v.shape[-1]  # may differ from q/k (MLA: value = latent, k = latent+rope)

    # pcast-to-varying: mark the fresh accumulators as varying over every
    # mapped axis (the ring axis, plus dp when the batch dim is sharded
    # through the shard_map) so the fori_loop carry type matches the
    # (device-varying) merged partials.
    vary = tuple(vary_axes) if vary_axes else (axis_name,)
    acc = jax.lax.pcast(jnp.zeros((b, tq, h, hd_v), jnp.float32), vary, to="varying")
    m = jax.lax.pcast(jnp.full((b, h, tq), NEG_INF, jnp.float32), vary, to="varying")
    l = jax.lax.pcast(jnp.zeros((b, h, tq), jnp.float32), vary, to="varying")

    def ring_step(i, carry):
        acc, m, l, k_cur, v_cur, kv_pos_cur = carry
        a2, m2, l2 = _chunk_attention(q, k_cur, v_cur, q_pos, kv_pos_cur, scale)
        acc, m, l = _merge(acc, m, l, a2, m2, l2)
        # Rotate K/V (and their positions) one step around the ring.
        perm = [(j, (j + 1) % n) for j in range(n)]
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        p_nxt = jax.lax.ppermute(kv_pos_cur, axis_name, perm)
        return acc, m, l, k_nxt, v_nxt, p_nxt

    acc, m, l, _, _, _ = jax.lax.fori_loop(
        0, n, ring_step, (acc, m, l, k, v, kv_pos)
    )
    out = acc / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ring_attention(
    q: jnp.ndarray,  # [B, T, H, hd] full sequence (host view)
    k: jnp.ndarray,  # [B, T, Hkv, hd]
    v: jnp.ndarray,
    positions: jnp.ndarray,  # [B, T] global positions
    mesh: Mesh,
    *,
    axis_name: str = "sp",
    scale: float | None = None,
) -> jnp.ndarray:
    """Causal exact attention with the sequence sharded over ``axis_name``.

    T must divide evenly by the axis size. Returns [B, T, H, hd].
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    # Keep the batch dim dp-sharded through the ring: the engine's step
    # inputs arrive P("dp", ...), and replicating batch here (P(None, sp))
    # forces an SPMD involuntary full rematerialization of every ring input
    # at the prefill boundary (a real collective on ICI). The ring's own
    # collectives ride only ``axis_name``; dp stays pure data parallel.
    batch_axis = "dp" if "dp" in mesh.axis_names else None
    seq_spec = P(batch_axis, axis_name, None, None)
    pos_spec = P(batch_axis, axis_name)

    body = functools.partial(
        ring_attention_sharded, axis_name=axis_name, scale=scale,
        vary_axes=(axis_name,) + ((batch_axis,) if batch_axis else ()),
    )
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(seq_spec, seq_spec, seq_spec, pos_spec, pos_spec),
        out_specs=seq_spec,
    )
    return fn(q, k, v, positions, positions)
