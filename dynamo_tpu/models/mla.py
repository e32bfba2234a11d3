"""Multi-head Latent Attention (DeepSeek-V2/V3) on the paged cache.

MLA caches a single shared **latent** per token — the KV-compressed vector
``c`` (kv_lora_rank wide) plus a decoupled rope key — instead of per-head
K/V. Per-token cache cost drops from ``2 * n_kv * head_dim`` to
``kv_lora_rank + rope_dim`` (e.g. V3: 576 values vs 32k for an equivalent
MHA), which is the architecture's whole point for long-context serving.

Implementation is the **absorbed** formulation: the per-head up-projections
``W_uk``/``W_uv`` never materialize per-head K/V. Queries are projected into
latent space (``q_nope @ W_uk``) so attention scores and the weighted sum
run directly against the cached latents; ``W_uv`` applies once to the
attention output. Prefill and decode share the path (same trick as the
dense forward), so chunked prefill/prefix reuse work unchanged.

Paged-cache mapping — no engine changes needed:

- ``k_cache`` stores the latents (width ``kv_lora_rank``)
- ``v_cache`` stores the rope keys (width ``qk_rope_head_dim``)

Both are ordinary ``[L, pages, page_size, W]`` arrays, so the allocator,
prefix cache, tier offload, and disagg transfer treat MLA pages exactly
like GQA pages. Attention streams pages through the Pallas MLA kernel
(``ops/pallas_mla.py``): a decode row as one query, a chunk's queries in
tiles within the kernel's row cap (``_query_tile``), each page read once per
tile. Geometries the kernel does not take use the gather
formulation. The 2D projections (w_kv_a, w_q*, wo_mla) are int8-quantizable
like every other matmul weight.

A family may scale the query and the KV latent after their latent norms
(``cfg.mla_scale_q`` / ``cfg.mla_scale_kv``, LongCat-Flash): the scaled
latent is what the cache holds.

Parity: the MLA serving capability the reference gets from SGLang/vLLM's
DeepSeek support (`examples/sglang`, BASELINE config #4).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.quant import held_flat, quant_matmul as _qmm
from dynamo_tpu.ops.norm import rms_norm
from dynamo_tpu.ops.rope import apply_rope

NEG_INF = -1e30

Params = dict


def init_mla_params(cfg: ModelConfig, key: jax.Array, dt, num_layers: int) -> dict[str, jnp.ndarray]:
    """MLA attention leaves, layers stacked on the leading axis."""
    d = cfg.hidden_size
    h = cfg.num_heads
    l = num_layers
    r_q, r_kv = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    keys = jax.random.split(key, 6)

    def w(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32) * (fan_in**-0.5)).astype(dt)

    leaves = {
        # x -> compressed kv latent + decoupled rope key (shared, 1 "head")
        "w_kv_a": w(keys[0], (l, d, r_kv + dr), d),
        "kv_norm": jnp.ones((l, r_kv), dt),
        # latent -> per-head K_nope / V
        "w_uk": w(keys[1], (l, r_kv, h, dn), r_kv),
        "w_uv": w(keys[2], (l, r_kv, h, dv), r_kv),
        "wo_mla": w(keys[3], (l, h * dv, d), h * dv),
    }
    if r_q > 0:
        leaves["w_q_a"] = w(keys[4], (l, d, r_q), d)
        leaves["q_norm"] = jnp.ones((l, r_q), dt)
        leaves["w_q_b"] = w(keys[5], (l, r_q, h * (dn + dr)), r_q)
    else:
        leaves["w_q"] = w(keys[4], (l, d, h * (dn + dr)), d)
    return leaves


#: The per-head up-projections as published (``[L, r_kv, H, d]``) and the einsum
#: each takes there; heads-major (``lay_heads_major``) a leaf is ``<name>_h``.
_UP = {"w_uk": "bthn,rhn->bthr", "w_uv": "bthr,rhv->bthv"}


def lay_heads_major(params: dict) -> dict:
    """``params`` with every latent layer stack's ``w_uk`` / ``w_uv`` (``[L, r_kv,
    H, d]``, layers leading) replaced by ``w_uk_h`` ``[L, H, dn, r_kv]`` / ``w_uv_h`` ``[L, H, r_kv,
    dv]``: a head's slab contiguous, the contracted dimension second to last.

    Heads are the batch dimension of both absorbed contractions. Published, the
    chip tiles ``(H, d)``, a head's slab is one sublane of every tile, and the
    compiler re-lays the layer's whole slice of the stack into VMEM before the
    dot: two serial operations a weight, 36 us a layer for two reads of 5
    (PERF.md, PR 41). Heads-major the slice fuses into the dot and the weight is
    read once, where it lies. Called once by an unsharded runner on the tree it
    takes; loaders, ``init_mla_params`` and ``parallel/sharding.py`` (the leaves
    shard by head under a mesh) keep the published lay-out."""
    if not isinstance(params, dict):
        return params
    out = {k: lay_heads_major(v) for k, v in params.items() if k not in _UP}
    if "w_uk" in params:
        out["w_uk_h"] = jnp.transpose(params["w_uk"], (0, 2, 3, 1))
        out["w_uv_h"] = jnp.transpose(params["w_uv"], (0, 2, 1, 3))
    return out


def up_project(lp: Params, name: str, x: jnp.ndarray) -> jnp.ndarray:
    """``x [B, T, H, d_in]`` through the per-head up-projection ``name``."""
    if name + "_h" in lp:
        return jnp.einsum("bthi,hio->btho", x, lp[name + "_h"])
    return jnp.einsum(_UP[name], x, lp[name])


def mla_cache_widths(cfg: ModelConfig) -> tuple[int, int]:
    """(k_cache width, v_cache width): latents and rope keys.

    The rope stream is padded up to one 128-lane tile: Mosaic cannot DMA a
    sub-tile HBM slice (the decode kernel streams [page_size, width] slabs),
    and a 64-wide array would be tile-padded by the compiler anyway — the
    pad makes the physical layout explicit instead of unaddressable.
    Readers slice [..., :qk_rope_head_dim]; writers zero-fill."""
    return cfg.kv_lora_rank, max(cfg.qk_rope_head_dim, 128)


def mla_attention(
    lp: Params,
    cfg: ModelConfig,
    h: jnp.ndarray,  # [B, T, D] normed input
    positions: jnp.ndarray,  # i32[B, T]
    c_cache: jnp.ndarray,  # [P, ps, r_kv]  (the layer's k_cache slice view)
    r_cache: jnp.ndarray,  # [P, ps, dr]    (the layer's v_cache slice view)
    block_tables: jnp.ndarray,  # i32[B, pages_per_seq]
    slot_mapping: jnp.ndarray,  # i32[B, T]
    inv_freq: jnp.ndarray,  # [qk_rope_head_dim // 2] (rope-dim frequencies)
    attn_mscale: float = 1.0,  # YaRN temperature (mscale^2), applied to logits
    ring: bool = False,  # sequence-parallel ring over mesh's sp axis
    mesh=None,  # required when ring
    ring_positions: jnp.ndarray | None = None,  # [B, T] padding-hidden positions
    impl: str | None = None,  # "pallas" enables the MLA decode/verify kernel
    contiguous_positions: bool = True,  # False: gappy rows (speculative verify)
    split: tuple[int, int, int] | None = None,  # (decode slots, chunk slots, tokens per chunk slot)
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One MLA layer: returns (attn_out [B,T,D], c_cache, r_cache).

    ``split = (nd, nc, tc)``: ``h`` is one token axis ``[1, nd + nc * tc, D]``
    (``llama.forward``'s ``split``) and ``block_tables`` has a row per slot.
    Projections and the cache write are per token; attention alone sees rows,
    the decode slots as ``[nd, 1]`` and the chunk slots as ``[nc, tc]``.

    ``ring=True`` runs the sp-sharded ring path for whole-prompt prefills:
    in the absorbed formulation MLA *is* MQA with key ``[c; k_rope]``
    (width r_kv + dr) and value ``c`` (width r_kv), so the generic ring
    machinery (``parallel/ring.py``) applies unchanged — the latent cache
    still writes through for the decode phase. This is the long-context
    DeepSeek serving path (VERDICT r2 item 3)."""
    b, t, _ = h.shape
    n_heads = cfg.num_heads
    r_kv, dn, dr, dv = cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim

    # -- latent + rope key, written through to the paged cache -------------
    kv_a = _qmm(h, lp["w_kv_a"])  # [B, T, r_kv + dr]
    c = rms_norm(kv_a[..., :r_kv], lp["kv_norm"], eps=cfg.rms_eps)
    if cfg.mla_scale_kv != 1.0:
        c = c * jnp.asarray(cfg.mla_scale_kv, c.dtype)
    k_rope = apply_rope(kv_a[..., None, r_kv:], positions, inv_freq)[:, :, 0]  # [B,T,dr]

    num_pages, ps, r_width = r_cache.shape[0], r_cache.shape[1], r_cache.shape[2]
    slots = slot_mapping.reshape(-1)
    c_flat = c_cache.reshape(num_pages * ps, r_kv).at[slots].set(
        c.reshape(-1, r_kv).astype(c_cache.dtype)
    )
    # Rope stream is lane-padded (mla_cache_widths): zero-fill the tail.
    k_rope_store = k_rope.reshape(-1, dr)
    if r_width != dr:
        k_rope_store = jnp.pad(k_rope_store, ((0, 0), (0, r_width - dr)))
    r_flat = r_cache.reshape(num_pages * ps, r_width).at[slots].set(
        k_rope_store.astype(r_cache.dtype)
    )
    c_cache = c_flat.reshape(num_pages, ps, r_kv)
    r_cache = r_flat.reshape(num_pages, ps, r_width)

    # -- queries, absorbed into latent space -------------------------------
    if "w_q_a" in lp:
        q_a = rms_norm(_qmm(h, lp["w_q_a"]), lp["q_norm"], eps=cfg.rms_eps)
        q = held_flat(_qmm(q_a, lp["w_q_b"])).reshape(b, t, n_heads, dn + dr)
    else:
        q = held_flat(_qmm(h, lp["w_q"])).reshape(b, t, n_heads, dn + dr)
    if cfg.mla_scale_q != 1.0:
        q = q * jnp.asarray(cfg.mla_scale_q, q.dtype)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, inv_freq)
    # absorb W_uk: scores live in latent space
    q_lat = up_project(lp, "w_uk", q_nope)  # [B,T,H,r_kv]

    scale = (dn + dr) ** -0.5 * attn_mscale
    if ring and "w_out_gate" in lp:
        raise NotImplementedError("a gated attention output is served by the paged path, not the ring")
    if ring:
        from dynamo_tpu.parallel.ring import ring_attention

        q_full = jnp.concatenate([q_lat.astype(h.dtype), q_rope], axis=-1)
        k_full = jnp.concatenate([c, k_rope], axis=-1)[:, :, None, :]  # MQA
        v_lat = c[:, :, None, :]
        out_lat = ring_attention(
            q_full, k_full, v_lat,
            positions if ring_positions is None else ring_positions,
            mesh, scale=scale,
        )  # [B, T, H, r_kv]
        out = jnp.einsum("bthr,rhv->bthv", out_lat.astype(h.dtype), lp["w_uv"])
        return _qmm(out.reshape(b, t, n_heads * dv), lp["wo_mla"]), c_cache, r_cache

    if impl is None:
        from dynamo_tpu.ops.attention import default_impl

        impl = default_impl()
    attend = functools.partial(_attend_paged, c_cache=c_cache, r_cache=r_cache, scale=scale, impl=impl, mesh=mesh,
                               contiguous_positions=contiguous_positions)
    if split is None:
        out_lat = attend(q_lat, q_rope, block_tables=block_tables, positions=positions)
    else:
        nd, nc, tc = split

        def rows(tok: slice, slot: slice, width: int):  # slots as rows, and back onto the token axis
            n = slot.stop - slot.start
            got = attend(q_lat[0, tok].reshape(n, width, n_heads, r_kv), q_rope[0, tok].reshape(n, width, n_heads, dr),
                         block_tables=block_tables[slot], positions=positions[0, tok].reshape(n, width))
            return got.reshape(1, n * width, n_heads, r_kv)

        out_lat = jnp.concatenate(
            [rows(slice(0, nd), slice(0, nd), 1), rows(slice(nd, t), slice(nd, nd + nc), tc)], axis=1)
    out = up_project(lp, "w_uv", out_lat.astype(h.dtype))  # [B,T,H,dv]
    if "w_out_gate" in lp:  # a sigmoid gate a head on the output, before its projection
        gate = jax.nn.sigmoid(jnp.dot(h, lp["w_out_gate"], preferred_element_type=jnp.float32))
        out = (out * gate[..., None]).astype(h.dtype)
    return _qmm(out.reshape(b, t, n_heads * dv), lp["wo_mla"]), c_cache, r_cache


def _query_tile(t: int, cap: int) -> int:
    """Queries a kernel row takes of a ``t``-token row: one where the kernel's
    row cap holds it, else the largest divisor of ``t`` within *half* the cap
    (64 tokens under a cap of 17: 8). The cap counts the staged queries and the
    accumulator; the score and probability blocks grow with the rows too, and
    16 queries of 64 heads overran a v5e's scoped VMEM by 5% where 8 compile
    (tests/test_chip_compile.py)."""
    if t <= cap:
        return t
    return max(q for q in range(1, max(1, cap // 2) + 1) if t % q == 0)


def _attend_paged(q_lat, q_rope, *, c_cache, r_cache, block_tables, positions, scale: float,
                  impl: str, mesh, contiguous_positions: bool) -> jnp.ndarray:
    """Absorbed attention of rows ``[B, T]`` against the paged latent cache:
    latent-space output ``[B, T, H, r_kv]`` (float32 from the kernel).

    The Pallas kernel reads each page once per kernel row, where the gather
    formulation below materializes the gathered latents and reads them for
    the scores and again for the output. Under a mesh it runs per device on
    the query-head shard against the replicated latent cache (shard_map, no
    collectives inside attention; parallel/sharding.cache_shardings)."""
    b, t, n_heads, r_kv = q_lat.shape
    dr = q_rope.shape[-1]
    ps, r_width = r_cache.shape[1], r_cache.shape[2]
    if impl == "pallas":
        from dynamo_tpu.ops.pallas_mla import (
            interpret_mode,
            mla_decode_supported,
            mla_paged_decode,
            mla_paged_decode_sharded,
        )
        from dynamo_tpu.ops.pallas_paged import _max_verify_t

        # The multi-query kernel's per-row causal mask is exact for ANY
        # position layout (T = 1 decode, gappy speculative-verify rows,
        # contiguous prefill windows): the only gates are geometry and the
        # VMEM row cap on the queries of one kernel row. A longer row goes in
        # tiles, each a kernel row of its own over the same block table.
        if mla_decode_supported(r_kv, r_width, 1, n_heads, interpret=interpret_mode()):
            tq = _query_tile(t, _max_verify_t(n_heads, r_kv + r_width))
            tiles = t // tq
            q_rope_k = q_rope
            if r_width != dr:  # match the lane-padded rope stream
                q_rope_k = jnp.pad(q_rope_k, ((0, 0), (0, 0), (0, 0), (0, r_width - dr)))
            kernel = mla_paged_decode if mesh is None else functools.partial(mla_paged_decode_sharded, mesh=mesh)
            out_lat = kernel(
                q_lat.reshape(b * tiles, tq, n_heads, r_kv), q_rope_k.reshape(b * tiles, tq, n_heads, r_width),
                c_cache, r_cache, jnp.repeat(block_tables, tiles, axis=0) if tiles > 1 else block_tables,
                positions.reshape(b * tiles, tq), scale=scale, interpret=interpret_mode(),
            )
            return out_lat.reshape(b, t, n_heads, r_kv)
        if t == 1 or not contiguous_positions:
            # Decode/verify falling off the kernel is the downgrade worth
            # alerting on; a prefill off it is the same geometry's.
            from dynamo_tpu.ops.pallas_paged import _record_fallback

            _record_fallback("mla_decode" if t == 1 else "mla_verify", q_lat, c_cache)

    # -- gather this batch's pages and attend ------------------------------
    s = block_tables.shape[1] * ps
    c_pages = c_cache[block_tables.reshape(-1)].reshape(b, s, r_kv)
    r_pages = r_cache[block_tables.reshape(-1)].reshape(b, s, r_width)[..., :dr]
    logits = (
        jnp.einsum("bthr,bsr->bhts", q_lat, c_pages, preferred_element_type=jnp.float32)
        + jnp.einsum("bthr,bsr->bhts", q_rope, r_pages, preferred_element_type=jnp.float32)
    ) * scale
    key_pos = jnp.arange(s, dtype=jnp.int32)
    mask = key_pos[None, None, :] <= positions[:, :, None]  # [B, T, S]
    logits = jnp.where(mask[:, None, :, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum(
        "bhts,bsr->bthr", probs.astype(c_pages.dtype), c_pages, preferred_element_type=jnp.float32
    )  # [B, T, H, r_kv]


def mla_attention_naive(
    lp: Params,
    cfg: ModelConfig,
    h: jnp.ndarray,  # [B, T, D]
    positions: jnp.ndarray,
    inv_freq: jnp.ndarray,
    attn_mscale: float = 1.0,
) -> jnp.ndarray:
    """Golden reference: materialize per-head K/V (no cache, full self-attn).

    The absorbed paged formulation must match this on whole sequences."""
    b, t, _ = h.shape
    n_heads = cfg.num_heads
    r_kv, dn, dr, dv = cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim

    kv_a = _qmm(h, lp["w_kv_a"])
    c = rms_norm(kv_a[..., :r_kv], lp["kv_norm"], eps=cfg.rms_eps) * jnp.asarray(cfg.mla_scale_kv, h.dtype)
    k_rope = apply_rope(kv_a[..., None, r_kv:], positions, inv_freq)  # [B,T,1,dr]
    k_nope = jnp.einsum("btr,rhn->bthn", c, lp["w_uk"])  # [B,T,H,dn]
    v = jnp.einsum("btr,rhv->bthv", c, lp["w_uv"])  # [B,T,H,dv]

    if "w_q_a" in lp:
        q_a = rms_norm(_qmm(h, lp["w_q_a"]), lp["q_norm"], eps=cfg.rms_eps)
        q = _qmm(q_a, lp["w_q_b"]).reshape(b, t, n_heads, dn + dr)
    else:
        q = _qmm(h, lp["w_q"]).reshape(b, t, n_heads, dn + dr)
    q = q * jnp.asarray(cfg.mla_scale_q, q.dtype)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, inv_freq)

    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (b, t, n_heads, dr))], axis=-1)
    qf = jnp.concatenate([q_nope, q_rope], axis=-1)
    scale = (dn + dr) ** -0.5 * attn_mscale
    logits = jnp.einsum("bthd,bshd->bhts", qf, k, preferred_element_type=jnp.float32) * scale
    mask = positions[:, :, None] >= positions[:, None, :]  # causal on true positions
    logits = jnp.where(mask[:, None, :, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhts,bshv->bthv", probs.astype(v.dtype), v, preferred_element_type=jnp.float32)
    return _qmm(out.astype(h.dtype).reshape(b, t, n_heads * dv), lp["wo_mla"])
