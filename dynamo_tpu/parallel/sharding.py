"""GSPMD sharding rules for Llama-family params, paged KV cache, activations.

Megatron-style tensor parallelism expressed purely as shardings — no explicit
collectives; XLA inserts the all-reduce after ``wo`` / ``w_down`` row-parallel
matmuls and partitions QKV/gate/up column-parallel:

| tensor              | shape                   | spec                        |
|---------------------|-------------------------|-----------------------------|
| embed               | [V, D]                  | (tp, None) — vocab-sharded  |
| lm_head             | [D, V]                  | (None, tp)                  |
| wq / wk / wv        | [L, D, H*hd]            | (None, None, tp)            |
| wo                  | [L, H*hd, D]            | (None, tp, None)            |
| w_gate / w_up       | [L, D, F]               | (None, None, tp)            |
| w_down              | [L, F, D]               | (None, tp, None)            |
| MoE expert weights  | [L, E, D, F]            | (None, ep, None, tp)        |
| router              | [L, D, E]               | replicated                  |
| norms               | [L, D] / [D]            | replicated                  |
| k/v cache           | [L, pages, ps, kv*hd]   | (None, None, None, tp)      |

KV-head sharding of the cache matches the head sharding of k/v projections,
so cache writes and paged-attention gathers are collective-free; GQA requires
``tp <= num_kv_heads`` (MeshPlan.auto enforces this).
"""

from __future__ import annotations

from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def param_shardings(mesh: Mesh, params: dict[str, Any]) -> dict[str, Any]:
    """A pytree of NamedShardings matching the params pytree."""

    def spec_for(path: tuple[str, ...], leaf) -> P:
        name = path[-1]
        if name == "embed":
            return P("tp", None)
        if name == "lm_head":
            return P(None, "tp")
        if name in ("wq", "wk", "wv"):
            return P(None, None, "tp")
        if name == "wo":
            return P(None, "tp", None)
        if name in ("w_gate", "w_up"):
            if leaf.ndim == 4:  # MoE: [L, E, D, F]
                return P(None, "ep", None, "tp")
            return P(None, None, "tp")
        if name == "w_down":
            if leaf.ndim == 4:  # MoE: [L, E, F, D]
                return P(None, "ep", "tp", None)
            return P(None, "tp", None)
        if name in ("bq", "bk", "bv"):  # qkv biases follow the head split
            return P(None, "tp")
        # MLA (models/mla.py): heads shard on tp; the shared latent
        # projections replicate (they're rank-512-ish — tiny next to the
        # per-head up-projections).
        if name in ("w_uk", "w_uv"):  # [L, r_kv, H, dn|dv]
            return P(None, None, "tp", None)
        if name in ("w_q_b", "w_q"):  # output dim is H*(dn+dr)
            return P(None, None, "tp")
        if name == "wo_mla":  # [L, H*dv, D]
            return P(None, "tp", None)
        if name in ("w_shared_gate", "w_shared_up"):
            return P(None, None, "tp")
        if name == "w_shared_down":
            return P(None, "tp", None)
        return P()  # norms, router, shared_gate: replicated

    def walk(tree, path):
        if isinstance(tree, dict):
            # Weight-only int8 leaf {"qw": int8, "scale": [..., d_out]}:
            # qw shards exactly like the float weight it replaces (derive
            # the spec from the real qw array — same ndim); scale keeps only
            # the output-channel axis (the weight spec minus its -2 axis).
            if "qw" in tree and "scale" in tree:
                base = spec_for(path, tree["qw"])
                scale_spec = P(*base[:-2], base[-1]) if len(base) >= 2 else base
                return {
                    "qw": NamedSharding(mesh, base),
                    "scale": NamedSharding(mesh, scale_spec),
                }
            # Packed int4 leaf {"qw4": int8[..., d_in//2, O], "scale":
            # [..., G, O], "qbias"?}: qw4 keeps the float weight's rank, so
            # the base spec applies unchanged. The scale's group axis
            # subdivides d_in exactly like the packed byte axis does, so it
            # inherits the same spec (a row-parallel tp split of d_in maps
            # to a tp split of whole groups, provided tp divides G — the
            # same divisibility the weight split already requires).
            if "qw4" in tree and "scale" in tree:
                base = spec_for(path, tree["qw4"])
                scale_spec = base
                out = {
                    "qw4": NamedSharding(mesh, base),
                    "scale": NamedSharding(mesh, scale_spec),
                }
                if "qbias" in tree:
                    out["qbias"] = NamedSharding(mesh, scale_spec)
                return out
            return {k: walk(v, path + (k,)) for k, v in tree.items()}
        return NamedSharding(mesh, spec_for(path, tree))

    return walk(params, ())


def cache_shardings(mesh: Mesh, attn_type: str = "gqa") -> NamedSharding:
    """Paged KV cache [L, pages, ps, W] placement.

    GQA: shard the head-major flattened KV-head dim on tp (head h occupies
    [h*hd, (h+1)*hd), so a tp-split is a contiguous block of whole heads,
    matching the k/v projection sharding).

    MLA: REPLICATE. The latent stream is shared by every query head (MQA) —
    a width split would slice latent channels and force per-layer
    collectives inside attention. Replication is what DeepSeek TP serving
    does everywhere: the latent cache is ~7-25x smaller than an equivalent
    GQA cache, so one copy per tp rank still beats a sharded GQA cache."""
    if attn_type == "mla":
        return NamedSharding(mesh, P())
    return NamedSharding(mesh, P(None, None, None, "tp"))


def batch_sharding(mesh: Mesh, ndim: int) -> NamedSharding:
    """Request-batch inputs [B, ...]: shard batch on dp."""
    return NamedSharding(mesh, P("dp", *([None] * (ndim - 1))))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_params(params: dict[str, Any], mesh: Mesh) -> dict[str, Any]:
    """Place a params pytree onto the mesh with TP/EP shardings."""
    shardings = param_shardings(mesh, params)
    return jax.tree.map(jax.device_put, params, shardings)


def init_sharded(init_fn, mesh: Mesh) -> dict[str, Any]:
    """Run a no-argument params initializer with TP/EP-sharded outputs: each
    device materializes only its own shard, so a model that needs the whole
    mesh's memory is never built on one device first."""
    shardings = param_shardings(mesh, jax.eval_shape(init_fn))
    return jax.jit(init_fn, out_shardings=shardings)()
