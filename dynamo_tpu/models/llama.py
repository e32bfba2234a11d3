"""Llama-family decoder (Llama 3.x, DeepSeek-R1-Distill-Llama) — pure-functional JAX.

Design (TPU-first, not a torch translation):

- Parameters are a pytree with **layers stacked on a leading axis** and the
  forward pass is a ``lax.scan`` over layers. One layer gets traced/compiled
  regardless of depth — compile time is O(1) in ``num_layers`` (matters at
  70B/80-layer scale) and XLA schedules identical per-layer programs.
- The KV cache is **paged** ([L, num_pages, page_size, n_kv * head_dim],
  page-major — see ``ops/attention.py``; a model that mixes window and full
  layers lays a pool per kind end to end, ``init_kv_cache``) and flows through
  the scan carry flat; each layer addresses its own pages by offset and writes
  back via scatter, which XLA aliases in place under buffer donation.
- One forward function serves prefill (T>1) and decode (T=1); queries attend
  to the paged cache, so chunked prefill and prefix reuse need no extra code
  path (see ``dynamo_tpu/ops/attention.py``).
- All matmuls are expressed so GSPMD can shard them from param/cache sharding
  annotations alone (no explicit collectives here; see ``dynamo_tpu/parallel``).

Replaces the model execution the reference delegates to vLLM/TRT-LLM
(SURVEY.md §2 parallelism table: TP/PP "engine-internal" — first-party here).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.ops.attention import paged_attention, write_kv
from dynamo_tpu.ops.norm import rms_norm
from dynamo_tpu.models.quant import held_flat, maybe_dequant as _dq, quant_matmul as _qmm
from dynamo_tpu.ops.rope import apply_mrope, apply_rope, rope_attention_factor, rope_frequencies

Params = dict


def param_dtype(cfg: ModelConfig) -> jnp.dtype:
    return jnp.dtype(cfg.dtype)


def init_params(cfg: ModelConfig, rng: jax.Array | int = 0) -> Params:
    """Random-init parameters (tests / benchmarks without checkpoint download).

    With ``cfg.first_k_dense > 0`` (DeepSeek first_k_dense_replace) the
    pytree carries two stacked subtrees: ``dense_layers`` (the first k
    layers, dense MLP) and ``layers`` (the remaining MoE layers)."""
    if isinstance(rng, int):
        rng = jax.random.PRNGKey(rng)
    dt = param_dtype(cfg)
    keys = jax.random.split(rng, 12)
    d, q, kv, f = cfg.hidden_size, cfg.q_dim, cfg.kv_dim, cfg.intermediate_size

    def w(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) * (fan_in**-0.5)).astype(dt)

    def layer_stack(l: int, moe: bool, key_salt: int, attn: bool = True) -> dict:
        ks = [jax.random.fold_in(k, key_salt) for k in keys]
        layers = {
            "attn_norm": jnp.ones((l, d), dt),
            "mlp_norm": jnp.ones((l, d), dt),
        }
        # (attn=False: a hybrid stack keeps its attention blocks by kind, beside the FFNs)
        if attn and cfg.qk_norm:
            qn = cfg.head_dim if cfg.qk_norm == "head" else cfg.q_dim
            kn = cfg.head_dim if cfg.qk_norm == "head" else cfg.kv_dim
            layers["q_norm"] = jnp.ones((l, qn), dt)
            layers["k_norm"] = jnp.ones((l, kn), dt)
        if attn and cfg.attn_type == "mla":
            from dynamo_tpu.models.mla import init_mla_params

            layers.update(init_mla_params(cfg, ks[0], dt, l))
        elif attn:
            layers.update(
                {
                    "wq": w(ks[0], (l, d, q), d),
                    "wk": w(ks[1], (l, d, kv), d),
                    "wv": w(ks[2], (l, d, kv), d),
                    "wo": w(ks[3], (l, q, d), q),
                }
            )
            if cfg.attn_out_gate:
                layers["w_out_gate"] = w(ks[8], (l, d, q), d)
        if attn and cfg.ssm_heads:  # a Mamba-2 mixer beside the attention, in every layer
            from dynamo_tpu.models.mamba2 import init_mamba_params

            layers.update(init_mamba_params(cfg, jax.random.fold_in(ks[0], 7), dt, l))
        if attn and cfg.attention_bias:
            layers.update(
                {
                    "bq": jnp.zeros((l, q), dt),
                    "bk": jnp.zeros((l, kv), dt),
                    "bv": jnp.zeros((l, kv), dt),
                }
            )
        if moe:
            # The experts held here; the router scores every output there is.
            e, mf = cfg.num_experts, cfg.moe_intermediate_size
            layers.update(
                {
                    "router": w(ks[4], (l, d, cfg.router_outputs), d),
                    "w_gate": w(ks[5], (l, e, d, mf), d),
                    "w_up": w(ks[6], (l, e, d, mf), d),
                    "w_down": w(ks[7], (l, e, mf, d), mf),
                }
            )
            if cfg.moe_router_bias:
                layers["router_bias"] = jnp.zeros((l, cfg.router_outputs), jnp.float32)
            if cfg.shared_expert_size:
                fs = cfg.shared_expert_size
                layers.update(
                    {
                        "w_shared_gate": w(ks[10], (l, d, fs), d),
                        "w_shared_up": w(ks[11], (l, d, fs), d),
                        "w_shared_down": w(ks[9], (l, fs, d), fs),
                    }
                )
                if cfg.shared_expert_gated:
                    layers["shared_gate"] = w(ks[8], (l, d, 1), d)
        else:
            layers.update(
                {
                    "w_gate": w(ks[5], (l, d, f), d),
                    "w_up": w(ks[6], (l, d, f), d),
                    "w_down": w(ks[7], (l, f, d), f),
                }
            )
        return layers

    def shortcut_stack(l: int) -> dict:
        """A shortcut-MoE layer's leaves: two sublayers (attention with its two
        norms and a dense FFN, each under a key of its own so that every
        matrix keeps the leaf name its kind has everywhere) beside one router
        and the held experts."""
        from dynamo_tpu.models.mla import init_mla_params

        def sub(i: int) -> dict:
            ks = [jax.random.fold_in(k, 2 + i) for k in keys]
            return {
                "attn_norm": jnp.ones((l, d), dt), "mlp_norm": jnp.ones((l, d), dt),
                **init_mla_params(cfg, ks[0], dt, l),
                "w_gate": w(ks[5], (l, d, f), d), "w_up": w(ks[6], (l, d, f), d), "w_down": w(ks[7], (l, f, d), f),
            }

        e, mf = cfg.num_experts, cfg.moe_intermediate_size
        return {
            "sub0": sub(0), "sub1": sub(1),
            "router": w(keys[4], (l, d, cfg.router_outputs), d),
            "router_bias": jnp.zeros((l, cfg.router_outputs), jnp.float32),
            "w_gate": w(keys[5], (l, e, d, mf), d), "w_up": w(keys[6], (l, e, d, mf), d),
            "w_down": w(keys[7], (l, e, mf, d), mf),
        }

    k_dense = cfg.first_k_dense if cfg.is_moe else 0
    hybrid = bool(cfg.layer_group_size)
    params: Params = {
        "embed": w(keys[8], (cfg.vocab_size, d), d),
        "norm_f": jnp.ones((d,), dt),
        "layers": (shortcut_stack(cfg.num_layers) if cfg.shortcut_moe
                   else layer_stack(cfg.num_layers - k_dense, cfg.is_moe, 0, attn=not hybrid)),
    }
    if k_dense:
        params["dense_layers"] = layer_stack(k_dense, False, 1, attn=not hybrid)
    if hybrid:
        # The blocks of a period by kind, each stack in layer order (``layers`` and ``dense_layers`` hold the
        # norms and the FFNs of every layer): the recurrent layers' (Mamba-2 layers that stand alone, or KDA) and
        # those of the layers that attend (latent attention with its head-wise output gate, or the GQA block).
        from dynamo_tpu.models.kda import init_kda_params
        from dynamo_tpu.models.mamba2 import init_mamba_params
        from dynamo_tpu.models.mla import init_mla_params

        n_attn = cfg.cache_layers
        if cfg.ssm_heads:
            params["ssm_layers"] = init_mamba_params(cfg, jax.random.fold_in(keys[0], 7), dt, cfg.recurrent_layers)
        else:
            params["kda_layers"] = init_kda_params(cfg, jax.random.fold_in(keys[0], 2), dt, cfg.recurrent_layers)
        if cfg.attn_type == "mla":
            params["mla_layers"] = {**init_mla_params(cfg, jax.random.fold_in(keys[0], 3), dt, n_attn),
                                    "w_out_gate": w(jax.random.fold_in(keys[1], 3), (n_attn, d, cfg.num_heads), d)}
        else:
            ks = [jax.random.fold_in(k, 4) for k in keys]
            params["attn_layers"] = {"wq": w(ks[0], (n_attn, d, q), d), "wk": w(ks[1], (n_attn, d, kv), d),
                                     "wv": w(ks[2], (n_attn, d, kv), d), "wo": w(ks[3], (n_attn, q, d), q)}
            if cfg.attn_out_gate:
                params["attn_layers"]["w_out_gate"] = w(ks[8], (n_attn, d, q), d)
    if not cfg.tie_embeddings:
        params["lm_head"] = w(keys[9], (d, cfg.vocab_size), d)
    return params


def window_pool_pages(cfg: ModelConfig, num_pages: int, page_size: int, rows: int, chunk_tokens: int | None) -> int:
    """Pages of a mixed model's window pool, the null page included: a row
    holds the pages its window and the chunk it is computing reach into and
    the page being written, ``ceil((window + chunk) / page) + 1``, whatever its
    context. Never more than the full pool's ``num_pages``, which is also what
    an unknown chunk (``None``: nothing bounds a row's step) takes."""
    if chunk_tokens is None:
        return num_pages
    return min(num_pages, rows * (-(-(cfg.sliding_window + chunk_tokens) // page_size) + 1) + 1)


def pool_layout(cfg: ModelConfig, flat_pages: int, window_pages: int | None) -> tuple[list[int], int, int]:
    """Where each layer of a mixed model starts in the flat cache: (each
    layer's first page, pages a full layer holds, pages a sliding layer
    holds). The full layers' pools lie first, then the sliding layers'.
    ``window_pages`` None: both kinds hold the same number of pages."""
    from dynamo_tpu.models.config import SLIDING

    n_win = cfg.cache_layers_of(SLIDING)
    n_full = cfg.num_layers - n_win
    p_win = flat_pages // cfg.num_layers if window_pages is None else window_pages
    p_full, rest = divmod(flat_pages - n_win * p_win, n_full)
    if rest or p_full <= 0:
        raise ValueError(f"a cache of {flat_pages} pages is not {n_full} full pools and {n_win} window pools of {p_win}")
    bases, seen = [], {True: 0, False: 0}
    for kind in cfg.layer_types:
        win = kind == SLIDING
        bases.append(n_full * p_full + seen[win] * p_win if win else seen[win] * p_full)
        seen[win] += 1
    return bases, p_full, p_win


def init_kv_cache(cfg: ModelConfig, num_pages: int, page_size: int, dtype: jnp.dtype | None = None,
                  window_pages: int | None = None):
    """Allocate the paged KV cache: two [L, num_pages, page_size, n_kv * hd] arrays,
    L one slab per attention (sub)layer (``cfg.cache_layers``).

    A model that mixes window and full layers (``cfg.mixed_attention``) has a
    page pool per kind: two [1, n_full * num_pages + n_window * window_pages,
    page_size, W] arrays, each full layer's ``num_pages`` end to end and then
    each sliding layer's ``window_pages`` (``pool_layout``; None = as many as
    ``num_pages``, so that one block table can serve both kinds). Page 0 of
    every layer's pool is its null page.

    Page-major per layer with KV heads flattened into the trailing (lane)
    dimension — one page is a single contiguous ``ps x W`` slab covering all
    KV heads, the native layout of the Pallas decode kernel (one big DMA per
    page). Keeping W = n_kv * head_dim as the physical trailing dim makes the
    array's TPU tiling padding-free even at head_dim 64, and means the
    kernel, the write scatter, and the gather all address the cache without
    relayout copies. Ops that need per-head structure reshape *gathered*
    slices (fresh intermediates XLA can fuse), never the cache itself.
    """
    dt = dtype or param_dtype(cfg)
    if cfg.attn_type == "mla":
        # MLA: k_cache holds the per-token latents, v_cache the decoupled
        # rope keys (models/mla.py) — same paged geometry, ~7x fewer bytes.
        from dynamo_tpu.models.mla import mla_cache_widths

        wk, wv = mla_cache_widths(cfg)
        return (
            jnp.zeros((cfg.cache_layers, num_pages, page_size, wk), dt),
            jnp.zeros((cfg.cache_layers, num_pages, page_size, wv), dt),
        )
    shape = (cfg.cache_layers, num_pages, page_size, cfg.num_kv_heads * cfg.head_dim)
    if cfg.mixed_attention:
        from dynamo_tpu.models.config import SLIDING

        n_win = cfg.cache_layers_of(SLIDING)
        pages = (cfg.num_layers - n_win) * num_pages + n_win * (num_pages if window_pages is None else window_pages)
        shape = (1, pages, *shape[2:])
    return jnp.zeros(shape, dt), jnp.zeros(shape, dt)


def _mlp_dense(lp: Params, x: jnp.ndarray, act: str = "silu", gate_mult: float = 1.0, down_mult: float = 1.0) -> jnp.ndarray:
    """``gate_mult`` / ``down_mult``: Falcon-H1's ``mlp_multipliers``, on the
    gate's pre-activation and on the output (1.0: no multiply in the program)."""
    gp = _qmm(x, lp["w_gate"])
    if gate_mult != 1.0:
        gp = gp * jnp.asarray(gate_mult, gp.dtype)
    # Gemma's GeGLU uses the tanh-approximate gelu (HF gelu_pytorch_tanh).
    gate = jax.nn.gelu(gp, approximate=True) if act == "gelu_tanh" else jax.nn.silu(gp)
    out = _qmm(gate * _qmm(x, lp["w_up"]), lp["w_down"])
    return out * jnp.asarray(down_mult, out.dtype) if down_mult != 1.0 else out


def _routing_kwargs(cfg: ModelConfig) -> dict:
    """Family router semantics for ``parallel/moe.route_tokens``."""
    return dict(
        scoring=cfg.moe_scoring,
        norm_topk=cfg.moe_norm_topk,
        scaling=cfg.moe_routed_scaling,
        n_group=cfg.moe_n_group,
        topk_group=cfg.moe_topk_group,
        # noaux_tc (V3) ranks groups by top-2 sum of biased scores;
        # group_limited_greedy (V2) by per-group max.
        group_score="top2sum" if cfg.moe_router_bias else "max",
    )


def _mlp_moe(lp: Params, x: jnp.ndarray, cfg: ModelConfig, mesh=None, valid: jnp.ndarray | None = None) -> jnp.ndarray:
    """Top-k routed MoE (``dynamo_tpu/parallel/moe.py``). ``valid``
    (``bool[B, T]``, a paged step's ``slot_mapping != 0``) keeps padding
    positions out of the dropless dispatch: they route to no expert.

    Without an ``ep`` mesh axis: dropless ragged-matmul dispatch — exact,
    batch-composition-independent (deterministic greedy). With experts
    sharded over ``ep``: capacity-bounded scatter dispatch, where GSPMD turns
    the buffer movement into all-to-all over the expert axis."""
    from dynamo_tpu.parallel.moe import moe_mlp, moe_mlp_dropless

    import os

    b, t, d = x.shape
    xt = x.reshape(b * t, d)
    ep = int(mesh.shape.get("ep", 1)) if mesh is not None else 1
    routing = _routing_kwargs(cfg)
    # DYNAMO_MOE_DISPATCH overrides the ragged-matmul default without an ep
    # axis (the default compiles for a v5e at OLMoE widths, 64 experts,
    # decode and prefill shapes alike):
    #  - "capacity": GShard scatter dispatch.
    #  - "dense": decode-sized batches (N*k tokens-choices <= 2048) run the
    #    dense formulation — every token through every expert, mixed by
    #    routing weight. At decode N the extra FLOPs are MXU-noise and the
    #    step stays weight-bandwidth-bound. Larger (prefill) batches fall
    #    through to the capacity dispatch.
    dispatch = os.environ.get("DYNAMO_MOE_DISPATCH", "")
    dense_ok = b * t * cfg.num_experts_per_token <= 2048
    if ep <= 1 and dispatch == "dense" and dense_ok:
        out = _routed_dense(lp, xt, cfg)
    elif ep <= 1 and dispatch not in ("capacity", "dense"):
        out = moe_mlp_dropless(
            lp, xt, num_experts_per_token=cfg.num_experts_per_token, routing=routing, mesh=mesh,
            valid=None if valid is None else valid.reshape(-1),
        )
    else:
        cf = cfg.moe_capacity_factor
        out = moe_mlp(
            lp, xt,
            num_experts_per_token=cfg.num_experts_per_token,
            capacity_factor=cf,
            capacity=(b * t * cfg.num_experts_per_token) if cf <= 0 else None,
            routing=routing,
        )
    if cfg.shared_expert_size:
        out = out + _shared_expert(lp, xt, cfg)
    return out.reshape(b, t, d)


def _shared_expert(lp: Params, xt: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """The always-on expert beside the routed ones, on flattened tokens [N, D]."""
    with jax.named_scope("moe.shared"):
        shared = _qmm(jax.nn.silu(_qmm(xt, lp["w_shared_gate"])) * _qmm(xt, lp["w_shared_up"]), lp["w_shared_down"])
        if cfg.shared_expert_gated:
            shared = shared * jax.nn.sigmoid((xt @ lp["shared_gate"]).astype(jnp.float32)).astype(shared.dtype)
        return shared


def _mlp_moe_held(lp: Params, x: jnp.ndarray, cfg: ModelConfig, valid: jnp.ndarray, mesh=None):
    """The MoE of a model that holds a share of its routed experts (or has
    identity experts), ``cfg.moe_held_share``: this holder's part of the routed
    sum (``parallel/moe.moe_mlp_held``) and the shared expert, which every
    holder computes whole. Returns the output and the layer's ``HELD_COUNTS``
    over the ``valid`` tokens."""
    from dynamo_tpu.parallel.moe import moe_mlp_held

    b, t, d = x.shape
    xt = x.reshape(b * t, d)
    out, counted = moe_mlp_held(
        lp, xt, num_experts_per_token=cfg.num_experts_per_token, first=cfg.moe_expert_first,
        routed=cfg.routed_experts, routing=_routing_kwargs(cfg), valid=valid.reshape(-1), mesh=mesh)
    if cfg.shared_expert_size:
        out = out + _shared_expert(lp, xt, cfg)
    return out.reshape(b, t, d), counted


def _routed_dense(lp: Params, xt: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """Routed MoE via dense compute over flattened tokens [N, D]: every
    token through every expert, mixed by routing weight. Exact (same output
    as the dropless dispatch); O(N*E) FLOPs, so only sensible for
    decode-sized N where the step is weight-bandwidth-bound anyway."""
    from dynamo_tpu.parallel.moe import route_tokens

    weights, topi = route_tokens(
        lp, xt, k=cfg.num_experts_per_token, **_routing_kwargs(cfg)
    )
    e = lp["router"].shape[-1]
    mix = jnp.zeros((xt.shape[0], e), jnp.float32).at[
        jnp.arange(xt.shape[0])[:, None], topi
    ].set(weights)  # [N, E]
    gate = jax.nn.silu(jnp.einsum("nd,edf->nef", xt, _dq(lp["w_gate"])))
    up = jnp.einsum("nd,edf->nef", xt, _dq(lp["w_up"]))
    expert_out = jnp.einsum("nef,efd->ned", gate * up, _dq(lp["w_down"]))  # [N, E, d]
    out = jnp.einsum("ned,ne->nd", expert_out.astype(jnp.float32), mix)
    return out.astype(xt.dtype)


def _mlp_moe_dense(lp: Params, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """Dense-compute MoE golden model for tests of the dispatched paths
    (and the serving decode path under DYNAMO_MOE_DISPATCH=dense)."""
    b, t, d = x.shape
    return _routed_dense(lp, x.reshape(b * t, d), cfg).reshape(b, t, d)


def forward(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # i32[B, T]
    positions: jnp.ndarray,  # i32[B, T]
    k_cache: jnp.ndarray,  # [L, num_pages, page_size, n_kv * hd]
    v_cache: jnp.ndarray,
    block_tables: jnp.ndarray,  # i32[B, pages_per_seq]
    slot_mapping: jnp.ndarray,  # i32[B, T]
    last_token_index: jnp.ndarray,  # i32[B] index in [0,T) of each seq's last real token
    *,
    attn_impl: str | None = None,
    mesh=None,  # required when attn_impl == "ring"
    mm_embeds: jnp.ndarray | None = None,  # [B, M, D] image embeddings (vision tower)
    mm_slot_offset: jnp.ndarray | None = None,  # i32[B] placeholders already cached; -1 = text row
    mm_counts: jnp.ndarray | None = None,  # i32[B] embedding rows provided per row
    mrope_positions: jnp.ndarray | None = None,  # i32[B, 3, T] Qwen2-VL 3D rope coords
    logit_indices: jnp.ndarray | None = None,  # i32[B, V] token columns to score (spec verify)
    contiguous_positions: bool = True,  # False: route attention via gappy-safe paths
    split: tuple[int, int, int] | None = None,  # (decode slots, chunk slots, tokens per chunk slot)
    moe_counts: bool = False,  # also return the held-share expert layers' counters
    recurrent: tuple | None = None,  # (state, conv, slot ids i32[rows]) of a model with recurrent layers
    window_tables: jnp.ndarray | None = None,  # a mixed model's sliding layers: their block tables,
    window_slots: jnp.ndarray | None = None,  # their slot mapping (shaped as the full layers')
    window_pages: int | None = None,  # and the pages each of them holds (``init_kv_cache``)
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One forward step. Returns (logits f32[B, vocab], k_cache, v_cache).

    ``window_tables`` / ``window_slots`` (a model that mixes window and full
    layers): the page ids of the sliding layers' pool, which holds a row's
    window and not its context; a block wholly under the window is the null
    page 0 (the kernels walk from the window's first block and the mask is by
    position). Without them the sliding layers take the full layers' tables,
    which a cache of equal pools (``window_pages`` None) seats.

    ``recurrent`` (a model with recurrent layers, ``cfg.recurrent_layers``: KDA
    or Mamba-2 layers in periods, or a Mamba-2 mixer beside every layer's attention):
    the two state buffers of ``models/kda.init_state`` and each row's slot
    (each slot's, on the split token axis; 0 is the null slot). The buffers
    come back as the last two outputs, updated: the caller donates them as it
    does the cache. Without it such a model's rows each start from a zero
    state that is dropped with the call, and the outputs are the usual three:
    a whole-sequence call from position 0, which is how the benchmark's
    ``tests/benchmark/test_benchmark_reference.py`` calls the program for
    every configuration it has (a file only a ``benchmark`` PR edits).

    ``moe_counts`` (a model whose expert layer holds a share or has identity
    experts, ``cfg.moe_held_share``): a fourth output, i32[4], the layers'
    ``parallel/moe.HELD_COUNTS`` summed over the real tokens of the step.

    Works for prefill (T = padded prompt chunk) and decode (T=1) alike; the
    engine runner donates the cache buffers so updates happen in place.

    ``attn_impl="ring"`` runs sequence-parallel ring attention over the
    mesh's ``sp`` axis (``parallel/ring.py``) for whole-prompt prefills —
    valid only when every sequence's full context is inside this chunk
    (positions start at 0, no cached prefix); K/V still write through to the
    paged cache so decode continues on the paged path.

    ``mm_embeds`` substitutes the k-th image placeholder token
    (``cfg.image_token_id``) of row b with ``mm_embeds[b, k + offset]`` —
    ``mm_slot_offset`` counts placeholders in already-cached chunks, so
    chunked prefill and prefix-cache resumption stay exact (the multimodal
    prefill handoff, reference `examples/multimodal/`).

    ``logit_indices`` switches the head to multi-position scoring for
    speculative verify: instead of one logits row per sequence at
    ``last_token_index``, score the V token columns named per row and
    return f32[B, V, vocab]. ``contiguous_positions=False`` additionally
    tells the paged-attention dispatch not to assume per-row contiguous
    position runs — verify rows from the n-gram drafter *are* contiguous,
    but the proposer interface admits draft layouts that are not, and the
    prefill kernel would silently mis-attend on a gappy row.

    ``split = (nd, nc, tc)`` lays a chunk step out on one token axis instead
    of a ``[B, T]`` rectangle: ``tokens``, ``positions`` and ``slot_mapping``
    are flat ``i32[nd + nc * tc]``, one position per decode slot then ``tc``
    per chunk slot; ``block_tables`` has one row per slot (``nd + nc``) and
    ``last_token_index`` names each slot's last token *on the flat axis*.
    Everything but attention is per token already; attention alone sees rows
    (the decode slots as ``[nd, 1]``, the chunk slots as ``[nc, tc]``: a GQA /
    MHA model's both through the chunked kernel, an MLA model's through the
    MLA kernel, a chunk's queries in tiles, ``models/mla.py``). Text models
    without a mesh only.
    """
    if cfg.recurrent_layers and (mesh is not None or logit_indices is not None
                                 or not contiguous_positions or mm_embeds is not None):
        raise NotImplementedError(
            "a model with recurrent layers (KDA, or a Mamba-2 mixer) is served on one device, one token after "
            "another: no mesh, no speculative verify, no image rows")
    keep_state = recurrent is not None
    if cfg.recurrent_layers and not keep_state:  # a state of the call's own: a slot a row, all zeros
        from dynamo_tpu.models.kda import init_state

        rows = block_tables.shape[0]
        recurrent = (*init_state(cfg, rows + 1), jnp.arange(1, rows + 1, dtype=jnp.int32))
    if split is not None:
        if (mesh is not None or attn_impl == "ring" or cfg.mrope_section
                or mm_embeds is not None or logit_indices is not None or not contiguous_positions):
            raise NotImplementedError("the split token axis serves unsharded text steps only")
        nd, nc, tc = split
        assert tokens.shape == (nd + nc * tc,) and block_tables.shape[0] == nd + nc
        tokens, positions, slot_mapping = tokens[None], positions[None], slot_mapping[None]
    b, t = tokens.shape
    nl, npages, ps = k_cache.shape[0], k_cache.shape[1], k_cache.shape[2]
    inv_freq = jnp.asarray(rope_frequencies(cfg.head_dim, theta=cfg.rope_theta, scaling=cfg.rope_scaling))
    attn_mscale = rope_attention_factor(cfg.rope_scaling) ** 2
    attn_rescale = cfg.attn_scale * cfg.head_dim**0.5 if cfg.attn_scale else 1.0
    if cfg.residual_multiplier != 1.0 and not cfg.layer_group_size:
        raise NotImplementedError("residual_multiplier is served in the period scan only (Granite-4.0-H's layers)")
    x = params["embed"][tokens]  # [B, T, D]
    if cfg.embed_scale:  # Gemma: embeddings scale by sqrt(hidden)
        x = x * jnp.asarray(cfg.hidden_size**0.5, x.dtype)
    if cfg.embed_multiplier != 1.0:  # Falcon-H1's muP, Granite's embedding_multiplier
        x = x * jnp.asarray(cfg.embed_multiplier, x.dtype)
    if mm_embeds is not None and cfg.image_token_id is not None:
        is_img = tokens == jnp.int32(cfg.image_token_id)  # [B, T]
        if cfg.video_token_id is not None:
            # Video placeholders substitute from the same embedding stream,
            # rows ordered by span position (images and videos interleaved).
            is_img = is_img | (tokens == jnp.int32(cfg.video_token_id))
        slot = jnp.cumsum(is_img.astype(jnp.int32), axis=1) - 1
        if mm_slot_offset is not None:
            slot = slot + jnp.maximum(mm_slot_offset, 0)[:, None]
            # Rows without images (offset -1) keep plain token embeddings —
            # a text prompt containing the placeholder id must not change
            # meaning based on which batch it shares a prefill with.
            is_img = is_img & (mm_slot_offset >= 0)[:, None]
        if mm_counts is not None:
            # Placeholders beyond the provided rows (e.g. *sampled* image
            # tokens recomputed after preemption) stay token embeddings.
            is_img = is_img & (slot < mm_counts[:, None])
        slot = jnp.clip(slot, 0, mm_embeds.shape[1] - 1)
        gathered = jnp.take_along_axis(mm_embeds.astype(x.dtype), slot[..., None], axis=1)
        x = jnp.where(is_img[..., None], gathered, x)

    # The stacked cache is kept flat ([L*pages, ps, W]) and every layer
    # addresses its region with offset indices (page' = li*pages + page).
    # This keeps cache writes a single in-place scatter on the donated carry
    # and cache reads a gather — slicing the layer out of the carry
    # (dynamic_index/update_in_dim) would copy the full multi-MB layer cache
    # twice per layer per step, which measures ~7 ms/step at 1B scale.
    kf0 = k_cache.reshape(nl * npages, ps, k_cache.shape[3])
    vf0 = v_cache.reshape(nl * npages, ps, v_cache.shape[3])

    if attn_impl is None:
        # Resolve the backend default up front: an unresolved None on a TPU
        # mesh would skip the sharded kernel wrapper below and run the
        # pallas_call under GSPMD, which replicates the whole cache onto
        # every device.
        from dynamo_tpu.ops.attention import default_impl

        attn_impl = default_impl()
    ring = attn_impl == "ring"
    if ring and cfg.sliding_window > 0:
        # Ring attention computes full causal attention over the sp axis;
        # silently serving a windowed model through it would change logits.
        raise ValueError(
            "ring attention does not implement sliding-window masking; "
            "serve SWA models with the paged path (no sp axis)"
        )
    if ring:
        # Padding tokens (slot 0) must not act as attendable keys in the ring
        # path (the paged path excludes them structurally via the null page).
        # A far-future sentinel position hides them from every real query.
        ring_pos = jnp.where(slot_mapping == 0, jnp.int32(2**30), positions)

    # Window and full attention layers in one model: the one layer scan carries
    # per-layer scalars beside the stacked weights (the window, with NO_WINDOW
    # for a full layer; which RoPE table; the YaRN factor squared), so both
    # kinds are one compiled layer body. A model whose layers are all alike
    # scans its weights alone, as before.
    layer_kinds = None
    if cfg.mixed_attention:
        if cfg.attn_type == "mla" or cfg.mrope_section:
            raise NotImplementedError("mixed window/full layers are served for GQA text models only")
        from dynamo_tpu.ops.pallas_paged import NO_WINDOW

        kinds = sorted(set(cfg.layer_types))
        ropes = [cfg.rope_of(kind) for kind in kinds]
        rope_tables = jnp.asarray(np.stack(
            [rope_frequencies(cfg.head_dim, theta=theta, scaling=scaling) for theta, scaling in ropes]))
        factors = [rope_attention_factor(scaling) ** 2 for _, scaling in ropes]
        which = [kinds.index(kind) for kind in cfg.layer_types]
        # A pool per kind in the one flat cache: each layer's first page and
        # which of the two block tables (and slot mappings) names its pages.
        from dynamo_tpu.models.config import SLIDING

        bases, _, _ = pool_layout(cfg, nl * npages, window_pages)
        if window_tables is None:
            window_tables, window_slots = block_tables, slot_mapping
        elif split is not None:
            window_slots = window_slots[None]
        layer_kinds = {
            "window": jnp.asarray([w or NO_WINDOW for w in cfg.layer_windows()], jnp.int32),
            "rope": jnp.asarray(which, jnp.int32),
            "mscale": jnp.asarray([factors[i] for i in which], jnp.float32),
            "base": jnp.asarray(bases, jnp.int32),
            "windowed": jnp.asarray([k == SLIDING for k in cfg.layer_types], jnp.bool_),
        }

    mla = cfg.attn_type == "mla"
    if mla:
        inv_freq_mla = jnp.asarray(
            rope_frequencies(cfg.qk_rope_head_dim, theta=cfg.rope_theta, scaling=cfg.rope_scaling)
        )

    # The fused expert kernel reads the stacked int8 experts in place, by
    # layer index (parallel/moe.split_expert_stack says why).
    moe_layers, expert_stack = params["layers"], None
    if cfg.is_moe:
        from dynamo_tpu.parallel.moe import join_expert_stack, split_expert_stack

        moe_layers, expert_stack = split_expert_stack(params["layers"], mesh=mesh)
    n_dense = jax.tree.leaves(params["dense_layers"])[0].shape[0] if "dense_layers" in params else 0

    def shortcut_layer_step(carry, lp):
        """A shortcut-MoE layer (LongCat-Flash): attention, dense FFN, attention,
        dense FFN on the residual stream, and a MoE that reads the first
        sublayer's post-attention norm and joins the stream at the layer's
        end. The sublayers' cache slabs are 2 li and 2 li + 1."""
        from dynamo_tpu.models.mla import mla_attention

        x, k_full, v_full, li, counts = carry
        lp = join_expert_stack(lp, expert_stack, li)

        def attention(i: int, x, k_full, v_full):
            sp, slab = lp[f"sub{i}"], 2 * li + i
            h = rms_norm(x, sp["attn_norm"], eps=cfg.rms_eps)
            with jax.named_scope(f"attn.{i}"):
                out, k_full, v_full = mla_attention(
                    sp, cfg, h, positions, k_full, v_full,
                    block_tables + slab * npages, slot_mapping + slab * (npages * ps), inv_freq_mla,
                    attn_mscale=attn_mscale, ring=ring, mesh=mesh, ring_positions=ring_pos if ring else None,
                    impl=attn_impl, contiguous_positions=contiguous_positions, split=split)
            x = x + out
            return x, rms_norm(x, sp["mlp_norm"], eps=cfg.rms_eps), k_full, v_full

        a0, h0, k_full, v_full = attention(0, x, k_full, v_full)
        with jax.named_scope("mlp.moe"):
            m, counted = _mlp_moe_held(lp, h0, cfg, slot_mapping != 0, mesh)
        with jax.named_scope("mlp.dense0"):
            b0 = a0 + _mlp_dense(lp["sub0"], h0, cfg.mlp_act)
        a1, h1, k_full, v_full = attention(1, b0, k_full, v_full)
        with jax.named_scope("mlp.dense1"):
            y = a1 + _mlp_dense(lp["sub1"], h1, cfg.mlp_act) + m
        return (y, k_full, v_full, li + 1, counts + counted), None

    def gqa_attention(lp, h, k_full, v_full, li, kind=None):
        """One layer's GQA block on its normed input ``h``: the projections with
        their optional biases, norms and multipliers, RoPE by the layer's
        kind, the cache write, paged attention (on the split token axis too),
        the output gate where the model has one and the output projection.
        ``li`` is the layer's slab of the cache, ``kind`` a mixed model's scalars
        of the layer. Returns ``(out, k_full,
        v_full)``. The plain layer body and the period scan both call it."""
        qp, kp, vp = _qmm(h, lp["wq"]), _qmm(h, lp["wk"]), _qmm(h, lp["wv"])
        if cfg.attention_bias:
            qp, kp, vp = qp + lp["bq"], kp + lp["bk"], vp + lp["bv"]
        if cfg.key_multiplier != 1.0:
            kp = kp * jnp.asarray(cfg.key_multiplier, kp.dtype)
        if cfg.qk_norm == "flat":  # OLMoE: norm the flat projection
            qp = rms_norm(qp, lp["q_norm"], eps=cfg.rms_eps)
            kp = rms_norm(kp, lp["k_norm"], eps=cfg.rms_eps)
        else:
            # Nothing stands between the projection and its heads, so
            # the heads' layout would reach the dot and re-lay wq and
            # wk. (A flat norm reads whole rows and is laid out flat
            # itself; v's heads are flattened again by the cache write.)
            qp, kp = held_flat(qp), held_flat(kp)
        q = qp.reshape(b, t, cfg.num_heads, cfg.head_dim)
        k = kp.reshape(b, t, cfg.num_kv_heads, cfg.head_dim)
        v = vp.reshape(b, t, cfg.num_kv_heads, cfg.head_dim)
        if cfg.qk_norm == "head":  # Qwen3: per-head norm before rope
            q = rms_norm(q, lp["q_norm"], eps=cfg.rms_eps)
            k = rms_norm(k, lp["k_norm"], eps=cfg.rms_eps)
        if mrope_positions is not None and cfg.mrope_section:
            # Qwen2-VL 3D rope: ONLY the rotation angles change; cache
            # slots, masking, and lengths keep the sequential positions.
            q = apply_mrope(q, mrope_positions, inv_freq, cfg.mrope_section)
            k = apply_mrope(k, mrope_positions, inv_freq, cfg.mrope_section)
        elif kind is not None:  # this layer's own RoPE, and its YaRN factor (1 if plain)
            q = apply_rope(q, positions, rope_tables[kind["rope"]])
            k = apply_rope(k, positions, rope_tables[kind["rope"]])
            q = q * kind["mscale"].astype(q.dtype)
        else:
            q = apply_rope(q, positions, inv_freq)
            k = apply_rope(k, positions, inv_freq)
        if kind is None and attn_mscale != 1.0:  # YaRN temperature: logits scale by mscale^2
            q = q * jnp.asarray(attn_mscale, q.dtype)
        if attn_rescale != 1.0:  # a softmax scale of the model's own: the kernels' is head_dim ** -0.5
            q = q * jnp.asarray(attn_rescale, q.dtype)
        if kind is None:
            slots_l = slot_mapping + li * (npages * ps)
        else:  # the layer's own pool: its kind's slots, from its first page
            slots_l = jnp.where(kind["windowed"], window_slots, slot_mapping) + kind["base"] * ps
        k_full, v_full = write_kv(k_full, v_full, k, v, slots_l)
        if ring:
            from dynamo_tpu.parallel.ring import ring_attention

            attn = ring_attention(q, k, v, ring_pos, mesh, scale=cfg.head_dim**-0.5)
        else:
            if kind is None:
                tables_l = block_tables + li * npages
            else:  # and its kind's block table
                tables_l = jnp.where(kind["windowed"], window_tables, block_tables) + kind["base"]
            # 0 = full causal; a mixed model hands each layer its own
            # (a runtime scalar: NO_WINDOW in its full layers).
            window = cfg.sliding_window if kind is None else kind["window"]
            if attn_impl == "pallas" and mesh is not None:
                # Explicit tp/dp layout around the kernel: GSPMD would
                # otherwise all-gather the cache and replicate the
                # pallas_call on every device.
                from dynamo_tpu.ops.attention import paged_attention_sharded

                attn = paged_attention_sharded(
                    q, k_full, v_full, tables_l, positions,
                    mesh=mesh, impl=attn_impl, sliding_window=window,
                    contiguous_positions=contiguous_positions,
                )
            elif split is not None:
                # One query per decode slot, tc per chunk slot; back onto the token axis.
                def rows(tok: slice, slot: slice, width: int):
                    n = slot.stop - slot.start
                    return paged_attention(
                        q[0, tok].reshape(n, width, cfg.num_heads, cfg.head_dim), k_full, v_full,
                        tables_l[slot], positions[0, tok].reshape(n, width),
                        impl=attn_impl, sliding_window=window, chunked=True,
                    ).reshape(1, n * width, cfg.num_heads, cfg.head_dim)

                attn = jnp.concatenate(
                    [rows(slice(0, nd), slice(0, nd), 1), rows(slice(nd, t), slice(nd, nd + nc), tc)], axis=1)
            else:
                attn = paged_attention(q, k_full, v_full, tables_l, positions, impl=attn_impl,
                                       sliding_window=window,
                                       contiguous_positions=contiguous_positions)
        attn = attn.reshape(b, t, cfg.q_dim)
        if cfg.attn_out_gate:  # a sigmoid gate a channel on the heads' outputs, from the layer's normed input
            with jax.named_scope("attn.gate"):
                gate = jax.nn.sigmoid(jnp.dot(h, lp["w_out_gate"], preferred_element_type=jnp.float32))
                attn = (attn * gate).astype(attn.dtype)
        attn_out = _qmm(attn, lp["wo"])
        if cfg.attn_out_multiplier != 1.0:
            attn_out = attn_out * jnp.asarray(cfg.attn_out_multiplier, attn_out.dtype)
        return attn_out, k_full, v_full

    def make_layer_step(moe_layer: bool):
        # A whole-expert layer's padding mask, made once before the scan: made in the
        # layer's body it is one more instruction a layer (compiled for a v5e: PERF.md, PR 50).
        routed_valid = slot_mapping != 0 if moe_layer and not cfg.moe_held_share else None

        def ffn(lp, h2, counts: list):
            """The layer's FFN on the normed stream; a model that holds a share
            of its experts carries their counters beside the stream."""
            with jax.named_scope("mlp"):
                if not moe_layer:
                    return _mlp_dense(lp, h2, cfg.mlp_act, cfg.mlp_gate_multiplier, cfg.mlp_down_multiplier), counts
                if cfg.moe_held_share:
                    mlp, counted = _mlp_moe_held(lp, h2, cfg, slot_mapping != 0, mesh)
                    return mlp, [counts[0] + counted]
                return _mlp_moe(lp, h2, cfg, mesh, routed_valid), counts

        def layer_step(carry, lp):
            x, k_full, v_full, li, *counts = carry
            counts, rec = counts[:n_counts], counts[n_counts:]  # then a mixer's two state buffers
            kind = None
            if layer_kinds is not None:
                lp, kind = lp
            if moe_layer:  # the expert stack's layers are counted from the first MoE layer
                lp = join_expert_stack(lp, expert_stack, li - n_dense)
            h = rms_norm(x, lp["attn_norm"], eps=cfg.rms_eps, plus_one=cfg.norm_plus_one)
            if mla:
                from dynamo_tpu.models.mla import mla_attention

                with jax.named_scope("attn"):
                    attn_out, k_full, v_full = mla_attention(
                        lp, cfg, h, positions, k_full, v_full,
                        block_tables + li * npages,
                        slot_mapping + li * (npages * ps),
                        inv_freq_mla,
                        attn_mscale=attn_mscale,
                        ring=ring, mesh=mesh,
                        ring_positions=ring_pos if ring else None,
                        impl=attn_impl,
                        contiguous_positions=contiguous_positions,
                        split=split,
                    )
                x = x + attn_out
                h2 = rms_norm(x, lp["mlp_norm"], eps=cfg.rms_eps, plus_one=cfg.norm_plus_one)
                mlp, counts = ffn(lp, h2, counts)
                return (x + mlp, k_full, v_full, li + 1, *counts), None
            mixed = None
            if cfg.ssm_heads:
                # The mixer reads the same normed input as the attention and joins the
                # stream with it: one more term of the block, its slot beside the layer's pages.
                from dynamo_tpu.models.mamba2 import mamba_mixer

                with jax.named_scope("attn.ssm"):
                    mixed, *rec = mamba_mixer(lp, cfg, h, positions, slot_mapping != 0, *rec,
                                              recurrent[2] + li * ssm_slots, impl=attn_impl, split=split)
                    mixed = mixed * jnp.asarray(cfg.ssm_out_multiplier, mixed.dtype)
                if cfg.attn_in_multiplier != 1.0:
                    h = h * jnp.asarray(cfg.attn_in_multiplier, h.dtype)
            with jax.named_scope("attn"):  # projections, rope, cache write, attention, output
                attn_out, k_full, v_full = gqa_attention(lp, h, k_full, v_full, li, kind)
                x = x + (attn_out if mixed is None else mixed + attn_out)
            h2 = rms_norm(x, lp["mlp_norm"], eps=cfg.rms_eps, plus_one=cfg.norm_plus_one)
            mlp, counts = ffn(lp, h2, counts)
            x = x + mlp
            return (x, k_full, v_full, li + 1, *counts, *rec), None

        return layer_step

    def hybrid_stack(x, kf, vf, *counts):
        """Periods of ``cfg.layer_group_size`` layers: recurrent layers, whose
        state is a slot, and one layer that attends, whose state is pages.
        The configuration says which recurrent kind (KDA, its leaves under
        ``kda_layers``; a Mamba-2 mixer where ``cfg.ssm_heads``, under
        ``ssm_layers``), which attention kind (latent attention, ``mla_layers``;
        the GQA block, ``attn_layers``), where in the period the layer that
        attends sits (``cfg.period_attn_index``: Ling's last of 6, Granite's
        sixth of 10, Solar-Open2's first of 4) and which FFN a layer has (the
        first ``n_dense`` layers' dense, every later one routed, whole or a held share), and
        ``cfg.residual_multiplier`` scales each block's output. Two kinds of
        layer have two parameter trees and two kinds of state, so the stack
        is a scan over periods around loops over the period's recurrent
        layers; a layer reads its leaves from the stacks by index (what a
        scan's own slicing does), so that each layer body is compiled once
        whatever the depth (twice the recurrent one where the layer that
        attends stands inside the period)."""
        from dynamo_tpu.models.kda import kda_attention
        from dynamo_tpu.models.mamba2 import mamba_mixer
        from dynamo_tpu.models.mla import mla_attention

        group, n_rec, attends = cfg.layer_group_size, cfg.recurrent_layers, cfg.period_attn_index
        if ring or layer_kinds is not None or cfg.first_k_dense != n_dense or n_dense > attends:
            raise NotImplementedError(
                "a hybrid stack is served by the paged path (no ring attention, no window layers), its leading dense "
                "FFNs as stated and under recurrent layers; not served: two layers that attend in a period, a "
                "recurrent kind per layer, head counts per attention kind")
        state, conv, slot_ids = recurrent
        slots = state.shape[0] // n_rec
        valid = slot_mapping != 0
        at = lambda tree, i: jax.tree.map(lambda a: a[i], tree)  # noqa: E731
        res = cfg.residual_multiplier
        joined = lambda x, out: x + (out if res == 1.0 else out * jnp.asarray(res, out.dtype))  # noqa: E731

        def ffn(carry, lp, i_moe):
            x, kf, vf, state, conv, *counts = carry
            h2 = rms_norm(x, lp["mlp_norm"], eps=cfg.rms_eps)
            with jax.named_scope("mlp"):
                if i_moe is None:
                    mlp = _mlp_dense(lp, h2, cfg.mlp_act)
                elif cfg.moe_held_share:
                    mlp, counted = _mlp_moe_held(join_expert_stack(lp, expert_stack, i_moe), h2, cfg, valid, mesh)
                    counts = [counts[0] + counted]
                else:
                    mlp = _mlp_moe(join_expert_stack(lp, expert_stack, i_moe), h2, cfg, mesh, valid)
            return (joined(x, mlp), kf, vf, state, conv, *counts)

        def recurrent_layer(carry, i_rec, ffn_layers, i_ffn, routed: bool):
            x, kf, vf, state, conv, *counts = carry
            lp = at(ffn_layers, i_ffn)
            h = rms_norm(x, lp["attn_norm"], eps=cfg.rms_eps)
            if cfg.ssm_heads:
                with jax.named_scope("attn.ssm"):
                    out, state, conv = mamba_mixer(
                        at(params["ssm_layers"], i_rec), cfg, h, positions, valid, state, conv,
                        slot_ids + i_rec * slots, impl=attn_impl, split=split)
            else:
                with jax.named_scope("attn.kda"):
                    out, state, conv = kda_attention(
                        at(params["kda_layers"], i_rec), cfg, h, positions, valid, state, conv,
                        slot_ids + i_rec * slots, impl=attn_impl, split=split)
            return ffn((joined(x, out), kf, vf, state, conv, *counts), lp, i_ffn if routed else None)

        def attention_layer(carry, i_attn, i_ffn):
            x, kf, vf, state, conv, *counts = carry
            lp = at(moe_layers, i_ffn)
            h = rms_norm(x, lp["attn_norm"], eps=cfg.rms_eps)
            with jax.named_scope("attn"):
                if mla:
                    out, kf, vf = mla_attention(
                        at(params["mla_layers"], i_attn), cfg, h, positions, kf, vf,
                        block_tables + i_attn * npages, slot_mapping + i_attn * (npages * ps), inv_freq_mla,
                        attn_mscale=attn_mscale, impl=attn_impl, split=split)
                else:
                    out, kf, vf = gqa_attention(at(params["attn_layers"], i_attn), h, kf, vf, i_attn)
            return ffn((joined(x, out), kf, vf, state, conv, *counts), lp, i_ffn)

        carry = (x, kf, vf, state, conv, *counts)
        if n_dense:
            carry, _ = jax.lax.scan(
                lambda c, i: (recurrent_layer(c, i, params["dense_layers"], i, False), None), carry, jnp.arange(n_dense))

        def period(carry, p):
            first = jnp.where(p == 0, n_dense, 0) if n_dense else 0  # the first period's dense layers are done
            if attends:  # the period's recurrent layers before the one that attends
                carry = jax.lax.fori_loop(
                    first, attends,
                    lambda j, c: recurrent_layer(c, p * (group - 1) + j, moe_layers, p * group + j - n_dense, True), carry)
            # (counted back from the period's end, not ``p * group + attends``: where the layer that attends closes the
            # period, Ling's, the traced arithmetic is then the parent's, and so is the compiled text of Ling's decode and
            # chunk programs down to its computations' names: ``tools/step_relayouts.py ling-3.0-flash-ep8-int8 64
            # --dump`` on both trees, ISSUE 49's acceptance; simplify it when Ling's programs next change anyway)
            carry = attention_layer(carry, p, p * group + group - (group - attends) - n_dense)
            if attends < group - 1:  # the period's recurrent layers after the one that attends
                carry = jax.lax.fori_loop(
                    attends, group - 1,
                    lambda j, c: recurrent_layer(c, p * (group - 1) + j, moe_layers, p * group + j + 1 - n_dense, True), carry)
            return carry, None

        carry, _ = jax.lax.scan(period, carry, jnp.arange(cfg.num_layers // group))
        return carry

    # Scan over layers: one layer's program is traced once — compile time is
    # O(1) in depth (matters at 70B/80-layer scale). Mixed DeepSeek stacks
    # (first_k_dense_replace) run two scans — dense layers first — with the
    # layer counter (cache offsets) carried straight through.
    # A model that holds a share of its experts (or has identity experts)
    # carries its expert layers' HELD_COUNTS beside the stream, summed.
    carry = (x, kf0, vf0, jnp.int32(0))
    n_counts = 0
    if cfg.moe_held_share:
        from dynamo_tpu.parallel.moe import HELD_COUNTS

        carry += (jnp.zeros((len(HELD_COUNTS),), jnp.int32),)
        n_counts = 1
    if cfg.ssm_heads and not cfg.layer_group_size:  # the plain body with a mixer: its two state buffers ride behind the counters
        if mla or ring or layer_kinds is not None:
            raise NotImplementedError("a Mamba-2 mixer is served beside GQA attention of one kind, by the paged path")
        ssm_slots = recurrent[0].shape[0] // cfg.num_layers
        carry += tuple(recurrent[:2])

    def scanned(layers, lo: int, hi: int):
        if layer_kinds is None:
            return layers
        return layers, {name: v[lo:hi] for name, v in layer_kinds.items()}

    state_out = ()
    if cfg.layer_group_size:
        x, k_out, v_out, *state_out = hybrid_stack(x, kf0, vf0, *carry[4:])
        counts, state_out = state_out[2:], state_out[:2] if keep_state else ()
    elif cfg.shortcut_moe:
        if layer_kinds is not None or not mla or n_dense:
            raise NotImplementedError("a shortcut-MoE layer is served with MLA sublayers, all alike")
        (x, k_out, v_out, _, *counts), _ = jax.lax.scan(shortcut_layer_step, carry, moe_layers)
    else:
        if "dense_layers" in params:
            carry, _ = jax.lax.scan(make_layer_step(False), carry, scanned(params["dense_layers"], 0, n_dense))
        (x, k_out, v_out, _, *counts), _ = jax.lax.scan(
            make_layer_step(cfg.is_moe),
            carry,
            scanned(moe_layers, n_dense, cfg.num_layers),
        )
        counts, state_out = counts[:n_counts], counts[n_counts:] if keep_state else ()
    k_out = k_out.reshape(k_cache.shape)
    v_out = v_out.reshape(v_cache.shape)
    extra = ((counts[0] if counts else None,) if moe_counts else ()) + tuple(state_out)

    x = rms_norm(x, params["norm_f"], eps=cfg.rms_eps, plus_one=cfg.norm_plus_one)
    # bf16 operands, f32 accumulate: no f32 materialization of the (huge)
    # embedding matrix per step; quantized lm_head goes through the shared
    # scale-after-dot helper.
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    if logit_indices is not None:
        # Speculative verify: score every candidate position in one head
        # matmul — V is small (spec_k + 1), so this stays cheap relative to
        # the layer stack it amortizes.
        sel = jnp.take_along_axis(x, logit_indices[:, :, None], axis=1)  # [B, V, D]
        logits = _qmm(sel, head, preferred_element_type=jnp.float32)  # [B, V, vocab]
        return (logits, k_out, v_out, *extra)
    if split is not None:
        last = x[0][last_token_index]  # [slots, D]
    else:
        last = jnp.take_along_axis(x, last_token_index[:, None, None], axis=1)[:, 0]  # [B, D]
    logits = _qmm(last, head, preferred_element_type=jnp.float32)  # [B, vocab]
    if cfg.lm_head_multiplier != 1.0:
        logits = logits * cfg.lm_head_multiplier
    return (logits, k_out, v_out, *extra)


def encode(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # i32[B, T]
    mask: jnp.ndarray,  # bool[B, T] — True on real tokens
    pooling: str = "mean",  # "mean" | "last"
) -> jnp.ndarray:
    """Sentence-embedding forward: pooled final hidden states, L2-normalized.

    Runs the same stacked-layer scan as :func:`forward` but with plain
    in-batch causal attention — no paged cache, nothing donated, so it can
    run concurrently with serving steps. Returns f32[B, D].

    BE EXPLICIT about what this is: embeddings come from the SERVING LM's
    hidden states (masked mean, or last-token with ``pooling="last"`` — the
    E5-Mistral-class recipe). Meaningful retrieval quality requires
    deploying a checkpoint actually trained for embeddings (e.g. a
    gte-Qwen2 / E5 model through the normal loader); on a plain chat
    checkpoint this endpoint is API-parity, not a quality claim.

    Parity: the reference's /v1/embeddings route + EmbeddingEngine adapter
    (`lib/llm/src/http/service/openai.rs:580`, `engines.rs:321`).
    """
    b, t = tokens.shape
    if cfg.mixed_attention or cfg.shortcut_moe or cfg.moe_held_share or cfg.recurrent_layers:
        raise NotImplementedError("encode() serves models whose layers are all alike (one window, one RoPE), "
                                  "hold one attention block each and all their experts")
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
    inv_freq = jnp.asarray(rope_frequencies(cfg.head_dim, theta=cfg.rope_theta, scaling=cfg.rope_scaling))
    attn_mscale = rope_attention_factor(cfg.rope_scaling) ** 2
    x = params["embed"][tokens]  # [B, T, D]
    if cfg.embed_scale:  # Gemma: embeddings scale by sqrt(hidden)
        x = x * jnp.asarray(cfg.hidden_size**0.5, x.dtype)

    causal = jnp.tril(jnp.ones((t, t), bool))
    if cfg.sliding_window > 0:
        causal = causal & (
            jnp.arange(t)[None, :] > jnp.arange(t)[:, None] - cfg.sliding_window
        )
    attendable = causal[None, :, :] & mask[:, None, :]  # [B, Tq, Tk]
    bias = jnp.where(attendable, 0.0, -jnp.inf).astype(jnp.float32)[:, None, :, :]
    groups = cfg.num_heads // cfg.num_kv_heads
    scale = cfg.head_dim**-0.5

    def make_layer_step(moe_layer: bool):
        def layer_step(x, lp):
            h = rms_norm(x, lp["attn_norm"], eps=cfg.rms_eps, plus_one=cfg.norm_plus_one)
            qp, kp, vp = _qmm(h, lp["wq"]), _qmm(h, lp["wk"]), _qmm(h, lp["wv"])
            if cfg.attention_bias:
                qp, kp, vp = qp + lp["bq"], kp + lp["bk"], vp + lp["bv"]
            if cfg.qk_norm == "flat":
                qp = rms_norm(qp, lp["q_norm"], eps=cfg.rms_eps)
                kp = rms_norm(kp, lp["k_norm"], eps=cfg.rms_eps)
            qh = qp.reshape(b, t, cfg.num_heads, cfg.head_dim)
            kh = kp.reshape(b, t, cfg.num_kv_heads, cfg.head_dim)
            if cfg.qk_norm == "head":
                qh = rms_norm(qh, lp["q_norm"], eps=cfg.rms_eps)
                kh = rms_norm(kh, lp["k_norm"], eps=cfg.rms_eps)
            q = apply_rope(qh, positions, inv_freq)
            k = apply_rope(kh, positions, inv_freq)
            if attn_mscale != 1.0:  # YaRN temperature: logits scale by mscale^2
                q = q * jnp.asarray(attn_mscale, q.dtype)
            v = vp.reshape(b, t, cfg.num_kv_heads, cfg.head_dim)
            q = q.reshape(b, t, cfg.num_kv_heads, groups, cfg.head_dim)
            scores = jnp.einsum("bqhgd,bkhd->bhgqk", q, k).astype(jnp.float32) * scale
            scores = scores + bias[:, :, None, :, :]
            probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
            attn = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v).reshape(b, t, cfg.q_dim)
            x = x + _qmm(attn, lp["wo"])
            h2 = rms_norm(x, lp["mlp_norm"], eps=cfg.rms_eps, plus_one=cfg.norm_plus_one)
            mlp = _mlp_moe(lp, h2, cfg) if moe_layer else _mlp_dense(lp, h2, cfg.mlp_act)
            return x + mlp, None

        return layer_step

    if "dense_layers" in params:
        x, _ = jax.lax.scan(make_layer_step(False), x, params["dense_layers"])
    x, _ = jax.lax.scan(make_layer_step(cfg.is_moe), x, params["layers"])
    x = rms_norm(x, params["norm_f"], eps=cfg.rms_eps, plus_one=cfg.norm_plus_one).astype(jnp.float32)
    m = mask[:, :, None].astype(jnp.float32)
    if pooling == "last":
        # Last real token's hidden state — the recipe instruction-tuned
        # embedders (E5-Mistral / gte-Qwen class) are trained with.
        last = jnp.maximum(mask.sum(1) - 1, 0)  # [B]
        pooled = jnp.take_along_axis(x, last[:, None, None].astype(jnp.int32), axis=1)[:, 0]
    else:
        pooled = (x * m).sum(1) / jnp.maximum(m.sum(1), 1.0)
    return pooled / jnp.maximum(jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-9)
