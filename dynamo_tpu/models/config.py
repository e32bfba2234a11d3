"""Model architecture configs + presets.

``ModelConfig`` is the single architecture description consumed by model
forwards, weight loaders, the engine's cache sizing, and the planner's memory
model. Convertible from HF `config.json` (`from_hf`).
"""

from __future__ import annotations

import dataclasses
import json
import math
import pathlib
from typing import Any


SLIDING, FULL = "sliding_attention", "full_attention"
#: Families whose full-attention layers take no rotary embedding where window
#: and full layers are mixed (EXAONE 4.0's hybrid attention and its MoE successor).
NOPE_IN_FULL_LAYERS = ("exaone4", "exaone_moe")


def _split_rope(params: dict) -> tuple[float, dict | None]:
    """One HF `rope_parameters` entry -> (theta, rope_scaling dict or None)."""
    if "rope_theta" not in params:
        raise ValueError(f"rope_parameters entry {params!r} carries no rope_theta")
    rest = {k: v for k, v in params.items() if k != "rope_theta"}
    plain = rest.get("rope_type", rest.get("type")) in (None, "default")
    return float(params["rope_theta"]), (None if plain else rest)


def _attention_layout(config: dict) -> dict:
    """The window, the per-layer kinds and the RoPE(s) of an HF config, as
    ModelConfig fields. Refuses by name what it cannot serve faithfully."""
    n = config["num_hidden_layers"]
    window = int(config.get("sliding_window") or 0)
    kinds = config.get("layer_types")
    mlp_kinds = config.get("mlp_layer_types")
    # A per-layer list may run past ``num_hidden_layers`` (a file that serves the
    # model's first layers keeps the published lists whole): the entries of
    # the layers held are read, a list shorter than them is refused.
    if mlp_kinds is not None:
        # The leading ``first_k_dense_replace`` layers are dense, every later one routed.
        k_dense = int(config.get("first_k_dense_replace", 0) or 0)
        if len(mlp_kinds) < n or list(mlp_kinds[:n]) != ["dense"] * min(k_dense, n) + ["sparse"] * max(n - k_dense, 0):
            raise ValueError(
                f"mlp_layer_types {sorted(set(mlp_kinds))} over {len(mlp_kinds)} entries: only 'dense' in the first "
                f"{k_dense} layers (first_k_dense_replace) and 'sparse' in each later one of the {n} is supported")
    if kinds is not None:
        held, kinds = len(kinds), tuple(kinds[:n])
        unknown = sorted(set(kinds) - {SLIDING, FULL})
        if unknown or held < n:
            raise ValueError(
                f"layer_types holds {unknown or held}: expected {n} entries of "
                f"{SLIDING!r} / {FULL!r}")
        if SLIDING in kinds and not config.get("use_sliding_window", True):
            raise ValueError("use_sliding_window is false but layer_types names sliding_attention layers")
        if SLIDING in kinds and window <= 0:
            raise ValueError("layer_types names sliding_attention layers but sliding_window is not set")
        if SLIDING not in kinds:
            window = 0
        # Where a config states the windows or the pattern a second time, they agree with layer_types.
        per_layer = config.get("sliding_windows")
        if per_layer is not None and list(per_layer[:n]) != [window if k == SLIDING else 0 for k in kinds]:
            raise ValueError(f"sliding_windows {list(per_layer)[:8]}... disagrees with layer_types and sliding_window {window}")
        pattern = config.get("sliding_window_pattern")
        if isinstance(pattern, str) and pattern and tuple(
                {"L": SLIDING, "G": FULL}.get(pattern[i % len(pattern)]) for i in range(n)) != kinds:
            raise ValueError(f"sliding_window_pattern {pattern!r} disagrees with layer_types")
        if len(set(kinds)) == 1:
            kinds = ()  # all alike: the one global window says it all
    else:
        # HF gates the window: Qwen2-family configs carry sliding_window
        # together with use_sliding_window=false (full causal). Without
        # layer_types the key is adopted only when the gate is on (absent =
        # on, Mistral-style) and max_window_layers, where given, covers every
        # layer. A partial count names a mix this config does not spell out
        # layer by layer, and 0 is no layer at all: full attention, never
        # "every layer".
        kinds = ()
        mwl = config.get("max_window_layers")
        if not config.get("use_sliding_window", True) or (mwl is not None and int(mwl) < n):
            window = 0
    out = dict(sliding_window=window, layer_types=kinds)
    by_kind = config.get("rope_parameters")
    if by_kind and set(by_kind) & {SLIDING, FULL}:
        used = set(kinds) or {SLIDING if window else FULL}
        missing = sorted(used - set(by_kind))
        if missing:
            raise ValueError(f"rope_parameters has no entry for {missing}")
        ropes = {k: _split_rope(by_kind[k]) for k in sorted(used)}
        if len(set(map(repr, ropes.values()))) == 1:
            theta, scaling = next(iter(ropes.values()))
        else:  # the top-level pair mirrors the full layers; forward reads by kind
            theta, scaling = ropes[FULL]
            out["rope_parameters"] = {k: dict(by_kind[k]) for k in sorted(used)}
    elif by_kind and config.get("model_type") in NOPE_IN_FULL_LAYERS and len(set(kinds)) > 1:
        # One flat set, which is the sliding layers': the full layers do not
        # rotate (an identity table is a kind like any other, models/llama.py).
        theta, scaling = _split_rope(by_kind)
        if scaling is not None:
            raise ValueError(f"rope_type {scaling.get('rope_type', scaling.get('type'))!r} is not served for "
                             f"model_type {config['model_type']!r}: only 'default' in its sliding layers")
        out["rope_parameters"] = {FULL: {"rope_type": "nope", "rope_theta": theta}, SLIDING: dict(by_kind)}
    elif by_kind:  # one flat set of parameters for every layer
        theta, scaling = _split_rope(by_kind)
    elif "rope_theta" in config:
        theta, scaling = float(config["rope_theta"]), config.get("rope_scaling")
    else:
        theta, scaling = 10000.0, config.get("rope_scaling")
    return dict(out, rope_theta=theta, rope_scaling=scaling)


def _expert_share(config: dict, held_key: str = "n_routed_experts") -> tuple[int, int, int]:
    """(held, total, first id) of the routed experts a config states: all of
    them, or the share a file gives as ``held_key`` held here of
    ``n_routed_experts_published``, the ``expert_share_rank``-th such share."""
    held = int(config[held_key])
    total = int(config.get("n_routed_experts_published", held))
    first = int(config.get("expert_share_rank", 0)) * held
    if held <= 0 or first + held > total:
        raise ValueError(f"experts [{first}, {first + held}) lie outside the {total} published")
    return held, total, first


def _refuse_unserved(config: dict, served: dict, model_type: str) -> None:
    """Refuses by name every key of ``served`` that ``config`` sets otherwise
    (a falsy key is a falsy key, however it is spelt)."""
    for key, want in served.items():
        got = config.get(key, want)
        if got != want and (got or want):
            raise ValueError(f"{key} {got!r} is not served for model_type {model_type!r}: only {want!r}")


def _group_limit(config: dict, held: int, total: int) -> tuple[int, int]:
    """(n_group, topk_group) of a group-limited router over ``total`` experts
    of which ``held`` are held here; one group is no group limit: (0, 0), no
    group top-k in the program. A share holds whole groups."""
    n_group = int(config.get("n_group", 0) or 0)
    if n_group <= 1:
        return 0, 0
    if held != total and (total % n_group or held % (total // n_group)):
        raise ValueError(f"a held share of {held} experts splits a routing group of "
                         f"{total // n_group} (n_group {n_group} over {total}): a share holds whole groups")
    return n_group, int(config.get("topk_group", 0) or 0)


def _stage_layers(config: dict) -> int:
    """Layers held here of a file that states a pipeline stage
    (``num_hidden_layers_published`` beside ``num_hidden_layers`` held here,
    ``pipeline_stages`` equal stages, this one ``stage_rank``): a stage holds
    an equal share of the layers."""
    layers, stages = int(config["num_hidden_layers"]), int(config.get("pipeline_stages", 1))
    published = int(config.get("num_hidden_layers_published", layers * stages))
    if layers * stages != published or not 0 <= int(config.get("stage_rank", 0)) < stages:
        raise ValueError(f"num_hidden_layers {layers} x pipeline_stages {stages} (stage_rank "
                         f"{config.get('stage_rank', 0)}) is not num_hidden_layers_published {published}: "
                         "a stage holds an equal share of the layers")
    return layers


def _period_layout(attending: list[int], listed: int, layers: int, key: str) -> tuple[int, int]:
    """(layers a period, the place in it of the one layer that attends) of a
    config whose ``key`` puts the layers that attend at ``attending`` of the
    ``listed`` layers it describes, ``layers`` of them held here: periods all
    alike, whole ones held."""
    period = attending[1] - attending[0] if len(attending) > 1 else listed
    if not attending or period < 2 or attending != list(range(attending[0], listed, period)) or attending[0] >= period:
        raise ValueError(f"{key} with 'attention' at {attending[:6]} is not served: periods of recurrent layers "
                         "with one attention layer at the same place in each")
    if layers % period:
        raise ValueError(f"num_hidden_layers {layers} is not whole periods of {period} layers ({key}): not served")
    return period, attending[0]


def _heads_per_row(head_dim: int, per_group: int) -> int:
    """Heads of a Mamba-2 state that lie side by side on the 128 lanes of one
    buffer row (``ModelConfig.ssm_heads_per_row``): ``128 // head_dim`` for
    heads narrower than the lanes where a group's heads fill whole rows, else 1."""
    side = 128 // head_dim if 0 < head_dim < 128 and 128 % head_dim == 0 else 1
    return side if per_group % side == 0 else 1


def _mamba_sizes(config: dict, inner: int, what: str) -> tuple[int, int, int, int]:
    """(heads, head channels, groups, state) of a config's Mamba-2 keys, held
    to the ``inner`` channels that ``what`` states. (A state the decode kernel
    does not tile is refused where the kernel is chosen, on a chip at any
    size: ``models/mamba2._rows_update``.)"""
    heads, head_dim, groups = int(config["mamba_n_heads"]), int(config["mamba_d_head"]), int(config["mamba_n_groups"])
    state = int(config["mamba_d_state"])
    if heads * head_dim != inner:
        raise ValueError(f"mamba_n_heads {heads} x mamba_d_head {head_dim} is not {what} {inner}: not served")
    if groups <= 0 or heads % groups:
        raise ValueError(f"mamba_n_heads {heads} is not a multiple of mamba_n_groups {groups}: not served")
    return heads, head_dim, groups, state


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    vocab_size: int
    hidden_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    intermediate_size: int
    rope_theta: float = 500000.0
    rope_scaling: dict | None = None
    rms_eps: float = 1e-5
    max_position: int = 131072
    tie_embeddings: bool = False
    dtype: str = "bfloat16"
    # MoE fields (0 experts = dense).
    num_experts: int = 0
    num_experts_per_token: int = 0
    moe_intermediate_size: int = 0
    # Per-expert buffer headroom for the dispatched (expert-parallel) MoE
    # path; <= 0 means no-drop capacity (exact, memory-heavier).
    moe_capacity_factor: float = 1.25
    # Always-on shared expert alongside the routed ones (Qwen2-MoE /
    # DeepSeek): total hidden width of the shared FFN; 0 disables.
    shared_expert_size: int = 0
    # Qwen2-MoE gates the shared expert with sigmoid(x @ g); DeepSeek doesn't.
    shared_expert_gated: bool = False
    # Router semantics (parallel/moe.py:route_tokens). DeepSeek-V3:
    # sigmoid scoring + aux-free e_score_correction_bias (noaux_tc) +
    # group-limited top-k + routed scaling; Mixtral: softmax + renorm;
    # Qwen2-MoE: softmax without renorm.
    moe_scoring: str = "softmax"  # "softmax" | "sigmoid"
    moe_norm_topk: bool = True  # renormalize the top-k weights
    moe_routed_scaling: float = 1.0  # DeepSeek routed_scaling_factor
    moe_n_group: int = 0  # group-limited routing (V3 n_group); 0 = off
    moe_topk_group: int = 0
    moe_router_bias: bool = False  # e_score_correction_bias present (noaux_tc)
    # DeepSeek first_k_dense_replace: the first k layers use a dense MLP
    # (params["dense_layers"]) while the rest are MoE (params["layers"]).
    first_k_dense: int = 0
    # Biases on q/k/v projections (Qwen2 family).
    attention_bias: bool = False
    # Gemma family: GeGLU MLP ("gelu_tanh"), zero-centered norm weights
    # ((1+w) convention), sqrt(hidden) embedding scaling.
    mlp_act: str = "silu"  # "silu" | "gelu_tanh"
    norm_plus_one: bool = False
    embed_scale: bool = False
    # Q/K RMS-norm before rope: "" (none), "head" (per-head over head_dim —
    # Qwen3), "flat" (over the full projection width — OLMoE).
    qk_norm: str = ""
    # Sliding-window attention (Mistral): queries attend to the last
    # `sliding_window` positions only. 0 = full causal.
    sliding_window: int = 0
    # Attention kind of each layer where kinds are mixed in one model:
    # "sliding_attention" (the last `sliding_window` positions) or
    # "full_attention", one entry per layer. () = every layer alike (windowed
    # iff sliding_window > 0). A mixed model's cache is a page pool and a block
    # table per kind (models/llama.init_kv_cache): the full layers' pool seats
    # the context, the sliding layers' a window of pages a row.
    layer_types: tuple = ()
    # RoPE per attention kind where the kinds differ (HF `rope_parameters`
    # keyed by kind: rope_theta plus the rope_scaling keys). None = the one
    # rope_theta / rope_scaling above for every layer.
    rope_parameters: dict | None = None
    # Multimodal: the placeholder token id image embeddings substitute for
    # (None = text-only model); vision tower geometry lives in VisionConfig.
    image_token_id: int | None = None
    # Qwen2-VL M-RoPE: frequency-dim split for (temporal, height, width)
    # coordinates, e.g. (16, 24, 24). None = standard 1D rope.
    mrope_section: tuple | None = None
    video_token_id: int | None = None
    # Attention family: "gqa" (default) or "mla" (DeepSeek latent attention,
    # models/mla.py). MLA caches one latent + rope key per token.
    attn_type: str = "gqa"
    q_lora_rank: int = 0  # 0 = direct q projection
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # DeepSeek-V2/V3 checkpoints store the rope dims of q_b_proj /
    # kv_a_proj_with_mqa in interleaved pair order (HF `rope_interleave`,
    # default true there); the loader permutes them to the half-split
    # convention models/ops use (models/loader.py). False for every
    # non-MLA family: their HF checkpoints are already half-split.
    rope_interleave: bool = False
    # LongCat-Flash: ``mla_scale_q_lora`` / ``mla_scale_kv_lora``. The query
    # (both parts) and the KV latent are multiplied by these after their
    # latent norms: sqrt(hidden / rank) where the config switches them on.
    mla_scale_q: float = 1.0
    mla_scale_kv: float = 1.0
    # Shortcut-connected MoE layer (LongCat-Flash): one layer holds two
    # attention sublayers and two dense FFNs, and a MoE that reads the first
    # sublayer's post-attention norm and is added at the end of the layer
    # (models/llama.py). The cache holds ``cache_layers`` slabs.
    shortcut_moe: bool = False
    # Identity "zero-compute" experts: router outputs past the routed experts
    # whose term is the token itself times its routing weight.
    moe_zero_experts: int = 0
    # A share of the routed experts held here (expert parallelism, one chip's
    # part): the router scores ``moe_experts_total`` experts (0 = all
    # ``num_experts`` are held), this model holds ``num_experts`` of them
    # from id ``moe_expert_first`` on and computes their part of the result.
    moe_experts_total: int = 0
    moe_expert_first: int = 0
    # Hybrid stack (Ling-3.0's ``bailing_hybrid``): layers come in periods of
    # ``layer_group_size``; the last layer of a period is latent attention
    # (``attn_type`` "mla"), every other one a delta-rule linear-attention layer
    # (KDA, models/kda.py) whose per-sequence state is a fixed-size slot
    # beside the paged latent cache. 0 = every layer alike.
    layer_group_size: int = 0
    kda_conv_size: int = 4  # taps of the causal depthwise convolution on q, k and v
    kda_lower_bound: float = -5.0  # the log-decay lies in (kda_lower_bound, 0)
    # A Mamba-2 mixer beside the attention of every layer (Falcon-H1's
    # ``falcon_h1``, models/mamba2.py): both read the layer's normed input and
    # their outputs are summed, so a layer owns a slab of the page pool *and* a
    # slot. ``ssm_heads`` heads of ``ssm_head_dim`` channels, each with a float32
    # ``ssm_state_size x ssm_head_dim`` state; ``ssm_groups`` groups of heads
    # share a B and a C; a causal depthwise convolution of ``ssm_conv_size``
    # taps, with a bias, over x, B and C. 0 heads = no mixer.
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state_size: int = 0
    ssm_groups: int = 1
    ssm_conv_size: int = 4
    # muP multipliers (Falcon-H1), each where the published code has it; 1.0 = none.
    # ``ssm_multipliers`` scales the five sections [z | x | B | C | dt] of the
    # mixer's input projection.
    embed_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    attn_in_multiplier: float = 1.0
    attn_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    mlp_gate_multiplier: float = 1.0
    mlp_down_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: tuple = (1.0, 1.0, 1.0, 1.0, 1.0)
    # A period whose recurrent layers are Mamba-2 mixers that stand alone
    # (Granite-4.0-H's ``granitemoehybrid``): ``ssm_heads`` *and*
    # ``layer_group_size`` set. The one layer of a period that attends (GQA
    # where ``attn_type`` is "gqa") sits at ``group_attn_index`` of the period
    # (-1: its last layer, as in Ling's; 0: it opens the period, as in
    # Solar-Open2's KDA periods round a GQA layer), and holds pages and no
    # slot; every other layer holds a slot and no pages.
    group_attn_index: int = -1
    # Granite's multipliers: ``residual_multiplier`` on each block's output
    # before it joins the stream, ``attn_scale`` the softmax scale where it is
    # not ``head_dim ** -0.5`` (0.0: the usual scale).
    residual_multiplier: float = 1.0
    attn_scale: float = 0.0
    # The KDA layer's form where it is not Ling's (Solar-Open2's ``solar_open2``:
    # Kimi Linear's own, arXiv 2510.26692). ``kda_decay``: "bounded", ``g =
    # kda_lower_bound * sigmoid(exp(a_log) (a + dt_bias))``, or "softplus", ``g =
    # -exp(a_log) * softplus(a + dt_bias)``. ``kda_beta_scale``: the write strength
    # is that times a sigmoid (2.0: ``I - beta k k^T`` may have a negative
    # eigenvalue). ``kda_low_rank`` r > 0: the decay input ``a`` and the output
    # gate come through pairs ``[hidden, r] [r, q_dim]`` and the gate is a value a
    # channel; 0: a full-rank ``w_decay`` and a gate a head.
    kda_decay: str = "bounded"
    kda_beta_scale: float = 1.0
    kda_low_rank: int = 0
    # A sigmoid gate a channel on the GQA block's attention output, before its
    # output projection, from the layer's normed input (``w_out_gate [hidden, q_dim]``).
    attn_out_gate: bool = False

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def ssm_inner(self) -> int:
        """Channels of the mixer's x (and of its gate z): heads x head channels."""
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_dim(self) -> int:
        """Channels the mixer's convolution runs over: x, then B and C a group."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state_size

    @property
    def recurrent_layers(self) -> int:
        """Layers whose state is a slot: of a model in periods (KDA layers, or
        Mamba-2 layers that stand alone) all but one a period (instead of
        pages); every layer of a model with a mixer beside its attention
        (beside its pages)."""
        g = self.layer_group_size
        if g:
            return self.num_layers - self.num_layers // g
        return self.num_layers if self.ssm_heads else 0

    @property
    def period_attn_index(self) -> int:
        """Where in a period the layer that attends sits, from 0."""
        return self.group_attn_index % self.layer_group_size

    @property
    def cache_layers(self) -> int:
        """Slabs of the paged cache: one per attention (sub)layer that attends
        over the context (a recurrent layer of a period holds none; a layer
        with a mixer beside its attention holds one)."""
        g = self.layer_group_size
        attending = self.num_layers // g if g else self.num_layers
        return attending * (2 if self.shortcut_moe else 1)

    @property
    def ssm_heads_per_row(self) -> int:
        """Heads of a Mamba-2 state that lie side by side on the 128 lanes of
        one buffer row: 1 where a head's channels are whole lane tiles (or
        tile nothing, a toy); ``128 // ssm_head_dim`` for narrower heads (two
        heads of 64 channels) where a group's heads fill whole rows, so that
        the buffer is not padded to the lanes and a row of it serves that many
        heads of one group at once (``ops/pallas_mamba.py``)."""
        return _heads_per_row(self.ssm_head_dim, self.ssm_heads // self.ssm_groups)

    def state_shapes(self) -> tuple[tuple[int, ...], tuple[int, ...]]:
        """One recurrent layer's part of a slot, by the kind of its state:
        (the float32 state, the conv state in the model's dtype). KDA: a
        ``key x value`` matrix a head and the last ``taps - 1`` inputs of the
        q, k and v streams. Mamba-2: a ``state x head channels`` matrix a head
        (state-major, the transposition of the published cache's; heads
        narrower than the lanes ``ssm_heads_per_row`` side by side, ``[heads /
        side, state, side x head channels]``) and the last
        ``taps - 1`` inputs of x, B and C. The conv state's channels lie in
        rows of 128 lanes, ``(taps - 1, channels / 128, 128)``, the rows rounded
        up to whole float32 sublane tiles of 8 (8,448 channels: 66 rows in 72;
        the device would lay a 66-row buffer out with the slots on the
        sublanes, and every step would re-lay it; a width that is no multiple
        of 128: one row), so that the device's tiles hold a slot's inputs
        whole and the buffer needs no other layout than the one it is
        allocated in (``models/kda.slot_conv``)."""
        if self.ssm_heads:
            side = self.ssm_heads_per_row
            state, taps, channels = ((self.ssm_heads // side, self.ssm_state_size, side * self.ssm_head_dim),
                                     self.ssm_conv_size, self.ssm_conv_dim)
        else:
            state, taps, channels = (self.num_heads, self.head_dim, self.head_dim), self.kda_conv_size, 3 * self.q_dim
        if channels % 128:
            return state, (taps - 1, 1, channels)
        return state, (taps - 1, -(-channels // 128 // 8) * 8, 128)

    def state_bytes_per_slot(self) -> int:
        """Bytes of recurrent state one sequence holds over all recurrent
        layers (``state_shapes``: float32, and the conv state at the dtype's width)."""
        itemsize = 2 if self.dtype == "bfloat16" else 4
        state, conv = self.state_shapes()
        return self.recurrent_layers * (math.prod(state) * 4 + math.prod(conv) * itemsize)

    @property
    def routed_experts(self) -> int:
        """Routed experts the router scores, held here or not."""
        return self.moe_experts_total or self.num_experts

    @property
    def router_outputs(self) -> int:
        return self.routed_experts + self.moe_zero_experts

    @property
    def moe_held_share(self) -> bool:
        """Some router outputs are not experts held here (a share, or zero
        experts): the expert layer of ``parallel/moe.moe_mlp_held``."""
        return self.is_moe and (self.router_outputs != self.num_experts)

    @property
    def mixed_attention(self) -> bool:
        """Window and full attention layers side by side in one model."""
        return len(set(self.layer_types)) > 1

    def layer_windows(self) -> tuple[int, ...]:
        """Each layer's window in tokens, 0 for full attention."""
        if not self.layer_types:
            return (self.sliding_window,) * self.num_layers
        return tuple(self.sliding_window if k == SLIDING else 0 for k in self.layer_types)

    def rope_of(self, kind: str) -> tuple[float, dict | None]:
        """(theta, scaling dict or None) of one attention kind."""
        if not self.rope_parameters:
            return self.rope_theta, self.rope_scaling
        return _split_rope(self.rope_parameters[kind])

    def cache_layers_of(self, kind: str) -> int:
        """Cache slabs of one attention kind (``SLIDING`` / ``FULL``) in a mixed model."""
        return sum(k == kind for k in self.layer_types)

    def kv_bytes_per_token(self, itemsize: int | None = None, kind: str | None = None) -> int:
        """Bytes of KV cache a token of context adds (2 = K and V; MLA caches
        one latent + rope key instead): over all layers, and in a model that
        mixes window and full layers over the full layers, whose pool seats
        the context (a sliding layer holds a window of pages a row however
        long the context: ``kind=SLIDING`` gives a token's bytes in those).
        ``itemsize`` overrides the dtype-derived cache element size (e.g. a
        bf16 cache for an f32 model)."""
        if itemsize is None:
            itemsize = 2 if self.dtype == "bfloat16" else 4
        if self.mixed_attention:
            return 2 * self.cache_layers_of(kind or FULL) * self.kv_dim * itemsize
        if self.attn_type == "mla":
            # Physical bytes: the rope stream is padded to one 128-lane tile
            # (models/mla.py:mla_cache_widths — Mosaic DMA alignment).
            rope_width = max(self.qk_rope_head_dim, 128)
            return self.cache_layers * (self.kv_lora_rank + rope_width) * itemsize
        return 2 * self.cache_layers * self.kv_dim * itemsize

    def param_count(self) -> int:
        """Parameters held here: of a share of the experts, the held ones."""
        d = self.hidden_size
        embed = self.vocab_size * d
        if self.attn_type == "mla":
            dq = self.num_heads * (self.qk_nope_head_dim + self.qk_rope_head_dim)
            q = d * self.q_lora_rank + self.q_lora_rank * (dq + 1) if self.q_lora_rank else d * dq
            attn = (q + d * (self.kv_lora_rank + self.qk_rope_head_dim) + self.kv_lora_rank
                    + self.kv_lora_rank * self.num_heads * (self.qk_nope_head_dim + self.v_head_dim)
                    + self.num_heads * self.v_head_dim * d)
        else:
            attn = d * (self.q_dim + 2 * self.kv_dim) + self.q_dim * d + d * self.q_dim * self.attn_out_gate
        dense = 3 * d * self.intermediate_size
        moe = (self.num_experts * 3 * d * self.moe_intermediate_size + d * self.router_outputs
               + 3 * d * self.shared_expert_size + (d if self.shared_expert_gated else 0))
        norms = 2 * d
        head = 0 if self.tie_embeddings else embed
        if self.shortcut_moe:  # two attention blocks, two dense FFNs and the experts in one layer
            return embed + head + d + self.num_layers * (2 * (attn + dense + norms) + moe)
        k_dense = self.first_k_dense if self.is_moe else self.num_layers
        # A mixer: two projections, the filter and its bias, A_log, dt_bias and D a head, the gated norm.
        inner = self.ssm_inner
        mixer = (d * (inner + self.ssm_conv_dim + self.ssm_heads) + inner * d
                 + (self.ssm_conv_size + 1) * self.ssm_conv_dim + 3 * self.ssm_heads + inner) if inner else 0
        if self.layer_group_size and inner:  # a period of Mamba-2 layers and one that attends
            return (embed + head + d + self.recurrent_layers * mixer + self.cache_layers * attn
                    + self.num_layers * norms + k_dense * dense + (self.num_layers - k_dense) * moe)
        if self.layer_group_size:
            # KDA: four full projections, the decay input and the output gate (full rank and a value a head, or a
            # low-rank pair each), the write strength, filters, decay constants, head norm.
            q, r = self.q_dim, self.kda_low_rank
            forms = 2 * (d * r + r * q) if r else d * q + d * self.num_heads
            kda = (4 * d * q + forms + d * self.num_heads + 3 * self.kda_conv_size * q + self.num_heads + q + self.head_dim)
            n_kda = self.recurrent_layers
            gate = d * self.num_heads if self.attn_type == "mla" else 0  # the latent-attention layers' head-wise output gate
            return (embed + head + d + n_kda * kda + (self.num_layers - n_kda) * (attn + gate)
                    + self.num_layers * norms + k_dense * dense + (self.num_layers - k_dense) * moe)
        return (embed + head + d + self.num_layers * (attn + mixer + norms)
                + k_dense * dense + (self.num_layers - k_dense) * moe)

    @classmethod
    def _from_longcat(cls, config: dict, name: str | None) -> "ModelConfig":
        """LongCat-Flash's config.json (``num_layers`` double layers, ``ffn_hidden_size``,
        ``expert_ffn_hidden_size``, ``moe_topk``, ``zero_expert_num``, ``mla_scale_*``).
        A file that states a share (``n_routed_experts_published`` beside
        ``n_routed_experts`` held here, of rank ``expert_share_rank``) gives a model
        that holds that share. Refuses by name what the layer does not compute."""
        if config.get("attention_method", "MLA") != "MLA":
            raise ValueError(f"attention_method {config['attention_method']!r} is not served: only 'MLA'")
        zero = int(config.get("zero_expert_num", 0) or 0)
        if zero and config.get("zero_expert_type") != "identity":
            raise ValueError(f"zero_expert_type {config.get('zero_expert_type')!r} is not served: only 'identity'")
        if config.get("rope_scaling"):
            raise ValueError("rope_scaling on a shortcut-MoE MLA model is not served")
        hidden, heads = config["hidden_size"], config["num_attention_heads"]
        held, total, first = _expert_share(config)
        return cls(
            name=name or config.get("_name_or_path", "longcat-flash"),
            vocab_size=config["vocab_size"], hidden_size=hidden, num_layers=config["num_layers"],
            num_heads=heads, num_kv_heads=heads, head_dim=config["v_head_dim"],
            intermediate_size=config["ffn_hidden_size"],
            rope_theta=float(config.get("rope_theta", 10000.0)), rope_scaling=None,
            rms_eps=config.get("rms_norm_eps", 1e-5),
            max_position=config.get("max_position_embeddings", 8192),
            tie_embeddings=bool(config.get("tie_word_embeddings", False)),
            num_experts=held, num_experts_per_token=int(config["moe_topk"]),
            moe_intermediate_size=config["expert_ffn_hidden_size"],
            moe_experts_total=total if total != held else 0, moe_expert_first=first,
            moe_zero_experts=zero,
            # Softmax over every router output, the top-k by score plus the
            # balancing bias, the scores themselves (not renormalised) times the factor.
            moe_scoring="softmax", moe_norm_topk=False, moe_router_bias=True,
            moe_routed_scaling=float(config.get("routed_scaling_factor", 1.0) or 1.0),
            attn_type="mla", q_lora_rank=config["q_lora_rank"], kv_lora_rank=config["kv_lora_rank"],
            qk_nope_head_dim=config["qk_nope_head_dim"], qk_rope_head_dim=config["qk_rope_head_dim"],
            v_head_dim=config["v_head_dim"],
            mla_scale_q=(hidden / config["q_lora_rank"]) ** 0.5 if config.get("mla_scale_q_lora") else 1.0,
            mla_scale_kv=(hidden / config["kv_lora_rank"]) ** 0.5 if config.get("mla_scale_kv_lora") else 1.0,
            attention_bias=False, shortcut_moe=True,
        )

    @classmethod
    def _from_bailing_hybrid(cls, config: dict, name: str | None) -> "ModelConfig":
        """Ling-3.0's config.json (``model_type`` ``bailing_hybrid``): KDA
        layers with one latent-attention layer every ``layer_group_size``,
        ``first_k_dense_replace`` leading dense FFNs, then a sigmoid router
        over ``num_experts`` limited to ``topk_group`` of ``n_group`` groups,
        and ``num_shared_experts`` shared experts. A file that states a share
        (``n_routed_experts_published`` beside ``num_experts`` held here, of
        rank ``expert_share_rank``) gives a model that holds that share.
        Refuses by name what the layers do not compute; keys that decide
        nothing at inference as set (``max_window_layers``, ``use_nGPT``,
        ``up_proj_norm``, ``use_mla_nope``, ``seq_aux``, ``mtp_*``,
        ``num_nextn_predict_layers``, ``partial_rotary_factor``, ``rotary_dim``)
        are taken without complaint."""
        layers, group = int(config["num_hidden_layers"]), int(config["layer_group_size"])
        if group < 2 or layers % group:
            raise ValueError(f"layer_group_size {group} over {layers} layers is not served: whole periods of "
                             "linear-attention layers closed by one latent-attention layer")
        first_dense = int(config.get("first_k_dense_replace", 0) or 0)
        if first_dense >= group:
            raise ValueError(f"first_k_dense_replace {first_dense} reaches the first latent-attention layer "
                             f"(layer_group_size {group}) is not served: the dense FFNs lie under linear-attention layers")
        unserved = {
            "use_kda_lora": False, "value_norm": False, "use_qkv_bias": False, "use_bias": False, "use_nGPT": False,
            "up_proj_norm": False, "scale_router_input": False, "num_kv_heads_for_linear_attn": 0,
            "linear_silu": True, "use_qk_norm": True, "kda_safe_gate": True, "group_norm_size": 1,
            "gated_attention_proj_granularity_type": "head_wise", "score_function": "sigmoid",
            "topk_method": "noaux_tc", "moe_router_enable_expert_bias": True, "hidden_act": "silu",
            "q_lora_rank": None, "rope_scaling": None,
        }
        _refuse_unserved(config, unserved, "bailing_hybrid")
        for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
            limits = list(config.get(key) or [])
            held = [(i, v) for i, v in enumerate(limits[:layers]) if v]
            if held:
                raise ValueError(f"{key} is {held[0][1]!r} at layer {held[0][0]}, a layer held here: a clamped SwiGLU "
                                 "is not served (entries of layers past num_hidden_layers are not read)")
        hidden, heads = config["hidden_size"], config["num_attention_heads"]
        held, total, first = _expert_share(config, "num_experts")
        n_group, topk_group = _group_limit(config, held, total)
        return cls(
            name=name or config.get("_name_or_path", "bailing_hybrid"),
            vocab_size=config["vocab_size"], hidden_size=hidden, num_layers=layers,
            num_heads=heads, num_kv_heads=heads, head_dim=config.get("head_dim") or hidden // heads,
            intermediate_size=config["intermediate_size"],
            rope_theta=float(config.get("rope_theta", 10000.0)), rope_scaling=None,
            rms_eps=config.get("rms_norm_eps", 1e-6),
            max_position=config.get("max_position_embeddings", 8192),
            tie_embeddings=bool(config.get("tie_word_embeddings", False)),
            num_experts=held, num_experts_per_token=int(config["num_experts_per_tok"]),
            moe_intermediate_size=config["moe_intermediate_size"],
            moe_experts_total=total if total != held else 0, moe_expert_first=first,
            shared_expert_size=int(config.get("num_shared_experts", 0) or 0) * int(
                config.get("moe_shared_expert_intermediate_size") or config["moe_intermediate_size"]),
            moe_scoring="sigmoid", moe_norm_topk=bool(config.get("norm_topk_prob", True)), moe_router_bias=True,
            moe_routed_scaling=float(config.get("routed_scaling_factor", 1.0) or 1.0),
            moe_n_group=n_group, moe_topk_group=topk_group, first_k_dense=first_dense,
            attn_type="mla", q_lora_rank=0, kv_lora_rank=config["kv_lora_rank"],
            qk_nope_head_dim=config["qk_nope_head_dim"], qk_rope_head_dim=config["qk_rope_head_dim"],
            v_head_dim=config["v_head_dim"], rope_interleave=bool(config.get("rope_interleave", True)),
            attention_bias=False, layer_group_size=group,
            kda_conv_size=int(config.get("short_conv_kernel_size", 4)),
            kda_lower_bound=float(config.get("kda_lower_bound", -5.0)),
        )

    @classmethod
    def _from_falcon_h1(cls, config: dict, name: str | None) -> "ModelConfig":
        """Falcon-H1's config.json (``model_type`` ``falcon_h1``): every layer
        a Mamba-2 mixer and GQA attention side by side on one normed input,
        their outputs summed, then a SwiGLU FFN; a muP multiplier on the
        embedding, on the mixer's input and the five sections of its
        projection, on the keys, on each block's output, inside the FFN and on
        the logits. A file that states a pipeline stage
        (``num_hidden_layers_published`` beside ``num_hidden_layers`` held here,
        ``pipeline_stages`` equal stages) gives a model of that many layers.
        Refuses by name what the layer does not compute; ``mamba_chunk_size``
        tiles the published kernels and changes no mathematics (the program's
        chunk is the engine's), ``mamba_expand`` is overridden by
        ``mamba_d_ssm``, ``mamba_use_mlp`` and ``num_logits_to_keep`` decide
        nothing here: taken without complaint."""
        unserved = {
            "mamba_norm_before_gate": False, "mamba_rms_norm": True, "mamba_conv_bias": True, "mamba_proj_bias": False,
            "projectors_bias": False, "attention_bias": False, "mlp_bias": False,
            "attn_layer_indices": None, "rope_scaling": None, "hidden_act": "silu", "tie_word_embeddings": False,
        }
        _refuse_unserved(config, unserved, "falcon_h1")
        hidden, layers = config["hidden_size"], _stage_layers(config)
        d_ssm = config.get("mamba_d_ssm")
        d_ssm = int(config["mamba_expand"] * hidden) if d_ssm is None else int(d_ssm)
        heads, head_dim, groups, state = _mamba_sizes(config, d_ssm, "mamba_d_ssm")
        mlp, ssm = config.get("mlp_multipliers") or (1.0, 1.0), config.get("ssm_multipliers") or (1.0,) * 5
        if len(mlp) != 2 or len(ssm) != 5:
            raise ValueError(f"mlp_multipliers {mlp!r} / ssm_multipliers {ssm!r}: expected 2 and 5 entries (z, x, B, C, dt)")
        attn_heads = config["num_attention_heads"]
        return cls(
            name=name or config.get("_name_or_path", "falcon_h1"),
            vocab_size=config["vocab_size"], hidden_size=hidden, num_layers=layers,
            num_heads=attn_heads, num_kv_heads=config.get("num_key_value_heads", attn_heads),
            head_dim=config.get("head_dim") or hidden // attn_heads, intermediate_size=config["intermediate_size"],
            rope_theta=float(config.get("rope_theta", 10000.0)), rope_scaling=None,
            rms_eps=config.get("rms_norm_eps", 1e-5), max_position=config.get("max_position_embeddings", 8192),
            tie_embeddings=False, attention_bias=False,
            ssm_heads=heads, ssm_head_dim=head_dim, ssm_state_size=state, ssm_groups=groups,
            ssm_conv_size=int(config.get("mamba_d_conv", 4)),
            embed_multiplier=float(config.get("embedding_multiplier", 1.0)),
            lm_head_multiplier=float(config.get("lm_head_multiplier", 1.0)),
            attn_in_multiplier=float(config.get("attention_in_multiplier", 1.0)),
            attn_out_multiplier=float(config.get("attention_out_multiplier", 1.0)),
            key_multiplier=float(config.get("key_multiplier", 1.0)),
            mlp_gate_multiplier=float(mlp[0]), mlp_down_multiplier=float(mlp[1]),
            ssm_in_multiplier=float(config.get("ssm_in_multiplier", 1.0)),
            ssm_out_multiplier=float(config.get("ssm_out_multiplier", 1.0)),
            ssm_multipliers=tuple(float(m) for m in ssm),
        )

    @classmethod
    def _from_granite_hybrid(cls, config: dict, name: str | None) -> "ModelConfig":
        """Granite-4.0-H's config.json (``model_type`` ``granitemoehybrid``): by
        ``layer_types`` a layer is a Mamba-2 mixer that stands alone or GQA
        attention without RoPE (``position_embedding_type`` ``nope``), one
        attention layer a period; every layer's FFN is ``num_local_experts``
        routed experts (the ``num_experts_per_tok`` largest router logits,
        softmax over those) beside a shared expert of
        ``shared_intermediate_size``; ``residual_multiplier`` on each block's
        output, ``attention_multiplier`` the softmax scale,
        ``embedding_multiplier`` on the embedding, ``logits_scaling`` a divisor
        of the logits. A file that states a pipeline stage gives a model of
        that many layers (``_stage_layers``), whole periods of them;
        ``layer_types`` may stay whole, the entries of the layers held are
        read. Refuses by name what the layers do not compute (a ``rope``
        sibling and the dense siblings, ``num_local_experts`` 0, are paths of
        their own and can follow); ``mamba_chunk_size`` tiles the published
        kernels and changes no mathematics, ``rope_theta`` rotates nothing:
        taken without complaint."""
        unserved = {
            "mamba_proj_bias": False, "attention_bias": False, "mamba_conv_bias": True, "position_embedding_type": "nope",
            "rope_scaling": None, "hidden_act": "silu", "normalization_function": "rmsnorm",
        }
        _refuse_unserved(config, unserved, "granitemoehybrid")
        hidden, layers = config["hidden_size"], _stage_layers(config)
        experts = int(config.get("num_local_experts", 0) or 0)
        if experts <= 0:
            raise ValueError(f"num_local_experts {experts} is not served for model_type 'granitemoehybrid': a layer whose "
                             "FFN is the shared MLP alone is a path of its own")
        heads, head_dim, groups, state = _mamba_sizes(config, int(config["mamba_expand"] * hidden), "mamba_expand x hidden_size")
        if groups != 1:  # GraniteMoeHybridRMSNormGated has no groups; the program's gated norm runs over a group's channels
            raise ValueError(f"mamba_n_groups {groups} is not served for model_type 'granitemoehybrid': only 1 (the published "
                             "gated norm runs over all the mixer's channels, the program's over each group's)")
        kinds = list(config.get("layer_types") or [])
        unknown = sorted(set(kinds) - {"mamba", "attention"})
        if unknown or len(kinds) < layers:
            raise ValueError(f"layer_types holds {unknown or len(kinds)}: expected at least {layers} entries of "
                             "'mamba' / 'attention'")
        period, attends = _period_layout([i for i, kind in enumerate(kinds) if kind == "attention"], len(kinds), layers,
                                         "layer_types")
        attn_heads = config["num_attention_heads"]
        attn_head_dim = hidden // attn_heads  # the published layer has no ``head_dim`` key
        return cls(
            name=name or config.get("_name_or_path", "granitemoehybrid"),
            vocab_size=config["vocab_size"], hidden_size=hidden, num_layers=layers,
            num_heads=attn_heads, num_kv_heads=config.get("num_key_value_heads", attn_heads), head_dim=attn_head_dim,
            intermediate_size=config["intermediate_size"],
            # No rotary embedding: the identity table (``ops/rope.py``), as in K-EXAONE's full layers.
            rope_theta=float(config.get("rope_theta", 10000.0)), rope_scaling={"rope_type": "nope"},
            rms_eps=config.get("rms_norm_eps", 1e-5), max_position=config.get("max_position_embeddings", 8192),
            tie_embeddings=bool(config.get("tie_word_embeddings", True)), attention_bias=False,
            num_experts=experts, num_experts_per_token=int(config["num_experts_per_tok"]),
            moe_intermediate_size=config["intermediate_size"],
            shared_expert_size=int(config.get("shared_intermediate_size", 0) or 0),
            # The k largest logits, softmax over those: the softmax over all, renormalised over the chosen. (The
            # published gate is ``self.layer(h).float()``: the product in the activations' dtype, then widened, which
            # is route_tokens' own form.)
            moe_scoring="softmax", moe_norm_topk=True,
            layer_group_size=period, group_attn_index=attends,
            ssm_heads=heads, ssm_head_dim=head_dim, ssm_state_size=state, ssm_groups=groups,
            ssm_conv_size=int(config.get("mamba_d_conv", 4)),
            embed_multiplier=float(config.get("embedding_multiplier", 1.0)),
            lm_head_multiplier=1.0 / float(config.get("logits_scaling", 1.0)),
            residual_multiplier=float(config.get("residual_multiplier", 1.0)),
            attn_scale=float(config.get("attention_multiplier", attn_head_dim**-0.5)),
        )

    @classmethod
    def _from_solar_open2(cls, config: dict, name: str | None) -> "ModelConfig":
        """Solar-Open2's config.json (``model_type`` ``solar_open2``): the layers
        ``gqa_layers`` name (every ``gqa_interval + 1``-th, from 0: the layer that
        attends *opens* its period) are GQA attention without RoPE (``use_rope``
        false) with a sigmoid gate a channel on their output (``use_gqa_gate``),
        every other one a KDA layer in Kimi Linear's own form
        (``linear_attn_config``; ``kda_use_full_proj`` false: the decay input and
        the output gate through low-rank pairs of rank ``head_dim``, the gate a
        value a channel; the decay ``-exp(a_log) softplus(.)``, the file has no
        ``kda_safe_gate`` / ``kda_lower_bound``; ``kda_allow_neg_eigval``: a write
        strength in (0, 2)); every layer's FFN ``n_routed_experts`` sigmoid-routed
        experts (DeepSeek-V3's keys and their convention: a selection bias,
        weights renormalised over the chosen) beside ``n_shared_experts`` of
        ``moe_intermediate_size``. A file that states a share
        (``n_routed_experts_published`` beside ``n_routed_experts`` held here, of
        rank ``expert_share_rank``) gives a model that holds that share;
        ``gqa_layers`` may stay whole, the entries of the layers held are read.
        Refuses by name what the layers do not compute; ``rope_theta`` and
        ``partial_rotary_factor`` rotate nothing and ``intermediate_size`` is
        no layer's width (no dense layer): taken without complaint."""
        unserved = {"kda_use_full_proj": False, "use_rope": False, "first_k_dense_replace": 0, "scoring_func": "sigmoid",
                    "topk_method": "noaux_tc", "hidden_act": "silu", "rope_scaling": None, "attention_bias": False}
        _refuse_unserved(config, unserved, "solar_open2")
        hidden, layers, heads = config["hidden_size"], int(config["num_hidden_layers"]), config["num_attention_heads"]
        head_dim = config.get("head_dim") or hidden // heads
        linear = config["linear_attn_config"]
        if linear.get("num_kv_heads") is not None:
            raise ValueError(f"linear_attn_config.num_kv_heads {linear['num_kv_heads']!r} is not served for model_type "
                             "'solar_open2': only None (every linear-attention head its own key and value)")
        if (linear["num_heads"], linear["head_dim"]) != (heads, head_dim):
            raise ValueError(f"linear_attn_config num_heads {linear['num_heads']} x head_dim {linear['head_dim']} against "
                             f"num_attention_heads {heads} x head_dim {head_dim} is not served: one head count for both layer kinds")
        attending = [int(i) for i in config["gqa_layers"]]
        listed = max(attending[-1] + 1, layers) if attending else layers
        period, attends = _period_layout(attending, listed, layers, "gqa_layers")
        if attends != 0 or period != int(config.get("gqa_interval", period - 1)) + 1:
            raise ValueError(f"gqa_layers {attending[:6]} with gqa_interval {config.get('gqa_interval')!r} is not served: "
                             "only range(0, num_hidden_layers, gqa_interval + 1)")
        held, total, first = _expert_share(config)
        n_group, topk_group = _group_limit(config, held, total)
        return cls(
            name=name or config.get("_name_or_path", "solar_open2"),
            vocab_size=config["vocab_size"], hidden_size=hidden, num_layers=layers,
            num_heads=heads, num_kv_heads=config.get("num_key_value_heads", heads), head_dim=head_dim,
            intermediate_size=config["intermediate_size"],
            # No rotary embedding: the identity table (``ops/rope.py``), as in Granite's attention layers.
            rope_theta=float(config.get("rope_theta", 10000.0)), rope_scaling={"rope_type": "nope"},
            rms_eps=config.get("rms_norm_eps", 1e-5), max_position=config.get("max_position_embeddings", 8192),
            tie_embeddings=bool(config.get("tie_word_embeddings", False)), attention_bias=False,
            num_experts=held, num_experts_per_token=int(config["num_experts_per_tok"]),
            moe_intermediate_size=config["moe_intermediate_size"],
            moe_experts_total=total if total != held else 0, moe_expert_first=first,
            shared_expert_size=int(config.get("n_shared_experts", 0) or 0) * int(config["moe_intermediate_size"]),
            moe_scoring="sigmoid", moe_norm_topk=bool(config.get("norm_topk_prob", True)), moe_router_bias=True,
            moe_routed_scaling=float(config.get("routed_scaling_factor", 1.0) or 1.0),
            moe_n_group=n_group, moe_topk_group=topk_group,
            layer_group_size=period, group_attn_index=0, attn_out_gate=bool(config.get("use_gqa_gate", False)),
            kda_conv_size=int(linear.get("short_conv_kernel_size", 4)), kda_decay="softplus",
            kda_beta_scale=2.0 if config.get("kda_allow_neg_eigval") else 1.0, kda_low_rank=int(linear["head_dim"]),
        )

    @classmethod
    def from_hf(cls, config: dict[str, Any] | str | pathlib.Path, *, name: str | None = None) -> "ModelConfig":
        """Build from an HF ``config.json`` dict or path (Llama/Qwen-style keys)."""
        if not isinstance(config, dict):
            config = json.loads(pathlib.Path(config).read_text())
        if "vision_config" in config and "text_config" not in config:
            # Original flat Qwen2-VL layout (Qwen/Qwen2-VL-*-Instruct):
            # text keys live at top level next to vision_config. Normalize
            # to the nested shape so one branch handles both.
            inner_flat = {k: v for k, v in config.items() if k != "vision_config"}
            config = {**config, "text_config": inner_flat}
        if "text_config" in config and "vision_config" in config:
            # VLM config: the LM is the nested text_config; the tower is
            # models/vision.VisionConfig.from_hf_llava (LLaVA/CLIP) or
            # models/qwen2_vl.Qwen2VLVisionConfig.from_hf (Qwen2-VL).
            import dataclasses as _dc

            inner = dict(config["text_config"])
            inner.setdefault("_name_or_path", config.get("_name_or_path", "vlm"))
            # Qwen2-VL M-RoPE rides in rope_scaling; it is a position-id
            # scheme, not a frequency modifier — extract it and neutralize
            # the scaling dict so rope_frequencies sees plain rope.
            mrope = None
            rs = inner.get("rope_scaling") or {}
            if rs.get("mrope_section"):
                mrope = tuple(rs["mrope_section"])
                rest = {k: v for k, v in rs.items() if k != "mrope_section"}
                if rest.get("rope_type", rest.get("type")) in (None, "default", "mrope"):
                    rest = None
                inner["rope_scaling"] = rest
            cfg = cls.from_hf(inner, name=name)
            return _dc.replace(
                cfg,
                image_token_id=config.get("image_token_index", config.get("image_token_id")),
                video_token_id=config.get("video_token_id"),
                mrope_section=mrope,
            )
        if config.get("model_type") in ("gemma2", "gemma3", "gemma3_text"):
            # Gemma-2/3 add logit softcapping and alternating local/global
            # attention; running them through Gemma-1 math would silently
            # produce wrong logits. Refuse loudly.
            raise ValueError(
                f"model_type {config['model_type']!r} is unsupported "
                "(Gemma-2/3 softcapping + alternating-window attention); "
                "supported Gemma family: model_type 'gemma'"
            )
        if "num_hidden_layers" not in config and "ffn_hidden_size" in config:
            return cls._from_longcat(config, name)
        if config.get("model_type") == "bailing_hybrid":
            return cls._from_bailing_hybrid(config, name)
        if config.get("model_type") == "falcon_h1":
            return cls._from_falcon_h1(config, name)
        if config.get("model_type") == "granitemoehybrid":
            return cls._from_granite_hybrid(config, name)
        if config.get("model_type") == "solar_open2":
            return cls._from_solar_open2(config, name)
        # A state-space model's config also describes a GQA stack: served by this
        # branch it would run as that stack alone, silently (what PR 26 found for Mellum2).
        ssm_keys = sorted(k for k in config if k.startswith(("mamba_", "ssm_")))
        if ssm_keys:
            raise ValueError(
                f"model_type {config.get('model_type')!r} states {ssm_keys[0]} (and {len(ssm_keys) - 1} more mamba_* / "
                "ssm_* keys): a state-space layer that no branch of from_hf reads is not served "
                "(served with a mixer: model_type 'falcon_h1', 'granitemoehybrid')")
        if "linear_attn_config" in config:  # likewise: linear-attention layers beside a GQA stack's keys
            raise ValueError(
                f"model_type {config.get('model_type')!r} states linear_attn_config: linear-attention layers that no branch "
                "of from_hf reads are not served (served with KDA layers: model_type 'bailing_hybrid', 'solar_open2')")
        hidden = config["hidden_size"]
        heads = config["num_attention_heads"]
        # DeepSeek replaces the first k MoE layers with dense MLPs
        # (first_k_dense_replace). k >= num_layers collapses to a plain
        # dense model; mixed stacks (0 < k < layers, real V2/V3) carry
        # first_k_dense through to the dense_layers/layers subtree split
        # (models/llama.py dual scan, models/loader._leaf_specs).
        first_dense = int(config.get("first_k_dense_replace", 0) or 0)
        all_dense = first_dense >= config["num_hidden_layers"]
        n_experts = 0 if all_dense else (
            config.get("num_experts", config.get("num_local_experts", config.get("n_routed_experts", 0))) or 0)
        if n_experts and config.get("moe_layer_freq", 1) != 1:
            raise ValueError(f"moe_layer_freq {config['moe_layer_freq']!r} is not served: only 1 "
                             "(every layer after the leading dense ones is routed)")
        # A file may state a share of the routed experts (one chip's part of an
        # expert-parallel deployment; ``ep_size``, where a config carries it, is
        # the publisher's own setting and decides nothing here).
        experts_total = expert_first = 0
        if not all_dense and "n_routed_experts_published" in config:
            n_experts, total, expert_first = _expert_share(
                config, "n_routed_experts" if "n_routed_experts" in config else "num_experts")
            experts_total = total if total != n_experts else 0
        n_group, topk_group = _group_limit(config, n_experts, experts_total or n_experts) if n_experts else (0, 0)
        if n_experts and not n_group and int(config.get("topk_group", 0) or 0) > 1:
            raise ValueError(f"topk_group {config['topk_group']!r} over n_group {config.get('n_group')!r} is not served: "
                             "one group is no group limit, and takes topk_group 1")
        return cls(
            name=name or config.get("_name_or_path", config.get("model_type", "model")),
            vocab_size=config["vocab_size"],
            hidden_size=hidden,
            num_layers=config["num_hidden_layers"],
            num_heads=heads,
            num_kv_heads=config.get("num_key_value_heads", heads),
            # (A latent-attention config's ``head_dim`` is its rope width, HF's
            # convention; that family's layer reads the four MLA sizes below.)
            head_dim=config.get("head_dim") or hidden // heads,
            intermediate_size=config["intermediate_size"],
            **_attention_layout(config),
            rms_eps=config.get("rms_norm_eps", 1e-5),
            max_position=config.get("max_position_embeddings", 8192),
            tie_embeddings=config.get("tie_word_embeddings", False),
            num_experts=n_experts,
            moe_experts_total=experts_total,
            moe_expert_first=expert_first,
            num_experts_per_token=(config.get("num_experts_per_tok", 0) or 0) if n_experts else 0,
            # Mixtral stores the expert width in intermediate_size itself.
            moe_intermediate_size=((config.get("moe_intermediate_size", 0) or 0) or config["intermediate_size"]) if n_experts else 0,
            # Qwen2-MoE names the width directly; DeepSeek counts experts.
            shared_expert_size=((config.get("shared_expert_intermediate_size", 0) or 0)
            or (config.get("n_shared_experts", config.get("num_shared_experts", 0)) or 0)
            * (config.get("moe_intermediate_size", 0) or 0)) if n_experts else 0,
            shared_expert_gated=config.get("model_type") == "qwen2_moe",
            # Native transformers' DeepseekV3Config does not serialize
            # scoring_func (its modeling hardcodes sigmoid routing), so a
            # missing key on deepseek_v3 means sigmoid — same model_type
            # fallback as moe_router_bias below.
            moe_scoring=config.get(
                "scoring_func",
                "sigmoid" if config.get("model_type") == "deepseek_v3" else "softmax",
            ) if n_experts else "softmax",
            # Mixtral renormalizes unconditionally (no config key) and
            # DeepSeek-V3 defaults norm_topk_prob=True; Qwen2-MoE/V2 default
            # False (real checkpoints set the key explicitly either way).
            moe_norm_topk=bool(config.get(
                "norm_topk_prob", config.get("model_type") in ("mixtral", "deepseek_v3")
            )),
            moe_routed_scaling=float(config.get("routed_scaling_factor", 1.0) or 1.0),
            moe_n_group=n_group,
            moe_topk_group=topk_group,
            # noaux_tc correction bias: native transformers' DeepseekV3Config
            # doesn't serialize topk_method, but its modeling always creates
            # e_score_correction_bias — key off model_type too.
            moe_router_bias=bool(n_experts) and (
                config.get("topk_method", "") == "noaux_tc"
                or config.get("model_type") in ("deepseek_v3", "exaone_moe")
            ),
            first_k_dense=0 if all_dense else first_dense,
            attention_bias=bool(config.get("attention_bias", config.get("model_type") in (
                "qwen2", "qwen2_moe", "qwen2_vl", "qwen2_vl_text"))),
            # Gemma: hidden_activation gelu_pytorch_tanh (None in older
            # configs means the same), (1+w) norms, sqrt(hidden) embeds.
            mlp_act="gelu_tanh" if config.get("model_type") == "gemma" else "silu",
            norm_plus_one=config.get("model_type") == "gemma",
            embed_scale=config.get("model_type") == "gemma",
            qk_norm={"qwen3": "head", "qwen3_moe": "head", "exaone4": "head", "exaone_moe": "head", "olmoe": "flat"}.get(
                config.get("model_type", ""), ""
            ),
            # DeepSeek-V2/V3: MLA signalled by the latent-rank keys.
            attn_type="mla" if config.get("kv_lora_rank") else "gqa",
            q_lora_rank=config.get("q_lora_rank") or 0,
            kv_lora_rank=config.get("kv_lora_rank") or 0,
            qk_nope_head_dim=config.get("qk_nope_head_dim") or 0,
            qk_rope_head_dim=config.get("qk_rope_head_dim") or 0,
            v_head_dim=config.get("v_head_dim") or 0,
            # HF defaults rope_interleave=True for DeepSeek MLA configs, so
            # a missing key means interleaved — matching every real V2/V3
            # checkpoint. save_params now always writes the key; MLA
            # checkpoints exported by THIS repo before the rope fix (no key,
            # weights half-split) load wrong under this default — re-export,
            # or add "rope_interleave": false to their config.json.
            rope_interleave=bool(config.get("rope_interleave", True))
            if config.get("kv_lora_rank")
            else False,
        )


# Presets for the tracked benchmark configs (BASELINE.md) plus tiny test models.
PRESETS: dict[str, ModelConfig] = {
    # Small enough for fast CPU unit tests, large enough to exercise GQA + paging.
    "test-tiny": ModelConfig(
        name="test-tiny", vocab_size=256, hidden_size=64, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=16, intermediate_size=128,
        rope_theta=10000.0, max_position=512, tie_embeddings=True, dtype="float32",
    ),
    # Kernel-geometry test model: shapes chosen so the Pallas paged kernels'
    # support predicate holds on the LOCAL shard at tp=2 (n_kv/tp * head_dim
    # = 2*64 = 128 lanes) — used by the sharded-kernel tests and the
    # attn_impl="pallas" multichip dryrun pass.
    "test-kernel": ModelConfig(
        name="test-kernel", vocab_size=256, hidden_size=512, num_layers=2,
        num_heads=8, num_kv_heads=4, head_dim=64, intermediate_size=256,
        rope_theta=10000.0, max_position=512, tie_embeddings=True, dtype="float32",
    ),
    # Vision-language test model: test-tiny plus an image placeholder token.
    "test-tiny-vl": ModelConfig(
        name="test-tiny-vl", vocab_size=256, hidden_size=64, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=16, intermediate_size=128,
        rope_theta=10000.0, max_position=512, tie_embeddings=True, dtype="float32",
        image_token_id=255,
    ),
    # MoE test model: 4 experts, top-2.
    "test-tiny-moe": ModelConfig(
        name="test-tiny-moe", vocab_size=256, hidden_size=64, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=16, intermediate_size=128,
        rope_theta=10000.0, max_position=512, tie_embeddings=True, dtype="float32",
        num_experts=4, num_experts_per_token=2, moe_intermediate_size=64,
    ),
    "llama-3.2-1b": ModelConfig(
        name="llama-3.2-1b", vocab_size=128256, hidden_size=2048, num_layers=16,
        num_heads=32, num_kv_heads=8, head_dim=64, intermediate_size=8192,
        rope_theta=500000.0, tie_embeddings=True,
        rope_scaling={"rope_type": "llama3", "factor": 32.0, "low_freq_factor": 1.0,
                      "high_freq_factor": 4.0, "original_max_position_embeddings": 8192},
    ),
    "llama-3-8b": ModelConfig(
        name="llama-3-8b", vocab_size=128256, hidden_size=4096, num_layers=32,
        num_heads=32, num_kv_heads=8, head_dim=128, intermediate_size=14336,
        rope_theta=500000.0, max_position=8192,
    ),
    "llama-3-70b": ModelConfig(
        name="llama-3-70b", vocab_size=128256, hidden_size=8192, num_layers=80,
        num_heads=64, num_kv_heads=8, head_dim=128, intermediate_size=28672,
        rope_theta=500000.0, max_position=8192,
    ),
    # DeepSeek-R1-Distill-Llama-8B: Llama-3.1-8B architecture (BASELINE
    # tracked config #2); distilled weights load via the standard Llama map.
    "deepseek-r1-distill-8b": ModelConfig(
        name="deepseek-r1-distill-8b", vocab_size=128256, hidden_size=4096,
        num_layers=32, num_heads=32, num_kv_heads=8, head_dim=128,
        intermediate_size=14336, rope_theta=500000.0, max_position=131072,
        rope_scaling={"rope_type": "llama3", "factor": 8.0, "low_freq_factor": 1.0,
                      "high_freq_factor": 4.0, "original_max_position_embeddings": 8192},
    ),
    # Qwen2.5-7B: Qwen2 family (q/k/v biases, untied head, 1M-theta rope).
    "qwen2.5-7b": ModelConfig(
        name="qwen2.5-7b", vocab_size=152064, hidden_size=3584, num_layers=28,
        num_heads=28, num_kv_heads=4, head_dim=128, intermediate_size=18944,
        rope_theta=1000000.0, max_position=32768, rms_eps=1e-6,
        attention_bias=True,
    ),
    # Mistral-7B-v0.1: Llama architecture + 4096-token sliding window.
    "mistral-7b": ModelConfig(
        name="mistral-7b", vocab_size=32000, hidden_size=4096, num_layers=32,
        num_heads=32, num_kv_heads=8, head_dim=128, intermediate_size=14336,
        rope_theta=10000.0, max_position=32768, sliding_window=4096,
    ),
    # Qwen3-8B: per-head Q/K RMS norm, untied head, no attention bias.
    "qwen3-8b": ModelConfig(
        name="qwen3-8b", vocab_size=151936, hidden_size=4096, num_layers=36,
        num_heads=32, num_kv_heads=8, head_dim=128, intermediate_size=12288,
        rope_theta=1000000.0, max_position=40960, rms_eps=1e-6,
        qk_norm="head",
    ),
    # Qwen3-30B-A3B: 128 experts / top-8 MoE with per-head qk-norm; needs
    # ep>=2 on 16 GB chips (~30 GB int8).
    "qwen3-30b-a3b": ModelConfig(
        name="qwen3-30b-a3b", vocab_size=151936, hidden_size=2048, num_layers=48,
        num_heads=32, num_kv_heads=4, head_dim=128, intermediate_size=6144,
        rope_theta=1000000.0, max_position=40960, rms_eps=1e-6,
        num_experts=128, num_experts_per_token=8, moe_intermediate_size=768,
        moe_scoring="softmax", moe_norm_topk=True, qk_norm="head",
    ),
    # Mixtral-8x7B: 8 routed experts / top-2, no shared expert.
    "mixtral-8x7b": ModelConfig(
        name="mixtral-8x7b", vocab_size=32000, hidden_size=4096, num_layers=32,
        num_heads=32, num_kv_heads=8, head_dim=128, intermediate_size=14336,
        rope_theta=1000000.0, max_position=32768,
        num_experts=8, num_experts_per_token=2, moe_intermediate_size=14336,
    ),
    # DeepSeek-V3-shaped wide-EP config (BASELINE tracked config #4):
    # 256 routed experts / top-8 with real MLA (latent KV cache, absorbed
    # up-projections — models/mla.py); expert-parallel serving exercises
    # dynamo_tpu/parallel/moe.py.
    "deepseek-v3-ep": ModelConfig(
        name="deepseek-v3-ep", vocab_size=129280, hidden_size=7168,
        num_layers=61, num_heads=128, num_kv_heads=128, head_dim=64,
        intermediate_size=18432, rope_theta=10000.0, max_position=163840,
        num_experts=256, num_experts_per_token=8, moe_intermediate_size=2048,
        shared_expert_size=2048,  # n_shared_experts=1
        attn_type="mla", q_lora_rank=1536, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        rope_interleave=True,  # real V3 checkpoints ship interleaved rope dims
        # V3 router: sigmoid scores + aux-free correction bias, 8 groups
        # with the best 4 eligible, renormalized weights scaled 2.5x.
        moe_scoring="sigmoid", moe_router_bias=True, moe_norm_topk=True,
        moe_routed_scaling=2.5, moe_n_group=8, moe_topk_group=4,
        first_k_dense=3,
    ),
    # DeepSeek-V2-Lite: the real 15.7B MoE+MLA checkpoint shape — 64 routed
    # experts / top-6 + 2 shared experts, MLA without q-LoRA, one leading
    # dense layer. Expert weights dominate (~14.4 GB int8), so single-chip
    # v5e serving needs ep>=2; the single-chip MoE bench uses olmoe-1b-7b.
    "deepseek-v2-lite": ModelConfig(
        name="deepseek-v2-lite", vocab_size=102400, hidden_size=2048,
        num_layers=27, num_heads=16, num_kv_heads=16, head_dim=128,
        intermediate_size=10944, rope_theta=10000.0, max_position=163840,
        num_experts=64, num_experts_per_token=6, moe_intermediate_size=1408,
        shared_expert_size=2816,  # n_shared_experts=2
        attn_type="mla", q_lora_rank=0, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        rope_interleave=True, moe_scoring="softmax", moe_norm_topk=False,
        moe_routed_scaling=1.0, first_k_dense=1,
    ),
    # Qwen1.5-MoE-A2.7B-class: 14.3B total / 2.7B active — 60 experts /
    # top-4 + a sigmoid-gated shared expert (Qwen2-MoE semantics).
    "qwen1.5-moe-a2.7b": ModelConfig(
        name="qwen1.5-moe-a2.7b", vocab_size=151936, hidden_size=2048,
        num_layers=24, num_heads=16, num_kv_heads=16, head_dim=128,
        intermediate_size=5632, rope_theta=1000000.0, max_position=8192,
        num_experts=60, num_experts_per_token=4, moe_intermediate_size=1408,
        shared_expert_size=5632, shared_expert_gated=True,
        moe_scoring="softmax", moe_norm_topk=False, attention_bias=True,
    ),
    # OLMoE-1B-7B: real 6.9B-total / 1.3B-active MoE checkpoint shape —
    # 64 experts / top-8, no shared expert, softmax routing with top-k
    # renorm. The single-chip MoE bench config: ~7 GB int8 on v5e.
    "olmoe-1b-7b": ModelConfig(
        name="olmoe-1b-7b", vocab_size=50304, hidden_size=2048,
        num_layers=16, num_heads=16, num_kv_heads=16, head_dim=128,
        intermediate_size=1024, rope_theta=10000.0, max_position=4096,
        num_experts=64, num_experts_per_token=8, moe_intermediate_size=1024,
        moe_scoring="softmax", moe_norm_topk=True, qk_norm="flat",
    ),
    # MLA throughput proxy at 8B-class scale: DeepSeek-V3's per-layer MLA
    # geometry (kv_lora 512 + rope 64 latent cache, absorbed projections)
    # on a 32-layer/4096-hidden dense trunk, sized to one 16 GB chip at
    # int8. Answers "MLA decode throughput on hardware" (VERDICT r3 missing
    # #1) without the 671B V3 trunk; named -proxy because no public
    # checkpoint has this exact shape.
    "mla-8b-proxy": ModelConfig(
        name="mla-8b-proxy", vocab_size=128256, hidden_size=4096,
        num_layers=32, num_heads=32, num_kv_heads=32, head_dim=128,
        intermediate_size=14336, rope_theta=500000.0, max_position=8192,
        attn_type="mla", q_lora_rank=1536, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        rope_interleave=True,
    ),
    # Tiny V3-true-shape test model: MLA + sigmoid/noaux_tc routing +
    # group-limited top-k + a leading dense layer (mirrors the golden test).
    "test-tiny-v3": ModelConfig(
        name="test-tiny-v3", vocab_size=256, hidden_size=64, num_layers=3,
        num_heads=4, num_kv_heads=4, head_dim=16, intermediate_size=128,
        rope_theta=10000.0, max_position=512, tie_embeddings=True, dtype="float32",
        num_experts=4, num_experts_per_token=2, moe_intermediate_size=32,
        shared_expert_size=32,
        attn_type="mla", q_lora_rank=32, kv_lora_rank=24,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        rope_interleave=True, moe_scoring="sigmoid", moe_router_bias=True,
        moe_norm_topk=True, moe_routed_scaling=2.5, moe_n_group=2,
        moe_topk_group=1, first_k_dense=1,
    ),
    # Shortcut-MoE test model (LongCat-Flash's layer, tiny): two MLA sublayers
    # and two dense FFNs a layer, 4 of 16 routed experts held here (ids 4-7),
    # 8 identity experts in the 24-way router, scaled latents.
    "test-tiny-scmoe": ModelConfig(
        name="test-tiny-scmoe", vocab_size=256, hidden_size=64, num_layers=2,
        num_heads=4, num_kv_heads=4, head_dim=16, intermediate_size=128,
        rope_theta=10000.0, max_position=512, dtype="float32",
        num_experts=4, num_experts_per_token=4, moe_intermediate_size=32,
        moe_experts_total=16, moe_expert_first=4, moe_zero_experts=8,
        moe_scoring="softmax", moe_norm_topk=False, moe_router_bias=True, moe_routed_scaling=6.0,
        attn_type="mla", q_lora_rank=32, kv_lora_rank=24,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        mla_scale_q=2.0 ** 0.5, mla_scale_kv=(64 / 24) ** 0.5, shortcut_moe=True,
    ),
    # MLA test model (tiny): latent cache + absorbed projections.
    "test-tiny-mla": ModelConfig(
        name="test-tiny-mla", vocab_size=256, hidden_size=64, num_layers=2,
        num_heads=4, num_kv_heads=4, head_dim=16, intermediate_size=128,
        rope_theta=10000.0, max_position=512, tie_embeddings=True, dtype="float32",
        attn_type="mla", q_lora_rank=32, kv_lora_rank=24,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    ),
}
# ``test-tiny-v3`` as one holder of an expert-parallel deployment sees it: 4 of
# 16 routed experts held (ids 4-7), the router over all 16 with no group limit,
# the shared expert and the leading dense layer whole.
PRESETS["test-tiny-v3-held"] = dataclasses.replace(
    PRESETS["test-tiny-v3"], name="test-tiny-v3-held", tie_embeddings=False,
    moe_experts_total=16, moe_expert_first=4, moe_n_group=0, moe_topk_group=0,
)


#: Ling-3.0-flash's published ``config.json`` (inclusionAI; ``model_type``
#: ``bailing_hybrid``), key for key: the presets below are what ``from_hf``
#: makes of it, and ``tests/benchmark/test_benchmark_ling.py`` holds it to the
#: catalog row where the catalog is on the machine.
LING_3_FLASH_HF: dict[str, Any] = {
    "model_type": "bailing_hybrid",
    "expert_swiglu_limit_list": [0] * 35 + [4] * 7,
    "share_expert_swiglu_limit_list": [0] * 34 + [5] * 6 + [7] * 2,
    "first_k_dense_replace": 2, "gated_attention_proj_granularity_type": "head_wise", "group_norm_size": 1,
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 6144,
    "kda_lower_bound": -5, "kda_safe_gate": True, "kv_lora_rank": 512, "layer_group_size": 6,
    "linear_silu": True, "max_position_embeddings": 262144, "max_window_layers": 20,
    "moe_intermediate_size": 768, "moe_router_enable_expert_bias": True,
    "moe_shared_expert_intermediate_size": 768, "mtp_loss_scaling_factor": 0, "mtp_use_kda": False,
    "n_group": 8, "no_kda_lora": True, "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 512,
    "num_experts_per_tok": 8, "num_hidden_layers": 42, "num_key_value_heads": 32,
    "num_kv_heads_for_linear_attn": 0, "num_nextn_predict_layers": 1, "num_shared_experts": 1,
    "partial_rotary_factor": 0.5, "q_lora_rank": None, "qk_head_dim": 192, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_interleave": True, "rope_scaling": None,
    "rope_theta": 6000000, "rotary_dim": 64, "routed_scaling_factor": 2.5, "scale_router_input": False,
    "score_function": "sigmoid", "scoring_func": "sigmoid", "seq_aux": True, "short_conv_kernel_size": 4,
    "tie_word_embeddings": False, "topk_group": 4, "topk_method": "noaux_tc", "up_proj_norm": False,
    "use_bias": False, "use_kda_lora": False, "use_mla_nope": False, "use_nGPT": False, "use_qk_norm": True,
    "use_qkv_bias": False, "v_head_dim": 128, "value_norm": False, "vocab_size": 157184,
}
#: The published model cut to the layers without a clamped SwiGLU (the limit
#: lists are non-zero from layer 34 on, ``from_hf`` refuses them by name):
#: layers 0-29, five whole periods, every expert held. Named for the cut: the
#: whole model is not served until the clamp is.
PRESETS["ling-3.0-flash-30l"] = ModelConfig.from_hf(
    {**LING_3_FLASH_HF, "num_hidden_layers": 30}, name="ling-3.0-flash-30l")
#: The same keys at toy widths: two periods of three layers (KDA, KDA, MLA),
#: one leading dense FFN, 8 of 16 experts held (the second of 2 routing groups,
#: one of which a token may choose from), float32.
TINY_HYBRID_HF: dict[str, Any] = {
    **LING_3_FLASH_HF, "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
    "moe_shared_expert_intermediate_size": 32, "num_hidden_layers": 6, "layer_group_size": 3,
    "first_k_dense_replace": 1, "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
    "kv_lora_rank": 24, "qk_rope_head_dim": 8, "rotary_dim": 8, "qk_nope_head_dim": 16, "qk_head_dim": 24,
    "v_head_dim": 16, "num_experts": 8, "n_routed_experts_published": 16, "expert_share_rank": 1,
    "expert_share_chips": 2, "n_group": 2, "topk_group": 1, "num_experts_per_tok": 2, "vocab_size": 256,
    "max_position_embeddings": 512,
}
PRESETS["test-tiny-hybrid"] = dataclasses.replace(
    ModelConfig.from_hf(TINY_HYBRID_HF, name="test-tiny-hybrid"), dtype="float32")


#: Falcon-H1-34B-Instruct's published ``config.json`` (tiiuae; ``model_type``
#: ``falcon_h1``), key for key: ``tests/benchmark/test_benchmark_falcon_h1.py``
#: holds it to the catalog row where the catalog is on the machine.
FALCON_H1_34B_HF: dict[str, Any] = {
    "model_type": "falcon_h1", "attention_bias": False, "attention_in_multiplier": 1, "attention_out_multiplier": 0.0375,
    "attn_layer_indices": None, "embedding_multiplier": 5.656854249492381, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 5120, "intermediate_size": 21504, "key_multiplier": 0.011048543456039804,
    "lm_head_multiplier": 0.0078125, "mamba_chunk_size": 128, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_head": 128, "mamba_d_ssm": 4096, "mamba_d_state": 256, "mamba_expand": 2, "mamba_n_groups": 2,
    "mamba_n_heads": 32, "mamba_norm_before_gate": False, "mamba_proj_bias": False, "mamba_rms_norm": True,
    "mamba_use_mlp": True, "max_position_embeddings": 262144, "mlp_bias": False, "mlp_expansion_factor": 8,
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284], "num_attention_heads": 20,
    "num_hidden_layers": 72, "num_key_value_heads": 4, "num_logits_to_keep": 1, "projectors_bias": False,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 100000000000, "ssm_in_multiplier": 0.25,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5, 0.3535533905932738],
    "ssm_out_multiplier": 0.08838834764831845, "tie_word_embeddings": False, "vocab_size": 261120,
}
#: The same keys at toy widths: three layers, 4 query heads over 2 KV
#: heads of 16, a mixer of 4 heads of 16 channels in 2 groups with a state of
#: 8, float32. The multipliers are made-up values near 1, each different, so
#: that a multiplier in the wrong place shows in the logits (the published
#: ``key_multiplier`` would make every softmax flat at this size).
TINY_FALCON_H1_HF: dict[str, Any] = {
    **FALCON_H1_34B_HF, "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16, "mamba_n_heads": 4, "mamba_d_head": 16,
    "mamba_d_ssm": 64, "mamba_d_state": 8, "mamba_chunk_size": 8, "vocab_size": 256, "max_position_embeddings": 512,
    "rope_theta": 10000.0, "attention_in_multiplier": 0.75, "attention_out_multiplier": 0.6,
    "embedding_multiplier": 1.5, "key_multiplier": 0.8, "lm_head_multiplier": 0.5, "mlp_multipliers": [0.7, 1.25],
    "ssm_in_multiplier": 0.5, "ssm_multipliers": [0.9, 0.8, 1.1, 1.2, 0.6], "ssm_out_multiplier": 0.7,
}
PRESETS["test-tiny-falcon-h1"] = dataclasses.replace(
    ModelConfig.from_hf(TINY_FALCON_H1_HF, name="test-tiny-falcon-h1"), dtype="float32")


#: granite-4.0-h-small's published ``config.json`` (ibm-granite; ``model_type``
#: ``granitemoehybrid``), the catalog row's keys key for key:
#: ``tests/benchmark/test_benchmark_granite.py`` holds it to the row where the
#: catalog is on the machine.
GRANITE_4_H_SMALL_HF: dict[str, Any] = {
    "model_type": "granitemoehybrid", "attention_bias": False, "attention_multiplier": 0.0078125,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 4096, "intermediate_size": 768,
    "layer_types": (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4, "logits_scaling": 16,
    "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 128, "mamba_proj_bias": False,
    "max_position_embeddings": 131072, "normalization_function": "rmsnorm", "num_attention_heads": 32,
    "num_experts_per_tok": 10, "num_hidden_layers": 40, "num_key_value_heads": 8, "num_local_experts": 72,
    "position_embedding_type": "nope", "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "shared_intermediate_size": 1536, "tie_word_embeddings": True, "vocab_size": 100352,
}
#: The same keys at toy widths: two periods of ``[mamba, mamba, attention,
#: mamba]`` (the layer that attends inside the period), 4 query heads over 2 KV
#: heads of 16, a mixer of 4 heads of 16 channels in one group with a state of
#: 8 (``mamba_expand`` 1), 6 experts top-3 beside a shared one, float32. The multipliers are made-up
#: values, none of them 1 and each different, so that a multiplier in the wrong
#: place shows in the logits (the published softmax scale is kept: 1/16 where
#: the usual one would be 1/4).
TINY_GRANITE_HYBRID_HF: dict[str, Any] = {
    **GRANITE_4_H_SMALL_HF, "hidden_size": 64, "intermediate_size": 32, "shared_intermediate_size": 48,
    "num_hidden_layers": 8, "layer_types": ["mamba", "mamba", "attention", "mamba"] * 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "mamba_n_heads": 4, "mamba_d_head": 16, "mamba_d_state": 8,
    "mamba_expand": 1, "mamba_chunk_size": 8, "num_local_experts": 6, "num_experts_per_tok": 3, "vocab_size": 256,
    "max_position_embeddings": 512, "attention_multiplier": 0.0625, "embedding_multiplier": 3.0,
    "logits_scaling": 4.0, "residual_multiplier": 0.6,
}
PRESETS["test-tiny-granite-hybrid"] = dataclasses.replace(
    ModelConfig.from_hf(TINY_GRANITE_HYBRID_HF, name="test-tiny-granite-hybrid"), dtype="float32")


#: Solar-Open2-250B's published ``config.json`` (upstage; ``model_type``
#: ``solar_open2``), the catalog row's keys key for key:
#: ``tests/benchmark/test_benchmark_solar_open2.py`` holds it to the row where
#: the catalog is on the machine.
SOLAR_OPEN2_250B_HF: dict[str, Any] = {
    "model_type": "solar_open2", "partial_rotary_factor": 1,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64, "num_kv_heads": None},
    "hidden_size": 4096, "num_hidden_layers": 48, "num_attention_heads": 64, "head_dim": 128, "num_key_value_heads": 8,
    "vocab_size": 196608, "intermediate_size": 10240, "moe_intermediate_size": 1280, "rms_norm_eps": 1e-05,
    "rope_theta": 10000, "tie_word_embeddings": False, "max_position_embeddings": 1048576, "first_k_dense_replace": 0,
    "use_rope": False, "gqa_interval": 3, "gqa_layers": list(range(0, 48, 4)), "use_gqa_gate": True,
    "kda_use_full_proj": False, "kda_allow_neg_eigval": True, "n_routed_experts": 320, "n_shared_experts": 1,
    "norm_topk_prob": True, "routed_scaling_factor": 1, "num_experts_per_tok": 8,
}
#: The same keys at toy widths: two periods of ``[GQA, KDA, KDA, KDA]``, 4 heads
#: of 16 over 2 KV heads, low-rank pairs of rank 16, 5 of 10 experts held (the
#: second of 2 shares: no power of two), top-2 beside a shared expert, float32.
TINY_SOLAR_OPEN2_HF: dict[str, Any] = {
    **SOLAR_OPEN2_250B_HF, "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_hidden_layers": 8, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 16, "num_heads": 4, "num_kv_heads": None},
    "n_routed_experts": 5, "n_routed_experts_published": 10, "expert_share_rank": 1, "expert_share_chips": 2,
    "num_experts_per_tok": 2, "vocab_size": 256, "max_position_embeddings": 512,
}
PRESETS["test-tiny-solar-open2"] = dataclasses.replace(
    ModelConfig.from_hf(TINY_SOLAR_OPEN2_HF, name="test-tiny-solar-open2"), dtype="float32")
