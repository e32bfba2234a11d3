"""Checkpoint loading: HF-style safetensors model dirs → stacked params pytree.

TPU-first design: the model's params pytree stacks layers on a leading axis
(``models/llama.py``), but HF checkpoints store one tensor per layer with
torch's ``[out_features, in_features]`` orientation. The loader maps names,
transposes projections to math orientation ``[in, out]``, stacks layers, and
places each leaf **directly onto the device mesh** — per-shard reads through
``jax.make_array_from_callback`` over lazy safetensors slices, so peak host
memory is one shard, not the checkpoint (required for 70B-class weights).

Supports dense Llama-family (Llama 3.x, Qwen2, DeepSeek-R1-Distill) and
routed-MoE layouts (Qwen2-MoE / DeepSeek-style ``mlp.gate`` +
``mlp.experts.{e}.*``, Mixtral ``block_sparse_moe`` aliases).

Also provides ``save_params`` (the reverse mapping) so tests and tools can
materialize an HF-compatible checkpoint from any params pytree — the same
role the reference's model-expression tooling plays for its engines.

Parity: reference ``lib/llm/src/local_model.rs:29-140`` (model resolution +
artifact discovery), ``lib/llm/src/model_card/create.rs`` (card built from
real artifacts), ``lib/llm/src/hub.rs:32`` (checkpoint acquisition — here a
local/shared-filesystem path; TPU pods mount shared storage, no download
daemon needed).
"""

from __future__ import annotations

import json
import pathlib
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.models.config import ModelConfig

Params = dict


# ---------------------------------------------------------------------------
# Checkpoint index: tensor name -> (file, lazy slice handle)
# ---------------------------------------------------------------------------


class CheckpointIndex:
    """All tensors of a (possibly sharded) safetensors checkpoint, lazily.

    Handles both single-file ``model.safetensors`` and sharded checkpoints
    with ``model.safetensors.index.json``. Tensors are exposed as lazy slice
    handles — bytes are only read for the slices actually requested.
    """

    def __init__(self, model_dir: str | pathlib.Path) -> None:
        from safetensors import safe_open

        self.dir = pathlib.Path(model_dir)
        index_file = self.dir / "model.safetensors.index.json"
        if index_file.exists():
            weight_map: dict[str, str] = json.loads(index_file.read_text())["weight_map"]
            files = sorted(set(weight_map.values()))
        else:
            files = sorted(f.name for f in self.dir.glob("*.safetensors"))
            if not files:
                raise FileNotFoundError(f"no *.safetensors under {self.dir}")
        self._handles = {f: safe_open(str(self.dir / f), framework="numpy") for f in files}
        self._where: dict[str, str] = {}
        for fname, h in self._handles.items():
            for key in h.keys():
                self._where[key] = fname

    def keys(self) -> list[str]:
        return sorted(self._where)

    def __contains__(self, name: str) -> bool:
        return name in self._where

    def get_slice(self, name: str):
        return self._handles[self._where[name]].get_slice(name)

    def shape(self, name: str) -> tuple[int, ...]:
        return tuple(self.get_slice(name).get_shape())

    def read(self, name: str) -> np.ndarray:
        return self._handles[self._where[name]].get_tensor(name)


# ---------------------------------------------------------------------------
# HF name mapping
# ---------------------------------------------------------------------------

# Per-layer sources: leaf name -> (hf suffix candidates, transpose?)
_LAYER_MAP: dict[str, tuple[tuple[str, ...], bool]] = {
    "attn_norm": (("input_layernorm.weight",), False),
    "mlp_norm": (("post_attention_layernorm.weight",), False),
    "wq": (("self_attn.q_proj.weight",), True),
    "wk": (("self_attn.k_proj.weight",), True),
    "wv": (("self_attn.v_proj.weight",), True),
    "wo": (("self_attn.o_proj.weight",), True),
    "w_gate": (("mlp.gate_proj.weight",), True),
    "w_up": (("mlp.up_proj.weight",), True),
    "w_down": (("mlp.down_proj.weight",), True),
}

# Qwen2-family attention biases.
_BIAS_MAP: dict[str, tuple[tuple[str, ...], bool]] = {
    "bq": (("self_attn.q_proj.bias",), False),
    "bk": (("self_attn.k_proj.bias",), False),
    "bv": (("self_attn.v_proj.bias",), False),
}

# MoE per-layer sources. Router: [E, D] in HF -> [D, E]. Experts are stored
# one tensor per expert; the loader stacks them on an expert axis.
_MOE_ROUTER = ("mlp.gate.weight", "block_sparse_moe.gate.weight")
_MOE_EXPERT_MAP: dict[str, tuple[tuple[str, ...], bool]] = {
    "w_gate": (("mlp.experts.{e}.gate_proj.weight", "block_sparse_moe.experts.{e}.w1.weight"), True),
    "w_up": (("mlp.experts.{e}.up_proj.weight", "block_sparse_moe.experts.{e}.w3.weight"), True),
    "w_down": (("mlp.experts.{e}.down_proj.weight", "block_sparse_moe.experts.{e}.w2.weight"), True),
}

# Always-on shared expert: Qwen2-MoE (`mlp.shared_expert.*` + sigmoid gate) /
# DeepSeek (`mlp.shared_experts.*`, ungated).
_SHARED_EXPERT_MAP: dict[str, tuple[tuple[str, ...], bool]] = {
    "w_shared_gate": (("mlp.shared_expert.gate_proj.weight", "mlp.shared_experts.gate_proj.weight"), True),
    "w_shared_up": (("mlp.shared_expert.up_proj.weight", "mlp.shared_experts.up_proj.weight"), True),
    "w_shared_down": (("mlp.shared_expert.down_proj.weight", "mlp.shared_experts.down_proj.weight"), True),
}
_SHARED_GATE = ("mlp.shared_expert_gate.weight",)


def _find(index: CheckpointIndex, candidates: tuple[str, ...], li: int, e: int | None = None) -> str:
    for cand in candidates:
        name = f"model.layers.{li}." + (cand.format(e=e) if e is not None else cand)
        if name in index:
            return name
    raise KeyError(f"layer {li}: none of {candidates} in checkpoint (expert={e})")


class _LazyLeaf:
    """A stacked-leaf view over per-layer checkpoint tensors.

    ``__getitem__`` with a tuple of slices (as produced by
    ``jax.make_array_from_callback``) reads only the bytes each device shard
    needs: the layer axis selects which per-layer tensors to touch, and the
    within-layer slices are pushed down into the safetensors lazy slice (with
    transposition handled by slicing the source in swapped order).
    """

    def __init__(
        self,
        index: CheckpointIndex,
        shape: tuple[int, ...],
        per_layer: Callable[[int], list[tuple[str, bool]]],
        dtype: np.dtype,
        expert_axis: bool = False,
        row_perm: np.ndarray | None = None,
    ) -> None:
        self.index = index
        self.shape = shape
        self.per_layer = per_layer  # li -> [(tensor name, transpose?)] (len>1 = expert stack)
        self.dtype = dtype
        self.expert_axis = expert_axis
        # Source-row (torch [out, in] axis-0) permutation applied at read
        # time (rope interleaved -> half-split, see rope_load_perm). A
        # permuted leaf materializes the full per-layer tensor: a shard's
        # slice no longer maps to contiguous source rows.
        self.row_perm = row_perm

    @property
    def ndim(self) -> int:
        return len(self.shape)

    def _read(self, name: str, transpose: bool, idx: tuple[slice, ...]) -> np.ndarray:
        sl = self.index.get_slice(name)
        if self.row_perm is not None:
            arr = np.asarray(sl[:])[self.row_perm]
            if transpose:
                arr = arr.T
            return arr[idx] if idx else arr
        if transpose:
            src = sl[idx[1], idx[0]] if len(idx) == 2 else sl[:]
            arr = np.asarray(src).T
        else:
            arr = np.asarray(sl[idx] if idx else sl[:])
        return arr

    def __getitem__(self, idx) -> np.ndarray:
        if not isinstance(idx, tuple):
            idx = (idx,)
        idx = tuple(
            i if isinstance(i, slice) else slice(i, i + 1) for i in idx
        ) + (slice(None),) * (len(self.shape) - len(idx))
        layers = range(*idx[0].indices(self.shape[0]))
        rest = idx[1:]
        out_layers = []
        for li in layers:
            sources = self.per_layer(li)
            if self.expert_axis:
                e_sl, inner = rest[0], rest[1:]
                chosen = sources[e_sl]
                arr = np.stack([self._read(n, t, inner) for n, t in chosen])
            else:
                (name, transpose), = sources
                arr = self._read(name, transpose, rest)
            out_layers.append(arr)
        return np.stack(out_layers).astype(self.dtype, copy=False)


def rope_load_perm(n_heads: int, head_size: int, rope_dim: int) -> np.ndarray:
    """Row permutation (torch ``[out, in]`` orientation) converting each
    head's trailing ``rope_dim`` rows from interleaved pair order to the
    half-split order ``ops/rope.apply_rope`` expects: ``new = old[perm]``.

    DeepSeek-V2/V3 checkpoints ship rope dims interleaved (HF
    ``rope_interleave=True``: modeling does ``view(d//2, 2).transpose`` on
    the activations before rotate_half — `modeling_deepseek_v3.py:311`);
    llama.cpp's converter likewise permutes whole Q/K heads of llama-family
    GGUFs into interleaved (GGML NORM-rope) order. Permuting the *weights*
    once at load is equivalent and keeps the runtime half-split everywhere.
    Half-split row ``p*half + d`` reads interleaved row ``2*d + p``.
    """
    half = rope_dim // 2
    idx = np.arange(n_heads * head_size)
    head, r = idx // head_size, idx % head_size
    off = head_size - rope_dim
    j = r - off
    src_r = np.where(r >= off, off + 2 * (j % max(half, 1)) + j // max(half, 1), r)
    return head * head_size + src_r


def rope_save_perm(n_heads: int, head_size: int, rope_dim: int) -> np.ndarray:
    """Inverse of :func:`rope_load_perm` (half-split -> interleaved), applied
    by the checkpoint writers so exports match the ecosystem convention."""
    return np.argsort(rope_load_perm(n_heads, head_size, rope_dim))


# MLA per-layer sources (DeepSeek-V2/V3 HF names). kv_b_proj packs per-head
# [K_nope; V] row blocks and is split by _KvBLeaf.
_MLA_MAP: dict[str, tuple[tuple[str, ...], bool]] = {
    "w_q_a": (("self_attn.q_a_proj.weight",), True),
    "q_norm": (("self_attn.q_a_layernorm.weight",), False),
    "w_q_b": (("self_attn.q_b_proj.weight",), True),
    "w_q": (("self_attn.q_proj.weight",), True),
    "w_kv_a": (("self_attn.kv_a_proj_with_mqa.weight",), True),
    "kv_norm": (("self_attn.kv_a_layernorm.weight",), False),
    "wo_mla": (("self_attn.o_proj.weight",), True),
}


class _KvBLeaf:
    """Stacked [L, r_kv, H, seg_width] view over per-layer kv_b_proj tensors.

    kv_b_proj is torch-[H*(dn+dv), r_kv]; head h's rows are
    ``h*(dn+dv) + offset .. + offset + width`` (offset 0/width dn for W_uk,
    offset dn/width dv for W_uv). Reads materialize one layer's tensor
    (~MBs) and slice — per-head lazy slicing isn't worth the complexity.
    """

    def __init__(self, index: "CheckpointIndex", num_layers: int, n_heads: int,
                 dn: int, dv: int, offset: int, width: int, dtype,
                 layer_offset: int = 0) -> None:
        self.index = index
        self.layer_offset = layer_offset
        self.shape = (
            num_layers,
            index.shape(f"model.layers.{layer_offset}.self_attn.kv_b_proj.weight")[1],
            n_heads, width,
        )
        self.n_heads, self.seg = n_heads, dn + dv
        self.offset, self.width = offset, width
        self.dtype = dtype
        self.ndim = 4

    def per_layer_name(self, li: int) -> str:
        return f"model.layers.{li + self.layer_offset}.self_attn.kv_b_proj.weight"

    def __getitem__(self, idx) -> np.ndarray:
        if not isinstance(idx, tuple):
            idx = (idx,)
        idx = tuple(i if isinstance(i, slice) else slice(i, i + 1) for i in idx)
        idx = idx + (slice(None),) * (4 - len(idx))
        out_layers = []
        for li in range(*idx[0].indices(self.shape[0])):
            full = np.asarray(self.index.get_slice(self.per_layer_name(li))[:])  # [H*seg, r_kv]
            per_head = full.reshape(self.n_heads, self.seg, -1)  # [H, dn+dv, r_kv]
            part = per_head[:, self.offset : self.offset + self.width, :]  # [H, w, r_kv]
            arr = np.transpose(part, (2, 0, 1))  # [r_kv, H, w]
            out_layers.append(arr[idx[1], :, :][:, idx[2], :][:, :, idx[3]])
        return np.stack(out_layers).astype(self.dtype, copy=False)


_MOE_ROUTER_BIAS = ("mlp.gate.e_score_correction_bias",)


def _leaf_specs(index: CheckpointIndex, cfg: ModelConfig, dtype: np.dtype) -> dict[str, Any]:
    """Build the params pytree of _LazyLeaf / lazy top-level reads.

    Mixed DeepSeek stacks (``cfg.first_k_dense``) produce two subtrees:
    ``dense_layers`` (checkpoint layers [0, k), dense MLP) and ``layers``
    (checkpoint layers [k, L), MoE)."""
    d = cfg.hidden_size

    def subtree(l0: int, count: int, moe: bool) -> dict[str, Any]:
        def simple(suffixes: tuple[str, ...], transpose: bool,
                   row_perm: np.ndarray | None = None, leaf_dtype=None):
            name0 = _find(index, suffixes, l0)
            shp = index.shape(name0)
            shp = shp[::-1] if transpose else shp
            return _LazyLeaf(
                index, (count, *shp),
                lambda li, s=suffixes, t=transpose: [(_find(index, s, li + l0), t)],
                leaf_dtype or dtype, row_perm=row_perm,
            )

        if cfg.attn_type == "mla":
            layers = {
                name: simple(suffixes, t)
                for name, (suffixes, t) in _LAYER_MAP.items()
                if name in ("attn_norm", "mlp_norm")
            }
            # DeepSeek checkpoints store rope dims interleaved: permute the
            # rope rows of the q projection (per head) and kv_a_proj (single
            # shared rope key) to half-split at load (rope_load_perm).
            q_perm = kv_perm = None
            if cfg.rope_interleave:
                q_perm = rope_load_perm(
                    cfg.num_heads, cfg.qk_nope_head_dim + cfg.qk_rope_head_dim, cfg.qk_rope_head_dim
                )
                kv_perm = rope_load_perm(
                    1, cfg.kv_lora_rank + cfg.qk_rope_head_dim, cfg.qk_rope_head_dim
                )
            for name, (suffixes, t) in _MLA_MAP.items():
                if name in ("w_q_a", "q_norm", "w_q_b") and cfg.q_lora_rank <= 0:
                    continue
                if name == "w_q" and cfg.q_lora_rank > 0:
                    continue
                perm = {"w_q_b": q_perm, "w_q": q_perm, "w_kv_a": kv_perm}.get(name)
                layers[name] = simple(suffixes, t, row_perm=perm)
            layers["w_uk"] = _KvBLeaf(
                index, count, cfg.num_heads, cfg.qk_nope_head_dim, cfg.v_head_dim,
                0, cfg.qk_nope_head_dim, dtype, layer_offset=l0,
            )
            layers["w_uv"] = _KvBLeaf(
                index, count, cfg.num_heads, cfg.qk_nope_head_dim, cfg.v_head_dim,
                cfg.qk_nope_head_dim, cfg.v_head_dim, dtype, layer_offset=l0,
            )
        else:
            layers = {
                name: simple(suffixes, t)
                for name, (suffixes, t) in _LAYER_MAP.items()
                if name not in ("w_gate", "w_up", "w_down")
            }
            if cfg.qk_norm:  # Qwen3 (per-head) / OLMoE (flat) q/k RMS norms
                layers["q_norm"] = simple(("self_attn.q_norm.weight",), False)
                layers["k_norm"] = simple(("self_attn.k_norm.weight",), False)
        if cfg.attention_bias:
            for name, (suffixes, t) in _BIAS_MAP.items():
                layers[name] = simple(suffixes, t)
        if moe:
            e = cfg.num_experts
            layers["router"] = simple(_MOE_ROUTER, True)
            if cfg.moe_router_bias:
                # The correction bias competes with sigmoid scores at O(1e-2)
                # margins: keep it fp32 (as HF does), never the compute dtype.
                layers["router_bias"] = simple(
                    _MOE_ROUTER_BIAS, False, leaf_dtype=np.float32
                )
            for name, (suffixes, t) in _MOE_EXPERT_MAP.items():
                name0 = _find(index, suffixes, l0, 0)
                shp = index.shape(name0)[::-1]
                layers[name] = _LazyLeaf(
                    index,
                    (count, e, *shp),
                    lambda li, s=suffixes, t=t: [(_find(index, s, li + l0, ei), t) for ei in range(e)],
                    dtype,
                    expert_axis=True,
                )
            if cfg.shared_expert_size:
                for name, (suffixes, t) in _SHARED_EXPERT_MAP.items():
                    layers[name] = simple(suffixes, t)
                if cfg.shared_expert_gated:
                    layers["shared_gate"] = simple(_SHARED_GATE, True)
        else:
            for name in ("w_gate", "w_up", "w_down"):
                layers[name] = simple(_LAYER_MAP[name][0], True)
        return layers

    k_dense = cfg.first_k_dense if cfg.is_moe else 0
    moe = cfg.is_moe and any(
        f"model.layers.{k_dense}.{c}" in index for c in _MOE_ROUTER
    )
    layers = subtree(k_dense, cfg.num_layers - k_dense, moe)

    class _TopLeaf:
        def __init__(self, name: str, transpose: bool) -> None:
            self.name, self.transpose = name, transpose
            shp = index.shape(name)
            self.shape = shp[::-1] if transpose else shp
            self.dtype = dtype
            self.ndim = len(self.shape)

        def __getitem__(self, idx) -> np.ndarray:
            sl = index.get_slice(self.name)
            if not isinstance(idx, tuple):
                idx = (idx,)
            idx = tuple(idx) + (slice(None),) * (len(self.shape) - len(idx))
            if self.transpose:
                arr = np.asarray(sl[idx[1], idx[0]]).T
            else:
                arr = np.asarray(sl[idx])
            return arr.astype(self.dtype, copy=False)

    params: dict[str, Any] = {
        "embed": _TopLeaf("model.embed_tokens.weight", False),
        "norm_f": _TopLeaf("model.norm.weight", False),
        "layers": layers,
    }
    if k_dense:
        params["dense_layers"] = subtree(0, k_dense, False)
    if not cfg.tie_embeddings:
        if "lm_head.weight" in index:
            params["lm_head"] = _TopLeaf("lm_head.weight", True)
        else:  # config said untied but checkpoint ties: reuse embeddings
            params["lm_head"] = _TopLeaf("model.embed_tokens.weight", True)
    return params


def _consumed_names(specs: dict, num_layers: int) -> set[str]:
    """Every checkpoint tensor the spec tree will read."""
    del num_layers  # each stacked leaf knows its own layer count (shape[0])
    names: set[str] = set()

    def walk(tree):
        for leaf in jax.tree.leaves(tree, is_leaf=lambda x: hasattr(x, "shape")):
            if isinstance(leaf, _LazyLeaf):
                for li in range(leaf.shape[0]):
                    names.update(n for n, _t in leaf.per_layer(li))
            elif isinstance(leaf, _KvBLeaf):
                names.update(leaf.per_layer_name(li) for li in range(leaf.shape[0]))
            else:
                names.add(leaf.name)

    walk(specs)
    return names


# Buffers some exporters serialize that carry no weights.
_IGNORABLE = ("rotary_emb.inv_freq", "masked_bias", ".attn.bias")


class _RenamedIndex:
    """View over a CheckpointIndex translating canonical Llama names
    (``model.X`` / ``lm_head.weight``) to a VLM checkpoint's language-model
    subtree. Handles both HF layouts: the post-refactor
    ``model.language_model.X`` (+ top-level ``lm_head.weight``) and the
    legacy ``language_model.model.X`` (+ ``language_model.lm_head.weight``).
    Vision/projector tensors are hidden from ``keys()`` so the strict
    leftover check applies to the LM subtree only."""

    def __init__(self, index: CheckpointIndex) -> None:
        self._index = index
        self._legacy = any(k.startswith("language_model.model.") for k in index.keys())

    def _translate(self, name: str) -> str:
        if self._legacy:
            if name == "lm_head.weight":
                return "language_model.lm_head.weight"
            if name.startswith("model."):
                return "language_model." + name
            return name
        if name.startswith("model."):
            return "model.language_model." + name[len("model."):]
        return name

    def keys(self) -> list[str]:
        out = []
        for k in self._index.keys():
            if self._legacy and k.startswith("language_model.model."):
                out.append("model." + k[len("language_model.model."):])
            elif self._legacy and k == "language_model.lm_head.weight":
                out.append("lm_head.weight")
            elif k.startswith("model.language_model."):
                out.append("model." + k[len("model.language_model."):])
            elif k == "lm_head.weight" and not self._legacy:
                out.append(k)
        return out

    def __contains__(self, name: str) -> bool:
        return self._translate(name) in self._index

    def get_slice(self, name: str):
        return self._index.get_slice(self._translate(name))

    def shape(self, name: str) -> tuple[int, ...]:
        return self._index.shape(self._translate(name))

    def read(self, name: str) -> np.ndarray:
        return self._index.read(self._translate(name))


def load_params(
    model_dir: str | pathlib.Path,
    cfg: ModelConfig,
    *,
    mesh: jax.sharding.Mesh | None = None,
    dtype: Any | None = None,
    strict: bool = True,
    index: Any | None = None,
) -> Params:
    """Load a params pytree from an HF-style safetensors checkpoint.

    With ``mesh``, every leaf is materialized **directly sharded**: each
    device shard is read from the checkpoint independently (lazy slices), so
    host memory stays O(largest shard). Without a mesh, leaves land on the
    default device.

    ``strict`` (default) fails on checkpoint tensors the mapping would
    silently drop — a model whose weights are partially ignored *looks* like
    a working deployment while generating garbage.
    """
    target_dtype = np.dtype(jnp.dtype(dtype or cfg.dtype).name) if str(dtype or cfg.dtype) != "bfloat16" else jnp.bfloat16
    import ml_dtypes

    np_dtype = ml_dtypes.bfloat16 if target_dtype == jnp.bfloat16 else np.dtype(target_dtype)
    index = index if index is not None else CheckpointIndex(model_dir)
    specs = _leaf_specs(index, cfg, np_dtype)
    if strict:
        consumed = _consumed_names(specs, cfg.num_layers)
        leftover = [
            n for n in index.keys()
            if n not in consumed and not any(n.endswith(sfx) for sfx in _IGNORABLE)
        ]
        if leftover:
            raise ValueError(
                f"checkpoint has {len(leftover)} tensors the {cfg.name!r} mapping would "
                f"silently drop (first few: {leftover[:6]}); the architecture config and "
                f"checkpoint disagree — pass strict=False only if this is intentional"
            )

    # _LazyLeaf/_TopLeaf are unregistered types: jax.tree.map sees them as leaves.
    if mesh is None:
        return jax.tree.map(
            lambda leaf: jnp.asarray(leaf[(slice(None),) * len(leaf.shape)]), specs
        )

    from dynamo_tpu.parallel.sharding import param_shardings

    shardings = param_shardings(mesh, specs)

    def place(leaf, sharding):
        return jax.make_array_from_callback(tuple(leaf.shape), sharding, lambda idx: leaf[idx])

    return jax.tree.map(place, specs, shardings)


# ---------------------------------------------------------------------------
# High-level entry: directory -> (config, params); plus the reverse writer
# ---------------------------------------------------------------------------


#: Architectures ``ModelConfig.from_hf`` reads whose checkpoint weight names
#: have no map in ``_leaf_specs`` (no published list of them to work from; for
#: ``falcon_h1`` and ``granitemoehybrid`` the names are published and the map is
#: not written: their mixers' leaves, and a state laid the other way round than
#: the published cache; ``solar_open2``'s KDA and gate leaves have no published
#: list either).
UNMAPPED_MODEL_TYPES = frozenset({"mellum", "exaone_moe", "falcon_h1", "granitemoehybrid", "solar_open2"})


def load_model(
    model_dir: str | pathlib.Path,
    *,
    mesh: jax.sharding.Mesh | None = None,
    dtype: Any | None = None,
    name: str | None = None,
) -> tuple[ModelConfig, Params]:
    """Resolve an HF model directory: config.json -> ModelConfig, weights -> pytree."""
    p = pathlib.Path(model_dir)
    model_type = json.loads((p / "config.json").read_text()).get("model_type")
    if model_type in UNMAPPED_MODEL_TYPES:
        raise ValueError(
            f"model_type {model_type!r}: the architecture is served (ModelConfig.from_hf, random or "
            f"benchmark-made weights) but its checkpoint's tensor names are not mapped here; "
            f"refusing to guess them")
    cfg = ModelConfig.from_hf(p / "config.json", name=name or p.name)
    if cfg.shortcut_moe:  # its config names no model_type to refuse by; its layer's leaves have no map either
        raise ValueError(
            "a shortcut-MoE model (two attention sublayers and two dense FFNs a layer) is served from "
            "random or benchmark-made weights; its checkpoint's tensor names are not mapped here")
    if dtype is not None:
        import dataclasses

        cfg = dataclasses.replace(cfg, dtype=str(jnp.dtype(dtype).name))
    return cfg, load_params(p, cfg, mesh=mesh, dtype=dtype)


def load_vision_params(index: CheckpointIndex, dtype: Any = np.float32) -> Params:
    """CLIP tower + LLaVA projector weights -> the vision pytree that
    ``models/vision.encode_image`` consumes.

    Maps HF names (``[model.]vision_tower.vision_model.*`` +
    ``[model.]multi_modal_projector.*``, reference
    `examples/multimodal/components/encode_worker.py:61-179` serves exactly
    this tower via HF). Conv patch embedding becomes the patchify matmul
    weight ([d,3,ph,pw] -> [(ph,pw,c), d] matching encode_image's flatten
    order); q/k/v projections stack into one ``wqkv``."""
    names = set(index.keys())
    pre = "model." if any(n.startswith("model.vision_tower.") for n in names) else ""
    vt = pre + "vision_tower.vision_model."
    proj = pre + "multi_modal_projector."

    def rd(name: str) -> np.ndarray:
        return index.read(name).astype(dtype)

    conv = rd(vt + "embeddings.patch_embedding.weight")  # [d, 3, ph, pw]
    d = conv.shape[0]
    patch_embed = conv.transpose(2, 3, 1, 0).reshape(-1, d)

    n_layers = 1 + max(
        int(n.split("encoder.layers.")[1].split(".")[0])
        for n in names if "encoder.layers." in n
    )

    def layer(li: int) -> dict:
        p = f"{vt}encoder.layers.{li}."
        q, k, v = (rd(p + f"self_attn.{x}_proj.weight") for x in "qkv")
        bq, bk, bv = (rd(p + f"self_attn.{x}_proj.bias") for x in "qkv")
        return {
            "ln1": rd(p + "layer_norm1.weight"), "ln1_b": rd(p + "layer_norm1.bias"),
            "ln2": rd(p + "layer_norm2.weight"), "ln2_b": rd(p + "layer_norm2.bias"),
            "wqkv": np.concatenate([q.T, k.T, v.T], axis=1),
            "bqkv": np.concatenate([bq, bk, bv]),
            "wo": rd(p + "self_attn.out_proj.weight").T,
            "bo": rd(p + "self_attn.out_proj.bias"),
            "w1": rd(p + "mlp.fc1.weight").T, "b1": rd(p + "mlp.fc1.bias"),
            "w2": rd(p + "mlp.fc2.weight").T, "b2": rd(p + "mlp.fc2.bias"),
        }

    layers = jax.tree.map(
        lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]),
        *[layer(i) for i in range(n_layers)],
    )
    params: Params = {
        "patch_embed": jnp.asarray(patch_embed),
        "cls": jnp.asarray(rd(vt + "embeddings.class_embedding")),
        "pos_embed": jnp.asarray(rd(vt + "embeddings.position_embedding.weight")),
        "pre_ln_g": jnp.asarray(rd(vt + "pre_layrnorm.weight")),
        "pre_ln_b": jnp.asarray(rd(vt + "pre_layrnorm.bias")),
        "ln_f": jnp.asarray(rd(vt + "post_layernorm.weight")),
        "ln_f_b": jnp.asarray(rd(vt + "post_layernorm.bias")),
        "proj1": jnp.asarray(rd(proj + "linear_1.weight").T),
        "b_proj1": jnp.asarray(rd(proj + "linear_1.bias")),
        "proj2": jnp.asarray(rd(proj + "linear_2.weight").T),
        "b_proj2": jnp.asarray(rd(proj + "linear_2.bias")),
        "layers": layers,
    }
    return params


class _HiddenPrefixIndex:
    """View over a CheckpointIndex hiding non-LM subtrees (``visual.*``) so
    the strict leftover check applies to the LM only. Qwen2-VL checkpoints
    store the LM under canonical ``model.*`` names already."""

    def __init__(self, index: CheckpointIndex, hidden: tuple[str, ...]) -> None:
        self._index = index
        self._hidden = hidden

    def keys(self) -> list[str]:
        return [k for k in self._index.keys() if not k.startswith(self._hidden)]

    def __contains__(self, name: str) -> bool:
        return not name.startswith(self._hidden) and name in self._index

    def read(self, name: str) -> np.ndarray:
        return self._index.read(name)

    def __getattr__(self, attr):  # shape(), dtype(), ... — name-keyed reads
        return getattr(self._index, attr)


def load_qwen2vl_vision_params(index: CheckpointIndex, dtype: Any = np.float32) -> Params:
    """Qwen2-VL tower + merger weights -> the pytree
    ``models/qwen2_vl.encode_qwen2vl`` consumes. Maps ``[model.]visual.*``:
    the Conv3d patch embedding becomes the patchify matmul weight
    ([D, C, tp, ph, pw] -> [(c, tp, ph, pw), D], the flatten order
    ``patchify_frames`` produces); qkv stays one fused projection."""
    names = set(index.keys())
    pre = "model.visual." if any(n.startswith("model.visual.") for n in names) else "visual."

    def rd(name: str) -> np.ndarray:
        return index.read(pre + name).astype(dtype)

    conv = rd("patch_embed.proj.weight")  # [D, C, tp, ph, pw]
    d = conv.shape[0]
    n_layers = 1 + max(
        int(n.split("blocks.")[1].split(".")[0])
        for n in names if n.startswith(pre + "blocks.")
    )

    def layer(li: int) -> dict:
        p = f"blocks.{li}."
        return {
            "ln1": rd(p + "norm1.weight"), "ln1_b": rd(p + "norm1.bias"),
            "ln2": rd(p + "norm2.weight"), "ln2_b": rd(p + "norm2.bias"),
            "wqkv": rd(p + "attn.qkv.weight").T, "bqkv": rd(p + "attn.qkv.bias"),
            "wo": rd(p + "attn.proj.weight").T, "bo": rd(p + "attn.proj.bias"),
            "w1": rd(p + "mlp.fc1.weight").T, "b1": rd(p + "mlp.fc1.bias"),
            "w2": rd(p + "mlp.fc2.weight").T, "b2": rd(p + "mlp.fc2.bias"),
        }

    return {
        "patch_embed": jnp.asarray(conv.reshape(d, -1).T),
        "merger_ln": jnp.asarray(rd("merger.ln_q.weight")),
        "merger_ln_b": jnp.asarray(rd("merger.ln_q.bias")),
        "merger_w1": jnp.asarray(rd("merger.mlp.0.weight").T),
        "merger_b1": jnp.asarray(rd("merger.mlp.0.bias")),
        "merger_w2": jnp.asarray(rd("merger.mlp.2.weight").T),
        "merger_b2": jnp.asarray(rd("merger.mlp.2.bias")),
        "layers": jax.tree.map(
            lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]),
            *[layer(i) for i in range(n_layers)],
        ),
    }


def load_vlm(
    model_dir: str | pathlib.Path,
    *,
    mesh: jax.sharding.Mesh | None = None,
    dtype: Any | None = None,
    name: str | None = None,
    load_tower: bool = True,
):
    """LLaVA-style VLM checkpoint -> (text ModelConfig, VisionConfig,
    lm_params, vision_params). The LM half loads through the standard Llama
    mapping via a renamed-index view; the tower loads eagerly (it is small
    relative to the LM). VERDICT r3 item 4."""
    import json as _json

    from dynamo_tpu.models.vision import VisionConfig

    p = pathlib.Path(model_dir)
    config = _json.loads((p / "config.json").read_text())
    if "vision_config" not in config:
        raise ValueError(f"{model_dir}: not a VLM checkpoint (no vision_config)")
    tcfg = ModelConfig.from_hf(config, name=name or p.name)
    if dtype is not None:
        import dataclasses as _dc

        tcfg = _dc.replace(tcfg, dtype=str(jnp.dtype(dtype).name))
    index = CheckpointIndex(p)
    # The tower stays f32: it is tiny next to the LM and LayerNorm-heavy.
    # load_tower=False skips it entirely — in a multi-worker deployment only
    # the worker backing the encode service needs a tower copy.
    if config.get("model_type") == "qwen2_vl":
        from dynamo_tpu.models.qwen2_vl import Qwen2VLVisionConfig

        vcfg = Qwen2VLVisionConfig.from_hf(config)
        lm_index = _HiddenPrefixIndex(index, ("visual.", "model.visual."))
        lm_params = load_params(p, tcfg, mesh=mesh, dtype=dtype, index=lm_index)
        vision_params = load_qwen2vl_vision_params(index, dtype=np.float32) if load_tower else None
    else:
        vcfg = VisionConfig.from_hf_llava(config)
        lm_params = load_params(p, tcfg, mesh=mesh, dtype=dtype, index=_RenamedIndex(index))
        vision_params = load_vision_params(index, dtype=np.float32) if load_tower else None
    return tcfg, vcfg, lm_params, vision_params


def save_params(
    model_dir: str | pathlib.Path,
    cfg: ModelConfig,
    params: Params,
) -> None:
    """Write params as an HF-compatible checkpoint (config.json + safetensors).

    The exact inverse of ``load_params``: unstack layers, transpose back to
    torch ``[out, in]`` orientation, emit HF Llama/Qwen2(-MoE) names. Used by
    tests (round-trip) and by tooling that re-exports fine-tuned weights.
    """
    p = pathlib.Path(model_dir)
    p.mkdir(parents=True, exist_ok=True)
    hf_cfg: dict[str, Any] = {
        "architectures": ["LlamaForCausalLM"],
        "model_type": "llama",
        "vocab_size": cfg.vocab_size,
        "hidden_size": cfg.hidden_size,
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "head_dim": cfg.head_dim,
        "intermediate_size": cfg.intermediate_size,
        "rope_theta": cfg.rope_theta,
        "rms_norm_eps": cfg.rms_eps,
        "max_position_embeddings": cfg.max_position,
        "tie_word_embeddings": cfg.tie_embeddings,
        "torch_dtype": cfg.dtype,
    }
    if cfg.rope_scaling:
        hf_cfg["rope_scaling"] = cfg.rope_scaling
    hf_cfg["attention_bias"] = cfg.attention_bias
    if cfg.qk_norm:
        # qk_norm is reconstructed from model_type at load (from_hf): pin
        # the family whose modeling carries these norms so a save->load
        # round-trip keeps them (head: Qwen3; flat: OLMoE).
        if cfg.qk_norm == "head":
            hf_cfg["model_type"] = "qwen3_moe" if cfg.is_moe else "qwen3"
            hf_cfg["architectures"] = ["Qwen3MoeForCausalLM" if cfg.is_moe else "Qwen3ForCausalLM"]
        else:
            hf_cfg["model_type"] = "olmoe"
            hf_cfg["architectures"] = ["OlmoeForCausalLM"]
    # Gemma's math (GeGLU, (1+w) norms, scaled embeddings) is keyed off
    # model_type at load — a "llama"-typed save would silently reload with
    # silu/plain-norm math over Gemma weights. GGUF-sourced Gemma arrives
    # with norm_plus_one=False (llama.cpp bakes the +1 into the weights) but
    # still gelu_tanh/embed_scale, so ANY of the three marks the family.
    gemma_family = cfg.norm_plus_one or cfg.mlp_act == "gelu_tanh" or cfg.embed_scale
    if gemma_family:
        hf_cfg["model_type"] = "gemma"
        hf_cfg["architectures"] = ["GemmaForCausalLM"]
        hf_cfg["hidden_activation"] = "gelu_pytorch_tanh"
    if cfg.attn_type == "mla":
        hf_cfg.update(
            model_type="deepseek_v3",
            architectures=["DeepseekV3ForCausalLM"],
            q_lora_rank=cfg.q_lora_rank or None,
            kv_lora_rank=cfg.kv_lora_rank,
            qk_nope_head_dim=cfg.qk_nope_head_dim,
            qk_rope_head_dim=cfg.qk_rope_head_dim,
            v_head_dim=cfg.v_head_dim,
            rope_interleave=cfg.rope_interleave,
        )
    if cfg.is_moe:
        if cfg.attn_type != "mla" and not cfg.qk_norm:
            # MLA pinned deepseek_v3; qk_norm pinned qwen3_moe/olmoe above.
            hf_cfg["model_type"] = (
                "qwen2_moe" if cfg.shared_expert_gated or not cfg.shared_expert_size else "deepseek_v2"
            )
        hf_cfg.update(
            num_experts=cfg.num_experts,
            num_experts_per_tok=cfg.num_experts_per_token,
            moe_intermediate_size=cfg.moe_intermediate_size,
            scoring_func=cfg.moe_scoring,
            norm_topk_prob=cfg.moe_norm_topk,
            routed_scaling_factor=cfg.moe_routed_scaling,
        )
        if cfg.moe_n_group:
            hf_cfg.update(n_group=cfg.moe_n_group, topk_group=cfg.moe_topk_group)
        if cfg.moe_router_bias:
            hf_cfg["topk_method"] = "noaux_tc"
        if cfg.first_k_dense:
            hf_cfg["first_k_dense_replace"] = cfg.first_k_dense
        if cfg.shared_expert_size:
            if cfg.shared_expert_gated:
                hf_cfg["shared_expert_intermediate_size"] = cfg.shared_expert_size
            else:
                hf_cfg["n_shared_experts"] = cfg.shared_expert_size // cfg.moe_intermediate_size
    (p / "config.json").write_text(json.dumps(hf_cfg, indent=2))

    tensors: dict[str, np.ndarray] = {}

    def put(name: str, arr, transpose: bool, row_perm: np.ndarray | None = None) -> None:
        a = np.asarray(arr)
        if transpose:
            a = a.T
        if row_perm is not None:  # half-split -> checkpoint (interleaved) order
            a = a[row_perm]
        tensors[name] = np.ascontiguousarray(a)

    # HF Gemma checkpoints store ZERO-CENTERED norm weights (runtime adds
    # +1). GGUF-sourced params carry the +1 baked in (norm_plus_one=False),
    # so saving them under model_type=gemma must subtract it back out or the
    # reload (which re-adds 1) would double-shift every norm.
    def zero_center(a):
        a = np.asarray(a)
        return (a.astype(np.float32) - 1.0).astype(a.dtype)

    shift_norms = gemma_family and not cfg.norm_plus_one

    put("model.embed_tokens.weight", params["embed"], False)
    put("model.norm.weight",
        zero_center(params["norm_f"]) if shift_norms else params["norm_f"], False)
    if not cfg.tie_embeddings and "lm_head" in params:
        put("lm_head.weight", params["lm_head"], True)
    def write_subtree(lp, l0: int, count: int, moe: bool) -> None:
        for li in range(count):
            base = f"model.layers.{li + l0}."
            for leaf, (suffixes, transpose) in _LAYER_MAP.items():
                if moe and leaf in _MOE_EXPERT_MAP:
                    continue
                if cfg.attn_type == "mla" and leaf in ("wq", "wk", "wv", "wo"):
                    continue
                arr = lp[leaf][li]
                if shift_norms and leaf in ("attn_norm", "mlp_norm"):
                    arr = zero_center(arr)
                put(base + suffixes[0], arr, transpose)
            if cfg.qk_norm and cfg.attn_type != "mla":
                put(base + "self_attn.q_norm.weight", lp["q_norm"][li], False)
                put(base + "self_attn.k_norm.weight", lp["k_norm"][li], False)
            if cfg.attn_type == "mla":
                q_sperm = kv_sperm = None
                if cfg.rope_interleave:
                    q_sperm = rope_save_perm(
                        cfg.num_heads, cfg.qk_nope_head_dim + cfg.qk_rope_head_dim, cfg.qk_rope_head_dim
                    )
                    kv_sperm = rope_save_perm(
                        1, cfg.kv_lora_rank + cfg.qk_rope_head_dim, cfg.qk_rope_head_dim
                    )
                for leaf, (suffixes, transpose) in _MLA_MAP.items():
                    if leaf in lp:
                        sperm = {"w_q_b": q_sperm, "w_q": q_sperm, "w_kv_a": kv_sperm}.get(leaf)
                        put(base + suffixes[0], lp[leaf][li], transpose, row_perm=sperm)
                # kv_b_proj: interleave per-head [K_nope; V] row blocks
                uk = np.asarray(lp["w_uk"][li])  # [r_kv, H, dn]
                uv = np.asarray(lp["w_uv"][li])  # [r_kv, H, dv]
                per_head = np.concatenate(
                    [np.transpose(uk, (1, 2, 0)), np.transpose(uv, (1, 2, 0))], axis=1
                )  # [H, dn+dv, r_kv]
                put(base + "self_attn.kv_b_proj.weight", per_head.reshape(-1, per_head.shape[-1]), False)
            if cfg.attention_bias:
                for leaf, (suffixes, transpose) in _BIAS_MAP.items():
                    put(base + suffixes[0], lp[leaf][li], transpose)
            if moe:
                put(base + _MOE_ROUTER[0], lp["router"][li], True)
                if "router_bias" in lp:
                    put(base + _MOE_ROUTER_BIAS[0], lp["router_bias"][li], False)
                for leaf, (suffixes, transpose) in _MOE_EXPERT_MAP.items():
                    for e in range(cfg.num_experts):
                        put(base + suffixes[0].format(e=e), lp[leaf][li, e], transpose)
                if cfg.shared_expert_size:
                    src = 0 if cfg.shared_expert_gated else 1
                    for leaf, (suffixes, transpose) in _SHARED_EXPERT_MAP.items():
                        put(base + suffixes[src], lp[leaf][li], transpose)
                    if cfg.shared_expert_gated:
                        put(base + _SHARED_GATE[0], lp["shared_gate"][li], True)

    k_dense = cfg.first_k_dense if cfg.is_moe else 0
    if k_dense:
        write_subtree(params["dense_layers"], 0, k_dense, False)
    write_subtree(params["layers"], k_dense, cfg.num_layers - k_dense, cfg.is_moe)

    from safetensors.numpy import save_file

    save_file(tensors, str(p / "model.safetensors"))
    index = {"metadata": {"total_size": sum(t.nbytes for t in tensors.values())},
             "weight_map": {k: "model.safetensors" for k in tensors}}
    (p / "model.safetensors.index.json").write_text(json.dumps(index))
