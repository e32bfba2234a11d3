"""Rotary position embeddings (RoPE), including Llama-3-style frequency scaling.

Applied at arbitrary absolute positions (paged decode needs per-token
positions, not a contiguous range). Uses the "split halves" convention of the
Llama family: the head dim is split into two halves that rotate together.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np


def rope_frequencies(
    head_dim: int,
    *,
    theta: float = 10000.0,
    scaling: dict | None = None,
) -> np.ndarray:
    """Inverse frequencies [head_dim//2], with optional Llama-3 rope scaling.

    ``scaling`` follows the HF config schema: ``{"rope_type": "llama3",
    "factor": f, "low_freq_factor": lo, "high_freq_factor": hi,
    "original_max_position_embeddings": n}``.
    """
    inv_freq = 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim))
    rope_type = (scaling or {}).get("rope_type", (scaling or {}).get("type"))
    if rope_type in (None, "none", "default"):
        pass
    elif rope_type == "nope":  # no rotation: every angle 0, the table of the identity
        inv_freq = np.zeros_like(inv_freq)
    elif rope_type == "llama3":
        factor = float(scaling["factor"])
        lo = float(scaling["low_freq_factor"])
        hi = float(scaling["high_freq_factor"])
        orig = float(scaling["original_max_position_embeddings"])
        wavelen = 2.0 * np.pi / inv_freq
        # Three bands: long wavelengths fully scaled, short untouched, smooth ramp between.
        smooth = (orig / wavelen - lo) / (hi - lo)
        smooth = np.clip(smooth, 0.0, 1.0)
        scaled = (1.0 - smooth) * inv_freq / factor + smooth * inv_freq
        inv_freq = np.where(wavelen > orig / lo, inv_freq / factor, scaled)
    elif rope_type == "linear":
        inv_freq = inv_freq / float(scaling["factor"])
    elif rope_type == "yarn":
        # NTK-by-parts interpolation (YaRN): dims whose wavelength fits the
        # original context keep extrapolated freqs, long-wavelength dims get
        # fully interpolated, a smooth ramp in between (beta_fast/beta_slow).
        factor = float(scaling["factor"])
        orig = float(scaling.get("original_max_position_embeddings", 4096))
        beta_fast = float(scaling.get("beta_fast", 32.0))
        beta_slow = float(scaling.get("beta_slow", 1.0))
        dims = np.arange(0, head_dim, 2, dtype=np.float64)

        def corr_dim(num_rot: float) -> float:
            return (head_dim * np.log(orig / (num_rot * 2.0 * np.pi))) / (2.0 * np.log(theta))

        low = max(np.floor(corr_dim(beta_fast)), 0.0)
        high = min(np.ceil(corr_dim(beta_slow)), head_dim - 1.0)
        ramp = np.clip((dims / 2.0 - low) / max(high - low, 1e-3), 0.0, 1.0)
        extrapolation = 1.0 - ramp  # 1 where we keep original freqs
        inv_freq = inv_freq / factor * ramp + inv_freq * extrapolation
    else:
        raise ValueError(
            f"unsupported rope scaling type {rope_type!r} (supported: llama3, linear, yarn) — "
            f"serving with unscaled frequencies would silently corrupt long-context output"
        )
    return inv_freq.astype(np.float32)


def rope_attention_factor(scaling: dict | None) -> float:
    """YaRN attention-temperature scaling (mscale).

    YaRN scales the rotated q/k embeddings by ``0.1*ln(s) + 1`` (the paper's
    ``sqrt(1/t)``), so attention logits grow by its square; HF exposes an
    explicit ``attention_factor`` override. Models apply the square to q once
    — equivalent to scaling both rotated tensors, one multiply cheaper.
    Non-yarn scaling types don't temperature-correct (factor 1.0).
    """
    if not scaling or scaling.get("rope_type", scaling.get("type")) != "yarn":
        return 1.0
    explicit = scaling.get("attention_factor")
    if explicit is not None:
        return float(explicit)
    factor = float(scaling.get("factor", 1.0))
    return 0.1 * float(np.log(factor)) + 1.0 if factor > 1.0 else 1.0


def apply_mrope(
    x: jnp.ndarray,  # [B, T, H, hd]
    positions3: jnp.ndarray,  # i32[B, 3, T] — (temporal, height, width)
    inv_freq: jnp.ndarray,  # [hd/2]
    sections: tuple[int, ...],  # e.g. (16, 24, 24), sums to hd/2
) -> jnp.ndarray:
    """Multimodal 3D rope (Qwen2-VL): frequency dims are partitioned into
    ``sections``; section j's dims take their rotation angle from coordinate
    axis j. Text tokens carry equal coords on all three axes, for which this
    reduces exactly to :func:`apply_rope`. Mirrors HF
    ``apply_multimodal_rotary_pos_emb`` (modeling_qwen2_vl.py:156) in the
    half-split convention."""
    angles3 = positions3[..., None].astype(jnp.float32) * inv_freq  # [B, 3, T, hd/2]
    oh = np.zeros((3, inv_freq.shape[0]), np.float32)
    start = 0
    for j, s in enumerate(sections):
        oh[j, start : start + s] = 1.0
        start += s
    angles = jnp.einsum("bctf,cf->btf", angles3, jnp.asarray(oh))
    cos = jnp.cos(angles)[..., None, :]  # [B, T, 1, hd/2]
    sin = jnp.sin(angles)[..., None, :]
    x32 = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    x1, x2 = x32[..., :half], x32[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, inv_freq: jnp.ndarray) -> jnp.ndarray:
    """Rotate ``x`` [..., T, n_heads, head_dim] at absolute ``positions`` [..., T]."""
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [..., T, hd/2]
    cos = jnp.cos(angles)[..., None, :]  # [..., T, 1, hd/2]
    sin = jnp.sin(angles)[..., None, :]
    x32 = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    x1, x2 = x32[..., :half], x32[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)
