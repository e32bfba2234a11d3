"""Shared pieces of the plain references: float32 ``jax.numpy``, no kernels,
no cache, no batching, nothing imported from the program. Callers run them
under ``jax.default_matmul_precision("highest")`` (on a TPU a float32 matmul
is otherwise computed in bf16 passes)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def widen(leaf) -> jnp.ndarray:
    """A served leaf as float32: int8 codes times their per-channel scale,
    packed int4 is refused (the reference never reads the control's form)."""
    if isinstance(leaf, dict):
        if "qw" not in leaf:
            raise ValueError("the reference reads int8 or plain leaves only")
        return leaf["qw"].astype(F32) * leaf["scale"].astype(F32)[..., None, :]
    return leaf.astype(F32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w.astype(F32)


def rope_inv_freq(dim: int, theta: float, scaling: dict | None) -> np.ndarray:
    """Inverse frequencies of plain RoPE. A family with scaled RoPE carries
    its own beside its reference."""
    if scaling:
        raise ValueError(f"this reference has no rope scaling {scaling!r}")
    return 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)


def rope(x, positions, inv_freq):
    """Rotate [T, H, dim] at ``positions`` [T]; the two halves of the head
    rotate together (a checkpoint that interleaves pairs is the same map up
    to a fixed permutation of its weights, which random weights do not see)."""
    ang = positions.astype(F32)[:, None] * jnp.asarray(inv_freq, F32)[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def causal_attention(q, k, v, scale):
    """q,k [T, H, dk], v [T, H, dv] -> [T, H, dv]; full causal softmax."""
    s = jnp.einsum("thd,shd->hts", q, k) * scale
    t = q.shape[0]
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
    return jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)


def swiglu(x, wg, wu, wd):
    return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd


def routed_experts(h, lp, *, top_k: int, renormalize: bool):
    """Softmax over all experts, the ``top_k`` largest, optionally renormalised;
    every token through every expert (one expert widened at a time), mixed by
    its routing weight (0 for experts not chosen)."""
    probs = jax.nn.softmax(h @ lp["router"].astype(F32), axis=-1)  # [T, E]
    w, idx = jax.lax.top_k(probs, top_k)
    if renormalize:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    mix = jnp.zeros_like(probs).at[jnp.arange(h.shape[0])[:, None], idx].set(w)

    def one(acc, xs):
        wg, wu, wd, m = xs
        return acc + m[:, None] * swiglu(h, widen(wg), widen(wu), widen(wd)), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h), (lp["w_gate"], lp["w_up"], lp["w_down"], mix.T))
    return out


def lm_head(x, params, chunks: int = 8):
    """[T, d] -> [T, vocab], the head widened a slice of the vocabulary at a time."""
    if "lm_head" not in params:
        return x @ params["embed"].astype(F32).T
    head = params["lm_head"]
    if not isinstance(head, dict) or head["qw"].shape[-1] % chunks:
        return x @ widen(head)
    d, v = head["qw"].shape
    qw = jnp.moveaxis(head["qw"].reshape(d, chunks, v // chunks), 1, 0)
    sc = head["scale"].reshape(chunks, v // chunks)
    out = jax.lax.map(lambda a: x @ (a[0].astype(F32) * a[1].astype(F32)[None, :]), (qw, sc))
    return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], v)
