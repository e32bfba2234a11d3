"""The Mamba-2 mixer: of a layer that runs it beside its attention (Falcon-H1,
``modeling_falcon_h1.FalconH1Mixer``: the slot *beside* the layer's pages) and
of a layer that is nothing else (Granite-4.0-H,
``modeling_granitemoehybrid.GraniteMoeHybridMambaLayer``: a slot and no pages,
in periods with one layer that attends). A selective state-space layer whose
per-sequence state is a fixed-size **slot**, as KDA's is (``models/kda.py``).

With ``u`` the layer's normed input, ``H`` heads of ``P`` channels, a state of
``N`` and ``G`` groups of heads that share a ``B`` and a ``C``:

    [z | x | B | C | dt] = (W_in (u * ssm_in_multiplier)) * mup     # mup: ``ssm_multipliers`` by section
    xBC = silu(conv(xBC) + bias)                                    # causal, depthwise, ``ssm_conv_size`` taps
    dt  = softplus(dt + dt_bias);  A = -exp(A_log)                  # one value a head
    S_t = exp(dt_t A) S_{t-1} + dt_t B_t x_t^T                      # S [N x P] a head, float32
    y_t = S_t^T C_t + D x_t
    out = W_out (RMS_group(y * silu(z)) * g)                        # the norm over each group's channels

(the caller multiplies ``out`` by ``ssm_out_multiplier``). A head keeps ``S``
state-major, ``[N, P]``: the transposition of the published cache's ``[P, N]``,
so that the decode kernel's two products are a multiply and a sum over
sublanes, as ``ops/pallas_kda`` has them. Heads narrower than the 128 lanes
lie ``cfg.ssm_heads_per_row`` of a group side by side in a buffer row, ``[H /
side, N, side x P]`` (:func:`lay_side_by_side`), so that the buffer is not
padded to the lanes. ``benchmark/reference/falcon_h1.py`` and
``granite_hybrid.py`` write the layer out equation by equation.

What a step does with a row's slot, exactly as for KDA:

- a **decode** row takes one step of the recurrence: on a TPU the Pallas
  kernel ``ops/pallas_mamba.mamba_decode_step`` (one read and one write of the
  slot's state, in place; a shape it does not tile is refused there by name,
  never gathered), elsewhere :func:`recurrent_step` on gathered rows;
- a **chunk** row takes the chunked form (:func:`chunk_step`): within the
  chunk the ``[tokens, tokens]`` decay-masked ``C B^T`` product a group times
  ``dt x``, the carried state in with its decay, the chunk's last state out,
  in plain ``jax.numpy``. Decays between two tokens are ``exp`` of a
  *difference* of cumulated log-decays, so nothing overflows;
- the conv state of either kind of row goes through ``models/kda.slot_conv``:
  on a TPU the kernel ``ops/pallas_conv.slot_conv_step``, the bias and SiLU in
  it, elsewhere ``causal_conv`` on gathered rows;
- a row whose first position is 0 starts from a zero state and a zero conv
  state: the sequence's own first chunk zeroes the slot it was given;
- a padding token (``valid`` false) leaves the state as it is (``dt = 0``:
  no decay, no write) and does not enter the conv state.

The two buffers are flat over ``(layer, slot)``; slot 0 is the null slot.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.kda import CHUNK_ROWS_AT_ONCE, conv_heads, slot_conv

Params = dict
HI = jax.lax.Precision.HIGHEST


def init_mamba_params(cfg: ModelConfig, key: jax.Array, dt, num_layers: int) -> dict[str, jnp.ndarray]:
    """A mixer's leaves, layers stacked on the leading axis. The two
    projections keep names of their own: they are served in the model's dtype
    (``models/quant._MATMUL_LEAVES`` does not list them)."""
    d, inner, conv_dim, h, taps, l = (cfg.hidden_size, cfg.ssm_inner, cfg.ssm_conv_dim, cfg.ssm_heads,
                                      cfg.ssm_conv_size, num_layers)
    keys = jax.random.split(key, 3)

    def w(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32) * (fan_in**-0.5)).astype(dt)

    return {
        "w_ssm_in": w(keys[0], (l, d, inner + conv_dim + h), d),  # [z | x | B | C | dt]
        "w_ssm_out": w(keys[1], (l, inner, d), inner),
        "ssm_conv": w(keys[2], (l, taps, conv_dim), taps),
        "ssm_conv_bias": jnp.zeros((l, conv_dim), dt),
        "ssm_a_log": jnp.zeros((l, h), dt),  # A = -exp(A_log)
        "ssm_dt_bias": jnp.zeros((l, h), dt),
        "ssm_d": jnp.ones((l, h), dt),
        "ssm_norm": jnp.ones((l, inner), dt),
    }


def mup_vector(cfg: ModelConfig) -> np.ndarray:
    """``ssm_multipliers`` laid over the sections of the input projection."""
    gn = cfg.ssm_groups * cfg.ssm_state_size
    widths = (cfg.ssm_inner, cfg.ssm_inner, gn, gn, cfg.ssm_heads)
    return np.concatenate([np.full(n, m, np.float32) for n, m in zip(widths, cfg.ssm_multipliers)])


def recurrent_step(s, x, b, c, dt, a):
    """One token of the recurrence on ``s f32[..., G, R, N, P]`` (``R`` heads a
    group): ``x [..., G, R, P]``, ``b c [..., G, N]``, ``dt [..., G, R]``,
    ``a [G, R]``. Returns ``(y [..., G, R, P], s)``."""
    s = s * jnp.exp(dt * a)[..., None, None] + b[..., None, :, None] * (dt[..., None] * x)[..., None, :]
    return jnp.einsum("...grnp,...gn->...grp", s, c, precision=HI), s


def chunk_step(s0, x, b, c, dt, a):
    """``T`` tokens of the recurrence at once, from the carried state:
    ``s0 f32[G, R, N, P]``, ``x [T, G, R, P]``, ``b c [T, G, N]``, ``dt [T, G,
    R]``, ``a [G, R]``. Returns ``(y [T, G, R, P], s [G, R, N, P])``.

    With ``L_t`` the log-decay cumulated up to and including token ``t``:
    ``S_t = e^{L_t} S_0 + sum_{s<=t} e^{L_t-L_s} dt_s B_s x_s^T``, so a token's
    output is its ``C`` against the carried state, decayed, plus the
    decay-masked ``C B^T`` of the chunk (one ``[T, T]`` product a group)
    against the tokens' ``dt x``."""
    t = x.shape[0]
    cum = jnp.cumsum(dt * a, axis=0)  # [T, G, R], <= 0 and falling
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    decay = jnp.exp(jnp.where(causal[:, :, None, None], cum[:, None] - cum[None, :], -jnp.inf))  # [t, s, G, R]
    cb = jnp.einsum("tgn,sgn->tsg", c, b, precision=HI)
    xw = x * dt[..., None]
    into = jnp.exp(cum)  # decay from the chunk's start to each token
    y = (jnp.einsum("tsgr,sgrp->tgrp", cb[..., None] * decay, xw, precision=HI)
         + jnp.einsum("grnp,tgn->tgrp", s0, c, precision=HI) * into[..., None])
    out_of = jnp.exp(cum[-1][None] - cum)  # decay from each token to the chunk's end
    s = into[-1][..., None, None] * s0 + jnp.einsum("sgn,sgrp->grnp", b, xw * out_of[..., None], precision=HI)
    return y, s


def lay_side_by_side(s, side: int):
    """States ``[..., H, N, P]`` as the buffer holds them, ``[..., H / side, N,
    side x P]``: ``side`` neighbouring heads on the lanes of one row."""
    if side == 1:
        return s
    *lead, h, n, p = s.shape
    return jnp.moveaxis(s.reshape(*lead, h // side, side, n, p), -3, -2).reshape(*lead, h // side, n, side * p)


def lay_by_head(s, side: int):
    """:func:`lay_side_by_side`'s inverse: buffer rows as ``[..., H, N, P]``."""
    if side == 1:
        return s
    *lead, rows, n, lanes = s.shape
    return jnp.moveaxis(s.reshape(*lead, rows, n, side, lanes // side), -2, -3).reshape(*lead, rows * side, n, lanes // side)


def _rows_update(state, ids, fresh, x, b, c, dt, a, *, impl: str | None):
    """The recurrence over rows ``[R, T]`` (``x [R, T, G, Hg, P]``, ``b c [R,
    T, G, N]``, ``dt [R, T, G, Hg]``, float32) on the slots ``ids`` of ``state
    f32[slots, H / side, N, side x P]``; ``fresh`` rows start from zeros.
    Returns ``(y like x, state)``."""
    r, t, g, hg, p = x.shape
    n = b.shape[-1]
    side = g * hg // state.shape[1]
    if t == 1 and impl == "pallas":
        from dynamo_tpu.ops import pallas_mamba

        if not pallas_mamba.supported(*state.shape[2:]):  # on a chip: never through the gather below, at any size
            raise ValueError(f"mamba_d_head {p} (mamba_d_state {n}, {hg} heads a group) is not a shape the decode kernel "
                             f"tiles: a slot's state lies [{state.shape[2]}, {state.shape[3]}] a buffer row, and the "
                             "kernel takes the state in eights and a row of whole lane tiles of 128 (a head's channels, "
                             "or narrower heads that fill them group by group): not served")
        y, state = pallas_mamba.mamba_decode_step(
            state, ids, fresh, x[:, 0].reshape(r, g * hg, p), b[:, 0], c[:, 0], dt[:, 0].reshape(r, g * hg),
            a.reshape(-1), interpret=pallas_mamba.interpret_mode())
        return y.reshape(r, 1, g, hg, p), state
    s0 = lay_by_head(jnp.where(fresh[:, None, None, None], 0.0, state[ids]), side).reshape(r, g, hg, n, p)
    if t == 1:
        y, s = recurrent_step(s0, x[:, 0], b[:, 0], c[:, 0], dt[:, 0], a)
        y = y[:, None]
    elif r > CHUNK_ROWS_AT_ONCE:
        y, s = jax.lax.map(lambda z: chunk_step(*z, a), (s0, x, b, c, dt))
    else:
        y, s = jax.vmap(lambda *z: chunk_step(*z, a))(s0, x, b, c, dt)
    return y, state.at[ids].set(lay_side_by_side(s.reshape(r, g * hg, n, p), side))


def mamba_mixer(
    lp: Params,
    cfg: ModelConfig,
    u: jnp.ndarray,  # [B, T, D] the layer's normed input
    positions: jnp.ndarray,  # i32[B, T]
    valid: jnp.ndarray,  # bool[B, T]: the token is real (it writes a live cache slot)
    state: jnp.ndarray,  # f32[layers * slots, H / side, N, side x P]
    conv: jnp.ndarray,  # [layers * slots, taps - 1, conv_dim / 128, 128]
    slot_ids: jnp.ndarray,  # i32[rows]: this layer's slot of each row (layer * slots + slot)
    *,
    impl: str | None = None,
    split: tuple[int, int, int] | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One layer's mixer: returns ``(out [B, T, D], state, conv)``, ``out``
    before ``ssm_out_multiplier``. ``split = (nd, nc, tc)`` as for
    ``kda_attention``: projections, gate, norm and output are per token, the
    conv and the recurrence see rows."""
    bsz, t, _ = u.shape
    heads, p, n, g = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state_size, cfg.ssm_groups
    inner, conv_dim, hg, f32 = cfg.ssm_inner, cfg.ssm_conv_dim, heads // g, jnp.float32
    if impl is None:
        from dynamo_tpu.ops.attention import default_impl

        impl = default_impl()

    if cfg.ssm_in_multiplier != 1.0:
        u = u * jnp.asarray(cfg.ssm_in_multiplier, u.dtype)
    # float32 out of the projection: the conv, the gate and the step size read it unrounded.
    proj = jnp.dot(u, lp["w_ssm_in"], preferred_element_type=f32)
    if any(m != 1.0 for m in cfg.ssm_multipliers):
        proj = proj * jnp.asarray(mup_vector(cfg))
    z, xbc, dt = proj[..., :inner], proj[..., inner: inner + conv_dim], proj[..., inner + conv_dim:]
    dt = jax.nn.softplus(dt + lp["ssm_dt_bias"].astype(f32))
    dt = jnp.where(valid[..., None], dt, 0.0)  # a padding token neither decays nor writes
    a = -jnp.exp(lp["ssm_a_log"].astype(f32)).reshape(g, hg)

    def rows(tok: slice, slot: slice, width: int, state, conv):
        """The rows ``slot`` of the step, ``width`` tokens each at ``tok`` of the token axis."""
        r = slot.stop - slot.start
        ids = slot_ids[slot]
        shape = lambda v: v[:, tok].reshape(r, width, *v.shape[2:])  # noqa: E731
        ok = shape(valid)
        fresh = shape(positions)[:, 0] == 0
        with jax.named_scope("attn.ssm.conv"):
            y, conv = slot_conv(conv, ids, fresh, shape(xbc), lp["ssm_conv"], ok.sum(axis=1, dtype=jnp.int32),
                                bias=lp["ssm_conv_bias"], impl=impl)
            x = conv_heads(y, 0, heads, p).reshape(r, width, g, hg, p)
            b, c = (conv_heads(y, inner + i * g * n, g, n) for i in range(2))
        with jax.named_scope("attn.ssm.state"):
            y, state = _rows_update(state, ids, fresh, x, b, c, shape(dt).reshape(r, width, g, hg), a, impl=impl)
            y = y + lp["ssm_d"].astype(f32).reshape(g, hg)[..., None] * x
        return y.reshape(1 if split else r, -1, inner), state, conv

    if split is None:
        y, state, conv = rows(slice(0, t), slice(0, bsz), t, state, conv)
    else:
        nd, nc, tc = split
        y_d, state, conv = rows(slice(0, nd), slice(0, nd), 1, state, conv)
        y_c, state, conv = rows(slice(nd, t), slice(nd, nd + nc), tc, state, conv)
        y = jnp.concatenate([y_d, y_c], axis=1)
    with jax.named_scope("attn.ssm.norm"):
        # The gate first, then an RMS norm over each group's channels (``mamba_norm_before_gate`` false).
        y = (y * jax.nn.silu(z)).reshape(bsz, t, g, inner // g)
        y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True) + cfg.rms_eps)
        y = (y.reshape(bsz, t, inner) * lp["ssm_norm"].astype(f32)).astype(u.dtype)
    return jnp.dot(y, lp["w_ssm_out"]), state, conv
