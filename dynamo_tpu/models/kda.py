"""Kimi Delta Attention (KDA, arXiv 2510.26692): a delta-rule linear-attention
layer whose per-sequence state is a fixed-size **slot**, not pages.

A head keeps a float32 ``[key, value]`` matrix ``S`` and, token by token,

    S_t = (I - beta_t k_t k_t^T) diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

with a decay ``alpha_t = exp(g_t)`` per key channel and a write strength
``beta_t`` per head. ``q``, ``k`` and ``v`` come from a causal depthwise
convolution of ``cfg.kda_conv_size`` taps over the projections followed by
SiLU, so a sequence also carries the last ``taps - 1`` inputs of the three
streams (its **conv state**). The configuration says which form the gates
take: Ling-3.0's (a full-rank decay input, the log-decay bounded ``g =
kda_lower_bound * sigmoid(.)``, ``beta`` in (0, 1), an output gate a head) or
Kimi Linear's own, Solar-Open2's (``cfg.kda_low_rank``: the decay input and
the output gate through low-rank pairs, the gate a value a channel;
``cfg.kda_decay`` "softplus": ``g = -exp(a_log) softplus(.)``;
``cfg.kda_beta_scale`` 2: ``beta`` in (0, 2)). ``benchmark/reference/ling_3_flash.py``
and ``solar_open2.py`` write the two out equation by equation.

What a step does with a row's slot:

- a **decode** row (one token) takes one step of the recurrence: on a TPU the
  Pallas kernel ``ops/pallas_kda.kda_decode_step`` (one read and one write of
  the slot's state, in place), elsewhere :func:`recurrent_step` on gathered
  rows;
- a **chunk** row (a mixed step's chunk of a prompt) takes the chunkwise form
  (:func:`chunk_step`): one triangular system per chunk and head, then the
  state update, as matrix products in plain ``jax.numpy``. Decays between two
  tokens of a chunk are taken as ``exp`` of a *difference* of cumulated
  log-decays, never as a quotient of two exponentials, so nothing overflows
  whatever the decay;
- a row whose first position is 0 starts from a zero state: the slot a
  sequence is given is zeroed by the sequence's own first chunk, not by a
  pass of its own;
- a padding token (``valid`` false: it writes the null page) leaves the state
  as it is (``beta = 0``, ``g = 0``) and does not enter the conv state;
- the conv state of either kind of row (:func:`slot_conv`) goes on a TPU
  through the Pallas kernel ``ops/pallas_conv.slot_conv_step`` (the slot's
  ``taps - 1`` inputs read once, the conv and SiLU done on them and the row's
  tokens, the last inputs written back in place), elsewhere through
  :func:`causal_conv` on gathered rows.

The state buffers are flat over ``(KDA layer, slot)`` like the paged cache is
over ``(layer, page)``: a layer addresses ``layer * slots + slot``. Slot 0 is
the null slot: padding rows read and write it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.quant import held_flat, quant_matmul as _qmm

Params = dict

#: Chunk rows the chunkwise form takes at once (``vmap``; more run row by row, ``lax.map``) at up to this many
#: heads: its ``[tokens, tokens, heads, key]`` decay differences are 67 MB a row at 64 tokens x 32 heads of 128.
CHUNK_ROWS_AT_ONCE, CHUNK_ROWS_HEADS = 4, 32


def chunk_rows_at_once(heads: int) -> int:
    """Chunk rows the chunkwise form takes at once at ``heads`` heads: as many
    as keep a step's decay differences where :data:`CHUNK_ROWS_AT_ONCE` rows of
    :data:`CHUNK_ROWS_HEADS` heads have them (4 at 32 heads, 2 at 64, 1 from 128)."""
    return max(1, CHUNK_ROWS_AT_ONCE * CHUNK_ROWS_HEADS // heads)


def init_kda_params(cfg: ModelConfig, key: jax.Array, dt, num_layers: int) -> dict[str, jnp.ndarray]:
    """A KDA block's leaves, layers stacked on the leading axis. The four
    large projections take the stack's names for them (``wq wk wv wo``: they
    are the layer's q, k, v and output projections, and are served int8)."""
    d, h, hd, q = cfg.hidden_size, cfg.num_heads, cfg.head_dim, cfg.q_dim
    l, taps, r = num_layers, cfg.kda_conv_size, cfg.kda_low_rank
    keys = jax.random.split(key, 10)

    def w(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32) * (fan_in**-0.5)).astype(dt)

    if r:  # the decay input and the output gate, a value a channel each, through pairs of rank r
        forms = {"w_decay_a": w(keys[4], (l, d, r), d), "w_decay_b": w(jax.random.fold_in(keys[4], 1), (l, r, q), r),
                 "w_out_gate_a": w(keys[6], (l, d, r), d), "w_out_gate_b": w(jax.random.fold_in(keys[6], 1), (l, r, q), r)}
    else:  # a_t: one log-decay input a head and key channel, full rank; the gate a value a head
        forms = {"w_decay": w(keys[4], (l, d, q), d), "w_out_gate": w(keys[6], (l, d, h), d)}
    return {
        "wq": w(keys[0], (l, d, q), d), "wk": w(keys[1], (l, d, q), d), "wv": w(keys[2], (l, d, q), d),
        "wo": w(keys[3], (l, q, d), q),
        **forms,
        "w_beta": w(keys[5], (l, d, h), d),
        "conv_q": w(keys[7], (l, taps, q), taps), "conv_k": w(keys[8], (l, taps, q), taps),
        "conv_v": w(keys[9], (l, taps, q), taps),
        "a_log": jnp.zeros((l, h), dt),  # exp(a_log) scales a head's decay input
        "dt_bias": jnp.zeros((l, q), dt),
        "o_norm": jnp.ones((l, hd), dt),  # RMS norm over each head's outputs, one weight for all heads
    }


def init_state(cfg: ModelConfig, slots: int, dtype=None) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The two state buffers of a model with recurrent layers, zeros, flat
    over ``(recurrent layer, slot)``; ``slots`` counts the null slot. The
    shapes are the model's (``cfg.state_shapes``). KDA: ``(state f32[layers *
    slots, heads, key, value], conv [layers * slots, taps - 1, 3 * q_dim / 128,
    128])``; a Mamba-2 mixer (``models/mamba2.py``): ``(f32[layers * slots,
    heads, state, head channels], [layers * slots, taps - 1, conv channels /
    128, 128])``. The conv buffer's channels lie in rows of 128 lanes, so that
    a slot's inputs of one layer are one run of whole tiles in the layout the
    buffer is allocated in: a step program takes it, updates the rows' slots
    where they lie and hands it back (:func:`slot_conv`)."""
    n = cfg.recurrent_layers * slots
    state, conv = cfg.state_shapes()
    return jnp.zeros((n, *state), jnp.float32), jnp.zeros((n, *conv), dtype or jnp.dtype(cfg.dtype))


def recurrent_step(s, q, k, v, g, beta):
    """One token of the recurrence on ``s f32[..., key, value]``; ``q k g``
    ``[..., key]``, ``v [..., value]``, ``beta [...]``. Returns ``(o, s)``."""
    s = s * jnp.exp(g)[..., :, None]
    u = (v - jnp.einsum("...k,...kv->...v", k, s, precision="highest")) * beta[..., None]
    s = s + k[..., :, None] * u[..., None, :]
    return jnp.einsum("...k,...kv->...v", q, s, precision="highest"), s


def chunk_step(s0, q, k, v, g, beta):
    """``C`` tokens of the recurrence at once, from the carried state:
    ``s0 f32[H, key, value]``, ``q k g [C, H, key]``, ``v [C, H, value]``,
    ``beta [C, H]``. Returns ``(o [C, H, value], s [H, key, value])``.

    With ``G_t`` the log-decay cumulated up to and including token ``t`` and
    ``w_t = beta_t (v_t - S'_t^T k_t)`` the token's write (``S'_t`` the state
    decayed to ``t``): ``S_t = diag(e^{G_t}) S_0 + sum_{s<=t} diag(e^{G_t-G_s})
    k_s w_s^T``, so the writes solve the unit lower-triangular system
    ``(I + diag(beta) A) W = diag(beta) (V - (K e^G) S_0)`` with ``A[t, s] =
    sum_c k_t[c] k_s[c] e^{G_t[c]-G_s[c]}`` for ``s < t``; outputs and the new
    state are products with ``W``."""
    hi = jax.lax.Precision.HIGHEST
    c = q.shape[0]
    cum = jnp.cumsum(g, axis=0)  # [C, H, K], <= 0 and falling
    # Decay from token s to token t >= s, per head and key channel: an exponent <= 0.
    diff = cum[:, None] - cum[None, :]  # [t, s, H, K]
    causal = jnp.arange(c)[:, None] >= jnp.arange(c)[None, :]
    decay = jnp.exp(jnp.where(causal[:, :, None, None], diff, -jnp.inf))  # 0 above the diagonal
    kk = jnp.einsum("thc,shc,tshc->hts", k, k, decay, precision=hi)
    qk = jnp.einsum("thc,shc,tshc->hts", q, k, decay, precision=hi)
    strict = jnp.arange(c)[:, None] > jnp.arange(c)[None, :]
    eye = jnp.eye(c, dtype=jnp.float32)
    bt = jnp.moveaxis(beta, 0, 1)  # [H, C]
    lower = eye + bt[:, :, None] * jnp.where(strict, kk, 0.0)
    into = jnp.exp(cum)  # decay from the chunk's start to each token
    rhs = bt[:, :, None] * (jnp.moveaxis(v, 0, 1) - jnp.einsum("thc,hcv->htv", k * into, s0, precision=hi))
    w = jax.scipy.linalg.solve_triangular(lower, rhs, lower=True, unit_diagonal=True)  # [H, C, V]
    o = jnp.einsum("thc,hcv->thv", q * into, s0, precision=hi) + jnp.einsum("hts,hsv->thv", qk, w, precision=hi)
    out_of = jnp.exp(cum[-1][None] - cum)  # decay from each token to the chunk's end
    s = into[-1][:, :, None] * s0 + jnp.einsum("shc,hsv->hcv", k * out_of, w, precision=hi)
    return o, s


def causal_conv(x, prev, filt, n_valid, bias=None):
    """Causal depthwise convolution (plus ``bias [W]``, where the layer has
    one) then SiLU of rows ``x [R, T, W]`` behind their carried inputs ``prev
    [R, taps - 1, W]``; ``filt [taps, W]``, the last tap on the current token.
    Returns ``(y [R, T, W], the last taps - 1 inputs up to each row's
    ``n_valid``-th token)``."""
    taps, t = filt.shape[0], x.shape[1]
    full = jnp.concatenate([prev.astype(x.dtype), x], axis=1)  # [R, taps - 1 + T, W]
    y = sum(full[:, j: j + t].astype(jnp.float32) * filt[j].astype(jnp.float32) for j in range(taps))
    carried = jax.vmap(lambda row, n: jax.lax.dynamic_slice_in_dim(row, n, taps - 1, axis=0))(full, n_valid)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return jax.nn.silu(y), carried


def slot_conv(conv, ids, fresh, x, filt, n_valid, bias=None, *, impl: str | None):
    """:func:`causal_conv` of rows ``x f32[R, T, W]`` through the slots ``ids``
    of ``conv [slots, taps - 1, W / lanes, lanes]``; ``fresh`` rows start from
    zeros, a row's first ``n_valid`` tokens enter its slot. Returns ``(y [R, T,
    W / lanes, lanes], conv)``: the output in the buffer's rows of lanes, for
    :func:`conv_heads` to split. Each row's slot moves once each way: through
    ``ops/pallas_conv.slot_conv_step`` where it tiles the shape, else by one
    gather and one scatter of the rows' slots. Where the buffer's rows hold
    more than ``W`` channels (``ModelConfig.state_shapes`` rounds them up to
    whole sublane tiles), the inputs are padded with zeros to fill them."""
    r, t, w = x.shape
    tile = conv.shape[1:]
    held = tile[1] * tile[2]  # the channels a slot's rows hold: ``w``, or ``w`` rounded up to whole tiles (zeros behind it)

    def lay(z):
        if z is None:
            return None
        if held > w:
            z = jnp.pad(z, [(0, 0)] * (z.ndim - 1) + [(0, held - w)])
        return z.reshape(*z.shape[:-1], *tile[1:])

    if impl == "pallas":
        from dynamo_tpu.ops import pallas_conv

        if pallas_conv.supported(t, *tile[1:]):
            return pallas_conv.slot_conv_step(conv, ids, fresh, n_valid, lay(x), lay(filt), lay(bias),
                                              interpret=pallas_conv.interpret_mode())
    prev = conv[ids].reshape(r, -1, held)
    prev = jnp.where(fresh[:, None, None], jnp.zeros((), conv.dtype), prev if held == w else prev[..., :w])
    y, carried = causal_conv(x, prev, filt, n_valid, bias)
    return lay(y), conv.at[ids].set(lay(carried.astype(conv.dtype)))


def conv_heads(y, first: int, heads: int, dim: int):
    """Channels ``[first, first + heads * dim)`` of :func:`slot_conv`'s output
    ``y [R, T, rows, lanes]`` as ``[R, T, heads, dim]``: the rows that hold
    them, sliced where they lie (a head of 128 channels *is* a row), where the
    channels are whole rows."""
    r, t, _, lanes = y.shape
    if first % lanes == 0 and (heads * dim) % lanes == 0:
        return y[:, :, first // lanes: (first + heads * dim) // lanes].reshape(r, t, heads, dim)
    return y.reshape(r, t, -1)[..., first: first + heads * dim].reshape(r, t, heads, dim)


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)


def _rows_update(state, ids, fresh, q, k, v, g, beta, *, impl: str | None):
    """The recurrence over rows ``[R, T]`` of heads (``q k g [R, T, H, K]``,
    ``v [R, T, H, V]``, ``beta [R, T, H]``, float32) on the slots ``ids``;
    ``fresh`` rows start from zeros. Returns ``(o [R, T, H, V], state)``."""
    r, t = q.shape[:2]
    if t == 1 and impl == "pallas":
        from dynamo_tpu.ops import pallas_kda

        if pallas_kda.supported(q.shape[-1], v.shape[-1]):
            o, state = pallas_kda.kda_decode_step(
                state, ids, fresh, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], interpret=pallas_kda.interpret_mode())
            return o[:, None], state
    s0 = jnp.where(fresh[:, None, None, None], 0.0, state[ids])
    if t == 1:
        o, s = recurrent_step(s0, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
        o = o[:, None]
    elif r > chunk_rows_at_once(q.shape[2]):
        o, s = jax.lax.map(lambda a: chunk_step(*a), (s0, q, k, v, g, beta))
    else:
        o, s = jax.vmap(chunk_step)(s0, q, k, v, g, beta)
    return o, state.at[ids].set(s)


def kda_attention(
    lp: Params,
    cfg: ModelConfig,
    h: jnp.ndarray,  # [B, T, D] normed input
    positions: jnp.ndarray,  # i32[B, T]
    valid: jnp.ndarray,  # bool[B, T]: the token is real (it writes a live cache slot)
    state: jnp.ndarray,  # f32[layers * slots, H, K, V]
    conv: jnp.ndarray,  # [layers * slots, taps - 1, 3 * q_dim / 128, 128]
    slot_ids: jnp.ndarray,  # i32[rows]: this layer's slot of each row (layer * slots + slot)
    *,
    impl: str | None = None,
    split: tuple[int, int, int] | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One KDA layer: returns ``(out [B, T, D], state, conv)``.

    ``split = (nd, nc, tc)``: ``h`` is one token axis ``[1, nd + nc * tc, D]``
    (``llama.forward``'s ``split``) and ``slot_ids`` has an entry per slot of
    the step; projections, gates and the output are per token, the conv and
    the recurrence see rows: the decode slots as ``[nd, 1]``, the chunk slots
    as ``[nc, tc]``."""
    b, t, _ = h.shape
    heads, hd = cfg.num_heads, cfg.head_dim
    if impl is None:
        from dynamo_tpu.ops.attention import default_impl

        impl = default_impl()

    with jax.named_scope("kda.conv"):
        # Held flat: the conv reads whole rows, and the heads' layout must not reach the dots. Kept float32: a
        # fast-decaying state is little more than the last token's k v^T, so a head's output is (q . k) v, and
        # the head norm divides the scale out again: where q . k is near zero, rounding q and k to bf16 first
        # turns the head's whole output (PERF.md, PR 40: half of the served model's distance from the reference).
        x = jnp.concatenate([held_flat(_qmm(h, lp[name], preferred_element_type=jnp.float32))
                             for name in ("wq", "wk", "wv")], axis=-1)  # f32[B, T, 3Q]
        filt = jnp.concatenate([lp["conv_q"], lp["conv_k"], lp["conv_v"]], axis=-1)  # [taps, 3Q]
    with jax.named_scope("kda.gates"):
        f32 = lambda name: jnp.dot(h, lp[name], preferred_element_type=jnp.float32)  # noqa: E731

        def through_pair(name: str):
            """``h`` through the low-rank pair ``name_a`` ``name_b``, float32 out; the rank-wide middle in ``h``'s dtype."""
            return jnp.dot(f32(f"{name}_a").astype(h.dtype), lp[f"{name}_b"], preferred_element_type=jnp.float32)

        # (held flat, as the q, k, v projections are: the heads' layout must not reach the dot and re-lay its weight)
        a = held_flat(through_pair("w_decay") if cfg.kda_low_rank else f32("w_decay")) + lp["dt_bias"].astype(jnp.float32)
        rate = jnp.repeat(jnp.exp(lp["a_log"].astype(jnp.float32)), hd)  # a head's rate on each of its channels
        if cfg.kda_decay == "softplus":
            g = -rate * jax.nn.softplus(a)  # <= 0, unbounded below
        elif cfg.kda_decay == "bounded":
            g = cfg.kda_lower_bound * jax.nn.sigmoid(rate * a)  # in (lower bound, 0)
        else:
            raise NotImplementedError(f"kda_decay {cfg.kda_decay!r}: 'bounded' or 'softplus'")
        beta = jax.nn.sigmoid(f32("w_beta"))  # [B, T, H]
        if cfg.kda_beta_scale != 1.0:
            beta = beta * cfg.kda_beta_scale
        # The output gate: a value a channel through its pair [B, T, H, hd], or a value a head [B, T, H].
        out_gate = (jax.nn.sigmoid(held_flat(through_pair("w_out_gate"))).reshape(b, t, heads, hd) if cfg.kda_low_rank
                    else jax.nn.sigmoid(f32("w_out_gate")))
        # A padding token neither decays nor writes.
        g = jnp.where(valid[..., None], g, 0.0)
        beta = jnp.where(valid[..., None], beta, 0.0)

    def rows(tok: slice, slot: slice, width: int, state, conv):
        """The rows ``slot`` of the step, ``width`` tokens each at ``tok`` of the token axis."""
        n = slot.stop - slot.start
        ids = slot_ids[slot]
        shape = lambda z: z[:, tok].reshape(n, width, *z.shape[2:])  # noqa: E731
        xr, ok = shape(x), shape(valid)
        fresh = shape(positions)[:, 0] == 0
        with jax.named_scope("kda.conv"):
            y, conv = slot_conv(conv, ids, fresh, xr, filt, ok.sum(axis=1, dtype=jnp.int32), impl=impl)
            q, k, v = (conv_heads(y, i * heads * hd, heads, hd) for i in range(3))
            q, k = _l2norm(q) * hd**-0.5, _l2norm(k)
        with jax.named_scope("kda.state"):
            o, state = _rows_update(state, ids, fresh, q, k, v, shape(g).reshape(n, width, heads, hd), shape(beta),
                                    impl=impl)
        return o.reshape(1 if split else n, -1, heads, hd), state, conv

    if split is None:
        o, state, conv = rows(slice(0, t), slice(0, b), t, state, conv)
    else:
        nd, nc, tc = split
        o_d, state, conv = rows(slice(0, nd), slice(0, nd), 1, state, conv)
        o_c, state, conv = rows(slice(nd, t), slice(nd, nd + nc), tc, state, conv)
        o = jnp.concatenate([o_d, o_c], axis=1)
    with jax.named_scope("kda.out"):
        o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + cfg.rms_eps) * lp["o_norm"].astype(jnp.float32)
        o = (o * (out_gate if cfg.kda_low_rank else out_gate[..., None])).astype(h.dtype)
        return _qmm(o.reshape(b, t, heads * hd), lp["wo"]), state, conv
