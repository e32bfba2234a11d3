"""A recurrent layer's causal convolution over slots (``models/kda.slot_conv``:
KDA's q, k, v streams and the Mamba-2 mixer's x, B, C alike).

For every row of ``T`` tokens (a decode row: one) the kernel reads the slot's
conv state (the last ``taps - 1`` inputs of every channel) once, applies

    full = [prev[0], ..., prev[taps - 2], x[0], ..., x[T - 1]]
    y[t] = silu(sum_j full[t + j] * filt[j] (+ bias))
    new  = full[n : n + taps - 1]          # n = the row's valid tokens

in float32, in ``models/kda.causal_conv``'s order, and writes the state back
**in place** (``input_output_aliases``: the conv buffer is the kernel's first
output) and the row's outputs. The slot of a row comes from a scalar-prefetched
id, exactly as in ``ops/pallas_kda.kda_decode_step``; a row flagged ``fresh``
reads zeros instead of what the slot held, and a row with no valid token
(``n`` 0: a padding row on the null slot) writes back what it read. Needed
bytes: one read and one write of the rows' conv state and of the rows' inputs
and outputs (74 KB + 74 KB + 49 KB + 49 KB a decode row at 12,288 channels).

Layout. The buffer is ``[layers * slots, taps - 1, channels / 128, 128]``
(``ModelConfig.state_shapes``): a slot's inputs of one layer are one contiguous
run of whole ``(16, 128)`` tiles (the last one half full where ``channels /
128`` is an odd multiple of 8), which is the layout the buffer is allocated in,
the one a step program is handed and the one it hands back: nothing re-lays
it, and no XLA operation of a step program touches it. ``x`` and ``y`` are
``[rows, T, channels / 128, 128]`` float32, tokens on an untiled axis, so that
with 128-wide heads ``y`` *is* ``[rows, T, heads, head_dim]`` for the streams
one after the other, the form the state kernels take. The filter (and the bias)
come as float32 ``[taps, channels / 128, 128]``, fetched once a call.

The grid is ``(rows,)``, a block a row: 64 grid steps a layer for 64 decode
rows, the DMA of the next row's blocks under the arithmetic of this one's (a
dozen vector operations a tile and token). A row's ``x`` and ``y`` blocks, each
double-buffered, have to fit ``ROWS_VMEM``: 12 MiB for a 64-token chunk of
12,288 channels; a longer chunk takes the XLA path (``supported``).

Tests: ``tests/test_pallas_conv.py`` (interpret mode against
``models/kda.causal_conv``), ``tests/test_chip_compile.py`` (compiled for a
described v5e), ``tools/state_kernel_bench.py --kinds conv`` (one call alone on
the chip beside a bare copy of the same blocks). ``docs/KERNELS.md`` has the
contract.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.pallas_paged import interpret_mode  # noqa: F401  (re-exported: the callers' one switch)

LANES = 128
#: VMEM a row's ``x`` and ``y`` blocks may take, each double-buffered (4 x tokens x channels x 4 bytes); the kernel's
#: ``vmem_limit_bytes`` is twice it: the slot's blocks, the filter and the body's temporaries are the rest.
ROWS_VMEM = 16 << 20


def supported(tokens: int, sublanes: int, lanes: int) -> bool:
    """Shapes the kernel tiles: channels in whole lane tiles, those in whole
    float32 sublane tiles, and a row's tokens within ``ROWS_VMEM`` (or
    interpret mode, which tiles nothing)."""
    return interpret_mode() or (lanes == LANES and sublanes % 8 == 0 and 16 * tokens * sublanes * lanes <= ROWS_VMEM)


def _kernel(slots_ref, fresh_ref, valid_ref, x_ref, f_ref, *rest, taps: int, bias: bool):
    del slots_ref  # read by the index maps only
    b_ref, c_ref, c_out_ref, y_ref = rest if bias else (None, *rest)
    r, m, tokens = pl.program_id(0), taps - 1, x_ref.shape[0]
    fresh, n = fresh_ref[r] != 0, valid_ref[r]

    def full(i: int):  # static i: a carried input (a fresh row's slot holds another sequence's: zeros), then the row's tokens
        return jnp.where(fresh, 0.0, c_ref[i].astype(jnp.float32)) if i < m else x_ref[i - m]

    def out(window):  # the taps' inputs of one token, oldest first
        y = window[0] * f_ref[0]
        for j in range(1, taps):
            y = y + window[j] * f_ref[j]
        return jax.nn.silu(y + b_ref[...] if bias else y)

    for t in range(min(m, tokens)):  # the tokens whose window reaches into the carried inputs
        y_ref[t] = out([full(t + j) for j in range(taps)])
    if tokens > m:
        def token(t, carry):
            y_ref[t] = out([x_ref[t - m + j] for j in range(taps)])
            return carry

        jax.lax.fori_loop(m, tokens, token, 0)
    for j in range(m):  # full[n + j]: one of the carried inputs while n + j < m, else the row's own token n + j - m
        i = n + j
        held = full(m - 1)
        for k in range(m - 2, j - 1, -1):
            held = jnp.where(i == k, full(k), held)
        c_out_ref[j] = jnp.where(i < m, held, x_ref[jnp.clip(i - m, 0, tokens - 1)]).astype(c_out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(0,))
def slot_conv_step(
    conv: jnp.ndarray,  # [N, taps - 1, C, 128]: every (layer, slot)'s conv state; updated in place
    slot_ids: jnp.ndarray,  # i32[R]
    fresh: jnp.ndarray,  # bool[R]: the row starts from zeros
    n_valid: jnp.ndarray,  # i32[R]: the row's first tokens that enter its slot (0: the slot comes back as it was read)
    x: jnp.ndarray,  # f32[R, T, C, 128]: the rows' inputs on every channel
    filt: jnp.ndarray,  # [taps, C, 128], the last tap on the current token
    bias: jnp.ndarray | None = None,  # [C, 128]
    *,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The conv of ``R`` rows of ``T`` tokens: ``(y f32[R, T, C, 128], conv)``."""
    rows, tokens, c, lanes = x.shape
    taps = filt.shape[0]
    f32 = jnp.float32

    def at(index):  # index maps see the grid position, then the prefetched scalars
        return lambda r, slots, *_: index(r, slots)

    c_spec = pl.BlockSpec((None, taps - 1, c, lanes), at(lambda r, slots: (slots[r], 0, 0, 0)))
    row_spec = pl.BlockSpec((None, tokens, c, lanes), at(lambda r, slots: (r, 0, 0, 0)))
    once = [pl.BlockSpec((taps, c, lanes), at(lambda r, slots: (0, 0, 0)))]  # the filter, then the bias: fetched once
    consts = [filt.astype(f32)]
    if bias is not None:
        once.append(pl.BlockSpec((c, lanes), at(lambda r, slots: (0, 0))))
        consts.append(bias.astype(f32))
    conv, y = pl.pallas_call(
        functools.partial(_kernel, taps=taps, bias=bias is not None),
        out_shape=(pltpu.HBM(conv.shape, conv.dtype), jax.ShapeDtypeStruct(x.shape, f32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(rows,),
            in_specs=[row_spec, *once, c_spec],
            out_specs=[c_spec, row_spec],
        ),
        input_output_aliases={4 + len(consts): 0},  # the conv buffer, after the three scalar operands, x and the constants
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",), vmem_limit_bytes=2 * ROWS_VMEM),
        cost_estimate=pl.CostEstimate(
            flops=(2 * taps + 4) * x.size, transcendentals=x.size,
            bytes_accessed=2 * 4 * x.size + 2 * rows * (taps - 1) * c * lanes * conv.dtype.itemsize),
        interpret=interpret,
        name="slot_conv_step",
    )(slot_ids.astype(jnp.int32), fresh.astype(jnp.int32), n_valid.astype(jnp.int32), x.astype(f32), *consts, conv)
    return y, conv
