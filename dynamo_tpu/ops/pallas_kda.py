"""The decode step of the KDA recurrence over slots (``models/kda.py``).

One token a row: for every (row, head) the kernel reads the slot's float32
``[key, value]`` state once, applies

    S' = diag(alpha) S;  u = beta (v - S'^T k);  S_new = S' + k u^T;  o = S_new^T q

and writes the state back **in place** (``input_output_aliases``: the state
buffer is the kernel's first output) and the head's ``value`` outputs. The
slot of a row comes from a scalar-prefetched id, as a page does in the paged
attention kernels; a row flagged ``fresh`` (its sequence starts in this step)
reads zeros instead of what the slot held. Needed bytes: one read and one
write of the state, ``2 * 4 * key * value`` a row a head (4.19 MB a row at 32
heads of 128 x 128); everything else is a few KB.

Layout. ``S`` lies key-major (keys on sublanes, values on lanes), so ``v``,
``u`` and ``o`` are lane rows and the two matrix-vector products are a
multiply and a sum over sublanes on the VPU (the MXU would reload a 128 x 128
operand for one row of work). ``q``, ``k``, ``alpha`` and ``beta k`` scale the
rows of ``S``, so the wrapper hands them over as columns: one
``[rows, head blocks, key, 4 * block]`` array (``cols``), a lane a (quantity,
head) pair.
The grid is ``(rows, heads / block)``; a block of ``HEADS_PER_BLOCK`` heads
moves 0.5 MiB in and out a step at 128 x 128.

Tests: ``tests/test_pallas_kda.py`` (interpret mode against
``models/kda.recurrent_step``), ``tests/test_chip_compile.py`` (compiled for a
described v5e). ``docs/KERNELS.md`` has the contract.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.pallas_paged import interpret_mode  # noqa: F401  (re-exported: the callers' one switch)

HEADS_PER_BLOCK = 8
VMEM_LIMIT = 32 << 20


def supported(key: int, value: int) -> bool:
    """Shapes the kernel tiles: keys in whole sublane tiles, values in whole
    lane tiles (or interpret mode, which tiles nothing)."""
    return interpret_mode() or (key % 8 == 0 and value % 128 == 0)


def _heads_block(heads: int) -> int:
    hb = min(HEADS_PER_BLOCK, heads)
    while heads % hb:
        hb -= 1
    return hb


def _kernel(slots_ref, fresh_ref, v_ref, cols_ref, s_ref, s_out_ref, o_ref, *, hb: int):
    del slots_ref  # read by the index maps only
    keep = jnp.where(fresh_ref[pl.program_id(0)] != 0, 0.0, 1.0)  # a fresh row's slot holds another sequence's state
    for i in range(hb):
        col = lambda n: cols_ref[:, n * hb + i: n * hb + i + 1]  # noqa: E731  [key, 1]
        q, k, alpha, kb = col(0), col(1), col(2), col(3)
        s = s_ref[i] * (alpha * keep)  # decayed (and zeroed where fresh)
        u = v_ref[pl.ds(i, 1), :] - jnp.sum(s * k, axis=0, keepdims=True)  # [1, value]
        s = s + kb * u
        s_out_ref[i] = s
        o_ref[pl.ds(i, 1), :] = jnp.sum(s * q, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(0,))
def kda_decode_step(
    state: jnp.ndarray,  # f32[N, H, K, V]: every (layer, slot)'s state; updated in place
    slot_ids: jnp.ndarray,  # i32[R]
    fresh: jnp.ndarray,  # bool[R]: the row starts from zeros
    q: jnp.ndarray,  # f32[R, H, K]
    k: jnp.ndarray,
    v: jnp.ndarray,  # f32[R, H, V]
    g: jnp.ndarray,  # f32[R, H, K] log-decay, <= 0
    beta: jnp.ndarray,  # f32[R, H]
    *,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One step of the recurrence for ``R`` rows: ``(o f32[R, H, V], state)``."""
    rows, heads, kd = q.shape
    vd = v.shape[-1]
    hb = _heads_block(heads)
    f32 = jnp.float32
    # Columns, a block of heads at a time: [R, H / hb, K, 4 * hb], lane n * hb + i holding quantity n of the block's head i.
    cols = jnp.stack([q, k, jnp.exp(g), k * beta[..., None]], axis=1).astype(f32)  # [R, 4, H, K]
    cols = cols.reshape(rows, 4, heads // hb, hb, kd).transpose(0, 2, 4, 1, 3).reshape(rows, heads // hb, kd, 4 * hb)

    def at(index):  # index maps see the grid position, then the two prefetched scalars
        return lambda r, j, slots, fresh: index(r, j, slots)

    s_spec = pl.BlockSpec((None, hb, kd, vd), at(lambda r, j, slots: (slots[r], j, 0, 0)))
    row_spec = pl.BlockSpec((None, hb, vd), at(lambda r, j, slots: (r, j, 0)))
    state, o = pl.pallas_call(
        functools.partial(_kernel, hb=hb),
        out_shape=(jax.ShapeDtypeStruct(state.shape, f32), jax.ShapeDtypeStruct((rows, heads, vd), f32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(rows, heads // hb),
            in_specs=[row_spec, pl.BlockSpec((None, None, kd, 4 * hb), at(lambda r, j, slots: (r, j, 0, 0))), s_spec],
            out_specs=[s_spec, row_spec],
        ),
        input_output_aliases={4: 0},  # the state, after the two scalars, v and cols
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary"),
                                             vmem_limit_bytes=VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(flops=8 * rows * heads * kd * vd, transcendentals=0,
                                      bytes_accessed=2 * 4 * rows * heads * kd * vd),
        interpret=interpret,
        name="kda_decode_step",
    )(slot_ids.astype(jnp.int32), fresh.astype(jnp.int32), v.astype(f32), cols, state)
    return o, state
