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
operand for one row of work). ``q``, ``k`` and ``alpha = exp(g)`` scale the
rows of ``S``, so the kernel needs them as columns. It takes ``q``, ``k`` and
``g`` as the layer's projections leave them, ``[rows, heads, key]`` with the
keys on lanes, and turns a block's ``[heads, key]`` rows into ``[key, heads]``
columns in VMEM (three small transpositions a grid step; ``exp`` there too);
``beta``, one number a (row, head), comes through SMEM beside the slot ids
and scales ``u``. The wrapper builds nothing.

The grid is ``(rows, heads / block)``. A block is the most heads that divide
the head count and whose state block, in and out and each double-buffered
(4 x block), fits ``STATE_VMEM`` (``heads_block``): all 32 heads of 128 x 128
(2 MiB a block, 8 MiB buffered; 64 grid steps for 64 rows), 16 of 32 heads of
256 x 128. VMEM in all: the four state buffers, the rows' operands and outputs
(5 x 16 KB, double-buffered), the three transposed tiles and a head's
temporaries, under ``vmem_limit_bytes = 2 x STATE_VMEM``.

Tests: ``tests/test_pallas_kda.py`` (interpret mode against
``models/kda.recurrent_step``), ``tests/test_chip_compile.py`` (compiled for a
described v5e), ``tools/state_kernel_bench.py`` (one call alone on the chip,
block by block). ``docs/KERNELS.md`` has the contract.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.pallas_paged import interpret_mode  # noqa: F401  (re-exported: the callers' one switch)

#: VMEM the state's four buffers may take (the block read and the block written, each double-buffered): the one
#: budget both slot-state kernels size their grid step from (``ops/pallas_mamba.py`` reads it here). The kernels'
#: ``vmem_limit_bytes`` is twice it: the rows' operands, the transposed tiles and a head's temporaries are the rest.
STATE_VMEM = 8 << 20


def supported(key: int, value: int) -> bool:
    """Shapes the kernel tiles: keys in whole sublane tiles, values in whole
    lane tiles (or interpret mode, which tiles nothing)."""
    return interpret_mode() or (key % 8 == 0 and value % 128 == 0)


def heads_block(heads: int, head_bytes: int, per_group: int = 1) -> int:
    """Heads a grid step takes: the most that divide ``heads`` and whose
    float32 states (``head_bytes`` each), in and out and double-buffered, fit
    ``STATE_VMEM``. Where heads share operands a group of ``per_group``, a
    block is a whole number of groups or a divisor of one."""
    hb = max(1, min(heads, STATE_VMEM // (4 * head_bytes)))
    while heads % hb or (hb % per_group and per_group % hb):
        hb -= 1
    return hb


def _kernel(slots_ref, fresh_ref, beta_ref, q_ref, k_ref, g_ref, v_ref, s_ref, s_out_ref, o_ref, *, hb: int):
    del slots_ref  # read by the index maps only
    r, j = pl.program_id(0), pl.program_id(1)
    keep = jnp.where(fresh_ref[r] != 0, 0.0, 1.0)  # a fresh row's slot holds another sequence's state
    first = (r * pl.num_programs(1) + j) * hb  # the block's first (row, head) among the scalars
    # A head's row as the column it scales S by: [key, hb].
    q, k, alpha = q_ref[...].T, k_ref[...].T, jnp.exp(g_ref[...]).T * keep
    for i in range(hb):
        ki = k[:, i: i + 1]
        s = s_ref[i] * alpha[:, i: i + 1]  # decayed (and zeroed where fresh)
        u = (v_ref[pl.ds(i, 1), :] - jnp.sum(s * ki, axis=0, keepdims=True)) * beta_ref[first + i]  # [1, value]
        s = s + ki * u
        s_out_ref[i] = s
        o_ref[pl.ds(i, 1), :] = jnp.sum(s * q[:, i: i + 1], axis=0, keepdims=True)


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
    hb = heads_block(heads, 4 * kd * vd)
    f32 = jnp.float32

    def at(index):  # index maps see the grid position, then the prefetched scalars
        return lambda r, j, slots, *_: index(r, j, slots)

    s_spec = pl.BlockSpec((None, hb, kd, vd), at(lambda r, j, slots: (slots[r], j, 0, 0)))
    k_spec, v_spec = (pl.BlockSpec((None, hb, width), at(lambda r, j, slots: (r, j, 0))) for width in (kd, vd))
    state, o = pl.pallas_call(
        functools.partial(_kernel, hb=hb),
        out_shape=(jax.ShapeDtypeStruct(state.shape, f32), jax.ShapeDtypeStruct((rows, heads, vd), f32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(rows, heads // hb),
            in_specs=[k_spec, k_spec, k_spec, v_spec, s_spec],
            out_specs=[s_spec, v_spec],
        ),
        input_output_aliases={7: 0},  # the state, after the three scalar operands, q, k, g and v
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary"),
                                             vmem_limit_bytes=2 * STATE_VMEM),
        cost_estimate=pl.CostEstimate(flops=8 * rows * heads * kd * vd, transcendentals=rows * heads * kd,
                                      bytes_accessed=2 * 4 * rows * heads * kd * vd),
        interpret=interpret,
        name="kda_decode_step",
    )(slot_ids.astype(jnp.int32), fresh.astype(jnp.int32), beta.astype(f32).reshape(-1),
      q.astype(f32), k.astype(f32), g.astype(f32), v.astype(f32), state)
    return o, state
