"""The decode step of the Mamba-2 recurrence over slots (``models/mamba2.py``).

One token a row: for every (row, head) the kernel reads the slot's float32
``[state, head channels]`` matrix once, applies

    S_new = exp(dt A) S + B (dt x)^T;  y = S_new^T C

and writes the state back **in place** (``input_output_aliases``: the state
buffer is the kernel's first output) and the head's outputs. Slot ids are
scalar-prefetched and a ``fresh`` row reads zeros instead of what the slot
held, exactly as in ``ops/pallas_kda.kda_decode_step``, whose pattern this
follows. Needed bytes: one read and one write of the state, ``2 * 4 * state *
head channels`` a row a head (8.39 MB a row at 32 heads of 256 x 128);
everything else is a few KB.

Layout. ``S`` lies state-major (the ``N`` state entries on sublanes, a head's
``P`` channels on lanes), so ``dt x``, the decay and ``y`` are lane rows, ``B``
and ``C`` scale the rows of ``S`` and come as columns, and both products are a
multiply and a sum over sublanes on the VPU. ``B`` and ``C`` belong to a
*group* of heads: the wrapper hands them over once a group (``[rows, groups,
N, 2]``) and a block of heads reads its group's. The grid is ``(rows, heads /
block)``; a block of ``HEADS_PER_BLOCK`` heads moves 1 MiB in and out a step
at 256 x 128.

Tests: ``tests/test_pallas_mamba.py`` (interpret mode against
``models/mamba2.recurrent_step``), ``tests/test_chip_compile.py`` (compiled
for a described v5e). ``docs/KERNELS.md`` has the contract.
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


def supported(state: int, channels: int) -> bool:
    """Shapes the kernel tiles: state entries in whole sublane tiles, a head's
    channels in whole lane tiles (or interpret mode, which tiles nothing)."""
    return interpret_mode() or (state % 8 == 0 and channels % 128 == 0)


def _heads_block(heads_per_group: int) -> int:
    """Heads a grid step takes: a divisor of a group's heads, so that a block reads one group's B and C."""
    hb = min(HEADS_PER_BLOCK, heads_per_group)
    while heads_per_group % hb:
        hb -= 1
    return hb


def _kernel(slots_ref, fresh_ref, u_ref, decay_ref, bc_ref, s_ref, s_out_ref, y_ref, *, hb: int):
    del slots_ref  # read by the index maps only
    keep = jnp.where(fresh_ref[pl.program_id(0)] != 0, 0.0, 1.0)  # a fresh row's slot holds another sequence's state
    b, c = bc_ref[:, 0:1], bc_ref[:, 1:2]  # [state, 1]
    for i in range(hb):
        s = s_ref[i] * (decay_ref[pl.ds(i, 1), :] * keep) + b * u_ref[pl.ds(i, 1), :]
        s_out_ref[i] = s
        y_ref[pl.ds(i, 1), :] = jnp.sum(s * c, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(0,))
def mamba_decode_step(
    state: jnp.ndarray,  # f32[slots, H, N, P]: every (layer, slot)'s state; updated in place
    slot_ids: jnp.ndarray,  # i32[R]
    fresh: jnp.ndarray,  # bool[R]: the row starts from zeros
    x: jnp.ndarray,  # f32[R, H, P]
    b: jnp.ndarray,  # f32[R, G, N]: a group's B,
    c: jnp.ndarray,  # and its C
    dt: jnp.ndarray,  # f32[R, H] step size, >= 0 (0: the row neither decays nor writes)
    a: jnp.ndarray,  # f32[H], < 0
    *,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One step of the recurrence for ``R`` rows: ``(y f32[R, H, P], state)``."""
    rows, heads, p = x.shape
    groups, n = b.shape[1:]
    hb = _heads_block(heads // groups)
    per_group = heads // groups // hb  # head blocks a group
    f32 = jnp.float32
    u = (x * dt[..., None]).astype(f32)
    decay = jnp.broadcast_to(jnp.exp(dt * a)[..., None], x.shape).astype(f32)  # a head's decay on each of its lanes
    bc = jnp.stack([b, c], axis=-1).astype(f32)  # [R, G, N, 2]

    def at(index):  # index maps see the grid position, then the two prefetched scalars
        return lambda r, j, slots, fresh: index(r, j, slots)

    s_spec = pl.BlockSpec((None, hb, n, p), at(lambda r, j, slots: (slots[r], j, 0, 0)))
    row_spec = pl.BlockSpec((None, hb, p), at(lambda r, j, slots: (r, j, 0)))
    state, y = pl.pallas_call(
        functools.partial(_kernel, hb=hb),
        out_shape=(jax.ShapeDtypeStruct(state.shape, f32), jax.ShapeDtypeStruct((rows, heads, p), f32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(rows, heads // hb),
            in_specs=[row_spec, row_spec,
                      pl.BlockSpec((None, None, n, 2), at(lambda r, j, slots: (r, j // per_group, 0, 0))), s_spec],
            out_specs=[s_spec, row_spec],
        ),
        input_output_aliases={5: 0},  # the state, after the two scalars, u, the decay and the group's B and C
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary"),
                                             vmem_limit_bytes=VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(flops=6 * rows * heads * n * p, transcendentals=0,
                                      bytes_accessed=2 * 4 * rows * heads * n * p),
        interpret=interpret,
        name="mamba_decode_step",
    )(slot_ids.astype(jnp.int32), fresh.astype(jnp.int32), u, decay, bc, state)
    return y, state
