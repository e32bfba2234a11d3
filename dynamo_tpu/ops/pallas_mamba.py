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
``P`` channels on lanes), so ``x`` and ``y`` are lane rows and both products
are a multiply and a sum over sublanes on the VPU. The decay ``exp(dt A)`` and
``dt`` are one number a (row, head): they come through SMEM beside the slot
ids, and the kernel scales ``S`` and ``x`` by them itself. ``B`` and ``C``
scale the rows of ``S`` and belong to a *group* of heads: the kernel takes
them as the conv leaves them, ``[rows, groups, N]`` with the state entries on
lanes, and turns a group's row into a ``[N, 1]`` column in VMEM. The wrapper
builds nothing but the ``[rows, heads]`` decays.

Heads narrower than the lanes (``P`` 64: granite-4.0-h's 128 heads of 64
channels in one group). A float32 ``[.., N, 64]`` buffer is padded to 128 lanes
by the device's tiling, twice the bytes, so such a state lies **``side = 128 /
P`` heads of a group side by side on the lanes**, ``[slots, H / side, N, side x
P]`` (``ModelConfig.ssm_heads_per_row`` decides it; ``models/mamba2.lay_side_by_side``
is the map): a buffer row *is* ``side`` heads. ``x`` and ``y`` are ``[R, H /
side, side x P]`` by a free reshape, the group's ``B`` and ``C`` columns serve
every head of the row, and the decay and ``dt`` become a lane row of ``side``
values, built in the kernel from the same SMEM scalars (a select a head). The
body is otherwise the one above. The kernel reads ``side`` off the buffer's
shape; with ``side`` 1 nothing of it is in the program.

The grid is ``(rows, heads / block)``, the block from ``ops/pallas_kda``'s
``heads_block`` and its one budget ``STATE_VMEM``: the most heads that divide
the head count, are a whole number of groups or a divisor of one, and whose
state block, in and out and each double-buffered (4 x block), fits: a group's
16 of the 32 heads of 256 x 128 (2 MiB a block, 8 MiB buffered; all 32 would
take 16 MiB and run no faster). A block that spans groups reads each head's
group statically; one inside a group finds its group from the grid position.
Of heads side by side a block is rows of the buffer: 32 rows of 2 x 64 of the
64 (the same 2 MiB).

Tests: ``tests/test_pallas_mamba.py`` (interpret mode against
``models/mamba2.recurrent_step``), ``tests/test_chip_compile.py`` (compiled
for a described v5e), ``tools/state_kernel_bench.py`` (one call alone on the
chip, block by block). ``docs/KERNELS.md`` has the contract.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops import pallas_kda
from dynamo_tpu.ops.pallas_paged import interpret_mode  # noqa: F401  (re-exported: the callers' one switch)


def supported(state: int, lanes: int) -> bool:
    """Shapes the kernel tiles, as the state buffer has them (``state.shape[2:]``):
    state entries in whole sublane tiles, a buffer row (a head's channels, or
    narrower heads side by side) in whole lane tiles. Or interpret mode, which
    tiles nothing."""
    return interpret_mode() or (state % 8 == 0 and lanes % 128 == 0)


def _kernel(slots_ref, fresh_ref, decay_ref, dt_ref, x_ref, b_ref, c_ref, s_ref, s_out_ref, y_ref, *, hb: int, per_group: int,
            side: int):
    del slots_ref  # read by the index maps only
    r, j = pl.program_id(0), pl.program_id(1)
    keep = jnp.where(fresh_ref[r] != 0, 0.0, 1.0)  # a fresh row's slot holds another sequence's state
    first = (r * pl.num_programs(1) + j) * hb * side  # the block's first (row, head) among the scalars
    if side > 1:
        lanes = s_ref.shape[-1]
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1)

    def of_heads(ref, head: int):
        """A head's scalar; of heads side by side, each one's over its own lanes."""
        value = ref[head]
        for k in range(1, side):
            value = jnp.where(lane >= k * (lanes // side), ref[head + k], value)
        return value

    for i in range(hb):  # a buffer row: a head, or ``side`` heads of one group
        head = i * side
        if head % per_group == 0:  # the next group's B and C (a block inside a group: its one group), as columns [state, 1]
            group = (j * hb * side + head) // per_group
            b, c = b_ref[pl.ds(group, 1), :].T, c_ref[pl.ds(group, 1), :].T
        s = s_ref[i] * (of_heads(decay_ref, first + head) * keep) + b * (x_ref[pl.ds(i, 1), :] * of_heads(dt_ref, first + head))
        s_out_ref[i] = s
        y_ref[pl.ds(i, 1), :] = jnp.sum(s * c, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(0,))
def mamba_decode_step(
    state: jnp.ndarray,  # f32[slots, H / side, N, side x P]: every (layer, slot)'s state; updated in place
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
    side = heads // state.shape[1]  # heads side by side on a buffer row's lanes
    assert state.shape[1:] == (heads // side, n, side * p) and (heads // groups) % side == 0, (state.shape, x.shape, b.shape)
    heads, p = heads // side, side * p  # the buffer's rows from here on
    hb = pallas_kda.heads_block(heads, 4 * n * p, max(heads // groups, 1))
    f32 = jnp.float32
    dt = dt.astype(f32)

    def at(index):  # index maps see the grid position, then the prefetched scalars
        return lambda r, j, slots, *_: index(r, j, slots)

    s_spec = pl.BlockSpec((None, hb, n, p), at(lambda r, j, slots: (slots[r], j, 0, 0)))
    row_spec = pl.BlockSpec((None, hb, p), at(lambda r, j, slots: (r, j, 0)))
    group_spec = pl.BlockSpec((None, groups, n), at(lambda r, j, slots: (r, 0, 0)))  # every group's: fetched once a row
    state, y = pl.pallas_call(
        functools.partial(_kernel, hb=hb, per_group=heads * side // groups, side=side),
        out_shape=(jax.ShapeDtypeStruct(state.shape, f32), jax.ShapeDtypeStruct((rows, heads, p), f32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(rows, heads // hb),
            in_specs=[row_spec, group_spec, group_spec, s_spec],
            out_specs=[s_spec, row_spec],
        ),
        input_output_aliases={7: 0},  # the state, after the four scalar operands, x, B and C
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary"),
                                             vmem_limit_bytes=2 * pallas_kda.STATE_VMEM),
        cost_estimate=pl.CostEstimate(flops=6 * rows * heads * n * p, transcendentals=0,
                                      bytes_accessed=2 * 4 * rows * heads * n * p),
        interpret=interpret,
        name="mamba_decode_step",
    )(slot_ids.astype(jnp.int32), fresh.astype(jnp.int32), jnp.exp(dt * a).reshape(-1), dt.reshape(-1),
      x.astype(f32).reshape(rows, heads, p), b.astype(f32), c.astype(f32), state)
    return y.reshape(x.shape), state
