"""Grouped matmul over int8 expert weights, read as they are stored.

The routed experts of a dropless MoE layer are three grouped matmuls: token
copies sorted by expert, ``rows of group e`` times ``W[e]``. With int8 experts
(``{"qw": int8[E, d_in, d_out], "scale": bf16[E, d_out]}``) the XLA path has to
widen the whole expert array to bf16 in HBM first, because ``lax.ragged_dot``
takes no quantized operand and no producer fuses into it: every weight crosses
HBM three times, twice at double width, whether a row chose its expert or not.

This kernel moves int8 tiles HBM -> VMEM, widens them to bf16 **in VMEM**,
multiplies on the MXU with bf16 activations into a float32 accumulator and
applies ``scale[e]`` to the accumulator in the epilogue. The per-output-channel
scale commutes with the contraction (``models/quant.py``; what ``quant_matmul``
does for dense weights), so this rounds less than the bf16 product
``qw * scale`` of the widened path, never more.

Grid and metadata (the pattern of JAX's ``megablox.gmm``, whose rhs is not
quantized): the grid is ``(n tiles, visits)``; a *visit* is one (row tile,
group) pair that has rows in common. Visits are listed in scalar-prefetched
arrays built from the group sizes, so a group with no rows is never visited
and its weights are never read; a row tile that holds a group boundary is
visited once per group and each visit stores only its own rows. Consecutive
visits of one row tile keep the output block resident, so the partial stores
compose. The grid is as long as the visits can be at most (row tiles + groups
- 1); the steps beyond the real visits repeat the last one's block indices (no
DMA) and skip the body.

The weights may carry a leading layer axis (``int8[L, E, d_in, d_out]``) with
the layer index prefetched as a scalar: under ``lax.scan`` over stacked layers
a custom call on the scan's slice would make XLA copy the layer's experts
first, so the model step hands over the stack and the index instead.

One kernel serves both halves of the expert FFN: ``act="silu_mul"`` takes two
weight arrays over one left operand and stores ``silu(x W_g) * (x W_u)``
(gate and up fused: one read of the rows, no intermediate in HBM), ``act=None``
one array (down).

Tests: ``tests/test_pallas_moe.py`` (interpret mode on the CPU against
``_widen`` + ``ragged_dot`` and a float32 reference), and
``tests/test_chip_compile.py`` (compiled for a described v5e at OLMoE's and
DeepSeek-V2-Lite's widths). ``docs/KERNELS.md`` has the tile sizes and what
they take of VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
#: Sublanes of one packed bf16 tile: the row tile is a multiple of it.
ROW_ALIGN = 16
#: v5e has 128 MiB of VMEM; the default scoped limit of 16 MiB is too tight
#: for two double-buffered int8 blocks beside their widened chunks.
VMEM_LIMIT = 64 << 20


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def row_tile(m: int) -> int:
    """Rows per tile for ``m`` token copies. Up to 128 copies one tile holds
    them all. Beyond, a visit costs MXU time for the whole tile whatever the
    group's rows in it, while an expert's block is fetched once however many
    tiles it spans (consecutive visits share the block index): 64 rows were
    fastest on a v5e up to 1,024 copies and 128 from there (PERF.md, PR 25)."""
    if m <= 128:
        return _round_up(m, ROW_ALIGN)
    return 64 if m <= 1024 else 128


def col_tile(d_in: int, d_out: int, n_rhs: int) -> int:
    """Output columns per tile: the largest multiple of 128 dividing
    ``d_out`` whose int8 blocks (``n_rhs`` arrays, double-buffered) stay
    within 8 MiB of VMEM."""
    budget = (8 << 20) // (2 * n_rhs * d_in)
    tn = max(LANES, min(d_out, budget // LANES * LANES))
    while d_out % tn:
        tn -= LANES
    return tn


def k_chunk(d_in: int) -> int:
    """Contraction rows widened per inner step: the widened chunk is a VMEM
    temporary, and the chunk loop lets the convert of one chunk overlap the
    matmul of the one before."""
    for tk in (512, 256, 128):
        if d_in % tk == 0:
            return tk
    return d_in


def supported(d_in: int, d_out: int) -> bool:
    """Widths the kernel tiles: both multiples of one lane tile."""
    return d_in % LANES == 0 and d_out % LANES == 0


def group_metadata(group_sizes: jnp.ndarray, m_pad: int, tm: int):
    """Visit lists for :func:`grouped_matmul_int8`.

    Returns ``(offsets i32[E+1], group_ids i32[V], tile_ids i32[V],
    num_visits i32[1])`` with ``V = m_pad // tm + E - 1``. Group ``g`` owns
    rows ``offsets[g]:offsets[g+1]`` and is visited once per row tile it
    touches; entries past ``num_visits`` repeat the last real visit.
    """
    e = group_sizes.shape[0]
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first_tile = starts // tm
    n_tiles = jnp.where(sizes > 0, (ends - 1) // tm - first_tile + 1, 0)
    visit_ends = jnp.cumsum(n_tiles)
    num_visits = visit_ends[-1]
    v = jnp.minimum(jnp.arange(m_pad // tm + e - 1, dtype=jnp.int32), jnp.maximum(num_visits - 1, 0))
    gid = jnp.minimum((v[:, None] >= visit_ends[None, :]).sum(axis=1), e - 1).astype(jnp.int32)
    tile = first_tile[gid] + v - (visit_ends[gid] - n_tiles[gid])
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    return offsets, gid, jnp.clip(tile, 0, m_pad // tm - 1).astype(jnp.int32), num_visits[None]


def _kernel(layer_ref, offs_ref, gid_ref, tile_ref, nvis_ref, x_ref, *refs, n_rhs: int, tk: int, act):
    del layer_ref  # read by the index maps only
    w_refs, s_refs, out_ref = refs[:n_rhs], refs[n_rhs:2 * n_rhs], refs[2 * n_rhs]
    v = pl.program_id(1)

    @pl.when(v < nvis_ref[0])
    def _visit():
        g = gid_ref[v]
        tm, tn = out_ref.shape
        d_in = x_ref.shape[1]
        ys = []
        for w_ref, s_ref in zip(w_refs, s_refs):
            acc = jnp.zeros((tm, tn), jnp.float32)
            for c in range(0, d_in, tk):
                # int8 -> bf16 in VMEM (exact: |q| <= 127); the MXU multiplies bf16.
                w = w_ref[c:c + tk, :].astype(jnp.float32).astype(jnp.bfloat16)
                acc += jnp.dot(x_ref[:, c:c + tk], w, preferred_element_type=jnp.float32)
            ys.append(acc * s_ref[pl.ds(g, 1), :])
        y = jax.nn.silu(ys[0]) * ys[1] if act == "silu_mul" else ys[0]
        row = tile_ref[v] * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, tn), 0)
        mine = (row >= offs_ref[g]) & (row < offs_ref[g + 1])
        out_ref[...] = jnp.where(mine, y.astype(out_ref.dtype), out_ref[...])


def _gmm(x, qws, scales, meta, layer, *, act, tm: int, tn: int | None, tk: int | None, interpret: bool):
    """The kernel call on rows already padded to a multiple of ``tm``."""
    n_rhs = len(qws)
    assert n_rhs == (2 if act == "silu_mul" else 1), (act, n_rhs)
    m_pad, d_in = x.shape
    qws = tuple(q if q.ndim == 4 else q[None] for q in qws)
    e, d_out = qws[0].shape[1], qws[0].shape[3]
    tn = tn or col_tile(d_in, d_out, n_rhs)
    tk = tk or k_chunk(d_in)
    layer = jnp.zeros(1, jnp.int32) if layer is None else jnp.asarray(layer, jnp.int32).reshape(1)

    def at(index):  # index maps see the grid position, then the five prefetched scalars
        return lambda n, v, layer, offs, gid, tile, nvis: index(n, v, layer, gid, tile)

    w_spec = pl.BlockSpec((None, None, d_in, tn), at(lambda n, v, layer, gid, tile: (layer[0], gid[v], 0, n)))
    s_spec = pl.BlockSpec((e, tn), at(lambda n, v, layer, gid, tile: (0, n)))
    return pl.pallas_call(
        functools.partial(_kernel, n_rhs=n_rhs, tk=tk, act=act),
        out_shape=jax.ShapeDtypeStruct((m_pad, d_out), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(d_out // tn, m_pad // tm + e - 1),
            in_specs=[
                pl.BlockSpec((tm, d_in), at(lambda n, v, layer, gid, tile: (tile[v], 0))),
                *([w_spec] * n_rhs),
                *([s_spec] * n_rhs),
            ],
            out_specs=pl.BlockSpec((tm, tn), at(lambda n, v, layer, gid, tile: (tile[v], n))),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT,
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * n_rhs * m_pad * d_in * d_out,
            bytes_accessed=n_rhs * min(e, m_pad) * d_in * d_out + m_pad * (d_in + d_out) * x.dtype.itemsize,
            transcendentals=m_pad * d_out if act else 0,
        ),
        interpret=interpret,
        name="moe_grouped_matmul_int8",
    )(layer, *meta, x, *qws, *(s.astype(jnp.float32) for s in scales))


def _rows_and_visits(x: jnp.ndarray, group_sizes: jnp.ndarray, tm: int):
    """``x`` zero-padded to whole row tiles, and the visit lists over them."""
    m_pad = _round_up(x.shape[0], tm)
    if m_pad != x.shape[0]:
        x = jnp.pad(x, ((0, m_pad - x.shape[0]), (0, 0)))
    return x, group_metadata(group_sizes, m_pad, tm)


@functools.partial(jax.jit, static_argnames=("act", "tm", "tn", "tk", "interpret"))
def grouped_matmul_int8(
    x: jnp.ndarray,  # [M, d_in] token copies sorted by group
    qws: tuple[jnp.ndarray, ...],  # each int8[E, d_in, d_out] or int8[L, E, d_in, d_out]
    scales: tuple[jnp.ndarray, ...],  # each [E, d_out]
    group_sizes: jnp.ndarray,  # i32[E], sums to M
    layer: jnp.ndarray | None = None,  # i32[] index into the leading axis of 4-d ``qws``
    *,
    act: str | None = None,  # "silu_mul": two arrays, silu(x W0) * (x W1)
    tm: int | None = None,
    tn: int | None = None,
    tk: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """``out[rows of g] = act(x[rows of g] @ (qw[g] * scale[g]))``; returns
    ``[M, d_out]`` in ``x``'s dtype."""
    m = x.shape[0]
    tm = tm or row_tile(m)
    x, meta = _rows_and_visits(x, group_sizes, tm)
    return _gmm(x, qws, scales, meta, layer, act=act, tm=tm, tn=tn, tk=tk, interpret=interpret)[:m]


@functools.partial(jax.jit, static_argnames=("interpret",))
def expert_ffn_int8(
    x: jnp.ndarray,  # [M, D] token copies sorted by expert
    gate: dict,  # int8 leaves: {"qw": int8[(L,) E, D, F], "scale": [E, F]}
    up: dict,
    down: dict,  # {"qw": int8[(L,) E, F, D], "scale": [E, D]}
    group_sizes: jnp.ndarray,  # i32[E], sums to M
    layer: jnp.ndarray | None = None,
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """``(silu(x W_gate) * (x W_up)) W_down`` per expert group: two kernel
    calls over one set of visit lists; returns ``[M, D]``."""
    m = x.shape[0]
    tm = row_tile(m)
    x, meta = _rows_and_visits(x, group_sizes, tm)
    kw = dict(tm=tm, tn=None, tk=None, interpret=interpret)
    with jax.named_scope("moe.experts_gate_up"):
        h = _gmm(x, (gate["qw"], up["qw"]), (gate["scale"], up["scale"]), meta, layer, act="silu_mul", **kw)
    with jax.named_scope("moe.experts_down"):
        return _gmm(h, (down["qw"],), (down["scale"],), meta, layer, act=None, **kw)[:m]
