#!/usr/bin/env python3
"""The slot kernels alone, on the chip: one call of
``ops/pallas_kda.kda_decode_step`` and of ``ops/pallas_mamba.mamba_decode_step``
at the shapes their cells serve, for each block of heads the VMEM budget can
choose and each operand form on offer, and of the conv rows' step
(``models/kda.slot_conv``) in each form on offer, the wrapper's operations
inside the timed function.

    python3 tools/state_kernel_bench.py [--kinds kda,mamba,conv] [--budgets-mib 2,4,8,16] [--parent DIR] [--extra FILE] [--iters 20]

Shapes: ``kda`` is Ling-3.0-flash's layer (64 rows, 32 heads of 128 x 128, 15
layers of 65 slots), ``mamba`` Falcon-H1-34B's mixer (64 rows, 32 heads of
256 x 128 in 2 groups, 9 layers of 65 slots) and, as ``mamba.narrow`` (``--kinds
mamba`` runs both), granite-4.0-h-small's (64 rows, 128 heads of 128 x 64 in
one group, 9 layers of 65 slots: heads narrower than the lanes, which the tree
serves two side by side in a buffer row, ``[slots, 64, 128, 128]``; the bench
lays the buffer out for a candidate outside the timed function, by the
candidate's ``layout``, and reads it back head by head to compare); the rows' slots are a seeded
permutation, not the row order, one row is a padding row (the null slot, no
decay, no write) and one is fresh. A candidate is called once a layer inside a
``lax.scan`` over the layers, as the layer scan calls it, on the layer's own
slots of one state buffer that the program donates; its operands are the
layer's slices of seeded arrays in the forms the model's projections leave
(``q k g [R, H, K]``, ``beta [R, H]``; ``x [R, H, P]``, ``B C [R, G, N]``, ``dt
[R, H]``), so whatever a wrapper lays out is inside the time.

Candidates: ``served@<MiB>`` is the tree's kernel with ``STATE_VMEM`` set to
each of ``--budgets-mib`` (the block that budget chooses is in the row;
``served`` marks the budget the tree ships); ``parent`` the kernels of another
tree (``--parent DIR``: its two kernel files are loaded beside this tree's);
``--extra FILE`` names a Python file whose ``CANDIDATES = {"kda": {name: fn},
"mamba": {name: fn}}`` take the kernels' arguments (a builder's scratch forms);
``copy@<block>``, ``read@<block>`` and ``write@<block>`` are no recurrence at
all, only the served block's traffic through the same ``BlockSpec`` pipeline
(the states copied where they lie, only read, only written): the rate the DMA
gives a stream of that kind, which no kernel on this pipeline can pass;
``xla`` is the plain step (``recurrent_step`` on gathered states, scattered
back), which is also what every candidate is checked against on the chip:
``out_err`` / ``state_err`` the largest difference over all layers, ``kept``
whether every slot no live row names, the padding row's among them, is still
bit for bit what it was.

``conv`` is the conv rows of both cells (``conv.kda``: Ling's ``[975, 3,
12288]`` bfloat16 buffer, no bias, 15 layers; ``conv.mamba``: Falcon-H1's
``[585, 3, 5120]`` with a bias, 9 layers): 64 one-token rows on the same
permuted slots, a fresh row and a padding row, the layer's ``x f32[R, W]`` as
the projections leave it, its filter and bias as the parameters hold them.
Candidates: ``flat`` is the form the buffer had before PR 48 (``[slots, taps -
1, W]``: ``conv[ids]``, ``causal_conv``, ``conv.at[ids].set``), which every
other candidate is checked against (``state_err`` 0: the carried inputs are
copies); ``xla@tiled`` the same gather and scatter on the buffer as it is
allocated now (``[slots, taps - 1, W / 128, 128]``); ``served`` the tree's
``slot_conv`` (the kernel ``slot_conv_step`` and what the wrapper lays out for
it); ``copy@row`` a bare copy of the same blocks (a row's slot copied where it
lies, its ``x`` copied to ``y``) through the same ``BlockSpec`` pipeline: the
ceiling. ``peak_pct`` counts the rows' conv state and ``x`` read and the state
and ``y`` written.

Per candidate: ``us_call`` by the host's clock over ``--iters`` runs of the
scan enqueued back to back (a run is 9 or 15 calls and takes the state the
run before gave) and, from a traced run, ``kernel_us`` (the device events
whose name starts with the kernel's, a call), ``other_us`` (every other
device operation inside the scan, a call: the wrapper's operations and the
scan's own slices) and ``peak_pct``: the state's bytes (one read and one write
of every row's float32 state: what the cells' roofline metrics count bar a
percent of small operands; a ``read@`` or ``write@`` row moves half) at the
HBM peak over ``kernel_us``. Written to
``chiprun_out/state_kernel_bench.json``. Without a TPU (or ``--rehearse``) it
runs toy shapes under the interpreter, prints no time and exits 3.
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import importlib.util
import json
import os
import pathlib
import shutil
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

# kind: rows, heads, groups (0: no groups), state rows (key / N), lanes (value / P), layers, slots a layer
SHAPES = {"kda": (64, 32, 0, 128, 128, 15, 65), "mamba": (64, 32, 2, 256, 128, 9, 65), "mamba.narrow": (64, 128, 1, 128, 64, 9, 65)}
TOY = {"kda": (4, 4, 0, 16, 128, 2, 6), "mamba": (4, 4, 2, 8, 128, 2, 6), "mamba.narrow": (4, 8, 1, 8, 64, 2, 6)}
KERNEL = {"kda": "kda_decode_step", "mamba": "mamba_decode_step", "mamba.narrow": "mamba_decode_step",
          "conv.kda": "slot_conv_step", "conv.mamba": "slot_conv_step"}


def layouts(side: int) -> dict:
    """name -> ``(lay, read back)`` of a Mamba-2 state buffer ``[slots, H, N, P]``: how a candidate's buffer lies."""
    from dynamo_tpu.models import mamba2

    return {"by_head": (lambda s: s, lambda s: s),
            "side_by_side": (lambda s: mamba2.lay_side_by_side(s, side), lambda s: mamba2.lay_by_head(s, side))}
# the conv rows: rows, channels, bias, taps, layers, slots a layer
SHAPES.update({"conv.kda": (64, 12288, False, 4, 15, 65), "conv.mamba": (64, 5120, True, 4, 9, 65)})
TOY.update({"conv.kda": (4, 3 * 128, False, 4, 2, 6), "conv.mamba": (4, 5 * 128, True, 4, 2, 6)})


@contextlib.contextmanager
def state_vmem(budget: int):
    """``ops/pallas_kda.STATE_VMEM`` set to ``budget`` for what is traced inside."""
    from dynamo_tpu.ops import pallas_kda

    was, pallas_kda.STATE_VMEM = pallas_kda.STATE_VMEM, budget
    try:
        yield
    finally:
        pallas_kda.STATE_VMEM = was


@contextlib.contextmanager
def interpreted(on: bool):
    """``DYNAMO_PALLAS_INTERPRET`` (``ops/pallas_paged.interpret_mode``) set for what is traced inside: the rehearsal's kernels."""
    was = os.environ.get("DYNAMO_PALLAS_INTERPRET")
    os.environ["DYNAMO_PALLAS_INTERPRET"] = "1" if on else ""
    try:
        yield
    finally:
        if was is None:
            del os.environ["DYNAMO_PALLAS_INTERPRET"]
        else:
            os.environ["DYNAMO_PALLAS_INTERPRET"] = was


def load_file(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def operands(kind: str, shape: tuple, seed: int):
    """``(state, ids, fresh, live, per-layer operands)``: row 1 fresh, row 2 a
    padding row on the null slot (not fresh, so that its slot must come back as it was)."""
    import jax.numpy as jnp
    import numpy as np

    rows, layers, slots = shape[0], shape[-2], shape[-1]
    rng = np.random.default_rng(seed)
    f = lambda x: jnp.asarray(x, jnp.float32)  # noqa: E731
    ids = rng.permutation(np.arange(1, slots))[:rows]
    ids[2] = 0
    live = np.ones(rows, bool)
    live[2] = False
    fresh = np.zeros(rows, bool)
    fresh[1] = True
    if kind.startswith("conv"):  # the flat buffer; x as the projections leave it, filter and bias as the parameters hold them
        _, width, bias, taps = shape[:4]
        bf = lambda x: jnp.asarray(x, jnp.bfloat16)  # noqa: E731
        ops = (f(rng.standard_normal((layers, rows, width))), bf(0.5 * rng.standard_normal((layers, taps, width))),
               bf(0.3 * rng.standard_normal((layers, width))) if bias else None, jnp.asarray(live, jnp.int32))
        return bf(rng.standard_normal((layers * slots, taps - 1, width))), jnp.asarray(ids, jnp.int32), jnp.asarray(fresh), live, ops
    _, heads, groups, n, p = shape[:5]
    state = f(rng.standard_normal((layers * slots, heads, n, p)))
    mask = live[None, :, None]
    if kind == "kda":
        unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
        q, k = (unit(rng.standard_normal((layers, rows, heads, n))) for _ in range(2))
        v = rng.standard_normal((layers, rows, heads, p))
        g = -5 * rng.uniform(size=(layers, rows, heads, n)) ** 3 * mask[..., None]
        beta = rng.uniform(size=(layers, rows, heads)) * mask
        ops = tuple(f(o) for o in (q, k, v, g, beta))
    else:
        x = rng.standard_normal((layers, rows, heads, p))
        b, c = (rng.standard_normal((layers, rows, groups, n)) for _ in range(2))
        dt = rng.uniform(0.0, 0.7, size=(layers, rows, heads)) ** 2 * mask
        ops = (f(x), f(b), f(c), f(dt), f(-rng.uniform(0.3, 3.0, size=heads)))
    return state, jnp.asarray(ids, jnp.int32), jnp.asarray(fresh), live, ops


def xla_step(kind: str):
    """The plain step through slots, with the kernels' signature."""
    import jax.numpy as jnp

    from dynamo_tpu.models import kda, mamba2

    def kda_step(state, ids, fresh, q, k, v, g, beta):
        o, s = kda.recurrent_step(jnp.where(fresh[:, None, None, None], 0.0, state[ids]), q, k, v, g, beta)
        return o, state.at[ids].set(s)

    def mamba_step(state, ids, fresh, x, b, c, dt, a):
        (r, h, p), (gr, n) = x.shape, b.shape[1:]
        s0 = jnp.where(fresh[:, None, None, None], 0.0, state[ids]).reshape(r, gr, h // gr, n, p)
        y, s = mamba2.recurrent_step(s0, x.reshape(r, gr, h // gr, p), b, c, dt.reshape(r, gr, h // gr), a.reshape(gr, h // gr))
        return y.reshape(r, h, p), state.at[ids].set(s.reshape(r, h, n, p))

    def conv_step(conv, ids, fresh, x, filt, bias, n_valid):  # the buffer flat, as it lay before PR 48
        prev = jnp.where(fresh[:, None, None], jnp.zeros((), conv.dtype), conv[ids])
        y, carried = kda.causal_conv(x[:, None], prev, filt, n_valid, bias)
        return y[:, 0], conv.at[ids].set(carried.astype(conv.dtype))

    return conv_step if kind.startswith("conv") else kda_step if kind == "kda" else mamba_step


def conv_candidates(interpret: bool) -> dict:
    """The conv rows' forms on the buffer as it is allocated (``tiled``: the
    bench lays the flat buffer out for them, outside the timed function)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from dynamo_tpu.models import kda

    def through(impl):
        def step(conv, ids, fresh, x, filt, bias, n_valid):
            y, conv = kda.slot_conv(conv, ids, fresh, x[:, None], filt, n_valid, bias, impl=impl)
            return y.reshape(x.shape), conv
        return step

    def served(*args):
        with interpreted(interpret):  # the model's wrapper reads the switch while the step is traced
            return through("pallas")(*args)

    def copy_rows(conv, ids, fresh, x, *_):
        """No conv: a row's slot copied where it lies and its ``x`` to ``y``, in ``slot_conv_step``'s blocks."""
        tile, rows = conv.shape[1:], x.shape[0]
        c_spec = pl.BlockSpec((None, *tile), lambda r, slots: (slots[r], 0, 0, 0))
        x_spec = pl.BlockSpec((None, *tile[1:]), lambda r, slots: (r, 0, 0))

        def kernel(slots_ref, x_ref, c_ref, c_out_ref, y_ref):
            c_out_ref[...] = c_ref[...]
            y_ref[...] = x_ref[...]

        conv, y = pl.pallas_call(
            kernel, out_shape=(jax.ShapeDtypeStruct(conv.shape, conv.dtype), jax.ShapeDtypeStruct((rows, *tile[1:]), jnp.float32)),
            grid_spec=pltpu.PrefetchScalarGridSpec(num_scalar_prefetch=1, grid=(rows,), in_specs=[x_spec, c_spec],
                                                   out_specs=[c_spec, x_spec]),
            input_output_aliases={2: 0}, compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
            interpret=interpret, name="stream_copy")(ids, x.reshape(rows, *tile[1:]), conv)
        return y.reshape(x.shape), conv

    return {"xla@tiled": (through("reference"), {"tiled": True}), "served": (served, {"tiled": True, "served": True}),
            "copy@row": (copy_rows, {"tiled": True, "stream": "copy"})}


def stream_step(mode: str, hb: int, interpret: bool):
    """No recurrence, only the kernels' traffic: the named slots' states in
    blocks of ``hb`` heads through the same ``BlockSpec`` pipeline, ``copy``
    (read, written back where they lay), ``read`` (read, a column sum out) or
    ``write`` (a constant written, nothing read): what the DMA alone takes."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(slots_ref, s_ref, *outs):
        for i in range(hb):
            if mode != "read":
                outs[0][i] = jnp.full(outs[0].shape[1:], 0.25, jnp.float32) if mode == "write" else s_ref[i]
            outs[-1][pl.ds(i, 1), :] = jnp.zeros((1, outs[-1].shape[-1]), jnp.float32) if mode == "write" else jnp.sum(
                s_ref[i], axis=0, keepdims=True)

    def step(state, ids, fresh, *_):
        (rows,), (_, heads, n, p) = ids.shape, state.shape
        s_spec = pl.BlockSpec((None, hb, n, p), lambda r, j, slots: (slots[r], j, 0, 0))
        y_spec, y_shape = pl.BlockSpec((None, hb, p), lambda r, j, slots: (r, j, 0)), jax.ShapeDtypeStruct((rows, heads, p), jnp.float32)
        writes = mode != "read"
        out = pl.pallas_call(
            kernel, out_shape=(jax.ShapeDtypeStruct(state.shape, jnp.float32), y_shape) if writes else y_shape,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(rows, heads // hb),
                in_specs=[pl.BlockSpec(memory_space=pl.ANY) if mode == "write" else s_spec],
                out_specs=[s_spec, y_spec] if writes else y_spec),
            input_output_aliases={1: 0} if writes else {},
            compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=64 << 20),
            interpret=interpret, name=f"stream_{mode}")(ids, state)
        return (out[1], out[0]) if writes else (out, state)

    return step


def scanned(kind: str, step, slots: int, ids, fresh):
    """``(state, operands) -> (state, outputs [L, R, H, P])``: ``step`` once a layer on the layer's slots."""
    import jax
    import jax.numpy as jnp

    def run(state, ops):
        per_layer, shared = (ops, ()) if kind == "kda" else (ops[:-1], ops[-1:])  # Mamba-2's A, the conv rows' valid tokens

        def layer(state, xs):
            l, *o = xs
            y, state = step(state, ids + l * slots, fresh, *o, *shared)
            return state, y

        return jax.lax.scan(layer, state, (jnp.arange(per_layer[0].shape[0], dtype=jnp.int32), *per_layer))

    return run


def device_us(trace_dir: str, kernel: str, calls: int) -> tuple[float, float]:
    """``(the kernel's events, every other device operation)`` of the trace, us a call."""
    from benchmark import trace_reduce as tr

    trace = tr.load_xplane(trace_dir)
    by_name = tr.exclusive_by_name(tr.line_events(tr.device_planes(trace)[0], tr.OPS_LINE))
    own = sum(s for name, s in by_name.items() if name.startswith(kernel))
    return own / calls * 1e6, (sum(by_name.values()) - own) / calls * 1e6


def bench(kind: str, candidates: dict, shape: tuple, *, seed: int, iters: int, timed: bool, peak: float) -> list[dict]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    rows, layers, slots = shape[0], shape[-2], shape[-1]
    if kind.startswith("conv"):  # the rows' conv state and x read, the state and y written
        moved = rows * shape[1] * (2 * (shape[3] - 1) * 2 + 2 * 4)
    else:  # one read and one write of every row's float32 state
        moved = 2 * 4 * rows * shape[1] * shape[3] * shape[4]
    state0, ids, fresh, live, ops = operands(kind, shape, seed)
    untouched = np.setdiff1d(np.arange(layers * slots), (np.asarray(ids)[live][None] + np.arange(layers)[:, None] * slots).ravel())
    want_state, want_out = jax.jit(scanned(kind, xla_step(kind), slots, ids, fresh))(jnp.array(state0), ops)
    apart = jax.jit(lambda a, b: jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max())  # fused: no third copy of a 2.4 GB state
    same_at = jax.jit(lambda a, b, at: jnp.array_equal(a[at], b[at]))
    table = []
    for name, (step, note) in candidates.items():
        row = {"kind": kind, "candidate": name, **note}
        try:
            run = jax.jit(scanned(kind, step, slots, ids, fresh), donate_argnums=(0,))
            # A conv candidate on the buffer as it is allocated: channels in rows of 128 lanes, laid out outside the timed function.
            tiled = (lambda z: z.reshape(*z.shape[:2], -1, 128)) if note.get("tiled") else (lambda z: z)
            back = lambda z: z.reshape(state0.shape)  # noqa: E731
            if note.get("layout"):  # a Mamba-2 state that lies otherwise than head by head
                tiled, back = (jax.jit(f) for f in layouts(max(1, 128 // shape[4]))[note["layout"]])
            state, out = run(tiled(jnp.array(state0)), ops)
            if "stream" not in note:
                row["out_err"] = float(apart(out, want_out))
                row["state_err"] = float(apart(back(state), want_state))
                row["kept"] = bool(same_at(back(state), state0, untouched))
            if timed:
                t0 = time.perf_counter()
                for _ in range(iters):  # enqueued back to back: each run takes the state the last one gave
                    state, out = run(state, ops)
                jax.block_until_ready(out)
                row["us_call"] = round((time.perf_counter() - t0) / iters / layers * 1e6, 1)
                trace_dir = ROOT / ".bench_work" / "state_kernel_trace"
                shutil.rmtree(trace_dir, ignore_errors=True)
                jax.profiler.start_trace(str(trace_dir))
                for _ in range(3):
                    state, out = run(state, ops)
                jax.block_until_ready(out)
                jax.profiler.stop_trace()
                own, other = device_us(str(trace_dir), "stream_" if "stream" in note else KERNEL[kind], 3 * layers)
                row["kernel_us"], row["other_us"] = round(own, 1), round(other, 1)
                if own:
                    half = note.get("stream") in ("read", "write")
                    row["peak_pct"] = round(100 * moved / (2 if half else 1) / peak / (own * 1e-6), 1)
                shutil.rmtree(trace_dir, ignore_errors=True)
            del state, out
        except Exception as e:  # a form the compiler refuses is a row of the table, not the end of the call
            row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
        print(json.dumps(row), flush=True)
        table.append(row)
    return table


def candidates_of(kind: str, shape: tuple, budgets: list[int], parent: str, extra, interpret: bool) -> dict:
    """name -> ``(step with the kernels' positional arguments, what the row says of it)``."""
    from dynamo_tpu.ops import pallas_kda, pallas_mamba

    if kind.startswith("conv"):
        return {"flat": (xla_step(kind), {}), **conv_candidates(interpret)}
    own = {"kda": pallas_kda.kda_decode_step}.get(kind, pallas_mamba.mamba_decode_step).__wrapped__
    _, heads, groups, n, p, _, _ = shape
    out = {"xla": (xla_step(kind), {})}
    lies = {}
    if p < 128:  # the tree's buffer rows: heads side by side on the lanes
        side = 128 // p
        heads, p, lies = heads // side, side * p, {"layout": "side_by_side"}

    def at_budget(budget: int):
        def step(*args):
            with state_vmem(budget):  # read while the step is traced
                return own(*args, interpret=interpret)
        return step

    for mib in budgets:
        budget = mib << 20 if not interpret else mib * 4 * 4 * n * p  # the toy: "MiB" counts heads
        with state_vmem(budget):
            block = pallas_kda.heads_block(heads, 4 * n * p, heads // groups if groups else 1)
        served = budget == pallas_kda.STATE_VMEM
        out[f"served@{mib}"] = (at_budget(budget), {"block": block, "served": served, **lies})
        if served or (interpret and mib == budgets[-1]):
            for mode in ("copy", "read", "write"):
                out[f"{mode}@{block}"] = (stream_step(mode, block, interpret), {"block": block, "stream": mode, **lies})
    if parent and kind in ("kda", "mamba"):
        mod = load_file(pathlib.Path(parent) / "dynamo_tpu" / "ops" / f"pallas_{kind}.py", f"parent_pallas_{kind}")
        fn = getattr(mod, KERNEL[kind]).__wrapped__
        out["parent"] = (functools.partial(fn, interpret=interpret), {"block": getattr(mod, "HEADS_PER_BLOCK", None)})
    if extra is not None:
        for name, fn in extra.CANDIDATES.get(kind, {}).items():
            out[name] = (functools.partial(fn, interpret=interpret), {"layout": fn.layout} if hasattr(fn, "layout") else {})
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--kinds", default="kda,mamba", help="kda, mamba (both its shapes; mamba.narrow: granite's alone), conv (the conv rows of both cells)")
    ap.add_argument("--budgets-mib", default="2,4,8,16", help="STATE_VMEM values to set, MiB")
    ap.add_argument("--parent", default="", help="another tree whose two kernel files are timed as they are")
    ap.add_argument("--extra", default="", help="a Python file with CANDIDATES = {kind: {name: fn}}")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--seed", type=int, default=4700000101)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    import jax

    on_chip = jax.default_backend() == "tpu" and not args.rehearse
    peak = 0.0
    if on_chip:
        peaks = json.loads((ROOT / "benchmark" / "peaks.json").read_text())
        peak = peaks[jax.devices()[0].device_kind]["hbm_bytes_per_s"]  # a device that is not in the table is an error
    extra = load_file(pathlib.Path(args.extra), "state_kernel_bench_extra") if args.extra else None
    budgets = [int(v) for v in args.budgets_mib.split(",")] if on_chip else [1, 2, 4]
    table = []
    several = {"conv": ["conv.kda", "conv.mamba"], "mamba": ["mamba", "mamba.narrow"]}  # a word that names more than one row set
    for kind in [k for word in args.kinds.split(",") for k in several.get(word, [word])]:
        shape = (SHAPES if on_chip else TOY)[kind]
        cands = candidates_of(kind, shape, budgets, args.parent, extra, interpret=not on_chip)
        table += bench(kind, cands, shape, seed=args.seed, iters=args.iters if on_chip else 1, timed=on_chip, peak=peak)
    sound = [r for r in table if "error" not in r]
    ok = all(r["kept"] and r["out_err"] < 1e-4 and r["state_err"] < 1e-4 for r in sound if "stream" not in r)
    verdict = {"state_kernel_bench": jax.devices()[0].device_kind if on_chip else "rehearsal: no time is a device time",
               "rows": len(table), "refused": len(table) - len(sound), "every_candidate_sound": ok}
    print(json.dumps(verdict))
    if on_chip:
        out = ROOT / "chiprun_out" / "state_kernel_bench.json"
        out.parent.mkdir(exist_ok=True)
        out.write_text(json.dumps({**verdict, "table": table}, indent=1))
    if not ok:
        return 1
    return 0 if on_chip else 3


if __name__ == "__main__":
    sys.exit(main())
