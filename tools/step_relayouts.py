#!/usr/bin/env python3
"""What a configuration's step programs re-lay per step: no chip needed.

    JAX_PLATFORMS=cpu python3 tools/step_relayouts.py <config name> [rows] [--dump DIR] [--count]

Compiles the decode step (``rows`` x 1) and the chunk step as it is served
(``rows`` decode slots + one chunk slot on the split token axis) of
``benchmark/configs/<config name>.json`` for a *described* v5e, as
``benchmark/compile_fit.py`` does (nothing runs), with the expert kernel on its
chip path, and lists every operation inside the programs' loops (the layer
scan's body and what it nests) that only moves data and whose result is 1 MiB
or more: a ``copy``, a ``transpose``, or a fusion that holds nothing but a
slice, a copy or a transposition. Each with its shape, the layouts it reads and
writes, its bytes, how often a step runs it and the ``op_name`` it came from;
then the bytes a step re-lays in all, and how many of them are ``s8``
(a quantized weight). For a model whose head is its embedding
(``tie_embeddings``) also ``embedding_copies``: every instruction, inside a
loop or not, that makes an array of the embedding's elements again (the head
contracts the embedding where it lies: the list is empty).

A projection whose output is split into heads should read its weight where it
lies (``models/quant.held_flat``). Where it does not, the compiler pushes
the layout the attention wants back through the reshape into the dot and pays
with a transposed copy of the layer's weight in every layer of every step
(Mellum2: 0.6 GB a step; LongCat-Flash: 0.5 GB; PERF.md, PR 35). Run this
before a chip does, on every new configuration; ``--dump`` keeps the compiled
text, in which an entry's name finds what feeds it and what it feeds.

``--count`` lists instead what the layer scan's body executes: its instructions
(a fusion is one; parameters, constants, tuples and bitcasts run nothing) by the
scope their ``op_name`` names (``attn``, ``moe.router``, ``moe.combine``,
``moe.shared``, ...; ``/while`` and ``/cond`` behind a scope mark what a loop or
a conditional nested in the body holds), the sorts, the scatters of a
``moe.`` scope, and how many ``copy``, ``transpose``, ``sort`` and ``gather``
operations it holds, fused or not (``moving``). A small operation costs a microsecond or more of a step however
little it computes, 39 times a step in JoyAI-LLM-Flash (PERF.md, PR 39).
"""

from __future__ import annotations

import argparse
import functools
import json
import math
import os
import pathlib
import re
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

MIN_BYTES = 1 << 20
#: Operations that compute nothing: a fusion made of these alone only moves data.
MOVES = frozenset({"parameter", "constant", "dynamic-slice", "slice", "bitcast", "copy", "transpose", "reshape",
                   "get-tuple-element", "tuple", "iota", "compare", "select", "add", "clamp", "broadcast"})
#: ... provided one of these is among them (index arithmetic alone is no move).
MOVERS = frozenset({"dynamic-slice", "slice", "copy", "transpose"})
ITEMSIZE = {"s8": 1, "u8": 1, "pred": 1, "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "f32": 4, "s32": 4, "u32": 4,
            "f64": 8, "s64": 8, "u64": 8}

#: Opcodes that run nothing on the device.
FREE = frozenset({"parameter", "constant", "get-tuple-element", "tuple", "bitcast"})
#: Operations that move data and compute nothing, counted by ``--count`` wherever they stand in the layer's body
#: (alone or inside a fusion; an asynchronous copy counts as a copy).
MOVING = ("copy", "transpose", "sort", "gather")
#: A layer body's scopes: an instruction is counted under the first of these that its ``op_name`` holds.
SCOPES = ("moe.combine", "moe.dispatch", "moe.shared", "moe.zero", "moe.experts", "moe.router", "attn", "mlp")

_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = (\(.*?\)|\S+) ([\w\-]+)\((.*)$")
_SHAPE = re.compile(r"^(\w+)\[([\d,]*)\](\{[^}]*\})?")


def _computations(text: str) -> dict[str, list[dict]]:
    """``{computation: [instruction, ...]}`` of a compiled module's text, an
    instruction as ``{name, shape, opcode, rest}`` (``rest``: operands and
    attributes, unparsed)."""
    out: dict[str, list[dict]] = {}
    current = None
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            current = out.setdefault(head.group(1), [])
        elif line.startswith("}"):
            current = None
        elif current is not None:
            m = _INSTRUCTION.match(line)
            if m:
                current.append(dict(zip(("name", "shape", "opcode", "rest"), m.groups())))
    return out


def _bytes(shape: str) -> tuple[str, int]:
    m = _SHAPE.match(shape)
    if not m or m.group(1) not in ITEMSIZE:
        return "", 0
    dims = [int(d) for d in m.group(2).split(",") if d]
    return m.group(1), math.prod(dims) * ITEMSIZE[m.group(1)]


def _layout(shape: str) -> str:
    m = _SHAPE.match(shape)
    return (m.group(3) or "{}").split(":")[0].rstrip("}") + "}" if m else ""


def relayouts(text: str, *, min_bytes: int = MIN_BYTES) -> list[dict]:
    """The data-moving operations of ``min_bytes`` or more inside the loops of
    a compiled program (``compiled.as_text()``): one entry each, ``times`` the
    product of the enclosing loops' trip counts."""
    comps = _computations(text)
    entry = next((name for name in comps if re.search(rf"^ENTRY %?{re.escape(name)} ", text, re.M)), None)
    found: list[dict] = []

    def moves_only(fused: str) -> bool:
        ops = {i["opcode"] for i in comps.get(fused, [])}
        return bool(ops & MOVERS) and ops <= MOVES

    def trip_count(cond: str) -> int:
        """A scan's: its condition compares the counter (from 0) with one
        constant. A loop whose count the text does not say counts once."""
        ins = comps.get(cond, [])
        bounds = [re.match(r"(\d+)\)", i["rest"]) for i in ins if i["opcode"] == "constant" and i["shape"].startswith("s32[]")]
        less_than = any(i["opcode"] == "compare" and "direction=LT" in i["rest"] for i in ins)
        return int(bounds[0].group(1)) if less_than and len(bounds) == 1 and bounds[0] else 1

    def walk(comp: str, times: int, in_loop: bool) -> None:
        shapes = {i["name"]: i["shape"] for i in comps.get(comp, [])}
        for ins in comps.get(comp, []):
            if ins["opcode"] == "while":
                cond, body = re.search(r"condition=%?([\w.\-]+), body=%?([\w.\-]+)", ins["rest"]).groups()
                walk(body, times * trip_count(cond), True)
                continue
            if not in_loop:
                continue
            fused = re.search(r"calls=%?([\w.\-]+)", ins["rest"]) if ins["opcode"] == "fusion" else None
            if not (ins["opcode"] in ("copy", "transpose") or (fused and moves_only(fused.group(1)))):
                continue
            dtype, nbytes = _bytes(ins["shape"])
            if nbytes < min_bytes:
                continue
            operands = re.findall(r"%([\w.\-]+)", ins["rest"].split("), ")[0])
            big = [shapes[o] for o in operands if o in shapes and _bytes(shapes[o])[1] >= min_bytes]
            found.append({
                "name": ins["name"], "opcode": ins["opcode"], "dtype": dtype, "shape": ins["shape"].split("{")[0],
                "reads": [f"{s.split('{')[0]}{_layout(s)}" for s in big], "writes": _layout(ins["shape"]),
                "bytes": nbytes, "times": times, "op_name": op_name_of(ins),
            })

    walk(entry, 1, False)
    return found


def op_name_of(ins: dict) -> str:
    """An instruction's ``op_name`` metadata; "" where the compiler kept none."""
    found = re.search(r'op_name="([^"]*)"', ins["rest"])
    return found.group(1) if found else ""


def op_names(text: str) -> dict[str, str]:
    """``{instruction: op_name}`` over every computation of a compiled text."""
    return {ins["name"]: op_name_of(ins) for instructions in _computations(text).values() for ins in instructions}


def scope_of(op_name: str) -> str:
    return next((scope for scope in SCOPES if scope in op_name), "other")


def _called(ins: dict) -> list[tuple[str, str]]:
    """``(kind, computation)`` of what a ``while`` or a ``conditional`` runs."""
    if ins["opcode"] == "while":
        return [("while", re.search(r"body=%?([\w.\-]+)", ins["rest"]).group(1))]
    if ins["opcode"] == "conditional":
        names = re.search(r"branch_computations=\{([^}]*)\}", ins["rest"])
        names = names.group(1).split(",") if names else re.findall(r"(?:true|false)_computation=%?([\w.\-]+)", ins["rest"])
        return [("cond", name.strip().lstrip("%")) for name in names]
    return []


def layer_body(comps: dict[str, list[dict]], text: str) -> str | None:
    """The layer scan's body: of the loops the entry computation runs, the
    one whose body holds the most instructions."""
    entry = next((name for name in comps if re.search(rf"^ENTRY %?{re.escape(name)} ", text, re.M)), None)
    bodies = [body for ins in comps.get(entry, []) for kind, body in _called(ins) if kind == "while"]
    return max(bodies, key=lambda body: len(comps.get(body, [])), default=None)


def body_counts(text: str) -> dict:
    """What the layer scan's body of a compiled program executes: ``body``,
    the instructions of the body itself; ``nested``, those of the loops and
    conditionals inside it, by kind (a loop inside a conditional's arm counts
    as the conditional's); ``arms``, the instructions of each arm of the
    body's conditionals, of which a run of the body takes one (``executed``:
    ``body``, one trip of each nested loop and the longest arm of each
    conditional); ``by_scope``, all of
    them by :func:`scope_of` (``<scope>/while``, ``<scope>/cond`` where
    nested); ``sorts`` and ``moe_scatters``, the names of every ``sort`` and
    of every ``scatter`` under a ``moe.`` scope, fused or not; ``moving``,
    how many of each of :data:`MOVING` the body and what it nests hold,
    fused or not."""
    comps = _computations(text)
    out = {"body": 0, "executed": 0, "nested": {"while": 0, "cond": 0}, "arms": [], "by_scope": {}, "sorts": [],
           "moe_scatters": [], "moving": dict.fromkeys(MOVING, 0)}

    def inside(comp: str, op_name: str) -> None:
        """Sorts, scatters and the tally of :data:`MOVING` of a computation, those of its fusions too."""
        for ins in comps.get(comp, []):
            name = op_name_of(ins) or op_name
            moved = "copy" if ins["opcode"] == "copy-start" else ins["opcode"]
            if moved in out["moving"]:
                out["moving"][moved] += 1
            if ins["opcode"] == "sort":
                out["sorts"].append(ins["name"])
            elif ins["opcode"] == "scatter" and "moe." in name:
                out["moe_scatters"].append(ins["name"])
            elif ins["opcode"] == "fusion":
                inside(re.search(r"calls=%?([\w.\-]+)", ins["rest"]).group(1), name)

    def walk(comp: str, nest: str) -> int:
        """Counts ``comp``'s instructions and what they nest; returns how many."""
        n = 0
        for ins in comps.get(comp, []):
            if ins["opcode"] in FREE:
                continue
            n += 1
            if nest:
                out["nested"][nest] += 1
            else:
                out["body"] += 1
            scope = scope_of(op_name_of(ins)) + (f"/{nest}" if nest else "")
            out["by_scope"][scope] = out["by_scope"].get(scope, 0) + 1
            inner = [walk(called, nest or kind) for kind, called in _called(ins)]
            n += sum(inner) if ins["opcode"] == "while" else 0
            if ins["opcode"] == "conditional" and not nest:
                out["arms"].append(inner)
        inside(comp, "")
        return n

    body = layer_body(comps, text)
    if body is not None:
        walk(body, "")
    out["executed"] = out["body"] + out["nested"]["while"] + sum(max(arms) for arms in out["arms"])
    out["by_scope"] = dict(sorted(out["by_scope"].items()))
    return out


def embedding_copies(text: str, vocab: int, hidden: int) -> list[dict]:
    """Every instruction of a compiled program, inside a loop or not, that
    makes an array of the embedding's elements again: of shape ``[vocab,
    hidden]`` or ``[hidden, vocab]``, whatever its dtype, and not the
    parameter itself, a bitcast of it (a fusion that holds nothing but
    bitcasts is one) or its way through a tuple. A tied head
    (``tie_embeddings``) contracts the embedding where the gather reads it;
    an entry here is a transposed or re-laid copy of it (822 MB a step at
    100,352 x 4,096 bfloat16)."""
    comps = _computations(text)
    found = []
    for comp, instructions in comps.items():
        for ins in instructions:
            shape = _SHAPE.match(ins["shape"])
            if not shape or shape.group(2) not in (f"{vocab},{hidden}", f"{hidden},{vocab}"):
                continue
            if ins["opcode"] in ("parameter", "bitcast", "get-tuple-element"):
                continue
            if ins["opcode"] == "fusion":
                called = re.search(r"calls=%?([\w.\-]+)", ins["rest"]).group(1)
                if all(i["opcode"] in ("parameter", "bitcast") for i in comps.get(called, [])):
                    continue
            found.append({"name": ins["name"], "opcode": ins["opcode"], "shape": ins["shape"], "in": comp})
    return found


def summary(found: list[dict]) -> dict:
    total = sum(f["bytes"] * f["times"] for f in found)
    s8 = sum(f["bytes"] * f["times"] for f in found if f["dtype"] == "s8")
    return {"relaid_bytes_per_step": total, "s8_relaid_bytes_per_step": s8, "ops": found}


def step_texts(mc, *, rows: int, chunk: int, pages_per_row: int, pool_pages: int, page_size: int,
               quant: str = "int8") -> dict[str, str]:
    """Compiled text of ``mc``'s decode step and served chunk step for one
    described v5e chip (the topology is described here: call from one process,
    after nothing else has loaded the TPU library)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark import weights
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.mla import lay_heads_major
    from dynamo_tpu.parallel import moe

    jax.config.update("jax_enable_compilation_cache", False)
    chip = SingleDeviceSharding(topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
    moe._kernel_platform = lambda: True  # the described chip, not this CPU

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=chip)

    def like(tree):
        return jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)

    # (the tree as an unsharded runner lays it: engine/runner.py)
    params = like(jax.eval_shape(lambda: lay_heads_major(weights.make_weights(mc, 0, quant=quant))))
    # A model with a page pool per layer kind: the window pool the serving path derives (launch.py).
    window_pages = llama.window_pool_pages(mc, pool_pages, page_size, rows, chunk) if mc.mixed_attention else None
    kc, vc = like(jax.eval_shape(lambda: llama.init_kv_cache(mc, pool_pages, page_size, window_pages=window_pages)))
    i32 = lambda *s: sds(s, jnp.int32)  # noqa: E731
    counted = {"moe_counts": True} if mc.moe_held_share else {}  # as engine/runner.py asks

    def compiled(split, toks, slots) -> str:
        fn = functools.partial(llama.forward, cfg=mc, attn_impl="pallas", split=split, **counted)
        kept = {}
        if mc.recurrent_layers:  # a model with recurrent layers: its state buffers (rows + the null slot) and slot ids
            from dynamo_tpu.models import kda

            kept["recurrent"] = (*like(jax.eval_shape(lambda: kda.init_state(mc, rows + 1))), i32(slots))
        if window_pages is not None:
            kept.update(window_tables=i32(slots, pages_per_row), window_slots=i32(*toks), window_pages=window_pages)
        return jax.jit(fn, donate_argnames=("k_cache", "v_cache", "recurrent"), static_argnames=("window_pages",)).lower(
            params=params, tokens=i32(*toks), positions=i32(*toks), k_cache=kc, v_cache=vc,
            block_tables=i32(slots, pages_per_row), slot_mapping=i32(*toks), last_token_index=i32(slots), **kept,
        ).compile().as_text()

    texts = {"decode": compiled(None, (rows, 1), rows)}
    if mc.mrope_section:  # no split token axis: the rectangle is what is served
        texts["mixed"] = compiled(None, (rows, chunk), rows)
    else:
        texts["mixed"] = compiled((rows, 1, chunk), (rows + chunk,), rows + 1)
    return texts


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config")
    ap.add_argument("rows", nargs="?", type=int, default=64)
    ap.add_argument("--dump", default="", help="write each program's compiled text into this directory")
    ap.add_argument("--count", action="store_true", help="list what the layer scan's body executes, by scope")
    args = ap.parse_args()

    from benchmark import serving

    conf = serving.load_config(ROOT / "benchmark" / "configs" / f"{args.config}.json")
    mc = serving.model_config(conf)
    eng = conf["serve"]["engine"]
    pages_per_row = 1 << (-(-eng["max_seq_len"] // eng["page_size"]) - 1).bit_length()
    texts = step_texts(mc, rows=args.rows, chunk=eng["chunk_prefill_tokens"], pages_per_row=pages_per_row,
                       pool_pages=eng["pool_tokens"] // eng["page_size"] + 1, page_size=eng["page_size"],
                       quant=conf["serve"]["quant"])
    out = {"config": args.config, "rows": args.rows, "layers": mc.num_layers, "steps": {}}
    for label, text in texts.items():
        if args.dump:
            pathlib.Path(args.dump).mkdir(parents=True, exist_ok=True)
            (pathlib.Path(args.dump) / f"{args.config}.{label}.hlo.txt").write_text(text)
        if args.count:
            out["steps"][label] = body_counts(text)
            continue
        out["steps"][label] = {"fusions": len(re.findall(r"^\s+(?:ROOT )?%?[\w.\-]+ = .* fusion\(", text, re.M)),
                               **summary(relayouts(text))}
        if mc.tie_embeddings:  # the head is the embedding: no step program may make a second copy of it
            out["steps"][label]["embedding_copies"] = embedding_copies(text, mc.vocab_size, mc.hidden_size)
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
