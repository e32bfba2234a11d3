#!/usr/bin/env python3
"""Every device operation of a cell's commonest step program, by scope: on the chip.

    python3 tools/step_ops_table.py --workload <cell> --seed N [--seconds 51] [--program _step_packed]
    python3 tools/step_ops_table.py --scopes chiprun_out/step_ops-<cell>.json [--hlo <compiled text>]

The benchmark's ``breakdown.device_ops`` keeps the ten operations with most
time; a layer body of a hundred small operations hides below its tenth entry
(PERF.md, PR 39). This runs the cell as ``benchmark/run.py --trace 1`` does
(its ``main``, one process) and, before the trace's files are thrown away,
reads them once more for the step program the traced seconds hold most often
(a saturated cell's full decode step; ``--program`` names another by a part
of its module's name: ``_step_packed`` is the decode step where a window holds
more mixed steps, ``_step_split`` the chunk step): every operation of the device's
``XLA Ops`` line inside each of its runs, its own time (what no operation
nested in it covers: a ``while``'s own time is its conditions and the gaps
between its children) and its whole time, summed by name and divided by the
program's runs, with the operation's compiled line and the profiler's own
fields. Written to ``chiprun_out/step_ops-<cell>.json``. The profiler keeps
no ``op_name`` on a v5e, so once the server has stopped the program's own
compiled text is taken again (the runner's jitted step function lowered at the
shapes it was called with: a hit in the compile cache), kept beside the table
as ``.hlo.txt``, and joined to it by instruction name: the sums by scope
(``tools/step_relayouts.scope_of``; ``/while`` or ``/cond`` behind a scope for
what a loop or a conditional nested in the layer scan runs) are printed, and
``--scopes`` prints them again from a kept table and text without a chip. For
a model that holds a share of its experts the window's ``moe_extra_passes``
(STEP records) is printed too: no benchmark metric reads it.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "benchmark"))
sys.path.insert(0, str(ROOT / "tools"))


def ops_table(trace_dir: str, program: str = "") -> dict:
    """The per-operation table of the commonest step program in the trace
    under ``trace_dir`` (the profiler's own files), of those whose module's
    name holds ``program``."""
    import jax

    from benchmark import trace_reduce as tr

    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    data = jax.profiler.ProfileData.from_file(files[-1])
    plane = next(p for p in data.planes if p.name.startswith(tr.DEVICE_PREFIX))
    lines = {line.name: line for line in plane.lines}
    mods = sorted(((e.name, float(e.start_ns), float(e.duration_ns)) for e in lines[tr.MODULES_LINE].events
                   if tr.STEP_MARK in e.name), key=lambda m: m[1])
    by_name: dict[str, list] = {}
    for m in mods:
        by_name.setdefault(m[0], []).append(m)
    named = {name: ms for name, ms in by_name.items() if program in name}
    if not named:
        raise ValueError(f"no step program named *{program}* in the trace: {sorted(by_name)}")
    module, runs = max(named.items(), key=lambda kv: len(kv[1]))
    events = sorted(((float(e.start_ns), float(e.duration_ns), e) for e in lines[tr.OPS_LINE].events if e.duration_ns > 0),
                    key=lambda t: (t[0], -t[1]))
    rows: dict[str, dict] = {}
    i = 0
    for _, start, dur in runs:
        while i < len(events) and events[i][0] < start:
            i += 1
        stack: list[list] = []  # [row, end, own_ns]

        def close(upto: float) -> None:
            while stack and stack[-1][1] <= upto:
                row, _end, own = stack.pop()
                row["own_ns"] += own

        while i < len(events) and events[i][0] < start + dur:
            s, d, e = events[i]
            i += 1
            close(s)
            name = tr.op_name(e.name)
            row = rows.get(name)
            if row is None:
                stats = {str(k): (v if isinstance(v, (int, float)) else str(v)[:400]) for k, v in e.stats}
                row = rows[name] = {"name": name, "line": e.name[:400], "stats": stats, "first_ns": s - start,
                                    "parent": stack[-1][0]["name"] if stack else "", "depth": len(stack),
                                    "calls": 0, "own_ns": 0.0, "whole_ns": 0.0}
            row["calls"] += 1
            row["whole_ns"] += d
            if stack:
                stack[-1][2] -= min(s + d, stack[-1][1]) - s
            stack.append([row, s + d, d])
        close(float("inf"))
    n = len(runs)
    ops = [{**{k: r[k] for k in ("name", "line", "stats", "parent", "depth")}, "calls_per_run": r["calls"] / n,
            "own_us_per_run": r["own_ns"] / n / 1e3, "whole_us_per_run": r["whole_ns"] / n / 1e3}
           for r in sorted(rows.values(), key=lambda r: r["first_ns"])]
    return {"module": module, "runs": n, "run_us": sum(m[2] for m in runs) / n / 1e3,
            "step_programs_in_trace": len(mods), "ops": ops}


def scopes(table: dict, hlo: str = "") -> dict:
    """Microseconds a run of the program by scope, the operations at depth 0
    (outside every loop) apart; ``per_layer_us`` divides what the layer scan
    holds by its trip count (the calls of its commonest operation)."""
    from step_relayouts import op_names, scope_of

    names = op_names(hlo)
    kind_of = {op["name"]: "while" if op["name"].startswith("while") else "cond" for op in table["ops"]
               if op["name"].startswith(("while", "cond"))}
    depth_of = {op["name"]: op["depth"] for op in table["ops"]}
    by_scope: dict[str, dict] = {}
    layers = 1
    for op in table["ops"]:
        if op["depth"] == 0:
            scope = "outside the layer scan"
        elif op["name"] in kind_of:
            scope = f"a nested {kind_of[op['name']]}'s own time"
        else:
            nested = kind_of.get(op["parent"]) if depth_of.get(op["parent"], 0) >= 1 else None
            scope = scope_of(names.get(op["name"], "")) + (f"/{nested}" if nested else "")
            layers = max(layers, round(op["calls_per_run"])) if op["depth"] == 1 else layers
        row = by_scope.setdefault(scope, {"ops": 0, "calls": 0.0, "us": 0.0})
        row["ops"] += 1
        row["calls"] += op["calls_per_run"]
        row["us"] += op["own_us_per_run"]
    for scope, row in by_scope.items():
        row["per_layer_us"] = None if scope == "outside the layer scan" else row["us"] / layers
    return {"module": table["module"], "runs": table["runs"], "run_us": table["run_us"], "layers": layers,
            "by_scope": dict(sorted(by_scope.items(), key=lambda kv: -kv[1]["us"]))}


def served_text(seen: dict, table: dict) -> str:
    """The compiled text of the table's program: of the step functions the
    runner called (``seen``: function name and static arguments -> function,
    the first call's shapes, static arguments, calls), those the module is
    named after are lowered again, the most called first, until one holds
    nine in ten of the table's instruction names; else the one with most."""
    from step_relayouts import op_names

    wanted = [op["name"] for op in table["ops"]]
    best, best_score = "", -1
    for (fn_name, _), (fn, args, kwargs, _calls) in sorted(seen.items(), key=lambda kv: -kv[1][3]):
        if not fn_name or fn_name not in table["module"]:
            continue
        text = fn.lower(*args, **kwargs).compile().as_text()
        held = op_names(text)
        score = sum(name in held for name in wanted)
        if score > best_score:
            best, best_score = text, score
        if score >= 0.9 * len(wanted):
            break
    return best


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=3900000101)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--program", default="", help="a part of the step program's module name; default: the commonest")
    ap.add_argument("--scopes", default="", help="print the sums by scope of a kept table; no chip")
    ap.add_argument("--hlo", default="", help="with --scopes: a compiled program's text to take op_name from")
    args = ap.parse_args()
    if args.scopes:
        hlo = pathlib.Path(args.hlo).read_text() if args.hlo else ""
        print(json.dumps(scopes(json.loads(pathlib.Path(args.scopes).read_text()), hlo), indent=1))
        return 0

    import jax

    import run as bench_run
    from benchmark import trace_reduce
    from dynamo_tpu.engine.runner import ModelRunner

    out = ROOT / "chiprun_out" / f"step_ops-{args.workload}.json"
    load, enqueue = trace_reduce.load_xplane, ModelRunner._enqueue
    kept: dict = {}
    seen: dict = {}

    def load_and_keep(trace_dir: str) -> dict:
        try:
            kept["table"] = ops_table(trace_dir, args.program)
            out.parent.mkdir(exist_ok=True)
            out.write_text(json.dumps(kept["table"]))
        except Exception as e:  # the benchmark's own line is worth more than the table
            print(json.dumps({"step_ops_error": repr(e)}), flush=True)
        return load(trace_dir)

    def enqueue_and_remember(runner, fn, *a, **kw):
        # (``state``: a recurrent model's buffers ride as a keyword, arrays like the positional ones)
        key = (getattr(fn, "__name__", ""), tuple(sorted((k, v) for k, v in kw.items() if k != "state")))
        if key not in seen:  # the shapes of a program's first call: what lowers it again
            shapes = lambda tree: jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding), tree)  # noqa: E731
            seen[key] = [fn, shapes(a), {k: shapes(v) if k == "state" else v for k, v in kw.items()}, 0]
        seen[key][3] += 1
        return enqueue(runner, fn, *a, **kw)

    offer = bench_run.offer

    async def offer_and_count(*a, **kw):
        ctx = await offer(*a, **kw)
        steps = [s for s in ctx["window"]["steps"] if "moe_extra_passes" in s]
        if steps:  # a model that holds a share of its experts: did any layer need every copy's rows?
            print(json.dumps({"moe_extra_passes": sum(s["moe_extra_passes"] for s in steps), "steps": len(steps),
                              "moe_choices_held": sum(s["moe_choices_held"] for s in steps)}), flush=True)
        return ctx

    bench_run.offer = offer_and_count
    trace_reduce.load_xplane = load_and_keep
    ModelRunner._enqueue = enqueue_and_remember
    sys.argv = [str(ROOT / "benchmark" / "run.py"), "--workload", args.workload, "--seed", str(args.seed),
                "--seconds", str(args.seconds), "--trace", "1"]
    code = bench_run.main()
    if code == bench_run.EXIT_REHEARSAL:  # no trace on the CPU: the join alone, on a table that names nothing
        kept["table"] = {"module": "jit__step_packed", "runs": 0, "run_us": 0.0, "ops": []}
    if "table" in kept:
        try:
            text = served_text(seen, kept["table"])
            out.with_suffix(".hlo.txt").write_text(text)
            print(json.dumps({"step_ops_scopes": scopes(kept["table"], text)}), flush=True)
        except Exception as e:
            print(json.dumps({"step_ops_error": repr(e)}), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
