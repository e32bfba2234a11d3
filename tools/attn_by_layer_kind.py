#!/usr/bin/env python3
"""Split the attention kernels' device time of a kept trace by layer kind.

    python3 tools/attn_by_layer_kind.py <reduced trace .json> [--config benchmark/configs/<name>.json]

A model that mixes window and full attention layers runs both through ONE scan
body, so the device trace (and an xprof capture) shows one kernel name for
both kinds. Inside one step program the k-th event of that kernel belongs to
layer k, so the configuration's ``layer_types`` tells the kinds apart. Reads
the reduced trace ``benchmark/run.py --trace 1 --keep-trace <file>`` writes;
prints, per kernel, the step programs that held exactly one event per layer,
the median device time of one call by kind, and each kind's share of the
trace's busy time.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import statistics
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

KERNELS = ("paged_decode_attention", "paged_prefill_attention")


def by_kind(trace: dict, layer_types: list[str]) -> dict:
    from benchmark import attn_kernels, trace_reduce

    busy = trace_reduce.busy_seconds(trace)
    out = {"busy_s": busy}
    for kernel in KERNELS:
        mods, events_inside = attn_kernels.kernel_events_by_program(trace, kernel)
        ops = [e for m in mods for e in events_inside(m)]
        per_kind: dict[str, list[float]] = {}
        programs = 0
        for mod in mods:
            inside = events_inside(mod)
            if len(inside) != len(layer_types):
                continue  # a step of the other kind, or a program cut by the trace's edge
            programs += 1
            for kind, ev in zip(layer_types, inside):
                per_kind.setdefault(kind, []).append(ev[2] / 1e3)
        out[kernel] = {"step_programs": len(mods), "programs": programs, "events": len(ops), "total_s": sum(o[2] for o in ops) / 1e9,
                       "share_of_busy_pct": 100 * sum(o[2] for o in ops) / 1e9 / busy if busy else None,
                       "by_kind": {k: {"calls": len(v), "p50_us": statistics.median(v),
                                       "p10_us": statistics.quantiles(v, n=10)[0], "p90_us": statistics.quantiles(v, n=10)[-1],
                                       "share_of_busy_pct": 100 * sum(v) / 1e6 / busy}
                                   for k, v in per_kind.items() if len(v) >= 2}}
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("trace")
    ap.add_argument("--config", default=str(ROOT / "benchmark" / "configs" / "mellum2-12b-a2.5b-int8.json"))
    args = ap.parse_args()
    layer_types = json.loads(pathlib.Path(args.config).read_text())["layer_types"]
    print(json.dumps(by_kind(json.loads(pathlib.Path(args.trace).read_text()), layer_types), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
