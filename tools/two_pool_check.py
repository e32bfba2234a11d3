#!/usr/bin/env python3
"""The outputs check of a configuration with a page pool per layer kind, at a
length at which the window pool gives pages back.

    python3 tools/two_pool_check.py [--workload <cell>] [--seed N] [--prompt-tokens 4096] [--decode 64]

``benchmark/correct.py`` checks sequences of 196 tokens: they cross a window of
128 and never release a page. This script uses the benchmark's own
``run.bring_up`` and ``correct`` in its own process: seven requests decode
through the served path, and beside them a prompt of ``--prompt-tokens``
(a multiple of the configuration's chunk) is prefilled chunk by chunk and
``--decode`` tokens are decoded with their logprobs. The float32 reference
scores the same tokens whole (its attention a group of query heads at a time),
under the configuration's own ``logprob_rel_limit``. Printed beside the verdict:
how many of the checked sequence's window pages had gone back to their pool
when its first compared token was sampled (at least ``--min-released``, or the
run fails), and the engine's counters of both pools.

The KV pool is cut to ``--pool-tokens`` in this process only, to leave room for
the reference. Run by hand on the chip (about 5 min); ``JAX_PLATFORMS=cpu``
rehearses at the configuration's toy size (prompt of 8 toy chunks, exit 3).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "benchmark"))

import run as bench_run  # noqa: E402

from tools.mellum2_long_check import _free  # noqa: E402

FILLERS = 7


async def check(args, bench, cell, rehearsal: bool) -> dict:
    import numpy as np

    from benchmark import correct, serving

    state = await bench_run.bring_up(args, bench, cell, rehearsal, warm=False)
    conf, core, service = state["conf"], state["core"], state["service"]
    eng = conf["serve"]["engine"]
    chunk = eng["chunk_prefill_tokens"]
    n_prompt = 8 * chunk if rehearsal else -(-args.prompt_tokens // chunk) * chunk
    n_out = 16 if rehearsal else args.decode
    rng = np.random.default_rng(np.random.SeedSequence([int(args.seed), 42]))
    vocab = conf["hf"]["vocab_size"]
    prompt = rng.integers(1, vocab, size=n_prompt).tolist()
    released_at_first = {}
    try:
        if core.window_allocator is None:
            raise SystemExit(f"{conf['name']} keeps one page pool: nothing to check")
        started = [asyncio.Event() for _ in range(FILLERS)]
        beside = [asyncio.ensure_future(correct._served(
            service, rng.integers(1, vocab, size=64).tolist(), n_prompt // chunk + n_out + 64, logprobs=False, started=ev))
            for ev in started]
        await asyncio.gather(*(ev.wait() for ev in started))

        async def watch():
            """The checked sequence's own window pages, read when its first token is out."""
            seq = None
            while "pages" not in released_at_first:
                live = [s for s in core.prefilling + core.running if s.num_prompt == n_prompt]
                seq = live[0] if live else seq
                if seq is not None and seq.num_generated >= 1:
                    released_at_first.update(pages=seq.window_pages.count(0), blocks=len(seq.window_pages),
                                             full_pages_given_back=seq.pages.count(0))
                await asyncio.sleep(0.002)

        watcher = asyncio.ensure_future(watch())
        served = await correct._served(service, prompt, n_out, logprobs=True)
        await asyncio.wait_for(watcher, timeout=30)
        await asyncio.gather(*beside)
        steps = core.flight.snapshot(kind="step")
        pools = {"window_pool_pages": core.window_allocator.num_pages, "full_pool_pages": core.allocator.num_pages,
                 "window_pages_released_total": core.window_pages_released,
                 "window_pages_live_max": max(s["window_pages_live"] for s in steps),
                 "full_pages_live_max": max(s["full_pages_live"] for s in steps),
                 "preemptions": core.num_preemptions,
                 "decode_rows_max": max(s["decode_rows"] for s in steps),
                 "mixed_steps": sum(1 for s in steps if s["step_kind"] == "mixed"),
                 "attn_paths": sorted({s["attn_path"] for s in steps if s["attn_path"]})}
    finally:
        await serving.stop(state["handles"])
    runner, params = core.runner, state["params"]
    _free(runner.k_cache, runner.v_cache)  # room for the reference's whole-sequence pass
    sample = {"served": [served], "sequences": [prompt + [e["id"] for e in served][:-1]],
              "spans": [(n_prompt - 1, len(served))]}
    verdict = correct.score(conf, params, sample)
    enough = released_at_first.get("pages", 0) >= (n_prompt // eng["page_size"] - 2 if rehearsal else args.min_released)
    return {"two_pool_check": {"seed": args.seed, "prompt_tokens": n_prompt, "decoded": len(served), "chunk": chunk,
                               "window_pages_released_before_first_compared_token": released_at_first, **pools,
                               **verdict, "released_enough": enough, "passed": bool(verdict["ok"] and enough)}}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="k-exaone-236b-a23b-ep8-int8.longctx-reason")
    ap.add_argument("--seed", type=int, default=2600000123)
    ap.add_argument("--prompt-tokens", type=int, default=4096)
    ap.add_argument("--decode", type=int, default=64)
    ap.add_argument("--min-released", type=int, default=30)
    ap.add_argument("--pool-tokens", type=int, default=65536)
    args = ap.parse_args()
    os.environ.setdefault("DYN_FLIGHT_BUFFER", "65536")
    from benchmark import serving

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    rehearsal = os.environ.get("JAX_PLATFORMS", "") == "cpu"
    load = serving.load_config

    def load_with_small_pool(path, *, rehearsal=False):
        conf = load(path, rehearsal=rehearsal)
        if not rehearsal:
            conf["serve"]["engine"]["pool_tokens"] = args.pool_tokens
        return conf

    serving.load_config = load_with_small_pool  # this process only: no file of the benchmark is edited
    out = asyncio.run(check(args, bench, cell, rehearsal))
    print(json.dumps(out), flush=True)
    if not out["two_pool_check"]["passed"]:
        return 1
    return 3 if rehearsal else 0


if __name__ == "__main__":
    sys.exit(main())
