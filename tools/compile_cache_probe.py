#!/usr/bin/env python3
"""Does a configuration's lattice of step programs stay in the compile cache?

    python tools/compile_cache_probe.py <config name> [--rows 1,64]

On the chip (under ``JAX_PLATFORMS=cpu`` it compiles for the CPU: a rehearsal of
the control flow, its sizes mean nothing). Says what the machine's cache is
(``JAX_COMPILATION_CACHE_DIR``, ``jax_compilation_cache_max_size``: an LRU when
it is not -1), lists the directory, then in a child process compiles the decode
step and the served chunk step of ``benchmark/configs/<config name>.json`` at
each ``--rows`` (``llama.forward`` alone on abstract arguments: no weights, no
sampling; what ``tools/step_relayouts.py`` compiles, here for the real device
and through the cache) with ``jax_explain_cache_misses`` on, so that JAX says
why an entry is not written, and lists what each compile left in the directory;
a second child compiles the same again and counts hits. The last line scales the
entries' bytes to the cell's lattice (rows buckets x context buckets, a decode
and a chunk program each) beside the cache's limit: a lattice larger than an
LRU is evicted in the order it is read, so a run after a run of the same tree
hits nothing (PERF.md, PR 40).

Beside it, the runners' executable store (``dynamo_tpu/executable_store.py``,
``executables/`` under the cache's directory, which no LRU looks at): each
build's digest, entries and bytes before and after, and of each child run the
store's hits and misses and what a load took. The children go through the store
as a runner's first call does: an entry found is loaded and nothing is lowered
or compiled; one not found is compiled as above and written.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import pathlib
import subprocess
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


def listing(path: str) -> dict[str, int]:
    """Cache entries by file name (the ``-atime`` stamps of an LRU left out)."""
    out = {}
    for f in pathlib.Path(path).iterdir():
        if f.is_file() and not f.name.endswith("-atime"):
            out[f.name] = f.stat().st_size
    return out


def child(config: str, rows: list[int]) -> int:
    import jax
    import jax.numpy as jnp

    from benchmark import serving, weights
    from dynamo_tpu import executable_store
    from dynamo_tpu.compile_cache import enable_compile_cache
    from dynamo_tpu.models import kda, llama
    from dynamo_tpu.models.mla import lay_heads_major

    path = enable_compile_cache()
    jax.config.update("jax_explain_cache_misses", True)
    events = {"hits": 0, "misses": 0}

    def on_event(event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            events["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            events["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    store = executable_store.open_store()
    conf = serving.load_config(ROOT / "benchmark" / "configs" / f"{config}.json", rehearsal=jax.default_backend() != "tpu")
    mc, eng = serving.model_config(conf), conf["serve"]["engine"]
    page_size, chunk = eng["page_size"], eng["chunk_prefill_tokens"]
    pages_per_row = 1 << (-(-eng["max_seq_len"] // page_size) - 1).bit_length()
    sds = jax.ShapeDtypeStruct
    like = lambda tree: jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)  # noqa: E731
    i32 = lambda *s: sds(s, jnp.int32)  # noqa: E731
    params = like(jax.eval_shape(lambda: lay_heads_major(weights.make_weights(mc, 0, quant=conf["serve"]["quant"]))))  # as the runner lays it
    pool_pages = eng["pool_tokens"] // page_size + 1
    # A model with a page pool per layer kind: the window pool the serving path derives (launch.py).
    window_pages = (llama.window_pool_pages(mc, pool_pages, page_size, eng["max_batch_size"], chunk)
                    if mc.mixed_attention else None)
    kc, vc = like(jax.eval_shape(lambda: llama.init_kv_cache(mc, pool_pages, page_size, window_pages=window_pages)))
    counted = {"moe_counts": True} if mc.moe_held_share else {}
    programs = []
    for r in rows:
        for label, split, toks, slots in (("decode", None, (r, 1), r), ("mixed", (r, 1, chunk), (r + chunk,), r + 1)):
            kept = {}
            if mc.recurrent_layers:
                kept["recurrent"] = (*like(jax.eval_shape(lambda: kda.init_state(mc, eng["max_batch_size"] + 1))), i32(slots))
            if window_pages is not None:
                kept.update(window_tables=i32(slots, pages_per_row), window_slots=i32(*toks))
                counted = {**counted, "window_pages": window_pages}
            fn = functools.partial(llama.forward, cfg=mc, attn_impl="pallas", split=split, **counted)
            arguments = dict(params=params, tokens=i32(*toks), positions=i32(*toks), k_cache=kc, v_cache=vc,
                             block_tables=i32(slots, pages_per_row), slot_mapping=i32(*toks),
                             last_token_index=i32(slots), **kept)
            before, t0 = listing(path), time.time()
            name = executable_store.program_key(f"probe:{config}", "forward", label, (r,), (), (), arguments)
            stored = store.load(name, jax.devices()[:1]) if store is not None else None
            load_s, hits = time.time() - t0, events["hits"]
            if stored is None:
                compiled = jax.jit(fn, donate_argnames=("k_cache", "v_cache", *(k for k in kept if k == "recurrent"))).lower(
                    **arguments).compile()
                # (as a runner: a program out of JAX's cache is written only where it serialises whole again)
                if store is not None and (events["hits"] == hits or jax.default_backend() in executable_store.RESERIALISES):
                    store.save(name, compiled)
            after = listing(path)
            programs.append({"rows": r, "program": label, "compile_s": round(time.time() - t0, 2),
                             "store": "off" if store is None else "hit" if stored is not None else "miss",
                             "store_load_s": round(load_s, 3),
                             "written": {k: v for k, v in after.items() if k not in before},
                             "gone": len([k for k in before if k not in after])})
    print(json.dumps({"backend": jax.default_backend(), "cache_max_size": jax.config.jax_compilation_cache_max_size,
                      "programs": programs, **events, "store": None if store is None else store.counters()}), flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config")
    ap.add_argument("--rows", default="1,64")
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args()
    rows = [int(r) for r in args.rows.split(",")]
    if args.child:
        return child(args.config, rows)

    from benchmark import serving, traffic
    from dynamo_tpu import executable_store
    from dynamo_tpu.compile_cache import CACHE_DIR_ENV

    path = os.environ.get(CACHE_DIR_ENV) or str(ROOT / ".jax_cache")
    os.makedirs(path, exist_ok=True)
    store_dir = os.path.join(path, executable_store.DIRECTORY)
    had = listing(path)
    by_module: dict[str, list[int]] = {}
    for name, size in had.items():
        by_module.setdefault(name.split("-")[0], []).append(size)
    print(json.dumps({"cache_dir": path, "env": {k: v for k, v in os.environ.items() if k.startswith("JAX_")},
                      "entries": len(had), "bytes": sum(had.values()),
                      "by_module": {k: [len(v), sum(v)] for k, v in by_module.items()},
                      "executable_store": executable_store.describe(store_dir)}), flush=True)
    runs = []
    for _ in range(2):  # the parent never touches JAX: a chip belongs to one process at a time
        done = subprocess.run([sys.executable, __file__, args.config, "--rows", args.rows, "--child"],
                              capture_output=True, text=True)
        lines = [ln for ln in done.stdout.splitlines() if ln.startswith("{")]
        said = sorted({ln[:300] for ln in done.stderr.splitlines()
                       if "persistent" in ln.lower() or "compilation cache" in ln.lower() or "compilation_cache" in ln})
        if done.returncode or not lines:
            print(done.stderr[-3000:], file=sys.stderr)
            return done.returncode or 1
        runs.append({**json.loads(lines[-1]), "jax_said": said[:12]})
        print(json.dumps(runs[-1]), flush=True)
    conf = serving.load_config(ROOT / "benchmark" / "configs" / f"{args.config}.json")
    cell = next((c for c in sorted((ROOT / "benchmark" / "cells").glob(f"{args.config}.*.json"))), None)
    lattice = None
    if cell is not None:
        mix = traffic.load_mix(ROOT / "benchmark" / "traffic" / f"{cell.stem.split('.')[-1]}.json", cell)
        lattice = len(serving.warm_shapes(conf, mix["warm"]))
    wrote = [sum(p["written"].values()) for p in runs[0]["programs"]]
    after = listing(path)
    print(json.dumps({"config": args.config, "first_run_hits": runs[0]["hits"], "second_run_hits": runs[1]["hits"],
                      "of": len(runs[1]["programs"]), "entry_bytes": wrote, "lattice_programs": lattice,
                      "lattice_bytes_about": None if lattice is None or not all(wrote) else int(sum(wrote) / len(wrote) * lattice),
                      "entries_after": len(after), "bytes_after": sum(after.values()),
                      "store_hits": [r["store"] and r["store"]["hits"] for r in runs],
                      "store_misses": [r["store"] and r["store"]["misses"] for r in runs],
                      "store_entry_bytes": runs[0]["store"] and runs[0]["store"]["bytes_written"],
                      "executable_store": executable_store.describe(store_dir)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
