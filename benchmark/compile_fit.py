#!/usr/bin/env python3
"""Sandbox compile of a configuration's decode and mixed step at its pinned
shapes, for a *described* v5e: nothing runs, no chip is needed. Says whether
the model forward fits the chip beside weights and cache (``memory_analysis``)
and whether the attention kernels are in the program (``tpu_custom_call``).
A compile that passes is not a chip run.

    JAX_PLATFORMS=cpu python3 benchmark/compile_fit.py <config name> [rows]
"""

from __future__ import annotations

import functools
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


def main() -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark import serving, weights
    from dynamo_tpu.models import llama

    jax.config.update("jax_enable_compilation_cache", False)
    name, rows = sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 64
    conf = serving.load_config(ROOT / "benchmark" / "configs" / f"{name}.json")
    mc = serving.model_config(conf)
    eng = conf["serve"]["engine"]
    chip = SingleDeviceSharding(topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=chip)

    def like(tree):
        return jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)

    params = like(jax.eval_shape(lambda: weights.make_weights(mc, 0, quant=conf["serve"]["quant"])))
    pages = eng["pool_tokens"] // eng["page_size"] + 1
    kc, vc = like(jax.eval_shape(lambda: llama.init_kv_cache(mc, pages, eng["page_size"])))
    nbytes = lambda t: sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(t))  # noqa: E731
    n_pages = 1 << (-(-eng["max_seq_len"] // eng["page_size"]) - 1).bit_length()
    out = {"config": name, "weights_bytes": nbytes(params), "cache_bytes": nbytes((kc, vc)), "steps": {}}
    for label, t in (("decode", 1), ("mixed", eng["chunk_prefill_tokens"])):
        i32 = lambda *s: sds(s, jnp.int32)  # noqa: E731
        t0 = time.time()
        compiled = jax.jit(
            functools.partial(llama.forward, cfg=mc, attn_impl="pallas"), donate_argnames=("k_cache", "v_cache"),
        ).lower(params=params, tokens=i32(rows, t), positions=i32(rows, t), k_cache=kc, v_cache=vc,
                block_tables=i32(rows, n_pages), slot_mapping=i32(rows, t), last_token_index=i32(rows)).compile()
        mem = compiled.memory_analysis()
        text = compiled.as_text()
        out["steps"][label] = {
            "rows": rows, "tokens_per_row": t, "pages_per_row": n_pages, "compile_s": round(time.time() - t0, 1),
            "temp_bytes": mem.temp_size_in_bytes, "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes, "alias_bytes": mem.alias_size_in_bytes,
            "resident_plus_temp_bytes": mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes,
            "tpu_custom_calls": text.count("tpu_custom_call"),
        }
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
