#!/usr/bin/env python3
"""Proof that the serving path starts, answers and computes correctly on the chip.

``python3 chip_smoke.py`` (one TPU chip): brings Llama-3.2-1B (bf16, full
published width and depth, random weights from the launcher's fixed seed) up
through ``dynamo_tpu.launch``'s own argument parser and ``--role local``
topology, talks to it over real sockets, and checks

- every HTTP request returns 200 with exactly the requested number of tokens;
- attention was dispatched to the Pallas kernels for prefill and decode, with
  zero recorded fallbacks and no interpret mode;
- logprobs the live engine returns (its own step programs: a two-chunk
  prefill, then decode steps on the pages that prefill wrote) agree with a
  plain forward over ``paged_attention_reference`` on the same weights within
  ``LOGIT_REL_TOL``, and sit no further from a float32 forward than
  ``ANCHOR_FACTOR`` times what bf16 rounding costs the plain forward.

``--chips 4`` (run by hand, never by the driver) runs only what exists across
chips: Llama-3-8B bf16 under ``--mesh tp=4`` with the same logits comparison
on the mesh plus placement evidence, then four 1B replicas behind the KV
router with per-device placement.

Everything runs in this one process (a chip belongs to one process), phases
one after another. Each fact is one JSON object per line on stdout; the last
line is the verdict. Any failure raises: non-zero exit and no ``"ok": true``.
No accelerator is an error — unless the caller pinned ``JAX_PLATFORMS=cpu``,
which selects a CPU rehearsal of the same control flow at ``test-tiny`` that
says so and still exits non-zero (``"ok": true`` only ever appears for tpu).
"""

from __future__ import annotations

import argparse
import asyncio
import functools
import gc
import importlib.metadata
import json
import os
import pathlib
import random
import subprocess
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent

#: KV pool the smoke asks ``--num-pages`` for: >= 64K tokens at the page size
#: the CLI really serves (launch has no page-size flag; its 512-page default
#: holds 8192 tokens, which a handful of 2K prompts overflow).
POOL_TOKENS = 65536
#: Served-vs-reference logprobs: max |diff| over the largest reference |logit|.
#: Both paths round to bf16 at every layer, in a different order. Anchored on
#: the chip at Llama-3.2-1B (16 layers): the plain bf16 forward sits 1.06% from
#: a float32 forward, the served path 0.93%, the two 0.94% apart (PERF.md,
#: PR 21) — so 3% is about three times what rounding costs at that depth.
LOGIT_REL_TOL = 3e-2
#: One chip: the served path may be this many times further from a float32
#: forward than the plain bf16 reference forward is.
ANCHOR_FACTOR = 3.0
#: (prompt tokens, output tokens) of the concurrent batch at context 4096;
#: scaled down with the context for the CPU rehearsal's tiny model.
CONCURRENT_MIX = [(64, 128), (160, 96), (320, 64), (512, 48),
                  (800, 32), (1200, 64), (1600, 96), (2048, 128)]
EXIT_REHEARSAL = 3


#: device_kind substring -> (peak HBM GB/s, peak bf16 dense TFLOPS) per chip, from
#: the datasheets. Matched case-insensitively against jax's device_kind strings
#: ("TPU v5 lite" is v5e, "TPU v6 lite" v6e, a bare "TPU v5" the p-class part).
CHIP_PEAKS: dict[str, tuple[float, float]] = {
    "v6e": (1640.0, 918.0),
    "v6 lite": (1640.0, 918.0),
    "v5e": (819.0, 197.0),
    "v5 lite": (819.0, 197.0),
    "v5p": (2765.0, 459.0),
    "v5": (2765.0, 459.0),
    "v4": (1228.0, 275.0),
}

#: The CPU rehearsal only: round numbers no one takes for a measurement.
CPU_PROXY_PEAKS = (50.0, 0.5)


def chip_peaks() -> tuple[float, float, str]:
    """(peak HBM GB/s, peak TFLOPS, source) of device 0: from ``CHIP_PEAKS``,
    ``CPU_PROXY_PEAKS`` on the CPU platform. An accelerator the table does not
    know is an error, not a default."""
    import jax

    dev = jax.devices()[0]
    kind = dev.device_kind
    if dev.platform == "cpu":
        return (*CPU_PROXY_PEAKS, f"cpu-proxy:{kind}")
    for sub, (hbm, tflops) in CHIP_PEAKS.items():
        if sub in kind.lower():
            return hbm, tflops, f"table:{kind}"
    raise RuntimeError(f"no peak HBM bandwidth / FLOPS known for {dev.platform} device_kind "
                       f"{kind!r}: add it to CHIP_PEAKS (chip_smoke.py)")


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


class Phase:
    """Times one phase and prints its wall seconds; never swallows."""

    def __init__(self, name: str) -> None:
        self.name = name

    def __enter__(self) -> "Phase":
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc_type is None:
            emit(phase=self.name, wall_s=round(time.perf_counter() - self.t0, 3))


class CompileStats:
    """JAX's own compile/cache events (jax.monitoring), summed."""

    def __init__(self) -> None:
        import jax

        self.cache_requests = 0
        self.cache_hits = 0
        self.cache_misses = 0  # counted by jax only for programs worth caching
        self.backend_compile_s = 0.0
        self.backend_compiles = 0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.cache_requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def _on_duration(self, event: str, seconds: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.backend_compile_s += seconds
            self.backend_compiles += 1

    def snapshot(self) -> dict:
        return dict(
            cache_requests=self.cache_requests, cache_hits=self.cache_hits,
            cache_misses=self.cache_misses,
            backend_compiles=self.backend_compiles,
            backend_compile_s=round(self.backend_compile_s, 3),
        )


# -- HTTP traffic -------------------------------------------------------------


def _prompt_text(rng: random.Random, n_tokens: int) -> str:
    """ASCII text of ``n_tokens - 1`` bytes: the byte tokenizer maps one byte
    to one token and the preprocessor adds BOS."""
    words = []
    size = 0
    while size < n_tokens - 1:
        w = "".join(rng.choice("abcdefghijklmnopqrstuvwxyz") for _ in range(rng.randint(2, 9)))
        words.append(w)
        size += len(w) + 1
    return " ".join(words)[: max(1, n_tokens - 1)]


async def _post(session, base: str, path: str, body: dict) -> dict:
    """Non-streaming POST; returns the parsed document after the 200 check."""
    async with session.post(base + path, json=body) as resp:
        text = await resp.text()
        check(resp.status == 200, f"POST {path} -> {resp.status}: {text[:300]}")
    return json.loads(text)


def _check_usage(doc: dict, want_out: int, what: str) -> dict:
    usage = doc.get("usage") or {}
    check(usage.get("completion_tokens") == want_out,
          f"{what}: asked for {want_out} tokens, usage says {usage}")
    return usage


async def _completion(session, base: str, model: str, prompt: str, max_tokens: int) -> dict:
    doc = await _post(session, base, "/v1/completions", {
        "model": model, "prompt": prompt, "max_tokens": max_tokens,
        "temperature": 0, "nvext": {"ignore_eos": True},
    })
    usage = _check_usage(doc, max_tokens, "completion")
    check(doc["choices"][0]["finish_reason"] == "length", f"finish_reason {doc['choices'][0]}")
    return usage


async def _chat_stream(session, base: str, model: str, content: str, max_tokens: int) -> dict:
    body = {
        "model": model, "messages": [{"role": "user", "content": content}],
        "max_tokens": max_tokens, "temperature": 0, "stream": True,
        "stream_options": {"include_usage": True}, "nvext": {"ignore_eos": True},
    }
    usage, chunks, done = None, 0, False
    async with session.post(base + "/v1/chat/completions", json=body) as resp:
        if resp.status != 200:
            raise AssertionError(f"streaming chat -> {resp.status}: {(await resp.text())[:300]}")
        async for raw in resp.content:
            line = raw.decode().strip()
            if not line.startswith("data: "):
                continue
            if line == "data: [DONE]":
                done = True
                continue
            doc = json.loads(line[6:])
            check("error" not in doc, f"stream error: {doc}")
            chunks += 1
            usage = doc.get("usage") or usage
    check(done, "stream ended without [DONE]")
    check(usage is not None and usage.get("completion_tokens") == max_tokens,
          f"streaming chat: asked for {max_tokens} tokens, usage says {usage}")
    return dict(usage, sse_chunks=chunks)


async def _concurrent_pass(session, base: str, model: str, mix, seed: int) -> dict:
    rng = random.Random(seed)
    prompts = [_prompt_text(rng, n_in) for n_in, _ in mix]
    t0 = time.perf_counter()
    usages = await asyncio.gather(*(
        _completion(session, base, model, p, n_out) for p, (_, n_out) in zip(prompts, mix)
    ))
    wall = time.perf_counter() - t0
    return dict(
        requests=len(mix), wall_s=round(wall, 3),
        prompt_tokens=[u["prompt_tokens"] for u in usages],
        completion_tokens=[u["completion_tokens"] for u in usages],
    )


def _scaled_mix(context: int) -> list[tuple[int, int]]:
    if context >= 4096:
        return CONCURRENT_MIX
    scale = context / 4096
    return [(max(8, int(i * scale)), max(4, int(o * scale))) for i, o in CONCURRENT_MIX]


# -- telemetry ----------------------------------------------------------------


def _dispatch_counts(core) -> dict[str, int]:
    return {f"{phase}:{path}": n for (phase, path), n in sorted(core.attn_dispatch_counts.items())}


def _check_kernels_ran(cores, *, enforce: bool) -> None:
    """Dispatch telemetry must show the Pallas path for prefill and decode on
    every worker that took traffic, and nothing may have fallen back."""
    from dynamo_tpu.ops.pallas_paged import fallback_snapshot, interpret_mode

    per_worker = [_dispatch_counts(c) for c in cores]
    fallbacks = fallback_snapshot()
    emit(attn_impl=[c.runner.attn_impl for c in cores], attn_dispatch=per_worker,
         kernel_fallbacks=fallbacks, interpret_mode=interpret_mode())
    if not enforce:
        return
    check(not interpret_mode(), "Pallas interpret mode is on")
    check(not fallbacks, f"kernel fallbacks recorded: {fallbacks}")
    total: dict[str, int] = {}
    for counts in per_worker:
        for key, n in counts.items():
            total[key] = total.get(key, 0) + n
    check(all(key.endswith(":pallas") for key in total), f"non-pallas dispatches: {total}")
    check(total.get("prefill:pallas", 0) > 0 and total.get("decode:pallas", 0) > 0,
          f"prefill and decode must both have run on the kernels: {total}")


def _memory(devices) -> list[dict]:
    out = []
    for d in devices:
        stats = d.memory_stats() or {}
        out.append({k: stats.get(k) for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")})
    return out


def _free_runner(runner) -> None:
    """Release a stopped server's device memory before the next phase."""
    import jax

    for leaf in jax.tree.leaves((runner.params, runner.k_cache, runner.v_cache)):
        leaf.delete()
    gc.collect()


# -- logits: what the engine served vs a plain reference forward ----------------


async def _served_logprobs(service, prompt: list[int], n_out: int,
                           decoding: asyncio.Event | None = None) -> list[dict]:
    """One request through the engine service the HTTP frontend feeds (token
    ids in, so the comparison can name ids): scheduler, chunked prefill and
    decode in the runner's own step programs, its fused sampler's logprobs.
    ``decoding`` is set once the first token is out."""
    from dynamo_tpu.engine.core import LOGPROBS_TOP_K
    from dynamo_tpu.protocols.common import PreprocessedRequest, SamplingOptions, StopConditions
    from dynamo_tpu.runtime.engine import Context

    request = PreprocessedRequest(
        token_ids=prompt,
        sampling=SamplingOptions(temperature=0.0, logprobs=LOGPROBS_TOP_K + 1),
        stop=StopConditions(max_tokens=n_out, ignore_eos=True),
    )
    entries: list[dict] = []
    async for out in service.generate(request, Context()):
        entries.extend(out.get("logprobs") or [])
        if decoding is not None and entries:
            decoding.set()
    check(len(entries) == n_out, f"asked the engine for {n_out} tokens with logprobs, got {len(entries)}")
    return entries


def _reference_logprobs(runner, prompts, generated, *, f32: bool):
    """The same tokens through a plain forward with the XLA gather attention
    (``paged_attention_reference``) on fresh caches: each whole prompt in one
    unchunked prefill, then decode steps fed the tokens the server sampled.
    ``f32``: weights, caches and matmuls in float32 — the anchor both bf16
    paths are measured against. Returns (log-softmax f32[rows, steps, vocab],
    max |logit|)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.models import llama

    cfg, ps, mesh = runner.cfg, runner.page_size, runner.mesh
    b, steps = len(prompts), len(generated[0])
    lens = np.asarray([len(p) for p in prompts])
    t = int(lens.max())
    pages_per_seq = -(-(t + steps) // ps)
    num_pages = 1 + b * pages_per_seq  # page 0 is the null page
    block_tables = 1 + np.arange(b * pages_per_seq, dtype=np.int32).reshape(b, pages_per_seq)
    params = runner.params
    dtype = runner.k_cache.dtype
    if f32:
        dtype = jnp.float32
        params = jax.tree.map(
            lambda x: x.astype(jnp.float32) if jnp.issubdtype(x.dtype, jnp.floating) else x, params)

    def put(a):
        if mesh is None:
            return jax.device_put(a, runner.device) if runner.device is not None else jnp.asarray(a)
        from dynamo_tpu.parallel.sharding import batch_sharding

        return jax.device_put(a, batch_sharding(mesh, np.ndim(a)))

    if mesh is None:
        with runner._on_device():
            kc, vc = llama.init_kv_cache(cfg, num_pages, ps, dtype=dtype)
    else:
        kc, vc = jax.jit(
            lambda: llama.init_kv_cache(cfg, num_pages, ps, dtype=dtype),
            out_shardings=(runner.k_cache.sharding, runner.v_cache.sharding),
        )()
    fwd = jax.jit(
        functools.partial(llama.forward, cfg=cfg, attn_impl="reference", mesh=mesh),
        donate_argnames=("k_cache", "v_cache"),
    )
    # Prefill: rows shorter than T carry position-0 / slot-0 (null page)
    # padding, the runner's convention.
    tok = np.zeros((b, t), np.int32)
    pos = np.zeros((b, t), np.int32)
    slot = np.zeros((b, t), np.int32)
    for r, n in enumerate(lens):
        at = np.arange(n)
        tok[r, :n], pos[r, :n] = prompts[r], at
        slot[r, :n] = block_tables[r, at // ps] * ps + at % ps
    out = []
    with jax.default_matmul_precision("highest" if f32 else "default"):
        logits, kc, vc = fwd(
            params=params, tokens=put(tok), positions=put(pos), k_cache=kc, v_cache=vc,
            block_tables=put(block_tables), slot_mapping=put(slot),
            last_token_index=put((lens - 1).astype(np.int32)))
        out.append(np.asarray(logits, np.float32))
        for j in range(steps - 1):  # token j sits at position len + j
            at = lens + j
            logits, kc, vc = fwd(
                params=params, tokens=put(np.asarray([[g[j]] for g in generated], np.int32)),
                positions=put(at.astype(np.int32)[:, None]), k_cache=kc, v_cache=vc,
                block_tables=put(block_tables),
                slot_mapping=put((block_tables[np.arange(b), at // ps] * ps + at % ps)
                                 .astype(np.int32)[:, None]),
                last_token_index=put(np.zeros((b,), np.int32)))
            out.append(np.asarray(logits, np.float32))
    kc.delete(), vc.delete()
    logits = np.stack(out, axis=1)  # [rows, steps, vocab]
    check(logits.shape == (b, steps, cfg.vocab_size) and np.isfinite(logits).all(),
          f"reference logits shape {logits.shape} / non-finite values")
    z = logits - logits.max(axis=-1, keepdims=True)
    return z - np.log(np.exp(z).sum(axis=-1, keepdims=True)), float(np.abs(logits).max())


async def logits_check(service, *, enforce: bool, anchor_f32: bool) -> None:
    """Two requests through the live engine, sent while a third is decoding:
    beside running decode rows the scheduler cuts prefill into chunks, so the
    longer prompt's second chunk attends its first through the cache in a
    mixed step, and every decode step reads pages the served prefill wrote.
    Asked for are the chosen token's logprob and the top alternatives. The
    same tokens then go through the plain reference forward. Compared are
    logprobs at the ids the server named — not token ids: random weights give
    near-tied argmaxes. Differences are stated relative to the largest
    reference |logit|."""
    import numpy as np

    core = service.core
    runner, cfg = core.runner, core.config
    n_out = 3
    long = min(cfg.chunk_prefill_tokens * 11 // 8, cfg.max_seq_len - n_out - 1)  # 704 at chunk 512
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, runner.cfg.vocab_size, size=n).tolist()
               for n in (long, long // 2 - 22, 40)]
    filler = prompts.pop()

    def prefill_steps() -> int:
        return sum(n for (phase, _), n in core.attn_dispatch_counts.items() if phase == "prefill")

    decoding = asyncio.Event()
    in_flight = asyncio.ensure_future(
        _served_logprobs(service, filler, min(64, cfg.max_seq_len - 41), decoding))
    await decoding.wait()
    steps_before = prefill_steps()
    served = await asyncio.gather(*(_served_logprobs(service, p, n_out) for p in prompts))
    chunked_steps = prefill_steps() - steps_before
    await in_flight
    generated = [[e["id"] for e in row] for row in served]

    def at_served_ids(lps):
        """[rows][steps] -> the reference's logprobs at the ids the server named."""
        return np.asarray([[lps[r, j, [e["id"]] + [i for i, _ in e["top"]]]
                            for j, e in enumerate(row)] for r, row in enumerate(served)])

    served_lps = np.asarray([[[e["logprob"]] + [lp for _, lp in e["top"]] for e in row] for row in served])
    # Off the event loop: the server is live, and its keep-alives run there.
    in_thread = asyncio.get_running_loop().run_in_executor
    ref_lps, absmax = await in_thread(
        None, functools.partial(_reference_logprobs, runner, prompts, generated, f32=False))
    report = dict(
        served_vs_reference=float(np.abs(served_lps - at_served_ids(ref_lps)).max() / absmax),
        ref_logit_absmax=absmax,
        argmax_agree=int(sum(g == int(ref_lps[r, j].argmax())
                             for r, row in enumerate(generated) for j, g in enumerate(row))),
        tokens=len(prompts) * n_out, ids_per_token=served_lps.shape[-1],
    )
    if anchor_f32:
        f32_lps, _ = await in_thread(
            None, functools.partial(_reference_logprobs, runner, prompts, generated, f32=True))
        report["served_vs_f32"] = float(np.abs(served_lps - at_served_ids(f32_lps)).max() / absmax)
        report["reference_vs_f32"] = float(
            np.abs(at_served_ids(ref_lps) - at_served_ids(f32_lps)).max() / absmax)
    emit(logits_check=report, served_impl=runner.attn_impl, tolerance=LOGIT_REL_TOL,
         anchor_factor=ANCHOR_FACTOR, prompt_tokens=[len(p) for p in prompts],
         prefill_steps=chunked_steps,
         chunk_prefill_tokens=cfg.chunk_prefill_tokens, page_size=cfg.page_size)
    if not enforce:
        return
    check(chunked_steps >= 2, f"the {long}-token prompt was not prefilled in chunks: {chunked_steps} step(s)")
    check(report["served_vs_reference"] <= LOGIT_REL_TOL, f"served logprobs differ from reference: {report}")
    if anchor_f32:
        # Served and reference both round to bf16; float32 says how much that
        # costs. The served path may not be further from it than the plain
        # bf16 forward is, times the factor (a wrong mask or slot is >10x).
        noise = max(report["reference_vs_f32"], 2.0**-9)
        check(report["served_vs_f32"] <= ANCHOR_FACTOR * noise,
              f"served path is further from float32 than bf16 rounding explains: {report}")


def sharded_forward_collectives(runner, *, enforce: bool) -> None:
    """The served forward compiled for the mesh (nothing runs): the all-reduces
    that show the model is really spread."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.models import llama
    from dynamo_tpu.parallel.sharding import batch_sharding

    def like(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)

    def batch(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=batch_sharding(runner.mesh, len(shape)))

    b, n = 8, 32
    hlo = jax.jit(
        functools.partial(llama.forward, cfg=runner.cfg, attn_impl=runner.attn_impl, mesh=runner.mesh)
    ).lower(
        params=jax.tree.map(like, runner.params), tokens=batch(b, 1), positions=batch(b, 1),
        k_cache=like(runner.k_cache), v_cache=like(runner.v_cache), block_tables=batch(b, n),
        slot_mapping=batch(b, 1), last_token_index=batch(b),
    ).compile().as_text()
    n_ar = hlo.count("all-reduce(") + hlo.count("all-reduce-start(")
    emit(forward_hlo_all_reduces=n_ar, forward_hlo_custom_calls=hlo.count("tpu_custom_call"))
    if enforce:
        check(n_ar > 0, "no all-reduce in the compiled sharded forward")


# -- phases -------------------------------------------------------------------


def _launch_argv(model: str, *extra: str) -> list[str]:
    """The CLI a user would type, plus the one sizing override the smoke needs."""
    from dynamo_tpu import launch

    page_size = launch.make_worker_spec(model).engine_config.page_size
    num_pages = POOL_TOKENS // page_size + 256  # page 0 is the reserved null page
    return ["--model", model, "--host", "127.0.0.1", "--http-port", "0", *extra,
            "--num-pages", str(num_pages)]


async def serve_one_chip(model: str, stats: CompileStats, *, enforce: bool) -> None:
    import aiohttp
    import jax

    from dynamo_tpu import launch

    argv = _launch_argv(model)
    with Phase("start_server"):
        handles = await launch.start_local(launch.parse_args(argv))
    try:
        core = handles["services"][0].core
        runner = core.runner
        cfg = core.config
        emit(launch_argv=argv, page_size=cfg.page_size, num_pages=cfg.num_pages,
             pool_tokens=cfg.page_size * (cfg.num_pages - 1),
             kv_cache_bytes=runner.cache_memory_bytes(), context=cfg.max_seq_len,
             chunk_prefill_tokens=cfg.chunk_prefill_tokens, max_batch_size=cfg.max_batch_size,
             decode_steps=cfg.decode_steps, overlap=cfg.overlap, spec_k=cfg.spec_k)
        base = f"http://127.0.0.1:{handles['port']}"
        tracker = runner.compile_tracker
        timeout = aiohttp.ClientTimeout(total=900)
        async with aiohttp.ClientSession(timeout=timeout) as session:
            with Phase("single_requests"):
                async with session.get(base + "/health") as resp:
                    check(resp.status == 200, f"/health -> {resp.status}")
                async with session.get(base + "/v1/models") as resp:
                    check(resp.status == 200, f"/v1/models -> {resp.status}")
                    ids = [m["id"] for m in (await resp.json())["data"]]
                check(model in ids, f"{model} not in /v1/models: {ids}")
                doc = await _post(session, base, "/v1/chat/completions", {
                    "model": model, "messages": [{"role": "user", "content": "Say hello."}],
                    "max_tokens": 16, "temperature": 0, "nvext": {"ignore_eos": True},
                })
                emit(request="chat", usage=_check_usage(doc, 16, "chat"))
                emit(request="chat_stream",
                     usage=await _chat_stream(session, base, model, "Count to ten.", 24))
                emit(request="completion",
                     usage=await _completion(session, base, model, "The quick brown fox", 20))
            mix = _scaled_mix(cfg.max_seq_len)
            with Phase("concurrent_pass_1_warm"):
                emit(concurrent_pass=1, **await _concurrent_pass(session, base, model, mix, 1))
            cold = stats.snapshot()
            events_before = len(tracker.events())
            with Phase("concurrent_pass_2_checked"):
                emit(concurrent_pass=2, **await _concurrent_pass(session, base, model, mix, 2))
            new = [e for e in tracker.events()[events_before:] if e["reason"] == "new_shape"]
            emit(compiled_in_second_pass=len(new), expected=0,
                 buckets=[[e["program"], *e["bucket"][:4]] for e in new])
            async with session.get(base + "/metrics") as resp:
                text = await resp.text()
                check(resp.status == 200 and "dynamo_" in text, f"/metrics -> {resp.status}")
            emit(metrics_lines=text.count("\n"),
                 has_attn_dispatch_series="dynamo_engine_attn_dispatch_steps_total" in text)
        emit(compile_programs={f"{p}:{r}": n for (p, r), n in sorted(tracker.counts().items())},
             through_first_pass=cold, total=stats.snapshot())
        emit(device_memory=_memory(jax.local_devices()[:1]))
        with Phase("logits_check"):
            await logits_check(handles["services"][0], enforce=enforce, anchor_f32=True)
        _check_kernels_ran([core], enforce=enforce)
    finally:
        with Phase("stop_server"):
            await launch.stop_local(handles)
    _free_runner(runner)


async def serve_sharded(model: str, *, enforce: bool) -> None:
    """(a) one model sharded over the four chips with ``--mesh tp=4``."""
    import aiohttp
    import jax

    from dynamo_tpu import launch

    argv = _launch_argv(model, "--mesh", "tp=4")
    with Phase("sharded_start_server"):
        handles = await launch.start_local(launch.parse_args(argv))
    try:
        core = handles["services"][0].core
        runner = core.runner
        base = f"http://127.0.0.1:{handles['port']}"
        model_bytes = sum(x.nbytes for x in jax.tree.leaves(runner.params))
        emit(launch_argv=argv, mesh=dict(runner.mesh.shape), model_bytes=model_bytes,
             kv_cache_bytes=runner.cache_memory_bytes(), page_size=core.config.page_size,
             num_pages=core.config.num_pages)
        mix = _scaled_mix(core.config.max_seq_len)[:4]
        async with aiohttp.ClientSession(timeout=aiohttp.ClientTimeout(total=900)) as session:
            with Phase("sharded_requests"):
                doc = await _post(session, base, "/v1/chat/completions", {
                    "model": model, "messages": [{"role": "user", "content": "Say hello."}],
                    "max_tokens": 16, "temperature": 0, "nvext": {"ignore_eos": True},
                })
                emit(request="chat", usage=_check_usage(doc, 16, "chat"))
                emit(concurrent_pass=1, **await _concurrent_pass(session, base, model, mix, 1))
        mem = _memory(jax.local_devices())
        emit(device_memory=mem)
        if enforce:
            used = [m["bytes_in_use"] for m in mem]
            check(max(used) <= 1.2 * min(used), f"devices hold unequal bytes: {used}")
            check(max(used) < 0.6 * model_bytes,
                  f"a device holds {max(used)} of a {model_bytes}-byte model: not spread")
        with Phase("sharded_logits_check"):
            # No float32 anchor here: a second, f32 copy of an 8B model does
            # not fit beside the served one; LOGIT_REL_TOL alone decides.
            await logits_check(handles["services"][0], enforce=enforce, anchor_f32=False)
            sharded_forward_collectives(runner, enforce=enforce)
        _check_kernels_ran([core], enforce=enforce)
    finally:
        with Phase("sharded_stop_server"):
            await launch.stop_local(handles)
    _free_runner(runner)


async def serve_replicas(model: str, *, enforce: bool) -> None:
    """(b) four one-chip replicas behind the KV router, one process."""
    import aiohttp
    import jax

    from dynamo_tpu import launch

    argv = _launch_argv(model, "--workers", "4", "--router-mode", "kv")
    before = [m["bytes_in_use"] or 0 for m in _memory(jax.local_devices())]
    with Phase("replicas_start_server"):
        handles = await launch.start_local(launch.parse_args(argv))
    try:
        cores = [s.core for s in handles["services"]]
        base = f"http://127.0.0.1:{handles['port']}"
        mix = _scaled_mix(cores[0].config.max_seq_len)
        async with aiohttp.ClientSession(timeout=aiohttp.ClientTimeout(total=900)) as session:
            with Phase("replicas_requests"):
                emit(concurrent_pass=1, **await _concurrent_pass(session, base, model, mix, 1))
                emit(concurrent_pass=2, **await _concurrent_pass(session, base, model, mix, 2))
        placement = [
            dict(worker=i,
                 params_on=sorted(str(d) for d in jax.tree.leaves(c.runner.params)[0].devices()),
                 cache_on=sorted(str(d) for d in c.runner.k_cache.devices()),
                 steps=sum(c.attn_dispatch_counts.values()))
            for i, c in enumerate(cores)
        ]
        after = [m["bytes_in_use"] or 0 for m in _memory(jax.local_devices())]
        emit(launch_argv=argv, replica_placement=placement,
             bytes_in_use_delta=[a - b for a, b in zip(after, before)])
        _check_kernels_ran([c for c in cores if c.attn_dispatch_counts], enforce=enforce)
        homes = {p["cache_on"][0] for p in placement}
        check(all(p["params_on"] == p["cache_on"] and len(p["cache_on"]) == 1 for p in placement),
              f"a replica's params and cache are not on one device: {placement}")
        check(len(homes) == min(4, len(jax.local_devices())),
              f"replicas share devices: {placement}")
        check(sum(p["steps"] > 0 for p in placement) >= 2,
              f"the router sent every request to one replica: {placement}")
    finally:
        with Phase("replicas_stop_server"):
            await launch.stop_local(handles)
    for c in cores:
        _free_runner(c.runner)


def probe_device_pull() -> str:
    """``device_pull_supported()`` in a bounded daemon thread: a transfer
    server that cannot come up on this machine must not hang the smoke."""
    import threading

    result: dict[str, object] = {}

    def probe() -> None:
        from dynamo_tpu.disagg.pull_transport import device_pull_supported

        result["ok"] = device_pull_supported()

    t = threading.Thread(target=probe, name="device-pull-probe", daemon=True)
    t.start()
    t.join(60)
    return "timeout" if t.is_alive() else str(result.get("ok"))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the cross-chip paths (tp=4 mesh, then replicas)")
    args = ap.parse_args()
    t_start = time.perf_counter()

    pinned_cpu = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    # The hashing extension is built from the committed source, never taken
    # from a stale git-ignored binary; the pure-Python xxh3 path is equivalent.
    import shutil

    native_build = "no make on PATH"
    if pinned_cpu:
        native_build = "skipped: a rehearsal builds nothing into the checkout"
    elif shutil.which("make"):
        native_build = subprocess.run(
            ["make", "-C", str(ROOT / "native")], capture_output=True, text=True
        ).returncode

    import jax
    import jaxlib

    from dynamo_tpu import tokens
    from dynamo_tpu.runtime.logging import setup_logging

    setup_logging()  # as launch.main does: the server's start-up facts go to stderr
    from dynamo_tpu.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    cache_entries_before = len(os.listdir(cache_dir))
    stats = CompileStats()
    devices = jax.devices()
    device = dict(platform=devices[0].platform, kind=devices[0].device_kind, count=len(devices))
    rehearsal = device["platform"] == "cpu" and pinned_cpu
    if device["platform"] != "tpu" and not rehearsal:
        raise SystemExit(f"chip_smoke: no TPU (jax found {device}); set JAX_PLATFORMS=cpu "
                         f"explicitly for the CPU rehearsal")
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} but jax sees {len(devices)} devices")
    peaks = chip_peaks()  # raises for an accelerator device_kind the table does not know

    def version(dist: str) -> str | None:
        try:
            return importlib.metadata.version(dist)
        except importlib.metadata.PackageNotFoundError:
            return None

    emit(device=device, rehearsal=rehearsal, chips=args.chips, python=sys.version.split()[0],
         jax=jax.__version__, jaxlib=jaxlib.__version__, libtpu=version("libtpu"),
         peaks=dict(hbm_gbps=peaks[0], tflops=peaks[1], source=peaks[2]),
         compile_cache_dir=cache_dir, compile_cache_entries_at_start=cache_entries_before,
         hashing="python-xxhash" if tokens._dyncore is None else "cpp-dyncore",
         native_build=native_build)

    enforce = not rehearsal
    small, large = ("test-tiny", "test-kernel") if rehearsal else ("llama-3.2-1b", "llama-3-8b")
    if args.chips == 1:
        asyncio.run(serve_one_chip(small, stats, enforce=enforce))
    else:
        asyncio.run(serve_sharded(large, enforce=enforce))
        asyncio.run(serve_replicas(small, enforce=enforce))
    emit(device_pull_supported=probe_device_pull())
    emit(compile_total=stats.snapshot(), compile_cache_hit=stats.cache_hits > 0,
         compile_cache_entries_at_end=len(os.listdir(cache_dir)),
         device_memory_at_end=_memory(devices[: args.chips]),
         wall_s=round(time.perf_counter() - t_start, 3))
    if rehearsal:
        emit(ok=False, cpu_rehearsal="completed", device=device)
        return EXIT_REHEARSAL
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
