"""Benchmark suite: single-chip serving throughput on the real TPU.

Runs the engine core directly (no HTTP) over a SUITE of model configs
(BASELINE.md tracked classes, sized to one chip):

  llama-3.2-1b            bf16  — round-over-round headline (fixed target)
  llama-3-8b              int8  — 8B-class dense; proves int8 8B fits 16 GB
  deepseek-r1-distill-8b  int8  — BASELINE tracked config #2's model
  olmoe-1b-7b             int8  — real 7B-total MoE (64 experts / top-8)
  mla-8b-proxy            int8  — DeepSeek-V3 MLA geometry on an 8B trunk

Each config runs a continuous-batching decode phase (ISL/OSL scaled from
the reference recipe `benchmarks/llm/perf.sh`: ISL 3000 / OSL 150,
concurrency to 256) and a packed-prefill TTFT phase. The TTFT here is
measured on an otherwise-idle engine (the decode batch has drained) — a
best-case number, labeled ``ttft_idle_*``; TTFT under live decode load is
measured by the closed-loop harness (`python -m dynamo_tpu.bench.pareto`,
committed artifacts in `bench/results/`).

Perf accounting (honest by construction, VERDICT r4 weak #3):

- ``vs_target``: measured / a FIXED external anchor — the 8000 tok/s
  north-star proxy for the 1B, round-4 measured results pinned as
  continuity anchors for the rest. Never the repo's own roofline estimate.
- ``vs_roofline``: measured / the physical ceiling (modeled bytes per
  decode step at the page-granular cache layout, divided by the v5e SPEC
  HBM bandwidth 819 GB/s) — cannot exceed 1 when the byte model is right.
- ``hbm_gbps_achieved`` / ``hbm_utilization``: modeled bytes over measured
  time, and that as a fraction of spec — the bandwidth-utilization view
  (modeled bytes floor real traffic, so utilization is a floor).

Also probes the device-path KV pull bandwidth (loopback
`jax.experimental.transfer` pull of a page stack — the NIXL-equivalent
wire; falls back to the in-process gather→put→scatter path where the PJRT
runtime lacks the transfer engine).

Prints a cumulative JSON snapshot line after every config (a driver
timeout mid-suite still leaves a parseable last line) and the final line
after the KV-pull probe; the headline metric/value is the 1B config, with
every config under detail.configs. Exits non-zero when any config or probe
recorded an ``error``.
"""

import gc
import json
import os
import sys
import time

import numpy as np

# Run on the real chip: do NOT force a platform here.
# Physical HBM bandwidth (v5e datasheet): the roofline denominator. A
# correct byte model divided by the spec ceiling can never yield
# vs_roofline > 1 — r4's "beat the roofline" artifacts came from using a
# practical-bandwidth estimate calibrated on the 1B config as if it were a
# ceiling for every access pattern (VERDICT r4 weak #3).
SPEC_HBM_GBPS = float(os.environ.get("BENCH_SPEC_HBM_GBPS", "819"))
HEADLINE_TARGET = float(os.environ.get("BENCH_TARGET", "8000"))

# Fixed per-config anchors (tok/s/chip), external to the byte model: the 1B
# anchor is the round-1 north-star proxy; the others were pinned from the
# round-4 measured results and stay FIXED so vs_target is comparable across
# rounds (beating your own roofline estimate is not a target).
ANCHOR_TOK_PER_SEC = {
    "llama-3.2-1b": HEADLINE_TARGET,
    "llama-3-8b": 2000.0,
    "deepseek-r1-distill-8b": 2000.0,
    "olmoe-1b-7b": 2600.0,
    "mla-8b-proxy": 3700.0,
}

# (preset, quant, batch, isl, osl, decode_steps)
DEFAULT_SUITE = [
    ("llama-3.2-1b", "", 256, 512, 256, 32),
    ("llama-3-8b", "int8", 48, 512, 128, 32),
    ("deepseek-r1-distill-8b", "int8", 48, 512, 128, 32),
    ("olmoe-1b-7b", "int8", 64, 512, 128, 32),
    ("mla-8b-proxy", "int8", 96, 512, 128, 32),
]


def parse_suite() -> list[tuple[str, str, int, int, int, int]]:
    """BENCH_SUITE="preset:quant:batch:isl:osl:steps,..." overrides; the
    legacy single-config env vars (BENCH_PRESET/BATCH/ISL/OSL/QUANT) select
    a one-entry suite for ad-hoc runs."""
    if os.environ.get("BENCH_SUITE"):
        suite = []
        for part in os.environ["BENCH_SUITE"].split(","):
            f = part.split(":")
            suite.append((f[0], f[1] if len(f) > 1 else "",
                          int(f[2]) if len(f) > 2 else 64,
                          int(f[3]) if len(f) > 3 else 512,
                          int(f[4]) if len(f) > 4 else 128,
                          int(f[5]) if len(f) > 5 else 32))
        return suite
    if os.environ.get("BENCH_PRESET"):
        return [(
            os.environ["BENCH_PRESET"], os.environ.get("BENCH_QUANT", ""),
            int(os.environ.get("BENCH_BATCH", "64")),
            int(os.environ.get("BENCH_ISL", "512")),
            int(os.environ.get("BENCH_OSL", "128")),
            int(os.environ.get("BENCH_DECODE_STEPS", "32")),
        )]
    return DEFAULT_SUITE


def tree_nbytes(tree) -> int:
    """Total bytes of every array leaf (packed quantized leaves count at
    their true storage size: int8 ~1 B/elem, packed int4 ~0.5)."""
    import jax

    return sum(x.nbytes for x in jax.tree.leaves(tree))


def kv_bytes_per_token(cfg, cache_itemsize: int = 2) -> int:
    """HBM bytes read per cached token per decode step, across all layers
    (of a model that mixes window and full layers: across the full layers,
    see :func:`decode_step_bytes` for the sliding ones).

    Delegates to ModelConfig.kv_bytes_per_token so the MLA accounting uses
    the *physical* cache layout (rope stream lane-padded to 128 — a local
    re-derivation here under-counted the streamed bytes by ~10%, ADVICE r4).
    """
    return cfg.kv_bytes_per_token(itemsize=cache_itemsize)


def decode_step_bytes(params, cfg, batch: int, isl: int, osl: int,
                      page_size: int, cache_itemsize: int = 2) -> int:
    """Mean HBM bytes streamed per decode step, from the ACTUAL geometry:

    - weights: measured tree bytes, minus the embedding table when it is
      untied (decode gathers ``batch`` rows of it, it never streams the
      full table; a tied table IS fully read as the lm_head). MoE expert
      weights are charged in full — correct for every dispatch this suite
      runs: dense reads all experts by definition, the capacity dispatch's
      batched einsum streams all E weight slabs, and at bench decode shapes
      (batch*k >= 8x experts) the dropless ragged_dot touches essentially
      every expert too. A genuinely sparse regime (tiny batch, huge E)
      would overstate bytes, understate the roofline, and could push
      vs_roofline back over 1 — don't use this model there;
    - KV: page-granular — the paged kernels DMA whole pages, so each
      sequence's window is its context rounded up to the page size,
      averaged over the osl decode steps. A sliding layer of a model that
      mixes window and full layers reads the pages its window reaches
      into and no more.
    """
    weight_read = decode_weight_bytes(params, cfg)
    per_tok = kv_bytes_per_token(cfg, cache_itemsize)
    contexts = [isl + s + 1 for s in range(osl)]
    page_tokens = sum(-(-c // page_size) * page_size for c in contexts) / max(osl, 1)
    kv = page_tokens * per_tok
    if getattr(cfg, "mixed_attention", False):
        from dynamo_tpu.models.config import SLIDING

        # blocks from the window's first position to the context's last
        window_tokens = sum(
            (-(-c // page_size) - max(0, c - cfg.sliding_window) // page_size) * page_size for c in contexts
        ) / max(osl, 1)
        kv += window_tokens * cfg.kv_bytes_per_token(itemsize=cache_itemsize, kind=SLIDING)
    return int(weight_read + batch * kv)


def decode_weight_bytes(params, cfg) -> int:
    """The weights component of :func:`decode_step_bytes`: measured tree
    bytes (packed quantized leaves count at their true size, so int8 is
    ~1 byte/elem and int4 ~0.5) minus the embedding table when untied —
    decode gathers ``batch`` rows of it, never the full table."""
    total = tree_nbytes(params)
    if not getattr(cfg, "tie_embeddings", True) and "embed" in params:
        total -= tree_nbytes(params["embed"])
    return total


def roofline_tok_per_sec(step_bytes: int, batch: int) -> float:
    """Decode throughput ceiling at the PHYSICAL (spec) HBM bandwidth; one
    step yields ``batch`` tokens. vs_roofline <= 1 by construction."""
    return batch / (step_bytes / (SPEC_HBM_GBPS * 1e9))


def run_config(preset: str, quant: str, batch: int, isl: int, osl: int,
               decode_steps: int) -> dict:
    from dynamo_tpu.engine.core import EngineConfig, EngineCore
    from dynamo_tpu.engine.runner import ModelRunner
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import PRESETS
    from dynamo_tpu.models.quant import init_params_quantized
    from dynamo_tpu.protocols.common import PreprocessedRequest, SamplingOptions, StopConditions

    cfg = PRESETS[preset]
    # Page 128 is the TPU-idiomatic serving page (JetStream-class stacks use
    # 128-512): each page is one large DMA slab, which the paged-attention
    # kernel needs to stay HBM-bound rather than descriptor-issue-bound
    # (measured: 8.6k tok/s at page 16 -> 11.6k at page 128 on v5e).
    page_size = int(os.environ.get("BENCH_PAGE_SIZE", "128"))
    pages_per_seq = (isl + osl) // page_size + 2
    num_pages = batch * pages_per_seq + 8

    t_init = time.perf_counter()
    if quant:
        # Direct-to-int8 random init: an 8B-class bf16 tree would OOM the
        # chip before quantize_params could shrink it.
        params = init_params_quantized(cfg, 0, mode=quant)
    else:
        params = llama.init_params(cfg, 0)
    weight_bytes = tree_nbytes(params)
    runner_kw = {}
    if os.environ.get("BENCH_KV_DTYPE"):
        import jax.numpy as jnp

        runner_kw["cache_dtype"] = jnp.dtype(os.environ["BENCH_KV_DTYPE"])
    runner = ModelRunner(
        cfg, params, num_pages=num_pages, page_size=page_size,
        max_batch_size=batch, prefill_bucket=max(isl, 64), **runner_kw,
    )
    core = EngineCore(
        runner,
        EngineConfig(
            num_pages=num_pages, page_size=page_size, max_batch_size=batch,
            # Prefill-batch budget per step: every step pays a fixed
            # dispatch cost, so TTFT at moderate concurrency is minimized by
            # packing many prompts per step.
            max_prefill_tokens=int(os.environ.get("BENCH_MAX_PREFILL", isl * 32)),
            max_seq_len=isl + osl + 8,
            enable_prefix_caching=False,  # uniform-random prompts: raw decode
            decode_steps=decode_steps,
        ),
    )

    rng = np.random.default_rng(0)
    for _ in range(batch):
        prompt = rng.integers(1, cfg.vocab_size - 1, size=isl).tolist()
        core.add_request(PreprocessedRequest(
            token_ids=prompt,
            sampling=SamplingOptions(temperature=0.0),
            stop=StopConditions(max_tokens=osl, ignore_eos=True),
        ))

    # Warmup: prefills + enough decode dispatches to compile the burst
    # programs (the pipelined path returns the first burst one step late).
    while core.waiting:
        core.step()
    for _ in range(2):
        core.step()
    compile_s = time.perf_counter() - t_init

    start = time.perf_counter()
    generated = 0
    while core.has_work:
        outputs = core.step()
        generated += sum(len(o.token_ids) for _, o in outputs)
    elapsed = time.perf_counter() - start
    tok_per_sec = generated / elapsed if elapsed > 0 else 0.0

    # -- TTFT phase (IDLE-ENGINE BEST CASE: decode batch has drained; the
    # under-load number comes from the pareto harness) -------------------
    ttft_batch = min(batch, int(os.environ.get("BENCH_TTFT_CONCURRENCY", "32")))
    prompts = [rng.integers(1, cfg.vocab_size - 1, size=isl).tolist()
               for _ in range(ttft_batch)]
    submitted: dict[int, float] = {}
    for prompt in prompts:
        seq = core.add_request(PreprocessedRequest(
            token_ids=prompt,
            sampling=SamplingOptions(temperature=0.0),
            stop=StopConditions(max_tokens=1, ignore_eos=True),
        ))
        submitted[id(seq)] = time.perf_counter()
    first_seen: dict[int, float] = {}
    while core.has_work and len(first_seen) < ttft_batch:
        outputs = core.step()
        now = time.perf_counter()
        for seq, out in outputs:
            if id(seq) not in first_seen and out.token_ids:
                first_seen[id(seq)] = now - submitted[id(seq)]
    ttfts = sorted(first_seen.values())

    def pct(p: float) -> float:
        return ttfts[min(len(ttfts) - 1, int(p * len(ttfts)))] if ttfts else 0.0

    slo_ttft_s = float(os.environ.get("BENCH_SLO_TTFT_MS", "500")) / 1e3
    slo_attainment = (
        sum(1 for t in ttfts if t <= slo_ttft_s) / len(ttfts) if ttfts else 0.0
    )

    cache_itemsize = np.dtype(runner.k_cache.dtype).itemsize
    step_bytes = decode_step_bytes(params, cfg, batch, isl, osl, page_size,
                                   cache_itemsize)
    roofline = roofline_tok_per_sec(step_bytes, batch)
    # Achieved bandwidth: modeled bytes over MEASURED time — the honest
    # utilization number (modeled bytes are a floor on real traffic, so
    # utilization is a floor too).
    steps = generated / batch
    achieved_gbps = step_bytes * steps / elapsed / 1e9 if elapsed > 0 else 0.0
    target = ANCHOR_TOK_PER_SEC.get(preset, 0.0)
    return {
        "preset": preset, "quant": quant or "bf16", "batch": batch,
        "isl": isl, "osl": osl, "decode_steps": decode_steps,
        "tok_per_sec": round(tok_per_sec, 2),
        "decode_tokens": generated, "seconds": round(elapsed, 3),
        "weights_gb": round(weight_bytes / 2**30, 2),
        "modeled_step_bytes": step_bytes,  # raw bytes: no GB/GiB ambiguity
        "hbm_gbps_achieved": round(achieved_gbps, 1),
        "hbm_utilization": round(achieved_gbps / SPEC_HBM_GBPS, 4),
        "roofline_tok_per_sec": round(roofline, 1),
        "vs_roofline": round(tok_per_sec / roofline, 4) if roofline else 0.0,
        "target": round(target, 1),
        "target_kind": ("north_star_proxy" if preset == "llama-3.2-1b"
                        else "fixed_r4_anchor" if target else "none"),
        "vs_target": round(tok_per_sec / target, 4) if target else 0.0,
        "ttft_idle_p50_ms": round(pct(0.50) * 1e3, 1),
        "ttft_idle_p99_ms": round(pct(0.99) * 1e3, 1),
        "ttft_concurrency": ttft_batch,
        "compile_s": round(compile_s, 1),
        # SLO-conditioned headline (the north star is goodput AT the latency
        # target, not raw throughput): fraction of measured TTFTs within the
        # p50 target, and throughput discounted by it.
        "slo_ttft_ms": round(slo_ttft_s * 1e3, 1),
        "slo_ttft_attainment": round(slo_attainment, 4),
        "goodput_tokens_per_s_at_slo": round(tok_per_sec * slo_attainment, 2),
    }


def probe_decode_stall() -> dict:
    """Long-prefill-during-decode stall probe (the metric ISSUE 2 targets).

    A small decode batch streams tokens; mid-stream a long prompt arrives.
    Phase-exclusive scheduling (chunk_prefill_tokens=0) runs the whole
    prefill as one step, freezing every decode for its duration; mixed-step
    scheduling bounds the freeze at roughly one chunk-step. Both modes run
    the identical scenario and report:

      max_decode_stall_ms — longest gap between consecutive steps that
        emitted at least one decode token, over the window where the long
        prefill is in flight (plus the surrounding steady decode, whose
        gaps are the per-step floor);
      itl_p99_ms — p99 inter-token latency across the decode streams.

    Each mode runs the scenario TWICE on the same engine and reports the
    second pass: the step-bucket lattice (batch, time, and page-table-width
    buckets) is data-dependent, so the only warm-up that provably compiles
    every shape the measurement hits is an identical dry run.

    The chunked run's numbers are promoted to stable top-level bench JSON
    keys; detail.stall_probe carries both runs and the stall ratio.
    """
    import jax

    from dynamo_tpu.engine.core import EngineConfig, EngineCore
    from dynamo_tpu.engine.runner import ModelRunner
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import PRESETS
    from dynamo_tpu.protocols.common import PreprocessedRequest, SamplingOptions, StopConditions

    preset = os.environ.get("BENCH_STALL_PRESET", "llama-3.2-1b")
    n_decode = int(os.environ.get("BENCH_STALL_DECODERS", "8"))
    short_isl = int(os.environ.get("BENCH_STALL_ISL", "128"))
    osl = int(os.environ.get("BENCH_STALL_OSL", "192"))
    long_isl = int(os.environ.get("BENCH_STALL_PREFILL_ISL", "3072"))
    chunk = int(os.environ.get("BENCH_STALL_CHUNK", "512"))
    cfg = PRESETS[preset]
    page_size = int(os.environ.get("BENCH_PAGE_SIZE", "128"))
    num_pages = (n_decode * ((short_isl + osl) // page_size + 2)
                 + long_isl // page_size + 12)
    params = llama.init_params(cfg, 0)

    def run(chunk_tokens: int) -> dict:
        runner = ModelRunner(
            cfg, params, num_pages=num_pages, page_size=page_size,
            max_batch_size=n_decode + 2, prefill_bucket=max(long_isl, 64),
        )
        core = EngineCore(runner, EngineConfig(
            num_pages=num_pages, page_size=page_size,
            max_batch_size=n_decode + 2, max_prefill_tokens=long_isl,
            max_seq_len=long_isl + osl + 8, enable_prefix_caching=False,
            decode_steps=1, chunk_prefill_tokens=chunk_tokens,
        ))
        rng = np.random.default_rng(1)

        def scenario() -> dict:
            decoders = []
            for _ in range(n_decode):
                decoders.append(core.add_request(PreprocessedRequest(
                    token_ids=rng.integers(1, cfg.vocab_size - 1, size=short_isl).tolist(),
                    sampling=SamplingOptions(temperature=0.0),
                    stop=StopConditions(max_tokens=osl, ignore_eos=True),
                )))
            while core.waiting or core.prefilling:
                core.step()
            decode_ids = {id(s) for s in decoders}
            emit_times: list[float] = []
            per_seq: dict[int, list[float]] = {id(s): [] for s in decoders}
            injected = False
            steps = 0
            while core.has_work:
                if not injected and steps >= 4:
                    core.add_request(PreprocessedRequest(
                        token_ids=rng.integers(1, cfg.vocab_size - 1, size=long_isl).tolist(),
                        sampling=SamplingOptions(temperature=0.0),
                        stop=StopConditions(max_tokens=4, ignore_eos=True),
                    ))
                    injected = True
                outputs = core.step()
                now = time.perf_counter()
                steps += 1
                got_decode = False
                for seq, out in outputs:
                    if id(seq) in decode_ids and out.token_ids:
                        got_decode = True
                        per_seq[id(seq)].append(now)
                if got_decode:
                    emit_times.append(now)
                if all(s.is_finished for s in decoders):
                    break
            # Drain the injected long prompt so the next pass starts clean.
            while core.has_work:
                core.step()
            gaps = sorted(b - a for a, b in zip(emit_times, emit_times[1:]))
            itls = sorted(b - a for ts in per_seq.values()
                          for a, b in zip(ts, ts[1:]))

            def pct(xs, p):
                return xs[min(len(xs) - 1, int(p * len(xs)))] if xs else 0.0

            return {
                "chunk_prefill_tokens": chunk_tokens,
                "max_decode_stall_ms": round(max(gaps, default=0.0) * 1e3, 2),
                "decode_step_p50_ms": round(pct(gaps, 0.50) * 1e3, 2),
                "itl_p50_ms": round(pct(itls, 0.50) * 1e3, 2),
                "itl_p99_ms": round(pct(itls, 0.99) * 1e3, 2),
                "mixed_steps": core.mixed_steps,
                "stall_violations": core.stall_violations,
                "steps": steps,
            }

        scenario()  # dry run: compiles every bucket the measured pass hits
        return scenario()

    out = {
        "preset": preset, "decoders": n_decode, "short_isl": short_isl,
        "osl": osl, "long_isl": long_isl, "backend": jax.default_backend(),
    }
    chunked = run(chunk)
    gc.collect()
    baseline = run(0)
    gc.collect()
    out["chunked"] = chunked
    out["baseline_phase_exclusive"] = baseline
    out["stall_ratio_baseline_over_chunked"] = round(
        baseline["max_decode_stall_ms"] / chunked["max_decode_stall_ms"], 2
    ) if chunked["max_decode_stall_ms"] > 0 else 0.0
    return out


def probe_spec_decode() -> dict:
    """Speculative-decoding probe: lossless n-gram drafting vs plain decode.

    Runs the identical repetitive-prompt decode scenario twice — spec_k=0
    (plain mixed steps) and spec_k=K (draft + batched verify) — and reports
    per-mode decode throughput plus the drafter's acceptance rate from the
    engine's own counters. Prompts tile a short token pattern so the
    prompt-lookup drafter has structure to match (the regime speculative
    decoding targets; uniform-random text pins acceptance near zero and
    the probe would only measure verify overhead).

    Like the stall probe, each mode runs the scenario twice on one engine
    and reports the second pass: the verify dispatch adds a (verify_width,
    lp_k) axis to the step-bucket lattice, so only an identical dry run
    provably compiles every shape the measurement hits.

    Top-level bench JSON promotes ``spec_accept_rate`` (accepted/proposed
    draft tokens, measured pass) and ``spec_decode_speedup`` (spec tok/s
    over baseline tok/s; >1 means drafting paid for its verify overhead).
    """
    import jax

    from dynamo_tpu.engine.core import EngineConfig, EngineCore
    from dynamo_tpu.engine.runner import ModelRunner
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import PRESETS
    from dynamo_tpu.protocols.common import PreprocessedRequest, SamplingOptions, StopConditions

    preset = os.environ.get("BENCH_SPEC_PRESET", "llama-3.2-1b")
    spec_k = int(os.environ.get("BENCH_SPEC_K", "4"))
    batch = int(os.environ.get("BENCH_SPEC_BATCH", "8"))
    isl = int(os.environ.get("BENCH_SPEC_ISL", "128"))
    osl = int(os.environ.get("BENCH_SPEC_OSL", "128"))
    chunk = int(os.environ.get("BENCH_SPEC_CHUNK", "512"))
    cfg = PRESETS[preset]
    page_size = int(os.environ.get("BENCH_PAGE_SIZE", "128"))
    num_pages = batch * ((isl + osl) // page_size + 2) + 8
    params = llama.init_params(cfg, 0)
    rng = np.random.default_rng(2)
    pattern = rng.integers(1, cfg.vocab_size - 1, size=16).tolist()
    prompts = []
    for i in range(batch):
        # Rotate the shared pattern per request so rows aren't identical
        # but every prompt is still periodic (drafter-matchable).
        rot = pattern[i % len(pattern):] + pattern[:i % len(pattern)]
        prompts.append((rot * (isl // len(rot) + 1))[:isl])

    def run(k: int) -> dict:
        runner = ModelRunner(
            cfg, params, num_pages=num_pages, page_size=page_size,
            max_batch_size=batch, prefill_bucket=max(isl, 64),
        )
        core = EngineCore(runner, EngineConfig(
            num_pages=num_pages, page_size=page_size, max_batch_size=batch,
            max_prefill_tokens=isl * batch, max_seq_len=isl + osl + 8,
            enable_prefix_caching=False, chunk_prefill_tokens=chunk,
            spec_k=k,
        ))

        def scenario() -> dict:
            for prompt in prompts:
                core.add_request(PreprocessedRequest(
                    token_ids=prompt,
                    sampling=SamplingOptions(temperature=0.0),
                    stop=StopConditions(max_tokens=osl, ignore_eos=True),
                ))
            while core.waiting or core.prefilling:
                core.step()
            p0, a0 = core.spec_tokens_proposed, core.spec_tokens_accepted
            t0 = time.perf_counter()
            generated = 0
            steps = 0
            while core.has_work:
                outputs = core.step()
                generated += sum(len(o.token_ids) for _, o in outputs)
                steps += 1
            elapsed = time.perf_counter() - t0
            proposed = core.spec_tokens_proposed - p0
            accepted = core.spec_tokens_accepted - a0
            return {
                "spec_k": k,
                "tok_per_sec": round(generated / elapsed, 1) if elapsed > 0 else 0.0,
                "decode_tokens": generated,
                "decode_steps": steps,
                "spec_tokens_proposed": proposed,
                "spec_tokens_accepted": accepted,
                "spec_accept_rate": round(accepted / proposed, 4) if proposed else 0.0,
            }

        scenario()  # dry run: compiles every bucket the measured pass hits
        return scenario()

    out = {
        "preset": preset, "batch": batch, "isl": isl, "osl": osl,
        "backend": jax.default_backend(),
    }
    spec = run(spec_k)
    gc.collect()
    baseline = run(0)
    gc.collect()
    out["spec"] = spec
    out["baseline"] = baseline
    out["spec_accept_rate"] = spec["spec_accept_rate"]
    out["spec_decode_speedup"] = round(
        spec["tok_per_sec"] / baseline["tok_per_sec"], 4
    ) if baseline["tok_per_sec"] > 0 else 0.0
    return out


def probe_decode_kernel(*, interpret: bool = False) -> dict:
    """Raw split-K paged-decode kernel microbench (ISSUE 7).

    Times ``paged_decode_attention`` alone — no engine, no weights — over a
    batch x context grid. Per cell it reports achieved HBM read bandwidth:
    modeled KV bytes (the kernel streams every whole page in each row's
    window, K and V) over measured wall time, a floor on real traffic just
    like the suite's utilization number. The best cell is promoted to the
    stable top-level keys ``decode_kernel_gbps`` / ``decode_roofline_frac``
    (fraction of BENCH_SPEC_HBM_GBPS).

    A bandwidth probe only means something on the chip: off-TPU it refuses
    to run. ``interpret=True`` is for the CPU structure test, which passes
    a tiny geometry through the BENCH_DK_* variables — the key contract
    holds, the numbers are emulation artifacts.
    """
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.ops.pallas_paged import (
        decode_kernel_supported,
        paged_decode_attention,
    )

    if not interpret and jax.default_backend() != "tpu":
        raise RuntimeError(
            f"probe_decode_kernel measures HBM bandwidth and needs a TPU "
            f"(backend is {jax.default_backend()!r})"
        )

    def ints(name: str, default: str) -> list[int]:
        return [int(x) for x in os.environ.get(name, default).split(",") if x]

    batches = ints("BENCH_DK_BATCHES", "1,8,32")
    contexts = ints("BENCH_DK_CONTEXTS", "1024,4096,16384")
    page_size = int(os.environ.get("BENCH_DK_PAGE_SIZE", "128"))
    n_heads = int(os.environ.get("BENCH_DK_HEADS", "32"))
    n_kv = int(os.environ.get("BENCH_DK_KV", "8"))
    head_dim = int(os.environ.get("BENCH_DK_HEAD_DIM", "128"))
    iters = int(os.environ.get("BENCH_DK_ITERS", "32"))
    width = n_kv * head_dim
    itemsize = 2  # bf16 cache
    out: dict = {
        "backend": jax.default_backend(), "interpret": interpret,
        "page_size": page_size, "n_heads": n_heads, "n_kv_heads": n_kv,
        "head_dim": head_dim, "iters": iters,
    }
    if not decode_kernel_supported(n_heads, head_dim, width, 1, interpret=interpret):
        out.update(error="decode kernel unsupported for this geometry",
                   grid=[], decode_kernel_gbps=0.0, decode_roofline_frac=0.0)
        return out

    rng = np.random.default_rng(0)
    grid: list[dict] = []
    best = 0.0
    scale = head_dim ** -0.5
    for batch in batches:
        for ctx in contexts:
            pages = -(-ctx // page_size)
            num_pages = batch * pages + 1  # page 0 is the null page
            k_cache = jnp.asarray(
                rng.standard_normal((num_pages, page_size, width)), jnp.bfloat16)
            v_cache = jnp.asarray(
                rng.standard_normal((num_pages, page_size, width)), jnp.bfloat16)
            tables = jnp.arange(1, num_pages, dtype=jnp.int32).reshape(batch, pages)
            q = jnp.asarray(
                rng.standard_normal((batch, 1, n_heads, head_dim)), jnp.float32)
            positions = jnp.full((batch, 1), ctx - 1, jnp.int32)
            # compile (and, per shape bucket, the only pass interpret gets)
            paged_decode_attention(
                q, k_cache, v_cache, tables, positions,
                scale=scale, interpret=interpret,
            ).block_until_ready()
            t0 = time.perf_counter()
            for _ in range(iters):
                res = paged_decode_attention(
                    q, k_cache, v_cache, tables, positions,
                    scale=scale, interpret=interpret,
                )
            res.block_until_ready()
            dt = time.perf_counter() - t0
            kv_bytes = 2 * batch * pages * page_size * width * itemsize
            gbps = kv_bytes * iters / dt / 1e9 if dt > 0 else 0.0
            best = max(best, gbps)
            grid.append({
                "batch": batch, "context": ctx,
                "kv_bytes_per_call": kv_bytes,
                "us_per_call": round(dt / iters * 1e6, 1),
                "gbytes_per_sec": round(gbps, 6),
                "roofline_frac": round(gbps / SPEC_HBM_GBPS, 4),
            })
            gc.collect()
    out.update(
        grid=grid,
        decode_kernel_gbps=round(best, 6),
        decode_roofline_frac=round(best / SPEC_HBM_GBPS, 6),
    )
    return out


def probe_kv_pull_gbps() -> dict:
    """Device-path KV transfer bandwidth (BASELINE north-star metric).

    Preferred wire: loopback `jax.experimental.transfer` pull of a
    page-stack-sized array (the cross-process NIXL-equivalent). Fallback
    (the PJRT runtime lacks the transfer engine): the in-process device
    path used by DeviceKvTransfer (gather→put→scatter)."""
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.disagg.pull_transport import device_pull_supported, get_transport

    size_mb = int(os.environ.get("BENCH_PULL_MB", "256"))
    stack = jnp.ones((size_mb * 2**20 // 2,), jnp.bfloat16)
    stack.block_until_ready()
    out: dict = {"stack_mb": size_mb}
    if device_pull_supported():
        t = get_transport()
        uuid = t.new_uuid()
        t.offer(uuid, [stack])
        sds = jax.ShapeDtypeStruct(stack.shape, stack.dtype,
                                   sharding=stack.sharding)
        t0 = time.perf_counter()
        [back] = t.pull(t.address(), uuid, [sds])
        back.block_until_ready()
        dt = time.perf_counter() - t0
        t.finish_offer(uuid)
        out.update(wire="transfer_engine_loopback",
                   gbytes_per_sec=round(stack.nbytes / dt / 1e9, 3))
        return out
    # In-process device path: a jitted page-granularity gather permutation —
    # the same read-everything/write-everything HBM operation the
    # DeviceKvTransfer gather/scatter path performs (a same-device
    # device_put can alias without copying, so it would overstate).
    pages = stack.reshape(-1, 128 * 1024 // 2)  # 128 KiB pages
    perm = jnp.asarray(np.random.default_rng(0).permutation(pages.shape[0]))
    # Two labeled numbers:
    # - amortized: iterate INSIDE jit (single dispatch) — raw HBM gather
    #   bandwidth once dispatch latency is amortized;
    # - per dispatch: ONE gather per dispatch — what a single one-shot
    #   transfer sees, dispatch latency included.
    iters = 16
    chain = jax.jit(lambda x, p: jax.lax.fori_loop(0, iters, lambda i, y: y[p], x))
    chain(pages, perm).block_until_ready()  # compile
    t0 = time.perf_counter()
    chain(pages, perm).block_until_ready()
    dt_amortized = time.perf_counter() - t0
    single = jax.jit(lambda x, p: x[p])
    single(pages, perm).block_until_ready()  # compile
    t0 = time.perf_counter()
    single(pages, perm).block_until_ready()
    dt_cold = time.perf_counter() - t0
    out.update(
        wire="in_process_page_gather", iters=iters,
        transfer_engine="unsupported",
        definition=(
            "amortized = iters gathers inside ONE jit dispatch (raw HBM "
            "bandwidth); per_dispatch = one warm, already-compiled gather "
            "per dispatch (includes dispatch latency; NOT the "
            "compile-inclusive 'cold' of kv_wire_cross_process)"
        ),
        amortized_gbytes_per_sec=round(2 * stack.nbytes * iters / dt_amortized / 1e9, 3),
        per_dispatch_gbytes_per_sec=round(2 * stack.nbytes / dt_cold / 1e9, 3),
    )
    return out


def probe_cross_process_wire() -> dict:
    """The packed-bytes TCP wire between the chip process and a separate
    CPU-mesh OS process: the DCN-path prefill->decode number the in-process
    gather can't stand in for (VERDICT r4 item 3a).

    Runs the wire-v3 stream-count x chunk-size sweep (ISSUE 8): entry 0 of
    BENCH_WIRE_STREAMS is the v2 single-stream baseline the headline
    ``speedup_vs_v2`` is measured against."""
    import asyncio

    from dynamo_tpu.bench.kv_wire import sweep_cross_process

    pages = int(os.environ.get("BENCH_WIRE_PAGES", "8"))
    iters = int(os.environ.get("BENCH_WIRE_ITERS", "5"))
    chunks = tuple(
        int(c) for c in os.environ.get("BENCH_WIRE_CHUNK", "0").split(",")
    )  # 0 = auto (pages/4)
    stream_counts = tuple(
        int(s) for s in os.environ.get("BENCH_WIRE_STREAMS", "0,1,2,4,8").split(",")
    )
    return asyncio.run(sweep_cross_process(
        pages_per_chain=pages, iters=iters,
        stream_counts=stream_counts, chunk_pages_list=chunks,
    ))


def probe_slo_sched() -> dict:
    """SLO admission-control probe (ISSUE 9): EDF + tenant quotas vs FIFO.

    A mixed-tenant burst on the mock-timed engine (MockRunner realtime:
    scheduling is the production EngineCore, latency is the simulated
    timing model, so the probe isolates *policy*): a heavy tenant submits
    a burst of long prompts first, then latency-sensitive light requests
    arrive behind them. FIFO intake serves the heavy burst head-of-line
    and the light requests blow their TTFT budget; the SLO plane (EDF over
    predicted TTFT + a token-bucket quota on the heavy tenant, heavy
    requests at priority tier 1) admits the light requests first.

    Both modes run the identical scenario and report goodput *under* the
    TTFT budget (tokens from requests whose TTFT met it, per second).
    Top-level bench JSON promotes:

      slo_sched_goodput_gain — EDF-mode goodput over FIFO-mode goodput
        (>1 means the plane converted the same capacity into more
        SLO-attaining tokens);
      slo_sched_ttft_p99_ms — p99 TTFT of the tier-0 (light) requests
        under the SLO plane.
    """
    from dynamo_tpu.engine.core import EngineConfig, EngineCore
    from dynamo_tpu.mocker import MockRunner
    from dynamo_tpu.protocols.common import PreprocessedRequest, SamplingOptions, StopConditions
    from dynamo_tpu.sched import (
        AdmissionConfig, AdmissionController, TenantQuota, TenantRegistry, TtftPredictor,
    )

    n_heavy = int(os.environ.get("BENCH_SLOSCHED_HEAVY", "4"))
    heavy_isl = int(os.environ.get("BENCH_SLOSCHED_HEAVY_ISL", "2048"))
    n_light = int(os.environ.get("BENCH_SLOSCHED_LIGHT", "16"))
    light_isl = int(os.environ.get("BENCH_SLOSCHED_LIGHT_ISL", "128"))
    osl = int(os.environ.get("BENCH_SLOSCHED_OSL", "32"))
    ttft_slo_ms = float(os.environ.get("BENCH_SLOSCHED_TTFT_MS", "250"))
    chunk = int(os.environ.get("BENCH_SLOSCHED_CHUNK", "512"))
    page_size = 16
    num_pages = (n_heavy * (heavy_isl + osl) + n_light * (light_isl + osl)) // page_size + 64
    rng = np.random.default_rng(7)
    heavy_prompts = [rng.integers(1, 31999, size=heavy_isl).tolist() for _ in range(n_heavy)]
    light_prompts = [rng.integers(1, 31999, size=light_isl).tolist() for _ in range(n_light)]

    def run(slo_on: bool) -> dict:
        cfg = EngineConfig(
            num_pages=num_pages, page_size=page_size,
            max_batch_size=n_heavy + n_light, max_prefill_tokens=heavy_isl,
            max_seq_len=heavy_isl + osl + 8, enable_prefix_caching=False,
            chunk_prefill_tokens=chunk,
        )
        runner = MockRunner(num_pages=num_pages, page_size=page_size, realtime=True)
        admission = None
        if slo_on:
            tenants = TenantRegistry()
            # Rate-limit the heavy tenant: the first long prompt borrows the
            # whole bucket, the rest pace in at the refill rate.
            tenants.configure("heavy", TenantQuota(
                rate_tokens_per_s=4 * heavy_isl, burst_tokens=heavy_isl,
            ))
            admission = AdmissionController(
                AdmissionConfig(ttft_budget_s=ttft_slo_ms / 1e3),
                predictor=TtftPredictor(),
                tenants=tenants,
            )
        core = EngineCore(runner, cfg, admission=admission)
        # Heavy burst first (the FIFO head-of-line scenario), lights behind.
        submit: dict[int, float] = {}
        tier0: set[int] = set()
        t0 = time.perf_counter()
        for prompt in heavy_prompts:
            seq = core.add_request(PreprocessedRequest(
                token_ids=prompt, sampling=SamplingOptions(temperature=0.0),
                stop=StopConditions(max_tokens=osl, ignore_eos=True),
                tenant_id="heavy", priority=1,
            ))
            submit[seq.seq_id] = time.perf_counter()
        for prompt in light_prompts:
            seq = core.add_request(PreprocessedRequest(
                token_ids=prompt, sampling=SamplingOptions(temperature=0.0),
                stop=StopConditions(max_tokens=osl, ignore_eos=True),
            ))
            submit[seq.seq_id] = time.perf_counter()
            tier0.add(seq.seq_id)
        first_tok: dict[int, float] = {}
        done_tokens: dict[int, int] = {}
        while core.has_work:
            for seq, out in core.step():
                now = time.perf_counter()
                if out.token_ids and seq.seq_id not in first_tok:
                    first_tok[seq.seq_id] = now
                done_tokens[seq.seq_id] = out.cumulative_tokens
        elapsed = time.perf_counter() - t0
        ttfts = {
            sid: first_tok[sid] - submit[sid] for sid in first_tok
        }
        met = {sid for sid, t in ttfts.items() if t * 1e3 <= ttft_slo_ms}
        goodput = sum(done_tokens.get(sid, 0) for sid in met) / elapsed if elapsed > 0 else 0.0
        light_ttfts = sorted(t for sid, t in ttfts.items() if sid in tier0)

        def pct(xs, p):
            return xs[min(len(xs) - 1, int(p * len(xs)))] if xs else 0.0

        return {
            "mode": "slo_sched" if slo_on else "fifo",
            "elapsed_s": round(elapsed, 3),
            "requests_met_ttft": len(met),
            "requests_total": len(submit),
            "goodput_tokens_per_s": round(goodput, 1),
            "light_ttft_p50_ms": round(pct(light_ttfts, 0.50) * 1e3, 2),
            "light_ttft_p99_ms": round(pct(light_ttfts, 0.99) * 1e3, 2),
            "deadline_misses": admission.deadline_misses if admission else 0,
            "throttle_events": admission.throttle_events if admission else 0,
            "tenant_throttled": dict(admission.tenants.throttled) if admission else {},
        }

    fifo = run(False)
    gc.collect()
    edf = run(True)
    gc.collect()
    return {
        "ttft_slo_ms": ttft_slo_ms,
        "heavy": {"n": n_heavy, "isl": heavy_isl},
        "light": {"n": n_light, "isl": light_isl},
        "osl": osl,
        "fifo": fifo,
        "slo_sched": edf,
        "slo_sched_goodput_gain": round(
            edf["goodput_tokens_per_s"] / fifo["goodput_tokens_per_s"], 4
        ) if fifo["goodput_tokens_per_s"] > 0 else 0.0,
        "slo_sched_ttft_p99_ms": edf["light_ttft_p99_ms"],
    }


def probe_engine_overlap() -> dict:
    """Overlapped-execution probe (ISSUE 10): DYN_OVERLAP off vs on.

    Identical decode-heavy work on the mock-timed engine (MockRunner
    realtime with a nonzero d2h latency — the blocking device->host result
    copy the overlapped loop exists to hide). The synchronous loop pays
    compute + d2h per token; the depth-1 pipeline dispatches step N+1 with
    device-chained input tokens before harvesting step N, so per-token wall
    collapses toward max(compute, d2h). Both modes run the same scenario and
    the probe asserts the token streams are identical. Top-level bench JSON
    promotes:

      engine_overlap_itl_gain — sync-mode mean ITL over overlap-mode mean
        ITL (>1 means overlap shortened the decode critical path);
      device_idle_frac — fraction of overlap-mode wall time the simulated
        device spent idle (strictly below the sync mode's).
    """
    from dynamo_tpu.engine.core import EngineConfig, EngineCore
    from dynamo_tpu.mocker import MockRunner
    from dynamo_tpu.protocols.common import PreprocessedRequest, SamplingOptions, StopConditions

    decoders = int(os.environ.get("BENCH_OVERLAP_DECODERS", "4"))
    isl = int(os.environ.get("BENCH_OVERLAP_ISL", "32"))
    osl = int(os.environ.get("BENCH_OVERLAP_OSL", "64"))
    decode_us = float(os.environ.get("BENCH_OVERLAP_DECODE_US", "2000"))
    d2h_us = float(os.environ.get("BENCH_OVERLAP_D2H_US", "1500"))
    page_size = 16
    num_pages = decoders * (isl + osl) // page_size + 32
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, 31999, size=isl).tolist() for _ in range(decoders)]

    def run(overlap_on: bool) -> tuple[dict, dict[int, list[int]]]:
        cfg = EngineConfig(
            num_pages=num_pages, page_size=page_size, max_batch_size=decoders,
            max_prefill_tokens=isl, max_seq_len=isl + osl + 8,
            enable_prefix_caching=False, chunk_prefill_tokens=0,
            overlap=overlap_on,
        )
        runner = MockRunner(
            num_pages=num_pages, page_size=page_size, realtime=True,
            decode_us_base=decode_us, d2h_us=d2h_us,
        )
        core = EngineCore(runner, cfg)
        for prompt in prompts:
            core.add_request(PreprocessedRequest(
                token_ids=prompt, sampling=SamplingOptions(temperature=0.0),
                stop=StopConditions(max_tokens=osl, ignore_eos=True),
            ))
        tokens: dict[int, list[int]] = {}
        t0 = time.perf_counter()
        while core.has_work:
            for seq, out in core.step():
                tokens.setdefault(seq.seq_id, []).extend(out.token_ids)
        elapsed = time.perf_counter() - t0
        idle_frac = max(0.0, 1.0 - runner.busy_us / (elapsed * 1e6)) if elapsed > 0 else 0.0
        return {
            "mode": "overlap" if overlap_on else "sync",
            "elapsed_s": round(elapsed, 4),
            "itl_mean_ms": round(elapsed * 1e3 / osl, 3),
            "device_idle_frac": round(idle_frac, 4),
            "overlap_steps": dict(core.overlap_step_counts),
            "mean_gap_ms": round(
                core.step_gap_ms_sum / core.step_gap_ms_count, 3
            ) if core.step_gap_ms_count else 0.0,
        }, tokens

    # Mixed-traffic variant (ISSUE 11): staggered admission + chunked
    # prefill at ISL-3000 scale — the workload where PR 10's pipeline
    # barriered on nearly every step. The chained mixed path must keep the
    # pipeline hot (overlap_chained_frac is the fraction of armed steps
    # that dispatched a chained lookahead) while every stream stays
    # bit-identical to the synchronous engine.
    m_decoders = int(os.environ.get("BENCH_OVERLAP_MIXED_DECODERS", "4"))
    m_isl = int(os.environ.get("BENCH_OVERLAP_MIXED_ISL", "3000"))
    m_osl = int(os.environ.get("BENCH_OVERLAP_MIXED_OSL", "32"))
    m_chunk = int(os.environ.get("BENCH_OVERLAP_MIXED_CHUNK", "512"))
    m_stagger = int(os.environ.get("BENCH_OVERLAP_MIXED_STAGGER", "3"))
    m_pages = m_decoders * (m_isl + m_osl) // page_size + 64
    m_prompts = [
        rng.integers(1, 31999, size=m_isl + 37 * i).tolist()
        for i in range(m_decoders)
    ]

    def run_mixed(overlap_on: bool) -> tuple[dict, dict[int, list[int]]]:
        cfg = EngineConfig(
            num_pages=m_pages, page_size=page_size, max_batch_size=m_decoders,
            max_prefill_tokens=max(m_chunk, m_isl), max_seq_len=m_isl + m_osl + 64,
            enable_prefix_caching=False, chunk_prefill_tokens=m_chunk,
            overlap=overlap_on,
        )
        runner = MockRunner(
            num_pages=m_pages, page_size=page_size, realtime=True,
            decode_us_base=decode_us, d2h_us=d2h_us,
        )
        core = EngineCore(runner, cfg)
        reqs = [PreprocessedRequest(
            token_ids=p, sampling=SamplingOptions(temperature=0.0),
            stop=StopConditions(max_tokens=m_osl, ignore_eos=True),
        ) for p in m_prompts]
        tokens: dict[int, list[int]] = {}
        admitted = 0
        steps = 0
        t0 = time.perf_counter()
        while core.has_work or admitted < len(reqs):
            # Staggered arrivals: a new long prompt lands every few steps,
            # so admission + chunked prefill continuously interleave with
            # the earlier requests' decodes.
            if admitted < len(reqs) and steps >= admitted * m_stagger:
                core.add_request(reqs[admitted])
                admitted += 1
            for seq, out in core.step():
                tokens.setdefault(seq.seq_id, []).extend(out.token_ids)
            steps += 1
        elapsed = time.perf_counter() - t0
        counts = dict(core.overlap_step_counts)
        armed = sum(counts.values())
        # Time-loss ledger coverage (ISSUE 15): the per-cause accounting
        # must explain nearly all non-compute wall (step wall + inter-step
        # gap - device dispatch). Queue/admission waits are pre-step and
        # excluded from the step-side comparison.
        lost = dict(core.lost_time_ms)
        noncompute = max(
            0.0,
            core.step_wall_ms_total + core.step_gap_ms_sum - core.step_dispatch_ms_total,
        )
        step_lost = sum(v for k, v in lost.items() if k not in ("queue", "admission"))
        return {
            "mode": "overlap" if overlap_on else "sync",
            "elapsed_s": round(elapsed, 4),
            "itl_mean_ms": round(elapsed * 1e3 / m_osl, 3),
            "overlap_steps": counts,
            "barrier_reasons": dict(core.overlap_barrier_counts),
            "overlap_chained_frac": round(
                counts.get("overlapped", 0) / armed, 4
            ) if armed else 0.0,
            "lost_time_ms": {k: round(v, 3) for k, v in sorted(lost.items())},
            "noncompute_wall_ms": round(noncompute, 3),
            "loss_coverage_frac": round(
                min(1.0, step_lost / noncompute), 4) if noncompute > 0 else 1.0,
        }, tokens

    # Constrained-traffic variant (ISSUE 14): JSON-mode rows under overlap.
    # Without mask lookahead every chained constrained row forces a barrier
    # (reason "constraint": the next step's token mask depends on the
    # not-yet-harvested sample), degenerating the pipeline to sync timing.
    # With lookahead the scheduler pre-builds masks for every admissible
    # successor state and resolves the right one in-graph against the
    # chained token; only cold-cache steps barrier ("constraint_miss")
    # while the mask cache warms. Baseline here is overlap ON with
    # constraint_lookahead_tokens=0, isolating the lookahead itself.
    j_decoders = int(os.environ.get("BENCH_OVERLAP_JSON_DECODERS", "4"))
    j_isl = int(os.environ.get("BENCH_OVERLAP_JSON_ISL", "32"))
    j_osl = int(os.environ.get("BENCH_OVERLAP_JSON_OSL", "48"))
    j_lookahead = int(os.environ.get("BENCH_OVERLAP_JSON_LOOKAHEAD", "32"))
    # Small vocab: the digit tokenizer has 9 distinct pieces, and the pure-
    # Python mask builder walks every id — at 32k ids two cold mask builds
    # cost more than the whole decode and swamp the timing comparison.
    j_vocab = int(os.environ.get("BENCH_OVERLAP_JSON_VOCAB", "512"))
    j_pages = j_decoders * (j_isl + j_osl) // page_size + 32
    j_prompts = [rng.integers(1, j_vocab - 2, size=j_isl).tolist()
                 for _ in range(j_decoders)]

    class _DigitTokenizer:
        """Nine-piece vocabulary: every token id decodes to a nonzero digit,
        so each sampled token extends a JSON number forever — the adversarial
        case where a fresh mask must be ready before every decode step."""

        def decode(self, ids, skip_special_tokens=False):
            return "".join("123456789"[int(t) % 9] for t in ids)

    def run_json(lookahead: int) -> tuple[dict, dict[int, list[int]]]:
        cfg = EngineConfig(
            num_pages=j_pages, page_size=page_size, max_batch_size=j_decoders,
            max_prefill_tokens=j_isl, max_seq_len=j_isl + j_osl + 8,
            enable_prefix_caching=False, chunk_prefill_tokens=0,
            overlap=True, constraint_lookahead_tokens=lookahead,
        )
        runner = MockRunner(
            num_pages=j_pages, page_size=page_size, realtime=True,
            vocab_size=j_vocab, decode_us_base=decode_us, d2h_us=d2h_us,
        )
        core = EngineCore(runner, cfg)
        core.set_constraint_tokenizer(_DigitTokenizer())
        for prompt in j_prompts:
            core.add_request(PreprocessedRequest(
                token_ids=prompt,
                sampling=SamplingOptions(temperature=0.0, json_mode=True),
                stop=StopConditions(max_tokens=j_osl, ignore_eos=True),
            ))
        tokens: dict[int, list[int]] = {}
        t0 = time.perf_counter()
        while core.has_work:
            for seq, out in core.step():
                tokens.setdefault(seq.seq_id, []).extend(out.token_ids)
        elapsed = time.perf_counter() - t0
        counts = dict(core.overlap_step_counts)
        armed = sum(counts.values())
        return {
            "mode": f"lookahead_{lookahead}" if lookahead else "no_lookahead",
            "elapsed_s": round(elapsed, 4),
            "itl_mean_ms": round(elapsed * 1e3 / j_osl, 3),
            "overlap_steps": counts,
            "barrier_reasons": dict(core.overlap_barrier_counts),
            "overlap_barrier_frac": round(
                counts.get("barrier", 0) / armed, 4
            ) if armed else 0.0,
            "mask_cache_hits": core.constraint_mask_cache_hits,
            "mask_cache_misses": core.constraint_mask_cache_misses,
        }, tokens

    sync, sync_tokens = run(False)
    gc.collect()
    overlap, overlap_tokens = run(True)
    gc.collect()
    m_sync, m_sync_tokens = run_mixed(False)
    gc.collect()
    m_overlap, m_overlap_tokens = run_mixed(True)
    gc.collect()
    j_base, j_base_tokens = run_json(0)
    gc.collect()
    j_la, j_la_tokens = run_json(j_lookahead)
    gc.collect()
    return {
        "decoders": decoders, "isl": isl, "osl": osl,
        "decode_us": decode_us, "d2h_us": d2h_us,
        "sync": sync,
        "overlap": overlap,
        "bit_identical": sync_tokens == overlap_tokens,
        "engine_overlap_itl_gain": round(
            sync["itl_mean_ms"] / overlap["itl_mean_ms"], 4
        ) if overlap["itl_mean_ms"] > 0 else 0.0,
        "device_idle_frac": overlap["device_idle_frac"],
        "mixed": {
            "decoders": m_decoders, "isl": m_isl, "osl": m_osl,
            "chunk": m_chunk, "stagger_steps": m_stagger,
            "sync": m_sync,
            "overlap": m_overlap,
            "bit_identical": m_sync_tokens == m_overlap_tokens,
        },
        "overlap_chained_frac": m_overlap["overlap_chained_frac"],
        "loss_coverage_frac": m_overlap["loss_coverage_frac"],
        "engine_overlap_mixed_itl_gain": round(
            m_sync["itl_mean_ms"] / m_overlap["itl_mean_ms"], 4
        ) if m_overlap["itl_mean_ms"] > 0 else 0.0,
        "constrained": {
            "decoders": j_decoders, "isl": j_isl, "osl": j_osl,
            "lookahead": j_lookahead,
            "no_lookahead": j_base,
            "lookahead_on": j_la,
            "bit_identical": j_base_tokens == j_la_tokens,
        },
        "overlap_constrained_itl_gain": round(
            j_base["itl_mean_ms"] / j_la["itl_mean_ms"], 4
        ) if j_la["itl_mean_ms"] > 0 else 0.0,
        "overlap_barrier_frac": j_la["overlap_barrier_frac"],
    }


def probe_prefix_reuse() -> dict:
    """Cache-aware serving probe (ISSUE 12): KV-tier reuse on vs off.

    A prefix-heavy workload from the synthesizer (shared system prompt +
    per-group few-shot prefixes + unique tails) replayed open-loop at fixed
    QPS on the mock-timed engine. The warm pass runs one prefix-covering
    request per group and write-through offloads their committed pages into
    a G2 host tier whose reads carry a simulated per-block latency; the G1
    prefix cache is then cleared, so every replay hit must come back
    through async tier onboarding (DYN_ASYNC_ONBOARD path: background
    fetch + batched write_pages landing, overlapped with other rows'
    prefill/decode compute). The reuse-off pass replays the identical
    arrival schedule with prefix caching disabled. Top-level bench JSON
    promotes:

      prefix_reuse_ttft_gain — reuse-off TTFT p50 over reuse-on TTFT p50
        at the same fixed QPS (>1 means tier reuse shortened time to first
        token);
      prefix_onboard_overlap_frac — fraction of engine steps with an
        onboarding session in flight that still dispatched fresh work
        (tier fetch demonstrably overlapped with compute, not stalled).
    """
    from dynamo_tpu.bench.synthesizer import SyntheticConfig, synthesize
    from dynamo_tpu.blocks import BlockManagerConfig, KvBlockManager
    from dynamo_tpu.blocks.storage import HostStorage
    from dynamo_tpu.engine.core import EngineConfig, EngineCore
    from dynamo_tpu.mocker import MockRunner
    from dynamo_tpu.protocols.common import PreprocessedRequest, SamplingOptions, StopConditions

    groups = int(os.environ.get("BENCH_PREFIXREUSE_GROUPS", "4"))
    n_requests = int(os.environ.get("BENCH_PREFIXREUSE_REQUESTS", "16"))
    shared_isl = int(os.environ.get("BENCH_PREFIXREUSE_SHARED_ISL", "512"))
    group_isl = int(os.environ.get("BENCH_PREFIXREUSE_GROUP_ISL", "256"))
    unique_isl = int(os.environ.get("BENCH_PREFIXREUSE_UNIQUE_ISL", "64"))
    osl = int(os.environ.get("BENCH_PREFIXREUSE_OSL", "16"))
    qps = float(os.environ.get("BENCH_PREFIXREUSE_QPS", "40"))
    chunk = int(os.environ.get("BENCH_PREFIXREUSE_CHUNK", "256"))
    fetch_us = float(os.environ.get("BENCH_PREFIXREUSE_FETCH_US", "100"))
    page_size = 16
    isl = shared_isl + group_isl + unique_isl
    num_pages = n_requests * ((isl + osl) // page_size + 2) + 64

    workload = synthesize(SyntheticConfig(
        num_requests=n_requests, shared_prefix_len=shared_isl,
        num_groups=groups, group_prefix_len=group_isl, unique_len=unique_isl,
        osl_mean=osl, osl_cv=0.0, vocab=31999, seed=5,
    ))
    prefix_len = (shared_isl + group_isl) // page_size * page_size
    warm_prompts = {}  # group -> prefix-only prompt (page-aligned)
    for req in workload:
        warm_prompts.setdefault(req.group, req.token_ids[:prefix_len])

    class SlowHostStorage(HostStorage):
        """G2 payload reads pay a simulated tier latency — the window the
        async onboarding session exists to hide under compute."""

        def read(self, block_hash):
            payload = super().read(block_hash)
            if payload is not None and fetch_us > 0:
                time.sleep(fetch_us / 1e6)
            return payload

        def exists(self, block_hash):  # membership probes stay cheap
            return block_hash in self._data

    def run(reuse_on: bool) -> dict:
        cfg = EngineConfig(
            num_pages=num_pages, page_size=page_size,
            max_batch_size=n_requests, max_prefill_tokens=isl,
            max_seq_len=isl + osl + 8, chunk_prefill_tokens=chunk,
            enable_prefix_caching=reuse_on, async_onboard=reuse_on,
        )
        runner = MockRunner(num_pages=num_pages, page_size=page_size, realtime=True)
        bm = None
        if reuse_on:
            bm = KvBlockManager(
                BlockManagerConfig(g2_capacity_blocks=4096),
                read_page=runner.read_page, write_page=runner.write_page,
                write_pages=runner.write_pages, g2_storage=SlowHostStorage(),
            )
        core = EngineCore(runner, cfg, block_manager=bm)
        if reuse_on:
            # Warm pass: commit each group's shared prefix and write it
            # through to G2, then drop G1 — replay reuse must onboard.
            for prompt in warm_prompts.values():
                core.add_request(PreprocessedRequest(
                    token_ids=prompt, sampling=SamplingOptions(temperature=0.0),
                    stop=StopConditions(max_tokens=2, ignore_eos=True),
                ))
            while core.has_work:
                core.step()
                core.flush_offloads()
            core.allocator.clear_cache()
        submit: dict[int, float] = {}
        first: dict[int, float] = {}
        arrivals = [i / qps for i in range(len(workload))]
        i = 0
        t0 = time.perf_counter()
        while core.has_work or i < len(workload):
            now = time.perf_counter() - t0
            while i < len(workload) and now >= arrivals[i]:
                seq = core.add_request(PreprocessedRequest(
                    token_ids=workload[i].token_ids,
                    sampling=SamplingOptions(temperature=0.0),
                    stop=StopConditions(max_tokens=workload[i].max_tokens,
                                        ignore_eos=True),
                ))
                submit[seq.seq_id] = time.perf_counter()
                i += 1
            if not core.has_work:
                if i < len(workload):  # open-loop: idle until next arrival
                    time.sleep(max(0.0, arrivals[i] - (time.perf_counter() - t0)))
                continue
            for seq, out in core.step():
                if out.token_ids and seq.seq_id not in first:
                    first[seq.seq_id] = time.perf_counter()
            core.flush_offloads()
        elapsed = time.perf_counter() - t0
        ttfts = sorted(first[sid] - submit[sid] for sid in first)

        def pct(xs, p):
            return xs[min(len(xs) - 1, int(p * len(xs)))] if xs else 0.0

        ob_steps = core.onboard_overlap_steps + core.onboard_stall_steps
        return {
            "mode": "reuse" if reuse_on else "cold",
            "elapsed_s": round(elapsed, 3),
            "ttft_p50_ms": round(pct(ttfts, 0.50) * 1e3, 2),
            "ttft_p99_ms": round(pct(ttfts, 0.99) * 1e3, 2),
            "onboard_sessions": core.onboard_sessions,
            "onboard_pages_by_tier": dict(core.onboard_page_counts),
            "onboard_shortfall_pages": core.onboard_shortfall_pages,
            "onboard_overlap_steps": core.onboard_overlap_steps,
            "onboard_stall_steps": core.onboard_stall_steps,
            "onboard_overlap_frac": round(
                core.onboard_overlap_steps / ob_steps, 4) if ob_steps else 0.0,
            "onboard_wait_ms_mean": round(
                core.onboard_wait_ms_sum / core.onboard_wait_count, 3
            ) if core.onboard_wait_count else 0.0,
            "cached_frac_last": core.last_admission.get("cached_frac", 0.0),
        }

    cold = run(False)
    gc.collect()
    reuse = run(True)
    gc.collect()
    return {
        "groups": groups, "requests": n_requests, "qps": qps,
        "isl": {"shared": shared_isl, "group": group_isl, "unique": unique_isl},
        "osl": osl, "fetch_us_per_block": fetch_us,
        "cold": cold,
        "reuse": reuse,
        "prefix_reuse_ttft_gain": round(
            cold["ttft_p50_ms"] / reuse["ttft_p50_ms"], 4
        ) if reuse["ttft_p50_ms"] > 0 else 0.0,
        "prefix_onboard_overlap_frac": reuse["onboard_overlap_frac"],
    }


def probe_fleet_sim() -> dict:
    """Fleet-simulation probe (ISSUE 13): a small fixed scenario end-to-end.

    Runs a registered fleetsim scenario (default ``smoke``: a deterministic
    Poisson trace replayed open-loop against the real frontend/router/store
    with mock workers as OS processes) twice — a dry run that generates and
    digests the trace without spawning anything, then the measured run.
    Top-level bench JSON promotes:

      fleet_goodput_frac_at_slo — fraction of the scenario's requests that
        attained the SLO (TTFT and per-request p99 ITL within targets),
        with TTFT clocked from intended injection time (open loop, no
        coordinated omission);
      fleet_tenant_fairness — min/max ratio of per-tenant attainment
        fractions (1.0 = perfectly fair).
    """
    import asyncio

    from dynamo_tpu.fleetsim.scenario import SCENARIOS, run_scenario

    name = os.environ.get("BENCH_FLEET_SCENARIO", "smoke")
    workers = int(os.environ.get("BENCH_FLEET_WORKERS", "0"))
    scn = SCENARIOS[name]
    dry = asyncio.run(run_scenario(scn, dry_run=True))
    report = asyncio.run(run_scenario(scn, workers_override=workers))
    return {
        "scenario": name,
        "trace_digest": dry["trace"]["digest"],
        "trace_events": dry["trace"]["events"],
        "digest_stable": dry["trace"]["digest"] == report["trace"]["digest"],
        "duration_s": report.get("duration_s", 0.0),
        "requests": report.get("requests", {}),
        "ttft_ms": report.get("ttft_ms", {}),
        "itl_ms": report.get("itl_ms", {}),
        "fleet": report.get("fleet", {}),
        "passed": report.get("passed"),
        "fleet_goodput_frac_at_slo": report.get("goodput_frac_at_slo", 0.0),
        "fleet_tenant_fairness": report.get("tenant_fairness", 0.0),
    }


def probe_quant_sweep() -> dict:
    """Quant-mode sweep (ISSUE 16): one shape, bf16 vs int8 vs int4.

    Runs the 8b proxy at an identical (batch, isl, osl) across the three
    weight formats so the bench trajectory captures the decode roofline
    burn-down directly. Top-level bench JSON promotes:

      quant_int8_decode_gain — int8 decode tok/s over the bf16 baseline
      quant_int4_decode_gain — int4 decode tok/s over the bf16 baseline
      quant_int4_vs_int8_decode_gain — int4 over int8, both measured

    The bf16 leg of an 8B-class proxy does not fit a 16 GB chip; when it
    OOMs, the baseline falls back to a bandwidth-modeled figure (the int4
    run's MEASURED achieved GB/s against the bf16 step's modeled bytes)
    and ``bf16_basis`` says so — on larger-HBM parts all three legs
    measure for real.
    """
    from dynamo_tpu.models.config import PRESETS

    spec = os.environ.get("BENCH_QUANT_SWEEP", "mla-8b-proxy:48:512:64:32")
    f = spec.split(":")
    preset, batch = f[0], int(f[1]) if len(f) > 1 else 48
    isl = int(f[2]) if len(f) > 2 else 512
    osl = int(f[3]) if len(f) > 3 else 64
    steps = int(f[4]) if len(f) > 4 else 32
    cfg = PRESETS[preset]
    modes: dict = {}
    for quant in ("", "int8", "int4"):
        label = quant or "bf16"
        try:
            modes[label] = run_config(preset, quant, batch, isl, osl, steps)
        except Exception as e:  # OOM (bf16 8B on a 16 GB chip) or compile
            modes[label] = {"error": f"{type(e).__name__}: {e}"[:300]}
        gc.collect()

    def tps(label: str) -> float:
        return modes.get(label, {}).get("tok_per_sec", 0.0)

    bf16_basis = "measured"
    bf16_tps = tps("bf16")
    if not bf16_tps and tps("int4"):
        # Model the baseline from the int4 leg's measured bandwidth: same
        # achieved GB/s, bf16-sized step bytes (weights at 2 bytes/elem).
        int4 = modes["int4"]
        bf16_params_bytes = tree_nbytes_modeled_bf16(cfg)
        int4_step = int4["modeled_step_bytes"]
        int4_weight = int4["weights_gb"] * 2**30
        bf16_step = int4_step - int4_weight + bf16_params_bytes
        bf16_tps = int4["hbm_gbps_achieved"] * 1e9 / bf16_step * batch
        bf16_basis = "modeled_from_int4_achieved_bw"
    return {
        "preset": preset, "batch": batch, "isl": isl, "osl": osl,
        "decode_steps": steps, "modes": modes,
        "bf16_basis": bf16_basis,
        "bf16_baseline_tok_per_sec": round(bf16_tps, 2),
        "quant_int8_decode_gain": round(tps("int8") / bf16_tps, 4) if bf16_tps else 0.0,
        "quant_int4_decode_gain": round(tps("int4") / bf16_tps, 4) if bf16_tps else 0.0,
        "quant_int4_vs_int8_decode_gain": round(
            tps("int4") / tps("int8"), 4) if tps("int8") else 0.0,
    }


def tree_nbytes_modeled_bf16(cfg) -> int:
    """Weight bytes of the preset AT bf16 without materializing the tree
    (the whole point is that the bf16 tree may not fit)."""
    import jax

    from dynamo_tpu.models import llama

    shapes = jax.eval_shape(lambda: llama.init_params(cfg, 0))
    return sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(shapes))


def probe_mask_build() -> dict:
    """Constrained-decoding cold-mask-build probe (ISSUE 16).

    Builds masks for a corpus of JSON-machine summaries over a synthetic
    128k-piece vocab with the vectorized builder and the pure-Python one,
    asserting bitwise identity (masks, close budgets, transition
    descriptors). Top-level bench JSON promotes:

      constraint_mask_build_ms — mean vectorized cold-build wall ms
      constraint_mask_build_gain — pure-Python ms over vectorized ms
    """
    import random

    from dynamo_tpu import constrained as C

    vocab = int(os.environ.get("BENCH_MASK_VOCAB", "128000"))
    rnd = random.Random(7)
    chars = list('{}[]",: \t\n0123456789.-+eE') + list(
        "abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ_\\/"
    ) + ["٣", "é", "世", "�"]
    pieces = [""]
    while len(pieces) < vocab:
        n = rnd.choice((1, 1, 2, 3, 4, 5, 6, 8, 12))
        pieces.append("".join(rnd.choice(chars) for _ in range(n)))

    class _Tok:
        def decode(self, ids, skip_special_tokens=False):
            return pieces[ids[0]]

    states = [
        C.advance_text(C.MachineState(), t)
        for t in ("", "{", '{"', '{"k": ', '{"k": "v', '{"k": [1, ', "[1")
    ]
    cache = C.TokenMaskCache(_Tok(), len(pieces), (0,))
    plist = cache._ensure_pieces()
    t0 = time.perf_counter()
    cache._vocab_table()
    table_s = time.perf_counter() - t0
    vec_s = py_s = 0.0
    mismatches = 0
    for st in states:
        key = st.summary()
        t0 = time.perf_counter()
        av, cv = cache._build_mask_vectorized(st, key, plist)
        vec_s += time.perf_counter() - t0
        dv = cache._descs[key]
        t0 = time.perf_counter()
        ap, cp = cache._build_mask_python(st, key, plist)
        py_s += time.perf_counter() - t0
        dp = cache._descs[key]
        if not (np.array_equal(av, ap) and np.array_equal(cv, cp)
                and np.array_equal(dv[0], dp[0]) and dv[1] == dp[1]):
            mismatches += 1
    n = len(states)
    return {
        "vocab": vocab, "summaries": n, "mismatches": mismatches,
        "table_build_ms": round(table_s * 1e3, 1),
        "python_build_ms": round(py_s / n * 1e3, 1),
        "constraint_mask_build_ms": round(vec_s / n * 1e3, 2),
        "constraint_mask_build_gain": round(py_s / vec_s, 1) if vec_s else 0.0,
    }


def build_doc(configs, pull, wire=None, stall=None, spec=None,
              decode_kernel=None, slo_sched=None, overlap=None,
              prefix_reuse=None, fleet=None, quant_sweep=None,
              mask_build=None) -> dict:
    """The bench JSON document (one stdout line per emit).

    Module-level (not a closure) so its top-level key contract — the stable
    serving-quality keys downstream BENCH_*.json tracking reads — is directly
    testable without running the suite.
    """
    import jax

    head = next((c for c in configs if c.get("preset") == "llama-3.2-1b"
                 and "error" not in c), None) or \
        next((c for c in configs if "error" not in c), {})
    return {
        "metric": "output_tokens_per_sec_per_chip",
        "value": head.get("tok_per_sec", 0.0),
        "unit": "tok/s",
        "vs_baseline": round(head.get("tok_per_sec", 0.0) / HEADLINE_TARGET, 4),
        # Stable top-level serving-quality keys (ISSUE 2): from the
        # chunked run of the long-prefill-during-decode stall probe.
        "itl_p99_ms": (stall or {}).get("chunked", {}).get("itl_p99_ms", 0.0),
        "max_decode_stall_ms": (stall or {}).get("chunked", {}).get(
            "max_decode_stall_ms", 0.0),
        # SLO-conditioned headline keys (ISSUE 4): the north-star metric is
        # goodput at p50 TTFT <= 500 ms, so BENCH_*.json tracks it directly.
        "goodput_tokens_per_s_at_slo": head.get("goodput_tokens_per_s_at_slo", 0.0),
        "slo_ttft_attainment": head.get("slo_ttft_attainment", 0.0),
        # Speculative decoding headline keys (ISSUE 6): acceptance rate and
        # spec-over-baseline decode speedup from the spec probe's measured
        # pass (repetitive-prompt scenario, see probe_spec_decode).
        "spec_accept_rate": (spec or {}).get("spec_accept_rate", 0.0),
        "spec_decode_speedup": (spec or {}).get("spec_decode_speedup", 0.0),
        # Decode-kernel headline keys (ISSUE 7): best achieved HBM bandwidth
        # of the raw split-K paged-decode kernel and its roofline fraction
        # (see probe_decode_kernel; meaningless off-TPU but always present).
        "decode_kernel_gbps": (decode_kernel or {}).get("decode_kernel_gbps", 0.0),
        "decode_roofline_frac": (decode_kernel or {}).get("decode_roofline_frac", 0.0),
        # KV-wire headline keys (ISSUE 8): best amortized cross-process wire
        # bandwidth from the stream-count x chunk-size sweep and its overlap
        # fraction (see probe_cross_process_wire / bench/kv_wire.py).
        "kv_wire_gbps": (wire or {}).get("kv_wire_gbps", 0.0),
        "kv_wire_overlap_frac": (wire or {}).get("kv_wire_overlap_frac", 0.0),
        # SLO admission-control headline keys (ISSUE 9): EDF+quota goodput
        # over FIFO goodput under the TTFT budget, and the light-tier TTFT
        # tail under the SLO plane (see probe_slo_sched).
        "slo_sched_goodput_gain": (slo_sched or {}).get("slo_sched_goodput_gain", 0.0),
        "slo_sched_ttft_p99_ms": (slo_sched or {}).get("slo_sched_ttft_p99_ms", 0.0),
        # Overlapped-execution headline keys (ISSUE 10): sync-over-overlap
        # mean ITL ratio and the overlapped mode's device-idle fraction on
        # identical decode-heavy work (see probe_engine_overlap).
        "engine_overlap_itl_gain": (overlap or {}).get("engine_overlap_itl_gain", 0.0),
        "device_idle_frac": (overlap or {}).get("device_idle_frac", 0.0),
        # Always-on overlap headline keys (ISSUE 11): fraction of armed
        # steps that dispatched a chained lookahead on the mixed-traffic
        # workload (staggered ISL-3000 admission + chunked prefill riding
        # live decodes), and the sync-over-overlap mean ITL ratio there.
        "overlap_chained_frac": (overlap or {}).get("overlap_chained_frac", 0.0),
        "engine_overlap_mixed_itl_gain": (overlap or {}).get(
            "engine_overlap_mixed_itl_gain", 0.0),
        # Attribution headline key (ISSUE 15): fraction of non-compute wall
        # in the mixed overlap probe explained by the time-loss ledger.
        "loss_coverage_frac": (overlap or {}).get("loss_coverage_frac", 0.0),
        # Chained constrained decode headline keys (ISSUE 14): ITL ratio of
        # lookahead-off over lookahead-on JSON-mode traffic under overlap
        # (both bit-identical streams), and the lookahead-on run's residual
        # barrier fraction (cold mask-cache steps only).
        "overlap_constrained_itl_gain": (overlap or {}).get(
            "overlap_constrained_itl_gain", 0.0),
        "overlap_barrier_frac": (overlap or {}).get(
            "overlap_barrier_frac", 0.0),
        # Cache-aware serving headline keys (ISSUE 12): cold-over-reuse TTFT
        # p50 at fixed QPS on the prefix-heavy workload, and the fraction of
        # onboarding-pending steps that still dispatched fresh work (tier
        # fetch overlapped with compute; see probe_prefix_reuse).
        "prefix_reuse_ttft_gain": (prefix_reuse or {}).get(
            "prefix_reuse_ttft_gain", 0.0),
        "prefix_onboard_overlap_frac": (prefix_reuse or {}).get(
            "prefix_onboard_overlap_frac", 0.0),
        # Fleet-simulation headline keys (ISSUE 13): goodput-under-SLO and
        # per-tenant fairness from the fixed fleet scenario replayed against
        # the real control plane with process-per-worker mock engines (see
        # probe_fleet_sim / dynamo_tpu/fleetsim).
        "fleet_goodput_frac_at_slo": (fleet or {}).get(
            "fleet_goodput_frac_at_slo", 0.0),
        "fleet_tenant_fairness": (fleet or {}).get("fleet_tenant_fairness", 0.0),
        # Quantization headline keys (ISSUE 16): decode tok/s of each weight
        # format over the bf16 baseline on one 8b-proxy shape, plus the
        # always-measured int4-over-int8 ratio (see probe_quant_sweep for
        # the bf16 OOM fallback semantics).
        "quant_int8_decode_gain": (quant_sweep or {}).get(
            "quant_int8_decode_gain", 0.0),
        "quant_int4_decode_gain": (quant_sweep or {}).get(
            "quant_int4_decode_gain", 0.0),
        "quant_int4_vs_int8_decode_gain": (quant_sweep or {}).get(
            "quant_int4_vs_int8_decode_gain", 0.0),
        # Constrained-decoding cold-build headline keys (ISSUE 16): mean
        # vectorized cold mask build at 128k vocab and its speedup over the
        # pure-Python builder, bitwise-identity asserted (probe_mask_build).
        "constraint_mask_build_ms": (mask_build or {}).get(
            "constraint_mask_build_ms", 0.0),
        "constraint_mask_build_gain": (mask_build or {}).get(
            "constraint_mask_build_gain", 0.0),
        "detail": {
            "backend": jax.default_backend(),
            "suite": [c.get("preset") for c in configs],
            "configs": configs,
            "stall_probe": stall or {"pending": True},
            "spec_probe": spec or {"pending": True},
            "decode_kernel_probe": decode_kernel or {"pending": True},
            "slo_sched_probe": slo_sched or {"pending": True},
            "engine_overlap_probe": overlap or {"pending": True},
            "prefix_reuse_probe": prefix_reuse or {"pending": True},
            "fleet_sim_probe": fleet or {"pending": True},
            "quant_sweep_probe": quant_sweep or {"pending": True},
            "mask_build_probe": mask_build or {"pending": True},
            "kv_pull": pull,
            "kv_wire_cross_process": wire or {"pending": True},
            "ttft_note": "ttft_idle_* is the drained-engine best case; "
                         "under-load TTFT: bench/results pareto artifacts",
        },
    }


def main() -> None:
    def emit(configs, pull, wire=None, stall=None, spec=None, dk=None, ss=None,
             ov=None, pr=None, fl=None, qs=None, mb=None):
        print(json.dumps(build_doc(configs, pull, wire, stall, spec, dk, ss, ov,
                                   pr, fl, qs, mb)),
              flush=True)

    from dynamo_tpu.compile_cache import enable_compile_cache

    enable_compile_cache()
    suite = parse_suite()
    configs = []
    for entry in suite:
        try:
            configs.append(run_config(*entry))
        except Exception as e:  # OOM or compile failure: record, continue
            configs.append({"preset": entry[0], "quant": entry[1] or "bf16",
                            "error": f"{type(e).__name__}: {e}"[:300]})
        gc.collect()
        # Cumulative snapshot after EVERY config: if a driver timeout kills
        # the suite mid-run, the last stdout line still parses with every
        # config completed so far.
        emit(configs, {"pending": True})
    try:
        stall = probe_decode_stall()
    except Exception as e:
        stall = {"error": f"{type(e).__name__}: {e}"[:200]}
    emit(configs, {"pending": True}, stall=stall)
    gc.collect()
    try:
        spec = probe_spec_decode()
    except Exception as e:
        spec = {"error": f"{type(e).__name__}: {e}"[:200]}
    emit(configs, {"pending": True}, stall=stall, spec=spec)
    gc.collect()
    try:
        dk = probe_decode_kernel()
    except Exception as e:
        dk = {"error": f"{type(e).__name__}: {e}"[:200]}
    emit(configs, {"pending": True}, stall=stall, spec=spec, dk=dk)
    gc.collect()
    try:
        ss = probe_slo_sched()
    except Exception as e:
        ss = {"error": f"{type(e).__name__}: {e}"[:200]}
    emit(configs, {"pending": True}, stall=stall, spec=spec, dk=dk, ss=ss)
    gc.collect()
    try:
        ov = probe_engine_overlap()
    except Exception as e:
        ov = {"error": f"{type(e).__name__}: {e}"[:200]}
    emit(configs, {"pending": True}, stall=stall, spec=spec, dk=dk, ss=ss, ov=ov)
    gc.collect()
    try:
        pr = probe_prefix_reuse()
    except Exception as e:
        pr = {"error": f"{type(e).__name__}: {e}"[:200]}
    emit(configs, {"pending": True}, stall=stall, spec=spec, dk=dk, ss=ss, ov=ov,
         pr=pr)
    gc.collect()
    try:
        fl = probe_fleet_sim()
    except Exception as e:
        fl = {"error": f"{type(e).__name__}: {e}"[:200]}
    emit(configs, {"pending": True}, stall=stall, spec=spec, dk=dk, ss=ss, ov=ov,
         pr=pr, fl=fl)
    gc.collect()
    try:
        qs = probe_quant_sweep()
    except Exception as e:
        qs = {"error": f"{type(e).__name__}: {e}"[:200]}
    emit(configs, {"pending": True}, stall=stall, spec=spec, dk=dk, ss=ss, ov=ov,
         pr=pr, fl=fl, qs=qs)
    gc.collect()
    try:
        mb = probe_mask_build()
    except Exception as e:
        mb = {"error": f"{type(e).__name__}: {e}"[:200]}
    emit(configs, {"pending": True}, stall=stall, spec=spec, dk=dk, ss=ss, ov=ov,
         pr=pr, fl=fl, qs=qs, mb=mb)
    gc.collect()
    try:
        pull = probe_kv_pull_gbps()
    except Exception as e:
        pull = {"error": f"{type(e).__name__}: {e}"[:200]}
    emit(configs, pull, stall=stall, spec=spec, dk=dk, ss=ss, ov=ov, pr=pr, fl=fl,
         qs=qs, mb=mb)
    gc.collect()
    try:
        wire = probe_cross_process_wire()
    except Exception as e:
        wire = {"error": f"{type(e).__name__}: {e}"[:200]}
    emit(configs, pull, wire, stall=stall, spec=spec, dk=dk, ss=ss, ov=ov, pr=pr,
         fl=fl, qs=qs, mb=mb)
    # Every phase ran and was recorded; a run with any error is still a
    # failed run.
    failed = [c.get("preset", "?") for c in configs if "error" in c] + [
        name for name, probe in (
            ("stall", stall), ("spec", spec), ("decode_kernel", dk), ("slo_sched", ss),
            ("overlap", ov), ("prefix_reuse", pr), ("fleet_sim", fl),
            ("quant_sweep", qs), ("mask_build", mb), ("kv_pull", pull),
            ("kv_wire", wire),
        ) if "error" in (probe or {})
    ]
    if failed:
        print(f"bench: errors recorded in {', '.join(failed)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    if "--tune" in sys.argv:
        # Closed-loop knob auto-tune instead of the measurement suite:
        # remaining flags pass through to python -m dynamo_tpu.tuning.
        from dynamo_tpu.tuning.__main__ import main as tune_main

        sys.exit(tune_main([a for a in sys.argv[1:] if a != "--tune"]))
    main()
