"""Benchmark harness tests: synthesizer structure + sweep over a live stack,
plus the suite's byte-accounting model (bench.py)."""

import asyncio

import pytest

from dynamo_tpu.bench import SyntheticConfig, synthesize, sweep_http
from dynamo_tpu.bench.synthesizer import sharing_ratio


def test_decode_step_bytes_geometry():
    """The roofline byte model must follow the real layout: page-granular KV
    windows, untied embedding tables excluded from streamed weights (decode
    gathers rows, never the table), MLA rope stream lane-padded."""
    import bench
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import PRESETS

    cfg = PRESETS["test-tiny"]  # tie_embeddings=True
    params = llama.init_params(cfg, 0)
    total = bench.tree_nbytes(params)
    ps, batch, isl, osl = 8, 4, 10, 4
    got = bench.decode_step_bytes(params, cfg, batch, isl, osl, ps)
    # contexts 11..14 round to 16 pages-tokens each at page 8.
    per_tok = cfg.kv_bytes_per_token(itemsize=2)
    assert got == total + batch * 16 * per_tok

    # Untied: the embedding table is subtracted from streamed bytes.
    import dataclasses

    cfg2 = dataclasses.replace(cfg, tie_embeddings=False)
    params2 = llama.init_params(cfg2, 0)
    got2 = bench.decode_step_bytes(params2, cfg2, batch, isl, osl, ps)
    assert got2 == bench.tree_nbytes(params2) - bench.tree_nbytes(params2["embed"]) \
        + batch * 16 * per_tok

    # vs_roofline <= 1 by construction: the ceiling uses spec bandwidth.
    roof = bench.roofline_tok_per_sec(got, batch)
    assert roof == batch / (got / (bench.SPEC_HBM_GBPS * 1e9))

    # Every suite preset has a FIXED external anchor (self-graded rooflines
    # as targets were VERDICT r4 weak #3).
    for preset, *_ in bench.DEFAULT_SUITE:
        assert preset in bench.ANCHOR_TOK_PER_SEC


def test_stall_probe_structure(monkeypatch):
    """probe_decode_stall's contract: stable keys for both scheduling modes
    plus the ratio, sized down to a CPU-friendly scenario. The 5x acceptance
    ratio is a TPU bench claim, not asserted here — CPU step times are
    dominated by dispatch overhead, so only structure and counters are
    stable."""
    import bench

    monkeypatch.setenv("BENCH_STALL_PRESET", "test-tiny")
    monkeypatch.setenv("BENCH_STALL_DECODERS", "2")
    monkeypatch.setenv("BENCH_STALL_ISL", "8")
    monkeypatch.setenv("BENCH_STALL_OSL", "8")
    monkeypatch.setenv("BENCH_STALL_PREFILL_ISL", "48")
    monkeypatch.setenv("BENCH_STALL_CHUNK", "8")
    monkeypatch.setenv("BENCH_PAGE_SIZE", "4")
    out = bench.probe_decode_stall()
    assert out["preset"] == "test-tiny"
    for mode in ("chunked", "baseline_phase_exclusive"):
        run = out[mode]
        for key in ("chunk_prefill_tokens", "max_decode_stall_ms",
                    "decode_step_p50_ms", "itl_p50_ms", "itl_p99_ms",
                    "mixed_steps", "stall_violations", "steps"):
            assert key in run, f"{mode} missing {key}"
        assert run["steps"] > 0
        assert run["max_decode_stall_ms"] >= 0
    # The modes really did schedule differently.
    assert out["chunked"]["chunk_prefill_tokens"] == 8
    assert out["chunked"]["mixed_steps"] > 0
    assert out["chunked"]["stall_violations"] == 0
    assert out["baseline_phase_exclusive"]["mixed_steps"] == 0
    assert out["baseline_phase_exclusive"]["stall_violations"] > 0
    assert "stall_ratio_baseline_over_chunked" in out


def test_spec_probe_structure(monkeypatch):
    """probe_spec_decode's contract: stable keys for both modes plus the
    headline acceptance rate and speedup, sized down to CPU. The >1 speedup
    is a TPU bench claim — on CPU a verify dispatch costs more than the
    decode it replaces — so only structure, losslessness-adjacent token
    counts, and a positive acceptance rate are asserted."""
    import bench

    monkeypatch.setenv("BENCH_SPEC_PRESET", "test-tiny")
    monkeypatch.setenv("BENCH_SPEC_K", "4")
    monkeypatch.setenv("BENCH_SPEC_BATCH", "2")
    monkeypatch.setenv("BENCH_SPEC_ISL", "32")
    monkeypatch.setenv("BENCH_SPEC_OSL", "16")
    monkeypatch.setenv("BENCH_SPEC_CHUNK", "16")
    monkeypatch.setenv("BENCH_PAGE_SIZE", "4")
    out = bench.probe_spec_decode()
    assert out["preset"] == "test-tiny"
    for mode in ("spec", "baseline"):
        run = out[mode]
        for key in ("spec_k", "tok_per_sec", "decode_tokens", "decode_steps",
                    "spec_tokens_proposed", "spec_tokens_accepted",
                    "spec_accept_rate"):
            assert key in run, f"{mode} missing {key}"
        assert run["decode_steps"] > 0
    # Identical scenario in both modes: losslessness means identical totals.
    assert out["spec"]["decode_tokens"] == out["baseline"]["decode_tokens"]
    assert out["baseline"]["spec_tokens_proposed"] == 0
    # Repetitive prompts: the drafter must engage and land some tokens.
    assert out["spec"]["spec_tokens_proposed"] > 0
    assert out["spec"]["spec_accept_rate"] > 0
    # Accepted drafts shrink the step count for the same token total.
    assert out["spec"]["decode_steps"] < out["baseline"]["decode_steps"]
    assert out["spec_accept_rate"] == out["spec"]["spec_accept_rate"]
    assert "spec_decode_speedup" in out


def test_decode_kernel_probe_structure(monkeypatch):
    """probe_decode_kernel's contract (ISSUE 7): stable headline keys plus a
    per-cell grid, sized down to a CPU/interpret-friendly geometry. The
    bandwidth values are emulation artifacts off-TPU, so only structure and
    positivity are asserted."""
    import bench

    monkeypatch.setenv("BENCH_DK_BATCHES", "1,2")
    monkeypatch.setenv("BENCH_DK_CONTEXTS", "24,40")
    monkeypatch.setenv("BENCH_DK_PAGE_SIZE", "8")
    monkeypatch.setenv("BENCH_DK_HEADS", "4")
    monkeypatch.setenv("BENCH_DK_KV", "2")
    monkeypatch.setenv("BENCH_DK_HEAD_DIM", "16")
    monkeypatch.setenv("BENCH_DK_ITERS", "1")
    with pytest.raises(RuntimeError, match="needs a TPU"):
        bench.probe_decode_kernel()  # a bandwidth probe refuses to run off-TPU
    out = bench.probe_decode_kernel(interpret=True)
    assert out["interpret"] is True
    assert "error" not in out
    assert len(out["grid"]) == 4  # 2 batches x 2 contexts
    for cell in out["grid"]:
        for key in ("batch", "context", "kv_bytes_per_call", "us_per_call",
                    "gbytes_per_sec", "roofline_frac"):
            assert key in cell, f"grid cell missing {key}"
        # KV read model: K and V, whole pages, bf16.
        pages = -(-cell["context"] // 8)
        assert cell["kv_bytes_per_call"] == 2 * cell["batch"] * pages * 8 * 32 * 2
        assert cell["gbytes_per_sec"] > 0
    assert out["decode_kernel_gbps"] == max(
        c["gbytes_per_sec"] for c in out["grid"])
    assert out["decode_roofline_frac"] > 0


def test_slo_sched_probe_structure(monkeypatch):
    """probe_slo_sched's contract (ISSUE 9): identical mixed-tenant scenario
    under FIFO and under the SLO plane, stable keys for both modes, and the
    headline gain. Sized down but with the head-of-line blocking still
    decisive (two ~100 ms heavy prefills ahead of eight light requests on a
    150 ms TTFT budget: FIFO serves the first heavy in budget but blows it
    for every light), so EDF must beat FIFO on goodput even on CPU."""
    import bench

    monkeypatch.setenv("BENCH_SLOSCHED_HEAVY", "2")
    monkeypatch.setenv("BENCH_SLOSCHED_HEAVY_ISL", "2048")
    monkeypatch.setenv("BENCH_SLOSCHED_LIGHT", "8")
    monkeypatch.setenv("BENCH_SLOSCHED_LIGHT_ISL", "64")
    monkeypatch.setenv("BENCH_SLOSCHED_OSL", "8")
    monkeypatch.setenv("BENCH_SLOSCHED_TTFT_MS", "150")
    monkeypatch.setenv("BENCH_SLOSCHED_CHUNK", "256")
    out = bench.probe_slo_sched()
    assert out["ttft_slo_ms"] == 150.0
    assert out["heavy"] == {"n": 2, "isl": 2048}
    assert out["light"] == {"n": 8, "isl": 64}
    for mode in ("fifo", "slo_sched"):
        run = out[mode]
        for key in ("mode", "elapsed_s", "requests_met_ttft", "requests_total",
                    "goodput_tokens_per_s", "light_ttft_p50_ms",
                    "light_ttft_p99_ms", "deadline_misses", "throttle_events",
                    "tenant_throttled"):
            assert key in run, f"{mode} missing {key}"
        assert run["requests_total"] == 10
    # FIFO never consults the plane; the SLO run throttles the heavy tenant.
    assert out["fifo"]["throttle_events"] == 0
    assert out["slo_sched"]["throttle_events"] > 0
    assert out["slo_sched"]["tenant_throttled"].get("heavy", 0) > 0
    # The headline: same capacity, more SLO-attaining tokens, lights fast.
    assert out["slo_sched_goodput_gain"] > 1.0
    assert out["slo_sched"]["requests_met_ttft"] > out["fifo"]["requests_met_ttft"]
    assert 0 < out["slo_sched_ttft_p99_ms"] <= 150.0
    assert out["slo_sched_ttft_p99_ms"] == out["slo_sched"]["light_ttft_p99_ms"]


def test_overlap_probe_structure(monkeypatch):
    """probe_engine_overlap's contract (ISSUE 10): the same decode-heavy
    scenario under the synchronous loop and under the depth-1 overlapped
    pipeline, bit-identical streams, and the two headline numbers. Sized
    down, but with d2h latency comparable to compute so hiding it is
    decisive even on a loaded CI host."""
    import bench

    monkeypatch.setenv("BENCH_OVERLAP_DECODERS", "2")
    monkeypatch.setenv("BENCH_OVERLAP_ISL", "16")
    monkeypatch.setenv("BENCH_OVERLAP_OSL", "24")
    monkeypatch.setenv("BENCH_OVERLAP_DECODE_US", "1500")
    monkeypatch.setenv("BENCH_OVERLAP_D2H_US", "1200")
    monkeypatch.setenv("BENCH_OVERLAP_MIXED_DECODERS", "3")
    monkeypatch.setenv("BENCH_OVERLAP_MIXED_ISL", "96")
    monkeypatch.setenv("BENCH_OVERLAP_MIXED_OSL", "16")
    monkeypatch.setenv("BENCH_OVERLAP_MIXED_CHUNK", "32")
    monkeypatch.setenv("BENCH_OVERLAP_JSON_DECODERS", "2")
    monkeypatch.setenv("BENCH_OVERLAP_JSON_ISL", "16")
    monkeypatch.setenv("BENCH_OVERLAP_JSON_OSL", "24")
    out = bench.probe_engine_overlap()
    assert out["decoders"] == 2 and out["osl"] == 24
    for mode in ("sync", "overlap"):
        run = out[mode]
        for key in ("mode", "elapsed_s", "itl_mean_ms", "device_idle_frac",
                    "overlap_steps", "mean_gap_ms"):
            assert key in run, f"{mode} missing {key}"
    assert out["sync"]["mode"] == "sync"
    assert out["sync"]["overlap_steps"] == {"overlapped": 0, "barrier": 0}
    assert out["overlap"]["overlap_steps"]["overlapped"] > 0
    # The acceptance bar: same tokens, device idles strictly less, ITL gain.
    assert out["bit_identical"] is True
    assert out["overlap"]["device_idle_frac"] < out["sync"]["device_idle_frac"]
    assert out["device_idle_frac"] == out["overlap"]["device_idle_frac"]
    assert out["engine_overlap_itl_gain"] > 1.0
    # Mixed-traffic variant (ISSUE 11): staggered admission + chunked
    # prefill must ride the chained pipeline, not barrier it away.
    mixed = out["mixed"]
    assert mixed["bit_identical"] is True
    assert mixed["sync"]["overlap_steps"] == {"overlapped": 0, "barrier": 0}
    mo = mixed["overlap"]
    for key in ("mode", "elapsed_s", "itl_mean_ms", "overlap_steps",
                "barrier_reasons", "overlap_chained_frac"):
        assert key in mo, f"mixed overlap missing {key}"
    assert mo["overlap_steps"]["overlapped"] > 0
    assert out["overlap_chained_frac"] == mo["overlap_chained_frac"]
    assert out["overlap_chained_frac"] >= 0.9  # the ISSUE 11 acceptance bar
    assert out["engine_overlap_mixed_itl_gain"] > 0.0
    # Constrained variant (ISSUE 14): JSON-mode rows chain through the mask
    # lookahead instead of barriering every step, streams stay identical,
    # and the residual barriers are not constraint-shaped.
    con = out["constrained"]
    assert con["bit_identical"] is True
    base, la = con["no_lookahead"], con["lookahead_on"]
    for key in ("mode", "elapsed_s", "itl_mean_ms", "overlap_steps",
                "barrier_reasons", "overlap_barrier_frac",
                "mask_cache_hits", "mask_cache_misses"):
        assert key in base and key in la, f"constrained run missing {key}"
    assert base["overlap_steps"]["overlapped"] == 0
    assert base["barrier_reasons"].get("constraint", 0) > 0
    assert la["overlap_steps"]["overlapped"] > 0
    assert la["barrier_reasons"].get("constraint", 0) == 0
    assert la["overlap_barrier_frac"] < base["overlap_barrier_frac"] == 1.0
    assert out["overlap_barrier_frac"] == la["overlap_barrier_frac"]
    assert out["overlap_constrained_itl_gain"] > 0.0
    assert la["mask_cache_hits"] > 0


def test_bench_doc_goodput_keys():
    """build_doc's top-level contract (ISSUE 4): the SLO-conditioned goodput
    headline keys are stable, sourced from the headline (llama-3.2-1b)
    config, and default to 0.0 when the suite produced nothing usable."""
    import bench

    configs = [
        {"preset": "test-tiny", "tok_per_sec": 5.0,
         "slo_ttft_attainment": 1.0, "goodput_tokens_per_s_at_slo": 5.0},
        {"preset": "llama-3.2-1b", "tok_per_sec": 100.0, "slo_ttft_ms": 500.0,
         "slo_ttft_attainment": 0.9, "goodput_tokens_per_s_at_slo": 90.0},
    ]
    doc = bench.build_doc(configs, pull={"skipped": True})
    assert doc["goodput_tokens_per_s_at_slo"] == 90.0  # headline, not first
    assert doc["slo_ttft_attainment"] == 0.9
    assert doc["value"] == 100.0
    assert doc["itl_p99_ms"] == 0.0  # stall probe absent: stable default
    assert doc["spec_accept_rate"] == 0.0  # spec probe absent: stable default
    spec = {"spec_accept_rate": 0.6, "spec_decode_speedup": 1.8}
    doc2 = bench.build_doc(configs, pull={}, spec=spec)
    assert doc2["spec_accept_rate"] == 0.6
    assert doc2["spec_decode_speedup"] == 1.8
    assert doc2["decode_kernel_gbps"] == 0.0  # probe absent: stable default
    dk = {"decode_kernel_gbps": 700.5, "decode_roofline_frac": 0.8553}
    doc3 = bench.build_doc(configs, pull={}, decode_kernel=dk)
    assert doc3["decode_kernel_gbps"] == 700.5
    assert doc3["decode_roofline_frac"] == 0.8553
    assert doc3["detail"]["decode_kernel_probe"] == dk
    assert doc3["kv_wire_gbps"] == 0.0  # wire sweep absent: stable default
    # KV-wire headline keys (ISSUE 8) surface from the sweep dict.
    wire = {"kv_wire_gbps": 2.375, "kv_wire_overlap_frac": 0.41,
            "speedup_vs_v2": 6.2, "sweep": []}
    doc4 = bench.build_doc(configs, pull={}, wire=wire)
    assert doc4["kv_wire_gbps"] == 2.375
    assert doc4["kv_wire_overlap_frac"] == 0.41
    assert doc4["detail"]["kv_wire_cross_process"] == wire
    assert doc4["slo_sched_goodput_gain"] == 0.0  # probe absent: stable default
    # SLO admission headline keys (ISSUE 9) surface from the probe dict.
    ss = {"slo_sched_goodput_gain": 5.4869, "slo_sched_ttft_p99_ms": 105.31}
    doc5 = bench.build_doc(configs, pull={}, slo_sched=ss)
    assert doc5["slo_sched_goodput_gain"] == 5.4869
    assert doc5["slo_sched_ttft_p99_ms"] == 105.31
    assert doc5["detail"]["slo_sched_probe"] == ss
    assert doc5["engine_overlap_itl_gain"] == 0.0  # probe absent: stable default
    # Overlapped-execution headline keys (ISSUE 10) surface from the probe.
    ov = {"engine_overlap_itl_gain": 1.7523, "device_idle_frac": 0.0508,
          "bit_identical": True, "overlap_chained_frac": 0.9412,
          "engine_overlap_mixed_itl_gain": 1.31,
          "overlap_constrained_itl_gain": 1.654, "overlap_barrier_frac": 0.115}
    doc6 = bench.build_doc(configs, pull={}, overlap=ov)
    assert doc6["engine_overlap_itl_gain"] == 1.7523
    assert doc6["device_idle_frac"] == 0.0508
    # Always-on overlap headline keys (ISSUE 11) surface from the probe.
    assert doc6["overlap_chained_frac"] == 0.9412
    assert doc6["engine_overlap_mixed_itl_gain"] == 1.31
    assert doc5["overlap_chained_frac"] == 0.0  # probe absent: stable default
    # Chained constrained decode headline keys (ISSUE 14).
    assert doc6["overlap_constrained_itl_gain"] == 1.654
    assert doc6["overlap_barrier_frac"] == 0.115
    assert doc5["overlap_constrained_itl_gain"] == 0.0  # probe absent
    assert doc5["overlap_barrier_frac"] == 0.0
    assert doc6["detail"]["engine_overlap_probe"] == ov
    # An all-errors suite still emits the full key set.
    empty = bench.build_doc([{"preset": "x", "error": "boom"}], pull={})
    for key in ("value", "goodput_tokens_per_s_at_slo", "slo_ttft_attainment",
                "itl_p99_ms", "max_decode_stall_ms", "spec_accept_rate",
                "spec_decode_speedup", "decode_kernel_gbps",
                "decode_roofline_frac", "kv_wire_gbps",
                "kv_wire_overlap_frac", "slo_sched_goodput_gain",
                "slo_sched_ttft_p99_ms", "engine_overlap_itl_gain",
                "device_idle_frac"):
        assert key in empty
        assert empty[key] == 0.0


def test_bench_doc_prefix_reuse_keys():
    """Cache-aware serving headline keys (ISSUE 12): the prefix-reuse probe
    surfaces stable top-level keys and a detail record; absent probe emits
    0.0 defaults so the doc schema never shifts."""
    import bench

    configs = [{"preset": "test-tiny", "tok_per_sec": 5.0}]
    doc = bench.build_doc(configs, pull={})
    assert doc["prefix_reuse_ttft_gain"] == 0.0
    assert doc["prefix_onboard_overlap_frac"] == 0.0
    assert doc["detail"]["prefix_reuse_probe"] == {"pending": True}
    pr = {"prefix_reuse_ttft_gain": 55.04, "prefix_onboard_overlap_frac": 1.0,
          "cold": {"ttft_p50_ms": 212.46}, "reuse": {"ttft_p50_ms": 3.86}}
    doc2 = bench.build_doc(configs, pull={}, prefix_reuse=pr)
    assert doc2["prefix_reuse_ttft_gain"] == 55.04
    assert doc2["prefix_onboard_overlap_frac"] == 1.0
    assert doc2["detail"]["prefix_reuse_probe"] == pr


def test_bench_doc_fleet_sim_keys():
    """Fleet-sim headline keys (ISSUE 13): probe_fleet_sim surfaces stable
    top-level goodput/fairness keys and a detail record; absent probe emits
    0.0 defaults so the doc schema never shifts."""
    import bench

    configs = [{"preset": "test-tiny", "tok_per_sec": 5.0}]
    doc = bench.build_doc(configs, pull={})
    assert doc["fleet_goodput_frac_at_slo"] == 0.0
    assert doc["fleet_tenant_fairness"] == 0.0
    assert doc["detail"]["fleet_sim_probe"] == {"pending": True}
    fl = {"scenario": "smoke", "trace_digest": "abc", "digest_stable": True,
          "fleet_goodput_frac_at_slo": 0.92, "fleet_tenant_fairness": 0.88,
          "passed": True}
    doc2 = bench.build_doc(configs, pull={}, fleet=fl)
    assert doc2["fleet_goodput_frac_at_slo"] == 0.92
    assert doc2["fleet_tenant_fairness"] == 0.88
    assert doc2["detail"]["fleet_sim_probe"] == fl
    # A probe that errored keeps the stable defaults.
    doc3 = bench.build_doc(configs, pull={}, fleet={"error": "boom"})
    assert doc3["fleet_goodput_frac_at_slo"] == 0.0
    assert doc3["fleet_tenant_fairness"] == 0.0


def test_bench_doc_quant_and_mask_keys():
    """Roofline burn-down keys (ISSUE 16): the quant-mode sweep and the
    vectorized-mask probe surface stable `_gain`/`_ms` suffixed keys and
    detail records; absent probes keep 0.0 defaults."""
    import bench

    configs = [{"preset": "test-tiny", "tok_per_sec": 5.0}]
    doc = bench.build_doc(configs, pull={})
    for key in ("quant_int8_decode_gain", "quant_int4_decode_gain",
                "quant_int4_vs_int8_decode_gain", "constraint_mask_build_ms",
                "constraint_mask_build_gain"):
        assert doc[key] == 0.0
    assert doc["detail"]["quant_sweep_probe"] == {"pending": True}
    assert doc["detail"]["mask_build_probe"] == {"pending": True}

    qs = {"preset": "mla-8b-proxy", "bf16_basis": "modeled_from_int4_achieved_bw",
          "quant_int8_decode_gain": 1.9, "quant_int4_decode_gain": 3.1,
          "quant_int4_vs_int8_decode_gain": 1.63}
    mb = {"vocab": 128000, "mismatches": 0,
          "constraint_mask_build_ms": 30.7, "constraint_mask_build_gain": 16.9}
    doc2 = bench.build_doc(configs, pull={}, quant_sweep=qs, mask_build=mb)
    assert doc2["quant_int8_decode_gain"] == 1.9
    assert doc2["quant_int4_decode_gain"] == 3.1
    assert doc2["quant_int4_vs_int8_decode_gain"] == 1.63
    assert doc2["constraint_mask_build_ms"] == 30.7
    assert doc2["constraint_mask_build_gain"] == 16.9
    assert doc2["detail"]["quant_sweep_probe"] == qs
    assert doc2["detail"]["mask_build_probe"] == mb


def test_synthesizer_prefix_structure():
    cfg = SyntheticConfig(num_requests=32, shared_prefix_len=16, num_groups=3,
                          group_prefix_len=8, unique_len=4, osl_mean=20, seed=7)
    reqs = synthesize(cfg)
    assert len(reqs) == 32
    shared = reqs[0].token_ids[:16]
    groups = {}
    for r in reqs:
        assert r.token_ids[:16] == shared  # corpus-wide prefix
        assert len(r.token_ids) == 16 + 8 + 4
        groups.setdefault(r.group, r.token_ids[16:24])
        assert r.token_ids[16:24] == groups[r.group]  # group prefix stable
        assert 1 <= r.max_tokens <= 80
    assert len(groups) == 3
    # Different groups have different prefixes (overwhelmingly likely).
    assert len({tuple(g) for g in groups.values()}) == 3
    assert abs(sharing_ratio(cfg) - 24 / 28) < 1e-9


def test_synthesizer_deterministic():
    a = synthesize(SyntheticConfig(seed=3))
    b = synthesize(SyntheticConfig(seed=3))
    assert [r.token_ids for r in a] == [r.token_ids for r in b]
    assert [r.token_ids for r in a] != [r.token_ids for r in synthesize(SyntheticConfig(seed=4))]


async def test_sweep_over_live_stack():
    """Closed-loop sweep against a real served stack (mock engine): pareto
    rows come back populated and error-free."""
    from dynamo_tpu.launch import run_local

    handles = await run_local("test-tiny", port=0, mock=True, num_pages=512, max_batch_size=16)
    base = f"http://127.0.0.1:{handles['port']}"
    try:
        workload = synthesize(SyntheticConfig(num_requests=8, shared_prefix_len=16,
                                              group_prefix_len=8, unique_len=8, osl_mean=12))
        stats = await sweep_http(base, "test-tiny", workload, levels=[1, 4])
        assert [s.concurrency for s in stats] == [1, 4]
        for s in stats:
            assert s.errors == 0
            assert s.requests == 8
            assert s.output_tokens > 0
            assert s.output_tok_per_sec > 0
            assert s.ttft_p50 > 0
            assert s.ttft_p99 >= s.ttft_p50
    finally:
        await handles["http"].stop()
        await handles["watcher"].close()
        for svc in handles["services"]:
            await svc.close()
        await handles["runtime"].close()
