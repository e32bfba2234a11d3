"""Observability plane: distributed trace propagation, EngineMetrics,
/debug/traces timeline assembly, /metrics federation, metric-name hygiene.

Covers the ISSUE 3 tentpole end to end: a TraceContext minted at the edge
rides runtime hops (real TCP), spans from every process land in the ring
buffer under one trace_id, the frontend assembles them into one timeline,
and the engine registries federate into the frontend's /metrics render.
"""

import asyncio
import json
import pathlib
import sys
import time
from types import SimpleNamespace
from typing import Any, AsyncIterator

import aiohttp
import pytest

from dynamo_tpu.observability.metrics import KV_PHASES, EngineMetrics, federate_text
from dynamo_tpu.observability.service import assemble_timeline
from dynamo_tpu.runtime.component import DistributedRuntime
from dynamo_tpu.runtime.discovery import MemoryStore
from dynamo_tpu.runtime.engine import AsyncEngine, Context, collect
from dynamo_tpu.runtime.tcp import TcpTransport
from dynamo_tpu.tracing import SPANS, Span, TraceContext, trace_of


# -- trace identity -----------------------------------------------------------


def test_traceparent_roundtrip():
    ctx = TraceContext.new()
    assert len(ctx.trace_id) == 32 and len(ctx.span_id) == 16
    parsed = TraceContext.from_traceparent(ctx.to_traceparent())
    assert parsed == ctx
    # W3C header from an external tracer.
    hdr = "00-0af7651916cd43dd8448eb211c80319c-b7ad6b7169203331-01"
    parsed = TraceContext.from_traceparent(hdr)
    assert parsed is not None
    assert parsed.trace_id == "0af7651916cd43dd8448eb211c80319c"
    assert parsed.span_id == "b7ad6b7169203331"
    for bad in (None, "", "garbage", "00-short-span-01"):
        assert TraceContext.from_traceparent(bad) is None
    # Dict form survives a msgpack/JSON hop.
    assert TraceContext.from_dict(ctx.to_dict()) == ctx
    assert TraceContext.from_dict(None) is None
    assert TraceContext.from_dict({"other": 1}) is None


def test_span_links_under_incoming_context():
    parent = TraceContext.new()
    with Span("child_phase", trace=parent, request_id="link-1") as span:
        pass
    assert span.trace_id == parent.trace_id
    assert span.parent_id == parent.span_id
    assert span.context.trace_id == parent.trace_id
    assert span.context.span_id == span.span_id
    recorded = SPANS.query(request_id="link-1")
    assert recorded and recorded[-1]["parent_id"] == parent.span_id


# -- trace propagation over the real TCP transport ----------------------------


class _TracingEngine(AsyncEngine[Any, Any]):
    """Worker-side engine that records a span under the incoming context."""

    async def generate(self, request: Any, context: Context) -> AsyncIterator[Any]:
        with Span("engine_side", trace=trace_of(context), request_id=context.id):
            await asyncio.sleep(0)
        yield {"ok": True}


async def test_trace_propagates_frontend_to_engine_over_tcp():
    """Frontend runtime -> worker runtime over real TCP sockets: the worker's
    span must share the root trace_id and link under the rpc_client hop."""
    store = MemoryStore()
    rt_worker = DistributedRuntime(store, TcpTransport(host="127.0.0.1"))
    rt_front = DistributedRuntime(store, TcpTransport(host="127.0.0.1"))
    try:
        await rt_worker.namespace("obs").component("backend").endpoint("generate").serve(
            _TracingEngine()
        )
        client = rt_front.namespace("obs").component("backend").endpoint("generate").client()
        await client.wait_for_instances(count=1, timeout=5)

        rid = "tcp-trace-1"
        root = Span("http_request", request_id=rid)
        ctx = Context(request_id=rid, trace=root.context.to_dict())
        with root:
            items = await collect(client.generate({}, ctx))
        assert items == [{"ok": True}]

        spans = {s["name"]: s for s in SPANS.query(request_id=rid)}
        assert {"http_request", "rpc_client", "engine_side"} <= set(spans)
        # One trace across the wire...
        assert spans["rpc_client"]["trace_id"] == root.trace_id
        assert spans["engine_side"]["trace_id"] == root.trace_id
        # ...with intact parent/child linkage: root -> rpc hop -> engine.
        assert spans["rpc_client"]["parent_id"] == root.span_id
        assert spans["engine_side"]["parent_id"] == spans["rpc_client"]["span_id"]
        assert spans["engine_side"]["status"] == "ok"
    finally:
        await rt_front.close()
        await rt_worker.close()


async def test_untraced_context_stays_untraced_over_tcp():
    """No trace on the context -> no rpc_client span, engine mints a root."""
    store = MemoryStore()
    rt = DistributedRuntime(store, TcpTransport(host="127.0.0.1"))
    try:
        await rt.namespace("obs").component("backend").endpoint("gen2").serve(_TracingEngine())
        client = rt.namespace("obs").component("backend").endpoint("gen2").client()
        await client.wait_for_instances(count=1, timeout=5)
        rid = "tcp-untraced-1"
        await collect(client.generate({}, Context(request_id=rid)))
        spans = {s["name"]: s for s in SPANS.query(request_id=rid)}
        assert "rpc_client" not in spans
        assert spans["engine_side"]["parent_id"] is None
    finally:
        await rt.close()


# -- EngineMetrics registry ---------------------------------------------------


class _FakeCore:
    last_step_info = {"decode_rows": 3, "chunk_rows": 2, "chunk_tokens": 128, "decodable": 3}
    mixed_steps = 7
    chunk_steps_split = 6
    chunk_steps_rows_x_t = 1
    stall_violations = 1
    num_preemptions = 2
    admission_rejections = 4
    spec_tokens_proposed = 20
    spec_tokens_accepted = 9
    attn_dispatch_counts = {("decode", "pallas"): 5, ("verify", "fallback"): 1}
    step_gap_ms_last = 0.75
    step_gap_ms_sum = 10.0
    step_gap_ms_count = 8
    overlap_step_counts = {"overlapped": 6, "barrier": 2}
    overlap_barrier_counts = {"spec": 1, "drain": 1}
    constraint_mask_cache_hits = 11
    constraint_mask_cache_misses = 3

    def drain_constraint_build_seconds(self):
        return [0.5, 0.05]

    lost_time_ms = {"gap": 1500.0, "queue": 250.0, "recompile": 40.0}
    step_wall_ms_total = 4000.0
    step_dispatch_ms_total = 3000.0
    step_kind_counts = {"mixed": 5, "decode": 30}
    long_steps = {"gc": 2, "": 1}
    long_step_lost_ms = {"gc": 210.0, "": 95.0}
    sentinel = SimpleNamespace(
        active={"recompile_storm": {"value": 9.0, "threshold": 8.0, "since_step": 300}},
        fired={"recompile_storm": 2},
    )
    waiting = ["a"]
    running = ["b", "c"]
    prefilling = ["d"]
    allocator = SimpleNamespace(
        stats=lambda: SimpleNamespace(
            total_pages=64, free_pages=16, cached_pages=8, active_pages=40, hit_rate=0.5
        )
    )
    runner = SimpleNamespace(
        compile_tracker=SimpleNamespace(
            counts=lambda: {("step", "new_shape"): 2, ("multi_step", "warm_cache"): 1}
        )
    )


class _FakeTransfer:
    def stats(self):
        return {
            "blocks": 12, "bytes": 4096, "streams_in_flight": 1,
            "wire_conns": 4, "staged_bytes": 2048,
            "paths": {
                "host_striped": {"transfers": 3, "bytes": 3072},
                "device_pull": {"transfers": 1, "bytes": 1024},
            },
        }


EXPECTED_ENGINE_FAMILIES = {
    "dynamo_engine_step_decode_rows",
    "dynamo_engine_step_chunk_rows",
    "dynamo_engine_step_chunk_tokens",
    "dynamo_engine_attn_dispatch_steps_total",
    "dynamo_engine_step_decodable_seqs",
    "dynamo_engine_mixed_steps_total",
    "dynamo_engine_chunk_steps_split_total",
    "dynamo_engine_chunk_steps_rows_x_t_total",
    "dynamo_engine_stall_violations_total",
    "dynamo_engine_preemptions_total",
    "dynamo_engine_admission_rejections_total",
    "dynamo_engine_spec_tokens_proposed_total",
    "dynamo_engine_spec_tokens_accepted_total",
    "dynamo_engine_pages_total",
    "dynamo_engine_pages_free",
    "dynamo_engine_pages_cached",
    "dynamo_engine_pages_active",
    "dynamo_engine_page_utilization_ratio",
    "dynamo_engine_page_fragmentation_ratio",
    "dynamo_engine_prefix_cache_hit_ratio",
    "dynamo_engine_requests_waiting",
    "dynamo_engine_requests_running",
    "dynamo_engine_recompiles_total",
    "dynamo_engine_prefill_queue_depth",
    "dynamo_kv_transfer_blocks_total",
    "dynamo_kv_transfer_bytes_total",
    "dynamo_kv_transfer_streams_in_flight",
    "dynamo_kv_transfer_crc_failures_total",
    "dynamo_kv_transfer_rollbacks_total",
    "dynamo_kv_wire_streams",
    "dynamo_kv_wire_inflight_sessions",
    "dynamo_kv_wire_staged_bytes",
    "dynamo_kv_wire_path_bytes_total",
    "dynamo_kv_wire_path_transfers_total",
    "dynamo_engine_prefill_requeues_total",
    "dynamo_engine_step_gap_ms",
    "dynamo_engine_step_gap_ms_mean",
    "dynamo_engine_overlap_steps_total",
    "dynamo_engine_overlap_barrier_total",
    "dynamo_incidents_captured_total",
    "dynamo_engine_constraint_mask_build_seconds",
    # _created appears once the worker-labeled child exists (the fake core's
    # drain returns samples) — same prometheus_client behavior as the kv
    # phase histogram below.
    "dynamo_engine_constraint_mask_build_seconds_created",
    "dynamo_engine_constraint_mask_cache_hits_total",
    "dynamo_engine_constraint_mask_cache_misses_total",
    "dynamo_engine_admission_queue_depth",
    "dynamo_engine_prefix_onboard_pages_total",
    "dynamo_engine_prefix_onboard_shortfall_pages_total",
    "dynamo_engine_onboard_wait_seconds",
    "dynamo_engine_deadline_misses_total",
    "dynamo_tenant_throttled_total",
    "dynamo_engine_chunk_budget_tokens",
    # Attribution plane (ISSUE 15): time-loss ledger, step-time composition,
    # and the anomaly sentinel's active/fired gauges. True Counters since
    # ISSUE 17 (delta-inc on scrape), so the `_total` sample suffix is
    # honest and each gains a `_created` timestamp family.
    "dynamo_engine_lost_time_seconds_total",
    "dynamo_engine_lost_time_seconds_created",
    "dynamo_engine_step_time_seconds_total",
    "dynamo_engine_step_time_seconds_created",
    "dynamo_engine_step_kind_steps_total",
    "dynamo_engine_step_kind_steps_created",
    "dynamo_anomaly_active",
    "dynamo_anomaly_fired_total",
    # Long steps by cause and the process's collections by generation
    # (ISSUE 38): Counters, delta-synced like the ledgers above.
    "dynamo_engine_long_steps_total",
    "dynamo_engine_long_steps_created",
    "dynamo_engine_long_step_lost_seconds_total",
    "dynamo_engine_long_step_lost_seconds_created",
    "dynamo_host_gc_pauses_total",
    "dynamo_host_gc_pauses_created",
    "dynamo_host_gc_pause_seconds_total",
    "dynamo_host_gc_pause_seconds_created",
    "dynamo_kv_transfer_phase_seconds",
    # prometheus_client emits the histogram's _created timestamps as their
    # own gauge family once a labelled child exists.
    "dynamo_kv_transfer_phase_seconds_created",
    # Recurrent-state slots of a model with KDA layers, and whether the model
    # switched prefix matching off (ISSUE 40); 0 for every other model.
    "dynamo_engine_state_slots",
    "dynamo_engine_state_slots_live",
    "dynamo_engine_prefix_matching_off_by_model",
    # The second page pool of a model that mixes window and full layers
    # (ISSUE 42): its AllocatorStats and the pages given back behind the
    # window; 0 for every other model.
    "dynamo_engine_window_pages_total",
    "dynamo_engine_window_pages_free",
    "dynamo_engine_window_pages_cached",
    "dynamo_engine_window_pages_active",
    "dynamo_engine_window_pages_released_total",
}


async def test_engine_metrics_names_labels_and_values():
    async def depth() -> int:
        return 5

    m = (
        EngineMetrics(worker="w1")
        .bind_core(_FakeCore())
        .bind_transfer(_FakeTransfer())
        .bind_queue_depth(depth)
    )
    for phase in KV_PHASES:
        m.observe_phase(phase, 0.01)
    text = (await m.render()).decode()

    # Family-name snapshot: a rename or drop here is an intentional,
    # reviewed change (dashboards and the docs inventory depend on these).
    families = {
        line.split(" ")[2] for line in text.splitlines() if line.startswith("# TYPE ")
    }
    assert families == EXPECTED_ENGINE_FAMILIES

    # Every sample carries the worker label (the federation key).
    for line in text.splitlines():
        if line and not line.startswith("#"):
            assert 'worker="w1"' in line, line

    assert 'dynamo_engine_step_decode_rows{worker="w1"} 3.0' in text
    assert 'dynamo_engine_step_chunk_tokens{worker="w1"} 128.0' in text
    assert 'dynamo_engine_mixed_steps_total{worker="w1"} 7.0' in text
    assert 'dynamo_engine_chunk_steps_split_total{worker="w1"} 6.0' in text
    assert 'dynamo_engine_chunk_steps_rows_x_t_total{worker="w1"} 1.0' in text
    assert 'dynamo_engine_admission_rejections_total{worker="w1"} 4.0' in text
    assert 'dynamo_engine_spec_tokens_proposed_total{worker="w1"} 20.0' in text
    assert 'dynamo_engine_spec_tokens_accepted_total{worker="w1"} 9.0' in text
    assert 'dynamo_engine_step_gap_ms{worker="w1"} 0.75' in text
    assert 'dynamo_engine_long_steps_total{cause="gc",worker="w1"} 2.0' in text
    assert 'dynamo_engine_long_steps_total{cause="unnamed",worker="w1"} 1.0' in text
    assert 'dynamo_engine_long_step_lost_seconds_total{cause="gc",worker="w1"} 0.21' in text
    assert 'dynamo_host_gc_pauses_total{generation="2",worker="w1"}' in text
    assert 'dynamo_engine_step_gap_ms_mean{worker="w1"} 1.25' in text
    assert 'dynamo_engine_overlap_steps_total{mode="overlapped",worker="w1"} 6.0' in text
    assert 'dynamo_engine_overlap_steps_total{mode="barrier",worker="w1"} 2.0' in text
    assert 'dynamo_engine_overlap_barrier_total{reason="spec",worker="w1"} 1.0' in text
    assert 'dynamo_engine_overlap_barrier_total{reason="drain",worker="w1"} 1.0' in text
    assert 'dynamo_engine_constraint_mask_cache_hits_total{worker="w1"} 11.0' in text
    assert 'dynamo_engine_constraint_mask_cache_misses_total{worker="w1"} 3.0' in text
    assert 'dynamo_engine_constraint_mask_build_seconds_count{worker="w1"} 2.0' in text
    assert 'dynamo_engine_constraint_mask_build_seconds_sum{worker="w1"} 0.55' in text
    # Attribution plane: per-cause lost seconds, step-time composition, and
    # the sentinel's active/fired state, all synced from the core.
    assert 'dynamo_engine_lost_time_seconds_total{cause="gap",worker="w1"} 1.5' in text
    assert 'dynamo_engine_lost_time_seconds_total{cause="queue",worker="w1"} 0.25' in text
    assert 'dynamo_engine_lost_time_seconds_total{cause="recompile",worker="w1"} 0.04' in text
    assert 'dynamo_engine_step_time_seconds_total{kind="wall",worker="w1"} 4.0' in text
    assert 'dynamo_engine_step_time_seconds_total{kind="dispatch",worker="w1"} 3.0' in text
    assert 'dynamo_engine_step_time_seconds_total{kind="gap",worker="w1"} 0.01' in text
    assert 'dynamo_engine_step_kind_steps_total{kind="mixed",worker="w1"} 5.0' in text
    assert 'dynamo_engine_step_kind_steps_total{kind="decode",worker="w1"} 30.0' in text
    assert 'dynamo_anomaly_active{kind="recompile_storm",worker="w1"} 1.0' in text
    assert 'dynamo_anomaly_fired_total{kind="recompile_storm",worker="w1"} 2.0' in text
    assert 'dynamo_engine_pages_active{worker="w1"} 40.0' in text
    assert 'dynamo_engine_page_utilization_ratio{worker="w1"} 0.625' in text
    # fragmentation = cached / (free + cached) = 8 / 24
    assert 'dynamo_engine_page_fragmentation_ratio{worker="w1"} 0.3333333333333333' in text
    assert 'dynamo_engine_requests_running{worker="w1"} 3.0' in text
    assert 'dynamo_engine_prefill_queue_depth{worker="w1"} 5.0' in text
    # Recompile counts synced from the runner's CompileTracker.
    assert 'dynamo_engine_recompiles_total{program="step",reason="new_shape",worker="w1"} 2.0' in text
    assert 'dynamo_engine_recompiles_total{program="multi_step",reason="warm_cache",worker="w1"} 1.0' in text
    # Attention dispatch path synced from the core's per-step counts.
    assert 'dynamo_engine_attn_dispatch_steps_total{path="pallas",phase="decode",worker="w1"} 5.0' in text
    assert 'dynamo_engine_attn_dispatch_steps_total{path="fallback",phase="verify",worker="w1"} 1.0' in text
    assert 'dynamo_kv_transfer_blocks_total{worker="w1"} 12.0' in text
    # Wire v3 surface: stripe connections, staging, and per-path attribution.
    assert 'dynamo_kv_wire_streams{worker="w1"} 4.0' in text
    assert 'dynamo_kv_wire_inflight_sessions{worker="w1"} 1.0' in text
    assert 'dynamo_kv_wire_staged_bytes{worker="w1"} 2048.0' in text
    assert 'dynamo_kv_wire_path_bytes_total{path="host_striped",worker="w1"} 3072.0' in text
    assert 'dynamo_kv_wire_path_transfers_total{path="device_pull",worker="w1"} 1.0' in text
    for phase in KV_PHASES:
        assert f'dynamo_kv_transfer_phase_seconds_count{{phase="{phase}",worker="w1"}} 1.0' in text


async def test_unbound_engine_metrics_render_safely():
    text = (await EngineMetrics(worker="idle").render()).decode()
    assert 'dynamo_engine_pages_total{worker="idle"} 0.0' in text


async def test_lost_time_counters_are_monotone_across_scrapes():
    """The lost-time/step-time exports are true Counters (ISSUE 17): a
    scrape incs by the core ledger's delta since the last sync — repeated
    scrapes never double-book, a growing ledger lands exactly once, and a
    rebound core's totals accumulate instead of resetting."""
    core = _FakeCore()
    core.lost_time_ms = {"gap": 1000.0}
    core.step_wall_ms_total = 2000.0
    core.step_kind_counts = {"decode": 10}
    m = EngineMetrics(worker="w1").bind_core(core)

    def sample(text: str, line_start: str) -> float:
        for line in text.splitlines():
            if line.startswith(line_start):
                return float(line.rsplit(" ", 1)[1])
        raise AssertionError(f"{line_start} not found")

    text = (await m.render()).decode()
    assert sample(text, 'dynamo_engine_lost_time_seconds_total{cause="gap",worker="w1"}') == 1.0
    # Idempotent scrape: no growth without ledger growth.
    text = (await m.render()).decode()
    assert sample(text, 'dynamo_engine_lost_time_seconds_total{cause="gap",worker="w1"}') == 1.0
    # Ledger growth lands exactly once.
    core.lost_time_ms = {"gap": 1500.0}
    core.step_kind_counts = {"decode": 12, "mixed": 1}
    text = (await m.render()).decode()
    assert sample(text, 'dynamo_engine_lost_time_seconds_total{cause="gap",worker="w1"}') == 1.5
    assert sample(text, 'dynamo_engine_step_kind_steps_total{kind="decode",worker="w1"}') == 12.0
    assert sample(text, 'dynamo_engine_step_kind_steps_total{kind="mixed",worker="w1"}') == 1.0
    # Rebinding a fresh core (restart) accumulates — monotone across cores.
    fresh = _FakeCore()
    fresh.lost_time_ms = {"gap": 100.0}
    fresh.step_kind_counts = {"decode": 2}
    m.bind_core(fresh)
    text = (await m.render()).decode()
    assert sample(text, 'dynamo_engine_lost_time_seconds_total{cause="gap",worker="w1"}') == 1.6
    assert sample(text, 'dynamo_engine_step_kind_steps_total{kind="decode",worker="w1"}') == 14.0


async def test_federate_text_merges_two_workers():
    parts = [await EngineMetrics(worker="w1").render(), await EngineMetrics(worker="w2").render()]
    merged = federate_text(parts).decode()
    # One header per family...
    assert merged.count("# TYPE dynamo_engine_pages_total gauge") == 1
    assert merged.count("# HELP dynamo_engine_pages_total") == 1
    # ...but both workers' samples survive.
    assert 'dynamo_engine_pages_total{worker="w1"} 0.0' in merged
    assert 'dynamo_engine_pages_total{worker="w2"} 0.0' in merged


def test_metric_names_unique_and_prefixed():
    """Invokes the tools/ hygiene check (ISSUE 3 satellite: CI wiring)."""
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent / "tools"))
    try:
        import check_metric_names
    finally:
        sys.path.pop(0)
    names = check_metric_names.collect_names()
    assert sum(len(v) for v in names.values()) > 20
    assert check_metric_names.check(names) == []
    # The extended hygiene pass: non-empty HELP text and no name registered
    # with conflicting label sets across registries (ISSUE 4 satellite).
    families = check_metric_names.collect_families()
    assert check_metric_names.check_families(families) == []
    assert all(f["help"] for fams in families.values() for f in fams)


def test_env_knobs_documented():
    """Invokes the tools/ env-knob gate (ISSUE 10 satellite: every DYN_*
    knob the source reads appears in a docs env table, and every documented
    knob still exists)."""
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent / "tools"))
    try:
        import check_env_knobs
    finally:
        sys.path.pop(0)
    source, prefixes = check_env_knobs.source_knobs()
    generated = check_env_knobs.generated_knobs()
    documented = check_env_knobs.documented_knobs()
    # The pipelined loop is the serving loop: no knob of the cascade arms it,
    # or says whether a verify rides it.
    assert not {"DYN_OVERLAP", "DYN_WORKER_OVERLAP", "DYN_OVERLAP_SPEC",
                "DYN_WORKER_OVERLAP_SPEC"} & (source | generated | documented)
    assert "DYN_CONSTRAINT_LOOKAHEAD_TOKENS" in source
    assert len(source | generated) > 40
    assert check_env_knobs.check(source, generated, prefixes, documented) == []


def test_barrier_reasons_synced():
    """Invokes the tools/ barrier-vocabulary gate (ISSUE 14 satellite): the
    BARRIER_REASONS tuple, the _note_barrier call sites, and the
    SCHEDULER.md barrier table must agree exactly."""
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent / "tools"))
    try:
        import check_barrier_reasons
    finally:
        sys.path.pop(0)
    declared = check_barrier_reasons.declared_reasons()
    recorded = check_barrier_reasons.recorded_reasons()
    documented = check_barrier_reasons.documented_reasons()
    assert "constraint_miss" in declared and "multistep" not in declared
    assert "mm" not in declared
    assert len(documented) == len(declared) > 5
    assert check_barrier_reasons.check(declared, recorded, documented) == []
    # The loss-cause layer (ISSUE 15 satellite): LOSS_CAUSES must be exactly
    # the barrier vocabulary + the literal extras tuple, and the
    # OBSERVABILITY.md loss-cause table must list all of them.
    extras = check_barrier_reasons.source_extra_causes()
    loss = check_barrier_reasons.declared_loss_causes()
    doc_loss = check_barrier_reasons.documented_loss_causes()
    assert extras == ("queue", "admission", "onboard_stall", "preempt", "recompile", "gap")
    assert loss == tuple(declared) + extras
    assert check_barrier_reasons.check_loss_causes(declared, loss, extras, doc_loss) == []


# -- latency attribution (ISSUE 15 tentpole) ----------------------------------


def _span_doc(name, start_s, dur_ms, *, tid="a" * 32, sid=None, parent=None):
    return {
        "name": name, "trace_id": tid,
        "span_id": sid or (name[:12] + "0000")[:16].ljust(16, "0"),
        "parent_id": parent, "start_ts": start_s, "duration_ms": dur_ms,
        "status": "ok",
    }


def test_build_explain_disagg_budget_sums_to_e2e():
    """The acceptance shape: a disagg request's segments (queue, admission,
    onboard, prefill, KV phases, transfer slack, decode split, recompiles,
    frontend) de-overlap along the span hierarchy and sum to the measured
    E2E latency, residual reported as unattributed."""
    from dynamo_tpu.observability.attribution import build_explain

    t0 = 1000.0
    spans = [
        _span_doc("http_request", t0, 100.0),
        # Remote-prefill window: queue pickup + exec (containing the
        # sender-side KV phases) + scatter, with 4ms of uncovered slack.
        _span_doc("remote_prefill", t0 + 0.005, 30.0),
        _span_doc("prefill_queue_wait", t0 + 0.005, 5.0),
        _span_doc("prefill_exec", t0 + 0.010, 18.0),
        _span_doc("kv_gather", t0 + 0.011, 2.0),
        _span_doc("kv_pack", t0 + 0.013, 1.0),
        _span_doc("kv_wire", t0 + 0.014, 5.0),
        _span_doc("kv_scatter", t0 + 0.028, 3.0),
        # Engine side: queue + admission + onboard waits inside a 12ms TTFT.
        _span_doc("engine_request", t0 + 0.036, 60.0),
        _span_doc("engine_queue_wait", t0 + 0.036, 4.0),
        _span_doc("engine_admission_wait", t0 + 0.040, 2.0),
        _span_doc("engine_onboard_wait", t0 + 0.042, 1.0),
        _span_doc("engine_first_token", t0 + 0.036, 12.0),
    ]
    steps = [
        {"ts": t0 + 0.050 + i * 0.006, "wall_ms": 5.0, "dispatch_ms": 4.0,
         "gap_ms": 1.0, "overlap_mode": "overlapped", "barrier_reason": ""}
        for i in range(8)
    ]
    steps[3]["overlap_mode"] = "barrier"
    steps[3]["barrier_reason"] = "pages"
    step_docs = [
        {"worker": "w-dec", "steps": steps, "compiles": [
            {"ts": t0 + 0.060, "wall_ms": 2.0, "reason": "new_shape", "program": "step"},
            {"ts": t0 + 0.061, "wall_ms": 9.0, "reason": "warm_cache", "program": "step"},
        ]},
        # A second worker with fewer in-window steps must lose the vote:
        # cross-worker records would double-charge the same wall clock.
        {"worker": "w-other", "steps": steps[:2], "compiles": []},
    ]
    doc = build_explain("req-attr-1", spans, step_docs)
    assert doc is not None
    assert doc["decode_worker"] == "w-dec"
    assert doc["steps_in_window"] == 8
    segs = {s["name"]: s["ms"] for s in doc["segments"]}
    assert segs["queue"] == pytest.approx(9.0)  # engine 4 + prefill 5
    assert segs["admission"] == pytest.approx(2.0)
    assert segs["onboard"] == pytest.approx(1.0)
    assert segs["prefill"] == pytest.approx(15.0)  # 10 remote compute + 5 local
    assert segs["kv_gather"] == pytest.approx(2.0)
    assert segs["kv_wire"] == pytest.approx(5.0)
    assert segs["kv_scatter"] == pytest.approx(3.0)
    assert segs["transfer_wait"] == pytest.approx(4.0)  # remote window slack
    assert segs["decode_compute"] == pytest.approx(30.0)  # 32 minus recompile
    assert segs["gap"] == pytest.approx(15.0)
    assert segs["barrier:pages"] == pytest.approx(1.0)
    assert segs["recompile"] == pytest.approx(2.0)  # warm_cache excluded
    assert segs["frontend"] == pytest.approx(10.0)  # e2e - engine - remote
    assert doc["segments"][-1]["name"] == "unattributed"
    assert doc["unattributed_ms"] == pytest.approx(0.0, abs=0.01)
    assert doc["coverage_frac"] == pytest.approx(1.0, abs=0.001)
    assert doc["within_tolerance"] is True
    assert doc["decode_ms"] == pytest.approx(48.0)


def test_build_explain_clamps_decode_overhang_and_handles_edges():
    from dynamo_tpu.observability.attribution import build_explain

    # No http_request/engine_request anchor -> no budget.
    assert build_explain("nope", [_span_doc("kv_wire", 1.0, 3.0)]) is None

    t0 = 2000.0
    spans = [
        _span_doc("engine_request", t0, 20.0),
        _span_doc("engine_first_token", t0, 5.0),
    ]
    # One step whose gap field spans pre-request idle: the raw decode split
    # (45ms) dwarfs the 15ms decode window and must be scaled down to it,
    # not surface as negative unattributed time.
    step_docs = [{"worker": "w1", "steps": [
        {"ts": t0 + 0.010, "wall_ms": 10.0, "dispatch_ms": 9.0, "gap_ms": 35.0},
    ], "compiles": []}]
    doc = build_explain("req-clamp", spans, step_docs)
    segs = {s["name"]: s["ms"] for s in doc["segments"]}
    assert segs.get("decode_compute", 0.0) + segs.get("gap", 0.0) == pytest.approx(15.0, abs=0.01)
    assert "frontend" not in segs  # anchor IS the engine span
    assert doc["within_tolerance"] is True

    # TTFT == engine duration: a zero decode window zeroes the decode split.
    spans2 = [
        _span_doc("engine_request", t0, 10.0),
        _span_doc("engine_first_token", t0, 10.0),
    ]
    doc2 = build_explain("req-zero-decode", spans2, step_docs)
    segs2 = {s["name"]: s["ms"] for s in doc2["segments"]}
    assert "decode_compute" not in segs2 and "gap" not in segs2
    assert segs2["prefill"] == pytest.approx(10.0)
    assert doc2["within_tolerance"] is True


def test_loss_cause_vocabulary_pinned_to_barriers():
    from dynamo_tpu.engine.core import BARRIER_REASONS
    from dynamo_tpu.observability import EXTRA_LOSS_CAUSES, LOSS_CAUSES  # lazy export

    assert LOSS_CAUSES[: len(BARRIER_REASONS)] == tuple(BARRIER_REASONS)
    assert LOSS_CAUSES[len(BARRIER_REASONS):] == EXTRA_LOSS_CAUSES
    assert len(set(LOSS_CAUSES)) == len(LOSS_CAUSES)
    assert {"queue", "admission", "onboard_stall", "preempt", "recompile", "gap"} <= set(LOSS_CAUSES)


def test_engine_lost_time_covers_noncompute_wall():
    """The fleet-wide ledger (acceptance criterion): after serving traffic,
    the per-cause lost-time totals explain >= 90% of the engine's
    non-compute wall time (wall + gap - dispatch), every cause in the
    pinned vocabulary."""
    from dynamo_tpu.engine.core import EngineConfig, EngineCore
    from dynamo_tpu.mocker import MockRunner
    from dynamo_tpu.observability.attribution import LOSS_CAUSES
    from dynamo_tpu.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )

    runner = MockRunner(num_pages=64, page_size=16, realtime=False)
    core = EngineCore(runner, EngineConfig(
        num_pages=64, page_size=16, max_batch_size=4, max_seq_len=256,
        chunk_prefill_tokens=32, enable_prefix_caching=False,
    ))
    for _ in range(3):
        core.add_request(PreprocessedRequest(
            token_ids=list(range(1, 25)),
            sampling=SamplingOptions(temperature=0.0),
            stop=StopConditions(max_tokens=12, ignore_eos=True),
        ))
    steps = 0
    while core.has_work and steps < 200:
        core.step()
        steps += 1
    assert not core.has_work

    assert set(core.lost_time_ms) <= set(LOSS_CAUSES)
    noncompute = core.step_wall_ms_total + core.step_gap_ms_sum - core.step_dispatch_ms_total
    step_lost = sum(
        ms for cause, ms in core.lost_time_ms.items()
        if cause not in ("queue", "admission")
    )
    if noncompute > 0.0:
        assert step_lost >= 0.9 * noncompute
    # The sentinel rode the same step stream without firing on quiet load.
    assert core.sentinel is not None
    assert core.sentinel.fired == {}


# -- anomaly sentinel ---------------------------------------------------------


def _feed(sent, *, n=1, recompiles=0, shortfall=0, barrier=False, gap=1.0):
    for _ in range(n):
        sent.observe_step(
            wall_ms=5.0, gap_ms=gap, barrier=barrier, outputs=3, decode_rows=3,
            recompiles=recompiles, shortfall_pages=shortfall,
        )


def test_anomaly_sentinel_quiet_stream_never_fires():
    from dynamo_tpu.config import AnomalySettings
    from dynamo_tpu.observability.anomaly import AnomalySentinel

    sent = AnomalySentinel(AnomalySettings(window=16, min_samples=32))
    _feed(sent, n=400)
    assert sent.active == {} and sent.fired == {}


def test_anomaly_sentinel_recompile_storm_fires_once_then_clears():
    from dynamo_tpu.config import AnomalySettings
    from dynamo_tpu.observability.anomaly import AnomalySentinel
    from dynamo_tpu.observability.flight import ANOMALY

    records = []
    flight = SimpleNamespace(record=lambda kind, **f: records.append((kind, f)))
    sent = AnomalySentinel(
        AnomalySettings(window=16, min_samples=32, clear_after=8), flight=flight
    )
    _feed(sent, n=64)
    # A storm: the cumulative compile counter jumps inside one window.
    for i in range(16):
        _feed(sent, recompiles=i)
    assert "recompile_storm" in sent.active
    assert sent.fired.get("recompile_storm") == 1  # one rising edge, no flap
    storm_records = [f for kind, f in records if kind == ANOMALY]
    assert [f["anomaly"] for f in storm_records] == ["recompile_storm"]
    assert storm_records[0]["value"] >= storm_records[0]["threshold"]
    # Hysteresis: clear_after consecutive quiet steps retire the alert but
    # the fired counter keeps the history.
    _feed(sent, n=24, recompiles=15)
    assert "recompile_storm" not in sent.active
    assert sent.fired.get("recompile_storm") == 1


def test_anomaly_sentinel_barrier_frac_spike_fires():
    from dynamo_tpu.config import AnomalySettings
    from dynamo_tpu.observability.anomaly import AnomalySentinel

    sent = AnomalySentinel(AnomalySettings(window=16, min_samples=32))
    _feed(sent, n=64)  # quiet baseline arms the relative detectors
    _feed(sent, n=16, barrier=True)
    assert "barrier_frac_spike" in sent.active
    assert sent.active["barrier_frac_spike"]["value"] >= 0.5
    assert sent.fired["barrier_frac_spike"] == 1
    # The spike also shows up as gap-free barrier steps, never as a goodput
    # drop (outputs stayed constant).
    assert "goodput_drop" not in sent.fired


def test_anomaly_kinds_exported():
    from dynamo_tpu.observability import ANOMALY_KINDS

    assert set(ANOMALY_KINDS) == {
        "barrier_frac_spike", "step_gap_regression", "goodput_drop",
        "recompile_storm", "onboard_shortfall_burst",
    }


# -- timeline assembly --------------------------------------------------------


def test_assemble_timeline_orders_and_links():
    t0 = 1000.0
    tid = "t" * 32
    spans = [
        {"name": "kv_wire", "trace_id": tid, "span_id": "c" * 16, "parent_id": "b" * 16,
         "start_ts": t0 + 0.020, "duration_ms": 5.0, "status": "ok"},
        {"name": "http_request", "trace_id": tid, "span_id": "a" * 16, "parent_id": None,
         "start_ts": t0, "duration_ms": 50.0, "status": "ok"},
        {"name": "remote_prefill", "trace_id": tid, "span_id": "b" * 16, "parent_id": "a" * 16,
         "start_ts": t0 + 0.010, "duration_ms": 30.0, "status": "ok"},
    ]
    doc = assemble_timeline("req-1", spans)
    assert doc["trace_ids"] == [tid]
    assert [s["name"] for s in doc["spans"]] == ["http_request", "remote_prefill", "kv_wire"]
    assert [s["offset_ms"] for s in doc["spans"]] == [0.0, 10.0, 20.0]
    root = doc["spans"][0]
    assert root["root"] is True and root["children"] == [1]
    assert doc["spans"][1]["children"] == [2]
    assert doc["duration_ms"] == 50.0
    assert all("parent_evicted" not in s for s in doc["spans"])


def test_assemble_timeline_surfaces_orphans_of_evicted_parents():
    """Regression (ISSUE 15 satellite): a span whose parent fell out of the
    bounded ring used to hang the tree — it must surface at top level,
    flagged parent_evicted, with its own children intact."""
    t0 = 3000.0
    tid = "d" * 32
    spans = [
        {"name": "engine_request", "trace_id": tid, "span_id": "a" * 16,
         "parent_id": "gone000000000000", "start_ts": t0, "duration_ms": 9.0,
         "status": "ok"},
        {"name": "kv_scatter", "trace_id": tid, "span_id": "b" * 16,
         "parent_id": "a" * 16, "start_ts": t0 + 0.001, "duration_ms": 2.0,
         "status": "ok"},
    ]
    doc = assemble_timeline("req-orphan", spans)
    orphan = doc["spans"][0]
    assert orphan["name"] == "engine_request"
    assert orphan["root"] is True and orphan["parent_evicted"] is True
    assert orphan["children"] == [1]
    assert "parent_evicted" not in doc["spans"][1]


def test_span_buffer_eviction_keeps_children_visible(monkeypatch):
    """An undersized ring (DYN_SPAN_BUFFER) evicting the root must not make
    its surviving children vanish from the assembled timeline."""
    import dynamo_tpu.tracing as tracing

    monkeypatch.setenv("DYN_SPAN_BUFFER", "2")
    buf = tracing.SpanBuffer(tracing._buffer_capacity())
    assert buf._spans.maxlen == 2
    monkeypatch.setattr(tracing, "SPANS", buf)
    rid = "evict-regress-1"
    root = Span("http_request", request_id=rid)
    with root:
        pass
    with Span("engine_request", trace=root.context, request_id=rid) as eng:
        pass
    with Span("engine_first_token", trace=eng.context, request_id=rid):
        pass
    spans = buf.query(request_id=rid)
    assert {s["name"] for s in spans} == {"engine_request", "engine_first_token"}
    doc = assemble_timeline(rid, spans)
    by_name = {s["name"]: s for s in doc["spans"]}
    assert by_name["engine_request"]["root"] is True
    assert by_name["engine_request"]["parent_evicted"] is True
    assert by_name["engine_first_token"].get("parent_evicted") is None
    assert doc["span_count"] == 2


async def test_debug_traces_endpoint_assembles_mocked_disagg_hop():
    """GET /debug/traces/{id}: frontend-local spans + a mocked remote
    prefill worker's spans merge into one timeline under one trace_id."""
    from dynamo_tpu.frontend.http import HttpService
    from dynamo_tpu.frontend.metrics import FrontendMetrics
    from dynamo_tpu.frontend.model_manager import ModelManager

    rid = "mock-disagg-1"
    root = Span("http_request", request_id=rid, model="m", endpoint="completions")
    with root:
        with Span("router_decision", trace=root.context, request_id=rid):
            pass

    # The "remote process": span docs as a prefill worker's SpanQueryService
    # would return them (same trace_id, linked under the frontend root).
    now = time.time()
    remote = [
        {"name": "prefill_exec", "trace_id": root.trace_id, "span_id": "e" * 16,
         "parent_id": root.span_id, "request_id": rid, "start_ts": now + 0.01,
         "duration_ms": 20.0, "status": "ok", "host": "prefill-host"},
        {"name": "kv_wire", "trace_id": root.trace_id, "span_id": "f" * 16,
         "parent_id": "e" * 16, "request_id": rid, "start_ts": now + 0.02,
         "duration_ms": 4.0, "status": "ok", "host": "prefill-host"},
    ]

    class FakeTelemetry:
        async def collect_spans(self, *, request_id=None, trace_id=None):
            if request_id is not None:
                return [dict(s) for s in remote if s["request_id"] == request_id]
            return [dict(s) for s in remote if s["trace_id"] == trace_id]

        async def collect_metrics_texts(self):
            return []

    service = HttpService(ModelManager(), metrics=FrontendMetrics(), telemetry=FakeTelemetry())
    port = await service.start("127.0.0.1", 0)
    try:
        async with aiohttp.ClientSession() as s:
            async with s.get(f"http://127.0.0.1:{port}/debug/traces/{rid}") as r:
                assert r.status == 200
                doc = await r.json()
            async with s.get(f"http://127.0.0.1:{port}/debug/traces/no-such-request") as r:
                assert r.status == 404
    finally:
        await service.stop()

    assert doc["request_id"] == rid
    assert doc["trace_ids"] == [root.trace_id]  # one trace across both processes
    names = [s["name"] for s in doc["spans"]]
    assert set(names) >= {"http_request", "router_decision", "prefill_exec", "kv_wire"}
    assert doc["span_count"] == len(names) == len({s["span_id"] for s in doc["spans"]})
    hosts = {s.get("host") for s in doc["spans"]}
    assert "prefill-host" in hosts
    by_name = {s["name"]: s for s in doc["spans"]}
    assert by_name["http_request"]["root"] is True
    assert names.index("prefill_exec") < names.index("kv_wire")


async def test_debug_explain_endpoint_serves_budget():
    """GET /debug/explain/{id}: the frontend joins the span union with the
    debug_explain fan-out's windowed STEP records into a segment budget."""
    from dynamo_tpu.frontend.http import HttpService
    from dynamo_tpu.frontend.metrics import FrontendMetrics
    from dynamo_tpu.frontend.model_manager import ModelManager

    rid = "mock-explain-1"
    root = Span("http_request", request_id=rid, model="m", endpoint="completions")
    with root:
        time.sleep(0.05)
    start = SPANS.query(request_id=rid)[-1]["start_ts"]

    class FakeTelemetry:
        def __init__(self):
            self.windows = []

        async def collect_spans(self, *, request_id=None, trace_id=None):
            return []

        async def collect_metrics_texts(self):
            return []

        async def collect_explain(self, *, t0=None, t1=None):
            self.windows.append((t0, t1))
            # One step overhanging the ~50ms window: the clamp scales the
            # decode split down to it, so the budget closes exactly.
            return [{"worker": "w-x", "steps": [
                {"ts": start + 0.010, "wall_ms": 60.0, "dispatch_ms": 55.0,
                 "gap_ms": 0.0, "overlap_mode": "overlapped", "barrier_reason": ""},
            ], "compiles": [], "lost_time_ms": {}}]

    telemetry = FakeTelemetry()
    service = HttpService(ModelManager(), metrics=FrontendMetrics(), telemetry=telemetry)
    port = await service.start("127.0.0.1", 0)
    try:
        async with aiohttp.ClientSession() as s:
            async with s.get(f"http://127.0.0.1:{port}/debug/explain/{rid}") as r:
                assert r.status == 200
                doc = await r.json()
            async with s.get(f"http://127.0.0.1:{port}/debug/explain/no-such") as r:
                assert r.status == 404
    finally:
        await service.stop()

    assert doc["request_id"] == rid
    assert doc["decode_worker"] == "w-x"
    assert doc["steps_in_window"] == 1
    assert doc["segments"][-1]["name"] == "unattributed"
    assert doc["within_tolerance"] is True
    assert doc["coverage_frac"] == pytest.approx(1.0, abs=0.01)
    # The fan-out was windowed to the request's span bounds (padded 1s).
    (t0, t1), = telemetry.windows
    assert t0 <= start and t1 >= start + 0.05


# -- full-stack disagg timeline + federation (acceptance criterion) -----------


@pytest.mark.e2e
async def test_disagg_request_yields_single_trace_timeline(monkeypatch):
    """A disaggregated request (remote prefill via the wire path + local
    decode) produces one /debug/traces timeline: spans from the decode side
    and the prefill worker under a single trace_id, including the
    KV-transfer phase spans; /metrics federates the engine registries."""
    from dynamo_tpu.disagg import device_transfer, prefill_worker
    from dynamo_tpu.disagg.router import DisaggConfig
    from dynamo_tpu.launch import run_local
    from dynamo_tpu.observability.attribution import LOSS_CAUSES

    # Force the chunked TCP wire path (the phase-span source): disable the
    # same-process device shortcut and the cross-process device pull.
    monkeypatch.setattr(device_transfer.REGISTRY, "lookup", lambda addr: None)

    async def no_pull(*a, **kw):
        raise RuntimeError("pull disabled for wire-path test")

    monkeypatch.setattr(prefill_worker, "send_pull_offer", no_pull)

    disagg = DisaggConfig(max_local_prefill_length=24, min_remote_prefill_blocks=1)
    handles = await run_local(
        "test-tiny", port=0, num_workers=1, num_prefill_workers=1,
        disagg=disagg, num_pages=64, max_batch_size=8,
    )
    base = f"http://127.0.0.1:{handles['port']}"
    rid = "disagg-trace-e2e-1"
    try:
        async with aiohttp.ClientSession() as s:
            body = {
                "model": "test-tiny", "prompt": "r" * 48, "max_tokens": 4,
                "temperature": 0, "request_id": rid,
            }
            traceparent = TraceContext.new().to_traceparent()
            async with s.post(
                base + "/v1/completions", json=body, headers={"traceparent": traceparent}
            ) as r:
                assert r.status == 200, await r.text()
                # Satellite: the unary response surfaces the trace id, so
                # /debug/traces and /debug/explain are reachable without
                # grepping logs — and it is the ingested traceparent's id.
                assert r.headers["x-dynamo-trace-id"] == traceparent.split("-")[1]

            # The prefill worker's final phase spans land just after the
            # decode response unblocks — poll the timeline briefly.
            needed = {"http_request", "remote_prefill", "prefill_exec", "kv_wire", "kv_scatter"}
            doc = None
            for _ in range(100):
                async with s.get(f"{base}/debug/traces/{rid}") as r:
                    if r.status == 200:
                        doc = await r.json()
                        if needed <= {sp["name"] for sp in doc["spans"]}:
                            break
                await asyncio.sleep(0.05)
            assert doc is not None, "no timeline assembled"
            names = {sp["name"] for sp in doc["spans"]}
            assert needed <= names, names
            # Every hop under ONE trace, rooted at the ingested traceparent.
            assert doc["trace_ids"] == [traceparent.split("-")[1]]
            assert "engine_queue_wait" in names  # decode-side admission span
            statuses = {sp["status"] for sp in doc["spans"]}
            assert statuses == {"ok"}

            # Attribution (ISSUE 15 acceptance): the explain budget's
            # segments must sum to within tolerance of the measured E2E,
            # joined from this worker's live flight STEP records.
            explain = None
            for _ in range(100):
                async with s.get(f"{base}/debug/explain/{rid}") as r:
                    if r.status == 200:
                        explain = await r.json()
                        if explain.get("within_tolerance") and explain.get("steps_in_window", 0) > 0:
                            break
                await asyncio.sleep(0.05)
            assert explain is not None, "no explain budget assembled"
            assert explain["within_tolerance"] is True, explain
            assert explain["steps_in_window"] > 0
            seg_names = [sg["name"] for sg in explain["segments"]]
            assert seg_names[-1] == "unattributed"  # residual always reported
            assert abs(explain["unattributed_ms"]) <= 0.1 * explain["e2e_ms"]
            assert explain["trace_id"] == traceparent.split("-")[1]
            known = set(LOSS_CAUSES) | {
                "queue", "admission", "onboard", "prefill", "transfer_wait",
                "decode_compute", "recompile", "frontend", "unattributed",
                "kv_gather", "kv_pack", "kv_wire", "kv_scatter",
            }
            for name in seg_names:
                base_name = name.split(":", 1)[1] if name.startswith("barrier:") else name
                assert base_name in known, name

            # Flight recorder (ISSUE 4): force a mixed step — hold one
            # stream in decode while a second short prompt (below the local
            # prefill threshold) is admitted, so its chunk rows fuse with
            # the live decode rows in one dispatch.
            async with s.post(
                base + "/v1/completions",
                json={"model": "test-tiny", "prompt": "s" * 8, "max_tokens": 48,
                      "temperature": 0, "stream": True},
            ) as r1:
                assert r1.status == 200
                # The SSE response carries the trace id too (satellite).
                assert len(r1.headers["x-dynamo-trace-id"]) == 32
                await r1.content.readany()  # first chunk: decode is live
                async with s.post(
                    base + "/v1/completions",
                    json={"model": "test-tiny", "prompt": "t" * 12, "max_tokens": 4,
                          "temperature": 0},
                ) as r2:
                    assert r2.status == 200, await r2.text()
                async for _ in r1.content:  # drain the stream to completion
                    pass

            flight_doc = None
            records: list[dict] = []
            for _ in range(100):
                async with s.get(base + "/debug/flight/all") as r:
                    if r.status == 200:
                        flight_doc = await r.json()
                        records = [
                            rec
                            for w in flight_doc["workers"].values()
                            for rec in w["records"]
                        ]
                        if any(rec["kind"] == "compile" for rec in records) and any(
                            rec.get("step_kind") == "mixed" for rec in records
                        ):
                            break
                await asyncio.sleep(0.05)
            assert flight_doc is not None, "no flight rings collected"
            kinds = {rec["kind"] for rec in records}
            assert "step" in kinds and "compile" in kinds, kinds
            assert any(rec.get("step_kind") == "mixed" for rec in records), (
                sorted({rec.get("step_kind") for rec in records if rec["kind"] == "step"})
            )
            # Records are ordered (monotonic seq) within each worker's ring,
            # and step records carry the per-step composition fields.
            for w in flight_doc["workers"].values():
                seqs = [rec["seq"] for rec in w["records"]]
                assert seqs == sorted(seqs)
            step_rec = next(rec for rec in records if rec["kind"] == "step")
            for key in ("decode_rows", "chunk_tokens", "free_pages", "wall_ms", "preemptions"):
                assert key in step_rec, step_rec
            compile_rec = next(rec for rec in records if rec["kind"] == "compile")
            assert compile_rec["program"] and compile_rec["reason"] in ("new_shape", "warm_cache")
            # Single-worker addressing: {worker} narrows the fan-out.
            one = next(iter(flight_doc["workers"]))
            async with s.get(f"{base}/debug/flight/{one}?last=5&kind=step") as r:
                assert r.status == 200
                narrowed = await r.json()
            assert set(narrowed["workers"]) == {one}
            assert len(narrowed["workers"][one]["records"]) <= 5
            assert all(
                rec["kind"] == "step" for rec in narrowed["workers"][one]["records"]
            )

            # Federation: the frontend /metrics render includes both engine
            # registries' families with per-worker labels, plus the
            # SLO-conditioned goodput accounting (ISSUE 4).
            async with s.get(base + "/metrics") as r:
                text = await r.text()
            assert "dynamo_frontend_requests_total" in text
            assert "dynamo_engine_step_decode_rows" in text
            assert "dynamo_engine_prefill_queue_depth" in text
            assert "dynamo_goodput_tokens_total" in text
            assert "dynamo_output_tokens_total" in text
            assert "dynamo_engine_recompiles_total" in text
            assert "dynamo_frontend_ttft_quantile_seconds" in text
            # The time-loss ledger federates with per-cause labels drawn
            # from the pinned vocabulary (ISSUE 15).
            assert "dynamo_engine_lost_time_seconds_total" in text
            assert 'dynamo_engine_step_time_seconds_total' in text
            causes = {
                line.split('cause="', 1)[1].split('"', 1)[0]
                for line in text.splitlines()
                if line.startswith("dynamo_engine_lost_time_seconds_total{")
            }
            assert causes and causes <= set(LOSS_CAUSES), causes
            assert 'dynamo_kv_transfer_phase_seconds_count{phase="wire"' in text
            assert text.count("# TYPE dynamo_engine_pages_total gauge") == 1
            workers = {
                line.split('worker="', 1)[1].split('"', 1)[0]
                for line in text.splitlines()
                if line.startswith("dynamo_engine_pages_total{")
            }
            assert len(workers) == 2, workers  # decode + prefill registries
    finally:
        await handles["http"].stop()
        await handles["watcher"].close()
        for svc in handles["services"]:
            await svc.close()
        await handles["runtime"].close()


# -- STEP records: which formulation the routed experts took -------------------


@pytest.mark.parametrize("case, want", [("dense", ""), ("moe_bf16", "widened"), ("moe_int8_kernel", "fused")])
def test_step_records_carry_moe_path_beside_attn_path(case, want, monkeypatch):
    """Every STEP record that dispatched says which formulation its routed
    experts took, from the predicate the forward dispatches on
    (``parallel/moe.experts_path``): "" for a dense model, "widened" for
    the XLA formulations, "fused" where the int8 kernel runs (interpret mode
    stands in for the TPU here)."""
    import dataclasses

    from dynamo_tpu.engine.core import EngineConfig, EngineCore
    from dynamo_tpu.engine.runner import ModelRunner
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import PRESETS
    from dynamo_tpu.models.quant import quantize_params
    from dynamo_tpu.observability.flight import STEP
    from dynamo_tpu.protocols.common import PreprocessedRequest, SamplingOptions, StopConditions

    if case == "dense":
        cfg = PRESETS["test-tiny"]
        params = llama.init_params(cfg, 0)
    else:
        cfg = dataclasses.replace(PRESETS["test-tiny-moe"], hidden_size=128, moe_intermediate_size=128)
        params = llama.init_params(cfg, 0)
    if case == "moe_int8_kernel":
        params = quantize_params(params, mode="int8")
        monkeypatch.setenv("DYNAMO_PALLAS_INTERPRET", "1")
    runner = ModelRunner(cfg, params, num_pages=32, page_size=4, max_batch_size=4, prefill_bucket=16,
                         attn_impl="reference")
    assert runner.moe_path == want
    core = EngineCore(runner, EngineConfig(num_pages=32, page_size=4, max_batch_size=4,
                                           max_prefill_tokens=64, max_seq_len=64))
    core.add_request(PreprocessedRequest(
        token_ids=[1, 2, 3, 4, 5], sampling=SamplingOptions(temperature=0.0), stop=StopConditions(max_tokens=3)))
    for _ in range(16):
        if not core.has_work:
            break
        core.step()
    records = core.flight.snapshot(kind=STEP)
    dispatched = [r for r in records if r["attn_path"]]
    assert dispatched and all("moe_path" in r for r in records)
    assert {r["moe_path"] for r in dispatched} == {want}
    assert all(r["moe_path"] == "" for r in records if not r["attn_path"])


@pytest.mark.parametrize("case", ["dense", "narrow", "grouped_wide"])
def test_step_records_carry_router_select_beside_moe_path(case, monkeypatch):
    """Every STEP record that dispatched says how the router took the step's
    experts: "" for a dense model, "sort" (``lax.top_k``) for a narrow router
    at any size, and for a wide, group-limited one "passes" on the step that
    holds the prompt's 16 tokens and "sort" on the one-row decode steps. The
    label and ``route_tokens`` read one predicate on the same numbers: every
    program traced asked ``parallel/moe.router_select`` with its record's
    ``step_tokens`` and got the record's word."""
    import dataclasses

    from dynamo_tpu.engine.core import EngineConfig, EngineCore
    from dynamo_tpu.engine.runner import ModelRunner
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import PRESETS
    from dynamo_tpu.observability.flight import STEP
    from dynamo_tpu.parallel import moe
    from dynamo_tpu.protocols.common import PreprocessedRequest, SamplingOptions, StopConditions

    cfg = {"dense": PRESETS["test-tiny"], "narrow": PRESETS["test-tiny-moe"],
           "grouped_wide": dataclasses.replace(PRESETS["test-tiny-v3"], num_experts=256, moe_n_group=8,
                                               moe_topk_group=4, moe_intermediate_size=8)}[case]
    traced = {}
    predicate = moe.router_select

    def recording(tokens, outputs, k):
        traced[tokens, outputs, k] = predicate(tokens, outputs, k)
        return traced[tokens, outputs, k]

    monkeypatch.setattr(moe, "router_select", recording)  # what route_tokens asks while a program is traced
    runner = ModelRunner(cfg, llama.init_params(cfg, 0), num_pages=32, page_size=4, max_batch_size=4,
                         prefill_bucket=16, attn_impl="reference")
    core = EngineCore(runner, EngineConfig(num_pages=32, page_size=4, max_batch_size=4,
                                           max_prefill_tokens=64, max_seq_len=64))
    core.add_request(PreprocessedRequest(
        token_ids=list(range(1, 14)), sampling=SamplingOptions(temperature=0.0), stop=StopConditions(max_tokens=3)))
    for _ in range(16):
        if not core.has_work:
            break
        core.step()
    records = core.flight.snapshot(kind=STEP)
    dispatched = [r for r in records if r["attn_path"]]
    assert dispatched and all(r["router_select"] == "" for r in records if not r["attn_path"])
    said = {(r["step_tokens"], r["router_select"]) for r in dispatched}
    want = {"dense": {""}, "narrow": {"sort"}, "grouped_wide": {"passes", "sort"}}[case]
    assert {word for _, word in said} == want, said
    if case == "dense":
        assert not traced
        return
    outputs, k = cfg.num_experts, cfg.num_experts_per_token
    assert {(tokens, outputs, k): word for tokens, word in said} == traced
    if case == "grouped_wide":
        assert (16, "passes") in said and min(said)[1] == "sort"


@pytest.mark.parametrize("case", ["whole_six_of_eight", "whole_full_bucket", "held_six_of_eight", "dense_six_of_eight"])
def test_step_records_count_the_positions_the_expert_layers_route_nowhere(case):
    """``moe_pad_positions``: the padding among a dispatched program's token
    positions, which a routed model's expert layers (either family) route to
    no expert. Six rows decoding in the 8-row program say 2, a full bucket 0,
    a dense model 0 whatever its padding, a step that dispatched nothing 0."""
    from dynamo_tpu.engine.core import EngineConfig, EngineCore
    from dynamo_tpu.engine.runner import ModelRunner
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import PRESETS
    from dynamo_tpu.observability.flight import STEP
    from dynamo_tpu.protocols.common import PreprocessedRequest, SamplingOptions, StopConditions

    model, rows = {"whole_six_of_eight": ("test-tiny-moe", 6), "whole_full_bucket": ("test-tiny-moe", 8),
                   "held_six_of_eight": ("test-tiny-scmoe", 6), "dense_six_of_eight": ("test-tiny", 6)}[case]
    cfg = PRESETS[model]
    runner = ModelRunner(cfg, llama.init_params(cfg, 0), num_pages=64, page_size=4, max_batch_size=8,
                         prefill_bucket=16, attn_impl="reference")
    core = EngineCore(runner, EngineConfig(num_pages=64, page_size=4, max_batch_size=8,
                                           max_prefill_tokens=64, max_seq_len=64))
    for i in range(rows):
        core.add_request(PreprocessedRequest(
            token_ids=list(range(1 + i, 6 + i)), sampling=SamplingOptions(temperature=0.0),
            stop=StopConditions(max_tokens=6, ignore_eos=True)))
    for _ in range(64):
        if not core.has_work:
            break
        core.step()
    records = core.flight.snapshot(kind=STEP)
    decodes = [r for r in records if r["step_tokens"] and r["decode_rows"] == rows and not r["chunk_rows"]]
    assert len(decodes) >= 3 and all(r["step_tokens"] == 8 for r in decodes)
    want = 0 if model == "test-tiny" else 8 - rows
    assert [r["moe_pad_positions"] for r in decodes] == [want] * len(decodes)
    assert all(type(r["moe_pad_positions"]) is int for r in records)
    for r in records:
        routed = bool(r["moe_path"])
        assert r["moe_pad_positions"] == routed * (r["step_tokens"] - r["decode_rows"] - r["chunk_tokens"]), r
    assert all(r["moe_pad_positions"] == 0 for r in records if not r["step_tokens"])
