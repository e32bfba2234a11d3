"""EngineMetrics: the Prometheus registry for engine-layer observables.

The frontend registry (``frontend/metrics.py``) covers the HTTP edge; this
one covers what happens *behind* it, per worker process:

- **Step composition** — the fused-dispatch shape of the last engine step
  (decode rows vs prefill chunk rows/tokens, from ``core.last_step_info``)
  plus the cumulative mixed-step / stall-violation counts that quantify the
  stall-free invariant.
- **Page pool** — utilization, fragmentation (reclaimable-but-cached share
  of idle pages), prefix-cache hit ratio, preemptions.
- **Admission** — requests waiting/running, intake rejections, and the
  disagg prefill queue depth.
- **KV transfer** — cumulative blocks/bytes and a per-phase duration
  histogram (``gather|pack|wire|scatter``) fed by the disagg wire path.

Every family carries a ``worker`` label so the frontend can federate many
workers' registries into one ``/metrics`` document without sample
collisions. Everything that has a cheap engine-side source of truth is
synced on scrape (the ``kernel_fallbacks`` idiom) rather than
double-counted; only the phase histogram is observed at record time.
``render()`` is async so the prefill queue depth (a discovery-store scan)
can be polled during the scrape.
"""

from __future__ import annotations

import logging
import weakref
from typing import Any, Awaitable, Callable

from prometheus_client import Counter, CollectorRegistry, Gauge, Histogram, generate_latest

from dynamo_tpu import tracing

logger = logging.getLogger(__name__)

_PHASE_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0)

#: KV-transfer phases tracked by the wire-path histogram.
KV_PHASES = ("gather", "pack", "wire", "scatter")


class EngineMetrics:
    """Per-worker engine telemetry registry.

    Bind engine internals after construction (``bind_core`` / ``bind_transfer``
    / ``bind_queue_depth``); unbound families simply stay at their defaults,
    so the registry is safe to serve from any worker role.
    """

    def __init__(self, registry: CollectorRegistry | None = None, *, worker: str = "local") -> None:
        self.registry = registry or CollectorRegistry()
        self.worker = worker
        ns = "dynamo_engine"

        def gauge(name: str, doc: str) -> Gauge:
            return Gauge(name, doc, ["worker"], registry=self.registry).labels(worker)

        # Step composition: the last fused dispatch's shape. Gauges, not
        # counters — the interesting signal is the *mix* per step.
        self.step_decode_rows = gauge(f"{ns}_step_decode_rows", "Decode rows in the last engine step")
        self.step_chunk_rows = gauge(f"{ns}_step_chunk_rows", "Prefill chunk rows in the last engine step")
        self.step_chunk_tokens = gauge(f"{ns}_step_chunk_tokens", "Prefill tokens in the last engine step")
        self.step_decodable = gauge(f"{ns}_step_decodable_seqs", "Sequences decodable at the last step")
        # Cumulative engine counters, synced from the core on scrape (the
        # core already counts; a prometheus Counter would double-book).
        self.mixed_steps = gauge(f"{ns}_mixed_steps_total", "Engine steps that fused prefill chunks with decodes")
        self.chunk_steps_split = gauge(
            f"{ns}_chunk_steps_split_total",
            "Steps with a prefill chunk row whose program held one token position per decode row",
        )
        self.chunk_steps_rows_x_t = gauge(
            f"{ns}_chunk_steps_rows_x_t_total",
            "Steps with a prefill chunk row whose program padded every row to the chunk",
        )
        self.stall_violations = gauge(
            f"{ns}_stall_violations_total", "Prefill-only dispatches that starved decodable sequences"
        )
        self.preemptions = gauge(f"{ns}_preemptions_total", "Sequences preempted (pages reclaimed under pressure)")
        self.admission_rejections = gauge(f"{ns}_admission_rejections_total", "Requests refused at engine intake")
        self.spec_tokens_proposed = gauge(
            f"{ns}_spec_tokens_proposed_total", "Draft tokens proposed by the speculative decoder"
        )
        self.spec_tokens_accepted = gauge(
            f"{ns}_spec_tokens_accepted_total", "Draft tokens verified and emitted by the speculative decoder"
        )
        # Page pool.
        self.pages_total = gauge(f"{ns}_pages_total", "Allocatable KV pages")
        self.pages_free = gauge(f"{ns}_pages_free", "Pages on the free list")
        self.pages_cached = gauge(f"{ns}_pages_cached", "Evictable prefix-cache pages (refcount 0)")
        self.pages_active = gauge(f"{ns}_pages_active", "Pages referenced by live sequences")
        self.page_utilization = gauge(f"{ns}_page_utilization_ratio", "active_pages / total_pages")
        # A model with recurrent layers: its second kind of per-sequence state (all 0 for every other model).
        self.state_slots_total = gauge(f"{ns}_state_slots", "Recurrent-state slots a running sequence can take (the null slot apart)")
        self.state_slots_live = gauge(f"{ns}_state_slots_live", "Recurrent-state slots held by live sequences")
        # A model with a page pool per layer kind: the sliding layers' pool beside the one above (all 0 for every other model).
        self.window_pages_total = gauge(f"{ns}_window_pages_total", "Allocatable pages of the window pool (sliding layers of a mixed model)")
        self.window_pages_free = gauge(f"{ns}_window_pages_free", "Window-pool pages on the free list")
        self.window_pages_cached = gauge(f"{ns}_window_pages_cached", "Evictable prefix-cache pages of the window pool")
        self.window_pages_active = gauge(f"{ns}_window_pages_active", "Window-pool pages referenced by live sequences")
        self.window_pages_released = gauge(
            f"{ns}_window_pages_released_total", "Window-pool pages given back as they fell wholly behind the window")
        self.prefix_matching_off = gauge(
            f"{ns}_prefix_matching_off_by_model",
            "1 where prefix caching is configured on and the model switches matching off (recurrent layers: pages alone bring back no state)",
        )
        self.page_fragmentation = gauge(
            f"{ns}_page_fragmentation_ratio",
            "cached / (free + cached): share of idle pages reclaimable only by eviction",
        )
        self.cache_hit_ratio = gauge(f"{ns}_prefix_cache_hit_ratio", "Prefix-cache block hit ratio (cumulative)")
        # Admission / scheduler occupancy.
        self.requests_waiting = gauge(f"{ns}_requests_waiting", "Admitted requests not yet scheduled")
        self.requests_running = gauge(f"{ns}_requests_running", "Sequences in prefill or decode")
        # SLO admission-control plane (dynamo_tpu/sched). Per-tier queue
        # depth and per-tenant throttle counts are labelled clear-then-set
        # gauges (label sets change as tenants come and go); the rest sync
        # from the controller's cumulative counters on scrape.
        self._admission_queue_depth = Gauge(
            "dynamo_engine_admission_queue_depth",
            "Waiting requests per priority tier in the engine admission queue "
            "(tier 0 = most latency-sensitive; all waiting under tier 0 when "
            "the SLO plane is off)",
            ["worker", "tier"], registry=self.registry,
        )
        self.deadline_misses = gauge(
            f"{ns}_deadline_misses_total",
            "Requests admitted after their EDF deadline (arrival + stretched "
            "TTFT budget) had already passed",
        )
        self._tenant_throttled = Gauge(
            "dynamo_tenant_throttled_total",
            "Admission deferrals charged to a tenant's quota (token bucket "
            "empty or in-flight token cap reached)",
            ["worker", "tenant"], registry=self.registry,
        )
        self.chunk_budget_tokens = gauge(
            f"{ns}_chunk_budget_tokens",
            "Live per-step prefill chunk budget (the ITL-driven controller's "
            "current value; the static chunk_prefill_tokens config when the "
            "SLO plane is off)",
        )
        # XLA compile observability: first executions per (program, reason),
        # synced from the runner's CompileTracker on scrape. Labelled gauge
        # (not Counter) for the same no-double-booking reason as above; the
        # label set is cleared and re-set per scrape so stale pairs drop out.
        self._recompiles = Gauge(
            "dynamo_engine_recompiles_total",
            "First executions of a padded shape bucket per jitted program "
            "(reason: new_shape = the first call took DYN_COMPILE_THRESHOLD_MS "
            "or more, warm_cache = less; whether the persistent cache had the "
            "program is the compile event's `cache`)",
            ["worker", "program", "reason"], registry=self.registry,
        )
        # Attention dispatch path per engine step, synced from the core's
        # cumulative counts on scrape. Same clear-then-set idiom as
        # recompiles so stale (phase, path) pairs drop out.
        self._attn_dispatch = Gauge(
            "dynamo_engine_attn_dispatch_steps_total",
            "Engine steps by attention phase (decode/verify/prefill) and "
            "dispatch path (pallas kernel, reference fallback, ring)",
            ["worker", "phase", "path"], registry=self.registry,
        )
        # The pipelined step loop: device-idle observability.
        # gap_ms is the host window between a step returning and the next
        # dispatch — the time the pipelined loop exists to hide.
        self.step_gap_ms_last = gauge(
            f"{ns}_step_gap_ms",
            "Host gap (ms) between the previous engine step completing and "
            "the latest step's dispatch (detok/stop/schedule time the device "
            "sits idle unless the overlapped loop hides it)",
        )
        self.step_gap_ms_mean = gauge(
            f"{ns}_step_gap_ms_mean",
            "Mean host gap (ms) between consecutive engine steps (cumulative)",
        )
        self._overlap_steps = Gauge(
            "dynamo_engine_overlap_steps_total",
            "Engine steps by pipeline mode: 'overlapped' = the step was "
            "dispatched before harvesting the previous one, 'barrier' = it "
            "dispatched with nothing in flight or fell back to the "
            "synchronous step (fill, cancel, spec, constraint miss, drain)",
            ["worker", "mode"], registry=self.registry,
        )
        self._overlap_barriers = Gauge(
            "dynamo_engine_overlap_barrier_total",
            "Overlap barrier steps by the condition that forced them: "
            "'cancel'/'drain' (in-flight state invalidated), 'spec' (verify "
            "harvest or no async verify), 'prefill' (whole-prompt XOR "
            "mode), 'constraint' (lookahead disabled), 'constraint_miss' "
            "(mask-cache miss or successor fan-out over the lookahead cap), "
            "'runner' (runner cannot chain), 'pages' (lookahead page "
            "reservation failed), 'fill'/'idle' (nothing to chain)",
            ["worker", "reason"], registry=self.registry,
        )
        # Constrained-decode lookahead mask cache (DYN_CONSTRAINT_LOOKAHEAD_
        # TOKENS): hit/miss totals synced from the engine's TokenMaskCache on
        # scrape. The miss rate is the live predictor of 'constraint_miss'
        # barriers — a hot grammar converges to ~100% hits after warm-up.
        self.constraint_mask_cache_hits = gauge(
            f"{ns}_constraint_mask_cache_hits_total",
            "Constrained-decode token-mask cache hits (mask reused for a "
            "machine-state summary already built)",
        )
        self.constraint_mask_cache_misses = gauge(
            f"{ns}_constraint_mask_cache_misses_total",
            "Constrained-decode token-mask cache misses (mask built by "
            "scanning the vocabulary for a new machine-state summary)",
        )
        # Async tier onboarding (DYN_ASYNC_ONBOARD / DYN_CACHE_AWARE):
        # per-tier landed page counts are clear-then-set labelled gauges
        # synced from the core's cumulative dict; the wait histogram is
        # observed from drained per-session samples at scrape time (each
        # session observed exactly once).
        self._onboard_pages = Gauge(
            "dynamo_engine_prefix_onboard_pages_total",
            "KV pages onboarded from the capacity tiers into device pages, "
            "by source tier (g2 host / g3 disk / g4 remote)",
            ["worker", "tier"], registry=self.registry,
        )
        self.onboard_shortfall = gauge(
            f"{ns}_prefix_onboard_shortfall_pages_total",
            "Probed tier pages whose payload fetch came up short (evicted or "
            "faulted between probe and fetch) and fell back to recompute",
        )
        self._onboard_wait = Histogram(
            "dynamo_engine_onboard_wait_seconds",
            "Wall time from onboarding-session start (admission) to its "
            "payloads landing in device pages",
            ["worker"], buckets=_PHASE_BUCKETS, registry=self.registry,
        )
        self._constraint_build = Histogram(
            "dynamo_engine_constraint_mask_build_seconds",
            "Wall time of each cold constrained-decoding mask build (a "
            "machine summary seen for the first time; warm steps are dict "
            "lookups and are not observed)",
            ["worker"], buckets=_PHASE_BUCKETS, registry=self.registry,
        )
        # Time-loss accounting (attribution plane): cumulative seconds the
        # engine charged per loss cause (attribution.LOSS_CAUSES = the pinned
        # barrier vocabulary + queue/admission/onboard_stall/preempt/
        # recompile/gap), plus the step-time totals consumers need to derive
        # non-compute wall time (wall + gap - dispatch) and the unattributed
        # residual. True monotone Counters (so Prometheus rate()/increase()
        # are valid and the ``_total`` sample suffix is honest): each scrape
        # incs by the delta of the core's cumulative ledger since the last
        # sync (tracked in ``_lost_time_synced``/``_step_time_synced``, reset
        # by bind_core so a rebound core's full totals land once).
        self._lost_time = Counter(
            "dynamo_engine_lost_time_seconds",
            "Wall-clock seconds the engine attributes to a latency loss "
            "cause: overlap barrier reasons plus queue (pre-admission "
            "resource wait), admission (quota-gated deferral), onboard_stall "
            "(steps idled on a tier fetch), preempt, recompile (new-shape "
            "compiles on the serving path), and gap (residual host time "
            "between dispatches)",
            ["worker", "cause"], registry=self.registry,
        )
        self._step_time = Counter(
            "dynamo_engine_step_time_seconds",
            "Cumulative engine step time by kind: wall (in-step wall clock), "
            "dispatch (runner dispatch inside steps; equals wall on runners "
            "without a compile tracker), gap (host gap between steps) — "
            "non-compute wall time = wall + gap - dispatch",
            ["worker", "kind"], registry=self.registry,
        )
        self._step_kinds = Counter(
            "dynamo_engine_step_kind_steps",
            "Engine steps recorded, by step kind (mixed / prefill / decode / "
            "drain) — the step-kind histogram behind EngineCore.loss_snapshot",
            ["worker", "kind"], registry=self.registry,
        )
        # Long steps (one whose period is five times its kind's) by cause, and
        # the process's garbage collections by generation: what the host was
        # doing when a step took a tenth of a second (docs/OBSERVABILITY.md,
        # "Long steps and host pauses"). Delta-synced like the ledgers above.
        self._long_steps = Counter(
            "dynamo_engine_long_steps",
            "Engine steps whose period was over five times their kind's "
            "expected period and at least 10 ms over it, by cause: gc, "
            "profiler, compile, or unnamed",
            ["worker", "cause"], registry=self.registry,
        )
        self._long_step_lost = Counter(
            "dynamo_engine_long_step_lost_seconds",
            "Seconds the long steps took beyond their kind's expected period, "
            "by cause: gc, profiler, compile, or unnamed",
            ["worker", "cause"], registry=self.registry,
        )
        self._gc_pauses = Counter(
            "dynamo_host_gc_pauses",
            "Garbage collections this process ran since the first engine was "
            "built, by generation (every collection, however short)",
            ["worker", "generation"], registry=self.registry,
        )
        self._gc_pause_seconds = Counter(
            "dynamo_host_gc_pause_seconds",
            "Seconds this process spent in garbage collections, by generation: "
            "the interpreter is held throughout, on whatever thread collects",
            ["worker", "generation"], registry=self.registry,
        )
        self._lost_time_synced: dict[str, float] = {}
        self._step_time_synced: dict[str, float] = {}
        self._step_kinds_synced: dict[str, int] = {}
        self._long_steps_synced: dict[str, tuple[int, float]] = {}
        self._gc_synced = [(0, 0)] * tracing.GC_GENERATIONS
        # Anomaly sentinel: 1 while a rolling-window detector is active on
        # this worker (hysteresis in the sentinel, not here), keyed by the
        # detector kind; fired totals count rising edges ever.
        self._anomaly_active = Gauge(
            "dynamo_anomaly_active",
            "1 while the worker's anomaly sentinel holds this detector "
            "active (barrier_frac_spike, step_gap_regression, goodput_drop, "
            "recompile_storm, onboard_shortfall_burst)",
            ["worker", "kind"], registry=self.registry,
        )
        self._anomaly_fired = Gauge(
            "dynamo_anomaly_fired_total",
            "Anomaly-sentinel rising edges ever fired, by detector kind",
            ["worker", "kind"], registry=self.registry,
        )
        self._incidents_captured = Gauge(
            "dynamo_incidents_captured_total",
            "Incident bundles this engine wrote to the on-disk store, by "
            "trigger kind (anomaly / crash / slo_burn)",
            ["worker", "kind"], registry=self.registry,
        )
        self.prefill_queue_depth = gauge(
            f"{ns}_prefill_queue_depth", "Unclaimed tasks in the distributed prefill queue"
        )
        self.prefill_requeues = gauge(
            f"{ns}_prefill_requeues_total",
            "Prefill tasks this worker claimed that a failed peer had already been delivered "
            "(requeue-to-peer via claim release or claim-lease expiry)",
        )
        # KV transfer (disagg prefill -> decode migration).
        self.kv_blocks = gauge("dynamo_kv_transfer_blocks_total", "KV blocks ingested into the local cache")
        self.kv_bytes = gauge("dynamo_kv_transfer_bytes_total", "KV bytes received over the transfer path")
        self.kv_streams = gauge("dynamo_kv_transfer_streams_in_flight", "Open v2 chunk-stream sessions")
        self.kv_crc_failures = gauge(
            "dynamo_kv_transfer_crc_failures_total",
            "KV wire payloads that failed the receiver-side crc32 check",
        )
        self.kv_rollbacks = gauge(
            "dynamo_kv_transfer_rollbacks_total",
            "v2 chunk-stream sessions rolled back (sender death, protocol error, unrecovered corruption)",
        )
        self._kv_phase = Histogram(
            "dynamo_kv_transfer_phase_seconds",
            "Per-phase KV transfer duration (sender gather/pack/wire, receiver scatter)",
            ["worker", "phase"], buckets=_PHASE_BUCKETS, registry=self.registry,
        )
        # KV wire v3 (striped duplex data plane).
        self.kv_wire_streams = gauge(
            "dynamo_kv_wire_streams",
            "Open striped KV data-plane connections (wire v3 stripes) on this worker",
        )
        self.kv_wire_sessions = gauge(
            "dynamo_kv_wire_inflight_sessions",
            "KV transfer sessions currently in flight on this worker (v2 + v3)",
        )
        self.kv_wire_staged = gauge(
            "dynamo_kv_wire_staged_bytes",
            "Host bytes held in out-of-order reassembly staging across sessions "
            "(bounded by DYN_KV_WIRE_INFLIGHT)",
        )
        # Which path served each transfer: device_colocated / device_pull /
        # host_striped / host_chunked / host_monolithic. Clear-then-set
        # labelled gauges synced from the service's cumulative counters.
        self._kv_path_bytes = Gauge(
            "dynamo_kv_wire_path_bytes_total",
            "KV bytes ingested per transfer path (device-pull vs host-striped "
            "vs host-chunked fallback ladder)",
            ["worker", "path"], registry=self.registry,
        )
        self._kv_path_transfers = Gauge(
            "dynamo_kv_wire_path_transfers_total",
            "Completed KV transfers per transfer path",
            ["worker", "path"], registry=self.registry,
        )
        self._core: Any = None
        self._transfer: Any = None
        self._queue_depth_fn: Callable[[], Awaitable[int]] | None = None
        self._queue: Any = None

    def observe_phase(self, phase: str, seconds: float) -> None:
        self._kv_phase.labels(self.worker, phase).observe(max(0.0, seconds))

    # -- binding -----------------------------------------------------------

    def bind_core(self, core: Any) -> "EngineMetrics":
        self._core = core
        # A fresh core's cumulative ledgers restart at zero; resetting the
        # sync watermarks makes its totals land as new Counter increments
        # (process-lifetime accumulation across cores, proper monotone).
        self._lost_time_synced.clear()
        self._step_time_synced.clear()
        self._step_kinds_synced.clear()
        self._long_steps_synced.clear()
        return self

    def bind_transfer(self, transfer: Any) -> "EngineMetrics":
        self._transfer = transfer
        return self

    def bind_queue_depth(self, fn: Callable[[], Awaitable[int]]) -> "EngineMetrics":
        """``fn`` is awaited per scrape (e.g. ``DistributedQueue.depth``)."""
        self._queue_depth_fn = fn
        return self

    def bind_queue(self, queue: Any) -> "EngineMetrics":
        """Bind a ``DistributedQueue``: depth is polled per scrape and the
        redelivery (requeue) counter is synced per scrape."""
        self._queue = queue
        self._queue_depth_fn = queue.depth
        return self

    # -- scrape ------------------------------------------------------------

    def _sync_core(self) -> None:
        core = self._core
        if core is None:
            return
        info = getattr(core, "last_step_info", None) or {}
        self.step_decode_rows.set(info.get("decode_rows", 0))
        self.step_chunk_rows.set(info.get("chunk_rows", 0))
        self.step_chunk_tokens.set(info.get("chunk_tokens", 0))
        self.step_decodable.set(info.get("decodable", 0))
        self.mixed_steps.set(getattr(core, "mixed_steps", 0))
        self.chunk_steps_split.set(getattr(core, "chunk_steps_split", 0))
        self.chunk_steps_rows_x_t.set(getattr(core, "chunk_steps_rows_x_t", 0))
        self.stall_violations.set(getattr(core, "stall_violations", 0))
        self.preemptions.set(getattr(core, "num_preemptions", 0))
        self.admission_rejections.set(getattr(core, "admission_rejections", 0))
        self.spec_tokens_proposed.set(getattr(core, "spec_tokens_proposed", 0))
        self.spec_tokens_accepted.set(getattr(core, "spec_tokens_accepted", 0))
        stats = core.allocator.stats()
        self.pages_total.set(stats.total_pages)
        self.pages_free.set(stats.free_pages)
        self.pages_cached.set(stats.cached_pages)
        self.pages_active.set(stats.active_pages)
        self.page_utilization.set(stats.active_pages / stats.total_pages if stats.total_pages else 0.0)
        window = getattr(core, "window_allocator", None)
        wstats = window.stats() if window is not None else None
        self.window_pages_total.set(wstats.total_pages if wstats else 0)
        self.window_pages_free.set(wstats.free_pages if wstats else 0)
        self.window_pages_cached.set(wstats.cached_pages if wstats else 0)
        self.window_pages_active.set(wstats.active_pages if wstats else 0)
        self.window_pages_released.set(getattr(core, "window_pages_released", 0))
        slots = getattr(core, "state_slots", None)
        self.state_slots_total.set(slots.total if slots is not None else 0)
        self.state_slots_live.set(slots.live if slots is not None else 0)
        self.prefix_matching_off.set(int(slots is not None and core.config.enable_prefix_caching))
        idle = stats.free_pages + stats.cached_pages
        self.page_fragmentation.set(stats.cached_pages / idle if idle else 0.0)
        self.cache_hit_ratio.set(stats.hit_rate)
        self.requests_waiting.set(len(getattr(core, "waiting", ())))
        self.requests_running.set(len(getattr(core, "running", ())) + len(getattr(core, "prefilling", ())))
        adm = getattr(core, "admission", None)
        self._admission_queue_depth.clear()
        if adm is not None:
            for tier, n in adm.queue_depth_by_tier(core.waiting).items():
                self._admission_queue_depth.labels(self.worker, str(tier)).set(n)
            self.deadline_misses.set(adm.deadline_misses)
            self._tenant_throttled.clear()
            for tenant, n in adm.tenants.throttled.items():
                self._tenant_throttled.labels(self.worker, tenant).set(n)
        else:
            self._admission_queue_depth.labels(self.worker, "0").set(
                len(getattr(core, "waiting", ()))
            )
            self.deadline_misses.set(0)
        cb = getattr(core, "chunk_budget_tokens", None)
        if callable(cb):
            self.chunk_budget_tokens.set(cb())
        tracker = getattr(getattr(core, "runner", None), "compile_tracker", None)
        if tracker is not None:
            self._recompiles.clear()
            for (program, reason), n in tracker.counts().items():
                self._recompiles.labels(self.worker, program, reason).set(n)
        dispatch = getattr(core, "attn_dispatch_counts", None)
        if dispatch is not None:
            self._attn_dispatch.clear()
            for (phase, path), n in dispatch.items():
                self._attn_dispatch.labels(self.worker, phase, path).set(n)
        self.step_gap_ms_last.set(getattr(core, "step_gap_ms_last", 0.0))
        gap_n = getattr(core, "step_gap_ms_count", 0)
        self.step_gap_ms_mean.set(
            getattr(core, "step_gap_ms_sum", 0.0) / gap_n if gap_n else 0.0
        )
        overlap_counts = getattr(core, "overlap_step_counts", None)
        if overlap_counts is not None:
            self._overlap_steps.clear()
            for mode, n in overlap_counts.items():
                self._overlap_steps.labels(self.worker, mode).set(n)
        barrier_counts = getattr(core, "overlap_barrier_counts", None)
        if barrier_counts is not None:
            self._overlap_barriers.clear()
            for reason, n in barrier_counts.items():
                self._overlap_barriers.labels(self.worker, reason).set(n)
        self.constraint_mask_cache_hits.set(getattr(core, "constraint_mask_cache_hits", 0))
        self.constraint_mask_cache_misses.set(getattr(core, "constraint_mask_cache_misses", 0))
        onboard_counts = getattr(core, "onboard_page_counts", None)
        if onboard_counts is not None:
            self._onboard_pages.clear()
            for tier, n in onboard_counts.items():
                self._onboard_pages.labels(self.worker, tier).set(n)
        self.onboard_shortfall.set(getattr(core, "onboard_shortfall_pages", 0))
        drain = getattr(core, "drain_onboard_waits", None)
        if callable(drain):
            for wait_s in drain():
                self._onboard_wait.labels(self.worker).observe(max(0.0, wait_s))
        drain_builds = getattr(core, "drain_constraint_build_seconds", None)
        if callable(drain_builds):
            for build_s in drain_builds():
                self._constraint_build.labels(self.worker).observe(max(0.0, build_s))
        lost = getattr(core, "lost_time_ms", None)
        if lost is not None:
            for cause, ms in lost.items():
                prev = self._lost_time_synced.get(cause, 0.0)
                if ms > prev:
                    self._lost_time.labels(self.worker, cause).inc((ms - prev) / 1e3)
                    self._lost_time_synced[cause] = ms
            step_totals = (
                ("wall", getattr(core, "step_wall_ms_total", 0.0)),
                ("dispatch", getattr(core, "step_dispatch_ms_total", 0.0)),
                ("gap", getattr(core, "step_gap_ms_sum", 0.0)),
            )
            for kind, ms in step_totals:
                prev = self._step_time_synced.get(kind, 0.0)
                if ms > prev:
                    self._step_time.labels(self.worker, kind).inc((ms - prev) / 1e3)
                    self._step_time_synced[kind] = ms
        kind_counts = getattr(core, "step_kind_counts", None)
        if kind_counts is not None:
            for kind, n in kind_counts.items():
                prev = self._step_kinds_synced.get(kind, 0)
                if n > prev:
                    self._step_kinds.labels(self.worker, kind).inc(n - prev)
                    self._step_kinds_synced[kind] = n
        long_steps = getattr(core, "long_steps", None)
        if long_steps is not None:
            for cause, n in long_steps.items():
                ms = core.long_step_lost_ms[cause]
                prev_n, prev_ms = self._long_steps_synced.get(cause, (0, 0.0))
                self._long_steps.labels(self.worker, cause or "unnamed").inc(n - prev_n)
                self._long_step_lost.labels(self.worker, cause or "unnamed").inc((ms - prev_ms) / 1e3)
                self._long_steps_synced[cause] = (n, ms)
        pauses = tracing.HOST_PAUSES
        for gen, now in enumerate(zip(pauses.gc_count, pauses.gc_ns)):
            prev = self._gc_synced[gen]
            self._gc_pauses.labels(self.worker, str(gen)).inc(now[0] - prev[0])
            self._gc_pause_seconds.labels(self.worker, str(gen)).inc((now[1] - prev[1]) / 1e9)
            self._gc_synced[gen] = now
        sentinel = getattr(core, "sentinel", None)
        if sentinel is not None:
            self._anomaly_active.clear()
            for kind in getattr(sentinel, "active", {}):
                self._anomaly_active.labels(self.worker, kind).set(1)
            self._anomaly_fired.clear()
            for kind, n in getattr(sentinel, "fired", {}).items():
                self._anomaly_fired.labels(self.worker, kind).set(n)
        incidents = getattr(core, "incidents", None)
        if incidents is not None:
            self._incidents_captured.clear()
            for kind, n in getattr(incidents, "captured", {}).items():
                self._incidents_captured.labels(self.worker, kind).set(n)

    def _sync_transfer(self) -> None:
        if self._transfer is None:
            return
        stats = self._transfer.stats()
        self.kv_blocks.set(stats.get("blocks", 0))
        self.kv_bytes.set(stats.get("bytes", 0))
        self.kv_streams.set(stats.get("streams_in_flight", 0))
        self.kv_crc_failures.set(stats.get("crc_failures", 0))
        self.kv_rollbacks.set(stats.get("rollbacks", 0))
        self.kv_wire_streams.set(stats.get("wire_conns", 0))
        self.kv_wire_sessions.set(stats.get("streams_in_flight", 0))
        self.kv_wire_staged.set(stats.get("staged_bytes", 0))
        paths = stats.get("paths")
        if paths is not None:
            self._kv_path_bytes.clear()
            self._kv_path_transfers.clear()
            for path, d in paths.items():
                self._kv_path_bytes.labels(self.worker, path).set(d.get("bytes", 0))
                self._kv_path_transfers.labels(self.worker, path).set(d.get("transfers", 0))

    async def render(self) -> bytes:
        self._sync_core()
        self._sync_transfer()
        if self._queue is not None:
            self.prefill_requeues.set(getattr(self._queue, "requeues", 0))
        if self._queue_depth_fn is not None:
            try:
                self.prefill_queue_depth.set(await self._queue_depth_fn())
            except Exception:
                logger.exception("prefill queue depth probe failed")
        return generate_latest(self.registry)


# -- KV-phase observation hook ------------------------------------------------
#
# The wire path (disagg/transfer.py) measures phases deep inside free
# functions; threading a metrics object through every call would couple the
# transfer protocol to the telemetry plane. Instead the worker installs its
# EngineMetrics once at bring-up and the transfer code calls
# observe_kv_phase() — a no-op until something is installed.
#
# Routing is keyed per engine core: install() registers the metrics under
# its bound core (weakly — a retired core drops its route with its last
# reference), and call sites that know their core pass it so several
# in-process workers (run_local) each attribute their own phases. The
# last-installed registry remains the fallback for core-less call sites.

_installed: EngineMetrics | None = None
_by_core: "weakref.WeakKeyDictionary[Any, EngineMetrics]" = weakref.WeakKeyDictionary()


def install(metrics: EngineMetrics | None) -> None:
    global _installed
    if metrics is not None and getattr(metrics, "_core", None) is not None:
        try:
            _by_core[metrics._core] = metrics
        except TypeError:  # core type without weakref support (test doubles)
            pass
    _installed = metrics


def installed() -> EngineMetrics | None:
    return _installed


def observe_kv_phase(phase: str, seconds: float, *, core: Any = None) -> None:
    m = None
    if core is not None:
        try:
            m = _by_core.get(core)
        except TypeError:  # core type without weakref support (test doubles)
            m = None
    if m is None:
        m = _installed
    if m is not None:
        try:
            m.observe_phase(phase, seconds)
        except Exception:
            logger.exception("kv phase observation failed")


# -- federation ---------------------------------------------------------------


def federate_text(parts: list[bytes]) -> bytes:
    """Merge rendered Prometheus texts into one legal document.

    Several processes exporting the same metric family each emit their own
    ``# HELP``/``# TYPE`` headers; Prometheus rejects duplicates, so keep the
    first header per family and pass every sample line through (sample
    uniqueness comes from the per-registry ``worker`` label).
    """
    seen_headers: set[tuple[str, str]] = set()
    out: list[str] = []
    for part in parts:
        for line in part.decode().splitlines():
            if line.startswith("# HELP ") or line.startswith("# TYPE "):
                kind, _, rest = line[2:].partition(" ")
                name = rest.split(" ", 1)[0]
                if (kind, name) in seen_headers:
                    continue
                seen_headers.add((kind, name))
            elif not line:
                continue
            out.append(line)
    return ("\n".join(out) + "\n").encode()
