"""Frontend Prometheus metrics.

Three levels, mirroring the reference (`http/service/metrics.rs:28-110`):
per-request counters/durations, streaming quality (TTFT / inter-token
latency), and size histograms (input/output sequence length). Exposed in
Prometheus text format at GET /metrics.
"""

from __future__ import annotations

import time

from prometheus_client import CollectorRegistry, Counter, Gauge, Histogram, generate_latest

from dynamo_tpu.observability.incidents import IncidentCapture
from dynamo_tpu.observability.slo import SloAccountant

_DURATION_BUCKETS = (0.005, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0)
_QUEUE_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 5.0)
_TTFT_BUCKETS = (0.01, 0.025, 0.05, 0.1, 0.2, 0.3, 0.5, 0.75, 1.0, 2.0, 5.0, 10.0)
_ITL_BUCKETS = (0.001, 0.005, 0.01, 0.02, 0.04, 0.06, 0.1, 0.2, 0.5, 1.0)
_LEN_BUCKETS = (16, 64, 256, 1024, 3000, 8192, 32768, 131072)


class FrontendMetrics:
    def __init__(self, registry: CollectorRegistry | None = None) -> None:
        self.registry = registry or CollectorRegistry()
        ns = "dynamo_frontend"
        self.requests = Counter(
            f"{ns}_requests_total", "Requests received", ["model", "endpoint", "status"], registry=self.registry
        )
        self.inflight = Gauge(f"{ns}_inflight_requests", "Requests in flight", ["model"], registry=self.registry)
        self.duration = Histogram(
            f"{ns}_request_duration_seconds", "Request duration", ["model"],
            buckets=_DURATION_BUCKETS, registry=self.registry,
        )
        self.ttft = Histogram(
            f"{ns}_time_to_first_token_seconds", "TTFT", ["model"], buckets=_TTFT_BUCKETS, registry=self.registry
        )
        self.itl = Histogram(
            f"{ns}_inter_token_latency_seconds", "ITL", ["model"], buckets=_ITL_BUCKETS, registry=self.registry
        )
        self.input_len = Histogram(
            f"{ns}_input_sequence_tokens", "Prompt tokens", ["model"], buckets=_LEN_BUCKETS, registry=self.registry
        )
        self.output_len = Histogram(
            f"{ns}_output_sequence_tokens", "Generated tokens", ["model"], buckets=_LEN_BUCKETS, registry=self.registry
        )
        self.cached_tokens = Counter(
            f"{ns}_cached_prompt_tokens_total", "Prompt tokens served from prefix cache", ["model"],
            registry=self.registry,
        )
        # Accept -> engine-dispatch gap: frontend-side time (parse, model
        # lookup, preprocessing) before the request enters the pipeline.
        self.request_queue = Histogram(
            f"{ns}_request_queue_seconds", "Accept to engine-dispatch gap", ["model"],
            buckets=_QUEUE_BUCKETS, registry=self.registry,
        )
        # Engine-side admission wait (add_request to first scheduling) —
        # distinct from request_queue, which ends when the request *enters*
        # the pipeline. This is where EDF deferral and tenant throttling
        # show up; reported once per request via the first delta's
        # admission_wait_ms.
        self.admission_wait = Histogram(
            f"{ns}_admission_wait_seconds",
            "Engine admission wait (add_request to first scheduling)", ["model"],
            buckets=_QUEUE_BUCKETS, registry=self.registry,
        )
        # Router-side staleness of each worker's last load publish (synced
        # per scrape from the KvMetricsAggregator when one is wired).
        self.worker_staleness = Gauge(
            "dynamo_router_worker_staleness_seconds",
            "Seconds since the router last saw a worker's ForwardPassMetrics publish",
            ["worker"], registry=self.registry,
        )
        # Kernel-fallback visibility: compiled paged-attention programs that
        # dropped to the ~5x-slower XLA gather formulation, by shape
        # signature (ops/pallas_paged.FALLBACK_COUNTS; synced per scrape).
        self.kernel_fallbacks = Gauge(
            "dynamo_attention_kernel_fallback_programs",
            "Compiled paged-attention programs that fell back to the XLA gather formulation",
            ["signature"], registry=self.registry,
        )
        # SLO-conditioned accounting: the north star is goodput (tokens from
        # requests that attained the latency targets), not raw throughput.
        # Source of truth is the SloAccountant; counters/gauges are synced on
        # scrape so nothing is double-booked. A burn-rate alert rising edge
        # is itself an incident-capture trigger: the frontend snapshots its
        # own bundle (SLO state + spans + config) into the incident store.
        self.incidents = IncidentCapture(worker="frontend")
        self.slo = SloAccountant(
            on_fire=lambda kind, info: self.incidents.capture("slo_burn", info)
        )
        self.output_tokens = Gauge(
            "dynamo_output_tokens_total",
            "Output tokens generated across finished requests (SLO-blind)",
            registry=self.registry,
        )
        self.goodput_tokens = Gauge(
            "dynamo_goodput_tokens_total",
            "Output tokens from finished requests that attained the SLO "
            "(TTFT and per-request p99 ITL within slo.ttft_ms / slo.itl_p99_ms)",
            registry=self.registry,
        )
        self.slo_requests = Counter(
            "dynamo_slo_requests_total",
            "Finished requests classified against the SLO targets",
            ["model", "outcome"], registry=self.registry,
        )
        self.slo_attainment = Gauge(
            "dynamo_slo_attainment_ratio",
            "Fraction of finished requests that attained the SLO (cumulative)",
            registry=self.registry,
        )
        # Multi-window burn-rate alerting over goodput attainment
        # (observability/slo.py): burn = window miss fraction / error budget.
        self.slo_burn_rate = Gauge(
            "dynamo_slo_burn_rate",
            "SLO burn rate per rolling window (window miss fraction over the "
            "error budget 1 - alert.objective; 1.0 burns the budget exactly "
            "at the sustainable rate)",
            ["window"], registry=self.registry,
        )
        self.alert_active = Gauge(
            "dynamo_alert_active",
            "Burn-rate alerts currently firing (1 while active; hysteresis "
            "clears after alert.clear_after quiet requests)",
            ["kind"], registry=self.registry,
        )
        self.alert_fired = Gauge(
            "dynamo_alert_fired_total",
            "Burn-rate alert rising edges since frontend start",
            ["kind"], registry=self.registry,
        )
        # Federation visibility: worker telemetry scrapes that failed (the
        # federated /metrics otherwise degrades silently to the frontend
        # registry alone). Synced per scrape from the telemetry client.
        self.federation_failures = Gauge(
            "dynamo_federation_scrape_failures_total",
            "Failed worker telemetry fan-out calls per worker (metrics "
            "scrapes and debug queries that timed out or errored)",
            ["worker"], registry=self.registry,
        )
        # Client-plane health: watch-loop restarts/staleness and per-instance
        # circuit-breaker state, synced per scrape from every live runtime
        # client in this process (runtime/client.py snapshots).
        self.client_watch_restarts = Gauge(
            "dynamo_client_watch_restarts_total",
            "Instance-watch reconnects per endpoint (a restart means the discovery watch died and was resubscribed)",
            ["endpoint"], registry=self.registry,
        )
        self.client_watch_staleness = Gauge(
            "dynamo_client_watch_staleness_seconds",
            "Seconds the endpoint's instance watch has been down (0 while healthy)",
            ["endpoint"], registry=self.registry,
        )
        self.client_breaker_state = Gauge(
            "dynamo_client_breaker_state",
            "Per-instance circuit breaker state (0 closed / 1 half-open / 2 open)",
            ["endpoint", "instance"], registry=self.registry,
        )
        # HA control plane: role/epoch/lag of the store replica hosted in
        # this process (if any) plus the client-side failover view — synced
        # per scrape from runtime/replication.py and runtime/store_server.py.
        self.store_role = Gauge(
            "dynamo_store_role",
            "Store replica role hosted or observed by this process (1 for the active role label)",
            ["role"], registry=self.registry,
        )
        self.store_epoch = Gauge(
            "dynamo_store_epoch",
            "Leadership epoch of the store cluster as seen by this process",
            registry=self.registry,
        )
        self.store_replication_lag = Gauge(
            "dynamo_store_replication_lag_seconds",
            "Wall-clock age of the last replicated record applied by the local follower (0 on a leader)",
            registry=self.registry,
        )
        self.store_failovers = Gauge(
            "dynamo_store_failovers_total",
            "Store leadership changes this process has observed",
            registry=self.registry,
        )
        self.store_client_retries = Gauge(
            "dynamo_store_client_op_retries_total",
            "Idempotent store ops transparently replayed after a connection loss",
            registry=self.registry,
        )
        self.router_index_resyncs = Gauge(
            "dynamo_router_index_resyncs_total",
            "KV-index reconstructions (snapshot rebases + gap-forced resyncs) since frontend start",
            registry=self.registry,
        )
        # Streaming P^2 quantiles — no fixed-bucket distortion at the 500 ms
        # target the way a histogram boundary would introduce.
        self.ttft_quantile = Gauge(
            "dynamo_frontend_ttft_quantile_seconds",
            "Streaming TTFT quantile estimate (P^2, deployment-wide)",
            ["quantile"], registry=self.registry,
        )
        self.itl_quantile = Gauge(
            "dynamo_frontend_itl_quantile_seconds",
            "Streaming inter-token-latency quantile estimate (P^2, deployment-wide)",
            ["quantile"], registry=self.registry,
        )

    def render(self) -> bytes:
        from dynamo_tpu.ops.pallas_paged import fallback_snapshot
        from dynamo_tpu.router.events import router_resync_snapshot
        from dynamo_tpu.runtime.client import breaker_snapshot, watch_snapshot
        from dynamo_tpu.runtime.replication import replica_snapshot
        from dynamo_tpu.runtime.store_server import store_client_snapshot

        # Drop label sets from a previous scrape first: a signature that
        # left the snapshot (fallback cache reset) must not keep exporting
        # its last value forever.
        self.kernel_fallbacks.clear()
        for sig, n in fallback_snapshot().items():
            self.kernel_fallbacks.labels(sig).set(n)
        self.client_watch_restarts.clear()
        self.client_watch_staleness.clear()
        self.client_breaker_state.clear()
        for path, view in watch_snapshot().items():
            self.client_watch_restarts.labels(path).set(view["restarts"])
            self.client_watch_staleness.labels(path).set(view["staleness"])
        for (path, instance), state in breaker_snapshot().items():
            self.client_breaker_state.labels(path, instance).set(state)
        self.output_tokens.set(self.slo.output_tokens_total)
        self.goodput_tokens.set(self.slo.goodput_tokens_total)
        self.slo_attainment.set(self.slo.attainment())
        for window, burn in self.slo.burn_rates().items():
            self.slo_burn_rate.labels(window).set(burn)
        self.alert_active.clear()
        for kind in self.slo.alerts_active:
            self.alert_active.labels(kind).set(1)
        self.alert_fired.clear()
        for kind, n in self.slo.alerts_fired.items():
            self.alert_fired.labels(kind).set(n)
        for q, v in self.slo.ttft.snapshot().items():
            self.ttft_quantile.labels(f"p{int(q * 100)}").set(v)
        for q, v in self.slo.itl.snapshot().items():
            self.itl_quantile.labels(f"p{int(q * 100)}").set(v)
        # HA view: an in-process replica coordinator is authoritative; a pure
        # client process (the usual frontend) reports what its StoreClient
        # learned from who_leads.
        replica = replica_snapshot()
        client = store_client_snapshot()
        self.store_role.clear()
        if replica is not None:
            self.store_role.labels(replica["role"]).set(1)
            self.store_epoch.set(replica["epoch"])
            self.store_replication_lag.set(replica["lag_s"])
            self.store_failovers.set(replica["failovers"])
        else:
            self.store_role.labels(client["role"]).set(1)
            self.store_epoch.set(client["epoch"])
            self.store_replication_lag.set(0.0)
            self.store_failovers.set(client["failovers"])
        self.store_client_retries.set(client["retries"])
        self.router_index_resyncs.set(router_resync_snapshot()["resyncs"])
        return generate_latest(self.registry)

    def sync_federation(self, failures: dict[str, int]) -> None:
        """Refresh the per-worker scrape-failure gauge from the telemetry
        client's counters (clears first so departed workers drop out)."""
        self.federation_failures.clear()
        for worker, n in failures.items():
            self.federation_failures.labels(worker).set(n)

    def sync_staleness(self, staleness: dict[int, float]) -> None:
        """Refresh the per-worker staleness gauge from an aggregator view
        (clears first so departed workers drop their label sets)."""
        self.worker_staleness.clear()
        for wid, age in staleness.items():
            self.worker_staleness.labels(f"{wid:x}").set(age)

    def tracker(self, model: str, endpoint: str) -> "RequestTracker":
        return RequestTracker(self, model, endpoint)


#: A stream's inter-token gaps are handed to the deployment-wide ITL
#: histogram and quantiles this many at a time, or after this many seconds.
_ITL_BATCH = 32
_ITL_BATCH_S = 1.0


class RequestTracker:
    """Per-request context manager: times the request + token stream gaps."""

    def __init__(self, metrics: FrontendMetrics, model: str, endpoint: str) -> None:
        self.m = metrics
        self.model = model
        self.endpoint = endpoint
        self._start = 0.0
        self._last_token: float | None = None
        self._dispatched = False
        self.status = "success"
        # Per-request latency profile for SLO classification at __exit__:
        # attainment needs this request's own TTFT and ITL-gap tail, not the
        # deployment aggregates.
        self._ttft: float | None = None
        self._gaps: list[float] = []
        # Gaps reach the deployment-wide histogram and quantiles in batches
        # (_feed_gaps): how many of _gaps they have seen, and when.
        self._fed = 0
        self._fed_at = 0.0
        self._tokens = 0
        self._admission_reported = False

    def __enter__(self) -> "RequestTracker":
        self._start = time.monotonic()
        self.m.inflight.labels(self.model).inc()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc_type is not None:
            self.status = "error"
        self.m.inflight.labels(self.model).dec()
        self.m.requests.labels(self.model, self.endpoint, self.status).inc()
        self.m.duration.labels(self.model).observe(time.monotonic() - self._start)
        self._feed_gaps(self._last_token or 0.0)
        if self._ttft is not None:  # token-producing request: classify vs SLO
            verdict = self.m.slo.account(
                ttft_s=self._ttft,
                itl_gaps=self._gaps,
                output_tokens=self._tokens,
                ok=self.status == "success",
            )
            met = verdict.met and self.status == "success"
            self.m.slo_requests.labels(self.model, "met" if met else "missed").inc()

    def on_dispatch(self) -> None:
        """The request is leaving the frontend for the engine pipeline."""
        if not self._dispatched:
            self._dispatched = True
            self.m.request_queue.labels(self.model).observe(time.monotonic() - self._start)

    def on_admission_wait(self, seconds: float) -> None:
        """Engine admission wait from the first delta (once per request)."""
        if not self._admission_reported:
            self._admission_reported = True
            self.m.admission_wait.labels(self.model).observe(max(0.0, seconds))

    def on_token(self) -> None:
        now = time.monotonic()
        last = self._last_token
        self._last_token = now
        if last is None:
            self._ttft = now - self._start
            self.m.ttft.labels(self.model).observe(self._ttft)
            self.m.slo.observe_ttft(self._ttft)
            self._fed_at = now
            return
        gaps = self._gaps
        gaps.append(now - last)
        if len(gaps) - self._fed >= _ITL_BATCH or now - self._fed_at >= _ITL_BATCH_S:
            self._feed_gaps(now)

    def _feed_gaps(self, now: float) -> None:
        """Hand the gaps not yet seen to the ITL histogram and the SLO
        quantiles, in one tight loop. Per token this costs the event loop a
        list append; a scrape in mid-stream lags a stream by at most
        ``_ITL_BATCH`` gaps or ``_ITL_BATCH_S`` seconds, and a finished
        request has handed in every gap (``__exit__``)."""
        fed, self._fed, self._fed_at = self._fed, len(self._gaps), now
        if fed == self._fed:
            return
        observe = self.m.itl.labels(self.model).observe
        observe_slo = self.m.slo.observe_itl
        for gap in self._gaps[fed:]:
            observe(gap)
            observe_slo(gap)

    def on_usage(self, prompt_tokens: int | None, output_tokens: int, cached_tokens: int | None) -> None:
        if prompt_tokens:
            self.m.input_len.labels(self.model).observe(prompt_tokens)
        self.m.output_len.labels(self.model).observe(output_tokens)
        self._tokens = max(self._tokens, int(output_tokens or 0))
        if cached_tokens:
            self.m.cached_tokens.labels(self.model).inc(cached_tokens)
