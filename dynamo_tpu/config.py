"""Layered configuration: defaults -> TOML file -> environment.

The reference builds RuntimeConfig/WorkerConfig with figment
(`lib/runtime/src/config.rs:26-143`): dataclass defaults, overlaid by a
TOML file, overlaid by ``DYN_<SECTION>_<FIELD>`` environment variables —
highest layer wins. This is the same cascade for this framework's settings;
the launch CLI seeds its argparse defaults from it, so precedence ends up
CLI > env > TOML > defaults.

Env naming: section ``runtime`` field ``http_port`` -> ``DYN_RUNTIME_HTTP_PORT``.
The TOML file is taken from ``DYN_CONFIG`` (path) unless given explicitly.
"""

from __future__ import annotations

import dataclasses
import logging
import os
from typing import Any, TypeVar

try:  # python >= 3.11
    import tomllib
except ModuleNotFoundError:  # 3.10: the vendored API-compatible backport
    import tomli as tomllib

logger = logging.getLogger(__name__)

T = TypeVar("T")


_TRUTHY = ("1", "true", "yes", "on")


def env_flag(env: dict[str, str], key: str, default: bool = False) -> bool:
    """Parse a boolean env toggle (the one definition of 'truthy')."""
    raw = env.get(key)
    return default if raw is None else raw.strip().lower() in _TRUTHY


def _coerce(value: str, target_type: Any) -> Any:
    """Parse an env string into the field's annotated type."""
    if target_type is bool or target_type == "bool":
        return value.strip().lower() in _TRUTHY
    if target_type is int or target_type == "int":
        return int(value)
    if target_type is float or target_type == "float":
        return float(value)
    return value


def _field_types(cls) -> dict[str, Any]:
    out = {}
    for f in dataclasses.fields(cls):
        t = f.type
        if isinstance(t, str):  # from __future__ annotations
            t = {"int": int, "float": float, "bool": bool, "str": str}.get(
                t.replace(" | None", ""), str
            )
        out[f.name] = t
    return out


def load_config(
    defaults: T,
    *,
    section: str,
    toml_path: str | os.PathLike | None = None,
    env: dict[str, str] | None = None,
    env_prefix: str = "DYN",
) -> T:
    """Overlay ``defaults`` (a dataclass instance) with the ``[section]``
    table of a TOML file and then with ``{env_prefix}_{SECTION}_{FIELD}``
    environment variables. Unknown TOML keys warn and are ignored."""
    env = os.environ if env is None else env
    cls = type(defaults)
    values = dataclasses.asdict(defaults)
    types = _field_types(cls)

    path = toml_path or env.get(f"{env_prefix}_CONFIG")
    if path:
        with open(path, "rb") as f:
            doc = tomllib.load(f)
        table = doc.get(section, {})
        for k, v in table.items():
            if k in values:
                values[k] = v
            else:
                logger.warning("config file %s: unknown key [%s] %s", path, section, k)

    for name, t in types.items():
        env_key = f"{env_prefix}_{section.upper()}_{name.upper()}"
        if env_key in env:
            try:
                values[name] = _coerce(env[env_key], t)
            except (ValueError, TypeError) as exc:
                raise ValueError(f"bad value for {env_key}: {env[env_key]!r}") from exc

    return cls(**values)


@dataclasses.dataclass
class RuntimeSettings:
    """Deployment-level settings (the reference's RuntimeConfig role)."""

    host: str = "127.0.0.1"
    http_port: int = 8080
    store: str = ""  # tcp://host:port; empty = in-process
    log_level: str = "INFO"
    log_jsonl: bool = False  # DYN_RUNTIME_LOG_JSONL=1 -> JSON-lines logs


@dataclasses.dataclass
class WorkerSettings:
    """Per-worker engine settings (the reference's WorkerConfig role)."""

    model: str = "test-tiny"
    num_pages: int = 512
    max_batch_size: int = 64
    router_mode: str = "round_robin"
    mesh: str = ""  # '' | 'auto' | 'dp=2,tp=4,...'
    decode_steps: int = 1
    # Per-step prefill chunk budget while decodes are running (stall-free
    # mixed steps); 0 restores phase-exclusive prefill-XOR-decode steps.
    chunk_prefill_tokens: int = 512
    # Speculative decoding draft length (n-gram self-drafting, lossless);
    # 0 disables. See docs/SCHEDULER.md "Speculative steps".
    spec_k: int = 0
    # KV-cache storage dtype: 'bf16' (default) or 'fp8' (float8_e4m3fn,
    # halves KV HBM; attention upcasts to the query dtype at the matmul).
    kv_cache_dtype: str = "bf16"


@dataclasses.dataclass
class SloSettings:
    """Latency targets the deployment is accountable to.

    The north-star metric is goodput *under* these targets (tokens/sec from
    requests that attained them), not raw throughput. Consumed by the
    frontend's SLO accountant (``observability/slo.py``) and, via the
    planner's percentile knob, by scaling decisions.
    """

    ttft_ms: float = 500.0  # p50 time-to-first-token target (north star)
    itl_p99_ms: float = 50.0  # per-request p99 inter-token-latency target


@dataclasses.dataclass
class SloSchedSettings:
    """Admission-control plane knobs (``dynamo_tpu/sched``).

    The master toggle is the bare ``DYN_SLO_SCHED`` flag (not part of this
    section); these tune the plane once it is on. Env: ``DYN_SLO_SCHED_*``,
    TOML: ``[slo_sched]``.
    """

    ttft_budget_ms: float = 500.0  # tier-0 EDF deadline budget
    tier_stretch: float = 2.0  # deadline budget multiplier per priority tier
    # Path to a profiler-produced WorkerProfile JSON; empty = the predictor
    # runs on its online-corrected fallback and the router skips the
    # attainment term unless a profile is wired in code.
    profile: str = ""
    attainment_weight: float = 1.0  # router cost weight for predicted attainment
    # ITL-driven chunk-budget controller (shrinks chunk_prefill_tokens when
    # the live decode-step tail nears the ITL budget; see SloSettings).
    chunk_floor_tokens: int = 64
    chunk_shrink_at: float = 0.9
    chunk_relax_at: float = 0.5
    chunk_cooldown_steps: int = 8


@dataclasses.dataclass
class TenantSettings:
    """Default per-tenant admission quota (``dynamo_tpu/sched/tenants``).

    Zeros mean unlimited. Env: ``DYN_TENANT_*``, TOML: ``[tenant]``.
    """

    rate_tokens_per_s: float = 0.0  # token-bucket refill rate (prompt tokens)
    burst_tokens: float = 0.0  # bucket capacity; 0 -> 2s of rate
    max_inflight_tokens: int = 0  # cap on a tenant's live prompt tokens
    # JSON object of per-tenant overrides keyed by tenant id, e.g.
    # '{"heavy": {"rate_tokens_per_s": 1000, "max_inflight_tokens": 4096}}'.
    quotas: str = ""


@dataclasses.dataclass
class CacheAwareSettings:
    """Cache-aware serving knobs (residual-cost admission + router term).

    The master toggle is the bare ``DYN_CACHE_AWARE`` flag (not part of
    this section); these tune the plane once it is on. Env:
    ``DYN_CACHE_AWARE_*``, TOML: ``[cache_aware]``.
    """

    weight: float = 1.0  # router cost weight for predicted residual prefill
    # Prefill throughput assumed when converting residual tokens into
    # seconds of predicted TTFT contribution for the router cost.
    rate_tokens_per_s: float = 20000.0
    # Router skips the cache term for a worker whose KV-event feed is
    # staler than this — a stale index must not skew placement.
    max_staleness_s: float = 10.0


@dataclasses.dataclass
class FleetSettings:
    """Fleet-simulation harness knobs (``dynamo_tpu/fleetsim``).

    Env: ``DYN_FLEET_*``, TOML: ``[fleet]``. These tune how the harness
    runs a scenario; the scenario spec itself (trace, fleet shape, faults,
    checks) stays in code so runs are reviewable and deterministic.
    """

    spawn_timeout_s: float = 120.0  # per-worker READY deadline
    drain_timeout_s: float = 15.0  # SIGTERM -> SIGKILL escalation deadline
    workers: int = 0  # override the scenario's fleet size (0 = scenario value)
    report_dir: str = ""  # write scenario reports here ("" = stdout only)
    metrics_poll_s: float = 1.0  # federated /metrics scrape cadence


@dataclasses.dataclass
class StoreSettings:
    """HA control-plane knobs (``dynamo_tpu/runtime/replication``).

    Replication is armed by a non-empty ``replicas`` list (every store
    process gets the same list plus its own ``replica_index``); with the
    defaults the store is the single-process deployment and the whole plane
    is dormant. Env: ``DYN_STORE_*``, TOML: ``[store]``.
    """

    # Comma list of every replica's advertised url (tcp://host:port), in
    # priority order; index 0 is the bootstrap leader. "" = no replication.
    replicas: str = ""
    replica_index: int = 0  # this process's position in ``replicas``
    promote_after_s: float = 1.0  # leaderless window before a follower elects
    poll_s: float = 0.25  # peer who_leads poll cadence (election + watchdog)
    # Extra seconds of lease grace granted at promotion, on top of one full
    # TTL — covers clients still walking the replica list for the new leader.
    epoch_grace_s: float = 0.0
    # How long a multi-endpoint StoreClient keeps walking the replica list
    # for a leader before an op fails with ConnectionError.
    client_failover_s: float = 5.0


@dataclasses.dataclass
class RouterResyncSettings:
    """Router KV-event resync knobs (``dynamo_tpu/router/events``).

    A frontend (re)start — or a dropped worker stream — rebuilds the prefix
    index from the workers' sequence-numbered snapshot feeds; these tune the
    reconnect discipline. Env: ``DYN_ROUTER_RESYNC_*``, TOML:
    ``[router_resync]``.
    """

    backoff_s: float = 0.2  # first reconnect delay after a dropped event stream
    max_backoff_s: float = 5.0  # reconnect delay ceiling


@dataclasses.dataclass
class AnomalySettings:
    """Anomaly-sentinel knobs (``dynamo_tpu/observability/anomaly``).

    Rolling-window detectors over the engine step stream; conservative
    defaults (warm-up floors + absolute thresholds on top of the relative
    ratios) so a quiet fleet never false-positives. Env: ``DYN_ANOMALY_*``,
    TOML: ``[anomaly]``.
    """

    enable: bool = True
    window: int = 64  # rolling detector window (steps)
    min_samples: int = 256  # baseline steps required before relative detectors arm
    ratio: float = 3.0  # window-vs-baseline ratio that counts as a spike/drop
    barrier_frac: float = 0.5  # absolute window barrier fraction floor
    gap_floor_ms: float = 50.0  # absolute window mean step-gap floor
    recompile_storm: int = 8  # new-shape compiles within one window
    shortfall_pages: int = 32  # onboard shortfall pages within one window
    clear_after: int = 64  # quiet steps before an active anomaly clears


@dataclasses.dataclass
class IncidentSettings:
    """Incident-plane capture knobs (``dynamo_tpu/observability/incidents``).

    When an anomaly detector rises, a step crashes, or an SLO burn-rate
    alert fires, the worker snapshots a bounded black-box bundle (flight
    excerpt, intersecting spans, loss ledger, config) into a size-capped
    on-disk store so a dead worker still leaves a postmortem artifact.
    Env: ``DYN_INCIDENT_*``, TOML: ``[incident]``.
    """

    enable: bool = True
    dir: str = ""  # bundle root; '' -> <tmp>/dynamo-incidents
    max_bundles: int = 32  # store-wide bundle count cap (oldest evicted)
    max_bytes: int = 16_000_000  # store-wide on-disk byte cap
    flight_last: int = 256  # flight-ring records captured per bundle
    span_window_s: float = 30.0  # spans whose lifetime intersects [now - window, now]
    cooldown_s: float = 30.0  # min seconds between bundles for the same trigger kind


@dataclasses.dataclass
class AlertSettings:
    """SLO burn-rate alerting knobs (``dynamo_tpu/observability/slo``).

    Multi-window burn rates over goodput attainment: burn = miss fraction
    in the window divided by the SLO error budget (``1 - objective``).
    A window's alert fires when its burn rate clears the threshold and
    clears only after ``clear_after`` consecutive quiet requests
    (hysteresis, same discipline as the anomaly sentinel).
    Env: ``DYN_ALERT_*``, TOML: ``[alert]``.
    """

    objective: float = 0.9  # SLO objective: fraction of requests that must attain
    fast_window: int = 64  # fast rolling window (requests; the "5 m" analogue)
    slow_window: int = 512  # slow rolling window (requests; the "1 h" analogue)
    fast_burn: float = 4.0  # fast-window burn-rate threshold
    slow_burn: float = 2.0  # slow-window burn-rate threshold
    min_requests: int = 32  # requests seen in a window before its alert arms
    clear_after: int = 32  # quiet requests before an active alert clears


@dataclasses.dataclass
class AttribSettings:
    """Latency-attribution knobs (``dynamo_tpu/observability/attribution``).

    Env: ``DYN_ATTRIB_*``, TOML: ``[attrib]``.
    """

    # |unattributed| / e2e above this marks the explain budget incomplete.
    tolerance_frac: float = 0.1
    # Cap on flight STEP records each worker returns per explain query.
    max_steps: int = 2048


@dataclasses.dataclass
class TuneSettings:
    """Auto-tuner knobs (``dynamo_tpu/tuning``).

    Tune the closed-loop knob search itself — the space it sweeps and the
    probe discipline behind each trial — not the knobs it searches over
    (those live in their own sections/envs). Env: ``DYN_TUNE_*``, TOML:
    ``[tune]``.
    """

    preset: str = "test-tiny"  # model preset the probe engine is built from
    mode: str = "mock"  # probe backend: 'mock' (CPU proxy) | 'jax' (real model)
    seed: int = 0  # workload seed; the whole search is deterministic under it
    rounds: int = 3  # max coordinate-descent sweeps over the knob list
    requests: int = 16  # requests per full-length measured probe
    isl: int = 96  # probe prompt length (tokens)
    osl: int = 48  # probe decode length (tokens)
    rung_frac: float = 0.5  # successive-halving rung-0 probe scale (of requests)
    plateau_eps: float = 0.005  # relative gain below this counts as a plateau
    plateau_rounds: int = 1  # consecutive plateau rounds before early stop
    max_trials: int = 0  # hard cap on measured trials (0 = unlimited)
    out_dir: str = "bench/results/tune"  # journal + profile + report root
    knobs: str = ""  # comma list restricting swept knob names ("" = all)


def load_runtime_settings(**kw) -> RuntimeSettings:
    return load_config(RuntimeSettings(), section="runtime", **kw)


def load_worker_settings(**kw) -> WorkerSettings:
    return load_config(WorkerSettings(), section="worker", **kw)


def load_slo_settings(**kw) -> SloSettings:
    return load_config(SloSettings(), section="slo", **kw)


def load_slo_sched_settings(**kw) -> SloSchedSettings:
    return load_config(SloSchedSettings(), section="slo_sched", **kw)


def load_tenant_settings(**kw) -> TenantSettings:
    return load_config(TenantSettings(), section="tenant", **kw)


def load_cache_aware_settings(**kw) -> CacheAwareSettings:
    return load_config(CacheAwareSettings(), section="cache_aware", **kw)


def load_fleet_settings(**kw) -> FleetSettings:
    return load_config(FleetSettings(), section="fleet", **kw)


def load_store_settings(**kw) -> StoreSettings:
    return load_config(StoreSettings(), section="store", **kw)


def load_router_resync_settings(**kw) -> RouterResyncSettings:
    return load_config(RouterResyncSettings(), section="router_resync", **kw)


def load_anomaly_settings(**kw) -> AnomalySettings:
    return load_config(AnomalySettings(), section="anomaly", **kw)


def load_incident_settings(**kw) -> IncidentSettings:
    return load_config(IncidentSettings(), section="incident", **kw)


def load_alert_settings(**kw) -> AlertSettings:
    return load_config(AlertSettings(), section="alert", **kw)


def load_attrib_settings(**kw) -> AttribSettings:
    return load_config(AttribSettings(), section="attrib", **kw)


def load_tune_settings(**kw) -> TuneSettings:
    return load_config(TuneSettings(), section="tune", **kw)
