"""Incident plane: capture-on-anomaly black-box bundles.

The anomaly sentinel (PR 15) can *detect* a barrier-fraction spike or a
recompile storm, but detection alone is worthless for unattended soak and
hardware campaigns: by the time a human looks, the flight ring has rotated
and the evidence is gone. This module makes detection self-preserving —
when a detector rises, a step crashes, or an SLO burn-rate alert fires, the
process snapshots a bounded **incident bundle** into a size-capped on-disk
store, so even a dead worker leaves a self-contained postmortem artifact.

A bundle is one JSON document:

- ``id`` / ``ts`` / ``kind`` / ``worker`` — identity; ``kind`` is one of
  :data:`INCIDENT_KINDS`;
- ``trigger`` — kind-specific evidence (anomaly kind/value/threshold, the
  crash exception, or the burn-rate window state);
- ``flight`` — the last ``incident.flight_last`` flight-ring records
  around the trigger (the black box);
- ``spans`` — finished request spans whose lifetime intersects the last
  ``incident.span_window_s`` seconds (from :data:`dynamo_tpu.tracing.SPANS`);
- ``loss`` — ``EngineCore.loss_snapshot()`` at capture time (engine-side
  bundles only);
- ``config`` — the active ``DYN_*`` environment plus the incident settings
  in force;
- ``device_trace`` — whether a device trace was armed and where it writes
  (``DYN_TRACE_DIR``-style profiling), so the XPlane dump can be joined.

The store (:class:`IncidentStore`) is bounded twice — bundle count and
total on-disk bytes — and evicts oldest-first, mirroring the flight ring's
discipline on disk. Capture never raises into the engine: it is
observability, not control flow. Knobs ride
:class:`~dynamo_tpu.config.IncidentSettings` (``DYN_INCIDENT_*``).

Bundles are listed/fetched remotely via the ``debug_incidents`` worker
endpoint (``observability/service.py``) and ``GET /debug/incidents[/{id}]``
on the frontend; ``python -m dynamo_tpu.top`` renders the fleet's recent
incidents live.
"""

from __future__ import annotations

import json
import logging
import os
import tempfile
import threading
import time
from typing import Any

from dynamo_tpu.config import IncidentSettings, load_incident_settings

logger = logging.getLogger(__name__)

#: Capture trigger kinds (the dynamo_incidents_captured_total{kind} labels).
INCIDENT_KINDS = ("anomaly", "crash", "slo_burn")


def default_incident_dir() -> str:
    return os.path.join(tempfile.gettempdir(), "dynamo-incidents")


class IncidentStore:
    """Size-capped on-disk bundle store (thread-safe).

    One JSON file per bundle, named ``<id>.json`` where the id embeds a
    millisecond timestamp + pid + per-process sequence — lexicographic
    filename order is capture order, which is what eviction sorts by.
    """

    def __init__(
        self,
        dir: str | None = None,
        *,
        max_bundles: int = 32,
        max_bytes: int = 16_000_000,
    ) -> None:
        self.dir = dir or default_incident_dir()
        self.max_bundles = max(1, int(max_bundles))
        self.max_bytes = max(1, int(max_bytes))
        self._lock = threading.Lock()
        self._seq = 0

    @classmethod
    def from_settings(cls, settings: IncidentSettings) -> "IncidentStore":
        return cls(
            settings.dir or None,
            max_bundles=settings.max_bundles,
            max_bytes=settings.max_bytes,
        )

    def _paths(self) -> list[str]:
        try:
            names = sorted(
                n for n in os.listdir(self.dir)
                if n.startswith("inc-") and n.endswith(".json")
            )
        except FileNotFoundError:
            return []
        return [os.path.join(self.dir, n) for n in names]

    def save(self, bundle: dict) -> str:
        """Persist one bundle; returns its id. Evicts oldest past the caps."""
        with self._lock:
            self._seq += 1
            incident_id = bundle.get("id") or (
                f"inc-{int(time.time() * 1e3):013d}-{os.getpid()}-{self._seq:04d}"
            )
            bundle = dict(bundle, id=incident_id)
            os.makedirs(self.dir, exist_ok=True)
            path = os.path.join(self.dir, f"{incident_id}.json")
            tmp = f"{path}.tmp"
            with open(tmp, "w") as f:
                json.dump(bundle, f)
            os.replace(tmp, path)  # atomic: a reader never sees a torn bundle
            self._evict_locked()
        return incident_id

    def _evict_locked(self) -> None:
        paths = self._paths()
        sizes = {}
        for p in paths:
            try:
                sizes[p] = os.path.getsize(p)
            except OSError:
                sizes[p] = 0
        while paths and (
            len(paths) > self.max_bundles or sum(sizes[p] for p in paths) > self.max_bytes
        ):
            victim = paths.pop(0)  # oldest-first, the flight ring's discipline
            try:
                os.remove(victim)
            except OSError:
                pass
            logger.info("incident store evicted %s", os.path.basename(victim))

    def list(self) -> list[dict]:
        """Bundle summaries, oldest first: id/ts/kind/worker/trigger/bytes."""
        out: list[dict] = []
        for path in self._paths():
            try:
                with open(path) as f:
                    b = json.load(f)
                out.append(
                    {
                        "id": b.get("id", os.path.basename(path)[:-5]),
                        "ts": b.get("ts"),
                        "kind": b.get("kind"),
                        "worker": b.get("worker", ""),
                        "trigger": b.get("trigger", {}),
                        "bytes": os.path.getsize(path),
                    }
                )
            except (OSError, ValueError):
                continue  # torn/evicted mid-read: skip, never raise
        return out

    def get(self, incident_id: str) -> dict | None:
        if "/" in incident_id or incident_id.startswith("."):
            return None  # ids are filenames: refuse traversal
        path = os.path.join(self.dir, f"{incident_id}.json")
        try:
            with open(path) as f:
                return json.load(f)
        except (OSError, ValueError):
            return None

    def __len__(self) -> int:
        return len(self._paths())


def _config_snapshot(settings: IncidentSettings) -> dict:
    import dataclasses

    return {
        "env": {k: v for k, v in sorted(os.environ.items()) if k.startswith("DYN_")},
        "incident": dataclasses.asdict(settings),
    }


def _device_trace_state() -> dict:
    """Live profile-capture state, not just a static env snapshot: whether a
    trace is running NOW, whether ``jax.profiler`` could start one (the
    ``POST /debug/profile/{worker}`` follow-up an operator reaches for on a
    ``recompile_storm`` / ``step_gap_regression`` bundle), and where
    artifacts land."""
    from dynamo_tpu import tracing

    return {
        "armed": tracing.trace_running(),
        "dir": os.environ.get("DYN_TRACE_DIR"),
        "capture_available": tracing.profiler_available(),
        "artifact_dir": tracing.profile_artifact_dir(),
    }


class IncidentCapture:
    """Assembles and persists bundles; owned per engine (or per frontend).

    ``capture()`` is called from rising edges on hot-adjacent paths
    (sentinel ``_update``, the step crash handler) — it never raises, and a
    per-kind cooldown keeps a flapping detector from flooding the store.
    """

    def __init__(
        self,
        settings: IncidentSettings | None = None,
        *,
        store: IncidentStore | None = None,
        worker: str = "",
        core: Any = None,
        flight: Any = None,
    ) -> None:
        self.settings = settings or load_incident_settings()
        self.store = store or IncidentStore.from_settings(self.settings)
        self.worker = worker
        self.core = core
        self.flight = flight
        #: trigger kind -> bundles written (dynamo_incidents_captured_total).
        self.captured: dict[str, int] = {}
        self._last: dict[str, float] = {}  # cooldown key -> monotonic stamp

    def capture(self, kind: str, trigger: dict) -> str | None:
        """Snapshot one bundle; returns its id (None when skipped/failed)."""
        if not self.settings.enable:
            return None
        try:
            return self._capture(kind, trigger)
        except Exception:
            logger.exception("incident capture failed (ignored)")
            return None

    def _capture(self, kind: str, trigger: dict) -> str | None:
        cooldown_key = f"{kind}:{trigger.get('anomaly', trigger.get('alert', ''))}"
        now = time.monotonic()
        last = self._last.get(cooldown_key)
        if last is not None and now - last < self.settings.cooldown_s:
            logger.info("incident capture for %s suppressed by cooldown", cooldown_key)
            return None
        self._last[cooldown_key] = now

        bundle = self.build_bundle(kind, trigger)
        incident_id = self.store.save(bundle)
        self.captured[kind] = self.captured.get(kind, 0) + 1
        logger.warning(
            "incident %s captured (%s) -> %s",
            incident_id, kind, os.path.join(self.store.dir, f"{incident_id}.json"),
        )
        return incident_id

    def build_bundle(self, kind: str, trigger: dict) -> dict:
        from dynamo_tpu.tracing import SPANS

        now = time.time()
        flight = self.flight or getattr(self.core, "flight", None)
        records: list[dict] = []
        if flight is not None:
            records = flight.snapshot(last=self.settings.flight_last)
        horizon = now - self.settings.span_window_s
        spans = [
            s for s in SPANS.query()
            if s.get("start_ts", 0.0) + s.get("duration_ms", 0.0) / 1e3 >= horizon
        ]
        loss = None
        if self.core is not None and hasattr(self.core, "loss_snapshot"):
            loss = self.core.loss_snapshot()
        return {
            "ts": now,
            "kind": kind,
            "worker": self.worker,
            "trigger": dict(trigger),
            "window_s": self.settings.span_window_s,
            "flight": records,
            "spans": spans,
            "loss": loss,
            "config": _config_snapshot(self.settings),
            "device_trace": _device_trace_state(),
        }
