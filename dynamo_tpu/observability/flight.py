"""Engine flight recorder: a bounded ring of per-step structured records.

The metrics plane (``metrics.py``) exports the *last* step's composition and
cumulative counters — enough for dashboards, useless for postmortems: by the
time a stall or crash is noticed, the interesting steps are gone. The flight
recorder keeps the last N steps verbatim, the way an aircraft FDR does:

- ``step`` records — one per ``EngineCore.step()``: step kind (mixed /
  decode / drain), decode rows, prefill chunk rows/tokens, pool free pages,
  cumulative preemptions/rejections, step wall time and in-step runner
  dispatch time, plus the overlapped-execution fields ``gap_ms`` (host gap
  since the previous step completed — the window the device idles unless
  the pipelined loop hides it) and ``overlap_mode`` ("overlapped" /
  "barrier"; "" only from an engine stepped synchronously throughout).
- ``compile`` records — emitted by the :class:`~dynamo_tpu.observability.
  compile.CompileTracker` when a runner dispatch hits a never-seen shape
  bucket (the XLA recompile a generic tool cannot see), with what the first
  call spent tracing, lowering and in the backend or its cache.
- ``crash`` records — appended by ``EngineCore.step()`` when a step raises,
  capturing the failing step's context before the exception propagates.
- ``anomaly`` records — rising edges from the
  :class:`~dynamo_tpu.observability.anomaly.AnomalySentinel` rolling-window
  detectors, landed next to the steps that tripped them.

The ring is dumpable two ways: remotely via the ``debug_flight`` worker
endpoint behind ``GET /debug/flight/{worker}`` (``service.py``), and to a
JSONL file on unhandled engine-loop exceptions (``engine/service.py`` calls
:meth:`FlightRecorder.dump_jsonl`), so a dead worker still leaves its last
seconds on disk.
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
from collections import deque
from typing import Any

logger = logging.getLogger(__name__)

#: Record kinds written into the ring.
STEP = "step"
COMPILE = "compile"
CRASH = "crash"
ANOMALY = "anomaly"

#: Every key of a STEP record (``EngineCore.step`` writes them all, whatever
#: the runner; docs/OBSERVABILITY.md has the table). ``dispatch_ms`` down to
#: ``layout`` are the runner's ``DispatchReport`` of the step.
STEP_KEYS = (
    "seq", "ts", "kind", "step_kind", "decode_rows", "chunk_rows", "chunk_tokens",
    "outputs", "waiting", "running", "prefilling", "free_pages", "preemptions",
    "admission_rejections", "mixed_steps", "stall_violations",
    "spec_drafted", "spec_accepted", "spec_accept_rate", "wall_ms",
    "dispatch_ms", "attn_phase", "attn_path", "moe_path", "router_select",
    "kv_tokens_full", "kv_tokens_window", "step_tokens", "moe_pad_positions",
    "moe_choices", "moe_choices_zero", "moe_choices_held", "moe_experts_touched",
    "moe_extra_passes", "state_rows", "state_slots_live",
    "full_pages_live", "window_pages_live", "window_pages_released", "layout",
    "admitted", "deferred", "deadline_slack_ms", "cached_frac", "gap_ms",
    "overlap_mode", "barrier_reason", "chained_rows",
    "t0_ns", "ann_ns", "traced", "phases_us",
)

_DEFAULT_CAPACITY = 2048
_DUMP_DIR_ENV = "DYN_FLIGHT_DUMP_DIR"
_CAPACITY_ENV = "DYN_FLIGHT_BUFFER"


def _default_capacity() -> int:
    try:
        return int(os.environ.get(_CAPACITY_ENV, str(_DEFAULT_CAPACITY)))
    except ValueError:
        return _DEFAULT_CAPACITY


class FlightRecorder:
    """Thread-safe bounded ring of structured engine records.

    Records are plain dicts carrying a monotonically increasing ``seq`` (so
    consumers can detect ring wrap: a gap in seq means records were lost),
    a wall-clock ``ts``, and a ``kind``. The recorder never raises into the
    engine — it is observability, not control flow.
    """

    def __init__(self, capacity: int | None = None) -> None:
        cap = capacity if capacity is not None else _default_capacity()
        self._records: deque[dict] = deque(maxlen=max(1, cap))
        self._lock = threading.Lock()
        self._seq = 0

    def record(self, kind: str, **fields: Any) -> dict:
        doc = {"seq": self._seq, "ts": time.time(), "kind": kind, **fields}
        with self._lock:
            doc["seq"] = self._seq
            self._seq += 1
            self._records.append(doc)
        return doc

    def snapshot(self, *, last: int | None = None, kind: str | None = None) -> list[dict]:
        """Ordered (oldest-first) copy of the ring, optionally filtered."""
        with self._lock:
            records = list(self._records)
        if kind is not None:
            records = [r for r in records if r.get("kind") == kind]
        if last is not None and last >= 0:
            records = records[-last:]
        return records

    def __len__(self) -> int:
        with self._lock:
            return len(self._records)

    def clear(self) -> None:
        with self._lock:
            self._records.clear()

    # -- crash dump --------------------------------------------------------

    def dump_jsonl(self, path: str | None = None, *, reason: str = "manual") -> str:
        """Write the ring to a JSONL file (one record per line, preceded by
        a header line identifying the dump); returns the path written.

        Default location: ``$DYN_FLIGHT_DUMP_DIR`` (or ``/tmp/dynamo-flight``),
        ``flight-<pid>-<unix ms>.jsonl`` — unique enough that successive
        crashes never clobber each other.
        """
        if path is None:
            d = os.environ.get(_DUMP_DIR_ENV, "/tmp/dynamo-flight")
            os.makedirs(d, exist_ok=True)
            path = os.path.join(d, f"flight-{os.getpid()}-{int(time.time() * 1e3)}.jsonl")
        records = self.snapshot()
        header = {
            "kind": "dump_header",
            "reason": reason,
            "pid": os.getpid(),
            "ts": time.time(),
            "records": len(records),
        }
        with open(path, "w") as f:
            f.write(json.dumps(header, default=str) + "\n")
            for r in records:
                f.write(json.dumps(r, default=str) + "\n")
        return path
