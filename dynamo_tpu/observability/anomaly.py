"""Anomaly sentinel: rolling-window self-diagnosis over the engine step stream.

The soak and hardware campaigns run unattended — nobody is watching the
dashboards when barrier fraction creeps or a mis-sized bucket lattice starts
recompiling on the serving path. The sentinel watches the same per-step
stream the flight recorder sees and raises structured ANOMALY records (into
the flight ring, next to the steps that triggered them) plus a
``dynamo_anomaly_active{kind}`` gauge when the recent window regresses
against the process's own baseline:

- ``barrier_frac_spike`` — overlap barrier fraction in the window clears an
  absolute floor AND a ratio over the long-run baseline;
- ``step_gap_regression`` — mean host gap between dispatches spikes;
- ``goodput_drop`` — tokens-out per decode-carrying step collapses;
- ``recompile_storm`` — new-shape compiles bunch inside one window;
- ``onboard_shortfall_burst`` — tier onboard shortfall pages bunch up.

Detection is deliberately conservative: relative detectors arm only after
``min_samples`` baseline steps, and every one also requires an absolute
floor, so a quiet fleet (or a cold start legitimately filling the bucket
lattice) never false-positives. An active anomaly clears after
``clear_after`` consecutive quiet steps (hysteresis — no flapping gauge).
All knobs ride :class:`~dynamo_tpu.config.AnomalySettings` (``DYN_ANOMALY_*``).

Beside the windows the sentinel names single **long steps**
(:meth:`AnomalySentinel.observe_period`): a step whose period is over
:data:`LONG_STEP_RATIO` times what its kind has been taking and at least
:data:`LONG_STEP_FLOOR_MS` over it. That one is always on and has no knob; the
engine writes the ``engine_long_step`` span for it.
"""

from __future__ import annotations

import logging
from collections import deque
from typing import Any

from dynamo_tpu.observability.flight import ANOMALY

logger = logging.getLogger(__name__)

#: Detector kinds (the dynamo_anomaly_active{kind} label values).
ANOMALY_KINDS = (
    "barrier_frac_spike",
    "step_gap_regression",
    "goodput_drop",
    "recompile_storm",
    "onboard_shortfall_burst",
)

#: A step is long when its period is over this many times its kind's expected
#: period and at least the floor over it.
LONG_STEP_RATIO = 5.0
LONG_STEP_FLOOR_MS = 10.0
#: The expected period is the mean of a kind's first steps, then an
#: exponential mean at 1 / LONG_STEP_ARM; no step is long before it is armed.
#: As many long steps in a row are a new regime: the kind arms anew.
LONG_STEP_ARM = 32


class AnomalySentinel:
    """Per-engine rolling-window detectors fed from ``EngineCore.step()``.

    ``observe_step`` is on the step path: everything is O(1) per call
    (window sums are maintained incrementally), and the sentinel never
    raises into the engine — it is observability, not control flow.
    """

    def __init__(self, settings=None, *, flight=None, on_fire=None) -> None:
        if settings is None:
            from dynamo_tpu.config import load_anomaly_settings

            settings = load_anomaly_settings()
        self.settings = settings
        self.flight = flight
        #: Rising-edge sink, ``on_fire(kind, info)`` — called exactly once per
        #: edge (never while a kind stays active); the incident plane hangs
        #: capture off it. Exceptions are swallowed by _observe's guard.
        self.on_fire = on_fire
        self._window: deque[dict] = deque(maxlen=max(2, settings.window))
        # Incremental window aggregates (subtract the evictee, add the new).
        self._w = {"barrier": 0, "gap_ms": 0.0, "decode_steps": 0, "outputs": 0}
        # Cumulative totals over every observed step; baseline = total - window.
        self._t = {"steps": 0, "barrier": 0, "gap_ms": 0.0, "decode_steps": 0, "outputs": 0}
        # kind -> consecutive quiet steps since the condition last held.
        self._quiet: dict[str, int] = {}
        #: kind -> {"value", "threshold", "since_step"} while active.
        self.active: dict[str, dict[str, Any]] = {}
        #: kind -> rising edges ever fired (scoreboards / tests).
        self.fired: dict[str, int] = {}
        #: (step kind, power-of-two bucket of decode rows) -> [steps folded,
        #: expected period in ms, long steps in a row].
        self._periods: dict[tuple[str, int], list] = {}

    # -- long steps --------------------------------------------------------

    def observe_period(self, step_kind: str, decode_rows: int, period_ms: float) -> float:
        """Fold one step's period; returns what was expected if the step is
        long, else 0.0. A long step does not move the expected period. Steps
        are told apart by kind and by the bucket of their decode rows, so a
        batch that fills up is another kind of step and not a long one."""
        key = (step_kind, decode_rows.bit_length())
        state = self._periods.get(key)
        if state is None:
            state = self._periods[key] = [0, 0.0, 0]
        n, expected = state[0], state[1]
        if n < LONG_STEP_ARM:
            state[0] = n + 1
            state[1] = expected + (period_ms - expected) / (n + 1)
            return 0.0
        if period_ms > LONG_STEP_RATIO * expected and period_ms - expected >= LONG_STEP_FLOOR_MS:
            state[2] += 1
            if state[2] >= LONG_STEP_ARM:
                state[:] = [0, 0.0, 0]
            return expected
        state[1] = expected + (period_ms - expected) / LONG_STEP_ARM
        state[2] = 0
        return 0.0

    # -- observation -------------------------------------------------------

    def observe_step(
        self,
        *,
        wall_ms: float,
        gap_ms: float,
        barrier: bool,
        outputs: int,
        decode_rows: int,
        recompiles: int,
        shortfall_pages: int,
    ) -> None:
        """Fold one recorded engine step; evaluate every detector.

        ``recompiles`` and ``shortfall_pages`` are the engine's *cumulative*
        counters — the window delta is taken against the oldest entry.
        """
        if not self.settings.enable:
            return
        try:
            self._observe(
                wall_ms=wall_ms, gap_ms=gap_ms, barrier=barrier, outputs=outputs,
                decode_rows=decode_rows, recompiles=recompiles,
                shortfall_pages=shortfall_pages,
            )
        except Exception:
            logger.exception("anomaly sentinel failed (ignored)")

    def _observe(self, *, wall_ms, gap_ms, barrier, outputs, decode_rows,
                 recompiles, shortfall_pages) -> None:
        entry = {
            "barrier": 1 if barrier else 0,
            "gap_ms": float(gap_ms),
            "decode_steps": 1 if decode_rows > 0 else 0,
            "outputs": int(outputs) if decode_rows > 0 else 0,
            "recompiles": int(recompiles),
            "shortfall_pages": int(shortfall_pages),
        }
        if len(self._window) == self._window.maxlen:
            old = self._window[0]
            for k in self._w:
                self._w[k] -= old[k]
        self._window.append(entry)
        for k in self._w:
            self._w[k] += entry[k]
        self._t["steps"] += 1
        self._t["barrier"] += entry["barrier"]
        self._t["gap_ms"] += entry["gap_ms"]
        self._t["decode_steps"] += entry["decode_steps"]
        self._t["outputs"] += entry["outputs"]
        self._evaluate()

    # -- detectors ---------------------------------------------------------

    def _evaluate(self) -> None:
        s = self.settings
        n_w = len(self._window)
        full = n_w == self._window.maxlen
        n_base = self._t["steps"] - n_w
        armed = n_base >= s.min_samples and full

        # barrier_frac_spike
        w_frac = self._w["barrier"] / n_w if n_w else 0.0
        b_frac = (self._t["barrier"] - self._w["barrier"]) / n_base if n_base else 0.0
        self._update(
            "barrier_frac_spike",
            armed and w_frac >= s.barrier_frac and w_frac >= s.ratio * max(b_frac, 0.01),
            value=w_frac, threshold=s.barrier_frac,
        )

        # step_gap_regression
        w_gap = self._w["gap_ms"] / n_w if n_w else 0.0
        b_gap = (self._t["gap_ms"] - self._w["gap_ms"]) / n_base if n_base else 0.0
        self._update(
            "step_gap_regression",
            armed and w_gap >= s.gap_floor_ms and w_gap >= s.ratio * max(b_gap, 1.0),
            value=w_gap, threshold=s.gap_floor_ms,
        )

        # goodput_drop (decode-carrying steps only: an idle tail is not a drop)
        wd, bd = self._w["decode_steps"], self._t["decode_steps"] - self._w["decode_steps"]
        w_out = self._w["outputs"] / wd if wd else 0.0
        b_out = (self._t["outputs"] - self._w["outputs"]) / bd if bd else 0.0
        self._update(
            "goodput_drop",
            bd >= s.min_samples and wd >= max(8, n_w // 4)
            and b_out >= 1.0 and w_out <= b_out / s.ratio,
            value=w_out, threshold=b_out / s.ratio if s.ratio else 0.0,
        )

        # recompile_storm (cumulative counter delta across the window)
        comp_delta = self._window[-1]["recompiles"] - self._window[0]["recompiles"]
        self._update(
            "recompile_storm",
            full and comp_delta >= s.recompile_storm,
            value=comp_delta, threshold=s.recompile_storm,
        )

        # onboard_shortfall_burst
        sf_delta = self._window[-1]["shortfall_pages"] - self._window[0]["shortfall_pages"]
        self._update(
            "onboard_shortfall_burst",
            full and sf_delta >= s.shortfall_pages,
            value=sf_delta, threshold=s.shortfall_pages,
        )

    def _update(self, kind: str, firing: bool, *, value, threshold) -> None:
        if firing:
            self._quiet[kind] = 0
            if kind not in self.active:
                self.active[kind] = {
                    "value": round(float(value), 4),
                    "threshold": round(float(threshold), 4),
                    "since_step": self._t["steps"],
                }
                self.fired[kind] = self.fired.get(kind, 0) + 1
                logger.warning(
                    "anomaly %s: value %.4g over threshold %.4g (window %d steps)",
                    kind, value, threshold, len(self._window),
                )
                if self.flight is not None:
                    self.flight.record(
                        ANOMALY, anomaly=kind,
                        value=round(float(value), 4),
                        threshold=round(float(threshold), 4),
                        window=len(self._window),
                    )
                if self.on_fire is not None:
                    self.on_fire(kind, dict(self.active[kind], anomaly=kind,
                                            window=len(self._window)))
            else:
                self.active[kind]["value"] = round(float(value), 4)
        elif kind in self.active:
            self._quiet[kind] = self._quiet.get(kind, 0) + 1
            if self._quiet[kind] >= self.settings.clear_after:
                del self.active[kind]
                del self._quiet[kind]
                logger.info("anomaly %s cleared", kind)
