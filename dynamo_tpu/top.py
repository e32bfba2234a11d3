"""Fleet control tower: a terminal dashboard over the frontend's debug plane.

``python -m dynamo_tpu.top [--url http://host:port] [--once] [--interval S]``

Polls three frontend surfaces and renders one consolidated frame:

- ``GET /metrics`` — the federated Prometheus document (frontend registry
  plus every worker's engine registry), from which we pull throughput, SLO
  attainment and burn rates, active alerts, per-worker queue depths, active
  anomalies, and the lost-time ledger's top causes.
- ``GET /debug/incidents`` — the fleet-wide incident bundle listing.
- ``GET /debug/federation`` — per-worker scrape-failure counters and the
  most recent failure detail.

``--once`` renders a single frame and exits (used by tests and for piping
into files); without it the screen refreshes every ``--interval`` seconds
until interrupted. The tower is read-only — it never mutates fleet state.
"""

from __future__ import annotations

import argparse
import asyncio
import re
import sys
import time
from collections import defaultdict
from typing import Any

# One exposition-format sample: name, optional {label="value",...}, value.
_SAMPLE = re.compile(r"^([A-Za-z_:][A-Za-z0-9_:]*)(?:\{(.*)\})?\s+(\S+)")
_LABEL = re.compile(r'([A-Za-z_][A-Za-z0-9_]*)="((?:[^"\\]|\\.)*)"')


def parse_prometheus(text: str) -> list[tuple[str, dict[str, str], float]]:
    """Parse Prometheus text exposition into (name, labels, value) samples.

    Tolerant by design: comment/blank lines are skipped and unparseable
    values (e.g. ``NaN`` renders fine via float, but garbage doesn't) drop
    the sample rather than raising — the tower must render whatever a
    half-healthy fleet serves.
    """
    samples: list[tuple[str, dict[str, str], float]] = []
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = _SAMPLE.match(line)
        if not m:
            continue
        name, raw_labels, raw_value = m.groups()
        try:
            value = float(raw_value)
        except ValueError:
            continue
        labels = dict(_LABEL.findall(raw_labels)) if raw_labels else {}
        samples.append((name, labels, value))
    return samples


class FleetSnapshot:
    """One poll of the frontend: parsed metrics + incident/federation JSON."""

    def __init__(
        self,
        samples: list[tuple[str, dict[str, str], float]],
        incidents: dict[str, Any] | None,
        federation: dict[str, Any] | None,
        errors: list[str],
    ) -> None:
        self.samples = samples
        self.incidents = incidents or {}
        self.federation = federation or {}
        self.errors = errors

    def value(self, name: str, **labels: str) -> float | None:
        for n, lab, v in self.samples:
            if n == name and all(lab.get(k) == want for k, want in labels.items()):
                return v
        return None

    def by_label(self, name: str, key: str) -> dict[str, float]:
        out: dict[str, float] = {}
        for n, lab, v in self.samples:
            if n == name and key in lab:
                out[lab[key]] = v
        return out

    def workers(self) -> list[str]:
        seen = {lab["worker"] for _, lab, _ in self.samples if "worker" in lab}
        return sorted(seen)


async def poll(url: str, *, timeout: float = 5.0) -> FleetSnapshot:
    import aiohttp

    errors: list[str] = []
    samples: list[tuple[str, dict[str, str], float]] = []
    incidents: dict[str, Any] | None = None
    federation: dict[str, Any] | None = None
    client_timeout = aiohttp.ClientTimeout(total=timeout)
    async with aiohttp.ClientSession(timeout=client_timeout) as session:
        try:
            async with session.get(f"{url}/metrics") as resp:
                samples = parse_prometheus(await resp.text())
        except Exception as exc:
            errors.append(f"/metrics: {type(exc).__name__}: {exc}")
        try:
            async with session.get(f"{url}/debug/incidents") as resp:
                if resp.status == 200:
                    incidents = await resp.json()
        except Exception as exc:
            errors.append(f"/debug/incidents: {type(exc).__name__}: {exc}")
        try:
            async with session.get(f"{url}/debug/federation") as resp:
                if resp.status == 200:
                    federation = await resp.json()
        except Exception as exc:
            errors.append(f"/debug/federation: {type(exc).__name__}: {exc}")
    return FleetSnapshot(samples, incidents, federation, errors)


def _fmt_age(ts: float | None, now: float) -> str:
    if not ts:
        return "-"
    age = max(0.0, now - ts)
    if age < 120:
        return f"{age:.0f}s ago"
    if age < 7200:
        return f"{age / 60:.0f}m ago"
    return f"{age / 3600:.1f}h ago"


def render(snap: FleetSnapshot, *, url: str, now: float | None = None) -> str:
    now = time.time() if now is None else now
    lines: list[str] = []
    lines.append(
        f"dynamo-tpu fleet control tower  {url}  "
        f"{time.strftime('%Y-%m-%d %H:%M:%S', time.localtime(now))}"
    )
    lines.append("=" * 78)
    for err in snap.errors:
        lines.append(f"  !! {err}")

    # --- SLO / throughput -------------------------------------------------
    out_tok = snap.value("dynamo_output_tokens_total")
    good_tok = snap.value("dynamo_goodput_tokens_total")
    attain = snap.value("dynamo_slo_attainment_ratio")
    lines.append("slo")
    lines.append(
        f"  output tokens {out_tok if out_tok is not None else '-':>12}"
        f"   goodput tokens {good_tok if good_tok is not None else '-':>12}"
        f"   attainment {f'{attain:.3f}' if attain is not None else '-':>7}"
    )
    burns = snap.by_label("dynamo_slo_burn_rate", "window")
    if burns:
        burn_txt = "   ".join(f"{w} burn {v:.2f}x" for w, v in sorted(burns.items()))
        lines.append(f"  {burn_txt}")

    # --- alerts -----------------------------------------------------------
    active = {k: v for k, v in snap.by_label("dynamo_alert_active", "kind").items() if v}
    fired = snap.by_label("dynamo_alert_fired_total", "kind")
    lines.append("alerts")
    if active:
        for kind in sorted(active):
            lines.append(f"  FIRING {kind}  (fired {fired.get(kind, 0):.0f}x total)")
    else:
        total_fired = sum(fired.values())
        lines.append(f"  none active  ({total_fired:.0f} fired total)")

    # --- store HA ---------------------------------------------------------
    roles = {k: v for k, v in snap.by_label("dynamo_store_role", "role").items() if v}
    epoch = snap.value("dynamo_store_epoch")
    lag = snap.value("dynamo_store_replication_lag_seconds")
    failovers = snap.value("dynamo_store_failovers_total")
    retries = snap.value("dynamo_store_client_op_retries_total")
    resyncs = snap.value("dynamo_router_index_resyncs_total")
    lines.append("store")
    role = next(iter(sorted(roles)), "-")
    lines.append(
        f"  role {role:<9} epoch {f'{epoch:.0f}' if epoch is not None else '-':>4}"
        f"   repl lag {f'{lag:.3f}s' if lag is not None else '-':>8}"
        f"   failovers {f'{failovers:.0f}' if failovers is not None else '-':>3}"
        f"   op retries {f'{retries:.0f}' if retries is not None else '-':>3}"
        f"   index resyncs {f'{resyncs:.0f}' if resyncs is not None else '-':>3}"
    )

    # --- per-worker -------------------------------------------------------
    running = snap.by_label("dynamo_engine_requests_running", "worker")
    waiting = snap.by_label("dynamo_engine_requests_waiting", "worker")
    anomalies: dict[str, list[str]] = defaultdict(list)
    for n, lab, v in snap.samples:
        if n == "dynamo_anomaly_active" and v and "worker" in lab and "kind" in lab:
            anomalies[lab["worker"]].append(lab["kind"])
    workers = sorted(set(running) | set(waiting) | set(anomalies))
    lines.append(f"workers ({len(workers)})")
    for w in workers:
        anom = ",".join(sorted(anomalies.get(w, []))) or "-"
        lines.append(
            f"  {w:<18} running {running.get(w, 0):>5.0f}"
            f"  waiting {waiting.get(w, 0):>5.0f}  anomalies {anom}"
        )
    if not workers:
        lines.append("  (no worker registries federated yet)")

    # --- lost time --------------------------------------------------------
    lost: dict[str, float] = defaultdict(float)
    for n, lab, v in snap.samples:
        # Exact sample name: the Counter family also emits a unix-epoch
        # `..._created` sample per label set, which must not be summed.
        if n == "dynamo_engine_lost_time_seconds_total" and "cause" in lab:
            lost[lab["cause"]] += v
    lines.append("lost time (top causes, fleet-wide)")
    if lost:
        for cause, secs in sorted(lost.items(), key=lambda kv: -kv[1])[:6]:
            lines.append(f"  {cause:<28} {secs:>9.3f}s")
    else:
        lines.append("  (no lost-time ledger samples)")

    # --- federation health ------------------------------------------------
    failures = snap.by_label("dynamo_federation_scrape_failures_total", "worker")
    fed_failures = snap.federation.get("failures") or {}
    merged = dict(fed_failures)
    for w, v in failures.items():
        merged[w] = max(float(merged.get(w, 0)), v)
    lines.append("federation")
    if merged:
        for w in sorted(merged):
            lines.append(f"  {w:<18} scrape failures {merged[w]:>6.0f}")
    else:
        lines.append("  no scrape failures")
    last = snap.federation.get("last_failure")
    if last:
        lines.append(
            f"  last: worker={last.get('worker', '?')} endpoint={last.get('endpoint', '?')}"
            f" {last.get('error', '?')} ({_fmt_age(last.get('ts'), now)})"
        )

    # --- incidents --------------------------------------------------------
    items = snap.incidents.get("incidents") or []
    lines.append(f"incidents ({snap.incidents.get('count', len(items))} on disk)")
    for item in sorted(items, key=lambda i: i.get("ts", 0), reverse=True)[:5]:
        trigger = item.get("trigger") or {}
        what = trigger.get("anomaly") or trigger.get("alert") or trigger.get("error") or ""
        lines.append(
            f"  {item.get('id', '?'):<34} {item.get('kind', '?'):<9}"
            f" {item.get('worker', '?'):<14} {what:<22} {_fmt_age(item.get('ts'), now)}"
        )
    if not items:
        lines.append("  none captured")
    return "\n".join(lines)


async def run(url: str, *, once: bool, interval: float) -> int:
    while True:
        snap = await poll(url)
        frame = render(snap, url=url)
        if once:
            print(frame)
            # Only connection-level failure of every surface is an error;
            # partial degradation still renders (and reports) fine.
            return 1 if len(snap.errors) >= 3 else 0
        sys.stdout.write("\x1b[2J\x1b[H" + frame + "\n")
        sys.stdout.flush()
        await asyncio.sleep(interval)


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m dynamo_tpu.top",
        description="Terminal control tower over a dynamo-tpu frontend.",
    )
    parser.add_argument(
        "--url", default="http://127.0.0.1:8000", help="frontend base URL"
    )
    parser.add_argument(
        "--once", action="store_true", help="render one frame and exit"
    )
    parser.add_argument(
        "--interval", type=float, default=2.0, help="refresh period in seconds"
    )
    args = parser.parse_args(argv)
    url = args.url.rstrip("/")
    try:
        return asyncio.run(run(url, once=args.once, interval=args.interval))
    except KeyboardInterrupt:
        return 0


if __name__ == "__main__":
    sys.exit(main())
