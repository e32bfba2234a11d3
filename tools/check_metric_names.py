"""Metric-name hygiene check: every Prometheus family the project exports
must be ``dynamo_``-prefixed, globally unique across registries, carry
non-empty HELP text, and never reuse a name with a different label set.

The frontend registry (``frontend/metrics.py``) and the per-worker engine
registry (``observability/metrics.py``) federate into one ``/metrics``
document (there is no separate router registry — the router-prefixed family
lives in the frontend's); a name collision between them would produce
duplicate families that Prometheus rejects, an unprefixed name would escape
the project's namespace, and a same-name/different-labels family would make
federated samples unjoinable. Run directly
(``python tools/check_metric_names.py``) or via the test suite
(``tests/test_observability.py``).
"""

from __future__ import annotations

import sys


def collect_families() -> dict[str, list[dict]]:
    """Family descriptors per registry: name, HELP text, label names.

    Importing here keeps the tool usable before optional deps of unrelated
    modules are present.
    """
    from dynamo_tpu.fleetsim.metrics import FleetMetrics
    from dynamo_tpu.frontend.metrics import FrontendMetrics
    from dynamo_tpu.observability.metrics import EngineMetrics
    from dynamo_tpu.tuning.metrics import TunerMetrics

    out: dict[str, list[dict]] = {}
    for label, registry in (
        ("frontend", FrontendMetrics().registry),
        ("engine", EngineMetrics(worker="check").registry),
        ("fleet", FleetMetrics().registry),
        ("tuner", TunerMetrics().registry),
    ):
        families: list[dict] = []
        for collector in registry._collector_to_names:  # noqa: SLF001 - no public enumeration API
            labels = tuple(getattr(collector, "_labelnames", ()) or ())
            for metric in collector.collect():
                families.append(
                    {
                        "name": metric.name,
                        "help": (metric.documentation or "").strip(),
                        "labels": labels,
                    }
                )
        out[label] = sorted(families, key=lambda f: f["name"])
    return out


def collect_names() -> dict[str, list[str]]:
    """Family names per registry (the name-only view of collect_families)."""
    return {
        label: [f["name"] for f in families]
        for label, families in collect_families().items()
    }


def check(names: dict[str, list[str]]) -> list[str]:
    """Name-level violations: prefix, cross-registry uniqueness, dupes."""
    problems: list[str] = []
    seen: dict[str, str] = {}
    for label, family_names in names.items():
        for name in family_names:
            if not name.startswith("dynamo_"):
                problems.append(f"{label}: {name!r} is not dynamo_-prefixed")
            prev = seen.get(name)
            if prev is not None and prev != label:
                problems.append(f"{name!r} exported by both {prev} and {label} registries")
            seen.setdefault(name, label)
        if len(set(family_names)) != len(family_names):
            dupes = sorted({n for n in family_names if family_names.count(n) > 1})
            problems.append(f"{label}: duplicate families {dupes}")
    return problems


#: Incident-plane families dashboards and the control tower depend on, and
#: the registry that must export each. A rename or accidental removal fails
#: the gate here rather than as a silently empty tower panel. (These
#: ``_total`` families are Gauges synced from internal counters, so unlike
#: Counter families the suffix stays part of the family name.)
REQUIRED_FAMILIES: dict[str, str] = {
    "dynamo_slo_burn_rate": "frontend",
    "dynamo_alert_active": "frontend",
    "dynamo_alert_fired_total": "frontend",
    "dynamo_federation_scrape_failures_total": "frontend",
    "dynamo_incidents_captured_total": "engine",
    "dynamo_anomaly_active": "engine",
    "dynamo_anomaly_fired_total": "engine",
    # HA control plane (replicated store + frontend reconstruction) — the
    # store_failover / frontend_restart fleetsim gates key on these.
    "dynamo_store_role": "frontend",
    "dynamo_store_epoch": "frontend",
    "dynamo_store_replication_lag_seconds": "frontend",
    "dynamo_store_failovers_total": "frontend",
    "dynamo_store_client_op_retries_total": "frontend",
    "dynamo_router_index_resyncs_total": "frontend",
}


def check_required(families: dict[str, list[dict]]) -> list[str]:
    problems: list[str] = []
    for name, registry in REQUIRED_FAMILIES.items():
        present = {f["name"] for f in families.get(registry, [])}
        if name not in present:
            problems.append(
                f"required family {name!r} missing from the {registry} "
                "registry (renamed? the control tower and dashboards key on it)"
            )
    return problems


def check_families(families: dict[str, list[dict]]) -> list[str]:
    """All violations: the name checks plus non-empty HELP, consistent
    label sets for any name seen more than once across registries, and
    required-presence of the incident-plane families."""
    problems = check(
        {label: [f["name"] for f in fams] for label, fams in families.items()}
    )
    problems += check_required(families)
    label_sets: dict[str, tuple[str, tuple]] = {}
    for label, fams in families.items():
        for f in fams:
            if not f["help"]:
                problems.append(f"{label}: {f['name']!r} has empty HELP text")
            prev = label_sets.get(f["name"])
            if prev is not None and prev[1] != f["labels"]:
                problems.append(
                    f"{f['name']!r} registered with conflicting label sets: "
                    f"{prev[1]} ({prev[0]}) vs {f['labels']} ({label})"
                )
            label_sets.setdefault(f["name"], (label, f["labels"]))
    return problems


def main() -> int:
    families = collect_families()
    problems = check_families(families)
    total = sum(len(v) for v in families.values())
    if problems:
        for p in problems:
            print(f"FAIL: {p}", file=sys.stderr)
        return 1
    print(
        f"ok: {total} metric families across {len(families)} registries — "
        "dynamo_-prefixed, unique, HELP'd, label-consistent"
    )
    return 0


if __name__ == "__main__":
    import pathlib

    # Direct CLI use from a checkout: make the repo importable.
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
    sys.exit(main())
