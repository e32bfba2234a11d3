"""``record``: what the always-on telemetry tail of ``EngineCore.step()`` costs
a step (flight record, cost join, loss ledger, anomaly sentinel). Decode steps, untraced."""
from benchmark import program_spans as ps


def read(ctx):
    return ps.phase_p50_ms(ctx, ("record",))
