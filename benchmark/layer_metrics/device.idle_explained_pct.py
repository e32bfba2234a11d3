"""Share of the device's idle time in the traced seconds that lies under a
mapped phase of a STEP record (idle while there was no request, ``no_work``,
left out of both sides). The phases tile the host's timeline, so a low reading
means the clock join is off. ``ctx["notes"]`` gets the join, the idle seconds
by phase (where a host optimisation starts from) and the check that the mapped
end of ``wait`` meets the end of the step's device program."""
from benchmark import program_spans as ps


def read(ctx):
    if ctx.get("trace") is None:
        return None
    idle = ps.idle_by_phase(ctx)
    if idle is None:
        return None
    ctx["notes"]["clock_join"] = ps.clock_join(ctx)
    ctx["notes"]["idle_by_phase_s"] = {k: round(v, 6) for k, v in sorted(idle.items(), key=lambda kv: -kv[1])}
    ctx["notes"]["wait_end_vs_program_end"] = ps.wait_end_check(ctx)
    total = sum(idle.values()) - idle.get("no_work", 0.0)
    return 100.0 * (total - idle["unexplained"]) / total if total > 0 else None
