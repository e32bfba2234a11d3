"""Share of the window's steps that dispatched their program while the step
before was still on the device: 100 x the STEP flight records whose
``overlap_mode`` is ``overlapped`` over those that dispatched a program at all
(``attn_phase`` set; a step that only reads the step in flight dispatches
none). A program that steps synchronously writes an empty ``overlap_mode`` and
reads 0; the rest are the pipelined loop's barriers, whose reasons a record's
``barrier_reason`` names."""


def read(ctx):
    steps = [s for s in ctx["window"]["steps"] if s.get("attn_phase")]
    if not steps:
        return None
    return 100.0 * sum(s.get("overlap_mode") == "overlapped" for s in steps) / len(steps)
