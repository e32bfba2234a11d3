"""Share of the cache bytes that a page pool per layer kind saves: over the
window's decode steps, 100 x (1 - bytes the two pools hold live / bytes one
page-id space would hold for the same live tokens). From the STEP records'
``full_pages_live`` and ``window_pages_live`` (pages live sequences hold in
the full layers' pool and in the sliding layers') and the configuration's
count of layers of each kind: the pools hold full x live_full + sliding x
live_window pages, one space would hold (full + sliding) x live_full. A
program without the fields, or whose model keeps one pool, or a configuration
without ``layer_types``, gives nothing to read."""


def read(ctx):
    hf = ctx["conf"]["hf"]
    steps = [s for s in ctx["window"]["steps"] if s["step_kind"] == "decode" and s.get("window_pages_live")]
    if not steps or "layer_types" not in hf:
        return None
    kinds = hf["layer_types"][: hf.get("num_hidden_layers")]  # the layers held: a file may keep the published list whole
    sliding = sum(1 for k in kinds if k == "sliding_attention")
    full = len(kinds) - sliding
    held = sum(full * s["full_pages_live"] + sliding * s["window_pages_live"] for s in steps)
    ctx["notes"]["kv_pools"] = {
        "steps": len(steps), "full_pages_live": sum(s["full_pages_live"] for s in steps) / len(steps),
        "window_pages_live": sum(s["window_pages_live"] for s in steps) / len(steps),
        "window_pages_released": sum(s.get("window_pages_released", 0) for s in steps)}
    return 100.0 * (1.0 - held / ((full + sliding) * sum(s["full_pages_live"] for s in steps)))
