"""1 - (union of the device's busy intervals) / (traced window)."""


def read(ctx):
    dev = ctx.get("device_trace")
    return 100.0 * (1.0 - dev["busy_s"] / dev["window_s"]) if dev else None
