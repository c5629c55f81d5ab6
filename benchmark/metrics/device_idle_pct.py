"""device_idle_pct: share of the traced window in which no op ran on the
device (layer: Device, TPU v5e), averaged over the chips of the cell."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices or ctx.window.seconds <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s() / ctx.window.seconds)
