"""kernel_cups: cell updates per second of device time in the stepping
program (layer: Kernels, ``ops/bitlife.py`` / ``ops/pallas_life.py``).

Updates made in the traced window over the device time of the program
whose module name matches the configuration's ``trace.step_module``,
averaged over the chips of the cell, so that on four chips it is the
aggregate rate, comparable to ``cups``."""


def read(ctx):
    if ctx.trace is None or not ctx.window.runs:
        return None
    seconds = ctx.trace.module_s(ctx.config["trace"]["step_module"])
    if seconds <= 0:
        return None
    return ctx.cells_per_run * len(ctx.window.runs) / seconds
