"""cups: cell updates per second over the whole window (host clock).

Board cells x steps x runs completed, over the time from the window's
start to the end of its last run (the run open at the close included)."""


def read(ctx):
    w = ctx.window
    if not w.runs or w.seconds <= 0:
        return None
    return ctx.cells_per_run * len(w.runs) / w.seconds
