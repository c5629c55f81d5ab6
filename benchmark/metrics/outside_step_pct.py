"""outside_step_pct: share of the window's run time outside the
program's stepping spans (layer: Model, ``models/life.py``).

The runner times each run, ``reset()`` to the final board on the host.
The program's ``life.advance`` / ``life.segment`` spans (``obs/trace.py``,
anchored on the board, so they cover the device work) are the part of it
spent stepping; the rest is upload, dispatch and collect."""

STEP_SPANS = ("life.advance", "life.segment")


def read(ctx):
    if not ctx.spans or not ctx.window.runs:
        return None
    stepping = sum(s["dur"] for s in ctx.spans
                   if s.get("kind") == "span" and s["name"] in STEP_SPANS)
    total = sum(d for _, d in ctx.window.runs)
    if stepping <= 0 or total <= 0:
        return None
    return 100.0 * (1.0 - stepping / total)
