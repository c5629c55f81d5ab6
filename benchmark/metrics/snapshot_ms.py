"""snapshot_ms: median time of one VTK snapshot, in ms (layer: Model,
``models/life.py``).

The program's ``life.snapshot`` span (``LifeSim.save_snapshot()``)
holds the board's collect to the host and the frame's write, so it is
the host round trip that a run with a save cadence pays before each
saved step. ``None`` where the program writes no such span."""

import statistics


def read(ctx):
    durs = [s["dur"] for s in ctx.spans or ()
            if s.get("kind") == "span" and s["name"] == "life.snapshot"]
    if not durs:
        return None
    return 1e3 * statistics.median(durs)
