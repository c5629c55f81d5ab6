"""collect_ms: median time of a run's board collect, in ms (layer:
Model, ``models/life.py``).

The program's ``life.collect`` span (``LifeSim.collect()``) wraps a
synchronous fetch, so it covers the device-to-host transfer, the host's
reordering of the device layout and the crop. ``None`` where the
program writes no such span."""

import statistics


def read(ctx):
    durs = [s["dur"] for s in ctx.spans or ()
            if s.get("kind") == "span" and s["name"] == "life.collect"]
    if not durs:
        return None
    return 1e3 * statistics.median(durs)
