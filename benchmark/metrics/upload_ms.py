"""upload_ms: median time of a run's board upload, in ms (layer: Model,
``models/life.py``).

The program's ``life.upload`` span (``LifeSim.reset()``) is anchored on
the new board, so it covers the host-to-device transfer and not only its
enqueue. ``None`` where the program writes no such span."""

import statistics


def read(ctx):
    durs = [s["dur"] for s in ctx.spans or ()
            if s.get("kind") == "span" and s["name"] == "life.upload"]
    if not durs:
        return None
    return 1e3 * statistics.median(durs)
