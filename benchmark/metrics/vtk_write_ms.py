"""vtk_write_ms: median time of one VTK frame's write, in ms (layer: IO,
``utils/vtk.py``).

The program's ``life.vtk_write`` span, inside ``life.snapshot``, covers
formatting the collected board as ASCII VTK and writing the file, by the
writer its ``writer`` attribute names (``native`` or ``python``).
``None`` where the program writes no such span."""

import statistics


def read(ctx):
    durs = [s["dur"] for s in ctx.spans or ()
            if s.get("kind") == "span" and s["name"] == "life.vtk_write"]
    if not durs:
        return None
    return 1e3 * statistics.median(durs)
