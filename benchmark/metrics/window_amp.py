"""window_amp: cells the fused tiled kernel computes per cell it writes
(layer: Kernels, ``ops/bitlife.py`` ``life_fused_tiles``).

The program's ``life.advance`` / ``life.segment`` spans of an advance
through the fused tiled kernel carry ``window_cells`` (the cells one fused
step computes over all chips and grid programs: each tile with its halo
rows and columns) and ``frame_cells`` (the frame the step writes, over all
chips). Sum of window cells over sum of frame cells, over the spans that
carry both: 1 would be a kernel that computes no halo. ``None`` where no
span carries them."""

STEP_SPANS = ("life.advance", "life.segment")


def read(ctx):
    counted = [s["attrs"] for s in ctx.spans or ()
               if s.get("kind") == "span" and s["name"] in STEP_SPANS
               and {"window_cells", "frame_cells"} <= set(s.get("attrs", {}))]
    frame = sum(a["frame_cells"] for a in counted)
    if frame <= 0:
        return None
    return sum(a["window_cells"] for a in counted) / frame
