"""frames_per_dispatch: VTK frames a run's snapshot path brings to the
host per dispatch of its stepping program (layer: Model,
``models/life.py``).

The program's ``life.frames`` span (``LifeSim.run()``'s chunked
snapshot path) holds one dispatch of the program that steps a chunk of
save intervals and one fetch of the chunk's frames; its ``frames``
attribute counts them. The mean over those spans. A run that stops at
every saved step writes no such span: ``None`` there, which reads as
one frame a dispatch."""

import statistics


def read(ctx):
    counts = [s["attrs"]["frames"] for s in ctx.spans or ()
              if s.get("kind") == "span" and s["name"] == "life.frames"]
    if not counts:
        return None
    return statistics.mean(counts)
