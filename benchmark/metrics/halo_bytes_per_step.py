"""halo_bytes_per_step: bytes one chip sends over ``ppermute`` per step
(layer: Parallel, ``parallel/halo.py``).

The program's ``life.advance`` / ``life.segment`` spans of a sharded
bitfused advance carry ``halo_bytes``: the ghosts one chip sends in the
span's exchange rounds (one round per ``k_max`` steps or fewer), both
axes, as the exchange slices them. Their sum over the steps of those
spans (``steps``, or ``stop - start`` on a segment). ``None`` where no
span carries it."""

STEP_SPANS = ("life.advance", "life.segment")


def _steps(span):
    a = span["attrs"]
    return a["steps"] if "steps" in a else a["stop"] - a["start"]


def read(ctx):
    counted = [s for s in ctx.spans or ()
               if s.get("kind") == "span" and s["name"] in STEP_SPANS
               and "halo_bytes" in s.get("attrs", {})]
    steps = sum(_steps(s) for s in counted)
    if steps <= 0:
        return None
    return sum(s["attrs"]["halo_bytes"] for s in counted) / steps
