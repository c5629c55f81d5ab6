"""round_us: device time of the stepping program per exchange round
(layer: Parallel, ``parallel/halo.py`` and the kernel each round
launches).

The program's ``life.advance`` / ``life.segment`` spans of a sharded
bitfused advance carry ``rounds``: the exchange rounds of the span (one
per ``k_max`` steps or fewer), each a ghost ``ppermute`` and a fused
kernel launch. The device time of the program whose module name matches
the configuration's ``trace.step_module``, averaged over the chips, over
the sum of ``rounds`` over those spans, in microseconds: what one round
costs, and what a change to the exchange has to cut. ``None`` without a
trace or without a span that carries ``rounds``."""

STEP_SPANS = ("life.advance", "life.segment")


def read(ctx):
    if ctx.trace is None:
        return None
    rounds = sum(s["attrs"]["rounds"] for s in ctx.spans or ()
                 if s.get("kind") == "span" and s["name"] in STEP_SPANS
                 and "rounds" in s.get("attrs", {}))
    if rounds <= 0:
        return None
    seconds = ctx.trace.module_s(ctx.config["trace"]["step_module"])
    if seconds <= 0:
        return None
    return 1e6 * seconds / rounds
