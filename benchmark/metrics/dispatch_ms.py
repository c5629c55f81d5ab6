"""dispatch_ms: device-idle time inside the stepping phase, in ms
(layer: Model, ``models/life.py``).

The program's ``life.advance`` / ``life.segment`` spans lie on the
profiler's clock as host events (each live span is a
``jax.profiler.TraceAnnotation``). For each such event, the time inside
it in which a chip runs no op (busy: the union of the chip's op
intervals), averaged over the chips; the median over the events, one a
run in a closed loop without snapshots. That is the host time the
stepping waits on: the Python of ``run()``, jit dispatch, eager pre-ops
and the wake-up after the block. ``None`` where the host line holds no
such event."""

import bisect
import statistics

import devtrace

STEP_SPANS = ("life.advance", "life.segment")


def _busy_within(busy, starts, s, e):
    """Nanoseconds of the merged ``busy`` intervals inside ``[s, e)``."""
    i = max(0, bisect.bisect_right(starts, s) - 1)
    total = 0.0
    while i < len(busy) and busy[i][0] < e:
        total += max(0.0, min(e, busy[i][1]) - max(s, busy[i][0]))
        i += 1
    return total


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    steps = [(s, s + d) for name, s, d in ctx.trace.host
             if name in STEP_SPANS]
    if not steps:
        return None
    chips = []
    for dev in ctx.trace.devices.values():
        busy = devtrace._union((s, s + d) for _, s, d in dev["ops"])
        chips.append((busy, [b[0] for b in busy]))
    idle = [sum((e - s) - _busy_within(busy, starts, s, e)
                for busy, starts in chips) / len(chips)
            for s, e in steps]
    return statistics.median(idle) / 1e6
