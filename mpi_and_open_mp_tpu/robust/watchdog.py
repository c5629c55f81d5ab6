"""Bounded, seeded retry waits.

The serving daemon's retry ladder (``serve/daemon.py``) spaces re-dispatch
attempts with these waits. Backoff is BOUNDED and deterministic
(exponential, capped): an unbounded retry loop against a failing
dispatch is just a slower hang.
"""

from __future__ import annotations

import random
from typing import Iterator


def backoff(base_s: float = 2.0, cap_s: float = 60.0, *,
            jitter: float = 0.0, seed: int | None = None,
            ) -> Iterator[float]:
    """Capped-exponential waits as a PURE generator: base, 2·base, 4·base,
    ... ≤ cap, each wait scaled by a seeded jitter factor drawn uniformly
    from ``[1 - jitter, 1]``.

    The jitter is the thundering-herd guard: when several retries start
    at once, identical schedules would march them back in lockstep —
    seeded desynchronisation spreads them while staying reproducible
    (same seed, same schedule; the tests assert the sequence without
    sleeping). ``jitter=0`` (the default) is the exact legacy schedule.
    The generator never sleeps and never ends — consumers take as many
    waits as their attempt budget allows.
    """
    if not 0.0 <= jitter <= 1.0:
        raise ValueError(f"jitter must be in [0, 1], got {jitter}")
    rng = random.Random(seed)
    i = 0
    while True:
        wait = min(cap_s, base_s * (2 ** i))
        if jitter:
            wait *= 1.0 - jitter * rng.random()
        yield wait
        # Past the cap the exponent no longer matters; freezing it keeps
        # the generator truly unbounded (no overflow at absurd i).
        if base_s * (2 ** i) < cap_s:
            i += 1


def backoff_schedule(n: int, base_s: float = 2.0, cap_s: float = 60.0,
                     *, jitter: float = 0.0,
                     seed: int | None = None) -> list[float]:
    """The first ``n`` waits of :func:`backoff` as a list (legacy shape;
    ``jitter=0`` keeps the original deterministic schedule)."""
    gen = backoff(base_s, cap_s, jitter=jitter, seed=seed)
    return [next(gen) for _ in range(max(0, n))]
