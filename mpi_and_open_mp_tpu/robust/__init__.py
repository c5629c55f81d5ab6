"""Fault injection, guards, bounded retry waits, preemption-safe resume.

The robustness layer of the stack — four small modules threaded through
``parallel/``, ``models/``, ``bench.py`` and the launchers:

``chaos``
    Env-driven (``MOMP_CHAOS``) deterministic fault injection: NaN/Inf
    ring-hop poisoning, corrupted/dropped halo rows, dispatch delay,
    simulated preemption. Zero injection code reachable when unset.
``guards``
    ``with_fallback(engines, validator)`` — the general engine-ranked
    retry with ``:recovered`` provenance — plus the validators and the
    process-wide recovery log recorders publish.
``watchdog``
    Bounded, seeded exponential backoff for the serving daemon's
    re-dispatch ladder.
``preempt``
    SIGTERM/SIGINT → checkpoint-flush-at-segment-boundary → exit 75,
    and the :class:`Preempted` contract drivers/queues key on.
"""

from mpi_and_open_mp_tpu.robust import chaos, guards, preempt, watchdog  # noqa: F401
from mpi_and_open_mp_tpu.robust.chaos import FaultPlan, active_plan  # noqa: F401
from mpi_and_open_mp_tpu.robust.guards import (  # noqa: F401
    FallbackExhausted,
    with_fallback,
)
from mpi_and_open_mp_tpu.robust.preempt import (  # noqa: F401
    EXIT_PREEMPTED,
    Preempted,
    SimulatedPreemption,
)
