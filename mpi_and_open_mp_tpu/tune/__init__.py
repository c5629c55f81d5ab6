"""Unified autotuner + durable plan store (DESIGN.md §16).

``tune.space`` enumerates the LEGAL candidates for one (workload, stack
shape, dtype, topology) — engine path, pack layout, batch-bucket
rounding, decomposition axis order. ``tune.runner`` measures them with
the repo's chained-dispatch differencing, parity-gating every timed
candidate against the NumPy oracle first. ``tune.plans`` persists the
winner as a CRC-framed ``momp-plan/1`` record under the SAME fingerprint
digest ``serve/aotcache.py`` computes, so one store directory holds the
decision (``<digest>.plan``) and its compiled form (``<digest>.aot``)
side by side, with the same corrupt/stale quarantine-and-rebuild
semantics.

Runtime knobs: ``MOMP_TUNE_PLANS`` points the daemons and ``apps.life``
at a store directory; ``MOMP_TUNE=0`` is the kill switch (heuristics
only, plans ignored untouched).
"""

from .plans import (  # noqa: F401
    PLAN_MAGIC,
    PLAN_SCHEMA,
    PlanError,
    PlanStore,
    fingerprint_for,
    load_plan,
    save_plan,
)
from .runner import tune, tune_sharded  # noqa: F401
from .space import (  # noqa: F401
    Candidate,
    axis_orders,
    candidates,
    heuristic_path,
    runner_for,
    sharded_candidates,
)
