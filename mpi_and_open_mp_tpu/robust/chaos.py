"""Deterministic fault injection — the chaos layer of the robust subsystem.

The fabric this framework rides (single-tenant chips, preemptible hosts,
collectives that can deliver bad data or none) fails in ways the
reference's PBS workflow only ever answered with "rerun the job". This
module makes those failures *injectable* so every recovery path in the
stack (``robust.guards``, ``LifeSim`` consistency probes, checkpoint
resume) is testable on the 8-virtual-device CPU mesh, deterministically,
without hardware.

Faults are driven entirely by the ``MOMP_CHAOS`` environment variable — a
semicolon-separated spec::

    MOMP_CHAOS="nan_hop=1;halo=corrupt;delay=0.01;preempt=60;seed=7"

Tokens:

``nan_hop=<j>`` / ``inf_hop=<j>``
    Poison the K/V partials of ring-attention hop ``j`` with NaN / +inf
    (``parallel/context.py`` fold engines, jnp and per-hop Pallas alike).
``halo=corrupt`` / ``halo=drop``
    Corrupt the ghost rows of every traced halo exchange with seeded
    out-of-range values, or zero them (the exchange "never arrived") —
    ``parallel/halo.py``.
``delay=<seconds>``
    Host-side artificial dispatch delay per guarded run segment and per
    fabric ping (``parallel/fabric.py``) — simulates a congested fabric
    or a slow host without touching traced code.
``preempt=<step>``
    Raise :class:`~mpi_and_open_mp_tpu.robust.preempt.SimulatedPreemption`
    when a ``LifeSim.run`` crosses global step ``<step>`` (after flushing
    a checkpoint when one is configured) — the SIGTERM rehearsal. The
    serving daemon (``serve.daemon``) reads the same token at BATCH
    granularity: its supervised loop preempts after dispatching
    ``<step>`` batches, checkpoint flushed, same exit-75 contract.
``serve_fail=<k>``
    Fail the first ``<k>`` serve-daemon batch dispatches at their
    primary engine (:func:`take_serve_fault` consumes the budget) — the
    mid-queue fault that drives the daemon's retry/degrade ladder in the
    chaos soak.
``crash=<site>:<k>``
    Hard-kill the process (``os._exit(137)`` — indistinguishable from a
    SIGKILL to everything outside it: no atexit, no finally, no signal
    handler) on the ``<k>``-th arrival at the named instrumented site.
    Sites: ``post-admit`` (ticket admitted to the in-memory queue,
    journal record NOT yet written), ``mid-frame`` (half of a WAL frame
    written to the OS, then death — the torn-tail rehearsal),
    ``post-dispatch`` (batch computed, RESOLVE record NOT yet
    journaled). The write-ahead journal's crash-matrix test drives all
    three to prove the per-fsync-policy loss bounds in
    ``serve/wal.py``. The session-pool lifecycle adds four more, each
    firing AFTER its handle-lifecycle frame is journaled but BEFORE the
    pool action runs: ``post-create``, ``post-step``, ``post-snapshot``,
    ``post-evict`` — the pool crash matrix proves resume re-materializes
    exactly the journaled state (a journaled-but-unapplied step is
    applied on resume; nothing acked is ever lost). The fleet membership
    protocol adds two more: ``post-rejoin`` (a rejoining/destination
    worker journaled a claimed session's CREATE+STEP handshake frames,
    the source's EVICT frame NOT yet written — a kill here leaves the
    session journaled at BOTH workers with identical resumable state,
    the at-most-duplicated, never-lost edge) and ``mid-drain`` (a
    drained worker's bucket was adopted — journaled — at its
    destination, the source's ``re-homed`` SHED frame NOT yet written —
    same duplication-not-loss edge for tickets). The membership crash
    matrix drives both across a kill -9 and asserts the books still
    balance over exactly the acked set.
``kill_worker=<i>:<k>``
    Fleet drill: hard-kill (``os._exit(137)``) the serving worker whose
    ``worker_index`` is ``<i>`` on its ``<k>``-th batch dispatch, after
    the DISPATCH frame hits the journal but before any engine runs — a
    mid-dispatch death, so the router's WAL replay must see the chunk
    in-flight and re-home it. Every process of a fleet shares one
    ``MOMP_CHAOS`` value; the index match makes exactly one worker the
    victim (:func:`kill_worker_armed` counts per-process arrivals, and
    processes with a different — or no — worker index never count).
``aot_corrupt=<kind>:<k>``
    Damage the first ``<k>`` AOT-cache artifacts ON DISK immediately
    after their crash-atomic save (:func:`take_aot_corrupt` consumes the
    budget). Kinds: ``bitflip`` (one payload byte flipped — the CRC
    catches it on the next load, the ``aot:corrupt`` quarantine path) and
    ``skew`` (envelope rewritten with a fake jax version in the stored
    fingerprint — valid CRC, exercises the key-stale rejection). The
    in-memory program the saving process holds stays good, so the fault
    lands where real bit rot does: in the NEXT process's warm resume.
``seed=<int>``
    Seed for corrupted-value generation (default 0).
``noguard``
    Inject without arming the guards — the test aid that proves a fault
    actually lands (the run must then *diverge*).

Injection decisions are made at TRACE time: a poisoned trace stays
poisoned for every execution of that compiled program ("sticky" faults —
a corrupted exchange corrupts every step through it), and recovery paths
re-trace under :func:`suppressed` to get a clean program. When
``MOMP_CHAOS`` is unset, :func:`active_plan` returns ``None`` and every
hook degenerates to a single ``is None`` check — no injection ops are
ever built into a program, no jit-cache key changes, nothing reachable.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os

_HOP_KINDS = ("nan", "inf")
_HALO_KINDS = ("corrupt", "drop")

#: Instrumented hard-kill sites for the ``crash=<site>:<k>`` token.
CRASH_SITES = ("post-admit", "mid-frame", "post-dispatch",
               "post-create", "post-step", "post-snapshot", "post-evict",
               "post-rejoin", "mid-drain")

#: The exit status a hard kill reports — 128+SIGKILL, so a requeue loop
#: or CI harness cannot tell an injected crash from a real ``kill -9``.
CRASH_EXIT = 137

#: Artifact-damage modes for the ``aot_corrupt=<kind>:<k>`` token.
AOT_CORRUPT_KINDS = ("bitflip", "skew")


@dataclasses.dataclass
class FaultPlan:
    """A parsed ``MOMP_CHAOS`` spec plus its (tiny) runtime state."""

    raw: str
    seed: int = 0
    hop_poison: tuple[str, int] | None = None  # ("nan"|"inf", hop index)
    halo_fault: str | None = None  # "corrupt" | "drop"
    delay_s: float = 0.0
    preempt_step: int | None = None
    guard: bool = True
    preempt_fired: bool = False  # in-process refire latch
    serve_fail: int = 0  # total serve-dispatch faults to inject
    serve_failed: int = 0  # runtime count consumed so far
    crash_site: str | None = None  # instrumented site to hard-kill at
    crash_at: int = 0  # 1-based arrival count that fires the kill
    crash_hits: int = 0  # runtime arrivals counted so far
    kill_worker_idx: int | None = None  # fleet worker index to hard-kill
    kill_worker_at: int = 0  # 1-based dispatch count that fires the kill
    kill_worker_hits: int = 0  # runtime dispatches counted so far
    aot_corrupt_kind: str | None = None  # "bitflip" | "skew"
    aot_corrupt: int = 0  # total artifact saves to damage
    aot_corrupted: int = 0  # runtime count consumed so far

    @classmethod
    def parse(cls, raw: str) -> "FaultPlan":
        plan = cls(raw=raw)
        for token in raw.split(";"):
            token = token.strip()
            if not token:
                continue
            key, _, val = token.partition("=")
            try:
                if key in ("nan_hop", "inf_hop"):
                    plan.hop_poison = (key[:3], int(val))
                elif key == "halo":
                    if val not in _HALO_KINDS:
                        raise ValueError(f"want one of {_HALO_KINDS}")
                    plan.halo_fault = val
                elif key == "delay":
                    plan.delay_s = float(val)
                    if plan.delay_s < 0:
                        raise ValueError("negative delay")
                elif key == "preempt":
                    plan.preempt_step = int(val)
                elif key == "serve_fail":
                    plan.serve_fail = int(val)
                    if plan.serve_fail < 0:
                        raise ValueError("negative serve_fail")
                elif key == "crash":
                    site, _, k = val.partition(":")
                    if site not in CRASH_SITES:
                        raise ValueError(f"want one of {CRASH_SITES}")
                    plan.crash_site = site
                    plan.crash_at = int(k) if k else 1
                    if plan.crash_at < 1:
                        raise ValueError("crash count must be >= 1")
                elif key == "kill_worker":
                    idx, _, k = val.partition(":")
                    plan.kill_worker_idx = int(idx)
                    if plan.kill_worker_idx < 0:
                        raise ValueError("worker index must be >= 0")
                    plan.kill_worker_at = int(k) if k else 1
                    if plan.kill_worker_at < 1:
                        raise ValueError("kill count must be >= 1")
                elif key == "aot_corrupt":
                    kind, _, k = val.partition(":")
                    if kind not in AOT_CORRUPT_KINDS:
                        raise ValueError(f"want one of {AOT_CORRUPT_KINDS}")
                    plan.aot_corrupt_kind = kind
                    plan.aot_corrupt = int(k) if k else 1
                    if plan.aot_corrupt < 1:
                        raise ValueError("aot_corrupt count must be >= 1")
                elif key == "seed":
                    plan.seed = int(val)
                elif key == "noguard" and not val:
                    plan.guard = False
                else:
                    raise ValueError("unknown token")
            except ValueError as e:
                raise ValueError(
                    f"MOMP_CHAOS: bad token {token!r} in {raw!r} ({e})"
                ) from None
        return plan

    def preempt_pending(self, step: int) -> bool:
        """Will the preemption still fire for a run currently at ``step``?

        False once fired in this process, and false when the run already
        starts at/after the preempt step — a ``--resume`` of the same
        spec must continue, not re-die at the step it resumed from.
        """
        return (
            self.preempt_step is not None
            and not self.preempt_fired
            and step < self.preempt_step
        )


_CACHE: tuple[str | None, FaultPlan | None] = (None, None)
_SUPPRESS = 0


def active_plan() -> FaultPlan | None:
    """The live :class:`FaultPlan`, or ``None`` when ``MOMP_CHAOS`` is
    unset/empty or injection is :func:`suppressed`. Cached per spec value
    so runtime state (the preemption latch) persists across calls."""
    global _CACHE
    if _SUPPRESS:
        return None
    raw = os.environ.get("MOMP_CHAOS", "")
    if not raw:
        return None
    if _CACHE[0] != raw:
        _CACHE = (raw, FaultPlan.parse(raw))
    return _CACHE[1]


def reset() -> None:
    """Drop the cached plan (tests switch specs mid-process)."""
    global _CACHE
    _CACHE = (None, None)


@contextlib.contextmanager
def suppressed():
    """No injection inside: recovery paths re-trace their programs here so
    a transient fault does not re-fire on the very dispatch that retries
    it (:func:`active_plan` returns ``None`` within)."""
    global _SUPPRESS
    _SUPPRESS += 1
    try:
        yield
    finally:
        _SUPPRESS -= 1


def trace_key(tag: str):
    """Jit-cache salt for chaos-aware dispatches: a poisoned trace must
    never be cache-shared with a clean one. ``None`` (the no-chaos key)
    whenever no plan is active."""
    plan = active_plan()
    return None if plan is None else (tag, plan.raw)


def hop_poison_spec() -> tuple[str, int] | None:
    """Trace-time query for the ring fold engines: ``(kind, hop)`` to
    poison, or ``None`` (no plan / suppressed / no hop fault)."""
    plan = active_plan()
    return None if plan is None else plan.hop_poison


def poison_hop(kb, vb, j, spec):
    """Poison a ring hop's K/V partials when ``j`` equals the planned hop.

    ``j`` may be a python int (the final unrolled fold) or a traced loop
    index: the hit test rides the program as data, so one traced fold
    body poisons exactly the planned hop at runtime.
    """
    import jax.numpy as jnp

    kind, hop = spec
    bad = jnp.float32(jnp.nan if kind == "nan" else jnp.inf)
    m = jnp.where(jnp.asarray(j) == hop, bad, jnp.float32(0))
    return kb + m.astype(kb.dtype), vb + m.astype(vb.dtype)


def poisoned_fold(fold, spec):
    """Wrap a ring fold ``(j, state, kb, vb) -> state`` so the planned
    hop's K/V arrive poisoned."""

    def wrapped(j, state, kb, vb):
        kb, vb = poison_hop(kb, vb, j, spec)
        return fold(j, state, kb, vb)

    return wrapped


def halo_ghost_spec() -> tuple[str, int] | None:
    """Trace-time query for the halo exchange: ``(kind, seed)`` to apply
    to ghost rows/columns, or ``None``."""
    plan = active_plan()
    if plan is None or plan.halo_fault is None:
        return None
    return (plan.halo_fault, plan.seed)


def corrupt_ghost(ghost, spec):
    """A faulted ghost block: zeroed ("drop" — the exchange never
    arrived) or filled with a seeded out-of-range value ("corrupt")."""
    import numpy as np
    import jax.numpy as jnp

    kind, seed = spec
    if kind == "drop":
        return jnp.zeros_like(ghost)
    val = int(np.random.default_rng(seed).integers(2, 200))
    return jnp.full_like(ghost, val)


def take_serve_fault() -> bool:
    """Consume one serve-dispatch fault from the plan's ``serve_fail``
    budget: ``True`` means "this dispatch must fail" (the daemon's
    primary-engine thunk raises). Stateful like the preemption latch —
    each call that returns ``True`` spends one fault, so the first ``k``
    dispatches fail and every later one runs clean. ``False`` whenever no
    plan is active or injection is :func:`suppressed` (recovery
    re-dispatches run clean by construction)."""
    plan = active_plan()
    if plan is None or plan.serve_failed >= plan.serve_fail:
        return False
    plan.serve_failed += 1
    return True


def take_aot_corrupt() -> str | None:
    """Consume one artifact-damage fault from the plan's ``aot_corrupt``
    budget: the kind (``"bitflip"``/``"skew"``) to apply to the artifact
    just saved, or ``None``. Stateful like :func:`take_serve_fault` —
    the first ``k`` saves are damaged, every later one stays clean — and
    inert when no plan is active or injection is :func:`suppressed`."""
    plan = active_plan()
    if plan is None or plan.aot_corrupted >= plan.aot_corrupt:
        return None
    plan.aot_corrupted += 1
    return plan.aot_corrupt_kind


def crash_armed(site: str) -> bool:
    """Count one arrival at instrumented ``site``; ``True`` exactly when
    this arrival is the planned ``<k>``-th — the caller must then tear
    whatever the site tears (a partial frame write, nothing) and call
    :func:`crash_now`. Counting is per-site-name against the single
    planned site, stateful like the preemption latch, and inert (no
    counting) when no plan targets this site or injection is
    :func:`suppressed`."""
    plan = active_plan()
    if plan is None or plan.crash_site != site:
        return False
    plan.crash_hits += 1
    return plan.crash_hits == plan.crash_at


def kill_worker_armed(worker_index: int | None) -> bool:
    """Count one batch dispatch of fleet worker ``worker_index``;
    ``True`` exactly when this dispatch is the planned ``<k>``-th of the
    planned victim — the caller must then :func:`crash_now`. Inert (no
    counting) for processes with no worker index, a non-matching index,
    no plan, or :func:`suppressed` injection — the whole fleet shares
    one ``MOMP_CHAOS`` value and only the victim ever dies."""
    plan = active_plan()
    if (plan is None or worker_index is None
            or plan.kill_worker_idx != worker_index):
        return False
    plan.kill_worker_hits += 1
    return plan.kill_worker_hits == plan.kill_worker_at


def crash_now() -> None:
    """Die as hard as ``kill -9``: ``os._exit`` runs no atexit hooks, no
    ``finally`` blocks, no signal handlers, flushes nothing — the point
    is that ONLY what was already durably journaled survives."""
    os._exit(CRASH_EXIT)


def dispatch_delay() -> float:
    """Seconds of host-side delay to inject per guarded dispatch (0.0
    when inactive)."""
    plan = active_plan()
    return 0.0 if plan is None else plan.delay_s
