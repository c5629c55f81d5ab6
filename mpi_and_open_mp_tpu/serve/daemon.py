"""Supervised serving daemon: deadline scheduling + recovery ladder.

The always-on layer over the ``serve`` batching library. The reference's
serving story is one workload per ``mpirun`` launch and a PBS queue that
requeues the whole job on any failure; here a single process keeps a
bounded admission-controlled queue (``serve.queue``), flushes shape
buckets when they fill OR when their oldest ticket hits the policy
deadline (padding waste traded against p99 — a bucket that never fills
still flushes at ``max_wait_s``), and wraps every batch dispatch in a
supervision envelope so one poisoned request or wedged engine cannot
take the process down:

* **Engine ladder** — ``robust.guards.with_fallback`` over the batched
  native path → the vmapped XLA path → the NumPy oracle; a self-healed
  dispatch carries the ``:recovered`` provenance suffix on every ticket
  it resolved and lands in the process recovery log (``bench.py``
  publishes it — a silently degraded batch would launder a fault into a
  clean-looking artifact).
* **Bounded retry** — a full-ladder failure retries behind the
  ``robust.watchdog`` capped-exponential backoff with seeded jitter,
  never past ``max_retries`` or any member ticket's end-to-end timeout;
  exhaustion sheds the chunk with an explicit reason instead of looping.
* **Preemption** — SIGTERM/SIGINT land as a flag checked between batch
  dispatches (``robust.preempt``): the in-flight batch completes, the
  pending queue snapshots through the crash-atomic CRC state checkpoint
  (``utils.checkpoint.save_state``), and :class:`Preempted` propagates so
  drivers exit 75 (EX_TEMPFAIL) for the scheduler's requeue;
  ``--resume`` restores every drained ticket, so an admitted request is
  never silently dropped. ``MOMP_CHAOS preempt=<k>`` rehearses the same
  path after ``k`` dispatched batches, and ``serve_fail=<k>`` drives the
  ladder mid-queue.
* **Hard-kill durability** — the drain checkpoint only exists if the
  process got to write it; a ``kill -9``/OOM/node loss never runs that
  code. With ``wal_path`` set, every ticket transition is journaled
  through the write-ahead log (``serve.wal``) *before* the daemon acts
  on it — admit, dispatch-begin, resolve, shed — under the
  policy-selectable fsync ladder, so :meth:`ServingDaemon.resume_any`
  can reconstruct the exact pending set (plus any in-flight batch, re-
  dispatched idempotently — dispatch is pure) from a process that died
  at an *arbitrary* instruction. Resume ladder: WAL snapshot+tail →
  drain checkpoint → fresh. ``MOMP_CHAOS crash=<site>:<k>`` hard-kills
  at the instrumented sites so the loss bound is proved, not assumed.

Every admission, shed, retry, degrade, and drain decision emits ``obs``
spans/events and metrics (``serve.*``), so a bench line or a CI soak can
assert the full accounting: requests == resolved + shed, always.
"""

from __future__ import annotations

import argparse
import collections
import contextlib
import json
import os
import sys
import time

import numpy as np

from mpi_and_open_mp_tpu.robust import chaos, guards, watchdog
from mpi_and_open_mp_tpu.robust.preempt import (
    EXIT_PREEMPTED, Preempted, SimulatedPreemption, flush_on_signal)
from mpi_and_open_mp_tpu.serve import policy as policy_mod
from mpi_and_open_mp_tpu.serve import wal as wal_mod
from mpi_and_open_mp_tpu.serve.batcher import bucket_batch_size
from mpi_and_open_mp_tpu.serve.policy import ServePolicy, percentile
from mpi_and_open_mp_tpu.serve.queue import (
    DONE, PENDING, SHED, ServeQueue, Ticket)
from mpi_and_open_mp_tpu.utils import checkpoint as checkpoint_mod


class ServingDaemon:
    """One supervised worker loop over a :class:`ServeQueue`.

    ``clock``/``sleep`` are injectable (tests drive deadlines and backoff
    without wall time); the default clock is monotonic — ticket
    timestamps never cross a process boundary raw (the checkpoint
    restores them against the resuming process's clock).
    """

    def __init__(self, policy: ServePolicy | None = None, *,
                 checkpoint_path: str | None = None,
                 wal_path: str | None = None,
                 wal_fsync: str = "every-record",
                 wal_compact_bytes: int = 1 << 20,
                 aot_cache=None,
                 plan_store=None,
                 worker_index: int | None = None,
                 pool_budget_bytes: int | None = None,
                 clock=time.monotonic, sleep=time.sleep):
        self.policy = policy or ServePolicy()
        # Fleet identity: which shard of a serve.fleet this process is.
        # None for the classic single-daemon deployment; the chaos
        # kill_worker=<i>:<k> drill targets exactly one index.
        self.worker_index = worker_index
        self.queue = ServeQueue(self.policy)
        self.checkpoint_path = checkpoint_path
        self._clock = clock
        self._sleep = sleep
        self._batches = 0
        self._retries = 0
        self._degraded = 0
        # Durable program store (serve.aotcache.AOTCache) — when set, the
        # dispatch ladder gets an `aot:*` top rung and resume preloads the
        # bucket executables, so the first restored ticket never waits on
        # a trace+compile. None = every dispatch traces as before.
        self._aot = aot_cache
        # Durable tuned-plan store (tune.plans.PlanStore) — installed at
        # construction so EVERY resume rung (wal/checkpoint/fresh) comes
        # up with plans steering native_path_batch before the first
        # dispatch, exactly as the AOT preload warms executables. None =
        # heuristics only, the historical behavior.
        self._plans = plan_store
        self._plans_summary = (plan_store.install()
                               if plan_store is not None else None)
        self._created_at = self._clock()
        self._first_result_s: float | None = None  # cold-start latency
        # The journal's "one chunk" loss bound under every-chunk is
        # literal: the buffer never holds more records than one dispatch
        # batch admits.
        self._wal = (wal_mod.TicketWAL(
            wal_path, fsync=wal_fsync,
            chunk_records=self.policy.max_batch,
            compact_bytes=wal_compact_bytes)
            if wal_path else None)
        # Device-resident session pool (serve.pool.SessionPool), built
        # lazily on the first create_session — single-shot burst daemons
        # never pay for it. `_session_log` is the HOST mirror of the
        # journal's view of every live session ({id, board, steps,
        # wall}): compaction snapshots it without touching the device,
        # and resume re-materializes into both the log and the pool.
        self._pool = None
        self._pool_budget = pool_budget_bytes
        self._session_log: dict[str, dict] = {}

    # -- intake ------------------------------------------------------------

    def submit(self, board: np.ndarray, steps: int,
               session: str | None = None,
               workload: str = "life") -> Ticket:
        """Admit (or reject-with-reason) one request; see
        :meth:`ServeQueue.submit`. An ADMITTED ticket is journaled before
        this returns — under ``every-record`` fsync the caller's ack
        implies durability (the crash-matrix's zero-acked-loss bound).
        Door-shed tickets are terminal before they exist anywhere worth
        replaying, so they never touch the journal. ``session`` is the
        fleet affinity key; it rides the journal so a router can re-home
        a dead worker's pending set by consistent hash. ``workload``
        names the stencil rule (``stencils.get``) — it buckets the
        dispatch, picks the engine ladder, and rides the journal."""
        t = self.queue.submit(board, steps, self._clock(), session=session,
                              workload=workload)
        if t.state == PENDING and self._wal is not None:
            # Instrumented crash site: admitted in memory, journal record
            # not yet written. A death here loses a ticket whose submit()
            # never returned — the caller was never acked, so the
            # zero-ACKED-loss bound is intact.
            if chaos.crash_armed("post-admit"):
                chaos.crash_now()
            self._wal.admit(t.id, t.board, t.steps, session=t.session,
                            workload=t.workload)
        return t

    # -- device-resident sessions -------------------------------------------

    @property
    def pool(self):
        """The device-resident session pool, built on first use."""
        if self._pool is None:
            from mpi_and_open_mp_tpu.serve.pool import SessionPool

            kw = {}
            if self._pool_budget is not None:
                kw["device_budget_bytes"] = self._pool_budget
            self._pool = SessionPool(**kw)
        return self._pool

    def create_session(self, session: str, board: np.ndarray):
        """Admit a board into the pool under ``session``. The board
        crosses the wire exactly once, here; the CREATE frame is durable
        before the device sees it, so kill -9 at any later instruction
        re-materializes the session from the journal. Returns the
        handle."""
        session = str(session)
        if session in self._session_log:
            raise ValueError(
                f"create_session: session {session!r} is already live")
        board = np.asarray(board)
        wall = time.time()
        if self._wal is not None:
            self._wal.pool_create(session, board, wall=wall)
            if chaos.crash_armed("post-create"):
                chaos.crash_now()
        handle = self.pool.create(session, board)
        self._session_log[session] = {
            "id": session, "board": board.copy(), "steps": 0, "wall": wall}
        return handle

    def step_session(self, session: str, steps: int) -> int:
        """Advance one resident session ``steps`` generations in place,
        synchronously (the ticketed fast path is
        :meth:`submit_session`). The STEP frame is write-ahead and
        authoritative: once this method returns, the advance survives
        any crash."""
        return self.step_sessions([str(session)], steps)

    def step_sessions(self, sessions: list[str], steps: int) -> int:
        steps = int(steps)
        for sid in sessions:
            if str(sid) not in self._session_log:
                raise ValueError(f"step_sessions: unknown session {sid!r}")
        if self._wal is not None:
            for sid in sessions:
                self._wal.pool_step(str(sid), steps)
            if chaos.crash_armed("post-step"):
                chaos.crash_now()
        n = self.pool.step_group([str(s) for s in sessions], steps)
        for sid in sessions:
            self._session_log[str(sid)]["steps"] += steps
        return n

    def submit_session(self, session: str, steps: int) -> Ticket:
        """Admit one resident step as a ticket — the handle-sized fast
        path. An admitted step journals exactly ONE frame (STEP, no
        ADMIT/DISPATCH/RESOLVE triple): write-ahead and authoritative,
        so the ack implied by this return is durable whether the
        dispatch happens in this process or is replayed into the pool
        on resume. Door-shed tickets never touch the journal."""
        session = str(session)
        if session not in self._session_log:
            raise ValueError(f"submit_session: unknown session {session!r}")
        t = self.queue.submit_session(
            session, self.pool.handle(session), steps, self._clock())
        if t.state == PENDING:
            if self._wal is not None:
                self._wal.pool_step(session, t.steps)
                if chaos.crash_armed("post-step"):
                    chaos.crash_now()
            self._session_log[session]["steps"] += t.steps
        return t

    def snapshot_session(self, session: str) -> np.ndarray:
        """Read a resident session's board (one device→host crossing).
        Parity contract: the returned board is bit-identical to the
        NumPy oracle advancing the create board by the journaled step
        total."""
        session = str(session)
        if session not in self._session_log:
            raise ValueError(
                f"snapshot_session: unknown session {session!r}")
        if self._wal is not None:
            self._wal.pool_snapshot(
                session, int(self._session_log[session]["steps"]))
            if chaos.crash_armed("post-snapshot"):
                chaos.crash_now()
        return self.pool.snapshot(session)

    def evict_session(self, session: str) -> np.ndarray:
        """Remove a session from the pool, returning its final board
        (the last wire crossing of the lifetime). The EVICT frame lands
        first, so a crash mid-evict replays to the evicted state rather
        than resurrecting the session."""
        session = str(session)
        if session not in self._session_log:
            raise ValueError(f"evict_session: unknown session {session!r}")
        if self._wal is not None:
            self._wal.pool_evict(session)
            if chaos.crash_armed("post-evict"):
                chaos.crash_now()
        board = self.pool.evict(session)
        del self._session_log[session]
        return board

    def adopt_session(self, session: str, board: np.ndarray,
                      steps: int):
        """The destination half of a pool re-home: journal a fresh
        CREATE + STEP lifetime on THIS worker's WAL, then let the
        device replay the advance (``board`` is the ORIGIN's create
        board; shipping it plus a step count moves one board across the
        wire instead of the whole history)."""
        session = str(session)
        board = np.asarray(board)
        steps = int(steps)
        wall = time.time()
        if self._wal is not None:
            self._wal.pool_create(session, board, wall=wall)
            if steps:
                self._wal.pool_step(session, steps)
            # Instrumented crash site: the destination half of a
            # membership handshake (rejoin adoption / drain migration)
            # is journaled, the SOURCE's EVICT frame is not — a kill
            # here leaves the session live in BOTH journals with
            # identical (create board, step total) resumable state:
            # duplicated, never lost, and bit-exact either way.
            if chaos.crash_armed("post-rejoin"):
                chaos.crash_now()
        handle = self.pool.create(session, board)
        if steps:
            self.pool.step(session, steps)
        self._session_log[session] = {
            "id": session, "board": board.copy(), "steps": steps,
            "wall": wall}
        return handle

    def sessions(self) -> list[str]:
        return list(self._session_log)

    def _rematerialize_pool(self, pool_sessions: dict[str, dict]) -> int:
        """Rebuild the device pool from a WAL replay's session map:
        every live session's create board enters the pool and advances
        by its journaled step total (a journaled-but-unacked step is
        applied — at-least-once on unacked work, zero acked loss)."""
        for sid, entry in pool_sessions.items():
            board = np.asarray(entry["board"])
            steps = int(entry["steps"])
            self.pool.create(sid, board)
            if steps:
                self.pool.step(sid, steps)
            self._session_log[sid] = {
                "id": sid, "board": board.copy(), "steps": steps,
                "wall": float(entry.get("wall", 0.0))}
        return len(pool_sessions)

    # -- fleet worker-mode hooks -------------------------------------------

    def release(self, tickets: list[Ticket],
                now: float | None = None) -> list[dict]:
        """Hand a group of PENDING tickets off this worker's books — the
        source half of a fleet re-home (wedged-worker drain) or a
        whole-bucket work steal. Each ticket sheds terminally here with
        the ``re-homed`` reason (journal frame first, so a later replay
        of THIS worker's WAL never re-dispatches work that now lives
        elsewhere) and comes back as a portable entry ``{board, steps,
        session, queued_s, wall}`` for :meth:`adopt` on the destination.
        Non-pending tickets are skipped — a result that already resolved
        must not be recomputed under a new id. Resident session tickets
        are skipped too: their STEP frames are already journaled and
        authoritative, so a pool re-home moves the SESSION (create board
        + step total, via :meth:`adopt_session`), never step tickets."""
        now = self._clock() if now is None else now
        live = [t for t in tickets
                if t.state == PENDING and t.board is not None]
        entries = self.export(live, now)
        self._shed_batch(live, policy_mod.SHED_REHOMED, now)
        return entries

    def export(self, tickets: list[Ticket],
               now: float | None = None) -> list[dict]:
        """Portable entries for a group of PENDING tickets WITHOUT
        closing this worker's books — the read half of :meth:`release`.
        A graceful drain adopts these at the destination FIRST and only
        then sheds them here: a crash between the halves leaves the
        bucket journaled at both workers (duplicated, re-dispatch is
        pure) instead of journaled at neither (lost). The wedge/steal
        path keeps the release-first order — there the source is
        already presumed dead and its journal replay is the source of
        truth."""
        now = self._clock() if now is None else now
        wall = time.time()
        return [
            {"board": np.asarray(t.board), "steps": t.steps,
             "session": t.session, "wall": wall,
             "workload": t.workload,
             "queued_s": t.queued_before_s + (now - t.submitted_at)}
            for t in tickets if t.state == PENDING and t.board is not None
        ]

    def adopt(self, entries: list[dict],
              now: float | None = None) -> list[Ticket]:
        """Admit re-homed/stolen entries (the destination half of
        :meth:`release`, and what the router feeds from a dead worker's
        WAL replay). No admission gate — the fleet already accepted this
        work once — and the carried ``queued_s``/``wall`` keep each
        ticket's end-to-end latency honest across the move. Adopted
        tickets are journaled like fresh admissions, so a crash of the
        ADOPTING worker re-homes them again instead of losing them."""
        now = self._clock() if now is None else now
        wall_now = time.time()
        out = []
        for e in entries:
            queued = float(e.get("queued_s", 0.0))
            wall = float(e.get("wall", 0.0))
            if wall:
                queued += max(0.0, wall_now - wall)
            t = self.queue.restore_ticket(
                e["board"], e["steps"], now, queued_s=queued,
                session=e.get("session"),
                workload=str(e.get("workload", "life")))
            if self._wal is not None:
                self._wal.admit(t.id, t.board, t.steps,
                                queued_s=queued, session=t.session,
                                workload=t.workload)
            out.append(t)
        return out

    @classmethod
    def resume(cls, checkpoint_path: str,
               policy: ServePolicy | None = None, **kw) -> "ServingDaemon":
        """A daemon whose queue starts from a drain checkpoint. Every
        pending ticket of the snapshot is re-admitted unconditionally
        (admission applies at the door, not to already-accepted work).
        Raises ``ValueError`` on a missing/corrupt/foreign checkpoint."""
        from mpi_and_open_mp_tpu.obs import trace

        state = checkpoint_mod.restore_state(checkpoint_path)
        daemon = cls(policy, checkpoint_path=checkpoint_path, **kw)
        restored = daemon.queue.restore(state, daemon._clock())
        trace.event("serve.resume", tickets=len(restored))
        if daemon._wal is not None:
            daemon._compact_wal()
        return daemon

    @classmethod
    def resume_any(cls, *, wal_path: str | None = None,
                   checkpoint_path: str | None = None,
                   policy: ServePolicy | None = None,
                   wal_fsync: str = "every-record",
                   **kw) -> tuple["ServingDaemon", str, dict]:
        """The resume ladder: WAL snapshot+tail → drain checkpoint →
        fresh. Returns ``(daemon, source, detail)`` where ``source`` is
        ``"wal"`` / ``"checkpoint"`` / ``"fresh"`` and ``detail`` carries
        replay accounting (and any swallowed ``wal_error``).

        The WAL rung survives deaths the checkpoint rung cannot: the
        drain checkpoint exists only if a polite signal handler got to
        run, while the journal was durable BEFORE the work happened. A
        WAL whose tail is torn replays to its last complete frame (loss
        bounded by the fsync policy); a WAL that is unreadable outright
        falls through to the checkpoint rung rather than refusing to
        serve. Tickets that were in-flight (DISPATCH without RESOLVE)
        come back pending — dispatch is pure, so redoing them is
        idempotent. After a WAL resume the journal is immediately
        compacted: the restored tickets carry NEW ids in this process,
        and rotation re-anchors the journal on them (also discarding any
        torn tail so fresh frames never sit behind garbage).

        Corrupt artifacts on EITHER durable rung quarantine to a
        generation-stamped ``.corrupt.<stamp>`` sibling
        (``utils.checkpoint.quarantine``) and the ladder falls through —
        a second corrupt resume gets its own forensic copy, never
        clobbering the first, and a rotten checkpoint degrades to a
        fresh daemon instead of a crash. With an ``aot_cache`` in
        ``**kw``, every rung ends in a preload phase: the bucket
        executables for the restored pending set deserialize (or build)
        BEFORE the first dispatch, so warm-resume p99 never eats a
        trace+compile (``detail["aot_preload"]``)."""
        from mpi_and_open_mp_tpu.obs import trace

        detail: dict = {}
        if wal_path and os.path.exists(wal_path):
            try:
                rep = wal_mod.replay(wal_path)
            except ValueError as e:
                detail["wal_error"] = str(e)[:300]
                trace.event("serve.resume.wal_error", error=str(e)[:200])
                # Quarantine the unreadable journal (forensics intact,
                # uniquely stamped): appending fresh frames behind a bad
                # head would poison every future replay too.
                q = checkpoint_mod.quarantine(wal_path)
                if q:
                    detail["wal_quarantine"] = q
            else:
                daemon = cls(policy, checkpoint_path=checkpoint_path,
                             wal_path=wal_path, wal_fsync=wal_fsync, **kw)
                daemon._wal._generation = rep.generation
                now = daemon._clock()
                wall_now = time.time()
                for entry in rep.pending:
                    queued = float(entry.get("queued_s", 0.0))
                    wall = float(entry.get("wall", 0.0))
                    if wall:
                        # Seconds the ticket sat in the DEAD process (and
                        # the gap until this restart) — wall clock is the
                        # only clock that crosses a process boundary.
                        queued += max(0.0, wall_now - wall)
                    daemon.queue.restore_ticket(
                        entry["board"], entry["steps"], now, queued_s=queued,
                        session=entry.get("session"),
                        workload=str(entry.get("workload", "life")))
                # Re-materialize the device pool BEFORE rotating the
                # journal: rotation snapshots the session log, so the
                # log must already hold every replayed session.
                if rep.pool_sessions:
                    daemon._rematerialize_pool(rep.pool_sessions)
                daemon._compact_wal()
                detail["wal_replay"] = rep.counts()
                trace.event("serve.resume", source="wal",
                            tickets=len(rep.pending))
                daemon._aot_preload(detail)
                daemon._plans_note(detail)
                return daemon, "wal", detail
        if checkpoint_path and os.path.exists(checkpoint_path):
            try:
                daemon = cls.resume(checkpoint_path, policy,
                                    wal_path=wal_path,
                                    wal_fsync=wal_fsync, **kw)
            except ValueError as e:
                # Same contract as the WAL rung: a corrupt/skewed drain
                # checkpoint is quarantined (stamped — the forensic copy
                # of an earlier corrupt resume survives) and the ladder
                # falls through to fresh rather than refusing to serve.
                detail["checkpoint_error"] = str(e)[:300]
                trace.event("serve.resume.checkpoint_error",
                            error=str(e)[:200])
                q = checkpoint_mod.quarantine(checkpoint_path)
                if q:
                    detail["checkpoint_quarantine"] = q
            else:
                daemon._aot_preload(detail)
                daemon._plans_note(detail)
                return daemon, "checkpoint", detail
        daemon = cls(policy, checkpoint_path=checkpoint_path,
                     wal_path=wal_path, wal_fsync=wal_fsync, **kw)
        trace.event("serve.resume", source="fresh", tickets=0)
        daemon._plans_note(detail)
        return daemon, "fresh", detail

    def _aot_preload(self, detail: dict | None = None) -> dict | None:
        """Warm the AOT cache for every (shape, dtype) currently pending:
        all power-of-two bucket programs up to ``max_batch`` are resident
        before the first dispatch. No-op without a cache or pending work;
        returns (and records in ``detail``) the warm-pass stats."""
        if self._aot is None:
            return None
        # The durable program store holds LIFE bucket executables only —
        # other stencil workloads trace per process (their rung ladder
        # has no aot top rung), so they contribute nothing to warm.
        boards = {(t.board.shape, str(np.asarray(t.board).dtype))
                  for t in self.queue.pending()
                  if t.board is not None and t.workload == "life"}
        if not boards:
            return None
        summary = self._aot.warm(sorted(boards), self.policy.max_batch)
        if detail is not None:
            detail["aot_preload"] = summary
        return summary

    def _plans_note(self, detail: dict | None = None) -> dict | None:
        """Record the plan-store install bookkeeping (done once, at
        construction) in the resume detail — an exit-75 requeue restarts
        with tuned plans AND their executables warm, observably."""
        if self._plans_summary is not None and detail is not None:
            detail["plans"] = self._plans_summary
        return self._plans_summary

    # -- the supervised loop ----------------------------------------------

    def serve(self, *, watch_signals: bool = True,
              idle_tick_s: float = 0.005) -> None:
        """Dispatch until every admitted ticket is terminal. Raises
        :class:`Preempted` (after checkpointing the queue) on SIGTERM/
        SIGINT or a chaos-plan preemption; anything else runs to a fully
        drained queue."""
        with flush_on_signal(watch_signals) as watch:
            while True:
                dispatched = self.pump(watch=watch)
                if not self.queue.pending():
                    if self._wal is not None:
                        self._wal.sync()
                    return
                if dispatched == 0:
                    self._check_interrupts(watch)
                    horizon = self.queue.next_deadline()
                    wait = idle_tick_s
                    if horizon is not None:
                        wait = max(1e-4, horizon - self._clock())
                    self._sleep(wait)

    def pump(self, now: float | None = None, *, drain: bool = False,
             watch=None) -> int:
        """Dispatch every currently-due chunk (all of them when
        ``drain``); returns the number of batches dispatched. Interrupt
        flags are honored BETWEEN chunk dispatches — an in-flight batch
        always completes (the drain half of the preemption contract)."""
        now = self._clock() if now is None else now
        n = 0
        for chunk in self.queue.due_chunks(now, drain=drain):
            self._check_interrupts(watch)
            self._dispatch_chunk(chunk)
            n += 1
        if self._wal is not None and self._wal.should_compact():
            self._compact_wal()
        if self._pool is not None:
            # Background lane hygiene: repack sparse planes left by dead
            # sessions while the queue is quiet — the device pays a
            # 32-at-a-time pack/unpack, never a per-lane shuffle.
            self._pool.maybe_compact()
        return n

    def drain(self) -> None:
        """Flush everything pending regardless of deadlines (shutdown
        path and tests)."""
        while self.queue.pending():
            self.pump(drain=True)

    # -- internals ---------------------------------------------------------

    def _compact_wal(self) -> None:
        """Rotate the journal around the CURRENT pending set: one
        crash-atomic snapshot (generation-stamped ``save_state`` file)
        plus a fresh WAL whose head frame points at it. Queued seconds
        are folded to now so a replay in a later process keeps the true
        end-to-end clock running."""
        now = self._clock()
        wall = time.time()
        entries = [
            {"id": t.id, "board": np.asarray(t.board), "steps": t.steps,
             "wall": wall, "session": t.session, "workload": t.workload,
             "queued_s": t.queued_before_s + (now - t.submitted_at)}
            for t in self.queue.pending() if t.board is not None
        ]
        self._wal.compact(entries, pool_sessions=self._session_log)

    def _shed_batch(self, tickets: list[Ticket], reason: str,
                    now: float) -> None:
        """Shed a group terminally, journal first — the SHED frame is
        what stops a replay from re-dispatching work the policy already
        refused (one frame for the group; the per-ticket accounting
        lives in the queue)."""
        if self._wal is not None and tickets:
            self._wal.shed([t.id for t in tickets], reason)
        for t in tickets:
            self.queue.shed_ticket(t, reason, now)

    def _check_interrupts(self, watch) -> None:
        if watch is not None and watch.fired is not None:
            self._preempt(signum=watch.fired)
        plan = chaos.active_plan()
        if (plan is not None and plan.preempt_pending(0)
                and self._batches >= plan.preempt_step):
            plan.preempt_fired = True
            self._preempt(simulated=True)

    def _preempt(self, signum: int | None = None,
                 simulated: bool = False) -> None:
        """Checkpoint the pending queue and stop. The drain decision is
        observable: a ``serve.drain`` event with the batch/pending counts
        and the checkpoint path rides the trace stream."""
        from mpi_and_open_mp_tpu.obs import metrics, trace

        path = None
        if self._wal is not None:
            self._wal.sync()
        if self.checkpoint_path:
            checkpoint_mod.save_state(
                self.checkpoint_path, self.queue.snapshot(self._clock()))
            path = self.checkpoint_path
        metrics.inc("serve.preempted")
        trace.event("serve.drain", batches=self._batches,
                    pending=self.queue.depth(), checkpoint=path or "")
        cls = SimulatedPreemption if simulated else Preempted
        raise cls(self._batches, checkpoint=path, signum=signum)

    def _validator(self, stack_shape: tuple, spec=None):
        """Sanity gate every rung's output passes before it resolves
        tickets. Life keeps the historic binary-board check; other
        stencil workloads validate through the spec's own invariant
        (state range for automata, finiteness for float fields)."""
        if spec is None or spec.name == "life":
            def ok(out) -> bool:
                a = np.asarray(out)
                return a.shape == stack_shape and bool((a <= 1).all())
        else:
            def ok(out) -> bool:
                a = np.asarray(out)
                return (a.shape == stack_shape
                        and all(spec.valid_board(b) for b in a))

        return ok

    def _engines(self, stack: np.ndarray, steps: int, spec=None):
        """The graceful-degradation ladder for one padded chunk, ranked:
        the durable AOT executable (when a cache is attached — a
        deserialized ``jax.export`` program that runs with ZERO
        retraces, oracle parity-gated on first use), then the batched
        native path — bitsliced board-planes when the stack qualifies,
        else the cell-packed ladder — then, under a bitsliced plan, the
        cell-packed native engine with the layout pinned off (a poisoned
        bitsliced dispatch degrades one rung, not straight to vmapped
        XLA), then the always-compilable vmapped XLA bit engine, then
        the NumPy oracle — the one engine that needs no device at all.
        The AOT rung's stamp carries its cache provenance:
        ``aot:<path>`` on a hit/resident program, ``aot:<path>:miss`` /
        ``aot:<path>:corrupt`` / ``aot:<path>:stale`` when this dispatch
        had to build fresh (a bad artifact was quarantined first).
        Fallback engines run under ``chaos.suppressed()`` so a recovery
        dispatch cannot be re-failed by the fault that triggered it.

        Non-life stencil workloads (``spec`` given and not life) get a
        two-rung ladder instead — the spec-generated vmapped roll engine
        (``batch:stencil:<name>``) over the spec's own NumPy oracle —
        because the bit-packed/bit-sliced machinery below is a Life
        binary-board specialization by construction."""
        import jax

        from mpi_and_open_mp_tpu.ops import bitlife, pallas_life

        if spec is not None and spec.name != "life":
            from mpi_and_open_mp_tpu import stencils

            def stencil_rung(runner, guarded: bool):
                def run():
                    import jax.numpy as jnp

                    if guarded and chaos.take_serve_fault():
                        raise RuntimeError(
                            "chaos: injected serve dispatch fault")
                    with (contextlib.nullcontext() if guarded
                          else chaos.suppressed()):
                        return np.asarray(
                            runner(jnp.asarray(stack), steps))
                return run

            def stencil_oracle():
                with chaos.suppressed():
                    out = np.array(stack, copy=True)
                    for b in range(out.shape[0]):
                        out[b] = stencils.oracle_run(spec, out[b], steps)
                    return out

            # Every legal engine for this spec, ladder order: the roll
            # engine leads, then the Pallas padded kernel
            # (single-channel specs), then the PR 20 engine families
            # where their legality gates + the MOMP_ENGINE_FAMILY pin
            # allow. An installed tuned plan promotes ITS rung to the
            # front (so the tuner's winner is exactly what serving
            # runs); the front rung is the guarded primary, the rest
            # are chaos-suppressed fallbacks, the oracle closes.
            avail = [(f"batch:stencil:{spec.name}", "stencil:roll",
                      lambda s, n: stencils.run_roll_batch(spec, s, n))]
            if stencils.pallas_batch_supported(spec, stack.shape):
                avail.append(
                    (f"batch:stencil-pallas:{spec.name}",
                     "stencil:pallas",
                     lambda s, n: stencils.run_padded_pallas_batch(
                         spec, s, n)))
            if (stencils.separable_supported(spec)
                    and stencils.family_allowed("sep")):
                avail.append(
                    (f"batch:stencil-sep:{spec.name}", "stencil:sep",
                     lambda s, n: stencils.run_family_batch(
                         spec, s, n, "sep")))
            if (stencils.fft_supported(spec)
                    and stencils.family_allowed("fft")):
                avail.append(
                    (f"batch:stencil-fft:{spec.name}", "stencil:fft",
                     lambda s, n: stencils.run_family_batch(
                         spec, s, n, "fft")))
            planned = pallas_life.planned_path(spec.name, stack.shape)
            avail.sort(key=lambda e: e[1] != planned)
            rungs = [(name, stencil_rung(runner, i == 0))
                     for i, (name, _, runner) in enumerate(avail)]
            return rungs + [("oracle", stencil_oracle)]

        on_tpu = jax.default_backend() == "tpu"
        path = pallas_life.native_path_batch(stack.shape, on_tpu=on_tpu)

        rungs = []
        if self._aot is not None:
            digest, exported, status = self._aot.ensure(
                stack.shape, stack.dtype)
            if exported is not None:
                stamp = (f"aot:{path}" if status in ("memory", "hit")
                         else f"aot:{path}:{status}")

                def aot():
                    if chaos.take_serve_fault():
                        raise RuntimeError(
                            "chaos: injected serve dispatch fault")
                    return self._aot.call_verified(digest, stack, steps)

                rungs.append((stamp, aot))

        def native():
            import jax.numpy as jnp

            if chaos.take_serve_fault():
                raise RuntimeError("chaos: injected serve dispatch fault")
            return np.asarray(
                pallas_life.life_run_vmem_batch(jnp.asarray(stack), steps))

        def xla():
            import jax.numpy as jnp

            with chaos.suppressed():
                return np.asarray(
                    bitlife.life_run_bits_xla_batch(jnp.asarray(stack),
                                                    steps))

        def oracle():
            from mpi_and_open_mp_tpu.ops.life_ops import life_step_numpy

            with chaos.suppressed():
                out = np.array(stack, copy=True)
                for b in range(out.shape[0]):
                    board = out[b]
                    for _ in range(steps):
                        board = life_step_numpy(board)
                    out[b] = board
                return out

        rungs.append((f"batch:{path}", native))
        if path == "bitsliced":
            # One-rung degrade: re-plan the same stack with the layout
            # pinned off. Off-TPU the cell-packed plan is "xla" already,
            # identical to the rung below — skip the duplicate.
            cp_path = pallas_life.native_path_batch(
                stack.shape, on_tpu=on_tpu, allow_bitsliced=False)
            if cp_path != "xla":

                def cellpacked():
                    import jax.numpy as jnp

                    with chaos.suppressed(), \
                            pallas_life._bitslice_pinned(False):
                        return np.asarray(pallas_life.life_run_vmem_batch(
                            jnp.asarray(stack), steps))

                rungs.append((f"batch:{cp_path}", cellpacked))
        rungs += [("batch:xla", xla), ("oracle", oracle)]
        return rungs

    def _dispatch_pool_chunk(self, chunk: list[Ticket]) -> None:
        """Resolve one slab-group of resident step tickets with a single
        in-place pool dispatch. No WAL frames here — each ticket's STEP
        frame was journaled (authoritative) at submit, so a death at any
        point in this method replays the advance into the pool on
        resume; no timeout shed either, for the same reason (the step is
        already promised durable, so it must happen exactly once)."""
        from mpi_and_open_mp_tpu.obs import metrics, trace

        steps = chunk[0].steps
        # Open-loop traffic can park TWO steps for the same session in
        # one bucket. `step_group` ORs each lane into the dispatch mask,
        # so duplicates collapse: the lane would advance `steps` once
        # while both tickets resolve. Split the chunk into waves of
        # distinct sessions and dispatch the waves in arrival order —
        # the all-distinct common case stays one dispatch.
        waves: list[list[Ticket]] = []
        for t in chunk:
            for wave in waves:
                if all(w.session != t.session for w in wave):
                    wave.append(t)
                    break
            else:
                waves.append([t])
        with trace.span("serve.dispatch.pool", requests=len(chunk),
                        steps=steps):
            for wave in waves:
                self.pool.step_group([t.session for t in wave], steps)
        now = self._clock()
        for t in chunk:
            self.queue.resolve(t, None, "pool:bitsliced", now)
        if self._first_result_s is None:
            self._first_result_s = now - self._created_at
        self._batches += 1
        metrics.inc("serve.batches")

    def _dispatch_chunk(self, chunk: list[Ticket]) -> None:
        from mpi_and_open_mp_tpu.obs import metrics, trace

        if chunk and chunk[0].handle is not None:
            self._dispatch_pool_chunk(chunk)
            return

        p = self.policy
        now = self._clock()
        # Per-request timeout, checked at the last instant before device
        # work: a ticket that already blew its end-to-end budget (earlier
        # retries, chaos delays, a starved bucket) sheds explicitly
        # instead of burning a dispatch whose answer nobody is waiting
        # for.
        live, stale = [], []
        for t in chunk:
            if now - t.submitted_at > p.request_timeout_s:
                stale.append(t)
            else:
                live.append(t)
        self._shed_batch(stale, policy_mod.SHED_TIMEOUT, now)
        if not live:
            return

        if self._wal is not None:
            # DISPATCH_BEGIN before any engine runs: a death between here
            # and the RESOLVE frame replays these tickets as pending (the
            # in-flight batch) and redispatches them — dispatch is pure,
            # so the redo is idempotent.
            self._wal.dispatch_begin([t.id for t in live])
        # Fleet chaos drill: kill_worker=<i>:<k> dies HERE, mid-dispatch
        # — the DISPATCH frame is journaled, no RESOLVE ever will be, so
        # the router's replay of this worker's WAL must surface the
        # chunk as in-flight and re-home it (dispatch is pure; redoing
        # it on a survivor is idempotent).
        if chaos.kill_worker_armed(self.worker_index):
            chaos.crash_now()
        from mpi_and_open_mp_tpu import stencils

        spec = stencils.get(live[0].workload)
        shape = live[0].board.shape
        steps = live[0].steps
        padded = bucket_batch_size(
            len(live), p.max_batch,
            slice_width=self.queue._slice_width(live[0].bucket_key))
        stack = np.zeros((padded, *shape), dtype=live[0].board.dtype)
        for i, t in enumerate(live):
            stack[i] = t.board
        # Life keeps the historic two-arg call (its ladder never needs
        # the spec); non-life workloads thread theirs through.
        if spec.name == "life":
            engines = self._engines(stack, steps)
        else:
            engines = self._engines(stack, steps, spec)
        validator = self._validator(stack.shape, spec)
        # One jittered backoff schedule per chunk, seeded off the lead
        # ticket so concurrent requeued daemons desynchronise while any
        # single run stays reproducible.
        waits = watchdog.backoff(p.backoff_base_s, p.backoff_cap_s,
                                 jitter=p.backoff_jitter,
                                 seed=p.seed + live[0].id)
        deadline = min(t.submitted_at for t in live) + p.request_timeout_s
        attempt = 0
        while True:
            delay = chaos.dispatch_delay()
            if delay:
                self._sleep(delay)
            try:
                with trace.span(
                    "serve.dispatch", shape=f"{shape[-2]}x{shape[-1]}",
                    steps=steps, requests=len(live), padded=padded,
                    workload=spec.name, attempt=attempt,
                ):
                    out, stamp, _notes = guards.with_fallback(
                        engines, validator=validator)
                break
            except guards.FallbackExhausted as e:
                attempt += 1
                self._retries += 1
                metrics.inc("serve.retries")
                trace.event("serve.retry", attempt=attempt,
                            notes="; ".join(e.notes)[:200])
                now = self._clock()
                if attempt > p.max_retries:
                    self._shed_batch(live, policy_mod.SHED_DISPATCH, now)
                    return
                wait = next(waits)
                if now + wait > deadline:
                    self._shed_batch(live, policy_mod.SHED_TIMEOUT, now)
                    return
                self._sleep(wait)

        if stamp.endswith(":recovered"):
            # The degrade decision, on the record: aggregate count +
            # ordered stamp in the process recovery log (what bench.py
            # publishes as `recovered`) + a trace event via the funnel.
            self._degraded += 1
            metrics.inc("serve.degraded")
            guards.record_recovery(f"serve:{stamp}")
        now = self._clock()
        host = np.asarray(out)[:len(live)]
        if self._wal is not None:
            # Instrumented crash site: batch computed, RESOLVE frame not
            # yet journaled. A death here replays the batch as in-flight
            # and the resumed daemon redoes it — results were never
            # surfaced, so redoing is the correct (idempotent) outcome.
            if chaos.crash_armed("post-dispatch"):
                chaos.crash_now()
            self._wal.resolve([t.id for t in live], engine=stamp)
        for i, t in enumerate(live):
            self.queue.resolve(t, host[i], stamp, now)
        if self._first_result_s is None:
            # Cold-start latency: daemon construction to the first
            # resolved result — the number the AOT cache exists to crush
            # (trace+compile lands here on a cold resume, pure
            # deserialization on a warm one).
            self._first_result_s = now - self._created_at
        self._batches += 1
        metrics.inc("serve.batches")
        if padded > len(live):
            metrics.inc("serve.padding", padded - len(live))

    # -- accounting --------------------------------------------------------

    def summary(self) -> dict:
        """The accounting the soak test and the bench line read: every
        ticket in exactly one terminal bucket, latency percentiles over
        the resolved set, engine/reason breakdowns."""
        tickets = self.queue.tickets()
        done = [t for t in tickets if t.state == DONE]
        shed = [t for t in tickets if t.state == SHED]
        lat = [t.latency_s for t in done]
        out = {
            "requests": len(tickets),
            "resolved": len(done),
            "shed": len(shed),
            "pending": self.queue.depth(),
            "batches": self._batches,
            "retries": self._retries,
            "degraded": self._degraded,
            "shed_reasons": dict(collections.Counter(
                t.reason for t in shed)),
            "engines": dict(collections.Counter(t.engine for t in done)),
            "p50_latency_s": round(percentile(lat, 50), 6),
            "p99_latency_s": round(percentile(lat, 99), 6),
        }
        if self._first_result_s is not None:
            out["cold_first_result_s"] = round(self._first_result_s, 6)
        if self._pool is not None:
            s = self._pool.stats()
            out["pool"] = s
            # Flat copies of the fields the bench line and the
            # regression sentinel watch.
            out["pool_sessions"] = s["sessions"]
            out["pool_hits"] = s["hits"]
            out["pool_misses"] = s["misses"]
            out["pool_evictions"] = s["evictions"]
            out["pool_spills"] = s["spills"]
            out["pool_compactions"] = s["compactions"]
            out["pool_settled_skips"] = s["settled_skips"]
        if self._wal is not None:
            out["wal"] = self._wal.stats()
        if self._aot is not None:
            s = self._aot.stats()
            out["aot"] = s
            # Flat copies of the fields the bench line and the
            # regression sentinel watch.
            out["aot_hits"] = s["hits"]
            out["aot_misses"] = s["misses"]
            out["aot_corrupt"] = s["corrupt"]
            out["aot_stale"] = s["stale"]
            out["aot_deserialize_s"] = s["deserialize_s"]
            out["aot_build_s"] = s["build_s"]
        if self._plans_summary is not None:
            out["plans"] = self._plans_summary
            out["plans_installed"] = self._plans_summary["installed"]
        return out


# -- CLI -------------------------------------------------------------------


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="mpi_and_open_mp_tpu.serve.daemon",
        description="Fault-tolerant Life serving daemon: submit a seeded "
        "mixed-shape burst, drain it under the supervision ladder, print "
        "ONE JSON summary line. SIGTERM checkpoints the queue and exits "
        "75 (EX_TEMPFAIL); --resume continues it.")
    p.add_argument("--requests", type=int, default=32, metavar="N",
                   help="burst size (default 32; 0 with --resume drains "
                   "the checkpoint only)")
    p.add_argument("--shapes", default="48x48,64x64", metavar="S",
                   help="comma-separated NYxNX request shapes, cycled "
                   "over the burst (default %(default)s)")
    p.add_argument("--steps", default="4,8", metavar="K",
                   help="comma-separated step counts, cycled (default "
                   "%(default)s)")
    p.add_argument("--workload", default="life", metavar="NAME",
                   help="stencil workload for the burst (a registered "
                   "stencils name: life, heat, gray_scott, wireworld; "
                   "default %(default)s) — boards come from the spec's "
                   "own seeder and dispatch through the spec's engine "
                   "ladder")
    p.add_argument("--max-batch", type=int, default=8)
    p.add_argument("--max-depth", type=int, default=4096)
    p.add_argument("--max-wait", type=float, default=0.02, metavar="S",
                   help="per-bucket deadline seconds (default 0.02)")
    p.add_argument("--timeout", type=float, default=60.0, metavar="S",
                   help="per-request end-to-end budget (default 60)")
    p.add_argument("--retries", type=int, default=2)
    p.add_argument("--max-padding-frac", type=float, default=0.375,
                   metavar="F",
                   help="admission budget for estimated dead-padding "
                   "fraction of the pending set (default %(default)s); "
                   "fleet workers run heterogeneous budgets through "
                   "this knob")
    p.add_argument("--backoff", default="0.05:1.0:0.5", metavar="B[:C[:J]]",
                   help="retry backoff schedule base[:cap[:jitter]] "
                   "seconds (default %(default)s) — the "
                   "capped-exponential ladder a full-ladder dispatch "
                   "failure retries behind")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--checkpoint", default=None, metavar="PATH",
                   help="queue drain checkpoint file (written on "
                   "SIGTERM/preemption)")
    p.add_argument("--wal", default=None, metavar="PATH",
                   help="write-ahead ticket journal: every transition is "
                   "durable BEFORE the daemon acts on it, so --resume "
                   "recovers from kill -9 at any instruction, not just "
                   "a polite SIGTERM drain")
    p.add_argument("--wal-fsync", default="every-record",
                   choices=list(wal_mod.FSYNC_POLICIES),
                   help="journal durability ladder: every-record = zero "
                   "acked loss on any death; every-chunk = at most one "
                   "batch of records on power cut; off = page-cache "
                   "only (still zero loss on process death; default "
                   "%(default)s)")
    p.add_argument("--aot-cache", default=None, metavar="DIR",
                   help="durable AOT executable cache directory (default "
                   "$MOMP_AOT_CACHE): bucket programs persist as "
                   "jax.export artifacts, so a restarted daemon "
                   "deserializes instead of re-tracing — warm resume "
                   "shows zero jit.retrace{fn=life_batch_*} ticks; a "
                   "corrupt/stale artifact quarantines and falls back "
                   "to a fresh trace (aot:corrupt provenance)")
    p.add_argument("--plans", default=None, metavar="DIR",
                   help="durable tuned-plan store directory (default "
                   "$MOMP_TUNE_PLANS; usually the SAME directory as "
                   "--aot-cache — plan and executable share one "
                   "fingerprint digest): momp-plan/1 records are "
                   "validated, parity-gated, and installed before the "
                   "first dispatch so native_path_batch follows the "
                   "measured winner; corrupt/stale/parity-failing "
                   "records quarantine and the heuristics serve "
                   "unchanged; MOMP_TUNE=0 ignores the store entirely")
    p.add_argument("--resume", action="store_true",
                   help="restore drained tickets before serving the "
                   "(possibly empty) new burst — WAL replay first, then "
                   "the drain checkpoint, then fresh (requires --wal "
                   "and/or --checkpoint)")
    p.add_argument("--verify", action="store_true",
                   help="gate every resolved board bit-exact against the "
                   "NumPy oracle before reporting (CI smoke)")
    return p


def _parse_backoff(spec: str) -> tuple[float, float, float]:
    """``base[:cap[:jitter]]`` → the three ServePolicy backoff numbers
    (missing fields keep the policy defaults)."""
    parts = [p for p in str(spec).split(":") if p != ""]
    if not 1 <= len(parts) <= 3:
        raise ValueError(
            f"--backoff wants base[:cap[:jitter]], got {spec!r}")
    base = float(parts[0])
    cap = float(parts[1]) if len(parts) > 1 else 1.0
    jitter = float(parts[2]) if len(parts) > 2 else 0.5
    return base, cap, jitter


def _parse_shapes(spec: str) -> list[tuple[int, int]]:
    shapes = []
    for tok in spec.split(","):
        ny, _, nx = tok.strip().partition("x")
        shapes.append((int(ny), int(nx)))
    return shapes


def _burst(daemon: ServingDaemon, args) -> None:
    from mpi_and_open_mp_tpu import stencils

    spec = stencils.get(args.workload)
    shapes = _parse_shapes(args.shapes)
    steps = [int(s) for s in args.steps.split(",")]
    rng = np.random.default_rng(args.seed)
    for i in range(args.requests):
        ny, nx = shapes[i % len(shapes)]
        daemon.submit(spec.init(rng, (ny, nx)), steps[i % len(steps)],
                      workload=spec.name)


def _verify(daemon: ServingDaemon) -> bool:
    from mpi_and_open_mp_tpu import stencils

    for t in daemon.queue.tickets():
        if t.state != DONE:
            continue
        spec = stencils.get(getattr(t, "workload", "life"))
        ref = stencils.oracle_run(spec, np.asarray(t.board), t.steps)
        if not stencils.parity_ok(spec, t.result, ref):
            return False
    return True


def main(argv=None) -> int:
    from mpi_and_open_mp_tpu.obs import metrics

    args = build_parser().parse_args(argv)
    if args.resume and not (args.checkpoint or args.wal):
        build_parser().error("--resume requires --checkpoint and/or --wal")
    aot_dir = args.aot_cache or os.environ.get("MOMP_AOT_CACHE") or None
    aot = None
    if aot_dir:
        from mpi_and_open_mp_tpu.serve.aotcache import AOTCache

        aot = AOTCache(aot_dir)
        rec_aot_cache = os.path.abspath(aot_dir)
    plans_dir = args.plans or os.environ.get("MOMP_TUNE_PLANS") or None
    plan_store = None
    if plans_dir:
        from mpi_and_open_mp_tpu.tune.plans import PlanStore

        plan_store = PlanStore(plans_dir)
        rec_plans_dir = os.path.abspath(plans_dir)
    try:
        backoff_base, backoff_cap, backoff_jitter = _parse_backoff(
            args.backoff)
    except ValueError as e:
        build_parser().error(str(e))
    policy = ServePolicy(
        max_batch=args.max_batch, max_depth=args.max_depth,
        max_padding_frac=args.max_padding_frac,
        max_wait_s=args.max_wait, request_timeout_s=args.timeout,
        max_retries=args.retries, backoff_base_s=backoff_base,
        backoff_cap_s=backoff_cap, backoff_jitter=backoff_jitter,
        seed=args.seed)
    rec: dict = {"daemon": "serve", "resume": bool(args.resume),
                 "workload": args.workload}
    if aot is not None:
        rec["aot_cache"] = rec_aot_cache
    if plan_store is not None:
        rec["plan_store"] = rec_plans_dir
    try:
        if args.resume:
            daemon, source, detail = ServingDaemon.resume_any(
                wal_path=args.wal, checkpoint_path=args.checkpoint,
                policy=policy, wal_fsync=args.wal_fsync, aot_cache=aot,
                plan_store=plan_store)
            rec["resume_source"] = source
            rec.update(detail)
            rec["resumed_tickets"] = daemon.queue.depth()
        else:
            daemon = ServingDaemon(
                policy, checkpoint_path=args.checkpoint,
                wal_path=args.wal, wal_fsync=args.wal_fsync,
                aot_cache=aot, plan_store=plan_store)
        if aot is not None and args.requests > 0 and args.workload == "life":
            # Preload for the incoming burst too (the resume preload
            # covered only already-pending shapes): every bucket program
            # the burst can need is resident before the first dispatch.
            # Life only — the store holds life bucket executables.
            rec["aot_warm"] = aot.warm(
                [(s, "uint8") for s in _parse_shapes(args.shapes)],
                policy.max_batch)
        _burst(daemon, args)
        t0 = time.perf_counter()
        daemon.serve()
        wall = time.perf_counter() - t0
    except Preempted as e:
        rec.update({"preempted": True, "resume": True,
                    "batches": e.step, "checkpoint": e.checkpoint,
                    **{k: v for k, v in daemon.summary().items()
                       if k != "engines"}})
        print(json.dumps(rec))
        return EXIT_PREEMPTED
    except Exception as e:  # noqa: BLE001 — the line IS the contract
        rec["error"] = f"{type(e).__name__}: {e}"[:300]
        print(json.dumps(rec))
        return 1
    rec.update({"preempted": False, "wall_sec": round(wall, 4),
                **daemon.summary()})
    if daemon._wal is not None:
        daemon._wal.close()
    if rec["resolved"] and wall > 0:
        rec["requests_per_sec"] = round(rec["resolved"] / wall, 2)
    if args.verify:
        rec["verified"] = _verify(daemon)
    if metrics.metrics_on():
        rec["metrics"] = metrics.snapshot()
    print(json.dumps(rec))
    if args.verify and not rec.get("verified"):
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
