"""Serving fleet harnesses: N workers under one FleetRouter.

Two deployments of the same :class:`~mpi_and_open_mp_tpu.serve.router.
FleetRouter` contract:

* :class:`Fleet` — N in-process :class:`ServingDaemon` workers sharing
  one injectable clock. This is what the unit tests drive:
  deterministic, no subprocess spawn tax, wedges simulated by halting a
  worker's pump (its heartbeat stops, the router declares it, the WAL
  replay + re-home ladder runs for real against the worker's real
  journal).
* The module CLI (``python -m mpi_and_open_mp_tpu.serve.fleet``) — the
  cross-process deployment CI's ``fleet-chaos-smoke`` kills for real: a
  parent partitions a seeded burst by consistent hash, writes one spool
  per worker, spawns one subprocess per worker (``--worker-main``; more
  than one only under ``JAX_PLATFORMS=cpu``, since a process on the
  accelerator holds every chip of its host),
  and when a worker dies (rc 137 from the ``kill_worker=<i>:<k>`` chaos
  token — indistinguishable from ``kill -9``) replays the victim's WAL,
  journals the ``re-homed`` sheds back to it, and spawns recovery
  workers for the re-homed entries on the surviving ring. One JSON line
  with the fleet books; the parity gate (``--verify``) covers every
  resolved ticket INCLUDING the re-homed ones.

The reference repo's answer to scale was a PBS multi-node launch
(``qsub -l nodes=N`` + ``mpirun``) whose answer to failure was "requeue
the whole job"; here the unit of failure is one worker, the unit of
recovery is one ticket, and the books must balance fleet-wide either
way (``docs/DESIGN.md`` §13).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import subprocess
import sys
import tempfile
import time

import numpy as np

from mpi_and_open_mp_tpu.obs import metrics as obs_metrics
from mpi_and_open_mp_tpu.obs import telemetry as telemetry_mod
from mpi_and_open_mp_tpu.obs import trace as obs_trace
from mpi_and_open_mp_tpu.serve import policy as policy_mod
from mpi_and_open_mp_tpu.serve import wal as wal_mod
from mpi_and_open_mp_tpu.serve.daemon import ServingDaemon, _parse_shapes
from mpi_and_open_mp_tpu.serve.policy import ServePolicy, percentile
from mpi_and_open_mp_tpu.serve.queue import DONE, SHED, Ticket
from mpi_and_open_mp_tpu.serve.router import (
    DEFAULT_MISS_K, DEFAULT_VNODES, FleetRollup, FleetRouter)
from mpi_and_open_mp_tpu.utils import checkpoint as checkpoint_mod
from mpi_and_open_mp_tpu.utils import runtime

SPOOL_SCHEMA = "momp-fleet-spool/1"


@dataclasses.dataclass
class WorkerHandle:
    """One worker as the router sees it: identity, daemon, journal
    path, and liveness. ``halted`` is the in-process wedge simulation
    (the fleet loop stops pumping it, so its heartbeat goes stale);
    ``wedged`` is the router's verdict and is never cleared.

    The membership flags: ``warming`` marks a worker deserializing its
    AOT cache after a spawn or REJOIN — alive but not yet pumping, so
    the fleet loop stamps its beat in the shared post-round beat (the
    same cover the slow-pump fix gives a compiling worker) until its
    first completed pump clears the flag. ``cordoned`` means the router
    took it off the ring mid-drain; ``drained`` is the graceful-exit
    terminal state (like ``wedged``, never cleared — a returning worker
    REJOINS under a fresh handle)."""

    index: int
    daemon: ServingDaemon
    wal_path: str | None = None
    last_beat: float = 0.0
    wedged: bool = False
    halted: bool = False
    warming: bool = False
    cordoned: bool = False
    drained: bool = False


class Fleet:
    """N in-process workers behind one router, one injectable clock.

    ``policies`` (one per worker) overrides the uniform ``policy`` —
    fleet workers may run heterogeneous budgets (the rollup projection
    and the per-worker doors are exercised either way). With a
    ``wal_dir`` every worker journals to ``<wal_dir>/worker<i>.wal``
    and a wedge re-homes from the journal replay; without one the
    re-home falls back to the live queue snapshot.
    """

    def __init__(self, n_workers: int, policy: ServePolicy | None = None,
                 *, policies: list[ServePolicy] | None = None,
                 wal_dir: str | None = None,
                 wal_fsync: str = "every-record",
                 heartbeat_interval_s: float = 0.02,
                 heartbeat_miss_k: int = DEFAULT_MISS_K,
                 steal: bool = True,
                 elasticity: policy_mod.ElasticityPolicy | None = None,
                 elastic_window_s: float = 1.0,
                 telemetry: bool | None = None,
                 telemetry_interval_s: float | None = None,
                 vnodes: int = DEFAULT_VNODES, seed: int = 0,
                 clock=time.monotonic, sleep=time.sleep):
        if n_workers < 1:
            raise ValueError(f"n_workers must be >= 1, got {n_workers}")
        if policies is not None and len(policies) != n_workers:
            raise ValueError(
                f"got {len(policies)} policies for {n_workers} workers")
        if policies is None:
            policies = [policy or ServePolicy()] * n_workers
        self._clock = clock
        self._sleep = sleep
        self._steal_enabled = steal
        self._wal_dir = wal_dir
        self._wal_fsync = wal_fsync
        self._spawn_policy = policies[-1]
        #: SLO-driven scaling: None = fixed fleet (the default — scaling
        #: is an OPERATOR policy, opted into per deployment). With a
        #: policy, every pump round feeds the hysteresis controller a
        #: rolling-window p99 + fleet depth; ``add`` spawns a warming
        #: worker, ``drain`` gracefully retires the shallowest one.
        self.controller = (policy_mod.ElasticController(elasticity)
                           if elasticity is not None else None)
        self._elastic_window_s = float(elastic_window_s)
        #: The telemetry plane: per-worker snapshot recorders shipped
        #: into the router's FleetRollup on the shared post-round beat
        #: (snapshots piggyback the heartbeat — a worker alive enough to
        #: beat is alive enough to report), plus the multi-window SLO
        #: burn-rate monitor whose window values every scale/drain
        #: decision records. ``MOMP_TELEMETRY=0`` (or telemetry=False)
        #: turns the whole plane off.
        self._telemetry_on = (telemetry_mod.telemetry_on()
                              if telemetry is None else bool(telemetry))
        self._telemetry_interval_s = (
            telemetry_mod.snapshot_interval_s()
            if telemetry_interval_s is None else float(telemetry_interval_s))
        epol = elasticity or policy_mod.ElasticityPolicy()
        self.burn = telemetry_mod.BurnRateMonitor(
            slo_p99_s=epol.slo_p99_s, goodput_frac=epol.slo_goodput_frac,
            short_window_s=self._elastic_window_s / 4,
            long_window_s=self._elastic_window_s,
        ) if self._telemetry_on else None
        #: Recorded elasticity decisions, each carrying the burn-rate
        #: window values that triggered it — the queryable record the
        #: ISSUE's "every decision explainable from recorded data" asks
        #: for (also emitted as ``serve.fleet.scale`` trace events).
        self.decisions: list[dict] = []
        self._wtel: dict[int, telemetry_mod.WorkerTelemetry] = {}
        self._tel_seen: dict[int, set] = {}
        self._tel_counts: dict[int, dict] = {}
        self._door_seen = 0
        self.handles: list[WorkerHandle] = []
        for i in range(n_workers):
            wal_path = (os.path.join(wal_dir, f"worker{i}.wal")
                        if wal_dir else None)
            d = ServingDaemon(policies[i], wal_path=wal_path,
                              wal_fsync=wal_fsync, worker_index=i,
                              clock=clock, sleep=sleep)
            self.handles.append(WorkerHandle(
                index=i, daemon=d, wal_path=wal_path, last_beat=clock()))
        self.router = FleetRouter(
            self.handles, vnodes=vnodes, seed=seed,
            heartbeat_interval_s=heartbeat_interval_s,
            heartbeat_miss_k=heartbeat_miss_k)

    # -- traffic -----------------------------------------------------------

    def submit(self, board, steps: int, session: str | None = None) -> Ticket:
        return self.router.submit(board, steps, self._clock(),
                                  session=session)

    def create_session(self, session: str, board):
        """Admit a resident session into its affinity worker's device
        pool (the ring is the session→pool map)."""
        return self.router.create_session(session, board, self._clock())

    def step_session(self, session: str, steps: int) -> Ticket:
        return self.router.step_session(session, steps, self._clock())

    def snapshot_session(self, session: str):
        return self.router.snapshot_session(session)

    def evict_session(self, session: str):
        return self.router.evict_session(session)

    def wedge(self, index: int) -> None:
        """Simulate a wedged worker: stop pumping it. Its heartbeat
        goes stale and the ROUTER must notice (``check_health``) —
        nothing here shortcuts the detection ladder."""
        for h in self.handles:
            if h.index == index:
                h.halted = True
                return
        raise ValueError(f"no worker with index {index}")

    # -- elastic membership --------------------------------------------------

    def _handle_at(self, index: int) -> WorkerHandle:
        for h in self.handles:
            if h.index == index:
                return h
        raise ValueError(f"no worker with index {index}")

    def rejoin_worker(self, index: int) -> int:
        """Bring a wedged (or drained) worker back: resume a FRESH
        daemon from the victim's own journal — the WAL handshake; a
        completed wedge re-home left it holding only the work the fleet
        never reassigned, so the rejoiner adopts exactly its claimed
        sessions and nothing else — then re-enter the ring under the
        old index (bounded movement: the old points come back, nothing
        else shifts) and claim back the whole slab groups that hash to
        it. The handle rejoins WARMING: the shared post-round beat
        covers it while the AOT cache deserializes, so the wedge
        horizon cannot re-declare it mid-warmup. Returns the number of
        sessions claimed."""
        old = self._handle_at(index)
        if not (old.wedged or old.drained):
            raise ValueError(
                f"worker {index} is live; rejoin re-admits a wedged or "
                "drained worker")
        d, _source, detail = ServingDaemon.resume_any(
            wal_path=old.wal_path, policy=old.daemon.policy,
            wal_fsync=self._wal_fsync, worker_index=index,
            clock=self._clock, sleep=self._sleep)
        fresh = WorkerHandle(index=index, daemon=d,
                             wal_path=old.wal_path,
                             last_beat=self._clock(), warming=True)
        claimed = self.router.rejoin_worker(fresh, self._clock())
        # The old handle leaves the pump loop but stays on the router's
        # retired list: its queue's history keeps counting in the books.
        self.handles[self.handles.index(old)] = fresh
        return claimed

    def drain_worker(self, index: int) -> dict:
        """Gracefully retire a live worker: cordon, migrate whole
        buckets and whole slab groups to the survivors, compact + sync
        its journal as the handoff receipt. Zero acked loss by
        construction — every pending entry adopts at its destination
        before the source sheds it."""
        return self.router.drain_worker(index, self._clock())

    def spawn_worker(self) -> WorkerHandle:
        """Add a brand-new worker under the next free index (the
        elasticity ``add`` verb). It joins WARMING — the post-round beat
        covers its AOT deserialization — and the ring/rollup widen via
        :meth:`FleetRouter.add_worker`."""
        index = max(h.index for h in self.handles) + 1
        wal_path = (os.path.join(self._wal_dir, f"worker{index}.wal")
                    if self._wal_dir else None)
        d = ServingDaemon(self._spawn_policy, wal_path=wal_path,
                          wal_fsync=self._wal_fsync, worker_index=index,
                          clock=self._clock, sleep=self._sleep)
        h = WorkerHandle(index=index, daemon=d, wal_path=wal_path,
                         last_beat=self._clock(), warming=True)
        self.router.add_worker(h)
        self.handles.append(h)
        return h

    def _autoscale(self, now: float) -> None:
        """One elasticity tick: rolling-window p99 + fleet depth into
        the hysteresis controller; act on its verdict. The controller
        owns the flap protection (breach/surplus streaks + cooldown);
        the fleet owns the verbs."""
        window = self._elastic_window_s
        lat = [t.latency_s for t in self.resolved_tickets()
               if t.resolved_at is not None
               and now - t.resolved_at <= window]
        p99 = percentile(lat, 99) if lat else 0.0
        live = self.router.live_workers()
        depth = self.pending()
        verdict = self.controller.observe(
            p99_s=p99, depth=depth, workers=len(live))
        if verdict is not None:
            # Every scale/drain verdict lands as recorded telemetry
            # WITH the burn-rate window values that triggered it — the
            # decision must be explainable from the recorded data alone.
            decision = {
                "action": verdict, "p99_s": round(p99, 6), "depth": depth,
                "workers": len(live), "mono": round(now, 6),
                **(self.burn.windows(now) if self.burn is not None else {}),
            }
            self.decisions.append(decision)
            obs_metrics.inc("serve.fleet.scale_decisions", action=verdict)
            obs_trace.event("serve.fleet.scale", **decision)
        if verdict == policy_mod.SCALE_ADD:
            self.spawn_worker()
        elif verdict == policy_mod.SCALE_DRAIN and len(live) > 1:
            # The shallowest live worker has the least to migrate; never
            # the last one.
            victim = min(
                (w for w in live if not getattr(w, "warming", False)),
                key=lambda w: w.daemon.queue.depth(), default=None)
            if victim is not None and len(live) > 1:
                self.router.drain_worker(victim.index, now)

    # -- the fleet loop ----------------------------------------------------

    def pump(self, *, drain: bool = False) -> int:
        """One fleet round: deliver any bucket parked mid-steal, every
        live worker pumps (its beat), then health check, a steal round,
        and the elasticity tick. Returns batches dispatched."""
        self.router.deliver_in_transit(self._clock())
        n = 0
        pumped = []
        for h in self.handles:
            if h.wedged or h.halted or h.drained:
                continue
            n += h.daemon.pump(self._clock(), drain=drain)
            pumped.append(h)
        # One shared post-round beat: a worker that just pumped is alive
        # by definition, however long the round took (first dispatches
        # compile for whole seconds — per-worker stamps taken mid-round
        # would look stale against the round-end clock and false-wedge
        # healthy workers). The beat also covers WARMING workers — a
        # rejoiner deserializing its AOT cache is alive but has not
        # pumped yet; without the stamp the wedge horizon would re-
        # declare it mid-warmup (the rejoin twin of the slow-pump
        # false wedge). Only never-pumped (halted) workers go stale.
        now = self._clock()
        for h in pumped:
            h.last_beat = now
            h.warming = False  # first completed pump ends the warmup
        for h in self.handles:
            if h.warming and not (h.wedged or h.drained):
                h.last_beat = now
        self.router.check_health(now)
        if self._steal_enabled:
            self.router.steal(self._clock(), defer=True)
        if self._telemetry_on:
            # Snapshot shipping rides the same post-round beat: the
            # telemetry tick runs BEFORE the elasticity tick, so a
            # burn-rate alert is on the record before any decision it
            # triggers (the merged timeline shows cause, then action).
            self._telemetry_tick(now)
        if self.controller is not None:
            self._autoscale(now)
        return n

    # -- telemetry ---------------------------------------------------------

    def _worker_telemetry(self, h: WorkerHandle):
        """The recorder for one handle LIFETIME (a rejoin's fresh handle
        gets a fresh series under the same worker index)."""
        wt = self._wtel.get(id(h))
        if wt is None:
            wt = telemetry_mod.WorkerTelemetry(
                h.index, interval_s=self._telemetry_interval_s)
            self._wtel[id(h)] = wt
            self._tel_seen[id(h)] = set()
            self._tel_counts[id(h)] = {"resolved": 0, "shed": 0}
        return wt

    def _telemetry_tick(self, now: float, *, force: bool = False) -> None:
        """Ship every due worker's snapshot into the router's rollup and
        feed the burn monitor the interval's good/bad counts. Interval-
        gated per worker; ``force`` flushes everyone (the end-of-run
        sample that makes surviving workers lose zero telemetry)."""
        good = bad = 0
        sampled = False
        for h in self.handles:
            if h.wedged or h.drained:
                continue  # frozen books; the last live sample stands
            wt = self._worker_telemetry(h)
            if not (force or wt.due(now)):
                continue
            seen = self._tel_seen[id(h)]
            counts = self._tel_counts[id(h)]
            for t in h.daemon.queue.tickets():
                if t.id in seen:
                    continue
                if t.state == DONE:
                    seen.add(t.id)
                    counts["resolved"] += 1
                    wt.observe_latency(t.latency_s)
                    if self.burn is not None and \
                            self.burn.is_bad(t.latency_s):
                        bad += 1
                    else:
                        good += 1
                elif (t.state == SHED
                      and t.reason != policy_mod.SHED_REHOMED):
                    # A real shed spends error budget; a re-homed ticket
                    # is a move, not an outcome — it resolves (or sheds)
                    # at its final owner and is judged there.
                    seen.add(t.id)
                    counts["shed"] += 1
                    bad += 1
            snap = wt.sample(now, {
                **counts, "depth": h.daemon.queue.depth(),
            }, force=force)
            if snap is not None:
                self.router.telemetry.ingest(snap)
                sampled = True
        if self.burn is None or not sampled:
            return
        door = sum(self.router.door_shed.values())
        bad += door - self._door_seen
        self._door_seen = door
        win = self.burn.observe(now, good, bad)
        if win.pop("alert_edge", False):
            obs_metrics.inc("serve.fleet.burn_alerts")
            obs_trace.event("serve.fleet.burn", mono=round(now, 6), **win)

    def pending(self) -> int:
        return (sum(h.daemon.queue.depth() for h in self.handles)
                + self.router.in_transit_depth())

    def serve_until_drained(self, *, drain: bool = False,
                            timeout_s: float = 120.0) -> None:
        """Pump until every admitted ticket fleet-wide is terminal. A
        halted worker's pending set drains via the wedge ladder: its
        beat goes stale while the loop idles, ``check_health`` declares
        it, and the re-homed tickets finish on the survivors."""
        start = self._clock()
        while self.pending():
            n = self.pump(drain=drain)
            if n == 0:
                self._sleep(max(1e-4, self.router.heartbeat_interval_s))
            if self._clock() - start > timeout_s:
                raise RuntimeError(
                    f"fleet failed to drain within {timeout_s}s "
                    f"({self.pending()} tickets pending)")
        if self._telemetry_on:
            # Final forced flush: every surviving worker's last interval
            # ships, so the rollup loses zero telemetry from survivors
            # (dead workers lose at most their final interval, counted).
            self._telemetry_tick(self._clock(), force=True)
        for h in self.handles:
            if h.daemon._wal is not None and not h.wedged:
                h.daemon._wal.sync()

    # -- accounting --------------------------------------------------------

    def resolved_tickets(self) -> list[Ticket]:
        """Every resolved ticket fleet-wide, INCLUDING the pre-failure
        lifetimes of rejoined workers (retired handles) — the parity
        gate and latency percentiles must cover work resolved before a
        membership change, not just the current roster's."""
        handles = list(self.handles) + list(self.router._retired)
        return [t for h in handles
                for t in h.daemon.queue.tickets() if t.state == DONE]

    def summary(self) -> dict:
        """Fleet books + aggregate latency over every resolved ticket
        (re-homed tickets carry their full cross-worker latency via the
        queued-seconds carry)."""
        books = self.router.books()
        lat = [t.latency_s for t in self.resolved_tickets()]
        books.update({
            "workers": len(self.handles),
            "wedged": list(self.router.wedged_workers),
            "drained": list(self.router.drained_workers),
            "p50_latency_s": round(percentile(lat, 50), 6),
            "p99_latency_s": round(percentile(lat, 99), 6),
        })
        return books


# -- cross-process CLI -----------------------------------------------------


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="mpi_and_open_mp_tpu.serve.fleet",
        description="Sharded serving fleet driver: partition a seeded "
        "burst across N worker subprocesses by consistent-hash session "
        "affinity, survive worker deaths by WAL replay + re-home, print "
        "ONE JSON line with the fleet books. The MOMP_CHAOS "
        "kill_worker=<i>:<k> token hard-kills worker <i> mid-dispatch "
        "(rc 137) — the books must still balance with zero acked loss.")
    p.add_argument("--workers", type=int, default=3, metavar="N")
    p.add_argument("--requests", type=int, default=48, metavar="R")
    p.add_argument("--sessions", type=int, default=12, metavar="S",
                   help="distinct session keys cycled over the burst "
                   "(default %(default)s)")
    p.add_argument("--shapes", default="48x48,64x64", metavar="S")
    p.add_argument("--steps", default="4,8", metavar="K")
    p.add_argument("--max-batch", type=int, default=4)
    p.add_argument("--max-depth", type=int, default=4096)
    p.add_argument("--max-wait", type=float, default=0.02, metavar="S")
    p.add_argument("--timeout", type=float, default=60.0, metavar="S")
    p.add_argument("--max-padding-frac", type=float, default=0.375)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--vnodes", type=int, default=DEFAULT_VNODES)
    p.add_argument("--dir", default=None, metavar="PATH",
                   help="state directory for spools/journals/worker "
                   "logs (default: a fresh temp dir)")
    p.add_argument("--verify", action="store_true",
                   help="each worker gates every resolved board "
                   "bit-exact against the NumPy oracle — including the "
                   "re-homed tickets on recovery workers")
    p.add_argument("--slo-p99", type=float, default=0.25, metavar="S",
                   help="latency SLO threshold the telemetry plane "
                   "classifies resolved tickets against (default "
                   "%(default)s s)")
    # Internal: run as one fleet worker over a spool file.
    p.add_argument("--worker-main", type=int, default=None, metavar="I",
                   help=argparse.SUPPRESS)
    p.add_argument("--spool", default=None, help=argparse.SUPPRESS)
    p.add_argument("--wal", default=None, help=argparse.SUPPRESS)
    p.add_argument("--telemetry-sidecar", default=None,
                   help=argparse.SUPPRESS)
    return p


def _policy(args) -> ServePolicy:
    return ServePolicy(
        max_batch=args.max_batch, max_depth=args.max_depth,
        max_padding_frac=args.max_padding_frac,
        max_wait_s=args.max_wait, request_timeout_s=args.timeout,
        seed=args.seed)


def _worker_main(args) -> int:
    """One fleet worker: drain a spool under the full daemon contract
    (WAL, chaos sites, supervision ladder), print one JSON line."""
    idx = args.worker_main
    try:
        runtime.require_backend()
    except RuntimeError as e:
        print(json.dumps({"worker": idx, "error": str(e)}))
        return 1
    spool = checkpoint_mod.restore_state(args.spool)
    if spool.get("schema") != SPOOL_SCHEMA:
        print(json.dumps({"worker": idx, "error": "bad spool schema"}))
        return 1
    daemon = ServingDaemon(_policy(args), wal_path=args.wal,
                           worker_index=idx)
    rehomed = [e for e in spool["entries"] if e.get("rehomed")]
    fresh = [e for e in spool["entries"] if not e.get("rehomed")]
    daemon.adopt(rehomed)
    for e in fresh:
        daemon.submit(e["board"], e["steps"], session=e.get("session"))

    shipper = None
    if args.telemetry_sidecar and telemetry_mod.telemetry_on():
        # The sidecar stream: a daemon thread frames periodic snapshots
        # into the per-worker file the parent merges post-run. A kill -9
        # stops the writer mid-frame at worst — the CRC framing bounds
        # the loss to this worker's final interval, and the parent
        # COUNTS it (`telemetry.loss`).
        seen: set = set()
        counts = {"resolved": 0, "shed": 0, "good": 0, "bad": 0}

        def _sample():
            new_lat = []
            for t in daemon.queue.tickets():
                if t.id in seen:
                    continue
                if t.state == DONE:
                    seen.add(t.id)
                    counts["resolved"] += 1
                    new_lat.append(t.latency_s)
                    if t.latency_s > args.slo_p99:
                        counts["bad"] += 1
                    else:
                        counts["good"] += 1
                elif t.state == SHED:
                    seen.add(t.id)
                    counts["shed"] += 1
                    if t.reason != policy_mod.SHED_REHOMED:
                        counts["bad"] += 1
            return (dict(counts, depth=daemon.queue.depth()), new_lat)

        shipper = telemetry_mod.SnapshotShipper(
            args.telemetry_sidecar, idx, _sample).start()

    t0 = time.perf_counter()
    try:
        daemon.serve(watch_signals=True)
    except Exception as e:  # noqa: BLE001 — the line IS the contract
        print(json.dumps({"worker": idx,
                          "error": f"{type(e).__name__}: {e}"[:300]}))
        return 1
    finally:
        if shipper is not None:
            shipper.stop()
    rec = {"worker": idx, "wall_sec": round(time.perf_counter() - t0, 4),
           **{k: v for k, v in daemon.summary().items() if k != "engines"}}
    if args.verify:
        from mpi_and_open_mp_tpu.serve.daemon import _verify

        rec["verified"] = _verify(daemon)
    if daemon._wal is not None:
        daemon._wal.close()
    print(json.dumps(rec))
    return 0 if (not args.verify or rec.get("verified")) else 1


def _spawn_worker(args, idx: int, spool_path: str, wal_path: str,
                  out_path: str, *, strip_chaos: bool = False):
    cmd = [sys.executable, "-m", "mpi_and_open_mp_tpu.serve.fleet",
           "--worker-main", str(idx), "--spool", spool_path,
           "--wal", wal_path,
           "--max-batch", str(args.max_batch),
           "--max-depth", str(args.max_depth),
           "--max-wait", str(args.max_wait),
           "--timeout", str(args.timeout),
           "--max-padding-frac", str(args.max_padding_frac),
           "--seed", str(args.seed),
           "--slo-p99", str(args.slo_p99)]
    if args.verify:
        cmd.append("--verify")
    env = dict(os.environ)
    stem = out_path[:-4] if out_path.endswith(".out") else out_path
    if telemetry_mod.telemetry_on():
        cmd += ["--telemetry-sidecar", stem + ".telemetry.bin"]
    if obs_trace.enabled():
        # Per-worker trace sink: every subprocess appends to its OWN
        # JSONL next to its stdout, so the merged Perfetto timeline
        # (analysis/fleet_report.py) gets one track per worker without
        # interleaved writes to the parent's file.
        env["MOMP_TRACE"] = stem + ".trace.jsonl"
    if strip_chaos:
        # Recovery workers run clean by the same convention as the
        # in-process ladder's chaos.suppressed(): the fault that killed
        # the victim must not re-kill the redo.
        env.pop("MOMP_CHAOS", None)
    out = open(out_path, "wb")
    err = open(out_path + ".err", "wb")
    return subprocess.Popen(cmd, stdout=out, stderr=err, env=env)


def _read_worker_line(out_path: str) -> dict | None:
    try:
        with open(out_path, "rb") as fd:
            lines = [ln for ln in fd.read().decode(
                "utf-8", "replace").splitlines() if ln.strip()]
    except OSError:
        return None
    for ln in reversed(lines):
        try:
            return json.loads(ln)
        except json.JSONDecodeError:
            continue
    return None


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    if args.worker_main is not None:
        if not (args.spool and args.wal):
            build_parser().error("--worker-main requires --spool and --wal")
        return _worker_main(args)
    if args.workers > 1 and not runtime.cpu_pinned():
        # A TPU process claims every chip of its host, so a second worker
        # would fail or hang on the first one's chip.
        build_parser().error(
            f"--workers {args.workers}: on an accelerator each worker "
            "process holds every chip of the host, so the fleet runs one "
            "worker; set JAX_PLATFORMS=cpu for a multi-worker CPU fleet, "
            "or use the in-process serve.fleet.Fleet")

    from mpi_and_open_mp_tpu.serve.router import (
        ConsistentHashRing, affinity_key)

    state_dir = args.dir or tempfile.mkdtemp(prefix="momp-fleet-")
    os.makedirs(state_dir, exist_ok=True)
    n = args.workers
    policy = _policy(args)
    roll = policy_mod.rollup([policy] * n)
    ring = ConsistentHashRing(range(n), vnodes=args.vnodes, seed=args.seed)

    # Partition the seeded burst by session affinity, with the driver
    # door applying the rolled-up + per-worker DEPTH budgets (padding
    # projection stays at each worker's own door — the driver holds no
    # queue to estimate against).
    shapes = _parse_shapes(args.shapes)
    step_list = [int(s) for s in args.steps.split(",")]
    rng = np.random.default_rng(args.seed)
    spools: dict[int, list[dict]] = {i: [] for i in range(n)}
    door_shed = 0
    for i in range(args.requests):
        ny, nx = shapes[i % len(shapes)]
        board = (rng.random((ny, nx)) < 0.3).astype(np.uint8)
        session = f"s{i % max(1, args.sessions):04d}"
        w = ring.lookup(affinity_key(session))
        total = sum(len(v) for v in spools.values())
        if total >= roll.max_depth or len(spools[w]) >= policy.max_depth:
            door_shed += 1
            continue
        spools[w].append({"board": board, "steps":
                          step_list[i % len(step_list)],
                          "session": session})

    t_start = time.perf_counter()
    procs = {}
    wal_paths = {}
    for i in range(n):
        spool_path = os.path.join(state_dir, f"worker{i}.spool")
        wal_paths[i] = os.path.join(state_dir, f"worker{i}.wal")
        checkpoint_mod.save_state(spool_path, {
            "schema": SPOOL_SCHEMA, "worker": i, "entries": spools[i]})
        procs[i] = _spawn_worker(
            args, i, spool_path, wal_paths[i],
            os.path.join(state_dir, f"worker{i}.out"))
    rcs = {i: p.wait() for i, p in procs.items()}
    lines = {i: _read_worker_line(os.path.join(state_dir, f"worker{i}.out"))
             for i in range(n)}

    # -- telemetry rollup: merge every worker's sidecar stream ---------
    tel_on = telemetry_mod.telemetry_on()
    rollup = FleetRollup() if tel_on else None
    burn = (telemetry_mod.BurnRateMonitor(slo_p99_s=args.slo_p99)
            if tel_on else None)
    scale_decisions: list[dict] = []

    def _ingest_sidecar(stem: str, worker_key=None) -> list[dict]:
        """Fold one sidecar file into the rollup; returns its snapshots
        (for the burn feed). Truncated tail frames charge loss."""
        rep = telemetry_mod.read_frames(stem + ".telemetry.bin")
        rollup.truncated += rep["truncated"]
        for s in rep["snapshots"]:
            rollup.ingest(s, worker=worker_key)
        return rep["snapshots"]

    def _feed_burn(streams: list[list[dict]]) -> None:
        """Replay the streams' good/bad counter deltas into the parent
        burn monitor on the shared WALL timeline (each worker stamps
        wall alongside mono — the clock-alignment exchange). Deltas
        from ALL streams merge-sort by wall first: the monitor's window
        pruning wants a monotone feed."""
        feed = []
        for snaps in streams:
            pg = pb = 0
            for s in snaps:
                c = s.get("counters") or {}
                g, b = int(c.get("good", 0)), int(c.get("bad", 0))
                feed.append((float(s["wall"]), g - pg, b - pb))
                pg, pb = g, b
        for wall_t, g, b in sorted(feed):
            win = burn.observe(wall_t, g, b)
            if win.pop("alert_edge", False):
                obs_metrics.inc("serve.fleet.burn_alerts")
                obs_trace.event("serve.fleet.burn",
                                wall=round(wall_t, 6), **win)

    if tel_on:
        _feed_burn([_ingest_sidecar(os.path.join(state_dir, f"worker{i}"))
                    for i in range(n)])

    # -- failure domain: replay each dead worker's WAL, re-home --------
    victims = [i for i, rc in rcs.items() if rc != 0]
    t_kill = time.perf_counter()
    rehomed = 0
    recovery_lines: list[dict] = []
    recovery_rcs: list[int] = []
    victim_resolved = victim_shed = 0
    for v in victims:
        rep = wal_mod.replay(wal_paths[v])
        victim_resolved += len(rep.resolved_ids)
        victim_shed += len(rep.shed_ids)
        if not rep.pending:
            continue
        # Journal the re-homed sheds back to the victim so a SECOND
        # replay (another recovery pass, forensics) finds nothing
        # pending — the same idempotence the in-process router keeps.
        w = wal_mod.TicketWAL(wal_paths[v])
        w.shed([e["id"] for e in rep.pending], policy_mod.SHED_REHOMED)
        w.close()
        ring.remove_worker(v)
        by_target: dict[int, list[dict]] = {}
        for e in rep.pending:
            key = affinity_key(e.get("session"), e.get("id"))
            by_target.setdefault(ring.lookup(key), []).append(e)
        rehomed += len(rep.pending)
        if tel_on:
            # The kill lands on the record BEFORE the autoscale verb:
            # the victim's lost pending set spends error budget NOW, the
            # burn event carries the window values, and only then does
            # the scale decision (spawn recovery capacity) follow — the
            # merged timeline shows cause, then action.
            now_wall = time.time()
            win = burn.observe(now_wall, 0, len(rep.pending))
            if win.pop("alert_edge", False):
                obs_metrics.inc("serve.fleet.burn_alerts")
            obs_trace.event("serve.fleet.burn", wall=round(now_wall, 6),
                            worker=v, pending=len(rep.pending), **win)
            decision = {
                "action": "add", "reason": "worker-death", "worker": v,
                "pending": len(rep.pending),
                "wall": round(time.time(), 6),
                **burn.windows(now_wall),
            }
            scale_decisions.append(decision)
            obs_metrics.inc("serve.fleet.scale_decisions", action="add")
            obs_trace.event("serve.fleet.scale", **decision)
        for tgt, group in by_target.items():
            spool_path = os.path.join(state_dir,
                                      f"worker{tgt}.rehome{v}.spool")
            checkpoint_mod.save_state(spool_path, {
                "schema": SPOOL_SCHEMA, "worker": tgt,
                "entries": [{**e, "rehomed": True} for e in group]})
            out = os.path.join(state_dir, f"worker{tgt}.rehome{v}.out")
            proc = _spawn_worker(
                args, tgt, spool_path,
                os.path.join(state_dir, f"worker{tgt}.rehome{v}.wal"),
                out, strip_chaos=True)
            recovery_rcs.append(proc.wait())
            recovery_lines.append(_read_worker_line(out) or {})
            if tel_on:
                # The recovery worker re-uses index `tgt` but is a new
                # lifetime: its stream rolls up under its own key.
                _feed_burn([_ingest_sidecar(
                    os.path.join(state_dir, f"worker{tgt}.rehome{v}"),
                    worker_key=f"{tgt}.rehome{v}")])
    recovery_s = time.perf_counter() - t_kill if victims else 0.0
    wall = time.perf_counter() - t_start

    # -- fleet books -------------------------------------------------------
    survivor_lines = [lines[i] or {} for i in range(n) if i not in victims]
    resolved = (sum(ln.get("resolved", 0) for ln in survivor_lines)
                + victim_resolved
                + sum(ln.get("resolved", 0) for ln in recovery_lines))
    shed = (sum(ln.get("shed", 0) for ln in survivor_lines)
            + victim_shed
            + sum(ln.get("shed", 0) for ln in recovery_lines))
    rehomed_resolved = sum(ln.get("resolved", 0) for ln in recovery_lines)
    acked = args.requests - door_shed
    acked_loss = acked - resolved - shed
    verified = None
    if args.verify:
        verified = all(ln.get("verified", False)
                       for ln in survivor_lines + recovery_lines)
    rec = {
        "fleet": n, "requests": args.requests, "sessions": args.sessions,
        "door_shed": door_shed,
        "worker_rcs": [rcs[i] for i in range(n)],
        "victims": victims,
        "recovery_rcs": recovery_rcs,
        "rehomed": rehomed,
        "rehomed_resolved": rehomed_resolved,
        "resolved": resolved, "shed": shed,
        "acked_loss": acked_loss,
        "books_balance": acked_loss == 0,
        "fleet_requests_per_sec": (round(resolved / wall, 2)
                                   if wall > 0 and resolved else 0.0),
        "fleet_p99_latency_s": round(max(
            [ln.get("p99_latency_s", 0.0)
             for ln in survivor_lines + recovery_lines] or [0.0]), 6),
        "fleet_kill_recovery_s": round(recovery_s, 4),
        "wall_sec": round(wall, 4),
        "state_dir": state_dir,
    }
    if verified is not None:
        rec["verified"] = verified
        rec["rehomed_parity"] = all(
            ln.get("verified", False) for ln in recovery_lines)
    if tel_on:
        rec["telemetry"] = {
            **rollup.summary(),
            **burn.summary(),
            "clock_offsets": rollup.clock_offsets(),
            "decisions": scale_decisions,
        }
    print(json.dumps(rec))
    ok = (rec["books_balance"]
          and all(rc == 0 for rc in recovery_rcs)
          and all(rcs[i] in (0, 137) for i in range(n))
          and (verified is None or verified))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
