"""Subprocess driver for the WAL crash-matrix test.

Runs a real serving daemon with a write-ahead journal and a
``MOMP_CHAOS crash=<site>:<k>`` plan armed by the parent test, acking
every ticket whose ``submit()`` RETURNED to a side file (write + flush +
fsync, so the ack record is durable before the parent can read it). The
chaos site hard-kills the process with ``os._exit(137)`` — no atexit, no
finally — and the parent then replays the journal and asserts the
per-fsync-policy loss bound over exactly the acked set.

Usage: ``python _wal_crash_driver.py WAL_PATH FSYNC_POLICY ACK_PATH N
[pool|settled]``

With the optional ``pool`` mode the driver exercises the resident-
session handle lifecycle instead of the ticket path: create N pool
sessions (ack ``C <sid>`` once create returns), two rounds of 2-step
resident steps per session (ack ``S <sid> 2``), one snapshot (``N
<sid>``), one evict (``E <sid>``). The pool chaos sites
(``post-create``/``post-step``/``post-snapshot``/``post-evict``) fire
AFTER the frame is journaled and BEFORE the pool acts, so an acked op is
always durable under ``every-record`` and the parent can assert the
resumed pool matches the acked ledger exactly (plus at most one
journaled-but-unacked op — the at-least-once edge).

The ``settled`` mode is the pool mode with session p0 seeded as a STILL
LIFE (a block) among active random boards, and enough 2-step rounds for
the settled-skip fast path to engage (p0's dispatches stop once its
fixed point is proven). The WAL's STEP frames stay authoritative:
replay re-applies every journaled step and RE-PROVES settledness, so
the parent asserts the resumed p0 snapshot is bit-identical to the
oracle at the acked step count even though some of those steps were
never dispatched by the pre-kill process.

The MEMBERSHIP modes run a 3-worker in-process ``Fleet`` instead of
one daemon — here WAL_PATH is a *directory* (one journal per worker).
``rejoin`` wedges worker 0, creates claimable sessions (names hashing
to worker 0, one distinct shape each so every one is its own slab
group), then calls ``rejoin_worker(0)`` — the ``post-rejoin`` chaos
site fires between the handshake halves (dest CREATE+STEP journaled,
source EVICT not). ``drain`` parks a whole pending bucket plus
resident sessions on worker 0 and calls ``drain_worker(0)`` — the
``mid-drain`` site fires between the destination adopt and the
source's ``re-homed`` SHED. Both sites are duplication-not-loss edges:
the parent replays every worker journal and asserts each acked
session appears in >=1 journal (bit-equal create board + step total
wherever it appears twice) and the ticket count over all journals is
bounded by ``acked <= total <= acked + one bucket``.

Exits 0 after a clean drain (printing a one-line JSON summary); a
planned crash never reaches that code.
"""

import json
import os
import sys

# This driver is CPU-only host-side work and must never touch the chip.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def _fleet_mode(wal_dir: str, fsync: str, rec, n: int, mode: str) -> int:
    """The membership crash modes: a 3-worker fleet, worker 0 the
    victim. Every ack below is durable BEFORE the fleet call that can
    crash — the parent's loss bound is judged over exactly this set."""
    import time as _time

    from mpi_and_open_mp_tpu.serve import Fleet, ServePolicy
    from mpi_and_open_mp_tpu.serve.router import ConsistentHashRing

    fleet = Fleet(3, ServePolicy(max_batch=4, max_wait_s=0.0),
                  wal_dir=wal_dir, wal_fsync=fsync,
                  heartbeat_interval_s=0.005, heartbeat_miss_k=2,
                  steal=False)
    # The full 3-worker ring (workers 0..2 all present) — session names
    # are picked by where they hash once worker 0 is BACK on the ring.
    ring3 = ConsistentHashRing(range(3))
    rng = np.random.default_rng(11)

    def names_for(worker: int, count: int, prefix: str) -> list[str]:
        out, j = [], 0
        while len(out) < count:
            name = f"{prefix}{j:03d}"
            if ring3.lookup(name) == worker:
                out.append(name)
            j += 1
        return out

    if mode == "rejoin":
        for i in range(n):
            board = (rng.random((12, 12)) < 0.3).astype(np.uint8)
            fleet.create_session(f"p{i}", board)
            rec(f"C p{i}")
            fleet.step_session(f"p{i}", 2)
            rec(f"S p{i} 2")
        fleet.serve_until_drained(drain=True)
        fleet.wedge(0)
        deadline = _time.monotonic() + 10.0
        while 0 not in fleet.router.wedged_workers:
            _time.sleep(0.02)
            fleet.pump()
            if _time.monotonic() > deadline:
                raise RuntimeError("worker 0 never wedged")
        # Sessions the rejoiner will claim back: names hashing to
        # worker 0 (they land on survivors now — 0 is off the ring),
        # each with a DISTINCT shape so each is its own slab group and
        # the whole-group rule moves it alone.
        for k, name in enumerate(names_for(0, 3, "q")):
            shape = (12 + 2 * (k + 1), 12)
            board = (rng.random(shape) < 0.3).astype(np.uint8)
            fleet.create_session(name, board)
            rec(f"C {name}")
            fleet.step_session(name, 2)
            rec(f"S {name} 2")
        fleet.serve_until_drained(drain=True)
        # The handshake: post-rejoin fires between the claim's halves.
        claimed = fleet.rejoin_worker(0)
        fleet.serve_until_drained(drain=True)
        books = fleet.router.books()
        print(json.dumps({"claimed": claimed,
                          "balanced": books["balanced"],
                          "rejoins": books["rejoins"]}))
        return 0

    if mode == "drain":
        # One whole pending bucket parked at worker 0: same shape/steps,
        # session keys hashing to 0. Acked at submit (journaled ADMIT).
        for name in names_for(0, n, "t"):
            board = (rng.random((12, 12)) < 0.3).astype(np.uint8)
            fleet.submit(board, 2, session=name)
            rec(f"T {name}")
        # Resident sessions on worker 0 with journaled-but-undispatched
        # steps — the drain must finish these locally before the pool
        # migrates.
        for k, name in enumerate(names_for(0, 2, "q")):
            shape = (12 + 2 * (k + 1), 12)
            board = (rng.random(shape) < 0.3).astype(np.uint8)
            fleet.create_session(name, board)
            rec(f"C {name}")
            fleet.step_session(name, 2)
            rec(f"S {name} 2")
        # The handoff: mid-drain fires between the destination adopt
        # and the source's re-homed SHED.
        stats = fleet.drain_worker(0)
        fleet.serve_until_drained(drain=True)
        books = fleet.router.books()
        print(json.dumps({"tickets_moved": stats["tickets_moved"],
                          "sessions_moved": stats["sessions_moved"],
                          "balanced": books["balanced"],
                          "drains": books["drains"]}))
        return 0

    raise ValueError(f"unknown fleet mode {mode!r}")


def main() -> int:
    import jax

    jax.config.update("jax_platforms", "cpu")

    from mpi_and_open_mp_tpu.serve import ServePolicy, ServingDaemon

    wal_path, fsync, ack_path = sys.argv[1], sys.argv[2], sys.argv[3]
    n = int(sys.argv[4])
    mode = sys.argv[5] if len(sys.argv) > 5 else ""
    pool_mode = mode in ("pool", "settled")
    if mode in ("rejoin", "drain"):
        with open(ack_path, "ab") as ack:
            def rec(line: str) -> None:
                ack.write((line + "\n").encode())
                ack.flush()
                os.fsync(ack.fileno())

            return _fleet_mode(wal_path, fsync, rec, n, mode)
    policy = ServePolicy(max_batch=4, max_wait_s=0.0)
    daemon = ServingDaemon(policy, wal_path=wal_path, wal_fsync=fsync)
    rng = np.random.default_rng(7)
    with open(ack_path, "ab") as ack:
        def rec(line: str) -> None:
            ack.write((line + "\n").encode())
            ack.flush()
            os.fsync(ack.fileno())

        if pool_mode:
            for i in range(n):
                board = (rng.random((12, 12)) < 0.3).astype(np.uint8)
                if mode == "settled" and i == 0:
                    # p0 is a still life: its dispatches stop once the
                    # pool proves the per-lane fixed point.
                    board = np.zeros((12, 12), np.uint8)
                    board[5:7, 5:7] = 1
                daemon.create_session(f"p{i}", board)
                rec(f"C p{i}")
            # settled mode runs extra rounds: the first round proves
            # p0's fixed point, later rounds exercise the skip path
            # with the chaos site still armed.
            for _ in range(5 if mode == "settled" else 2):
                for i in range(n):
                    daemon.step_session(f"p{i}", 2)
                    rec(f"S p{i} 2")
            daemon.snapshot_session("p0")
            rec("N p0")
            daemon.evict_session(f"p{n - 1}")
            rec(f"E p{n - 1}")
            daemon._wal.sync()
            s = daemon.summary()
            daemon._wal.close()
            print(json.dumps({"sessions": s["pool_sessions"]}))
            return 0

        for i in range(n):
            board = (rng.random((12, 12)) < 0.3).astype(np.uint8)
            t = daemon.submit(board, 2)
            rec(str(t.id))
    daemon.serve()
    s = daemon.summary()
    daemon._wal.close()
    print(json.dumps({"resolved": s["resolved"], "shed": s["shed"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
