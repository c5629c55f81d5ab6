"""Sharded serving fleet: consistent-hash router, re-home ladder, books.

The fleet contracts under test: the ring is deterministic ACROSS
processes (sha256, never Python's salted ``hash``) and movement under
resize is structurally bounded — removing a worker re-homes only its own
sessions, adding one claims only the keys landing on its points; a
wedged worker (missed heartbeats) is declared by the router, its WAL
replayed, and every pending ticket re-homed to survivors with the fleet
books balanced and every re-homed result oracle-exact; a hot shard sheds
at its own door while cold shards keep admitting, and the fleet-wide
rolled-up door refuses what no combination of workers could absorb; work
stealing moves whole buckets only; and the ``kill_worker=<i>:<k>`` chaos
token arms in exactly one worker's process at exactly one dispatch.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from conftest import oracle_n
from mpi_and_open_mp_tpu.robust import chaos
from mpi_and_open_mp_tpu.serve import (
    ConsistentHashRing,
    Fleet,
    ServePolicy,
    TicketWAL,
)
from mpi_and_open_mp_tpu.serve import policy as policy_mod
from mpi_and_open_mp_tpu.serve import wal as wal_mod
from mpi_and_open_mp_tpu.serve.daemon import _parse_backoff
from mpi_and_open_mp_tpu.serve.queue import DONE, PENDING, SHED
from mpi_and_open_mp_tpu.serve.router import affinity_key

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_chaos():
    chaos.reset()
    yield
    chaos.reset()


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t

    def sleep(self, s: float) -> None:
        self.t += s


def _fleet(n, policy, clk=None, **kw) -> tuple[Fleet, FakeClock]:
    clk = clk or FakeClock()
    return Fleet(n, policy, clock=clk, sleep=clk.sleep, **kw), clk


def _session_for(fleet: Fleet, worker: int) -> str:
    """A session key whose affinity worker is ``worker``."""
    for i in range(10_000):
        s = f"probe-{i}"
        if fleet.router.target_for(s) == worker:
            return s
    raise AssertionError(f"no session found for worker {worker}")


# ------------------------------------------------------------------- ring


def test_ring_cross_process_determinism():
    """The same (workers, vnodes, seed) ring shards identically in a
    fresh interpreter with a DIFFERENT hash salt — the property the
    fleet CLI leans on when parent and workers each rebuild the ring."""
    keys = [f"s{i:03d}" for i in range(32)]
    ring = ConsistentHashRing(range(5), vnodes=32, seed=9)
    local = [ring.lookup(k) for k in keys]
    code = (
        "import json\n"
        "from mpi_and_open_mp_tpu.serve.router import ConsistentHashRing\n"
        "r = ConsistentHashRing(range(5), vnodes=32, seed=9)\n"
        "print(json.dumps([r.lookup(f's{i:03d}') for i in range(32)]))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONHASHSEED="271828")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         capture_output=True, text=True, env=env)
    assert out.returncode == 0, out.stderr[-800:]
    assert json.loads(out.stdout.strip().splitlines()[-1]) == local


def test_ring_removal_moves_only_the_victims_keys():
    ring = ConsistentHashRing(range(4), vnodes=64, seed=7)
    keys = [f"sess-{i}" for i in range(500)]
    before = {k: ring.lookup(k) for k in keys}
    ring.remove_worker(2)
    for k in keys:
        after = ring.lookup(k)
        if before[k] != 2:
            assert after == before[k]  # untouched — structural bound
        else:
            assert after != 2


def test_ring_addition_claims_only_its_own_points():
    ring = ConsistentHashRing(range(3), vnodes=64, seed=1)
    keys = [f"sess-{i}" for i in range(1000)]
    before = {k: ring.lookup(k) for k in keys}
    ring.add_worker(3)
    moved = [k for k in keys if ring.lookup(k) != before[k]]
    assert all(ring.lookup(k) == 3 for k in moved)
    # Expected movement is keys/(N+1) = 25%; 64 vnodes keep the
    # realized share close (generous statistical bound, seed-pinned).
    assert 0 < len(moved) / len(keys) < 0.45


def test_ring_empty_lookup_raises_and_affinity_key_fallback():
    ring = ConsistentHashRing((), vnodes=8)
    with pytest.raises(RuntimeError, match="no live workers"):
        ring.lookup("s")
    assert affinity_key("sess-a", 7) == "sess-a"
    assert affinity_key(None, 7) == "ticket/7"
    assert affinity_key(None) == "ticket/0"


# ----------------------------------------------------------------- rollup


def test_rollup_depth_adds_per_request_knobs_take_min():
    a = ServePolicy(max_batch=4, max_depth=10, max_padding_frac=0.5,
                    max_wait_s=0.1, request_timeout_s=5.0, max_retries=3,
                    backoff_base_s=0.1, backoff_cap_s=2.0)
    b = ServePolicy(max_batch=8, max_depth=6, max_padding_frac=0.25,
                    max_wait_s=0.2, request_timeout_s=9.0, max_retries=1,
                    backoff_base_s=0.05, backoff_cap_s=4.0)
    r = policy_mod.rollup([a, b])
    assert r.max_depth == 16  # capacity ADDS across the fleet
    assert r.max_batch == 8
    assert r.max_padding_frac == 0.25  # most conservative worker wins
    assert r.max_wait_s == 0.1
    assert r.request_timeout_s == 5.0
    assert r.max_retries == 1
    assert r.backoff_base_s == 0.05 and r.backoff_cap_s == 2.0


# ----------------------------------------------------- wedge + re-home


def test_fleet_wedge_rehomes_from_wal_books_balance(tmp_path, make_board):
    """Kill drill against the journal: halt the busiest worker, let the
    heartbeat ladder declare it, and require zero acked loss — every
    ticket resolves (oracle-exact) or sheds explicitly, with the
    re-homed sheds pairing 1:1 against adoptions."""
    pol = ServePolicy(max_batch=4, max_wait_s=0.05)
    f, clk = _fleet(3, pol, wal_dir=str(tmp_path), steal=False,
                    heartbeat_interval_s=0.02)
    for i in range(18):
        f.submit(make_board(16, 16), (i % 3) + 1, session=f"s{i % 6}")
    victim = max(f.handles, key=lambda h: h.daemon.queue.depth()).index
    depth_before = f.handles[victim].daemon.queue.depth()
    assert depth_before > 0
    f.wedge(victim)
    f.serve_until_drained()
    s = f.summary()
    assert s["balanced"] and s["pending"] == 0
    assert s["wedged"] == [victim]
    assert s["rehomed"] == depth_before == s["rehomed_resolved"]
    assert s["resolved"] == 18 and s["shed"] == 0
    # The victim's journal is idempotent: a second replay finds nothing
    # pending (the re-homed sheds were framed before adoption).
    rep = wal_mod.replay(str(tmp_path / f"worker{victim}.wal"))
    assert rep.pending == []
    # Parity over every resolved ticket, re-homed included.
    for t in f.resolved_tickets():
        np.testing.assert_array_equal(
            t.result, oracle_n(t.board, t.steps),
            err_msg=f"ticket {t.id} lost parity across the re-home")


def test_fleet_wedge_without_journal_rehomes_from_live_queue(make_board):
    pol = ServePolicy(max_batch=4, max_wait_s=0.05)
    f, _ = _fleet(3, pol, steal=False, heartbeat_interval_s=0.02)
    for i in range(12):
        f.submit(make_board(16, 16), 2, session=f"s{i % 4}")
    victim = max(f.handles, key=lambda h: h.daemon.queue.depth()).index
    f.wedge(victim)
    f.serve_until_drained()
    s = f.summary()
    assert s["balanced"] and s["resolved"] == 12 and s["pending"] == 0


def test_slow_pump_round_does_not_false_wedge(make_board):
    """Regression: one worker's dispatch taking far longer than the
    heartbeat horizon (a first-dispatch compile) must not wedge the
    workers that pumped earlier in the same round — liveness is judged
    on the shared post-round beat, not mid-round stamps."""
    pol = ServePolicy(max_batch=4, max_wait_s=0.0)
    f, clk = _fleet(3, pol, steal=False, heartbeat_interval_s=0.02)
    slow = f.handles[1].daemon
    orig = slow.pump

    def glacial_pump(now=None, **kw):
        clk.sleep(5.0)  # ~80x the wedge horizon
        return orig(clk(), **kw)

    slow.pump = glacial_pump
    for i in range(6):
        f.submit(make_board(16, 16), 2, session=f"s{i}")
    f.pump()
    assert not any(h.wedged for h in f.handles)
    # ...while a genuinely dead worker is still declared.
    f.wedge(0)
    for _ in range(6):
        f.pump()
        clk.sleep(0.02)
    assert f.handles[0].wedged and not f.handles[2].wedged


# ------------------------------------------------- admission + stealing


def test_hot_shard_sheds_while_cold_shard_admits(make_board):
    pol = ServePolicy(max_batch=4, max_depth=2, max_wait_s=100.0)
    f, _ = _fleet(2, pol, steal=False)
    hot = _session_for(f, 0)
    cold = _session_for(f, 1)
    b = make_board(16, 16)
    assert f.submit(b, 2, session=hot).state == PENDING
    assert f.submit(b, 2, session=hot).state == PENDING
    t = f.submit(b, 2, session=hot)  # worker 0 at its local depth cap
    assert t.state == SHED and t.reason == policy_mod.SHED_DEPTH
    assert t.id >= 0  # the WORKER door shed it, not the router door
    assert f.submit(b, 2, session=cold).state == PENDING  # cold admits
    assert f.submit(b, 2, session=cold).state == PENDING
    # Fleet-wide rolled-up depth (2+2) is now exhausted: the ROUTER
    # door refuses before any worker sees the request.
    t = f.submit(b, 2, session=cold)
    assert t.state == SHED and t.id < 0
    assert f.router.door_shed.get(policy_mod.SHED_DEPTH) == 1
    assert f.router.books()["balanced"]


def test_steal_moves_oldest_whole_bucket_to_idle_worker(make_board):
    pol = ServePolicy(max_batch=4, max_wait_s=100.0)
    f, clk = _fleet(2, pol, steal=False)
    donor_sess = _session_for(f, 0)
    for _ in range(3):
        f.submit(make_board(16, 16), 2, session=donor_sess)
    for _ in range(2):
        f.submit(make_board(24, 24), 2, session=donor_sess)
    assert [h.daemon.queue.depth() for h in f.handles] == [5, 0]
    moved = f.router.steal(clk())
    # The (16,16) bucket holds the oldest lead ticket — it moves WHOLE;
    # the donor keeps the other bucket.
    assert moved == 3
    assert [h.daemon.queue.depth() for h in f.handles] == [2, 3]
    assert f.router.steals == 1
    assert f.router.steal(clk()) == 0  # nobody idle now
    f.serve_until_drained(drain=True)
    s = f.summary()
    assert s["balanced"] and s["resolved"] == 5


def test_steal_never_splits_or_empties_a_single_bucket(make_board):
    pol = ServePolicy(max_batch=4, max_wait_s=100.0)
    f, clk = _fleet(2, pol, steal=False)
    donor_sess = _session_for(f, 0)
    for _ in range(3):
        f.submit(make_board(16, 16), 2, session=donor_sess)
    # One bucket only: stealing it would just move the wait.
    assert f.router.steal(clk()) == 0
    assert [h.daemon.queue.depth() for h in f.handles] == [3, 0]


# -------------------------------------------------------------- chaos


def test_kill_worker_token_parse_and_validation():
    plan = chaos.FaultPlan.parse("kill_worker=2:3")
    assert plan.kill_worker_idx == 2 and plan.kill_worker_at == 3
    assert chaos.FaultPlan.parse("kill_worker=1").kill_worker_at == 1
    with pytest.raises(ValueError):
        chaos.FaultPlan.parse("kill_worker=-1:2")
    with pytest.raises(ValueError):
        chaos.FaultPlan.parse("kill_worker=0:0")


def test_kill_worker_arms_only_matching_index_at_kth_hit(monkeypatch):
    monkeypatch.setenv("MOMP_CHAOS", "kill_worker=1:2")
    chaos.reset()
    assert not chaos.kill_worker_armed(0)  # wrong worker — never counts
    assert not chaos.kill_worker_armed(None)  # not a fleet worker
    assert not chaos.kill_worker_armed(1)  # dispatch 1 of 2
    assert chaos.kill_worker_armed(1)  # dispatch 2 — fire
    assert not chaos.kill_worker_armed(1)  # one-shot


# ------------------------------------------------------- WAL + CLI knobs


def test_wal_admit_carries_session_through_replay(tmp_path, make_board):
    path = str(tmp_path / "w.wal")
    w = TicketWAL(path)
    b = make_board(8, 8)
    w.admit(0, b, 3, session="sess-a")
    w.admit(1, b, 2)
    w.close()
    rep = wal_mod.replay(path)
    assert [e["session"] for e in rep.pending] == ["sess-a", None]
    # ...and survives a compaction (the snapshot must not forget it).
    w = TicketWAL(path)
    w.compact(rep.pending)
    w.close()
    rep2 = wal_mod.replay(path)
    assert [e["session"] for e in rep2.pending] == ["sess-a", None]


def test_parse_backoff_spec():
    assert _parse_backoff("0.1") == (0.1, 1.0, 0.5)
    assert _parse_backoff("0.1:2.0") == (0.1, 2.0, 0.5)
    assert _parse_backoff("0.1:2.0:0.0") == (0.1, 2.0, 0.0)
    with pytest.raises(ValueError):
        _parse_backoff("1:2:3:4")


def test_daemon_cli_exposes_padding_and_backoff_knobs():
    from mpi_and_open_mp_tpu.serve.daemon import build_parser

    args = build_parser().parse_args(
        ["--requests", "0", "--max-padding-frac", "0.2",
         "--backoff", "0.01:0.5:0.0"])
    assert args.max_padding_frac == 0.2
    assert _parse_backoff(args.backoff) == (0.01, 0.5, 0.0)


# ----------------------------------------------------------- guardrails


def test_fleet_and_router_validation(make_board):
    with pytest.raises(ValueError, match="n_workers"):
        Fleet(0)
    with pytest.raises(ValueError, match="policies"):
        Fleet(2, policies=[ServePolicy()])
    f, clk = _fleet(2, ServePolicy(max_batch=4, max_wait_s=100.0))
    f.wedge(0)
    # check_health never wedges the LAST live worker — re-homing needs
    # a survivor, and a one-worker fleet degraded is better than none.
    clk.sleep(10.0)
    assert f.router.check_health(clk()) == [0]
    clk.sleep(10.0)
    assert f.router.check_health(clk()) == []
    assert not f.handles[1].wedged


def test_sentinel_polarity_for_fleet_fields():
    sys.path.insert(0, os.path.join(REPO, "analysis"))
    import regression_sentinel as rs

    for field in ("fleet_requests_per_sec", "fleet_p99_latency_s",
                  "fleet_kill_recovery_s"):
        assert field in rs.WATCH_FIELDS
    assert rs.direction_for("fleet_requests_per_sec") == "higher"
    assert rs.direction_for("fleet_p99_latency_s") == "lower"
    assert rs.direction_for("fleet_kill_recovery_s") == "lower"


# ------------------------------------------------- live join + pool re-home


def test_add_worker_rerolls_admission_live(make_board):
    """Regression (the satellite's target): joining a worker mid-burst
    must widen the router door's rolled-up depth budget IMMEDIATELY —
    before the fix, the rollup was computed once at construction, so a
    grown fleet kept shedding at yesterday's capacity."""
    from mpi_and_open_mp_tpu.serve import ServingDaemon, WorkerHandle

    pol = ServePolicy(max_batch=4, max_depth=2, max_wait_s=100.0)
    f, clk = _fleet(2, pol, steal=False)
    b = make_board(16, 16)
    # Fill the 2-worker rolled depth (2+2) exactly.
    admitted = 0
    i = 0
    while admitted < 4:
        t = f.submit(b, 2, session=f"fill-{i}")
        admitted += t.state == PENDING
        i += 1
    t = f.submit(b, 2, session="overflow")
    assert t.state == SHED and t.id < 0  # the ROUTER door, pre-worker
    door_shed_before = f.router.door_shed.get(policy_mod.SHED_DEPTH)

    d = ServingDaemon(pol, worker_index=2, clock=clk, sleep=clk.sleep)
    h = WorkerHandle(index=2, daemon=d, last_beat=clk())
    f.router.add_worker(h)
    f.handles.append(h)
    # The door's budget is now 6: capacity that joined admits at once.
    sess = _session_for(f, 2)  # lands on the new worker: no local cap
    assert f.submit(b, 2, session=sess).state == PENDING
    assert f.router.door_shed.get(policy_mod.SHED_DEPTH) == door_shed_before
    with pytest.raises(ValueError, match="already in the fleet"):
        f.router.add_worker(h)
    f.serve_until_drained()
    assert f.summary()["balanced"]


def test_fleet_wedge_rehomes_pool_sessions(tmp_path, make_board):
    """A wedged worker's RESIDENT sessions survive it: the router
    replays the victim's journal, adopts each session at its new ring
    home (one board crosses the wire; the destination device replays
    the advance), closes the victim's books with EVICT frames, and
    every re-homed snapshot stays bit-identical to the oracle."""
    pol = ServePolicy(max_batch=4, max_wait_s=0.0)
    f, clk = _fleet(3, pol, wal_dir=str(tmp_path), steal=False,
                    heartbeat_interval_s=0.02)
    boards = {f"sess-{i}": make_board(16, 16) for i in range(12)}
    for sid, b in boards.items():
        f.create_session(sid, b)
    tickets = [f.step_session(sid, 2) for sid in boards]
    f.serve_until_drained()
    assert all(t.state == DONE for t in tickets)

    victim = f.router.target_for("sess-0")
    moved = [sid for sid in boards if f.router.target_for(sid) == victim]
    f.wedge(victim)
    for _ in range(6):
        f.pump()
        clk.sleep(0.02)
    assert f.handles[victim].wedged
    assert f.router.pool_rehomed == len(moved)
    for sid, b in boards.items():
        assert f.router.target_for(sid) != victim
        np.testing.assert_array_equal(
            f.snapshot_session(sid), oracle_n(b, 2),
            err_msg=f"session {sid} lost parity across the re-home")
    # The victim's journal closed its books: a second replay finds no
    # resident sessions (EVICT framed per adoption), so a recovery
    # worker can never double-adopt.
    rep = wal_mod.replay(str(tmp_path / f"worker{victim}.wal"))
    assert rep.pool_sessions == {}
    # Life goes on at the new homes.
    t = f.step_session("sess-0", 3)
    f.serve_until_drained()
    assert t.state == DONE
    np.testing.assert_array_equal(
        f.snapshot_session("sess-0"), oracle_n(boards["sess-0"], 5))


# ----------------------------------------------- REJOIN + drain + elasticity


def _claimable_sessions(fleet, worker, count, make_board):
    """Session names whose FULL-ring affinity is ``worker``, each a
    DISTINCT shape (its own slab group — the whole-group rule moves it
    alone at rejoin time)."""
    from mpi_and_open_mp_tpu.serve.router import ConsistentHashRing

    full = ConsistentHashRing(sorted({h.index for h in fleet.handles}))
    out, i = {}, 0
    while len(out) < count:
        name = f"claim-{i}"
        i += 1
        if full.lookup(name) == worker:
            # Never 16x16: the claimable sessions must not join the
            # survivors' existing 16x16 slab group (whose lead is the
            # survivor's own session and would pin the whole group).
            shape = 18 + 2 * len(out)
            out[name] = make_board(shape, 16)
    return out


def test_rejoin_reenters_ring_and_claims_bit_exact(tmp_path, make_board):
    """The full REJOIN ladder: wedge → recover → rejoin under the old
    index. Bounded re-entry (victim-affine keys route to it again),
    bit-exact claims (whole slab groups whose lead hashes to the
    rejoiner migrate back, snapshots oracle-identical), warming handle,
    and books that balance across BOTH membership changes."""
    pol = ServePolicy(max_batch=4, max_wait_s=0.0)
    f, clk = _fleet(3, pol, wal_dir=str(tmp_path), steal=False,
                    heartbeat_interval_s=0.02)
    boards = {f"sess-{i}": make_board(16, 16) for i in range(9)}
    for sid, b in boards.items():
        f.create_session(sid, b)
    for sid in boards:
        f.step_session(sid, 2)
    f.serve_until_drained()

    victim = f.router.target_for("sess-0")
    f.wedge(victim)
    for _ in range(6):
        f.pump()
        clk.sleep(0.02)
    assert f.handles[victim].wedged

    # Sessions created while the victim is out, whose affinity on the
    # FULL ring is the victim: the rejoin claim pass must move exactly
    # these back (each its own slab group via a distinct shape).
    claim = _claimable_sessions(f, victim, 3, make_board)
    for sid, b in claim.items():
        f.create_session(sid, b)
        f.step_session(sid, 2)
    f.serve_until_drained()

    with pytest.raises(ValueError, match="is live"):
        f.rejoin_worker((victim + 1) % 3)
    claimed = f.rejoin_worker(victim)
    fresh = next(h for h in f.handles if h.index == victim)
    assert fresh.warming and not fresh.wedged
    assert claimed >= len(claim)
    assert f.router.rejoins == 1
    # Bounded re-entry: the old ring points are back, so victim-affine
    # keys route to the rejoiner again.
    assert f.router.target_for("sess-0") == victim
    # Claims are bit-exact at the rejoiner.
    for sid, b in claim.items():
        assert f.router._home_worker(sid).index == victim
        np.testing.assert_array_equal(
            f.snapshot_session(sid), oracle_n(b, 2),
            err_msg=f"claimed session {sid} lost parity across rejoin")
    # The fleet serves through the rejoiner again, books balanced over
    # the retired lifetime + the new one.
    t = f.step_session("sess-0", 3)
    f.serve_until_drained()
    assert t.state == DONE
    s = f.summary()
    assert s["balanced"] and s["rejoins"] == 1
    assert fresh.warming is False  # first completed pump cleared it


def test_rejoin_warming_worker_not_false_wedged(tmp_path, make_board):
    """The satellite fix: a rejoined worker still deserializing its AOT
    cache (alive, not yet pumping) must be covered by the shared
    post-round beat — before the fix its stale stamp would re-wedge it
    mid-warmup after one horizon."""
    pol = ServePolicy(max_batch=4, max_wait_s=0.0)
    f, clk = _fleet(3, pol, wal_dir=str(tmp_path), steal=False,
                    heartbeat_interval_s=0.02)
    for i in range(6):
        f.submit(make_board(16, 16), 2, session=f"s{i}")
    victim = 0
    f.wedge(victim)
    f.serve_until_drained()
    assert f.handles[victim].wedged

    f.rejoin_worker(victim)
    fresh = next(h for h in f.handles if h.index == victim)
    assert fresh.warming
    # Simulate a long warmup: the rejoiner cannot pump yet, and many
    # wedge horizons pass under live traffic.
    fresh.halted = True
    for i in range(8):
        f.submit(make_board(16, 16), 2, session=f"w{i}")
        f.pump()
        clk.sleep(0.05)  # 2.5 horizons per round
    assert not fresh.wedged, "warming worker was false-wedged"
    # Warmup ends: it pumps, clears the flag, and serves.
    fresh.halted = False
    f.serve_until_drained()
    assert not fresh.warming and not fresh.wedged
    assert f.summary()["balanced"]
    # A worker that is NOT warming still wedges on the same staleness —
    # the cover is for warmup, not amnesty.
    f.wedge(2)
    for _ in range(6):
        f.pump()
        clk.sleep(0.05)
    assert f.handles[2].wedged


def test_steal_in_transit_counted_once_at_door(make_board):
    """The satellite fix: a stolen bucket between release and adopt
    belongs to the FLEET (the in-transit ledger) and to neither queue —
    the door must count it exactly once and the books must balance
    mid-move."""
    pol = ServePolicy(max_batch=4, max_depth=3, max_wait_s=100.0)
    f, clk = _fleet(2, pol, steal=False)
    donor = _session_for(f, 0)
    b16, b24 = make_board(16, 16), make_board(24, 24)
    for _ in range(2):
        f.submit(b16, 2, session=donor)
    f.submit(b24, 2, session=donor)

    moved = f.router.steal(clk(), defer=True)
    assert moved == 2  # the (16,16) bucket parked, not yet adopted
    assert f.router.in_transit_depth() == 2
    assert [h.daemon.queue.depth() for h in f.handles] == [1, 0]
    assert f.pending() == 3  # parked work is still pending work
    books = f.router.books()
    assert books["in_transit"] == 2 and books["balanced"], books

    # The door counts the parked bucket: fleet-wide depth is 3 of a
    # rolled 6, so exactly 3 more admissions fit.
    cold = _session_for(f, 1)
    for _ in range(3):
        assert f.submit(b16, 2, session=cold).state == PENDING
    # The 7th submit targets the DONOR (local depth 1 of 3 — its own
    # door would admit): only the fleet door counting the 2 parked
    # tickets sees depth 6 of the rolled 6 and sheds.
    t = f.submit(b16, 2, session=donor)
    assert t.state == SHED and t.id < 0, (
        "door forgot the in-transit bucket")

    delivered = f.router.deliver_in_transit(clk())
    assert delivered == 2 and f.router.in_transit_depth() == 0
    assert f.router.steals == 1
    f.serve_until_drained(drain=True)
    s = f.summary()
    assert s["balanced"] and s["resolved"] == 6 and s["in_transit"] == 0


def test_steal_in_transit_reroutes_if_thief_dies(make_board):
    """A bucket parked for a thief that wedges mid-transfer re-routes
    by ring affinity instead of evaporating with its recipient."""
    pol = ServePolicy(max_batch=4, max_wait_s=100.0)
    f, clk = _fleet(3, pol, steal=False, heartbeat_interval_s=0.02)
    donor = _session_for(f, 0)
    for _ in range(2):
        f.submit(make_board(16, 16), 2, session=donor)
    f.submit(make_board(24, 24), 2, session=donor)
    moved = f.router.steal(clk(), defer=True)
    assert moved == 2
    thief = f.router._in_transit[0]["thief"]
    f.router.declare_wedged(thief, clk())
    assert f.handles[thief].wedged
    assert f.router.deliver_in_transit(clk()) == 2
    f.serve_until_drained(drain=True)
    s = f.summary()
    assert s["balanced"] and s["resolved"] == 3 and s["pending"] == 0


def test_drain_worker_moves_whole_buckets_zero_loss(tmp_path, make_board):
    """Graceful drain: cordoned at the door, board buckets migrate
    WHOLE (one destination per bucket), resident-step tickets finish
    locally, slab groups move unsplit, and the compacted journal is the
    handoff receipt — a replay finds nothing live. Zero acked loss,
    oracle parity end to end."""
    pol = ServePolicy(max_batch=4, max_wait_s=100.0)
    f, clk = _fleet(3, pol, wal_dir=str(tmp_path), steal=False)
    victim = 0
    vsess = _session_for(f, victim)
    boards = [make_board(16, 16) for _ in range(3)]
    tickets = [f.submit(b, 2, session=vsess) for b in boards]
    assert all(t.state == PENDING for t in tickets)
    assert f.handles[victim].daemon.queue.depth() == 3
    # A resident session on the victim with a journaled, undispatched
    # step the drain must flush locally before the pool moves.
    sb = make_board(16, 16)
    f.create_session(vsess, sb)
    st = f.step_session(vsess, 2)

    stats = f.drain_worker(victim)
    assert f.handles[victim].drained and f.handles[victim].cordoned
    assert stats["tickets_moved"] == 3 and stats["sessions_moved"] == 1
    assert st.state == DONE  # finished locally, never migrated
    # Whole-bucket rule: all three tickets landed at ONE survivor.
    depths = [h.daemon.queue.depth() for h in f.handles
              if h.index != victim]
    assert sorted(depths) == [0, 3]
    # Cordoned at the router door: nothing routes to it anymore.
    assert all(f.router.target_for(f"probe-{i}") != victim
               for i in range(50))
    # The handoff receipt: the drained journal replays to empty.
    rep = wal_mod.replay(str(tmp_path / f"worker{victim}.wal"))
    assert rep.pending == [] and rep.pool_sessions == {}

    f.serve_until_drained(drain=True)
    s = f.summary()
    assert s["balanced"] and s["drains"] == 1
    assert s["drained"] == [victim]
    assert s["resolved"] == 4 and s["pending"] == 0  # zero acked loss
    for t in f.resolved_tickets():
        if t.board is not None:
            np.testing.assert_array_equal(
                t.result, oracle_n(t.board, t.steps),
                err_msg=f"ticket {t.id} lost parity across the drain")
    np.testing.assert_array_equal(f.snapshot_session(vsess),
                                  oracle_n(sb, 2))
    with pytest.raises(ValueError, match="already left"):
        f.drain_worker(victim)


def test_drain_last_survivor_refused():
    f, _clk = _fleet(2, ServePolicy(max_batch=4, max_wait_s=0.0))
    f.drain_worker(0)
    with pytest.raises(RuntimeError, match="no survivors"):
        f.drain_worker(1)


def test_autoscale_adds_on_breach_drains_on_surplus(make_board):
    """The SLO loop end to end: sustained p99 breach grows the fleet
    (after breach_k consecutive breaches, never during cooldown),
    sustained surplus drains it back — and the action log shows two
    clean decisions, not a flap."""
    elastic = policy_mod.ElasticityPolicy(
        slo_p99_s=0.01, min_workers=2, max_workers=3,
        breach_k=2, surplus_k=3, cooldown_k=2)
    pol = ServePolicy(max_batch=4, max_wait_s=0.0)
    f, clk = _fleet(2, pol, steal=False, elasticity=elastic,
                    elastic_window_s=5.0)
    assert f.controller is not None

    # Breach: every resolved ticket waits ~0.05s >> the 0.01s SLO.
    rounds_before = len(f.handles)
    for i in range(4):
        f.submit(make_board(16, 16), 2, session=f"s{i}")
        clk.sleep(0.05)
        f.pump()
    assert len(f.handles) == rounds_before + 1 == 3
    assert f.controller.actions == [policy_mod.SCALE_ADD]
    new = f.handles[-1]
    assert new.index == 2 and not new.wedged  # next free index
    # ... and max_workers caps further growth even under breach.
    for i in range(6):
        f.submit(make_board(16, 16), 2, session=f"b{i}")
        clk.sleep(0.05)
        f.pump()
    assert len(f.handles) == 3

    # Surplus: quiet fleet, p99 window empties, depth zero → after
    # cooldown + surplus_k the shallowest worker drains.
    f.serve_until_drained(drain=True)
    clk.sleep(10.0)  # age the window out
    for _ in range(8):
        f.pump()
        clk.sleep(0.01)
    assert f.controller.actions == [policy_mod.SCALE_ADD,
                                    policy_mod.SCALE_DRAIN]
    assert len(f.router.live_workers()) == 2  # back at min capacity
    assert f.summary()["balanced"]


def test_fleet_cli_refuses_workers_sharing_a_chip(monkeypatch, capsys):
    """Off the explicit CPU pin the fleet CLI runs at most one worker
    process (each would claim every chip of the host), and a worker
    refuses a CPU backend nobody asked for."""
    from mpi_and_open_mp_tpu.serve import fleet as fleet_mod

    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(SystemExit) as ei:
        fleet_mod.main(["--workers", "2"])
    assert ei.value.code == 2
    assert "JAX_PLATFORMS=cpu" in capsys.readouterr().err
    rc = fleet_mod.main(["--worker-main", "0", "--spool", "unused",
                         "--wal", "unused"])
    assert rc == 1
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["worker"] == 0 and "not a TPU" in line["error"]
