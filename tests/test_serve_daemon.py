"""Serving daemon: admission, deadlines, supervision ladder, drain/resume.

The fault-tolerant serving layer (``serve.policy`` / ``serve.queue`` /
``serve.daemon``) on the 8-virtual-device CPU mesh, against the NumPy
oracle throughout. The contracts under test: a rejected request carries
an explicit shed reason (never silently dropped); a bucket that never
fills still flushes at its max-wait deadline; results hold ticket order
under interleaved buckets; a chaos-injected dispatch fault degrades down
the engine ladder with ``:recovered`` provenance and oracle-exact output;
retry exhaustion and per-request timeouts shed with their own reasons;
a preemption (chaos plan or SIGTERM via the CLI) checkpoints the pending
queue, exits 75, and ``--resume`` restores every admitted ticket — zero
loss across the process boundary; and the chaos soak: every admitted
ticket ends in a result or an explicit shed, requests == resolved + shed.
"""

import json

import numpy as np
import pytest

from conftest import oracle_n
from mpi_and_open_mp_tpu.robust import chaos, guards, preempt
from mpi_and_open_mp_tpu.serve import (
    SHED_REASONS,
    ServePolicy,
    ServeQueue,
    ServingDaemon,
)
from mpi_and_open_mp_tpu.serve import policy as policy_mod
from mpi_and_open_mp_tpu.serve.queue import DONE, PENDING, SHED


@pytest.fixture(autouse=True)
def _clean_chaos():
    chaos.reset()
    guards.clear_recovery_log()
    yield
    chaos.reset()
    guards.clear_recovery_log()


class FakeClock:
    """Deterministic monotonic clock; ``sleep`` advances it."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t

    def sleep(self, s: float) -> None:
        self.t += s


def _daemon(policy, clk=None, **kw) -> tuple[ServingDaemon, FakeClock]:
    clk = clk or FakeClock()
    return ServingDaemon(policy, clock=clk, sleep=clk.sleep, **kw), clk


# ------------------------------------------------------------------ policy


def test_padding_waste_math():
    pw = policy_mod.padding_waste
    assert pw([], 8) == 0.0
    assert pw([8], 8) == 0.0  # a full chunk wastes nothing
    assert pw([3], 8) == pytest.approx(1 / 4)  # 3 live in a pow2-4 pad
    assert pw([5], 8) == pytest.approx(3 / 8)
    # 11 = one full 8-chunk + a 3-remainder padded to 4.
    assert pw([11], 8) == pytest.approx(1 / 12)
    assert pw([8, 3], 8) == pytest.approx(1 / 12)  # two buckets, same sum


def test_admit_depth_then_padding():
    pol = ServePolicy(max_batch=8, max_depth=4, max_padding_frac=0.2)
    assert policy_mod.admit(pol, 0, [1]) is None
    assert policy_mod.admit(pol, 4, [5]) == policy_mod.SHED_DEPTH
    # 3 pending in one bucket pads to 4: waste 0.25 > 0.2.
    assert policy_mod.admit(pol, 2, [3]) == policy_mod.SHED_PADDING


def test_percentile_nearest_rank():
    pct = policy_mod.percentile
    assert pct([], 99) == 0.0
    xs = [float(i) for i in range(1, 101)]
    assert pct(xs, 50) == 50.0
    assert pct(xs, 99) == 99.0
    assert pct(xs, 100) == 100.0
    assert pct([7.0], 99) == 7.0


def test_policy_validation():
    with pytest.raises(ValueError, match="max_batch"):
        ServePolicy(max_batch=0)
    with pytest.raises(ValueError, match="max_padding_frac"):
        ServePolicy(max_padding_frac=1.5)
    with pytest.raises(ValueError, match="max_wait_s"):
        ServePolicy(max_wait_s=-1.0)


# ------------------------------------------------------------------- queue


def test_queue_admission_sheds_with_reason(make_board):
    q = ServeQueue(ServePolicy(max_batch=8, max_depth=2))
    t0 = q.submit(make_board(16, 16), 2, now=0.0)
    t1 = q.submit(make_board(16, 16), 2, now=0.0)
    t2 = q.submit(make_board(16, 16), 2, now=0.0)
    assert t0.state == t1.state == PENDING
    assert t2.state == SHED and t2.reason == policy_mod.SHED_DEPTH
    assert q.depth() == 2
    # The rejected ticket is still on the ledger: nothing silently drops.
    assert len(q.tickets()) == 3


def test_queue_deadline_and_chunk_order(make_board):
    """A full chunk is always due; a remainder waits for max_wait; chunks
    come out oldest-lead-ticket first across interleaved buckets."""
    q = ServeQueue(ServePolicy(max_batch=4, max_wait_s=1.0))
    q.submit(make_board(8, 8), 2, now=0.0)  # ticket 0: the starved bucket
    for _ in range(4):  # tickets 1-4: a full chunk of the other shape
        q.submit(make_board(16, 16), 2, now=0.5)
    chunks = q.due_chunks(now=0.6)
    assert [len(c) for c in chunks] == [4]  # remainder not yet due
    assert q.next_deadline() == 1.0
    chunks = q.due_chunks(now=1.0)
    assert [[t.id for t in c] for c in chunks] == [[0], [1, 2, 3, 4]]
    assert q.due_chunks(now=0.0, drain=True)  # drain ignores deadlines


def test_queue_snapshot_restore_roundtrip_and_rejects_foreign(make_board):
    q = ServeQueue(ServePolicy())
    boards = [make_board(12, 12) for _ in range(3)]
    for b in boards:
        q.submit(b, 5, now=0.0)
    snap = q.snapshot()
    q2 = ServeQueue(ServePolicy())
    restored = q2.restore(snap, now=7.0)
    assert [t.steps for t in restored] == [5, 5, 5]
    assert all(t.resumed and t.submitted_at == 7.0 for t in restored)
    for t, b in zip(restored, boards):
        np.testing.assert_array_equal(t.board, b)
    with pytest.raises(ValueError, match="schema"):
        q2.restore({"schema": "something-else"}, now=0.0)
    with pytest.raises(ValueError, match="malformed"):
        q2.restore({"schema": "momp-serve-queue/1",
                    "pending": [{"id": 1}]}, now=0.0)


def test_queue_snapshot_carries_queued_seconds(make_board):
    """The satellite regression: a ticket that sat queued 3 s before the
    drain must NOT restart its latency clock on resume — the snapshot
    carries cumulative queued seconds and ``latency_s`` keeps counting
    from the FIRST submission."""
    q = ServeQueue(ServePolicy())
    q.submit(make_board(8, 8), 1, now=10.0)
    snap = q.snapshot(now=13.0)  # drained after 3 s queued
    assert snap["pending"][0]["queued_s"] == pytest.approx(3.0)

    q2 = ServeQueue(ServePolicy())
    (t,) = q2.restore(snap, now=0.0)  # fresh process, fresh clock
    assert t.queued_before_s == pytest.approx(3.0)
    q2.resolve(t, t.board, "oracle", now=2.0)
    assert t.latency_s == pytest.approx(5.0)  # 3 s before + 2 s after

    # A second drain/restore keeps accumulating, never resets.
    q2._tickets.clear()
    (t2,) = q2.restore(snap, now=5.0)
    snap2 = q2.snapshot(now=9.0)
    assert snap2["pending"][0]["queued_s"] == pytest.approx(7.0)


# ------------------------------------------------------------------ daemon


def test_daemon_zero_requests_noop():
    d, clk = _daemon(ServePolicy())
    d.serve()
    assert d.pump() == 0
    s = d.summary()
    assert s["requests"] == s["resolved"] == s["shed"] == s["batches"] == 0
    assert s["p50_latency_s"] == s["p99_latency_s"] == 0.0


def test_daemon_never_full_bucket_flushes_at_max_wait(make_board):
    """3 requests into a max_batch=8 bucket: nothing is due at submit
    time; serve() sleeps to the deadline and flushes — the padding-vs-p99
    trade in action."""
    d, clk = _daemon(ServePolicy(max_batch=8, max_wait_s=0.5))
    boards = [make_board(16, 16) for _ in range(3)]
    for b in boards:
        d.submit(b, 4)
    assert d.pump() == 0  # not due yet
    d.serve()
    assert clk.t >= 0.5  # the flush waited for the deadline, not forever
    s = d.summary()
    assert s["resolved"] == 3 and s["shed"] == 0 and s["batches"] == 1
    for t, b in zip(d.queue.tickets(), boards):
        assert t.state == DONE and t.engine == "batch:xla"
        np.testing.assert_array_equal(t.result, oracle_n(b, 4))
    assert s["p99_latency_s"] >= 0.5  # latency includes the bucket wait


def test_daemon_ticket_order_stable_under_interleaved_buckets(make_board):
    """Alternating shapes and step counts: every ticket's result must be
    its OWN board's oracle — no cross-bucket or cross-chunk mixups."""
    d, _ = _daemon(ServePolicy(max_batch=4, max_wait_s=0.0))
    shapes = [(16, 16), (24, 16), (16, 16), (24, 16)]
    subs = []
    for i in range(12):
        ny, nx = shapes[i % len(shapes)]
        b = make_board(ny, nx)
        steps = (i % 3) + 1
        subs.append((b, steps, d.submit(b, steps)))
    d.drain()
    assert [t.id for t in d.queue.tickets()] == list(range(12))
    for b, steps, t in subs:
        assert t.state == DONE
        np.testing.assert_array_equal(
            t.result, oracle_n(b, steps),
            err_msg=f"ticket {t.id} shape {b.shape} steps {steps}")


def test_daemon_degrades_on_chaos_fault_with_provenance(
        monkeypatch, make_board):
    """``serve_fail=1``: the primary engine raises once mid-queue; the
    ladder recovers on the suppressed XLA engine, stamps ``:recovered``,
    funnels through the recovery log, and stays oracle-exact."""
    monkeypatch.setenv("MOMP_CHAOS", "serve_fail=1")
    chaos.reset()
    d, _ = _daemon(ServePolicy(max_batch=4, max_wait_s=0.0))
    boards = [make_board(16, 16) for _ in range(4)]
    for b in boards:
        d.submit(b, 3)
    d.serve()
    s = d.summary()
    assert s["resolved"] == 4 and s["degraded"] == 1 and s["retries"] == 0
    assert list(s["engines"]) == ["batch:xla:recovered"]
    assert guards.recovery_log() == ["serve:batch:xla:recovered"]
    for t, b in zip(d.queue.tickets(), boards):
        np.testing.assert_array_equal(t.result, oracle_n(b, 3))


def test_daemon_retry_exhaustion_sheds_dispatch_failed(make_board):
    d, clk = _daemon(ServePolicy(
        max_batch=4, max_wait_s=0.0, max_retries=1,
        backoff_base_s=0.01, backoff_jitter=0.0, request_timeout_s=100.0))

    def boom():
        raise RuntimeError("wedged engine")

    d._engines = lambda stack, steps: [("a", boom), ("b", boom)]
    tickets = [d.submit(make_board(8, 8), 1) for _ in range(2)]
    d.serve()
    s = d.summary()
    assert s["resolved"] == 0 and s["shed"] == 2
    assert s["shed_reasons"] == {policy_mod.SHED_DISPATCH: 2}
    assert s["retries"] == 2  # max_retries + the final exhausted attempt
    assert all(t.reason == policy_mod.SHED_DISPATCH for t in tickets)


def test_daemon_timeout_during_backoff_sheds_timeout(make_board):
    """The retry ladder never sleeps past a member ticket's end-to-end
    budget: a backoff wait that would cross the deadline sheds the chunk
    with the timeout reason instead."""
    d, _ = _daemon(ServePolicy(
        max_batch=4, max_wait_s=0.0, max_retries=5,
        backoff_base_s=5.0, backoff_jitter=0.0, request_timeout_s=1.0))

    def boom():
        raise RuntimeError("still wedged")

    d._engines = lambda stack, steps: [("a", boom)]
    t = d.submit(make_board(8, 8), 1)
    d.serve()
    assert t.state == SHED and t.reason == policy_mod.SHED_TIMEOUT


def test_daemon_sheds_stale_tickets_before_dispatch(make_board):
    """A ticket that aged past its budget while queued is shed at the
    dispatch boundary, not advanced for nobody."""
    d, clk = _daemon(ServePolicy(max_wait_s=0.0, request_timeout_s=1.0))
    t = d.submit(make_board(8, 8), 1)
    clk.t = 5.0
    d.serve()
    assert t.state == SHED and t.reason == policy_mod.SHED_TIMEOUT
    assert d.summary()["batches"] == 0


def test_chaos_preempt_checkpoint_resume_zero_loss(
        monkeypatch, tmp_path, make_board):
    """The tentpole acceptance cycle, in-process: preempt after one
    dispatched batch, pending queue checkpointed, resume restores every
    drained ticket, and ALL 12 admitted requests end resolved with
    oracle parity — an admitted request is never dropped."""
    monkeypatch.setenv("MOMP_CHAOS", "preempt=1")
    chaos.reset()
    ck = tmp_path / "queue.state"
    pol = ServePolicy(max_batch=4, max_wait_s=0.0)
    d, clk = _daemon(pol, checkpoint_path=str(ck))
    boards = [make_board(16, 16) for _ in range(12)]
    for b in boards:
        d.submit(b, 2)
    with pytest.raises(preempt.SimulatedPreemption) as ei:
        d.serve()
    assert ei.value.step == 1 and ei.value.checkpoint == str(ck)
    assert d.summary()["resolved"] == 4 and d.queue.depth() == 8
    assert ck.exists()

    # "Cross-process" resume: chaos spec gone (the CI smoke resumes
    # without MOMP_CHAOS; in-process the latch already blocks a refire).
    monkeypatch.delenv("MOMP_CHAOS")
    chaos.reset()
    d2 = ServingDaemon.resume(str(ck), pol, clock=clk, sleep=clk.sleep)
    assert d2.queue.depth() == 8
    assert all(t.resumed for t in d2.queue.pending())
    d2.serve()
    s2 = d2.summary()
    assert s2["resolved"] == 8 and s2["shed"] == 0
    for t, b in zip(d2.queue.tickets(), boards[4:]):
        np.testing.assert_array_equal(t.board, b)  # payloads survived
        np.testing.assert_array_equal(t.result, oracle_n(b, 2))


def test_resume_rejects_corrupt_checkpoint(tmp_path):
    bad = tmp_path / "garbage.state"
    bad.write_bytes(b"this is not a MOMP-STATE file")
    with pytest.raises(ValueError, match="magic"):
        ServingDaemon.resume(str(bad))
    with pytest.raises(ValueError, match="no readable"):
        ServingDaemon.resume(str(tmp_path / "missing.state"))


def test_resume_any_quarantines_corrupt_checkpoint_and_serves(tmp_path):
    """The ladder half of the version-skew contract: a corrupt/foreign
    drain checkpoint (the strict `resume` above refuses it) must not
    refuse service on the full ladder — resume_any quarantines the bad
    file to a stamped forensic copy and falls through to fresh. TWO
    corrupt resumes keep TWO distinct copies: the evidence of two
    independent corruptions is itself evidence."""
    import glob

    bad = tmp_path / "skewed.state"
    bad.write_bytes(b"MOMP-STATE/9\n" + b"\x00" * 32)  # future version
    d, source, detail = ServingDaemon.resume_any(
        checkpoint_path=str(bad), policy=ServePolicy(max_batch=2))
    assert source == "fresh" and d.queue.depth() == 0
    assert "magic" in detail["checkpoint_error"]
    copies = glob.glob(str(bad) + ".corrupt.*")
    assert len(copies) == 1 and detail["checkpoint_quarantine"] == copies[0]
    assert not bad.exists()  # moved aside, never re-read

    bad.write_bytes(b"second independent corruption")
    d2, source2, detail2 = ServingDaemon.resume_any(
        checkpoint_path=str(bad), policy=ServePolicy(max_batch=2))
    assert source2 == "fresh"
    copies2 = sorted(glob.glob(str(bad) + ".corrupt.*"))
    assert len(copies2) == 2  # the first forensic copy survived
    assert detail2["checkpoint_quarantine"] in copies2


def test_chaos_soak_every_ticket_terminal(monkeypatch, make_board):
    """The soak contract: under mid-queue faults AND admission pressure,
    every submitted ticket ends in exactly one terminal state with either
    a parity-checked result or an explicit policy reason, and the
    accounting closes: requests == resolved + shed."""
    monkeypatch.setenv("MOMP_CHAOS", "serve_fail=3;delay=0.001")
    chaos.reset()
    d, _ = _daemon(ServePolicy(
        max_batch=4, max_depth=10, max_padding_frac=0.5, max_wait_s=0.01,
        backoff_base_s=0.01))
    shapes = [(16, 16), (24, 16)]
    subs = []
    for i in range(16):
        ny, nx = shapes[i % 2]
        b = make_board(ny, nx)
        subs.append((b, d.submit(b, 2)))
    d.serve()
    s = d.summary()
    assert s["requests"] == 16
    assert s["resolved"] + s["shed"] == 16 and s["pending"] == 0
    assert s["shed_reasons"].get(policy_mod.SHED_DEPTH, 0) == 6  # cap 10
    assert s["degraded"] == 3  # every injected fault self-healed
    for b, t in subs:
        assert t.state in (DONE, SHED)
        if t.state == DONE:
            assert t.engine is not None
            np.testing.assert_array_equal(t.result, oracle_n(b, 2))
        else:
            assert t.reason in SHED_REASONS


# --------------------------------------------------------------------- CLI


def test_daemon_cli_preempt_exits_75_then_resume_verifies(
        monkeypatch, tmp_path, capsys):
    """The cross-process contract through the CLI: chaos preemption →
    one JSON line, exit 75, checkpoint on disk; ``--resume --verify`` →
    exit 0 with every restored ticket resolved oracle-exact, and the
    two lines' accounting covers the full burst."""
    from mpi_and_open_mp_tpu.serve import daemon as daemon_cli

    ck = tmp_path / "q.state"
    monkeypatch.setenv("MOMP_CHAOS", "preempt=1")
    chaos.reset()
    rc = daemon_cli.main(["--requests", "8", "--max-batch", "4",
                          "--max-wait", "0", "--checkpoint", str(ck),
                          "--seed", "3"])
    line1 = json.loads(capsys.readouterr().out.strip())
    assert rc == preempt.EXIT_PREEMPTED == 75
    assert line1["preempted"] is True and line1["resume"] is True
    assert line1["checkpoint"] == str(ck) and ck.exists()

    monkeypatch.delenv("MOMP_CHAOS")
    chaos.reset()
    rc = daemon_cli.main(["--requests", "0", "--resume",
                          "--checkpoint", str(ck), "--verify"])
    line2 = json.loads(capsys.readouterr().out.strip())
    assert rc == 0
    assert line2["verified"] is True and line2["preempted"] is False
    assert line2["resumed_tickets"] == line2["resolved"]
    assert (line1["resolved"] + line1["shed"]
            + line2["resolved"] + line2["shed"]) == 8


def test_daemon_cli_resume_requires_checkpoint(capsys):
    from mpi_and_open_mp_tpu.serve import daemon as daemon_cli

    with pytest.raises(SystemExit) as ei:
        daemon_cli.main(["--resume"])
    assert ei.value.code == 2
