"""Chaos fabric: fault injection, guards, watchdog, preemption/resume.

Every recovery path in the robust subsystem, exercised on the 8-virtual-
device CPU mesh: the plan parser, the zero-reachability contract when
``MOMP_CHAOS`` is unset, the engine-fallback ladder, ring-attention hop
poisoning (inject-and-diverge under ``noguard``, inject-and-recover with
guards), halo-corruption recovery in ``LifeSim``, simulated and
signal-driven preemption with checkpoint flush + bit-identical resume,
the watchdog backoff, and the bench error-JSON / exit-75 contracts.
"""

import json
import os
import signal
import sys
import threading

import numpy as np
import pytest

import jax.numpy as jnp

from conftest import oracle_n
from mpi_and_open_mp_tpu.models.life import LifeSim
from mpi_and_open_mp_tpu.parallel import context, mesh as mesh_lib
from mpi_and_open_mp_tpu.robust import chaos, guards, preempt, watchdog
from mpi_and_open_mp_tpu.utils.config import config_from_board

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_chaos():
    """Fresh plan cache and recovery log around every test: the plan
    carries runtime state (the preemption latch) keyed on the env raw."""
    chaos.reset()
    guards.clear_recovery_log()
    yield
    chaos.reset()
    guards.clear_recovery_log()


# --------------------------------------------------------------- plan parsing


def test_fault_plan_parses_full_spec():
    plan = chaos.FaultPlan.parse(
        "nan_hop=1;halo=corrupt;delay=0.25;preempt=60;seed=7")
    assert plan.hop_poison == ("nan", 1)
    assert plan.halo_fault == "corrupt"
    assert plan.delay_s == 0.25
    assert plan.preempt_step == 60
    assert plan.seed == 7
    assert plan.guard  # default armed
    plan = chaos.FaultPlan.parse("inf_hop=3;halo=drop;noguard")
    assert plan.hop_poison == ("inf", 3)
    assert plan.halo_fault == "drop"
    assert not plan.guard


@pytest.mark.parametrize("bad", [
    "nan_hop=x", "halo=melt", "delay=-1", "preempt=ten", "bogus=1", "noguard=1",
    "crash=elsewhere:1", "crash=mid-frame:0", "crash=post-admit:x",
])
def test_fault_plan_rejects_bad_tokens(bad):
    with pytest.raises(ValueError, match="MOMP_CHAOS"):
        chaos.FaultPlan.parse(f"seed=1;{bad}")


def test_crash_token_parses_and_arms(monkeypatch):
    plan = chaos.FaultPlan.parse("crash=mid-frame:3")
    assert plan.crash_site == "mid-frame" and plan.crash_at == 3
    assert chaos.FaultPlan.parse("crash=post-admit").crash_at == 1

    monkeypatch.setenv("MOMP_CHAOS", "crash=post-dispatch:2")
    chaos.reset()
    # Wrong site never counts; the right site fires exactly on arrival k.
    assert not chaos.crash_armed("post-admit")
    assert not chaos.crash_armed("post-dispatch")  # arrival 1 of 2
    with chaos.suppressed():
        assert not chaos.crash_armed("post-dispatch")  # inert, no count
    assert chaos.crash_armed("post-dispatch")  # arrival 2: fire
    assert not chaos.crash_armed("post-dispatch")  # never refires


def test_preempt_pending_latch_and_resume_semantics():
    plan = chaos.FaultPlan.parse("preempt=60")
    assert plan.preempt_pending(0) and plan.preempt_pending(59)
    assert not plan.preempt_pending(60)  # a --resume at the preempt step
    assert not plan.preempt_pending(80)  # ... or past it must continue
    plan.preempt_fired = True
    assert not plan.preempt_pending(0)  # in-process refire latch


# --------------------------------------------- zero reachability when unset


def test_no_injection_when_unset(monkeypatch):
    monkeypatch.delenv("MOMP_CHAOS", raising=False)
    chaos.reset()
    assert chaos.active_plan() is None
    assert chaos.trace_key("ring") is None
    assert chaos.hop_poison_spec() is None
    assert chaos.halo_ghost_spec() is None
    assert chaos.dispatch_delay() == 0.0
    # The halo hook is an identity passthrough — the SAME object, no
    # injection ops built.
    from mpi_and_open_mp_tpu.parallel.halo import _chaos_ghost

    ghost = jnp.ones((2, 8))
    assert _chaos_ghost(ghost) is ghost


def test_suppressed_hides_an_active_plan(monkeypatch):
    monkeypatch.setenv("MOMP_CHAOS", "halo=drop")
    chaos.reset()
    assert chaos.active_plan() is not None
    with chaos.suppressed():
        assert chaos.active_plan() is None
        with chaos.suppressed():  # reentrant
            assert chaos.active_plan() is None
        assert chaos.active_plan() is None
    assert chaos.active_plan() is not None


# ------------------------------------------------------------ with_fallback


def test_with_fallback_first_engine_clean():
    out, stamp, notes = guards.with_fallback(
        [("a", lambda: 1), ("b", lambda: 2)], validator=lambda r: r == 1)
    assert (out, stamp, notes) == (1, "a", [])


def test_with_fallback_recovers_with_provenance():
    calls = []

    def bad():
        calls.append("bad")
        raise RuntimeError("boom")

    out, stamp, notes = guards.with_fallback(
        [("a", bad), ("b", lambda: 7)])
    assert out == 7 and stamp == "b:recovered"
    assert any("boom" in n for n in notes)


def test_with_fallback_validator_failure_and_exhaustion():
    # A validator exception counts as a failure, not a crash.
    with pytest.raises(guards.FallbackExhausted) as ei:
        guards.with_fallback(
            [("a", lambda: 1), ("b", lambda: 2)],
            validator=lambda r: (_ for _ in ()).throw(ValueError("nope")))
    assert "nope" in str(ei.value)
    # Falsy results fall through too (the gated_parity_check usage).
    with pytest.raises(guards.FallbackExhausted):
        guards.with_fallback([("a", lambda: False)], validator=bool)


def test_with_fallback_retries_same_engine():
    attempts = []

    def flaky():
        attempts.append(1)
        if len(attempts) < 2:
            raise RuntimeError("transient")
        return 42

    out, stamp, _ = guards.with_fallback([("a", flaky)], retries=2)
    assert out == 42 and stamp == "a:recovered" and len(attempts) == 2


# ----------------------------------------------------------------- watchdog


def test_watchdog_backoff_schedule_capped():
    assert watchdog.backoff_schedule(5, base_s=2.0, cap_s=10.0) == [
        2.0, 4.0, 8.0, 10.0, 10.0]
    assert watchdog.backoff_schedule(0) == []


def test_watchdog_backoff_generator_jitter_seeded_and_bounded():
    """The jittered schedule is a pure generator: seeded draws are
    reproducible, every wait stays within [base*(1-jitter), base] of the
    un-jittered capped-exponential value, and distinct seeds decorrelate
    (the thundering-herd property a requeue loop of several daemons
    needs)."""
    import itertools

    pure = [1.0, 2.0, 4.0, 8.0, 8.0, 8.0]
    assert list(itertools.islice(watchdog.backoff(1.0, 8.0), 6)) == pure
    a = list(itertools.islice(
        watchdog.backoff(1.0, 8.0, jitter=0.5, seed=3), 6))
    b = list(itertools.islice(
        watchdog.backoff(1.0, 8.0, jitter=0.5, seed=3), 6))
    assert a == b  # seeded: same schedule every time
    for got, base in zip(a, pure):
        assert base * 0.5 <= got <= base
    c = list(itertools.islice(
        watchdog.backoff(1.0, 8.0, jitter=0.5, seed=4), 6))
    assert c != a  # different seed, different herd slot
    with pytest.raises(ValueError, match="jitter"):
        next(watchdog.backoff(jitter=1.5))


def test_watchdog_backoff_schedule_jitter_matches_generator():
    import itertools

    want = list(itertools.islice(
        watchdog.backoff(2.0, 60.0, jitter=0.25, seed=9), 4))
    assert watchdog.backoff_schedule(
        4, base_s=2.0, cap_s=60.0, jitter=0.25, seed=9) == want


# ------------------------------------------------- ring-attention hop guard


def _ring_operands(n=256):
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(4, n, 64)), jnp.float32)
               for _ in range(3))
    return q, k, v


def test_ring_nan_hop_noguard_diverges(monkeypatch):
    """Injection must actually land: under ``noguard`` the poisoned hop
    reaches the output as NaN — proof the fault isn't a no-op."""
    monkeypatch.setenv("MOMP_CHAOS", "nan_hop=2;noguard")
    chaos.reset()
    q, k, v = _ring_operands()
    out = context.ring_attention(
        q, k, v, mesh=mesh_lib.make_mesh_1d(axis="sp"), causal=True)
    assert not np.isfinite(np.asarray(out)).all()
    assert guards.recovery_log() == []


def test_ring_nan_hop_guard_recovers(monkeypatch):
    """With guards armed the NaN-poisoned hop engine is re-dispatched on
    the jnp fold oracle under suppression: finite output, oracle parity,
    ``:recovered`` provenance in the process log."""
    monkeypatch.setenv("MOMP_CHAOS", "nan_hop=2;seed=5")
    chaos.reset()
    q, k, v = _ring_operands()
    out = context.ring_attention(
        q, k, v, mesh=mesh_lib.make_mesh_1d(axis="sp"), causal=True)
    assert np.isfinite(np.asarray(out)).all()
    want = context.attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(want), atol=2e-5, rtol=1e-5)
    assert any(s.startswith("ring_attention:jnp:recovered")
               for s in guards.recovery_log())


def test_ring_guard_env_clean_pass_no_recovery(monkeypatch):
    """MOMP_GUARD=1 arms validation without chaos: a healthy dispatch
    passes first try and records nothing."""
    monkeypatch.delenv("MOMP_CHAOS", raising=False)
    monkeypatch.setenv("MOMP_GUARD", "1")
    chaos.reset()
    q, k, v = _ring_operands()
    out = context.ring_attention(
        q, k, v, mesh=mesh_lib.make_mesh_1d(axis="sp"), causal=True)
    want = context.attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(want), atol=2e-5, rtol=1e-5)
    assert guards.recovery_log() == []


# ------------------------------------------------------- LifeSim halo guard


def test_halo_drop_noguard_diverges(monkeypatch, make_board):
    """A dropped halo row without guards must corrupt the run — the
    injection-reaches-the-exchange proof for the LifeSim layer."""
    board = make_board(32, 32)
    cfg = config_from_board(board, steps=6, save_steps=0)
    monkeypatch.setenv("MOMP_CHAOS", "halo=drop;noguard")
    chaos.reset()
    sim = LifeSim(cfg, layout="row", impl="halo")
    final = sim.run(save=False)
    assert not np.array_equal(final, oracle_n(board, 6))
    assert sim.recoveries == []


@pytest.mark.parametrize("fault", ["corrupt", "drop"])
def test_halo_fault_guard_recovers_bit_identical(monkeypatch, make_board,
                                                 fault):
    """The consistency probe catches both halo fault kinds (Life output
    is always binary — only the single-step oracle probe can see them)
    and the suppressed re-trace recovers bit-identically."""
    board = make_board(32, 32)
    cfg = config_from_board(board, steps=12, save_steps=4)
    monkeypatch.setenv("MOMP_CHAOS", f"halo={fault};seed=3")
    chaos.reset()
    sim = LifeSim(cfg, layout="row", impl="halo")
    final = sim.run(save=False)
    np.testing.assert_array_equal(final, oracle_n(board, 12))
    assert sim.recoveries and "recovered" in sim.recoveries[0]
    assert guards.recovery_log()


def test_halo_guard_cart_layout(monkeypatch, make_board):
    """Same recovery through the 2-D cart exchange (both axes faulted)."""
    board = make_board(32, 24)
    cfg = config_from_board(board, steps=8, save_steps=0)
    monkeypatch.setenv("MOMP_CHAOS", "halo=corrupt;seed=11")
    chaos.reset()
    sim = LifeSim(cfg, layout="cart", impl="halo")
    final = sim.run(save=False)
    np.testing.assert_array_equal(final, oracle_n(board, 8))
    assert sim.recoveries


# ------------------------------------------------------ preemption + resume


def test_simulated_preemption_checkpoint_resume_bit_identity(
        monkeypatch, make_board, tmp_path):
    """The acceptance cycle: preempt at step 60 with checkpoints every
    20, resume from the flushed checkpoint, finish — bit-identical to an
    uninterrupted 100-step oracle run."""
    board = make_board(32, 32)
    cfg = config_from_board(board, steps=100, save_steps=0)
    ck = tmp_path / "ck"
    monkeypatch.setenv("MOMP_CHAOS", "preempt=60")
    chaos.reset()
    sim = LifeSim(cfg, layout="row", impl="halo",
                  checkpoint_dir=ck, checkpoint_every=20)
    with pytest.raises(preempt.SimulatedPreemption) as ei:
        sim.run()
    assert ei.value.step == 60
    assert ei.value.checkpoint.endswith("step_000060")
    assert sorted(os.listdir(ck)) == [
        "step_000020", "step_000040", "step_000060"]

    # Cross-process resume: fresh plan cache (new latch); the preempt
    # spec still set, but preempt_pending(60) is False — must NOT refire.
    chaos.reset()
    resumed = LifeSim.from_checkpoint(
        ck / "step_000060", cfg, layout="cart", impl="halo",
        checkpoint_dir=ck, checkpoint_every=20)
    assert resumed.step_count == 60
    final = resumed.run()
    np.testing.assert_array_equal(final, oracle_n(board, 100))


def test_preemption_without_checkpoint_dir(monkeypatch, make_board):
    """No checkpoint_dir: the preemption still fires (the run must not
    silently complete under a preempt plan), with no checkpoint path."""
    board = make_board(16, 16)
    cfg = config_from_board(board, steps=20, save_steps=0)
    monkeypatch.setenv("MOMP_CHAOS", "preempt=10;noguard")
    chaos.reset()
    sim = LifeSim(cfg, layout="row", impl="halo")
    with pytest.raises(preempt.SimulatedPreemption) as ei:
        sim.run(save=True)
    assert ei.value.checkpoint is None


def test_sigterm_flushes_checkpoint_and_resumes(monkeypatch, make_board,
                                                tmp_path):
    """A real SIGTERM mid-run: the handler only sets a flag; the loop
    flushes a checkpoint at the next segment boundary and raises
    Preempted(signum=SIGTERM); resume is bit-identical. The chaos delay
    paces segments so the timer lands deterministically mid-run."""
    board = make_board(24, 24)
    cfg = config_from_board(board, steps=100, save_steps=0)
    ck = tmp_path / "ck"
    monkeypatch.setenv("MOMP_CHAOS", "delay=0.05;noguard")
    chaos.reset()
    sim = LifeSim(cfg, layout="row", impl="halo",
                  checkpoint_dir=ck, checkpoint_every=5)
    # Safety net: if the run somehow finishes first, a late SIGTERM must
    # hit this ignore-handler, not pytest's default (process death).
    prev = signal.signal(signal.SIGTERM, lambda *a: None)
    timer = threading.Timer(
        0.12, os.kill, (os.getpid(), signal.SIGTERM))
    try:
        timer.start()
        with pytest.raises(preempt.Preempted) as ei:
            sim.run()
    finally:
        timer.cancel()
        signal.signal(signal.SIGTERM, prev)
    assert ei.value.signum == signal.SIGTERM
    assert 0 < ei.value.step < 100
    assert ei.value.checkpoint and os.path.isdir(ei.value.checkpoint)

    monkeypatch.delenv("MOMP_CHAOS")
    chaos.reset()
    from mpi_and_open_mp_tpu.apps.life import find_latest_checkpoint

    path, step = find_latest_checkpoint(str(ck))
    assert step == ei.value.step
    resumed = LifeSim.from_checkpoint(path, cfg, layout="row", impl="halo")
    np.testing.assert_array_equal(resumed.run(save=False),
                                  oracle_n(board, 100))


def test_flush_on_signal_restores_handlers():
    prev = signal.getsignal(signal.SIGTERM)
    with preempt.flush_on_signal() as watch:
        assert watch.fired is None
        assert signal.getsignal(signal.SIGTERM) is not prev
    assert signal.getsignal(signal.SIGTERM) is prev
    with preempt.flush_on_signal(enabled=False):
        assert signal.getsignal(signal.SIGTERM) is prev  # no-op when off


# ------------------------------------------------------------ fabric delay


def test_fabric_ping_carries_injected_delay(monkeypatch):
    import time as time_lib

    from mpi_and_open_mp_tpu.parallel import fabric

    mesh = mesh_lib.make_mesh_1d(axis="i")
    base = fabric.ping(mesh, 1, reps=2)
    monkeypatch.setenv("MOMP_CHAOS", "delay=0.1;noguard")
    chaos.reset()
    t0 = time_lib.perf_counter()
    delayed = fabric.ping(mesh, 1, reps=2)
    assert time_lib.perf_counter() - t0 >= 0.1
    assert delayed * 2 >= 0.1  # the delay lands INSIDE the timed bracket
    assert delayed > base


# ------------------------------------------------------- bench driver paths


def _import_bench():
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    import bench

    return bench


def test_bench_error_json_carries_phase(tmp_path, capsys, monkeypatch):
    """A failure mid-bench prints {"metric","error","phase"} and exits 1
    instead of dying on a traceback with no line."""
    bench = _import_bench()
    empty = tmp_path / "empty"
    empty.mkdir()
    rc = bench.main(["--board", "32", "--steps", "16",
                     "--checkpoint-dir", str(empty), "--resume"])
    assert rc == 1
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["metric"] == "life_steady_cups_p46gun_big"
    assert rec["phase"] == "checkpoint"
    assert "no checkpoints" in rec["error"]


def test_bench_chaos_preempt_then_resume(tmp_path, capsys, monkeypatch):
    """The CI chaos smoke, in-process: a chaos preemption exits 75 with
    "resume": true; the --resume invocation completes with oracle parity
    and resumed-step provenance in the bench line."""
    bench = _import_bench()
    ck = tmp_path / "ck"
    monkeypatch.setenv("MOMP_CHAOS", "preempt=60")
    chaos.reset()
    rc = bench.main(["--board", "48", "--steps", "100",
                     "--checkpoint-dir", str(ck), "--checkpoint-every", "20"])
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == preempt.EXIT_PREEMPTED == 75
    assert rec["resume"] is True and rec["phase"] == "checkpoint"
    assert "step 60" in rec["error"]

    monkeypatch.delenv("MOMP_CHAOS")
    chaos.reset()
    rc = bench.main(["--board", "48", "--steps", "100",
                     "--checkpoint-dir", str(ck), "--checkpoint-every", "20",
                     "--resume"])
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0
    assert rec["resumed_step"] == 60
    assert rec["checkpoint_parity"] is True
    assert rec["platform"] == "cpu"  # the explicit JAX_PLATFORMS=cpu pin


def test_bench_resume_requires_checkpoint_dir(capsys):
    bench = _import_bench()
    with pytest.raises(SystemExit) as ei:
        bench.main(["--resume"])
    assert ei.value.code == 2


def test_life_cli_preempt_exits_75(tmp_path, capsys, make_board, monkeypatch):
    """The life CLI translates Preempted to exit 75 (EX_TEMPFAIL) — the
    contract a batch scheduler keys its requeue on."""
    from mpi_and_open_mp_tpu.apps import life as life_app
    from mpi_and_open_mp_tpu.utils.config import save_config

    cfg = config_from_board(make_board(16, 16), steps=20, save_steps=0)
    cfg_path = tmp_path / "run.cfg"
    save_config(cfg_path, cfg)
    ck = tmp_path / "ck"
    monkeypatch.setenv("MOMP_CHAOS", "preempt=10;noguard")
    chaos.reset()
    rc = life_app.main([str(cfg_path), "--layout", "row", "--impl", "halo",
                        "--checkpoint-dir", str(ck),
                        "--checkpoint-every", "5"])
    assert rc == 75
    assert "requeue with --resume" in capsys.readouterr().err
    assert "step_000010" in os.listdir(ck)

    monkeypatch.delenv("MOMP_CHAOS")
    chaos.reset()
    capsys.readouterr()
    rc = life_app.main([str(cfg_path), "--layout", "row", "--impl", "halo",
                        "--checkpoint-dir", str(ck), "--resume"])
    assert rc == 0
    assert "resuming from checkpoint" in capsys.readouterr().err
