"""Round benchmark: Game-of-Life cell-updates/sec on the p46gun_big workload.

Workload per the reference's scaling benchmark (`3-life/p46gun_big.cfg`):
500x500 periodic torus, 10,000 steps, no intermediate saves = 2.5e9 cell
updates. Baseline: best recorded MPI result, 1.937 s @ 27 ranks = 1.29e9
cups (`6-cartesian/times.txt:27`, see BASELINE.md). The board content is a
fixed-seed random soup — cups is content-independent for a dense stencil.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
``value`` is the STEADY-STATE rate — the marginal per-step cups,
differenced between two run lengths so the fixed host dispatch cost
cancels. End-to-end time/rate stay as secondary fields.

Runs on the backend JAX initialises and stamps it; that must be the TPU,
or the CPU pinned with ``JAX_PLATFORMS=cpu``. Exits non-zero when any
phase recorded an ``*_error`` field (the line is still printed).
"""

import argparse
import functools
import json
import os
import sys
import time

import numpy as np


BASELINE_CUPS = 1.29e9
NY = NX = 500
STEPS = 10_000


def _checkpointed_run(args) -> dict:
    """The robustness phase: a checkpointed (optionally resumed) serial
    Life run of the bench workload, CRC-stamped and — when the board is
    small enough to replay on the host — parity-gated against the
    fault-free NumPy oracle. This is what the chaos CI smoke drives:
    under ``MOMP_CHAOS=preempt=k`` the run raises
    :class:`~mpi_and_open_mp_tpu.robust.preempt.Preempted` after flushing
    a checkpoint (main() turns that into exit 75 + ``"resume": true``),
    and the follow-up ``--resume`` invocation must complete bit-identical
    to the oracle.
    """
    import zlib

    from mpi_and_open_mp_tpu.apps.life import find_latest_checkpoint
    from mpi_and_open_mp_tpu.models.life import LifeSim
    from mpi_and_open_mp_tpu.ops.life_ops import life_step_numpy
    from mpi_and_open_mp_tpu.utils.config import config_from_board

    rng = np.random.default_rng(46)  # same board as the headline phases
    board = (rng.random((NY, NX)) < 0.3).astype(np.uint8)
    cfg = config_from_board(board, steps=STEPS, save_steps=0)
    every = args.checkpoint_every or max(1, STEPS // 10)
    kwargs = dict(layout="serial", impl="auto",
                  checkpoint_dir=args.checkpoint_dir,
                  checkpoint_every=every)
    fields = {"checkpoint_every": every}
    if args.resume:
        latest = find_latest_checkpoint(args.checkpoint_dir)
        if latest is None:
            raise RuntimeError(
                f"--resume: no checkpoints in {args.checkpoint_dir!r}")
        path, step = latest
        sim = LifeSim.from_checkpoint(path, cfg, **kwargs)
        fields["resumed_step"] = step
    else:
        sim = LifeSim(cfg, **kwargs)
    final = sim.run()  # raises Preempted on signal / chaos preemption
    crc = zlib.crc32(np.ascontiguousarray(final).tobytes()) & 0xFFFFFFFF
    fields["checkpoint_run_crc32"] = f"{crc:08x}"
    if sim.recoveries:
        fields["checkpoint_run_recovered"] = list(sim.recoveries)
    # Host oracle replay is O(NY*NX*STEPS) python-side — gate it to the
    # smoke sizes; the flagship keeps only the CRC (cross-run comparable).
    if NY * NX * STEPS <= 2**26:
        oracle = board.copy()
        for _ in range(STEPS):
            oracle = life_step_numpy(oracle)
        if not np.array_equal(final, oracle):
            raise RuntimeError(
                "checkpointed run diverged from the fault-free oracle")
        fields["checkpoint_parity"] = True
    return fields


def _batched_phase(batch: int, cups_single: float) -> dict:
    """The request-batched throughput phase (``--batch B``): B DISTINCT
    boards of the bench shape advanced STEPS steps in ONE device
    dispatch through the batched native engines
    (``ops.pallas_life.life_run_vmem_batch``), plus the serve-layer
    micro-batcher driving the same stack shape. Runs on every backend —
    batching amortizes the fixed dispatch cost, which is exactly what
    a CPU line is dominated by. Honesty discipline matches
    the headline: EVERY board is gated bit-exact against the NumPy
    oracle before any timing is recorded, and the steady rate is
    chain-differenced (the batched step count is a runtime scalar on
    every path, so the chained dispatch reuses the same executable).
    """
    import jax
    import jax.numpy as jnp

    from mpi_and_open_mp_tpu.ops import bitlife, pallas_life
    from mpi_and_open_mp_tpu.ops.life_ops import life_step_numpy
    from mpi_and_open_mp_tpu.serve import ShapeBucketBatcher, retrace_counts
    from mpi_and_open_mp_tpu.utils.timing import anchor_sync

    rng = np.random.default_rng(47)  # distinct per-board soups
    stack = (rng.random((batch, NY, NX)) < 0.3).astype(np.uint8)
    on_tpu = jax.default_backend() == "tpu"
    path = pallas_life.native_path_batch(stack.shape, on_tpu=on_tpu)
    fields = {
        "batch": batch,
        "batch_engine": f"batch:{path}",
        # Closed vocabulary {cell-packed, bitsliced}; the ledger keys on
        # it and the sentinel flags bitsliced -> cell-packed downgrades.
        "batch_pack_layout": pallas_life.batch_pack_layout(
            stack.shape, on_tpu=on_tpu),
    }

    # Per-board honesty gate: the batched engine must be bit-exact on
    # EVERY board of the stack (a fused-over-batch bug could corrupt one
    # board while the rest pass — name the divergent ones).
    stack_j = jnp.asarray(stack)
    got = np.asarray(pallas_life.life_run_vmem_batch(stack_j, 8))
    bad = []
    for b in range(batch):
        ref = stack[b].copy()
        for _ in range(8):
            ref = life_step_numpy(ref)
        if not np.array_equal(got[b], ref):
            bad.append(b)
    if bad:
        fields["batched_error"] = (
            f"parity check failed on boards {bad[:8]} of {batch}")
        return fields
    fields["batched_parity"] = True

    def timed(n, reps=3):
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            anchor_sync(pallas_life.life_run_vmem_batch(stack_j, n),
                        fetch_all=True)
            best = min(best, time.perf_counter() - t0)
        return best

    # Compile/warm outside the brackets (the gate above ran n=8; n is a
    # runtime scalar, so this is a warm re-dispatch, not a compile).
    anchor_sync(pallas_life.life_run_vmem_batch(stack_j, STEPS),
                fetch_all=True)
    best = timed(STEPS)
    # Chained differencing, same discipline as measure(): big chains
    # only when the base run is RTT-bound (sub-second); a multi-second
    # CPU run takes the cheapest chain (2x) single-shot.
    rtt_bound = best < 1.0
    mult, reps = (161, 3) if rtt_bound else (2, 1)
    chained = timed(STEPS * mult, reps)
    differenced = chained > best
    steady = (chained - best) / (mult - 1) if differenced else best
    updates = batch * NY * NX * STEPS
    fields.update({
        "batched_cups": round(updates / best, 1),
        "batched_requests_per_sec": round(batch / best, 3),
        "batched_steady_cups": round(updates / steady, 1),
        "batched_is_differenced": differenced,
        # The amortization headline: aggregate end-to-end rate vs the
        # single-board end-to-end rate measured by the headline phase.
        "batched_vs_single": (round(updates / best / cups_single, 2)
                              if cups_single else None),
    })

    if fields["batch_pack_layout"] == "bitsliced":
        # Layout A/B, both sides the same discipline: chain-differenced
        # per-step rate (9x chain, best of 3) with the baseline engine
        # parity-gated first. The baseline is the engine a bitsliced
        # stack would otherwise run — the vmapped cell-packed XLA loop
        # (the daemon's "batch:xla" rung). The ratio is measured in ONE
        # process so RTT and machine noise cancel; the sentinel watches
        # it for quiet erosion of the layout's advantage.
        n0, mult_ab, cells = min(STEPS, 200), 9, batch * NY * NX

        def steady_of(run):
            anchor_sync(run(n0), fetch_all=True)  # warm re-dispatch

            def t(n):
                b = float("inf")
                for _ in range(3):
                    t0 = time.perf_counter()
                    anchor_sync(run(n), fetch_all=True)
                    b = min(b, time.perf_counter() - t0)
                return b

            t1, t2 = t(n0), t(n0 * mult_ab)
            if t2 > t1:
                return (t2 - t1) / (n0 * (mult_ab - 1))
            return t1 / n0

        base8 = np.asarray(bitlife.life_run_bits_xla_batch(stack_j, 8))
        if not np.array_equal(base8, got):
            fields["batched_error"] = (
                "cell-packed baseline diverged from the gated bitsliced "
                "output — layout A/B not recorded")
            return fields
        per_bs = steady_of(
            lambda n: pallas_life.life_run_vmem_batch(stack_j, n))
        per_cp = steady_of(
            lambda n: bitlife.life_run_bits_xla_batch(stack_j, n))
        fields.update({
            "bitsliced_cups": round(cells / per_bs, 1),
            "cellpacked_vmapped_cups": round(cells / per_cp, 1),
            "vs_cellpacked": round(per_cp / per_bs, 2),
        })

    # Serve-layer demo: the SAME B requests through the micro-batcher —
    # one shape bucket, one dispatch, and (steps being runtime) zero new
    # compiles beyond the gate's. The jit.retrace{fn=life_batch_*}
    # counters on the line's metrics snapshot are the proof.
    bat = ShapeBucketBatcher(max_batch=batch)
    for b in range(batch):
        bat.submit(stack[b], 8)
    out = bat.flush()
    fields.update({
        "serve_buckets": len(bat.last_flush_stats),
        "serve_batches": len(bat.last_flush_stats),
        "serve_requests": sum(s.requests for s in bat.last_flush_stats),
        "serve_parity": all(
            np.array_equal(o, g) for o, g in zip(out, got)),
        "batch_retraces": retrace_counts(),
    })
    return fields


def _phase_metrics_delta(key: str, before: dict) -> dict:
    """Per-phase metric scoping (``obs.metrics.delta``): each opt-in
    phase snapshots the registry at entry and publishes only the
    movement IT caused, so ``--batch`` counters cannot bleed into the
    ``--serve`` / ``--loadgen`` sub-objects when phases stack on one
    bench line. The global cumulative snapshot still rides the line
    unchanged (``metrics``)."""
    from mpi_and_open_mp_tpu.obs import metrics as obs_metrics

    if not obs_metrics.metrics_on():
        return {}
    return {f"{key}_phase_metrics":
            obs_metrics.delta(before, obs_metrics.snapshot())}


def _serve_phase(n: int) -> dict:
    """The serving-daemon latency phase (``--serve N``): a seeded
    mixed-shape burst of N requests through the supervised daemon
    (``serve.daemon`` — admission control, per-bucket deadlines, the
    guards recovery ladder), reporting throughput and latency
    percentiles. Honesty discipline matches every other phase: EVERY
    resolved board is gated bit-exact against the NumPy oracle before
    the numbers are recorded, and a shed ticket must carry an explicit
    policy reason. A chaos plan (``MOMP_CHAOS``) drives the same code
    the soak test exercises: ``serve_fail`` faults surface here as
    ``serve_degraded``/``serve_retries``, a ``preempt`` plan raises
    Preempted through main()'s exit-75 contract.
    """
    import tempfile

    from mpi_and_open_mp_tpu.ops.life_ops import life_step_numpy
    from mpi_and_open_mp_tpu.serve import ServePolicy, ServingDaemon
    from mpi_and_open_mp_tpu.serve.queue import DONE

    policy = ServePolicy(max_batch=8, max_depth=max(64, 2 * n),
                         max_wait_s=0.005)

    def burst(wal_path=None, wal_fsync="every-record", aot_dir=None):
        """One seeded burst through a fresh daemon; identical request
        stream every time so the WAL-on/off and AOT-cold/warm deltas
        isolate the journal tax and the warm-start win respectively.
        With ``aot_dir`` the cache attach + preload runs INSIDE the
        timed window — a cold cache honestly pays its export builds
        where a cold daemon would pay its traces. Returns (summary,
        wall, oracle-mismatch count)."""
        shapes = ((48, 48), (64, 64))
        steps = (4, 8)
        aot = None
        t0 = time.perf_counter()
        if aot_dir is not None:
            from mpi_and_open_mp_tpu.serve.aotcache import AOTCache

            aot = AOTCache(aot_dir)
        daemon = ServingDaemon(policy, wal_path=wal_path,
                               wal_fsync=wal_fsync, aot_cache=aot)
        if aot is not None:
            aot.warm([(sh, "uint8") for sh in shapes], policy.max_batch)
        rng = np.random.default_rng(48)
        for i in range(n):
            ny, nx = shapes[i % len(shapes)]
            daemon.submit((rng.random((ny, nx)) < 0.3).astype(np.uint8),
                          steps[i % len(steps)])
        daemon.serve()  # Preempted propagates: the exit-75 contract
        wall = time.perf_counter() - t0
        s = daemon.summary()
        bad = 0
        for t in daemon.queue.tickets():
            if t.state != DONE:
                continue
            ref = np.asarray(t.board).copy()
            for _ in range(t.steps):
                ref = life_step_numpy(ref)
            if not np.array_equal(t.result, ref):
                bad += 1
        if wal_path is not None:
            daemon._wal.close()
        return s, wall, bad

    # The serve_* baseline fields stay WAL-OFF: the regression sentinel
    # trends them against pre-WAL history, which must not silently
    # absorb the durability tax. The tax gets its own serve_wal_*
    # fields from a second identical burst, journaled every-record.
    s, wall, bad = burst()
    fields = {
        "serve_daemon_requests": s["requests"],
        "serve_admitted": s["requests"] - s["shed_reasons"].get(
            "queue-depth", 0) - s["shed_reasons"].get("padding-waste", 0),
        "serve_resolved": s["resolved"],
        "serve_shed": s["shed"],
        "serve_shed_reasons": s["shed_reasons"],
        "serve_degraded": s["degraded"],
        "serve_retries": s["retries"],
        "serve_daemon_batches": s["batches"],
        "serve_daemon_engines": s["engines"],
        "serve_requests_per_sec": (round(s["resolved"] / wall, 2)
                                   if wall > 0 else None),
        "serve_p50_latency_s": s["p50_latency_s"],
        "serve_p99_latency_s": s["p99_latency_s"],
        "serve_daemon_parity": bad == 0,
    }
    if bad:
        fields["serve_daemon_error"] = (
            f"parity check failed on {bad} resolved boards")

    with tempfile.TemporaryDirectory(prefix="momp-bench-wal-") as td:
        ws, wwall, wbad = burst(wal_path=os.path.join(td, "serve.wal"))
    w = ws["wal"]
    fields.update({
        "serve_wal_fsync": w["fsync"],
        "serve_wal_records": w["records"],
        "serve_wal_bytes": w["bytes"],
        "serve_wal_syncs": w["syncs"],
        "serve_wal_fsync_s": w["sync_seconds"],
        "serve_wal_p50_latency_s": ws["p50_latency_s"],
        "serve_wal_p99_latency_s": ws["p99_latency_s"],
        # The durability tax, directly comparable: same seed, same
        # request stream, only the journal differs.
        "serve_wal_p50_delta_s": round(
            ws["p50_latency_s"] - s["p50_latency_s"], 6),
        "serve_wal_p99_delta_s": round(
            ws["p99_latency_s"] - s["p99_latency_s"], 6),
        "serve_wal_parity": wbad == 0,
    })
    if wbad:
        fields["serve_wal_error"] = (
            f"parity check failed on {wbad} resolved boards (WAL run)")

    # The warm-start win, measured the honest way: the SAME burst twice
    # over one cache directory. Burst 1 is the cold process (exports and
    # persists every bucket program inside its timed window); burst 2 is
    # the simulated restart (fresh AOTCache = fresh deserialize, like a
    # requeued daemon). cold_first_result_s is the ISSUE's headline:
    # construction -> first resolved ticket, where trace+compile lands.
    # Baseline serve_* fields above stay AOT-OFF (and WAL-OFF) so the
    # sentinel's history keys don't silently change meaning.
    with tempfile.TemporaryDirectory(prefix="momp-bench-aot-") as td:
        cs, cwall, cbad = burst(aot_dir=td)
        hs, hwall, hbad = burst(aot_dir=td)
    fields.update({
        "serve_cold_first_result_s": cs.get("cold_first_result_s"),
        "serve_aot_first_result_s": hs.get("cold_first_result_s"),
        "serve_aot_hits": hs["aot_hits"],
        "serve_aot_misses": hs["aot_misses"],
        "serve_aot_deserialize_s": hs["aot_deserialize_s"],
        "serve_aot_build_s": cs["aot_build_s"],
        "serve_aot_engines": hs["engines"],
        "serve_aot_p99_latency_s": hs["p99_latency_s"],
        "serve_aot_parity": cbad == 0 and hbad == 0,
    })
    if cbad or hbad:
        fields["serve_aot_error"] = (
            f"parity check failed on {cbad + hbad} resolved boards "
            "(AOT cold/warm runs)")
    return fields


def _fleet_phase(n: int, workers: int) -> dict:
    """The sharded-fleet phase (``--serve N --fleet W``): the same
    seeded burst twice through an in-process W-worker fleet
    (``serve.fleet.Fleet`` — consistent-hash affinity, rolled-up
    admission, per-worker WALs). Burst 1 runs clean and prices the
    aggregate serving surface (``fleet_requests_per_sec`` + tail
    latency). Burst 2 is the kill drill: the busiest worker is wedged
    mid-stream, the router must detect the missed heartbeats, replay
    the victim's journal, and re-home its pending set to the survivors
    — ``fleet_kill_recovery_s`` is wedge-to-last-re-homed-resolved, the
    tail-latency-under-kill number. Honesty discipline as everywhere:
    every resolved board (re-homed included) gates bit-exact against
    the NumPy oracle before anything is recorded, and the fleet books
    must balance (admitted == resolved + shed, re-home moves netted)."""
    import tempfile

    from mpi_and_open_mp_tpu.ops.life_ops import life_step_numpy
    from mpi_and_open_mp_tpu.serve import ServePolicy
    from mpi_and_open_mp_tpu.serve.fleet import Fleet

    policy = ServePolicy(max_batch=8, max_depth=max(64, 2 * n),
                         max_wait_s=0.005)
    shapes = ((48, 48), (64, 64))
    steps = (4, 8)
    sessions = max(4 * workers, 8)

    def burst(fleet, lo=0, hi=None):
        rng = np.random.default_rng(48)
        for i in range(n):
            ny, nx = shapes[i % len(shapes)]
            board = (rng.random((ny, nx)) < 0.3).astype(np.uint8)
            if lo <= i < (n if hi is None else hi):
                fleet.submit(board, steps[i % len(steps)],
                             session=f"s{i % sessions:04d}")

    def parity_bad(fleet) -> int:
        bad = 0
        for t in fleet.resolved_tickets():
            ref = np.asarray(t.board).copy()
            for _ in range(t.steps):
                ref = life_step_numpy(ref)
            if not np.array_equal(t.result, ref):
                bad += 1
        return bad

    fields: dict = {"fleet_workers": workers}
    with tempfile.TemporaryDirectory(prefix="momp-bench-fleet-") as td:
        fleet = Fleet(workers, policy,
                      wal_dir=os.path.join(td, "clean"),
                      heartbeat_interval_s=0.01)
        burst(fleet)
        t0 = time.perf_counter()
        fleet.serve_until_drained()
        wall = time.perf_counter() - t0
        s = fleet.summary()
        bad = parity_bad(fleet)
        fields.update({
            "fleet_requests": s["submitted"],
            "fleet_resolved": s["resolved"],
            "fleet_shed": s["shed"] + s["door_shed"],
            "fleet_steals": s["steals"],
            "fleet_requests_per_sec": (round(s["resolved"] / wall, 2)
                                       if wall > 0 else None),
            "fleet_p50_latency_s": s["p50_latency_s"],
            "fleet_p99_latency_s": s["p99_latency_s"],
            "fleet_books_balance": s["balanced"],
            "fleet_parity": bad == 0,
        })
        if bad:
            fields["fleet_error"] = (
                f"parity check failed on {bad} resolved boards")

        # The kill drill: same seed, fresh fleet; partial progress, then
        # the busiest worker stops heartbeating and the fleet must drain
        # anyway through the wedge->replay->re-home ladder.
        kfleet = Fleet(workers, policy,
                       wal_dir=os.path.join(td, "kill"),
                       heartbeat_interval_s=0.01)
        # Partial progress first (half the burst dispatched clean), then
        # the rest lands and the busiest worker wedges with a loaded
        # queue — the mid-stream death whose pending set the router must
        # recover from the victim's journal.
        burst(kfleet, hi=n // 2)
        kfleet.pump()
        burst(kfleet, lo=n // 2)
        victim = max(kfleet.handles,
                     key=lambda h: h.daemon.queue.depth()).index
        t_kill = time.monotonic()
        kfleet.wedge(victim)
        kfleet.serve_until_drained()
        ks = kfleet.summary()
        kbad = parity_bad(kfleet)
        adopted = kfleet.router.last_rehomed
        recovered_at = [t.resolved_at for t in adopted
                        if t.resolved_at is not None]
        fields.update({
            "fleet_kill_victim": victim,
            "fleet_rehomed": ks["rehomed"],
            "fleet_rehomed_resolved": ks["rehomed_resolved"],
            "fleet_kill_recovery_s": (
                round(max(recovered_at) - t_kill, 4)
                if recovered_at else None),
            "fleet_kill_books_balance": ks["balanced"],
            "fleet_kill_parity": kbad == 0,
        })
        if kbad:
            fields["fleet_kill_error"] = (
                f"parity check failed on {kbad} resolved boards "
                "(kill drill)")
    return fields


def _loadgen_phase(args) -> dict:
    """The elastic-fleet-under-load phase (``--loadgen R1,R2,..``).

    Two drills. (1) **Saturation sweep**: an open-loop Poisson arrival
    schedule (``serve.loadgen`` — arrivals are precomputed, never a
    reaction to completions, so there is no coordinated omission) over
    a mixed scenario (one-shot batch boards, resident-session steps,
    snapshot reads) at each offered rate on a FRESH fleet, judged
    against the declared SLO; ``loadgen_knee_rps`` is the last rung
    that met it — the capacity number — and the whole curve rides the
    line as ``loadgen_curve``. (2) **Membership cycle**: one run at the
    knee rate with the production failure script as scheduled events —
    wedge the busiest worker at 25% of the run, REJOIN it at 45%
    (``rejoin_recovery_s`` prices the resume-from-WAL + bounded ring
    re-entry + claim ladder), gracefully drain another at 65% — and
    the final-quartile goodput must recover to the pre-fault rate
    (``loadgen_cycle_recovery_frac``) with zero acked loss and the
    books balanced across both membership changes. Honesty discipline
    as everywhere: every resolved board gates bit-exact against the
    NumPy oracle, and every resident session's final snapshot gates
    against the oracle at its journaled step total, before anything is
    recorded."""
    import tempfile

    from mpi_and_open_mp_tpu.obs import telemetry as telemetry_mod
    from mpi_and_open_mp_tpu.ops.life_ops import life_step_numpy
    from mpi_and_open_mp_tpu.serve import (
        SLO, ElasticityPolicy, ScenarioMix, ServePolicy, run_open_loop,
        saturation_knee)
    from mpi_and_open_mp_tpu.serve.fleet import Fleet

    rates = [float(r) for r in str(args.loadgen).split(",") if r.strip()]
    workers = args.fleet or 3
    duration = args.loadgen_duration
    slo = SLO(p99_s=args.loadgen_slo_p99, goodput_frac=0.5)
    mix = ScenarioMix(batch=0.7, resident=0.25, snapshot=0.05,
                      shapes=((48, 48), (64, 64)), steps=(2, 4),
                      sessions=max(8, 2 * workers))
    policy = ServePolicy(max_batch=8, max_depth=256, max_wait_s=0.005)

    def parity_bad(fleet) -> int:
        bad = 0
        for t in fleet.resolved_tickets():
            if t.board is None:
                continue  # resident step — gated via the snapshot below
            ref = np.asarray(t.board).copy()
            for _ in range(t.steps):
                ref = life_step_numpy(ref)
            if not np.array_equal(t.result, ref):
                bad += 1
        for sid in list(fleet.router._session_home):
            home = fleet.router._home_worker(sid)
            entry = home.daemon._session_log.get(sid)
            if entry is None:
                bad += 1
                continue
            ref = np.asarray(entry["board"]).copy()
            for _ in range(int(entry["steps"])):
                ref = life_step_numpy(ref)
            if not np.array_equal(fleet.snapshot_session(sid), ref):
                bad += 1
        return bad

    fields: dict = {
        "loadgen_workers": workers,
        "loadgen_rates": rates,
        "loadgen_duration_s": duration,
        "loadgen_slo_p99_s": slo.p99_s,
        "loadgen_slo_goodput_frac": slo.goodput_frac,
    }
    with tempfile.TemporaryDirectory(prefix="momp-bench-loadgen-") as td:
        # -- (1) the saturation sweep: fresh fleet per rung ------------
        reports = []
        rollups = []
        burns = []
        bad = 0
        balanced = True
        for j, rate in enumerate(rates):
            fleet = Fleet(workers, policy,
                          wal_dir=os.path.join(td, f"rung{j}"),
                          heartbeat_interval_s=0.01,
                          telemetry_interval_s=0.02)
            rep = run_open_loop(fleet, rate, duration, mix=mix, slo=slo,
                                seed=17)
            reports.append(rep)
            rollups.append(fleet.router.telemetry)
            burns.append(fleet.burn)
            bad += parity_bad(fleet)
            balanced = balanced and rep.books["balanced"]
        knee = saturation_knee(reports)
        at_knee = next((r for r in reversed(reports) if r.slo_ok),
                       reports[0])
        kroll = rollups[reports.index(at_knee)]
        kburn = burns[reports.index(at_knee)]
        fields.update({
            "loadgen_knee_rps": knee["knee_rps"],
            "loadgen_breach_rps": knee["breach_rps"],
            "loadgen_curve": knee["points"],
            "loadgen_goodput_rps": round(at_knee.goodput_rps, 3),
            "loadgen_p50_latency_s": round(at_knee.p50_s, 6),
            "loadgen_p99_latency_s": round(at_knee.p99_s, 6),
            "loadgen_p999_latency_s": round(at_knee.p999_s, 6),
            "loadgen_shed": dict(at_knee.shed),
            "loadgen_slo_ok": bool(at_knee.slo_ok),
            "loadgen_books_balance": balanced,
            "loadgen_parity": bad == 0,
        })
        if bad:
            fields["loadgen_error"] = (
                f"parity check failed on {bad} resolved boards/sessions "
                "(saturation sweep)")

        # Telemetry plane at the knee: the fleet rollup's merged-bucket
        # quantiles must agree with the loadgen-side exact percentiles
        # within the DECLARED histogram bucket error (adjacent-bucket
        # tolerance — the acceptance gate for the shipped series), and
        # the burn-rate peak at a met SLO is the recorded headroom.
        ksum = kroll.summary() if kroll is not None else {}
        fields.update({
            "telemetry_snapshots": ksum.get("snapshots", 0),
            "telemetry_rollup_rps": ksum.get("resolved_rps", 0.0),
            "telemetry_rollup_p50_s": ksum.get("p50_s"),
            "telemetry_rollup_p99_s": ksum.get("p99_s"),
            "telemetry_rollup_p999_s": ksum.get("p999_s"),
            "telemetry_bucket_rel_err": round(
                telemetry_mod.BUCKET_REL_ERR, 6),
            "telemetry_quantile_agree": (
                kroll is not None and kroll.hist.count > 0
                and kroll.hist.agrees(kroll.quantile(50), at_knee.p50_s)
                and kroll.hist.agrees(kroll.quantile(99), at_knee.p99_s)),
            "telemetry_snapshot_loss_frac": (
                ksum.get("loss", {}).get("frac", 0.0)),
            "loadgen_burn_rate_peak": (
                kburn.summary()["burn_peak_long"]
                if kburn is not None else None),
        })

        # -- (2) the membership cycle at the knee rate -----------------
        cycle_rate = knee["knee_rps"] or rates[0]
        cfleet = Fleet(workers, policy, wal_dir=os.path.join(td, "cycle"),
                       heartbeat_interval_s=0.01,
                       telemetry_interval_s=0.02,
                       # The controller rides the cycle drill so its
                       # verdicts land as recorded telemetry decisions.
                       # Surplus is unreachable (p99 < 0 never holds), so
                       # the controller can only ADD — the drill's single
                       # scripted drain stays the only drain on the books.
                       elasticity=ElasticityPolicy(
                           slo_p99_s=slo.p99_s,
                           slo_goodput_frac=slo.goodput_frac,
                           min_workers=1, max_workers=workers + 2,
                           surplus_p99_frac=0.0))
        drill: dict = {}

        def ev_wedge(fl):
            h = max((w for w in fl.handles
                     if not (w.wedged or w.drained)),
                    key=lambda w: w.daemon.queue.depth())
            drill["victim"] = h.index
            fl.wedge(h.index)

        def ev_rejoin(fl):
            idx = drill["victim"]
            deadline = time.monotonic() + 10.0
            while idx not in fl.router.wedged_workers:
                fl.pump()
                time.sleep(fl.router.heartbeat_interval_s)
                if time.monotonic() > deadline:
                    raise RuntimeError(
                        f"cycle victim {idx} never declared wedged")
            t0 = time.perf_counter()
            drill["claimed"] = fl.rejoin_worker(idx)
            drill["rejoin_s"] = time.perf_counter() - t0

        def ev_drain(fl):
            live = [w for w in fl.handles
                    if not (w.wedged or w.drained or w.halted)
                    and w.index != drill["victim"]]
            h = max(live, key=lambda w: w.daemon.queue.depth())
            drill["drained"] = h.index
            fl.drain_worker(h.index)

        crep = run_open_loop(
            cfleet, cycle_rate, duration, mix=mix, slo=slo, seed=23,
            events=[(0.25, ev_wedge), (0.45, ev_rejoin),
                    (0.65, ev_drain)])
        cbad = parity_bad(cfleet)
        cs = cfleet.summary()
        # Goodput recovery: resolved-per-second in the pre-fault first
        # quartile vs the post-drain final quartile of the offered
        # window (plus the drain tail for the last requests' results).
        # Anchored on the first submission stamp — the run's own clock
        # zero, after the up-front session creates' compile time.
        done = [t for t in cfleet.resolved_tickets()
                if t.resolved_at is not None]
        t0 = min((t.submitted_at for t in done), default=0.0)
        t_end = max((t.resolved_at for t in done), default=t0)
        pre = [t for t in done if t.resolved_at - t0 < 0.25 * duration]
        post = [t for t in done
                if t.resolved_at - t0 >= 0.75 * duration]
        pre_rate = len(pre) / (0.25 * duration)
        post_win = max(t_end - t0 - 0.75 * duration, 1e-9)
        post_rate = len(post) / post_win
        recovery = post_rate / pre_rate if pre_rate > 0 else None
        zero_loss = (cs["balanced"] and cs["pending"] == 0
                     and cs["in_transit"] == 0)
        fields.update({
            "loadgen_cycle_rate_rps": round(cycle_rate, 3),
            "loadgen_cycle_victim": drill.get("victim"),
            "loadgen_cycle_claimed": drill.get("claimed"),
            "loadgen_cycle_drained": drill.get("drained"),
            "rejoin_recovery_s": (round(drill["rejoin_s"], 4)
                                  if "rejoin_s" in drill else None),
            "loadgen_cycle_goodput_rps": round(crep.goodput_rps, 3),
            "loadgen_cycle_recovery_frac": (round(recovery, 3)
                                            if recovery is not None
                                            else None),
            "loadgen_cycle_rejoins": cs["rejoins"],
            "loadgen_cycle_drains": cs["drains"],
            "loadgen_cycle_zero_acked_loss": zero_loss,
            "loadgen_cycle_books_balance": cs["balanced"],
            "loadgen_cycle_parity": cbad == 0,
            "loadgen_cycle_ok": (
                zero_loss and cbad == 0
                and cs["rejoins"] == 1 and cs["drains"] == 1
                and recovery is not None and recovery >= 0.9),
        })
        if cbad:
            fields["loadgen_cycle_error"] = (
                f"parity check failed on {cbad} resolved "
                "boards/sessions (membership cycle)")

        # The cycle drill's telemetry record: every controller verdict
        # carries the burn-rate window values that triggered it, the
        # wedge shows up as burn alerts, and the surviving workers lose
        # ZERO snapshots (the drain flush ships every last interval).
        csum = cfleet.router.telemetry.summary()
        fields.update({
            "telemetry_cycle_snapshots": csum["snapshots"],
            "telemetry_cycle_loss_frac": csum["loss"]["frac"],
            "telemetry_cycle_burn_alerts": (
                cfleet.burn.summary()["burn_alerts"]
                if cfleet.burn is not None else 0),
            "telemetry_cycle_burn_peak": (
                cfleet.burn.summary()["burn_peak_short"]
                if cfleet.burn is not None else 0.0),
            "telemetry_decisions": len(cfleet.decisions),
            "loadgen_cycle_decisions": cfleet.decisions,
            "telemetry_decisions_have_windows": all(
                "burn_short" in d and "burn_long" in d
                for d in cfleet.decisions),
        })
    return fields


def _sessions_phase(s: int) -> dict:
    """The resident-session phase (``--sessions S``): the device-resident
    A/B that prices what the session pool exists for. Side A (resident):
    S sessions created once into the daemon's ``serve.pool`` — boards
    cross the wire at create, then ``rounds`` rounds of one 4-step
    resident step per session, each round one in-place donated dispatch
    per slab, results never shipped back. Side B (ship): the identical
    workload through the plain ticket path — every round re-ships every
    board to the daemon and fetches the stepped board back, the
    per-request round trip the reference workflow (and PR 5-11 serving)
    always paid. Same seed, same boards, same total Life steps; only the
    residency discipline differs, so ``session_vs_ship`` is an RTT- and
    machine-noise-cancelled ratio (like ``vs_cellpacked``). Honesty
    gate: every final session snapshot must be bit-exact against the
    NumPy oracle advanced ``rounds * steps`` from the seed board before
    any number is recorded. Session creation happens OUTSIDE the timed
    bracket — the phase prices steady-state resident stepping, and the
    one-time create cost is exactly what the ship side pays per round.
    """
    from mpi_and_open_mp_tpu.ops.life_ops import life_step_numpy
    from mpi_and_open_mp_tpu.serve import ServePolicy, ServingDaemon
    from mpi_and_open_mp_tpu.serve.queue import DONE

    shape = (48, 48)
    steps_per_round = 4
    rounds = 8
    policy = ServePolicy(max_batch=8, max_depth=max(64, 4 * s),
                         max_wait_s=0.0)
    rng = np.random.default_rng(48)
    boards0 = {f"sess{i:04d}": (rng.random(shape) < 0.3).astype(np.uint8)
               for i in range(s)}

    # Side A: resident. Creates ship each board once; the timed bracket
    # is pure resident stepping (handle-based submits, in-place slab
    # dispatches, zero result traffic).
    daemon = ServingDaemon(policy)
    for sid, b in boards0.items():
        daemon.create_session(sid, b)
    res_tickets = []
    t0 = time.perf_counter()
    for _ in range(rounds):
        for sid in boards0:
            res_tickets.append(daemon.submit_session(sid, steps_per_round))
        daemon.pump(drain=True)
    res_wall = time.perf_counter() - t0
    res_done = sum(1 for t in res_tickets if t.state == DONE)
    rs = daemon.summary()

    bad = 0
    for sid, b in boards0.items():
        ref = b.copy()
        for _ in range(rounds * steps_per_round):
            ref = life_step_numpy(ref)
        if not np.array_equal(daemon.snapshot_session(sid), ref):
            bad += 1

    # Side B: ship-every-call. The same boards advance the same total
    # steps, but each round round-trips every board through the ticket
    # path (host -> queue -> stacked dispatch -> host), chained so round
    # k+1 ships what round k fetched — the honest no-pool workflow.
    ship = ServingDaemon(policy)
    cur = {sid: b.copy() for sid, b in boards0.items()}
    ship_done = 0
    t0 = time.perf_counter()
    for _ in range(rounds):
        tks = {sid: ship.submit(cur[sid], steps_per_round) for sid in cur}
        ship.pump(drain=True)
        for sid, t in tks.items():
            if t.state == DONE:
                ship_done += 1
                cur[sid] = np.asarray(t.result)
    ship_wall = time.perf_counter() - t0

    res_rate = round(res_done / res_wall, 2) if res_wall > 0 else None
    ship_rate = round(ship_done / ship_wall, 2) if ship_wall > 0 else None
    fields = {
        "resident": "pool",
        "session_count": s,
        "session_rounds": rounds,
        "session_steps_per_round": steps_per_round,
        "session_requests": res_done,
        "session_requests_per_sec": res_rate,
        "ship_requests_per_sec": ship_rate,
        "session_vs_ship": (round(res_rate / ship_rate, 2)
                            if res_rate and ship_rate else None),
        "session_p50_latency_s": rs["p50_latency_s"],
        "session_p99_latency_s": rs["p99_latency_s"],
        "session_dispatches": rs["batches"],
        "pool_sessions": rs["pool_sessions"],
        "pool_hits": rs["pool_hits"],
        "pool_misses": rs["pool_misses"],
        "pool_evictions": rs["pool_evictions"],
        "pool_spills": rs["pool_spills"],
        "pool_compactions": rs["pool_compactions"],
        "session_parity": bad == 0,
    }
    if bad:
        fields["session_error"] = (
            f"snapshot parity failed on {bad} of {s} sessions")
    return fields


def _sparse_seed_board(edge: int, tile: int) -> np.ndarray:
    """The sparse A/B's mostly-dead Life board: blinkers parked in tile
    INTERIORS on a coarse deterministic grid (each keeps its own tile
    active and — via the border-band check — none of its neighbours)
    plus one glider crossing tile boundaries (the pattern that forces
    honest wake-up propagation). Active tile fraction stays well under
    5% at the default 2048/64 geometry."""
    board = np.zeros((edge, edge), dtype=np.uint8)
    ty = edge // tile
    stride = max(3, ty // 3)
    placed = 0
    for j in range(1, ty, stride):
        for i in range(1, ty, stride):
            if placed >= 10:
                break
            cy, cx = j * tile + tile // 2, i * tile + tile // 2
            board[cy, cx - 1:cx + 2] = 1  # horizontal blinker
            placed += 1
    # Glider aimed across tile edges, offset so it never collides with
    # the blinker grid (placed just off the (0, 0) tile's corner).
    gy, gx = tile - 2, tile - 2
    glider = np.array([[0, 1, 0], [0, 0, 1], [1, 1, 1]], dtype=np.uint8)
    board[gy:gy + 3, gx:gx + 3] = glider
    return board


def _sparse_ab_phase(n_steps: int, edge: int, tile: int) -> dict:
    """The sparse active-tile A/B (``--sparse-ab K``): K Life steps of a
    mostly-dead ``edge``² board through ``stencils.sparse.
    ActiveTileEngine`` versus the dense jitted roll engine. Honesty
    discipline matches the headline: the dense engine is parity-gated
    against the NumPy oracle first (8 steps), the sparse final board
    must be bit-identical to the dense final board over the FULL run,
    and both rates are chain-differenced — two run lengths (K and 2K)
    from fresh state, so compile/warm cost cancels on each side. The
    ratio ``sparse_vs_dense`` is measured in one process, so machine
    noise cancels like ``vs_cellpacked``."""
    from mpi_and_open_mp_tpu import stencils
    from mpi_and_open_mp_tpu.stencils.sparse import ActiveTileEngine
    from mpi_and_open_mp_tpu.utils.timing import anchor_sync

    spec = stencils.get("life")
    board = _sparse_seed_board(edge, tile)
    fields = {"sparse_board": edge, "sparse_steps": n_steps,
              "sparse_tile": tile}

    # Oracle gate on the dense side (the sparse side then gates against
    # dense over the full run — transitively oracle-exact).
    got8 = np.asarray(stencils.run_roll(spec, board, 8))
    ref8 = stencils.oracle_run(spec, board, 8)
    if not np.array_equal(got8, ref8):
        fields["sparse_error"] = "dense roll engine failed oracle parity"
        return fields

    def dense_timed(n):
        t0 = time.perf_counter()
        anchor_sync(stencils.run_roll(spec, board, n), fetch_all=True)
        return time.perf_counter() - t0

    # Warm (n is a runtime scalar: one compile covers both lengths).
    anchor_sync(stencils.run_roll(spec, board, n_steps), fetch_all=True)
    d1 = min(dense_timed(n_steps) for _ in range(2))
    d2 = min(dense_timed(2 * n_steps) for _ in range(2))
    dense_per_step = (d2 - d1) / n_steps if d2 > d1 else d1 / n_steps

    def sparse_run(n):
        eng = ActiveTileEngine(spec, board, tile=tile)
        t0 = time.perf_counter()
        out = eng.step(n)
        dt = time.perf_counter() - t0
        return eng, out, dt

    eng1, _, s1 = sparse_run(n_steps)
    eng2, sparse_final, s2 = sparse_run(2 * n_steps)
    sparse_per_step = (s2 - s1) / n_steps if s2 > s1 else s1 / n_steps

    dense_final = np.asarray(stencils.run_roll(spec, board, 2 * n_steps))
    parity = np.array_equal(sparse_final, dense_final)
    fields.update({
        "sparse_parity": parity,
        "sparse_cups": round(edge * edge / sparse_per_step, 1),
        "dense_cups": round(edge * edge / dense_per_step, 1),
        "sparse_vs_dense": round(dense_per_step / sparse_per_step, 2),
        "active_frac": round(eng2.mean_active_frac, 6),
        "sparse_engine": eng2.engine_stamp,
        "sparse_counters": eng2.counters(),
    })
    if not parity:
        fields["sparse_error"] = (
            "sparse final board diverged from the dense engine")
    return fields


def _sharded_ab_phase(args, workload: str) -> dict:
    """The SHARDED HALO A/B (``--sharded-ab K``): K torus steps of a
    ``--sharded-board``² board through the plan-scheduled sharded engine
    (``stencils.engine``), overlap schedule versus forced-sequential
    baseline over the SAME mesh. Honesty discipline matches the sparse
    A/B: the overlap leg is oracle-parity-gated first (8 steps), the seq
    leg must match it bit-exactly, both rates are chain-differenced (K
    and 2K from warm executables, min-of-2), and the two full-run final
    boards must be BIT-identical — the overlap split computes every cell
    with the same arithmetic, only the iteration space is partitioned.
    The exposed-vs-hidden accounting rides a separate exchange-only
    microbench: ``transfer_s`` prices the ghost ppermutes alone per
    round, ``exposed_s`` is the remainder the overlap failed to hide
    behind interior compute, and their ratio is the overlap efficiency
    (``halo.ab`` trace event + the same fields on the line). The
    ``sharded_halo`` stamp is what the overlap leg actually resolved to
    (``overlap:*``, or ``seq:*`` when the ``MOMP_HALO_OVERLAP=0`` kill
    switch or a degenerate geometry downgraded it — the ledger keys on
    it and the sentinel treats that downgrade as a failure)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding

    from mpi_and_open_mp_tpu import stencils
    from mpi_and_open_mp_tpu.obs import trace as obs_trace
    from mpi_and_open_mp_tpu.parallel import haloplan, mesh as mesh_lib
    from mpi_and_open_mp_tpu.stencils import engine as stencil_engine
    from mpi_and_open_mp_tpu.utils.timing import anchor_sync

    n_steps, edge = args.sharded_ab, args.sharded_board
    spec = stencils.get(workload)
    fields = {"sharded_ab_board": edge, "sharded_ab_steps": n_steps}
    if jax.device_count() < 2:
        fields["sharded_ab_error"] = (
            "needs >= 2 devices (the halo exchange engages from 2 "
            "shards); CI runs it under the 8-virtual-device CPU mesh")
        return fields
    mesh = mesh_lib.make_mesh_1d()  # every device on y: row layout
    py = mesh.shape.get("y", 1)
    if edge % py:
        fields["sharded_ab_error"] = (
            f"--sharded-board {edge} does not divide the {py}-way mesh")
        return fields

    rng = np.random.default_rng(46)
    board = spec.init(rng, (edge, edge))

    # Oracle gate on the overlap leg (8 steps, emits the halo.overlap
    # span), then the seq leg (halo.seq span) must match it bit-exactly
    # — transitively oracle-exact. Both schedule stamps ride the line.
    got8 = np.asarray(stencil_engine.run_sharded(
        spec, board, 8, mesh=mesh, layout="row"))
    plan_ovl = stencil_engine.run_sharded.last_plan
    fields["sharded_halo"] = plan_ovl.engine
    if not stencils.parity_ok(spec, got8,
                              stencils.oracle_run(spec, board, 8)):
        fields["sharded_ab_error"] = (
            "overlap schedule failed oracle parity")
        return fields
    seq8 = np.asarray(stencil_engine.run_sharded(
        spec, board, 8, mesh=mesh, layout="row", overlap=False))
    fields["sharded_seq_halo"] = stencil_engine.run_sharded.last_plan.engine
    if not np.array_equal(got8, seq8):
        fields["sharded_ab_error"] = (
            "overlap and sequential schedules diverged at 8 steps")
        return fields

    run_ovl, _ = stencil_engine.make_sharded_runner(
        spec, mesh, "row", (edge, edge))
    run_seq, _ = stencil_engine.make_sharded_runner(
        spec, mesh, "row", (edge, edge), overlap=False)
    pspec = stencil_engine._sharded_pspec("row", spec.channels)
    dev_board = jax.device_put(jnp.asarray(board, spec.dtype),
                               NamedSharding(mesh, pspec))

    def timed(run, n):
        t0 = time.perf_counter()
        anchor_sync(run(dev_board, n), fetch_all=True)
        return time.perf_counter() - t0

    def per_step(run):
        # run() jit-caches per STATIC n: warm both lengths outside the
        # brackets (the 2K warm-up doubles as the full-run final), then
        # chain-difference so the per-dispatch overhead cancels.
        anchor_sync(run(dev_board, n_steps), fetch_all=True)
        final = run(dev_board, 2 * n_steps)
        anchor_sync(final, fetch_all=True)
        t1 = min(timed(run, n_steps) for _ in range(2))
        t2 = min(timed(run, 2 * n_steps) for _ in range(2))
        return ((t2 - t1) / n_steps if t2 > t1 else t1 / n_steps,
                np.asarray(final), t2 > t1)

    ovl_step, ovl_final, ovl_diff = per_step(run_ovl)
    seq_step, seq_final, seq_diff = per_step(run_seq)
    parity = np.array_equal(ovl_final, seq_final)
    cells = edge * edge
    fields.update({
        "sharded_ab_parity": parity,
        "sharded_overlap_cups": round(cells / ovl_step, 1),
        "sharded_seq_cups": round(cells / seq_step, 1),
        "vs_sequential": round(seq_step / ovl_step, 3),
        "sharded_ab_is_differenced": ovl_diff and seq_diff,
    })
    if not parity:
        fields["sharded_ab_error"] = (
            "overlap final board diverged from the sequential schedule")
        return fields

    # Exchange-only microbench: the ghost ppermutes with no stencil
    # behind them, same chained-differencing bracket. The concat keeps
    # the collectives live in the loop (an unused ppermute is dead code
    # XLA may elide); values shift per round, which is irrelevant — this
    # is a pure timing probe on the production ghost shapes.
    depth = plan_ovl.depth

    def exch(block):
        top, bot = haloplan.ghosts_y(block, depth)
        return jnp.concatenate(
            [bot, block[..., depth:-depth, :], top], axis=-2)

    smapped = jax.shard_map(exch, mesh=mesh, in_specs=pspec,
                            out_specs=pspec, check_vma=False)

    @jax.jit
    def exch_n(b, n):
        return lax.fori_loop(0, n, lambda _, c: smapped(c), b)

    def exch_timed(n):
        t0 = time.perf_counter()
        anchor_sync(exch_n(dev_board, jnp.int32(n)), fetch_all=True)
        return time.perf_counter() - t0

    anchor_sync(exch_n(dev_board, jnp.int32(n_steps)), fetch_all=True)
    x1 = min(exch_timed(n_steps) for _ in range(2))
    x2 = min(exch_timed(2 * n_steps) for _ in range(2))
    transfer_s = (x2 - x1) / n_steps if x2 > x1 else x1 / n_steps

    # hidden = the seconds the overlap actually saved per round;
    # exposed = the transfer remainder still on the critical path
    # (clamped to the transfer itself: an overlap leg slower than seq
    # exposed the whole exchange, not more than it).
    hidden_s = max(0.0, seq_step - ovl_step)
    exposed_s = min(transfer_s, max(0.0, transfer_s - hidden_s))
    efficiency = (min(1.0, hidden_s / transfer_s)
                  if transfer_s > 0 else 0.0)
    fields.update({
        "sharded_transfer_s": round(transfer_s, 8),
        "sharded_exposed_s": round(exposed_s, 8),
        "sharded_overlap_efficiency": round(efficiency, 4),
    })
    obs_trace.event("halo.ab", workload=spec.name, board=edge,
                    halo=plan_ovl.engine,
                    transfer_s=round(transfer_s, 8),
                    exposed_s=round(exposed_s, 8),
                    efficiency=round(efficiency, 4),
                    vs_sequential=fields["vs_sequential"])

    # PARTITIONED-BOUNDARY sweep (PR 18): the same spec through every
    # layout the transport supports — row, col (x-mirror), cart (two-
    # phase corners) — with the boundary split one step per sub-
    # exchange (fuse=2, boundary=1, the ``:pb1`` stamps). Each leg is
    # parity-gated against the 8-step oracle and required bit-identical
    # to its own forced-sequential coupled twin: partitioning moves
    # signalling, never arithmetic. The row leg also gets a chain-
    # differenced rate against the coupled fuse=2 schedule so the split
    # is priced, not just proven.
    fuse, bs = 2, 1
    engines: dict = {}
    boundary_ok = True
    for lay in ("row", "col", "cart"):
        bmesh = (mesh if lay == "row"
                 else mesh_lib.make_mesh_1d(axis=mesh_lib.AXIS_X)
                 if lay == "col" else mesh_lib.make_mesh_2d())
        bpy, bpx = stencil_engine.mesh_axes_for(lay, bmesh)
        if edge % bpy or edge % bpx:
            engines[lay] = f"skipped: {edge} % ({bpy},{bpx})"
            continue
        got = np.asarray(stencil_engine.run_sharded(
            spec, board, 8, mesh=bmesh, layout=lay, fuse_steps=fuse,
            boundary_steps=bs))
        engines[lay] = stencil_engine.run_sharded.last_plan.engine
        seq = np.asarray(stencil_engine.run_sharded(
            spec, board, 8, mesh=bmesh, layout=lay, fuse_steps=fuse,
            overlap=False))
        if not (np.array_equal(got, seq) and stencils.parity_ok(
                spec, got, stencils.oracle_run(spec, board, 8))):
            boundary_ok = False
            engines[lay] += " PARITY-FAIL"
    fields.update({
        "sharded_boundary_fuse": fuse,
        "sharded_boundary_depth": bs,
        "sharded_boundary_engines": engines,
        "sharded_boundary_parity": boundary_ok,
    })
    if not boundary_ok:
        fields["sharded_ab_error"] = (
            "partitioned-boundary sweep diverged: "
            + json.dumps(engines))
        return fields

    run_pb, _ = stencil_engine.make_sharded_runner(
        spec, mesh, "row", (edge, edge), fuse_steps=fuse,
        boundary_steps=bs)
    run_cpl, _ = stencil_engine.make_sharded_runner(
        spec, mesh, "row", (edge, edge), fuse_steps=fuse)
    pb_step, pb_final, _ = per_step(run_pb)
    cpl_step, cpl_final, _ = per_step(run_cpl)
    fields.update({
        "sharded_boundary_cups": round(cells / pb_step, 1),
        "sharded_boundary_vs_coupled": round(cpl_step / pb_step, 3),
    })
    if not np.array_equal(pb_final, cpl_final):
        fields["sharded_ab_error"] = (
            "partitioned-boundary full run diverged from the coupled "
            "schedule")
    return fields


def _ring_ab_phase(args) -> dict:
    """``_ring_ab_measure`` behind a hop-span opt-out. With a trace sink
    live, ``ring_attention`` reroutes to the hop-by-hop telemetry
    dispatch (``trace.hop_spans_active``): p-1 host-anchored hops — a
    host RTT per hop that would swamp the A/B, and a forward with no
    grad path (the per-hop re-plan differentiates through a bare
    ``pallas_call``, which JVP rejects). The A/B must price the
    production fused dispatch, so the phase pins ``MOMP_TRACE_HOPS=0``
    for its duration; whole-call spans and the ``ring.ab`` event still
    land in the trace."""
    prev = os.environ.get("MOMP_TRACE_HOPS")
    os.environ["MOMP_TRACE_HOPS"] = "0"
    try:
        return _ring_ab_measure(args)
    finally:
        if prev is None:
            os.environ.pop("MOMP_TRACE_HOPS", None)
        else:
            os.environ["MOMP_TRACE_HOPS"] = prev


def _ring_ab_measure(args) -> dict:
    """The RING-ATTENTION HOP-PREFETCH A/B (``--ring-ab R``): R causal
    ring-attention trips over the full device mesh with the double-slot
    K/V hop prefetch engaged (``context._RING_PREFETCH``, ``:pf``
    stamps) versus the single-slot schedule it deepens, on the SAME
    operands. Honesty discipline mirrors ``_sharded_ab_phase``: the
    prefetch leg is dense-oracle parity-gated first, the single-slot
    leg must match it bit-exactly (same folds in the same order — only
    the rotation issue points move), gradients are cross-checked the
    same way, and both rates are chain-differenced (R and 2R calls from
    warm executables, min-of-2). The exposed-vs-hidden accounting rides
    a rotation-only microbench: ``ring_transfer_s`` prices the p-1 K/V
    ppermutes of one trip with no kernel behind them, the single-slot
    baseline is charged the whole transfer (it is the baseline the
    hiding is measured against, exactly like the sharded A/B's forced-
    sequential leg), and ``ring_exposed_s`` is the remainder the
    prefetch failed to hide. The ``ring_hop_engine``/``_bwd`` stamps
    are what the prefetch leg actually dispatched (``…:pf``, or the
    bare kernel stamp when ``MOMP_RING_PREFETCH=0`` downgraded it —
    the sentinel fails that rerun as a provenance downgrade)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from mpi_and_open_mp_tpu.obs import trace as obs_trace
    from mpi_and_open_mp_tpu.parallel import context, mesh as mesh_lib
    from mpi_and_open_mp_tpu.parallel.halo import ring_perm
    from mpi_and_open_mp_tpu.utils.timing import anchor_sync

    n_calls = args.ring_ab
    p = jax.device_count()
    fields = {"ring_ab_calls": n_calls, "ring_ab_devices": p}
    if p < 3:
        fields["ring_ab_error"] = (
            "needs >= 3 devices (a 2-device ring has a single transfer "
            "— nothing to pipeline deeper); CI runs it under the "
            "8-virtual-device CPU mesh")
        return fields

    # 128-token shards at an MXU-width head dim: the one hop shape the
    # interpret-mode kernel takes (block == n_local), so the SAME phase
    # exercises the real hopflash prefetch on the CPU CI mesh
    # (MOMP_PALLAS_INTERPRET=1) and on chip.
    h, d, nl = 4, 128, 128
    n = nl * p
    fields["ring_ab_shape"] = [h, n, d]
    axis = context.AXIS_SP
    mesh = mesh_lib.make_mesh_1d(axis=axis)

    stamp = context.ring_hop_engine_for(
        jax.ShapeDtypeStruct((h, n, d), jnp.float32),
        jax.ShapeDtypeStruct((h, n, d), jnp.float32),
        jax.ShapeDtypeStruct((h, n, d), jnp.float32), p=p, causal=True)
    fields["ring_hop_engine"] = stamp
    fields["ring_hop_engine_bwd"] = context.ring_hop_bwd_engine_for(
        jax.ShapeDtypeStruct((h, n, d), jnp.float32),
        jax.ShapeDtypeStruct((h, n, d), jnp.float32),
        jax.ShapeDtypeStruct((h, n, d), jnp.float32), p=p, causal=True)
    if not stamp.endswith(":pf"):
        fields["ring_ab_error"] = (
            f"hop prefetch not engaged (stamp {stamp}): the A/B needs "
            "the Pallas hop engine (TPU backend, or "
            "MOMP_PALLAS_INTERPRET=1 with 128-token shards) and "
            "MOMP_RING_PREFETCH unset")
        return fields

    rng = np.random.default_rng(48)
    q, k, v = (jnp.asarray(rng.standard_normal((h, n, d)), jnp.float32)
               for _ in range(3))

    def ring(q_, k_, v_):
        return context.ring_attention(q_, k_, v_, mesh=mesh, axis=axis,
                                      causal=True)

    @jax.jit
    def chain(q_, k_, v_, r):
        # Output feeds the next call's queries so the chain can't be
        # elided; K/V are re-rotated around the ring every link.
        return lax.fori_loop(0, r, lambda _, c: ring(c, k_, v_), q_)

    def grads(q_, k_, v_):
        def loss(a, b, c):
            return (ring(a, b, c).astype(jnp.float32) ** 2).sum()

        return jax.grad(loss, argnums=(0, 1, 2))(q_, k_, v_)

    def timed(call):
        best = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            anchor_sync(call(), fetch_all=True)
            best = min(best, time.perf_counter() - t0)
        return best

    def leg():
        fwd = np.asarray(ring(q, k, v))
        g = [np.asarray(x) for x in grads(q, k, v)]
        anchor_sync(chain(q, k, v, jnp.int32(n_calls)), fetch_all=True)
        anchor_sync(chain(q, k, v, jnp.int32(2 * n_calls)),
                    fetch_all=True)
        t1 = timed(lambda: chain(q, k, v, jnp.int32(n_calls)))
        t2 = timed(lambda: chain(q, k, v, jnp.int32(2 * n_calls)))
        per_call = (t2 - t1) / n_calls if t2 > t1 else t1 / n_calls
        return fwd, g, per_call, t2 > t1

    # Parity gate BEFORE any recorded timing: the prefetch leg against
    # the dense oracle, then the single-slot leg bit-identical to it
    # (forward) and matching on gradients. The kill switch is a
    # trace-time flag, so each flip clears the jit caches (same
    # discipline as the MOMP_RING_HOP tests).
    pf_fwd, pf_g, pf_call, pf_diff = leg()
    want = np.asarray(context.attention_reference(q, k, v, causal=True))
    if not np.allclose(pf_fwd, want, rtol=1e-4, atol=1e-4):
        fields["ring_ab_error"] = "prefetch leg failed oracle parity"
        return fields
    prev_pf = context._RING_PREFETCH
    try:
        context._RING_PREFETCH = False
        jax.clear_caches()
        fields["ring_nopf_engine"] = context.ring_hop_engine_for(
            jax.ShapeDtypeStruct((h, n, d), jnp.float32),
            jax.ShapeDtypeStruct((h, n, d), jnp.float32),
            jax.ShapeDtypeStruct((h, n, d), jnp.float32), p=p,
            causal=True)
        nopf_fwd, nopf_g, nopf_call, nopf_diff = leg()
    finally:
        context._RING_PREFETCH = prev_pf
        jax.clear_caches()
    parity = np.array_equal(pf_fwd, nopf_fwd)
    grad_parity = all(
        np.allclose(a, b, rtol=1e-6, atol=1e-6)
        for a, b in zip(pf_g, nopf_g))
    flops = 2 * h * n * n * d  # QK^T + PV, causal half
    fields.update({
        "ring_ab_parity": parity,
        "ring_ab_grad_parity": grad_parity,
        "ring_prefetch_sec": round(pf_call, 6),
        "ring_prefetch_tflops": round(flops / pf_call / 1e12, 4),
        "ring_nopf_sec": round(nopf_call, 6),
        "ring_nopf_tflops": round(flops / nopf_call / 1e12, 4),
        "ring_vs_nopf": round(nopf_call / pf_call, 3),
        "ring_ab_is_differenced": pf_diff and nopf_diff,
    })
    if not parity:
        fields["ring_ab_error"] = (
            "prefetch forward diverged from the single-slot schedule")
        return fields
    if not grad_parity:
        fields["ring_ab_error"] = (
            "prefetch gradients diverged from the single-slot schedule")
        return fields

    # Rotation-only microbench: the p-1 K/V ppermutes of one ring trip
    # with no kernel behind them, same chained-differencing bracket.
    # The tuple carry keeps the collectives live in the loop.
    spec = context._seq_spec(axis)
    sharding = jax.sharding.NamedSharding(mesh, spec)
    kd = jax.device_put(k, sharding)
    vd = jax.device_put(v, sharding)

    def rot(kb, vb):
        perm = ring_perm(p, 1)
        return (lax.ppermute(kb, axis, perm),
                lax.ppermute(vb, axis, perm))

    smapped = jax.shard_map(rot, mesh=mesh, in_specs=(spec, spec),
                            out_specs=(spec, spec), check_vma=False)

    @jax.jit
    def rot_n(kb, vb, r):
        return lax.fori_loop(0, r, lambda _, c: smapped(*c), (kb, vb))

    def rot_timed(r):
        t0 = time.perf_counter()
        anchor_sync(rot_n(kd, vd, jnp.int32(r)), fetch_all=True)
        return time.perf_counter() - t0

    hops = (p - 1) * n_calls
    anchor_sync(rot_n(kd, vd, jnp.int32(hops)), fetch_all=True)
    x1 = min(rot_timed(hops) for _ in range(2))
    x2 = min(rot_timed(2 * hops) for _ in range(2))
    per_rot = (x2 - x1) / hops if x2 > x1 else x1 / hops
    transfer_s = per_rot * (p - 1)

    # hidden = the seconds the deeper pipeline actually saved per trip;
    # exposed = the transfer remainder still on the critical path
    # (clamped to the transfer itself). The single-slot baseline is
    # charged the full transfer by the same accounting the sharded A/B
    # charges its forced-sequential leg.
    hidden_s = max(0.0, nopf_call - pf_call)
    exposed_s = min(transfer_s, max(0.0, transfer_s - hidden_s))
    efficiency = (min(1.0, hidden_s / transfer_s)
                  if transfer_s > 0 else 0.0)
    fields.update({
        "ring_transfer_s": round(transfer_s, 8),
        "ring_exposed_s": round(exposed_s, 8),
        "ring_exposed_nopf_s": round(transfer_s, 8),
        "ring_prefetch_efficiency": round(efficiency, 4),
    })
    obs_trace.event("ring.ab", devices=p, shape=[h, n, d],
                    engine=stamp,
                    transfer_s=round(transfer_s, 8),
                    exposed_s=round(exposed_s, 8),
                    efficiency=round(efficiency, 4),
                    vs_nopf=fields["ring_vs_nopf"])
    return fields


def _sparse_sharded_ab_phase(args) -> dict:
    """The SPARSE x SHARDED A/B (``--sparse-sharded-ab K``): K Life
    steps of the mostly-dead ``--sparse-board``² seed board through
    ``stencils.sparse_sharded.SparseShardedEngine`` on the row mesh,
    versus (a) the dense sharded runner on the SAME mesh and (b) the
    single-device ``ActiveTileEngine`` — the composition this engine
    exists for, measured against both parents. Honesty discipline is
    the union of the parents': the sparse-sharded leg is oracle-parity-
    gated first (8 steps), its full-run final board must be
    BIT-identical to the dense sharded schedule's, every leg is
    chain-differenced (K and 2K) from warm state with min-of-2
    brackets, and fresh engines open every host-driven bracket (mask
    state is the engine — reuse would grade a warmer mask). The
    ``sparse_sharded_engine`` stamp is what the run resolved to
    (``sparse-sharded:row:t<tile>``, or ``dense:*`` when the crossover
    or the ``MOMP_SPARSE_SHARDED=0`` kill switch forced dense rounds —
    the ledger keys on it and the sentinel fails the downgrade), and
    the exchange_rounds/exchange_skips counters ride the line so a
    recorded win shows how many rounds shipped no ghost payload."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from mpi_and_open_mp_tpu import stencils
    from mpi_and_open_mp_tpu.parallel import mesh as mesh_lib
    from mpi_and_open_mp_tpu.stencils import engine as stencil_engine
    from mpi_and_open_mp_tpu.stencils.sparse import ActiveTileEngine
    from mpi_and_open_mp_tpu.stencils.sparse_sharded import (
        SparseShardedEngine)
    from mpi_and_open_mp_tpu.utils.timing import anchor_sync

    n_steps, edge, tile = (args.sparse_sharded_ab, args.sparse_board,
                           args.sparse_tile)
    spec = stencils.get("life")
    fields = {"sparse_sharded_board": edge,
              "sparse_sharded_steps": n_steps,
              "sparse_sharded_tile": tile}
    if jax.device_count() < 2:
        fields["sparse_sharded_error"] = (
            "needs >= 2 devices (cross-shard activation engages from 2 "
            "shards); CI runs it under the 8-virtual-device CPU mesh")
        return fields
    mesh = mesh_lib.make_mesh_1d()  # every device on y: row layout
    py = mesh.shape.get("y", 1)
    if edge % py or (edge // py) % tile:
        fields["sparse_sharded_error"] = (
            f"--sparse-board {edge} does not tile the {py}-way mesh "
            f"at --sparse-tile {tile}")
        return fields
    board = _sparse_seed_board(edge, tile)

    def fresh():
        return SparseShardedEngine(spec, board, mesh=mesh, layout="row",
                                   tile=tile)

    # Oracle gate on the sparse-sharded leg (8 steps), before any
    # number is recorded.
    eng8 = fresh()
    eng8.step(8)
    fields["sparse_sharded_engine"] = eng8.engine_stamp
    if not np.array_equal(eng8.snapshot(),
                          stencils.oracle_run(spec, board, 8)):
        fields["sparse_sharded_error"] = (
            "sparse-sharded engine failed oracle parity")
        return fields

    # Dense sharded leg: the same mesh, the same schedule family the
    # sparse rounds gather from — warm both static-n programs, then
    # chain-difference with min-of-2.
    run_dense, _plan = stencil_engine.make_sharded_runner(
        spec, mesh, "row", (edge, edge))
    dev_board = jax.device_put(
        jnp.asarray(board, spec.dtype),
        NamedSharding(mesh, stencil_engine.sharded_pspec(
            "row", spec.channels)))

    def dense_timed(n):
        t0 = time.perf_counter()
        anchor_sync(run_dense(dev_board, n), fetch_all=True)
        return time.perf_counter() - t0

    anchor_sync(run_dense(dev_board, n_steps), fetch_all=True)
    dense_final = run_dense(dev_board, 2 * n_steps)
    anchor_sync(dense_final, fetch_all=True)
    dense_final = np.asarray(dense_final)
    d1 = min(dense_timed(n_steps) for _ in range(2))
    d2 = min(dense_timed(2 * n_steps) for _ in range(2))
    dense_step = (d2 - d1) / n_steps if d2 > d1 else d1 / n_steps

    # Sparse-sharded leg: fresh engine per bracket; one warm run first
    # so the kcap-ladder programs are compiled outside the brackets.
    def sparse_sharded_run(n):
        eng = fresh()
        t0 = time.perf_counter()
        eng.step(n)
        anchor_sync(eng.board, fetch_all=True)
        return eng, time.perf_counter() - t0

    # Warm the FULL 2K trajectory: the rung ladder is trajectory-
    # dependent, and a rung first reached between K and 2K would
    # otherwise compile inside the 2K bracket only — inflating the
    # differenced per-step cost instead of cancelling.
    sparse_sharded_run(2 * n_steps)
    s1 = min(sparse_sharded_run(n_steps)[1] for _ in range(2))
    eng_final, t2a = sparse_sharded_run(2 * n_steps)
    s2 = min(t2a, sparse_sharded_run(2 * n_steps)[1])
    sparse_step = (s2 - s1) / n_steps if s2 > s1 else s1 / n_steps

    # Single-device sparse leg (PR 13's engine): the other parent.
    def single_run(n):
        eng = ActiveTileEngine(spec, board, tile=tile)
        t0 = time.perf_counter()
        eng.step(n)
        return eng, time.perf_counter() - t0

    single_run(n_steps)  # warm
    g1 = min(single_run(n_steps)[1] for _ in range(2))
    g2 = min(single_run(2 * n_steps)[1] for _ in range(2))
    single_step = (g2 - g1) / n_steps if g2 > g1 else g1 / n_steps

    bitident = np.array_equal(eng_final.snapshot(), dense_final)
    cells = edge * edge
    fields.update({
        "sparse_sharded_bitident": bitident,
        "sparse_sharded_cups": round(cells / sparse_step, 1),
        "sparse_sharded_dense_cups": round(cells / dense_step, 1),
        "sparse_sharded_vs_dense": round(dense_step / sparse_step, 2),
        "sparse_sharded_single_cups": round(cells / single_step, 1),
        "sparse_sharded_vs_single": round(single_step / sparse_step, 2),
        "active_frac": round(eng_final.mean_active_frac, 6),
        "sparse_sharded_engine": eng_final.engine_stamp,
        "sparse_sharded_counters": eng_final.counters(),
    })
    if not bitident:
        fields["sparse_sharded_error"] = (
            "sparse-sharded final board diverged from the dense "
            "sharded schedule")
    return fields


def _radius_ab_phase(args) -> dict:
    """The WIDE-RADIUS ENGINE-FAMILY A/B (``--radius-ab K``): K steps
    of an ephemeral lenia spec at every ``--radius-list`` radius on a
    ``--radius-board``² float32 board, racing the three aggregation
    families (``stencils.engine.run_family``) — the O(r²·n) offset
    walk, the rank-k separable row×col pass, the cached-rfft2 circular
    convolution — wherever each family's legality gate admits the spec
    and the ``MOMP_ENGINE_FAMILY`` pin allows it. Honesty discipline is
    the headline's: every (radius, family) leg is oracle-parity-gated
    first (8 steps, at the family's gate-owned tolerance —
    ``parity_tol_for``), then warmed and chain-differenced (K vs 2K,
    min-of-2 brackets; ``n`` is a runtime scalar so one executable
    serves both). The table is the artifact — ``vs_offset`` per row is
    the measured crossover — and the scalars the sentinel watches
    (``radius_ab_*_cups``, ``radius_ab_vs_offset_best``) plus the
    ``engine_family`` stamp (the winner at the widest radius; the
    ledger keys on it, so a kill-switch run stamps ``offset`` and the
    sentinel fails the downgrade) ride the line."""
    from mpi_and_open_mp_tpu import stencils
    from mpi_and_open_mp_tpu.stencils import engine as stencil_engine
    from mpi_and_open_mp_tpu.utils.timing import anchor_sync

    n_steps, edge = args.radius_ab, args.radius_board
    radii = sorted({int(r) for r in str(args.radius_list).split(",")
                    if r.strip()})
    fields = {"radius_ab_board": edge, "radius_ab_steps": n_steps,
              "radius_ab_radii": radii}
    pin = stencil_engine.family_pinned()
    if pin is not None:
        fields["radius_ab_family_pin"] = pin
    rows = []
    rng = np.random.default_rng(46)
    cells = edge * edge
    best_at_widest = None  # (step_sec, family) at the widest radius
    for radius in radii:
        spec = stencils.make_lenia(radius, f"lenia_ab_r{radius}")
        board = spec.init(rng, (edge, edge))
        ref8 = stencils.oracle_run(spec, board, 8)
        steps_by_family = {}
        for fam in stencil_engine.ENGINE_FAMILIES:
            if not stencil_engine.family_allowed(fam):
                continue
            if fam == "sep" and not stencil_engine.separable_supported(
                    spec):
                continue
            if fam == "fft" and not stencil_engine.fft_supported(spec):
                continue
            row = {"radius": radius, "family": fam}
            rows.append(row)
            # Oracle gate at the family's gate-owned tolerance, before
            # any number is recorded for this leg.
            got = np.asarray(stencil_engine.run_family(
                spec, board, 8, fam))
            tol = stencil_engine.parity_tol_for(fam)
            if not stencils.parity_ok(spec, got, ref8, **tol):
                row["parity"] = False
                continue
            row["parity"] = True

            def timed(n, fam=fam):
                t0 = time.perf_counter()
                anchor_sync(stencil_engine.run_family(
                    spec, board, n, fam), fetch_all=True)
                return time.perf_counter() - t0

            timed(2 * n_steps)  # warm (n is runtime: one executable)
            t1 = min(timed(n_steps) for _ in range(2))
            t2 = min(timed(2 * n_steps) for _ in range(2))
            diff = t2 > t1
            step = (t2 - t1) / n_steps if diff else t1 / n_steps
            steps_by_family[fam] = step
            row.update({"cups": round(cells / step, 1),
                        "is_differenced": diff})
        off = steps_by_family.get("offset")
        if off is not None:
            for row in rows:
                if (row["radius"] == radius and row["family"] != "offset"
                        and row["family"] in steps_by_family):
                    row["vs_offset"] = round(
                        off / steps_by_family[row["family"]], 2)
        if steps_by_family:
            step, fam = min((s, f) for f, s in steps_by_family.items())
            best_at_widest = (step, fam)
            for f, s in steps_by_family.items():
                fields[f"radius_ab_{f}_cups"] = round(cells / s, 1)
    fields["radius_ab_table"] = rows
    # The sentinel's headline watch scalar: the best measured speedup of
    # a wide-radius family over the offset walk at radius >= 8. Absent
    # (not 0) when no such leg ran — e.g. MOMP_ENGINE_FAMILY=offset —
    # so the provenance downgrade, not a fake regression, is the signal.
    vs = [row["vs_offset"] for row in rows
          if row.get("vs_offset") is not None and row["radius"] >= 8]
    if vs:
        fields["radius_ab_vs_offset_best"] = max(vs)
    crossed = [row["radius"] for row in rows
               if row.get("vs_offset", 0) >= 1.0]
    fields["radius_ab_crossover_radius"] = (
        min(crossed) if crossed else None)
    if best_at_widest is not None:
        fields["engine_family"] = best_at_widest[1]
    return fields


def _autotune_phase(args, workload: str) -> dict:
    """The AUTOTUNE phase (``--autotune K``): install any persisted
    plans from the store first (validated + parity-gated), then either
    reuse the installed plan for this exact (workload, batch, board)
    config — ``plan_source=store``, the persisted A/B numbers ride the
    line and ``tune_retraces`` (the life_batch retrace DELTA across this
    phase) proves the reuse dispatched without re-tracing — or run one
    bounded measured tuning pass (``tune.runner.tune``) and persist the
    winner: ``plan_source=fresh``. ``MOMP_TUNE=0`` skips the whole
    phase with an explicit ``fallback_reason`` so the sentinel's match
    keys still see every field. The heuristic-vs-tuned A/B is
    ``heuristic_cups`` / ``tuned_cups`` / ``vs_heuristic`` — >= 1.0 by
    construction because the heuristic's own choice is always among the
    timed candidates."""
    from mpi_and_open_mp_tpu.ops import pallas_life
    from mpi_and_open_mp_tpu.serve import retrace_counts
    from mpi_and_open_mp_tpu.tune import plans as tune_plans
    from mpi_and_open_mp_tpu.tune import runner as tune_runner

    shape = (args.tune_batch, args.tune_board, args.tune_board)
    fields = {"tune_board": args.tune_board,
              "tune_batch": args.tune_batch,
              "tune_steps": args.autotune}
    if not pallas_life._tune_enabled():
        return {**fields, "plan_source": "heuristic",
                "fallback_reason": "autotune skipped: MOMP_TUNE=0"}
    before = retrace_counts()
    plans_dir = args.plans or os.environ.get("MOMP_TUNE_PLANS") or None
    store = tune_plans.PlanStore(plans_dir) if plans_dir else None
    if store is not None:
        fields["plans"] = store.install()
        hit = store.lookup(workload, shape)
        if hit is not None:
            heur = hit.get("heuristic") or {}
            fields.update({
                "plan_source": "store",
                "tuned_path": hit["choice"]["path"],
                "tuned_cups": hit["tuned"]["cups"],
                "heuristic_cups": heur.get("cups"),
                "vs_heuristic": hit["vs_heuristic"],
            })
            after = retrace_counts()
            fields["tune_retraces"] = {
                k: after[k] - before.get(k, 0) for k in after
                if after[k] - before.get(k, 0)}
            return fields
    res = tune_runner.tune(workload, shape, steps=args.autotune,
                           store=store)
    heur = res.get("heuristic") or {}
    fields.update({
        "plan_source": "fresh",
        "tuned_path": res["tuned"]["path"],
        "tuned_cups": res["tuned"]["cups"],
        "heuristic_cups": heur.get("cups"),
        "vs_heuristic": res["vs_heuristic"],
        "tune_candidates": len(res["measurements"]),
        "tune_rejected": len(res["rejected"]),
    })
    for k in ("plan_file", "aot_export", "digest"):
        if k in res:
            fields[f"tune_{k}" if k == "digest" else k] = res[k]
    after = retrace_counts()
    fields["tune_retraces"] = {
        k: after[k] - before.get(k, 0) for k in after
        if after[k] - before.get(k, 0)}
    return fields


def _stencil_bench(args, state, *, platform, device_kind) -> int:
    """The non-life headline (``--workload NAME``): the spec-generated
    roll engine over the workload's own seeded board, parity-gated
    against the spec oracle, steady rate chain-differenced exactly like
    the Life headline (run_roll's step count is a runtime scalar, so the
    chained dispatch reuses the executable). No ``vs_baseline`` — the
    reference MPI baseline is a Life measurement."""
    import jax

    from mpi_and_open_mp_tpu import stencils
    from mpi_and_open_mp_tpu.obs import metrics as obs_metrics
    from mpi_and_open_mp_tpu.obs import trace as obs_trace
    from mpi_and_open_mp_tpu.utils.timing import anchor_sync

    spec = stencils.get(args.workload)
    metric = _metric_name(spec.name)
    rng = np.random.default_rng(46)
    board = spec.init(rng, (NY, NX))

    state["phase"] = "parity"
    with obs_trace.span("bench.phase", phase="parity", workload=spec.name):
        got = np.asarray(stencils.run_roll(spec, board, 8))
    ref = stencils.oracle_run(spec, board, 8)
    if not stencils.parity_ok(spec, got, ref):
        print(json.dumps({"metric": metric, "workload": spec.name,
                          "value": 0.0,
                          "unit": "cell_updates_per_sec",
                          "error": "parity check failed",
                          "phase": "parity"}))
        return 1

    # Autotune phase (opt-in via --autotune K): non-life workloads tune
    # through the same machinery (roll vs per-spec Pallas candidates).
    # A failure costs its fields, never the line.
    tuned = {}
    if args.autotune:
        state["phase"] = "autotune"
        with obs_trace.span("bench.phase", phase="autotune",
                            workload=spec.name):
            try:
                tuned = _autotune_phase(args, spec.name)
            except Exception as e:
                tuned = {"plan_source": "heuristic",
                         "tune_error": f"{type(e).__name__}: {e}"[:200]}

    # The sharded halo A/B is workload-generic: heat/gray_scott/
    # wireworld price their own overlap win through the same plan-
    # scheduled engine legs.
    sharded_ab = {}
    if args.sharded_ab:
        state["phase"] = "sharded_ab"
        with obs_trace.span("bench.phase", phase="sharded_ab",
                            workload=spec.name):
            try:
                sharded_ab = _sharded_ab_phase(args, spec.name)
            except Exception as e:
                sharded_ab = {"sharded_ab_board": args.sharded_board,
                              "sharded_ab_error":
                              f"{type(e).__name__}: {e}"[:200]}

    # The ring A/B is workload-generic too: it prices the attention
    # hop-prefetch schedule, not the stencil.
    ring_ab = {}
    if args.ring_ab:
        state["phase"] = "ring_ab"
        with obs_trace.span("bench.phase", phase="ring_ab"):
            try:
                ring_ab = _ring_ab_phase(args)
            except Exception as e:
                ring_ab = {"ring_ab_calls": args.ring_ab,
                           "ring_ab_error":
                           f"{type(e).__name__}: {e}"[:200]}

    # The radius A/B is workload-generic (it sweeps its own ephemeral
    # lenia specs): any headline may carry the crossover table.
    radius_ab = {}
    if args.radius_ab:
        state["phase"] = "radius_ab"
        with obs_trace.span("bench.phase", phase="radius_ab"):
            try:
                radius_ab = _radius_ab_phase(args)
            except Exception as e:
                radius_ab = {"radius_ab_board": args.radius_board,
                             "radius_ab_error":
                             f"{type(e).__name__}: {e}"[:200]}

    state["phase"] = "measure"

    def timed(n, reps=3):
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            anchor_sync(stencils.run_roll(spec, board, n), fetch_all=True)
            best = min(best, time.perf_counter() - t0)
        return best

    # Warm re-dispatch (the gate compiled the engine; n is runtime).
    anchor_sync(stencils.run_roll(spec, board, STEPS), fetch_all=True)
    best = timed(STEPS)
    rtt_bound = best < 1.0
    mult, reps = (161, 3) if rtt_bound else (2, 1)
    chained = timed(STEPS * mult, reps)
    differenced = chained > best
    steady = (chained - best) / (mult - 1) if differenced else best
    cups = NY * NX * STEPS / best
    steady_cups = NY * NX * STEPS / steady

    state["phase"] = "report"
    metrics_fields = ({"metrics": obs_metrics.snapshot()}
                      if obs_metrics.metrics_on() else {})
    rec = {
        "metric": metric,
        "value": round(steady_cups, 1),
        "unit": "cell_updates_per_sec",
        "end_to_end_sec": round(best, 4),
        "end_to_end_cups": round(cups, 1),
        "steady_is_differenced": differenced,
        "stencil_parity": True,
        "backend": jax.default_backend(),
        "impl": "roll",
        "workload": spec.name,
        "board": [NY, NX],
        "channels": spec.channels,
        "steps": STEPS,
        "dtype": spec.dtype,
        "platform": platform,
        "device_kind": device_kind,
        "devices": jax.device_count(),
        # Plan provenance rides EVERY line like the engine stamps:
        # heuristic unless the autotune phase overrides it below.
        "plan_source": "heuristic",
        **tuned,
        **sharded_ab,
        **ring_ab,
        **radius_ab,
        **metrics_fields,
    }
    print(json.dumps(rec))
    _ledger_append(args.ledger, rec, platform=platform,
                   device_kind=device_kind,
                   device_count=jax.device_count())
    return _phase_errors_rc(rec)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--board", type=int, default=None, metavar="N",
                    help="override board edge (e.g. 8192 for the big-grid "
                    "strong-scaling config); default 500 (p46gun_big)")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--workload", default="life", metavar="NAME",
                    help="stencil workload to bench (a registered "
                    "stencils name: life, heat, gray_scott, wireworld; "
                    "default life). Non-life workloads run the generic "
                    "spec-engine headline (metric stencil_steady_cups_"
                    "<name>, same parity-gate + chained-differencing "
                    "discipline) and support --board/--steps/--trace/"
                    "--ledger/--autotune/--sharded-ab/--radius-ab only "
                    "— the "
                    "life-specific phases "
                    "(--batch/--serve/--sessions/--checkpoint-dir/"
                    "--sparse-ab) are rejected")
    ap.add_argument("--sparse-ab", type=int, default=0, metavar="K",
                    help="also run the SPARSE ACTIVE-TILE A/B (life "
                    "only): K steps of a mostly-dead --sparse-board "
                    "board through stencils.sparse.ActiveTileEngine vs "
                    "the dense jitted roll engine, both sides "
                    "chain-differenced and the sparse result gated "
                    "bit-exact against the dense one, reporting "
                    "sparse_cups / dense_cups / sparse_vs_dense / "
                    "active_frac on the JSON line (runs on every "
                    "backend)")
    ap.add_argument("--sharded-ab", type=int, default=0, metavar="K",
                    help="also run the SHARDED HALO A/B (any workload): "
                    "K torus steps of a --sharded-board² board through "
                    "the plan-scheduled sharded engine (stencils.engine "
                    "+ parallel.haloplan), overlap schedule vs forced-"
                    "sequential baseline on the same mesh, both legs "
                    "oracle-parity-gated, chain-differenced and required "
                    "bit-identical, reporting sharded_overlap_cups / "
                    "sharded_seq_cups / vs_sequential plus the exchange-"
                    "only transfer-vs-exposed accounting on the JSON "
                    "line (needs >= 2 devices — CI uses the 8-virtual-"
                    "device CPU mesh; MOMP_HALO_OVERLAP=0 downgrades the "
                    "sharded_halo stamp to seq:*, which the sentinel "
                    "fails as a provenance downgrade)")
    ap.add_argument("--ring-ab", type=int, default=0, metavar="R",
                    help="also run the RING-ATTENTION HOP-PREFETCH A/B "
                    "(any workload): R causal ring-attention trips over "
                    "the full device mesh, double-slot K/V hop prefetch "
                    "(:pf) vs the single-slot schedule on the same "
                    "operands, prefetch leg oracle-parity-gated, both "
                    "legs chain-differenced and required bit-identical "
                    "forward (gradients cross-checked), reporting "
                    "ring_prefetch_tflops / ring_nopf_tflops / "
                    "ring_vs_nopf plus the rotation-only transfer-vs-"
                    "exposed accounting on the JSON line (needs >= 3 "
                    "devices — CI uses the 8-virtual-device CPU mesh "
                    "with MOMP_PALLAS_INTERPRET=1; MOMP_RING_PREFETCH=0 "
                    "drops the :pf stamp, which the sentinel fails as a "
                    "provenance downgrade)")
    ap.add_argument("--sparse-sharded-ab", type=int, default=0,
                    metavar="K",
                    help="also run the SPARSE x SHARDED A/B (life "
                    "only): K steps of the mostly-dead --sparse-board "
                    "seed through stencils.sparse_sharded."
                    "SparseShardedEngine on the row mesh vs the dense "
                    "sharded runner AND vs the single-device sparse "
                    "engine, all legs chain-differenced, the sparse-"
                    "sharded leg oracle-parity-gated and required "
                    "bit-identical to the dense sharded schedule, "
                    "reporting sparse_sharded_cups / _vs_dense / "
                    "_vs_single / active_frac plus the exchange-skip "
                    "counters on the JSON line (needs >= 2 devices; "
                    "MOMP_SPARSE_SHARDED=0 downgrades the "
                    "sparse_sharded_engine stamp to dense:sharded, "
                    "which the sentinel fails as a provenance "
                    "downgrade)")
    ap.add_argument("--sharded-board", type=int, default=512, metavar="N",
                    help="board edge for the sharded halo A/B (default "
                    "%(default)s; must divide across the mesh's y axis)")
    ap.add_argument("--sparse-board", type=int, default=2048, metavar="N",
                    help="board edge for the sparse A/B (default 2048; "
                    "must be a multiple of --sparse-tile)")
    ap.add_argument("--sparse-tile", type=int, default=64, metavar="T",
                    help="active-tile size for the sparse A/B "
                    "(default 64)")
    ap.add_argument("--radius-ab", type=int, default=0, metavar="K",
                    help="also run the WIDE-RADIUS ENGINE-FAMILY A/B "
                    "(any workload): K steps of an ephemeral lenia spec "
                    "per --radius-list radius on a --radius-board² "
                    "float32 board, racing the offset-table walk vs the "
                    "separable row×col pass vs the cached-rfft2 "
                    "circular convolution (stencils.engine.run_family) "
                    "wherever each family's legality gate admits it, "
                    "every leg oracle-parity-gated at its gate-owned "
                    "tolerance and chain-differenced, reporting the "
                    "radius_ab_table crossover rows plus "
                    "radius_ab_{offset,sep,fft}_cups / "
                    "radius_ab_vs_offset_best and the engine_family "
                    "stamp on the JSON line (runs on every backend; "
                    "MOMP_ENGINE_FAMILY=offset pins the walk, which "
                    "the sentinel fails as a provenance downgrade)")
    ap.add_argument("--radius-board", type=int, default=128, metavar="N",
                    help="board edge for the radius A/B "
                    "(default %(default)s)")
    ap.add_argument("--radius-list", default="1,4,8,16", metavar="R1,R2,..",
                    help="comma list of kernel radii the radius A/B "
                    "sweeps (default %(default)s)")
    ap.add_argument("--checkpoint-dir", default=None, metavar="DIR",
                    help="run the checkpointed robustness phase, writing "
                    "Orbax restart points here")
    ap.add_argument("--checkpoint-every", type=int, default=0, metavar="K",
                    help="checkpoint cadence for that phase "
                    "(default: steps//10)")
    ap.add_argument("--resume", action="store_true",
                    help="continue the checkpointed phase from the latest "
                    "restart point in --checkpoint-dir")
    ap.add_argument("--batch", type=int, default=0, metavar="B",
                    help="also run the BATCHED phase: advance B distinct "
                    "boards of the bench shape in one dispatch through the "
                    "batched native engines (ops.pallas_life."
                    "life_run_vmem_batch) plus a serve-layer bucketing "
                    "demo, reporting aggregate batched_cups / requests "
                    "per sec on the JSON line (runs on every backend)")
    ap.add_argument("--serve", type=int, default=0, metavar="N",
                    help="also run the SERVING-DAEMON phase: a seeded "
                    "mixed-shape burst of N requests through the "
                    "supervised daemon (serve.daemon — admission control, "
                    "deadline flushes, recovery ladder), reporting "
                    "serve_requests_per_sec and p50/p99 latency plus "
                    "shed/degrade counts on the JSON line, then the same "
                    "burst again under the every-record write-ahead "
                    "journal to price the durability tax (serve_wal_* "
                    "fields incl. p50/p99 delta), then a cold/warm pair "
                    "over one durable AOT executable cache to price the "
                    "warm-start win (serve_cold_first_result_s vs "
                    "serve_aot_first_result_s + hit/miss/deserialize "
                    "accounting; runs on every backend; honors "
                    "MOMP_CHAOS)")
    ap.add_argument("--fleet", type=int, default=0, metavar="W",
                    help="with --serve N: also run the SHARDED-FLEET "
                    "phase — the same burst through W in-process worker "
                    "daemons behind the consistent-hash router "
                    "(serve.fleet), clean (fleet_requests_per_sec + "
                    "fleet_p99_latency_s) and then again with the "
                    "busiest worker wedged mid-stream so the "
                    "heartbeat->WAL-replay->re-home ladder is priced "
                    "(fleet_kill_recovery_s); fleet books must balance "
                    "and every re-homed board is oracle-parity-gated")
    ap.add_argument("--loadgen", default=None, metavar="R1,R2,..",
                    help="also run the ELASTIC-FLEET-UNDER-LOAD phase: "
                    "an open-loop Poisson saturation sweep over these "
                    "strictly increasing offered rates (requests/s) "
                    "through a fresh consistent-hash fleet per rung "
                    "(serve.loadgen — arrivals are a precomputed "
                    "schedule, no coordinated omission), reporting the "
                    "saturation knee + goodput + p50/p99/p999 + shed "
                    "breakdown + SLO verdict per rung on the JSON line, "
                    "then one run at the knee rate with the membership "
                    "drill scripted in (wedge busiest at 25%%, REJOIN at "
                    "45%% — rejoin_recovery_s — graceful drain at 65%%): "
                    "final-quartile goodput must recover with zero acked "
                    "loss, balanced books, and oracle parity")
    ap.add_argument("--loadgen-duration", type=float, default=2.0,
                    metavar="S", help="offered-load window per sweep "
                    "rung and for the membership cycle "
                    "(default %(default)s)")
    ap.add_argument("--loadgen-slo-p99", type=float, default=0.5,
                    metavar="S", help="declared p99 latency SLO bound "
                    "the sweep rungs are judged against "
                    "(default %(default)s)")
    ap.add_argument("--sessions", type=int, default=0, metavar="S",
                    help="also run the RESIDENT-SESSION phase: S "
                    "device-resident sessions in the serving daemon's "
                    "session pool (serve.pool — boards live on device as "
                    "(slab, bit-lane) handles, stepping is in-place "
                    "donated dispatch) vs the identical workload shipped "
                    "board-by-board through the ticket path, reporting "
                    "session_requests_per_sec / ship_requests_per_sec / "
                    "session_vs_ship plus pool hit/miss/evict accounting; "
                    "every final snapshot is oracle-parity-gated (runs on "
                    "every backend)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write obs span/event JSONL here (sets MOMP_TRACE; "
                    "summarise with analysis/trace_report.py). The timed "
                    "brackets carry no trace hooks — steady-state numbers "
                    "are unaffected by construction")
    ap.add_argument("--ledger", default=None, metavar="PATH",
                    help="append the stamped JSON line to this run ledger "
                    "(obs.ledger schema; default: $MOMP_LEDGER when set). "
                    "Judge it with analysis/regression_sentinel.py")
    ap.add_argument("--autotune", type=int, default=0, metavar="K",
                    help="also run the AUTOTUNE phase (any workload): "
                    "install persisted plans from --plans (validated + "
                    "oracle-parity-gated; plan_source=store reuses the "
                    "recorded A/B with zero retraces), else one bounded "
                    "measured tuning pass over the legal candidate space "
                    "at (--tune-batch, --tune-board²) with K-step "
                    "chained-differencing brackets, persisting the "
                    "winner plus (life) its exported executable under "
                    "one fingerprint digest (plan_source=fresh); "
                    "reports tuned_cups / heuristic_cups / vs_heuristic "
                    "on the JSON line; MOMP_TUNE=0 skips with an "
                    "explicit fallback_reason")
    ap.add_argument("--tune-board", type=int, default=64, metavar="N",
                    help="board edge the autotune phase profiles "
                    "(default %(default)s — small enough for CPU CI; "
                    "the chip launchers pass the production shapes)")
    ap.add_argument("--tune-batch", type=int, default=32, metavar="B",
                    help="stack batch size the autotune phase profiles "
                    "(default %(default)s)")
    ap.add_argument("--plans", default=None, metavar="DIR",
                    help="durable tuned-plan store directory (default "
                    "$MOMP_TUNE_PLANS): momp-plan/1 records keyed by "
                    "the serve/aotcache fingerprint digest, living "
                    "beside the <digest>.aot executables; corrupt/"
                    "stale/parity-failing records quarantine and the "
                    "heuristics serve unchanged")
    args = ap.parse_args(argv)
    if args.ledger is None:
        args.ledger = os.environ.get("MOMP_LEDGER") or None
    if args.resume and not args.checkpoint_dir:
        ap.error("--resume requires --checkpoint-dir")
    if args.fleet and not args.serve:
        ap.error("--fleet requires --serve N")
    if args.loadgen:
        try:
            rates = [float(r) for r in str(args.loadgen).split(",")
                     if r.strip()]
        except ValueError:
            ap.error(f"--loadgen wants a comma list of offered rates, "
                     f"got {args.loadgen!r}")
        if not rates or any(b <= a for a, b in zip(rates, rates[1:])):
            ap.error(f"--loadgen rates must be strictly increasing, "
                     f"got {args.loadgen!r}")
    if args.workload != "life":
        from mpi_and_open_mp_tpu import stencils as _stencils

        try:
            _stencils.get(args.workload)
        except KeyError as e:
            ap.error(str(e))
        for flag, val in (("--batch", args.batch), ("--serve", args.serve),
                          ("--sessions", args.sessions),
                          ("--loadgen", args.loadgen),
                          ("--checkpoint-dir", args.checkpoint_dir),
                          ("--sparse-ab", args.sparse_ab),
                          ("--sparse-sharded-ab", args.sparse_sharded_ab)):
            if val:
                ap.error(f"{flag} is a life-workload phase; "
                         f"--workload {args.workload} runs the stencil "
                         "headline only")
    if args.autotune and args.autotune < 16:
        ap.error("--autotune needs >= 16 steps for the "
                 "chained-differencing bracket")
    if args.sharded_ab and args.sharded_ab < 16:
        ap.error("--sharded-ab needs >= 16 steps for the "
                 "chained-differencing bracket")
    if args.ring_ab and args.ring_ab < 16:
        ap.error("--ring-ab needs >= 16 calls for the "
                 "chained-differencing bracket")
    if args.radius_ab:
        if args.radius_ab < 16:
            ap.error("--radius-ab needs >= 16 steps for the "
                     "chained-differencing bracket")
        try:
            radii = [int(r) for r in str(args.radius_list).split(",")
                     if r.strip()]
        except ValueError:
            ap.error(f"--radius-list wants a comma list of radii, "
                     f"got {args.radius_list!r}")
        if not radii or any(r < 1 for r in radii):
            ap.error(f"--radius-list radii must be positive, "
                     f"got {args.radius_list!r}")
        if args.radius_board < 4 * max(radii):
            ap.error(f"--radius-board {args.radius_board} is too small "
                     f"for radius {max(radii)} (needs >= 4*radius)")
    if args.sparse_ab or args.sparse_sharded_ab:
        if args.sparse_ab and args.sparse_ab < 16:
            ap.error("--sparse-ab needs >= 16 steps for the "
                     "chained-differencing bracket")
        if args.sparse_sharded_ab and args.sparse_sharded_ab < 16:
            ap.error("--sparse-sharded-ab needs >= 16 steps for the "
                     "chained-differencing bracket")
        if args.sparse_tile < 1 or args.sparse_board % args.sparse_tile:
            ap.error(f"--sparse-board {args.sparse_board} must be a "
                     f"positive multiple of --sparse-tile "
                     f"{args.sparse_tile}")
    if args.trace:
        # Before any phase runs, so the sink (append-mode, cached per env
        # value) collects every span of this invocation.
        os.environ["MOMP_TRACE"] = args.trace
    global NY, NX, STEPS
    if args.board:
        NY = NX = args.board
    if args.steps:
        STEPS = args.steps

    # Driver contract: ONE JSON line, always — a failure anywhere prints
    # {"metric", "error", "phase"} and exits nonzero instead of dying on
    # a traceback with no line. A preemption (signal or chaos plan) is
    # the one non-error failure: state is flushed, the line says
    # "resume": true, and the exit code is 75 (EX_TEMPFAIL) so queue
    # loops requeue instead of dropping the job.
    state = {"phase": "backend"}
    try:
        return _bench(args, state)
    except BaseException as e:  # noqa: BLE001 — the line IS the contract
        if isinstance(e, (KeyboardInterrupt, SystemExit)):
            raise
        from mpi_and_open_mp_tpu.robust.preempt import (
            EXIT_PREEMPTED, Preempted)

        rec = {"metric": _metric_name(args.workload),
               "workload": args.workload,
               "error": f"{type(e).__name__}: {e}"[:300],
               "phase": state["phase"]}
        if isinstance(e, Preempted):
            rec["resume"] = True
            print(json.dumps(rec))
            _ledger_append(args.ledger, rec)
            return EXIT_PREEMPTED
        print(json.dumps(rec))
        _ledger_append(args.ledger, rec)
        return 1


def _metric_name(workload: str) -> str:
    """The headline metric for a workload: life keeps its historical
    name (the ledger/sentinel history keys on it); every other stencil
    gets ``stencil_steady_cups_<name>``."""
    return ("life_steady_cups_p46gun_big" if workload == "life"
            else f"stencil_steady_cups_{workload}")


def _ledger_append(path, rec, **stamps) -> None:
    """Best-effort ledger append — a ledger IO failure must never cost
    the bench line or change the exit code (stderr note only)."""
    if not path:
        return
    try:
        from mpi_and_open_mp_tpu.obs import ledger as obs_ledger

        obs_ledger.append(obs_ledger.stamp(rec, **stamps), path)
    except Exception as e:  # noqa: BLE001
        print(f"bench: ledger append failed: {type(e).__name__}: {e}",
              file=sys.stderr)


def _phase_errors_rc(rec: dict) -> int:
    """Exit code for a printed line: 1 when any phase recorded an
    ``*_error`` field (the line still carries every field), else 0."""
    failed = sorted(k for k in rec if k.endswith("_error"))
    if failed:
        print(f"bench: phase errors: {', '.join(failed)}", file=sys.stderr)
        return 1
    return 0


def _bench(args, state) -> int:
    # No device probe and no CPU fallback: the line is stamped with the
    # backend JAX initialised, which must be the TPU unless the caller
    # pinned the CPU with JAX_PLATFORMS=cpu (the tests do).
    from mpi_and_open_mp_tpu.obs import metrics as obs_metrics
    from mpi_and_open_mp_tpu.obs import trace as obs_trace
    from mpi_and_open_mp_tpu.robust import guards
    from mpi_and_open_mp_tpu.utils.runtime import (
        enable_compile_cache, require_backend)

    enable_compile_cache()
    import jax

    platform = require_backend()
    device_kind = jax.devices()[0].device_kind

    if args.workload != "life":
        return _stencil_bench(args, state, platform=platform,
                              device_kind=device_kind)

    from mpi_and_open_mp_tpu.models.life import LifeSim
    from mpi_and_open_mp_tpu.ops.life_ops import life_step_numpy
    from mpi_and_open_mp_tpu.utils.config import config_from_board

    rng = np.random.default_rng(46)  # p46 in spirit
    board = (rng.random((NY, NX)) < 0.3).astype(np.uint8)

    # Honesty gate: the timed impl must be bit-exact vs the host oracle.
    state["phase"] = "parity"
    cfg_check = config_from_board(board, steps=8, save_steps=0)
    sim_check = LifeSim(cfg_check, layout="serial", impl="auto")
    # Phase spans (no-op singletons when MOMP_TRACE is unset) bracket the
    # UNTIMED phases only; the chained-dispatch brackets inside measure()
    # stay hook-free so tracing cannot perturb the recorded rates.
    with obs_trace.span("bench.phase", phase="parity"):
        got = sim_check.run(save=False)
    ref = board.copy()
    for _ in range(8):
        ref = life_step_numpy(ref)
    if not np.array_equal(got, ref):
        print(json.dumps({"metric": "life_steady_cups_p46gun_big",
                          "value": 0.0,
                          "unit": "cell_updates_per_sec", "vs_baseline": 0.0,
                          "error": "parity check failed",
                          "phase": "parity"}))
        return 1

    # Robustness phase (opt-in via --checkpoint-dir): checkpointed run
    # with resume/preemption semantics; its fields ride the bench line.
    ckpt_fields = {}
    if args.checkpoint_dir:
        state["phase"] = "checkpoint"
        with obs_trace.span("bench.phase", phase="checkpoint"):
            ckpt_fields = _checkpointed_run(args)

    state["phase"] = "measure"

    def measure(sim):
        """(best_sec, steady_sec, differenced) for STEPS steps.

        Steady-state rate: the single-run number carries one fixed
        host->device dispatch and sync cost, which can rival the few-ms
        compute. On the pallas/bitfused paths the step
        count is a runtime scalar, so a mult-x-longer dispatch reuses the
        same executable; differencing the two durations isolates the
        marginal per-step rate. The other impls (roll/halo) jit with a
        STATIC step count, so the chained run is a different compiled
        program: it gets compiled OUTSIDE the timing bracket by a
        discarded warm-up advance (an AOT ``lower().compile()`` does
        not seed the jit call cache), and the chain uses the cheapest
        mult (2) with one rep — these impls run on CPU where a 161x
        chain would grind through 161x the actual steps. Every line is
        differenced now; ``steady_is_differenced: false`` survives only
        as the jitter-anomaly flag (chained run not slower than base).
        """
        sim.warmup()  # compiles the exact stepper the timed loop uses
        best = float("inf")
        for _ in range(3):
            sim.reset()
            sim.sync()  # absorb reset()'s async host->device transfer
            t0 = time.perf_counter()
            sim.step(STEPS)
            sim.sync()
            best = min(best, time.perf_counter() - t0)
        steady, differenced = best, False
        if sim.impl in ("pallas", "bitfused"):
            # RTT-bound sub-second runs: make the differencing signal
            # large vs the ~±10 ms RTT jitter (161x chain ≈ 0.3 s of pure
            # compute at the flagship rate → jitter is <5% of signal) and
            # take best-of-3. Multi-second big-board runs: jitter is
            # negligible and a 6x chain already costs real chip time —
            # single shot.
            rtt_bound = best < 1.0
            mult, reps = (161, 3) if rtt_bound else (6, 1)
            chained = float("inf")
            for _ in range(reps):
                sim.reset()
                sim.sync()
                t0 = time.perf_counter()
                sim.step(STEPS * mult)
                sim.sync()
                chained = min(chained, time.perf_counter() - t0)
            if chained > best:
                steady = (chained - best) / (mult - 1)
                differenced = True
        else:
            from mpi_and_open_mp_tpu.utils.timing import anchor_sync

            mult = 2
            # Compile-and-discard: advance is functional, so this seeds
            # the static-n jit cache for the chained length without
            # touching sim state — the timed dispatch below then reuses
            # the executable, exactly like warmup() does for run().
            anchor_sync(sim._advance(sim.board, STEPS * mult),
                        fetch_all=True)
            sim.reset()
            sim.sync()
            t0 = time.perf_counter()
            sim.step(STEPS * mult)
            sim.sync()
            chained = time.perf_counter() - t0
            if chained > best:
                steady = (chained - best) / (mult - 1)
                differenced = True
        return best, steady, differenced

    cfg = config_from_board(board, steps=STEPS, save_steps=0)
    sim = LifeSim(cfg, layout="serial", impl="auto")
    with obs_trace.span("bench.phase", phase="measure"):
        best, steady, differenced = measure(sim)
    cups = NY * NX * STEPS / best
    steady_cups = NY * NX * STEPS / steady

    # Batched phase (opt-in via --batch): aggregate throughput of B
    # boards per dispatch + the serve-layer bucketing counters. Runs on
    # every backend; a failure costs its fields, never the bench line.
    batched = {}
    if args.batch:
        state["phase"] = "batch"
        m0 = obs_metrics.snapshot()
        with obs_trace.span("bench.phase", phase="batch"):
            try:
                batched = _batched_phase(args.batch, cups)
            except Exception as e:
                batched = {"batch": args.batch,
                           "batched_error": f"{type(e).__name__}: {e}"[:200]}
        batched.update(_phase_metrics_delta("batch", m0))

    # Autotune phase (opt-in via --autotune K): bounded measured tuning
    # pass or persisted-plan reuse; heuristic-vs-tuned A/B fields ride
    # the line. A failure costs its fields, never the bench line.
    tuned = {}
    if args.autotune:
        state["phase"] = "autotune"
        with obs_trace.span("bench.phase", phase="autotune"):
            try:
                tuned = _autotune_phase(args, "life")
            except Exception as e:
                tuned = {"plan_source": "heuristic",
                         "tune_error": f"{type(e).__name__}: {e}"[:200]}

    # Serving-daemon phase (opt-in via --serve N): latency percentiles
    # and shed/degrade accounting from the supervised daemon. A failure
    # costs its fields, never the bench line — EXCEPT a preemption
    # (signal or chaos plan), which follows the global exit-75 contract.
    served = {}
    if args.serve:
        from mpi_and_open_mp_tpu.robust.preempt import Preempted

        state["phase"] = "serve"
        m0 = obs_metrics.snapshot()
        with obs_trace.span("bench.phase", phase="serve"):
            try:
                served = _serve_phase(args.serve)
            except Preempted:
                raise
            except Exception as e:
                served = {"serve_daemon_requests": args.serve,
                          "serve_daemon_error":
                          f"{type(e).__name__}: {e}"[:200]}
        served.update(_phase_metrics_delta("serve", m0))
        if args.fleet:
            state["phase"] = "fleet"
            m0 = obs_metrics.snapshot()
            with obs_trace.span("bench.phase", phase="fleet"):
                try:
                    served.update(_fleet_phase(args.serve, args.fleet))
                except Preempted:
                    raise
                except Exception as e:
                    served.update({"fleet_workers": args.fleet,
                                   "fleet_error":
                                   f"{type(e).__name__}: {e}"[:200]})
            served.update(_phase_metrics_delta("fleet", m0))

    # Elastic-fleet-under-load phase (opt-in via --loadgen R1,R2,..):
    # open-loop saturation sweep + the wedge->REJOIN->drain membership
    # cycle. Same failure contract as the other serve-layer phases.
    if args.loadgen:
        from mpi_and_open_mp_tpu.robust.preempt import Preempted

        state["phase"] = "loadgen"
        m0 = obs_metrics.snapshot()
        with obs_trace.span("bench.phase", phase="loadgen"):
            try:
                served.update(_loadgen_phase(args))
            except Preempted:
                raise
            except Exception as e:
                served.update({"loadgen_rates": args.loadgen,
                               "loadgen_error":
                               f"{type(e).__name__}: {e}"[:200]})
        served.update(_phase_metrics_delta("loadgen", m0))

    # Resident-session phase (opt-in via --sessions S): the device-
    # resident vs ship-every-call A/B through the session pool. Same
    # failure contract as the other serve-layer phases.
    if args.sessions:
        from mpi_and_open_mp_tpu.robust.preempt import Preempted

        state["phase"] = "sessions"
        m0 = obs_metrics.snapshot()
        with obs_trace.span("bench.phase", phase="sessions"):
            try:
                served.update(_sessions_phase(args.sessions))
            except Preempted:
                raise
            except Exception as e:
                served.update({"session_count": args.sessions,
                               "session_error":
                               f"{type(e).__name__}: {e}"[:200]})
        served.update(_phase_metrics_delta("sessions", m0))

    # Sparse active-tile A/B (opt-in via --sparse-ab K): the mostly-dead
    # big-board scaling axis. Same failure contract as the other opt-in
    # phases: an exception costs its fields, never the bench line.
    sparse = {}
    if args.sparse_ab:
        state["phase"] = "sparse"
        with obs_trace.span("bench.phase", phase="sparse"):
            try:
                sparse = _sparse_ab_phase(
                    args.sparse_ab, args.sparse_board, args.sparse_tile)
            except Exception as e:
                sparse = {"sparse_board": args.sparse_board,
                          "sparse_error": f"{type(e).__name__}: {e}"[:200]}

    # Sharded halo-schedule A/B (opt-in via --sharded-ab K): overlap vs
    # forced-sequential through the plan-scheduled engine. Same failure
    # contract as the other opt-in phases.
    sharded_ab = {}
    if args.sharded_ab:
        state["phase"] = "sharded_ab"
        with obs_trace.span("bench.phase", phase="sharded_ab"):
            try:
                sharded_ab = _sharded_ab_phase(args, "life")
            except Exception as e:
                sharded_ab = {"sharded_ab_board": args.sharded_board,
                              "sharded_ab_error":
                              f"{type(e).__name__}: {e}"[:200]}

    # Ring-attention hop-prefetch A/B (opt-in via --ring-ab R): the
    # double-slot K/V rotation schedule vs the single-slot one it
    # deepens. Same failure contract as the other opt-in phases.
    ring_ab = {}
    if args.ring_ab:
        state["phase"] = "ring_ab"
        with obs_trace.span("bench.phase", phase="ring_ab"):
            try:
                ring_ab = _ring_ab_phase(args)
            except Exception as e:
                ring_ab = {"ring_ab_calls": args.ring_ab,
                           "ring_ab_error":
                           f"{type(e).__name__}: {e}"[:200]}

    # Sparse x sharded A/B (opt-in via --sparse-sharded-ab K): the
    # composition of the sparse active-tile mask with the sharded halo
    # exchange. Same failure contract as the other opt-in phases.
    sparse_sharded = {}
    if args.sparse_sharded_ab:
        state["phase"] = "sparse_sharded"
        with obs_trace.span("bench.phase", phase="sparse_sharded"):
            try:
                sparse_sharded = _sparse_sharded_ab_phase(args)
            except Exception as e:
                sparse_sharded = {
                    "sparse_sharded_board": args.sparse_board,
                    "sparse_sharded_error":
                    f"{type(e).__name__}: {e}"[:200]}

    # Wide-radius engine-family A/B (opt-in via --radius-ab K): the
    # offset/sep/fft crossover sweep. Same failure contract as the
    # other opt-in phases.
    radius_ab = {}
    if args.radius_ab:
        state["phase"] = "radius_ab"
        with obs_trace.span("bench.phase", phase="radius_ab"):
            try:
                radius_ab = _radius_ab_phase(args)
            except Exception as e:
                radius_ab = {"radius_ab_board": args.radius_board,
                             "radius_ab_error":
                             f"{type(e).__name__}: {e}"[:200]}

    # Secondary: the SHARDED flagship entry point (row-layout bitfused
    # over a 1-device mesh — all the bench chip has). Since the 1-device
    # serial dispatch, this measures what a user of the sharded API gets
    # on one chip (the serial stepper; sharded_plan says so) — the
    # ppermute-halo exchange machinery itself engages from 2 devices and
    # is validated for correctness by the CPU-mesh suite and
    # dryrun_multichip, not timed here. TPU-only (interpret-mode Pallas
    # would grind on CPU).
    sharded = {}
    if jax.default_backend() == "tpu":
        state["phase"] = "sharded"
        from mpi_and_open_mp_tpu.parallel import mesh as mesh_lib

        sim_sh = LifeSim(cfg, layout="row", impl="bitfused",
                         mesh=mesh_lib.make_mesh_1d(1, axis="y"))
        # Same honesty discipline as the headline: the sharded stepper
        # (whatever path it dispatched to) must be bit-exact vs the host
        # oracle before its timing is recorded.
        sim_sh.step(8)
        sh_ok = np.array_equal(sim_sh.collect(), ref)
        sharded = {
            # The EXECUTED path: a 1-device mesh dispatches to the
            # serial stepper (no neighbours -> no ghost redundancy),
            # labelled "serial-1dev:<path>"; real multi-device meshes
            # report the exchange plan's mode.
            "sharded_plan": getattr(sim_sh, "plan_note", sim_sh._plan.mode),
        }
        if sh_ok:
            _, steady_sh, diff_sh = measure(sim_sh)
            sharded.update({
                "sharded_steady_cups": round(NY * NX / steady_sh * STEPS, 1),
                "sharded_steady_is_differenced": diff_sh,
            })
        else:
            sharded["sharded_error"] = "parity check failed"

        # Long-context layer: 32k-token causal attention forward (8 heads,
        # d=128) through the flash-chunked kernel that carries
        # ring_attention's per-shard compute. Marginal per-call seconds by
        # chaining R calls in one dispatch (output feeds the next call's
        # queries, so the chain can't be elided) and differencing —
        # the same RTT-cancelling discipline as the Life numbers.
        state["phase"] = "attention"
        import jax.numpy as jnp
        from jax import lax as jlax

        from mpi_and_open_mp_tpu.parallel import context
        from mpi_and_open_mp_tpu.parallel.context import flash_attention
        from mpi_and_open_mp_tpu.utils.timing import anchor_sync

        # The shared honesty gate (context.gated_parity_check, same one
        # sweep_attention runs): whichever engine flash_attention
        # dispatches to must match the dense oracle before its timings
        # are recorded, with automatic fallback to the jnp engine.
        # for_seq aims the gate at the exact engine+block configuration
        # the timed 32k operands will dispatch. Unlike the sweep, a
        # total gate failure doesn't abort — the bench line (with the
        # Life numbers already in hand) still prints, carrying the
        # error instead of attention fields.
        attn_ok, _, gate_notes = context.gated_parity_check(
            for_seq=32 * 1024)
        if gate_notes:
            # Recorded even when the gate ultimately passed: an engine
            # downgrade (pallas -> jnp) must be explained in the
            # artifact, not only on a transient stderr.
            sharded["attention_gate_notes"] = "; ".join(gate_notes)
        if not attn_ok:
            sharded["attention_error"] = "parity gate failed on every engine"

        h, n, d = 8, 32 * 1024, 128
        flops = 2 * h * n * n * d  # QK^T + PV, causal half
        qkv = [jnp.asarray(rng.standard_normal((h, n, d)), jnp.bfloat16)
               for _ in range(3)]
        # Shape-aware provenance: the engine the timed 32k operands
        # actually dispatch to (a block override that doesn't divide
        # 32k routes them to jnp even when the gate passed on pallas).
        # The ring-hop stamps (fwd/bwd/zigzag) are emitted in the
        # report phase so they ride EVERY line, CPU lines included.
        sharded["attention_engine"] = context.flash_engine_for(*qkv)

        @jax.jit
        def chain(q, k, v, r):
            return jlax.fori_loop(
                0, r, lambda _, c: flash_attention(c, k, v, causal=True), q
            )

        def timed(call):
            best_r = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                anchor_sync(call(), fetch_all=True)
                best_r = min(best_r, time.perf_counter() - t0)
            return best_r

        if attn_ok:
            # The gate ran at 2048; the timed shape is 32k — a per-shape
            # kernel failure here must cost the attention fields only,
            # never the already-measured Life numbers.
            try:
                anchor_sync(chain(*qkv, jnp.int32(1)),
                            fetch_all=True)  # compile
                t_1 = timed(lambda: chain(*qkv, jnp.int32(1)))
                t_9 = timed(lambda: chain(*qkv, jnp.int32(9)))
            except Exception as e:
                attn_ok = False
                sharded["attention_error"] = (
                    f"{type(e).__name__}: {e}"[:200])
            else:
                # Same anomaly discipline as measure(): if jitter made
                # the longer chain "faster", report the end-to-end
                # single call un-differenced and flag it, rather than
                # emitting a nonsense marginal rate.
                attn_diff = t_9 > t_1
                attn_sec = (t_9 - t_1) / 8 if attn_diff else t_1
                sharded.update({
                    "attention_32k_causal_sec": round(attn_sec, 5),
                    "attention_32k_causal_tflops": round(
                        flops / attn_sec / 1e12, 1),
                    "attention_is_differenced": attn_diff,
                })

        # Training path: the flash custom_vjp backward, FULL (q, k, v)
        # gradients — grad wrt q alone lets XLA prune the dk+dv pass and
        # overstate the rate. The chain is UNROLLED (python loop, static
        # r): grad through a lax.scan of the custom_vjp stacks O(seq^2)
        # forward intermediates per link (see parallel/context.py).
        @functools.partial(jax.jit, static_argnames=("r",))
        def grad_chain(q, k, v, r):
            def loss(q_, k_, v_):
                c = q_
                for _ in range(r):
                    c = flash_attention(c, k_, v_, causal=True)
                return (c.astype(jnp.float32) ** 2).sum()

            return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

        try:
            if not attn_ok:
                raise RuntimeError(
                    "attention gate or forward timing failed")
            anchor_sync(grad_chain(*qkv, r=1), fetch_all=True)  # compile
            anchor_sync(grad_chain(*qkv, r=3), fetch_all=True)
            g_1 = timed(lambda: grad_chain(*qkv, r=1))
            g_3 = timed(lambda: grad_chain(*qkv, r=3))
        except Exception as e:  # never lose the whole bench line to this
            sharded["attention_grad_error"] = f"{type(e).__name__}: {e}"[:200]
        else:
            grad_diff = g_3 > g_1
            grad_sec = (g_3 - g_1) / 2 if grad_diff else g_1
            sharded.update({
                # grad_sec times one FULL grad step (forward + backward
                # per chain link — a backward can't run without its
                # forward); TFLOP/s uses the matching fwd+bwd = 3.5x fwd
                # accounting (bwd = 5 block matmuls vs 2).
                "attention_32k_grad_sec": round(grad_sec, 5),
                "attention_32k_grad_tflops": round(
                    3.5 * flops / grad_sec / 1e12, 1),
                "attention_grad_is_differenced": grad_diff,
            })
    # Profile phase: live-buffer and device memory gauges (obs.profile)
    # ride the line's metrics sub-object.
    state["phase"] = "profile"
    from mpi_and_open_mp_tpu.obs import profile as obs_profile

    obs_profile.record_memory_gauges()
    if "attention_32k_causal_tflops" in sharded:
        # The attention twin rides only when the fwd timing landed: its
        # FLOPs are exact (2hn²d causal), so the roofline is just the
        # achieved rate over the bf16 peak for this device kind.
        peak_flops, _, _ = obs_profile.peaks_for(device_kind)
        sharded["attention_roofline_pct"] = round(
            100 * sharded["attention_32k_causal_tflops"] * 1e12 / peak_flops,
            3)

    state["phase"] = "report"
    # Sharded-attention engine provenance rides EVERY bench line — CPU
    # lines and the CI bench-contract run included. The stamps are
    # pure shape analysis over the flagship 32k operands
    # (ShapeDtypeStructs, never device arrays): the forward hop engine,
    # the backward hop engine (ops.flash_hop_bwd vs the
    # _flash_block_grads fold), and the causal-zigzag forward
    # decomposition. Off-chip they honestly read "jnp"/"local:…", and
    # the MOMP_RING_HOP / MOMP_RING_HOP_BWD / MOMP_RING_ZZ escape
    # hatches show up here rather than silently changing the engine.
    from mpi_and_open_mp_tpu.parallel import context as _ctx
    _spec = jax.ShapeDtypeStruct((8, 32 * 1024, 128), jax.numpy.bfloat16)
    sharded["attention_hop_engine"] = _ctx.ring_hop_engine_for(
        _spec, _spec, _spec, causal=True)
    sharded["attention_hop_engine_bwd"] = _ctx.ring_hop_bwd_engine_for(
        _spec, _spec, _spec, causal=True)
    sharded["attention_hop_engine_zz"] = _ctx.ring_hop_engine_for(
        _spec, _spec, _spec, causal=True, layout="zigzag")
    # Trace probe (only when a MOMP_TRACE sink is set): the attention
    # phase above is TPU-only, so a CPU bench run would otherwise produce
    # a trace with no ring spans at all — and the CI trace cycle asserts
    # on exactly those. One tiny ring_attention over the default mesh
    # exercises the traced hop-by-hop dispatch (chaos-free: 2*(p-1) hop
    # spans) or the guarded path (active chaos plan: a recovery event),
    # in milliseconds at this shape. Failures cost a field, never the
    # bench line.
    trace_fields = {}
    if obs_trace.enabled():
        try:
            from mpi_and_open_mp_tpu.parallel import context as _pctx
            from mpi_and_open_mp_tpu.utils.timing import anchor_sync

            p_dev = jax.device_count()
            prng = np.random.default_rng(7)
            h, n, d = 4, 64 * p_dev, 32
            qkv_t = [jax.numpy.asarray(
                prng.standard_normal((h, n, d)), jax.numpy.float32)
                for _ in range(3)]
            anchor_sync(_pctx.ring_attention(*qkv_t, causal=True),
                        fetch_all=True)
            trace_fields["trace_probe"] = f"ring_attention p={p_dev}"
        except Exception as e:
            trace_fields["trace_probe_error"] = (
                f"{type(e).__name__}: {e}"[:200])
    # The registry snapshot rides the line (retraces, hop counts, guard
    # ladder, checkpoint totals) and — when tracing — lands in the trace
    # stream too, so trace_report can summarise retraces offline.
    obs_trace.event("metrics", snapshot=obs_metrics.snapshot())
    metrics_fields = ({"metrics": obs_metrics.snapshot()}
                      if obs_metrics.metrics_on() else {})
    # Self-healed dispatches (robust.guards) must surface in the
    # artifact: a silently recovered engine would launder a fault into a
    # clean-looking measurement line.
    recovered = guards.recovery_log()
    rec = {
        "metric": "life_steady_cups_p46gun_big",
        "value": round(steady_cups, 1),
        "unit": "cell_updates_per_sec",
        "vs_baseline": round(steady_cups / BASELINE_CUPS, 2),
        "end_to_end_sec": round(best, 4),
        "end_to_end_cups": round(cups, 1),
        "end_to_end_vs_baseline": round(cups / BASELINE_CUPS, 2),
        # False = the differencing never beat the base run (non-pallas
        # impl, or a sub-RTT anomaly): value is then the end-to-end rate,
        # not a true marginal per-step rate — don't compare across kinds.
        "steady_is_differenced": differenced,
        "backend": jax.default_backend(),
        "impl": sim.impl,
        # Workload + provenance stamps: the run-ledger configuration key
        # (obs.ledger) and the sentinel's downgrade comparison both read
        # these, so they ride EVERY line.
        "board": [NY, NX],
        "steps": STEPS,
        "dtype": "uint8",
        "workload": "life",
        "platform": platform,
        "device_kind": device_kind,
        "devices": jax.device_count(),
        # Plan provenance rides EVERY line like the engine stamps
        # (CPU lines included): heuristic unless the autotune
        # phase overrides it via **tuned below.
        "plan_source": "heuristic",
        **({"recovered": recovered} if recovered else {}),
        **ckpt_fields,
        **batched,
        **tuned,
        **served,
        **sparse,
        **sharded_ab,
        **ring_ab,
        **sparse_sharded,
        **radius_ab,
        **sharded,
        **trace_fields,
        **metrics_fields,
    }
    print(json.dumps(rec))
    _ledger_append(args.ledger, rec, platform=platform,
                   device_kind=device_kind,
                   device_count=jax.device_count())
    return _phase_errors_rc(rec)


if __name__ == "__main__":
    sys.exit(main())
