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


METRIC = "life_steady_cups_p46gun_big"
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--board", type=int, default=None, metavar="N",
                    help="override board edge (e.g. 8192 for the big-grid "
                    "strong-scaling config); default 500 (p46gun_big)")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--checkpoint-dir", default=None, metavar="DIR",
                    help="run the checkpointed robustness phase, writing "
                    "Orbax restart points here")
    ap.add_argument("--checkpoint-every", type=int, default=0, metavar="K",
                    help="checkpoint cadence for that phase "
                    "(default: steps//10)")
    ap.add_argument("--resume", action="store_true",
                    help="continue the checkpointed phase from the latest "
                    "restart point in --checkpoint-dir")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write obs span/event JSONL here (sets MOMP_TRACE; "
                    "summarise with analysis/trace_report.py). The timed "
                    "brackets carry no trace hooks — steady-state numbers "
                    "are unaffected by construction")
    ap.add_argument("--ledger", default=None, metavar="PATH",
                    help="append the stamped JSON line to this run ledger "
                    "(obs.ledger schema; default: $MOMP_LEDGER when set). "
                    "Judge it with analysis/regression_sentinel.py")
    args = ap.parse_args(argv)
    if args.ledger is None:
        args.ledger = os.environ.get("MOMP_LEDGER") or None
    if args.resume and not args.checkpoint_dir:
        ap.error("--resume requires --checkpoint-dir")
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

        rec = {"metric": METRIC,
               "workload": "life",
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
        print(json.dumps({"metric": METRIC,
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
        "metric": METRIC,
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
        # Plan provenance rides every line like the engine stamps; the
        # ledger's configuration key reads it.
        "plan_source": "heuristic",
        **({"recovered": recovered} if recovered else {}),
        **ckpt_fields,
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
