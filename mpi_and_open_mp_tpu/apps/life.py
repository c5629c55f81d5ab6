"""Life driver CLI.

Contract (reference ``3-life/life_mpi.c:38-72``): positional ``.cfg``,
VTK snapshots under ``--outdir`` at the cfg's save cadence, and ONE line on
stdout — elapsed wall seconds of the timed step loop — so the reference's
``times.txt``/speedup-plot harness consumes TPU runs unchanged. The timer
brackets the whole simulate loop (saves included), like the reference's
``MPI_Wtime`` pair (``life_mpi.c:50,64``), but after a one-step compile
warm-up so XLA compilation isn't billed as simulation.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

import numpy as np

import jax

from mpi_and_open_mp_tpu.apps._common import (
    add_platform_args, apply_platform_args, is_primary)
from mpi_and_open_mp_tpu.models.life import IMPLS, LAYOUTS, LifeSim
from mpi_and_open_mp_tpu.parallel import mesh as mesh_lib
from mpi_and_open_mp_tpu.robust.preempt import EXIT_PREEMPTED, Preempted
from mpi_and_open_mp_tpu.utils.config import load_config
from mpi_and_open_mp_tpu.utils.timing import append_times_txt


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="mpi_and_open_mp_tpu.apps.life",
        description="Distributed Game of Life on a periodic torus (TPU backend)",
    )
    p.add_argument("cfg", help="board config file (steps/save_steps/nx ny/cells)")
    p.add_argument("--layout", choices=LAYOUTS, default="row")
    p.add_argument("--impl", choices=IMPLS, default="auto")
    p.add_argument("--fuse-steps", type=int, default=1, metavar="K",
                   help="halo depth: exchange once per K local steps")
    p.add_argument("--mesh", metavar="PY,PX",
                   help="explicit 2-D mesh shape (cart layout)")
    p.add_argument("--devices", type=int, metavar="N",
                   help="use only the first N devices (1-D layouts)")
    p.add_argument("--batch", type=int, default=0, metavar="B",
                   help="throughput mode: advance B stacked copies of the "
                        "cfg board in ONE device dispatch per segment "
                        "(batched LifeSim; needs --layout serial, excludes "
                        "snapshots/checkpoints/resume). The elapsed line "
                        "then covers B boards' worth of updates")
    p.add_argument("--serve", type=int, default=0, metavar="N",
                   help="serving mode: push N copies of the cfg board "
                        "through the fault-tolerant daemon (serve.daemon: "
                        "admission, bucket deadlines, retry/degrade "
                        "ladder). SIGTERM drains the in-flight batch, "
                        "checkpoints the queue under --checkpoint-dir, and "
                        "exits 75; --resume restores it. Prints the drain "
                        "wall seconds on the times.txt contract (first-"
                        "dispatch compile included — serving pays it too). "
                        "Needs --layout serial; --batch B caps the bucket "
                        "(default 8); excludes --outdir")
    p.add_argument("--outdir", default=None,
                   help="write VTK snapshots here (default: no saves)")
    p.add_argument("--save-final", action="store_true",
                   help="also write the final board to --outdir "
                        "(life_<steps>.vtk), after the timed loop")
    p.add_argument("--times-file", default=None,
                   help="append elapsed seconds to this file (times.txt contract)")
    p.add_argument("--print-final-population", action="store_true")
    p.add_argument("--resume", action="store_true",
                   help="restart from the latest Orbax checkpoint in "
                        "--checkpoint-dir, else the latest VTK in --outdir")
    p.add_argument("--checkpoint-dir", default=None, metavar="DIR",
                   help="write an Orbax checkpoint at every save point "
                        "(sharded; no gather-to-root on multi-host)")
    p.add_argument("--checkpoint-every", type=int, default=0, metavar="N",
                   help="also checkpoint every N steps, independent of the "
                        "save cadence (preemption-safe restart points; "
                        "SIGTERM flushes one and exits 75)")
    p.add_argument("--plans", default=None, metavar="DIR",
                   help="durable tuned-plan store (default "
                        "$MOMP_TUNE_PLANS): records are validated + "
                        "parity-gated and installed BEFORE the first "
                        "dispatch, so a requeued exit-75 --resume run "
                        "restarts both warm and tuned; the resume status "
                        "line (stderr JSON) carries plan_source")
    p.add_argument("--profile", metavar="DIR", default=None,
                   help="capture a jax.profiler trace of the run into DIR; "
                        "unless --trace or MOMP_TRACE names a sink, the "
                        "obs spans go to DIR/spans.jsonl, so the profile "
                        "shows the life.* spans over the device ops")
    p.add_argument("--trace", metavar="PATH", default=None,
                   help="write obs span/event JSONL here (sets MOMP_TRACE; "
                        "read it back with analysis/trace_report.py)")
    p.add_argument("--debug-check", action="store_true",
                   help="assert halo-exchange consistency vs the oracle "
                        "before and after the run")
    add_platform_args(p)
    return p


def _find_latest(directory: str, pattern: str) -> tuple[str, int] | None:
    """Highest-step entry in ``directory`` matching ``pattern`` (one numeric
    group = the step index)."""
    import re

    if not directory or not os.path.isdir(directory):
        return None
    best = None
    for name in os.listdir(directory):
        m = re.fullmatch(pattern, name)
        if m:
            step = int(m.group(1))
            if best is None or step > best[1]:
                best = (os.path.join(directory, name), step)
    return best


def find_latest_snapshot(outdir: str) -> tuple[str, int] | None:
    """Latest ``life_NNNNNN.vtk`` in ``outdir`` and its step index."""
    return _find_latest(outdir, r"life_(\d{6,})\.vtk")


def find_latest_checkpoint(ckpt_dir: str) -> tuple[str, int] | None:
    """Latest ``step_NNNNNN`` Orbax checkpoint in ``ckpt_dir``."""
    return _find_latest(ckpt_dir, r"step_(\d{6,})")


def _plan_store(args):
    """The durable tuned-plan store named by ``--plans`` /
    ``MOMP_TUNE_PLANS``, or None (heuristics only)."""
    plans_dir = args.plans or os.environ.get("MOMP_TUNE_PLANS") or None
    if not plans_dir:
        return None
    from mpi_and_open_mp_tpu.tune.plans import PlanStore

    return PlanStore(plans_dir)


def _plan_fields(store, cfg, batch: int) -> dict:
    """The ``plan_source`` stamp for the resume status line: ``store``
    when the installed plans cover THIS (workload, stack shape) config,
    ``heuristic`` otherwise (no store, a miss, or ``MOMP_TUNE=0`` — the
    install was already skipped/quarantined upstream, so the lookup is
    honestly empty)."""
    fields = {"plan_source": "heuristic"}
    if store is None:
        return fields
    hit = store.lookup("life", (max(batch, 1), cfg.ny, cfg.nx))
    if hit is not None:
        fields["plan_source"] = "store"
        fields["tuned_path"] = hit["choice"]["path"]
    return fields


def make_mesh(args):
    if args.layout == "serial":
        return None
    if args.mesh:
        py, px = (int(v) for v in args.mesh.split(","))
        return mesh_lib.make_mesh_2d(py, px)
    if args.devices:
        axis = "x" if args.layout == "col" else "y"
        if args.layout == "cart":
            return mesh_lib.make_mesh_2d(*mesh_lib.dims_create(args.devices, 2))
        return mesh_lib.make_mesh_1d(args.devices, axis=axis)
    return None  # LifeSim default: all devices


def _serve(args, cfg, parser) -> int:
    """``--serve N``: the cfg board as N daemon requests.

    The times.txt line is the queue drain wall seconds; the service
    summary (resolved/shed/degraded, p99) goes to stderr so the
    reference harness still sees exactly one stdout number. Preemption
    follows the app contract: checkpoint (when ``--checkpoint-dir`` is
    set), stderr note, exit 75 for the queue loop's requeue.
    """
    from mpi_and_open_mp_tpu.obs import trace
    from mpi_and_open_mp_tpu.serve import ServePolicy, ServingDaemon

    ckpt = (os.path.join(args.checkpoint_dir, "serve_queue.state")
            if args.checkpoint_dir else None)
    policy = ServePolicy(max_batch=args.batch or 8,
                         max_depth=max(64, 2 * args.serve))
    # The daemon installs the store at construction, so EVERY resume
    # rung comes up tuned before the first dispatch (ROADMAP autotune
    # follow-on (c): a requeued exit-75 run restarts warm AND tuned).
    store = _plan_store(args)
    if args.resume:
        if not ckpt:
            parser.error("--serve --resume needs --checkpoint-dir")
        try:
            daemon = ServingDaemon.resume(ckpt, policy, plan_store=store)
        except ValueError as e:
            print(f"--serve --resume: {e}", file=sys.stderr)
            return 2
        print(f"resuming {daemon.queue.depth()} queued tickets from "
              f"{ckpt}", file=sys.stderr)
        print(json.dumps({
            "resumed": "serve_queue", "tickets": daemon.queue.depth(),
            **_plan_fields(store, cfg, policy.max_batch)}),
            file=sys.stderr)
    else:
        daemon = ServingDaemon(policy, checkpoint_path=ckpt,
                               plan_store=store)
    board = cfg.board()
    for _ in range(args.serve):
        daemon.submit(board, cfg.steps)
    t0 = time.perf_counter()
    try:
        with trace.span("life.serve", cfg=os.path.basename(args.cfg),
                        requests=args.serve, steps=cfg.steps):
            daemon.serve()
    except Preempted as e:
        print(f"{e} -- requeue with --serve --resume", file=sys.stderr)
        return EXIT_PREEMPTED
    elapsed = time.perf_counter() - t0
    if is_primary():
        print(f"{elapsed:.6f}")
        if args.times_file:
            append_times_txt(args.times_file, elapsed)
        s = daemon.summary()
        print(f"served {s['resolved']}/{s['requests']} "
              f"(shed {s['shed']}, degraded {s['degraded']}, "
              f"p99 {s['p99_latency_s']}s)", file=sys.stderr)
    return 0


def main(argv=None) -> int:
    parser = build_parser()
    args = parser.parse_args(argv)
    apply_platform_args(args)
    if args.trace:
        # Before any sim work so every span of this run lands in the sink
        # (the sink is cached per env value; appends across invocations).
        os.environ["MOMP_TRACE"] = args.trace
    elif args.profile and not os.environ.get("MOMP_TRACE"):
        # A live span is also a profiler annotation: the profile then
        # names the host phase behind each device idle gap.
        os.environ["MOMP_TRACE"] = os.path.join(args.profile, "spans.jsonl")
    from mpi_and_open_mp_tpu.obs import trace

    cfg = load_config(args.cfg)
    serve_ckpt = (os.path.join(args.checkpoint_dir, "serve_queue.state")
                  if args.checkpoint_dir else None)
    if args.serve or (args.resume and serve_ckpt
                      and os.path.exists(serve_ckpt)):
        # Serving mode is its own driver: the daemon owns batching,
        # retries, and the queue checkpoint — the VTK path serialises
        # one simulation, so it's excluded at the CLI edge like --batch.
        # A bare --resume over a serve-queue checkpoint re-enters here
        # too (a requeued job must drain its tickets, not roll back to
        # an Orbax snapshot and silently drop them).
        if args.layout != "serial":
            parser.error("--serve needs --layout serial "
                         "(a bucket is one single-program dispatch)")
        if args.outdir:
            parser.error("--serve is a serving mode: drop --outdir")
        return _serve(args, cfg, parser)
    if args.batch:
        # Batched throughput mode maps straight onto the batched LifeSim
        # contract (models/life.py): serial layout only, and the VTK /
        # checkpoint paths serialise ONE board, so they're excluded at
        # the CLI edge rather than failing deeper in.
        if args.layout != "serial":
            parser.error("--batch needs --layout serial "
                         "(a batch is one single-program dispatch)")
        if args.outdir or args.checkpoint_dir or args.resume:
            parser.error("--batch is a throughput mode: drop --outdir/"
                         "--checkpoint-dir/--resume")
    if args.save_final and not args.outdir:
        parser.error("--save-final needs --outdir")
    kwargs = dict(
        layout=args.layout,
        impl=args.impl,
        mesh=make_mesh(args),
        fuse_steps=args.fuse_steps,
        outdir=args.outdir,
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=args.checkpoint_every,
    )
    # Install tuned plans BEFORE the sim exists: the batched native
    # engines consult them per dispatch, so a --resume with --plans (or
    # MOMP_TUNE_PLANS in the queue loop's environment) restarts tuned,
    # not just warm. Install summary + the per-config plan_source ride
    # the stderr JSON status line the queue loop / tests read.
    store = _plan_store(args)
    plans_installed = store.install() if store is not None else None
    if args.resume:
        # Resume from whichever persisted state is newest (a stale
        # checkpoint dir must not roll back past newer VTK snapshots).
        ckpt = find_latest_checkpoint(args.checkpoint_dir)
        snap = find_latest_snapshot(args.outdir)
        if ckpt is not None and (snap is None or ckpt[1] >= snap[1]):
            path, step = ckpt
            print(f"resuming from checkpoint {path} (step {step})",
                  file=sys.stderr)
            sim = LifeSim.from_checkpoint(path, cfg, **kwargs)
        elif snap is not None:
            path, step = snap
            print(f"resuming from {path} (step {step})", file=sys.stderr)
            sim = LifeSim.from_snapshot(cfg, path, step, **kwargs)
        else:
            sources = [f"no snapshots in {args.outdir!r}"]
            if args.checkpoint_dir is not None:
                sources.insert(
                    0, f"no checkpoints in {args.checkpoint_dir!r}"
                )
            print(f"--resume: {' and '.join(sources)}", file=sys.stderr)
            return 2
        print(json.dumps({
            "resumed": os.path.basename(path), "step": step,
            **({"plans_installed": plans_installed.get("installed", 0)}
               if plans_installed is not None else {}),
            **_plan_fields(store, cfg, args.batch)}), file=sys.stderr)
    elif args.batch:
        # B stacked copies of the cfg board: cups is content-independent
        # for a dense stencil, so identical copies time exactly what B
        # distinct requests would.
        stack = np.stack([cfg.board()] * args.batch)
        sim = LifeSim(cfg, initial_board=stack, **kwargs)
    else:
        sim = LifeSim(cfg, **kwargs)
    # Warm-up: compile every stepper run() will hit, on THIS instance (jit
    # caches are per-instance and keyed on the static step count), so no
    # XLA compilation lands inside the timed bracket.
    sim.warmup()
    if args.debug_check:
        sim.debug_check()

    if args.profile:
        import jax

        ctx = jax.profiler.trace(args.profile)
    else:
        ctx = contextlib.nullcontext()
    with ctx:
        t0 = time.perf_counter()
        try:
            # The whole-run root span: segments/advances nest under it; a
            # Preempted exit closes it with an error attr, so the trace
            # still shows how far the run got.
            with trace.span(
                "life.run",
                cfg=os.path.basename(args.cfg),
                steps=cfg.steps,
                impl=sim.impl,
                layout=sim.layout,
                plan=getattr(sim, "plan_note", sim.impl),
            ):
                final = sim.run()  # collect() inside forces completion
        except Preempted as e:
            # EX_TEMPFAIL: the queue keeps the job; --resume continues
            # from the flushed checkpoint (docs/MIGRATION.md workflow).
            print(f"{e} -- requeue with --resume", file=sys.stderr)
            return EXIT_PREEMPTED
        elapsed = time.perf_counter() - t0
    if args.debug_check:
        sim.debug_check()
    if args.save_final:
        sim.save_snapshot()

    # One process owns stdout and the times file — the reference's
    # print-from-one-rank discipline (3-life/life_mpi.c:64-67).
    if is_primary():
        print(f"{elapsed:.6f}")
        if args.times_file:
            append_times_txt(args.times_file, elapsed)
        if args.print_final_population:
            print(int(np.asarray(final).sum()), file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
