"""Measured candidate profiling with the repo's timing discipline.

Nothing here trusts a model: every candidate is DISPATCHED, parity-gated
against the NumPy oracle FIRST (an engine that cannot reproduce the rule
may never win, however fast), then timed with the same chained-dispatch
differencing every recorded number in ``results/`` uses — two run
lengths through one compiled program (``n`` is a runtime scalar on every
engine), steady per-step cost = the difference over the extra steps, so
the ~70 ms host<->device RTT and the fixed dispatch overhead cancel.
Profiling flows through ``obs/`` (a ``tune.candidate`` span per timed
candidate, ``tune.candidate`` status counters), so a tuning pass is as
observable as a serve window.

The heuristic's own choice is always candidate #0 and ties keep it
(strict ``<`` to dethrone), which makes the reported ``vs_heuristic``
ratio >= 1.0 by construction: tuned never loses to the heuristic it
replaces, because the heuristic is in the race.
"""

from __future__ import annotations

import time

import numpy as np

from mpi_and_open_mp_tpu.tune import plans as plans_mod
from mpi_and_open_mp_tpu.tune import space

_TUNE_SEED = 46


def _build_stack(spec, shape) -> np.ndarray:
    b, ny, nx = shape
    rng = np.random.default_rng(_TUNE_SEED)
    return np.stack([spec.init(rng, (ny, nx)) for _ in range(b)]).astype(
        spec.np_dtype)


def tune(workload: str, shape, *, steps: int = 64, store=None,
         reps: int = 2, mult: int = 5,
         parity_steps: int = plans_mod.PARITY_STEPS) -> dict:
    """One bounded tuning pass for (workload, stack shape): enumerate
    legal candidates, parity-gate each, time the survivors, install the
    winner in-process, and (with ``store``) persist it as a
    ``momp-plan/1`` record — for life, exporting the winner's bucket
    executable into the SAME store directory under the SAME digest, so
    the next process deserializes instead of retracing.

    ``steps`` is the short bracket; the long bracket is ``steps *
    mult`` and the steady per-step cost is their difference over the
    extra steps (falling back to the short bracket when differencing is
    ill-conditioned)."""
    import jax
    import jax.numpy as jnp

    from mpi_and_open_mp_tpu import stencils
    from mpi_and_open_mp_tpu.obs import metrics, trace
    from mpi_and_open_mp_tpu.ops import pallas_life
    from mpi_and_open_mp_tpu.serve import aotcache
    from mpi_and_open_mp_tpu.utils.timing import anchor_sync

    shape = tuple(int(x) for x in shape)
    b, ny, nx = shape
    spec = stencils.get(workload)
    stack = _build_stack(spec, shape)
    stack_j = jnp.asarray(stack)
    cells = b * ny * nx
    on_tpu = jax.default_backend() == "tpu"
    heur = space.heuristic_path(workload, shape, on_tpu)
    cands = space.candidates(workload, shape, on_tpu=on_tpu)
    want = [stencils.oracle_run(spec, stack[i], parity_steps)
            for i in range(b)]

    measurements, rejected = [], []
    for cand in cands:
        with trace.span("tune.candidate", workload=str(workload),
                        path=cand.path, axis_order=cand.axis_order):
            try:
                run = space.runner_for(workload, cand.path)
                got = np.asarray(run(stack_j, jnp.int32(parity_steps)))
                # The parity GATE owns each family's float tolerance
                # (offset keeps the default; sep/fft get their
                # amplification-sized slack from parity_tol_for).
                tol = stencils.parity_tol_for(
                    stencils.family_for_path(cand.path))
                ok = got.shape == stack.shape and all(
                    stencils.parity_ok(spec, got[i], want[i], **tol)
                    for i in range(b))
            except Exception as e:  # noqa: BLE001 — a candidate that
                # cannot dispatch is a rejection, never a crash
                metrics.inc("tune.candidate", status="error")
                rejected.append({
                    "path": cand.path,
                    "reason": f"{type(e).__name__}: {e}"[:200]})
                continue
            if not ok:
                metrics.inc("tune.candidate", status="parity_rejected")
                rejected.append({"path": cand.path, "reason": "parity"})
                continue
            # Warm re-dispatch outside the brackets (n is a runtime
            # scalar: the gate above already compiled this program).
            anchor_sync(run(stack_j, jnp.int32(steps)), fetch_all=True)

            def timed(n):
                best = float("inf")
                for _ in range(max(1, int(reps))):
                    t0 = time.perf_counter()
                    anchor_sync(run(stack_j, jnp.int32(n)),
                                fetch_all=True)
                    best = min(best, time.perf_counter() - t0)
                return best

            t1, t2 = timed(steps), timed(steps * mult)
            differenced = t2 > t1
            steady = ((t2 - t1) / (steps * (mult - 1)) if differenced
                      else t1 / steps)
            metrics.inc("tune.candidate", status="timed")
            measurements.append({
                "path": cand.path,
                "pack_layout": cand.pack_layout,
                "bucket_rounding": cand.bucket_rounding,
                "axis_order": cand.axis_order,
                "steady_s_per_step": steady,
                "cups": round(cells / steady, 1),
                "is_differenced": differenced,
            })
    if not measurements:
        raise RuntimeError(
            f"autotune found no parity-clean candidate for "
            f"{workload} {shape} (rejected: {rejected})")
    best = measurements[0]
    for m in measurements[1:]:
        if m["steady_s_per_step"] < best["steady_s_per_step"]:
            best = m
    heur_meas = next(
        (m for m in measurements if m["path"] == heur), None)
    vs = (round(heur_meas["steady_s_per_step"]
                / best["steady_s_per_step"], 3)
          if heur_meas else None)

    pallas_life.install_planned_path(workload, shape, best["path"])
    result = {
        "workload": str(workload),
        "shape": list(shape),
        "dtype": str(spec.np_dtype),
        "steps_budget": int(steps),
        "heuristic": heur_meas,
        "heuristic_path": heur,
        "tuned": best,
        "vs_heuristic": vs,
        "measurements": measurements,
        "rejected": rejected,
    }
    if store is not None:
        key = plans_mod.fingerprint_for(
            workload, shape, spec.np_dtype, best["path"])
        record = {
            "schema": plans_mod.PLAN_SCHEMA,
            "key": key,
            "choice": {
                "workload": str(workload), "shape": list(shape),
                "dtype": str(spec.np_dtype), "path": best["path"],
                "pack_layout": best["pack_layout"],
                "bucket_rounding": best["bucket_rounding"],
                "axis_order": best["axis_order"],
            },
            "heuristic": heur_meas,
            "tuned": best,
            "vs_heuristic": vs,
            "steps_budget": int(steps),
            "measurements": measurements,
            "rejected": rejected,
        }
        result["plan_file"] = store.save(record)
        result["digest"] = aotcache.digest_for(key)
        if workload == "life":
            # Export the winner's bucket executable into the SAME
            # directory: the plan is installed, so AOTCache computes the
            # IDENTICAL fingerprint -> <digest>.aot beside <digest>.plan.
            _, _, status = aotcache.AOTCache(store.root).ensure(
                shape, spec.np_dtype)
            result["aot_export"] = status
    trace.event("tune.done", workload=str(workload),
                path=best["path"], vs_heuristic=vs or 0.0)
    return result


def tune_sharded(workload: str, shape, *, mesh=None, steps: int = 32,
                 store=None, reps: int = 2, mult: int = 5,
                 parity_steps: int = plans_mod.PARITY_STEPS) -> dict:
    """One bounded SHARDED tuning pass for (workload, board shape):
    profile every legal (axis_order, halo schedule) candidate on a real
    >=2-device mesh — the measured form of PAPERS.md's process-mapping
    axis, which single-device profiling could only enumerate. Same
    discipline as :func:`tune`: oracle parity FIRST, chain-differenced
    brackets, the historic schedule (seq) is always in the race and ties
    keep it. 1-D meshes are legality-gated per layout by
    ``space.sharded_candidates`` (a mesh that shards nothing under a
    layout simply does not list it); a mesh with no legal candidate at
    all raises rather than reporting an empty win."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from mpi_and_open_mp_tpu import stencils
    from mpi_and_open_mp_tpu.obs import metrics, trace
    from mpi_and_open_mp_tpu.parallel import mesh as mesh_lib
    from mpi_and_open_mp_tpu.serve import aotcache
    from mpi_and_open_mp_tpu.stencils import engine as stencil_engine
    from mpi_and_open_mp_tpu.utils.timing import anchor_sync

    if mesh is None:
        mesh = mesh_lib.make_mesh_2d()
    shape = tuple(int(x) for x in shape)
    ny, nx = shape
    spec = stencils.get(workload)
    board = spec.init(np.random.default_rng(_TUNE_SEED), (ny, nx))
    want = stencils.oracle_run(spec, board, parity_steps)
    cells = ny * nx
    cands = space.sharded_candidates(workload, shape, mesh)
    if not cands:
        raise RuntimeError(
            f"no legal sharded candidate for {workload} {shape} on mesh "
            f"{dict(mesh.shape)} (1-shard axes and non-dividing layouts "
            "are gated out)")
    # Baseline-first: the historic sequential schedule on the first
    # legal layout opens the race, so ties keep it (strict < below).
    cands = sorted(cands, key=lambda c: c.halo_overlap != "seq")

    measurements, rejected = [], []
    for cand in cands:
        layout = cand.axis_order
        ovl = None if cand.halo_overlap == "overlap" else False
        with trace.span("tune.candidate", workload=str(workload),
                        path=cand.path, axis_order=layout,
                        halo_overlap=cand.halo_overlap):
            try:
                if cand.path.startswith("sparse_sharded:"):
                    # Host-driven engine: every timed leg runs a FRESH
                    # engine from the same board (the mask state is the
                    # engine, so reuse would grade a warmer mask).
                    from mpi_and_open_mp_tpu.stencils import (
                        sparse_sharded)

                    def bench_once(n, fuse=cand.fuse_steps):
                        eng = sparse_sharded.SparseShardedEngine(
                            spec, board, mesh=mesh, layout=layout,
                            tile=space.SPARSE_SHARDED_TILE, fuse=fuse)
                        anchor_sync(eng.step(int(n)))
                        return eng

                    parity_eng = bench_once(int(parity_steps))
                    got = parity_eng.snapshot()
                    engine_stamp = parity_eng.engine_stamp
                else:
                    run, plan = stencil_engine.make_sharded_runner(
                        spec, mesh, layout, shape,
                        fuse_steps=cand.fuse_steps,
                        boundary_steps=cand.boundary_steps,
                        overlap=ovl)
                    sharding = NamedSharding(
                        mesh, stencil_engine._sharded_pspec(
                            layout, spec.channels))
                    dev = jax.device_put(
                        jnp.asarray(board, spec.dtype), sharding)

                    def bench_once(n, run=run, dev=dev):
                        anchor_sync(run(dev, int(n)))

                    got = np.asarray(run(dev, int(parity_steps)))
                    engine_stamp = plan.engine
                ok = stencils.parity_ok(spec, got, want)
            except Exception as e:  # noqa: BLE001 — rejection, not crash
                metrics.inc("tune.candidate", status="error")
                rejected.append({
                    "path": cand.path,
                    "halo_overlap": cand.halo_overlap,
                    "reason": f"{type(e).__name__}: {e}"[:200]})
                continue
            if not ok:
                metrics.inc("tune.candidate", status="parity_rejected")
                rejected.append({"path": cand.path,
                                 "halo_overlap": cand.halo_overlap,
                                 "reason": "parity"})
                continue
            bench_once(steps)

            def timed(n):
                best_t = float("inf")
                for _ in range(max(1, int(reps))):
                    t0 = time.perf_counter()
                    bench_once(n)
                    best_t = min(best_t, time.perf_counter() - t0)
                return best_t

            t1, t2 = timed(steps), timed(steps * mult)
            differenced = t2 > t1
            steady = ((t2 - t1) / (steps * (mult - 1)) if differenced
                      else t1 / steps)
            metrics.inc("tune.candidate", status="timed")
            measurements.append({
                "path": cand.path,
                "axis_order": layout,
                "halo_overlap": cand.halo_overlap,
                "fuse_steps": cand.fuse_steps,
                "boundary_steps": cand.boundary_steps,
                "engine": engine_stamp,
                "steady_s_per_step": steady,
                "cups": round(cells / steady, 1),
                "is_differenced": differenced,
            })
    if not measurements:
        raise RuntimeError(
            f"sharded autotune found no parity-clean candidate for "
            f"{workload} {shape} (rejected: {rejected})")
    best = measurements[0]
    for m in measurements[1:]:
        if m["steady_s_per_step"] < best["steady_s_per_step"]:
            best = m
    baseline = measurements[0]  # seq leg, sort above
    vs = round(baseline["steady_s_per_step"]
               / best["steady_s_per_step"], 3)
    # The coupled-depth heuristic — overlap at fuse depth 1, boundary
    # depth coupled (what the pre-depth-axis tuner always picked) — is
    # in every race where overlap is legal, so vs_heuristic >= 1.0 by
    # construction; where the geometry gates overlap out entirely, the
    # sequential baseline IS the heuristic.
    heur = next((m for m in measurements
                 if m["halo_overlap"] == "overlap"
                 and m["fuse_steps"] == 1), baseline)
    vs_heur = round(heur["steady_s_per_step"]
                    / best["steady_s_per_step"], 3)

    py, px = (mesh.shape.get("y", 1), mesh.shape.get("x", 1))
    result = {
        "workload": str(workload),
        "shape": list(shape),
        "dtype": str(spec.np_dtype),
        "mesh_axes": [py, px],
        "steps_budget": int(steps),
        "baseline": baseline,
        "heuristic": heur,
        "tuned": best,
        "vs_sequential": vs,
        "vs_heuristic": vs_heur,
        "measurements": measurements,
        "rejected": rejected,
    }
    if store is not None:
        key = plans_mod.fingerprint_for(
            workload, shape, spec.np_dtype, best["path"])
        record = {
            "schema": plans_mod.PLAN_SCHEMA,
            "key": key,
            "choice": {
                "workload": str(workload), "shape": list(shape),
                "dtype": str(spec.np_dtype), "path": best["path"],
                "pack_layout": "-",
                "bucket_rounding": space.BUCKET_POW2,
                "axis_order": best["axis_order"],
                "halo_overlap": best["halo_overlap"],
                "fuse_steps": best["fuse_steps"],
                "boundary_steps": best["boundary_steps"],
                "mesh_axes": [py, px],
                # Sparse winners re-run through a fresh engine at
                # install parity; the tile rides along so the rebuild
                # is exactly the profiled geometry.
                **({"tile": space.SPARSE_SHARDED_TILE}
                   if best["path"].startswith("sparse_sharded:") else {}),
            },
            "heuristic": heur,
            "tuned": best,
            "vs_heuristic": vs_heur,
            "vs_sequential": vs,
            "steps_budget": int(steps),
            "measurements": measurements,
            "rejected": rejected,
        }
        result["plan_file"] = store.save(record)
        result["digest"] = aotcache.digest_for(key)
    trace.event("tune.sharded.done", workload=str(workload),
                path=best["path"], axis_order=best["axis_order"],
                halo_overlap=best["halo_overlap"],
                fuse_steps=best["fuse_steps"],
                boundary_steps=best["boundary_steps"],
                vs_sequential=vs, vs_heuristic=vs_heur)
    return result
