#!/usr/bin/env python3
"""Bring-up smoke: the Life main path end to end on the chip.

Run from the repo root, one process, nothing else on the chip::

    python chip_smoke.py               # one chip
    python chip_smoke.py --multichip   # one host with four chips

Every phase checks its final board bit for bit against an independent host
oracle: the C++ stepper of ``native/lifeio.cpp``, built here from source
(``make -C native``), or ``ops.life_ops.life_step_numpy`` when that build
fails. A prebuilt library is never loaded.

One chip:
  1. device check: ``jax.devices()[0].platform`` must be ``"tpu"``;
     there is no CPU fallback.
  2. reference flagship: ``configs/gun_big_500x500.cfg`` (500², 10 000
     steps) through the Life CLI's own ``main(argv)``, default layout and
     impl.
  3. BASELINE's pod size on one chip: a seeded 8192² board through
     ``LifeSim(layout="serial", impl="auto")`` for more than
     ``2 * FUSE_MAX_STEPS`` steps, so the fused kernel makes several HBM
     passes.

``--multichip`` runs only the sharded path and what it is compared with:
``LifeSim(layout="cart")`` on a 2x2 mesh and ``layout="row"`` on a 4x1
mesh (impl auto: the bitfused halo path) on the same 8192² board, each
against the one-device run on ``jax.devices()[0]``, with the board's
sharding required to span 4 distinct devices.

Earlier lines are one JSON object per phase. The last line is the contract
line ``{"ok": true, "device": {...}}``; any failure raises and exits
non-zero before it is printed.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import subprocess
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
FLAGSHIP_CFG = os.path.join(REPO, "configs", "gun_big_500x500.cfg")
BIG_BOARD = 8192
BIG_STEPS = 300   # > 2 * FUSE_MAX_STEPS (128), and not a multiple of it
SEED = 8192


def say(**fields) -> None:
    print(json.dumps(fields), flush=True)


def require(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def build_native() -> bool:
    """Build the C++ oracle from source. On failure the library is
    switched off (``LIFE_TPU_NO_NATIVE``) so no stale build can load."""
    r = subprocess.run(["make", "-B", "-C", os.path.join(REPO, "native")],
                       capture_output=True, text=True)
    if r.returncode != 0:
        os.environ["LIFE_TPU_NO_NATIVE"] = "1"
        say(phase="native_build", ok=False,
            note="make -C native failed; oracle is life_step_numpy",
            stderr=r.stderr[-400:])
        return False
    from mpi_and_open_mp_tpu.utils import native

    ok = native.available()
    say(phase="native_build", ok=ok,
        oracle="native/lifeio.cpp (bit-packed)" if ok else "life_step_numpy")
    return ok


def make_oracle(use_native: bool):
    """``(board, steps) -> board`` on the host, independent of the device
    code under test."""
    if use_native:
        from mpi_and_open_mp_tpu.utils import native

        return lambda board, steps: native.life_steps(board, steps, bits=True)

    from mpi_and_open_mp_tpu.ops.life_ops import life_step_numpy

    def numpy_oracle(board, steps):
        b = np.asarray(board, np.uint8)
        for _ in range(steps):
            b = life_step_numpy(b)
        return b

    return numpy_oracle


def seeded_board(n: int, seed: int) -> np.ndarray:
    return (np.random.default_rng(seed).random((n, n)) < 0.3).astype(
        np.uint8)


def phase_cli(cfg_path: str, oracle, workdir: str) -> dict:
    """The Life CLI's ``main(argv)`` in-process with its default layout and
    impl; the final board comes back through ``--save-final``'s VTK and the
    path label through the ``life.run`` span of ``--trace``."""
    from mpi_and_open_mp_tpu.apps import life as life_app
    from mpi_and_open_mp_tpu.obs import report, trace
    from mpi_and_open_mp_tpu.utils import vtk
    from mpi_and_open_mp_tpu.utils.config import load_config

    cfg = load_config(cfg_path)
    outdir = os.path.join(workdir, "cli_vtk")
    trace_path = os.path.join(workdir, "cli.trace.jsonl")
    out = io.StringIO()
    t0 = time.perf_counter()
    try:
        with contextlib.redirect_stdout(out):
            rc = life_app.main([cfg_path, "--outdir", outdir, "--save-final",
                                "--trace", trace_path])
    finally:
        # The CLI turns tracing on for the process; the later phases run
        # untraced.
        os.environ.pop("MOMP_TRACE", None)
        trace.reset()
    wall = time.perf_counter() - t0
    require(rc == 0, f"life CLI exited {rc}")
    run_s = float(out.getvalue().split()[-1])
    (span,) = [r for r in report.load(trace_path)
               if r.get("kind") == "span" and r["name"] == "life.run"]
    final = vtk.read_vtk(vtk.vtk_path(outdir, cfg.steps))
    want = oracle(cfg.board(), cfg.steps)
    require(np.array_equal(final, want),
            f"CLI final board differs from the oracle in "
            f"{int((final != want).sum())} cells")
    return {"phase": "cli", "cfg": os.path.basename(cfg_path),
            "board": list(cfg.shape), "steps": cfg.steps,
            "layout": span["attrs"]["layout"], "impl": span["attrs"]["impl"],
            "native_path": span["attrs"]["plan"], "parity": True,
            "run_s": run_s, "setup_s": wall - run_s,
            "setup_is": "compile + config load + VTK writes"}


def _timed_run(sim, steps: int) -> tuple[float, float]:
    """(first_call_s, warm_run_s): the first call compiles and runs once;
    the warm run starts from the reset board."""
    t0 = time.perf_counter()
    sim.warmup()
    first = time.perf_counter() - t0
    sim.reset()
    sim.sync()
    t0 = time.perf_counter()
    sim.step(steps)
    sim.sync()
    return first, time.perf_counter() - t0


def phase_board(n: int, steps: int, seed: int, oracle) -> dict:
    """A seeded n² board through ``LifeSim(serial, auto)`` on one device."""
    import jax

    from mpi_and_open_mp_tpu.models.life import LifeSim
    from mpi_and_open_mp_tpu.ops import bitlife
    from mpi_and_open_mp_tpu.ops.pallas_life import native_path
    from mpi_and_open_mp_tpu.utils.config import LifeConfig

    on_tpu = jax.default_backend() == "tpu"
    board = seeded_board(n, seed)
    cfg = LifeConfig(steps=steps, save_steps=0, nx=n, ny=n,
                     cells=np.zeros((0, 2), np.int64))
    sim = LifeSim(cfg, layout="serial", impl="auto", initial_board=board)
    path = (native_path((n, n), on_tpu=on_tpu) if sim.impl == "pallas"
            else sim.impl)
    if on_tpu:
        require(sim.impl == "pallas" and path == "fused",
                f"{n}² took impl={sim.impl} path={path}, not pallas/fused")
        require(steps > 2 * bitlife.FUSE_MAX_STEPS,
                "steps must exceed 2 * FUSE_MAX_STEPS")
    first, warm = _timed_run(sim, steps)
    got = sim.collect()
    want = oracle(board, steps)
    require(np.array_equal(got, want),
            f"{n}² board differs from the oracle in "
            f"{int((got != want).sum())} cells")
    return {"phase": "board", "board": [n, n], "steps": steps, "seed": seed,
            "impl": sim.impl, "native_path": path, "parity": True,
            "first_call_s": first, "first_call_is": "compile + one run",
            "warm_run_s": warm, "cups": n * n * steps / warm}


def _block_probe(sim, steps: int) -> dict:
    """Whether ``jax.block_until_ready`` alone waits for a sharded board:
    time step(k) + block, then a one-element fetch after it, at k and 4k.
    A block that waits grows with k and leaves the fetch nothing to wait
    for."""
    import jax

    rows = {}
    for k in (steps, 4 * steps):
        sim.reset()
        sim.sync()
        t0 = time.perf_counter()
        sim.step(k)
        jax.block_until_ready(sim.board)
        t1 = time.perf_counter()
        jax.device_get(sim.board.addressable_shards[0].data[:1, :1])
        rows[k] = {"block_s": t1 - t0, "fetch_after_s": time.perf_counter() - t1}
    return {str(k): v for k, v in rows.items()}


def phase_multichip(n: int, steps: int, seed: int, oracle,
                    devices: int = 4) -> list[dict]:
    """cart 2x2 and row 4x1 against the one-device run, bit for bit."""
    import jax

    from mpi_and_open_mp_tpu.models.life import LifeSim
    from mpi_and_open_mp_tpu.parallel import mesh as mesh_lib
    from mpi_and_open_mp_tpu.utils.config import LifeConfig

    on_tpu = jax.default_backend() == "tpu"
    require(len(jax.devices()) >= devices,
            f"need {devices} devices, have {len(jax.devices())}")
    board = seeded_board(n, seed)
    cfg = LifeConfig(steps=steps, save_steps=0, nx=n, ny=n,
                     cells=np.zeros((0, 2), np.int64))

    one = LifeSim(cfg, layout="serial", impl="auto", initial_board=board)
    require(one.board.sharding.device_set == {jax.devices()[0]},
            "one-device board is not on jax.devices()[0]")
    first, warm = _timed_run(one, steps)
    ref = one.collect()
    want = oracle(board, steps)
    require(np.array_equal(ref, want),
            f"one-device board differs from the oracle in "
            f"{int((ref != want).sum())} cells")
    rows = [{"phase": "one_device", "board": [n, n], "steps": steps,
             "impl": one.impl, "parity_vs_oracle": True,
             "first_call_s": first, "warm_run_s": warm}]
    del one

    py, px = mesh_lib.dims_create(devices, 2)
    for layout, mesh in (("cart", mesh_lib.make_mesh_2d(py, px)),
                         ("row", mesh_lib.make_mesh_1d(devices, axis="y"))):
        sim = LifeSim(cfg, layout=layout, impl="auto", mesh=mesh,
                      initial_board=board)
        placed = sim.board.sharding.device_set
        require(len(placed) == devices,
                f"{layout}: board on {len(placed)} devices, not {devices}")
        if on_tpu:
            require(sim.impl == "bitfused",
                    f"{layout}: impl={sim.impl}, not the bitfused halo path")
        first, warm = _timed_run(sim, steps)
        got = sim.collect()
        require(np.array_equal(got, ref),
                f"{layout} {dict(mesh.shape)} differs from the one-device "
                f"board in {int((got != ref).sum())} cells")
        rows.append({"phase": f"multichip_{layout}",
                     "mesh": dict(mesh.shape), "impl": sim.impl,
                     "plan": getattr(sim, "plan_note", sim.impl),
                     "devices": sorted(d.id for d in placed),
                     "bit_identical_to_one_device": True,
                     "first_call_s": first, "warm_run_s": warm,
                     "block_until_ready": _block_probe(sim, steps)})
        del sim
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--multichip", action="store_true",
                    help="run only the 4-chip cart/row comparison")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    from mpi_and_open_mp_tpu.utils.runtime import enable_compile_cache

    say(phase="device", platform=dev.platform, kind=dev.device_kind,
        count=len(jax.devices()), jax=jax.__version__,
        compile_cache=enable_compile_cache())
    oracle = make_oracle(build_native())
    if args.multichip:
        for row in phase_multichip(BIG_BOARD, BIG_STEPS, SEED, oracle):
            say(**row)
    else:
        with tempfile.TemporaryDirectory() as workdir:
            say(**phase_cli(FLAGSHIP_CFG, oracle, workdir))
        say(**phase_board(BIG_BOARD, BIG_STEPS, SEED, oracle))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
