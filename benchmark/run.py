#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration and a traffic mix in ``BENCHMARK.json``.
Set-up builds the Life simulation as the CLI does (``apps/life.py
main``: the configuration's layout and mesh, ``impl="auto"``, then
``warmup()``), makes the board from ``--seed`` and makes one untimed run.
The window then drives ``LifeSim.reset()`` and ``LifeSim.run()`` back to
back for ``--seconds``. Once it has closed, a sample of the boards the
window produced, drawn from the seed, is compared cell for cell with the
plain reference (``reference.py``) run on the same chip.

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer ones from a profiler trace and the program's spans. The last
line of standard output is one JSON object; the numbers compared, each
with its limit, are the last lines of standard error and the last key of
that object. No TPU, or fewer chips than the cell asks for: exit 2 and
no result.
"""

from __future__ import annotations

import time

_T0 = time.monotonic()  # set-up is timed from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import numpy as np  # noqa: E402

import suite  # noqa: E402

# The persistent compile cache sits at a fixed path in the checkout, so
# that every run of a cell after its first finds its programs there. The
# program's own cache setting (utils/runtime.py) takes this directory.
CACHE_DIR = os.path.join(suite.BENCH, ".jax_cache")

# The numbers compared, each with its limit (PERF.md §2 says how the
# limits were set).
LIMITS = {"mismatched_cells": 0, "boards_unchecked": 0}

# Final boards kept for the check: all of them while they fit in this.
CHECK_BYTES = 1 << 30

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """The device this cell needs is not there."""


@dataclasses.dataclass
class Window:
    """What the measured window did: each run's start (seconds after the
    window opened) and duration, its latency (its duration, or in an open
    loop the time from its arrival to its final board), and the final
    boards kept for the check."""

    seconds: float = 0.0
    runs: list = dataclasses.field(default_factory=list)
    latency: list = dataclasses.field(default_factory=list)
    kept: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Ctx:
    """Everything a metric reader may read (``metrics/<name>.py``)."""

    config: dict
    setup_s: float
    window: Window
    cells_per_run: int
    spans: list | None = None
    trace: object | None = None


# ------------------------------------------------------------------ inputs


def _seed_rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed % (1 << 64), stream])


def _cfg_cells(path: str) -> np.ndarray:
    """The ``(i, j)`` live cells of a reference ``.cfg`` file (after its
    header ``steps save_steps nx ny``; ``3-life/life2d.c`` format)."""
    with open(path) as fd:
        tok = np.array(fd.read().split(), dtype=np.int64)
    return tok[4:].reshape(-1, 2)


def make_board(config: dict, seed: int) -> np.ndarray:
    """The cell's ``(ny, nx)`` uint8 board for ``seed``."""
    ny, nx = config["ny"], config["nx"]
    init = config["initial"]
    rng = _seed_rng(seed, 0)
    if init["kind"] == "cfg_cells":
        cells = _cfg_cells(os.path.join(config["dir"], init["file"]))
        board = np.zeros((ny, nx), np.uint8)
        board[cells[:, 1] % ny, cells[:, 0] % nx] = 1
        if init.get("seeded_offset"):
            board = np.roll(board, (int(rng.integers(ny)),
                                    int(rng.integers(nx))), (0, 1))
        return board
    if init["kind"] == "soup":
        raw = np.frombuffer(rng.bytes(ny * nx), np.uint8).reshape(ny, nx)
        return (raw < init["live_per_256"]).astype(np.uint8)
    raise ValueError(f"unknown initial board kind {init['kind']!r}")


# ----------------------------------------------------------------- system


def _devices(chips: int, require_tpu: bool) -> list:
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def build_sim(config: dict, board: np.ndarray, devices: list,
              outdir: str | None = None):
    """``LifeSim`` as ``apps/life.py main`` builds it for this layout and
    mesh, with the seeded board in place of the cfg's. A configuration
    whose ``save_steps`` falls inside its run writes its VTK snapshots to
    ``outdir``, which it then needs."""
    from mpi_and_open_mp_tpu.models.life import LifeSim
    from mpi_and_open_mp_tpu.parallel import mesh as mesh_lib
    from mpi_and_open_mp_tpu.utils.config import LifeConfig

    cfg = LifeConfig(steps=config["steps"], save_steps=config["save_steps"],
                     nx=config["nx"], ny=config["ny"],
                     cells=np.zeros((0, 2), np.int64))
    shape = config["mesh"]
    if shape is None:
        mesh = None
    elif len(shape) == 2:
        mesh = mesh_lib.make_mesh_2d(*shape)
    else:
        mesh = mesh_lib.make_mesh_1d(
            shape[0], axis="x" if config["layout"] == "col" else "y")
    snapshots = 0 < config["save_steps"] < config["steps"]
    if snapshots and outdir is None:
        raise ValueError(f"config {config.get('name')!r} saves every "
                         f"{config['save_steps']} steps: it needs an outdir")
    sim = LifeSim(cfg, layout=config["layout"], impl=config["impl"],
                  mesh=mesh, initial_board=board,
                  outdir=outdir if snapshots else None)
    placed = sim.board.sharding.device_set
    if len(placed) != len(devices) or not placed <= set(devices):
        raise RuntimeError(f"the board spans {len(placed)} devices, the "
                           f"cell asks for {len(devices)}")
    return sim


# ----------------------------------------------------------------- window


def arrivals(traffic: dict, seconds: float,
             rng: np.random.Generator) -> np.ndarray | None:
    """Arrival times (seconds after the window opens) of an open-loop mix,
    or ``None`` for a closed loop. Every seed gets the same set of gaps,
    drawn once at the mix's rate, in an order of its own, so that the
    seed changes the order of the work and not its amount."""
    if traffic["loop"] == "closed":
        return None
    if traffic["loop"] != "open":
        raise ValueError(f"traffic {traffic['name']!r}: loop must be "
                         f"'closed' or 'open', not {traffic['loop']!r}")
    rate = float(traffic["rate_per_s"])
    n = int(np.ceil(rate * seconds))
    if traffic.get("arrival", "poisson") == "fixed":
        gaps = np.full(n, 1.0 / rate)
    else:
        gaps = np.random.default_rng(0).exponential(1.0 / rate, n)
    return np.concatenate([[0.0], np.cumsum(rng.permutation(gaps))[:-1]])


def drive(sim, seconds: float, traffic: dict, keep: int,
          rng: np.random.Generator, annotate=None) -> Window:
    """The one generator every traffic mix is read by. A run is
    ``reset()`` then ``run()``; one client sends them, either back to back
    (``loop: closed``) or as they arrive at ``rate_per_s`` (``loop:
    open``: Poisson or ``fixed`` gaps, served in order, each run's latency
    counted from its arrival). Runs start until ``seconds`` have passed;
    the run open at the close is finished and counted. A reservoir of
    ``keep`` final boards, drawn from ``rng``, is kept for the check."""
    mark = annotate or (lambda name: contextlib.nullcontext())
    due = arrivals(traffic, seconds, rng)
    w = Window()
    start = time.perf_counter()
    deadline = start + seconds
    while True:
        i = len(w.runs)
        if due is not None:
            if i == len(due) or start + due[i] >= deadline:
                break
            wait = start + due[i] - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
        t0 = time.perf_counter()
        with mark("bench.reset"):
            sim.reset()
        with mark("bench.run"):
            board = sim.run()
        t1 = time.perf_counter()
        w.runs.append((t0 - start, t1 - t0))
        w.latency.append(t1 - t0 if due is None else t1 - start - due[i])
        if i < keep:
            w.kept.append(board)
        else:
            j = int(rng.integers(i + 1))
            if j < keep:
                w.kept[j] = board
        if t1 >= deadline:
            break
    end = w.runs[-1][0] + w.runs[-1][1] if w.runs else 0.0
    w.seconds = end if due is None else max(end, seconds)
    return w


@contextlib.contextmanager
def traced(workdir: str):
    """Profiler trace and the program's spans (``MOMP_TRACE``) around
    the window; yields a dict that holds both once the block exits."""
    import jax

    from mpi_and_open_mp_tpu.obs import trace as obs_trace

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    spans_path = os.path.join(workdir, "spans.jsonl")
    out = {}
    os.environ["MOMP_TRACE"] = spans_path
    try:
        with jax.profiler.trace(workdir, profiler_options=opts):
            yield out
    finally:
        os.environ.pop("MOMP_TRACE", None)
        obs_trace.reset()
    import devtrace

    (xplane,) = glob.glob(os.path.join(
        workdir, "plugins", "profile", "*", "*.xplane.pb"))
    out["trace"] = devtrace.from_xplane(xplane)
    with open(spans_path) as fd:
        out["spans"] = [json.loads(line) for line in fd if line.strip()]


# ------------------------------------------------------------------- check


def check(config: dict, board: np.ndarray, window: Window,
          device) -> tuple[dict[str, int], int]:
    """The numbers compared, and how many kept boards failed. The numbers
    are the most cells by which a kept board differs from the reference,
    and how many kept boards could not be compared (wrong shape, or none
    kept at all)."""
    import reference

    want = reference.life_steps(board, config["steps"], device=device)
    worst = 0
    unchecked = 0 if window.kept else 1
    failed = 0
    for got in window.kept:
        if got.shape != want.shape:
            unchecked += 1
            failed += 1
            continue
        diff = int(np.count_nonzero(got != want))
        worst = max(worst, diff)
        failed += diff > 0
    return {"mismatched_cells": worst, "boards_unchecked": unchecked}, failed


def _compile_counter() -> list[int]:
    """A one-element list that counts the process's XLA compiles from
    now on (persistent-cache hits do not count)."""
    import jax.monitoring

    count = [0]

    def on_event(event, duration, **_):
        if event == COMPILE_EVENT:
            count[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)
    return count


# -------------------------------------------------------------------- run


def run_cell(spec: dict, workload: str, seed: int, seconds: float,
             trace: bool, config: dict | None = None,
             require_tpu: bool = True) -> dict:
    """One run of ``workload``: the result object of the last line.
    ``config`` replaces the cell's configuration (the tests' small
    sizes); ``require_tpu=False`` lets the tests drive the CPU. Snapshots,
    where the configuration writes them, go to a directory under
    ``TMPDIR`` that is removed at the end."""
    cell = suite.cell(spec, workload)
    config = config or suite.config(spec, cell["config"])
    traffic = suite.traffic(spec, cell["traffic"])
    metric_entries = suite.metrics(spec, workload, trace)
    readers = {m["name"]: suite.reader(spec, m["name"])
               for m in metric_entries}

    import jax

    compiles = _compile_counter()
    devices = _devices(cell["chips"], require_tpu)
    kind = devices[0].device_kind
    if require_tpu:
        suite.peaks(kind)
    board = make_board(config, seed)
    vtkdir = tempfile.mkdtemp(prefix="bench-vtk-")
    workdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        sim = build_sim(config, board, devices, outdir=vtkdir)
        sim.warmup()
        sim.reset()
        sim.run()  # every program and transfer of a run, once, untimed

        keep = max(1, CHECK_BYTES // board.nbytes)
        rng = _seed_rng(seed, 1)
        setup_s = time.monotonic() - _T0
        compiled_before = compiles[0]
        if trace:
            with traced(workdir) as got:
                window = drive(sim, seconds, traffic, keep, rng,
                               annotate=jax.profiler.TraceAnnotation)
        else:
            got = {}
            window = drive(sim, seconds, traffic, keep, rng)
    finally:
        for d in (vtkdir, workdir):
            if d:
                shutil.rmtree(d, ignore_errors=True)
    compiled_in_window = compiles[0] - compiled_before

    stats = [d.memory_stats() or {} for d in devices]
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": max(int(s.get("peak_bytes_in_use", 0))
                                       for s in stats)}
    ctx = Ctx(config=config, setup_s=setup_s, window=window,
              cells_per_run=config["nx"] * config["ny"] * config["steps"],
              spans=got.get("spans"),
              trace=got.get("trace"))
    metrics = {}
    for m in metric_entries:
        value = readers[m["name"]](ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": False, "attempted": len(window.runs), "failed": 0,
              "metrics": metrics, "device": device}
    if trace:
        result["device"]["busy_s"] = ctx.trace.busy_s()
        result["device"]["window_s"] = window.seconds
        result["breakdown"] = ctx.trace.breakdown()

    del sim
    gc.collect()
    numbers, failed = check(config, board, window, devices[0])
    result["correct"] = all(numbers[k] <= LIMITS[k] for k in LIMITS)
    result["failed"] = failed
    durations = sorted(window.latency)
    result["notes"] = {
        "boards_compared": len(window.kept),
        "compiles_in_window": compiled_in_window,
        "latency_ms_min_q1_q2_q3_max": [round(1e3 * durations[int(q * (len(
            durations) - 1))], 3) for q in (0, 0.25, 0.5, 0.75, 1)]}
    result["check"] = {k: {"value": numbers[k], "limit": LIMITS[k]}
                       for k in LIMITS}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    sys.path.insert(0, suite.ROOT)
    import jax

    import mpi_and_open_mp_tpu  # noqa: F401  (the system under test)

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    spec = suite.load()
    try:
        result = run_cell(spec, args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except NoChip as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    for name, value in result["notes"].items():
        print(f"note {name} {value}", file=sys.stderr)
    for name, c in result["check"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
