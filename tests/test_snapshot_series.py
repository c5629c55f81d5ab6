"""A run that saves writes the reference's VTK series: one frame before
each step ``i < steps`` with ``i % save_steps == 0``, each byte for byte
the text of the benchmark's plain reference
(``benchmark/reference_snap.py``) for the board at that step, whether
the frames come in chunks or one at a time; the frame's write span names
the writer that ran."""

import json
import os

import numpy as np
import pytest

from mpi_and_open_mp_tpu.models import life
from mpi_and_open_mp_tpu.models.life import LifeSim
from mpi_and_open_mp_tpu.parallel import mesh as mesh_lib
from mpi_and_open_mp_tpu.utils import vtk
from mpi_and_open_mp_tpu.utils.config import config_from_board

from conftest import bench_module, oracle_n

bench_module("reference")  # what reference_snap imports
reference_snap = bench_module("reference_snap")

STEPS = 30


@pytest.mark.parametrize("impl", ["auto", "bitfused"])
def test_every_step_frame_is_the_reference_text(make_board, tmp_path, impl):
    """The cell ``p46gun.snap`` at a small size: row layout on a
    1-device mesh, ``save_steps`` 1. On the CPU ``auto`` resolves to the
    halo path; ``bitfused`` runs its kernel in interpret mode."""
    board = make_board(40, 64)
    cfg = config_from_board(board, steps=STEPS, save_steps=1)
    outdir = tmp_path / "vtk"
    sim = LifeSim(cfg, layout="row", impl=impl,
                  mesh=mesh_lib.make_mesh_1d(1, axis="y"), outdir=outdir)
    final = sim.run()

    assert sorted(os.listdir(outdir)) == [
        f"life_{i:06d}.vtk" for i in range(STEPS)]
    frames = list(reference_snap.series(board, STEPS, 1))
    assert [i for i, _ in frames] == list(range(STEPS))
    for i, want in frames:
        got = (outdir / f"life_{i:06d}.vtk").read_bytes()
        assert got == reference_snap.vtk_text(want).encode(), f"step {i}"
    np.testing.assert_array_equal(final, oracle_n(board, STEPS))
    np.testing.assert_array_equal(frames[-1][1], oracle_n(board, STEPS - 1))


def _assert_series(outdir, board, steps, save_steps, start=0):
    """``outdir`` holds exactly the reference's frames of steps
    ``start`` and on, each byte for byte its text."""
    want = [(i, b) for i, b in reference_snap.series(board, steps, save_steps)
            if i >= start]
    assert sorted(os.listdir(outdir)) == [f"life_{i:06d}.vtk"
                                          for i, _ in want]
    for i, b in want:
        got = (outdir / f"life_{i:06d}.vtk").read_bytes()
        assert got == reference_snap.vtk_text(b).encode(), f"step {i}"


@pytest.mark.parametrize("steps,save_steps,resume_at", [
    (30, 1, 0),
    (50, 7, 0),  # the last interval is 1 step
    (40, 3, 0),
    (50, 7, 10),  # resumed between save points
])
def test_chunked_frames_are_the_reference_text(make_board, tmp_path,
                                               monkeypatch, steps,
                                               save_steps, resume_at):
    """``run()``'s chunked path in chunks of at most four frames: every
    frame is the reference's text for its step, and the
    final board is the oracle's. A run resumed from a snapshot at a step
    between save points advances to the next save point first."""
    board = make_board(20, 24)
    monkeypatch.setattr(life, "_FRAME_CHUNK_BYTES", 4 * board.size)
    cfg = config_from_board(board, steps=steps, save_steps=save_steps)
    outdir = tmp_path / "vtk"
    kw = dict(layout="serial", impl="roll", outdir=outdir)
    if resume_at:
        snap = str(tmp_path / "start.vtk")
        vtk.write_vtk(snap, oracle_n(board, resume_at))
        sim = LifeSim.from_snapshot(cfg, snap, resume_at, **kw)
    else:
        sim = LifeSim(cfg, **kw)
    lead, chunks = sim._frame_chunks(save=True)
    assert lead == -resume_at % save_steps
    assert len(chunks) > 1
    sim.warmup()
    final = sim.run()
    _assert_series(outdir, board, steps, save_steps, start=resume_at)
    np.testing.assert_array_equal(final, oracle_n(board, steps))


def _retraces():
    from mpi_and_open_mp_tpu.obs import metrics

    return {k: v for k, v in metrics.snapshot()["counters"].items()
            if k.startswith("jit.retrace")}


@pytest.mark.parametrize("path", ["chunked", "checkpoint", "guard"])
def test_frames_span_marks_the_chunked_path(make_board, tmp_path,
                                            monkeypatch, path):
    """``life.frames`` spans come from the chunked path alone, and their
    ``frames`` add up to the run's ``life.snapshot`` spans; after
    ``warmup()`` the run traces no program. Checkpoints or guards keep
    the stop at every saved step: no ``life.frames`` span, the same
    frames."""
    from mpi_and_open_mp_tpu.obs import trace

    board = make_board(16, 24)
    monkeypatch.setattr(life, "_FRAME_CHUNK_BYTES", 3 * board.size)
    cfg = config_from_board(board, steps=12, save_steps=3)
    kw = {}
    if path == "checkpoint":
        kw["checkpoint_dir"] = tmp_path / "ckpt"
    elif path == "guard":
        monkeypatch.setenv("MOMP_GUARD", "1")
    sim = LifeSim(cfg, layout="serial", impl="roll",
                  outdir=tmp_path / "vtk", **kw)
    sim.warmup()
    sink = tmp_path / "spans.jsonl"
    monkeypatch.setenv("MOMP_TRACE", str(sink))
    trace.reset()
    before = _retraces()
    try:
        final = sim.run()
    finally:
        trace.reset()
    recs = [json.loads(line) for line in sink.read_text().splitlines()]
    chunks = [r["attrs"] for r in recs if r["name"] == "life.frames"]
    snaps = [r for r in recs if r["name"] == "life.snapshot"]
    assert len(snaps) == 4
    if path == "chunked":
        assert [(c["start"], c["frames"], c["wire_bytes"]) for c in chunks] \
            == [(0, 3, 3 * board.size), (9, 1, board.size)]
        assert sum(c["frames"] for c in chunks) == len(snaps)
        assert _retraces() == before
    else:
        assert chunks == []
    _assert_series(tmp_path / "vtk", board, 12, 3)
    np.testing.assert_array_equal(final, oracle_n(board, 12))


@pytest.fixture
def writer(request, monkeypatch):
    """``numpy`` (a Life board, as it comes) or ``python`` (the
    one-digit check made to refuse)."""
    if request.param == "python":
        monkeypatch.setattr(vtk, "_one_digit", lambda board: False)
    return request.param


@pytest.mark.parametrize("writer", ["numpy", "python"], indirect=True)
def test_vtk_write_span_names_its_writer(make_board, tmp_path, monkeypatch,
                                         writer):
    from mpi_and_open_mp_tpu.obs import trace

    sink = tmp_path / "spans.jsonl"
    monkeypatch.setenv("MOMP_TRACE", str(sink))
    trace.reset()
    try:
        board = make_board(16, 24)
        cfg = config_from_board(board, steps=3, save_steps=1)
        sim = LifeSim(cfg, layout="serial", impl="roll",
                      outdir=tmp_path / "vtk")
        sim.run()
    finally:
        trace.reset()
    writes = [r for r in map(json.loads, sink.read_text().splitlines())
              if r["name"] == "life.vtk_write"]
    assert len(writes) == 3
    for r in writes:
        assert r["attrs"]["writer"] == writer
        assert r["attrs"]["bytes"] == len(reference_snap.vtk_text(board))
    for i, want in reference_snap.series(board, 3, 1):
        path = tmp_path / "vtk" / f"life_{i:06d}.vtk"
        assert path.read_bytes() == reference_snap.vtk_text(want).encode()
