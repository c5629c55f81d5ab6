"""A run that saves every step writes the reference's VTK series: one
frame before each step ``i < steps``, each byte for byte the text of the
benchmark's plain reference (``benchmark/reference_snap.py``) for the
board at that step; the frame's write span names the writer that ran."""

import json
import os

import numpy as np
import pytest

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
