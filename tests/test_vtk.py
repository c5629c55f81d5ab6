"""The frame writer: a board of one-digit values takes the one-pass path
(``"numpy"``), whose bytes are those of the general writer
(``write_vtk_py``) and of the benchmark's plain reference
(``benchmark/reference_snap.py``); any other board takes the general
path (``"python"``), with the bytes it always had."""

import numpy as np
import pytest

from mpi_and_open_mp_tpu.utils.vtk import read_vtk, write_vtk, write_vtk_py

from conftest import bench_module

bench_module("reference")  # what reference_snap imports
reference_snap = bench_module("reference_snap")


@pytest.mark.parametrize("top", [1, 9], ids=["0-1", "0-9"])
@pytest.mark.parametrize("shape", [(1, 1), (1, 9), (9, 1), (13, 21),
                                   (100, 300), (7, 33)],
                         ids=lambda s: "%dx%d" % s)
def test_numpy_path_is_the_reference_text(tmp_path, rng, shape, top):
    board = rng.integers(0, top + 1, size=shape).astype(np.uint8)
    fast, general = tmp_path / "fast.vtk", tmp_path / "general.vtk"
    assert write_vtk(fast, board) == "numpy"
    write_vtk_py(general, board)
    want = reference_snap.vtk_text(board).encode()
    assert fast.read_bytes() == want
    assert general.read_bytes() == want
    np.testing.assert_array_equal(read_vtk(fast), board)


@pytest.mark.parametrize("odd", [10, -1])
def test_values_past_one_digit_take_the_general_path(tmp_path, make_board,
                                                     odd):
    board = make_board(13, 21).astype(np.int32)
    board[4, 7] = odd
    ours, general = tmp_path / "ours.vtk", tmp_path / "general.vtk"
    assert write_vtk(ours, board) == "python"
    write_vtk_py(general, board)
    assert ours.read_bytes() == general.read_bytes()
    assert ours.read_bytes() == reference_snap.vtk_text(board).encode()


@pytest.mark.parametrize("dtype", [np.uint8, np.bool_, np.int8, np.int32,
                                   np.int64, np.float32])
def test_write_vtk_matches_python(tmp_path, make_board, dtype):
    """Whatever the board's dtype, the default writer's bytes are the
    general writer's; float values are printed as ``int32`` truncates
    them, on either path."""
    board = make_board(13, 21).astype(dtype)
    if dtype == np.float32:
        board = board * np.float32(8.75)  # 0 or 8.75, which prints 8
    ours, general = tmp_path / "ours.vtk", tmp_path / "general.vtk"
    assert write_vtk(ours, board) == "numpy"
    write_vtk_py(general, board)
    assert ours.read_bytes() == general.read_bytes()
