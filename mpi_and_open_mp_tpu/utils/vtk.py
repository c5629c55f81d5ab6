"""ASCII VTK 3.0 STRUCTURED_POINTS board snapshots.

Output is format-compatible with the reference's ``life_save_vtk``
(``/root/reference/3-life/life_mpi.c:120-148``): header with
``DIMENSIONS nx+1 ny+1 1``, ``CELL_DATA nx*ny``, scalar field ``life``,
one cell value per line in ``ind = i + j*nx`` order (row-major over a
``(ny, nx)`` array). Snapshots land in a ``vtk/`` directory created on
demand, files named ``life_%06d.vtk`` by step index.
"""

from __future__ import annotations

import functools
import os
import re

import numpy as np


def vtk_path(outdir: str | os.PathLike, step: int) -> str:
    return os.path.join(outdir, f"life_{step:06d}.vtk")


@functools.lru_cache(maxsize=16)
def _header(nx: int, ny: int) -> bytes:
    return (
        "# vtk DataFile Version 3.0\n"
        "Created by mpi_and_open_mp_tpu\n"
        "ASCII\n"
        "DATASET STRUCTURED_POINTS\n"
        f"DIMENSIONS {nx + 1} {ny + 1} 1\n"
        "SPACING 1 1 0.0\n"
        "ORIGIN 0 0 0.0\n"
        f"CELL_DATA {nx * ny}\n"
        "SCALARS life int 1\n"
        "LOOKUP_TABLE life_table\n"
    ).encode()


def _one_digit(board: np.ndarray) -> bool:
    """Every value prints as one digit (0..9)."""
    return board.size > 0 and board.min() >= 0 and board.max() <= 9


def write_vtk(path: str | os.PathLike, board: np.ndarray) -> str:
    """Write one board snapshot; returns the path taken, ``"numpy"`` or
    ``"python"``.

    A board of one-digit values (every Life board) is a header and then
    a digit byte and ``\n`` per cell: one vectorised pass fills that
    buffer and one ``write()`` puts it on disk. Any other board takes
    :func:`write_vtk_py`. Both give the same bytes.
    """
    board = np.asarray(board)
    if board.dtype.kind not in "biu":
        board = board.astype(np.int32)  # the values write_vtk_py prints
    if not _one_digit(board):
        write_vtk_py(path, board)
        return "python"
    ny, nx = board.shape
    head = _header(nx, ny)
    out = np.empty(len(head) + 2 * nx * ny, np.uint8)
    out[: len(head)] = np.frombuffer(head, np.uint8)
    cells = out[len(head):].reshape(ny, nx, 2)
    np.add(board, ord("0"), out=cells[..., 0], casting="unsafe")
    cells[..., 1] = ord("\n")
    with open(path, "wb") as fd:
        fd.write(out)
    return "numpy"


def write_vtk_py(path: str | os.PathLike, board: np.ndarray) -> None:
    """The general writer: one ``str`` per cell, any integer values."""
    board = np.asarray(board, dtype=np.int32)
    ny, nx = board.shape
    body = "\n".join(str(v) for v in board.ravel())
    with open(path, "wb") as fd:
        fd.write(_header(nx, ny) + (body + "\n").encode())


_DIMS_RE = re.compile(r"DIMENSIONS\s+(\d+)\s+(\d+)\s+(\d+)")


def read_vtk(path: str | os.PathLike) -> np.ndarray:
    """Read a snapshot back into a ``(ny, nx)`` uint8 array (for tests)."""
    with open(path) as fd:
        text = fd.read()
    m = _DIMS_RE.search(text)
    if not m:
        raise ValueError(f"{path}: no DIMENSIONS header")
    nx, ny = int(m.group(1)) - 1, int(m.group(2)) - 1
    # Cell values start after the LOOKUP_TABLE line.
    body = text.split("LOOKUP_TABLE", 1)[1].split("\n", 1)[1]
    vals = np.array(body.split(), dtype=np.int64)
    if vals.size != nx * ny:
        raise ValueError(f"{path}: expected {nx * ny} cells, got {vals.size}")
    return vals.reshape(ny, nx).astype(np.uint8)
