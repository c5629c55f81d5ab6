#!/usr/bin/env python3
"""The plain reference of a run's VTK series.

Independent of the code under test: nothing here imports the program.
The reference's ``3-life/life_mpi.c`` writes a frame before every step
``i < steps`` with ``i % save_steps == 0`` (``life_save_vtk``,
``:120-148``): an ASCII VTK 3.0 ``STRUCTURED_POINTS`` header, then one
``%d`` line per cell in ``i + j * nx`` order, to ``life_%06d.vtk``.
:func:`series` gives the board at each such step, stepped by
``reference.life_steps``; :func:`vtk_text` gives the bytes of its frame.
Line 2 of the header is a free comment in VTK 3.0; the frames compared
carry this repository's (``tests/fixtures/golden_glider_000000.vtk``),
every other byte is ``life_save_vtk``'s.

Compare a directory of frames with the series of a cfg's board::

    python3 benchmark/reference_snap.py --cfg benchmark/configs/gun_300x100.cfg --frames vtk

and read the control, the final board of the reference with the torus
wrap left out, for seeds of a configuration::

    python3 benchmark/reference_snap.py --config p46gun --control-seeds 1,2,3

Each prints one JSON object.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

import reference

CREATOR = "Created by mpi_and_open_mp_tpu"


def saved_steps(steps: int, save_steps: int) -> list[int]:
    """The steps ``i < steps`` a frame is written before."""
    if save_steps <= 0:
        return []
    return [i for i in range(steps) if i % save_steps == 0]


def series(board: np.ndarray, steps: int, save_steps: int, device=None):
    """``(step, board)`` at every saved step, in order."""
    b = np.asarray(board, np.uint8)
    at = 0
    for i in saved_steps(steps, save_steps):
        if i > at:
            b = reference.life_steps(b, i - at, device=device)
            at = i
        yield i, b


def vtk_text(board: np.ndarray) -> str:
    """The frame ``life_save_vtk`` writes for a ``(ny, nx)`` board."""
    ny, nx = board.shape
    head = ("# vtk DataFile Version 3.0\n"
            f"{CREATOR}\n"
            "ASCII\n"
            "DATASET STRUCTURED_POINTS\n"
            f"DIMENSIONS {nx + 1} {ny + 1} 1\n"
            "SPACING 1 1 0.0\n"
            "ORIGIN 0 0 0.0\n"
            f"CELL_DATA {nx * ny}\n"
            "SCALARS life int 1\n"
            "LOOKUP_TABLE life_table\n")
    cells = []
    for row in np.asarray(board).tolist():  # j = 0 .. ny-1
        for v in row:  # i = 0 .. nx-1
            cells.append("%d\n" % v)
    return head + "".join(cells)


def frame_name(step: int) -> str:
    return "life_%06d.vtk" % step


def compare(frames_dir: str, board: np.ndarray, steps: int, save_steps: int,
            device=None) -> dict:
    """How the files in ``frames_dir`` stand against the series of
    ``board``: frames expected, equal byte for byte, differing, missing,
    and files that no saved step names."""
    want = saved_steps(steps, save_steps)
    names = {frame_name(i) for i in want}
    out = {"frames_expected": len(want), "frames_equal": 0,
           "frames_differing": 0, "frames_missing": 0,
           "files_extra": sorted(set(os.listdir(frames_dir)) - names)}
    for i, b in series(board, steps, save_steps, device):
        path = os.path.join(frames_dir, frame_name(i))
        if not os.path.exists(path):
            out["frames_missing"] += 1
            continue
        with open(path, "rb") as fd:
            same = fd.read() == vtk_text(b).encode()
        out["frames_equal" if same else "frames_differing"] += 1
    return out


def read_cfg(path: str) -> tuple[int, int, np.ndarray]:
    """``steps``, ``save_steps`` and the board of a reference ``.cfg``
    (``3-life/life2d.c`` format: ``steps save_steps nx ny`` then live
    ``i j`` pairs, wrapped onto the torus)."""
    with open(path) as fd:
        tok = np.array(fd.read().split(), dtype=np.int64)
    steps, save_steps, nx, ny = (int(t) for t in tok[:4])
    cells = tok[4:].reshape(-1, 2)
    board = np.zeros((ny, nx), np.uint8)
    board[cells[:, 1] % ny, cells[:, 0] % nx] = 1
    return steps, save_steps, board


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--cfg", help="the run's .cfg, with --frames")
    ap.add_argument("--frames", help="the directory of the run's frames")
    ap.add_argument("--config", help="a configuration of BENCHMARK.json, "
                    "with --control-seeds")
    ap.add_argument("--control-seeds", help="comma-separated seeds")
    args = ap.parse_args(argv)
    if args.cfg and args.frames:
        steps, save_steps, board = read_cfg(args.cfg)
        result = compare(args.frames, board, steps, save_steps)
        print(json.dumps(result))
        whole = (result["frames_equal"] == result["frames_expected"]
                 and not result["files_extra"])
        return 0 if whole else 1
    if args.config and args.control_seeds:
        import run
        import suite

        config = suite.config(suite.load(), args.config)
        mismatched = {}
        for seed in (int(s) for s in args.control_seeds.split(",")):
            board = run.make_board(config, seed)
            want = reference.life_steps(board, config["steps"])
            got = reference.life_steps_dead_edge(board, config["steps"])
            mismatched[seed] = int(np.count_nonzero(got != want))
        print(json.dumps({"control_mismatched_cells": mismatched}))
        return 0
    ap.error("give --cfg and --frames, or --config and --control-seeds")


if __name__ == "__main__":
    sys.exit(main())
