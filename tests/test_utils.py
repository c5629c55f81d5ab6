"""Config parsing, VTK IO, decomposition, and dims_create semantics."""

import os

import numpy as np
import pytest

from mpi_and_open_mp_tpu.parallel.mesh import decomposition, dims_create
from mpi_and_open_mp_tpu.utils.config import (
    config_from_board,
    load_config_py,
    save_config,
)
from mpi_and_open_mp_tpu.utils.vtk import read_vtk, write_vtk, write_vtk_py

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")


def test_load_glider():
    cfg = load_config_py(os.path.join(FIXTURES, "glider_10x10.cfg"))
    assert (cfg.steps, cfg.save_steps, cfg.nx, cfg.ny) == (100, 25, 10, 10)
    board = cfg.board()
    assert board.shape == (10, 10)
    assert board.sum() == 5
    # (i, j) -> board[j, i]
    assert board[2, 0] == 1 and board[0, 1] == 1


def test_load_empty():
    cfg = load_config_py(os.path.join(FIXTURES, "empty_10x10.cfg"))
    assert cfg.cells.shape == (0, 2)
    assert cfg.board().sum() == 0


def test_config_roundtrip(tmp_path, make_board):
    board = make_board(12, 7)
    cfg = config_from_board(board, steps=42, save_steps=6)
    path = tmp_path / "rt.cfg"
    save_config(path, cfg)
    cfg2 = load_config_py(path)
    assert (cfg2.steps, cfg2.save_steps, cfg2.nx, cfg2.ny) == (42, 6, 7, 12)
    np.testing.assert_array_equal(cfg2.board(), board)


def test_vtk_roundtrip(tmp_path, make_board):
    board = make_board(9, 14)
    path = tmp_path / "life_000000.vtk"
    write_vtk_py(path, board)
    np.testing.assert_array_equal(read_vtk(path), board)
    text = path.read_text()
    assert "DIMENSIONS 15 10 1" in text
    assert f"CELL_DATA {9 * 14}" in text


def test_vtk_golden_file(tmp_path):
    """Committed golden frame (the in-repo mirror of the reference's
    `4-life/vtk/life_000000.vtk` artifact): the writer's byte-level
    output for the glider fixture is pinned, so any format drift —
    header, ordering, line endings — fails here even when the reference
    tree is absent. Both paths (the one-pass default and the general
    writer) must reproduce it exactly, and the reader must invert it."""
    golden = os.path.join(FIXTURES, "golden_glider_000000.vtk")
    cfg = load_config_py(os.path.join(FIXTURES, "glider_10x10.cfg"))
    np.testing.assert_array_equal(read_vtk(golden), cfg.board())

    for write in (write_vtk, write_vtk_py):
        ours = tmp_path / f"{write.__name__}.vtk"
        write(ours, cfg.board())
        assert ours.read_bytes() == open(golden, "rb").read(), write.__name__


@pytest.mark.parametrize("n,p", [(500, 8), (10, 3), (28, 28), (7, 2), (100, 1)])
def test_decomposition_reference_semantics(n, p):
    """Floor chunks, last shard absorbs the remainder (3-life/life_mpi.c:178-183)."""
    spans = [decomposition(n, p, k) for k in range(p)]
    chunk = n // p
    for k, (start, stop) in enumerate(spans):
        assert start == k * chunk
        if k < p - 1:
            assert stop - start == chunk
    assert spans[-1][1] == n
    # Exact cover, no overlap.
    covered = sorted(i for s, e in spans for i in range(s, e))
    assert covered == list(range(n))


@pytest.mark.parametrize(
    "n,expect",
    [(1, (1, 1)), (4, (2, 2)), (8, (4, 2)), (12, (4, 3)), (7, (7, 1)), (36, (6, 6))],
)
def test_dims_create(n, expect):
    dims = dims_create(n, 2)
    assert dims == expect
    assert dims[0] * dims[1] == n


# ------------------------------------------------------------- anchor_sync


def _probes_captured(monkeypatch):
    """Patch jax.device_get to record what anchor_sync fetches."""
    import jax

    calls = []
    real = jax.device_get

    def spy(x):
        calls.append(x)
        return real(x)

    monkeypatch.setattr(jax, "device_get", spy)
    return calls


def test_anchor_sync_probes_mesh_placed_leaves(monkeypatch):
    """Mesh-placed leaves get ONE batched one-element probe fetch."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mpi_and_open_mp_tpu.parallel import mesh as mesh_lib
    from mpi_and_open_mp_tpu.utils.timing import anchor_sync

    mesh = mesh_lib.make_mesh_1d(8, axis="y")
    a = jax.device_put(jnp.ones((16, 4)), NamedSharding(mesh, P("y")))
    b = jax.device_put(jnp.ones((8,)), NamedSharding(mesh, P("y")))
    calls = _probes_captured(monkeypatch)
    anchor_sync({"a": a, "b": b})
    assert len(calls) == 1  # batched: one RTT, not one per leaf
    probes = calls[0]
    assert [p.shape for p in probes] == [(1, 1), (1,)]


def test_anchor_sync_skips_single_device_unless_fetch_all(monkeypatch):
    """SingleDeviceSharding leaves are block-only by default (the fetch
    would cost a host RTT inside timing brackets); fetch_all probes them."""
    import jax.numpy as jnp

    from mpi_and_open_mp_tpu.utils.timing import anchor_sync

    x = jnp.ones((4, 4)) + 0  # committed single-device array
    calls = _probes_captured(monkeypatch)
    anchor_sync(x)
    assert calls == []
    anchor_sync(x, fetch_all=True)
    assert len(calls) == 1 and calls[0][0].shape == (1, 1)


def test_anchor_sync_skips_empty_shards_and_non_arrays(monkeypatch):
    """Zero-size shards can't be probed (guard), and non-jax leaves
    (numpy, python scalars) pass through untouched."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mpi_and_open_mp_tpu.parallel import mesh as mesh_lib
    from mpi_and_open_mp_tpu.utils.timing import anchor_sync

    mesh = mesh_lib.make_mesh_1d(8, axis="y")
    empty = jax.device_put(jnp.zeros((0, 3)), NamedSharding(mesh, P()))
    calls = _probes_captured(monkeypatch)
    anchor_sync({"e": empty, "np": np.ones(3), "i": 7}, fetch_all=True)
    assert calls == []  # nothing probeable -> no fetch at all


def test_vtk_golden_cross_compat_with_reference_artifact(tmp_path):
    """The reference repo commits an actual VTK frame
    (`4-life/vtk/life_000000.vtk` — p46gun_big.cfg at step 0, verified by
    content). Our reader must consume it exactly, and our writer must
    reproduce it byte-for-byte apart from line 2's creator comment — the
    strongest cross-compatibility evidence available: artifacts produced
    by the reference's C writer and by this framework interchange."""
    ref_path = "/root/reference/4-life/vtk/life_000000.vtk"
    ref_cfg = "/root/reference/4-life/p46gun_big.cfg"
    if not os.path.exists(ref_path):
        pytest.skip("reference tree not present")
    # Our parser consumes the reference's own cfg, and our reader its
    # committed frame; the two must agree (the frame is step 0).
    cfg = load_config_py(ref_cfg)
    board = read_vtk(ref_path)
    np.testing.assert_array_equal(board, cfg.board())

    want = open(ref_path).read().splitlines()
    for write in (write_vtk, write_vtk_py):
        ours = tmp_path / f"{write.__name__}.vtk"
        write(ours, board)
        got = ours.read_text().splitlines()
        assert len(got) == len(want)
        for i, (g, w) in enumerate(zip(got, want)):
            if i == 1:  # creator comment line differs by design
                continue
            assert g == w, f"{write.__name__} line {i}: {g!r} != {w!r}"


def test_config_cells_wrap_like_reference_ind_macro(tmp_path):
    """Out-of-range and negative cell coordinates wrap onto the torus —
    the reference's loader writes cells through its `ind` macro
    (`3-life/life2d.c:9,69`: `((i+nx)%nx) + ((j+ny)%ny)*nx`), so a cfg
    listing (9,9) on a 4x4 board lights (1,1), and (-1,2) lights (3,2).
    Python's % matches the macro for ANY magnitude, including beyond
    -nx where the macro's single +nx would not — pinned here so a
    future loader rewrite keeps the quirk."""
    p = tmp_path / "wrap.cfg"
    p.write_text("5\n1\n4 4\n9 9\n-1 2\n")
    cfg = load_config_py(p)
    b = cfg.board()
    assert b.sum() == 2
    assert b[1, 1] == 1  # (i=9, j=9) -> (1, 1)
    assert b[2, 3] == 1  # (i=-1, j=2) -> col 3, row 2


def test_write_csv_rows(tmp_path):
    """The sweeps' crash-proof per-point writer: creates the directory,
    rewrites whole, trailing newline (artifact hygiene)."""
    from mpi_and_open_mp_tpu.utils.timing import write_csv_rows

    out = tmp_path / "deep" / "rows.csv"
    write_csv_rows(str(out), ["a,b", "1,2"])
    assert out.read_text() == "a,b\n1,2\n"
    write_csv_rows(str(out), ["a,b", "1,2", "3,4"])  # grows idempotently
    assert out.read_text() == "a,b\n1,2\n3,4\n"
