"""Sparse x sharded engine (PR 16): activation crossing shards, the
dead-boundary exchange skip, the crossover/kill-switch fallbacks, and
the sentinel/ledger provenance plumbing.

Everything runs on the conftest 8-virtual-device CPU mesh; parity is
always against the NumPy oracle (``conftest.oracle_n``) or the dense
sharded runner.
"""

import numpy as np
import pytest

from tests.conftest import oracle_n

from mpi_and_open_mp_tpu import stencils
from mpi_and_open_mp_tpu.parallel import mesh as mesh_lib
from mpi_and_open_mp_tpu.stencils import engine as stencil_engine
from mpi_and_open_mp_tpu.stencils import sparse_sharded
from mpi_and_open_mp_tpu.stencils.sparse_sharded import SparseShardedEngine

LIFE = stencils.get("life")

GLIDER = np.array([[0, 1, 0], [0, 0, 1], [1, 1, 1]], np.uint8)


def _mesh(layout):
    if layout == "cart":
        return mesh_lib.make_mesh_2d()
    return mesh_lib.make_mesh_1d(axis="x" if layout == "col" else "y")


def _glider_board():
    """A 128^2 board whose glider crosses every row- and col-shard edge
    over 80 steps (8-way row shards are 16 rows deep; the glider starts
    at the origin corner and walks the diagonal), plus a blinker and a
    block to keep oscillating and settled regions in play."""
    board = np.zeros((128, 128), np.uint8)
    board[1:4, 1:4] = GLIDER
    board[60, 60:63] = 1
    board[100:102, 36:38] = 1
    board[100:102, 38] = 0  # make it a domino -> dies, then quiet
    return board


@pytest.mark.parametrize("layout", ["row", "col", "cart"])
def test_glider_crosses_shard_edges(layout):
    board = _glider_board()
    eng = SparseShardedEngine(LIFE, board, mesh=_mesh(layout),
                              layout=layout, tile=16)
    done = 0
    # Awkward checkpoints on purpose: 5 and 37 land mid-fused-round, so
    # the tail (fuse < engine.fuse) program paths get parity coverage.
    for n in (5, 16, 37, 80):
        eng.step(n - done)
        done = n
        np.testing.assert_array_equal(eng.snapshot(), oracle_n(board, n))
    assert eng.engine_stamp.startswith("sparse-sharded:")
    assert eng.engine_stamp == f"sparse-sharded:{layout}:t16"
    c = eng.counters()
    assert c["sparse_steps"] > 0
    assert c["tiles_skipped"] > c["tiles_stepped"]


def test_exchange_skip_is_bit_exact_and_counted():
    """Interior-only activity: the twin with the skip enabled must ship
    no ghosts on dead-boundary rounds yet stay bit-identical to the
    always-exchange twin (the zero sentinel replaces provably-zero
    ghosts)."""
    board = np.zeros((256, 256), np.uint8)
    # Blinkers in shard interiors (row shards are 32 deep): rows 8 and
    # 72 keep every oscillation >= 4 rows from any shard boundary band.
    board[8, 100:103] = 1
    board[72, 40:43] = 1
    mesh = mesh_lib.make_mesh_1d()
    kw = dict(mesh=mesh, layout="row", tile=32, fuse=4)
    on = SparseShardedEngine(LIFE, board, **kw)
    off = SparseShardedEngine(LIFE, board, exchange_skip=False, **kw)
    on.step(48)
    off.step(48)
    np.testing.assert_array_equal(on.snapshot(), off.snapshot())
    np.testing.assert_array_equal(on.snapshot(), oracle_n(board, 48))
    assert on.counters()["exchange_skips"] > 0
    assert off.counters()["exchange_skips"] == 0
    assert off.counters()["exchange_rounds"] > 0


def test_fused_wake_survives_oscillators():
    """A period-2 blinker with fuse=2: initial-vs-final diffing would
    see identical frames and put the tile to sleep mid-oscillation; the
    consecutive-state wake diff must keep it alive."""
    board = np.zeros((128, 128), np.uint8)
    board[40, 40:43] = 1
    eng = SparseShardedEngine(LIFE, board, mesh=mesh_lib.make_mesh_1d(),
                              layout="row", tile=16, fuse=2)
    eng.step(13)  # odd: ends mid-period
    np.testing.assert_array_equal(eng.snapshot(), oracle_n(board, 13))
    assert eng.active.any(), "oscillating tile fell asleep"


def test_settled_board_stops_dispatching():
    """A still life settles the whole mask; subsequent steps are pure
    bookkeeping (settled_steps) and stay bit-exact."""
    board = np.zeros((128, 128), np.uint8)
    board[40:42, 40:42] = 1  # block
    eng = SparseShardedEngine(LIFE, board, mesh=mesh_lib.make_mesh_1d(),
                              layout="row", tile=16)
    eng.step(96)
    np.testing.assert_array_equal(eng.snapshot(), board)
    assert eng.counters()["settled_steps"] > 0
    assert not eng.active.any()


def test_crossover_falls_back_dense(make_board):
    """A dense random board exceeds the crossover fraction every round:
    all steps run the dense sharded runner, stamped dense:crossover,
    still oracle-exact."""
    board = make_board(128, 128, density=0.35)
    eng = SparseShardedEngine(LIFE, board, mesh=mesh_lib.make_mesh_1d(),
                              layout="row", tile=16, crossover=0.05)
    eng.step(8)
    np.testing.assert_array_equal(eng.snapshot(), oracle_n(board, 8))
    assert eng.engine_stamp == "dense:crossover"
    assert eng.counters()["sparse_steps"] == 0


def test_bit_identity_vs_dense_sharded():
    """The reassembled sparse-sharded board equals the dense sharded
    schedule bit-for-bit."""
    board = _glider_board()
    mesh = mesh_lib.make_mesh_1d()
    eng = SparseShardedEngine(LIFE, board, mesh=mesh, layout="row",
                              tile=16)
    eng.step(64)
    run, _plan = stencil_engine.make_sharded_runner(
        LIFE, mesh, "row", board.shape)
    import jax
    from jax.sharding import NamedSharding

    dev = jax.device_put(
        np.asarray(board),
        NamedSharding(mesh, stencil_engine.sharded_pspec("row", 1)))
    np.testing.assert_array_equal(eng.snapshot(), np.asarray(run(dev, 64)))


def test_kill_switch_downgrades_to_dense_sharded(monkeypatch):
    monkeypatch.setenv(sparse_sharded.ENV_SPARSE_SHARDED, "0")
    board = _glider_board()
    eng = SparseShardedEngine(LIFE, board, mesh=mesh_lib.make_mesh_1d(),
                              layout="row", tile=16)
    assert not eng.plan.enabled
    assert sparse_sharded.ENV_SPARSE_SHARDED in eng.plan.why
    eng.step(32)
    np.testing.assert_array_equal(eng.snapshot(), oracle_n(board, 32))
    assert eng.engine_stamp == "dense:sharded"
    assert eng.counters()["sparse_steps"] == 0


def test_plan_gates():
    plan = sparse_sharded.plan_sparse_sharded("row", (8, 1), (16, 128),
                                              1, 32)
    assert not plan.enabled and "divide" in plan.why
    plan = sparse_sharded.plan_sparse_sharded("row", (8, 1), (32, 256),
                                              1, 32)
    assert plan.enabled and plan.engine == "sparse-sharded:row:t32"


def test_tuner_lists_sparse_sharded_candidate():
    from mpi_and_open_mp_tpu.tune import space

    mesh = mesh_lib.make_mesh_1d()
    cands = space.sharded_candidates(
        "life", (8 * space.SPARSE_SHARDED_TILE,
                 8 * space.SPARSE_SHARDED_TILE), mesh)
    paths = [c.path for c in cands]
    assert "sharded:row" in paths, "dense legs must stay in the race"
    assert "sparse_sharded:row" in paths
    sp = next(c for c in cands if c.path == "sparse_sharded:row")
    assert sp.halo_overlap == "sparse"
    # Dense legs enumerate FIRST: the heuristic baseline stays seeded.
    assert paths.index("sharded:row") < paths.index("sparse_sharded:row")


def test_sentinel_and_ledger_plumbing():
    from analysis import regression_sentinel as sentinel
    from mpi_and_open_mp_tpu.obs import ledger

    for f in ("sparse_sharded_cups", "sparse_sharded_vs_dense",
              "sparse_sharded_vs_single"):
        assert f in sentinel.WATCH_FIELDS
        assert sentinel.direction_for(f) == "higher"
    assert "sparse_sharded_engine" in sentinel.PROVENANCE_FIELDS
    # The kill-switch downgrade must be visible to the rank compare.
    assert (sentinel.engine_rank("sparse-sharded:row:t64")
            > sentinel.engine_rank("dense:sharded"))
    assert (sentinel.engine_rank("sparse-sharded:row:t64")
            > sentinel.engine_rank("dense:crossover"))
    assert "sparse" in ledger.KEY_FIELDS
    entry = ledger.stamp({"metric": "m", "board": [64, 64],
                          "sparse_sharded_engine": "sparse-sharded:row:t64"},
                         platform="cpu", device_count=8)
    assert entry["key"]["sparse"] == "sparse-sharded:row:t64"
    # Lines that only ran the single-device sparse phase keep its stamp.
    entry = ledger.stamp({"metric": "m", "board": [64, 64],
                          "sparse_engine": "sparse:t64"},
                         platform="cpu", device_count=8)
    assert entry["key"]["sparse"] == "sparse:t64"
    # Pre-PR-16 entries match new "-" lines through the key defaults.
    old = {"key": {f: "x" for f in ledger.KEY_FIELDS if f != "sparse"}}
    assert "sparse=-" in ledger.config_key(old, ("sparse",))
