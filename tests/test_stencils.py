"""Parity + unit pins for the stencil spec subsystem (PR 13).

Three layers:

* Per-spec fuzz: for EVERY registered workload, the jitted generic paths
  (``step_roll``, ``step_padded``, ``run_roll``, ``run_roll_batch``)
  must agree with the spec's NumPy oracle — bit-exact for integer rules,
  tight allclose for floats (``engine.parity_ok``). Life is additionally
  pinned bit-exact against the historical independent oracle
  (``ops.life_ops.life_step_numpy``) so the generic machinery is gated
  against the original truth, not against itself.
* Sparse active-tile engine: glider crossing tile boundaries stays
  bit-exact while most tiles sleep; dense boards fall back past the
  crossover and stamp ``dense:crossover``; settled boards go to zero
  work; the pad ladder and counters are pinned.
* Halo generality: ``halo_pad_y``/``halo_pad_x`` at depth 2, float32,
  and with a leading channel axis — radius-2 and multi-channel sharded
  steps through ``halo_pad_2d`` + ``step_padded`` must reproduce the
  single-device oracle on the 8-virtual-device mesh.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from mpi_and_open_mp_tpu import stencils
from mpi_and_open_mp_tpu.ops import life_ops
from mpi_and_open_mp_tpu.parallel import halo, mesh as mesh_lib
from mpi_and_open_mp_tpu.stencils import engine
from mpi_and_open_mp_tpu.stencils.spec import BOX3
from mpi_and_open_mp_tpu.stencils.sparse import ActiveTileEngine, _pad_count


def _board(spec, rng, ny, nx):
    b = spec.init(rng, (ny, nx))
    assert b.shape == spec.board_shape(ny, nx)
    assert b.dtype == spec.np_dtype
    return b


def _pad_wrap(board, r):
    """Torus halo on the last two axes only (channels ride through)."""
    width = [(0, 0)] * (board.ndim - 2) + [(r, r), (r, r)]
    return np.pad(board, width, mode="wrap")


# --------------------------------------------------------------------------
# Registry surface.


def test_registry_has_the_four_workloads():
    assert set(stencils.names()) >= {"life", "heat", "gray_scott",
                                     "wireworld"}


def test_get_unknown_workload_names_the_registered_set():
    with pytest.raises(KeyError, match="gray_scott"):
        stencils.get("brians_brain")


def test_register_rejects_bad_weights():
    with pytest.raises(ValueError, match="weights shape"):
        stencils.register(stencils.StencilSpec(
            name="bad-shape", radius=2, dtype="float32",
            weights=BOX3, update=lambda c, a, xp: c))
    with pytest.raises(ValueError, match="center must be 0"):
        stencils.register(stencils.StencilSpec(
            name="bad-center", radius=1, dtype="float32",
            weights=((1, 1, 1), (1, 1, 1), (1, 1, 1)),
            update=lambda c, a, xp: c))


# --------------------------------------------------------------------------
# Per-spec oracle parity fuzz: every registered workload, every path.


@pytest.mark.parametrize("name", stencils.names())
@pytest.mark.parametrize("ny,nx", [(24, 32), (17, 23)])
def test_step_roll_matches_oracle(name, ny, nx, rng):
    spec = stencils.get(name)
    board = _board(spec, rng, ny, nx)
    want = board
    got = jnp.asarray(board)
    for step in range(5):
        want = engine.step_numpy(spec, want)
        got = engine.step_roll(spec, got)
        assert engine.parity_ok(spec, got, want), f"{name} step {step}"


@pytest.mark.parametrize("name", stencils.names())
@pytest.mark.parametrize("ny,nx", [(24, 32), (17, 23)])
def test_step_padded_matches_oracle(name, ny, nx, rng):
    spec = stencils.get(name)
    board = _board(spec, rng, ny, nx)
    want = board
    for step in range(3):
        padded = _pad_wrap(want, spec.radius)
        got = engine.step_padded(spec, jnp.asarray(padded))
        want = engine.step_numpy(spec, want)
        assert engine.parity_ok(spec, got, want), f"{name} step {step}"


def test_life_generic_paths_bit_exact_vs_historic_oracle(rng):
    """The acceptance pin: life through the GENERIC engine must equal
    the pre-existing independent oracle exactly, board for board."""
    spec = stencils.get("life")
    board = _board(spec, rng, 48, 64)
    want = board
    for _ in range(8):
        want = life_ops.life_step_numpy(want)
    assert np.array_equal(
        np.asarray(engine.run_roll(spec, jnp.asarray(board), 8)), want)
    padded = _pad_wrap(board, 1)
    assert np.array_equal(
        np.asarray(engine.step_padded(spec, jnp.asarray(padded))),
        life_ops.life_step_numpy(board))


@pytest.mark.parametrize("name", stencils.names())
def test_run_roll_and_batch_match_oracle(name, rng):
    spec = stencils.get(name)
    boards = [_board(spec, rng, 16, 24) for _ in range(3)]
    n = 6
    wants = [engine.oracle_run(spec, b, n) for b in boards]
    for b, w in zip(boards, wants):
        got = engine.run_roll(spec, jnp.asarray(b), n)
        assert engine.parity_ok(spec, got, w), name
    stack = np.stack(boards)
    out = np.asarray(engine.run_roll_batch(spec, jnp.asarray(stack), n))
    for i, w in enumerate(wants):
        assert engine.parity_ok(spec, out[i], w), f"{name} lane {i}"


# --------------------------------------------------------------------------
# Sparse active-tile engine.


def test_pad_count_ladder():
    assert [_pad_count(n) for n in range(1, 17)] == [
        1, 2, 3, 4, 6, 6, 8, 8, 12, 12, 12, 12, 16, 16, 16, 16]
    for n in (1, 5, 33, 100, 1000):
        assert _pad_count(n) >= n


def test_sparse_glider_crossing_tiles_stays_bit_exact():
    spec = stencils.get("life")
    board = np.zeros((256, 256), np.uint8)
    # Glider straddling the (30..32, 30..32) tile corner at tile=32 —
    # it must wake exactly the tiles it enters, never drop cells.
    board[30:33, 30:33] = [[0, 1, 0], [0, 0, 1], [1, 1, 1]]
    eng = ActiveTileEngine(spec, board, tile=32)
    got = eng.step(200)
    want = engine.oracle_run(spec, board, 200)
    assert np.array_equal(got, want)
    c = eng.counters()
    # Step 1 is dense (everything starts active); the rest ride sparse.
    assert c["dense_steps"] == 1 and c["sparse_steps"] == 199
    assert c["tiles_skipped"] > c["tiles_stepped"]
    assert eng.engine_stamp == "sparse:t32"


def test_sparse_dense_board_falls_back_and_stamps_crossover(rng):
    spec = stencils.get("life")
    board = spec.init(rng, (64, 64))  # ~33% live: every tile active
    eng = ActiveTileEngine(spec, board, tile=16, crossover=0.25)
    got = eng.step(4)
    assert np.array_equal(got, engine.oracle_run(spec, board, 4))
    assert eng.dense_steps >= 1
    if eng.sparse_steps == 0:
        assert eng.engine_stamp == "dense:crossover"


def test_sparse_settled_board_does_zero_work():
    spec = stencils.get("life")
    eng = ActiveTileEngine(spec, np.zeros((64, 64), np.uint8), tile=32)
    eng.step(1)  # proves settledness (everything starts active)
    stepped = eng.tiles_stepped
    eng.step(5)
    assert eng.tiles_stepped == stepped  # mask empty: no tile gathered
    assert eng.active_frac == 0.0
    assert np.array_equal(eng.board, np.zeros((64, 64), np.uint8))


def test_sparse_active_frac_decays_to_the_live_region(rng):
    spec = stencils.get("life")
    board = np.zeros((256, 256), np.uint8)
    board[78:81, 80] = 1  # lone blinker, deep in tile (2,2) at tile=32
    eng = ActiveTileEngine(spec, board, tile=32)
    eng.step(10)
    # Border-band activation keeps the blinker's neighbours asleep:
    # exactly one of the 64 tiles stays awake.
    assert eng.active_frac == 1 / 64
    assert 0.0 < eng.mean_active_frac < 0.2


def test_sparse_multichannel_gray_scott_parity(rng):
    spec = stencils.get("gray_scott")
    board = _board(spec, rng, 64, 64)
    eng = ActiveTileEngine(spec, board, tile=32)
    got = eng.step(20)
    want = engine.oracle_run(spec, board, 20)
    assert engine.parity_ok(spec, got, want)


def test_sparse_rejects_bad_geometry(rng):
    spec = stencils.get("life")
    with pytest.raises(ValueError, match="must divide"):
        ActiveTileEngine(spec, np.zeros((60, 64), np.uint8), tile=32)
    with pytest.raises(ValueError, match="does not match"):
        ActiveTileEngine(
            stencils.get("gray_scott"), np.zeros((64, 64), np.float32),
            tile=32)


# --------------------------------------------------------------------------
# Halo generality: depth-2, float dtype, leading channel axis.

#: Radius-2 float diffusion used to exercise depth-2 halo exchange; the
#: weights are an asymmetric-by-distance box so a wrong halo row/column
#: cannot cancel out of the aggregate.
R2 = stencils.StencilSpec(
    name="r2-test", radius=2, dtype="float32",
    weights=((1, 1, 1, 1, 1),
             (1, 2, 2, 2, 1),
             (1, 2, 0, 2, 1),
             (1, 2, 2, 2, 1),
             (1, 1, 1, 1, 1)),
    update=lambda c, a, xp: (c + 0.01 * (a - 24 * c)).astype(c.dtype))


def _sharded_step(spec, board, mesh, in_spec):
    """One torus step via halo_pad_2d + step_padded under shard_map."""
    arr = jax.device_put(jnp.asarray(board), NamedSharding(mesh, in_spec))
    fn = jax.jit(jax.shard_map(
        lambda blk: engine.step_padded(
            spec, halo.halo_pad_2d(blk, depth=spec.radius)),
        mesh=mesh, in_specs=in_spec, out_specs=in_spec, check_vma=False,
    ))
    return np.asarray(jax.device_get(fn(arr)))


def test_halo_pad_depth2_float_periodic_extension(rng):
    """halo_pad_y/x at depth=2 on float32 must build the exact periodic
    window — the depth-generic analogue of the packed-halo pins."""
    board = rng.random((64, 48)).astype(np.float32)
    mesh = mesh_lib.make_mesh_1d(4, axis="y")
    arr = jax.device_put(
        jnp.asarray(board), NamedSharding(mesh, P("y", None)))
    ext = jax.jit(jax.shard_map(
        lambda blk: halo.halo_pad_y(blk, "y", 2),
        mesh=mesh, in_specs=P("y", None), out_specs=P("y", None),
        check_vma=False,
    ))(arr)
    ext = np.asarray(jax.device_get(ext))
    S, win = 16, 20  # 64/4 rows per shard, +2 ghost rows each side
    for i in range(4):
        got = ext[i * win:(i + 1) * win]
        rows = np.arange(i * S - 2, (i + 1) * S + 2) % 64
        assert np.array_equal(got, board[rows]), f"shard {i}"


def test_radius2_sharded_step_matches_oracle(rng):
    board = rng.random((64, 64)).astype(np.float32)
    mesh = mesh_lib.make_mesh_2d(4, 2)
    got = _sharded_step(R2, board, mesh, P("y", "x"))
    want = engine.step_numpy(R2, board)
    assert engine.parity_ok(R2, got, want)


@pytest.mark.parametrize("name", ["heat", "wireworld"])
def test_sharded_stencil_step_matches_oracle(name, rng):
    spec = stencils.get(name)
    board = _board(spec, rng, 64, 64)
    mesh = mesh_lib.make_mesh_2d(4, 2)
    got = _sharded_step(spec, board, mesh, P("y", "x"))
    want = engine.step_numpy(spec, board)
    assert engine.parity_ok(spec, got, want)


def test_channel_board_rides_through_sharded_halo(rng):
    """gray_scott's (2, ny, nx) board: channels on the leading axis must
    pass through halo_pad_* untouched while y/x shards exchange ghosts."""
    spec = stencils.get("gray_scott")
    board = _board(spec, rng, 64, 64)
    mesh = mesh_lib.make_mesh_2d(4, 2)
    got = _sharded_step(spec, board, mesh, P(None, "y", "x"))
    want = engine.step_numpy(spec, board)
    assert engine.parity_ok(spec, got, want)
