"""Unit-level pins for the packed wrap-halo exchanges.

The LifeSim parity suites prove the packed paths end to end; these tests
pin the exchange layer itself: for every shard, the halo-extended window
``packed_halo_y``/``packed_halo_x`` builds must equal the corresponding
slice of the board's INFINITE PERIODIC TILING (the invariant the fused
kernels rely on — ops/bitlife.py module docs). A regression in the
funnel offsets or mirror refresh shows up here as the exact wrong rows,
not as a far-downstream cell diff.
"""

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from conftest import random_board

from mpi_and_open_mp_tpu.ops import bitlife
from mpi_and_open_mp_tpu.parallel import halo, mesh as mesh_lib


def _frame_rows(board, Nyp):
    """The padded frame's row content: board rows then mirror rows."""
    ny = board.shape[0]
    return np.concatenate([board, board[: Nyp - ny]], axis=0)


def test_packed_halo_y_periodic_extension():
    ny, nx, py = 230, 64, 4  # Nyp=256, pad_y=26, nw_s=2 -> h=1
    plan = bitlife.plan_sharded_bits((ny, nx), py, 1, True, False)
    assert plan.pad_y == 26 and plan.h == 1
    board = random_board(np.random.default_rng(3), ny, nx)
    frame = np.zeros((plan.frame[0], nx), np.uint8)
    frame[:ny] = board
    mesh = mesh_lib.make_mesh_1d(py, axis="y")
    packed = jax.device_put(
        bitlife.pack_board_exact(jnp.asarray(frame)),
        NamedSharding(mesh, P("y", None)),
    )
    ext = jax.jit(jax.shard_map(
        lambda q: halo.packed_halo_y(q, "y", plan.h, pad=plan.pad_y),
        mesh=mesh, in_specs=P("y", None), out_specs=P("y", None),
        check_vma=False,
    ))(packed)
    ext = np.asarray(bitlife.unpack_board_exact(jax.device_get(ext)))

    S, hrows = 32 * plan.nw_s, 32 * plan.h
    frows = _frame_rows(board, plan.frame[0])
    win = S + 2 * hrows
    for i in range(py):
        got = ext[i * win : (i + 1) * win]
        top = (board[ny - hrows : ny] if i == 0
               else frows[i * S - hrows : i * S])
        bot = (board[plan.pad_y : plan.pad_y + hrows] if i == py - 1
               else frows[(i + 1) * S : (i + 1) * S + hrows])
        want = np.concatenate([top, frows[i * S : (i + 1) * S], bot])
        assert np.array_equal(got, want), f"shard {i}"


def test_packed_halo_x_periodic_extension():
    ny, nx, px = 64, 460, 4  # narrow re-pitch: W=120, pad_x=20, hx=100
    plan = bitlife.plan_sharded_bits((ny, nx), 1, px, False, True)
    assert plan.pad_x > 0 and plan.x_sharded
    board = random_board(np.random.default_rng(5), ny, nx)
    frame = np.zeros((ny, plan.frame[1]), np.uint8)
    frame[:, :nx] = board
    mesh = mesh_lib.make_mesh_1d(px, axis="x")
    packed = jax.device_put(
        bitlife.pack_board_exact(jnp.asarray(frame)),
        NamedSharding(mesh, P(None, "x")),
    )
    ext = jax.jit(jax.shard_map(
        lambda q: halo.packed_halo_x(q, "x", plan.hx, pad=plan.pad_x),
        mesh=mesh, in_specs=P(None, "x"), out_specs=P(None, "x"),
        check_vma=False,
    ))(packed)
    ext = np.asarray(bitlife.unpack_board_exact(jax.device_get(ext)))

    W, hx = plan.W, plan.hx
    fcols = np.concatenate([board, board[:, : plan.pad_x]], axis=1)
    wcols = W + 2 * hx
    for i in range(px):
        got = ext[:, i * wcols : (i + 1) * wcols]
        left = (board[:, nx - hx : nx] if i == 0
                else fcols[:, i * W - hx : i * W])
        right = (board[:, plan.pad_x : plan.pad_x + hx] if i == px - 1
                 else fcols[:, (i + 1) * W : (i + 1) * W + hx])
        want = np.concatenate(
            [left, fcols[:, i * W : (i + 1) * W], right], axis=1)
        assert np.array_equal(got, want), f"shard {i}"


def test_packed_halo_degenerates_to_plain_pad_when_aligned():
    """pad=0 must route through the plain halo_pad_* word/column rings."""
    board = random_board(np.random.default_rng(8), 256, 128)
    mesh = mesh_lib.make_mesh_1d(4, axis="y")
    packed = jax.device_put(
        bitlife.pack_board_exact(jnp.asarray(board)),
        NamedSharding(mesh, P("y", None)),
    )

    def both(q):
        a = halo.packed_halo_y(q, "y", 2, pad=0)
        b = halo.halo_pad_y(q, "y", 2)
        return a, b

    a, b = jax.jit(jax.shard_map(
        both, mesh=mesh, in_specs=P("y", None),
        out_specs=(P("y", None), P("y", None)), check_vma=False,
    ))(packed)
    assert np.array_equal(np.asarray(a), np.asarray(b))

    mesh_x = mesh_lib.make_mesh_1d(4, axis="x")
    packed_x = jax.device_put(
        bitlife.pack_board_exact(jnp.asarray(board)),
        NamedSharding(mesh_x, P(None, "x")),
    )

    def both_x(q):
        a = halo.packed_halo_x(q, "x", 16, pad=0)
        b = halo.halo_pad_x(q, "x", 16)
        return a, b

    a, b = jax.jit(jax.shard_map(
        both_x, mesh=mesh_x, in_specs=P(None, "x"),
        out_specs=(P(None, "x"), P(None, "x")), check_vma=False,
    ))(packed_x)
    assert np.array_equal(np.asarray(a), np.asarray(b))
