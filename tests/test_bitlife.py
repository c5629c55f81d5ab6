"""Bit-packed kernel parity vs the NumPy oracle (SURVEY §4 mechanism 1).

The packed layout has two hazard zones the shapes below target: the
word-crossing single-bit shifts (ny straddling multiples of 32) and the
offset-ghost torus wrap rows. The Pallas runs are interpret-mode on CPU —
the same kernel code Mosaic compiles on TPU; the XLA packed loop is the
identical compiled path used on every backend.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from conftest import oracle_n as _oracle

from mpi_and_open_mp_tpu.ops import bitlife


def _soup(ny, nx, seed=0, density=0.4):
    rng = np.random.default_rng(seed)
    return (rng.random((ny, nx)) < density).astype(np.uint8)


SHAPES = [(3, 5), (10, 10), (30, 8), (31, 8), (32, 8), (33, 37), (100, 33)]


@pytest.mark.parametrize("ny,nx", SHAPES)
def test_pack_roundtrip(ny, nx):
    b = _soup(ny, nx)
    packed = bitlife.pack_board(jnp.asarray(b))
    assert packed.shape == (bitlife.n_words(ny), nx)
    assert np.array_equal(np.asarray(bitlife.unpack_board(packed, ny)), b)


@pytest.mark.parametrize("ny,nx", SHAPES)
def test_vmem_bits_parity(ny, nx):
    b = _soup(ny, nx)
    got = np.asarray(
        bitlife.life_run_vmem_bits(jnp.asarray(b), 7, interpret=True)
    )
    assert np.array_equal(got, _oracle(b, 7)), (ny, nx)


def test_vmem_bits_glider_torus():
    """Period-4 glider translation incl. the torus wrap (SURVEY §4 fixture)."""
    b = np.zeros((10, 10), np.uint8)
    for j, i in [(0, 1), (1, 2), (2, 0), (2, 1), (2, 2)]:
        b[j, i] = 1
    got = np.asarray(
        bitlife.life_run_vmem_bits(jnp.asarray(b), 100, interpret=True)
    )
    assert np.array_equal(got, _oracle(b, 100))
    assert got.sum() == 5


@pytest.mark.parametrize("ny,nx", SHAPES + [(300, 33), (257, 16), (600, 9)])
def test_bits_xla_parity(ny, nx):
    """The compiled-XLA packed loop (big-board dispatch target) across
    word-boundary and multi-word shapes."""
    b = _soup(ny, nx, seed=1)
    got = np.asarray(bitlife.life_run_bits_xla(jnp.asarray(b), 5))
    assert np.array_equal(got, _oracle(b, 5)), (ny, nx)


def test_bits_xla_glider_torus():
    b = np.zeros((10, 10), np.uint8)
    for j, i in [(0, 1), (1, 2), (2, 0), (2, 1), (2, 2)]:
        b[j, i] = 1
    got = np.asarray(bitlife.life_run_bits_xla(jnp.asarray(b), 100))
    assert np.array_equal(got, _oracle(b, 100))
    assert got.sum() == 5


@pytest.mark.parametrize("ny,nx,steps", [(256, 128, 7), (512, 256, 33)])
def test_fused_bits_parity(ny, nx, steps):
    """The multi-step-fused tiled kernel (big-board dispatch target on
    TPU), interpret mode at small aligned shapes: exercises the
    word-aligned wrap halo and in-window multi-step validity."""
    b = _soup(ny, nx, seed=3)
    assert bitlife.fused_bits_supported((ny, nx))
    got = np.asarray(
        bitlife.life_run_fused_bits(jnp.asarray(b), steps, interpret=True)
    )
    assert np.array_equal(got, _oracle(b, steps)), (ny, nx, steps)


@pytest.mark.parametrize("steps", [5, 40])
def test_fused_bits_multitile_seams(steps):
    """Force grid > 1 via a small tile budget so the per-tile DMA offsets
    and inter-tile halo seams run in interpret mode (at production sizes
    they only run compiled on TPU). nw=32 with an 8-word budget -> 4
    tiles; a seam off-by-one corrupts rows at every 256-row boundary."""
    b = _soup(1024, 128, seed=6)
    budget = (8 + 2 * bitlife._FUSE_HALO_WORDS) * 4 * 128
    assert bitlife._fused_tile_words(32, 128, budget) == 8
    got = np.asarray(
        bitlife.life_run_fused_bits(
            jnp.asarray(b), steps, interpret=True, tile_budget_bytes=budget
        )
    )
    assert np.array_equal(got, _oracle(b, steps)), steps


def test_fused_bits_pass_boundary():
    """Step counts straddling FUSE_MAX_STEPS force a second HBM pass whose
    input is the first pass's output."""
    b = _soup(256, 128, seed=4, density=0.3)
    for steps in (bitlife.FUSE_MAX_STEPS, bitlife.FUSE_MAX_STEPS + 1):
        got = np.asarray(
            bitlife.life_run_fused_bits(jnp.asarray(b), steps, interpret=True)
        )
        assert np.array_equal(got, _oracle(b, steps)), steps


@pytest.mark.parametrize("steps", [5, 40, bitlife.FUSE_MAX_STEPS + 2])
def test_fused_bits_column_tiled_serial(steps):
    """Force the serial runner onto the column-tiled 2-D grid (x-wrap
    border + per-tile column windows) with a budget that rules out
    full-width row tiles; seams in BOTH axes are exercised, and the
    largest step count crosses a pass boundary so the inter-pass x-halo
    re-concat runs."""
    b = _soup(512, 512, seed=7)
    budget = 4 * (8 + 8) * (128 + 256)
    assert bitlife._fused_tile_words(16, 512, budget) < 8
    plan = bitlife._col_tile_plan(16, 512, budget)
    assert plan is not None and plan[2] < 512  # genuinely column-tiled
    got = np.asarray(bitlife.life_run_fused_bits(
        jnp.asarray(b), steps, interpret=True, tile_budget_bytes=budget))
    assert np.array_equal(got, _oracle(b, steps)), steps


def test_fused_bits_full_width_serial():
    """The mirror of the column-tiled case: a budget 17/5 of one where
    only column tiles fit (the tile budget over the resident one) lets
    full-width row tiles win, on a 2-program grid; bit-exact across a
    pass boundary."""
    b = _soup(1024, 1024, seed=8)
    big = 4 * (16 + 2 * bitlife._FUSE_HALO_WORDS) * 1024
    small = big * 5 // 17
    assert bitlife.serial_fused_halo_x(32, 1024, small) == bitlife._FUSE_HALO_X
    assert bitlife.serial_fused_halo_x(32, 1024, big) == 0
    assert bitlife._fused_tile_grid(32, 1024, 0, big) == (16, 1024)
    steps = bitlife.FUSE_MAX_STEPS + 2
    got = np.asarray(bitlife.life_run_fused_bits(
        jnp.asarray(b), steps, interpret=True, tile_budget_bytes=big))
    assert np.array_equal(got, _oracle(b, steps))


# The plans of the benchmark's fused-tile cells at the default budgets:
# (tr, cx, window cells per frame cell) of the tiled stepper, and for the
# 2x2 plans the frame and exchange the resident budget decides.
CELL_PLANS = {
    "serial8192": ((8192, 8192), None, (128, 8192, 1.0625)),
    "cart8192": ((8192, 8192),
                 dict(frame=(8192, 8192), nw_s=128, h=4, hx=128, k_max=128),
                 (128, 4096, 1.12890625)),
    "cart10000": ((10000, 10000),
                  dict(frame=(10240, 10240), nw_s=160, h=4, hx=128,
                       k_max=128),
                  (160, 5120, 1.1025)),
}


@pytest.mark.parametrize("cell", sorted(CELL_PLANS))
def test_default_tile_plans(cell):
    shape, pinned, (tr, cx, amp) = CELL_PLANS[cell]
    if pinned is None:
        nw, nx = shape[0] // 32, shape[1]
        halo_x = bitlife.serial_fused_halo_x(nw, nx)
        assert halo_x == 0  # full-width row tiles
        cells = bitlife.fused_tile_cells(shape)
    else:
        plan = bitlife.plan_sharded_bits(shape, 2, 2, True, True)
        # The tile budget moves the tiles only: mode, frame and exchange
        # are those of the resident budget.
        resident = bitlife.plan_sharded_bits(
            shape, 2, 2, True, True, budget=bitlife._PACKED_VMEM_LIMIT)
        for key, value in dict(pinned, mode="tiled").items():
            assert getattr(plan, key) == getattr(resident, key) == value, key
        assert plan.budget == bitlife._PACKED_VMEM_LIMIT
        assert plan.tile_budget == bitlife._FUSED_TILE_BUDGET
        nw, nx, halo_x = plan.nw_s, plan.W, plan.hx
        cells = bitlife.plan_tile_cells(plan)
    assert bitlife._fused_tile_grid(
        nw, nx, halo_x, bitlife._FUSED_TILE_BUDGET) == (tr, cx)
    assert cells["window_cells"] / cells["frame_cells"] == pytest.approx(
        amp, rel=1e-12)


def test_fused_bits_gate():
    assert bitlife.fused_bits_supported((8192, 8192))
    assert bitlife.fused_bits_supported((16384, 16384))
    # Ultra-wide boards: full-width row tiles don't fit the budget, the
    # column-tiled plan does.
    assert bitlife._fused_tile_words(8192 // 32, 131072) < 8
    assert bitlife.fused_bits_supported((8192, 131072))
    assert not bitlife.fused_bits_supported((250, 128))  # ny % 32 != 0
    assert not bitlife.fused_bits_supported((256, 500))  # nx % 128 != 0
    assert not bitlife.fused_bits_supported((288, 384))  # nw=9: no 8k split
    with pytest.raises(ValueError, match="fused_bits_supported"):
        bitlife.life_run_fused_bits(
            jnp.zeros((288, 384), jnp.uint8), 1, interpret=True
        )


def test_pack_exact_roundtrip():
    b = _soup(96, 33, seed=5)
    packed = bitlife.pack_board_exact(jnp.asarray(b))
    assert packed.shape == (3, 33)
    assert np.array_equal(np.asarray(bitlife.unpack_board_exact(packed)), b)


def test_steps_runtime_scalar_no_retrace():
    """Changing the step count must reuse the compiled kernel (SMEM scalar)."""
    b = jnp.asarray(_soup(20, 20))
    f = bitlife._run_vmem_bits_jit
    bitlife.life_run_vmem_bits(b, 1, interpret=True)
    before = f._cache_size()
    bitlife.life_run_vmem_bits(b, 3, interpret=True)
    assert f._cache_size() == before


def test_bits_xla_steps_runtime_scalar_no_retrace():
    b = jnp.asarray(_soup(40, 24))
    f = bitlife._run_bits_xla_jit
    bitlife.life_run_bits_xla(b, 1)
    before = f._cache_size()
    bitlife.life_run_bits_xla(b, 4)
    assert f._cache_size() == before


def test_empty_board_stays_empty():
    b = np.zeros((40, 12), np.uint8)
    got = np.asarray(
        bitlife.life_run_vmem_bits(jnp.asarray(b), 10, interpret=True)
    )
    assert got.sum() == 0


# ------------------------------------------ padded-frame (unaligned) helpers


def test_take_rows_funnel():
    """take_rows must equal an unpack-slice-repack round trip at every
    bit offset, aligned and not."""
    b = _soup(96, 16, seed=11)
    packed = bitlife.pack_board_exact(jnp.asarray(b))
    for start, h in [(0, 1), (32, 2), (5, 1), (37, 1), (1, 2), (63, 1)]:
        got = np.asarray(bitlife.take_rows(packed, start, h))
        want = np.asarray(bitlife.pack_board_exact(
            jnp.asarray(b[start : start + 32 * h])))
        assert np.array_equal(got, want), (start, h)


@pytest.mark.parametrize("pad", [1, 12, 31, 32, 45, 64])
def test_mirror_tail(pad):
    """The last ``pad`` bit rows become copies of rows [0, pad)."""
    rows = 128
    b = _soup(rows, 16, seed=3)
    packed = bitlife.pack_board_exact(jnp.asarray(b))
    src = bitlife.take_rows(packed, 0, 3)  # rows [0, 96) >= pad + 32
    got = np.asarray(bitlife.unpack_board_exact(
        bitlife.mirror_tail(packed, src, pad)))
    want = b.copy()
    want[rows - pad :] = b[:pad]
    assert np.array_equal(got, want)


@pytest.mark.parametrize("ny,h", [(100, 2), (97, 1), (128, 2), (70, 1)])
def test_wrap_y_padded_matches_logical_torus(ny, h):
    """The local padded wrap must present, in window coordinates, exactly
    the periodic extension of the logical board: mirrors refreshed, top
    border = rows [ny-32h, ny), bottom border = rows [pad, pad+32h)."""
    nw = -(-ny // 32)
    pad = 32 * nw - ny
    b = _soup(ny, 24, seed=9)
    frame = np.zeros((32 * nw, 24), np.uint8)
    frame[:ny] = b
    ext = np.asarray(bitlife.unpack_board_exact(bitlife.wrap_y_padded(
        bitlife.pack_board_exact(jnp.asarray(frame)), ny, h)))
    want = np.concatenate([
        b[ny - 32 * h :],                            # top wrap border
        b, b[:pad],                                  # frame, live mirrors
        np.concatenate([b, b])[pad : pad + 32 * h],  # bottom border: the
        # periodic extension continued past the frame = rows [pad, pad+32h)
    ])
    assert np.array_equal(ext, want)


@pytest.mark.parametrize("steps", [1, 40, 130])
def test_fused_stepper_tiled_unaligned_x(steps):
    """The DMA-tiled kernel with wrap-patched lane rolls (unsharded
    unaligned x): a 768x250 board in a 768x256 frame, tile budget forced
    small enough that the window stepper is rejected and full-width row
    tiles carry the fused rounds."""
    ny, nx = 768, 250
    budget = 20_000
    plan = bitlife.plan_sharded_bits((ny, nx), 1, 1, False, False,
                                     budget=budget)
    assert plan is not None and plan.mode == "tiled"
    assert plan.nx_exact == nx and plan.pad_y == 0
    b = _soup(ny, nx, seed=21)
    frame = np.zeros((ny, plan.W), np.uint8)
    frame[:, :nx] = b
    step = bitlife.make_plan_stepper(plan, interpret=True)
    q = bitlife.pack_board_exact(jnp.asarray(frame))
    rem = steps
    while rem > 0:
        k = min(rem, plan.k_max)
        q = step(jnp.asarray([k], jnp.int32), bitlife.wrap_y(q, plan.h))
        rem -= k
    got = np.asarray(bitlife.unpack_board_exact(q))[:, :nx]
    assert np.array_equal(got, _oracle(b, steps))


def test_plan_window_small_shards():
    """500x500 over an 8-way ring — the geometry every pre-plan gate
    rejected (2-word shards) — must plan onto the window stepper."""
    plan = bitlife.plan_sharded_bits((500, 500), 8, 1, True, False)
    assert plan is not None and plan.mode == "window"
    assert plan.frame == (512, 512) and plan.nw_s == 2 and plan.h == 1
    assert plan.nx_exact == 500 and plan.k_max == 32
    # Hopeless geometry still returns None.
    assert bitlife.plan_sharded_bits((64, 128), 8, 1, True, False) is None
    assert bitlife.plan_sharded_bits((256, 20), 4, 2, True, True) is None


@pytest.mark.parametrize("shape,budget,mode,steps", [
    ((100, 130), bitlife._PACKED_VMEM_LIMIT, "window", 110),
    ((740, 250), 20_000, "tiled", 140),  # pad_y=28 + nx_exact, multi-tile
])
def test_frame_bits_serial_unaligned(shape, budget, mode, steps):
    """The single-device padded-frame runner: unaligned boards through
    the fused kernels (local funnel y wrap + wrap-patched x rolls),
    crossing fused-round boundaries."""
    plan = bitlife.plan_sharded_bits(shape, 1, 1, False, False, budget)
    assert plan is not None and plan.mode == mode, plan
    assert steps > plan.k_max
    b = _soup(*shape, seed=33)
    got = np.asarray(bitlife.life_run_frame_bits(
        jnp.asarray(b), steps, interpret=True, budget=budget))
    assert np.array_equal(got, _oracle(b, steps))


def test_frame_bits_steps_runtime_scalar_no_retrace():
    b = jnp.asarray(_soup(100, 130))
    f = bitlife._run_frame_bits_jit
    bitlife.life_run_frame_bits(b, 2, interpret=True)
    before = f._cache_size()
    bitlife.life_run_frame_bits(b, 7, interpret=True)
    assert f._cache_size() == before


def test_rule_exhaustive_all_512_neighbourhoods():
    """Every 3x3 neighbourhood through the packed rule. On a 3x3 torus a
    cell's 8 neighbours are exactly the other 8 cells, so the 512 board
    configurations enumerate the rule's full truth table — the one test
    that can never be fooled by a lucky soup. Checked via the XLA packed
    step (same _carry_save_rule as the Pallas kernels) against the
    birth-on-3 / survive-on-2-or-3 spec directly, not another oracle."""
    boards = np.stack([
        np.array([(cfg >> b) & 1 for b in range(9)], dtype=np.uint8
                 ).reshape(3, 3)
        for cfg in range(512)
    ])
    for cfg in range(512):
        b = boards[cfg]
        got = np.asarray(bitlife.life_run_bits_xla(jnp.asarray(b), 1))
        n = b.sum() - b[1, 1]  # 8-neighbour count of the centre
        want_centre = 1 if (n == 3 or (b[1, 1] and n == 2)) else 0
        assert got[1, 1] == want_centre, (cfg, b, got)


# ------------------------------------------------- board-sliced batch layout


BATCHES = [1, 31, 32, 33, 64]


@pytest.mark.parametrize("b", BATCHES)
@pytest.mark.parametrize("ny,nx", [(3, 5), (33, 37), (8, 8)])
def test_pack_batch_bits_roundtrip_ragged(b, ny, nx):
    """Exact round trip for any B, plane-width multiples or not; the
    dead high bits of a ragged plane must come back as zeros nowhere —
    they are sliced off, not unpacked."""
    rng = np.random.default_rng(b * 1000 + ny)
    s = (rng.random((b, ny, nx)) < 0.4).astype(np.uint8)
    planes = bitlife.pack_batch_bits(jnp.asarray(s))
    assert planes.shape == (bitlife.n_planes(b), ny, nx)
    assert planes.dtype == jnp.uint32
    assert np.array_equal(
        np.asarray(bitlife.unpack_batch_bits(planes, b)), s)


def test_n_planes():
    assert [bitlife.n_planes(b) for b in (1, 31, 32, 33, 64, 65)] == \
        [1, 1, 1, 2, 2, 3]


@pytest.mark.parametrize("b", BATCHES)
def test_bitsliced_xla_parity_ragged(b):
    """Bit-exact per board vs the NumPy oracle through the halo-fused
    XLA runner, for every board of ragged-B stacks (the acceptance
    criterion verbatim). 13 steps is deliberately not a multiple of the
    halo depth, so the ragged final refresh block runs."""
    rng = np.random.default_rng(b)
    s = (rng.random((b, 16, 20)) < 0.4).astype(np.uint8)
    got = np.asarray(bitlife.life_run_bitsliced_batch(
        jnp.asarray(s), 13, use_kernel=False))
    for i in range(b):
        assert np.array_equal(got[i], _oracle(s[i], 13)), f"board {i}"


@pytest.mark.parametrize("b", [5, 32, 33])
def test_bitsliced_kernel_parity_interpret(b):
    """The Pallas VMEM kernel (interpret mode — the code Mosaic compiles
    on TPU), pltpu.roll gathers vs the oracle."""
    rng = np.random.default_rng(b + 7)
    s = (rng.random((b, 13, 17)) < 0.4).astype(np.uint8)
    got = np.asarray(bitlife.life_run_bitsliced_batch(
        jnp.asarray(s), 6, use_kernel=True, interpret=True))
    for i in range(b):
        assert np.array_equal(got[i], _oracle(s[i], 6)), f"board {i}"


def test_bitsliced_small_board_edges():
    """Degenerate spatial extents (1-wide / 2-wide axes) where the halo
    depth clamps to min(ny, nx) and neighbor rolls alias."""
    for ny, nx in [(1, 8), (8, 1), (2, 2), (3, 3)]:
        rng = np.random.default_rng(ny * 100 + nx)
        s = (rng.random((9, ny, nx)) < 0.5).astype(np.uint8)
        got = np.asarray(bitlife.life_run_bitsliced_batch(
            jnp.asarray(s), 5, use_kernel=False))
        for i in range(9):
            assert np.array_equal(got[i], _oracle(s[i], 5)), (ny, nx, i)


def test_bitsliced_glider_torus_per_board():
    """A glider in board 0, a blinker in board 40 (second plane), empty
    elsewhere: cross-board isolation over 100 steps incl. torus wraps —
    a single leaked bit between planes or boards would kill a pattern."""
    s = np.zeros((48, 10, 10), np.uint8)
    for j, i in [(0, 1), (1, 2), (2, 0), (2, 1), (2, 2)]:
        s[0, j, i] = 1
    s[40, 4, 3:6] = 1
    got = np.asarray(bitlife.life_run_bitsliced_batch(
        jnp.asarray(s), 100, use_kernel=False))
    assert np.array_equal(got[0], _oracle(s[0], 100))
    assert got[0].sum() == 5
    assert np.array_equal(got[40], _oracle(s[40], 100))
    dead = np.delete(got, (0, 40), axis=0)
    assert dead.sum() == 0  # padding + empty boards stay dead


def test_bitsliced_zero_steps_and_dtype():
    s = _soup(16, 16, seed=2).astype(np.int32)[None].repeat(8, axis=0)
    got = bitlife.life_run_bitsliced_batch(jnp.asarray(s), 0,
                                           use_kernel=False)
    assert got.dtype == jnp.int32
    assert np.array_equal(np.asarray(got), s)


def test_bitsliced_steps_runtime_scalar_no_retrace():
    """One compile per plane shape serves ANY step count AND any ragged
    B within the plane — the serve-layer bucketing contract, observable
    via the jit.retrace counter the way the daemon sees it."""
    from mpi_and_open_mp_tpu.obs import metrics

    metrics.reset()
    # 19x21 is unique to this test: the process-wide jit cache must not
    # have seen the plane shape before, or the count would read 0.
    for b in (17, 25, 32):  # same plane count, differing ragged B
        s = jnp.asarray(_soup(19, 21, seed=b)[None].repeat(b, axis=0))
        for n in (1, 4, 9):
            bitlife.life_run_bitsliced_batch(s, n, use_kernel=False)
    assert metrics.get("jit.retrace", fn="life_batch_bitsliced") == 1
    metrics.reset()


def test_fits_vmem_bitsliced_gate():
    # One plane of 500x500 lane-pads to 500x512 words = 1.02 MB: in.
    assert bitlife.fits_vmem_bitsliced((32, 500, 500))
    assert bitlife.fits_vmem_bitsliced((8, 64, 64))
    # Plane count scales the footprint: enough boards push any shape out.
    assert not bitlife.fits_vmem_bitsliced((32 * 64, 500, 500))
    assert not bitlife.fits_vmem_bitsliced((8, 2048, 2048))
