"""The reference, and its control: the reference with the torus wrap
left out must be called wrong on every cell's boards.

``control_mismatch`` is also what was run on the chip at the cells' own
sizes (PERF.md §2); here it runs at a size a test run holds."""

import numpy as np
import pytest

import reference
import run
import suite

SPEC = suite.load()


def life_numpy(board, steps):
    """Life on a torus, one cell at a time: the slowest plain check."""
    b = np.asarray(board, np.uint8)
    ny, nx = b.shape
    for _ in range(steps):
        n = sum(np.roll(np.roll(b, dy, 0), dx, 1)
                for dy in (-1, 0, 1) for dx in (-1, 0, 1) if dy or dx)
        b = ((n == 3) | ((b == 1) & (n == 2))).astype(np.uint8)
    return b


def control_mismatch(config, seed, device=None):
    """Cells by which the control's final board differs from the
    reference's, on the cell's seeded board."""
    board = run.make_board(config, seed)
    want = reference.life_steps(board, config["steps"], device=device)
    got = reference.life_steps_dead_edge(board, config["steps"],
                                         device=device)
    return int(np.count_nonzero(got != want))


def small(config_name, n=48, steps=60):
    """The configuration with fewer steps, and its board cut to ``n``
    where it is larger than 512 (a cfg's cells keep their board)."""
    config = suite.config(SPEC, config_name)
    if config["nx"] > 512:
        config.update(nx=n, ny=n)
    config.update(steps=steps)
    return config


def test_glider_crosses_the_torus():
    board = np.zeros((10, 10), np.uint8)
    board[[0, 1, 2, 2, 2], [1, 2, 0, 1, 2]] = 1
    # a glider moves one cell down-right every 4 steps: 40 steps wrap it
    assert np.array_equal(reference.life_steps(board, 40), board)
    assert not np.array_equal(reference.life_steps_dead_edge(board, 40),
                              board)


@pytest.mark.parametrize("n", [40, 64])  # byte a cell; 32 to a word
@pytest.mark.parametrize("seed", [1, 2**31 + 5, 987654321])
def test_reference_matches_plain_numpy(seed, n):
    board = run.make_board(small("pod8192", n, 30), seed)
    assert np.array_equal(reference.life_steps(board, 30),
                          life_numpy(board, 30))


@pytest.mark.parametrize("shape", [(32, 32), (48, 96), (7, 64)])
def test_packed_reference_matches_plain_form(shape):
    board = (np.random.default_rng(shape[1]).random(shape) < 0.3).astype(
        np.uint8)
    for steps in (1, 2, 75):
        assert np.array_equal(reference.life_steps(board, steps),
                              reference.life_steps_plain(board, steps))


@pytest.mark.parametrize("seed", [3, 2**31 + 11, 424242])
@pytest.mark.parametrize("config_name",
                         [c["name"] for c in SPEC["configs"]])
def test_control_is_called_wrong(config_name, seed):
    assert control_mismatch(small(config_name), seed) > run.LIMITS[
        "mismatched_cells"]
