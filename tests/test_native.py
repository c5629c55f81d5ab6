"""Native C++ library: build, bind, and parity with the Python fallbacks."""

import os
import subprocess

import numpy as np
import pytest

from mpi_and_open_mp_tpu.utils import native
from mpi_and_open_mp_tpu.utils.config import load_config_py, save_config, config_from_board

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")


@pytest.fixture(scope="module", autouse=True)
def built_lib():
    rc = subprocess.run(
        ["make", "-C", os.path.join(REPO, "native")], capture_output=True
    )
    # An earlier test in this process may have tried (and failed) to load
    # the library before it was built; forget that attempt.
    native._LIB, native._TRIED = None, False
    if rc.returncode != 0 or not native.available():
        pytest.skip("native toolchain unavailable")


def test_native_load_matches_python():
    for name in ("glider_10x10.cfg", "empty_10x10.cfg", "rpentomino_40x32.cfg"):
        path = os.path.join(FIXTURES, name)
        py = load_config_py(path)
        nat = native.load_config(path)
        assert (nat.steps, nat.save_steps, nat.nx, nat.ny) == (
            py.steps, py.save_steps, py.nx, py.ny)
        np.testing.assert_array_equal(nat.cells, py.cells)


def test_native_load_errors(tmp_path):
    bad = tmp_path / "bad.cfg"
    bad.write_text("1\n2\n")
    with pytest.raises(ValueError):
        native.load_config(bad)
    dangling = tmp_path / "dangling.cfg"
    dangling.write_text("1\n1\n4 4\n3\n")
    with pytest.raises(ValueError):
        native.load_config(dangling)
    with pytest.raises(ValueError):
        native.load_config(tmp_path / "missing.cfg")


def test_native_oracle_matches_numpy(make_board):
    """Two independent oracles (C++ scanline vs NumPy roll) must agree —
    the strongest form of the reference's serial-parity discipline."""
    from conftest import oracle_n

    for shape in [(10, 10), (17, 23), (64, 48)]:
        b = make_board(*shape)
        np.testing.assert_array_equal(native.life_steps(b, 12), oracle_n(b, 12))
    # Glider translation survives the torus in the native oracle too.
    g = np.zeros((10, 10), np.uint8)
    for i, j in [(0, 2), (1, 0), (1, 2), (2, 1), (2, 2)]:
        g[j, i] = 1
    np.testing.assert_array_equal(
        native.life_steps(g, 40), g  # period 40 on a 10x10 torus
    )


def test_native_bits_oracle_matches(make_board):
    """The bit-packed native oracle (third independent implementation)
    must agree with both the scalar C++ and NumPy oracles — including
    word-boundary widths (63/64/65), sub-word boards, and degenerate
    torus sizes where neighbours alias (nx or ny in {1, 2})."""
    from conftest import oracle_n

    for shape in [(10, 10), (17, 23), (48, 63), (48, 64), (48, 65),
                  (8, 200), (3, 130), (2, 70), (70, 2), (1, 9), (9, 1)]:
        b = make_board(*shape)
        got = native.life_steps(b, 9, bits=True)
        np.testing.assert_array_equal(got, oracle_n(b, 9), err_msg=str(shape))
        np.testing.assert_array_equal(got, native.life_steps(b, 9))


def test_native_roundtrip_config(tmp_path, make_board):
    board = make_board(9, 9)
    cfg = config_from_board(board, 7, 3)
    path = tmp_path / "rt.cfg"
    save_config(path, cfg)
    nat = native.load_config(path)
    np.testing.assert_array_equal(nat.board(), board)
