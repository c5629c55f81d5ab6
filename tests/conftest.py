"""Test harness: force JAX onto 8 virtual CPU devices.

Mirrors the SURVEY §4 test strategy: "multi-node" behaviour is exercised
without a TPU pod by running every sharded code path on a virtual 8-device
CPU mesh (``--xla_force_host_platform_device_count``). This must run before
any backend is initialised; ``jax_platforms`` is also pinned in-process so a
caller that did not export ``JAX_PLATFORMS=cpu`` still gets the CPU mesh.
"""

import importlib.util
import os
import sys

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def _assert_virtual_mesh():
    assert jax.default_backend() == "cpu"
    assert len(jax.devices()) == 8, "tests expect 8 virtual CPU devices"


@pytest.fixture
def rng():
    return np.random.default_rng(20260729)


def random_board(rng, ny, nx, density=0.35):
    return (rng.random((ny, nx)) < density).astype(np.uint8)


@pytest.fixture
def make_board(rng):
    def _make(ny, nx, density=0.35):
        return random_board(rng, ny, nx, density)

    return _make


def oracle_n(board, n):
    """Advance ``board`` ``n`` steps through the NumPy oracle (shared by the
    parity tests; the single source of ground truth)."""
    from mpi_and_open_mp_tpu.ops.life_ops import life_step_numpy

    b = np.asarray(board)
    for _ in range(n):
        b = life_step_numpy(b)
    return b


BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")


def bench_module(name):
    """``benchmark/<name>.py`` by its path: the benchmark's directory is
    not put on ``sys.path``, where its ``tests`` would shadow these."""
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module
