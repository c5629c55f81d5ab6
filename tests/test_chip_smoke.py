"""CPU rehearsal of ``chip_smoke.py``'s phases at small sizes, and the
compile-cache helper every entry point calls.

The phases run here without the device check (which refuses anything but a
TPU); the chip run is ``python chip_smoke.py`` through the chip tool.
"""

import os
import sys

import jax
import pytest

from mpi_and_open_mp_tpu.utils import runtime

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


@pytest.fixture(scope="module")
def oracle():
    return chip_smoke.make_oracle(use_native=False)


def test_phase_cli_glider_parity(tmp_path, oracle):
    row = chip_smoke.phase_cli(
        os.path.join(FIXTURES, "glider_10x10.cfg"), oracle, str(tmp_path))
    assert row["parity"] is True and row["board"] == [10, 10]
    assert row["layout"] == "row" and row["native_path"]
    assert row["run_s"] > 0
    assert "MOMP_TRACE" not in os.environ


def test_phase_board_parity(oracle):
    row = chip_smoke.phase_board(256, 20, 3, oracle)
    assert row["parity"] is True and row["board"] == [256, 256]
    assert row["impl"] == "roll" and row["native_path"] == "roll"
    assert row["warm_run_s"] > 0


def test_phase_multichip_on_four_virtual_devices(oracle):
    rows = chip_smoke.phase_multichip(256, 20, 5, oracle, devices=4)
    assert [r["phase"] for r in rows] == [
        "one_device", "multichip_cart", "multichip_row"]
    assert rows[1]["mesh"] == {"y": 2, "x": 2}
    assert rows[2]["mesh"] == {"y": 4}
    for r in rows[1:]:
        assert r["bit_identical_to_one_device"] is True
        assert len(r["devices"]) == 4
        assert set(r["block_until_ready"]) == {"20", "80"}


def test_main_refuses_a_host_without_tpu(capsys):
    assert chip_smoke.main([]) == 2
    out = capsys.readouterr()
    assert out.out == "" and "no TPU" in out.err


def test_compile_cache_dir_honours_env(monkeypatch, tmp_path):
    monkeypatch.setenv(runtime.CACHE_ENV, str(tmp_path))
    assert runtime.compile_cache_dir() == str(tmp_path)
    before = jax.config.jax_compilation_cache_dir
    assert runtime.enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; the helper sets no other directory.
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_dir_defaults_to_fixed_repo_path(monkeypatch):
    monkeypatch.delenv(runtime.CACHE_ENV, raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert runtime.compile_cache_dir() == want
    before = jax.config.jax_compilation_cache_dir
    try:
        assert runtime.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    with open(os.path.join(REPO, ".gitignore")) as fd:
        assert ".jax_cache/" in fd.read().split()


def test_require_backend_accepts_only_tpu_or_explicit_cpu(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert runtime.require_backend() == "cpu"
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(RuntimeError, match="not a TPU"):
        runtime.require_backend()
