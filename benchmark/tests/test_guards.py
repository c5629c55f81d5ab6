"""The runner measures a TPU or nothing: no CPU, no unknown device, no
result from a directory that holds only the benchmark."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import suite


def test_unknown_device_kind_is_an_error():
    assert suite.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        suite.peaks("cpu")
    with pytest.raises(KeyError):
        suite.peaks("TPU v99")


def _run(cwd, env_extra):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(env_extra)
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "pod8192.runs",
         "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_cpu_is_refused_even_when_pinned():
    r = _run(suite.ROOT, {"JAX_PLATFORMS": "cpu"})
    assert r.returncode == 2, r.stderr[-2000:]
    assert r.stdout == ""
    assert "no TPU" in r.stderr


def test_benchmark_alone_gives_no_result(tmp_path):
    shutil.copy(os.path.join(suite.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(suite.BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".scratch", ".jax_cache",
                                                  "__pycache__"))
    r = _run(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert r.stdout == ""
    assert "mpi_and_open_mp_tpu" in r.stderr
    with open(tmp_path / "BENCHMARK.json") as fd:
        assert json.load(fd)["paths"] == ["benchmark"]
