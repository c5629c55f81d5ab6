"""Harness layer: config suite semantics, plot scripts, hello app, launchers."""

import os
import subprocess
import sys

import numpy as np
import pytest

from mpi_and_open_mp_tpu.apps import hello as hello_app
from mpi_and_open_mp_tpu.utils.config import load_config_py

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(REPO, "configs")


from conftest import oracle_n  # noqa: E402


def test_config_suite_present_and_parsable():
    expected = {
        "test_10x10.cfg": (10, 10, 0),
        "glider_10x10.cfg": (10, 10, 5),
        "mix_40x20.cfg": (40, 20, 18),
        "pulsar_field_500x500.cfg": (500, 500, 64 * 48),
        "gun_300x100.cfg": (300, 100, 36),
        "gun_big_500x500.cfg": (500, 500, None),
    }
    for name, (nx, ny, ncells) in expected.items():
        cfg = load_config_py(os.path.join(CONFIGS, name))
        assert (cfg.nx, cfg.ny) == (nx, ny), name
        if ncells is not None:
            assert len(cfg.cells) == ncells, name


def test_pulsar_field_period_3():
    cfg = load_config_py(os.path.join(CONFIGS, "pulsar_field_500x500.cfg"))
    b0 = cfg.board()
    assert not np.array_equal(oracle_n(b0, 1), b0)
    np.testing.assert_array_equal(oracle_n(b0, 3), b0)


def test_gosper_gun_emits_gliders():
    cfg = load_config_py(os.path.join(CONFIGS, "gun_300x100.cfg"))
    b0 = cfg.board()
    pop0 = b0.sum()
    pop120 = oracle_n(b0, 120).sum()
    # Period-30 gun: 4 gliders after 120 steps -> +20 cells.
    assert pop120 == pop0 + 4 * 5


def test_gun_full_1000_step_parity():
    """The gun fixture at its FULL configured step budget (SURVEY §4: the
    reference's p46gun runs 1000 steps) through the sharded 2-D engine —
    the longest-horizon parity gate in the suite. By step 1000 the gun's
    glider stream has wrapped the torus and collided with the gun itself,
    so this also exercises long-range wrap interactions."""
    from mpi_and_open_mp_tpu.models.life import LifeSim
    from mpi_and_open_mp_tpu.parallel import mesh as mesh_lib

    cfg = load_config_py(os.path.join(CONFIGS, "gun_300x100.cfg"))
    assert cfg.steps == 1000
    sim = LifeSim(cfg, layout="cart", impl="halo",
                  mesh=mesh_lib.make_mesh_2d(4, 2), fuse_steps=4)
    final = sim.run(save=False)
    np.testing.assert_array_equal(final, oracle_n(cfg.board(), 1000))


def test_mix_still_lifes_stable_block():
    cfg = load_config_py(os.path.join(CONFIGS, "mix_40x20.cfg"))
    b = oracle_n(cfg.board(), 4)
    # The block at (2..3, 2..3) must be untouched.
    assert b[2:4, 2:4].sum() == 4


def test_plot_life_script(tmp_path):
    times = tmp_path / "times.txt"
    times.write_text("30.0\n16.0\nCommand exited with non-zero status 1\n8.0\n")
    out = tmp_path / "accel.png"
    sys.path.insert(0, os.path.join(REPO, "analysis"))
    import plot_life

    rc = plot_life.main([str(times), str(out)])
    assert rc == 0 and out.exists() and out.stat().st_size > 1000
    np.testing.assert_allclose(plot_life.load_times(times), [30.0, 16.0, 8.0])


def test_plot_network_script(tmp_path, monkeypatch, capsys):
    csv = tmp_path / "probe.csv"
    csv.write_text("size,time\n1,2.5\n1000,3.5\n1000000,1002.5\n")
    sys.path.insert(0, os.path.join(REPO, "analysis"))
    import plot_network

    monkeypatch.chdir(tmp_path)
    rc = plot_network.main([str(csv)])
    assert rc == 0
    assert (tmp_path / "network_params.png").exists()
    out = capsys.readouterr().out
    assert "alpha=" in out and "r2=" in out


def test_plot_integral_script(tmp_path):
    """Integral speedup analog of the reference's integral_plots.ipynb
    cells 1-2: raw times + T1/TN accel PNGs from a times file, tolerant
    of gtime error lines."""
    times = tmp_path / "integral_out.txt"
    times.write_text(
        "120.4\n61.0\nCommand exited with non-zero status 1\n31.2\n")
    sys.path.insert(0, os.path.join(REPO, "analysis"))
    import plot_integral

    prefix = tmp_path / "integral_plot"
    rc = plot_integral.main([str(times), str(prefix)])
    assert rc == 0
    for suffix in (".png", "_accel.png"):
        p = tmp_path / f"integral_plot{suffix}"
        assert p.exists() and p.stat().st_size > 1000


def test_plot_bigboard_script(tmp_path):
    csv = tmp_path / "bb.csv"
    csv.write_text(
        "n,steps,path,steady_us_per_step,steady_gcups,differenced\n"
        "500,1000,vmem,0.2,1200.0,1\n"
        "2048,500,fused,2.0,2100.0,1\n"
        "9000,100,frame,60.0,1350.0,1\n")
    out = tmp_path / "bb.png"
    sys.path.insert(0, os.path.join(REPO, "analysis"))
    import plot_bigboard

    rc = plot_bigboard.main(["plot_bigboard", str(csv), str(out)])
    assert rc == 0 and out.exists() and out.stat().st_size > 1000


def test_plot_attention_script_with_and_without_bwd(tmp_path):
    sys.path.insert(0, os.path.join(REPO, "analysis"))
    import plot_attention

    full = tmp_path / "att.csv"
    # New-schema CSV (trailing engine column, possibly mixed mid-sweep).
    full.write_text(
        "seq,fwd_sec,fwd_tflops,bwd_sec,bwd_tflops,differenced,engine\n"
        "8192,0.003,48.0,0.010,47.0,1,pallas\n"
        "16384,0.012,46.0,0.042,45.0,1,jnp\n")
    out = tmp_path / "att.png"
    rc = plot_attention.main(["plot_attention", str(full), str(out)])
    assert rc == 0 and out.stat().st_size > 1000
    # All-forward CSV (e.g. --bwd-max 0): must render, not crash.
    fwd_only = tmp_path / "att_f.csv"
    fwd_only.write_text("seq,fwd_sec,fwd_tflops,bwd_sec,bwd_tflops,"
                        "differenced\n8192,0.003,48.0,,,1\n")
    out2 = tmp_path / "att_f.png"
    rc = plot_attention.main(["plot_attention", str(fwd_only), str(out2)])
    assert rc == 0 and out2.stat().st_size > 1000


def test_sweep_scripts_refuse_off_tpu(tmp_path):
    """The real-chip sweep recorders must refuse to record from a CPU
    backend rather than committing dishonest numbers."""
    sys.path.insert(0, os.path.join(REPO, "analysis"))
    import sweep_attention
    import sweep_bigboard

    for mod in (sweep_bigboard, sweep_attention):
        rc = mod.main(["--out", str(tmp_path / "x.csv")])
        assert rc == 1
        assert not (tmp_path / "x.csv").exists()
    # GQA flag validation fires before the backend refusal; 0 and
    # negative "divisors" are rejected too (0 would silently record a
    # full-MHA sweep under a GQA label).
    for bad in ("3", "0", "-2"):
        rc = sweep_attention.main(
            ["--kv-heads", bad, "--out", str(tmp_path / "x.csv")])
        assert rc == 2


def test_bench_cpu_end_to_end(capsys):
    """The driver-contract bench runs end to end under the explicit CPU
    pin (``JAX_PLATFORMS=cpu``, set by conftest) and prints one valid
    JSON line with the promised schema, stamped with the CPU it ran on
    (the TPU-only sharded/attention extras rightly absent)."""
    import json

    sys.path.insert(0, REPO)
    import bench

    assert os.environ["JAX_PLATFORMS"] == "cpu"
    rc = bench.main(["--board", "64", "--steps", "64"])
    assert rc == 0
    line = capsys.readouterr().out.strip().splitlines()[-1]
    rec = json.loads(line)
    assert rec["metric"] == "life_steady_cups_p46gun_big"
    assert rec["unit"] == "cell_updates_per_sec"
    assert rec["value"] > 0 and rec["vs_baseline"] > 0
    assert rec["backend"] == rec["platform"] == "cpu"
    assert rec["device_kind"] == "cpu" and rec["devices"] == 8
    for gone in ("backend_fallback", "chip_record", "fallback_reason",
                 "degraded"):
        assert gone not in rec, gone
    assert "error" not in rec and "sharded_steady_cups" not in rec
    # The ring-hop engine provenance (fwd / bwd / zigzag) rides EVERY
    # line, CPU lines included — honest "jnp"-family stamps here.
    for key in ("attention_hop_engine", "attention_hop_engine_bwd",
                "attention_hop_engine_zz"):
        stamp = rec[key]
        assert stamp == "jnp" or stamp.startswith(("local:", "pallas:")), (
            key, stamp)


def test_bench_refuses_an_unpinned_cpu(capsys, monkeypatch):
    """A host where JAX found no chip and nobody asked for the CPU gets an
    error line and a non-zero exit, never a CPU number."""
    import json

    sys.path.insert(0, REPO)
    import bench

    monkeypatch.delenv("JAX_PLATFORMS")
    rc = bench.main(["--board", "64", "--steps", "64"])
    assert rc == 1
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["phase"] == "backend" and "not a TPU" in rec["error"]
    assert "value" not in rec


def test_bench_phase_error_exits_nonzero(capsys, monkeypatch, tmp_path):
    """A headline extra that fails (here the trace probe) keeps its
    ``*_error`` field on the printed line, and the run exits non-zero."""
    import json

    from mpi_and_open_mp_tpu.parallel import context

    sys.path.insert(0, REPO)
    import bench

    def boom(*a, **k):
        raise RuntimeError("injected")

    trace = str(tmp_path / "trace.jsonl")
    monkeypatch.setenv("MOMP_TRACE", trace)  # restored after the test
    monkeypatch.setattr(context, "ring_attention", boom)
    rc = bench.main(["--board", "64", "--steps", "64", "--trace", trace])
    assert rc == 1
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["trace_probe_error"] == "RuntimeError: injected"
    assert rec["value"] > 0


def test_native_path_matches_dispatcher_gates():
    """native_path is the single source of truth the sweeps label rows
    with; pin its decisions at the regime boundaries."""
    from mpi_and_open_mp_tpu.ops.pallas_life import native_path

    assert native_path((500, 500)) == "vmem"
    assert native_path((3072, 3072)) == "vmem"
    assert native_path((8192, 8192)) == "fused"
    assert native_path((10000, 10000)) == "frame"  # ny % 32 != 0
    assert native_path((8192, 8192), on_tpu=False) == "xla"


def test_hello_app(capsys):
    rc = hello_app.main(["--devices", "8"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "ring ok" in out
    assert "device 3 received hello from device 2" in out


def test_run_life_launcher_virtual(tmp_path):
    """End-to-end launcher sweep on the virtual CPU mesh (2 points)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + ":" + env.get("PYTHONPATH", "")
    times_path = tmp_path / "times.txt"
    r = subprocess.run(
        ["bash", os.path.join(REPO, "launchers", "run_life.sh"),
         "--cfg=configs/glider_10x10.cfg", "--max-dev=2", "--virtual",
         "--layout=row", f"--times-file={times_path}"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300,
    )
    assert r.returncode == 0, r.stderr
    lines = [l for l in times_path.read_text().strip().split("\n") if l]
    assert len(lines) == 2
    for l in lines:
        float(l)


def test_committed_results_layer_parses():
    """The recorded-measurement artifacts under results/ (the analogue of
    the reference's committed times.txt / out_*.csv) must stay consumable
    by the analysis layer."""
    sys.path.insert(0, os.path.join(REPO, "analysis"))
    import plot_life
    import plot_network

    results = os.path.join(REPO, "results")
    for rel in ("life/times_virtual8.txt", "life/times_job2.txt",
                "life/times_job2_fuse10.txt", "integral/times_virtual8.txt"):
        times = plot_life.load_times(os.path.join(results, rel))
        assert len(times) >= 2 and (times > 0).all(), rel
    for rel in ("network/out_single.csv", "network/out_mult.csv",
                "network/out_tpu_loopback.csv"):
        rows = plot_network.load_csv(os.path.join(results, rel))
        assert len(rows) == 7 and rows[0][0] == 1, rel
        assert all(t > 0 for _, t in rows), rel
    import csv as csv_mod

    for rel, col in (("life/bigboard_tpu.csv", "steady_gcups"),
                     ("attention/attention_tpu.csv", "fwd_tflops"),
                     ("attention/attention_gqa_tpu.csv", "fwd_tflops")):
        with open(os.path.join(results, rel)) as f:
            rows = list(csv_mod.DictReader(f))
        assert rows and all(float(r[col]) > 0 for r in rows), rel
    for png in ("life/life_accel_virtual8.png", "network/network_params.png",
                "life/bigboard_tpu.png", "attention/attention_tpu.png",
                "attention/attention_gqa_tpu.png"):
        assert os.path.getsize(os.path.join(results, png)) > 1000, png


def test_mpi_baseline_serial_oracle_builds_and_matches():
    """mpi_baseline/Makefile must compile the reference's serial oracle
    from the read-only reference tree and its VTK output must agree with
    this framework's oracle — the self-contained --backend=mpi
    prerequisite (SURVEY §7 step 7). MPI binaries need mpicc (absent in
    this image); the serial target proves the build plumbing."""
    import shutil
    import tempfile

    ref = "/root/reference"
    if not os.path.isdir(ref):
        pytest.skip("reference tree not present")
    repo = REPO
    r = subprocess.run(
        ["make", "-C", os.path.join(repo, "mpi_baseline"), "life2d",
         f"REF_DIR={ref}"],
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 0, r.stderr
    binary = os.path.join(repo, "mpi_baseline", "build", "life2d")
    with tempfile.TemporaryDirectory() as tmp:
        cfg_path = os.path.join(repo, "configs", "glider_10x10.cfg")
        shutil.copy(cfg_path, tmp)
        r = subprocess.run(
            [binary, "glider_10x10.cfg"], cwd=tmp,
            capture_output=True, text=True, timeout=120,
        )
        assert r.returncode == 0, r.stderr
        from mpi_and_open_mp_tpu.utils.vtk import read_vtk

        cfg = load_config_py(cfg_path)
        got = read_vtk(os.path.join(tmp, "life_000075.vtk"))
        np.testing.assert_array_equal(got, oracle_n(cfg.board(), 75))
