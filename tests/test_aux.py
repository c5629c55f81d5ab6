"""Aux subsystems: checkpoint/resume, halo debug mode, profiler hook."""

import os

import numpy as np
import pytest

from mpi_and_open_mp_tpu.apps import life as life_app
from mpi_and_open_mp_tpu.models.life import LifeSim
from mpi_and_open_mp_tpu.utils.config import config_from_board

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")


from conftest import oracle_n  # noqa: E402


def test_resume_from_snapshot_bit_exact(tmp_path, make_board):
    """Run to completion in one go vs. interrupted-and-resumed: identical."""
    board = make_board(32, 40)
    cfg = config_from_board(board, steps=40, save_steps=10)
    out_a = tmp_path / "a"
    full = LifeSim(cfg, layout="row", impl="halo", outdir=out_a).run()

    # Interrupted run: stop after 25 steps (last snapshot at 20).
    out_b = tmp_path / "b"
    sim = LifeSim(cfg, layout="row", impl="halo", outdir=out_b)
    i = 0
    while i < 25:
        if i % cfg.save_steps == 0:
            sim.save_snapshot()
        n = min(cfg.save_steps - i % cfg.save_steps, 25 - i)
        sim.step(n)
        i += n
    latest = life_app.find_latest_snapshot(str(out_b))
    assert latest is not None and latest[1] == 20
    resumed = LifeSim.from_snapshot(
        cfg, latest[0], latest[1], layout="cart", impl="halo", outdir=out_b
    )
    final = resumed.run()
    np.testing.assert_array_equal(final, full)
    np.testing.assert_array_equal(final, oracle_n(board, 40))
    # Resumed run wrote the step-30 snapshot the interrupted run missed.
    assert os.path.exists(out_b / "life_000030.vtk")


def test_resume_cli(tmp_path, capsys, make_board):
    cfg_path = os.path.join(FIXTURES, "glider_10x10.cfg")
    outdir = tmp_path / "vtk"
    assert life_app.main([cfg_path, "--layout", "serial", "--impl", "roll",
                          "--outdir", str(outdir)]) == 0
    capsys.readouterr()
    rc = life_app.main([cfg_path, "--layout", "serial", "--impl", "roll",
                        "--outdir", str(outdir), "--resume"])
    assert rc == 0
    cap = capsys.readouterr()
    assert "resuming from" in cap.err and "life_000075.vtk" in cap.err


def test_resume_cli_no_snapshots(tmp_path, capsys):
    rc = life_app.main([os.path.join(FIXTURES, "glider_10x10.cfg"),
                        "--outdir", str(tmp_path / "none"), "--resume"])
    assert rc == 2


def test_debug_check_passes_and_fails(make_board):
    board = make_board(48, 40)
    cfg = config_from_board(board, steps=4, save_steps=0)
    sim = LifeSim(cfg, layout="cart", impl="halo", fuse_steps=2)
    sim.debug_check()  # must hold on a healthy pipeline
    sim.step(3)
    sim.debug_check()  # and at any intermediate state

    # Sabotage: a wrong advance must be caught.
    healthy = sim._advance
    sim._advance = lambda b, n: healthy(b, n + 1)
    with pytest.raises(AssertionError, match="diverge"):
        sim.debug_check()


def test_profile_flag_writes_trace(tmp_path, capsys, monkeypatch):
    """--profile captures a profiler trace and, with no other sink named,
    turns the span tracer on into DIR/spans.jsonl."""
    import json

    from mpi_and_open_mp_tpu.obs import trace

    monkeypatch.setenv("MOMP_TRACE", "")  # unset, and restored after
    prof = tmp_path / "trace"
    try:
        rc = life_app.main([os.path.join(FIXTURES, "glider_10x10.cfg"),
                            "--layout", "serial", "--impl", "roll",
                            "--profile", str(prof)])
    finally:
        trace.reset()
    assert rc == 0
    # jax.profiler.trace writes plugins/profile/<ts>/*.
    found = list(prof.rglob("*.xplane.pb")) + list(prof.rglob("*.trace.json.gz"))
    assert found, f"no trace artifacts under {prof}"
    spans = [json.loads(line)["name"]
             for line in (prof / "spans.jsonl").read_text().splitlines()]
    assert {"life.advance", "life.collect", "life.run"} <= set(spans)
