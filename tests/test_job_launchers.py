"""The multi-host-style job launchers (launchers/job_*.sh) end to end.

The analogue of the reference's PBS batch layer (``3-life/job_life.sh``,
``2-network-params/job_mult.sh``): each script drives N real
``jax.distributed`` processes on this machine (CPU backend, one device per
process — the single-machine stand-in for a DCN pod) and produces the same
artifacts the reference's cluster runs committed (times.txt lines, CSV
rows). Heavier than unit tests (each rank is a full JAX runtime), so the
sweeps are kept minimal.
"""

import os
import subprocess

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, *args, timeout=240):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    return subprocess.run(
        [os.path.join(REPO, "launchers", script), *args],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO,
    )


def test_job_life_two_process_sweep(tmp_path):
    """np=1..2 Life sweep: each np appends exactly ONE wall-seconds line
    (rank-0-only output discipline), consumable by analysis/plot_life.py."""
    times = tmp_path / "times.txt"
    r = _run("job_life.sh",
             "--cfg=tests/fixtures/rpentomino_40x32.cfg",
             "--max-procs=2", f"--times-file={times}")
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr}"
    lines = times.read_text().strip().splitlines()
    assert len(lines) == 2, lines
    assert all(float(x) > 0 for x in lines)


def test_job_pingpong_mult_placement(tmp_path):
    """The 2-process fabric probe (the reference's job_mult.sh placement)
    writes the reference CSV schema from rank 0."""
    out = tmp_path / "out_mult.csv"
    r = _run("job_pingpong.sh", "--placement=mult", "--reps=5",
             "--max-power=2", f"--out={out}")
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr}"
    rows = out.read_text().strip().splitlines()
    assert rows[0] == "size,time" and len(rows) == 4
    sizes = [int(line.split(",")[0]) for line in rows[1:]]
    assert sizes == [1, 10, 100]
    assert all(float(line.split(",")[1]) > 0 for line in rows[1:])


def test_job_integral_two_process(tmp_path):
    times = tmp_path / "times_int.txt"
    r = _run("job_integral.sh", "--n=1000000", "--max-procs=2",
             f"--times-file={times}")
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr}"
    lines = times.read_text().strip().splitlines()
    assert len(lines) == 2
    assert all(float(x) >= 0 for x in lines)


def test_job_attention_zigzag_grad(tmp_path):
    """The long-context job launcher: 2 real processes running the
    striped/zigzag causal ring with GQA and the flash backward; the
    primary rank's parity check passes and exactly one elapsed-seconds
    line lands in the times file (Gloo banners share stdout, so the
    launcher matches the contract line by shape)."""
    times = tmp_path / "times_att.txt"
    r = _run("job_attention.sh", "--procs=2", "--variant=ring",
             "--layout=zigzag", "--seq=256", "--heads=4", "--kv-heads=2",
             "--head-dim=16", "--causal", "--grad",
             f"--times-file={times}")
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr}"
    assert "parity ok" in r.stderr
    lines = times.read_text().strip().splitlines()
    assert len(lines) == 1 and float(lines[0]) > 0
