"""The harness finds every part of a cell by name, and BENCHMARK.json
keeps to the benchmark's contract."""

import json
import os
import re
import shutil

import numpy as np
import pytest

import run
import suite

SPEC = suite.load()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("cell", [c["name"] for c in SPEC["workloads"]])
def test_cell_parts_found_by_name(cell):
    c = suite.cell(SPEC, cell)
    config = suite.config(SPEC, c["config"])
    assert config["nx"] > 0 and config["ny"] > 0 and config["steps"] > 0
    assert suite.traffic(SPEC, c["traffic"])["loop"] in ("closed", "open")
    for traced in (False, True):
        entries = suite.metrics(SPEC, cell, traced)
        assert entries
        for m in entries:
            assert callable(suite.reader(SPEC, m["name"]))


def test_unknown_names_refused():
    with pytest.raises(KeyError):
        suite.cell(SPEC, "no_such.cell")
    with pytest.raises(KeyError):
        suite.config(SPEC, "no_such_config")


def test_new_config_picked_up_without_editing(tmp_path):
    """A later PR adds a configuration and a cell as a new file and new
    entries; no existing file of the benchmark changes."""
    bench = tmp_path / "benchmark"
    shutil.copytree(suite.BENCH, bench,
                    ignore=shutil.ignore_patterns(".scratch", ".jax_cache",
                                                  "__pycache__"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    new = json.loads((bench / "configs" / "pod8192.json").read_text())
    new.update(nx=4096, ny=4096)
    (bench / "configs" / "pod4096.json").write_text(json.dumps(new))
    spec = json.loads(json.dumps({k: v for k, v in SPEC.items()
                                  if k != "_root"}))
    spec["configs"].append({"name": "pod4096", "source": "x",
                            "file": "benchmark/configs/pod4096.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "pod4096.runs", "config": "pod4096",
                              "traffic": "closed_loop_runs", "chips": 1,
                              "why": "x"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    loaded = suite.load(str(tmp_path))
    cell = suite.cell(loaded, "pod4096.runs")
    assert suite.config(loaded, cell["config"])["nx"] == 4096
    assert [m["name"] for m in suite.metrics(loaded, "pod4096.runs", False)]
    after = {p: p.read_bytes() for p in before}
    assert after == before


def _copy_bench(tmp_path):
    bench = tmp_path / "benchmark"
    shutil.copytree(suite.BENCH, bench,
                    ignore=shutil.ignore_patterns(".scratch", ".jax_cache",
                                                  "__pycache__"))
    return bench


def _spec_with(tmp_path, configs=(), workloads=(), metrics=()):
    """BENCHMARK.json with entries added: configurations, cells, and each
    new cell named in the ``workloads`` of the metrics ``metrics``."""
    spec = json.loads(json.dumps({k: v for k, v in SPEC.items()
                                  if k != "_root"}))
    spec["configs"] += list(configs)
    spec["workloads"] += list(workloads)
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] in metrics:
            m["workloads"] += [w["name"] for w in workloads]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return suite.load(str(tmp_path))


def test_new_mix_runs_without_editing(tmp_path):
    """A later PR adds an open-loop mix as a data file and a cell that
    uses it; the runner drives it, and no file of the benchmark, run.py
    among them, changes."""
    bench = _copy_bench(tmp_path)
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    (bench / "traffic" / "open_poisson.json").write_text(json.dumps(
        {"loop": "open", "arrival": "poisson", "rate_per_s": 40.0}))
    spec = _spec_with(tmp_path, workloads=[{
        "name": "p46gun_big.open", "config": "p46gun_big",
        "traffic": "open_poisson", "chips": 1, "why": "x"}],
        metrics=["cups.host_bound"])
    config = suite.config(spec, "p46gun_big")
    config.update(steps=20)
    r = run.run_cell(spec, "p46gun_big.open", 2**33 + 7, 0.5, False,
                     config=config, require_tpu=False)
    assert r["correct"] and 0 < r["attempted"] <= 21
    assert r["metrics"]["cups.host_bound"]["value"] > 0
    assert {p: p.read_bytes() for p in before} == before


def test_open_loop_seeds_reorder_the_same_arrivals():
    mix = {"name": "m", "loop": "open", "rate_per_s": 50.0}
    a = run.arrivals(mix, 4.0, np.random.default_rng(1))
    b = run.arrivals(mix, 4.0, np.random.default_rng(2))
    assert len(a) == len(b) == 200 and a[0] == b[0] == 0
    gaps_a, gaps_b = (set(np.round(np.diff(x), 12)) for x in (a, b))
    assert len(gaps_a & gaps_b) >= 198  # one gap each falls past the end
    assert not np.allclose(np.diff(a), np.diff(b))
    assert run.arrivals({"name": "c", "loop": "closed"}, 4.0, None) is None
    with pytest.raises(ValueError):
        run.arrivals({"name": "x", "loop": "burst"}, 4.0, None)


def test_snapshot_config_writes_its_snapshots(tmp_path, monkeypatch):
    """A configuration that saves inside its run gets a snapshot
    directory; the timed runs write into it."""
    from mpi_and_open_mp_tpu.models.life import LifeSim

    written = []
    save = LifeSim.save_snapshot

    def counted(self, *a, **k):
        out = save(self, *a, **k)
        written.append(out)
        return out

    monkeypatch.setattr(LifeSim, "save_snapshot", counted)
    config = suite.config(SPEC, "p46gun_big")
    config.update(steps=12, save_steps=4)
    board = run.make_board(config, 5)
    with pytest.raises(ValueError):
        run.build_sim(config, board, run._devices(1, False))
    r = run.run_cell(SPEC, "p46gun_big.runs", 5, 0.2, False, config=config,
                     require_tpu=False)
    assert r["correct"]
    assert len(written) == 3 * (r["attempted"] + 1)  # and the untimed run


def test_benchmark_json_keeps_the_contract():
    top = {k for k in SPEC if k != "_root"}
    assert top == {"command", "paths", "run_seconds", "configs",
                   "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["benchmark"]
    assert 1 <= SPEC["run_seconds"] <= 51
    root = SPEC["_root"]
    configs = {c["name"]: c for c in SPEC["configs"]}
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"])
        assert c["file"].startswith("benchmark/")
        assert os.path.isfile(os.path.join(root, c["file"]))
        assert all(NAME.match(k) for k in c["reduced"])
        assert len(c["source"]) <= 200 and len(c["why"]) <= 200
        data = json.load(open(os.path.join(root, c["file"])))
        assert data["reduced"] == c["reduced"]
    cells = {w["name"]: w for w in SPEC["workloads"]}
    assert len(cells) == len(SPEC["workloads"])
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["config"] in configs
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    assert {w["config"] for w in SPEC["workloads"]} == set(configs)
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(cells) // 2)
    names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(set(names)) == len(names)
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= set(cells)
        moved = set(e2e[m["moves"]].get("workloads", cells))
        assert set(m.get("workloads", cells)) <= moved
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert callable(suite.reader(SPEC, m["name"]))
    for cell in cells:
        ends = [m["name"] for m in suite.metrics(SPEC, cell, False)]
        assert "setup_s" in ends and len(ends) >= 2
        assert suite.metrics(SPEC, cell, True)
    assert os.path.getsize(os.path.join(root, "BENCHMARK.json")) <= 65536
