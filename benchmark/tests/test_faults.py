"""A whole run, past the harness's look for a chip, with the timed path
broken underneath: ``correct`` has to come out false for each fault a
cell can have, and true with none."""

import numpy as np
import pytest

import run
import suite
from mpi_and_open_mp_tpu.models.life import LifeSim
from mpi_and_open_mp_tpu.parallel import halo

SPEC = suite.load()
CELLS = [w["name"] for w in SPEC["workloads"]]


def drive(cell, seed=2**31 + 99, n=64, steps=40):
    config = suite.config(SPEC, suite.cell(SPEC, cell)["config"])
    if config["nx"] > 512:  # a cfg's cells keep their own board
        config.update(nx=n, ny=n)
    config.update(steps=steps)
    return run.run_cell(SPEC, cell, seed, 0.3, False, config=config,
                        require_tpu=False)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    r = drive(cell)
    assert r["correct"] and r["attempted"] > 0
    assert r["check"]["mismatched_cells"]["value"] == 0


@pytest.mark.parametrize("cell", CELLS)
def test_state_left_unchanged(cell, monkeypatch):
    monkeypatch.setattr(LifeSim, "step", lambda self, n=1: None)
    r = drive(cell)
    assert not r["correct"] and r["failed"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_answer_altered_where_produced(cell, monkeypatch):
    collect = LifeSim.collect

    def altered(self):
        board = np.array(collect(self))
        board[3, 5] ^= 1
        return board

    monkeypatch.setattr(LifeSim, "collect", altered)
    assert not drive(cell)["correct"]


def test_exchange_between_chips_left_out(monkeypatch):
    """Every halo comes from the shard itself: each chip wraps its own
    block as if it held the whole torus."""
    monkeypatch.setattr(halo, "ring_perm",
                        lambda p, shift=1: [(i, i) for i in range(p)])
    assert not drive("pod8192_cart2x2.runs")["correct"]
