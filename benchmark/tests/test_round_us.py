"""round_us on a synthetic trace and spans, the metrics of the row 4x1
cell, and that cell driven through ``run_cell`` on the CPU at its own
board and plan, for fewer steps."""

import pytest

import devtrace
import run
import suite

SPEC = suite.load()
CELL = "p46gun_big_row4x1.runs"


def ctx(spans=None, trace=None):
    w = run.Window(seconds=1.0, runs=[(0.0, 0.5), (0.5, 0.5)])
    cfg = {"trace": {"step_module": "^jit_advance\\(",
                     "collective_op": "^collective-permute"}}
    return run.Ctx(config=cfg, setup_s=1.0, window=w, cells_per_run=1,
                   spans=spans, trace=trace)


def read(c):
    return suite.reader(SPEC, "round_us.host_bound")(c)


def advance(rounds, name="life.advance"):
    return {"kind": "span", "name": name, "dur": 0.005,
            "attrs": {"run": 1, "steps": 10000, "impl": "bitfused",
                      "layout": "row", "board_cells": 250000,
                      "frame_cells": 262144, "rounds": rounds,
                      "halo_bytes": rounds * 16384}}


def trace():
    # two chips; the stepping program 4.2 ms on one and 4.0 ms on the
    # other, in two launches; another program that the reader leaves out
    return devtrace.Trace(
        devices={
            "/device:TPU:0": {
                "ops": [["life_fused_window", 0, 4_200_000]],
                "modules": [["jit_advance(123)", 0, 2_100_000],
                            ["jit_advance(123)", 3_000_000, 2_100_000],
                            ["jit_pack(7)", 6_000_000, 900_000]]},
            "/device:TPU:1": {
                "ops": [["life_fused_window", 0, 4_000_000]],
                "modules": [["jit_advance(123)", 0, 2_000_000],
                            ["jit_advance(123)", 3_000_000, 2_000_000]]}},
        host=[])


def test_round_us():
    # 4.1 ms of stepping a chip over 2 x 105 rounds
    spans = [advance(105), advance(105, name="life.segment")]
    assert read(ctx(spans, trace())) == pytest.approx(4100 / 210)
    upload = {"kind": "span", "name": "life.upload", "dur": 0.001,
              "attrs": {"run": 1, "bytes": 1, "rounds": 1000}}
    assert read(ctx(spans + [upload], trace())) == pytest.approx(4100 / 210)


def test_none_without_rounds_or_trace():
    bare = {"kind": "span", "name": "life.advance", "dur": 0.005,
            "attrs": {"run": 1, "steps": 10000, "impl": "bitfused",
                      "layout": "row"}}
    assert read(ctx([bare], trace())) is None
    assert read(ctx([], trace())) is None
    assert read(ctx(None, trace())) is None
    assert read(ctx([advance(105)], None)) is None
    empty = devtrace.Trace(devices={}, host=[])
    assert read(ctx([advance(105)], empty)) is None


def test_the_cells_metrics_have_readers():
    names = {False: {"cups.host_bound", "setup_s"},
             True: {"kernel_cups.host_bound", "device_idle_pct.host_bound",
                    "upload_ms.host_bound", "collect_ms.host_bound",
                    "dispatch_ms.host_bound", "collective_pct.host_bound",
                    "halo_bytes_per_step.host_bound",
                    "window_amp.host_bound", "round_us.host_bound"}}
    assert suite.cell(SPEC, CELL)["chips"] == 4
    for traced, want in names.items():
        entries = suite.metrics(SPEC, CELL, traced)
        assert {m["name"] for m in entries} == want
        for m in entries:
            assert callable(suite.reader(SPEC, m["name"]))


def test_traced_cell_reports_the_plan():
    """The cell's own board (the cfg's cells at a seeded offset) on four
    CPU devices for 200 steps: the row plan of the chip, three rounds a
    run, correct against the reference."""
    config = suite.config(SPEC, "p46gun_big_row4x1")
    config.update(steps=200, impl="bitfused")
    r = run.run_cell(SPEC, CELL, 2**33 + 17, 0.3, True, config=config,
                     require_tpu=False)
    assert r["correct"]
    assert r["check"]["mismatched_cells"]["value"] == 0
    m = r["metrics"]
    assert m["window_amp.host_bound"] == {"value": 2.5, "unit": "x"}
    assert m["halo_bytes_per_step.host_bound"]["value"] == pytest.approx(
        3 * 16384 / 200)
