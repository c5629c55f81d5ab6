"""The padded frame's readers (``frame_pad_pct``,
``halo_bytes_per_step``) on recorded spans, and on a small
``unaligned10000_cart2x2`` cell driven through ``run_cell``."""

import pytest

import run
import suite

SPEC = suite.load()


def ctx(spans=None):
    w = run.Window(seconds=1.0, runs=[(0.0, 1.0)])
    return run.Ctx(config={}, setup_s=1.0, window=w, cells_per_run=1,
                   spans=spans)


def read(name, c):
    return suite.reader(SPEC, name)(c)


def advance(steps, board, frame, round_bytes, k_max=128):
    rounds = -(-steps // k_max)
    return {"kind": "span", "name": "life.advance", "dur": 0.2,
            "attrs": {"run": 1, "steps": steps, "impl": "bitfused",
                      "layout": "cart", "board_cells": board,
                      "frame_cells": frame, "rounds": rounds,
                      "halo_bytes": rounds * round_bytes}}


# One run's stepping span of each 2x2 cell, with the counters the
# program writes for its plan: 8192² in an exact frame, 270336 bytes a
# round; 10000² in a 10240² frame, 987136 bytes a round.
ALIGNED = advance(10000, 8192 ** 2, 8192 ** 2, 270336)
UNALIGNED = advance(10000, 10000 ** 2, 10240 ** 2, 987136)


def test_frame_pad_pct():
    assert read("frame_pad_pct", ctx([ALIGNED])) == 0.0
    assert read("frame_pad_pct", ctx([UNALIGNED] * 3)) == pytest.approx(
        4.8576)


def test_halo_bytes_per_step():
    assert read("halo_bytes_per_step", ctx([ALIGNED] * 2)) == pytest.approx(
        79 * 270336 / 10000)
    assert read("halo_bytes_per_step", ctx([UNALIGNED])) == pytest.approx(
        79 * 987136 / 10000)
    # a segment counts its steps from start and stop
    seg = {"kind": "span", "name": "life.segment", "dur": 0.1,
           "attrs": {"run": 2, "start": 100, "stop": 150, "rounds": 1,
                     "halo_bytes": 987136, "board_cells": 1,
                     "frame_cells": 1}}
    assert read("halo_bytes_per_step", ctx([seg, UNALIGNED])) == (
        pytest.approx(80 * 987136 / 10050))


def test_none_without_the_counters():
    bare = {"kind": "span", "name": "life.advance", "dur": 0.2,
            "attrs": {"run": 1, "steps": 10000, "impl": "bitfused",
                      "layout": "cart"}}
    upload = {"kind": "span", "name": "life.upload", "dur": 0.01,
              "attrs": {"run": 1, "bytes": 1}}
    for name in ("frame_pad_pct", "halo_bytes_per_step"):
        assert read(name, ctx([bare, upload])) is None
        assert read(name, ctx([])) is None
        assert read(name, ctx()) is None


def small():
    """300x400 on the 2x2 mesh, stepped by the bitfused path on the CPU:
    a 320x512 frame, padded on both sharded axes, two rounds a run."""
    config = suite.config(SPEC, "unaligned10000_cart2x2")
    config.update(nx=400, ny=300, steps=140, impl="bitfused")
    return config


def test_traced_cell_reports_its_counters():
    r = run.run_cell(SPEC, "unaligned10000_cart2x2.runs", 2**33 + 11, 0.3,
                     True, config=small(), require_tpu=False)
    assert r["correct"]
    m = r["metrics"]
    assert m["frame_pad_pct"] == {
        "value": pytest.approx(100 * (320 * 512 - 300 * 400) / 120000),
        "unit": "%"}
    # a round: x, 2 x 5 words x 240 columns; y, 2 x 5 words x 512 columns
    assert m["halo_bytes_per_step"]["value"] == pytest.approx(
        2 * 4 * (2 * 5 * 240 + 2 * 5 * 512) / 140)
