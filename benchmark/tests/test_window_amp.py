"""window_amp on recorded spans, and on a small 2x2 cell whose plan is
forced onto the fused tiled kernel."""

from functools import partial

import pytest

import run
import suite

SPEC = suite.load()


def ctx(spans=None):
    w = run.Window(seconds=1.0, runs=[(0.0, 1.0)])
    return run.Ctx(config={}, setup_s=1.0, window=w, cells_per_run=1,
                   spans=spans)


def read(c):
    return suite.reader(SPEC, "window_amp")(c)


def advance(window, frame, name="life.advance"):
    return {"kind": "span", "name": name, "dur": 0.2,
            "attrs": {"run": 1, "steps": 10000, "impl": "pallas",
                      "layout": "serial", "window_cells": window,
                      "frame_cells": frame, "board_cells": frame}}


# One run's stepping span of each fused-tile cell at the default tile
# budget: 8192² in full-width row tiles of 128 words (136/128); 8192² on
# 2x2 in one 128 x 4096 tile a chip; 10000² on 2x2, a 10240² frame in one
# 160 x 5120 tile a chip.
POD = advance(8192 ** 2 * 136 // 128, 8192 ** 2)
CART = advance(4 * 136 * 32 * 4352, 8192 ** 2)
UNALIGNED = advance(4 * 168 * 32 * 5376, 10240 ** 2)


def test_window_amp():
    assert read(ctx([POD] * 3)) == pytest.approx(1.0625)
    assert read(ctx([CART])) == pytest.approx(1.12890625)
    assert read(ctx([UNALIGNED, UNALIGNED])) == pytest.approx(1.1025)
    # sums over the spans, a segment as an advance
    seg = advance(3 * 100, 100, name="life.segment")
    assert read(ctx([seg, advance(100, 100)])) == pytest.approx(2.0)


def test_none_without_the_counters():
    bare = {"kind": "span", "name": "life.advance", "dur": 0.2,
            "attrs": {"run": 1, "steps": 10000, "impl": "bitfused",
                      "layout": "cart", "board_cells": 1, "frame_cells": 1,
                      "rounds": 79, "halo_bytes": 1}}
    upload = {"kind": "span", "name": "life.upload", "dur": 0.01,
              "attrs": {"run": 1, "bytes": 1, "window_cells": 1,
                        "frame_cells": 1}}
    assert read(ctx([bare, upload])) is None
    assert read(ctx([])) is None
    assert read(ctx()) is None


def test_traced_cell_reports_the_plan(monkeypatch):
    """784x528 on the 2x2 mesh at a budget that tiles the plan: the
    cell reports the tiled stepper's window over its frame."""
    from mpi_and_open_mp_tpu.ops import bitlife

    monkeypatch.setattr(bitlife, "plan_sharded_bits",
                        partial(bitlife.plan_sharded_bits, budget=30_000))
    config = suite.config(SPEC, "unaligned10000_cart2x2")
    config.update(nx=528, ny=784, steps=140, impl="bitfused")
    plan = bitlife.plan_sharded_bits((784, 528), 2, 2, True, True)
    assert plan.mode == "tiled"
    cells = bitlife.plan_tile_cells(plan)
    r = run.run_cell(SPEC, "unaligned10000_cart2x2.runs", 2**33 + 13, 0.3,
                     True, config=config, require_tpu=False)
    assert r["correct"]
    assert r["metrics"]["window_amp"] == {
        "value": pytest.approx(cells["window_cells"] / cells["frame_cells"]),
        "unit": "x"}
    assert r["metrics"]["window_amp"]["value"] > 1
