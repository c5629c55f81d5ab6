"""The snapshot path's readers (``snapshot_ms``, ``vtk_write_ms``), the
``p46gun`` configuration driven at a small size, and the series'
reference against the committed golden frame."""

import json
import os

import numpy as np
import pytest

import reference
import reference_snap
import run
import suite

SPEC = suite.load()
GOLDEN = os.path.join(suite.ROOT, "tests", "fixtures",
                      "golden_glider_000000.vtk")


def ctx(spans=None):
    w = run.Window(seconds=1.0, runs=[(0.0, 1.0)])
    return run.Ctx(config={}, setup_s=1.0, window=w, cells_per_run=1,
                   spans=spans)


def read(name, c):
    return suite.reader(SPEC, name)(c)


def small(steps=12):
    config = suite.config(SPEC, "p46gun")
    config.update(nx=40, ny=20, steps=steps)
    return config


# A recorded span list: one reset() + run() of a 40x20 board, 3 steps,
# a frame every step (CPU, halo path, native writer), as MOMP_TRACE
# wrote it, less the ts, pid and host fields.
RECORDED = [json.loads(line) for line in """\
{"kind": "span", "name": "life.upload", "dur": 0.00024, "id": 2, "parent": null, "attrs": {"run": 2, "bytes": 800}}
{"kind": "span", "name": "life.collect", "dur": 4.9e-05, "id": 4, "parent": 3, "attrs": {"run": 2, "bytes": 800, "packed": false, "wire_bytes": 800}}
{"kind": "span", "name": "life.vtk_write", "dur": 0.001786, "id": 5, "parent": 3, "attrs": {"run": 2, "bytes": 1797, "writer": "native"}}
{"kind": "span", "name": "life.snapshot", "dur": 0.001917, "id": 3, "parent": null, "attrs": {"run": 2, "step": 0}}
{"kind": "span", "name": "life.segment", "dur": 0.000159, "id": 6, "parent": null, "attrs": {"run": 2, "start": 0, "stop": 1, "impl": "halo", "layout": "row", "guarded": false}}
{"kind": "span", "name": "life.collect", "dur": 6.7e-05, "id": 8, "parent": 7, "attrs": {"run": 2, "bytes": 800, "packed": false, "wire_bytes": 800}}
{"kind": "span", "name": "life.vtk_write", "dur": 0.000135, "id": 9, "parent": 7, "attrs": {"run": 2, "bytes": 1797, "writer": "native"}}
{"kind": "span", "name": "life.snapshot", "dur": 0.000257, "id": 7, "parent": null, "attrs": {"run": 2, "step": 1}}
{"kind": "span", "name": "life.segment", "dur": 7.5e-05, "id": 10, "parent": null, "attrs": {"run": 2, "start": 1, "stop": 2, "impl": "halo", "layout": "row", "guarded": false}}
{"kind": "span", "name": "life.collect", "dur": 4.1e-05, "id": 12, "parent": 11, "attrs": {"run": 2, "bytes": 800, "packed": false, "wire_bytes": 800}}
{"kind": "span", "name": "life.vtk_write", "dur": 0.000133, "id": 13, "parent": 11, "attrs": {"run": 2, "bytes": 1797, "writer": "native"}}
{"kind": "span", "name": "life.snapshot", "dur": 0.000221, "id": 11, "parent": null, "attrs": {"run": 2, "step": 2}}
{"kind": "span", "name": "life.segment", "dur": 7.3e-05, "id": 14, "parent": null, "attrs": {"run": 2, "start": 2, "stop": 3, "impl": "halo", "layout": "row", "guarded": false}}
{"kind": "span", "name": "life.collect", "dur": 4.7e-05, "id": 15, "parent": null, "attrs": {"run": 2, "bytes": 800, "packed": false, "wire_bytes": 800}}
""".splitlines()]


@pytest.mark.parametrize("metric,want", [("snapshot_ms", 0.257),
                                         ("vtk_write_ms", 0.135)])
def test_snapshot_readers_on_a_recorded_span_list(metric, want):
    assert read(metric + ".host_bound", ctx(RECORDED)) == pytest.approx(want)
    # an instant event of the same name is not a span
    spans = RECORDED + [{"kind": "event", "name": "life.snapshot"},
                        {"kind": "event", "name": "life.vtk_write"}]
    assert read(metric, ctx(spans)) == pytest.approx(want)


@pytest.mark.parametrize("metric", ["snapshot_ms", "vtk_write_ms"])
def test_snapshot_readers_none_without_their_span(metric):
    no_snapshots = [s for s in RECORDED
                    if s["name"] not in ("life.snapshot", "life.vtk_write")]
    assert read(metric, ctx(no_snapshots)) is None
    assert read(metric, ctx([])) is None
    assert read(metric, ctx()) is None


def test_snapshot_cell_writes_a_frame_a_step(tmp_path, monkeypatch):
    """``p46gun`` at 40x20 and 12 steps through ``run_cell``: every run,
    the untimed one included, writes 12 frames under one ``run`` number,
    and the final boards come out correct."""
    from mpi_and_open_mp_tpu.obs import trace

    sink = tmp_path / "spans.jsonl"
    monkeypatch.setenv("MOMP_TRACE", str(sink))
    trace.reset()
    try:
        r = run.run_cell(SPEC, "p46gun.snap", 2**32 + 11, 0.3, False,
                         config=small(), require_tpu=False)
    finally:
        trace.reset()
    assert r["correct"] and r["attempted"] >= 1
    assert r["metrics"]["cups.host_bound"]["value"] > 0
    writes = {}
    for rec in map(json.loads, sink.read_text().splitlines()):
        if rec["name"] == "life.vtk_write":
            writes.setdefault(rec["attrs"]["run"], []).append(rec)
    # the constructor's reset is run 1 and writes nothing
    assert len(writes) == r["attempted"] + 1
    assert all(len(w) == 12 for w in writes.values())


def test_traced_snapshot_cell_reports_its_readers():
    r = run.run_cell(SPEC, "p46gun.snap", 7, 0.3, True, config=small(4),
                     require_tpu=False)
    assert r["correct"]
    for name in ("snapshot_ms.host_bound", "vtk_write_ms.host_bound",
                 "collect_ms.host_bound"):
        assert r["metrics"][name]["value"] > 0


def test_reference_snap_is_the_golden_frame():
    """The committed golden frame is the glider fixture's board at step
    0; the reference's text for that board is the file, byte for byte."""
    steps, save_steps, board = reference_snap.read_cfg(os.path.join(
        suite.ROOT, "tests", "fixtures", "glider_10x10.cfg"))
    with open(GOLDEN, "rb") as fd:
        golden = fd.read()
    assert reference_snap.vtk_text(board).encode() == golden
    (first, b0), *_ = reference_snap.series(board, steps, save_steps)
    assert first == 0 and np.array_equal(b0, board)


def test_series_steps_and_compare(tmp_path):
    """The glider fixture saves every 25 of 100 steps: frames at 0, 25,
    50 and 75, each the board ``reference.life_steps`` gives there; a
    directory of exactly those frames compares equal, and one frame
    altered, one missing or one extra is counted."""
    steps, save_steps, board = reference_snap.read_cfg(os.path.join(
        suite.ROOT, "tests", "fixtures", "glider_10x10.cfg"))
    got = list(reference_snap.series(board, steps, save_steps))
    assert [i for i, _ in got] == [0, 25, 50, 75]
    for i, b in got:
        assert np.array_equal(b, reference.life_steps(board, i))
        (tmp_path / reference_snap.frame_name(i)).write_text(
            reference_snap.vtk_text(b))
    out = reference_snap.compare(str(tmp_path), board, steps, save_steps)
    assert out == {"frames_expected": 4, "frames_equal": 4,
                   "frames_differing": 0, "frames_missing": 0,
                   "files_extra": []}
    (tmp_path / "life_000025.vtk").write_text(
        reference_snap.vtk_text(1 - got[1][1]))
    (tmp_path / "life_000050.vtk").unlink()
    (tmp_path / "life_000100.vtk").write_text("")
    out = reference_snap.compare(str(tmp_path), board, steps, save_steps)
    assert (out["frames_equal"], out["frames_differing"],
            out["frames_missing"], out["files_extra"]) == (
        2, 1, 1, ["life_000100.vtk"])
