"""The chunked snapshot path's reader (``frames_per_dispatch``) on
recorded spans, and on a small ``p46gun`` cell driven through
``run_cell``."""

import json

import pytest

import run
import suite

SPEC = suite.load()


def ctx(spans=None):
    w = run.Window(seconds=1.0, runs=[(0.0, 1.0)])
    return run.Ctx(config={}, setup_s=1.0, window=w, cells_per_run=1,
                   spans=spans)


def read(c):
    return suite.reader(SPEC, "frames_per_dispatch.host_bound")(c)


# Two runs of a 40x20 board, 12 steps, a frame every step, in chunks of
# at most 5 frames (CPU, halo path): their ``life.frames`` spans and the
# ``life.segment`` inside each, as MOMP_TRACE wrote them, less the ts,
# pid and host fields (durations rounded); one ``life.snapshot`` a run
# stands for its twelve.
RECORDED = [json.loads(line) for line in """\
{"kind": "span", "name": "life.segment", "dur": 0.00028, "id": 4, "parent": 3, "attrs": {"run": 2, "start": 0, "stop": 5, "impl": "halo", "layout": "row", "guarded": false}}
{"kind": "span", "name": "life.frames", "dur": 0.00038, "id": 3, "parent": null, "attrs": {"run": 2, "start": 0, "frames": 5, "wire_bytes": 4000}}
{"kind": "span", "name": "life.snapshot", "dur": 0.00033, "id": 5, "parent": null, "attrs": {"run": 2, "step": 0}}
{"kind": "span", "name": "life.segment", "dur": 0.00018, "id": 16, "parent": 15, "attrs": {"run": 2, "start": 5, "stop": 10, "impl": "halo", "layout": "row", "guarded": false}}
{"kind": "span", "name": "life.frames", "dur": 0.00027, "id": 15, "parent": null, "attrs": {"run": 2, "start": 5, "frames": 5, "wire_bytes": 4000}}
{"kind": "span", "name": "life.segment", "dur": 0.00023, "id": 28, "parent": 27, "attrs": {"run": 2, "start": 10, "stop": 12, "impl": "halo", "layout": "row", "guarded": false}}
{"kind": "span", "name": "life.frames", "dur": 0.00031, "id": 27, "parent": null, "attrs": {"run": 2, "start": 10, "frames": 2, "wire_bytes": 1600}}
{"kind": "span", "name": "life.segment", "dur": 0.00015, "id": 36, "parent": 35, "attrs": {"run": 3, "start": 0, "stop": 5, "impl": "halo", "layout": "row", "guarded": false}}
{"kind": "span", "name": "life.frames", "dur": 0.00023, "id": 35, "parent": null, "attrs": {"run": 3, "start": 0, "frames": 5, "wire_bytes": 4000}}
{"kind": "span", "name": "life.snapshot", "dur": 0.0002, "id": 37, "parent": null, "attrs": {"run": 3, "step": 0}}
{"kind": "span", "name": "life.segment", "dur": 0.00016, "id": 48, "parent": 47, "attrs": {"run": 3, "start": 5, "stop": 10, "impl": "halo", "layout": "row", "guarded": false}}
{"kind": "span", "name": "life.frames", "dur": 0.00026, "id": 47, "parent": null, "attrs": {"run": 3, "start": 5, "frames": 5, "wire_bytes": 4000}}
{"kind": "span", "name": "life.segment", "dur": 0.00019, "id": 60, "parent": 59, "attrs": {"run": 3, "start": 10, "stop": 12, "impl": "halo", "layout": "row", "guarded": false}}
{"kind": "span", "name": "life.frames", "dur": 0.00026, "id": 59, "parent": null, "attrs": {"run": 3, "start": 10, "frames": 2, "wire_bytes": 1600}}
""".splitlines()]


def test_mean_frames_over_the_chunk_spans():
    assert read(ctx(RECORDED)) == pytest.approx(4.0)  # 24 frames, 6 spans
    # an instant event of the same name is not a span
    spans = RECORDED + [{"kind": "event", "name": "life.frames",
                         "attrs": {"frames": 1000}}]
    assert read(ctx(spans)) == pytest.approx(4.0)


def test_none_without_chunk_spans():
    per_frame = [s for s in RECORDED if s["name"] != "life.frames"]
    assert read(ctx(per_frame)) is None
    assert read(ctx([])) is None
    assert read(ctx()) is None


def small():
    config = suite.config(SPEC, "p46gun")
    config.update(nx=40, ny=20, steps=12)
    return config


def test_traced_snapshot_cell_reports_frames_per_dispatch():
    """``p46gun`` at 40x20 and 12 steps: every frame of a run fits one
    chunk, so each dispatch brings all 12."""
    r = run.run_cell(SPEC, "p46gun.snap", 2**33 + 5, 0.3, True,
                     config=small(), require_tpu=False)
    assert r["correct"]
    assert r["metrics"]["frames_per_dispatch.host_bound"] == {
        "value": 12, "unit": "frames"}


def test_chunked_state_left_unchanged_is_caught(monkeypatch):
    """The chunked path steps through the sim's ``_advance``: with every
    advance a no-op, the snapshot cell's boards come out wrong."""
    from mpi_and_open_mp_tpu.models.life import LifeSim

    monkeypatch.setattr(LifeSim, "_build_advance",
                        lambda self: lambda board, n: board)
    r = run.run_cell(SPEC, "p46gun.snap", 2**31 + 99, 0.3, False,
                     config=small(), require_tpu=False)
    assert not r["correct"] and r["failed"] > 0
