"""The Model layer's per-phase readers (``upload_ms``, ``collect_ms``,
``dispatch_ms``) on synthetic spans and traces."""

import pytest

import devtrace
import run
import suite

SPEC = suite.load()


def ctx(spans=None, trace=None):
    w = run.Window(seconds=1.0, runs=[(0.0, 1.0)])
    return run.Ctx(config={}, setup_s=1.0, window=w, cells_per_run=1,
                   spans=spans, trace=trace)


def read(name, c):
    return suite.reader(SPEC, name)(c)


def span(name, dur, kind="span"):
    return {"kind": kind, "name": name, "dur": dur}


@pytest.mark.parametrize("metric,name", [("upload_ms", "life.upload"),
                                         ("collect_ms", "life.collect")])
def test_phase_median_in_ms(metric, name):
    spans = [span(name, 0.004), span(name, 0.010), span(name, 0.002),
             span("life.advance", 9.0), span(name, 5.0, kind="event")]
    assert read(metric, ctx(spans)) == pytest.approx(4.0)
    assert read(metric + ".host_bound", ctx(spans)) == pytest.approx(4.0)
    assert read(metric, ctx([span("life.advance", 1.0)])) is None
    assert read(metric, ctx([])) is None
    assert read(metric, ctx()) is None


def _two_chips(host):
    # ns. Chip 0: ops nested ([10,40) holds [15,20)) and one straddling
    # the second span's start; chip 1: one op straddling the first
    # span's end and one inside the second span.
    return devtrace.Trace(
        devices={
            "/device:TPU:0": {"ops": [["while:w", 10, 30],
                                      ["custom-call:k", 15, 5],
                                      ["copy:c", 90, 20]],
                              "modules": []},
            "/device:TPU:1": {"ops": [["fusion:f", 80, 40],
                                      ["fusion:g", 150, 10]],
                              "modules": []}},
        host=host)


def test_dispatch_ms_is_idle_inside_the_stepping_spans():
    host = [["bench.run", 0, 300], ["life.advance", 0, 100],
            ["life.collect", 100, 50], ["life.segment", 100, 100]]
    t = _two_chips(host)
    # [0,100): chip 0 busy [10,40)+[90,100) = 40 -> idle 60;
    #          chip 1 busy [80,100) = 20 -> idle 80; mean 70.
    # [100,200): chip 0 busy [100,110) = 10 -> idle 90;
    #            chip 1 busy [100,120)+[150,160) = 30 -> idle 70; mean 80.
    assert read("dispatch_ms", ctx(trace=t)) == pytest.approx(75 / 1e6)
    three = host + [["life.advance", 300, 10]]  # both chips idle: 10
    assert read("dispatch_ms.host_bound",
                ctx(trace=_two_chips(three))) == pytest.approx(70 / 1e6)


def test_dispatch_ms_none_without_stepping_events_or_trace():
    assert read("dispatch_ms", ctx()) is None
    assert read("dispatch_ms", ctx(trace=_two_chips(
        [["bench.run", 0, 100]]))) is None
    assert read("dispatch_ms", ctx(trace=devtrace.Trace(
        {}, [["life.advance", 0, 10]]))) is None
