"""Metric arithmetic on synthetic windows, spans and traces."""

import pytest

import devtrace
import run
import suite

SPEC = suite.load()


def ctx(runs, seconds, cells_per_run=1000, spans=None, trace=None,
        setup_s=7.5):
    w = run.Window(seconds=seconds, runs=runs)
    cfg = {"trace": {"step_module": "advance",
                     "collective_op": "^collective-permute"}}
    return run.Ctx(config=cfg, setup_s=setup_s, window=w,
                   cells_per_run=cells_per_run, spans=spans,
                   trace=trace)


def read(name, c):
    return suite.reader(SPEC, name)(c)


def test_cups_counts_every_run_over_the_whole_window():
    runs = [(0.0, 0.4), (0.4, 0.4), (0.8, 0.5)]
    assert read("cups", ctx(runs, 1.3)) == pytest.approx(3000 / 1.3)
    assert read("cups", ctx([], 0.0)) is None


def test_a_split_metric_reads_its_base():
    assert suite.reader(SPEC, "cups.host_bound")(ctx([(0.0, 0.5)], 0.5)) == (
        pytest.approx(2000))
    with pytest.raises(KeyError):
        suite.reader(SPEC, "no_such_metric.part")


def test_setup_s_is_the_runner_reading():
    assert read("setup_s", ctx([(0.0, 1.0)], 1.0, setup_s=12.25)) == 12.25


def test_outside_step_pct():
    runs = [(0.0, 0.010), (0.010, 0.030)]
    spans = [{"kind": "span", "name": "life.advance", "dur": 0.006},
             {"kind": "span", "name": "life.advance", "dur": 0.024},
             {"kind": "span", "name": "life.run", "dur": 5.0},
             {"kind": "event", "name": "life.advance", "dur": 9.0}]
    got = read("outside_step_pct", ctx(runs, 0.04, spans=spans))
    assert got == pytest.approx(100 * (1 - 0.030 / 0.040))
    assert read("outside_step_pct", ctx(runs, 0.04, spans=[])) is None
    assert read("outside_step_pct", ctx(runs, 0.04)) is None


def _trace():
    # two devices; ns; device 0 busy [0,10)+[20,30), device 1 [0,40)
    return devtrace.Trace(
        devices={
            "/device:TPU:0": {
                "ops": [["fusion.1", 0, 10], ["collective-permute-done", 20,
                                               5], ["fusion.2", 25, 5]],
                "modules": [["jit_advance", 0, 30], ["jit_other", 35, 5]]},
            "/device:TPU:1": {
                "ops": [["fusion.1", 0, 30], ["collective-permute-done", 30,
                                               10]],
                "modules": [["jit_advance", 0, 40]]}},
        host=[["bench.run", 0, 40], ["bench.reset", 12, 4]])


def test_kernel_cups_and_idle_and_collectives():
    t = _trace()
    runs = [(0.0, 4e-8)]
    c = ctx(runs, 50e-9, cells_per_run=70, trace=t)
    assert t.busy_s() == pytest.approx((20 + 40) / 2 / 1e9)
    assert read("kernel_cups", c) == pytest.approx(70 / ((30 + 40) / 2e9))
    assert read("device_idle_pct", c) == pytest.approx(100 * (1 - 30 / 50))
    assert read("collective_pct", c) == pytest.approx(
        100 * (5 / 20 + 10 / 40) / 2)
    one = devtrace.Trace({"/device:TPU:0": t.devices["/device:TPU:0"]}, [])
    assert one.op_share("^all-reduce") is None
    assert read("collective_pct", ctx(runs, 1.0)) is None
    assert read("kernel_cups", ctx(runs, 1.0)) is None


def test_breakdown_splits_gaps_over_the_innermost_host_events():
    b = _trace().breakdown()
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(20e-9)]
    assert len(b["device_ops"]) <= 10
    # device 0's one gap, [10, 20): bench.reset holds [12, 16) of it
    assert b["idle_gaps"] == [["bench.run", pytest.approx(6e-9)],
                              ["bench.reset", pytest.approx(4e-9)]]


def test_self_times_take_nested_ops_out():
    ops = [["while:w", 0, 100], ["custom-call:k", 10, 60],
           ["fusion:f", 20, 10], ["copy:c", 80, 5]]
    assert dict(devtrace.self_times(ops)) == {
        "while:w": 35, "custom-call:k": 50, "fusion:f": 10, "copy:c": 5}


def test_op_name_from_hlo_text():
    hlo = ("%body.3 = u32[256,8192]{1,0:T(8,128)S(1)} custom-call(s32[1]"
           "{0:T(128)} %bitcast.3), custom_call_target=\"tpu_custom_call\"")
    assert devtrace.op_name(hlo) == "custom-call:body.3"
    tup = ("%while = (u32[256,8192]{1,0:T(8,128)S(1)}, s32[]{:T(128)}) "
           "while((u32[256,8192]{1,0:T(8,128)S(1)}, s32[]) %tuple.19)")
    assert devtrace.op_name(tup) == "while:while"
