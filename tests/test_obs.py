"""Observability layer: span tracer, metrics registry, traced ring dispatch.

The contract under test mirrors the chaos discipline: everything is OFF
by default (one env check, a shared no-op singleton, an untouched
registry), and when armed the telemetry must tell the truth — hop spans
match the ``2*(p-1)`` ring structure with the same engine stamp
``ring_hop_engine_for`` reports, recovery events match what the guards
actually did, and the traced dispatch stays parity-exact.
"""

import contextlib
import json
import math
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mpi_and_open_mp_tpu.obs import metrics, profile, report, trace
from mpi_and_open_mp_tpu.parallel import mesh as mesh_lib
from mpi_and_open_mp_tpu.parallel.context import (
    attention_reference,
    ring_attention,
    ring_hop_engine_for,
)
from mpi_and_open_mp_tpu.utils.timing import Timer


@pytest.fixture(autouse=True)
def _fresh_registry():
    metrics.reset()
    yield
    metrics.reset()


@pytest.fixture
def sink(tmp_path, monkeypatch):
    """Arm a fresh trace sink; tear it down so later tests see it off."""
    path = tmp_path / "trace.jsonl"
    monkeypatch.setenv("MOMP_TRACE", str(path))
    trace.reset()
    yield path
    trace.reset()


@pytest.fixture
def sp_mesh():
    return mesh_lib.make_mesh_1d(8, axis="sp")


def _records(path):
    return [json.loads(line)
            for line in path.read_text().splitlines() if line.strip()]


def _qkv(rng, h, n, d):
    return tuple(jnp.asarray(rng.standard_normal((h, n, d)), jnp.float32)
                 for _ in range(3))


# --------------------------------------------------------------- tracer core


def test_span_nesting_jsonl_roundtrip(sink):
    with trace.span("outer", phase="x") as outer:
        with trace.span("inner", hop=1) as inner:
            assert inner.parent == outer.id
            trace.event("ping", note="hi")
        assert outer.elapsed >= 0.0
    recs = _records(sink)
    assert [r["name"] for r in recs] == ["ping", "inner", "outer"]
    ev, inner_r, outer_r = recs
    for r in recs:  # schema every consumer relies on
        assert {"kind", "name", "ts", "id", "parent", "pid", "host"} <= r.keys()
    assert ev["kind"] == "event"
    assert ev["parent"] == inner_r["id"]  # parented to the innermost span
    assert inner_r["parent"] == outer_r["id"]
    assert outer_r["parent"] is None
    assert inner_r["attrs"] == {"hop": 1}
    assert outer_r["attrs"] == {"phase": "x"}
    assert 0.0 <= inner_r["dur"] <= outer_r["dur"]


def test_span_records_error_and_still_closes(sink):
    with pytest.raises(ValueError):
        with trace.span("doomed"):
            raise ValueError("boom")
    (rec,) = _records(sink)
    assert rec["name"] == "doomed"
    assert rec["error"] == "ValueError"


def test_span_set_updates_attrs_mid_span(sink):
    with trace.span("s", engine="?") as sp:
        sp.set(engine="jnp")
    (rec,) = _records(sink)
    assert rec["attrs"]["engine"] == "jnp"


def test_sink_appends_across_invocations(sink):
    """Two arm/reset cycles share one file — the CI trace cycle runs two
    bench invocations against the same ``MOMP_TRACE`` path."""
    with trace.span("first"):
        pass
    trace.reset()  # simulate process end; env unchanged
    with trace.span("second"):
        pass
    assert [r["name"] for r in _records(sink)] == ["first", "second"]


def test_tracing_off_is_a_shared_noop(monkeypatch, tmp_path):
    monkeypatch.delenv("MOMP_TRACE", raising=False)
    trace.reset()
    assert not trace.enabled()
    assert not trace.hop_spans_active()
    sp = trace.span("anything", attr=1)
    assert sp is trace.NULL  # one shared instance, no allocation
    assert sp is trace.span("other")
    with sp as s:
        assert math.isnan(s.elapsed)
        s.set(x=1).anchor(None)
    trace.event("nothing")  # must not create a sink either
    assert list(tmp_path.iterdir()) == []


class _Annotations:
    """A recorder in place of ``jax.profiler.TraceAnnotation``."""

    def __init__(self):
        self.log = []

    def __call__(self, name, **kwargs):
        log = self.log

        class _One:
            def __enter__(self):
                log.append(("enter", name))
                return self

            def __exit__(self, *exc):
                log.append(("exit", name))
                return False

        return _One()


@pytest.mark.parametrize("raises", [False, True])
def test_live_span_is_a_profiler_annotation_of_its_name(sink, monkeypatch,
                                                        raises):
    rec = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", rec)
    with pytest.raises(ValueError) if raises else contextlib.nullcontext():
        with trace.span("outer", attr=1):
            with trace.span("inner"):
                if raises:
                    raise ValueError("boom")
    assert rec.log == [("enter", "outer"), ("enter", "inner"),
                       ("exit", "inner"), ("exit", "outer")]
    assert [r["name"] for r in _records(sink)] == ["inner", "outer"]


def test_off_span_opens_no_annotation(monkeypatch):
    monkeypatch.delenv("MOMP_TRACE", raising=False)
    trace.reset()
    rec = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", rec)
    with trace.span("anything") as sp:
        sp.anchor(jnp.ones(4))
    assert rec.log == []


def test_anchor_blocks_and_fetches_nothing(sink, monkeypatch, sp_mesh):
    """The anchor waits for the device work and nothing else: no
    one-element probe comes back to the host, sharded or not."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    sharded = jax.device_put(jnp.arange(64.0),
                             NamedSharding(sp_mesh, P("sp")))
    blocked = []
    block = jax.block_until_ready

    def recorded(tree):
        blocked.append(tree)
        return block(tree)

    def no_fetch(*_a, **_k):
        raise AssertionError("the anchor fetched to the host")

    monkeypatch.setattr(jax, "block_until_ready", recorded)
    monkeypatch.setattr(jax, "device_get", no_fetch)
    tree = (sharded * 2, jnp.ones(3))
    with trace.span("anchored") as sp:
        sp.anchor(tree)
    assert blocked == [tree]
    (rec,) = _records(sink)
    assert rec["name"] == "anchored" and "error" not in rec


def test_hop_spans_opt_out_env(sink, monkeypatch):
    assert trace.hop_spans_active()
    monkeypatch.setenv("MOMP_TRACE_HOPS", "0")
    assert trace.enabled() and not trace.hop_spans_active()


# ------------------------------------------------------------------ metrics


def test_metrics_counters_gauges_histograms():
    metrics.inc("hits")
    metrics.inc("hits", 2)
    metrics.inc("hits", engine="jnp")
    metrics.gauge("depth", 3, axis="y")
    metrics.gauge("depth", 5, axis="y")  # last wins
    metrics.observe("lat", 1.0)
    metrics.observe("lat", 3.0)
    metrics.observe("lat", float("nan"))  # dropped, never poisons min/max
    assert metrics.get("hits") == 3
    assert metrics.get("hits", engine="jnp") == 1
    assert metrics.get("never") == 0
    snap = metrics.snapshot()
    assert snap["counters"]["hits"] == 3
    assert snap["counters"]["hits{engine=jnp}"] == 1
    assert snap["gauges"]["depth{axis=y}"] == 5
    assert snap["histograms"]["lat"] == {
        "count": 2, "total": 4.0, "min": 1.0, "max": 3.0}
    json.dumps(snap)  # the bench-line sub-object must serialise
    metrics.reset()
    assert metrics.snapshot() == {
        "counters": {}, "gauges": {}, "histograms": {}}


def test_metrics_env_kill_switch(monkeypatch):
    monkeypatch.setenv("MOMP_METRICS", "0")
    metrics.inc("hits")
    metrics.gauge("g", 1)
    metrics.observe("h", 1.0)
    assert metrics.snapshot() == {
        "counters": {}, "gauges": {}, "histograms": {}}


def test_metrics_mixed_label_value_types_snapshot():
    metrics.inc("m", hop=1)
    metrics.inc("m", hop="one")
    snap = metrics.snapshot()["counters"]
    assert snap == {"m{hop=1}": 1, "m{hop=one}": 1}


# ----------------------------------------------------------- the span clock


def test_timer_live_elapsed_inside_with():
    with Timer() as t:
        first = t.elapsed
        assert first >= 0.0  # live, not NaN, before __exit__
        time.sleep(0.01)
        assert t.elapsed > first
    frozen = t.elapsed
    time.sleep(0.005)
    assert t.elapsed == frozen  # stops at exit


# ------------------------------------------------- traced ring hop dispatch


def test_traced_ring_parity_and_hop_span_contract(rng, sp_mesh, sink):
    h, n, d = 2, 128, 16
    q, k, v = _qkv(rng, h, n, d)
    p = sp_mesh.shape["sp"]
    got = ring_attention(q, k, v, mesh=sp_mesh, causal=True)
    want = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    recs = _records(sink)
    transfers = [r for r in recs if r["name"] == "ring.hop.transfer"]
    folds = [r for r in recs if r["name"] == "ring.hop.fold"]
    roots = [r for r in recs if r["name"] == "ring_attention"]
    # The acceptance contract: 2*(p-1) hop spans per attention step.
    assert len(transfers) == p - 1
    assert len(folds) == p - 1
    assert [r["attrs"]["hop"] for r in transfers] == list(range(1, p))
    assert all(r["attrs"]["bytes"] > 0 for r in transfers)
    (root,) = roots
    assert root["attrs"]["traced_dispatch"] is True
    assert root["attrs"]["devices"] == p
    # Engine honesty: hop spans carry the stamp ring_hop_engine_for
    # reports for the same global operands.
    engine = ring_hop_engine_for(q, k, v, p=p, causal=True)
    assert root["attrs"]["engine"] == engine
    assert all(r["attrs"]["engine"] == engine for r in folds)
    assert all(r["parent"] == root["id"] for r in transfers + folds)
    assert metrics.get("ring.hops.fwd", engine=engine) == p - 1
    assert metrics.get("ring.steps.traced") == 1


def test_traced_ring_noncausal_parity(rng, sp_mesh, sink):
    q, k, v = _qkv(rng, 3, 256, 8)
    got = ring_attention(q, k, v, mesh=sp_mesh, causal=False)
    want = attention_reference(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    recs = _records(sink)
    assert len([r for r in recs if r["name"].startswith("ring.hop.")]) == 14


@pytest.fixture
def pallas_interpret(monkeypatch):
    """Interpret-mode Pallas hop engine (same discipline as
    test_context's fixture: the flag is trace-time, not a jit cache key,
    so caches clear on both sides)."""
    from mpi_and_open_mp_tpu.parallel import context

    jax.clear_caches()
    monkeypatch.setattr(context, "_PALLAS_INTERPRET", True)
    yield context
    jax.clear_caches()


def test_traced_ring_engine_tag_matches_pallas_plan(rng, sp_mesh, sink,
                                                    pallas_interpret):
    h, n, d = 2, 8 * 128, 128  # per-shard 128 = interpret-eligible block
    q, k, v = _qkv(rng, h, n, d)
    p = sp_mesh.shape["sp"]
    engine = ring_hop_engine_for(q, k, v, p=p, causal=True)
    assert engine.startswith("pallas:") and engine.endswith(":pf")
    # The traced decomposition dispatches each hop from the host —
    # rotation, then fold, strictly serial — so there is no prefetch to
    # claim: its spans carry the fused stamp minus the :pf suffix.
    engine = engine[:-len(":pf")]
    got = ring_attention(q, k, v, mesh=sp_mesh, causal=True)
    want = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    recs = _records(sink)
    folds = [r for r in recs if r["name"] == "ring.hop.fold"]
    assert len(folds) == p - 1
    assert all(r["attrs"]["engine"] == engine for r in folds)
    assert metrics.get("ring.hops.fwd", engine=engine) == p - 1


def test_hop_opt_out_gets_whole_call_span(rng, sp_mesh, sink, monkeypatch):
    monkeypatch.setenv("MOMP_TRACE_HOPS", "0")
    q, k, v = _qkv(rng, 2, 128, 16)
    ring_attention(q, k, v, mesh=sp_mesh, causal=True)
    recs = _records(sink)
    assert [r["name"] for r in recs] == ["ring_attention"]
    assert "traced_dispatch" not in recs[0].get("attrs", {})
    assert metrics.get("ring.steps.traced") == 0


def test_chaos_recovery_lands_in_trace_and_registry(rng, sp_mesh, sink,
                                                    monkeypatch):
    """An injected NaN hop under guards must surface everywhere the ISSUE
    promises: a ``recovery`` trace event (parented to the guarded span),
    the ``recovery{stamp=...}`` counter, and the capped recovery log."""
    from mpi_and_open_mp_tpu.robust import chaos, guards

    q, k, v = _qkv(rng, 2, 128, 16)
    monkeypatch.setenv("MOMP_CHAOS", "nan_hop=1;seed=3")
    chaos.reset()
    guards.reset_recovery_log()
    try:
        out = ring_attention(q, k, v, mesh=sp_mesh, causal=True)
    finally:
        monkeypatch.delenv("MOMP_CHAOS")
        chaos.reset()
        jax.clear_caches()
    assert np.isfinite(np.asarray(out)).all()
    stamp = "ring_attention:jnp:recovered"
    assert guards.recovery_log() == [stamp]
    assert metrics.get("recovery", stamp=stamp) == 1
    recs = _records(sink)
    events = [r for r in recs if r["kind"] == "event"
              and r["name"] == "recovery"]
    assert [e["attrs"]["stamp"] for e in events] == [stamp]
    (span_rec,) = [r for r in recs if r["name"] == "ring_attention"]
    assert span_rec["attrs"]["guarded"] is True
    assert span_rec["attrs"]["engine"] == "jnp:recovered"
    assert events[0]["parent"] == span_rec["id"]
    guards.reset_recovery_log()


# ------------------------------------------------------------ recovery log


def test_recovery_log_ring_buffer_cap():
    from mpi_and_open_mp_tpu.robust import guards

    guards.reset_recovery_log()
    for i in range(300):
        guards.record_recovery(f"s{i}")
    log = guards.recovery_log()
    assert len(log) == guards.RECOVERY_LOG_CAP == 256
    assert log[0] == "s44" and log[-1] == "s299"  # oldest dropped first
    # The registry's own cardinality guard caps distinct stamps at
    # max_labelsets(); the overflow is COUNTED, never silent — 300
    # recoveries are still 300 recoveries on the books.
    kept = sum(metrics.get("recovery", stamp=f"s{i}") for i in range(300))
    assert kept == metrics.max_labelsets() == 256
    assert kept + metrics.get(metrics.DROPPED_LABELS) == 300
    guards.clear_recovery_log()  # the pre-obs alias keeps working
    assert guards.recovery_log() == []


# -------------------------------------------------------- checkpoint spans


def test_checkpoint_save_restore_spans_and_metrics(tmp_path, sink):
    from mpi_and_open_mp_tpu.utils import checkpoint

    board = jnp.asarray(
        np.random.default_rng(1).integers(0, 2, (16, 16), np.uint8))
    path = tmp_path / "ckpt"
    checkpoint.save(path, board, step=7)
    got, step = checkpoint.restore(path)
    assert step == 7 and np.array_equal(got, np.asarray(board))
    names = [r["name"] for r in _records(sink)]
    assert "checkpoint.save" in names and "checkpoint.restore" in names
    snap = metrics.snapshot()
    assert snap["counters"]["checkpoint.saves"] == 1
    assert snap["counters"]["checkpoint.restores"] == 1
    assert snap["counters"]["checkpoint.save.bytes"] == 256
    assert snap["counters"]["checkpoint.restore.bytes"] == 256
    assert snap["histograms"]["checkpoint.save_seconds"]["count"] == 1
    assert snap["histograms"]["checkpoint.restore_seconds"]["count"] == 1


# ------------------------------------------------------------- trace report


def _span(name, id, parent=None, dur=1.0, **attrs):
    rec = {"kind": "span", "name": name, "ts": 0.0, "dur": dur,
           "id": id, "parent": parent, "pid": 1, "host": "h"}
    if attrs:
        rec["attrs"] = attrs
    return rec


def test_report_phases_attention_and_fit():
    recs = [
        _span("ring.hop.transfer", 2, parent=1, dur=10e-6, hop=1, bytes=100),
        _span("ring.hop.fold", 3, parent=1, dur=5e-6, hop=1, engine="jnp"),
        _span("ring.hop.transfer", 4, parent=1, dur=20e-6, hop=2,
              bytes=10_000),
        _span("ring.hop.fold", 5, parent=1, dur=5e-6, hop=2, engine="jnp"),
        _span("ring_attention", 1, dur=50e-6, traced_dispatch=True,
              engine="jnp", devices=3),
    ]
    rep = report.report_dict(recs)
    att = rep["attention"]
    assert att["traced_steps"] == 1
    assert att["hop_spans"] == 4 and att["hop_spans_per_step"] == 4.0
    assert att["engines"] == ["jnp"]
    fit = att["hop_fit"]  # t = alpha + beta*n over (100, 10us), (1e4, 20us)
    assert fit["identifiable"] is True
    assert fit["alpha_us"] == pytest.approx(9.899, rel=1e-3)
    # Share accounting: only the root span counts toward the wall.
    assert rep["phases"]["wall_s"] == pytest.approx(50e-6)
    assert rep["phases"]["by_name"]["ring_attention"]["share"] == 1.0


def test_report_recoveries_and_retraces():
    recs = [
        {"kind": "event", "name": "recovery", "ts": 0, "id": 1,
         "parent": None, "pid": 1, "host": "h",
         "attrs": {"stamp": "ring_attention:jnp:recovered"}},
        {"kind": "event", "name": "metrics", "ts": 0, "id": 2,
         "parent": None, "pid": 1, "host": "h",
         "attrs": {"snapshot": {"counters": {
             "jit.retrace{fn=sharded_attention}": 2,
             "recovery{stamp=ring_attention:jnp:recovered}": 1}}}},
    ]
    rep = report.report_dict(recs)
    assert rep["recoveries"] == {
        "total": 1,
        "by_stamp": {"ring_attention:jnp:recovered": 1}}
    assert rep["retraces"] == {"sharded_attention": 2}
    assert "hop_fit" in rep["attention"]
    assert rep["attention"]["hop_fit"] is None  # no transfer spans
    report.render(rep)  # text mode must not crash on a ring-free trace


def test_report_load_rejects_malformed_lines(tmp_path):
    p = tmp_path / "t.jsonl"
    p.write_text('{"kind": "span", "name": "a"}\nnot json\n')
    with pytest.raises(ValueError, match="t.jsonl:2"):
        report.load(str(p))
    p.write_text('{"kind": "event", "name": "a"}\n\n')
    assert len(report.load(str(p))) == 1


def test_report_end_to_end_on_a_real_trace(rng, sp_mesh, sink):
    """The CLI's own pipeline over a genuinely produced trace: hop span
    arithmetic and JSON serialisability, end to end."""
    q, k, v = _qkv(rng, 2, 128, 16)
    ring_attention(q, k, v, mesh=sp_mesh, causal=True)
    rep = report.report_dict(report.load(str(sink)))
    assert rep["attention"]["traced_steps"] == 1
    assert rep["attention"]["hop_spans"] == 14
    assert rep["attention"]["hop_spans_per_step"] == 14.0
    json.dumps(rep)
    assert "ring_attention" in report.render(rep)


# ------------------------------------------------------------ chrome export


def test_chrome_export_schema_and_track_nesting():
    """Spans → "X" events on per-root tracks: tid is the root ancestor's
    span id, args carry span_id/parent for nesting verification, events
    become "i" instants on their parent's track, and every (pid, host)
    pair gets a process_name metadata row."""
    recs = [
        _span("root_a", 1, dur=50e-6),
        _span("child", 2, parent=1, dur=20e-6, hop=1),
        _span("grandchild", 3, parent=2, dur=10e-6),
        _span("root_b", 9, dur=5e-6),
        {"kind": "event", "name": "recovery", "ts": 1e-6, "id": 4,
         "parent": 2, "pid": 1, "host": "h", "attrs": {"stamp": "s"}},
    ]
    doc = report.to_chrome(recs)
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    by_name = {e["name"]: e for e in evs if e.get("ph") in ("X", "i")}
    # The whole subtree shares root_a's track; root_b has its own.
    assert by_name["root_a"]["tid"] == 1
    assert by_name["child"]["tid"] == 1
    assert by_name["grandchild"]["tid"] == 1
    assert by_name["root_b"]["tid"] == 9
    # Source parentage rides in args, µs in ts/dur.
    assert by_name["grandchild"]["args"]["span_id"] == 3
    assert by_name["grandchild"]["args"]["parent"] == 2
    assert by_name["child"]["dur"] == pytest.approx(20.0)
    # The instant event lands on its parent span's track.
    ev = by_name["recovery"]
    assert ev["ph"] == "i" and ev["tid"] == 1
    assert ev["args"] == {"stamp": "s"}
    meta = [e for e in evs if e.get("ph") == "M"]
    assert [m["args"]["name"] for m in meta] == ["h (pid 1)"]
    # Non-metadata events are time-ordered for stream consumers.
    xs = [e for e in evs if e.get("ph") != "M"]
    assert [e["ts"] for e in xs] == sorted(e["ts"] for e in xs)
    json.dumps(doc)  # must serialise as-is


def test_chrome_export_orphan_parent_roots_its_subtree():
    """A truncated trace (killed process) may reference a parent that
    never flushed — the orphan becomes its own root, not a KeyError."""
    recs = [_span("orphan", 5, parent=404, dur=1e-6)]
    (ev,) = [e for e in report.to_chrome(recs)["traceEvents"]
             if e.get("ph") == "X"]
    assert ev["tid"] == 5


def test_chrome_export_error_span_marked():
    rec = _span("doomed", 1, dur=1e-6)
    rec["error"] = "ValueError"
    (ev,) = [e for e in report.to_chrome([rec])["traceEvents"]
             if e.get("ph") == "X"]
    assert ev["args"]["error"] == "ValueError"


def test_chrome_cli_round_trip_on_real_trace(rng, sp_mesh, sink, tmp_path,
                                             capsys):
    """trace_report --chrome over a genuinely traced ring step: valid
    JSON, all 14 hop events nested (by track + time enclosure) inside
    their ring_attention root — parentage reproduced, as the ISSUE's
    acceptance asks."""
    import os
    import sys

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "analysis"))
    import trace_report

    q, k, v = _qkv(rng, 2, 128, 16)
    ring_attention(q, k, v, mesh=sp_mesh, causal=True)
    out = tmp_path / "chrome.json"
    assert trace_report.main([str(sink), "--chrome", str(out)]) == 0
    assert "trace events" in capsys.readouterr().out
    doc = json.loads(out.read_text())
    xs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    (root,) = [e for e in xs if e["name"] == "ring_attention"]
    hops = [e for e in xs
            if e["name"] in ("ring.hop.transfer", "ring.hop.fold")]
    assert len(hops) == 14
    for e in hops:
        assert e["tid"] == root["args"]["span_id"]
        assert root["ts"] <= e["ts"]
        assert e["ts"] + e["dur"] <= root["ts"] + root["dur"] + 1e-6


# ------------------------------------------------------------------ profile


def test_peaks_env_override(monkeypatch):
    monkeypatch.setenv("MOMP_PEAK_FLOPS", "5e12")
    monkeypatch.setenv("MOMP_PEAK_BYTES_S", "1e11")
    flops, bw, label = profile.peaks_for("cpu")
    assert (flops, bw) == (5e12, 1e11)
    assert label == "cpu-nominal"


def test_peaks_unknown_device_kind_raises():
    with pytest.raises(ValueError, match="weird-part"):
        profile.peaks_for("weird-part")
    with pytest.raises(ValueError):
        profile.peaks_for(None)


def test_record_memory_gauges_live_and_watermark():
    buf = jnp.zeros((256, 256), jnp.float32)  # 256KiB held live
    live = profile.record_memory_gauges()
    assert live >= buf.nbytes
    snap = metrics.snapshot()["gauges"]
    assert snap["memory.live_buffer_bytes"] == live
    assert snap["memory.live_buffer_watermark_bytes"] >= live
    del buf
