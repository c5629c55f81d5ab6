"""Cross-run perf ledger and regression sentinel.

The contract under test: every bench line lands in the append-only
ledger with enough provenance (git SHA, platform, device kind, topology,
configuration key) that ``analysis/regression_sentinel.py`` can judge a
new run against its own history — flagging steady-rate drops past the
noise floor and engine/backend downgrades (pallas→jnp, TPU→CPU) with a
non-zero exit, while passing identical runs and first-of-a-kind
configurations.
"""

import json
import os
import sys

import pytest

from mpi_and_open_mp_tpu.obs import ledger

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "analysis"))

import regression_sentinel  # noqa: E402


def _entry(value=100.0, *, ts, impl="pallas", platform="tpu",
           source="synthetic", extra=None, record=None):
    """One ledger entry around a minimal flagship-shaped bench record."""
    rec = {
        "metric": "life_steady_cups_p46gun_big",
        "value": value,
        "unit": "cell_updates_per_sec",
        "board": [500, 500],
        "steps": 10_000,
        "dtype": "uint8",
        "backend": platform,
        "impl": impl,
    }
    if extra:
        rec.update(extra)
    if record is not None:
        rec = record
    return ledger.stamp(rec, source=source, platform=platform,
                        device_kind="test-kind", device_count=1,
                        ts=ts, sha="feedcafe")


# ------------------------------------------------------------------ ledger


def test_stamp_schema_and_config_key():
    e = _entry(123.0, ts=10.0)
    assert e["schema"] == "momp-ledger/1"
    assert e["ts"] == 10.0 and e["git_sha"] == "feedcafe"
    assert e["platform"] == "tpu" and e["topology"] == "tpu:1"
    assert e["device_kind"] == "test-kind"
    assert e["key"] == {
        "metric": "life_steady_cups_p46gun_big", "topology": "tpu:1",
        "shape": "500x500", "dtype": "uint8", "steps": 10_000,
        "batch": 0, "batch_pack_layout": "-", "resident": "-",
        "workload": "life", "plan": "-", "halo": "-", "sparse": "-",
        "engine_family": "-", "engine": "pallas",
    }
    # Full key renders in canonical order; any subset stays stable.
    full = ledger.config_key(e)
    assert full.startswith("metric=life_steady_cups_p46gun_big|")
    assert "topology=tpu:1" in full and "engine=pallas" in full
    assert ledger.config_key(e, ("shape", "dtype")) == "shape=500x500|dtype=uint8"


def test_stamp_falls_back_to_record_provenance():
    """Backfilled lines carry their own backend; omitted stamps must not
    invent provenance the artifact never recorded."""
    rec = {"metric": "m", "backend": "tpu", "impl": "roll"}
    e = ledger.stamp(rec, source="backfill:x", ts=1.0, sha="s")
    assert e["platform"] == "tpu"
    assert e["device_kind"] == "unrecorded"
    assert e["key"]["shape"] == "?"


def test_append_load_query_roundtrip(tmp_path):
    path = str(tmp_path / "sub" / "ledger.jsonl")  # parent dirs created
    a = _entry(1.0, ts=1.0)
    b = _entry(2.0, ts=2.0, impl="roll", platform="cpu")
    ledger.append(a, path)
    ledger.append(b, path)
    got = ledger.load(path)
    assert got == [a, b]
    assert ledger.query(got, engine="roll") == [b]
    assert ledger.query(got, topology="tpu:1", engine="pallas") == [a]
    assert ledger.query(got, metric="nope") == []


@pytest.mark.parametrize("line", ["not json {", '{"no_record": true}'])
def test_load_rejects_malformed_lines(tmp_path, line):
    path = tmp_path / "bad.jsonl"
    path.write_text(json.dumps(_entry(1.0, ts=1.0)) + "\n" + line + "\n")
    with pytest.raises(ValueError, match="bad.jsonl:2"):
        ledger.load(str(path))


# ---------------------------------------------------------------- sentinel


def _run_main(tmp_path, entries, *argv):
    path = str(tmp_path / "ledger.jsonl")
    for e in entries:
        ledger.append(e, path)
    return regression_sentinel.main([path, *argv])


def test_sentinel_passes_identical_runs(tmp_path, capsys):
    entries = [_entry(100.0, ts=float(i)) for i in range(4)]
    assert _run_main(tmp_path, entries) == 0
    verdict = json.loads(capsys.readouterr().out)
    assert verdict["verdict"] == "pass"
    assert verdict["baseline_n"] == 3
    assert verdict["regressions"] == [] and verdict["downgrades"] == []
    assert "value" in verdict["checked"]


def test_sentinel_flags_cups_drop(tmp_path, capsys):
    entries = [_entry(100.0, ts=float(i)) for i in range(5)]
    entries.append(_entry(80.0, ts=5.0))  # 20% drop vs noise floor 10%
    assert _run_main(tmp_path, entries, "--noise", "0.1") == 1
    verdict = json.loads(capsys.readouterr().out)
    assert verdict["verdict"] == "fail"
    (reg,) = verdict["regressions"]
    assert reg["field"] == "value" and reg["baseline_median"] == 100.0
    assert reg["drop"] == pytest.approx(0.2)


def test_sentinel_drop_within_noise_floor_passes(tmp_path):
    entries = [_entry(100.0, ts=float(i)) for i in range(3)]
    entries.append(_entry(95.0, ts=3.0))  # 5% < the 10% default floor
    assert _run_main(tmp_path, entries) == 0


def test_sentinel_flags_engine_and_platform_downgrade(tmp_path, capsys):
    """The BENCH_r04/r05 shape: same workload key, value intact, but the
    run fell to CPU and the dense fold — both downgrades must fail the
    verdict and the fallback WHY must survive into it."""
    entries = [_entry(100.0, ts=float(i)) for i in range(3)]
    entries.append(_entry(
        100.0, ts=3.0, impl="roll", platform="cpu",
        extra={"fallback_reason": "discovery hung; probe abandoned"}))
    assert _run_main(tmp_path, entries) == 1
    verdict = json.loads(capsys.readouterr().out)
    assert verdict["verdict"] == "fail" and verdict["regressions"] == []
    fields = {d["field"]: d for d in verdict["downgrades"]}
    assert fields["platform"]["new"] == "cpu"
    assert fields["platform"]["baseline_best"] == "tpu"
    assert fields["platform"]["fallback_reason"].startswith("discovery hung")
    assert fields["impl"]["new"] == "roll"
    assert fields["impl"]["baseline_best"] == "pallas"


def test_sentinel_no_baseline_and_key_isolation(tmp_path, capsys):
    """A first-of-a-kind configuration has nothing to regress against —
    and entries of a DIFFERENT workload key must not become its baseline."""
    other = _entry(1.0, ts=0.0,
                   extra={"board": [64, 64], "steps": 100})
    fresh = _entry(100.0, ts=1.0)
    assert _run_main(tmp_path, [other, fresh]) == 0
    verdict = json.loads(capsys.readouterr().out)
    assert verdict["verdict"] == "no-baseline"
    assert verdict["baseline_n"] == 0


def test_sentinel_skips_error_records(tmp_path, capsys):
    """A crashed run's error line is not a candidate (nothing to judge)
    and not a baseline (its rates never existed)."""
    entries = [_entry(100.0, ts=0.0), _entry(100.0, ts=1.0)]
    entries.append(_entry(0.0, ts=2.0,
                          record={"error": "boom", "phase": "measure",
                                  "metric": "life_steady_cups_p46gun_big",
                                  "board": [500, 500], "steps": 10_000,
                                  "dtype": "uint8", "impl": "pallas"}))
    assert _run_main(tmp_path, entries) == 0
    verdict = json.loads(capsys.readouterr().out)
    assert verdict["verdict"] == "pass"
    assert verdict["candidate_ts"] == 1.0  # the newest NON-error entry


def test_sentinel_unreadable_ledger_exits_2(tmp_path, capsys):
    path = tmp_path / "broken.jsonl"
    path.write_text("junk\n")
    assert regression_sentinel.main([str(path)]) == 2
    assert regression_sentinel.main([str(tmp_path / "missing.jsonl")]) == 2


def test_engine_rank_tiers():
    rank = regression_sentinel.engine_rank
    assert rank("pallas:vmem") == 3
    assert rank("batch:pallas:b1024") == 3
    assert rank("bitfused") == 2 and rank("frame") == 2
    assert rank("local:jnp") == 1 and rank("roll") == 1
    assert rank("jnp") == 1 and rank("batch:xla") == 1
    assert rank(None) == 0 and rank("") == 0


def test_direction_for_name_keying():
    """Polarity comes from the metric NAME: rates are higher-is-better
    even when they end in ``_sec``; latencies and badness counters are
    lower-is-better."""
    d = regression_sentinel.direction_for
    assert d("value") == "higher"
    assert d("batched_cups") == "higher"
    assert d("serve_requests_per_sec") == "higher"  # NOT the _sec rule
    assert d("batched_requests_per_sec") == "higher"
    assert d("attention_32k_grad_tflops") == "higher"
    assert d("attention_32k_causal_sec") == "lower"
    assert d("serve_p50_latency_s") == "lower"
    assert d("serve_p99_latency_s") == "lower"
    assert d("serve_shed") == "lower"
    assert d("serve_degraded") == "lower"
    # The WAL durability-tax fields: swelling journal volume or sync
    # stall is the regression.
    assert d("serve_wal_bytes") == "lower"
    assert d("serve_wal_fsync_s") == "lower"


def test_sentinel_flags_p99_inflation(tmp_path, capsys):
    """Higher-is-WORSE: a serve p99 that grows past the noise floor must
    fail even with every throughput field flat."""
    entries = [_entry(100.0, ts=float(i),
                      extra={"serve_p99_latency_s": 0.05}) for i in range(3)]
    entries.append(_entry(100.0, ts=3.0,
                          extra={"serve_p99_latency_s": 0.12}))
    assert _run_main(tmp_path, entries, "--noise", "0.1") == 1
    verdict = json.loads(capsys.readouterr().out)
    (reg,) = verdict["regressions"]
    assert reg["field"] == "serve_p99_latency_s"
    assert reg["direction"] == "lower" and reg["baseline_median"] == 0.05
    assert reg["drop"] == pytest.approx(1.4)  # (0.12-0.05)/0.05


def test_sentinel_p99_improvement_and_rate_drop(tmp_path, capsys):
    """Both directions, same ledger: a p99 that SHRINKS passes; a
    requests/sec rate that drops fails under the throughput polarity."""
    entries = [_entry(100.0, ts=float(i),
                      extra={"serve_p99_latency_s": 0.05,
                             "serve_requests_per_sec": 200.0})
               for i in range(3)]
    entries.append(_entry(100.0, ts=3.0,
                          extra={"serve_p99_latency_s": 0.01,
                                 "serve_requests_per_sec": 210.0}))
    assert _run_main(tmp_path, entries) == 0

    entries.append(_entry(100.0, ts=4.0,
                          extra={"serve_p99_latency_s": 0.05,
                                 "serve_requests_per_sec": 120.0}))
    assert _run_main(tmp_path, entries) == 1
    verdict = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    fields = {r["field"]: r for r in verdict["regressions"]}
    assert "serve_requests_per_sec" in fields
    assert fields["serve_requests_per_sec"]["direction"] == "higher"
    # The shrunken p99 must not register as a regression either way.
    assert "serve_p99_latency_s" not in fields


# ------------------------------------------------------- bench integration


def test_bench_cpu_line_lands_in_ledger(tmp_path, capsys, monkeypatch):
    """The bench line under the explicit CPU pin (the suite never touches
    a real chip) must carry the provenance stamps and the memory gauges,
    no ``roofline`` (a cost model of another step than the one timed),
    and land in the --ledger file as one well-keyed entry the sentinel
    can read."""
    sys.path.insert(0, REPO)
    import bench

    lpath = str(tmp_path / "ledger.jsonl")
    rc = bench.main(["--board", "64", "--steps", "64", "--ledger", lpath])
    assert rc == 0
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])

    assert rec["platform"] == "cpu" and rec["backend"] == "cpu"
    assert isinstance(rec["device_kind"], str) and rec["device_kind"]
    assert rec["board"] == [64, 64] and rec["steps"] == 64
    assert rec["dtype"] == "uint8"
    assert "fallback_reason" not in rec

    assert "roofline" not in rec and "roofline_error" not in rec
    gauges = rec["metrics"]["gauges"]
    assert gauges.get("memory.live_buffer_bytes", 0) >= 0
    assert "memory.live_buffer_watermark_bytes" in gauges

    (entry,) = ledger.load(lpath)
    assert entry["source"] == "bench.py"
    assert entry["platform"] == "cpu"
    assert entry["key"]["shape"] == "64x64" and entry["key"]["steps"] == 64
    assert entry["record"]["value"] == rec["value"]


def test_bench_ledger_append_failure_never_costs_the_line(
        tmp_path, capsys, monkeypatch):
    """Ledger IO is best-effort by contract: an unwritable path must cost
    a stderr note only — same line, same exit code."""
    sys.path.insert(0, REPO)
    import bench

    bad = str(tmp_path / "ledger_as_dir")
    os.makedirs(bad)  # open(path, "a") on a directory raises
    rc = bench.main(["--board", "64", "--steps", "64", "--ledger", bad])
    assert rc == 0
    out = capsys.readouterr()
    rec = json.loads(out.out.strip().splitlines()[-1])
    assert rec["value"] > 0
    assert "ledger append failed" in out.err
