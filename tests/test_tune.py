"""tune/ — the unified autotuner + durable plan store (PR 14).

The claims under test, in the ISSUE's words: one fingerprint digest
holds the chosen plan AND its exported executable side by side
(``<digest>.plan`` / ``<digest>.aot``); every timed candidate is
oracle-parity-gated before it may win and the heuristic's own choice is
always in the race (``vs_heuristic >= 1.0`` by construction); a second
process installs persisted plans with zero life_batch retrace ticks;
corrupt/stale records quarantine via ``utils.checkpoint.quarantine``
and fall back to heuristics; a parity-failing plan is rejected and
NEVER installed; ``MOMP_TUNE=0`` restores pure-heuristic routing
without touching the store. All on the 8-virtual-device CPU mesh.
"""

import glob
import os

import numpy as np
import pytest

from mpi_and_open_mp_tpu import stencils
from mpi_and_open_mp_tpu.obs import ledger, metrics
from mpi_and_open_mp_tpu.ops import pallas_life
from mpi_and_open_mp_tpu.serve import aotcache
from mpi_and_open_mp_tpu.tune import (
    PlanError,
    PlanStore,
    fingerprint_for,
    load_plan,
    save_plan,
    space,
    tune,
)


@pytest.fixture(autouse=True)
def _clean_plan_table():
    """Every test starts and ends with an empty in-process plan table —
    an installed plan leaking across tests would silently reroute every
    later ``native_path_batch`` call."""
    pallas_life.clear_planned_paths()
    yield
    pallas_life.clear_planned_paths()


def _stack(workload: str, shape, seed=46) -> np.ndarray:
    spec = stencils.get(workload)
    b, ny, nx = shape
    rng = np.random.default_rng(seed)
    return np.stack([spec.init(rng, (ny, nx)) for _ in range(b)]).astype(
        spec.np_dtype)


# -- candidate space -------------------------------------------------------


def test_life_candidates_heuristic_first_cpu():
    """The heuristic's own choice is candidate #0 (that ordering is what
    makes vs_heuristic >= 1.0 by construction), and the CPU space is
    exactly the legal set: bitsliced (no min-batch gate — that is the
    heuristic a plan may override) + the always-compilable xla fold."""
    shape = (4, 64, 64)
    cands = space.candidates("life", shape, on_tpu=False)
    paths = [c.path for c in cands]
    assert paths[0] == space.heuristic_path("life", shape, False) == "xla"
    assert sorted(paths) == ["bitsliced", "xla"]
    by = {c.path: c for c in cands}
    assert by["bitsliced"].pack_layout == "bitsliced"
    assert by["bitsliced"].bucket_rounding == space.BUCKET_PLANE32
    assert by["xla"].pack_layout == "cell-packed"
    assert by["xla"].bucket_rounding == space.BUCKET_POW2


def test_stencil_candidates_channels_gate():
    """Single-channel specs race roll vs the spec-generated Pallas
    padded kernel; the 2-channel gray_scott stack is 4-D, outside the
    Pallas batch contract, so roll is its whole space."""
    heat = [c.path for c in space.candidates("heat", (2, 16, 16))]
    assert heat == ["stencil:roll", "stencil:pallas"]
    gs = [c.path for c in space.candidates("gray_scott", (2, 2, 16, 16))]
    assert gs == ["stencil:roll"]
    assert all(space.pack_layout_for(p) == "-" for p in heat)


def test_runner_for_unknown_path_raises():
    with pytest.raises(ValueError, match="unknown"):
        space.runner_for("life", "warp-drive")


def test_run_padded_pallas_batch_parity():
    """The new spec-generic Pallas batch engine (satellite 1) reproduces
    the oracle for both an automaton and a float field."""
    import jax.numpy as jnp

    for workload in ("heat", "wireworld"):
        spec = stencils.get(workload)
        stack = _stack(workload, (3, 16, 16))
        assert stencils.pallas_batch_supported(spec, stack.shape)
        got = np.asarray(stencils.run_padded_pallas_batch(
            spec, jnp.asarray(stack), 5))
        for i in range(stack.shape[0]):
            assert stencils.parity_ok(
                spec, got[i], stencils.oracle_run(spec, stack[i], 5)), \
                workload


# -- the measured tuning pass ----------------------------------------------


def test_tune_vs_heuristic_floor_and_colocation(tmp_path):
    """One bounded pass: winner installed in-process, vs_heuristic >=
    1.0 (the heuristic is in the race, strict < to dethrone), and the
    persisted plan shares ONE digest with the exported executable."""
    store = PlanStore(tmp_path)
    res = tune("life", (8, 16, 16), steps=16, store=store)
    assert res["vs_heuristic"] >= 1.0
    assert res["measurements"][0]["path"] == res["heuristic_path"]
    assert pallas_life.planned_path("life", (8, 16, 16)) \
        == res["tuned"]["path"]
    digest = res["digest"]
    assert os.path.exists(str(tmp_path / (digest + ".plan")))
    assert os.path.exists(str(tmp_path / (digest + ".aot")))
    assert res["plan_file"].endswith(digest + ".plan")
    # The record round-trips and its key IS the aotcache fingerprint.
    rec = load_plan(res["plan_file"])
    assert aotcache.digest_for(rec["key"]) == digest
    assert rec["choice"]["path"] == res["tuned"]["path"]


def test_second_process_install_reuses_plan(tmp_path):
    """A fresh PlanStore (a restarted process's view) validates +
    parity-gates the persisted record and reroutes dispatch with ZERO
    life_batch retrace ticks — the parity gate runs the co-located
    exported executable, not a fresh trace."""
    res = tune("life", (8, 16, 16), steps=16, store=PlanStore(tmp_path))
    pallas_life.clear_planned_paths()
    metrics.reset()
    summary = PlanStore(tmp_path).install()
    assert summary["installed"] == 1 and summary["scanned"] == 1
    assert summary["corrupt"] == summary["stale"] == 0
    assert summary["parity_rejected"] == 0
    assert summary["plans"][0]["path"] == res["tuned"]["path"]
    assert pallas_life.planned_path("life", (8, 16, 16)) \
        == res["tuned"]["path"]
    retraces = {k: v for k, v in metrics.snapshot()["counters"].items()
                if k.startswith("jit.retrace{fn=life_batch")}
    assert retraces == {}


# -- durability: corrupt / stale / parity ----------------------------------


def test_corrupt_plan_quarantined_heuristics_unchanged(tmp_path):
    """A flipped bit anywhere in the frame is corrupt: the record is
    quarantined with a forensic stamp and NOTHING is installed — the
    heuristics serve unchanged."""
    tune("life", (8, 16, 16), steps=16, store=PlanStore(tmp_path))
    pallas_life.clear_planned_paths()
    (plan_file,) = glob.glob(str(tmp_path / "*.plan"))
    size = os.path.getsize(plan_file)
    with open(plan_file, "r+b") as fd:
        fd.seek(size // 2)
        byte = fd.read(1)
        fd.seek(size // 2)
        fd.write(bytes([byte[0] ^ 0xFF]))
    summary = PlanStore(tmp_path).install()
    assert summary["corrupt"] == 1 and summary["installed"] == 0
    assert glob.glob(plan_file + ".corrupt.*")
    assert not os.path.exists(plan_file)
    assert pallas_life.planned_path("life", (8, 16, 16)) is None


def test_stale_plan_quarantined_on_fingerprint_drift(tmp_path):
    """An intact envelope whose stored fingerprint no longer recomputes
    (here: version skew, i.e. the environment moved under the plan) is
    stale — quarantined, never installed."""
    tune("life", (8, 16, 16), steps=16, store=PlanStore(tmp_path))
    pallas_life.clear_planned_paths()
    (plan_file,) = glob.glob(str(tmp_path / "*.plan"))
    rec = load_plan(plan_file)
    save_plan(plan_file, dict(rec, key=dict(rec["key"], jax="0.0.0")))
    summary = PlanStore(tmp_path).install()
    assert summary["stale"] == 1 and summary["installed"] == 0
    assert glob.glob(plan_file + ".stale.*")
    assert pallas_life.planned_path("life", (8, 16, 16)) is None


def test_bad_schema_is_stale_missing_choice_is_corrupt(tmp_path):
    p = str(tmp_path / "x.plan")
    save_plan(p, {"schema": "momp-plan/0", "key": {}, "choice": {}})
    with pytest.raises(PlanError, match="schema") as ei:
        load_plan(p)
    assert ei.value.kind == "stale"
    save_plan(p, {"schema": "momp-plan/1", "key": {}})
    with pytest.raises(PlanError, match="key/choice") as ei:
        load_plan(p)
    assert ei.value.kind == "corrupt"


def test_parity_failing_plan_rejected_never_installed(tmp_path):
    """The last line of defense: a CRC-valid plan whose co-located
    executable computes the WRONG function (identity, not Life) fails
    the install-time oracle gate — the plan is quarantined as
    ``parity`` and never steers a dispatch, whatever it claims to win."""
    import jax
    import jax.numpy as jnp
    from jax import export as jax_export

    shape = (1, 12, 12)
    key = fingerprint_for("life", shape, np.uint8, "xla")
    store = PlanStore(tmp_path)
    plan_file = store.save({
        "schema": "momp-plan/1", "key": key,
        "choice": {"workload": "life", "shape": list(shape),
                   "dtype": "uint8", "path": "xla",
                   "pack_layout": "cell-packed",
                   "bucket_rounding": "pow2", "axis_order": "row"},
        "vs_heuristic": 99.0,
    })
    wrong = jax_export.export(jax.jit(lambda boards, steps: boards))(
        jax.ShapeDtypeStruct(shape, jnp.uint8),
        jax.ShapeDtypeStruct((), jnp.int32))
    aotcache.save_artifact(
        str(tmp_path / (aotcache.digest_for(key) + ".aot")),
        key, wrong.serialize())
    summary = PlanStore(tmp_path).install()
    assert summary["parity_rejected"] == 1 and summary["installed"] == 0
    assert glob.glob(plan_file + ".parity.*")
    assert pallas_life.planned_path("life", shape) is None


# -- dispatch integration --------------------------------------------------


def test_native_path_batch_consults_installed_plan(monkeypatch):
    """A plan may override the BITSLICE_MIN_BATCH heuristic (B=4 <
    min-batch still routes bitsliced when planned) but never a hard
    legality gate (``allow_bitsliced=False`` is the daemon's poisoned-
    layout rung: the plan yields), and ``MOMP_TUNE=0`` restores the
    heuristic without uninstalling anything."""
    shape = (4, 64, 64)
    assert pallas_life.native_path_batch(shape, on_tpu=False) == "xla"
    pallas_life.install_planned_path("life", shape, "bitsliced")
    assert pallas_life.native_path_batch(shape, on_tpu=False) \
        == "bitsliced"
    assert pallas_life.native_path_batch(
        shape, on_tpu=False, allow_bitsliced=False) == "xla"
    monkeypatch.setenv("MOMP_TUNE", "0")
    assert pallas_life.native_path_batch(shape, on_tpu=False) == "xla"
    assert pallas_life.planned_path("life", shape) is None
    monkeypatch.delenv("MOMP_TUNE")
    assert pallas_life.native_path_batch(shape, on_tpu=False) \
        == "bitsliced"
    pallas_life.clear_planned_paths()
    assert pallas_life.native_path_batch(shape, on_tpu=False) == "xla"


def test_kill_switch_short_circuits_install(tmp_path, monkeypatch):
    tune("life", (8, 16, 16), steps=16, store=PlanStore(tmp_path))
    pallas_life.clear_planned_paths()
    monkeypatch.setenv("MOMP_TUNE", "0")
    summary = PlanStore(tmp_path).install()
    assert summary == {"scanned": 0, "installed": 0, "corrupt": 0,
                       "stale": 0, "parity_rejected": 0,
                       "disabled": True, "plans": []}
    assert glob.glob(str(tmp_path / "*.plan"))  # store untouched


def test_daemon_stencil_rung_order_follows_plan():
    """The daemon's non-life ladder: roll-primary by default with the
    Pallas kernel as the suppressed fallback; an installed
    ``stencil:pallas`` plan swaps the rungs so serving dispatches
    exactly the tuner's winner. The 2-channel stack stays roll-only."""
    from mpi_and_open_mp_tpu.serve import ServePolicy, ServingDaemon

    d = ServingDaemon(ServePolicy(max_batch=8))
    heat = stencils.get("heat")
    stack = _stack("heat", (2, 16, 16))
    names = [n for n, _ in d._engines(stack, 4, spec=heat)]
    assert names == ["batch:stencil:heat", "batch:stencil-pallas:heat",
                     "oracle"]
    pallas_life.install_planned_path("heat", stack.shape,
                                     "stencil:pallas")
    names = [n for n, _ in d._engines(stack, 4, spec=heat)]
    assert names == ["batch:stencil-pallas:heat", "batch:stencil:heat",
                     "oracle"]
    gs = stencils.get("gray_scott")
    rng = np.random.default_rng(7)
    gstack = np.stack([gs.init(rng, (12, 12))
                       for _ in range(2)]).astype(gs.np_dtype)
    names = [n for n, _ in d._engines(gstack, 2, spec=gs)]
    assert names == ["batch:stencil:gray_scott", "oracle"]


# -- ledger + sentinel -----------------------------------------------------


def test_ledger_plan_key_field():
    """``plan`` joined KEY_FIELDS: tuned lines carry their plan_source,
    pre-autotuner lines default to "-" on both sides of a match."""
    stamped = ledger.stamp({"metric": "m", "plan_source": "store"})
    assert stamped["key"]["plan"] == "store"
    assert ledger.stamp({"metric": "m"})["key"]["plan"] == "-"
    old = {"key": {"metric": "m"}}  # pre-PR-14 entry: no plan field
    assert "plan=-" in ledger.config_key(old, ("metric", "plan"))


def test_sentinel_fails_plan_source_downgrade(tmp_path):
    """tuned (store) -> heuristic is a provenance downgrade exactly like
    tpu -> cpu: the sentinel fails it and surfaces the candidate's own
    fallback_reason; store <-> fresh is NOT a downgrade."""
    import json
    import sys

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "analysis"))
    import regression_sentinel

    def entry(ts, plan_source, extra=None):
        rec = {"metric": "m", "value": 100.0, "board": [64, 64],
               "dtype": "uint8", "steps": 100, "batch": 0,
               "plan_source": plan_source, **(extra or {})}
        return ledger.stamp(rec, platform="cpu", device_count=8, ts=ts,
                            sha="deadbee")

    entries = [entry(float(i), "store") for i in range(3)]
    entries.append(entry(3.0, "fresh"))
    verdict = regression_sentinel.evaluate(entries)
    assert verdict["verdict"] == "pass"  # fresh ranks equal to store

    entries.append(entry(
        4.0, "heuristic",
        {"fallback_reason": "autotune skipped: MOMP_TUNE=0"}))
    verdict = regression_sentinel.evaluate(entries)
    assert verdict["verdict"] == "fail"
    (down,) = [d for d in verdict["downgrades"]
               if d["field"] == "plan_source"]
    assert down["new"] == "heuristic" and down["baseline_best"] == "store"
    assert "MOMP_TUNE=0" in down["fallback_reason"]
    assert "plan_source" in verdict["checked"]
    json.dumps(verdict)  # the verdict stays a plain JSON document
