"""Wide-radius engine families (PR 20): the lenia registry spec, the
separable and FFT aggregation paths racing the offset table, the fuse
depth as a tuned axis, and the sentinel/ledger provenance plumbing.

Everything runs on the conftest 8-virtual-device CPU mesh; parity is
always against the NumPy oracle at the GATE-owned per-family tolerance
(``stencils.parity_tol_for``) — the same gates the tuner and the
plan-store install path use.
"""

import numpy as np
import pytest

from mpi_and_open_mp_tpu import stencils
from mpi_and_open_mp_tpu.ops import pallas_life
from mpi_and_open_mp_tpu.parallel import mesh as mesh_lib
from mpi_and_open_mp_tpu.stencils import engine as stencil_engine
from mpi_and_open_mp_tpu.stencils import spec as spec_mod
from mpi_and_open_mp_tpu.tune import space

LENIA = stencils.get("lenia")


def _board(shape=(32, 32), seed=46):
    return LENIA.init(np.random.default_rng(seed), shape)


# ------------------------------------------------- the lenia registry spec


def test_lenia_registered_wide_radius_float():
    assert LENIA.radius == 8 and LENIA.dtype == "float32"
    assert LENIA.boundary == "torus" and LENIA.channels == 1
    # The Gaussian ring minus its center pixel is exactly rank 2, and
    # the rank is cached on the spec so legality gates never
    # re-factorize per call.
    assert LENIA.separable_rank == 2
    assert stencil_engine.separable_supported(LENIA)
    assert stencil_engine.fft_supported(LENIA)
    # Narrow zero-center tables never factor at rank <= radius, so the
    # legacy specs enumerate exactly as before this PR.
    for name in ("life", "heat", "wireworld"):
        assert stencils.get(name).separable_rank is None
    w = np.asarray(LENIA.weights, np.float64)
    assert w[LENIA.radius, LENIA.radius] == 0.0
    np.testing.assert_allclose(w.sum(), 1.0, atol=1e-12)


def test_register_rejects_nonfinite_weights():
    import dataclasses

    w = np.asarray(spec_mod.make_lenia(4, "lenia_nan").weights,
                   np.float64)
    w[0, 0] = np.nan
    bad = dataclasses.replace(
        spec_mod.make_lenia(4, "lenia_nan"),
        weights=tuple(tuple(float(x) for x in row) for row in w))
    with pytest.raises(ValueError):
        spec_mod.register(bad)
    assert "lenia_nan" not in stencils.names()


# ------------------------------------------ single-device family parity


@pytest.mark.parametrize("family", stencil_engine.ENGINE_FAMILIES)
def test_family_parity_vs_oracle(family):
    board = _board()
    got = np.asarray(stencil_engine.run_family(LENIA, board, 8, family))
    ref = stencils.oracle_run(LENIA, board, 8)
    assert stencils.parity_ok(LENIA, got, ref,
                              **stencil_engine.parity_tol_for(family))


@pytest.mark.parametrize("family", ["sep", "fft"])
def test_family_batch_parity_vs_oracle(family):
    rng = np.random.default_rng(7)
    stack = np.stack([LENIA.init(rng, (24, 40)) for _ in range(3)])
    got = np.asarray(stencil_engine.run_family_batch(
        LENIA, stack, 6, family))
    tol = stencil_engine.parity_tol_for(family)
    for i in range(3):
        assert stencils.parity_ok(
            LENIA, got[i], stencils.oracle_run(LENIA, stack[i], 6), **tol)


def test_fft_tolerance_is_gate_owned():
    """The FFT path is approximate by construction: the parity GATE
    owns the float slack (``parity_tol_for("fft")``), the engine never
    loosens anything itself — the same output rejects under a
    bit-tight gate and passes under the family's declared one."""
    board = _board()
    got = np.asarray(stencil_engine.run_family(LENIA, board, 8, "fft"))
    ref = stencils.oracle_run(LENIA, board, 8)
    assert stencils.parity_ok(LENIA, got, ref,
                              **stencil_engine.parity_tol_for("fft"))
    # A bit-tight gate rejects: the transform really is approximate,
    # and nothing inside the engine hides that from the gate.
    assert not stencils.parity_ok(LENIA, got, ref, rtol=0.0, atol=1e-9)
    with pytest.raises(ValueError):
        stencil_engine.parity_tol_for("warp")  # unknown family


# --------------------------------------------------------------- refusals


def test_separable_refuses_nonfactorizable_weights():
    # heat's 3x3 zero-center table is rank 2 > radius 1: refused.
    heat = stencils.get("heat")
    assert not stencil_engine.separable_supported(heat)
    with pytest.raises(ValueError, match="factor"):
        stencil_engine.run_family(
            heat, heat.init(np.random.default_rng(3), (16, 16)), 2, "sep")
    # A full-rank random wide table refuses too — rank > radius.
    rng = np.random.default_rng(5)
    w = rng.random((5, 5))
    w[2, 2] = 0.0
    import dataclasses

    rand = dataclasses.replace(
        spec_mod.make_lenia(2, "lenia_rand"),
        weights=tuple(tuple(float(x) for x in row) for row in w))
    assert rand.separable_rank is None
    with pytest.raises(ValueError):
        stencil_engine.run_family(
            rand, rand.init(np.random.default_rng(3), (16, 16)), 2, "sep")


def test_fft_refuses_int_dtype_and_narrow_radius():
    life = stencils.get("life")
    assert not stencil_engine.fft_supported(life)  # uint8 rules
    with pytest.raises(ValueError):
        stencil_engine.run_family(
            life, life.init(np.random.default_rng(3), (16, 16)), 2, "fft")
    # The radius floor is an ENUMERATION gate (below it the transform
    # can't win), not a correctness refusal: a forced narrow-radius
    # float run still computes and still passes its parity gate.
    heat = stencils.get("heat")
    assert not stencil_engine.fft_supported(heat)  # radius 1 < minimum
    hboard = heat.init(np.random.default_rng(3), (16, 16))
    got = np.asarray(stencil_engine.run_family(heat, hboard, 4, "fft"))
    assert stencils.parity_ok(heat, got,
                              stencils.oracle_run(heat, hboard, 4),
                              **stencil_engine.parity_tol_for("fft"))
    narrow = spec_mod.make_lenia(stencil_engine.FFT_MIN_RADIUS - 1,
                                 "lenia_narrow")
    assert not stencil_engine.fft_supported(narrow)


def test_sharded_runner_refuses_eagerly():
    mesh = mesh_lib.make_mesh_2d(4, 2)
    heat = stencils.get("heat")
    with pytest.raises(ValueError):
        stencil_engine.make_sharded_runner(
            heat, mesh, "row", (48, 48), family="sep")
    with pytest.raises(ValueError):
        stencil_engine.make_sharded_runner(
            LENIA, mesh, "row", (96, 96), family="warp")


# ------------------------------------------------- sharded family parity


@pytest.mark.parametrize("family", stencil_engine.ENGINE_FAMILIES)
@pytest.mark.parametrize("layout", ["row", "col", "cart"])
def test_sharded_family_parity_every_layout(layout, family):
    """All three families through the PR 15 halo machinery on every
    layout: the halo plan is family-blind (radius-deep ghosts serve
    any aggregation order), parity is at the family's gate tolerance."""
    board = _board((96, 96))
    mesh = mesh_lib.make_mesh_2d(4, 2)
    got = np.asarray(stencil_engine.run_sharded(
        LENIA, board, 4, mesh=mesh, layout=layout, family=family))
    ref = stencils.oracle_run(LENIA, board, 4)
    assert stencils.parity_ok(LENIA, got, ref,
                              **stencil_engine.parity_tol_for(family))


# ------------------------------------- candidate space + the kill switch


def test_stencil_paths_list_families_and_respect_pin(monkeypatch):
    shape = (2, 32, 32)
    paths = space.stencil_paths(LENIA, shape)
    assert paths == ["stencil:roll", "stencil:pallas", "stencil:sep",
                     "stencil:fft"]
    # Narrow specs enumerate exactly as before the families landed.
    heat = stencils.get("heat")
    assert space.stencil_paths(heat, shape) == [
        "stencil:roll", "stencil:pallas"]
    monkeypatch.setenv(stencil_engine.ENV_FAMILY, "offset")
    assert space.stencil_paths(LENIA, shape) == [
        "stencil:roll", "stencil:pallas"]
    monkeypatch.setenv(stencil_engine.ENV_FAMILY, "sep")
    assert space.stencil_paths(LENIA, shape) == [
        "stencil:roll", "stencil:pallas", "stencil:sep"]
    monkeypatch.setenv(stencil_engine.ENV_FAMILY, "warp")
    with pytest.raises(ValueError):
        stencil_engine.family_pinned()


def test_planned_family_neutralized_by_pin(monkeypatch):
    """An installed ``stencil:fft`` plan under ``MOMP_ENGINE_FAMILY=
    offset`` stops steering at the NEXT dispatch — no uninstall, the
    pin is honored at read time."""
    shape = (2, 32, 32)
    pallas_life.clear_planned_paths()
    try:
        pallas_life.install_planned_path("lenia", shape, "stencil:fft")
        assert pallas_life.planned_path("lenia", shape) == "stencil:fft"
        monkeypatch.setenv(stencil_engine.ENV_FAMILY, "offset")
        assert pallas_life.planned_path("lenia", shape) is None
        monkeypatch.setenv(stencil_engine.ENV_FAMILY, "fft")
        assert pallas_life.planned_path("lenia", shape) == "stencil:fft"
    finally:
        pallas_life.clear_planned_paths()


def test_family_for_path_vocabulary():
    assert stencil_engine.family_for_path("stencil:sep") == "sep"
    assert stencil_engine.family_for_path("stencil:fft") == "fft"
    for p in ("stencil:roll", "stencil:pallas", "vmem", "seq:halo"):
        assert stencil_engine.family_for_path(p) == "offset"


# ----------------------------------------------- fuse depth as tuned axis


def test_sparse_fuse_depths_heuristic_first_and_legal(monkeypatch):
    monkeypatch.delenv("MOMP_TUNE_SPARSE_FUSE", raising=False)
    # radius 1, tile 64: heuristic 16 first, then the env defaults
    # minus duplicates; everything within the radius*fuse <= tile clamp.
    assert space.sparse_fuse_depths(1, 64) == (16, 4, 64)
    # radius 8, tile 64: the clamp bites — cap 8 shrinks the heuristic
    # rung itself (exactly what an untuned ctor runs) and gates 16/64.
    assert space.sparse_fuse_depths(8, 64) == (8, 4)
    # A tile the radius fills entirely leaves only depth 1.
    assert space.sparse_fuse_depths(8, 8) == (1,)
    monkeypatch.setenv("MOMP_TUNE_SPARSE_FUSE", "2,32")
    assert space.sparse_fuse_depths(1, 64) == (16, 2, 32)
    for f in space.sparse_fuse_depths(8, 64):
        assert 8 * f <= 64


def test_sharded_candidates_enumerate_fuse_axis():
    mesh = mesh_lib.make_mesh_1d()
    edge = 8 * space.SPARSE_SHARDED_TILE
    cands = space.sharded_candidates("life", (edge, edge), mesh)
    sparse = [c for c in cands if c.path == "sparse_sharded:row"]
    want = space.sparse_fuse_depths(1, space.SPARSE_SHARDED_TILE)
    assert tuple(c.fuse_steps for c in sparse) == want
    # Heuristic depth stays candidate #0 so vs_heuristic >= 1.0 holds.
    assert sparse[0].fuse_steps == min(space.SPARSE_FUSE_HEURISTIC,
                                       space.SPARSE_SHARDED_TILE)
    assert all(c.halo_overlap == "sparse" for c in sparse)


def test_plan_store_persists_sparse_fuse(tmp_path):
    """A sparse-sharded record's tuned fuse depth survives the
    save -> fresh-process install (parity re-gated at the persisted
    tile+fuse geometry) -> lookup_sharded roundtrip."""
    from mpi_and_open_mp_tpu.tune import plans as tune_plans

    shape, tile, fuse = (128, 128), 16, 4
    spec = stencils.get("life")
    key = tune_plans.fingerprint_for(
        "life", shape, spec.np_dtype, "sparse_sharded:row")
    leg = {"path": "sparse_sharded:row", "axis_order": "row",
           "halo_overlap": "sparse", "fuse_steps": fuse,
           "boundary_steps": fuse, "engine": f"sparse-sharded:row:t{tile}",
           "steady_s_per_step": 1e-4, "cups": 1.0, "is_differenced": True}
    record = {
        "schema": tune_plans.PLAN_SCHEMA,
        "key": key,
        "choice": {"workload": "life", "shape": list(shape),
                   "dtype": str(spec.np_dtype),
                   "path": "sparse_sharded:row", "pack_layout": "-",
                   "bucket_rounding": space.BUCKET_POW2,
                   "axis_order": "row", "halo_overlap": "sparse",
                   "fuse_steps": fuse, "boundary_steps": fuse,
                   "mesh_axes": [8, 1], "tile": tile},
        "heuristic": leg, "tuned": leg, "vs_heuristic": 1.0,
        "vs_sequential": 1.0, "steps_budget": 16,
        "measurements": [leg], "rejected": [],
    }
    store = tune_plans.PlanStore(str(tmp_path))
    store.save(record)
    fresh = tune_plans.PlanStore(str(tmp_path))
    summary = fresh.install()
    assert summary["installed"] == 1 and summary["parity_rejected"] == 0
    hit = fresh.lookup_sharded("life", shape)
    assert hit is not None
    assert hit["choice"]["fuse_steps"] == fuse
    assert hit["choice"]["tile"] == tile


def test_tune_lenia_families_race_vs_heuristic(tmp_path):
    """The acceptance invariant: with sep/fft in the race the tuner's
    winner still never loses to the heuristic's own choice (which is
    always among the timed candidates)."""
    from mpi_and_open_mp_tpu.tune import plans as tune_plans
    from mpi_and_open_mp_tpu.tune import runner as tune_runner

    try:
        res = tune_runner.tune("lenia", (2, 32, 32), steps=16,
                               store=tune_plans.PlanStore(str(tmp_path)))
    finally:
        pallas_life.clear_planned_paths()
    timed = {m["path"] for m in res["measurements"]}
    assert {"stencil:roll", "stencil:sep", "stencil:fft"} <= timed
    assert res["vs_heuristic"] >= 1.0


# ----------------------------------------------------- serve daemon rungs


def test_daemon_rungs_list_families_and_follow_plan(monkeypatch):
    """The non-life recovery ladder grows sep/fft rungs for specs that
    support them, keeps the roll rung primary by default, promotes the
    planned family to the front, and drops pinned-out families — all
    with the oracle still last."""
    from mpi_and_open_mp_tpu.serve import ServePolicy, ServingDaemon

    d = ServingDaemon(ServePolicy(max_batch=8))
    rng = np.random.default_rng(7)
    stack = np.stack([LENIA.init(rng, (32, 32)) for _ in range(2)])
    pallas_life.clear_planned_paths()
    try:
        names = [n for n, _ in d._engines(stack, 4, spec=LENIA)]
        assert names == ["batch:stencil:lenia",
                         "batch:stencil-pallas:lenia",
                         "batch:stencil-sep:lenia",
                         "batch:stencil-fft:lenia", "oracle"]
        pallas_life.install_planned_path("lenia", stack.shape,
                                         "stencil:fft")
        names = [n for n, _ in d._engines(stack, 4, spec=LENIA)]
        assert names[0] == "batch:stencil-fft:lenia"
        assert names[-1] == "oracle"
        monkeypatch.setenv(stencil_engine.ENV_FAMILY, "offset")
        names = [n for n, _ in d._engines(stack, 4, spec=LENIA)]
        assert names == ["batch:stencil:lenia",
                         "batch:stencil-pallas:lenia", "oracle"]
    finally:
        pallas_life.clear_planned_paths()


# --------------------------------------- sentinel + ledger provenance


def test_sentinel_and_ledger_plumbing():
    from analysis import regression_sentinel as sentinel
    from mpi_and_open_mp_tpu.obs import ledger

    for f in ("radius_ab_offset_cups", "radius_ab_sep_cups",
              "radius_ab_fft_cups", "radius_ab_vs_offset_best"):
        assert f in sentinel.WATCH_FIELDS
        assert sentinel.direction_for(f) == "higher"
    assert "engine_family" in sentinel.PROVENANCE_FIELDS
    # fft -> offset on the same workload must read as a DOWNGRADE.
    assert (sentinel.engine_rank("fft") > sentinel.engine_rank("offset"))
    assert (sentinel.engine_rank("sep") > sentinel.engine_rank("offset"))
    assert (sentinel.engine_rank("fft") > sentinel.engine_rank("sep"))
    assert (sentinel.engine_rank("batch:stencil:fft")
            > sentinel.engine_rank("batch:stencil:sep"))
    # The halo schedule stamp must NOT collide with the sep matcher.
    assert sentinel.engine_rank("seq:halo") == 1
    assert "engine_family" in ledger.KEY_FIELDS
    entry = ledger.stamp({"metric": "m", "board": [64, 64],
                          "engine_family": "fft"},
                         platform="cpu", device_count=8)
    assert entry["key"]["engine_family"] == "fft"
    entry = ledger.stamp({"metric": "m", "board": [64, 64]},
                         platform="cpu", device_count=8)
    assert entry["key"]["engine_family"] == "-"
    # Pre-PR-20 entries match new "-" lines through the key defaults.
    old = {"key": {f: "x" for f in ledger.KEY_FIELDS
                   if f != "engine_family"}}
    assert "engine_family=-" in ledger.config_key(old, ("engine_family",))
