"""Persistent halo plans: derivation, overlap/sequential bit-identity, fuzz.

The PR-15 contract (``parallel/haloplan.py``): a frozen plan per (mesh
topology, shard shape, depth, pack layout) splits each fused round into
an interior partition computed while the ghost ``ppermute`` flies and two
boundary strips computed after it lands — and the reassembled shard must
equal the sequential whole-shard round bit-for-bit, for every registry
spec (radius 1), a custom radius-2 spec, multi-channel boards, fuse depth
K in {1, 4}, and the packed bit-sliced twin. Degenerate geometry (1-shard
meshes, shards with no interior) must degrade to the sequential schedule,
not wrap garbage. Runs on the 8-virtual-CPU-device mesh from conftest.
"""

import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import oracle_n
from mpi_and_open_mp_tpu import stencils
from mpi_and_open_mp_tpu.models.life import LifeSim
from mpi_and_open_mp_tpu.parallel import haloplan, mesh as mesh_lib
from mpi_and_open_mp_tpu.robust import chaos
from mpi_and_open_mp_tpu.stencils import engine as stencil_engine
from mpi_and_open_mp_tpu.utils.config import config_from_board

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_chaos_and_plans():
    """Chaos plans are read at trace time and plan tables are global:
    leave both exactly as found (same discipline as test_tune.py)."""
    from mpi_and_open_mp_tpu.ops import pallas_life

    pallas_life.clear_planned_paths()
    yield
    pallas_life.clear_planned_paths()
    chaos.reset()


# ------------------------------------------------------------ plan derivation


def test_plan_stamps_depth_and_cache():
    p = haloplan.plan_halo("row", (4, 1), (64, 128), 1, 1)
    assert p.overlap and p.engine == "overlap:deferred"
    assert p.depth == 1 and p.why == ""
    # Persistent: the same geometry yields the SAME frozen plan object.
    assert haloplan.plan_halo("row", (4, 1), (64, 128), 1, 1) is p
    # Depth is radius * fuse_steps.
    assert haloplan.plan_halo("row", (4, 1), (64, 128), 1, 3).depth == 3
    assert haloplan.plan_halo("row", (4, 1), (64, 128), 2, 3).depth == 6
    # The packed twin carries its own stamp.
    packed = haloplan.plan_halo("row", (2, 1), (128, 128), 32, 1,
                                pack_layout="packed")
    assert packed.overlap and packed.engine == "overlap:packed"


def test_plan_degenerate_geometry_goes_sequential():
    # 1-shard axis: nothing to overlap.
    p = haloplan.plan_halo("row", (1, 1), (64, 128), 1, 1)
    assert not p.overlap and p.engine == "seq:halo" and "1-shard" in p.why
    # Shard too shallow for a non-empty interior (extent <= 2*depth).
    p = haloplan.plan_halo("row", (4, 1), (2, 128), 1, 1)
    assert not p.overlap and "empty interior" in p.why
    # The packed twin downgrades to its own sequential stamp.
    p = haloplan.plan_halo("row", (2, 1), (64, 128), 32, 1,
                           pack_layout="packed")
    assert not p.overlap and p.engine == "seq:packed"
    # col overlaps the x axis: a y-only mesh is 1-shard in x.
    p = haloplan.plan_halo("col", (4, 1), (64, 128), 1, 1)
    assert not p.overlap and "1-shard x" in p.why
    with pytest.raises(ValueError, match="layout"):
        haloplan.plan_halo("diag", (4, 1), (64, 128), 1, 1)


def test_plan_kill_switch_is_part_of_the_cache_key(monkeypatch):
    assert haloplan.plan_halo("row", (4, 1), (64, 128), 1, 1).overlap
    monkeypatch.setenv(haloplan.ENV_OVERLAP, "0")
    p = haloplan.plan_halo("row", (4, 1), (64, 128), 1, 1)
    assert not p.overlap and haloplan.ENV_OVERLAP in p.why
    monkeypatch.delenv(haloplan.ENV_OVERLAP)
    assert haloplan.plan_halo("row", (4, 1), (64, 128), 1, 1).overlap


# ---------------------------------------- overlap vs sequential bit-identity


@pytest.mark.parametrize("layout", ["row", "col", "cart"])
@pytest.mark.parametrize("workload", sorted(stencils.names()))
def test_overlap_bit_equals_sequential_every_spec(workload, layout):
    """The tentpole invariant: for every registry spec (incl. the
    2-channel gray_scott) and every layout, the overlapped schedule's
    board is bit-identical to the forced-sequential schedule AND passes
    the independent oracle gate."""
    spec = stencils.get(workload)
    # Wide-radius specs (lenia r=8) need every layout's min shard to
    # keep a non-empty interior past 2*radius, or the plan legally
    # gates overlap out to seq and the overlap assertion below is moot.
    s = max(48, 12 * spec.radius)
    board = spec.init(np.random.default_rng(46), (s, s))
    mesh = mesh_lib.make_mesh_2d(4, 2)
    got = np.asarray(stencil_engine.run_sharded(
        spec, board, 5, mesh=mesh, layout=layout))
    plan = stencil_engine.run_sharded.last_plan
    assert plan.overlap and plan.engine.startswith("overlap:")
    seq = np.asarray(stencil_engine.run_sharded(
        spec, board, 5, mesh=mesh, layout=layout, overlap=False))
    assert stencil_engine.run_sharded.last_plan.engine == "seq:halo"
    np.testing.assert_array_equal(got, seq)
    assert stencils.parity_ok(spec, got, stencils.oracle_run(spec, board, 5))


@pytest.mark.parametrize("layout", ["row", "cart"])
@pytest.mark.parametrize("workload", ["life", "heat"])
def test_overlap_fused_k4_with_remainder_round(workload, layout):
    """Depth-4 fusion, 10 steps: two full rounds plus a depth-2 remainder
    round (its OWN plan — may legally differ in schedule)."""
    spec = stencils.get(workload)
    board = spec.init(np.random.default_rng(47), (48, 48))
    mesh = mesh_lib.make_mesh_2d(4, 2)
    got = np.asarray(stencil_engine.run_sharded(
        spec, board, 10, mesh=mesh, layout=layout, fuse_steps=4))
    assert stencil_engine.run_sharded.last_plan.overlap
    seq = np.asarray(stencil_engine.run_sharded(
        spec, board, 10, mesh=mesh, layout=layout, fuse_steps=4,
        overlap=False))
    np.testing.assert_array_equal(got, seq)
    assert stencils.parity_ok(
        spec, got, stencils.oracle_run(spec, board, 10))


def _blur2_update(center, agg, xp):
    return (center * 0.5 + agg * 0.01).astype(center.dtype)


@pytest.mark.parametrize("fuse", [1, 2])
def test_overlap_custom_radius2_spec(fuse):
    """Radius-2 coverage (every registry spec is radius 1): an
    unregistered 5x5 float spec, depth up to 4 per round."""
    w = np.ones((5, 5), np.int64)
    w[2, 2] = 0
    spec = stencils.StencilSpec(
        name="blur2", radius=2, dtype="float32",
        weights=tuple(tuple(int(x) for x in row) for row in w),
        update=_blur2_update)
    board = np.random.default_rng(48).random((48, 48)).astype(np.float32)
    mesh = mesh_lib.make_mesh_1d(4, axis="y")
    got = np.asarray(stencil_engine.run_sharded(
        spec, board, 5, mesh=mesh, layout="row", fuse_steps=fuse))
    plan = stencil_engine.run_sharded.last_plan
    assert plan.overlap and plan.depth == 2 * fuse
    seq = np.asarray(stencil_engine.run_sharded(
        spec, board, 5, mesh=mesh, layout="row", fuse_steps=fuse,
        overlap=False))
    np.testing.assert_array_equal(got, seq)
    assert stencils.parity_ok(spec, got, stencils.oracle_run(spec, board, 5))


def test_one_shard_mesh_degrades_to_sequential():
    """The degenerate mesh: overlap must decline (not wrap garbage) and
    the run must still be oracle-exact."""
    spec = stencils.get("life")
    board = spec.init(np.random.default_rng(49), (16, 16))
    mesh = mesh_lib.make_mesh_1d(1, axis="y")
    got = np.asarray(stencil_engine.run_sharded(
        spec, board, 4, mesh=mesh, layout="row"))
    plan = stencil_engine.run_sharded.last_plan
    assert not plan.overlap and "1-shard" in plan.why
    np.testing.assert_array_equal(got, oracle_n(board, 4))


def test_engine_kill_switch_forces_sequential(monkeypatch):
    spec = stencils.get("life")
    board = spec.init(np.random.default_rng(50), (32, 32))
    monkeypatch.setenv(haloplan.ENV_OVERLAP, "0")
    got = np.asarray(stencil_engine.run_sharded(
        spec, board, 4, mesh=mesh_lib.make_mesh_1d(), layout="row"))
    plan = stencil_engine.run_sharded.last_plan
    assert plan.engine == "seq:halo" and haloplan.ENV_OVERLAP in plan.why
    np.testing.assert_array_equal(got, oracle_n(board, 4))


def test_direct_fused_step_schedules_bit_equal(make_board):
    """Unit-level: ``overlap_fused_step`` vs ``sequential_fused_step``
    under the same shard_map, same plan — the two schedules, nothing
    else, k=2."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    spec = stencils.get("life")
    board = make_board(64, 64)
    mesh = mesh_lib.make_mesh_1d()  # 8 shards of (8, 64); depth 2 fits
    plan = haloplan.plan_halo("row", (8, 1), (8, 64), spec.radius, 2)
    assert plan.overlap

    def step_fn(padded):
        return stencil_engine.step_padded(spec, padded, jnp)

    pspec = P("y", None)
    dev = jax.device_put(jnp.asarray(board, spec.dtype),
                         NamedSharding(mesh, pspec))

    def smapped(fn):
        return jax.jit(jax.shard_map(
            lambda b: fn(plan, step_fn, b), mesh=mesh,
            in_specs=pspec, out_specs=pspec, check_vma=False))

    got = np.asarray(smapped(haloplan.overlap_fused_step)(dev))
    seq = np.asarray(smapped(haloplan.sequential_fused_step)(dev))
    np.testing.assert_array_equal(got, seq)
    np.testing.assert_array_equal(got, oracle_n(board, 2))


# ------------------------------------------------- packed bit-sliced overlap


def test_bitfused_packed_overlap_crosses_round_boundary(make_board):
    """The bit-sliced twin on an exact frame: (640, 128) over a 2-way
    ring is window mode with nw_s=10 > 2h=8 word rows per shard, so the
    plan overlaps — 140 steps crosses the k_max=128 round boundary, so
    the second round's ghost words carry first-round state."""
    board = make_board(640, 128, density=0.35)
    cfg = config_from_board(board, steps=140, save_steps=1000)
    mesh = mesh_lib.make_mesh_1d(2, axis="y")
    sim = LifeSim(cfg, layout="row", impl="bitfused", mesh=mesh)
    assert sim.plan_note == "window+overlap:packed"
    sim.step(140)
    np.testing.assert_array_equal(sim.collect(), oracle_n(board, 140))


def test_bitfused_packed_kill_switch_stays_bit_exact(monkeypatch,
                                                     make_board):
    """MOMP_HALO_OVERLAP=0 on overlap-capable packed geometry: the note
    downgrades to the sequential stamp and the run stays oracle-exact
    (same bits as the overlap run, by transitivity)."""
    board = make_board(640, 128, density=0.35)
    cfg = config_from_board(board, steps=10, save_steps=1000)
    mesh = mesh_lib.make_mesh_1d(2, axis="y")
    monkeypatch.setenv(haloplan.ENV_OVERLAP, "0")
    sim = LifeSim(cfg, layout="row", impl="bitfused", mesh=mesh)
    assert sim.plan_note == "window+seq:packed"
    sim.step(10)
    np.testing.assert_array_equal(sim.collect(), oracle_n(board, 10))


def test_bitfused_packed_ineligible_geometry_keeps_bare_note(make_board):
    # Padded frame (pad_y > 0): the funnel-shift exchange stays
    # sequential and the note stays the historical bare mode string.
    board = make_board(100, 130)
    cfg = config_from_board(board, steps=5, save_steps=1000)
    sim = LifeSim(cfg, layout="row", impl="bitfused",
                  mesh=mesh_lib.make_mesh_2d(2, 4))
    assert "+" not in sim.plan_note
    sim.step(5)
    np.testing.assert_array_equal(sim.collect(), oracle_n(board, 5))
    # Exact frame but no interior (nw_s=2 <= 2h): also bare.
    board = make_board(128, 128)
    cfg = config_from_board(board, steps=5, save_steps=1000)
    sim = LifeSim(cfg, layout="row", impl="bitfused",
                  mesh=mesh_lib.make_mesh_1d(2, axis="y"))
    assert "+" not in sim.plan_note


# ------------------------------------- chaos on padded packed frames (PR 15)


def test_packed_halo_chaos_padded_frame_diverges(monkeypatch, make_board):
    """The blind spot this PR closes: a dropped ghost on a PADDED packed
    frame (pad_y > 0, the funnel-shift path) must corrupt the run —
    proof the injection hook reaches the pad>0 exchange."""
    board = make_board(100, 130)
    cfg = config_from_board(board, steps=6, save_steps=0)
    monkeypatch.setenv("MOMP_CHAOS", "halo=drop;noguard")
    chaos.reset()
    sim = LifeSim(cfg, layout="row", impl="bitfused",
                  mesh=mesh_lib.make_mesh_2d(2, 4))
    final = sim.run(save=False)
    assert not np.array_equal(final, oracle_n(board, 6))
    assert sim.recoveries == []


def test_packed_halo_chaos_padded_frame_recovers(monkeypatch, make_board):
    """Same padded-frame fault with guards armed: the consistency probe
    catches it and the suppressed re-trace recovers bit-identically."""
    board = make_board(100, 130)
    cfg = config_from_board(board, steps=12, save_steps=4)
    monkeypatch.setenv("MOMP_CHAOS", "halo=drop;seed=3")
    chaos.reset()
    sim = LifeSim(cfg, layout="row", impl="bitfused",
                  mesh=mesh_lib.make_mesh_2d(2, 4))
    final = sim.run(save=False)
    np.testing.assert_array_equal(final, oracle_n(board, 12))
    assert sim.recoveries and "recovered" in sim.recoveries[0]


def test_packed_halo_x_chaos_padded_frame_diverges(monkeypatch, make_board):
    """The x twin (``packed_halo_x`` pad > 0): column strips of an
    unaligned board, dropped left ghost."""
    board = make_board(64, 460)
    cfg = config_from_board(board, steps=6, save_steps=0)
    monkeypatch.setenv("MOMP_CHAOS", "halo=drop;noguard")
    chaos.reset()
    sim = LifeSim(cfg, layout="col", impl="bitfused",
                  mesh=mesh_lib.make_mesh_1d(4, axis="x"))
    final = sim.run(save=False)
    assert not np.array_equal(final, oracle_n(board, 6))
    assert sim.recoveries == []


# --------------------------------------------------- tune space integration


def test_axis_orders_legality():
    from mpi_and_open_mp_tpu.tune import space

    assert space.axis_orders(1) == ("row",)
    assert space.axis_orders(8, (8, 1)) == ("row", "col")
    assert space.axis_orders(8, (4, 2)) == ("row", "col", "cart")


def test_sharded_candidates_gate_overlap_per_geometry():
    from mpi_and_open_mp_tpu.tune import space

    mesh = mesh_lib.make_mesh_2d(4, 2)
    cands = space.sharded_candidates("life", (48, 48), mesh)
    by = {(c.axis_order, c.halo_overlap) for c in cands}
    # All three layouts legal, overlap + seq legs each.
    assert by == {(lo, s) for lo in ("row", "col", "cart")
                  for s in ("overlap", "seq")}
    # A shard too shallow for an interior loses only the overlap leg.
    cands = space.sharded_candidates("life", (8, 48), mesh)
    rows = {c.halo_overlap for c in cands if c.axis_order == "row"}
    assert rows == {"seq"}
    # 1-device mesh: nothing shards, no candidates at all.
    assert space.sharded_candidates(
        "life", (48, 48), mesh_lib.make_mesh_1d(1, axis="y")) == []


def test_tune_sharded_seq_baseline_and_store_roundtrip(tmp_path):
    from mpi_and_open_mp_tpu.tune import tune_sharded
    from mpi_and_open_mp_tpu.tune.plans import PlanStore

    store = PlanStore(tmp_path)
    res = tune_sharded("life", (64, 64), mesh=mesh_lib.make_mesh_2d(4, 2),
                       steps=16, store=store)
    # Baseline-first ordering: the historic sequential schedule opens
    # the race, so vs_sequential is measured against it.
    assert res["baseline"]["halo_overlap"] == "seq"
    assert res["vs_sequential"] > 0
    assert {m["halo_overlap"] for m in res["measurements"]} >= {"seq"}
    fresh = PlanStore(tmp_path)
    fresh.install()
    hit = fresh.lookup_sharded("life", (64, 64))
    assert hit is not None
    assert hit["choice"]["path"].startswith("sharded:")

    with pytest.raises(RuntimeError, match="no legal sharded candidate"):
        tune_sharded("life", (64, 64),
                     mesh=mesh_lib.make_mesh_1d(1, axis="y"), steps=16)


# ------------------------------------- ledger / sentinel / report provenance


def test_ledger_stamps_halo_key():
    from mpi_and_open_mp_tpu.obs import ledger

    e = ledger.stamp({"metric": "m", "sharded_halo": "overlap:deferred"},
                     sha="x")
    assert e["key"]["halo"] == "overlap:deferred"
    e = ledger.stamp({"metric": "m"}, sha="x")
    assert e["key"]["halo"] == "-"
    assert "halo" in ledger.KEY_FIELDS


def test_sentinel_ranks_overlap_above_sequential():
    sys.path.insert(0, os.path.join(REPO, "analysis"))
    import regression_sentinel

    rank = regression_sentinel.engine_rank
    assert rank("overlap:deferred") == rank("overlap:packed") == 4
    assert rank("seq:halo") == rank("seq:packed") == 1
    assert rank("overlap:rdma") > rank("seq:halo")
    assert "sharded_halo" in regression_sentinel.PROVENANCE_FIELDS
    assert "vs_sequential" in regression_sentinel.WATCH_FIELDS
    assert "sharded_overlap_cups" in regression_sentinel.WATCH_FIELDS


def test_trace_report_halo_section():
    from mpi_and_open_mp_tpu.obs import report

    records = [
        {"kind": "span", "id": 1, "name": "halo.overlap", "ts": 0.0,
         "dur": 0.5, "attrs": {"engine": "overlap:deferred"}},
        {"kind": "span", "id": 2, "name": "halo.seq", "ts": 0.6,
         "dur": 0.5, "attrs": {"engine": "seq:halo"}},
        {"kind": "event", "id": 3, "name": "halo.ab", "ts": 1.2,
         "attrs": {"transfer_s": 1e-4, "exposed_s": 2e-5,
                   "efficiency": 0.8, "vs_sequential": 1.4}},
    ]
    rep = report.report_dict(records)
    hal = rep["halo"]
    assert hal["overlap_spans"] == 1 and hal["seq_spans"] == 1
    assert "overlap:deferred" in hal["engines"]
    assert hal["ab"]["efficiency"] == 0.8
    text = report.render(rep)
    assert "halo A/B" in text and "efficiency=80.0%" in text


# ------------------------------------------------ apps/life --resume + plans


def _resume_status_line(err: str) -> dict:
    lines = [ln for ln in err.splitlines() if ln.startswith("{")]
    assert lines, f"no JSON status line on stderr: {err!r}"
    return json.loads(lines[-1])


def test_resume_status_line_carries_plan_source(tmp_path, capsys,
                                                make_board):
    """ROADMAP autotune follow-on (c): a requeued --resume run reports
    how its dispatch was routed — heuristic without a store."""
    from mpi_and_open_mp_tpu.apps import life as life_app
    from mpi_and_open_mp_tpu.utils.config import save_config

    cfg = config_from_board(make_board(16, 16), steps=20, save_steps=5)
    cfg_path = tmp_path / "run.cfg"
    save_config(cfg_path, cfg)
    out = tmp_path / "vtk"
    assert life_app.main([str(cfg_path), "--layout", "row",
                          "--outdir", str(out)]) == 0
    capsys.readouterr()
    assert life_app.main([str(cfg_path), "--layout", "row",
                          "--outdir", str(out), "--resume"]) == 0
    err = capsys.readouterr().err
    assert "resuming from" in err  # the historical prose line survives
    status = _resume_status_line(err)
    assert status["plan_source"] == "heuristic"
    assert "resumed" in status and "plans_installed" not in status


def test_resume_consumes_installed_plans(tmp_path, capsys, make_board):
    """The warm-AND-tuned restart: with a populated --plans store, the
    resumed run installs the records before the first dispatch and the
    status line stamps plan_source=store."""
    from mpi_and_open_mp_tpu.apps import life as life_app
    from mpi_and_open_mp_tpu.tune import tune
    from mpi_and_open_mp_tpu.tune.plans import PlanStore
    from mpi_and_open_mp_tpu.utils.config import save_config

    plans = tmp_path / "plans"
    tune("life", (1, 16, 16), steps=16, store=PlanStore(plans))
    cfg = config_from_board(make_board(16, 16), steps=20, save_steps=5)
    cfg_path = tmp_path / "run.cfg"
    save_config(cfg_path, cfg)
    out = tmp_path / "vtk"
    assert life_app.main([str(cfg_path), "--layout", "row",
                          "--outdir", str(out)]) == 0
    capsys.readouterr()
    assert life_app.main([str(cfg_path), "--layout", "row",
                          "--outdir", str(out), "--resume",
                          "--plans", str(plans)]) == 0
    status = _resume_status_line(capsys.readouterr().err)
    assert status["plans_installed"] >= 1
    assert status["plan_source"] == "store"
    assert status["tuned_path"]
