"""PR 18: partitioned halo transport everywhere — satellites.

The tentpole contract (``parallel/haloplan.py``): the Pallas
async-remote-copy rung covers every layout (row, col's x-mirror, cart's
two-phase corner exchange) and the boundary itself can be partitioned
into per-edge sub-rounds (``boundary_steps < fuse_steps``, the
``MPI_Pready`` analogue of arxiv 2508.13370) — all bit-exact to the
sequential oracle. CPU CI executes the RDMA *schedule* through a
``ppermute`` stand-in with identical semantics (predecessor's forward
edge, successor's backward edge), so the exchange order, corner
assembly, and chaos hooks are exercised here and only the DMA transport
itself is chip-only. Chaos must reach every
new exchange (a corrupted ghost diverges the run; the LifeSim guard
ladder recovers with ``:recovered`` provenance), and the tuner's
independent interior x boundary depth axis must keep the coupled-depth
heuristic in the race (``vs_heuristic >= 1.0`` by construction) and
persist winners. Runs on the 8-virtual-CPU-device mesh from conftest.
"""

import os
import sys

import numpy as np
import pytest

import jax
from jax import lax

from conftest import oracle_n
from mpi_and_open_mp_tpu import stencils
from mpi_and_open_mp_tpu.models.life import LifeSim
from mpi_and_open_mp_tpu.obs import ledger
from mpi_and_open_mp_tpu.parallel import halo, haloplan, mesh as mesh_lib
from mpi_and_open_mp_tpu.robust import chaos
from mpi_and_open_mp_tpu.stencils import engine as stencil_engine
from mpi_and_open_mp_tpu.utils.config import config_from_board

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_chaos_and_plans():
    """Chaos plans are trace-time and the halo plan cache keys on the
    env flags but NOT on the backend (the backend cannot change in a
    real process) — tests that fake the backend must drop their cached
    ``overlap:rdma`` plans on the way out."""
    haloplan._plan.cache_clear()
    yield
    haloplan._plan.cache_clear()
    chaos.reset()


def _fake_edge_pair(fwd_edge, bwd_edge, axis_name, p, *, collective_id):
    """``ppermute`` stand-in for the Pallas RDMA kernel — the same
    contract (returns the predecessor's ``fwd_edge`` and the successor's
    ``bwd_edge``) so the CPU mesh executes the RDMA schedule, corner
    assembly, and chaos wrappers; only the DMA transport is swapped."""
    return (lax.ppermute(fwd_edge, axis_name, halo.ring_perm(p, 1)),
            lax.ppermute(bwd_edge, axis_name, halo.ring_perm(p, -1)))


def _arm_rdma(monkeypatch):
    """Opt the plan into the RDMA rung on the CPU mesh: flag on, backend
    faked (the engine choice lives inside the cached plan derivation),
    transport stubbed."""
    monkeypatch.setenv(haloplan.ENV_RDMA, "1")
    monkeypatch.setattr(haloplan.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(haloplan, "_rdma_edge_pair", _fake_edge_pair)
    haloplan._plan.cache_clear()


# ------------------------------------------------------------ plan derivation


def test_partitioned_plan_stamps_and_legality():
    plan = haloplan.plan_halo("row", (8, 1), (64, 64), 1, 4,
                              boundary_steps=2)
    assert plan.overlap and plan.engine == "overlap:deferred:pb2"
    assert plan.boundary_steps == 2 and plan.fuse_steps == 4

    coupled = haloplan.plan_halo("row", (8, 1), (64, 64), 1, 4)
    assert coupled.boundary_steps == 4
    assert coupled.engine == "overlap:deferred"

    with pytest.raises(ValueError, match="must divide"):
        haloplan.plan_halo("row", (8, 1), (64, 64), 1, 4,
                           boundary_steps=3)
    with pytest.raises(ValueError, match="coupled boundary"):
        haloplan.plan_halo("row", (8, 1), (64, 64), 1, 4,
                           boundary_steps=2, pack_layout="packed")


def test_partitioned_plan_degrades_coupled(monkeypatch):
    """Kill switch / degenerate geometry resets the boundary axis too:
    a sequential plan has one exchange per round by definition."""
    monkeypatch.setenv(haloplan.ENV_OVERLAP, "0")
    plan = haloplan.plan_halo("row", (8, 1), (64, 64), 1, 4,
                              boundary_steps=2)
    assert not plan.overlap and plan.engine == "seq:halo"
    assert plan.boundary_steps == plan.fuse_steps == 4
    monkeypatch.delenv(haloplan.ENV_OVERLAP)
    haloplan._plan.cache_clear()
    shallow = haloplan.plan_halo("row", (8, 1), (6, 64), 1, 4,
                                 boundary_steps=2)
    assert not shallow.overlap and "empty interior" in shallow.why


# --------------------------------------- partitioned-boundary bit identity


@pytest.mark.parametrize("layout", ["row", "col", "cart"])
@pytest.mark.parametrize("workload", sorted(stencils.names()))
def test_partitioned_boundary_bit_equals_sequential(workload, layout):
    """The satellite invariant: for every registry spec and layout the
    partitioned round (fuse=2, per-edge depth 1, ``:pb1``) reassembles
    bit-identically to the forced-sequential schedule (ulp-identically
    for wide-radius float tap sums, which may reassociate) and passes
    the oracle gate — partitioning moves message boundaries, not
    values."""
    spec = stencils.get(workload)
    # Wide-radius specs (lenia r=8): the round's full fused depth is
    # fuse(2)*radius, and overlap needs every layout's min shard (s/4)
    # to keep a non-empty interior past 2*that — else the plan legally
    # gates out to seq and the :pb1 assertion below is moot.
    s = max(48, 20 * spec.radius)
    board = spec.init(np.random.default_rng(46), (s, s))
    mesh = mesh_lib.make_mesh_2d(4, 2)
    got = np.asarray(stencil_engine.run_sharded(
        spec, board, 6, mesh=mesh, layout=layout, fuse_steps=2,
        boundary_steps=1))
    plan = stencil_engine.run_sharded.last_plan
    assert plan.overlap and plan.engine.endswith(":pb1")
    seq = np.asarray(stencil_engine.run_sharded(
        spec, board, 6, mesh=mesh, layout=layout, fuse_steps=2,
        overlap=False))
    if spec.radius > 1 and spec.is_float:
        # A wide-radius float tap sum (lenia: 288 adds per cell) may
        # legally reassociate between the boundary-strip and full-shard
        # programs; the agreement bound is ulp-level, not bit-level
        # (measured 0.5 ulp at the seams).
        np.testing.assert_allclose(
            got, seq, rtol=0, atol=4 * np.finfo(np.float32).eps)
    else:
        np.testing.assert_array_equal(got, seq)
    assert stencils.parity_ok(spec, got,
                              stencils.oracle_run(spec, board, 6))


def test_partitioned_deep_fuse_with_remainder_round():
    """fuse=4 split into depth-2 sub-rounds, 10 steps: two partitioned
    rounds plus a depth-2 remainder round (its own coupled plan)."""
    spec = stencils.get("life")
    board = spec.init(np.random.default_rng(47), (48, 48))
    mesh = mesh_lib.make_mesh_2d(4, 2)
    got = np.asarray(stencil_engine.run_sharded(
        spec, board, 10, mesh=mesh, layout="cart", fuse_steps=4,
        boundary_steps=2))
    assert stencil_engine.run_sharded.last_plan.engine.endswith(":pb2")
    seq = np.asarray(stencil_engine.run_sharded(
        spec, board, 10, mesh=mesh, layout="cart", fuse_steps=4,
        overlap=False))
    np.testing.assert_array_equal(got, seq)
    assert stencils.parity_ok(
        spec, got, stencils.oracle_run(spec, board, 10))


# --------------------------------------------------- RDMA rung on the mesh


@pytest.mark.parametrize("layout", ["row", "col", "cart"])
@pytest.mark.parametrize("boundary", [None, 1])
def test_rdma_schedule_bit_parity_every_layout(monkeypatch, layout,
                                               boundary):
    """The RDMA rung's schedule for every layout — col's x-mirror,
    cart's two-phase corner exchange — coupled and partitioned, through
    the ppermute transport stand-in: stamped ``overlap:rdma[:pb1]`` and
    bit-identical to the sequential oracle."""
    _arm_rdma(monkeypatch)
    spec = stencils.get("life")
    board = spec.init(np.random.default_rng(48), (48, 48))
    mesh = mesh_lib.make_mesh_2d(4, 2)
    got = np.asarray(stencil_engine.run_sharded(
        spec, board, 6, mesh=mesh, layout=layout, fuse_steps=2,
        boundary_steps=boundary))
    plan = stencil_engine.run_sharded.last_plan
    want_stamp = "overlap:rdma" + (":pb1" if boundary else "")
    assert plan.engine == want_stamp
    seq = np.asarray(stencil_engine.run_sharded(
        spec, board, 6, mesh=mesh, layout=layout, fuse_steps=2,
        overlap=False))
    np.testing.assert_array_equal(got, seq)
    assert stencils.parity_ok(spec, got,
                              stencils.oracle_run(spec, board, 6))


# ------------------------------------------------- cart corner, every rung


def _corner_glider_board(edge=64):
    """A glider aimed straight through the (4, 2) cart mesh's interior
    shard corner at (16, 32): it crosses the y edge, the x edge, and the
    diagonal corner words within ~12 steps — the exact cells the
    two-phase exchange forwards without a third transfer."""
    b = np.zeros((edge, edge), np.uint8)
    glider = np.array([[0, 1, 0],
                       [0, 0, 1],
                       [1, 1, 1]], np.uint8)  # travels down-right
    b[10:13, 26:29] = glider
    return b


@pytest.mark.parametrize("rdma", [False, True])
@pytest.mark.parametrize("schedule", ["seq", "coupled", "partitioned"])
def test_cart_corner_glider_every_schedule(monkeypatch, rdma, schedule):
    """Acceptance: a glider crossing the 2-D shard corner stays
    bit-equal to the sequential oracle under every (rdma, overlap,
    partitioned-boundary) combination, across fused-round boundaries
    (24 steps of fuse=2 rounds, plus a 7-step run with a remainder
    round)."""
    if rdma:
        _arm_rdma(monkeypatch)
    spec = stencils.get("life")
    board = _corner_glider_board()
    mesh = mesh_lib.make_mesh_2d(4, 2)
    kw = {"seq": {"overlap": False},
          "coupled": {},
          "partitioned": {"boundary_steps": 1}}[schedule]
    for steps in (7, 24):
        got = np.asarray(stencil_engine.run_sharded(
            spec, board, steps, mesh=mesh, layout="cart", fuse_steps=2,
            **kw))
        np.testing.assert_array_equal(
            got[0] if got.ndim == 3 else got, oracle_n(board, steps))
    plan = stencil_engine.run_sharded.last_plan
    if schedule != "seq":
        assert plan.overlap
        assert plan.engine.startswith(
            "overlap:rdma" if rdma else "overlap:deferred")


# ----------------------------------------------------------- chaos coverage


def test_chaos_corrupts_partitioned_col_exchange(monkeypatch,
                                                 make_board):
    """``_chaos_ghost`` reaches the partitioned per-edge sends (the
    ``x-part`` sub-rounds): a corrupted ghost with guards off must
    diverge the run — the fault is injected, not absorbed. (Dense
    random board: every shard edge carries live cells, so a faulted
    ghost must change the outcome.)"""
    spec = stencils.get("life")
    board = make_board(48, 48)
    mesh = mesh_lib.make_mesh_2d(4, 2)
    clean = np.asarray(stencil_engine.run_sharded(
        spec, board, 6, mesh=mesh, layout="col", fuse_steps=2,
        boundary_steps=1))
    monkeypatch.setenv("MOMP_CHAOS", "halo=corrupt;noguard")
    chaos.reset()
    jax.clear_caches()
    hurt = np.asarray(stencil_engine.run_sharded(
        spec, board, 6, mesh=mesh, layout="col", fuse_steps=2,
        boundary_steps=1))
    assert not np.array_equal(clean, hurt)


def test_chaos_corrupts_rdma_cart_corner_exchange(monkeypatch,
                                                  make_board):
    """The two-phase corner exchange funnels through the same chaos
    hook: a corrupted phase-2 (x) ghost — which carries the corner
    words — diverges the cart run on the RDMA rung."""
    _arm_rdma(monkeypatch)
    spec = stencils.get("life")
    board = make_board(48, 48)
    mesh = mesh_lib.make_mesh_2d(4, 2)
    clean = np.asarray(stencil_engine.run_sharded(
        spec, board, 6, mesh=mesh, layout="cart", fuse_steps=2))
    monkeypatch.setenv("MOMP_CHAOS", "halo=corrupt;noguard")
    chaos.reset()
    jax.clear_caches()
    hurt = np.asarray(stencil_engine.run_sharded(
        spec, board, 6, mesh=mesh, layout="cart", fuse_steps=2))
    assert not np.array_equal(clean, hurt)


def test_chaos_col_halo_recovers_with_provenance(monkeypatch, make_board):
    """Guard ladder over the col layout's deferred overlap exchange:
    the consistency probe catches the corrupted x ghost and the
    suppressed re-trace recovers bit-identically, stamping
    ``:recovered`` provenance."""
    board = make_board(64, 64)
    cfg = config_from_board(board, steps=12, save_steps=4)
    monkeypatch.setenv("MOMP_CHAOS", "halo=corrupt;seed=3")
    chaos.reset()
    sim = LifeSim(cfg, layout="col", impl="halo",
                  mesh=mesh_lib.make_mesh_1d(8, axis="x"))
    final = sim.run(save=False)
    np.testing.assert_array_equal(final, oracle_n(board, 12))
    assert sim.recoveries and "recovered" in sim.recoveries[0]


def test_chaos_cart_rdma_recovers_with_provenance(monkeypatch,
                                                  make_board):
    """Same ladder on the cart RDMA rung (two-phase corner exchange via
    the transport stand-in): recovery must re-trace with injection
    suppressed and land bit-identical."""
    _arm_rdma(monkeypatch)
    board = make_board(64, 64)
    cfg = config_from_board(board, steps=12, save_steps=4)
    monkeypatch.setenv("MOMP_CHAOS", "halo=corrupt;seed=5")
    chaos.reset()
    sim = LifeSim(cfg, layout="cart", impl="halo",
                  mesh=mesh_lib.make_mesh_2d(4, 2))
    final = sim.run(save=False)
    np.testing.assert_array_equal(final, oracle_n(board, 12))
    assert sim.recoveries and "recovered" in sim.recoveries[0]


# ------------------------------------------------ tuner depth axis + store


def test_sharded_fuse_depths_env_override(monkeypatch):
    from mpi_and_open_mp_tpu.tune import space

    monkeypatch.delenv("MOMP_TUNE_FUSE_DEPTHS", raising=False)
    assert space.sharded_fuse_depths() == (1, 2)
    monkeypatch.setenv("MOMP_TUNE_FUSE_DEPTHS", "4")
    assert space.sharded_fuse_depths() == (1, 4)  # heuristic stays in
    monkeypatch.setenv("MOMP_TUNE_FUSE_DEPTHS", "8,2,2")
    assert space.sharded_fuse_depths() == (1, 2, 8)
    assert space._boundary_depths(4) == (4, 2, 1)


def test_tune_sharded_depth_axis_and_heuristic_race(tmp_path,
                                                    monkeypatch):
    """The tuner enumerates interior x boundary depths independently
    (legality-gated), always races the coupled-depth heuristic
    (vs_heuristic >= 1.0 by construction — the heuristic is IN the
    race), and persists the winning depths for zero-retrace reuse."""
    from mpi_and_open_mp_tpu.tune import space, tune_sharded
    from mpi_and_open_mp_tpu.tune.plans import PlanStore

    monkeypatch.setenv("MOMP_TUNE_FUSE_DEPTHS", "1,2")
    mesh = mesh_lib.make_mesh_2d(4, 2)
    cands = space.sharded_candidates("life", (64, 64), mesh)
    pairs = {(c.axis_order, c.fuse_steps, c.boundary_steps)
             for c in cands if c.halo_overlap == "overlap"}
    for lo in ("row", "col", "cart"):
        assert {(lo, 1, 1), (lo, 2, 2), (lo, 2, 1)} <= pairs

    store = PlanStore(tmp_path)
    res = tune_sharded("life", (64, 64), mesh=mesh, steps=16,
                       store=store)
    assert res["vs_heuristic"] >= 1.0
    assert res["heuristic"]["halo_overlap"] == "overlap"
    assert res["heuristic"]["fuse_steps"] == 1
    assert {"fuse_steps", "boundary_steps"} <= set(res["tuned"])

    fresh = PlanStore(tmp_path)
    fresh.install()
    hit = fresh.lookup_sharded("life", (64, 64))
    assert hit is not None
    assert {"fuse_steps", "boundary_steps"} <= set(hit["choice"])


# ------------------------------------------------- sentinel ring provenance


def test_sentinel_ring_fields_polarity():
    sys.path.insert(0, os.path.join(REPO, "analysis"))
    import regression_sentinel

    assert "ring_prefetch_tflops" in regression_sentinel.WATCH_FIELDS
    assert "ring_exposed_s" in regression_sentinel.WATCH_FIELDS
    assert regression_sentinel.direction_for(
        "ring_prefetch_tflops") == "higher"
    assert regression_sentinel.direction_for("ring_exposed_s") == "lower"
    assert "ring_hop_engine" in regression_sentinel.PROVENANCE_FIELDS
    assert "ring_hop_engine_bwd" in regression_sentinel.PROVENANCE_FIELDS
    # :pf is a tiebreak WITHIN the pallas tier, not a new tier.
    key = regression_sentinel._provenance_key
    assert key("pallas:b128:pf") > key("pallas:b128")
    assert (regression_sentinel.engine_rank("pallas:b128:pf")
            == regression_sentinel.engine_rank("pallas:b128"))


def test_sentinel_fails_pf_loss_not_pf_gain():
    """Losing the ``:pf`` suffix at the same engine tier (the
    MOMP_RING_PREFETCH=0 rerun) is a provenance downgrade the sentinel
    fails; gaining it is not."""
    sys.path.insert(0, os.path.join(REPO, "analysis"))
    import regression_sentinel

    def entry(ts, stamp):
        rec = {"metric": "m", "value": 100.0, "board": [64, 64],
               "dtype": "uint8", "steps": 100, "batch": 0,
               "ring_hop_engine": stamp}
        return ledger.stamp(rec, platform="cpu", device_count=8, ts=ts,
                            sha="deadbee")

    entries = [entry(float(i), "pallas:b128:pf") for i in range(3)]
    entries.append(entry(3.0, "pallas:b128"))
    verdict = regression_sentinel.evaluate(entries)
    assert verdict["verdict"] == "fail"
    (down,) = [d for d in verdict["downgrades"]
               if d["field"] == "ring_hop_engine"]
    assert down["new"] == "pallas:b128"
    assert down["baseline_best"] == "pallas:b128:pf"

    entries = [entry(float(i), "pallas:b128") for i in range(3)]
    entries.append(entry(3.0, "pallas:b128:pf"))
    verdict = regression_sentinel.evaluate(entries)
    assert not [d for d in verdict.get("downgrades", [])
                if d["field"] == "ring_hop_engine"]
