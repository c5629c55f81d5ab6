"""End-to-end LifeSim parity across layouts, impls, meshes, and fusion depths.

Every sharded configuration must produce a board bit-identical to the NumPy
oracle — the framework analogue of the reference's serial-vs-MPI VTK parity
(SURVEY §4). Runs on the 8-virtual-CPU-device mesh from conftest.
"""

import os

import numpy as np
import pytest


from mpi_and_open_mp_tpu.models.life import LifeSim
from mpi_and_open_mp_tpu.parallel import mesh as mesh_lib
from mpi_and_open_mp_tpu.utils.config import config_from_board, load_config_py
from mpi_and_open_mp_tpu.utils.vtk import read_vtk

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")


from conftest import oracle_n  # noqa: E402


@pytest.mark.parametrize("layout", ["serial", "row", "col", "cart"])
@pytest.mark.parametrize("impl", ["roll", "halo"])
def test_parity_divisible_board(make_board, layout, impl):
    if layout == "serial" and impl == "halo":
        with pytest.raises(ValueError, match="sharded layout"):
            LifeSim(config_from_board(make_board(8, 8), 1, 1),
                    layout="serial", impl="halo")
        return
    board = make_board(48, 40)  # divides 8 (row), 8 (col), and 4x2 (cart)
    cfg = config_from_board(board, steps=20, save_steps=1000)
    sim = LifeSim(cfg, layout=layout, impl=impl)
    sim.step(20)
    np.testing.assert_array_equal(sim.collect(), oracle_n(board, 20))


@pytest.mark.parametrize("layout", ["row", "col", "cart"])
def test_parity_uneven_board_roll(make_board, layout):
    """Non-divisible boards (the reference's last-rank-absorbs-remainder
    case, 3-life/life_mpi.c:178-183) via the global roll step."""
    board = make_board(50, 37)
    cfg = config_from_board(board, steps=15, save_steps=1000)
    sim = LifeSim(cfg, layout=layout, impl="roll")
    sim.step(15)
    np.testing.assert_array_equal(sim.collect(), oracle_n(board, 15))


@pytest.mark.parametrize("fuse", [2, 3, 5])
@pytest.mark.parametrize("layout", ["row", "col", "cart"])
def test_parity_fused_halo_steps(make_board, layout, fuse):
    """Depth-k halo fusion: k local steps per exchange, incl. a non-divisible
    remainder round (17 = 3*5 + 2 etc.)."""
    board = make_board(48, 40)
    cfg = config_from_board(board, steps=17, save_steps=1000)
    sim = LifeSim(cfg, layout=layout, impl="halo", fuse_steps=fuse)
    sim.step(17)
    np.testing.assert_array_equal(sim.collect(), oracle_n(board, 17))


@pytest.mark.parametrize("steps", [5, 130])
def test_parity_bitfused_row_ring(make_board, steps):
    """The packed scale-out path: ppermute 4-word halos + <=128 fused steps
    per round. 130 steps crosses a round boundary, so the second round's
    halo exchange carries first-round state."""
    board = make_board(2048, 128, density=0.35)  # 8 shards x 8 word rows
    cfg = config_from_board(board, steps=steps, save_steps=1000)
    sim = LifeSim(cfg, layout="row", impl="bitfused")
    sim.step(steps)
    np.testing.assert_array_equal(sim.collect(), oracle_n(board, steps))


def test_bitfused_segmented_run_and_debug(make_board, tmp_path):
    """run() with a save cadence drives advance at several segment lengths
    through ONE compiled program (n is a runtime scalar), and the halo
    debug check passes on the live sharded state."""
    board = make_board(2048, 128, density=0.3)
    cfg = config_from_board(board, steps=9, save_steps=4)
    sim = LifeSim(cfg, layout="row", impl="bitfused", outdir=tmp_path)
    sim.debug_check()
    final = sim.run(save=True)
    np.testing.assert_array_equal(final, oracle_n(board, 9))
    assert len(list(tmp_path.glob("*.vtk"))) == 3  # steps 0, 4, 8


@pytest.mark.parametrize("steps", [5, 130])
def test_parity_bitfused_col_strips(make_board, steps):
    """Column-strip bitfused: 128-column ppermute halos along x, local
    y wrap (the py=1 cart case). 8 shards of 1024x128."""
    board = make_board(1024, 1024, density=0.35)
    cfg = config_from_board(board, steps=steps, save_steps=1000)
    sim = LifeSim(cfg, layout="col", impl="bitfused")
    sim.step(steps)
    np.testing.assert_array_equal(sim.collect(), oracle_n(board, steps))


@pytest.mark.parametrize("steps", [5, 130])
def test_parity_bitfused_cart_mesh(make_board, steps):
    """The 2-D cart bitfused path: 128-column x halo + 4-word y halo per
    round (corners via the sequenced exchange), <=128 fused steps. The
    4x2 mesh gives 256x128 shards; 130 steps crosses a round boundary."""
    board = make_board(1024, 256, density=0.35)
    mesh = mesh_lib.make_mesh_2d(4, 2)
    cfg = config_from_board(board, steps=steps, save_steps=1000)
    sim = LifeSim(cfg, layout="cart", impl="bitfused", mesh=mesh)
    sim.step(steps)
    np.testing.assert_array_equal(sim.collect(), oracle_n(board, steps))


# The plan a benchmark cell runs, pinned where a parity case is one.
CELL_PLANS = {
    ((500, 500), "row", (4, 1)): dict(
        mode="window", frame=(512, 512), nw_s=4, h=3, pad_y=12,
        nx_exact=500, k_max=96),
}


@pytest.mark.parametrize(
    "shape,layout,mesh_args,steps",
    [
        # The flagship geometry (3-life/p46gun_big.cfg): 500x500 on the
        # 8-way ring — 512x512 frame, 2-word shards, window stepper,
        # k_max=32 so 40 steps crosses a round boundary.
        ((500, 500), "row", None, 40),
        # 500x500 on the default 2-D mesh: funnel y wrap + mirror x wrap
        # + corners, k_max=96.
        ((500, 500), "cart", (4, 2), 100),
        # Narrow column strips: 8-column re-pitch, shrunken x halo.
        ((500, 500), "col", None, 60),
        # Small unaligned boards, both axes padded (row on a 2-D mesh
        # shards y only; a 2-way ring leaves room for the halo).
        ((100, 130), "row", (2, 4), 40),
        ((100, 300), "cart", (2, 2), 40),
        # Previously gate-rejected aligned-ish shapes, now planned:
        ((2040, 128), "row", None, 140),   # ny % (32*8) != 0
        ((2048, 120), "row", None, 140),   # nx % 128 != 0 (patched rolls)
        ((1024, 192), "cart", (4, 2), 100),  # 96-col shards, narrow pitch
        # The flagship as four row strips (the p46gun_big_row4x1 cell):
        # 4-word strips of a 512x512 frame, window stepper, sub-word
        # pad_y 12 with h 3, exact 500-wide wrap; 200 steps, 3 rounds.
        ((500, 500), "row", (4, 1), 200),
    ],
)
def test_parity_bitfused_unaligned(make_board, shape, layout, mesh_args, steps):
    """Arbitrary board shapes through the packed fused path: the torus
    lives in a word/lane-aligned padded frame with periodic mirrors and
    funnel-shifted wrap halos (ops.bitlife module docs); every
    combination must stay bit-exact across fused-round boundaries."""
    board = make_board(*shape, density=0.35)
    mesh = mesh_lib.make_mesh_2d(*mesh_args) if mesh_args else None
    cfg = config_from_board(board, steps=steps, save_steps=1000)
    sim = LifeSim(cfg, layout=layout, impl="bitfused", mesh=mesh)
    assert steps > sim._plan.k_max, "steps must cross a fused round"
    for key, value in CELL_PLANS.get((shape, layout, mesh_args), {}).items():
        assert getattr(sim._plan, key) == value, key
    sim.step(steps)
    np.testing.assert_array_equal(sim.collect(), oracle_n(board, steps))


@pytest.mark.parametrize("steps", [140, 300])  # two and three rounds
def test_parity_bitfused_tiled_padded_cart(make_board, monkeypatch, steps):
    """The tiled kernel on a 2x2 cart mesh with both sharded axes padded:
    the 10000x10000 plan's features at a CPU size. 784x528 pitches as
    10000² does (no 8-word split of 13 words, so 16 words a shard): a
    1024x768 frame with 240 mirror rows and 240 mirror columns, more
    than the 128-column halo, so the y ghosts are funnel-shifted across
    words and the x mirrors reach past the ghosts. A small VMEM budget
    rejects the window stepper and tiles each shard 2x3."""
    from functools import partial

    from mpi_and_open_mp_tpu.ops import bitlife

    monkeypatch.setattr(bitlife, "plan_sharded_bits",
                        partial(bitlife.plan_sharded_bits, budget=30_000))
    board = make_board(784, 528, density=0.35)
    cfg = config_from_board(board, steps=steps, save_steps=1000)
    sim = LifeSim(cfg, layout="cart", impl="bitfused",
                  mesh=mesh_lib.make_mesh_2d(2, 2))
    plan = sim._plan
    assert plan.mode == "tiled" and plan.frame == (1024, 768)
    assert (plan.pad_y, plan.pad_x, plan.nw_s, plan.hx) == (240, 240, 16, 128)
    assert steps > plan.k_max, "steps must cross a fused round"
    sim.step(steps)
    np.testing.assert_array_equal(sim.collect(), oracle_n(board, steps))


def test_bitfused_1dev_serial_dispatch(make_board, monkeypatch):
    """A 1-device mesh has no neighbours: the bitfused path dispatches
    to the serial whole-board stepper (no ghost-window redundancy, no
    exchange rounds), sliced out of / re-padded into the plan's frame —
    and must stay bit-exact, including across what would have been
    fused-round boundaries. CPU-gated behind the test flag so the
    interpret suite's machinery coverage is unchanged by default."""
    from mpi_and_open_mp_tpu.models import life as life_mod
    from mpi_and_open_mp_tpu.parallel import mesh as mesh_lib

    board = make_board(100, 130)
    cfg = config_from_board(board, steps=150, save_steps=0)
    mesh = mesh_lib.make_mesh_1d(1, axis="y")

    # Default on CPU: the exchange machinery runs even on 1 device.
    sim_default = LifeSim(cfg, layout="row", impl="bitfused", mesh=mesh)
    assert sim_default.plan_note == sim_default._plan.mode

    monkeypatch.setattr(life_mod, "_BITFUSED_1DEV_SERIAL_ON_CPU", True)
    sim = LifeSim(cfg, layout="row", impl="bitfused", mesh=mesh)
    assert sim.plan_note.startswith("serial-1dev:")
    sim.step(150)  # crosses the machinery's k_max round boundary
    np.testing.assert_array_equal(sim.collect(), oracle_n(board, 150))
    # The sharded-state contract survives: the stored board keeps the
    # plan's frame shape, so snapshots/checkpoints are unaffected.
    assert sim.board.shape == sim._plan.frame


def test_bitfused_gates(make_board):
    with pytest.raises(ValueError, match="sharded layout"):
        LifeSim(config_from_board(make_board(2048, 128), 1, 1),
                layout="serial", impl="bitfused")
    # Genuinely unplannable: 64 rows over 8 shards leaves no room for a
    # fused halo next to the 192 frame-padding rows.
    with pytest.raises(ValueError, match="can't plan"):
        LifeSim(config_from_board(make_board(64, 128), 1, 1),
                layout="row", impl="bitfused")
    # Same on a 2-D mesh: 20-column shards can't feed an 8-column x halo.
    with pytest.raises(ValueError, match="can't plan"):
        LifeSim(config_from_board(make_board(256, 20), 1, 1),
                layout="cart", impl="bitfused",
                mesh=mesh_lib.make_mesh_2d(4, 2))


def test_parity_explicit_meshes(make_board):
    board = make_board(48, 40)
    for py, px in [(2, 4), (8, 1), (1, 8), (2, 2)]:
        mesh = mesh_lib.make_mesh_2d(py, px)
        cfg = config_from_board(board, steps=12, save_steps=1000)
        sim = LifeSim(cfg, layout="cart", impl="halo", mesh=mesh)
        sim.step(12)
        np.testing.assert_array_equal(sim.collect(), oracle_n(board, 12))


def test_auto_impl_selection(make_board):
    cfg = config_from_board(make_board(48, 40), steps=4, save_steps=10)
    assert LifeSim(cfg, layout="row", impl="auto").impl == "halo"
    cfg2 = config_from_board(make_board(50, 37), steps=4, save_steps=10)
    assert LifeSim(cfg2, layout="row", impl="auto").impl == "roll"
    with pytest.raises(ValueError):
        LifeSim(cfg2, layout="row", impl="halo")


def test_auto_selects_bitfused_on_tpu(monkeypatch, make_board):
    """On a TPU backend, auto must route the unaligned flagship geometry
    (500x500, any mesh) onto the packed fused path — construction only,
    so the faked backend never has to compile Mosaic on CPU."""
    import mpi_and_open_mp_tpu.models.life as life_mod

    monkeypatch.setattr(life_mod.jax, "default_backend", lambda: "tpu")
    cfg = config_from_board(make_board(500, 500), steps=4, save_steps=10)
    for layout in ("row", "col", "cart"):
        assert LifeSim(cfg, layout=layout, impl="auto").impl == "bitfused"
    # Geometry the planner rejects still falls back.
    cfg2 = config_from_board(make_board(64, 128), steps=4, save_steps=10)
    assert LifeSim(cfg2, layout="row", impl="auto").impl == "halo"


def test_glider_fixture_end_to_end(tmp_path):
    """Full driver contract: cfg in, VTK snapshots out at the reference's
    cadence (save at i % save_steps == 0, before stepping)."""
    cfg = load_config_py(os.path.join(FIXTURES, "glider_10x10.cfg"))
    outdir = tmp_path / "vtk"
    sim = LifeSim(cfg, layout="serial", impl="roll", outdir=outdir)
    final = sim.run(save=True)
    saved = sorted(os.listdir(outdir))
    assert saved == [f"life_{i:06d}.vtk" for i in (0, 25, 50, 75)]
    # Glider on a 10x10 torus has period 40; after 100 steps it sits at
    # the 60-step phase: shifted by (100//4) % 10 = 5 in both axes.
    start = cfg.board()
    np.testing.assert_array_equal(final, oracle_n(start, 100))
    np.testing.assert_array_equal(
        read_vtk(outdir / "life_000075.vtk"), oracle_n(start, 75)
    )


def test_rpentomino_fixture_all_layouts():
    cfg = load_config_py(os.path.join(FIXTURES, "rpentomino_40x32.cfg"))
    start = cfg.board()
    expect = oracle_n(start, cfg.steps)
    assert expect.sum() > 0  # r-pentomino is long-lived
    for layout in ["row", "col", "cart"]:
        sim = LifeSim(cfg, layout=layout, impl="auto")
        got = sim.run(save=False)
        np.testing.assert_array_equal(got, expect)


def test_empty_fixture():
    cfg = load_config_py(os.path.join(FIXTURES, "empty_10x10.cfg"))
    sim = LifeSim(cfg, layout="row", impl="roll")
    assert sim.run(save=False).sum() == 0


# ------------------------------------------------------------ run spans


@pytest.fixture
def sink(tmp_path, monkeypatch):
    from mpi_and_open_mp_tpu.obs import trace

    path = tmp_path / "spans.jsonl"
    monkeypatch.setenv("MOMP_TRACE", str(path))
    trace.reset()
    yield path
    trace.reset()


def _spans(path):
    import json

    return [r for r in map(json.loads, path.read_text().splitlines())
            if r["kind"] == "span"]


def test_run_spans_share_the_run_number(make_board, sink):
    """Each reset() + run() writes its upload, advance and collect under
    one ``run`` number of its own, with the board's bytes."""
    board = make_board(48, 40)
    cfg = config_from_board(board, steps=6, save_steps=1000)
    sim = LifeSim(cfg, layout="cart", impl="halo")
    sink.write_text("")  # drop the constructor's upload
    for _ in range(2):
        sim.reset()
        np.testing.assert_array_equal(sim.run(save=False),
                                      oracle_n(board, 6))
    recs = _spans(sink)
    assert [r["name"] for r in recs] == ["life.upload", "life.advance",
                                         "life.collect"] * 2
    runs = [r["attrs"]["run"] for r in recs]
    assert runs[:3] == [runs[0]] * 3 and runs[3:] == [runs[0] + 1] * 3
    for r in recs:
        if r["name"] != "life.advance":
            assert r["attrs"]["bytes"] == sim.board.nbytes == 48 * 40
        assert r["dur"] > 0 and r["parent"] is None


@pytest.mark.parametrize("chunked", [True, False])
def test_snapshot_span_holds_collect_and_vtk_write(make_board, sink,
                                                   tmp_path, monkeypatch,
                                                   chunked):
    """A frame fetched one at a time is collected inside its snapshot
    span; a chunk's frames are fetched together in ``life.frames``, so
    their snapshot spans hold the write alone."""
    from mpi_and_open_mp_tpu.models import life

    if not chunked:
        monkeypatch.setattr(life, "_FRAME_CHUNK_BYTES", 0)
    board = make_board(16, 16)
    cfg = config_from_board(board, steps=4, save_steps=2)
    sim = LifeSim(cfg, layout="serial", impl="roll",
                  outdir=tmp_path / "vtk")
    sim.run(save=True)
    recs = _spans(sink)
    snaps = [r for r in recs if r["name"] == "life.snapshot"]
    assert [s["attrs"]["step"] for s in snaps] == [0, 2]
    assert len([r for r in recs if r["name"] == "life.frames"]) == chunked
    for snap in snaps:
        kids = [r for r in recs if r["parent"] == snap["id"]]
        assert [k["name"] for k in kids] == (
            ["life.vtk_write"] if chunked
            else ["life.collect", "life.vtk_write"])
        write = kids[-1]
        assert write["attrs"]["bytes"] == os.path.getsize(
            tmp_path / "vtk" / f"life_{snap['attrs']['step']:06d}.vtk")
        assert {k["attrs"]["run"] for k in kids} == {snap["attrs"]["run"]}


def test_untraced_run_adds_no_block_fetch_or_counter(make_board, tmp_path,
                                                     monkeypatch):
    """With MOMP_TRACE unset, reset(), run() and collect() neither block
    nor fetch beyond the collect's own fetch, touch no metrics counter
    and write no sink; the board comes out as the oracle's."""
    import jax

    from mpi_and_open_mp_tpu.obs import metrics, trace

    monkeypatch.delenv("MOMP_TRACE", raising=False)
    trace.reset()
    board = make_board(48, 40)
    cfg = config_from_board(board, steps=5, save_steps=1000)
    sim = LifeSim(cfg, layout="row", impl="halo")
    sim.warmup()
    fetches = []
    get = jax.device_get

    def counted(x):
        fetches.append(x)
        return get(x)

    def no_block(*_a, **_k):
        raise AssertionError("an untraced run blocked")

    monkeypatch.setattr(jax, "device_get", counted)
    monkeypatch.setattr(jax, "block_until_ready", no_block)
    before = metrics.snapshot()
    sim.reset()
    got = sim.run(save=False)
    again = sim.collect()
    assert len(fetches) == 2  # run()'s collect and the explicit one
    assert metrics.snapshot() == before
    np.testing.assert_array_equal(got, oracle_n(board, 5))
    np.testing.assert_array_equal(again, got)
    assert list(tmp_path.iterdir()) == []


def test_run_spans_lie_on_the_profiler_clock(make_board, tmp_path, sink):
    """A traced run inside jax.profiler.trace puts its upload, advance
    and collect on the host's line of the profile, in that order."""
    import jax
    from jax.profiler import ProfileData

    board = make_board(32, 32)
    cfg = config_from_board(board, steps=3, save_steps=1000)
    sim = LifeSim(cfg, layout="serial", impl="roll")
    sim.warmup()
    prof = tmp_path / "prof"
    with jax.profiler.trace(str(prof)):
        sim.reset()
        sim.run(save=False)
    (xplane,) = prof.rglob("*.xplane.pb")
    names = {"life.upload", "life.advance", "life.collect"}
    found = [(e.start_ns, e.name)
             for plane in ProfileData.from_file(str(xplane)).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name in names]
    assert [n for _, n in sorted(found)] == ["life.upload", "life.advance",
                                            "life.collect"]


# -------------------------------------------------------- packed collect


@pytest.fixture
def pack_any_size(monkeypatch):
    """Lets small test boards take the packed collect (real boards take
    it from ``_PACK_MIN_BYTES``)."""
    from mpi_and_open_mp_tpu.models import life

    monkeypatch.setattr(life, "_PACK_MIN_BYTES", 0)


_MESHES = {
    "serial": lambda: None,
    "cart2x2": lambda: mesh_lib.make_mesh_2d(2, 2),
    "row4x1": lambda: mesh_lib.make_mesh_1d(4, axis="y"),
    "col1x4": lambda: mesh_lib.make_mesh_1d(4, axis="x"),
}


@pytest.mark.parametrize("layout,mesh,shape,packed", [
    ("serial", "serial", (40, 64), True),
    ("serial", "serial", (40, 500), False),
    ("cart", "cart2x2", (48, 128), True),
    ("row", "row4x1", (48, 96), True),
    ("col", "col1x4", (40, 256), True),
    ("col", "col1x4", (40, 192), False),  # 48-cell shards
    ("serial", "serial", (3, 33, 64), True),  # a batched stack
    ("serial", "serial", (520, 2048), True),  # unpacked in two chunks
])
def test_packed_collect_is_the_byte_board(make_board, pack_any_size, layout,
                                          mesh, shape, packed):
    """collect() and run() return the byte fetch's board, bit for bit;
    the pack engages exactly where each shard's width is a multiple of
    32, and its sharded program holds no collective."""
    ny, nx = shape[-2:]
    board = (np.stack([make_board(ny, nx) for _ in range(shape[0])])
             if len(shape) == 3 else make_board(ny, nx))
    cfg = config_from_board(np.zeros((ny, nx), np.uint8), steps=6,
                            save_steps=1000)
    impl = "roll" if layout == "serial" else "halo"
    sim = LifeSim(cfg, layout=layout, impl=impl, mesh=_MESHES[mesh](),
                  initial_board=board)
    assert (sim._pack is not None) is packed
    start = sim.collect()
    assert start.dtype == np.uint8 and start.shape == shape
    np.testing.assert_array_equal(start, board)
    got = sim.run(save=False)
    np.testing.assert_array_equal(got, sim._collect(packed=False))
    np.testing.assert_array_equal(got, oracle_n(board, 6)
                                  if len(shape) == 2 else
                                  np.stack([oracle_n(b, 6) for b in board]))
    if packed and sim.sharding is not None:
        hlo = sim._pack.lower(sim.board).compile().as_text()
        for op in ("all-gather", "collective-permute", "all-to-all",
                   "all-reduce"):
            assert op not in hlo


def test_warmup_compiles_the_pack(make_board, pack_any_size):
    board = make_board(32, 64)
    cfg = config_from_board(board, steps=3, save_steps=1000)
    sim = LifeSim(cfg, layout="cart", impl="halo",
                  mesh=mesh_lib.make_mesh_2d(2, 2))
    sim.warmup()
    assert sim._pack._cache_size() == 1
    sim.reset()
    sim.run(save=False)
    assert sim._pack._cache_size() == 1


def test_guard_sees_a_non_binary_cell_past_the_packed_collect(
        make_board, pack_any_size):
    """A 2 on the board folds into the packed words' bits, so the guard
    fetches bytes: the cell is still reported as non-binary."""
    board = make_board(32, 64)
    cfg = config_from_board(board, steps=4, save_steps=1000)
    sim = LifeSim(cfg, layout="cart", impl="halo",
                  mesh=mesh_lib.make_mesh_2d(2, 2))
    assert sim._pack is not None
    bad = board.copy()
    bad[3, 5] = 2
    sim._set_board(bad, 0)
    assert sim.collect().max() == 1  # what a packed probe would see
    assert sim._consistency_violation() == "non-binary cells on the board"
    with pytest.raises(AssertionError, match="non-binary"):
        sim.debug_check()


@pytest.mark.parametrize("nx,packed", [(64, True), (500, False)])
def test_collect_span_says_what_crossed(make_board, sink, pack_any_size, nx,
                                        packed):
    """life.collect carries ``packed`` and ``wire_bytes``, the bytes
    fetched to the host; ``bytes`` stays the board's."""
    board = make_board(24, nx)
    cfg = config_from_board(board, steps=2, save_steps=1000)
    sim = LifeSim(cfg, layout="serial", impl="roll")
    sink.write_text("")  # drop the constructor's upload
    np.testing.assert_array_equal(sim.collect(), board)
    (rec,) = _spans(sink)
    assert rec["name"] == "life.collect"
    attrs = rec["attrs"]
    assert attrs["packed"] is packed
    assert attrs["bytes"] == sim.board.nbytes == 24 * nx
    assert attrs["wire_bytes"] == (sim.board.nbytes // 8 if packed
                                   else sim.board.nbytes)


@pytest.mark.parametrize("ny,packed", [(4096, True), (4095, False)])
def test_pack_engages_from_min_bytes(ny, packed):
    """Without the test override, a board packs from 32 MiB on: 4096 rows
    of 8192 cells, but not 4095."""
    from mpi_and_open_mp_tpu.models import life

    assert life._PACK_MIN_BYTES == 4096 * 8192
    board = np.zeros((ny, 8192), np.uint8)
    board[ny - 1, 8191] = board[7, 40] = 1
    cfg = config_from_board(board, steps=1, save_steps=1000)
    sim = LifeSim(cfg, layout="serial", impl="roll")
    assert (sim._pack is not None) is packed
    np.testing.assert_array_equal(sim.collect(), board)


@pytest.mark.parametrize("shape", [(5, 3), (64, 32), (2, 7, 4)])
def test_unpack_words_in_chunks(rng, monkeypatch, shape):
    """The pooled unpack is np.unpackbits over the words' little-endian
    bytes, whatever the chunk size cuts the rows into."""
    from mpi_and_open_mp_tpu.models import life

    words = rng.integers(0, 2**32, shape, dtype=np.uint32)
    want = np.unpackbits(words.astype("<u4").view(np.uint8), axis=-1,
                         bitorder="little")
    for chunk in (1, 3 * 32 * shape[-1], 1 << 20):
        monkeypatch.setattr(life, "_UNPACK_CHUNK_BYTES", chunk)
        got = life._unpack_words(words)
        assert got.dtype == np.uint8 and got.shape == (*shape[:-1],
                                                       32 * shape[-1])
        np.testing.assert_array_equal(got, want)
