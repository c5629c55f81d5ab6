"""The counters on a sharded bitfused advance's stepping spans
(``LifeSim._step_attrs``): cells of the board and of the padded frame
the kernel steps, exchange rounds, and the bytes a chip sends, tied to
the ``collective-permute``s of the lowered program; and the cells the
fused steppers (tiled and whole-window) compute per cell they write."""

import json
import re
from functools import partial

import numpy as np
import pytest

from mpi_and_open_mp_tpu.models.life import LifeSim
from mpi_and_open_mp_tpu.obs import trace
from mpi_and_open_mp_tpu.ops import bitlife
from mpi_and_open_mp_tpu.parallel import mesh as mesh_lib
from mpi_and_open_mp_tpu.utils.config import config_from_board

from conftest import oracle_n

KEYS = {"board_cells", "frame_cells", "rounds", "halo_bytes"}
STEP_SPANS = ("life.advance", "life.segment")
PERMUTE = re.compile(r"stablehlo\.collective_permute\".*?:\s*"
                     r"\(tensor<((?:\d+x)*)(u?i|f)(\d+)>\)")


def permute_bytes(sim) -> int:
    """Summed operand bytes of the ``collective_permute``s in the
    lowered advance: one exchange round's, since the round is the body
    of the advance's loop."""
    text = sim._advance.lower(sim.board, 1).as_text()
    total = 0
    for line in text.splitlines():
        m = PERMUTE.search(line)
        if m:
            dims = [int(d) for d in m.group(1).split("x") if d]
            total += int(np.prod(dims)) * int(m.group(3)) // 8
    return total


def spans_of(path):
    with open(path) as fd:
        return [r for r in map(json.loads, fd)
                if r["kind"] == "span" and r["name"] in STEP_SPANS]


@pytest.fixture
def traced(tmp_path, monkeypatch):
    path = tmp_path / "spans.jsonl"
    monkeypatch.setenv("MOMP_TRACE", str(path))
    trace.reset()
    yield path
    trace.reset()


# (shape, VMEM budget): an exact 512² frame (window stepper), and 784x528
# in a 1024x768 frame, padded on both sharded axes, tiled as 10000² is.
GEOMETRIES = {"aligned": ((512, 512), bitlife._PACKED_VMEM_LIMIT),
              "unaligned": ((784, 528), 30_000)}


def cart_sim(make_board, monkeypatch, geometry, steps, save_steps,
             outdir=None):
    shape, budget = GEOMETRIES[geometry]
    monkeypatch.setattr(bitlife, "plan_sharded_bits",
                        partial(bitlife.plan_sharded_bits, budget=budget))
    board = make_board(*shape)
    sim = LifeSim(config_from_board(board, steps, save_steps), layout="cart",
                  impl="bitfused", mesh=mesh_lib.make_mesh_2d(2, 2),
                  outdir=outdir)
    return sim, board


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_advance_span_counts_what_the_program_sends(
        make_board, monkeypatch, traced, geometry):
    sim, board = cart_sim(make_board, monkeypatch, geometry,
                          steps=140, save_steps=0)
    np.testing.assert_array_equal(sim.run(), oracle_n(board, 140))
    (span,) = spans_of(traced)
    attrs = span["attrs"]
    assert span["name"] == "life.advance" and KEYS <= set(attrs)
    ny, nx = board.shape
    assert attrs["board_cells"] == ny * nx
    fy, fx = sim._plan.frame
    assert attrs["frame_cells"] == fy * fx
    if geometry == "aligned":
        assert attrs["frame_cells"] == attrs["board_cells"]
    else:
        assert attrs["frame_cells"] > attrs["board_cells"]
    assert attrs["rounds"] == 2  # 128 + 12 steps
    assert attrs["halo_bytes"] == attrs["rounds"] * permute_bytes(sim)


def test_snapshot_chunk_counts_each_advance(make_board, monkeypatch, traced,
                                            tmp_path):
    """A chunk of the snapshot path steps its save intervals as separate
    advances, so 140 steps in intervals of 50 take three rounds, not
    the two that one advance of 140 would."""
    sim, board = cart_sim(make_board, monkeypatch, "unaligned", steps=140,
                          save_steps=50, outdir=tmp_path / "vtk")
    np.testing.assert_array_equal(sim.run(), oracle_n(board, 140))
    (span,) = spans_of(traced)
    assert span["name"] == "life.segment"
    assert span["attrs"]["rounds"] == 3
    assert span["attrs"]["halo_bytes"] == 3 * permute_bytes(sim)


@pytest.mark.parametrize("layout,impl,mesh", [
    ("serial", "roll", None),
    ("row", "bitfused", (1, 1)),
    ("cart", "halo", (2, 2)),
], ids=["serial", "bitfused_one_device", "cart_halo"])
def test_other_paths_carry_no_counters(make_board, traced, layout, impl, mesh):
    """No exchange counters where nothing is exchanged. The one-device
    bitfused run still steps its window stepper on the CPU (the chip
    hands such a mesh to the serial kernel), so it counts that window
    and nothing else; the roll and halo paths count nothing."""
    board = make_board(64, 64)
    sim = LifeSim(config_from_board(board, steps=20, save_steps=0),
                  layout=layout, impl=impl,
                  mesh=mesh_lib.make_mesh_2d(*mesh) if mesh else None)
    np.testing.assert_array_equal(sim.run(), oracle_n(board, 20))
    (span,) = spans_of(traced)
    counted = {k: v for k, v in span["attrs"].items()
               if k in KEYS | {"window_cells"}}
    if impl == "bitfused":
        assert sim._plan.mode == "window"
        assert counted == bitlife.plan_tile_cells(sim._plan)
    else:
        assert counted == {}


def row_sim(make_board, shape, steps):
    """``shape`` as four row strips, as ``apps/life.py --layout row
    --devices 4`` meshes them."""
    board = make_board(*shape)
    sim = LifeSim(config_from_board(board, steps, 0), layout="row",
                  impl="bitfused", mesh=mesh_lib.make_mesh_1d(4, axis="y"))
    return sim, board


def test_window_span_counts_the_flagship_row_split(make_board, traced):
    """``p46gun_big.cfg``'s 500² board as four row strips: a 512² frame
    in window mode, each chip's 4-word strip stepped in a 10-word window
    (h 3 a side), so a fused step computes 2.5 cells a cell it writes.
    A 10⁴-step run takes 105 rounds of 96 steps or fewer, and a round
    sends 4 words × 512 columns each way (h + 1 words, from which the
    wrap ghosts are funnel-shifted past the 12 mirror rows): what the
    lowered program's ``collective_permute``s carry."""
    sim, board = row_sim(make_board, (500, 500), steps=200)
    plan = sim._plan
    assert (plan.mode, plan.nw_s, plan.h, plan.pad_y, plan.nx_exact,
            plan.k_max) == ("window", 4, 3, 12, 500, 96)
    np.testing.assert_array_equal(sim.run(), oracle_n(board, 200))
    (span,) = spans_of(traced)
    attrs = span["attrs"]
    assert attrs["window_cells"] == 4 * 32 * 10 * 512 == 655_360
    assert attrs["frame_cells"] == 512 * 512 == 262_144
    assert attrs["board_cells"] == 500 * 500
    assert attrs["rounds"] == 3
    assert permute_bytes(sim) == 16_384
    run = sim._step_attrs(10_000)
    assert run["rounds"] == 105
    assert run["halo_bytes"] == 105 * 16_384 == 1_720_320
    assert run["window_cells"] / run["frame_cells"] == 2.5


def test_overlapped_window_span_counts_both_windows(make_board, traced):
    """An exact-frame row split steps its round as the raw shard plus
    two 3h-word edge windows while the ghosts fly
    (``make_overlap_steppers``): 1280x128 on four chips, 10-word strips,
    h 4, so a fused step computes 10 + 24 words a strip."""
    sim, board = row_sim(make_board, (1280, 128), steps=100)
    assert (sim._plan.nw_s, sim._plan.h) == (10, 4)
    assert sim.plan_note == "window+overlap:packed"
    np.testing.assert_array_equal(sim.run(), oracle_n(board, 100))
    (span,) = spans_of(traced)
    attrs = span["attrs"]
    assert attrs["window_cells"] == 4 * 32 * (10 + 6 * 4) * 128
    assert attrs["frame_cells"] == attrs["board_cells"] == 1280 * 128


def test_tiled_span_counts_the_window(make_board, monkeypatch, traced):
    """An advance through the fused tiled kernel carries the cells one
    fused step computes (``window_cells``) beside the frame it writes:
    1024² on one device, tiled at a budget that gives two full-width
    row tiles of 16 words, steps 24/16 of its cells."""
    budget = 4 * (16 + 2 * bitlife._FUSE_HALO_WORDS) * 1024
    monkeypatch.setattr(bitlife, "plan_sharded_bits",
                        partial(bitlife.plan_sharded_bits, budget=budget))
    board = make_board(1024, 1024)
    sim = LifeSim(config_from_board(board, 130, 0), layout="row",
                  impl="bitfused", mesh=mesh_lib.make_mesh_1d(1, axis="y"))
    assert sim._plan.mode == "tiled"
    np.testing.assert_array_equal(sim.run(), oracle_n(board, 130))
    (span,) = spans_of(traced)
    attrs = span["attrs"]
    assert attrs["frame_cells"] == attrs["board_cells"] == 1024 * 1024
    assert attrs["window_cells"] == bitlife.fused_window_cells(
        32, 1024, 0, budget)
    assert attrs["window_cells"] / attrs["frame_cells"] == 1.5
    assert not {"rounds", "halo_bytes"} & set(attrs)  # no exchange


def test_serial_fused_advance_counts_the_window(make_board, monkeypatch):
    """The one-chip path (``impl="pallas"``) dispatches a 4096² board to
    the aligned fused runner on the chip: its spans carry that runner's
    window at the default tile budget (steered onto the chip's branch;
    nothing runs)."""
    from mpi_and_open_mp_tpu.ops import pallas_life

    monkeypatch.setattr(pallas_life, "_interpret", lambda: False)
    sim = LifeSim(config_from_board(make_board(4096, 4096), 10, 0),
                  layout="serial", impl="pallas")
    attrs = sim._step_attrs(10)
    assert attrs == bitlife.fused_tile_cells((4096, 4096))
    assert attrs["frame_cells"] == 4096 * 4096
    assert attrs["window_cells"] > attrs["frame_cells"]
