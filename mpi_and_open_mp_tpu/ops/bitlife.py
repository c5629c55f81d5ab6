"""Bit-packed Life kernels: 32 cells per uint32 lane, bitwise rule.

The reference's compute kernel spends ~12 arithmetic ops per cell on the
8-neighbour count (``/root/reference/3-life/life2d.c:104-130``). On a TPU
VPU the state is 1 bit, so the idiomatic kernel packs 32 cells into each
uint32 **along y** (the sublane axis) and evaluates the rule with bitwise
carry-save adders — ~35 vector ops per 32 cells ≈ 1.1 ops/cell, and 32x
less VMEM/HBM traffic than an int32 board. This is the framework's fast
path for single-shard boards; it is bit-exact against the NumPy oracle
(tests/test_bitlife.py exercises odd sizes, gliders, and random soups).

Packed layout ("offset-ghost"): bit position ``p`` of the packed column
holds board row ``y = p - 1``; position ``0`` mirrors row ``ny-1`` and
position ``ny+1`` mirrors row ``0`` (the torus ghosts). Each step first
refreshes the two ghost bits from live state, then

* y-neighbours are single-bit shifts across the packed words (cross-word
  carries via a sublane roll),
* x-neighbours are lane rolls with the exact ``nx`` wrap (no padding in x),
* the 8-neighbour count ``N`` is built as 2-bit column sums combined by
  full adders into a mod-8 count (N==8 wraps to 0 and correctly dies —
  see ``_carry_save_rule``), and the rule is ``(n0|alive) & n1 & ~n2``
  (birth-on-3 / survive-on-2-or-3, ``life2d.c:117-123``).

The whole step loop runs inside one ``pallas_call`` with the packed board
VMEM-resident; a 500x500 board packs to 16x500 uint32 = 32 KB. The gate
is the packed bytes times the ~11 live step temporaries against the
~16 MB/core scoped-VMEM budget (see ``_PACKED_VMEM_LIMIT``): ~3200² is
the measured ceiling. Beyond it, aligned boards run the multi-step-fused
tiled kernel (:func:`life_run_fused_bits` — one HBM pass per up-to-128
steps) and anything else the compiled-XLA packed loop
(:func:`life_run_bits_xla`). The tiled kernel holds one halo-extended
tile window, not the board, so its tiles have a budget of their own
(``_FUSED_TILE_BUDGET``) under a scoped-VMEM limit it raises to match.
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Packed board bytes kept VMEM-resident. The step body holds ~10 live
# same-shape temporaries, so the working set is ~11x the board against the
# ~16 MB/core scoped-VMEM budget; measured on v5e: 1.23 MB packed (3200²)
# compiles, 1.47 MB (3500²) is rejected by Mosaic.
_PACKED_VMEM_LIMIT = 5 << 18

# Bytes of the fused tiled kernel's halo-extended tile window (its
# ``tile_budget_bytes`` default), apart from the resident gate above:
# the tiled kernel holds one window, not a board, so its tiles grow to
# what the core's VMEM holds under the scoped limit the kernel raises
# (``_fused_vmem_limit``; a v5e core has 128 MiB). 17 << 18 (4.25 MiB)
# is one 136 x 8192-word window: an 8192² board steps full-width row
# tiles of 128 words, (128+8)/128 = 1.0625 of its cells a step, where
# the resident budget allowed the column plan's 1.1953.
_FUSED_TILE_BUDGET = 17 << 18


def n_words(ny: int) -> int:
    """Packed sublane words for ``ny`` rows plus the two ghost positions."""
    return (ny + 2 + 31) // 32


def fits_vmem_packed(shape: tuple[int, int]) -> bool:
    ny, nx = shape
    nxp = -(-nx // 128) * 128  # lane padding (see life_run_vmem_bits)
    return n_words(ny) * nxp * 4 <= _PACKED_VMEM_LIMIT


def fits_vmem_packed_batch(shape: tuple[int, int, int]) -> bool:
    """Whether a WHOLE (B, ny, nx) stack fits the packed VMEM budget at
    once — the batched twin of :func:`fits_vmem_packed`, with the working
    set scaled by B (the batched step holds the same ~11 live temporaries,
    each now B boards deep). Stacks past this gate but whose single board
    still fits stream through a grid over the batch axis instead (one
    board resident per program — see :func:`life_run_vmem_bits_batch`)."""
    b, ny, nx = shape
    nxp = -(-nx // 128) * 128
    return b * n_words(ny) * nxp * 4 <= _PACKED_VMEM_LIMIT


def pack_board(board: jnp.ndarray) -> jnp.ndarray:
    """(ny, nx) 0/1 ints -> (n_words(ny), nx) uint32, offset-ghost layout.

    Ghost bits are left zero; the kernel refreshes them at the top of every
    step, so they never need to be materialised here.
    """
    ny, nx = board.shape
    nw = n_words(ny)
    rows = jnp.zeros((nw * 32, nx), dtype=jnp.uint32)
    rows = rows.at[1 : ny + 1, :].set(board.astype(jnp.uint32))
    shifts = jnp.arange(32, dtype=jnp.uint32)[None, :, None]
    return (rows.reshape(nw, 32, nx) << shifts).sum(
        axis=1, dtype=jnp.uint32
    )


def unpack_board(packed: jnp.ndarray, ny: int) -> jnp.ndarray:
    """Inverse of :func:`pack_board`; returns (ny, nx) uint8."""
    nw, nx = packed.shape
    shifts = jnp.arange(32, dtype=jnp.uint32)[None, :, None]
    rows = ((packed[:, None, :] >> shifts) & jnp.uint32(1)).reshape(
        nw * 32, nx
    )
    return rows[1 : ny + 1, :].astype(jnp.uint8)


def _set_word_row(p: jnp.ndarray, w: int, row: jnp.ndarray) -> jnp.ndarray:
    """Replace word-row ``w`` of ``p`` (static index) via concatenation.

    ``p.at[w:w+1].set`` is avoided: when the slice covers a whole axis, its
    lowering closes over an empty i32 array, which ``pallas_call`` rejects
    as a captured constant.
    """
    parts = []
    if w > 0:
        parts.append(p[:w, :])
    parts.append(row)
    if w + 1 < p.shape[0]:
        parts.append(p[w + 1 :, :])
    return jnp.concatenate(parts, axis=0) if len(parts) > 1 else row


def _refresh_ghosts(p: jnp.ndarray, ny: int) -> jnp.ndarray:
    """Rewrite the two torus ghost bits from live board state.

    Position 0 := position ny (board row ny-1); position ny+1 := position 1
    (board row 0). Static word/bit indices — ``ny`` is a trace-time const.
    """
    # np.uint32 literals throughout: concrete jnp scalars would be captured
    # as pallas kernel constants (rejected), and Python ints above 2^31
    # overflow the weak-int32 promotion path.
    w_lo, b_lo = divmod(ny, 32)  # source bit for ghost position 0
    src = (p[w_lo : w_lo + 1, :] >> b_lo) & 1
    p = _set_word_row(p, 0, (p[0:1, :] & np.uint32(0xFFFFFFFE)) | src)
    w_hi, b_hi = divmod(ny + 1, 32)  # target word/bit for ghost top
    src = (p[0:1, :] >> 1) & 1  # position 1 = board row 0
    new_hi = (
        p[w_hi : w_hi + 1, :] & np.uint32(0xFFFFFFFF ^ (1 << b_hi))
    ) | (src << b_hi)
    return _set_word_row(p, w_hi, new_hi)


def _roll_sub(p: jnp.ndarray, shift: int) -> jnp.ndarray:
    nw = p.shape[0]
    if nw == 1:
        return p
    return pltpu.roll(p, shift % nw, 0)


def _carry_save_rule(c, up, dn, roll_left, roll_right) -> jnp.ndarray:
    """The bitwise Life rule given centre/up/down bit columns.

    ``roll_left(x)``/``roll_right(x)`` supply each lane its left/right
    torus neighbour — plain rolls when the array width IS the board
    width, rolls + wrap-column fixup on the lane-padded fast path.

    Counts the 8 NEIGHBOURS ``N`` (centre excluded), mod 8 — the bit-3
    carry only fires at N == 8, which wraps to 000 and correctly dies.
    Excluding the centre is what makes the rule term cheap: birth-on-3 /
    survive-on-2-or-3 becomes ``(n0 | alive) & n1 & ~n2`` (N==3 sets it
    regardless of ``alive``; N==2 needs ``alive`` to supply bit 0), four
    ops versus eight for the centre-included ``T==3 | (alive & T==4)``
    form — ~24 logicals per 32 cells all told. The neighbour columns
    still contribute their full 3-cell sums (``ys``), whose half-adder
    prefix is the centre column's 2-cell sum (``cs``) — shared, so both
    cost 5 ops together. Bit-exactness is pinned by the three-oracle
    parity suite (rule spec ``3-life/life2d.c:104-130``).
    """
    # Column sums: cs = up+dn (centre column, centre EXCLUDED) and
    # ys = up+c+dn (what this column contributes as a NEIGHBOUR column).
    cs0 = up ^ dn
    cs1 = up & dn
    ys0 = cs0 ^ c
    ys1 = cs1 | (cs0 & c)
    # x-neighbours.
    l0 = roll_left(ys0)
    r0 = roll_right(ys0)
    l1 = roll_left(ys1)
    r1 = roll_right(ys1)
    # P = L + R (two 2-bit sums -> 3 bits).
    p0 = l0 ^ r0
    q0 = l0 & r0
    p1x = l1 ^ r1
    p1 = p1x ^ q0
    p2 = (l1 & r1) | (p1x & q0)
    # N = P + cs, bits (n2, n1, n0) = N mod 8.
    n0 = p0 ^ cs0
    rc = p0 & cs0
    n1x = p1 ^ cs1
    n1 = n1x ^ rc
    n2 = p2 ^ ((p1 & cs1) | (n1x & rc))
    # alive' = (N == 3) | (alive & N == 2).
    return (n0 | c) & n1 & ~n2


def _lane_rolls(shape: tuple[int, int], nx: int):
    """``(roll_left, roll_right)`` lane-neighbour rolls with the torus
    wrap at column ``nx``. When the array is wider than ``nx`` (lane
    padding) the two wrap columns are patched explicitly: lane 0's true
    left neighbour is column ``nx-1`` (the roll would hand it a slack
    column), and lane ``nx-1``'s right neighbour is column 0 — slack
    columns carry junk that never feeds a valid column."""
    nxp = shape[1]
    if nxp == nx:
        return (
            lambda x: pltpu.roll(x, 1, 1),
            lambda x: pltpu.roll(x, nx - 1, 1),
        )
    lane = lax.broadcasted_iota(jnp.int32, shape, 1)

    def roll_left(x):
        return jnp.where(lane == 0, x[:, nx - 1 : nx], pltpu.roll(x, 1, 1))

    def roll_right(x):
        return jnp.where(
            lane == nx - 1, x[:, 0:1], pltpu.roll(x, nxp - 1, 1)
        )

    return roll_left, roll_right


def bit_step(p: jnp.ndarray, ny: int, nx: int) -> jnp.ndarray:
    """One Life step on a packed board (ghost refresh + bitwise rule).

    ``p`` may be lane-padded (``p.shape[1] > nx``): Mosaic lane rolls at
    a non-128-multiple width cost ~3.4x (measured 401 vs 1376 Gcups at
    500² vs 512² on v5e), so the runner pads the board to the next lane
    multiple and the wrap columns are patched (see :func:`_lane_rolls`).
    """
    p = _refresh_ghosts(p, ny)
    nw = p.shape[0]
    # y-neighbours: single-bit shifts through the packed words. The junk
    # carried into ghost/slack positions never reaches a live bit.
    dn = (p << 1) | (_roll_sub(p, 1) >> 31)
    up = (p >> 1) | (_roll_sub(p, nw - 1) << 31)
    return _carry_save_rule(p, up, dn, *_lane_rolls(p.shape, nx))


def _vmem_bits_kernel(steps_ref, p_ref, out_ref, *, ny: int, nx: int):
    out_ref[:] = lax.fori_loop(
        0, steps_ref[0], lambda _, p: bit_step(p, ny, nx), p_ref[:]
    )


@functools.partial(jax.jit, static_argnames=("ny", "nx", "interpret"))
def _run_vmem_bits_jit(packed, steps, *, ny: int, nx: int, interpret: bool):
    return pl.pallas_call(
        functools.partial(_vmem_bits_kernel, ny=ny, nx=nx),
        out_shape=jax.ShapeDtypeStruct(packed.shape, packed.dtype),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        interpret=interpret,
        name="life_vmem_bits",
    )(steps, packed)


def life_run_vmem_bits(
    board: jnp.ndarray, n: int, *, interpret: bool = False
) -> jnp.ndarray:
    """Advance ``n`` steps with the packed VMEM-resident loop kernel.

    The board is lane-padded to the next multiple of 128 columns before
    packing (see :func:`bit_step` — unaligned lane rolls cost ~3.4x);
    pack/unpack are plain XLA ops fused around the single kernel launch;
    ``n`` is a runtime SMEM scalar (no recompile when it changes).
    """
    ny, nx = board.shape
    dtype = board.dtype
    nxp = -(-nx // 128) * 128
    if nxp != nx:
        board = jnp.pad(board, ((0, 0), (0, nxp - nx)))
    packed = pack_board(board)
    steps = jnp.asarray([n], dtype=jnp.int32)
    out = _run_vmem_bits_jit(packed, steps, ny=ny, nx=nx, interpret=interpret)
    return unpack_board(out, ny)[:, :nx].astype(dtype)


# ------------------------------------------- big boards (fused tiled Pallas)


def pack_board_exact(board: jnp.ndarray) -> jnp.ndarray:
    """(ny, nx) 0/1 ints -> (ny/32, nx) uint32, NO ghost offset.

    Bit ``b`` of word row ``w`` holds board row ``32*w + b``. Requires
    ``ny % 32 == 0``, which makes the torus wrap word-aligned — the fused
    tiled kernel's halo is then plain word rows copied from the opposite
    board edge, no ghost-bit bookkeeping at all.
    """
    ny, nx = board.shape
    assert ny % 32 == 0, ny
    rows = board.astype(jnp.uint32).reshape(ny // 32, 32, nx)
    shifts = jnp.arange(32, dtype=jnp.uint32)[None, :, None]
    return (rows << shifts).sum(axis=1, dtype=jnp.uint32)


def unpack_board_exact(packed: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`pack_board_exact`; returns (ny, nx) uint8."""
    nw, nx = packed.shape
    shifts = jnp.arange(32, dtype=jnp.uint32)[None, :, None]
    rows = ((packed[:, None, :] >> shifts) & jnp.uint32(1)).reshape(
        nw * 32, nx
    )
    return rows.astype(jnp.uint8)


# Halo word rows DMA'd on each side of a tile: 4 words = 128 bit rows of
# valid neighbour state, so up to 128 steps can run on one tile window
# before the outside-in junk front reaches the tile interior (validity
# shrinks 1 bit row per step per side). Also keeps DMA extents 8-aligned
# (tr % 8 == 0 and 2*H == 8).
_FUSE_HALO_WORDS = 4
FUSE_MAX_STEPS = 32 * _FUSE_HALO_WORDS


def _fused_window_step(
    w: jnp.ndarray, nx: int, nx_exact: int | None = None
) -> jnp.ndarray:
    """One Life step over a full tile window (no ghost refresh: y-wrap
    content is real halo rows; the sublane-roll junk entering the two
    outermost bit rows is tracked by the validity argument above).

    ``nx_exact`` set (and < ``nx``) means the window is a lane-padded
    board whose torus wrap must land on the logical column: the lane
    rolls get the same wrap-column patch as :func:`bit_step`, the pad
    columns carry junk that never feeds a valid column, and no x halo
    or validity tracking is needed in the lane dimension at all.
    """
    dn = (w << 1) | (_roll_sub(w, 1) >> 31)
    up = (w >> 1) | (_roll_sub(w, w.shape[0] - 1) << 31)
    wrap = nx if nx_exact is None else nx_exact
    return _carry_save_rule(w, up, dn, *_lane_rolls(w.shape, wrap))


def _fused_tiles_kernel(
    k_ref, hbm_ref, out_ref, scratch, sem, *, tr: int, hx: int = 0,
    cx: int | None = None, nx_exact: int | None = None,
):
    """One program = one (tr, cx-or-full-width) output tile, ``k_ref[0]``
    fused steps.

    DMAs the tile plus ``_FUSE_HALO_WORDS`` halo word rows per side from
    the wrap-extended board, steps the whole window k times in VMEM, and
    writes back only the (still-valid) interior — one HBM read+write pass
    per k steps instead of per step. ``hx`` > 0 means the input carries
    ``hx`` halo columns per side (from an x wrap or a cart-mesh ppermute;
    corner cells arrive via the y-exchange of the x-extended slab) and
    the output slices them off. ``cx`` additionally tiles columns on a
    2-D grid — each program's window is its column range plus the same
    ``hx`` border, read from the extended input at a 128-aligned offset.
    All lane offsets/extents stay 128-aligned, so the value-level x slice
    is vreg-clean.
    """
    i = pl.program_id(0)
    h = _FUSE_HALO_WORDS
    if cx is None:
        w_ext = hbm_ref.shape[1]
        src = hbm_ref.at[pl.ds(i * tr, tr + 2 * h)]
    else:
        j = pl.program_id(1)
        w_ext = cx + 2 * hx
        src = hbm_ref.at[pl.ds(i * tr, tr + 2 * h), pl.ds(j * cx, w_ext)]
    cp = pltpu.make_async_copy(src, scratch, sem)
    cp.start()
    cp.wait()
    w = lax.fori_loop(
        0, k_ref[0],
        lambda _, x: _fused_window_step(x, w_ext, nx_exact), scratch[:]
    )
    out_ref[:] = w[h : h + tr, hx : w_ext - hx]


def _fused_tile_words(
    nw: int, nx: int, tile_budget_bytes: int = _FUSED_TILE_BUDGET
) -> int:
    """Tile word rows: the largest multiple-of-8 divisor of ``nw`` whose
    halo-extended window fits the tile budget (``_FUSED_TILE_BUDGET``).
    0 = no legal split. ``tile_budget_bytes`` exists so tests can force
    multi-tile grids (and their DMA seams) at small shapes."""
    cap = tile_budget_bytes // (4 * nx) - 2 * _FUSE_HALO_WORDS
    best = 0
    for d in range(8, min(cap, nw) + 1, 8):
        if nw % d == 0:
            best = d
    return best


def fused_bits_supported(shape: tuple[int, int]) -> bool:
    """Whether the fused tiled kernel can run ``shape`` compiled: word-
    aligned torus (ny % 32), 128-aligned lane dim (explicit-DMA scratch),
    and a legal tile split — full-width row tiles or the column-tiled
    plan (which also covers ultra-wide boards)."""
    ny, nx = shape
    if ny % 32 or nx % 128:
        return False
    nw = ny // 32
    return _fused_tile_words(nw, nx) >= 8 or _col_tile_plan(nw, nx) is not None


# Column halo for the 2-D (cart) fused path: 128 lanes = 128 cell columns
# per side, matching FUSE_MAX_STEPS (x junk marches 1 column per step).
_FUSE_HALO_X = 128


def _col_tile_plan(
    nw: int, nxl: int, tile_budget_bytes: int = _FUSED_TILE_BUDGET
):
    """Best ``(amplification, tr, cx)`` column-tiling plan for an ext
    carrying ``_FUSE_HALO_X`` borders, or None. Amplification = redundant
    window area per output area = (tr+2H)/tr * (cx+2HX)/cx; wide boards
    prefer narrower column tiles (taller row tiles fit the tile budget),
    e.g. a 16384-wide board steps 1.129x in 128 x 4096 tiles where
    full-width tiles of 32 words would step 1.25x."""
    best = None
    for cx in range(128, nxl + 1, 128):
        if nxl % cx:
            continue
        w_ext = cx + 2 * _FUSE_HALO_X
        tr = _fused_tile_words(nw, w_ext, tile_budget_bytes)
        if tr < 8:
            continue
        amp = (tr + 2 * _FUSE_HALO_WORDS) / tr * (w_ext / cx)
        if best is None or amp < best[0] - 1e-9:
            best = (amp, tr, cx)
    return best


def _fused_tile_grid(
    nw: int, nxl: int, halo_x: int, tile_budget_bytes: int,
) -> tuple[int, int]:
    """``(tr, cx)`` of :func:`make_fused_stepper`'s grid: full-width row
    tiles (``cx = nxl``) without an x border, the column plan with one.
    Raises where no split fits the budget."""
    if halo_x:
        plan = _col_tile_plan(nw, nxl, tile_budget_bytes)
        if plan is None:
            raise ValueError(
                f"no legal fused tile split for extended shape "
                f"{(nw, nxl + 2 * halo_x)}; gate callers on "
                "fused_bits_supported() / plan_sharded_bits()"
            )
        return plan[1], plan[2]
    tr = _fused_tile_words(nw, nxl, tile_budget_bytes)
    if tr < 8:
        raise ValueError(
            f"no legal fused tile split for packed shape {(nw, nxl)}; "
            "gate callers on fused_bits_supported()"
        )
    return tr, nxl


def fused_window_cells(
    nw: int, nxl: int, halo_x: int = 0,
    tile_budget_bytes: int = _FUSED_TILE_BUDGET,
) -> int:
    """Cells one fused step of :func:`make_fused_stepper` computes over
    its whole grid: programs x 32·(tr + 2H) bit rows x the window's
    ``cx + 2·halo_x`` columns. Over the ``32·nw x nxl`` cells it writes,
    that is the tile plan's amplification; host arithmetic only."""
    tr, cx = _fused_tile_grid(nw, nxl, halo_x, tile_budget_bytes)
    return ((nw // tr) * (nxl // cx)
            * 32 * (tr + 2 * _FUSE_HALO_WORDS) * (cx + 2 * halo_x))


def _fused_vmem_limit(window_bytes: int) -> int:
    """Scoped-VMEM limit of one ``life_fused_tiles`` program: its window
    scratch, the ~11 same-shape temporaries of the step body and a
    double-buffered output block (at most a window each), 14 windows in
    all; never below the 16 MiB default, well under a v5e core's 128.
    For a 4.25 MiB window the v5e compiler asks 34.5 MB."""
    return max(16 << 20, 14 * window_bytes)


def make_fused_stepper(
    nw: int,
    nxl: int,
    *,
    interpret: bool,
    tile_budget_bytes: int = _FUSED_TILE_BUDGET,
    halo_x: int = 0,
    nx_exact: int | None = None,
):
    """Build ``step_call(k, ext) -> (nw, nxl)``: the fused tiled kernel
    over a wrap-extended ``(nw + 2*_FUSE_HALO_WORDS, nxl + 2*halo_x)``
    packed board, running ``k[0]`` fused steps. Shared by the serial
    big-board runner, the row-sharded ring path (``halo_x=0``; halo rows
    arrive by ``ppermute`` instead of a local wrap concat), and the x-
    extended paths (``halo_x=_FUSE_HALO_X``: cart-mesh shards and wide
    serial boards), which additionally column-tile on a 2-D grid when
    that lowers the redundant-window amplification."""
    h = _FUSE_HALO_WORDS
    assert not (halo_x and nx_exact is not None), (
        "wrap-patched rolls need the full width")
    tr, cx = _fused_tile_grid(nw, nxl, halo_x, tile_budget_bytes)
    window = (tr + 2 * h, cx + 2 * halo_x)
    if halo_x:
        grid = (nw // tr, nxl // cx)
        kernel = functools.partial(
            _fused_tiles_kernel, tr=tr, hx=halo_x, cx=cx)
        out_block = pl.BlockSpec(
            (tr, cx), lambda i, j: (i, j), memory_space=pltpu.VMEM)
    else:
        grid = (nw // tr,)
        kernel = functools.partial(
            _fused_tiles_kernel, tr=tr, nx_exact=nx_exact)
        out_block = pl.BlockSpec(
            (tr, nxl), lambda i: (i, 0), memory_space=pltpu.VMEM)
    return pl.pallas_call(
        kernel,
        grid=grid,
        out_shape=jax.ShapeDtypeStruct((nw, nxl), jnp.uint32),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=out_block,
        scratch_shapes=[
            pltpu.VMEM(window, jnp.uint32),
            pltpu.SemaphoreType.DMA(()),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_fused_vmem_limit(4 * window[0] * window[1])),
        interpret=interpret,
        name="life_fused_tiles",
    )


def wrap_y(p: jnp.ndarray, h: int = _FUSE_HALO_WORDS) -> jnp.ndarray:
    """Extend a packed board with ``h`` torus-wrap word rows per side —
    the local (single-shard / unsharded-axis) form of the fused kernel's
    y halo. Sharded axes get the same rows via ``ppermute`` instead
    (``halo.halo_pad_y``); both must honour ``_FUSE_HALO_WORDS``."""
    return jnp.concatenate([p[-h:], p, p[:h]], axis=0)


# ------------------------------------- arbitrary shapes (padded torus frame)
#
# The fused kernels above want word-aligned rows and lane-aligned columns.
# To run ANY board (the reference's flagship is 500x500 —
# ``3-life/p46gun_big.cfg:3``) on any mesh, the board is stored in a FRAME
# padded up to (32*py)-row / lane-pitch-column alignment, kept consistent
# with the infinite periodic tiling of the logical board:
#
# * frame rows   [ny, Nyp)  mirror board rows    [0, pad_y)
# * frame cols   [nx, Nxp)  mirror board columns [0, pad_x)  (sharded x)
#
# A window whose content agrees with the periodic tiling evolves every
# cell — mirrors included — exactly as the torus does, so the mirrors
# self-maintain across fused rounds; they are still refreshed from the
# authoritative shard each round (cheap, and fixes the zero-padded initial
# state). The wrap halos are then *unaligned* row/column ranges of the
# frame, extracted with funnel shifts (:func:`take_rows`) outside the
# kernel — the kernel itself never learns the board was unaligned. For
# unsharded x the mirror machinery is unnecessary: the wrap-column-patched
# rolls of :func:`bit_step` (``nx_exact``) give an exact x torus at any
# width.


def take_rows(words: jnp.ndarray, start: int, h: int) -> jnp.ndarray:
    """Bit rows ``[start, start + 32*h)`` of a packed word stack.

    ``start`` is a static bit-row offset. Word-aligned offsets are plain
    slices; anything else funnels each output word from two neighbouring
    input words — the packed-layout form of an unaligned row slice.
    """
    q, b = divmod(start, 32)
    if b == 0:
        return words[q : q + h]
    return (words[q : q + h] >> b) | (words[q + 1 : q + h + 1] << (32 - b))


def mirror_tail(e: jnp.ndarray, src: jnp.ndarray, pad: int) -> jnp.ndarray:
    """Rewrite the last ``pad`` bit rows of frame shard ``e`` with rows
    ``[0, pad)`` of ``src`` — the periodic-mirror refresh: frame rows
    ``[ny, Nyp)`` must copy board rows ``[0, pad_y)`` so every window cut
    from the frame agrees with the torus tiling. ``src`` must carry at
    least ``pad + 32`` bit rows starting at board row 0."""
    nw = e.shape[0]
    q, b = divmod(pad, 32)
    parts = [e[: nw - q - (1 if b else 0)]]
    if b:
        keep = np.uint32((1 << (32 - b)) - 1)
        parts.append(((e[nw - 1 - q] & keep) | (src[0] << (32 - b)))[None])
    if q:
        parts.append(take_rows(src, b, q))
    return jnp.concatenate(parts, axis=0) if len(parts) > 1 else parts[0]


def wrap_y_padded(e: jnp.ndarray, ny: int, h: int) -> jnp.ndarray:
    """Local y-extension of a packed frame taller than the board: refresh
    the mirror rows, then append funnel-shifted torus borders — the
    unaligned generalisation of :func:`wrap_y` (its exact degenerate).
    Callers must honour the :func:`plan_sharded_bits` gate
    ``h + 1 + pad//32 <= nw``."""
    nw = e.shape[0]
    pad = 32 * nw - ny
    if pad == 0:
        return wrap_y(e, h)
    s = h + 1 + pad // 32
    # Top border = board rows [ny - 32h, ny): real rows only — the funnel
    # stops one bit short of the mirror region (checked in tests).
    top = take_rows(e[-s:], 32 * s - pad - 32 * h, h)
    bot = take_rows(e[:s], pad, h)
    e = mirror_tail(e, e[:s], pad)
    return jnp.concatenate([top, e, bot], axis=0)


def make_window_stepper(
    nw: int,
    nxl: int,
    *,
    h: int,
    halo_x: int = 0,
    nx_exact: int | None = None,
    interpret: bool = False,
):
    """Whole-shard fused stepper: the halo-extended window VMEM-resident
    in a single program, ``k_ref[0]`` fused steps, interior write-back.

    The small-shard counterpart of :func:`make_fused_stepper` (whose DMA
    tiles need >=8 word rows): a 500x500 board over an 8-way ring packs
    to 2-word slabs, far below any legal tile split, but the whole
    halo-extended window is then a few KB — exactly the VMEM-resident
    regime. Same calling convention as the tiled stepper.
    """
    # Wrap-patched rolls assume board column 0 sits at lane 0 — an x
    # border would shift it to lane halo_x and silently corrupt the wrap.
    assert halo_x == 0 or nx_exact is None, (
        "wrap-patched rolls need the unextended board width"
    )
    w_ext = nxl + 2 * halo_x

    def kernel(k_ref, ext_ref, out_ref):
        w = lax.fori_loop(
            0, k_ref[0],
            lambda _, x: _fused_window_step(x, w_ext, nx_exact),
            ext_ref[:],
        )
        out_ref[:] = w[h : h + nw, halo_x : halo_x + nxl]

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((nw, nxl), jnp.uint32),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        interpret=interpret,
        name="life_fused_window",
    )


@dataclasses.dataclass(frozen=True)
class BitPlan:
    """How to run one board/mesh combination through the packed fused
    path: frame padding, halo depths, fuse budget, and stepper kind.
    Produced by :func:`plan_sharded_bits`; consumed by
    :func:`make_plan_stepper` and the model layer's exchange loop."""

    shape: tuple[int, int]   # logical (ny, nx)
    py: int
    px: int
    y_sharded: bool
    x_sharded: bool
    frame: tuple[int, int]   # padded (Nyp, Nxp) — the stored board shape
    pad_y: int
    pad_x: int
    nw_s: int                # packed word rows per shard
    W: int                   # columns per shard
    h: int                   # y halo words per side
    hx: int                  # x halo columns per side (0 = no x border)
    nx_exact: int | None     # wrap-patched roll width (unsharded pad_x>0)
    k_max: int               # fused steps per exchange round
    mode: str                # "window" | "tiled"
    budget: int              # VMEM budget the mode choice was validated at
    tile_budget: int         # the tiled stepper's tile budget


def plan_sharded_bits(
    shape: tuple[int, int],
    py: int,
    px: int,
    y_sharded: bool,
    x_sharded: bool,
    budget: int = _PACKED_VMEM_LIMIT,
) -> BitPlan | None:
    """Plan the packed fused path for ANY board over a ``(py, px)`` mesh.

    Returns None only when the geometry is genuinely hopeless for halo
    fusion (a shard too small to carry even a 1-word halo next to its
    padding, or a window/tile split that fits no VMEM budget) — callers
    then fall back to the unpacked halo/roll impls. Covers the
    reference's per-step ghost exchange (``3-life/life_mpi.c:198-209``)
    amortised ``k_max``-fold for every shape, not just aligned ones.

    ``budget`` decides the frame and the stepper kind; a tiled plan's
    stepper then tiles at ``_FUSED_TILE_BUDGET``, whose tiles hold at
    least those ``budget`` allows. A ``budget`` below the resident gate's
    (tests forcing small shapes) caps the tiles too.
    """
    tile_budget = (_FUSED_TILE_BUDGET if budget >= _PACKED_VMEM_LIMIT
                   else budget)
    ny, nx = shape
    if ny < 8 or nx < 8:
        return None
    # ---- x axis: lane pitch, pad, halo columns.
    if x_sharded:
        nx_exact = None
        W = -(-nx // (128 * px)) * 128
        pad_x = W * px - nx
        hx = _FUSE_HALO_X
        if W - pad_x < hx:
            # Narrow shards can't feed a full 128-column halo: re-pitch at
            # 8-column granularity (unaligned lane rolls cost ~3.4x but
            # the fused path still wins) and shrink the halo — and with
            # it k_max — to what a neighbour can supply.
            W = -(-nx // (8 * px)) * 8
            pad_x = W * px - nx
            hx = min(_FUSE_HALO_X, W - pad_x)
            if hx < 8:
                return None
    else:
        W = -(-nx // 128) * 128
        pad_x = W - nx
        hx = 0
        nx_exact = nx if pad_x else None
    # ---- y axis: word pitch, pad, halo words, stepper kind. Two pitch
    # attempts: the minimal 1-word (32-row) granularity first, then
    # 8-word granularity — the tiled kernel needs a split tr | nw_s with
    # tr % 8 == 0, which a prime/odd word count can never supply (e.g.
    # 10000 rows -> 313 words), but the frame is OURS to choose: padding
    # to an 8-word multiple guarantees a split at the cost of up to 255
    # extra mirror rows per shard.
    for words_pitch in (1, 8):
        nw_s = -(-ny // (32 * words_pitch * py)) * words_pitch
        pad_y = 32 * nw_s * py - ny
        if pad_y:
            # Wrap funnels read h+1+pad_y//32 words from the neighbour;
            # the shard must hold them (and the wrap-border source rows).
            h = min(_FUSE_HALO_WORDS, nw_s - 1 - pad_y // 32)
        else:
            h = min(_FUSE_HALO_WORDS, nw_s)
        if h < 1:
            continue
        # Stepper kind: whole-window VMEM program when it fits, else the
        # DMA-tiled kernel (needs full-depth halos and lane alignment).
        if (nw_s + 2 * h) * (W + 2 * hx) * 4 <= budget:
            mode = "window"
        elif h == _FUSE_HALO_WORDS and W % 128 == 0:
            if hx:
                if (hx != _FUSE_HALO_X
                        or _col_tile_plan(nw_s, W, budget) is None):
                    continue
            elif _fused_tile_words(nw_s, W, budget) < 8:
                continue
            mode = "tiled"
        else:
            continue
        return BitPlan(
            shape=shape, py=py, px=px,
            y_sharded=y_sharded, x_sharded=x_sharded,
            frame=(32 * nw_s * py, W * px), pad_y=pad_y, pad_x=pad_x,
            nw_s=nw_s, W=W, h=h, hx=hx, nx_exact=nx_exact,
            k_max=min(32 * h, hx or FUSE_MAX_STEPS, FUSE_MAX_STEPS),
            mode=mode, budget=budget, tile_budget=tile_budget,
        )
    return None


def local_wrap_y(plan: BitPlan, q: jnp.ndarray) -> jnp.ndarray:
    """The plan's LOCAL (unsharded-y) torus extension: funnel wrap +
    mirror refresh when the frame is padded, plain word-row wrap when it
    is exact. Shared by the serial frame runner and the model layer's
    col-layout shard body — the unsharded twin of ``halo.packed_halo_y``."""
    if plan.pad_y:
        return wrap_y_padded(q, plan.shape[0], plan.h)
    return wrap_y(q, plan.h)


@functools.partial(
    jax.jit, static_argnames=("ny", "nx", "interpret", "budget")
)
def _run_frame_bits_jit(
    packed, steps, *, ny: int, nx: int, interpret: bool, budget: int
):
    plan = plan_sharded_bits((ny, nx), 1, 1, False, False, budget)
    step_call = make_plan_stepper(plan, interpret=interpret)

    def body(carry):
        q, rem = carry
        k = jnp.minimum(rem, plan.k_max)
        return step_call(k.reshape(1), local_wrap_y(plan, q)), rem - k

    out, _ = lax.while_loop(lambda c: c[1] > 0, body, (packed, steps[0]))
    return out


def life_run_frame_bits(
    board: jnp.ndarray, n: int, *, interpret: bool = False,
    budget: int = _PACKED_VMEM_LIMIT,
) -> jnp.ndarray:
    """Advance ``n`` steps of an UNALIGNED big board on one device via the
    padded torus frame: word-padded rows (periodic mirrors + funnel wrap
    borders, :func:`wrap_y_padded`) and lane-padded columns
    (wrap-patched rolls), stepped by the plan's window or tiled fused
    kernel — the single-device form of the sharded bitfused path, for
    shapes the aligned fused kernel rejects (``ny % 32``/``nx % 128``).
    Measured v5e @ 10000² (r05 bigboard re-record,
    ``results/life/bigboard_tpu.csv``): 66.5 µs/step = 1.50 Tcups
    steady — the any-shape path at scale, with a
    one-HBM-pass-per-128-steps traffic bound the XLA roll loop loses
    once its intermediates spill through HBM (653 vs 242 µs/step at
    16384², ``bit_step_xla`` docstring). An earlier r04 probe recorded
    "37.0 vs 32.6 µs/step" for frame-vs-XLA at this size; 32.6 µs/step
    at 10⁸ cells would be 3.1 Tcups — above the 2.24 peak of the whole
    curve — so that pair was a measurement error (un-differenced timing),
    and the r05 differenced re-record above replaces it. None of these
    numbers was taken on today's code. Gate callers on ``plan_sharded_bits(shape, 1, 1, False, False)``.
    """
    ny, nx = board.shape
    plan = plan_sharded_bits((ny, nx), 1, 1, False, False, budget)
    if plan is None:
        raise ValueError(
            f"no padded-frame plan for {board.shape}; gate callers on "
            "plan_sharded_bits()"
        )
    dtype = board.dtype
    frame = jnp.pad(
        board, ((0, plan.frame[0] - ny), (0, plan.frame[1] - nx))
    )
    packed = pack_board_exact(frame)
    steps = jnp.asarray([n], dtype=jnp.int32)
    out = _run_frame_bits_jit(
        packed, steps, ny=ny, nx=nx, interpret=interpret, budget=budget
    )
    return unpack_board_exact(out)[:ny, :nx].astype(dtype)


def make_plan_stepper(plan: BitPlan, *, interpret: bool = False):
    """``step_call(k, ext) -> (nw_s, W)`` for a :class:`BitPlan`: the
    whole-window VMEM program for small shards, the DMA-tiled kernel for
    large ones (tiled at the plan's ``tile_budget``). ``ext`` is the
    ``(nw_s + 2h, W + 2hx)`` halo-extended packed shard the model layer
    assembles each exchange round."""
    if plan.mode == "window":
        return make_window_stepper(
            plan.nw_s, plan.W, h=plan.h, halo_x=plan.hx,
            nx_exact=plan.nx_exact, interpret=interpret,
        )
    return make_fused_stepper(
        plan.nw_s, plan.W, interpret=interpret,
        tile_budget_bytes=plan.tile_budget,
        halo_x=plan.hx, nx_exact=plan.nx_exact,
    )


def plan_tile_cells(plan: BitPlan, overlap: bool = False) -> dict:
    """The counters of a stepping span of the plan's stepper, over all
    ``py·px`` shards: ``window_cells`` (what one fused step computes),
    ``frame_cells`` (the padded frame it writes) and ``board_cells``. A
    tiled plan computes :func:`fused_window_cells`; a window-mode plan
    its whole halo-extended shard, ``32·(nw_s + 2h)·(W + 2hx)`` cells,
    or, split for ``overlap`` (:func:`make_overlap_steppers`), the raw
    shard and two ``3h``-word edge windows."""
    if plan.mode == "window":
        words = plan.nw_s + (6 if overlap else 2) * plan.h
        window = 32 * words * (plan.W + 2 * plan.hx)
    else:
        window = fused_window_cells(plan.nw_s, plan.W, plan.hx,
                                    plan.tile_budget)
    return {"window_cells": plan.py * plan.px * window,
            "frame_cells": plan.frame[0] * plan.frame[1],
            "board_cells": plan.shape[0] * plan.shape[1]}


def plan_overlap_supported(plan: BitPlan) -> bool:
    """Whether the plan's geometry admits the interior/boundary overlap
    split (``parallel.haloplan``): window-mode row shards with an EXACT
    word frame. ``pad_y > 0`` frames exchange funnel-shifted unaligned
    ranges and refresh mirrors — a sequencing the split would have to
    replicate in every partition for no interior gain — and an x-sharded
    plan's y ghosts must ride AFTER the x exchange (corners), so both
    stay on the sequential schedule. ``nw_s > 2h`` keeps the interior
    partition non-empty; 1-shard meshes are the caller's degenerate
    gate (nothing to overlap)."""
    return (plan.mode == "window" and plan.y_sharded
            and not plan.x_sharded and plan.pad_y == 0
            and plan.nw_s > 2 * plan.h)


def make_overlap_steppers(plan: BitPlan, *, interpret: bool = False):
    """``(interior_call, edge_call)`` for the overlapped packed round —
    gate on :func:`plan_overlap_supported`.

    * ``interior_call(k, q) -> (nw_s - 2h, W)``: the RAW packed shard is
      its own window — the outer ``h`` words per side play the halo role
      — so word rows ``[h, nw_s - h)`` compute from purely local data
      while the ghost ``ppermute`` flies.
    * ``edge_call(k, ext3h) -> (h, W)``: a ``3h``-word extension
      (``concat([ghost, q[:2h]])`` / ``(q[-2h:], ghost)``) yields the
      edge partition once the ghost lands.

    Soundness is the window path's own argument: roll-wrap garbage
    enters a window edge and walks ONE bit row per fused step, and every
    valid output bit row sits ``32h >= k_max >= k`` rows from the
    nearest edge — in all three programs. The per-word carry-save ops
    are position-identical to the sequential window's, so the
    reassembled ``concat([edge, interior, edge])`` is bit-exact to
    ``make_plan_stepper``'s result (fuzzed in ``tests/test_haloplan.py``).
    One halo word carries 32 board rows: the overlap win multiplied by
    the packing density."""
    if not plan_overlap_supported(plan):
        raise ValueError(f"plan admits no overlap split: {plan}")
    interior = make_window_stepper(
        plan.nw_s - 2 * plan.h, plan.W, h=plan.h, halo_x=0,
        nx_exact=plan.nx_exact, interpret=interpret,
    )
    edge = make_window_stepper(
        plan.h, plan.W, h=plan.h, halo_x=0,
        nx_exact=plan.nx_exact, interpret=interpret,
    )
    return interior, edge


def serial_fused_halo_x(
    nw: int, nx: int, tile_budget_bytes: int = _FUSED_TILE_BUDGET
) -> int:
    """The serial runner's x border: 0 for full-width row tiles (wrap by
    lane roll), ``_FUSE_HALO_X`` for column tiles (x-wrap border + 2-D
    grid), whichever steps the fewer window cells."""
    tr_full = _fused_tile_words(nw, nx, tile_budget_bytes)
    amp_full = ((tr_full + 2 * _FUSE_HALO_WORDS) / tr_full if tr_full >= 8
                else float("inf"))
    plan = _col_tile_plan(nw, nx, tile_budget_bytes)
    return _FUSE_HALO_X if plan is not None and plan[0] < amp_full else 0


def fused_tile_cells(shape: tuple[int, int]) -> dict:
    """:func:`plan_tile_cells` of the serial aligned runner
    (:func:`life_run_fused_bits`) at its default budget: the board is
    its own frame."""
    ny, nx = shape
    nw = ny // 32
    window = fused_window_cells(nw, nx, serial_fused_halo_x(nw, nx))
    return {"window_cells": window, "frame_cells": ny * nx,
            "board_cells": ny * nx}


@functools.partial(
    jax.jit, static_argnames=("interpret", "tile_budget_bytes")
)
def _run_fused_bits_jit(
    packed, steps, *, interpret: bool,
    tile_budget_bytes: int = _FUSED_TILE_BUDGET,
):
    nw, nx = packed.shape
    h = _FUSE_HALO_WORDS
    halo_x = serial_fused_halo_x(nw, nx, tile_budget_bytes)
    step_call = make_fused_stepper(
        nw, nx, interpret=interpret, tile_budget_bytes=tile_budget_bytes,
        halo_x=halo_x,
    )

    def body(carry):
        p, rem = carry
        k = jnp.minimum(rem, FUSE_MAX_STEPS)
        if halo_x:
            p = jnp.concatenate([p[:, -halo_x:], p, p[:, :halo_x]], axis=1)
        ext = wrap_y(p, h)
        return step_call(k.reshape(1), ext), rem - k

    out, _ = lax.while_loop(
        lambda c: c[1] > 0, body, (packed, steps[0])
    )
    return out


def life_run_fused_bits(
    board: jnp.ndarray, n: int, *, interpret: bool = False,
    tile_budget_bytes: int = _FUSED_TILE_BUDGET,
) -> jnp.ndarray:
    """Advance ``n`` steps of a big board with the multi-step-fused tiled
    kernel: each HBM pass DMAs row tiles once (plus a 128-bit-row halo —
    nearly free in the packed layout) and runs up to ``FUSE_MAX_STEPS``
    steps tile-resident in VMEM. HBM traffic per step drops ~100x vs a
    step-per-pass kernel, which is what the big-board regime is bound by.
    """
    dtype = board.dtype
    packed = pack_board_exact(board)
    steps = jnp.asarray([n], dtype=jnp.int32)
    out = _run_fused_bits_jit(
        packed, steps, interpret=interpret,
        tile_budget_bytes=tile_budget_bytes,
    )
    return unpack_board_exact(out).astype(dtype)


# ----------------------------------------------------------- big boards (XLA)


def bit_step_xla(p: jnp.ndarray, ny: int, nx: int) -> jnp.ndarray:
    """One packed Life step as plain XLA ops (``jnp.roll`` shifts).

    The compiled-XLA twin of the Pallas :func:`bit_step`: same ghost
    refresh, same carry-save rule, lane rolls via ``jnp.roll``. No
    lane-alignment or tile-budget constraints at all, and competitive
    while the packed board stays near VMEM scale (measured v5e, marginal
    per-step: 41 µs at 8192² vs the fused kernel's 38 µs) — but once XLA
    must materialise the roll intermediates through HBM it falls off
    (653 µs vs 242 µs at 16384²), which is why aligned big boards
    dispatch to :func:`life_run_fused_bits` first.
    """
    p = _refresh_ghosts(p, ny)
    nw = p.shape[0]
    dn = (p << 1) | (jnp.roll(p, 1, 0) >> 31)
    up = (p >> 1) | (jnp.roll(p, nw - 1, 0) << 31)
    return _carry_save_rule(
        p, up, dn,
        lambda x: jnp.roll(x, 1, 1),
        lambda x: jnp.roll(x, nx - 1, 1),
    )


@functools.partial(jax.jit, static_argnames=("ny",))
def _run_bits_xla_jit(packed, steps, *, ny: int):
    nx = packed.shape[1]
    return lax.fori_loop(
        0, steps[0], lambda _, q: bit_step_xla(q, ny, nx), packed
    )


def life_run_bits_xla(board: jnp.ndarray, n: int) -> jnp.ndarray:
    """Advance ``n`` steps with the compiled-XLA packed loop.

    The dispatch target for boards beyond the Pallas VMEM kernel's budget,
    on every backend and any shape (replaces both an earlier explicit-DMA
    row-tiled Pallas kernel and the unpacked roll fallback — see
    :func:`bit_step_xla`). ``n`` is a runtime scalar; no recompile.
    """
    ny, _ = board.shape
    dtype = board.dtype
    packed = pack_board(board)
    steps = jnp.asarray([n], dtype=jnp.int32)
    out = _run_bits_xla_jit(packed, steps, ny=ny)
    return unpack_board(out, ny).astype(dtype)


# ------------------------------------------------- batched (B-board) engines
#
# Every engine above moves ONE board per device program, so a stream of
# independent small boards is dispatch-bound (one host round trip per
# request). The batched variants below thread a leading
# batch axis through the same packed machinery — B boards advance in ONE
# dispatch, bit-exact per board vs the serial engines:
#
# * the packed layout gains a leading axis, (B, n_words(ny), nx) — the
#   word/lane axes stay the minor (sublane, lane) pair, so the VPU sees
#   the identical tile shapes and the rolls/adders vectorise over B free;
# * the VMEM kernel has a whole-stack-resident form (gated by
#   :func:`fits_vmem_packed_batch` — B x the working set) and a
#   grid-over-batch form (one board resident per program, the batch axis
#   streamed by the Pallas pipeline) for stacks past that gate;
# * the fused/frame big-board engines run the stack as a sequential
#   ``lax.map`` inside one compiled program: big boards are compute-bound
#   (grid parallelism buys nothing on one core), so one dispatch per
#   stack is the whole win;
# * the XLA packed loop vmaps — pure jnp, compiled on every backend.
#
# ``steps`` stays a runtime SMEM/scalar everywhere, so one compiled
# program per (B, ny, nx) shape serves any step count — the property the
# serve-layer shape bucketing (mpi_and_open_mp_tpu/serve/) relies on.
# Each batched jit body ticks ``jit.retrace{fn=...}`` so the bucketing's
# one-compile-per-bucket claim is observable, not asserted.


def _note_retrace(fn: str) -> None:
    """Tick ``jit.retrace{fn=...}`` — call INSIDE jitted bodies only (a
    jit body runs on cache miss, so the count is compiles, not calls)."""
    from mpi_and_open_mp_tpu.obs import metrics

    metrics.inc("jit.retrace", fn=fn)


def pack_boards(boards: jnp.ndarray) -> jnp.ndarray:
    """(B, ny, nx) 0/1 ints -> (B, n_words(ny), nx) uint32 — the batched
    offset-ghost pack (:func:`pack_board` vmapped over the stack)."""
    return jax.vmap(pack_board)(boards)


def unpack_boards(packed: jnp.ndarray, ny: int) -> jnp.ndarray:
    """Inverse of :func:`pack_boards`; returns (B, ny, nx) uint8."""
    return jax.vmap(lambda p: unpack_board(p, ny))(packed)


def _set_word_row_b(p: jnp.ndarray, w: int, row: jnp.ndarray) -> jnp.ndarray:
    """Batched :func:`_set_word_row`: replace word-row ``w`` (axis 1) of a
    (B, nw, nx) stack via concatenation — same ``.at[]`` avoidance."""
    parts = []
    if w > 0:
        parts.append(p[:, :w, :])
    parts.append(row)
    if w + 1 < p.shape[1]:
        parts.append(p[:, w + 1 :, :])
    return jnp.concatenate(parts, axis=1) if len(parts) > 1 else row


def _refresh_ghosts_b(p: jnp.ndarray, ny: int) -> jnp.ndarray:
    """Batched :func:`_refresh_ghosts`: the ghost word/bit indices are a
    function of ``ny`` alone, so one static slice refreshes all B boards."""
    w_lo, b_lo = divmod(ny, 32)
    src = (p[:, w_lo : w_lo + 1, :] >> b_lo) & 1
    p = _set_word_row_b(p, 0, (p[:, 0:1, :] & np.uint32(0xFFFFFFFE)) | src)
    w_hi, b_hi = divmod(ny + 1, 32)
    src = (p[:, 0:1, :] >> 1) & 1
    new_hi = (
        p[:, w_hi : w_hi + 1, :] & np.uint32(0xFFFFFFFF ^ (1 << b_hi))
    ) | (src << b_hi)
    return _set_word_row_b(p, w_hi, new_hi)


def _roll_sub_b(p: jnp.ndarray, shift: int) -> jnp.ndarray:
    nw = p.shape[1]
    if nw == 1:
        return p
    return pltpu.roll(p, shift % nw, 1)


def _lane_rolls_b(shape: tuple[int, int, int], nx: int):
    """3-D twin of :func:`_lane_rolls`: lane axis 2, same wrap-column
    patch when the stack is lane-padded past the board width."""
    nxp = shape[2]
    if nxp == nx:
        return (
            lambda x: pltpu.roll(x, 1, 2),
            lambda x: pltpu.roll(x, nx - 1, 2),
        )
    lane = lax.broadcasted_iota(jnp.int32, shape, 2)

    def roll_left(x):
        return jnp.where(
            lane == 0, x[:, :, nx - 1 : nx], pltpu.roll(x, 1, 2)
        )

    def roll_right(x):
        return jnp.where(
            lane == nx - 1, x[:, :, 0:1], pltpu.roll(x, nxp - 1, 2)
        )

    return roll_left, roll_right


def bit_step_b(p: jnp.ndarray, ny: int, nx: int) -> jnp.ndarray:
    """One Life step on a (B, nw, nx) packed stack — :func:`bit_step`
    vectorised over the leading batch axis (the word/lane axes stay the
    minor sublane/lane pair, so every roll and adder is the same VPU op,
    B boards deep). Boards never interact: the y rolls are per-board
    (axis 1) and the rule is positionwise."""
    p = _refresh_ghosts_b(p, ny)
    nw = p.shape[1]
    dn = (p << 1) | (_roll_sub_b(p, 1) >> 31)
    up = (p >> 1) | (_roll_sub_b(p, nw - 1) << 31)
    return _carry_save_rule(p, up, dn, *_lane_rolls_b(p.shape, nx))


def _vmem_bits_batch_kernel(steps_ref, p_ref, out_ref, *, ny: int, nx: int):
    out_ref[:] = lax.fori_loop(
        0, steps_ref[0], lambda _, p: bit_step_b(p, ny, nx), p_ref[:]
    )


@functools.partial(
    jax.jit, static_argnames=("ny", "nx", "interpret", "resident")
)
def _run_vmem_bits_batch_jit(
    packed, steps, *, ny: int, nx: int, interpret: bool, resident: bool
):
    _note_retrace("life_batch_vmem")
    b, nw, nxp = packed.shape
    if resident:
        # Whole stack VMEM-resident in one program: gated by
        # fits_vmem_packed_batch (B x the per-board working set).
        return pl.pallas_call(
            functools.partial(_vmem_bits_batch_kernel, ny=ny, nx=nx),
            out_shape=jax.ShapeDtypeStruct(packed.shape, packed.dtype),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            interpret=interpret,
            name="life_vmem_bits_batch",
        )(steps, packed)
    # Grid over the batch axis: one board resident per program, the
    # stack streamed through VMEM by the pipeline (per-board gate only).
    return pl.pallas_call(
        functools.partial(_vmem_bits_batch_kernel, ny=ny, nx=nx),
        grid=(b,),
        out_shape=jax.ShapeDtypeStruct(packed.shape, packed.dtype),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, nw, nxp), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, nw, nxp), lambda i: (i, 0, 0)),
        interpret=interpret,
        name="life_vmem_bits_batch_grid",
    )(steps, packed)


def life_run_vmem_bits_batch(
    boards: jnp.ndarray, n: int, *, interpret: bool = False,
    resident: bool | None = None,
) -> jnp.ndarray:
    """Advance B stacked boards ``n`` steps in ONE packed VMEM dispatch.

    Same lane padding and runtime-scalar step count as
    :func:`life_run_vmem_bits`. ``resident=None`` picks the whole-stack-
    resident kernel when :func:`fits_vmem_packed_batch` allows and the
    grid-over-batch form otherwise (tests pin either form explicitly);
    callers must gate per-board shapes on :func:`fits_vmem_packed`.
    """
    b, ny, nx = boards.shape
    dtype = boards.dtype
    nxp = -(-nx // 128) * 128
    if nxp != nx:
        boards = jnp.pad(boards, ((0, 0), (0, 0), (0, nxp - nx)))
    if resident is None:
        resident = fits_vmem_packed_batch((b, ny, nx))
    packed = pack_boards(boards)
    steps = jnp.asarray([n], dtype=jnp.int32)
    out = _run_vmem_bits_batch_jit(
        packed, steps, ny=ny, nx=nx, interpret=interpret, resident=resident
    )
    return unpack_boards(out, ny)[:, :, :nx].astype(dtype)


@functools.partial(jax.jit, static_argnames=("ny",))
def _run_bits_xla_batch_jit(packed, steps, *, ny: int):
    _note_retrace("life_batch_xla")
    nx = packed.shape[2]
    step = jax.vmap(lambda q: bit_step_xla(q, ny, nx))
    return lax.fori_loop(0, steps[0], lambda _, q: step(q), packed)


def life_run_bits_xla_batch(boards: jnp.ndarray, n: int) -> jnp.ndarray:
    """Advance B stacked boards with the compiled-XLA packed loop — the
    any-shape any-backend batched engine (:func:`bit_step_xla` vmapped;
    one dispatch, runtime-scalar step count)."""
    _, ny, _ = boards.shape
    dtype = boards.dtype
    packed = pack_boards(boards)
    steps = jnp.asarray([n], dtype=jnp.int32)
    out = _run_bits_xla_batch_jit(packed, steps, ny=ny)
    return unpack_boards(out, ny).astype(dtype)


@functools.partial(
    jax.jit, static_argnames=("interpret", "tile_budget_bytes")
)
def _run_fused_bits_batch_jit(
    packed, steps, *, interpret: bool,
    tile_budget_bytes: int = _FUSED_TILE_BUDGET,
):
    _note_retrace("life_batch_fused")
    # Sequential scan over the stack, ONE compiled program: fused-regime
    # boards are compute-bound on the core, so batching exists to
    # amortise the dispatch, not to overlap boards. (A vmap would lean on
    # pallas batching rules over the explicit-DMA scratch kernel; the
    # scan keeps the proven single-board program byte-identical.)
    return lax.map(
        lambda p: _run_fused_bits_jit(
            p, steps, interpret=interpret,
            tile_budget_bytes=tile_budget_bytes,
        ),
        packed,
    )


def life_run_fused_bits_batch(
    boards: jnp.ndarray, n: int, *, interpret: bool = False,
    tile_budget_bytes: int = _FUSED_TILE_BUDGET,
) -> jnp.ndarray:
    """Advance B stacked ALIGNED big boards via the multi-step-fused tiled
    kernel, all boards in one dispatch (see the scan note in the jit)."""
    dtype = boards.dtype
    packed = jax.vmap(pack_board_exact)(boards)
    steps = jnp.asarray([n], dtype=jnp.int32)
    out = _run_fused_bits_batch_jit(
        packed, steps, interpret=interpret,
        tile_budget_bytes=tile_budget_bytes,
    )
    return jax.vmap(unpack_board_exact)(out).astype(dtype)


@functools.partial(
    jax.jit, static_argnames=("ny", "nx", "interpret", "budget")
)
def _run_frame_bits_batch_jit(
    packed, steps, *, ny: int, nx: int, interpret: bool, budget: int
):
    _note_retrace("life_batch_frame")
    return lax.map(
        lambda p: _run_frame_bits_jit(
            p, steps, ny=ny, nx=nx, interpret=interpret, budget=budget
        ),
        packed,
    )


def life_run_frame_bits_batch(
    boards: jnp.ndarray, n: int, *, interpret: bool = False,
    budget: int = _PACKED_VMEM_LIMIT,
) -> jnp.ndarray:
    """Advance B stacked UNALIGNED big boards via the padded-torus frame,
    all boards in one dispatch (same sequential-scan rationale as the
    fused batch). Gate on ``plan_sharded_bits(shape, 1, 1, False, False)``.
    """
    b, ny, nx = boards.shape
    plan = plan_sharded_bits((ny, nx), 1, 1, False, False, budget)
    if plan is None:
        raise ValueError(
            f"no padded-frame plan for {(ny, nx)}; gate callers on "
            "plan_sharded_bits()"
        )
    dtype = boards.dtype
    frames = jnp.pad(
        boards,
        ((0, 0), (0, plan.frame[0] - ny), (0, plan.frame[1] - nx)),
    )
    packed = jax.vmap(pack_board_exact)(frames)
    steps = jnp.asarray([n], dtype=jnp.int32)
    out = _run_frame_bits_batch_jit(
        packed, steps, ny=ny, nx=nx, interpret=interpret, budget=budget
    )
    return jax.vmap(unpack_board_exact)(out)[:, :ny, :nx].astype(dtype)


# --------------------------------------------- board-sliced (bitsliced) layout
#
# Second pluggable pack layout for batched stacks. The cell-packed layout
# above slices SPACE into bits (32 board rows per uint32, one board per
# bitplane), so B boards still cost B times the vector work. Board-sliced
# flips the packing axis: bit ``b`` of every word belongs to board ``b``,
# tensor shape (n_planes, ny, nx) with ``n_planes = ceil(B / 32)`` — one
# VPU op advances up to 32 worlds at once, and the spatial axes stay
# plain, so every neighbour gather is an ordinary torus roll with no
# cross-word carry games and no ghost rows.
#
# Engines (both runtime-scalar steps, ``jit.retrace{fn=
# life_batch_bitsliced}`` observable):
#
# * :func:`_run_bitsliced_pallas_jit` — whole plane stack VMEM-resident,
#   the step loop inside one kernel; spatial gathers are ``pltpu.roll``
#   with the :func:`_lane_rolls_b` wrap-column patch for lane padding.
# * :func:`_run_bitsliced_xla_jit` — the compiled-XLA twin, structured
#   for XLA:CPU fusion rather than as literal rolls: the stack carries a
#   ``_BITSLICE_HALO``-deep wrapped halo, each step is NINE static slices
#   feeding one fused rule + pad kernel (measured ~8x the vmapped
#   cell-packed loop at B=32, 64² on CPU; plain per-step rolls measure
#   only ~1.9x because each roll materialises a concat).
#
# Ragged B zero-pads the high bits; an all-dead plane bit stays dead
# under the rule (N = 0 never births), so padding boards are inert and
# :func:`unpack_batch_bits` simply slices them off.

_BITSLICE_HALO = 4


def n_planes(b: int) -> int:
    """Board-sliced planes for a B-board stack: ``ceil(B / 32)``."""
    return -(-b // 32)


def fits_vmem_bitsliced(shape: tuple[int, int, int]) -> bool:
    """Whether a (B, ny, nx) stack's plane tensor fits the VMEM budget.

    Same arithmetic as :func:`fits_vmem_packed`: lane-padded plane bytes
    against ``_PACKED_VMEM_LIMIT`` (the step loop holds the same ~11
    live temporaries, each ``n_planes`` deep). A 500² board is one
    1.0 MB plane (passes); past ~1000² the cell-packed big-board ladder
    takes over."""
    b, ny, nx = shape
    nxp = -(-nx // 128) * 128
    return n_planes(b) * ny * nxp * 4 <= _PACKED_VMEM_LIMIT


def pack_batch_bits(boards: jnp.ndarray) -> jnp.ndarray:
    """(B, ny, nx) 0/1 ints -> (n_planes, ny, nx) uint32, board-sliced:
    bit ``b % 32`` of plane ``b // 32`` holds board ``b``'s cell. Ragged
    B zero-pads the high bits (inert under the rule — see above)."""
    b, ny, nx = boards.shape
    npl = n_planes(b)
    pad = npl * 32 - b
    if pad:
        boards = jnp.concatenate(
            [boards, jnp.zeros((pad, ny, nx), boards.dtype)], axis=0
        )
    shifts = jnp.arange(32, dtype=jnp.uint32)[None, :, None, None]
    return (
        boards.astype(jnp.uint32).reshape(npl, 32, ny, nx) << shifts
    ).sum(axis=1, dtype=jnp.uint32)


def unpack_batch_bits(planes: jnp.ndarray, b: int) -> jnp.ndarray:
    """Inverse of :func:`pack_batch_bits`; returns (b, ny, nx) uint8."""
    npl, ny, nx = planes.shape
    shifts = jnp.arange(32, dtype=jnp.uint32)[None, :, None, None]
    rows = ((planes[:, None] >> shifts) & jnp.uint32(1)).reshape(
        npl * 32, ny, nx
    )
    return rows[:b].astype(jnp.uint8)


def lane_change_bits(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Per-lane change summary of two (P, ny, nx) bit-sliced slabs: one
    uint32 per plane whose bit ``l`` is set iff lane ``l``'s board
    differs anywhere between ``a`` and ``b`` — an OR-reduction of the
    XOR over both spatial axes, so the whole summary costs one
    elementwise pass and ships 4*P bytes. When ``a`` and ``b`` are
    CONSECUTIVE steps of the same slab, a zero bit is a proven fixed
    point (the next step of an unchanged board is unchanged forever) —
    the predicate the session pool's settled-skip rides."""
    return lax.reduce(a ^ b, jnp.uint32(0), lax.bitwise_or, (1, 2))


def _carry_save_rule9(c, up, dn, lf, rt, ul, ur, dl, dr):
    """:func:`_carry_save_rule` with all eight neighbours supplied as
    operands instead of via roll callbacks — the form the halo-fused XLA
    engine needs, where every neighbour is a static slice of the same
    halo-padded array (so XLA fuses the whole rule, slices included,
    into one elementwise kernel per step). Identical adder tree and
    mod-8 wrap semantics; the column sums just can't share the
    half-adder prefix because the side columns arrive pre-gathered."""
    cs0 = up ^ dn
    cs1 = up & dn
    l0 = ul ^ lf ^ dl
    l1 = (ul & lf) | ((ul ^ lf) & dl)
    r0 = ur ^ rt ^ dr
    r1 = (ur & rt) | ((ur ^ rt) & dr)
    p0 = l0 ^ r0
    q0 = l0 & r0
    p1x = l1 ^ r1
    p1 = p1x ^ q0
    p2 = (l1 & r1) | (p1x & q0)
    n0 = p0 ^ cs0
    rc = p0 & cs0
    n1x = p1 ^ cs1
    n1 = n1x ^ rc
    n2 = p2 ^ ((p1 & cs1) | (n1x & rc))
    return (n0 | c) & n1 & ~n2


def bitsliced_step(planes: jnp.ndarray, nx: int) -> jnp.ndarray:
    """One Life step on a (n_planes, ny, nx-or-lane-padded) stack — the
    roll form shared by the Pallas kernel (and usable under interpret
    mode). The bit axis is batch, so the spatial gathers are plain torus
    rolls: y via sublane rolls, x via :func:`_lane_rolls_b` (exact
    ``nx`` wrap on the lane-padded fast path)."""
    ny = planes.shape[1]
    up = pltpu.roll(planes, ny - 1, 1) if ny > 1 else planes
    dn = pltpu.roll(planes, 1, 1) if ny > 1 else planes
    return _carry_save_rule(
        planes, up, dn, *_lane_rolls_b(planes.shape, nx)
    )


def _bitsliced_kernel(steps_ref, p_ref, out_ref, *, nx: int):
    out_ref[:] = lax.fori_loop(
        0, steps_ref[0], lambda _, p: bitsliced_step(p, nx), p_ref[:]
    )


@functools.partial(jax.jit, static_argnames=("nx", "interpret"))
def _run_bitsliced_pallas_jit(planes, steps, *, nx: int, interpret: bool):
    _note_retrace("life_batch_bitsliced")
    return pl.pallas_call(
        functools.partial(_bitsliced_kernel, nx=nx),
        out_shape=jax.ShapeDtypeStruct(planes.shape, planes.dtype),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        interpret=interpret,
        name="life_bitsliced",
    )(steps, planes)


@jax.jit
def _run_bitsliced_xla_jit(planes, steps):
    """Compiled-XLA bitsliced loop, halo-fused for CPU throughput.

    The stack carries a K-deep wrapped halo (K = ``_BITSLICE_HALO``,
    clamped to the board for tiny shapes). Every K steps the halo is
    rebuilt from the valid centre (two concats); each step reads NINE
    static slices of the halo frame into :func:`_carry_save_rule9` and
    zero-pads the result back to frame shape — slices and pad fuse with
    the rule into one XLA:CPU kernel per step, where per-step torus
    rolls would each materialise a concat. Validity shrinks one ring
    per step and never reaches the centre before the next refresh; the
    pad ring is junk by construction. ``steps`` stays a runtime scalar:
    the block loop is a while over remaining steps, the intra-block
    loop a fori over ``min(rem, K)``."""
    _note_retrace("life_batch_bitsliced")
    _, ny, nx = planes.shape
    k_halo = min(_BITSLICE_HALO, ny, nx)
    nyp, nxp = ny + 2 * k_halo, nx + 2 * k_halo

    def refresh(frame):
        rows = jnp.concatenate(
            [
                frame[:, ny : k_halo + ny],
                frame[:, k_halo : k_halo + ny],
                frame[:, k_halo : 2 * k_halo],
            ],
            axis=1,
        )
        return jnp.concatenate(
            [
                rows[:, :, nx : k_halo + nx],
                rows[:, :, k_halo : k_halo + nx],
                rows[:, :, k_halo : 2 * k_halo],
            ],
            axis=2,
        )

    def halo_step(frame):
        def s(dy, dx):
            return frame[:, 1 + dy : nyp - 1 + dy, 1 + dx : nxp - 1 + dx]

        out = _carry_save_rule9(
            s(0, 0), s(-1, 0), s(1, 0), s(0, -1), s(0, 1),
            s(-1, -1), s(-1, 1), s(1, -1), s(1, 1),
        )
        return jnp.pad(out, ((0, 0), (1, 1), (1, 1)))

    def body(carry):
        frame, rem = carry
        k = jnp.minimum(rem, k_halo)
        frame = refresh(frame)
        frame = lax.fori_loop(0, k, lambda _, f: halo_step(f), frame)
        return frame, rem - k

    frame0 = jnp.pad(
        planes, ((0, 0), (k_halo, k_halo), (k_halo, k_halo))
    )
    frame, _ = lax.while_loop(
        lambda c: c[1] > 0, body, (frame0, steps[0])
    )
    return frame[:, k_halo : k_halo + ny, k_halo : k_halo + nx]


def life_run_bitsliced_batch(
    boards: jnp.ndarray, n: int, *, interpret: bool = False,
    use_kernel: bool | None = None,
) -> jnp.ndarray:
    """Advance B stacked boards ``n`` steps through the board-sliced
    layout in ONE dispatch: pack to bitplanes, run the whole step loop
    compiled, unpack, slice the ragged padding off.

    ``use_kernel=None`` picks the Pallas VMEM kernel on real hardware
    (``interpret=False``) and the halo-fused XLA twin otherwise — on CPU
    the twin IS the fast path, not a consolation (see the section
    comment); tests pin ``use_kernel=True, interpret=True`` to cover the
    kernel itself. The inner jit is keyed on the PLANE shape, so one
    compile per (n_planes, ny, nx) serves every ragged B in the plane
    and every step count."""
    b, ny, nx = boards.shape
    dtype = boards.dtype
    planes = pack_batch_bits(boards)
    steps = jnp.asarray([n], dtype=jnp.int32)
    if use_kernel is None:
        use_kernel = not interpret
    if use_kernel:
        nxp = -(-nx // 128) * 128
        if nxp != nx:
            planes = jnp.pad(planes, ((0, 0), (0, 0), (0, nxp - nx)))
        out = _run_bitsliced_pallas_jit(
            planes, steps, nx=nx, interpret=interpret
        )[:, :, :nx]
    else:
        out = _run_bitsliced_xla_jit(planes, steps)
    return unpack_batch_bits(out, b).astype(dtype)
