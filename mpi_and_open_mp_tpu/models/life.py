"""Distributed Game-of-Life simulation engine.

The TPU-native re-design of the reference's four Life drivers:

* ``layout="row"``  ≙ 1-D row-strip decomposition (``3-life/life_mpi.c``)
* ``layout="col"``  ≙ 1-D column strips via strided datatypes (``4-life/life_mpi.c``)
* ``layout="cart"`` ≙ 2-D Cartesian blocks (``6-cartesian/life_cart.c``)
* ``layout="serial"`` ≙ the single-process oracle (``3-life/life2d.c``)

Instead of per-rank slabs with in-place ghost writes, the global board is ONE
``jax.Array`` sharded over a ``Mesh``; the step is either

* ``impl="roll"``: the global circular-shift step — XLA inserts
  collective-permutes for the sharded axes. Works for any board size: a
  board that doesn't divide the mesh is stored padded to the next even
  multiple and un/re-padded inside the jitted step (static shapes), which
  covers the reference's last-rank-absorbs-remainder decomposition
  (``3-life/life_mpi.c:178-183``) without its rank-loop idiom; or
* ``impl="halo"``: an explicit ``shard_map`` step — ``lax.ppermute``
  depth-``k`` halo exchange then ``k`` fused local stencil steps per round
  (amortising one exchange over ``k`` steps; state-identical to stepping
  ``k`` times). Requires the sharded axes to divide the board.
* ``impl="pallas"``: like ``halo`` but the local stencil is a Pallas TPU
  kernel; single-device meshes use the whole-board-in-VMEM multi-step
  kernel (see ``ops.pallas_life``).
* ``impl="bitfused"`` (row/col/cart): the scale-out flagship — each
  shard holds a bit-packed slab (``ops.bitlife``), exchanges an
  up-to-4-word (=128-cell-row) y halo and/or an up-to-128-column x halo
  by ``ppermute`` (unsharded axes wrap locally; cart corners ride the
  sequenced exchange), then runs up to 128 fused steps slab-resident
  through the fused kernel before the next exchange. One collective
  round per up to 128 steps instead of per step; the ICI analogue of
  the reference's ghost Send/Recv (``3-life/life_mpi.c:198-209``,
  ``4-life:197-208``) amortised up to 128-fold. Any board shape on any
  mesh the planner (``bitlife.plan_sharded_bits``) accepts — unaligned
  boards (the 500x500 flagship included) live in a word/lane-aligned
  padded frame whose torus wrap is kept exact via periodic mirrors and
  funnel-shifted wrap halos. A 1-device mesh has no neighbours, so on
  TPU it dispatches straight to the serial whole-board stepper (ghost
  redundancy and exchange rounds buy nothing there); the exchange
  machinery engages from 2 devices.

``impl="auto"``: serial boards pick ``pallas`` on TPU / ``roll``
elsewhere; sharded layouts pick ``bitfused`` on TPU whenever the
planner covers the board/mesh geometry, else ``halo`` when shapes
divide, else ``roll``.

A STACKED ``(B, ny, nx)`` ``initial_board`` puts the sim in batched
mode (serial layout only): all B independent boards advance in ONE
device dispatch through the batched native engines
(``ops.pallas_life.life_run_vmem_batch``; ``impl="roll"`` vmaps the
unpacked step instead), ``collect()`` returns the stack, and the
honesty gate (``debug_check``/guards) checks EVERY board against the
NumPy oracle individually. The serve-layer micro-batcher
(``mpi_and_open_mp_tpu.serve``) is the request-collecting front door
over the same engines.

The run loop preserves the reference's ordering (``3-life/life_mpi.c:51-62``):
at step ``i``, save a snapshot when ``i % save_steps == 0`` (i.e. *before*
stepping), then advance one step; where nothing else stops the loop, one
device program steps a chunk of save intervals and one fetch brings its
frames to the host (``LifeSim.run``). Collect-to-host is ``jax.device_get`` of
the sharded array — the ``MPI_Gather``/manual-recv-loop equivalent
(``5-gather/life_mpi.c:178``, ``3-life/life_mpi.c:185-196``); a Life board
of 32 MiB or more crosses as bit-packed words (``LifeSim.collect``).

The board is Life's 0/1 ``uint8`` state on every path. Other stencil
rules have their own entry (``stencils.engine``: ``run_roll``,
``run_sharded``).
"""

from __future__ import annotations

import functools
import os
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from mpi_and_open_mp_tpu.obs import trace
from mpi_and_open_mp_tpu.ops import life_ops
from mpi_and_open_mp_tpu.parallel import halo, haloplan, mesh as mesh_lib
from mpi_and_open_mp_tpu.utils import vtk as vtk_lib
from mpi_and_open_mp_tpu.utils.config import LifeConfig

LAYOUTS = ("serial", "row", "col", "cart")
IMPLS = ("auto", "roll", "halo", "pallas", "bitfused")

# The bitfused 1-device serial dispatch is TPU-only by default (on CPU
# the interpret-mode suite keeps exercising the exchange machinery the
# fast path bypasses); tests flip this to cover the dispatch itself.
_BITFUSED_1DEV_SERIAL_ON_CPU = False


def _layout_spec(layout: str) -> P:
    return P(*{
        "serial": (),
        "row": ("y", None),
        "col": (None, "x"),
        "cart": ("y", "x"),
    }[layout])


def _default_mesh(layout: str) -> Mesh | None:
    if layout == "serial":
        return None
    if layout == "row":
        return mesh_lib.make_mesh_1d(axis="y")
    if layout == "col":
        return mesh_lib.make_mesh_1d(axis="x")
    return mesh_lib.make_mesh_2d()


def _mesh_divisors(layout: str, mesh: Mesh | None) -> tuple[int, int]:
    """(py, px) the board axes must divide for even sharding under ``layout``."""
    if layout == "serial" or mesh is None:
        return (1, 1)
    py = mesh.shape.get("y", 1) if layout in ("row", "cart") else 1
    px = mesh.shape.get("x", 1) if layout in ("col", "cart") else 1
    return (py, px)


def _divisible(shape: tuple[int, int], layout: str, mesh: Mesh | None) -> bool:
    ny, nx = shape
    py, px = _mesh_divisors(layout, mesh)
    return ny % py == 0 and nx % px == 0


def _ceil_to(n: int, m: int) -> int:
    return -(-n // m) * m


def _oracle_step(board: np.ndarray) -> np.ndarray:
    """One NumPy-oracle step; a (B, ny, nx) stack steps per board."""
    if board.ndim == 3:
        return np.stack([life_ops.life_step_numpy(b) for b in board])
    return life_ops.life_step_numpy(board)


def _note_retrace(fn: str) -> None:
    """Retrace accounting (``obs.metrics``): called from INSIDE jitted
    ``advance`` bodies, which only execute on a jit-cache miss — so the
    counter reads "how many distinct programs XLA built for this
    function", the number that explains a slow first segment or a
    shape-churn pathology. Free at execution time by construction."""
    from mpi_and_open_mp_tpu.obs import metrics

    metrics.inc("jit.retrace", fn=fn)


# The smallest board ``collect()`` packs. The pack costs one more dispatch
# and device pass, and pays only where the host's write of the byte board
# is dear: on a TPU v5e host, packing lost below 16 MiB (2.5 against
# 1.6 ms at 4 MiB), broke even at 16 MiB and won at 64 MiB (32 against
# 100 ms), where each fresh board is mapped and faulted in anew (glibc
# serves arrays above 32 MiB from fresh mappings).
_PACK_MIN_BYTES = 32 << 20

# Bytes of frames that one chunk program of ``run()``'s snapshot path
# stacks on the device and fetches in one transfer: 279 frames of a
# 300x100 board, a quarter of the size at which the host maps fresh pages
# for the fetch. A board of more than half of this keeps one dispatch and
# one fetch a frame.
_FRAME_CHUNK_BYTES = 8 << 20

# Output bytes per task of ``_unpack_words``.
_UNPACK_CHUNK_BYTES = 1 << 20


@functools.cache
def _unpack_pool() -> ThreadPoolExecutor:
    return ThreadPoolExecutor(os.cpu_count() or 1,
                              thread_name_prefix="life-unpack")


def _unpack_words(words: np.ndarray) -> np.ndarray:
    """``uint32`` words ``(..., n)`` back to 0/1 bytes ``(..., 32 n)``:
    bit ``j`` of word ``k`` becomes cell ``32 k + j``.

    Writing a fresh board costs a page fault per page, and where faults
    are dear (a gVisor-sandboxed TPU v5e host: 76 ms to fault 64 MiB,
    4 ms to copy into pages already touched) they, not the unpacking,
    take the time. So the rows unpack in chunks on a thread pool, which
    spreads the faults over the host's cores (27 against 79 ms at 64 MiB
    on that host's 13 cores).
    """
    src = np.ascontiguousarray(words, "<u4").view(np.uint8)
    rows = src.reshape(-1, src.shape[-1])
    out = np.empty((rows.shape[0], 8 * rows.shape[1]), np.uint8)
    step = max(1, _UNPACK_CHUNK_BYTES // out.shape[1])

    def unpack(i: int) -> None:
        out[i: i + step] = np.unpackbits(rows[i: i + step], axis=-1,
                                         bitorder="little")

    list(_unpack_pool().map(unpack, range(0, rows.shape[0], step)))
    return out.reshape(*words.shape[:-1], out.shape[-1])


def _round_halo_bytes(plan) -> int:
    """Bytes one shard sends over ``ppermute`` in one exchange round of
    the sharded bitfused advance: a ghost each way on every sharded axis,
    x first on the ``(nw_s, W)`` packed shard, then y on the x-extended
    one, each as ``halo.packed_halo_x``/``packed_halo_y`` slice it."""
    cols, words = plan.W, 0
    if plan.x_sharded:
        words += 2 * plan.nw_s * halo.packed_send_x(plan.hx, plan.pad_x)
        cols += 2 * plan.hx
    if plan.y_sharded:
        words += 2 * cols * halo.packed_send_y(plan.h, plan.pad_y)
    return 4 * words


class LifeSim:
    """One Life run: sharded board state + compiled steppers + snapshot IO."""

    def _bitfused_plan(self, layout: str, shape: tuple[int, int]):
        """The packed-path plan for this board/mesh, or None (serial
        layouts, or geometry the frame-padding scheme can't cover)."""
        from mpi_and_open_mp_tpu.ops import bitlife

        if layout == "serial":
            return None
        py, px = _mesh_divisors(layout, self.mesh)
        return bitlife.plan_sharded_bits(
            shape, py, px,
            y_sharded=layout in ("row", "cart"),
            x_sharded=layout in ("col", "cart"),
        )

    def __init__(
        self,
        cfg: LifeConfig,
        layout: str = "row",
        impl: str = "auto",
        mesh: Mesh | None = None,
        fuse_steps: int = 1,
        outdir: str | os.PathLike | None = None,
        checkpoint_dir: str | os.PathLike | None = None,
        checkpoint_every: int = 0,
        initial_board: np.ndarray | None = None,
        initial_step: int = 0,
    ):
        if layout not in LAYOUTS:
            raise ValueError(f"layout must be one of {LAYOUTS}, got {layout!r}")
        if impl not in IMPLS:
            raise ValueError(f"impl must be one of {IMPLS}, got {impl!r}")
        # Batched mode: a STACKED (B, ny, nx) initial board advances all B
        # independent boards per dispatch through the batched native
        # engines (ops.pallas_life.life_run_vmem_batch) — the model-layer
        # face of the serve-layer micro-batching. Serial layout only (a
        # batch of sharded boards is the serve layer's bucketing problem,
        # not one mesh program), and no VTK/checkpoint channels (both
        # serialise ONE board; batched runs are throughput runs).
        self.batch: int | None = None
        if initial_board is not None and np.asarray(initial_board).ndim == 3:
            if layout != "serial":
                raise ValueError(
                    "stacked (B, ny, nx) boards need layout='serial'; "
                    "sharded layouts advance one board per mesh program"
                )
            if impl in ("halo", "bitfused"):
                raise ValueError(
                    f"impl={impl!r} has no batched form; use 'auto', "
                    "'pallas' (batched native dispatch) or 'roll'"
                )
            if outdir is not None or checkpoint_dir is not None:
                raise ValueError(
                    "batched runs have no snapshot/checkpoint channels "
                    "(both serialise one board); drop outdir/checkpoint_dir"
                )
            self.batch = int(np.asarray(initial_board).shape[0])
        self.cfg = cfg
        self.layout = layout
        self.mesh = mesh if mesh is not None else _default_mesh(layout)
        self.fuse_steps = max(1, int(fuse_steps))
        self.outdir = os.fspath(outdir) if outdir is not None else None
        self.checkpoint_dir = (
            os.fspath(checkpoint_dir) if checkpoint_dir is not None else None
        )
        # Periodic restart cadence (steps between Orbax checkpoints inside
        # run(); 0 = only the save_steps cadence writes checkpoints) and
        # the per-run recovery provenance the guards append to.
        self.checkpoint_every = max(0, int(checkpoint_every))
        self.recoveries: list[str] = []
        self._probe = None  # lazy (board, oracle) pair for _probe_case
        self.step_count = int(initial_step)

        divisible = _divisible(cfg.shape, layout, self.mesh)
        plan = (
            self._bitfused_plan(layout, cfg.shape)
            if impl in ("auto", "bitfused")
            else None
        )
        if impl == "auto":
            on_tpu = jax.default_backend() == "tpu"
            if self.batch is not None:
                # The batched dispatcher compiles on EVERY backend (off-TPU
                # it routes to the vmapped packed-XLA loop, never interpret
                # mode — ops.pallas_life.native_path_batch), so batched
                # auto is always the native dispatch.
                impl = "pallas"
            elif layout == "serial":
                # Pallas only where it compiles natively; elsewhere it would
                # run in interpret mode, orders of magnitude slower.
                impl = "pallas" if on_tpu else "roll"
            elif on_tpu and plan is not None:
                # Best sharded path whenever the frame-padding plan covers
                # the geometry (any board shape, aligned or not): one
                # collective round per <=128 fused steps. TPU-only — on
                # CPU the kernel would run in interpret mode.
                impl = "bitfused"
            elif divisible:
                impl = "halo"
            else:
                impl = "roll"
        if impl == "halo" and layout == "serial":
            raise ValueError(
                "impl='halo' needs a sharded layout (row/col/cart); "
                "serial runs use impl='roll' or 'pallas'"
            )
        if impl in ("halo", "pallas") and not divisible and layout != "serial":
            raise ValueError(
                f"impl={impl!r} needs board {cfg.shape} divisible by mesh "
                f"{dict(self.mesh.shape)}; use impl='roll' (uneven shards OK)"
            )
        if impl == "bitfused":
            if layout == "serial":
                raise ValueError(
                    "impl='bitfused' needs a sharded layout (row/col/cart); "
                    "serial big boards already take the fused kernel via "
                    "impl='pallas'"
                )
            if plan is None:
                raise ValueError(
                    f"impl='bitfused' can't plan board {cfg.shape} over "
                    f"mesh {dict(self.mesh.shape)}: a shard is too small "
                    "to carry a fused halo next to its frame padding; use "
                    "impl='halo' or 'roll'"
                )
        self.impl = impl
        self._plan = plan if impl == "bitfused" else None

        if impl in ("halo", "pallas") and layout != "serial":
            py, px = _mesh_divisors(layout, self.mesh)
            local = min(cfg.ny // py, cfg.nx // px)
            if self.fuse_steps > local:
                raise ValueError(
                    f"fuse_steps={self.fuse_steps} exceeds the smallest "
                    f"local shard extent ({local}); a halo cannot be "
                    f"deeper than the shard it pads"
                )

        self.sharding = (
            NamedSharding(self.mesh, _layout_spec(layout))
            if self.mesh is not None
            else None
        )
        # Uneven boards: store padded to the next mesh-even multiple; the
        # roll step un/re-pads inside jit so the torus wrap stays on the
        # LOGICAL (ny, nx) coordinates, never the padded ones. The packed
        # path pads further, to its word/lane-aligned frame, and keeps the
        # torus via periodic mirrors (ops.bitlife module docs).
        if self._plan is not None:
            self.padded_shape = self._plan.frame
        else:
            py, px = _mesh_divisors(layout, self.mesh)
            self.padded_shape = (_ceil_to(cfg.ny, py), _ceil_to(cfg.nx, px))
        if initial_board is not None:
            board = np.asarray(initial_board, dtype=np.uint8)
            expect = (
                (self.batch, *cfg.shape) if self.batch is not None
                else cfg.shape
            )
            if board.shape != expect:
                raise ValueError(
                    f"initial_board {board.shape} != expected {expect}"
                )
        else:
            board = cfg.board()
        if self.batch is None and self.padded_shape != cfg.shape:
            full = np.zeros(self.padded_shape, dtype=board.dtype)
            full[: cfg.ny, : cfg.nx] = board
            board = full
        self._initial = board
        self._initial_step = int(initial_step)
        # The number of the current run: reset() starts the next one, and
        # every span of a run carries it as ``run``.
        self._run_id = 0
        self.reset()
        # What a sharded bitfused advance exchanges, and the cells an
        # advance through a fused stepper steps: its stepping spans'
        # counters (``_step_attrs``), set by the step builders.
        self._exchange = None
        self._tile_cells = {}
        self._advance = self._build_advance()
        self._frames = self._build_frames()
        self._pack = self._build_pack()

    # ---------------------------------------------------------- step builders

    def _halo_plan(self, k: int) -> "haloplan.HaloPlan":
        """The persistent exchange plan for one ``k``-step fused round
        (derived once per geometry, ``lru_cache``d in ``haloplan``)."""
        py, px = _mesh_divisors(self.layout, self.mesh)
        return haloplan.plan_halo(
            self.layout, (py, px),
            (self.padded_shape[0] // py, self.padded_shape[1] // px),
            1, k,
        )

    def _local_fused_step(self, block: jnp.ndarray, k: int) -> jnp.ndarray:
        """One fused round of ``k`` local steps (each consuming one
        halo cell per side), scheduled by the persistent
        halo plan: ghost ``ppermute``s overlap the interior stencil when
        the geometry allows (``parallel.haloplan``), else the historic
        blocking ``halo_pad_*`` concat."""
        return haloplan.fused_step(self._halo_plan(k), self._padded_step,
                                   block)

    def _padded_step(self, padded: jnp.ndarray) -> jnp.ndarray:
        if self.impl == "pallas":
            from mpi_and_open_mp_tpu.ops import pallas_life

            return pallas_life.life_step_padded_pallas(padded)
        return life_ops.life_step_padded(padded)

    def _build_advance(self) -> Callable[[jnp.ndarray, int], jnp.ndarray]:
        """Return ``advance(board, n)`` running ``n`` steps, jit-cached on ``n``."""
        if self.batch is not None:
            return self._build_batched_advance()

        if self.impl == "bitfused":
            return self._build_bitfused_advance()

        if self.impl == "pallas" and (
            self.mesh is None or self.mesh.size == 1
        ):
            from mpi_and_open_mp_tpu.ops import pallas_life

            shape = self.padded_shape
            self._tile_cells = pallas_life.native_tile_cells(
                pallas_life.native_path(
                    shape, on_tpu=not pallas_life._interpret()), shape)

            def advance(board, n):
                return pallas_life.life_run_vmem(board, n)

            return advance

        if self.impl == "roll" or self.layout == "serial":
            sharding = self.sharding
            ny, nx = self.cfg.shape
            pad_y = self.padded_shape[0] - ny
            pad_x = self.padded_shape[1] - nx

            @functools.partial(jax.jit, static_argnums=1)
            def advance(board, n):
                _note_retrace("life_advance_roll")

                def body(_, b):
                    if pad_y or pad_x:
                        v = life_ops.life_step_roll(b[..., :ny, :nx])
                        b = jnp.pad(v, ((0, pad_y), (0, pad_x)))
                    else:
                        b = life_ops.life_step_roll(b)
                    if sharding is not None:
                        b = lax.with_sharding_constraint(b, sharding)
                    return b

                with jax.named_scope("life_advance"):
                    return lax.fori_loop(0, n, body, board)

            return advance

        # shard_map halo/pallas path, with k-step fusion per exchange round.
        spec = _layout_spec(self.layout)
        k = self.fuse_steps
        # Provenance: the persistent plan's schedule stamp for the main
        # round depth ("overlap:*" when the ghost exchange hides behind
        # the interior stencil, "seq:halo" with the reason otherwise).
        self.plan_note = self._halo_plan(k).engine

        def make_smapped(kk: int):
            # check_vma=False: the Pallas per-shard kernel can't annotate
            # varying-mesh-axes on its out_shape; the specs are authoritative.
            return jax.shard_map(
                lambda b: self._local_fused_step(b, kk),
                mesh=self.mesh,
                in_specs=spec,
                out_specs=spec,
                check_vma=False,
            )

        smapped_k = make_smapped(k)
        smapped_cache = {k: smapped_k}

        @functools.partial(jax.jit, static_argnums=1)
        def advance(board, n):
            _note_retrace("life_advance_halo")
            rounds, rem = divmod(n, k)
            with jax.named_scope("life_advance"):
                board = lax.fori_loop(
                    0, rounds, lambda _, b: smapped_k(b), board)
                if rem:
                    if rem not in smapped_cache:
                        smapped_cache[rem] = make_smapped(rem)
                    board = smapped_cache[rem](board)
            return board

        return advance

    def _build_batched_advance(self) -> Callable:
        """Stacked-board steppers: all B boards advance in ONE dispatch.

        ``impl="pallas"`` is the batched native dispatch
        (``ops.pallas_life.life_run_vmem_batch`` — runtime-scalar step
        count, one compiled program per stack shape on every backend);
        ``impl="roll"`` is the unpacked roll step vmapped over the stack
        (jit-cached per static ``n``, like the single-board roll).
        """
        if self.impl == "pallas":
            from mpi_and_open_mp_tpu.ops import pallas_life

            path = pallas_life.native_path_batch(
                (self.batch, *self.cfg.shape),
                on_tpu=jax.default_backend() == "tpu",
            )
            self.plan_note = "batch:" + path
            self._tile_cells = pallas_life.native_tile_cells(
                path, self.cfg.shape, boards=self.batch)

            def advance(board, n):
                return pallas_life.life_run_vmem_batch(board, n)

            return advance

        @functools.partial(jax.jit, static_argnums=1)
        def advance(board, n):
            _note_retrace("life_advance_roll_batch")
            step = jax.vmap(life_ops.life_step_roll)
            with jax.named_scope("life_advance"):
                return lax.fori_loop(0, n, lambda _, b: step(b), board)

        return advance

    def _build_bitfused_advance(self) -> Callable:
        """Packed scale-out path: ppermute packed halos, fuse <=128 steps.

        Each shard packs its slab once per ``advance`` call (pack/unpack are
        fused XLA ops, amortised over the whole step budget), then loops:
        exchange the plan's halo word rows (row layout; plus halo columns
        first on col/cart meshes — corners ride the y-exchange of the
        x-extended slab, the reference's 2-phase trick at
        ``6-cartesian/life_cart.c:275-279``), run ``min(rem, k_max)``
        steps slab-resident via the fused kernel, repeat. Unaligned
        boards live in the plan's padded frame: the halo calls slide the
        torus wrap onto the logical shape and refresh the periodic
        mirrors (``halo.packed_halo_*``/``bitlife.wrap_y_padded``), so
        the same one-collective-per-k_max-steps economy covers every
        shape — the reference's per-step ghost Send/Recv
        (``3-life/life_mpi.c:198-209``) amortised up to 128-fold. ``n``
        is a runtime scalar — one compiled program serves every segment
        length.
        """
        from mpi_and_open_mp_tpu.ops import bitlife

        plan = self._plan
        mesh = self.mesh
        spec = _layout_spec(self.layout)
        interpret = jax.default_backend() != "tpu"

        if mesh.size == 1 and (not interpret
                               or _BITFUSED_1DEV_SERIAL_ON_CPU):
            # A 1-device mesh has no neighbours: the ghost-window
            # redundancy ((nw_s+2h)/nw_s ≈ 1.5x extra cells at the 500²
            # flagship) and the per-round exchange+launch cost buy
            # nothing, so dispatch the board to the serial whole-board
            # stepper — the sharded machinery begins at 2 devices. The
            # plan's frame padding is sliced off/restored around the
            # call (once per advance, amortised over the whole step
            # budget); the serial dispatcher does its own padding.
            # TPU-only: on CPU the interpret-mode tests keep exercising
            # the exchange machinery this fast path would bypass.
            from mpi_and_open_mp_tpu.ops.pallas_life import (
                life_run_vmem, native_path, native_tile_cells)

            ny, nx = self.cfg.shape
            fy, fx = plan.frame
            # on_tpu must mirror life_run_vmem's own dispatch decision
            # or this provenance label could name a path that never runs.
            path = native_path((ny, nx), on_tpu=not interpret)
            self.plan_note = f"serial-1dev:{path}"
            self._tile_cells = native_tile_cells(path, (ny, nx))

            @jax.jit
            def advance(board, n):
                _note_retrace("life_advance_bitfused")
                with jax.named_scope("life_advance"):
                    out = life_run_vmem(board[:ny, :nx], jnp.int32(n))
                    out = jnp.pad(out, ((0, fy - ny), (0, fx - nx)))
                return lax.with_sharding_constraint(
                    out.astype(jnp.uint8), self.sharding)

            return advance

        # Packed overlap: window-mode exact-frame row shards split each
        # round into interior (the raw slab is its own window — the outer
        # h words play the halo role) and two 3h-word edge extensions,
        # so the ghost ppermute flies while the interior kernel runs —
        # one halo word carries 32 board rows, the overlap win
        # multiplied (parallel.haloplan module docs). The haloplan
        # carries the env kill switch + degenerate-geometry gates; depth
        # is the full 32h-bit-row fuse budget of one exchange round.
        eligible = bitlife.plan_overlap_supported(plan)
        hp = (
            haloplan.plan_halo(
                "row", (plan.py, plan.px), (32 * plan.nw_s, plan.W),
                32 * plan.h, 1, pack_layout="packed")
            if eligible else None
        )
        use_overlap = hp is not None and hp.overlap
        if mesh.size > 1:
            self._exchange = (plan, _round_halo_bytes(plan))
        self._tile_cells = bitlife.plan_tile_cells(plan, use_overlap)
        # 1-shard / ineligible geometry keeps the bare mode string (the
        # historical note); capable geometry appends the schedule stamp.
        self.plan_note = (
            f"{plan.mode}+{hp.engine}" if hp is not None else plan.mode
        )
        step_call = bitlife.make_plan_stepper(plan, interpret=interpret)
        if use_overlap:
            interior_call, edge_call = bitlife.make_overlap_steppers(
                plan, interpret=interpret)

        def shard_fn(block, n):
            packed = bitlife.pack_board_exact(block)

            def body(carry):
                q, rem = carry
                k = jnp.minimum(rem, plan.k_max)
                kk = k.reshape(1)
                if use_overlap:
                    # Ghosts issued first, consumed last: the interior
                    # window reads only local words, so XLA's scheduler
                    # pairs the permute-start with a done after it.
                    haloplan._note_schedule(hp)
                    top, bot = haloplan.packed_ghosts_y(q, plan.h)
                    mid = interior_call(kk, q)
                    lead = edge_call(
                        kk, jnp.concatenate([top, q[: 2 * plan.h]]))
                    tail = edge_call(
                        kk, jnp.concatenate([q[-2 * plan.h:], bot]))
                    out = jnp.concatenate([lead, mid, tail])
                    return out, rem - k
                # The packed, k_max-amortised ghost exchange: the same
                # ring halos as every other impl, in word rows / lane
                # columns (cf. 3-life/life_mpi.c:203-207, 4-life:197-208).
                # Axes the mesh doesn't shard wrap locally — same content,
                # no collective; unsharded unaligned x needs nothing at
                # all (the kernel's wrap-patched rolls are exact).
                e = q
                if plan.x_sharded:
                    e = halo.packed_halo_x(e, "x", plan.hx, pad=plan.pad_x)
                if plan.y_sharded:
                    e = halo.packed_halo_y(e, "y", plan.h, pad=plan.pad_y)
                else:
                    e = bitlife.local_wrap_y(plan, e)
                return step_call(k.reshape(1), e), rem - k

            q, _ = lax.while_loop(
                lambda c: c[1] > 0, body, (packed, jnp.int32(n))
            )
            return bitlife.unpack_board_exact(q).astype(jnp.uint8)

        smapped = jax.shard_map(
            shard_fn,
            mesh=mesh,
            in_specs=(spec, P()),
            out_specs=spec,
            check_vma=False,
        )

        @jax.jit
        def advance(board, n):
            _note_retrace("life_advance_bitfused")
            with jax.named_scope("life_advance"):
                return smapped(board, jnp.int32(n))

        return advance

    def _build_frames(self) -> Callable:
        """Return ``frames(board, count, last)``, the program of one chunk
        of ``run()``'s snapshot path, jit-cached on ``count`` and ``last``.

        It takes the board at a save point and gives ``count`` frames:
        each the board cropped to ``(ny, nx)``, then ``save_steps`` steps
        of ``advance``, except that the last frame's interval is ``last``
        steps. It returns the board after them and the ``(count, ny,
        nx)`` frames, so that the host makes one dispatch and one fetch a
        chunk instead of one of each a frame.
        """
        ny, nx = self.cfg.shape
        every = self.cfg.save_steps

        @functools.partial(jax.jit, static_argnums=(1, 2))
        def frames(board, count, last):
            _note_retrace("life_frames")

            def interval(b, _):
                return self._advance(b, every), b[:ny, :nx]

            with jax.named_scope("life_frames"):
                board, head = lax.scan(interval, board, length=count - 1)
                tail = board[None, :ny, :nx]
                board = self._advance(board, last)
            return board, jnp.concatenate([head, tail])

        return frames

    def _build_pack(self) -> Callable[[jnp.ndarray], jnp.ndarray] | None:
        """The program ``collect()`` packs the board with, or None where
        it fetches bytes.

        It packs 32 cells to a ``uint32`` along x: bit ``j`` of word ``k``
        in row ``y`` holds cell ``(y, 32k + j)``, so ``(..., ny, nx)``
        becomes ``(..., ny, nx // 32)`` under the board's own
        ``PartitionSpec``, each shard packing its own cells with no
        collective. It exists only where it pays: a board this process
        addresses whole (multi-host boards gather bytes), a shard width
        that is a multiple of 32, and a board of at least
        ``_PACK_MIN_BYTES``.
        """
        board = self.board
        if (board.nbytes < _PACK_MIN_BYTES
                or not board.sharding.is_fully_addressable):
            return None
        if board.sharding.shard_shape(board.shape)[-1] % 32:
            return None

        def pack(b):
            w = b.astype(jnp.uint32).reshape(
                *b.shape[:-1], b.shape[-1] // 32, 32)
            return (w << jnp.arange(32, dtype=jnp.uint32)).sum(
                -1, dtype=jnp.uint32)

        if self.sharding is not None:
            pack = jax.shard_map(pack, mesh=self.mesh,
                                 in_specs=self.sharding.spec,
                                 out_specs=self.sharding.spec)
        return jax.jit(pack)

    # ------------------------------------------------------------ public API

    def step(self, n: int = 1) -> None:
        """Advance ``n`` steps."""
        self.board = self._advance(self.board, int(n))
        self.step_count += n

    def sync(self) -> None:
        """Wait for all dispatched device work on the board to complete.

        The timing analog of the reference's implicit synchronisation at
        its ``MPI_Wtime`` bracket (``3-life/life_mpi.c:64-67``): JAX
        dispatch is async, so timed sections must end here (or at a host
        fetch). Mesh-placed boards also get a one-element fetch after the
        block (``utils.timing.anchor_sync``); single-device boards skip
        it, since the fetch would cost a host round trip inside the timing
        bracket.
        """
        from mpi_and_open_mp_tpu.utils.timing import anchor_sync

        anchor_sync(self.board)

    def reset(self) -> None:
        """Restore the initial board without rebuilding compiled steppers.

        This starts the next run. Under ``MOMP_TRACE`` the upload is the
        span ``life.upload``, anchored on the new board, so that it covers
        the host-to-device transfer and not only its enqueue.
        """
        self._run_id += 1
        with trace.span("life.upload", run=self._run_id) as sp:
            board = jnp.asarray(self._initial, dtype=jnp.uint8)
            self.board = (
                jax.device_put(board, self.sharding) if self.sharding
                else board
            )
            sp.set(bytes=self.board.nbytes).anchor(self.board)
        self.step_count = self._initial_step

    def save_checkpoint(self, path: str | os.PathLike) -> None:
        """Orbax checkpoint of the live sharded state (see utils.checkpoint:
        no gather-to-root on multi-host, unlike the VTK snapshot path)."""
        from mpi_and_open_mp_tpu.utils import checkpoint

        checkpoint.save(path, self.board, self.step_count)

    @classmethod
    def from_checkpoint(
        cls, path: str | os.PathLike, cfg: LifeConfig, **kwargs
    ) -> "LifeSim":
        """Resume from an Orbax checkpoint, re-sharding onto this mesh."""
        from mpi_and_open_mp_tpu.utils import checkpoint

        board, step = checkpoint.restore(path)
        # Stored state is the padded board; crop to the logical shape (the
        # constructor re-pads for its own mesh).
        board = board[: cfg.ny, : cfg.nx]
        return cls(cfg, initial_board=board, initial_step=step, **kwargs)

    @classmethod
    def from_snapshot(
        cls, cfg: LifeConfig, snapshot_path: str, step: int, **kwargs
    ) -> "LifeSim":
        """Resume a run from a VTK snapshot (checkpoint/restart).

        The reference's periodic VTK dump (``3-life/life_mpi.c:51-58``) is a
        full-board serialisation; this turns it into an actual restart
        capability the reference lacks (SURVEY §5): ``run()`` continues from
        ``step`` with the original save cadence and step budget.
        """
        from mpi_and_open_mp_tpu.utils import vtk as vtk_lib

        board = vtk_lib.read_vtk(snapshot_path)
        return cls(cfg, initial_board=board, initial_step=step, **kwargs)

    def _next_stop(self, i: int, save: bool) -> int:
        """First step index after ``i`` where run() must pause the advance:
        the end of the budget, a snapshot/checkpoint boundary, or a pending
        simulated-preemption point (segments never straddle the preempt
        step — the flush must happen exactly there)."""
        from mpi_and_open_mp_tpu.robust import chaos

        cfg = self.cfg
        stops = [cfg.steps]
        if save and cfg.save_steps > 0:
            stops.append((i // cfg.save_steps + 1) * cfg.save_steps)
        ck = self.checkpoint_every
        if self.checkpoint_dir is not None and ck > 0:
            stops.append((i // ck + 1) * ck)
        plan = chaos.active_plan()
        if plan is not None and plan.preempt_pending(i):
            stops.append(plan.preempt_step)
        return min(s for s in stops if s > i)

    def _segment_lengths(self, save: bool = True) -> list[int]:
        """Distinct ``advance`` step counts a full ``run()`` will request."""
        i = self.step_count
        lengths = set()
        while i < self.cfg.steps:
            next_stop = self._next_stop(i, save)
            lengths.add(next_stop - i)
            i = next_stop
        return sorted(lengths)

    def _frame_chunks(self, save: bool) -> tuple[int, list] | None:
        """The chunked snapshot path of ``run()`` from ``step_count``, or
        None where ``run()`` stops at every saved step.

        It is taken where the save cadence is the loop's only stop: VTK
        frames and no checkpoints, no fault plan, guards off, one board,
        one process, and at least two frames to a chunk of
        ``_FRAME_CHUNK_BYTES``. It is ``(lead, chunks)``: the steps up to
        the first save point, then ``(start, count, last)`` for each
        chunk, ``last`` being the steps after its last frame.
        """
        from mpi_and_open_mp_tpu.robust import chaos, guards

        cfg = self.cfg
        every = cfg.save_steps
        size = _FRAME_CHUNK_BYTES // (cfg.ny * cfg.nx)
        if not (save and every > 0 and size > 1
                and self.outdir is not None and self.checkpoint_dir is None
                and self.batch is None and chaos.active_plan() is None
                and not guards.guards_active()
                and jax.process_count() == 1):
            return None
        i = min(-(-self.step_count // every) * every, cfg.steps)
        lead = max(0, i - self.step_count)
        chunks = []
        while i < cfg.steps:
            count = min(size, -(-(cfg.steps - i) // every))
            stop = min(i + count * every, cfg.steps)
            chunks.append((i, count, stop - i - (count - 1) * every))
            i = stop
        return lead, chunks

    def _consistency_violation(self) -> str | None:
        """The semantic halo-consistency probe, as a description or None.

        Life's stencil output is ALWAYS binary, so a value invariant alone
        can never catch a corrupted halo row after a step — the meaningful
        check is (a) the cheap binary-domain scan plus (b) a single-step
        parity probe: one step of the configured pipeline from the current
        collected board must equal one oracle (NumPy) step. Under an active
        fault plan the n=1 probe program traces through the same injection
        hooks as the segment program (faults are sticky at trace time), so
        a poisoned exchange cannot hide from the probe.
        """
        # Bytes, not packed words: packing would fold a corrupt cell (a 2)
        # into the 0/1 bits and hide it from the domain scan.
        before = self._collect(packed=False)
        if not np.isin(before, (0, 1)).all():
            return "non-binary cells on the board"
        after_impl = np.asarray(
            jax.device_get(self._advance(self.board, 1)), dtype=np.uint8,
        )[..., : self.cfg.ny, : self.cfg.nx]
        expect = _oracle_step(before)
        if not np.array_equal(after_impl, expect):
            if self.batch is not None:
                # PER-BOARD honesty: name every diverging board of the
                # stack, not just "the batch diverged".
                bad = [
                    f"board {b}: {int((after_impl[b] != expect[b]).sum())}"
                    for b in range(after_impl.shape[0])
                    if not np.array_equal(after_impl[b], expect[b])
                ]
                return (
                    f"cells diverge from the oracle after one "
                    f"{self.impl}/{self.layout} step ({'; '.join(bad)})"
                )
            diff = int((after_impl != expect).sum())
            return (
                f"{diff} cells diverge from the oracle after one "
                f"{self.impl}/{self.layout} step"
            )
        # The live-board probe alone can be blind: a corrupted exchange
        # whose effect on THIS board's next step happens to be nil leaves
        # earlier accumulated divergence undetected. The same n=1 program
        # on a fixed dense random board is board-state-independent — a
        # poisoned ghost row over a random edge perturbs neighbour counts
        # with near-certainty.
        probe, probe_expect = self._probe_case()
        after_probe = np.asarray(
            jax.device_get(self._advance(probe, 1)), dtype=np.uint8
        )[..., : self.cfg.ny, : self.cfg.nx]
        if not np.array_equal(after_probe, probe_expect):
            diff = int((after_probe != probe_expect).sum())
            return (
                f"{diff} cells diverge from the oracle after one "
                f"{self.impl}/{self.layout} step on the fixed probe board"
            )
        return None

    def _probe_case(self):
        """Cached ``(device_board, oracle_next)`` for the fixed-probe leg of
        ``_consistency_violation`` — placed exactly like the live board."""
        if self._probe is None:
            rng = np.random.default_rng(0xC0FFEE)
            shape = (self.cfg.ny, self.cfg.nx)
            if self.batch is not None:
                # B DISTINCT dense boards (one rng stream): a fault that
                # corrupts only some stack positions must still perturb
                # the board that sits there.
                shape = (self.batch, *shape)
            host = rng.integers(0, 2, shape, dtype=np.uint8)
            if self.batch is None and self.padded_shape != self.cfg.shape:
                full = np.zeros(self.padded_shape, dtype=np.uint8)
                full[: self.cfg.ny, : self.cfg.nx] = host
            else:
                full = host
            b = jnp.asarray(full, dtype=jnp.uint8)
            b = jax.device_put(b, self.sharding) if self.sharding else b
            self._probe = (b, _oracle_step(host))
        return self._probe

    def debug_check(self) -> None:
        """Debug mode: assert halo-exchange consistency on the live state.

        The reference's blocking-send halo pattern is its main unchecked
        race/deadlock surface (SURVEY §5, ``3-life/life_mpi.c:203-207``);
        deterministic collectives make a data race impossible here, so the
        meaningful assertion is semantic: one step through the configured
        (halo/pallas/roll) pipeline must equal the oracle step on the
        gathered global board. Raises AssertionError with a cell-diff count
        on mismatch.
        """
        why = self._consistency_violation()
        if why is not None:
            raise AssertionError(f"halo debug check failed: {why}")

    def _set_board(self, board: np.ndarray, step: int) -> None:
        """Install a host board as the live state (pad + device_put), the
        same placement the constructor performs."""
        board = np.asarray(board, dtype=np.uint8)
        if (self.batch is None
                and board.shape[-2:] != tuple(self.padded_shape)):
            full = np.zeros(self.padded_shape, dtype=np.uint8)
            full[: self.cfg.ny, : self.cfg.nx] = board
            board = full
        b = jnp.asarray(board, dtype=jnp.uint8)
        self.board = jax.device_put(b, self.sharding) if self.sharding else b
        self.step_count = int(step)

    def _checkpoint_now(self) -> str:
        path = os.path.join(
            self.checkpoint_dir, f"step_{self.step_count:06d}")
        self.save_checkpoint(path)
        return path

    def _guarded_step(self, n: int) -> None:
        """``step(n)`` with the halo-exchange checksum guard armed.

        On a consistency violation: rebuild the compiled steppers with
        injection suppressed (the poisoned traces are cached on the old
        wrappers — a transient fault must not re-fire on the dispatch that
        retries it), restore the pre-segment board and re-step; if even the
        clean re-trace diverges, replay the segment on the NumPy oracle as
        the last resort. Every recovery stamps ``self.recoveries`` and the
        process-wide log ``bench.py`` publishes.
        """
        from mpi_and_open_mp_tpu.robust import chaos, guards

        prev_board = self.board
        prev_step = self.step_count
        self.step(n)
        why = self._consistency_violation()
        if why is None:
            return
        with chaos.suppressed():
            self._advance = self._build_advance()
            self.board = prev_board
            self.step_count = prev_step
            self.step(n)
            still = self._consistency_violation()
        if still is None:
            stamp = f"life_step:{self.impl}:recovered"
            self.recoveries.append(f"{stamp} ({why})")
            guards.record_recovery(stamp)
            return
        board = np.asarray(jax.device_get(prev_board), dtype=np.uint8)[
            ..., : self.cfg.ny, : self.cfg.nx]
        for _ in range(n):
            board = _oracle_step(board)
        self._set_board(board, prev_step + n)
        stamp = "life_step:numpy-oracle:recovered"
        self.recoveries.append(f"{stamp} ({why}; then {still})")
        guards.record_recovery(stamp)

    def warmup(self) -> None:
        """Compile every stepper a subsequent ``run()`` will hit.

        ``advance`` is jit-cached per static step count ON THIS INSTANCE, so
        warm-up must use the same instance and the same counts; it runs each
        compiled program once on the current board and discards the result
        (``advance`` is functional — state is untouched). Synchronisation
        goes through ``anchor_sync`` (not a whole-array fetch): on
        multi-host runs the board spans non-addressable devices, where a
        full ``device_get`` is impossible.

        Where ``run()`` takes its chunked snapshot path
        (``_frame_chunks``), that is the advance up to the first save
        point and the chunk program of each chunk length it will use: the
        full chunk and the last one.
        """
        from mpi_and_open_mp_tpu.utils.timing import anchor_sync

        chunked = self._frame_chunks(save=True)
        if chunked is None:
            lengths = self._segment_lengths()
        else:
            lead, chunks = chunked
            lengths = [lead] if lead else []
            for shape in sorted({c[1:] for c in chunks}):
                anchor_sync(self._frames(self.board, *shape), fetch_all=True)
        for n in lengths:
            anchor_sync(self._advance(self.board, n), fetch_all=True)
        if self._pack is not None:
            anchor_sync(self._pack(self.board), fetch_all=True)

    def collect(self) -> np.ndarray:
        """Gather the global board to the host (uint8 ``(ny, nx)``).

        Where ``_build_pack`` allows (Life, a board this process
        addresses whole, a shard width that is a multiple of 32, a board
        of at least ``_PACK_MIN_BYTES``), the device packs the board 32
        cells to a ``uint32`` word and only the words cross to the host,
        an eighth of the board's bytes, which ``np.unpackbits`` turns back
        into the same 0/1 board in one pass (``_unpack_words``).
        Elsewhere the board's bytes cross as they are. On multi-host
        (``jax.distributed``) runs the board is not fully addressable from
        one process, so the gather goes through a cross-process allgather
        — every host gets the full board, the multi-host generalisation of
        the reference's gather-to-root (``5-gather/life_mpi.c:178``). The
        halo guard (``_consistency_violation``) always fetches bytes: a
        packed word cannot show a cell that is neither 0 nor 1.

        The fetch is synchronous, so the span ``life.collect`` covers the
        pack, the transfer, the host's unpacking or reordering of the
        device layout and the crop; it carries ``packed`` and
        ``wire_bytes``, the bytes fetched to the host.
        """
        return self._collect(packed=self._pack is not None)

    def _collect(self, packed: bool) -> np.ndarray:
        with trace.span("life.collect", run=self._run_id,
                        bytes=self.board.nbytes, packed=packed) as sp:
            if packed:
                words = jax.device_get(self._pack(self.board))
                sp.set(wire_bytes=words.nbytes)
                full = _unpack_words(words)
                return full[..., : self.cfg.ny, : self.cfg.nx]
            sp.set(wire_bytes=self.board.nbytes)
            if self.board.is_fully_addressable:
                full = np.asarray(jax.device_get(self.board), dtype=np.uint8)
            else:
                from jax.experimental import multihost_utils

                full = np.asarray(
                    multihost_utils.process_allgather(
                        self.board, tiled=True),
                    dtype=np.uint8,
                )
            # Ellipsis crop: batched boards are (B, ny, nx), the crop
            # applies to the trailing board axes either way.
            return full[..., : self.cfg.ny, : self.cfg.nx]

    def save_snapshot(self, frame: np.ndarray | None = None,
                      step: int | None = None) -> str:
        """Write the VTK frame of the live board at ``step_count``, or of
        ``frame``, a host board of step ``step`` (a frame that ``run()``'s
        chunked path has already fetched). Returns its path."""
        assert self.outdir is not None, "LifeSim(outdir=...) required to save"
        if frame is None:
            step = self.step_count
        path = vtk_lib.vtk_path(self.outdir, step)
        # collect() is COLLECTIVE on multi-host runs (cross-process
        # allgather) — every process must enter it; only process 0 writes
        # the file, the reference's write-from-one-rank discipline
        # (3-life/life_mpi.c:54-57; shared-FS double-writes otherwise).
        with trace.span("life.snapshot", run=self._run_id, step=step):
            board = self.collect() if frame is None else frame
            if jax.process_index() == 0:
                with trace.span("life.vtk_write", run=self._run_id) as sp:
                    os.makedirs(self.outdir, exist_ok=True)
                    writer = vtk_lib.write_vtk(path, board)
                    sp.set(bytes=os.path.getsize(path), writer=writer)
        return path

    def save_state(self) -> None:
        """Persist the current step through every configured channel: VTK
        snapshot (``outdir``) and/or Orbax checkpoint (``checkpoint_dir``)."""
        if self.outdir is not None:
            self.save_snapshot()
        if self.checkpoint_dir is not None:
            self.save_checkpoint(
                os.path.join(self.checkpoint_dir, f"step_{self.step_count:06d}")
            )

    def _step_attrs(self, *advances: int) -> dict:
        """The counters of a stepping span, for ``advance`` calls of these
        step counts. An advance through a fused stepper (the tiled
        ``life_fused_tiles`` or the whole-shard ``life_fused_window``)
        carries ``window_cells`` (the cells one fused step computes over
        all chips and grid programs, halo rows and columns included),
        ``frame_cells`` (the frame it writes) and ``board_cells``
        (``ny * nx``). A sharded bitfused advance carries
        ``board_cells``, ``frame_cells`` (the padded frame the kernel
        steps), ``rounds`` (exchange rounds, ``k_max`` steps or fewer
        each) and ``halo_bytes`` (what one chip sends over ``ppermute``
        in them). Empty on every other path."""
        attrs = dict(self._tile_cells)
        if self._exchange is not None:
            plan, round_bytes = self._exchange
            rounds = sum(-(-n // plan.k_max) for n in advances)
            attrs.update(board_cells=plan.shape[0] * plan.shape[1],
                         frame_cells=plan.frame[0] * plan.frame[1],
                         rounds=rounds, halo_bytes=rounds * round_bytes)
        return attrs

    def _segment_span(self, start: int, stop: int, guarded: bool = False,
                      advances: tuple[int, ...] = ()):
        """The span of ``run()``'s stepping from ``start`` to ``stop``:
        one advance of ``stop - start`` steps, or the ``advances`` of a
        snapshot chunk."""
        return trace.span("life.segment", run=self._run_id, start=start,
                          stop=stop, impl=self.impl, layout=self.layout,
                          guarded=guarded,
                          **self._step_attrs(*advances or (stop - start,)))

    def _run_chunked(self, lead: int, chunks: list) -> None:
        """``run()``'s chunked snapshot path (``_frame_chunks``): one
        dispatch of the chunk program and one fetch of its frames a chunk,
        then each frame's write in step order. The span ``life.frames``
        (``start``, ``frames``, ``wire_bytes``) holds a chunk's dispatch,
        as ``life.segment``, and its fetch."""
        every = self.cfg.save_steps
        if lead:
            with self._segment_span(self.step_count,
                                    self.step_count + lead) as sp:
                self.step(lead)
                sp.anchor(self.board)
        for start, count, last in chunks:
            stop = start + (count - 1) * every + last
            with trace.span("life.frames", run=self._run_id, start=start,
                            frames=count) as fsp:
                with self._segment_span(
                        start, stop,
                        advances=(every,) * (count - 1) + (last,)) as sp:
                    self.board, frames = self._frames(self.board, count, last)
                    sp.anchor(self.board)
                frames = jax.device_get(frames)
                fsp.set(wire_bytes=frames.nbytes)
            self.step_count = stop
            for k, frame in enumerate(frames):
                self.save_snapshot(frame, start + k * every)

    def run(self, save: bool | None = None) -> np.ndarray:
        """Run ``cfg.steps`` steps with the reference's save cadence.

        Snapshots are written at every step index ``i < steps`` with
        ``i % save_steps == 0`` (before stepping), matching
        ``3-life/life_mpi.c:51-58``. Returns the final board.

        Where the save cadence is the loop's only stop (VTK frames, no
        checkpoints, no fault plan, guards off, one board, one process)
        and two frames or more fit in ``_FRAME_CHUNK_BYTES``, the frames
        come in chunks (``_frame_chunks``): one device program steps a
        chunk of save intervals and stacks the board of each save point,
        one fetch brings them to the host, and they are written in step
        order. The frames, their steps, their bytes and the final board
        are those of the loop below, which stops at every saved step and
        serves every other case.

        Robustness (all inert on the default path): periodic Orbax
        checkpoints every ``checkpoint_every`` steps; SIGTERM/SIGINT flush
        a final checkpoint at the next segment boundary and raise
        :class:`~mpi_and_open_mp_tpu.robust.preempt.Preempted`; an active
        ``MOMP_CHAOS`` plan can inject halo faults (caught by the guarded
        step) or fire a simulated preemption at a fixed step; guards are
        armed by the plan or ``MOMP_GUARD=1``.
        """
        from mpi_and_open_mp_tpu.robust import chaos, guards, preempt

        cfg = self.cfg
        if save is None:
            save = self.outdir is not None or self.checkpoint_dir is not None
        # save_steps <= 0 means "never save" (the reference's 999999 idiom,
        # p46gun_big.cfg, taken to its limit); so does save=False.
        save = save and cfg.save_steps > 0
        plan = chaos.active_plan()
        guard = guards.guards_active()
        checkpointing = (
            self.checkpoint_dir is not None and self.checkpoint_every > 0
        )
        if not save and not checkpointing and plan is None and not guard:
            # The default fast path, unchanged: one advance covers the
            # whole budget, no host round trips inside it. The span (a
            # shared no-op singleton when MOMP_TRACE is unset) anchors on
            # the board so its duration covers execution, not dispatch.
            if cfg.steps > self.step_count:
                steps = cfg.steps - self.step_count
                with trace.span(
                    "life.advance",
                    run=self._run_id,
                    steps=steps,
                    impl=self.impl,
                    layout=self.layout,
                    **self._step_attrs(steps),
                ) as sp:
                    self.step(steps)
                    sp.anchor(self.board)
            return self.collect()
        chunked = self._frame_chunks(save)
        if chunked is not None:
            self._run_chunked(*chunked)
            return self.collect()
        i = self.step_count
        with preempt.flush_on_signal(
                enabled=self.checkpoint_dir is not None) as sig:
            while i < cfg.steps:
                if sig.fired is not None:
                    # A real SIGTERM/SIGINT landed mid-run: flush a
                    # restart point at this segment boundary and hand the
                    # driver the exit-75 contract (preempt module docs).
                    path = (self._checkpoint_now()
                            if self.checkpoint_dir is not None else None)
                    raise preempt.Preempted(
                        i, checkpoint=path, signum=sig.fired)
                if save and i % cfg.save_steps == 0:
                    self.save_state()
                elif checkpointing and i > 0 and i % self.checkpoint_every == 0:
                    self._checkpoint_now()
                if plan is not None and plan.delay_s:
                    time.sleep(plan.delay_s)
                # Advance to the next boundary in one jit call.
                next_stop = self._next_stop(i, save)
                with self._segment_span(i, next_stop, guard) as sp:
                    if guard:
                        self._guarded_step(next_stop - i)
                    else:
                        self.step(next_stop - i)
                    sp.anchor(self.board)
                prev_i, i = i, next_stop
                if (plan is not None and plan.preempt_step is not None
                        and not plan.preempt_fired
                        and prev_i < plan.preempt_step <= i):
                    plan.preempt_fired = True
                    path = (self._checkpoint_now()
                            if self.checkpoint_dir is not None else None)
                    raise preempt.SimulatedPreemption(i, checkpoint=path)
        return self.collect()
