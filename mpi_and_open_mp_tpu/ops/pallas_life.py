"""Pallas TPU kernels for the Life stencil.

The reference's "native layer" is its compiled C kernels
(``/root/reference/3-life/life_mpi.c:150-176`` and friends); here the native
compute layer is Mosaic-compiled Pallas:

* ``life_run_vmem`` — the flagship single-shard dispatcher. Boards up to
  ~3200² bit-pack into VMEM (``ops.bitlife``) with the ENTIRE step loop
  inside one kernel launch, so 10,000 steps cost one dispatch and zero
  HBM round trips; bigger aligned boards run the multi-step-fused tiled
  kernel (``bitlife.life_run_fused_bits``); anything else takes the
  compiled-XLA packed loop (``bitlife.life_run_bits_xla``). Torus wrap
  everywhere is circular shifting — exactly the reference's ``ind()``
  modular indexing (``3-life/life2d.c:9``), vectorised on the VPU.
* ``life_step_padded_pallas`` — one stencil step over a halo-padded block,
  used as the per-shard kernel inside the ``shard_map`` halo path.

(Two earlier big-board paths lived here — an int32 explicit-DMA row-tiled
stencil and an unpacked XLA roll fallback; both were superseded by the
packed fused/XLA pair above and removed rather than kept as dead code.)

All are bit-exact against the NumPy oracle (integer 0/1 state). On
non-TPU backends the kernels run in Pallas interpret mode so CPU tests
exercise the same code path.
"""

from __future__ import annotations

import contextlib
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mpi_and_open_mp_tpu.ops import life_ops

# Keep the in-kernel board + temporaries comfortably inside VMEM.
_VMEM_BYTES_LIMIT = 4 * 1024 * 1024

# Board-sliced batched layout (ops.bitlife pack_batch_bits): bit axis =
# batch, 32 boards per uint32 word, one vector op advances every world.
# MOMP_BITSLICE=0 pins every batched dispatch back to the cell-packed
# ladder (the regression sentinel flags that as a provenance downgrade —
# the switch exists for triage, not for quiet production use).
_BITSLICE = os.environ.get("MOMP_BITSLICE", "1") != "0"

# Below this batch the plane is >75% padding and the cell-packed ladder
# (which scales its work with B, not ceil(B/32)) stays competitive.
BITSLICE_MIN_BATCH = 8


@contextlib.contextmanager
def _bitslice_pinned(value: bool):
    """Pin the bitsliced layout gate for one dispatch: the serve
    daemon's guarded fallback rung re-dispatches a poisoned bitsliced
    bucket on the cell-packed ladder by re-planning with the layout
    pinned off (same shape, distinct engine + jit cache key — the flag
    is read at plan time, like ``context._ring_hop_pinned``)."""
    global _BITSLICE
    prev = _BITSLICE
    _BITSLICE = value
    try:
        yield
    finally:
        _BITSLICE = prev


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# Installed tuned plans: (workload, *stack shape) -> engine path — the
# shape rides whole so multi-channel stacks (gray_scott's 4-D (B, C, ny,
# nx)) key cleanly. Populated by tune.plans.PlanStore.install() after
# each record survives its CRC / fingerprint / parity gates; consulted
# by native_path_batch BEFORE the static heuristics. MOMP_TUNE=0 is the
# kill switch — read per call, not at import, so a triage export takes
# effect on the very next dispatch.
_PLANNED_PATHS: dict[tuple, str] = {}


def _tune_enabled() -> bool:
    return os.environ.get("MOMP_TUNE", "1") != "0"


def _plan_key(workload: str, shape) -> tuple:
    return (str(workload), *(int(x) for x in shape))


def install_planned_path(workload: str, shape, path: str) -> None:
    """Install a tuned engine path for one (workload, stack shape).
    Only ``tune.plans`` calls this, AFTER the record passed its
    durability and parity gates — nothing here re-validates."""
    _PLANNED_PATHS[_plan_key(workload, shape)] = str(path)


def planned_path(workload: str, shape) -> str | None:
    """The installed tuned path for (workload, stack shape), or ``None``
    when no plan is installed or ``MOMP_TUNE=0`` pins tuning off. An
    installed ``stencil:sep``/``stencil:fft`` plan whose family the
    ``MOMP_ENGINE_FAMILY`` pin disallows is neutralized the same way —
    the pin takes effect at the NEXT dispatch, no uninstall needed."""
    if not _tune_enabled():
        return None
    path = _PLANNED_PATHS.get(_plan_key(workload, shape))
    if path is not None and path.startswith("stencil:"):
        from mpi_and_open_mp_tpu.stencils import engine as stencil_engine

        if not stencil_engine.family_allowed(
                stencil_engine.family_for_path(path)):
            return None
    return path


def clear_planned_paths() -> None:
    _PLANNED_PATHS.clear()


@contextlib.contextmanager
def _planned_pinned(workload: str, shape, path: str | None):
    """Pin one (workload, shape) plan entry for the duration — the
    fingerprint trick behind plan/executable co-location: computing the
    AOT fingerprint under the plan's choice pinned IN yields the same
    digest the serving process computes once the plan is installed, so
    ``<digest>.plan`` and ``<digest>.aot`` land side by side. Pinning
    ``None`` removes any entry (how ``tune.space.heuristic_path`` asks
    what the static ladder would do, untouched by the plan under test)."""
    key = _plan_key(workload, shape)
    missing = object()
    prev = _PLANNED_PATHS.get(key, missing)
    if path is None:
        _PLANNED_PATHS.pop(key, None)
    else:
        _PLANNED_PATHS[key] = str(path)
    try:
        yield
    finally:
        if prev is missing:
            _PLANNED_PATHS.pop(key, None)
        else:
            _PLANNED_PATHS[key] = prev


def _planned_legal(
    path: str, shape: tuple[int, int, int], on_tpu: bool,
    allow_bitsliced: bool,
) -> bool:
    """Hard legality for an installed plan's path on THIS process: VMEM
    fits, backend support, and the runtime pins (``MOMP_BITSLICE=0``,
    the daemon's ``allow_bitsliced=False`` fallback rung) all stay
    binding — a plan may override the BITSLICE_MIN_BATCH heuristic, but
    never dispatch an engine that cannot run here."""
    from mpi_and_open_mp_tpu.ops import bitlife

    b, ny, nx = shape
    if path == "bitsliced":
        return (
            allow_bitsliced
            and _BITSLICE
            and bitlife.fits_vmem_bitsliced(shape)
        )
    if path == "vmem":
        return on_tpu and bitlife.fits_vmem_packed_batch(shape)
    if path == "vmem-grid":
        return on_tpu and bitlife.fits_vmem_packed((ny, nx))
    if path == "fused":
        return on_tpu and bitlife.fused_bits_supported((ny, nx))
    if path == "frame":
        return on_tpu and bitlife.plan_sharded_bits(
            (ny, nx), 1, 1, False, False
        ) is not None
    return path == "xla"


def fits_vmem(shape: tuple[int, int]) -> bool:
    ny, nx = shape
    return ny * nx * 4 <= _VMEM_BYTES_LIMIT


def native_path(shape: tuple[int, int], on_tpu: bool = True) -> str:
    """Which native path :func:`life_run_vmem` dispatches ``shape`` to:
    ``"vmem"`` (whole-board VMEM-resident packed loop), ``"fused"``
    (multi-step-fused tiled kernel), ``"frame"`` (padded-torus-frame
    runner for unaligned big boards), or ``"xla"`` (compiled-XLA packed
    loop). The single source of truth for the dispatch decision — the
    recorded-results sweeps label their rows with this."""
    from mpi_and_open_mp_tpu.ops import bitlife

    if bitlife.fits_vmem_packed(shape):
        return "vmem"
    if on_tpu:
        # Interpret-mode Pallas at big-board sizes is impractical; CPU
        # takes the XLA loop (the fused kernels are covered in interpret
        # mode by tests at small shapes).
        if bitlife.fused_bits_supported(shape):
            return "fused"
        if bitlife.plan_sharded_bits(shape, 1, 1, False, False) is not None:
            return "frame"
    return "xla"


def native_tile_cells(
    path: str, shape: tuple[int, int], boards: int = 1
) -> dict:
    """The counters of a stepping span whose advance steps ``boards``
    boards of ``shape`` on the native ``path`` (what :func:`native_path`
    or :func:`native_path_batch` answered) through a fused stepper (the
    aligned tiled runner, or the frame runner's plan stepper):
    ``window_cells`` (what one fused step computes, halo rows and columns
    included), ``frame_cells`` (the frame it writes) and ``board_cells``.
    Empty on the paths that run neither; host arithmetic only."""
    from mpi_and_open_mp_tpu.ops import bitlife

    if path == "fused":
        cells = bitlife.fused_tile_cells(shape)
    elif path == "frame":
        cells = bitlife.plan_tile_cells(
            bitlife.plan_sharded_bits(shape, 1, 1, False, False))
    else:
        return {}
    return {k: boards * v for k, v in cells.items()}


def native_path_batch(
    shape: tuple[int, int, int], on_tpu: bool = True,
    allow_bitsliced: bool = True,
) -> str:
    """Which batched native path :func:`life_run_vmem_batch` dispatches a
    (B, ny, nx) stack to — the single source of truth for batched
    LAYOUT and path, as :func:`native_path` is for single boards:
    ``"bitsliced"`` (board-sliced planes, bit axis = batch — Pallas
    VMEM kernel on hardware, the halo-fused XLA twin elsewhere),
    ``"vmem"`` (whole stack VMEM-resident cell-packed — the gate is B x
    the per-board working set, ``bitlife.fits_vmem_packed_batch``),
    ``"vmem-grid"`` (per-board VMEM-resident, batch axis streamed by a
    Pallas grid), ``"fused"`` / ``"frame"`` (big-board engines, the
    stack scanned inside one program), or ``"xla"`` (vmapped
    compiled-XLA packed loop).

    Small-board/large-B stacks go ``"bitsliced"`` on EVERY backend: B
    boards cost ``ceil(B/32)`` planes of vector work instead of B
    bitplanes, and the XLA twin is the fastest CPU engine too (~8x the
    vmapped cell-packed loop at B=32, 64²). ``MOMP_BITSLICE=0`` (or
    ``allow_bitsliced=False``, the daemon's fallback-rung pin) restores
    the cell-packed ladder. Off-TPU that ladder always lands ``"xla"``:
    a batch exists for THROUGHPUT — interpret mode would grind B boards
    through a Python-level VM while the vmapped packed loop compiles on
    every backend (the batched kernels get their interpret-mode
    coverage from tests/test_batched.py directly).

    An installed tuned plan (``tune/``, keyed by workload + stack
    shape) is consulted FIRST and wins whenever its path is legal for
    this process (:func:`_planned_legal`); the static ladder below is
    the heuristic fallback and the no-plans behavior."""
    from mpi_and_open_mp_tpu.ops import bitlife

    b, ny, nx = shape
    planned = planned_path("life", shape)
    if planned is not None and _planned_legal(
        planned, shape, on_tpu, allow_bitsliced
    ):
        return planned
    if (
        allow_bitsliced
        and _BITSLICE
        and b >= BITSLICE_MIN_BATCH
        and bitlife.fits_vmem_bitsliced(shape)
    ):
        return "bitsliced"
    if on_tpu:
        if bitlife.fits_vmem_packed_batch(shape):
            return "vmem"
        if bitlife.fits_vmem_packed((ny, nx)):
            return "vmem-grid"
        if bitlife.fused_bits_supported((ny, nx)):
            return "fused"
        if bitlife.plan_sharded_bits((ny, nx), 1, 1, False, False) is not None:
            return "frame"
    return "xla"


def batch_pack_layout(
    shape: tuple[int, int, int], on_tpu: bool = True
) -> str:
    """The pack layout :func:`life_run_vmem_batch` uses for a (B, ny,
    nx) stack: ``"bitsliced"`` (bit axis = batch) or ``"cell-packed"``
    (bit axis = space). Derived from :func:`native_path_batch` so the
    two can never disagree; bench lines and the ledger config key
    record this vocabulary."""
    path = native_path_batch(shape, on_tpu=on_tpu)
    return "bitsliced" if path == "bitsliced" else "cell-packed"


def batch_slice_width(
    shape: tuple[int, int], on_tpu: bool = True
) -> int | None:
    """Plane width (32) when (ny, nx) boards can take the bitsliced
    path at some batch size, else ``None``. The serve layer sizes its
    buckets with this: a bitsliced dispatch costs the same for every B
    within a plane, so buckets pad to multiples of 32 (filling planes
    exactly) instead of the pow2 ladder — and admission's
    padding-waste projection must use the SAME width, or tickets get
    shed against the wrong denominator."""
    from mpi_and_open_mp_tpu.ops import bitlife

    ny, nx = shape
    if _BITSLICE and bitlife.fits_vmem_bitsliced(
        (BITSLICE_MIN_BATCH, ny, nx)
    ):
        return 32
    return None


def life_run_vmem_batch(boards: jnp.ndarray, n: int) -> jnp.ndarray:
    """Advance a (B, ny, nx) stack ``n`` steps in ONE dispatch, picking
    the fastest batched native path (see :func:`native_path_batch`).
    Bit-exact per board vs the serial engines; ``n`` is a runtime scalar
    on every path, so one compiled program per stack shape serves any
    step count — the contract the serve-layer bucketing depends on."""
    from mpi_and_open_mp_tpu.ops import bitlife

    path = native_path_batch(boards.shape, on_tpu=not _interpret())
    if path == "bitsliced":
        # Pallas VMEM kernel on hardware; on CPU the halo-fused XLA
        # twin IS the fast path (use_kernel=None picks per backend).
        return bitlife.life_run_bitsliced_batch(
            boards, n, interpret=_interpret()
        )
    if path in ("vmem", "vmem-grid"):
        return bitlife.life_run_vmem_bits_batch(
            boards, n, interpret=_interpret(), resident=(path == "vmem")
        )
    if path == "fused":
        return bitlife.life_run_fused_bits_batch(boards, n)
    if path == "frame":
        return bitlife.life_run_frame_bits_batch(boards, n)
    return bitlife.life_run_bits_xla_batch(boards, n)


def life_run_vmem(board: jnp.ndarray, n: int) -> jnp.ndarray:
    """Advance ``n`` steps on one device, picking the fastest native path.

    The board is bit-packed (32 cells/uint32 word — see ``ops.bitlife``):
    packed boards up to ~3200² stay VMEM-resident with the whole step loop
    in one kernel launch (interpret-mode on CPU, so tests exercise the
    production dispatch); bigger aligned boards run the multi-step-fused
    tiled kernel (one HBM pass per up-to-128 steps); bigger UNALIGNED
    boards take the padded-torus-frame runner (same fused kernels over a
    word/lane-padded frame, ``bitlife.life_run_frame_bits``); anything
    left takes the compiled-XLA packed loop (any shape, any backend).
    ``n`` is a runtime scalar — changing it does not recompile any path.
    """
    from mpi_and_open_mp_tpu.ops import bitlife

    path = native_path(board.shape, on_tpu=not _interpret())
    if path == "vmem":
        return bitlife.life_run_vmem_bits(board, n, interpret=_interpret())
    if path == "fused":
        return bitlife.life_run_fused_bits(board, n)
    if path == "frame":
        return bitlife.life_run_frame_bits(board, n)
    return bitlife.life_run_bits_xla(board, n)


def _padded_step_kernel(p_ref, out_ref):
    out_ref[:] = life_ops.life_step_padded(p_ref[:])


def stencil_step_padded_pallas(spec, padded: jnp.ndarray) -> jnp.ndarray:
    """Spec-generic Pallas twin of :func:`life_step_padded_pallas`: one
    stencil step over a ``radius``-halo-padded block (channels on the
    leading axis ride through), generated from any
    :class:`~..stencils.StencilSpec`.

    The kernel body is ``stencils.engine.step_padded`` — pure slicing +
    the spec's ``update``, the same code the jnp path runs, so Mosaic
    sees a static-shape VPU stencil regardless of rule. Integer specs
    compute in int32 inside the kernel (sub-word dtypes hit Mosaic
    layout gaps — same cast the life kernel carries); float specs stay
    in their native dtype. Over-VMEM blocks take the compiled jnp
    stencil, like the life kernel.
    """
    from mpi_and_open_mp_tpu.stencils import engine as stencil_engine

    r = spec.radius
    h, w = padded.shape[-2] - 2 * r, padded.shape[-1] - 2 * r
    dtype = padded.dtype
    out_shape = (*padded.shape[:-2], h, w)
    if padded.size * 4 > _VMEM_BYTES_LIMIT:
        return stencil_engine.step_padded(spec, padded, jnp)
    compute = dtype if jnp.issubdtype(dtype, jnp.floating) else jnp.int32

    def kernel(p_ref, out_ref):
        out_ref[:] = stencil_engine.step_padded(spec, p_ref[:], jnp)

    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(out_shape, compute),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        interpret=_interpret(),
        name="stencil_step_padded",
    )(padded.astype(compute))
    return out.astype(dtype)


def life_step_padded_pallas(padded: jnp.ndarray) -> jnp.ndarray:
    """Pallas version of ``ops.life_step_padded``: step the interior of a
    halo-padded ``(h+2, w+2)`` block, returning ``(h, w)``.

    Blocks beyond the VMEM budget take the compiled jnp stencil instead
    (``life_ops.life_step_padded``) — see the comment below.
    """
    h, w = padded.shape[0] - 2, padded.shape[1] - 2
    dtype = padded.dtype
    if not fits_vmem(padded.shape):
        # Over-VMEM blocks take the compiled jnp stencil: a halo-padded
        # block has odd dims by construction, and the explicit-DMA row
        # tiling that would stream it needs sublane/lane-aligned slices on
        # real Mosaic.
        return life_ops.life_step_padded(padded)
    p32 = padded.astype(jnp.int32)
    out = pl.pallas_call(
        _padded_step_kernel,
        out_shape=jax.ShapeDtypeStruct((h, w), jnp.int32),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        interpret=_interpret(),
        name="life_step_padded",
    )(p32)
    return out.astype(dtype)
