"""Persistent halo plans: interior/boundary overlap for sharded stencils.

The sequential halo schedule (``parallel.halo``) is the reference's
blocking ghost-row exchange translated to ``ppermute``: every fused round
waits for the full ``(h + 2d, w)`` padded block before ANY compute
starts — exactly the ``MPI_Send``/``MPI_Recv``-then-step serialisation of
``/root/reference/3-life/life_mpi.c:198-209``. PAPERS.md's "Persistent
and Partitioned MPI for Stencil Communication" (arxiv 2508.13370) shows
the fix: derive the exchange ONCE per (mesh, shard shape, depth) as a
persistent plan, and overlap the ghost transfer with the interior cells
that never needed it.

This module is that plan. A frozen :class:`HaloPlan` splits each fused
round of ``k`` steps (ghost depth ``d = k * radius``) into

* an **interior partition** — rows ``[d, h - d)`` of the shard (columns
  for ``col`` layouts), computable from purely local data: ``k`` fused
  steps applied to the RAW shard, each consuming ``radius`` per side, so
  the trimming lands exactly on the interior; and
* a **boundary partition** — two depth-``d`` edge strips, each computed
  from a ``3d``-deep extension ``concat([ghost, edge_2d])`` after the
  ghost ``ppermute`` completes.

The boundary itself can be **partitioned** (``boundary_steps < fuse_steps``):
each edge strip advances in ``boundary_steps``-deep sub-rounds, and every
sub-round's ghost send is issued straight from that strip's freshly
computed cells — per-edge readiness signalling instead of one
barrier-shaped exchange per fused round, arxiv 2508.13370's
``MPI_Pready`` analogue. The interior keeps the full ``fuse_steps``
depth (deeper interior, shallower edges); total ghost volume is
unchanged but moves in ``fuse_steps / boundary_steps`` smaller per-edge
messages that pipeline behind the interior chain.

The ghost permutes are issued FIRST and consumed LAST: they have no data
dependence on the interior compute, so XLA's latency-hiding scheduler
pairs the collective-permute start with a done AFTER the interior stencil
— the ICI transfer hides behind VPU work, the same double-buffered
schedule as the ring-attention hop (``parallel/context.py`` ``hop()``:
step *k*'s edge slices are in flight while step *k*'s interior computes).
The permutes stay unconditional and OUTSIDE any per-device branch or
kernel body — a collective inside a cond/kernel would deadlock the ring
(DESIGN.md §17).

Bit-exactness: interior and boundary apply the SAME per-cell arithmetic
(``step_fn``) to the same neighbourhood values in the same order as the
sequential whole-shard schedule — only the iteration space is
partitioned, so the reassembled shard equals the sequential result
bit-for-bit (integer rules) / value-for-value (floats; no reassociation
is introduced because each output cell's reduction tree is unchanged).
``tests/test_haloplan.py`` fuzzes this for every registry spec.

Engine stamps (ledger/sentinel provenance — ``seq:`` is the downgrade):

* ``overlap:deferred`` — deferred-concat schedule, every backend.
* ``overlap:rdma``     — ghosts move by Pallas async remote copy
  (``MOMP_HALO_RDMA=1``, real TPU, every layout: row/col exchange their
  edge pair over the 1-D ring, cart runs the two-phase corner exchange —
  y edges first, then x edges carrying the corner words); schedule
  unchanged.
* ``…:pb{b}``          — suffix on either overlap stamp when the
  boundary is partitioned at ``boundary_steps = b < fuse_steps``.
* ``overlap:packed``   — the bit-sliced twin (``ops.bitlife``
  ``make_overlap_steppers``): 32 boards per halo word.
* ``seq:halo`` / ``seq:packed`` — the sequential fallback, stamped with
  the reason in :attr:`HaloPlan.why`.

``MOMP_HALO_OVERLAP=0`` is the kill switch (read at PLAN time, so a
long-lived process re-plans under the flag, not under import order).
Degenerate geometry — a 1-shard axis, or a shard too shallow to hold a
non-empty interior (``extent <= 2d``) — falls back to the sequential
schedule rather than wrapping garbage.
"""

from __future__ import annotations

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
from jax import lax

from mpi_and_open_mp_tpu.parallel import halo

ENV_OVERLAP = "MOMP_HALO_OVERLAP"
ENV_RDMA = "MOMP_HALO_RDMA"

LAYOUTS = ("row", "col", "cart")


def overlap_enabled() -> bool:
    """The ``MOMP_HALO_OVERLAP`` kill switch (default ON)."""
    return os.environ.get(ENV_OVERLAP, "1") != "0"


def rdma_requested() -> bool:
    """Whether ``MOMP_HALO_RDMA=1`` asks for the explicit Pallas
    async-remote-copy ghost path (default OFF: the deferred ``ppermute``
    schedule already overlaps via XLA's latency-hiding scheduler; the
    RDMA kernels compile for a described v5e (tests/test_chip_compile.py)
    but have not run on a chip; see DESIGN.md §20 for the layout
    matrix)."""
    return os.environ.get(ENV_RDMA, "0") == "1"


@dataclasses.dataclass(frozen=True)
class HaloPlan:
    """One (mesh topology, shard shape, depth, pack layout) exchange
    schedule, derived once and reused every round — the persistent-
    request analogue of arxiv 2508.13370's ``MPI_Psend_init``."""

    layout: str                  # row | col | cart
    mesh_axes: tuple[int, int]   # (py, px) mesh axis sizes
    shard_shape: tuple[int, int] # local (h, w) cell extent per shard
    radius: int
    fuse_steps: int
    boundary_steps: int          # edge sub-round depth; == fuse_steps
                                 # for the coupled (one-exchange) round
    channels: int
    pack_layout: str             # "cell" | "packed"
    depth: int                   # radius * fuse_steps, ghost cells/side
    overlap: bool                # interior/boundary schedule active
    engine: str                  # provenance stamp (module docstring)
    why: str                     # reason overlap was declined ("" if on)


def _overlap_axis(layout: str) -> str:
    """The axis whose exchange the plan overlaps: the sharded row axis
    for ``row``/``cart`` (cart's x exchange on the deferred path stays
    sequential — its ghosts feed the y ghosts' corners, a real data
    dependence; the RDMA rung folds it into phase 2 of the corner
    exchange), the column axis for ``col``."""
    return "x" if layout == "col" else "y"


@functools.lru_cache(maxsize=512)
def _plan(layout: str, mesh_axes: tuple[int, int],
          shard_shape: tuple[int, int], radius: int, fuse_steps: int,
          boundary_steps: int, channels: int, pack_layout: str,
          enabled: bool, rdma: bool) -> HaloPlan:
    depth = radius * fuse_steps
    py, px = mesh_axes
    h, w = shard_shape
    axis = _overlap_axis(layout)
    shards = py if axis == "y" else px
    extent = h if axis == "y" else w

    def seq(why: str) -> HaloPlan:
        stamp = "seq:packed" if pack_layout == "packed" else "seq:halo"
        return HaloPlan(layout, mesh_axes, shard_shape, radius,
                        fuse_steps, fuse_steps, channels, pack_layout,
                        depth, False, stamp, why)

    if layout not in LAYOUTS:
        raise ValueError(f"layout must be one of {LAYOUTS}, got {layout!r}")
    if (boundary_steps < 1 or boundary_steps > fuse_steps
            or fuse_steps % boundary_steps):
        raise ValueError(
            f"boundary_steps={boundary_steps} must divide "
            f"fuse_steps={fuse_steps}")
    if pack_layout == "packed" and boundary_steps != fuse_steps:
        raise ValueError(
            "packed frames keep the coupled boundary depth "
            "(boundary_steps == fuse_steps)")
    if not enabled:
        return seq(f"{ENV_OVERLAP}=0")
    if shards <= 1:
        return seq(f"1-shard {axis} axis: nothing to overlap")
    if extent <= 2 * depth:
        return seq(
            f"shard {axis} extent {extent} <= 2*depth {2 * depth}: "
            "empty interior")
    if pack_layout == "packed":
        engine = "overlap:packed"
    elif rdma and jax.default_backend() == "tpu":
        engine = "overlap:rdma"
    else:
        engine = "overlap:deferred"
    if boundary_steps != fuse_steps:
        engine += f":pb{boundary_steps}"
    return HaloPlan(layout, mesh_axes, shard_shape, radius, fuse_steps,
                    boundary_steps, channels, pack_layout, depth, True,
                    engine, "")


def plan_halo(layout: str, mesh_axes: tuple[int, int],
              shard_shape: tuple[int, int], radius: int,
              fuse_steps: int = 1, *, boundary_steps: int | None = None,
              channels: int = 1,
              pack_layout: str = "cell") -> HaloPlan:
    """Derive (or fetch) the persistent plan for one geometry. The env
    kill switch and the RDMA opt-in are part of the cache key: flipping
    ``MOMP_HALO_OVERLAP`` mid-process yields a fresh plan, never a stale
    cached schedule. ``boundary_steps`` (default: coupled, ==
    ``fuse_steps``) partitions the boundary into shallower per-edge
    sub-rounds; it must divide ``fuse_steps``."""
    bs = fuse_steps if boundary_steps is None else int(boundary_steps)
    return _plan(layout, tuple(mesh_axes), tuple(shard_shape),
                 int(radius), int(fuse_steps), bs, int(channels),
                 pack_layout, overlap_enabled(), rdma_requested())


def _note_schedule(plan: HaloPlan) -> None:
    """Trace-time metrics hook, same discipline as
    ``halo._note_exchange``: counts schedules TRACED per engine stamp —
    zero overlap traces means the overlap path never engaged."""
    from mpi_and_open_mp_tpu.obs import metrics

    metrics.inc("halo.schedule.traced", engine=plan.engine,
                layout=plan.layout)


# --------------------------------------------------------------- ghost moves


def ghosts_y(block: jnp.ndarray, depth: int,
             axis_name: str = "y") -> tuple[jnp.ndarray, jnp.ndarray]:
    """The y ghost pair ``(top, bot)`` by ring ``ppermute`` — the same
    slices :func:`halo.halo_pad_y` concatenates, WITHOUT the concat, so
    the interior compute can proceed while they fly. Chaos hook on the
    top ghost, mirroring the sequential path's injection point."""
    halo._note_exchange("y-overlap", axis_name)
    p = lax.axis_size(axis_name)
    top = halo._chaos_ghost(lax.ppermute(
        block[..., -depth:, :], axis_name, halo.ring_perm(p, 1)))
    bot = lax.ppermute(
        block[..., :depth, :], axis_name, halo.ring_perm(p, -1))
    return top, bot


def ghosts_x(block: jnp.ndarray, depth: int,
             axis_name: str = "x") -> tuple[jnp.ndarray, jnp.ndarray]:
    """The x ghost pair ``(left, right)`` — :func:`ghosts_y` transposed
    to the last axis (cf. ``halo.halo_pad_x``)."""
    halo._note_exchange("x-overlap", axis_name)
    p = lax.axis_size(axis_name)
    left = halo._chaos_ghost(lax.ppermute(
        block[..., -depth:], axis_name, halo.ring_perm(p, 1)))
    right = lax.ppermute(
        block[..., :depth], axis_name, halo.ring_perm(p, -1))
    return left, right


def packed_ghosts_y(q: jnp.ndarray, h: int,
                    axis_name: str = "y") -> tuple[jnp.ndarray, jnp.ndarray]:
    """Packed-frame y ghost pair ``(top, bot)``, ``h`` words per side —
    the deferred form of ``halo.packed_halo_y``'s ``pad == 0`` path (the
    packed overlap plan is gated to exact frames; padded frames stay on
    the sequential funnel-shift path). One halo word carries 32 boards'
    worth of ghost rows — the overlap win multiplied."""
    halo._note_exchange("packed_y-overlap", axis_name)
    p = lax.axis_size(axis_name)
    top = halo._chaos_ghost(
        lax.ppermute(q[-h:], axis_name, halo.ring_perm(p, 1)))
    bot = lax.ppermute(q[:h], axis_name, halo.ring_perm(p, -1))
    return top, bot


# ------------------------------------------- Pallas async remote copy (TPU)


def _rdma_edge_pair(fwd_edge: jnp.ndarray, bwd_edge: jnp.ndarray,
                    axis_name: str, p: int, *, collective_id: int
                    ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One ghost-pair exchange by explicit Pallas async remote copy.

    Each device starts two RDMAs — ``fwd_edge`` into the ring
    successor's first output buffer, ``bwd_edge`` into the
    predecessor's second — after a neighbour barrier (both peers must
    have entered the kernel before a remote write may land). Returns
    ``(from_prev, from_next)``: the predecessor's ``fwd_edge`` and the
    successor's ``bwd_edge``. Semantically identical to a ``ppermute``
    pair; the difference is WHO schedules the transfer: here the DMA
    engines are driven directly instead of through the
    collective-permute lowering. Real-TPU only (``MOMP_HALO_RDMA=1``);
    CPU CI stays on the deferred ``ppermute`` schedule. Transport only: chaos injection and ghost
    orientation live in the ``_rdma_ghosts_*`` wrappers so every layout
    funnels through ``halo._chaos_ghost`` exactly like the deferred
    path.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    # Peers are named by their index on ``axis_name`` (MESH ids); the
    # other mesh axes keep this device's own coordinate, so on a 2-D mesh
    # the transfer stays on the ring it is meant for.
    mesh_id = pltpu.DeviceIdType.MESH

    def kernel(fwd, bwd, prev_out, next_out, s1, r1, s2, r2):
        i = lax.axis_index(axis_name)
        nxt = {axis_name: lax.rem(i + 1, p)}
        prv = {axis_name: lax.rem(i + p - 1, p)}
        barrier = pltpu.get_barrier_semaphore()
        pltpu.semaphore_signal(barrier, 1, device_id=nxt,
                               device_id_type=mesh_id)
        pltpu.semaphore_signal(barrier, 1, device_id=prv,
                               device_id_type=mesh_id)
        pltpu.semaphore_wait(barrier, 2)
        send_fwd = pltpu.make_async_remote_copy(
            src_ref=fwd, dst_ref=prev_out, send_sem=s1, recv_sem=r1,
            device_id=nxt, device_id_type=mesh_id)
        send_bwd = pltpu.make_async_remote_copy(
            src_ref=bwd, dst_ref=next_out, send_sem=s2, recv_sem=r2,
            device_id=prv, device_id_type=mesh_id)
        send_fwd.start()
        send_bwd.start()
        send_fwd.wait()
        send_bwd.wait()

    from_prev, from_next = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct(fwd_edge.shape, fwd_edge.dtype),
                   jax.ShapeDtypeStruct(bwd_edge.shape, bwd_edge.dtype)),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 2,
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 2,
        scratch_shapes=[pltpu.SemaphoreType.DMA] * 4,
        compiler_params=pltpu.CompilerParams(
            collective_id=collective_id),
    )(fwd_edge, bwd_edge)
    return from_prev, from_next


def _rdma_ghosts_y(block: jnp.ndarray, depth: int, axis_name: str,
                   p: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """:func:`ghosts_y` by RDMA — bottom edge forward, top edge
    backward over the y ring (row/cart layouts); chaos hook on the top
    ghost, mirroring the deferred path's injection point."""
    top, bot = _rdma_edge_pair(
        block[..., -depth:, :], block[..., :depth, :], axis_name, p,
        collective_id=13)
    return halo._chaos_ghost(top), bot


def _rdma_ghosts_x(block: jnp.ndarray, depth: int, axis_name: str,
                   p: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """:func:`ghosts_x` by RDMA — the x-mirror schedule for the ``col``
    layout: right edge forward, left edge backward over the x ring."""
    left, right = _rdma_edge_pair(
        block[..., -depth:], block[..., :depth], axis_name, p,
        collective_id=14)
    return halo._chaos_ghost(left), right


def _rdma_ghosts_cart(block: jnp.ndarray, depth: int,
                      mesh_axes: tuple[int, int]
                      ) -> tuple[jnp.ndarray, jnp.ndarray,
                                 jnp.ndarray, jnp.ndarray]:
    """Two-phase cart corner exchange by RDMA: y edges first, then x
    edges carrying the corner words.

    Phase 1 moves the raw y edge pair over the y ring. Phase 2 moves
    the x edge pair OF THE Y-PADDED BLOCK over the x ring — each
    ``(h + 2d, d)`` column strip's first/last ``d`` rows are phase 1's
    freshly landed ghosts, so the diagonal corner words ride the x
    exchange without a third (diagonal) transfer, the same forwarding
    the sequential schedule gets from ``halo.halo_pad_2d``'s pad-x-
    then-pad-y order. Returns ``(top, bot, left, right)`` with
    ``top``/``bot`` of shape ``(..., d, w)`` and ``left``/``right`` of
    shape ``(..., h + 2d, d)`` (corners included)."""
    d = depth
    py, px = mesh_axes
    top, bot = _rdma_edge_pair(
        block[..., -d:, :], block[..., :d, :], "y", py,
        collective_id=13)
    top = halo._chaos_ghost(top)
    pady = jnp.concatenate([top, block, bot], axis=-2)
    left, right = _rdma_edge_pair(
        pady[..., -d:], pady[..., :d], "x", px, collective_id=14)
    left = halo._chaos_ghost(left)
    return top, bot, left, right


# --------------------------------------------------------- fused schedules


def _steps(step_fn, padded: jnp.ndarray, k: int) -> jnp.ndarray:
    for _ in range(k):
        padded = step_fn(padded)
    return padded


def overlap_fused_step(plan: HaloPlan, step_fn, block: jnp.ndarray
                       ) -> jnp.ndarray:
    """One overlapped fused round of ``k = plan.fuse_steps`` steps.

    ``step_fn`` consumes one ``radius`` of halo per side per call (the
    ``stencils.step_padded`` contract). Ghost permutes are issued before
    the interior compute and consumed after it; the three partitions
    reassemble by concat into exactly the sequential round's result.
    Must run inside ``shard_map`` with the layout's axes in scope.
    """
    if not plan.overlap:
        return sequential_fused_step(plan, step_fn, block)
    if plan.boundary_steps != plan.fuse_steps:
        return _partitioned_fused_step(plan, step_fn, block)
    _note_schedule(plan)
    k, d = plan.fuse_steps, plan.depth
    rdma = plan.engine.startswith("overlap:rdma")
    if plan.layout == "col":
        # x-mirror of the row schedule: interior pads y locally (the
        # unsharded axis wraps itself), boundary strips extend in x.
        if rdma:
            left, right = _rdma_ghosts_x(block, d, "x",
                                         plan.mesh_axes[1])
        else:
            left, right = ghosts_x(block, d)
        wrapped = jnp.concatenate(
            [block[..., -d:, :], block, block[..., :d, :]], axis=-2)
        interior = _steps(step_fn, wrapped, k)
        lead = jnp.concatenate([left, block[..., : 2 * d]], axis=-1)
        tail = jnp.concatenate([block[..., -2 * d:], right], axis=-1)
        lead = _steps(
            step_fn, jnp.concatenate(
                [lead[..., -d:, :], lead, lead[..., :d, :]], axis=-2), k)
        tail = _steps(
            step_fn, jnp.concatenate(
                [tail[..., -d:, :], tail, tail[..., :d, :]], axis=-2), k)
        return jnp.concatenate([lead, interior, tail], axis=-1)

    if plan.layout == "cart" and rdma and plan.mesh_axes[1] > 1:
        # Two-phase corner exchange inside the RDMA kernels: y edges
        # first, then x edges carrying the corner words — both axes'
        # ghosts fly while the interior computes (the deferred cart
        # path below still serialises the x exchange up front).
        top2, bot2, left, right = _rdma_ghosts_cart(
            block, d, plan.mesh_axes)
        base = jnp.concatenate(
            [left[..., d:-d, :], block, right[..., d:-d, :]], axis=-1)
        top = jnp.concatenate(
            [left[..., :d, :], top2, right[..., :d, :]], axis=-1)
        bot = jnp.concatenate(
            [left[..., -d:, :], bot2, right[..., -d:, :]], axis=-1)
        interior = _steps(step_fn, base, k)
        lead = _steps(
            step_fn, jnp.concatenate([top, base[..., : 2 * d, :]],
                                     axis=-2), k)
        tail = _steps(
            step_fn, jnp.concatenate([base[..., -2 * d:, :], bot],
                                     axis=-2), k)
        return jnp.concatenate([lead, interior, tail], axis=-2)

    # row / cart: overlap the y exchange. Deferred cart first completes
    # the x exchange sequentially (its ghost columns feed the y ghosts'
    # corners — the reference's two-phase order, life_cart.c:275-279);
    # row wraps x locally. Either way `base` carries d ghost columns.
    if plan.layout == "cart":
        base = halo.halo_pad_x(block, "x", d)
    else:
        base = jnp.concatenate(
            [block[..., -d:], block, block[..., :d]], axis=-1)
    if rdma:
        top, bot = _rdma_ghosts_y(base, d, "y", plan.mesh_axes[0])
    else:
        top, bot = ghosts_y(base, d)
    interior = _steps(step_fn, base, k)
    lead = _steps(
        step_fn, jnp.concatenate([top, base[..., : 2 * d, :]], axis=-2), k)
    tail = _steps(
        step_fn, jnp.concatenate([base[..., -2 * d:, :], bot], axis=-2), k)
    return jnp.concatenate([lead, interior, tail], axis=-2)


def _partitioned_fused_step(plan: HaloPlan, step_fn, block: jnp.ndarray
                            ) -> jnp.ndarray:
    """The partitioned-boundary round: interior keeps the full
    ``k = fuse_steps`` fuse; each edge strip advances in
    ``b = boundary_steps`` sub-rounds, exchanging ``radius * b``-deep
    per-edge ghosts whose sends are issued straight from the strip's
    just-computed cells (per-edge readiness, no whole-round barrier —
    the ``MPI_Pready`` shape of arxiv 2508.13370). Sub-round ``j``'s
    ghost is the neighbour strip's state at step ``j * b``, so the
    reassembled shard is bit-identical to the coupled round: every
    output cell sees the same neighbourhood values through the same
    reduction tree, only sliced along different message boundaries.
    Band extents shrink by ``radius * b`` per side per sub-round along
    the unsharded axis exactly as the coupled strips shrink over ``k``
    fused applications."""
    _note_schedule(plan)
    k, d, b = plan.fuse_steps, plan.depth, plan.boundary_steps
    e = plan.radius * b
    rdma = plan.engine.startswith("overlap:rdma")
    if plan.layout == "col":
        base = jnp.concatenate(
            [block[..., -d:, :], block, block[..., :d, :]], axis=-2)
        interior = _steps(step_fn, base, k)
        lead, tail = base[..., : 2 * d], base[..., -2 * d:]
        p = lax.axis_size("x")
        for _ in range(k // b):
            halo._note_exchange("x-part", "x")
            if rdma:
                left, right = _rdma_edge_pair(
                    tail[..., -e:], lead[..., :e], "x", p,
                    collective_id=14)
                left = halo._chaos_ghost(left)
            else:
                left = halo._chaos_ghost(lax.ppermute(
                    tail[..., -e:], "x", halo.ring_perm(p, 1)))
                right = lax.ppermute(
                    lead[..., :e], "x", halo.ring_perm(p, -1))
            lead = _steps(
                step_fn, jnp.concatenate([left, lead], axis=-1), b)
            tail = _steps(
                step_fn, jnp.concatenate([tail, right], axis=-1), b)
        return jnp.concatenate([lead, interior, tail], axis=-1)

    # row / cart: bands along y. Cart pre-pads x sequentially (corners
    # ride the x ghosts, which then shrink with the band), row wraps x
    # locally; either way each band starts with d ghost columns and
    # narrows by e per side per sub-round.
    if plan.layout == "cart":
        base = halo.halo_pad_x(block, "x", d)
    else:
        base = jnp.concatenate(
            [block[..., -d:], block, block[..., :d]], axis=-1)
    interior = _steps(step_fn, base, k)
    lead, tail = base[..., : 2 * d, :], base[..., -2 * d:, :]
    p = lax.axis_size("y")
    for _ in range(k // b):
        halo._note_exchange("y-part", "y")
        if rdma:
            top, bot = _rdma_edge_pair(
                tail[..., -e:, :], lead[..., :e, :], "y", p,
                collective_id=13)
            top = halo._chaos_ghost(top)
        else:
            top = halo._chaos_ghost(lax.ppermute(
                tail[..., -e:, :], "y", halo.ring_perm(p, 1)))
            bot = lax.ppermute(
                lead[..., :e, :], "y", halo.ring_perm(p, -1))
        lead = _steps(step_fn, jnp.concatenate([top, lead], axis=-2), b)
        tail = _steps(step_fn, jnp.concatenate([tail, bot], axis=-2), b)
    return jnp.concatenate([lead, interior, tail], axis=-2)


def sequential_fused_step(plan: HaloPlan, step_fn, block: jnp.ndarray
                          ) -> jnp.ndarray:
    """The sequential (blocking-concat) round — the historical
    ``halo_pad_*`` schedule, kept callable from the same plan so the A/B
    and the kill switch measure schedules, not code paths."""
    _note_schedule(plan)
    d = plan.depth
    if plan.layout == "row":
        padded = halo.halo_pad_y(jnp.concatenate(
            [block[..., -d:], block, block[..., :d]], axis=-1), "y", d)
    elif plan.layout == "col":
        padded = halo.halo_pad_x(jnp.concatenate(
            [block[..., -d:, :], block, block[..., :d, :]], axis=-2),
            "x", d)
    else:
        padded = halo.halo_pad_2d(block, "y", "x", d)
    return _steps(step_fn, padded, plan.fuse_steps)


def fused_step(plan: HaloPlan, step_fn, block: jnp.ndarray) -> jnp.ndarray:
    """Dispatch one fused round by the plan's schedule."""
    if plan.overlap:
        return overlap_fused_step(plan, step_fn, block)
    return sequential_fused_step(plan, step_fn, block)


# ------------------------------------------------- padded frames for engines
#
# The sparse-sharded engine (stencils.sparse_sharded) gathers tiles out
# of a padded shard frame instead of stepping the whole shard, so it
# needs the PADDING itself, not the fused round. Exposing the exact
# sequential-schedule frame keeps its per-cell arithmetic bit-identical
# to the dense sharded path; the zero-sentinel twin is the exchange-skip
# round — legal only when every shard's boundary band is dead (the
# ghosts it replaces are then provably all-zero; DESIGN.md §18).


def padded_round_block(layout: str, block: jnp.ndarray,
                       depth: int) -> jnp.ndarray:
    """One round's halo-padded shard frame, exchanged exactly as the
    sequential schedule pads it (same concat order, same ppermutes —
    ``halo._note_exchange`` ticks identically). Must run inside
    ``shard_map`` with the layout's axes in scope."""
    d = depth
    if layout == "row":
        return halo.halo_pad_y(jnp.concatenate(
            [block[..., -d:], block, block[..., :d]], axis=-1), "y", d)
    if layout == "col":
        return halo.halo_pad_x(jnp.concatenate(
            [block[..., -d:, :], block, block[..., :d, :]], axis=-2),
            "x", d)
    return halo.halo_pad_2d(block, "y", "x", d)


def padded_round_block_local(layout: str, block: jnp.ndarray,
                             depth: int) -> jnp.ndarray:
    """The zero-sentinel twin of :func:`padded_round_block`: unsharded
    axes wrap locally (they hold the full torus extent, so the local
    wrap IS the true wrap), sharded axes pad with static zeros and no
    collective is issued. Bit-exact iff every shard's boundary band is
    dead — the caller's host-global skip decision, never a per-device
    branch (the ring stays deadlock-free because each compiled program
    is collective-complete)."""
    d = depth
    pad = [(0, 0)] * (block.ndim - 2)
    if layout == "row":
        wrapped = jnp.concatenate(
            [block[..., -d:], block, block[..., :d]], axis=-1)
        return jnp.pad(wrapped, pad + [(d, d), (0, 0)])
    if layout == "col":
        wrapped = jnp.concatenate(
            [block[..., -d:, :], block, block[..., :d, :]], axis=-2)
        return jnp.pad(wrapped, pad + [(0, 0), (d, d)])
    return jnp.pad(block, pad + [(d, d), (d, d)])
