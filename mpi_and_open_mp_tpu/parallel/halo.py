"""Halo (ghost-cell) exchange over mesh axes via ``lax.ppermute``.

The TPU-native replacement for the reference's blocking ghost-row
``MPI_Send``/``MPI_Recv`` pairs (``/root/reference/3-life/life_mpi.c:198-209``
for 1-D rows, ``4-life/life_mpi.c:197-208`` for strided columns,
``6-cartesian/life_cart.c:225-279`` for the 2-D row/column/corner sequence).

Key differences by design:

* ``ppermute`` is a deterministic collective routed over ICI — there is no
  eager-protocol deadlock hazard (the reference's simultaneous blocking sends
  only work for small messages; see SURVEY §2 quirks).
* Derived datatypes disappear: a "strided column" is just a slice of the
  shard; XLA owns the layout.
* Corners come for free by sequencing the two axis exchanges — pad x first,
  then exchange the *already-padded* rows along y, exactly the two-phase
  trick the reference implements manually at ``life_cart.c:257-279``.

All functions here must be called inside ``shard_map`` with the named axis
in scope. Ghost depth ``k > 1`` enables multi-step halo fusion: exchange a
depth-``k`` halo once, then take ``k`` local stencil steps before the next
exchange round.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax


def ring_perm(p: int, shift: int = 1) -> list[tuple[int, int]]:
    """Permutation sending each ring member's value to ``(i + shift) % p``."""
    return [(i, (i + shift) % p) for i in range(p)]


def _chaos_ghost(ghost: jnp.ndarray) -> jnp.ndarray:
    """Trace-time chaos hook (``robust.chaos``): with no active
    ``MOMP_CHAOS`` halo fault the ghost block passes through untouched and
    no injection ops enter the program — this body runs only while
    tracing, so the check costs nothing per step. A corrupted/dropped
    ghost here is what the ``LifeSim`` consistency probe must catch.
    Every ghost route funnels through this hook — including the packed
    ``pad > 0`` frame paths, which wrap their INCOMING ghost block only
    (the same-direction permute also refreshes the wrap shard's mirror
    region from live data; corrupting that write would alter real board
    state, which chaos must never do)."""
    from mpi_and_open_mp_tpu.robust import chaos

    spec = chaos.halo_ghost_spec()
    if spec is None:
        return ghost
    return chaos.corrupt_ghost(ghost, spec)


def _note_exchange(kind: str, axis_name: str) -> None:
    """Trace-time metrics hook (``obs.metrics``): counts halo exchanges
    TRACED, not executed — like :func:`_chaos_ghost`, these bodies run
    only while XLA traces the program, so per-step execution counts are
    not host-observable from in here. A traced-exchange count per
    kind/axis is still the useful signal: it is the retrace-style "how
    many distinct exchange programs were built" number, and zero of them
    means the sharded path never engaged at all."""
    from mpi_and_open_mp_tpu.obs import metrics

    metrics.inc("halo.exchange.traced", kind=kind, axis=axis_name)


def halo_pad_y(block: jnp.ndarray, axis_name: str = "y", depth: int = 1) -> jnp.ndarray:
    """Pad axis 0 of a shard with ghost rows from its ring neighbours.

    Returns ``(h + 2*depth, w)``: ``depth`` rows from the previous shard on
    top, ``depth`` rows from the next shard at the bottom. With a single
    shard on the axis this degenerates to a torus self-wrap.

    Row/column axes are the LAST TWO axes — leading channel axes (multi-
    field stencils like gray_scott) ride through untouched, and ``depth``
    is the stencil radius times any fuse depth, so every stencil spec
    shares this one exchange (dtype never appears: ``ppermute`` moves
    whatever the slice holds).
    """
    _note_exchange("y", axis_name)
    p = lax.axis_size(axis_name)
    # My top ghost rows are the *last* rows of my predecessor: everyone
    # sends their bottom edge forward around the ring.
    top = _chaos_ghost(
        lax.ppermute(block[..., -depth:, :], axis_name, ring_perm(p, 1)))
    bot = lax.ppermute(block[..., :depth, :], axis_name, ring_perm(p, -1))
    return jnp.concatenate([top, block, bot], axis=-2)


def halo_pad_x(block: jnp.ndarray, axis_name: str = "x", depth: int = 1) -> jnp.ndarray:
    """Pad axis 1 of a shard with ghost columns from its ring neighbours.

    The reference needed ``MPI_Type_vector`` strided datatypes for this
    (``4-life/life_mpi.c:106-109``); here it is a slice + ``ppermute``.
    Last-axis columns; leading channel axes ride along (see
    :func:`halo_pad_y` for the radius/dtype-generic contract).
    """
    _note_exchange("x", axis_name)
    p = lax.axis_size(axis_name)
    left = _chaos_ghost(
        lax.ppermute(block[..., -depth:], axis_name, ring_perm(p, 1)))
    right = lax.ppermute(block[..., :depth], axis_name, ring_perm(p, -1))
    return jnp.concatenate([left, block, right], axis=-1)


def packed_send_y(h: int, pad: int) -> int:
    """Word rows :func:`packed_halo_y` sends each way: ``h``, or with
    ``pad`` mirror rows the ``h + 1 + pad // 32`` that hold the wrap
    shard's funnel sources and the mirror refresh."""
    return h + 1 + pad // 32 if pad else h


def packed_send_x(hx: int, pad: int) -> int:
    """Columns :func:`packed_halo_x` sends each way: ``hx``, or with
    ``pad`` mirror columns ``hx + pad``."""
    return hx + pad if pad else hx


def packed_halo_y(
    e: jnp.ndarray, axis_name: str = "y", h: int = 4, *, pad: int = 0
) -> jnp.ndarray:
    """y halo of a bit-packed frame shard (word rows x cell columns).

    ``h`` ghost words per side travel the ring; when the frame carries
    ``pad`` mirror rows (board height padded to 32*py alignment — see
    ``ops.bitlife.plan_sharded_bits``) the wrap edges are funnel-shifted
    onto the LOGICAL board height and the wrap shard's mirror rows are
    refreshed from the first shard's live data. ``pad == 0`` degenerates
    to :func:`halo_pad_y`. With one shard on the axis this is the local
    torus wrap, same content as ``bitlife.wrap_y_padded``.
    """
    from mpi_and_open_mp_tpu.ops import bitlife

    if pad == 0:
        return halo_pad_y(e, axis_name, h)
    _note_exchange("packed_y", axis_name)
    p = lax.axis_size(axis_name)
    s = packed_send_y(h, pad)
    # Chaos wraps the INCOMING top ghost only (injection-point parity
    # with halo_pad_y): `dn` also refreshes the wrap shard's mirror
    # rows from live data, a write chaos must never corrupt.
    up = _chaos_ghost(lax.ppermute(e[-s:], axis_name, ring_perm(p, 1)))
    dn = lax.ppermute(e[:s], axis_name, ring_perm(p, -1))
    i = lax.axis_index(axis_name)
    # Shard 0's top ghost is board rows [ny-32h, ny) — an unaligned range
    # of the LAST shard (the frame's tail is mirror rows, not the wrap);
    # interior shards take their predecessor's word-aligned tail.
    top = jnp.where(
        i == 0,
        bitlife.take_rows(up, 32 * s - pad - 32 * h, h),
        up[s - h :],
    )
    bot = jnp.where(
        i == p - 1, bitlife.take_rows(dn, pad, h), dn[:h]
    )
    e = jnp.where(i == p - 1, bitlife.mirror_tail(e, dn, pad), e)
    return jnp.concatenate([top, e, bot], axis=0)


def packed_halo_x(
    block: jnp.ndarray, axis_name: str = "x", hx: int = 128, *, pad: int = 0
) -> jnp.ndarray:
    """x halo of a packed frame shard, ``hx`` ghost columns per side.

    Column-granular twin of :func:`packed_halo_y`: with ``pad`` mirror
    columns (board width padded to the lane pitch) the wrap edges are
    slid onto the logical board width and the wrap shard's mirror
    columns are refreshed; ``pad == 0`` degenerates to
    :func:`halo_pad_x`. Packed columns are whole cell columns, so unlike
    y there is no bit-level funnel — just offset slices.
    """
    if pad == 0:
        return halo_pad_x(block, axis_name, hx)
    _note_exchange("packed_x", axis_name)
    p = lax.axis_size(axis_name)
    s = packed_send_x(hx, pad)
    # Chaos on the incoming left ghost only — `right` also feeds the
    # wrap shard's mirror-column refresh (see packed_halo_y).
    left = _chaos_ghost(
        lax.ppermute(block[:, -s:], axis_name, ring_perm(p, 1)))
    right = lax.ppermute(block[:, :s], axis_name, ring_perm(p, -1))
    i = lax.axis_index(axis_name)
    lb = jnp.where(i == 0, left[:, :hx], left[:, pad:])
    rb = jnp.where(i == p - 1, right[:, pad : pad + hx], right[:, :hx])
    block = jnp.where(
        i == p - 1,
        jnp.concatenate([block[:, :-pad], right[:, :pad]], axis=1),
        block,
    )
    return jnp.concatenate([lb, block, rb], axis=1)


def halo_pad_2d(
    block: jnp.ndarray,
    axis_y: str = "y",
    axis_x: str = "x",
    depth: int = 1,
) -> jnp.ndarray:
    """Full 2-D halo including corners, by sequential axis exchange.

    Phase 1 pads columns (x axis); phase 2 exchanges rows of the x-padded
    block, so the row ghosts already carry the corner cells — mirroring the
    reference's exchange order at ``6-cartesian/life_cart.c:275-279``.
    Returns ``(h + 2*depth, w + 2*depth)``.
    """
    padded_x = halo_pad_x(block, axis_x, depth)
    return halo_pad_y(padded_x, axis_y, depth)
