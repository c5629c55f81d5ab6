"""Device mesh topology — the TPU-native replacement for MPI communicators.

The reference derives its process topology from ``MPI_COMM_WORLD``:
a flat rank list for the 1-D strip decompositions
(``/root/reference/3-life/life_mpi.c:101-103``) and a periodic 2-D grid via
``MPI_Dims_create`` + ``MPI_Cart_create``
(``/root/reference/6-cartesian/life_cart.c:117-121``). Here the same roles
are played by a ``jax.sharding.Mesh``: 1-D meshes over axis ``"y"`` or
``"x"``, and a 2-D ``("y", "x")`` mesh. Periodicity lives in the
``ppermute`` permutations (see ``parallel.halo``), not the mesh itself —
every mesh axis is a ring when the halo code says so.

Axis naming convention (used across the whole framework): ``"y"`` shards the
row dimension (axis 0 of the ``(ny, nx)`` board), ``"x"`` shards the column
dimension (axis 1).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh

AXIS_Y = "y"
AXIS_X = "x"


# Classic GSPMD propagation (Auto) rather than sharding-in-types (Explicit,
# the jax>=0.9 make_mesh default): the roll-based global step relies on XLA
# propagating shardings through circular shifts of arbitrary (uneven) sizes.
def _auto_mesh(shape: tuple[int, ...], names: tuple[str, ...]) -> Mesh:
    """``jax.make_mesh`` with Auto semantics on every axis."""
    return jax.make_mesh(shape, names,
                         axis_types=tuple(AxisType.Auto for _ in names))


def dims_create(n: int, ndims: int = 2) -> tuple[int, ...]:
    """Balanced factorisation of ``n`` over ``ndims`` mesh axes.

    Same contract as ``MPI_Dims_create`` (used by the reference at
    ``6-cartesian/life_cart.c:118``): dimensions as close to each other as
    possible, in non-increasing order. Deterministic greedy algorithm:
    repeatedly peel the largest factor ≤ the remaining ``ndims``-th root.
    """
    if n < 1 or ndims < 1:
        raise ValueError(f"dims_create({n}, {ndims})")
    dims = []
    remaining = n
    for d in range(ndims, 0, -1):
        if d == 1:
            dims.append(remaining)
            break
        # Largest divisor of `remaining` that is <= remaining ** (1/d),
        # searched downward from the integer root.
        target = round(remaining ** (1.0 / d))
        best = 1
        for cand in range(target, 0, -1):
            if remaining % cand == 0:
                best = cand
                break
        # Try upward too: pick whichever divisor is closest to the root.
        for cand in range(target + 1, remaining + 1):
            if remaining % cand == 0:
                if abs(cand - remaining ** (1.0 / d)) < abs(best - remaining ** (1.0 / d)):
                    best = cand
                break
        dims.append(best)
        remaining //= best
    return tuple(sorted(dims, reverse=True))


def decomposition(n: int, p: int, k: int) -> tuple[int, int]:
    """Reference shard map: rank ``k`` of ``p`` owns ``[start, stop)`` of ``n``.

    Floor-chunking with the LAST shard absorbing the remainder — the exact
    semantics of the reference's ``decomposition()``
    (``3-life/life_mpi.c:178-183``, identical in ``4-life``/``5-gather``/
    ``6-cartesian``). Used for host-side partitioning bookkeeping and for
    documenting parity; on-device sharding uses even blocks (XLA requirement)
    with the global roll-based step handling any residue.
    """
    chunk = n // p
    start = k * chunk
    stop = n if k == p - 1 else (k + 1) * chunk
    return start, stop


def make_mesh_1d(n: int | None = None, axis: str = AXIS_Y) -> Mesh:
    """1-D device mesh over ``n`` devices (default: all local devices)."""
    if n is None:
        n = len(jax.devices())
    return _auto_mesh((n,), (axis,))


def make_mesh_2d(py: int | None = None, px: int | None = None) -> Mesh:
    """2-D ``("y", "x")`` device mesh.

    With no arguments, factorises the full device count like
    ``MPI_Dims_create`` (``6-cartesian/life_cart.c:117-118``).
    """
    if py is None and px is None:
        py, px = dims_create(len(jax.devices()), 2)
    elif py is None or px is None:
        raise ValueError("pass both py and px, or neither")
    return _auto_mesh((py, px), (AXIS_Y, AXIS_X))
