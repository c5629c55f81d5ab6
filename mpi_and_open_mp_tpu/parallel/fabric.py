"""Fabric latency/bandwidth probe — the ICI/DCN analogue of the reference's
MPI ping-pong benchmark (``/root/reference/2-network-params/mpi_send_recv.c``).

The reference times 10⁵ blocking Send/Recv round trips between two ranks for
message sizes 1..10⁶ B and prints ``size,half-RTT µs`` CSV rows
(``mpi_send_recv.c:20-39``); the same binary at two placements (1 node vs 2
nodes) characterises shared-memory vs NIC transport. Here the transport is
the accelerator fabric: a timed ``lax.ppermute`` ring shift of an N-byte
buffer over a mesh axis, ``reps`` rounds fused in one jitted ``fori_loop``
(so dispatch overhead amortises exactly like the reference's tight loop).
One hop of a ring permute is the ppermute analogue of a half round trip.

The α+βn model fit (``plot.ipynb`` cells 5-6) lives in ``fit_alpha_beta``:
α = latency intercept, 1/β = asymptotic bandwidth.
"""

from __future__ import annotations

import functools
import time
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from mpi_and_open_mp_tpu.parallel import mesh as mesh_lib
from mpi_and_open_mp_tpu.parallel.halo import ring_perm

# Message sizes in bytes: 10^0 .. 10^6, matching mpi_send_recv.c:22.
DEFAULT_SIZES = tuple(10**k for k in range(7))


@functools.partial(jax.jit, static_argnames=("axis", "reps", "mesh"))
def _ring_shift_loop(buf: jnp.ndarray, *, axis: str, reps: int, mesh: Mesh):
    """``reps`` sequential one-hop ring shifts of each device's buffer."""

    def shifted(b):
        p = lax.axis_size(axis)
        return lax.ppermute(b, axis, ring_perm(p, 1))

    smapped = jax.shard_map(
        lambda b: lax.fori_loop(0, reps, lambda _, x: shifted(x), b),
        mesh=mesh,
        in_specs=P(axis),
        out_specs=P(axis),
        check_vma=False,
    )
    return smapped(buf)


def ping(mesh: Mesh, msg_bytes: int, reps: int = 100) -> float:
    """Mean seconds per one-hop transfer of a ``msg_bytes`` buffer.

    Each device holds its own ``msg_bytes`` payload (int8), so one round
    moves ``msg_bytes`` over every link in parallel — the fabric analogue of
    the reference's 2-rank half-RTT.
    """
    axis = next(iter(mesh.shape))
    p = mesh.size
    n = max(1, msg_bytes)
    buf = jnp.zeros((p * n,), dtype=jnp.int8)
    buf = jax.device_put(buf, NamedSharding(mesh, P(axis)))
    from mpi_and_open_mp_tpu.utils.timing import anchor_sync

    # Warm-up: compile + first transfer.
    anchor_sync(_ring_shift_loop(buf, axis=axis, reps=reps, mesh=mesh),
                fetch_all=True)
    # Chaos hook (robust.chaos): an injected host-side delay INSIDE the
    # timed bracket simulates a congested fabric / slow hop, so
    # harness code consuming these probes (fit sanity, CSV writers) can
    # be tested against pathological timings. No-op when MOMP_CHAOS is
    # unset.
    from mpi_and_open_mp_tpu.robust import chaos

    delay = chaos.dispatch_delay()
    t0 = time.perf_counter()
    if delay:
        time.sleep(delay)
    out = _ring_shift_loop(buf, axis=axis, reps=reps, mesh=mesh)
    # Anchored one-element fetch after block_until_ready (see
    # utils.timing.anchor_sync): the anchor reads a locally addressable
    # shard, so it also works on multi-process meshes where a global
    # fetch is impossible.
    anchor_sync(out, fetch_all=True)
    elapsed = time.perf_counter() - t0
    return elapsed / reps


def sweep(
    mesh: Mesh | None = None,
    sizes: tuple[int, ...] = DEFAULT_SIZES,
    reps: int = 100,
) -> list[tuple[int, float]]:
    """Probe each message size; returns ``(bytes, microseconds_per_hop)``
    rows — the reference's CSV schema (``mpi_send_recv.c:38``)."""
    if mesh is None:
        mesh = mesh_lib.make_mesh_1d(axis="i")
    return [(s, ping(mesh, s, reps) * 1e6) for s in sizes]


def write_csv(path: str, rows: list[tuple[int, float]]) -> None:
    """``size,time`` CSV compatible with the reference's ``out_*.csv`` files
    consumed by its ``plot.ipynb`` analysis."""
    with open(path, "w") as fd:
        fd.write("size,time\n")
        for s, us in rows:
            fd.write(f"{s},{us:.6f}\n")


class Fit(NamedTuple):
    """α+βn fit result with its quality: consumers must be able to tell a
    measured bandwidth from fit noise (a loopback Gloo probe once shipped
    an artifact reading "infinite bandwidth" off a β ≤ 0 slope)."""

    alpha_us: float
    bandwidth_mb_s: float  # math.inf when unidentifiable — check the flag
    r2: float  # of the unconstrained linear fit
    identifiable: bool  # False when β ≤ 0 (noise-dominated probe)

    def render(self) -> str:
        """The one rendering every consumer (CLI stderr, fit.txt) uses,
        so artifacts and logs cannot disagree on the flag format."""
        bw = (f"{self.bandwidth_mb_s:.1f}MB/s" if self.identifiable
              else "unidentifiable(beta<=0)")
        return f"alpha={self.alpha_us:.3f}us bandwidth={bw} r2={self.r2:.3f}"

    def as_json(self) -> dict:
        """JSON-ready view for machine consumers (pingpong's fit line,
        trace_report's hop fit). An unidentifiable fit must NOT emit the
        internal ``inf`` sentinel — ``json.dumps`` would write bare
        ``Infinity``, which strict parsers reject — so bandwidth/beta
        become ``None``/``0.0`` there and the flag carries the verdict."""
        # bandwidth is 1/β with β in µs/byte (bytes/µs ≡ MB/s numerically).
        beta = (1.0 / self.bandwidth_mb_s) if self.identifiable else 0.0
        return {
            "alpha_us": round(float(self.alpha_us), 6),
            "beta_us_per_byte": round(float(beta), 12),
            "bandwidth_mb_s": (round(float(self.bandwidth_mb_s), 3)
                               if self.identifiable else None),
            "r2": round(float(self.r2), 6),
            "identifiable": bool(self.identifiable),
        }


def fit_alpha_beta(rows: list[tuple[int, float]]) -> Fit:
    """Linear model t = α + β·n over the probe rows (times in µs).

    Returns :class:`Fit` — the latency intercept ``alpha_us`` and the 1/β
    asymptotic bandwidth, as in the reference's ``plot.ipynb`` cell 5
    ``np.polyfit(buffer_size, time, 1)`` fit, plus the fit's R² and an
    ``identifiable`` flag. A noise-dominated probe can fit β ≤ 0 (observed
    on loopback Gloo): β is then clamped to 0 — α degrades to the mean
    latency, bandwidth is reported as ``inf`` with ``identifiable=False``,
    and renderers should print the flag, not the number.
    """
    sizes = np.array([r[0] for r in rows], dtype=np.float64)
    times = np.array([r[1] for r in rows], dtype=np.float64)
    beta, alpha = np.polyfit(sizes, times, 1)
    ss_tot = float(((times - times.mean()) ** 2).sum())
    ss_res = float(((times - (alpha + beta * sizes)) ** 2).sum())
    r2 = 1.0 - ss_res / ss_tot if ss_tot > 0 else 1.0
    if beta <= 0:
        # Constrained refit with β = 0: the best constant model.
        return Fit(float(times.mean()), float("inf"), r2, False)
    return Fit(float(alpha), float(1.0 / beta), r2, True)
