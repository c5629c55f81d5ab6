"""Distributed trapezoidal quadrature driver.

The TPU re-design of ``/root/reference/1-integral/integral.c``: shard the N
trapezoids over a 1-D device mesh, vectorised per-device sums, one
``lax.psum`` instead of the reference's hand-rolled Send/Recv reduction star
(``integral.c:39-43``). Keeps the driver contract: given N, print elapsed
seconds (the reference never prints the value — ``integral.c:27,44`` comment
it out — but we expose it).
"""

from __future__ import annotations

from typing import Callable

import numpy as np

import jax
from jax.sharding import Mesh

from mpi_and_open_mp_tpu.ops import quadrature
from mpi_and_open_mp_tpu.parallel import mesh as mesh_lib


class Integral:
    """∫_a^b f(x) dx by N trapezoids over a device mesh."""

    def __init__(
        self,
        n: int,
        a: float = 0.0,
        b: float = 2.0,
        f: Callable = quadrature.f_circle,
        mesh: Mesh | None = None,
    ):
        if n < 1:
            raise ValueError(f"need at least one trapezoid, got n={n}")
        self.n = int(n)  # int64 semantics: no 32-bit atoi truncation here
        self.a, self.b, self.f = float(a), float(b), f
        self.mesh = mesh if mesh is not None else mesh_lib.make_mesh_1d(axis="i")
        self._compiled = self._build()

    def _build(self):
        f, a, b, n = self.f, self.a, self.b, self.n
        axis = next(iter(self.mesh.shape))
        if self.mesh.size == 1:
            return jax.jit(lambda: quadrature.trapezoid_serial(f, a, b, n))
        smapped = jax.shard_map(
            lambda: quadrature.trapezoid_shard_sum(f, a, b, n, axis),
            mesh=self.mesh,
            in_specs=(),
            out_specs=jax.sharding.PartitionSpec(),
            check_vma=False,
        )
        return jax.jit(smapped)

    def compute(self) -> float:
        """Run the quadrature; blocks until the value is on the host."""
        return float(np.asarray(jax.device_get(self._compiled())))
