"""Trapezoidal quadrature kernels.

Reference: ``/root/reference/1-integral/integral.c`` — ∫₀² √(4−x²) dx ≈ π by
N trapezoids of width h = 2/N (``integral.c:12-13``), partial sums per rank
(``integral.c:50-53``) hand-reduced to the root with Send/Recv
(``integral.c:39-43``).

TPU-native design: no rank loops — one ``shard_map`` over a 1-D mesh where
each device evaluates its contiguous range as vectorised VPU blocks
(``fori_loop`` over CHUNK-point blocks, tails masked) and the reduction is a
single ``lax.psum``. A grid point ``i ∈ [0, N]`` contributes ``h·w·f(a+i·h)``
with half weight at the two global endpoints — one ``f`` evaluation per
point instead of the reference's two per trapezoid.

Index arithmetic is done in *chunk units* so N up to 10¹²⁺ works without
64-bit device integers (TPU jnp ints are int32 by default): a point is
``(g, r)`` with global chunk id ``g = i // CHUNK`` (≤ N/CHUNK ≈ 7.6M at
N=10¹², exact in int32 AND in f32's 24-bit mantissa) and lane ``r = i %
CHUNK``; its abscissa is ``a + g·(CHUNK·h) + r·h``. This also fixes, rather
than inherits, the reference's 32-bit ``atoi`` truncation of N=10¹²
(``integral.c:12``, SURVEY §2 quirks).

Precision (TPU has no fast f64): per-chunk sums are XLA tree reductions in
f32, and the across-chunk accumulator uses Kahan compensated summation, so
accumulation error stays near f32 ulp level instead of growing with chunk
count. Remaining error is dominated by f32 rounding of the abscissae and of
``f`` itself — observed relative error vs π is ~1e-6 at N=10⁸ and stays at
that order for larger N (each sample's abscissa is exact to ~1.2e-7
relative; the rule error itself falls below f32 noise past N≈10⁶).
"""

from __future__ import annotations

from typing import Callable

import jax.numpy as jnp
from jax import lax

# Grid points evaluated per loop iteration on each device (VPU-friendly).
CHUNK = 1 << 17


def f_circle(x: jnp.ndarray) -> jnp.ndarray:
    """The reference integrand √(4 − x²)  (``integral.c:7``)."""
    return jnp.sqrt(jnp.maximum(4.0 - x * x, 0.0))


def _chunk_grid(n: int):
    """Static chunk-unit geometry for grid points 0..n."""
    last_chunk = n // CHUNK  # chunk holding point n
    last_lane = n % CHUNK
    n_chunks = last_chunk + 1
    return n_chunks, last_chunk, last_lane


def _block_sum(f: Callable, a: float, h: float, g, n: int) -> jnp.ndarray:
    """Weighted Σ f over the CHUNK points of global chunk ``g`` (traced int32),
    masking lanes past point ``n`` and half-weighting the global endpoints."""
    _, last_chunk, last_lane = _chunk_grid(n)
    r = lax.broadcasted_iota(jnp.int32, (CHUNK, 1), 0).squeeze(-1)
    in_range = (g < last_chunk) | ((g == last_chunk) & (r <= last_lane))
    is_first = (g == 0) & (r == 0)
    is_last = (g == last_chunk) & (r == last_lane)
    w = jnp.where(is_first | is_last, 0.5, 1.0).astype(jnp.float32)
    x = (
        jnp.float32(a)
        + g.astype(jnp.float32) * jnp.float32(CHUNK * h)
        + r.astype(jnp.float32) * jnp.float32(h)
    )
    return jnp.sum(jnp.where(in_range, w * f(x), 0.0))


def trapezoid_shard_sum(
    f: Callable, a: float, b: float, n: int, axis_name: str
) -> jnp.ndarray:
    """Per-device partial trapezoid sum; call inside ``shard_map``.

    Whole chunks are dealt round-robin-free in contiguous ceil-blocks over
    the mesh axis (the TPU version of the reference's ``interval_size =
    ceil(N/size)`` chunking, ``integral.c:34,49``); returns the
    ``lax.psum``-reduced global integral.
    """
    p = lax.axis_size(axis_name)  # static: mesh shape known at trace time
    k = lax.axis_index(axis_name)
    h = (b - a) / n
    n_chunks, _, _ = _chunk_grid(n)
    per = (n_chunks + p - 1) // p  # ceil chunks per device (static)

    def body(c, carry):
        acc, comp = carry  # Kahan: comp carries the lost low-order bits
        g = k.astype(jnp.int32) * per + c  # global chunk id, int32-safe
        val = jnp.where(
            g < n_chunks, _block_sum(f, a, h, g, n), jnp.float32(0.0)
        )
        y = val - comp
        t = acc + y
        comp = (t - acc) - y
        return (t, comp)

    partial, _ = lax.fori_loop(
        0, per, body, (jnp.float32(0.0), jnp.float32(0.0))
    )
    return lax.psum(partial, axis_name) * jnp.float32(h)


def trapezoid_serial(f: Callable, a: float, b: float, n: int) -> jnp.ndarray:
    """Single-device vectorised trapezoid rule (the ``size==1`` fast path,
    ``integral.c:20-29``)."""
    h = (b - a) / n
    n_chunks, _, _ = _chunk_grid(n)

    def body(c, carry):
        acc, comp = carry  # Kahan compensated accumulation
        y = _block_sum(f, a, h, jnp.int32(0) + c, n) - comp
        t = acc + y
        comp = (t - acc) - y
        return (t, comp)

    total, _ = lax.fori_loop(0, n_chunks, body, (jnp.float32(0.0), jnp.float32(0.0)))
    return total * jnp.float32(h)
