"""Plain Game of Life on a torus: the benchmark's reference.

Independent of the code under test: nothing here imports the program.
B3/S23 on a periodic ``(ny, nx)`` board of 0/1 ``uint8`` cells, as the
reference repository's ``3-life/life2d.c`` steps it. The plain form is
four ``jnp.roll`` calls and a compare, one byte a cell. Where ``nx`` is a
multiple of 32 the board is stepped 32 cells to a ``uint32`` word
instead: the eight neighbours are shifted words, counted by a three-bit
ripple counter, ~10x faster at 8192^2 and checked against the plain form
in the tests. Both run on whatever device JAX gives them (the chip in a
benchmark run, the CPU in the tests), after the measured window has
closed.

``life_steps_dead_edge`` is the control: the same rule with the torus
wrap left out (cells beyond the edge read as dead). It breaks the one
guarantee the configurations state besides the rule, so a comparison
against :func:`life_steps` has to call it wrong.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _rule(board, neighbours):
    return ((neighbours == 3) | ((board == 1) & (neighbours == 2))).astype(
        jnp.uint8)


def _torus_step(b):
    rows = jnp.roll(b, 1, 0) + b + jnp.roll(b, -1, 0)
    n = jnp.roll(rows, 1, 1) + rows + jnp.roll(rows, -1, 1) - b
    return _rule(b, n)


def _dead_edge_step(b):
    p = jnp.pad(b, 1)
    rows = p[:-2] + p[1:-1] + p[2:]
    n = rows[:, :-2] + rows[:, 1:-1] + rows[:, 2:] - b
    return _rule(b, n)


def _packed_torus_step(p):
    """One step of a board packed along rows: bit ``k`` of word ``j`` is
    column ``32 j + k``."""
    west = (p << 1) | (jnp.roll(p, 1, 1) >> 31)  # column c - 1, at c
    east = (p >> 1) | (jnp.roll(p, -1, 1) << 31)  # column c + 1, at c
    planes = [west, east]
    for shift in (1, -1):  # the row above, then the row below
        planes += [jnp.roll(x, shift, 0) for x in (west, p, east)]
    zero = jnp.zeros_like(p)
    s0, s1, s4 = zero, zero, zero  # count bits 1 and 2; "4 or more"
    for x in planes:
        c0 = s0 & x
        s0 = s0 ^ x
        s4 = s4 | (s1 & c0)
        s1 = s1 ^ c0
    return s1 & ~s4 & (s0 | p)  # 3 neighbours, or 2 and alive


@functools.partial(jax.jit, static_argnums=1)
def _run(board, step, steps):
    return jax.lax.fori_loop(0, steps, lambda _, b: step(b), board)


def _steps(board: np.ndarray, steps: int, step, device=None) -> np.ndarray:
    b = jax.device_put(np.asarray(board, np.uint8), device)
    out = _run(b, step, jnp.int32(steps))
    return np.asarray(jax.device_get(out))


def _pack(board: np.ndarray) -> np.ndarray:
    bits = np.packbits(np.asarray(board, np.uint8), axis=1,
                       bitorder="little")
    return np.ascontiguousarray(bits).view("<u4")


def _unpack(words: np.ndarray) -> np.ndarray:
    return np.unpackbits(np.ascontiguousarray(words, "<u4").view(np.uint8),
                         axis=1, bitorder="little")


def life_steps_plain(board: np.ndarray, steps: int,
                     device=None) -> np.ndarray:
    """``board`` advanced ``steps`` generations on the torus, a byte a
    cell."""
    return _steps(board, steps, _torus_step, device)


def life_steps(board: np.ndarray, steps: int, device=None) -> np.ndarray:
    """``board`` advanced ``steps`` generations on the torus: packed 32
    cells to a word where the width allows, else a byte a cell."""
    if board.shape[1] % 32:
        return life_steps_plain(board, steps, device)
    p = jax.device_put(_pack(board), device)
    out = _run(p, _packed_torus_step, jnp.int32(steps))
    return _unpack(np.asarray(jax.device_get(out)))


def life_steps_dead_edge(board: np.ndarray, steps: int,
                         device=None) -> np.ndarray:
    """The control: ``steps`` generations with no wrap at the edges."""
    return _steps(board, steps, _dead_edge_step, device)
