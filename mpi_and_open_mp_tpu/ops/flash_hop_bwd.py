"""Pallas TPU kernels for the ring-attention HOP backward.

The multi-device ring backward (``parallel/context.py:_ring_flash_bwd``)
keeps its travelling-dk/dv contract: K/V blocks make a second trip
around the ring and every hop recomputes one score block's gradients
from the saved row statistics. These kernels are that per-hop block
gradient — the Pallas engine replacing the jnp ``_flash_block_grads``
fold (which stays the parity oracle and the ineligible-shape fallback).

Why not the bundled kernel's backward
(``jax.experimental.pallas.ops.tpu.flash_attention``)? Two reasons:

* The ring never enters the kernel's own vjp — the custom_vjp wraps the
  whole multi-hop trip, and a hop backward needs exactly one block's
  (dq, dk, dv) against the TRIP's logsumexp, not a full single-device
  backward. The bundled ``_flash_attention_bwd_*`` impls can be bent to
  that (residual trick ``m := L, l := 1``), but:
* jax 0.4.37's interpret-mode discharge rule breaks on their
  ``pl.load(ref, (0, 0, k_slice, slice(None)))`` int-index pattern and
  on ``pltpu.repeat`` — so the CPU-mesh test rig (the only rig the
  repo's parity gates run without hardware) could never execute them.

These kernels therefore use only the idioms the bundled FORWARD
single-step kernel proves safe under both Mosaic and the 0.4.37
interpreter: whole-block ``ref[0]`` reads, plain jnp broadcasting
(``x[:, None]``), ``lax.broadcasted_iota`` masks, ``pl.when``
predication, and output-ref accumulation over the minor grid dimension.

Layout: per-q-head ``(h, n, d)`` operands (GQA K/V pre-expanded by the
caller, plan-budgeted — the ppermutes still carry un-expanded blocks).
The per-row statistics ``L`` (trip logsumexp) and ``D = rowsum(do·o)``
arrive lane-broadcast to ``(h, n, LANES)`` — see :func:`lane_broadcast`
— because a ``(1, blk)`` window would put the rows on lanes; inside the
kernel a lane-reduction (``jnp.max`` over identical lanes, the same op
shape as the forward kernel's row-max) recovers the ``(blk, 1)``
column. Outputs are float32; matmuls run on the MXU in the operands'
dtype with ``preferred_element_type=float32``.

The arithmetic is exactly ``_flash_block_grads``:

    p  = exp(s - L)         (s causal-masked additively before the exp)
    dv = pᵀ do ;  t = p ∘ (do vᵀ - D)
    dq = scale · t k ;  dk = scale · tᵀ q

``causal=True`` is the hop-0 diagonal triangle in LOCAL coordinates
(row block iq, col block ik: keep ``col <= row``); every other unskipped
hop is fully unmasked. Above-diagonal tiles are ``pl.when``-skipped.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Lane width the L/D statistics are broadcast to (the TPU vector lane
# count; the bundled kernel pads its l/m residuals the same way).
LANES = 128

# Score-block temporaries are (blk, blk) f32 and there are ~3 of them
# live (p, dp, t) next to the 6 operand blocks: 512 keeps the footprint
# ~4 MB, comfortably inside VMEM; 1024 would put the temporaries alone
# at 12 MB. Callers cap their block edge here (the single-device
# backward's grid-occupancy floor independently prefers <= 512 edges).
MAX_BLOCK = 512

_NEG = -1e30
_TRANS_B = (((1,), (1,)), ((), ()))   # x @ y.T
_TRANS_A = (((0,), (0,)), ((), ()))   # x.T @ y (contract the q rows)


def lane_broadcast(x):
    """``(h, n)`` row statistic -> ``(h, n, LANES)`` with identical
    lanes, the layout the kernels take L and D in."""
    return jnp.broadcast_to(x[..., None], (*x.shape, LANES))


def _col(x128):
    # (blk, LANES) identical lanes -> (blk, 1): a lane reduction, the
    # same op shape as the forward kernel's row-max (chip-validated),
    # instead of a width-1 lane slice.
    return jnp.max(x128, axis=1)[:, None]


def _block_scores(q, k, scale, causal, iq, ik, blk):
    s = lax.dot_general(q, k, _TRANS_B,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        shape = (blk, blk)
        row = lax.broadcasted_iota(jnp.int32, shape, 0) + iq * blk
        col = lax.broadcasted_iota(jnp.int32, shape, 1) + ik * blk
        s = jnp.where(col <= row, s, _NEG)
    return s


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, l_ref, d_ref, dq_ref, *,
               scale, causal, blk):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _():
        dq_ref[0] = jnp.zeros_like(dq_ref[0])

    live = iq >= ik if causal else ik >= 0

    @pl.when(live)
    def _():
        k = k_ref[0]
        s = _block_scores(q_ref[0], k, scale, causal, iq, ik, blk)
        p = jnp.exp(s - _col(l_ref[0]))
        dp = lax.dot_general(do_ref[0], v_ref[0], _TRANS_B,
                             preferred_element_type=jnp.float32)
        t = p * (dp - _col(d_ref[0]))
        dq_ref[0] += scale * lax.dot_general(
            t.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, l_ref, d_ref, dk_ref,
                dv_ref, *, scale, causal, blk):
    ik = pl.program_id(1)
    iq = pl.program_id(2)

    @pl.when(iq == 0)
    def _():
        dk_ref[0] = jnp.zeros_like(dk_ref[0])
        dv_ref[0] = jnp.zeros_like(dv_ref[0])

    live = iq >= ik if causal else iq >= 0

    @pl.when(live)
    def _():
        q = q_ref[0]
        do = do_ref[0]
        s = _block_scores(q, k_ref[0], scale, causal, iq, ik, blk)
        p = jnp.exp(s - _col(l_ref[0]))
        dv_ref[0] += lax.dot_general(p.astype(do.dtype), do, _TRANS_A,
                                     preferred_element_type=jnp.float32)
        dp = lax.dot_general(do, v_ref[0], _TRANS_B,
                             preferred_element_type=jnp.float32)
        t = p * (dp - _col(d_ref[0]))
        dk_ref[0] += scale * lax.dot_general(
            t.astype(q.dtype), q, _TRANS_A,
            preferred_element_type=jnp.float32)


def hop_block_grads(q, do, L128, D128, kb, vb, *, causal: bool,
                    blk: int, interpret: bool = False):
    """One hop's block gradients ``(dq, dk, dv)``, all float32.

    ``q``/``do`` ``(h, nq, d)``; ``kb``/``vb`` ``(h, nk, d)`` (GQA
    pre-expanded); ``L128``/``D128`` ``(h, nq, LANES)`` lane-broadcast
    (:func:`lane_broadcast`). ``blk`` must divide both sequence edges
    (and stay within :data:`MAX_BLOCK` for the VMEM footprint the
    kernels were sized for). Two kernel launches: dq accumulates over
    the k-block grid axis, dk/dv over the q-block axis — both via
    output-ref revisiting on the minor ("arbitrary") grid dimension.
    """
    h, nq, d = q.shape
    nk = kb.shape[1]
    if nq % blk or nk % blk or blk > MAX_BLOCK:
        raise ValueError(
            f"hop_block_grads: block {blk} must divide nq={nq} and "
            f"nk={nk} and be <= {MAX_BLOCK}")
    scale = 1.0 / math.sqrt(d)
    f32 = jnp.float32
    sem = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))

    qside = pl.BlockSpec((1, blk, d), lambda ih, ia, ib: (ih, ia, 0))
    kside_minor = pl.BlockSpec((1, blk, d), lambda ih, ia, ib: (ih, ib, 0))
    stat = pl.BlockSpec((1, blk, LANES), lambda ih, ia, ib: (ih, ia, 0))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal, blk=blk),
        grid=(h, nq // blk, nk // blk),
        in_specs=[qside, kside_minor, kside_minor, qside, stat, stat],
        out_specs=pl.BlockSpec((1, blk, d), lambda ih, ia, ib: (ih, ia, 0)),
        out_shape=jax.ShapeDtypeStruct((h, nq, d), f32),
        compiler_params=sem,
        interpret=interpret,
    )(q, kb, vb, do, L128, D128)

    # dk/dv: k blocks on the revisited (major) axis, q on the minor.
    qside2 = pl.BlockSpec((1, blk, d), lambda ih, ia, ib: (ih, ib, 0))
    kside2 = pl.BlockSpec((1, blk, d), lambda ih, ia, ib: (ih, ia, 0))
    stat2 = pl.BlockSpec((1, blk, LANES), lambda ih, ia, ib: (ih, ib, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal, blk=blk),
        grid=(h, nk // blk, nq // blk),
        in_specs=[qside2, kside2, kside2, qside2, stat2, stat2],
        out_specs=[
            pl.BlockSpec((1, blk, d), lambda ih, ia, ib: (ih, ia, 0))] * 2,
        out_shape=[jax.ShapeDtypeStruct((h, nk, d), f32)] * 2,
        compiler_params=sem,
        interpret=interpret,
    )(q, kb, vb, do, L128, D128)
    return dq, dk, dv
