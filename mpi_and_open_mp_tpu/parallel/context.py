"""Long-context sequence/context parallelism: ring attention + Ulysses.

The reference's 1-D ring domain decomposition with neighbour halo exchange
(``/root/reference/3-life/life_mpi.c:103,150-176,198-209``) is structurally
the communication pattern of ring attention: a ring of peers, each owning a
contiguous slab of one long axis, streaming boundary/block state to the next
peer. This module makes that correspondence concrete — the framework's
first-class long-context layer, built on the exact same primitives as the
Life halo exchange (``parallel.halo.ring_perm`` + ``lax.ppermute`` inside
``shard_map`` over a named mesh axis):

* ``ring_attention`` — sequence-sharded attention where K/V blocks rotate
  around the ring, one hop per step, combined with an online-softmax
  (flash-style) running max/sum so the full score matrix never materialises.
  Comm rides ICI ``ppermute`` exactly like the ghost-row exchange, and is
  double-buffered: each hop issues the next rotation BEFORE folding the
  block in hand, so the transfer overlaps the MXU block matmuls; compute
  per hop is a dense (n_local x n_local) block that maps onto the MXU.
  An optional striped/zigzag token layout (``layout="zigzag"`` +
  ``zigzag_shard``/``zigzag_unshard``) balances CAUSAL work: half-block
  hops, uniform across devices, roughly halving the causal trip's
  critical path.
* ``ulysses_attention`` — the all-to-all alternative: ``lax.all_to_all``
  re-shards from sequence-parallel to head-parallel, runs full local
  attention per head group, and all-to-alls back. Two collectives total
  instead of ``p`` hops; the better choice when heads >= devices and the
  fabric favours large transposes.

Both are differentiable (static ring trip count => ``fori_loop`` lowers to
``scan``), accept any float dtype, and accumulate in float32. Parity oracle:
``attention_reference`` on the gathered sequence.
"""

from __future__ import annotations

import contextlib
import functools
import math
import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from mpi_and_open_mp_tpu.parallel import mesh as mesh_lib
from mpi_and_open_mp_tpu.parallel.halo import ring_perm

AXIS_SP = "sp"

# Finite "minus infinity" for masked scores: large enough that exp() of a
# masked-vs-unmasked gap underflows to 0, small enough that NEG - NEG = 0
# stays exact (avoids the -inf - -inf = nan trap in the online softmax).
_NEG = -1e30


def attention_reference(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, causal: bool = False
) -> jnp.ndarray:
    """Plain single-device softmax attention — the parity oracle.

    Shapes ``(heads, seq, head_dim)``; float32 softmax regardless of input
    dtype, result cast back to ``q.dtype``.
    """
    h, n, d = q.shape
    s = jnp.einsum(
        "hqd,hkd->hqk", q, k, preferred_element_type=jnp.float32
    ) * (1.0 / math.sqrt(d))
    if causal:
        qpos = jnp.arange(n)[:, None]
        kpos = jnp.arange(n)[None, :]
        s = jnp.where(qpos >= kpos, s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hqk,hkd->hqd", p, v.astype(jnp.float32))
    return o.astype(q.dtype)


# Per-device q-chunk size: when a shard's local sequence exceeds this, the
# per-hop fold scans over q chunks (padding non-multiple lengths) so the
# materialised score block is (heads, _Q_CHUNK, n_local) instead of
# (heads, n_local, n_local) — long contexts on few devices would otherwise
# OOM HBM (a 16k-token shard is a 16 GB fp32 score matrix).
_Q_CHUNK = 512


def _ring_positions(layout: str, dev, p: int, nl: int, local_rows):
    """Global token positions for local row indices of a ring shard.

    ``contiguous``: shard ``dev`` owns tokens ``[dev*nl, (dev+1)*nl)`` —
    the natural split, with causal hop skipping but causal load
    IMBALANCE (ring position p-1 computes p blocks per trip, position 0
    one — the straggler sets the pace).

    ``zigzag``: tokens are pre-sharded in ``2p`` half-chunks of
    ``nl/2``; shard ``dev`` owns half-chunks ``(dev, 2p-1-dev)`` — the
    striped/zigzag causal-balancing layout: every shard holds an equal
    share of early AND late tokens, so every hop carries the same
    half-masked block of work on every device. Use
    :func:`zigzag_shard` / :func:`zigzag_unshard` to move operands
    between natural and zigzag order.
    """
    if layout == "zigzag":
        if nl % 2:
            raise ValueError(
                f"zigzag layout needs an even local length, got {nl}")
        half = nl // 2
        lo = local_rows < half
        chunk = jnp.where(lo, dev, 2 * p - 1 - dev)
        return chunk * half + local_rows - jnp.where(lo, 0, half)
    if layout != "contiguous":
        raise ValueError(f"unknown ring layout {layout!r}")
    return dev * nl + local_rows


@functools.lru_cache(maxsize=64)
def zigzag_order(n: int, p: int):
    """Natural token position held at each zigzag slot. Pure host numpy
    (cached): ``x_zig = x[..., zigzag_order(n, p), :]`` produces the
    operand order ``ring_attention(layout="zigzag")`` expects over a
    ``p``-ring — no device ops are dispatched building it."""
    import numpy as np

    if n % (2 * p):
        raise ValueError(f"zigzag needs seq % (2*mesh) == 0, got {n}/{p}")
    nl = n // p
    half = nl // 2
    slot = np.arange(n)
    shard, r = slot // nl, slot % nl
    lo = r < half
    chunk = np.where(lo, shard, 2 * p - 1 - shard)
    out = chunk * half + np.where(lo, r, r - half)
    out.setflags(write=False)  # cached: a caller mutation must not poison it
    return out


@functools.lru_cache(maxsize=64)
def _zigzag_inverse(n: int, p: int):
    import numpy as np

    out = np.argsort(zigzag_order(n, p))
    out.setflags(write=False)
    return out


def zigzag_shard(x, p: int):
    """Permute ``(heads, seq, d)`` from natural to zigzag ring order."""
    return jnp.take(x, zigzag_order(x.shape[1], p), axis=1)


def zigzag_unshard(x, p: int):
    """Inverse of :func:`zigzag_shard` (zigzag order back to natural)."""
    return jnp.take(x, _zigzag_inverse(x.shape[1], p), axis=1)


def _mask_from_pos(qpos, kpos, n: int | None, causal: bool):
    """Boolean ``(nq, nk)`` allow-mask from position vectors: ``kpos < n``
    validity (padding) when ``n`` is given, causality when ``causal`` —
    or None when everything is allowed."""
    valid = None
    if n is not None:
        valid = kpos[None, :] < n
    if causal:
        c = qpos[:, None] >= kpos[None, :]
        valid = c if valid is None else valid & c
    return valid


@functools.partial(jax.checkpoint, static_argnums=(5, 6))
def _block_update(q32, k, v, qpos, kpos, n, causal, o, m, l):
    """One online-softmax accumulation of a K/V block into (o, m, l).

    The allow-mask is built INSIDE from the ``qpos``/``kpos`` position
    vectors (``n`` = valid k length for padding, or None; ``causal``
    static). Running state: ``o`` (hq, nq, d) unnormalised output, ``m``
    (hq, nq) running max, ``l`` (hq, nq) running denominator — all
    float32.

    Rematerialised (``jax.checkpoint``): reverse-mode would otherwise
    store every block's softmax weights — O(seq²) residuals across the
    scan/ring — where recomputing them in the backward pass keeps
    training-style gradients O(chunk x seq) like the forward (the flash
    attention backward trick). Measured: a causal 16k-token backward on
    one chip OOMs HBM without this and runs with it. Building the mask
    in here (rather than passing it) matters for the same reason: a
    passed mask is a checkpoint residual — O(hq·nq·nk) bools per block
    stacked across the ring/scan — where the position vectors are O(n).
    (Neither production path differentiates through this any more: the
    local chunked path has ``_flash_chunked_bwd`` and the multi-device
    ring has ``_ring_flash_bwd``; the remat decorator remains as a
    safety net for any future caller that autodiffs a fold directly.)
    """
    d = q32.shape[-1]
    mask = _mask_from_pos(qpos, kpos, n, causal)
    s = jnp.einsum(
        "hqd,hkd->hqk", q32, k.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    ) * (1.0 / math.sqrt(d))
    if mask is not None:
        s = jnp.where(mask, s, _NEG)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None])
    if mask is not None:
        p = p * mask  # exp(NEG - NEG) = 1 on fully-masked rows; zero it
    corr = jnp.exp(m - m_new)
    l = l * corr + jnp.sum(p, axis=-1)
    o = o * corr[..., None] + jnp.einsum(
        "hqk,hkd->hqd", p, v.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    return o, m_new, l


def _ring_attention_local(q, k, v, *, axis: str, causal: bool,
                          layout: str = "contiguous"):
    """Per-shard body (inside ``shard_map``): rotate K/V around the ring.

    Each of the ``p`` hops computes one (n_local x n_local) score block
    (its live quarter-blocks under the causal-zigzag layout) and folds it
    into the online softmax; K/V then move one hop forward — the
    attention analogue of the ghost-row ``ppermute`` at
    ``parallel/halo.py:halo_pad_y`` (reference: ``3-life/life_mpi.c:203-207``).

    Differentiation takes the ring flash backward (``_ring_flash``'s
    ``custom_vjp``): the forward saves only ``(q, k, v, o, logsumexp)``
    per shard — O(seq·d/p) — and the backward re-rotates K/V around the
    ring, recomputing each block from the saved row statistics.
    """
    p = lax.axis_size(axis)
    if p == 1:
        # A 1-device ring is just full local attention (under EITHER
        # layout: the p=1 zigzag order is the identity); the
        # doubly-chunked local path additionally skips future k blocks
        # under causal. GQA folds query groups on the jnp engine; on
        # TPU, budget-fitting GQA expands K/V into the Pallas kernel
        # instead (_flash_dispatch_plan).
        return _attention_chunked(q, k, v, causal)
    return _ring_flash(axis, causal, layout, q, k, v)


def _ring_forward(axis: str, causal: bool, layout: str, q, k, v):
    """The rotate-and-fold forward; returns the normalised output and the
    per-row logsumexp ``L = m + log l`` of the scaled scores in the FOLDED
    GQA layout ``(hkv, n_local·g)`` — the one statistic the ring backward
    needs to recompute any hop's probabilities as ``exp(s - L)``.

    ``layout`` picks the token-to-shard map (:func:`_ring_positions`):
    every position the masks see flows from it. Causal-zigzag hops run
    HALF-blocks (live-pair table in the zigzag branch below): per hop
    each device computes only its live (q-half x k-half) pairs — two
    quarter-size blocks off the diagonal, three (two of them
    half-masked) on the src == idx hop — so a causal trip costs every
    device about half a full-block per hop, versus the contiguous split
    where hop wall-clock is set by whichever device's block is
    unskipped (the straggler)."""
    p = lax.axis_size(axis)
    # TPU-eligible hop shapes take the per-hop Pallas engine instead of
    # the jnp fold below (which remains the oracle and the fallback) —
    # same ring schedule, flash-kernel hops, online-softmax merge.
    hop_plan = _ring_hop_plan(q, k, v, causal, layout)
    if hop_plan is not None:
        if causal and layout == "zigzag":
            return _ring_forward_hopflash_zz(axis, p, q, k, v, hop_plan)
        return _ring_forward_hopflash(axis, causal, p, q, k, v, hop_plan)
    # Non-causal folds build no masks, so every consumer of the axis
    # index is dead code — and jax 0.4.37's shard_map does not DCE the
    # resulting bare partition_id, which the SPMD partitioner then
    # rejects. Only materialise the index when a mask can consume it.
    idx = lax.axis_index(axis) if causal else 0
    nl, d = q.shape[1:]
    hkv = k.shape[0]
    g = q.shape[0] // hkv
    # GQA stays un-expanded through the whole ring: K/V blocks ride the
    # ppermutes at hkv heads and the folds run q with query groups
    # folded into the row axis (row r <-> position r // g), exactly like
    # the local flash path — no repeated K/V is ever materialised.
    q32 = _fold_groups(q.astype(jnp.float32), hkv, g)
    perm = ring_perm(p, 1)
    cg = _Q_CHUNK * g
    zz = causal and layout == "zigzag"

    def make_folder(npos, qsub, qpos_of):
        """(state0, fold, finish) for a q subset of ``npos`` positions
        (folded rows ``npos*g``). Flash-style q chunking whenever the
        subset is long: q rows are independent, so pad them to a chunk
        multiple (padded rows compute junk that ``finish`` slices off)
        — no divisibility cliff. ``qpos_of`` maps subset-local position
        indices to global token positions."""
        chunked = npos > _Q_CHUNK
        nc = -(-npos // _Q_CHUNK)
        npp = nc * _Q_CHUNK if chunked else npos
        if npp != npos:
            qsub = jnp.pad(qsub, ((0, 0), (0, (npp - npos) * g), (0, 0)))
        rows = npp * g
        state0 = (jnp.zeros((hkv, rows, d), jnp.float32),
                  jnp.full((hkv, rows), _NEG, jnp.float32),
                  jnp.zeros((hkv, rows), jnp.float32))

        def fold(state, kb, vb, kpos):
            o, m, l = state
            if not chunked:
                qpos = qpos_of(jnp.arange(npos * g) // g)
                return _block_update(qsub, kb, vb, qpos, kpos, None,
                                     causal, o, m, l)
            # Scan q (and its running state) in (hkv, _Q_CHUNK * g)
            # folded slices so only a (hkv, _Q_CHUNK * g, nk) score
            # block is ever live.

            def body(_, xs):
                qc, oc, mc, lc, ci = xs
                qpos = qpos_of(ci * _Q_CHUNK + jnp.arange(cg) // g)
                oc, mc, lc = _block_update(qc, kb, vb, qpos, kpos, None,
                                           causal, oc, mc, lc)
                return None, (oc, mc, lc)

            _, (os_, ms, ls) = lax.scan(
                body, None,
                (_chunk(qsub, nc, cg), _chunk(o, nc, cg),
                 _chunk(m, nc, cg), _chunk(l, nc, cg), jnp.arange(nc)))
            return _unchunk(os_), _unchunk(ms), _unchunk(ls)

        def finish(state):
            return tuple(x[:, : npos * g] for x in state)

        return state0, fold, finish

    if not zz:
        state0, fold_q, finish = make_folder(
            nl, q32, lambda r: _ring_positions(layout, idx, p, nl, r))

        def fold(j, state, kb, vb):
            # After j forward rotations my K/V block originated on ring
            # position (idx - j) mod p.
            src = (idx - j) % p
            kpos = _ring_positions(layout, src, p, nl, jnp.arange(nl))
            if not causal:
                return fold_q(state, kb, vb, kpos)
            # Contiguous causal: blocks entirely in the future
            # (src > idx) contribute nothing; skip their matmul+exp
            # instead of computing and masking it out (~(p-1)/2 of the
            # hops on average). The predicate differs per device
            # (idx-dependent), so neither branch may contain a
            # collective — the ppermutes stay outside, in the hop body.
            # cond is reverse-mode differentiable; the scan lowering is
            # unaffected.
            return lax.cond(
                src <= idx,
                lambda s: fold_q(s, kb, vb, kpos),
                lambda s: s,
                state)
    else:
        # Causal zigzag: shard idx holds half-chunks (idx, 2p-1-idx) of
        # size half = nl/2. Of the four (q-half x k-half) pairs per hop
        # only these ever carry unmasked work (`_zz_pairs`):
        #   (lo, lo)  iff src <= idx   (diagonal at src == idx)
        #   (hi, lo)  always           (high chunks are after every low)
        #   (hi, hi)  iff src >= idx   (diagonal at src == idx)
        # — (lo, hi) is always fully masked. That is two quarter-blocks
        # per off-diagonal hop (three on the diagonal hop, two of them
        # half-masked) on EVERY device: balanced, and about half the
        # FLOPs of a masked full block.
        half = nl // 2
        hg = half * g
        s_lo0, fold_lo, fin_lo = make_folder(
            half, q32[:, :hg], lambda r: idx * half + r)
        s_hi0, fold_hi, fin_hi = make_folder(
            half, q32[:, hg:], lambda r: (2 * p - 1 - idx) * half + r)

        def fold(j, state, kb, vb):
            s_lo, s_hi = state
            src = (idx - j) % p
            k_lo, k_hi = kb[:, :half], kb[:, half:]
            v_lo, v_hi = vb[:, :half], vb[:, half:]
            kpos_lo = src * half + jnp.arange(half)
            kpos_hi = (2 * p - 1 - src) * half + jnp.arange(half)
            s_lo = lax.cond(
                src <= idx,
                lambda s: fold_lo(s, k_lo, v_lo, kpos_lo),
                lambda s: s, s_lo)
            s_hi = fold_hi(s_hi, k_lo, v_lo, kpos_lo)
            s_hi = lax.cond(
                src >= idx,
                lambda s: fold_hi(s, k_hi, v_hi, kpos_hi),
                lambda s: s, s_hi)
            return s_lo, s_hi

        state0 = (s_lo0, s_hi0)

    # Chaos hook (robust.chaos): a planned nan_hop/inf_hop poisons the
    # K/V partials of exactly that hop, baked in at trace time. The jnp
    # fold carries the same injection point as the per-hop Pallas engine
    # so an UNgated fold provably diverges under injection; the guarded
    # recovery path re-traces under chaos.suppressed() and stays clean.
    # When MOMP_CHAOS is unset no ops are added (trace-time `is None`).
    from mpi_and_open_mp_tpu.robust import chaos as _chaos

    _poison = _chaos.hop_poison_spec()
    if _poison is not None:
        fold = _chaos.poisoned_fold(fold, _poison)

    def hop(j, carry):
        state, kb, vb = carry
        # Double-buffered rotation: issue the NEXT hop's K/V transfer
        # before folding the block just received, so the async
        # collective-permute rides the fabric while the MXU computes the
        # score block (XLA's latency-hiding scheduler pairs the
        # permute-start here with a permute-done after the fold — the
        # fold reads only the held kb/vb, never the in-flight pair). The
        # ppermutes stay unconditional and outside fold's causal `cond`:
        # collectives inside a per-device branch would deadlock the ring.
        kb_next = lax.ppermute(kb, axis, perm)
        vb_next = lax.ppermute(vb, axis, perm)
        state = fold(j, state, kb, vb)
        return state, kb_next, vb_next

    # p-1 rotate+compute hops, then a final fold with no trailing rotation
    # (the p-th ppermute pair would only feed discarded loop carries).
    state, kb, vb = lax.fori_loop(0, p - 1, hop, (state0, k, v))
    state = fold(p - 1, state, kb, vb)
    if zz:
        o, m, l = (jnp.concatenate(parts, axis=1) for parts in zip(
            fin_lo(state[0]), fin_hi(state[1])))
    else:
        o, m, l = finish(state)
    L = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-37)), -_NEG)
    o = o / jnp.where(l > 0, l, 1.0)[..., None]
    return _unfold_groups(o, hkv, g).astype(q.dtype), L


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _ring_flash(axis: str, causal: bool, layout: str, q, k, v):
    return _ring_forward(axis, causal, layout, q, k, v)[0]


def _ring_flash_fwd(axis: str, causal: bool, layout: str, q, k, v):
    o, L = _ring_forward(axis, causal, layout, q, k, v)
    return o, (q, k, v, o, L)


def _flash_block_grads(qc, doc, Lc, Dc, kb, vb, mask, scale: float):
    """One block of the flash backward — THE shared arithmetic of the
    chunked (``_flash_chunked_bwd``) and ring (``_ring_flash_bwd``)
    backwards, so the two paths cannot drift numerically:

        p  = exp(s - L)            (recomputed; ``mask`` = allow or None)
        dv = pᵀ do ;  t = p∘(do vᵀ - D)
        dq = scale · t k ;  dk = scale · tᵀ q

    All operands float32. Folded GQA q rows carry all g groups: the
    dk/dv einsums sum the group contributions into the hkv kv heads.
    Returns ``(dq, dk, dv)`` for the block.
    """
    f32 = jnp.float32
    s = jnp.einsum("hqd,hkd->hqk", qc, kb,
                   preferred_element_type=f32) * scale
    p = jnp.exp(s - Lc[..., None])
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    dp = jnp.einsum("hqd,hkd->hqk", doc, vb, preferred_element_type=f32)
    t = p * (dp - Dc[..., None])
    return (
        scale * jnp.einsum("hqk,hkd->hqd", t, kb,
                           preferred_element_type=f32),
        scale * jnp.einsum("hqk,hqd->hkd", t, qc,
                           preferred_element_type=f32),
        jnp.einsum("hqk,hqd->hkd", p, doc, preferred_element_type=f32),
    )


def _ring_flash_bwd(axis: str, causal: bool, layout: str, res, do):
    """Ring flash backward: O(seq·d/p) residuals on the sharded path.

    K/V blocks make a second trip around the ring, each carrying its own
    ``(dk, dv)`` accumulator: at every hop the local device recomputes the
    block's probabilities from the saved logsumexp (``p = exp(s - L)``),
    folds the block's contribution into its local ``dq`` and into the
    travelling accumulators, and forwards all four. After ``p`` rotations
    the accumulators are back on their home shard having collected every
    device's contribution — the gradient analogue of the forward's
    rotate-and-fold, same ``ppermute`` fabric, no gather. Per block the
    arithmetic matches ``_flash_chunked_bwd``:

        p  = exp(s - L)            (recomputed, causal-masked)
        D  = rowsum(do * o)
        dv += pᵀ do ;  t = p∘(do vᵀ - D)
        dq += scale · t k ;  dk += scale · tᵀ q

    Causal hop skipping mirrors the forward (blocks with src > idx are
    never computed); the ``ppermute``s stay unconditional and outside the
    per-device ``cond`` — a collective inside a branch would deadlock the
    ring. GQA runs in the same folded layout as the forward: ``dk``/``dv``
    come out group-summed, ``dq`` is unfolded at the end.
    """
    q, k, v, o, L = res
    p = lax.axis_size(axis)
    # TPU-eligible hop shapes take the per-hop Pallas backward kernels
    # instead of the jnp fold below (which remains the oracle and the
    # ineligible-shape fallback) — same travelling-dk/dv schedule,
    # kernel-rate per-hop block gradients.
    bwd_plan = _ring_hop_bwd_plan(q, k, v, causal, layout)
    if bwd_plan is not None:
        return _ring_backward_hopflash(axis, causal, p, res, do, bwd_plan)
    # See the forward's note: keep the axis index out of the non-causal
    # trace (its consumers are all dead there and 0.4.37's shard_map
    # leaves the bare partition_id for the SPMD partitioner to reject).
    idx = lax.axis_index(axis) if causal else 0
    nl, d = q.shape[1:]
    hkv = k.shape[0]
    g = q.shape[0] // hkv
    scale = 1.0 / math.sqrt(d)
    f32 = jnp.float32
    perm = ring_perm(p, 1)

    q32 = _fold_groups(q.astype(f32), hkv, g)
    do32 = _fold_groups(do.astype(f32), hkv, g)
    o32 = _fold_groups(o.astype(f32), hkv, g)
    D = jnp.sum(do32 * o32, axis=-1)  # (hkv, nl*g)
    Lf = L

    cg = _Q_CHUNK * g
    zz = causal and layout == "zigzag"

    def block_grads(qc, doc, Lc, Dc, qpos, kpos, kb32, vb32):
        mask = _mask_from_pos(qpos, kpos, None, causal)
        return _flash_block_grads(qc, doc, Lc, Dc, kb32, vb32, mask, scale)

    def make_bwd(npos, qsub, dosub, Lsub, Dsub, qpos_of):
        """Per-hop (dq, dk, dv) contribution fn for a q subset of
        ``npos`` positions against one K/V block — the same q-chunking
        decision as the forward's folder; padded rows carry L = -_NEG
        (huge) so their recomputed p underflows to 0 — they contribute
        nothing to dk/dv and their dq rows are sliced off."""
        chunked = npos > _Q_CHUNK
        nc = -(-npos // _Q_CHUNK)
        npp = nc * _Q_CHUNK if chunked else npos
        if npp != npos:
            rows = (npp - npos) * g
            qsub = jnp.pad(qsub, ((0, 0), (0, rows), (0, 0)))
            dosub = jnp.pad(dosub, ((0, 0), (0, rows), (0, 0)))
            Dsub = jnp.pad(Dsub, ((0, 0), (0, rows)))
            Lsub = jnp.pad(Lsub, ((0, 0), (0, rows)),
                           constant_values=-_NEG)

        def contribution(kb32, vb32, kpos):
            if not chunked:
                qpos = qpos_of(jnp.arange(npos * g) // g)
                dqs, dkj, dvj = block_grads(qsub, dosub, Lsub, Dsub,
                                            qpos, kpos, kb32, vb32)
                return dqs, dkj, dvj

            def body(carry, xs):
                dka, dva = carry
                qc, doc, Lc, Dc, ci = xs
                qpos = qpos_of(ci * _Q_CHUNK + jnp.arange(cg) // g)
                dqc, dkc, dvc = block_grads(qc, doc, Lc, Dc, qpos, kpos,
                                            kb32, vb32)
                return (dka + dkc, dva + dvc), dqc

            z = jnp.zeros((hkv, kb32.shape[1], d), f32)
            (dkj, dvj), dqs = lax.scan(
                body, (z, z),
                (_chunk(qsub, nc, cg), _chunk(dosub, nc, cg),
                 _chunk(Lsub, nc, cg), _chunk(Dsub, nc, cg),
                 jnp.arange(nc)))
            return _unchunk(dqs)[:, : npos * g], dkj, dvj

        return contribution

    if not zz:
        contrib_q = make_bwd(
            nl, q32, do32, Lf, D,
            lambda r: _ring_positions(layout, idx, p, nl, r))

        def contribute(j, kb, vb):
            src = (idx - j) % p
            kpos = _ring_positions(layout, src, p, nl, jnp.arange(nl))
            if not causal:
                return contrib_q(kb.astype(f32), vb.astype(f32), kpos)
            # Hop skipping mirrors the forward (contiguous causal). The
            # f32 casts live INSIDE the taken branch: as cond operands
            # XLA would materialise them on skipped hops too.
            return lax.cond(
                src <= idx,
                lambda _: contrib_q(kb.astype(f32), vb.astype(f32), kpos),
                lambda _: (jnp.zeros((hkv, nl * g, d), f32),
                           jnp.zeros((hkv, nl, d), f32),
                           jnp.zeros((hkv, nl, d), f32)),
                None)
    else:
        # Same live-pair analysis as the forward's causal-zigzag fold:
        # (lo,lo) iff src <= idx; (hi,lo) always; (hi,hi) iff
        # src >= idx; (lo,hi) never — two quarter-blocks of gradient
        # work per off-diagonal hop (three on the diagonal hop),
        # uniformly across devices.
        half = nl // 2
        hg = half * g
        bwd_lo = make_bwd(half, q32[:, :hg], do32[:, :hg], Lf[:, :hg],
                          D[:, :hg], lambda r: idx * half + r)
        bwd_hi = make_bwd(half, q32[:, hg:], do32[:, hg:], Lf[:, hg:],
                          D[:, hg:], lambda r: (2 * p - 1 - idx) * half + r)

        def contribute(j, kb, vb):
            src = (idx - j) % p
            k_lo, k_hi = kb[:, :half], kb[:, half:]
            v_lo, v_hi = vb[:, :half], vb[:, half:]
            kpos_lo = src * half + jnp.arange(half)
            kpos_hi = (2 * p - 1 - src) * half + jnp.arange(half)

            def zero3(_):
                return (jnp.zeros((hkv, hg, d), f32),
                        jnp.zeros((hkv, half, d), f32),
                        jnp.zeros((hkv, half, d), f32))

            # f32 casts inside each taken branch (see the contiguous
            # note); the always-live (hi, lo) pair casts unconditionally.
            dq_lo, dk_lo, dv_lo = lax.cond(
                src <= idx,
                lambda _: bwd_lo(k_lo.astype(f32), v_lo.astype(f32),
                                 kpos_lo), zero3, None)
            dq_hi, dk_lo2, dv_lo2 = bwd_hi(k_lo.astype(f32),
                                           v_lo.astype(f32), kpos_lo)
            dq_hi2, dk_hi, dv_hi = lax.cond(
                src >= idx,
                lambda _: bwd_hi(k_hi.astype(f32), v_hi.astype(f32),
                                 kpos_hi), zero3, None)
            return (jnp.concatenate([dq_lo, dq_hi + dq_hi2], axis=1),
                    jnp.concatenate([dk_lo + dk_lo2, dk_hi], axis=1),
                    jnp.concatenate([dv_lo + dv_lo2, dv_hi], axis=1))

    def hop(j, carry):
        dq, kb, vb, dkb, dvb = carry
        # Prefetch the next K/V pair before the fold (the forward's
        # double-buffering); the accumulator permutes necessarily wait
        # on the fold's contribution.
        kb_next = lax.ppermute(kb, axis, perm)
        vb_next = lax.ppermute(vb, axis, perm)
        dqj, dkj, dvj = contribute(j, kb, vb)
        dkb = lax.ppermute(dkb + dkj, axis, perm)
        dvb = lax.ppermute(dvb + dvj, axis, perm)
        return dq + dqj, kb_next, vb_next, dkb, dvb

    z = jnp.zeros((hkv, nl, d), f32)
    dq, kb, vb, dkb, dvb = lax.fori_loop(
        0, p - 1, hop, (jnp.zeros((hkv, nl * g, d), f32), k, v, z, z))
    # Last block: contribute, then one final accumulator rotation (the
    # p-th) lands every (dk, dv) back on its home shard; kb/vb need no
    # trailing transfer.
    dqj, dkj, dvj = contribute(p - 1, kb, vb)
    dq = dq + dqj
    dk = lax.ppermute(dkb + dkj, axis, perm)
    dv = lax.ppermute(dvb + dvj, axis, perm)
    dq = _unfold_groups(dq, hkv, g).astype(q.dtype)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


_ring_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)


# On-TPU the single-device engine can dispatch to jax's bundled Pallas
# flash-attention kernel (block-pipelined HBM->VMEM, MXU-shaped tiles)
# instead of the jnp-chunked path, which tops out around 25% MFU as pure
# XLA. The kernel is only faster with EXPLICIT block sizes: chip
# head-to-head (v5 lite, 8 heads, d=128, causal bf16, chain-differenced)
# measured the kernel's own default blocks at 15-17 TFLOP/s forward —
# SLOWER than the 47-49 jnp engine — while uniform 512/1024 blocks reach
# 105-140 forward and 84-120 full-grad TFLOP/s (the jnp flash backward
# runs ~32). 2048 blocks fail to compile (VMEM). Dispatch therefore
# always passes explicit blocks (:func:`_flash_block_for`). The jnp
# path remains the CPU/interpret oracle and the fallback for shapes the
# kernel doesn't take. MOMP_TPU_FLASH=0 forces the jnp engine
# everywhere (and the sweep's parity gate flips this off at runtime if
# the kernel ever disagrees with the dense oracle).
_TPU_FLASH = os.environ.get("MOMP_TPU_FLASH", "1") != "0"

# MOMP_PALLAS_INTERPRET=1 routes Pallas-eligible shapes through the
# bundled kernel in Pallas interpret mode on ANY backend — the CPU-mesh
# test rig for kernel-inside-shard_map paths (tests/conftest.py pins 8
# virtual CPU devices; nothing here needs hardware). Interpret
# eligibility is narrower than the chip's: jax 0.4.37's interpret-mode
# discharge rule breaks on the kernel's scratch branch (block_k <
# kv_seq) and on the kernel's own backward, so only block == seq
# forwards qualify — exactly what the per-hop ring engine runs (our own
# custom_vjp supplies the ring backward; the kernel's vjp is never
# entered there).
_PALLAS_INTERPRET = os.environ.get("MOMP_PALLAS_INTERPRET", "0") == "1"


@contextlib.contextmanager
def _pallas_interpret_calls(fa):
    """Trace-time patch turning every ``pallas_call`` the bundled kernel
    makes into an interpret-mode call (jax 0.4.37 has no global
    interpret switch). A no-op unless ``_PALLAS_INTERPRET`` is set.
    Callers flipping the flag at runtime must ``jax.clear_caches()`` —
    the flag is not a jit cache key."""
    if not _PALLAS_INTERPRET:
        yield
        return
    orig = fa.pl.pallas_call
    fa.pl.pallas_call = functools.partial(orig, interpret=True)
    try:
        yield
    finally:
        fa.pl.pallas_call = orig

# Chip-validated uniform block edges, best first; the auto dispatch
# picks the largest that divides the sequence AND leaves at least
# _MIN_GRID programs per grid axis (gate + recorders then exercise that
# very configuration).
_AUTO_BLOCKS = (1024, 512, 256, 128)

# Grid-occupancy floor for the auto block choice. Chip-measured at 8k
# causal bf16 (8 heads, d=128): b=1024 leaves an 8x8 grid and the
# kernel's vjp collapses to 25.8 TFLOP/s grad (79.5 fwd); b=512 (16x16)
# measures 113.4 grad / 97.9 fwd — the backward needs >= ~16 programs
# per axis to fill the chip's pipeline. 16k+ at b=1024 already satisfy
# the floor (137-147 fwd measured). The floor applies at EVERY edge:
# 2k-4k sequences step down to 128/256 blocks for a full grid rather
# than keep the largest-dividing block with a starved 2-4 program grid
# (the 8k collapse extrapolated per-edge; the per-hop ring engine puts
# exactly these short local blocks on the kernel, so starved grids are
# no longer a corner case). A sequence too short to satisfy the floor
# with ANY edge (< 2048) takes the largest fitting block — at that size
# the kernel call is latency- not occupancy-bound.
_MIN_GRID = 16


def tpu_flash_engine() -> str:
    """Which engine ``flash_attention`` will dispatch eligible shapes to
    — ``"pallas"`` or ``"jnp"`` — for recorders' provenance fields.
    Off-TPU the answer is always ``"jnp"`` regardless of the flag."""
    try:
        on_tpu = jax.default_backend() == "tpu"
    except RuntimeError:
        on_tpu = False
    return "pallas" if (_TPU_FLASH and on_tpu) else "jnp"


def _fold_batch(x: jnp.ndarray) -> jnp.ndarray:
    """Fold a (B, h, n, d) request batch into the head axis: (B*h, n, d).

    Heads are UNSHARDED in every sequence-parallel spec here
    (``_seq_spec`` keeps axis 0 replicated), so a request batch rides
    the fold/kernel machinery unchanged as extra heads — including GQA:
    with g = H/Hkv query groups, folded q head ``b*H + h`` integer-
    divides by g to kv head ``b*Hkv + h//g``, i.e. exactly board ``b``'s
    own kv heads. Ring ``ppermute`` payloads become (B*Hkv, n_local, d)
    — one hop moves every request's K/V block."""
    return x.reshape((x.shape[0] * x.shape[1],) + tuple(x.shape[2:]))


def _fold_batch_probes(q, k, v):
    """ShapeDtypeStruct twins of :func:`_fold_batch` over (q, k, v) —
    engine-stamp functions probe shapes without touching data."""
    return tuple(
        jax.ShapeDtypeStruct(
            (x.shape[0] * x.shape[1],) + tuple(x.shape[2:]), x.dtype)
        for x in (q, k, v))


def flash_engine_for(q, k, v) -> str:
    """Shape-aware engine provenance: the engine ``flash_attention``
    will actually dispatch THESE operands to, with the effective block
    edge (``"pallas:b512"``) since perf swings ~8x across blocks.
    Recorders must stamp artifacts with this (not the flag-level
    :func:`tpu_flash_engine`): a block override that doesn't divide a
    timed sequence routes that shape to the jnp engine regardless of
    the flag. Sequences at or below the chunk size short-circuit to the
    dense reference before any engine dispatch and stamp ``"dense"``.

    4D ``(B, heads, seq, d)`` operands (the request-batched entry) fold
    the batch into the head axis exactly as ``flash_attention`` does,
    and the stamp gains a ``:b{B}`` suffix so recorded artifacts carry
    the batching alongside the block edge. Works on
    ``jax.ShapeDtypeStruct`` probes like the 3D form."""
    if len(q.shape) == 4:
        probe_q, probe_k, probe_v = _fold_batch_probes(q, k, v)
        return flash_engine_for(probe_q, probe_k, probe_v) + f":b{q.shape[0]}"
    if q.shape[1] <= _Q_CHUNK:  # mirrors _attention_chunked's ordering
        return "dense"
    plan = _flash_dispatch_plan(q, k, v)
    if plan is None:
        return "jnp"
    return _plan_stamp(plan)


def disable_tpu_flash() -> None:
    """Force the jnp engine from here on (recorders call this when the
    Pallas kernel fails a parity gate or fails to compile). Drops jit
    caches too: already-compiled callers would otherwise keep
    dispatching to the Pallas kernel, making the flip silently a no-op.
    """
    global _TPU_FLASH
    _TPU_FLASH = False
    jax.clear_caches()


def gated_parity_check(heads: int = 8, n: int = 2048, dim: int = 128,
                       seed: int = 0, for_seq: int | None = None,
                       kv_heads: int | None = None,
                       ) -> tuple[bool, str, list[str]]:
    """THE honesty gate every attention recorder runs before recording:
    check whatever engine :func:`flash_attention` dispatches to against
    the dense oracle — FORWARD AND FULL (q, k, v) GRADIENTS, since the
    recorders publish backward timings and the Pallas kernel brings its
    own custom_vjp that only this gate ever checks on chip — at f32,
    highest matmul precision (the default TPU f32 matmul takes bf16 MXU
    passes whose rounding would swamp the algorithmic tolerance); on a
    Pallas-engine failure (numeric or compile),
    :func:`disable_tpu_flash` and re-gate the jnp engine.

    ``for_seq`` aims the gate at the exact engine+block configuration a
    length-``for_seq`` dispatch will use (the dense oracle is O(n²), so
    the gate cannot simply run at the timed length): a Pallas-bound
    sequence pins its effective block for the gate's smaller run, and a
    jnp-bound one steers the gate sequence off the 128-multiple grid so
    the gate dispatches the jnp engine too. ``kv_heads`` gates a
    GQA/MQA configuration (fewer K/V heads): the gate operands carry it,
    so a timed GQA shape's engine — the expand dispatch, or folded jnp —
    is what gets checked; the ``for_seq`` routing probe uses bf16
    operands (what recorders time), since the expand budget is
    byte-counted. Recorders timing several sequences must gate once per
    distinct configuration (``_flash_block_for(seq, dim)`` x kv_heads).

    Returns ``(ok, engine, notes)`` — ``engine`` is the engine the gate
    passed on (= the one subsequent calls will use), ``notes`` records
    any per-engine failure on the way. Callers decide abort-vs-continue
    policy; the gate itself is shared so recorders cannot drift.
    """
    import numpy as np

    global _FORCED_BLOCK, _FORCED_BLOCK_BWD
    hkv = kv_heads or heads
    forced = 0
    forced_bwd = 0
    steer_jnp = False
    if for_seq is not None and tpu_flash_engine() == "pallas":
        # Route exactly as the timed shape will: same plan function,
        # bf16 shape probes (recorders time bf16; the expand budget is
        # byte-counted so dtype matters).
        sq = jax.ShapeDtypeStruct((heads, for_seq, dim), jnp.bfloat16)
        skv = jax.ShapeDtypeStruct((hkv, for_seq, dim), jnp.bfloat16)
        plan = (_flash_dispatch_plan(sq, skv, skv)
                if for_seq > _Q_CHUNK else None)
        if plan is not None:
            forced, forced_bwd = plan[1], plan[2]
        else:
            # The timed shape is jnp-bound (no block divides it, an
            # override doesn't, or its GQA expansion is over budget):
            # steer the gate sequence off the block grid so the gate
            # dispatches the jnp engine too.
            steer_jnp = True
            if n % 128 == 0:
                n += 16

    # The gate must exercise the same engine+block the timed shapes will
    # get: under a pin (MOMP_FLASH_BLOCK override, which wins, or the
    # for_seq force above), round the gate sequence up to a block
    # multiple so the Pallas kernel with those very block sizes is what
    # gets checked — otherwise an oversized block would make the gate
    # silently jnp-only while the recordings dispatch ungated. (Not
    # when steering jnp-ward: the round-up would put an overridden
    # block's multiple right back on the Pallas grid.)
    blk = _flash_block_override() or forced
    bwd = _flash_block_override_bwd() or forced_bwd or blk
    if blk and not steer_jnp:
        # With the backward edge decoupled, the gate sequence must be a
        # multiple of BOTH effective edges (the kernel rejects either
        # non-divisor), so round up to their lcm.
        m = math.lcm(blk, bwd)
        n = -(-n // m) * m
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((heads, n, dim)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((hkv, n, dim)), jnp.float32)
            for _ in range(2))

    def close(a, b, tol):
        return bool(np.allclose(np.asarray(a), np.asarray(b),
                                rtol=tol, atol=tol))

    def oracle(a, b, c):
        # The dense oracle wants equal heads; expanding INSIDE the
        # differentiated function keeps the reference dk/dv group-summed
        # to the same (hkv, ...) shapes the gated engine produces.
        return attention_reference(
            a, *_repeat_heads(b, c, heads // hkv), causal=True)

    def gate() -> bool:
        with jax.default_matmul_precision("highest"):
            got = flash_attention(q, k, v, causal=True)
            want = oracle(q, k, v)
            if not close(got, want, 2e-4):
                return False
            g_got = jax.grad(
                lambda a, b, c: jnp.sum(
                    flash_attention(a, b, c, causal=True) ** 2),
                argnums=(0, 1, 2))(q, k, v)
            g_want = jax.grad(
                lambda a, b, c: jnp.sum(oracle(a, b, c) ** 2),
                argnums=(0, 1, 2))(q, k, v)
        return all(close(a, b, 5e-4) for a, b in zip(g_got, g_want))

    notes: list[str] = []

    def attempt() -> bool:
        try:
            ok = gate()
        except Exception as e:
            notes.append(f"{tpu_flash_engine()} engine: "
                         f"{type(e).__name__}: {e}"[:160])
            return False
        if not ok:
            notes.append(f"{tpu_flash_engine()} engine failed parity")
        return ok

    # Retry keyed on the engine the first attempt actually dispatched to
    # (not the bare flag): off-TPU a jnp failure would otherwise trigger
    # a pointless cache drop and an identical second jnp run. The ladder
    # itself is robust.guards.with_fallback — the same engine-ranked
    # retry ring_attention's hop guard uses; attempt() keeps appending
    # its own notes, and disable_tpu_flash flips the global so the
    # post-fallback tpu_flash_engine() reports the engine that passed.
    from mpi_and_open_mp_tpu.robust.guards import (
        FallbackExhausted, with_fallback)

    _FORCED_BLOCK = forced
    _FORCED_BLOCK_BWD = forced_bwd
    try:
        engines = [(tpu_flash_engine(), attempt)]
        if tpu_flash_engine() == "pallas" and not steer_jnp:
            engines.append(
                ("jnp", lambda: (disable_tpu_flash(), attempt())[1]))
        try:
            with_fallback(engines, validator=bool)
            ok = True
        except FallbackExhausted:
            ok = False
    finally:
        _FORCED_BLOCK = 0
        _FORCED_BLOCK_BWD = 0
    # When the steer aimed the gate at the jnp engine, that IS the
    # engine the for_seq shape will use — report it, not the flag.
    return ok, ("jnp" if steer_jnp else tpu_flash_engine()), notes


def _parse_block_env(name: str) -> int:
    """Validated block-edge env knob (0 = unset). One shared parse for
    the routing predicate, the dispatch, and the parity gate, so they
    cannot disagree on the effective block — and a typo'd knob fails
    loudly with its own name, not as an opaque error from some later
    dispatch."""
    raw = os.environ.get(name, "").strip()
    if not raw:
        return 0
    try:
        b = int(raw)
    except ValueError:
        raise ValueError(f"{name}={raw!r} is not an integer") from None
    if b < 0 or (b and (b < 128 or b % 128)):
        raise ValueError(
            f"{name}={b} must be 0 or a multiple of 128 >= 128")
    return b


def _flash_block_override() -> int:
    """The ``MOMP_FLASH_BLOCK`` pin: all blocks (forward, and backward
    too unless the backward knob overrides it)."""
    return _parse_block_env("MOMP_FLASH_BLOCK")


def _flash_block_override_bwd() -> int:
    """The ``MOMP_FLASH_BLOCK_BWD`` pin: the eight dq/dkv blocks only
    (:func:`_flash_bwd_block_for`)."""
    return _parse_block_env("MOMP_FLASH_BLOCK_BWD")


# Gate-time pins of the auto block choices (module-internal; see
# gated_parity_check): let the small-sequence parity gate run the very
# block configuration a larger timed sequence will dispatch, since the
# dense oracle is O(n^2) and cannot be evaluated at the timed length.
# The backward edge is pinned separately (decoupled dispatch).
_FORCED_BLOCK = 0
_FORCED_BLOCK_BWD = 0

# b*d budget for the auto choice, anchored at the chip-validated
# (b=1024, d=128) point: 2048*128 failed to compile (VMEM), so wider
# head dims scale the block edge down rather than risk an unvalidated
# footprint on library callers with no fallback path.
_BLOCK_BUDGET = 1024 * 128


def _block_pin() -> int:
    """The pinned block edge, if any: the ``MOMP_FLASH_BLOCK`` env
    override, else the gate's module-internal force."""
    return _flash_block_override() or _FORCED_BLOCK


def _flash_block_for(n: int, d: int = 128) -> int:
    """Effective Pallas FORWARD block edge for a ``(seq=n, head_dim=d)``
    dispatch: the pin (env override / gate force) if set, else the
    largest chip-validated block (``_AUTO_BLOCKS``) dividing ``n``
    within the ``b*d <= _BLOCK_BUDGET`` footprint that keeps the grid
    at least ``_MIN_GRID`` programs per axis (short sequences starve
    the kernel below that — see the ``_MIN_GRID`` note); if no edge
    satisfies the floor, the largest fitting block regardless. 0 = no
    block fits (the shape is then jnp-engine territory)."""
    b = _block_pin()
    if b:
        return b
    fits = [b for b in _AUTO_BLOCKS
            if b * d <= _BLOCK_BUDGET and n % b == 0]
    for b in fits:
        if n >= _MIN_GRID * b:
            return b
    return fits[0] if fits else 0


def _flash_bwd_block_for(n: int, d: int = 128) -> int:
    """Effective Pallas BACKWARD block edge (the eight dq/dkv blocks).
    Decoupled from the forward's: ``MOMP_FLASH_BLOCK_BWD`` (or the
    gate's backward force) pins it independently, so a chip session can
    sweep e.g. a b1024 forward against a b512 backward — the backward
    is the grid-occupancy-sensitive side (``_MIN_GRID`` note) and its
    best edge need not match the forward's. Unpinned, it follows the
    forward choice (a single ``MOMP_FLASH_BLOCK`` still pins all eight
    blocks, exactly the pre-decoupling behaviour); the auto edges
    coincide until a chip sweep separates them."""
    b = _flash_block_override_bwd() or _FORCED_BLOCK_BWD
    if b:
        return b
    return _flash_block_for(n, d)


def _pallas_flash_eligible(q, k, v) -> bool:
    """Static (trace-time) routing predicate for the bundled Pallas TPU
    kernel taking the operands DIRECTLY: TPU backend (or interpret mode
    on any backend), equal head counts (GQA shapes go through
    :func:`_flash_dispatch_plan`'s expand form instead), validated
    forward AND backward block edges that divide the sequence within
    the ``b*d`` footprint budget (:func:`_flash_block_for` /
    :func:`_flash_bwd_block_for`; a pinned block tightens divisibility
    to its own multiple), MXU-width head dim, and a dtype the MXU takes
    directly. Interpret mode additionally requires block == seq (jax
    0.4.37's interpret discharge rule breaks on the scratch branch)."""
    if not _TPU_FLASH:
        return False
    if not _PALLAS_INTERPRET:
        try:
            if jax.default_backend() != "tpu":
                return False
        except RuntimeError:  # no backend at all (early init)
            return False
    h, n, d = q.shape
    blk = _flash_block_for(n, d)
    bwd = _flash_bwd_block_for(n, d)
    if _PALLAS_INTERPRET and not (blk == n and bwd == n):
        return False
    return (k.shape[0] == h and d % 128 == 0
            and blk != 0 and n % blk == 0 and bwd != 0 and n % bwd == 0
            and q.dtype in (jnp.float32, jnp.bfloat16)
            and k.dtype == q.dtype and v.dtype == q.dtype)


# Combined-K+V byte ceiling for the GQA expand dispatch (HBM is ~16 GB
# on the measured chip; 2 GiB keeps the expansion a rounding error next
# to the score-block working set while admitting every realistic
# (heads, seq) this framework records).
_GQA_EXPAND_BYTES = 2 << 30


def _flash_dispatch_plan(q, k, v):
    """How (if at all) these operands reach the Pallas kernel:
    ``("direct", blk, blk_bwd, 1)``, ``("expand", blk, blk_bwd,
    groups)``, or ``None`` (the jnp engine). ``blk`` is the forward
    block edge, ``blk_bwd`` the (independently pinnable) edge of the
    eight dq/dkv blocks. GQA/MQA shapes whose broadcast K/V fit
    ``_GQA_EXPAND_BYTES`` are dispatched by expanding — chip-measured
    (32k, 8q/2kv, causal bf16, two runs): expand+kernel 130.7-134.1
    fwd / 100.0-106.4 grad TFLOP/s vs 48.4 / 47.5 for the folded jnp
    path, i.e. the repeat's HBM cost is a ~2.7x win. The gradient through ``jnp.repeat`` sums
    per-group dk/dv exactly as the folded path does."""
    h, n, d = q.shape
    if _pallas_flash_eligible(q, k, v):
        return ("direct", _flash_block_for(n, d), _flash_bwd_block_for(n, d), 1)
    hkv = k.shape[0]
    if hkv and h % hkv == 0 and h > hkv:
        ek = jax.ShapeDtypeStruct((h, n, d), k.dtype)
        ev = jax.ShapeDtypeStruct((h, n, d), v.dtype)
        if (2 * h * n * d * q.dtype.itemsize <= _GQA_EXPAND_BYTES
                and _pallas_flash_eligible(q, ek, ev)):
            return ("expand", _flash_block_for(n, d),
                    _flash_bwd_block_for(n, d), h // hkv)
    return None


def _plan_stamp(plan) -> str:
    """Provenance string for a dispatch plan: ``pallas:b<blk>`` plus
    ``:bw<blk_bwd>`` when the backward edge differs from the forward's
    and ``:kvx<groups>`` for the GQA expand form — the exact
    configuration recorders must gate and stamp."""
    kind, blk, bwd, groups = plan
    stamp = f"pallas:b{blk}"
    if bwd != blk:
        stamp += f":bw{bwd}"
    if kind == "expand":
        stamp += f":kvx{groups}"
    return stamp


# The multi-device ring's per-hop engine: run the Pallas flash kernel on
# each arriving K/V block instead of the jnp `_block_update` fold
# (chip-measured 132-147 vs 47-49 TFLOP/s — see the `_TPU_FLASH` note),
# and merge hops with the exact online-softmax combine. MOMP_RING_HOP=0
# pins the ring to the jnp fold (which remains the CPU/interpret oracle
# and the fallback for hop shapes the kernel doesn't take).
_RING_HOP = os.environ.get("MOMP_RING_HOP", "1") != "0"

# The ring BACKWARD's per-hop engine (the repo-owned hop kernels in
# ops/flash_hop_bwd — see that module for why the bundled kernel's
# backward can't serve here). MOMP_RING_HOP_BWD=0 pins the backward
# hops to the jnp _flash_block_grads fold while the forward hops keep
# the kernel; MOMP_RING_HOP=0 pins both directions.
_RING_HOP_BWD = os.environ.get("MOMP_RING_HOP_BWD", "1") != "0"

# Causal-zigzag forward hop dispatch: decompose each hop's live
# quarter-blocks into kernel calls per half-chunk (hop 0 = causal
# triangles, later hops = unmasked rectangles) merged through
# _merge_partials. MOMP_RING_ZZ=0 pins causal zigzag to the jnp fold
# (the pre-decomposition behaviour).
_RING_ZZ = os.environ.get("MOMP_RING_ZZ", "1") != "0"

# Hop prefetch: issue hop i+1's K/V rotation before hop i's flash
# kernel launches. The hopflash loops always had ONE rotation in
# flight (issued at the top of each hop, consumed at the top of the
# next); the prefetched schedule carries TWO K/V slots — the block
# being folded and the block in flight — so every rotation gets two
# kernel launches of hiding slack instead of one. Same p-1 rotations,
# same folds in the same order (parity is bit-exact); only the issue
# points move earlier. Needs p >= 3 (with fewer devices there is no
# second transfer to deepen the pipeline with) and applies to the
# hopflash forward, its causal-zigzag decomposition, and the
# travelling-dk/dv backward's K/V trip (the dk/dv accumulator
# rotations cannot prefetch — each carries the hop's own
# contribution). MOMP_RING_PREFETCH=0 is the kill switch back to the
# single-slot schedule; the guarded recovery path pins it off with
# the hop kernels (the recovered trace is the plain jnp fold).
_RING_PREFETCH = os.environ.get("MOMP_RING_PREFETCH", "1") != "0"


def _ring_prefetch_on(p: int) -> bool:
    """Whether the hopflash loops run the double-slot prefetched
    schedule for a ``p``-device ring (gate + eligibility: a 2-device
    ring has a single transfer — nothing to pipeline deeper)."""
    return _RING_PREFETCH and p > 2


@contextlib.contextmanager
def _ring_hop_pinned(value: bool):
    """Pin the ring-hop engine gates for one dispatch: the guarded
    recovery path in :func:`ring_attention` re-dispatches a poisoned
    fold on the jnp fold oracle by tracing with the hop kernels pinned
    off — BOTH directions, and the hop prefetch with them, so the
    recovered trace is the full single-slot jnp fold (paired with a
    distinct jit-cache key — the flags are read at trace time, not
    part of the cache key)."""
    global _RING_HOP, _RING_HOP_BWD, _RING_PREFETCH
    prev = (_RING_HOP, _RING_HOP_BWD, _RING_PREFETCH)
    _RING_HOP = value
    _RING_HOP_BWD = value
    _RING_PREFETCH = value
    try:
        yield
    finally:
        _RING_HOP, _RING_HOP_BWD, _RING_PREFETCH = prev


def _ring_hop_plan(q, k, v, causal: bool, layout: str):
    """Dispatch plan for the per-hop Pallas ring FORWARD engine, or
    ``None`` (the jnp fold). Operands are the PER-SHARD
    ``(h, n_local, d)`` blocks, so eligibility — block edges, GQA
    expand budget — is judged at hop granularity. The contiguous ring
    needs only the kernel's static causal flag (hop 0 is the diagonal
    triangle, every other unskipped hop is fully unmasked); causal
    zigzag runs HALF-chunk kernel calls (``_ring_forward_hopflash_zz``:
    hop-0 triangles via the same flag, off-diagonal live pairs fully
    unmasked), so its eligibility is judged on the ``(h, n_local/2,
    d)`` half shape — ``MOMP_RING_ZZ=0`` pins it to the jnp fold."""
    if not _RING_HOP:
        return None
    if causal and layout == "zigzag":
        if not _RING_ZZ:
            return None
        h, nl, d = q.shape
        if nl % 2:
            return None
        half = nl // 2
        return _flash_dispatch_plan(
            jax.ShapeDtypeStruct((h, half, d), q.dtype),
            jax.ShapeDtypeStruct((k.shape[0], half, d), k.dtype),
            jax.ShapeDtypeStruct((v.shape[0], half, d), v.dtype))
    return _flash_dispatch_plan(q, k, v)


def _ring_hop_bwd_plan(q, k, v, causal: bool, layout: str):
    """Dispatch plan ``(kind, blk, groups)`` for the per-hop Pallas ring
    BACKWARD engine (``ops.flash_hop_bwd``), or ``None`` (the jnp
    ``_flash_block_grads`` fold). Gated by the forward's eligibility
    machinery — same per-shard block-edge and GQA-expand-budget
    judgement — with the backward edge capped at the hop kernels' VMEM
    budget (``flash_hop_bwd.MAX_BLOCK``: the cap keeps dividing the
    sequence since edges are 128-multiples of powers of two). Causal
    zigzag stays on the jnp fold: its half-chunk gradient decomposition
    isn't implemented (the travelling accumulators would need per-half
    routing), so it is an ineligible shape by definition here."""
    if not (_RING_HOP and _RING_HOP_BWD):
        return None
    if causal and layout == "zigzag":
        return None
    plan = _flash_dispatch_plan(q, k, v)
    if plan is None:
        return None
    from mpi_and_open_mp_tpu.ops import flash_hop_bwd

    kind, _, bwd, groups = plan
    return (kind, min(bwd, flash_hop_bwd.MAX_BLOCK), groups)


def _merge_partials(o1, L1, o2, L2):
    """Online-softmax combine of two NORMALISED attention partials over
    disjoint key sets: ``L = logaddexp(L1, L2)``, ``o = o1·exp(L1-L) +
    o2·exp(L2-L)``. Exact (it is the algebraic merge of the two
    softmaxes' numerators and denominators) and associative, so hops
    may fold in any order. ``o`` rows ``(h, n, d)``, ``L`` ``(h, n)``,
    all float32."""
    L = jnp.logaddexp(L1, L2)
    w1 = jnp.exp(L1 - L)[..., None]
    w2 = jnp.exp(L2 - L)[..., None]
    return o1 * w1 + o2 * w2, L


def _hop_flash_block(q, kb, vb, causal: bool, blk: int, groups: int):
    """One hop's attention through the bundled Pallas kernel: the
    NORMALISED partial output and its per-row logsumexp ``L = m +
    log(l)`` of the scaled scores — the partial :func:`_merge_partials`
    combines, both float32. Calls the kernel's forward impl directly
    with ``save_residuals=True`` (the public ``fa._flash_attention``
    custom_vjp refuses residuals in its fwd): safe here because the
    ring's own ``custom_vjp`` wraps the whole trip, so the kernel's vjp
    is never entered — the travelling-dk/dv ``_ring_flash_bwd`` keeps
    the backward contract. GQA hops broadcast K/V locally per hop
    (plan-budgeted); the ppermutes still carry the un-expanded
    ``(hkv, ...)`` blocks."""
    from jax.experimental.pallas.ops.tpu import flash_attention as fa

    if groups > 1:
        kb, vb = _repeat_heads(kb, vb, groups)
    d = q.shape[-1]
    with _pallas_interpret_calls(fa):
        o, l, m = fa._flash_attention_impl(
            q[None], kb[None], vb[None], None, None, True, causal,
            1.0 / math.sqrt(d), block_b=1, block_q=blk,
            block_k_major=blk, block_k=blk, debug=False)
    L = m[0] + jnp.log(l[0])
    return o[0].astype(jnp.float32), L.astype(jnp.float32)


def _ring_forward_hopflash(axis: str, causal: bool, p: int, q, k, v, plan):
    """The rotate-and-fold forward with the Pallas kernel as the per-hop
    engine (contiguous layout; :func:`_ring_hop_plan` gated). Same ring
    schedule as the jnp fold — double-buffered ppermutes outside the
    causal ``cond`` — but each hop runs the flash kernel to a
    normalised ``(o, L)`` partial and hops merge via
    :func:`_merge_partials` instead of carrying raw ``(o, m, l)``
    state. Hop 0 is the resident diagonal block — the one hop whose
    causal mask is the standard triangle in local coordinates, i.e. the
    kernel's static ``causal`` flag; every later unskipped hop
    (``src < idx``) is fully unmasked. Returns ``(o, L)`` with ``L`` in
    the folded GQA layout ``_ring_flash_bwd`` consumes.

    With :func:`_ring_prefetch_on` the loop runs the double-slot
    prefetched schedule (see the ``_RING_PREFETCH`` note): hop 1 AND
    hop 2 rotations leave before the diagonal kernel, and each loop
    iteration issues hop ``j+2``'s rotation from the arriving buffer
    before folding hop ``j`` — two folds of hiding slack per transfer,
    identical fold order and rotation count."""
    idx = lax.axis_index(axis) if causal else 0
    hkv = k.shape[0]
    g = q.shape[0] // hkv
    _, blk, _, groups = plan
    perm = ring_perm(p, 1)

    # Chaos hook, mirroring the jnp fold's (see _ring_forward): hop 0 is
    # the resident diagonal block outside the fold, so it takes the
    # poison directly; later hops go through the wrapped fold.
    from mpi_and_open_mp_tpu.robust import chaos as _chaos

    _poison = _chaos.hop_poison_spec()
    k0, v0 = (_chaos.poison_hop(k, v, 0, _poison)
              if _poison is not None else (k, v))

    # Issue the first rotation before the diagonal block's kernel call
    # (the jnp fold's double-buffering, same latency-hiding pairing).
    k1 = lax.ppermute(k, axis, perm)
    v1 = lax.ppermute(v, axis, perm)
    prefetch = _ring_prefetch_on(p)
    if prefetch:
        # Hop 2's rotation leaves before the diagonal kernel too — from
        # here on two K/V transfers are in flight at every kernel launch.
        k2 = lax.ppermute(k1, axis, perm)
        v2 = lax.ppermute(v1, axis, perm)
    state = _hop_flash_block(q, k0, v0, causal, blk, groups)

    def fold(j, state, kb, vb):
        # After j forward rotations this block originated on ring
        # position (idx - j) mod p — never the diagonal for j >= 1, so
        # it is either fully unmasked (src < idx, or any hop when
        # non-causal) or entirely in the future and skipped. The
        # ppermutes stay outside the cond (collectives inside a
        # per-device branch would deadlock the ring).
        def take(s):
            o2, L2 = _hop_flash_block(q, kb, vb, False, blk, groups)
            return _merge_partials(s[0], s[1], o2, L2)

        if not causal:
            return take(state)
        src = (idx - j) % p
        return lax.cond(src < idx, take, lambda s: s, state)

    if _poison is not None:
        fold = _chaos.poisoned_fold(fold, _poison)

    if prefetch:

        def hop(j, carry):
            state, kb, vb, kb_in, vb_in = carry
            kb_next = lax.ppermute(kb_in, axis, perm)
            vb_next = lax.ppermute(vb_in, axis, perm)
            state = fold(j, state, kb, vb)
            return state, kb_in, vb_in, kb_next, vb_next

        # Loop issues hops 3..p-1 (two ahead of consumption); the last
        # two arrived blocks fold outside it — same p-1 rotations total.
        state, kb, vb, kb_in, vb_in = lax.fori_loop(
            1, p - 2, hop, (state, k1, v1, k2, v2))
        state = fold(p - 2, state, kb, vb)
        o, L = fold(p - 1, state, kb_in, vb_in)
    else:

        def hop(j, carry):
            state, kb, vb = carry
            kb_next = lax.ppermute(kb, axis, perm)
            vb_next = lax.ppermute(vb, axis, perm)
            state = fold(j, state, kb, vb)
            return state, kb_next, vb_next

        state, kb, vb = lax.fori_loop(1, p - 1, hop, (state, k1, v1))
        o, L = fold(p - 1, state, kb, vb)
    # The kernel emits per-q-head rows; the ring backward consumes the
    # folded GQA layout (row r <-> position r // g, group r % g).
    return o.astype(q.dtype), _fold_groups(L, hkv, g)


def _ring_forward_hopflash_zz(axis: str, p: int, q, k, v, plan):
    """Causal-zigzag rotate-and-fold with the Pallas kernel as the
    per-hop engine. Shard ``idx`` holds half-chunks ``(idx, 2p-1-idx)``;
    the jnp fold's live-pair table (see ``_ring_forward``) decomposes
    into at most two RECTANGULAR kernel calls per half-chunk per hop,
    merged through the exact :func:`_merge_partials` combine:

      hop 0 (resident): (lo,lo) and (hi,hi) are the two diagonal
        TRIANGLES in local coordinates — the kernel's static causal
        flag; (hi,lo) is a fully unmasked half-square.
      hop j >= 1 (src != idx): every live pair is fully unmasked —
        (lo,lo) iff src < idx, (hi,lo) always, (hi,hi) iff src > idx —
        so the kernel runs maskless and the per-device ``cond``s skip
        dead pairs entirely (collectives stay outside, as always).

    Same balanced cost as the jnp zigzag fold (~half a full block per
    hop on EVERY device), kernel-rate arithmetic. Returns ``(o, L)``
    with the lo‖hi half order and folded GQA ``L`` — exactly the
    residual layout ``_ring_flash_bwd``'s zigzag branch consumes."""
    idx = lax.axis_index(axis)
    hkv = k.shape[0]
    g = q.shape[0] // hkv
    nl = q.shape[1]
    half = nl // 2
    _, blk, _, groups = plan
    perm = ring_perm(p, 1)
    q_lo, q_hi = q[:, :half], q[:, half:]

    # Chaos hook, mirroring _ring_forward_hopflash: the resident hop 0
    # takes the poison directly; later hops go through the wrapped fold.
    from mpi_and_open_mp_tpu.robust import chaos as _chaos

    _poison = _chaos.hop_poison_spec()
    k0, v0 = (_chaos.poison_hop(k, v, 0, _poison)
              if _poison is not None else (k, v))

    k1 = lax.ppermute(k, axis, perm)
    v1 = lax.ppermute(v, axis, perm)
    prefetch = _ring_prefetch_on(p)
    if prefetch:
        # Double-slot prefetch, exactly as the contiguous forward: hop
        # 2's rotation also leaves before the resident half-chunk
        # kernels run.
        k2 = lax.ppermute(k1, axis, perm)
        v2 = lax.ppermute(v1, axis, perm)

    k_lo, k_hi = k0[:, :half], k0[:, half:]
    v_lo, v_hi = v0[:, :half], v0[:, half:]
    s_lo = _hop_flash_block(q_lo, k_lo, v_lo, True, blk, groups)
    s_hi = _hop_flash_block(q_hi, k_lo, v_lo, False, blk, groups)
    s_hi = _merge_partials(
        *s_hi, *_hop_flash_block(q_hi, k_hi, v_hi, True, blk, groups))

    def fold(j, state, kb, vb):
        s_lo, s_hi = state
        src = (idx - j) % p
        k_lo, k_hi = kb[:, :half], kb[:, half:]
        v_lo, v_hi = vb[:, :half], vb[:, half:]
        s_lo = lax.cond(
            src < idx,
            lambda s: _merge_partials(
                *s, *_hop_flash_block(q_lo, k_lo, v_lo, False, blk,
                                      groups)),
            lambda s: s, s_lo)
        s_hi = _merge_partials(
            *s_hi, *_hop_flash_block(q_hi, k_lo, v_lo, False, blk, groups))
        s_hi = lax.cond(
            src > idx,
            lambda s: _merge_partials(
                *s, *_hop_flash_block(q_hi, k_hi, v_hi, False, blk,
                                      groups)),
            lambda s: s, s_hi)
        return s_lo, s_hi

    if _poison is not None:
        fold = _chaos.poisoned_fold(fold, _poison)

    if prefetch:

        def hop(j, carry):
            state, kb, vb, kb_in, vb_in = carry
            kb_next = lax.ppermute(kb_in, axis, perm)
            vb_next = lax.ppermute(vb_in, axis, perm)
            state = fold(j, state, kb, vb)
            return state, kb_in, vb_in, kb_next, vb_next

        state, kb, vb, kb_in, vb_in = lax.fori_loop(
            1, p - 2, hop, ((s_lo, s_hi), k1, v1, k2, v2))
        state = fold(p - 2, state, kb, vb)
        s_lo, s_hi = fold(p - 1, state, kb_in, vb_in)
    else:

        def hop(j, carry):
            state, kb, vb = carry
            kb_next = lax.ppermute(kb, axis, perm)
            vb_next = lax.ppermute(vb, axis, perm)
            state = fold(j, state, kb, vb)
            return state, kb_next, vb_next

        state, kb, vb = lax.fori_loop(
            1, p - 1, hop, ((s_lo, s_hi), k1, v1))
        s_lo, s_hi = fold(p - 1, state, kb, vb)
    o = jnp.concatenate([s_lo[0], s_hi[0]], axis=1).astype(q.dtype)
    L = jnp.concatenate([s_lo[1], s_hi[1]], axis=1)
    return o, _fold_groups(L, hkv, g)


def _ring_backward_hopflash(axis: str, causal: bool, p: int, res, do,
                            plan):
    """The travelling-dk/dv ring backward with the repo-owned Pallas hop
    kernels (``ops.flash_hop_bwd``) as the per-hop gradient engine
    (contiguous layout; :func:`_ring_hop_bwd_plan` gated). Identical
    ring schedule and accumulator contract to the jnp path in
    ``_ring_flash_bwd`` — K/V make the second ring trip, each block
    carrying its (dk, dv) accumulator home over ``p`` rotations — but
    every unskipped hop's (dq, dk, dv) block comes from the two kernel
    launches instead of the ``_flash_block_grads`` fold. Hop 0 is
    peeled out of the ``fori_loop``: it is the one hop whose causal
    mask is the local diagonal triangle (the kernels' static ``causal``
    flag); every later unskipped hop (``src < idx``) runs maskless.

    The per-row statistics are hop-invariant, so ``L`` (unfolded from
    the residual's folded GQA layout to per-q-head rows) and ``D =
    rowsum(do·o)`` are lane-broadcast ONCE outside the loop. GQA K/V
    expand per hop inside the taken branch (plan-budgeted, like the
    forward hop engine); dk/dv come back per-q-head and are group-summed
    into the (hkv, ...) travelling accumulators."""
    from mpi_and_open_mp_tpu.ops import flash_hop_bwd

    q, k, v, o, L = res
    idx = lax.axis_index(axis) if causal else 0
    nl, d = q.shape[1:]
    hkv = k.shape[0]
    g = q.shape[0] // hkv
    f32 = jnp.float32
    perm = ring_perm(p, 1)
    _, blk, groups = plan

    D = jnp.sum(do.astype(f32) * o.astype(f32), axis=-1)  # (h, nl)
    L128 = flash_hop_bwd.lane_broadcast(_unfold_groups(L, hkv, g))
    D128 = flash_hop_bwd.lane_broadcast(D)

    def kernel_contrib(kb, vb, diag: bool):
        kbx, vbx = _repeat_heads(kb, vb, groups)
        dqh, dkh, dvh = flash_hop_bwd.hop_block_grads(
            q, do, L128, D128, kbx, vbx, causal=diag and causal,
            blk=blk, interpret=_PALLAS_INTERPRET)
        if g > 1:
            dkh = dkh.reshape(hkv, g, nl, d).sum(axis=1)
            dvh = dvh.reshape(hkv, g, nl, d).sum(axis=1)
        # The hop loop carries dq in the folded GQA layout (it is
        # unfolded once at the end, like the jnp path's).
        return _fold_groups(dqh, hkv, g), dkh, dvh

    def zero3(_):
        return (jnp.zeros((hkv, nl * g, d), f32),
                jnp.zeros((hkv, nl, d), f32),
                jnp.zeros((hkv, nl, d), f32))

    # Hop 0: resident diagonal block, double-buffered like the forward
    # (first rotation issued before the kernel launches; under prefetch
    # the second K/V rotation leaves before them too — the dk/dv
    # accumulator rotations CANNOT prefetch, each carries the hop's own
    # contribution, so only the K/V trip deepens).
    k1 = lax.ppermute(k, axis, perm)
    v1 = lax.ppermute(v, axis, perm)
    prefetch = _ring_prefetch_on(p)
    if prefetch:
        k2 = lax.ppermute(k1, axis, perm)
        v2 = lax.ppermute(v1, axis, perm)
    dq0, dk0, dv0 = kernel_contrib(k, v, True)
    dkb = lax.ppermute(dk0, axis, perm)
    dvb = lax.ppermute(dv0, axis, perm)

    def contribute(j, kb, vb):
        # j >= 1 only: never the diagonal, so either fully unmasked or
        # entirely in the future and skipped (contiguous causal). The
        # ppermutes stay outside the cond (collectives in a per-device
        # branch would deadlock the ring).
        if not causal:
            return kernel_contrib(kb, vb, False)
        src = (idx - j) % p
        return lax.cond(
            src < idx, lambda _: kernel_contrib(kb, vb, False), zero3,
            None)

    if prefetch:

        def hop(j, carry):
            dq, kb, vb, kb_in, vb_in, dkb, dvb = carry
            kb_next = lax.ppermute(kb_in, axis, perm)
            vb_next = lax.ppermute(vb_in, axis, perm)
            dqj, dkj, dvj = contribute(j, kb, vb)
            dkb = lax.ppermute(dkb + dkj, axis, perm)
            dvb = lax.ppermute(dvb + dvj, axis, perm)
            return dq + dqj, kb_in, vb_in, kb_next, vb_next, dkb, dvb

        # Loop issues K/V hops 3..p-1 two ahead of consumption; the
        # last two arrived blocks contribute outside it. Accumulator
        # rotations: hop-0 peel + p-3 loop + the two tail ones = p,
        # same count as the single-slot schedule.
        dq, kb, vb, kb_in, vb_in, dkb, dvb = lax.fori_loop(
            1, p - 2, hop, (dq0, k1, v1, k2, v2, dkb, dvb))
        dqj, dkj, dvj = contribute(p - 2, kb, vb)
        dq = dq + dqj
        dkb = lax.ppermute(dkb + dkj, axis, perm)
        dvb = lax.ppermute(dvb + dvj, axis, perm)
        dqj, dkj, dvj = contribute(p - 1, kb_in, vb_in)
        dq = dq + dqj
        dk = lax.ppermute(dkb + dkj, axis, perm)
        dv = lax.ppermute(dvb + dvj, axis, perm)
    else:

        def hop(j, carry):
            dq, kb, vb, dkb, dvb = carry
            kb_next = lax.ppermute(kb, axis, perm)
            vb_next = lax.ppermute(vb, axis, perm)
            dqj, dkj, dvj = contribute(j, kb, vb)
            dkb = lax.ppermute(dkb + dkj, axis, perm)
            dvb = lax.ppermute(dvb + dvj, axis, perm)
            return dq + dqj, kb_next, vb_next, dkb, dvb

        dq, kb, vb, dkb, dvb = lax.fori_loop(
            1, p - 1, hop, (dq0, k1, v1, dkb, dvb))
        # Last block, then the p-th accumulator rotation lands every
        # (dk, dv) back on its home shard (hop-0 peel + p-2 loop
        # rotations + this one = p, same count as the jnp path).
        dqj, dkj, dvj = contribute(p - 1, kb, vb)
        dq = dq + dqj
        dk = lax.ppermute(dkb + dkj, axis, perm)
        dv = lax.ppermute(dvb + dvj, axis, perm)
    dq = _unfold_groups(dq, hkv, g).astype(q.dtype)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


# ---------------------------------------------------------------------------
# Traced hop-by-hop ring dispatch (obs.trace): per-hop telemetry.
#
# Per-hop ring spans are impossible from inside the compiled ring: the
# p-1 hops live in one `fori_loop` inside one `shard_map` program — the
# host sees a single dispatch, so there is nothing to bracket. When a
# trace sink is armed (`MOMP_TRACE`, and no chaos plan / guards in the
# way), `ring_attention` therefore re-plans the CONTIGUOUS forward as
# p-1 host-level hop dispatches: each hop issues (1) one jitted
# shard_map ppermute rotation of the K/V blocks — the `ring.hop.transfer`
# span, anchored so the wire time is attributed — then (2) one jitted
# fold of the arrived block into the running normalised (o, L) partial
# via `_merge_partials` — the `ring.hop.fold` span, tagged with the same
# engine stamp `ring_hop_engine_for` reports (the fold runs the real
# per-hop engine: `_hop_flash_block` whenever `_ring_hop_plan` grants a
# plan, else a `_block_update`-based jnp partial). Exactly 2*(p-1)
# `ring.hop.*` spans per attention step; the hop-0 resident diagonal is
# a separate `ring.fold.resident` span (it moves no bytes). The result
# is parity-exact with the fused ring — `_merge_partials` is the exact
# associative combine — but each hop pays a host round trip, so this
# path exists for telemetry, never inside timing brackets. Causal zigzag
# keeps the fused engine (its half-chunk hops don't decompose into
# whole-block host folds) and gets a whole-call span instead.


def _traced_hop_partial(qs, kb, vb, causal_blk: bool, plan):
    """One hop's NORMALISED (o, L) partial on the planned engine — the
    same quantity `_hop_flash_block` emits, computed per shard."""
    if plan is not None:
        _, blk, _, groups = plan
        return _hop_flash_block(qs, kb, vb, causal_blk, blk, groups)
    hq, nl, _ = qs.shape
    if kb.shape[0] != hq:
        kb, vb = _repeat_heads(kb, vb, hq // kb.shape[0])
    rows = jnp.arange(nl)
    o0 = jnp.zeros(qs.shape, jnp.float32)
    m0 = jnp.full((hq, nl), _NEG, jnp.float32)
    l0 = jnp.zeros((hq, nl), jnp.float32)
    o, m, l = _block_update(qs.astype(jnp.float32), kb, vb,
                            rows, rows, None, causal_blk, o0, m0, l0)
    l = jnp.maximum(l, 1e-37)
    return o / l[..., None], m + jnp.log(l)


def _traced_L_spec(axis: str) -> P:
    return P(None, axis)


@functools.partial(jax.jit, static_argnames=("mesh", "axis"))
def _traced_rotate_jit(kb, vb, *, mesh: Mesh, axis: str):
    """One K/V ring rotation — the traced ring's transfer step."""

    def body(kb, vb):
        p = lax.axis_size(axis)
        perm = ring_perm(p, 1)
        return lax.ppermute(kb, axis, perm), lax.ppermute(vb, axis, perm)

    spec = _seq_spec(axis)
    return jax.shard_map(
        body, mesh=mesh, in_specs=(spec, spec), out_specs=(spec, spec),
        check_vma=False)(kb, vb)


@functools.partial(jax.jit,
                   static_argnames=("mesh", "axis", "causal", "plan"))
def _traced_fold0_jit(q, kb, vb, *, mesh: Mesh, axis: str, causal: bool,
                      plan):
    """Hop 0: the resident diagonal block's partial (the one hop whose
    causal mask is the standard triangle in local coordinates)."""

    def body(qs, kb, vb):
        return _traced_hop_partial(qs, kb, vb, causal, plan)

    spec = _seq_spec(axis)
    return jax.shard_map(
        body, mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=(spec, _traced_L_spec(axis)), check_vma=False)(q, kb, vb)


@functools.partial(jax.jit,
                   static_argnames=("mesh", "axis", "causal", "plan"))
def _traced_fold_jit(o, L, q, kb, vb, j, *, mesh: Mesh, axis: str,
                     causal: bool, plan):
    """Fold the block that arrived after ``j >= 1`` rotations into the
    running (o, L). ``j`` rides as data (one compile serves every hop).
    After j rotations the block originated on ring position
    ``(idx - j) % p`` — never the diagonal, so it is either fully
    unmasked or (causal, src > idx) entirely in the future and skipped.
    No collectives in here, so the skip `cond` is safe per device."""

    def body(o, L, qs, kb, vb, j):
        def take(state):
            o2, L2 = _traced_hop_partial(qs, kb, vb, False, plan)
            return _merge_partials(state[0], state[1], o2, L2)

        if not causal:
            return take((o, L))
        p = lax.axis_size(axis)
        idx = lax.axis_index(axis)
        src = (idx - j) % p
        return lax.cond(src < idx, take, lambda s: s, (o, L))

    spec = _seq_spec(axis)
    lsp = _traced_L_spec(axis)
    return jax.shard_map(
        body, mesh=mesh, in_specs=(spec, lsp, spec, spec, spec, P()),
        out_specs=(spec, lsp), check_vma=False)(o, L, q, kb, vb, j)


def _ring_attention_traced(q, k, v, *, mesh: Mesh, axis: str, causal: bool):
    """Hop-by-hop instrumented contiguous ring forward (module comment
    above). Operands arrive already device_put with the ring sharding."""
    from mpi_and_open_mp_tpu.obs import metrics, trace

    p = mesh.shape[axis]
    h, n, d = q.shape
    nl = n // p
    plan = _ring_hop_plan(
        jax.ShapeDtypeStruct((h, nl, d), q.dtype),
        jax.ShapeDtypeStruct((k.shape[0], nl, d), k.dtype),
        jax.ShapeDtypeStruct((v.shape[0], nl, d), v.dtype),
        causal, "contiguous")
    engine = "jnp" if plan is None else _plan_stamp(plan)
    hop_bytes = (k.nbytes + v.nbytes) // p  # per-device K/V block pair
    with trace.span("ring_attention", devices=p, seq=n, heads=h,
                    causal=causal, engine=engine,
                    traced_dispatch=True) as sp:
        with trace.span("ring.fold.resident", engine=engine) as rsp:
            o, L = _traced_fold0_jit(q, k, v, mesh=mesh, axis=axis,
                                     causal=causal, plan=plan)
            rsp.anchor((o, L))
        kb, vb = k, v
        for j in range(1, p):
            with trace.span("ring.hop.transfer", hop=j,
                            bytes=hop_bytes) as tsp:
                kb, vb = _traced_rotate_jit(kb, vb, mesh=mesh, axis=axis)
                tsp.anchor((kb, vb))
            with trace.span("ring.hop.fold", hop=j, engine=engine) as fsp:
                o, L = _traced_fold_jit(o, L, q, kb, vb, jnp.int32(j),
                                        mesh=mesh, axis=axis,
                                        causal=causal, plan=plan)
                fsp.anchor((o, L))
        metrics.inc("ring.hops.fwd", p - 1, engine=engine)
        metrics.inc("ring.steps.traced")
        sp.anchor(o)
    return o.astype(q.dtype)


def ring_hop_engine_for(q, k, v, *, p: int | None = None,
                        causal: bool = True,
                        layout: str = "contiguous") -> str:
    """Shape-aware provenance for the MULTI-DEVICE ring fold: the engine
    each K/V hop of a ``ring_attention`` over these GLOBAL operands
    will run — a ``pallas:b…`` stamp (per-hop kernel; ``:zz`` marks the
    causal-zigzag half-chunk decomposition, whose block edge is sized
    for the half shape) or ``"jnp"`` (the fold oracle). ``p`` defaults
    to the local device count (what ``ring_attention``'s default mesh
    uses). A 1-device ring never enters the ring body; its local engine
    is reported as ``"local:<flash_engine_for stamp>"``. Recorders
    publishing ring timings must stamp artifacts with this, exactly as
    single-device recorders stamp :func:`flash_engine_for`. 4D
    ``(B, heads, seq, d)`` operands stamp the folded-batch engine with
    a ``:b{B}`` suffix (see :func:`_fold_batch`). A trailing ``:pf``
    marks the double-slot hop-prefetch schedule (``_RING_PREFETCH``
    on, ring size > 2): hop ``i+1``'s K/V rotation is issued before
    hop ``i``'s kernel launches."""
    if len(q.shape) == 4:
        probe_q, probe_k, probe_v = _fold_batch_probes(q, k, v)
        return ring_hop_engine_for(
            probe_q, probe_k, probe_v, p=p, causal=causal, layout=layout
        ) + f":b{q.shape[0]}"
    if p is None:
        p = len(jax.devices())
    h, n, d = q.shape
    if p == 1:
        return "local:" + flash_engine_for(q, k, v)
    nl = n // p
    sq = jax.ShapeDtypeStruct((h, nl, d), q.dtype)
    sk = jax.ShapeDtypeStruct((k.shape[0], nl, d), k.dtype)
    sv = jax.ShapeDtypeStruct((v.shape[0], nl, d), v.dtype)
    plan = _ring_hop_plan(sq, sk, sv, causal, layout)
    if plan is None:
        return "jnp"
    stamp = _plan_stamp(plan)
    if causal and layout == "zigzag":
        stamp += ":zz"
    if _ring_prefetch_on(p):
        stamp += ":pf"
    return stamp


def ring_hop_bwd_engine_for(q, k, v, *, p: int | None = None,
                            causal: bool = True,
                            layout: str = "contiguous") -> str:
    """Shape-aware provenance for the ring BACKWARD's per-hop engine:
    ``pallas:b…`` when each hop's (dq, dk, dv) block runs the
    ``ops.flash_hop_bwd`` kernels (``:kvx…`` for the per-hop GQA
    expand), ``"jnp"`` for the ``_flash_block_grads`` fold (causal
    zigzag, ineligible hop shapes, or ``MOMP_RING_HOP_BWD=0`` /
    ``MOMP_RING_HOP=0``). The stamped block edge is the hop kernels'
    effective one — the single-device backward edge capped at
    ``flash_hop_bwd.MAX_BLOCK``. A 1-device ring reports its local
    engine (whose stamp already carries the kernel backward edge when
    it differs). Recorders publishing ring GRADIENT timings must stamp
    artifacts with this, alongside :func:`ring_hop_engine_for`. 4D
    operands fold and stamp ``:b{B}`` exactly as the forward twin; a
    trailing ``:pf`` marks the prefetched K/V trip exactly as the
    forward's (the dk/dv accumulator rotations never prefetch)."""
    if len(q.shape) == 4:
        probe_q, probe_k, probe_v = _fold_batch_probes(q, k, v)
        return ring_hop_bwd_engine_for(
            probe_q, probe_k, probe_v, p=p, causal=causal, layout=layout
        ) + f":b{q.shape[0]}"
    if p is None:
        p = len(jax.devices())
    h, n, d = q.shape
    if p == 1:
        return "local:" + flash_engine_for(q, k, v)
    nl = n // p
    sq = jax.ShapeDtypeStruct((h, nl, d), q.dtype)
    sk = jax.ShapeDtypeStruct((k.shape[0], nl, d), k.dtype)
    sv = jax.ShapeDtypeStruct((v.shape[0], nl, d), v.dtype)
    plan = _ring_hop_bwd_plan(sq, sk, sv, causal, layout)
    if plan is None:
        return "jnp"
    kind, blk, groups = plan
    stamp = f"pallas:b{blk}"
    if kind == "expand":
        stamp += f":kvx{groups}"
    if _ring_prefetch_on(p):
        stamp += ":pf"
    return stamp


def _pallas_flash(q, k, v, causal: bool) -> jnp.ndarray:
    """Dispatch one (heads, seq, d) attention to the bundled Pallas TPU
    flash kernel (batch dim added/stripped; same 1/sqrt(d) scaling as
    ``attention_reference``). Differentiable via the kernel's own
    flash custom_vjp. Blocks are ALWAYS explicit — the kernel's own
    defaults measured 3x slower than the jnp engine on chip, explicit
    512/1024 blocks 2-4x faster (see the ``_TPU_FLASH`` note) — sized
    by :func:`_flash_block_for` (largest validated edge dividing seq
    that keeps >= ``_MIN_GRID`` grid programs per axis — 8k takes b512,
    16k+ take b1024;
    ``MOMP_FLASH_BLOCK=<n>`` overrides uniformly, a measurement knob so
    a chip session can sweep block sizes without code edits; the
    recorders' parity gates cover whatever value is in effect)."""
    from jax.experimental.pallas.ops.tpu import flash_attention as fa

    # eligibility ensured both edges exist and divide seq
    b = _flash_block_for(q.shape[1], q.shape[2])
    bw = _flash_bwd_block_for(q.shape[1], q.shape[2])
    blocks = fa.BlockSizes(
        block_q=b, block_k_major=b, block_k=b, block_b=1,
        block_q_major_dkv=bw, block_k_major_dkv=bw,
        block_k_dkv=bw, block_q_dkv=bw,
        block_k_major_dq=bw, block_k_dq=bw, block_q_dq=bw)
    with _pallas_interpret_calls(fa):
        out = fa.flash_attention(
            q[None], k[None], v[None], causal=causal,
            sm_scale=1.0 / math.sqrt(q.shape[-1]), block_sizes=blocks)
    return out[0].astype(q.dtype)


def _attention_chunked(q, k, v, causal: bool) -> jnp.ndarray:
    """Full local attention, flash-style double chunking (exact softmax).

    On a TPU backend, shapes the bundled Pallas flash kernel takes are
    dispatched to it (:func:`_flash_dispatch_plan` — directly, or by
    broadcasting budget-fitting GQA K/V, a chip-measured ~2.7x win over
    the folded path); everything below describes the jnp engine that
    carries every other case and is the CPU/interpret oracle.

    Scans q AND k/v in ``_Q_CHUNK`` slices so only a ``(h, _Q_CHUNK,
    _Q_CHUNK)`` score block is ever live; causal k blocks entirely in a q
    chunk's future are skipped via ``cond`` (halving the long-context
    FLOPs, like the ring path's hop skipping). Non-multiple sequence
    lengths are padded — padded k positions are masked out, padded q rows
    are computed and discarded — so there is no divisibility cliff.
    GQA/MQA K/V (fewer heads dividing q's) run UN-expanded on the jnp
    engine: query groups are folded into the row axis
    (:func:`_fold_groups`) so no repeated K/V is ever materialised and
    dk/dv come out group-summed. (On TPU, GQA shapes within the expand
    budget take the Pallas kernel with broadcast K/V instead — the
    kernel's throughput beats the folded path by more than the repeat
    costs; the fold carries the rest.) Used by the Ulysses path and by
    single-device rings.

    Differentiation takes the flash-attention backward (``custom_vjp``
    below), NOT autodiff through the scans: reverse-mode of the chunked
    forward saves O(seq²) block residuals even under remat (measured: a
    causal 16k backward OOMs 16 GB HBM, and 8k runs 15x slower than its
    forward), where the flash backward stores only ``(q, k, v, o,
    logsumexp)`` — O(seq·d) — and recomputes each score block from the
    saved row statistics.

    Caveat (measured, JAX 0.8): differentiating THROUGH a ``lax.scan``
    whose body calls this function (e.g. scanning attention layers and
    grad-ing the whole stack) defeats the memory bound — scan
    linearisation stacks per-block forward intermediates across
    iterations even though the custom backward is still the one invoked.
    Unroll such chains (python loop) or keep ``jax.grad`` inside the scan
    body; ``tests/test_context.py::test_flash_backward_residuals_bounded``
    pins the unrolled behaviour.
    """
    h, n, d = q.shape
    if n <= _Q_CHUNK:
        return attention_reference(
            q, *_repeat_heads(k, v, h // k.shape[0]), causal=causal)
    plan = _flash_dispatch_plan(q, k, v)
    if plan is not None:
        kind, _, _, groups = plan
        if kind == "expand":
            k, v = _repeat_heads(k, v, groups)
        return _pallas_flash(q, k, v, causal)
    return _flash_chunked(causal, q, k, v)


def _chunk(x, nc: int, c: int):
    """(h, nc*c, d...) -> (nc, h, c, d...) scan-leading chunk view."""
    h = x.shape[0]
    return x.reshape(h, nc, c, *x.shape[2:]).swapaxes(0, 1)


def _unchunk(x):
    h, c = x.shape[1], x.shape[2]
    y = x.swapaxes(0, 1)
    return y.reshape(h, x.shape[0] * c, *x.shape[3:])


def _fold_groups(x, hkv: int, g: int):
    """(hkv*g, n, d...) -> (hkv, n*g, d...): GQA query heads folded into
    the row axis, g group-rows per position, so every flash einsum runs
    directly against the UN-expanded (hkv, ...) K/V — no ``jnp.repeat``
    materialisation, and dk/dv come out group-summed for free. Row ``r``
    of the folded array holds position ``r // g``."""
    if g == 1:
        return x
    n = x.shape[1]
    return x.reshape(hkv, g, n, *x.shape[2:]).swapaxes(1, 2).reshape(
        hkv, n * g, *x.shape[2:])


def _unfold_groups(x, hkv: int, g: int):
    if g == 1:
        return x
    ng = x.shape[1]
    return x.reshape(hkv, ng // g, g, *x.shape[2:]).swapaxes(1, 2).reshape(
        hkv * g, ng // g, *x.shape[2:])


def _flash_forward(causal: bool, q, k, v):
    """Chunked forward returning ``(o, L)``: the attention output and the
    per-row logsumexp ``L = m + log l`` of the *scaled* scores — the only
    row statistic the flash backward needs to recompute any block's
    normalised probabilities as ``exp(s - L)``. Padded/fully-masked rows
    get ``L = -_NEG`` (huge) so recomputed probabilities underflow to 0.

    GQA/MQA: ``k``/``v`` may carry ``hkv = h // g`` heads; q is folded to
    ``(hkv, n*g, d)`` (see :func:`_fold_groups`) and the returned ``L``
    stays in that FOLDED layout — the backward consumes it directly.
    """
    h, n, d = q.shape
    hkv = k.shape[0]
    g = h // hkv
    c = _Q_CHUNK
    cg = c * g  # folded q rows per chunk
    nc = -(-n // c)
    pad = nc * c - n
    q32 = jnp.pad(q.astype(jnp.float32), ((0, 0), (0, pad), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
    qs = _chunk(_fold_groups(q32, hkv, g), nc, cg)
    ks, vs = _chunk(kp, nc, c), _chunk(vp, nc, c)
    rep = jnp.arange(cg) // g  # folded row -> within-chunk position

    def body_q(_, xs):
        qc, ci = xs
        qpos = ci * c + rep

        def body_k(carry, ys):
            oc, mc, lc = carry
            kb, vb, kj = ys
            kpos = kj * c + jnp.arange(c)
            n_valid = n if pad else None  # padded k tail needs masking

            def upd(args):
                return _block_update(qc, args[0], args[1], qpos, kpos,
                                     n_valid, causal,
                                     args[2], args[3], args[4])

            if causal:
                # Skip k blocks entirely in this q chunk's future.
                oc, mc, lc = lax.cond(
                    kj <= ci, upd,
                    lambda args: (args[2], args[3], args[4]),
                    (kb, vb, oc, mc, lc),
                )
            else:
                oc, mc, lc = upd((kb, vb, oc, mc, lc))
            return (oc, mc, lc), None

        o0 = jnp.zeros((hkv, cg, d), jnp.float32)
        m0 = jnp.full((hkv, cg), _NEG, jnp.float32)
        l0 = jnp.zeros((hkv, cg), jnp.float32)
        (oc, mc, lc), _ = lax.scan(
            body_k, (o0, m0, l0), (ks, vs, jnp.arange(nc)))
        Lc = jnp.where(lc > 0, mc + jnp.log(jnp.maximum(lc, 1e-37)), -_NEG)
        oc = oc / jnp.where(lc > 0, lc, 1.0)[..., None]
        return None, (oc, Lc)

    _, (os_, Ls) = lax.scan(body_q, None, (qs, jnp.arange(nc)))
    o = _unfold_groups(_unchunk(os_), hkv, g)[:, :n, :].astype(q.dtype)
    return o, _unchunk(Ls)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash_chunked(causal: bool, q, k, v):
    return _flash_forward(causal, q, k, v)[0]


def _flash_chunked_fwd(causal: bool, q, k, v):
    o, L = _flash_forward(causal, q, k, v)
    return o, (q, k, v, o, L)


def _flash_chunked_bwd(causal: bool, res, do):
    """Flash-attention backward: recompute each block's probabilities
    from the saved logsumexp in ONE pass over the allowed (q-chunk,
    k-chunk) blocks — each block's p and dp feed dq, dk and dv together
    (dk/dv accumulate into per-k-chunk stacks by indexed adds carried
    through the scans), causal block skipping mirrored from the
    forward. Per block:

        p  = exp(s - L)            (recomputed, masked)
        D  = rowsum(do * o)
        dv = pᵀ do
        dq = scale · [p∘(do vᵀ - D)] k ;  dk = scale · [...]ᵀ q
    """
    q, k, v, o, L = res
    h, n, d = q.shape
    hkv = k.shape[0]
    g = h // hkv
    c = _Q_CHUNK
    cg = c * g
    nc = -(-n // c)
    pad = nc * c - n
    scale = 1.0 / math.sqrt(d)
    f32 = jnp.float32

    def padded(x, fill=0.0):
        return jnp.pad(x.astype(f32), ((0, 0), (0, pad), (0, 0)),
                       constant_values=fill)

    k32, v32 = padded(k), padded(v)
    q32 = _fold_groups(padded(q), hkv, g)
    do32 = _fold_groups(padded(do), hkv, g)
    o32 = _fold_groups(padded(o), hkv, g)
    Lp = L  # saved FOLDED and padded by the forward (pad rows = -_NEG)
    D = jnp.sum(do32 * o32, axis=-1)  # (hkv, nc*c*g)
    qs, dos = _chunk(q32, nc, cg), _chunk(do32, nc, cg)
    ks, vs = _chunk(k32, nc, c), _chunk(v32, nc, c)
    Ls, Ds = _chunk(Lp, nc, cg), _chunk(D, nc, cg)
    ar = jnp.arange(c)
    rep = jnp.arange(cg) // g  # folded row -> within-chunk position

    # ONE pass over the allowed (i, j) block triangle: each block's
    # recomputed p and dp feed dq, dk AND dv together (5 matmuls/block —
    # the separate dq and dk/dv passes each redid s and dp, 7 total).
    # dk/dv accumulate into per-k-chunk stacks via indexed adds carried
    # through the scans; XLA aliases scan carries in place.
    def body_i(carry, xs):
        dks, dvs = carry
        qc, doc, Lc, Dc, ci = xs

        def body_j(inner, ys):
            dqc, dks, dvs = inner
            kb, vb, kj = ys

            def upd(_):
                mask = _mask_from_pos(ci * c + rep, kj * c + ar, n,
                                      causal)
                return _flash_block_grads(qc, doc, Lc, Dc, kb, vb, mask,
                                          scale)

            # Only the small per-block contributions pass through the
            # causal-skip cond; the O(seq) accumulators stay pure scan
            # carries (in-place aliasing is only guaranteed there — an
            # accumulator routed through a cond branch may be copied
            # per block, turning the O(seq) working set quadratic).
            if causal:
                dqj, dkj, dvj = lax.cond(
                    kj <= ci, upd,
                    lambda _: (jnp.zeros((hkv, cg, d), f32),
                               jnp.zeros((hkv, c, d), f32),
                               jnp.zeros((hkv, c, d), f32)),
                    None)
            else:
                dqj, dkj, dvj = upd(None)
            return (dqc + dqj, dks.at[kj].add(dkj),
                    dvs.at[kj].add(dvj)), None

        (dqc, dks, dvs), _ = lax.scan(
            body_j, (jnp.zeros((hkv, cg, d), f32), dks, dvs),
            (ks, vs, jnp.arange(nc)))
        return (dks, dvs), dqc

    z = jnp.zeros((nc, hkv, c, d), f32)
    (dks, dvs), dqs = lax.scan(
        body_i, (z, z), (qs, dos, Ls, Ds, jnp.arange(nc)))
    dq = _unfold_groups(_unchunk(dqs), hkv, g)[:, :n, :].astype(q.dtype)
    dk = _unchunk(dks)[:, :n, :].astype(k.dtype)
    dv = _unchunk(dvs)[:, :n, :].astype(v.dtype)
    return dq, dk, dv


_flash_chunked.defvjp(_flash_chunked_fwd, _flash_chunked_bwd)


def _seq_spec(axis: str) -> P:
    return P(None, axis, None)


def _check_seq(n: int, p: int, what: str) -> None:
    if n % p:
        raise ValueError(
            f"{what}: sequence length {n} not divisible by mesh size {p}; "
            "pad the sequence to a multiple (the framework's uneven-board "
            "handling pads globally the same way)"
        )


def _check_gqa(q, k, v, what: str) -> int:
    """Validate GQA/MQA head counts; returns the group count hq // hkv."""
    hq, hkv = q.shape[0], k.shape[0]
    if v.shape[0] != hkv:
        raise ValueError(
            f"{what}: v has {v.shape[0]} kv heads but k has {hkv}"
        )
    if hq % hkv:
        raise ValueError(
            f"{what}: {hq} query heads not a multiple of {hkv} kv heads"
        )
    return hq // hkv


def _repeat_heads(k, v, groups: int):
    """Broadcast K/V heads across query-head groups. The jnp compute
    paths avoid this entirely (ring and flash-chunked fold query groups
    into the row axis instead — see :func:`_fold_groups`); it serves
    the dense small-n oracle fallback, Ulysses' pre-wire expansion when
    the kv-head count doesn't split over the mesh (and then minimally —
    see ulysses_attention), and the TPU expand dispatch that broadcasts
    budget-fitting GQA K/V into the Pallas kernel
    (:func:`_flash_dispatch_plan`)."""
    if groups == 1:
        return k, v
    return jnp.repeat(k, groups, axis=0), jnp.repeat(v, groups, axis=0)


@functools.partial(
    jax.jit,
    static_argnames=("local_fn", "mesh", "axis", "causal", "layout",
                     "chaos_key"),
)
def _sharded_attention_jit(q, k, v, *, local_fn, mesh: Mesh, axis: str,
                           causal: bool, chaos_key=None, **local_kwargs):
    """Shared jit + ``shard_map`` scaffold for both attention variants;
    ``local_fn`` is the module-level per-shard body (hashable, so the jit
    cache keys stably on it); extra static kwargs (e.g. the ring
    ``layout``) pass through. ``chaos_key`` is a cache salt only
    (``robust.chaos``): injection and engine pins are trace-time
    decisions, so distinct chaos states must never share a trace — it is
    ``None`` (one cache entry, zero overhead) whenever no plan is
    active."""
    del chaos_key
    # Body runs only on a jit-cache miss — i.e. this IS the retrace
    # counter (obs.metrics): every compile of the sharded attention
    # scaffold lands one tick, cache hits land none.
    from mpi_and_open_mp_tpu.obs import metrics as _metrics

    _metrics.inc("jit.retrace", fn="sharded_attention")
    body = functools.partial(local_fn, axis=axis, causal=causal,
                             **local_kwargs)
    spec = _seq_spec(axis)
    return jax.shard_map(
        body, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh | None = None,
    axis: str = AXIS_SP,
    causal: bool = False,
    layout: str = "contiguous",
) -> jnp.ndarray:
    """Sequence-parallel attention over a ring mesh axis.

    ``q, k, v``: ``(heads, seq, head_dim)`` with ``seq`` sharded over
    ``axis``. K/V may carry fewer heads (GQA/MQA) as long as they divide
    the query heads. Peak memory per device is O(chunk * seq/p) scores —
    long contexts scale with the ring size. Returns the same sharding.

    ``layout="zigzag"`` (striped ring attention) balances CAUSAL work:
    under the contiguous split every hop's wall-clock is set by
    whichever device's block is unskipped (there always is one), so a
    causal trip costs ~p full-block times despite computing only half
    the scores. Zigzag pre-shards tokens in ``2p`` half-chunks, shard
    ``i`` holding half-chunks ``(i, 2p-1-i)``; each hop then computes
    only its LIVE (q-half x k-half) quarter-blocks (two off the
    diagonal hop, three on it) — uniformly on every device, forward
    and backward — roughly halving the causal trip's critical path. Operands must arrive in zigzag order
    (:func:`zigzag_shard`; invert outputs/gradients with
    :func:`zigzag_unshard`); needs ``seq % (2 * mesh size) == 0``.

    4D ``(B, heads, seq, head_dim)`` operands run B independent
    requests in ONE ring trip: the batch folds into the (unsharded)
    head axis (:func:`_fold_batch` — GQA grouping preserved per
    request, ``ppermute`` payloads carrying every request's K/V block
    per hop), the fold machinery runs unchanged, and the output
    unfolds to ``(B, heads, seq, head_dim)``. Differentiable like the
    3D form; :func:`ring_hop_engine_for` stamps the shape ``:b{B}``.
    """
    if q.ndim == 4:
        if not (k.ndim == v.ndim == 4 and k.shape[0] == q.shape[0]):
            raise ValueError(
                f"ring_attention: batched q {q.shape} needs k/v with the "
                f"same leading batch, got {k.shape} / {v.shape}")
        out = ring_attention(
            _fold_batch(q), _fold_batch(k), _fold_batch(v),
            mesh=mesh, axis=axis, causal=causal, layout=layout)
        return out.reshape(q.shape)
    if mesh is None:
        mesh = mesh_lib.make_mesh_1d(axis=axis)
    p = mesh.shape[axis]
    _check_seq(q.shape[1], p, "ring_attention")
    _check_gqa(q, k, v, "ring_attention")
    if layout not in ("contiguous", "zigzag"):
        # Eagerly: the p == 1 local path never consults the layout, and
        # a typo must not run silently there.
        raise ValueError(f"unknown ring layout {layout!r}")
    if layout == "zigzag" and q.shape[1] % (2 * p):
        raise ValueError(
            f"ring_attention zigzag layout needs seq % (2*mesh) == 0, "
            f"got seq {q.shape[1]} over {p} devices")
    sharding = NamedSharding(mesh, _seq_spec(axis))
    q, k, v = (jax.device_put(x, sharding) for x in (q, k, v))

    def dispatch(key=None):
        return _sharded_attention_jit(
            q, k, v, local_fn=_ring_attention_local, mesh=mesh, axis=axis,
            causal=causal, layout=layout, chaos_key=key)

    from mpi_and_open_mp_tpu.robust import chaos, guards

    plan = chaos.active_plan()
    if plan is None and not guards.guard_env():
        from mpi_and_open_mp_tpu.obs import trace

        if trace.hop_spans_active() and p > 1 and layout == "contiguous":
            # Telemetry dispatch: hop-by-hop with per-hop spans (see the
            # _ring_attention_traced block comment). Parity-exact, but a
            # host round trip per hop — never on the untraced hot path.
            return _ring_attention_traced(q, k, v, mesh=mesh, axis=axis,
                                          causal=causal)
        if trace.enabled():
            # Shapes the hop-by-hop decomposition doesn't cover (1-device
            # local, causal zigzag) or MOMP_TRACE_HOPS=0: whole-call span.
            with trace.span("ring_attention", devices=p, seq=q.shape[1],
                            layout=layout, causal=causal,
                            engine=ring_hop_engine_for(
                                q, k, v, p=p, causal=causal,
                                layout=layout)) as sp:
                out = dispatch()
                sp.anchor(out)
            return out
        # The production hot path: one env check, no validator (a finite
        # check is a full host fetch — see robust.guards module docs).
        return dispatch()
    if not guards.guards_active():
        # Chaos armed with `noguard`: inject, but let the fault land —
        # the test aid that proves injection reaches the fabric.
        return dispatch(chaos.trace_key("ring"))

    # NaN/divergence guard on the hop engine: validate the dispatched
    # fold, and re-dispatch a poisoned one on the jnp fold oracle —
    # injection suppressed (a transient fault must not re-fire on the
    # dispatch that retries it), hop kernel pinned off, fresh trace.
    def primary():
        return dispatch(chaos.trace_key("ring"))

    def jnp_fold_oracle():
        with chaos.suppressed(), _ring_hop_pinned(False):
            return dispatch(("ring", "recover"))

    from mpi_and_open_mp_tpu.obs import trace

    with trace.span("ring_attention", devices=p, seq=q.shape[1],
                    layout=layout, causal=causal, guarded=True) as sp:
        out, stamp, _notes = guards.with_fallback(
            [("hop", primary), ("jnp", jnp_fold_oracle)],
            validator=guards.all_finite)
        sp.set(engine=stamp)
        if stamp.endswith(":recovered"):
            # The funnel emits the trace event — parented to this span.
            guards.record_recovery(f"ring_attention:{stamp}")
        sp.anchor(out)
    return out


def flash_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, causal: bool = False
) -> jnp.ndarray:
    """Single-device flash-chunked attention — the local engine behind
    ``ring_attention``/``ulysses_attention``, exposed for unsharded use
    (one-chip training steps, benches). Exact softmax in O(chunk·seq)
    memory, the flash ``custom_vjp`` backward (O(seq·d) residuals), and
    GQA/MQA K/V heads run un-expanded on the jnp engine (query groups
    fold into the row axis). On TPU, eligible shapes (block-multiple
    seq, MXU-width head dim) run jax's bundled Pallas flash kernel —
    equal-head directly, budget-fitting GQA via broadcast K/V
    (:func:`_flash_dispatch_plan`); ``MOMP_TPU_FLASH=0`` forces the jnp
    engine. Shapes ``(heads, seq, head_dim)``; ``k``/``v`` may carry
    fewer heads as long as they divide ``q``'s. 4D
    ``(B, heads, seq, head_dim)`` operands fold the request batch into
    the head axis (:func:`_fold_batch` — GQA grouping preserved per
    request) and unfold on the way out; one dispatch serves all B."""
    if q.ndim == 4:
        if not (k.ndim == v.ndim == 4 and k.shape[0] == q.shape[0]):
            raise ValueError(
                f"flash_attention: batched q {q.shape} needs k/v with the "
                f"same leading batch, got {k.shape} / {v.shape}")
        out = flash_attention(
            _fold_batch(q), _fold_batch(k), _fold_batch(v), causal=causal)
        return out.reshape(q.shape)
    _check_gqa(q, k, v, "flash_attention")
    return _attention_chunked(q, k, v, causal)


def _ulysses_local(q, k, v, *, axis: str, causal: bool):
    """Per-shard body: all-to-all seq->head re-shard, local attention, back.

    ``lax.all_to_all`` is the third collective family the framework maps onto
    ICI (after ``ppermute`` halos and ``psum`` reductions); the reference has
    no direct analogue — its closest structure is the gather/scatter pair of
    ``life_collect`` (``5-gather/life_mpi.c:178``) done symmetrically by all
    peers at once.
    """
    # (H, n_local, d) -> (H/p, n_global, d): scatter heads, gather sequence.
    qh = lax.all_to_all(q, axis, split_axis=0, concat_axis=1, tiled=True)
    kh = lax.all_to_all(k, axis, split_axis=0, concat_axis=1, tiled=True)
    vh = lax.all_to_all(v, axis, split_axis=0, concat_axis=1, tiled=True)
    # GQA with hkv % p == 0 stays un-expanded end to end: the contiguous
    # q-head block on each device maps exactly onto its kv-head block on
    # the wire, and the flash-chunked path then folds query groups
    # against the (hkv, ...) K/V directly (the small-n dense fallback
    # expands internally).
    oh = _attention_chunked(qh, kh, vh, causal=causal)
    # (H/p, n_global, d) -> (H, n_local, d).
    return lax.all_to_all(oh, axis, split_axis=1, concat_axis=0, tiled=True)


def ulysses_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh | None = None,
    axis: str = AXIS_SP,
    causal: bool = False,
) -> jnp.ndarray:
    """All-to-all (Ulysses-style) sequence-parallel attention.

    Requires ``heads`` divisible by the mesh size (each device computes full
    attention for ``heads/p`` heads). Two ``all_to_all`` collectives per
    call instead of ring hops; exact softmax, no online accumulation
    needed. GQA/MQA K/V heads whose count splits over the mesh stay
    un-expanded end to end (wire and local compute — the flash path
    folds query groups instead); otherwise they are pre-expanded just
    enough to split.
    """
    if mesh is None:
        mesh = mesh_lib.make_mesh_1d(axis=axis)
    p = mesh.shape[axis]
    _check_seq(q.shape[1], p, "ulysses_attention")
    groups = _check_gqa(q, k, v, "ulysses_attention")
    if q.shape[0] % p:
        raise ValueError(
            f"ulysses_attention: {q.shape[0]} heads not divisible by mesh "
            f"size {p}; use ring_attention (no head constraint) instead"
        )
    hkv = k.shape[0]
    if hkv % p:
        # Too few kv heads to split across the mesh: expand pre-wire, but
        # only to the smallest count divisible by p that still divides hq
        # (the local repeat after the all_to_all covers the rest) — full
        # expansion only as a last resort.
        e = hkv * (p // math.gcd(hkv, p))
        factor = e // hkv if q.shape[0] % e == 0 else groups
        k, v = _repeat_heads(k, v, factor)
    sharding = NamedSharding(mesh, _seq_spec(axis))
    q, k, v = (jax.device_put(x, sharding) for x in (q, k, v))
    return _sharded_attention_jit(q, k, v, local_fn=_ulysses_local,
                                  mesh=mesh, axis=axis, causal=causal)
