"""Device-resident session pool: handle-based serving state.

Every serve ticket before this module shipped its full board host →
device and the result back, while a bit-sliced step on a 64² board costs
microseconds — the transfer and dispatch cost can dwarf the compute at
production traffic (not yet measured on a chip). The pool
inverts the data flow (the Casper near-memory argument in PAPERS.md:
move compute to where the state lives, not state to the compute): a
live Life session STAYS on device between requests as a
:class:`Handle` — a (slab, bit-lane) pair over a bit-sliced
``(n_planes, ny, nx)`` uint32 slab (the PR 10 board-sliced layout:
bit ``lane % 32`` of plane ``lane // 32`` is one whole board). Boards
cross the wire on exactly three occasions: session **create**, explicit
**snapshot**, and **evict**. Everything in between is a handle-sized
dispatch.

**In-place stepping.** :func:`_pool_step_jit` advances a whole slab
with ``donate_argnums=(0,)`` — the slab buffer is donated, so the
device updates state in place instead of allocating a second slab per
step. The step count is a runtime int32 scalar and the lane selection a
runtime uint32 mask per plane (``(stepped & mask) | (planes & ~mask)``),
so ONE compiled program per plane shape serves every lane subset and
every step count — stepping one lone session and stepping 32 slab-mates
coalesced is the same executable (``jit.retrace{fn=pool_step}``
observable, and the program fingerprint is
``serve.aotcache.fingerprint(..., program="pool-step", donated=True)``
— donation is part of the key because a donated and a non-donated
program are different executables). Lanes NOT in the mask pass through
bit-identically: slab-mates are untouched, which is what makes the
slab a pool and not a batch.

**Settled skip.** Each step dispatch also returns a per-plane settled
word (``ops.bitlife.lane_change_bits`` over the loop's final
consecutive-state pair — a set bit is a PROVEN fixed point, so a
period-k oscillator never reads as settled). The word resolves lazily
before the slab's next step; when every session in a slab group is
settled, the dispatch is skipped outright (``pool.settled_skips``) and
only the logical ``steps_applied`` advances — bit-identical by the
fixed-point argument, and WAL STEP frames stay authoritative because
replay re-proves settledness from the same boards. Any rewrite
(create, revive) clears the flag; it is re-proven, never assumed.

**Lane allocation** is a free-lane bitmap per slab (bit ``l`` set =
lane ``l`` free). Create takes the lowest free lane of the fullest
slab of the board's shape (dense packing keeps masks cheap and
fragmentation low); when no lane is free a new slab allocates against
the hard ``device_budget_bytes`` — and when THAT would breach the
budget, the least-recently-used sessions spill to the host tier until
a lane or the budget frees up.

**Lane compaction.** Evictions leave sparse planes — 31 dead lanes
still pay a full slab of VMEM and a full plane of vector work on every
group step. :meth:`SessionPool.compact` repacks a shape's survivors
32-at-a-time through the EXISTING pack/unpack kernels
(``ops.bitlife.pack_batch_bits`` / ``unpack_batch_bits``) into the
minimum number of slabs and frees the rest; :meth:`maybe_compact` is
the cheap fragmentation trigger the serving daemon polls between
pump rounds ("background" compaction — no thread, same
clock-free discipline as the rest of ``serve/``). Handles move;
sessions don't notice (every lookup resolves ``sid →`` current
handle), and step results are unchanged — the drill test evicts 31 of
32, compacts, and bit-compares the survivor.

**Spill tier.** Spilled sessions live as host boards; the next step
revives them (a ``pool.miss``) through the normal create path.
Snapshots of spilled sessions are served from the host copy without
reviving. The budget is HARD: a revive that cannot spill anything else
(every resident session pinned by the in-flight group) raises rather
than silently over-allocating.

Durability is the caller's job by design: the pool owns device state
and host spill copies, no files. The serving daemon journals
CREATE/STEP/SNAPSHOT/EVICT frames write-ahead (``serve/wal.py``) and
re-materializes the pool on resume from journaled create-boards +
replayed step counts — see ``docs/DESIGN.md`` §14 for the loss bounds.
"""

from __future__ import annotations

import dataclasses
import functools
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from mpi_and_open_mp_tpu.ops.bitlife import (
    _carry_save_rule9, _note_retrace, lane_change_bits, pack_batch_bits,
    unpack_batch_bits)

#: Boards per bit-plane — the uint32 word width of the sliced layout.
LANES_PER_PLANE = 32

#: Default hard budget for live slab bytes on device. 64 MiB holds
#: ~4000 resident 64² sessions (one 16 KB plane per 32) — far past the
#: CI/bench scales, small next to any real HBM.
DEFAULT_DEVICE_BUDGET = 64 << 20


class PoolError(ValueError):
    """A session-pool contract violation (duplicate create, unknown
    session, a budget too small to hold even one slab)."""


@dataclasses.dataclass(frozen=True)
class Handle:
    """Where a resident session lives: ``lane % 32`` is the bit, ``lane
    // 32`` the plane, inside slab ``slab``. Handles are pool-internal
    coordinates — compaction moves them; sessions are addressed by id."""

    slab: int
    lane: int


@dataclasses.dataclass
class _Slab:
    shape: tuple[int, int]
    planes: object  # jax (P, ny, nx) uint32 array
    free: int  # bitmap over 32*P lanes; bit set = lane free
    lanes: dict[int, str] = dataclasses.field(default_factory=dict)

    @property
    def capacity(self) -> int:
        return int(self.planes.shape[0]) * LANES_PER_PLANE

    @property
    def live(self) -> int:
        return len(self.lanes)


@dataclasses.dataclass
class _Session:
    sid: str
    shape: tuple[int, int]
    handle: Handle | None = None  # None = spilled to host
    host: np.ndarray | None = None  # the board, when spilled
    steps_applied: int = 0
    #: Proven still life: the last dispatch's final step changed nothing
    #: on this lane (consecutive-state equality, NOT same-as-start — a
    #: period-k oscillator that returns to its start is never settled).
    #: False on create/revive; cleared whenever the board is rewritten.
    settled: bool = False


# --------------------------------------------------------------- device ops
#
# Three compiled programs per plane shape — step (donated, masked),
# lane write (donated), lane read — all with runtime-scalar operands so
# lane index, step count, and mask never retrace.


def _torus_step(planes):
    """One Life step on a (P, ny, nx) bit-sliced stack via plain torus
    rolls into the 9-operand carry-save rule — the backend-portable
    form (XLA on CPU, XLA on TPU; the Pallas kernels stay the batch
    engines' fast path). Neighbour at (dy, dx) = roll by (+dy, +dx)."""
    up = jnp.roll(planes, 1, axis=1)
    dn = jnp.roll(planes, -1, axis=1)
    lf = jnp.roll(planes, 1, axis=2)
    rt = jnp.roll(planes, -1, axis=2)
    ul = jnp.roll(up, 1, axis=2)
    ur = jnp.roll(up, -1, axis=2)
    dl = jnp.roll(dn, 1, axis=2)
    dr = jnp.roll(dn, -1, axis=2)
    return _carry_save_rule9(planes, up, dn, lf, rt, ul, ur, dl, dr)


@functools.partial(jax.jit, donate_argnums=(0,))
def _pool_step_jit(planes, steps, mask):
    """Advance the masked lanes ``steps`` Life steps IN PLACE (the slab
    buffer is donated). Unmasked lanes pass through bit-identically.

    Also returns the per-plane SETTLED word: bit ``l`` set iff lane
    ``l`` is masked and its final step was the identity — the loop
    carries ``(prev, cur)`` so the comparison is between consecutive
    states, which proves a true fixed point (an oscillator whose period
    divides ``steps`` returns to its start but fails prev == cur). The
    word costs one XOR/OR reduction on state already in registers; the
    pool uses it to skip future dispatches for all-settled groups."""
    _note_retrace("pool_step")
    prev, cur = jax.lax.fori_loop(
        0, steps, lambda _, c: (c[1], _torus_step(c[1])),
        (planes, planes))
    settled = ~lane_change_bits(prev, cur) & mask
    m = mask[:, None, None]
    return (cur & m) | (planes & ~m), settled


@functools.partial(jax.jit, donate_argnums=(0,))
def _lane_write_jit(planes, board, plane_idx, bitpos):
    """Write one 0/1 board into (plane_idx, bitpos) of a donated slab —
    the create/revive path: only board-sized data crosses the wire."""
    _note_retrace("pool_lane_write")
    bit = jnp.uint32(1) << bitpos
    sel = (jnp.arange(planes.shape[0], dtype=jnp.int32)
           == plane_idx)[:, None, None]
    written = (planes & ~bit) | (board.astype(jnp.uint32) << bitpos)[None]
    return jnp.where(sel, written, planes)


@jax.jit
def _lane_read_jit(planes, plane_idx, bitpos):
    """Read one lane back as a (ny, nx) uint8 board — the snapshot/
    evict path; again only board-sized data moves."""
    _note_retrace("pool_lane_read")
    row = jnp.take(planes, plane_idx, axis=0)
    return ((row >> bitpos) & jnp.uint32(1)).astype(jnp.uint8)


class SessionPool:
    """The device-resident session pool. Host-side manager, clock-free,
    no threads, no IO — slabs, bitmaps, an LRU, and a host spill dict.

    ``planes_per_slab`` sets slab capacity (32 lanes per plane); the
    default of one plane keeps the masked step's wasted work bounded by
    one word of lanes and makes the compaction arithmetic legible.
    """

    def __init__(self, *, device_budget_bytes: int = DEFAULT_DEVICE_BUDGET,
                 planes_per_slab: int = 1):
        if planes_per_slab < 1:
            raise PoolError(
                f"planes_per_slab must be >= 1, got {planes_per_slab}")
        if device_budget_bytes < 1:
            raise PoolError(
                f"device_budget_bytes must be >= 1, got {device_budget_bytes}")
        self._budget = int(device_budget_bytes)
        self._planes_per_slab = int(planes_per_slab)
        self._slabs: dict[int, _Slab] = {}
        self._next_slab = 0
        self._sessions: dict[str, _Session] = {}
        self._lru: OrderedDict[str, None] = OrderedDict()  # resident only
        self._pinned: set[str] = set()  # in-flight group, spill-exempt
        self._program_digests: dict[tuple, str] = {}
        self.counts = {
            "creates": 0, "hits": 0, "misses": 0, "evictions": 0,
            "spills": 0, "revivals": 0, "compactions": 0, "migrated": 0,
            "slabs_freed": 0, "dispatches": 0, "steps_applied": 0,
            "settled_skips": 0,
        }
        # Deferred settled words: slab_id -> (device word array, [(sess,
        # lane)] at dispatch time). Resolved lazily at the NEXT step of
        # the same slab so the fetch never forces a sync on the dispatch
        # hot path (the dispatch itself stays fire-and-forget).
        self._pending_settled: dict[int, tuple] = {}

    # -- geometry ----------------------------------------------------------

    def _slab_bytes(self, shape: tuple[int, int]) -> int:
        ny, nx = shape
        return self._planes_per_slab * ny * nx * 4

    def device_bytes(self) -> int:
        return sum(self._slab_bytes(s.shape) for s in self._slabs.values())

    def _capacity(self) -> int:
        return self._planes_per_slab * LANES_PER_PLANE

    # -- introspection -----------------------------------------------------

    def sessions(self) -> list[str]:
        return list(self._sessions)

    def has(self, sid: str) -> bool:
        return sid in self._sessions

    def handle(self, sid: str) -> Handle | None:
        """The session's CURRENT handle (``None`` when spilled) — a
        grouping hint only; compaction and spills move it."""
        return self._require(sid).handle

    def slab_groups(self) -> dict[int | None, list[str]]:
        """Live sessions grouped by resident slab (``None`` = spilled).
        A membership change (graceful drain, rejoin claim) migrates one
        group as a unit: slab-mates advance under one donated dispatch,
        so scattering them across destinations would split one program
        invocation into several padded ones — the whole-bucket rule of
        the work stealer, applied to resident state."""
        out: dict[int | None, list[str]] = {}
        for sid, s in self._sessions.items():
            key = s.handle.slab if s.handle is not None else None
            out.setdefault(key, []).append(sid)
        return out

    def steps_applied(self, sid: str) -> int:
        return self._require(sid).steps_applied

    def program_digest(self, shape: tuple[int, int]) -> str:
        """The AOT-fingerprint digest of this shape's in-place step
        program — plane shape + ``program="pool-step"`` +
        ``donated=True`` in the key, so a pool executable can never be
        confused with a bucket program for the same stack shape."""
        key = (self._planes_per_slab, *shape)
        if key not in self._program_digests:
            from mpi_and_open_mp_tpu.serve import aotcache

            self._program_digests[key] = aotcache.digest_for(
                aotcache.fingerprint(key, np.uint32, program="pool-step",
                                     donated=True))
        return self._program_digests[key]

    def stats(self) -> dict:
        resident = sum(1 for s in self._sessions.values()
                       if s.handle is not None)
        out = dict(self.counts)
        out.update({
            "sessions": len(self._sessions),
            "resident": resident,
            "spilled": len(self._sessions) - resident,
            "slabs": len(self._slabs),
            "lanes_live": sum(s.live for s in self._slabs.values()),
            "lanes_free": sum(s.capacity - s.live
                              for s in self._slabs.values()),
            "device_bytes": self.device_bytes(),
            "device_budget_bytes": self._budget,
        })
        return out

    def _gauges(self) -> None:
        from mpi_and_open_mp_tpu.obs import metrics

        s = self.stats()
        metrics.gauge("pool.slabs", s["slabs"])
        metrics.gauge("pool.lanes_live", s["lanes_live"])
        metrics.gauge("pool.lanes_free", s["lanes_free"])
        metrics.gauge("pool.device_bytes", s["device_bytes"])
        metrics.gauge("pool.spilled", s["spilled"])

    # -- internals ---------------------------------------------------------

    def _require(self, sid: str) -> _Session:
        try:
            return self._sessions[sid]
        except KeyError:
            raise PoolError(f"unknown session {sid!r}") from None

    def _touch(self, sid: str) -> None:
        self._lru[sid] = None
        self._lru.move_to_end(sid)

    def _alloc_lane(self, shape: tuple[int, int]) -> Handle:
        """A free lane for one board of ``shape``: fullest existing slab
        first (dense packing), else a new slab under the budget, else
        spill LRU sessions until one of those works."""
        while True:
            candidates = [(sl.live, slab_id) for slab_id, sl
                          in self._slabs.items()
                          if sl.shape == shape and sl.free]
            if candidates:
                _, slab_id = max(candidates)
                slab = self._slabs[slab_id]
                lane = (slab.free & -slab.free).bit_length() - 1
                slab.free &= ~(1 << lane)
                return Handle(slab_id, lane)
            if self.device_bytes() + self._slab_bytes(shape) <= self._budget:
                return Handle(self._new_slab(shape), self._take_lane_0(shape))
            if not self._spill_one():
                raise PoolError(
                    f"device budget {self._budget} B cannot hold one "
                    f"{shape} slab ({self._slab_bytes(shape)} B) with "
                    "every resident session pinned")

    def _new_slab(self, shape: tuple[int, int]) -> int:
        ny, nx = shape
        slab_id = self._next_slab
        self._next_slab += 1
        planes = jnp.zeros((self._planes_per_slab, ny, nx), jnp.uint32)
        self._slabs[slab_id] = _Slab(
            shape=shape, planes=planes,
            free=(1 << self._capacity()) - 1)
        return slab_id

    def _take_lane_0(self, shape: tuple[int, int]) -> int:
        slab = self._slabs[self._next_slab - 1]
        slab.free &= ~1
        return 0

    def _write_lane(self, h: Handle, board: np.ndarray) -> None:
        slab = self._slabs[h.slab]
        slab.planes = _lane_write_jit(
            slab.planes, jnp.asarray(board, jnp.uint32),
            jnp.int32(h.lane // LANES_PER_PLANE),
            jnp.uint32(h.lane % LANES_PER_PLANE))

    def _read_lane(self, h: Handle) -> np.ndarray:
        slab = self._slabs[h.slab]
        return np.asarray(_lane_read_jit(
            slab.planes,
            jnp.int32(h.lane // LANES_PER_PLANE),
            jnp.uint32(h.lane % LANES_PER_PLANE)))

    def _free_lane(self, h: Handle) -> None:
        slab = self._slabs[h.slab]
        slab.free |= 1 << h.lane
        slab.lanes.pop(h.lane, None)
        if not slab.lanes:
            del self._slabs[h.slab]
            self._pending_settled.pop(h.slab, None)
            self.counts["slabs_freed"] += 1

    def _spill_one(self) -> bool:
        """Spill the least-recently-used unpinned resident session to
        the host tier; ``False`` when nothing is spillable."""
        from mpi_and_open_mp_tpu.obs import metrics

        for sid in self._lru:
            if sid in self._pinned:
                continue
            sess = self._sessions[sid]
            sess.host = self._read_lane(sess.handle)
            self._free_lane(sess.handle)
            sess.handle = None
            del self._lru[sid]
            self.counts["spills"] += 1
            metrics.inc("pool.spill")
            return True
        return False

    def _resolve_settled(self, slab_id: int) -> None:
        """Fetch a slab's deferred settled word (if one is pending) and
        fan the bits out to the dispatched sessions. Called before the
        slab's next step decision — by then the dispatch that produced
        the word has long completed, so the fetch is not a stall."""
        pending = self._pending_settled.pop(slab_id, None)
        if pending is None:
            return
        word, lanes = pending
        word = np.asarray(word)
        for sess, lane in lanes:
            sess.settled = bool(
                (int(word[lane // LANES_PER_PLANE])
                 >> (lane % LANES_PER_PLANE)) & 1)

    def _resident(self, sid: str) -> _Session:
        """The session, revived onto a lane if it was spilled. Counts
        the pool.hit/pool.miss pair — a miss is exactly one host→device
        board re-materialization."""
        from mpi_and_open_mp_tpu.obs import metrics

        sess = self._require(sid)
        if sess.handle is not None:
            self.counts["hits"] += 1
            metrics.inc("pool.hit")
            self._touch(sid)
            return sess
        self.counts["misses"] += 1
        self.counts["revivals"] += 1
        metrics.inc("pool.miss")
        h = self._alloc_lane(sess.shape)
        self._write_lane(h, sess.host)
        self._slabs[h.slab].lanes[h.lane] = sid
        sess.handle, sess.host = h, None
        sess.settled = False  # re-prove after any rewrite, never carry
        self._touch(sid)
        return sess

    # -- the session lifecycle ---------------------------------------------

    def create(self, sid: str, board: np.ndarray) -> Handle:
        """Admit one live session: the board crosses the wire ONCE,
        into a lane of a bit-sliced slab. Raises on a duplicate id —
        create/evict is the lifecycle, not upsert."""
        from mpi_and_open_mp_tpu.obs import metrics, trace

        if sid in self._sessions:
            raise PoolError(f"session {sid!r} already exists")
        board = np.asarray(board)
        if board.ndim != 2:
            raise PoolError(
                f"create: one 2D board per session, got {board.shape}")
        shape = (int(board.shape[0]), int(board.shape[1]))
        h = self._alloc_lane(shape)
        self._write_lane(h, (board != 0).astype(np.uint32))
        self._slabs[h.slab].lanes[h.lane] = sid
        self._sessions[sid] = _Session(sid=sid, shape=shape, handle=h)
        self._touch(sid)
        self.counts["creates"] += 1
        metrics.inc("pool.create")
        trace.event("pool.create", sid=sid, slab=h.slab, lane=h.lane,
                    shape=f"{shape[0]}x{shape[1]}")
        self._gauges()
        return h

    def step(self, sid: str, steps: int) -> None:
        """Advance ONE session in place — no board moves. A lone step
        and a 32-lane group step share the same compiled program (the
        lane mask is runtime data)."""
        self.step_group([sid], steps)

    def step_group(self, sids: list[str], steps: int) -> int:
        """Advance many sessions ``steps`` steps with as few dispatches
        as their slab placement allows: all lanes sharing a slab ride
        ONE in-place masked dispatch. Returns the dispatch count."""
        from mpi_and_open_mp_tpu.obs import metrics, trace

        steps = int(steps)
        if steps < 0:
            raise PoolError(f"steps must be >= 0, got {steps}")
        if not sids:
            return 0
        self._pinned.update(sids)
        try:
            by_slab: dict[int, list[_Session]] = {}
            for sid in sids:
                sess = self._resident(sid)
                by_slab.setdefault(sess.handle.slab, []).append(sess)
        finally:
            self._pinned.difference_update(sids)
        if steps == 0:
            return 0
        dispatches = skips = 0
        for slab_id, group in by_slab.items():
            slab = self._slabs[slab_id]
            self._resolve_settled(slab_id)
            if all(sess.settled for sess in group):
                # Every lane in the group is a proven fixed point:
                # advancing ANY step count is the identity, so the
                # logical step count moves while the device does
                # nothing. WAL STEP frames stay authoritative — replay
                # re-proves settledness from the board and lands on the
                # same bits whether or not the skip engages.
                skips += 1
                for sess in group:
                    sess.steps_applied += steps
                trace.event("pool.settled_skip", slab=slab_id,
                            lanes=len(group), steps=steps)
                continue
            mask = np.zeros(self._planes_per_slab, np.uint32)
            for sess in group:
                lane = sess.handle.lane
                mask[lane // LANES_PER_PLANE] |= np.uint32(
                    1 << (lane % LANES_PER_PLANE))
            slab.planes, settled = _pool_step_jit(
                slab.planes, jnp.int32(steps), jnp.asarray(mask))
            self._pending_settled[slab_id] = (
                settled, [(sess, sess.handle.lane) for sess in group])
            dispatches += 1
            for sess in group:
                sess.steps_applied += steps
            trace.event("pool.step", slab=slab_id, lanes=len(group),
                        steps=steps)
        self.counts["dispatches"] += dispatches
        self.counts["steps_applied"] += steps * len(sids)
        self.counts["settled_skips"] += skips
        metrics.inc("pool.dispatches", dispatches)
        if skips:
            metrics.inc("pool.settled_skips", skips)
        return dispatches

    def snapshot(self, sid: str) -> np.ndarray:
        """The session's current board, host-side (uint8) — one
        board-sized device→host read for resident sessions, a host copy
        for spilled ones (no revival)."""
        sess = self._require(sid)
        if sess.handle is None:
            return np.array(sess.host, dtype=np.uint8)
        self._touch(sid)
        return self._read_lane(sess.handle)

    def evict(self, sid: str) -> np.ndarray:
        """End the session: its final board comes back (the last wire
        crossing), its lane frees, an emptied slab is released."""
        from mpi_and_open_mp_tpu.obs import metrics, trace

        sess = self._require(sid)
        board = self.snapshot(sid)
        if sess.handle is not None:
            self._free_lane(sess.handle)
            self._lru.pop(sid, None)
        del self._sessions[sid]
        self.counts["evictions"] += 1
        metrics.inc("pool.evict")
        trace.event("pool.evict", sid=sid, steps=sess.steps_applied)
        self._gauges()
        return board

    # -- lane compaction ---------------------------------------------------

    def fragmented_shapes(self) -> list[tuple[int, int]]:
        """Shapes whose live lanes would fit in fewer slabs than they
        occupy — the compaction trigger condition."""
        by_shape: dict[tuple[int, int], tuple[int, int]] = {}
        for slab in self._slabs.values():
            n, live = by_shape.get(slab.shape, (0, 0))
            by_shape[slab.shape] = (n + 1, live + slab.live)
        cap = self._capacity()
        return [shape for shape, (n, live) in by_shape.items()
                if n > max(1, -(-live // cap)) or (n and live == 0)]

    def maybe_compact(self) -> dict | None:
        """Compact iff fragmented — the cheap poll the daemon pump runs
        between rounds; ``None`` when there is nothing to do."""
        return self.compact() if self.fragmented_shapes() else None

    def compact(self) -> dict:
        """Repack every fragmented shape's survivors 32-at-a-time
        through the existing pack/unpack kernels into the minimum slab
        count, free the emptied slabs, and re-point the handles. Step
        results are unchanged — lanes carry whole boards, so a migrated
        session is the same bits in a different word position."""
        from mpi_and_open_mp_tpu.obs import metrics, trace

        migrated = freed = 0
        cap = self._capacity()
        for shape in self.fragmented_shapes():
            slab_ids = sorted(s_id for s_id, sl in self._slabs.items()
                              if sl.shape == shape)
            # Unpack every live lane of the shape (the unpack kernel,
            # one call per donor slab), keyed by session.
            boards: list[np.ndarray] = []
            sids: list[str] = []
            for s_id in slab_ids:
                slab = self._slabs[s_id]
                if slab.lanes:
                    stack = np.asarray(unpack_batch_bits(
                        slab.planes, cap))
                    for lane, sid in sorted(slab.lanes.items()):
                        boards.append(stack[lane])
                        sids.append(sid)
                del self._slabs[s_id]
                # Lanes move: a deferred settled word indexed by the old
                # lane order must not resolve against the new layout.
                # Dropping it is conservative (settled stays False).
                self._pending_settled.pop(s_id, None)
                freed += 1
            # Repack 32*P-at-a-time (the pack kernel) into fresh dense
            # slabs; zero-padded tail lanes stay free.
            for lo in range(0, len(sids), cap):
                chunk_sids = sids[lo:lo + cap]
                chunk = np.stack(boards[lo:lo + cap]).astype(np.uint8)
                slab_id = self._next_slab
                self._next_slab += 1
                pad = cap - len(chunk_sids)
                if pad:
                    chunk = np.concatenate(
                        [chunk, np.zeros((pad, *shape), np.uint8)])
                self._slabs[slab_id] = _Slab(
                    shape=shape,
                    planes=pack_batch_bits(jnp.asarray(chunk)),
                    free=((1 << cap) - 1) & ~((1 << len(chunk_sids)) - 1),
                    lanes={i: sid for i, sid in enumerate(chunk_sids)})
                for i, sid in enumerate(chunk_sids):
                    old = self._sessions[sid].handle
                    if (old.slab, old.lane) != (slab_id, i):
                        migrated += 1
                    self._sessions[sid].handle = Handle(slab_id, i)
                freed -= 1
        self.counts["compactions"] += 1
        self.counts["migrated"] += migrated
        self.counts["slabs_freed"] += max(freed, 0)
        metrics.inc("pool.compactions")
        if migrated:
            metrics.inc("pool.migrated", migrated)
        trace.event("pool.compact", migrated=migrated, freed=freed)
        self._gauges()
        return {"migrated": migrated, "slabs_freed": max(freed, 0),
                "slabs": len(self._slabs)}
