"""Sparse active tiles on a sharded board: live-area cost at mesh scale.

``stencils.sparse`` (PR 13) bounds a single device's per-step cost by
the live area; ``parallel.haloplan`` (PR 15) hides the ghost exchange
behind interior compute. Neither composes with the other: a sharded
board pays full dense cost per shard no matter how dead it is. This
module is the composition — a host-maintained GLOBAL active-tile mask
over a ``shard_map``-sharded board, where each round gathers only the
active tiles of each shard (with radius halos taken from the exchanged
ghost frame), steps them in one collective dispatch, and scatters them
back in place.

**Activation crosses shards for free.** The mask lives in global tile
coordinates: each stepped tile reports a 3x3 border-band change flag
(did cells within ``radius`` of each edge/corner change?), and the host
wakes ``(gy+dy) % ty, (gx+dx) % tx`` — modular arithmetic that neither
knows nor cares where the shard boundaries fall. A glider leaving shard
A wakes the tile it is entering in shard B because the stepped edge
tile read B's cells through the ghost exchange and its band flag fired;
the woken tile is gathered (on B) next round. Bit-exactness is
inherited, not argued: gathered tiles step through the SAME
``engine.step_padded`` arithmetic over the SAME exchanged padding as
the dense sequential schedule, so the reassembled board equals the
dense-sharded board bit-for-bit at every step (integer rules).

**The exchange skip.** A round's ghost payload is exactly the boundary
band (the ``radius``-deep strips along the sharded axes). Every
dispatch also returns one scalar per shard: "is my boundary band
live?". When EVERY shard's band is dead, the next round runs a twin
program whose sharded axes are padded with a static zero sentinel
instead of ``ppermute``d ghosts — bit-exact because the ghosts it
replaces are provably all-zero. The skip decision is made on the HOST
from the global flag, selecting between two compiled programs, so the
collective stays unconditional inside each program and the ring can
never deadlock (DESIGN.md §17 still holds; the legality argument is
§18). ``counters()["exchange_skips"]`` counts the rounds that shipped
no ghosts.

**The crossover ladder survives.** Above ``crossover`` active fraction
the round falls back to the dense sharded runner (PR 15 plans intact)
and the mask rebuilds from the full-board diff — the
``dense:crossover`` rung from PR 13, so adversarial all-alive boards
never regress past one diff. ``MOMP_SPARSE_SHARDED=0`` is the kill
switch (read at PLAN time, same semantics as ``MOMP_HALO_OVERLAP``):
a disabled plan pins every step to the dense sharded path and stamps
``dense:sharded``, which the regression sentinel ranks below any
``sparse*`` stamp — flipping the switch under a recorded sparse
baseline is a provenance downgrade, by construction.
"""

from __future__ import annotations

import dataclasses
import functools
import os

import numpy as np

from . import engine
from .sparse import _dilate
from .spec import StencilSpec

ENV_SPARSE_SHARDED = "MOMP_SPARSE_SHARDED"


def sparse_sharded_enabled() -> bool:
    """The ``MOMP_SPARSE_SHARDED`` kill switch (default ON)."""
    return os.environ.get(ENV_SPARSE_SHARDED, "1") != "0"


@dataclasses.dataclass(frozen=True)
class SparseShardedPlan:
    """One (layout, mesh, shard, tile) sparse-sharded decision, derived
    once per geometry — the sparse twin of ``haloplan.HaloPlan``."""

    layout: str                   # row | col | cart
    mesh_axes: tuple[int, int]    # (py, px)
    shard_shape: tuple[int, int]  # local (h, w) per shard
    tile: int
    crossover: float
    enabled: bool                 # sparse rounds may run at all
    engine: str                   # provenance stamp while sparse wins
    why: str                      # reason sparse was declined ("" if on)


@functools.lru_cache(maxsize=512)
def _plan(layout: str, mesh_axes: tuple[int, int],
          shard_shape: tuple[int, int], radius: int, tile: int,
          crossover: float, enabled: bool) -> SparseShardedPlan:
    h, w = shard_shape

    def off(why: str) -> SparseShardedPlan:
        return SparseShardedPlan(layout, mesh_axes, shard_shape, tile,
                                 crossover, False, "dense:sharded", why)

    if layout not in ("row", "col", "cart"):
        raise ValueError(f"layout must be row|col|cart, got {layout!r}")
    if not enabled:
        return off(f"{ENV_SPARSE_SHARDED}=0")
    if h % tile or w % tile:
        return off(f"tile {tile} does not divide shard {h}x{w}")
    if radius > tile:
        return off(f"radius {radius} exceeds tile {tile}")
    return SparseShardedPlan(
        layout, mesh_axes, shard_shape, tile, crossover, True,
        f"sparse-sharded:{layout}:t{tile}", "")


def plan_sparse_sharded(layout: str, mesh_axes: tuple[int, int],
                        shard_shape: tuple[int, int], radius: int,
                        tile: int, *, crossover: float = 0.5
                        ) -> SparseShardedPlan:
    """Derive (or fetch) the plan for one geometry. The env kill switch
    is part of the cache key — flipping ``MOMP_SPARSE_SHARDED``
    mid-process yields a fresh plan, never a stale cached decision."""
    return _plan(layout, tuple(int(a) for a in mesh_axes),
                 tuple(int(a) for a in shard_shape), int(radius),
                 int(tile), float(crossover), sparse_sharded_enabled())


@functools.lru_cache(maxsize=512)
def _compiled_round(spec: StencilSpec, mesh, layout: str, tile: int,
                    kcap: int, fuse: int, band: int, exchange: bool):
    """Build + jit the collective sparse round for one
    ``(spec, mesh, layout, tile, kcap, fuse, band, exchange)`` tuple.
    Module-level so every :class:`SparseShardedEngine` over the same
    geometry reuses the compile — without this, the bench's min-of-2
    fresh-engine brackets would recompile the whole rung ladder per
    run, and the 2K leg would compile rungs the K leg never reached,
    breaking the chain-differencing cancellation.
    ``StencilSpec`` is a frozen dataclass and ``jax.sharding.Mesh``
    hashes by value, so the key is sound; jit's own trace cache keys
    the shard shape.

    ``fuse`` is the number of steps advanced per dispatch: tiles are
    gathered with a ``radius * fuse``-deep halo (the same data-complete
    margin as a dense fused-halo schedule) and stepped ``fuse`` times
    on device, so the host's per-round sync amortizes over ``fuse``
    steps. Wake flags compare the FINAL state against the PENULTIMATE
    one — an oscillator whose period divides ``fuse`` would look
    settled under an initial-vs-final diff — and the flag bands are
    ``band`` cells deep (``radius *`` the engine's MAX fuse, not this
    round's, so a short tail round still wakes every tile the next
    full-width round could spread into)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from mpi_and_open_mp_tpu.parallel import haloplan

    t, r = tile, spec.radius
    d_halo = r * fuse               # gathered halo / ghost depth
    b = min(band, t)                # wake-flag band depth
    lead = {"row": ("y",), "col": ("x",),
            "cart": (("y", "x"),)}[layout]
    pspec = engine.sharded_pspec(layout, 1)
    coords_spec = P(*lead, None, None)
    nvalid_spec = P(*lead)
    flags_spec = P(*lead, None, None, None)

    def body(block, coords, nvalid):
        coords = coords[0]          # (kcap, 2) local tile coords
        valid = jnp.arange(kcap) < nvalid[0]
        if exchange:
            padded = haloplan.padded_round_block(layout, block, d_halo)
        else:
            padded = haloplan.padded_round_block_local(
                layout, block, d_halo)

        def gather(c):
            return lax.dynamic_slice(
                padded, (c[0] * t, c[1] * t),
                (t + 2 * d_halo, t + 2 * d_halo))

        def advance(p):
            # fuse steps at CONSTANT patch shape — step shrinks the
            # frame by 2r, re-zero-padding restores it, and the valid
            # interior shrinks r per step exactly as a shrinking
            # schedule would. fori_loop (not unrolling) keeps the op
            # count and the XLA compile flat in `fuse`; the carry pair
            # keeps the penultimate frame for the consecutive-state
            # wake diff.
            def one(_, carry):
                _prev, cur = carry
                return cur, jnp.pad(engine.step_padded(spec, cur, jnp),
                                    [(r, r), (r, r)])
            return lax.fori_loop(0, fuse, one, (p, p))

        penult, out = jax.vmap(advance)(jax.vmap(gather)(coords))
        # Center t^2 of the final frame is valid after fuse shrinks of
        # r; the penultimate frame is valid one ring wider, so its
        # center crop is too.
        final = out[:, d_halo:-d_halo, d_halo:-d_halo]
        penult = penult[:, d_halo:-d_halo, d_halo:-d_halo]
        d = valid[:, None, None] & (final != penult)
        flags = jnp.stack([
            jnp.stack([d[:, :b, :b].any((1, 2)),
                       d[:, :b, :].any((1, 2)),
                       d[:, :b, -b:].any((1, 2))], 1),
            jnp.stack([d[:, :, :b].any((1, 2)),
                       d.any((1, 2)),
                       d[:, :, -b:].any((1, 2))], 1),
            jnp.stack([d[:, -b:, :b].any((1, 2)),
                       d[:, -b:, :].any((1, 2)),
                       d[:, -b:, -b:].any((1, 2))], 1),
        ], axis=1)
        # Scatter as a fori_loop so XLA aliases the block through the
        # loop carry (one block copy total, not one per tile). `old`
        # slices the RUNNING block, so an invalid (zero-padded) coord
        # that collides with an already-written tile writes back what
        # is there — a no-op.
        def scatter(i, blk):
            cy, cx = coords[i, 0] * t, coords[i, 1] * t
            old = lax.dynamic_slice(blk, (cy, cx), (t, t))
            new = jnp.where(valid[i], final[i], old)
            return lax.dynamic_update_slice(blk, new, (cy, cx))

        newblk = lax.fori_loop(0, kcap, scatter, block)
        live = jnp.zeros((), bool)
        if layout in ("row", "cart"):
            live |= (newblk[:b, :] != 0).any()
            live |= (newblk[-b:, :] != 0).any()
        if layout in ("col", "cart"):
            live |= (newblk[:, :b] != 0).any()
            live |= (newblk[:, -b:] != 0).any()
        return newblk, flags[None], live.reshape(1)

    smapped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(pspec, coords_spec, nvalid_spec),
        out_specs=(pspec, flags_spec, nvalid_spec),
        check_vma=False)
    return jax.jit(smapped)


class SparseShardedEngine:
    """Advance a SHARDED torus board, stepping only tiles that might
    change — per-round cost proportional to the live area of the whole
    mesh, not the board area of any shard.

    The board is device-resident (sharded by ``layout``); the tile mask
    is host-resident in GLOBAL tile coordinates, and every round is one
    collective dispatch: gather active tiles per shard from the
    exchanged (or zero-sentinel) padded frame, step them ``fuse`` times
    (radius*fuse-deep halos make the round data-complete, amortizing
    the host sync across fuse steps), scatter back, return per-tile
    band flags + a per-shard boundary-live scalar. The per-shard tile
    counts are padded on a pow2 rung ladder (floor 8) so a run compiles
    O(log tiles) programs (x2 for the exchange/skip twin).

    ``engine_stamp``: ``sparse-sharded:<layout>:t<tile>`` while sparse
    rounds ran, ``dense:crossover`` when the active fraction forced
    every round dense, ``dense:sharded`` when the plan is disabled.
    """

    def __init__(self, spec: StencilSpec, board, *, mesh,
                 layout: str = "row", tile: int = 64,
                 crossover: float = 0.5, exchange_skip: bool = True,
                 fuse: int = 16):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding

        if spec.channels != 1:
            raise ValueError(
                f"sparse_sharded: single-channel specs only, "
                f"{spec.name!r} has {spec.channels}")
        board = np.asarray(board, dtype=spec.np_dtype)
        ny, nx = board.shape[-2:]
        py, px = engine.mesh_axes_for(layout, mesh)
        if ny % py or nx % px:
            raise ValueError(
                f"board {(ny, nx)} does not divide mesh "
                f"{dict(mesh.shape)} under layout={layout!r}")
        h, w = ny // py, nx // px
        if h % tile or w % tile:
            raise ValueError(
                f"sparse_sharded: tile {tile} must divide the shard "
                f"{h}x{w}")
        if spec.radius > tile:
            raise ValueError(
                f"sparse_sharded: radius {spec.radius} exceeds tile "
                f"{tile} (one-tile dilation would under-activate)")
        self.spec = spec
        self.mesh = mesh
        self.layout = layout
        self.tile = int(tile)
        self.crossover = float(crossover)
        self.exchange_skip = bool(exchange_skip)
        # Steps per dispatch. The fused halo must stay inside one tile
        # ring (radius * fuse <= tile) so the 3x3 wake flags still name
        # every tile activation can reach in one round.
        self.fuse = max(1, min(int(fuse), self.tile // spec.radius))
        self.shape = (ny, nx)
        self.mesh_axes = (py, px)
        self.shard_shape = (h, w)
        self.plan = plan_sparse_sharded(
            layout, (py, px), (h, w), spec.radius, tile,
            crossover=crossover)
        # Global and per-shard tile grids.
        self.ty, self.tx = ny // tile, nx // tile
        self._mty, self._mtx = h // tile, w // tile
        self._pspec = engine.sharded_pspec(layout, 1)
        self.board = jax.device_put(
            jnp.asarray(board, spec.dtype),
            NamedSharding(mesh, self._pspec))
        # Everything starts active, and the first round exchanges:
        # settledness and dead boundaries are proven, never assumed.
        self.active = np.ones((self.ty, self.tx), dtype=bool)
        self._exchange_needed = True
        self._programs: dict = {}
        self._dense_run = None  # built lazily: crossover may never hit
        self.sparse_steps = 0
        self.dense_steps = 0
        self.settled_steps = 0
        self.tiles_stepped = 0
        self.tiles_skipped = 0
        self.exchange_rounds = 0
        self.exchange_skips = 0
        self._frac_sum = 0.0
        self._frac_n = 0

    # -- observability -----------------------------------------------------
    @property
    def active_frac(self) -> float:
        return float(self.active.mean())

    @property
    def mean_active_frac(self) -> float:
        return self._frac_sum / self._frac_n if self._frac_n else 1.0

    @property
    def engine_stamp(self) -> str:
        if not self.plan.enabled:
            return "dense:sharded"
        if self.dense_steps and not self.sparse_steps:
            return "dense:crossover"
        return self.plan.engine

    def counters(self) -> dict:
        """Bench/ledger sub-object: step mix, skip accounting, and the
        exchange-round/skip split the tests assert a delta on."""
        return {
            "sparse_steps": self.sparse_steps,
            "dense_steps": self.dense_steps,
            "settled_steps": self.settled_steps,
            "tiles_stepped": self.tiles_stepped,
            "tiles_skipped": self.tiles_skipped,
            "exchange_rounds": self.exchange_rounds,
            "exchange_skips": self.exchange_skips,
            "tile": self.tile,
            "fuse": self.fuse,
            "crossover": self.crossover,
            "active_frac": round(self.mean_active_frac, 6),
        }

    def snapshot(self) -> np.ndarray:
        return np.asarray(self.board)

    # -- stepping ----------------------------------------------------------
    def step(self, n: int = 1):
        n = int(n)
        while n > 0:
            f = min(self.fuse, n)
            self._round(f)
            n -= f
        return self.board

    def _round(self, f: int) -> None:
        frac = self.active.mean()
        self._frac_sum += float(frac)
        self._frac_n += 1
        if not self.plan.enabled or frac > self.crossover:
            self._dense_round(f)
            return
        self.sparse_steps += f
        idx = np.argwhere(self.active)
        k = len(idx)
        self.tiles_stepped += k
        self.tiles_skipped += self.ty * self.tx - k
        if k == 0:
            # Fully settled: nothing can change, by construction — no
            # dispatch, no exchange, and the board's boundary liveness
            # is unchanged so the standing exchange flag stays valid.
            self.settled_steps += f
            return
        self._sparse_round(idx, f)

    # -- the sparse collective round ---------------------------------------

    def _bucket(self, idx: np.ndarray):
        """Bucket global active-tile coords by owning shard: returns
        ``(coords, nvalid, per_shard)`` where ``coords`` is
        ``(nshards, kcap, 2)`` int32 LOCAL tile coords (zero-padded),
        ``nvalid`` the per-shard valid counts, and ``per_shard`` the
        host-side global-coord lists in gather order."""
        py, px = self.mesh_axes
        nshards = {"row": py, "col": px, "cart": py * px}[self.layout]
        per_shard: list[list[tuple[int, int]]] = [
            [] for _ in range(nshards)]
        for gy, gx in idx:
            sy, sx = gy // self._mty, gx // self._mtx
            s = {"row": sy, "col": sx, "cart": sy * px + sx}[self.layout]
            per_shard[s].append((int(gy), int(gx)))
        # Rung ladder coarser than sparse.py's: pow2 with a floor of 8.
        # Each rung is a separate shard_map compile, and a rung first
        # reached late in a long run would land its compile inside the
        # timed region — over-padding a handful of 64^2 tile steps is
        # far cheaper than another trace+compile.
        k = max(1, max(len(p) for p in per_shard))
        kcap = 8
        while kcap < k:
            kcap *= 2
        coords = np.zeros((nshards, kcap, 2), np.int32)
        nvalid = np.zeros((nshards,), np.int32)
        for s, tiles in enumerate(per_shard):
            nvalid[s] = len(tiles)
            for i, (gy, gx) in enumerate(tiles):
                coords[s, i] = (gy % self._mty, gx % self._mtx)
        return coords, nvalid, per_shard

    def _sparse_round(self, idx: np.ndarray, f: int) -> None:
        exchange = self._exchange_needed or not self.exchange_skip
        coords, nvalid, per_shard = self._bucket(idx)
        prog = self._program(coords.shape[1], f, exchange)
        self.board, flags, live = prog(self.board, coords, nvalid)
        if exchange:
            self.exchange_rounds += 1
        else:
            self.exchange_skips += 1
        # flags/live are tiny ((nshards, kcap, 3, 3) bools + nshards
        # scalars); fetching them is the host's per-round sync point —
        # one combined fetch, the board itself stays device-resident.
        import jax

        flags, live = jax.device_get((flags, live))
        self._exchange_needed = bool(live.any())
        nxt = np.zeros((self.ty, self.tx), dtype=bool)
        ty, tx = self.ty, self.tx
        for s, tiles in enumerate(per_shard):
            for i, (gy, gx) in enumerate(tiles):
                f = flags[s, i]
                if not f[1, 1]:
                    continue  # tile came back bit-identical: sleeps
                for dy in (-1, 0, 1):
                    for dx in (-1, 0, 1):
                        if f[dy + 1, dx + 1]:
                            nxt[(gy + dy) % ty, (gx + dx) % tx] = True
        self.active = nxt

    def _program(self, kcap: int, f: int, exchange: bool):
        """The jitted shard_map round for one (kcap, fuse, exchange)
        triple — the engine's whole compiled-program space is the
        kcap rung ladder times the exchange/zero-sentinel twin
        (times a tail-fuse rung when ``n % fuse != 0``). Programs are
        cached at MODULE level (``_compiled_round``) so fresh engine
        instances over the same geometry — the bench's honesty bracket
        re-runs, the tuner's per-candidate engines — share compiles."""
        key = (kcap, f, exchange)
        if key not in self._programs:
            self._programs[key] = _compiled_round(
                self.spec, self.mesh, self.layout, self.tile,
                kcap, f, self.spec.radius * self.fuse, exchange)
        return self._programs[key]

    # -- the dense-crossover rung ------------------------------------------

    def _dense_round(self, f: int) -> None:
        import jax

        self.dense_steps += f
        if self._dense_run is None:
            run, _plan_ = engine.make_sharded_runner(
                self.spec, self.mesh, self.layout, self.shape,
                fuse_steps=1)
            ty, tx, t = self.ty, self.tx, self.tile
            diff = jax.jit(lambda a, b: (a != b).reshape(
                ty, t, tx, t).any(axis=(1, 3)))
            self._dense_run = (run, diff)
        run, diff = self._dense_run
        # The mask rebuild diffs the LAST step pair, not first-vs-final
        # — an oscillator whose period divides f would look settled
        # under the cumulative diff (same trap as the fused wake).
        prev = run(self.board, f - 1) if f > 1 else self.board
        new = run(prev, 1)
        changed = np.asarray(diff(new, prev))
        self.board = new
        self.active = _dilate(changed)
        # Conservative: the dense round computed no boundary-live flag.
        self._exchange_needed = True
