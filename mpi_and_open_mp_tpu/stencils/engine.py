"""Generic stencil step generators: one spec, every fast path.

Every path here derives from the SAME offset table (nonzero ``weights``
entries in row-major order), so the NumPy oracle and the jitted fast
paths perform the aggregation in the same order — bit-exact for integer
dtypes, reproducibly close for floats (XLA may still fuse/reassociate,
which is why float parity gates use a tight ``allclose`` instead of
``array_equal``; see ``tests/test_stencils.py``).

Paths:

* :func:`step_roll` — torus step via shifts on the last two axes
  (channels ride the leading axis untouched). The radius-1 all-ones box
  (Life's neighbourhood) takes the separable row-sum/col-sum form —
  exactly ``ops.life_ops.life_step_roll``'s shape, 4 shifts instead
  of 8.
* :func:`step_padded` — interior step over a board carrying a
  ``radius``-wide halo on the last two axes; pure slicing, no wrap, so
  it drops straight into shard-local halo blocks and Pallas kernels.
* :func:`step_numpy` — the derived NumPy oracle (plain per-offset roll
  loop; specs may pin an independent ``oracle_step`` instead).
* :func:`run_roll` — jitted ``fori_loop`` chain of :func:`step_roll`
  for benchmarking (n is a runtime scalar: one compile per board shape).

Engine families (PR 20): every path above walks the same
``(2r+1)^2 - 1`` offset table — O(r^2) work per cell. Two families
restructure the aggregation itself for wide-radius float kernels:

* ``sep`` (:func:`step_sep` / :func:`step_padded_sep`) — the weight
  table factors into ``rank`` row x col passes (``spec.separable_rank``,
  SVD-exact); O(rank * r) rolls per cell. Exact when the factorization
  residual is zero; REFUSED (ValueError) otherwise.
* ``fft`` (:func:`step_fft` / :func:`step_padded_fft`) — the torus
  aggregate is a circular convolution, computed via ``rfft2`` with a
  cached kernel transform; O(log n) per cell, radius-independent. Float
  only, periodic boundary native. The parity GATE owns the float
  tolerance (:func:`parity_tol_for`); the engine itself never rounds.

``MOMP_ENGINE_FAMILY`` pins one family (offset|sep|fft) — the offset
walk always stays available as the safety fallback.
"""

from __future__ import annotations

import functools
import os

import numpy as np

from .spec import BOX3, StencilSpec, _separable_factors


@functools.lru_cache(maxsize=None)
def offsets(spec: StencilSpec) -> tuple:
    """Nonzero ``(dy, dx, weight)`` neighbour displacements, row-major.
    A neighbour at displacement ``(dy, dx)`` contributes
    ``weight * board[y + dy, x + dx]`` to the aggregate."""
    r = spec.radius
    out = []
    for j, row in enumerate(spec.weights):
        for i, w in enumerate(row):
            if w:
                out.append((j - r, i - r, w))
    return tuple(out)


def _is_box3(spec: StencilSpec) -> bool:
    return spec.radius == 1 and spec.weights == BOX3


def _shift(field, dy, dx, xp):
    # roll(-dy) moves the value at y+dy into row y (and likewise for x).
    out = field
    if dy:
        out = xp.roll(out, -dy, axis=-2)
    if dx:
        out = xp.roll(out, -dx, axis=-1)
    return out


def aggregate_roll(spec: StencilSpec, board, xp):
    """The weighted neighbour sum of a torus board (last two axes)."""
    field = board if spec.pre is None else spec.pre(board, xp)
    if _is_box3(spec):
        rows = field + xp.roll(field, 1, axis=-2) + xp.roll(field, -1, axis=-2)
        return (rows + xp.roll(rows, 1, axis=-1)
                + xp.roll(rows, -1, axis=-1) - field)
    agg = None
    for dy, dx, w in offsets(spec):
        term = _shift(field, dy, dx, xp)
        if w != 1:
            term = term * w
        agg = term if agg is None else agg + term
    return agg


def step_roll(spec: StencilSpec, board, xp=None):
    """One torus step via rolls; works under numpy or jax.numpy."""
    if xp is None:
        import jax.numpy as xp  # noqa: F811
    return spec.update(board, aggregate_roll(spec, board, xp), xp)


def step_padded(spec: StencilSpec, padded, xp=None):
    """One interior step over a halo-padded block.

    ``padded`` carries a ``spec.radius``-deep halo on the last two axes;
    the result is the updated interior (halo trimmed). Slicing only —
    usable inside Pallas kernels and shard_map bodies unchanged.
    """
    if xp is None:
        import jax.numpy as xp  # noqa: F811
    r = spec.radius
    h = padded.shape[-2] - 2 * r
    w = padded.shape[-1] - 2 * r
    field = padded if spec.pre is None else spec.pre(padded, xp)
    center = padded[..., r:r + h, r:r + w]
    if _is_box3(spec):
        rows = (field[..., 0:h, :] + field[..., 1:h + 1, :]
                + field[..., 2:h + 2, :])
        agg = (rows[..., 0:w] + rows[..., 1:w + 1] + rows[..., 2:w + 2]
               - field[..., 1:h + 1, 1:w + 1])
    else:
        agg = None
        for dy, dx, wt in offsets(spec):
            term = field[..., r + dy:r + dy + h, r + dx:r + dx + w]
            if wt != 1:
                term = term * wt
            agg = term if agg is None else agg + term
    return spec.update(center, agg, xp)


def step_numpy(spec: StencilSpec, board: np.ndarray) -> np.ndarray:
    """The spec's NumPy oracle step (independent ``oracle_step`` when
    the spec pins one, else the derived per-offset roll loop)."""
    board = np.asarray(board, dtype=spec.np_dtype)
    if spec.oracle_step is not None:
        return spec.oracle_step(board)
    field = board if spec.pre is None else spec.pre(board, np)
    agg = None
    for dy, dx, w in offsets(spec):
        term = _shift(field, dy, dx, np)
        if w != 1:
            term = term * w
        agg = term if agg is None else agg + term
    return np.asarray(spec.update(board, agg, np), dtype=spec.np_dtype)


def oracle_run(spec: StencilSpec, board: np.ndarray, n: int) -> np.ndarray:
    out = np.asarray(board, dtype=spec.np_dtype)
    for _ in range(int(n)):
        out = step_numpy(spec, out)
    return out


def parity_ok(spec: StencilSpec, got, want, *, rtol=1e-5, atol=1e-6) -> bool:
    """The per-spec parity predicate: exact for integer dtypes, tight
    allclose for floats (XLA vs NumPy may reassociate float sums)."""
    got = np.asarray(got)
    want = np.asarray(want)
    if got.shape != want.shape:
        return False
    if spec.is_float:
        return bool(np.allclose(got, want, rtol=rtol, atol=atol))
    return bool(np.array_equal(got, want))


@functools.lru_cache(maxsize=None)
def _run_roll_jit(spec: StencilSpec):
    import jax
    import jax.numpy as jnp
    from jax import lax

    def run(board, n):
        return lax.fori_loop(
            0, n, lambda _, b: step_roll(spec, b, jnp), board)

    return jax.jit(run)


def run_roll(spec: StencilSpec, board, n: int):
    """``n`` chained :func:`step_roll` steps as ONE dispatch (jitted
    fori_loop; ``n`` is a runtime scalar so run-length differencing
    reuses a single compiled program per board shape)."""
    return _run_roll_jit(spec)(board, n)


@functools.lru_cache(maxsize=None)
def _run_roll_batch_jit(spec: StencilSpec):
    import jax
    import jax.numpy as jnp
    from jax import lax

    # vmap over the leading stack axis so multi-channel rules (which
    # index channels as center[0]/center[1]) see one board at a time.
    vstep = jax.vmap(lambda b: step_roll(spec, b, jnp))

    def run(stack, n):
        return lax.fori_loop(0, n, lambda _, s: vstep(s), stack)

    return jax.jit(run)


def run_roll_batch(spec: StencilSpec, stack, n: int):
    """``n`` chained torus steps of a STACK of boards as one dispatch —
    the generic serve-layer batch engine (``n`` is a runtime scalar,
    matching the life batch engines' calling convention, so a bucket
    compiles once per stack shape)."""
    return _run_roll_batch_jit(spec)(stack, n)


def pallas_batch_supported(spec: StencilSpec, shape) -> bool:
    """Whether the per-spec Pallas padded kernel can serve a batched
    ``(B, ny, nx)`` stack of this spec: single-channel rules only. The
    kernel rides the stack through the padded block's leading axis, and
    a multi-channel update (which indexes ``center[0]``/``center[1]``)
    would misread that axis as channels — gray_scott stays on the
    vmapped roll engine."""
    return int(spec.channels) == 1 and len(tuple(shape)) == 3


@functools.lru_cache(maxsize=None)
def _run_padded_pallas_batch_jit(spec: StencilSpec):
    import jax
    import jax.numpy as jnp
    from jax import lax

    from mpi_and_open_mp_tpu.ops import pallas_life

    r = spec.radius

    def step(stack):
        padded = jnp.pad(stack, ((0, 0), (r, r), (r, r)), mode="wrap")
        return pallas_life.stencil_step_padded_pallas(spec, padded)

    def run(stack, n):
        return lax.fori_loop(0, n, lambda _, s: step(s), stack)

    return jax.jit(run)


def run_padded_pallas_batch(spec: StencilSpec, stack, n: int):
    """``n`` chained steps of a single-channel stack through the
    spec-generic Pallas padded kernel (``ops.pallas_life.
    stencil_step_padded_pallas``): wrap-pad the halo, one kernel launch
    per step, same runtime-scalar ``n`` contract as
    :func:`run_roll_batch`. Over-VMEM blocks degrade to the compiled
    jnp interior step inside the same loop — the caller never has to
    re-plan. Gate callers on :func:`pallas_batch_supported`."""
    return _run_padded_pallas_batch_jit(spec)(stack, n)


# ------------------------------------------------------- sharded halo steps
#
# The engine-level sharded entry: shard_map halo rounds driven by a
# persistent HaloPlan (parallel.haloplan) — the overlap/sequential
# schedule decision is the PLAN's, derived once per geometry, so the
# tuner, the bench A/B and the model layer all measure the same two
# schedules instead of three ad-hoc code paths.


def _sharded_pspec(layout: str, channels: int):
    """PartitionSpec for a (channels-leading) board under ``layout`` —
    the engine-side twin of ``models.life._layout_spec``."""
    from jax.sharding import PartitionSpec as P

    axes = {"row": ("y", None), "col": (None, "x"),
            "cart": ("y", "x")}[layout]
    return P(None, *axes) if channels > 1 else P(*axes)


#: Public alias — the sparse-sharded engine and the tuner place boards
#: with the same spec the sharded runner uses, by name.
sharded_pspec = _sharded_pspec


def mesh_axes_for(layout: str, mesh) -> tuple[int, int]:
    """(py, px) shard counts per board axis under ``layout``."""
    py = mesh.shape.get("y", 1) if layout in ("row", "cart") else 1
    px = mesh.shape.get("x", 1) if layout in ("col", "cart") else 1
    return py, px


def fused_steps_valid(spec: StencilSpec, shard_shape: tuple[int, int],
                      fuse_steps: int) -> bool:
    """Whether ``fuse_steps`` legal-fuses on this shard: the halo depth
    ``fuse_steps * radius`` cannot exceed the smallest shard extent (a
    halo deeper than the shard it pads would wrap a neighbour's
    neighbour)."""
    return fuse_steps * spec.radius <= min(shard_shape)


def make_sharded_runner(spec: StencilSpec, mesh, layout: str,
                        shape: tuple[int, int], *, fuse_steps: int = 1,
                        boundary_steps: int | None = None,
                        overlap: bool | None = None,
                        family: str = "offset"):
    """Build ``(run, plan)`` for a sharded board: ``run(board, n)``
    advances ``n`` torus steps via plan-scheduled shard_map halo rounds.

    ``overlap=None`` lets the plan decide (geometry + the
    ``MOMP_HALO_OVERLAP`` kill switch); ``False`` forces the sequential
    schedule — the A/B baseline leg — and stamps ``why`` accordingly.
    ``boundary_steps`` (default: coupled) partitions each round's
    boundary into shallower per-edge sub-exchanges; it must divide
    ``fuse_steps``. ``family`` picks the per-shard aggregation engine
    (:func:`step_padded_family`) — the halo plan itself is family-blind:
    every family consumes the same ``radius``-deep ghosts. ``run`` is
    jit-cached per static ``n`` (remainder rounds get their own
    smaller-depth plan — coupled boundary, and possibly a legal
    sequential degrade — even when the main rounds overlap partitioned).
    """
    import dataclasses as _dc
    import functools as _ft

    import jax
    import jax.numpy as jnp
    from jax import lax

    from mpi_and_open_mp_tpu.parallel import haloplan

    if family == "sep":
        _require_sep(spec)
    elif family == "fft":
        _require_fft(spec)
    elif family != "offset":
        raise ValueError(f"unknown engine family {family!r}; "
                         f"expected one of {ENGINE_FAMILIES}")
    ny, nx = shape
    py, px = mesh_axes_for(layout, mesh)
    if ny % py or nx % px:
        raise ValueError(
            f"board {shape} does not divide mesh {dict(mesh.shape)} "
            f"under layout={layout!r}")
    shard = (ny // py, nx // px)
    if not fused_steps_valid(spec, shard, fuse_steps):
        raise ValueError(
            f"fuse_steps={fuse_steps} x radius {spec.radius} exceeds "
            f"shard {shard}")

    def plan_for(k: int) -> "haloplan.HaloPlan":
        bs = boundary_steps if k == fuse_steps else None
        p = haloplan.plan_halo(layout, (py, px), shard, spec.radius, k,
                               boundary_steps=bs,
                               channels=spec.channels)
        if overlap is False and p.overlap:
            p = _dc.replace(p, overlap=False, engine="seq:halo",
                            why="forced sequential (A/B baseline)")
        return p

    plan = plan_for(fuse_steps)
    pspec = _sharded_pspec(layout, spec.channels)

    def step_fn(padded):
        return step_padded_family(spec, padded, family, jnp)

    def make_smapped(k: int):
        pk = plan_for(k)
        return jax.shard_map(
            lambda b: haloplan.fused_step(pk, step_fn, b),
            mesh=mesh, in_specs=pspec, out_specs=pspec, check_vma=False)

    smapped_k = make_smapped(fuse_steps)
    smapped_cache = {fuse_steps: smapped_k}

    @_ft.partial(jax.jit, static_argnums=1)
    def run(board, n):
        rounds, rem = divmod(n, fuse_steps)
        board = lax.fori_loop(0, rounds, lambda _, b: smapped_k(b), board)
        if rem:
            if rem not in smapped_cache:
                smapped_cache[rem] = make_smapped(rem)
            board = smapped_cache[rem](board)
        return board

    return run, plan


def run_sharded(spec: StencilSpec, board, n: int, *, mesh,
                layout: str = "row", fuse_steps: int = 1,
                boundary_steps: int | None = None,
                overlap: bool | None = None, family: str = "offset"):
    """Advance ``n`` sharded steps under a ``halo.overlap`` /
    ``halo.seq`` trace span (host-level: the span brackets dispatch
    through completion; schedule hooks never enter the jitted program).
    Places the board on the mesh if the caller has not. Returns the
    advanced board; the plan rides on ``run_sharded.last_plan`` for
    provenance stamping."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from mpi_and_open_mp_tpu.obs import trace
    from mpi_and_open_mp_tpu.utils.timing import anchor_sync

    run, plan = make_sharded_runner(
        spec, mesh, layout, tuple(board.shape[-2:]),
        fuse_steps=fuse_steps, boundary_steps=boundary_steps,
        overlap=overlap, family=family)
    run_sharded.last_plan = plan
    sharding = NamedSharding(mesh, _sharded_pspec(layout, spec.channels))
    board = jax.device_put(jnp.asarray(board, spec.dtype), sharding)
    name = "halo.overlap" if plan.overlap else "halo.seq"
    with trace.span(name, engine=plan.engine, layout=layout,
                    workload=spec.name, steps=int(n),
                    fuse_steps=int(fuse_steps), family=family):
        out = run(board, int(n))
        anchor_sync(out)
    return out


run_sharded.last_plan = None


# --------------------------------------------------------- engine families
#
# PR 20: the first aggregation paths whose cost model is NOT the offset
# table. Everything above this line walks (2r+1)^2 - 1 offsets; the
# separable family walks 2 * rank * (2r+1) row/col passes and the FFT
# family is radius-independent. The tuner races all three; the plan
# store persists the winner; MOMP_ENGINE_FAMILY pins one for triage.

#: Closed vocabulary — ledger keys, sentinel provenance and the bench
#: crossover table all speak these three names.
ENGINE_FAMILIES = ("offset", "sep", "fft")

#: Below this radius the FFT's setup constant cannot win — the kernel
#: transform multiply costs the same at radius 1 as radius 16, so the
#: legality gate keeps narrow specs off the candidate list entirely.
FFT_MIN_RADIUS = 4

#: Kill switch: pin one family (offset|sep|fft). The offset walk is
#: always allowed regardless — pinning selects a family, it never
#: removes the safety fallback.
ENV_FAMILY = "MOMP_ENGINE_FAMILY"

#: Gate-owned parity tolerances per family. The ENGINES are exact (sep)
#: or correctly-rounded-transform (fft); what differs is how float32
#: noise amplifies through the update over a parity window, so the GATE
#: — not the engine — owns the slack. offset keeps parity_ok's default.
_FAMILY_TOL = {
    "offset": {},
    "sep": {"rtol": 1e-4, "atol": 1e-5},
    "fft": {"rtol": 1e-3, "atol": 1e-4},
}


def parity_tol_for(family: str) -> dict:
    """kwargs for :func:`parity_ok` when gating ``family`` output."""
    if family not in ENGINE_FAMILIES:
        raise ValueError(f"unknown engine family {family!r}; "
                         f"expected one of {ENGINE_FAMILIES}")
    return dict(_FAMILY_TOL[family])


def family_pinned() -> str | None:
    """The ``MOMP_ENGINE_FAMILY`` pin, validated; None when unset."""
    v = os.environ.get(ENV_FAMILY, "").strip()
    if not v:
        return None
    if v not in ENGINE_FAMILIES:
        raise ValueError(
            f"{ENV_FAMILY}={v!r}: expected one of {ENGINE_FAMILIES}")
    return v


def family_allowed(family: str) -> bool:
    """Whether ``family`` may be enumerated/served under the pin.
    ``offset`` is always allowed — the pin narrows, never strands."""
    pin = family_pinned()
    return pin is None or family == pin or family == "offset"


def family_for_path(path: str) -> str:
    """Engine family of a tuner/plan path string (``stencil:sep`` ->
    ``sep``; everything else is the offset walk)."""
    if path.endswith(":sep"):
        return "sep"
    if path.endswith(":fft"):
        return "fft"
    return "offset"


def separable_supported(spec: StencilSpec) -> bool:
    """Whether the sep family can serve this spec exactly (the weight
    table factors at rank <= radius with zero residual)."""
    return spec.separable_rank is not None


def fft_supported(spec: StencilSpec) -> bool:
    """FFT legality: float dtype (the transform is real-to-complex),
    native periodic boundary, and radius past the setup constant."""
    return (spec.is_float and spec.boundary == "torus"
            and spec.radius >= FFT_MIN_RADIUS)


@functools.lru_cache(maxsize=None)
def _sep_factors(spec: StencilSpec):
    """The spec's row x col factor pairs as plain-float tuples (weak
    scalars: multiplying a float32 field keeps float32 under both
    numpy and jax.numpy), or None when the table does not factor."""
    f = _separable_factors(spec.weights, spec.radius)
    if f is None:
        return None
    return tuple((tuple(float(x) for x in u), tuple(float(x) for x in v))
                 for u, v in f)


def _require_sep(spec: StencilSpec):
    facs = _sep_factors(spec)
    if facs is None:
        raise ValueError(
            f"stencil {spec.name!r}: weights do not factor at rank <= "
            f"radius ({spec.radius}); separable family refused")
    return facs


def _require_fft(spec: StencilSpec):
    if not spec.is_float:
        raise ValueError(
            f"stencil {spec.name!r}: fft family needs a float dtype, "
            f"got {spec.dtype}")
    if spec.boundary != "torus":
        raise ValueError(
            f"stencil {spec.name!r}: fft family is periodic-native; "
            f"boundary {spec.boundary!r} unsupported")


def aggregate_sep(spec: StencilSpec, board, xp):
    """The torus neighbour sum as ``rank`` row-pass x col-pass sweeps:
    ``agg = sum_k (sum_j u_k[j] roll_y) conv (sum_i v_k[i] roll_x)`` —
    2 * rank * (2r+1) rolls instead of (2r+1)^2 - 1."""
    facs = _require_sep(spec)
    field = board if spec.pre is None else spec.pre(board, xp)
    r = spec.radius
    agg = None
    for u, v in facs:
        rows = None
        for j, uw in enumerate(u):
            if not uw:
                continue
            term = xp.roll(field, r - j, axis=-2) if j != r else field
            if uw != 1:
                term = term * uw
            rows = term if rows is None else rows + term
        part = None
        for i, vw in enumerate(v):
            if not vw:
                continue
            term = xp.roll(rows, r - i, axis=-1) if i != r else rows
            if vw != 1:
                term = term * vw
            part = term if part is None else part + term
        agg = part if agg is None else agg + part
    return agg


def step_sep(spec: StencilSpec, board, xp=None):
    """One torus step via the separable family; raises ValueError on
    non-factorizable weights (the refusal is the contract — a silent
    low-rank APPROXIMATION would poison every parity gate above it)."""
    if xp is None:
        import jax.numpy as xp  # noqa: F811
    return spec.update(board, aggregate_sep(spec, board, xp), xp)


@functools.lru_cache(maxsize=None)
def _fft_kernel_rfft(spec: StencilSpec, ny: int, nx: int):
    """rfft2 of the spec's kernel image on an ``ny x nx`` torus. The
    aggregate is a cross-correlation, so the convolution kernel is the
    offset table point-reflected: ``k[(-dy) % ny, (-dx) % nx] = w``
    (``+=``: on boards narrower than the table, wrapped taps pile up
    exactly like the roll path wraps them). complex64 so float32
    pipelines stay float32 end to end."""
    k = np.zeros((ny, nx), np.float64)
    for dy, dx, w in offsets(spec):
        k[(-dy) % ny, (-dx) % nx] += w
    return np.fft.rfft2(k).astype(np.complex64)


def step_fft(spec: StencilSpec, board, xp=None):
    """One torus step via the FFT family: rfft2 of the field times the
    cached kernel transform, inverse-transformed back. Works under
    numpy and jax.numpy; float specs only (refused otherwise)."""
    if xp is None:
        import jax.numpy as xp  # noqa: F811
    _require_fft(spec)
    field = board if spec.pre is None else spec.pre(board, xp)
    ny, nx = int(field.shape[-2]), int(field.shape[-1])
    kf = _fft_kernel_rfft(spec, ny, nx)
    agg = xp.fft.irfft2(xp.fft.rfft2(field) * kf, s=(ny, nx))
    agg = agg.astype(board.dtype)
    return spec.update(board, agg, xp)


def step_padded_sep(spec: StencilSpec, padded, xp=None):
    """Interior separable step over a halo-padded block (slicing only,
    same contract as :func:`step_padded`): row passes slice ``[j:j+h]``,
    col passes slice ``[i:i+w]`` — drops into the PR 15 halo plans with
    ``radius``-deep ghosts unchanged."""
    if xp is None:
        import jax.numpy as xp  # noqa: F811
    facs = _require_sep(spec)
    r = spec.radius
    h = padded.shape[-2] - 2 * r
    w = padded.shape[-1] - 2 * r
    field = padded if spec.pre is None else spec.pre(padded, xp)
    center = padded[..., r:r + h, r:r + w]
    agg = None
    for u, v in facs:
        rows = None
        for j, uw in enumerate(u):
            if not uw:
                continue
            term = field[..., j:j + h, :]
            if uw != 1:
                term = term * uw
            rows = term if rows is None else rows + term
        part = None
        for i, vw in enumerate(v):
            if not vw:
                continue
            term = rows[..., i:i + w]
            if vw != 1:
                term = term * vw
            part = term if part is None else part + term
        agg = part if agg is None else agg + part
    return spec.update(center, agg, xp)


def step_padded_fft(spec: StencilSpec, padded, xp=None):
    """Interior FFT step over a halo-padded block: circular convolution
    on the PADDED extent, interior crop. For output rows ``y`` in
    ``[r, r+h)`` and taps ``dy`` in ``[-r, r]``, ``y + dy`` never wraps
    the padded block — the circular result equals the linear gather
    exactly, so halo semantics match :func:`step_padded` bit-for-float."""
    if xp is None:
        import jax.numpy as xp  # noqa: F811
    _require_fft(spec)
    r = spec.radius
    h = padded.shape[-2] - 2 * r
    w = padded.shape[-1] - 2 * r
    H, W = int(padded.shape[-2]), int(padded.shape[-1])
    field = padded if spec.pre is None else spec.pre(padded, xp)
    kf = _fft_kernel_rfft(spec, H, W)
    full = xp.fft.irfft2(xp.fft.rfft2(field) * kf, s=(H, W))
    agg = full[..., r:r + h, r:r + w].astype(padded.dtype)
    center = padded[..., r:r + h, r:r + w]
    return spec.update(center, agg, xp)


def step_family(spec: StencilSpec, board, family: str = "offset",
                xp=None):
    """One torus step through the named engine family."""
    if family == "offset":
        return step_roll(spec, board, xp)
    if family == "sep":
        return step_sep(spec, board, xp)
    if family == "fft":
        return step_fft(spec, board, xp)
    raise ValueError(f"unknown engine family {family!r}; "
                     f"expected one of {ENGINE_FAMILIES}")


def step_padded_family(spec: StencilSpec, padded, family: str = "offset",
                       xp=None):
    """One interior halo-padded step through the named engine family."""
    if family == "offset":
        return step_padded(spec, padded, xp)
    if family == "sep":
        return step_padded_sep(spec, padded, xp)
    if family == "fft":
        return step_padded_fft(spec, padded, xp)
    raise ValueError(f"unknown engine family {family!r}; "
                     f"expected one of {ENGINE_FAMILIES}")


@functools.lru_cache(maxsize=None)
def _run_family_jit(spec: StencilSpec, family: str):
    import jax
    import jax.numpy as jnp
    from jax import lax

    def run(board, n):
        return lax.fori_loop(
            0, n, lambda _, b: step_family(spec, b, family, jnp), board)

    return jax.jit(run)


def run_family(spec: StencilSpec, board, n: int, family: str = "offset"):
    """``n`` chained steps of one engine family as ONE dispatch — the
    family twin of :func:`run_roll` (same runtime-scalar ``n``, same
    chain-differencing contract). Refusals (non-factorizable sep, int
    fft) raise eagerly, before any compile."""
    if family == "offset":
        return run_roll(spec, board, n)
    if family == "sep":
        _require_sep(spec)
    elif family == "fft":
        _require_fft(spec)
    else:
        raise ValueError(f"unknown engine family {family!r}; "
                         f"expected one of {ENGINE_FAMILIES}")
    return _run_family_jit(spec, family)(board, n)


@functools.lru_cache(maxsize=None)
def _run_family_batch_jit(spec: StencilSpec, family: str):
    import jax
    import jax.numpy as jnp
    from jax import lax

    vstep = jax.vmap(lambda b: step_family(spec, b, family, jnp))

    def run(stack, n):
        return lax.fori_loop(0, n, lambda _, s: vstep(s), stack)

    return jax.jit(run)


def run_family_batch(spec: StencilSpec, stack, n: int,
                     family: str = "offset"):
    """Batched :func:`run_family` — the serve-layer engine behind the
    ``batch:stencil-sep``/``batch:stencil-fft`` rungs, same calling
    convention as :func:`run_roll_batch`."""
    if family == "offset":
        return run_roll_batch(spec, stack, n)
    if family == "sep":
        _require_sep(spec)
    elif family == "fft":
        _require_fft(spec)
    else:
        raise ValueError(f"unknown engine family {family!r}; "
                         f"expected one of {ENGINE_FAMILIES}")
    return _run_family_batch_jit(spec, family)(stack, n)
