"""Long-context attention CLI: drive the sequence-parallel layer.

The usable surface over ``parallel.context`` (ring + Ulysses attention) —
runs one forward pass of the chosen variant on an ``sp`` ring mesh,
verifies it against the single-device oracle (the same parity discipline
as the Life engine; skippable for oracle-infeasible lengths), and prints
elapsed seconds on stdout — the framework's standard timing contract
(cf. ``3-life/life_mpi.c:64-67``).
"""

from __future__ import annotations

import argparse
import functools
import sys
import time

import numpy as np

from mpi_and_open_mp_tpu.apps._common import (
    add_platform_args, apply_platform_args, is_primary)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="mpi_and_open_mp_tpu.apps.attention")
    p.add_argument("--variant", choices=("ring", "ulysses", "flash"),
                   default="ring",
                   help="sharded ring / sharded all-to-all / single-"
                   "device flash-chunked (no mesh)")
    p.add_argument("--seq", type=int, default=8192)
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--head-dim", type=int, default=64)
    p.add_argument("--causal", action="store_true")
    p.add_argument("--grad", action="store_true",
                   help="time the backward pass too (both the chunked "
                   "path and the multi-device ring take a flash "
                   "custom_vjp backward, O(seq*d) residuals)")
    p.add_argument("--kv-heads", type=int, default=None,
                   help="GQA/MQA: fewer K/V heads than query heads")
    p.add_argument("--devices", type=int, default=None,
                   help="sp ring size (default: all local devices)")
    p.add_argument("--ring-layout", choices=("contiguous", "zigzag"),
                   default="contiguous",
                   help="ring variant only: zigzag = striped causal-"
                   "load-balanced token layout (the driver permutes "
                   "operands in and outputs back out, so the parity "
                   "check still runs in natural order)")
    p.add_argument("--dtype", choices=("float32", "bfloat16"),
                   default="bfloat16")
    p.add_argument("--no-check", action="store_true",
                   help="skip the oracle parity check (long sequences)")
    p.add_argument("--engine", choices=("auto", "jnp"), default="auto",
                   help="auto = on TPU, eligible shapes dispatch to the "
                   "bundled Pallas flash kernel; jnp = force the "
                   "chunked XLA engine (same as MOMP_TPU_FLASH=0)")
    p.add_argument("--seed", type=int, default=0)
    add_platform_args(p)
    args = p.parse_args(argv)
    apply_platform_args(args)

    import jax
    import jax.numpy as jnp

    from mpi_and_open_mp_tpu.parallel import context, mesh as mesh_lib

    if args.engine == "jnp":
        context.disable_tpu_flash()

    if args.variant == "flash":
        if args.devices not in (None, 1):
            p.error(f"--variant flash is single-device; --devices "
                    f"{args.devices} would be silently ignored (use "
                    "--variant ring/ulysses for a sharded run)")
        mesh = mesh_lib.make_mesh_1d(1, axis=context.AXIS_SP)  # size only

        def fn(q, k, v, mesh=None, causal=False):
            return context.flash_attention(q, k, v, causal=causal)
    else:
        mesh = mesh_lib.make_mesh_1d(args.devices, axis=context.AXIS_SP)
        fn = (context.ring_attention if args.variant == "ring"
              else context.ulysses_attention)
    zig = args.ring_layout != "contiguous"
    if zig:
        if args.variant != "ring":
            p.error("--ring-layout applies to --variant ring only")
        ring = fn

        def fn(q, k, v, mesh=None, causal=False):
            return ring(q, k, v, mesh=mesh, causal=causal, layout="zigzag")
    dtype = jnp.dtype(args.dtype)
    rng = np.random.default_rng(args.seed)
    hkv = args.kv_heads or args.heads
    q = jnp.asarray(
        rng.standard_normal((args.heads, args.seq, args.head_dim)), dtype)
    k, v = (jnp.asarray(
        rng.standard_normal((hkv, args.seq, args.head_dim)), dtype)
        for _ in range(2))
    qn, kn, vn = q, k, v  # natural order, for the oracle check
    if zig:
        # Pre-shard ONCE, outside the timed bracket — the zigzag order
        # is a deployment-time layout, not per-step work; timing the
        # permutes (plus their host sync) would bias exactly the
        # zigzag-vs-contiguous comparison this flag exists to make.
        pdev = mesh.shape[context.AXIS_SP]
        q, k, v = (context.zigzag_shard(x, pdev) for x in (q, k, v))

    from mpi_and_open_mp_tpu.utils.timing import anchor_sync

    if args.grad:
        def loss(q, k, v):
            o = fn(q, k, v, mesh=mesh, causal=args.causal)
            return jnp.sum(o.astype(jnp.float32) ** 2)

        # Jitted: an EAGER grad of the sharded variants hits a
        # "reshard non-addressable input" on multi-process meshes (the
        # internal device_put happens under the grad trace); under jit
        # the whole step stays in SPMD land — the pattern
        # tests/_dist_worker.py proves across real processes.
        run = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    else:
        run = functools.partial(fn, mesh=mesh, causal=args.causal)
    # All outputs (all three grads in --grad mode) must land before the
    # timer stops. fetch_all: jax.grad outputs come back SingleDeviceSharding
    # even on a mesh, and this is a timing bracket — one batched probe
    # fetch buys a guaranteed landing.
    sync = functools.partial(anchor_sync, fetch_all=True)

    sync(run(q, k, v))  # compile + warm
    t0 = time.perf_counter()
    result = run(q, k, v)
    sync(result)
    elapsed = time.perf_counter() - t0
    multiproc = jax.process_count() > 1
    if not args.no_check:
        # The parity operand: --grad timed the gradients, so a (single,
        # un-timed) forward supplies the checked output. Behind no_check
        # — the oracle-infeasible long-sequence mode — nothing here runs.
        out = (fn(q, k, v, mesh=mesh, causal=args.causal) if args.grad
               else result)
        if zig and not multiproc:
            # The zigzag output comes back in zigzag order; compare (and
            # report) in natural order, against the natural-order oracle.
            # (Multi-process: the un-permute would gather a
            # non-addressable global array — compare in zigzag order
            # instead, below.)
            out = context.zigzag_unshard(out, pdev)
        # The dense oracle wants one K/V head per query head — expand
        # GQA/MQA heads explicitly (the variants keep them un-expanded).
        groups = args.heads // hkv
        want = context.attention_reference(
            qn.astype(jnp.float32),
            jnp.repeat(kn.astype(jnp.float32), groups, axis=0),
            jnp.repeat(vn.astype(jnp.float32), groups, axis=0),
            causal=args.causal)
        if zig and multiproc:
            want = jnp.take(want, context.zigzag_order(args.seq, pdev),
                            axis=1)
        # On TPU, XLA's default matmul precision feeds the MXU bf16 even
        # for f32 operands, so differently-ordered reductions legitimately
        # diverge at the ~1e-3 level; only CPU f32 gets the tight bound.
        exact = dtype == jnp.float32 and jax.default_backend() != "tpu"
        tol = 1e-4 if exact else 0.06
        if multiproc:
            # Each process checks the shards it can address against the
            # matching slice of the (deterministic, same-seed) oracle —
            # then the errors are max-reduced ACROSS processes, so the
            # primary's verdict (and the timing line that follows it)
            # covers every shard, not just its own.
            from jax.experimental import multihost_utils

            want_np = np.asarray(want, np.float32)
            err = max((float(np.max(np.abs(
                np.asarray(s.data, np.float32) - want_np[s.index])))
                for s in out.addressable_shards), default=0.0)
            err = float(np.max(multihost_utils.process_allgather(
                np.float32(err))))
        else:
            err = float(np.max(np.abs(
                np.asarray(out, np.float32) - np.asarray(want))))
        if err > tol:
            print(f"PARITY FAIL: max|err|={err:.3g} > {tol}", file=sys.stderr)
            return 1
        if is_primary():
            print(f"parity ok (max|err|={err:.3g})", file=sys.stderr)

    # 2*(softmax QK^T)*V matmuls = 4*h*n^2*d multiply-adds (x0.5 causal).
    flops = 4 * args.heads * args.seq**2 * args.head_dim
    if args.causal:
        flops //= 2
    if is_primary():  # print-from-one-rank (3-life/life_mpi.c:64-67)
        print(f"{elapsed:.6f}")
        print(f"variant={args.variant} seq={args.seq} devices={mesh.size} "
              f"tflops={flops / elapsed / 1e12:.2f}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
