"""SPMD bootstrap + ring messaging demo.

Reference parity: ``0-intro/hello_world.c`` (init, print size/rank) and
``0-intro/send.c`` (each rank sends a greeting to ``(r+1)%size`` and
receives from ``(r-1+size)%size``). The TPU equivalents: device/process
enumeration via ``jax.devices``/``jax.process_index``, and a one-hop ring
``lax.ppermute`` carrying each device's token to its successor — the same
ring pattern, minus the blocking-send deadlock hazard.
"""

from __future__ import annotations

import argparse
import sys

import numpy as np

from mpi_and_open_mp_tpu.apps._common import add_platform_args, apply_platform_args


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="mpi_and_open_mp_tpu.apps.hello")
    p.add_argument("--devices", type=int, default=None)
    add_platform_args(p)
    args = p.parse_args(argv)
    apply_platform_args(args)

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mpi_and_open_mp_tpu.parallel import mesh as mesh_lib
    from mpi_and_open_mp_tpu.parallel.halo import ring_perm

    n = args.devices or len(jax.devices())
    print(f"process {jax.process_index()} of {jax.process_count()}; "
          f"{n} device(s): {[d.device_kind for d in jax.devices()[:n]]}")

    mesh = mesh_lib.make_mesh_1d(n, axis="r")
    tokens = jax.device_put(
        jnp.arange(n, dtype=jnp.int32), NamedSharding(mesh, P("r"))
    )
    received = jax.shard_map(
        lambda t: jax.lax.ppermute(t, "r", ring_perm(n, 1)),
        mesh=mesh, in_specs=P("r"), out_specs=P("r"),
    )(tokens)
    for i, src in enumerate(np.asarray(jax.device_get(received))):
        print(f"device {i} received hello from device {int(src)}")
    ok = np.array_equal(
        np.asarray(jax.device_get(received)), np.roll(np.arange(n), 1)
    )
    print("ring ok" if ok else "ring BROKEN")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
