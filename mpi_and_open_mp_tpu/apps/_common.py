"""Shared CLI plumbing: platform selection and multi-host bootstrap.

The reference's process bootstrap is ``MPI_Init`` under ``mpirun``
(``0-intro/hello_world.c:8``); here it splits into two knobs:

* ``--distributed``: ``jax.distributed.initialize()`` — multi-host pod
  bootstrap. Coordinator/rank come from ``--coordinator``/
  ``--num-processes``/``--process-id`` (or the ``JOB_COORDINATOR``/
  ``JOB_NUM_PROCS``/``JOB_PROC_ID`` environment the ``launchers/job_*.sh``
  scripts export, the way ``mpirun``/PBS exported ranks for the reference
  — ``job_life.sh:2-8``); with none of them set, JAX's own cluster
  auto-detection runs (SLURM, GKE, ...).
* ``--virtual-devices N``: run on N virtual CPU devices (XLA host-platform
  device count), which is how scaling sweeps and tests exercise multi-chip
  code paths on a single host. Must be applied before any JAX device use;
  it pins ``jax_platforms`` to cpu, which is what asking for it means.

Every CLI also turns on JAX's persistent compile cache here, before its
first compile (``utils.runtime.enable_compile_cache``).

Multi-process output discipline: exactly one process owns stdout/file
artifacts (:func:`is_primary`), the reference's write-from-one-rank rule
(``3-life/life_mpi.c:54-57`` — there it is rank size-1; here process 0).
"""

from __future__ import annotations

import argparse
import os


def add_platform_args(parser: argparse.ArgumentParser) -> None:
    parser.add_argument(
        "--virtual-devices", type=int, default=None, metavar="N",
        help="simulate N devices on CPU (scaling studies without a pod)",
    )
    parser.add_argument(
        "--distributed", action="store_true",
        help="multi-host bootstrap via jax.distributed.initialize()",
    )
    parser.add_argument(
        "--coordinator", metavar="HOST:PORT", default=None,
        help="explicit coordinator for --distributed "
             "(default: $JOB_COORDINATOR, else JAX cluster auto-detection)",
    )
    parser.add_argument(
        "--num-processes", type=int, default=None, metavar="N",
        help="process count for --distributed (default: $JOB_NUM_PROCS)",
    )
    parser.add_argument(
        "--process-id", type=int, default=None, metavar="I",
        help="this process's rank for --distributed (default: $JOB_PROC_ID)",
    )


def apply_platform_args(args) -> None:
    import jax

    from mpi_and_open_mp_tpu.utils.runtime import enable_compile_cache

    enable_compile_cache()
    if args.distributed:
        # Flags beat the JOB_* environment; anything still unset stays
        # None, which jax.distributed.initialize fills via its own
        # cluster auto-detection (SLURM, GKE, ...).
        env = os.environ.get
        coord = args.coordinator or env("JOB_COORDINATOR")
        nprocs = (args.num_processes if args.num_processes is not None
                  else int(env("JOB_NUM_PROCS", 0)) or None)
        proc_id = (args.process_id if args.process_id is not None
                   else (int(env("JOB_PROC_ID"))
                         if env("JOB_PROC_ID") is not None else None))
        jax.distributed.initialize(
            coordinator_address=coord,
            num_processes=nprocs,
            process_id=proc_id,
        )
    if args.virtual_devices:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.virtual_devices}"
        )
        jax.config.update("jax_platforms", "cpu")


def is_primary() -> bool:
    """True in the process that owns stdout/artifact writes (process 0;
    trivially true un-distributed)."""
    import jax

    return jax.process_index() == 0
