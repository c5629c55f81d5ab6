"""Micro-batching front door over the batched native engines.

The reference serves exactly one workload per process launch (``mpirun
-np N ./life``); the batched execution layer (``ops.bitlife`` B-board
kernels, ``models.LifeSim`` stacked boards) removes the one-board-per-
dispatch limit, and this package supplies the request-collecting layer
on top: callers :meth:`~ShapeBucketBatcher.submit` independent boards,
:meth:`~ShapeBucketBatcher.flush` groups them into shape buckets and
advances each bucket in ONE device dispatch through
``ops.pallas_life.life_run_vmem_batch``.

Why bucketing matters: every distinct ``(B, ny, nx)`` stack shape is
one compiled XLA program, and an uncontrolled shape set would spend its
life retracing and compiling. The
batcher therefore (a) keys buckets on board shape+dtype, (b) pads each
dispatch's batch up to a power of two capped at ``max_batch`` (zero
boards, sliced off afterwards — a dead board stays dead under Life's
rule, so padding can never perturb live boards), and (c) leans on the
step count being a RUNTIME scalar on every batched path, so requests
with different step counts share one compiled program. The compiled-
program set is thus at most ``log2(max_batch)+1`` programs per board
shape, verified idle via the ``jit.retrace`` counters
(``obs.metrics.get("jit.retrace", fn="life_batch_...")`` — the PR-4
observability layer ticks them inside each batched jit body, once per
compile).

That small closed program set is also what makes the programs
*persistable*: ``serve.aotcache`` serializes every bucket executable
through ``jax.export`` into a durable on-disk cache, so a restarted
daemon deserializes in milliseconds instead of re-tracing — zero
``jit.retrace`` ticks on a warm resume, with corrupt/stale artifacts
quarantined and parity-gated so a bad cache can only ever cost a fresh
trace, never a wrong answer.
"""

from mpi_and_open_mp_tpu.serve.batcher import (  # noqa: F401
    ShapeBucketBatcher,
    bucket_batch_size,
    retrace_counts,
)
from mpi_and_open_mp_tpu.serve.policy import (  # noqa: F401
    SCALE_ADD,
    SCALE_DRAIN,
    SHED_DEPTH,
    SHED_DISPATCH,
    SHED_PADDING,
    SHED_REASONS,
    SHED_REHOMED,
    SHED_TIMEOUT,
    ElasticController,
    ElasticityPolicy,
    ServePolicy,
    rollup,
)
from mpi_and_open_mp_tpu.serve.queue import (  # noqa: F401
    ServeQueue,
    Ticket,
)
from mpi_and_open_mp_tpu.serve.wal import (  # noqa: F401
    FSYNC_POLICIES,
    TicketWAL,
    WALReplay,
    replay,
)
from mpi_and_open_mp_tpu.serve.aotcache import AOTCache  # noqa: F401
from mpi_and_open_mp_tpu.serve.pool import (  # noqa: F401
    Handle,
    PoolError,
    SessionPool,
)
from mpi_and_open_mp_tpu.serve.daemon import ServingDaemon  # noqa: F401
from mpi_and_open_mp_tpu.serve.router import (  # noqa: F401
    ConsistentHashRing,
    FleetRouter,
)
from mpi_and_open_mp_tpu.serve.fleet import Fleet, WorkerHandle  # noqa: F401
from mpi_and_open_mp_tpu.serve.loadgen import (  # noqa: F401
    SLO,
    LoadgenReport,
    ScenarioMix,
    arrivals_poisson,
    arrivals_trace,
    run_open_loop,
    saturation_knee,
    sweep,
)
