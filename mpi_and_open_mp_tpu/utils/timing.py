"""Wall-clock timing with the reference's measurement contract.

The reference brackets its hot loop with ``MPI_Wtime`` and prints bare
elapsed seconds from one rank (``/root/reference/3-life/life_mpi.c:50,64-67``).
Here the equivalent is ``time.perf_counter`` around fully-materialised device
work: callers must pass results through ``block_until_ready`` (JAX dispatch is
async) before stopping the clock.
"""

from __future__ import annotations

import time


def anchor_sync(tree, fetch_all: bool = False) -> None:
    """Wait until every array in ``tree`` has actually materialised.

    After blocking, this anchors each mesh-placed leaf with a one-element
    host fetch — from a locally addressable shard, so it also works on
    multi-host arrays — batched into a single ``device_get`` (one host
    round trip, not one per leaf). The anchor was added for a remote-chip
    stack on which ``jax.block_until_ready`` returned early for sharded
    arrays. On a directly attached v5e (2x2, PR 21 ``chip_smoke.py
    --multichip``) the block alone waits: its time grows with the step
    count and the fetch after it costs a flat ~2 ms. Single-device leaves
    stay block-only by default, since the fetch would add a host round
    trip inside timing brackets. Pass ``fetch_all=True`` to probe those
    too, for brackets where a guaranteed landing is worth one round
    trip.
    """
    import jax

    jax.block_until_ready(tree)
    probes = []
    for leaf in jax.tree_util.tree_leaves(tree):
        if getattr(leaf, "sharding", None) is None or not hasattr(
            leaf, "addressable_shards"
        ):
            continue
        if not fetch_all and isinstance(
            leaf.sharding, jax.sharding.SingleDeviceSharding
        ):
            continue
        shard = leaf.addressable_shards[0].data
        if shard.size == 0:
            continue
        probes.append(shard[(slice(0, 1),) * shard.ndim])
    if probes:
        jax.device_get(probes)


class Timer:
    """Context manager measuring wall seconds.

    ``.elapsed`` reads the RUNNING total inside the ``with`` block (a live
    ``perf_counter`` difference — mid-flight progress reads, span
    heartbeats) and freezes at exit. This is the one wall-clock
    implementation in the framework: the span tracer (``obs.trace``) uses
    it as its clock, so spans and bench brackets can never disagree on
    what a second is.
    """

    def __enter__(self) -> "Timer":
        self.start = time.perf_counter()
        self._stopped: float | None = None
        return self

    @property
    def elapsed(self) -> float:
        if self._stopped is None:
            return time.perf_counter() - self.start
        return self._stopped

    def __exit__(self, *exc) -> None:
        self._stopped = time.perf_counter() - self.start


def append_times_txt(path: str, seconds: float) -> None:
    """Append one wall-clock entry, matching the ``gtime -o times.txt -a``
    accumulation used by the reference launchers (``3-life/run_life.sh:5``)."""
    with open(path, "a") as fd:
        fd.write(f"{seconds:.3f}\n")


def write_csv_rows(path: str, rows: list[str]) -> None:
    """(Re)write a CSV artifact whole, creating its directory. The chip
    sweeps call this after EVERY recorded point so a mid-sweep crash
    cannot discard rows bought with scarce chip time."""
    import os

    outdir = os.path.dirname(path)
    if outdir:
        os.makedirs(outdir, exist_ok=True)
    with open(path, "w") as fd:
        fd.write("\n".join(rows) + "\n")
