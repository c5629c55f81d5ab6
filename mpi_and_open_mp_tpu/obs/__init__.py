"""Observability: span tracing, a metrics registry, and trace reporting.

The reference repo's entire observability story is one ``MPI_Wtime``
bracket printed from rank 0 plus a hand-grown ``times.txt``
(``/root/reference/3-life/life_mpi.c:50,64-67``). This package is its
TPU-native replacement, zero-dependency (stdlib only) and zero-overhead
when off:

``trace``
    Nestable spans with a context-manager API, monotonic durations
    (``utils.timing.Timer`` is the clock), process/host ids, and a JSONL
    sink selected by ``MOMP_TRACE=path``. An anchored span closes through
    ``jax.block_until_ready`` so async device work is attributed to the
    span that dispatched it, and a live span is a
    ``jax.profiler.TraceAnnotation`` of its name, so it lies on the
    profiler's clock beside the device ops. When ``MOMP_TRACE`` is unset
    every call degenerates to one env lookup returning a shared no-op
    span — the chaos layer's ``is None`` discipline.
``metrics``
    Process-wide counters/gauges/histograms: jit retraces per function,
    ring hops per engine, traced halo exchanges, guard validations and
    ``:recovered`` ladder falls, checkpoint bytes/durations. On by
    default (host-side dict ops); ``MOMP_METRICS=0`` no-ops every
    recorder. ``bench.py`` publishes ``snapshot()`` on its JSON line.
``telemetry``
    The fleet time-series layer over the registry: bounded per-worker
    snapshot rings (periodic deltas, paired mono/wall clock stamps),
    fixed-bucket latency histograms with p50/p99/p999 readout and a
    DECLARED bucket error, the multi-window SLO burn-rate monitor the
    elasticity controller's decisions record, and the length-prefixed
    CRC-framed sidecar stream worker subprocesses ship snapshots over
    (a kill -9 loses at most one partial frame, and the loss is
    counted). ``MOMP_TELEMETRY=0`` switches the plane off.
``report``
    Pure-host analysis of a trace file: per-phase breakdown, α+βn fit
    over ring-hop transfer spans, recovery/retrace summary, and a Chrome
    trace-event exporter (``to_chrome``) so span timelines open in
    Perfetto. CLI form: ``analysis/trace_report.py`` (``--chrome``).
``ledger``
    The CROSS-run layer: an append-only JSONL run ledger where every
    bench line lands stamped with git SHA, platform/device kind, and a
    (topology, shape, dtype, batch, engine) configuration key — the
    baseline store ``analysis/regression_sentinel.py`` judges new runs
    against. Stdlib-only; safe on chip-forbidden hosts.
``profile``
    Per-device-kind peak tables and live-buffer/memory gauges through
    the metrics registry.
"""

from mpi_and_open_mp_tpu.obs import (  # noqa: F401
    ledger, metrics, telemetry, trace)
