"""Device peaks and memory gauges.

Two helpers; the second feeds the metrics registry:

* :func:`peaks_for` — peak FLOP/s and bytes/s per device kind
  (:data:`_PEAKS`; override with ``MOMP_PEAK_FLOPS`` /
  ``MOMP_PEAK_BYTES_S`` when the table's entry is wrong for your part).
  A device kind the table does not know is an error, not a default. The
  CPU row is a NOMINAL order-of-magnitude host number — it keeps a
  fraction finite on CPU test lines, it does not claim to model the host.
* :func:`record_memory_gauges` — live-buffer bytes (``jax.live_arrays``),
  a process-lifetime watermark, and per-device ``memory_stats`` bytes in
  use where the backend exposes them, as registry gauges so they ride the
  bench line's ``metrics`` sub-object.
"""

from __future__ import annotations

import os

from mpi_and_open_mp_tpu.obs import metrics

#: (device_kind substring, label, peak FLOP/s, peak bytes/s). Matched
#: case-insensitively in order; first hit wins. TPU rows are bf16 peak +
#: HBM bandwidth from the public chip specs (Google Cloud TPU docs); the
#: CPU row is a NOMINAL host-class placeholder (see module docs).
_PEAKS: tuple[tuple[str, str, float, float], ...] = (
    # v5e: "TPU v5 lite" is the device_kind string.
    ("v5 lite", "v5 lite-table", 197e12, 819e9),
    ("v5e", "v5e-table", 197e12, 819e9),
    ("v5p", "v5p-table", 459e12, 2765e9),
    ("v6", "v6-table", 918e12, 1640e9),
    ("v4", "v4-table", 275e12, 1228e9),
    ("v3", "v3-table", 123e12, 900e9),
    ("v2", "v2-table", 45e12, 700e9),
    ("cpu", "cpu-nominal", 1e11, 2e10),
)


def peaks_for(device_kind: str | None) -> tuple[float, float, str]:
    """``(peak_flops_per_sec, peak_bytes_per_sec, label)`` for a device
    kind, env-overridable per component. Raises ``ValueError`` for a kind
    the table does not know."""
    kind = (device_kind or "").lower()
    for sub, label, flops, bw in _PEAKS:
        if sub in kind:
            break
    else:
        raise ValueError(f"no peak table entry for device kind "
                         f"{device_kind!r}; add it to obs.profile._PEAKS")
    try:
        flops = float(os.environ.get("MOMP_PEAK_FLOPS", flops))
        bw = float(os.environ.get("MOMP_PEAK_BYTES_S", bw))
    except ValueError:
        pass
    return flops, bw, label


_WATERMARK = 0


def live_buffer_bytes() -> int:
    """Total bytes of live device arrays in this process."""
    import jax

    return sum(int(getattr(a, "nbytes", 0)) for a in jax.live_arrays())


def record_memory_gauges() -> int:
    """Gauge live-buffer bytes + process watermark (+ per-device
    ``memory_stats`` where the backend exposes them); returns the live
    total."""
    import jax

    global _WATERMARK
    live = live_buffer_bytes()
    _WATERMARK = max(_WATERMARK, live)
    metrics.gauge("memory.live_buffer_bytes", live)
    metrics.gauge("memory.live_buffer_watermark_bytes", _WATERMARK)
    for dev in jax.local_devices():
        try:
            stats = dev.memory_stats()
        except Exception:  # noqa: BLE001 — CPU backends have none
            stats = None
        if stats and "bytes_in_use" in stats:
            metrics.gauge("memory.device_bytes_in_use",
                          stats["bytes_in_use"], device=str(dev.id))
    return live
