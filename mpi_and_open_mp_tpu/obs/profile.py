"""Compiled-artifact introspection: cost models, rooflines, memory gauges.

The bench discipline so far records *rates* (cups, TFLOP/s) against the
reference baseline; nothing says how far a rate sits from what the silicon
could do. This module closes that: lower-and-compile a phase's function
once (outside every timing bracket), read XLA's own
``compiled.cost_analysis()`` FLOPs/bytes, and turn a measured
seconds-per-step into a roofline fraction against the device's peak
compute and memory bandwidth — the annotation every cups number on the
bench line now carries.

Three instruments, all feeding the PR 4 metrics registry:

* :func:`cost` — lower+compile on abstract shapes, return
  ``{"flops", "bytes", "compile_seconds", ...memory sizes}``. Memoised per
  (name, arg shapes/dtypes); the ``profile.cost_cache{result=hit|miss}``
  counters extend the ``jit.retrace`` accounting to the profiling layer,
  and compile wall-time lands in the ``profile.compile_seconds{fn=...}``
  histogram.
* :func:`roofline` — achieved FLOP/s and bytes/s vs per-device-kind peaks
  (:data:`_PEAKS`; override with ``MOMP_PEAK_FLOPS`` /
  ``MOMP_PEAK_BYTES_S`` when the table's entry is wrong for your part).
  A device kind the table does not know is an error, not a default. The
  CPU row is a NOMINAL order-of-magnitude host number — it keeps the
  fraction finite on CPU test lines, it does not claim to model the host.
* :func:`record_memory_gauges` — live-buffer bytes (``jax.live_arrays``),
  a process-lifetime watermark, and per-device ``memory_stats`` bytes in
  use where the backend exposes them, as registry gauges so they ride the
  bench line's ``metrics`` sub-object.

Cost numbers are MODELS of the work (XLA's static analysis of one
compiled step — a Pallas custom call contributes its operands, not its
internal FLOPs), so ``bench.py`` stamps which function the cost came from
(``roofline.model``); the measured seconds are real either way.
"""

from __future__ import annotations

import math
import os
import time

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

_COST_CACHE: dict[tuple, dict] = {}


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


def _first_dict(cost_analysis) -> dict:
    # jax 0.4.x returns list[dict]; newer returns the dict itself.
    if isinstance(cost_analysis, (list, tuple)):
        return cost_analysis[0] if cost_analysis else {}
    return cost_analysis or {}


def cost(fn, *args, static_argnums=(), name: str | None = None) -> dict:
    """FLOPs/bytes/compile-time of ``fn`` compiled for ``args``' shapes.

    ``args`` may be ``jax.ShapeDtypeStruct``s — nothing executes; the
    artifact is lowered, compiled, and introspected. Raises whatever the
    lowering raises: callers decide whether a missing cost model costs a
    field or the run.
    """
    import jax

    name = name or getattr(fn, "__name__", "fn")
    sig = (name, tuple(
        (tuple(a.shape), str(a.dtype)) if hasattr(a, "shape") else repr(a)
        for a in args), tuple(static_argnums))
    cached = _COST_CACHE.get(sig)
    if cached is not None:
        metrics.inc("profile.cost_cache", result="hit")
        return dict(cached)
    metrics.inc("profile.cost_cache", result="miss")
    t0 = time.perf_counter()
    compiled = jax.jit(fn, static_argnums=static_argnums).lower(
        *args).compile()
    compile_seconds = time.perf_counter() - t0
    ca = _first_dict(compiled.cost_analysis())
    out = {
        "flops": float(ca.get("flops", float("nan"))),
        "bytes": float(ca.get("bytes accessed", float("nan"))),
        "compile_seconds": round(compile_seconds, 6),
    }
    try:
        ma = compiled.memory_analysis()
        out.update({
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "code_bytes": int(ma.generated_code_size_in_bytes),
        })
    except Exception:  # noqa: BLE001 — memory stats are backend-optional
        pass
    metrics.observe("profile.compile_seconds", compile_seconds, fn=name)
    if "temp_bytes" in out:
        metrics.gauge("profile.temp_bytes", out["temp_bytes"], fn=name)
    _COST_CACHE[sig] = dict(out)
    return out


def roofline(flops_per_step: float, bytes_per_step: float,
             seconds_per_step: float,
             device_kind: str | None = None) -> dict:
    """Roofline placement of a measured per-step time against a cost
    model: achieved rates, peak fractions, and which ceiling binds."""
    peak_flops, peak_bw, label = peaks_for(device_kind)
    if not (seconds_per_step > 0 and math.isfinite(seconds_per_step)):
        raise ValueError(
            f"seconds_per_step must be finite/positive: {seconds_per_step}")
    flops_rate = flops_per_step / seconds_per_step
    bytes_rate = bytes_per_step / seconds_per_step
    flops_frac = flops_rate / peak_flops
    bw_frac = bytes_rate / peak_bw
    return {
        "flops_per_step": flops_per_step,
        "bytes_per_step": bytes_per_step,
        "flops_per_sec": round(flops_rate, 1),
        "bytes_per_sec": round(bytes_rate, 1),
        "flops_pct": round(100 * flops_frac, 3),
        "bw_pct": round(100 * bw_frac, 3),
        # The binding ceiling — the larger fraction is the wall the
        # measured rate actually sits under.
        "bound": "memory" if bw_frac >= flops_frac else "compute",
        "roofline_pct": round(100 * max(flops_frac, bw_frac), 3),
        "peaks": label,
        "peak_flops_per_sec": peak_flops,
        "peak_bytes_per_sec": peak_bw,
    }


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


def reset_cost_cache() -> None:
    """Empty the cost memo (tests)."""
    _COST_CACHE.clear()
