"""Process-wide metrics registry: counters, gauges, histograms.

Host-side only — recorders are plain dict updates under a lock, cheap
enough to live on warm paths (a segment boundary, a trace-time function
body, a checkpoint write) but NEVER inside a timed chained-dispatch
bracket. Collection is ON by default; ``MOMP_METRICS=0`` turns every
recorder into an immediate return (the registry stays empty), mirroring
the chaos/trace off-path discipline.

Keys are ``(name, sorted label items)``; :func:`snapshot` renders them
``name{k=v,...}`` — the flat, diffable form ``bench.py`` publishes as
the ``metrics`` sub-object of its JSON line. Histograms keep
count/total/min/max (no buckets: the consumers here want "how many, how
long altogether, worst case", not quantiles — latency SERIES that need
p50/p99/p999 live in ``obs.telemetry.LatencyHist`` on top of this
registry). Label cardinality is capped per metric name
(``MOMP_METRICS_MAX_LABELSETS``, default 256): a high-cardinality label
(per-session ids under loadgen) stops growing the registry at the cap
and ticks ``metrics.dropped_labels`` instead.

What lands here (the instrumented layers):

* ``jit.retrace{fn=...}`` — incremented INSIDE jitted function bodies,
  which only run on a jit-cache miss: the retrace counter per function.
* ``ring.hops.fwd{engine=...}`` / ``ring.steps.traced`` — ring-attention
  hops executed per engine stamp (traced hop-by-hop dispatch).
* ``halo.exchange.traced{kind=...,axis=...}`` — halo exchanges TRACED
  (bodies run at trace time only; executions per step are not
  host-visible from inside a compiled loop — documented, like chaos's
  trace-time injection).
* ``guard.validation{engine=...}`` / ``guard.validation_failed{...}`` /
  ``recovery{stamp=...}`` — the guards ladder (``robust.guards``).
* ``checkpoint.saves`` / ``checkpoint.save.bytes`` /
  ``checkpoint.save_seconds`` (histogram) and the ``restore`` twins.
"""

from __future__ import annotations

import math
import os
import threading

_ENV = "MOMP_METRICS"
_ENV_MAX_LABELSETS = "MOMP_METRICS_MAX_LABELSETS"

#: Overflow counter ticked when the cardinality guard drops a record.
DROPPED_LABELS = "metrics.dropped_labels"

_LOCK = threading.Lock()
_COUNTERS: dict[tuple, float] = {}
_GAUGES: dict[tuple, float] = {}
_HISTS: dict[tuple, list[float]] = {}  # [count, total, min, max]
_LABELSETS: dict[str, int] = {}  # distinct label sets seen per name


def max_labelsets() -> int:
    """Distinct label sets admitted per metric name before the guard
    drops new ones (``MOMP_METRICS_MAX_LABELSETS``, default 256)."""
    try:
        v = int(os.environ.get(_ENV_MAX_LABELSETS, "256"))
    except ValueError:
        return 256
    return v if v > 0 else 256


def _admit(k: tuple, store: dict) -> bool:
    """Cardinality guard, called under ``_LOCK``: an EXISTING key always
    updates; a new key is admitted only while its metric name is under
    the label-set cap. Without this, one per-session label under loadgen
    grows the registry with the traffic — unbounded resident memory and
    a snapshot() that swamps the bench line. Drops tick
    :data:`DROPPED_LABELS` (itself label-free, so never droppable)."""
    if k in store:
        return True
    name = k[0]
    if _LABELSETS.get(name, 0) >= max_labelsets():
        dk = (DROPPED_LABELS, ())
        _COUNTERS[dk] = _COUNTERS.get(dk, 0) + 1
        return False
    _LABELSETS[name] = _LABELSETS.get(name, 0) + 1
    return True


def metrics_on() -> bool:
    """Collection is on unless ``MOMP_METRICS=0``."""
    return os.environ.get(_ENV, "1") != "0"


def _key(name: str, labels: dict) -> tuple:
    # Label values stringify so keys always sort/compare (an int-valued
    # and a str-valued label under one name must not break snapshot()).
    return (name, tuple(sorted((a, str(b)) for a, b in labels.items())))


def inc(name: str, value: float = 1, **labels) -> None:
    """Add to a monotonic counter."""
    if not metrics_on():
        return
    k = _key(name, labels)
    with _LOCK:
        if _admit(k, _COUNTERS):
            _COUNTERS[k] = _COUNTERS.get(k, 0) + value


def gauge(name: str, value: float, **labels) -> None:
    """Set a last-value-wins gauge."""
    if not metrics_on():
        return
    k = _key(name, labels)
    with _LOCK:
        if _admit(k, _GAUGES):
            _GAUGES[k] = value


def observe(name: str, value: float, **labels) -> None:
    """Record one histogram observation (count/total/min/max). NaN
    observations are dropped — a no-op span clock must not poison the
    aggregate."""
    if not metrics_on() or math.isnan(value):
        return
    k = _key(name, labels)
    with _LOCK:
        h = _HISTS.get(k)
        if h is None:
            if not _admit(k, _HISTS):
                return
            _HISTS[k] = [1, value, value, value]
        else:
            h[0] += 1
            h[1] += value
            h[2] = min(h[2], value)
            h[3] = max(h[3], value)


def get(name: str, **labels) -> float:
    """Current counter value (0 when never incremented)."""
    with _LOCK:
        return _COUNTERS.get(_key(name, labels), 0)


def _render(k: tuple) -> str:
    name, items = k
    if not items:
        return name
    return name + "{" + ",".join(f"{a}={b}" for a, b in items) + "}"


def snapshot() -> dict:
    """The registry as plain JSON-ready dicts (always all three
    sections, so consumers can index unconditionally)."""
    with _LOCK:
        return {
            "counters": {_render(k): v for k, v in sorted(_COUNTERS.items())},
            "gauges": {_render(k): v for k, v in sorted(_GAUGES.items())},
            "histograms": {
                _render(k): {"count": h[0], "total": h[1],
                             "min": h[2], "max": h[3]}
                for k, h in sorted(_HISTS.items())
            },
        }


def delta(before: dict, after: dict) -> dict:
    """The registry movement BETWEEN two :func:`snapshot` calls, in
    snapshot shape: what one stretch of work moved. Counters and
    histogram count/total subtract (zero movement drops out); gauges are
    last-value-wins so the window reports those it TOUCHED at their
    ``after`` value; histogram min/max cannot be un-merged and honestly
    report the window's ``after`` values only when the count moved."""
    out: dict = {"counters": {}, "gauges": {}, "histograms": {}}
    b, a = before.get("counters", {}), after.get("counters", {})
    for key, v in a.items():
        moved = v - b.get(key, 0)
        if moved:
            out["counters"][key] = moved
    bg, ag = before.get("gauges", {}), after.get("gauges", {})
    for key, v in ag.items():
        if key not in bg or bg[key] != v:
            out["gauges"][key] = v
    bh, ah = before.get("histograms", {}), after.get("histograms", {})
    for key, h in ah.items():
        prev = bh.get(key, {"count": 0, "total": 0.0})
        moved = h["count"] - prev["count"]
        if moved:
            out["histograms"][key] = {
                "count": moved, "total": h["total"] - prev["total"],
                "min": h["min"], "max": h["max"],
            }
    return out


def reset() -> None:
    """Empty the registry (tests)."""
    with _LOCK:
        _COUNTERS.clear()
        _GAUGES.clear()
        _HISTS.clear()
        _LABELSETS.clear()
