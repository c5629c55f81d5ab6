"""Span tracer: nested wall-clock spans + instant events to a JSONL sink.

Activation mirrors ``robust.chaos``: the ``MOMP_TRACE`` environment
variable selects the sink path; when unset, :func:`span` returns a shared
no-op singleton and :func:`event` returns immediately — one env lookup,
no allocation, no I/O, nothing reachable. The sink is cached per env
value (like ``chaos.active_plan``'s ``_CACHE``) and opened in APPEND
mode, so multiple processes/invocations may share one trace file (the CI
trace cycle relies on this).

Record schema, one JSON object per line::

    {"kind": "span",  "name": ..., "ts": <epoch sec>, "dur": <sec>,
     "id": N, "parent": M|null, "pid": ..., "host": ..., "attrs": {...}}
    {"kind": "event", "name": ..., "ts": <epoch sec>,
     "id": N, "parent": M|null, "pid": ..., "host": ..., "attrs": {...}}

Spans are written at EXIT (children before parents — reconstruct nesting
via ``parent``). The duration clock is ``utils.timing.Timer`` — the one
wall-clock implementation in the framework.

A live span also enters ``jax.profiler.TraceAnnotation(name)`` for its
lifetime, so under ``jax.profiler.trace`` it shows on the host's line of
the profile, on the clock the device ops share: an idle gap of the
device can then be named by the span the host was in. The annotation
carries the name only; attributes stay in the JSONL record. ``jax`` is
imported on this live path alone, so ``obs`` imports without it.

Device-work attribution: JAX dispatch is async, so a span that merely
brackets a dispatch times the enqueue, not the work. Call
``span.anchor(tree)`` with the dispatched output; the span then closes
through ``jax.block_until_ready(tree)``, so ``dur`` covers the device
work the span claims to measure, with no host fetch of its own.
"""

from __future__ import annotations

import itertools
import json
import os
import socket
import threading

from mpi_and_open_mp_tpu.utils.timing import Timer

_ENV = "MOMP_TRACE"
_ENV_HOPS = "MOMP_TRACE_HOPS"

_CACHE: tuple[str | None, object | None] = (None, None)
_IDS = itertools.count(1)
_LOCAL = threading.local()
_HOST: str | None = None
_WRITE_LOCK = threading.Lock()


def enabled() -> bool:
    """Whether tracing is on (``MOMP_TRACE`` names a sink path)."""
    return bool(os.environ.get(_ENV, ""))


def hop_spans_active() -> bool:
    """Whether per-hop ring instrumentation should engage: tracing on and
    not opted out via ``MOMP_TRACE_HOPS=0`` (the hop-by-hop traced ring
    dispatch re-plans the forward as p-1 host-anchored hops — always
    parity-exact, but a different dispatch shape than the fused
    ``fori_loop`` ring; the opt-out keeps whole-call spans only)."""
    return enabled() and os.environ.get(_ENV_HOPS, "1") != "0"


def _sink():
    """The open line-buffered sink for the current ``MOMP_TRACE`` value,
    or ``None``. Cached per value; a changed path closes the old file."""
    global _CACHE
    raw = os.environ.get(_ENV, "")
    if not raw:
        return None
    if _CACHE[0] != raw:
        if _CACHE[1] is not None:
            try:
                _CACHE[1].close()
            except OSError:
                pass
        outdir = os.path.dirname(raw)
        if outdir:
            os.makedirs(outdir, exist_ok=True)
        _CACHE = (raw, open(raw, "a", buffering=1))
    return _CACHE[1]


def reset() -> None:
    """Close and drop the cached sink (tests switch paths mid-process)."""
    global _CACHE
    if _CACHE[1] is not None:
        try:
            _CACHE[1].close()
        except OSError:
            pass
    _CACHE = (None, None)


def _host() -> str:
    global _HOST
    if _HOST is None:
        _HOST = socket.gethostname()
    return _HOST


def _stack() -> list:
    s = getattr(_LOCAL, "stack", None)
    if s is None:
        s = _LOCAL.stack = []
    return s


def _write(rec: dict) -> None:
    fd = _sink()
    if fd is None:  # sink vanished mid-span (env cleared): drop silently
        return
    line = json.dumps(rec, default=str)
    with _WRITE_LOCK:
        fd.write(line + "\n")


class _NullSpan:
    """The off-path span: every method a no-op, one shared instance."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **attrs) -> "_NullSpan":
        return self

    def anchor(self, tree) -> "_NullSpan":
        return self

    @property
    def elapsed(self) -> float:
        return float("nan")


NULL = _NullSpan()


class Span:
    """One live span. Use via ``with trace.span(name, **attrs) as sp``."""

    __slots__ = ("name", "attrs", "id", "parent", "_timer", "_ts", "_tree",
                 "_annotation")

    def __init__(self, name: str, attrs: dict):
        self.name = name
        self.attrs = attrs
        self._tree = None

    def __enter__(self) -> "Span":
        import time

        import jax

        stack = _stack()
        self.parent = stack[-1].id if stack else None
        self.id = next(_IDS)
        stack.append(self)
        self._annotation = jax.profiler.TraceAnnotation(self.name)
        self._annotation.__enter__()
        self._ts = time.time()
        self._timer = Timer().__enter__()
        return self

    def set(self, **attrs) -> "Span":
        """Attach/override attributes mid-span."""
        self.attrs.update(attrs)
        return self

    def anchor(self, tree) -> "Span":
        """Close through ``jax.block_until_ready(tree)``: the span's
        duration then includes the device work behind these (possibly
        async) arrays."""
        self._tree = tree
        return self

    @property
    def elapsed(self) -> float:
        """Running wall seconds (live inside the ``with`` block)."""
        return self._timer.elapsed

    def __exit__(self, exc_type, exc, tb) -> bool:
        try:
            if self._tree is not None and exc_type is None:
                import jax

                jax.block_until_ready(self._tree)
            self._tree = None
            self._timer.__exit__()
        finally:
            self._annotation.__exit__(exc_type, exc, tb)
        stack = _stack()
        if stack and stack[-1] is self:
            stack.pop()
        rec = {
            "kind": "span", "name": self.name, "ts": self._ts,
            "dur": self._timer.elapsed, "id": self.id, "parent": self.parent,
            "pid": os.getpid(), "host": _host(),
        }
        if exc_type is not None:
            rec["error"] = exc_type.__name__
        if self.attrs:
            rec["attrs"] = self.attrs
        _write(rec)
        return False


def span(name: str, **attrs):
    """A new span, or the shared no-op when tracing is off."""
    if not os.environ.get(_ENV, ""):
        return NULL
    return Span(name, attrs)


def event(name: str, **attrs) -> None:
    """An instant (zero-duration) record — recovery stamps, metric
    snapshots. Parented to the innermost open span of this thread."""
    if not os.environ.get(_ENV, ""):
        return
    import time

    stack = _stack()
    rec = {
        "kind": "event", "name": name, "ts": time.time(), "id": next(_IDS),
        "parent": stack[-1].id if stack else None,
        "pid": os.getpid(), "host": _host(),
    }
    if attrs:
        rec["attrs"] = attrs
    _write(rec)
