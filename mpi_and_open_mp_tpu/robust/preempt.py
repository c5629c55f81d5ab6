"""Preemption-safe shutdown: signal-triggered checkpoint flush.

The reference's answer to a preempted PBS job was to requeue and restart
from step 0. Here a SIGTERM/SIGINT lands as a *flag* checked at segment
boundaries of ``LifeSim.run``: the loop flushes a final checkpoint and
raises :class:`Preempted`, which drivers translate to exit code 75
(EX_TEMPFAIL) — a batch scheduler requeues the job and its ``--resume``
continues the bracket from the flushed step.

Handlers only *set the flag* — no checkpoint IO, no device work, nothing
async-signal-unsafe runs inside the handler itself. The flush happens in
the run loop, between dispatches, where the board is a complete step.
"""

from __future__ import annotations

import contextlib
import signal
import threading

EXIT_PREEMPTED = 75  # EX_TEMPFAIL: transient, resumable — requeue me


class Preempted(RuntimeError):
    """A run stopped early with its state flushed; resume to continue."""

    def __init__(self, step: int, checkpoint: str | None = None,
                 signum: int | None = None):
        self.step = int(step)
        self.checkpoint = checkpoint
        self.signum = signum
        how = (f"signal {signum}" if signum is not None else "chaos plan")
        where = f"; checkpoint {checkpoint}" if checkpoint else ""
        super().__init__(f"preempted at step {step} by {how}{where}")


class SimulatedPreemption(Preempted):
    """The ``MOMP_CHAOS`` ``preempt=<k>`` fault — same recovery contract
    as a real signal, minus the dying process."""


class SignalWatch:
    """The flag a run loop polls; ``fired`` is the signum or ``None``."""

    def __init__(self):
        self.fired: int | None = None


@contextlib.contextmanager
def flush_on_signal(enabled: bool = True):
    """Arm SIGTERM/SIGINT to request a checkpoint flush at the next
    segment boundary. Yields a :class:`SignalWatch`; previous handlers
    are restored on exit. A no-op (always-unfired watch) when disabled
    or off the main thread (signal.signal would raise there)."""
    watch = SignalWatch()
    if not enabled or threading.current_thread() is not threading.main_thread():
        yield watch
        return
    prev = {}

    def handler(signum, frame):
        watch.fired = signum

    try:
        for s in (signal.SIGTERM, signal.SIGINT):
            try:
                prev[s] = signal.signal(s, handler)
            except (ValueError, OSError):  # exotic embedding; stay a no-op
                pass
        yield watch
    finally:
        for s, h in prev.items():
            signal.signal(s, h)
