"""Reduce a profiler trace to the numbers the per-layer metrics read.

``from_xplane`` reads the ``.xplane.pb`` that ``jax.profiler.trace``
writes, with nothing but JAX, into a :class:`Trace`: for each device
plane (``/device:TPU:<n>``) its op events (line ``XLA Ops``) and its
program events (line ``XLA Modules``), and the events of the host's
Python thread: the line that holds the runner's ``bench.*`` marks (it is
named after the interpreter, ``python3`` or ``python``), with JAX's own
events on it, such as ``np.asarray(jax.Array)`` or
``PjitFunction(advance)``.
``to_json`` / ``from_json`` keep that structure as plain data, which is
what the test fixture holds.

An op event's name is ``<kind>:<name>``, taken from the HLO text the
trace gives (``%body.3 = u32[..] custom-call(..)`` becomes
``custom-call:body.3``). Ops nest (a ``while`` holds its body's ops), so
op times are self times: an op's duration less that of the ops inside
it. All times are nanoseconds on the profiler's clock, which the device
and host planes share.
"""

from __future__ import annotations

import collections
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_MARK = "bench."
_HLO = re.compile(r"^%?([^\s=]+) = .*? ([a-z][a-z0-9-]*)\(")


def op_name(hlo: str) -> str:
    """``<kind>:<name>`` of one ``XLA Ops`` event."""
    m = _HLO.match(hlo)
    return f"{m.group(2)}:{m.group(1)}" if m else hlo[:80]


def _union(intervals) -> list[tuple[float, float]]:
    """Merged ``(start, end)`` intervals, sorted."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def self_times(ops: list) -> list[tuple[str, float]]:
    """``(name, self ns)`` of each op: its duration less the durations of
    the ops directly inside it."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    own = [float(op[2]) for op in ops]
    stack: list[int] = []
    for i in order:
        start = ops[i][1]
        while stack and ops[stack[-1]][1] + ops[stack[-1]][2] <= start:
            stack.pop()
        if stack:
            own[stack[-1]] -= ops[i][2]
        stack.append(i)
    return [(op[0], max(0.0, t)) for op, t in zip(ops, own)]


class Trace:
    """Device op and module events per chip, and the host's events.

    ``devices``: ``{plane name: {"ops": [[name, start_ns, dur_ns], ...],
    "modules": [...]}}``; ``host``: ``[[name, start_ns, dur_ns], ...]``.
    """

    def __init__(self, devices: dict, host: list):
        self.devices = devices
        self.host = host

    def _busy(self, dev: dict) -> list[tuple[float, float]]:
        return _union((s, s + d) for _, s, d in dev["ops"])

    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the devices."""
        if not self.devices:
            return 0.0
        total = sum(e - s for d in self.devices.values()
                    for s, e in self._busy(d))
        return total / len(self.devices) / 1e9

    def module_s(self, pattern: str) -> float:
        """Seconds of the programs whose module name matches ``pattern``,
        averaged over the devices."""
        if not self.devices:
            return 0.0
        rx = re.compile(pattern)
        total = sum(dur for d in self.devices.values()
                    for name, _, dur in d["modules"] if rx.search(name))
        return total / len(self.devices) / 1e9

    def op_share(self, pattern: str) -> float | None:
        """Share of busy time in the self time of ops whose name matches
        ``pattern``, averaged over the devices; ``None`` where no op
        matches on any device."""
        rx = re.compile(pattern)
        shares = []
        found = False
        for d in self.devices.values():
            busy = sum(e - s for s, e in self._busy(d))
            hit = sum(t for name, t in self_times(d["ops"])
                      if rx.search(name))
            found = found or any(rx.search(op[0]) for op in d["ops"])
            shares.append(hit / busy if busy else 0.0)
        if not found:
            return None
        return sum(shares) / len(shares)

    def breakdown(self, top: int = 10) -> dict:
        """The ops with the most self time (seconds, averaged over the
        devices), and the first device's idle time by what the host's
        Python thread was doing: each gap between busy intervals is split
        over the innermost host events that cover it (``"no host
        event"`` where none does)."""
        per_op: dict[str, float] = collections.defaultdict(float)
        n = max(1, len(self.devices))
        for d in self.devices.values():
            for name, t in self_times(d["ops"]):
                per_op[name] += t / n / 1e9
        ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
        idle: dict[str, float] = collections.defaultdict(float)
        if self.devices:
            busy = self._busy(self.devices[min(self.devices)])
            pieces = innermost(self.host)
            k = 0
            for (_, g0), (g1, _) in zip(busy, busy[1:]):
                while k < len(pieces) and pieces[k][1] <= g0:
                    k += 1
                covered = 0.0
                j = k
                while j < len(pieces) and pieces[j][0] < g1:
                    s, e, name = pieces[j]
                    part = min(e, g1) - max(s, g0)
                    if part > 0:
                        idle[name] += part / 1e9
                        covered += part
                    j += 1
                idle["no host event"] += (g1 - g0 - covered) / 1e9
        idle = {k: v for k, v in idle.items() if v > 0}
        worst = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in worst]}


def innermost(events: list) -> list[tuple[float, float, str]]:
    """``(start, end, name)`` pieces of the timeline, each named by the
    innermost of the nested ``[name, start, dur]`` events over it."""
    pieces = []
    stack: list[tuple[float, str]] = []  # (end, name)
    t = None
    for name, s, d in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][0] <= s:
            end, top = stack.pop()
            if end > t:
                pieces.append((t, end, top))
            t = max(t, end)
        if stack and s > t:
            pieces.append((t, s, stack[-1][1]))
        t = s
        stack.append((s + d, name))
    while stack:
        end, top = stack.pop()
        if end > t:
            pieces.append((t, end, top))
        t = max(t, end)
    return pieces


def to_json(trace: Trace) -> dict:
    return {"devices": trace.devices, "host": trace.host}


def from_json(data: dict) -> Trace:
    return Trace(data["devices"], data["host"])


def from_xplane(path: str) -> Trace:
    """The device planes' op and module events and the host Python
    thread's events of one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices = {}
    host = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev["ops"] = [[op_name(e.name), e.start_ns, e.duration_ns]
                                  for e in line.events]
                elif line.name == MODULES_LINE:
                    dev["modules"] = [[e.name, e.start_ns, e.duration_ns]
                                      for e in line.events]
            devices[plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                events = [[e.name, e.start_ns, e.duration_ns]
                          for e in line.events]
                if any(ev[0].startswith(HOST_MARK) for ev in events):
                    host.extend(events)
    return Trace(devices, host)
