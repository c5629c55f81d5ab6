"""Fleet trace merge: one Perfetto timeline for the whole fleet.

A cross-process fleet run (``python -m mpi_and_open_mp_tpu.serve.fleet
--dir STATE``) leaves one trace JSONL per worker subprocess
(``worker<i>.trace.jsonl``, plus ``worker<t>.rehome<v>.trace.jsonl`` for
recovery lifetimes), one telemetry sidecar per worker
(``*.telemetry.bin``), and — when the parent ran under ``MOMP_TRACE`` —
the router's own trace with the ``serve.fleet.burn`` /
``serve.fleet.scale`` events. This tool merges them into ONE timeline:

* **Span-id namespacing** — ``obs.trace`` ids are a per-process counter,
  so two workers both emit span id 1; every source file gets its own id
  namespace before the merge (ids and parent links remap together, so
  nesting survives).
* **Per-worker tracks** — each source keeps its own pid, and the merged
  Chrome JSON names each process track after its source
  (``worker0``, ``worker2.rehome1``, ``router``), so the timeline reads
  as one row per worker lifetime.
* **Clock alignment** — telemetry snapshots carry paired (mono, wall)
  stamps sampled together on the heartbeat; the median ``wall - mono``
  per worker is its monotonic→wall offset (``obs.telemetry.
  clock_offset``). Trace ``ts`` values are already wall-clock; the
  offsets map the SIDECAR series onto the same axis, emitted as Perfetto
  counter tracks (queue depth / resolved per worker).

Usage::

    python analysis/fleet_report.py STATE_DIR --chrome merged.json
    python analysis/fleet_report.py STATE_DIR --json
    python analysis/trace_report.py STATE_DIR --fleet   # same thing

The summary JSON answers the drill questions directly: every worker
track present, burn event preceding the scale decision, snapshot loss
per worker bounded to the dead one's last interval.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

# Host-side analysis; never claim the TPU a running workload may hold.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from mpi_and_open_mp_tpu.obs import report  # noqa: E402
from mpi_and_open_mp_tpu.obs import telemetry  # noqa: E402

#: Id-namespace stride per source file: far above any real per-process
#: span count, so remapped ids never collide across sources.
_ID_STRIDE = 10_000_000


def discover(state_dir: str, router_trace: str | None = None) -> dict:
    """The fleet run's observability files, by role. Worker stems sort
    so ``worker10`` follows ``worker9`` (and rehome lifetimes follow
    their target's base stem)."""
    traces = sorted(glob.glob(os.path.join(state_dir, "worker*.trace.jsonl")))
    sidecars = sorted(glob.glob(os.path.join(state_dir,
                                             "worker*.telemetry.bin")))
    return {
        "worker_traces": traces,
        "sidecars": sidecars,
        "router_trace": (router_trace if router_trace
                         and os.path.exists(router_trace) else None),
    }


def _label(path: str) -> str:
    """``.../worker2.rehome1.trace.jsonl`` → ``worker2.rehome1``."""
    base = os.path.basename(path)
    for suffix in (".trace.jsonl", ".telemetry.bin"):
        if base.endswith(suffix):
            return base[: -len(suffix)]
    return base


def merge_traces(sources: list[tuple[str, list[dict]]]) -> list[dict]:
    """Merge per-process records under per-source id namespaces. Each
    source's span ids (a per-process counter starting at 1) shift by a
    distinct stride; parent links shift with them, so parentage — and
    therefore Perfetto track assignment — survives the merge intact."""
    merged: list[dict] = []
    for fi, (label, records) in enumerate(sources):
        base = (fi + 1) * _ID_STRIDE
        for r in records:
            r = dict(r)
            if isinstance(r.get("id"), int):
                r["id"] = base + r["id"]
            if isinstance(r.get("parent"), int):
                r["parent"] = base + r["parent"]
            r.setdefault("attrs", {})
            r["attrs"] = dict(r["attrs"] or {}, track=label)
            merged.append(r)
    merged.sort(key=lambda r: r.get("ts", 0.0))
    return merged


def _track_names(sources: list[tuple[str, list[dict]]]) -> dict[int, str]:
    """pid → source label (each subprocess owns its pid; a shared trace
    appended by several runs keeps the label of its first writer)."""
    names: dict[int, str] = {}
    for label, records in sources:
        for r in records:
            pid = r.get("pid")
            if isinstance(pid, int) and pid not in names:
                names[pid] = label
    return names


def to_chrome(sources: list[tuple[str, list[dict]]],
              rollup_series: dict | None = None) -> dict:
    """One Chrome trace-event JSON for the whole fleet: merged spans on
    per-worker (per-pid) tracks, process tracks named after their source
    file, and — when sidecar series are supplied — per-worker Perfetto
    counter tracks (queue depth, resolved) placed on the wall axis via
    the worker's clock offset."""
    merged = merge_traces(sources)
    chrome = report.to_chrome(merged)
    names = _track_names(sources)
    for ev in chrome["traceEvents"]:
        if ev.get("ph") == "M" and ev.get("name") == "process_name":
            pid = ev.get("pid")
            if pid in names:
                ev["args"]["name"] = f"{names[pid]} (pid {pid})"
    label_pid = {label: pid for pid, label in names.items()}
    for label, series in (rollup_series or {}).items():
        snaps = series.get("snapshots") or []
        offset = telemetry.clock_offset(snaps)
        if offset is None:
            continue
        pid = label_pid.get(label, 0)
        for s in snaps:
            counters = s.get("counters") or {}
            wall_us = (s["mono"] + offset) * 1e6
            for cname in ("depth", "resolved"):
                if cname in counters:
                    chrome["traceEvents"].append({
                        "ph": "C", "name": f"{label}.{cname}",
                        "ts": wall_us, "pid": pid, "tid": 0,
                        "args": {cname: counters[cname]},
                    })
    return chrome


def fleet_report(state_dir: str, router_trace: str | None = None,
                 chrome_out: str | None = None) -> dict:
    """Merge a fleet state dir's traces + sidecars; returns the summary
    dict (and writes the merged Chrome JSON when ``chrome_out``)."""
    from mpi_and_open_mp_tpu.serve.router import FleetRollup

    found = discover(state_dir, router_trace)
    sources: list[tuple[str, list[dict]]] = []
    load_errors: list[str] = []
    for path in found["worker_traces"]:
        try:
            sources.append((_label(path), report.load(path)))
        except (OSError, ValueError) as e:
            # A killed worker's trace may end mid-line; its intact
            # prefix still merges. Fall back to a line-tolerant parse.
            load_errors.append(str(e))
            sources.append((_label(path), _lenient_load(path)))
    if found["router_trace"]:
        try:
            sources.append(("router", report.load(found["router_trace"])))
        except (OSError, ValueError) as e:
            load_errors.append(str(e))
            sources.append(("router", _lenient_load(found["router_trace"])))

    rollup = FleetRollup()
    series: dict[str, dict] = {}
    for path in found["sidecars"]:
        label = _label(path)
        rep = telemetry.read_frames(path)
        rollup.truncated += rep["truncated"]
        for s in rep["snapshots"]:
            rollup.ingest(s, worker=label)
        series[label] = rep

    merged = merge_traces(sources)
    burn_events = [r for r in merged if r.get("kind") == "event"
                   and r.get("name") == "serve.fleet.burn"]
    scale_events = [r for r in merged if r.get("kind") == "event"
                    and r.get("name") == "serve.fleet.scale"]
    burn_precedes_scale = None
    if burn_events and scale_events:
        burn_precedes_scale = (min(e.get("ts", 0.0) for e in burn_events)
                               <= min(e.get("ts", 0.0) for e in scale_events))

    per_worker_loss = {
        label: {"snapshots": len(rep["snapshots"]),
                "truncated": rep["truncated"]}
        for label, rep in series.items()
    }
    summary = {
        "state_dir": state_dir,
        "sources": [label for label, _ in sources],
        "records": len(merged),
        "tracks": sorted({label for label, recs in sources if recs}),
        "load_errors": load_errors,
        "telemetry": rollup.summary() if series else None,
        "clock_offsets": rollup.clock_offsets() if series else None,
        "per_worker_sidecar": per_worker_loss,
        "burn_events": len(burn_events),
        "scale_events": [
            {"ts": e.get("ts"), **(e.get("attrs") or {})}
            for e in scale_events
        ],
        "burn_precedes_scale": burn_precedes_scale,
    }
    if chrome_out:
        chrome = to_chrome(sources, series)
        with open(chrome_out, "w") as fd:
            json.dump(chrome, fd)
        summary["chrome"] = chrome_out
        summary["chrome_events"] = len(chrome["traceEvents"])
    return summary


def _lenient_load(path: str) -> list[dict]:
    """Best-effort record parse: skip unparseable lines instead of
    raising — the shape of a trace file whose writer was killed."""
    records: list[dict] = []
    try:
        with open(path) as fd:
            for line in fd:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if isinstance(rec, dict) and "kind" in rec:
                    records.append(rec)
    except OSError:
        pass
    return records


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="analysis/fleet_report.py")
    p.add_argument("state_dir", help="fleet run state dir (--dir)")
    p.add_argument("--router-trace", default=None, metavar="PATH",
                   help="the parent's MOMP_TRACE file (burn/scale events)")
    p.add_argument("--chrome", default=None, metavar="OUT",
                   help="write the merged Perfetto timeline here")
    p.add_argument("--json", action="store_true",
                   help="emit the summary as one JSON object")
    args = p.parse_args(argv)
    if not os.path.isdir(args.state_dir):
        print(f"fleet_report: not a directory: {args.state_dir}",
              file=sys.stderr)
        return 2
    summary = fleet_report(args.state_dir, args.router_trace, args.chrome)
    if args.json:
        print(json.dumps(summary))
    else:
        print(f"fleet: {len(summary['sources'])} trace sources, "
              f"{summary['records']} records, tracks: "
              f"{', '.join(summary['tracks']) or '-'}")
        tel = summary["telemetry"]
        if tel:
            loss = tel["loss"]
            print(f"telemetry: {tel['snapshots']} snapshots, "
                  f"resolved={tel['resolved']} shed={tel['shed']} "
                  f"p50={tel['p50_s']}s p99={tel['p99_s']}s "
                  f"loss={loss['lost']}/{loss['expected']}")
        if summary["scale_events"]:
            print(f"scale decisions: {len(summary['scale_events'])} "
                  f"(burn events: {summary['burn_events']}, "
                  f"burn precedes scale: {summary['burn_precedes_scale']})")
        if summary.get("chrome"):
            print(f"wrote {summary['chrome_events']} trace events to "
                  f"{summary['chrome']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
