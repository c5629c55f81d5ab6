"""Trace-report CLI: summarise a ``MOMP_TRACE`` JSONL file.

Usage::

    python analysis/trace_report.py /tmp/trace.jsonl          # text tables
    python analysis/trace_report.py /tmp/trace.jsonl --json   # machine form

Text mode prints the per-span phase breakdown, the ring-attention hop
summary (span counts, engines, α+βn transfer fit when the trace carries
two or more hop sizes), recoveries by stamp, and the jit-retrace counters
from the last ``metrics`` snapshot event. ``--json`` emits the same data
as one JSON object (``obs.report.report_dict`` schema) — what the CI
trace cycle asserts against. ``--chrome OUT`` instead exports the spans
to Chrome trace-event JSON (``obs.report.to_chrome``) so the timeline
opens directly in Perfetto / chrome://tracing.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

# A trace file is host-side data; nothing here needs (or should claim)
# the TPU. The fit path imports jax transitively, so pin the platform
# before any package import: a chip belongs to one process, and a second
# TPU process would fight the real workload.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from mpi_and_open_mp_tpu.obs import report  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="analysis/trace_report.py")
    p.add_argument("trace", help="MOMP_TRACE JSONL file to summarise "
                   "(with --fleet: a fleet state DIRECTORY)")
    p.add_argument("--json", action="store_true",
                   help="emit the report as one JSON object")
    p.add_argument("--chrome", metavar="OUT",
                   help="write Chrome trace-event JSON (Perfetto-loadable) "
                   "here instead of reporting")
    p.add_argument("--fleet", action="store_true",
                   help="treat the positional as a fleet state dir and "
                   "merge every worker trace + sidecar into one timeline "
                   "(delegates to analysis/fleet_report.py)")
    p.add_argument("--router-trace", default=None, metavar="PATH",
                   help="with --fleet: the parent's own MOMP_TRACE file")
    args = p.parse_args(argv)

    if args.fleet:
        from analysis import fleet_report as fleet_mod

        argv2 = [args.trace]
        if args.router_trace:
            argv2 += ["--router-trace", args.router_trace]
        if args.chrome:
            argv2 += ["--chrome", args.chrome]
        if args.json:
            argv2.append("--json")
        return fleet_mod.main(argv2)

    try:
        records = report.load(args.trace)
    except (OSError, ValueError) as e:
        print(f"trace_report: {e}", file=sys.stderr)
        return 2
    if args.chrome:
        chrome = report.to_chrome(records)
        with open(args.chrome, "w") as fd:
            json.dump(chrome, fd)
        print(f"wrote {len(chrome['traceEvents'])} trace events "
              f"to {args.chrome}")
        return 0
    rep = report.report_dict(records)
    if args.json:
        print(json.dumps(rep))
    else:
        print(report.render(rep))
    return 0


if __name__ == "__main__":
    sys.exit(main())
