#!/usr/bin/env bash
# Fault-tolerant serving drain — the requeueable form
# of the daemon cycle, replacing the reference's PBS qsub-requeue
# workflow (docs/MIGRATION.md): the first pass admits a mixed-shape
# request burst and drains it through serve.daemon under a write-ahead
# ticket journal; ANY death — polite preemption (scheduler SIGTERM, or
# MOMP_CHAOS preempt=K, exit 75 after checkpointing the queue) or an
# impolite kill -9/OOM that runs no handler at all — leaves either the
# drain checkpoint or the journal behind, and the NEXT pass resumes
# whichever survives (WAL first: it is durable at every instruction,
# not just at the drain). No admitted ticket is ever dropped across
# passes. Idempotent by design: rerun until exit 0.
#
# Usage:
#   launchers/job_serve.sh [--requests=N] [--max-batch=B] [--shapes=S]
#                          [--checkpoint=PATH] [--wal=PATH]
#                          [--wal-fsync=POLICY] [--aot-cache=DIR]
#                          [--seed=K]
set -euo pipefail
cd "$(dirname "$0")/.."

REQUESTS=64
MAXBATCH=8
SHAPES=48x48,64x64
CKPT=/tmp/momp_serve_queue.state
WAL=/tmp/momp_serve.wal
WALFSYNC=every-record
AOTDIR="${MOMP_AOT_CACHE:-/tmp/momp_serve_aot}"
SEED=0
for arg in "$@"; do
  case "$arg" in
    --requests=*)   REQUESTS="${arg#*=}" ;;
    --max-batch=*)  MAXBATCH="${arg#*=}" ;;
    --shapes=*)     SHAPES="${arg#*=}" ;;
    --checkpoint=*) CKPT="${arg#*=}" ;;
    --wal=*)        WAL="${arg#*=}" ;;
    --wal-fsync=*)  WALFSYNC="${arg#*=}" ;;
    --aot-cache=*)  AOTDIR="${arg#*=}" ;;
    --seed=*)       SEED="${arg#*=}" ;;
    *) echo "unknown arg: $arg" >&2; exit 2 ;;
  esac
done

if [ -s "$WAL" ] || [ -f "$CKPT" ]; then
  echo "serve state survives ($WAL / $CKPT); resuming drained tickets" >&2
  python -m mpi_and_open_mp_tpu.serve.daemon \
    --requests 0 --resume --wal "$WAL" --wal-fsync "$WALFSYNC" \
    --aot-cache "$AOTDIR" --checkpoint "$CKPT" --verify
else
  python -m mpi_and_open_mp_tpu.serve.daemon \
    --requests "$REQUESTS" --shapes "$SHAPES" --max-batch "$MAXBATCH" \
    --seed "$SEED" --wal "$WAL" --wal-fsync "$WALFSYNC" \
    --aot-cache "$AOTDIR" --checkpoint "$CKPT" --verify
fi
# Only reached on a clean drain (set -e; a preempted pass exits 75
# above, a killed pass never gets here): drop the consumed state —
# journal, its compaction snapshots, checkpoint, and any stamped
# quarantine copies — so the next invocation starts a fresh burst
# instead of re-serving resolved work. The AOT cache is deliberately
# KEPT: executables are state-free and fingerprint-keyed, and a warm
# cache is the whole point — the next burst's first ticket must not
# pay a trace+compile.
rm -f "$CKPT" "$WAL" "$WAL".snap.* "$WAL".corrupt*
