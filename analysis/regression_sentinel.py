"""Regression sentinel: gate the newest ledger entry against its history.

Usage::

    python analysis/regression_sentinel.py LEDGER
    python analysis/regression_sentinel.py LEDGER --n 5 --noise 0.1 \
        --match metric,shape,dtype,steps,batch

Compares the NEWEST non-error ledger entry (``obs.ledger`` schema)
against a rolling median-of-N baseline over the previous entries with the
same workload key, and prints ONE JSON verdict line — the CI sentinel
job gates on the exit code:

* 0 — ``"pass"`` (every watched rate within the noise floor, no engine
  downgrade) or ``"no-baseline"`` (first run of a configuration).
* 1 — ``"fail"``: a watched rate regressed past the noise floor, or the
  engine/backend provenance downgraded (pallas→jnp, TPU→CPU).
* 2 — unreadable/malformed ledger.

The match key deliberately EXCLUDES topology and engine by default: a run
that fell back to CPU must land in the same comparison group as its
real-chip history (that is the regression), not escape into a fresh key.
Add fields via ``--match`` for per-topology trending instead.

Rates are judged against the MEDIAN of the baseline window (robust to a
single outlier run); provenance against the BEST rank the window reached
(one good run proves the configuration can run that engine, so anything
lower is a downgrade until it ages out of the window). End-to-end wall
seconds are deliberately not watched — they carry the fixed host
dispatch and sync cost, which is noise here; the steady-state/differenced
rates are the signal.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

# Verdicts are host-side work over a JSONL file; never touch the chip.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from mpi_and_open_mp_tpu.obs import ledger  # noqa: E402

#: Record fields to judge — checked whenever the field is present on
#: the candidate AND at least one baseline record. Throughput numbers
#: are steady-state / differenced (RTT-cancelled); the serve latency
#: percentiles are wall-clock but CPU-mesh-stable (the daemon phase has
#: no device RTT in its latency path on the CI runner). Directions come
#: from :func:`direction_for` — keyed off the metric NAME, so a new
#: bench field gets the right polarity by naming convention instead of
#: silently defaulting to higher-is-better.
WATCH_FIELDS = (
    "value",
    "sharded_steady_cups",
    "batched_cups",
    "batched_steady_cups",
    "batched_requests_per_sec",
    # Board-sliced batched engine (PR 10): the raw rate plus its ratio
    # over the vmapped cell-packed baseline measured in the same process
    # (the ratio is RTT- and machine-noise-cancelled, so a quiet erosion
    # of the layout's advantage trips the sentinel even when absolute
    # rates drift together).
    "bitsliced_cups",
    "vs_cellpacked",
    "attention_32k_causal_tflops",
    "attention_32k_grad_tflops",
    "attention_32k_causal_sec",
    "attention_32k_grad_sec",
    "serve_requests_per_sec",
    "serve_p50_latency_s",
    "serve_p99_latency_s",
    "serve_wal_bytes",
    "serve_wal_fsync_s",
    # AOT warm-start gates (all lower-is-better by the _s suffix rule):
    # cold = trace+compile in the first ticket's path, warm = pure
    # deserialization — a warm first-result that regresses toward cold
    # means the executable cache stopped working.
    "serve_cold_first_result_s",
    "serve_aot_first_result_s",
    "serve_aot_deserialize_s",
    # Sharded fleet (PR 11): aggregate throughput/latency across the
    # 3-worker router, plus the wedge-to-last-rehomed-resolution time
    # from the kill drill — recovery regressing means the heartbeat →
    # WAL replay → re-home ladder got slower (all polarities by name:
    # per_sec higher, _s lower).
    "fleet_requests_per_sec",
    "fleet_p99_latency_s",
    "fleet_kill_recovery_s",
    # Device-resident session pool (PR 12): the resident step rate, its
    # ratio over the ship-boards-every-call baseline measured in the
    # same process (RTT- and noise-cancelled, like vs_cellpacked), the
    # resident-path latency tail, and the pool's eviction count for the
    # phase — evictions climbing at fixed session count means the
    # residency budget or the compactor regressed (``evict`` is in the
    # lower-is-better vocabulary).
    "session_requests_per_sec",
    "session_vs_ship",
    "session_p99_latency_s",
    "pool_evictions",
    # Sparse active-tile engine (PR 13): the sparse rate and its ratio
    # over the dense roll engine measured in the same process (RTT- and
    # noise-cancelled, like vs_cellpacked) — both higher-is-better by
    # the cups/vs naming rules. ``active_frac`` is deliberately NOT
    # watched: it describes the workload's liveness, not the engine's
    # quality (a busier seed board is not a regression); it rides the
    # line as context for the two rates that ARE watched.
    "sparse_cups",
    "sparse_vs_dense",
    # Autotuner (PR 14): the tuned engine's rate and its ratio over the
    # heuristic choice measured in the same process (RTT- and
    # noise-cancelled, like vs_cellpacked; >= 1.0 by construction since
    # the heuristic is in the race) — both higher-is-better by the
    # cups/vs naming rules. A vs_heuristic sliding toward 1.0 means the
    # tuner stopped finding wins; tuned_cups falling means the plan it
    # persists got slower.
    "tuned_cups",
    "vs_heuristic",
    # Persistent halo plans (PR 15): the overlapped sharded rate and its
    # ratio over the sequential schedule measured in the same process
    # (RTT- and noise-cancelled, like vs_heuristic) — both
    # higher-is-better by the cups/vs naming rules. A vs_sequential
    # sliding toward 1.0 means the ghost exchange stopped hiding behind
    # the interior stencil.
    "sharded_overlap_cups",
    "vs_sequential",
    # Sparse x sharded (PR 16): the composed engine's rate and its
    # ratios over the dense sharded schedule and the single-device
    # sparse engine, measured in the same process (RTT- and noise-
    # cancelled, like vs_sequential) — all higher-is-better by the
    # cups/vs naming rules. vs_dense sliding toward 1.0 means per-round
    # cost stopped tracking the live area; vs_single sliding down means
    # the mesh stopped paying for itself. ``active_frac`` stays
    # unwatched here for the same reason as PR 13's.
    "sparse_sharded_cups",
    "sparse_sharded_vs_dense",
    "sparse_sharded_vs_single",
    # Elastic fleet under open-loop load (PR 17): steady-state goodput
    # at the saturation sweep's knee rung (higher by default — "rps"
    # deliberately avoids the _s suffix), the extreme-tail latency at
    # that rung (lower by the latency rule), and the wedge→REJOIN→
    # recovered time from the membership drill (lower by the _s rule) —
    # recovery regressing means the resume-from-WAL + ring re-entry +
    # claim ladder got slower. The per-rung curve rides the JSON line
    # as context; the knee scalars are what the sentinel judges.
    "loadgen_goodput_rps",
    "loadgen_p999_latency_s",
    "rejoin_recovery_s",
    # Ring-attention hop prefetch (PR 18): the prefetched ring's
    # arithmetic rate (higher by the tflops rule) and the per-step K/V
    # transfer time left EXPOSED after the double-slot schedule hides
    # what it can (lower by the _s rule — this is the quantity the
    # prefetch exists to shrink, the attention twin of
    # sharded_exposed_s). ring_exposed_s growing back toward the
    # rotation-priced transfer time means the issue-first schedule
    # stopped hiding the wire; ring_prefetch_tflops falling means the
    # deeper pipeline itself got slower. The engine-provenance side is
    # covered separately: losing the ``:pf`` stamp suffix (the
    # MOMP_RING_PREFETCH kill switch left on) is a downgrade within the
    # pallas tier — see ``_prefetch_rank``.
    "ring_prefetch_tflops",
    "ring_exposed_s",
    # Fleet telemetry plane (PR 19): snapshot loss is the fraction of
    # the per-worker time series the rollup never received (seq gaps +
    # truncated sidecar frames) — growing loss means the shipping path
    # is dropping intervals (lower by the ``loss`` rule). The burn-rate
    # peak at the saturation knee is the long-window error-budget
    # consumption while the SLO is still MET — recorded headroom; a
    # rising peak means the fleet runs ever closer to its budget at the
    # same capacity number (lower by the ``burn`` rule).
    "telemetry_snapshot_loss_frac",
    "loadgen_burn_rate_peak",
    # Wide-radius engine families (PR 20): per-family steady rates from
    # the radius crossover sweep, recorded at the widest
    # parity-clean radius measured (higher by the cups rule), plus the
    # best family-vs-offset ratio over the radius >= 8 cells (higher by
    # default — the ratio is same-process, RTT- and noise-cancelled
    # like vs_heuristic). vs_offset_best sliding toward 1.0 means the
    # restructured aggregation stopped beating the offset walk on the
    # workload it exists for; the kill-switch flip (MOMP_ENGINE_FAMILY=
    # offset left pinned) is caught by the ``engine_family`` provenance
    # field, not a rate.
    "radius_ab_offset_cups",
    "radius_ab_sep_cups",
    "radius_ab_fft_cups",
    "radius_ab_vs_offset_best",
)


def direction_for(field: str) -> str:
    """Judging polarity for a watched metric name.

    Rates (``*per_sec*``, ``*cups*``, ``*tflops*``) are higher-is-better
    and take precedence — ``batched_requests_per_sec`` must NOT fall
    through to the ``_sec`` latency rule. Durations, badness counts and
    overhead volumes (``*latency*``, ``*_sec``/``*_seconds``/``*_s``/
    ``*_bytes`` suffixes, ``shed``/``degrad`` counters) are
    lower-is-better: a p99 that GROWS is the regression, and so is a
    write-ahead-journal durability tax that swells (``serve_wal_bytes``
    volume, ``serve_wal_fsync_s`` sync stall). Telemetry badness is
    lower-is-better too: ``loss`` (snapshot series the rollup never
    saw) and ``burn`` (SLO error-budget consumption rate). Anything
    unrecognised defaults to higher-is-better (the historical
    behaviour for throughput fields).
    """
    if "per_sec" in field or "cups" in field or "tflops" in field:
        return "higher"
    if ("latency" in field or "shed" in field or "degrad" in field
            or "evict" in field or "loss" in field or "burn" in field
            or field.endswith(("_sec", "_seconds", "_s", "_bytes"))):
        return "lower"
    return "higher"

#: Record fields carrying engine provenance, rank-compared for downgrades.
PROVENANCE_FIELDS = ("impl", "batch_engine", "batch_pack_layout",
                     "attention_engine", "attention_hop_engine",
                     "attention_hop_engine_bwd", "sparse_engine",
                     "sharded_halo", "sparse_sharded_engine",
                     "ring_hop_engine", "ring_hop_engine_bwd",
                     "engine_family")

#: ``workload`` joined in PR 13: a heat line and a life line of the same
#: shape are different rules — they must never share a baseline group
#: (pre-stencil entries default to "life" via the ledger key defaults).
DEFAULT_MATCH = ("metric", "shape", "dtype", "steps", "batch", "resident",
                 "workload")

_BACKEND_RANK = {"cpu": 0, "gpu": 1, "tpu": 2}

#: ``plan_source`` vocabulary, rank-compared like backends: a line that
#: ran under a tuned plan (freshly measured or loaded from the store —
#: equally good, both are the tuner's measured choice) regressing to
#: heuristic routing means the plan store silently stopped applying
#: (quarantined plans, a bad MOMP_TUNE_PLANS path, MOMP_TUNE=0 leaking
#: into CI) — the same downgrade shape as a TPU→CPU backend change.
_PLAN_RANK = {"store": 2, "fresh": 2, "heuristic": 1}


def engine_rank(stamp) -> int:
    """Coarse engine tiers: the board-sliced batched layout > repo
    Pallas kernels > packed/fused native paths > jnp/XLA folds (the
    cell-packed ``batch_pack_layout`` vocabulary lands in the bottom
    tier, so ``bitsliced -> cell-packed`` is a downgrade exactly like
    ``pallas -> jnp``). Suffixes (``:b1024``, ``:zz``, ``:bB``) and the
    ``batch:``/``local:`` prefixes don't change the tier. The sparse
    active-tile stamp (``sparse:t<tile>``) sits above everything dense:
    on the mostly-dead workload it serves, a silent flip to
    ``dense:crossover`` is THE downgrade this field exists to catch.
    The halo schedule stamp (``overlap:*`` vs ``seq:*``) ranks overlap
    above every sequential tier: a ``sharded_halo`` flipping from
    ``overlap:deferred`` to ``seq:halo`` (the MOMP_HALO_OVERLAP=0 kill
    switch left on, or a geometry gate silently engaging) is a
    provenance downgrade even when the rates are within noise. The
    engine-family stamps (PR 20) rank ``fft`` above ``sep`` above the
    offset table: on the wide-radius workloads those families exist
    for, an ``fft -> offset`` flip on the same configuration (the
    MOMP_ENGINE_FAMILY=offset kill switch left pinned) is exactly the
    silent O(r^2·n) regression this field exists to catch — ``offset``
    itself falls through to the bottom tier. Matching is exact or
    affixed (``fft``/``fft:*``/``*:fft``) so ``seq:halo`` never reads
    as a ``sep`` stamp."""
    s = str(stamp or "")
    for prefix in ("batch:", "local:"):
        if s.startswith(prefix):
            s = s[len(prefix):]
    if s == "fft" or s.startswith("fft:") or s.endswith(":fft"):
        return 5
    if s == "sep" or s.startswith("sep:") or s.endswith(":sep"):
        return 4
    if s.startswith("sparse"):
        return 5
    if s.startswith("overlap:"):
        return 4
    if s.startswith("bitsliced"):
        return 4
    if "pallas" in s:
        return 3
    if s.startswith(("bitfused", "vmem", "grid", "fused", "frame")):
        return 2
    return 1 if s else 0


def _prefetch_rank(stamp) -> int:
    """Within-tier schedule sub-rank: the ring hop stamps carry a
    trailing ``:pf`` when the double-slot K/V prefetch is engaged
    (``context._ring_prefetch_on``). Losing it at the same engine tier
    — the MOMP_RING_PREFETCH kill switch left on after a chaos drill,
    exactly like MOMP_HALO_OVERLAP's failure shape — is a provenance
    downgrade even when the rates sit inside the noise floor."""
    return 1 if ":pf" in str(stamp or "") else 0


def _provenance_key(stamp):
    """Sort/compare key for provenance stamps: engine tier first, the
    schedule sub-rank as tiebreak (a tier upgrade always wins; a same-
    tier prefetch loss still counts as a downgrade)."""
    return (engine_rank(stamp), _prefetch_rank(stamp))


def _usable(entry: dict) -> bool:
    rec = entry.get("record") or {}
    return "error" not in rec


def _match_key(entry: dict, fields: tuple[str, ...]) -> str:
    return ledger.config_key(entry, fields)


def evaluate(entries: list[dict], *, n: int = 5, noise: float = 0.1,
             match: tuple[str, ...] = DEFAULT_MATCH) -> dict:
    """The verdict dict for the newest usable entry of ``entries``."""
    usable = sorted((e for e in entries if _usable(e)),
                    key=lambda e: e.get("ts", 0.0))
    if not usable:
        return {"sentinel": "momp-regression-sentinel/1",
                "verdict": "no-baseline",
                "reason": "no non-error entries in the ledger"}
    candidate = usable[-1]
    key = _match_key(candidate, match)
    pool = [e for e in usable[:-1] if _match_key(e, match) == key][-n:]
    verdict = {
        "sentinel": "momp-regression-sentinel/1",
        "key": key,
        "candidate_source": candidate.get("source", "?"),
        "candidate_ts": candidate.get("ts"),
        "candidate_git_sha": candidate.get("git_sha", "?"),
        "baseline_n": len(pool),
        "noise_floor": noise,
    }
    if not pool:
        verdict["verdict"] = "no-baseline"
        return verdict

    cand_rec = candidate.get("record") or {}
    regressions, downgrades, checked = [], [], []

    for field in WATCH_FIELDS:
        direction = direction_for(field)
        new = cand_rec.get(field)
        base_vals = [e["record"][field] for e in pool
                     if isinstance((e.get("record") or {}).get(field),
                                   (int, float))]
        if not isinstance(new, (int, float)) or not base_vals:
            continue
        baseline = statistics.median(base_vals)
        if baseline == 0:
            continue
        checked.append(field)
        drop = ((baseline - new) / baseline if direction == "higher"
                else (new - baseline) / abs(baseline))
        if drop > noise:
            regressions.append({
                "field": field, "direction": direction,
                "new": new, "baseline_median": baseline,
                "drop": round(drop, 4),
            })

    # Backend/platform downgrade: a TPU history judged against a CPU run.
    new_backend = candidate.get("platform") or cand_rec.get("backend")
    base_backends = [e.get("platform") or (e.get("record") or {}).get(
        "backend") for e in pool]
    base_backends = [b for b in base_backends if b]
    if new_backend and base_backends:
        checked.append("platform")
        best = max(base_backends, key=lambda b: _BACKEND_RANK.get(b, 0))
        if (_BACKEND_RANK.get(new_backend, 0)
                < _BACKEND_RANK.get(best, 0)):
            item = {"field": "platform", "new": new_backend,
                    "baseline_best": best}
            if cand_rec.get("fallback_reason"):
                item["fallback_reason"] = cand_rec["fallback_reason"]
            downgrades.append(item)

    # Plan-provenance downgrade: tuned (store/fresh) -> heuristic means
    # the autotuner's measured decision silently stopped being applied.
    new_plan = cand_rec.get("plan_source")
    base_plans = [(e.get("record") or {}).get("plan_source") for e in pool]
    base_plans = [p for p in base_plans if p in _PLAN_RANK]
    if new_plan in _PLAN_RANK and base_plans:
        checked.append("plan_source")
        best = max(base_plans, key=lambda p: _PLAN_RANK[p])
        if _PLAN_RANK[new_plan] < _PLAN_RANK[best]:
            item = {"field": "plan_source", "new": new_plan,
                    "baseline_best": best}
            if cand_rec.get("fallback_reason"):
                item["fallback_reason"] = cand_rec["fallback_reason"]
            downgrades.append(item)

    for field in PROVENANCE_FIELDS:
        new = cand_rec.get(field)
        base = [(e.get("record") or {}).get(field) for e in pool]
        base = [b for b in base if b is not None]
        if new is None or not base:
            continue
        checked.append(field)
        best = max(base, key=_provenance_key)
        if _provenance_key(new) < _provenance_key(best):
            downgrades.append({"field": field, "new": new,
                               "baseline_best": best})

    verdict.update({
        "checked": checked,
        "regressions": regressions,
        "downgrades": downgrades,
        "verdict": "fail" if (regressions or downgrades) else "pass",
    })
    return verdict


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="analysis/regression_sentinel.py")
    p.add_argument("ledger", help="obs.ledger JSONL file to judge")
    p.add_argument("--n", type=int, default=5, metavar="N",
                   help="rolling baseline window per configuration key "
                   "(median of the last N matching runs; default 5)")
    p.add_argument("--noise", type=float, default=0.1, metavar="FRAC",
                   help="noise floor: drops up to this fraction of the "
                   "baseline median pass (default 0.1)")
    p.add_argument("--match", default=",".join(DEFAULT_MATCH),
                   metavar="FIELDS",
                   help="comma-separated key fields runs must share to be "
                   "comparable (default %(default)s; add 'topology' or "
                   "'engine' for per-topology trending)")
    args = p.parse_args(argv)

    try:
        entries = ledger.load(args.ledger)
    except (OSError, ValueError) as e:
        print(f"regression_sentinel: {e}", file=sys.stderr)
        return 2
    match = tuple(f.strip() for f in args.match.split(",") if f.strip())
    verdict = evaluate(entries, n=args.n, noise=args.noise, match=match)
    print(json.dumps(verdict))
    return 1 if verdict["verdict"] == "fail" else 0


if __name__ == "__main__":
    sys.exit(main())
