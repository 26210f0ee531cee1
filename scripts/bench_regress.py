#!/usr/bin/env python
"""Diff two benchmark JSON artifacts and fail on regression.

CI guard for the bench trajectory: compare the metrics shared by two
``BENCH_*.json`` (or ``BUSBW_*.json`` / bench one-liner) artifacts and
exit non-zero when any shared metric regressed by more than
``--threshold`` (default 10%).

Accepted file shapes (everything the in-tree benchmarks emit):

* a single JSON object (``bench.py`` / ``gpt_bench.py`` one-liners) —
  its ``metric``/``value`` pair plus any numeric perf fields become
  metrics;
* ``{"summary": {...}, "rows": [...]}`` (``allreduce_bench.py --out``) —
  the summary is read, rows are ignored (per-size noise isn't a metric);
  a ``"sweep"`` list (``--fused-sweep``) is read row-by-row — each entry
  is a gated metric in its own right (per bucket x compressor, named
  without the kernel backend so fused and unfused artifacts diff
  directly);
* a JSON list or JSONL stream of such objects.

Direction is inferred from the metric name: names containing
``ms``/``time``/``latency``/``ttft``/``tpot`` are lower-is-better,
everything else (throughput, busbw, mfu, fractions) higher-is-better —
EXCEPT ratio/rate/acceptance names (``prefix_hit_ratio``,
``spec_accept_per_verify``), which stay higher-is-better even when a
latency token also appears in the name.

Exit codes: 0 ok (improvements included), 1 regression(s), 3 no shared
metrics (a diff that compares nothing must be loud, not green) — pass
``--allow-disjoint`` to downgrade that to 0 for trajectory bootstraps.

Usage::

    python scripts/bench_regress.py BEFORE.json AFTER.json
    python scripts/bench_regress.py old.json new.json --threshold 0.05
"""

from __future__ import annotations

import argparse
import json
import re
import sys

# Numeric fields that are configuration/provenance, not performance —
# a changed seq_len is a different experiment, not a regression.  The
# "metrics" block is the embedded telemetry snapshot (horovod_tpu.obs)
# and "trace" the embedded per-run trace pointer + critical-path report
# (--trace; docs/tracing.md): diagnostic context for a human reading
# the artifact, not a regression signal (counters scale with run
# length, span timings with scheduling noise — not performance).
_NON_METRIC_KEYS = {
    "vs_baseline", "n_params", "seq_len", "vocab_chunk", "elems", "bytes",
    "n_slots", "sizes_swept", "max_elems", "microbatches", "pipeline_depth",
    "bench_buckets", "per_chip_batch", "probe_attempts", "requests",
    "warmup", "iters", "steps_per_call", "metrics", "trace",
    "prefix_shared", "spec_k", "prefix_hit",
    # Fused-sweep structure (allreduce_bench.py --fused-sweep): bucket
    # geometry and the schedule's structural HBM-intermediate count are
    # experiment configuration — the pallas backend's count DROPPING to
    # 0 is the design, not a higher-is-better metric regressing.
    "bucket_elems", "block_size", "hbm_materializations",
    # Quotient of two independently-gated wall-clock metrics (int8 peak
    # over exact peak); gating it too double-counts denominator jitter.
    "int8_vs_exact",
    # Fleet-sim structure (benchmarks/fleet_sim_bench.py): event/check
    # counts scale with the scenario, and fault/scale/kill tallies ARE
    # the scenario — the gated signals are the calibration errors, the
    # violation count (zero-tolerance below), and events_per_s.
    "events", "replicas", "invariant_checks", "faults_injected",
    "kills", "scale_out", "scale_in", "level_transitions", "delivered",
    # The fitted profile and the sim's raw percentiles are calibration
    # INPUTS/outputs whose job is to MATCH, not to shrink — the gated
    # signal is calibration_error_*, their relative difference.
    "profile_ttft_ms_p50", "profile_ttft_ms_p99",
    "sim_ttft_ms_p50", "sim_ttft_ms_p99",
    # Telemetry-plane drill structure (fleet_sim_bench detector phase /
    # serving_bench collector phase): rounds-to-fire are acceptance
    # facts pinned by the drill's own test (<= 3), collection-round and
    # alert tallies are scenario shape, and the overhead multiple is
    # the quotient of two independently-gated TTFTs — the gated
    # signals are detector_violations / false_alert_violations /
    # collector_overhead_violations (zero-tolerance) and the raw
    # latencies.
    "rounds_to_fire_spiral", "rounds_to_fire_convoy", "collect_rounds",
    "alerts_fired", "clean_seeds", "collector_overhead_x",
}

_LOWER_IS_BETTER_TOKENS = ("_ms", "_us", "time", "latency", "ttft",
                           "tpot", "error", "violation")

# Zero-tolerance metrics: the baseline value SHOULD be 0 (invariant
# violations), so the o == 0 "nothing to regress from" skip in
# ``compare`` must not wave new ones through — any increase fails.
_ZERO_TOLERANCE_RE = re.compile(r"violation")

# Override checked FIRST: ratio/rate/acceptance metrics are
# higher-is-better even when the name also carries a latency token
# (``prefix_hit_ratio``, ``spec_accept_per_verify`` — the serving
# bench's cache/speculation quality signals).  Matching is anchored on
# ``_``-separated WORDS, so "separate_ms" cannot false-match "rate"
# and a future "accept_wait_ms" would need its own row here before it
# could flip direction.
_HIGHER_IS_BETTER_RE = re.compile(r"(^|_)(ratio|rate|accept\w*)(_|$)")


def _rows(path: str):
    with open(path) as f:
        text = f.read()
    try:
        doc = json.loads(text)
    except json.JSONDecodeError:
        # JSONL stream: one object per line.
        doc = [json.loads(line) for line in text.splitlines()
               if line.strip()]
    if isinstance(doc, dict):
        if "summary" in doc and isinstance(doc["summary"], dict):
            # Sweep entries (--fused-sweep) gate individually alongside
            # the headline summary; plain "rows" stay diagnostic.
            sweep = [r for r in doc.get("sweep", [])
                     if isinstance(r, dict)]
            return [doc["summary"]] + sweep
        return [doc]
    if isinstance(doc, list):
        out = []
        for item in doc:
            if isinstance(item, dict):
                out.append(item.get("summary", item)
                           if isinstance(item.get("summary"), dict)
                           else item)
        return out
    raise ValueError(f"{path}: unrecognized artifact shape")


def extract_metrics(path: str) -> dict:
    """``{metric_name: value}`` for every numeric perf field in the
    artifact.  A row's headline ``value`` is keyed by its ``metric``;
    auxiliary numeric fields are keyed ``<metric>.<field>`` (or bare
    ``<field>`` for rows without a metric name)."""
    metrics: dict = {}
    for row in _rows(path):
        name = row.get("metric")
        if row.get("error"):
            continue  # a measured outage is not a datapoint to diff
        for key, val in row.items():
            if key in _NON_METRIC_KEYS or isinstance(val, bool):
                continue
            if key.endswith("_est"):
                # Cost-model ESTIMATES (hidden_comm_frac_est, ...) are
                # derived, sometimes from wall-clock bases — jitter
                # there is not a perf regression.
                continue
            if not isinstance(val, (int, float)):
                continue
            if key == "metric":
                continue
            if key == "value" and name:
                metrics[name] = float(val)
            elif name:
                metrics[f"{name}.{key}"] = float(val)
            else:
                metrics[key] = float(val)
    return metrics


def lower_is_better(name: str) -> bool:
    low = name.lower()
    if _HIGHER_IS_BETTER_RE.search(low):
        return False
    return any(tok in low for tok in _LOWER_IS_BETTER_TOKENS)


def compare(old: dict, new: dict, threshold: float):
    """Returns ``(report_rows, regressions)`` over the shared metrics."""
    report, regressions = [], []
    for name in sorted(set(old) & set(new)):
        o, v = old[name], new[name]
        if _ZERO_TOLERANCE_RE.search(name.lower()):
            # 0 is the healthy baseline here: report each new unit as
            # +100% (no relative base exists) and fail on ANY increase.
            change = (v - o) / abs(o) if o else float(v)
            row = {"metric": name, "old": o, "new": v,
                   "change_pct": round(change * 100.0, 2),
                   "direction": "zero_tolerance",
                   "regressed": v > o}
            report.append(row)
            if row["regressed"]:
                regressions.append(row)
            continue
        if o == 0:
            # Nothing to regress FROM (outage rounds emit 0.0); only a
            # direction exists when the old value is meaningful.
            continue
        change = (v - o) / abs(o)
        worse = -change if not lower_is_better(name) else change
        row = {"metric": name, "old": o, "new": v,
               "change_pct": round(change * 100.0, 2),
               "direction": "lower_is_better" if lower_is_better(name)
               else "higher_is_better",
               "regressed": worse > threshold}
        report.append(row)
        if row["regressed"]:
            regressions.append(row)
    return report, regressions


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="fail on >threshold regression between two bench "
                    "artifacts")
    parser.add_argument("old", help="baseline artifact (BENCH_*.json)")
    parser.add_argument("new", help="candidate artifact")
    parser.add_argument("--threshold", type=float, default=0.10,
                        help="relative regression tolerance "
                             "(default 0.10 = 10%%)")
    parser.add_argument("--allow-disjoint", action="store_true",
                        help="exit 0 when the artifacts share no "
                             "metrics (default: exit 3 — a diff that "
                             "compares nothing must not read as green)")
    args = parser.parse_args(argv)

    old = extract_metrics(args.old)
    new = extract_metrics(args.new)
    report, regressions = compare(old, new, args.threshold)
    out = {
        "old": args.old, "new": args.new, "threshold": args.threshold,
        "compared": len(report), "regressions": len(regressions),
        "rows": report,
    }
    print(json.dumps(out, indent=1))
    if not report:
        print(f"bench_regress: no shared metrics between {args.old} and "
              f"{args.new}", file=sys.stderr)
        return 0 if args.allow_disjoint else 3
    if regressions:
        for r in regressions:
            print(f"bench_regress: REGRESSION {r['metric']}: "
                  f"{r['old']} -> {r['new']} ({r['change_pct']:+.2f}%)",
                  file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
