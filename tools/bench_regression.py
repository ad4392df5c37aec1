#!/usr/bin/env python3
"""Gate the latest BENCH round against the trajectory (ISSUE 7
satellite).

The driver captures one `BENCH_r<N>.json` per round; regressions so
far have been caught by a human reading BASELINE.md. This tool makes
the check mechanical:

  python tools/bench_regression.py            # repo root, defaults
  python tools/bench_regression.py --dir . --band 0.05

For each gated metric (higher-is-better throughput figures, plus a
LOWER_IS_BETTER set — the elastic-recovery costs — where the band
flips into a ceiling), the LATEST round is compared against the
MEDIAN of the previous `--window` rounds that report the metric. The tolerance band is the
larger of `--band` (the noise floor — slope timing jitters a few
percent run-to-run) and the observed relative
spread of those prior rounds (median absolute deviation × 2 / median),
so a historically noisy metric doesn't cry wolf and a historically
flat one stays tight. Exit codes: 0 = no regression (or not enough
history), 1 = regression, 2 = usage error. `--strict` makes
insufficient history an error instead of a pass.

Accepts both file shapes: the driver wrapper (`{"parsed": {...}}`)
and bench.py's bare result object.

`--kind multichip` gates the MULTICHIP_r*.json trajectory the same
way (tools/multichip_bench.py's scaling-efficiency rounds; the gated
set is MULTICHIP_METRICS). Seed rounds that are driver failure
records ({rc, ok, tail} — no metrics) are skipped like any other
result-free file.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import Any, Dict, List, Optional, Tuple

# higher-is-better figures gated by default; ms_per_step & friends are
# redundant inverses of these. Schema growth rule: rounds predating a
# metric (e.g. the round-13 `sparse_*` family) simply lack the key —
# they are excluded from that metric's history and the LATEST round
# gates on the metrics it actually reports (older rounds effectively
# gate on `value` and whatever else they carry); a missing or
# non-numeric key is never fatal to the gate.
# Per-phase attribution of the sparse step (ISSUE 15): bench.py emits
# phase_<name>_ms every round; gated LOWER-is-better so a single phase
# regressing 2x fails the gate even while the headline pc/s holds
# (slack created by one phase's win can hide another's regression in
# any whole-step figure). These literals are the canonical set;
# default-set runs (no --metrics) additionally auto-gate ANY other
# phase_*_ms key the rounds carry (a mesh capture's allreduce pair,
# the int8 backward_apply remainder), so no phase escapes the gate.
PHASE_MS_METRICS = ("phase_embed_gather_ms", "phase_concat_dense_ms",
                    "phase_forward_pool_ms", "phase_backward_ms",
                    "phase_table_apply_ms")

DEFAULT_METRICS = ("value", "int8_pc_per_sec", "transformer_pc_per_sec",
                   "fwd_bwd_floor_pc_per_sec", "sparse_pc_per_sec"
                   ) + PHASE_MS_METRICS

# The MULTICHIP trajectory (tools/multichip_bench.py, round 14):
# scaling efficiency is the headline — a pod that got faster per chip
# but lost more to the process boundary is a regression this gate must
# see; multi_pc_per_sec catches absolute multi-leg slowdowns the ratio
# could mask (both legs regressing together). The kill-mid-run leg
# (ISSUE 13) adds the recovery-cost pair — gated LOWER-is-better: a
# re-form that loses more steps or takes longer to reach its first
# post-resize step is the regression. host_skew_ratio (ISSUE 17) is
# the cohort-evenness gate: worst member step p50 over the cohort
# median — a straggler host taxes every step through the lock-step
# all-reduce, and the ratio catches it even when the summed
# throughput still squeaks past its floor.
MULTICHIP_METRICS = ("scaling_efficiency", "multi_pc_per_sec",
                     "recovery_steps_lost", "recovery_seconds",
                     "host_skew_ratio")

# The SERVING trajectory (tools/serving_bench.py, ISSUE 18): the
# client-observed tail and the sustained completion rate through the
# whole external plane (HTTP front-end -> replica pool -> batcher ->
# device). p99 is the SLO figure — gated LOWER-is-better; req/s
# catches an absolute throughput slide the tail could mask (queue
# shrinks because everything sheds).
SERVING_METRICS = ("serving_p99_ms", "serving_req_per_sec")

# Metrics where SMALLER is healthier: the band becomes a ceiling
# (baseline * (1 + band)) instead of a floor. Everything else in the
# gate — median baseline, MAD-widened band, history windowing — is
# direction-agnostic. Any phase_*_ms key rides the same direction via
# _lower_is_better (per-phase device times are costs, not throughput).
LOWER_IS_BETTER = frozenset({"recovery_steps_lost",
                             "recovery_seconds",
                             "host_skew_ratio",
                             "serving_p99_ms"})


def _lower_is_better(metric: str) -> bool:
    return metric in LOWER_IS_BETTER or (
        metric.startswith("phase_") and metric.endswith("_ms"))

KINDS = {
    "bench": ("BENCH_r*.json", DEFAULT_METRICS),
    "multichip": ("MULTICHIP_r*.json", MULTICHIP_METRICS),
    "serving": ("SERVING_r*.json", SERVING_METRICS),
}


def _round_re(pattern: str) -> "re.Pattern[str]":
    """`BENCH_r*.json` -> a regex capturing the round number."""
    return re.compile(
        re.escape(pattern).replace(r"\*", r"(\d+)") + "$")


def load_rounds(dir_path: str, pattern: str = "BENCH_r*.json"
                ) -> List[Tuple[int, Dict[str, Any]]]:
    """[(round_n, result_dict)] sorted by round. Files that carry no
    result (a failed round's wrapper — e.g. the seed MULTICHIP rounds,
    whose shape is the driver's {rc, ok, tail} failure record) are
    skipped, not fatal."""
    round_re = _round_re(pattern)
    rounds = []
    for path in glob.glob(os.path.join(dir_path, pattern)):
        m = round_re.search(os.path.basename(path))
        if not m:
            continue
        try:
            with open(path, encoding="utf-8") as f:
                obj = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            print(f"warning: skipping unreadable {path}: {e}",
                  file=sys.stderr)
            continue
        result = obj.get("parsed") if isinstance(obj, dict) else None
        if result is None and isinstance(obj, dict) \
                and ("value" in obj
                     or obj.get("schema") in ("multichip", "serving")):
            result = obj  # bench/multichip/serving bare round object
        if not isinstance(result, dict):
            print(f"warning: {path} carries no parsed bench result; "
                  "skipped", file=sys.stderr)
            continue
        rounds.append((int(m.group(1)), result))
    rounds.sort()
    return rounds


def _num(res: Dict[str, Any], metric: str) -> Optional[float]:
    """The metric's finite numeric value, or None when the round
    predates the metric (mixed-schema history) or carries a
    non-numeric placeholder — either way the round is excluded from
    this metric's series instead of crashing the gate."""
    v = res.get(metric)
    if isinstance(v, bool) or not isinstance(v, (int, float)):
        return None
    v = float(v)
    return v if v == v and v not in (float("inf"), float("-inf")) \
        else None


def _median(vals: List[float]) -> float:
    s = sorted(vals)
    n = len(s)
    mid = n // 2
    return s[mid] if n % 2 else (s[mid - 1] + s[mid]) / 2.0


def check_metric(metric: str, history: List[Tuple[int, float]],
                 latest_round: int, latest: float,
                 band_floor: float, min_history: int
                 ) -> Dict[str, Any]:
    """One metric's verdict row. `history` excludes the latest round.
    LOWER_IS_BETTER metrics regress when the latest rises ABOVE the
    banded ceiling; everything else when it falls below the floor."""
    row: Dict[str, Any] = {"metric": metric, "round": latest_round,
                           "latest": latest}
    if len(history) < min_history:
        row.update(status="skip",
                   note=f"history {len(history)} < {min_history}")
        return row
    values = [v for _r, v in history]
    baseline = _median(values)
    lower_better = _lower_is_better(metric)
    # a non-positive baseline means broken data for a throughput
    # metric — but for a lower-is-better COST metric, 0 is the best
    # possible baseline (perfect recovery) and any positive latest is
    # exactly the regression the gate exists for
    if (baseline <= 0 and not lower_better) \
            or (lower_better and baseline < 0):
        row.update(status="skip", note="non-positive baseline")
        return row
    mad = _median([abs(v - baseline) for v in values])
    band = band_floor if baseline == 0 \
        else max(band_floor, 2.0 * mad / baseline)
    if lower_better:
        bound = baseline * (1.0 + band)
        regressed = latest > bound
    else:
        bound = baseline * (1.0 - band)
        regressed = latest < bound
    row.update(baseline=baseline, band=band, floor=bound,
               lower_is_better=lower_better,
               ratio=latest / baseline if baseline > 0 else None,
               status="REGRESSION" if regressed else "ok",
               history_rounds=[r for r, _v in history])
    return row


def run(dir_path: str, metrics: List[str], band: float, window: int,
        min_history: int, strict: bool,
        pattern: str = "BENCH_r*.json",
        auto_phases: bool = False) -> Tuple[int, List[Dict]]:
    rounds = load_rounds(dir_path, pattern)
    if not rounds:
        print(f"error: no {pattern} with results under "
              f"{dir_path}", file=sys.stderr)
        return 2, []
    latest_round, latest = rounds[-1]
    prior = rounds[:-1]
    if auto_phases:
        # default-set runs gate EVERY phase_*_ms key the rounds carry,
        # not just the PHASE_MS_METRICS literals: a future capture
        # growing a phase (phase_allreduce_ms under a mesh, the int8
        # backward_apply remainder) must not escape the gate the docs
        # promise. An explicit --metrics list is respected as given.
        metrics = list(metrics) + sorted({
            k for _r, res in rounds for k in res
            if _lower_is_better(k) and k.startswith("phase_")
            and k not in metrics})
    rows = []
    for metric in metrics:
        latest_val = _num(latest, metric)
        if latest_val is None:
            rows.append({"metric": metric, "round": latest_round,
                         "status": "skip",
                         "note": ("non-numeric in latest round"
                                  if metric in latest
                                  else "absent from latest round")})
            continue
        history = [(r, v) for r, res in prior
                   for v in [_num(res, metric)]
                   if v is not None][-window:]
        rows.append(check_metric(metric, history, latest_round,
                                 latest_val, band, min_history))
    regressed = [r for r in rows if r["status"] == "REGRESSION"]
    skipped = [r for r in rows if r["status"] == "skip"]
    if strict and len(skipped) == len(rows):
        print("error: --strict and no metric had enough history",
              file=sys.stderr)
        return 2, rows
    return (1 if regressed else 0), rows


def render(rows: List[Dict[str, Any]]) -> str:
    lines = ["| Metric | latest | baseline (median) | floor/ceiling "
             "(band) | ratio | verdict |",
             "|---|---|---|---|---|---|"]

    def f(v, nd=1):
        return "—" if v is None else f"{v:,.{nd}f}"

    for r in rows:
        if r["status"] == "skip":
            lines.append(f"| {r['metric']} | {f(r.get('latest'))} "
                         f"| — | — | — | skip: {r['note']} |")
            continue
        ratio = ("—" if r.get("ratio") is None
                 else f"{r['ratio']:.3f}")
        lines.append(
            f"| {r['metric']} | {f(r['latest'])} "
            f"| {f(r['baseline'])} "
            f"| {f(r['floor'])} ({r['band'] * 100:.1f}%) "
            f"| {ratio} | {r['status']} |")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="compare the latest BENCH_r*.json against the "
                    "round trajectory; exit 1 on regression")
    ap.add_argument("--dir", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))),
        help="directory holding BENCH_r*.json (default: repo root)")
    ap.add_argument("--kind", choices=sorted(KINDS), default="bench",
                    help="which round trajectory to gate: 'bench' = "
                         "BENCH_r*.json single-chip rounds, "
                         "'multichip' = MULTICHIP_r*.json "
                         "scaling-efficiency rounds, 'serving' = "
                         "SERVING_r*.json external-plane rounds "
                         "(p99 ceiling + req/s floor)")
    ap.add_argument("--metrics", nargs="+", default=None,
                    help="result keys to gate (higher is better); "
                         "default: the --kind's gated set")
    ap.add_argument("--band", type=float, default=0.05,
                    help="noise-band floor as a fraction (the "
                         "tolerance is max of this and the history's "
                         "observed spread)")
    ap.add_argument("--window", type=int, default=5,
                    help="how many prior rounds form the baseline")
    ap.add_argument("--min_history", type=int, default=2,
                    help="prior rounds required before a metric is "
                         "gated at all")
    ap.add_argument("--strict", action="store_true",
                    help="fail (exit 2) when NO metric has enough "
                         "history, instead of passing quietly")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable row dump instead of the "
                         "table")
    args = ap.parse_args(argv)
    pattern, kind_metrics = KINDS[args.kind]
    metrics = args.metrics if args.metrics is not None \
        else list(kind_metrics)
    rc, rows = run(args.dir, metrics, args.band, args.window,
                   args.min_history, args.strict, pattern=pattern,
                   auto_phases=args.metrics is None)
    if rows:
        print(json.dumps(rows, indent=1) if args.json
              else render(rows))
    if rc == 1:
        print("REGRESSION: latest bench round fell below the "
              "trajectory floor", file=sys.stderr)
    return rc


if __name__ == "__main__":
    sys.exit(main())
