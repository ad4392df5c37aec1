#!/usr/bin/env python3
"""obs_top: live multi-host terminal view over N `/metrics` endpoints
(ISSUE 7).

The pull-based counterpart of `tools/telemetry_report.py --merge`:
instead of aggregating per-process JSONL run dirs after the fact, poll
each host's `--metrics_port` exposition endpoint on an interval and
render ONE table — global throughput summed across hosts, per-host
rows keeping the skew visible (a straggler host is a slow row, not a
hidden average). MULTICHIP groundwork: a v4-32 pod run is 4 hosts ×
one endpoint each.

  python tools/obs_top.py host1:9100 host2:9100 [--interval 2]
  python tools/obs_top.py localhost:9100 --once   # one sample, no TUI

Rates (steps/s, examples/s, requests/s) are differenced between
consecutive polls of each endpoint's cumulative counters; a counter
that went BACKWARD means the process restarted (supervisor relaunch /
elastic resize zeroes its counters) — the row is annotated RESTARTED
and rates clamp to the new process's progress instead of rendering
negative steps/s. path-contexts/s = examples-rate × the
`train_max_contexts` gauge the train loop publishes. Health verdicts,
firing alerts, stalled components and stale gauges (age > --stale_s)
come straight off the same scrape. Pure stdlib
(urllib + the shared obs/promtext parser, itself re-only) — runs on a
laptop against a pod with nothing installed beyond this checkout.

`--fleet <url>` (ISSUE 17) switches the source: instead of scraping N
raw endpoints and differencing counters here, poll the supervisor-side
fleet collector's `/fleet` aggregate — per-host rows plus the cohort
signals only the collector can compute (straggler score with phase
attribution, loss/params divergence, measured clock offsets).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import urllib.error
import urllib.request
from typing import Any, Dict, List, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# ONE exposition parser + counter-reset discipline for every scrape
# consumer (ISSUE 17 hoist): obs_top grew the original; the shared
# module now owns it and the fleet collector imports the same one.
# Re-exported names keep the historical `from tools.obs_top import
# parse_prometheus` imports working.
from code2vec_tpu.obs.promtext import (CounterRates,  # noqa: E402
                                       labeled, parse_prometheus,
                                       scalar)

__all__ = ["EndpointState", "labeled", "main", "parse_prometheus",
           "render", "render_fleet", "scalar", "scrape"]

def scrape(endpoint: str, timeout_s: float = 3.0) -> Dict:
    url = endpoint if "://" in endpoint else f"http://{endpoint}"
    with urllib.request.urlopen(f"{url.rstrip('/')}/metrics",
                                timeout=timeout_s) as resp:
        return parse_prometheus(resp.read().decode("utf-8"))


class EndpointState:
    """One endpoint's scrape history: the previous counter sample, so
    each poll yields rates."""

    def __init__(self, endpoint: str):
        self.endpoint = endpoint
        # the shared counter-reset discipline (obs/promtext): a counter
        # going BACKWARD annotates the row RESTARTED and rates clamp to
        # the new process's progress instead of negative steps/s
        self.rates = CounterRates()
        self.error: Optional[str] = None

    def poll(self, stale_s: float) -> Optional[Dict[str, Any]]:
        """Scrape once; returns a row dict (None until two samples
        exist for the rate fields — other fields fill in on the first
        poll)."""
        t = time.monotonic()
        try:
            metrics = scrape(self.endpoint)
            self.error = None
        except (urllib.error.URLError, OSError, ValueError) as e:
            self.error = str(getattr(e, "reason", e))
            return {"endpoint": self.endpoint, "error": self.error}
        rate = self.rates.advance(t, metrics)
        ex_rate = rate("train_examples")
        max_ctx = scalar(metrics, "train_max_contexts")
        stalled = [labels.get("component", "?")
                   for labels, v in metrics.get("component_stalled", ())
                   if v]
        firing = [labels.get("rule", "?")
                  for labels, v in metrics.get("alert_active", ())
                  if v]
        unhealthy = [labels.get("monitor", "?")
                     for labels, v in metrics.get("health_status", ())
                     if v]
        stale = [labels.get("gauge", "?")
                 for labels, v in metrics.get("gauge_age_seconds", ())
                 if v > stale_s]
        return {
            "endpoint": self.endpoint,
            "steps": scalar(metrics, "train_steps"),
            "steps_s": rate("train_steps"),
            "ex_s": ex_rate,
            "pc_s": (ex_rate * max_ctx
                     if ex_rate is not None and max_ctx else None),
            "step_p50": labeled(metrics, "train_step_ms",
                                quantile="0.5"),
            # analytic-floor attainment (health/opt_efficiency: the
            # sparse path's static [U, E]-aware floor over observed
            # p50 step time) — an optimizer-efficiency regression is
            # a dropping number here, mid-run
            "opt_eff": scalar(metrics, "health_opt_efficiency"),
            "infeed_p95": labeled(metrics, "train_infeed_wait_ms",
                                  quantile="0.95"),
            "req_s": rate("serve_requests"),
            "queue_depth": scalar(metrics, "serve_queue_depth"),
            "loss": scalar(metrics, "train_loss"),
            "stalled": stalled,
            "alerts": firing,
            "unhealthy": unhealthy,
            "stale_gauges": stale,
            "restarted": self.rates.restarted,
        }


def _f(v, nd: int = 1) -> str:
    if v is None:
        return "—"
    if isinstance(v, float) and v != v:
        return "NaN"
    return f"{v:,.{nd}f}"


def render(rows: List[Dict[str, Any]]) -> str:
    """One frame: the summed headline + per-host skew rows (the
    telemetry_report --merge table shape, live)."""
    lines: List[str] = []
    ok_rows = [r for r in rows if "error" not in r]
    total_pc = sum(r["pc_s"] for r in ok_rows
                   if r.get("pc_s") is not None) or None
    total_req = sum(r["req_s"] for r in ok_rows
                    if r.get("req_s") is not None) or None
    n_bad = sum(bool(r.get("stalled") or r.get("alerts"))
                for r in ok_rows)
    lines.append(
        f"obs_top — {len(ok_rows)}/{len(rows)} hosts up | "
        f"pc/s (sum) {_f(total_pc)} | req/s (sum) {_f(total_req)} | "
        f"{n_bad} host(s) unhealthy | "
        f"{time.strftime('%H:%M:%S')}")
    lines.append(
        "| Host | steps | ex/s | pc/s | step p50 ms | opt eff "
        "| infeed p95 ms | req/s | q | loss | status |")
    lines.append("|---|---|---|---|---|---|---|---|---|---|---|")
    for r in rows:
        if "error" in r:
            lines.append(f"| {r['endpoint']} | DOWN: {r['error']} "
                         "| | | | | | | | | |")
            continue
        bits = []
        if r["stalled"]:
            bits.append("STALLED:" + ",".join(r["stalled"]))
        if r.get("restarted"):
            # counter reset this window (supervisor restart / elastic
            # resize): rates shown are the NEW process's, not deltas
            bits.append("RESTARTED")
        if r["alerts"]:
            bits.append("ALERT:" + ",".join(r["alerts"]))
        if r["unhealthy"]:
            bits.append("bad:" + ",".join(r["unhealthy"]))
        if r["stale_gauges"]:
            bits.append(f"{len(r['stale_gauges'])} stale gauge(s)")
        lines.append(
            f"| {r['endpoint']} | {_f(r['steps'], 0)} "
            f"| {_f(r['ex_s'])} | {_f(r['pc_s'])} "
            f"| {_f(r['step_p50'], 2)} | {_f(r.get('opt_eff'), 3)} "
            f"| {_f(r['infeed_p95'], 2)} "
            f"| {_f(r['req_s'])} | {_f(r['queue_depth'], 0)} "
            f"| {_f(r['loss'], 4)} "
            f"| {' '.join(bits) if bits else 'ok'} |")
    return "\n".join(lines)


def fetch_fleet(url: str, timeout_s: float = 3.0) -> Dict[str, Any]:
    """One `/fleet` aggregate off the supervisor-side collector."""
    base = url if "://" in url else f"http://{url}"
    base = base.rstrip("/")
    if not base.endswith("/fleet"):
        base += "/fleet"
    with urllib.request.urlopen(base, timeout=timeout_s) as resp:
        return json.loads(resp.read().decode("utf-8"))


def render_fleet(agg: Dict[str, Any]) -> str:
    """One frame off the fleet aggregate: cohort headline (summed
    throughput, straggler verdict with its attributed series,
    divergence), then per-host rows with measured clock offsets —
    the collector already did the differencing and the cross-host
    math, so this renders, it does not derive."""
    cohort = agg.get("cohort") or {}
    hosts = agg.get("hosts") or []
    lines: List[str] = []
    strag = cohort.get("straggler_score")
    strag_bit = "—"
    if strag is not None:
        strag_bit = f"{strag:.2f}x"
        if cohort.get("straggler_host"):
            strag_bit += (f" ({cohort['straggler_host']} via "
                          f"{cohort.get('straggler_series')})")
    div = "DIVERGED" if cohort.get("divergence") else "converged"
    lines.append(
        f"obs_top --fleet — {cohort.get('hosts_up', 0)}"
        f"/{cohort.get('hosts_total', 0)} hosts up | "
        f"pc/s (sum) {_f(cohort.get('pc_per_sec'))} | "
        f"straggler {strag_bit} | {div} | "
        f"clock spread {_f((cohort.get('clock_spread_s') or 0) * 1e3, 3)} ms | "
        f"{time.strftime('%H:%M:%S')}")
    lines.append("| Host | steps | ex/s | pc/s | step p50 ms "
                 "| infeed p50 ms | loss | straggler | clock off ms "
                 "| status |")
    lines.append("|---" * 10 + "|")
    for r in hosts:
        if not r.get("up"):
            lines.append(f"| {r['endpoint']} | DOWN: "
                         f"{r.get('error')} | | | | | | | | |")
            continue
        bits = []
        if r.get("restarted"):
            bits.append("RESTARTED")
        score = r.get("straggler_score")
        score_bit = "—"
        if score is not None:
            score_bit = f"{score:.2f}x {r.get('straggler_series')}"
        off = r.get("clock_offset_s")
        lines.append(
            f"| {r['endpoint']} | {_f(r.get('steps'), 0)} "
            f"| {_f(r.get('ex_s'))} | {_f(r.get('pc_s'))} "
            f"| {_f(r.get('step_p50'), 2)} "
            f"| {_f(r.get('infeed_p50'), 2)} "
            f"| {_f(r.get('loss'), 4)} | {score_bit} "
            f"| {_f(off * 1e3 if off is not None else None, 3)} "
            f"| {' '.join(bits) if bits else 'ok'} |")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="live multi-host view over /metrics endpoints")
    ap.add_argument("endpoints", nargs="*",
                    help="host:port (or full URL) of each "
                         "--metrics_port exposition server")
    ap.add_argument("--fleet", default=None, metavar="URL",
                    help="poll the supervisor-side fleet collector's "
                         "/fleet aggregate instead of raw endpoints "
                         "(ISSUE 17)")
    ap.add_argument("--interval", type=float, default=2.0,
                    help="poll interval in seconds")
    ap.add_argument("--once", action="store_true",
                    help="two quick polls (rates need a delta), one "
                         "printed frame, exit — the scripting mode")
    ap.add_argument("--count", type=int, default=0,
                    help="stop after N frames (0 = run until ^C)")
    ap.add_argument("--stale_s", type=float, default=60.0,
                    help="mark gauges older than this as stale")
    args = ap.parse_args(argv)
    if args.fleet is None and not args.endpoints:
        ap.error("give /metrics endpoints, or --fleet <url>")

    if args.fleet is not None:
        # aggregate mode: the collector differenced and derived; poll
        # and render its latest sweep (no warm-up frame needed)
        n = 0
        try:
            while True:
                try:
                    out = render_fleet(fetch_fleet(args.fleet))
                except (urllib.error.URLError, OSError,
                        ValueError) as e:
                    out = (f"obs_top --fleet — {args.fleet} DOWN: "
                           f"{getattr(e, 'reason', e)}")
                if not args.once and n:
                    sys.stdout.write("\x1b[2J\x1b[H")
                print(out)
                n += 1
                if args.once or (args.count and n >= args.count):
                    return 0
                time.sleep(max(args.interval, 0.05))
        except KeyboardInterrupt:
            return 0

    states = [EndpointState(e) for e in args.endpoints]

    def frame() -> List[Dict[str, Any]]:
        return [s.poll(args.stale_s) for s in states]

    if args.once:
        frame()  # prime the counter baselines
        time.sleep(max(args.interval, 0.05))
        print(render(frame()))
        return 0
    n = 0
    try:
        while True:
            rows = frame()
            if n:  # first frame has no rates yet; start painting at 2
                sys.stdout.write("\x1b[2J\x1b[H")  # clear + home
                print(render(rows))
            n += 1
            if args.count and n > args.count:
                return 0
            time.sleep(max(args.interval, 0.05))
    except KeyboardInterrupt:
        return 0


if __name__ == "__main__":
    sys.exit(main())
