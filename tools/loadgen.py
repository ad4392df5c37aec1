#!/usr/bin/env python3
"""Load generator for the batched serving subsystem (ISSUE 3).

Replays extractor-format requests against `serving/server.py` and
reports p50/p95/p99 latency + throughput through the obs registry —
the serving analogue of bench.py's training numbers.

Modes:
  - closed  — `--concurrency` workers, each issuing its next request the
              moment the previous one returns (throughput-bound).
  - open    — requests ARRIVE at `--qps` regardless of completions
              (Poisson-less fixed-interval arrivals); overload shows up
              as shed requests, not as a slowed generator.
  - sequential — the pre-server baseline: one `model.predict` at a time
              on one thread (what the REPL alone could drive).
  - compare — sequential then closed on the same corpus; prints the
              throughput ratio (the ISSUE 3 acceptance metric).

A corpus is one request per line-group: `--corpus <file.c2v>` (raw
extractor/preprocess lines, grouped `--methods` per request) or the
built-in synthetic generator. `--load <ckpt>` serves a real model;
`--synthetic` builds a tiny random-weight model in a temp dir (latency
is shape-, not value-dependent — fine for load testing).

Long-run mode (`--duration S`) loops the corpus for S seconds — pytest
runs it `slow`-marked only (tests/test_loadgen.py).

Reports go to stdout as JSON; with `--telemetry_dir` the run also lands
as a JSONL event log (`kind: loadgen`) that tools/telemetry_report.py
renders into the BASELINE.md serving row.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import tempfile
import threading
import time
from typing import Dict, List, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# mirrors tests/helpers.make_raw_lines' shape but stays standalone:
# tools must not import the test tree
_TOKENS = ["foo", "bar", "baz", "qux", "value", "name", "index", "count"]
_PATHS = [str(h) for h in (123456, -98765, 424242, 1337, -777, 31415)]
_TARGETS = ["get|value", "set|value", "get|name", "set|name", "add|item",
            "remove|item", "to|string", "is|empty"]


def gen_corpus(n_requests: int, methods_per_request: int = 1,
               max_ctx: int = 12, seed: int = 0,
               distinct: bool = True) -> List[List[str]]:
    """Synthetic extractor-format requests. `distinct=True` salts every
    method's token choice with its global index so an LRU cache can't
    turn a throughput run into a cache benchmark."""
    rng = random.Random(seed)
    corpus = []
    for r in range(n_requests):
        lines = []
        for m in range(methods_per_request):
            uid = r * methods_per_request + m
            t_idx = rng.randrange(len(_TARGETS))
            ctxs = []
            for c in range(rng.randint(2, max_ctx)):
                tok_a = _TOKENS[(t_idx + c) % len(_TOKENS)]
                tok_b = (f"u{uid}" if distinct and c == 0
                         else _TOKENS[(t_idx * 3 + c) % len(_TOKENS)])
                ctxs.append(f"{tok_a},{rng.choice(_PATHS)},{tok_b}")
            lines.append(_TARGETS[t_idx] + " " + " ".join(ctxs))
        corpus.append(lines)
    return corpus


def _percentiles(stat) -> Dict[str, float]:
    s = stat.summary()
    return {k: s[k] for k in ("count", "mean_ms", "p50_ms", "p95_ms",
                              "p99_ms", "max_ms")}


def run_sequential(model, corpus: List[List[str]],
                   duration: Optional[float] = None) -> Dict:
    """Baseline: one request at a time through `model.predict` — the
    pre-server path (extract cost excluded on both sides)."""
    from code2vec_tpu.obs import Telemetry
    tele = Telemetry.memory("loadgen-seq")
    t_start = time.perf_counter()
    done = 0
    i = 0
    while True:
        if duration is None:
            if i >= len(corpus):
                break
        elif time.perf_counter() - t_start >= duration:
            break
        t0 = time.perf_counter()
        model.predict(corpus[i % len(corpus)])
        tele.record_ms("loadgen/request_ms",
                       (time.perf_counter() - t0) * 1e3)
        done += 1
        i += 1
    wall = time.perf_counter() - t_start
    return {"mode": "sequential", "requests": done, "ok": done,
            "shed": 0, "errors": 0, "wall_s": round(wall, 3),
            "throughput_rps": round(done / max(wall, 1e-9), 2),
            "latency": _percentiles(tele.timer("loadgen/request_ms"))}


def _modulation_fn(modulation: Optional[str], period_s: float):
    """Offered-load multiplier over elapsed time (ISSUE 18: the open
    loop as a traffic MODEL, not a metronome):

      - None      — flat 1.0 (the PR-3 behavior);
      - "diurnal" — a smooth day-cycle compressed to `period_s`:
                    1 + 0.5*sin(2*pi*t/period), floored at 0.05 so the
                    trough still trickles;
      - "bursty"  — a 3x spike for the first 10% of each period, 0.8x
                    the rest: the flash-crowd shape autoscaling and
                    admission control have to absorb.
    """
    if modulation is None or modulation == "none":
        return lambda _t: 1.0
    if modulation == "diurnal":
        import math
        return lambda t: max(
            0.05, 1.0 + 0.5 * math.sin(2 * math.pi * t / period_s))
    if modulation == "bursty":
        return lambda t: 3.0 if (t % period_s) < 0.1 * period_s else 0.8
    raise ValueError(f"unknown modulation {modulation!r}")


def run_load(server, corpus: List[List[str]], mode: str = "closed",
             concurrency: int = 8, qps: float = 100.0,
             duration: Optional[float] = None,
             arrivals: str = "fixed",
             modulation: Optional[str] = None,
             modulation_period_s: float = 60.0,
             hot_key_frac: float = 0.0, hot_keys: int = 8,
             seed: int = 0) -> Dict:
    """Drive `server.predict_lines` with the chosen arrival process.
    The server must be started (buckets warmed) by the caller.

    Open-loop extras (ISSUE 18): `arrivals="poisson"` draws
    exponential inter-arrival gaps (the memoryless process real
    traffic approximates — fixed intervals can phase-lock with the
    batcher window and hide tail latency); `modulation` shapes the
    instantaneous rate (see `_modulation_fn`); `hot_key_frac` sends
    that fraction of arrivals to the first `hot_keys` corpus entries
    (Zipf-style skew — what makes the shared prediction cache earn
    its keep under replica fan-out). All draws come from one seeded
    stream, so a capture is replayable."""
    from code2vec_tpu.serving.batcher import ServerOverloaded

    tele = server.telemetry
    lock = threading.Lock()
    state = {"next": 0, "ok": 0, "shed": 0, "errors": 0}
    t_start = time.perf_counter()

    def _expired() -> bool:
        return (duration is not None
                and time.perf_counter() - t_start >= duration)

    def one(i: int) -> None:
        t0 = time.perf_counter()
        try:
            server.predict_lines(corpus[i % len(corpus)])
            with lock:
                state["ok"] += 1
            tele.record_ms("loadgen/request_ms",
                           (time.perf_counter() - t0) * 1e3)
        except ServerOverloaded:
            with lock:
                state["shed"] += 1
        except Exception as e:  # noqa: BLE001 — counted + sampled,
            with lock:          # reported, not fatal
                state["errors"] += 1
                state.setdefault("first_error", repr(e))

    if mode == "closed":
        def worker():
            while True:
                with lock:
                    i = state["next"]
                    if _expired() or (duration is None
                                      and i >= len(corpus)):
                        return
                    state["next"] = i + 1
                one(i)

        threads = [threading.Thread(target=worker, daemon=True)
                   for _ in range(concurrency)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    elif mode == "open":
        import concurrent.futures
        if arrivals not in ("fixed", "poisson"):
            raise ValueError(f"unknown arrivals {arrivals!r}")
        rng = random.Random(seed)
        mod_fn = _modulation_fn(modulation, modulation_period_s)
        n_hot = max(1, min(hot_keys, len(corpus)))
        n = len(corpus) if duration is None else (1 << 30)
        next_arrival = t_start
        with concurrent.futures.ThreadPoolExecutor(
                max_workers=concurrency) as pool:
            futures = []
            for i in range(n):
                if _expired():
                    break
                idx = i
                if hot_key_frac > 0 and rng.random() < hot_key_frac:
                    # skewed traffic: this arrival re-asks one of the
                    # hot keys instead of walking the corpus
                    idx = rng.randrange(n_hot)
                futures.append(pool.submit(one, idx))
                if len(futures) >= 4096:
                    # long-run soak mode: reap finished futures so the
                    # list stays bounded over hours of offered load
                    futures = [f for f in futures if not f.done()]
                # instantaneous rate at THIS arrival; the gap to the
                # next one is 1/rate (fixed) or an exponential draw
                # with that mean (poisson)
                rate = max(1e-9, qps * mod_fn(next_arrival - t_start))
                gap = (rng.expovariate(rate) if arrivals == "poisson"
                       else 1.0 / rate)
                next_arrival += gap
                sleep = next_arrival - time.perf_counter()
                if sleep > 0:
                    time.sleep(sleep)
            for f in futures:
                f.result()
    else:
        raise ValueError(f"unknown mode {mode!r}")

    wall = time.perf_counter() - t_start
    issued = state["ok"] + state["shed"] + state["errors"]
    report = {
        "mode": mode, "concurrency": concurrency,
        "requests": issued, "ok": state["ok"], "shed": state["shed"],
        "errors": state["errors"], "wall_s": round(wall, 3),
        "throughput_rps": round(state["ok"] / max(wall, 1e-9), 2),
        "latency": _percentiles(tele.timer("loadgen/request_ms")),
        "counters": dict(tele.counters),
    }
    if state["errors"]:
        report["first_error"] = state["first_error"]
    if mode == "open":
        report["offered_qps"] = qps
        report["arrivals"] = arrivals
        report["modulation"] = modulation or "none"
        if modulation:
            report["modulation_period_s"] = modulation_period_s
        if hot_key_frac > 0:
            report["hot_key_frac"] = hot_key_frac
            report["hot_keys"] = hot_keys
    return report


def _build_model(args):
    from code2vec_tpu.config import Config
    from code2vec_tpu.models.jax_model import Code2VecModel
    if args.load:
        cfg = Config()
        cfg.load_path = args.load
    else:  # --synthetic: tiny random-weight model in a temp workdir
        from code2vec_tpu.data import preprocess as preprocess_mod
        workdir = tempfile.mkdtemp(prefix="loadgen_")
        raw = os.path.join(workdir, "raw.txt")
        flat = [ln for req in gen_corpus(64, 2, seed=7) for ln in req]
        with open(raw, "w", encoding="utf-8") as f:
            f.write("\n".join(flat) + "\n")
        prefix = os.path.join(workdir, "tiny")
        preprocess_mod.main([
            "--train_data", raw, "--val_data", raw, "--test_data", raw,
            "--max_contexts", "16", "--word_vocab_size", "1000",
            "--path_vocab_size", "1000", "--target_vocab_size", "1000",
            "--output_name", prefix])
        cfg = Config(MAX_CONTEXTS=16, MAX_TOKEN_VOCAB_SIZE=1000,
                     MAX_PATH_VOCAB_SIZE=1000,
                     MAX_TARGET_VOCAB_SIZE=1000,
                     DEFAULT_EMBEDDINGS_SIZE=16, USE_BF16=False)
        cfg.train_data_path = prefix
    for name in ("serve_batch_max", "serve_batch_timeout_ms",
                 "serve_queue_depth", "serve_deadline_ms",
                 "serve_cache_size"):
        val = getattr(args, name)
        if val is not None:
            setattr(cfg, name.upper(), val)
    return cfg, Code2VecModel(cfg)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--mode", default="compare",
                    choices=["closed", "open", "sequential", "compare"])
    ap.add_argument("--load", default=None,
                    help="checkpoint dir; omit for --synthetic")
    ap.add_argument("--synthetic", action="store_true",
                    help="tiny random-weight model (default when no "
                         "--load)")
    ap.add_argument("--corpus", default=None,
                    help="file of raw extractor lines; default: "
                         "synthetic corpus")
    ap.add_argument("--requests", type=int, default=128)
    ap.add_argument("--methods", type=int, default=1,
                    help="methods per request")
    ap.add_argument("--concurrency", type=int, default=8)
    ap.add_argument("--qps", type=float, default=100.0,
                    help="open-loop offered load")
    ap.add_argument("--arrivals", default="fixed",
                    choices=["fixed", "poisson"],
                    help="open-loop arrival process: fixed intervals "
                         "or Poisson (exponential gaps)")
    ap.add_argument("--modulation", default="none",
                    choices=["none", "diurnal", "bursty"],
                    help="open-loop rate shaping: a compressed "
                         "day-cycle sine or a 3x flash-crowd burst "
                         "per period")
    ap.add_argument("--modulation_period_s", type=float, default=60.0,
                    help="one diurnal/bursty cycle length in seconds")
    ap.add_argument("--hot_key_frac", type=float, default=0.0,
                    help="fraction of open-loop arrivals redirected "
                         "to the --hot_keys hottest corpus entries "
                         "(cache-skew traffic)")
    ap.add_argument("--hot_keys", type=int, default=8,
                    help="size of the hot-key set")
    ap.add_argument("--seed", type=int, default=0,
                    help="arrival/hot-key draw seed (replayable "
                         "captures)")
    ap.add_argument("--duration", type=float, default=None,
                    help="long-run mode: loop the corpus for S seconds")
    ap.add_argument("--serve_batch_max", type=int, default=None)
    ap.add_argument("--serve_batch_timeout_ms", type=float, default=None)
    ap.add_argument("--serve_queue_depth", type=int, default=None)
    ap.add_argument("--serve_deadline_ms", type=float, default=None)
    ap.add_argument("--serve_cache_size", type=int, default=0,
                    help="0 (default) keeps throughput numbers honest "
                         "on a repeating corpus")
    ap.add_argument("--telemetry_dir", default=None)
    ap.add_argument("--trace", action="store_true",
                    help="request-scoped tracing: queue -> batch -> "
                         "device -> decode span trees per request; "
                         "exports Chrome trace JSON after the run "
                         "(defaults --telemetry_dir to a temp dir "
                         "when unset)")
    ap.add_argument("--trace_out", default=None,
                    help="Chrome trace JSON path (default: "
                         "<run_dir>/trace.json)")
    ap.add_argument("--watchdog_stall_s", type=float, default=0.0,
                    help="stall watchdog deadline for the batcher "
                         "consumer (0 = off)")
    ap.add_argument("--watchdog_mode", default="warn",
                    choices=["warn", "raise"])
    ap.add_argument("--metrics_port", type=int, default=0,
                    help="serve /metrics //healthz //vars from the "
                         "PredictionServer while the load runs "
                         "(0 = off)")
    ap.add_argument("--alerts_mode", default="off",
                    choices=["off", "warn", "raise"],
                    help="serving health monitors (cache-hit "
                         "collapse, shed burn-rate) + alert rules "
                         "(defaults --telemetry_dir to a temp dir "
                         "when unset — alert events need a run dir)")
    ap.add_argument("--alerts_rules", default=None,
                    help="JSON alert-rule file (see README)")
    ap.add_argument("--out", default=None, help="also write JSON here")
    args = ap.parse_args(argv)
    from code2vec_tpu.device import enable_compile_cache
    enable_compile_cache()
    if args.load and args.synthetic:
        ap.error("--load and --synthetic are mutually exclusive")
    if (args.trace or args.watchdog_stall_s > 0
            or args.alerts_mode != "off") and not args.telemetry_dir:
        # spans, stall dumps and alert events live in the run dir —
        # make one
        args.telemetry_dir = tempfile.mkdtemp(prefix="loadgen_trace_")

    cfg, model = _build_model(args)
    if args.telemetry_dir:
        cfg.TELEMETRY_DIR = args.telemetry_dir
    cfg.TRACE = bool(args.trace)
    cfg.WATCHDOG_STALL_S = args.watchdog_stall_s
    cfg.WATCHDOG_MODE = args.watchdog_mode
    cfg.METRICS_PORT = args.metrics_port
    cfg.ALERTS_MODE = args.alerts_mode
    cfg.ALERTS_RULES = args.alerts_rules

    if args.corpus:
        with open(args.corpus, encoding="utf-8") as f:
            flat = [ln for ln in f if ln.strip()]
        corpus = [flat[i:i + args.methods]
                  for i in range(0, len(flat), args.methods)]
        if args.requests and len(corpus) > args.requests:
            corpus = corpus[:args.requests]
    else:
        corpus = gen_corpus(args.requests, args.methods,
                            max_ctx=min(cfg.MAX_CONTEXTS, 12))

    from code2vec_tpu.obs import Telemetry
    from code2vec_tpu.serving.server import PredictionServer
    tele = Telemetry.create(cfg.TELEMETRY_DIR, config=cfg,
                            mesh=getattr(model, "mesh", None),
                            component="loadgen")
    if not tele.enabled:
        tele = Telemetry.memory("loadgen")
    tele.make_threadsafe()

    reports = []
    if args.mode in ("sequential", "compare"):
        model.warmup_predict(args.methods)  # compile the batch-1 bucket
        reports.append(run_sequential(model, corpus,
                                      duration=args.duration))
    if args.mode != "sequential":
        server = PredictionServer(cfg, model, telemetry=tele)
        server.start()
        compiled_after_warmup = model.predict_compile_count()
        mode = "closed" if args.mode == "compare" else args.mode
        rep = run_load(server, corpus, mode=mode,
                       concurrency=args.concurrency, qps=args.qps,
                       duration=args.duration,
                       arrivals=args.arrivals,
                       modulation=(None if args.modulation == "none"
                                   else args.modulation),
                       modulation_period_s=args.modulation_period_s,
                       hot_key_frac=args.hot_key_frac,
                       hot_keys=args.hot_keys, seed=args.seed)
        rep["compiled_variants_after_warmup"] = compiled_after_warmup
        rep["new_compilations_under_load"] = (
            model.predict_compile_count() - compiled_after_warmup)
        server.close()
        reports.append(rep)

    out = {"reports": reports}
    if args.mode == "compare" and len(reports) == 2:
        seq, bat = reports
        out["speedup"] = round(
            bat["throughput_rps"] / max(seq["throughput_rps"], 1e-9), 2)
    for rep in reports:
        tele.event("loadgen", **rep)
    tele.close()
    if args.trace and tele.run_dir:
        # export the run's spans as Chrome trace-event JSON (Perfetto /
        # chrome://tracing; tools/trace_report.py prints the
        # critical-path breakdown from the same run dir)
        from tools.trace_report import write_chrome_trace
        trace_out = args.trace_out or os.path.join(tele.run_dir,
                                                   "trace.json")
        n_events = write_chrome_trace([tele.run_dir], trace_out)
        out["trace_json"] = trace_out
        out["trace_events"] = n_events
        out["trace_run_dir"] = tele.run_dir
    text = json.dumps(out, indent=2)
    print(text)
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            f.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
