#!/usr/bin/env python3
"""Epoch-boundary stall: synchronous vs async checkpointing, measured.

ISSUE 5 acceptance driver. Every epoch boundary used to stall the chip
for the FULL wall time of a synchronous orbax save plus a full eval
plus an infeed cold restart. This tool trains the same tiny synthetic
model twice on the CPU mesh harness — `--async_checkpoint off` then
`on` — with per-run telemetry, and reports per boundary:

  - save_blocked_ms   loop-side blocked time (the submit + snapshot
                      dispatch under async; the whole save under sync)
  - save_total_ms     writer-side wall (snapshot fetch + serialize +
                      commit rename + pruning)
  - eval_ms           the epoch eval that overlaps the writer tail
  - boundary_ms       wall time from the last step event before the
                      boundary to the first step event after it — the
                      actual training gap
  - steps_during_save step events timestamped inside the async save
                      window (training demonstrably proceeding while
                      the writer drains; requires epochs >= 2)

plus the headline ratio: async blocked time as a fraction of the sync
save wall (< 0.25 is the acceptance bar).

Usage:
  python tools/epoch_overhead.py [--epochs 3] [--examples 768]
      [--batch 64] [--emb 64] [--max_contexts 16] [--no_eval]
      [--out boundaries.jsonl]
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import tempfile
from typing import Any, Dict, List, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_TOKENS = ["foo", "bar", "baz", "qux", "value", "name", "index", "count"]
_PATHS = [str(h) for h in (123456, -98765, 424242, 1337, -777, 31415)]
_TARGETS = ["get|value", "set|value", "get|name", "set|name", "add|item",
            "remove|item", "to|string", "is|empty"]


def _raw_lines(n: int, seed: int, max_ctx: int) -> List[str]:
    rng = random.Random(seed)
    lines = []
    for _ in range(n):
        t = rng.randrange(len(_TARGETS))
        ctxs = [f"{_TOKENS[(t + rng.randrange(2)) % len(_TOKENS)]},"
                f"{_PATHS[t % len(_PATHS)]},"
                f"{_TOKENS[(t * 3 + rng.randrange(2)) % len(_TOKENS)]}"
                for _ in range(rng.randint(1, max_ctx))]
        lines.append(_TARGETS[t] + " " + " ".join(ctxs))
    return lines


def build_dataset(tmpdir: str, n_train: int, max_contexts: int) -> str:
    """Synthetic extractor output -> preprocessed `.c2v` prefix (the
    tests/helpers recipe, standalone so the tool needs no test deps)."""
    from code2vec_tpu.data import preprocess as preprocess_mod
    paths = {}
    for split, n, seed in (("train", n_train, 1), ("val", 32, 2),
                           ("test", 64, 3)):
        p = os.path.join(tmpdir, f"raw.{split}.txt")
        with open(p, "w") as f:
            f.write("\n".join(_raw_lines(n, seed, max_contexts)) + "\n")
        paths[split] = p
    prefix = os.path.join(tmpdir, "tiny")
    preprocess_mod.main([
        "--train_data", paths["train"], "--val_data", paths["val"],
        "--test_data", paths["test"],
        "--max_contexts", str(max_contexts),
        "--word_vocab_size", "1000", "--path_vocab_size", "1000",
        "--target_vocab_size", "1000", "--output_name", prefix])
    return prefix


def analyze(events: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Per-boundary metrics from one run's telemetry events."""
    from tools.telemetry_report import boundary_rows
    rows = boundary_rows(events)
    steps = sorted((e for e in events if e.get("kind") == "step"),
                   key=lambda e: e["ts"])
    saves = {int(e["step"]): e for e in events
             if e.get("kind") == "save" and "step" in e}
    commits = {int(e["step"]): e for e in events
               if e.get("kind") == "save_committed" and "step" in e}
    for r in rows:
        save_ev, commit_ev = saves.get(r["step"]), commits.get(r["step"])
        before = [e for e in steps if int(e["step"]) <= r["step"]]
        after = [e for e in steps if int(e["step"]) > r["step"]]
        r["boundary_ms"] = (
            round((after[0]["ts"] - before[-1]["ts"]) * 1e3, 1)
            if before and after else None)
        # async save window: the `save` event fires when the loop
        # unblocks (writer still draining), `save_committed` at the
        # rename — step events inside that window prove the loop ran
        # while the writer wrote
        n_during = 0
        if save_ev is not None and commit_ev is not None:
            n_during = sum(1 for e in after
                           if save_ev["ts"] <= e["ts"] <= commit_ev["ts"])
        r["steps_during_save"] = n_during
    return rows


def _read_events(run_dir: str) -> List[Dict[str, Any]]:
    out = []
    with open(os.path.join(run_dir, "events.jsonl"),
              encoding="utf-8") as f:
        for line in f:
            if line.strip():
                out.append(json.loads(line))
    return out


def run_mode(prefix: str, workdir: str, *, use_async: bool, epochs: int,
             batch: int, emb: int, max_contexts: int,
             with_eval: bool, trace: bool = False,
             trace_out: Optional[str] = None) -> List[Dict[str, Any]]:
    from code2vec_tpu.config import Config
    from code2vec_tpu.models.jax_model import Code2VecModel
    tag = "async" if use_async else "sync"
    cfg = Config(
        MAX_CONTEXTS=max_contexts, MAX_TOKEN_VOCAB_SIZE=1000,
        MAX_PATH_VOCAB_SIZE=1000, MAX_TARGET_VOCAB_SIZE=1000,
        DEFAULT_EMBEDDINGS_SIZE=emb, TRAIN_BATCH_SIZE=batch,
        TEST_BATCH_SIZE=batch, NUM_TRAIN_EPOCHS=epochs,
        SAVE_EVERY_EPOCHS=1, NUM_BATCHES_TO_LOG_PROGRESS=10_000,
        USE_BF16=False, ASYNC_CHECKPOINT=use_async, TRACE=trace,
        TELEMETRY_DIR=os.path.join(workdir, f"tele_{tag}"))
    cfg.train_data_path = prefix
    if with_eval:
        cfg.test_data_path = prefix + ".test.c2v"
    cfg.save_path = os.path.join(workdir, f"ckpt_{tag}")
    model = Code2VecModel(cfg)
    model.train()
    model.close_session()
    if trace and trace_out:
        # Chrome trace of the boundary: step_cycle spans on the loop
        # row, save_write on the ckpt-writer row, infeed/produce on the
        # producer row — the overlap the summary numbers claim, visible
        from tools.trace_report import write_chrome_trace
        n = write_chrome_trace([model.telemetry.run_dir], trace_out)
        print(json.dumps({"trace_json": trace_out, "mode": tag,
                          "trace_events": n}), flush=True)
    return analyze(_read_events(model.telemetry.run_dir))


def main(argv: Optional[List[str]] = None) -> Dict[str, Any]:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--epochs", type=int, default=5)
    ap.add_argument("--examples", type=int, default=768)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--emb", type=int, default=64)
    ap.add_argument("--max_contexts", type=int, default=16)
    ap.add_argument("--warmup_boundaries", type=int, default=2,
                    help="boundaries excluded from the summary medians:"
                         " the first boundary's save overlaps the eval"
                         " jit compile (GIL starvation inflates the"
                         " writer wall) and the second inherits its"
                         " tail as blocked time — steady state starts"
                         " after them")
    ap.add_argument("--no_eval", action="store_true",
                    help="skip the per-epoch eval (isolates the save "
                         "overlap: next-epoch steps run during the "
                         "writer drain instead of eval)")
    ap.add_argument("--trace", action="store_true",
                    help="per-step span trees (--trace) for both "
                         "runs; writes epoch_overhead_trace_{sync,"
                         "async}.json Chrome traces to the cwd")
    ap.add_argument("--out", default=None, help="also append JSONL here")
    a = ap.parse_args(argv)
    from code2vec_tpu.device import enable_compile_cache
    enable_compile_cache()

    result: Dict[str, Any] = {}
    with tempfile.TemporaryDirectory(prefix="epoch_overhead_") as wd:
        prefix = build_dataset(wd, a.examples, a.max_contexts)
        for tag, use_async in (("sync", False), ("async", True)):
            rows = run_mode(prefix, wd, use_async=use_async,
                            epochs=a.epochs, batch=a.batch, emb=a.emb,
                            max_contexts=a.max_contexts,
                            with_eval=not a.no_eval, trace=a.trace,
                            trace_out=(f"epoch_overhead_trace_{tag}"
                                       ".json") if a.trace else None)
            result[tag] = rows
            for r in rows:
                print(json.dumps({"mode": tag, **r}), flush=True)

    def med(vals):
        s = sorted(v for v in vals if v is not None and v == v)
        return s[len(s) // 2] if s else float("nan")

    # steady state only: the warmup boundaries measure jit-compile GIL
    # contention, not the checkpoint protocol
    w = max(0, min(a.warmup_boundaries, a.epochs - 1))
    sync_rows = result["sync"][w:]
    async_rows = result["async"][w:]
    sync_wall = med([r["blocked_ms"] for r in sync_rows])
    async_blocked = med([r["blocked_ms"] for r in async_rows])
    async_total = med([r["total_ms"] for r in async_rows])
    summary = {
        "warmup_boundaries_excluded": w,
        "sync_save_wall_ms_p50": round(sync_wall, 2),
        "async_blocked_ms_p50": round(async_blocked, 2),
        "async_total_ms_p50": round(async_total, 2),
        "blocked_vs_sync_wall": round(async_blocked / sync_wall, 4)
        if sync_wall == sync_wall and sync_wall > 0 else None,
        "sync_boundary_ms_p50": med(
            [r["boundary_ms"] for r in sync_rows]),
        "async_boundary_ms_p50": med(
            [r["boundary_ms"] for r in async_rows]),
        "async_steps_during_save": sum(
            r["steps_during_save"] for r in result["async"]),
    }
    result["summary"] = summary
    print(json.dumps({"summary": summary}), flush=True)
    if a.out:
        with open(a.out, "a", encoding="utf-8") as f:
            for tag in ("sync", "async"):
                for r in result[tag]:
                    f.write(json.dumps({"mode": tag, **r}) + "\n")
            f.write(json.dumps({"summary": summary}) + "\n")
    return result


if __name__ == "__main__":
    main()
