#!/usr/bin/env python3
"""Padding-reduction step-time sweep: MAX_CONTEXTS in {200, 128, 100}.

VERDICT r4 item 2: the corpus context distribution is p50/p90 = 65/97
(BASELINE.md extractor coverage) yet every config runs C=200, so over
half the gather/scatter/attention work is padding. The quality half of
the argument is measured by tools/quality_study.py --max_contexts (the
reader's seeded over-cap sampling handles C < the binarized width);
this tool measures the device half: the shipped train step's time at
java-large capacities for each C, slope-timed exactly like bench.py
(same dims/optimizer/batch builders — imported from it).

Reporting note: examples/s is the number that converts to
time-to-quality (an example carries the same label at any C >= its
context count); path-contexts/s scales with C by definition and is
reported only for cross-checking against bench.

Usage: python tools/c_sweep_step.py [--contexts 200,128,100]
Prints one JSON line per C and a summary.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--contexts", default="200,128,100")
    ap.add_argument("--tables_dtype", default="bfloat16",
                    choices=["bfloat16", "int8"])
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    from code2vec_tpu.device import enable_compile_cache
    enable_compile_cache()
    from tools._bench_common import load_bench_module
    bench = load_bench_module()

    rows = []
    for c in (int(s) for s in args.contexts.split(",")):
        pc, ms, _ = bench._measure_encoder(
            "bag", tables_dtype=args.tables_dtype, max_contexts=c)
        row = {
            "max_contexts": c,
            "tables_dtype": args.tables_dtype,
            "ms_per_step": round(ms, 2),
            "examples_per_sec": round(bench.BATCH / ms * 1e3, 1),
            "path_contexts_per_sec": round(pc, 1),
        }
        rows.append(row)
        print(json.dumps(row), flush=True)

    base = rows[0]
    for r in rows[1:]:
        r["examples_per_sec_vs_first"] = round(
            r["examples_per_sec"] / base["examples_per_sec"], 3)
    print(json.dumps({"summary": rows}), flush=True)
    if args.out:
        with open(args.out, "a") as f:
            for r in rows:
                f.write(json.dumps(r) + "\n")


if __name__ == "__main__":
    main()
