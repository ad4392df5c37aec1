#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name, from `BENCHMARK.json` at
the root of the checkout down:

  cell            an entry of `workloads`: a configuration, a traffic mix, chips
  configuration   `benchmark/configs/<config>.json`
  traffic mix     `benchmark/traffic/<traffic>.json`, whose `kind` names
                  `benchmark/kinds/<kind>.py` (builds the inputs, drives the
                  window, decides `correct`)
  per-layer metric `benchmark/layer_metrics/<metric>.json`, whose `reader`
                  names `benchmark/readers/<reader>.py`

so a later PR adds a cell, a configuration, a mix or a metric as new
files and a new entry, and edits nothing that is here. A name that
cannot be found is an error that says which file was looked for.

The run fails (non-zero exit, no result line) when JAX finds no TPU or
another number of chips than the cell asks for. The last line of
standard output is the result: `correct`, `attempted`, `failed`,
`metrics`, `device`, with `--trace 1` also `breakdown`, and last `compared`
(each number that decided `correct` beside its limit; the same lines
close standard error).
"""

from __future__ import annotations

import time

T_START = time.time()          # process start, as near as Python gets

import argparse               # noqa: E402
import importlib.util         # noqa: E402
import json                   # noqa: E402
import os                     # noqa: E402
import sys                    # noqa: E402
import types                  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class BenchError(RuntimeError):
    """A fault of the benchmark's own files or of the machine: the run
    exits non-zero with this message and prints no result."""


def load_json(path: str, what: str) -> dict:
    if not os.path.isfile(path):
        raise BenchError(f"{what}: looked for {os.path.relpath(path, ROOT)}"
                         ", which is not there")
    with open(path) as f:
        return json.load(f)


def load_module(directory: str, name: str, what: str):
    path = os.path.join(BENCH_DIR, directory, name + ".py")
    if not os.path.isfile(path):
        raise BenchError(f"{what} {name!r}: looked for "
                         f"{os.path.relpath(path, ROOT)}, which is not there")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{directory}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def find_cell(manifest: dict, workload: str) -> dict:
    for cell in manifest["workloads"]:
        if cell["name"] == workload:
            return cell
    names = ", ".join(c["name"] for c in manifest["workloads"])
    raise BenchError(f"workload {workload!r} is not in BENCHMARK.json "
                     f"(it has: {names})")


def metrics_of(manifest: dict, group: str, workload: str) -> list:
    """The metrics of `end_to_end` / `per_layer` this cell reports: all
    that name it under `workloads`, and all that have no such key."""
    return [m for m in manifest[group]
            if "workloads" not in m or workload in m["workloads"]]


def require_chips(chips: int):
    """`jax.devices()`, all of them TPUs and exactly as many as the cell
    asks for (the program builds its mesh from every device it sees)."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise BenchError(f"JAX found no TPU (platform {platform!r}, "
                         f"{devices[0].device_kind}); the benchmark has "
                         "no CPU path")
    if len(devices) != chips:
        raise BenchError(f"the cell asks for {chips} chip(s) and JAX "
                         f"sees {len(devices)}")
    return devices


def peaks_for(device_kind: str) -> dict:
    table = load_json(os.path.join(BENCH_DIR, "peaks.json"), "table of peaks")
    if device_kind not in table["devices"]:
        raise BenchError(f"device_kind {device_kind!r} is not in "
                         "benchmark/peaks.json; a peak is never borrowed")
    return table["devices"][device_kind]


def reduce_trace(ctx, run: dict) -> None:
    """The run's `.xplane.pb` (under the directory the kind traced into)
    reduced for the readers: `ctx.trace_data`, `ctx.trace_summary`."""
    import glob
    import shutil

    import trace_reduce

    paths = glob.glob(os.path.join(run["trace_dir"], "plugins", "profile",
                                   "*", "*.xplane.pb"))
    if len(paths) != 1:
        raise BenchError(f"expected one .xplane.pb under "
                         f"{run['trace_dir']}, found {paths}")
    if ctx.args.keep_trace:
        os.makedirs(ctx.args.keep_trace, exist_ok=True)
        shutil.copy(paths[0], os.path.join(
            ctx.args.keep_trace, ctx.cell["name"] + ".xplane.pb"))
    ctx.trace_data = trace_reduce.load(paths[0])
    ctx.trace_summary = trace_reduce.summary(ctx.trace_data,
                                             ctx.window["seconds"])
    shutil.rmtree(run["trace_dir"], ignore_errors=True)


def read_layer_metrics(manifest: dict, cell: dict, ctx) -> dict:
    out = {}
    for metric in metrics_of(manifest, "per_layer", cell["name"]):
        name = metric["name"]
        spec = load_json(os.path.join(BENCH_DIR, "layer_metrics",
                                      name + ".json"),
                         f"per-layer metric {name!r}")
        reader = load_module("readers", spec["reader"],
                             f"reader of metric {name!r}:")
        value = reader.read(ctx, spec.get("args", {}))
        if value is None:          # nothing to read: left out of the line
            continue
        out[name] = {"value": float(value), "unit": metric["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--readings", choices=("run", "control", "all"),
                    default="run",
                    help="'control' also reads the control against the "
                         "reference, 'all' the planted faults too (how "
                         "the limits were set; no check runs them)")
    ap.add_argument("--keep-trace", default=None,
                    help="copy the run's .xplane.pb into this directory")
    args = ap.parse_args(argv)

    manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"),
                         "the manifest")
    cell = find_cell(manifest, args.workload)
    configs = {c["name"]: c for c in manifest["configs"]}
    if cell["config"] not in configs:
        raise BenchError(f"cell {cell['name']!r} names configuration "
                         f"{cell['config']!r}, which BENCHMARK.json lacks")
    config = load_json(os.path.join(ROOT, configs[cell["config"]]["file"]),
                       f"configuration {cell['config']!r}")
    traffic = load_json(os.path.join(BENCH_DIR, "traffic",
                                     cell["traffic"] + ".json"),
                        f"traffic mix {cell['traffic']!r}")
    kind = load_module("kinds", traffic["kind"],
                       f"kind of traffic mix {cell['traffic']!r}:")

    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    sys.path.insert(0, BENCH_DIR)
    devices = require_chips(cell["chips"])
    import jax

    from code2vec_tpu.device import enable_compile_cache
    enable_compile_cache()
    # every program goes to the cache, the small ones too: the second run
    # of a cell in a checkout compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    ctx = types.SimpleNamespace(
        args=args, manifest=manifest, cell=cell, config=config,
        traffic=traffic, devices=devices, device=device,
        peaks=peaks_for(device["kind"]) if device["platform"] == "tpu"
        else None,
        root=ROOT, bench_dir=BENCH_DIR, t_start=T_START,
        seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        load_module=load_module,
        workdir=os.path.join(ROOT, ".bench_work", cell["name"]),
        cache_dir=os.path.join(ROOT, ".bench_work", "_cache"))
    os.makedirs(ctx.workdir, exist_ok=True)
    os.makedirs(ctx.cache_dir, exist_ok=True)

    # the kind builds, warms, measures, reads the memory peak, frees the
    # program's state and only then runs the reference
    run = kind.run(ctx)

    device["memory_peak_bytes"] = int(run["memory_peak_bytes"])
    if ctx.trace:
        reduce_trace(ctx, run)
        metrics = read_layer_metrics(manifest, cell, ctx)
        device["busy_s"] = float(ctx.trace_summary["busy_s"])
        device["window_s"] = float(ctx.trace_summary["window_s"])
    else:
        metrics = {}
        for m in metrics_of(manifest, "end_to_end", cell["name"]):
            if m["name"] not in run["end_to_end"]:
                raise BenchError(f"kind {traffic['kind']!r} reported no "
                                 f"{m['name']!r} in cell {cell['name']!r}")
            metrics[m["name"]] = {"value": float(run["end_to_end"][m["name"]]),
                                  "unit": m["unit"]}
    result = {"correct": bool(run["correct"]),
              "attempted": int(run["attempted"]),
              "failed": int(run["failed"]),
              "metrics": metrics, "device": device}
    if ctx.trace:
        result["breakdown"] = ctx.trace_summary["breakdown"]
    result["facts"] = run.get("facts", {})
    result["compared"] = run["compared"]
    sys.stdout.flush()
    for name, c in run["compared"].items():
        print(f"compared {name}: {c['value']:.6g} (limit {c['limit']:.6g})"
              + ("" if c["value"] <= c["limit"] else "  <-- over"),
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BenchError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        sys.exit(2)
