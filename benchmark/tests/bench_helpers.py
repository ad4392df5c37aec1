"""Shared by the benchmark's own tests: a temporary copy of
`BENCHMARK.json` and `benchmark/` with a tiny configuration, two cells a
configuration, a second traffic mix and one more per-layer metric, all
added as NEW files and NEW entries (no file that is there is edited), and
the way to run a cell of it on virtual CPU devices."""

import json
import os
import shutil
import subprocess
import sys

TESTS = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(TESTS))

# tiny sizes: the embedding keeps its width (the program has no flag for
# it), everything else shrinks
TINY_MODEL = dict(tokens=300, paths=200, targets=150, max_contexts=12,
                  num_sampled=32)
# limits of the tiny configurations, set as the real ones are: above what
# the program reads against the reference at this size on the CPU (bag
# change 0.006, dense 0.006; xf2 loss 0.0013, dense 0.009; a dozen seeds)
# and below what the fp8 control and the planted faults read (fp8
# change 0.23-0.45; half batch grad 0.33)
TINY_LIMITS = {"loss1_gap": 4e-3, "loss2_gap": 4e-3, "loss3_gap": 4e-3,
               "grad_norm_gap": 0.01, "change_norm_gap": 0.012,
               "dense_grad_diff": 0.025}


def make_copy(dst: str) -> str:
    shutil.rmtree(dst, ignore_errors=True)
    os.makedirs(dst)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dst)
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(dst, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = os.path.join(dst, "benchmark")
    with open(os.path.join(dst, "BENCHMARK.json")) as f:
        manifest = json.load(f)

    with open(os.path.join(bench, "traffic", "corpus-train.json")) as f:
        traffic = json.load(f)
    traffic.update(steps_per_epoch=4, trace_seconds=2)
    # a pair of configuration and traffic may stand once in the manifest,
    # so the four-device cells get a mix of their own, as the x4 cell has
    for name in ("corpus-tiny", "corpus-tiny-x4"):
        traffic["name"] = name
        with open(os.path.join(bench, "traffic", name + ".json"), "w") as f:
            json.dump(traffic, f)

    for enc, base in (("bag", "java-large-bag"), ("xf2", "java-large-xf2")):
        with open(os.path.join(bench, "configs", base + ".json")) as f:
            config = json.load(f)
        config["name"] = "tiny-" + enc
        config["model"].update(TINY_MODEL)
        config["train"].update(batch_per_chip=16, epochs=400)
        config["flags"] = ["--sampled_softmax", "--num_sampled", "32",
                           "--max_contexts", "12", "--epochs", "400"]
        if enc == "xf2":
            config["flags"] += ["--encoder", "transformer"]
        config["reference"]["block"] = 8
        config["correct"]["limits"] = TINY_LIMITS
        rel = f"benchmark/configs/{config['name']}.json"
        with open(os.path.join(dst, rel), "w") as f:
            json.dump(config, f)
        manifest["configs"].append({"name": config["name"], "source": "test",
                                    "file": rel, "reduced": [],
                                    "why": "test"})
        for chips in (1, 4):
            manifest["workloads"].append({
                "name": f"tiny-{enc}-{chips}", "config": config["name"],
                "traffic": "corpus-tiny" if chips == 1 else "corpus-tiny-x4",
                "chips": chips, "why": "test"})

    # one more per-layer metric, read by a reader that is already there
    with open(os.path.join(bench, "layer_metrics", "dispatch_ms.json"),
              "w") as f:
        json.dump({"name": "dispatch_ms", "layer": "entry",
                   "unit": "ms/step", "moves": "train_methods_per_s",
                   "reader": "host_span",
                   "args": {"field": "dispatch_s"}}, f)
    manifest["per_layer"].append({
        "name": "dispatch_ms", "unit": "ms/step", "better": "lower",
        "source": "host_clock", "layer": "entry",
        "moves": "train_methods_per_s",
        "workloads": [w["name"] for w in manifest["workloads"]
                      if w["name"].startswith("tiny-")]})
    for metric in manifest["per_layer"]:       # the new cells join lists
        if metric["name"].startswith("allreduce"):
            metric["workloads"] += ["tiny-bag-4", "tiny-xf2-4"]
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return dst


def run_cell(root: str, workload: str, devices: int, *, seed: int = 5,
             seconds: float = 1.0, trace: int = 0, fault: str = None,
             extra=()):
    """(return code, the result line parsed or None, standard error)."""
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    if fault:
        env["BENCH_TEST_FAULT"] = fault
    proc = subprocess.run(
        [sys.executable, os.path.join(TESTS, "cpu_run.py"), root,
         str(devices), "--", "--workload", workload, "--seed", str(seed),
         "--seconds", str(seconds), "--trace", str(trace), *extra],
        env=env, capture_output=True, text=True, timeout=900)
    lines = proc.stdout.strip().splitlines()
    result = json.loads(lines[-1]) if proc.returncode == 0 and lines \
        else None
    err = "\n".join(ln for ln in proc.stderr.splitlines()
                    if "cpu_aot_loader" not in ln)
    return proc.returncode, result, err
