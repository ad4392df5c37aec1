#!/usr/bin/env python3
"""Multi-process distribution-cost harness, ON THE CPU: the
MULTICHIP_r*.json producer. Every worker it spawns is forced onto
virtual CPU devices (`compat.cpu_worker_env`); it never touches a
chip, and "MULTICHIP" names the topology it emulates, not where it
ran — its numbers are counts and CPU ratios, not device metrics.

It answers "what fraction of single-process speed survives the REAL
process boundary" by running the same synthetic train harness twice
over the SAME global device count and global batch:

  baseline  1 process  x (procs * devices_per_proc) local CPU devices
  multi     `--procs` OS processes x `--devices_per_proc` devices each,
            joined via `jax.distributed.initialize` with Gloo
            collectives (tests/mp_worker.py's harness shape) — the
            code path a v4-32 pod slice runs, minus the ICI.

`scaling_efficiency` = multi global pc/s / baseline global pc/s: with
equal chips and equal math, anything below 1.0 is pure
distribution cost (Gloo gradient allreduce, per-process infeed,
coordination). The multi leg's workers are CPU-pinned to disjoint
equal core groups (`taskset`) so each emulated host owns its cores the
way a pod host owns its chips — without pinning every worker's XLA
threadpool claims ALL cores and the ratio measures N× scheduler
oversubscription, not distribution cost. See `_core_groups`.

Usage (repo root):

  python tools/multichip_bench.py                      # dense DP step
  python tools/multichip_bench.py --sparse             # sparse tables
  python tools/multichip_bench.py --telemetry_dir /tmp/tele
      # per-process run dirs + the `telemetry_report.py --merge` table

Kill-mid-run leg (ISSUE 13, on by default; `--no_kill_leg` skips it):
after the scaling pairs, the driver runs the elastic-recovery half of
`tools/chaos.py kill_resize` — a real 2-process training cohort under
the shrink-policy supervisor, one peer SIGKILLed mid-epoch, the cohort
re-formed at 1 process — and records the recovery cost into the round
file: `recovery_steps_lost` (kill step minus the committed step the
re-formed cohort resumed from) and `recovery_seconds` (kill to first
post-resize training step). `bench_regression --kind multichip` gates
both as lower-is-better.

Writes `MULTICHIP_r<next>.json` into `--out` (default: repo root)
and prints the result JSON to stdout, bench.py style. `--no_write` suppresses the file for ad-hoc runs.

The worker half of this file re-executes itself with `--worker`; the
parent owns spawn, timeout and orphan cleanup (no worker survives a
failed run — the same discipline tests/conftest.py asserts for the
test suite's subprocesses).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import subprocess
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Synthetic harness defaults: a large global batch so a step is
# compute-bound (the efficiency number should measure the distribution
# cost against real work, not the dispatch floor) and small vocab
# tables so the dense-grad allreduce doesn't swamp the 2-core CI
# container the harness was calibrated on. Measured there (round 14):
# the per-step multi-leg overhead is roughly CONSTANT in the batch but
# grows with max_contexts (0.737 at B=1536, 0.785 at B=4096, 0.874 at
# B=8192, all C=64; doubling C at B=4096 doubled the overhead) — so
# the calibrated shape is large-batch/modest-C, which is also the
# direction of the real java-large per-chip load. The config is
# recorded in every MULTICHIP_r*.json, so the regression gate always
# compares like-for-like rounds.
DEF_BATCH = 8192
DEF_CONTEXTS = 64
DEF_STEPS = 10
DEF_WARMUP = 2
DEF_TOKEN_VOCAB = 2048
DEF_PATH_VOCAB = 2048
DEF_TARGET_VOCAB = 2048
DEF_EMBED = 128
DEF_NUM_SAMPLED = 512


def _percentile(vals, p):
    """Linear-interpolated percentile (numpy 'linear' rule). The
    nearest-rank shortcut is WRONG for this driver's 2-element
    per-process p50 lists: int(round(0.5)) banker's-rounds to 0, so
    'p50' would always elect the FASTER worker and bias the gated
    scaling_efficiency headline optimistic."""
    s = sorted(vals)
    if not s:
        return float("nan")
    x = (p / 100.0) * (len(s) - 1)
    lo = int(x)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (x - lo)


# ---------------------------------------------------------------- worker

def _worker(args) -> None:
    """One process of a leg. The parent exported JAX_PLATFORMS/XLA_FLAGS
    via compat.cpu_worker_env BEFORE this interpreter started, so the
    device count is pinned at backend build."""
    sys.path.insert(0, _REPO)

    from code2vec_tpu.parallel.distributed import maybe_initialize

    if args.num_procs > 1:
        maybe_initialize(
            coordinator_address=f"127.0.0.1:{args.port}",
            num_processes=args.num_procs, process_id=args.proc_id)

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from code2vec_tpu.models.encoder import ModelDims, init_params
    from code2vec_tpu.parallel.mesh import make_mesh
    from code2vec_tpu.parallel.sharding import (shard_batch,
                                                shard_opt_state,
                                                shard_params)
    from code2vec_tpu.training.steps import make_train_step

    assert jax.process_count() == args.num_procs, (
        jax.process_count(), args.num_procs)

    dims = ModelDims(token_vocab_size=args.token_vocab,
                     path_vocab_size=args.path_vocab,
                     target_vocab_size=args.target_vocab,
                     embeddings_size=args.embed,
                     max_contexts=args.max_contexts,
                     dropout_keep_rate=1.0)
    mesh = make_mesh(0, 1)  # pure data parallelism over every device
    B_global = args.batch
    B_local = B_global // args.num_procs

    params = init_params(jax.random.PRNGKey(0), dims)
    optimizer = optax.adam(1e-3)
    if args.sparse:
        from code2vec_tpu.training.sparse_steps import \
            init_sparse_opt_state
        opt_state = init_sparse_opt_state(params, optimizer, True)
    else:
        opt_state = optimizer.init(params)
    params = shard_params(mesh, params)
    opt_state = shard_opt_state(mesh, opt_state, params)

    step = make_train_step(
        dims, optimizer, use_sampled_softmax=True,
        num_sampled=args.num_sampled, compute_dtype=jnp.float32,
        mesh=mesh if args.sparse else None,
        sparse_updates=args.sparse, learning_rate=1e-3)

    def local_batch(seed: int):
        """This process's slice of a deterministic GLOBAL batch — every
        leg sees identical global data regardless of process count."""
        r = np.random.default_rng(seed)
        C = dims.max_contexts
        lo, hi = args.proc_id * B_local, (args.proc_id + 1) * B_local
        labels = r.integers(0, dims.target_vocab_size, (B_global,),
                            dtype=np.int32)
        src = r.integers(0, dims.token_vocab_size, (B_global, C),
                         dtype=np.int32)
        pth = r.integers(0, dims.path_vocab_size, (B_global, C),
                         dtype=np.int32)
        dst = r.integers(0, dims.token_vocab_size, (B_global, C),
                         dtype=np.int32)
        mask = (r.random((B_global, C)) > 0.3).astype(np.float32)
        mask[:, 0] = 1.0
        weights = np.ones((B_global,), dtype=np.float32)
        return tuple(a[lo:hi] for a in
                     (labels, src, pth, dst, mask, weights))

    n_rot = 4  # rotate distinct batches so no cross-step result reuse
    batches = [shard_batch(mesh, local_batch(s), process_local=True)
               for s in range(n_rot)]
    assert batches[0][0].shape[0] == B_global

    telemetry = None
    if args.telemetry_dir:
        from code2vec_tpu.obs.telemetry import Telemetry

        class _Cfg:  # manifest snapshot: the fields the report reads
            MAX_CONTEXTS = args.max_contexts
            BATCH_SIZE = args.batch
            SPARSE_EMBEDDING_UPDATES = bool(args.sparse)

        telemetry = Telemetry.create(args.telemetry_dir, config=_Cfg(),
                                     mesh=mesh,
                                     component="multichip_bench")

    # keys pre-split outside the timed loop (bench.py discipline: a
    # split is its own dispatch)
    total = args.warmup + args.steps
    keys = list(jax.random.split(jax.random.PRNGKey(11), total))

    step_ms = []
    loss = None
    for i in range(total):
        t0 = time.perf_counter()
        params, opt_state, loss = step(params, opt_state,
                                       batches[i % n_rot], keys[i])
        lf = float(loss)  # per-step hard sync: honest walls, every leg
        dt_ms = (time.perf_counter() - t0) * 1e3
        if i >= args.warmup:
            step_ms.append(dt_ms)
            if telemetry is not None:
                telemetry.event("step", step=i - args.warmup,
                                step_ms=dt_ms, infeed_wait_ms=0.0,
                                examples=B_local, loss=lf)

    run_dir = getattr(telemetry, "run_dir", None)
    if telemetry is not None:
        telemetry.close()

    total_s = sum(step_ms) / 1e3
    local_pc_s = (B_local * dims.max_contexts * len(step_ms)) / total_s
    out = {
        "proc_id": args.proc_id,
        "num_procs": args.num_procs,
        "steps": len(step_ms),
        "ms_per_step_p50": _percentile(step_ms, 50),
        "ms_per_step_p95": _percentile(step_ms, 95),
        "local_pc_per_sec": local_pc_s,
        "final_loss": float(loss),
        "run_dir": run_dir,
    }
    with open(os.path.join(args.out_dir,
                           f"proc{args.proc_id}.json"), "w",
              encoding="utf-8") as f:
        json.dump(out, f)


# ---------------------------------------------------------------- parent

def _core_groups(num_procs: int) -> list:
    """Partition this box's cores into `num_procs` contiguous groups —
    one per worker, like a pod host owns its own chips. Without
    pinning, every worker's XLA threadpool sizes itself to ALL cores,
    so an N-process leg runs N× oversubscribed against the 1-process
    baseline. (On the 2-core CI box the pinned and unpinned ratios
    measure the same — the multi leg there is bound by loopback-TCP
    allreduce latency, not thread thrash — but on wider hosts the
    oversubscription term grows with the core count, so the harness
    always pins.) Returns [] when pinning can't be done fairly (fewer
    cores than workers, or no taskset)."""
    ncores = os.cpu_count() or 1
    if num_procs <= 1 or ncores < num_procs:
        return []
    import shutil
    if not shutil.which("taskset"):
        return []
    per = ncores // num_procs
    # leftover cores go unused on the multi leg: equal shares keep the
    # workers symmetric (a straggler drags every collective)
    return [list(range(i * per, (i + 1) * per))
            for i in range(num_procs)]


def _spawn_leg(num_procs: int, devices_per_proc: int, leg_dir: str,
               forward: list, telemetry_dir: str | None,
               timeout_s: float) -> dict:
    """Run one leg (1 or N processes), aggregate the per-process
    results. Kills every worker on any failure — no orphans."""
    sys.path.insert(0, _REPO)
    from code2vec_tpu.parallel.compat import cpu_worker_env, free_port

    os.makedirs(leg_dir, exist_ok=True)
    n_devices = num_procs * devices_per_proc if num_procs > 1 \
        else devices_per_proc
    port = free_port() if num_procs > 1 else 0
    env = cpu_worker_env(n_devices if num_procs == 1
                         else devices_per_proc)
    groups = _core_groups(num_procs)
    procs = []
    for pid in range(num_procs):
        pin = ["taskset", "-c",
               ",".join(str(c) for c in groups[pid])] if groups else []
        cmd = pin + [sys.executable, os.path.abspath(__file__),
                     "--worker",
                     "--proc_id", str(pid), "--num_procs",
                     str(num_procs),
                     "--port", str(port), "--out_dir", leg_dir] + forward
        if telemetry_dir:
            cmd += ["--telemetry_dir",
                    os.path.join(telemetry_dir, f"leg{num_procs}")]
        procs.append(subprocess.Popen(
            cmd, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True, cwd=_REPO))
    outs = []
    try:
        for p in procs:
            outs.append(p.communicate(timeout=timeout_s)[0])
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    for pid, (p, out) in enumerate(zip(procs, outs)):
        if p.returncode != 0:
            raise RuntimeError(
                f"worker {pid} of {num_procs}-process leg "
                f"failed (rc {p.returncode}):\n{out}")
    per_proc = []
    for pid in range(num_procs):
        with open(os.path.join(leg_dir, f"proc{pid}.json"),
                  encoding="utf-8") as f:
            per_proc.append(json.load(f))
    all_p50 = [r["ms_per_step_p50"] for r in per_proc]
    return {
        "n_processes": num_procs,
        "n_devices": n_devices,
        "pc_per_sec": sum(r["local_pc_per_sec"] for r in per_proc),
        "ms_per_step_p50": _percentile(all_p50, 50),
        "final_loss": per_proc[0]["final_loss"],
        "cpu_pinned": bool(groups),
        "per_process": per_proc,
    }


def next_round(out_dir: str) -> int:
    rounds = [0]
    for path in glob.glob(os.path.join(out_dir, "MULTICHIP_r*.json")):
        m = re.search(r"MULTICHIP_r(\d+)\.json$",
                      os.path.basename(path))
        if m:
            rounds.append(int(m.group(1)))
    return max(rounds) + 1


def build_result(base: dict, multi: dict, args_ns) -> dict:
    """The MULTICHIP result object. `scaling_efficiency` is the gated
    headline: multi-process throughput over the single-process
    same-chip-count baseline (equal chips, equal global batch — the
    ratio isolates pure distribution cost). It is computed from the
    MEDIAN step times: with equal global batch the throughput ratio is
    the inverse step-time ratio, and the median is robust to the
    transient multi-second Gloo hiccups the loopback TCP harness
    produces (the per-process p95 column keeps them visible;
    `scaling_efficiency_mean` is the mean-based ratio for
    comparison)."""
    eff = base["ms_per_step_p50"] / multi["ms_per_step_p50"] \
        if multi["ms_per_step_p50"] > 0 else float("nan")
    eff_mean = multi["pc_per_sec"] / base["pc_per_sec"] \
        if base["pc_per_sec"] > 0 else float("nan")
    # per-host step-time skew (ISSUE 17): worst member p50 over the
    # cohort median p50 — the offline twin of the fleet plane's live
    # `fleet/step_p50_skew`. 1.0 = perfectly even hosts; a straggler
    # inflates it and the lock-step all-reduce makes everyone pay, so
    # bench_regression gates it LOWER-is-better.
    member_p50 = [r["ms_per_step_p50"] for r in multi["per_process"]]
    med = _percentile(member_p50, 50)
    skew = max(member_p50) / med \
        if member_p50 and med > 0 else float("nan")
    return {
        "schema": "multichip",
        "sparse": bool(args_ns.sparse),
        "host_cores": os.cpu_count(),
        "cpu_pinned": bool(multi.get("cpu_pinned")),
        "n_processes": multi["n_processes"],
        "devices_per_process": args_ns.devices_per_proc,
        "n_devices": multi["n_devices"],
        "batch_global": args_ns.batch,
        "max_contexts": args_ns.max_contexts,
        "steps": args_ns.steps,
        "baseline_pc_per_sec": base["pc_per_sec"],
        "baseline_ms_per_step_p50": base["ms_per_step_p50"],
        "multi_pc_per_sec": multi["pc_per_sec"],
        "multi_ms_per_step_p50": multi["ms_per_step_p50"],
        "pc_per_sec_per_chip": multi["pc_per_sec"]
        / multi["n_devices"],
        "scaling_efficiency": eff,
        "scaling_efficiency_mean": eff_mean,
        "host_skew_ratio": skew,
        "loss_delta": abs(multi["final_loss"] - base["final_loss"]),
        "baseline": base,
        "multi": multi,
    }


def _add_harness_args(ap: argparse.ArgumentParser) -> None:
    ap.add_argument("--steps", type=int, default=DEF_STEPS)
    ap.add_argument("--warmup", type=int, default=DEF_WARMUP)
    ap.add_argument("--batch", type=int, default=DEF_BATCH)
    ap.add_argument("--max_contexts", type=int, default=DEF_CONTEXTS)
    ap.add_argument("--token_vocab", type=int, default=DEF_TOKEN_VOCAB)
    ap.add_argument("--path_vocab", type=int, default=DEF_PATH_VOCAB)
    ap.add_argument("--target_vocab", type=int,
                    default=DEF_TARGET_VOCAB)
    ap.add_argument("--embed", type=int, default=DEF_EMBED)
    ap.add_argument("--num_sampled", type=int, default=DEF_NUM_SAMPLED)
    ap.add_argument("--sparse", action="store_true",
                    help="sparse embedding updates (the round-14 mesh "
                         "path: dedup/segment-sum/live-row inside "
                         "shard_map — no dense [V, E] carrier)")
    ap.add_argument("--telemetry_dir", default=None)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="2-leg (1-process vs N-process Gloo) "
                    "scaling-efficiency bench; writes "
                    "MULTICHIP_r<next>.json")
    ap.add_argument("--worker", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--proc_id", type=int, default=0,
                    help=argparse.SUPPRESS)
    ap.add_argument("--num_procs", type=int, default=1,
                    help=argparse.SUPPRESS)
    ap.add_argument("--port", type=int, default=0,
                    help=argparse.SUPPRESS)
    ap.add_argument("--out_dir", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--procs", type=int, default=2,
                    help="process count of the multi leg")
    ap.add_argument("--devices_per_proc", type=int, default=4)
    ap.add_argument("--out", default=_REPO,
                    help="where MULTICHIP_r<N>.json lands")
    ap.add_argument("--no_write", action="store_true",
                    help="print JSON only, write no round file")
    ap.add_argument("--timeout_s", type=float, default=900.0,
                    help="per-leg wall clock before workers are killed")
    ap.add_argument("--no_kill_leg", action="store_true",
                    help="skip the kill-mid-run recovery leg (the "
                         "elastic-resume cost measurement)")
    ap.add_argument("--reps", type=int, default=3,
                    help="baseline/multi leg pairs to run back-to-back;"
                         " the MEDIAN-ratio pair is reported (shared "
                         "boxes have minute-scale noise bursts — "
                         "adjacent pairing cancels them, the median "
                         "drops a burst that hits one pair)")
    _add_harness_args(ap)
    args = ap.parse_args(argv)

    if args.worker:
        _worker(args)
        return 0

    if args.batch % (args.procs * args.devices_per_proc):
        print(f"error: --batch {args.batch} must divide over "
              f"{args.procs} procs x {args.devices_per_proc} devices",
              file=sys.stderr)
        return 2

    forward = []
    for k in ("steps", "warmup", "batch", "max_contexts",
              "token_vocab", "path_vocab", "target_vocab", "embed",
              "num_sampled"):
        forward += [f"--{k}", str(getattr(args, k))]
    if args.sparse:
        forward.append("--sparse")

    # Gloo over loopback TCP intermittently dies mid-run with
    # `EnforceNotMet: op.preamble.length <= op.nbytes` (a transport
    # race the compat docstring documents; the crashed worker takes
    # its peer down with it). One rep's crash is transient infra, not
    # a measurement — retry the whole PAIR on a fresh port (each
    # attempt's _spawn_leg picks one) so the elected ratio never mixes
    # legs from different attempts. TimeoutExpired is the same failure
    # seen from the other side: the crashed worker's peer can sit
    # inside a collective until the (CPU-widened) heartbeat tolerance
    # expires, so the parent hits its communicate() wall first. The
    # retry itself is the shared resilience policy (ISSUE 10) — the
    # hand-rolled attempt loop this file used to carry is gone.
    sys.path.insert(0, _REPO)
    from code2vec_tpu.resilience import retry as retry_mod
    pair_retry = retry_mod.transient_distributed(
        "multichip-rep", base_delay_s=0.2,
        log=lambda m: print(m, file=sys.stderr))

    import tempfile
    pairs = []
    rep_retries = 0
    with tempfile.TemporaryDirectory(prefix="multichip_") as tmp:
        t0 = time.time()
        for rep in range(max(1, args.reps)):
            calls = {"n": 0}

            def run_pair():
                calls["n"] += 1
                tag = f"{rep}_{calls['n']}"
                base = _spawn_leg(
                    1, args.devices_per_proc * args.procs,
                    os.path.join(tmp, f"base{tag}"),
                    forward, args.telemetry_dir, args.timeout_s)
                multi = _spawn_leg(
                    args.procs, args.devices_per_proc,
                    os.path.join(tmp, f"multi{tag}"),
                    forward, args.telemetry_dir, args.timeout_s)
                return base, multi

            base, multi = pair_retry.call(run_pair)
            rep_retries += calls["n"] - 1
            pairs.append((base, multi))
            print(f"rep {rep}: base p50 "
                  f"{base['ms_per_step_p50']:.0f} ms, multi p50 "
                  f"{multi['ms_per_step_p50']:.0f} ms, ratio "
                  f"{base['ms_per_step_p50'] / multi['ms_per_step_p50']:.3f}",
                  file=sys.stderr)

        # kill-mid-run leg (ISSUE 13): the elastic-recovery cost of a
        # REAL training cohort losing a peer — reuses the run half of
        # tools/chaos.py kill_resize (shrink-policy supervisor, fault-
        # injected SIGKILL, re-form at N−1)
        kill_leg = None
        if not args.no_kill_leg:
            from tools import chaos as chaos_mod
            print("kill leg: 2-process cohort, SIGKILL one peer, "
                  "re-form at 1 ...", file=sys.stderr)
            kill_dir = os.path.join(tmp, "kill_leg")
            os.makedirs(kill_dir, exist_ok=True)
            kill_leg = chaos_mod.run_kill_resize(
                kill_dir, timeout_s=args.timeout_s)
            print(f"kill leg: resumed from step "
                  f"{kill_leg['resumed_from_step']}, steps lost "
                  f"{kill_leg['recovery_steps_lost']}, recovery "
                  f"{kill_leg['recovery_seconds']}s, resizes "
                  f"{kill_leg['resizes']}", file=sys.stderr)
        wall = time.time() - t0

    # elect the median-ratio pair: each pair's legs ran back-to-back,
    # so a slow-varying noise burst perturbs both legs of a pair and
    # cancels in its ratio; a burst spanning only one leg skews that
    # pair's ratio, and the median drops it
    ratios = [b["ms_per_step_p50"] / m["ms_per_step_p50"]
              for b, m in pairs]
    order = sorted(range(len(pairs)), key=lambda i: ratios[i])
    elected = order[(len(order) - 1) // 2]
    base, multi = pairs[elected]

    result = build_result(base, multi, args)
    result["bench_wall_s"] = wall
    result["rep_retries"] = rep_retries
    if kill_leg is not None:
        # the leg is a MEASUREMENT only when the injected kill really
        # fired after a committed checkpoint existed and the re-formed
        # cohort finished — a leg that lost every retry to the
        # loopback-Gloo startup race must not smuggle fabricated
        # numbers into the gated trajectory (they'd read as a phantom
        # regression now, then pad the MAD band against real ones)
        valid = bool(kill_leg["kill_fired"]
                     and kill_leg["supervisor_rc"] == 0
                     and kill_leg["resumed_from_step"] is not None)
        result["kill_leg"] = dict(
            {k: kill_leg[k] for k in
             ("kill_fired", "supervisor_rc", "restarts", "resizes",
              "full_relaunches", "cohort_size_final",
              "resumed_from_step", "kill_at_step")}, valid=valid)
        if valid:
            # gated headline metrics at top level (bench_regression
            # reads them flat, lower-is-better)
            result["recovery_steps_lost"] = \
                kill_leg["recovery_steps_lost"]
            result["recovery_seconds"] = kill_leg["recovery_seconds"]
        else:
            print("kill leg invalid after retries (transient infra); "
                  "recovery metrics NOT recorded this round",
                  file=sys.stderr)
    result["reps"] = [{"scaling_efficiency": r,
                       "baseline_ms_per_step_p50": b["ms_per_step_p50"],
                       "multi_ms_per_step_p50": m["ms_per_step_p50"],
                       "elected": i == elected}
                      for i, (r, (b, m)) in
                      enumerate(zip(ratios, pairs))]

    if args.telemetry_dir:
        # render the per-process runs as ONE logical multi-host run —
        # the telemetry_report --merge shape (obs_top renders the same
        # live via per-process --metrics_port scrapes)
        from tools.telemetry_report import render_merged
        run_dirs = [r["run_dir"] for r in multi["per_process"]
                    if r.get("run_dir")]
        if run_dirs:
            result["merged_report"] = render_merged(run_dirs)

    if not args.no_write:
        rnd = next_round(args.out)
        path = os.path.join(args.out, f"MULTICHIP_r{rnd:02d}.json")
        result["round"] = rnd
        with open(path, "w", encoding="utf-8") as f:
            json.dump(result, f, indent=1)
        print(f"wrote {path}", file=sys.stderr)

    print(json.dumps(result, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
