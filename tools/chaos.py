#!/usr/bin/env python3
"""Chaos scenario runner (ISSUE 10): exercise the detect -> decide ->
recover loop end to end, deterministically, on the CPU harness —
every child it spawns is forced onto virtual CPU devices
(`compat.cpu_worker_env`, `--backend cpu`); nothing here reaches a
chip.

Each scenario builds a tiny synthetic dataset, runs REAL
`code2vec.py` training processes under the REAL supervisor
(training/supervisor.py) with a `--faults` spec arming the relevant
failpoint, and asserts the recovery contract:

  kill_resume        SIGKILL the (1-process) training run mid-epoch
                     under constant LR; the supervisor relaunches it
                     with --auto_resume and the final checkpoint is
                     BIT-IDENTICAL to an uninterrupted run's — the
                     step-keyed rng + resumed shuffle stream replay
                     the exact trajectory (the chaos-parity
                     acceptance). Tier-1 smoke: tests/test_chaos.py.
  kill_resume_2proc  Same contract through the 2-process Gloo cohort:
                     SIGKILL worker 1 mid-epoch, the supervisor
                     detects the dead peer, reaps the survivor, and
                     relaunches the WHOLE cohort coherently on a
                     fresh port (slow-marked test).
  corrupt_checkpoint Bit-flip a leaf blob in the latest committed
                     step; the supervisor's pre-launch verification
                     detects it, QUARANTINES the step dir, emits an
                     `alert` event through the alert engine, and the
                     run resumes from the prior committed step.
  serve_swap_kill    The serving-plane acceptance (ISSUE 18): under
                     open-loop Poisson load against a replica pool, a
                     replica dies mid-request (`serve/kill`, action
                     raise), a VERIFIED committed checkpoint hot-swaps
                     in one replica at a time, and a bit-flipped step
                     is REFUSED (ticket alert) — while p99 holds the
                     SLO, zero requests are lost, and zero new jit
                     compilations happen under load.
  kill_resize        The elastic-resume parity bar (ISSUE 13): SIGKILL
                     one peer of a 2-process cohort mid-epoch; the
                     supervisor (resize_policy=shrink) RE-FORMS the
                     cohort at 1 process instead of relaunching the
                     world — zero full-cohort relaunches — the
                     checkpoint layer reshards the restore onto the
                     new mesh, and the finished run's params are
                     BIT-IDENTICAL to an uninterrupted 1-process run
                     resumed from the same committed step (constant
                     LR). Also measures recovery cost
                     (recovery_steps_lost, recovery_seconds — the
                     multichip bench's kill-mid-run leg reuses the
                     run half of this scenario).

Usage (repo root):

  python tools/chaos.py --list
  python tools/chaos.py kill_resume --out /tmp/chaos
  python tools/chaos.py corrupt_checkpoint --out /tmp/chaos

Prints a JSON result per scenario; exit 0 = contract held, 1 = it did
not. The fault markers make every kill a cross-restart once-latch, so
a scenario is a TEST, not a dice roll.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import subprocess
import sys
import tempfile
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

# tiny-but-learnable synthetic corpus (the tests/helpers.py shape,
# re-stated here so a TOOL does not import the test tree)
_TOKENS = ["foo", "bar", "baz", "qux", "value", "name", "index", "count"]
_PATHS = [str(h) for h in (123456, -98765, 424242, 1337, -777, 31415)]
_TARGETS = ["get|value", "set|value", "get|name", "set|name",
            "add|item", "remove|item", "to|string", "is|empty"]


def _raw_lines(n: int, seed: int, max_ctx: int) -> list:
    rng = random.Random(seed)
    lines = []
    for _ in range(n):
        t = rng.randrange(len(_TARGETS))
        ctxs = []
        for _ in range(rng.randint(1, max_ctx)):
            a = _TOKENS[(t + rng.randrange(2)) % len(_TOKENS)]
            b = _TOKENS[(t * 3 + rng.randrange(2)) % len(_TOKENS)]
            p = _PATHS[t % len(_PATHS)] if rng.random() < 0.7 \
                else rng.choice(_PATHS)
            ctxs.append(f"{a},{p},{b}")
        lines.append(_TARGETS[t] + " " + " ".join(ctxs))
    return lines


def build_dataset(out_dir: str, *, n_train: int = 96,
                  max_contexts: int = 8) -> str:
    from code2vec_tpu.data import preprocess as preprocess_mod
    os.makedirs(out_dir, exist_ok=True)
    paths = {}
    for split, n, seed in (("train", n_train, 1), ("val", 16, 2),
                           ("test", 16, 3)):
        p = os.path.join(out_dir, f"raw.{split}.txt")
        with open(p, "w", encoding="utf-8") as f:
            f.write("\n".join(_raw_lines(n, seed, max_contexts)) + "\n")
        paths[split] = p
    prefix = os.path.join(out_dir, "chaos")
    preprocess_mod.main([
        "--train_data", paths["train"], "--val_data", paths["val"],
        "--test_data", paths["test"],
        "--max_contexts", str(max_contexts),
        "--word_vocab_size", "1000", "--path_vocab_size", "1000",
        "--target_vocab_size", "1000", "--output_name", prefix])
    return prefix


def train_cmd(prefix: str, save_dir: str, *, epochs: int,
              batch: int = 32, max_contexts: int = 8) -> list:
    """Constant LR (the parity acceptance's requirement: a resumed
    cosine horizon would legitimately diverge) over the tiny corpus;
    everything else is the shipped default — async checkpointing
    included."""
    return [sys.executable, os.path.join(_REPO, "code2vec.py"),
            "--data", prefix, "--save", save_dir,
            "--epochs", str(epochs), "--batch_size", str(batch),
            "--max_contexts", str(max_contexts),
            "--lr_schedule", "constant", "--seed", "11",
            "--backend", "cpu"]


def _run_plain(cmd: list, *, cpu_devices: int, timeout_s: float) -> None:
    from code2vec_tpu.parallel.compat import cpu_worker_env
    r = subprocess.run(cmd, env=cpu_worker_env(cpu_devices),
                       stdout=subprocess.PIPE,
                       stderr=subprocess.STDOUT, text=True,
                       timeout=timeout_s)
    if r.returncode != 0:
        raise RuntimeError(f"oracle run failed (rc {r.returncode}):\n"
                           f"{r.stdout[-4000:]}")


def _latest_state(ckpt_dir: str):
    """Restore the latest committed step onto THIS process's first
    device, template built from orbax metadata: a cohort-saved
    checkpoint carries distributed device ids its saver owned, so a
    template-free restore here would refuse — explicit single-device
    shardings reshard it instead (the cross-topology restore the
    checkpoint layer already promises)."""
    import jax
    import numpy as np
    import orbax.checkpoint as ocp

    from code2vec_tpu.training import checkpoint as ckpt
    step = ckpt.latest_step(ckpt_dir)
    assert step is not None, f"no committed checkpoint under {ckpt_dir}"
    path = os.path.abspath(
        os.path.join(ckpt_dir, f"step_{step}", "state"))
    sharding = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    with ocp.StandardCheckpointer() as c:
        meta = c.metadata(path).item_metadata.tree
        def leaf_template(m):
            if m.shape:
                return jax.ShapeDtypeStruct(m.shape, m.dtype,
                                            sharding=sharding)
            # scalar leaves (step, optimizer counts) restore as plain
            # python scalars — numpy scalars are not a supported
            # template type
            return 0 if np.issubdtype(m.dtype, np.integer) else 0.0

        template = jax.tree_util.tree_map(leaf_template, meta)
        restored = c.restore(path, template)
    return step, jax.tree_util.tree_map(
        lambda x: np.asarray(x) if hasattr(x, "shape") else x,
        restored)


def trees_bit_equal(a, b) -> list:
    """Leaf paths that DIFFER between two restored pytrees (empty =
    bit-identical)."""
    import jax
    import numpy as np
    la = jax.tree_util.tree_flatten_with_path(a)[0]
    lb = jax.tree_util.tree_flatten_with_path(b)[0]
    diffs = []
    if len(la) != len(lb):
        return ["<structure mismatch>"]
    for (ka, va), (kb, vb) in zip(la, lb):
        if ka != kb:
            diffs.append(f"<key {ka} vs {kb}>")
        elif not np.array_equal(np.asarray(va), np.asarray(vb)):
            diffs.append(jax.tree_util.keystr(ka))
    return diffs


def _write_faults(path: str, sites: dict) -> str:
    with open(path, "w", encoding="utf-8") as f:
        json.dump({"seed": 0, "sites": sites}, f)
    return path


def _supervised(child_cmd: list, *, out: str, num_procs: int = 1,
                cpu_devices: int = 1, max_restarts: int = 2,
                ckpt_dir: str, telemetry_dir: str | None = None,
                attempt_timeout_s: float = 600.0, **sup_kwargs):
    from code2vec_tpu.obs import Telemetry
    from code2vec_tpu.resilience.retry import RetryPolicy
    from code2vec_tpu.training.supervisor import (Supervisor,
                                                  build_cli_spawn)

    def log(msg: str) -> None:
        print(f"[chaos] {msg}", flush=True)

    telemetry = Telemetry.create(telemetry_dir, component="supervisor",
                                 log=log) if telemetry_dir else None
    sup = Supervisor(
        build_cli_spawn(child_cmd, num_procs=num_procs,
                        out_dir=os.path.join(out, "logs"),
                        cpu_devices=cpu_devices, log=log),
        num_procs=num_procs, max_restarts=max_restarts,
        ckpt_dir=ckpt_dir, telemetry=telemetry, log=log,
        peer_grace_s=10.0, attempt_timeout_s=attempt_timeout_s,
        backoff=RetryPolicy("supervisor-restart", max_attempts=1,
                            base_delay_s=0.2, max_delay_s=1.0,
                            seed=0), **sup_kwargs)
    try:
        rc = sup.run()
    finally:
        # flush even when the budget exhausts — the supervisor JSONL
        # is the postmortem for exactly that case
        if telemetry is not None:
            telemetry.close()
    return rc, sup, telemetry.run_dir if telemetry is not None else None


def _read_events(run_dir: str) -> list:
    out = []
    with open(os.path.join(run_dir, "events.jsonl"),
              encoding="utf-8") as f:
        for ln in f:
            if ln.strip():
                out.append(json.loads(ln))
    return out


# ------------------------------------------------------------ scenarios

def scenario_kill_resume(out: str, *, epochs: int = 2,
                         kill_at_step: int = 5) -> dict:
    """SIGKILL mid-epoch (1 process) -> supervisor relaunch ->
    auto-resume -> final checkpoint bit-identical to an uninterrupted
    run's."""
    prefix = build_dataset(os.path.join(out, "data"))
    oracle_dir = os.path.join(out, "ckpt_oracle")
    chaos_dir = os.path.join(out, "ckpt_chaos")
    t0 = time.time()
    _run_plain(train_cmd(prefix, oracle_dir, epochs=epochs),
               cpu_devices=1, timeout_s=600)

    marker = os.path.join(out, "killed.once")
    faults = _write_faults(os.path.join(out, "faults.json"), {
        "train/kill": {"action": "kill", "at": kill_at_step,
                       "marker": marker}})
    cmd = train_cmd(prefix, chaos_dir, epochs=epochs) \
        + ["--auto_resume", "--faults", faults]
    rc, sup, run_dir = _supervised(
        cmd, out=out, ckpt_dir=chaos_dir,
        telemetry_dir=os.path.join(out, "tele"))

    o_step, o_state = _latest_state(oracle_dir)
    c_step, c_state = _latest_state(chaos_dir)
    diffs = trees_bit_equal(o_state, c_state)
    result = {
        "scenario": "kill_resume",
        "kill_fired": os.path.exists(marker),
        "supervisor_rc": rc,
        "restarts": sup.restarts,
        "resumed_from_step": sup.resumed_from_step,
        "oracle_step": o_step, "chaos_step": c_step,
        "param_diffs": diffs,
        "wall_s": round(time.time() - t0, 1),
        "telemetry_run_dir": run_dir,
    }
    result["ok"] = (result["kill_fired"] and rc == 0
                    and sup.restarts == 1 and o_step == c_step
                    and not diffs)
    return result


def scenario_kill_resume_2proc(out: str, *, epochs: int = 3,
                               kill_at_step: int = 4) -> dict:
    """The same parity contract through a REAL 2-process Gloo cohort:
    worker 1 is SIGKILLed mid-epoch; the supervisor reaps the
    surviving peer and relaunches the cohort coherently on a fresh
    port."""
    prefix = build_dataset(os.path.join(out, "data"))
    oracle_dir = os.path.join(out, "ckpt_oracle")
    chaos_dir = os.path.join(out, "ckpt_chaos")
    t0 = time.time()
    # the oracle is ALSO a 2-process supervised run: identical
    # topology, the only difference is the injected fault. The Gloo
    # loopback transport race can restart the ORACLE too (its child
    # has --auto_resume appended just like any supervised run) — that
    # is fine precisely BECAUSE resume is bit-exact, which is the
    # property under test; oracle restarts are recorded, not rejected.
    rc_o, sup_o, _ = _supervised(
        train_cmd(prefix, oracle_dir, epochs=epochs)
        + ["--auto_resume"],
        out=os.path.join(out, "oracle"), num_procs=2, cpu_devices=2,
        ckpt_dir=oracle_dir)
    if rc_o != 0:
        return {"scenario": "kill_resume_2proc", "ok": False,
                "error": f"oracle cohort failed (rc {rc_o}, "
                         f"restarts {sup_o.restarts})"}

    marker = os.path.join(out, "killed.once")
    faults = _write_faults(os.path.join(out, "faults.json"), {
        "train/kill": {"action": "kill", "at": kill_at_step,
                       "process": 1, "marker": marker}})
    cmd = train_cmd(prefix, chaos_dir, epochs=epochs) \
        + ["--auto_resume", "--faults", faults]
    rc, sup, run_dir = _supervised(
        cmd, out=os.path.join(out, "chaos"), num_procs=2,
        cpu_devices=2, ckpt_dir=chaos_dir,
        telemetry_dir=os.path.join(out, "tele"))

    o_step, o_state = _latest_state(oracle_dir)
    c_step, c_state = _latest_state(chaos_dir)
    diffs = trees_bit_equal(o_state, c_state)
    result = {
        "scenario": "kill_resume_2proc",
        "kill_fired": os.path.exists(marker),
        "supervisor_rc": rc,
        "oracle_restarts": sup_o.restarts,
        "restarts": sup.restarts,
        "resumed_from_step": sup.resumed_from_step,
        "oracle_step": o_step, "chaos_step": c_step,
        "param_diffs": diffs,
        "wall_s": round(time.time() - t0, 1),
        "telemetry_run_dir": run_dir,
    }
    result["ok"] = (result["kill_fired"] and rc == 0
                    and sup.restarts >= 1 and o_step == c_step
                    and not diffs)
    return result


def _step_event_times(tele_root: str) -> list:
    """(ts, step) for every per-step telemetry event under any run dir
    of `tele_root`. JSONL is flushed per event, so even a SIGKILLed
    attempt's steps are on disk up to the kill."""
    import glob as glob_mod
    out = []
    for path in glob_mod.glob(os.path.join(tele_root, "*",
                                           "events.jsonl")):
        with open(path, encoding="utf-8") as f:
            for ln in f:
                if not ln.strip():
                    continue
                ev = json.loads(ln)
                if ev.get("kind") == "step":
                    out.append((float(ev["ts"]), int(ev["step"])))
    return sorted(out)


def _marker_ts(marker: str) -> float | None:
    """The firing wall-clock the fault site wrote into its once-latch
    marker (`... ts=<float>`)."""
    import re as re_mod
    try:
        with open(marker, encoding="utf-8") as f:
            m = re_mod.search(r"ts=([0-9.]+)", f.read())
        return float(m.group(1)) if m else None
    except OSError:
        return None


def run_kill_resize(out: str, *, epochs: int = 3, kill_at_step: int = 4,
                    procs: int = 2, cpu_devices: int = 2,
                    timeout_s: float = 600.0, tries: int = 3) -> dict:
    """The run half of the kill_resize scenario, reused by
    tools/multichip_bench.py's kill-mid-run leg: train a `procs`-process
    cohort under the shrink-policy supervisor, SIGKILL worker 1 at
    `kill_at_step`, let the cohort RE-FORM at procs−1, and measure the
    recovery cost — steps lost (kill step minus the committed step the
    re-formed cohort resumed from) and seconds from the kill to the
    first post-resize training step (per-step telemetry events from the
    relaunched children, against the kill timestamp the fault marker
    recorded).

    The CPU harness's loopback-Gloo transport race (the compat
    docstring's `op.preamble.length <= op.nbytes` crash) can abort a
    cohort at startup BEFORE the injected kill arms — the supervisor
    handles it per its policy (a lone early death resizes, a
    simultaneous whole-cohort crash relaunches full size as
    `cohort_failure`), but as a measurement such a try is transient
    infra, not the contract: it is retried in a fresh subdir (the
    multichip_bench pair-retry discipline) until the kill actually
    fired after a committed checkpoint existed."""
    last = None
    for i in range(max(1, tries)):
        sub = os.path.join(out, f"try{i}")
        os.makedirs(sub, exist_ok=True)
        last = _run_kill_resize_once(
            sub, epochs=epochs, kill_at_step=kill_at_step,
            procs=procs, cpu_devices=cpu_devices, timeout_s=timeout_s)
        if (last["kill_fired"] and last["supervisor_rc"] == 0
                and last["resumed_from_step"] is not None):
            return last
        print(f"[chaos] kill_resize try {i} hit transient infra "
              f"(kill_fired={last['kill_fired']}, resumed="
              f"{last['resumed_from_step']}); retrying in a fresh dir",
              flush=True)
    return last


def _run_kill_resize_once(out: str, *, epochs: int, kill_at_step: int,
                          procs: int, cpu_devices: int,
                          timeout_s: float) -> dict:
    prefix = build_dataset(os.path.join(out, "data"))
    chaos_dir = os.path.join(out, "ckpt_chaos")
    child_tele = os.path.join(out, "child_tele")
    marker = os.path.join(out, "killed.once")
    faults = _write_faults(os.path.join(out, "faults.json"), {
        "train/kill": {"action": "kill", "at": kill_at_step,
                       "process": 1, "marker": marker}})
    # sync checkpointing: the contract under test is TOPOLOGY recovery
    # from a committed step, so the committed step must be
    # deterministic — on this harness post-compile steps run ~20 ms
    # while the 2-process collective async commit takes hundreds, so a
    # mid-epoch kill would race (and essentially always beat) the
    # boundary save. The mid-ASYNC-save kill discipline for fixed
    # cohorts is kill_resume's job (shipped defaults there).
    cmd = train_cmd(prefix, chaos_dir, epochs=epochs) \
        + ["--async_checkpoint", "off",
           "--auto_resume", "--faults", faults,
           "--telemetry_dir", child_tele]
    rc, sup, run_dir = _supervised(
        cmd, out=out, num_procs=procs, cpu_devices=cpu_devices,
        ckpt_dir=chaos_dir, telemetry_dir=os.path.join(out, "tele"),
        attempt_timeout_s=timeout_s,
        resize_policy="shrink", min_procs=1)

    kill_ts = _marker_ts(marker)
    resumed = sup.resumed_from_step
    steps = _step_event_times(child_tele)
    first_post = next((ts for ts, _s in steps
                       if sup.last_launch_ts is not None
                       and ts >= sup.last_launch_ts), None)
    recovery_seconds = (round(first_post - kill_ts, 3)
                        if first_post is not None
                        and kill_ts is not None else None)
    recovery_steps_lost = (kill_at_step - resumed
                           if resumed is not None else kill_at_step)
    return {
        "kill_fired": os.path.exists(marker),
        "supervisor_rc": rc,
        "restarts": sup.restarts,
        "resizes": [list(r) for r in sup.resizes],
        "full_relaunches": sup.full_relaunches,
        "cohort_size_final": sup.cur_procs,
        "resumed_from_step": resumed,
        "kill_at_step": kill_at_step,
        "recovery_steps_lost": recovery_steps_lost,
        "recovery_seconds": recovery_seconds,
        "data_prefix": prefix,
        "ckpt_dir": chaos_dir,
        "telemetry_run_dir": run_dir,
    }


def scenario_kill_resize(out: str, *, epochs: int = 3,
                         kill_at_step: int = 4) -> dict:
    """SIGKILL one peer of a 2-process cohort mid-epoch; the supervisor
    re-forms the mesh at 1 process (a resize, ZERO full-cohort
    relaunches), the checkpoint reshards onto the survivor, and the
    final params are bit-identical to an uninterrupted 1-process run
    resumed from the same committed step (constant LR) — the elastic
    resume parity bar (ISSUE 13)."""
    import shutil
    t0 = time.time()
    run = run_kill_resize(out, epochs=epochs,
                          kill_at_step=kill_at_step)
    result = dict(run, scenario="kill_resize",
                  wall_s=None, param_diffs=["<not compared>"])
    chaos_dir = run["ckpt_dir"]
    S = run["resumed_from_step"]
    if run["supervisor_rc"] != 0 or S is None:
        result["ok"] = False
        result["wall_s"] = round(time.time() - t0, 1)
        return result

    # the oracle: an UNINTERRUPTED 1-process run resumed from the SAME
    # committed step the re-formed cohort restored — committed step
    # dirs are immutable, so the chaos dir still holds the exact bytes
    oracle_dir = os.path.join(out, "ckpt_oracle")
    os.makedirs(oracle_dir)
    shutil.copytree(os.path.join(chaos_dir, f"step_{S}"),
                    os.path.join(oracle_dir, f"step_{S}"))
    for sidecar in ("manifest.json", "vocab.pkl"):
        shutil.copy(os.path.join(chaos_dir, sidecar),
                    os.path.join(oracle_dir, sidecar))
    # cpu_devices + checkpoint mode match the re-formed chaos child
    # (1 process x 2 virtual devices, sync saves) so the two runs
    # differ in NOTHING but history
    _run_plain(train_cmd(run["data_prefix"], oracle_dir, epochs=epochs)
               + ["--async_checkpoint", "off", "--auto_resume"],
               cpu_devices=2, timeout_s=600)

    o_step, o_state = _latest_state(oracle_dir)
    c_step, c_state = _latest_state(chaos_dir)
    diffs = trees_bit_equal(o_state, c_state)
    result.update(
        oracle_step=o_step, chaos_step=c_step, param_diffs=diffs,
        wall_s=round(time.time() - t0, 1))
    result["ok"] = (run["kill_fired"] and run["supervisor_rc"] == 0
                    and run["restarts"] == 1
                    and run["resizes"] == [[2, 1]]
                    and run["full_relaunches"] == 0
                    and o_step == c_step and not diffs)
    return result


def _flip_byte_in_largest_blob(step_dir: str) -> str:
    """Flip one byte mid-file in the largest file of the committed
    state tree — the bit-rot the checksums exist to catch."""
    state = os.path.join(step_dir, "state")
    largest, size = None, -1
    for base, _dirs, files in os.walk(state):
        for name in files:
            p = os.path.join(base, name)
            s = os.path.getsize(p)
            if s > size:
                largest, size = p, s
    assert largest is not None and size > 0
    with open(largest, "r+b") as f:
        f.seek(size // 2)
        b = f.read(1)
        f.seek(size // 2)
        f.write(bytes([b[0] ^ 0xFF]))
    return largest


def scenario_corrupt_checkpoint(out: str) -> dict:
    """Bit-flip a leaf blob in the latest committed step: verified
    restore detects it, the supervisor quarantines the step dir, emits
    an `alert` event, and training resumes from the prior committed
    step."""
    from code2vec_tpu.training import checkpoint as ckpt
    prefix = build_dataset(os.path.join(out, "data"))
    ckpt_dir = os.path.join(out, "ckpt")
    t0 = time.time()
    # 2 epochs -> two committed, checksummed steps (3 and 6)
    _run_plain(train_cmd(prefix, ckpt_dir, epochs=2),
               cpu_devices=1, timeout_s=600)
    steps = sorted(s for s, _ in ckpt._step_dirs(ckpt_dir))
    assert len(steps) == 2, steps
    flipped = _flip_byte_in_largest_blob(
        os.path.join(ckpt_dir, f"step_{steps[-1]}"))

    # resume for a 3rd epoch: the supervisor must fall back to steps[0]
    cmd = train_cmd(prefix, ckpt_dir, epochs=3) + ["--auto_resume"]
    rc, sup, run_dir = _supervised(
        cmd, out=out, ckpt_dir=ckpt_dir,
        telemetry_dir=os.path.join(out, "tele"))

    quarantined = os.path.join(ckpt_dir, ckpt.QUARANTINE_DIRNAME,
                               f"step_{steps[-1]}")
    alerts = [e for e in _read_events(run_dir)
              if e.get("kind") == "alert"
              and e.get("rule") == "checkpoint_quarantined"
              and e.get("transition") == "firing"] if run_dir else []
    final = ckpt.latest_step(ckpt_dir)
    result = {
        "scenario": "corrupt_checkpoint",
        "flipped_file": os.path.relpath(flipped, out),
        "supervisor_rc": rc,
        "restarts": sup.restarts,
        "resumed_from_step": sup.resumed_from_step,
        "quarantined": sup.quarantined,
        "quarantine_dir_exists": os.path.isdir(quarantined),
        "alert_events": len(alerts),
        "final_step": final,
        "wall_s": round(time.time() - t0, 1),
        "telemetry_run_dir": run_dir,
    }
    result["ok"] = (rc == 0 and result["quarantine_dir_exists"]
                    and sup.resumed_from_step == steps[0]
                    and len(alerts) == 1
                    and final is not None and final > steps[-1])
    return result


def scenario_serve_swap_kill(out: str, *, replicas: int = 2,
                             requests: int = 768, qps: float = 120.0,
                             kill_at: int = 40) -> dict:
    """The serving-plane acceptance (ISSUE 18): a replica pool under
    open-loop Poisson load with hot-key skew takes a mid-request
    replica death (`serve/kill`), a rolling hot swap of a VERIFIED
    committed checkpoint, and a REFUSED bit-flipped step — and the
    external contract holds: p99 under the SLO, zero requests lost
    (sheds are explicit), zero new jit compilations under load, pool
    back to full strength."""
    import threading

    from code2vec_tpu.config import Config
    from code2vec_tpu.data import preprocess as preprocess_mod
    from code2vec_tpu.models.jax_model import Code2VecModel
    from code2vec_tpu.obs import Telemetry
    from code2vec_tpu.obs.alerts import AlertEngine, serving_slo_rules
    from code2vec_tpu.resilience import faults
    from code2vec_tpu.serving import ReloadManager, ReplicaPool
    from code2vec_tpu.training import checkpoint as ckpt
    from tools import loadgen

    t0 = time.time()
    # the loadgen tiny-model recipe: latency is shape-dependent, not
    # value-dependent, so random weights over tiny vocabs serve fine
    data_dir = os.path.join(out, "data")
    os.makedirs(data_dir, exist_ok=True)
    raw = os.path.join(data_dir, "raw.txt")
    with open(raw, "w", encoding="utf-8") as f:
        f.write("\n".join(ln for req in loadgen.gen_corpus(64, 2, seed=7)
                          for ln in req) + "\n")
    prefix = os.path.join(data_dir, "tiny")
    preprocess_mod.main([
        "--train_data", raw, "--val_data", raw, "--test_data", raw,
        "--max_contexts", "16", "--word_vocab_size", "1000",
        "--path_vocab_size", "1000", "--target_vocab_size", "1000",
        "--output_name", prefix])
    cfg = Config(MAX_CONTEXTS=16, MAX_TOKEN_VOCAB_SIZE=1000,
                 MAX_PATH_VOCAB_SIZE=1000, MAX_TARGET_VOCAB_SIZE=1000,
                 DEFAULT_EMBEDDINGS_SIZE=16, USE_BF16=False)
    cfg.train_data_path = prefix
    cfg.SERVE_REPLICAS = replicas
    cfg.SERVE_MAX_REPLICAS = max(replicas, cfg.SERVE_MAX_REPLICAS)

    # one in-band kill: the kill_at-th predict_lines call raises
    # FaultInjected inside whichever replica serves it (action "kill"
    # would SIGKILL this whole process) — the pool must retry the
    # request on a survivor and refill in the background
    faults.install({"seed": 0, "sites": {
        "serve/kill": {"action": "raise", "at": kill_at}}},
        log=lambda m: print(f"[chaos] {m}", flush=True))

    tele = Telemetry.memory("chaos-serving").make_threadsafe()
    pool = ReplicaPool(cfg, lambda: Code2VecModel(cfg),
                       replicas=replicas, telemetry=tele).start()
    alerts = AlertEngine.create(
        tele, mode="warn", rules=serving_slo_rules(cfg.SERVE_SLO_MS))
    reload_dir = os.path.join(out, "serve_ckpt")
    rm = ReloadManager(reload_dir, pool, telemetry=tele, alerts=alerts,
                       poll_s=0.1).start()

    progress = {}

    def _chaos_actions() -> None:
        import jax
        # vocabs/dims for the sidecars come from a live replica; the
        # swapped-in params are a real value change (same shapes, so
        # the swap must not recompile anything)
        model = pool._replicas[0].server.model
        new_params = jax.tree_util.tree_map(
            lambda x: (x * 1.001).astype(x.dtype),
            pool.params_template())
        time.sleep(0.5)  # let the load establish itself first
        ckpt.save_checkpoint(reload_dir, {"params": new_params}, 1,
                             model.vocabs, model.dims)
        deadline = time.time() + 60
        while rm.last_step < 1 and time.time() < deadline:
            time.sleep(0.05)
        if rm.last_step >= 1:
            progress["swap_ts"] = time.time()
        ckpt.save_checkpoint(reload_dir, {"params": new_params}, 2,
                             model.vocabs, model.dims)
        _flip_byte_in_largest_blob(os.path.join(reload_dir, "step_2"))
        deadline = time.time() + 60
        while 2 not in rm.refused and time.time() < deadline:
            time.sleep(0.05)
        if 2 in rm.refused:
            progress["refused_ts"] = time.time()

    actions = threading.Thread(target=_chaos_actions,
                               name="chaos-actions", daemon=True)
    corpus = loadgen.gen_corpus(requests, 1,
                                max_ctx=min(cfg.MAX_CONTEXTS, 12))
    try:
        actions.start()
        report = loadgen.run_load(
            pool, corpus, mode="open", concurrency=16, qps=qps,
            arrivals="poisson", hot_key_frac=0.25, hot_keys=8, seed=0)
        t_load_end = time.time()
        actions.join(timeout=120)
        # the refill may still be warming when the load drains; it
        # must land (back to full strength) before the verdict
        pool.wait_ready(replicas, timeout_s=120)
        compile_delta = pool.compile_delta()
        table = pool.pool_table()
        counters = dict(tele.counters)
        fired = faults.stats().get("serve/kill", {}).get("fired", 0)
        refused_state = next(
            (r["state"] for r in alerts.status_table()
             if r["rule"] == "reload_refused"), None)
    finally:
        rm.stop()
        pool.close()
        faults.clear()

    result = {
        "scenario": "serve_swap_kill",
        "requests": report["requests"],
        "ok_requests": report["ok"],
        "shed": report["shed"],
        "errors": report["errors"],
        "p50_ms": report["latency"]["p50_ms"],
        "p99_ms": report["latency"]["p99_ms"],
        "slo_ms": cfg.SERVE_SLO_MS,
        "throughput_rps": report["throughput_rps"],
        "kill_fired": fired == 1,
        "replica_dead": counters.get("serve/replica_dead", 0),
        "replica_refill": counters.get("serve/replica_refill", 0),
        "reloads": counters.get("serve/reloads", 0),
        "reload_refused": counters.get("serve/reload_refused", 0),
        "swapped_step": rm.last_step,
        "refused_steps": sorted(rm.refused),
        "swap_under_load": ("swap_ts" in progress
                            and progress["swap_ts"] <= t_load_end),
        "refused_alert_state": refused_state,
        "pool_generation": table["generation"],
        "pool_ready": table["ready"],
        "new_compilations_under_load": compile_delta,
        "cache_hits": counters.get("serve/cache_hit", 0),
        "wall_s": round(time.time() - t0, 1),
    }
    result["ok"] = (
        report["errors"] == 0
        and report["requests"] == report["ok"] + report["shed"]
        and report["latency"]["p99_ms"] <= cfg.SERVE_SLO_MS
        and result["kill_fired"]
        and result["replica_dead"] == 1
        and result["replica_refill"] == 1
        and result["swapped_step"] == 1
        and table["generation"] == 1
        and result["refused_steps"] == [2]
        and result["swap_under_load"]
        and refused_state == "firing"
        and compile_delta == 0
        and table["ready"] >= replicas)
    return result


SCENARIOS = {
    "kill_resume": scenario_kill_resume,
    "kill_resume_2proc": scenario_kill_resume_2proc,
    "kill_resize": scenario_kill_resize,
    "corrupt_checkpoint": scenario_corrupt_checkpoint,
    "serve_swap_kill": scenario_serve_swap_kill,
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="deterministic chaos scenarios over the real "
                    "supervisor + failpoint registry")
    ap.add_argument("scenario", nargs="?", choices=sorted(SCENARIOS),
                    help="which contract to exercise")
    ap.add_argument("--list", action="store_true",
                    help="list scenarios and exit")
    ap.add_argument("--out", default=None,
                    help="work dir (default: a fresh temp dir)")
    args = ap.parse_args(argv)

    if args.list or not args.scenario:
        for name, fn in sorted(SCENARIOS.items()):
            print(f"{name}: {' '.join((fn.__doc__ or '').split())}")
        return 0

    out = args.out or tempfile.mkdtemp(prefix=f"chaos_{args.scenario}_")
    os.makedirs(out, exist_ok=True)
    result = SCENARIOS[args.scenario](out)
    print(json.dumps(result, indent=1, default=str))
    return 0 if result.get("ok") else 1


if __name__ == "__main__":
    sys.exit(main())
