#!/usr/bin/env python3
"""Phase-level profile of the java-large training step on the local chip.

Times, SLOPE-TIMED (two chained-run lengths, differenced — which
separates the per-step time from the fixed dispatch and sync cost a
single chain folds in), each of:

  - HBM streaming bandwidth (fold-resistant in-jit copy loop) — ceiling
  - forward only (encode + sampled softmax loss)
  - forward + backward (grads materialized)
  - full step (fwd + bwd + optimizer), adam and adafactor

Usage: python tools/profile_step.py [--batch 1024] [--steps 20]
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TOKEN_VOCAB = 1_301_136
PATH_VOCAB = 911_417
TARGET_VOCAB = 261_245
CTX = 200
NUM_SAMPLED = 4096


def timeit(fn, sync, steps, warmup=3):
    """Slope timing: run chains of `steps` and `3*steps` calls and
    difference them, cancelling both the fixed sync overhead and (to
    first order) nothing else — per-call dispatch cost is part of the
    steady-state step cost and is retained deliberately (a real train
    loop pays it too)."""
    def chain(n):
        out = None
        t0 = time.perf_counter()
        for _ in range(n):
            out = fn()
        sync(out)
        return time.perf_counter() - t0

    chain(warmup)
    t1 = chain(steps)
    t2 = chain(3 * steps)
    return (t2 - t1) / (2 * steps)


def main(argv=None) -> None:
    # argv=None (programmatic callers) means "no flags", NOT sys.argv —
    # the CLI entry below passes sys.argv[1:] explicitly (same contract
    # as bench.main, so a test calling main() never eats pytest's argv)
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=1024)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--telemetry_dir", default=None,
                    help="also emit each phase measurement as telemetry "
                         "events (code2vec_tpu/obs) so ad-hoc profiling "
                         "and BENCH rounds share one JSONL format")
    args = ap.parse_args(argv if argv is not None else [])
    from code2vec_tpu.device import enable_compile_cache
    enable_compile_cache()
    B = args.batch

    from code2vec_tpu.obs import Telemetry
    tele = Telemetry.create(args.telemetry_dir, component="profile")

    def emit(phase: str, ms: float, **extra) -> None:
        tele.record_ms(f"profile/{phase}_ms", ms)
        tele.event("profile", phase=phase, ms=round(ms, 3),
                   batch=B, **extra)

    import jax
    import jax.numpy as jnp

    from code2vec_tpu.models.encoder import ModelDims, encode, init_params
    from code2vec_tpu.ops.sampled_softmax import sampled_softmax_loss
    from code2vec_tpu.training.steps import make_train_step

    # bf16 tables — the SHIPPED config (round-4 reconcile fix: this
    # tool previously defaulted to f32 tables while BASELINE.md labeled
    # its floors "bf16 tables"; f32 measures ~5 ms/step slower)
    dims = ModelDims(token_vocab_size=TOKEN_VOCAB,
                     path_vocab_size=PATH_VOCAB,
                     target_vocab_size=TARGET_VOCAB,
                     embeddings_size=128, max_contexts=CTX,
                     tables_dtype="bfloat16")
    params = init_params(jax.random.PRNGKey(0), dims)

    r = np.random.default_rng(0)
    labels = jnp.asarray(r.integers(0, TARGET_VOCAB, (B,), dtype=np.int32))
    src = jnp.asarray(r.integers(0, TOKEN_VOCAB, (B, CTX), dtype=np.int32))
    pth = jnp.asarray(r.integers(0, PATH_VOCAB, (B, CTX), dtype=np.int32))
    dst = jnp.asarray(r.integers(0, TOKEN_VOCAB, (B, CTX), dtype=np.int32))
    mask = jnp.ones((B, CTX), jnp.float32)
    weights = jnp.ones((B,), jnp.float32)
    batch = (labels, src, pth, dst, mask, weights)
    rng = jax.random.PRNGKey(1)

    # ---- HBM streaming ceiling (shared helper, ops/membench.py) ----
    from code2vec_tpu.ops.membench import measure_hbm_ceiling

    bw = measure_hbm_ceiling()
    print(f"HBM streaming (1 GiB copy): {bw/1e9:.0f} GB/s effective")
    tele.gauge("profile/hbm_ceiling_gbps", round(bw / 1e9, 1),
               emit=False)
    tele.event("profile", phase="hbm_ceiling", gbps=round(bw / 1e9, 1))

    # ---- forward only ----
    def loss_fn(params, rng):
        code, _, _ = encode(params, src, pth, dst, mask,
                            compute_dtype=jnp.bfloat16)
        loss, _ = sampled_softmax_loss(
            params["target_emb"], code, labels, rng, NUM_SAMPLED,
            example_weights=weights, vocab_size=TARGET_VOCAB)
        return loss

    fwd = jax.jit(loss_fn)
    dt = timeit(lambda: fwd(params, rng), lambda o: float(o), args.steps)
    print(f"forward only:        {dt*1e3:6.2f} ms")
    emit("forward", dt * 1e3)

    # ---- forward + backward ----
    grad_fn = jax.jit(jax.value_and_grad(loss_fn))
    dt = timeit(lambda: grad_fn(params, rng), lambda o: float(o[0]),
                args.steps)
    print(f"forward + backward:  {dt*1e3:6.2f} ms")
    emit("forward_backward", dt * 1e3)

    # ---- full step, dense Adam ----
    def run_full(label, step, opt_state0):
        p = jax.tree_util.tree_map(jnp.copy, params)
        s = opt_state0
        k = jax.random.PRNGKey(2)
        nonlocal_state = {"p": p, "s": s, "k": k}

        def one():
            st = nonlocal_state
            st["k"], sub = jax.random.split(st["k"])
            st["p"], st["s"], loss = step(st["p"], st["s"], batch, sub)
            return loss

        dt = timeit(one, lambda o: float(o), args.steps)
        pc = B * CTX / dt
        print(f"{label}: {dt*1e3:6.2f} ms -> {pc/1e6:.2f}M pc/s")
        emit(label.replace(" ", "_").replace("(", "").replace(")", ""),
             dt * 1e3, pc_per_sec=round(pc, 1))
        return dt

    from code2vec_tpu.training.optimizers import make_optimizer

    for oname in ("adam", "adafactor"):
        opt = make_optimizer(1e-3, oname)
        step = make_train_step(dims, opt, use_sampled_softmax=True,
                               num_sampled=NUM_SAMPLED,
                               compute_dtype=jnp.bfloat16,
                               use_pallas=jax.default_backend() == "tpu")
        run_full(f"full step ({oname})", step, opt.init(params))

    tele.close()


if __name__ == "__main__":
    main(sys.argv[1:])
