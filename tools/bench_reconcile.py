#!/usr/bin/env python3
"""Reconcile bench.py's full-step time against the phase floors
(VERDICT r3 weak #1/#2: bench measured 30.8 ms/step while the round-3
phase study reported 26.0 ms for "the same" config — a 4.8 ms gap,
larger than the whole adafactor optimizer phase, blamed on hand-wavy
"variance + batch rotation").

This tool slope-times ONE factor at a time, all with the shipped
java-large adafactor config (bf16 tables, sampled S=4096, Pallas pool
on TPU), so the residual decomposes into named, measured pieces:

  A  full step, 1 device-resident batch, keys pre-split   (phase-study
     conditions, but on bench's exact dims/optimizer build)
  B  full step, 4-batch rotation, keys pre-split          (bench.py
     conditions)
  C  full step, 1 batch, jax.random.split INSIDE the loop (the round-3
     profile_step.py loop shape — dispatch-cost probe)
  D  fwd+bwd only, 1 batch vs 4-batch rotation            (is the
     rotation effect in the backward scatter or the optimizer?)

Also prints the round-3 discrepancy suspects it can falsify:
  - profile_step.py's ModelDims defaulted tables_dtype to float32
    while BASELINE.md labeled the phase floors "bf16 tables" — A is
    measured at BOTH dtypes so the 26.0 ms row can be attributed.

Usage: python tools/bench_reconcile.py [--steps 40]
One JSON line per measurement + a summary table.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from _bench_common import (BATCH as B, CTX, NUM_SAMPLED, PATH_VOCAB,  # noqa: E402
                           TARGET_VOCAB, TOKEN_VOCAB, slope_time)


def _dims(tables_dtype: str):
    from code2vec_tpu.models.encoder import ModelDims
    return ModelDims(token_vocab_size=TOKEN_VOCAB,
                     path_vocab_size=PATH_VOCAB,
                     target_vocab_size=TARGET_VOCAB,
                     embeddings_size=128, max_contexts=CTX,
                     tables_dtype=tables_dtype)


def _batches(n: int):
    import jax.numpy as jnp
    r = np.random.default_rng(0)
    out = []
    for _ in range(n):
        out.append(tuple(jnp.asarray(a) for a in (
            r.integers(0, TARGET_VOCAB, (B,), dtype=np.int32),
            r.integers(0, TOKEN_VOCAB, (B, CTX), dtype=np.int32),
            r.integers(0, PATH_VOCAB, (B, CTX), dtype=np.int32),
            r.integers(0, TOKEN_VOCAB, (B, CTX), dtype=np.int32),
            np.ones((B, CTX), np.float32),
            np.ones((B,), np.float32))))
    return out


def time_full_step(dims, n_batches: int, split_in_loop: bool,
                   steps: int) -> float:
    import jax
    import jax.numpy as jnp

    from code2vec_tpu.models.encoder import init_params
    from code2vec_tpu.training.optimizers import make_optimizer
    from code2vec_tpu.training.steps import make_train_step

    params = init_params(jax.random.PRNGKey(0), dims)
    opt = make_optimizer(1e-3)  # shipped default: adafactor tables
    step = make_train_step(dims, opt, use_sampled_softmax=True,
                           num_sampled=NUM_SAMPLED,
                           compute_dtype=jnp.bfloat16,
                           use_pallas=jax.default_backend() == "tpu")
    batches = _batches(n_batches)

    def chain(n, state):
        params, opt_state, rng = state
        if not split_in_loop:
            rng, sub = jax.random.split(rng)
            keys = list(jax.random.split(sub, max(n, 1)))
        t0 = time.perf_counter()
        for i in range(n):
            if split_in_loop:
                rng, k = jax.random.split(rng)
            else:
                k = keys[i]
            params, opt_state, loss = step(
                params, opt_state, batches[i % n_batches], k)
        float(loss)
        return time.perf_counter() - t0, (params, opt_state, rng)

    state = (params, opt.init(params), jax.random.PRNGKey(1))
    return slope_time(chain, state, steps)


def time_fwd_bwd(dims, n_batches: int, steps: int) -> float:
    import jax
    import jax.numpy as jnp

    from code2vec_tpu.models.encoder import init_params
    from code2vec_tpu.training.steps import make_train_loss_fn

    params = init_params(jax.random.PRNGKey(0), dims)
    loss_fn = make_train_loss_fn(
        dims, use_sampled_softmax=True, num_sampled=NUM_SAMPLED,
        compute_dtype=jnp.bfloat16,
        use_pallas=jax.default_backend() == "tpu")
    grad_fn = jax.jit(jax.value_and_grad(loss_fn))
    batches = _batches(n_batches)

    def chain(n, rng):
        rng, sub = jax.random.split(rng)
        keys = list(jax.random.split(sub, max(n, 1)))
        t0 = time.perf_counter()
        for i in range(n):
            loss, _g = grad_fn(params, batches[i % n_batches], keys[i])
        float(loss)
        return time.perf_counter() - t0, rng

    return slope_time(chain, jax.random.PRNGKey(3), steps)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=40)
    args = ap.parse_args()
    from code2vec_tpu.device import enable_compile_cache
    enable_compile_cache()

    rows = []

    def rec(name, dt):
        row = {"case": name, "ms_per_step": round(dt * 1e3, 2),
               "pc_per_sec": round(B * CTX / dt, 1)}
        rows.append(row)
        print(json.dumps(row), flush=True)

    bf16 = _dims("bfloat16")
    f32 = _dims("float32")

    rec("A_full_1batch_presplit_bf16",
        time_full_step(bf16, 1, False, args.steps))
    rec("A32_full_1batch_presplit_f32",
        time_full_step(f32, 1, False, args.steps))
    rec("B_full_4batch_presplit_bf16  [bench.py conditions]",
        time_full_step(bf16, 4, False, args.steps))
    rec("C_full_1batch_splitinloop_bf16  [profile_step.py loop shape]",
        time_full_step(bf16, 1, True, args.steps))
    rec("D1_fwdbwd_1batch_bf16", time_fwd_bwd(bf16, 1, args.steps))
    rec("D4_fwdbwd_4batch_bf16", time_fwd_bwd(bf16, 4, args.steps))

    a = rows[0]["ms_per_step"]
    b = rows[2]["ms_per_step"]
    c = rows[3]["ms_per_step"]
    d1, d4 = rows[4]["ms_per_step"], rows[5]["ms_per_step"]
    print(f"\nrotation cost (B-A):          {b - a:+.2f} ms/step")
    print(f"split-in-loop cost (C-A):     {c - a:+.2f} ms/step")
    print(f"rotation cost in fwd+bwd:     {d4 - d1:+.2f} ms/step")
    print(f"optimizer phase (A-D1):       {a - d1:+.2f} ms/step")


if __name__ == "__main__":
    main()
