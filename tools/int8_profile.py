#!/usr/bin/env python3
"""Phase attribution for the int8-tables step (BASELINE.md round 5).

The full int8 step measured slower than bf16 (43.3 ms with threefry
dither, 38.5 ms with the fused hash dither, vs 30.7 bf16) — this tool
splits the regression by phase so the doc can say WHERE the bytes
saving loses to added work. Slope-timed exactly like bench.py, at
java-large capacities, for each tables_dtype:

  - fwd+bwd only (value_and_grad of the shared train loss): isolates
    the gather/dequant + scatter side;
  - optimizer.update + apply only (precomputed grads): isolates the
    adafactor chain + (for int8) the requantize pass;
  - full step (reference point = bench.py's number).

Usage: python tools/int8_profile.py [--out f]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _build_grad_fn(loss_fn, params0, quantized):
    """Jitted fwd+bwd for one tables_dtype — a factory so the jit is
    evaluated ONCE per dtype, outside the measurement loops (the
    graftlint retrace-hazard fix: the old inline construction rebuilt a
    fresh callable with an empty compile cache inside `main`'s dtype
    loop)."""
    import jax
    import jax.numpy as jnp

    from code2vec_tpu.ops.quant import is_quantized

    if not quantized:
        return jax.jit(jax.value_and_grad(loss_fn))
    qkeys = sorted(k for k in params0 if is_quantized(params0[k]))

    @jax.jit
    def grad_fn(params, batch, rng):
        def lf(carriers, params):
            virt = dict(params)
            for k, c in carriers.items():
                virt[k] = dict(params[k], g=c)
            return loss_fn(virt, batch, rng)
        carriers = {k: jnp.zeros(params[k]["q"].shape,
                                 jnp.bfloat16) for k in qkeys}
        return jax.value_and_grad(
            lf, argnums=(0, 1), allow_int=True)(carriers, params)

    return grad_fn


def _build_apply_step(optimizer, flat_grads):
    """Jitted optimizer.update + apply on precomputed grads (same
    factory-per-dtype reasoning as `_build_grad_fn`)."""
    import functools

    import jax
    import jax.numpy as jnp
    import optax

    from code2vec_tpu.ops.quant import is_quantized, requantize

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def apply_step(params, opt_state, rng):
        qkeys_l = sorted(k for k in params if is_quantized(params[k]))
        rng, *qrngs = jax.random.split(rng, 1 + len(qkeys_l))
        flat_params = {k: (jnp.zeros(params[k]["q"].shape,
                                     jnp.bfloat16)
                           if is_quantized(params[k]) else params[k])
                       for k in params}
        updates, opt_state = optimizer.update(flat_grads, opt_state,
                                              flat_params)
        new_params = {}
        for k, qrng in zip(qkeys_l, qrngs):
            new_params[k] = requantize(params[k], updates[k], qrng)
        for k in params:
            if k not in new_params:
                new_params[k] = optax.apply_updates(params[k],
                                                    updates[k])
        return new_params, opt_state, rng

    return apply_step


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    ap.add_argument("--dtypes", default="bfloat16,int8")
    args = ap.parse_args()
    from code2vec_tpu.device import enable_compile_cache
    enable_compile_cache()
    from tools._bench_common import load_bench_module
    bench = load_bench_module()

    import jax
    import jax.numpy as jnp

    from code2vec_tpu.models.encoder import init_params
    from code2vec_tpu.ops.quant import is_quantized, opt_param_view
    from code2vec_tpu.training.optimizers import make_optimizer
    from code2vec_tpu.training.steps import make_train_loss_fn

    rows = []
    for tdtype in args.dtypes.split(","):
        dims = bench._java_large_dims("bag", tables_dtype=tdtype)
        params0 = init_params(jax.random.PRNGKey(0), dims)
        optimizer = make_optimizer(1e-3)
        batches = bench._device_batches()
        loss_fn = make_train_loss_fn(
            dims, use_sampled_softmax=True, num_sampled=bench.NUM_SAMPLED,
            compute_dtype=jnp.bfloat16,
            use_pallas=jax.default_backend() == "tpu")
        quantized = tdtype == "int8"

        # ---- fwd+bwd ----
        grad_fn = _build_grad_fn(loss_fn, params0, quantized)

        def chain_fb(n, rng, _params=params0, _grad_fn=grad_fn):
            rng, sub = jax.random.split(rng)
            keys = list(jax.random.split(sub, max(n, 1)))
            t0 = time.perf_counter()
            for i in range(n):
                out = _grad_fn(_params, batches[i % len(batches)],
                               keys[i])
            # hard sync via host transfer of the scalar loss
            # (block_until_ready can return early on this platform)
            float(out[0])
            return time.perf_counter() - t0, rng

        fb_ms = bench._slope_time(chain_fb, jax.random.PRNGKey(3)) * 1e3

        # ---- optimizer.update + apply on precomputed grads ----
        view = opt_param_view(params0)
        opt_state0 = optimizer.init(view)
        flat_grads = {k: (jnp.full(view[k].shape, 1e-3, jnp.bfloat16)
                          if is_quantized(params0[k])
                          else jnp.full(params0[k].shape, 1e-3,
                                        params0[k].dtype))
                      for k in params0}
        apply_step = _build_apply_step(optimizer, flat_grads)

        def chain_opt(n, state, apply_step=apply_step):
            params, opt_state, rng = state
            t0 = time.perf_counter()
            for _ in range(n):
                params, opt_state, rng = apply_step(params, opt_state,
                                                    rng)
            float(jax.tree_util.tree_leaves(params)[0].ravel()[0])
            return time.perf_counter() - t0, (params, opt_state, rng)

        # apply_step donates its params/opt_state, so hand it real
        # copies: params0 is reused by the full-step measurement below
        params_copy = jax.tree_util.tree_map(jnp.copy, params0)
        opt_ms = bench._slope_time(
            chain_opt, (params_copy, opt_state0,
                        jax.random.PRNGKey(5))) * 1e3

        # ---- full step (bench's own measurement path) ----
        full_pc, full_ms, _ = bench._measure_encoder(
            "bag", tables_dtype=tdtype)

        row = {"tables_dtype": tdtype,
               "fwd_bwd_ms": round(fb_ms, 2),
               "optimizer_apply_ms": round(opt_ms, 2),
               "full_step_ms": round(full_ms, 2),
               "full_pc_per_sec": round(full_pc, 1)}
        rows.append(row)
        print(json.dumps(row), flush=True)

    if args.out:
        with open(args.out, "a") as f:
            for r in rows:
                f.write(json.dumps(r) + "\n")


if __name__ == "__main__":
    main()
