#!/usr/bin/env python3
"""Phase-level profile of the TRANSFORMER (configs[4]) training step.

VERDICT r3 item 4: the transformer config benches at 1.04x the V100
baseline with no engineering behind the number — no phase breakdown of
its ~100 ms step, no roofline statement. This tool slope-times each
phase of the xf2 java-large step (B=1024, C=200, D=384, H=4, L=2,
bf16 compute) and compares against a MEASURED MXU peak (big bf16
matmul on this chip, not a quoted spec), so the output answers: is the
step MXU-bound, HBM-bound, or idle?

Phases:
  matmul peak    dense [8192x8192]@[8192x8192] bf16 -> measured TFLOP/s
  emb gathers    3 embedding takes + concat + in_proj
  xf fwd         full encoder forward (layers + pool)
  attn core      the L x H attention blocks alone (qkv/logits/softmax/
                 out on real shapes) — the Pallas-candidate region
  mlp core       the L MLP blocks alone
  loss fwd       encoder + sampled softmax head
  fwd+bwd        value_and_grad of the loss
  full step      shipped adafactor train step

Analytic FLOPs for each phase give achieved TFLOP/s and MXU
utilization; the attention row also prints the [B,H,C,C] logits HBM
bytes the XLA path materializes (the traffic a fused kernel removes).

Usage: python tools/xf_profile.py [--steps 30] [--layers 2]
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
                           TARGET_VOCAB, TOKEN_VOCAB, slope_time,
                           time_fn)

E = 128


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--heads", type=int, default=3)  # shipped default
    # (hd=128 lane-aligned; --heads 4 reproduces the round-4
    # before/after comparison)
    args = ap.parse_args()
    from code2vec_tpu.device import enable_compile_cache
    enable_compile_cache()
    L, H = args.layers, args.heads

    import jax
    import jax.numpy as jnp

    from code2vec_tpu.models.encoder import ModelDims, init_params
    from code2vec_tpu.models.transformer_encoder import (_mha, _rms_norm,
                                                         encode_transformer)
    from code2vec_tpu.training.optimizers import make_optimizer
    from code2vec_tpu.training.steps import (make_train_loss_fn,
                                             make_train_step)

    dims = ModelDims(token_vocab_size=TOKEN_VOCAB,
                     path_vocab_size=PATH_VOCAB,
                     target_vocab_size=TARGET_VOCAB,
                     embeddings_size=E, max_contexts=CTX,
                     tables_dtype="bfloat16",
                     encoder_type="transformer", xf_layers=L,
                     xf_heads=H)
    D = dims.context_vector_size  # 3E = 384
    MLP = dims.xf_mlp_ratio * D
    params = init_params(jax.random.PRNGKey(0), dims)

    r = np.random.default_rng(0)
    labels = jnp.asarray(r.integers(0, TARGET_VOCAB, (B,), np.int32))
    src = jnp.asarray(r.integers(0, TOKEN_VOCAB, (B, CTX), np.int32))
    pth = jnp.asarray(r.integers(0, PATH_VOCAB, (B, CTX), np.int32))
    dst = jnp.asarray(r.integers(0, TOKEN_VOCAB, (B, CTX), np.int32))
    mask = jnp.ones((B, CTX), jnp.float32)
    weights = jnp.ones((B,), jnp.float32)
    batch = (labels, src, pth, dst, mask, weights)
    x_bcd = jnp.asarray(r.normal(size=(B, CTX, D)), jnp.bfloat16)
    log_mask = jnp.zeros((B, CTX), jnp.float32)

    rows = []

    def rec(name, dt, flops=None, extra=None):
        row = {"phase": name, "ms": round(dt * 1e3, 2)}
        if flops:
            row["tflops_per_sec"] = round(flops / dt / 1e12, 1)
        if extra:
            row.update(extra)
        rows.append(row)
        print(json.dumps(row), flush=True)
        return row

    # ---- measured MXU peak ----
    M = 8192
    a = jnp.asarray(r.normal(size=(M, M)), jnp.bfloat16)
    bmat = jnp.asarray(r.normal(size=(M, M)), jnp.bfloat16)
    mm = jax.jit(lambda a, b: a @ b)
    dt = time_fn(mm, (a, bmat), args.steps)
    peak = 2 * M**3 / dt
    peak_row = rec("matmul_peak_bf16", dt, flops=2 * M**3)

    # ---- embedding gathers + in_proj ----
    @jax.jit
    def emb_fn(params, src, pth, dst):
        e = jnp.concatenate([
            jnp.take(params["token_emb"], src, axis=0),
            jnp.take(params["path_emb"], pth, axis=0),
            jnp.take(params["token_emb"], dst, axis=0),
        ], axis=-1).astype(jnp.bfloat16)
        return e @ params["xf"]["in_proj"].astype(jnp.bfloat16)

    dt = time_fn(emb_fn, (params, src, pth, dst), args.steps)
    rec("emb_gathers_in_proj", dt, flops=2 * B * CTX * D * D)

    # ---- attention core (L layers of pre-LN MHA on real shapes) ----
    xf = params["xf"]

    @jax.jit
    def attn_fn(x):
        for layer in xf["layers"]:
            h = _rms_norm(x, layer["ln1_scale"])
            x = x + _mha(h, layer["qkv"], layer["out"], log_mask, H)
        return x

    attn_flops = L * (2 * B * CTX * D * 3 * D      # qkv
                      + 2 * 2 * B * H * CTX * CTX * (D // H)  # qk, av
                      + 2 * B * CTX * D * D)       # out
    logits_bytes = L * B * H * CTX * CTX * 4       # f32 materialization
    dt = time_fn(attn_fn, (x_bcd,), args.steps)
    rec("attn_core_fwd", dt, flops=attn_flops,
        extra={"xla_logits_hbm_bytes": logits_bytes})

    # ---- MLP core ----
    @jax.jit
    def mlp_fn(x):
        for layer in xf["layers"]:
            h = _rms_norm(x, layer["ln2_scale"])
            h = jax.nn.gelu(h @ layer["mlp_up"].astype(jnp.bfloat16))
            x = x + h @ layer["mlp_down"].astype(jnp.bfloat16)
        return x

    mlp_flops = L * 2 * 2 * B * CTX * D * MLP
    dt = time_fn(mlp_fn, (x_bcd,), args.steps)
    rec("mlp_core_fwd", dt, flops=mlp_flops)

    # ---- encoder fwd / loss fwd / fwd+bwd / full step ----
    @jax.jit
    def enc_fn(params, src, pth, dst, mask):
        code, _, _ = encode_transformer(params, src, pth, dst, mask,
                                        dims=dims,
                                        compute_dtype=jnp.bfloat16)
        return code

    dt = time_fn(enc_fn, (params, src, pth, dst, mask), args.steps)
    enc_flops = (2 * B * CTX * D * D + attn_flops + mlp_flops
                 + 2 * B * CTX * D)
    rec("encoder_fwd", dt, flops=enc_flops)

    head_flops = 2 * B * (NUM_SAMPLED + 1) * D
    rng = jax.random.PRNGKey(1)
    on_tpu = jax.default_backend() == "tpu"

    def measure_variant(tag, use_pallas):
        """Build + time one attention path's loss/grad/step. A factory
        so each variant's jits are evaluated once, outside the tag loop
        (graftlint retrace-hazard burndown: the two variants need
        genuinely different callables — use_pallas changes the program
        — so per-variant construction is the honest structure)."""
        loss_fn = make_train_loss_fn(dims, use_sampled_softmax=True,
                                     num_sampled=NUM_SAMPLED,
                                     compute_dtype=jnp.bfloat16,
                                     use_pallas=use_pallas)
        fwd = jax.jit(loss_fn)
        dt = time_fn(fwd, (params, batch, rng), args.steps,
                     sync=lambda o: float(o))
        rec(f"loss_fwd_{tag}", dt, flops=enc_flops + head_flops)

        grad_fn = jax.jit(jax.value_and_grad(loss_fn))
        dt = time_fn(grad_fn, (params, batch, rng), args.steps,
                     sync=lambda o: float(o[0]))
        fb = rec(f"fwd_bwd_{tag}", dt,
                 flops=3 * (enc_flops + head_flops))

        opt = make_optimizer(1e-3)
        step = make_train_step(dims, opt, use_sampled_softmax=True,
                               num_sampled=NUM_SAMPLED,
                               compute_dtype=jnp.bfloat16,
                               use_pallas=use_pallas)

        def chain(n, state):
            p, s, rng = state
            rng, sub = jax.random.split(rng)
            keys = list(jax.random.split(sub, max(n, 1)))
            t0 = time.perf_counter()
            for i in range(n):
                p, s, loss = step(p, s, batch, keys[i])
            float(loss)
            return time.perf_counter() - t0, (p, s, rng)

        # the chained step DONATES its params/opt_state — feed it
        # copies or the next tag's measurements read deleted arrays
        p0 = jax.tree_util.tree_map(jnp.copy, params)
        dt = slope_time(
            chain, (p0, opt.init(p0), jax.random.PRNGKey(2)),
            args.steps)
        full = rec(f"full_step_adafactor_{tag}", dt,
                   flops=3 * (enc_flops + head_flops),
                   extra={"pc_per_sec": round(B * CTX / dt, 1)})
        return fb, full

    # both attention paths: XLA einsum+softmax vs the fused Pallas
    # kernel pair (ops/xf_attention.py) — the before/after of the
    # [B,H,C,C] HBM materialization. Off-TPU only XLA runs (interpret
    # mode would measure the interpreter).
    fb, full = measure_variant("xla", False)
    if on_tpu:
        fb, full = measure_variant("pallas", True)

    # ---- roofline statement ----
    util = (full["tflops_per_sec"]
            / peak_row["tflops_per_sec"])
    print(f"\nmeasured bf16 matmul peak: "
          f"{peak_row['tflops_per_sec']} TFLOP/s")
    print(f"full step achieved:        {full['tflops_per_sec']} "
          f"TFLOP/s = {util:.0%} of measured peak")
    print(f"fwd+bwd achieved:          {fb['tflops_per_sec']} TFLOP/s "
          f"= {fb['tflops_per_sec'] / peak_row['tflops_per_sec']:.0%}")


if __name__ == "__main__":
    main()
