#!/usr/bin/env python3
"""Chip smoke: drive training and serving once on the TPU, at java-large
width, through the entry points a user calls.

    python3 chip_smoke.py [--config bag|transformer|int8|sparse|lfm2_moe|qwen3_next|joyai_flash]

One process, phases in order, the first failure ends the run with a
non-zero exit and no result line:

  device   JAX must report a TPU (there is no CPU path in this file)
  sync     block_until_ready and a scalar host transfer must time a
           >=200 ms device loop alike
  data     a java-large-capacity dataset generated from a seed
  train    code2vec.main() — reader, prefetch, jitted step, eval on the
           val split, async checkpoint — then the checkpoint verified,
           the compiled step inspected and device memory read
  serve    --load that checkpoint behind a warmed PredictionServer and
           answer requests of mixed sizes with no new compilation
  kernels  every Pallas kernel the program selects on a TPU, compiled
           (interpret=False) at java-large shape against its reference

The summary (steps, losses, compile and run seconds, the cache directory,
per-kernel results, the sync-check numbers) is printed as one
`chip_smoke: summary {...}` line. The last line of standard output is the
verdict, one JSON object with these keys and no others:
{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}.
"""

from __future__ import annotations

import argparse
import glob
import json
import math
import os
import pickle
import re
import shutil
import sys
import time

_ROOT = os.path.dirname(os.path.abspath(__file__))

# java-large capacities (preprocess.sh, bench.py) and the shipped step
TOKENS, PATHS, TARGETS = 1_301_136, 911_417, 261_245
BATCH, MAX_CONTEXTS, NUM_SAMPLED, EMB = 1024, 200, 4096, 128
TRAIN_STEPS = 10            # methods generated = TRAIN_STEPS * BATCH
VAL_METHODS = 1024
TARGET_CLASSES = 512        # targets the generated methods actually use
REQUEST_SIZES = (1, 2, 3, 5, 8, 13, 21, 34, 64, 1)
_BACKEND = "tpu"

# lfm2_moe at LFM2-24B-A2B's published widths and a small depth (one
# dense convolution layer, one attention layer with 8 of 64 experts):
# 175M float32 parameters under Adam, so 128 methods a batch
LFM_BLOCK = {"layer_types": ["conv", "full_attention"],
             "num_dense_layers": 1, "hidden_size": 2048,
             "intermediate_size": 11776, "moe_intermediate_size": 1536,
             "num_attention_heads": 32, "num_key_value_heads": 8,
             "num_experts": 8, "num_routed_experts": 64, "first_expert": 0,
             "num_experts_per_tok": 4, "conv_L_cache": 3, "norm_eps": 1e-5,
             "rope_parameters": {"rope_theta": 1000000}}
# qwen3_next at Qwen3-Next-80B-A3B's published widths and a small depth
# (one gated-DeltaNet layer, one gated-attention layer, each with 8 of
# 512 routed experts beside the shared one): 112M float32 parameters
QWEN_BLOCK = {"num_hidden_layers": 2, "full_attention_interval": 2,
              "hidden_size": 2048, "num_attention_heads": 16,
              "num_key_value_heads": 2, "head_dim": 256,
              "partial_rotary_factor": 0.25, "rope_theta": 10000000,
              "rms_norm_eps": 1e-6, "linear_conv_kernel_dim": 4,
              "linear_key_head_dim": 128, "linear_value_head_dim": 128,
              "linear_num_key_heads": 16, "linear_num_value_heads": 32,
              "num_experts": 8, "num_routed_experts": 512,
              "first_expert": 0, "num_experts_per_tok": 10,
              "moe_intermediate_size": 512,
              "shared_expert_intermediate_size": 512}
# joyai_flash at JoyAI-LLM-Flash's published widths and a small depth
# (the leading dense layer and one expert layer with 16 of 256 routed
# experts beside the shared one, latent attention in both): 177M float32
# parameters
JOYAI_BLOCK = {"num_hidden_layers": 2, "hidden_size": 2048,
               "num_attention_heads": 32, "q_lora_rank": 1536,
               "kv_lora_rank": 512, "qk_nope_head_dim": 128,
               "qk_rope_head_dim": 64, "v_head_dim": 128,
               "intermediate_size": 7168, "moe_intermediate_size": 768,
               "n_routed_experts": 16, "num_routed_experts": 256,
               "first_expert": 0, "n_shared_experts": 1,
               "num_experts_per_tok": 8, "first_k_dense_replace": 1,
               "routed_scaling_factor": 2.5, "rope_theta": 32000000,
               "rms_norm_eps": 1e-6}
# the encoders that read their sizes from a file (--block_config)
BLOCKS = {"lfm2_moe": LFM_BLOCK, "qwen3_next": QWEN_BLOCK,
          "joyai_flash": JOYAI_BLOCK}
CONFIG_BATCH = {"lfm2_moe": 128, "qwen3_next": 128, "joyai_flash": 128}

CONFIG_FLAGS = {
    "bag": [],
    "transformer": ["--encoder", "transformer"],
    "int8": ["--tables_dtype", "int8"],
    "sparse": ["--sparse_embeddings", "--embedding_optimizer", "adam",
               "--lr_schedule", "constant"],
    "lfm2_moe": ["--encoder", "lfm2_moe"],
    "qwen3_next": ["--encoder", "qwen3_next"],
    "joyai_flash": ["--encoder", "joyai_flash"],
}


def check(cond, phase: str, msg: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: {phase} FAILED: {msg}")


# ---- device -------------------------------------------------------------

def device_phase() -> dict:
    import jax

    devices = jax.devices()
    d = devices[0]
    print(f"chip_smoke: jax {jax.__version__} platform={d.platform} "
          f"device_kind={d.device_kind!r} count={len(devices)}",
          flush=True)
    check(d.platform == "tpu", "device",
          f"JAX found no TPU (platform {d.platform!r})")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


# ---- sync check ---------------------------------------------------------

def sync_phase() -> dict:
    """Does block_until_ready wait for the device? Time one jitted loop
    of >=200 ms three ways: to block_until_ready, to a scalar host
    transfer, and the transfer left over after block_until_ready."""
    import jax
    import jax.numpy as jnp

    n = 1024
    w = jax.random.normal(jax.random.PRNGKey(0), (n, n)) / math.sqrt(n)

    @jax.jit
    def spin(x, iters):
        y = jax.lax.fori_loop(0, iters,
                              lambda _, a: jnp.tanh(a @ w), x)
        return y, y[0, 0]

    x = jnp.ones((n, n), jnp.float32)
    float(spin(x, 1)[1])  # compile
    iters = 256
    while True:
        t0 = time.perf_counter()
        float(spin(x, iters)[1])
        if time.perf_counter() - t0 >= 0.25:
            break
        iters *= 2
    blocks, hosts, residuals = [], [], []
    for _ in range(3):  # best of three: one-shot wall times are noisy
        t0 = time.perf_counter()
        out = spin(x, iters)
        jax.block_until_ready(out)
        blocks.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        float(out[1])
        residuals.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        float(spin(x, iters)[1])
        hosts.append(time.perf_counter() - t0)
    block_s, host_s, residual_s = min(blocks), min(hosts), max(residuals)
    res = {"iters": iters, "block_until_ready_s": round(block_s, 4),
           "host_transfer_s": round(host_s, 4),
           "transfer_after_block_s": round(residual_s, 5)}
    print(f"chip_smoke: sync {res}", flush=True)
    check(block_s >= 0.2, "sync", f"loop too short: {res}")
    check(abs(block_s - host_s) <= 0.10 * max(block_s, host_s), "sync",
          f"block_until_ready and a host transfer disagree: {res}")
    check(residual_s <= 0.10 * block_s, "sync",
          f"work was left after block_until_ready returned: {res}")
    return res


# ---- data ---------------------------------------------------------------

def _method_lines(n: int, seed: int):
    """`target tok,path,tok ...` lines with up to MAX_CONTEXTS contexts
    drawn over the whole java-large vocabularies. Half of a method's
    contexts are cues fixed by its target class, so the target is
    recoverable from the bag (as tests/helpers.make_raw_lines biases
    its lines)."""
    import numpy as np

    r = np.random.default_rng(seed)
    lines = []
    for _ in range(n):
        k = int(r.integers(TARGET_CLASSES))
        c = int(r.integers(MAX_CONTEXTS // 2, MAX_CONTEXTS + 1))
        cue = r.random(c) < 0.5
        jit = r.integers(0, 2, (3, c))
        src = np.where(cue, (k * 7 + jit[0]) % TOKENS,
                       r.integers(0, TOKENS, c))
        pth = np.where(cue, (k * 5 + jit[1]) % PATHS,
                       r.integers(0, PATHS, c))
        dst = np.where(cue, (k * 11 + jit[2]) % TOKENS,
                       r.integers(0, TOKENS, c))
        ctx = " ".join(f"t{a},p{b},t{d}" for a, b, d in
                       zip(src.tolist(), pth.tolist(), dst.tolist()))
        target = k * (TARGETS // TARGET_CLASSES)
        lines.append(f"get|m{target} {ctx}")
    return lines


def data_phase(out_dir: str, seed: int, batch: int) -> dict:
    prefix = os.path.join(out_dir, "data", "smoke")
    os.makedirs(os.path.dirname(prefix))
    # every word distinct in count, so the frequency cut keeps all of
    # them in this order: vocab sizes are the java-large capacities
    with open(prefix + ".dict.c2v", "wb") as f:
        for stem, n in (("t", TOKENS), ("p", PATHS)):
            pickle.dump({f"{stem}{i}": n - i for i in range(n)}, f)
        pickle.dump({f"get|m{i}": TARGETS - i for i in range(TARGETS)},
                    f)
        pickle.dump(TRAIN_STEPS * batch, f)
    splits = {"train": (TRAIN_STEPS * batch, seed),
              "val": (VAL_METHODS, seed + 1)}
    val_lines = None
    for split, (n, s) in splits.items():
        lines = _method_lines(n, s)
        with open(f"{prefix}.{split}.c2v", "w", encoding="utf-8") as f:
            f.write("\n".join(lines) + "\n")
        if split == "val":
            val_lines = lines
    return {"prefix": prefix, "val_lines": val_lines}


# ---- train --------------------------------------------------------------

def _events(telemetry_dir: str):
    out = []
    for path in sorted(glob.glob(
            os.path.join(telemetry_dir, "*", "events.jsonl"))):
        with open(path) as f:
            out.extend(json.loads(ln) for ln in f if ln.strip())
    return out


def _placement(x) -> dict:
    shards = x.addressable_shards
    return {"shape": list(x.shape),
            "devices": len({s.device.id for s in shards}),
            "shard_shape": list(shards[0].data.shape)}


def train_phase(out_dir: str, prefix: str, config: str,
                batch: int) -> dict:
    import jax
    import numpy as np

    import code2vec

    ckpt_dir = os.path.join(out_dir, "ckpt")
    tele_dir = os.path.join(out_dir, "telemetry")
    argv = ["--data", prefix, "--test", prefix + ".val.c2v",
            "--save", ckpt_dir, "--sampled_softmax",
            "--num_sampled", str(NUM_SAMPLED),
            "--batch_size", str(batch),
            "--max_contexts", str(MAX_CONTEXTS), "--epochs", "1",
            "--backend", _BACKEND, "--telemetry_dir", tele_dir,
            *CONFIG_FLAGS[config]]
    if config in BLOCKS:
        block = os.path.join(out_dir, "block.json")
        with open(block, "w") as f:
            json.dump(BLOCKS[config], f)
        argv += ["--block_config", block]
    print(f"chip_smoke: code2vec.main({' '.join(argv)})", flush=True)
    rc = code2vec.main(argv)
    check(rc == 0, "train", f"code2vec.main returned {rc}")

    events = _events(tele_dir)
    losses = [e["loss"] for e in events if e.get("kind") == "step"]
    check(len(losses) >= 8, "train", f"{len(losses)} steps taken, < 8")
    check(all(math.isfinite(x) for x in losses), "train",
          f"non-finite loss: {losses}")
    evals = [e for e in events if e.get("kind") == "eval"]
    check(len(evals) == 1 and math.isfinite(evals[0]["loss"]), "train",
          f"val-split evaluation missing or non-finite: {evals}")

    from code2vec_tpu.training import checkpoint as ckpt
    step = ckpt.latest_step(ckpt_dir)
    check(step == len(losses), "train",
          f"committed checkpoint step {step} != steps {len(losses)}")
    check(ckpt.verify_step(ckpt_dir, step) is True, "train",
          f"verify_step({step}) is not clean")

    stats = jax.local_devices()[0].memory_stats()
    peak_bytes = (stats or {}).get("peak_bytes_in_use")
    check(isinstance(peak_bytes, int), "train",
          f"memory_stats() gave no peak_bytes_in_use: {stats}")

    # The step that just trained, rebuilt from the same flags: the
    # pool must be the Mosaic kernel, and on several chips the batch
    # must reach it sharded.
    from code2vec_tpu.config import Config
    from code2vec_tpu.data.reader import BatchTensors
    from code2vec_tpu.models.jax_model import Code2VecModel
    model = Code2VecModel(Config.load_from_args(argv))
    ids = np.zeros((batch, model.dims.max_contexts), np.int32)
    dev_batch = model._device_batch(BatchTensors(
        np.zeros((batch,), np.int32), ids, ids, ids,
        ids.astype(np.float32), batch))
    hlo = model._train_step.lower(
        model.params, model.opt_state, dev_batch,
        jax.random.PRNGKey(0)).compile().as_text()
    mosaic_calls = hlo.count('custom_call_target="tpu_custom_call"')
    # (the sparse step pools with XLA — sparse_steps.make_gathered_loss
    # — and its bf16 rows take the reference apply: no kernel in it)
    check(mosaic_calls > 0 or config == "sparse", "train",
          "the compiled train step holds no tpu_custom_call: the "
          "Pallas kernels did not reach Mosaic")
    gathers = sorted(set(re.findall(
        r"= (\S+) all-gather(?:-start)?\(", hlo)))
    tables = {k: _placement(v["q"] if isinstance(v, dict) else v)
              for k, v in model.params.items() if k.endswith("_emb")}
    return {"steps": len(losses), "losses": losses,
            "first_loss": losses[0], "last_loss": losses[-1],
            "eval_loss": evals[0]["loss"], "checkpoint_step": step,
            "peak_bytes_in_use": peak_bytes,
            "use_pallas": model.use_pallas,
            "mosaic_calls_in_train_step": mosaic_calls,
            "mesh": None if model.mesh is None else
            {k: int(v) for k, v in model.mesh.shape.items()},
            "batch_placement": _placement(dev_batch[1]),
            "table_placement": tables,
            "all_gathers_in_train_step": gathers[:16],
            "ckpt_dir": ckpt_dir}


# ---- serve --------------------------------------------------------------

def serve_phase(ckpt_dir: str, val_lines, config: str) -> dict:
    from code2vec_tpu.config import Config
    from code2vec_tpu.models.jax_model import Code2VecModel
    from code2vec_tpu.serving.interactive_predict import \
        InteractivePredictor

    block_encoder = config in BLOCKS
    config = Config.load_from_args(
        ["--load", ckpt_dir, "--predict", "--backend", _BACKEND])
    model = Code2VecModel(config)
    predictor = InteractivePredictor(config, model)
    server = predictor.server
    t0 = time.perf_counter()
    server.start(warmup=True)
    warmup_s = time.perf_counter() - t0
    try:
        compiled = model.predict_compile_count()
        check(compiled >= 1, "serve",
              f"compile count after warm-up is {compiled}")
        at, answered = 0, 0
        for n in REQUEST_SIZES:
            lines = val_lines[at:at + n]
            at += n
            results = server.predict_lines(lines)
            check(len(results) == n, "serve",
                  f"{len(results)} results for {n} methods")
            for line, res in zip(lines, results):
                check(res.original_name == line.split(" ", 1)[0]
                      and res.predictions
                      and all(math.isfinite(p["probability"])
                              for p in res.predictions), "serve",
                      f"no top-k names for {res.original_name}")
            answered += 1
        # the batcher must hand back the rows the model itself gives.
        # Methods asked before come out of the cache, answered in batches
        # of other sizes; a 2048-wide encoder's matmuls round differently
        # from one batch shape to another and swap near-tied names, so
        # a block encoder is asked methods not asked before: one batch of
        # one size on both sides
        first = at if block_encoder else 0
        lines = val_lines[first:first + REQUEST_SIZES[3]]
        direct = model.predict(lines)
        served = server.predict_lines(lines)
        check([[p["name"] for p in r.predictions] for r in direct]
              == [[p["name"] for p in r.predictions] for r in served],
              "serve", "server and direct predict disagree")
        after = model.predict_compile_count()
        check(after == compiled, "serve",
              f"compile count grew under load: {compiled} -> {after}")
    finally:
        server.close()
        predictor.telemetry.close()
    return {"requests": answered, "request_sizes": list(REQUEST_SIZES),
            "warmup_s": round(warmup_s, 2),
            "compiled_after_warmup": compiled,
            "new_compilations_under_load": after - compiled}


# ---- kernels ------------------------------------------------------------

def _err(a, b) -> float:
    import jax.numpy as jnp

    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32))))


def _ulps(a, b) -> int:
    """Largest distance between two f32 arrays in units in the last
    place (sign-magnitude bits mapped onto one ordered integer line)."""
    import jax
    import jax.numpy as jnp

    def ordered(x):
        bits = jax.lax.bitcast_convert_type(x, jnp.int32)
        mag = bits & 0x7FFFFFFF
        return jnp.where(bits < 0, -mag, mag)
    return int(jnp.max(jnp.abs(ordered(a) - ordered(b))))


def kernels_phase() -> dict:
    """Each kernel compiled by Mosaic (`interpret=False`) and run once
    at java-large shape, against its in-repo reference computed in f32
    at the highest matmul precision. The tolerances are those of the
    repo's own CPU tests for the same comparison, widened only where
    the kernel's bf16 inputs and outputs set the error floor."""
    import jax
    import jax.numpy as jnp

    out = {}
    key = jax.random.PRNGKey(7)
    f32, bf16 = jnp.float32, jnp.bfloat16
    B, C, D, H = BATCH, MAX_CONTEXTS, 3 * EMB, 3

    def report(name, ok, **facts):
        out[name] = {"ok": bool(ok), **facts}
        print(f"chip_smoke: kernel {name}: {out[name]}", flush=True)
        check(ok, "kernels", f"{name} is outside its tolerance: {facts}")

    # -- attention pool (ops/pallas_attention.py) --
    from code2vec_tpu.ops.attention import attention_pool
    from code2vec_tpu.ops.pallas_attention import attention_pool_pallas
    k1, k2, k3, k4, key = jax.random.split(key, 5)
    ctx = (jax.random.normal(k1, (B, C, D)) * 0.5).astype(bf16)
    init = jax.nn.initializers.variance_scaling(1.0, "fan_avg",
                                                "uniform")
    tr, at = init(k2, (D, D), f32), init(k3, (D, 1), f32)[:, 0]
    mask = (jax.random.uniform(k4, (B, C)) > 0.3).astype(f32)
    mask = mask.at[:, 0].set(1.0)
    code, attn = attention_pool_pallas(ctx, tr, at, mask,
                                       interpret=False)
    with jax.default_matmul_precision("highest"):
        code_ref, attn_ref = jax.jit(attention_pool)(
            ctx.astype(f32), tr, at, mask)
    e_code, e_attn = _err(code, code_ref), _err(attn, attn_ref)
    report("attention_pool", e_code <= 2e-3 and e_attn <= 1e-3,
           shape=[B, C, D], code_max_err=e_code, attn_max_err=e_attn,
           tolerance={"code": 2e-3, "attn": 1e-3})
    del ctx, code, attn, code_ref, attn_ref

    # -- fused MHA forward and backward (ops/xf_attention.py) --
    from code2vec_tpu.ops import xf_attention as xa
    kq, kk, kv, kd, km, key = jax.random.split(key, 6)
    hd = D // H
    q, k, v, do = ((jax.random.normal(kx, (B, H, C, hd)) * 0.5
                    ).astype(bf16) for kx in (kq, kk, kv, kd))
    log_mask = jnp.where(jax.random.uniform(km, (B, C)) > 0.3, 0.0,
                         -1e9).astype(f32).at[:, 0].set(0.0)
    o = xa._mha_fwd_pallas(q, k, v, log_mask, interpret=False)
    dq, dk, dv = xa._mha_bwd_pallas(q, k, v, log_mask, do,
                                    interpret=False)
    with jax.default_matmul_precision("highest"):
        qf, kf, vf = (a.astype(f32) for a in (q, k, v))
        o_ref, vjp = jax.vjp(
            lambda a, b, c: xa.mha_reference(a, b, c, log_mask),
            qf, kf, vf)
        dq_ref, dk_ref, dv_ref = vjp(do.astype(f32))
    e_fwd = _err(o, o_ref)
    report("mha_forward", e_fwd <= 3e-2, shape=[B, H, C, hd],
           max_err=e_fwd, tolerance=3e-2)
    e_bwd = max(_err(dq, dq_ref), _err(dk, dk_ref), _err(dv, dv_ref))
    report("mha_backward", e_bwd <= 3e-2, shape=[B, H, C, hd],
           max_err=e_bwd, tolerance=3e-2)
    del q, k, v, do, o, dq, dk, dv, o_ref, dq_ref, dk_ref, dv_ref, vjp
    del qf, kf, vf

    # -- fused requantize row-pass (ops/pallas_requant.py) --
    from code2vec_tpu.ops.pallas_requant import requantize_fused
    from code2vec_tpu.ops.quant import (quantize_table,
                                        requantize_reference)
    kt, ku, kr, key = jax.random.split(key, 4)
    qt = jax.jit(lambda kk_: quantize_table(
        jax.random.normal(kk_, (TOKENS, EMB)) * 0.1))(kt)
    upd = (jax.random.normal(ku, (TOKENS, EMB)) * 1e-3).astype(bf16)
    fused = requantize_fused(qt, upd, kr, interpret=False)
    ref = jax.jit(requantize_reference)(qt, upd, kr)
    dq_ = jnp.abs(fused["q"].astype(jnp.int32)
                  - ref["q"].astype(jnp.int32))
    q_off = int(jnp.sum(dq_ > 0))
    s_ulp = _ulps(fused["s"], ref["s"])
    report("requantize", q_off == 0 and s_ulp <= 2,
           shape=[TOKENS, EMB], q_mismatches=q_off,
           q_max_diff=int(jnp.max(dq_)), s_max_ulp=s_ulp,
           tolerance={"q": "exact", "s_ulp": 2})
    del qt, upd, fused, ref, dq_

    # -- sparse live-row Adam (ops/pallas_sparse_update.py) --
    from code2vec_tpu.training import sparse_update as su
    from code2vec_tpu.training.sparse_adam import RowAdamState
    ki, kg, kp, k0, key = jax.random.split(key, 5)
    n_ids = 2 * B * C  # src + dst token ids of one step, uniform
    ids = jax.random.randint(ki, (n_ids,), 0, TOKENS, jnp.int32)
    grads = (jax.random.normal(kg, (n_ids, EMB)) * 1e-3).astype(bf16)
    table = jax.random.normal(kp, (TOKENS, EMB)) * 0.1
    g0 = jax.random.normal(k0, (TOKENS, EMB)) * 1e-3  # one Adam step in
    state = RowAdamState(m=0.1 * g0, v=0.001 * jnp.square(g0))
    count = jnp.asarray(3, jnp.int32)
    check(su._resolve_fused(None, table), "kernels",
          "auto does not select the live-row kernel for f32 rows")

    def apply(fused_):
        return jax.jit(lambda t, s, i, g: su.sparse_row_adam(
            t, s, i, g, count=count, lr=1e-3, fused=fused_))(
            table, state, ids, grads)
    (t_f, s_f), (t_r, s_r) = apply(True), apply(False)
    # the contract in ops/pallas_sparse_update.py: moments exact, the
    # parameter within 2 ulp at the scale of the larger of its old
    # value and its step
    scale = jnp.maximum(jnp.abs(table), jnp.abs(table - t_r))
    p_ulp = float(jnp.max(jnp.abs(t_f - t_r)
                          / (jnp.nextafter(scale, jnp.inf) - scale)))
    m_ulp, v_ulp = _ulps(s_f.m, s_r.m), _ulps(s_f.v, s_r.v)
    moved = int(jnp.sum(jnp.any(t_r != table, axis=1)))
    report("sparse_row_adam_f32",
           p_ulp <= 2 and m_ulp == 0 and v_ulp == 0 and moved > 0,
           shape=[TOKENS, EMB], ids=n_ids, rows_moved=moved,
           p_max_ulp=p_ulp, p_max_err=_err(t_f, t_r), m_max_ulp=m_ulp,
           v_max_ulp=v_ulp, tolerance={"p_ulp": 2, "m": "exact",
                                       "v": "exact"})
    # packed rows cannot be DMA'd singly (sparse_update._resolve_fused
    # carries Mosaic's message): auto must say so, not try
    for name, tbl in (("bf16", table.astype(bf16)),
                      ("int8", {"q": jnp.zeros((8, EMB), jnp.int8),
                                "s": jnp.ones((8, 1), f32)})):
        check(not su._resolve_fused(None, tbl), "kernels",
              f"auto selects the live-row kernel for {name} rows, "
              "which Mosaic refuses")
    out["sparse_row_adam_bf16"] = out["sparse_requant_adam_int8"] = {
        "ok": None, "selected": False,
        "why": "Mosaic refuses single-row DMA of packed rows; auto "
               "takes the XLA reference"}
    return out


# ---- main ---------------------------------------------------------------

def verdict_line(device: dict) -> str:
    """The last line of standard output: exactly `ok` and `device`, the
    device exactly `platform`, `kind`, `count` as JAX reports them.
    Everything else the run learned is on the summary line before it."""
    return json.dumps({"ok": True,
                       "device": {"platform": str(device["platform"]),
                                  "kind": str(device["kind"]),
                                  "count": int(device["count"])}})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", choices=sorted(CONFIG_FLAGS),
                    default="bag",
                    help="which existing flag set the train phase uses")
    ap.add_argument("--out", default=os.path.join(_ROOT, ".chip_smoke"),
                    help="scratch directory (emptied first)")
    ap.add_argument("--seed", type=int, default=239)
    args = ap.parse_args(argv)

    t_start = time.perf_counter()
    device = device_phase()

    import jax

    from code2vec_tpu.device import enable_compile_cache
    cache_dir = enable_compile_cache()
    compile_s = [0.0]
    cache = {"hits": 0, "misses": 0}

    def on_duration(event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s[0] += duration

    def on_event(event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache["misses"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)

    phase_s = {}

    def timed(name, fn, *a):
        t0 = time.perf_counter()
        print(f"chip_smoke: == {name} ==", flush=True)
        res = fn(*a)
        phase_s[name] = round(time.perf_counter() - t0, 2)
        return res

    sync = timed("sync", sync_phase)
    shutil.rmtree(args.out, ignore_errors=True)
    batch = CONFIG_BATCH.get(args.config, BATCH)
    data = timed("data", data_phase, args.out, args.seed, batch)
    train = timed("train", train_phase, args.out, data["prefix"],
                  args.config, batch)
    serve = timed("serve", serve_phase, train.pop("ckpt_dir"),
                  data["val_lines"], args.config)
    kernels = timed("kernels", kernels_phase)
    shutil.rmtree(args.out, ignore_errors=True)

    total_s = time.perf_counter() - t_start
    print("chip_smoke: summary " + json.dumps({
        "config": args.config, "device": device,
        "jax": jax.__version__, "train": train, "serve": serve,
        "kernels": kernels, "sync_check": sync,
        "compile_cache": {"dir": cache_dir, **cache},
        "seconds": {"total": round(total_s, 1),
                    "compiling": round(compile_s[0], 1),
                    "running": round(total_s - compile_s[0], 1),
                    "phases": phase_s}}), flush=True)
    print(verdict_line(device), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
