#!/usr/bin/env python3
"""Benchmark: training throughput of the java-large config on one chip.

Prints ONE JSON line:
  {"metric": "path-contexts/sec/chip", "value": N, "unit": "...",
   "vs_baseline": N, ...}

Metric (BASELINE.json): path-contexts/sec/chip on java-large =
examples/sec * MAX_CONTEXTS(200), measured over the jitted training step
(sampled softmax over the 261K-name target vocab — the north-star
java-large configuration; full vocab tables at reference capacity),
using the SHIPPED config: bf16 tables, adafactor table optimizer
(training/optimizers.make_optimizer), bf16 compute, Pallas pool on TPU.

Extra keys:
  - hbm_gbps / hbm_ceiling_gbps: achieved HBM bandwidth of the step
    (analytic streaming-traffic model below / measured step time) vs the
    measured 1-GiB-copy streaming ceiling on this chip. The step is
    HBM-bound (BASELINE.md "Phase isolation"), so hbm_gbps close to the
    ceiling means the config is at its roofline and further per-chip
    gains need less *traffic*, not better overlap.
  - transformer_*: the same measurement for --encoder transformer
    (xf_layers=2), the BASELINE.json configs[4] stretch encoder.
  - sparse_*: the carrier-free sparse-update config (ROADMAP item 1:
    --sparse_embeddings, gathered-row diff + dedup/segment-sum +
    live-row row-Adam) with the update phase attributed every round:
    sparse_update_ms (the apply alone, fused Pallas live-row kernel on
    TPU), sparse_update_bytes ([U, E]-aware analytic bytes),
    sparse_update_unique_rows, and sparse_step_floor_pc_per_sec — the
    corrected analytic floor counting [U, E] traffic instead of the
    dense [V, E] carrier.
  - int8_*: the sub-bf16 memory-lever config (ops/quant.py), with the
    requantize phase attributed every round: int8_requant_ms (the
    apply alone, fused Pallas row-pass on TPU), int8_requant_bytes
    (analytic bytes of ONE fused sweep), int8_requant_gbps achieved vs
    int8_requant_floor_ms (= bytes / streaming ceiling — the phase at
    its roofline). int8_hbm_gbps uses the quantized-carrier-aware
    traffic model (bf16 [V, E] grad carrier + int8 q / f32 s r+w).

Baseline denominator: derived, methodology-documented single-V100
estimate of the reference step (fp32, full softmax, dense Adam, input
pipeline assumed free — every assumption favoring the reference):
1.94M path-contexts/s, the midpoint of the 1.67M-2.20M device-bound band
computed by tools/v100_roofline.py and anchored against a real TF 2.21
execution of the same graph math by tools/tf_baseline.py. See
BASELINE.md "Baseline denominator". The community-anecdote figure used
in round 1 (700K) survives only as the real-world lower bound.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

V100_BASELINE_PATH_CONTEXTS_PER_SEC = 1_940_000.0  # tools/v100_roofline.py
V100_BASELINE_BAND = (1_675_000.0, 2_197_000.0)

# java-large capacities (SURVEY.md §3 config row)
TOKEN_VOCAB = 1_301_136
PATH_VOCAB = 911_417
TARGET_VOCAB = 261_245
BATCH = 1024
MAX_CONTEXTS = 200
NUM_SAMPLED = 4096
WARMUP_STEPS = 5
MEASURE_STEPS = 40


def _step_hbm_bytes(params, opt_state) -> int:
    """Analytic per-step HBM traffic of the table-dominated phases
    (BASELINE.md "Phase isolation" — the step is streaming-bound on
    exactly this traffic):

      backward: dense grad buffer written once per table (grad dtype ==
                param dtype under value_and_grad);
      optimizer: grads read, params read + written, every optimizer-state
                leaf read + written (Adam: 2 full-table f32 moments;
                adafactor: factored row/col stats, ~V+E per table);
      quantized {q, s} subtrees (tables_dtype int8): the table gradient
                is a bf16 [V, E] CARRIER (ops/quant.py straight-through
                custom_vjp), not an int8 array, so the grad term counts
                2 bytes/elt; the param term is the requantize pass's
                int8 q + f32 s read + write. Sizing the grad by the
                stored dtype undercounted int8 2x (ADVICE r5 finding 2).

    Gathers/activations (~0.3 GB at B=1024, and running at random-access
    bandwidth, not streaming) are excluded — this is a lower bound, so
    achieved GB/s derived from it is conservative."""
    import jax

    from code2vec_tpu.ops.quant import is_quantized

    total = 0
    for p in params.values():
        if is_quantized(p):
            total += p["q"].size * 2 * 2  # bf16 carrier grad write+read
            total += p["q"].size * p["q"].dtype.itemsize * 2  # q r+w
            total += p["s"].size * p["s"].dtype.itemsize * 2  # s r+w
            continue
        # plain leaves — including nested subtrees (transformer "xf")
        for leaf in jax.tree_util.tree_leaves(p):
            b = leaf.size * leaf.dtype.itemsize
            total += b * 4  # grad write + grad read + param read + write
    for s in jax.tree_util.tree_leaves(opt_state):
        total += s.size * s.dtype.itemsize * 2  # state read + write
    return total


def _measure_hbm_ceiling() -> float:
    """Streaming bandwidth ceiling (ops/membench.py — shared with
    tools/profile_step.py)."""
    from code2vec_tpu.ops.membench import measure_hbm_ceiling
    return measure_hbm_ceiling()


def _java_large_dims(encoder_type: str = "bag",
                     tables_dtype: str = "bfloat16",
                     max_contexts: int = MAX_CONTEXTS):
    from code2vec_tpu.models.encoder import ModelDims
    # xf_heads=3: the shipped default (head_dim 128 = MXU lane width;
    # quality-identical to 4 heads, 9% faster — BASELINE.md round 4)
    return ModelDims(token_vocab_size=TOKEN_VOCAB,
                     path_vocab_size=PATH_VOCAB,
                     target_vocab_size=TARGET_VOCAB,
                     embeddings_size=128, max_contexts=max_contexts,
                     tables_dtype=tables_dtype, encoder_type=encoder_type,
                     xf_layers=2, xf_heads=3)


def _device_batches(n: int = 4, max_contexts: int = MAX_CONTEXTS):
    """n distinct uniform-random batches, placed on device once (the
    rotation defeats any cross-step input caching; ids are uniform —
    the worst case for the embedding gathers)."""
    import jax.numpy as jnp

    r = np.random.default_rng(0)
    out = []
    for _ in range(n):
        arrays = (
            r.integers(0, TARGET_VOCAB, size=(BATCH,), dtype=np.int32),
            r.integers(0, TOKEN_VOCAB, size=(BATCH, max_contexts),
                       dtype=np.int32),
            r.integers(0, PATH_VOCAB, size=(BATCH, max_contexts),
                       dtype=np.int32),
            r.integers(0, TOKEN_VOCAB, size=(BATCH, max_contexts),
                       dtype=np.int32),
            np.ones((BATCH, max_contexts), dtype=np.float32),
            np.ones((BATCH,), dtype=np.float32))
        out.append(tuple(jnp.asarray(a) for a in arrays))
    return out


def _slope_time(chain, state):
    """Slope timing: two chain lengths, differenced — cancels the fixed
    dispatch/sync overhead. `chain(n, state) -> (seconds, state)` must
    end in a sync (a scalar host transfer or block_until_ready)."""
    _, state = chain(WARMUP_STEPS, state)
    t1, state = chain(10, state)
    t2, state = chain(10 + MEASURE_STEPS, state)
    return (t2 - t1) / MEASURE_STEPS


def _measure_fwd_bwd_floor():
    """Forward+backward only (no optimizer), with the IDENTICAL math and
    inputs as the full step (dropout on, same 4-batch rotation): the
    zero-cost-optimizer ceiling of this config. The full step can't beat
    B*C/floor_dt pc/s whatever the optimizer does — the floor is the
    backward scatter-add of the dense embedding grads running at
    random-access (not streaming) bandwidth; see BASELINE.md round-3
    phase floors."""
    import jax
    import jax.numpy as jnp

    from code2vec_tpu.models.encoder import init_params
    from code2vec_tpu.training.steps import make_train_loss_fn

    dims = _java_large_dims()
    params = init_params(jax.random.PRNGKey(0), dims)
    batches = _device_batches()
    # the exact loss make_train_step differentiates — shared builder
    loss_fn = make_train_loss_fn(
        dims, use_sampled_softmax=True, num_sampled=NUM_SAMPLED,
        compute_dtype=jnp.bfloat16,
        use_pallas=jax.default_backend() == "tpu")
    grad_fn = jax.jit(jax.value_and_grad(loss_fn))

    def chain(n, rng):
        # keys pre-split OUTSIDE the timed region: each jax.random.split
        # is its own dispatch — splitting in the loop would add
        # per-step dispatch overhead the slope can't cancel.
        rng, sub = jax.random.split(rng)
        keys = list(jax.random.split(sub, max(n, 1)))
        t0 = time.perf_counter()
        for i in range(n):
            loss, _g = grad_fn(params, batches[i % len(batches)],
                               keys[i])
        float(loss)
        return time.perf_counter() - t0, rng

    dt = _slope_time(chain, jax.random.PRNGKey(3))
    return BATCH * MAX_CONTEXTS / dt


def _measure_sparse_update_phase():
    """Slope-time the sparse table-update apply ALONE (dedup +
    segment-sum + live-row row-Adam over the three tables — the
    training/sparse_update facade exactly as the sparse train step runs
    it: fused Pallas live-row kernel on TPU, XLA reference elsewhere)
    plus the analytic [U, E] bytes one apply must move, so the phase is
    attributed against the streaming ceiling every round. Returns
    (ms, bytes, unique_rows, fused?)."""
    import functools

    import jax
    import jax.numpy as jnp

    from code2vec_tpu.models.encoder import init_params
    from code2vec_tpu.training.sparse_adam import init_row_adam
    from code2vec_tpu.training.sparse_update import \
        sparse_update_traffic_bytes

    dims = _java_large_dims()
    params = init_params(jax.random.PRNGKey(0), dims)
    batch = _device_batches(1)[0]
    labels, src, pth, dst, _mask, _w = batch
    fused = jax.default_backend() == "tpu"

    # the exact id/cotangent layout the sparse step feeds the facade
    # (target rows are code-vector-wide, not E-wide)
    r = np.random.default_rng(5)
    sampled = jnp.asarray(
        r.integers(0, TARGET_VOCAB, NUM_SAMPLED), jnp.int32)
    table_ids = {
        "token_emb": jnp.concatenate([src.reshape(-1),
                                      dst.reshape(-1)]),
        "path_emb": pth.reshape(-1),
        "target_emb": jnp.concatenate([labels, sampled]),
    }
    grads = {k: jnp.asarray(
        r.normal(size=(int(v.size), params[k].shape[-1])) * 1e-3,
        jnp.bfloat16)
        for k, v in table_ids.items()}
    tables = {k: params[k] for k in table_ids}
    states = {k: init_row_adam(params[k]) for k in table_ids}

    unique_rows = {k: int(np.unique(np.asarray(v)).size)
                   for k, v in table_ids.items()}
    nbytes = sum(
        sparse_update_traffic_bytes(tables[k], int(v.size),
                                    unique_rows[k], grad_itemsize=2)
        for k, v in table_ids.items())

    from code2vec_tpu.training.sparse_update import sparse_row_adam

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def apply(tables, states, count):
        new_t, new_s = {}, {}
        for k in sorted(tables):
            new_t[k], new_s[k] = sparse_row_adam(
                tables[k], states[k], table_ids[k], grads[k],
                count=count, lr=1e-3, fused=fused)
        return new_t, new_s, count + 1

    def chain(n, state):
        tables, states, count = state
        t0 = time.perf_counter()
        for _ in range(n):
            tables, states, count = apply(tables, states, count)
        # hard sync via a scalar host transfer (slope-timing contract)
        float(tables["path_emb"].ravel()[0])
        return time.perf_counter() - t0, (tables, states, count)

    dt = max(_slope_time(chain, (tables, states,
                                 jnp.asarray(1, jnp.int32))), 1e-9)
    return dt * 1e3, nbytes, sum(unique_rows.values()), fused


def _measure_sparse_step():
    """The full sparse-update train step (make_train_step's sparse
    dispatch — gathered-row diff + dedup/segment-sum/live-row apply,
    bf16 tables, row-Adam): the config ROADMAP item 1 aims at the old
    8.48M fwd/bwd floor with. Returns (pc/s, ms, hbm_gbps,
    floor_bytes) — hbm_gbps uses the [U, E]-aware analytic traffic
    model (sparse_update.sparse_step_floor_bytes), NOT the dense
    _step_hbm_bytes, whose [V, E] carrier + full-table walk this step
    does not perform; the caller derives the corrected floor from
    floor_bytes over the measured ceiling."""
    import jax
    import jax.numpy as jnp
    import optax

    from code2vec_tpu.models.encoder import init_params
    from code2vec_tpu.training.sparse_steps import init_sparse_opt_state
    from code2vec_tpu.training.sparse_update import \
        sparse_step_floor_bytes
    from code2vec_tpu.training.steps import make_train_step

    dims = _java_large_dims()
    params = init_params(jax.random.PRNGKey(0), dims)
    dense_opt = optax.adam(1e-3)
    opt_state = init_sparse_opt_state(params, dense_opt, True)
    step = make_train_step(dims, dense_opt, use_sampled_softmax=True,
                           num_sampled=NUM_SAMPLED,
                           compute_dtype=jnp.bfloat16,
                           use_pallas=jax.default_backend() == "tpu",
                           sparse_updates=True, learning_rate=1e-3)
    floor_bytes = sparse_step_floor_bytes(params, BATCH, MAX_CONTEXTS,
                                          num_sampled=NUM_SAMPLED)
    batches = _device_batches()

    def chain(n, state):
        params, opt_state, rng = state
        rng, sub = jax.random.split(rng)
        keys = list(jax.random.split(sub, max(n, 1)))
        t0 = time.perf_counter()
        for i in range(n):
            params, opt_state, loss = step(params, opt_state,
                                           batches[i % len(batches)],
                                           keys[i])
        float(loss)
        return time.perf_counter() - t0, (params, opt_state, rng)

    dt = _slope_time(chain, (params, opt_state, jax.random.PRNGKey(2)))
    return (BATCH * MAX_CONTEXTS / dt, dt * 1e3,
            floor_bytes / dt / 1e9, floor_bytes)


def _measure_requant_phase():
    """Slope-time the int8 requantize apply ALONE over the two
    quantized tables (the fused Pallas row-pass on TPU, the XLA
    reference elsewhere — ops/quant.requantize's auto-select, i.e. the
    exact code the train step runs) plus the analytic bytes one fused
    sweep must move, so the phase is attributed against the streaming
    ceiling every round instead of once per profiling session
    (VERDICT r5 weak #2). Returns (ms, bytes, fused?)."""
    import functools

    import jax
    import jax.numpy as jnp

    from code2vec_tpu.models.encoder import init_params
    from code2vec_tpu.ops.pallas_requant import requant_traffic_bytes
    from code2vec_tpu.ops.quant import is_quantized, requantize

    dims = _java_large_dims("bag", tables_dtype="int8")
    params = init_params(jax.random.PRNGKey(0), dims)
    qkeys = sorted(k for k in params if is_quantized(params[k]))
    # the optimizer's table output is a bf16 [V, E] update (carrier
    # grads are bf16); a fixed sub-quantum magnitude keeps q stable
    updates = {k: jnp.full(params[k]["q"].shape, 1e-5, jnp.bfloat16)
               for k in qkeys}
    nbytes = sum(requant_traffic_bytes(params[k], updates[k])
                 for k in qkeys)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def apply(tables, rng):
        rng, *qrngs = jax.random.split(rng, 1 + len(qkeys))
        new = {k: requantize(tables[k], updates[k], r)
               for k, r in zip(qkeys, qrngs)}
        return new, rng

    def chain(n, state):
        tables, rng = state
        t0 = time.perf_counter()
        for _ in range(n):
            tables, rng = apply(tables, rng)
        # hard sync via a scalar host transfer (slope-timing contract)
        float(tables[qkeys[0]]["s"].ravel()[0])
        return time.perf_counter() - t0, (tables, rng)

    tables0 = {k: params[k] for k in qkeys}
    dt = max(_slope_time(chain, (tables0, jax.random.PRNGKey(7))), 1e-9)
    return dt * 1e3, nbytes, jax.default_backend() == "tpu"


def _measure_encoder(encoder_type: str, tables_dtype: str = "bfloat16",
                     max_contexts: int = MAX_CONTEXTS):
    """Build the shipped train step for one encoder and time it.
    Returns (path_contexts_per_sec, ms_per_step, hbm_gbps)."""
    import jax
    import jax.numpy as jnp

    from code2vec_tpu.models.encoder import init_params
    from code2vec_tpu.ops.quant import opt_param_view
    from code2vec_tpu.training.optimizers import make_optimizer
    from code2vec_tpu.training.steps import make_train_step

    dims = _java_large_dims(encoder_type, tables_dtype, max_contexts)
    params = init_params(jax.random.PRNGKey(0), dims)
    optimizer = make_optimizer(1e-3)  # shipped default: adafactor tables
    # int8 tables: the optimizer sees the flat [V, E] view (shared
    # helper so the structure can't drift from the model's)
    opt_state = optimizer.init(opt_param_view(params))
    hbm_bytes = _step_hbm_bytes(params, opt_state)
    step = make_train_step(dims, optimizer, use_sampled_softmax=True,
                           num_sampled=NUM_SAMPLED,
                           compute_dtype=jnp.bfloat16,
                           use_pallas=jax.default_backend() == "tpu")
    batches = _device_batches(max_contexts=max_contexts)

    def chain(n, state):
        """Run n chained steps; the donated-params chain serializes
        them, so the final host transfer bounds the full computation.
        RNG keys are pre-split outside the timed region (a split per
        step would add a second dispatch per iteration — overhead the
        slope cannot cancel)."""
        params, opt_state, rng = state
        rng, sub = jax.random.split(rng)
        keys = list(jax.random.split(sub, max(n, 1)))
        t0 = time.perf_counter()
        for i in range(n):
            params, opt_state, loss = step(params, opt_state,
                                           batches[i % len(batches)],
                                           keys[i])
        float(loss)
        return time.perf_counter() - t0, (params, opt_state, rng)

    dt = _slope_time(chain, (params, opt_state, jax.random.PRNGKey(1)))
    pc_per_sec = BATCH * max_contexts / dt
    return pc_per_sec, dt * 1e3, hbm_bytes / dt / 1e9


def main(argv=None) -> None:
    # argv=None (programmatic / test callers) means "no flags", NOT
    # sys.argv — the CLI entry below passes sys.argv[1:] explicitly.
    ap = argparse.ArgumentParser(description="one-chip java-large "
                                             "throughput benchmark")
    ap.add_argument("--telemetry_dir", default=None,
                    help="also emit the measurements as telemetry "
                         "events (code2vec_tpu/obs): BENCH rounds and "
                         "train runs share one JSONL format")
    ap.add_argument("--metrics_port", type=int, default=0,
                    help="serve /metrics //healthz //vars while the "
                         "benchmark runs (phase results appear as "
                         "bench/* gauges the moment each phase "
                         "lands); 0 = off")
    args = ap.parse_args(argv if argv is not None else [])
    from code2vec_tpu.device import enable_compile_cache
    from code2vec_tpu.obs import MetricsServer, Telemetry
    enable_compile_cache()
    if args.telemetry_dir:
        tele = Telemetry.create(args.telemetry_dir, component="bench")
    elif args.metrics_port:
        # live scrape without persistence: the registry lives in
        # memory, /metrics serves it
        tele = Telemetry.memory("bench")
    else:
        tele = Telemetry.disabled()
    metrics_server = MetricsServer.create(
        tele.make_threadsafe() if tele.enabled else tele,
        port=args.metrics_port)
    metrics_server.start()

    def _live(**kv) -> None:
        # publish each phase's numbers the moment they land, so a
        # scraper watching --metrics_port sees progress mid-benchmark
        # (static: phase results are set-once facts, not heartbeats —
        # they must not read as stale while later phases run)
        for k, v in kv.items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                tele.gauge(f"bench/{k}", v, emit=False, static=True)

    ceiling = _measure_hbm_ceiling()
    _live(hbm_ceiling_gbps=ceiling / 1e9, phases_done=1)
    value, ms, hbm_gbps = _measure_encoder("bag")
    _live(value=value, ms_per_step=ms, hbm_gbps=hbm_gbps,
          phases_done=2)
    floor = _measure_fwd_bwd_floor()
    _live(fwd_bwd_floor_pc_per_sec=floor, phases_done=3)
    i8_value, i8_ms, i8_hbm = _measure_encoder("bag", tables_dtype="int8")
    _live(int8_pc_per_sec=i8_value, int8_ms_per_step=i8_ms,
          phases_done=4)
    rq_ms, rq_bytes, rq_fused = _measure_requant_phase()
    rq_gbps = rq_bytes / (rq_ms / 1e3) / 1e9
    _live(int8_requant_ms=rq_ms, phases_done=5)
    sp_value, sp_ms, sp_hbm, sp_floor_bytes = _measure_sparse_step()
    sp_floor = BATCH * MAX_CONTEXTS / (sp_floor_bytes / ceiling)
    _live(sparse_pc_per_sec=sp_value, sparse_ms_per_step=sp_ms,
          phases_done=6)
    su_ms, su_bytes, su_rows, su_fused = _measure_sparse_update_phase()
    su_gbps = su_bytes / (su_ms / 1e3) / 1e9
    _live(sparse_update_ms=su_ms, phases_done=7)
    xf_value, xf_ms, xf_hbm = _measure_encoder("transformer")
    _live(transformer_pc_per_sec=xf_value,
          transformer_ms_per_step=xf_ms, phases_done=8)
    result = {
        "metric": "path-contexts/sec/chip",
        "value": round(value, 1),
        "unit": "path-contexts/sec/chip (java-large, sampled softmax, "
                "batch 1024, bf16 compute + bf16 tables, adafactor "
                "tables)",
        "vs_baseline": round(value / V100_BASELINE_PATH_CONTEXTS_PER_SEC,
                             3),
        "baseline_denominator": V100_BASELINE_PATH_CONTEXTS_PER_SEC,
        "baseline_band": V100_BASELINE_BAND,
        "baseline_methodology": "measured-anchored V100 estimate "
                                "(tools/v100_roofline.py + "
                                "tools/tf_baseline.py; BASELINE.md)",
        "vs_baseline_band": [
            round(value / V100_BASELINE_BAND[1], 3),
            round(value / V100_BASELINE_BAND[0], 3)],
        "ms_per_step": round(ms, 2),
        "hbm_gbps": round(hbm_gbps, 1),
        "hbm_ceiling_gbps": round(ceiling / 1e9, 1),
        "hbm_utilization": round(hbm_gbps / (ceiling / 1e9), 3),
        # zero-cost-optimizer ceiling of this config (fwd+bwd only):
        # the step is backward-scatter-bound, so value/floor close to 1
        # means the optimizer is no longer the lever (BASELINE.md)
        "fwd_bwd_floor_pc_per_sec": round(floor, 1),
        "optimizer_efficiency": round(value / floor, 3),
        # sub-bf16 lever (ops/quant.py): int8 token/path tables +
        # per-row scales, stochastic-rounding requantize
        "int8_pc_per_sec": round(i8_value, 1),
        "int8_ms_per_step": round(i8_ms, 2),
        "int8_vs_baseline": round(
            i8_value / V100_BASELINE_PATH_CONTEXTS_PER_SEC, 3),
        # int8 analytic-traffic bandwidth (quantized-carrier-aware
        # _step_hbm_bytes) + the requantize phase attributed against
        # the streaming ceiling: requant_ms at the floor (_floor_ms =
        # one fused sweep's bytes / ceiling) means the memory lever is
        # speed-neutral; the round-5 unfused phase ran ~9.7 ms
        "int8_hbm_gbps": round(i8_hbm, 1),
        "int8_requant_ms": round(rq_ms, 3),
        "int8_requant_bytes": int(rq_bytes),
        "int8_requant_gbps": round(rq_gbps, 1),
        "int8_requant_floor_ms": round(rq_bytes / ceiling * 1e3, 3),
        "int8_requant_vs_ceiling": round(rq_gbps / (ceiling / 1e9), 3),
        "int8_requant_fused": rq_fused,
        # sparse table-update lever (ROADMAP item 1, round 13): the
        # carrier-free step (--sparse_embeddings, bf16 tables,
        # row-Adam) + the dedup/segment-sum/live-row phase attributed
        # alone. sparse_step_floor_pc_per_sec is the CORRECTED analytic
        # floor counting [U, E] traffic (sparse_update.
        # sparse_step_floor_bytes) instead of the dense [V, E] carrier
        # + full-table walk; the acceptance story is sparse_pc_per_sec
        # punching through the old measured fwd_bwd floor above while
        # sparse_optimizer_efficiency (vs that OLD floor) exceeds 0.9.
        "sparse_pc_per_sec": round(sp_value, 1),
        "sparse_ms_per_step": round(sp_ms, 2),
        "sparse_hbm_gbps": round(sp_hbm, 1),
        "sparse_vs_baseline": round(
            sp_value / V100_BASELINE_PATH_CONTEXTS_PER_SEC, 3),
        "sparse_step_floor_pc_per_sec": round(sp_floor, 1),
        "sparse_optimizer_efficiency": round(sp_value / floor, 3),
        "sparse_update_ms": round(su_ms, 3),
        "sparse_update_bytes": int(su_bytes),
        "sparse_update_gbps": round(su_gbps, 1),
        "sparse_update_floor_ms": round(su_bytes / ceiling * 1e3, 3),
        "sparse_update_vs_ceiling": round(
            su_gbps / (ceiling / 1e9), 3),
        "sparse_update_unique_rows": int(su_rows),
        "sparse_update_fused": su_fused,
        "transformer_pc_per_sec": round(xf_value, 1),
        "transformer_ms_per_step": round(xf_ms, 2),
        "transformer_hbm_gbps": round(xf_hbm, 1),
        "transformer_vs_baseline": round(
            xf_value / V100_BASELINE_PATH_CONTEXTS_PER_SEC, 3),
    }
    if tele.enabled:
        tele.event("bench", **result)
        for k, v in result.items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                tele.gauge(f"bench/{k}", v, emit=False)
    metrics_server.stop()
    if tele.enabled:
        tele.close()
    print(json.dumps(result))


if __name__ == "__main__":
    main(sys.argv[1:])
