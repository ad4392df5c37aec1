"""Train step with sparse-row embedding updates (the TPU fast path).

Same math as training/steps.make_train_step, restructured so the three
vocab tables are differentiated at the GATHERED-ROW level: the gathers
happen outside the differentiated function, autodiff produces cotangents
for the gathered [rows, E] arrays directly (no dense-table scatter in the
backward pass), and the sparse-update facade
(training/sparse_update.py, round 13) dedups + segment-sums those
cotangents into a compact [U, E] gradient and applies touched-rows-only
Adam — no dense [V, E] carrier anywhere, and on int8 {q, s} tables a
requantize-aware row update reusing the ops/pallas_requant dither/absmax
machinery. Dense params (TRANSFORM / ATTENTION — and TARGET_WORDS_VOCAB
when running full softmax, whose logits touch every row anyway) keep
ordinary optax Adam.

Why: BENCH_r05 (git history at a4bf2f7) measures the shipped
dense-path step at 6.66M pc/s/chip against an 8.48M fwd/bwd floor (optimizer efficiency 0.786, HBM at
15.7% of the 637 GB/s ceiling) — the gap IS the dense backward scatter
plus the table-proportional optimizer walk this module avoids. The
round-6 lesson (the fused requantize row-pass turned the int8 +26%
step-time tax into ~0) repeats one level up: `--sparse_update_pallas`
selects the fused Pallas live-row kernel on a single-device TPU and the
XLA segment-sum reference on CPU. Under a mesh (round 14) the SAME
compact path runs inside `shard_map` via
`sparse_update.mesh_sparse_apply` — no dense [V, E] carrier on the
data-parallel path either; bench.py attributes the phase
every round (`sparse_update_*`). The pre-round-6 "45 ms dense" numbers
previously quoted here predate the adafactor default and the bf16
tables — BENCH_r*.json is the trajectory of record.
"""

from __future__ import annotations

from typing import Callable, Dict

import jax
import jax.numpy as jnp
import optax

from code2vec_tpu.models.encoder import ModelDims
from code2vec_tpu.ops.attention import attention_pool
from code2vec_tpu.ops.quant import is_quantized
from code2vec_tpu.ops.sampled_softmax import (
    _log_expected_count, log_uniform_sample)
from code2vec_tpu.training.optimizers import apply_updates
from code2vec_tpu.training.sparse_adam import init_row_adam
from code2vec_tpu.training.sparse_update import (mesh_sparse_apply,
                                                 sparse_requant_adam,
                                                 sparse_row_adam)


def init_sparse_opt_state(params: Dict[str, jax.Array],
                          dense_opt: optax.GradientTransformation,
                          use_sampled_softmax: bool):
    dense_keys = ["transform", "attention"]
    if not use_sampled_softmax:
        dense_keys.append("target_emb")
    dense_params = {k: params[k] for k in dense_keys}
    rows = {"token_emb": init_row_adam(params["token_emb"]),
            "path_emb": init_row_adam(params["path_emb"])}
    if use_sampled_softmax:
        rows["target_emb"] = init_row_adam(params["target_emb"])
    return {"dense": dense_opt.init(dense_params), "rows": rows,
            "count": jnp.zeros((), jnp.int32)}


def _gather_rows(table, ids):
    """Row gather in the dtype autodiff differentiates: plain tables
    as-is; int8 {q, s} dequantize AFTER the gather to bf16 (q*s carries
    <= 8 significant bits — same rationale as ops/quant.quantized_take,
    but no straight-through carrier: the rows themselves are the
    differentiated leaves here)."""
    if is_quantized(table):
        rows = (jnp.take(table["q"], ids, axis=0).astype(jnp.float32)
                * jnp.take(table["s"], ids, axis=0))
        return rows.astype(jnp.bfloat16)
    return jnp.take(table, ids, axis=0)


def prepare_step_inputs(params, batch, rng, *, use_sampled_softmax:
                        bool, num_sampled: int, target_vocab: int):
    """The sparse step's non-differentiated preliminaries + gathers.
    Returns
    `(dense, gathered, ctx)`: the dense-param dict, the gathered-row
    dict autodiff differentiates, and a ctx dict carrying everything
    `make_gathered_loss` and the apply section need (drop_rng, qrngs,
    sampled ids + sampled-softmax corrections)."""
    labels, src, pth, dst, mask, weights = batch
    qkeys = sorted(k for k in ("token_emb", "path_emb")
                   if is_quantized(params[k]))
    drop_rng, sample_rng, *qrngs = jax.random.split(
        rng, 2 + len(qkeys))
    ctx = {"drop_rng": drop_rng, "qrngs": dict(zip(qkeys, qrngs)),
           "labels": labels, "mask": mask, "weights": weights}

    if use_sampled_softmax:
        S, V = num_sampled, target_vocab
        sampled = log_uniform_sample(sample_rng, S, V)            # [S]
        ctx["sampled"] = sampled
        ctx["true_corr"] = _log_expected_count(labels, S, V)      # [B]
        ctx["samp_corr"] = _log_expected_count(sampled, S, V)     # [S]
        ctx["accidental"] = sampled[None, :] == labels[:, None]   # [B,S]

    # ---- gathers OUTSIDE the differentiated function, under the
    # phase names models/encoder.py and ops/sampled_softmax.py give
    # the dense step's ----
    with jax.named_scope("c2v/embed_gather"):
        gathered = {"src_e": _gather_rows(params["token_emb"], src),
                    "pth_e": _gather_rows(params["path_emb"], pth),
                    "dst_e": _gather_rows(params["token_emb"], dst)}
    if use_sampled_softmax:
        with jax.named_scope("c2v/loss"):
            gathered["true_w"] = _gather_rows(params["target_emb"],
                                              labels)
            gathered["samp_w"] = _gather_rows(params["target_emb"],
                                              ctx["sampled"])

    dense_keys = ["transform", "attention"]
    if not use_sampled_softmax:
        dense_keys.append("target_emb")
    dense = {k: params[k] for k in dense_keys}
    return dense, gathered, ctx


def make_gathered_loss(dims: ModelDims, ctx, *, use_sampled_softmax:
                       bool, compute_dtype):
    """`loss_fn(dense, gathered)` over prepare_step_inputs' outputs —
    the exact function the sparse step differentiates (and the phase
    probes' forward/backward prefixes re-run)."""
    V = dims.target_vocab_size
    mask, weights = ctx["mask"], ctx["weights"]

    def loss_fn(dense, gathered):
        with jax.named_scope("c2v/encode"):
            contexts = jnp.concatenate(
                [gathered["src_e"], gathered["pth_e"],
                 gathered["dst_e"]], axis=-1).astype(compute_dtype)
            if dims.dropout_keep_rate < 1.0:
                keep = jax.random.bernoulli(
                    ctx["drop_rng"], dims.dropout_keep_rate,
                    contexts.shape)
                contexts = jnp.where(keep,
                                     contexts / dims.dropout_keep_rate,
                                     0.0)
        with jax.named_scope("c2v/pool"):
            code, _ = attention_pool(contexts, dense["transform"],
                                     dense["attention"], mask)
        with jax.named_scope("c2v/loss"):
            return _gathered_loss(code, dense, gathered)

    def _gathered_loss(code, dense, gathered):
        if use_sampled_softmax:
            true_w = gathered["true_w"].astype(code.dtype)
            samp_w = gathered["samp_w"].astype(code.dtype)
            true_logits = jnp.sum(code * true_w, axis=-1).astype(
                jnp.float32) - ctx["true_corr"]
            samp_logits = (code @ samp_w.T).astype(
                jnp.float32) - ctx["samp_corr"][None, :]
            samp_logits = jnp.where(ctx["accidental"], -1e9,
                                    samp_logits)
            logits = jnp.concatenate(
                [true_logits[:, None], samp_logits], axis=1)
            per_ex = -jax.nn.log_softmax(logits, axis=-1)[:, 0]
        else:
            table = dense["target_emb"].astype(code.dtype)
            logits = (code @ table.T).astype(jnp.float32)
            col = jnp.arange(table.shape[0])
            logits = jnp.where(col[None, :] < V, logits, -1e9)
            per_ex = optax.softmax_cross_entropy_with_integer_labels(
                logits, ctx["labels"])
        denom = jnp.maximum(jnp.sum(weights), 1.0)
        return jnp.sum(per_ex * weights) / denom

    return loss_fn


def make_sparse_train_step(dims: ModelDims, *, learning_rate: float,
                           dense_optimizer: optax.GradientTransformation
                           | None = None,
                           use_sampled_softmax: bool = False,
                           num_sampled: int = 4096,
                           compute_dtype=jnp.float32,
                           use_pallas: bool = False,
                           b1: float = 0.9, b2: float = 0.999,
                           eps: float = 1e-8,
                           sparse_update_fused=None,
                           sparse_block_rows: int | None = None,
                           mesh=None) -> Callable:
    """Returns jitted `step(params, opt_state, batch, rng) ->
    (params, opt_state, loss)`; opt_state from init_sparse_opt_state.

    `dense_optimizer` must be the SAME transformation passed to
    init_sparse_opt_state (single source of truth for the dense-param
    hyperparameters); `learning_rate`/`b1`/`b2`/`eps` govern only the
    row-sparse table updates and should match it. `sparse_update_fused`
    selects the live-row implementation on single-device runs AND
    under a mesh (sparse_update facade: None = Pallas kernel on TPU,
    XLA reference on CPU — the mesh path runs it per device inside
    shard_map's manual region, so SPARSE_UPDATE_PALLAS is honored
    everywhere).

    Mesh runs (round 14) use `mesh_sparse_apply`: the compact
    dedup/segment-sum composition MISCOMPILES when the GSPMD
    partitioner shards its inputs (measured, round 13 — wrong segment
    sums), so the whole dedup + apply runs inside `shard_map` where
    the partitioner never sees it, fed by an all-gather of the
    per-occurrence [N]/[N, E] cotangents (NOT a [V, E] carrier).
    Sharded INPUTS into a step built with mesh=None still hit the
    miscompile: callers must pass the mesh they shard with."""
    dense_opt = dense_optimizer if dense_optimizer is not None else \
        optax.adam(learning_rate, b1=b1, b2=b2, eps=eps)
    S = min(num_sampled, dims.target_vocab_size)
    V = dims.target_vocab_size

    def step_impl(params, opt_state, batch, rng):
        labels, src, pth, dst, mask, weights = batch
        dense, gathered, ctx = prepare_step_inputs(
            params, batch, rng, use_sampled_softmax=use_sampled_softmax,
            num_sampled=S, target_vocab=V)
        qrngs = ctx["qrngs"]
        sampled = ctx.get("sampled")
        loss_fn = make_gathered_loss(
            dims, ctx, use_sampled_softmax=use_sampled_softmax,
            compute_dtype=compute_dtype)

        loss, (g_dense, g_rows) = jax.value_and_grad(
            loss_fn, argnums=(0, 1))(dense, gathered)

        count = opt_state["count"] + 1

        # ---- dense params: ordinary Adam ----
        with jax.named_scope("c2v/dense_apply"):
            updates, dense_state = dense_opt.update(
                g_dense, opt_state["dense"], dense)
        dense = apply_updates(dense, updates)

        # ---- tables: dedup + segment-sum + live-rows-only update
        # (training/sparse_update.py — no dense [V, E] carrier) ----
        E = dims.embeddings_size

        def apply_rows(key, parts):
            """`parts` = [(ids, grads, sharded), ...] in the SAME order
            the single-device path concatenates them — mesh_sparse_apply
            all-gathers + concatenates in this order, which is what
            makes mesh-vs-single-device parity bit-exact."""
            with jax.named_scope("c2v/table_apply"):
                table, state = params[key], opt_state["rows"][key]
                kw = dict(count=count, lr=learning_rate, b1=b1, b2=b2,
                          eps=eps, fused=sparse_update_fused,
                          block_rows=sparse_block_rows)
                if mesh is not None:
                    return mesh_sparse_apply(mesh, table, state, parts,
                                             rng=qrngs.get(key), **kw)
                ids = jnp.concatenate([i.reshape(-1) for i, _g, _s in parts])
                grads = jnp.concatenate(
                    [g.reshape(i.reshape(-1).shape[0], -1)
                     for i, g, _s in parts])
                if is_quantized(table):
                    return sparse_requant_adam(table, state, ids, grads,
                                               qrngs[key], **kw)
                return sparse_row_adam(table, state, ids, grads, **kw)

        new_tok, tok_state = apply_rows(
            "token_emb", [(src, g_rows["src_e"].reshape(-1, E), True),
                          (dst, g_rows["dst_e"].reshape(-1, E), True)])
        new_pth, pth_state = apply_rows(
            "path_emb", [(pth, g_rows["pth_e"].reshape(-1, E), True)])

        new_params = dict(params)
        new_params["token_emb"] = new_tok
        new_params["path_emb"] = new_pth
        new_params["transform"] = dense["transform"]
        new_params["attention"] = dense["attention"]
        new_rows = {"token_emb": tok_state, "path_emb": pth_state}
        if use_sampled_softmax:
            D = dims.code_vector_size
            # labels ride the batch axes; the shared sample is
            # replicated on every device (same rng) — no gather needed
            new_tgt, tgt_state = apply_rows(
                "target_emb",
                [(labels, g_rows["true_w"].reshape(-1, D), True),
                 (sampled, g_rows["samp_w"].reshape(-1, D), False)])
            new_params["target_emb"] = new_tgt
            new_rows["target_emb"] = tgt_state
        else:
            new_params["target_emb"] = dense["target_emb"]

        new_opt_state = {"dense": dense_state, "rows": new_rows,
                         "count": count}
        return new_params, new_opt_state, loss

    return jax.jit(step_impl, donate_argnums=(0, 1))
