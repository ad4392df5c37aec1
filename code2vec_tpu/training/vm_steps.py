"""Jitted train/eval steps for the VarMisuse head (models/varmisuse.py).

Same shape discipline as training/steps.py: static shapes, pure
functions, sharding carried by the inputs, donation on the hot path.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
import optax

from code2vec_tpu.models.encoder import ModelDims
from code2vec_tpu.models.varmisuse import vm_loss, vm_scores
from code2vec_tpu.training.optimizers import apply_updates


_VM_TABLE_KEYS = ("token_emb", "path_emb")


def init_vm_sparse_opt_state(params, dense_opt:
                             optax.GradientTransformation):
    """Sparse-row opt state for the vm head: row-Adam moments for the
    two vocab tables, the dense optimizer for everything else — the
    same {dense, rows, count} layout as sparse_steps so checkpoints
    and telemetry read uniformly."""
    from code2vec_tpu.training.sparse_adam import init_row_adam
    dense_params = {k: v for k, v in params.items()
                    if k not in _VM_TABLE_KEYS}
    rows = {k: init_row_adam(params[k]) for k in _VM_TABLE_KEYS}
    return {"dense": dense_opt.init(dense_params), "rows": rows,
            "count": jnp.zeros((), jnp.int32)}


def make_vm_train_step(dims: ModelDims,
                       optimizer: optax.GradientTransformation, *,
                       compute_dtype=jnp.float32,
                       use_pallas: bool = False,
                       sparse_updates: bool = False,
                       learning_rate: float | None = None,
                       sparse_update_fused=None,
                       sparse_block_rows: int | None = None,
                       mesh=None) -> Callable:
    """step(params, opt_state, batch, rng) -> (params, opt_state, loss);
    batch = (labels, src, pth, dst, mask, cand_ids, cand_mask,
    weights).

    `sparse_updates=True` (Config.SPARSE_EMBEDDING_UPDATES): the two
    vocab tables take a live-rows-only row-Adam step through
    training/sparse_update.rows_from_dense instead of riding the dense
    optax walk; opt_state must then come from init_vm_sparse_opt_state.
    The vm loss gathers INSIDE the differentiated function, so autodiff
    still emits the dense [V, E] cotangent — this buys the
    optimizer-walk half of the sparse win (the backward scatter stays
    dense; the code2vec head's sparse_steps path removes that too).
    Precision caveat: that cotangent is accumulated by autodiff's
    scatter-add in the TABLE dtype, so bf16 tables sum duplicate-row
    occurrences in bf16 — identical to what the vm DENSE path feeds
    optax (parity, not a regression), but weaker than the code2vec
    head's f32 segment-sum guarantee; prefer f32 tables when vm
    gradient fidelity matters."""

    def loss_fn(params, batch, rng):
        return vm_loss(params, batch, dropout_rng=rng,
                       dropout_keep_rate=dims.dropout_keep_rate,
                       compute_dtype=compute_dtype, use_pallas=use_pallas)

    if sparse_updates:
        assert learning_rate is not None, (
            "sparse_updates needs the tables' learning_rate")
        if mesh is not None:
            # the id-dedup composition (concat -> unique) miscompiles
            # under GSPMD on the virtual CPU mesh (measured, round 13
            # — see sparse_steps' dense-carrier mesh rule); the vm
            # head has no carrier fallback worth keeping, so gate.
            raise ValueError(
                "--sparse_embeddings on the varmisuse head is "
                "single-device only; drop the flag for mesh runs")
        from code2vec_tpu.training.sparse_update import rows_from_dense
        fused = sparse_update_fused

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def sparse_step(params, opt_state, batch, rng):
            loss, grads = jax.value_and_grad(loss_fn)(params, batch,
                                                      rng)
            count = opt_state["count"] + 1
            dense = {k: v for k, v in params.items()
                     if k not in _VM_TABLE_KEYS}
            g_dense = {k: grads[k] for k in dense}
            updates, dense_state = optimizer.update(
                g_dense, opt_state["dense"], dense)
            new_params = dict(params, **apply_updates(dense, updates))
            # table ids gathered by vm_scores: src/dst/candidate token
            # rows, path rows
            _labels, src, pth, dst, _mask, cand_ids, _cm, _w = batch
            table_ids = {
                "token_emb": jnp.concatenate(
                    [src.reshape(-1), dst.reshape(-1),
                     cand_ids.reshape(-1)]),
                "path_emb": pth.reshape(-1)}
            new_rows = {}
            for k in _VM_TABLE_KEYS:
                with jax.named_scope("c2v/table_apply"):
                    new_params[k], new_rows[k] = rows_from_dense(
                        params[k], opt_state["rows"][k], grads[k],
                        table_ids[k], count=count, lr=learning_rate,
                        fused=fused, block_rows=sparse_block_rows)
            return new_params, {"dense": dense_state,
                                "rows": new_rows,
                                "count": count}, loss

        return sparse_step

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, batch, rng):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch, rng)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = apply_updates(params, updates)
        return params, opt_state, loss

    return step


def make_vm_eval_step(dims: ModelDims, *, compute_dtype=jnp.float32,
                      use_pallas: bool = False) -> Callable:
    """step(params, batch) -> (loss_sum, correct_sum, pred [B]);
    no dropout."""

    @jax.jit
    def step(params, batch):
        labels, src, pth, dst, mask, cand_ids, cand_mask, weights = batch
        scores, _ = vm_scores(params, src, pth, dst, mask, cand_ids,
                              cand_mask, compute_dtype=compute_dtype,
                              use_pallas=use_pallas)
        logp = jax.nn.log_softmax(scores, axis=-1)
        ce = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
        pred = jnp.argmax(scores, axis=-1)
        correct = (pred == labels).astype(jnp.float32)
        return (jnp.sum(ce * weights), jnp.sum(correct * weights), pred)

    return step
