"""Jit-compiled train / eval / predict steps.

Reference parity target: the three graphs of `tensorflow_model.py`
(SURVEY.md §3: `_build_tf_training_graph`, `_build_tf_testing_graph`,
`_build_tf_predict_graph`) — here they are three pure functions closed
over static ModelDims and jitted once each. Everything inside is
XLA-friendly: static shapes, no data-dependent control flow
(SURVEY.md "XLA semantics").

The same step functions serve single-chip and mesh runs: SPMD sharding is
carried by the INPUTS (params/batch placed with NamedSharding by
parallel/sharding.py), and jit's "computation follows sharding" does the
partitioning — gradient allreduce over 'data' and table-sharded gathers
over 'model' are inserted by XLA, not hand-written collectives.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
import optax

from code2vec_tpu.models.encoder import (ModelDims, full_logits,
                                         get_encode_fn)
from code2vec_tpu.models.registry import spec as encoder_spec
from code2vec_tpu.ops.sampled_softmax import sampled_softmax_loss
from code2vec_tpu.training.optimizers import apply_updates


class TrainBatch(tuple):
    """A training batch's six device arrays, and what the producer's
    staircase check said of the batch they were put from
    (`Code2VecModel._train_device_batch`; data/staircase.py): `fits`,
    by which `make_train_step`'s step picks the program it runs, and
    `gather_slots`, the slots that program takes table rows for (the
    `infeed/transfer` span carries it). To jit it is the plain tuple:
    the marks are the host's."""

    def __new__(cls, arrays, fits: bool, gather_slots: int):
        self = super().__new__(cls, arrays)
        self.fits = fits
        self.gather_slots = gather_slots
        return self


jax.tree_util.register_pytree_node(
    TrainBatch, lambda b: (tuple(b), None), lambda _, arrays: tuple(arrays))


def _weighted_mean(values: jax.Array, weights: jax.Array) -> jax.Array:
    denom = jnp.maximum(jnp.sum(weights), 1.0)
    return jnp.sum(values * weights) / denom


def _make_loss_and_aux_fn(dims: ModelDims, *, use_sampled_softmax: bool,
                          num_sampled: int, compute_dtype,
                          use_pallas: bool, mesh,
                          staircase=None) -> Callable:
    """`fn(params, batch, rng) -> (loss, aux)`, for
    `value_and_grad(..., has_aux=True)`: the training-time loss
    (dropout on, sampled or full softmax) and what the encoder hands
    the step beside it (the encode contract's third value, None for
    most encoders). The one description of the float step's forward.
    `staircase`: `encoder.embed_contexts`."""
    encode = get_encode_fn(dims, mesh)

    def loss_fn(params, batch, rng):
        labels, src, pth, dst, mask, weights = batch
        drop_rng, sample_rng = jax.random.split(rng)
        code, _attn, aux = encode(
            params, src, pth, dst, mask, dropout_rng=drop_rng,
            dropout_keep_rate=dims.dropout_keep_rate,
            compute_dtype=compute_dtype, use_pallas=use_pallas,
            staircase=staircase)
        if use_sampled_softmax:
            loss, _ = sampled_softmax_loss(
                params["target_emb"], code, labels, sample_rng,
                num_sampled, example_weights=weights,
                vocab_size=dims.target_vocab_size)
        else:
            with jax.named_scope("c2v/loss"):
                logits = full_logits(params, code,
                                     dims.target_vocab_size)
                ce = optax.softmax_cross_entropy_with_integer_labels(
                    logits, labels)
                loss = _weighted_mean(ce, weights)
        return loss, aux

    return loss_fn


def make_train_loss_fn(dims: ModelDims, *,
                       use_sampled_softmax: bool = False,
                       num_sampled: int = 4096,
                       compute_dtype=jnp.float32,
                       use_pallas: bool = False,
                       mesh=None) -> Callable:
    """`loss_fn(params, batch, rng)`: the first value of the function
    the float step differentiates (`_make_loss_and_aux_fn`, no
    staircase). The int8 step differentiates this; the measuring tools
    (bench.py, tools/*_profile.py) time it."""
    loss_and_aux = _make_loss_and_aux_fn(
        dims, use_sampled_softmax=use_sampled_softmax,
        num_sampled=num_sampled, compute_dtype=compute_dtype,
        use_pallas=use_pallas, mesh=mesh)
    return lambda params, batch, rng: loss_and_aux(params, batch, rng)[0]


def make_train_step(dims: ModelDims, optimizer: optax.GradientTransformation,
                    *, use_sampled_softmax: bool = False,
                    num_sampled: int = 4096,
                    compute_dtype=jnp.float32,
                    use_pallas: bool = False,
                    mesh=None,
                    augment_fn: Callable = None,
                    requant_fused: bool = None,
                    sparse_updates: bool = False,
                    learning_rate: float | None = None,
                    sparse_update_fused=None,
                    sparse_block_rows: int | None = None,
                    staircase=None) -> Callable:
    """Returns jitted `step(params, opt_state, batch, rng) ->
    (params, opt_state, loss)` where batch is a 6-tuple of arrays
    (labels [B], src/path/dst ids [B, C], mask [B, C],
    example_weights [B]). `augment_fn(batch, rng) -> batch` is an
    optional train-only input transform (the --adv_rename_prob
    adversarial-training defense, attacks/defense.py); it runs inside
    the jit, before the loss. `requant_fused` selects the int8 tables'
    requantize implementation (ops/quant.requantize: None = fused
    Pallas row-pass on single-device TPU, XLA reference elsewhere —
    incl. under a mesh, where the kernel-in-GSPMD composition is
    unexercised); ignored for float/bf16 tables.

    `sparse_updates=True` (Config.SPARSE_EMBEDDING_UPDATES) dispatches
    to training/sparse_steps.make_sparse_train_step — gathered-row
    differentiation + the dedup/segment-sum/live-row facade
    (training/sparse_update.py), with `sparse_update_fused` /
    `sparse_block_rows` (Config.SPARSE_UPDATE_PALLAS) selecting the
    Pallas live-row kernel vs the XLA reference; opt_state must then
    come from sparse_steps.init_sparse_opt_state and `learning_rate`
    names the tables' row-Adam LR.

    With a `staircase` (data/staircase.py; the float step only: the
    int8 and sparse steps take none) the returned step holds two
    programs and runs, batch by batch, the one the producer's check
    chose: the step whose embedding gather and scatter stop at the
    staircase for a `TrainBatch` that `fits`, this step as it always
    was for every other batch (a plain tuple among them). Each is
    compiled when first run; `.lower` is the full step's.

    An encoder whose `encode` hands the step an `aux` (the registry's
    spec names its recorder) gets the step behind `_recording`: same
    contract, the recorder on the step's `route_recorder`."""
    if sparse_updates:
        assert augment_fn is None, (
            "sparse_updates has no augmentation hook "
            "(Config.verify gates --adv_rename_prob)")
        assert learning_rate is not None, (
            "sparse_updates needs the tables' learning_rate")
        from code2vec_tpu.training.sparse_steps import \
            make_sparse_train_step
        return make_sparse_train_step(
            dims, learning_rate=learning_rate,
            dense_optimizer=optimizer,
            use_sampled_softmax=use_sampled_softmax,
            num_sampled=num_sampled, compute_dtype=compute_dtype,
            use_pallas=use_pallas,
            sparse_update_fused=sparse_update_fused,
            sparse_block_rows=sparse_block_rows, mesh=mesh)

    loss_kw = dict(use_sampled_softmax=use_sampled_softmax,
                   num_sampled=num_sampled, compute_dtype=compute_dtype,
                   use_pallas=use_pallas, mesh=mesh)
    if dims.tables_dtype == "int8":
        return _make_quantized_train_step(
            optimizer, make_train_loss_fn(dims, **loss_kw), augment_fn,
            requant_fused, mesh)

    def jitted(staircase):
        loss_and_aux = _make_loss_and_aux_fn(dims, staircase=staircase,
                                             **loss_kw)

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def step(params, opt_state, batch, rng):
            if augment_fn is not None:
                # a rename keeps PAD where it was: the staircase holds
                rng, aug_rng = jax.random.split(rng)
                batch = augment_fn(batch, aug_rng)
            (loss, aux), grads = jax.value_and_grad(
                loss_and_aux, has_aux=True)(params, batch, rng)
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = apply_updates(params, updates)
            return params, opt_state, loss if aux is None else (loss, aux)

        return step

    step = jitted(None)
    if staircase is not None:
        step = _by_fit(jitted(staircase), step)
    recorder = encoder_spec(dims.encoder_type).recorder
    return step if recorder is None else _recording(step, recorder())


def _by_fit(staircase_step: Callable, full_step: Callable) -> Callable:
    """One step over two jitted programs, chosen batch by batch by what
    the producer's check left on the batch (`TrainBatch.fits`); both
    get the plain tuple, so each is traced once."""

    def step(params, opt_state, batch, rng):
        run = staircase_step if getattr(batch, "fits", False) else full_step
        return run(params, opt_state, tuple(batch), rng)

    step.lower = full_step.lower
    step.full_step, step.staircase_step = full_step, staircase_step
    return step


def _recording(step: Callable, recorder) -> Callable:
    """A train step whose encoder hands it an `aux`, behind the contract
    of the others, `(params, opt_state, loss)` and `.lower`: the jitted
    step's loss is `(loss, aux)`, and `aux` goes to the spec's recorder
    (obs/route.py: the routed experts' counts, read a step later without
    a wait). The recorder is the returned function's `route_recorder`
    (the name the benchmark reads; the train loop hands it the run's
    tracer and flushes it after its last sync)."""

    def recorded(params, opt_state, batch, rng):
        params, opt_state, (loss, aux) = step(params, opt_state, batch,
                                              rng)
        recorder.push(aux)
        return params, opt_state, loss

    recorded.route_recorder = recorder
    recorded.lower = step.lower
    return recorded


def _make_quantized_train_step(optimizer, loss_fn, augment_fn,
                               requant_fused=None, mesh=None):
    """The int8-tables train step (ops/quant.py; VERDICT r4 item 3).

    Differs from the float step in exactly three ways:
    1. gradients for the quantized tables flow to zero "carriers"
       created inside the step — the straight-through custom_vjp routes
       each table's dense [V, E] cotangent there, and XLA DCEs the
       zeros in the forward, so the carriers cost no HBM traffic beyond
       the scatter-add every table gradient already pays;
    2. the optimizer sees a FLAT gradient view (one [V, E] array per
       table, same keys/structure as the float path), so opt_state
       structure and the multi_transform labels are unchanged;
    3. the apply requantizes: dequant + update + stochastic-rounding
       int8 round-trip per table (ops/quant.requantize — a fused
       Pallas row-pass on TPU, `requant_fused` forces either form),
       instead of optax.apply_updates' dense add.
    """
    from code2vec_tpu.ops.quant import is_quantized, requantize

    if requant_fused is None and mesh is not None:
        # Auto-select stays on the XLA reference under a mesh: the
        # fused kernel inside a GSPMD-partitioned step is unexercised
        # (int8 supports data-parallel meshes only — the tables and
        # their updates replicate, so the reference is exactly the
        # round-5 dryrun-tested path). `--requant_pallas fused` still
        # forces the kernel for anyone measuring that composition.
        requant_fused = False

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, batch, rng):
        if augment_fn is not None:
            rng, aug_rng = jax.random.split(rng)
            batch = augment_fn(batch, aug_rng)
        qkeys = sorted(k for k in params if is_quantized(params[k]))
        rng, loss_rng, *qrngs = jax.random.split(rng, 2 + len(qkeys))

        def lf(carriers, params):
            virt = dict(params)
            for k, c in carriers.items():
                virt[k] = dict(params[k], g=c)
            return loss_fn(virt, batch, loss_rng)

        carriers = {k: jnp.zeros(params[k]["q"].shape, jnp.bfloat16)
                    for k in qkeys}
        loss, (g_tables, g_rest) = jax.value_and_grad(
            lf, argnums=(0, 1), allow_int=True)(carriers, params)
        flat_grads = {k: (g_tables[k] if k in g_tables else g_rest[k])
                      for k in params}
        # optax's factored_rms requires a params arg even when
        # multiply_by_parameter_scale=False (shape-only use); give the
        # quantized tables flat zero stand-ins matching the grad view —
        # their VALUES are never read, so XLA drops the zeros
        flat_params = {k: (carriers[k] if k in carriers else params[k])
                       for k in params}
        updates, opt_state = optimizer.update(flat_grads, opt_state,
                                              flat_params)
        new_params = {}
        for k, qrng in zip(qkeys, qrngs):
            with jax.named_scope("c2v/table_apply"):
                new_params[k] = requantize(params[k], updates[k], qrng,
                                           fused=requant_fused)
        new_params.update(apply_updates(params, updates, skip=qkeys))
        return new_params, opt_state, loss

    return step


def make_eval_step(dims: ModelDims, *, top_k: int = 10,
                   compute_dtype=jnp.float32,
                   use_pallas: bool = False,
                   mesh=None) -> Callable:
    """Returns jitted `step(params, batch) -> (loss_sum, topk_ids,
    topk_probs)`; no dropout (SURVEY.md §4.3)."""
    encode = get_encode_fn(dims, mesh)

    @jax.jit
    def step(params, batch):
        labels, src, pth, dst, mask, weights = batch
        code, _attn, _aux = encode(params, src, pth, dst, mask,
                                   compute_dtype=compute_dtype,
                                   use_pallas=use_pallas)
        logits = full_logits(params, code, dims.target_vocab_size)
        ce = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
        # CE is mathematically >= 0; on TPU the logsumexp-minus-logit
        # difference can come out a hair negative for near-zero-loss
        # examples (different reduction paths), which makes the REPORTED
        # eval loss print as e.g. -0.019 on overfit tiny runs. Clamp —
        # this is an eval-only metric, no gradients flow through it.
        ce = jnp.maximum(ce, 0.0)
        loss_sum = jnp.sum(ce * weights)
        probs = jax.nn.softmax(logits, axis=-1)
        topk_probs, topk_ids = jax.lax.top_k(probs, top_k)
        return loss_sum, topk_ids, topk_probs

    return step


def make_encode_step(dims: ModelDims, *,
                     compute_dtype=jnp.float32,
                     use_pallas: bool = False,
                     mesh=None) -> Callable:
    """Returns jitted `step(params, batch) -> code_vectors [B, D] f32` —
    encoder only, no [B, V] logits matmul. Used by --export_code_vectors
    over a whole test split, where top-k/softmax would be wasted FLOPs."""
    encode = get_encode_fn(dims, mesh)

    @jax.jit
    def step(params, batch):
        _labels, src, pth, dst, mask, _weights = batch
        code, _attn, _aux = encode(params, src, pth, dst, mask,
                                   compute_dtype=compute_dtype,
                                   use_pallas=use_pallas)
        return code.astype(jnp.float32)

    return step


def make_predict_step(dims: ModelDims, *, top_k: int = 10,
                      compute_dtype=jnp.float32,
                      use_pallas: bool = False,
                      mesh=None) -> Callable:
    """Returns jitted `step(params, batch) -> (topk_ids, topk_probs,
    attention, code_vectors)` — the predict graph additionally surfaces
    per-context attention and the code vector (SURVEY.md §4.4,
    interpretability output + --export_code_vectors)."""
    encode = get_encode_fn(dims, mesh)

    @jax.jit
    def step(params, batch):
        _labels, src, pth, dst, mask, _weights = batch
        code, attn, _aux = encode(params, src, pth, dst, mask,
                                  compute_dtype=compute_dtype,
                                  use_pallas=use_pallas)
        logits = full_logits(params, code, dims.target_vocab_size)
        probs = jax.nn.softmax(logits, axis=-1)
        topk_probs, topk_ids = jax.lax.top_k(probs, top_k)
        return topk_ids, topk_probs, attn, code.astype(jnp.float32)

    return step
