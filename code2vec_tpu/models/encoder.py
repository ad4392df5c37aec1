"""The path-context encoder: the model core as pure functions on a pytree.

Reference parity target: `tensorflow_model.py` forward graph (SURVEY.md §3):
trainable variables WORDS_VOCAB [Vt, 128], PATHS_VOCAB [Vp, 128],
TARGET_WORDS_VOCAB [Vy, 384], TRANSFORM [384, 384], ATTENTION [384, 1];
forward = 3 embedding gathers -> concat(384) -> dropout(keep 0.75) ->
tanh(ctx @ TRANSFORM) -> masked attention softmax over MAX_CONTEXTS ->
weighted sum = code vector -> logits vs TARGET_WORDS_VOCABᵀ.

TPU-first design choices:
- pure-jax param pytree (a flat dict) rather than a framework Module: the
  five arrays are exactly the reference's variables, and explicit pytrees
  make NamedSharding rules trivial (parallel/sharding.py).
- vocab-table row counts are padded up to a multiple of the model-parallel
  mesh axis so tables shard evenly (padding rows are dead: PAD/OOV indices
  are < the true size and the sampler clips to the true vocab size).
- compute dtype is bfloat16 on the MXU (params stay f32; casts at use).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from code2vec_tpu.models.registry import EncoderSpec, spec
from code2vec_tpu.ops.attention import attention_pool

Params = Dict[str, jax.Array]

# `vocab.vocabularies.Vocab` reserves index 0 for PAD in every table
PAD_ID = 0


@dataclasses.dataclass(frozen=True)
class ModelDims:
    """Static model dimensions (hashable: usable as a jit static arg)."""
    token_vocab_size: int
    path_vocab_size: int
    target_vocab_size: int
    embeddings_size: int = 128
    max_contexts: int = 200
    dropout_keep_rate: float = 0.75
    # Row padding so vocab dims divide the 'model' mesh axis evenly.
    vocab_pad_multiple: int = 1
    # Storage dtype of the three vocab tables
    # ("float32" | "bfloat16" | "int8").
    # bf16 tables halve the gather / scatter / optimizer HBM traffic that
    # dominates the java-large step (~30-40% end-to-end, measured on
    # v5e-lite; see BASELINE.md). "int8" (ops/quant.py, VERDICT r4
    # item 3) halves the token/path-table bytes AGAIN: int8 rows +
    # per-row f32 scales, gather-level dequantization,
    # stochastic-rounding requantize in the apply; target_emb stays
    # bf16 (the sampled-softmax head matmuls against it).
    # TRANSFORM/ATTENTION always stay f32.
    tables_dtype: str = "float32"
    # Encoder architecture, a name of models/registry.py: "bag" is the
    # reference's single-query attention pool (this module).
    encoder_type: str = "bag"
    xf_layers: int = 2
    # 3 -> head_dim 384/3 = 128 = one MXU lane width (shipped default,
    # matches Config.XF_HEADS; quality-identical to 4, 9% faster
    # through the fused kernels — BASELINE.md round 4)
    xf_heads: int = 3
    xf_mlp_ratio: int = 4
    # Rematerialize each transformer layer in the backward pass
    # (jax.checkpoint): trades ~30% more FLOPs for O(layers) -> O(1)
    # activation memory — required to fit CodeBERT-depth (12-layer)
    # encoders at B*C activation scale (SURVEY.md "HBM bandwidth" row).
    xf_remat: bool = False
    # Ring attention over the 'ctx' mesh axis (ops/ring_attention.py):
    # K/V stay sharded and rotate via ppermute instead of the XLA
    # all-gather — O(C/s) per-device attention memory for long-context
    # sequence parallelism. Takes effect only when the mesh's ctx axis
    # is > 1 (numerically exact either way).
    ring_attention: bool = False
    # lfm2_moe's own sizes (models/lfm2_moe_encoder.Lfm2Dims)
    lfm: Optional[Any] = None
    # qwen3_next's (models/qwen3_next_encoder.Qwen3NextDims)
    qwen: Optional[Any] = None
    # joyai_flash's (models/joyai_flash_encoder.JoyaiDims)
    joyai: Optional[Any] = None

    @property
    def context_vector_size(self) -> int:
        return 3 * self.embeddings_size

    @property
    def code_vector_size(self) -> int:
        return self.context_vector_size

    def padded(self, n: int) -> int:
        m = self.vocab_pad_multiple
        return ((n + m - 1) // m) * m


def init_params(rng: jax.Array, dims: ModelDims,
                dtype=jnp.float32) -> Params:
    """Variance-scaled init, matching the reference's scheme in spirit
    (TF used glorot-ish initializers on the tables and TRANSFORM).
    The vocab tables are stored in dims.tables_dtype; TRANSFORM and
    ATTENTION stay in `dtype` (f32) for numerics."""
    k_tok, k_path, k_tgt, k_tr, k_at = jax.random.split(rng, 5)
    E = dims.embeddings_size
    D = dims.context_vector_size
    init = jax.nn.initializers.variance_scaling(
        1.0, "fan_avg", "uniform")
    quantized = dims.tables_dtype == "int8"
    t_dtype = jnp.bfloat16 if quantized else jnp.dtype(dims.tables_dtype)
    params = {
        "token_emb": init(k_tok, (dims.padded(dims.token_vocab_size), E),
                          t_dtype),
        "path_emb": init(k_path, (dims.padded(dims.path_vocab_size), E),
                         t_dtype),
        "target_emb": init(k_tgt, (dims.padded(dims.target_vocab_size), D),
                           t_dtype),
        "transform": init(k_tr, (D, D), dtype),
        "attention": init(k_at, (D, 1), dtype)[:, 0],
    }
    if quantized:
        # int8 + per-row scale for the two leaf-token tables;
        # target_emb stays bf16 (ops/quant.py module docstring)
        from code2vec_tpu.ops.quant import (QUANTIZED_TABLE_KEYS,
                                            quantize_table)
        for k in QUANTIZED_TABLE_KEYS:
            params[k] = quantize_table(params[k])
    encoder = spec(dims.encoder_type)
    if encoder.params_key is not None:
        params[encoder.params_key] = encoder.init(rng, dims)
    return params


def take_rows(params: Params, name: str, ids: jax.Array) -> jax.Array:
    """Embedding-row gather that understands the three table storages:
    plain float arrays, {"q","s"} int8 tables (no-grad dequantizing
    gather — eval/predict/serving), and {"q","s","g"} int8 tables with
    a gradient carrier attached by the quantized train step (the
    straight-through custom_vjp gather; ops/quant.py)."""
    t = params[name]
    if isinstance(t, dict):
        if "g" in t:
            from code2vec_tpu.ops.quant import quantized_take
            return quantized_take(t["g"], t, ids)
        # bf16 output, matching quantized_take (int8 rows carry <= 8
        # significant bits; f32 would double the activation traffic)
        return (jnp.take(t["q"], ids, axis=0).astype(jnp.float32)
                * jnp.take(t["s"], ids, axis=0)).astype(jnp.bfloat16)
    # A PAD slot never reads row 0 through the gather: on the chip a
    # million reads of one address cost more than as many reads of
    # distinct rows (PERF.md finding 5). Each PAD slot reads a row of
    # its own and the select puts the PAD row's value back: bit for
    # bit `jnp.take(t, ids, axis=0)`, whatever the caller does with
    # PAD slots afterwards. The backward is autodiff's, row 0 getting
    # the sum of the PAD slots' cotangents (written out with
    # `.at[0].add` it is 1 ms a step faster on one chip and 4 ms slower
    # on four, where the partitioner then all-reduces the token
    # table's two gradients apart; PERF.md, PR 27).
    is_pad, spread = _spread_pad(t.shape[0], ids)
    return jnp.where(is_pad[..., None], t[PAD_ID],
                     jnp.take(t, spread, axis=0))


def _spread_pad(vocab: int, ids: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """`ids == PAD_ID`, and the ids with each PAD slot naming the row
    its own position hashes to (in bounds; Knuth's multiplier, modulo
    2**32 and then the rows): rows scattered over the table's pages
    read a little faster than the consecutive rows of `slot % vocab`
    (PERF.md, PR 27)."""
    is_pad = ids == PAD_ID
    slot = jnp.arange(ids.size, dtype=jnp.uint32).reshape(ids.shape)
    own = (slot * jnp.uint32(2654435761)) % jnp.uint32(vocab)
    return is_pad, jnp.where(is_pad, own.astype(ids.dtype), ids)


def _kept_of_column(rects) -> np.ndarray:
    """int32 [C]: the rows the rectangle over each column keeps."""
    return np.repeat([kept for _, _, kept in rects],
                     [hi - lo for lo, hi, _ in rects]).astype(np.int32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _place(flat: jax.Array, pad_value: jax.Array, rects, rows: int
           ) -> jax.Array:
    """`[rows, C, E]` with `flat`'s rows at the slots of the rectangles
    `rects` (`(first column, end column, rows kept)`, left to right up
    to C; `flat` holds them one after another, each row-major) and
    `pad_value` `[E]` at every slot outside. A rectangle reaches its
    place as a block (filled up to `rows` below, set side by side, one
    select on the slot's row puts `pad_value` outside), never by an
    index, and the backward is written out as what it is: slices of
    the cotangent for `flat`, the sum of the rest for `pad_value`
    (autodiff's own transpose masks the whole cotangent before it
    slices it, one more pass over `[rows, C, E]`)."""
    E = flat.shape[1]
    blocks, start = [], 0
    for lo, hi, kept in rects:
        stop = start + kept * (hi - lo)
        blocks.append(jax.lax.pad(
            flat[start:stop].reshape(kept, hi - lo, E),
            jnp.zeros((), flat.dtype),
            ((0, rows - kept, 0), (0, 0, 0), (0, 0, 0))))
        start = stop
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, rects[-1][1], 1), 0)
    return jnp.where(row < _kept_of_column(rects)[None, :, None],
                     jnp.concatenate(blocks, axis=1), pad_value)


def _place_fwd(flat, pad_value, rects, rows):
    return _place(flat, pad_value, rects, rows), None


def _place_bwd(rects, rows, _, ct):
    row = jax.lax.broadcasted_iota(jnp.int32, ct.shape[:2] + (1,), 0)
    outside = row >= _kept_of_column(rects)[None, :, None]
    return (jnp.concatenate([ct[:kept, lo:hi].reshape(-1, ct.shape[2])
                             for lo, hi, kept in rects]),
            jnp.sum(jnp.where(outside, ct, 0), axis=(0, 1)))


_place.defvjp(_place_fwd, _place_bwd)


def _take_staircase(token_emb: jax.Array, path_emb: jax.Array,
                    source_ids: jax.Array, path_ids: jax.Array,
                    target_ids: jax.Array, stairs):
    """`(token_emb[source], path_emb[path], token_emb[target])`, each
    `[B, C, E]`, for ids that are PAD outside the staircase `stairs`
    (data/staircase.py: rectangles `rows[:kept] x columns[first:next
    first]`): table rows are taken for the rectangles' slots only, one
    flat gather a table, and every slot outside holds the PAD row's
    value, which is what `take_rows` gives a PAD slot. So the values
    are `take_rows`' bit for bit, and the gathers' scatters in the
    backward see the rectangles' rows only (the cotangent of the slots
    outside is summed into row 0, as before)."""
    B, C = path_ids.shape
    firsts = [first for first, _ in stairs] + [C]
    rects = tuple((first, firsts[k + 1], kept)
                  for k, (first, kept) in enumerate(stairs))

    def cut(ids):       # the rectangles' ids, one flat vector
        return [ids[:kept, lo:hi].reshape(-1) for lo, hi, kept in rects]

    tables = {"token_emb": token_emb, "path_emb": path_emb}
    tok = take_rows(tables, "token_emb",
                    jnp.concatenate(cut(source_ids) + cut(target_ids)))
    pth = take_rows(tables, "path_emb", jnp.concatenate(cut(path_ids)))
    src, dst = jnp.split(tok, 2)
    return (_place(src, token_emb[PAD_ID], rects, B),
            _place(pth, path_emb[PAD_ID], rects, B),
            _place(dst, token_emb[PAD_ID], rects, B))


def embed_contexts(params: Params, source_ids: jax.Array,
                   path_ids: jax.Array, target_ids: jax.Array,
                   dropout_rng: Optional[jax.Array],
                   dropout_keep_rate: float, compute_dtype,
                   staircase=None, mesh=None) -> jax.Array:
    """[B, C, 3E] contexts in the compute dtype, dropout applied: what
    every encoder starts from.

    `staircase` (a `data/staircase.Stairs`; training only) says that
    the caller has CHECKED that every id outside its rectangles is PAD:
    `BinaryShardReader.order_by_length` orders a training batch's rows
    by bag length, the producer checks the ordered batch
    (`staircase.fits`) and `training/steps` hands the staircase to the
    step it runs for a batch that fits. Table rows are then taken for
    the rectangles only, and the result is the same array bit for bit.
    Nothing here looks at the ids: with a staircase and other ids the
    slots outside would read as PAD. Evaluation, prediction and serving
    pass none and are never ordered. Over `mesh` each device takes its
    own rows' rectangles (the staircase is a device's)."""
    # the step's phases by name (`c2v/...`): an op's metadata carries
    # the scope path, the backward's as `transpose(jvp(c2v/...))`, so
    # a profile tells the phases apart without reading shapes
    with jax.named_scope("c2v/embed_gather"):
        if staircase is None:
            rows = (take_rows(params, "token_emb", source_ids),
                    take_rows(params, "path_emb", path_ids),
                    take_rows(params, "token_emb", target_ids))
        else:
            take = functools.partial(_take_staircase, stairs=staircase)
            if mesh is not None:
                from code2vec_tpu.parallel.sharding import \
                    shard_map_over_batch
                take = shard_map_over_batch(
                    take, mesh, (False, False, True, True, True))
            rows = take(params["token_emb"], params["path_emb"],
                        source_ids, path_ids, target_ids)

    with jax.named_scope("c2v/encode"):
        contexts = jnp.concatenate(rows, axis=-1).astype(compute_dtype)
        if dropout_rng is not None and dropout_keep_rate < 1.0:
            keep = jax.random.bernoulli(dropout_rng, dropout_keep_rate,
                                        contexts.shape)
            contexts = jnp.where(keep, contexts / dropout_keep_rate, 0.0)
    return contexts


def encode(params: Params, source_ids: jax.Array, path_ids: jax.Array,
           target_ids: jax.Array, mask: jax.Array, *,
           dropout_rng: Optional[jax.Array] = None,
           dropout_keep_rate: float = 1.0,
           compute_dtype=jnp.float32,
           use_pallas: bool = False,
           mesh=None, staircase=None, dims: Optional[ModelDims] = None
           ) -> Tuple[jax.Array, jax.Array, None]:
    """Forward to the code vector: the bag encoder, under the one
    encode contract (registry.EncoderSpec).

    Args: [B, C] int32 ids for source token / path / target token, [B, C]
    f32 mask. Returns (code_vectors [B, D] in compute dtype,
    attention [B, C] f32, None: it hands the step nothing beside the
    loss). use_pallas selects the fused Pallas pooling
    kernel (ops/pallas_attention.py); inside a step partitioned over
    `mesh` each device runs it on its own batch rows. `staircase`:
    `embed_contexts`. `dims` is taken and not read: the shapes say all.
    """
    del dims
    contexts = embed_contexts(params, source_ids, path_ids, target_ids,
                              dropout_rng, dropout_keep_rate, compute_dtype,
                              staircase, mesh)

    with jax.named_scope("c2v/pool"):
        if use_pallas:
            from code2vec_tpu.ops.pallas_attention import \
                attention_pool_fused
            pool = attention_pool_fused
            if mesh is not None:
                from code2vec_tpu.parallel.sharding import \
                    shard_map_over_batch
                pool = shard_map_over_batch(pool, mesh,
                                            (True, False, False, True))
            code, attn = pool(contexts, params["transform"],
                              params["attention"], mask)
            return code.astype(compute_dtype), attn, None
        return (*attention_pool(contexts, params["transform"],
                                params["attention"], mask), None)


def get_encode_fn(dims: ModelDims, mesh=None):
    """`dims.encoder_type`'s `encode` (registry.EncoderSpec) with
    `dims` and `mesh` bound; the jitted steps in training/steps.py
    close over it. `mesh` places the Pallas kernels per device (and
    feeds the transformer's ring-attention path: dims.ring_attention
    with a ctx axis > 1)."""
    return functools.partial(spec(dims.encoder_type).encode, dims=dims,
                             mesh=mesh)


def logits_vs_table(table: jax.Array, code_vectors: jax.Array,
                    true_target_vocab_size: Optional[int] = None
                    ) -> jax.Array:
    """[B, V] logits against a (possibly row-padded) target table.
    Padding rows are masked to -inf so they never win top-k."""
    table = table.astype(code_vectors.dtype)
    logits = (code_vectors @ table.T).astype(jnp.float32)
    if (true_target_vocab_size is not None
            and true_target_vocab_size < table.shape[0]):
        col = jnp.arange(table.shape[0])
        logits = jnp.where(col[None, :] < true_target_vocab_size,
                           logits, -1e9)
    return logits


def full_logits(params: Params, code_vectors: jax.Array,
                true_target_vocab_size: Optional[int] = None) -> jax.Array:
    return logits_vs_table(params["target_emb"], code_vectors,
                           true_target_vocab_size)


SPEC = EncoderSpec(encode=encode, table_step_variants=True)
