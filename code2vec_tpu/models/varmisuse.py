"""VarMisuse head: pointer-style variable-misuse localization/repair.

BASELINE.json configs[3] ("variable-naming / VarMisuse head — reuse path
encoder, new target space"); SURVEY.md §8.3 step 8. The reference has no
such head — this is one of the driver-required stretch configs, built
the TPU-first way on top of the same encoder:

  - A method with one variable occurrence replaced by the special
    `slotvar` token is extracted to path-contexts as usual (the slot's
    contexts carry the syntactic environment of the hole).
  - The method's candidate variables (<= K, padded) are embedded with
    the SAME token table the encoder uses.
  - The code vector q = encode(contexts) queries a bilinear pointer:
        score_k = (q W) . tok_emb[cand_k]  + mask
    softmax over the K candidates, cross-entropy on the true variable.

Everything is static-shape ([B, K] candidates) and jit-compiled; the
head adds ONE [D, E] matrix, so DP/TP sharding rules are unchanged
(pointer matrix replicated like TRANSFORM).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from code2vec_tpu.models.encoder import ModelDims, encode, init_params

SLOT_TOKEN = "slotvar"   # the hole marker; goes through normal
                         # token normalization (already lowercase)

Params = Dict[str, jax.Array]


def init_vm_params(rng: jax.Array, dims: ModelDims) -> Params:
    """Encoder params + the pointer matrix W [D, E]."""
    k_enc, k_ptr = jax.random.split(rng)
    params = init_params(k_enc, dims)
    init = jax.nn.initializers.variance_scaling(1.0, "fan_avg", "uniform")
    params["vm_pointer"] = init(
        k_ptr, (dims.context_vector_size, dims.embeddings_size),
        jnp.float32)
    return params


def vm_scores(params: Params, source_ids: jax.Array, path_ids: jax.Array,
              target_ids: jax.Array, mask: jax.Array,
              cand_ids: jax.Array, cand_mask: jax.Array, *,
              dropout_rng: Optional[jax.Array] = None,
              dropout_keep_rate: float = 1.0,
              compute_dtype=jnp.float32,
              use_pallas: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Candidate scores.

    Args: the usual [B, C] context tensors + [B, K] candidate token ids
    and 0/1 candidate mask. Returns (scores [B, K] f32 with -inf on
    padded candidates, attention [B, C]).
    """
    code, attn, _ = encode(params, source_ids, path_ids, target_ids, mask,
                           dropout_rng=dropout_rng,
                           dropout_keep_rate=dropout_keep_rate,
                           compute_dtype=compute_dtype,
                           use_pallas=use_pallas)
    cand = jnp.take(params["token_emb"], cand_ids, axis=0)  # [B, K, E]
    q = code.astype(jnp.float32) @ params["vm_pointer"]     # [B, E]
    scores = jnp.einsum("be,bke->bk", q,
                        cand.astype(jnp.float32))           # [B, K]
    scores = jnp.where(cand_mask > 0, scores, -1e9)
    return scores, attn


def vm_loss(params: Params, batch, *, dropout_rng=None,
            dropout_keep_rate: float = 1.0, compute_dtype=jnp.float32,
            use_pallas: bool = False) -> jax.Array:
    """Weighted-mean CE over candidates. batch = (labels [B],
    src, pth, dst, mask, cand_ids [B,K], cand_mask [B,K], weights [B])."""
    labels, src, pth, dst, mask, cand_ids, cand_mask, weights = batch
    scores, _ = vm_scores(params, src, pth, dst, mask, cand_ids,
                          cand_mask, dropout_rng=dropout_rng,
                          dropout_keep_rate=dropout_keep_rate,
                          compute_dtype=compute_dtype,
                          use_pallas=use_pallas)
    logp = jax.nn.log_softmax(scores, axis=-1)
    ce = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    denom = jnp.maximum(jnp.sum(weights), 1.0)
    return jnp.sum(ce * weights) / denom
