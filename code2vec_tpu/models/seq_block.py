"""What the decoder-block encoders share (`lfm2_moe_encoder.py`,
`qwen3_next_encoder.py`, `joyai_flash_encoder.py`): a stack of residual layers run over a method's
path-contexts in reader order, position = slot index, valid contexts
filling from the left. `x` is [B, C, H], `m` the context mask.

  input    x = (c W_in) m                 3E -> H; masked slots enter as
                                          zeros (`run_block`)
  layer    x = x + Mixer(norm(x)) ; x = x + FF(norm(x))
                                          `residual_layer`: rematerialised,
                                          under `c2v/blk_<i>/...` scopes
  output   norm ; the product's learned-query pool over valid slots at
           width H ; code = pooled W_out2          H -> 3E (`run_block`)

and the operators more than one block has: rotary over the whole head
or its first part (`rotary`), the masked causal softmax
(`causal_softmax`) under the two softmax mixers, causal grouped-query
attention with the head's width, the q/k norm and an optional output
gate as arguments (`attention`) and multi-head latent attention
(`latent_attention`: norms on two low-rank latents, a key of two parts
of which one is a single rotary head shared by every query head), the
SwiGLU (`swiglu`), the sigmoid routers' fixed selection bias
(`BIAS_SCALE`), and the routed experts' wrapper that makes the counts
which leave the step (`routed_experts`). Which norm, which mixers and
which router a block has is its own module's.
"""

from __future__ import annotations

import math
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from code2vec_tpu.models.transformer_encoder import (learned_query_pool,
                                                     padding_log_mask)
from code2vec_tpu.ops.moe import held_experts_ffn, ran_at_bound

# the sigmoid routers' selection bias is a seeded buffer of this scale
# x normal, held fixed: small beside the gaps between a token's top
# scores (about 0.016 between the fourth and the fifth of 64), it turns
# near-ties and leaves the load on the experts even, as the trained
# buffer's job is
BIAS_SCALE = 0.005


def rotary(x: jax.Array, theta: float,
           turned: Optional[int] = None) -> jax.Array:
    """x [B, heads, C, hd], position = index along C; the first `turned`
    of each head turn (None: the whole head), pairs (i, i + turned/2)
    (rotate-half); the rest pass as they are."""
    hd = x.shape[-1]
    if turned is not None and turned < hd:
        return jnp.concatenate(
            [rotary(x[..., :turned], theta), x[..., turned:]], axis=-1)
    C = x.shape[-2]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    angle = jnp.arange(C, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)
    x32 = x.astype(jnp.float32)
    half = jnp.concatenate([-x32[..., hd // 2:], x32[..., :hd // 2]], -1)
    return (x32 * cos + half * sin).astype(x.dtype)


def causal_softmax(logits: jax.Array, mask: jax.Array,
                   dtype) -> jax.Array:
    """Scores [B, ..heads.., C queries, C keys] in float32 to attention
    weights in `dtype`: query t sees the valid slots up to t, the
    softmax runs in float32."""
    slot = jnp.arange(logits.shape[-1])
    seen = (slot[None, :] <= slot[:, None])[None] & (mask > 0)[:, None, :]
    heads = (None,) * (logits.ndim - 3)
    logits = jnp.where(seen[(slice(None),) + heads], logits, -1e30)
    return jax.nn.softmax(logits, axis=-1).astype(dtype)


def attention(h: jax.Array, mask: jax.Array, layer: Dict, *, heads: int,
              kv_heads: int, head_dim: int, theta: float, norm: Callable,
              turned: Optional[int] = None, gated: bool = False
              ) -> jax.Array:
    """Causal grouped-query attention over h [B, C, H]: `layer` holds q,
    k, v, o and the q/k norms' q_norm, k_norm (`norm(t, scale)` runs
    over each head); kv head j serves query heads j n/n_kv ..; scores
    over sqrt(head_dim) under the causal and the padding mask, softmax
    in float32. `gated`: q's projection is twice as wide, a head's
    second half a gate, and the heads' output is multiplied by its
    sigmoid before o."""
    dtype = h.dtype
    B, C, _ = h.shape
    n, n_kv, hd = heads, kv_heads, head_dim

    def split(t, count, scale=None):
        t = t.reshape(B, C, count, -1)
        gate = None
        if t.shape[-1] != hd:
            t, gate = t[..., :hd], t[..., hd:]
        if scale is not None:
            t = norm(t, scale)
        return t.transpose(0, 2, 1, 3), gate           # [B, count, C, hd]

    q, gate = split(h @ layer["q"].astype(dtype), n, layer["q_norm"])
    assert (gate is not None) == gated
    q = rotary(q, theta, turned)
    k = rotary(split(h @ layer["k"].astype(dtype), n_kv,
                     layer["k_norm"])[0], theta, turned)
    v, _ = split(h @ layer["v"].astype(dtype), n_kv)
    q = q.reshape(B, n_kv, n // n_kv, C, hd)
    logits = jnp.einsum("bkgqd,bkcd->bkgqc", q, k).astype(jnp.float32) \
        / math.sqrt(hd)
    att = causal_softmax(logits, mask, dtype)
    out = jnp.einsum("bkgqc,bkcd->bkgqd", att, v)
    out = out.reshape(B, n, C, hd).transpose(0, 2, 1, 3)
    if gated:
        out = out * jax.nn.sigmoid(gate)
    return out.reshape(B, C, n * hd) @ layer["o"].astype(dtype)


def deinterleaved(x: jax.Array) -> jax.Array:
    """The last axis' pairs (2i, 2i + 1) laid out as (i, i + n/2): what
    `rotary` turns after this is what an interleaved rotary turns
    before it, and a product of two heads so laid out is the product of
    the heads."""
    return jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)


def latent_attention(h: jax.Array, mask: jax.Array, layer: Dict, *,
                     heads: int, nope: int, rope: int, v_dim: int,
                     theta: float, norm: Callable) -> jax.Array:
    """Causal multi-head latent attention over h [B, C, H]. `layer`
    holds q_a [H, r_q], q_a_norm, q_b [r_q, heads (nope + rope)], kv_a
    [H, r_kv + rope], kv_a_norm, kv_b [r_kv, heads (nope + v_dim)] and
    o [heads v_dim, H]; `norm(t, scale)` runs over each latent:

      c_q = norm(h q_a) ; a head of q = [q_nope | q_rope] = c_q q_b
      [c_kv | k_rope] = h kv_a ; c_kv = norm(c_kv)
      a head of [k_nope | v] = c_kv kv_b ; k_rope is ONE head, every
      query head's
      q_rope and k_rope turn, pairs (2i, 2i + 1) (interleaved), theta
      scores = (q_nope . k_nope + q_rope . k_rope) / sqrt(nope + rope)

    under the causal and the padding mask, softmax in float32. The
    scores are one einsum over the concatenated head, summed in
    float32, the shared rotary key broadcast beside each head's k_nope
    (105 MB in bfloat16 at the published widths): on the v5e that is
    10.5 ms a layer cheaper, forward and backward, than a second einsum
    for the rotary part, whose float32 scores XLA writes and copies a
    second time (PERF.md section 6, PR 34)."""
    dtype = h.dtype
    B, C, _ = h.shape

    def turned(t):                              # [B, C, n, rope]
        return rotary(deinterleaved(t).transpose(0, 2, 1, 3),
                      theta).transpose(0, 2, 1, 3)

    with jax.named_scope("q_lora"):
        c_q = norm(h @ layer["q_a"].astype(dtype), layer["q_a_norm"])
        q = (c_q @ layer["q_b"].astype(dtype)).reshape(B, C, heads,
                                                       nope + rope)
    with jax.named_scope("kv_lora"):
        c_kv, k_rope = jnp.split(h @ layer["kv_a"].astype(dtype),
                                 [layer["kv_a"].shape[1] - rope], axis=-1)
        c_kv = norm(c_kv, layer["kv_a_norm"])
        kv = (c_kv @ layer["kv_b"].astype(dtype)).reshape(B, C, heads,
                                                          nope + v_dim)
    with jax.named_scope("core"):
        q = jnp.concatenate([q[..., :nope], turned(q[..., nope:])], axis=-1)
        k_rope = jnp.broadcast_to(turned(k_rope[:, :, None, :]),
                                  (B, C, heads, rope))
        k = jnp.concatenate([kv[..., :nope], k_rope], axis=-1)
        logits = jnp.einsum("bqnd,bcnd->bnqc", q, k,
                            preferred_element_type=jnp.float32) \
            / math.sqrt(nope + rope)
        att = causal_softmax(logits, mask, dtype)
        out = jnp.einsum("bnqc,bcnd->bqnd", att, kv[..., nope:])
    with jax.named_scope("o"):
        return out.reshape(B, C, heads * v_dim) @ layer["o"].astype(dtype)


def swiglu(h: jax.Array, w1: jax.Array, w3: jax.Array,
           w2: jax.Array) -> jax.Array:
    dtype = h.dtype
    return (jax.nn.silu(h @ w1.astype(dtype))
            * (h @ w3.astype(dtype))) @ w2.astype(dtype)


def routed_experts(h: jax.Array, mask: jax.Array, score: Callable,
                   w1: jax.Array, w3: jax.Array, w2: jax.Array, *,
                   first_expert: int, routed: int
                   ) -> Tuple[jax.Array, jax.Array]:
    """(the held experts' output [B, C, H]; int32 [1, held + 3]: the
    rows each held expert took, the valid tokens, the rows the layer's
    arrays may hold and whether it ran at that bound
    (`moe.ran_at_bound`)). `score(tokens [N, H])` is the block's router:
    (chosen [N, K], p [N, K]), `ops/moe.route`."""
    B, C, H = h.shape
    tokens = h.reshape(B * C, H)
    valid = mask.reshape(B * C) > 0
    with jax.named_scope("router"):
        chosen, p = score(tokens)
    with jax.named_scope("experts"):
        out, rows = held_experts_ffn(tokens, valid, chosen, p, w1, w3, w2,
                                     first_expert, routed)
    bound, at_bound = ran_at_bound(rows, chosen.size, routed)
    counts = jnp.concatenate([rows, jnp.stack([
        jnp.sum(valid, dtype=jnp.int32), jnp.int32(bound),
        at_bound.astype(jnp.int32)])])
    return out.reshape(B, C, H), counts[None]


def residual_layer(i: int, *, norm: Callable, mixer_scope: str,
                   mixer: Callable, ff: Callable,
                   ff_scope: Optional[str] = None) -> Callable:
    """Layer i as `run(x, layer) -> (x, counts)`, rematerialised in the
    backward pass (at H = 2048 a layer's activations are the memory).
    `mixer(h, layer)` gives the operator's output; `ff(h, layer)` the
    feed-forward's and its counts ([devices, n] int32, summed here over
    the devices, or None); `norm(x, scale)` the block's norm, over the
    layer's `op_norm` and `ff_norm`."""
    ff_scope = f"c2v/blk_{i}" + (f"/{ff_scope}" if ff_scope else "")

    def run(x, layer):
        h = norm(x, layer["op_norm"])
        with jax.named_scope(f"c2v/blk_{i}/{mixer_scope}"):
            x = x + mixer(h, layer)
        h = norm(x, layer["ff_norm"])
        with jax.named_scope(ff_scope):
            out, counts = ff(h, layer)
        return x + out, (None if counts is None
                         else jnp.sum(counts, axis=0))

    return jax.checkpoint(run)


def run_block(sub: Dict, emb: jax.Array, mask: jax.Array, compute_dtype,
              *, layer_fn: Callable, norm: Callable, counts_width: int
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The stack over embedded contexts emb [B, C, 3E]: `sub` is the
    encoder's subtree (`in_proj`, `layers`, `ln_f_scale`, `pool_query`,
    `out_proj`), `layer_fn(i)` a `residual_layer`. Returns the encode
    contract's (code, pool attention, aux): aux int32 [layers that
    count, counts_width], empty where none does."""
    counted = []
    with jax.named_scope("c2v/encode"):
        # masked slots enter as zeros
        x = (emb @ sub["in_proj"].astype(compute_dtype)) \
            * mask[..., None].astype(compute_dtype)
        for i, layer in enumerate(sub["layers"]):
            x, counts = layer_fn(i)(x, layer)
            if counts is not None:
                counted.append(counts)

    with jax.named_scope("c2v/pool"):
        x = norm(x, sub["ln_f_scale"])
        pooled, attn = learned_query_pool(x, sub["pool_query"],
                                          padding_log_mask(mask),
                                          compute_dtype)
        code = pooled @ sub["out_proj"].astype(compute_dtype)
    return code, attn, (jnp.stack(counted) if counted else jnp.zeros(
        (0, counts_width), jnp.int32))


def refuse_context_parallel(cfg, name: str) -> None:
    if cfg.RING_ATTENTION or cfg.MESH_CONTEXT_AXIS > 1:
        raise ValueError(
            f"--encoder {name} has no ring attention and no "
            "context-parallel layout (its causal operators and "
            "mask run over whole sequences).")


def require_block_config(cfg, name: str) -> None:
    """A run that builds a model from scratch names the block's file
    (`--block_config`, also spelled `--lfm_config`)."""
    if not cfg.BLOCK_CONFIG and not cfg.is_loading:
        raise ValueError(
            f"--encoder {name} needs --block_config <json> (also spelled "
            "--lfm_config: the block's sizes; a checkpoint carries its "
            "own).")
