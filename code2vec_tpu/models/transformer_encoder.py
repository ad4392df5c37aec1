"""Transformer path-encoder (BASELINE.json configs[4]).

Replaces the reference's single-query attention pool with a set
transformer over the ≤MAX_CONTEXTS path-contexts. Design notes
(SURVEY.md §6 long-context row):

- Contexts are an UNORDERED bag, so there is no positional encoding —
  layers are permutation-equivariant (masked self-attention + MLP,
  pre-LN), and the code vector comes from a learned-query attention
  pool (PMA-style), which degenerates to exactly the reference's pool
  at zero layers.
- Everything is static-shape and jit-friendly; attention masks are
  additive log-masks. Heads/layers live in ModelDims so the jitted
  steps stay closed over static config.
- Activations keep the [B, C, D] layout with the context dim second, so
  a future context-parallel mesh axis shards `C` without a layout
  change (the axis is reserved in parallel/mesh.py; at size 1 today the
  sharding constraint is a no-op).
- Params sit under one "xf" subtree (replicated on the mesh — they are
  ~L*12*D^2 floats, tiny next to the vocab tables, which keep their
  row-sharded TP layout from parallel/sharding.py).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from code2vec_tpu.models.encoder import ModelDims, embed_contexts
from code2vec_tpu.models.registry import EncoderSpec


def init_xf_params(rng: jax.Array, dims: ModelDims) -> Dict:
    """The "xf" subtree: input projection, L layers, pool query."""
    D = dims.context_vector_size
    H = dims.xf_heads
    assert D % H == 0, f"context_vector_size {D} % heads {H} != 0"
    mlp = dims.xf_mlp_ratio * D
    init = jax.nn.initializers.variance_scaling(1.0, "fan_avg", "uniform")
    keys = jax.random.split(rng, 2 + 4 * dims.xf_layers)
    layers = []
    for i in range(dims.xf_layers):
        k_qkv, k_o, k_up, k_down = keys[2 + 4 * i: 6 + 4 * i]
        layers.append({
            "ln1_scale": jnp.ones((D,), jnp.float32),
            "ln2_scale": jnp.ones((D,), jnp.float32),
            "qkv": init(k_qkv, (D, 3 * D), jnp.float32),
            "out": init(k_o, (D, D), jnp.float32),
            "mlp_up": init(k_up, (D, mlp), jnp.float32),
            "mlp_down": init(k_down, (mlp, D), jnp.float32),
        })
    return {
        "ln_f_scale": jnp.ones((D,), jnp.float32),
        "pool_query": init(keys[0], (D, 1), jnp.float32)[:, 0],
        "in_proj": init(keys[1], (D, D), jnp.float32),
        "layers": layers,
    }


def _rms_norm(x: jax.Array, scale: jax.Array,
              eps: float = 1e-6) -> jax.Array:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                   keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
            ).astype(x.dtype) * scale.astype(x.dtype)


def padding_log_mask(mask: jax.Array) -> jax.Array:
    """[B, C] float32 additive mask: 0 at valid slots, log(1e-30) at
    padding. An all-pad row keeps every slot live so that a softmax
    over it stays finite."""
    safe_mask = jnp.where(jnp.sum(mask, axis=-1, keepdims=True) > 0,
                          mask, jnp.ones_like(mask))
    return jnp.log(jnp.maximum(safe_mask, 1e-30)).astype(jnp.float32)


def learned_query_pool(x: jax.Array, query: jax.Array,
                       log_mask: jax.Array, compute_dtype
                       ) -> Tuple[jax.Array, jax.Array]:
    """The reference's attention pool over already transformed
    representations x [B, C, D]: (code [B, D], weights [B, C] f32)."""
    pool_logits = (x.astype(jnp.float32)
                   @ query.astype(jnp.float32)) + log_mask
    attn = jax.nn.softmax(pool_logits, axis=-1)    # [B, C]
    code = jnp.einsum("bc,bcd->bd", attn.astype(compute_dtype), x)
    return code, attn


def _mha(x: jax.Array, qkv: jax.Array, out: jax.Array,
         log_mask: jax.Array, heads: int,
         ring_mesh=None, use_pallas: bool = False,
         mesh=None) -> jax.Array:
    B, C, D = x.shape
    hd = D // heads
    proj = x @ qkv.astype(x.dtype)                     # [B, C, 3D]
    q, k, v = jnp.split(proj, 3, axis=-1)

    def split_heads(t):
        return t.reshape(B, C, heads, hd).transpose(0, 2, 1, 3)

    q, k, v = split_heads(q), split_heads(k), split_heads(v)
    if ring_mesh is not None:
        from code2vec_tpu.ops.ring_attention import ring_attention
        ctx = ring_attention(q, k, v, log_mask, ring_mesh)
    elif use_pallas:
        # fused fwd+bwd kernels: no [B, H, C, C] tensor in HBM either
        # direction (ops/xf_attention.py)
        from code2vec_tpu.ops.xf_attention import fused_mha
        mha = fused_mha
        if mesh is not None:
            from code2vec_tpu.parallel.sharding import shard_map_over_batch
            mha = shard_map_over_batch(mha, mesh, (True,) * 4)
        ctx = mha(q, k, v, log_mask)
    else:
        logits = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32)
        # hd is the Python-int head dim: trace-time scale math, no
        # device sync here  # graftlint: disable=host-sync-in-hot-path
        logits = logits / jnp.sqrt(float(hd)) \
            + log_mask[:, None, None, :]
        attn = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
        ctx = jnp.einsum("bhqk,bhkd->bhqd", attn, v)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(B, C, D)
    return ctx @ out.astype(x.dtype)


def encode_transformer(params: Dict, source_ids: jax.Array,
                       path_ids: jax.Array, target_ids: jax.Array,
                       mask: jax.Array, *,
                       dims: ModelDims,
                       mesh=None,
                       dropout_rng: Optional[jax.Array] = None,
                       dropout_keep_rate: float = 1.0,
                       compute_dtype=jnp.float32,
                       use_pallas: bool = False,
                       staircase=None
                       ) -> Tuple[jax.Array, jax.Array, None]:
    """The encode contract (registry.EncoderSpec): returns (code [B, D]
    in compute dtype, pool attention [B, C] f32, None). With
    `use_pallas`, the self-attention runs as the fused Pallas kernel pair
    (ops/xf_attention.py — no [B, H, C, C] HBM materialization in
    either direction). With dims.ring_attention and a mesh whose 'ctx'
    axis is > 1, it runs as ring attention instead (K/V rotate via
    ppermute, O(C/s) per-device memory) — the ring path wins over the
    kernel because sharded-C blocks are small enough for XLA."""
    from code2vec_tpu.parallel.mesh import CONTEXT_AXIS
    ring_mesh = (mesh if (dims.ring_attention and mesh is not None
                          and dict(mesh.shape).get(CONTEXT_AXIS, 1) > 1)
                 else None)
    if ring_mesh is not None:
        use_pallas = False
    xf = params["xf"]
    # phase scopes as in encoder.encode, `c2v/xf_layer_<i>` inside
    # `c2v/encode`
    emb = embed_contexts(params, source_ids, path_ids, target_ids,
                         dropout_rng, dropout_keep_rate, compute_dtype,
                         staircase, mesh)              # [B, C, D]
    with jax.named_scope("c2v/encode"):
        log_mask = padding_log_mask(mask)

    def layer_fn(x, layer):
        h = _rms_norm(x, layer["ln1_scale"])
        x = x + _mha(h, layer["qkv"], layer["out"], log_mask,
                     dims.xf_heads, ring_mesh=ring_mesh,
                     use_pallas=use_pallas, mesh=mesh)
        h = _rms_norm(x, layer["ln2_scale"])
        h = jax.nn.gelu(h @ layer["mlp_up"].astype(compute_dtype))
        return x + h @ layer["mlp_down"].astype(compute_dtype)

    if dims.xf_remat:
        # O(1)-in-depth activation memory for CodeBERT-scale encoders
        layer_fn = jax.checkpoint(layer_fn)

    with jax.named_scope("c2v/encode"):
        x = emb @ xf["in_proj"].astype(compute_dtype)
        for i, layer in enumerate(xf["layers"]):
            with jax.named_scope(f"c2v/xf_layer_{i}"):
                x = layer_fn(x, layer)

    with jax.named_scope("c2v/pool"):
        x = _rms_norm(x, xf["ln_f_scale"])
        return (*learned_query_pool(x, xf["pool_query"], log_mask,
                                    compute_dtype), None)


def _init(rng: jax.Array, dims: ModelDims) -> Dict:
    return init_xf_params(jax.random.fold_in(rng, 0x5f), dims)


SPEC = EncoderSpec(encode=encode_transformer, params_key="xf", init=_init)
