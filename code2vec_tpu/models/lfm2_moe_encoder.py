"""The LFM2-MoE decoder block as the path encoder (`--encoder lfm2_moe`).

LiquidAI's LFM2-24B-A2B (`model_type` `lfm2_moe`,
huggingface.co/LiquidAI/LFM2-24B-A2B `config.json`): a stack whose
layers are of two kinds, gated short convolutions and grouped-query
attention, with two kinds of feed-forward, one dense SwiGLU and then
routed experts. Here the stack runs over a method's path-contexts in
reader order: position = slot index, the reader fills valid contexts
from the left. `x` is [B, C, H], `m` the context mask.

  input    c = concat(tok[src], path[pth], tok[dst])      3E, dropout
           x = (c W_in) m                                 3E -> H; masked
                                                          slots enter as zeros
  layer    x = x + Op(RMSNorm(x)) ; x = x + FF(RMSNorm(x))   eps norm_eps
  conv     [b, g, u] = split3(h W_in3)                    H -> 3H, no bias
           v = b * u * m
           w_t = sum_{j<L} K[:, j] v_{t-(L-1)+j}          depthwise, causal,
                                                          zeros before slot 0
           Op = (g * w) W_out                             H -> H
  full_attention
           q = h W_q, k = h W_k, v = h W_v                heads of H / n_heads;
                                                          n_kv key/value heads
           q, k = RMSNorm over each head (learned scale), then rotary
           (theta, over the whole head, rotate-half); scores / sqrt(head),
           causal mask and padding mask, softmax in float32; kv head j
           serves query heads j n/n_kv .. ; Op = concat(heads) W_o
  FF, layers before num_dense_layers
           (silu(h W1) * (h W3)) W2                       width intermediate_size
  FF, the rest (ops/moe.py)
           s = sigmoid(h W_r) ; chosen = top K of (s + bias)
           p_e = s_e / (sum of the K chosen s + 1e-6)
           FF = sum over chosen e held here of
                p_e (silu(h W1_e) * (h W3_e)) W2_e        width moe_intermediate_size
           no shared expert, no auxiliary loss; a masked slot is routed
           nowhere
  output   RMSNorm ; the product's learned-query pool over valid slots
           at width H ; code = pooled W_out2               H -> 3E

Departures from the model, all of them the product's: the vocabulary
and the head are the three code2vec tables and the sampled softmax over
the name table; the two projections W_in and W_out2 stand where the
model's own embedding and head would; a sequence is a bag of at most
MAX_CONTEXTS contexts in reader order; the loss is the product's. The
q/k RMSNorm is LFM2's (its config's keys do not state it). The
selection bias is a seeded buffer, small and non-zero, held fixed: it
selects only, so no gradient reaches it, and its update rule is not in
the config.

Expert parallelism: `Lfm2Dims.num_experts` experts from `first_expert`
are held here, of `routed` the router scores. On one chip the layer
runs without its exchange, and what absent experts would add is left
out. Under a mesh every device routes its own rows of the batch
(`shard_map`), the weights replicated. Each layer is rematerialised in
the backward pass: at H = 2048 a layer's activations are the memory.

What this block shares with `qwen3_next_encoder.py` lives in
`models/seq_block.py`: the input and output projections and the pool
(`run_block`), the rematerialised residual layer and its scopes
(`residual_layer`), the rotary term, the grouped-query attention, the
SwiGLU and the routed experts' wrapper that makes the counts. Here: the
sizes, the weights, the short convolution, the sigmoid router's bias
and which layer runs what.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from code2vec_tpu.models import seq_block
from code2vec_tpu.models.encoder import ModelDims, embed_contexts
from code2vec_tpu.models.registry import EncoderSpec
from code2vec_tpu.models.seq_block import BIAS_SCALE
from code2vec_tpu.models.transformer_encoder import _rms_norm
from code2vec_tpu.ops.moe import route


@dataclasses.dataclass(frozen=True)
class Lfm2Dims:
    """The block's sizes, under the keys of the model's own
    `config.json` (`model_type` `lfm2_moe`); every one comes from the
    file `--block_config` (also spelled `--lfm_config`) names.
    `num_experts` counts the experts whose weights THIS process holds,
    `first_expert` the first of them, and `num_routed_experts` the
    router's width (None: all are held here, as the published file
    means it)."""
    layer_types: Tuple[str, ...]
    num_dense_layers: int
    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    num_attention_heads: int
    num_key_value_heads: int
    num_experts: int
    num_experts_per_tok: int
    conv_L_cache: int
    norm_eps: float
    rope_theta: float
    num_routed_experts: Optional[int] = None
    first_expert: int = 0

    @property
    def routed(self) -> int:
        return self.num_routed_experts or self.num_experts

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @classmethod
    def from_config(cls, config: dict) -> "Lfm2Dims":
        """From a parsed `config.json`. Keys the block does not read
        are passed over; a switch the block does not implement is an
        error, not a silent default."""
        fixed = {"norm_topk_prob": True, "use_expert_bias": True,
                 "conv_bias": False, "routed_scaling_factor": 1}
        for k, want in fixed.items():
            if config.get(k, want) != want:
                raise ValueError(
                    f"lfm2_moe implements {k}={want!r} only (the file "
                    f"gives {config[k]!r})" + (
                        "; a block that weighs the routed sum hands "
                        "ops/moe.route its `scale`, as "
                        "models/joyai_flash_encoder.py does"
                        if k == "routed_scaling_factor" else ""))
        kw = {f.name: config[f.name] for f in dataclasses.fields(cls)
              if f.name in config}
        if "rope_parameters" in config:
            kw["rope_theta"] = float(config["rope_parameters"]["rope_theta"])
        missing = [f.name for f in dataclasses.fields(cls)
                   if f.default is dataclasses.MISSING and f.name not in kw]
        if missing:
            raise ValueError("lfm2_moe: the block's file (--block_config, "
                             f"also spelled --lfm_config) lacks {missing}")
        kw["layer_types"] = tuple(kw["layer_types"])
        dims = cls(**kw)
        dims.check()
        return dims

    def check(self) -> None:
        bad = set(self.layer_types) - {"conv", "full_attention"}
        if bad or not self.layer_types:
            raise ValueError(f"lfm2_moe layer_types {sorted(bad)} unknown "
                             "(conv, full_attention)")
        if self.hidden_size % self.num_attention_heads or \
                self.num_attention_heads % self.num_key_value_heads or \
                self.head_dim % 2:
            raise ValueError("lfm2_moe: heads must divide hidden_size, "
                             "kv heads the heads, and a head be even")
        if not (0 <= self.first_expert
                and self.first_expert + self.num_experts <= self.routed
                and self.num_experts_per_tok <= self.routed):
            raise ValueError(
                f"lfm2_moe: experts {self.first_expert}.."
                f"{self.first_expert + self.num_experts - 1} held of "
                f"{self.routed} routed, {self.num_experts_per_tok} a token")


def _is_moe(cfg: Lfm2Dims, i: int) -> bool:
    return i >= cfg.num_dense_layers


def init_lfm_params(rng: jax.Array, dims: ModelDims) -> Dict:
    """The "lfm" subtree. Every leaf has a key of its own, and an
    expert's weights hang on its index in the whole layer, so the
    shares of a layer drawn on different chips are slices of one
    layer."""
    cfg = dims.lfm
    D, H = dims.context_vector_size, cfg.hidden_size
    hd, f32 = cfg.head_dim, jnp.float32
    init = jax.nn.initializers.variance_scaling(1.0, "fan_avg", "uniform")
    k_in, k_out, k_pool = jax.random.split(rng, 3)
    layers = []
    for i, kind in enumerate(cfg.layer_types):
        k = jax.random.split(jax.random.fold_in(rng, 100 + i), 7)
        layer = {"op_norm": jnp.ones((H,), f32),
                 "ff_norm": jnp.ones((H,), f32)}
        if kind == "conv":
            bound = 1.0 / math.sqrt(cfg.conv_L_cache)
            layer.update(
                conv_in=init(k[0], (H, 3 * H), f32),
                conv_k=jax.random.uniform(k[1], (H, cfg.conv_L_cache), f32,
                                          -bound, bound),
                conv_out=init(k[2], (H, H), f32))
        else:
            kv = cfg.num_key_value_heads * hd
            layer.update(
                q=init(k[0], (H, H), f32), k=init(k[1], (H, kv), f32),
                v=init(k[2], (H, kv), f32), o=init(k[3], (H, H), f32),
                q_norm=jnp.ones((hd,), f32), k_norm=jnp.ones((hd,), f32))
        if _is_moe(cfg, i):
            F = cfg.moe_intermediate_size

            def expert(e):
                k1, k3, k2 = jax.random.split(jax.random.fold_in(k[6], e), 3)
                return (init(k1, (H, F), f32), init(k3, (H, F), f32),
                        init(k2, (F, H), f32))

            w1, w3, w2 = jax.vmap(expert)(
                cfg.first_expert + jnp.arange(cfg.num_experts))
            layer.update(
                router=init(k[4], (H, cfg.routed), f32),
                expert_bias=BIAS_SCALE * jax.random.normal(
                    k[5], (cfg.routed,), f32),
                w1=w1, w3=w3, w2=w2)
        else:
            I = cfg.intermediate_size
            layer.update(w1=init(k[4], (H, I), f32),
                         w3=init(k[5], (H, I), f32),
                         w2=init(k[6], (I, H), f32))
        layers.append(layer)
    return {"in_proj": init(k_in, (D, H), f32),
            "out_proj": init(k_out, (H, D), f32),
            "pool_query": init(k_pool, (H, 1), f32)[:, 0],
            "ln_f_scale": jnp.ones((H,), f32),
            "layers": layers}


# ---- the operator of its own ---------------------------------------------

def _short_conv(h: jax.Array, mask: jax.Array, layer: Dict) -> jax.Array:
    dtype, C = h.dtype, h.shape[1]
    b, g, u = jnp.split(h @ layer["conv_in"].astype(dtype), 3, axis=-1)
    v = b * u * mask[..., None].astype(dtype)
    kernel = layer["conv_k"].astype(dtype)             # [H, L]
    taps = kernel.shape[1]
    padded = jnp.pad(v, ((0, 0), (taps - 1, 0), (0, 0)))
    w = sum(kernel[:, j] * padded[:, j:j + C, :] for j in range(taps))
    return (g * w) @ layer["conv_out"].astype(dtype)


# ---- the encoder ---------------------------------------------------------

def encode_lfm2_moe(params: Dict, source_ids: jax.Array,
                    path_ids: jax.Array, target_ids: jax.Array,
                    mask: jax.Array, *, dims: ModelDims, mesh=None,
                    dropout_rng: Optional[jax.Array] = None,
                    dropout_keep_rate: float = 1.0,
                    compute_dtype=jnp.float32,
                    use_pallas: bool = False, staircase=None
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The encode contract (registry.EncoderSpec): (code [B, 3E] in
    the compute dtype, pool attention [B, C] f32, aux), aux being int32
    [expert layers, held + 3], per expert layer the rows each held
    expert took, the valid tokens, the layer's row bound and whether it
    ran at the bound, each summed over the mesh's devices (the train
    step hands it to the spec's recorder, `obs.route`; the other steps
    let it fall).
    `use_pallas` is taken and not read: the
    grouped product is XLA's own kernel on the TPU, the attention XLA's
    on every backend. `staircase` (training only; `embed_contexts` has
    who checks it): the attention layers' core also runs by the query
    blocks `seq_block.core_blocks` makes of it, every layer's
    feed-forward half over the positions of `seq_block.ff_rectangles`."""
    del use_pallas
    cfg, lfm = dims.lfm, params["lfm"]
    norm = functools.partial(_rms_norm, eps=cfg.norm_eps)
    emb = embed_contexts(params, source_ids, path_ids, target_ids,
                         dropout_rng, dropout_keep_rate, compute_dtype,
                         staircase, mesh)

    def _routed_experts(h, mask, router, bias, w1, w3, w2):
        return seq_block.routed_experts(
            h, mask, lambda tokens: route(tokens, router, bias,
                                          cfg.num_experts_per_tok),
            w1, w3, w2, first_expert=cfg.first_expert, routed=cfg.routed)

    experts = _routed_experts
    if mesh is not None:
        # each device routes its own rows of the batch
        from code2vec_tpu.parallel.sharding import shard_map_over_batch
        experts = shard_map_over_batch(experts, mesh,
                                       (True, True) + (False,) * 5)

    blocks = seq_block.core_blocks(staircase, mesh, mask.shape[1])
    rectangles = seq_block.ff_rectangles(staircase, mesh, mask.shape[1])

    def mixer(h, layer):
        if "conv_k" in layer:
            return _short_conv(h, mask, layer)
        return seq_block.attention(
            h, mask, layer, heads=cfg.num_attention_heads,
            kv_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
            theta=cfg.rope_theta, norm=norm, blocks=blocks)

    def dense(h, mask, layer):
        return seq_block.swiglu(h, layer["w1"], layer["w3"],
                                layer["w2"]), None

    def routed(h, mask, layer):
        return experts(h, mask, layer["router"], layer["expert_bias"],
                       layer["w1"], layer["w3"], layer["w2"])

    def layer_fn(i: int):
        conv = cfg.layer_types[i] == "conv"
        moe = _is_moe(cfg, i)
        return seq_block.residual_layer(
            i, norm=norm, mixer_scope="conv" if conv else "attn",
            mixer=mixer, ff=routed if moe else dense, mask=mask,
            ff_scope=None if moe else "mlp", rectangles=rectangles)

    return seq_block.run_block(lfm, emb, mask, compute_dtype,
                               layer_fn=layer_fn, norm=norm,
                               counts_width=cfg.num_experts + 3)


# ---- the spec ------------------------------------------------------------

def _init(rng: jax.Array, dims: ModelDims) -> Dict:
    return init_lfm_params(jax.random.fold_in(rng, 0x1f2), dims)


def _sizes_from_config(cfg) -> Dict:
    """`--block_config`'s file (`check_config` has seen that it is
    named; `--lfm_config` is the same option)."""
    with open(cfg.BLOCK_CONFIG) as f:
        return {"lfm": Lfm2Dims.from_config(json.load(f))}


def _sizes_from_manifest(manifest: dict) -> Dict:
    return {"lfm": Lfm2Dims.from_config(manifest["lfm"])}


def _check_config(cfg) -> None:
    seq_block.refuse_context_parallel(cfg, "lfm2_moe")
    seq_block.require_block_config(cfg, "lfm2_moe")


def _recorder():
    from code2vec_tpu.obs.route import RouteRecorder
    return RouteRecorder()


SPEC = EncoderSpec(
    encode=encode_lfm2_moe, params_key="lfm", init=_init,
    sizes_from_config=_sizes_from_config,
    sizes_from_manifest=_sizes_from_manifest, check_config=_check_config,
    eval_batch_at_most_train=True, scores_by_staircase=True,
    recorder=_recorder)
