"""JoyAI-LLM-Flash's decoder block as the path encoder (`--encoder
joyai_flash`).

JD's JoyAI-LLM-Flash (`model_type` `joyai_llm_flash`, 48B-A2.7B,
huggingface.co/jdopensource/JoyAI-LLM-Flash `config.json`): a stack
whose every layer mixes by multi-head latent attention and whose
feed-forward is a dense SwiGLU in the leading layers and, in the rest,
256 routed experts under a sigmoid router with a selection bias and a
scaled sum, beside one shared expert. Here the stack runs over a
method's path-contexts in reader order: position = slot index, the
reader fills valid contexts from the left. `x` is [B, C, H], `m` the
context mask, `h` the normed input of a sub-layer.

  norm     RMSNorm(x) = w x / rms(x), w starts at 1, eps rms_norm_eps
  input    c = concat(tok[src], path[pth], tok[dst])      3E, dropout
           x = (c W_in) m                                 3E -> H; masked
                                                          slots enter as zeros
  layer    x = x + MLA(RMSNorm(x)) ; x = x + FF(RMSNorm(x))
  MLA (`seq_block.latent_attention`), n heads
           c_q = RMSNorm(h W_qa)                          H -> q_lora_rank
           q   = c_q W_qb                                 -> n heads of
                                                          [q_nope | q_rope]
           [c_kv | k_rope] = h W_kva                      H -> kv_lora_rank
                                                          + qk_rope_head_dim;
                                                          k_rope is ONE head,
                                                          every query head's
           c_kv = RMSNorm(c_kv)
           a head of [k_nope | v] = c_kv W_kvb            -> n (qk_nope_head_dim
                                                          + v_head_dim)
           q_rope, k_rope turn by the rotary term, theta, pairs
           (2i, 2i + 1) of the rope part (rope_interleave); no yarn term
           scores = (q_nope . k_nope + q_rope . k_rope)
                    / sqrt(qk_nope_head_dim + qk_rope_head_dim)
           causal and padding mask, softmax in float32
           MLA = concat over heads of (att v) W_o         n v_head_dim -> H
  FF, layers before first_k_dense_replace
           (silu(h W1) * (h W3)) W2                       width intermediate_size
  FF, the rest (`ops/moe.py`)
           s = sigmoid(h W_r), float32 ; chosen = top K of (s + bias)
           p_e = routed_scaling_factor s_e / (sum of the K chosen s + 1e-20)
           FF = sum over chosen e held here of p_e SwiGLU_e(h)
                + SwiGLU_shared(h)                        both of width
                                                          moe_intermediate_size
                                                          (the shared one x
                                                          n_shared_experts),
                                                          the shared one ungated
           a masked slot is routed nowhere
  output   RMSNorm ; the product's learned-query pool over valid slots
           at width H ; code = pooled W_out2               H -> 3E

Departures from the model, all of them the product's: the vocabulary
and the head are the three code2vec tables and the sampled softmax over
the name table, so there is no next position and the model's
multi-token-prediction layer has nothing to predict: it is not built
(`num_nextn_predict_layers` other than 0 is refused); the two
projections W_in and W_out2 stand where the model's own embedding and
head would; a sequence is a bag of at most MAX_CONTEXTS contexts in
reader order; no auxiliary loss. The selection bias
(`e_score_correction_bias`) is `lfm2_moe`'s: a seeded buffer, small and
non-zero, held fixed; it selects only, so no gradient reaches it, and
its update rule is a training switch the config does not hold.

Expert parallelism: `JoyaiDims.n_routed_experts` counts the experts
whose weights THIS process holds (`num_experts` is the repo's name for
the same number), from `first_expert`, of `num_routed_experts` the
router scores. The shared expert is every chip's, computed for every
position alike and added once. On one chip the layer runs without its
exchange, and what absent experts would add is left out. Under a mesh
every device routes its own rows of the batch (`shard_map`), the weights
replicated. Each layer is rematerialised in the backward pass. What
this block shares with `lfm2_moe_encoder.py` and
`qwen3_next_encoder.py` is `models/seq_block.py`'s.
"""

from __future__ import annotations

import dataclasses
import functools
import json
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from code2vec_tpu.models import seq_block
from code2vec_tpu.models.encoder import ModelDims, embed_contexts
from code2vec_tpu.models.registry import EncoderSpec
from code2vec_tpu.models.seq_block import BIAS_SCALE
from code2vec_tpu.models.transformer_encoder import _rms_norm
from code2vec_tpu.ops.moe import route

ROUTE_EPS = 1e-20       # under the sum of the chosen scores
MLA = "latent_attention"
# the source's key -> the repo's name for the same number (what the
# benchmark's counts and the other blocks' files call it)
TWINS = {"n_routed_experts": "num_experts",
         "first_k_dense_replace": "num_dense_layers"}


@dataclasses.dataclass(frozen=True)
class JoyaiDims:
    """The block's sizes, under the keys of the model's own
    `config.json` (`model_type` `joyai_llm_flash`); every one comes from
    the file `--block_config` names. `n_routed_experts` counts the
    experts whose weights THIS process holds, `first_expert` the first
    of them, and `num_routed_experts` the router's width (None: all are
    held here, as the published file means it)."""
    num_hidden_layers: int
    hidden_size: int
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    intermediate_size: int
    moe_intermediate_size: int
    n_routed_experts: int
    n_shared_experts: int
    num_experts_per_tok: int
    first_k_dense_replace: int
    routed_scaling_factor: float
    rope_theta: float
    rms_norm_eps: float
    num_routed_experts: Optional[int] = None
    first_expert: int = 0

    @property
    def num_experts(self) -> int:
        return self.n_routed_experts

    @property
    def num_dense_layers(self) -> int:
        return self.first_k_dense_replace

    @property
    def routed(self) -> int:
        return self.num_routed_experts or self.n_routed_experts

    @property
    def layer_types(self) -> Tuple[str, ...]:
        return (MLA,) * self.num_hidden_layers

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def shared_width(self) -> int:
        return self.n_shared_experts * self.moe_intermediate_size

    @classmethod
    def from_config(cls, config: dict) -> "JoyaiDims":
        """From a parsed `config.json`. Keys the block does not read
        are passed over; a switch the block does not implement is an
        error, not a silent default, and so is a source key that
        disagrees with its repo-named twin."""
        fixed = {"scoring_func": "sigmoid", "topk_method": "noaux_tc",
                 "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
                 "rope_scaling": None, "rope_interleave": True,
                 "attention_bias": False, "moe_layer_freq": 1,
                 "num_nextn_predict_layers": 0, "hidden_act": "silu"}
        for k, want in fixed.items():
            if config.get(k, want) != want:
                raise ValueError(f"joyai_flash implements {k}={want!r} "
                                 f"only (the file gives {config[k]!r})")
        if config.get("q_lora_rank", 0) is None:
            raise ValueError("joyai_flash implements a low-rank query "
                             "only (the file gives q_lora_rank=None)")
        config = dict(config)
        for source, twin in TWINS.items():
            if twin in config and \
                    config.setdefault(source, config[twin]) != config[twin]:
                raise ValueError(
                    f"joyai_flash: the file states {source}="
                    f"{config[source]!r} and {twin}={config[twin]!r}, two "
                    "names of one number")
        kw = {f.name: config[f.name] for f in dataclasses.fields(cls)
              if f.name in config}
        missing = [f.name for f in dataclasses.fields(cls)
                   if f.default is dataclasses.MISSING and f.name not in kw]
        if missing:
            raise ValueError("joyai_flash: the block's file "
                             f"(--block_config) lacks {missing}")
        kw["rope_theta"] = float(kw["rope_theta"])
        kw["routed_scaling_factor"] = float(kw["routed_scaling_factor"])
        dims = cls(**kw)
        dims.check(config)
        return dims

    def check(self, stated: dict) -> None:
        """`stated`: the file, for the sizes it may hold that follow
        from the others."""
        follows = {"qk_head_dim": self.qk_head_dim,
                   "num_key_value_heads": self.num_attention_heads}
        for k, want in follows.items():
            if stated.get(k, want) != want:
                raise ValueError(f"joyai_flash: {k}={stated[k]!r} is not "
                                 f"the {want} the block's other sizes give")
        if self.num_hidden_layers < 1 or self.n_shared_experts < 1 or \
                not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError(
                "joyai_flash: num_hidden_layers and n_shared_experts start "
                "at 1, first_k_dense_replace lies within the layers")
        if self.qk_rope_head_dim % 2 or not self.qk_rope_head_dim:
            raise ValueError("joyai_flash: the rotary part of a head "
                             "(qk_rope_head_dim) is even and not 0")
        if not (0 <= self.first_expert
                and self.first_expert + self.n_routed_experts <= self.routed
                and self.num_experts_per_tok <= self.routed):
            raise ValueError(
                f"joyai_flash: experts {self.first_expert}.."
                f"{self.first_expert + self.n_routed_experts - 1} held of "
                f"{self.routed} routed, {self.num_experts_per_tok} a token")


def _is_moe(cfg: JoyaiDims, i: int) -> bool:
    return i >= cfg.first_k_dense_replace


def init_joyai_params(rng: jax.Array, dims: ModelDims) -> Dict:
    """The "joyai" subtree. Every leaf has a key of its own, and an
    expert's weights hang on its index in the whole layer, so the
    shares of a layer drawn on different chips are slices of one
    layer."""
    cfg = dims.joyai
    D, H = dims.context_vector_size, cfg.hidden_size
    f32 = jnp.float32
    init = jax.nn.initializers.variance_scaling(1.0, "fan_avg", "uniform")
    k_in, k_out, k_pool = jax.random.split(rng, 3)
    n, r_q, r_kv = (cfg.num_attention_heads, cfg.q_lora_rank,
                    cfg.kv_lora_rank)
    layers = []
    for i in range(cfg.num_hidden_layers):
        k = jax.random.split(jax.random.fold_in(rng, 100 + i), 12)
        layer = {
            "op_norm": jnp.ones((H,), f32), "ff_norm": jnp.ones((H,), f32),
            "q_a": init(k[0], (H, r_q), f32),
            "q_a_norm": jnp.ones((r_q,), f32),
            "q_b": init(k[1], (r_q, n * cfg.qk_head_dim), f32),
            "kv_a": init(k[2], (H, r_kv + cfg.qk_rope_head_dim), f32),
            "kv_a_norm": jnp.ones((r_kv,), f32),
            "kv_b": init(k[3], (r_kv, n * (cfg.qk_nope_head_dim
                                           + cfg.v_head_dim)), f32),
            "o": init(k[4], (n * cfg.v_head_dim, H), f32)}
        if _is_moe(cfg, i):
            F, Fs = cfg.moe_intermediate_size, cfg.shared_width

            def expert(e):
                k1, k3, k2 = jax.random.split(jax.random.fold_in(k[7], e), 3)
                return (init(k1, (H, F), f32), init(k3, (H, F), f32),
                        init(k2, (F, H), f32))

            w1, w3, w2 = jax.vmap(expert)(
                cfg.first_expert + jnp.arange(cfg.n_routed_experts))
            layer.update(
                router=init(k[5], (H, cfg.routed), f32),
                expert_bias=BIAS_SCALE * jax.random.normal(
                    k[6], (cfg.routed,), f32),
                w1=w1, w3=w3, w2=w2,
                shared_w1=init(k[8], (H, Fs), f32),
                shared_w3=init(k[9], (H, Fs), f32),
                shared_w2=init(k[10], (Fs, H), f32))
        else:
            I = cfg.intermediate_size
            layer.update(w1=init(k[5], (H, I), f32),
                         w3=init(k[6], (H, I), f32),
                         w2=init(k[7], (I, H), f32))
        layers.append(layer)
    return {"in_proj": init(k_in, (D, H), f32),
            "out_proj": init(k_out, (H, D), f32),
            "pool_query": init(k_pool, (H, 1), f32)[:, 0],
            "ln_f_scale": jnp.ones((H,), f32),
            "layers": layers}


# ---- the encoder ---------------------------------------------------------

def encode_joyai_flash(params: Dict, source_ids: jax.Array,
                       path_ids: jax.Array, target_ids: jax.Array,
                       mask: jax.Array, *, dims: ModelDims, mesh=None,
                       dropout_rng: Optional[jax.Array] = None,
                       dropout_keep_rate: float = 1.0,
                       compute_dtype=jnp.float32,
                       use_pallas: bool = False, staircase=None
                       ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The encode contract (registry.EncoderSpec): (code [B, 3E] in
    the compute dtype, pool attention [B, C] f32, aux), aux being int32
    [expert layers, held + 3] as `lfm2_moe`'s: per expert layer the rows
    each held expert took, the valid tokens, the layer's row bound and
    whether it ran at the bound, each summed over the mesh's devices
    (the train step hands it to the spec's recorder, `obs.route`; the
    other steps let it fall). `use_pallas` is taken and not read: the
    grouped product is XLA's own kernel on the TPU, the attention XLA's
    on every backend. `staircase` (training only; `embed_contexts` has
    who checks it): the mixers' core also runs by the query blocks
    `seq_block.core_blocks` makes of it, every layer's feed-forward half
    over the positions of `seq_block.ff_rectangles`."""
    del use_pallas
    cfg, sub = dims.joyai, params["joyai"]
    norm = functools.partial(_rms_norm, eps=cfg.rms_norm_eps)
    emb = embed_contexts(params, source_ids, path_ids, target_ids,
                         dropout_rng, dropout_keep_rate, compute_dtype,
                         staircase, mesh)

    def _routed_experts(h, mask, router, bias, w1, w3, w2):
        return seq_block.routed_experts(
            h, mask, lambda tokens: route(
                tokens, router, bias, cfg.num_experts_per_tok,
                scale=cfg.routed_scaling_factor, eps=ROUTE_EPS),
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
        return seq_block.latent_attention(
            h, mask, layer, heads=cfg.num_attention_heads,
            nope=cfg.qk_nope_head_dim, rope=cfg.qk_rope_head_dim,
            v_dim=cfg.v_head_dim, theta=cfg.rope_theta, norm=norm,
            blocks=blocks)

    def dense(h, mask, layer):
        return seq_block.swiglu(h, layer["w1"], layer["w3"],
                                layer["w2"]), None

    def routed(h, mask, layer):
        out, counts = experts(h, mask, layer["router"],
                              layer["expert_bias"], layer["w1"],
                              layer["w3"], layer["w2"])
        with jax.named_scope("shared"):
            return out + seq_block.swiglu(
                h, layer["shared_w1"], layer["shared_w3"],
                layer["shared_w2"]), counts

    def layer_fn(i: int):
        moe = _is_moe(cfg, i)
        return seq_block.residual_layer(
            i, norm=norm, mixer_scope="mla", mixer=mixer,
            ff=routed if moe else dense, mask=mask,
            ff_scope=None if moe else "mlp", rectangles=rectangles)

    return seq_block.run_block(sub, emb, mask, compute_dtype,
                               layer_fn=layer_fn, norm=norm,
                               counts_width=cfg.n_routed_experts + 3)


# ---- the spec ------------------------------------------------------------

def _init(rng: jax.Array, dims: ModelDims) -> Dict:
    return init_joyai_params(jax.random.fold_in(rng, 0x10a1), dims)


def _sizes_from_config(cfg) -> Dict:
    """`--block_config`'s file (`check_config` has seen that it is
    named)."""
    with open(cfg.BLOCK_CONFIG) as f:
        return {"joyai": JoyaiDims.from_config(json.load(f))}


def _sizes_from_manifest(manifest: dict) -> Dict:
    return {"joyai": JoyaiDims.from_config(manifest["joyai"])}


def _check_config(cfg) -> None:
    seq_block.refuse_context_parallel(cfg, "joyai_flash")
    seq_block.require_block_config(cfg, "joyai_flash")


def _recorder():
    from code2vec_tpu.obs.route import RouteRecorder
    return RouteRecorder()


SPEC = EncoderSpec(
    encode=encode_joyai_flash, params_key="joyai", init=_init,
    sizes_from_config=_sizes_from_config,
    sizes_from_manifest=_sizes_from_manifest, check_config=_check_config,
    eval_batch_at_most_train=True, scores_by_staircase=True,
    recorder=_recorder)
