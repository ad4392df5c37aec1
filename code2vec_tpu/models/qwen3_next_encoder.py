"""Qwen3-Next's decoder block as the path encoder (`--encoder qwen3_next`).

Qwen's Qwen3-Next-80B-A3B (`model_type` `qwen3_next`,
huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct `config.json`): a stack
whose layers mix by one of two operators, three gated-DeltaNet layers to
one gated softmax-attention layer, and whose every feed-forward sums
routed experts and a gated shared expert. Here the stack runs over a
method's path-contexts in reader order: position = slot index, the
reader fills valid contexts from the left. `x` is [B, C, H], `m` the
context mask, `h` the normed input of a sub-layer.

  norm      RMSNorm0(x) = x / rms(x) * (1 + w), w starts at 0, eps
            rms_norm_eps (input norm, post-mixer norm, final norm,
            q_norm, k_norm)
  input     c = concat(tok[src], path[pth], tok[dst])     3E, dropout
            x = (c W_in) m                                3E -> H
  layer i   x = x + Mixer_i(RMSNorm0(x)) ; x = x + MoE(RMSNorm0(x))
            Mixer_i = full_attention where (i + 1) %
            full_attention_interval == 0, else linear_attention

  linear_attention (gated DeltaNet; `ops/delta_rule.py`); n_k key
  heads, n_v value heads of d_k, d_v
            [q, k, v, z] = h W_qkvz         H -> n_k d_k + n_k d_k +
                                            n_v d_v + n_v d_v, no bias
            [b, a]       = h W_ba           H -> n_v + n_v
            [q, k, v]    = silu(causal depthwise conv over
                           concat(q, k, v) m, linear_conv_kernel_dim
                           taps, no bias, zeros before slot 0)
            beta_t = sigmoid(b_t)                         a value head
            g_t    = -exp(A_log) softplus(a_t + dt_bias)  a value head
            q, k: key head j serves value heads j n_v/n_k ..; each
            x / sqrt(sum x^2 + 1e-6) over d_k; q / sqrt(d_k)
            per value head, S_0 = 0 in R^{d_k x d_v}, float32:
              S'_t = exp(g_t) S_{t-1}
              S_t  = S'_t + k_t (beta_t (v_t - S'_t^T k_t))^T
              o_t  = S_t^T q_t
            a masked slot leaves the state as it is and gives o = 0
            y = w (o / rms(o)) silu(z)      over each head's d_v, w
                                            starts at 1, eps 1e-6
            Mixer = concat(heads of y) W_out              n_v d_v -> H

  full_attention (gated; `seq_block.attention`)
            [q, gate] = h W_q               H -> n (hd + hd), split a head
            k = h W_k, v = h W_v            H -> n_kv hd each
            q = RMSNorm0(q), k = RMSNorm0(k) over the head; rotary
            (theta, rotate-half) over the first partial_rotary_factor x
            hd of each head, the rest pass unturned; scores over
            sqrt(hd), causal and padding mask, softmax in float32; kv
            head j serves query heads j n/n_kv ..
            Mixer = (concat(heads) sigmoid(gate)) W_o     n hd -> H

  MoE (`ops/moe.py`)
            p = softmax(h W_r) over all E, float32 ; chosen = top K
            p_e = p_e / sum of the K chosen p            (norm_topk_prob)
            routed = sum over chosen e held here of
                     p_e (silu(h W1_e) (h W3_e)) W2_e
            MoE = routed + sigmoid(h w_s) (silu(h V1) (h V3)) V2
            a masked slot is routed nowhere

  output    RMSNorm0 ; the product's learned-query pool over valid
            slots at width H ; code = pooled W_out2       H -> 3E

Departures from the model, all of them the product's: the vocabulary
and the head are the three code2vec tables and the sampled softmax over
the name table (no next-token head, no multi-token prediction); the two
projections W_in and W_out2 stand where the model's own embedding and
head would; a sequence is a bag of at most MAX_CONTEXTS contexts in
reader order, not text; no auxiliary balancing loss. Assumed: the column
order of W_qkvz ([q | k | v | z], heads side by side in each) and of
W_ba ([b | a]): any order is the same model at seeded weights, a loader
of real weights would need the published one; the initial values (A_log
= log of U(0, 16), dt_bias = 1, the conv taps uniform in +-1/2, norms at
their identity, every matrix variance-scaled uniform).

Expert parallelism: `Qwen3NextDims.num_experts` experts from
`first_expert` are held here, of `routed` the router scores; the shared
expert is every chip's. On one chip the layer runs without its
exchange, and what absent experts would add is left out. Under a mesh
every device routes and scans its own rows of the batch (`shard_map`),
the weights replicated. Each layer is rematerialised in the backward
pass. What this block shares with `lfm2_moe_encoder.py` is
`models/seq_block.py`'s.
"""

from __future__ import annotations

import dataclasses
import functools
import json
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from code2vec_tpu.data.staircase import rows_kept
from code2vec_tpu.models import seq_block
from code2vec_tpu.models.encoder import ModelDims, embed_contexts
from code2vec_tpu.models.registry import EncoderSpec
from code2vec_tpu.models.transformer_encoder import _rms_norm
from code2vec_tpu.ops import delta_rule
from code2vec_tpu.ops.moe import route

L2_EPS = 1e-6       # under the root of q's and k's L2 norm
GATED_NORM_EPS = 1e-6
LINEAR, FULL = "linear_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class Qwen3NextDims:
    """The block's sizes, under the keys of the model's own
    `config.json` (`model_type` `qwen3_next`); every one comes from the
    file `--block_config` names. `num_experts` counts the experts whose
    weights THIS process holds, `first_expert` the first of them, and
    `num_routed_experts` the router's width (None: all are held here,
    as the published file means it)."""
    num_hidden_layers: int
    full_attention_interval: int
    hidden_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    partial_rotary_factor: float
    rope_theta: float
    rms_norm_eps: float
    linear_conv_kernel_dim: int
    linear_key_head_dim: int
    linear_value_head_dim: int
    linear_num_key_heads: int
    linear_num_value_heads: int
    num_experts: int
    num_experts_per_tok: int
    moe_intermediate_size: int
    shared_expert_intermediate_size: int
    num_routed_experts: Optional[int] = None
    first_expert: int = 0

    @property
    def routed(self) -> int:
        return self.num_routed_experts or self.num_experts

    @property
    def layer_types(self) -> Tuple[str, ...]:
        return tuple(FULL if (i + 1) % self.full_attention_interval == 0
                     else LINEAR for i in range(self.num_hidden_layers))

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def key_dim(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_dim(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    @classmethod
    def from_config(cls, config: dict) -> "Qwen3NextDims":
        """From a parsed `config.json`. Keys the block does not read
        are passed over; a switch the block does not implement is an
        error, not a silent default."""
        fixed = {"mlp_only_layers": [], "decoder_sparse_step": 1,
                 "norm_topk_prob": True, "rope_scaling": None,
                 "hidden_act": "silu", "attention_bias": False}
        for k, want in fixed.items():
            if config.get(k, want) != want:
                raise ValueError(f"qwen3_next implements {k}={want!r} only "
                                 f"(the file gives {config[k]!r})")
        kw = {f.name: config[f.name] for f in dataclasses.fields(cls)
              if f.name in config}
        missing = [f.name for f in dataclasses.fields(cls)
                   if f.default is dataclasses.MISSING and f.name not in kw]
        if missing:
            raise ValueError("qwen3_next: the block's file "
                             f"(--block_config) lacks {missing}")
        kw["rope_theta"] = float(kw["rope_theta"])
        dims = cls(**kw)
        dims.check(config.get("layer_types"))
        return dims

    def check(self, stated_layer_types=None) -> None:
        if stated_layer_types is not None and \
                tuple(stated_layer_types) != self.layer_types:
            raise ValueError(
                "qwen3_next: layer_types is not the pattern that "
                f"full_attention_interval={self.full_attention_interval} "
                f"gives over {self.num_hidden_layers} layers "
                f"({list(self.layer_types)})")
        if self.num_hidden_layers < 1 or self.full_attention_interval < 1:
            raise ValueError("qwen3_next: num_hidden_layers and "
                             "full_attention_interval start at 1")
        if self.num_attention_heads % self.num_key_value_heads or \
                self.linear_num_value_heads % self.linear_num_key_heads \
                or self.rotary_dim % 2 or not self.rotary_dim:
            raise ValueError(
                "qwen3_next: kv heads must divide the heads, key heads "
                "the value heads, and the turned part of a head be even")
        if not (0 <= self.first_expert
                and self.first_expert + self.num_experts <= self.routed
                and self.num_experts_per_tok <= self.routed):
            raise ValueError(
                f"qwen3_next: experts {self.first_expert}.."
                f"{self.first_expert + self.num_experts - 1} held of "
                f"{self.routed} routed, {self.num_experts_per_tok} a token")


def init_qwen_params(rng: jax.Array, dims: ModelDims) -> Dict:
    """The "qwen" subtree. Every leaf has a key of its own, and an
    expert's weights hang on its index in the whole layer, so the
    shares of a layer drawn on different chips are slices of one
    layer."""
    cfg = dims.qwen
    D, H = dims.context_vector_size, cfg.hidden_size
    f32 = jnp.float32
    init = jax.nn.initializers.variance_scaling(1.0, "fan_avg", "uniform")
    k_in, k_out, k_pool = jax.random.split(rng, 3)
    n_v, hd = cfg.linear_num_value_heads, cfg.head_dim
    layers = []
    for i, kind in enumerate(cfg.layer_types):
        k = jax.random.split(jax.random.fold_in(rng, 100 + i), 12)
        layer = {"op_norm": jnp.zeros((H,), f32),
                 "ff_norm": jnp.zeros((H,), f32)}
        if kind == LINEAR:
            layer.update(
                in_qkvz=init(k[0], (H, 2 * cfg.key_dim + 2 * cfg.value_dim),
                             f32),
                in_ba=init(k[1], (H, 2 * n_v), f32),
                conv_k=jax.random.uniform(
                    k[2], (2 * cfg.key_dim + cfg.value_dim,
                           cfg.linear_conv_kernel_dim), f32, -0.5, 0.5),
                A_log=jnp.log(jax.random.uniform(k[3], (n_v,), f32, 0.0,
                                                 16.0)),
                dt_bias=jnp.ones((n_v,), f32),
                gdn_norm=jnp.ones((cfg.linear_value_head_dim,), f32),
                gdn_out=init(k[4], (cfg.value_dim, H), f32))
        else:
            n, kv = cfg.num_attention_heads, cfg.num_key_value_heads * hd
            layer.update(
                q=init(k[0], (H, 2 * n * hd), f32),
                k=init(k[1], (H, kv), f32), v=init(k[2], (H, kv), f32),
                o=init(k[3], (n * hd, H), f32),
                q_norm=jnp.zeros((hd,), f32), k_norm=jnp.zeros((hd,), f32))
        F, Fs = cfg.moe_intermediate_size, cfg.shared_expert_intermediate_size

        def expert(e):
            k1, k3, k2 = jax.random.split(jax.random.fold_in(k[6], e), 3)
            return (init(k1, (H, F), f32), init(k3, (H, F), f32),
                    init(k2, (F, H), f32))

        w1, w3, w2 = jax.vmap(expert)(
            cfg.first_expert + jnp.arange(cfg.num_experts))
        layer.update(
            router=init(k[5], (H, cfg.routed), f32), w1=w1, w3=w3, w2=w2,
            shared_w1=init(k[7], (H, Fs), f32),
            shared_w3=init(k[8], (H, Fs), f32),
            shared_w2=init(k[9], (Fs, H), f32),
            shared_gate=init(k[10], (H, 1), f32)[:, 0])
        layers.append(layer)
    return {"in_proj": init(k_in, (D, H), f32),
            "out_proj": init(k_out, (H, D), f32),
            "pool_query": init(k_pool, (H, 1), f32)[:, 0],
            "ln_f_scale": jnp.zeros((H,), f32),
            "layers": layers}


# ---- the operator of its own ---------------------------------------------

def _l2_normalised(x: jax.Array) -> jax.Array:
    x32 = x.astype(jnp.float32)
    return x32 * jax.lax.rsqrt(jnp.sum(x32 * x32, axis=-1, keepdims=True)
                               + L2_EPS)


def _gated_delta_net(h: jax.Array, mask: jax.Array, layer: Dict,
                     cfg: Qwen3NextDims, scan) -> jax.Array:
    """`scan` is `delta_rule.gated_delta_rule`, whole or under a mesh's
    `shard_map`."""
    dtype = h.dtype
    B, C, _ = h.shape
    n_k, n_v = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    d_k, d_v = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    qkv, z = jnp.split(h @ layer["in_qkvz"].astype(dtype),
                       [2 * cfg.key_dim + cfg.value_dim], axis=-1)
    b, a = jnp.split((h @ layer["in_ba"].astype(dtype)).astype(jnp.float32),
                     2, axis=-1)
    with jax.named_scope("conv"):
        # the taps are summed in float32, as the model's own kernel
        # sums them: four products of either sign, and q and k are
        # normalised afterwards (summed in bfloat16 the taps' rounding
        # is the block's largest error; tests/test_qwen3_next.py)
        padded = jnp.pad(qkv * mask[..., None].astype(dtype),
                         ((0, 0), (layer["conv_k"].shape[1] - 1, 0), (0, 0)))
        qkv = jax.nn.silu(sum(
            tap * padded[:, j:j + C, :].astype(jnp.float32)
            for j, tap in enumerate(layer["conv_k"].T)))
    q, k, v = jnp.split(qkv, [cfg.key_dim, 2 * cfg.key_dim], axis=-1)
    q = (_l2_normalised(q.reshape(B, C, n_k, d_k)) * d_k ** -0.5
         ).astype(dtype)
    k = _l2_normalised(k.reshape(B, C, n_k, d_k)).astype(dtype)
    v = v.astype(dtype)
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(layer["A_log"]) * jax.nn.softplus(a + layer["dt_bias"])
    with jax.named_scope("scan"):
        o = scan(q, k, v.reshape(B, C, n_v, d_v), g, beta, mask)
    normed = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                               + GATED_NORM_EPS)
    y = (normed * layer["gdn_norm"]).astype(dtype) \
        * jax.nn.silu(z.reshape(B, C, n_v, d_v))
    return y.reshape(B, C, n_v * d_v) @ layer["gdn_out"].astype(dtype)


def _shared_expert(h: jax.Array, layer: Dict) -> jax.Array:
    gate = jax.nn.sigmoid(h @ layer["shared_gate"].astype(h.dtype))
    return gate[..., None] * seq_block.swiglu(
        h, layer["shared_w1"], layer["shared_w3"], layer["shared_w2"])


# ---- the encoder ---------------------------------------------------------

SCAN_COLUMNS = 3


def encode_qwen3_next(params: Dict, source_ids: jax.Array,
                      path_ids: jax.Array, target_ids: jax.Array,
                      mask: jax.Array, *, dims: ModelDims, mesh=None,
                      dropout_rng: Optional[jax.Array] = None,
                      dropout_keep_rate: float = 1.0,
                      compute_dtype=jnp.float32,
                      use_pallas: bool = False, staircase=None
                      ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The encode contract (registry.EncoderSpec): (code [B, 3E] in
    the compute dtype, pool attention [B, C] f32, aux), aux being int32
    [layers, held + 3 + 3]: per layer what `lfm2_moe`'s aux holds of
    its experts (the rows each held expert took, the valid tokens, the
    row bound, whether the layer ran at it; summed over the mesh's
    devices), then the slots a chunk, the chunks its scan ran over and
    those of them with a valid slot (zeros on an attention layer). The
    train step hands it to the spec's recorder (`obs.route`); the other
    steps let it fall. `use_pallas` is taken and not read: the rule is
    plain JAX, the grouped product XLA's own kernel on the TPU.

    `staircase` (training only; `embed_contexts` has who checks it)
    also bounds the scans: chunk n runs over the rows its first slot's
    rectangle keeps, on each device its own, and "the chunks its scan
    ran over" is that bound's sum over the devices; with none it is
    every method's every chunk. The attention layers' core runs by the
    query blocks `seq_block.core_blocks` makes of it, every layer's
    feed-forward half over the positions of
    `seq_block.ff_rectangles`."""
    del use_pallas
    cfg, sub = dims.qwen, params["qwen"]

    def norm(x, w):
        return _rms_norm(x, 1.0 + w, cfg.rms_norm_eps)

    emb = embed_contexts(params, source_ids, path_ids, target_ids,
                         dropout_rng, dropout_keep_rate, compute_dtype,
                         staircase, mesh)

    def experts(h, mask, router, w1, w3, w2):
        return seq_block.routed_experts(
            h, mask, lambda tokens: route(tokens, router, None,
                                          cfg.num_experts_per_tok,
                                          score="softmax"),
            w1, w3, w2, first_expert=cfg.first_expert, routed=cfg.routed)

    B, C = mask.shape
    L, chunks = delta_rule.chunk_len(C), delta_rule.chunks_of(C)
    # the staircase is one device's rows, longest bag first: from a
    # chunk's first slot on, the rows its rectangle leaves out are PAD
    bound = None if staircase is None else tuple(
        rows_kept(staircase, n * L) for n in range(chunks))
    scan = functools.partial(delta_rule.gated_delta_rule, rows=bound)
    devices = seq_block.batch_devices(mesh)
    if mesh is not None:
        # each device routes and scans its own rows of the batch
        from code2vec_tpu.parallel.sharding import shard_map_over_batch
        experts = shard_map_over_batch(experts, mesh,
                                       (True, True) + (False,) * 4)
        scan = shard_map_over_batch(scan, mesh, (True,) * 6)

    scanned = jnp.stack([jnp.int32(L),
                         jnp.int32(B * chunks if bound is None
                                   else devices * sum(bound)),
                         delta_rule.live_chunks(mask)])
    blocks = seq_block.core_blocks(staircase, mesh, C)
    rectangles = seq_block.ff_rectangles(staircase, mesh, C)

    def mixer(h, layer):
        if "in_qkvz" in layer:
            return _gated_delta_net(h, mask, layer, cfg, scan)
        return seq_block.attention(
            h, mask, layer, heads=cfg.num_attention_heads,
            kv_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
            theta=cfg.rope_theta, norm=norm, turned=cfg.rotary_dim,
            gated=True, blocks=blocks)

    def ff(h, mask, layer):
        out, counts = experts(h, mask, layer["router"], layer["w1"],
                              layer["w3"], layer["w2"])
        with jax.named_scope("shared"):
            return out + _shared_expert(h, layer), counts

    def layer_fn(i: int):
        linear = cfg.layer_types[i] == LINEAR
        run = seq_block.residual_layer(
            i, norm=norm, mixer_scope="gdn" if linear else "attn",
            mixer=mixer, ff=ff, mask=mask, rectangles=rectangles)

        def counted(x, layer):
            x, counts = run(x, layer)
            return x, jnp.concatenate(
                [counts, scanned if linear else jnp.zeros_like(scanned)])

        return counted

    return seq_block.run_block(
        sub, emb, mask, compute_dtype, layer_fn=layer_fn, norm=norm,
        counts_width=cfg.num_experts + 3 + SCAN_COLUMNS)


# ---- the spec ------------------------------------------------------------

def _init(rng: jax.Array, dims: ModelDims) -> Dict:
    return init_qwen_params(jax.random.fold_in(rng, 0x93e), dims)


def _sizes_from_config(cfg) -> Dict:
    """`--block_config`'s file (`check_config` has seen that it is
    named)."""
    with open(cfg.BLOCK_CONFIG) as f:
        return {"qwen": Qwen3NextDims.from_config(json.load(f))}


def _sizes_from_manifest(manifest: dict) -> Dict:
    return {"qwen": Qwen3NextDims.from_config(manifest["qwen"])}


def _check_config(cfg) -> None:
    seq_block.refuse_context_parallel(cfg, "qwen3_next")
    seq_block.require_block_config(cfg, "qwen3_next")


def _recorder():
    from code2vec_tpu.obs.route import RouteRecorder
    return RouteRecorder(scan=True)


SPEC = EncoderSpec(
    encode=encode_qwen3_next, params_key="qwen", init=_init,
    sizes_from_config=_sizes_from_config,
    sizes_from_manifest=_sizes_from_manifest, check_config=_check_config,
    eval_batch_at_most_train=True, scores_by_staircase=True,
    recorder=_recorder)
