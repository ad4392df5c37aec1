"""Granite-4.0-H's hybrid block as the path encoder (`--encoder
granite_hybrid`).

IBM's Granite 4.0-H Micro (`model_type` `granitemoehybrid`, 3B,
huggingface.co/ibm-granite/granite-4.0-h-micro `config.json`): a stack
whose layers mix by one of two operators, Mamba-2 state-space mixers in
nine layers of ten beside one grouped-query attention layer with no
position term, every layer's feed-forward one dense SwiGLU, and both
residual branches scaled. Here the stack runs over a method's
path-contexts in reader order: position = slot index, the reader fills
valid contexts from the left. `x` is [B, C, H], `m` the context mask,
`h` the normed input of a sub-layer, r = residual_multiplier.

  norm      RMSNorm(x) = w x / rms(x), w starts at 1, eps rms_norm_eps
  input     c = concat(tok[src], path[pth], tok[dst])     3E, dropout
            x = embedding_multiplier (c W_in) m           3E -> H
  layer i   x = x + r Mixer_i(RMSNorm(x)) ; x = x + r MLP(RMSNorm(x))
            Mixer_i by layer_types[i]: mamba or attention

  mamba (Mamba-2, `ops/delta_rule.ssd_scan`); n heads of P, state N,
  one group, d_inner = mamba_expand H = n P
            [z | xBC | dt] = h W_in                 H -> d_inner +
                                                    (d_inner + 2 N) + n
            xBC = silu(causal depthwise conv over (xBC m), mamba_d_conv
                  taps, + b, zeros before slot 0)   d_inner + 2 N channels
            [x | B | C] = xBC                       d_inner, N, N
            dt_t = softplus(dt_t + dt_bias) ; A = -exp(A_log)  a head
            per head, S_0 = 0 in R^{N x P}, float32:
              S_t = exp(dt_t A) S_{t-1} + B_t (dt_t x_t)^T
              y_t = S_t^T C_t + D x_t
            a masked slot leaves the state as it is and reads S^T C = 0
            Mixer = RMSNorm_{d_inner}(y silu(z)) W_out    the gate before
                                                          the norm

  attention (`seq_block.attention`, no position term)
            q = h W_q, k = h W_k, v = h W_v       n and n_kv heads of
                                                  H / n, no bias, no norm
            scores = attention_multiplier q k^T, causal and padding
            mask, softmax in float32; kv head j serves query heads
            j n/n_kv ..
            Mixer = concat(heads) W_o

  MLP       (silu(h W1) (h W3)) W2                width
                                                  shared_intermediate_size
  output    RMSNorm ; the product's learned-query pool over valid
            slots at width H ; code = pooled W_out2       H -> 3E

Departures from the model, all of them the product's: the vocabulary
and the head are the three code2vec tables and the sampled softmax over
the name table, so there is no LM head and `logits_scaling` has nothing
to scale; the two projections W_in and W_out2 stand where the model's
own embedding and head would; a sequence is a bag of at most
MAX_CONTEXTS contexts in reader order. Assumed: the MLP's one input
matrix [g | u] is held as two, W1 and W3 (the same model); the initial
values as Mamba-2 draws them (A_log = log(1 .. n), dt_bias the inverse
softplus of a log-uniform dt in [1e-3, 1e-1], D = 1, the conv's taps
and bias uniform in +-1/sqrt(taps)), norms at 1, every matrix
variance-scaled uniform.

No layer is shared between chips: the model's 40 layers would lie on
four chips as pipeline stages of one period each, this one a stage.
Under a mesh every device scans its own rows of the batch
(`shard_map`), the weights replicated. Each layer is rematerialised in
the backward pass. What this block shares with the other blocks is
`models/seq_block.py`'s, and with `qwen3_next` the chunked scans'
machinery (`ops/delta_rule.py`).
"""

from __future__ import annotations

import dataclasses
import functools
import json
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from code2vec_tpu.data.staircase import rows_kept
from code2vec_tpu.models import seq_block
from code2vec_tpu.models.encoder import ModelDims, embed_contexts
from code2vec_tpu.models.registry import EncoderSpec
from code2vec_tpu.models.transformer_encoder import _rms_norm
from code2vec_tpu.ops import delta_rule

MAMBA, ATTENTION = "mamba", "attention"
# Mamba-2's draw of the initial step: dt log-uniform in [DT_MIN, DT_MAX],
# no smaller than DT_FLOOR
DT_MIN, DT_MAX, DT_FLOOR = 1e-3, 1e-1, 1e-4


@dataclasses.dataclass(frozen=True)
class GraniteDims:
    """The block's sizes, under the keys of the model's own
    `config.json` (`model_type` `granitemoehybrid`); every one comes
    from the file `--block_config` names."""
    num_hidden_layers: int
    layer_types: Tuple[str, ...]
    hidden_size: int
    num_attention_heads: int
    num_key_value_heads: int
    attention_multiplier: float
    residual_multiplier: float
    embedding_multiplier: float
    rms_norm_eps: float
    mamba_n_heads: int
    mamba_d_head: int
    mamba_d_state: int
    mamba_n_groups: int
    mamba_d_conv: int
    mamba_expand: int
    shared_intermediate_size: int

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.mamba_d_state

    @classmethod
    def from_config(cls, config: dict) -> "GraniteDims":
        """From a parsed `config.json`. Keys the block does not read
        are passed over; a switch the block does not implement is an
        error, not a silent default."""
        fixed = {"num_local_experts": 0, "mamba_n_groups": 1,
                 "mamba_conv_bias": True, "position_embedding_type": "nope",
                 "attention_bias": False, "mamba_proj_bias": False,
                 "hidden_act": "silu", "normalization_function": "rmsnorm"}
        for k, want in fixed.items():
            if config.get(k, want) != want:
                raise ValueError(f"granite_hybrid implements {k}={want!r} "
                                 f"only (the file gives {config[k]!r})")
        kw = {f.name: config[f.name] for f in dataclasses.fields(cls)
              if f.name in config}
        missing = [f.name for f in dataclasses.fields(cls) if f.name not in kw]
        if missing:
            raise ValueError("granite_hybrid: the block's file "
                             f"(--block_config) lacks {missing}")
        kw["layer_types"] = tuple(kw["layer_types"])
        for k in ("attention_multiplier", "residual_multiplier",
                  "embedding_multiplier"):
            kw[k] = float(kw[k])
        dims = cls(**kw)
        dims.check()
        return dims

    def check(self) -> None:
        if len(self.layer_types) != self.num_hidden_layers or \
                set(self.layer_types) - {MAMBA, ATTENTION}:
            raise ValueError(
                f"granite_hybrid: layer_types {list(self.layer_types)} is "
                f"not {self.num_hidden_layers} of {MAMBA!r} and "
                f"{ATTENTION!r}")
        if self.d_inner != self.mamba_expand * self.hidden_size:
            raise ValueError(
                "granite_hybrid: mamba_n_heads x mamba_d_head must be "
                "mamba_expand x hidden_size")
        if self.hidden_size % self.num_attention_heads or \
                self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("granite_hybrid: heads must divide "
                             "hidden_size, kv heads the heads")


def init_granite_params(rng: jax.Array, dims: ModelDims) -> Dict:
    """The "granite" subtree, every leaf from a key of its own."""
    cfg = dims.granite
    D, H = dims.context_vector_size, cfg.hidden_size
    f32 = jnp.float32
    init = jax.nn.initializers.variance_scaling(1.0, "fan_avg", "uniform")
    k_in, k_out, k_pool = jax.random.split(rng, 3)
    n, I = cfg.mamba_n_heads, cfg.shared_intermediate_size
    tap = 1.0 / math.sqrt(cfg.mamba_d_conv)
    layers = []
    for i, kind in enumerate(cfg.layer_types):
        k = jax.random.split(jax.random.fold_in(rng, 100 + i), 10)
        layer = {"op_norm": jnp.ones((H,), f32),
                 "ff_norm": jnp.ones((H,), f32),
                 "w1": init(k[0], (H, I), f32), "w3": init(k[1], (H, I), f32),
                 "w2": init(k[2], (I, H), f32)}
        if kind == MAMBA:
            dt = jnp.maximum(jnp.exp(jax.random.uniform(
                k[6], (n,), f32, math.log(DT_MIN), math.log(DT_MAX))),
                DT_FLOOR)
            layer.update(
                ssm_in=init(k[3], (H, cfg.d_inner + cfg.conv_dim + n), f32),
                conv_w=jax.random.uniform(
                    k[4], (cfg.conv_dim, cfg.mamba_d_conv), f32, -tap, tap),
                conv_b=jax.random.uniform(k[5], (cfg.conv_dim,), f32,
                                          -tap, tap),
                A_log=jnp.log(jnp.arange(1, n + 1, dtype=f32)),
                dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
                D=jnp.ones((n,), f32),
                ssm_norm=jnp.ones((cfg.d_inner,), f32),
                ssm_out=init(k[7], (cfg.d_inner, H), f32))
        else:
            hd = cfg.head_dim
            kv = cfg.num_key_value_heads * hd
            layer.update(
                q=init(k[3], (H, cfg.num_attention_heads * hd), f32),
                k=init(k[4], (H, kv), f32), v=init(k[5], (H, kv), f32),
                o=init(k[6], (cfg.num_attention_heads * hd, H), f32))
        layers.append(layer)
    return {"in_proj": init(k_in, (D, H), f32),
            "out_proj": init(k_out, (H, D), f32),
            "pool_query": init(k_pool, (H, 1), f32)[:, 0],
            "ln_f_scale": jnp.ones((H,), f32),
            "layers": layers}


# ---- the operator of its own ---------------------------------------------

def _mamba(h: jax.Array, mask: jax.Array, layer: Dict, cfg: GraniteDims,
           scan) -> jax.Array:
    """`scan` is `delta_rule.ssd_scan`, whole or under a mesh's
    `shard_map`."""
    dtype, f32 = h.dtype, jnp.float32
    C = h.shape[1]
    P, N = cfg.mamba_d_head, cfg.mamba_d_state
    z, xbc, dt = jnp.split(h @ layer["ssm_in"].astype(dtype),
                           [cfg.d_inner, cfg.d_inner + cfg.conv_dim],
                           axis=-1)
    with jax.named_scope("conv"):
        # the taps are summed in float32, as qwen3_next's are
        padded = jnp.pad(xbc * mask[..., None].astype(dtype),
                         ((0, 0), (cfg.mamba_d_conv - 1, 0), (0, 0)))
        xbc = jax.nn.silu(layer["conv_b"] + sum(
            tap * padded[:, j:j + C, :].astype(f32)
            for j, tap in enumerate(layer["conv_w"].T)))
    # x and the scan's o are [B, C, n P], head h in columns h P .. h P +
    # P - 1, so D is repeated over a head's P columns
    x, b, c = jnp.split(xbc, [cfg.d_inner, cfg.d_inner + N], axis=-1)
    dt = jax.nn.softplus(dt.astype(f32) + layer["dt_bias"])
    g = -jnp.exp(layer["A_log"]) * dt
    with jax.named_scope("scan"):
        o = scan(c[:, :, None].astype(dtype), b[:, :, None].astype(dtype),
                 x.astype(dtype), dt, g, mask)
    y = (o + jnp.repeat(layer["D"], P) * x) * jax.nn.silu(z.astype(f32))
    y = _rms_norm(y, layer["ssm_norm"], cfg.rms_norm_eps)
    return y.astype(dtype) @ layer["ssm_out"].astype(dtype)


# ---- the encoder ---------------------------------------------------------

def encode_granite_hybrid(params: Dict, source_ids: jax.Array,
                          path_ids: jax.Array, target_ids: jax.Array,
                          mask: jax.Array, *, dims: ModelDims, mesh=None,
                          dropout_rng: Optional[jax.Array] = None,
                          dropout_keep_rate: float = 1.0,
                          compute_dtype=jnp.float32,
                          use_pallas: bool = False, staircase=None
                          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The encode contract (registry.EncoderSpec): (code [B, 3E] in
    the compute dtype, pool attention [B, C] f32, aux), aux being int32
    [mamba layers, 3]: per layer the slots a chunk, the chunks its scan
    ran over and those of them with a valid slot, as `qwen3_next`'s
    last three columns (the train step hands it to the spec's recorder,
    `obs.route`, which writes `ssm/scan`; the other steps let it fall).
    `use_pallas` is taken and not read: the scan is plain JAX.

    `staircase` (training only; `embed_contexts` has who checks it)
    bounds the scans as it bounds `qwen3_next`'s: chunk n runs over the
    rows its first slot's rectangle keeps. The attention layer's core
    runs by the query blocks `seq_block.core_blocks` makes of it, every
    layer's MLP over the positions of `seq_block.ff_rectangles`."""
    del use_pallas
    cfg, sub = dims.granite, params["granite"]
    norm = functools.partial(_rms_norm, eps=cfg.rms_norm_eps)
    emb = embed_contexts(params, source_ids, path_ids, target_ids,
                         dropout_rng, dropout_keep_rate, compute_dtype,
                         staircase, mesh)

    B, C = mask.shape
    L, chunks = delta_rule.chunk_len(C), delta_rule.chunks_of(C)
    # the staircase is one device's rows, longest bag first: from a
    # chunk's first slot on, the rows its rectangle leaves out are PAD
    bound = None if staircase is None else tuple(
        rows_kept(staircase, n * L) for n in range(chunks))
    scan = functools.partial(delta_rule.ssd_scan, rows=bound)
    devices = seq_block.batch_devices(mesh)
    if mesh is not None:
        # each device scans its own rows of the batch
        from code2vec_tpu.parallel.sharding import shard_map_over_batch
        scan = shard_map_over_batch(scan, mesh, (True,) * 6)
    scanned = jnp.stack([jnp.int32(L),
                         jnp.int32(B * chunks if bound is None
                                   else devices * sum(bound)),
                         delta_rule.live_chunks(mask)])
    blocks = seq_block.core_blocks(staircase, mesh, C)
    rectangles = seq_block.ff_rectangles(staircase, mesh, C)

    def mixer(h, layer):
        if "ssm_in" in layer:
            return _mamba(h, mask, layer, cfg, scan)
        return seq_block.attention(
            h, mask, layer, heads=cfg.num_attention_heads,
            kv_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
            theta=None, norm=None, blocks=blocks,
            scale=cfg.attention_multiplier)

    def mlp(h, mask, layer):
        return seq_block.swiglu(h, layer["w1"], layer["w3"],
                                layer["w2"]), None

    def layer_fn(i: int):
        mamba = cfg.layer_types[i] == MAMBA
        run = seq_block.residual_layer(
            i, norm=norm, mixer_scope="ssm" if mamba else "attn",
            mixer=mixer, ff=mlp, mask=mask, ff_scope="mlp",
            rectangles=rectangles,
            residual_multiplier=cfg.residual_multiplier)
        if not mamba:
            return run

        def counted(x, layer):
            return run(x, layer)[0], scanned

        return counted

    return seq_block.run_block(
        sub, emb, mask, compute_dtype, layer_fn=layer_fn, norm=norm,
        counts_width=3, input_multiplier=cfg.embedding_multiplier)


# ---- the spec ------------------------------------------------------------

def _init(rng: jax.Array, dims: ModelDims) -> Dict:
    return init_granite_params(jax.random.fold_in(rng, 0x6a4), dims)


def _sizes_from_config(cfg) -> Dict:
    """`--block_config`'s file (`check_config` has seen that it is
    named)."""
    with open(cfg.BLOCK_CONFIG) as f:
        return {"granite": GraniteDims.from_config(json.load(f))}


def _sizes_from_manifest(manifest: dict) -> Dict:
    return {"granite": GraniteDims.from_config(manifest["granite"])}


def _check_config(cfg) -> None:
    seq_block.refuse_context_parallel(cfg, "granite_hybrid")
    seq_block.require_block_config(cfg, "granite_hybrid")


def _recorder():
    from code2vec_tpu.obs.route import RouteRecorder
    return RouteRecorder(scan=True, span="ssm/scan", experts=False)


SPEC = EncoderSpec(
    encode=encode_granite_hybrid, params_key="granite", init=_init,
    sizes_from_config=_sizes_from_config,
    sizes_from_manifest=_sizes_from_manifest, check_config=_check_config,
    eval_batch_at_most_train=True, scores_by_staircase=True,
    recorder=_recorder)
