"""The plain reference of configuration `java-large-joyai`: JoyAI-LLM-Flash's
decoder block (jdopensource/JoyAI-LLM-Flash `config.json`, `model_type`
`joyai_llm_flash`) as code2vec's path encoder, one chip's share of its
experts, trained three steps in straightforward `jax.numpy` float32 at the
highest matmul precision.

It imports nothing of the program. From `reference.py` and
`reference_lfm2moe.py` it takes what the product and the other blocks
share: the three tables' law and the step's keys, the dropout mask, the
negatives and the sampled softmax, Adafactor and Adam written out, the
warm-up and cosine rate, the tables' storing in their stated dtype, the
RMSNorm and the SwiGLU. Its own: the block's weights from the seed (every
leaf from a key of its own, an expert's from its index in the whole layer)
and the block's equations. `x` is [b, C, H], `m` the context mask, `h` a
sub-layer's normed input, position = slot index t:

  norm    RMSNorm(x) = w x / rms(x), w starts at 1, eps rms_norm_eps
  input   c = concat(tok[src], path[pth], tok[dst]), dropout ; x = (c W_in) m
  layer   x = x + MLA(RMSNorm(x)) ; x = x + FF(RMSNorm(x))
  MLA     c_q = RMSNorm(h W_qa) ; q = c_q W_qb, n heads of
          [q_nope | q_rope] (qk_nope_head_dim + qk_rope_head_dim)
          [c_kv | k_rope] = h W_kva ; c_kv = RMSNorm(c_kv) ; k_rope is ONE
          head of qk_rope_head_dim, every query head's
          a head of [k_nope | v] = c_kv W_kvb (qk_nope_head_dim + v_head_dim)
          the rotary term turns the pairs (2i, 2i + 1) of q_rope and of
          k_rope at slot t by the angle t theta^(-2i / qk_rope_head_dim)
          (`rotary_pairs`: rope_interleave; no yarn term)
          k = [k_nope | k_rope, the same for every head] ; q = [q_nope | q_rope]
          scores = q k^T / sqrt(qk_nope_head_dim + qk_rope_head_dim) ;
          causal and padding mask ; softmax
          MLA = concat over heads of (att v) W_o
  FF      layers before first_k_dense_replace: (silu(h W1) (h W3)) W2
          the rest: s = sigmoid(h W_r) ; chosen = top K of s + bias (the
          bias selects only: a seeded buffer, held fixed) ;
          p_e = routed_scaling_factor s_e / (sum of the K chosen s + 1e-20)
          FF = sum over chosen e held here of p_e (silu(h W1_e) (h W3_e)) W2_e
               + (silu(h V1) (h V3)) V2     the shared expert, ungated, for
                                            every position
          every held expert is applied to every position and weighted by
          its p or 0 (no sort, no kernel); a masked slot is routed nowhere;
          what the experts held elsewhere would add is left out
  output  RMSNorm ; a = softmax_i(x_i . q | valid) ; code = (sum a x) W_out2
  loss    the product's sampled softmax (`reference._loss_sum`)

Nothing is approximated to fit the chip; an expert's product is recomputed
in the backward pass and not kept (`jax.checkpoint`, which changes no
value), as `reference_lfm2moe.py` does.

`quant="fp8"` is the control, as in `reference.py`: tables stored and
every matmul's operands rounded to 8-bit floats, forward and backward. The
router's scores stay exact there, as the program takes them in float32
whatever its compute dtype. `fault` plants one of the block's own faults,
each one sentence of the mathematics left out: "expert_left_out" (the last
held expert adds nothing), "shared_left_out", "scale_one" (the routed sum's
2.5 read as 1), "no_kv_norm" (c_kv enters W_kvb unnormed), "k_rope_unturned"
(the shared rotary key is not turned; q_rope is), "no_causal_mask".
"""

from __future__ import annotations

import math
from typing import List, Optional

import numpy as np

import reference as base
import reference_lfm2moe as lfm

TABLES = base.TABLES
BIAS_SCALE = lfm.BIAS_SCALE
ROUTE_EPS = 1e-20
FAULTS = ("expert_left_out", "shared_left_out", "scale_one", "no_kv_norm",
          "k_rope_unturned", "no_causal_mask")


# ---- weights ------------------------------------------------------------

def make_weights(seed: int, spec: dict):
    """(params as a flat {path: f32 array} dict, the key the steps fold).
    The tables, `transform` and `attention` (which this encoder leaves
    unused) are `reference.make_weights`'s; the block's leaves follow."""
    import jax
    import jax.numpy as jnp

    p, key = base.make_weights(seed, dict(spec, encoder="bag"))
    _, k_init = jax.random.split(jax.random.PRNGKey(seed))
    rng = jax.random.fold_in(k_init, 0x10a1)
    init = jax.nn.initializers.variance_scaling(1.0, "fan_avg", "uniform")
    f32 = jnp.float32
    D, H = 3 * spec["embedding"], spec["hidden_size"]
    n, r_q, r_kv = (spec["num_attention_heads"], spec["q_lora_rank"],
                    spec["kv_lora_rank"])
    nope, rope, v_dim = (spec["qk_nope_head_dim"], spec["qk_rope_head_dim"],
                         spec["v_head_dim"])
    k_in, k_out, k_pool = jax.random.split(rng, 3)
    p["joyai/in_proj"] = init(k_in, (D, H), f32)
    p["joyai/out_proj"] = init(k_out, (H, D), f32)
    p["joyai/pool_query"] = init(k_pool, (H, 1), f32)[:, 0]
    p["joyai/ln_f_scale"] = jnp.ones((H,), f32)
    for i in range(spec["num_hidden_layers"]):
        pre = f"joyai/layers/{i}/"
        k = jax.random.split(jax.random.fold_in(rng, 100 + i), 12)
        p[pre + "op_norm"] = jnp.ones((H,), f32)
        p[pre + "ff_norm"] = jnp.ones((H,), f32)
        p[pre + "q_a"] = init(k[0], (H, r_q), f32)
        p[pre + "q_a_norm"] = jnp.ones((r_q,), f32)
        p[pre + "q_b"] = init(k[1], (r_q, n * (nope + rope)), f32)
        p[pre + "kv_a"] = init(k[2], (H, r_kv + rope), f32)
        p[pre + "kv_a_norm"] = jnp.ones((r_kv,), f32)
        p[pre + "kv_b"] = init(k[3], (r_kv, n * (nope + v_dim)), f32)
        p[pre + "o"] = init(k[4], (n * v_dim, H), f32)
        if i < spec["first_k_dense_replace"]:
            I = spec["intermediate_size"]
            p[pre + "w1"] = init(k[5], (H, I), f32)
            p[pre + "w3"] = init(k[6], (H, I), f32)
            p[pre + "w2"] = init(k[7], (I, H), f32)
            continue
        F, E = spec["moe_intermediate_size"], spec["num_routed_experts"]
        Fs = spec["n_shared_experts"] * F
        p[pre + "router"] = init(k[5], (H, E), f32)
        p[pre + "expert_bias"] = BIAS_SCALE * jax.random.normal(
            k[6], (E,), f32)
        w1, w3, w2 = [], [], []
        for e in range(spec["first_expert"],
                       spec["first_expert"] + spec["n_routed_experts"]):
            k1, k3, k2 = jax.random.split(jax.random.fold_in(k[7], e), 3)
            w1.append(init(k1, (H, F), f32))
            w3.append(init(k3, (H, F), f32))
            w2.append(init(k2, (F, H), f32))
        p[pre + "w1"], p[pre + "w3"], p[pre + "w2"] = (
            jnp.stack(w1), jnp.stack(w3), jnp.stack(w2))
        p[pre + "shared_w1"] = init(k[8], (H, Fs), f32)
        p[pre + "shared_w3"] = init(k[9], (H, Fs), f32)
        p[pre + "shared_w2"] = init(k[10], (Fs, H), f32)
    return p, key


# ---- forward ------------------------------------------------------------

def rotary_pairs(x, theta):
    """x [..., C, d]: at slot t the pair (x_2i, x_2i+1) turns by the angle
    t theta^(-2i / d)."""
    import jax.numpy as jnp

    C, d = x.shape[-2], x.shape[-1]
    angle = jnp.arange(C, dtype=jnp.float32)[:, None] \
        * theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)[None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    turned = jnp.stack([even * jnp.cos(angle) - odd * jnp.sin(angle),
                        even * jnp.sin(angle) + odd * jnp.cos(angle)],
                       axis=-1)
    return turned.reshape(x.shape)


def expert_layer(h, valid, router, bias, w1, w3, w2, *, first: int,
                 per_token: int, scale: float, mm=lambda x, w: x @ w,
                 fault=None):
    """The routed part of one layer's feed-forward for h [..., H]: the
    router scores every expert of the layer (`router` [H, E], `bias`
    [E]); `w1`, `w3` [held, H, F] and `w2` [held, F, H] are experts
    `first ..`, each applied to every position and weighted by its p
    or 0."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.sigmoid(h @ router)
    _, chosen = jax.lax.top_k(s + bias, per_token)
    s_chosen = jnp.take_along_axis(s, chosen, axis=-1)
    if fault == "scale_one":
        scale = 1.0
    share = scale * s_chosen / (jnp.sum(s_chosen, axis=-1, keepdims=True)
                                + ROUTE_EPS)
    count = w1.shape[0] - (fault == "expert_left_out")

    def one(acc, expert):
        e_w1, e_w3, e_w2, e = expert
        gate = jnp.sum(jnp.where(chosen == first + e, share, 0.0),
                       axis=-1) * valid
        return acc + gate[..., None] * lfm.swiglu(h, e_w1, e_w3, e_w2,
                                                  mm), None

    # recomputed in the backward pass: sixteen experts' products over
    # every position would else be kept, layer by layer
    out, _ = jax.lax.scan(
        jax.checkpoint(one), jnp.zeros_like(h),
        (w1[:count], w3[:count], w2[:count], jnp.arange(count)))
    return out


def encode(p, c, mask, q, spec, fault=None):
    """Code vectors [b, 3E] of contexts c [b, C, 3E] (dropout applied)."""
    import jax
    import jax.numpy as jnp

    eps, theta = spec["rms_norm_eps"], spec["rope_theta"]
    n = spec["num_attention_heads"]
    nope, rope, v_dim = (spec["qk_nope_head_dim"], spec["qk_rope_head_dim"],
                         spec["v_head_dim"])
    r_kv = spec["kv_lora_rank"]
    B, C, _ = c.shape
    valid = mask > 0

    def mm(x, w):
        return q.b(q.f(x) @ q.f(w.T).T)

    def latent_attention(h, pre):
        c_q = lfm._rms(mm(h, p[pre + "q_a"]), p[pre + "q_a_norm"], eps)
        qh = mm(c_q, p[pre + "q_b"]).reshape(B, C, n, nope + rope) \
            .transpose(0, 2, 1, 3)                        # [B, n, C, 192]
        kv_a = mm(h, p[pre + "kv_a"])
        c_kv, k_rope = kv_a[..., :r_kv], kv_a[..., r_kv:]  # k_rope [B, C, 64]
        if fault != "no_kv_norm":
            c_kv = lfm._rms(c_kv, p[pre + "kv_a_norm"], eps)
        kv = mm(c_kv, p[pre + "kv_b"]).reshape(B, C, n, nope + v_dim) \
            .transpose(0, 2, 1, 3)                        # [B, n, C, 256]
        k_nope, vh = kv[..., :nope], kv[..., nope:]
        q_rope = rotary_pairs(qh[..., nope:], theta)
        if fault != "k_rope_unturned":
            k_rope = rotary_pairs(k_rope, theta)
        # the one rotary key, the same under every head
        kh = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, None], (B, n, C, rope))],
            axis=-1)
        qh = jnp.concatenate([qh[..., :nope], q_rope], axis=-1)
        scores = q.b(jnp.einsum("bhqd,bhkd->bhqk", q.f(qh), q.f(kh))) \
            / math.sqrt(nope + rope)
        slot = jnp.arange(C)
        seen = valid[:, None, None, :]
        if fault != "no_causal_mask":
            seen = seen & (slot[None, :] <= slot[:, None])[None, None]
        att = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
        o = q.b(jnp.einsum("bhqk,bhkd->bhqd", q.f(att),
                           q.f(vh.swapaxes(-1, -2)).swapaxes(-1, -2)))
        return mm(o.transpose(0, 2, 1, 3).reshape(B, C, n * v_dim),
                  p[pre + "o"])

    def experts(h, pre):
        out = expert_layer(
            h, valid, p[pre + "router"], p[pre + "expert_bias"],
            p[pre + "w1"], p[pre + "w3"], p[pre + "w2"],
            first=spec["first_expert"], per_token=spec["num_experts_per_tok"],
            scale=spec["routed_scaling_factor"], mm=mm, fault=fault)
        if fault == "shared_left_out":
            return out
        return out + lfm.swiglu(h, p[pre + "shared_w1"], p[pre + "shared_w3"],
                                p[pre + "shared_w2"], mm)

    x = mm(c, p["joyai/in_proj"]) * mask[..., None]
    for i in range(spec["num_hidden_layers"]):
        pre = f"joyai/layers/{i}/"
        x = x + latent_attention(lfm._rms(x, p[pre + "op_norm"], eps), pre)
        h = lfm._rms(x, p[pre + "ff_norm"], eps)
        if i < spec["first_k_dense_replace"]:
            x = x + lfm.swiglu(h, p[pre + "w1"], p[pre + "w3"],
                               p[pre + "w2"], mm)
        else:
            x = x + experts(h, pre)
    x = lfm._rms(x, p["joyai/ln_f_scale"], eps)
    any_valid = jnp.sum(mask, -1, keepdims=True) > 0
    score = jnp.where(valid | ~any_valid, x @ p["joyai/pool_query"], -1e30)
    a = jax.nn.softmax(score, axis=-1)
    return mm(jnp.einsum("bc,bcd->bd", a, x), p["joyai/out_proj"])


# ---- one step's loss and gradient, block by block -----------------------

def _make_block_fn(spec: dict, batch: int, block: int, quant: Optional[str],
                   fault: Optional[str]):
    """`reference_lfm2moe._make_block_fn` with this module's `encode`."""
    import jax
    import jax.numpy as jnp

    C, D = spec["max_contexts"], 3 * spec["embedding"]
    keep_rate = spec["dropout_keep"]
    vocab = spec["targets"] + 2
    S = min(spec["num_sampled"], vocab)
    tries = base.effective_tries(S, vocab)
    q = base.rounding(quant)

    def block_loss(p, blk, drop_key, sampled, start):
        labels, src, pth, dst, mask, weights = blk
        keep = base._keep_rows(drop_key, keep_rate, (batch, C, D), start,
                               block)
        c = base._contexts(p, src, pth, dst, keep, keep_rate, q)
        code = encode(p, c, mask, q, spec, fault)
        return base._loss_sum(p, code, labels, weights, sampled, tries,
                              vocab, q)

    @jax.jit
    def negatives(sample_key):
        return base._negatives(sample_key, S, vocab)

    def accumulate(p, acc, loss_acc, blk, drop_key, sampled, start):
        with jax.default_matmul_precision("highest"):
            loss, g = jax.value_and_grad(block_loss)(
                p, blk, drop_key, sampled, start)
        return (jax.tree_util.tree_map(jnp.add, acc, g), loss_acc + loss)

    return negatives, jax.jit(accumulate, donate_argnums=(1, 2))


# ---- three steps --------------------------------------------------------

def follow(seed: int, spec: dict, batches: List[tuple], *, block: int,
           quant: Optional[str] = None,
           weights: Optional[List[np.ndarray]] = None,
           fault: Optional[str] = None) -> dict:
    """`reference_lfm2moe.follow` for this configuration: the losses, the
    first gradient's norm leaf by leaf and whole for the leaves outside
    the tables, and the norm of each leaf's change over the steps."""
    import jax

    assert fault is None or fault in FAULTS, fault
    p, key = make_weights(seed, spec)
    n = batches[0][0].shape[0]
    fns = _make_block_fn(spec, n, block, quant, fault)
    apply = base._make_apply()
    # the tables are stored as stated; the control stores them in 8 bits
    keep = base.rounding(quant).store if quant is not None else \
        lfm.stored_as(spec["tables_dtype"])
    store = jax.jit(lambda t: {k: (keep(v) if k in TABLES else v)
                               for k, v in t.items()}, donate_argnums=0)
    p = store(p)
    # the copy the change is measured from waits on the host: weights,
    # gradient, its block's share and Adam's moments fill the chip
    p0 = jax.device_get(p)
    state: dict = {}
    losses, grad_norms, dense_grads = [], None, None
    for step, batch in enumerate(batches):
        if weights is not None:
            batch = tuple(batch[:5]) + (weights[step],)
        loss, grads = base.loss_and_grad(
            p, batch, jax.random.fold_in(key, step), fns, block)
        losses.append(float(loss))
        if step == 0:
            grad_norms = base._norms(grads)
            dense_grads = {k: np.asarray(v) for k, v in grads.items()
                           if k not in TABLES}
        p, state = apply(p, grads, state, step=step,
                         lr=lfm.learning_rate(step, spec))
        p = store(p)
    del state, grads
    change = base._norms(jax.jit(
        lambda a, b: {k: a[k] - b[k] for k in a}, donate_argnums=(0, 1))(
            p, jax.device_put(p0)))
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change, "dense_grads": dense_grads}
