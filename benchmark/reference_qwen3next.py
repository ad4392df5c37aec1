"""The plain reference of configuration `java-large-qwen3next`: Qwen3-Next's
decoder block (Qwen/Qwen3-Next-80B-A3B-Instruct `config.json`,
`model_type` `qwen3_next`) as code2vec's path encoder, one chip's share of
its experts, trained three steps in straightforward `jax.numpy` float32 at
the highest matmul precision.

It imports nothing of the program. From `reference.py` and
`reference_lfm2moe.py` it takes what the product and the other block
share: the three tables' law and the step's keys, the dropout mask, the
negatives and the sampled softmax, Adafactor and Adam written out, the
warm-up and cosine rate, the tables' storing in their stated dtype, the
SwiGLU. Its own: the block's weights from the seed (every leaf from a key
of its own, an expert's from its index in the whole layer) and the block's
equations. `x` is [b, C, H], `m` the context mask, `h` a sub-layer's
normed input, position = slot index:

  norm    RMSNorm0(x) = x / rms(x) (1 + w), w starts at 0, eps rms_norm_eps
  input   c = concat(tok[src], path[pth], tok[dst]), dropout ; x = (c W_in) m
  layer i x = x + Mixer_i(RMSNorm0(x)) ; x = x + MoE(RMSNorm0(x)) ;
          full_attention where (i + 1) % full_attention_interval == 0,
          else linear_attention
  linear_attention (gated DeltaNet), n_k key heads, n_v value heads
          [q, k, v, z] = h W_qkvz ; [b, a] = h W_ba
          [q, k, v] = silu(causal depthwise conv of concat(q, k, v) m,
          taps linear_conv_kernel_dim, zeros before slot 0)
          beta = sigmoid(b) ; g = -exp(A_log) softplus(a + dt_bias)
          q, k: x / sqrt(sum x^2 + 1e-6) over d_k, q / sqrt(d_k); key head
          j serves value heads j n_v/n_k ..
          token by token, a value head, S_0 = 0 [d_k, d_v]:
            S' = exp(g_t) S ; S = S' + k_t (beta_t (v_t - S'^T k_t))^T ;
            o_t = S^T q_t ; a masked slot: beta = 0, g = 0, o = 0
          (one `lax.scan` over the slots, no chunks)
          y = w (o / rms(o)) silu(z) over d_v, eps 1e-6 ; Mixer = y W_out
  full_attention (gated)
          [q, gate] = h W_q, split a head ; k = h W_k ; v = h W_v
          RMSNorm0 over each head of q and of k ; rotary (theta,
          rotate-half) over the first partial_rotary_factor x head_dim of a
          head ; scores over sqrt(head_dim) ; causal and padding mask ;
          softmax ; kv head j serves query heads j n/n_kv ..
          Mixer = (concat(heads) sigmoid(gate)) W_o
  MoE     p = softmax(h W_r) over all E ; chosen = top K ; p_e = p_e / sum
          of the K chosen p ; routed = sum over chosen e held here of
          p_e (silu(h W1_e) (h W3_e)) W2_e, every held expert applied to
          every position and weighted by p or 0 (no sort, no kernel); a
          masked slot is routed nowhere; what the experts held elsewhere
          would add is left out
          MoE = routed + sigmoid(h w_s) (silu(h V1) (h V3)) V2
  output  RMSNorm0 ; a = softmax_i(x_i . q | valid) ; code = (sum a x) W_out2
  loss    the product's sampled softmax (`reference._loss_sum`)

Nothing is approximated to fit the chip, but two things are recomputed
in the backward pass and not kept (`jax.checkpoint`, which changes no
value): a slot's step of the recurrence, whose [b, n_v, d_k, d_v] states
would else all be held, and an expert's product, as
`reference_lfm2moe.py` does.

`quant="fp8"` is the control, as in `reference.py`: tables stored and
every matmul's operands rounded to 8-bit floats, forward and backward (the
recurrence's products among them). The router's scores stay exact there, as
the program takes them in float32 whatever its compute dtype. `fault`
plants one of the block's own faults: "expert_left_out" (the last held
expert adds nothing), "shared_left_out", "no_decay" (g = 0), "beta_one",
"no_causal_mask", "no_output_gate". (A state rounded to bfloat16 after
every slot was one more until REVIEW 32: it moved every compared number
by 0.0016 or less on the chip, two hundred times under a sound bfloat16
step's own distance, so no limit a sound run passes can fail it; what
holds the state to float32 is tests/test_delta_rule.py on the CPU.)
"""

from __future__ import annotations

import math
from typing import List, Optional

import numpy as np

import reference as base
import reference_lfm2moe as lfm

TABLES = base.TABLES
FAULTS = ("expert_left_out", "shared_left_out", "no_decay", "beta_one",
          "no_causal_mask", "no_output_gate")
LINEAR = "linear_attention"


def layer_types(spec: dict) -> List[str]:
    return ["full_attention"
            if (i + 1) % spec["full_attention_interval"] == 0 else LINEAR
            for i in range(spec["num_hidden_layers"])]


# ---- weights ------------------------------------------------------------

def make_weights(seed: int, spec: dict):
    """(params as a flat {path: f32 array} dict, the key the steps fold).
    The tables, `transform` and `attention` (which this encoder leaves
    unused) are `reference.make_weights`'s; the block's leaves follow."""
    import jax
    import jax.numpy as jnp

    p, key = base.make_weights(seed, dict(spec, encoder="bag"))
    _, k_init = jax.random.split(jax.random.PRNGKey(seed))
    rng = jax.random.fold_in(k_init, 0x93e)
    init = jax.nn.initializers.variance_scaling(1.0, "fan_avg", "uniform")
    f32 = jnp.float32
    D, H = 3 * spec["embedding"], spec["hidden_size"]
    n_k, n_v = spec["linear_num_key_heads"], spec["linear_num_value_heads"]
    d_k, d_v = spec["linear_key_head_dim"], spec["linear_value_head_dim"]
    hd, n = spec["head_dim"], spec["num_attention_heads"]
    kv = spec["num_key_value_heads"] * hd
    F, Fs = spec["moe_intermediate_size"], \
        spec["shared_expert_intermediate_size"]
    E = spec["num_routed_experts"]
    k_in, k_out, k_pool = jax.random.split(rng, 3)
    p["qwen/in_proj"] = init(k_in, (D, H), f32)
    p["qwen/out_proj"] = init(k_out, (H, D), f32)
    p["qwen/pool_query"] = init(k_pool, (H, 1), f32)[:, 0]
    p["qwen/ln_f_scale"] = jnp.zeros((H,), f32)
    for i, kind in enumerate(layer_types(spec)):
        pre = f"qwen/layers/{i}/"
        k = jax.random.split(jax.random.fold_in(rng, 100 + i), 12)
        p[pre + "op_norm"] = jnp.zeros((H,), f32)
        p[pre + "ff_norm"] = jnp.zeros((H,), f32)
        if kind == LINEAR:
            p[pre + "in_qkvz"] = init(
                k[0], (H, 2 * n_k * d_k + 2 * n_v * d_v), f32)
            p[pre + "in_ba"] = init(k[1], (H, 2 * n_v), f32)
            p[pre + "conv_k"] = jax.random.uniform(
                k[2], (2 * n_k * d_k + n_v * d_v,
                       spec["linear_conv_kernel_dim"]), f32, -0.5, 0.5)
            p[pre + "A_log"] = jnp.log(jax.random.uniform(
                k[3], (n_v,), f32, 0.0, 16.0))
            p[pre + "dt_bias"] = jnp.ones((n_v,), f32)
            p[pre + "gdn_norm"] = jnp.ones((d_v,), f32)
            p[pre + "gdn_out"] = init(k[4], (n_v * d_v, H), f32)
        else:
            p[pre + "q"] = init(k[0], (H, 2 * n * hd), f32)
            p[pre + "k"] = init(k[1], (H, kv), f32)
            p[pre + "v"] = init(k[2], (H, kv), f32)
            p[pre + "o"] = init(k[3], (n * hd, H), f32)
            p[pre + "q_norm"] = jnp.zeros((hd,), f32)
            p[pre + "k_norm"] = jnp.zeros((hd,), f32)
        p[pre + "router"] = init(k[5], (H, E), f32)
        w1, w3, w2 = [], [], []
        for e in range(spec["first_expert"],
                       spec["first_expert"] + spec["num_experts"]):
            k1, k3, k2 = jax.random.split(jax.random.fold_in(k[6], e), 3)
            w1.append(init(k1, (H, F), f32))
            w3.append(init(k3, (H, F), f32))
            w2.append(init(k2, (F, H), f32))
        p[pre + "w1"], p[pre + "w3"], p[pre + "w2"] = (
            jnp.stack(w1), jnp.stack(w3), jnp.stack(w2))
        p[pre + "shared_w1"] = init(k[7], (H, Fs), f32)
        p[pre + "shared_w3"] = init(k[8], (H, Fs), f32)
        p[pre + "shared_w2"] = init(k[9], (Fs, H), f32)
        p[pre + "shared_gate"] = init(k[10], (H, 1), f32)[:, 0]
    return p, key


# ---- forward ------------------------------------------------------------

def _rms0(x, w, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * (1.0 + w)


def _sigmoid(x):
    import jax

    return jax.nn.sigmoid(x)


def _rotary_part(x, theta, turned: int):
    """x [b, heads, C, hd]: the first `turned` of a head turn."""
    import jax.numpy as jnp

    return jnp.concatenate([lfm._rotary(x[..., :turned], theta),
                            x[..., turned:]], axis=-1)


def expert_layer(h, valid, router, w1, w3, w2, *, first: int,
                 per_token: int, mm=lambda x, w: x @ w, fault=None):
    """The routed part of one layer's feed-forward for h [..., H]: the
    router scores every expert of the layer (`router` [H, E]); `w1`, `w3`
    [held, H, F] and `w2` [held, F, H] are experts `first ..`, each
    applied to every position and weighted by its p or 0."""
    import jax
    import jax.numpy as jnp

    p_all = jax.nn.softmax(h @ router, axis=-1)
    p_top, chosen = jax.lax.top_k(p_all, per_token)
    share = p_top / jnp.sum(p_top, axis=-1, keepdims=True)
    count = w1.shape[0] - (fault == "expert_left_out")

    def one(acc, expert):
        e_w1, e_w3, e_w2, e = expert
        gate = jnp.sum(jnp.where(chosen == first + e, share, 0.0),
                       axis=-1) * valid
        return acc + gate[..., None] * lfm.swiglu(h, e_w1, e_w3, e_w2,
                                                  mm), None

    out, _ = jax.lax.scan(
        jax.checkpoint(one), jnp.zeros_like(h),
        (w1[:count], w3[:count], w2[:count], jnp.arange(count)))
    return out


def shared_expert(h, gate_w, v1, v3, v2, mm=lambda x, w: x @ w):
    return _sigmoid(h @ gate_w)[..., None] * lfm.swiglu(h, v1, v3, v2, mm)


def delta_recurrence(q, k, v, g, beta, q8=None):
    """Token by token. q, k [b, C, n_v, d_k] (normalised, scaled, key
    heads already repeated), v [b, C, n_v, d_v], g, beta [b, C, n_v]
    (zero at masked slots). Returns o [b, C, n_v, d_v]. `q8` rounds the
    products' operands (the control)."""
    import jax
    import jax.numpy as jnp

    f = (lambda x: x) if q8 is None else q8.f
    b_ = (lambda x: x) if q8 is None else q8.b

    def slot(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        S = S * jnp.exp(g_t)[..., None, None]
        seen = b_(jnp.einsum("bhkv,bhk->bhv", f(S), f(k_t)))
        write = b_t[..., None] * (v_t - seen)
        S = S + b_(jnp.einsum("bhk,bhv->bhkv", f(k_t), f(write)))
        return S, b_(jnp.einsum("bhkv,bhk->bhv", f(S), f(q_t)))

    S0 = jnp.zeros(v.shape[:1] + (v.shape[2], k.shape[-1], v.shape[-1]),
                   jnp.float32)
    _, o = jax.lax.scan(jax.checkpoint(slot), S0, tuple(
        jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def encode(p, c, mask, q, spec, fault=None):
    """Code vectors [b, 3E] of contexts c [b, C, 3E] (dropout applied)."""
    import jax
    import jax.numpy as jnp

    eps, theta = spec["rms_norm_eps"], spec["rope_theta"]
    n, n_kv, hd = (spec["num_attention_heads"], spec["num_key_value_heads"],
                   spec["head_dim"])
    turned = int(hd * spec["partial_rotary_factor"])
    n_k, n_v = spec["linear_num_key_heads"], spec["linear_num_value_heads"]
    d_k, d_v = spec["linear_key_head_dim"], spec["linear_value_head_dim"]
    key_dim = n_k * d_k
    K, first = spec["num_experts_per_tok"], spec["first_expert"]
    B, C, _ = c.shape
    valid = mask > 0
    exact = q is base.rounding(None)

    def mm(x, w):
        return q.b(q.f(x) @ q.f(w.T).T)

    def delta_net(h, pre):
        qkvz = mm(h, p[pre + "in_qkvz"])
        qkv, z = qkvz[..., :2 * key_dim + n_v * d_v], \
            qkvz[..., 2 * key_dim + n_v * d_v:]
        b, a = jnp.split(mm(h, p[pre + "in_ba"]), 2, axis=-1)
        qkv = qkv * mask[..., None]
        kernel = p[pre + "conv_k"]
        taps = kernel.shape[1]
        conv = jnp.zeros_like(qkv)
        for j in range(taps):
            back = taps - 1 - j         # slot t takes slot t - back
            conv = conv + kernel[:, j] * jnp.pad(
                qkv, ((0, 0), (back, 0), (0, 0)))[:, :C, :]
        qkv = lfm._silu(conv)

        def unit(t):
            t = t.reshape(B, C, n_k, d_k)
            t = t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)
            return jnp.repeat(t, n_v // n_k, axis=2)

        qh = unit(qkv[..., :key_dim]) / math.sqrt(d_k)
        kh = unit(qkv[..., key_dim:2 * key_dim])
        vh = qkv[..., 2 * key_dim:].reshape(B, C, n_v, d_v)
        beta = _sigmoid(b)
        g = -jnp.exp(p[pre + "A_log"]) * jax.nn.softplus(
            a + p[pre + "dt_bias"])
        if fault == "no_decay":
            g = jnp.zeros_like(g)
        if fault == "beta_one":
            beta = jnp.ones_like(beta)
        o = delta_recurrence(qh, kh, vh, g * mask[..., None],
                             beta * mask[..., None],
                             None if exact else q) \
            * mask[..., None, None]
        y = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + 1e-6) \
            * p[pre + "gdn_norm"] * lfm._silu(z.reshape(B, C, n_v, d_v))
        return mm(y.reshape(B, C, n_v * d_v), p[pre + "gdn_out"])

    def attention(h, pre):
        def heads(t, count):
            return t.reshape(B, C, count, -1).transpose(0, 2, 1, 3)

        q_gate = heads(mm(h, p[pre + "q"]), n)          # [B, n, C, 2 hd]
        qh, gate = q_gate[..., :hd], q_gate[..., hd:]
        qh = _rotary_part(_rms0(qh, p[pre + "q_norm"], eps), theta, turned)
        kh = _rotary_part(_rms0(heads(mm(h, p[pre + "k"]), n_kv),
                                p[pre + "k_norm"], eps), theta, turned)
        vh = heads(mm(h, p[pre + "v"]), n_kv)
        # kv head j serves query heads j n/n_kv ..
        kh = jnp.repeat(kh, n // n_kv, axis=1)
        vh = jnp.repeat(vh, n // n_kv, axis=1)
        scores = q.b(jnp.einsum("bhqd,bhkd->bhqk", q.f(qh), q.f(kh))) \
            / math.sqrt(hd)
        slot = jnp.arange(C)
        seen = valid[:, None, None, :]
        if fault != "no_causal_mask":
            seen = seen & (slot[None, :] <= slot[:, None])[None, None]
        att = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
        o = q.b(jnp.einsum("bhqk,bhkd->bhqd", q.f(att),
                           q.f(vh.swapaxes(-1, -2)).swapaxes(-1, -2)))
        if fault != "no_output_gate":
            o = o * _sigmoid(gate)
        return mm(o.transpose(0, 2, 1, 3).reshape(B, C, n * hd),
                  p[pre + "o"])

    def moe(h, pre):
        out = expert_layer(
            h, valid, p[pre + "router"], p[pre + "w1"], p[pre + "w3"],
            p[pre + "w2"], first=first, per_token=K, mm=mm, fault=fault)
        if fault == "shared_left_out":
            return out
        return out + shared_expert(
            h, p[pre + "shared_gate"], p[pre + "shared_w1"],
            p[pre + "shared_w3"], p[pre + "shared_w2"], mm)

    x = mm(c, p["qwen/in_proj"]) * mask[..., None]
    for i, kind in enumerate(layer_types(spec)):
        pre = f"qwen/layers/{i}/"
        h = _rms0(x, p[pre + "op_norm"], eps)
        if kind == LINEAR:
            # its slots' states are recomputed in the backward pass
            x = x + jax.checkpoint(lambda h, pre=pre: delta_net(h, pre))(h)
        else:
            x = x + attention(h, pre)
        x = x + moe(_rms0(x, p[pre + "ff_norm"], eps), pre)
    x = _rms0(x, p["qwen/ln_f_scale"], eps)
    any_valid = jnp.sum(mask, -1, keepdims=True) > 0
    score = jnp.where(valid | ~any_valid, x @ p["qwen/pool_query"], -1e30)
    a = jax.nn.softmax(score, axis=-1)
    return mm(jnp.einsum("bc,bcd->bd", a, x), p["qwen/out_proj"])


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
