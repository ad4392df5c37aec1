"""The plain reference of configuration `java-large-lfm2moe`: the
LFM2-MoE decoder block (LiquidAI/LFM2-24B-A2B `config.json`, `model_type`
`lfm2_moe`) as code2vec's path encoder, one chip's share of its experts,
trained three steps in straightforward `jax.numpy` float32 at the highest
matmul precision.

It imports nothing of the program. From `reference.py`, the accepted
cells' reference, it takes what the product shares with them: the three
tables' law and the step's keys, the dropout mask, the negatives and the
sampled softmax, Adafactor and Adam written out, the cosine rate. Its own:
the block's weights from the seed (every leaf from a key of its own, an
expert's from its index in the whole layer) and the block's equations. `x`
is [b, C, H], `m` the context mask, position = slot index:

  input   c = concat(tok[src], path[pth], tok[dst]), dropout ; x = (c W_in) m
  layer   x = x + Op(RMSNorm(x)) ; x = x + FF(RMSNorm(x)), eps norm_eps
  conv    [b, g, u] = split3(h W_in3) ; v = b u m
          w_t = sum_{j<L} K[:, j] v_{t-(L-1)+j}, zeros before slot 0
          Op = (g w) W_out
  full_attention
          q, k, v = h W_q, h W_k, h W_v ; n heads of H/n, n_kv key/value
          heads ; RMSNorm over each head of q and of k, learned scale
          (LFM2's q/k layernorm: assumed, its config does not state it) ;
          rotary, theta, whole head, pairs (i, i + head/2) ; scores over
          sqrt(head) ; causal and padding mask ; softmax ; kv head j
          serves query heads j n/n_kv .. ; Op = concat(heads) W_o
  FF      layers before num_dense_layers: (silu(h W1) (h W3)) W2
          the rest: s = sigmoid(h W_r) ; chosen = top K of s + bias (the
          bias selects only: a seeded buffer, held fixed) ;
          p_e = s_e / (sum of the K chosen s + 1e-6) ;
          FF = sum over chosen e held here of p_e (silu(h W1_e) (h W3_e)) W2_e
          every held expert is applied to every position under its mask
          (no sort, no kernel); a masked slot is routed nowhere; what the
          experts held elsewhere would add is left out
  output  RMSNorm ; a = softmax_i(x_i . q | valid) ; code = (sum a x) W_out2
  loss    the product's sampled softmax (`reference._loss_sum`)

The three tables are stored in the dtype the configuration states
(`tables_dtype`): after every apply they are rounded to it, to nearest
even, as the program's are, whose tables are arrays of that dtype. Under
a warm-up the first rates put an update under a bfloat16 unit of most
stored values, and what the store rounds away is part of the result
(`reference.py` keeps float32 tables: its cells' first updates are a
thousand times larger).

`quant="fp8"` is the control, as in `reference.py`: tables stored and
every matmul's operands rounded to 8-bit floats, forward and backward.
The router's scores stay exact there, as the program takes them in
float32 whatever its compute dtype. `fault` plants one of the block's own
faults: "expert_left_out" (the last held expert adds nothing),
"bias_in_p" (the selection bias enters p), "no_causal_mask".
"""

from __future__ import annotations

import math
from typing import List, Optional

import numpy as np

import reference as base

TABLES = base.TABLES
# small beside the gaps between a token's top scores (about 0.016
# between the fourth and the fifth of 64): it turns near-ties and leaves
# the load on the experts even, as the trained buffer's job is
BIAS_SCALE = 0.005
FAULTS = ("expert_left_out", "bias_in_p", "no_causal_mask")


# ---- weights ------------------------------------------------------------

def make_weights(seed: int, spec: dict):
    """(params as a flat {path: f32 array} dict, the key the steps fold).
    The tables, `transform` and `attention` (which this encoder leaves
    unused) are `reference.make_weights`'s; the block's leaves follow."""
    import jax
    import jax.numpy as jnp

    p, key = base.make_weights(seed, dict(spec, encoder="bag"))
    _, k_init = jax.random.split(jax.random.PRNGKey(seed))
    rng = jax.random.fold_in(k_init, 0x1f2)
    init = jax.nn.initializers.variance_scaling(1.0, "fan_avg", "uniform")
    f32 = jnp.float32
    D, H = 3 * spec["embedding"], spec["hidden_size"]
    hd = H // spec["num_attention_heads"]
    kv = spec["num_key_value_heads"] * hd
    k_in, k_out, k_pool = jax.random.split(rng, 3)
    p["lfm/in_proj"] = init(k_in, (D, H), f32)
    p["lfm/out_proj"] = init(k_out, (H, D), f32)
    p["lfm/pool_query"] = init(k_pool, (H, 1), f32)[:, 0]
    p["lfm/ln_f_scale"] = jnp.ones((H,), f32)
    for i, kind in enumerate(spec["layer_types"]):
        pre = f"lfm/layers/{i}/"
        k = jax.random.split(jax.random.fold_in(rng, 100 + i), 7)
        p[pre + "op_norm"] = jnp.ones((H,), f32)
        p[pre + "ff_norm"] = jnp.ones((H,), f32)
        if kind == "conv":
            taps = spec["conv_L_cache"]
            p[pre + "conv_in"] = init(k[0], (H, 3 * H), f32)
            p[pre + "conv_k"] = jax.random.uniform(
                k[1], (H, taps), f32, -1 / math.sqrt(taps),
                1 / math.sqrt(taps))
            p[pre + "conv_out"] = init(k[2], (H, H), f32)
        else:
            p[pre + "q"] = init(k[0], (H, H), f32)
            p[pre + "k"] = init(k[1], (H, kv), f32)
            p[pre + "v"] = init(k[2], (H, kv), f32)
            p[pre + "o"] = init(k[3], (H, H), f32)
            p[pre + "q_norm"] = jnp.ones((hd,), f32)
            p[pre + "k_norm"] = jnp.ones((hd,), f32)
        if i < spec["num_dense_layers"]:
            I = spec["intermediate_size"]
            p[pre + "w1"] = init(k[4], (H, I), f32)
            p[pre + "w3"] = init(k[5], (H, I), f32)
            p[pre + "w2"] = init(k[6], (I, H), f32)
            continue
        F, E = spec["moe_intermediate_size"], spec["num_routed_experts"]
        p[pre + "router"] = init(k[4], (H, E), f32)
        p[pre + "expert_bias"] = BIAS_SCALE * jax.random.normal(
            k[5], (E,), f32)
        w1, w3, w2 = [], [], []
        for e in range(spec["first_expert"],
                       spec["first_expert"] + spec["num_experts"]):
            k1, k3, k2 = jax.random.split(jax.random.fold_in(k[6], e), 3)
            w1.append(init(k1, (H, F), f32))
            w3.append(init(k3, (H, F), f32))
            w2.append(init(k2, (F, H), f32))
        p[pre + "w1"], p[pre + "w3"], p[pre + "w2"] = (
            jnp.stack(w1), jnp.stack(w3), jnp.stack(w2))
    return p, key


# ---- forward ------------------------------------------------------------

def _rms(x, scale, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _silu(x):
    import jax

    return x * jax.nn.sigmoid(x)


def _rotary(x, theta):
    """x [b, heads, C, hd]."""
    import jax.numpy as jnp

    C, hd = x.shape[-2], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    angle = jnp.arange(C, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(angle), jnp.cos(angle)], axis=-1)
    sin = jnp.concatenate([jnp.sin(angle), jnp.sin(angle)], axis=-1)
    turned = jnp.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], axis=-1)
    return x * cos + turned * sin


def swiglu(h, w1, w3, w2, mm):
    return mm(_silu(mm(h, w1)) * mm(h, w3), w2)


def expert_layer(h, valid, router, bias, w1, w3, w2, *, first: int,
                 per_token: int, mm=lambda x, w: x @ w, fault=None):
    """One expert layer's feed-forward for h [..., H]: the router scores
    every expert of the layer (`router` [H, E], `bias` [E]); `w1`, `w3`
    [held, H, F] and `w2` [held, F, H] are experts `first ..`, each
    applied to every position under its mask."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.sigmoid(h @ router)
    _, chosen = jax.lax.top_k(s + bias, per_token)
    s_chosen = jnp.take_along_axis(s, chosen, axis=-1)
    if fault == "bias_in_p":
        s_chosen = s_chosen + bias[chosen]
    share = s_chosen / (jnp.sum(s_chosen, axis=-1, keepdims=True) + 1e-6)
    count = w1.shape[0] - (fault == "expert_left_out")

    def one(acc, expert):
        e_w1, e_w3, e_w2, e = expert
        gate = jnp.sum(jnp.where(chosen == first + e, share, 0.0),
                       axis=-1) * valid
        return acc + gate[..., None] * swiglu(h, e_w1, e_w3, e_w2, mm), None

    # recomputed in the backward pass: eight experts' products over
    # every position would else be kept, layer by layer
    out, _ = jax.lax.scan(
        jax.checkpoint(one), jnp.zeros_like(h),
        (w1[:count], w3[:count], w2[:count], jnp.arange(count)))
    return out


def encode(p, c, mask, q, spec, fault=None):
    """Code vectors [b, 3E] of contexts c [b, C, 3E] (dropout applied)."""
    import jax
    import jax.numpy as jnp

    eps, theta = spec["norm_eps"], spec["rope_theta"]
    n, n_kv = spec["num_attention_heads"], spec["num_key_value_heads"]
    K, first = spec["num_experts_per_tok"], spec["first_expert"]
    B, C, _ = c.shape
    valid = mask > 0

    def mm(x, w):
        return q.b(q.f(x) @ q.f(w.T).T)

    def conv(h, pre):
        b, g, u = jnp.split(mm(h, p[pre + "conv_in"]), 3, axis=-1)
        v = b * u * mask[..., None]
        kernel = p[pre + "conv_k"]
        taps = kernel.shape[1]
        w = jnp.zeros_like(v)
        for j in range(taps):
            back = taps - 1 - j         # w_t takes v_{t - back}
            shifted = jnp.pad(v, ((0, 0), (back, 0), (0, 0)))[:, :C, :]
            w = w + kernel[:, j] * shifted
        return mm(g * w, p[pre + "conv_out"])

    def attention(h, pre):
        hd = h.shape[-1] // n

        def heads(t, count):
            return t.reshape(B, C, count, hd).transpose(0, 2, 1, 3)

        qh = _rotary(_rms(heads(mm(h, p[pre + "q"]), n), p[pre + "q_norm"],
                          eps), theta)
        kh = _rotary(_rms(heads(mm(h, p[pre + "k"]), n_kv),
                          p[pre + "k_norm"], eps), theta)
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
        return mm(o.transpose(0, 2, 1, 3).reshape(B, C, n * hd),
                  p[pre + "o"])

    def experts(h, pre):
        return expert_layer(
            h, valid, p[pre + "router"], p[pre + "expert_bias"],
            p[pre + "w1"], p[pre + "w3"], p[pre + "w2"], first=first,
            per_token=K, mm=mm, fault=fault)

    x = mm(c, p["lfm/in_proj"]) * mask[..., None]
    for i, kind in enumerate(spec["layer_types"]):
        pre = f"lfm/layers/{i}/"
        h = _rms(x, p[pre + "op_norm"], eps)
        x = x + (conv(h, pre) if kind == "conv" else attention(h, pre))
        h = _rms(x, p[pre + "ff_norm"], eps)
        if i < spec["num_dense_layers"]:
            x = x + swiglu(h, p[pre + "w1"], p[pre + "w3"], p[pre + "w2"],
                           mm)
        else:
            x = x + experts(h, pre)
    x = _rms(x, p["lfm/ln_f_scale"], eps)
    any_valid = jnp.sum(mask, -1, keepdims=True) > 0
    score = jnp.where(valid | ~any_valid, x @ p["lfm/pool_query"], -1e30)
    a = jax.nn.softmax(score, axis=-1)
    return mm(jnp.einsum("bc,bcd->bd", a, x), p["lfm/out_proj"])


# ---- one step's loss and gradient, block by block -----------------------

def _make_block_fn(spec: dict, batch: int, block: int, quant: Optional[str],
                   fault: Optional[str]):
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

def learning_rate(step: int, spec: dict) -> float:
    """`reference.learning_rate`, and the schedule it lacks:
    "warmup_cosine" rises linearly from 0 to the rate over
    `lr_warmup_steps`, then falls by a cosine to a tenth of it at
    `lr_total_steps`."""
    if spec["lr_schedule"] != "warmup_cosine":
        return base.learning_rate(step, spec)
    warm, total = spec["lr_warmup_steps"], spec["lr_total_steps"]
    if step < warm:
        return spec["lr"] * step / warm
    t = min(step - warm, total - warm) / (total - warm)
    return spec["lr"] * (0.9 * 0.5 * (1.0 + math.cos(math.pi * t)) + 0.1)


def stored_as(dtype: str):
    """Float32 values rounded to what an array of `dtype` holds, to
    nearest even. One `reduce_precision`, not a cast there and back: the
    TPU compiler drops such a pair of converts (it allows excess
    precision), and the tables then keep every bit (my chip run, PR 28:
    the change norms read as float32's)."""
    import jax
    import jax.numpy as jnp

    info = jnp.finfo(jnp.dtype(dtype))
    return lambda v: jax.lax.reduce_precision(
        v, exponent_bits=info.nexp, mantissa_bits=info.nmant)


def follow(seed: int, spec: dict, batches: List[tuple], *, block: int,
           quant: Optional[str] = None,
           weights: Optional[List[np.ndarray]] = None,
           fault: Optional[str] = None) -> dict:
    """`reference.follow` for this configuration: the losses, the first
    gradient's norm leaf by leaf and whole for the leaves outside the
    tables, and the norm of each leaf's change over the steps."""
    import jax
    import jax.numpy as jnp

    assert fault is None or fault in FAULTS, fault
    p, key = make_weights(seed, spec)
    n = batches[0][0].shape[0]
    fns = _make_block_fn(spec, n, block, quant, fault)
    apply = base._make_apply()
    # the tables are stored as stated; the control stores them in 8 bits
    keep = base.rounding(quant).store if quant is not None else \
        stored_as(spec["tables_dtype"])
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
                         lr=learning_rate(step, spec))
        p = store(p)
    del state, grads
    change = base._norms(jax.jit(
        lambda a, b: {k: a[k] - b[k] for k in a}, donate_argnums=(0, 1))(
            p, jax.device_put(p0)))
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change, "dense_grads": dense_grads}
