"""The plain reference: code2vec's training step, three times over, in
straightforward `jax.numpy` float32 at the highest matmul precision.

It imports nothing of the program and takes nothing the program made.
From the seed it draws its own weights (the law the configuration file
states: variance-scaled uniform, drawn in the stated table dtype), from
the step number its own dropout mask and negatives (the step's key is
`fold_in(key after the weights' split, step)`, split into dropout and
sampling), and it follows the first steps of training on the batches it
is handed: loss, the gradient the optimizer is given, Adafactor on the
three tables, Adam on the rest, the cosine learning rate.

Equations (Alon et al. 2019, section 4, with the repo's two encoders):

  c_i   = [tok[s_i]; path[p_i]; tok[t_i]]            context, 3E = D
  c_i   = dropout(c_i, keep)
  bag:  h_i = tanh(c_i W);  a = softmax_i(h_i . att  | valid)
        v   = sum_i a_i h_i
  xf:   x = c W_in; L pre-norm blocks (RMSNorm, masked MHA, RMSNorm,
        GELU MLP x4), RMSNorm; a = softmax_i(x_i . q | valid); v = sum a x
  loss: sampled softmax over 1 true + S log-uniform negatives drawn
        without replacement (Gumbel top-S), logits corrected by
        log(expected count), accidental hits removed; mean over the
        batch's valid methods.

The batch is walked in blocks of methods so that it fits beside nothing
else on a 16 GB chip; table gradients are dense float32.

`quant="fp8"` is the control: the same arithmetic with the tables
stored, and both operands of every matmul rounded, forward and backward,
in 8-bit floats (one scale per row), the precision step below the
bfloat16 the configurations state. `weights` lets a caller plant a batch fault (rows left out).
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, Optional

import numpy as np

TABLES = ("token_emb", "path_emb", "target_emb")


# ---- weights ------------------------------------------------------------

def make_weights(seed: int, spec: dict):
    """(params as a flat {path: f32 array} dict, the key the steps fold)."""
    import jax
    import jax.numpy as jnp

    E, D = spec["embedding"], 3 * spec["embedding"]
    init = jax.nn.initializers.variance_scaling(1.0, "fan_avg", "uniform")
    t_dtype = jnp.dtype(spec["tables_dtype"])
    key = jax.random.PRNGKey(seed)
    key, k_init = jax.random.split(key)
    k_tok, k_path, k_tgt, k_tr, k_at = jax.random.split(k_init, 5)
    f32 = jnp.float32
    p = {
        "token_emb": init(k_tok, (spec["tokens"] + 2, E), t_dtype),
        "path_emb": init(k_path, (spec["paths"] + 2, E), t_dtype),
        "target_emb": init(k_tgt, (spec["targets"] + 2, D), t_dtype),
        "transform": init(k_tr, (D, D), f32),
        "attention": init(k_at, (D, 1), f32)[:, 0],
    }
    if spec["encoder"] == "transformer":
        L, mlp = spec["xf_layers"], spec["xf_mlp_ratio"] * D
        keys = jax.random.split(jax.random.fold_in(k_init, 0x5f),
                                2 + 4 * L)
        p["xf/ln_f_scale"] = jnp.ones((D,), f32)
        p["xf/pool_query"] = init(keys[0], (D, 1), f32)[:, 0]
        p["xf/in_proj"] = init(keys[1], (D, D), f32)
        for i in range(L):
            k_qkv, k_o, k_up, k_down = keys[2 + 4 * i: 6 + 4 * i]
            pre = f"xf/layers/{i}/"
            p[pre + "ln1_scale"] = jnp.ones((D,), f32)
            p[pre + "ln2_scale"] = jnp.ones((D,), f32)
            p[pre + "qkv"] = init(k_qkv, (D, 3 * D), f32)
            p[pre + "out"] = init(k_o, (D, D), f32)
            p[pre + "mlp_up"] = init(k_up, (D, mlp), f32)
            p[pre + "mlp_down"] = init(k_down, (mlp, D), f32)
    return {k: v.astype(f32) for k, v in p.items()}, key


# ---- the control's rounding ---------------------------------------------

def _round_float8(dtype, top: float):
    def rounded(x):
        import jax.numpy as jnp

        s = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / top
        s = jnp.where(s > 0, s, 1.0)
        return (x / s).astype(dtype).astype(jnp.float32) * s
    return rounded


class Rounding:
    """How the control computes: `f` rounds a matmul's operand (or a
    stored table's rows) on the way forward and lets the gradient through;
    `b` lets a matmul's result through and rounds its cotangent, so the
    backward products take 8-bit operands too. fp8 is e4m3 forward and
    e5m2 backward (Micikevicius et al. 2022), each row scaled into the
    format's range."""

    def __init__(self, forward, backward):
        import jax

        @jax.custom_vjp
        def f(x):
            return forward(x)
        f.defvjp(lambda x: (forward(x), None), lambda _, g: (g,))

        @jax.custom_vjp
        def b(y):
            return y
        b.defvjp(lambda y: (y, None), lambda _, g: (backward(g),))
        self.f, self.b, self.store = f, b, forward


class _Exact:
    f = b = store = staticmethod(lambda x: x)


def rounding(quant):
    import jax.numpy as jnp

    if quant is None:
        return _Exact
    if quant == "fp8":
        return Rounding(_round_float8(jnp.float8_e4m3fn, 448.0),
                        _round_float8(jnp.float8_e5m2, 57344.0))
    raise ValueError(f"unknown control precision {quant!r}")


# ---- forward ------------------------------------------------------------

def _rms(x, scale):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + 1e-6) * scale


def _gelu(x):
    import jax.numpy as jnp

    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def _keep_rows(key, keep_rate: float, shape, start, rows: int):
    """Rows [start, start + rows) of `jax.random.bernoulli(key, keep_rate,
    shape)`, without drawing the rest: the step's mask is one draw over
    the whole batch, 10 GB of random bits at 32,768 methods. JAX's
    default generator gives element i of an array threefry2x32(key, i),
    both words xor-ed; a uniform float is those bits' top 23 as the
    mantissa of a number in [1, 2), less 1."""
    import jax
    import jax.numpy as jnp
    from jax.extend.random import threefry2x32_p

    n, c, d = shape
    assert n * c * d < 2 ** 32, "mask index needs the counter's high word"
    u32 = jnp.uint32
    index = ((start.astype(u32) + jnp.arange(rows, dtype=u32))[:, None, None]
             * u32(c * d)
             + jnp.arange(c, dtype=u32)[None, :, None] * u32(d)
             + jnp.arange(d, dtype=u32)[None, None, :])
    k1, k2 = jax.random.key_data(key)
    full = index.shape
    b1, b2 = threefry2x32_p.bind(jnp.broadcast_to(k1, full),
                                 jnp.broadcast_to(k2, full),
                                 jnp.zeros(full, u32), index)
    bits = ((b1 ^ b2) >> u32(9)) | u32(0x3F800000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32) - 1.0 < keep_rate


def _contexts(p, src, pth, dst, keep, keep_rate, q):
    import jax.numpy as jnp

    c = jnp.concatenate([q.f(p["token_emb"][src]), q.f(p["path_emb"][pth]),
                         q.f(p["token_emb"][dst])], axis=-1)
    return jnp.where(keep, c / keep_rate, 0.0)


def _encode_bag(p, c, mask, q):
    import jax
    import jax.numpy as jnp

    h = jnp.tanh(q.b(q.f(c) @ q.f(p["transform"].T).T))
    s = jnp.where(mask > 0, q.b(q.f(h) @ q.f(p["attention"])), -1e9)
    a = jax.nn.softmax(s, axis=-1)
    a = jnp.where(jnp.sum(mask, -1, keepdims=True) > 0, a, 0.0)
    return jnp.einsum("bc,bcd->bd", a, h)


def _encode_xf(p, c, mask, q, spec):
    import jax
    import jax.numpy as jnp

    H = spec["xf_heads"]
    B, C, D = c.shape
    hd = D // H
    safe = jnp.where(jnp.sum(mask, -1, keepdims=True) > 0, mask,
                     jnp.ones_like(mask))
    log_mask = jnp.log(jnp.maximum(safe, 1e-30))

    def mm(x, w):
        return q.b(q.f(x) @ q.f(w.T).T)

    def heads(t):
        return t.reshape(B, C, H, hd).transpose(0, 2, 1, 3)

    x = mm(c, p["xf/in_proj"])
    for i in range(spec["xf_layers"]):
        pre = f"xf/layers/{i}/"
        h = _rms(x, p[pre + "ln1_scale"])
        qh, kh, vh = (heads(t) for t in
                      jnp.split(mm(h, p[pre + "qkv"]), 3, axis=-1))
        logits = q.b(jnp.einsum("bhqd,bhkd->bhqk", q.f(qh), q.f(kh))) \
            / math.sqrt(hd) + log_mask[:, None, None, :]
        att = jax.nn.softmax(logits, axis=-1)
        o = q.b(jnp.einsum("bhqk,bhkd->bhqd", q.f(att),
                           q.f(vh.swapaxes(-1, -2)).swapaxes(-1, -2)))
        o = o.transpose(0, 2, 1, 3).reshape(B, C, D)
        x = x + mm(o, p[pre + "out"])
        h = _rms(x, p[pre + "ln2_scale"])
        x = x + mm(_gelu(mm(h, p[pre + "mlp_up"])), p[pre + "mlp_down"])
    x = _rms(x, p["xf/ln_f_scale"])
    a = jax.nn.softmax(x @ p["xf/pool_query"] + log_mask, axis=-1)
    return jnp.einsum("bc,bcd->bd", a, x)


# ---- sampled softmax ----------------------------------------------------

def _log_uniform_p(k, vocab: int):
    import jax.numpy as jnp

    return jnp.log1p(1.0 / (k + 1.0)) / math.log(vocab + 1.0)


def effective_tries(num_sampled: int, vocab: int) -> float:
    """T with sum_k (1 - (1 - p_k)^T) = S: the draw count at which
    log-uniform sampling with replacement yields S distinct classes in
    expectation (Newton, float64, on the host)."""
    k = np.arange(vocab, dtype=np.float64)
    l1p = np.log1p(-(np.log1p(1.0 / (k + 1.0)) / np.log(vocab + 1.0)))
    t = float(num_sampled)
    for _ in range(100):
        f = np.sum(-np.expm1(t * l1p)) - num_sampled
        step = f / np.sum(-l1p * np.exp(t * l1p))
        t -= step
        if abs(step) < 1e-9:
            break
    return t


def _negatives(key, num_sampled: int, vocab: int):
    import jax
    import jax.numpy as jnp

    k = jnp.arange(vocab, dtype=jnp.float32)
    scores = jnp.log(_log_uniform_p(k, vocab)) + jax.random.gumbel(
        key, (vocab,), jnp.float32)
    return jax.lax.top_k(scores, num_sampled)[1].astype(jnp.int32)


def _log_expected_count(ids, tries: float, vocab: int):
    import jax.numpy as jnp

    p = _log_uniform_p(ids.astype(jnp.float32), vocab)
    return jnp.log(-jnp.expm1(tries * jnp.log1p(-p)))


def _loss_sum(p, code, labels, weights, sampled, tries, vocab, q):
    """Sum over the block of weight x (-log softmax of the true class)."""
    import jax
    import jax.numpy as jnp

    code = q.f(code)
    true_w = q.f(p["target_emb"][labels])
    samp_w = q.f(p["target_emb"][sampled])
    true_logit = q.b(jnp.sum(code * true_w, -1)) - _log_expected_count(
        labels, tries, vocab)
    samp_logit = q.b(code @ samp_w.T) - _log_expected_count(
        sampled, tries, vocab)[None, :]
    samp_logit = jnp.where(sampled[None, :] == labels[:, None], -1e9,
                           samp_logit)
    logits = jnp.concatenate([true_logit[:, None], samp_logit], axis=1)
    return jnp.sum(-jax.nn.log_softmax(logits, axis=-1)[:, 0] * weights)


# ---- one step's loss and gradient, block by block -----------------------

def _make_block_fn(spec: dict, batch: int, block: int, quant: Optional[str]):
    import jax
    import jax.numpy as jnp

    C, D = spec["max_contexts"], 3 * spec["embedding"]
    keep_rate = spec["dropout_keep"]
    vocab = spec["targets"] + 2
    S = min(spec["num_sampled"], vocab)
    tries = effective_tries(S, vocab)
    q = rounding(quant)

    def block_loss(p, blk, drop_key, sampled, start):
        labels, src, pth, dst, mask, weights = blk
        keep = _keep_rows(drop_key, keep_rate, (batch, C, D), start, block)
        c = _contexts(p, src, pth, dst, keep, keep_rate, q)
        if spec["encoder"] == "transformer":
            code = _encode_xf(p, c, mask, q, spec)
        else:
            code = _encode_bag(p, c, mask, q)
        return _loss_sum(p, code, labels, weights, sampled, tries, vocab, q)

    @jax.jit
    def negatives(sample_key):
        return _negatives(sample_key, S, vocab)

    def accumulate(p, acc, loss_acc, blk, drop_key, sampled, start):
        with jax.default_matmul_precision("highest"):
            loss, g = jax.value_and_grad(block_loss)(
                p, blk, drop_key, sampled, start)
        return (jax.tree_util.tree_map(jnp.add, acc, g), loss_acc + loss)

    return negatives, jax.jit(accumulate, donate_argnums=(1, 2))


@functools.lru_cache(maxsize=None)
def _scale_fn():
    import jax

    return jax.jit(lambda t, f: jax.tree_util.tree_map(
        lambda x: x * f, t), donate_argnums=0)


def loss_and_grad(p, batch_arrays, step_key, fns, block: int):
    """Mean loss over the batch's weighted methods and its gradient."""
    import jax
    import jax.numpy as jnp

    negatives, accumulate = fns
    drop_key, sample_key = jax.random.split(step_key)
    sampled = negatives(sample_key)
    acc = jax.tree_util.tree_map(jnp.zeros_like, p)
    loss = jnp.zeros((), jnp.float32)
    n = batch_arrays[0].shape[0]
    for start in range(0, n, block):
        blk = tuple(jnp.asarray(a[start:start + block])
                    for a in batch_arrays)
        acc, loss = accumulate(p, acc, loss, blk, drop_key, sampled,
                               jnp.int32(start))
    denom = max(float(np.sum(batch_arrays[5])), 1.0)
    return loss / denom, _scale_fn()(acc, 1.0 / denom)


# ---- the optimizer ------------------------------------------------------

def learning_rate(step: int, spec: dict) -> float:
    if spec["lr_schedule"] == "constant":
        return spec["lr"]
    assert spec["lr_schedule"] == "cosine", spec["lr_schedule"]
    t = min(step, spec["lr_total_steps"]) / spec["lr_total_steps"]
    return spec["lr"] * (0.9 * 0.5 * (1.0 + math.cos(math.pi * t)) + 0.1)


def _adafactor(g, state, step: int, lr: float):
    """Adafactor without momentum or parameter scaling on a [V, E] table
    (Shazeer & Stern 2018): factored second moment with decay
    1 - (t+1)^-0.8, update clipped to unit RMS, times the rate."""
    import jax.numpy as jnp

    decay = 1.0 - (step + 1.0) ** -0.8
    g2 = g * g + 1e-30
    v_e = decay * state["v_e"] + (1 - decay) * jnp.mean(g2, axis=0)
    v_v = decay * state["v_v"] + (1 - decay) * jnp.mean(g2, axis=1)
    u = g * ((v_e / jnp.mean(v_e)) ** -0.5)[None, :] \
        * (v_v ** -0.5)[:, None]
    u = u / jnp.maximum(1.0, jnp.sqrt(jnp.mean(u * u)))
    return -lr * u, {"v_e": v_e, "v_v": v_v}


def _adam(g, state, step: int, lr: float):
    import jax.numpy as jnp

    m = 0.9 * state["m"] + 0.1 * g
    v = 0.999 * state["v"] + 0.001 * g * g
    m_hat = m / (1 - 0.9 ** (step + 1))
    v_hat = v / (1 - 0.999 ** (step + 1))
    return -lr * m_hat / (jnp.sqrt(v_hat) + 1e-8), {"m": m, "v": v}


def _make_apply():
    import jax
    import jax.numpy as jnp

    def apply(p, grads, state, step, lr):
        new_p, new_s = {}, {}
        for k in p:
            if k in TABLES:
                if k not in state:
                    state = dict(state, **{k: {
                        "v_e": jnp.zeros(p[k].shape[1], jnp.float32),
                        "v_v": jnp.zeros(p[k].shape[0], jnp.float32)}})
                u, new_s[k] = _adafactor(grads[k], state[k], step, lr)
            else:
                if k not in state:
                    state = dict(state, **{k: {
                        "m": jnp.zeros_like(p[k]),
                        "v": jnp.zeros_like(p[k])}})
                u, new_s[k] = _adam(grads[k], state[k], step, lr)
            new_p[k] = p[k] + u
        return new_p, new_s

    return jax.jit(apply, donate_argnums=(0, 1, 2),
                   static_argnames=("step",))


# ---- three steps --------------------------------------------------------

def _norms(tree) -> Dict[str, float]:
    import jax
    import jax.numpy as jnp

    out = jax.jit(lambda t: {k: jnp.sqrt(jnp.sum(jnp.square(v)))
                             for k, v in t.items()})(tree)
    return {k: float(v) for k, v in out.items()}


def follow(seed: int, spec: dict, batches: List[tuple], *, block: int,
           quant: Optional[str] = None,
           weights: Optional[List[np.ndarray]] = None) -> dict:
    """Train `len(batches)` steps from the seed's weights. `batches[i]`
    is the host 6-tuple (labels, src, path, dst, mask, weights) of step
    i. Returns the losses, the first gradient's norm leaf by leaf, and
    the norm of each leaf's change over the steps."""
    import jax
    import jax.numpy as jnp

    p, key = make_weights(seed, spec)
    p0 = jax.tree_util.tree_map(jnp.copy, p)
    n = batches[0][0].shape[0]
    fns = _make_block_fn(spec, n, block, quant)
    apply = _make_apply()
    keep = rounding(quant).store
    store = jax.jit(lambda t: {k: (keep(v) if k in TABLES else v)
                               for k, v in t.items()}, donate_argnums=0)
    if quant is not None:
        p = store(p)
        p0 = jax.tree_util.tree_map(jnp.copy, p)
    state: dict = {}
    losses, grad_norms = [], None
    for step, batch in enumerate(batches):
        if weights is not None:
            batch = tuple(batch[:5]) + (weights[step],)
        loss, grads = loss_and_grad(p, batch, jax.random.fold_in(key, step),
                                    fns, block)
        losses.append(float(loss))
        if step == 0:
            grad_norms = _norms(grads)
            dense_grads = {k: np.asarray(v) for k, v in grads.items()
                           if k not in TABLES}
        p, state = apply(p, grads, state, step=step,
                         lr=learning_rate(step, spec))
        if quant is not None:
            # the control stores its tables in 8 bits
            p = store(p)
    change = _norms(jax.jit(lambda a, b: {k: a[k] - b[k] for k in a})(p, p0))
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change, "dense_grads": dense_grads}
