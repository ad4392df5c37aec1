"""`--encoder smallthinker` (models/smallthinker_encoder.py,
models/seq_block.py, ops/moe.py) at tiny sizes on the CPU, against the
configuration's plain reference (benchmark/reference_smallthinker.py):
code vector, loss, every leaf's gradient and three optimizer steps in
float32, through the full step and the staircase step; the window term
against a banded mask where it binds, whole and by query block; NoPE
against rotary under a rotation of the earlier slots; the router that
reads the layer's input before attention; the ReGLU through both bodies
of `held_experts_ffn` and its `custom_vjp`, and its count of zeros; the
four expert-parallel shares adding up to the uncut layer; what
`SmallthinkerDims.from_config` and `Config.verify()` refuse; and the four
accepted blocks' lowered train steps unchanged by the new arguments at
their defaults, in one test against copies of the functions as they were
before the window, the early router and the experts' activation came."""

import dataclasses
import functools
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from code2vec_tpu.data import staircase as st
from code2vec_tpu.models import seq_block
from code2vec_tpu.models.encoder import (ModelDims, get_encode_fn,
                                         init_params)
from code2vec_tpu.models.smallthinker_encoder import SmallthinkerDims
from code2vec_tpu.ops import moe
from code2vec_tpu.training import steps
from tests.helpers import (STAIR_CASES, assert_staircase_mixer_is_the_whole,
                           build_tiny_dataset, staircase_mask)
from tests.test_encoders import STAIRS, dims_of

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

import reference_smallthinker as ref_mod  # noqa: E402

# one whole period (global NoPE, then three rotary window layers); 14
# query heads to 2 kv heads, groups of 7 as published; a window of 4
# over 12 slots, so that it binds; 4 of 16 experts held from the fifth,
# 3 a token
BLOCK = dict(num_hidden_layers=4, hidden_size=32, num_attention_heads=14,
             num_key_value_heads=2, head_dim=8, moe_ffn_hidden_size=24,
             moe_num_primary_experts=4, num_routed_experts=16,
             first_expert=4, moe_num_active_primary_experts=3,
             sliding_window_layout=[0, 1, 1, 1], rope_layout=[0, 1, 1, 1],
             sliding_window_size=4, rope_theta=1.5e6, rms_norm_eps=1e-6)
THINKER = SmallthinkerDims.from_config(BLOCK)
# the tables keep the product's width and at least 128 rows: optax
# factors Adafactor's second moment only from 128 up, as the reference
# always does
SIZES = dict(tokens=200, paths=150, targets=130, embedding=128,
             max_contexts=12, num_sampled=16, dropout_keep=0.75)
DIMS = ModelDims(token_vocab_size=202, path_vocab_size=152,
                 target_vocab_size=132, embeddings_size=128, max_contexts=12,
                 dropout_keep_rate=0.75, encoder_type="smallthinker",
                 smallthinker=THINKER)
SEED = 7


def spec(dtype="float32"):
    return dict(SIZES, encoder="smallthinker", tables_dtype=dtype, lr=1e-3,
                lr_schedule="cosine", lr_total_steps=400, **BLOCK)


def batches(n=8, steps=3, seed=3, lengths=None):
    """`steps` batches of `n` bags, of `lengths` contexts where given."""
    r = np.random.default_rng(seed)
    C = SIZES["max_contexts"]
    out = []
    for _ in range(steps):
        lens = r.integers(1, C + 1, n) if lengths is None else lengths
        mask = (np.arange(C)[None, :] < lens[:, None]).astype(np.float32)

        def ids(v):
            return (r.integers(2, v + 2, (n, C)) * mask).astype(np.int32)

        out.append((r.integers(2, SIZES["targets"] + 2, n).astype(np.int32),
                    ids(SIZES["tokens"]), ids(SIZES["paths"]),
                    ids(SIZES["tokens"]), mask, np.ones(n, np.float32)))
    return out


def program_weights():
    """The program's own start from SEED, as `Code2VecModel` draws it."""
    rng, init_rng = jax.random.split(jax.random.PRNGKey(SEED))
    return init_params(init_rng, DIMS), rng


def flat(tree):
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {"/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path): v for path, v in leaves}


def reference_code(mask, src, pth, dst, fault=None):
    p, _ = ref_mod.make_weights(SEED, spec())
    c = jnp.concatenate([p["token_emb"][src], p["path_emb"][pth],
                         p["token_emb"][dst]], axis=-1)
    encode = jax.jit(lambda p, c: ref_mod.encode(
        p, c, jnp.asarray(mask), ref_mod.base.rounding(None), spec(),
        fault))
    with jax.default_matmul_precision("highest"):
        return encode(p, c)


def test_reference_draws_the_programs_weights():
    params, _ = program_weights()
    ref, _key = ref_mod.make_weights(SEED, spec())
    got = flat(params)
    assert set(got) == set(ref)
    for k in ref:
        np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(ref[k]),
                                      err_msg=k)
    # every layer routes; 14 heads of 8 are wider than the hidden 32
    assert got["smallthinker/layers/0/w1"].shape == (4, 32, 24)
    assert got["smallthinker/layers/3/router"].shape == (32, 16)
    assert got["smallthinker/layers/1/q"].shape == (32, 14 * 8)
    assert got["smallthinker/layers/1/k"].shape == (32, 2 * 8)
    assert got["smallthinker/layers/2/o"].shape == (14 * 8, 32)
    assert "q_norm" not in params["smallthinker"]["layers"][0]


# float32 leaves room for summation order alone; bfloat16 for its
# rounding and for the tokens whose near-tied experts it swaps, four
# routers deep
@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5),
                                       ("bfloat16", 0.4)])
def test_code_vector_matches_reference(dtype, tol):
    params, _ = program_weights()
    _labels, src, pth, dst, mask, _w = batches()[0]
    encode = jax.jit(lambda p: get_encode_fn(DIMS)(
        p, src, pth, dst, jnp.asarray(mask),
        compute_dtype=jnp.dtype(dtype)))
    code, attn, _ = encode(params)
    want = reference_code(mask, src, pth, dst)
    gap = float(jnp.linalg.norm(code.astype(jnp.float32) - want)
                / jnp.linalg.norm(want))
    assert gap <= tol, gap
    assert np.all(np.asarray(attn)[mask == 0] < 1e-6)


@pytest.mark.parametrize("fault", ref_mod.FAULTS)
def test_every_planted_fault_moves_the_reference(fault):
    """What `--readings all` plants is seen at all, and past the float32
    tolerance the program is held to: the reference's code vectors
    differ with the fault in by more than 2e-5 (so the program, which
    matches the sound reference to that, reads neither the router after
    attention, nor SwiGLU experts, nor unturned window layers)."""
    _labels, src, pth, dst, mask, _w = batches()[0]
    sound = reference_code(mask, src, pth, dst)
    faulty = reference_code(mask, src, pth, dst, fault)
    gap = float(jnp.linalg.norm(sound - faulty) / jnp.linalg.norm(sound))
    assert gap > 1e-3, gap


def _first_gradients(ref, got):
    """Every leaf's first gradient against the reference's: the norm of
    the difference over the leaf's norm or the median leaf's, whichever
    is larger (`train_corpus.compare`'s measure)."""
    norms = {k: float(np.linalg.norm(v))
             for k, v in ref["dense_grads"].items()}
    median = float(np.median(list(norms.values())))
    assert set(ref["dense_grads"]) <= set(got)
    return {k: float(np.linalg.norm(np.asarray(got[k], np.float32) - want))
            / max(norms[k], median)
            for k, want in ref["dense_grads"].items()}, norms


# float32 holds the program to the reference: the loss to summation
# order, a leaf's gradient to 2e-4 of its norm (four routers and the
# reference's per-expert loop sum in other orders), a change to 2e-3
def test_three_steps_match_reference():
    """Loss, every leaf's first gradient and the norm of each leaf's
    change over three optimizer steps, through the full step."""
    from code2vec_tpu.training.optimizers import make_lr, make_optimizer
    from code2vec_tpu.training.steps import (make_train_loss_fn,
                                             make_train_step)

    lr = 1e-4
    params, rng = program_weights()
    bs = batches()
    ref = ref_mod.follow(SEED, dict(spec(), lr=lr), bs, block=4)
    loss_fn = make_train_loss_fn(DIMS, use_sampled_softmax=True,
                                 num_sampled=16)
    first_gradient = jax.jit(jax.value_and_grad(loss_fn))
    loss, grads = first_gradient(
        params, tuple(jnp.asarray(a) for a in bs[0]),
        jax.random.fold_in(rng, 0))
    assert float(loss) == pytest.approx(ref["losses"][0], rel=1e-5)
    diffs, norms = _first_gradients(ref, flat(grads))
    assert max(diffs.values()) <= 2e-4, max(diffs.items(),
                                            key=lambda kv: kv[1])
    # every leaf of the block learns, the router of every layer among
    # them (its gradient comes through p, from the pre-attention norm)
    for i in range(4):
        for leaf in ("router", "op_norm", "ff_norm", "q", "k", "w1", "w2"):
            assert norms[f"smallthinker/layers/{i}/{leaf}"] > 0, (i, leaf)

    opt = make_optimizer(make_lr(lr, "cosine", 400))
    step = make_train_step(DIMS, opt, use_sampled_softmax=True,
                           num_sampled=16)
    start = jax.tree_util.tree_map(jnp.copy, params)
    state = opt.init(params)
    losses = []
    for i, b in enumerate(bs):
        params, state, loss = step(params, state,
                                   tuple(jnp.asarray(a) for a in b),
                                   jax.random.fold_in(rng, i))
        losses.append(float(loss))
    np.testing.assert_allclose(losses, ref["losses"], rtol=1e-4)
    after, before = flat(params), flat(start)
    c_ref = ref["change_norms"]
    c_median = float(np.median([v for v in c_ref.values() if v > 0]))
    for k, want in c_ref.items():
        change = float(jnp.linalg.norm((after[k].astype(jnp.float32)
                                        - before[k].astype(jnp.float32))))
        assert abs(change - want) <= 2e-3 * max(want, c_median), k


def test_the_staircase_steps_first_gradient_matches_reference(monkeypatch):
    """The staircase step (three query blocks, the feed-forward half and
    the router's logits packed to three rectangles) on a batch that fits
    its staircase, longest bag first: a step of plain gradient descent
    at rate 1 moves every leaf by minus its first gradient, held to the
    reference as the full step is."""
    monkeypatch.setattr(st, "_BLOCK_SLOTS", 1)
    params, rng = program_weights()
    bs = batches(lengths=np.array([12, 11, 10, 9, 5, 4, 3, 1]))
    stairs = ((0, 8), (4, 6), (8, 4))
    assert st.fits(stairs, [jnp.asarray(a) for a in bs[0][1:4]])
    assert len(seq_block.core_blocks(stairs, None, 12)) == 3
    ref = ref_mod.follow(SEED, spec(), bs[:1], block=4)
    opt = optax.sgd(1.0)
    step = steps.make_train_step(DIMS, opt, use_sampled_softmax=True,
                                 num_sampled=16, staircase=stairs)
    start = flat(params)
    moved, _state, loss = step(
        jax.tree_util.tree_map(jnp.copy, params), opt.init(params),
        steps.TrainBatch(tuple(jnp.asarray(a) for a in bs[0]), True, 0),
        jax.random.fold_in(rng, 0))
    assert float(loss) == pytest.approx(ref["losses"][0], rel=1e-5)
    grads = {k: start[k] - v for k, v in flat(moved).items()}
    diffs, _norms = _first_gradients(ref, grads)
    assert max(diffs.values()) <= 2e-4, max(diffs.items(),
                                            key=lambda kv: kv[1])


# ---- the window ------------------------------------------------------------

def _attention_layer(seed=0, H=16, n=14, n_kv=2, hd=8):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    return {"q": 0.3 * jax.random.normal(k[0], (H, n * hd)),
            "k": 0.3 * jax.random.normal(k[1], (H, n_kv * hd)),
            "v": 0.3 * jax.random.normal(k[2], (H, n_kv * hd)),
            "o": 0.3 * jax.random.normal(k[3], (n * hd, H))}


def _banded_reference(h, mask, layer, theta, window, n=14, n_kv=2, hd=8):
    """Grouped-query attention written out a head at a time, with the
    reference's own banded mask and rotary."""
    B, C, _ = h.shape
    outs = []
    for head in range(n):
        j = head // (n // n_kv)
        q = h @ layer["q"][:, head * hd:(head + 1) * hd]
        k = h @ layer["k"][:, j * hd:(j + 1) * hd]
        v = h @ layer["v"][:, j * hd:(j + 1) * hd]
        if theta is not None:
            q, k = ref_mod.rotate_half(q, theta), ref_mod.rotate_half(k, theta)
        scores = jnp.einsum("bqd,bkd->bqk", q, k) / math.sqrt(hd)
        seen = ref_mod.banded(C, window)[None] & (mask > 0)[:, None, :]
        att = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
        outs.append(att @ v)
    return jnp.concatenate(outs, axis=-1) @ layer["o"]


def _mixer(theta, window, **kw):
    def mixer(h, mask, layer, stairs=None):
        blocks = None if stairs is None else seq_block.core_blocks(
            stairs, None, mask.shape[1])
        return seq_block.attention(
            h, mask, layer, heads=14, kv_heads=2, head_dim=8, theta=theta,
            norm=None, blocks=blocks, window=window, **kw)
    return mixer


@pytest.mark.parametrize("theta", [None, 1.5e6])
def test_the_window_is_a_banded_causal_mask_where_it_binds(theta):
    """Window 4 at 20 slots: the whole core against the banded reference,
    valid slots; and not the plain causal core."""
    h = jax.random.normal(jax.random.PRNGKey(3), (8, 20, 16))
    mask = jnp.asarray(staircase_mask(STAIR_CASES["uneven"]))
    layer = _attention_layer()
    with jax.default_matmul_precision("highest"):
        got = _mixer(theta, 4)(h, mask, layer)
        want = _banded_reference(h, mask, layer, theta, 4)
        causal = _mixer(theta, None)(h, mask, layer)
    valid = np.asarray(mask) > 0
    np.testing.assert_allclose(np.asarray(got)[valid],
                               np.asarray(want)[valid], rtol=1e-4,
                               atol=1e-5)
    far = valid.copy()
    far[:, :4] = False             # a query past the window's reach
    assert not np.allclose(np.asarray(got)[far], np.asarray(causal)[far],
                           atol=1e-3)


@pytest.mark.parametrize("block_slots", [1, 8])
@pytest.mark.parametrize("case", list(STAIR_CASES))
def test_the_window_by_query_block_is_the_whole_core(case, block_slots,
                                                     monkeypatch):
    """The banded core by the staircase's query blocks (one of them
    starting past the window, `_BLOCK_SLOTS` 1) against the whole banded
    core: outputs at the valid slots and every gradient."""
    monkeypatch.setattr(st, "_BLOCK_SLOTS", block_slots)
    h = jax.random.normal(jax.random.PRNGKey(5), (8, 20, 16))
    assert_staircase_mixer_is_the_whole(_mixer(1.5e6, 4), h,
                                        _attention_layer(1),
                                        STAIR_CASES[case])


def test_a_window_no_query_outruns_adds_no_term():
    """window >= C lowers to the program of no window, and a window of
    C - 1 does not."""
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 12, 16))
    mask = jnp.ones((2, 12), jnp.float32)
    layer = _attention_layer()

    def text(window):
        return jax.jit(lambda h: _mixer(1.5e6, window)(h, mask, layer)
                       ).lower(h).as_text()

    assert text(12) == text(4096) == text(None)
    assert text(11) != text(None)


def test_nope_is_blind_to_a_rotation_of_the_earlier_slots():
    """The last query's output under a rotation of the slots before it:
    a NoPE layer sees the same set of keys and gives the same output;
    a rotary layer sees them at other distances and does not."""
    h = jax.random.normal(jax.random.PRNGKey(7), (2, 10, 16))
    turned = jnp.concatenate([jnp.roll(h[:, :-1], 3, axis=1), h[:, -1:]],
                             axis=1)
    mask = jnp.ones((2, 10), jnp.float32)
    layer = _attention_layer(2)
    with jax.default_matmul_precision("highest"):
        for theta, same in ((None, True), (10.0, False)):
            a = _mixer(theta, None)(h, mask, layer)[:, -1]
            b = _mixer(theta, None)(turned, mask, layer)[:, -1]
            assert np.allclose(np.asarray(a), np.asarray(b), atol=1e-5) \
                == same, theta
    # and the encoder gives the published layout: layer 0 NoPE and
    # global, the others rotary under the window
    assert [THINKER.theta(i) for i in range(4)] == [None] + [1.5e6] * 3
    assert [THINKER.window(i) for i in range(4)] == [None] + [4] * 3
    assert THINKER.layer_types == ("full_attention",) + (
        "sliding_attention",) * 3


# ---- the router before attention -------------------------------------------

def test_the_router_reads_the_layer_input_before_attention():
    """A one-layer block: the rows each held expert took are the ones a
    router over RMSNorm_in(x) chooses, not the ones over the
    post-attention norm."""
    block = dict(BLOCK, num_hidden_layers=1, sliding_window_layout=[0],
                 rope_layout=[0], moe_num_primary_experts=16,
                 first_expert=0)
    dims = dataclasses.replace(
        DIMS, smallthinker=SmallthinkerDims.from_config(block))
    params = init_params(jax.random.PRNGKey(1), dims)
    _labels, src, pth, dst, mask, _w = batches()[0]
    sub = params["smallthinker"]
    layer = sub["layers"][0]
    encode = jax.jit(lambda p: get_encode_fn(dims)(
        p, src, pth, dst, jnp.asarray(mask)))
    _code, _attn, aux = encode(params)
    # the stack's input and the two norms, in float32 as the program runs
    c = jnp.concatenate([params["token_emb"][src], params["path_emb"][pth],
                         params["token_emb"][dst]], axis=-1)
    x = (c @ sub["in_proj"]) * jnp.asarray(mask)[..., None]
    h = ref_mod.lfm._rms(x, layer["op_norm"], 1e-6)
    attn = seq_block.attention(h, jnp.asarray(mask), layer, heads=14,
                               kv_heads=2, head_dim=8, theta=None,
                               norm=None)
    g = ref_mod.lfm._rms(x + attn, layer["ff_norm"], 1e-6)
    valid = mask.reshape(-1) > 0

    def rows(t):
        chosen, _p = moe.route(t.reshape(-1, 32), layer["router"], None, 3,
                               "softmax")
        return np.bincount(np.asarray(chosen)[valid].reshape(-1),
                           minlength=16)

    got = np.asarray(aux)[0, :16]
    np.testing.assert_array_equal(got, rows(h))
    assert np.any(got != rows(g))


def test_choose_is_route_from_the_logits():
    k = jax.random.split(jax.random.PRNGKey(1), 2)
    h = jax.random.normal(k[0], (64, 16))
    router = 0.5 * jax.random.normal(k[1], (16, 64))
    for fn in (lambda f: f, jax.jit):
        got = fn(lambda h: moe.choose(moe.router_logits(h, router), None, 6,
                                      "softmax"))(h)
        want = fn(lambda h: moe.route(h, router, None, 6, "softmax"))(h)
        for a, b in zip(got, want):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---- the ReGLU experts ------------------------------------------------------

def _layer_case(E=16, H=16, F=12, n_tokens=96, seed=2):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    return dict(
        h=jax.random.normal(k[0], (n_tokens, H)),
        valid=jnp.arange(n_tokens) % 7 != 3,
        router=0.4 * jax.random.normal(k[1], (H, E)),
        w1=0.2 * jax.random.normal(k[2], (E, H, F)),
        w3=0.2 * jax.random.normal(k[3], (E, H, F)),
        w2=0.2 * jax.random.normal(k[4], (E, F, H)))


def _dense_loop(case, first, held, per_token, h=None):
    h = case["h"] if h is None else h
    return ref_mod.expert_layer(
        h, h, case["valid"], case["router"], case["w1"][first:first + held],
        case["w3"][first:first + held], case["w2"][first:first + held],
        first=first, per_token=per_token)


@pytest.mark.parametrize("held,per_token,n_tokens,body", [
    (4, 3, 96, "first_pairs"),     # the live pairs fit the bound
    (4, 3, 512, "every_pair"),     # they do not: the cond's other body
    (16, 3, 96, "one_body")])      # every expert held: no conditional
def test_reglu_experts_are_the_dense_loop(held, per_token, n_tokens, body):
    """Output, the gradients of h and of every weight (through the
    `custom_vjp`'s backward where there is a conditional), and the count
    of zero inner entries, against every held expert applied to every
    token. For the second body the router is skewed by a constant
    feature of h so that every valid token chooses three held experts:
    more live pairs than the bound holds."""
    case = _layer_case(n_tokens=n_tokens)
    E = case["router"].shape[1]
    first = 2 if held < E else 0
    sl = slice(first, first + held)
    if body == "every_pair":
        case["h"] = case["h"].at[:, 0].set(3.0)
        case["router"] = case["router"].at[0].set(
            jnp.where((jnp.arange(E) >= first) & (jnp.arange(E) < first
                                                  + held), 10.0, -10.0))
    pairs = case["h"].shape[0] * per_token
    bound = moe.row_bound(pairs, held, E)

    def program(h, w1, w3, w2):
        chosen, p = moe.route(h, case["router"], None, per_token, "softmax")
        return moe.held_experts_ffn(h, case["valid"], chosen, p, w1, w3, w2,
                                    first, E, act="relu")

    weights = (case["w1"][sl], case["w3"][sl], case["w2"][sl])
    with jax.default_matmul_precision("highest"):
        run = jax.jit(program)
        out, counts = run(case["h"], *weights)
        want = _dense_loop(case, first, held, per_token)
        g = jax.grad(lambda *a: jnp.sum(jnp.sin(program(*a)[0])),
                     argnums=(0, 1, 2, 3))(case["h"], *weights)

        def dense(h, w1, w3, w2):
            local = dict(case, w1=case["w1"].at[sl].set(w1),
                         w3=case["w3"].at[sl].set(w3),
                         w2=case["w2"].at[sl].set(w2))
            return _dense_loop(local, first, held, per_token, h)

        g_want = jax.grad(lambda *a: jnp.sum(jnp.sin(dense(*a))),
                          argnums=(0, 1, 2, 3))(case["h"], *weights)
    rows = np.asarray(counts[:held])
    assert {"first_pairs": bound < pairs and rows.sum() <= bound,
            "every_pair": bound < pairs and rows.sum() > bound,
            "one_body": bound == pairs}[body]
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5)
    # float32 sums in another order: gradients of a few hundred agree to
    # a few ulps of their size
    for a, b in zip(g, g_want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=5e-5)
    # the zeros and the entries: the held experts' relu(h W1_e) on the
    # rows routed to them
    chosen, _p = moe.route(case["h"], case["router"], None, per_token,
                           "softmax")
    zeros = 0
    for e in range(held):
        took = np.any(np.asarray(chosen) == first + e, axis=1) \
            & np.asarray(case["valid"])
        gate = np.asarray(case["h"])[took] @ np.asarray(weights[0][e])
        zeros += int(np.sum(gate <= 0))
    assert counts.shape == (held + 2,)
    assert int(counts[held]) == zeros
    assert int(counts[held + 1]) == int(rows.sum()) * 12
    assert 0.3 < zeros / int(counts[held + 1]) < 0.7


def test_silu_experts_count_rows_alone():
    """The default activation is the SwiGLU and hands back the rows
    alone, as before; relu is another product."""
    case = _layer_case()
    chosen, p = moe.route(case["h"], case["router"], None, 3, "softmax")
    args = (case["h"], case["valid"], chosen, p, case["w1"][:4],
            case["w3"][:4], case["w2"][:4], 0, 16)
    out, rows = moe.held_experts_ffn(*args)
    assert rows.shape == (4,)
    out_relu, _ = moe.held_experts_ffn(*args, act="relu")
    assert not np.allclose(np.asarray(out), np.asarray(out_relu), atol=1e-3)


def test_the_four_shares_add_up():
    """Experts 0-15, 16-31, 32-47, 48-63 as the four chips of the
    deployment hold a 64-expert layer, 6 a token: the routed parts of the
    four shares equal the uncut layer of the reference, and their rows
    every choice of every valid token."""
    case = _layer_case(E=64)
    with jax.default_matmul_precision("highest"):
        whole = _dense_loop(case, 0, 64, 6)
        chosen, p = moe.route(case["h"], case["router"], None, 6, "softmax")
        parts = [moe.held_experts_ffn(
            case["h"], case["valid"], chosen, p,
            case["w1"][first:first + 16], case["w3"][first:first + 16],
            case["w2"][first:first + 16], first, 64, act="relu")
            for first in (0, 16, 32, 48)]
        total = sum(out for out, _counts in parts)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=5e-5)
    rows = sum(int(c[:16].sum()) for _out, c in parts)
    assert rows == 6 * int(case["valid"].sum())
    # one share against the reference given the same share
    with jax.default_matmul_precision("highest"):
        want = _dense_loop(case, 32, 16, 6)
    np.testing.assert_allclose(np.asarray(parts[2][0]), np.asarray(want),
                               atol=5e-5)
    # a masked token is routed nowhere
    np.testing.assert_array_equal(
        np.asarray(parts[0][0])[~np.asarray(case["valid"])], 0.0)


@pytest.mark.parametrize("first,held,routed", [
    (0, 16, 64), (16, 16, 64), (48, 16, 64),    # the deployment's four
    (4, 4, 16),                                 # the tests' block
    (8, 24, 64)])          # shares of 24 from 8: 8-31, 32-55, 56-63 0-7
def test_the_busiest_share_is_the_most_rows_any_share_took(first, held,
                                                           routed):
    r = np.random.default_rng(first + held)
    # a skewed router: low experts are chosen more often
    chosen = np.stack([r.choice(routed, 6, replace=False,
                                p=np.linspace(2, 1, routed)
                                / np.linspace(2, 1, routed).sum())
                       for _ in range(300)])
    valid = r.random(300) < 0.6
    share = ((chosen[valid] - first) % routed) // held
    want = np.bincount(share.reshape(-1)).max()
    got = moe.busiest_share(jnp.asarray(chosen, jnp.int32),
                            jnp.asarray(valid), first, held, routed)
    assert int(got) == want
    own = np.sum((chosen[valid] >= first) & (chosen[valid] < first + held))
    assert int(got) >= own


@pytest.mark.parametrize("n_tokens,body", [(96, "first_pairs"),
                                           (512, "every_pair")])
def test_waiting_for_the_busiest_share_changes_no_output_gradient_or_count(
        n_tokens, body):
    """The dead rows the products run over when they wait for the
    busiest share are zeros: the output, every gradient and the counts
    are those of the live rows, through both bodies and the
    `custom_vjp`."""
    case = _layer_case(n_tokens=n_tokens)
    held, first, E = 4, 2, 16
    sl = slice(first, first + held)
    if body == "every_pair":     # every valid token picks three held
        case["h"] = case["h"].at[:, 0].set(3.0)
        case["router"] = case["router"].at[0].set(
            jnp.where((jnp.arange(E) >= first) & (jnp.arange(E) < first
                                                  + held), 10.0, -10.0))
    else:                        # the held share is not the busiest
        case["h"] = case["h"].at[:, 0].set(3.0)
        case["router"] = case["router"].at[0].set(
            jnp.where(jnp.arange(E) >= 8, 1.5, -1.5))
    weights = (case["w1"][sl], case["w3"][sl], case["w2"][sl])

    def program(wait):
        def run(h, w1, w3, w2):
            chosen, p = moe.route(h, case["router"], None, 3, "softmax")
            busiest = (moe.busiest_share(chosen, case["valid"], first, held,
                                         E) if wait else None)
            return moe.held_experts_ffn(h, case["valid"], chosen, p, w1, w3,
                                        w2, first, E, act="relu",
                                        busiest=busiest)
        return run

    runs = {False: jax.jit(program(False)), True: jax.jit(program(True))}
    with jax.default_matmul_precision("highest"):
        results = {}
        for wait in (False, True):
            out, counts = runs[wait](case["h"], *weights)
            g = jax.grad(lambda *a: jnp.sum(jnp.sin(program(wait)(*a)[0])),
                         argnums=(0, 1, 2, 3))(case["h"], *weights)
            results[wait] = out, counts, g
        chosen, _p = moe.route(case["h"], case["router"], None, 3,
                               "softmax")
    busiest = int(moe.busiest_share(chosen, case["valid"], first, held, E))
    rows = int(np.asarray(results[False][1][:held]).sum())
    bound = moe.row_bound(n_tokens * 3, held, E)
    assert (rows <= bound) == (body == "first_pairs")
    if body == "first_pairs":
        assert rows < busiest <= bound     # the products run dead rows
    np.testing.assert_array_equal(np.asarray(results[True][1]),
                                  np.asarray(results[False][1]))
    np.testing.assert_allclose(np.asarray(results[True][0]),
                               np.asarray(results[False][0]), atol=1e-6)
    # zero rows join the last group's sums: a few ulps at most
    for a, b in zip(results[True][2], results[False][2]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   atol=1e-6)


def _choices_of_two_shares(moved, n=96):
    """(chosen [n, 3], p [n, 3]): 4 of 16 experts held from 2, every
    token valid. A third of the tokens take one held expert and two of
    share 2 (experts 10-13), the rest three of share 2, and the first
    `moved` tokens of that third three of share 2 too: the held share
    takes 32 - moved rows, share 2 256 + moved, of 288 pairs."""
    chosen = np.zeros((n, 3), np.int32)
    for t in range(n):
        chosen[t] = [10 + (t + j) % 4 for j in range(3)]
        if t % 3 == 0 and t // 3 >= moved:
            chosen[t, 0] = 2 + t % 4
    p = jax.random.uniform(jax.random.PRNGKey(7), (n, 3), minval=0.1)
    return jnp.asarray(chosen), p / p.sum(-1, keepdims=True)


@pytest.mark.parametrize("moved,body", [(0, "first_pairs"),
                                        (1, "every_pair")])
def test_a_busiest_share_past_the_bound_runs_every_pair(moved, body,
                                                        monkeypatch):
    """The layer's own rows fit R (256 of 288 pairs) either way; the
    busiest share's 256 rows do too, its 257 do not, and then the layer
    runs every pair rather than cut the wait short at R. The counts'
    column (`ran_at_bound`) says which ran; the output, the gradients
    and the rows are those of the layer that does not wait; with the
    other body poisoned nothing changes."""
    case = _layer_case()
    held, first, E = 4, 2, 16
    chosen, p = _choices_of_two_shares(moved)
    valid = jnp.ones(chosen.shape[0], bool)
    busiest = moe.busiest_share(chosen, valid, first, held, E)
    assert int(busiest) == 256 + moved
    sl = slice(first, first + held)
    weights = (case["w1"][sl], case["w3"][sl], case["w2"][sl])

    def program(wait):
        def run(h, w1, w3, w2):
            return moe.held_experts_ffn(h, valid, chosen, p, w1, w3, w2,
                                        first, E, act="relu",
                                        busiest=busiest if wait else None)
        return run

    def results(wait):
        run = jax.jit(program(wait))       # traced anew: see the poison
        out, counts = run(case["h"], *weights)
        g = jax.grad(lambda *a: jnp.sum(jnp.sin(program(wait)(*a)[0])),
                     argnums=(0, 1, 2, 3))(case["h"], *weights)
        return out, counts, g

    with jax.default_matmul_precision("highest"):
        out, counts, g = results(True)
        want_out, want_counts, want_g = results(False)
    rows = counts[:held]
    assert int(rows.sum()) == 32 - moved
    assert moe.ran_at_bound(rows, 288, E) == (256, True)
    bound, at_bound = moe.ran_at_bound(rows, 288, E, busiest)
    assert (bound, bool(at_bound)) == (256, body == "first_pairs")
    np.testing.assert_array_equal(np.asarray(counts),
                                  np.asarray(want_counts))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want_out),
                               atol=1e-6)
    for a, b in zip(g, want_g):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   atol=1e-6)
    other = "_every_pair" if body == "first_pairs" else "_first_pairs"
    monkeypatch.setattr(moe, other, lambda h, *a, **kw: (
        jnp.full_like(h, jnp.nan), jnp.int32(0)))
    with jax.default_matmul_precision("highest"):
        out2, _counts, g2 = results(True)
    np.testing.assert_array_equal(np.asarray(out2), np.asarray(out))
    for a, b in zip(g2, g):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_products_run_over_the_busiest_shares_rows(monkeypatch):
    """`_products` hands `ragged_dot` the live groups with the dead rows
    up to `busiest` (no more than the rows it has) in the last, and the
    live groups alone without it."""
    case = _layer_case()
    held, L = 4, 64
    rows = jnp.asarray([5, 0, 7, 3], jnp.int32)
    x = case["h"][:L]
    pair = jnp.arange(L)
    p = jnp.ones((L, 1), jnp.float32)
    weights = (case["w1"][:held], case["w3"][:held], case["w2"][:held])
    seen = []
    ragged_dot = jax.lax.ragged_dot

    def spy(a, w, groups, **kw):
        seen.append(np.asarray(groups).tolist())
        return ragged_dot(a, w, groups, **kw)

    monkeypatch.setattr(jax.lax, "ragged_dot", spy)
    want, _ = moe._products(x, pair, p, *weights, rows, "relu")
    assert seen == [[5, 0, 7, 3]] * 3
    for busiest, last in ((40, 3 + 25), (200, 3 + L - 15), (15, 3)):
        seen.clear()
        got, _ = moe._products(x, pair, p, *weights, rows, "relu",
                               jnp.int32(busiest))
        assert seen == [[5, 0, 7, last]] * 3
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-6)


def test_the_encoder_waits_for_the_busiest_share(monkeypatch):
    """Every expert layer of the block asks for the busiest share of its
    16 experts' shares of 4."""
    asked = []
    busiest_share = moe.busiest_share

    def spy(chosen, valid, first, held, routed):
        asked.append((first, held, routed))
        return busiest_share(chosen, valid, first, held, routed)

    monkeypatch.setattr(seq_block, "busiest_share", spy)
    params, _ = program_weights()
    _labels, src, pth, dst, mask, _w = batches()[0]
    jax.jit(lambda p: get_encode_fn(DIMS)(
        p, src, pth, dst, jnp.asarray(mask))).lower(params)
    assert asked == [(4, 4, 16)] * 4


def test_encoder_counts_its_layers_and_the_recorder_writes_the_zeros():
    """aux, a layer: the held experts' rows, the valid tokens, the row
    bound (8 x 12 x 3 = 288 pairs, 4 of 16 held: 256) and whether the
    layer ran at it, the zero inner entries and the entries; the
    recorder's `moe/route` record holds their share."""
    from code2vec_tpu.obs import memory_tracer
    from code2vec_tpu.obs.route import RouteRecorder

    params, _ = program_weights()
    _labels, src, pth, dst, mask, _w = batches()[0]
    encode = jax.jit(lambda p: get_encode_fn(DIMS)(
        p, src, pth, dst, jnp.asarray(mask)))
    _code, _attn, aux = encode(params)
    aux = np.asarray(aux)
    assert aux.shape == (4, 4 + 5)
    assert aux[:, 4].tolist() == [int(mask.sum())] * 4
    assert aux[:, 5:7].tolist() == [[256, 1]] * 4
    assert 0 < aux[:, :4].sum() <= 4 * 3 * int(mask.sum())
    np.testing.assert_array_equal(aux[:, 8], aux[:, :4].sum(axis=1) * 24)
    assert np.all((0 < aux[:, 7]) & (aux[:, 7] < aux[:, 8]))
    rec = RouteRecorder(inner=True)
    rec.push(jnp.asarray(aux))
    rec.flush()
    record = memory_tracer().records("moe/route")[-1]["attrs"]
    assert record["layers"] == aux[:, :4].tolist()
    assert record["rows_here"] == int(aux[:, :4].sum())
    assert (record["valid_tokens"], record["row_bound"],
            record["compact_layers"]) == (int(mask.sum()), 256, 4)
    assert record["inner_zeros"] == int(aux[:, 7].sum())
    assert record["inner_zero_share"] == pytest.approx(
        100.0 * aux[:, 7].sum() / aux[:, 8].sum())
    # the cell: 128 methods x 200 slots, 6 choices, 16 of 64 held
    assert moe.row_bound(25600 * 6, 16, 64) == 38400


# ---- configuration -------------------------------------------------------

def _block_config_file(tmp_path):
    path = tmp_path / "block.json"
    path.write_text(json.dumps(BLOCK))
    return str(path)


@pytest.mark.parametrize("flags,message", [
    (["--tables_dtype", "int8"], "int8"),
    (["--head", "varmisuse"], "varmisuse"),
    (["--ring_attention"], "ring attention"),
    (["--mesh_context", "2"], "context-parallel"),
    (["--no_block_config"], "--block_config")])
def test_verify_refuses(tmp_path, flags, message):
    from code2vec_tpu.config import Config

    argv = ["--data", str(tmp_path / "d"), "--encoder", "smallthinker",
            "--backend", "cpu"]
    if flags == ["--no_block_config"]:
        flags = []
    else:
        argv += ["--block_config", _block_config_file(tmp_path)]
    with pytest.raises(ValueError, match=message):
        Config.load_from_args(argv + flags)


@pytest.mark.parametrize("key,value", [
    ("moe_primary_router_apply_softmax", False), ("norm_topk_prob", False),
    ("rope_scaling", {"type": "yarn", "factor": 4.0}),
    ("hidden_act", "silu"), ("attention_bias", True),
    ("num_dense_layers", 1)])
def test_from_config_refuses_a_switch_it_does_not_implement(key, value):
    with pytest.raises(ValueError, match=key):
        SmallthinkerDims.from_config(dict(BLOCK, **{key: value}))


@pytest.mark.parametrize("bad,message", [
    (dict(sliding_window_layout=[0, 1, 1]), "sliding_window_layout"),
    (dict(rope_layout=[0, 2, 1, 1]), "rope_layout"),
    (dict(layer_types=["full_attention"] * 4), "layer_types"),
    (dict(num_key_value_heads=3), "kv heads"),
    (dict(first_expert=14), "held"),
    (dict(num_experts=8), "moe_num_primary_experts.*num_experts"),
    (dict(moe_intermediate_size=768), "moe_ffn_hidden_size"),
    (dict(num_experts_per_tok=6), "moe_num_active_primary_experts"),
    (dict(sliding_window_size=0), "sliding_window_size")])
def test_from_config_refuses_what_disagrees(bad, message):
    with pytest.raises(ValueError, match=message):
        SmallthinkerDims.from_config(dict(BLOCK, **bad))


def test_block_sizes_come_from_the_config_json():
    # the repo's names for the numbers the source names otherwise
    assert (THINKER.num_experts, THINKER.moe_intermediate_size,
            THINKER.num_experts_per_tok, THINKER.num_dense_layers,
            THINKER.routed) == (4, 24, 3, 0, 16)
    # a twin may stand beside its source key or for it; the manifest's
    # form comes back whole
    assert SmallthinkerDims.from_config(dict(
        BLOCK, num_experts=4, moe_intermediate_size=24,
        num_experts_per_tok=3, num_dense_layers=0,
        layer_types=list(THINKER.layer_types), vocab_size=151936,
        max_position_embeddings=16384, tie_word_embeddings=False,
        model_name="smallthinker_21b_instruct")) == THINKER
    renamed = {k: v for k, v in BLOCK.items()
               if k not in ("moe_num_primary_experts", "moe_ffn_hidden_size",
                            "moe_num_active_primary_experts")}
    assert SmallthinkerDims.from_config(dict(
        renamed, num_experts=4, moe_intermediate_size=24,
        num_experts_per_tok=3)) == THINKER
    assert SmallthinkerDims.from_config(dataclasses.asdict(THINKER)) == \
        THINKER
    with pytest.raises(ValueError, match="head_dim"):
        SmallthinkerDims.from_config({k: v for k, v in BLOCK.items()
                                      if k != "head_dim"})


def test_the_benchmarks_file_is_a_block_config():
    """`benchmark/configs/java-large-smallthinker.json` is the program's
    `--block_config`: the published widths, one period, 16 of 64
    experts."""
    with open(os.path.join(REPO, "benchmark", "configs",
                           "java-large-smallthinker.json")) as f:
        config = json.load(f)
    dims = SmallthinkerDims.from_config(config)
    assert (dims.num_hidden_layers, dims.hidden_size,
            dims.num_attention_heads, dims.num_key_value_heads,
            dims.head_dim, dims.moe_ffn_hidden_size) == \
        (4, 2560, 28, 4, 128, 768)
    assert (dims.num_experts, dims.routed, dims.first_expert,
            dims.num_experts_per_tok, dims.sliding_window_size) == \
        (16, 64, 0, 6, 4096)
    assert dims.rope_layout == dims.sliding_window_layout == (0, 1, 1, 1)
    assert dims.rope_theta == 1.5e6
    for key in config["block"]["keys"]:
        got = getattr(dims, key)
        assert (list(got) if isinstance(got, tuple) else got) == config[key]


# ---- the accepted blocks' programs -----------------------------------------

# the functions as they stood before the window, the early router and the
# experts' activation came, their comments left out: what a caller that
# passes no new argument lowers to

def _causal_softmax_as_it_was(logits, mask, dtype):
    queries, keys = logits.shape[-2:]
    slot = jnp.arange(keys)
    at = slot if queries == keys else slot[keys - queries:]
    seen = (slot[None, :] <= at[:, None])[None] & (mask > 0)[:, None, :]
    heads = (None,) * (logits.ndim - 3)
    logits = jnp.where(seen[(slice(None),) + heads], logits, -1e30)
    return jax.nn.softmax(logits, axis=-1).astype(dtype)


def _causal_core_as_it_was(logits, v, mask, dtype, *, values, slot_axis,
                           blocks=None):
    B, C = mask.shape
    cut = seq_block.cut

    def core(block):
        _, end, kept = block
        att = seq_block.causal_softmax(
            logits(block), cut(mask, (0, kept), (0, end)), dtype)
        return jnp.einsum(values, att, v(block))

    if blocks is None:
        return core((0, C, B))
    return jnp.concatenate([seq_block._to_rows(core(block), B)
                            for block in blocks], axis=slot_axis)


def _attention_as_it_was(h, mask, layer, *, heads, kv_heads, head_dim,
                         theta, norm, turned=None, gated=False, blocks=None,
                         scale=None):
    dtype = h.dtype
    B, C, _ = h.shape
    n, n_kv, hd = heads, kv_heads, head_dim
    rotary, cut = seq_block.rotary, seq_block.cut

    def split(t, count, scale=None):
        t = t.reshape(B, C, count, -1)
        gate = None
        if t.shape[-1] != hd:
            t, gate = t[..., :hd], t[..., hd:]
        if scale is not None:
            t = norm(t, scale)
        return t.transpose(0, 2, 1, 3), gate

    q, gate = split(h @ layer["q"].astype(dtype), n, layer.get("q_norm"))
    turn = theta is not None
    if blocks is None and turn:
        q = rotary(q, theta, turned)
    k = split(h @ layer["k"].astype(dtype), n_kv, layer.get("k_norm"))[0]
    if turn:
        k = rotary(k, theta, turned)
    v, _ = split(h @ layer["v"].astype(dtype), n_kv)

    def logits(block):
        first, end, kept = block
        q_b = cut(q, (0, kept), None, (first, end))
        if blocks is not None and turn:
            q_b = rotary(q_b, theta, turned, first=first)
        q_b = q_b.reshape(-1, n_kv, n // n_kv, end - first, hd)
        scores = jnp.einsum("bkgqd,bkcd->bkgqc", q_b,
                            cut(k, (0, kept), None, (0, end))
                            ).astype(jnp.float32)
        return scores / math.sqrt(hd) if scale is None else scores * scale

    out = seq_block.causal_core(
        logits, lambda block: cut(v, (0, block[2]), None, (0, block[1])),
        mask, dtype, values="bkgqc,bkcd->bkgqd", slot_axis=-2, blocks=blocks)
    out = out.reshape(B, n, C, hd).transpose(0, 2, 1, 3)
    if gated:
        out = out * jax.nn.sigmoid(gate)
    return out.reshape(B, C, n * hd) @ layer["o"].astype(dtype)


def _residual_layer_as_it_was(i, *, norm, mixer_scope, mixer, ff, mask,
                              ff_scope=None, rectangles=None,
                              residual_multiplier=None):
    pack, lay_back = seq_block.pack, seq_block.lay_back
    ff_scope = f"c2v/blk_{i}" + (f"/{ff_scope}" if ff_scope else "")
    ff_mask = mask if rectangles is None else pack(mask, rectangles)

    def scaled(t):
        return t if residual_multiplier is None else t * residual_multiplier

    def run(x, layer):
        h = norm(x, layer["op_norm"])
        with jax.named_scope(f"c2v/blk_{i}/{mixer_scope}"):
            x = x + scaled(mixer(h, layer))
        kept = x if rectangles is None else pack(x, rectangles)
        h = norm(kept, layer["ff_norm"])
        with jax.named_scope(ff_scope):
            out, counts = ff(h, ff_mask, layer)
        out = scaled(out)
        if rectangles is not None:
            out = lay_back(out, rectangles, x.shape[0])
        return x + out, (None if counts is None
                         else jnp.sum(counts, axis=0))

    return jax.checkpoint(run)


def _routed_experts_as_it_was(h, mask, score, w1, w3, w2, *, first_expert,
                              routed):
    B, C, H = h.shape
    tokens = h.reshape(B * C, H)
    valid = mask.reshape(B * C) > 0
    with jax.named_scope("router"):
        chosen, p = score(tokens)
    with jax.named_scope("experts"):
        out, rows = _held_experts_ffn_as_it_was(
            tokens, valid, chosen, p, w1, w3, w2, first_expert, routed)
    bound, at_bound = moe.ran_at_bound(rows, chosen.size, routed)
    counts = jnp.concatenate([rows, jnp.stack([
        jnp.sum(valid, dtype=jnp.int32), jnp.int32(bound),
        at_bound.astype(jnp.int32)])])
    return out.reshape(B, C, H), counts[None]


def _products_as_it_was(x, pair, p, w1, w3, w2, rows):
    live = (jnp.arange(x.shape[0]) < jnp.sum(rows))[:, None]
    x = jnp.where(live, x, 0)

    def grouped(a, w):
        return jax.lax.ragged_dot(a, w.astype(a.dtype), rows)

    inner = jax.nn.silu(grouped(x, w1)) * grouped(x, w3)
    y = jnp.where(live, grouped(jnp.where(live, inner, 0), w2), 0)
    return y * jnp.take(p.reshape(-1), pair)[:, None].astype(y.dtype)


def _every_pair_as_it_was(h, p, w1, w3, w2, order, rows):
    k = p.shape[1]
    inverse = jnp.argsort(order)
    y = _products_as_it_was(moe._spread(h, order, inverse, k), order, p, w1,
                            w3, w2, rows)
    return moe._gather_back(y, order, inverse, k)


def _first_pairs_as_it_was(h, p, w1, w3, w2, order, rows, bound,
                           scatter=True):
    k = p.shape[1]
    head = order[:bound]
    token = head // k
    y = _products_as_it_was(moe._spread_head(h, token), head, p, w1, w3, w2,
                            rows)
    if not scatter:
        return moe._take_back_head(y, order, k)
    return moe._sum_back_head(y, token, h.shape[0])


def _bodies_as_it_was(bound, scatter=True):
    return (functools.partial(_first_pairs_as_it_was, bound=bound,
                              scatter=scatter), _every_pair_as_it_was)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _bounded_as_it_was(h, p, w1, w3, w2, order, rows, bound):
    operands = jax.lax.optimization_barrier((h, p, w1, w3, w2, order, rows))
    return jax.lax.cond(moe.fits(rows, bound),
                        *_bodies_as_it_was(bound, scatter=False), *operands)


def _bounded_fwd_as_it_was(h, p, w1, w3, w2, order, rows, bound):
    operands = jax.lax.optimization_barrier((h, p, w1, w3, w2, order, rows))
    return (jax.lax.cond(moe.fits(rows, bound), *_bodies_as_it_was(bound),
                         *operands),
            (h, p, w1, w3, w2, order, rows))


def _bounded_bwd_as_it_was(bound, res, g):
    *floats, order, rows = res

    def pull(body):
        def run(floats, g):
            return jax.vjp(lambda *f: body(*f, order, rows), *floats)[1](g)
        return run

    floats, g = jax.lax.optimization_barrier((floats, g))
    grads = jax.lax.cond(moe.fits(rows, bound),
                         *map(pull, _bodies_as_it_was(bound)), floats, g)
    return (*grads, None, None)


_bounded_as_it_was.defvjp(_bounded_fwd_as_it_was, _bounded_bwd_as_it_was)


def _held_experts_ffn_as_it_was(h, valid, chosen, p, w1, w3, w2,
                                first_expert, routed=None):
    n, k = chosen.shape
    held = w1.shape[0]
    local = chosen - first_expert
    here = (local >= 0) & (local < held) & valid[:, None]
    key = jnp.where(here, local, held).reshape(-1)
    order = jnp.argsort(key)
    rows = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
    bound = moe.row_bound(n * k, held, routed or held)
    if bound == n * k:
        return _every_pair_as_it_was(*jax.lax.optimization_barrier(
            (h, p, w1, w3, w2, order, rows))), rows
    return _bounded_as_it_was(h, p, w1, w3, w2, order, rows, bound), rows


@pytest.mark.parametrize("run", ["full_step", "staircase_step"])
@pytest.mark.parametrize("name", ["lfm2_moe", "qwen3_next", "joyai_flash",
                                  "granite_hybrid"])
def test_the_four_blocks_train_steps_lower_to_what_they_did(
        name, run, monkeypatch):
    """The full step and the staircase step of each accepted block: with
    `seq_block`'s masked softmax, core, attention, residual layer and
    routed experts and `ops/moe`'s grouped product as they were and as
    they are, the same text. The copies hold the barriers before the
    expert conditional and call today's `moe.row_bound`,
    `moe.fits` and `moe.ran_at_bound`, so for the three expert blocks
    this pins the layer around them at today's bound; the bound and the
    choice of body are held by tests/test_lfm2_moe.py and the busiest
    share's part in it by this file's tests above."""
    dims = dims_of(name)
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), dims))
    optimizer = optax.sgd(0.1)
    state = jax.eval_shape(optimizer.init, params)
    B, C = STAIRS[0][1], dims.max_contexts
    S = jax.ShapeDtypeStruct
    batch = (S((B,), jnp.int32), *(S((B, C), jnp.int32),) * 3,
             S((B, C), jnp.float32), S((B,), jnp.float32))
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    monkeypatch.setattr(steps, "_recording", lambda step, recorder: step)

    def text():
        step = steps.make_train_step(dims, optimizer,
                                     use_sampled_softmax=True,
                                     num_sampled=8, staircase=STAIRS)
        return getattr(step, run).lower(params, state, batch, key).as_text()

    now = text()
    for attr, old in (("causal_softmax", _causal_softmax_as_it_was),
                      ("causal_core", _causal_core_as_it_was),
                      ("attention", _attention_as_it_was),
                      ("residual_layer", _residual_layer_as_it_was),
                      ("routed_experts", _routed_experts_as_it_was)):
        monkeypatch.setattr(seq_block, attr, old)
    assert text() == now


# ---- tracing -------------------------------------------------------------

def test_named_scopes_stand_in_every_step_that_runs_the_encoder():
    from code2vec_tpu.training.steps import make_eval_step, make_train_step

    params, rng = program_weights()
    batch = tuple(jnp.asarray(a) for a in batches()[0])
    opt = optax.adam(1e-3)
    train = make_train_step(DIMS, opt, use_sampled_softmax=True,
                            num_sampled=16).lower(
        params, opt.init(params), batch, rng).as_text(debug_info=True)
    evaluate = make_eval_step(DIMS, top_k=3).lower(params, batch).as_text(
        debug_info=True)
    for text in (train, evaluate):
        for i in range(4):
            for scope in ("router", "attn", "experts"):
                assert f"c2v/blk_{i}/{scope}" in text, (i, scope)
        assert "c2v/blk_0/mlp" not in text and "c2v/pool" in text


# ---- the model class -----------------------------------------------------

def test_model_trains_evaluates_saves_and_reloads(tmp_path):
    """Through `Code2VecModel` on the tests' 8-device mesh (every device
    routes its own rows), with the encoder's sizes kept by the
    checkpoint's manifest and the zeros' share in the route record."""
    from code2vec_tpu.models.jax_model import Code2VecModel
    from code2vec_tpu.obs import memory_tracer
    from tests.test_model import tiny_config

    prefix = build_tiny_dataset(str(tmp_path), n_train=256, n_val=32,
                                n_test=64, max_contexts=16)
    block = dict(BLOCK, hidden_size=32, num_attention_heads=4,
                 num_key_value_heads=2, head_dim=8, moe_ffn_hidden_size=16)
    path = tmp_path / "block.json"
    path.write_text(json.dumps(block))
    cfg = tiny_config(prefix, ENCODER_TYPE="smallthinker",
                      BLOCK_CONFIG=str(path), NUM_TRAIN_EPOCHS=6,
                      LEARNING_RATE=0.003)
    ckpt_dir = str(tmp_path / "ckpt")
    cfg.save_path = ckpt_dir
    model = Code2VecModel(cfg)
    model.train()
    result = model.evaluate()
    assert result.subtoken_f1 > 0.3
    record = memory_tracer().records("moe/route")[-1]["attrs"]
    assert len(record["layers"]) == 4 and len(record["layers"][0]) == 4
    assert 0 < record["inner_zero_share"] < 100
    model.save(ckpt_dir)

    cfg2 = tiny_config(prefix)
    cfg2.load_path = ckpt_dir
    model2 = Code2VecModel(cfg2)
    assert model2.dims.smallthinker == SmallthinkerDims.from_config(block)
    loaded = model2.evaluate()
    assert loaded.topk_acc == pytest.approx(result.topk_acc)
    with open(os.path.join(ckpt_dir, "manifest.json")) as f:
        manifest = json.load(f)
    assert manifest["encoder_type"] == "smallthinker"
    assert manifest["smallthinker"]["rope_layout"] == [0, 1, 1, 1]
