"""`--encoder lfm2_moe` (models/lfm2_moe_encoder.py, ops/moe.py,
obs/route.py) at tiny sizes on the CPU, against the configuration's plain
reference (benchmark/reference_lfm2moe.py): code vector, loss, every
leaf's gradient and three optimizer steps in float32 and bfloat16; the
convolution's causality and masking; the router; the expert-parallel
shares adding up to the whole layer; no dropped row; no recompilation
across routings; what `Config.verify()` refuses; the `moe/route` record;
the step's named scopes; the model class end to end under the tests'
8-device mesh."""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from code2vec_tpu.models.encoder import (ModelDims, get_encode_fn,
                                         init_params)
from code2vec_tpu.models.lfm2_moe_encoder import Lfm2Dims
from code2vec_tpu.models import lfm2_moe_encoder as lfm
from code2vec_tpu.models import seq_block
from code2vec_tpu.ops import moe
from tests.helpers import (STAIR_CASES, assert_staircase_mixer_is_the_whole,
                           build_tiny_dataset, float_scatters,
                           lowered_texts, staircase_mask)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

import reference_lfm2moe as ref_mod  # noqa: E402

BLOCK = dict(layer_types=["conv", "full_attention", "conv"],
             num_dense_layers=1, hidden_size=64, intermediate_size=96,
             moe_intermediate_size=48, num_attention_heads=4,
             num_key_value_heads=2, num_experts=4, num_routed_experts=8,
             first_expert=2, num_experts_per_tok=2, conv_L_cache=3,
             norm_eps=1e-5, rope_parameters={"rope_theta": 1e6})
LFM = Lfm2Dims.from_config(BLOCK)
# the tables keep the product's width and at least 128 rows: optax
# factors Adafactor's second moment only from 128 up, as the reference
# always does
SIZES = dict(tokens=200, paths=150, targets=130, embedding=128,
             max_contexts=12, num_sampled=16, dropout_keep=0.75)
DIMS = ModelDims(token_vocab_size=202, path_vocab_size=152,
                 target_vocab_size=132, embeddings_size=128, max_contexts=12,
                 dropout_keep_rate=0.75, encoder_type="lfm2_moe", lfm=LFM)
SEED = 7


def spec(dtype="float32"):
    s = dict(SIZES, encoder="lfm2_moe", tables_dtype=dtype, lr=1e-3,
             lr_schedule="cosine", lr_total_steps=400,
             **{k: v for k, v in BLOCK.items() if k != "rope_parameters"})
    s["rope_theta"] = 1e6
    return s


def batches(n=8, steps=3, seed=3):
    r = np.random.default_rng(seed)
    C = SIZES["max_contexts"]
    out = []
    for _ in range(steps):
        lens = r.integers(1, C + 1, n)
        mask = (np.arange(C)[None, :] < lens[:, None]).astype(np.float32)

        def ids(v):
            return (r.integers(2, v + 2, (n, C)) * mask).astype(np.int32)

        out.append((r.integers(2, SIZES["targets"] + 2, n).astype(np.int32),
                    ids(SIZES["tokens"]), ids(SIZES["paths"]),
                    ids(SIZES["tokens"]), mask, np.ones(n, np.float32)))
    return out


def program_weights(dtype="float32"):
    """The program's own start from SEED, as `Code2VecModel` draws it."""
    rng, init_rng = jax.random.split(jax.random.PRNGKey(SEED))
    dims = dataclasses.replace(DIMS, tables_dtype=dtype)
    return dims, init_params(init_rng, dims), rng


def flat(tree):
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {"/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path): v for path, v in leaves}


def test_reference_draws_the_programs_weights():
    _dims, params, _ = program_weights()
    ref, _key = ref_mod.make_weights(SEED, spec())
    got = flat(params)
    assert set(got) == set(ref)
    for k in ref:
        np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(ref[k]),
                                      err_msg=k)


# the norm of the difference over the norm: float32 leaves room for
# summation order alone; bfloat16 (8 bits of mantissa through three
# layers) for its rounding and, at these widths, for the few tokens
# whose second and third scores it swaps
@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5),
                                       ("bfloat16", 0.12)])
def test_code_vector_matches_reference(dtype, tol):
    dims, params, _ = program_weights()
    _labels, src, pth, dst, mask, _w = batches()[0]
    code, attn, _ = get_encode_fn(dims)(params, src, pth, dst,
                                        jnp.asarray(mask),
                                        compute_dtype=jnp.dtype(dtype))
    p, _ = ref_mod.make_weights(SEED, spec())
    c = jnp.concatenate([p["token_emb"][src], p["path_emb"][pth],
                         p["token_emb"][dst]], axis=-1)
    with jax.default_matmul_precision("highest"):
        want = ref_mod.encode(p, c, jnp.asarray(mask),
                              ref_mod.base.rounding(None), spec())
    gap = float(jnp.linalg.norm(code.astype(jnp.float32) - want)
                / jnp.linalg.norm(want))
    assert gap <= tol, gap
    assert np.all(np.asarray(attn)[mask == 0] < 1e-6)


@pytest.mark.parametrize("dtype,loss_tol,grad_tol,change_tol", [
    ("float32", 1e-5, 2e-4, 2e-3), ("bfloat16", 5e-3, 0.15, 0.05)])
def test_three_steps_match_reference(dtype, loss_tol, grad_tol, change_tol):
    """Loss, every leaf's first gradient (the norm of the difference over
    the leaf's norm or the median leaf's) and the norm of each leaf's
    change over three optimizer steps."""
    from code2vec_tpu.training.optimizers import make_lr, make_optimizer
    from code2vec_tpu.training.steps import (make_train_loss_fn,
                                             make_train_step)

    dims, params, rng = program_weights(dtype)
    bs = batches()
    ref = ref_mod.follow(SEED, spec(dtype), bs, block=4)
    compute = jnp.dtype(dtype)
    loss_fn = make_train_loss_fn(dims, use_sampled_softmax=True,
                                 num_sampled=16, compute_dtype=compute)
    # jitted, as the step runs it: op by op bfloat16 rounds at other
    # places and swaps other near-tied experts than the step does
    first_gradient = jax.jit(jax.value_and_grad(loss_fn))
    loss, grads = first_gradient(
        params, tuple(jnp.asarray(a) for a in bs[0]),
        jax.random.fold_in(rng, 0))
    assert float(loss) == pytest.approx(ref["losses"][0], rel=loss_tol)
    norms = {k: float(np.linalg.norm(v))
             for k, v in ref["dense_grads"].items()}
    median = float(np.median(list(norms.values())))
    got = flat(grads)
    for k, want in ref["dense_grads"].items():
        diff = float(np.linalg.norm(np.asarray(got[k], np.float32) - want))
        assert diff <= grad_tol * max(norms[k], median), k
    for k in ("token_emb", "path_emb", "target_emb"):
        assert float(jnp.linalg.norm(got[k].astype(jnp.float32))) == \
            pytest.approx(ref["grad_norms"][k], rel=grad_tol)

    opt = make_optimizer(make_lr(1e-3, "cosine", 400))
    step = make_train_step(dims, opt, use_sampled_softmax=True,
                           num_sampled=16, compute_dtype=compute)
    start = jax.tree_util.tree_map(jnp.copy, params)
    state = opt.init(params)
    losses = []
    for i, b in enumerate(bs):
        params, state, loss = step(params, state,
                                   tuple(jnp.asarray(a) for a in b),
                                   jax.random.fold_in(rng, i))
        losses.append(float(loss))
    np.testing.assert_allclose(losses, ref["losses"], rtol=loss_tol * 4)
    after, before = flat(params), flat(start)
    c_ref = ref["change_norms"]
    c_median = float(np.median([v for v in c_ref.values() if v > 0]))
    for k, want in c_ref.items():
        change = float(jnp.linalg.norm((after[k].astype(jnp.float32)
                                        - before[k].astype(jnp.float32))))
        assert abs(change - want) <= change_tol * max(want, c_median), k
    # the selection bias is a buffer: no gradient, no change
    assert c_ref["lfm/layers/1/expert_bias"] == 0.0
    np.testing.assert_array_equal(
        np.asarray(after["lfm/layers/1/expert_bias"]),
        np.asarray(before["lfm/layers/1/expert_bias"]))


def test_convolution_is_causal_and_ignores_masked_slots():
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    H, C = 8, 10
    layer = {"conv_in": jax.random.normal(k[0], (H, 3 * H)),
             "conv_k": jax.random.normal(k[1], (H, 3)),
             "conv_out": jax.random.normal(k[2], (H, H))}
    h = jax.random.normal(k[3], (2, C, H))
    mask = np.ones((2, C), np.float32)
    mask[:, 4] = 0.0                      # a hole, and padding at the end
    mask[:, 8:] = 0.0
    out = lfm._short_conv(h, jnp.asarray(mask), layer)
    # causal: slots before t do not see a change at t
    moved = lfm._short_conv(h.at[:, 6].add(1.0), jnp.asarray(mask), layer)
    np.testing.assert_array_equal(np.asarray(out[:, :6]),
                                  np.asarray(moved[:, :6]))
    assert not np.allclose(np.asarray(out[:, 6:8]), np.asarray(moved[:, 6:8]))
    # reach: three taps, so slot 9 does not see slot 6
    np.testing.assert_array_equal(np.asarray(out[:, 9]),
                                  np.asarray(moved[:, 9]))
    # a masked slot's input reaches no other slot
    holed = lfm._short_conv(h.at[:, 4].add(3.0).at[:, 8].add(3.0),
                            jnp.asarray(mask), layer)
    keep = [0, 1, 2, 3, 5, 6, 7, 9]
    np.testing.assert_allclose(np.asarray(out[:, keep]),
                               np.asarray(holed[:, keep]), atol=1e-6)


def test_masked_contexts_do_not_affect_code():
    _dims, params, _ = program_weights()
    _labels, src, pth, dst, mask, _w = batches()[0]
    mask = mask.copy()
    mask[:, 5:] = 0.0
    enc = get_encode_fn(DIMS)
    code1, attn, _ = enc(params, src, pth, dst, jnp.asarray(mask))
    src2 = src.copy()
    src2[:, 5:] = (src2[:, 5:] + 7) % DIMS.token_vocab_size
    code2, *_ = enc(params, jnp.asarray(src2), pth, dst, jnp.asarray(mask))
    np.testing.assert_allclose(np.asarray(code1), np.asarray(code2),
                               atol=1e-6)
    assert np.all(np.isfinite(np.asarray(code1)))
    assert np.all(np.asarray(attn)[:, 5:] < 1e-6)


def test_order_of_contexts_matters():
    """Contexts have an order for the first time: reversing a full bag
    changes the code vector (the bag and the set transformer do not)."""
    _dims, params, _ = program_weights()
    _labels, src, pth, dst, _mask, _w = batches()[0]
    ones = jnp.ones(src.shape, jnp.float32)
    enc = get_encode_fn(DIMS)
    code1, *_ = enc(params, src, pth, dst, ones)
    code2, *_ = enc(params, src[:, ::-1], pth[:, ::-1], dst[:, ::-1], ones)
    assert float(jnp.max(jnp.abs(code1 - code2))) > 1e-3


# ---- the router ----------------------------------------------------------

def _router_case(n=64, H=16, E=16):
    k = jax.random.split(jax.random.PRNGKey(1), 3)
    return (jax.random.normal(k[0], (n, H)),
            0.5 * jax.random.normal(k[1], (H, E)),
            0.3 * jax.random.normal(k[2], (E,)))


# ---- attention's core over the training staircase (ISSUE 35) -------------

def attention_before_the_blocks(h, mask, layer, *, heads, kv_heads, head_dim,
                                theta, norm, turned=None, gated=False):
    """`seq_block.attention` as it stood before ISSUE 35 (its comments
    left out): what a caller that passes no staircase still lowers
    to."""
    import math

    dtype = h.dtype
    B, C, _ = h.shape
    n, n_kv, hd = heads, kv_heads, head_dim

    def split(t, count, scale=None):
        t = t.reshape(B, C, count, -1)
        gate = None
        if t.shape[-1] != hd:
            t, gate = t[..., :hd], t[..., hd:]
        if scale is not None:
            t = norm(t, scale)
        return t.transpose(0, 2, 1, 3), gate

    q, gate = split(h @ layer["q"].astype(dtype), n, layer["q_norm"])
    q = seq_block.rotary(q, theta, turned)
    k = seq_block.rotary(split(h @ layer["k"].astype(dtype), n_kv,
                               layer["k_norm"])[0], theta, turned)
    v, _ = split(h @ layer["v"].astype(dtype), n_kv)
    q = q.reshape(B, n_kv, n // n_kv, C, hd)
    logits = jnp.einsum("bkgqd,bkcd->bkgqc", q, k).astype(jnp.float32) \
        / math.sqrt(hd)
    slot = jnp.arange(logits.shape[-1])
    seen = (slot[None, :] <= slot[:, None])[None] & (mask > 0)[:, None, :]
    logits = jnp.where(seen[:, None, None], logits, -1e30)
    att = jax.nn.softmax(logits, axis=-1).astype(dtype)
    out = jnp.einsum("bkgqc,bkcd->bkgqd", att, v)
    out = out.reshape(B, n, C, hd).transpose(0, 2, 1, 3)
    if gated:
        out = out * jax.nn.sigmoid(gate)
    return out.reshape(B, C, n * hd) @ layer["o"].astype(dtype)


def attention_case(gated: bool, H=32, n=4, n_kv=2, hd=8, turned=None):
    """(layer, h [8, 20, H], the mixer's keywords): four query heads on
    two key heads."""
    k = jax.random.split(jax.random.PRNGKey(21), 5)
    w = lambda key, shape: 0.3 * jax.random.normal(key, shape)  # noqa: E731
    layer = {"q": w(k[0], (H, n * hd * (2 if gated else 1))),
             "k": w(k[1], (H, n_kv * hd)), "v": w(k[2], (H, n_kv * hd)),
             "o": w(k[3], (n * hd, H)),
             "q_norm": 1.0 + 0.1 * jnp.arange(hd, dtype=jnp.float32),
             "k_norm": jnp.ones((hd,))}
    kw = dict(heads=n, kv_heads=n_kv, head_dim=hd, theta=1e4, turned=turned,
              gated=gated, norm=lambda t, s: (
                  t * jax.lax.rsqrt(jnp.mean(t * t, -1, keepdims=True)
                                    + 1e-6) * s).astype(t.dtype))
    return layer, jax.random.normal(k[4], (8, 20, H)), kw


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_with_no_staircase_attention_lowers_to_the_old_program(dtype):
    """Evaluation, prediction, serving and every batch that does not
    fit its staircase pass none: forward and backward, what they lower
    to does not know the argument exists."""
    layer, h, kw = attention_case(gated=False)
    h = h.astype(dtype)
    mask = jnp.asarray(staircase_mask(STAIR_CASES["uneven"]))

    def new(h, layer):
        return seq_block.attention(h, mask, layer, **kw)

    def old(h, layer):
        return attention_before_the_blocks(h, mask, layer, **kw)

    assert lowered_texts(new, h, layer) == lowered_texts(old, h, layer)
    by_block = lowered_texts(
        lambda h, layer: seq_block.attention(
            h, mask, layer, blocks=seq_block.core_blocks(
                STAIR_CASES["uneven"], None, 20), **kw), h, layer)
    assert by_block[0] != lowered_texts(new, h, layer)[0]


@pytest.mark.parametrize("block_slots", [1, 8])
@pytest.mark.parametrize("case", list(STAIR_CASES))
def test_attention_over_a_staircase_is_the_whole_core(case, block_slots,
                                                      monkeypatch):
    """Grouped heads, no gate: each rectangle a query block, and
    neighbours joined to blocks of 8 slots."""
    from code2vec_tpu.data import staircase

    monkeypatch.setattr(staircase, "_BLOCK_SLOTS", block_slots)
    layer, h, kw = attention_case(gated=False)
    assert_staircase_mixer_is_the_whole(
        lambda h, mask, layer, stairs: seq_block.attention(
            h, mask, layer,
            blocks=seq_block.core_blocks(stairs, None, h.shape[1]), **kw),
        h, layer, STAIR_CASES[case])


def test_the_encoder_hands_its_staircase_to_its_attention_layers(
        monkeypatch):
    """With a staircase the encoder's text holds the blocks' scores (a
    [rows, 2, 2, slots, keys] product a block, each rectangle one) and
    not the whole layer's; the code vector is the one it gives with
    none."""
    from code2vec_tpu.data import staircase

    monkeypatch.setattr(staircase, "_BLOCK_SLOTS", 1)
    stairs = ((0, 8), (4, 6), (8, 3))
    _dims, params, _ = program_weights()
    mask = staircase_mask(stairs, 8, 12)
    r = np.random.default_rng(9)
    src, pth, dst = (jnp.asarray((r.integers(2, v + 2, mask.shape)
                                  * mask).astype(np.int32))
                     for v in (SIZES["tokens"], SIZES["paths"],
                               SIZES["tokens"]))

    def encode(stairs):
        return jax.jit(lambda p: get_encode_fn(DIMS)(
            p, src, pth, dst, jnp.asarray(mask), staircase=stairs)[0])

    text = encode(stairs).lower(params).as_text()
    assert "tensor<8x2x2x12x12xf32>" not in text
    for rows, queries, keys in ((8, 4, 4), (6, 4, 8), (3, 4, 12)):
        assert f"tensor<{rows}x2x2x{queries}x{keys}xf32>" in text
    assert "tensor<8x2x2x12x12xf32>" in encode(None).lower(params).as_text()
    np.testing.assert_allclose(np.asarray(encode(stairs)(params)),
                               np.asarray(encode(None)(params)),
                               rtol=1e-4, atol=1e-5)
    # `core_blocks` alone says whether the core runs by block
    monkeypatch.setattr(seq_block, "core_blocks", lambda *a: None)
    assert "tensor<8x2x2x12x12xf32>" in encode(stairs).lower(
        params).as_text()


def test_router_bias_changes_who_is_chosen_and_not_p():
    h, router, bias = _router_case()
    chosen0, p0 = moe.route(h, router, jnp.zeros_like(bias), 4)
    chosen1, p1 = moe.route(h, router, bias, 4)
    assert np.any(np.sort(chosen0, -1) != np.sort(chosen1, -1))
    # p is made of the scores alone, whatever the bias that chose them
    s = jax.nn.sigmoid(h @ router)
    s1 = jnp.take_along_axis(s, chosen1, axis=-1)
    np.testing.assert_allclose(
        np.asarray(p1), np.asarray(s1 / (s1.sum(-1, keepdims=True) + 1e-6)),
        rtol=1e-5)
    # a token whose choice the bias left alone keeps its p
    same = np.all(np.sort(chosen0, -1) == np.sort(chosen1, -1), axis=-1)
    assert same.any()
    np.testing.assert_allclose(np.sort(np.asarray(p0)[same], -1),
                               np.sort(np.asarray(p1)[same], -1), rtol=1e-6)


def test_router_p_sums_to_one_over_the_chosen():
    h, router, bias = _router_case()
    chosen, p = moe.route(h, router, bias, 4)
    assert chosen.shape == p.shape == (64, 4)
    assert all(len(set(row)) == 4 for row in np.asarray(chosen).tolist())
    np.testing.assert_allclose(np.asarray(p.sum(-1)), 1.0, atol=1e-5)
    assert np.all(np.asarray(p) > 0)


# ---- expert parallelism --------------------------------------------------

def _layer_case(E=64, H=32, F=24, n_tokens=96, seed=2):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    return dict(
        h=jax.random.normal(k[0], (n_tokens, H)),
        valid=jnp.arange(n_tokens) % 7 != 3,
        router=0.4 * jax.random.normal(k[1], (H, E)),
        bias=0.05 * jax.random.normal(k[2], (E,)),
        w1=0.2 * jax.random.normal(k[3], (E, H, F)),
        w3=0.2 * jax.random.normal(k[4], (E, H, F)),
        w2=0.2 * jax.random.normal(k[5], (E, F, H)))


def _share(case, first, held, per_token=4):
    chosen, p = moe.route(case["h"], case["router"], case["bias"], per_token)
    sl = slice(first, first + held)
    return moe.held_experts_ffn(case["h"], case["valid"], chosen, p,
                                case["w1"][sl], case["w3"][sl],
                                case["w2"][sl], first)


def test_the_eight_shares_add_up_to_the_whole_layer():
    """Experts 0-7, 8-15, ... as eight chips would hold them: their parts
    of the result sum to the uncut 64-expert reference layer, and their
    rows to every choice of every valid token."""
    case = _layer_case()
    with jax.default_matmul_precision("highest"):
        whole = ref_mod.expert_layer(
            case["h"], case["valid"], case["router"], case["bias"],
            case["w1"], case["w3"], case["w2"], first=0, per_token=4)
        parts = [_share(case, first, 8) for first in range(0, 64, 8)]
    total = sum(out for out, _rows in parts)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=2e-5)
    rows = sum(int(r.sum()) for _out, r in parts)
    assert rows == 4 * int(case["valid"].sum())
    # one share against the reference given the same share
    with jax.default_matmul_precision("highest"):
        want = ref_mod.expert_layer(
            case["h"], case["valid"], case["router"], case["bias"],
            case["w1"][16:24], case["w3"][16:24], case["w2"][16:24],
            first=16, per_token=4)
    np.testing.assert_allclose(np.asarray(parts[2][0]), np.asarray(want),
                               atol=2e-5)
    assert not np.allclose(np.asarray(parts[2][0]), np.asarray(whole),
                           atol=1e-3)


def test_no_row_is_dropped_when_all_tokens_choose_one_held_expert():
    case = _layer_case(E=16)
    case["valid"] = jnp.ones(96, bool)
    # expert 5's score saturates for every token
    case["router"] = case["router"].at[:, 5].set(0.0)
    case["bias"] = case["bias"].at[5].set(10.0)
    with jax.default_matmul_precision("highest"):
        out, rows = _share(case, 4, 4)
        want = ref_mod.expert_layer(
            case["h"], case["valid"], case["router"], case["bias"],
            case["w1"][4:8], case["w3"][4:8], case["w2"][4:8], first=4,
            per_token=4)
    assert int(rows[1]) == 96                   # every token, none dropped
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def test_masked_tokens_are_routed_nowhere():
    case = _layer_case(E=16)
    out, rows = _share(case, 0, 16)
    assert int(rows.sum()) == 4 * int(case["valid"].sum())
    np.testing.assert_array_equal(np.asarray(out)[~np.asarray(case["valid"])],
                                  0.0)


# ---- the row bound -------------------------------------------------------

def _bound_case(live, n=96, E=64, first=16, held=8, K=4, H=32, F=24):
    """A layer of `held` of E experts over n all-valid tokens whose
    choices are set by hand: `live` of the n K pairs fall on held
    experts (a token's on different ones), the others on experts held
    elsewhere. At these sizes the bound is 128 of 384 pairs."""
    k = jax.random.split(jax.random.PRNGKey(5), 5)
    chosen = np.zeros((n, K), np.int32)
    for t in range(n):
        here = min(K, max(0, live - K * t))
        for j in range(K):
            chosen[t, j] = first + (t + j) % held if j < here \
                else (first + held + (3 * t + j) % (E - held)) % E
        chosen[t] = np.roll(chosen[t], t)
    assert int(((chosen >= first) & (chosen < first + held)).sum()) == live
    assert all(len(set(row)) == K for row in chosen.tolist())
    p = jax.random.uniform(k[0], (n, K), minval=0.1, maxval=1.0)
    return dict(
        h=jax.random.normal(k[1], (n, H)), valid=jnp.ones(n, bool),
        chosen=jnp.asarray(chosen), p=p / p.sum(-1, keepdims=True),
        w1=0.2 * jax.random.normal(k[2], (held, H, F)),
        w3=0.2 * jax.random.normal(k[3], (held, H, F)),
        w2=0.2 * jax.random.normal(k[4], (held, F, H)), first=first, E=E)


_FLOATS = ("h", "p", "w1", "w3", "w2")


def _layer_and_grads(case, routed, wrap=lambda f: f):
    """The layer's output, the rows, and the gradient of a weighted sum
    of the output for h, p and the three weights."""
    weight = jax.random.normal(jax.random.PRNGKey(9), case["h"].shape)

    def run(*floats):
        kw = dict(zip(_FLOATS, floats))
        out, rows = moe.held_experts_ffn(
            kw["h"], case["valid"], case["chosen"], kw["p"], kw["w1"],
            kw["w3"], kw["w2"], case["first"], routed)
        return jnp.sum(out * weight), (out, rows)

    step = jax.jit(jax.grad(wrap(run), argnums=tuple(range(5)),
                            has_aux=True))
    grads, (out, rows) = step(*(case[name] for name in _FLOATS))
    return out, rows, dict(zip(_FLOATS, grads))


def test_row_bound_is_twice_the_even_share_up_to_a_tile():
    # the cell: 128 methods x 200 slots, 4 choices, 8 of 64 held
    assert moe.row_bound(25600 * 4, 8, 64) == 25600
    assert moe.row_bound(384, 8, 64) == 128          # 96, up to the tile
    assert moe.row_bound(1000 * 128, 8, 64) % moe.ROW_TILE == 0
    # half or all of the experts here: nothing to leave out
    assert moe.row_bound(192, 4, 8) == 192
    assert moe.row_bound(102400, 64, 64) == 102400


# 60 live pairs of 384, the bound itself, one more, and every pair
@pytest.mark.parametrize("live,at_bound", [(60, True), (128, True),
                                           (129, False), (384, False)])
def test_the_bounded_layer_is_the_full_length_layer(live, at_bound,
                                                    monkeypatch):
    """Output, rows and the gradients for h, p, w1, w3, w2 with 8 of 64
    experts held (arrays of 128 rows, or the overflow's 384) against the
    one full-length body (`routed` None). The overflow runs that same
    body: bit for bit. At the bound the products see the same rows at
    the same offsets, so p's and the weights' gradients are the full
    length's to the last bits of a float32 sum over rows; the output and
    h's gradient add a token's rows in sorted order, not choice order:
    float32 rounding."""
    case = _bound_case(live)
    want_out, want_rows, want = _layer_and_grads(case, None)
    out, rows, got = _layer_and_grads(case, case["E"])
    assert int(rows.sum()) == live
    np.testing.assert_array_equal(np.asarray(rows), np.asarray(want_rows))
    assert bool(moe.fits(rows, moe.row_bound(384, 8, 64))) == at_bound
    if at_bound:
        np.testing.assert_allclose(np.asarray(out), np.asarray(want_out),
                                   rtol=1e-5, atol=1e-6)
        for name in _FLOATS:
            np.testing.assert_allclose(
                np.asarray(got[name]), np.asarray(want[name]), rtol=1e-5,
                atol=1e-6, err_msg=name)
    else:
        np.testing.assert_array_equal(np.asarray(out), np.asarray(want_out))
        for name in _FLOATS:
            np.testing.assert_array_equal(
                np.asarray(got[name]), np.asarray(want[name]), err_msg=name)
    # which body ran: the other one, poisoned, changes nothing
    other = "_every_pair" if at_bound else "_first_pairs"
    monkeypatch.setattr(
        moe, other, lambda h, *a, **kw: jnp.full_like(h, jnp.nan))
    out2, _rows, got2 = _layer_and_grads(case, case["E"])
    np.testing.assert_array_equal(np.asarray(out2), np.asarray(out))
    np.testing.assert_array_equal(np.asarray(got2["w2"]),
                                  np.asarray(got["w2"]))


def test_the_products_live_rows_are_the_full_lengths_bit_for_bit():
    case = _bound_case(100)
    n, k = case["chosen"].shape
    key = np.where((case["chosen"] >= 16) & (case["chosen"] < 24),
                   case["chosen"] - 16, 8).reshape(-1)
    order = jnp.argsort(jnp.asarray(key))
    rows = jnp.bincount(jnp.asarray(key), length=9)[:8].astype(jnp.int32)
    x = jnp.take(case["h"], order // k, axis=0)
    weights = (case["p"], case["w1"], case["w3"], case["w2"], rows)
    whole = moe._products(x, order, *weights)
    head = moe._products(x[:128], order[:128], *weights)
    np.testing.assert_array_equal(np.asarray(head), np.asarray(whole[:128]))
    assert np.all(np.asarray(whole[100:]) == 0)
    assert np.all(np.asarray(whole[:100]).any(axis=1))


def test_all_experts_held_lowers_to_one_body_and_no_conditional():
    case = _bound_case(60)

    def text(routed):
        return jax.jit(
            lambda h: moe.held_experts_ffn(
                h, case["valid"], case["chosen"], case["p"], case["w1"],
                case["w3"], case["w2"], case["first"], routed)[0]
        ).lower(case["h"]).as_text()

    assert "stablehlo.case" in text(64)
    for one_body in (text(None), text(8), text(16)):
        assert "stablehlo.case" not in one_body
        assert "stablehlo.if" not in one_body


@pytest.mark.parametrize("live", [60, 128, 129])
def test_a_program_that_takes_no_gradient_adds_no_rows_by_index(live):
    """Evaluation, prediction and serving run `_bounded` itself, whose
    rows go back through a gather (`_take_back_head`): the same output
    as the differentiated forward's scatter-add, to float32 rounding at
    the bound and bit for bit in the overflow (one body), and no scatter
    of floats in the lowered text, where the differentiated program has
    one (on the v5e XLA's scatter-add of 512 rows did not return inside
    the qwen3_next predict step of 8 methods: PERF.md section 6, PR
    32)."""
    case = _bound_case(live)

    def layer(h):
        return moe.held_experts_ffn(
            h, case["valid"], case["chosen"], case["p"], case["w1"],
            case["w3"], case["w2"], case["first"], case["E"])[0]

    forward = jax.jit(layer)
    trained = jax.jit(lambda h: jax.value_and_grad(
        lambda h: jnp.sum(layer(h)))(h))
    assert not float_scatters(forward.lower(case["h"]).as_text())
    assert float_scatters(trained.lower(case["h"]).as_text())
    want = _layer_and_grads(case, case["E"])[0]
    got = forward(case["h"])
    if live <= 128:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)
    else:
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_gradients_under_remat_are_the_unrematted_ones():
    """`encode_lfm2_moe` runs each layer under `jax.checkpoint` inside
    the step's jit."""
    case = _bound_case(90)
    _out, _rows, plain = _layer_and_grads(case, case["E"])
    _out, _rows, remat = _layer_and_grads(case, case["E"],
                                          wrap=jax.checkpoint)
    for name in _FLOATS:
        np.testing.assert_array_equal(np.asarray(remat[name]),
                                      np.asarray(plain[name]), err_msg=name)


def _eight_of_64():
    block = dict(BLOCK, num_experts=8, num_routed_experts=64,
                 first_expert=16, num_experts_per_tok=4)
    return dataclasses.replace(DIMS, lfm=Lfm2Dims.from_config(block))


@pytest.mark.parametrize("all_here", [False, True])
def test_encoder_counts_the_bound_and_the_layers_that_ran_at_it(all_here):
    """aux, per expert layer: the held experts' rows, the valid tokens,
    the row bound (8 methods x 12 slots x 4 choices = 384 pairs, 128
    held) and whether the layer ran at it; not when every token's
    choices fall on held experts (the overflow)."""
    dims = _eight_of_64()
    params = init_params(jax.random.PRNGKey(3), dims)
    _labels, src, pth, dst, mask, _w = batches()[0]
    if all_here:
        mask = np.ones_like(mask)
        for layer in params["lfm"]["layers"][1:]:
            layer["expert_bias"] = layer["expert_bias"].at[16:20].set(10.0)
    encode = jax.jit(
        lambda p: get_encode_fn(dims)(p, src, pth, dst, jnp.asarray(mask)))
    _code, _attn, aux = encode(params)
    aux = np.asarray(aux)
    assert aux.shape == (2, 8 + 3)
    assert aux[:, -3].tolist() == [int(mask.sum())] * 2
    assert aux[:, -2].tolist() == [128, 128]
    if all_here:
        assert aux[:, :8].sum(axis=1).tolist() == [384, 384]
        assert aux[:, -1].tolist() == [0, 0]
    else:
        assert np.all(aux[:, :8].sum(axis=1) <= 128)
        assert aux[:, -1].tolist() == [1, 1]


def test_under_a_mesh_every_device_decides_for_its_own_rows():
    """Four devices, 4 methods x 12 slots x 4 choices = 192 pairs and a
    bound of 128 each: aux sums the devices' bounds and decisions, the
    code vector and its gradient are the one-device encoder's."""
    from code2vec_tpu.parallel.mesh import make_mesh

    dims = _eight_of_64()
    params = init_params(jax.random.PRNGKey(3), dims)
    _labels, src, pth, dst, mask, _w = batches(n=16)[0]
    mesh = make_mesh(0, 1, devices=jax.devices()[:4])

    def run(mesh):
        def loss(p):
            code, _attn, aux = get_encode_fn(dims)(
                p, src, pth, dst, jnp.asarray(mask), mesh=mesh)
            return jnp.sum(code ** 2), (code, aux)
        return jax.jit(jax.grad(loss, has_aux=True))

    on_mesh, on_one = run(mesh), run(None)
    grads, (code, aux) = on_mesh(params)
    want_grads, (want_code, want_aux) = on_one(params)
    aux, want_aux = np.asarray(aux), np.asarray(want_aux)
    assert aux[:, -2:].tolist() == [[4 * 128, 4]] * 2
    assert want_aux[:, -2:].tolist() == [[256, 1]] * 2
    np.testing.assert_array_equal(aux[:, :-2], want_aux[:, :-2])
    np.testing.assert_allclose(np.asarray(code), np.asarray(want_code),
                               rtol=1e-4, atol=1e-5)
    got, want = flat(grads["lfm"]), flat(want_grads["lfm"])
    for name in ("layers/1/w1", "layers/2/w2", "layers/1/router"):
        np.testing.assert_allclose(np.asarray(got[name]),
                                   np.asarray(want[name]), rtol=1e-3,
                                   atol=1e-5, err_msg=name)


def test_encoder_with_half_the_experts_here_has_one_body():
    _dims, params, _ = program_weights()
    _labels, src, pth, dst, mask, _w = batches()[0]
    _code, _attn, aux = get_encode_fn(DIMS)(params, src, pth, dst,
                                            jnp.asarray(mask))
    # 4 of 8 held: the bound is every pair (8 x 12 x 2), never "compact"
    assert np.asarray(aux)[:, -2:].tolist() == [[192, 0]] * 2


def test_compiles_stay_zero_across_batches_of_different_routing():
    import optax

    from code2vec_tpu.training.steps import make_train_step

    _dims, params, rng = program_weights()
    opt = optax.adam(1e-3)
    step = make_train_step(DIMS, opt, use_sampled_softmax=True,
                           num_sampled=16)
    state = opt.init(params)
    compiles = [0]

    def on_duration(event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    rows = []
    for i, b in enumerate(batches(steps=5, seed=11)):
        params, state, _loss = step(params, state,
                                    tuple(jnp.asarray(a) for a in b),
                                    jax.random.fold_in(rng, i))
        if i == 0:
            jax.block_until_ready(params)
            compiles[0] = 0
    step.route_recorder.flush()
    from code2vec_tpu.obs import memory_tracer
    rows = [tuple(map(tuple, r["attrs"]["layers"]))
            for r in memory_tracer().records("moe/route")[-5:]]
    assert len(set(rows)) == 5                  # five different routings
    assert compiles[0] == 0


# ---- configuration -------------------------------------------------------

def _lfm_config_file(tmp_path):
    path = tmp_path / "block.json"
    path.write_text(json.dumps(BLOCK))
    return str(path)


@pytest.mark.parametrize("flags,message", [
    (["--tables_dtype", "int8"], "int8"),
    (["--sparse_embeddings", "--embedding_optimizer", "adam",
      "--lr_schedule", "constant"], "SPARSE_EMBEDDING_UPDATES"),
    (["--head", "varmisuse"], "varmisuse"),
    (["--ring_attention"], "ring attention"),
    (["--mesh_context", "2"], "context-parallel"),
    (["--no_lfm_config"], "--lfm_config")])
def test_verify_refuses(tmp_path, flags, message):
    from code2vec_tpu.config import Config

    argv = ["--data", str(tmp_path / "d"), "--encoder", "lfm2_moe",
            "--backend", "cpu"]
    if flags == ["--no_lfm_config"]:
        flags = []
    else:
        argv += ["--lfm_config", _lfm_config_file(tmp_path)]
    with pytest.raises(ValueError, match=message):
        Config.load_from_args(argv + flags)


def test_block_sizes_come_from_the_config_json():
    assert LFM.routed == 8 and LFM.head_dim == 16
    assert LFM.layer_types == ("conv", "full_attention", "conv")
    # every width comes from the file; only the share has a default:
    # all the router's experts, held from the first
    whole = Lfm2Dims.from_config({k: v for k, v in BLOCK.items() if k not in
                                  ("num_routed_experts", "first_expert")})
    assert (whole.routed, whole.first_expert) == (4, 0)
    with pytest.raises(ValueError, match="hidden_size"):
        Lfm2Dims.from_config({k: v for k, v in BLOCK.items()
                              if k != "hidden_size"})
    with pytest.raises(ValueError, match="norm_topk_prob"):
        Lfm2Dims.from_config(dict(BLOCK, norm_topk_prob=False))
    with pytest.raises(ValueError, match="held"):
        Lfm2Dims.from_config(dict(BLOCK, first_expert=6))
    with pytest.raises(ValueError, match="layer_types"):
        Lfm2Dims.from_config(dict(BLOCK, layer_types=["mamba"]))


# ---- tracing -------------------------------------------------------------

class _FakeCounts:
    """What `RouteRecorder` uses of a device array."""

    def __init__(self, table, ready=True):
        self.table, self.ready, self.copied = table, ready, False

    def copy_to_host_async(self):
        self.copied = True

    def is_ready(self):
        return self.ready

    def tolist(self):
        return self.table


def test_route_records_are_written_a_step_behind_and_never_waited_for(
        monkeypatch):
    from code2vec_tpu.obs import route, trace

    monkeypatch.setattr(trace, "_MEMORY_TRACER", trace.MemoryTracer())
    records = lambda: trace.memory_tracer().records("moe/route")  # noqa: E731
    rec = route.RouteRecorder()
    first = _FakeCounts([[3, 1, 9, 128, 1], [2, 2, 9, 128, 0]])
    rec.push(first)
    assert first.copied and records() == []      # never the step just sent
    slow = _FakeCounts([[1, 1, 5, 128, 1]], ready=False)
    rec.push(slow)
    (only,) = records()
    assert only["attrs"] == {"seq": 0, "layers": [[3, 1], [2, 2]],
                             "rows_here": 8, "valid_tokens": 9,
                             "row_bound": 128, "compact_layers": 1}
    rec.push(_FakeCounts([[0, 0, 0, 128, 1]]))
    assert len(records()) == 1                   # the unready one is not awaited
    rec.flush()
    assert [r["attrs"]["seq"] for r in records()] == [0, 1, 2]


def test_named_scopes_stand_in_every_step_that_runs_the_encoder():
    from code2vec_tpu.training.steps import make_eval_step, make_train_step
    import optax

    _dims, params, rng = program_weights()
    batch = tuple(jnp.asarray(a) for a in batches()[0])
    opt = optax.adam(1e-3)
    train = make_train_step(DIMS, opt, use_sampled_softmax=True,
                            num_sampled=16).lower(
        params, opt.init(params), batch, rng).as_text(debug_info=True)
    evaluate = make_eval_step(DIMS, top_k=3).lower(params, batch).as_text(
        debug_info=True)
    for text in (train, evaluate):
        for scope in ("c2v/encode", "c2v/blk_0/conv", "c2v/blk_0/mlp",
                      "c2v/blk_1/attn", "c2v/blk_1/router",
                      "c2v/blk_1/experts", "c2v/blk_2/conv",
                      "c2v/blk_2/experts", "c2v/pool"):
            assert scope in text, scope


# ---- the model class -----------------------------------------------------

def test_model_trains_evaluates_predicts_and_reloads(tmp_path):
    """Through `Code2VecModel` on the tests' 8-device mesh (every device
    routes its own rows), with the encoder's sizes kept by the
    checkpoint."""
    from code2vec_tpu.models.jax_model import Code2VecModel
    from tests.test_model import tiny_config

    prefix = build_tiny_dataset(str(tmp_path), n_train=256, n_val=32,
                                n_test=64, max_contexts=16)
    cfg = tiny_config(prefix, ENCODER_TYPE="lfm2_moe",
                      BLOCK_CONFIG=_lfm_config_file(tmp_path),
                      NUM_TRAIN_EPOCHS=6, LEARNING_RATE=0.003,
                      TELEMETRY_DIR=str(tmp_path / "tele"), TRACE=True)
    ckpt_dir = str(tmp_path / "ckpt")
    cfg.save_path = ckpt_dir
    model = Code2VecModel(cfg)
    model.train()
    result = model.evaluate()
    assert result.subtoken_f1 > 0.3
    model.save(ckpt_dir)
    from code2vec_tpu.obs import memory_tracer
    assert memory_tracer().records("moe/route")[-1]["attrs"]["rows_here"] > 0
    # the --trace log holds a record a step, and the report prints them
    from tests.test_trace import _spans
    from tools.trace_report import render, route_summary
    spans = _spans(model.telemetry.run_dir)
    route = route_summary(spans)
    assert route["steps"] == model.step_num
    assert (route["expert_layers"], route["held_experts"]) == (2, 4)
    assert 0 < route["rows_here"] <= 2 * 2 * route["valid_tokens"]
    assert route["imbalance"] >= 1.0
    report = render([({}, spans)])
    assert f"Routed experts: {route['rows_here']:,} rows" in report
    # 4 of 8 experts are held: one body over every pair (32 methods x
    # 16 slots x 2 choices, summed over the 8 devices)
    assert route["row_bound"] == 32 * 16 * 2
    assert route["compact_layers"] == 0
    assert f"row_bound {route['row_bound']:,}, compact_layers 0 of " \
        f"{2 * model.step_num}" in report
    assert route_summary([s for s in spans if s["name"] != "moe/route"]) \
        is None

    cfg2 = tiny_config(prefix)
    cfg2.load_path = ckpt_dir
    model2 = Code2VecModel(cfg2)
    assert model2.dims.lfm == LFM
    loaded = model2.evaluate()
    assert loaded.topk_acc == pytest.approx(result.topk_acc)
