"""`--encoder qwen3_next` (models/qwen3_next_encoder.py, models/seq_block.py,
ops/delta_rule.py, ops/moe.py, obs/route.py) at tiny sizes on the CPU,
against the configuration's plain reference
(benchmark/reference_qwen3next.py): code vector, loss, every leaf's
gradient and three optimizer steps in float32 and bfloat16; the
expert-parallel shares and the shared expert adding up to the whole
layer; the partial rotary term; the two gates; the softmax router; no
dropped row; no recompilation across routings; what `Config.verify()`
and `Qwen3NextDims.from_config` refuse; the option's two spellings; the
`gdn/scan` record; the step's named scopes; the scan under the training
staircase (ISSUE 33); the model class end to end under the tests' 8-device
mesh, saved and resumed."""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from code2vec_tpu.models import qwen3_next_encoder as qwen
from code2vec_tpu.models import seq_block
from code2vec_tpu.models.encoder import (ModelDims, get_encode_fn,
                                         init_params)
from code2vec_tpu.models.qwen3_next_encoder import Qwen3NextDims
from code2vec_tpu.ops import moe
from tests.helpers import (STAIR_CASES, assert_staircase_mixer_is_the_whole,
                           build_tiny_dataset, float_scatters,
                           lowered_texts, staircase_mask)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

import reference_qwen3next as ref_mod  # noqa: E402

# one whole period, lin lin lin full; 4 of 16 experts held from the
# fourth; two value heads a key head; a quarter of a head turns
BLOCK = dict(num_hidden_layers=4, full_attention_interval=4, hidden_size=64,
             num_attention_heads=4, num_key_value_heads=2, head_dim=16,
             partial_rotary_factor=0.25, rope_theta=1e7, rms_norm_eps=1e-6,
             linear_conv_kernel_dim=4, linear_key_head_dim=8,
             linear_value_head_dim=8, linear_num_key_heads=2,
             linear_num_value_heads=4, num_experts=4, num_routed_experts=16,
             first_expert=4, num_experts_per_tok=3, moe_intermediate_size=24,
             shared_expert_intermediate_size=24)
QWEN = Qwen3NextDims.from_config(BLOCK)
# the tables keep the product's width and at least 128 rows: optax
# factors Adafactor's second moment only from 128 up, as the reference
# always does
SIZES = dict(tokens=200, paths=150, targets=130, embedding=128,
             max_contexts=12, num_sampled=16, dropout_keep=0.75)
DIMS = ModelDims(token_vocab_size=202, path_vocab_size=152,
                 target_vocab_size=132, embeddings_size=128, max_contexts=12,
                 dropout_keep_rate=0.75, encoder_type="qwen3_next",
                 qwen=QWEN)
SEED = 7


def spec(dtype="float32"):
    return dict(SIZES, encoder="qwen3_next", tables_dtype=dtype, lr=1e-3,
                lr_schedule="cosine", lr_total_steps=400, **BLOCK)


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
    # the zero-centred norms start at 0, the gated norm at 1
    assert float(jnp.max(jnp.abs(got["qwen/layers/0/op_norm"]))) == 0.0
    assert float(jnp.min(got["qwen/layers/0/gdn_norm"])) == 1.0
    assert float(jnp.min(got["qwen/layers/1/dt_bias"])) == 1.0
    a = np.exp(np.asarray(got["qwen/layers/2/A_log"]))
    assert np.all((a > 0) & (a < 16))


# the norm of the difference over the norm: float32 leaves room for
# summation order alone; bfloat16 (8 bits of mantissa through four
# layers) for its rounding and for the tokens whose near-tied experts it
# swaps, four routers deep: a method of three contexts with one of them
# swapped stands 0.7 off, the batch 0.13-0.26 by its seed
@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5),
                                       ("bfloat16", 0.4)])
def test_code_vector_matches_reference(dtype, tol):
    dims, params, _ = program_weights()
    _labels, src, pth, dst, mask, _w = batches()[0]
    encode = jax.jit(lambda p: get_encode_fn(dims)(
        p, src, pth, dst, jnp.asarray(mask),
        compute_dtype=jnp.dtype(dtype)))
    code, attn, _ = encode(params)
    p, _ = ref_mod.make_weights(SEED, spec())
    c = jnp.concatenate([p["token_emb"][src], p["path_emb"][pth],
                         p["token_emb"][dst]], axis=-1)
    reference = jax.jit(lambda p, c: ref_mod.encode(
        p, c, jnp.asarray(mask), ref_mod.base.rounding(None), spec()))
    with jax.default_matmul_precision("highest"):
        want = reference(p, c)
    gap = float(jnp.linalg.norm(code.astype(jnp.float32) - want)
                / jnp.linalg.norm(want))
    assert gap <= tol, gap
    assert np.all(np.asarray(attn)[mask == 0] < 1e-6)


# float32 holds the program to the reference. The bfloat16 limits only
# say that the bfloat16 path runs at the right scale: at these widths a
# hidden state of 64 with rms 0.17, under norms that divide by it, lets 8
# bits of mantissa move the first layers' gradients by more than their
# own size (1.0-3.1 on the worst leaf by the batch's seed, and as much
# with every expert chosen and no router to swap; the convolution's taps
# summed in bfloat16 read 5 times that, which is why they are summed in
# float32). What bfloat16 costs at the published widths is the cell's
# `dense_grad_diff` (PERF.md section 2). The rate is a tenth of the
# lfm2_moe test's: at 1e-3 the second step's routers differ
@pytest.mark.parametrize("dtype,loss_tol,grad_tol,change_tol", [
    ("float32", 1e-5, 2e-4, 2e-3), ("bfloat16", 3e-2, 6.0, 0.3)])
def test_three_steps_match_reference(dtype, loss_tol, grad_tol, change_tol):
    """Loss, every leaf's first gradient (the norm of the difference over
    the leaf's norm or the median leaf's) and the norm of each leaf's
    change over three optimizer steps."""
    from code2vec_tpu.training.optimizers import make_lr, make_optimizer
    from code2vec_tpu.training.steps import (make_train_loss_fn,
                                             make_train_step)

    lr = 1e-4
    dims, params, rng = program_weights(dtype)
    bs = batches()
    ref = ref_mod.follow(SEED, dict(spec(dtype), lr=lr), bs, block=4)
    compute = jnp.dtype(dtype)
    loss_fn = make_train_loss_fn(dims, use_sampled_softmax=True,
                                 num_sampled=16, compute_dtype=compute)
    first_gradient = jax.jit(jax.value_and_grad(loss_fn))
    loss, grads = first_gradient(
        params, tuple(jnp.asarray(a) for a in bs[0]),
        jax.random.fold_in(rng, 0))
    assert float(loss) == pytest.approx(ref["losses"][0], rel=loss_tol)
    norms = {k: float(np.linalg.norm(v))
             for k, v in ref["dense_grads"].items()}
    median = float(np.median(list(norms.values())))
    got = flat(grads)
    assert set(ref["dense_grads"]) <= set(got)
    for k, want in ref["dense_grads"].items():
        diff = float(np.linalg.norm(np.asarray(got[k], np.float32) - want))
        assert diff <= grad_tol * max(norms[k], median), k
    for k in ("token_emb", "path_emb", "target_emb"):
        assert float(jnp.linalg.norm(got[k].astype(jnp.float32))) == \
            pytest.approx(ref["grad_norms"][k], rel=grad_tol)
    # every leaf of the block learns: the decays and the gates among them
    for k in ("qwen/layers/0/A_log", "qwen/layers/0/dt_bias",
              "qwen/layers/1/conv_k", "qwen/layers/2/gdn_norm",
              "qwen/layers/3/q_norm", "qwen/layers/3/shared_gate",
              "qwen/layers/0/router"):
        assert norms[k] > 0, k

    opt = make_optimizer(make_lr(lr, "cosine", 400))
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
    np.testing.assert_allclose(losses, ref["losses"], rtol=loss_tol * 10)
    after, before = flat(params), flat(start)
    c_ref = ref["change_norms"]
    c_median = float(np.median([v for v in c_ref.values() if v > 0]))
    for k, want in c_ref.items():
        change = float(jnp.linalg.norm((after[k].astype(jnp.float32)
                                        - before[k].astype(jnp.float32))))
        assert abs(change - want) <= change_tol * max(want, c_median), k


@pytest.mark.parametrize("fault", ref_mod.FAULTS)
def test_every_planted_fault_moves_the_reference(fault):
    """What `--readings all` plants is seen at all: the reference's code
    vectors differ with the fault in."""
    p, _ = ref_mod.make_weights(SEED, spec())
    _labels, src, pth, dst, mask, _w = batches()[0]
    c = jnp.concatenate([p["token_emb"][src], p["path_emb"][pth],
                         p["token_emb"][dst]], axis=-1)

    def run(fault):
        encode = jax.jit(lambda p, c: ref_mod.encode(
            p, c, jnp.asarray(mask), ref_mod.base.rounding(None), spec(),
            fault))
        with jax.default_matmul_precision("highest"):
            return encode(p, c)

    sound, faulty = run(None), run(fault)
    gap = float(jnp.linalg.norm(sound - faulty) / jnp.linalg.norm(sound))
    assert gap > 1e-3, gap


def test_masked_contexts_do_not_affect_code():
    _dims, params, _ = program_weights()
    _labels, src, pth, dst, mask, _w = batches()[0]
    mask = mask.copy()
    mask[:, 5:] = 0.0
    enc = jax.jit(lambda s: get_encode_fn(DIMS)(params, s, pth, dst,
                                                jnp.asarray(mask)))
    code1, attn, _ = enc(src)
    src2 = src.copy()
    src2[:, 5:] = (src2[:, 5:] + 7) % DIMS.token_vocab_size
    code2, *_ = enc(jnp.asarray(src2))
    np.testing.assert_allclose(np.asarray(code1), np.asarray(code2),
                               atol=1e-6)
    assert np.all(np.isfinite(np.asarray(code1)))
    assert np.all(np.asarray(attn)[:, 5:] < 1e-6)


def test_order_of_contexts_matters():
    _dims, params, _ = program_weights()
    _labels, src, pth, dst, _mask, _w = batches()[0]
    ones = jnp.ones(src.shape, jnp.float32)
    enc = jax.jit(lambda s, p, d: get_encode_fn(DIMS)(params, s, p, d, ones))
    code1, *_ = enc(src, pth, dst)
    code2, *_ = enc(src[:, ::-1], pth[:, ::-1], dst[:, ::-1])
    assert float(jnp.max(jnp.abs(code1 - code2))) > 1e-3


def test_the_period_is_three_linear_layers_to_one_full():
    eight = Qwen3NextDims.from_config(dict(BLOCK, num_hidden_layers=8))
    assert eight.layer_types == ("linear_attention",) * 3 + (
        "full_attention",) + ("linear_attention",) * 3 + ("full_attention",)
    _dims, params, _ = program_weights()
    kinds = ["in_qkvz" in layer for layer in params["qwen"]["layers"]]
    assert kinds == [True, True, True, False]
    assert all("shared_w1" in layer and "router" in layer
               for layer in params["qwen"]["layers"])


# ---- the attention layer's own --------------------------------------------

def test_partial_rotary_turns_the_first_quarter_of_a_head_and_no_more():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 10, 16))
    turned = seq_block.rotary(x, 1e7, 4)
    np.testing.assert_array_equal(np.asarray(turned[..., 4:]),
                                  np.asarray(x[..., 4:]))
    # slot 0 is never turned; later slots' first quarter is
    np.testing.assert_allclose(np.asarray(turned[..., 0, :]),
                               np.asarray(x[..., 0, :]), atol=1e-7)
    assert not np.allclose(np.asarray(turned[..., 1:, :4]),
                           np.asarray(x[..., 1:, :4]), atol=1e-3)
    # the part that turns is the whole-head rotation of those 4 alone
    np.testing.assert_array_equal(
        np.asarray(turned[..., :4]),
        np.asarray(seq_block.rotary(x[..., :4], 1e7)))
    # rotations keep a head's length
    np.testing.assert_allclose(
        np.asarray(jnp.linalg.norm(turned, axis=-1)),
        np.asarray(jnp.linalg.norm(x, axis=-1)), rtol=1e-5)
    assert QWEN.rotary_dim == 4
    # and the whole head when no part is named (lfm2_moe's)
    np.testing.assert_array_equal(np.asarray(seq_block.rotary(x, 1e7, 16)),
                                  np.asarray(seq_block.rotary(x, 1e7)))


def _attention_layer(seed=0, H=32, n=4, n_kv=2, hd=8):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    w = lambda key, shape: 0.3 * jax.random.normal(key, shape)  # noqa: E731
    layer = {"q": w(k[0], (H, 2 * n * hd)), "k": w(k[1], (H, n_kv * hd)),
             "v": w(k[2], (H, n_kv * hd)), "o": w(k[3], (n * hd, H)),
             "q_norm": jnp.zeros((hd,)), "k_norm": jnp.zeros((hd,))}
    h = jax.random.normal(k[4], (2, 9, H))
    kw = dict(heads=n, kv_heads=n_kv, head_dim=hd, theta=1e7, turned=2,
              norm=lambda t, s: t * (1.0 + s))
    return layer, h, kw


def test_the_output_gate_multiplies_each_heads_output_by_its_sigmoid():
    layer, h, kw = _attention_layer()
    mask = jnp.ones((2, 9))
    gated = seq_block.attention(h, mask, layer, gated=True, **kw)
    # the same layer without its gate columns
    q4 = layer["q"].reshape(32, 4, 16)
    plain = dict(layer, q=q4[:, :, :8].reshape(32, 32))
    ungated = seq_block.attention(h, mask, plain, gated=False, **kw)
    assert not np.allclose(np.asarray(gated), np.asarray(ungated),
                           atol=1e-3)
    # gate columns of zero: sigmoid(0) = 1/2 of the ungated layer
    half = dict(layer, q=q4.at[:, :, 8:].set(0.0).reshape(32, 64))
    np.testing.assert_allclose(
        np.asarray(seq_block.attention(h, mask, half, gated=True, **kw)),
        0.5 * np.asarray(ungated), atol=1e-5)
    # a head's gate reaches that head alone: zeroing head 3's gate
    # columns moves only what head 3 adds through o
    one = dict(layer, q=q4.at[:, 3, 8:].set(0.0).reshape(32, 64))
    moved = seq_block.attention(h, mask, one, gated=True, **kw) - gated
    only3 = dict(one, o=layer["o"].at[:24].set(0.0))
    base3 = dict(layer, o=layer["o"].at[:24].set(0.0))
    np.testing.assert_allclose(
        np.asarray(moved),
        np.asarray(seq_block.attention(h, mask, only3, gated=True, **kw)
                   - seq_block.attention(h, mask, base3, gated=True, **kw)),
        atol=1e-5)


def test_attention_is_causal_and_grouped():
    layer, h, kw = _attention_layer()
    mask = jnp.ones((2, 9))
    out = seq_block.attention(h, mask, layer, gated=True, **kw)
    moved = seq_block.attention(h.at[:, 6].add(1.0), mask, layer,
                                gated=True, **kw)
    np.testing.assert_allclose(np.asarray(out[:, :6]),
                               np.asarray(moved[:, :6]), atol=1e-6)
    assert not np.allclose(np.asarray(out[:, 6:]), np.asarray(moved[:, 6:]),
                           atol=1e-3)
    # kv head 1 serves query heads 2 and 3 alone
    other = dict(layer, v=layer["v"].at[:, 8:].multiply(-1.0))
    moved = seq_block.attention(h, mask, other, gated=True, **kw) - out
    last = dict(layer, o=layer["o"].at[:16].set(0.0))
    last_other = dict(other, o=layer["o"].at[:16].set(0.0))
    np.testing.assert_allclose(
        np.asarray(moved),
        np.asarray(seq_block.attention(h, mask, last_other, gated=True, **kw)
                   - seq_block.attention(h, mask, last, gated=True, **kw)),
        atol=1e-5)


# ---- the feed-forward ------------------------------------------------------

def _layer_case(E=16, H=32, F=24, n_tokens=96, seed=2):
    k = jax.random.split(jax.random.PRNGKey(seed), 10)
    return dict(
        h=jax.random.normal(k[0], (n_tokens, H)),
        valid=jnp.arange(n_tokens) % 7 != 3,
        router=0.4 * jax.random.normal(k[1], (H, E)),
        w1=0.2 * jax.random.normal(k[3], (E, H, F)),
        w3=0.2 * jax.random.normal(k[4], (E, H, F)),
        w2=0.2 * jax.random.normal(k[5], (E, F, H)),
        shared_gate=0.3 * jax.random.normal(k[6], (H,)),
        shared_w1=0.2 * jax.random.normal(k[7], (H, F)),
        shared_w3=0.2 * jax.random.normal(k[8], (H, F)),
        shared_w2=0.2 * jax.random.normal(k[9], (F, H)))


def _share(case, first, held, per_token=3):
    chosen, p = moe.route(case["h"], case["router"], None, per_token,
                          score="softmax")
    sl = slice(first, first + held)
    return moe.held_experts_ffn(case["h"], case["valid"], chosen, p,
                                case["w1"][sl], case["w3"][sl],
                                case["w2"][sl], first, case["router"].shape[1])


def _shared(case):
    return qwen._shared_expert(case["h"], case)


def test_the_shares_add_up():
    """Experts 0-3, 4-7, ... as four chips would hold them: the routed
    parts of all the shares, with the shared expert counted once, equal
    the uncut 16-expert layer of the reference, and their rows every
    choice of every valid token."""
    case = _layer_case()
    with jax.default_matmul_precision("highest"):
        whole = ref_mod.expert_layer(
            case["h"], case["valid"], case["router"], case["w1"],
            case["w3"], case["w2"], first=0, per_token=3) \
            + ref_mod.shared_expert(
                case["h"], case["shared_gate"], case["shared_w1"],
                case["shared_w3"], case["shared_w2"])
        parts = [_share(case, first, 4) for first in range(0, 16, 4)]
        total = sum(out for out, _rows in parts) + _shared(case)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=2e-5)
    rows = sum(int(r.sum()) for _out, r in parts)
    assert rows == 3 * int(case["valid"].sum())
    # one share against the reference given the same share
    with jax.default_matmul_precision("highest"):
        want = ref_mod.expert_layer(
            case["h"], case["valid"], case["router"], case["w1"][8:12],
            case["w3"][8:12], case["w2"][8:12], first=8, per_token=3)
    np.testing.assert_allclose(np.asarray(parts[2][0]), np.asarray(want),
                               atol=2e-5)
    # every share's shared expert counted: three too many
    assert not np.allclose(
        np.asarray(total + 3 * _shared(case)), np.asarray(whole), atol=1e-3)


def test_the_shared_experts_gate_is_a_sigmoid_a_token():
    case = _layer_case()
    out = _shared(case)
    plain = seq_block.swiglu(case["h"], case["shared_w1"], case["shared_w3"],
                             case["shared_w2"])
    gate = jax.nn.sigmoid(case["h"] @ case["shared_gate"])
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(gate[:, None] * plain), atol=1e-6)
    shut = _shared(dict(case, shared_gate=jnp.zeros(32)))
    np.testing.assert_allclose(np.asarray(shut), 0.5 * np.asarray(plain),
                               atol=1e-6)
    # every token passes through it, masked or not
    assert np.all(np.abs(np.asarray(out)).sum(axis=1) > 0)


def test_softmax_router_renormalises_its_top_k():
    case = _layer_case()
    chosen, p = moe.route(case["h"], case["router"], None, 3,
                          score="softmax")
    assert chosen.shape == p.shape == (96, 3) and chosen.dtype == jnp.int32
    assert all(len(set(row)) == 3 for row in np.asarray(chosen).tolist())
    np.testing.assert_allclose(np.asarray(p.sum(-1)), 1.0, atol=1e-6)
    full = jax.nn.softmax(case["h"] @ case["router"], axis=-1)
    top = np.sort(np.asarray(full), axis=-1)[:, -3:][:, ::-1]
    np.testing.assert_allclose(
        np.asarray(p), top / top.sum(-1, keepdims=True), rtol=1e-5)
    # the sigmoid router is the other one, and a bias is its alone
    chosen_s, p_s = moe.route(case["h"], case["router"], jnp.zeros(16), 3)
    np.testing.assert_array_equal(np.sort(np.asarray(chosen_s), -1),
                                  np.sort(np.asarray(chosen), -1))
    assert not np.allclose(np.asarray(p_s), np.asarray(p), atol=1e-3)
    with pytest.raises(AssertionError):
        moe.route(case["h"], case["router"], jnp.zeros(16), 3,
                  score="softmax")


def test_no_row_is_dropped_when_all_tokens_choose_one_held_expert():
    """The logits of held experts 4, 5 and 6 tower for every token:
    expert 5's group is every token, and the 288 live pairs are more
    than the bound's 256, so the overflow body runs."""
    case = _layer_case()
    case["valid"] = jnp.ones(96, bool)
    case["h"] = jnp.concatenate([case["h"][:, :-1],
                                 jnp.ones((96, 1))], axis=1)
    case["router"] = case["router"].at[:, 4:7].set(0.0).at[-1, 4:7].set(
        jnp.array([28.0, 30.0, 29.0]))
    with jax.default_matmul_precision("highest"):
        out, rows = _share(case, 4, 4)
        want = ref_mod.expert_layer(
            case["h"], case["valid"], case["router"], case["w1"][4:8],
            case["w3"][4:8], case["w2"][4:8], first=4, per_token=3)
    assert rows.tolist() == [96, 96, 96, 0]     # every token, none dropped
    assert not bool(moe.fits(rows, moe.row_bound(288, 4, 16)))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def test_row_bound_is_an_eighth_of_the_cells_pairs():
    """R is a sixteenth of the cell's pairs (32 of 512 experts held), up
    to whole tiles of 256 rows; the name is from when it was twice
    that."""
    # the cell: 128 methods x 200 slots, 10 choices, 32 of 512 held
    assert moe.row_bound(25600 * 10, 32, 512) == 16128
    assert moe.row_bound(288, 4, 16) == 256


def test_masked_tokens_are_routed_nowhere():
    case = _layer_case()
    out, rows = _share(case, 0, 16)
    assert int(rows.sum()) == 3 * int(case["valid"].sum())
    np.testing.assert_array_equal(np.asarray(out)[~np.asarray(case["valid"])],
                                  0.0)


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
    bs = batches(steps=5, seed=11)
    for i, b in enumerate(bs):
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
    # the scans' record, against a numpy count of the live chunks: 12
    # slots are one chunk a method, live where the method has a context
    scans = [r["attrs"] for r in memory_tracer().records("gdn/scan")[-5:]]
    for attrs, b in zip(scans, bs):
        live = int((b[4].sum(axis=1) > 0).sum())
        assert (attrs["chunk"], attrs["chunks"], attrs["live_chunks"]) == \
            (12, 3 * 8, 3 * live)
    assert [a["seq"] for a in scans] == list(range(5))


def test_encoder_counts_routes_and_scans_layer_by_layer():
    """aux, a layer: the held experts' rows, the valid tokens, the row
    bound (8 x 12 x 3 = 288 pairs, 256 held at 4 of 16) and whether the
    layer ran at it, then the scan's chunk, chunks and live chunks:
    zeros on the attention layer."""
    _dims, params, _ = program_weights()
    _labels, src, pth, dst, mask, _w = batches()[0]
    mask = mask.copy()
    mask[2] = 0.0                               # a method with no context
    encode = jax.jit(lambda p: get_encode_fn(DIMS)(
        p, src, pth, dst, jnp.asarray(mask)))
    _code, _attn, aux = encode(params)
    aux = np.asarray(aux)
    assert aux.shape == (4, 4 + 3 + 3)
    assert aux[:, 4].tolist() == [int(mask.sum())] * 4
    assert aux[:, 5:7].tolist() == [[256, 1]] * 4
    assert aux[:3, 7:].tolist() == [[12, 8, 7]] * 3
    assert aux[3, 7:].tolist() == [0, 0, 0]


def test_under_a_mesh_every_device_routes_and_scans_its_own_rows():
    from code2vec_tpu.parallel.mesh import make_mesh

    _dims, params, _ = program_weights()
    _labels, src, pth, dst, mask, _w = batches(n=32)[0]
    mesh = make_mesh(0, 1, devices=jax.devices()[:4])

    def run(mesh):
        def loss(p):
            code, _attn, aux = get_encode_fn(DIMS)(
                p, src, pth, dst, jnp.asarray(mask), mesh=mesh)
            return jnp.sum(code ** 2), (code, aux)
        return jax.jit(jax.grad(loss, has_aux=True))

    grads, (code, aux) = run(mesh)(params)
    want_grads, (want_code, want_aux) = run(None)(params)
    aux, want_aux = np.asarray(aux), np.asarray(want_aux)
    # rows, valid tokens and the scans' counts are the whole batch's;
    # the bound and the decisions are sums over the four devices (8
    # methods x 12 slots x 3 choices = 288 pairs and a bound of 256 each)
    np.testing.assert_array_equal(aux[:, :5], want_aux[:, :5])
    np.testing.assert_array_equal(aux[:, 7:], want_aux[:, 7:])
    assert aux[:, 5:7].tolist() == [[4 * 256, 4]] * 4
    np.testing.assert_allclose(np.asarray(code), np.asarray(want_code),
                               rtol=1e-4, atol=5e-5)
    got, want = flat(grads["qwen"]), flat(want_grads["qwen"])
    for name in ("layers/0/in_qkvz", "layers/1/A_log", "layers/2/w2",
                 "layers/3/q", "layers/1/router", "layers/0/shared_w1"):
        # four partial sums added in another order
        gap = float(jnp.linalg.norm(got[name] - want[name])
                    / jnp.linalg.norm(want[name]))
        assert gap < 1e-3, (name, gap)


# ---- the scan under the training staircase (ISSUE 33) --------------------

SLOTS = 160                 # chunks of 64, 64 and 32 slots a bag
# 16 rows a device; the chunks start inside the first, second and third
# rectangle and run over 16, 10 and 6 rows: 32 of 48 method-chunks
STAIRS = ((0, 16), (48, 10), (96, 6), (144, 3))
BOUND = (16, 10, 6)
FITS = [160, 150, 145, 140, 120, 100, 96, 80, 70, 50, 48, 30, 20, 10, 5, 1]
ONE_TOO_LONG = FITS[:10] + [49] + FITS[11:]


def ordered_batch(lengths, devices, seed=5):
    """A training batch of bags of `lengths` for every device, ordered
    as the training reader orders it (`length_order`, a block a
    device)."""
    from code2vec_tpu.data.staircase import length_order
    lengths = np.repeat(np.asarray(lengths), devices)
    lengths = lengths[length_order(lengths, devices)]
    r = np.random.default_rng(seed)
    n = len(lengths)
    mask = np.arange(SLOTS)[None, :] < lengths[:, None]

    def ids(v):
        return (r.integers(2, v + 2, (n, SLOTS)) * mask).astype(np.int32)

    return (r.integers(2, SIZES["targets"] + 2, n).astype(np.int32),
            ids(SIZES["tokens"]), ids(SIZES["paths"]), ids(SIZES["tokens"]),
            mask.astype(np.float32), np.ones(n, np.float32))


@pytest.mark.parametrize("devices", [1, 2])
def test_the_staircase_step_scans_under_its_bound_and_is_the_full_step(
        devices):
    """A batch that fits runs the scan over the bound's rows, chunk by
    chunk and device by device, and is the full step's loss and
    gradients (one SGD step) on the same batch; a batch that does not
    fit runs the full step; the record says which ran."""
    import optax

    from code2vec_tpu.data import staircase as st
    from code2vec_tpu.obs import memory_tracer
    from code2vec_tpu.ops import delta_rule
    from code2vec_tpu.parallel.mesh import make_mesh
    from code2vec_tpu.training.steps import TrainBatch, make_train_step

    assert BOUND == tuple(st.rows_kept(STAIRS, n * delta_rule.CHUNK)
                          for n in range(delta_rule.chunks_of(SLOTS)))
    dims = dataclasses.replace(DIMS, max_contexts=SLOTS)
    mesh = None if devices == 1 else make_mesh(
        devices, 1, devices=jax.devices()[:devices])
    opt = optax.sgd(0.1)
    kw = dict(use_sampled_softmax=True, num_sampled=16, mesh=mesh)
    both = make_train_step(dims, opt, staircase=STAIRS, **kw)
    full = make_train_step(dims, opt, **kw)
    key = jax.random.PRNGKey(4)

    def run(step, batch):
        """(loss, the parameters after the step, the scan's record)"""
        _dims, params, _ = program_weights()
        new, _state, loss = step(params, opt.init(params), batch, key)
        step.route_recorder.flush()
        return (float(loss), flat(new),
                memory_tracer().records("gdn/scan")[-1]["attrs"])

    rows = 16 * devices
    for lengths, fits in ((FITS, True), (ONE_TOO_LONG, False)):
        batch = ordered_batch(lengths, devices)
        assert st.fits(STAIRS, batch[1:4], devices) == fits
        loss, after, scan = run(both, TrainBatch(batch, fits, 0))
        want_loss, want_after, want_scan = run(full, batch)
        live = 3 * int(np.ceil(batch[4].sum(axis=1) / 64).sum())
        assert (want_scan["chunks"], want_scan["live_chunks"]) == \
            (rows * 3 * 3, live)
        assert scan["chunk"] == want_scan["chunk"] == 64
        assert scan["live_chunks"] == live
        assert scan["chunks"] == (devices * sum(BOUND) * 3 if fits
                                  else rows * 3 * 3)
        assert loss == pytest.approx(want_loss, rel=1e-5)
        _dims, start, _ = program_weights()
        start = flat(start)
        moved = {k: np.asarray(want_after[k]) - np.asarray(start[k])
                 for k in start}
        norms = {k: float(np.linalg.norm(v)) for k, v in moved.items()}
        median = float(np.median(list(norms.values())))
        assert norms["qwen/layers/0/A_log"] > 0
        for k, change in moved.items():
            got = np.asarray(after[k]) - np.asarray(start[k])
            assert float(np.linalg.norm(got - change)) <= \
                2e-4 * max(norms[k], median), k


# ---- the gated attention's core under the staircase (ISSUE 35) -----------

@pytest.mark.parametrize("block_slots", [1, 8])
@pytest.mark.parametrize("case", list(STAIR_CASES))
def test_gated_attention_over_a_staircase_is_the_whole_core(
        case, block_slots, monkeypatch):
    """Grouped heads with the output gate and a quarter of each head
    turned: each rectangle a query block, and neighbours joined to
    blocks of 8 slots."""
    from code2vec_tpu.data import staircase
    from tests.test_lfm2_moe import attention_case

    monkeypatch.setattr(staircase, "_BLOCK_SLOTS", block_slots)
    layer, h, kw = attention_case(gated=True, turned=2)
    assert_staircase_mixer_is_the_whole(
        lambda h, mask, layer, stairs: seq_block.attention(
            h, mask, layer,
            blocks=seq_block.core_blocks(stairs, None, h.shape[1]), **kw),
        h, layer, STAIR_CASES[case])


def test_with_no_staircase_gated_attention_lowers_to_the_old_program():
    from tests.test_lfm2_moe import (attention_before_the_blocks,
                                     attention_case)

    layer, h, kw = attention_case(gated=True, turned=2)
    mask = jnp.asarray(staircase_mask(STAIR_CASES["uneven"]))
    assert lowered_texts(
        lambda h, layer: seq_block.attention(h, mask, layer, **kw),
        h, layer) == lowered_texts(
        lambda h, layer: attention_before_the_blocks(h, mask, layer, **kw),
        h, layer)


def test_the_encoder_hands_its_staircase_to_the_scan_and_the_attention(
        monkeypatch):
    """With a staircase the fourth layer's scores are its query
    blocks' (a [rows, 2, 2, slots, keys] product a block, each
    rectangle one), beside the bounded scans of the first three."""
    from code2vec_tpu.data import staircase

    monkeypatch.setattr(staircase, "_BLOCK_SLOTS", 1)
    dims = dataclasses.replace(DIMS, max_contexts=SLOTS)
    _dims, params, _ = program_weights()
    batch = ordered_batch(FITS, 1)

    def text(stairs):
        return jax.jit(lambda p: get_encode_fn(dims)(
            p, *(jnp.asarray(a) for a in batch[1:5]),
            staircase=stairs)[0]).lower(params).as_text()

    whole = f"tensor<16x2x2x{SLOTS}x{SLOTS}xf32>"
    assert whole in text(None) and whole not in text(STAIRS)
    for rows, queries, keys in ((16, 48, 48), (10, 48, 96), (6, 48, 144),
                                (3, 16, 160)):
        assert f"tensor<{rows}x2x2x{queries}x{keys}xf32>" in text(STAIRS)


# ---- configuration -------------------------------------------------------

def _block_config_file(tmp_path):
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
    (["--no_block_config"], "--block_config")])
def test_verify_refuses(tmp_path, flags, message):
    from code2vec_tpu.config import Config

    argv = ["--data", str(tmp_path / "d"), "--encoder", "qwen3_next",
            "--backend", "cpu"]
    if flags == ["--no_block_config"]:
        flags = []
    else:
        argv += ["--block_config", _block_config_file(tmp_path)]
    with pytest.raises(ValueError, match=message):
        Config.load_from_args(argv + flags)


@pytest.mark.parametrize("encoder", ["qwen3_next", "lfm2_moe"])
def test_both_spellings_of_the_option_give_one_config(tmp_path, encoder):
    from code2vec_tpu.config import Config

    if encoder == "lfm2_moe":
        from tests.test_lfm2_moe import BLOCK as block
    else:
        block = BLOCK
    path = tmp_path / "block.json"
    path.write_text(json.dumps(block))
    argv = ["--data", str(tmp_path / "d"), "--encoder", encoder,
            "--backend", "cpu"]
    a = Config.load_from_args(argv + ["--block_config", str(path)])
    b = Config.load_from_args(argv + ["--lfm_config", str(path)])
    assert a.BLOCK_CONFIG == b.BLOCK_CONFIG == str(path)
    assert dataclasses.asdict(a) == dataclasses.asdict(b) \
        if dataclasses.is_dataclass(a) else vars(a) == vars(b)
    assert not hasattr(a, "LFM_CONFIG")


def test_block_sizes_come_from_the_config_json():
    assert QWEN.routed == 16 and QWEN.rotary_dim == 4
    assert (QWEN.key_dim, QWEN.value_dim) == (16, 32)
    # every width comes from the file; only the share has a default:
    # all the router's experts, held from the first
    whole = Qwen3NextDims.from_config(
        {k: v for k, v in BLOCK.items()
         if k not in ("num_routed_experts", "first_expert")})
    assert (whole.routed, whole.first_expert) == (4, 0)
    # keys the block does not read are passed over, stated ones checked
    assert Qwen3NextDims.from_config(dict(
        BLOCK, vocab_size=151936, mlp_only_layers=[], rope_scaling=None,
        layer_types=list(QWEN.layer_types))) == QWEN
    with pytest.raises(ValueError, match="hidden_size"):
        Qwen3NextDims.from_config({k: v for k, v in BLOCK.items()
                                   if k != "hidden_size"})
    with pytest.raises(ValueError, match="held"):
        Qwen3NextDims.from_config(dict(BLOCK, first_expert=14))
    with pytest.raises(ValueError, match="layer_types"):
        Qwen3NextDims.from_config(dict(
            BLOCK, layer_types=["full_attention"] * 4))


@pytest.mark.parametrize("key,value", [
    ("mlp_only_layers", [0]), ("decoder_sparse_step", 2),
    ("norm_topk_prob", False),
    ("rope_scaling", {"type": "yarn", "factor": 4.0}),
    ("hidden_act", "gelu"), ("attention_bias", True)])
def test_from_config_refuses_a_switch_it_does_not_implement(key, value):
    with pytest.raises(ValueError, match=key):
        Qwen3NextDims.from_config(dict(BLOCK, **{key: value}))


# ---- tracing -------------------------------------------------------------

class _FakeCounts:
    """What `RouteRecorder` uses of a device array."""

    def __init__(self, table):
        self.table = table

    def copy_to_host_async(self):
        pass

    def is_ready(self):
        return True

    def tolist(self):
        return self.table


def test_scan_record_rides_on_the_routes_fetch(monkeypatch):
    from code2vec_tpu.obs import route, trace

    monkeypatch.setattr(trace, "_MEMORY_TRACER", trace.MemoryTracer())
    rec = route.RouteRecorder(scan=True)
    rec.push(_FakeCounts([[3, 1, 9, 128, 1, 64, 8, 5],
                          [2, 2, 9, 128, 0, 64, 8, 5],
                          [0, 4, 9, 128, 1, 0, 0, 0]]))
    rec.flush()
    (moe_record,) = trace.memory_tracer().records("moe/route")
    (scan_record,) = trace.memory_tracer().records("gdn/scan")
    assert moe_record["attrs"] == {
        "seq": 0, "layers": [[3, 1], [2, 2], [0, 4]], "rows_here": 12,
        "valid_tokens": 9, "row_bound": 128, "compact_layers": 2}
    assert scan_record["attrs"] == {"seq": 0, "chunk": 64, "chunks": 16,
                                    "live_chunks": 10}
    assert (scan_record["t0"], scan_record["t1"]) == \
        (moe_record["t0"], moe_record["t1"])
    # a recorder that was not asked for scans writes none
    plain = route.RouteRecorder()
    plain.push(_FakeCounts([[3, 1, 9, 128, 1]]))
    plain.flush()
    assert len(trace.memory_tracer().records("gdn/scan")) == 1


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
        for scope in ("c2v/encode", "c2v/blk_0/gdn", "c2v/blk_0/gdn/conv",
                      "c2v/blk_0/gdn/scan", "c2v/blk_2/gdn/scan",
                      "c2v/blk_3/attn", "c2v/blk_0/router",
                      "c2v/blk_0/experts", "c2v/blk_0/shared",
                      "c2v/blk_3/router", "c2v/blk_3/experts",
                      "c2v/blk_3/shared", "c2v/pool"):
            assert scope in text, scope
        assert "c2v/blk_3/gdn" not in text and "c2v/blk_0/attn" not in text


def test_the_steps_that_take_no_gradient_add_no_rows_by_index():
    """The eval and predict steps (4 of 16 experts held: the bounded
    layer and its conditional) hold no scatter of floats, the train step
    does: `ops/moe._bounded`. On the v5e the predict step of 8 methods
    did not return while its rows went back by a scatter-add (PERF.md
    section 6, PR 32)."""
    from code2vec_tpu.training.steps import (make_eval_step,
                                             make_predict_step,
                                             make_train_step)
    import optax

    _dims, params, rng = program_weights()
    batch = tuple(jnp.asarray(a) for a in batches()[0])
    opt = optax.adam(1e-3)
    train = make_train_step(DIMS, opt, use_sampled_softmax=True,
                            num_sampled=16).lower(
        params, opt.init(params), batch, rng).as_text()
    assert "stablehlo.case" in train and float_scatters(train)
    for step in (make_eval_step(DIMS, top_k=3),
                 make_predict_step(DIMS, top_k=3)):
        text = step.lower(params, batch).as_text()
        assert "stablehlo.case" in text
        assert not float_scatters(text)


# ---- the model class -----------------------------------------------------

def test_model_trains_evaluates_predicts_saves_and_resumes(tmp_path):
    """Through `Code2VecModel` on the tests' 8-device mesh (every device
    routes and scans its own rows), with the encoder's sizes kept by the
    checkpoint; a second run resumes from it and trains on."""
    from code2vec_tpu.models.jax_model import Code2VecModel
    from tests.test_model import tiny_config

    prefix = build_tiny_dataset(str(tmp_path), n_train=256, n_val=32,
                                n_test=64, max_contexts=16)
    cfg = tiny_config(prefix, ENCODER_TYPE="qwen3_next",
                      BLOCK_CONFIG=_block_config_file(tmp_path),
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
    # the --trace log holds both records a step, and the report prints them
    from tests.test_trace import _spans
    from tools.trace_report import render, route_summary, scan_summary
    spans = _spans(model.telemetry.run_dir)
    route = route_summary(spans)
    assert route["steps"] == model.step_num
    assert (route["expert_layers"], route["held_experts"]) == (4, 4)
    scan = scan_summary(spans)
    # 32 methods x 1 chunk of 16 slots x 3 linear layers a step
    assert scan["steps"] == model.step_num and scan["chunk"] == 16
    assert scan["chunks"] == 32 * 3 * model.step_num
    assert 0 < scan["live_chunks"] <= scan["chunks"]
    report = render([({}, spans)])
    assert f"Routed experts: {route['rows_here']:,} rows" in report
    assert f"Scanned state: {scan['chunks']:,} chunks of 16 slots" in report
    assert scan_summary([s for s in spans if s["name"] != "gdn/scan"]) \
        is None

    cfg2 = tiny_config(prefix)
    cfg2.load_path = ckpt_dir
    model2 = Code2VecModel(cfg2)
    assert model2.dims.qwen == QWEN and model2.dims.lfm is None
    loaded = model2.evaluate()
    assert loaded.topk_acc == pytest.approx(result.topk_acc)
    with open(os.path.join(ckpt_dir, "manifest.json")) as f:
        manifest = json.load(f)
    assert manifest["encoder_type"] == "qwen3_next"
    assert manifest["qwen"]["linear_num_value_heads"] == 4
    assert manifest["lfm"] is None

    # resumed: two more epochs from the saved step
    cfg3 = tiny_config(prefix, NUM_TRAIN_EPOCHS=8, LEARNING_RATE=0.003)
    cfg3.load_path = ckpt_dir
    cfg3.save_path = str(tmp_path / "ckpt2")
    model3 = Code2VecModel(cfg3)
    assert model3.step_num == model.step_num
    model3.train()
    assert model3.step_num > model.step_num
    assert model3.evaluate().subtoken_f1 > 0.3
