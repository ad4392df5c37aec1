"""`--encoder joyai_flash` (models/joyai_flash_encoder.py,
models/seq_block.py, ops/moe.py) at tiny sizes on the CPU, against the
configuration's plain reference (benchmark/reference_joyai.py): code
vector, loss, every leaf's gradient and three optimizer steps in float32
and bfloat16; latent attention's own (the interleaved rotary against
de-interleave + rotate-half, the one shared rotary key, the norms on the
latents, causality); the router's scale and epsilon, and `route` with its
defaults bit for bit what it was; the sixteen expert-parallel shares and
the shared expert adding up to the uncut layer; what `Config.verify()` and
`JoyaiDims.from_config` refuse; the step's named scopes; a mesh of two
devices; the model class end to end, saved and reloaded."""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from code2vec_tpu.models import seq_block
from code2vec_tpu.models.encoder import (ModelDims, get_encode_fn,
                                         init_params)
from code2vec_tpu.models.joyai_flash_encoder import JoyaiDims
from code2vec_tpu.ops import moe
from tests.helpers import (STAIR_CASES, assert_staircase_mixer_is_the_whole,
                           build_tiny_dataset, lowered_texts,
                           staircase_mask)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

import reference_joyai as ref_mod  # noqa: E402

# a leading dense layer and two expert layers; 4 of 16 experts held from
# the fourth; heads of 8 + 4 for the scores and 6 for the values
BLOCK = dict(num_hidden_layers=3, hidden_size=64, num_attention_heads=4,
             q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8,
             qk_rope_head_dim=4, v_head_dim=6, intermediate_size=96,
             moe_intermediate_size=24, n_routed_experts=4,
             num_routed_experts=16, first_expert=4, n_shared_experts=1,
             num_experts_per_tok=3, first_k_dense_replace=1,
             routed_scaling_factor=2.5, rope_theta=32e6, rms_norm_eps=1e-6)
JOYAI = JoyaiDims.from_config(BLOCK)
# the tables keep the product's width and at least 128 rows: optax
# factors Adafactor's second moment only from 128 up, as the reference
# always does
SIZES = dict(tokens=200, paths=150, targets=130, embedding=128,
             max_contexts=12, num_sampled=16, dropout_keep=0.75)
DIMS = ModelDims(token_vocab_size=202, path_vocab_size=152,
                 target_vocab_size=132, embeddings_size=128, max_contexts=12,
                 dropout_keep_rate=0.75, encoder_type="joyai_flash",
                 joyai=JOYAI)
SEED = 7


def spec(dtype="float32"):
    return dict(SIZES, encoder="joyai_flash", tables_dtype=dtype, lr=1e-3,
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
    # layer 0 is dense, the rest route; every layer mixes by MLA
    assert "router" not in params["joyai"]["layers"][0]
    assert got["joyai/layers/0/w1"].shape == (64, 96)
    assert got["joyai/layers/1/w1"].shape == (4, 64, 24)
    assert got["joyai/layers/2/kv_a"].shape == (64, 16 + 4)
    assert got["joyai/layers/2/kv_b"].shape == (16, 4 * (8 + 6))
    bias = np.asarray(got["joyai/layers/1/expert_bias"])
    assert 0 < np.abs(bias).max() < 0.05


# float32 leaves room for summation order alone; bfloat16 for its
# rounding and for the tokens whose near-tied experts it swaps, two
# routers deep
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
# say that the bfloat16 path runs at the right scale (tiny widths under
# norms that divide by a small rms: tests/test_qwen3_next.py has the
# reasons); what bfloat16 costs at the published widths is the cell's
# `dense_grad_diff` (PERF.md section 2)
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
    # every leaf of the block learns, the latents' norms and the shared
    # expert among them; the selection bias does not (it selects only)
    for k in ("joyai/layers/0/q_a_norm", "joyai/layers/1/kv_a_norm",
              "joyai/layers/2/kv_a", "joyai/layers/1/q_b",
              "joyai/layers/1/shared_w2", "joyai/layers/2/router",
              "joyai/layers/0/w3"):
        assert norms[k] > 0, k
    assert norms["joyai/layers/1/expert_bias"] == 0.0
    assert float(jnp.max(jnp.abs(got["joyai/layers/1/expert_bias"]))) == 0.0

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


# ---- latent attention's own -----------------------------------------------

def test_interleaved_rotary_is_deinterleave_then_rotate_half():
    """The published pairing turns (2i, 2i + 1). The program lays the
    pairs out as (i, i + n/2) and calls the blocks' rotate-half `rotary`;
    turning the pairs directly (the reference's `rotary_pairs`) gives the
    same heads up to that layout, so the same scores."""
    k = jax.random.split(jax.random.PRNGKey(0), 2)
    q = jax.random.normal(k[0], (2, 3, 10, 8))          # [B, n, C, rope]
    key = jax.random.normal(k[1], (2, 1, 10, 8))        # the one shared head
    theta = 32e6

    def ours(t):
        return seq_block.rotary(seq_block.deinterleaved(t), theta)

    direct_q, direct_k = (ref_mod.rotary_pairs(t, theta) for t in (q, key))
    np.testing.assert_allclose(np.asarray(ours(q)),
                               np.asarray(seq_block.deinterleaved(direct_q)),
                               atol=1e-6)
    scores = jnp.einsum("bnqr,bncr->bnqc", ours(q),
                        jnp.broadcast_to(ours(key), q.shape))
    want = jnp.einsum("bnqr,bncr->bnqc", direct_q,
                      jnp.broadcast_to(direct_k, q.shape))
    np.testing.assert_allclose(np.asarray(scores), np.asarray(want),
                               atol=1e-5)
    # slot 0 is never turned, a pair keeps its length, and the score of
    # two slots hangs on their distance alone
    np.testing.assert_allclose(np.asarray(direct_q[..., 0, :]),
                               np.asarray(q[..., 0, :]), atol=1e-7)
    pairs = lambda t: np.asarray(t).reshape(2, 3, 10, 4, 2)  # noqa: E731
    np.testing.assert_allclose(np.linalg.norm(pairs(direct_q), axis=-1),
                               np.linalg.norm(pairs(q), axis=-1), rtol=1e-5)
    same = jnp.broadcast_to(q[:, :, :1], q.shape)       # one vector, every slot
    turned = ours(same)
    gram = np.asarray(jnp.einsum("bnqr,bncr->bnqc", turned, turned))
    np.testing.assert_allclose(gram[..., 3, 1], gram[..., 7, 5], rtol=1e-4)
    # and not the rotate-half of the published layout
    assert not np.allclose(np.asarray(seq_block.rotary(q, theta)),
                           np.asarray(direct_q), atol=1e-3)


def _mla_layer(seed=0, H=32, n=4, r_q=12, r_kv=10, nope=8, rope=4, v_dim=6):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    w = lambda key, shape: 0.3 * jax.random.normal(key, shape)  # noqa: E731
    layer = {"q_a": w(k[0], (H, r_q)), "q_a_norm": jnp.ones((r_q,)),
             "q_b": w(k[1], (r_q, n * (nope + rope))),
             "kv_a": w(k[2], (H, r_kv + rope)),
             "kv_a_norm": jnp.ones((r_kv,)),
             "kv_b": w(k[3], (r_kv, n * (nope + v_dim))),
             "o": w(k[4], (n * v_dim, H))}
    h = jax.random.normal(k[5], (2, 9, H))
    kw = dict(heads=n, nope=nope, rope=rope, v_dim=v_dim, theta=1e4,
              norm=lambda t, s: t * jax.lax.rsqrt(
                  jnp.mean(t * t, -1, keepdims=True) + 1e-6) * s)
    return layer, h, kw


def test_latent_attention_written_out_a_head_at_a_time():
    """Against the equations with the shared key laid out under every
    head, the pairs turned directly and one softmax a head."""
    layer, h, kw = _mla_layer()
    mask = jnp.asarray((np.arange(9)[None, :] < np.array([[9], [5]]))
                       .astype(np.float32))
    got = seq_block.latent_attention(h, mask, layer, **kw)
    n, nope, rope, v_dim = 4, 8, 4, 6
    c_q = kw["norm"](h @ layer["q_a"], layer["q_a_norm"])
    q = (c_q @ layer["q_b"]).reshape(2, 9, n, nope + rope)
    kv_a = h @ layer["kv_a"]
    c_kv = kw["norm"](kv_a[..., :10], layer["kv_a_norm"])
    k_rope = ref_mod.rotary_pairs(kv_a[..., 10:], 1e4)           # [B, C, r]
    kv = (c_kv @ layer["kv_b"]).reshape(2, 9, n, nope + v_dim)
    heads = []
    for j in range(n):
        q_j = jnp.concatenate(
            [q[:, :, j, :nope], ref_mod.rotary_pairs(q[:, :, j, nope:], 1e4)],
            axis=-1)
        k_j = jnp.concatenate([kv[:, :, j, :nope], k_rope], axis=-1)
        s = jnp.einsum("bqd,bcd->bqc", q_j, k_j) / np.sqrt(nope + rope)
        seen = (np.arange(9)[None, :] <= np.arange(9)[:, None])[None] \
            & (mask > 0)[:, None, :]
        att = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
        heads.append(jnp.einsum("bqc,bcd->bqd", att, kv[:, :, j, nope:]))
    want = jnp.concatenate(heads, axis=-1) @ layer["o"]
    valid = np.asarray(mask) > 0
    np.testing.assert_allclose(np.asarray(got)[valid], np.asarray(want)[valid],
                               atol=2e-5)


def test_latent_attention_is_causal_and_its_rotary_key_is_every_heads():
    layer, h, kw = _mla_layer()
    mask = jnp.ones((2, 9))
    out = seq_block.latent_attention(h, mask, layer, **kw)
    moved = seq_block.latent_attention(h.at[:, 6].add(1.0), mask, layer, **kw)
    np.testing.assert_allclose(np.asarray(out[:, :6]),
                               np.asarray(moved[:, :6]), atol=1e-6)
    assert not np.allclose(np.asarray(out[:, 6:]), np.asarray(moved[:, 6:]),
                           atol=1e-3)
    # the rope columns of kv_a feed ONE key, read by all four heads:
    # zeroing them moves what every head adds through o
    flat_key = dict(layer, kv_a=layer["kv_a"].at[:, 10:].set(0.0))
    for j in range(4):
        only_j = jnp.zeros_like(layer["o"]).at[6 * j:6 * j + 6].set(
            layer["o"][6 * j:6 * j + 6])
        a = seq_block.latent_attention(h, mask, dict(layer, o=only_j), **kw)
        b = seq_block.latent_attention(h, mask, dict(flat_key, o=only_j),
                                       **kw)
        assert float(jnp.max(jnp.abs(a - b))) > 1e-3, j
    # the norm on c_kv is there: scaling kv_a's latent columns by 10
    # changes nothing (an RMSNorm forgets its input's scale), scaling
    # the norm's own weight does
    scaled = dict(layer, kv_a=layer["kv_a"].at[:, :10].multiply(10.0))
    np.testing.assert_allclose(
        np.asarray(seq_block.latent_attention(h, mask, scaled, **kw)),
        np.asarray(out), atol=1e-4)
    louder = dict(layer, kv_a_norm=2.0 * layer["kv_a_norm"])
    assert not np.allclose(
        np.asarray(seq_block.latent_attention(h, mask, louder, **kw)),
        np.asarray(out), atol=1e-3)
    # the scores run over nope + rope = 12, the values over 6
    assert out.shape == (2, 9, 32)


# ---- the core over the training staircase (ISSUE 35) ---------------------

def _latent_attention_before_the_blocks(h, mask, layer, *, heads, nope, rope,
                                        v_dim, theta, norm):
    """`seq_block.latent_attention` as it stood before ISSUE 35 (its
    comments left out): what a caller that passes no staircase still
    lowers to."""
    import math

    dtype = h.dtype
    B, C, _ = h.shape

    def turned(t):
        return seq_block.rotary(
            seq_block.deinterleaved(t).transpose(0, 2, 1, 3),
            theta).transpose(0, 2, 1, 3)

    with jax.named_scope("q_lora"):
        c_q = norm(h @ layer["q_a"].astype(dtype), layer["q_a_norm"])
        q = (c_q @ layer["q_b"].astype(dtype)).reshape(B, C, heads,
                                                       nope + rope)
    with jax.named_scope("kv_lora"):
        c_kv, k_rope = jnp.split(h @ layer["kv_a"].astype(dtype),
                                 [layer["kv_a"].shape[1] - rope], axis=-1)
        c_kv = norm(c_kv, layer["kv_a_norm"])
        kv = (c_kv @ layer["kv_b"].astype(dtype)).reshape(B, C, heads,
                                                          nope + v_dim)
    with jax.named_scope("core"):
        q = jnp.concatenate([q[..., :nope], turned(q[..., nope:])], axis=-1)
        k_rope = jnp.broadcast_to(turned(k_rope[:, :, None, :]),
                                  (B, C, heads, rope))
        k = jnp.concatenate([kv[..., :nope], k_rope], axis=-1)
        logits = jnp.einsum("bqnd,bcnd->bnqc", q, k,
                            preferred_element_type=jnp.float32) \
            / math.sqrt(nope + rope)
        slot = jnp.arange(logits.shape[-1])
        seen = (slot[None, :] <= slot[:, None])[None] \
            & (mask > 0)[:, None, :]
        logits = jnp.where(seen[:, None], logits, -1e30)
        att = jax.nn.softmax(logits, axis=-1).astype(dtype)
        out = jnp.einsum("bnqc,bcnd->bqnd", att, kv[..., nope:])
    with jax.named_scope("o"):
        return out.reshape(B, C, heads * v_dim) @ layer["o"].astype(dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_with_no_staircase_latent_attention_lowers_to_the_old_program(
        dtype):
    """Evaluation, prediction, serving and every batch that does not
    fit its staircase pass none: forward and backward, what they lower
    to does not know the argument exists."""
    layer, _, kw = _mla_layer()
    h = jax.random.normal(jax.random.PRNGKey(3), (8, 20, 32)).astype(dtype)
    mask = jnp.asarray(staircase_mask(STAIR_CASES["uneven"]))

    def new(h, layer):
        return seq_block.latent_attention(h, mask, layer, **kw)

    def old(h, layer):
        return _latent_attention_before_the_blocks(h, mask, layer, **kw)

    assert lowered_texts(new, h, layer) == lowered_texts(old, h, layer)
    by_block = lowered_texts(
        lambda h, layer: seq_block.latent_attention(
            h, mask, layer, blocks=seq_block.core_blocks(
                STAIR_CASES["uneven"], None, 20), **kw), h, layer)
    assert by_block[0] != lowered_texts(new, h, layer)[0]


@pytest.mark.parametrize("block_slots", [1, 8])
@pytest.mark.parametrize("case", list(STAIR_CASES))
def test_latent_attention_over_a_staircase_is_the_whole_core(
        case, block_slots, monkeypatch):
    """Each rectangle a query block, and neighbours joined to blocks of
    8 slots: the valid slots' output and every gradient are the whole
    core's to float32 rounding, the slots outside the rectangles read
    zero."""
    from code2vec_tpu.data import staircase

    monkeypatch.setattr(staircase, "_BLOCK_SLOTS", block_slots)
    layer, _, kw = _mla_layer()
    h = jax.random.normal(jax.random.PRNGKey(3), (8, 20, 32))
    assert_staircase_mixer_is_the_whole(
        lambda h, mask, layer, stairs: seq_block.latent_attention(
            h, mask, layer,
            blocks=seq_block.core_blocks(stairs, None, h.shape[1]), **kw),
        h, layer, STAIR_CASES[case])


def test_under_a_mesh_of_two_the_core_stays_whole(monkeypatch):
    """The staircase is one device's rows: dealt to two devices the
    embedding gather takes it (each device its own rows' rectangles)
    and the mixers' core stays today's, so the encoder gives what it
    gives with no staircase and no mesh, and its text holds no block's
    scores."""
    from code2vec_tpu.data import staircase
    from code2vec_tpu.parallel.mesh import make_mesh

    monkeypatch.setattr(staircase, "_BLOCK_SLOTS", 1)

    stairs = ((0, 8), (4, 6), (8, 3))
    _dims, params, _ = program_weights()
    mask = np.tile(staircase_mask(stairs, 8, 12), (2, 1))
    r = np.random.default_rng(9)

    def ids(v):
        return jnp.asarray((r.integers(2, v + 2, mask.shape)
                            * mask).astype(np.int32))

    src, pth, dst = (ids(SIZES["tokens"]), ids(SIZES["paths"]),
                     ids(SIZES["tokens"]))
    mesh = make_mesh(0, 1, devices=jax.devices()[:2])

    def run(mesh, stairs):
        def loss(p):
            code, _attn, _aux = get_encode_fn(DIMS)(
                p, src, pth, dst, jnp.asarray(mask), mesh=mesh,
                staircase=stairs)
            return jnp.sum(code ** 2), code
        grad = jax.jit(jax.grad(loss, has_aux=True))
        return grad(params)

    def text(mesh, stairs):
        return jax.jit(lambda p: get_encode_fn(DIMS)(
            p, src, pth, dst, jnp.asarray(mask), mesh=mesh,
            staircase=stairs)[0]).lower(params).as_text()

    assert "x4x12x12xf32>" in text(mesh, stairs)
    assert "tensor<6x4x4x8xf32>" not in text(mesh, stairs)
    assert "tensor<6x4x4x8xf32>" in text(None, stairs)
    grads, code = run(mesh, stairs)
    want_grads, want_code = run(None, None)
    np.testing.assert_allclose(np.asarray(code), np.asarray(want_code),
                               rtol=1e-4, atol=5e-5)
    got, want = flat(grads["joyai"]), flat(want_grads["joyai"])
    for name in want:
        norm = float(jnp.linalg.norm(want[name]))
        if "expert_bias" in name:
            continue            # selects only: no gradient reaches it
        assert norm > 0, name
        gap = float(jnp.linalg.norm(got[name] - want[name])) / norm
        assert gap < 1e-3, (name, gap)


# ---- the router ----------------------------------------------------------

def _router_case(n=64, H=16, E=16):
    k = jax.random.split(jax.random.PRNGKey(1), 3)
    return (jax.random.normal(k[0], (n, H)),
            0.5 * jax.random.normal(k[1], (H, E)),
            0.3 * jax.random.normal(k[2], (E,)))


def _route_as_it_was(h, router, bias, top_k, score="sigmoid"):
    """`ops/moe.route` as it stood before it took `scale` and `eps`
    (commit 90214db), written out."""
    logits = jnp.dot(h.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if score == "softmax":
        s_chosen, chosen = jax.lax.top_k(jax.nn.softmax(logits, axis=-1),
                                         top_k)
        return chosen.astype(jnp.int32), \
            s_chosen / jnp.sum(s_chosen, axis=-1, keepdims=True)
    s = jax.nn.sigmoid(logits)
    _, chosen = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
    s_chosen = jnp.take_along_axis(s, chosen, axis=-1)
    p = s_chosen / (jnp.sum(s_chosen, axis=-1, keepdims=True) + 1e-6)
    return chosen.astype(jnp.int32), p


@pytest.mark.parametrize("score", ["sigmoid", "softmax"])
def test_route_with_its_defaults_is_bit_for_bit_what_it_was(score):
    h, router, bias = _router_case()
    bias = bias if score == "sigmoid" else None
    for fn in (lambda f: f, jax.jit):
        chosen, p = fn(lambda h: moe.route(h, router, bias, 4, score))(h)
        want_chosen, want_p = fn(lambda h: _route_as_it_was(
            h, router, bias, 4, score))(h)
        np.testing.assert_array_equal(np.asarray(chosen),
                                      np.asarray(want_chosen))
        np.testing.assert_array_equal(np.asarray(p), np.asarray(want_p))
    # and the lowered text holds no multiplication by a scale of 1
    def text(f):
        return jax.jit(f).lower(h).as_text()
    assert text(lambda h: moe.route(h, router, bias, 4, score)[1]) == \
        text(lambda h: _route_as_it_was(h, router, bias, 4, score)[1])


def test_the_scale_weighs_the_sum_and_the_bias_selects_only():
    h, router, bias = _router_case()
    chosen, p = moe.route(h, router, bias, 4, scale=2.5, eps=1e-20)
    chosen1, p1 = moe.route(h, router, bias, 4)
    np.testing.assert_array_equal(np.asarray(chosen), np.asarray(chosen1))
    # p = 2.5 s / (sum of the chosen s + 1e-20), of the scores alone
    s = jax.nn.sigmoid(h @ router)
    s_chosen = jnp.take_along_axis(s, chosen, axis=-1)
    np.testing.assert_allclose(
        np.asarray(p),
        2.5 * np.asarray(s_chosen / s_chosen.sum(-1, keepdims=True)),
        rtol=1e-5)
    np.testing.assert_allclose(np.asarray(p.sum(-1)), 2.5, rtol=1e-5)
    # the bias changes who is chosen
    chosen0, p0 = moe.route(h, router, jnp.zeros_like(bias), 4, scale=2.5,
                            eps=1e-20)
    assert np.any(np.sort(chosen0, -1) != np.sort(chosen, -1))
    # and p does not see it: a token whose choice the bias left alone
    # keeps its p, and no p holds the bias of its expert
    same = np.all(np.sort(chosen0, -1) == np.sort(chosen, -1), axis=-1)
    assert same.any()
    np.testing.assert_allclose(np.sort(np.asarray(p0)[same], -1),
                               np.sort(np.asarray(p)[same], -1), rtol=1e-6)
    with_bias = s_chosen + bias[chosen]
    assert not np.allclose(
        np.asarray(p),
        2.5 * np.asarray(with_bias / with_bias.sum(-1, keepdims=True)),
        rtol=1e-3)


# ---- expert parallelism --------------------------------------------------

def _layer_case(E=32, H=32, F=24, n_tokens=96, seed=2):
    k = jax.random.split(jax.random.PRNGKey(seed), 9)
    return dict(
        h=jax.random.normal(k[0], (n_tokens, H)),
        valid=jnp.arange(n_tokens) % 7 != 3,
        router=0.4 * jax.random.normal(k[1], (H, E)),
        bias=0.05 * jax.random.normal(k[2], (E,)),
        w1=0.2 * jax.random.normal(k[3], (E, H, F)),
        w3=0.2 * jax.random.normal(k[4], (E, H, F)),
        w2=0.2 * jax.random.normal(k[5], (E, F, H)),
        shared_w1=0.2 * jax.random.normal(k[6], (H, F)),
        shared_w3=0.2 * jax.random.normal(k[7], (H, F)),
        shared_w2=0.2 * jax.random.normal(k[8], (F, H)))


def _share(case, first, held, per_token=8):
    chosen, p = moe.route(case["h"], case["router"], case["bias"], per_token,
                          scale=2.5, eps=1e-20)
    sl = slice(first, first + held)
    return moe.held_experts_ffn(case["h"], case["valid"], chosen, p,
                                case["w1"][sl], case["w3"][sl],
                                case["w2"][sl], first,
                                case["router"].shape[1])


def _shared(case):
    return seq_block.swiglu(case["h"], case["shared_w1"], case["shared_w3"],
                            case["shared_w2"])


def test_the_sixteen_shares_add_up():
    """Experts 0-1, 2-3, ... as sixteen chips would hold a 32-expert toy
    layer: the routed parts of all the shares, with the shared expert
    counted once, equal the uncut layer of the reference, and their rows
    every choice of every valid token."""
    case = _layer_case()
    with jax.default_matmul_precision("highest"):
        whole = ref_mod.expert_layer(
            case["h"], case["valid"], case["router"], case["bias"],
            case["w1"], case["w3"], case["w2"], first=0, per_token=8,
            scale=2.5) + ref_mod.lfm.swiglu(
                case["h"], case["shared_w1"], case["shared_w3"],
                case["shared_w2"], lambda x, w: x @ w)
        parts = [_share(case, first, 2) for first in range(0, 32, 2)]
        total = sum(out for out, _rows in parts) + _shared(case)
    assert len(parts) == 16
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=5e-5)
    rows = sum(int(r.sum()) for _out, r in parts)
    assert rows == 8 * int(case["valid"].sum())
    # one share against the reference given the same share
    with jax.default_matmul_precision("highest"):
        want = ref_mod.expert_layer(
            case["h"], case["valid"], case["router"], case["bias"],
            case["w1"][8:10], case["w3"][8:10], case["w2"][8:10], first=8,
            per_token=8, scale=2.5)
    np.testing.assert_allclose(np.asarray(parts[4][0]), np.asarray(want),
                               atol=5e-5)
    # every share's shared expert counted: fifteen too many
    assert not np.allclose(
        np.asarray(total + 15 * _shared(case)), np.asarray(whole), atol=1e-3)
    # the shared expert is ungated and sees every token, masked or not
    assert np.all(np.abs(np.asarray(_shared(case))).sum(axis=1) > 0)
    # a masked token is routed nowhere
    np.testing.assert_array_equal(
        np.asarray(parts[0][0])[~np.asarray(case["valid"])], 0.0)


def test_encoder_counts_its_expert_layers():
    """aux, an expert layer: the held experts' rows, the valid tokens, the
    row bound (8 x 12 x 3 = 288 pairs, 256 held at 4 of 16) and whether
    the layer ran at it; the dense layer counts nothing."""
    _dims, params, _ = program_weights()
    _labels, src, pth, dst, mask, _w = batches()[0]
    encode = jax.jit(lambda p: get_encode_fn(DIMS)(
        p, src, pth, dst, jnp.asarray(mask)))
    _code, _attn, aux = encode(params)
    aux = np.asarray(aux)
    assert aux.shape == (2, 4 + 3)
    assert aux[:, 4].tolist() == [int(mask.sum())] * 2
    assert aux[:, 5:].tolist() == [[256, 1]] * 2
    assert 0 < aux[:, :4].sum() <= 2 * 3 * int(mask.sum())
    # the cell: 128 methods x 200 slots, 8 choices, 16 of 256 held
    assert moe.row_bound(25600 * 8, 16, 256) == 25600


def test_under_a_mesh_of_two_every_device_routes_its_own_rows():
    from code2vec_tpu.parallel.mesh import make_mesh

    _dims, params, _ = program_weights()
    _labels, src, pth, dst, mask, _w = batches(n=16)[0]
    mesh = make_mesh(0, 1, devices=jax.devices()[:2])

    def run(mesh):
        def loss(p):
            code, _attn, aux = get_encode_fn(DIMS)(
                p, src, pth, dst, jnp.asarray(mask), mesh=mesh)
            return jnp.sum(code ** 2), (code, aux)
        return jax.jit(jax.grad(loss, has_aux=True))

    grads, (code, aux) = run(mesh)(params)
    want_grads, (want_code, want_aux) = run(None)(params)
    aux, want_aux = np.asarray(aux), np.asarray(want_aux)
    # rows and valid tokens are the whole batch's, summed over the two
    # devices; the bound (16 x 12 x 3 / 2 = 288 pairs a device, 256 held)
    # and the decisions are sums too
    np.testing.assert_array_equal(aux[:, :5], want_aux[:, :5])
    assert aux[:, 5:].tolist() == [[2 * 256, 2]] * 2
    np.testing.assert_allclose(np.asarray(code), np.asarray(want_code),
                               rtol=1e-4, atol=5e-5)
    got, want = flat(grads["joyai"]), flat(want_grads["joyai"])
    for name in ("layers/0/kv_a", "layers/1/w2", "layers/2/q_b",
                 "layers/1/router", "layers/2/shared_w1", "layers/0/w1"):
        gap = float(jnp.linalg.norm(got[name] - want[name])
                    / jnp.linalg.norm(want[name]))
        assert gap < 1e-3, (name, gap)


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

    argv = ["--data", str(tmp_path / "d"), "--encoder", "joyai_flash",
            "--backend", "cpu"]
    if flags == ["--no_block_config"]:
        flags = []
    else:
        argv += ["--block_config", _block_config_file(tmp_path)]
    with pytest.raises(ValueError, match=message):
        Config.load_from_args(argv + flags)


def test_block_sizes_come_from_the_config_json():
    assert (JOYAI.routed, JOYAI.qk_head_dim, JOYAI.shared_width) == \
        (16, 12, 24)
    assert JOYAI.layer_types == ("latent_attention",) * 3
    # the repo's names for the two numbers the source names otherwise
    assert (JOYAI.num_experts, JOYAI.num_dense_layers) == (4, 1)
    # only the share has a default: all the router's experts, held from
    # the first
    whole = JoyaiDims.from_config(
        {k: v for k, v in BLOCK.items()
         if k not in ("num_routed_experts", "first_expert")})
    assert (whole.routed, whole.first_expert) == (4, 0)
    # keys the block does not read are passed over, stated ones checked,
    # and a twin may stand for its source key or beside it
    assert JoyaiDims.from_config(dict(
        BLOCK, vocab_size=129280, qk_head_dim=12, num_key_value_heads=4,
        head_dim=64, ep_size=1, num_experts=4, num_dense_layers=1,
        scoring_func="sigmoid", topk_method="noaux_tc",
        num_nextn_predict_layers=0)) == JOYAI
    renamed = {k: v for k, v in BLOCK.items()
               if k not in ("n_routed_experts", "first_k_dense_replace")}
    assert JoyaiDims.from_config(dict(renamed, num_experts=4,
                                      num_dense_layers=1)) == JOYAI
    # the manifest's form comes back whole
    assert JoyaiDims.from_config(dataclasses.asdict(JOYAI)) == JOYAI
    with pytest.raises(ValueError, match="hidden_size"):
        JoyaiDims.from_config({k: v for k, v in BLOCK.items()
                               if k != "hidden_size"})
    with pytest.raises(ValueError, match="held"):
        JoyaiDims.from_config(dict(BLOCK, first_expert=14))
    with pytest.raises(ValueError, match="qk_head_dim"):
        JoyaiDims.from_config(dict(BLOCK, qk_head_dim=192))
    with pytest.raises(ValueError, match="n_routed_experts.*num_experts"):
        JoyaiDims.from_config(dict(BLOCK, num_experts=16))
    with pytest.raises(ValueError,
                       match="first_k_dense_replace.*num_dense_layers"):
        JoyaiDims.from_config(dict(BLOCK, num_dense_layers=0))


@pytest.mark.parametrize("key,value", [
    ("scoring_func", "softmax"), ("topk_method", "greedy"),
    ("n_group", 8), ("topk_group", 4), ("norm_topk_prob", False),
    ("rope_scaling", {"type": "yarn", "factor": 40.0}),
    ("rope_interleave", False), ("attention_bias", True),
    ("moe_layer_freq", 2), ("num_nextn_predict_layers", 1),
    ("hidden_act", "gelu"), ("q_lora_rank", None)])
def test_from_config_refuses_a_switch_it_does_not_implement(key, value):
    with pytest.raises(ValueError, match=key):
        JoyaiDims.from_config(dict(BLOCK, **{key: value}))


def test_lfm2_moe_points_a_scaled_router_at_routes_argument():
    from code2vec_tpu.models.lfm2_moe_encoder import Lfm2Dims
    from tests.test_lfm2_moe import BLOCK as lfm_block

    with pytest.raises(ValueError, match="routed_scaling_factor.*scale"):
        Lfm2Dims.from_config(dict(lfm_block, routed_scaling_factor=2.5))


def test_the_benchmarks_file_is_a_block_config():
    """`benchmark/configs/java-large-joyai.json` is the program's
    `--block_config`: the published widths, one chip's share."""
    with open(os.path.join(REPO, "benchmark", "configs",
                           "java-large-joyai.json")) as f:
        config = json.load(f)
    dims = JoyaiDims.from_config(config)
    assert (dims.num_hidden_layers, dims.hidden_size, dims.q_lora_rank,
            dims.kv_lora_rank, dims.qk_head_dim, dims.v_head_dim) == \
        (5, 2048, 1536, 512, 192, 128)
    assert (dims.num_experts, dims.routed, dims.first_expert,
            dims.num_experts_per_tok, dims.num_dense_layers) == \
        (16, 256, 0, 8, 1)
    assert (dims.routed_scaling_factor, dims.rope_theta) == (2.5, 32e6)
    for key in config["block"]["keys"]:
        got = getattr(dims, key)
        assert (list(got) if isinstance(got, tuple) else got) == config[key]
    # the source's file as published is refused for its MTP layer alone
    with pytest.raises(ValueError, match="num_nextn_predict_layers"):
        JoyaiDims.from_config(dict(config, **config["published"]))


# ---- tracing -------------------------------------------------------------

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
        for scope in ("c2v/encode", "c2v/blk_0/mla", "c2v/blk_0/mla/q_lora",
                      "c2v/blk_0/mla/kv_lora", "c2v/blk_0/mla/core",
                      "c2v/blk_0/mla/o", "c2v/blk_0/mlp", "c2v/blk_1/mla/core",
                      "c2v/blk_1/router", "c2v/blk_1/experts",
                      "c2v/blk_1/shared", "c2v/blk_2/mla/kv_lora",
                      "c2v/blk_2/router", "c2v/blk_2/experts",
                      "c2v/blk_2/shared", "c2v/pool"):
            assert scope in text, scope
        assert "c2v/blk_0/router" not in text and \
            "c2v/blk_1/mlp" not in text


def test_compiles_stay_zero_across_batches_of_different_routing():
    import optax

    from code2vec_tpu.obs import memory_tracer
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
    for i, b in enumerate(batches(steps=5, seed=11)):
        params, state, _loss = step(params, state,
                                    tuple(jnp.asarray(a) for a in b),
                                    jax.random.fold_in(rng, i))
        if i == 0:
            jax.block_until_ready(params)
            compiles[0] = 0
    step.route_recorder.flush()
    records = [r["attrs"] for r in memory_tracer().records("moe/route")[-5:]]
    assert len({tuple(map(tuple, a["layers"])) for a in records}) == 5
    assert compiles[0] == 0
    # two expert layers of four held experts a record
    assert all(len(a["layers"]) == 2 and len(a["layers"][0]) == 4
               for a in records)


# ---- the model class -----------------------------------------------------

def test_model_trains_evaluates_saves_and_reloads(tmp_path):
    """Through `Code2VecModel` on the tests' 8-device mesh (every device
    routes its own rows), with the encoder's sizes kept by the
    checkpoint's manifest."""
    from code2vec_tpu.models.jax_model import Code2VecModel
    from tests.test_model import tiny_config

    prefix = build_tiny_dataset(str(tmp_path), n_train=256, n_val=32,
                                n_test=64, max_contexts=16)
    cfg = tiny_config(prefix, ENCODER_TYPE="joyai_flash",
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
    # the --trace log holds a record a step, and the report prints them
    from tests.test_trace import _spans
    from tools.trace_report import render, route_summary
    spans = _spans(model.telemetry.run_dir)
    route = route_summary(spans)
    assert route["steps"] == model.step_num
    assert (route["expert_layers"], route["held_experts"]) == (2, 4)
    assert 0 < route["rows_here"] <= 2 * 3 * route["valid_tokens"]
    assert f"Routed experts: {route['rows_here']:,} rows" in \
        render([({}, spans)])

    cfg2 = tiny_config(prefix)
    cfg2.load_path = ckpt_dir
    model2 = Code2VecModel(cfg2)
    assert model2.dims.joyai == JOYAI
    assert model2.dims.lfm is None and model2.dims.qwen is None
    loaded = model2.evaluate()
    assert loaded.topk_acc == pytest.approx(result.topk_acc)
    with open(os.path.join(ckpt_dir, "manifest.json")) as f:
        manifest = json.load(f)
    assert manifest["encoder_type"] == "joyai_flash"
    assert manifest["joyai"]["kv_lora_rank"] == 16
    assert manifest["lfm"] is None and manifest["qwen"] is None
