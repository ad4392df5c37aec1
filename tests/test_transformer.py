"""Transformer path-encoder (BASELINE.json configs[4]): shape/mask
invariants, permutation equivariance (contexts are a bag), end-to-end
learning vs the bag encoder, checkpoint round-trip, and REAL context
parallelism — the train step on a ('data','ctx','model') = (2,2,2) mesh
with the context dim sharded must match single-device numerics."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from code2vec_tpu.models.encoder import ModelDims, get_encode_fn, \
    init_params
from tests.helpers import build_tiny_dataset, example_batch

DIMS = ModelDims(token_vocab_size=40, path_vocab_size=30,
                 target_vocab_size=20, embeddings_size=16, max_contexts=8,
                 dropout_keep_rate=1.0, encoder_type="transformer",
                 xf_layers=2, xf_heads=4)


def test_init_params_has_xf_subtree():
    p = init_params(jax.random.PRNGKey(0), DIMS)
    assert "xf" in p and len(p["xf"]["layers"]) == 2
    D = DIMS.context_vector_size
    assert p["xf"]["layers"][0]["qkv"].shape == (D, 3 * D)
    # bag dims get no xf subtree
    bag = init_params(jax.random.PRNGKey(0),
                      ModelDims(40, 30, 20, 16, 8))
    assert "xf" not in bag


def test_masked_contexts_do_not_affect_code():
    p = init_params(jax.random.PRNGKey(1), DIMS)
    enc = get_encode_fn(DIMS)
    labels, src, pth, dst, mask, _w = example_batch(3, DIMS, 4)
    mask = np.ones_like(mask)
    mask[:, 5:] = 0.0
    code1, attn1, _ = enc(p, src, pth, dst, jnp.asarray(mask))
    # change ids ONLY in masked positions
    src2 = src.copy()
    src2[:, 5:] = (src2[:, 5:] + 7) % DIMS.token_vocab_size
    code2, attn2, _ = enc(p, jnp.asarray(src2), pth, dst, jnp.asarray(mask))
    np.testing.assert_allclose(np.asarray(code1), np.asarray(code2),
                               atol=1e-5)
    assert np.all(np.asarray(attn1)[:, 5:] < 1e-6)


def test_permutation_equivariance_of_code():
    """Contexts are an unordered bag: permuting them (and the mask) must
    not change the code vector."""
    p = init_params(jax.random.PRNGKey(2), DIMS)
    enc = get_encode_fn(DIMS)
    labels, src, pth, dst, mask, _w = example_batch(4, DIMS, 4)
    perm = np.random.default_rng(0).permutation(DIMS.max_contexts)
    code1, *_ = enc(p, src, pth, dst, jnp.asarray(mask))
    code2, *_ = enc(p, jnp.asarray(src[:, perm]), jnp.asarray(pth[:, perm]),
                    jnp.asarray(dst[:, perm]), jnp.asarray(mask[:, perm]))
    np.testing.assert_allclose(np.asarray(code1), np.asarray(code2),
                               atol=1e-4)


def test_all_pad_row_is_finite():
    p = init_params(jax.random.PRNGKey(3), DIMS)
    enc = get_encode_fn(DIMS)
    labels, src, pth, dst, mask, _w = example_batch(5, DIMS, 2)
    mask = np.zeros_like(mask)
    code, attn, _ = enc(p, src, pth, dst, jnp.asarray(mask))
    assert np.all(np.isfinite(np.asarray(code)))
    assert np.all(np.isfinite(np.asarray(attn)))


def test_transformer_train_step_learns():
    from code2vec_tpu.training.steps import make_train_step

    p = init_params(jax.random.PRNGKey(0), DIMS)
    opt = optax.adam(3e-3)
    step = make_train_step(DIMS, opt)
    state = opt.init(p)
    batch = tuple(jnp.asarray(a) for a in example_batch(7, DIMS, 16))
    rng = jax.random.PRNGKey(0)
    losses = []
    for _ in range(30):
        rng, k = jax.random.split(rng)
        p, state, loss = step(p, state, batch, k)
        losses.append(float(loss))
    assert np.mean(losses[-5:]) < np.mean(losses[:5])
    assert np.isfinite(losses[-1])


@pytest.mark.parametrize("ring", [False, True],
                         ids=["xla-allgather", "ring-attention"])
def test_context_parallel_matches_single_device(ring):
    """(data=2, ctx=2, model=2) mesh, context dim sharded — via XLA's
    inserted collectives or explicit ring attention (K/V ppermute
    rotation): numerics must match one device either way."""
    import dataclasses

    from code2vec_tpu.parallel.mesh import make_mesh
    from code2vec_tpu.parallel.sharding import (shard_batch,
                                                shard_opt_state,
                                                shard_params)
    from code2vec_tpu.training.steps import make_train_step

    dims = ModelDims(token_vocab_size=40, path_vocab_size=30,
                     target_vocab_size=20, embeddings_size=16,
                     max_contexts=8, dropout_keep_rate=1.0,
                     encoder_type="transformer", xf_layers=2, xf_heads=4,
                     vocab_pad_multiple=2)
    params = init_params(jax.random.PRNGKey(0), dims)
    opt = optax.adam(1e-2)
    batch = tuple(jnp.asarray(a) for a in example_batch(9, dims, 8))
    rng = jax.random.PRNGKey(1)

    step = make_train_step(dims, opt)
    p1, _, loss1 = step(jax.tree_util.tree_map(jnp.copy, params),
                        opt.init(params), batch, rng)

    mesh = make_mesh(2, 2, 2)
    assert dict(mesh.shape) == {"dcn": 1, "data": 2, "ctx": 2,
                                "model": 2}
    dims2 = dataclasses.replace(dims, ring_attention=ring)
    sp = shard_params(mesh, params)
    so = shard_opt_state(mesh, opt.init(sp), sp)
    sb = shard_batch(mesh, batch, shard_contexts=True)
    # [B, C] tensors really are context-sharded
    assert "ctx" in str(sb[1].sharding.spec)
    step2 = make_train_step(dims2, opt, mesh=mesh if ring else None)
    p2, _, loss2 = step2(sp, so, sb, rng)

    np.testing.assert_allclose(float(loss1), float(loss2), rtol=1e-5)
    flat1, tree1 = jax.tree_util.tree_flatten(p1)
    flat2, tree2 = jax.tree_util.tree_flatten(p2)
    assert tree1 == tree2
    for a, b in zip(flat1, flat2):
        np.testing.assert_allclose(np.asarray(jax.device_get(a)),
                                   np.asarray(jax.device_get(b)),
                                   atol=2e-5)


def test_transformer_model_end_to_end(tmp_path):
    """Tiny dataset: transformer encoder trains through the full model
    class, ties/beats the bag encoder's F1, and round-trips its
    checkpoint (encoder config from the manifest)."""
    from code2vec_tpu.models.jax_model import Code2VecModel
    from tests.test_model import tiny_config

    prefix = build_tiny_dataset(str(tmp_path), n_train=256, n_val=32,
                                n_test=64, max_contexts=16)
    cfg = tiny_config(prefix, ENCODER_TYPE="transformer", XF_LAYERS=2,
                      XF_HEADS=4, NUM_TRAIN_EPOCHS=8, LEARNING_RATE=0.01)
    ckpt_dir = str(tmp_path / "ckpt")
    cfg.save_path = ckpt_dir
    model = Code2VecModel(cfg)
    model.train()
    xf_eval = model.evaluate()
    assert xf_eval.subtoken_f1 > 0.5
    model.save(ckpt_dir)

    cfg2 = tiny_config(prefix)   # encoder comes from the manifest
    cfg2.load_path = ckpt_dir
    model2 = Code2VecModel(cfg2)
    assert model2.dims.encoder_type == "transformer"
    loaded = model2.evaluate()
    assert loaded.topk_acc == pytest.approx(xf_eval.topk_acc)


def test_xf_remat_identical_numerics():
    """xf_remat recomputes activations in backward but must not change
    forward values or gradients (CodeBERT-depth memory knob)."""
    import dataclasses

    from code2vec_tpu.training.steps import make_train_step
    dims_r = dataclasses.replace(DIMS, xf_remat=True)
    p = init_params(jax.random.PRNGKey(5), DIMS)
    labels, src, pth, dst, mask, w = example_batch(5, DIMS, 4)
    batch = tuple(jnp.asarray(a) for a in
                  (labels, src, pth, dst, mask, w))
    opt = optax.adam(0.01)
    outs = []
    for d in (DIMS, dims_r):
        step = make_train_step(d, opt)
        p2, _, loss = step(jax.tree_util.tree_map(jnp.copy, p),
                           opt.init(p), batch, jax.random.PRNGKey(6))
        outs.append((np.asarray(p2["xf"]["layers"][0]["qkv"]),
                     float(loss)))
    np.testing.assert_allclose(outs[0][1], outs[1][1], rtol=1e-6)
    np.testing.assert_allclose(outs[0][0], outs[1][0], atol=1e-6)


def test_checkpoint_roundtrips_xf_remat(tmp_path):
    import dataclasses

    from code2vec_tpu.training import checkpoint as ckpt
    from code2vec_tpu.vocab.vocabularies import (Code2VecVocabs, Vocab,
                                                 VocabType)
    dims_r = dataclasses.replace(DIMS, xf_remat=True)
    p = init_params(jax.random.PRNGKey(7), dims_r)
    vocabs = Code2VecVocabs(Vocab(VocabType.Token, ["a"]),
                            Vocab(VocabType.Path, ["1"]),
                            Vocab(VocabType.Target, ["t"]))
    ckpt.save_checkpoint(str(tmp_path / "c"), {"params": p, "step": 0},
                         0, vocabs, dims_r)
    assert ckpt.load_dims(str(tmp_path / "c")).xf_remat is True
