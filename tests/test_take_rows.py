"""`models/encoder.take_rows` reads a spread row for every PAD slot and
puts the PAD row's value back (ISSUE 27): bit for bit `jnp.take` in the
forward, the same gradient on every row, alone, under `jit` and over
the batch axis of a 4-device mesh; and whole train steps of both
encoders against the same steps with plain `jnp.take` in its place."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from code2vec_tpu.models import encoder
from code2vec_tpu.models.encoder import (PAD_ID, ModelDims, init_params,
                                         take_rows)
from code2vec_tpu.training.steps import make_train_step

V, E, B, C = 37, 8, 8, 6        # B * C > V: the spread rows wrap around


def plain_take(params, name, ids):
    """The path before ISSUE 27, kept here as the reference only."""
    return jnp.take(params[name], ids, axis=0)


def make_ids(pad: str) -> np.ndarray:
    """[B, C] ids in 1..V-1 with PAD as the bags' tails, as the reader
    pads them."""
    rng = np.random.default_rng(7)
    ids = rng.integers(1, V, size=(B, C)).astype(np.int32)
    lengths = {"none": np.full(B, C),
               "sixty_percent": rng.permutation(
                   np.array([0, 1, 1, 2, 2, 3, 4, 6])),     # 29 of 48 PAD
               "one_bag": np.array([C] * 3 + [0] + [C] * 4),
               "everywhere": np.zeros(B, int)}[pad]
    ids[np.arange(C)[None, :] >= lengths[:, None]] = PAD_ID
    return ids


def table(dtype) -> jax.Array:
    return jax.random.normal(jax.random.PRNGKey(3), (V, E),
                             jnp.float32).astype(dtype)


def run(fn, how: str, t, ids, *rest):
    """`fn(t, ids, *rest)` as is, jitted, or jitted with the ids (and
    what rides with them) split over four devices and the table on
    each."""
    if how == "eager":
        return fn(t, ids, *rest)
    jitted = jax.jit(fn)
    if how == "jit":
        return jitted(t, ids, *rest)
    from code2vec_tpu.parallel.mesh import make_mesh
    from code2vec_tpu.parallel.sharding import shard_batch, shard_params
    mesh = make_mesh(4, 1, 1, devices=jax.devices()[:4])
    t = shard_params(mesh, {"token_emb": t})["token_emb"]
    ids, *rest = shard_batch(mesh, tuple(np.asarray(a)
                                         for a in (ids, *rest)))
    return jitted(t, ids, *rest)


PADS = ["none", "sixty_percent", "one_bag", "everywhere"]
HOWS = ["eager", "jit", "mesh4"]


def test_pad_is_index_zero_of_every_vocabulary():
    from code2vec_tpu.vocab.vocabularies import Vocab, VocabType
    for kind in VocabType:
        assert Vocab(kind, ["a", "b"]).pad_index == PAD_ID


@pytest.mark.parametrize("pad", PADS)
def test_the_gather_reads_a_row_of_its_own_for_each_pad_slot(pad):
    ids = make_ids(pad)
    is_pad, spread = encoder._spread_pad(V, jnp.asarray(ids))
    is_pad, spread = np.asarray(is_pad), np.asarray(spread)
    np.testing.assert_array_equal(is_pad, ids == PAD_ID)
    np.testing.assert_array_equal(spread[~is_pad], ids[~is_pad])
    assert spread.dtype == ids.dtype
    assert ((0 <= spread) & (spread < V)).all()
    # no row is named by more PAD slots than the wrap-around forces
    if is_pad.any():
        counts = np.bincount(spread[is_pad], minlength=V)
        assert counts.max() <= -(-B * C // V) + 1


@pytest.mark.parametrize("how", HOWS)
@pytest.mark.parametrize("pad", PADS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_forward_is_bit_equal_to_take(dtype, pad, how):
    t, ids = table(dtype), make_ids(pad)
    got = run(lambda t, i: take_rows({"t": t}, "t", i), how, t, ids)
    want = jnp.take(t, ids, axis=0)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(
        np.asarray(got.astype(jnp.float32)),
        np.asarray(want.astype(jnp.float32)))


@pytest.mark.parametrize("ids", [
    np.int32(0), np.int32(5), np.array([0, 3, 0], np.int32),
    np.zeros((2, 3, 4), np.int32),
    np.array([[V, -1, 0], [V + 5, 2, -V]], np.int32)],
    ids=["pad_scalar", "scalar", "vector", "rank3", "out_of_bounds"])
def test_forward_is_bit_equal_for_any_shape_and_for_ids_out_of_bounds(ids):
    t = table(jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(take_rows({"t": t}, "t", ids)),
        np.asarray(jnp.take(t, ids, axis=0)))


@pytest.mark.parametrize("how", HOWS)
@pytest.mark.parametrize("pad", PADS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gradient_equals_takes_on_every_row(dtype, pad, how):
    """A cotangent that is nonzero at the PAD slots too: row 0 gets
    their sum, the spread rows nothing, every other row what it got."""
    t, ids = table(dtype), make_ids(pad)
    w = jax.random.normal(jax.random.PRNGKey(5), (B, C, E), jnp.float32)

    def grad_of(take):
        def loss(t, ids, w):
            rows = take({"t": t}, "t", ids).astype(jnp.float32)
            return jnp.sum(rows * rows * w + rows * w)
        return jax.grad(loss)

    got = run(grad_of(take_rows), how, t, ids, w)
    want = grad_of(plain_take)(t, ids, w)
    assert got.dtype == want.dtype
    got, want = (np.asarray(g.astype(jnp.float32)) for g in (got, want))
    if dtype == jnp.float32:        # exact up to the order of a row's sum
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    else:
        # bf16 rows add up in bf16 in either path, in another order
        scale = np.abs(want).max(axis=1, keepdims=True) + 1.0
        assert np.max(np.abs(got - want) / scale) < 0.05
    untouched = np.setdiff1d(np.arange(V), ids)
    assert not got[untouched].any()


def ragged_batch(dims: ModelDims):
    rng = np.random.default_rng(11)
    n, c = 8, dims.max_contexts
    lengths = np.array([0, 1, 2, 3, 4, c, c, 2])
    live = np.arange(c)[None, :] < lengths[:, None]

    def ids(vocab):
        return np.where(live, rng.integers(2, vocab, size=(n, c)),
                        PAD_ID).astype(np.int32)
    return (rng.integers(2, dims.target_vocab_size, n).astype(np.int32),
            ids(dims.token_vocab_size), ids(dims.path_vocab_size),
            ids(dims.token_vocab_size), live.astype(np.float32),
            (lengths > 0).astype(np.float32))


@pytest.mark.parametrize("encoder_type", ["bag", "transformer"])
@pytest.mark.parametrize("tables_dtype", ["float32", "bfloat16"])
def test_three_train_steps_equal_those_with_plain_take(
        monkeypatch, encoder_type, tables_dtype):
    dims = ModelDims(token_vocab_size=50, path_vocab_size=40,
                     target_vocab_size=30, embeddings_size=8,
                     max_contexts=6, tables_dtype=tables_dtype,
                     encoder_type=encoder_type, xf_layers=1, xf_heads=2)
    batch = tuple(jnp.asarray(a) for a in ragged_batch(dims))

    def three_steps():
        opt = optax.adam(0.01)
        step = make_train_step(dims, opt, use_sampled_softmax=True,
                               num_sampled=8)
        params = init_params(jax.random.PRNGKey(0), dims)
        opt_state = opt.init(params)
        losses = []
        for i in range(3):
            params, opt_state, loss = step(
                params, opt_state, batch,
                jax.random.fold_in(jax.random.PRNGKey(1), i))
            losses.append(float(loss))
        return losses, params

    losses, params = three_steps()
    # every encoder gathers through `encoder.embed_contexts`
    monkeypatch.setattr(encoder, "take_rows", plain_take)
    want_losses, want = three_steps()
    # a PAD slot's cotangent is an exact zero under the bag's mask and
    # about 1e-30 under the transformer's, so row 0's sum is the same
    # whatever its order
    tol = dict(rtol=1e-6, atol=1e-7) if tables_dtype == "float32" \
        else dict(rtol=1e-2, atol=1e-3)
    np.testing.assert_allclose(losses, want_losses, **tol)
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    flat_want, _ = jax.tree_util.tree_flatten_with_path(want)
    assert len(flat) == len(flat_want) > 4
    for (key, a), (_key, b) in zip(flat, flat_want):
        a, b = (np.asarray(x.astype(jnp.float32)) for x in (a, b))
        np.testing.assert_allclose(a, b, err_msg=jax.tree_util.keystr(key),
                                   **tol)
