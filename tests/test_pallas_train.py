"""The fused Pallas pool under grad (custom VJP) must match the XLA path
in both loss and gradients (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax

from code2vec_tpu.models.encoder import ModelDims, init_params
from code2vec_tpu.training.steps import make_train_step

DIMS = ModelDims(token_vocab_size=20, path_vocab_size=16,
                 target_vocab_size=12, embeddings_size=8, max_contexts=6,
                 dropout_keep_rate=1.0)


def _batch(b=16):
    r = np.random.default_rng(0)
    C = DIMS.max_contexts
    mask = np.ones((b, C), np.float32)
    mask[0, 3:] = 0.0
    return tuple(jnp.asarray(a) for a in (
        r.integers(0, 12, (b,)).astype(np.int32),
        r.integers(0, 20, (b, C)).astype(np.int32),
        r.integers(0, 16, (b, C)).astype(np.int32),
        r.integers(0, 20, (b, C)).astype(np.int32),
        mask, np.ones((b,), np.float32)))


def test_pallas_train_step_matches_xla_train_step():
    params = init_params(jax.random.PRNGKey(0), DIMS)
    opt = optax.adam(0.01)
    batch = _batch()
    rng = jax.random.PRNGKey(1)

    step_x = make_train_step(DIMS, opt)
    p1, _, loss1 = step_x(jax.tree_util.tree_map(jnp.copy, params),
                          opt.init(params), batch, rng)
    step_p = make_train_step(DIMS, opt, use_pallas=True)
    p2, _, loss2 = step_p(jax.tree_util.tree_map(jnp.copy, params),
                          opt.init(params), batch, rng)

    np.testing.assert_allclose(float(loss1), float(loss2), rtol=1e-5)
    for k in p1:
        np.testing.assert_allclose(np.asarray(p1[k]), np.asarray(p2[k]),
                                   atol=1e-4, err_msg=k)


def _mesh_step_matches_single_device(dims, n_data=4):
    """A partitioned step with the Pallas kernels in it (GSPMD refuses
    to split a Mosaic call, so they sit in shard_map over the batch
    axes — parallel/sharding.shard_map_over_batch) must take the same
    step as one device: same loss, same params, replicated weights'
    gradients summed over the shards."""
    from code2vec_tpu.parallel.mesh import make_mesh
    from code2vec_tpu.parallel.sharding import (shard_batch,
                                                shard_opt_state,
                                                shard_params)
    params = init_params(jax.random.PRNGKey(0), dims)
    opt = optax.adam(0.01)
    batch = _batch()
    rng = jax.random.PRNGKey(1)
    step_1 = make_train_step(dims, opt, use_pallas=True)
    p1, _, loss1 = step_1(jax.tree_util.tree_map(jnp.copy, params),
                          opt.init(params), batch, rng)
    mesh = make_mesh(n_data, 1, 1, devices=jax.devices()[:n_data])
    step_m = make_train_step(dims, opt, use_pallas=True, mesh=mesh)
    sp = shard_params(mesh, jax.tree_util.tree_map(jnp.copy, params))
    p2, _, loss2 = step_m(
        sp, shard_opt_state(mesh, opt.init(params), sp),
        shard_batch(mesh, tuple(np.asarray(a) for a in batch)), rng)
    np.testing.assert_allclose(float(loss1), float(loss2), rtol=1e-5)
    for (k1, a), (_k2, b) in zip(
            jax.tree_util.tree_flatten_with_path(p1)[0],
            jax.tree_util.tree_flatten_with_path(p2)[0]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5,
                                   err_msg=jax.tree_util.keystr(k1))


def test_pallas_pool_inside_a_partitioned_step():
    _mesh_step_matches_single_device(DIMS)


def test_fused_mha_inside_a_partitioned_step():
    import dataclasses
    _mesh_step_matches_single_device(dataclasses.replace(
        DIMS, encoder_type="transformer", xf_layers=1, xf_heads=2))
