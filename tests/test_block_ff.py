"""The feed-forward half of a block's layer over the training staircase
(ISSUE 37; `models/seq_block.residual_layer`, `pack`, `lay_back`,
`ff_rectangles`):

a. on an ordered batch that fits, the staircase step's loss and every
   gradient leaf are the full step's;
b. a batch that does not fit runs the full step, bit for bit;
c. with no rectangles (no staircase, or the rows dealt to several
   devices) the train, eval and predict steps lower to the text they
   lowered to before the rectangles (a copy of the old layer stands
   here);
d. `pack` then `lay_back` is the identity on the rectangles and leaves
   `x` outside them;
e. the counts that leave the step (`moe/route`) are the same routing,
   under the row bound of the staircase's area.

The cases are the three block encoders of `tests/test_encoders.py`.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from code2vec_tpu.data import staircase as st
from code2vec_tpu.models import seq_block
from code2vec_tpu.models.encoder import get_encode_fn, init_params
from code2vec_tpu.ops.moe import row_bound
from code2vec_tpu.parallel.mesh import make_mesh
from code2vec_tpu.training import steps
from code2vec_tpu.training.steps import (TrainBatch, _make_loss_and_aux_fn,
                                         make_eval_step, make_predict_step,
                                         make_train_step)
from tests.test_encoders import (B, C, LENGTHS, STAIRS, V, dims_of,
                                 ordered_batch)
from tests.test_staircase import CELL_STAIRS

BLOCKS = ("lfm2_moe", "qwen3_next", "joyai_flash")
RECTANGLES = seq_block.ff_rectangles(STAIRS, None, C)
# experts held, routed and chosen a token in each of the tiny blocks
HELD, ROUTED, CHOSEN = 2, 4, 2


def test_the_cases_fit_and_the_rectangles_are_the_staircases_own():
    assert STAIRS == ((0, 24), (8, 16))
    assert RECTANGLES == ((0, 8, 24), (8, 16, 16))
    assert seq_block.ff_rectangles(CELL_STAIRS, None, 200) == (
        (0, 32, 128), (32, 64, 120), (64, 96, 88), (96, 128, 64),
        (128, 160, 48), (160, 200, 40))
    assert sum(kept * (end - first) for first, end, kept
               in seq_block.ff_rectangles(CELL_STAIRS, None, 200)) \
        == st.area(CELL_STAIRS, 200) == 15_936


def test_ff_rectangles_is_none_with_no_staircase_or_rows_on_two_devices():
    """`core_blocks`' condition: a staircase, and the batch's rows on
    one device."""
    assert seq_block.ff_rectangles(None, None, 200) is None
    one = make_mesh(0, 1, devices=jax.devices()[:1])
    assert seq_block.ff_rectangles(CELL_STAIRS, one, 200) == \
        seq_block.ff_rectangles(CELL_STAIRS, None, 200)
    two = make_mesh(0, 1, devices=jax.devices()[:2])
    assert seq_block.ff_rectangles(CELL_STAIRS, two, 200) is None
    # `fits` refuses a staircase that does not start at slot 0
    assert seq_block.ff_rectangles(((8, 24),), None, 16) is None


# ---- (d) pack and lay back ---------------------------------------------------

@pytest.mark.parametrize("stairs,rows,slots", [
    (((0, 5),), 8, 20),                 # one rectangle, three rows outside
    (CELL_STAIRS, 128, 200),            # the cells' six
    (((0, 8), (8, 5), (16, 2)), 8, 20),     # the first keeps every row
    (((0, 8),), 8, 20)],
    ids=["one_rectangle", "six_rectangles", "kept_is_B", "whole"])
def test_pack_then_lay_back_is_the_identity_on_the_rectangles(stairs, rows,
                                                              slots):
    rectangles = seq_block.ff_rectangles(stairs, None, slots)
    inside = np.zeros((rows, slots), bool)
    for first, end, kept in rectangles:
        inside[:kept, first:end] = True
    x = jax.random.normal(jax.random.PRNGKey(3), (rows, slots, 4))
    flat = seq_block.pack(x, rectangles)
    assert flat.shape == (1, st.area(stairs, slots), 4)
    # row by row inside a rectangle, rectangle by rectangle
    first, end, kept = rectangles[0]
    np.testing.assert_array_equal(
        np.asarray(flat[0, :end - first]), np.asarray(x[0, first:end]))
    back = seq_block.lay_back(flat, rectangles, rows)
    np.testing.assert_array_equal(
        np.asarray(back), np.asarray(x) * inside[..., None])
    mask = jnp.asarray(inside, jnp.float32)
    assert seq_block.pack(mask, rectangles).shape == (1, inside.sum())
    assert np.all(np.asarray(seq_block.pack(mask, rectangles)) == 1)

    # through the layer: the feed-forward's output lands on the
    # rectangles and a slot outside keeps x; so does its cotangent
    def layer(rectangles):
        return seq_block.residual_layer(
            0, norm=lambda t, scale: t * scale, mixer_scope="m",
            mixer=lambda h, layer: jnp.zeros_like(h),
            ff=lambda h, m, layer: (3.0 * h * m[..., None], None),
            mask=mask, rectangles=rectangles)

    scales = {"op_norm": jnp.ones(4), "ff_norm": jnp.full(4, 2.0)}
    out, counts = layer(rectangles)(x, scales)
    assert counts is None
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(x) * (1 + 6 * inside[..., None]),
        rtol=1e-6)
    whole, _ = layer(None)(x, scales)
    np.testing.assert_allclose(np.asarray(out), np.asarray(whole), rtol=1e-6)
    grad = jax.grad(lambda x: jnp.sum(layer(rectangles)(x, scales)[0]))(x)
    np.testing.assert_allclose(
        np.asarray(grad), np.broadcast_to(1 + 6 * inside[..., None], x.shape),
        rtol=1e-6)


# ---- (a), (b) the two programs of the train step -----------------------------

def _flat(tree) -> dict:
    return {jax.tree_util.keystr(path): np.asarray(leaf) for path, leaf
            in jax.tree_util.tree_leaves_with_path(tree)}


@pytest.mark.parametrize("name", BLOCKS)
def test_staircase_loss_and_every_gradient_leaf_are_the_full_steps(name):
    dims = dims_of(name)
    params = init_params(jax.random.PRNGKey(0), dims)
    batch = tuple(jnp.asarray(a) for a in ordered_batch())
    key = jax.random.PRNGKey(4)

    def run(stairs):
        loss_and_aux = _make_loss_and_aux_fn(
            dims, use_sampled_softmax=True, num_sampled=8,
            compute_dtype=jnp.float32, use_pallas=False, mesh=None,
            staircase=stairs)
        grad = jax.jit(jax.value_and_grad(loss_and_aux, has_aux=True))
        (loss, _aux), grads = grad(params, batch, key)
        return float(loss), _flat(grads)

    loss, grads = run(STAIRS)
    want_loss, want = run(None)
    assert loss == pytest.approx(want_loss, rel=1e-5)
    norms = {k: float(np.linalg.norm(v)) for k, v in want.items()}
    median = float(np.median(list(norms.values())))
    assert grads.keys() == want.keys()
    for k, g in want.items():
        assert float(np.linalg.norm(grads[k] - g)) <= \
            2e-4 * max(norms[k], median), k
    # every leaf of the feed-forward half takes a gradient on both sides
    ff = [k for k in want if any(n in k for n in (
        "ff_norm", "w1", "w2", "w3", "router", "shared"))]
    assert ff and all(norms[k] > 0 for k in ff)


# ---- (e) the counts that leave the step --------------------------------------

@pytest.mark.parametrize("name", BLOCKS)
def test_the_counts_are_the_same_routing_under_the_areas_row_bound(name):
    """`moe/route`'s columns (`seq_block.routed_experts`): the rows each
    held expert took and the valid tokens are the full step's, the row
    bound is `row_bound(area x K, ...)`."""
    dims = dims_of(name)
    params = init_params(jax.random.PRNGKey(0), dims)
    _labels, src, pth, dst, mask, _w = ordered_batch()

    def counts(stairs):
        aux = jax.jit(lambda p: get_encode_fn(dims)(
            p, src, pth, dst, jnp.asarray(mask), staircase=stairs)[2])
        return np.asarray(aux(params))

    aux, want = counts(STAIRS), counts(None)
    held, routed, chosen = HELD, ROUTED, CHOSEN
    assert aux.shape == want.shape and aux.shape[0] >= 1
    np.testing.assert_array_equal(aux[:, :held + 1], want[:, :held + 1])
    assert np.all(aux[:, :held].sum(axis=1) > 0)
    assert np.all(aux[:, held] == int(mask.sum()))
    area = st.area(STAIRS, C)
    assert area == 24 * 8 + 16 * 8 < B * C
    assert np.all(aux[:, held + 1] == row_bound(area * chosen, held, routed))
    assert np.all(want[:, held + 1]
                  == row_bound(B * C * chosen, held, routed))
    assert row_bound(area * chosen, held, routed) \
        < row_bound(B * C * chosen, held, routed)


@pytest.mark.parametrize("name", BLOCKS)
def test_a_batch_that_does_not_fit_runs_the_full_step_unchanged(name):
    dims = dims_of(name)
    optimizer = optax.sgd(0.1)
    kw = dict(use_sampled_softmax=True, num_sampled=8)
    both = make_train_step(dims, optimizer, staircase=STAIRS, **kw)
    alone = make_train_step(dims, optimizer, **kw)
    arrays = list(ordered_batch())
    lengths = LENGTHS.copy()
    lengths[20] = 9                 # a long bag below the second rectangle
    live = np.arange(C)[None, :] < lengths[:, None]
    rng = np.random.default_rng(5)
    for i in (1, 2, 3):
        arrays[i] = np.where(live, rng.integers(1, V - 8, (B, C)),
                             0).astype(np.int32)
    arrays[4] = live.astype(np.float32)
    assert not st.fits(STAIRS, arrays[1:4])
    key = jax.random.PRNGKey(4)

    def run(step, batch):
        params = init_params(jax.random.PRNGKey(0), dims)   # donated
        new, _state, loss = step(params, optimizer.init(params), batch, key)
        return float(loss), _flat(new)

    loss, after = run(both, TrainBatch(tuple(arrays), False, 0))
    want_loss, want = run(alone, tuple(arrays))
    assert loss == want_loss
    for k, leaf in want.items():
        np.testing.assert_array_equal(after[k], leaf, err_msg=k)


# ---- (c) no rectangles: the programs of before -------------------------------

def residual_layer_before_the_rectangles(i, *, norm, mixer_scope, mixer, ff,
                                         mask, ff_scope=None,
                                         rectangles=None):
    """`seq_block.residual_layer` as it stood before ISSUE 37 (its
    feed-forward closed over the encoder's mask; here it is handed the
    same one): what every program with no rectangles still lowers
    to."""
    assert rectangles is None
    ff_scope = f"c2v/blk_{i}" + (f"/{ff_scope}" if ff_scope else "")

    def run(x, layer):
        h = norm(x, layer["op_norm"])
        with jax.named_scope(f"c2v/blk_{i}/{mixer_scope}"):
            x = x + mixer(h, layer)
        h = norm(x, layer["ff_norm"])
        with jax.named_scope(ff_scope):
            out, counts = ff(h, mask, layer)
        return x + out, (None if counts is None
                         else jnp.sum(counts, axis=0))

    return jax.checkpoint(run)


@pytest.mark.parametrize("devices", [1, 2])
@pytest.mark.parametrize("name", BLOCKS)
def test_with_no_rectangles_the_steps_lower_to_the_old_programs(
        name, devices, monkeypatch):
    """No staircase on one device (the full step, eval, predict), and a
    staircase with the rows dealt to two devices (a device's rows are
    `STAIRS`' 24, the batch's 48)."""
    dims = dims_of(name)
    mesh = None if devices == 1 else make_mesh(
        devices, 1, devices=jax.devices()[:devices])
    stairs = None if devices == 1 else STAIRS
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), dims))
    optimizer = optax.sgd(0.1)
    state = jax.eval_shape(optimizer.init, params)
    rows = B * devices
    S = jax.ShapeDtypeStruct
    batch = (S((rows,), jnp.int32), *(S((rows, C), jnp.int32),) * 3,
             S((rows, C), jnp.float32), S((rows,), jnp.float32))
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))

    # the two programs bare, not behind the recorder of their counts
    monkeypatch.setattr(steps, "_recording", lambda step, recorder: step)

    def texts():
        train = make_train_step(dims, optimizer, use_sampled_softmax=True,
                                num_sampled=8, mesh=mesh, staircase=stairs)
        train = getattr(train, "staircase_step", train)
        return [train.lower(params, state, batch, key).as_text(),
                make_eval_step(dims, top_k=3, mesh=mesh).lower(
                    params, batch).as_text(),
                make_predict_step(dims, top_k=3, mesh=mesh).lower(
                    params, batch).as_text()]

    now = texts()
    residual_layer = seq_block.residual_layer
    monkeypatch.setattr(seq_block, "residual_layer",
                        residual_layer_before_the_rectangles)
    assert now == texts()
    if devices == 1:
        # and the staircase's step does pack: its text is another
        monkeypatch.setattr(seq_block, "residual_layer", residual_layer)
        packed = make_train_step(
            dims, optimizer, use_sampled_softmax=True, num_sampled=8,
            staircase=STAIRS).staircase_step.lower(
                params, state, batch, key).as_text()
        area = st.area(STAIRS, C)
        assert f"tensor<1x{area}x32xf32>" in packed
        assert f"tensor<1x{area}x32xf32>" not in now[0]


def test_ff_rectangles_alone_says_whether_the_layer_packs(monkeypatch):
    """The encoders ask `seq_block.ff_rectangles` and nothing else."""
    dims = dims_of("joyai_flash")
    params = init_params(jax.random.PRNGKey(0), dims)
    _labels, src, pth, dst, mask, _w = ordered_batch()

    def text():
        return jax.jit(lambda p: get_encode_fn(dims)(
            p, src, pth, dst, jnp.asarray(mask), staircase=STAIRS)[0]
        ).lower(params).as_text()

    area = st.area(STAIRS, C)
    assert f"tensor<1x{area}x32xf32>" in text()
    monkeypatch.setattr(seq_block, "ff_rectangles", lambda *a: None)
    assert f"tensor<1x{area}x32xf32>" not in text()
