"""The embedding gather and its scatter stop at the end of each bag
(ISSUE 29; data/staircase.py has the division of labour):

1. `encoder.embed_contexts` with a staircase equals `embed_contexts`
   without, bit for bit, and the table gradients agree to the order of
   addition, on every batch the producer's check lets through;
2. `BinaryShardReader.order_by_length` moves every per-row array of a
   whole batch by one permutation, longest first in each device's block,
   and nothing else is ever ordered;
3. the staircase is a function of the shard's multiset of lengths;
4. the train step runs the staircase program exactly when the check
   says the batch fits;
5. `rows_kept` (ISSUE 33): the rows a column's rectangle keeps bound,
   in every batch that fits, the rows with a valid slot from that
   column on;
6. `query_blocks` and `attn_pairs` (ISSUE 35): the query blocks the
   softmax mixers' core runs over hold every causal pair of valid slots
   of a batch that fits, the pairs are a brute-force count, and the
   producer leaves them on the batches of an encoder that scores by
   block (over a mesh the core stays whole, and the count says so).
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from code2vec_tpu.config import Config
from code2vec_tpu.data import staircase as st
from code2vec_tpu.data.reader import BinaryShardReader, open_reader
from code2vec_tpu.models import encoder
from code2vec_tpu.models.encoder import ModelDims, init_params
from code2vec_tpu.models.jax_model import Code2VecModel
from code2vec_tpu.training.steps import (TrainBatch, _by_fit,
                                         make_train_step)
from tests.helpers import load_tiny_vocabs

V, E, C, B = 61, 8, 16, 24
# a fifth of the bags longer than 8 slots: 24 rows keep 24 and 16
POPULATION = np.array([1, 2, 3, 4, 5, 6, 7, 8] * 4 + [9, 11, 13, 16] * 2)
STAIRS = st.from_lengths(POPULATION, B, C)


def batch_of(lengths) -> tuple:
    """(src, pth, dst) `[len(lengths), C]` ids in 1..V-1, each bag
    filled from slot 0 to its length."""
    rng = np.random.default_rng(11)
    lengths = np.asarray(lengths)
    out = []
    for _ in range(3):
        ids = rng.integers(1, V, (len(lengths), C)).astype(np.int32)
        ids[np.arange(C)[None, :] >= lengths[:, None]] = 0
        out.append(ids)
    return tuple(out)


def case(name: str):
    """(ids, stairs, example weights): the batches ISSUE 29 names."""
    long_, short = [16, 13, 12, 11, 10, 9, 9, 9], [8, 8, 7, 6, 5, 5, 4, 4,
                                                   3, 3, 2, 2, 1, 1, 1, 1]
    weights = np.ones(B, np.float32)
    stairs = STAIRS
    if name == "fits":
        lengths = long_ + short
    elif name == "fills_its_rectangles":
        lengths = [16] * 16 + [8] * 8
    elif name == "unordered":
        lengths = (long_ + short)[::-1]
    elif name == "one_bag_too_long":
        lengths = [16] * 17 + [8] * 7
    elif name == "all_pad":
        lengths = [0] * B
    elif name == "padding_rows_last":
        lengths = long_ + short[:11] + [0] * 5
        weights[-5:] = 0.0
    elif name == "odd_rows":         # 21 rows: not a multiple of the step
        lengths, weights = (long_ + short)[:21], weights[:21]
        stairs = st.from_lengths(POPULATION, 21, C)
    return batch_of(lengths), stairs, weights


FITTING = ["fits", "fills_its_rectangles", "all_pad", "padding_rows_last",
           "odd_rows"]
NOT_FITTING = ["unordered", "one_bag_too_long"]


def test_the_cases_are_what_their_names_say():
    assert STAIRS == ((0, 24), (8, 16))
    assert case("odd_rows")[1] == ((0, 21), (8, 16))
    for name in FITTING + NOT_FITTING:
        ids, stairs, _ = case(name)
        assert st.fits(stairs, ids) == (name in FITTING)


def tables(dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    return {"token_emb": jax.random.normal(k1, (V, E)).astype(dtype),
            "path_emb": jax.random.normal(k2, (V, E)).astype(dtype)}


def embed(params, ids, stairs, mesh=None):
    return encoder.embed_contexts(params, *ids, jax.random.PRNGKey(5), 0.75,
                                  jnp.float32, stairs, mesh)


# ---- 1. the take -----------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("name", FITTING)
def test_staircase_take_is_the_full_take_bit_for_bit(name, dtype):
    ids, stairs, _ = case(name)
    params = tables(dtype)
    full = jax.jit(lambda p: embed(p, ids, None))
    taken = jax.jit(lambda p: embed(p, ids, stairs))
    want = full(params)
    np.testing.assert_array_equal(np.asarray(taken(params)),
                                  np.asarray(want))

    # the gradients: equal updates added in another order
    w = jax.random.normal(jax.random.PRNGKey(9), want.shape)

    def grads(stairs, w):
        grad = jax.jit(jax.grad(
            lambda p: jnp.sum(embed(p, ids, stairs) * w)))
        return grad(params)

    g_want, g_got = grads(None, w), grads(stairs, w)
    # what a row's terms add up to in size: the same sum over |w|
    size = grads(None, jnp.abs(w))
    for k in params:
        assert g_got[k].dtype == g_want[k].dtype == params[k].dtype
        a, b, s = (np.asarray(x.astype(jnp.float32))
                   for x in (g_got[k], g_want[k], size[k]))
        tol = 1e-6 if dtype == jnp.float32 else 2.0 ** -6
        assert np.all(np.abs(a - b) <= tol * (s + 1.0)), k


def test_staircase_take_over_a_mesh_is_each_devices_own():
    """Four blocks of the fitting batch side by side: every device
    takes its own block's rectangles."""
    from code2vec_tpu.parallel.mesh import make_mesh
    from code2vec_tpu.parallel.sharding import shard_batch, shard_params
    ids, stairs, _ = case("fits")
    mesh = make_mesh(4, 1, 1, devices=jax.devices()[:4])
    params = shard_params(mesh, tables(jnp.float32))
    ids4 = shard_batch(mesh, tuple(np.tile(a, (4, 1)) for a in ids))
    full = jax.jit(lambda p, i: embed(p, i, None))
    taken = jax.jit(lambda p, i: embed(p, i, stairs, mesh))
    want = full(params, ids4)
    np.testing.assert_array_equal(np.asarray(taken(params, ids4)),
                                  np.asarray(want))
    w = jax.random.normal(jax.random.PRNGKey(9), want.shape)
    grad_full = jax.jit(jax.grad(
        lambda p, i: jnp.sum(embed(p, i, None) * w)))
    grad_taken = jax.jit(jax.grad(
        lambda p, i: jnp.sum(embed(p, i, stairs, mesh) * w)))
    g_want, g_got = grad_full(params, ids4), grad_taken(params, ids4)
    for k in params:
        np.testing.assert_allclose(np.asarray(g_got[k]),
                                   np.asarray(g_want[k]),
                                   rtol=1e-5, atol=1e-5)


def test_without_a_staircase_the_lowered_program_is_the_old_one():
    """Evaluation, prediction and serving pass no staircase: what they
    lower to does not know the argument exists."""
    ids = case("fits")[0]
    params = tables(jnp.float32)

    def old(params, src, pth, dst):
        # `embed_contexts` as it stood before ISSUE 29
        with jax.named_scope("c2v/embed_gather"):
            rows = [encoder.take_rows(params, "token_emb", src),
                    encoder.take_rows(params, "path_emb", pth),
                    encoder.take_rows(params, "token_emb", dst)]
        with jax.named_scope("c2v/encode"):
            return jnp.concatenate(rows, axis=-1).astype(jnp.bfloat16)

    def new(params, src, pth, dst):
        return encoder.embed_contexts(params, src, pth, dst, None, 1.0,
                                      jnp.bfloat16)

    def text(fn):
        return jax.jit(fn).lower(params, *ids).as_text()

    assert text(new).replace("jit_new", "jit_old") == text(old)


# ---- 2. the order ----------------------------------------------------------

def write_shard(prefix: str, lengths, seed: int = 0) -> np.ndarray:
    """A binary shard (data/binarize.py's layout) whose row `i` has the
    label `i` and a bag of `lengths[i]` contexts; returns its matrix."""
    rng = np.random.default_rng(seed)
    n = len(lengths)
    rows = np.zeros((n, 1 + 3 * C), np.int32)
    rows[:, 0] = np.arange(n)
    live = np.arange(C)[None, :] < np.asarray(lengths)[:, None]
    for j in range(3):
        rows[:, 1 + j * C:1 + (j + 1) * C][live] = rng.integers(
            2, V, int(live.sum()))
    rows.tofile(prefix + ".bin")
    with open(prefix + ".bin.json", "w") as f:
        json.dump({"num_examples": n, "max_contexts": C, "pad_index": 0}, f)
    with open(prefix + ".bin.targets", "w") as f:
        f.write("".join(f"name|{i}\n" for i in range(n)))
    return rows


SHARD_ROWS = 3 * 32 + 5        # three whole batches of 32 and a short one


@pytest.fixture(scope="module")
def shard(tmp_path_factory):
    prefix = str(tmp_path_factory.mktemp("shard") / "s")
    lengths = np.random.default_rng(2).choice(POPULATION, SHARD_ROWS)
    return prefix, write_shard(prefix, lengths)


def readers(prefix, groups, **kw):
    plain = BinaryShardReader(prefix, 32, seed=7, keep_strings=True, **kw)
    ordered = BinaryShardReader(prefix, 32, seed=7, keep_strings=True, **kw)
    ordered.order_by_length(groups)
    return plain, ordered


@pytest.mark.parametrize("groups", [1, 2, 4])
def test_ordering_moves_every_array_by_one_permutation(shard, groups):
    prefix, matrix = shard
    plain, ordered = readers(prefix, groups, shuffle=True)
    for _epoch in range(2):
        for a, b in zip(plain, ordered):
            if a.num_valid_examples < 32:
                continue
            # membership is the shuffled permutation's, untouched
            assert sorted(a.target_index) == sorted(b.target_index)
            # the label is the row's number in the shard: every array
            # of a row moved with it
            rows = matrix[b.target_index]
            np.testing.assert_array_equal(b.path_source_token_indices,
                                          rows[:, 1:1 + C])
            np.testing.assert_array_equal(b.path_indices,
                                          rows[:, 1 + C:1 + 2 * C])
            np.testing.assert_array_equal(b.path_target_token_indices,
                                          rows[:, 1 + 2 * C:])
            np.testing.assert_array_equal(b.context_valid_mask,
                                          (b.path_indices != 0))
            assert b.target_strings == [f"name|{i}" for i in b.target_index]
            for arr in b[1:4]:
                assert arr.flags["C_CONTIGUOUS"] and arr.dtype == np.int32
            # longest first in each device's block, equal loads
            lengths = (b.path_indices != 0).sum(axis=1)
            blocks = lengths.reshape(groups, -1)
            assert (np.diff(blocks, axis=1) <= 0).all()
            loads = blocks.sum(axis=1)
            assert loads.max() - loads.min() <= C
            # stable: equal bags keep the file order they had
            if groups == 1:
                for length in np.unique(lengths):
                    same = b.target_index[lengths == length]
                    assert (np.diff(same) > 0).all()


def test_a_short_batch_and_an_unasked_reader_keep_file_order(shard):
    prefix, matrix = shard
    plain, ordered = readers(prefix, 2, shuffle=True)
    (a,) = [x for x in plain if x.num_valid_examples < 32]
    (b,) = [x for x in ordered if x.num_valid_examples < 32]
    assert b.num_valid_examples == 5
    for x, y in zip(a[:5], b[:5]):
        np.testing.assert_array_equal(x, y)
    assert a.target_strings == b.target_strings
    # evaluation opens its reader with no shuffle and never asks
    for i, batch in enumerate(BinaryShardReader(prefix, 32)):
        nv = batch.num_valid_examples
        np.testing.assert_array_equal(batch.target_index[:nv],
                                      np.arange(32 * i, 32 * i + nv))


def test_the_check_reads_all_three_arrays_and_nothing_inside():
    """Every id outside the rectangles is PAD, whichever array holds
    it; inside them anything goes, holes too; a block a device gets has
    to fit by itself."""
    ids = [a.copy() for a in case("fits")[0]]
    assert st.fits(STAIRS, ids)
    ids[1][3, 2] = 0                # a hole inside the first rectangle
    ids[0][20, 7] = 5               # a lone token beside an empty path
    assert st.fits(STAIRS, ids)
    for which, (row, col) in enumerate([(16, 8), (23, 15), (17, 12)]):
        bad = [a.copy() for a in ids]
        bad[which][row, col] = 7    # outside the second rectangle
        assert not st.fits(STAIRS, bad)
    two = [np.concatenate([a, a]) for a in ids]
    assert st.fits(STAIRS, two, groups=2) and not st.fits(STAIRS, two)
    assert not st.fits(((0, 48), (8, 32)), ids)     # another batch's
    assert not st.fits(STAIRS, [a[:23] for a in ids], groups=2)


# ---- 3. the staircase from a shard ------------------------------------------

def test_same_multiset_in_another_order_gives_the_same_staircase(tmp_path):
    lengths = np.random.default_rng(4).choice(POPULATION, 500)
    tuples = []
    for seed in (0, 1):
        prefix = str(tmp_path / f"s{seed}")
        write_shard(prefix, np.random.default_rng(seed).permutation(lengths),
                    seed)
        reader = BinaryShardReader(prefix, 64)
        got = st.shard_lengths(reader.data, C, 0)
        assert sorted(got) == sorted(lengths)
        tuples.append(st.from_lengths(got, 64, C))
    assert tuples[0] == tuples[1] == ((0, 64), (8, 32))
    assert st.area(tuples[0], C) == 64 * 8 + 32 * 8


def test_a_large_shard_is_read_by_a_stride(monkeypatch):
    monkeypatch.setattr(st, "_WHOLE_SHARD_ROWS", 100)
    monkeypatch.setattr(st, "_SAMPLE_ROWS", 50)
    data = np.zeros((1000, 1 + 3 * C), np.int32)
    data[::20, 1 + C:1 + C + 4] = 3         # the rows the stride visits
    got = st.shard_lengths(data, C, 0)
    assert len(got) == 50 and (got == 4).all()


def test_full_bags_give_the_whole_rectangle():
    stairs = st.from_lengths(np.full(300, C), 64, C)
    assert stairs == ((0, 64),)
    assert st.area(stairs, C) == 64 * C


def test_java_large_lengths_give_six_rectangles_of_half_the_slots():
    """The benchmark corpus's law at 8,192 rows a device."""
    stairs = st.from_lengths(java_large_lengths(), 8192, 200)
    assert [first for first, _ in stairs] == [0, 32, 64, 96, 128, 160]
    kept = [k for _, k in stairs]
    assert kept[0] == 8192 and all(k % 256 == 0 for k in kept)
    assert kept == sorted(kept, reverse=True)
    assert 0.45 < st.area(stairs, 200) / (8192 * 200) < 0.55


def java_large_lengths(n=200_000):
    """The benchmark corpus's law: lognormal, median 60, sigma 1,
    clipped to 1-200."""
    z = np.random.default_rng(0).standard_normal(n)
    return np.clip(np.rint(60 * np.exp(z)), 1, 200)


# ---- 4. the choice of the step ----------------------------------------------

def test_by_fit_runs_the_program_the_batch_names():
    ran = []

    class Program:
        def __init__(self, name):
            self.name = name

        def __call__(self, params, opt_state, batch, rng):
            ran.append((self.name, type(batch)))

        def lower(self, *args):
            return self.name

    step = _by_fit(Program("stairs"), Program("full"))
    assert step.lower() == "full"
    arrays = (np.zeros(2),) * 6
    step(None, None, TrainBatch(arrays, True, 10), None)
    step(None, None, TrainBatch(arrays, False, 12), None)
    step(None, None, arrays, None)          # a caller that never checks
    assert ran == [("stairs", tuple), ("full", tuple), ("full", tuple)]
    batch = TrainBatch(arrays, True, 10)
    assert batch.gather_slots == 10 and len(batch) == 6
    # to jit it is the plain tuple, whatever it was marked
    assert jax.tree_util.tree_structure(batch).num_leaves == 6
    assert type(jax.tree_util.tree_map(lambda x: x, batch)) is tuple


DIMS = ModelDims(token_vocab_size=V, path_vocab_size=V, target_vocab_size=13,
                 embeddings_size=E, max_contexts=C)


@pytest.mark.parametrize("name", FITTING + NOT_FITTING)
def test_train_step_takes_the_staircase_exactly_when_the_batch_fits(name):
    ids, stairs, weights = case(name)
    rows = len(weights)
    fits = st.fits(stairs, ids)
    labels = np.arange(rows, dtype=np.int32) % 13

    def arrays():
        return (labels, *ids, (ids[1] != 0).astype(np.float32), weights)

    optimizer = optax.sgd(0.1)
    both = make_train_step(DIMS, optimizer, staircase=stairs)
    full = make_train_step(DIMS, optimizer)

    def state():
        params = init_params(jax.random.PRNGKey(0), DIMS)
        return params, optimizer.init(params)

    key = jax.random.PRNGKey(4)
    got = both(*state(), TrainBatch(arrays(), fits, 0), key)
    want = full(*state(), arrays(), key)
    assert both.staircase_step._cache_size() == int(fits)
    assert both.full_step._cache_size() == int(not fits)
    # the forward is bit for bit, so the loss is; the update differs by
    # the order of addition at most
    assert float(got[2]) == float(want[2])
    for k in want[0]:
        np.testing.assert_allclose(np.asarray(got[0][k]),
                                   np.asarray(want[0][k]),
                                   rtol=1e-5, atol=1e-6)
    # and a batch nobody marked runs the full step whatever it holds
    both(*state(), arrays(), key)
    assert both.full_step._cache_size() == 1


# ---- the model: who orders, who checks -------------------------------------

@pytest.fixture(scope="module")
def dataset(tmp_path_factory):
    """A binary training shard of 8 whole batches of 128 and a short
    one, with the vocabularies of the tiny text dataset."""
    from tests.helpers import build_tiny_dataset
    d = str(tmp_path_factory.mktemp("data"))
    prefix = build_tiny_dataset(d, n_train=64, n_val=8, n_test=8,
                                max_contexts=C)
    vocabs = load_tiny_vocabs(prefix)
    n = 8 * 128 + 9
    lengths = np.random.default_rng(5).choice(POPULATION, n)
    rng = np.random.default_rng(6)
    rows = np.zeros((n, 1 + 3 * C), np.int32)
    rows[:, 0] = rng.integers(2, vocabs.target_vocab.size, n)
    live = np.arange(C)[None, :] < lengths[:, None]
    for j, size in enumerate((vocabs.token_vocab.size,
                              vocabs.path_vocab.size,
                              vocabs.token_vocab.size)):
        rows[:, 1 + j * C:1 + (j + 1) * C][live] = rng.integers(
            2, size, int(live.sum()))
    rows.tofile(prefix + ".train.bin")
    with open(prefix + ".train.bin.json", "w") as f:
        json.dump({"num_examples": n, "max_contexts": C, "pad_index": 0}, f)
    return prefix


def model_of(prefix, data_axis, **kw):
    cfg = Config(MAX_CONTEXTS=C, MAX_TOKEN_VOCAB_SIZE=1000,
                 MAX_PATH_VOCAB_SIZE=1000, MAX_TARGET_VOCAB_SIZE=1000,
                 DEFAULT_EMBEDDINGS_SIZE=16, TRAIN_BATCH_SIZE=128,
                 TEST_BATCH_SIZE=32, NUM_TRAIN_EPOCHS=1, USE_BF16=False,
                 MESH_DATA_AXIS=data_axis, MESH_MODEL_AXIS=1)
    cfg.train_data_path = prefix
    cfg.VERBOSE_MODE = 0
    for k, v in kw.items():
        setattr(cfg, k, v)
    return Code2VecModel(cfg)


@pytest.mark.parametrize("data_axis", [1, 4])
def test_model_orders_checks_and_chooses(dataset, data_axis, monkeypatch):
    model = model_of(dataset, data_axis)
    rows = 128 // data_axis
    stairs = model._staircase
    assert model._stair_groups == data_axis
    assert stairs is not None and stairs[0] == (0, rows)
    # the twin runs today's step on the batches the first one was fed
    monkeypatch.setattr(Code2VecModel, "_training_staircase",
                        lambda self: (1, None))
    twin = model_of(dataset, data_axis)
    assert twin._staircase is None

    reader = open_reader(model.config.data_path("train"), model.vocabs, C,
                         128, shuffle=True, seed=3)
    plain = list(open_reader(model.config.data_path("train"), model.vocabs,
                             C, 128, shuffle=True, seed=3))
    seen = []
    for i, (dev, host) in enumerate(model._train_infeed(reader)):
        whole = host.num_valid_examples == 128
        assert isinstance(dev, TrainBatch) and dev.fits == whole
        assert dev.gather_slots == (
            data_axis * st.area(stairs, C) if whole
            else host.num_valid_examples * C)
        if not whole:               # never reordered
            np.testing.assert_array_equal(host.target_index,
                                          plain[i].target_index)
        # the reference's batch is the device's batch
        for got, want in zip(dev, model._host_batch_arrays(host)):
            np.testing.assert_array_equal(np.asarray(got), want)
        key = jax.random.fold_in(model.rng, i)
        # the full program is first needed by the short batch
        assert model._train_step.full_step._cache_size() == 0
        model.params, model.opt_state, loss = model._train_step(
            model.params, model.opt_state, dev, key)
        twin.params, twin.opt_state, want = twin._train_step(
            twin.params, twin.opt_state, tuple(dev), key)
        # the first forward is bit for bit; from then on the two states
        # stand an order of addition apart
        np.testing.assert_allclose(float(loss), float(want),
                                   rtol=0 if i == 0 else 1e-3)
        seen.append(whole)
    assert seen == [True] * 8 + [False]
    assert model._train_step.full_step._cache_size() == 1
    for k in ("token_emb", "path_emb", "transform"):
        # the tables are bf16: a unit in the last place here and there
        a, b = (np.asarray(m.params[k]).astype(np.float32)
                for m in (model, twin))
        np.testing.assert_allclose(a, b, rtol=2.0 ** -6, atol=1e-3)
    # evaluation and prediction batches carry no mark and no order
    assert type(model._device_batch(plain[0])) is tuple


@pytest.mark.parametrize("why,kw", [
    ("int8 tables", dict(TABLES_DTYPE="int8")),
    ("the sparse step", dict(SPARSE_EMBEDDING_UPDATES=True,
                             EMBEDDING_OPTIMIZER="adam",
                             LR_SCHEDULE="constant")),
    ("tables split over the model axis", dict(MESH_MODEL_AXIS=2)),
    ("a batch the devices do not divide", dict(TRAIN_BATCH_SIZE=129)),
])
def test_no_staircase_where_todays_step_runs_alone(dataset, why, kw):
    model = model_of(dataset, 2, **kw)
    assert model._staircase is None, why
    assert not hasattr(model._train_step, "staircase_step")


def test_a_text_corpus_gets_no_staircase(tmp_path):
    from tests.helpers import build_tiny_dataset
    prefix = build_tiny_dataset(str(tmp_path), n_train=64, n_val=8,
                                n_test=8, max_contexts=C)
    assert not os.path.exists(prefix + ".train.bin.json")
    model = model_of(prefix, 1)
    assert model._staircase is None


# ---- 5. the rows a column keeps ---------------------------------------------

@pytest.mark.parametrize("column,kept,where", [
    (0, 24, "the first column"), (7, 24, "inside the first rectangle"),
    (8, 16, "on a boundary"), (11, 16, "inside"), (12, 4, "on the last"),
    (15, 4, "the last column: the last rectangle runs to the end"),
])
def test_rows_kept_is_the_rectangle_that_holds_the_column(column, kept,
                                                          where):
    assert st.rows_kept(((0, 24), (8, 16), (12, 4)), column) == kept, where
    assert st.rows_kept(((0, 24),), column) == 24


def test_the_cells_law_at_128_rows_bounds_the_chunks_at_128_88_48_40():
    """`qwen3next-train-corpus`: 128 methods of 200 slots a device,
    chunks of 64 slots (ops/delta_rule.CHUNK) from slots 0, 64, 128
    and 192: 304 of 512 method-chunks."""
    stairs = st.from_lengths(java_large_lengths(), 128, 200)
    assert stairs == ((0, 128), (32, 120), (64, 88), (96, 64), (128, 48),
                      (160, 40))
    assert [st.rows_kept(stairs, c) for c in (0, 64, 128, 192)] == \
        [128, 88, 48, 40]


@pytest.mark.parametrize("seed", range(6))
def test_a_batch_that_fits_has_no_valid_slot_under_a_chunks_bound(seed):
    """What the bounded scan rests on: whenever the producer's check
    passes, the mask the reader makes (`pth != 0`) is all PAD from each
    chunk's first column on, in every row from that column's bound
    down; a batch with such a slot does not fit."""
    rng = np.random.default_rng(seed)
    population = np.clip(np.rint(rng.lognormal(np.log(5), 1.0, 400)), 1, C)
    rows, chunk = 32, 4 + seed % 3          # chunks on and off boundaries
    stairs = st.from_lengths(population, rows, C)
    bound = [st.rows_kept(stairs, first) for first in range(0, C, chunk)]
    assert bound == sorted(bound, reverse=True) and bound[0] <= rows
    fitted = 0
    for _ in range(40):
        lengths = rng.choice(population, rows).astype(int)
        lengths = lengths[st.length_order(lengths)]
        ids = batch_of(lengths)
        mask = ids[1] != 0
        clear = all(not mask[kept:, n * chunk:].any()
                    for n, kept in enumerate(bound))
        if st.fits(stairs, ids):
            fitted += 1
            assert clear
        # one valid slot just under a bound: no fit
        n = int(rng.integers(len(bound)))
        if bound[n] < rows:
            ids[1][bound[n], n * chunk] = 7
            assert not st.fits(stairs, ids)
    assert fitted >= 20


# ---- 6. the query blocks of the softmax mixers' core ------------------------

CELL_STAIRS = ((0, 128), (32, 120), (64, 88), (96, 64), (128, 48), (160, 40))


@pytest.mark.parametrize("stairs,slots,width,blocks", [
    (CELL_STAIRS, 200, 1, ((0, 32, 128), (32, 64, 120), (64, 96, 88),
                           (96, 128, 64), (128, 160, 48), (160, 200, 40))),
    (CELL_STAIRS, 200, 64, ((0, 64, 128), (64, 128, 88), (128, 200, 48))),
    (CELL_STAIRS, 200, 96, ((0, 96, 128), (96, 200, 64))),
    (CELL_STAIRS, 200, 200, ((0, 200, 128),)),
    (((0, 8),), 20, 1, ((0, 20, 8),)),
    (((0, 8),), 20, 64, ((0, 20, 8),)),
    (((0, 8), (8, 5), (16, 2)), 20, 1, ((0, 8, 8), (8, 16, 5), (16, 20, 2))),
    (((0, 8), (8, 5), (16, 2)), 20, 8, ((0, 8, 8), (8, 20, 5))),
], ids=["cell_each", "cell_64", "cell_96", "cell_whole", "one", "one_64",
        "narrow_last_each", "narrow_last_joins"])
def test_query_blocks_are_rectangles_or_neighbours_under_the_firsts_rows(
        stairs, slots, width, blocks, monkeypatch):
    monkeypatch.setattr(st, "_BLOCK_SLOTS", width)
    assert st.query_blocks(stairs, slots) == blocks
    # side by side from slot 0 to the last, rows never rising
    assert blocks[0][0] == 0 and blocks[-1][1] == slots
    assert all(a[1] == b[0] and a[2] >= b[2]
               for a, b in zip(blocks, blocks[1:]))


def _scored(stairs, rows, slots) -> np.ndarray:
    """[rows, queries, keys] bool: the pairs the blocks score, marked
    one by one."""
    scored = np.zeros((rows, slots, slots), bool)
    for first, end, kept in st.query_blocks(stairs, slots):
        for b in range(kept):
            for q in range(first, end):
                for c in range(end):
                    assert not scored[b, q, c]
                    scored[b, q, c] = True
    return scored


@pytest.mark.parametrize("width", [1, 5, 9])
def test_attn_pairs_is_a_brute_force_count(width, monkeypatch):
    monkeypatch.setattr(st, "_BLOCK_SLOTS", width)
    for stairs, rows, slots in ((STAIRS, B, C), (((0, 8),), 8, 20),
                                (((0, 8), (4, 7), (8, 5), (12, 3), (16, 2)),
                                 8, 20)):
        assert st.attn_pairs(st.query_blocks(stairs, slots)) == int(
            _scored(stairs, rows, slots).sum())
    monkeypatch.setattr(st, "_BLOCK_SLOTS", 1)
    assert st.attn_pairs(st.query_blocks(CELL_STAIRS, 200)) == 1_475_072
    assert st.attn_pairs(st.query_blocks(((0, 128),), 200)) \
        == 128 * 200 * 200


@pytest.mark.parametrize("width", [1, 9])
@pytest.mark.parametrize("name", FITTING)
def test_the_blocks_hold_every_causal_pair_of_a_batch_that_fits(
        name, width, monkeypatch):
    """What a valid query may see (the valid slots up to its own) lies
    inside its block's keys, in every batch the check lets through."""
    monkeypatch.setattr(st, "_BLOCK_SLOTS", width)
    ids, stairs, _ = case(name)
    assert st.fits(stairs, ids)
    valid = np.asarray(ids[1]) != 0
    slot = np.arange(C)
    causal = (slot[None, :] <= slot[:, None])[None] \
        & valid[:, :, None] & valid[:, None, :]
    assert not (causal & ~_scored(stairs, len(valid), C)).any()


@pytest.fixture(scope="module")
def block_model_config(tmp_path_factory):
    from tests.test_lfm2_moe import BLOCK
    path = tmp_path_factory.mktemp("block") / "block.json"
    path.write_text(json.dumps(BLOCK))
    return str(path)


@pytest.mark.parametrize("data_axis", [1, 2])
def test_model_counts_the_pairs_its_softmax_layers_score(
        dataset, block_model_config, data_axis, monkeypatch):
    """An encoder whose mixers score by query block (its spec says so):
    every training batch carries the pairs a head of one softmax layer
    of its step scores, the blocks' where it fits on one device and
    rows x contexts^2 where it does not or a mesh keeps the core whole,
    and the span holds them; the steps' losses are those of a twin
    that runs today's step. The bag's batches carry none."""
    from code2vec_tpu.obs import memory_tracer

    monkeypatch.setattr(st, "_BLOCK_SLOTS", 1)      # each rectangle a block
    kw = dict(ENCODER_TYPE="lfm2_moe", BLOCK_CONFIG=block_model_config,
              LR_SCHEDULE="constant")
    model = model_of(dataset, data_axis, **kw)
    stairs = model._staircase
    assert stairs is not None and model._stair_groups == data_axis
    bag = model_of(dataset, 1)
    monkeypatch.setattr(Code2VecModel, "_training_staircase",
                        lambda self: (1, None))
    twin = model_of(dataset, data_axis, **kw)
    reader = open_reader(model.config.data_path("train"), model.vocabs, C,
                         128, shuffle=True, seed=3)
    before = len(memory_tracer().records("infeed/transfer"))
    pairs, slots = [], []
    for i, (dev, host) in enumerate(model._train_infeed(reader)):
        whole = host.num_valid_examples == 128
        assert dev.fits == whole
        assert dev.attn_pairs == (
            st.attn_pairs(st.query_blocks(stairs, C))
            if whole and data_axis == 1
            else host.num_valid_examples * C * C)
        pairs.append(dev.attn_pairs)
        # ISSUE 37: and the positions its layers' feed-forward half
        # runs over, by `seq_block.ff_rectangles`
        assert dev.ff_slots == (
            st.area(stairs, C) if whole and data_axis == 1
            else host.num_valid_examples * C)
        slots.append(dev.ff_slots)
        if i in (0, 1, 8):      # two that fit and the short one
            key = jax.random.fold_in(model.rng, i)
            model.params, model.opt_state, loss = model._train_step(
                model.params, model.opt_state, dev, key)
            twin.params, twin.opt_state, want = twin._train_step(
                twin.params, twin.opt_state, tuple(dev), key)
            np.testing.assert_allclose(float(loss), float(want), rtol=1e-3)
    assert len(pairs) == 9
    assert (pairs[0] < 128 * C * C) == (data_axis == 1)
    spans = memory_tracer().records("infeed/transfer")[before:]
    assert [s["attrs"]["attn_pairs"] for s in spans] == pairs
    assert [s["attrs"]["ff_slots"] for s in spans] == slots
    assert (slots[0] < 128 * C) == (data_axis == 1)
    dev, _host = next(iter(bag._train_infeed(open_reader(
        bag.config.data_path("train"), bag.vocabs, C, 128, shuffle=True,
        seed=3))))
    assert dev.fits and not hasattr(dev, "attn_pairs")
    assert not hasattr(dev, "ff_slots")
    assert not {"attn_pairs", "ff_slots"} & set(memory_tracer().records(
        "infeed/transfer")[-1]["attrs"])
    # the count asks the function the encoder compiles its step by
    from code2vec_tpu.models import seq_block
    monkeypatch.setattr(seq_block, "core_blocks", lambda *a: None)
    monkeypatch.setattr(seq_block, "ff_rectangles", lambda *a: None)
    dev, host = next(iter(model._train_infeed(open_reader(
        model.config.data_path("train"), model.vocabs, C, 128, shuffle=True,
        seed=3))))
    assert dev.fits and dev.attn_pairs == host.num_valid_examples * C * C
    assert dev.ff_slots == host.num_valid_examples * C


def test_core_blocks_is_none_with_no_staircase_or_rows_on_two_devices():
    from code2vec_tpu.models.seq_block import core_blocks
    from code2vec_tpu.parallel.mesh import make_mesh

    assert core_blocks(None, None, 200) is None
    assert core_blocks(CELL_STAIRS, None, 200) == st.query_blocks(
        CELL_STAIRS, 200)
    one = make_mesh(0, 1, devices=jax.devices()[:1])
    assert core_blocks(CELL_STAIRS, one, 200) == st.query_blocks(
        CELL_STAIRS, 200)
    two = make_mesh(0, 1, devices=jax.devices()[:2])
    assert core_blocks(CELL_STAIRS, two, 200) is None
