"""Shared test fixtures: tiny synthetic extractor output + dataset build."""

from __future__ import annotations

import os
import random
import re

from code2vec_tpu.data import binarize as binarize_mod
from code2vec_tpu.data import preprocess as preprocess_mod
from code2vec_tpu.vocab.vocabularies import Code2VecVocabs

TOKENS = ["foo", "bar", "baz", "qux", "value", "name", "index", "count"]
PATHS = [str(h) for h in (123456, -98765, 424242, 1337, -777, 31415)]
TARGETS = ["get|value", "set|value", "get|name", "set|name", "add|item",
           "remove|item", "to|string", "is|empty"]


def make_raw_lines(n: int, seed: int = 0, max_ctx: int = 12):
    """Synthetic extractor-format lines: `target tok,path,tok ...` where
    the target is (weakly) recoverable from the contexts: target class k
    biases which tokens/paths appear."""
    rng = random.Random(seed)
    lines = []
    for _ in range(n):
        t_idx = rng.randrange(len(TARGETS))
        target = TARGETS[t_idx]
        n_ctx = rng.randint(1, max_ctx)
        ctxs = []
        for _ in range(n_ctx):
            # bias token/path choice by the target class so the model can
            # actually learn the mapping
            tok_a = TOKENS[(t_idx + rng.randrange(2)) % len(TOKENS)]
            tok_b = TOKENS[(t_idx * 3 + rng.randrange(2)) % len(TOKENS)]
            path = PATHS[t_idx % len(PATHS)] if rng.random() < 0.7 \
                else rng.choice(PATHS)
            ctxs.append(f"{tok_a},{path},{tok_b}")
        lines.append(target + " " + " ".join(ctxs))
    return lines


def float_scatters(text: str):
    """The result types of a lowered program's scatters over floats (a
    count by `bincount` scatters integers)."""
    types = re.findall(r'"stablehlo\.scatter"\(.*?\}\) : \(.*?\) -> '
                       r'(tensor<[^>]*>)', text, flags=re.DOTALL)
    return [t for t in types if not re.search(r"x[su]?i\d+>", t)]


def example_batch(seed: int, dims, batch: int):
    """Deterministic synthetic device-batch tuple in the train-step format
    (labels, src, pth, dst, mask, weights)."""
    import numpy as np
    r = np.random.default_rng(seed)
    C = dims.max_contexts
    labels = r.integers(0, dims.target_vocab_size, (batch,)).astype(np.int32)
    src = r.integers(0, dims.token_vocab_size, (batch, C)).astype(np.int32)
    pth = r.integers(0, dims.path_vocab_size, (batch, C)).astype(np.int32)
    dst = r.integers(0, dims.token_vocab_size, (batch, C)).astype(np.int32)
    mask = (r.random((batch, C)) > 0.3).astype(np.float32)
    mask[:, 0] = 1.0
    weights = np.ones((batch,), dtype=np.float32)
    return labels, src, pth, dst, mask, weights


def build_tiny_dataset(tmpdir: str, n_train: int = 256, n_val: int = 32,
                       n_test: int = 64, max_contexts: int = 16,
                       binarize: bool = False) -> str:
    """Write raw lines, run preprocess (+ optional binarize); returns the
    dataset prefix."""
    raw_train = os.path.join(tmpdir, "raw.train.txt")
    raw_val = os.path.join(tmpdir, "raw.val.txt")
    raw_test = os.path.join(tmpdir, "raw.test.txt")
    for path, n, seed in ((raw_train, n_train, 1), (raw_val, n_val, 2),
                          (raw_test, n_test, 3)):
        with open(path, "w") as f:
            f.write("\n".join(make_raw_lines(n, seed=seed)) + "\n")
    prefix = os.path.join(tmpdir, "tiny")
    preprocess_mod.main([
        "--train_data", raw_train, "--val_data", raw_val,
        "--test_data", raw_test, "--max_contexts", str(max_contexts),
        "--word_vocab_size", "1000", "--path_vocab_size", "1000",
        "--target_vocab_size", "1000", "--output_name", prefix])
    if binarize:
        binarize_mod.main(["--data", prefix,
                           "--max_contexts", str(max_contexts),
                           "--word_vocab_size", "1000",
                           "--path_vocab_size", "1000",
                           "--target_vocab_size", "1000"])
    return prefix


def load_tiny_vocabs(prefix: str) -> Code2VecVocabs:
    return Code2VecVocabs.load_from_dict_file(
        prefix + ".dict.c2v", 1000, 1000, 1000)


def sharded_eval_setup(dir_path: str):
    """The (dataset, Config) pair shared by the 2-process sharded-eval
    worker (tests/mp_worker.py) and its single-process oracle
    (tests/test_multihost.py) — one definition, so the comparison can
    never drift via config edits to only one side."""
    from code2vec_tpu.config import Config

    prefix = build_tiny_dataset(dir_path, n_train=48, n_val=8, n_test=8,
                                max_contexts=16)
    cfg = Config(MAX_CONTEXTS=16, MAX_TOKEN_VOCAB_SIZE=1000,
                 MAX_PATH_VOCAB_SIZE=1000, MAX_TARGET_VOCAB_SIZE=1000,
                 DEFAULT_EMBEDDINGS_SIZE=16, TRAIN_BATCH_SIZE=16,
                 TEST_BATCH_SIZE=8, USE_BF16=False,
                 LR_SCHEDULE="constant")
    cfg.train_data_path = prefix
    cfg.test_data_path = prefix + ".train.c2v"
    return cfg


# ---- the softmax mixers' core over a staircase (ISSUE 35) ----------------

# staircases of 8 rows x 20 slots: rectangles of uneven rows, the whole
# rectangle, and a last rectangle narrower than the others
STAIR_CASES = {
    "uneven": ((0, 8), (4, 7), (8, 5), (12, 3), (16, 2)),
    "one_rectangle": ((0, 8),),
    "narrow_last": ((0, 8), (8, 5), (16, 2)),
}


def staircase_mask(stairs, rows: int = 8, slots: int = 20):
    """The mask [rows, slots] float32 of a batch that fits `stairs`,
    longest bag first, the first bag of every rectangle filling it and
    the last bag one context long."""
    import numpy as np

    firsts = [first for first, _ in stairs] + [slots]
    inside = np.zeros((rows, slots), bool)
    for k, (first, kept) in enumerate(stairs):
        inside[:kept, first:firsts[k + 1]] = True
    reach = inside.sum(axis=1)
    lengths = np.maximum(1, reach - np.arange(rows) % 3)
    lengths[-1] = 1
    mask = (np.arange(slots)[None, :] < lengths[:, None]) & inside
    return mask.astype(np.float32)


def assert_staircase_mixer_is_the_whole(mixer, h, layer, stairs):
    """`mixer(h, mask, layer, stairs)` over a batch that fits `stairs`
    against `stairs=None`, float32: the output at every valid slot, zeros
    at the slots outside the query blocks (the rectangles, or the joined
    ones), and the gradients of `h` and of every leaf of `layer` under a
    loss that reads the valid slots."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from code2vec_tpu.data.staircase import query_blocks

    mask = staircase_mask(stairs, *h.shape[:2])
    inside = np.zeros(mask.shape, bool)
    for first, end, kept in query_blocks(stairs, mask.shape[1]):
        inside[:kept, first:end] = True
    w = jax.random.normal(jax.random.PRNGKey(11), h.shape) * mask[..., None]

    def run(stairs):
        def loss(h, layer):
            out = mixer(h, jnp.asarray(mask), layer, stairs)
            return jnp.sum(out * w), out
        grad = jax.jit(jax.grad(loss, argnums=(0, 1), has_aux=True))
        return grad(h, layer)

    (g_h, g_layer), out = run(stairs)
    (want_h, want_layer), want = run(None)
    valid = mask > 0
    np.testing.assert_allclose(np.asarray(out)[valid],
                               np.asarray(want)[valid], rtol=1e-4,
                               atol=1e-5)
    assert not np.asarray(out)[~inside].any()
    if not inside.all():
        assert np.asarray(want)[~inside].any()
    np.testing.assert_allclose(np.asarray(g_h), np.asarray(want_h),
                               rtol=1e-4, atol=1e-5)
    assert set(g_layer) == set(want_layer)
    for name in want_layer:
        scale = float(jnp.max(jnp.abs(want_layer[name])))
        assert scale > 0, name
        np.testing.assert_allclose(
            np.asarray(g_layer[name]), np.asarray(want_layer[name]),
            rtol=1e-4, atol=1e-5 * max(1.0, scale), err_msg=name)


def lowered_texts(fn, *args):
    """The lowered text of `fn` and of the gradient of its sum with
    respect to every argument (under one name whatever `fn` is called,
    so that two functions' texts can be compared)."""
    import jax
    import jax.numpy as jnp

    def mixer(*a):
        return fn(*a)

    grad = jax.grad(lambda *a: jnp.sum(fn(*a)),
                    argnums=tuple(range(len(args))))
    return [jax.jit(f).lower(*args).as_text() for f in (mixer, grad)]
