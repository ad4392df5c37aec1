"""`ops/delta_rule.py` in float32 on the CPU: the chunked gated delta
rule against the token-by-token recurrence, output and every gradient,
for lengths that are and are not multiples of the chunk and with masked
tails; what a masked slot leaves alone; the triangular inverse and its
backward; the grouping of value heads under key heads; the count of live
chunks; the rule over a per-chunk bound on the rows (ISSUE 33): the
recurrence and the unbounded rule on batches ordered longest first, zeros
outside the bound, and with no bound the program it always lowered to."""

import functools
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from code2vec_tpu.ops import delta_rule as dr

_HIGHEST = jax.lax.Precision.HIGHEST


def case(B=3, C=23, n_k=2, n_v=4, d_k=8, d_v=6, seed=0, lens=None,
         decay=3.0):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)

    def unit(key):
        t = jax.random.normal(key, (B, C, n_k, d_k))
        return t / jnp.linalg.norm(t, axis=-1, keepdims=True)

    lens = np.array([C, max(C - 7, 1), max(C // 3, 1)][:B]) \
        if lens is None else np.asarray(lens)
    mask = (np.arange(C)[None, :] < lens[:, None]).astype(np.float32)
    return (unit(k[0]) / np.sqrt(d_k), unit(k[1]),
            jax.random.normal(k[2], (B, C, n_v, d_v)),
            -jax.random.uniform(k[3], (B, C, n_v), minval=0.0, maxval=decay),
            jax.nn.sigmoid(jax.random.normal(k[4], (B, C, n_v))),
            jnp.asarray(mask))


def _recurrence(q, k, v, g, beta, mask):
    """The rule slot by slot, a `lax.scan` over slots:
    `dr.gated_delta_rule`'s arguments and result."""
    r = v.shape[2] // k.shape[2]
    f32 = jnp.float32
    q, k = (jnp.repeat(t.astype(f32), r, axis=2) for t in (q, k))
    live = mask.astype(f32)[..., None]
    g, beta = g.astype(f32) * live, beta.astype(f32) * live

    def slot(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        S = S * jnp.exp(g_t)[..., None, None]
        seen = jnp.einsum("bhkv,bhk->bhv", S, k_t, precision=_HIGHEST)
        S = S + jnp.einsum("bhk,bhv->bhkv", k_t,
                           b_t[..., None] * (v_t - seen),
                           precision=_HIGHEST)
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t, precision=_HIGHEST)

    B, _, n_v, d_v = v.shape
    S0 = jnp.zeros((B, n_v, k.shape[-1], d_v), f32)
    _, o = jax.lax.scan(slot, S0, tuple(
        jnp.moveaxis(t, 1, 0) for t in (q, k, v.astype(f32), g, beta)))
    return jnp.moveaxis(o, 0, 1) * live[..., None]


def rule_in_chunks_of(chunk, rows=None):
    """`dr.gated_delta_rule` traced with the module's `CHUNK` at `chunk`
    (None: as it stands): the chunk length is the module's, not an
    argument. `rows`: the rows each chunk runs over."""
    def rule(*args):
        with mock.patch.object(dr, "CHUNK", chunk or dr.CHUNK):
            return dr.gated_delta_rule(*args, rows=rows)
    return rule


recurrence = jax.jit(_recurrence)


@functools.partial(jax.jit, static_argnames=("chunk",))
def chunked(q, k, v, g, beta, mask, chunk=None):
    return rule_in_chunks_of(chunk)(q, k, v, g, beta, mask)


def output_and_gradients(fn, args):
    """The output, and the gradients of a weighted sum of it for q, k,
    v, g and beta (one compiled program: op by op the CPU compiles
    every primitive apart)."""
    weight = jax.random.normal(jax.random.PRNGKey(5), args[2].shape)

    def loss(*a):
        out = fn(*a, args[5])
        return jnp.sum(out * weight), out

    run = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True))
    grads, out = run(*args[:5])
    return out, grads


# slots, chunk: a multiple, not a multiple, one chunk, a chunk longer
# than the bag's live part, a chunk that is no power of two, the cell's
@pytest.mark.parametrize("C,chunk", [(24, 8), (23, 8), (16, 16), (23, 5),
                                     (7, 64), (200, 64)])
def test_chunked_rule_is_the_recurrence(C, chunk):
    args = case(C=C)
    want, want_grads = output_and_gradients(_recurrence, args)
    got, got_grads = output_and_gradients(rule_in_chunks_of(chunk), args)
    assert got.dtype == jnp.float32 and got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=5e-6)
    for name, a, b in zip("q k v g beta".split(), want_grads, got_grads):
        scale = float(jnp.max(jnp.abs(a))) + 1e-9
        np.testing.assert_allclose(np.asarray(b) / scale,
                                   np.asarray(a) / scale, atol=2e-5,
                                   err_msg=name)


def test_default_chunk_is_the_modules_or_the_whole_sequence():
    assert (dr.chunk_len(200), dr.chunks_of(200)) == (64, 4)
    assert (dr.chunk_len(12), dr.chunks_of(12)) == (12, 1)
    assert (dr.chunk_len(64), dr.chunks_of(64)) == (64, 1)
    args = case(C=12)
    np.testing.assert_array_equal(
        np.asarray(chunked(*args)),
        np.asarray(chunked(*args, chunk=12)))


@pytest.mark.parametrize("rule", [recurrence,
                                  lambda *a: chunked(*a, chunk=4)])
def test_a_masked_slot_leaves_the_state_and_later_slots_unchanged(rule):
    """A hole in the mask: whatever stands in the hole's q, k, v, g and
    beta, the slots after it read what they read without it, and the
    hole reads 0; the sequence with the hole cut out gives the same."""
    C = 11
    q, k, v, g, beta, _ = case(B=2, C=C, lens=[C, C])
    mask = np.ones((2, C), np.float32)
    mask[:, 5] = 0.0
    out = rule(q, k, v, g, beta, jnp.asarray(mask))
    np.testing.assert_array_equal(np.asarray(out[:, 5]), 0.0)
    moved = rule(q.at[:, 5].add(1.0), k.at[:, 5].multiply(-2.0),
                 v.at[:, 5].add(3.0), g.at[:, 5].add(-1.0),
                 beta.at[:, 5].set(0.9), jnp.asarray(mask))
    np.testing.assert_array_equal(np.asarray(moved), np.asarray(out))
    keep = [i for i in range(C) if i != 5]
    cut = rule(*(t[:, keep] for t in (q, k, v, g, beta)),
               jnp.ones((2, C - 1), jnp.float32))
    np.testing.assert_allclose(np.asarray(out[:, keep]), np.asarray(cut),
                               atol=2e-6)


def test_a_masked_tail_reads_zero_and_costs_the_head_nothing():
    args = case(C=20, lens=[20, 9, 1])
    out = chunked(*args, chunk=8)
    np.testing.assert_array_equal(np.asarray(out[1, 9:]), 0.0)
    np.testing.assert_array_equal(np.asarray(out[2, 1:]), 0.0)
    short = chunked(*(t[1:2, :9] for t in args), chunk=8)
    np.testing.assert_allclose(np.asarray(out[1:2, :9]), np.asarray(short),
                               atol=2e-6)


def test_key_head_j_serves_value_heads_2j_and_2j_plus_1():
    q, k, v, g, beta, mask = case(n_k=2, n_v=4)
    grouped = chunked(q, k, v, g, beta, mask, chunk=8)
    repeated = chunked(jnp.repeat(q, 2, axis=2),
                                   jnp.repeat(k, 2, axis=2), v, g, beta,
                                   mask, chunk=8)
    np.testing.assert_allclose(np.asarray(grouped), np.asarray(repeated),
                               atol=1e-6)
    # and a value head reads no other key head: moving key head 1
    # leaves value heads 0 and 1 as they were
    moved = chunked(q, k.at[:, :, 1].multiply(-1.0), v, g, beta,
                                mask, chunk=8)
    np.testing.assert_array_equal(np.asarray(moved[:, :, :2]),
                                  np.asarray(grouped[:, :, :2]))
    assert not np.allclose(np.asarray(moved[:, :, 2:]),
                           np.asarray(grouped[:, :, 2:]))


def test_with_no_decay_and_full_writes_the_state_holds_the_last_value():
    """g = 0, beta = 1, orthonormal keys: the rule is a key-value store,
    and reading with a key returns the value written under it."""
    C = d_k = 6
    k = jnp.eye(d_k)[None, :, None, :]                  # [1, C, 1, d_k]
    v = jax.random.normal(jax.random.PRNGKey(1), (1, C, 1, 4))
    ones = jnp.ones((1, C, 1))
    # slot t asks for the key written at slot 0
    q = jnp.broadcast_to(k[:, :1], k.shape)
    out = chunked(q, k, v, 0.0 * ones, ones,
                              jnp.ones((1, C)), chunk=4)
    np.testing.assert_allclose(np.asarray(out[0, :, 0]),
                               np.broadcast_to(np.asarray(v[0, 0, 0]),
                                               (C, 4)), atol=1e-6)


@pytest.mark.parametrize("L", [1, 2, 5, 8, 64])
def test_unit_lower_inverse_is_the_inverse(L):
    # entries as the rule's: beta (k_i . k_j) times a decay, under 1
    a = jnp.tril(jax.random.uniform(jax.random.PRNGKey(L), (3, L, L),
                                    minval=-0.5, maxval=0.5), -1)
    t = dr.unit_lower_inverse(a)
    np.testing.assert_allclose(np.asarray(t @ (jnp.eye(L) + a)),
                               np.broadcast_to(np.eye(L), (3, L, L)),
                               atol=1e-4)
    assert np.all(np.triu(np.asarray(t), 1) == 0)


def test_unit_lower_inverse_survives_keys_that_are_all_alike():
    """Every a_ij = 1: the inverse is 1 on the diagonal and -1 under it,
    while the powers of a reach 1e18 (a sum of them cancels to
    nothing in float32)."""
    L = 64
    a = jnp.tril(jnp.ones((L, L)), -1)
    want = np.eye(L) - np.eye(L, k=-1)
    np.testing.assert_allclose(np.asarray(dr.unit_lower_inverse(a)), want,
                               atol=1e-5)


def test_unit_lower_inverse_backward_is_the_inverses_own():
    L = 8
    a = jnp.tril(0.5 * jax.random.normal(jax.random.PRNGKey(2), (L, L)), -1)
    w = jax.random.normal(jax.random.PRNGKey(3), (L, L))
    got = jax.grad(lambda a: jnp.sum(dr.unit_lower_inverse(a) * w))(a)
    want = jax.grad(lambda a: jnp.sum(
        jnp.linalg.inv(jnp.eye(L) + jnp.tril(a, -1)) * w))(a)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


def test_bfloat16_inputs_keep_a_float32_state():
    args = case(C=40)
    want = recurrence(*args)
    q, k, v = (t.astype(jnp.bfloat16) for t in args[:3])
    got = chunked(q, k, v, *args[3:], chunk=16)
    assert got.dtype == jnp.float32
    gap = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert gap < 0.02, gap


# ---- the rows a chunk runs over (ISSUE 33) -------------------------------

def ordered_case(B, C, chunk, rows, dtype=jnp.float32):
    """`case` with bags ordered longest first that the bound `rows`
    holds: row b is valid up to the first slot of the first chunk
    that leaves it out (some rows to the very slot), and no further; a
    row the bound never holds is an empty bag."""
    allowed = np.array([chunk * sum(b < kept for kept in rows)
                        for b in range(B)])
    lens = np.sort(np.clip(allowed - 3 * (np.arange(B) % 3),
                           np.minimum(allowed, 1), C))[::-1]
    q, k, v, g, beta, mask = case(B=B, C=C, lens=lens)
    return (q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta,
            mask)


BF16 = jnp.bfloat16
BOUNDED = {
    "the cell's 200 slots, a falling bound": (8, 200, 64, (8, 5, 3, 2)),
    "slots no multiple of the chunk": (6, 23, 8, (6, 4, 2)),
    "one chunk": (3, 16, 16, (3,)),
    "every chunk over every row": (4, 24, 8, (4, 4, 4)),
    "down to a single row": (5, 24, 8, (5, 1, 1)),
    "fewer rows than the batch from chunk 0": (5, 16, 8, (4, 2)),
    "bfloat16 inputs": (6, 40, 16, (6, 4, 3), BF16),
}


@pytest.mark.parametrize("name", BOUNDED)
def test_bounded_rule_is_the_recurrence_and_the_unbounded_rule(name):
    B, C, chunk, rows, *dtype = BOUNDED[name]
    args = ordered_case(B, C, chunk, rows, *dtype)
    mask = np.asarray(args[5])
    # the bound holds the batch: no valid slot from chunk n on at or
    # below row rows[n] (and the first case cuts into every chunk)
    for n, kept in enumerate(rows):
        assert not mask[kept:, n * chunk:].any()
    want, want_grads = output_and_gradients(_recurrence, args)
    full, full_grads = output_and_gradients(rule_in_chunks_of(chunk), args)
    got, got_grads = output_and_gradients(rule_in_chunks_of(chunk, rows), args)
    assert got.dtype == jnp.float32 and got.shape == want.shape
    # float32: the recurrence to its rounding, the unbounded rule to a
    # unit or two (the same products on the same operands, row by row);
    # bfloat16 operands: the unbounded rule's own distance
    exact = not dtype
    np.testing.assert_allclose(np.asarray(got), np.asarray(full),
                               atol=1e-6 if exact else 1e-2)
    if exact:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=5e-6)
    else:
        gap = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
        assert gap < 0.02, gap
    for name, a, f, b in zip("q k v g beta".split(), want_grads,
                             full_grads, got_grads):
        assert b.dtype == a.dtype and b.shape == a.shape
        a, f, b = (np.asarray(t.astype(jnp.float32)) for t in (a, f, b))
        scale = np.abs(a).max() + 1e-9
        np.testing.assert_allclose(b / scale, f / scale,
                                   atol=2e-6 if exact else 2e-2,
                                   err_msg=name)
        np.testing.assert_allclose(b / scale, a / scale,
                                   atol=2e-5 if exact else 5e-2,
                                   err_msg=name)


def test_rows_outside_the_bound_read_zero_and_get_zero_gradient():
    """The rule does not look at the mask to check the bound: with
    every slot valid, what the bound leaves out reads exactly 0 and
    hands exactly nothing back, and what it keeps is the rule over
    those rows alone."""
    B, C, chunk, rows = 5, 24, 8, (5, 3, 1)
    args = case(B=B, C=C, lens=[C] * B)
    out, grads = output_and_gradients(rule_in_chunks_of(chunk, rows), args)
    for n, kept in enumerate(rows):
        slots = slice(n * chunk, (n + 1) * chunk)
        np.testing.assert_array_equal(np.asarray(out[kept:, slots]), 0.0)
        for name, grad in zip("q k v g beta".split(), grads):
            np.testing.assert_array_equal(np.asarray(grad[kept:, slots]),
                                          0.0, err_msg=name)
        assert np.asarray(out[:kept, slots]).any()
    # row 2 runs two chunks and stops: the rule over its first 16 slots
    short = chunked(*(t[2:3, :16] for t in args), chunk=chunk)
    np.testing.assert_allclose(np.asarray(out[2:3, :16]), np.asarray(short),
                               atol=2e-6)


@pytest.mark.parametrize("rows,why", [
    ((4, 4), "a count a chunk"), ((4, 2, 3), "never rising"),
    ((5, 4, 4), "at most the batch"), ((4, 2, 0), "at least a row"),
])
def test_a_bound_that_is_no_bound_is_refused(rows, why):
    args = case(B=4, C=24, lens=[24, 16, 8, 8])
    with pytest.raises(ValueError, match="rows"):
        rule_in_chunks_of(8, rows)(*args)


def _rule_before_the_bound(q, k, v, g, beta, mask):
    """`dr.gated_delta_rule` as it stood before ISSUE 33 (its comments
    left out): what a caller that passes no bound still lowers to."""
    B, C, n_k, d_k = k.shape
    n_v, d_v = v.shape[2], v.shape[3]
    r = n_v // n_k
    L = dr.chunk_len(C)
    N = -(-C // L)
    f32, dtype = jnp.float32, v.dtype
    live = mask.astype(f32)[..., None]
    g, beta = g.astype(f32) * live, beta.astype(f32) * live

    def split(t, grouped: bool):
        tail = t.shape[3:]
        t = jnp.pad(t, ((0, 0), (0, N * L - C)) + ((0, 0),) * (t.ndim - 2))
        t = t.reshape(B, N, L, *((n_k, r) if grouped else (n_k,)), *tail)
        return jnp.moveaxis(t, 2, -1 - len(tail))

    q, k = split(q, False), split(k, False)
    v = split(v, True)
    g, beta = split(g, True), split(beta, True)
    i = jnp.arange(L)

    def one_chunk(S, q, k, v, g, beta):
        G = jnp.cumsum(g, axis=-1)
        decay = jnp.exp(jnp.where(i[:, None] >= i[None, :],
                                  G[..., :, None] - G[..., None, :],
                                  -jnp.inf))
        kk = jnp.einsum("bhid,bhjd->bhij", k, k, preferred_element_type=f32)
        qk = jnp.einsum("bhid,bhjd->bhij", q, k, preferred_element_type=f32)
        a = jnp.where(i[:, None] > i[None, :],
                      beta[..., None] * kk[:, :, None] * decay, 0.0)
        t_u = dr.unit_lower_inverse(a) * beta[..., None, :]
        t_w = (t_u * jnp.exp(G)[..., None, :]).astype(dtype)
        u = jnp.matmul(t_u.astype(dtype), v, preferred_element_type=f32)
        w = jnp.einsum("bhrij,bhjd->bhrid", t_w, k)
        s_in = S.astype(dtype)
        written = u - jnp.matmul(w, s_in, preferred_element_type=f32)
        read = jnp.einsum("bhid,bhrdv->bhriv", q, s_in,
                          preferred_element_type=f32)
        o = jnp.exp(G)[..., None] * read + jnp.matmul(
            (qk[:, :, None] * decay).astype(dtype), written.astype(dtype),
            preferred_element_type=f32)
        to_end = jnp.exp(G[..., -1:] - G)[..., None]
        S = S * jnp.exp(G[..., -1])[..., None, None] + jnp.einsum(
            "bhid,bhriv->bhrdv", k, (to_end * written).astype(dtype),
            preferred_element_type=f32)
        return S, o

    one_chunk = jax.checkpoint(one_chunk)
    S = jnp.zeros((B, n_k, r, d_k, d_v), f32)
    out = []
    for n in range(N):
        S, o = one_chunk(S, q[:, n], k[:, n], v[:, n], g[:, n], beta[:, n])
        out.append(o)
    o = jnp.stack(out, axis=1)
    o = jnp.moveaxis(o, -2, 2).reshape(B, N * L, n_v, d_v)[:, :C]
    return o * live[..., None]


@pytest.mark.parametrize("rows", [None, (3, 3, 3, 3)])
def test_with_no_bound_the_lowered_program_is_the_old_one(rows):
    """Evaluation, prediction, serving and every batch that does not
    fit its staircase pass no bound: forward and backward, what they
    lower to does not know the argument exists (and a bound that keeps
    every row is no bound)."""
    args = case(C=200)
    args = tuple(t.astype(BF16) for t in args[:3]) + args[3:]

    def old(*a):
        return _rule_before_the_bound(*a)

    def new(*a):
        return dr.gated_delta_rule(*a) if rows is None \
            else dr.gated_delta_rule(*a, rows=rows)

    def texts(fn):
        grad = jax.grad(lambda *a: jnp.sum(fn(*a)), argnums=(0, 1, 2, 3, 4))
        return [jax.jit(f).lower(*args).as_text().replace("jit_new",
                                                          "jit_old")
                for f in (fn, grad)]

    assert texts(new) == texts(old)
    bounded = jax.jit(lambda *a: dr.gated_delta_rule(
        *a, rows=(3, 2, 1, 1))).lower(*args).as_text()
    assert bounded != texts(new)[0]


def test_live_chunks_is_a_numpy_count():
    r = np.random.default_rng(0)
    for C in (12, 64, 100, 200):
        lens = r.integers(0, C + 1, 16)
        mask = (np.arange(C)[None, :] < lens[:, None]).astype(np.float32)
        L = dr.chunk_len(C)
        want = int(sum(-(-int(n) // L) for n in lens))
        assert int(dr.live_chunks(jnp.asarray(mask))) == want
