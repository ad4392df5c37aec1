"""`ops/delta_rule.ssd_scan` (Mamba-2's scalar-decay state-space scan)
in float32 on the CPU, x and o flat [B, C, n_v d_v] as the Mamba layer
holds them: the chunked scan against the token-by-token recurrence,
output and every gradient, at two chunk lengths, with slots no multiple
of the chunk, and under a staircase's bound on the rows; what a masked
slot leaves alone; one key head serving every value head; and a lowered
program that holds no array of the chunks side by side and no padding to
whole chunks. And the delta rule, whose chunk machinery the scan shares:
its lowered text, bounded or not, is what it was (the three block
encoders' lowered train steps, under this copy of the old rule and the
old `seq_block` functions, are held in tests/test_granite_hybrid.py)."""

from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from code2vec_tpu.ops import delta_rule as dr

_HIGHEST = jax.lax.Precision.HIGHEST
ARGS = "q k v dt g".split()


def case(B=3, C=23, n_v=4, d_k=8, d_v=6, seed=0, lens=None):
    """(q, k, v, dt, g, mask): one key head of C and B [B, C, 1, d_k],
    n_v value heads of d_v in x [B, C, n_v d_v], dt > 0 and
    g = -dt A <= 0."""
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    lens = np.array([C, max(C - 7, 1), max(C // 3, 1)][:B]) \
        if lens is None else np.asarray(lens)
    mask = (np.arange(C)[None, :] < lens[:, None]).astype(np.float32)
    dt = jax.nn.softplus(jax.random.normal(k[3], (B, C, n_v)) - 1.0)
    A = jnp.arange(1, n_v + 1, dtype=jnp.float32)
    return (jax.random.normal(k[0], (B, C, 1, d_k)) / np.sqrt(d_k),
            jax.random.normal(k[1], (B, C, 1, d_k)) / np.sqrt(d_k),
            jax.random.normal(k[2], (B, C, n_v * d_v)), dt, -dt * A,
            jnp.asarray(mask))


def _recurrence(q, k, v, dt, g, mask):
    """The scan slot by slot, a `lax.scan` over slots:
    `dr.ssd_scan`'s arguments and result."""
    f32 = jnp.float32
    live = mask.astype(f32)[..., None]
    dt, g = dt.astype(f32) * live, g.astype(f32) * live

    def slot(S, xs):
        q_t, k_t, v_t, dt_t, g_t = xs
        S = S * jnp.exp(g_t)[..., None, None] + jnp.einsum(
            "bk,bhv->bhkv", k_t, dt_t[..., None] * v_t, precision=_HIGHEST)
        return S, jnp.einsum("bhkv,bk->bhv", S, q_t, precision=_HIGHEST)

    B, C, n_v = dt.shape
    d_v = v.shape[2] // n_v
    S0 = jnp.zeros((B, n_v, k.shape[-1], d_v), f32)
    _, o = jax.lax.scan(slot, S0, tuple(jnp.moveaxis(t, 1, 0) for t in (
        q[:, :, 0].astype(f32), k[:, :, 0].astype(f32),
        v.reshape(B, C, n_v, d_v).astype(f32), dt, g)))
    return jnp.moveaxis(o, 0, 1).reshape(B, C, n_v * d_v) * live


recurrence = jax.jit(_recurrence)


def scan_in_chunks_of(chunk, rows=None):
    """`dr.ssd_scan` traced with the module's `CHUNK` at `chunk`: the
    chunk length is the module's, not an argument."""
    def scan(*args):
        with mock.patch.object(dr, "CHUNK", chunk):
            return dr.ssd_scan(*args, rows=rows)
    return scan


def output_and_gradients(fn, args):
    """The output, and the gradients of a weighted sum of it for q, k,
    v, dt and g, in one compiled program."""
    weight = jax.random.normal(jax.random.PRNGKey(5), args[2].shape)

    def loss(*a):
        out = fn(*a, args[5])
        return jnp.sum(out * weight), out

    run = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True))
    grads, out = run(*args[:5])
    return out, grads


def assert_same(got, want):
    out, grads = got
    w_out, w_grads = want
    assert out.dtype == jnp.float32 and out.shape == w_out.shape
    scale = float(jnp.max(jnp.abs(w_out)))
    np.testing.assert_allclose(np.asarray(out) / scale,
                               np.asarray(w_out) / scale, atol=5e-6)
    for name, a, b in zip(ARGS, w_grads, grads):
        scale = float(jnp.max(jnp.abs(a))) + 1e-9
        np.testing.assert_allclose(np.asarray(b) / scale,
                                   np.asarray(a) / scale, atol=2e-5,
                                   err_msg=name)


# slots, chunk: a multiple, not a multiple (two chunk lengths), the cell's
@pytest.mark.parametrize("C,chunk", [(24, 8), (23, 8), (23, 5), (200, 64)])
def test_chunked_scan_is_the_recurrence(C, chunk):
    args = case(C=C)
    assert_same(output_and_gradients(scan_in_chunks_of(chunk), args),
                output_and_gradients(_recurrence, args))


# rows, slots, chunk, the bound: the cell's 200 slots and a falling
# bound; slots no multiple of the chunk
@pytest.mark.parametrize("B,C,chunk,rows", [(8, 200, 64, (8, 5, 3, 2)),
                                            (6, 23, 8, (6, 4, 2))])
def test_scan_under_a_staircase_bound_is_the_recurrence(B, C, chunk, rows):
    """Bags ordered longest first that the bound holds: each row valid
    up to the first slot of the first chunk that leaves it out."""
    allowed = np.array([chunk * sum(b < kept for kept in rows)
                        for b in range(B)])
    lens = np.sort(np.clip(allowed - 3 * (np.arange(B) % 3),
                           np.minimum(allowed, 1), C))[::-1]
    args = case(B=B, C=C, lens=lens)
    mask = np.asarray(args[5])
    for n, kept in enumerate(rows):
        assert not mask[kept:, n * chunk:].any()
    assert_same(output_and_gradients(scan_in_chunks_of(chunk, rows), args),
                output_and_gradients(_recurrence, args))


@pytest.mark.parametrize("scan", [recurrence, scan_in_chunks_of(4)])
def test_a_masked_slot_leaves_the_state(scan):
    """A hole in the mask: whatever stands in the hole's q, k, v, dt and
    g, the slots after it read what they read without it, and the hole
    reads 0; the sequence with the hole cut out gives the same."""
    C = 11
    q, k, v, dt, g, _ = case(B=2, C=C, lens=[C, C])
    mask = np.ones((2, C), np.float32)
    mask[:, 5] = 0.0
    out = scan(q, k, v, dt, g, jnp.asarray(mask))
    np.testing.assert_array_equal(np.asarray(out[:, 5]), 0.0)
    moved = scan(q.at[:, 5].add(1.0), k.at[:, 5].multiply(-2.0),
                 v.at[:, 5].add(3.0), dt.at[:, 5].add(2.0),
                 g.at[:, 5].add(-1.0), jnp.asarray(mask))
    np.testing.assert_allclose(np.asarray(moved), np.asarray(out),
                               atol=1e-6)
    keep = [i for i in range(C) if i != 5]
    cut = scan(*(t[:, keep] for t in (q, k, v, dt, g)),
               jnp.ones((2, C - 1), jnp.float32))
    np.testing.assert_allclose(np.asarray(out[:, keep]), np.asarray(cut),
                               atol=2e-6)


def test_with_no_decay_a_head_reads_the_sum_of_its_writes():
    """g = 0: o_t = sum over j <= t of (q_t . k_j) dt_j v_j, each value
    head with its own dt over the one key head."""
    q, k, v, dt, _g, mask = case(B=2, C=10, lens=[10, 10])
    out = scan_in_chunks_of(4)(q, k, v, dt, jnp.zeros_like(dt), mask)
    qk = jnp.einsum("bid,bjd->bij", q[:, :, 0], k[:, :, 0],
                    precision=_HIGHEST) * np.tril(np.ones((10, 10)))
    want = jnp.einsum("bij,bjh,bjhv->bihv", qk, dt, v.reshape(2, 10, 4, 6),
                      precision=_HIGHEST)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(want).reshape(2, 10, 24), atol=1e-5)


def test_bfloat16_operands_keep_a_float32_state():
    args = case(C=40)
    low = tuple(t.astype(jnp.bfloat16) for t in args[:3]) + args[3:]
    got = scan_in_chunks_of(16)(*low)
    want = _recurrence(*args)
    assert got.dtype == jnp.float32
    gap = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert gap < 0.02, gap


@pytest.mark.parametrize("rows", [(8, 5, 3, 2), None])
def test_the_scan_lowers_with_no_chunks_side_by_side(rows):
    """At 8 rows and 200 slots (four chunks of 64, the last of 8), bounded
    or not: no array of the program, forward or backward, holds the four
    chunks side by side at all 8 rows, and none is padded to 256 slots.
    Each chunk is laid out and back at its own rows and slots, and the
    chunks are joined once along the slots."""
    args = case(B=8, C=200, n_v=3, lens=[200, 150, 120, 90, 60, 40, 20, 5])
    fn = scan_in_chunks_of(64, rows)
    grad = jax.grad(lambda *a: jnp.sum(fn(*a, args[5])),
                    argnums=(0, 1, 2, 3, 4))
    texts = [jax.jit(f).lower(*a).as_text()
             for f, a in ((fn, args), (grad, args[:5]))]
    for text in texts:
        assert "tensor<8x4x" not in text
        assert "x256x" not in text and "tensor<8x256" not in text
        assert "tensor<8x200x18xf32>" in text


# ---- the delta rule, whose chunk machinery the scan shares ---------------

def _rule_as_it_was(q, k, v, g, beta, mask, rows=None):
    """`dr.gated_delta_rule` as it stood before ISSUE 38 (its comments
    and its check of `rows` left out)."""
    B, C, n_k, d_k = k.shape
    n_v, d_v = v.shape[2], v.shape[3]
    r = n_v // n_k
    L = dr.chunk_len(C)
    N = -(-C // L)
    if rows is None:
        rows = (B,) * N
    f32, dtype = jnp.float32, v.dtype
    live = mask.astype(f32)[..., None]
    g, beta = g.astype(f32) * live, beta.astype(f32) * live

    def split(t, grouped: bool):
        b, c, tail = t.shape[0], t.shape[1], t.shape[3:]
        n = -(-c // L)
        t = jnp.pad(t, ((0, 0), (0, n * L - c)) + ((0, 0),) * (t.ndim - 2))
        t = t.reshape(b, n, L, *((n_k, r) if grouped else (n_k,)), *tail)
        return jnp.moveaxis(t, 2, -1 - len(tail))

    grouped = (False, False, True, True, True)
    if rows[-1] == B:
        whole = [split(t, gr) for t, gr in zip((q, k, v, g, beta), grouped)]

        def chunk_inputs(n):
            return [t[:, n] for t in whole]
    else:
        def chunk_inputs(n):
            return [split(t[:rows[n], n * L:(n + 1) * L], gr)[:, 0]
                    for t, gr in zip((q, k, v, g, beta), grouped)]

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
    S = jnp.zeros((rows[0], n_k, r, d_k, d_v), f32)
    out = []
    for n in range(N):
        if rows[n] < S.shape[0]:
            S = S[:rows[n]]
        S, o = one_chunk(S, *chunk_inputs(n))
        if rows[n] < B:
            o = jnp.pad(o, ((0, B - rows[n]),) + ((0, 0),) * 4)
        out.append(o)
    o = jnp.stack(out, axis=1)
    o = jnp.moveaxis(o, -2, 2).reshape(B, N * L, n_v, d_v)[:, :C]
    return o * live[..., None]


@pytest.mark.parametrize("rows", [None, (8, 5, 3, 2)])
def test_the_delta_rule_lowers_to_what_it_did(rows):
    """Forward and backward, bounded or not: the rule's program is the
    one it was before the scan shared its machinery."""
    k = jax.random.split(jax.random.PRNGKey(1), 5)
    B, C = 8, 200
    args = (jax.random.normal(k[0], (B, C, 2, 8), jnp.bfloat16),
            jax.random.normal(k[1], (B, C, 2, 8), jnp.bfloat16),
            jax.random.normal(k[2], (B, C, 4, 6), jnp.bfloat16),
            -jax.random.uniform(k[3], (B, C, 4)),
            jax.random.uniform(k[4], (B, C, 4)), jnp.ones((B, C)))

    def texts(rule):
        def fn(*a):
            return rule(*a, rows=rows)

        grad = jax.grad(lambda *a: jnp.sum(fn(*a)), argnums=(0, 1, 2, 3, 4))
        return [jax.jit(f).lower(*args).as_text() for f in (fn, grad)]

    assert texts(dr.gated_delta_rule) == texts(_rule_as_it_was)
