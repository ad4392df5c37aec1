"""The gated delta rule along the context axis, in chunks.

A head keeps a state S in R^{d_k x d_v}, float32, zero before slot 0,
and moves it slot by slot (Yang et al., Gated Delta Networks, 2024;
`model_type` `qwen3_next`'s `linear_attention` layers):

  S'_t = exp(g_t) S_{t-1}                       the decay, g_t <= 0
  S_t  = S'_t + k_t (beta_t (v_t - S'_t^T k_t))^T     the write
  o_t  = S_t^T q_t                              the read-out

A masked slot leaves the state as it is (beta = 0, g = 0) and gives
o = 0. Written as a `lax.scan` over slots that is what the chunked form
is held to (`recurrence` in tests/test_delta_rule.py; the reference's
`delta_recurrence`), and at the cell's batch a `[B, 32, 128, 128]`
float32 state moved 200 times a layer and again backward.

`gated_delta_rule` is the same function as matrix products over chunks
of L slots (the WY form of the rule's Householder-like products). With
G_i the sum of g over the chunk's slots up to i, and per chunk

  A_ij = beta_i (k_i . k_j) exp(G_i - G_j)      for i > j, else 0
  T    = (I + A)^{-1}
  U    = T (beta v)         W = T (beta exp(G) k)

the chunk's slots see the state S that entered the chunk as

  V'   = U - W S                                what each slot writes
  O    = (exp(G) q) S + ((q k^T) exp(G_i - G_j), i >= j) V'
  S    = exp(G_L) S + (exp(G_L - G) k)^T V'     the state that leaves

Every exponent is <= 0. The state, the decays, A and T are float32; the
products take their operands in the inputs' dtype and sum in float32.
T is a unit lower triangular inverse, built from the diagonal down by
blocks, [[T11, 0], [-T22 M21 T11, T22]], which doubles the block six
times for L = 64: matrix products only, and no sum of powers of A (that
series cancels catastrophically where neighbouring keys are alike). Its
backward is the inverse's own, -T^T dT T^T, so no doubling stage is
kept. The chunks run in a Python loop (C / L of them, four at 200
slots), each rematerialised in the backward pass from its inputs and the
state that entered it: every product carries the chunk's or the state's
shape in the trace. Each chunk may run over fewer rows than the one
before (`rows`): a training batch ordered longest bag first is all
padding below its staircase, a chunk of padding computes zeros and an
unchanged state at full price, and a row that has left never comes back,
so the state is cut down with the rows and the rows left out read 0.
With no bound every chunk runs over every row, as it always did.

The chunk length is the implementation's and not the model's: for any L
and any C, multiple of L or not, the chunked rule equals the recurrence
to float32 rounding, forward and in every gradient.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

# slots a chunk: half the MXU's side. At 128 the triangle's work per
# slot doubles and 200 slots pad to 256 all the same.
CHUNK = 64

_HIGHEST = jax.lax.Precision.HIGHEST


def chunk_len(slots: int) -> int:
    """The chunk `gated_delta_rule` cuts a sequence of `slots` into."""
    return min(CHUNK, slots)


def chunks_of(slots: int) -> int:
    return -(-slots // chunk_len(slots))


def live_chunks(mask: jax.Array) -> jax.Array:
    """How many of the chunks of mask [B, C] hold a valid slot: int32."""
    B, C = mask.shape
    L = chunk_len(C)
    padded = jnp.pad(mask, ((0, 0), (0, -C % L)))
    return jnp.sum(jnp.any(padded.reshape(B, -1, L) > 0, axis=-1),
                   dtype=jnp.int32)


@jax.custom_vjp
def unit_lower_inverse(a: jax.Array) -> jax.Array:
    """(I + a)^{-1} for a [..., L, L] strictly lower triangular, float32.
    At block size s the inverse of every diagonal block of 2 s is
    [[T11, 0], [-T22 a21 T11, T22]]: with D the block-diagonal inverse
    so far and E the a21 blocks, D - D E D."""
    L = a.shape[-1]
    i = jnp.arange(L)
    t = jnp.broadcast_to(jnp.eye(L, dtype=a.dtype), a.shape)
    s = 1
    while s < L:
        row, col = i[:, None] // s, i[None, :] // s
        lower_left = (row // 2 == col // 2) & (row % 2 == 1) & (col % 2 == 0)
        e = jnp.where(lower_left, a, 0)
        t = t - jnp.matmul(jnp.matmul(t, e, precision=_HIGHEST), t,
                           precision=_HIGHEST)
        s *= 2
    return t


def _inverse_fwd(a):
    t = unit_lower_inverse(a)
    return t, t


def _inverse_bwd(t, g):
    tt = jnp.swapaxes(t, -1, -2)
    ga = -jnp.matmul(jnp.matmul(tt, g, precision=_HIGHEST), tt,
                     precision=_HIGHEST)
    return (jnp.tril(ga, -1),)


unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def gated_delta_rule(q: jax.Array, k: jax.Array, v: jax.Array,
                     g: jax.Array, beta: jax.Array, mask: jax.Array,
                     rows: Optional[Tuple[int, ...]] = None) -> jax.Array:
    """The rule in chunks of `chunk_len(C)` slots. q, k [B, C, n_k, d_k]
    (L2-normalised and scaled by the caller), v [B, C, n_v, d_v], g and
    beta [B, C, n_v] float32, mask [B, C]; key head j serves value heads
    j r .. j r + r - 1, r = n_v / n_k. Returns o [B, C, n_v, d_v]
    float32.

    `rows` (static; None: every chunk runs over all B rows) is the
    caller's word that row `rows[n]` and every row after it hold no
    valid slot from chunk n on: `chunks_of(C)` counts that never rise,
    which a batch ordered longest bag first has from its staircase
    (data/staircase.py). Chunk n then runs over its first `rows[n]`
    rows and the state is cut down with it; the rows left out read
    o = 0, as masked slots do. Nothing here looks at the mask to check
    the word: a valid slot outside the bound would read 0."""
    B, C, n_k, d_k = k.shape
    n_v, d_v = v.shape[2], v.shape[3]
    r = n_v // n_k
    L = chunk_len(C)
    N = -(-C // L)
    if rows is None:
        rows = (B,) * N
    if len(rows) != N or any(a < b for a, b in zip((B,) + rows, rows)) \
            or rows[-1] < 1:
        raise ValueError(f"gated_delta_rule: rows {rows} for {N} chunks "
                         f"of {B} rows: one count a chunk, none over the "
                         "one before, none under 1")
    f32, dtype = jnp.float32, v.dtype
    live = mask.astype(f32)[..., None]
    g, beta = g.astype(f32) * live, beta.astype(f32) * live

    def split(t, grouped: bool):
        """[b, c, heads, *tail] -> [b, c / L, n_k, (r,) L, *tail]; the
        slots added to fill the last chunk are masked ones (g = beta =
        0)."""
        b, c, tail = t.shape[0], t.shape[1], t.shape[3:]
        n = -(-c // L)
        t = jnp.pad(t, ((0, 0), (0, n * L - c)) + ((0, 0),) * (t.ndim - 2))
        t = t.reshape(b, n, L, *((n_k, r) if grouped else (n_k,)), *tail)
        return jnp.moveaxis(t, 2, -1 - len(tail))

    grouped = (False, False, True, True, True)
    if rows[-1] == B:
        # no bound: the chunks are laid side by side once, the program
        # this always was. q, k [B, N, n_k, L, d_k]; v [B, N, n_k, r, L,
        # d_v]; g, beta [B, N, n_k, r, L]
        whole = [split(t, gr) for t, gr in zip((q, k, v, g, beta), grouped)]

        def chunk_inputs(n):
            return [t[:, n] for t in whole]
    else:
        # cut to the chunk's rows before the chunks are laid side by
        # side: the layout's copies are then the bound's size too
        def chunk_inputs(n):
            return [split(t[:rows[n], n * L:(n + 1) * L], gr)[:, 0]
                    for t, gr in zip((q, k, v, g, beta), grouped)]

    i = jnp.arange(L)

    def one_chunk(S, q, k, v, g, beta):
        """One chunk: q, k [B, n_k, L, d_k], v [B, n_k, r, L, d_v], g and
        beta [B, n_k, r, L], the state S [B, n_k, r, d_k, d_v] that
        enters: (the state that leaves, o [B, n_k, r, L, d_v])."""
        G = jnp.cumsum(g, axis=-1)
        # exp(G_i - G_j) for i >= j, 0 above the diagonal (never exp of
        # a positive number)
        decay = jnp.exp(jnp.where(i[:, None] >= i[None, :],
                                  G[..., :, None] - G[..., None, :],
                                  -jnp.inf))                 # [.., r, L, L]
        kk = jnp.einsum("bhid,bhjd->bhij", k, k, preferred_element_type=f32)
        qk = jnp.einsum("bhid,bhjd->bhij", q, k, preferred_element_type=f32)
        a = jnp.where(i[:, None] > i[None, :],
                      beta[..., None] * kk[:, :, None] * decay, 0.0)
        # T's columns carry beta (and, for W, the decay up to their
        # slot), so that k, one a key head, is never spread over its
        # value heads
        t_u = unit_lower_inverse(a) * beta[..., None, :]
        t_w = (t_u * jnp.exp(G)[..., None, :]).astype(dtype)
        u = jnp.matmul(t_u.astype(dtype), v, preferred_element_type=f32)
        w = jnp.einsum("bhrij,bhjd->bhrid", t_w, k)          # [.., L, d_k]
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

    # a chunk's triangle, its inverse and its products are recomputed in
    # the backward pass: kept for every chunk they are the layer's memory
    # (4 GB of the 9.7 a layer's backward held at the cell's sizes)
    one_chunk = jax.checkpoint(one_chunk)
    S = jnp.zeros((rows[0], n_k, r, d_k, d_v), f32)
    out = []
    for n in range(N):
        if rows[n] < S.shape[0]:
            S = S[:rows[n]]     # a row that leaves never comes back
        S, o = one_chunk(S, *chunk_inputs(n))
        if rows[n] < B:
            o = jnp.pad(o, ((0, B - rows[n]),) + ((0, 0),) * 4)
        out.append(o)
    o = jnp.stack(out, axis=1)                       # [B, N, n_k, r, L, d_v]
    o = jnp.moveaxis(o, -2, 2).reshape(B, N * L, n_v, d_v)[:, :C]
    return o * live[..., None]
