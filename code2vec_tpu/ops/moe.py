"""Routed experts for one expert-parallel share: the router over every
expert of the layer, and the grouped matrix product over the experts
this process holds.

  s      = sigmoid(h W_r)                      [N, E], float32
  chosen = top K of (s + bias)                 the bias selects only
  p_e    = s_e / (sum of the K chosen s + 1e-6)
  FF(h)  = sum over chosen e held here of p_e (silu(h W1_e) * (h W3_e)) W2_e

or, the second router (`score="softmax"`, `model_type` `qwen3_next`):

  s      = softmax(h W_r) over all E           float32, no bias
  chosen = top K of s ;  p_e = s_e / (sum of the K chosen s)

A block whose router weighs the sum (`routed_scaling_factor`) or guards
its division otherwise hands `route` its `scale` and `eps`
(`model_type` `joyai_llm_flash`: p_e = 2.5 s_e / (sum + 1e-20)); it is
the same router, not a third.

The layer is told the first expert it holds and how many; it routes over
all E and computes its own part. What the absent experts would add is
left out, and nothing stands in for the exchange that would bring other
chips' rows here.

The grouped product is exact and its shapes are static: the N x K
(token, choice) pairs are sorted by held expert, pairs that chose an
expert held elsewhere (and every pair of a masked token) last, and
`jax.lax.ragged_dot` runs over the sorted rows with the held experts'
row counts as group sizes. On the TPU XLA lowers `ragged_dot` to a
Mosaic kernel whose grid follows the group sizes, so the work is that of
the rows routed here; rows past the last group belong to no expert and
are masked on both sides of each product (a kernel need not write them).

No capacity, no dropped row, and this is how. Were every token to
choose one held expert, its group would be N rows long, so only arrays
of N K rows hold every routing; but held of E experts take held / E of
the choices when the router is even, and every array between the sort
and the sum back is copied, selected and weighted at its full length
whatever is live in it. So those arrays hold `row_bound` rows, R =
twice the rows the held experts would take from an all-valid, evenly
routed batch (2 N K held / E, up to the product's row tile): the first R
sorted pairs, which are every live pair whenever the live pairs are at
most R. A layer whose live pairs are more than R (the device alone
knows; `fits`) runs the same body over all N K sorted pairs instead:
one `lax.cond`, inside a `custom_vjp` whose backward is its own `cond`
over the two bodies' vjps, so that no residual of either body crosses a
branch (a plain `cond` under autodiff keeps both bodies' residuals).
Where R would not be under N K (all experts held here) there is one
body and no conditional. Either way each live row is multiplied by the
same weights in the same group at the same offset.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

ROUTE_EPS = 1e-6


def route(h: jax.Array, router: jax.Array, bias: Optional[jax.Array],
          top_k: int, score: str = "sigmoid", *, scale: float = 1.0,
          eps: float = ROUTE_EPS) -> Tuple[jax.Array, jax.Array]:
    """(chosen experts [N, K] int32, their weights p [N, K] float32).
    The scores are taken in float32 at the highest matmul precision:
    2 H E operations a token, and who is chosen should not hang on a
    bfloat16 product. `score` "softmax" takes no bias and no `eps`.
    p = `scale` s / (sum of the chosen s + `eps`); a scale of 1 is not
    multiplied in."""
    logits = jnp.dot(h.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if score == "softmax":
        assert bias is None
        s_chosen, chosen = jax.lax.top_k(jax.nn.softmax(logits, axis=-1),
                                         top_k)
        chosen = chosen.astype(jnp.int32)
        p = s_chosen / jnp.sum(s_chosen, axis=-1, keepdims=True)
    else:
        assert score == "sigmoid", score
        s = jax.nn.sigmoid(logits)
        _, chosen = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
        s_chosen = jnp.take_along_axis(s, chosen, axis=-1)
        p = s_chosen / (jnp.sum(s_chosen, axis=-1, keepdims=True) + eps)
        chosen = chosen.astype(jnp.int32)
    return chosen, (p if scale == 1.0 else scale * p)


# the arrays between the sort and the sum back hold this many times the
# rows an all-valid, evenly routed batch would send to the held experts
# (PERF.md section 6, PR 31: the cell's live rows are a fifth of it, a
# collapsing router's 40% of the valid choices two thirds)
BOUND_OVER_EVEN = 2
ROW_TILE = 128


def row_bound(pairs: int, held: int, routed: int) -> int:
    """R, from shapes alone: how many of a layer's `pairs` = N K sorted
    (token, choice) pairs its arrays hold when `held` of `routed`
    experts are here. `pairs` itself where that is no less (nothing to
    leave out: one body, no conditional)."""
    tiles = -(-BOUND_OVER_EVEN * pairs * held // (routed * ROW_TILE))
    return min(tiles * ROW_TILE, pairs)


def fits(rows: jax.Array, bound: int) -> jax.Array:
    """Whether the layer's live pairs (`rows` [held], the rows each held
    expert took) are all among the first `bound` sorted pairs: the
    device's own choice between the two bodies."""
    return jnp.sum(rows) <= bound


def ran_at_bound(rows: jax.Array, pairs: int,
                 routed: int) -> Tuple[int, jax.Array]:
    """(R, whether a layer of `pairs` pairs whose held experts took
    `rows` ran at R): what `held_experts_ffn` decided, for the counts
    that leave the step. Never where R is the full length."""
    bound = row_bound(pairs, rows.shape[0], routed)
    return bound, fits(rows, bound) & (bound < pairs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _spread(h, order, inverse, k: int):
    """[N, H] -> [N K, H]: for every sorted pair its token's row, at the
    full length. The transpose is `_gather_back`, so neither direction
    scatters N K updates (the TPU's scatter pays by the update; PERF.md
    section 5)."""
    return jnp.take(h, order // k, axis=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gather_back(y, order, inverse, k: int):
    """[N K, H] -> [N, H]: each token's K sorted rows, summed."""
    rows = jnp.take(y, inverse, axis=0)
    return jnp.sum(rows.reshape(-1, k, y.shape[-1]), axis=1)


_spread.defvjp(
    lambda h, order, inverse, k: (_spread(h, order, inverse, k),
                                  (order, inverse)),
    lambda k, res, g: (_gather_back(g, *res, k), None, None))
_gather_back.defvjp(
    lambda y, order, inverse, k: (_gather_back(y, order, inverse, k),
                                  (order, inverse)),
    lambda k, res, g: (_spread(g, *res, k), None, None))


def _spread_head(h, token):
    """[N, H] -> [R, H]: the first R sorted pairs' token rows. Autodiff's
    transpose is `_sum_back_head`'s scatter of R rows."""
    return jnp.take(h, token, axis=0)


def _sum_back_head(y, token, n: int):
    """[R, H] -> [N, H]: every row added to its token's. R updates, not
    N K; autodiff's transpose is `_spread_head`'s gather."""
    return jnp.zeros((n, y.shape[-1]), y.dtype).at[token].add(y)


def _products(x, pair, p, w1, w3, w2, rows):
    """The held experts over x [L, H], the token rows of the first L
    sorted pairs (`pair` [L]: which pair each is): [L, H], weighted by
    the pairs' p, zero past the last group."""
    live = (jnp.arange(x.shape[0]) < jnp.sum(rows))[:, None]
    x = jnp.where(live, x, 0)

    def grouped(a, w):
        return jax.lax.ragged_dot(a, w.astype(a.dtype), rows)

    inner = jax.nn.silu(grouped(x, w1)) * grouped(x, w3)
    y = jnp.where(live, grouped(jnp.where(live, inner, 0), w2), 0)
    return y * jnp.take(p.reshape(-1), pair)[:, None].astype(y.dtype)


def _every_pair(h, p, w1, w3, w2, order, rows):
    """The body over all N K sorted pairs: right for every routing."""
    k = p.shape[1]
    inverse = jnp.argsort(order)
    y = _products(_spread(h, order, inverse, k), order, p, w1, w3, w2, rows)
    return _gather_back(y, order, inverse, k)


def _take_back_head(y, order, k: int):
    """[R, H] -> [N, H], `_sum_back_head` with no scatter: each of the
    N K pairs takes the row at its place in the sorted order, a zero row
    where that place is past R, and a token's K rows are summed. It
    reads N K rows where the scatter adds R, so it is the sum back of
    the programs that take no gradient (`_bounded`)."""
    padded = jnp.concatenate([y, jnp.zeros((1, y.shape[-1]), y.dtype)])
    place = jnp.minimum(jnp.argsort(order), y.shape[0])
    rows = jnp.take(padded, place, axis=0)
    return jnp.sum(rows.reshape(-1, k, y.shape[-1]), axis=1)


def _first_pairs(h, p, w1, w3, w2, order, rows, bound: int,
                 scatter: bool = True):
    """The body over the first `bound` sorted pairs: right when `fits`.
    `scatter` False: the rows go back by `_take_back_head`."""
    k = p.shape[1]
    head = order[:bound]
    token = head // k
    y = _products(_spread_head(h, token), head, p, w1, w3, w2, rows)
    if not scatter:
        return _take_back_head(y, order, k)
    return _sum_back_head(y, token, h.shape[0])


def _bodies(bound: int, scatter: bool = True):
    """(the body where the live pairs fit `bound`, the body where not)"""
    return (functools.partial(_first_pairs, bound=bound, scatter=scatter),
            _every_pair)


def _differentiated(h, p, w1, w3, w2, order, rows, bound: int):
    return jax.lax.cond(fits(rows, bound), *_bodies(bound),
                        h, p, w1, w3, w2, order, rows)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _bounded(h, p, w1, w3, w2, order, rows, bound: int):
    """`_first_pairs` where the layer's live pairs fit `bound`,
    `_every_pair` where not. Under differentiation the forward is
    `_differentiated`, whose rows go back by a scatter-add of R rows,
    and the backward keeps the inputs alone and takes, behind the same
    test, the vjp of the body that ran. This, the function itself, is
    what evaluation, prediction and serving run, and it holds no
    scatter: on the v5e XLA's scatter-add of 512 rows into
    [1600, 2048], as it stands inside the qwen3_next predict step of 8
    methods, does not return (PERF.md section 6, PR 32), so the
    programs that have to answer add nothing by index."""
    return jax.lax.cond(fits(rows, bound), *_bodies(bound, scatter=False),
                        h, p, w1, w3, w2, order, rows)


def _bounded_fwd(h, p, w1, w3, w2, order, rows, bound):
    return (_differentiated(h, p, w1, w3, w2, order, rows, bound),
            (h, p, w1, w3, w2, order, rows))


def _bounded_bwd(bound, res, g):
    *floats, order, rows = res

    def pull(body):
        def run(floats, g):
            return jax.vjp(lambda *f: body(*f, order, rows), *floats)[1](g)
        return run

    grads = jax.lax.cond(fits(rows, bound), *map(pull, _bodies(bound)),
                         floats, g)
    return (*grads, None, None)


_bounded.defvjp(_bounded_fwd, _bounded_bwd)


def held_experts_ffn(h: jax.Array, valid: jax.Array, chosen: jax.Array,
                     p: jax.Array, w1: jax.Array, w3: jax.Array,
                     w2: jax.Array, first_expert: int,
                     routed: Optional[int] = None
                     ) -> Tuple[jax.Array, jax.Array]:
    """The held experts' part of the layer's output for tokens h [N, H]
    (`valid` [N] bool: a masked token is routed nowhere), and the rows
    each held expert took, int32 [held]. `w1`, `w3` [held, H, F] and
    `w2` [held, F, H] are experts `first_expert ..` of the `routed` the
    router scores (None: all of them are held here)."""
    n, k = chosen.shape
    held = w1.shape[0]
    local = chosen - first_expert
    here = (local >= 0) & (local < held) & valid[:, None]
    # a pair routed elsewhere sorts after the last held expert's rows
    key = jnp.where(here, local, held).reshape(-1)
    order = jnp.argsort(key)                       # stable
    rows = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
    bound = row_bound(n * k, held, routed or held)
    if bound == n * k:
        return _every_pair(h, p, w1, w3, w2, order, rows), rows
    return _bounded(h, p, w1, w3, w2, order, rows, bound), rows
