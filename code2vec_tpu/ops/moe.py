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
the same router, not a third. A block that scores its tokens elsewhere
(`model_type` `smallthinker`: from the layer's input, before attention)
takes `router_logits` there and hands them to `choose`, `route`'s second
half. Its experts are ReGLUs, relu(h W1_e) * (h W3_e)
(`held_experts_ffn(act="relu")`), which also count the live inner
entries relu(h W1_e) that are zero.

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
and the sum back is gathered, selected, weighted and added back at its
full length whatever is live in it. So those arrays hold `row_bound`
rows, R = the rows the held experts would take from an all-valid,
evenly routed batch (N K held / E), up to a whole number of 256-row
tiles: the first R sorted pairs, which are every live pair whenever
the live pairs are at most R. A near-even router's live pairs fill
62-76% of it in the cells, and the tile is 256 because the grouped
product pays 19-41% a call over rows that are no multiple of 256
(PERF.md section 6). A layer whose live pairs (or the busiest share's
rows, below) are more than R (the device alone knows; `fits`) runs the
same body over all N K sorted pairs instead: one `lax.cond`, inside a
`custom_vjp` whose backward is its own `cond` over the two bodies'
vjps, so that no residual of either body crosses a branch (a plain
`cond` under autodiff keeps both bodies' residuals). Where R would not
be under N K (all experts held here) there is one body and no
conditional. Either way each live row is multiplied by the same
weights in the same group at the same offset.

A layer can be told to wait for its busiest share (`busiest`,
`model_type` `smallthinker`): were the E experts cut into shares of
held, one a chip, a synchronous layer would last on every chip as long
as the products of the share that took the most rows. So the products
run over that many rows (`busiest_share`, from the choices of every
valid token), the rows past the live ones dead: zeros, in the last
held expert's group, out of every count and output. On the TPU the
products' cost then follows the busiest share's rows, not this one's,
and a layer whose busiest share is more than R runs every pair, so
that the wait is never cut short.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

ROUTE_EPS = 1e-6


def router_logits(h: jax.Array, router: jax.Array) -> jax.Array:
    """h W_r [N, E] in float32 at the highest matmul precision: 2 H E
    operations a token, and who is chosen should not hang on a bfloat16
    product."""
    return jnp.dot(h.astype(jnp.float32), router.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)


def route(h: jax.Array, router: jax.Array, bias: Optional[jax.Array],
          top_k: int, score: str = "sigmoid", *, scale: float = 1.0,
          eps: float = ROUTE_EPS) -> Tuple[jax.Array, jax.Array]:
    """(chosen experts [N, K] int32, their weights p [N, K] float32):
    `choose` over `router_logits(h, router)`."""
    return choose(router_logits(h, router), bias, top_k, score,
                  scale=scale, eps=eps)


def choose(logits: jax.Array, bias: Optional[jax.Array], top_k: int,
           score: str = "sigmoid", *, scale: float = 1.0,
           eps: float = ROUTE_EPS) -> Tuple[jax.Array, jax.Array]:
    """`route` from the router's float32 logits [N, E], for a block that
    scores its tokens elsewhere (`seq_block.residual_layer`'s `early`).
    `score` "softmax" takes no bias and no `eps`. p = `scale` s / (sum of
    the chosen s + `eps`); a scale of 1 is not multiplied in."""
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


# the arrays between the sort and the sum back hold the rows an
# all-valid, evenly routed batch would send to the held experts, up to
# whole tiles of the grouped product (PERF.md section 6)
ROW_TILE = 256


def row_bound(pairs: int, held: int, routed: int) -> int:
    """R, from shapes alone: how many of a layer's `pairs` = N K sorted
    (token, choice) pairs its arrays hold when `held` of `routed`
    experts are here. `pairs` itself where that is no less (nothing to
    leave out: one body, no conditional)."""
    tiles = -(-pairs * held // (routed * ROW_TILE))
    return min(tiles * ROW_TILE, pairs)


def fits(rows: jax.Array, bound: int,
         busiest: Optional[jax.Array] = None) -> jax.Array:
    """Whether the layer's live pairs (`rows` [held], the rows each held
    expert took) are all among the first `bound` sorted pairs, and the
    `busiest` share's rows too where the layer waits for them: the
    device's own choice between the two bodies. Fewer rows than the
    busiest share's would cut the wait short."""
    need = jnp.sum(rows)
    if busiest is not None:
        need = jnp.maximum(need, busiest)
    return need <= bound


def ran_at_bound(rows: jax.Array, pairs: int, routed: int,
                 busiest: Optional[jax.Array] = None
                 ) -> Tuple[int, jax.Array]:
    """(R, whether a layer of `pairs` pairs whose held experts took
    `rows`, waiting for `busiest`, ran at R): what `held_experts_ffn`
    decided, for the counts that leave the step. Never where R is the
    full length."""
    bound = row_bound(pairs, rows.shape[0], routed)
    return bound, fits(rows, bound, busiest) & (bound < pairs)


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


# the experts' activation, by name: silu(h W1) * (h W3) is the SwiGLU,
# relu(h W1) * (h W3) the ReGLU
ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def counts_zeros(act: str) -> bool:
    """Whether the bodies under `act` also hand back the live rows' inner
    entries that are zero (a ReGLU's: the SwiGLU's are never zero), as
    `(y, zeros)` in the place of `y`."""
    return act == "relu"


def _products(x, pair, p, w1, w3, w2, rows, act: str = "silu",
              busiest=None):
    """The held experts over x [L, H], the token rows of the first L
    sorted pairs (`pair` [L]: which pair each is): [L, H], weighted by
    the pairs' p, zero past the last group; for a ReGLU (`counts_zeros`)
    beside it the live rows' inner entries act(x W1_e) that are zero, an
    int32 scalar. `busiest` (`busiest_share`; None: the live rows): how
    many rows the products run over, the dead rows past the live ones
    (zeros) joining the last group, up to L."""
    live = (jnp.arange(x.shape[0]) < jnp.sum(rows))[:, None]
    x = jnp.where(live, x, 0)
    groups = rows
    if busiest is not None:
        dead = jnp.clip(busiest, jnp.sum(rows), x.shape[0]) - jnp.sum(rows)
        groups = rows.at[-1].add(dead.astype(rows.dtype))

    def grouped(a, w):
        return jax.lax.ragged_dot(a, w.astype(a.dtype), groups)

    gate = grouped(x, w1)
    inner = ACTIVATIONS[act](gate) * grouped(x, w3)
    y = jnp.where(live, grouped(jnp.where(live, inner, 0), w2), 0)
    y = y * jnp.take(p.reshape(-1), pair)[:, None].astype(y.dtype)
    if not counts_zeros(act):
        return y
    return y, jnp.sum(live & (gate <= 0), dtype=jnp.int32)


def _each(result, fn):
    """`fn` on a body's output, the zeros passed as they are."""
    if isinstance(result, tuple):
        return fn(result[0]), result[1]
    return fn(result)


def _every_pair(h, p, w1, w3, w2, order, rows, busiest=None,
                act: str = "silu"):
    """The body over all N K sorted pairs: right for every routing.
    [N, H] (and `_products`' zeros)."""
    k = p.shape[1]
    inverse = jnp.argsort(order)
    y = _products(_spread(h, order, inverse, k), order, p, w1, w3, w2, rows,
                  act, busiest)
    return _each(y, lambda y: _gather_back(y, order, inverse, k))


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


def _first_pairs(h, p, w1, w3, w2, order, rows, busiest=None, *,
                 bound: int, scatter: bool = True, act: str = "silu"):
    """The body over the first `bound` sorted pairs: right when `fits`.
    `scatter` False: the rows go back by `_take_back_head`. [N, H] (and
    `_products`' zeros)."""
    k = p.shape[1]
    head = order[:bound]
    token = head // k
    y = _products(_spread_head(h, token), head, p, w1, w3, w2, rows, act,
                  busiest)
    if not scatter:
        return _each(y, lambda y: _take_back_head(y, order, k))
    return _each(y, lambda y: _sum_back_head(y, token, h.shape[0]))


def _bodies(bound: int, scatter: bool = True, act: str = "silu"):
    """(the body where the live pairs fit `bound`, the body where not)"""
    return (functools.partial(_first_pairs, bound=bound, scatter=scatter,
                              act=act),
            functools.partial(_every_pair, act=act))


def _fenced(*operands):
    """The operands as they are, behind an `optimization_barrier`: XLA
    otherwise sinks their producers (the staircase's packing of the
    layer's input, the router's weights) into the conditional's
    branches, where the body over R rows ran 8 ms a step slower on the
    v5e in the lfm2moe cell (PERF.md section 5)."""
    return jax.lax.optimization_barrier(operands)


def _differentiated(h, p, w1, w3, w2, order, rows, busiest, bound: int,
                    act: str):
    operands = _fenced(h, p, w1, w3, w2, order, rows, busiest)
    return jax.lax.cond(fits(rows, bound, busiest),
                        *_bodies(bound, act=act), *operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9))
def _bounded(h, p, w1, w3, w2, order, rows, busiest, bound: int, act: str):
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
    operands = _fenced(h, p, w1, w3, w2, order, rows, busiest)
    return jax.lax.cond(fits(rows, bound, busiest),
                        *_bodies(bound, scatter=False, act=act), *operands)


def _bounded_fwd(h, p, w1, w3, w2, order, rows, busiest, bound, act):
    return (_differentiated(h, p, w1, w3, w2, order, rows, busiest, bound,
                            act),
            (h, p, w1, w3, w2, order, rows, busiest))


def _bounded_bwd(bound, act, res, g):
    *floats, order, rows, busiest = res
    counts = counts_zeros(act)
    if counts:
        g = g[0]                # the zeros' is none: they are a count

    def pull(body):
        def run(floats, g):
            def output(*f):
                y = body(*f, order, rows, busiest)
                return y[0] if counts else y
            return jax.vjp(output, *floats)[1](g)
        return run

    floats, g = _fenced(floats, g)
    grads = jax.lax.cond(fits(rows, bound, busiest),
                         *map(pull, _bodies(bound, act=act)), floats, g)
    return (*grads, None, None, None)


_bounded.defvjp(_bounded_fwd, _bounded_bwd)


def busiest_share(chosen: jax.Array, valid: jax.Array, first_expert: int,
                  held: int, routed: int) -> jax.Array:
    """The rows the busiest share took, int32: the `routed` experts cut
    into shares of `held` from `first_expert` on (this process's share
    the first), and of the valid tokens' choices `chosen` [N, K] the
    most that fell in one share. Counted by comparison and sum, not by
    `bincount`: on the TPU that is a scatter-add of N K updates, which
    pays by the update (PERF.md section 6, PR 42)."""
    shares = -(-routed // held)
    share = ((chosen - first_expert) % routed) // held
    taken = jnp.sum((share[..., None] == jnp.arange(shares))
                    & valid[:, None, None], axis=(0, 1), dtype=jnp.int32)
    return jnp.max(taken)


def held_experts_ffn(h: jax.Array, valid: jax.Array, chosen: jax.Array,
                     p: jax.Array, w1: jax.Array, w3: jax.Array,
                     w2: jax.Array, first_expert: int,
                     routed: Optional[int] = None, act: str = "silu",
                     busiest: Optional[jax.Array] = None
                     ) -> Tuple[jax.Array, jax.Array]:
    """The held experts' part of the layer's output for tokens h [N, H]
    (`valid` [N] bool: a masked token is routed nowhere), and its counts,
    int32: the rows each held expert took [held], and, for a ReGLU
    (`act` "relu"), two more: how many of the live rows' inner entries
    relu(h W1_e) are zero, and how many there are (the rows x F; their
    ratio is the share of the down-projection a sparse kernel could
    skip). `w1`, `w3` [held, H, F] and `w2` [held, F, H]
    are experts `first_expert ..` of the `routed` the router scores
    (None: all of them are held here); `act` names the activation
    (`ACTIVATIONS`). `busiest` (None: the live rows), the rows the
    busiest share of the layer took (`busiest_share`): the products run
    over that many rows, the rows past the live ones dead, as a
    synchronous expert-parallel layer lasts on every chip as long as on
    the busiest; the output and the counts are those of the live
    rows."""
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
        out = _every_pair(*_fenced(h, p, w1, w3, w2, order, rows, busiest),
                          act=act)
    else:
        out = _bounded(h, p, w1, w3, w2, order, rows, busiest, bound, act)
    if not counts_zeros(act):
        return out, rows
    out, zeros = out
    entries = jnp.sum(rows) * w1.shape[2]
    return out, jnp.concatenate([rows, jnp.stack([zeros, entries])])
