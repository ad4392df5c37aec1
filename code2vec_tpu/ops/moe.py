"""Routed experts for one expert-parallel share: the router over every
expert of the layer, and the grouped matrix product over the experts
this process holds.

  s      = sigmoid(h W_r)                      [N, E], float32
  chosen = top K of (s + bias)                 the bias selects only
  p_e    = s_e / (sum of the K chosen s + 1e-6)
  FF(h)  = sum over chosen e held here of p_e (silu(h W1_e) * (h W3_e)) W2_e

The layer is told the first expert it holds and how many; it routes over
all E and computes its own part. What the absent experts would add is
left out, and nothing stands in for the exchange that would bring other
chips' rows here.

The grouped product is exact and its shapes are static: the N x K
(token, choice) pairs are sorted by held expert, pairs that chose an
expert held elsewhere (and every pair of a masked token) last, and
`jax.lax.ragged_dot` runs over the sorted rows with the held experts'
row counts as group sizes. No capacity, no dropped row: were every
token to choose one held expert, its group would be N rows long. On
the TPU XLA lowers `ragged_dot` to a Mosaic kernel whose grid follows
the group sizes, so the work is that of the rows routed here; rows past
the last group belong to no expert and are masked on both sides of each
product (a kernel need not write them).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

ROUTE_EPS = 1e-6


def route(h: jax.Array, router: jax.Array, bias: jax.Array,
          top_k: int) -> Tuple[jax.Array, jax.Array]:
    """(chosen experts [N, K] int32, their weights p [N, K] float32).
    The scores are taken in float32 at the highest matmul precision:
    2 H E operations a token, and who is chosen should not hang on a
    bfloat16 product."""
    logits = jnp.dot(h.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits)
    _, chosen = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
    s_chosen = jnp.take_along_axis(s, chosen, axis=-1)
    p = s_chosen / (jnp.sum(s_chosen, axis=-1, keepdims=True) + ROUTE_EPS)
    return chosen.astype(jnp.int32), p


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _spread(h, order, inverse, k: int):
    """[N, H] -> [N K, H]: for every sorted pair its token's row. The
    transpose is `_gather_back`, so neither direction scatters (the
    TPU's scatter pays by the update; PERF.md section 5)."""
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


def held_experts_ffn(h: jax.Array, valid: jax.Array, chosen: jax.Array,
                     p: jax.Array, w1: jax.Array, w3: jax.Array,
                     w2: jax.Array, first_expert: int
                     ) -> Tuple[jax.Array, jax.Array]:
    """The held experts' part of the layer's output for tokens h [N, H]
    (`valid` [N] bool: a masked token is routed nowhere), and the rows
    each held expert took, int32 [held]. `w1`, `w3` [held, H, F] and
    `w2` [held, F, H] are experts `first_expert ..` of the layer."""
    n, k = chosen.shape
    held = w1.shape[0]
    local = chosen - first_expert
    here = (local >= 0) & (local < held) & valid[:, None]
    # a pair routed elsewhere sorts after the last held expert's rows
    key = jnp.where(here, local, held).reshape(-1)
    order = jnp.argsort(key)                       # stable
    inverse = jnp.argsort(order)
    rows = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
    live = (jnp.arange(n * k) < jnp.sum(rows))[:, None]
    x = jnp.where(live, _spread(h, order, inverse, k), 0)

    def grouped(a, w):
        return jax.lax.ragged_dot(a, w.astype(a.dtype), rows)

    inner = jax.nn.silu(grouped(x, w1)) * grouped(x, w3)
    y = jnp.where(live, grouped(jnp.where(live, inner, 0), w2), 0)
    y = y * jnp.take(p.reshape(-1), order)[:, None].astype(y.dtype)
    return _gather_back(y, order, inverse, k), rows
