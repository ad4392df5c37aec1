"""Dropout whose mask is drawn once a training step.

`jnp.where(jax.random.bernoulli(key, keep, x.shape), x / keep, 0)` leaves
XLA free to fuse the generator into every consumer of the mask: in the
bag step the forward's apply, the backward's recompute of the
pre-activation and the backward's `d_pre @ W^T` each ran a whole
threefry over the `[B, C, D]` contexts (PERF.md section 7). Here the
mask is drawn once, kept as 4-bit integers (XLA packs eight to a 32-bit
word) and passed through an `optimization_barrier`, so no consumer can
reach the generator; the forward's apply and the backward read the kept
bits. The values are the expression above bit for bit, and so is
autodiff's gradient with respect to `x`.

Why 4 bits: every use of such an element is elementwise, so XLA fuses
the read into the matmuls that take the mask. Bits packed by hand, 32
to a `uint32` along an axis, are unpacked by a broadcast and a reshape,
which XLA does not fuse there: each use wrote a `[B, C, D]` array of
words. A 2-bit element compiles, but the TPU runtime halts on the step
or returns another mask (PERF.md section 7).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def dropout(x: jax.Array, key: Optional[jax.Array],
            keep_rate: float) -> jax.Array:
    """`x` with each element kept at `keep_rate` and scaled by
    `1 / keep_rate`, the mask `jax.random.bernoulli(key, keep_rate,
    x.shape)`; `x` itself, with nothing drawn, when `key` is None or
    `keep_rate` is 1."""
    if key is None or keep_rate >= 1.0:
        return x
    bits = jax.lax.optimization_barrier(
        jax.random.bernoulli(key, keep_rate, x.shape).astype(jnp.uint4))
    return jnp.where(bits != 0, x / keep_rate, 0.0)
