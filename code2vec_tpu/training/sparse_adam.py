"""Sparse-row (lazy) Adam state + the dense-carrier oracle update.

SURVEY.md §8.4 item 2: table traffic dominates the java-large step, and
a batch touches far fewer than V unique rows — BENCH_r05 (git history
at a4bf2f7) puts the shipped dense path at 6.66M pc/s/chip against an
8.48M fwd/bwd floor
(optimizer efficiency 0.786, HBM at 15.7% of the 637 GB/s ceiling), so
moments and parameters are updated for TOUCHED ROWS ONLY. (The "45 ms
dense / ~9 GB moment traffic" figures previously quoted here were
pre-round-3 Adam-table measurements; adafactor tables + bf16 storage
retired them — BENCH_r*.json is the trajectory of record.)

The production path is training/sparse_update.py (round 13): dedup +
segment-sum into a COMPACT [U, E] gradient, then a live-rows-only
row-Adam / requantize-aware apply — fused into one Pallas pass over the
live rows on TPU (`--sparse_update_pallas`), XLA reference elsewhere.
`row_adam_update` below is the ORIGINAL dense-carrier form (scatter-ADD
cotangents into a dense [V, E] buffer — the VJP of a gather — gather
back at the touched ids, per-row Adam, idempotent scatter-SET): it
survives as the bit-parity oracle the compact path is property-tested
against (tests/test_sparse_update.py) and for A/B attribution of the
carrier's cost.

Semantics note (documented deviation): TF1's AdamOptimizer._apply_sparse
decays m/v over ALL rows each step (which is exactly the dense traffic we
must avoid); this implementation is the LazyAdam variant — untouched rows
keep stale moments. LazyAdam is the standard large-embedding practice and
matches reference quality in our integration tests; set
Config.SPARSE_EMBEDDING_UPDATES=False for strict dense-Adam semantics.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp


class RowAdamState(NamedTuple):
    m: jax.Array  # [V, E] first moment (same rows as the table)
    v: jax.Array  # [V, E] second moment


def init_row_adam(table) -> RowAdamState:
    """Zero moments for a table — f32 regardless of storage dtype
    (bf16 moments would lose the low accumulation bits Adam needs;
    int8 {q, s} tables get moments shaped like q). Moment rows are
    only ever read/written at touched ids, so the f32 cost is HBM
    capacity, not step traffic."""
    shape = table["q"].shape if isinstance(table, dict) else table.shape
    return RowAdamState(m=jnp.zeros(shape, jnp.float32),
                        v=jnp.zeros(shape, jnp.float32))


def adam_step_size(count, lr: float, b1: float, b2: float):
    """The bias-corrected Adam step size lr * sqrt(1 - b2^c) /
    (1 - b1^c), an f32 scalar; `count` is the (already incremented)
    global step shared with the dense-parameter optimizer so bias
    correction matches. 1 - b^c is taken as -expm1(c * log b): written
    as `1 - b ** c` it cancels (0.999^3 = 0.997), one ulp of the power
    moves the step size by 1e-5, and two compilations of that one line
    — XLA's and the one feeding the Mosaic kernel — then disagree by
    that much (seen on the chip, PR 21)."""
    c = count.astype(jnp.float32)
    return (lr * jnp.sqrt(-jnp.expm1(c * math.log(b2)))
            / -jnp.expm1(c * math.log(b1)))


def row_adam_update(table: jax.Array, state: RowAdamState,
                    ids: jax.Array, grads: jax.Array, *, count: jax.Array,
                    lr: float, b1: float = 0.9, b2: float = 0.999,
                    eps: float = 1e-8, vocab_size: int | None = None):
    """Apply one lazy-Adam step to the rows named by `ids`.

    Duplicate handling without any sort: scatter-ADD the cotangents into
    one dense [V, E] gradient-sum buffer (exactly what the VJP of a
    gather would emit), gather the per-row sums back at `ids`, compute
    the Adam row update, and scatter-SET results — duplicates of a row
    all write identical values, so the sets are idempotent. The dense
    buffer costs one zeros+scatter pass (~table-sized write); the win is
    skipping the two full m/v read-modify-write passes of dense Adam.

    `count` is the (already incremented) global step, shared with the
    dense-parameter optimizer so bias correction matches.
    Returns (new_table, new_state).
    """
    del vocab_size  # all ids are in-range here; kept for API stability
    g_rows = grads.astype(table.dtype)
    g_sum_dense = jnp.zeros_like(table).at[ids].add(g_rows)  # [V, E]
    g = jnp.take(g_sum_dense, ids, axis=0)                   # [N, E]

    m_rows = jnp.take(state.m, ids, axis=0)
    v_rows = jnp.take(state.v, ids, axis=0)
    p_rows = jnp.take(table, ids, axis=0)

    m_new = b1 * m_rows + (1.0 - b1) * g
    v_new = b2 * v_rows + (1.0 - b2) * jnp.square(g)
    lr_t = adam_step_size(count, lr, b1, b2)
    p_new = p_rows - lr_t * m_new / (jnp.sqrt(v_new) + eps)

    table = table.at[ids].set(p_new)
    m = state.m.at[ids].set(m_new)
    v = state.v.at[ids].set(v_new)
    return table, RowAdamState(m=m, v=v)
