#!/usr/bin/env python3
"""Block-size x id-count x vocab microbench for the fused Pallas
live-row sparse update (ops/pallas_sparse_update.py) vs the XLA
gather/scatter reference — the tuning driver for the facade's
_BLOCK_ROWS knob and the per-phase attribution behind BASELINE.md's
round-13 sparse-update story (the requant_sweep playbook one level
up).

Emits one JSON line per (vocab, n_ids, block_rows) cell: fused ms,
reference ms, the analytic [U, E]-aware bytes of one apply
(training/sparse_update.sparse_update_traffic_bytes at the cell's
MEASURED unique-row count) and the achieved GB/s, all slope-timed
(tools/_bench_common.slope_time — cancels the fixed dispatch
cost). The timed callable is the exact facade
composition the sparse train step runs: dedup + segment-sum + live-row
apply, state threaded through a donated jit so the in-place aliasing
matches production.

Interpret-safe: off-TPU the kernel runs in Pallas interpreter mode, so
the default grid auto-shrinks to a smoke-scale sweep (off-TPU numbers
exercise the machinery, they do NOT attribute the chip). Tier-1 never
runs this — the pytest entry point is marked `slow`
(tests/test_sparse_update_sweep.py; the tier-1 command deselects
`-m 'not slow'`).

Usage:
  python tools/sparse_update_sweep.py \
      [--vocabs 65536,262144,1048576] [--blocks 128,256,512,1024] \
      [--ids 409600] [--emb 128] [--dtype bfloat16|float32|int8] \
      [--steps 20] [--out sweep.jsonl]
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--vocabs", default=None,
                    help="comma-separated table row counts")
    ap.add_argument("--blocks", default=None,
                    help="comma-separated kernel row-block sizes")
    ap.add_argument("--ids", type=int, default=None,
                    help="gathered ids per apply (default: 2*B*C on "
                         "TPU — the token-table workload — else a "
                         "smoke count)")
    ap.add_argument("--emb", type=int, default=128)
    ap.add_argument("--dtype", default="bfloat16",
                    choices=["bfloat16", "float32", "int8"],
                    help="table storage dtype (int8 sweeps the "
                         "requantize-aware row update)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--out", default=None, help="also append JSONL here")
    a = ap.parse_args(argv)
    from code2vec_tpu.device import enable_compile_cache
    enable_compile_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from code2vec_tpu.ops.quant import quantize_table
    from code2vec_tpu.training import sparse_update as su
    from code2vec_tpu.training.sparse_adam import init_row_adam
    from tools._bench_common import BATCH, CTX, slope_time

    on_tpu = jax.default_backend() == "tpu"
    vocabs = [int(x) for x in
              (a.vocabs or ("65536,262144,1048576" if on_tpu
                            else "2048")).split(",")]
    blocks = [int(x) for x in
              (a.blocks or ("128,256,512,1024" if on_tpu
                            else "128,256")).split(",")]
    n_ids = a.ids if a.ids is not None else \
        (2 * BATCH * CTX if on_tpu else 4096)
    warmup, base = (5, 10) if on_tpu else (1, 2)
    quantized = a.dtype == "int8"
    dtype = jnp.bfloat16 if a.dtype == "bfloat16" else jnp.float32

    # ONE donated jitted callable per table layout, hoisted out of the
    # sweep loops: different (vocab, block) cells retrace into the SAME
    # shape/static-keyed compile cache instead of rebuilding an
    # empty-cache callable per cell (the requant_sweep lesson). The
    # donation makes the fused path's input->output aliasing real, as
    # in the production train step.
    @functools.partial(jax.jit, donate_argnums=(0, 1),
                       static_argnames=("fused", "block_rows"))
    def apply_float(table, state, count, ids, grads, fused, block_rows):
        t, s = su.sparse_row_adam(table, state, ids, grads, count=count,
                                  lr=1e-3, fused=fused,
                                  block_rows=block_rows)
        return t, s, count + 1

    @functools.partial(jax.jit, donate_argnums=(0, 1),
                       static_argnames=("fused", "block_rows"))
    def apply_int8(qt, state, count, ids, grads, rng, fused, block_rows):
        t, s = su.sparse_requant_adam(qt, state, ids, grads, rng,
                                      count=count, lr=1e-3, fused=fused,
                                      block_rows=block_rows)
        return t, s, count + 1

    def timed_ms(make_state, run_one):
        """Slope-time `run_one(state, key) -> state` threading the
        donated (table, state, count) chain, hard-synced via a scalar
        host transfer (the _bench_common contract)."""
        def chain(n, st):
            state, rng = st
            rng, sub = jax.random.split(rng)
            keys = list(jax.random.split(sub, max(n, 1)))
            t0 = time.perf_counter()
            for i in range(n):
                state = run_one(state, keys[i])
            tbl = state[0]["s"] if quantized else state[0]
            float(tbl.ravel()[0])
            return time.perf_counter() - t0, (state, rng)
        return max(slope_time(chain, (make_state(),
                                      jax.random.PRNGKey(3)),
                              a.steps, warmup=warmup, base=base),
                   1e-9) * 1e3

    rows = []
    for V in vocabs:
        r = np.random.default_rng(V)
        base_tbl = jnp.asarray(r.normal(size=(V, a.emb)) * 0.3,
                               jnp.float32)
        table = quantize_table(base_tbl) if quantized \
            else base_tbl.astype(dtype)
        ids = jnp.asarray(r.integers(0, V, n_ids), jnp.int32)
        grads = jnp.asarray(r.normal(size=(n_ids, a.emb)) * 1e-3,
                            jnp.bfloat16 if not quantized
                            and dtype == jnp.bfloat16 else jnp.float32)
        unique_rows = int(np.unique(np.asarray(ids)).size)
        grad_itemsize = grads.dtype.itemsize

        def make_state(table=table):
            return (jax.tree_util.tree_map(jnp.copy, table),
                    init_row_adam(table), jnp.asarray(1, jnp.int32))

        for br in blocks:
            nbytes = su.sparse_update_traffic_bytes(
                table, n_ids, unique_rows,
                grad_itemsize=grad_itemsize, block_rows=br)

            def run_one(fused):
                if quantized:
                    return lambda st, k: apply_int8(
                        st[0], st[1], st[2], ids, grads, k,
                        fused=fused, block_rows=br)
                return lambda st, k: apply_float(
                    st[0], st[1], st[2], ids, grads,
                    fused=fused, block_rows=br)

            ref_ms = timed_ms(make_state, run_one(False))
            fused_ms = timed_ms(make_state, run_one(True))
            row = {"vocab": V, "emb": a.emb, "n_ids": n_ids,
                   "dtype": a.dtype, "block_rows": br,
                   "mode": "tpu" if on_tpu else "interpret",
                   "unique_rows": unique_rows,
                   "fused_ms": round(fused_ms, 3),
                   "reference_ms": round(ref_ms, 3),
                   "update_bytes": int(nbytes),
                   "fused_gbps": round(
                       nbytes / (fused_ms / 1e3) / 1e9, 2)}
            rows.append(row)
            print(json.dumps(row), flush=True)

    if a.out:
        with open(a.out, "a", encoding="utf-8") as f:
            for row in rows:
                f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main()
