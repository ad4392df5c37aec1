#!/usr/bin/env python3
"""Row-block-size x vocab-size microbench for the fused Pallas
requantize row-pass (ops/pallas_requant.py) vs the multi-pass XLA
reference — the tuning driver for the kernel's _BLOCK_ROWS knob and
the per-phase attribution behind BASELINE.md's int8 requantize story.

Emits one JSON line per (vocab, block_rows) cell: fused ms, reference
ms, analytic bytes of one fused sweep (ops/pallas_requant.
requant_traffic_bytes) and the achieved GB/s, all slope-timed
(tools/_bench_common.slope_time — cancels the fixed dispatch
cost).

Interpret-safe: off-TPU the kernel runs in Pallas interpreter mode, so
the default grid auto-shrinks to a smoke-scale sweep (off-TPU numbers
exercise the machinery, they do NOT attribute the chip). Tier-1 never
runs this — the pytest entry point is marked `slow`
(tests/test_requant_sweep.py; the tier-1 command deselects
`-m 'not slow'`).

Usage:
  python tools/requant_sweep.py \
      [--vocabs 65536,262144,1048576] [--blocks 128,256,512,1024] \
      [--emb 128] [--steps 20] [--out sweep.jsonl]
"""

from __future__ import annotations

import argparse
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
    ap.add_argument("--emb", type=int, default=128)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--out", default=None, help="also append JSONL here")
    a = ap.parse_args(argv)
    from code2vec_tpu.device import enable_compile_cache
    enable_compile_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from code2vec_tpu.ops.pallas_requant import (requant_traffic_bytes,
                                                 requantize_fused)
    from code2vec_tpu.ops.quant import quantize_table, requantize_reference
    from tools._bench_common import slope_time

    # ONE jitted callable per implementation, hoisted out of the sweep
    # loops: different (vocab, block) cells retrace into the SAME shape-
    # keyed compile cache instead of rebuilding an empty-cache callable
    # per cell (the grandfathered graftlint retrace-hazard entries).
    ref_jit = jax.jit(requantize_reference)
    fused_jit = jax.jit(requantize_fused,
                        static_argnames=("block_rows",))

    on_tpu = jax.default_backend() == "tpu"
    # off-TPU the kernel interprets: shrink the default grid so the
    # sweep stays a smoke (the chip numbers come from a TPU run)
    vocabs = [int(x) for x in
              (a.vocabs or ("65536,262144,1048576" if on_tpu
                            else "2048")).split(",")]
    blocks = [int(x) for x in
              (a.blocks or ("128,256,512,1024" if on_tpu
                            else "128,256")).split(",")]
    warmup, base = (5, 10) if on_tpu else (1, 2)

    def timed_ms(fn, sync_key):
        """Slope-time `fn(rng) -> QuantTable` with pre-split keys and a
        scalar-transfer hard sync (the _bench_common contract)."""
        def chain(n, rng):
            rng, sub = jax.random.split(rng)
            keys = list(jax.random.split(sub, max(n, 1)))
            t0 = time.perf_counter()
            out = None
            for i in range(n):
                out = fn(keys[i])
            float(out["s"].ravel()[0])
            return time.perf_counter() - t0, rng
        return max(slope_time(chain, jax.random.PRNGKey(sync_key),
                              a.steps, warmup=warmup, base=base), 1e-9) \
            * 1e3

    rows = []
    for V in vocabs:
        r = np.random.default_rng(V)
        qt = quantize_table(jnp.asarray(
            r.normal(size=(V, a.emb)) * 0.3, jnp.float32))
        upd = jnp.asarray(r.normal(size=(V, a.emb)) * 1e-4, jnp.bfloat16)
        nbytes = requant_traffic_bytes(qt, upd)
        ref_ms = timed_ms(lambda rng: ref_jit(qt, upd, rng), 1)
        for br in blocks:
            fused_ms = timed_ms(
                lambda rng, br=br: fused_jit(qt, upd, rng,
                                             block_rows=br), 2)
            row = {"vocab": V, "emb": a.emb, "block_rows": br,
                   "mode": "tpu" if on_tpu else "interpret",
                   "fused_ms": round(fused_ms, 3),
                   "reference_ms": round(ref_ms, 3),
                   "sweep_bytes": int(nbytes),
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
