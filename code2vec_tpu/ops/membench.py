"""HBM streaming-bandwidth measurement (shared by bench.py and
tools/profile_step.py — methodology-critical, keep ONE copy).

The copy loop runs INSIDE one jit (fori_loop) so per-call dispatch
latency doesn't pollute the number, with an i-dependent term in the
body so XLA cannot fold the K copies into one multiply (a foldable
bf16 body reports an impossible 9.9 TB/s). Outer chains are
slope-timed (two lengths, differenced) to cancel the fixed sync
overhead.
"""

from __future__ import annotations

import time


def measure_hbm_ceiling(gib: float = 1.0, inner_loops: int = 32) -> float:
    """Returns effective streaming bandwidth in bytes/sec of a
    read+write copy over a `gib`-GiB f32 buffer."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    n = int(gib * 256 * 1024 * 1024)
    big = jnp.zeros((n,), jnp.float32)
    K = inner_loops

    @jax.jit
    def copyN(x):
        return lax.fori_loop(
            0, K, lambda i, x: x * jnp.float32(1.0 + 1e-7) + i * 0.0, x)

    def chain(m, x):
        t0 = time.perf_counter()
        for _ in range(m):
            x = copyN(x)
        jax.block_until_ready(x)
        return time.perf_counter() - t0, x

    _, out = chain(1, big)  # compile + warm
    t1, out = chain(2, out)
    t2, out = chain(6, out)
    dt = (t2 - t1) / 4 / K
    return 2 * n * 4 / dt
