"""Shared java-large benchmark constants + slope-timing helpers for the
round-4 measurement tools (bench_reconcile.py, xf_profile.py).

bench.py and tools/profile_step.py keep their own self-contained copies
deliberately — bench.py is the driver artifact (run standalone at repo
root every round, must not grow import edges) and profile_step.py is
the round-3 provenance tool; THIS module is the single source for new
tools so shape/methodology fixes stop fanning out (advisor round-4
reuse finding: the bf16-tables fix had to be applied in two places).
"""

from __future__ import annotations

import time

# java-large capacities (SURVEY.md §3 config row) — match bench.py
TOKEN_VOCAB = 1_301_136
PATH_VOCAB = 911_417
TARGET_VOCAB = 261_245
BATCH = 1024
CTX = 200
NUM_SAMPLED = 4096


def slope_time(chain, state, steps: int, warmup: int = 5,
               base: int = 10):
    """Slope timing: run chains of `base` and `base+steps` calls and
    difference, cancelling the fixed dispatch/sync cost.
    `chain(n, state) -> (seconds, state)` must end in a sync that moves
    at most a SCALAR to the host (transferring a full tensor drowns the
    slope in transfer noise — tools/xf_profile.py round-4 history)."""
    _, state = chain(warmup, state)
    t1, state = chain(base, state)
    t2, state = chain(base + steps, state)
    return (t2 - t1) / steps


def time_fn(fn, args, steps: int, sync=None):
    """Slope-time a stateless `fn(*args)` with a scalar-slice sync."""
    if sync is None:
        def sync(o):
            import jax.numpy as jnp
            return float(jnp.ravel(o)[0])

    def chain(n, _):
        t0 = time.perf_counter()
        out = None
        for _ in range(n):
            out = fn(*args)
        sync(out)
        return time.perf_counter() - t0, None

    return slope_time(chain, None, steps)


def load_bench_module():
    """Import repo-root bench.py as a module (it is the standalone
    driver artifact, not a package member). Shared by the tools that
    reuse its measurement entry points (c_sweep_step, int8_profile) so
    the loader does not fan out per tool."""
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "bench", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
