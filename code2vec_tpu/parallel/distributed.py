"""Multi-host initialization and cross-process data movement.

SURVEY.md §3.3 (comm-backend row): the reference is single-process; the
TPU framework scales to multi-host pod slices by running one JAX process
per host inside a single SPMD program — XLA collectives over ICI/DCN
replace the NCCL/MPI backend a GPU framework would carry. This module
owns the `jax.distributed.initialize` call (which must run before the
backend is first touched on every process) and the helpers that move
host data into / out of globally-sharded arrays.

Launch recipe (one command per host):

    python code2vec.py ... --dist_coordinator <host0>:<port> \
        --dist_num_processes <H> --dist_process_id <i>

or rely on auto-detection: on Cloud TPU pods / Slurm,
`jax.distributed.initialize()` discovers the topology itself, and this
module calls it whenever such an environment is detected.
"""

from __future__ import annotations

import os
from typing import Callable, Optional

# Environment markers that indicate "this process is one worker of a
# multi-host job". Explicit coordination uses JAX_COORDINATOR_ADDRESS;
# Slurm jobs expose SLURM_NTASKS; Cloud TPU pod slices expose a
# comma-separated TPU_WORKER_HOSTNAMES (single-host environments set it
# too, with one entry, so it only counts when it names several hosts).
_MULTIHOST_ENV_MARKERS = (
    "JAX_COORDINATOR_ADDRESS",
    "COORDINATOR_ADDRESS",
    "MEGASCALE_COORDINATOR_ADDRESS",
)


def _looks_multihost() -> bool:
    # CODE2VEC_DIST_DISABLE=1 is the escape hatch for processes launched
    # inside an allocation that *looks* multi-task but isn't one JAX job
    # (e.g. one task of a heterogeneous Slurm job): initialize() would
    # otherwise block forever waiting for peers that never connect.
    if os.environ.get("CODE2VEC_DIST_DISABLE", "").lower() in (
            "1", "true", "yes"):
        return False
    if any(os.environ.get(k) for k in _MULTIHOST_ENV_MARKERS):
        return True
    hostnames = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    if len([h for h in hostnames.split(",") if h.strip()]) > 1:
        return True
    # Slurm: SLURM_NTASKS>1 alone is too weak a signal (a single-task
    # step inside a multi-task allocation inherits it); require the
    # per-step variables JAX's Slurm cluster detection actually consumes
    # to be consistent too.
    ntasks = int(os.environ.get("SLURM_STEP_NUM_TASKS")
                 or os.environ.get("SLURM_NTASKS") or 1)
    return ntasks > 1 and "SLURM_PROCID" in os.environ \
        and "SLURM_STEP_NODELIST" in os.environ

_initialized = False


def maybe_initialize(coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None,
                     log: Optional[Callable[[str], None]] = None) -> bool:
    """Call `jax.distributed.initialize` when this looks like (or is
    explicitly flagged as) one process of a multi-host job.

    Safe to call unconditionally: single-host runs detect nothing and
    return False without touching the backend. Returns True when the
    distributed runtime was initialized (or already was).
    """
    global _initialized
    if _initialized:
        return True

    flags = (coordinator_address, num_processes, process_id)
    if any(f is not None for f in flags) and any(f is None for f in flags):
        raise ValueError(
            "--dist_coordinator, --dist_num_processes and "
            "--dist_process_id must be given together (got "
            f"coordinator={coordinator_address!r}, "
            f"num_processes={num_processes!r}, process_id={process_id!r})")
    explicit = coordinator_address is not None
    if not (explicit or _looks_multihost()):
        return False

    import jax

    # (widens the heartbeat timeout on oversubscribed CPU harnesses)
    from code2vec_tpu.parallel.compat import distributed_initialize

    kwargs = {}
    if explicit:
        kwargs = dict(coordinator_address=coordinator_address,
                      num_processes=num_processes,
                      process_id=process_id)
    if log is not None:
        # initialize() blocks until every peer connects — announce first
        # so a mis-detected topology is debuggable rather than a silent
        # hang (set CODE2VEC_DIST_DISABLE=1 to skip auto-detection).
        log(f"initializing jax.distributed (explicit={explicit}) — "
            "blocks until all peers connect")
    # Transient coordination-service/Gloo connect failures ride the
    # shared retry policy (ISSUE 10) instead of killing the worker on
    # the first hiccup. jax's State.initialize assigns the
    # global-state client BEFORE connect(), so a failed connect leaves
    # it set and a naive re-call raises "should only be called once"
    # forever, masking the real error — each failed attempt therefore
    # best-effort RESETS the distributed global state
    # (jax.distributed.shutdown clears client/service) so the retry
    # retries the connect, not the precondition. Genuine
    # non-transients give up immediately: the ordering precondition
    # ("must run before any JAX computation") and a reset that didn't
    # take ("should only be called once" — surfacing it beats burning
    # the budget on it). The `dist/init` failpoint exercises this.
    from code2vec_tpu.resilience import faults
    from code2vec_tpu.resilience import retry as retry_mod

    def _init() -> None:
        faults.fire("dist/init")
        try:
            distributed_initialize(**kwargs)
        except BaseException:
            import jax.distributed
            try:
                jax.distributed.shutdown()
            except Exception as reset_err:
                # keep the ORIGINAL connect error in flight; a failed
                # reset only means the next attempt gives up fast
                if log is not None:
                    log("distributed-state reset after failed init "
                        f"also failed: {reset_err}")
            raise

    retry_mod.transient_distributed(
        "distributed-init", log=log,
        giveup=lambda e: (
            "must run before any JAX computation" in str(e)
            or "should only be called once" in str(e))).call(_init)
    _initialized = True
    if log is not None:
        log(f"jax.distributed initialized: process "
            f"{jax.process_index()}/{jax.process_count()}, "
            f"{jax.local_device_count()} local / "
            f"{jax.device_count()} global devices")
    return True


def allreduce_sum_hosts(vec):
    """Sum a small host-side float64 vector across processes (identity
    for single-process runs). Used to merge per-host evaluation metric
    partials after a host-sharded eval pass.

    Exactness: process_allgather round-trips through a device array,
    which canonicalizes float64 -> float32 (x64 is off), so a value is
    only transmitted exactly below 2^24. Each per-host value is split
    into a 2^24 quotient and remainder before the gather and recombined
    in float64 after, keeping integer metric counts exact up to 2^48
    PER HOST (the cross-host summation itself happens host-side in
    float64)."""
    import numpy as np

    import jax

    vec = np.asarray(vec, np.float64)
    if jax.process_count() == 1:
        return vec
    from jax.experimental import multihost_utils
    SPLIT = float(1 << 24)
    hi = np.floor(vec / SPLIT)
    lo = vec - hi * SPLIT
    gathered = np.asarray(multihost_utils.process_allgather(
        np.stack([hi, lo]).astype(np.float32), tiled=False),
        np.float64)  # [H, 2, n]
    return (gathered[:, 0] * SPLIT + gathered[:, 1]).sum(axis=0)


def fetch_global(x):
    """Bring a (possibly non-fully-addressable) global array to the host
    as numpy, identical on every process.

    Single-process: plain np.asarray. Multi-process: allgather the
    process-local shards over the coordination backend so host-side code
    (metrics, prediction decoding) sees the full batch everywhere.

    This IS the deliberate device->host sync that ends the predict /
    eval hot paths — the results must reach the host to be decoded, and
    the predict path's `serve/predict_ms` telemetry span (jax_model.
    predict_device) budgets it explicitly. graftlint's host-sync rule
    SANCTIONS this function by name (round 14 — the parallel layer's
    counterpart of obs.device_sync: one named, greppable terminal-fetch
    seam instead of per-site suppressions; `code2vec_tpu/parallel/` is
    under NO_BASELINE_PREFIXES, so no grandfathering either). Policy:
    hot-path code that must bring a result to the host routes through
    fetch_global; an ad-hoc np.asarray/.item()/float() still gets
    flagged.
    """
    import jax
    import numpy as np

    if jax.process_count() == 1:
        return np.asarray(x)  # the deliberate result fetch (docstring)
    from jax.experimental import multihost_utils
    return np.asarray(multihost_utils.process_allgather(x, tiled=True))
