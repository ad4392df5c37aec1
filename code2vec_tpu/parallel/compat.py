"""Seams between the parallel layer and the one JAX this repo is
written for (jax 0.9.0 / jaxlib 0.9.0, on the TPU and on the CPU
harness alike), plus the bring-up guards the multi-process CPU
harnesses share.

- `shard_map`: `jax.shard_map` with the varying-manual-axes check off.
  Every caller wants it off — the parallel bodies use collectives
  (`ppermute`, `all_gather`) on values the checker would make them
  annotate — so one wrapper owns the argument.
- `distributed_initialize`: `jax.distributed.initialize`, with a wider
  heartbeat timeout on the CPU harness.
- `cpu_worker_env`: the spawn environment of a CPU worker
  (`JAX_PLATFORMS=cpu` plus the virtual device count), pinned before
  the worker's jax import; every multi-process spawner
  (tests/mp_worker.py, tools/multichip_bench.py, tools/chaos.py)
  provisions that way. CPU cross-process collectives need no knob:
  Gloo is this JAX's default.
"""

from __future__ import annotations

import os
from typing import Any


def shard_map(f, *, mesh, in_specs, out_specs):
    """`jax.shard_map` with `check_vma=False`."""
    import jax

    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def distributed_initialize(coordinator_address=None, num_processes=None,
                           process_id=None) -> None:
    """`jax.distributed.initialize`, with the coordination service's
    heartbeat timeout widened from 100 s to 600 s when the platform is
    the CPU. The Gloo harnesses this repo runs (2 OS processes x 4
    virtual devices on a 2-core box) can starve a worker's heartbeat
    thread past the default during the first big XLA compile; the
    coordinator then evicts the healthy-but-descheduled worker and the
    peer dies mid-collective with `gloo ... Connection reset by peer`.
    TPU runs keep the stock timeout: there it is the right failure
    detector, and eviction latency matters."""
    import jax

    kwargs = {}
    if coordinator_address is not None:
        kwargs = dict(coordinator_address=coordinator_address,
                      num_processes=num_processes,
                      process_id=process_id)
    if jax.config.jax_platforms == "cpu":
        kwargs["heartbeat_timeout_seconds"] = 600
    jax.distributed.initialize(**kwargs)


# distinctive exit status for a wedged first collective (greppable in
# the spawner's captured worker output / returncode)
BARRIER_TIMEOUT_EXIT = 19


def first_collective_barrier(timeout_s: float = 90.0, *,
                             tag: str = "cohort-bringup",
                             setup_fn=None, barrier_fn=None,
                             on_timeout=None, log=None) -> None:
    """Bounded cohort bring-up (ISSUE 14 satellite — the PR 12
    postscript hang). On oversubscribed 1-core containers the
    loopback-Gloo rendezvous can wedge EVERY cohort member during
    bring-up — inside `jax.distributed.initialize` itself (it blocks
    until every peer connects) or at the FIRST collective right after
    it returns (a Gloo transport race). Each
    worker then blocks forever, the spawner burns its full
    communicate() wall, and one wedge eats a whole test module's
    budget.

    This arms a hard watchdog deadline over BOTH phases: `setup_fn`
    (the caller's distributed init, when provided) and a trivial
    `sync_global_devices` probe collective. If bring-up doesn't
    complete in `timeout_s`, the watchdog
    `os._exit(BARRIER_TIMEOUT_EXIT)`s THIS process — converting a
    silent module-eating hang into a fast, retryable worker death
    that the spawner's fresh-port retry
    (resilience/retry.transient_distributed) absorbs by re-forming
    the cohort. `os._exit`, not `sys.exit`: a wedged Gloo op holds
    locks no finally-block should touch, and SIGKILL-style death is
    exactly what the retry layer already classifies as a peer crash.

    Single-process runs skip the probe (nothing to rendezvous; the
    check runs AFTER setup_fn so it cannot touch the backend before
    init). `setup_fn` / `barrier_fn` / `on_timeout` are injectable so
    the deadline path is unit-testable without a wedgeable cohort
    (tests/test_parallel.py)."""
    import threading

    if on_timeout is None:
        def on_timeout():  # pragma: no cover - exercised via injection
            if log is not None:
                log(f"first-collective barrier '{tag}' timed out after "
                    f"{timeout_s}s — exiting for the spawner's "
                    "fresh-port retry")
            os._exit(BARRIER_TIMEOUT_EXIT)

    timer = threading.Timer(timeout_s, on_timeout)
    timer.daemon = True
    timer.start()
    try:
        if setup_fn is not None:
            setup_fn()
        if barrier_fn is not None:
            barrier_fn()
        else:
            import jax

            if jax.process_count() > 1:
                from jax.experimental import multihost_utils
                multihost_utils.sync_global_devices(tag)
    finally:
        timer.cancel()
        # reap the watchdog thread (cancel() alone leaves it parked
        # until the deadline); in the fired production path the
        # process is already gone via os._exit, so this never blocks
        timer.join()


class PhaseDeadline:
    """Re-armable per-phase deadline for spawned cohort workers — the
    companion of `first_collective_barrier` for everything AFTER
    bring-up. The loopback-Gloo race can wedge a later collective too
    (observed: a mid-workload hang burning the spawner's full 300 s
    communicate() wall); `beat(phase)` re-arms the deadline at each
    phase boundary, so any SINGLE phase wedging hard-exits the worker
    (default `os._exit(BARRIER_TIMEOUT_EXIT)`) within `timeout_s` of
    its last beat and the spawner's fresh-port retry re-forms the
    cohort. `close()` disarms and reaps the watchdog thread.

    This is a last-resort process killer for DISPOSABLE test/bench
    workers, not a replacement for obs.watchdog (which is in-process
    training observability with stack dumps); phases here are coarse
    (~seconds each idle), so the default 4x headroom absorbs a loaded
    box without false kills. `on_timeout` is injectable for unit
    tests (tests/test_parallel.py)."""

    def __init__(self, timeout_s: float = 120.0, *, on_timeout=None,
                 log=None):
        import threading

        self.timeout_s = timeout_s
        self._on_timeout = on_timeout
        self._log = log
        self._lock = threading.Lock()
        self._timer = None

    def _expire(self, phase: str) -> None:
        if self._on_timeout is not None:
            self._on_timeout(phase)
            return
        if self._log is not None:  # pragma: no cover - via injection
            self._log(f"phase deadline: {phase!r} wedged for "
                      f"{self.timeout_s}s — exiting for the spawner's "
                      "fresh-port retry")
        os._exit(BARRIER_TIMEOUT_EXIT)

    def beat(self, phase: str = "work",
             timeout_s: "float | None" = None) -> None:
        """Enter `phase`: the previous phase completed, re-arm.
        `timeout_s` overrides the default for THIS phase — the first
        compile-heavy phase needs more headroom (the compat
        distributed_initialize docstring: a first big XLA compile can
        starve a 1-core box past 100 s without being wedged)."""
        import threading

        new = threading.Timer(timeout_s or self.timeout_s,
                              self._expire, args=(phase,))
        new.daemon = True
        with self._lock:
            old, self._timer = self._timer, new
            new.start()
        if old is not None:
            old.cancel()
            old.join()

    def close(self) -> None:
        """Disarm and reap (the worker finished its workload)."""
        with self._lock:
            old, self._timer = self._timer, None
        if old is not None:
            old.cancel()
            old.join()


def cohort_world() -> "tuple[int, int]":
    """(process_index, process_count) of the LIVE cohort this process
    joined — the one seam topology-dependent host code re-derives the
    world from (ISSUE 13). After the supervisor re-forms a cohort at
    N−1, the relaunched children initialize the distributed runtime at
    the new size and everything built on this seam — the mesh
    (`models/setup.build_mesh` via `jax.devices()`) and the per-host
    infeed split (`models/setup.infeed_split`) — rebuilds itself from
    the surviving process set with no resize-specific code anywhere
    downstream. Single-process (a cohort re-formed at one survivor, or
    a plain run) reads (0, 1) without ever touching the distributed
    runtime."""
    import jax

    return int(jax.process_index()), int(jax.process_count())


def free_port() -> int:
    """An OS-assigned free TCP port for a coordinator about to bind —
    the one definition shared by every multi-process spawner (the
    tests/test_multihost.py fixture, tools/multichip_bench.py legs)."""
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def cpu_worker_env(n_devices: int, extra: dict[str, Any] | None = None
                   ) -> dict:
    """Environment for a spawned CPU worker process: CPU platform +
    n virtual devices pinned BEFORE its jax import. Used by the
    multi-process test/bench spawners."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={n_devices}")
    if extra:
        env.update({k: str(v) for k, v in extra.items()})
    return env
