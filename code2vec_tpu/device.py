"""Which device this process runs on, and where its compiled programs
are kept.

code2vec.py holds the run to the platform --backend names
(`select_backend`, `require_backend`); every entry point that compiles
on the chip calls `enable_compile_cache` before its first compile.
Imports jax lazily: importing this module touches no backend.
"""

from __future__ import annotations

import os
from typing import Optional

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Peak HBM bandwidth per chip in GB/s, keyed by `device_kind`: the
# denominator of the analytic floor gauges (train/step_floor_ms,
# health/phase_*). Source: Google Cloud documentation, "TPU v5e"
# system architecture (16 GB HBM2e at 819 GB/s per chip). A kind that
# is not listed publishes no floor gauge rather than borrowing a rate.
HBM_PEAK_GBPS = {
    "TPU v5 lite": 819.0,
}


class BackendUnavailable(RuntimeError):
    """JAX's platform is not the one --backend names."""


def select_backend(backend: str) -> None:
    """Name the platform before the first backend touch: 'cpu' pins
    JAX to the CPU explicitly (tests, tools, the chaos harness); 'tpu'
    and 'gpu' are whatever JAX finds, and `require_backend` then holds
    the run to it."""
    if backend == "cpu":
        import jax

        jax.config.update("jax_platforms", "cpu")


def require_backend(backend: str):
    """`jax.devices()`, or BackendUnavailable when their platform is
    not the one --backend names — a run that asked for a TPU never
    carries on somewhere else. Call after the distributed runtime is
    up (this is a backend touch)."""
    import jax

    devices = jax.devices()
    found = devices[0].platform
    if found != backend:
        raise BackendUnavailable(
            f"--backend {backend}: JAX found no {backend} device (the "
            f"platform here is {found!r}, {devices[0].device_kind}). "
            f"Pass --backend {found} to run there on purpose.")
    return devices


def platform() -> str:
    """`jax.devices()[0].platform` — what the kernels are chosen by."""
    import jax

    return jax.devices()[0].platform


def hbm_peak_gbps() -> Optional[float]:
    """Published HBM peak of the first local device, or None when its
    `device_kind` is not in HBM_PEAK_GBPS."""
    import jax

    return HBM_PEAK_GBPS.get(jax.local_devices()[0].device_kind)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a place that is the
    same on every run, and return it. Where JAX_COMPILATION_CACHE_DIR
    is set JAX already reads it and nothing is set in code; otherwise
    the cache is `<checkout>/.jax_cache` — derived from this file's
    location, because the directory is part of the cache key and one
    that moves never hits. Every entry point that compiles comes
    through here first, so this is also where the program starts to
    keep its record of what JAX compiles or reads from that cache
    (obs/setup_trace.py: one listener a process, however often this
    is called)."""
    import jax

    from code2vec_tpu.obs import setup_trace
    setup_trace.install(jax.monitoring)
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    cache_dir = os.path.join(_REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir
