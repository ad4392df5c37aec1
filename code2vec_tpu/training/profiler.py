"""--profile support: trace a window of training steps with jax.profiler.

SURVEY.md §6 (tracing row): the reference has no profiler at all — only
throughput log lines. The TPU framework adds a first-class trace hook:
`--profile <dir>` wraps steps [PROFILE_START_STEP, +PROFILE_STEPS) of the
current process's run in `jax.profiler.start_trace`/`stop_trace`; the
result opens in tensorboard-plugin-profile. Shared by every train loop
(code2vec and varmisuse heads).
"""

from __future__ import annotations

from typing import Callable, Optional


class StepProfiler:
    """Drives one bounded jax.profiler trace window over a train loop.

    Call `tick(step, sync_leaf)` once per step BEFORE launching the
    step's device work, with `step` counted from the start of this
    process (so resumed runs still profile), and `finish(sync_leaf)`
    after the loop in case the run was shorter than the window.
    `sync_leaf` is any device array to block on before stop_trace so the
    trace captures complete device timelines.
    """

    def __init__(self, profile_dir: Optional[str], start_step: int,
                 num_steps: int,
                 log: Optional[Callable[[str], None]] = None):
        self.profile_dir = profile_dir
        self.start_step = start_step
        self.num_steps = num_steps
        self.log = log or (lambda _msg: None)
        self._active = False
        self._done = profile_dir is None
        self._stop_at = start_step + num_steps

    def tick(self, step: int, sync_leaf) -> None:
        if self._done:
            return
        import jax
        if not self._active and step >= self.start_step:
            jax.profiler.start_trace(self.profile_dir)
            self._active = True
            self.log(f"profiler: tracing {self.num_steps} steps "
                     f"-> {self.profile_dir}")
        elif self._active and step >= self._stop_at:
            self._stop(sync_leaf)

    def finish(self, sync_leaf) -> None:
        """Close the trace if the run ended inside the window."""
        if self._active:
            self._stop(sync_leaf)
        elif not self._done:
            # --profile was requested but the run ended before
            # start_step — say so instead of leaving an empty directory
            self.log(f"profiler: run ended before step {self.start_step};"
                     f" no trace written (lower --profile start via "
                     f"PROFILE_START_STEP or train longer)")
            self._done = True

    def _stop(self, sync_leaf) -> None:
        import jax

        # sync first (shared with the telemetry spans —
        # obs.device_sync): stopping earlier would cut off traced steps
        # that are still in flight
        from code2vec_tpu.obs import device_sync
        device_sync(sync_leaf)
        jax.profiler.stop_trace()
        self._active = False
        self._done = True
        self.log(f"profiler: trace written to {self.profile_dir}")
