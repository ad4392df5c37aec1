"""Train-loop instrumentation shared by both model heads.

`TrainStepRecorder` answers the question the throughput log line can't:
is the step device-bound or infeed-bound? Per step it records

  - `infeed_wait_ms` — host time blocked on the double-buffered infeed
    (data/prefetch.py). Near zero while the producer thread keeps up;
    grows exactly when the input pipeline, not the chip, is the
    bottleneck.
  - `step_ms` — wall time from infeed yield to step completion,
    device-sync-aware: the recorder syncs via the loss scalar's host
    transfer, so the figure bounds the dispatched device work (and the
    loss ride-along means per-step loss costs no extra transfer).
  - periodic device-memory gauges (`bytes_in_use`,
    `peak_bytes_in_use`) where the backend exposes them.

With a tracer attached (`--trace`, ISSUE 6) each step additionally
becomes a trace: a `train/step_cycle` root span with `train/infeed_wait`
and `train/step` children (recorded retroactively from the timings the
recorder already took — no extra clock reads on the hot path beyond
one), LINKING the `infeed/produce` span of the batch it consumed (the
producer thread sends that span's context through a `SpanChannel` in
lockstep with the infeed queue — obs/trace.py has the handoff
discipline). `last_step_context` exposes the newest step's context so
the epoch-boundary save can link the step that triggered it. A
heartbeat (`--watchdog_stall_s`) beats once per step.

Cost model: telemetry is opt-in (`--telemetry_dir`), and enabling it
trades step pipelining for attribution — the per-step device sync
serializes the loop (steps no longer overlap the next host dispatch).
That is the documented price of in-band per-step numbers; the
jax.profiler trace window (`--profile`) remains the non-intrusive tool.
Disabled, the recorder costs ONE boolean check per step and `wrap()`
returns the infeed unchanged — zero per-step allocation. Trace and
watchdog ride the same discipline: off, they add one boolean check and
one no-op method call per step.
"""

from __future__ import annotations

import time
from typing import Iterable, Optional

from code2vec_tpu.obs.telemetry import Telemetry
from code2vec_tpu.obs.trace import SpanChannel, SpanContext, Tracer


def infeed_produce_instrument(tracer: Tracer,
                              channel: Optional[SpanChannel]):
    """Producer-side tracing hook for `build_train_infeed`: called ON
    the producer thread with each batch's production record
    (data/prefetch.py `BatchRecord`), it gives the batch an
    `infeed/produce` span from the start of the reader's `next()` to
    the end of the transfer — built from the record's clock reads, the
    in-memory recorder's (`time.monotonic`, this tracer's default
    too), never from a second pair — whose context is handed to the
    consuming step through `channel` (FIFO-aligned with the infeed
    queue — the recorder links it from the step span). Returns None
    when tracing is off, so the infeed path stays the untraced one.
    ONE definition shared by both train loops: the FIFO handoff
    contract must not drift between them."""
    if not tracer.enabled:
        return None

    def on_produced(record) -> None:
        channel.send(tracer.record_span(
            "infeed/produce", record.read_start, record.transfer_end,
            seq=record.seq, rows=record.rows,
            pad_slots=record.pad_slots,
            gather_slots=record.gather_slots,
            attn_pairs=record.attn_pairs, ff_slots=record.ff_slots,
            bytes=record.bytes))
    return on_produced


class TrainStepRecorder:
    """Per-step telemetry for a `for dev_batch, batch in infeed:` loop.

    Usage (both heads):
        rec = TrainStepRecorder(telemetry, gauge_every=N)
        for epoch ...:
            for dev_batch, batch in rec.wrap(infeed):
                ... dispatch step ...
                loss_f = rec.end_step(step_num, loss, n) \
                    if rec.enabled else None
    """

    def __init__(self, telemetry: Telemetry, gauge_every: int = 100,
                 tracer: Optional[Tracer] = None,
                 infeed_channel: Optional[SpanChannel] = None,
                 heartbeat=None, alerts=None):
        self.enabled = telemetry.enabled
        self._tele = telemetry
        self._tracer = tracer if tracer is not None else Tracer.disabled()
        self._channel = infeed_channel
        self._heartbeat = heartbeat
        # alert engine (obs/alerts.py): end_step is "the training
        # loop's next beat" where a raise-mode sticky alert surfaces
        self._alerts = alerts
        self.last_step_context: Optional[SpanContext] = None
        self._gauge_every = max(1, gauge_every)
        self._steps = 0
        self._infeed_wait_ms = 0.0
        self._t_yield = 0.0

    def wrap(self, infeed: Iterable) -> Iterable:
        """Time the infeed pops. Disabled: returns `infeed` itself, so
        the loop iterates exactly what it iterated before."""
        if not self.enabled:
            return infeed
        return self._timed_iter(infeed)

    def _timed_iter(self, infeed: Iterable):
        it = iter(infeed)
        while True:
            t0 = time.perf_counter()
            try:
                item = next(it)
            except StopIteration:
                return
            now = time.perf_counter()
            self._infeed_wait_ms = (now - t0) * 1e3
            self._t_yield = now
            yield item

    def end_step(self, step: int, loss, n_examples: int,
                 params=None) -> float:
        """Close the current step: sync on the loss transfer, record the
        step/infeed timers, write the per-step event. Returns the loss
        as a float so the loop's log line reuses the one transfer.

        `params` (optional, the live param pytree) feeds the fleet
        plane's divergence check: every `gauge_every` steps a sampled
        fingerprint (sum of one sliver per leaf) publishes as a gauge
        pair, step-labeled so the cohort collector compares hosts at
        MATCHING steps (obs/fleet.py)."""
        loss_f = float(loss)  # device sync: bounds the dispatched step
        now = time.perf_counter()
        step_ms = (now - self._t_yield) * 1e3
        tele = self._tele
        tele.record_ms("train/step_ms", step_ms)
        tele.record_ms("train/infeed_wait_ms", self._infeed_wait_ms)
        tele.count("train/steps")
        tele.count("train/examples", int(n_examples))
        # live-plane feed (obs/health.py): the newest loss as a gauge
        # so the non-finite / spike monitors can read it off the hot
        # path (emit=False: a dict store, never a JSONL event)
        tele.gauge("train/loss", loss_f, emit=False)
        # step label for the loss gauge: SPMD replicas publishing
        # different losses at the SAME step is runtime divergence
        tele.gauge("train/loss_step", float(step), emit=False)
        tele.event("step", step=int(step), step_ms=round(step_ms, 3),
                   infeed_wait_ms=round(self._infeed_wait_ms, 3),
                   loss=round(loss_f, 6), examples=int(n_examples))
        if self._heartbeat is not None:
            self._heartbeat.beat()
        alerts = self._alerts
        if alerts is not None and alerts._sticky is not None:
            alerts.poll()  # raise-mode alert lands at the loop's beat
        if self._tracer.enabled:
            self._trace_step(step, step_ms, n_examples)
        self._steps += 1
        if self._steps % self._gauge_every == 0:
            self._device_memory_gauges()
            if params is not None:
                self._params_digest_gauges(step, params)
        return loss_f

    def _params_digest_gauges(self, step: int, params) -> None:
        """Sampled params fingerprint for the cohort divergence check:
        one sliver (`leaf[..., :1]`) per leaf, summed in float32 — a
        few hundred elements instead of the full model, cheap enough
        for the gauge cadence while still moving when ANY layer's
        leading column drifts. Replicated-SPMD hosts must agree on it
        bit-for-bit-ish; the fleet collector compares hosts at the
        step this pair labels.

        The math MUST stay process-local: an op over a multi-process
        global array lowers to a collective, and a telemetry-path
        collective interleaving with the step's gradient all-reduce
        desyncs the cohort (Gloo aborts on the size mismatch). So
        only fully-replicated leaves contribute — every host skips
        the same sharded leaves, so digests stay comparable — and
        each is read through its LOCAL shard, never the global
        view."""
        try:
            import jax.numpy as jnp
            total = 0.0
            import jax
            for leaf in jax.tree_util.tree_leaves(params):
                if hasattr(leaf, "is_fully_replicated"):
                    if not leaf.is_fully_replicated:
                        continue
                    leaf = leaf.addressable_data(0)
                probe = leaf if getattr(leaf, "ndim", 0) == 0 \
                    else leaf[..., :1]
                total += float(jnp.sum(probe.astype(jnp.float32)))
        except Exception:  # non-array pytree / backend quirk: skip
            return
        self._tele.gauge("train/params_digest", total, emit=False)
        self._tele.gauge("train/params_digest_step", float(step),
                         emit=False)

    def _trace_step(self, step: int, step_ms: float,
                    n_examples: int) -> None:
        """One trace per step, built retroactively from the timings
        end_step already measured (the tracer clock and perf_counter
        tick at the same rate; only the interval lengths matter).
        Root `train/step_cycle` = infeed wait + step; its `train/step`
        child links the consumed batch's `infeed/produce` span via the
        producer's SpanChannel (FIFO-aligned with the infeed queue)."""
        tracer = self._tracer
        t_end = tracer.clock()
        t_yield = t_end - step_ms / 1e3
        t_wait0 = t_yield - self._infeed_wait_ms / 1e3
        produced = self._channel.recv() if self._channel is not None \
            else None
        root = tracer.record_span(
            "train/step_cycle", t_wait0, t_end, parent=None,
            step=int(step), examples=int(n_examples))
        tracer.record_span("train/infeed_wait", t_wait0, t_yield,
                           parent=root)
        tracer.record_span(
            "train/step", t_yield, t_end, parent=root,
            links=(produced,) if produced is not None else (),
            step=int(step))
        self.last_step_context = root

    def _device_memory_gauges(self) -> None:
        import jax
        # None on the CPU backend, which keeps no allocator statistics
        stats = jax.local_devices()[0].memory_stats() or {}
        for key in ("bytes_in_use", "peak_bytes_in_use"):
            if key in stats:
                self._tele.gauge(f"device/{key}", int(stats[key]))
