"""`moe/route`: what the expert layers' routers did, step by step.

A train step that runs routed experts (`--encoder lfm2_moe`) returns,
beside the loss, one small int32 device array: per expert layer the rows
each held expert took and, last, the valid tokens. `RouteRecorder.push`
keeps it and starts its copy to the host; the record is written one or
more steps later, when the array is ready, so the loop is never made to
wait for a step it has only dispatched. `flush()` writes what is left
and does wait: it is for after a sync the loop makes anyway (the end of
training, the close of a benchmark window).

One record a step, in `obs.trace.memory_tracer()` (always) and in the
run's `--trace` JSONL (when that tracer is handed over as `tracer`):

  name   moe/route        t0 the step's dispatch, t1 the fetch
  attrs  seq              the step, counted from this recorder's first
         layers           per expert layer the rows of each held expert
         rows_here        their sum: the rows routed to experts held here
         valid_tokens     the step's valid tokens (each makes K choices)

Stdlib-only, as all of `obs`: the array is used through `is_ready`,
`copy_to_host_async` and `tolist` alone.
"""

from __future__ import annotations

import collections
import time

from code2vec_tpu.obs.trace import Tracer, memory_tracer


class RouteRecorder:
    def __init__(self):
        self.tracer = Tracer.disabled()     # the run's, when --trace is on
        self._pending: "collections.deque" = collections.deque()
        self._seq = 0

    def push(self, counts) -> None:
        """`counts`: the step's int32 [expert layers, held + 1] array,
        still on the device. It is kept; older ones are written as far
        as they are ready (a loop's run-ahead, which its own syncs
        bound, bounds what is kept)."""
        counts.copy_to_host_async()
        self._pending.append((self._seq, time.monotonic(), counts))
        self._seq += 1
        while len(self._pending) > 1 and self._pending[0][2].is_ready():
            self._emit(*self._pending.popleft())

    def flush(self) -> None:
        """Write every pending record, waiting for its array."""
        while self._pending:
            self._emit(*self._pending.popleft())

    def _emit(self, seq: int, t_push: float, counts) -> None:
        table = counts.tolist()
        attrs = dict(seq=seq, layers=[row[:-1] for row in table],
                     rows_here=sum(sum(row[:-1]) for row in table),
                     valid_tokens=table[0][-1] if table else 0)
        now = time.monotonic()
        for tracer in (memory_tracer(), self.tracer):
            if tracer.enabled:
                tracer.record_span("moe/route", t_push, now, **attrs)
