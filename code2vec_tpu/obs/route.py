"""`moe/route`: what the expert layers' routers did, step by step.

A train step that runs routed experts (`--encoder lfm2_moe`) returns,
beside the loss, one small int32 device array: per expert layer the rows
each held expert took, the valid tokens, the rows the layer's arrays may
hold and whether the layer ran at that bound. `RouteRecorder.push`
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
         row_bound        the rows an expert layer's arrays hold when its
                          live rows fit (`ops/moe.row_bound`; every
                          (token, choice) pair where the layer has one
                          body only)
         compact_layers   how many of the step's expert layers ran at
                          that bound, the device's own decision
                          (under a mesh both are sums over its devices)

An encoder whose layers also scan a state along the context axis
(`--encoder qwen3_next`, `ops/delta_rule.py`) hands the same array three
columns wider, per layer the slots a chunk, the chunks the layer scanned
and those of them that hold a valid slot (zeros on a layer that scans
nothing), and asks for `RouteRecorder(scan=True)`: the same fetch then
also writes

  name   gdn/scan         t0, t1 as above
  attrs  seq              the step, as the route's
         chunk            slots a chunk (`ops/delta_rule.chunk_len`)
         chunks           what the step's scans ran over, summed over
                          the layers that scan: methods x chunks a
                          method, or the rows each chunk kept where the
                          step scans under its staircase's bound
         live_chunks      those of them with at least one valid slot,
                          counted on the device from the mask

Stdlib-only, as all of `obs`: the array is used through `is_ready`,
`copy_to_host_async` and `tolist` alone.
"""

from __future__ import annotations

import collections
import time

from code2vec_tpu.obs.trace import Tracer, memory_tracer


class RouteRecorder:
    def __init__(self, scan: bool = False):
        self.scan = scan                    # the array carries the scans
        self.tracer = Tracer.disabled()     # the run's, when --trace is on
        self._pending: "collections.deque" = collections.deque()
        self._seq = 0

    def push(self, counts) -> None:
        """`counts`: the step's int32 [expert layers, held + 3] array
        (three columns more with `scan`), still on the device. It is
        kept; older ones are written as far as they are ready (a loop's
        run-ahead, which its own syncs bound, bounds what is kept)."""
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
        spans = []
        if self.scan:
            scans = [row[-3:] for row in table]
            table = [row[:-3] for row in table]
            spans.append(("gdn/scan", dict(
                seq=seq, chunk=max((s[0] for s in scans), default=0),
                chunks=sum(s[1] for s in scans),
                live_chunks=sum(s[2] for s in scans))))
        attrs = dict(seq=seq, layers=[row[:-3] for row in table],
                     rows_here=sum(sum(row[:-3]) for row in table),
                     valid_tokens=table[0][-3] if table else 0,
                     row_bound=table[0][-2] if table else 0,
                     compact_layers=sum(row[-1] for row in table))
        spans.insert(0, ("moe/route", attrs))
        now = time.monotonic()
        for tracer in (memory_tracer(), self.tracer):
            if tracer.enabled:
                for name, a in spans:
                    tracer.record_span(name, t_push, now, **a)
