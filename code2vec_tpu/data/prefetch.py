"""Double-buffered device infeed (SURVEY.md §3.3 infeed row:
"fixed-shape int32 [B,200]x3 + f32 mask, double buffered").

The reference's tf.data pipeline prefetches to the GPU; the TPU
equivalent here is a daemon thread that runs the host side of the next
`depth` batches — `.c2v`/binary parsing, padding, and the
host->device `device_put`/`make_array_from_process_local_data` calls —
while the chip executes the current step. jax transfers are themselves
asynchronous, so by the time the train loop pops batch k+1 from the
queue its bytes are already streaming into HBM; the loop never blocks
on the host between steps (VERDICT r3 item 2: the round-3 loop
transferred synchronously inside the step loop, idling the chip on
every host->device copy).

Default depth 2 = classic double buffering: one batch on the chip, one
in flight. Deeper pipelines buy nothing here (the reader's measured
27x headroom means the producer is never the bottleneck) and cost host
RAM at B=8192 shapes.

Multi-host note: each process prefetches its OWN reader shard in
deterministic reader order, and `make_array_from_process_local_data`
is per-process local work, so threading it does not reorder anything
across hosts.

The production record (ISSUE 26). Every batch is timed where the work
happens, through the process-wide in-memory recorder
(`obs.trace.memory_tracer()`; always on, spans also stand in a running
profiler's trace on the device's clock):

  infeed/read      producer   `next()` on the reader's iterator
                              (`seq`, `rows`, `epoch_first`: the first
                              batch of a pass holds the permutation;
                              `pad_slots`: the slots of the batch that
                              hold no context, counted from its mask,
                              which is how often `take_rows` spreads
                              a PAD read)
  infeed/transfer  producer   `put_fn(batch)`: host arrays, then the
                              device_put (`seq`, `bytes`;
                              `gather_slots` where what it returns
                              carries one, as the model's training
                              batches do: the slots the step chosen
                              for the batch takes table rows for, the
                              staircase's area when the batch fits it
                              and rows x max_contexts when it does
                              not; data/staircase.py; `attn_pairs`
                              beside it for an encoder whose softmax
                              mixers' core runs by query block over
                              the staircase, `models/seq_block.py`:
                              the query-key pairs a head of one
                              softmax layer of the chosen step scores,
                              `staircase.attn_pairs` when the batch
                              fits and rows x max_contexts^2 when
                              not; `ff_slots` with it: the positions
                              every layer's feed-forward half of the
                              chosen step runs over, the staircase's
                              area when the batch fits and rows x
                              max_contexts when not)
  infeed/blocked   producer   the bounded put into the queue: the
                              producer's slack (`seq`)
  infeed/pop_wait  consumer   `q.get()` (`seq` of the batch it popped;
                              of a chunk, its first)

Batches, rows and bytes are counted by those attributes: one `seq` a
batch, with its `rows` and `bytes`. The `BatchRecord` (sequence
number, rows, bytes, where its read started and its transfer ended)
rides the queue item, so the consumer's pop names the batch that
caused it. Cost a batch: three spans on the producer, one on the
consumer, tens of microseconds.
"""

from __future__ import annotations

import itertools
import queue
import threading
from typing import Callable, Iterable, Iterator, Optional, Tuple

import numpy as np

from code2vec_tpu.obs.trace import memory_tracer

_SENTINEL = object()
_EPOCH_END = object()
# process-wide sequence number of a produced batch: the attribute that
# ties a batch's producer spans to the consumer's pop of it
_BATCH_SEQ = itertools.count()


# what a transferred batch may say of the step chosen for it
TRANSFER_COUNTS = ("gather_slots", "attn_pairs", "ff_slots")


class BatchRecord:
    """One produced batch: its sequence number, rows, PAD slots,
    gathered slots, scored pairs, fed-forward slots and bytes, and on
    the recorder's clock where its read started and its transfer ended.
    Rides the queue item the producer builds; `on_produced` (the
    `--trace` hook) gets it after the transfer."""

    __slots__ = ("seq", "rows", "pad_slots", "gather_slots", "attn_pairs",
                 "ff_slots", "bytes", "read_start", "transfer_end")

    def __init__(self, seq: int, rows, pad_slots, read_start: float):
        self.seq = seq
        self.rows = rows
        self.pad_slots = pad_slots
        self.gather_slots = self.attn_pairs = self.ff_slots = None
        self.bytes = 0
        self.read_start = read_start
        self.transfer_end = None


def _nbytes(arrays) -> int:
    """Bytes of one batch's arrays, host or device (a device array is
    as many bytes as the host array it was put from; a multi-process
    global array reads its global size)."""
    if not isinstance(arrays, (tuple, list)):
        arrays = (arrays,)
    return sum(int(getattr(a, "nbytes", 0)) for a in arrays)


def _read_batches(batches: Iterable, recorder
                  ) -> Iterator[Tuple[object, BatchRecord]]:
    """One pass over the reader with `infeed/read` around each
    `next()`. The `next()` that finds the pass exhausted is a span too
    (`exhausted`, no `seq`): it is time on the same thread."""
    it = iter(batches)
    first = True
    while True:
        with recorder.start_span("infeed/read") as span:
            try:
                b = next(it)
            except StopIteration:
                span.attrs["exhausted"] = True
                return
            seq = next(_BATCH_SEQ)
            rows = getattr(b, "num_valid_examples", None)
            mask = getattr(b, "context_valid_mask", None)
            pad_slots = (None if mask is None
                         else int(np.count_nonzero(mask == 0)))
            span.attrs.update(seq=seq, rows=rows, pad_slots=pad_slots,
                              epoch_first=first)
        yield b, BatchRecord(seq, rows, pad_slots, span.interval[0])
        first = False


def _transfer(fn: Callable, b, record: BatchRecord, recorder,
              on_produced: Optional[Callable]):
    """`fn(b)` under `infeed/transfer`; the bytes are those of what it
    returns, and the gathered slots, scored pairs and fed-forward
    slots what it says of itself."""
    with recorder.start_span("infeed/transfer", seq=record.seq) as span:
        out = fn(b)
        record.bytes = span.attrs["bytes"] = _nbytes(out)
        for count in TRANSFER_COUNTS:
            value = getattr(out, count, None)
            setattr(record, count, value)
            if value is not None:
                span.attrs[count] = value
    record.transfer_end = span.interval[1]
    if on_produced is not None:
        on_produced(record)
    return out


class _ThreadedInfeed:
    """Shared producer-thread machinery: bounded queue, (sentinel, exc)
    completion protocol, abandoned-iteration shutdown (a consumer that
    exits early — exception in the step, generator GC'd — must release
    the thread and its device-resident batches instead of pinning them
    for the process lifetime). Subclasses implement `_produce(put)`
    (call `put(item)`; stop when it returns False) and `_emit(item)`
    (yield consumer tuples for one queue item). Each __iter__ is one
    epoch: fresh queue + thread, so one instance wraps a re-iterable
    reader across epochs."""

    def __init__(self, depth: int):
        assert depth >= 1
        self._depth = depth
        # optional obs.watchdog Heartbeat: the producer thread beats on
        # every queue-put attempt (a put blocked on a FULL queue still
        # beats — that means the CONSUMER is slow, not the producer
        # stuck) and goes idle when its passes are done, so "infeed
        # producer wedged in parse/transfer" is distinguishable from
        # "nothing left to produce"
        self._heartbeat = None
        # where the production record goes (injectable: a MemoryTracer
        # with a fake clock), and the `--trace` hook called on the
        # producer thread with each batch's record after its transfer
        self._recorder = memory_tracer()
        self._on_produced = None

    def _produce(self, put: Callable) -> None:
        raise NotImplementedError

    def _emit(self, item) -> Iterator[Tuple]:
        raise NotImplementedError

    def _put_recorded(self, put: Callable, item,
                      record: BatchRecord) -> bool:
        """`put(item)` under `infeed/blocked`, named by `record`."""
        with self._recorder.start_span("infeed/blocked", seq=record.seq):
            return put(item)

    def _pop(self, q: "queue.Queue"):
        """`q.get()` under `infeed/pop_wait`, which names the batch it
        popped (a chunk's first; none for an end marker)."""
        with self._recorder.start_span("infeed/pop_wait") as span:
            item = q.get()
            record = item[-1]
            if isinstance(record, list):  # a chunk's
                record = record[0]
            if isinstance(record, BatchRecord):
                span.attrs["seq"] = record.seq
        return item

    def __iter__(self) -> Iterator[Tuple]:
        q: queue.Queue = queue.Queue(maxsize=self._depth)
        stop = threading.Event()
        heartbeat = self._heartbeat

        def put(item) -> bool:
            # bounded-wait put so shutdown can interrupt a full queue
            while not stop.is_set():
                if heartbeat is not None:
                    heartbeat.beat()
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        def run() -> None:
            try:
                self._produce(put)
            except BaseException as e:  # propagate into the consumer
                put((_SENTINEL, e))
            else:
                put((_SENTINEL, None))
            finally:
                # idle LAST (the sentinel put itself beats): a finished
                # producer is exempt from the deadline, not stalled
                if heartbeat is not None:
                    heartbeat.idle()

        thread = threading.Thread(target=run, daemon=True)
        thread.start()
        try:
            while True:
                item = self._pop(q)
                if item[0] is _SENTINEL:
                    thread.join()
                    if item[1] is not None:
                        raise item[1]
                    return
                yield from self._emit(item)
        finally:
            stop.set()
            while thread.is_alive():  # drain so a blocked put returns
                try:
                    q.get_nowait()
                except queue.Empty:
                    pass
                thread.join(timeout=0.05)


class DevicePrefetcher(_ThreadedInfeed):
    """Iterate `(put_fn(batch), batch)` pairs with the put_fn work done
    up to `depth` batches ahead on the producer thread.

    put_fn is the host->device transfer (e.g. jax_model._device_batch);
    the original host batch rides along because the consumers also need
    host-side fields (num_valid_examples, target_strings).

    Exceptions in the producer surface in the consumer at the position
    they occurred (not silently truncating the epoch)."""

    def __init__(self, batches: Iterable, put_fn: Callable,
                 depth: int = 2):
        super().__init__(depth)
        self._batches = batches
        self._put_fn = put_fn

    def _produce(self, put: Callable) -> None:
        recorder, on_produced = self._recorder, self._on_produced
        for b, record in _read_batches(self._batches, recorder):
            dev = _transfer(self._put_fn, b, record, recorder,
                            on_produced)
            if not self._put_recorded(put, (dev, b, record), record):
                return

    def _emit(self, item) -> Iterator[Tuple]:
        yield item[:2]


class ChunkedDevicePrefetcher(_ThreadedInfeed):
    """Latency-amortizing infeed: group `chunk` host batches, transfer
    them as ONE stacked device array per field, then yield on-device
    slices — N per-batch transfers per epoch become N/chunk.

    This targets HIGH-LATENCY host->device links: where every
    device_put costs a fixed round trip regardless of size and the
    transfers serialize on one connection, thread overlap
    (DevicePrefetcher) cannot help, and stacking G batches turns G
    round trips into one plus a device-side slice per step. On a host
    with local PCIe and sub-ms transfers plain depth prefetch is the
    right tool — this class is opt-in via --infeed_chunk (whether any
    deployment still needs it is ROADMAP C6). Inherently threaded (the
    producer stacks ahead); Config.verify rejects --infeed_prefetch 0
    with chunking so the synchronous A/B control stays unconfounded.

    Single-device only (the stacked array is not mesh-sharded);
    jax_model falls back to DevicePrefetcher when a mesh is active.

    `to_arrays(batch) -> tuple[np.ndarray, ...]` converts a host batch
    to its per-field numpy arrays; `transfer` (default jnp.asarray,
    injectable for tests) moves a stacked field to the device.
    """

    def __init__(self, batches: Iterable, to_arrays: Callable,
                 chunk: int, depth: int = 2, transfer=None):
        assert chunk >= 1
        super().__init__(depth)
        self._batches = batches
        self._to_arrays = to_arrays
        self._chunk = chunk
        self._transfer = transfer

    def _produce(self, put: Callable) -> None:
        transfer = self._transfer
        if transfer is None:
            import jax.numpy as jnp
            transfer = jnp.asarray

        recorder, on_produced = self._recorder, self._on_produced

        def ship(hosts, rows, records) -> bool:
            # the chunk's one stacked transfer and its one put are
            # charged to its last batch (a second `infeed/transfer`
            # under that seq, `stacked` = batches in it, no bytes: each
            # batch's own span counted them)
            last = records[-1]
            with recorder.start_span("infeed/transfer", seq=last.seq,
                                     stacked=len(rows)):
                stacked = tuple(
                    transfer(np.stack([r[f] for r in rows]))
                    for f in range(len(rows[0])))
            return self._put_recorded(put, (stacked, hosts, records),
                                      last)

        hosts, rows, records = [], [], []
        for b, record in _read_batches(self._batches, recorder):
            hosts.append(b)
            records.append(record)
            rows.append(_transfer(self._to_arrays, b, record, recorder,
                                  on_produced))
            if len(rows) == self._chunk:
                if not ship(hosts, rows, records):
                    return
                hosts, rows, records = [], [], []
        if rows:  # partial tail chunk
            ship(hosts, rows, records)

    def _emit(self, item) -> Iterator[Tuple]:
        stacked, hosts, _records = item
        for i, host in enumerate(hosts):
            yield tuple(a[i] for a in stacked), host


class _SyncInfeed:
    """depth=0: synchronous transfer in the caller's loop (the round-3
    behavior, kept for A/B measurement via --infeed_prefetch 0).
    Re-iterable like DevicePrefetcher so epoch loops treat both alike."""

    def __init__(self, batches: Iterable, put_fn: Callable):
        self._batches = batches
        self._put_fn = put_fn
        self._recorder = memory_tracer()
        self._on_produced = None

    def __iter__(self) -> Iterator[Tuple]:
        # read and transfer on the caller's thread; no queue, so no
        # blocked time and no pop
        recorder, on_produced = self._recorder, self._on_produced
        for b, record in _read_batches(self._batches, recorder):
            yield _transfer(self._put_fn, b, record, recorder,
                            on_produced), b


def prefetch_to_device(batches: Iterable, put_fn: Callable,
                       depth: int = 2) -> Iterable[Tuple]:
    if depth <= 0:
        return _SyncInfeed(batches, put_fn)
    return DevicePrefetcher(batches, put_fn, depth)


def persistent_epochs(infeed, num_epochs: int, first_epoch: int = 1
                      ) -> Iterator[Tuple[int, Iterator[Tuple]]]:
    """Keep the infeed producer WARM across epoch boundaries.

    Yields `(epoch, epoch_batches)` pairs for epochs
    `first_epoch..num_epochs` (1-based; `first_epoch > 1` is the
    auto-resume path — a restarted run trains only the epochs its
    killed predecessor had not finished, with the reader's
    `epoch_offset` replaying the matching shuffle stream). For a threaded
    infeed, ONE producer thread runs all `num_epochs` passes over the
    reader back-to-back, separating them with an epoch-end marker in
    the shared queue — so while the consumer is doing epoch-boundary
    work (checkpoint save, eval), the producer is already parsing and
    transferring epoch k+1's first batches instead of cold-restarting a
    fresh thread and re-filling the double buffer from scratch.
    Per-epoch shuffle semantics are preserved exactly: each pass is one
    `iter(reader)`, which advances the reader's `_epoch` counter and
    draws that epoch's seeded permutation, same as the cold path.

    The synchronous A/B control (`--infeed_prefetch 0` -> _SyncInfeed)
    re-iterates cold per epoch — persistence is inherently threaded and
    must not confound the no-thread measurement.

    The consumer must drain each epoch's iterator before taking the
    next pair (a `for` over the pair's iterator does); abandoning the
    generator mid-run (exception in the step loop) releases the
    producer thread and its device-resident batches via the `finally`
    drain, exactly like `_ThreadedInfeed.__iter__`.
    """
    epochs = range(first_epoch, num_epochs + 1)
    if not isinstance(infeed, _ThreadedInfeed):
        for epoch in epochs:
            yield epoch, iter(infeed)
        return

    q: queue.Queue = queue.Queue(maxsize=infeed._depth)
    stop = threading.Event()
    heartbeat = infeed._heartbeat

    def put(item) -> bool:
        while not stop.is_set():
            if heartbeat is not None:
                heartbeat.beat()
            try:
                q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def run() -> None:
        try:
            for _ in epochs:
                infeed._produce(put)
                if not put((_EPOCH_END, None)):
                    return
        except BaseException as e:  # surfaces at the consumer position
            put((_SENTINEL, e))
        else:
            put((_SENTINEL, None))
        finally:
            # idle LAST (the sentinel put itself beats): the producer
            # finishing all passes is exempt, not stalled
            if heartbeat is not None:
                heartbeat.idle()

    thread = threading.Thread(target=run, daemon=True,
                              name="train-infeed")
    thread.start()
    finished = threading.Event()  # producer exhausted (error or done):
    #                               later epochs must not block on q.get

    def epoch_iter() -> Iterator[Tuple]:
        if finished.is_set():
            return
        while True:
            item = infeed._pop(q)
            if item[0] is _EPOCH_END:
                return
            if item[0] is _SENTINEL:
                finished.set()
                thread.join()
                if item[1] is not None:
                    raise item[1]
                return
            yield from infeed._emit(item)

    try:
        for epoch in epochs:
            yield epoch, epoch_iter()
    finally:
        stop.set()
        while thread.is_alive():  # drain so a blocked put returns
            try:
                q.get_nowait()
            except queue.Empty:
                pass
            thread.join(timeout=0.05)


def build_train_infeed(reader: Iterable, *, chunk: int, depth: int,
                       mesh, host_arrays_fn: Callable,
                       device_batch_fn: Callable,
                       log: Callable, instrument: Callable = None,
                       heartbeat=None) -> Iterable[Tuple]:
    """The train-loop infeed both model heads share: chunked
    (latency-amortizing, single-device only) when --infeed_chunk > 1,
    else depth-prefetched; logs instead of silently ignoring the chunk
    request when a mesh forces the fallback.

    `instrument` (ISSUE 6 tracing; `obs.infeed_produce_instrument`)
    is called on the PRODUCER thread with each batch's `BatchRecord`
    once its transfer is done and before it is queued, so the model
    can build its `infeed/produce` span from the record's own clock
    reads and send the context down a SpanChannel in step with the
    queue. `heartbeat` is the producer's obs.watchdog Heartbeat
    (beaten on every queue put attempt). Both default to off and cost
    nothing when unset.

    The `infeed/produce` failpoint (ISSUE 10, armed via --faults)
    wraps the same seam: an injected raise happens ON the producer
    thread and surfaces at the consumer through the existing
    sentinel/exception protocol — exactly the path a real parse or
    transfer failure takes. Only the per-batch function the CHOSEN
    infeed actually calls is wrapped, so the site counts exactly one
    hit per batch (the spec's `at`/`prob` semantics). Disarmed,
    nothing is wrapped."""
    use_chunked = chunk > 1 and mesh is None
    from code2vec_tpu.resilience import faults
    fp = faults.point("infeed/produce")
    if fp.armed:
        def _faulted(fn, _fp=fp):
            def wrapped(b):
                _fp.fire()
                return fn(b)
            return wrapped
        if use_chunked:
            host_arrays_fn = _faulted(host_arrays_fn)
        else:
            device_batch_fn = _faulted(device_batch_fn)
    if use_chunked:
        infeed = ChunkedDevicePrefetcher(reader, host_arrays_fn, chunk,
                                         depth=max(1, depth))
    else:
        if chunk > 1:
            log("--infeed_chunk ignored: chunked infeed is "
                "single-device only (mesh active); using depth "
                "prefetch")
        infeed = prefetch_to_device(reader, device_batch_fn, depth)
    infeed._on_produced = instrument
    if isinstance(infeed, _ThreadedInfeed):
        infeed._heartbeat = heartbeat
    return infeed
