"""The input pipeline's own record (ISSUE 26): the in-memory recorder
(`obs.trace.MemoryTracer`), the per-batch spans the producer and the
consumer of data/prefetch.py leave in it, the profiler annotations they
enter, the `--trace` span built from the same clock reads, and the
step's `c2v/` scopes."""

import glob
import itertools
import os
import re
import subprocess
import sys
import textwrap
import threading

import numpy as np
import pytest

from code2vec_tpu.data import prefetch
from code2vec_tpu.data.prefetch import (ChunkedDevicePrefetcher,
                                        DevicePrefetcher, _SyncInfeed,
                                        persistent_epochs)
from code2vec_tpu.obs.trace import MemoryTracer, Tracer, memory_tracer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

READ_S, FIRST_READ_S, PUT_S, STACK_S = 0.010, 0.050, 0.002, 0.003


class FakeClock:
    def __init__(self):
        self.now = 100.0

    def __call__(self) -> float:
        return self.now


class Batch:
    def __init__(self, i: int, rows: int = 8):
        self.i = i
        self.num_valid_examples = rows
        self.ids = np.zeros((rows, 5), np.int32)       # 20 bytes a row


class FakeReader:
    """Re-iterable; each `next()` takes READ_S on the fake clock, the
    first of a pass FIRST_READ_S (the permutation)."""

    def __init__(self, clock: FakeClock, n: int, rows: int = 8):
        self.clock, self.n, self.rows = clock, n, rows

    def __iter__(self):
        for i in range(self.n):
            self.clock.now += FIRST_READ_S if i == 0 else READ_S
            yield Batch(i, self.rows)


def fake_put_fn(clock: FakeClock):
    def put_fn(b):
        clock.now += PUT_S
        return (b.ids, b.ids[:, 0].astype(np.float32))  # 24 bytes a row
    return put_fn


@pytest.fixture(autouse=True)
def batch_seq_from_zero(monkeypatch):
    """The sequence number is the process's; each test counts from 0."""
    monkeypatch.setattr(prefetch, "_BATCH_SEQ", itertools.count())


def durations(recorder, name):
    return [round(r["t1"] - r["t0"], 9) for r in recorder.records(name)]


def attrs(recorder, name, key):
    return [r["attrs"].get(key) for r in recorder.records(name)]


# ---- the recorder -------------------------------------------------------

def test_recorder_is_bounded_and_keeps_the_newest():
    rec = MemoryTracer(maxlen=8)
    for i in range(20):
        with rec.start_span("x", i=i):
            pass
    assert attrs(rec, "x", "i") == list(range(12, 20))
    assert rec.live_spans() == []


def test_default_recorder_holds_4096_batches_and_is_process_wide():
    assert memory_tracer() is memory_tracer()
    assert memory_tracer()._records.maxlen >= 4 * 4096
    assert memory_tracer().enabled and memory_tracer().annotate
    assert not Tracer.disabled().annotate


def test_recorder_is_thread_safe_under_producer_and_consumer():
    rec = MemoryTracer(maxlen=8192)
    n, errors = 3000, []

    def emit(name):
        try:
            for i in range(n):
                with rec.start_span(name, i=i):
                    pass
        except BaseException as e:    # pragma: no cover - the failure
            errors.append(e)

    def snapshot():
        try:
            for _ in range(300):
                for r in rec.records("infeed/"):
                    assert r["t1"] >= r["t0"]
        except BaseException as e:    # pragma: no cover - the failure
            errors.append(e)

    threads = [threading.Thread(target=emit, args=("infeed/read",)),
               threading.Thread(target=emit, args=("infeed/pop_wait",)),
               threading.Thread(target=snapshot)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)        # many more thread switches
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors
    assert len(rec.records()) == 2 * n and rec.live_spans() == []
    # each thread's own spans are all there, in its order
    for name in ("infeed/read", "infeed/pop_wait"):
        assert attrs(rec, name, "i") == list(range(n))


def test_span_interval_gives_record_span_its_timestamps():
    clock = FakeClock()
    rec = MemoryTracer(clock=clock)
    with rec.start_span("a") as span:
        assert span.interval == (100.0, None)
        clock.now += 1.5
    assert span.interval == (100.0, 101.5)
    rec.record_span("b", *span.interval, seq=3)
    a, b = rec.records()
    assert (a["t0"], a["t1"]) == (b["t0"], b["t1"]) == (100.0, 101.5)
    assert b["attrs"] == {"seq": 3}


def test_recorder_annotates_nothing_in_a_process_without_jax(tmp_path):
    """obs/ never imports jax itself: a span under the recorder works
    where jax cannot be imported at all."""
    blocker = tmp_path / "block"
    blocker.mkdir()
    (blocker / "jax.py").write_text("raise ImportError('jax blocked')\n")
    code = textwrap.dedent("""
        import sys
        from code2vec_tpu.obs.trace import memory_tracer
        with memory_tracer().start_span("infeed/read", seq=0):
            pass
        assert "jax" not in sys.modules
        assert [r["name"] for r in memory_tracer().records()] == \\
            ["infeed/read"]
    """)
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [str(blocker), REPO]))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr


# ---- the production record, one thread, exact ---------------------------

class FakeQueue:
    """What `_pop` needs of a queue: `get()` takes `wait_s` on the
    fake clock."""

    def __init__(self, clock, items, wait_s):
        self.clock, self.items, self.wait_s = clock, list(items), wait_s

    def get(self):
        self.clock.now += self.wait_s
        return self.items.pop(0)


def produce_on_this_thread(infeed, clock, passes, blocked_s):
    """Drive `_produce` as the producer thread does, with a `put` that
    blocks for `blocked_s(k)` on the k-th item; then pop every item."""
    items = []

    def put(item):
        clock.now += blocked_s(len(items))
        items.append(item)
        return True

    for _ in range(passes):
        infeed._produce(put)
    q = FakeQueue(clock, items, wait_s=0.004)
    out = []
    while q.items:
        out.extend(infeed._emit(infeed._pop(q)))
    return out


def test_device_prefetcher_record_is_exact_across_two_passes():
    clock = FakeClock()
    infeed = DevicePrefetcher(FakeReader(clock, 3), fake_put_fn(clock))
    rec = infeed._recorder = MemoryTracer(clock=clock)
    out = produce_on_this_thread(infeed, clock, passes=2,
                                 blocked_s=lambda k: 0.1 * k)
    assert [host.i for _dev, host in out] == [0, 1, 2, 0, 1, 2]
    assert all(len(pair) == 2 for pair in out)
    reads = rec.records("infeed/read")
    # the last next() of each pass finds it exhausted: a span with no seq
    assert [r["attrs"].get("exhausted", False) for r in reads] == \
        [False] * 3 + [True] + [False] * 3 + [True]
    named = [r for r in reads if "seq" in r["attrs"]]
    assert [r["attrs"]["seq"] for r in named] == list(range(6))
    assert [r["attrs"]["epoch_first"] for r in named] == \
        [True, False, False] * 2
    assert [r["attrs"]["rows"] for r in named] == [8] * 6
    assert [round(r["t1"] - r["t0"], 9) for r in named] == \
        [FIRST_READ_S, READ_S, READ_S] * 2
    assert durations(rec, "infeed/transfer") == [PUT_S] * 6
    assert attrs(rec, "infeed/transfer", "bytes") == [8 * 24] * 6
    assert attrs(rec, "infeed/transfer", "seq") == list(range(6))
    assert durations(rec, "infeed/blocked") == \
        [round(0.1 * k, 9) for k in range(6)]
    assert attrs(rec, "infeed/blocked", "seq") == list(range(6))
    assert durations(rec, "infeed/pop_wait") == [0.004] * 6
    assert attrs(rec, "infeed/pop_wait", "seq") == list(range(6))
    # a record holds what a reader reads, and no more
    assert all(set(r) == {"name", "t0", "t1", "tname", "attrs"}
               for r in rec.records())
    assert rec.live_spans() == []


def test_batch_seq_is_the_processes_not_the_feeds():
    """Two feeds in one process never give two batches one number, so
    a pop's `seq` names one batch of the whole record."""
    clock = FakeClock()
    rec = MemoryTracer(clock=clock)
    for _ in range(2):
        infeed = DevicePrefetcher(FakeReader(clock, 2), fake_put_fn(clock))
        infeed._recorder = rec
        produce_on_this_thread(infeed, clock, passes=1,
                               blocked_s=lambda k: 0.0)
    sync = _SyncInfeed(FakeReader(clock, 2), fake_put_fn(clock))
    sync._recorder = rec
    list(sync)
    assert attrs(rec, "infeed/transfer", "seq") == list(range(6))
    assert attrs(rec, "infeed/pop_wait", "seq") == list(range(4))


def test_chunked_prefetcher_record_is_exact():
    clock = FakeClock()

    def to_arrays(b):
        clock.now += PUT_S
        return (b.ids,)                                # 20 bytes a row

    def transfer(stacked):
        clock.now += STACK_S
        return stacked

    infeed = ChunkedDevicePrefetcher(FakeReader(clock, 5), to_arrays,
                                     chunk=2, transfer=transfer)
    rec = infeed._recorder = MemoryTracer(clock=clock)
    out = produce_on_this_thread(infeed, clock, passes=1,
                                 blocked_s=lambda k: 0.2)
    assert [host.i for _dev, host in out] == [0, 1, 2, 3, 4]
    assert out[3][0][0].shape == (8, 5)
    transfers = rec.records("infeed/transfer")
    own = [r for r in transfers if "stacked" not in r["attrs"]]
    stacked = [r for r in transfers if "stacked" in r["attrs"]]
    # every batch's host arrays under its own seq, with its bytes; each
    # chunk's one stacked transfer and one put under its last batch's
    assert [r["attrs"]["seq"] for r in own] == [0, 1, 2, 3, 4]
    assert [r["attrs"]["bytes"] for r in own] == [160] * 5
    assert [(r["attrs"]["seq"], r["attrs"]["stacked"])
            for r in stacked] == [(1, 2), (3, 2), (4, 1)]
    assert [round(r["t1"] - r["t0"], 9) for r in stacked] == [STACK_S] * 3
    assert attrs(rec, "infeed/blocked", "seq") == [1, 3, 4]
    assert durations(rec, "infeed/blocked") == [0.2] * 3
    # a pop names its chunk's first batch
    assert attrs(rec, "infeed/pop_wait", "seq") == [0, 2, 4]
    assert rec.live_spans() == []


def test_sync_infeed_records_on_the_callers_thread_and_never_blocks():
    clock = FakeClock()
    infeed = _SyncInfeed(FakeReader(clock, 3), fake_put_fn(clock))
    rec = infeed._recorder = MemoryTracer(clock=clock)
    for _epoch in range(2):
        assert [host.i for _dev, host in infeed] == [0, 1, 2]
    named = [r for r in rec.records("infeed/read") if "seq" in r["attrs"]]
    assert [round(r["t1"] - r["t0"], 9) for r in named] == \
        [FIRST_READ_S, READ_S, READ_S] * 2
    assert durations(rec, "infeed/transfer") == [PUT_S] * 6
    assert {r["tname"] for r in rec.records()} == \
        {threading.current_thread().name}
    assert rec.records("infeed/blocked") == []
    assert rec.records("infeed/pop_wait") == []


# ---- how often the gather spreads a PAD read -----------------------------

@pytest.mark.parametrize("reader_kind", ["binary", "text"])
def test_infeed_read_counts_the_batchs_pad_slots(tmp_path, reader_kind):
    """`pad_slots` on `infeed/read` (and on the `--trace` span built
    from the record) is the count of the batch's slots whose path id is
    PAD, taken from the mask the reader made."""
    from code2vec_tpu.data.reader import open_reader
    from code2vec_tpu.obs import SpanChannel, infeed_produce_instrument
    from tests.helpers import build_tiny_dataset, load_tiny_vocabs

    prefix = build_tiny_dataset(str(tmp_path), n_train=40, n_val=4,
                                n_test=4, max_contexts=16,
                                binarize=reader_kind == "binary")
    vocabs = load_tiny_vocabs(prefix)
    reader = open_reader(prefix + ".train.c2v", vocabs, 16, 16)
    assert type(reader).__name__ == {"binary": "BinaryShardReader",
                                     "text": "C2VTextReader"}[reader_kind]
    tele = _Events()
    infeed = _SyncInfeed(reader, lambda b: (b.path_indices,))
    rec = infeed._recorder = MemoryTracer()
    infeed._on_produced = infeed_produce_instrument(Tracer.create(tele),
                                                    SpanChannel())
    batches = [host for _dev, host in infeed]
    assert len(batches) == 3            # the last one padded from 8 rows
    pad = vocabs.path_vocab.pad_index
    want = [int((b.path_indices == pad).sum()) for b in batches]
    assert want == [int((b.context_valid_mask == 0).sum()) for b in batches]
    assert 0 < want[0] < 16 * 16 and want[2] >= 8 * 16
    named = [r for r in rec.records("infeed/read") if "seq" in r["attrs"]]
    assert [r["attrs"]["pad_slots"] for r in named] == want
    assert [r["attrs"]["rows"] for r in named] == [16, 16, 8]
    assert [s["attrs"]["pad_slots"] for s in tele.spans] == want


def test_a_batch_with_no_mask_has_no_pad_count():
    clock = FakeClock()
    infeed = _SyncInfeed(FakeReader(clock, 2), fake_put_fn(clock))
    rec = infeed._recorder = MemoryTracer(clock=clock)
    list(infeed)
    named = [r for r in rec.records("infeed/read") if "seq" in r["attrs"]]
    assert [r["attrs"]["pad_slots"] for r in named] == [None, None]


def test_infeed_transfer_carries_the_gathered_slots_a_batch_names():
    """`gather_slots` on `infeed/transfer` (and on the `--trace` span)
    is what the transferred batch says of itself: the model's training
    batches do (`training/steps.TrainBatch`), a plain tuple does not."""
    from code2vec_tpu.obs import SpanChannel, infeed_produce_instrument
    from code2vec_tpu.training.steps import TrainBatch

    clock, tele = FakeClock(), _Events()
    marked = _SyncInfeed(FakeReader(clock, 3), lambda b: TrainBatch(
        (np.zeros(4),), b.i % 2 == 0, 100 + b.i))
    rec = marked._recorder = MemoryTracer(clock=clock)
    marked._on_produced = infeed_produce_instrument(Tracer.create(tele),
                                                    SpanChannel())
    assert [dev.fits for dev, _host in marked] == [True, False, True]
    assert [r["attrs"]["gather_slots"]
            for r in rec.records("infeed/transfer")] == [100, 101, 102]
    assert [s["attrs"]["gather_slots"] for s in tele.spans] == [100, 101,
                                                                102]
    plain = _SyncInfeed(FakeReader(clock, 2), fake_put_fn(clock))
    rec = plain._recorder = MemoryTracer(clock=clock)
    list(plain)
    assert all("gather_slots" not in r["attrs"]
               for r in rec.records("infeed/transfer"))


def test_infeed_transfer_carries_the_scored_pairs_a_batch_names():
    """`attn_pairs` beside `gather_slots` (ISSUE 35): on the transfer's
    span and on the `--trace` span where the transferred batch says it
    of itself (the model's training batches of an encoder whose softmax
    mixers score by query block do), on neither where it does not."""
    from code2vec_tpu.obs import SpanChannel, infeed_produce_instrument
    from code2vec_tpu.training.steps import TrainBatch

    def put(b):
        out = TrainBatch((np.zeros(4),), True, 100 + b.i)
        if b.i != 1:
            out.attn_pairs = 5000 + b.i
        return out

    clock, tele = FakeClock(), _Events()
    infeed = _SyncInfeed(FakeReader(clock, 3), put)
    rec = infeed._recorder = MemoryTracer(clock=clock)
    infeed._on_produced = infeed_produce_instrument(Tracer.create(tele),
                                                    SpanChannel())
    list(infeed)
    assert [r["attrs"].get("attn_pairs")
            for r in rec.records("infeed/transfer")] == [5000, None, 5002]
    assert "attn_pairs" not in rec.records("infeed/transfer")[1]["attrs"]
    assert [r["attrs"]["gather_slots"]
            for r in rec.records("infeed/transfer")] == [100, 101, 102]
    assert [s["attrs"].get("attn_pairs") for s in tele.spans] == [
        5000, None, 5002]


def test_infeed_transfer_carries_the_fed_forward_slots_a_batch_names():
    """`ff_slots` beside them (ISSUE 37): every count of
    `prefetch.TRANSFER_COUNTS` that the transferred batch says of
    itself stands on the transfer's span and on the `--trace` span, and
    one it does not say stands on neither."""
    from code2vec_tpu.data.prefetch import TRANSFER_COUNTS
    from code2vec_tpu.obs import SpanChannel, infeed_produce_instrument
    from code2vec_tpu.training.steps import TrainBatch

    assert TRANSFER_COUNTS == ("gather_slots", "attn_pairs", "ff_slots")

    def put(b):
        out = TrainBatch((np.zeros(4),), True, 100 + b.i)
        if b.i != 1:
            out.ff_slots = 70 + b.i
        return out

    clock, tele = FakeClock(), _Events()
    infeed = _SyncInfeed(FakeReader(clock, 3), put)
    rec = infeed._recorder = MemoryTracer(clock=clock)
    infeed._on_produced = infeed_produce_instrument(Tracer.create(tele),
                                                    SpanChannel())
    list(infeed)
    transfers = rec.records("infeed/transfer")
    assert [r["attrs"].get("ff_slots") for r in transfers] == [70, None, 72]
    assert "ff_slots" not in transfers[1]["attrs"]
    assert all("attn_pairs" not in r["attrs"] for r in transfers)
    assert [s["attrs"].get("ff_slots") for s in tele.spans] == [70, None, 72]
    assert [s["attrs"]["gather_slots"] for s in tele.spans] == [100, 101,
                                                                102]


# ---- the production record, on its threads ------------------------------

@pytest.mark.parametrize("kind", ["per_batch", "chunked"])
def test_persistent_epochs_record_across_an_epoch_boundary(kind):
    clock = FakeClock()
    if kind == "per_batch":
        infeed = DevicePrefetcher(FakeReader(clock, 4), fake_put_fn(clock),
                                  depth=2)
    else:
        infeed = ChunkedDevicePrefetcher(
            FakeReader(clock, 4), lambda b: fake_put_fn(clock)(b),
            chunk=2, depth=2, transfer=lambda a: a)
    rec = infeed._recorder = MemoryTracer(clock=clock)
    seen = []
    for epoch, batches in persistent_epochs(infeed, 3):
        seen.extend((epoch, host.i) for _dev, host in batches)
    assert seen == [(e, i) for e in (1, 2, 3) for i in range(4)]
    named = [r for r in rec.records("infeed/read") if "seq" in r["attrs"]]
    assert [r["attrs"]["seq"] for r in named] == list(range(12))
    assert [r["attrs"]["epoch_first"] for r in named] == \
        [True, False, False, False] * 3
    # only the producer's fakes move the clock, so each span holds
    # exactly what ran inside it, and the put never waits on the clock
    assert [round(r["t1"] - r["t0"], 9) for r in named] == \
        [FIRST_READ_S, READ_S, READ_S, READ_S] * 3
    own = [r for r in rec.records("infeed/transfer")
           if "stacked" not in r["attrs"]]
    assert [round(r["t1"] - r["t0"], 9) for r in own] == [PUT_S] * 12
    assert [r["attrs"]["bytes"] for r in own] == [8 * 24] * 12
    assert set(durations(rec, "infeed/blocked")) == {0.0}
    pops = [r for r in rec.records("infeed/pop_wait")
            if "seq" in r["attrs"]]
    per_item = 1 if kind == "per_batch" else 2
    assert [r["attrs"]["seq"] for r in pops] == \
        list(range(0, 12, per_item))
    # the consumer's spans are on its thread, the producer's on theirs
    assert {r["tname"] for r in pops} == {threading.current_thread().name}
    assert {r["tname"] for r in named} == {"train-infeed"}
    # the pops of the three end-of-epoch markers name no batch
    assert len(rec.records("infeed/pop_wait")) == len(pops) + 3
    assert rec.live_spans() == []


@pytest.mark.parametrize("how", ["iter", "persistent_epochs"])
def test_abandoned_iteration_leaves_no_open_span(how):
    clock = FakeClock()
    infeed = DevicePrefetcher(FakeReader(clock, 50), fake_put_fn(clock),
                              depth=2)
    rec = infeed._recorder = MemoryTracer(clock=clock)
    if how == "iter":
        it = iter(infeed)
        next(it)
        it.close()
    else:
        epochs = persistent_epochs(infeed, 5)
        _epoch, batches = next(epochs)
        next(batches)
        batches.close()
        epochs.close()
    assert rec.live_spans() == []
    assert len(rec.records("infeed/pop_wait")) == 1
    # the producer stopped in the put it was blocked in, far from the
    # reader's end, and that put ended as a span
    produced = len(rec.records("infeed/transfer"))
    assert produced < 10
    assert len(rec.records("infeed/blocked")) == produced


# ---- the --trace span from the same clock reads -------------------------

class _Events:
    """What a Tracer needs of a telemetry run."""

    enabled, sinks = True, [object()]

    def __init__(self):
        self.spans = []

    def event(self, kind, **ev):
        assert kind == "span"
        self.spans.append(ev)


def test_trace_infeed_produce_covers_the_readers_next():
    from code2vec_tpu.obs import SpanChannel, infeed_produce_instrument

    clock = FakeClock()
    tele = _Events()
    tracer = Tracer.create(tele, clock=clock)
    reads = []
    real_clock = tracer.clock
    tracer.clock = lambda: (reads.append(1), real_clock())[1]
    channel = SpanChannel()
    infeed = DevicePrefetcher(FakeReader(clock, 3), fake_put_fn(clock))
    infeed._recorder = MemoryTracer(clock=clock)
    infeed._on_produced = infeed_produce_instrument(tracer, channel)
    produce_on_this_thread(infeed, clock, passes=1,
                           blocked_s=lambda k: 0.5)
    assert [s["name"] for s in tele.spans] == ["infeed/produce"] * 3
    # read + transfer, the wait on the queue left out
    assert [s["dur_ms"] for s in tele.spans] == pytest.approx(
        [1e3 * (FIRST_READ_S + PUT_S)] + [1e3 * (READ_S + PUT_S)] * 2)
    assert tele.spans[0]["t0"] == 100.0
    assert [s["attrs"]["seq"] for s in tele.spans] == [0, 1, 2]
    assert [s["attrs"]["bytes"] for s in tele.spans] == [192] * 3
    # one context a batch went down the channel, in order
    sent = [channel.recv() for _ in range(3)]
    assert [c.span_id for c in sent] == [s["span"] for s in tele.spans]
    assert channel.recv() is None
    # and the --trace tracer took no clock read of its own
    assert reads == []
    assert infeed_produce_instrument(Tracer.disabled(), None) is None


# ---- on the profiler's clock --------------------------------------------

def test_cpu_profile_of_a_tiny_run_holds_the_infeed_annotations(tmp_path):
    import jax

    from code2vec_tpu.models.jax_model import Code2VecModel
    from tests.helpers import build_tiny_dataset
    from tests.test_model import tiny_config

    prefix = build_tiny_dataset(str(tmp_path), n_train=64, n_val=8,
                                n_test=8, max_contexts=16)
    model = Code2VecModel(tiny_config(prefix, NUM_TRAIN_EPOCHS=2))
    before = len(memory_tracer().records("infeed/"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "prof"),
                             profiler_options=options)
    try:
        model.train()
    finally:
        jax.profiler.stop_trace()
    model.close_session()
    (path,) = glob.glob(str(tmp_path / "prof" / "plugins" / "profile"
                            / "*" / "*.xplane.pb"))
    annotated = {ev.name
                 for plane in jax.profiler.ProfileData.from_file(path).planes
                 for line in plane.lines for ev in line.events
                 if ev.name.startswith("infeed/")}
    assert annotated == {"infeed/read", "infeed/transfer",
                         "infeed/blocked", "infeed/pop_wait"}
    # and the always-on record of the same run: 2 batches an epoch
    mine = memory_tracer().records("infeed/")[before:]
    named = [r for r in mine if r["name"] == "infeed/read"
             and "seq" in r["attrs"]]
    assert len(named) == 4
    assert [r["attrs"]["epoch_first"] for r in named] == \
        [True, False, True, False]
    assert all(r["attrs"]["rows"] == 32 for r in named)
    producer = {r["tname"] for r in named}
    consumer = {r["tname"] for r in mine if r["name"] == "infeed/pop_wait"}
    assert producer == {"train-infeed"} and producer.isdisjoint(consumer)


# ---- the step's phases by name ------------------------------------------

@pytest.mark.parametrize("encoder,extra", [
    ("bag", []),
    ("transformer", ["c2v/xf_layer_0", "c2v/xf_layer_1"])])
def test_compiled_step_names_its_phases(encoder, extra):
    import jax
    import jax.numpy as jnp

    from code2vec_tpu.models.encoder import ModelDims, init_params
    from code2vec_tpu.training.optimizers import make_optimizer
    from code2vec_tpu.training.steps import make_train_step

    dims = ModelDims(token_vocab_size=50, path_vocab_size=40,
                     target_vocab_size=30, embeddings_size=8,
                     max_contexts=6, encoder_type=encoder, xf_heads=2)
    optimizer = make_optimizer(1e-3)
    params = init_params(jax.random.PRNGKey(0), dims)
    step = make_train_step(dims, optimizer, use_sampled_softmax=True,
                           num_sampled=8)
    B, C = 4, 6
    ids = jnp.ones((B, C), jnp.int32)
    batch = (jnp.ones((B,), jnp.int32), ids, ids, ids,
             jnp.ones((B, C), jnp.float32), jnp.ones((B,), jnp.float32))
    text = step.lower(params, optimizer.init(params), batch,
                      jax.random.PRNGKey(1)).compile().as_text()
    op_names = re.findall(r'op_name="([^"]*)"', text)
    for scope in ["c2v/embed_gather", "c2v/encode", "c2v/pool", "c2v/loss",
                  "c2v/table_apply", "c2v/dense_apply", *extra]:
        assert any(scope in n for n in op_names), scope
    # the backward's ops carry the forward's scope by themselves
    assert any("transpose(jvp(c2v/embed_gather))" in n for n in op_names)
    assert any("transpose(jvp(c2v/encode))" in n for n in op_names)


def _op_names(step, *args):
    return re.findall(r'op_name="([^"]*)"',
                      step.lower(*args).compile().as_text())


@pytest.mark.parametrize("which", ["adam", "sparse", "vm", "vm_sparse"])
def test_every_train_step_names_its_applies(which):
    """The table and the dense apply go by the same two names whatever
    the step: adam's one walk over all leaves has a name of its own."""
    import jax
    import jax.numpy as jnp
    import optax

    from code2vec_tpu.models.encoder import ModelDims, init_params
    from code2vec_tpu.training.optimizers import make_optimizer

    dims = ModelDims(token_vocab_size=32, path_vocab_size=16,
                     target_vocab_size=8, embeddings_size=8,
                     max_contexts=5)
    B, C, K = 4, 5, 3
    ids = jnp.ones((B, C), jnp.int32)
    head = (jnp.ones((B,), jnp.int32), ids, ids, ids,
            jnp.ones((B, C), jnp.float32))
    weights, rng = jnp.ones((B,), jnp.float32), jax.random.PRNGKey(1)
    scopes = ["c2v/table_apply", "c2v/dense_apply"]
    if which == "adam":
        from code2vec_tpu.training.steps import make_train_step
        optimizer = make_optimizer(1e-3, embedding_optimizer="adam")
        params = init_params(jax.random.PRNGKey(0), dims)
        names = _op_names(make_train_step(dims, optimizer), params,
                          optimizer.init(params), head + (weights,), rng)
        scopes.append("c2v/apply")
    elif which == "sparse":
        from code2vec_tpu.training.sparse_steps import (
            init_sparse_opt_state, make_sparse_train_step)
        params = init_params(jax.random.PRNGKey(0), dims)
        state = init_sparse_opt_state(params, optax.adam(1e-3),
                                      use_sampled_softmax=False)
        names = _op_names(make_sparse_train_step(dims, learning_rate=1e-3),
                          params, state, head + (weights,), rng)
        scopes += ["c2v/embed_gather", "c2v/encode", "c2v/pool",
                   "c2v/loss"]
    else:
        from code2vec_tpu.models.varmisuse import init_vm_params
        from code2vec_tpu.training.vm_steps import (
            init_vm_sparse_opt_state, make_vm_train_step)
        params = init_vm_params(jax.random.PRNGKey(0), dims)
        opt = optax.adam(1e-3)
        batch = head + (jnp.ones((B, K), jnp.int32),
                        jnp.ones((B, K), jnp.float32), weights)
        if which == "vm":
            step, state = make_vm_train_step(dims, opt), opt.init(params)
        else:
            step = make_vm_train_step(dims, opt, sparse_updates=True,
                                      learning_rate=1e-3)
            state = init_vm_sparse_opt_state(params, opt)
        names = _op_names(step, params, state, batch, rng)
    for scope in scopes:   # `c2v/apply` is no part of `c2v/apply_x`
        named = re.compile(re.escape(scope) + r"([/)]|$)")
        assert any(named.search(n) for n in names), scope


def test_scoped_optimizer_keeps_state_and_numbers():
    import jax
    import jax.numpy as jnp
    import optax

    from code2vec_tpu.training.optimizers import _scoped

    tx = optax.adam(1e-2)
    params = {"w": jnp.arange(4.0)}
    grads = {"w": jnp.ones(4)}
    scoped = _scoped(tx, "c2v/dense_apply")
    s0, s1 = tx.init(params), scoped.init(params)
    assert jax.tree_util.tree_structure(s0) == \
        jax.tree_util.tree_structure(s1)
    u0, _ = tx.update(grads, s0, params)
    u1, _ = scoped.update(grads, s1, params)
    assert jnp.array_equal(u0["w"], u1["w"])
