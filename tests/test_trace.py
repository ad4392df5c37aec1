"""Request-scoped tracing (ISSUE 6): trace-id propagation across the
serving queue/batcher threads under concurrent load, the Chrome
trace-event export schema, critical-path breakdowns, the train-loop
span tree (step <- infeed producer, save <- step, writer <- save), and
the disabled path's zero-allocation discipline. All CPU tier-1."""

import json
import os
import threading

import numpy as np
import pytest

from code2vec_tpu.config import Config
from code2vec_tpu.obs import SpanChannel, Telemetry, Tracer
from code2vec_tpu.obs.trace import _NULL_SPAN


def _events(run_dir):
    out = []
    with open(os.path.join(run_dir, "events.jsonl"),
              encoding="utf-8") as f:
        for line in f:
            if line.strip():
                out.append(json.loads(line))
    return out


def _spans(run_dir):
    return [e for e in _events(run_dir) if e["kind"] == "span"]


# ---------------------------------------------------------------------
# tracer unit behavior
# ---------------------------------------------------------------------

def test_span_tree_ids_and_thread_local_parenting(tmp_path):
    tele = Telemetry.create(str(tmp_path), component="t")
    tr = Tracer.create(tele)
    root = tr.start_trace("root", k=1)
    with tr.start_span("mid", parent=root.context()):
        leaf = tr.start_span("leaf")  # implicit: current span = mid
        leaf.end()
    root.end()
    tele.close()
    spans = {s["name"]: s for s in _spans(tele.run_dir)}
    assert spans["root"]["trace"] == spans["mid"]["trace"] == \
        spans["leaf"]["trace"]
    assert spans["mid"]["parent"] == spans["root"]["span"]
    assert spans["leaf"]["parent"] == spans["mid"]["span"]
    assert spans["root"].get("parent") is None
    assert spans["root"]["attrs"] == {"k": 1}
    # distinct ids throughout
    assert len({s["span"] for s in spans.values()}) == 3


def test_record_span_retroactive_and_live_span_table(tmp_path):
    tele = Telemetry.create(str(tmp_path), component="t")
    tr = Tracer.create(tele)
    open_span = tr.start_trace("in-flight")
    ctx = tr.record_span("retro", 10.0, 10.25,
                         parent=open_span.context(), track="a-queue")
    assert ctx.trace_id == open_span.trace_id
    live = tr.live_spans()
    assert [s["name"] for s in live] == ["in-flight"]
    open_span.end()
    assert tr.live_spans() == []
    tele.close()
    retro = next(s for s in _spans(tele.run_dir) if s["name"] == "retro")
    assert retro["dur_ms"] == pytest.approx(250.0)
    assert retro["tname"] == "a-queue"


def test_span_channel_fifo():
    ch = SpanChannel()
    assert ch.recv() is None
    ch.send("a")
    ch.send("b")
    assert ch.recv() == "a" and ch.recv() == "b" and ch.recv() is None


def test_disabled_tracer_is_shared_and_allocation_free(tmp_path):
    tr = Tracer.disabled()
    assert tr is Tracer.disabled()
    assert not tr.enabled
    # every span-producing call returns the ONE shared null span
    assert tr.start_trace("x") is _NULL_SPAN
    assert tr.start_span("y", parent=None) is _NULL_SPAN
    assert tr.record_span("z", 0.0, 1.0) is None
    with tr.start_trace("w") as s:
        assert s is _NULL_SPAN
    assert _NULL_SPAN.end() == 0.0 and _NULL_SPAN.context() is None
    assert tr.live_spans() == []
    # memory-mode telemetry (no sinks) gets the disabled singleton too
    assert Tracer.create(Telemetry.memory("m")) is tr
    assert Tracer.create(Telemetry.disabled()) is tr
    assert Tracer.create(None) is tr


def test_disabled_path_stays_out_of_recorder_and_server():
    """PR 2 discipline: with trace off, the recorder wraps nothing new
    and the null tracer is what models/servers hold by default."""
    from code2vec_tpu.obs import TrainStepRecorder
    rec = TrainStepRecorder(Telemetry.disabled())
    infeed = [1, 2]
    assert rec.wrap(infeed) is infeed
    assert rec._tracer is Tracer.disabled()


# ---------------------------------------------------------------------
# propagation across the queue/batcher threads under concurrent load
# (stub model: no device work, so thread interleaving is the test)
# ---------------------------------------------------------------------

class _StubModel:
    telemetry = Telemetry.disabled()
    tracer = Tracer.disabled()

    def prepare_predict_rows(self, lines):
        from code2vec_tpu.models.jax_model import PreparedRows
        n = len([ln for ln in lines if ln.strip()])
        z = np.zeros((n, 4), np.int32)
        return PreparedRows(np.zeros((n,), np.int32), z, z, z,
                            z.astype(np.float32), ["m"] * n,
                            [[] for _ in range(n)])

    def predict_device(self, prepared):
        n = prepared.n
        return (np.zeros((n, 1), np.int32),
                np.zeros((n, 1), np.float32),
                np.zeros((n, 4), np.float32),
                np.zeros((n, 4), np.float32))

    def decode_predictions(self, prepared, device_out):
        return ["res"] * prepared.n

    def warmup_predict(self, max_batch):
        return [1]

    def predict_compile_count(self):
        return 0


@pytest.fixture()
def traced_serving_run(tmp_path):
    """12 concurrent 2-method requests through the REAL server +
    batcher with tracing on; yields the run dir's span events."""
    from code2vec_tpu.serving.server import PredictionServer
    cfg = Config(SERVE_CACHE_SIZE=0, SERVE_BATCH_MAX=8,
                 SERVE_BATCH_TIMEOUT_MS=2.0, TRACE=True,
                 TELEMETRY_DIR=str(tmp_path))
    cfg.train_data_path = "unused"  # bypass verify's train-or-load rule
    tele = Telemetry.create(str(tmp_path), config=cfg,
                            component="serve").make_threadsafe()
    server = PredictionServer(cfg, _StubModel(), telemetry=tele)
    server.start()
    try:
        threads = [threading.Thread(
            target=lambda i=i: server.predict_lines(
                [f"m a,{i},b", f"m c,{i},d"])) for i in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        server.close()
    tele.close()
    return _spans(tele.run_dir)


def test_trace_propagates_through_queue_and_batcher(traced_serving_run):
    spans = traced_serving_run
    roots = [s for s in spans if s["name"] == "serve/request"]
    assert len(roots) == 12
    flushes = [s for s in spans if s["name"] == "serve/batch_flush"]
    assert flushes, "no batch flush spans"
    # per request: parse + queue_wait + decode all carry ITS trace id
    for r in roots:
        mine = {s["name"] for s in spans if s["trace"] == r["trace"]}
        assert {"serve/parse", "serve/queue_wait",
                "serve/decode"} <= mine, (r["trace"], mine)
    # ACCEPTANCE: at least one request's queue -> batch chain shares a
    # single trace id end-to-end (the flush continues its trace)
    primary = {f["trace"] for f in flushes}
    assert primary & {r["trace"] for r in roots}
    # every other coalesced request is linked from some flush
    linked = {link[0] for f in flushes for link in (f.get("links") or ())}
    for r in roots:
        assert r["trace"] in primary or r["trace"] in linked
    # queue_wait is recorded retroactively on the virtual queue track,
    # parented to the request root (cross-thread handoff worked)
    by_span = {s["span"]: s for s in spans}
    for qw in (s for s in spans if s["name"] == "serve/queue_wait"):
        assert qw["tname"] == "serve-queue"
        assert by_span[qw["parent"]]["name"] == "serve/request"


def test_chrome_trace_schema_round_trip(traced_serving_run, tmp_path):
    from tools.trace_report import chrome_trace_events
    events = chrome_trace_events([({"process_index": 0},
                                   traced_serving_run)])
    # schema: every complete event has the required fields, metadata
    # names the threads, flows come in s/f pairs sharing an id
    assert {e["ph"] for e in events} >= {"X", "M", "s", "f"}
    for e in events:
        if e["ph"] == "X":
            assert {"name", "ts", "dur", "pid", "tid",
                    "args"} <= set(e)
            assert e["dur"] >= 1.0 and e["ts"] >= 0.0
            assert "trace" in e["args"] and "span" in e["args"]
    starts = {e["id"] for e in events if e["ph"] == "s"}
    finishes = {e["id"] for e in events if e["ph"] == "f"}
    assert starts and starts == finishes
    assert any(e["ph"] == "M" and e["name"] == "thread_name"
               for e in events)
    # and it survives a JSON round trip through the file format
    out = tmp_path / "trace.json"
    from tools.trace_report import write_chrome_trace
    # write_chrome_trace reads run dirs; emulate via json dump/load of
    # the same event list instead
    out.write_text(json.dumps({"traceEvents": events,
                               "displayTimeUnit": "ms"}))
    doc = json.loads(out.read_text())
    assert len(doc["traceEvents"]) == len(events)


def test_request_critical_path_breakdown(traced_serving_run, capsys):
    from tools.trace_report import render, request_breakdowns
    rows = request_breakdowns(traced_serving_run)
    assert len(rows) == 12
    for r in rows:
        # every phase of the critical path is attributed — device and
        # encode come from the flush (by trace id or by link)
        for phase in ("queue_wait", "parse", "decode"):
            assert phase in r, (phase, r)
        assert r["total_ms"] > 0
    text = render([({"run_id": "r", "component": "serve"},
                    traced_serving_run)])
    assert "queue_wait" in text and "| Phase (all requests) |" in text


# ---------------------------------------------------------------------
# train-loop trace tree (real model, tiny CPU run)
# ---------------------------------------------------------------------

@pytest.fixture(scope="module")
def traced_train_run(tmp_path_factory):
    from code2vec_tpu.models.jax_model import Code2VecModel
    from tests.helpers import build_tiny_dataset
    from tests.test_model import tiny_config
    d = str(tmp_path_factory.mktemp("trace_train"))
    prefix = build_tiny_dataset(d, n_train=64, n_val=8, n_test=8,
                                max_contexts=16)
    cfg = tiny_config(prefix, NUM_TRAIN_EPOCHS=2,
                      TELEMETRY_DIR=os.path.join(d, "tele"),
                      TRACE=True, SAVE_EVERY_EPOCHS=1)
    cfg.save_path = os.path.join(d, "ckpt")
    model = Code2VecModel(cfg)
    model.train()
    model.close_session()
    return _spans(model.telemetry.run_dir)


def test_step_spans_link_consumed_infeed_batches(traced_train_run):
    spans = traced_train_run
    steps = [s for s in spans if s["name"] == "train/step"]
    produces = {(s["trace"], s["span"])
                for s in spans if s["name"] == "infeed/produce"}
    assert steps and produces
    # FIFO alignment: every step links exactly the produce span of the
    # batch it consumed, and no two steps share one
    linked = [tuple(s["links"][0]) for s in steps if s.get("links")]
    assert len(linked) == len(steps), "a step lost its infeed handoff"
    assert len(set(linked)) == len(linked)
    assert set(linked) <= produces
    # the producer really ran on its own thread
    prod_threads = {s["tname"] for s in spans
                    if s["name"] == "infeed/produce"}
    step_threads = {s["tname"] for s in steps}
    assert prod_threads and prod_threads.isdisjoint(step_threads)


def test_save_spans_link_step_and_parent_writer(traced_train_run):
    spans = traced_train_run
    saves = [s for s in spans if s["name"] == "train/save_blocked"]
    writes = [s for s in spans if s["name"] == "train/save_write"]
    steps = {(s["trace"], s["span"]): s for s in spans
             if s["name"] == "train/step_cycle"}
    assert saves and writes
    for s in saves:
        assert s.get("links") and tuple(s["links"][0]) in steps, \
            "save did not link the step that triggered it"
    save_ids = {s["span"]: s for s in saves}
    for w in writes:
        # writer-thread span parented (cross-thread) to the loop's save
        assert w["parent"] in save_ids
        assert w["trace"] == save_ids[w["parent"]]["trace"]
        assert w["tname"] == "ckpt-writer"


def test_step_breakdown_tool(traced_train_run):
    from tools.trace_report import save_breakdowns, step_breakdowns
    rows = step_breakdowns(traced_train_run)
    assert rows and all("infeed_wait" in r and "step_ms" in r
                        for r in rows)
    assert {r["step"] for r in rows} == set(
        range(1, len(rows) + 1))
    srows = save_breakdowns(traced_train_run)
    assert srows and all(r["save_blocked_ms"] > 0 for r in srows)
    assert all(r["save_write_ms"] is not None for r in srows)


def test_trace_report_prints_the_pad_slots(traced_train_run):
    from tools.trace_report import pad_slot_summary, render
    produced = [s for s in traced_train_run
                if s["name"] == "infeed/produce"]
    pad = pad_slot_summary(traced_train_run)
    assert pad["batches"] == len(produced) > 0
    assert pad["pad_slots"] == sum(s["attrs"]["pad_slots"]
                                   for s in produced) > 0
    text = render([({"config": {"MAX_CONTEXTS": 16}}, traced_train_run)])
    share = 100.0 * pad["pad_slots"] / (pad["rows"] * 16)
    assert (f"PAD slots: {pad['pad_slots']:,} in {pad['rows']:,} rows of "
            f"{pad['batches']} batches ({share:.2f}% of rows x 16 "
            "contexts)") in text
    assert "PAD slots" not in render([({}, [
        dict(s, attrs={k: v for k, v in s["attrs"].items()
                       if k != "pad_slots"})
        for s in produced])])


def test_trace_report_prints_the_gathered_slots_beside_them(
        traced_train_run):
    """A text corpus gets no staircase: every slot of every batch is
    gathered, and the report says so from the producer's own count."""
    from tools.trace_report import pad_slot_summary, render
    pad = pad_slot_summary(traced_train_run)
    assert pad["gather_slots"] == pad["rows"] * 16
    text = render([({"config": {"MAX_CONTEXTS": 16}}, traced_train_run)])
    assert f"Gathered slots: {pad['gather_slots']:,} (100.00%)" in text
    produced = [s for s in traced_train_run
                if s["name"] == "infeed/produce"]
    assert "Gathered slots" not in render([({}, [
        dict(s, attrs={k: v for k, v in s["attrs"].items()
                       if k != "gather_slots"})
        for s in produced])])


def test_trace_report_prints_the_scored_pairs_beside_them(
        traced_train_run):
    """The bag's batches carry no `attn_pairs` and the report has no
    such line; where every counted batch carries one (an encoder whose
    softmax mixers score by query block) it prints their sum over rows
    x contexts squared."""
    from tools.trace_report import pad_slot_summary, render
    assert "attn_pairs" not in pad_slot_summary(traced_train_run)
    manifest = {"config": {"MAX_CONTEXTS": 16}}
    assert "Scored pairs" not in render([(manifest, traced_train_run)])
    produced = [s for s in traced_train_run
                if s["name"] == "infeed/produce"]
    counted = [dict(s, attrs=dict(s["attrs"],
                                  attn_pairs=s["attrs"]["rows"] * 100))
               for s in produced]
    pad = pad_slot_summary(counted)
    assert pad["attn_pairs"] == 100 * pad["rows"]
    assert (f"Scored pairs a head and softmax layer: {pad['attn_pairs']:,} "
            f"({100.0 * 100 / 256:.2f}% of rows x 16^2)") in render(
        [(manifest, counted)])
    # one batch without a count: no sum is printed for the others
    assert "Scored pairs" not in render([(manifest,
                                          counted[:-1] + produced[-1:])])


def test_trace_report_prints_the_fed_forward_slots_beside_them(
        traced_train_run):
    """`ff_slots` (ISSUE 37) likewise: no line for the bag, the sum over
    rows x contexts where every counted batch carries one."""
    from tools.trace_report import pad_slot_summary, render
    assert "ff_slots" not in pad_slot_summary(traced_train_run)
    manifest = {"config": {"MAX_CONTEXTS": 16}}
    assert "feed-forward half" not in render([(manifest, traced_train_run)])
    produced = [s for s in traced_train_run
                if s["name"] == "infeed/produce"]
    counted = [dict(s, attrs=dict(s["attrs"],
                                  ff_slots=s["attrs"]["rows"] * 10))
               for s in produced]
    pad = pad_slot_summary(counted)
    assert pad["ff_slots"] == 10 * pad["rows"]
    assert ("Slots a layer's feed-forward half ran over: "
            f"{pad['ff_slots']:,} ({100.0 * 10 / 16:.2f}%)") in render(
        [(manifest, counted)])
    assert "feed-forward half" not in render([(manifest,
                                               counted[:-1] + produced[-1:])])


def test_breakdown_primary_and_linked_requests_agree():
    """Regression: the flush's encode/device children share the
    PRIMARY request's trace id — they must be attributed through the
    flush exactly once, so the primary and its coalesced (linked)
    siblings report identical device cost."""
    from tools.trace_report import request_breakdowns
    spans = [
        {"name": "serve/request", "trace": "tA", "span": "r1",
         "t0": 0.0, "dur_ms": 50.0, "tid": 1, "tname": "c1"},
        {"name": "serve/request", "trace": "tB", "span": "r2",
         "t0": 0.0, "dur_ms": 50.0, "tid": 2, "tname": "c2"},
        # flush continues tA, links tB's root
        {"name": "serve/batch_flush", "trace": "tA", "span": "f1",
         "parent": "r1", "links": [["tB", "r2"]],
         "t0": 1.0, "dur_ms": 40.0, "tid": 3, "tname": "batcher"},
        {"name": "serve/encode", "trace": "tA", "span": "e1",
         "parent": "f1", "t0": 1.0, "dur_ms": 10.0, "tid": 3,
         "tname": "batcher"},
        {"name": "serve/device", "trace": "tA", "span": "d1",
         "parent": "f1", "t0": 2.0, "dur_ms": 30.0, "tid": 3,
         "tname": "batcher"},
    ]
    rows = {r["trace"]: r for r in request_breakdowns(spans)}
    assert rows["tA"]["encode"] == rows["tB"]["encode"] == 10.0
    assert rows["tA"]["device"] == rows["tB"]["device"] == 30.0


def test_span_end_is_idempotent_and_error_paths_close_roots(tmp_path):
    """Regression: a failing parse must not leak the request root into
    the live-span table (a long-running traced server would grow it
    unboundedly and pollute every stall dump)."""
    from code2vec_tpu.serving.server import PredictionServer
    tele = Telemetry.create(str(tmp_path), component="t")
    tr = Tracer.create(tele)
    s = tr.start_trace("x")
    assert s.end() > 0.0 or True
    assert s.end() == 0.0          # second end: no-op, no re-emit
    assert tr.live_spans() == []
    tele.close()
    assert sum(1 for e in _events(tele.run_dir)
               if e["kind"] == "span") == 1

    class _BadParseModel(_StubModel):
        def prepare_predict_rows(self, lines):
            raise ValueError("malformed input")

    cfg = Config(SERVE_CACHE_SIZE=0, TRACE=True,
                 TELEMETRY_DIR=str(tmp_path))
    cfg.train_data_path = "unused"
    tele2 = Telemetry.create(str(tmp_path), config=cfg,
                             component="serve").make_threadsafe()
    server = PredictionServer(cfg, _BadParseModel(), telemetry=tele2)
    server.start()
    try:
        for _ in range(3):
            with pytest.raises(ValueError):
                server.predict_lines(["m a,1,b"])
        assert server.tracer.live_spans() == [], \
            "failed requests leaked live spans"
    finally:
        server.close()
    tele2.close()


# ---- tools/trace_report.py --merge ----

def _write_run(d, manifest, events):
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, "manifest.json"), "w") as f:
        json.dump(manifest, f)
    with open(os.path.join(d, "events.jsonl"), "w") as f:
        for e in events:
            f.write(json.dumps(e) + "\n")


def test_trace_report_merge_cohort(tmp_path, capsys):
    from tools.trace_report import main, write_chrome_trace
    spans0 = [{"kind": "span", "trace": "t0", "span": "s0",
               "name": "train/step_cycle", "t0": 100.0, "dur_ms": 5.0,
               "tid": 1, "tname": "main", "attrs": {"step": 1}}]
    spans1 = [{"kind": "span", "trace": "t1", "span": "s1",
               "name": "train/step_cycle", "t0": 900.0, "dur_ms": 5.0,
               "tid": 1, "tname": "main", "attrs": {"step": 1}}]
    d0 = str(tmp_path / "r0")
    d1 = str(tmp_path / "r1")
    _write_run(d0, {"run_id": "run-p0", "component": "train",
                    "process_index": 0, "process_count": 2,
                    "created_unix": 1000.0}, spans0)
    _write_run(d1, {"run_id": "run-p1", "component": "train",
                    "process_index": 1, "process_count": 2,
                    "created_unix": 1002.5}, spans1)
    out = str(tmp_path / "merged.json")
    write_chrome_trace([d0, d1], out, merge=True)
    with open(out) as f:
        trace = json.load(f)["traceEvents"]
    names = [(e["name"], e.get("pid")) for e in trace]
    assert ("process_name", 0) in names and ("process_name", 1) in names
    # wall-clock alignment: p1's span starts ~2.5 s after p0's (each
    # run's own monotonic base is meaningless across processes)
    e0 = next(e for e in trace
              if e["name"] == "train/step_cycle" and e["pid"] == 0)
    e1 = next(e for e in trace
              if e["name"] == "train/step_cycle" and e["pid"] == 1)
    assert e1["ts"] - e0["ts"] == pytest.approx(2.5e6, abs=1.0)
    notes = [e for e in trace if e["name"] == "clock_note"]
    assert len(notes) == 2
    assert "monotonic" in notes[0]["args"]["note"]
    # unmerged export stays byte-compatible: no metadata injected
    out2 = str(tmp_path / "flat.json")
    write_chrome_trace([d0, d1], out2)
    with open(out2) as f:
        flat = json.load(f)["traceEvents"]
    assert not [e for e in flat if e["name"] in ("process_name",
                                                 "clock_note")]
    # --merge without --chrome: usage error, not a silent non-merge
    assert main(["--merge", d0, d1]) == 2
    capsys.readouterr()
