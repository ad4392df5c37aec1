"""The reader of the program's own infeed record: `reduce` on a recorded
list of spans against values worked out by hand, and a tiny CPU cell run
with `--trace 1` that prints the four metrics read through it.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""

import importlib.util
import json
import os
import types

import pytest

import bench_helpers as helpers

NEW = ("infeed_read_ms", "infeed_transfer_ms",
       "infeed_producer_busy_share", "infeed_bytes_per_step")


def _reader():
    spec = importlib.util.spec_from_file_location(
        "program_span", os.path.join(helpers.REPO, "benchmark", "readers",
                                     "program_span.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _span(name, t0, t1, **attrs):
    return {"name": name, "t0": t0, "t1": t1, "attrs": attrs}


def recorded():
    """Four batches as the producer and the consumer leave them: batch
    k is read for 0.010 + 0.001 k s, transferred for 0.002 s (1,000
    bytes) and blocked for 0.020 k s; its pop waits 0.003 + 0.001 k s.
    An end-of-epoch marker is popped (0.0005 s) before batch 2, and a
    fifth batch is produced and never popped."""
    out, t = [], 0.0
    for k in range(5):
        read, blocked = 0.010 + 0.001 * k, 0.020 * k
        out.append(_span("infeed/read", t, t + read, seq=k, rows=8,
                         epoch_first=k in (0, 2)))
        t += read
        out.append(_span("infeed/transfer", t, t + 0.002, seq=k,
                         bytes=1000))
        t += 0.002
        out.append(_span("infeed/blocked", t, t + blocked, seq=k))
        t += blocked
        if k < 4:
            out.append(_span("infeed/pop_wait", t, t + 0.003 + 0.001 * k,
                             seq=k))
        if k == 1:
            out.append(_span("infeed/read", t, t, exhausted=True))
            out.append(_span("infeed/pop_wait", t, t + 0.0005))
    return out


def test_reduce_gives_the_sums_worked_out_by_hand():
    got = _reader().reduce(recorded(), 4)
    assert got["bytes"] == 4000
    assert got["read_s"] == pytest.approx(0.010 + 0.011 + 0.012 + 0.013)
    assert got["transfer_s"] == pytest.approx(0.008)
    assert got["blocked_s"] == pytest.approx(0.0 + 0.02 + 0.04 + 0.06)
    assert got["pop_wait_s"] == pytest.approx(
        0.003 + 0.004 + 0.005 + 0.006 + 0.0005)


def test_reduce_clips_to_the_last_pops():
    got = _reader().reduce(recorded(), 2)       # batches 2 and 3
    assert got["bytes"] == 2000
    assert got["read_s"] == pytest.approx(0.012 + 0.013)
    assert got["blocked_s"] == pytest.approx(0.04 + 0.06)
    # the marker popped before batch 2 belongs to the same wait
    assert got["pop_wait_s"] == pytest.approx(0.005 + 0.006 + 0.0005)


def test_reduce_gives_none_on_a_chunked_feeds_record():
    """A real `ChunkedDevicePrefetcher` pops and puts a chunk at once,
    so its pops do not each name a batch with spans of its own."""
    import numpy as np

    from code2vec_tpu.data.prefetch import ChunkedDevicePrefetcher
    from code2vec_tpu.obs.trace import MemoryTracer

    batches = [types.SimpleNamespace(num_valid_examples=2) for _ in range(5)]
    infeed = ChunkedDevicePrefetcher(
        batches, lambda b: (np.zeros((2, 3), np.int32),), chunk=2,
        transfer=lambda a: a)
    rec = infeed._recorder = MemoryTracer()
    assert len(list(infeed)) == 5
    records = rec.records("infeed/")
    assert len([r for r in records if r["name"] == "infeed/pop_wait"
                and "seq" in r["attrs"]]) == 3
    for steps in (1, 2, 3):     # 1: the tail chunk, one batch, two transfers
        assert _reader().reduce(records, steps) is None


@pytest.mark.parametrize("spans,steps", [
    ([], 3),                                    # an empty recorder
    (recorded(), 5),                            # fewer pops than steps
    (recorded()[3:], 4),                        # batch 0's spans dropped
    (recorded(), 0)])
def test_reduce_gives_none_where_the_record_does_not_hold_the_window(
        spans, steps):
    assert _reader().reduce(spans, steps) is None


def test_read_gives_the_four_values_and_none_on_an_empty_recorder(
        monkeypatch, capsys):
    from code2vec_tpu.obs import trace

    reader = _reader()
    ctx = types.SimpleNamespace(window={"steps": 4, "infeed_wait_s": 0.0185})
    monkeypatch.setattr(trace, "_MEMORY_TRACER", trace.MemoryTracer())
    for value in ("read_ms", "transfer_ms", "busy_share", "mb_per_step"):
        assert reader.read(ctx, {"value": value}) is None
    monkeypatch.setattr(trace.MemoryTracer, "records",
                        lambda self, prefix="": recorded())
    assert reader.read(ctx, {"value": "read_ms"}) == pytest.approx(11.5)
    assert reader.read(ctx, {"value": "transfer_ms"}) == pytest.approx(2.0)
    assert reader.read(ctx, {"value": "mb_per_step"}) == pytest.approx(0.001)
    assert reader.read(ctx, {"value": "busy_share"}) == pytest.approx(
        100 * 0.054 / 0.174)
    assert "pop_wait 4.625 ms" in capsys.readouterr().err


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """The tests' tiny cells, with the four metrics' lists of cells
    given the tiny ones too."""
    root = helpers.make_copy(str(tmp_path_factory.mktemp("bench") / "c"))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        manifest = json.load(f)
    tiny = [w["name"] for w in manifest["workloads"]
            if w["name"].startswith("tiny-")]
    for metric in manifest["per_layer"]:
        if metric["name"] in NEW:
            metric["workloads"] += tiny
    with open(path, "w") as f:
        json.dump(manifest, f)
    return root


@pytest.mark.parametrize("workload,devices", [("tiny-bag-1", 1),
                                              ("tiny-bag-4", 4)])
def test_traced_cell_prints_the_four_metrics(copy, workload, devices):
    rc, result, err = helpers.run_cell(copy, workload, devices, trace=1,
                                       seconds=2)
    assert rc == 0, err[-3000:]
    m = result["metrics"]
    assert set(NEW) <= set(m), sorted(m)
    # 16 rows a device and 12 contexts: 4 arrays of 12 x 4 bytes and two
    # of 4 bytes a row
    rows = 16 * devices
    assert m["infeed_bytes_per_step"]["value"] == pytest.approx(
        rows * (4 * 12 * 4 + 8) / 1e6, rel=1e-12)
    assert m["infeed_read_ms"]["value"] > 0
    assert m["infeed_transfer_ms"]["value"] > 0
    assert 0 < m["infeed_producer_busy_share"]["value"] <= 100
    # the pops the program timed lie inside the waits the harness timed
    # from outside (the rest of `next(feed)` comes after the pop)
    line = [ln for ln in err.splitlines() if ln.startswith("program_span:")]
    assert len(line) == 1, err[-2000:]
    inside = float(line[0].split("pop_wait ")[1].split(" ms")[0])
    assert 0 < inside <= m["infeed_wait_ms"]["value"] + 0.001, line[0]
