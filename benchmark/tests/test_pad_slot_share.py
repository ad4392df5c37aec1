"""The reader of `gather_pad_slot_share`: `reduce` on a recorded list of
spans against values worked out by hand, what it gives a program whose
reads carry no count, and a tiny CPU cell run with `--trace 1`.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""

import importlib.util
import json
import os
import types

import pytest

import bench_helpers as helpers


def _reader():
    spec = importlib.util.spec_from_file_location(
        "pad_slot_share", os.path.join(helpers.REPO, "benchmark", "readers",
                                       "pad_slot_share.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _span(name, **attrs):
    return {"name": name, "t0": 0.0, "t1": 0.0, "attrs": attrs}


def recorded(counted: bool = True):
    """Five batches of 8 rows and 10 contexts; batch k has 10 k PAD
    slots. An end-of-epoch marker is popped before batch 2, and the
    fifth batch is produced and never popped."""
    out = []
    for k in range(5):
        attrs = dict(seq=k, rows=8, epoch_first=k in (0, 2))
        if counted:
            attrs["pad_slots"] = 10 * k
        out.append(_span("infeed/read", **attrs))
        out.append(_span("infeed/transfer", seq=k, bytes=1000))
        if k < 4:
            out.append(_span("infeed/pop_wait", seq=k))
        if k == 1:
            out.append(_span("infeed/read", exhausted=True))
            out.append(_span("infeed/pop_wait"))
    return out


def test_reduce_gives_the_share_worked_out_by_hand():
    reduce = _reader().reduce
    assert reduce(recorded(), 4, 10) == pytest.approx(
        100 * (0 + 10 + 20 + 30) / (4 * 8 * 10))
    # the last two pops only: batches 2 and 3
    assert reduce(recorded(), 2, 10) == pytest.approx(100 * 50 / 160)


@pytest.mark.parametrize("spans,steps", [
    (recorded(counted=False), 4),       # the parent: reads with no count
    ([dict(s, attrs=dict(s["attrs"], pad_slots=None))
      for s in recorded()], 4),         # a reader whose batch has no mask
    ([], 3),                            # an empty recorder
    (recorded(), 5),                    # fewer pops than steps
    (recorded()[1:], 4),                # batch 0's read dropped
    (recorded(), 0)])
def test_reduce_gives_none_where_there_is_nothing_to_read(spans, steps):
    assert _reader().reduce(spans, steps, 10) is None


def test_read_takes_the_contexts_from_the_configuration(monkeypatch):
    from code2vec_tpu.obs import trace

    reader = _reader()
    ctx = types.SimpleNamespace(window={"steps": 4},
                                config={"model": {"max_contexts": 10}})
    monkeypatch.setattr(trace, "_MEMORY_TRACER", trace.MemoryTracer())
    assert reader.read(ctx, {}) is None
    monkeypatch.setattr(trace.MemoryTracer, "records",
                        lambda self, prefix="": recorded())
    assert reader.read(ctx, {}) == pytest.approx(18.75)


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """The tests' tiny cells, with the metric's list of cells given the
    tiny ones too."""
    root = helpers.make_copy(str(tmp_path_factory.mktemp("bench") / "c"))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        manifest = json.load(f)
    (metric,) = [m for m in manifest["per_layer"]
                 if m["name"] == "gather_pad_slot_share"]
    assert metric["layer"] == "embed gather"
    assert metric["source"] == "program_span"
    assert metric["workloads"] == [
        "bag-train-corpus", "xf2-train-corpus", "bag-train-corpus-x4"]
    metric["workloads"] += [w["name"] for w in manifest["workloads"]
                            if w["name"].startswith("tiny-")]
    with open(path, "w") as f:
        json.dump(manifest, f)
    return root


@pytest.mark.parametrize("workload,devices", [("tiny-bag-1", 1),
                                              ("tiny-xf2-4", 4)])
def test_traced_cell_prints_the_share_of_its_corpus(copy, workload, devices):
    rc, result, err = helpers.run_cell(copy, workload, devices, trace=1,
                                       seconds=2)
    assert rc == 0, err[-3000:]
    share = result["metrics"]["gather_pad_slot_share"]
    assert share["unit"] == "%"
    # bags of the corpus's lognormal lengths clipped to 12 contexts are
    # mostly full; whatever the share, the window's batches are whole
    # passes over a corpus of four, so it is the corpus's own
    facts = result["facts"]["corpus"]
    slots = facts["num_methods"] * 12
    assert share["value"] == pytest.approx(
        100 * (slots - facts["valid_contexts"]) / slots, abs=3.0)
