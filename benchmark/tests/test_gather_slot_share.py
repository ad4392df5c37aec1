"""The reader of `gather_slot_share`: `reduce` on a recorded list of
spans with fitting and non-fitting batches against values worked out by
hand, what it gives a program whose transfers carry no count, and a
tiny CPU cell run with `--trace 1`.

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
        "gather_slot_share", os.path.join(helpers.REPO, "benchmark",
                                          "readers", "gather_slot_share.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _span(name, **attrs):
    return {"name": name, "t0": 0.0, "t1": 0.0, "attrs": attrs}


def recorded(counted: bool = True):
    """Five batches of 10 contexts. Batches 0, 1 and 3 are whole (8
    rows) and fit a staircase of 44 slots; batch 2 is whole and does not
    fit (80 slots); batch 4 is a short one of 3 rows (30 slots) that is
    produced and never popped. An end-of-epoch marker is popped before
    batch 2."""
    out = []
    for k, (rows, slots) in enumerate([(8, 44), (8, 44), (8, 80), (8, 44),
                                       (3, 30)]):
        out.append(_span("infeed/read", seq=k, rows=rows, pad_slots=5,
                         epoch_first=k in (0, 2)))
        attrs = dict(seq=k, bytes=1000)
        if counted:
            attrs["gather_slots"] = slots
        out.append(_span("infeed/transfer", **attrs))
        if k < 4:
            out.append(_span("infeed/pop_wait", seq=k))
        if k == 1:
            out.append(_span("infeed/read", exhausted=True))
            out.append(_span("infeed/pop_wait"))
    return out


def test_reduce_gives_the_share_worked_out_by_hand():
    reduce = _reader().reduce
    assert reduce(recorded(), 4, 10) == pytest.approx(
        100 * (44 + 44 + 80 + 44) / (4 * 8 * 10))
    # the last two pops only: the batch that did not fit and one that did
    assert reduce(recorded(), 2, 10) == pytest.approx(100 * 124 / 160)
    # a window of the one batch that did not fit reads 100
    assert reduce(recorded()[:-5], 1, 10) == pytest.approx(100.0)


@pytest.mark.parametrize("spans,steps", [
    (recorded(counted=False), 4),       # the parent: transfers, no count
    ([], 3),                            # an empty recorder
    (recorded(), 5),                    # fewer pops than steps
    ([s for s in recorded() if not (s["name"] == "infeed/transfer"
                                    and s["attrs"]["seq"] == 0)], 4),
    ([s for s in recorded() if not (s["name"] == "infeed/read"
                                    and s["attrs"].get("seq") == 3)], 4),
    (recorded(), 0)],
    ids=["no_count", "empty", "too_few_pops", "transfer_dropped",
         "read_dropped", "no_steps"])
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
    assert reader.read(ctx, {}) == pytest.approx(66.25)


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """The tests' tiny cells, with the metric's list of cells given the
    tiny ones too."""
    root = helpers.make_copy(str(tmp_path_factory.mktemp("bench") / "c"))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        manifest = json.load(f)
    (metric,) = [m for m in manifest["per_layer"]
                 if m["name"] == "gather_slot_share"]
    assert metric["layer"] == "embed gather"
    assert metric["source"] == "program_span"
    assert metric["workloads"] == [
        "bag-train-corpus", "xf2-train-corpus", "bag-train-corpus-x4",
        "lfm2moe-train-corpus"]
    metric["workloads"] += [w["name"] for w in manifest["workloads"]
                            if w["name"].startswith("tiny-")]
    with open(path, "w") as f:
        json.dump(manifest, f)
    return root


@pytest.mark.parametrize("workload,devices", [("tiny-bag-1", 1),
                                              ("tiny-xf2-4", 4)])
def test_traced_cell_prints_the_share(copy, workload, devices):
    """16 rows a device and 12 contexts: the staircase of so few rows
    is the whole rectangle, so every slot is gathered."""
    rc, result, err = helpers.run_cell(copy, workload, devices, trace=1,
                                       seconds=2)
    assert rc == 0, err[-3000:]
    share = result["metrics"]["gather_slot_share"]
    assert share["unit"] == "%"
    assert share["value"] == pytest.approx(100.0)
