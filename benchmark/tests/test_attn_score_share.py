"""The reader of `attn_score_share` (`readers/transfer_count_share.py`
under the metric file's `attr` and `power`): `reduce` on a recorded
excerpt of the program's `infeed/` spans (batches that fit their staircase and a
short one that does not) against values worked out by hand, what it
gives a program whose transfers carry no count, and a tiny CPU cell run
with `--trace 1`.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""

import copy as copy_module
import importlib.util
import json
import os
import types

import pytest

import bench_helpers as helpers
import test_moe_carried_row_share as carried


def _module(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(helpers.REPO, "benchmark", "readers",
                           name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _args():
    with open(os.path.join(helpers.REPO, "benchmark", "layer_metrics",
                           "attn_score_share.json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "transfer_count_share"
    return spec["args"]


def _reduce(records, steps, contexts):
    return _module("transfer_count_share").reduce(records, steps, contexts,
                                                  **_args())


def recorded():
    with open(os.path.join(helpers.TESTS, "recorded_records",
                           "infeed_attn_pairs_5batches.json")) as f:
        return json.load(f)


def test_reduce_on_the_recorded_excerpt():
    """Four whole batches of 128 rows x 16 contexts that fit a staircase
    of two rectangles, (0, 128) and (8, 48): 128 x 8 x 8 + 48 x 8 x 16 =
    14,336 of 32,768 pairs each; then the short batch of 9 rows, every
    pair of its 9 x 256."""
    reduce = _reduce
    excerpt = recorded()
    records, contexts = excerpt["records"], excerpt["max_contexts"]
    assert contexts == 16
    pairs = [r["attrs"]["attn_pairs"] for r in records
             if r["name"] == "infeed/transfer"]
    assert pairs == [14336] * 4 + [9 * 256]
    assert reduce(records, 5, contexts) == pytest.approx(
        100.0 * (4 * 14336 + 2304) / ((4 * 128 + 9) * 256))
    assert reduce(records, 5, contexts) == pytest.approx(
        excerpt["expect"]["attn_score_share_5"])
    # the short batch alone: no batch of the window fitted
    assert reduce(records, 1, contexts) == pytest.approx(100.0)
    # a window of whole batches only: every batch fitted
    whole = [r for r in records if r["attrs"].get("seq") != 8]
    assert reduce(whole, 4, contexts) == pytest.approx(100.0 * 14336 / 32768)
    assert reduce(whole, 4, contexts) == pytest.approx(
        excerpt["expect"]["attn_score_share_4_whole"])
    assert reduce(whole, 2, contexts) == pytest.approx(43.75)


def _without_counts(records):
    out = copy_module.deepcopy(records)
    for r in out:
        r["attrs"].pop("attn_pairs", None)
    return out


def _dropped(records, name, seq):
    return [r for r in records
            if not (r["name"] == name and r["attrs"].get("seq") == seq)]


@pytest.mark.parametrize("records,steps", [
    (_without_counts(recorded()["records"]), 4),    # the parent, the bag
    ([], 3),                                        # an empty recorder
    (recorded()["records"], 6),                     # fewer pops than steps
    (_dropped(recorded()["records"], "infeed/transfer", 5), 5),
    (_dropped(recorded()["records"], "infeed/read", 7), 5),
    (recorded()["records"], 0)],
    ids=["no_count", "empty", "too_few_pops", "transfer_dropped",
         "read_dropped", "no_steps"])
def test_reduce_gives_none_where_there_is_nothing_to_read(records, steps):
    assert _reduce(records, steps, 16) is None


def test_another_count_is_a_metric_file_and_no_reader():
    """The same reader under `gather_slots` and power 1 gives what
    `gather_slot_share.py` gives on the record."""
    excerpt = recorded()
    records, contexts = excerpt["records"], excerpt["max_contexts"]
    for steps in (1, 4, 5):
        want = _module("gather_slot_share").reduce(records, steps, contexts)
        assert want is not None
        assert _module("transfer_count_share").reduce(
            records, steps, contexts, "gather_slots", 1) == want


def test_read_takes_the_contexts_from_the_configuration(monkeypatch):
    from code2vec_tpu.obs import trace

    reader, args = _module("transfer_count_share"), _args()
    ctx = types.SimpleNamespace(window={"steps": 5},
                                config={"model": {"max_contexts": 16}})
    monkeypatch.setattr(trace, "_MEMORY_TRACER", trace.MemoryTracer())
    assert reader.read(ctx, args) is None
    monkeypatch.setattr(trace.MemoryTracer, "records",
                        lambda self, prefix="": recorded()["records"])
    assert reader.read(ctx, args) == pytest.approx(
        recorded()["expect"]["attn_score_share_5"])


def test_the_manifest_lists_the_cells_whose_pins_let_it():
    """`qwen3next-train-corpus` carries the count too and is left off
    the list: `test_qwen3next.py` pins that cell's set of metrics
    (REVIEW 35; the next `benchmark` PR appends it there and here)."""
    with open(os.path.join(helpers.REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    (entry,) = [m for m in manifest["per_layer"]
                if m["name"] == "attn_score_share"]
    assert entry == {
        "name": "attn_score_share", "unit": "%", "better": "lower",
        "source": "program_span", "layer": "latent attention",
        "moves": "train_methods_per_s",
        "workloads": ["lfm2moe-train-corpus", "joyai-train-corpus"]}


def test_traced_cell_prints_the_share(tmp_path_factory):
    """The tiny LFM2-MoE cell of `test_moe_carried_row_share.py`, the
    metric's list of cells given it: 16 rows x 12 contexts get no
    staircase (so few rows give the whole rectangle), every batch runs
    the full step and every pair is scored."""
    root = carried.copy.__wrapped__(tmp_path_factory)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        manifest = json.load(f)
    (metric,) = [m for m in manifest["per_layer"]
                 if m["name"] == "attn_score_share"]
    assert "tiny-lfm2moe-1" in metric["workloads"]
    rc, result, err = helpers.run_cell(root, "tiny-lfm2moe-1", 1, trace=1,
                                       seconds=2)
    assert rc == 0, err[-3000:]
    share = result["metrics"]["attn_score_share"]
    assert share["unit"] == "%"
    assert share["value"] == pytest.approx(100.0)
