"""`block_ff_slot_share`: the accepted reader
`readers/transfer_count_share.py` under this metric file's `attr` and
`power` ("a new count of this kind is a metric file, no reader").
`reduce` on a recorded excerpt of the program's `infeed/` spans (batches
that fit their staircase and a short one that does not) against values
worked out by hand, what it gives a program whose transfers carry no
such count (the parent, the bag), the manifest's entry, and a tiny CPU
cell run with `--trace 1`.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""

import copy as copy_module
import json
import os
import types

import pytest

import bench_helpers as helpers
import test_attn_score_share as pairs
import test_moe_carried_row_share as carried

NAME = "block_ff_slot_share"
BLOCK_CELLS = ["lfm2moe-train-corpus", "qwen3next-train-corpus",
               "joyai-train-corpus"]


def _args():
    with open(os.path.join(helpers.REPO, "benchmark", "layer_metrics",
                           NAME + ".json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "transfer_count_share"
    assert (spec["name"], spec["unit"]) == (NAME, "%")
    assert spec["args"] == {"attr": "ff_slots", "power": 1}
    return spec["args"]


def _reduce(records, steps, contexts):
    return pairs._module("transfer_count_share").reduce(
        records, steps, contexts, **_args())


def recorded():
    with open(os.path.join(helpers.TESTS, "recorded_records",
                           "infeed_ff_slots_5batches.json")) as f:
        return json.load(f)


def test_reduce_on_the_recorded_excerpt():
    """Four whole batches of 128 rows x 16 contexts that fit a staircase
    of two rectangles, (0, 128) and (8, 48): 128 x 8 + 48 x 8 = 1,408 of
    2,048 positions each; then the short batch of 9 rows, every position
    of its 9 x 16."""
    excerpt = recorded()
    records, contexts = excerpt["records"], excerpt["max_contexts"]
    assert contexts == 16
    slots = [r["attrs"]["ff_slots"] for r in records
             if r["name"] == "infeed/transfer"]
    assert slots == [1408] * 4 + [9 * 16]
    assert _reduce(records, 5, contexts) == pytest.approx(
        100.0 * (4 * 1408 + 144) / ((4 * 128 + 9) * 16))
    assert _reduce(records, 5, contexts) == pytest.approx(
        excerpt["expect"]["block_ff_slot_share_5"])
    # the short batch alone: no batch of the window fitted
    assert _reduce(records, 1, contexts) == pytest.approx(100.0)
    # a window of whole batches only: every batch fitted
    whole = [r for r in records if r["attrs"].get("seq") != 8]
    assert _reduce(whole, 4, contexts) == pytest.approx(100.0 * 1408 / 2048)
    assert _reduce(whole, 4, contexts) == pytest.approx(
        excerpt["expect"]["block_ff_slot_share_4_whole"])
    assert _reduce(whole, 2, contexts) == pytest.approx(68.75)
    # where the step packs what it gathers, the two shares agree
    assert _reduce(whole, 4, contexts) == pairs._module(
        "gather_slot_share").reduce(whole, 4, contexts)


def _without_counts(records):
    out = copy_module.deepcopy(records)
    for r in out:
        r["attrs"].pop("ff_slots", None)
    return out


@pytest.mark.parametrize("records,steps", [
    (_without_counts(recorded()["records"]), 4),    # the parent, the bag
    (pairs.recorded()["records"], 4),               # PR 35's record: none
    ([], 3),                                        # an empty recorder
    (recorded()["records"], 6),                     # fewer pops than steps
    (pairs._dropped(recorded()["records"], "infeed/transfer", 5), 5),
    (pairs._dropped(recorded()["records"], "infeed/read", 7), 5),
    (recorded()["records"], 0)],
    ids=["no_count", "the_parents_record", "empty", "too_few_pops",
         "transfer_dropped", "read_dropped", "no_steps"])
def test_reduce_gives_none_where_there_is_nothing_to_read(records, steps):
    assert _reduce(records, steps, 16) is None


def test_read_takes_the_contexts_from_the_configuration(monkeypatch):
    from code2vec_tpu.obs import trace

    reader, args = pairs._module("transfer_count_share"), _args()
    ctx = types.SimpleNamespace(window={"steps": 5},
                                config={"model": {"max_contexts": 16}})
    monkeypatch.setattr(trace, "_MEMORY_TRACER", trace.MemoryTracer())
    assert reader.read(ctx, args) is None
    monkeypatch.setattr(trace.MemoryTracer, "records",
                        lambda self, prefix="": recorded()["records"])
    assert reader.read(ctx, args) == pytest.approx(
        recorded()["expect"]["block_ff_slot_share_5"])


def test_the_manifest_lists_the_three_block_cells():
    with open(os.path.join(helpers.REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    assert manifest["per_layer"][-1]["name"] == NAME    # appended
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "program_span", "layer": "block feed-forward",
        "moves": "train_methods_per_s", "workloads": BLOCK_CELLS}
    cells = {w["name"]: w for w in manifest["workloads"]}
    assert all(cells[c]["chips"] == 1 for c in BLOCK_CELLS)


def test_traced_cell_prints_the_share(tmp_path_factory):
    """The tiny LFM2-MoE cell of `test_moe_carried_row_share.py`, the
    metric's list of cells given it: 16 rows x 12 contexts get no
    staircase (so few rows give the whole rectangle), every batch runs
    the full step and every position is fed forward."""
    root = carried.copy.__wrapped__(tmp_path_factory)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        manifest = json.load(f)
    (metric,) = [m for m in manifest["per_layer"] if m["name"] == NAME]
    assert "tiny-lfm2moe-1" in metric["workloads"]
    rc, result, err = helpers.run_cell(root, "tiny-lfm2moe-1", 1, trace=1,
                                       seconds=2)
    assert rc == 0, err[-3000:]
    share = result["metrics"][NAME]
    assert share["unit"] == "%"
    assert share["value"] == pytest.approx(100.0)
