"""The reader of `moe_carried_row_share`: `reduce` on a recorded excerpt
of the program's `moe/route` spans (layers at the bound and layers at
the full length) against values worked out by hand, what it gives a
program whose spans carry no bound, and a tiny CPU cell run with
`--trace 1`.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""

import copy as copy_module
import importlib.util
import json
import os
import types

import pytest

import bench_helpers as helpers
import test_lfm2moe as lfm2moe


def _reader():
    spec = importlib.util.spec_from_file_location(
        "moe_carried_row_share",
        os.path.join(helpers.REPO, "benchmark", "readers",
                     "moe_carried_row_share.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def recorded():
    with open(os.path.join(helpers.TESTS, "recorded_records",
                           "moe_route_bound_6steps.json")) as f:
        return json.load(f)


def test_reduce_on_the_recorded_excerpt():
    """Six steps, two expert layers, 8 methods x 12 slots x 4 choices =
    384 pairs a layer and a bound of 128: on the steps of seq 3 and 4
    one layer overflowed and ran at 384."""
    reduce = _reader().reduce
    excerpt = recorded()
    records, pairs = excerpt["records"], excerpt["pairs"]
    assert pairs == 384
    assert [r["attrs"]["compact_layers"] for r in records] == [2, 2, 2, 1,
                                                               1, 2]
    assert {r["attrs"]["row_bound"] for r in records} == {128}
    # the overflowing layers are those whose rows are over the bound
    for r in records:
        over = sum(sum(layer) > 128 for layer in r["attrs"]["layers"])
        assert over == 2 - r["attrs"]["compact_layers"]
    assert reduce(records, 6, pairs) == pytest.approx(
        100.0 * (10 * 128 + 2 * 384) / (12 * 384))
    assert reduce(records, 6, pairs) == pytest.approx(
        excerpt["expect"]["carried_row_share"])
    # the last step alone: both layers at the bound, a third of the pairs
    assert reduce(records, 1, pairs) == pytest.approx(100.0 * 128 / 384)
    # the last three: two layers of six at the full length
    assert reduce(records, 3, pairs) == pytest.approx(
        100.0 * (4 * 128 + 2 * 384) / (6 * 384))


def test_reduce_under_a_mesh_takes_the_devices_sums():
    """Four devices of 96 pairs and a bound of 32 each: the span carries
    the sums, 128 and the (layer, device) pairs that ran at the bound."""
    span = {"name": "moe/route", "t0": 0.0, "t1": 0.0, "attrs": {
        "seq": 0, "layers": [[1, 2], [3, 4]], "rows_here": 10,
        "valid_tokens": 40, "row_bound": 128, "compact_layers": 7}}
    assert _reader().reduce([span], 1, 384, 4) == pytest.approx(
        100.0 * (7 * 32 + 1 * 96) / (8 * 96))
    # a program with one body only reports its pairs and no layer
    span["attrs"].update(row_bound=384, compact_layers=0)
    assert _reader().reduce([span], 1, 384, 4) == pytest.approx(100.0)


def _without(records, key):
    out = copy_module.deepcopy(records)
    for r in out:
        del r["attrs"][key]
    return out


@pytest.mark.parametrize("records,steps,pairs", [
    (_without(recorded()["records"], "row_bound"), 4, 384),   # the parent
    (_without(recorded()["records"], "compact_layers"), 4, 384),
    ([], 3, 384),                       # a program that routes nothing
    (recorded()["records"], 7, 384),    # a window longer than the record
    (recorded()["records"], 0, 384),
    (recorded()["records"], 4, 0)],
    ids=["no_bound", "no_compact_layers", "empty", "too_few_steps",
         "no_steps", "no_pairs"])
def test_reduce_gives_none_where_there_is_nothing_to_read(records, steps,
                                                          pairs):
    assert _reader().reduce(records, steps, pairs) is None


def test_read_takes_the_pairs_from_the_configuration(monkeypatch):
    from code2vec_tpu.obs import trace

    reader = _reader()
    config = {"num_experts_per_tok": 4, "model": {"max_contexts": 12}}
    ctx = types.SimpleNamespace(
        window={"steps": 6, "batch": 8, "chips": 1}, config=config)
    monkeypatch.setattr(trace, "_MEMORY_TRACER", trace.MemoryTracer())
    assert reader.read(ctx, {}) is None
    monkeypatch.setattr(trace.MemoryTracer, "records",
                        lambda self, prefix="": recorded()["records"])
    assert reader.read(ctx, {}) == pytest.approx(
        recorded()["expect"]["carried_row_share"])
    # another encoder's configuration routes nothing
    ctx.config = {"model": {"max_contexts": 12}}
    assert reader.read(ctx, {}) is None


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """The tests' copy with a tiny LFM2-MoE cell of 8 held experts of 64
    routed, 4 a token, and the metric's list of cells given that cell."""
    root = helpers.make_copy(str(tmp_path_factory.mktemp("bench") / "c"))
    bench = os.path.join(root, "benchmark")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    (metric,) = [m for m in manifest["per_layer"]
                 if m["name"] == "moe_carried_row_share"]
    assert metric == {
        "name": "moe_carried_row_share", "unit": "%", "better": "lower",
        "source": "program_span", "layer": "expert grouped product",
        "moves": "train_methods_per_s",
        "workloads": ["lfm2moe-train-corpus"]}
    with open(os.path.join(bench, "configs",
                           "java-large-lfm2moe.json")) as f:
        config = json.load(f)
    config["name"] = "tiny-lfm2moe"
    config.update(lfm2moe.TINY_BLOCK, num_experts=8, num_routed_experts=64,
                  first_expert=16, num_experts_per_tok=4)
    config["model"].update(helpers.TINY_MODEL)
    config["train"].update(batch_per_chip=16, epochs=400, warmup_steps=100)
    config["flags"] = ["--sampled_softmax", "--num_sampled", "32",
                       "--max_contexts", "12", "--epochs", "400",
                       "--encoder", "lfm2_moe", "--lr_schedule",
                       "warmup_cosine", "--warmup_steps", "100"]
    config["reference"]["block"] = 8
    rel = "benchmark/configs/tiny-lfm2moe.json"
    with open(os.path.join(root, rel), "w") as f:
        json.dump(config, f)
    with open(os.path.join(bench, "traffic", "corpus-train-ref.json")) as f:
        traffic = json.load(f)
    traffic.update(steps_per_epoch=4, trace_seconds=2,
                   name="corpus-tiny-ref")
    with open(os.path.join(bench, "traffic", "corpus-tiny-ref.json"),
              "w") as f:
        json.dump(traffic, f)
    manifest["configs"].append({"name": "tiny-lfm2moe", "source": "test",
                                "file": rel, "reduced": [], "why": "test"})
    manifest["workloads"].append({
        "name": "tiny-lfm2moe-1", "config": "tiny-lfm2moe",
        "traffic": "corpus-tiny-ref", "chips": 1, "why": "test"})
    for m in manifest["per_layer"]:
        if "lfm2moe-train-corpus" in m.get("workloads", []):
            m["workloads"].append("tiny-lfm2moe-1")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root


def test_traced_cell_prints_the_share(copy):
    """16 methods x 12 slots x 4 choices = 768 pairs a layer; 8 of 64
    held: twice the even share is 192 rows, 256 up to the tile, and a
    fresh router sends about an eighth of the valid choices here, so
    every layer of the window runs at the bound."""
    rc, result, err = helpers.run_cell(copy, "tiny-lfm2moe-1", 1, trace=1,
                                       seconds=2)
    assert rc == 0, err[-3000:]
    share = result["metrics"]["moe_carried_row_share"]
    assert share["unit"] == "%"
    assert share["value"] == pytest.approx(100.0 * 256 / 768)
    window = result["facts"]["window"]
    assert 0 < window["routed_rows"] <= 2 * 256 * window["steps"]
