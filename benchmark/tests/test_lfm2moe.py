"""Configuration `java-large-lfm2moe`'s benchmark files: the two counts
against numbers worked by hand, the reader of the `moe/route` record on a
recorded excerpt, and kind `train_corpus_ref` end to end at a tiny size on
a CPU device (timed, traced, and with a fault planted under it).

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""

import importlib.util
import json
import os
import types

import pytest

import bench_helpers as helpers

BENCH = os.path.dirname(helpers.TESTS)


def _load(directory, name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, directory, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# ---- counts ---------------------------------------------------------------

# H = 8, D = 4, heads 2 of 4 with 1 kv head, dense width 16, expert width
# 6, 4 routed experts, 3 taps; layers conv (dense), full_attention
# (experts), conv (experts); 2 sampled classes
SIZES = {"hidden_size": 8, "code_vector": 4, "num_attention_heads": 2,
         "num_key_value_heads": 1, "intermediate_size": 16,
         "moe_intermediate_size": 6, "num_routed_experts": 4,
         "num_experts": 2, "conv_L_cache": 3, "num_dense_layers": 1,
         "layer_types": ["conv", "full_attention", "conv"],
         "num_sampled": 2, "compute_dtype": "bfloat16"}
# one step, one method of 3 valid contexts, 5 rows routed to held experts
WINDOW = {"methods": 1, "contexts": 3, "contexts_sq": 9, "steps": 1,
          "routed_rows": 5}


def test_step_lfm2moe():
    # a position: in 2*4*8 = 64, pool 4*8 = 32; conv layers 2 x (8*64 +
    # 2*3*8 = 560); attention 4*64 + 4*8*4 = 384; dense MLP 6*8*16 = 768;
    # two routers 2 x 2*8*4 = 128: 64 + 32 + 1120 + 384 + 768 + 128 = 2496
    # pairs: 3*4/2 = 6, each 4*8 = 32: 192; rows: 5 x 6*8*6 = 1440
    # method: out 2*8*4 = 64, logits 2*4*3 = 24
    assert _load("counts", "step_lfm2moe").flops(SIZES, WINDOW) == 3 * (
        3 * 2496 + 192 + 1440 + 88)


def test_step_lfm2moe_wants_the_routed_rows():
    window = {k: v for k, v in WINDOW.items() if k != "routed_rows"}
    with pytest.raises(AssertionError, match="routed rows"):
        _load("counts", "step_lfm2moe").flops(SIZES, window)


def test_expert_mm():
    w = _load("counts", "expert_mm").work(SIZES, WINDOW)
    # forward 6*8*6 a row, x3, 5 rows
    assert w["flops"] == 18 * 8 * 6 * 5
    # a pass: 2 expert layers x 2 held experts x 3*8*6 weights = 576, and
    # 5 rows x 2*8 = 80; three passes, 2 bytes
    assert w["bytes"] == 3 * (576 + 80) * 2


# ---- the reader of moe/route ----------------------------------------------

def recorded():
    # (`recorded/*.json` are traces, and `test_trace_reduce.py` reads all)
    with open(os.path.join(helpers.TESTS, "recorded_records",
                           "moe_route_6steps.json")) as f:
        return json.load(f)


def test_reduce_on_the_recorded_excerpt():
    """Six steps of the tiny configuration on the CPU (4 held experts of 8
    routed, 2 a token, 2 expert layers), the values worked out here from
    the excerpt's own rows."""
    reduce = _load("readers", "moe_route").reduce
    excerpt = recorded()
    records = excerpt["records"]
    assert len(records) == 6
    last4 = [r["attrs"] for r in records[-4:]]
    rows = sum(sum(sum(layer) for layer in a["layers"]) for a in last4)
    assert rows == sum(a["rows_here"] for a in last4)
    choices = sum(a["valid_tokens"] for a in last4) * 2 * 2
    assert reduce(records, 4, "held_row_share", 2) == pytest.approx(
        100.0 * rows / choices)
    worst = [max(max(layer) / (sum(layer) / 4) for layer in a["layers"])
             for a in last4]
    assert reduce(records, 4, "imbalance", 2) == pytest.approx(
        sum(worst) / 4)
    assert reduce(records, 4, "imbalance", 2) >= 1.0
    # what the excerpt was recorded with
    assert reduce(records, 6, "held_row_share", 2) == pytest.approx(
        excerpt["expect"]["held_row_share"])
    assert reduce(records, 6, "imbalance", 2) == pytest.approx(
        excerpt["expect"]["imbalance"])


@pytest.mark.parametrize("records,steps", [
    ([], 3),                        # the parent: no such record
    (recorded()["records"], 7),     # a window longer than the record
    (recorded()["records"], 0)])
def test_reduce_gives_none_where_there_is_nothing_to_read(records, steps):
    reduce = _load("readers", "moe_route").reduce
    assert reduce(records, steps, "imbalance", 2) is None
    assert reduce(records, steps, "held_row_share", 2) is None


def test_read_takes_the_record_from_the_program(monkeypatch):
    from code2vec_tpu.obs import trace

    reader = _load("readers", "moe_route")
    ctx = types.SimpleNamespace(window={"steps": 6},
                                config={"num_experts_per_tok": 2})
    monkeypatch.setattr(trace, "_MEMORY_TRACER", trace.MemoryTracer())
    assert reader.read(ctx, {"value": "imbalance"}) is None
    monkeypatch.setattr(trace.MemoryTracer, "records",
                        lambda self, prefix="": recorded()["records"])
    assert reader.read(ctx, {"value": "held_row_share"}) == pytest.approx(
        recorded()["expect"]["held_row_share"])


# ---- the reader of the routing's row copies --------------------------------

def test_moe_row_gather_reads_the_pairs_rows_and_not_the_embeddings(
        monkeypatch):
    """A hand-made trace of one step on one device: the routing's gather
    of 2 methods x 3 slots x 2 choices = 12 rows of width 8 (40 us, twice),
    and the embedding's of 6 rows of width 4, which it passes over."""
    monkeypatch.syspath_prepend(BENCH)
    import trace_reduce as tr

    def ev(plane, line, name, start_us, dur_us):
        return {"plane": plane, "line": line, "name": name, "stats": {},
                "start_ns": start_us * 1000, "dur_ns": dur_us * 1000}

    def gather(i, rows, width, table):
        return (f"%fusion.{i} = bf16[{rows},{width}]{{1,0}} fusion("
                f"bf16[{table},{width}]{{1,0}} %x.{i}, s32[{rows}]{{0}} "
                f"%ids.{i}), kind=kCustom, calls=%fused_computation.{i}")

    d0, ops = "/device:TPU:0", "XLA Ops"
    trace = tr.from_events([
        ev("/host:CPU", "python3", "bench/window", 0, 1000),
        ev(d0, ops, gather(1, 12, 8, 6), 100, 40),
        ev(d0, ops, gather(2, 12, 8, 6), 300, 40),
        ev(d0, ops, gather(3, 6, 4, 50), 500, 70)])
    reader = _load("readers", "moe_row_gather")
    config = {"num_experts_per_tok": 2, "hidden_size": 8,
              "model": {"max_contexts": 3}}
    window = {"steps": 1, "batch": 2, "chips": 1}
    ctx = types.SimpleNamespace(window=window, config=config,
                                trace_data=trace)
    assert reader.read(ctx, {}) == pytest.approx(0.080)
    # another encoder's configuration, and a trace with no such gather
    ctx.config = {"model": {"max_contexts": 3}}
    assert reader.read(ctx, {}) is None
    ctx.config = dict(config, hidden_size=16)
    assert reader.read(ctx, {}) is None


# ---- kind train_corpus_ref on a CPU device ---------------------------------

# set as the real limits are: above what the program reads against the
# reference at this size on the CPU (bf16, a dozen seeds: loss 0.0012,
# grad 0.02, change 0.01, dense 0.11) and below what half the batch reads
TINY_LIMITS = {"loss1_gap": 4e-3, "grad_norm_gap": 0.06,
               "change_norm_gap": 0.05, "dense_grad_diff": 0.2}
TINY_BLOCK = dict(hidden_size=64, intermediate_size=96,
                  moe_intermediate_size=48, num_attention_heads=4,
                  num_key_value_heads=2,
                  layer_types=["conv", "full_attention", "conv"],
                  num_hidden_layers=3, num_experts=4, num_routed_experts=8,
                  first_expert=2, num_experts_per_tok=2)


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """The tests' copy with a tiny LFM2-MoE configuration, traffic mix and
    cell added as new files and entries, as a later PR would add them."""
    root = helpers.make_copy(str(tmp_path_factory.mktemp("bench") / "c"))
    bench = os.path.join(root, "benchmark")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    with open(os.path.join(bench, "configs",
                           "java-large-lfm2moe.json")) as f:
        config = json.load(f)
    config["name"] = "tiny-lfm2moe"
    config.update(TINY_BLOCK)
    config["model"].update(helpers.TINY_MODEL)
    config["train"].update(batch_per_chip=16, epochs=400, warmup_steps=100)
    config["flags"] = ["--sampled_softmax", "--num_sampled", "32",
                       "--max_contexts", "12", "--epochs", "400",
                       "--encoder", "lfm2_moe", "--lr_schedule",
                       "warmup_cosine", "--warmup_steps", "100"]
    config["reference"]["block"] = 8
    config["correct"]["limits"] = TINY_LIMITS
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
    for metric in manifest["per_layer"]:
        if "lfm2moe-train-corpus" in metric.get("workloads", []):
            metric["workloads"].append("tiny-lfm2moe-1")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root


def test_timed_run(copy):
    rc, result, err = helpers.run_cell(copy, "tiny-lfm2moe-1", 1,
                                       seed=2 ** 31 + 54321)
    assert rc == 0, err[-3000:]
    assert result["correct"] is True, err[-2000:]
    assert set(result["metrics"]) == {"train_methods_per_s", "setup_s"}
    assert set(result["compared"]) == set(TINY_LIMITS)
    window = result["facts"]["window"]
    assert window["compiles"] == 0
    # every valid token makes 2 choices in each of 2 expert layers, and 4
    # of 8 experts are held here
    assert 0 < window["routed_rows"] <= 4 * window["valid_tokens"]
    assert "lfm/layers/1/expert_bias" in result["facts"]["left_out_of_change"]


def test_traced_run_prints_the_new_metrics(copy):
    rc, result, err = helpers.run_cell(copy, "tiny-lfm2moe-1", 1, trace=1,
                                       seconds=2)
    assert rc == 0, err[-3000:]
    m = result["metrics"]
    window = result["facts"]["window"]
    assert m["moe_held_row_share"]["value"] == pytest.approx(
        100.0 * window["routed_rows"] / (4 * window["valid_tokens"]))
    assert m["moe_expert_imbalance"]["value"] >= 1.0
    assert m["moe_expert_imbalance"]["unit"] == "x"
    assert m["compiles_in_window"]["value"] == 0
    assert "infeed_transfer_ms" in m and "infeed_read_ms" in m
    # (an accepted test pins `gather_pad_slot_share`'s list of cells)
    assert "gather_pad_slot_share" not in m
    # shares of a peak are left out on a CPU, never reported as 0
    assert "expert_mm_roofline" not in m and "train_step_mfu" not in m
    assert result["correct"] is True


def test_half_the_batch_comes_out_not_correct(copy):
    rc, result, err = helpers.run_cell(copy, "tiny-lfm2moe-1", 1,
                                       fault="half_batch")
    assert rc == 0, err[-3000:]
    assert result["correct"] is False
    assert result["compared"]["grad_norm_gap"]["value"] > \
        result["compared"]["grad_norm_gap"]["limit"]


def test_the_reference_stores_its_tables_as_stated(monkeypatch):
    import jax
    import jax.numpy as jnp
    import numpy as np

    monkeypatch.syspath_prepend(BENCH)
    import reference_lfm2moe
    # a stored 1.0, 2^-13 and 0.00244 (bfloat16 units 2^-7, 2^-20, 2^-16)
    # after an update of 1e-6: only the small one moves, by a whole unit
    stored = np.float32([1.0, 2.0 ** -13, 0.00244140625])
    moved = jax.jit(reference_lfm2moe.stored_as("bfloat16"))(
        jnp.asarray(stored + np.float32(1e-6)))
    assert np.asarray(moved).tolist() == [1.0, 2.0 ** -13 + 2.0 ** -20,
                                          0.00244140625]
    assert np.all(np.asarray(moved) == np.asarray(
        moved.astype(jnp.bfloat16).astype(jnp.float32)))
    same = jax.jit(reference_lfm2moe.stored_as("float32"))(
        jnp.asarray(stored + np.float32(1e-6)))
    assert np.all(np.asarray(same) == stored + np.float32(1e-6))


def test_the_references_warm_up_is_the_programs(monkeypatch):
    import optax

    monkeypatch.syspath_prepend(BENCH)
    import reference_lfm2moe
    spec = {"lr": 1e-3, "lr_schedule": "warmup_cosine",
            "lr_warmup_steps": 2000, "lr_total_steps": 32000}
    program = optax.warmup_cosine_decay_schedule(0.0, 1e-3, 2000, 32000,
                                                 1e-4)
    for step in (0, 1, 2, 1999, 2000, 2001, 17000, 31999, 32000, 40000):
        assert reference_lfm2moe.learning_rate(step, spec) == pytest.approx(
            float(program(step)), rel=1e-4, abs=1e-12), step
    cosine = dict(spec, lr_schedule="cosine")
    assert reference_lfm2moe.learning_rate(5, cosine) == \
        reference_lfm2moe.base.learning_rate(5, cosine)


def test_the_configuration_file_keeps_the_catalog_rows_numbers():
    """Every number of LFM2-24B-A2B's config stands in the file under
    its key, or the key is listed in `reduced` with the published value
    beside it; no width is among them."""
    with open(os.path.join(BENCH, "configs", "java-large-lfm2moe.json")) as f:
        config = json.load(f)
    published = {"conv_L_cache": 3, "hidden_size": 2048,
                 "intermediate_size": 11776, "moe_intermediate_size": 1536,
                 "norm_eps": 1e-05, "num_attention_heads": 32,
                 "num_key_value_heads": 8, "num_experts_per_tok": 4,
                 "max_position_embeddings": 128000,
                 "routed_scaling_factor": 1, "num_dense_layers": 2,
                 "num_experts": 64, "num_hidden_layers": 40,
                 "vocab_size": 65536}
    for k, v in published.items():
        if k in config["reduced"]:
            assert config["published"][k] == v and config[k] != v
        else:
            assert config[k] == v, k
    assert config["rope_parameters"] == {"rope_theta": 1000000,
                                         "rope_type": "default"}
    assert set(config["reduced"]) == {"num_hidden_layers", "layer_types",
                                      "num_dense_layers", "num_experts",
                                      "vocab_size"}
    assert config["num_routed_experts"] == 64
    assert len(config["published"]["layer_types"]) == 40
    assert config["published"]["layer_types"][1:6] == config["layer_types"]
    with open(os.path.join(helpers.REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "java-large-lfm2moe")
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
