"""Configuration `java-large-joyai`'s benchmark files: the two counts
against numbers worked by hand for a two-method window, the
configuration's file against the catalog row, every file the new entries
name found by `run.py`'s lookup, and kind `train_corpus_block` end to end
with this block at a tiny size on a CPU device (timed, traced, with a
fault planted under it, and with every reading): the kind's first block
that is not the one it was written beside.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""

import importlib.util
import json
import os

import pytest

import bench_helpers as helpers

BENCH = os.path.dirname(helpers.TESTS)
CELL = "joyai-train-corpus"
CONFIG = "java-large-joyai"


def _load(directory, name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, directory, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


# ---- counts ---------------------------------------------------------------

# H = 8, D = 4; 2 heads of 3 + 2 for the scores and 4 for the values; ranks
# 6 and 5; a dense layer of width 10, then two expert layers: 4 routed
# experts of width 6 (2 held), one shared expert; 2 sampled classes
SIZES = {"hidden_size": 8, "code_vector": 4, "num_attention_heads": 2,
         "q_lora_rank": 6, "kv_lora_rank": 5, "qk_nope_head_dim": 3,
         "qk_rope_head_dim": 2, "v_head_dim": 4, "intermediate_size": 10,
         "moe_intermediate_size": 6, "n_shared_experts": 1,
         "num_routed_experts": 4, "num_experts": 2, "num_dense_layers": 1,
         "layer_types": ["latent_attention"] * 3,
         "num_sampled": 2, "compute_dtype": "bfloat16"}
# one step, two methods of 3 and 1 valid contexts, 5 rows routed to held
# experts
WINDOW = {"methods": 2, "contexts": 4, "contexts_sq": 10, "steps": 1,
          "routed_rows": 5}


def test_mla_attention():
    w = _load("counts", "mla_attention").work(SIZES, WINDOW)
    # pairs: 3*4/2 + 1*2/2 = 7 = (10 + 4) / 2; a pair and head
    # 2*(5 + 4) = 18 forward, x3; 2 heads, 3 layers
    assert w["flops"] == 7 * 18 * 3 * 2 * 3
    # a slot and layer: q 2*5, k_nope 2*3, k_rope 2 once, v 2*4, o 2*4 =
    # 34 values; three passes, 3 layers, 4 slots, 2 bytes
    assert w["bytes"] == 3 * 34 * 3 * 4 * 2


def test_step_joyai():
    # a layer's five MLA products: 2*8*6 = 96, 2*6*2*5 = 120,
    # 2*8*(5 + 2) = 112, 2*5*2*(3 + 4) = 140, 2*2*4*8 = 128: 596
    # the dense layer's MLP 6*8*10 = 480; an expert layer's router
    # 2*8*4 = 64 and shared expert 6*8*6 = 288: 352
    # a position: in 2*4*8 = 64, pool 32, 3 x 596, 480, 2 x 352 = 3068
    # pairs: 7, each 2*(5 + 4)*2 = 36 a layer, 3 layers: 756
    # rows: 5 x 6*8*6 = 1440; a method: out 2*8*4 = 64, logits
    # 2*4*3 = 24
    assert _load("counts", "step_joyai").flops(SIZES, WINDOW) == 3 * (
        4 * 3068 + 756 + 1440 + 2 * 88)


def test_step_joyai_wants_the_routed_rows():
    window = {k: v for k, v in WINDOW.items() if k != "routed_rows"}
    with pytest.raises(AssertionError, match="routed rows"):
        _load("counts", "step_joyai").flops(SIZES, window)


def test_expert_mm_reads_what_the_kind_fills():
    """The accepted count over the sizes kind `train_corpus_block` hands
    it: one dense layer (the file's `num_dense_layers`), so two layers'
    held weights are counted."""
    w = _load("counts", "expert_mm").work(SIZES, WINDOW)
    assert w["flops"] == 18 * 8 * 6 * 5
    assert w["bytes"] == 3 * (2 * 2 * 3 * 8 * 6 + 5 * 2 * 8) * 2


# ---- the configuration's file ----------------------------------------------

def test_the_configuration_file_keeps_the_catalog_rows_numbers():
    """Every number of JoyAI-LLM-Flash's config stands in the file under
    its key, or the key is listed in `reduced` with the published value
    beside it; no width is among them."""
    config = _json(BENCH, "configs", CONFIG + ".json")
    published = {
        "ep_size": 1, "first_k_dense_replace": 1, "head_dim": 64,
        "hidden_size": 2048, "intermediate_size": 7168, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "moe_intermediate_size": 768,
        "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 256,
        "n_shared_experts": 1, "num_attention_heads": 32,
        "num_experts_per_tok": 8, "num_hidden_layers": 40,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 1,
        "q_lora_rank": 1536, "qk_head_dim": 192, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_theta": 32000000, "routed_scaling_factor": 2.5,
        "topk_group": 1, "v_head_dim": 128, "vocab_size": 129280}
    for k, v in published.items():
        if k in config["reduced"]:
            assert config["published"][k] == v and config[k] != v, k
        else:
            assert config[k] == v, k
    for k, v in {"attention_bias": False, "hidden_act": "silu",
                 "model_type": "joyai_llm_flash", "norm_topk_prob": True,
                 "rope_interleave": True, "rope_scaling": None,
                 "scoring_func": "sigmoid", "tie_word_embeddings": False,
                 "topk_method": "noaux_tc"}.items():
        assert config[k] == v, k
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "num_nextn_predict_layers", "vocab_size"]
    assert not [k for k in config["reduced"]
                if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]
    # the chip's share, under the source's key and the repo's names
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["num_experts"], config["num_routed_experts"],
            config["first_expert"], config["num_dense_layers"],
            config["num_nextn_predict_layers"]) == (5, 16, 16, 256, 0, 1, 0)
    for key in ("assumed", "departures", "deployment", "published"):
        assert config[key], key
    manifest = _json(helpers.REPO, "BENCHMARK.json")
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    # against the catalog itself, where this machine has it
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "JoyAI-LLM-Flash")
        assert row["source_url"] == config["source"]
        for k, v in row["config"].items():
            if k in config["reduced"]:
                assert config["published"][k] == v, k
            else:
                assert config[k] == v, k


def test_the_stated_keys_are_the_blocks_own():
    """`block.keys` are keys of the file, and the program's dims hold
    each under the same name; what the accepted counts and readers read
    is among them or at the file's top level."""
    from code2vec_tpu.models.joyai_flash_encoder import JoyaiDims

    config = _json(BENCH, "configs", CONFIG + ".json")
    dims = JoyaiDims.from_config(config)
    for k in config["block"]["keys"]:
        assert getattr(dims, k) == config[k], k
    assert dims.layer_types == ("latent_attention",) * 5
    assert {"hidden_size", "moe_intermediate_size", "num_experts"} <= set(
        config["block"]["keys"])              # counts/expert_mm.py
    assert config["num_dense_layers"] == 1    # model_sizes, expert_mm.py
    assert config["num_experts_per_tok"] == 8   # readers/moe_route.py
    assert config["block"]["choice_leaves"] == [
        "router", "w1", "w3", "w2", "shared_w1", "shared_w3", "shared_w2",
        "ff_norm"]


# ---- what run.py looks up by name ------------------------------------------

def test_every_file_the_new_entries_name_is_found():
    manifest = _json(helpers.REPO, "BENCHMARK.json")
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "corpus-train-block", 1)
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    config = _json(helpers.REPO, entry["file"])
    assert config["name"] == CONFIG
    traffic = _json(BENCH, "traffic", cell["traffic"] + ".json")
    assert traffic["kind"] == "train_corpus_block"
    for directory, name in (("kinds", traffic["kind"]),
                            ("counts", config["counts"]),
                            ("", config["reference"]["module"])):
        assert os.path.isfile(os.path.join(BENCH, directory, name + ".py"))
    mine = [m for m in manifest["per_layer"]
            if CELL in m.get("workloads", [])]
    assert {m["name"] for m in mine} == {
        "infeed_read_ms", "infeed_transfer_ms",
        "infeed_producer_busy_share", "infeed_bytes_per_step",
        "expert_mm_roofline", "moe_expert_imbalance", "moe_held_row_share",
        "mla_attention_roofline"}
    # and none that an accepted test pins or whose reader would read
    # another layer here (or nothing)
    for name in ("gather_pad_slot_share", "gather_slot_share",
                 "moe_carried_row_share", "gather_ms", "scatter_ms",
                 "moe_row_gather_ms", "gdn_scan_roofline",
                 "gdn_live_chunk_share"):
        other = next(x for x in manifest["per_layer"] if x["name"] == name)
        assert CELL not in other["workloads"]
    for m in manifest["per_layer"]:
        if "workloads" in m and CELL not in m["workloads"]:
            continue
        spec = _json(BENCH, "layer_metrics", m["name"] + ".json")
        assert (spec["name"], spec["unit"]) == (m["name"], m["unit"])
        assert os.path.isfile(os.path.join(BENCH, "readers",
                                           spec["reader"] + ".py"))
        if "counts" in spec.get("args", {}):
            assert os.path.isfile(os.path.join(
                BENCH, "counts", spec["args"]["counts"] + ".py"))
    m = next(x for x in manifest["per_layer"]
             if x["name"] == "mla_attention_roofline")
    assert m == {"name": "mla_attention_roofline", "unit": "%",
                 "better": "higher", "source": "device_trace",
                 "layer": "latent attention",
                 "moves": "train_methods_per_s", "workloads": [CELL]}
    spec = _json(BENCH, "layer_metrics", "mla_attention_roofline.json")
    assert (spec["reader"], spec["args"]["counts"], spec["layer"]) == \
        ("kernel_roofline", "mla_attention", "latent attention")


def test_the_accepted_entries_stand_as_they_were():
    """New entries only: the entries the benchmark had before this cell
    stand first and in their order, and the end-to-end metrics and
    `run_seconds` are what they were."""
    manifest = _json(helpers.REPO, "BENCHMARK.json")
    assert manifest["run_seconds"] == 10
    assert [c["name"] for c in manifest["configs"]][:5] == [
        "java-large-bag", "java-large-xf2", "java-large-lfm2moe",
        "java-large-qwen3next", CONFIG]
    assert [w["name"] for w in manifest["workloads"]][:6] == [
        "bag-train-corpus", "xf2-train-corpus", "bag-train-corpus-x4",
        "lfm2moe-train-corpus", "qwen3next-train-corpus", CELL]
    assert [(m["name"], m["bound"]) for m in manifest["end_to_end"]] == [
        ("train_methods_per_s", 0.01), ("setup_s", 0.1)]


# ---- kind train_corpus_block on a CPU device --------------------------------

# float32 at the tiny size, as the qwen3next tests have it and for their
# reason; set as the real limits are: above what the program reads at this
# size on the CPU and below what half the batch reads
TINY_LIMITS = {"loss1_gap": 1e-3, "grad_norm_gap": 0.02,
               "change_norm_gap": 0.05, "dense_grad_diff": 0.05,
               "choice_norm_gap": 0.02, "choice_grad_diff": 0.05}
TINY_BLOCK = dict(num_hidden_layers=3, hidden_size=64, num_attention_heads=4,
                  num_key_value_heads=4, head_dim=16, q_lora_rank=24,
                  kv_lora_rank=16, qk_head_dim=12, qk_nope_head_dim=8,
                  qk_rope_head_dim=4, v_head_dim=6, intermediate_size=96,
                  moe_intermediate_size=24, n_routed_experts=4,
                  num_experts=4, num_routed_experts=16, first_expert=4,
                  num_experts_per_tok=3)


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """The tests' copy with a tiny JoyAI configuration, traffic mix and
    cell added as new files and entries, as a later PR would add them."""
    root = helpers.make_copy(str(tmp_path_factory.mktemp("bench") / "c"))
    bench = os.path.join(root, "benchmark")
    manifest = _json(root, "BENCHMARK.json")
    config = _json(bench, "configs", CONFIG + ".json")
    config["name"] = "tiny-joyai"
    config.update(TINY_BLOCK)
    config["model"].update(helpers.TINY_MODEL, compute_dtype="float32")
    config["train"].update(batch_per_chip=16, epochs=400, warmup_steps=100)
    config["flags"] = ["--sampled_softmax", "--num_sampled", "32",
                       "--max_contexts", "12", "--epochs", "400",
                       "--encoder", "joyai_flash", "--lr_schedule",
                       "warmup_cosine", "--warmup_steps", "100",
                       "--no_bf16"]
    config["reference"]["block"] = 8
    config["correct"]["limits"] = TINY_LIMITS
    rel = "benchmark/configs/tiny-joyai.json"
    with open(os.path.join(root, rel), "w") as f:
        json.dump(config, f)
    traffic = _json(bench, "traffic", "corpus-train-block.json")
    traffic.update(steps_per_epoch=4, trace_seconds=2,
                   name="corpus-tiny-block")
    with open(os.path.join(bench, "traffic", "corpus-tiny-block.json"),
              "w") as f:
        json.dump(traffic, f)
    manifest["configs"].append({"name": "tiny-joyai", "source": "test",
                                "file": rel, "reduced": [], "why": "test"})
    manifest["workloads"].append({
        "name": "tiny-joyai-1", "config": "tiny-joyai",
        "traffic": "corpus-tiny-block", "chips": 1, "why": "test"})
    for metric in manifest["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append("tiny-joyai-1")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root


def test_timed_run(copy):
    rc, result, err = helpers.run_cell(copy, "tiny-joyai-1", 1,
                                       seed=2 ** 31 + 54321)
    assert rc == 0, err[-3000:]
    assert result["correct"] is True, err[-2000:]
    assert set(result["metrics"]) == {"train_methods_per_s", "setup_s"}
    assert set(result["compared"]) == set(TINY_LIMITS)
    window = result["facts"]["window"]
    assert window["compiles"] == 0
    # every valid token makes 3 choices in each of 2 expert layers, and 4
    # of 16 experts are held here; the block scans nothing
    assert 0 < window["routed_rows"] <= 6 * window["valid_tokens"]
    assert "scanned_chunks" not in window
    route = result["facts"]["route_by_step"]
    assert len(route["rows_here"]) == window["steps"]


def test_traced_run_prints_the_metrics_the_cell_lists(copy):
    rc, result, err = helpers.run_cell(copy, "tiny-joyai-1", 1, trace=1,
                                       seconds=2)
    assert rc == 0, err[-3000:]
    m = result["metrics"]
    window = result["facts"]["window"]
    assert m["moe_held_row_share"]["value"] == pytest.approx(
        100.0 * window["routed_rows"] / (6 * window["valid_tokens"]))
    assert m["moe_expert_imbalance"]["value"] >= 1.0
    assert m["compiles_in_window"]["value"] == 0
    assert "infeed_transfer_ms" in m and "infeed_read_ms" in m
    for name in ("gather_pad_slot_share", "gather_slot_share",
                 "moe_carried_row_share", "gdn_live_chunk_share",
                 "scatter_ms", "gather_ms"):
        assert name not in m
    # shares of a peak are left out on a CPU, never reported as 0
    for name in ("mla_attention_roofline", "expert_mm_roofline",
                 "train_step_mfu"):
        assert name not in m
    assert result["correct"] is True


def test_half_the_batch_comes_out_not_correct(copy):
    rc, result, err = helpers.run_cell(copy, "tiny-joyai-1", 1,
                                       fault="half_batch")
    assert rc == 0, err[-3000:]
    assert result["correct"] is False


def test_readings_all_reads_every_planted_fault(copy):
    """In float32 at this size every planted fault but the left-out expert
    comes out not correct by a compared number, and the left-out expert
    reads about 1 on `expert_norm_gap`, where the sound run reads
    rounding."""
    rc, result, err = helpers.run_cell(copy, "tiny-joyai-1", 1,
                                       extra=("--readings", "all"))
    assert rc == 0, err[-3000:]
    facts = result["facts"]
    assert result["correct"] is True
    sound = dict(facts["not_compared"],
                 **{k: v["value"] for k, v in result["compared"].items()})
    assert sound["expert_norm_gap"] < 1e-3
    readings = facts["readings"]
    assert set(readings) == {
        "control_fp8", "fault_half_batch", "fault_expert_left_out",
        "fault_shared_left_out", "fault_scale_one", "fault_no_kv_norm",
        "fault_k_rope_unturned", "fault_no_causal_mask",
        "fault_state_unchanged", "fault_tables_unchanged"}
    assert readings["fault_expert_left_out"]["numbers"][
        "expert_norm_gap"] > 0.5
    for name, reading in readings.items():
        assert reading["correct"] is False, name


def test_the_kind_refuses_another_size_than_the_file_states(copy):
    path = os.path.join(copy, "benchmark", "configs", "tiny-joyai.json")
    config = _json(path)
    with open(path, "w") as f:
        json.dump(dict(config, max_position_embeddings=64,
                       block=dict(config["block"], keys=config["block"][
                           "keys"] + ["max_position_embeddings"])), f)
    try:
        rc, result, err = helpers.run_cell(copy, "tiny-joyai-1", 1)
    finally:
        with open(path, "w") as f:
            json.dump(config, f)
    assert rc != 0 and result is None
    assert "max_position_embeddings" in err
