"""Configuration `java-large-qwen3next`'s benchmark files: the two counts
against numbers worked by hand, the reader of the `gdn/scan` record on a
recorded record, the configuration's file against the catalog row, every
file the new entries name found by `run.py`'s lookup, and kind
`train_corpus_block` end to end at a tiny size on a CPU device (timed,
traced, and with a fault planted under it).

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""

import importlib.util
import json
import os
import types

import pytest

import bench_helpers as helpers

BENCH = os.path.dirname(helpers.TESTS)
CELL = "qwen3next-train-corpus"
CONFIG = "java-large-qwen3next"


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

# H = 8, D = 4; 2 heads of 4 with 1 kv head; 1 key head and 2 value heads
# of 2 and 3 (K = 2, V = 6), 4 taps; 4 routed experts of width 6, a shared
# expert of width 5; layers linear, linear, full; 2 sampled classes
SIZES = {"hidden_size": 8, "code_vector": 4, "num_attention_heads": 2,
         "head_dim": 4, "num_key_value_heads": 1,
         "linear_num_key_heads": 1, "linear_num_value_heads": 2,
         "linear_key_head_dim": 2, "linear_value_head_dim": 3,
         "linear_conv_kernel_dim": 4, "num_routed_experts": 4,
         "num_experts": 2, "moe_intermediate_size": 6,
         "shared_expert_intermediate_size": 5, "num_dense_layers": 0,
         "layer_types": ["linear_attention", "linear_attention",
                         "full_attention"],
         "num_sampled": 2, "compute_dtype": "bfloat16"}
# one step, one method of 3 valid contexts, 5 rows routed to held experts
WINDOW = {"methods": 1, "contexts": 3, "contexts_sq": 9, "steps": 1,
          "routed_rows": 5}


def test_gdn_scan():
    w = _load("counts", "gdn_scan").work(SIZES, WINDOW)
    # 3 slots x 2 linear layers x 2 value heads x 6*2*3 forward, x3
    assert w["flops"] == 3 * 2 * 2 * 36 * 3
    # a slot and layer: q, k 2 each, v and o 6 each, g and beta 2 each =
    # 20 values; three passes, 2 bytes
    assert w["bytes"] == 3 * 20 * (3 * 2) * 2


def test_step_qwen3next():
    # every layer: router 2*8*4 = 64, shared 6*8*5 = 240, its gate 16: 320
    # a linear layer: projections 2*8*(4 + 12) = 256 and 4*8*2 = 64, conv
    # 2*4*(4 + 6) = 80, rule 6*2*3*2 = 72, out 2*6*8 = 96: 568
    # the attention layer: q with gate 4*8*8 = 256, k and v 4*8*4 = 128,
    # o 2*8*8 = 128: 512
    # a position: in 2*4*8 = 64, pool 32, 3 x 320, 2 x 568, 512 = 2704
    # pairs: 3*4/2 = 6, each 4*2*4 = 32: 192; rows: 5 x 6*8*6 = 1440
    # method: out 2*8*4 = 64, logits 2*4*3 = 24
    assert _load("counts", "step_qwen3next").flops(SIZES, WINDOW) == 3 * (
        3 * 2704 + 192 + 1440 + 88)


def test_step_qwen3next_wants_the_routed_rows():
    window = {k: v for k, v in WINDOW.items() if k != "routed_rows"}
    with pytest.raises(AssertionError, match="routed rows"):
        _load("counts", "step_qwen3next").flops(SIZES, window)


def test_expert_mm_reads_what_the_kind_fills():
    """The accepted count over the sizes kind `train_corpus_block` hands
    it: no dense layer, so all three layers' held weights are counted."""
    w = _load("counts", "expert_mm").work(SIZES, WINDOW)
    assert w["flops"] == 18 * 8 * 6 * 5
    assert w["bytes"] == 3 * (3 * 2 * 3 * 8 * 6 + 5 * 2 * 8) * 2


# ---- the reader of gdn/scan ------------------------------------------------

RECORDS = [{"name": "gdn/scan", "t0": float(i), "t1": i + 0.5,
            "attrs": {"seq": i, "chunk": 64, "chunks": 1536, "live_chunks": n}}
           for i, n in enumerate([700, 690, 710, 680])]


def test_reduce_on_a_recorded_record():
    reduce = _load("readers", "gdn_scan").reduce
    assert reduce(RECORDS, 4) == pytest.approx(100.0 * 2780 / 6144)
    assert reduce(RECORDS, 2) == pytest.approx(100.0 * 1390 / 3072)


@pytest.mark.parametrize("records,steps", [
    ([], 3),                        # the parent: no such record
    (RECORDS, 5),                   # a window longer than the record
    (RECORDS, 0)])
def test_reduce_gives_none_where_there_is_nothing_to_read(records, steps):
    assert _load("readers", "gdn_scan").reduce(records, steps) is None


def test_read_takes_the_record_from_the_program(monkeypatch):
    from code2vec_tpu.obs import trace

    reader = _load("readers", "gdn_scan")
    ctx = types.SimpleNamespace(window={"steps": 4})
    monkeypatch.setattr(trace, "_MEMORY_TRACER", trace.MemoryTracer())
    assert reader.read(ctx, {}) is None
    monkeypatch.setattr(trace.MemoryTracer, "records",
                        lambda self, prefix="": RECORDS)
    assert reader.read(ctx, {}) == pytest.approx(100.0 * 2780 / 6144)


# ---- the configuration's file ----------------------------------------------

def test_the_configuration_file_keeps_the_catalog_rows_numbers():
    """Every number of Qwen3-Next-80B-A3B-Instruct's config stands in the
    file under its key, or the key is listed in `reduced` with the
    published value beside it; no width is among them."""
    config = _json(BENCH, "configs", CONFIG + ".json")
    published = {
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_size": 2048, "intermediate_size": 5120,
        "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
        "linear_num_key_heads": 16, "linear_num_value_heads": 32,
        "linear_value_head_dim": 128, "max_position_embeddings": 262144,
        "moe_intermediate_size": 512, "num_attention_heads": 16,
        "num_experts": 512, "num_experts_per_tok": 10,
        "num_hidden_layers": 48, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
        "rope_theta": 10000000, "shared_expert_intermediate_size": 512,
        "vocab_size": 151936}
    for k, v in published.items():
        if k in config["reduced"]:
            assert config["published"][k] == v and config[k] != v, k
        else:
            assert config[k] == v, k
    for k, v in {"hidden_act": "silu", "mlp_only_layers": [],
                 "model_type": "qwen3_next", "norm_topk_prob": True,
                 "rope_scaling": None, "tie_word_embeddings": False,
                 "use_sliding_window": False}.items():
        assert config[k] == v, k
    assert set(config["reduced"]) == {"num_hidden_layers", "num_experts",
                                      "vocab_size"}
    assert not [k for k in config["reduced"]
                if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]
    assert (config["num_hidden_layers"], config["num_experts"],
            config["num_routed_experts"], config["first_expert"]) == \
        (4, 32, 512, 0)
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
                       if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
        assert row["source_url"] == config["source"]
        for k, v in row["config"].items():
            if k in config["reduced"]:
                assert config["published"][k] == v, k
            else:
                assert config[k] == v, k


def test_the_stated_keys_are_the_blocks_own():
    """`block.keys` are keys of the file, and the program's dims hold
    each under the same name."""
    from code2vec_tpu.models.qwen3_next_encoder import Qwen3NextDims

    config = _json(BENCH, "configs", CONFIG + ".json")
    dims = Qwen3NextDims.from_config(config)
    for k in config["block"]["keys"]:
        assert getattr(dims, k) == config[k], k
    assert dims.layer_types == ("linear_attention",) * 3 + (
        "full_attention",)
    assert config["num_experts_per_tok"] == 10      # read by two readers


# ---- what run.py looks up by name ------------------------------------------

def test_every_file_the_new_entries_name_is_found():
    """What `test_run.py` holds the accepted entries to, for this PR's:
    the cell's configuration and traffic, the traffic's kind, the
    configuration's reference and counts, each metric's file, reader and
    counts."""
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
    assert traffic["name"] == cell["traffic"]
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
        "gdn_scan_roofline", "gdn_live_chunk_share"}
    # accepted tests pin these metrics' lists of cells
    # (test_pad_slot_share.py, test_gather_slot_share.py,
    # test_moe_carried_row_share.py): the next `benchmark` issue appends
    # and `scatter_ms` names the tables' layer, where four fifths of
    # this cell's scatter time are the routed experts' sum back (REVIEW
    # 32): off its list until a `benchmark` PR reads scopes
    for name in ("gather_pad_slot_share", "gather_slot_share",
                 "moe_carried_row_share", "scatter_ms"):
        pinned = next(x for x in manifest["per_layer"] if x["name"] == name)
        assert CELL not in pinned["workloads"]
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
    for name in ("gdn_scan_roofline", "gdn_live_chunk_share"):
        m = next(x for x in manifest["per_layer"] if x["name"] == name)
        assert m["workloads"] == [CELL] and m["layer"] == "gated delta rule"
        assert m["moves"] == "train_methods_per_s"
    # the traffic's parameters are corpus-train-ref's, number for number
    accepted = _json(BENCH, "traffic", "corpus-train-ref.json")
    for k in accepted:
        if k not in ("name", "kind", "what", "sources"):
            assert traffic[k] == accepted[k], k


def test_an_unknown_name_in_the_new_cell_says_which_file(tmp_path):
    root = helpers.make_copy(str(tmp_path / "c"))
    manifest = _json(root, "BENCHMARK.json")
    manifest["workloads"].append({"name": "lost", "config": CONFIG,
                                  "traffic": "no-such-mix", "chips": 1,
                                  "why": "test"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    rc, result, err = helpers.run_cell(root, "lost", 1)
    assert rc != 0 and result is None
    assert "benchmark/traffic/no-such-mix.json" in err


# ---- kind train_corpus_block on a CPU device --------------------------------

# the tiny configuration computes in float32 (`--no_bf16`): at a hidden
# size of 64 a bfloat16 step of this block stands its own gradient's size
# from the reference (tests/test_qwen3_next.py says why), which leaves no
# room between a sound run and a fault. Set as the real limits are: above
# what the program reads at this size on the CPU and below what half the
# batch reads
TINY_LIMITS = {"loss1_gap": 1e-3, "grad_norm_gap": 0.02,
               "change_norm_gap": 0.05, "dense_grad_diff": 0.05,
               "choice_norm_gap": 0.02, "choice_grad_diff": 0.05}
TINY_BLOCK = dict(num_hidden_layers=4, hidden_size=64, num_attention_heads=4,
                  num_key_value_heads=2, head_dim=16,
                  linear_key_head_dim=8, linear_value_head_dim=8,
                  linear_num_key_heads=2, linear_num_value_heads=4,
                  num_experts=4, num_routed_experts=16, first_expert=4,
                  num_experts_per_tok=3, moe_intermediate_size=24,
                  shared_expert_intermediate_size=24)


def test_expert_norm_gap_reads_a_missing_expert_as_about_one():
    """Hand arithmetic: a layer of four experts whose slices of w1 and w2
    together have norms 5 (3 and 4), 5, 5 and 10 in the reference. Sound
    (the program's slices 1% up): 0.01. The last expert left out of the
    reference (0 there, the program's 10.1 against the median 5): 2.02;
    the first left out: 1.01. A leaf that stacks no experts is not
    read."""
    import sys

    import numpy as np

    sys.path.insert(0, BENCH)
    kind = _load("kinds", "train_corpus_block")
    scale = np.array([1, 1, 1, 2], np.float32)[:, None, None]
    w1 = np.zeros((4, 2, 2), np.float32)
    w1[:, 0, 0] = 3
    w2 = np.zeros((4, 2, 2), np.float32)
    w2[:, 1, 1] = 4
    ref = {"blk/layers/0/w1": w1 * scale, "blk/layers/0/w2": w2 * scale,
           "blk/layers/0/router": w1[0]}
    got = {k: 1.01 * v for k, v in ref.items()}
    got["blk/layers/0/router"] = 5 * w1[0]
    names = ["w1", "w3", "w2"]
    number, where = kind.expert_norm_gap(got, ref, names)
    assert number == pytest.approx(0.01, rel=1e-4)

    def without(e):
        keep = np.ones(4, np.float32)
        keep[e] = 0
        return {k: v * keep[:, None, None] if v.ndim == 3 else v
                for k, v in ref.items()}

    number, where = kind.expert_norm_gap(got, without(3), names)
    assert number == pytest.approx(2.02) and where == "blk/layers/0[3]"
    number, where = kind.expert_norm_gap(got, without(0), names)
    assert number == pytest.approx(1.01) and where == "blk/layers/0[0]"
    assert kind.expert_norm_gap(got, ref, []) == (0.0, None)
    config = _json(BENCH, "configs", CONFIG + ".json")
    assert config["block"]["expert_leaves"] == names


def _compared_state(scale: dict):
    """A reference of five leaves (one a table, with no dense gradient)
    and a program whose leaf `k` stands `scale[k]` times the
    reference's."""
    import numpy as np

    rng = np.random.default_rng(0)
    dense = {"blk/in_proj": rng.normal(size=(4, 6)),
             "blk/layers/0/in_qkvz": 2 * rng.normal(size=(6, 8)),
             "blk/layers/0/router": 0.5 * rng.normal(size=(6, 4)),
             "blk/layers/0/w1": rng.normal(size=(2, 6, 3))}
    dense = {k: v.astype(np.float32) for k, v in dense.items()}
    norms = {k: float(np.linalg.norm(v)) for k, v in dense.items()}
    norms["token_emb"] = 3.0
    ref = {"losses": [1.0], "grad_norms": norms, "dense_grads": dense,
           "change_norms": {k: 0.1 * v for k, v in norms.items()}}
    got = {"losses": [1.0],
           "grad_norms": {k: scale.get(k, 1.0) * v
                          for k, v in norms.items()},
           "change_norms": dict(ref["change_norms"]),
           "dense_grads": {k: np.float32(scale.get(k, 1.0)) * v
                           for k, v in dense.items()}}
    return got, ref


def test_the_leaves_of_the_choice_are_compared_apart():
    """A program 30% up on an experts' stack and 4% up on a mixer's
    leaf: with no `choice_leaves` both stand under one number, as
    `train_corpus.compare` has it; with the stack named, each kind of
    leaf reads its own, and leaf by leaf every number is there."""
    import sys

    sys.path.insert(0, BENCH)
    kind = _load("kinds", "train_corpus_block")
    got, ref = _compared_state({"blk/layers/0/w1": 1.3,
                                "blk/layers/0/in_qkvz": 1.04})
    whole = kind.compare(got, ref, {"block": {}})
    assert whole["numbers"] == kind.base.compare(got, ref)["numbers"]
    assert whole["numbers"]["grad_norm_gap"] == pytest.approx(0.3, rel=1e-5)
    assert whole["worst_leaf"]["grad_norm_gap"] == "blk/layers/0/w1"
    for measure in ("grad_norm_gap", "dense_grad_diff", "change_norm_gap"):
        assert whole["numbers"][measure] == pytest.approx(max(
            leaf.get(measure, 0.0) for leaf in whole["by_leaf"].values()))
    assert "dense_grad_diff" not in whole["by_leaf"]["token_emb"]

    apart = kind.compare(got, ref, {"block": {
        "choice_leaves": ["router", "w1"]}})
    numbers, worst = apart["numbers"], apart["worst_leaf"]
    assert numbers["grad_norm_gap"] == pytest.approx(0.04, rel=1e-5)
    assert numbers["dense_grad_diff"] == pytest.approx(0.04, rel=1e-5)
    assert worst["grad_norm_gap"] == "blk/layers/0/in_qkvz"
    assert numbers["choice_norm_gap"] == pytest.approx(0.3, rel=1e-5)
    assert numbers["choice_grad_diff"] == pytest.approx(0.3, rel=1e-5)
    assert worst["choice_grad_diff"] == "blk/layers/0/w1"
    assert numbers["change_norm_gap"] == whole["numbers"]["change_norm_gap"]
    verdict = kind.base.judge(numbers, {"grad_norm_gap": 0.1,
                                        "choice_norm_gap": 0.2})
    assert verdict["over"] == ["choice_norm_gap"]
    config = _json(BENCH, "configs", CONFIG + ".json")
    assert set(config["block"]["expert_leaves"]) < set(
        config["block"]["choice_leaves"])
    assert set(kind.CHOICE_NUMBERS.values()) < set(
        config["correct"]["limits"])


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """The tests' copy with a tiny Qwen3-Next configuration, traffic mix
    and cell added as new files and entries, as a later PR would add
    them."""
    root = helpers.make_copy(str(tmp_path_factory.mktemp("bench") / "c"))
    bench = os.path.join(root, "benchmark")
    manifest = _json(root, "BENCHMARK.json")
    config = _json(bench, "configs", CONFIG + ".json")
    config["name"] = "tiny-qwen3next"
    config.update(TINY_BLOCK)
    config["model"].update(helpers.TINY_MODEL, compute_dtype="float32")
    config["train"].update(batch_per_chip=16, epochs=400, warmup_steps=100)
    config["flags"] = ["--sampled_softmax", "--num_sampled", "32",
                       "--max_contexts", "12", "--epochs", "400",
                       "--encoder", "qwen3_next", "--lr_schedule",
                       "warmup_cosine", "--warmup_steps", "100",
                       "--no_bf16"]
    config["reference"]["block"] = 8
    config["correct"]["limits"] = TINY_LIMITS
    rel = "benchmark/configs/tiny-qwen3next.json"
    with open(os.path.join(root, rel), "w") as f:
        json.dump(config, f)
    traffic = _json(bench, "traffic", "corpus-train-block.json")
    traffic.update(steps_per_epoch=4, trace_seconds=2,
                   name="corpus-tiny-block")
    with open(os.path.join(bench, "traffic", "corpus-tiny-block.json"),
              "w") as f:
        json.dump(traffic, f)
    manifest["configs"].append({"name": "tiny-qwen3next", "source": "test",
                                "file": rel, "reduced": [], "why": "test"})
    manifest["workloads"].append({
        "name": "tiny-qwen3next-1", "config": "tiny-qwen3next",
        "traffic": "corpus-tiny-block", "chips": 1, "why": "test"})
    for metric in manifest["per_layer"]:
        if CELL in metric.get("workloads", []):
            metric["workloads"].append("tiny-qwen3next-1")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root


def test_timed_run(copy):
    rc, result, err = helpers.run_cell(copy, "tiny-qwen3next-1", 1,
                                       seed=2 ** 31 + 54321)
    assert rc == 0, err[-3000:]
    assert result["correct"] is True, err[-2000:]
    assert set(result["metrics"]) == {"train_methods_per_s", "setup_s"}
    assert set(result["compared"]) == set(TINY_LIMITS)
    window = result["facts"]["window"]
    assert window["compiles"] == 0
    # every valid token makes 3 choices in each of 4 expert layers, and 4
    # of 16 experts are held here
    assert 0 < window["routed_rows"] <= 12 * window["valid_tokens"]
    # 16 methods x 1 chunk of 12 slots x 3 linear layers a step
    assert window["scan_chunk"] == 12
    assert window["scanned_chunks"] == 48 * window["steps"]
    assert 0 < window["live_chunks"] <= window["scanned_chunks"]


def test_traced_run_prints_the_new_metrics(copy):
    rc, result, err = helpers.run_cell(copy, "tiny-qwen3next-1", 1, trace=1,
                                       seconds=2)
    assert rc == 0, err[-3000:]
    m = result["metrics"]
    window = result["facts"]["window"]
    assert m["gdn_live_chunk_share"]["value"] == pytest.approx(
        100.0 * window["live_chunks"] / window["scanned_chunks"])
    assert m["gdn_live_chunk_share"]["unit"] == "%"
    assert m["moe_held_row_share"]["value"] == pytest.approx(
        100.0 * window["routed_rows"] / (12 * window["valid_tokens"]))
    assert m["moe_expert_imbalance"]["value"] >= 1.0
    assert m["compiles_in_window"]["value"] == 0
    assert "infeed_transfer_ms" in m and "infeed_read_ms" in m
    # (accepted tests pin these three metrics' lists of cells)
    for name in ("gather_pad_slot_share", "gather_slot_share",
                 "moe_carried_row_share"):
        assert name not in m
    # shares of a peak are left out on a CPU, never reported as 0
    for name in ("gdn_scan_roofline", "expert_mm_roofline",
                 "train_step_mfu"):
        assert name not in m
    assert result["correct"] is True


def test_half_the_batch_comes_out_not_correct(copy):
    rc, result, err = helpers.run_cell(copy, "tiny-qwen3next-1", 1,
                                       fault="half_batch")
    assert rc == 0, err[-3000:]
    assert result["correct"] is False


def test_readings_all_sees_the_left_out_expert_by_its_own_number(copy):
    """In float32 the sound run's `expert_norm_gap` is rounding and the
    reference without its last held expert reads about 1 there, whatever
    the other numbers say of it."""
    rc, result, err = helpers.run_cell(copy, "tiny-qwen3next-1", 1,
                                       extra=("--readings", "all"))
    assert rc == 0, err[-3000:]
    facts = result["facts"]
    sound = dict(facts["not_compared"],
                 **{k: v["value"] for k, v in result["compared"].items()})
    assert sound["expert_norm_gap"] < 1e-3
    assert "/layers/" in facts["worst_leaf"]["expert_norm_gap"]
    readings = facts["readings"]
    assert readings["fault_expert_left_out"]["numbers"][
        "expert_norm_gap"] > 0.5
    assert set(readings) >= {"control_fp8", "fault_half_batch",
                             "fault_shared_left_out", "fault_no_decay",
                             "fault_state_unchanged",
                             "fault_tables_unchanged"}
    assert "fault_state_in_bf16" not in readings


def test_the_kind_refuses_another_size_than_the_file_states(copy):
    """The program builds what the file's keys say; a stated key that the
    program's dims do not hold under that value stops the run."""
    path = os.path.join(copy, "benchmark", "configs", "tiny-qwen3next.json")
    config = _json(path)
    with open(path, "w") as f:
        json.dump(dict(config, intermediate_size=64,
                       block=dict(config["block"], keys=config["block"][
                           "keys"] + ["intermediate_size"])), f)
    try:
        rc, result, err = helpers.run_cell(copy, "tiny-qwen3next-1", 1)
    finally:
        with open(path, "w") as f:
            json.dump(config, f)
    assert rc != 0 and result is None
    assert "intermediate_size" in err
