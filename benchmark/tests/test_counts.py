"""The files under `counts/` against numbers worked by hand."""

import importlib.util
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, "counts", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# one method of 3 valid contexts, D = 4, 2 sampled classes
SIZES = {"code_vector": 4, "num_sampled": 2, "xf_layers": 2,
         "xf_mlp_ratio": 4, "compute_dtype": "bfloat16"}
WINDOW = {"methods": 1, "contexts": 3, "contexts_sq": 9, "steps": 1}


def test_step_bag():
    # context: 2*4*4 + 4*4 = 48, x3 contexts = 144; method: 2*4*3 = 24
    assert load("step_bag").flops(SIZES, WINDOW) == 3 * (144 + 24)


def test_step_xf2():
    # context: in 32 + 2 layers x (8 + 16) x 16 = 768, pool 16 -> 816, x3
    # = 2448; attention 2 x 4 x 4 x 9 = 288; method 24
    assert load("step_xf2").flops(SIZES, WINDOW) == 3 * (2448 + 288 + 24)


def test_attention_pool():
    w = load("attention_pool").work(SIZES, WINDOW)
    assert w["flops"] == 3 * 48
    # read 3 contexts x 4 x 2 B + (16 + 4) x 4 B; write 4 x 4 B + 3 x 4 B
    assert w["bytes"] == 24 + 80 + 16 + 12


def test_xf_mha():
    w = load("xf_mha").work(SIZES, WINDOW)
    assert w["flops"] == 2 * 12 * 4 * 9
    assert w["bytes"] == 2 * 11 * 4 * 3 * 2
