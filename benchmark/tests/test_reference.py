"""`reference.py` by itself: its mask is JAX's own draw, and put in the
program's place in fp8 (the control) the harness's own verdict on it under
the tiny configurations' limits is not correct, as on the planted faults. (The
reference against the program's step, both encoders, is test_run.py's
`test_timed_run`: `correct` there IS that comparison.)"""

import importlib.util
import json
import os
import sys

import numpy as np
import pytest

import bench_helpers as helpers

BENCH = os.path.dirname(helpers.TESTS)
sys.path.insert(0, BENCH)


def _kind():
    spec = importlib.util.spec_from_file_location(
        "train_corpus", os.path.join(BENCH, "kinds", "train_corpus.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_mask_rows_are_jax_bernoulli():
    import jax
    import jax.numpy as jnp

    import reference

    key = jax.random.PRNGKey(42)
    shape = (64, 12, 384)
    full = jax.random.bernoulli(key, 0.75, shape)
    for start in (0, 8, 56):
        rows = jax.jit(lambda k, s: reference._keep_rows(
            k, 0.75, shape, s, 8))(key, jnp.int32(start))
        assert bool((rows == full[start:start + 8]).all())


def _batches(rng, spec, n, steps=3):
    C = spec["max_contexts"]
    out = []
    for _ in range(steps):
        lens = rng.integers(1, C + 1, n)
        mask = (np.arange(C)[None, :] < lens[:, None]).astype(np.float32)
        ids = lambda v: (rng.integers(2, v + 2, (n, C))
                         * mask).astype(np.int32)
        out.append((rng.integers(2, spec["targets"] + 2, n).astype(np.int32),
                    ids(spec["tokens"]), ids(spec["paths"]),
                    ids(spec["tokens"]), mask, np.ones(n, np.float32)))
    return out


@pytest.mark.parametrize("encoder", ["bag", "transformer"])
def test_control_and_faults_come_out_not_correct(encoder):
    import reference

    kind = _kind()
    spec = dict(helpers.TINY_MODEL, embedding=128, code_vector=384,
                dropout_keep=0.75, encoder=encoder,
                tables_dtype="bfloat16", compute_dtype="bfloat16",
                xf_layers=2, xf_heads=3, xf_mlp_ratio=4, lr=1e-3,
                lr_schedule="cosine", lr_total_steps=1600)
    batches = _batches(np.random.default_rng(3), spec, 16)
    ref = reference.follow(11, spec, batches, block=8)
    again = reference.follow(11, spec, batches, block=16)
    same = kind.compare(again, ref)["numbers"]
    assert max(same.values()) < 1e-5          # blocks do not change it

    # the harness's own verdicts, under the limits a run is held to: the
    # reference against itself is correct, the control and every planted
    # fault is not
    assert kind.judge(same, helpers.TINY_LIMITS)["correct"] is True
    readings = kind.other_readings(
        11, spec, batches, 8, ref, 2, limits=helpers.TINY_LIMITS,
        control="fp8", faults=True)
    assert set(readings) == {"control_fp8", "fault_half_batch",
                             "fault_no_exchange", "fault_state_unchanged"}
    for name, reading in readings.items():
        assert reading["correct"] is False and reading["over"], name
    assert "grad_norm_gap" in readings["fault_half_batch"]["over"]
    assert readings["fault_state_unchanged"]["over"] == ["change_norm_gap"]


def test_a_number_without_a_limit_is_not_compared():
    kind = _kind()
    numbers = {"loss1_gap": 1e-7, "loss2_gap": 0.5, "grad_norm_gap": 1e-3}
    verdict = kind.judge(numbers, {"loss1_gap": 1e-5, "grad_norm_gap": 0.01})
    assert verdict["correct"] is True
    assert set(verdict["compared"]) == {"loss1_gap", "grad_norm_gap"}
    assert verdict["not_compared"] == {"loss2_gap": 0.5}
    assert kind.judge(dict(numbers, grad_norm_gap=float("nan")),
                      {"grad_norm_gap": 0.01})["correct"] is False
    with pytest.raises(RuntimeError):       # a limit on no number
        kind.judge(numbers, {"loss9_gap": 1.0})
