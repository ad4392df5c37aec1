"""`run.py` end to end at a tiny size on one and on four virtual CPU
devices, with every piece of the tiny cells added as new files; the
planted faults; the names that cannot be found.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""

import json
import os

import pytest

import bench_helpers as helpers


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return helpers.make_copy(str(tmp_path_factory.mktemp("bench") / "c"))


RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.mark.parametrize("workload,devices", [
    ("tiny-bag-1", 1), ("tiny-xf2-1", 1), ("tiny-bag-4", 4)])
def test_timed_run(copy, workload, devices):
    rc, result, err = helpers.run_cell(copy, workload, devices,
                                       seed=2 ** 31 + 12345)
    assert rc == 0, err[-3000:]
    assert RESULT_KEYS <= set(result)
    assert list(result)[-1] == "compared"
    assert result["correct"] is True, err[-2000:]
    assert set(result["metrics"]) == {"train_methods_per_s", "setup_s"}
    assert result["metrics"]["train_methods_per_s"]["value"] > 0
    assert result["device"]["count"] == devices
    assert err.strip().splitlines()[-1].startswith("compared ")
    for c in result["compared"].values():
        assert 0 <= c["value"] <= c["limit"]


@pytest.mark.parametrize("workload,devices", [
    ("tiny-bag-4", 4), ("tiny-xf2-1", 1)])
def test_traced_run(copy, workload, devices):
    rc, result, err = helpers.run_cell(copy, workload, devices, trace=1,
                                       seconds=3)
    assert rc == 0, err[-3000:]
    m = result["metrics"]
    # the metric added as a new file is read; device shares of a peak
    # are left out on a CPU (no peak is borrowed), never reported as 0
    assert "dispatch_ms" in m and "infeed_wait_ms" in m
    assert m["compiles_in_window"]["value"] == 0
    assert "train_step_mfu" not in m and "peak_hbm_gb" not in m
    assert 0 <= m["device_idle_share"]["value"] <= 100
    assert result["device"]["busy_s"] > 0
    assert result["device"]["window_s"] <= 2.5       # trace_seconds caps it
    assert len(result["breakdown"]["device_ops"]) <= 10
    assert result["correct"] is True
    if devices == 4:
        assert m["allreduce_ms"]["value"] >= \
            m["allreduce_exposed_ms"]["value"] >= 0


@pytest.mark.parametrize("fault,workload,devices", [
    ("state_unchanged", "tiny-bag-1", 1),
    ("half_batch", "tiny-bag-1", 1),
    ("half_batch", "tiny-xf2-1", 1),
    ("no_exchange", "tiny-bag-4", 4)])
def test_fault_comes_out_not_correct(copy, fault, workload, devices):
    rc, result, err = helpers.run_cell(copy, workload, devices, fault=fault)
    assert rc == 0, err[-3000:]
    assert result["correct"] is False
    assert any(c["value"] > c["limit"] for c in result["compared"].values())


def test_unknown_names_say_which_file(copy, tmp_path):
    rc, result, err = helpers.run_cell(copy, "no-such-cell", 1)
    assert rc != 0 and result is None and "no-such-cell" in err
    with open(os.path.join(copy, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["workloads"].append({"name": "lost", "config": "tiny-bag",
                                  "traffic": "no-such-mix", "chips": 1,
                                  "why": "test"})
    with open(os.path.join(copy, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    rc, result, err = helpers.run_cell(copy, "lost", 1)
    assert rc != 0 and result is None
    assert "benchmark/traffic/no-such-mix.json" in err


def test_no_tpu_is_a_failure_with_no_result():
    """The real command on this machine (no accelerator): non-zero, no
    result line."""
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(helpers.REPO, "benchmark", "run.py"),
         "--workload", "bag-train-corpus", "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_manifest_names_each_pair_once(tmp_path):
    """The contract's rule that PR 24's first check refused the manifest
    on: a pair of configuration and traffic stands once, in the committed
    manifest and in the tiny cells the tests add; every named file is
    there. (A copy of its own: another test adds a lost cell to the
    module's.)"""
    for root in (helpers.REPO, helpers.make_copy(str(tmp_path / "c"))):
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            manifest = json.load(f)
        pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
        assert len(pairs) == len(set(pairs)), pairs
        for w in manifest["workloads"]:
            assert os.path.isfile(os.path.join(
                root, "benchmark", "traffic", w["traffic"] + ".json"))
