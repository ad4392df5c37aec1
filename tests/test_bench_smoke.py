"""Smoke-test bench.py's measurement machinery on the virtual CPU mesh
with tiny capacities: the benchmark is the driver-facing artifact run
once per round on real hardware, so API drift (encoder/step/loss
signatures, optimizer construction, JSON assembly) must be caught by CI
rather than at round end."""

import json

import numpy as np
import pytest

import bench


@pytest.fixture(autouse=True)
def tiny_bench(monkeypatch):
    monkeypatch.setattr(bench, "TOKEN_VOCAB", 128)
    monkeypatch.setattr(bench, "PATH_VOCAB", 96)
    monkeypatch.setattr(bench, "TARGET_VOCAB", 64)
    monkeypatch.setattr(bench, "BATCH", 8)
    monkeypatch.setattr(bench, "MAX_CONTEXTS", 6)
    monkeypatch.setattr(bench, "NUM_SAMPLED", 16)
    monkeypatch.setattr(bench, "WARMUP_STEPS", 1)
    monkeypatch.setattr(bench, "MEASURE_STEPS", 4)


def test_measure_encoder_and_floor_run():
    # API-drift smoke only: on a contended CI host the slope-timed
    # difference of two tiny chains can legitimately come out <= 0, so
    # assert finiteness, not positivity (bench runs on an idle chip).
    pc, ms, gbps = bench._measure_encoder("bag")
    assert all(np.isfinite(x) for x in (pc, ms, gbps))
    floor = bench._measure_fwd_bwd_floor()
    assert np.isfinite(floor)


def test_main_emits_one_valid_json_line(monkeypatch, capsys):
    # the 1-GiB ceiling copy is too slow for CI; stub it
    monkeypatch.setattr(bench, "_measure_hbm_ceiling",
                        lambda: 590e9)
    bench.main()
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 1
    j = json.loads(out[0])
    for key in ("metric", "value", "unit", "vs_baseline", "ms_per_step",
                "hbm_gbps", "hbm_ceiling_gbps",
                "fwd_bwd_floor_pc_per_sec", "optimizer_efficiency",
                "transformer_pc_per_sec",
                # int8 requantize phase attribution (round 6): the
                # acceptance contract is these fields present off-TPU
                "int8_hbm_gbps", "int8_requant_ms", "int8_requant_bytes",
                "int8_requant_gbps", "int8_requant_floor_ms",
                "int8_requant_vs_ceiling", "int8_requant_fused",
                # sparse table-update attribution (round 13): same
                # present-off-TPU contract
                "sparse_pc_per_sec", "sparse_ms_per_step",
                "sparse_hbm_gbps", "sparse_step_floor_pc_per_sec",
                "sparse_optimizer_efficiency", "sparse_update_ms",
                "sparse_update_bytes", "sparse_update_gbps",
                "sparse_update_floor_ms", "sparse_update_vs_ceiling",
                "sparse_update_unique_rows", "sparse_update_fused"):
        assert key in j, key
    assert j["metric"] == "path-contexts/sec/chip"
    assert np.isfinite(j["value"])
    assert j["int8_requant_fused"] is False  # CPU -> reference path
    assert j["int8_requant_bytes"] > 0
    assert j["sparse_update_fused"] is False  # CPU -> reference path
    assert j["sparse_update_bytes"] > 0
    # per-table uniques are bounded by each vocab (the id draws cover
    # the tiny vocabs almost fully: _device_batches' max_contexts
    # default binds the REAL 200 at import time, not the patched 6)
    assert 0 < j["sparse_update_unique_rows"] <= 128 + 96 + 64


def test_step_hbm_bytes_counts_quantized_carrier():
    """int8 subtrees: the analytic grad term must size the bf16 [V, E]
    carrier (2 B/elt), not the stored int8 (1 B/elt), and the param
    term the q/s read+write (ADVICE r5 finding 2)."""
    import jax
    import jax.numpy as jnp

    from code2vec_tpu.models.encoder import ModelDims, init_params
    from code2vec_tpu.ops.quant import is_quantized

    dims = ModelDims(token_vocab_size=64, path_vocab_size=32,
                     target_vocab_size=24, embeddings_size=8,
                     max_contexts=6, tables_dtype="int8")
    params = init_params(jax.random.PRNGKey(0), dims)
    opt_state = {"nu": jnp.zeros((3, 4), jnp.float32)}
    expected = opt_state["nu"].size * 4 * 2
    for p in params.values():
        if is_quantized(p):
            expected += (p["q"].size * 2 * 2          # bf16 carrier r+w
                         + p["q"].size * 1 * 2        # int8 q r+w
                         + p["s"].size * 4 * 2)       # f32 s r+w
        else:
            expected += p.size * p.dtype.itemsize * 4
    assert bench._step_hbm_bytes(params, opt_state) == expected
    # regression guard for the original bug: the quantized accounting
    # must exceed stored-dtype sizing (1 B grads) for the same params
    naive = sum(x.size * x.dtype.itemsize * 4
                for x in jax.tree_util.tree_leaves(params)) \
        + opt_state["nu"].size * 4 * 2
    assert bench._step_hbm_bytes(params, opt_state) > naive


def test_aggregate_projection_collective_model():
    """The v4-32 projection (tools/aggregate_projection.py) must model
    DP efficiency from explicit collective traffic, not imply 1.0
    (VERDICT r3 item 5)."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "aggregate_projection",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools",
            "aggregate_projection.py"))
    ap = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ap)

    m = ap.collective_model(per_chip_batch=1024, step_ms=26.0)
    dp = m["pure_dp16_replicated"]
    tp = m["data4xmodel4_rowsharded"]
    # both shipped meshes are itemized with strictly positive comm
    assert 0 < dp["dp_efficiency"] < 1 and dp["comm_ms"] > 0
    assert tp["comm_ms"] > 0
    # the TP mesh models compute replication explicitly (ADVICE r4:
    # shard_batch shards over 'data' only, so model-axis chips repeat
    # the dense work): replicated + sharded + comm adds up to the
    # modeled group step, and the aggregate counts each batch shard
    # once — NOT chips x per-chip
    assert tp["replicated_dense_ms"] > 0
    assert abs(tp["replicated_dense_ms"] + tp["sharded_table_ms"]
               + tp["comm_ms"] - tp["modeled_step_ms_per_group"]) < 0.05
    recon = m["data_ax"] * 1024 * ap.CTX \
        / tp["modeled_step_ms_per_group"] * 1e3
    assert abs(tp["aggregate_pc_per_sec"] - recon) / recon < 1e-2
    # bytes sanity: replicated allreduce carries the three bf16 tables
    expected = 2 * (ap.VT * ap.E + ap.VP * ap.E + ap.VY * ap.D3)
    assert abs(dp["allreduce_bytes_per_step"] - expected) < 1e7
    # the formula itself rides in the output (checkable prose)
    assert "2*(N-1)/N" in m["formula"]
    assert "replicate" in m["formula"]
    # a zero-comm step would be efficiency 1; the DP formula must be
    # monotone in step time (longer steps amortize the same traffic)
    m_slow = ap.collective_model(per_chip_batch=1024, step_ms=100.0)
    assert m_slow["dp_efficiency"] > m["dp_efficiency"]
