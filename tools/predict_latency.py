#!/usr/bin/env python3
"""Prediction latency at java-large capacities (SURVEY.md §7 row).

The reference claims "milliseconds per example" serving latency (code2vec
paper; BASELINE.md row, confidence Low). This measures this framework's
equivalents on the real chip:

  - device_predict_ms: the jitted predict step (encode -> full [1, Vy]
    logits -> top-k) at batch 1, java-large dims, slope-timed (the
    slope cancels the fixed sync and per-dispatch cost).
  - device_predict_call_ms: the same step timed as one naive dispatch+
    sync round trip — what a caller on this host observes per call.
  - extract_ms: the native C++ extractor CLI on Input.java (subprocess
    wall time, includes process startup — the REPL pays exactly this).
  - tensorize_ms: host-side c2v row -> padded int32 tensors.
  - repl_end_to_end_ms: extract + tensorize + one naive predict call.

Params are random at java-large shapes (latency is shape-, not
value-dependent). Usage: python tools/predict_latency.py [--out f]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXTRACTOR = os.path.join(REPO, "code2vec_tpu/extractor/build/c2v_extract")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    ap.add_argument("--steps", type=int, default=50)
    args = ap.parse_args()
    from code2vec_tpu.device import enable_compile_cache
    enable_compile_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from code2vec_tpu.models.encoder import init_params
    from code2vec_tpu.training.steps import make_predict_step

    sys.path.insert(0, REPO)
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "bench", os.path.join(REPO, "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)

    dims = bench._java_large_dims("bag")
    params = init_params(jax.random.PRNGKey(0), dims)
    step = make_predict_step(dims, compute_dtype=jnp.bfloat16,
                             use_pallas=jax.default_backend() == "tpu")
    r = np.random.default_rng(0)
    batch = (jnp.zeros((1,), jnp.int32),
             jnp.asarray(r.integers(0, dims.token_vocab_size, (1, 200)),
                         jnp.int32),
             jnp.asarray(r.integers(0, dims.path_vocab_size, (1, 200)),
                         jnp.int32),
             jnp.asarray(r.integers(0, dims.token_vocab_size, (1, 200)),
                         jnp.int32),
             jnp.ones((1, 200), jnp.float32),
             jnp.ones((1,), jnp.float32))

    def run_n(n):
        t0 = time.perf_counter()
        for _ in range(n):
            ids, probs, _attn, _code = step(params, batch)
        float(probs[0, 0])  # hard sync (host transfer)
        return time.perf_counter() - t0

    run_n(3)  # warm the compile cache
    # slope: cancels the fixed sync + per-dispatch overhead
    t_a, t_b = run_n(10), run_n(10 + args.steps)
    device_ms = (t_b - t_a) / args.steps * 1e3
    # naive single-call latency (dispatch + sync round trip)
    calls = [run_n(1) for _ in range(5)]
    call_ms = sorted(calls)[len(calls) // 2] * 1e3

    # ---- extractor + tensorize (host side) ----
    extract_ms = tensorize_ms = None
    sample = os.path.join(REPO, "Input.java")
    if os.path.exists(EXTRACTOR) and os.path.exists(sample):
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            out = subprocess.run([EXTRACTOR, "--file", sample],
                                 capture_output=True, text=True,
                                 check=True).stdout
            ts.append(time.perf_counter() - t0)
        extract_ms = sorted(ts)[2] * 1e3
        line = out.strip().splitlines()[0]
        from code2vec_tpu.data.reader import parse_c2v_rows
        from code2vec_tpu.vocab.vocabularies import Code2VecVocabs
        del Code2VecVocabs  # tensorize timing uses a synthetic vocab:

        # real vocab lookup is a dict probe per token — emulate with the
        # tiny test vocab would understate hashing cost, so time the
        # split/pad path on the raw line against a stub that maps every
        # token to a fixed id (the dict probe itself is O(100ns)/token)
        class _Stub:
            pad_index = 0
            oov_index = 1

            def lookup_index(self, w):
                return 2

        stub = type("V", (), {})()
        stub.token_vocab = _Stub()
        stub.path_vocab = _Stub()
        stub.target_vocab = _Stub()
        t0 = time.perf_counter()
        for _ in range(20):
            parse_c2v_rows([line], stub, dims.max_contexts)
        tensorize_ms = (time.perf_counter() - t0) / 20 * 1e3

    row = {
        "metric": "prediction_latency_java_large",
        "device_predict_ms_batch1": round(device_ms, 3),
        "device_predict_call_ms": round(call_ms, 1),
        "extract_ms_subprocess": (round(extract_ms, 1)
                                  if extract_ms else None),
        "tensorize_ms": (round(tensorize_ms, 2)
                         if tensorize_ms else None),
        "repl_end_to_end_ms": (
            round(call_ms + extract_ms + tensorize_ms, 1)
            if extract_ms else None),
        "backend": jax.default_backend(),
        "note": "device_predict_ms is the chip latency (slope-timed); "
                "the call and end-to-end rows include this host's "
                "dispatch round trip and subprocess startup",
    }
    print(json.dumps(row))
    if args.out:
        with open(args.out, "a") as f:
            f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main()
