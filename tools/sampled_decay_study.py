#!/usr/bin/env python3
"""Root-cause instrumentation for the sampled-softmax f32 top1 decay.

Round-2 quality study (BASELINE.md) found sampled+f32 tables plateau
~2.6 F1 points below full softmax on the 50K-name corpus, with top1
DECAYING late in training, while bf16 tables "evidently damp" the
instability. This tool trains the sampled config and captures, every
`--probe_epochs` epochs:

  - val top1 split by target-frequency decile (head = most frequent);
  - mean L2 norm of target-embedding rows per decile;
  - mean Adam second-moment (nu) per decile for the target table;
  - mean bias-corrected update magnitude per decile (the quantity that
    bf16 storage would round away once it drops below ~1/256 of the
    row's scale — the hypothesized damping mechanism).

Mechanism hypotheses it separates:
  H1 head-negative pressure: the log-uniform sampler draws head classes
     as negatives almost every step, so between their (rarer) positive
     occurrences their logits are pushed down; late in training the
     positive/negative pressure balance tips and head top1 decays.
     Signature: head-decile top1 falls while tail deciles hold; head row
     norms keep moving late in training.
  H2 effective-LR spike: Adam nu for converged head rows decays, so the
     per-row effective LR rises late and the rows oscillate. Signature:
     nu(head) falling while update magnitude holds or grows.
  H3 bf16 damping: with bf16 tables the late tiny updates round to zero
     (|update| < row_scale/256), freezing converged rows — stability by
     quantization. Signature: f32 update magnitudes late in training
     sitting below the bf16 rounding threshold for head rows.

Usage (after the corpus build in BASELINE.md "Quality study"):
  python tools/sampled_decay_study.py --data /tmp/qs/ds/qs \
      --epochs 12 --tables_dtype float32 [--lr 1e-3] [--out out.jsonl]
Run once with float32 and once with bfloat16; diff the trajectories.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def target_freq_deciles(vocabs, train_prefix: str, n_deciles: int = 10):
    """Decile boundaries over target ids ranked by training frequency.
    Vocab ids are already frequency-ordered (Vocab.create_from_freq_dict
    sorts by count), so deciles are contiguous id ranges past the
    specials."""
    V = vocabs.target_vocab.size
    first_real = 2  # PAD, OOV
    ids = np.arange(first_real, V)
    return np.array_split(ids, n_deciles)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--data", required=True)
    ap.add_argument("--epochs", type=int, default=12)
    ap.add_argument("--probe_epochs", type=int, default=2)
    ap.add_argument("--batch", type=int, default=1024)
    ap.add_argument("--num_sampled", type=int, default=4096)
    ap.add_argument("--tables_dtype", default="float32",
                    choices=["float32", "bfloat16"])
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--seed", type=int, default=239)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    from code2vec_tpu.device import enable_compile_cache
    enable_compile_cache()

    import jax.numpy as jnp

    from code2vec_tpu.config import Config
    from code2vec_tpu.models.jax_model import Code2VecModel

    cfg = Config(
        MAX_CONTEXTS=200, MAX_TOKEN_VOCAB_SIZE=150_000,
        MAX_PATH_VOCAB_SIZE=150_000, MAX_TARGET_VOCAB_SIZE=60_000,
        TRAIN_BATCH_SIZE=args.batch, TEST_BATCH_SIZE=args.batch,
        NUM_TRAIN_EPOCHS=args.probe_epochs, SAVE_EVERY_EPOCHS=1000,
        NUM_BATCHES_TO_LOG_PROGRESS=100000, LEARNING_RATE=args.lr,
        SEED=args.seed, USE_SAMPLED_SOFTMAX=True,
        NUM_SAMPLED_CLASSES=args.num_sampled,
        TABLES_DTYPE=args.tables_dtype,
        # the probes read Adam's mu/nu chain state — pin adam explicitly
        # (the shipped default is adafactor, whose state is factored)
        EMBEDDING_OPTIMIZER="adam",
    )
    cfg.train_data_path = args.data
    cfg.test_data_path = args.data + ".val.c2v"
    model = Code2VecModel(cfg)
    deciles = target_freq_deciles(model.vocabs, args.data)

    def probe(epoch_end: int) -> dict:
        # --- per-decile top1 over the val set ---
        from code2vec_tpu.data.reader import open_reader
        reader = open_reader(cfg.test_data_path, model.vocabs,
                             cfg.MAX_CONTEXTS, cfg.TEST_BATCH_SIZE,
                             shuffle=False)
        correct = np.zeros(len(deciles))
        count = np.zeros(len(deciles))
        dec_of = np.zeros(model.vocabs.target_vocab.size, np.int32) - 1
        for d, ids in enumerate(deciles):
            dec_of[ids] = d
        for batch in reader:
            dev = model._device_batch(batch, process_local=False)
            _, topk_ids, _ = model._eval_step(model.params, dev)
            nv = batch.num_valid_examples
            top1 = np.asarray(topk_ids)[:nv, 0]
            true = batch.target_index[:nv]
            for t, p in zip(true, top1):
                d = dec_of[t]
                if d >= 0:
                    count[d] += 1
                    correct[d] += float(t == p)
        top1_by_decile = (correct / np.maximum(count, 1)).round(4)

        # --- table / optimizer-state statistics per decile ---
        emb = np.asarray(model.params["target_emb"], np.float32)
        row_norm = np.linalg.norm(emb, axis=1)
        # Adam state: chain(scale_by_adam_f32_moments, scale) -> [0].nu
        nu = model.opt_state[0].nu["target_emb"]
        nu_row = np.asarray(jnp.mean(nu, axis=1), np.float32)
        mu = model.opt_state[0].mu["target_emb"]
        count_t = int(model.opt_state[0].count)
        bc1 = 1.0 - 0.9 ** max(count_t, 1)
        bc2 = 1.0 - 0.999 ** max(count_t, 1)
        upd = np.asarray(jnp.mean(jnp.abs(
            (mu / bc1) / (jnp.sqrt(nu / bc2) + 1e-8)), axis=1), np.float32)
        out = {"epoch": epoch_end, "tables_dtype": args.tables_dtype,
               "lr": args.lr,
               "top1_by_decile": top1_by_decile.tolist(),
               "row_norm_by_decile":
                   [round(float(row_norm[ids].mean()), 4)
                    for ids in deciles],
               "nu_by_decile":
                   [float(nu_row[ids].mean()) for ids in deciles],
               "lr_x_update_by_decile":
                   [float(args.lr * upd[ids].mean()) for ids in deciles],
               # bf16 rounding threshold for a row of this scale:
               # updates below norm/sqrt(D)/256 round to nothing
               "bf16_round_threshold_by_decile":
                   [round(float(row_norm[ids].mean())
                          / np.sqrt(emb.shape[1]) / 256, 8)
                    for ids in deciles]}
        print(json.dumps(out), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(out) + "\n")
        return out

    done = 0
    while done < args.epochs:
        t0 = time.time()
        model.train()  # runs cfg.NUM_TRAIN_EPOCHS (= probe_epochs)
        done += cfg.NUM_TRAIN_EPOCHS
        print(f"epochs {done}/{args.epochs} "
              f"({time.time() - t0:.0f}s)", file=sys.stderr)
        probe(done)


if __name__ == "__main__":
    main()
