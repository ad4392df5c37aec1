#!/usr/bin/env python3
"""Quality ablation: sampled softmax & low-precision vs the exact config.

BASELINE.md quality-evidence requirement (SURVEY.md §8.4 item 3): show on
a ≥50K-name corpus (tools/gen_java_corpus.py, extracted by the native
C++ extractor) that
  - sampled softmax matches full softmax F1 (the java-large config), and
  - bf16 tables / the adafactor table optimizer (the perf configs,
    BASELINE.md) match f32/adam F1
at matched steps, seeds, and data order.

Usage:
  python tools/gen_java_corpus.py --out /tmp/qs/raw ...
  TRAIN_DIR=... ./preprocess.sh   (see BASELINE.md)
  python tools/quality_study.py --data /tmp/qs/ds/qs --epochs 6 \
      [--variants full-f32-adam,sampled-f32-adam,...]
Prints one JSON line per variant and a summary table.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

VARIANTS = {
    # name: (use_sampled, tables_dtype, embedding_optimizer, encoder)
    "full-f32-adam": (False, "float32", "adam", "bag"),
    "sampled-f32-adam": (True, "float32", "adam", "bag"),
    "sampled-bf16-adam": (True, "bfloat16", "adam", "bag"),
    "sampled-bf16-adafactor": (True, "bfloat16", "adafactor", "bag"),
    "sampled-int8-adafactor": (True, "int8", "adafactor", "bag"),
    "sampled-bf16-xf2": (True, "bfloat16", "adam", "transformer"),
}


def run_variant(name: str, data: str, epochs: int, batch: int,
                num_sampled: int, seed: int, lr: float = 1e-3,
                lr_schedule: str = "constant",
                max_contexts: int = 200,
                save_path: str = None,
                warmup_steps: int = 0,
                trust_ratio: bool = False,
                trust_ratio_scope: str = "all") -> dict:
    from code2vec_tpu.config import Config
    from code2vec_tpu.models.jax_model import Code2VecModel

    use_sampled, tdtype, eopt, encoder = VARIANTS[name]
    cfg = Config(
        MAX_CONTEXTS=max_contexts,
        MAX_TOKEN_VOCAB_SIZE=150_000,
        MAX_PATH_VOCAB_SIZE=150_000,
        MAX_TARGET_VOCAB_SIZE=60_000,
        TRAIN_BATCH_SIZE=batch,
        TEST_BATCH_SIZE=batch,
        NUM_TRAIN_EPOCHS=epochs,
        SAVE_EVERY_EPOCHS=1000,
        NUM_BATCHES_TO_LOG_PROGRESS=100,
        LEARNING_RATE=lr,
        LR_SCHEDULE=lr_schedule,
        LR_WARMUP_STEPS=warmup_steps,
        TRUST_RATIO=trust_ratio,
        TRUST_RATIO_SCOPE=trust_ratio_scope,
        SEED=seed,
        USE_SAMPLED_SOFTMAX=use_sampled,
        NUM_SAMPLED_CLASSES=num_sampled,
        TABLES_DTYPE=tdtype,
        EMBEDDING_OPTIMIZER=eopt,
        ENCODER_TYPE=encoder,
    )
    cfg.train_data_path = data
    cfg.test_data_path = data + ".val.c2v"
    cfg.verify()  # e.g. reject --warmup_steps with a non-warmup
    # schedule instead of recording a misleading combination
    model = Code2VecModel(cfg)
    t0 = time.time()
    model.train()
    train_s = time.time() - t0
    if save_path:
        # save OUTSIDE the timed window (a mid-train save cadence would
        # also trigger mid-train evaluate() calls and skew train_seconds
        # across variants)
        model.save(save_path)
    res = model.evaluate()
    out = {
        "variant": name,
        "use_sampled_softmax": use_sampled,
        "tables_dtype": tdtype,
        "embedding_optimizer": eopt,
        "encoder": encoder,
        "epochs": epochs,
        "batch": batch,
        "lr": lr,
        "lr_schedule": lr_schedule,
        "warmup_steps": warmup_steps,
        "trust_ratio": trust_ratio,
        "trust_ratio_scope": trust_ratio_scope,
        "max_contexts": max_contexts,
        "steps": model.step_num,
        "train_seconds": round(train_s, 1),
        "val_loss": round(float(res.loss), 4),
        "val_top1": round(res.topk_acc[0], 4),
        "val_top5": round(res.topk_acc[4], 4),
        "val_precision": round(res.subtoken_precision, 4),
        "val_recall": round(res.subtoken_recall, 4),
        "val_f1": round(res.subtoken_f1, 4),
        "target_vocab_size": model.vocabs.target_vocab.size,
    }
    print(json.dumps(out), flush=True)
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--data", required=True)
    ap.add_argument("--epochs", type=int, default=6)
    ap.add_argument("--batch", type=int, default=1024,
                    help="batch size; with matched --epochs, different "
                         "batch sizes see the same token budget "
                         "(VERDICT r2 item 1a: large-batch convergence "
                         "neutrality)")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--lr_schedule", default="constant",
                    choices=["constant", "cosine", "linear",
                             "warmup_cosine"])
    ap.add_argument("--warmup_steps", type=int, default=0,
                    help="warmup_cosine warmup length (0 = auto 5%%)")
    ap.add_argument("--trust_ratio", action="store_true",
                    help="LAMB-style per-array trust ratio")
    ap.add_argument("--trust_ratio_scope", default="all",
                    choices=["all", "dense"],
                    help="'dense' = trust-scale non-table params only "
                         "(the sane LAMB form; VERDICT r4 item 8)")
    ap.add_argument("--num_sampled", type=int, default=1024)
    ap.add_argument("--max_contexts", type=int, default=200,
                    help="match the dataset's binarized width (200 for "
                         "the production corpus; smaller for smokes)")
    ap.add_argument("--seed", type=int, default=239)
    ap.add_argument("--variants", default=",".join(VARIANTS))
    ap.add_argument("--save", default=None,
                    help="checkpoint dir prefix (enables the decay "
                         "study's per-epoch analysis)")
    ap.add_argument("--out", default=None,
                    help="append JSON lines here too")
    args = ap.parse_args()
    from code2vec_tpu.device import enable_compile_cache
    enable_compile_cache()

    results = []
    for name in args.variants.split(","):
        r = run_variant(name.strip(), args.data, args.epochs, args.batch,
                        args.num_sampled, args.seed, lr=args.lr,
                        lr_schedule=args.lr_schedule,
                        max_contexts=args.max_contexts,
                        save_path=(args.save + "." + name.strip()
                                   if args.save else None),
                        warmup_steps=args.warmup_steps,
                        trust_ratio=args.trust_ratio,
                        trust_ratio_scope=args.trust_ratio_scope)
        results.append(r)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(r) + "\n")

    print("\nvariant                    B     lr      sched     F1      "
          "top1    loss")
    for r in results:
        print(f"{r['variant']:26s} {r['batch']:<5d} {r['lr']:<7g} "
              f"{r['lr_schedule']:9s} {r['val_f1']:.4f}  "
              f"{r['val_top1']:.4f}  {r['val_loss']:.3f}")


if __name__ == "__main__":
    main()
