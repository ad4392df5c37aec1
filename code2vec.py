#!/usr/bin/env python3
"""Entry point — the reference CLI surface, unchanged (SURVEY.md §2 L6):

  python3 code2vec.py --data <prefix> --test <file> --save/--load <ckpt>
      [--predict] [--release] [--export_code_vectors]
      [--save_w2v <p>] [--save_t2v <p>] [--framework jax] [--backend tpu]

Dispatch order mirrors the reference `code2vec.py.__main__`: train if
--data, release if --release, w2v/t2v export if requested, predict REPL if
--predict, else evaluate if --test.
"""

import sys

from code2vec_tpu import device
from code2vec_tpu.config import Config
from code2vec_tpu.obs import memory_tracer
from code2vec_tpu.parallel.distributed import maybe_initialize
from code2vec_tpu.vocab.vocabularies import VocabType


def main(argv=None) -> int:
    try:
        config = Config.load_from_args(argv)
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    # `setup/backend` (obs/setup_trace.py): the import of jax and the
    # first touch of the device, select_backend to require_backend
    with memory_tracer().start_span("setup/backend") as span:
        device.select_backend(config.BACKEND)
        cache_dir = device.enable_compile_cache()
        # Deterministic fault injection (ISSUE 10): arm the registry
        # BEFORE anything builds — sites fetch their handles at setup
        # time, and dist/init below is itself a site.
        if config.FAULTS:
            from code2vec_tpu.resilience import faults
            try:
                faults.install(config.FAULTS, log=config.log)
            except ValueError as e:
                print(f"error: --faults: {e}", file=sys.stderr)
                return 2
        # Multi-host jobs must initialize the distributed runtime
        # before the first backend touch; single-host runs detect
        # nothing and continue.
        maybe_initialize(config.DIST_COORDINATOR,
                         config.DIST_NUM_PROCESSES,
                         config.DIST_PROCESS_ID, log=config.log)
        # --backend is a demand, not a hint: a run that asked for a TPU
        # and found none stops here, before any model is built.
        try:
            devices = device.require_backend(config.BACKEND)
        except device.BackendUnavailable as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
        span.attrs.update(platform=devices[0].platform,
                          devices=len(devices))
    # Preemption recovery: with --auto_resume, an existing checkpoint in
    # --save turns this run into a resume of itself — the SAME command
    # line continues after a pod restart instead of training from
    # scratch. This takes precedence over --load (a fine-tune's base
    # checkpoint): after a preemption the run's OWN progress in --save
    # is the thing to restore; --load applies only on the first run.
    if config.AUTO_RESUME and config.is_saving and config.is_training:
        from code2vec_tpu.training.checkpoint import latest_step
        step = latest_step(config.save_path)
        if step is not None:
            if config.is_loading and config.load_path != config.save_path:
                config.log(
                    f"--auto_resume: --save has checkpoint step {step}; "
                    f"resuming from it INSTEAD of --load "
                    f"{config.load_path}")
            else:
                config.log(f"--auto_resume: found checkpoint step "
                           f"{step} in {config.save_path}; resuming")
            config.load_path = config.save_path
    # A checkpoint knows which head trained it; adopt (or cross-check)
    # the manifest so `--load <vm_ckpt>` works without re-passing --head.
    if config.is_loading:
        import json
        import os
        mpath = os.path.join(config.load_path, "manifest.json")
        if os.path.exists(mpath):
            with open(mpath) as f:
                manifest = json.load(f)
            ckpt_head = manifest.get("head", "code2vec")
            if config.HEAD_EXPLICIT and ckpt_head != config.HEAD:
                print(f"error: checkpoint was trained with --head "
                      f"{ckpt_head}, but --head {config.HEAD} was given",
                      file=sys.stderr)
                return 2
            config.HEAD = ckpt_head
            # tables_dtype gates surfaces the same way head does
            # (--attack on an int8 checkpoint must fail the verify
            # below, not crash in the attack's table matvec)
            config.TABLES_DTYPE = manifest.get("tables_dtype",
                                               config.TABLES_DTYPE)
    # Config.verify() ran before the manifest could set HEAD or the
    # dims set TABLES_DTYPE; re-run it now that the effective values are
    # known — varmisuse checkpoints must reject the code2vec-only
    # surfaces (--predict/--release/--attack/--save_w2v/--save_t2v/
    # --export_code_vectors) and int8 checkpoints must reject --attack
    # with a clean error, not a downstream crash.
    try:
        config.verify()
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2

    # `setup/imports`: the model's modules bring orbax, optax and the
    # encoders along, which is seconds on a TPU host (PERF.md section 5)
    with memory_tracer().start_span("setup/imports"):
        from code2vec_tpu.serving.interactive_predict import \
            InteractivePredictor
        if config.HEAD == "varmisuse":
            from code2vec_tpu.models.vm_model import \
                VarMisuseModel as Model
        else:
            from code2vec_tpu.models.jax_model import \
                Code2VecModel as Model
    model = Model(config)
    config.log(f"model loaded: framework=jax platform="
               f"{devices[0].platform} device_kind="
               f"{devices[0].device_kind!r} devices={len(devices)} "
               f"compile_cache={cache_dir}")

    if config.release:
        model.release()
        return 0

    if config.ATTACK:
        # Adversarial attack on --attack_input's source (the noamyft
        # fork delta; attacks/source_attack.py). The printed outcome is
        # the model's prediction on the REWRITTEN source, re-extracted.
        from code2vec_tpu.attacks.source_attack import (
            SourceAttack, normalize_target_name)
        target = normalize_target_name(config.ATTACK_TARGET)
        attack = SourceAttack(config, model,
                              top_k_candidates=config.ATTACK_TOPK,
                              max_iters=config.ATTACK_ITERS)
        try:
            result = attack.attack_file(
                config.ATTACK_INPUT,
                method_index=config.ATTACK_METHOD_INDEX,
                targeted=config.ATTACK == "targeted",
                target_name=target,
                max_renames=config.ATTACK_MAX_RENAMES,
                deadcode=config.ATTACK_DEADCODE)
        except ValueError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
        print(str(result))
        # only a VERIFIED success earns the .adversarial artifact —
        # scripts treat the file's existence as the success signal
        if result.adversarial_source is not None and \
                result.verified_success:
            dest = config.ATTACK_INPUT + ".adversarial"
            with open(dest, "w", encoding="utf-8") as f:
                f.write(result.adversarial_source)
            config.log(f"adversarial source -> {dest}")
        return 0

    if config.is_training:
        model.train()

    if config.save_w2v:
        model.save_word2vec_format(config.save_w2v, VocabType.Token)
        config.log(f"token embeddings (w2v format) -> {config.save_w2v}")
    if config.save_t2v:
        model.save_word2vec_format(config.save_t2v, VocabType.Target)
        config.log(f"target embeddings (w2v format) -> {config.save_t2v}")

    if config.is_predict:
        InteractivePredictor(config, model).predict()
    elif config.is_testing and not config.is_training:
        results = model.evaluate()
        print(str(results))
        if config.export_code_vectors:
            dest = config.test_data_path + ".vectors"
            model.export_code_vectors_file(config.test_data_path, dest)
            config.log(f"code vectors -> {dest}")

    model.close_session()
    return 0


if __name__ == "__main__":
    sys.exit(main())
