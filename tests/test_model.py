"""Integration tests (SURVEY.md §5): tiny synthetic dataset -> short train
-> loss decreases & F1 beats naive; checkpoint -> resume continuity;
release + predict round-trip."""

import pytest

from code2vec_tpu.config import Config
from code2vec_tpu.models.jax_model import Code2VecModel
from tests.helpers import build_tiny_dataset, make_raw_lines


def tiny_config(prefix, **kw):
    cfg = Config(
        MAX_CONTEXTS=16,
        MAX_TOKEN_VOCAB_SIZE=1000,
        MAX_PATH_VOCAB_SIZE=1000,
        MAX_TARGET_VOCAB_SIZE=1000,
        DEFAULT_EMBEDDINGS_SIZE=16,
        TRAIN_BATCH_SIZE=32,
        TEST_BATCH_SIZE=32,
        NUM_TRAIN_EPOCHS=6,
        SAVE_EVERY_EPOCHS=100,  # no mid-train saves unless asked
        NUM_BATCHES_TO_LOG_PROGRESS=1000,
        LEARNING_RATE=0.05,
        USE_BF16=False,
        MESH_MODEL_AXIS=1,
    )
    cfg.train_data_path = prefix
    cfg.test_data_path = prefix + ".test.c2v"
    for k, v in kw.items():
        setattr(cfg, k, v)
    return cfg


@pytest.fixture(scope="module")
def dataset(tmp_path_factory):
    d = tmp_path_factory.mktemp("data")
    return build_tiny_dataset(str(d), n_train=256, n_val=32, n_test=64,
                              max_contexts=16)


def test_train_loss_decreases_and_f1_beats_naive(dataset, tmp_path):
    cfg = tiny_config(dataset, save_path=str(tmp_path / "ckpt"))
    model = Code2VecModel(cfg)

    # capture initial loss via one eval pass
    before = model.evaluate()
    model.train()
    after = model.evaluate()
    assert after.loss < before.loss
    # synthetic data is learnable: expect real F1, far above a naive
    # always-predict-most-frequent baseline on 8 balanced classes
    assert after.subtoken_f1 > 0.5
    assert after.topk_acc[0] > 0.3
    model.save(str(tmp_path / "ckpt"))


def test_checkpoint_resume_continuity(dataset, tmp_path):
    ckpt_dir = str(tmp_path / "ckpt")
    cfg = tiny_config(dataset, NUM_TRAIN_EPOCHS=2)
    cfg.save_path = ckpt_dir
    model = Code2VecModel(cfg)
    model.train()
    model.save(ckpt_dir)
    saved_eval = model.evaluate()
    step_before = model.step_num

    cfg2 = tiny_config(dataset)
    cfg2.load_path = ckpt_dir
    model2 = Code2VecModel(cfg2)
    assert model2.step_num == step_before
    loaded_eval = model2.evaluate()
    # same params -> metric continuity
    assert abs(loaded_eval.loss - saved_eval.loss) < 1e-4
    assert loaded_eval.topk_acc == pytest.approx(saved_eval.topk_acc)
    # vocab sidecar round-trip
    assert (model2.vocabs.target_vocab.word_to_index
            == model.vocabs.target_vocab.word_to_index)


def test_release_and_predict_roundtrip(dataset, tmp_path):
    ckpt_dir = str(tmp_path / "ckpt")
    cfg = tiny_config(dataset, NUM_TRAIN_EPOCHS=2)
    cfg.save_path = ckpt_dir
    model = Code2VecModel(cfg)
    model.train()
    model.save(ckpt_dir)

    release_dir = str(tmp_path / "released")
    cfg_rel = tiny_config(dataset)
    cfg_rel.load_path = ckpt_dir
    cfg_rel.save_path = release_dir
    model_rel = Code2VecModel(cfg_rel)
    model_rel.release()

    cfg3 = tiny_config(dataset, export_code_vectors=True)
    cfg3.train_data_path = None
    cfg3.load_path = release_dir
    model3 = Code2VecModel(cfg3)
    lines = make_raw_lines(3, seed=9, max_ctx=10)
    results = model3.predict(lines)
    assert len(results) == 3
    r = results[0]
    assert r.original_name
    assert len(r.predictions) >= 1
    assert all(0.0 <= p["probability"] <= 1.0 for p in r.predictions)
    # attention paths sorted descending, only valid contexts
    scores = [a.attention_score for a in r.attention_paths]
    assert scores == sorted(scores, reverse=True)
    assert len(scores) >= 1
    assert r.code_vector is not None and r.code_vector.shape == (48,)


def test_w2v_export(dataset, tmp_path):
    from code2vec_tpu.vocab.vocabularies import VocabType
    cfg = tiny_config(dataset, NUM_TRAIN_EPOCHS=1)
    model = Code2VecModel(cfg)
    dest = str(tmp_path / "tokens.w2v")
    model.save_word2vec_format(dest, VocabType.Token)
    with open(dest) as f:
        header = f.readline().split()
        n, dim = int(header[0]), int(header[1])
        assert dim == 16
        lines = f.readlines()
        assert len(lines) == n
        first = lines[0].split()
        assert first[0] == "<PAD>" and len(first) == dim + 1


def test_sampled_softmax_training_works(dataset, tmp_path):
    cfg = tiny_config(dataset, USE_SAMPLED_SOFTMAX=True,
                      NUM_SAMPLED_CLASSES=6, NUM_TRAIN_EPOCHS=6)
    model = Code2VecModel(cfg)
    before = model.evaluate()
    model.train()
    after = model.evaluate()
    assert after.loss < before.loss
    assert after.topk_acc[0] > 0.2


def test_profile_flag_writes_trace(dataset, tmp_path):
    import os
    trace_dir = str(tmp_path / "trace")
    cfg = tiny_config(dataset, NUM_TRAIN_EPOCHS=1,
                      PROFILE_DIR=trace_dir, PROFILE_START_STEP=1,
                      PROFILE_STEPS=3)
    model = Code2VecModel(cfg)
    model.train()
    # jax.profiler writes plugins/profile/<run>/*.xplane.pb under the dir
    found = []
    for root, _dirs, files in os.walk(trace_dir):
        found.extend(f for f in files if f.endswith(".xplane.pb"))
    assert found, f"no trace files under {trace_dir}"


def test_cosine_lr_schedule_trains_and_resumes(dataset, tmp_path):
    ckpt = str(tmp_path / "ckpt")
    cfg = tiny_config(dataset, NUM_TRAIN_EPOCHS=4, LR_SCHEDULE="cosine",
                      save_path=ckpt)
    model = Code2VecModel(cfg)
    before = model.evaluate()
    model.train()
    after = model.evaluate()
    assert after.loss < before.loss
    model.save(ckpt)

    # resume restores schedule structure from the manifest even though
    # the fresh config requests a DIFFERENT schedule (the manifest must
    # win or the opt_state template won't match)
    cfg2 = tiny_config(dataset, NUM_TRAIN_EPOCHS=1,
                       LR_SCHEDULE="constant")
    cfg2.load_path = ckpt
    model2 = Code2VecModel(cfg2)
    assert cfg2.LR_SCHEDULE == "cosine"
    loaded = model2.evaluate()
    assert abs(loaded.loss - after.loss) < 1e-4
    model2.train()  # one more epoch continues without structure errors

    # eval-only load (no train data): the opt_state template must still
    # carry the schedule structure or orbax restore fails
    cfg3 = tiny_config(dataset)
    cfg3.train_data_path = None
    cfg3.load_path = ckpt
    model3 = Code2VecModel(cfg3)
    eval_only = model3.evaluate()
    assert abs(eval_only.loss - after.loss) < 1e-4


def test_warmup_trust_ratio_trains_and_resumes(dataset, tmp_path):
    """The large-global-batch recipe (warmup_cosine + LAMB-style trust
    ratio; BASELINE.md round-4 study): trains, saves, and a resume gets
    BOTH structure-affecting settings back from the manifest even when
    the fresh config asks for the defaults."""
    ckpt = str(tmp_path / "ckpt")
    cfg = tiny_config(dataset, NUM_TRAIN_EPOCHS=4,
                      LR_SCHEDULE="warmup_cosine", LR_WARMUP_STEPS=3,
                      TRUST_RATIO=True, save_path=ckpt)
    model = Code2VecModel(cfg)
    before = model.evaluate()
    model.train()
    after = model.evaluate()
    assert after.loss < before.loss
    model.save(ckpt)

    cfg2 = tiny_config(dataset, NUM_TRAIN_EPOCHS=1,
                       LR_SCHEDULE="constant")
    cfg2.load_path = ckpt
    model2 = Code2VecModel(cfg2)
    assert cfg2.LR_SCHEDULE == "warmup_cosine"
    assert cfg2.TRUST_RATIO is True
    # warmup length is restored too — the resumed schedule must follow
    # the original trajectory, not an auto length from the new horizon
    assert cfg2.LR_WARMUP_STEPS == 3
    loaded = model2.evaluate()
    assert abs(loaded.loss - after.loss) < 1e-4
    model2.train()  # structure matches; training continues

    # eval-only load (no train data, schedule horizon 1): the
    # warmup_cosine schedule must still build — optax needs positive
    # cosine steps past the warmup (caught by /verify in round 4)
    cfg3 = tiny_config(dataset)
    cfg3.train_data_path = None
    cfg3.load_path = ckpt
    model3 = Code2VecModel(cfg3)
    eval_only = model3.evaluate()
    assert abs(eval_only.loss - after.loss) < 1e-4


def test_tensorboard_scalars_written(dataset, tmp_path):
    import os
    tb = str(tmp_path / "tb")
    cfg = tiny_config(dataset, NUM_TRAIN_EPOCHS=2,
                      NUM_BATCHES_TO_LOG_PROGRESS=2,
                      SAVE_EVERY_EPOCHS=1, TENSORBOARD_DIR=tb)
    model = Code2VecModel(cfg)
    model.train()
    events = []
    for root, _d, files in os.walk(tb):
        events.extend(f for f in files if "tfevents" in f)
    assert events, f"no event files under {tb}"


def test_auto_resume_via_cli_dispatch(dataset, tmp_path, monkeypatch):
    """--auto_resume turns a rerun into a resume OF ITSELF (round-15
    semantics, the supervisor's contract): the restored step counts
    toward NUM_TRAIN_EPOCHS, so a partially-finished run trains only
    the REMAINING epochs and a completed run's rerun is a no-op — not
    the old behavior of training the full epoch budget again."""
    import sys

    import code2vec as cli
    ckpt = str(tmp_path / "ckpt")

    # the CLI would otherwise turn this session's compile cache on
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "xla"))

    def run(epochs):
        monkeypatch.setattr(sys, "argv", [
            "code2vec.py", "--data", dataset, "--save", ckpt,
            "--epochs", str(epochs), "--batch_size", "32",
            "--max_contexts", "16", "--auto_resume",
            "--backend", "cpu"])
        assert cli.main() == 0
        from code2vec_tpu.training.checkpoint import latest_step
        return latest_step(ckpt)

    step1 = run(1)
    assert step1 and step1 > 0
    # raise the epoch budget: the rerun resumes from the checkpoint and
    # trains exactly the ONE remaining epoch
    step2 = run(2)
    assert step2 == 2 * step1
    # rerun the COMPLETED command: nothing left to train, step unchanged
    step3 = run(2)
    assert step3 == step2


def test_auto_resume_ignores_torn_checkpoint_dir(dataset, tmp_path,
                                                 monkeypatch):
    """A step dir without a committed `state` (preemption mid-save) must
    be invisible to latest_step, so auto-resume restarts cleanly."""
    import os

    from code2vec_tpu.training.checkpoint import latest_step
    ckpt = str(tmp_path / "ckpt")
    os.makedirs(os.path.join(ckpt, "step_7"))  # torn: no state/ inside
    assert latest_step(ckpt) is None

    cfg = tiny_config(dataset, NUM_TRAIN_EPOCHS=1, SAVE_EVERY_EPOCHS=1,
                      save_path=ckpt)
    model = Code2VecModel(cfg)
    model.train()
    assert latest_step(ckpt) == model.step_num  # real save is visible
