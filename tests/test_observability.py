"""Existing observability corners (ISSUE 2 satellites): StepProfiler
when the run ends before the trace window opens, and ScalarWriter's
no-op + missing-TensorFlow fallback. All tier-1, CPU, TF-free."""

import logging
import sys

from code2vec_tpu.training.profiler import StepProfiler


def test_step_profiler_finish_before_window_start():
    msgs = []
    p = StepProfiler("/tmp/never-written", start_step=100, num_steps=5,
                     log=msgs.append)
    # a run shorter than PROFILE_START_STEP: tick never opens the trace
    p.tick(0, None)
    p.tick(1, None)
    assert not p._active
    p.finish(None)  # must not call jax.profiler.stop_trace / crash
    assert any("no trace written" in m for m in msgs)
    assert p._done
    p.finish(None)  # idempotent: says it once
    assert sum("no trace written" in m for m in msgs) == 1


def test_step_profiler_disabled_is_inert():
    p = StepProfiler(None, start_step=0, num_steps=5)
    p.tick(0, None)
    p.finish(None)  # no profile dir: never logs, never traces
    assert p._done and not p._active


def test_scalar_writer_none_dir_is_noop():
    from code2vec_tpu.training.scalars import ScalarWriter
    w = ScalarWriter(None)
    w.write(1, {"train/loss": 1.0})  # must not raise, must not need TF
    w.close()
    assert w._writer is None


def test_scalar_writer_missing_tf_degrades_to_warn_once(
        tmp_path, monkeypatch, caplog):
    import code2vec_tpu.training.scalars as scalars_mod

    # None in sys.modules makes `import tensorflow` raise ImportError
    # ("import halted") — the no-TF container image, simulated
    monkeypatch.setitem(sys.modules, "tensorflow", None)
    monkeypatch.setattr(scalars_mod, "_WARNED_MISSING_TF", False)
    with caplog.at_level(logging.WARNING, logger="code2vec-tpu"):
        w = scalars_mod.ScalarWriter(str(tmp_path))
        assert w._writer is None  # degraded, not raised
        w.write(1, {"train/loss": 1.0})
        w.close()
        w2 = scalars_mod.ScalarWriter(str(tmp_path))
        assert w2._writer is None
    warnings = [r for r in caplog.records
                if "TensorFlow" in r.getMessage()]
    assert len(warnings) == 1  # warn-once across constructions


def test_scalar_writer_warn_latch_suppresses_log_only():
    # the latch only suppresses repeat WARNINGs; construction still
    # attempts the TF import every time, so a later writer in an image
    # WITH TensorFlow works regardless of earlier failures
    import code2vec_tpu.training.scalars as scalars_mod
    w = scalars_mod.ScalarWriter(None)
    assert w._writer is None


# ---- tools/obs_top.py: a counter that went backward ----

def _fake_metrics(steps, examples):
    return (f"train_steps {steps}\ntrain_examples {examples}\n"
            "train_max_contexts 16\n")


def test_obs_top_counter_reset_clamps_and_annotates(monkeypatch):
    import tools.obs_top as obs_top
    feed = [_fake_metrics(1000, 32000), _fake_metrics(5, 160)]

    def fake_scrape(endpoint, timeout_s=3.0):
        return obs_top.parse_prometheus(feed.pop(0))

    monkeypatch.setattr(obs_top, "scrape", fake_scrape)
    st = obs_top.EndpointState("h:1")
    st.poll(60.0)
    row = st.poll(60.0)
    # supervisor restart zeroed the counters: no negative rates, the
    # row says why
    assert row["steps_s"] is not None and row["steps_s"] >= 0
    assert row["ex_s"] is not None and row["ex_s"] >= 0
    assert "train_steps" in row["restarted"]
    out = obs_top.render([row])
    assert "RESTARTED" in out
    assert "-" + "1" not in out.replace("|---", "")  # no negative cell
