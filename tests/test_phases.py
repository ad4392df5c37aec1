"""Phase-attribution plane (ISSUE 15): sampled phase-split profiler
(obs/phases.py + training/phase_probes.py), the PhaseRoofline health
monitor, per-phase bench gating, and the tool surface (obs_top phase
columns + counter-reset clamp, telemetry_report phase table,
trace_report --merge). All tier-1, CPU."""

import json
import os
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from code2vec_tpu.models.encoder import ModelDims, init_params
from code2vec_tpu.obs.phases import (PhaseProfiler, ProbeKit,
                                     derive_chain_phases)
from code2vec_tpu.obs.telemetry import Telemetry
from code2vec_tpu.training.phase_probes import (make_code2vec_probes,
                                                make_vm_probes)
from code2vec_tpu.training.steps import make_train_step
from tests.helpers import example_batch

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def tiny_dims(**kw):
    base = dict(token_vocab_size=50, path_vocab_size=40,
                target_vocab_size=30, embeddings_size=8,
                max_contexts=6, tables_dtype="float32")
    base.update(kw)
    return ModelDims(**base)


def _copy_tree(tree):
    return jax.tree_util.tree_map(jnp.copy, tree)


def _assert_trees_bit_equal(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert np.array_equal(np.asarray(x), np.asarray(y)), \
            "tree leaves differ bit-for-bit"


def _dense_setup(dims):
    optimizer = optax.adam(1e-2)
    params = init_params(jax.random.PRNGKey(0), dims)
    opt_state = optimizer.init(params)
    step = make_train_step(dims, optimizer)
    batch = example_batch(3, dims, batch=8)
    return optimizer, params, opt_state, step, batch


# ---- tentpole: split-vs-fused parity + derivation ----

def test_dense_split_vs_fused_bit_parity():
    """The sampled step's state update IS the fused dispatch, so
    loss/params after run_split must equal the plain fused step
    bit-for-bit — sampling can never perturb the trajectory."""
    dims = tiny_dims()
    optimizer, params, opt_state, step, batch = _dense_setup(dims)
    rng = jax.random.PRNGKey(7)
    p1, s1, loss1 = step(_copy_tree(params), _copy_tree(opt_state),
                         batch, rng)

    tele = Telemetry.memory("train")
    prof = PhaseProfiler.create(
        tele, fused_step=step,
        probes_factory=lambda: make_code2vec_probes(dims, optimizer),
        enabled=True, sample_every=1)
    p2, s2, loss2 = prof.run_split(_copy_tree(params),
                                   _copy_tree(opt_state), batch, rng,
                                   step=5)
    assert float(loss1) == loss2
    _assert_trees_bit_equal(p1, p2)
    _assert_trees_bit_equal(s1, s2)


def test_sparse_split_vs_fused_bit_parity_and_timers():
    """Same parity bar on the sparse (--sparse_embeddings) path — the
    java-large go-forward config — plus the published surface: every
    chain phase + the table_apply remainder lands a train/phase/*
    timer and one `phase` event whose device phases reconcile with the
    fused dispatch (split_sum + residual == fused)."""
    from code2vec_tpu.training.sparse_steps import init_sparse_opt_state
    dims = tiny_dims()
    dense_opt = optax.adam(1e-2)
    params = init_params(jax.random.PRNGKey(1), dims)
    opt_state = init_sparse_opt_state(params, dense_opt, True)
    step = make_train_step(dims, dense_opt, use_sampled_softmax=True,
                           num_sampled=16, sparse_updates=True,
                           learning_rate=1e-2)
    batch = example_batch(11, dims, batch=8)
    rng = jax.random.PRNGKey(3)
    p1, s1, loss1 = step(_copy_tree(params), _copy_tree(opt_state),
                         batch, rng)

    events = []
    tele = Telemetry.memory("train")
    tele.sinks = [type("S", (), {"write": lambda _s, e: events.append(e),
                                 "close": lambda _s: None})()]
    prof = PhaseProfiler.create(
        tele, fused_step=step,
        probes_factory=lambda: make_code2vec_probes(
            dims, None, use_sampled_softmax=True, num_sampled=16,
            sparse_updates=True),
        enabled=True, sample_every=1)
    p2, s2, loss2 = prof.run_split(_copy_tree(params),
                                   _copy_tree(opt_state), batch, rng,
                                   step=64, infeed_wait_ms=0.5)
    assert float(loss1) == loss2
    _assert_trees_bit_equal(p1, p2)
    _assert_trees_bit_equal(s1, s2)

    for phase in ("embed_gather", "concat_dense", "forward_pool",
                  "backward", "table_apply", "infeed_wait",
                  "fused_step"):
        stat = tele.timers.get(f"train/phase/{phase}_ms")
        assert stat is not None and stat.count == 1, phase
    ev = [e for e in events if e.get("kind") == "phase"]
    assert len(ev) == 1 and ev[0]["step"] == 64
    # the accounting identity: fused == split_sum + residual always;
    # on this remainder-attributed kit table_apply = fused - chain, so
    # the residual is just clamp slack (~0)
    assert ev[0]["fused_ms"] == pytest.approx(
        ev[0]["split_sum_ms"] + ev[0]["residual_ms"], abs=0.01)
    # zero when fused >= chain; negative only by probe jitter slack
    assert ev[0]["residual_ms"] <= 0.02
    assert ev[0]["table_apply_ms"] >= 0.0


def test_run_split_beats_and_rebases_recorder():
    """The sampled step must not leak probe time into the step-time
    plane: run_split beats the recorder after EVERY probe dispatch
    (first-sample compiles can exceed a stall deadline) and rebases
    the step window right before the fused dispatch, so the sampled
    step's train/step_ms records the fused step alone."""
    dims = tiny_dims()
    optimizer, params, opt_state, step, batch = _dense_setup(dims)

    class FakeRecorder:
        ticks = 0
        rebased = 0

        def probe_tick(self):
            FakeRecorder.ticks += 1

        def rebase_step_window(self):
            # the rebase must come AFTER all probe dispatches
            FakeRecorder.rebased += 1
            FakeRecorder.ticks_at_rebase = FakeRecorder.ticks

    tele = Telemetry.memory("train")
    prof = PhaseProfiler.create(
        tele, fused_step=step,
        probes_factory=lambda: make_code2vec_probes(dims, optimizer),
        enabled=True, sample_every=1)
    prof.run_split(_copy_tree(params), _copy_tree(opt_state), batch,
                   jax.random.PRNGKey(7), recorder=FakeRecorder())
    chain_len = len(prof._kit.chain)
    # first sample: warmup pass + measured pass each beat per probe
    assert FakeRecorder.ticks == 2 * chain_len
    assert FakeRecorder.rebased == 1
    assert FakeRecorder.ticks_at_rebase == FakeRecorder.ticks
    prof.run_split(_copy_tree(params), _copy_tree(opt_state), batch,
                   jax.random.PRNGKey(8), recorder=FakeRecorder())
    assert FakeRecorder.ticks == 3 * chain_len  # no warmup this time
    assert FakeRecorder.rebased == 2


def test_derive_chain_phases_clamps_and_diffs():
    assert derive_chain_phases(["a", "b", "c"], [2.0, 5.0, 4.0]) == [
        ("a", 2.0), ("b", 3.0), ("c", 0.0)]


def test_vm_probe_kit_runs():
    """The vm head's kit: gather → forward → backward chain, with
    table_apply riding the fused remainder — all dispatchable on the
    vm batch layout."""
    from code2vec_tpu.models.varmisuse import init_vm_params
    dims = tiny_dims()
    params = init_vm_params(jax.random.PRNGKey(0), dims)
    kit = make_vm_probes(dims)
    r = np.random.default_rng(0)
    B, C, K = 4, dims.max_contexts, 3
    batch = (r.integers(0, K, (B,)).astype(np.int32),
             r.integers(0, dims.token_vocab_size, (B, C)).astype(np.int32),
             r.integers(0, dims.path_vocab_size, (B, C)).astype(np.int32),
             r.integers(0, dims.token_vocab_size, (B, C)).astype(np.int32),
             np.ones((B, C), np.float32),
             r.integers(0, dims.token_vocab_size, (B, K)).astype(np.int32),
             np.ones((B, K), np.float32),
             np.ones((B,), np.float32))
    rng = jax.random.PRNGKey(2)
    assert [n for n, _ in kit.chain] == ["embed_gather",
                                         "forward_pool", "backward"]
    out = None
    for _name, fn in kit.chain:
        out = fn(params, batch, rng)
    loss, grads = out
    assert np.isfinite(float(loss))
    assert set(grads) == set(params)
    # apply rides the fused remainder (sampling-overhead budget)
    assert kit.apply_fn is None
    assert kit.remainder_name == "table_apply"


# ---- disabled path + cadence ----

def test_disabled_profiler_is_shared_noop():
    """PR-2 discipline: off is one boolean check — create() returns
    the shared singleton for every off-shape (flag off, dead registry,
    missing step), should_sample is always False, run_split refuses."""
    dead = Telemetry.disabled()
    live = Telemetry.memory("t")
    off = PhaseProfiler.create(live, fused_step=lambda *a: None,
                               probes_factory=lambda: None,
                               enabled=False)
    assert off is PhaseProfiler.disabled()
    assert PhaseProfiler.create(dead, fused_step=lambda *a: None,
                                probes_factory=lambda: None,
                                enabled=True) is off
    assert PhaseProfiler.create(live, enabled=True) is off
    assert not off.enabled
    assert not off.should_sample(64)
    with pytest.raises(RuntimeError):
        off.run_split(None, None, None, None)
    # and the off registry carries no phase state at all
    assert not [t for t in live.timers if t.startswith("train/phase/")]


def test_sampler_cadence_fake_clock():
    """Step-count cadence with a fake-clock min-interval rate limit:
    step 0 (the compile step) is never sampled; the interval gate
    suppresses a due step until the clock catches up."""
    clock = {"t": 100.0}
    prof = PhaseProfiler(
        Telemetry.memory("t"), fused_step=lambda *a: None,
        probes_factory=lambda: None, sample_every=4,
        min_interval_s=10.0, clock=lambda: clock["t"])
    assert not prof.should_sample(0)   # compile step, never sampled
    assert not prof.should_sample(3)
    assert prof.should_sample(4)
    prof._last_sample_t = clock["t"]   # as run_split would stamp
    clock["t"] = 105.0
    assert not prof.should_sample(8)   # due by count, too soon by clock
    clock["t"] = 111.0
    assert prof.should_sample(8)
    # no min-interval: pure step cadence
    prof2 = PhaseProfiler(Telemetry.memory("t"),
                          fused_step=lambda *a: None,
                          probes_factory=lambda: None, sample_every=2)
    assert [s for s in range(9) if prof2.should_sample(s)] == [2, 4, 6, 8]


# ---- health: PhaseRoofline monitor + /metrics rendering ----

def test_phase_roofline_monitor_and_prometheus_render():
    from code2vec_tpu.obs.exposition import render_prometheus
    from code2vec_tpu.obs.health import PhaseRoofline
    tele = Telemetry.memory("train")
    mon = PhaseRoofline()
    mon.evaluate(tele, 0.0)
    assert mon.status == "unknown"  # no sampled step yet

    # a sampled step's worth of timers + the static analytic gauges
    tele.gauge("train/phase_ceiling_gbps", 100.0, emit=False,
               static=True)
    tele.gauge("train/phase_bytes/embed_gather", 4_000_000, emit=False,
               static=True)
    for name, ms in (("embed_gather", 0.2), ("concat_dense", 0.3),
                     ("forward_pool", 0.5), ("backward", 1.0),
                     ("table_apply", 1.0), ("infeed_wait", 5.0),
                     ("fused_step", 3.0)):
        tele.record_ms(f"train/phase/{name}_ms", ms)
    mon.evaluate(tele, 1.0)
    # coverage = (0.2+0.3+0.5+1.0+1.0)/3.0 — infeed_wait excluded
    assert mon.value == pytest.approx(1.0)
    assert mon.status == "ok"
    # per-phase roofline gauge: 4 MB / 0.2 ms = 20 GB/s over 100 GB/s
    assert tele.gauges["health/phase_embed_gather"] == pytest.approx(
        0.2)
    text = render_prometheus(tele)
    assert "health_phase_embed_gather" in text
    assert "health_phase_coverage" in text
    assert "train_phase_backward_ms" in text

    # a drifting split (uncovered fused time) turns the verdict bad
    for _ in range(9):
        tele.record_ms("train/phase/fused_step_ms", 9.0)
    mon.evaluate(tele, 2.0)
    assert mon.status == "bad"


# ---- acceptance: A/B trajectory parity + mid-train scrape ----

@pytest.fixture
def cpu_has_a_peak(monkeypatch):
    """The roofline gauges divide by the published HBM peak of the
    device_kind (code2vec_tpu/device.py) and are not published for a
    kind the table does not list — 'cpu' among them. These runs test
    the gauges' plumbing, so they list it."""
    from code2vec_tpu import device
    monkeypatch.setitem(device.HBM_PEAK_GBPS, "cpu", 100.0)


@pytest.fixture(scope="module")
def tiny_prefix(tmp_path_factory):
    from tests.helpers import build_tiny_dataset
    d = tmp_path_factory.mktemp("phase_ds")
    return build_tiny_dataset(str(d), n_train=96, n_val=8, n_test=8,
                              max_contexts=16)


def test_train_ab_trajectory_bit_identical(tiny_prefix, tmp_path,
                                           cpu_has_a_peak):
    """--phase_profile off vs on (sampling every 2 steps): the final
    params are bit-identical — the off hot path is untouched AND the
    sampled steps' state updates are the fused dispatches. The on-run
    additionally persists `phase` events + train/phase timers."""
    from code2vec_tpu.models.jax_model import Code2VecModel
    from tests.test_model import tiny_config

    cfg_off = tiny_config(tiny_prefix, NUM_TRAIN_EPOCHS=2)
    m_off = Code2VecModel(cfg_off)
    m_off.train()

    tdir = str(tmp_path / "tele")
    cfg_on = tiny_config(tiny_prefix, NUM_TRAIN_EPOCHS=2,
                         TELEMETRY_DIR=tdir, PHASE_PROFILE="on",
                         PHASE_SAMPLE_EVERY=2)
    m_on = Code2VecModel(cfg_on)
    m_on.train()

    _assert_trees_bit_equal(m_off.params, m_on.params)
    run_dir = os.path.join(tdir, os.listdir(tdir)[0])
    with open(os.path.join(run_dir, "events.jsonl")) as f:
        events = [json.loads(ln) for ln in f if ln.strip()]
    phase_events = [e for e in events if e.get("kind") == "phase"]
    # 6 steps (2 epochs x 3 batches): samples at steps-into-run 2, 4
    assert len(phase_events) == 2
    summary = [e for e in events if e.get("kind") == "summary"][-1]
    assert "train/phase/fused_step_ms" in summary["timers"]
    assert summary["timers"]["train/phase/fused_step_ms"]["count"] == 2
    assert "train/phase_bytes/embed_gather" in summary["gauges"]


def test_metrics_scrape_has_health_phase_mid_train(tiny_prefix,
                                                  tmp_path,
                                                  cpu_has_a_peak):
    """Acceptance: a /metrics scrape DURING a --phase_profile run
    carries the health_phase_* roofline gauges and train_phase_*
    summaries. The run is held open by a gate after several sampled
    steps so the scrape provably happens mid-train."""
    import socket

    from code2vec_tpu.models.jax_model import Code2VecModel
    from tests.test_model import tiny_config

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    cfg = tiny_config(tiny_prefix, NUM_TRAIN_EPOCHS=6,
                      TELEMETRY_DIR=str(tmp_path / "tele"),
                      PHASE_PROFILE="on", PHASE_SAMPLE_EVERY=2,
                      HEALTH_EVERY_S=0.05)
    cfg.METRICS_PORT = port
    model = Code2VecModel(cfg)

    orig_step = model._train_step
    gate = threading.Event()
    calls = []

    def gated_step(params, opt_state, batch, rng):
        calls.append(1)
        if len(calls) == 6:
            gate.wait(timeout=60)
        return orig_step(params, opt_state, batch, rng)

    model._train_step = gated_step
    err = []

    def run():
        try:
            model.train()
        except BaseException as e:
            err.append(e)

    trainer = threading.Thread(target=run, daemon=True)
    trainer.start()
    try:
        deadline = time.time() + 120
        seen = None
        while time.time() < deadline:
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/metrics",
                        timeout=1.0) as resp:
                    body = resp.read().decode("utf-8")
            except (urllib.error.URLError, OSError):
                time.sleep(0.05)
                continue
            if "health_phase_embed_gather" in body \
                    and "train_phase_fused_step_ms" in body:
                seen = body
                break
            time.sleep(0.05)
        assert seen is not None, \
            "never scraped health_phase_* mid-train"
        assert "health_phase_coverage" in seen
        assert "train_phase_table_apply_ms" in seen
    finally:
        gate.set()
        trainer.join(timeout=120)
    assert not err, f"train thread failed: {err}"


def test_phase_profile_config_verify():
    from code2vec_tpu.config import Config
    with pytest.raises(ValueError, match="phase_profile"):
        Config(PHASE_PROFILE="sometimes", load_path="x").verify()
    with pytest.raises(ValueError, match="phase_sample_every"):
        Config(PHASE_SAMPLE_EVERY=0, load_path="x").verify()
    with pytest.raises(ValueError, match="live registry"):
        Config(PHASE_PROFILE="on", load_path="x").verify()
    Config(PHASE_PROFILE="on", METRICS_PORT=9100,
           load_path="x").verify()
    Config(PHASE_PROFILE="on", TELEMETRY_DIR="/tmp/t",
           load_path="x").verify()


# ---- bench gate: single-phase regression vs headline-only ----

def test_bench_regression_catches_single_phase_2x():
    """Acceptance: the injected single-phase 2x regression fixture
    exits 1 under the default (phase-gated) metric set while the
    headline-only check would have passed."""
    from tools.bench_regression import DEFAULT_METRICS, run
    fixture = os.path.join(REPO, "tests", "bench_fixtures",
                           "phase_regress")
    rc, rows = run(fixture, list(DEFAULT_METRICS), band=0.05,
                   window=5, min_history=2, strict=False)
    assert rc == 1
    by = {r["metric"]: r for r in rows}
    assert by["phase_backward_ms"]["status"] == "REGRESSION"
    assert by["phase_backward_ms"]["lower_is_better"] is True
    assert by["value"]["status"] == "ok"
    # headline-only: the regression sails through — the reason the
    # per-phase gate exists
    rc2, _ = run(fixture, ["value", "sparse_pc_per_sec"], band=0.05,
                 window=5, min_history=2, strict=False)
    assert rc2 == 0


def test_bench_regression_gates_unlisted_phase_keys(tmp_path):
    """A phase key OUTSIDE the PHASE_MS_METRICS literals (a future
    mesh capture's phase_allreduce_ms, the int8 backward_apply
    remainder) is auto-discovered from the rounds and gated
    lower-is-better — no phase escapes the gate the docs promise."""
    from tools.bench_regression import run
    base = {"metric": "path-contexts/sec/chip", "value": 6.6e6,
            "phase_backward_apply_ms": 8.0}
    for n in (1, 2, 3):
        (tmp_path / f"BENCH_r0{n}.json").write_text(json.dumps(base))
    bad = dict(base)
    bad["phase_backward_apply_ms"] = 16.0  # 2x, headline steady
    (tmp_path / "BENCH_r04.json").write_text(json.dumps(bad))
    rc, rows = run(str(tmp_path), ["value"], band=0.05, window=5,
                   min_history=2, strict=False, auto_phases=True)
    assert rc == 1
    by = {r["metric"]: r for r in rows}
    assert by["phase_backward_apply_ms"]["status"] == "REGRESSION"
    assert by["value"]["status"] == "ok"
    # an explicit metric list is respected as given (the CLI passes
    # auto_phases only for default-set runs)
    rc2, _ = run(str(tmp_path), ["value"], band=0.05, window=5,
                 min_history=2, strict=False)
    assert rc2 == 0


def test_bench_regression_phase_direction_is_lower_better():
    from tools.bench_regression import (PHASE_MS_METRICS,
                                        _lower_is_better)
    for m in PHASE_MS_METRICS:
        assert _lower_is_better(m)
    assert _lower_is_better("recovery_seconds")
    assert not _lower_is_better("value")
    assert not _lower_is_better("phase_sum_bytes")


def test_tool_phase_order_copies_match_canonical():
    """obs_top and telemetry_report carry literal copies of
    PHASE_ORDER (+ the trailing fused_step timer) so they stay
    runnable with nothing installed — this pin is what keeps the
    copies from drifting when a phase is added."""
    from code2vec_tpu.obs.phases import DEVICE_PHASES, PHASE_ORDER
    from tools.obs_top import _PHASE_ORDER as top_order
    from tools.telemetry_report import _PHASE_ORDER as report_order
    canonical = PHASE_ORDER + ("fused_step",)
    assert tuple(top_order) == canonical
    assert tuple(report_order) == canonical
    assert set(DEVICE_PHASES) <= set(PHASE_ORDER)


# ---- obs_top: counter-reset clamp + phase columns ----

def _fake_metrics(steps, examples, phases=None):
    text = [f"train_steps {steps}", f"train_examples {examples}",
            "train_max_contexts 16"]
    for name, p50 in (phases or {}).items():
        text.append(f'train_phase_{name}_ms{{quantile="0.5"}} {p50}')
    return "\n".join(text) + "\n"


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


def test_obs_top_phase_columns(monkeypatch):
    import tools.obs_top as obs_top
    phases = {"embed_gather": 4.1, "backward": 9.3, "fused_step": 30.2}
    feed = [_fake_metrics(10, 320, phases),
            _fake_metrics(20, 640, phases)]

    def fake_scrape(endpoint, timeout_s=3.0):
        return obs_top.parse_prometheus(feed.pop(0))

    monkeypatch.setattr(obs_top, "scrape", fake_scrape)
    st = obs_top.EndpointState("h:1")
    st.poll(60.0)
    row = st.poll(60.0)
    assert row["phases"] == phases
    out = obs_top.render([row])
    assert "embed_gather" in out and "backward" in out
    assert "9.300" in out
    # a host without phase summaries renders no phase table
    assert obs_top.render_phases([{"endpoint": "x", "phases": {}}]) == []


# ---- telemetry_report phase table + trace_report --merge ----

def _write_run(d, manifest, events):
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, "manifest.json"), "w") as f:
        json.dump(manifest, f)
    with open(os.path.join(d, "events.jsonl"), "w") as f:
        for e in events:
            f.write(json.dumps(e) + "\n")


def test_telemetry_report_phase_table(tmp_path):
    from tools.telemetry_report import phase_rows, render
    run_dir = str(tmp_path / "run-1")
    events = [
        {"kind": "phase", "ts": 1.0, "step": 64, "fused_ms": 30.0,
         "split_sum_ms": 29.0, "residual_ms": 1.0,
         "embed_gather_ms": 4.0, "backward_ms": 9.0,
         "table_apply_ms": 7.0},
        {"kind": "phase", "ts": 2.0, "step": 128, "fused_ms": 31.0,
         "split_sum_ms": 30.0, "residual_ms": 1.0,
         "embed_gather_ms": 4.2, "backward_ms": 9.4,
         "table_apply_ms": 7.1},
        {"kind": "summary",
         "gauges": {"train/phase_bytes/embed_gather": 1_000_000_000,
                    "train/phase_ceiling_gbps": 500.0}},
    ]
    _write_run(run_dir, {"run_id": "run-1", "component": "train",
                         "process_index": 0, "process_count": 1},
               events)
    gauges = events[-1]["gauges"]
    rows = phase_rows(events, gauges)
    by = {r["phase"]: r for r in rows}
    assert by["embed_gather"]["n"] == 2
    # 1 GB / 4.0 ms (nearest-rank p50 of [4.0, 4.2]) = 250 GB/s
    assert by["embed_gather"]["gbps"] == pytest.approx(250.0, abs=1.0)
    assert by["embed_gather"]["vs_ceiling"] == pytest.approx(
        0.5, abs=0.01)
    assert "fused_step" in by and by["fused_step"]["n"] == 2
    # derived-only keys never masquerade as phases
    assert "split_sum" not in by and "residual" not in by
    out = render([run_dir])
    assert "| Phase | samples |" in out
    assert "embed_gather" in out


def test_trace_report_merge_cohort(tmp_path, capsys):
    from tools.trace_report import main, write_chrome_trace
    spans0 = [{"kind": "span", "trace": "t0", "span": "s0",
               "name": "train/step_cycle", "t0": 100.0, "dur_ms": 5.0,
               "tid": 1, "tname": "main", "attrs": {"step": 1}}]
    spans1 = [{"kind": "span", "trace": "t1", "span": "s1",
               "name": "train/step_cycle", "t0": 900.0, "dur_ms": 5.0,
               "tid": 1, "tname": "main", "attrs": {"step": 1}}]
    d0 = str(tmp_path / "r0")
    d1 = str(tmp_path / "r1")
    _write_run(d0, {"run_id": "run-p0", "component": "train",
                    "process_index": 0, "process_count": 2,
                    "created_unix": 1000.0}, spans0)
    _write_run(d1, {"run_id": "run-p1", "component": "train",
                    "process_index": 1, "process_count": 2,
                    "created_unix": 1002.5}, spans1)
    out = str(tmp_path / "merged.json")
    write_chrome_trace([d0, d1], out, merge=True)
    with open(out) as f:
        trace = json.load(f)["traceEvents"]
    names = [(e["name"], e.get("pid")) for e in trace]
    assert ("process_name", 0) in names and ("process_name", 1) in names
    # wall-clock alignment: p1's span starts ~2.5 s after p0's (each
    # run's own monotonic base is meaningless across processes)
    e0 = next(e for e in trace
              if e["name"] == "train/step_cycle" and e["pid"] == 0)
    e1 = next(e for e in trace
              if e["name"] == "train/step_cycle" and e["pid"] == 1)
    assert e1["ts"] - e0["ts"] == pytest.approx(2.5e6, abs=1.0)
    notes = [e for e in trace if e["name"] == "clock_note"]
    assert len(notes) == 2
    assert "monotonic" in notes[0]["args"]["note"]
    # unmerged export stays byte-compatible: no metadata injected
    out2 = str(tmp_path / "flat.json")
    write_chrome_trace([d0, d1], out2)
    with open(out2) as f:
        flat = json.load(f)["traceEvents"]
    assert not [e for e in flat if e["name"] in ("process_name",
                                                 "clock_note")]
    # --merge without --chrome: usage error, not a silent non-merge
    assert main(["--merge", d0, d1]) == 2
    capsys.readouterr()
