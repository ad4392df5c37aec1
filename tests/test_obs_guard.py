"""TF-free guard (ISSUE 2 satellite; extended for ISSUE 6): all of
`code2vec_tpu.obs` — telemetry, tracing, the stall watchdog — must
import and RUN (disabled + file-backed paths, span recording, a
fake-clock stall with its diagnostic dump) on an image with no
TensorFlow at all, and tier-1 test COLLECTION must never pull
TensorFlow in (TF is a tooling dependency, not a training one).

Both tests run subprocesses with a blocker module shadowing
`tensorflow` on PYTHONPATH, so any import attempt anywhere in the
chain fails loudly instead of silently using the locally-installed TF.
"""

import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tf_blocked_env(tmp_path, block_jax=False):
    blocker = tmp_path / "tfblock"
    blocker.mkdir(exist_ok=True)
    (blocker / "tensorflow.py").write_text(
        "raise ImportError('tensorflow blocked by test_obs_guard')\n")
    if block_jax:
        (blocker / "jax.py").write_text(
            "raise ImportError('jax blocked by test_obs_guard')\n")
    env = dict(os.environ)
    parts = [str(blocker), REPO]
    if env.get("PYTHONPATH"):
        parts.append(env["PYTHONPATH"])
    env["PYTHONPATH"] = os.pathsep.join(parts)
    env["JAX_PLATFORMS"] = "cpu"
    return env


def test_obs_imports_and_runs_without_tensorflow(tmp_path):
    code = textwrap.dedent("""
        import json, os, sys, tempfile
        import code2vec_tpu.obs as obs

        # disabled path (the --telemetry_dir-unset production default)
        t = obs.Telemetry.disabled()
        assert not t.enabled
        t.count("x"); t.record_ms("a", 1.0); t.event("k"); t.close()
        rec = obs.TrainStepRecorder(t)
        infeed = [1]
        assert rec.wrap(infeed) is infeed

        # memory + file-backed paths
        m = obs.Telemetry.memory("guard")
        m.record_ms("a", 1.0)
        assert m.timer("a").count == 1
        d = tempfile.mkdtemp()
        run = obs.Telemetry.create(d, component="guard")
        run.event("step", step=1, step_ms=1.0, infeed_wait_ms=0.0,
                  loss=0.5)

        # tracing + watchdog (ISSUE 6) ride the same no-TF/no-JAX
        # constraint: spans record, the fake-clock watchdog fires and
        # dumps, and both disabled paths are shared no-op singletons
        tr_off = obs.Tracer.disabled()
        assert tr_off.start_trace("x") is tr_off.start_span("y")
        assert obs.Watchdog.disabled().register("z").beat() is None
        tr = obs.Tracer.create(run)
        root = tr.start_trace("guard/request")
        with tr.start_span("guard/phase", parent=root.context()):
            pass
        clock = [0.0]
        wd = obs.Watchdog(run, stall_s=5.0, tracer=tr,
                          clock=lambda: clock[0])
        hb = wd.register("guard_component")
        hb.beat()
        clock[0] = 6.0
        assert wd.check_now(), "fake-clock stall did not fire"
        assert [s["name"] for s in tr.live_spans()] == \
            ["guard/request"]
        root.end()
        run.close()
        assert os.path.exists(os.path.join(run.run_dir,
                                           "manifest.json"))
        with open(os.path.join(run.run_dir, "events.jsonl")) as f:
            kinds = [json.loads(ln)["kind"] for ln in f if ln.strip()]
        assert "span" in kinds and "stall" in kinds
        assert any(fn.startswith("stall_dump")
                   for fn in os.listdir(run.run_dir))

        # the ScalarWriter fallback rides the same no-TF constraint
        from code2vec_tpu.training.scalars import ScalarWriter
        w = ScalarWriter(d)   # TF blocked -> warn-once no-op
        assert w._writer is None
        w.write(1, {"a": 1.0}); w.close()

        assert "tensorflow" not in sys.modules
        print("GUARD-OK")
    """)
    r = subprocess.run([sys.executable, "-c", code],
                       env=_tf_blocked_env(tmp_path), cwd=REPO,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "GUARD-OK" in r.stdout


def test_live_plane_serves_and_evaluates_without_jax_or_tf(tmp_path):
    """ISSUE 7 extension of the blocked-import pattern: the live
    metrics plane — exposition server, health monitors, alert engine —
    must import AND run (HTTP round-trips included) with BOTH jax and
    tensorflow import-blocked. obs/ stays a pure-stdlib layer."""
    code = textwrap.dedent("""
        import json, sys, urllib.request
        import code2vec_tpu.obs as obs
        from code2vec_tpu.obs.alerts import AlertRule
        from code2vec_tpu.obs.health import (NonFiniteGauges,
                                             default_train_monitors)

        # registry + live plane, fully in memory (no jax manifest)
        t = obs.Telemetry.memory("guard").make_threadsafe()
        t.count("train/steps", 3)
        t.record_ms("train/step_ms", 5.0)
        t.gauge("train/loss", float("nan"), emit=False)
        clock = [0.0]
        wd = obs.Watchdog(t, stall_s=5.0, clock=lambda: clock[0])
        hb = wd.register("infeed_producer"); hb.beat()
        health = obs.HealthEngine.create(t)
        health.add(*default_train_monitors())
        alerts = obs.AlertEngine.create(
            t, mode="raise",
            rules=[AlertRule("nan", metric="health/loss_nonfinite",
                             op=">=", value=1.0)])
        health.add_listener(alerts.evaluate)
        wd.attach(health=health, alerts=alerts)
        health.check_now()  # evaluates monitors, fires the rule
        try:
            alerts.poll()
            raise SystemExit("sticky AlertError never surfaced")
        except obs.AlertError:
            pass

        srv = obs.MetricsServer(t, port=0, watchdog=wd,
                                health=health, alerts=alerts).start()
        base = f"http://127.0.0.1:{srv.bound_port}"
        text = urllib.request.urlopen(base + "/metrics",
                                      timeout=5).read().decode()
        assert "train_steps 3" in text
        assert 'alert_active{rule="nan"} 1' in text
        assert 'health_status{monitor="loss_nonfinite"} 1' in text
        assert "gauge_age_seconds" in text
        v = json.load(urllib.request.urlopen(base + "/vars",
                                             timeout=5))
        assert v["counters"]["train/steps"] == 3
        assert v["alerts"][0]["state"] == "firing"
        # healthz: firing page-severity alert -> 503
        import urllib.error
        try:
            urllib.request.urlopen(base + "/healthz", timeout=5)
            raise SystemExit("healthz should be 503")
        except urllib.error.HTTPError as e:
            assert e.code == 503
        srv.stop()
        assert "jax" not in sys.modules
        assert "tensorflow" not in sys.modules
        print("LIVE-PLANE-OK")
    """)
    r = subprocess.run([sys.executable, "-c", code],
                       env=_tf_blocked_env(tmp_path, block_jax=True),
                       cwd=REPO, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "LIVE-PLANE-OK" in r.stdout


def test_fleet_collector_runs_without_jax_or_tf(tmp_path):
    """ISSUE 17 extension of the blocked-import pattern: the fleet
    plane — member exposition with /clock + identity, the cohort
    collector's handshake / scrape / straggler math — must import AND
    run (real HTTP round-trips included) with BOTH jax and tensorflow
    import-blocked. The collector runs on laptops and supervisors;
    obs/ stays a pure-stdlib layer."""
    code = textwrap.dedent("""
        import json, sys, threading, urllib.request
        import code2vec_tpu.obs as obs
        from code2vec_tpu.obs.fleet import FleetCollector

        # disabled path first: no members -> the shared no-op
        # singleton, and not one thread started
        before = len(threading.enumerate())
        off = FleetCollector.create(obs.Telemetry.memory("sup"),
                                    members=())
        off.start(); off.sample(); off.stop()
        assert not off.enabled and off.aggregate() == {}
        assert len(threading.enumerate()) == before

        # one real member endpoint (memory registry + exposition)
        m = obs.Telemetry.memory("member").make_threadsafe()
        m.count("train/steps", 4)
        m.count("train/examples", 128)
        m.gauge("train/max_contexts", 8, emit=False)
        m.record_ms("train/step_ms", 100.0)
        srv = obs.MetricsServer(
            m, port=0,
            identity={"run_id": "r-guard", "process_index": 0,
                      "process_count": 1}).start()
        ep = f"127.0.0.1:{srv.bound_port}"

        # /clock serves paired readings + identity
        c = json.load(urllib.request.urlopen(
            f"http://{ep}/clock", timeout=5))
        assert "mono" in c and "wall" in c
        assert c["identity"]["run_id"] == "r-guard"

        # supervisor-side collector: real handshake + scrape over HTTP
        sup = obs.Telemetry.memory("sup").make_threadsafe()
        fc = FleetCollector.create(sup, members=[ep],
                                   handshake_samples=3)
        agg = fc.sample()
        row = agg["hosts"][0]
        assert row["up"] and row["run_id"] == "r-guard"
        assert row["step_p50"] == 100.0
        assert row["clock_offset_s"] is not None
        assert agg["cohort"]["hosts_up"] == 1
        # /fleet serves the aggregate when a collector is attached
        fsrv = obs.MetricsServer(sup, port=0, fleet=fc).start()
        out = json.load(urllib.request.urlopen(
            f"http://127.0.0.1:{fsrv.bound_port}/fleet", timeout=5))
        assert out["cohort"]["hosts_up"] == 1
        prom = urllib.request.urlopen(
            f"http://127.0.0.1:{fsrv.bound_port}/fleet?format=prom",
            timeout=5).read().decode()
        assert "fleet_hosts_up 1.0" in prom
        fc.stop(); fsrv.stop(); srv.stop()

        assert "jax" not in sys.modules
        assert "tensorflow" not in sys.modules
        print("FLEET-GUARD-OK")
    """)
    r = subprocess.run([sys.executable, "-c", code],
                       env=_tf_blocked_env(tmp_path, block_jax=True),
                       cwd=REPO, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "FLEET-GUARD-OK" in r.stdout


def test_setup_trace_runs_without_jax_or_tf(tmp_path):
    """ISSUE 36: the set-up record is part of obs/: its listener takes
    `jax.monitoring` from the caller, so the module imports, records
    and summarises with jax and tensorflow import-blocked (the
    monitoring module here is a stand-in that replays JAX's events)."""
    code = textwrap.dedent("""
        import sys, types
        import code2vec_tpu.obs as obs
        from code2vec_tpu.obs import setup_trace

        listeners = {}
        fake = types.SimpleNamespace(
            register_event_time_span_listener=
                lambda f: listeners.setdefault("span", f),
            register_event_listener=
                lambda f: listeners.setdefault("event", f),
            register_event_duration_secs_listener=
                lambda f: listeners.setdefault("duration", f))
        assert setup_trace.install(fake) is setup_trace.install(fake)
        rec = obs.memory_tracer()
        import time
        with rec.start_span("setup/model", loading=False, encoder="bag"):
            with rec.start_span("setup/init_params"):
                now = time.time()
                listeners["span"](
                    "/jax/core/compile/jaxpr_to_mlir_module_duration",
                    now - 0.002, now - 0.001, fun_name="jit(f)")
                listeners["event"]("/jax/compilation_cache/cache_hits")
                listeners["duration"](
                    "/jax/compilation_cache/cache_retrieval_time_sec",
                    0.0005)
                listeners["span"](
                    "/jax/core/compile/backend_compile_duration",
                    now - 0.001, now, fun_name="jit(f)")
        s = setup_trace.summarize(rec.records())
        assert (s["programs"], s["from_cache"]) == (1, 1), s
        assert [n for n, _, _ in s["phases"]] == ["init_params", "(self)"]
        (backend,) = rec.records("compile/backend")
        assert backend["attrs"]["under"] == "setup/init_params"
        assert "set-up: model" in setup_trace.format_line(s)
        assert "jax" not in sys.modules
        print("SETUP-GUARD-OK")
    """)
    r = subprocess.run([sys.executable, "-c", code],
                       env=_tf_blocked_env(tmp_path, block_jax=True),
                       cwd=REPO, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "SETUP-GUARD-OK" in r.stdout


def test_tier1_collection_is_tf_free(tmp_path):
    """`pytest --collect-only` over the tier-1 selection with TF
    blocked: any test module importing TensorFlow at module scope
    fails collection here before it can fail tier-1 on a TF-free
    image."""
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "tests/", "--collect-only",
         "-q", "-m", "not slow", "-p", "no:cacheprovider"],
        env=_tf_blocked_env(tmp_path), cwd=REPO, capture_output=True,
        text=True, timeout=540)
    assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-2000:]
    assert "error" not in r.stdout.lower().splitlines()[-1]
