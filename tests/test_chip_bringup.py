"""What PR 21's chip bring-up added that a CPU can check: the compile
cache is placed from outside, --backend is a demand, a supervisor
parent stays off JAX (its children may need the chip),
predict_compile_count never answers "cannot tell", and chip_smoke.py
refuses to run anywhere but on a TPU."""

import os
import subprocess
import sys
import textwrap

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_config_restored():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_from_env_sets_nothing_in_code(monkeypatch):
    from code2vec_tpu.device import enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x")
    assert enable_compile_cache() == "/x"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_fixed_under_checkout(
        monkeypatch, cache_config_restored):
    from code2vec_tpu.device import enable_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = enable_compile_cache()
    assert first == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
    assert enable_compile_cache() == first


def test_backend_tpu_refused_on_cpu(monkeypatch, tmp_path, capsys):
    """The default --backend tpu on a machine whose JAX platform is the
    CPU: exit 2 naming the missing TPU, before any dataset is read."""
    import code2vec as cli
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    rc = cli.main(["--data", str(tmp_path / "absent"),
                   "--save", str(tmp_path / "ckpt")])
    assert rc == 2
    err = capsys.readouterr().err
    assert "--backend tpu" in err and "no tpu device" in err
    assert "--backend cpu" in err  # says how to run here on purpose


def test_supervisor_parent_never_initialises_jax(tmp_path):
    """The supervisor's children inherit its environment and may need
    the chip, which one process holds at a time: constructing a
    Supervisor, verifying a checkpoint dir and running a cohort to
    completion must leave the parent's backend untouched."""
    ckpt_dir = tmp_path / "ckpt" / "step_3" / "state"
    ckpt_dir.mkdir(parents=True)
    (ckpt_dir / "leaf").write_bytes(b"weights")
    script = textwrap.dedent(f"""
        import sys
        from jax._src import xla_bridge
        from code2vec_tpu.training import checkpoint as ckpt
        from code2vec_tpu.training.supervisor import (Supervisor,
                                                      build_cli_spawn)
        import tools.train_supervisor  # the CLI wrapper imports clean too

        d = {str(tmp_path / "ckpt")!r}
        ckpt.write_step_checksums(d, 3)
        sup = Supervisor(build_cli_spawn([sys.executable, "-c", "pass"]),
                         num_procs=1, max_restarts=0, ckpt_dir=d,
                         log=lambda _m: None)
        assert ckpt.verify_and_resolve(d) == (3, [])
        assert sup.verify_checkpoint() == 3
        assert sup.run() == 0
        assert not xla_bridge.backends_are_initialized(), \\
            "the supervisor parent touched the JAX backend"
        print("PARENT-OFF-JAX")
        """)
    r = subprocess.run([sys.executable, "-c", script], cwd=REPO,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "PARENT-OFF-JAX" in r.stdout


def test_predict_compile_count_is_a_count_or_an_error():
    from code2vec_tpu.models.jax_model import Code2VecModel
    model = Code2VecModel.__new__(Code2VecModel)
    model._predict_step = jax.jit(lambda x: x + 1)
    assert model.predict_compile_count() == 0
    model._predict_step(1.0)
    assert model.predict_compile_count() == 1
    # a step with no counter: an error, never a -1 that "-1 - -1 == 0"
    # would turn into a passed zero-new-compilations check
    model._predict_step = lambda x: x + 1
    with pytest.raises(RuntimeError, match="_cache_size"):
        model.predict_compile_count()


def test_chip_smoke_refuses_a_cpu_without_importing_the_model():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                       env=env, capture_output=True, text=True,
                       timeout=120)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert '"ok"' not in r.stdout  # no result line
    probe = textwrap.dedent("""
        import sys
        import chip_smoke
        try:
            chip_smoke.main([])
        except SystemExit:
            pass
        print("MODEL-IMPORTED" if any(m.startswith("code2vec")
                                      for m in sys.modules) else "CLEAN")
        """)
    r = subprocess.run([sys.executable, "-c", probe], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=120)
    assert "CLEAN" in r.stdout, r.stdout + r.stderr


def test_chip_smoke_verdict_line_has_exactly_the_contract_keys():
    import json

    import chip_smoke
    line = chip_smoke.verdict_line(
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    assert "\n" not in line
    assert json.loads(line) == {
        "ok": True, "device": {"platform": "tpu", "kind": "TPU v5 lite",
                               "count": 1}}
