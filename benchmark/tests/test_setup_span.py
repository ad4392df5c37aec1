"""The reader of the program's set-up record: `reduce` and `read` on a
recorded list of spans against values worked out by hand, and a tiny CPU
cell run with `--trace 1`, twice in one checkout, that prints the six
metrics read through it.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""

import importlib.util
import json
import os
import types

import pytest

import bench_helpers as helpers

NEW = ("setup_model_s", "setup_compile_s", "setup_step_compile_s",
       "setup_programs", "setup_cache_misses", "recompiles_in_window")
VALUES = {name: json.load(open(os.path.join(
    helpers.REPO, "benchmark", "layer_metrics", name + ".json")))["args"]
    for name in NEW}


def _reader():
    spec = importlib.util.spec_from_file_location(
        "setup_span", os.path.join(helpers.REPO, "benchmark", "readers",
                                   "setup_span.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _span(name, t0, t1, **attrs):
    return {"name": name, "t0": t0, "t1": t1, "attrs": attrs}


def _program(fun_name, t0, nth=1, cache="hit", under=None, trace=0.1,
             lower=0.2, backend=0.7):
    """The three records of one program, one after the other from
    `t0`."""
    common = dict(fun_name=fun_name, nth=nth, under=under)
    t1, t2 = t0 + trace, t0 + trace + lower
    return [_span("compile/trace", t0, t1, **common),
            _span("compile/lower", t1, t2, **common),
            _span("compile/backend", t2, t2 + backend, cache=cache,
                  **common)]


def recorded():
    """A process as the program records it, in the order spans end.

    Set-up: a model built from t = 100 to 120 (vocabularies 2 s,
    parameters 10 s with two `_uniform` programs read from the cache and
    a `sqrt` compiled under them, the three steps 1 s; 7 s its own); then
    the harness's checked steps: the staircase `step` (5 s, a hit), a
    program of the harness's own (`grad_norms`, 2 s, a miss) and the full
    `step` at its first use (4 s, a hit). Two warm-up pops, an
    end-of-epoch marker, then the window's three pops from t = 140: a
    second after it opens the program compiles `broadcast_in_dim` (a fault: the
    window holds one program). The window is 9 s long; the reference's
    two programs come at 150 and 152 and are nobody's."""
    out = [_span("setup/vocabs", 100.0, 102.0, tokens=7, paths=5,
                 targets=3)]
    out += _program("_uniform", 103.0, under="setup/init_params")
    out += _program("_uniform", 104.0, nth=2, under="setup/init_params")
    out += _program("sqrt", 105.5, cache="miss", under="setup/init_params")
    out += [_span("setup/init_params", 102.0, 112.0, leaves=5, bytes=640),
            _span("setup/steps", 112.0, 113.0),
            _span("setup/model", 100.0, 120.0, loading=False,
                  encoder="bag")]
    out += [_span("infeed/pop_wait", 121.0, 121.5, seq=0)]
    out += _program("step", 122.0, trace=1.0, lower=1.0, backend=3.0)
    out += _program("grad_norms", 128.0, cache="miss", trace=0.5,
                    lower=0.5, backend=1.0)
    out += [_span("infeed/pop_wait", 131.0, 131.5, seq=1)]
    out += _program("step", 132.0, nth=2, trace=1.0, lower=1.0,
                    backend=2.0)
    out += [_span("infeed/pop_wait", 139.9, 139.95)]        # the marker
    out += [_span("infeed/pop_wait", 140.0, 140.5, seq=2)]
    out += _program("broadcast_in_dim", 141.0, nth=7, cache="miss")
    out += [_span("infeed/pop_wait", 143.0, 143.5, seq=3),
            _span("infeed/pop_wait", 146.0, 146.5, seq=4)]
    out += _program("follow", 150.0, cache="miss")
    out += _program("follow", 152.0, nth=2, cache="miss")
    return out


def _ctx(steps=3, seconds=9.0):
    return types.SimpleNamespace(window={"steps": steps,
                                         "seconds": seconds})


def _read(monkeypatch, records, ctx=None):
    from code2vec_tpu.obs import trace

    monkeypatch.setattr(
        trace.MemoryTracer, "records",
        lambda self, prefix="": [r for r in records
                                 if r["name"].startswith(prefix)])
    reader = _reader()
    return {name: reader.read(ctx or _ctx(), VALUES[name]) for name in NEW}


def test_read_gives_the_six_values_worked_out_by_hand(monkeypatch, capsys):
    got = _read(monkeypatch, recorded())
    # the marker's pop is part of the first window pop's wait: the window
    # opens at 139.9 and closes at 148.9
    assert got["setup_model_s"] == pytest.approx(20.0)
    # 3 x 1 s under the model, 5 + 2 + 4 s after it
    assert got["setup_compile_s"] == pytest.approx(14.0)
    assert got["setup_step_compile_s"] == pytest.approx(9.0)
    assert got["setup_programs"] == 6
    assert got["setup_cache_misses"] == 2          # sqrt, grad_norms
    assert got["recompiles_in_window"] == 1
    err = capsys.readouterr().err
    assert ("setup_span: setup/model 20.000 s: init_params 10.000, (self) "
            "7.000, vocabs 2.000, steps 1.000") in err
    assert ("compile/* before the window 14.000 s, 3.000 s of it inside "
            "setup/model") in err
    assert ("longest programs: step 9.000 s x2, _uniform 2.000 s x2, "
            "grad_norms 2.000 s x1, sqrt 1.000 s x1") in err
    assert ("recompiles: broadcast_in_dim nth 7 cache miss under None "
            "0.700 s") in err
    assert ("cache_misses: grad_norms nth 1 cache miss under None 1.000 s, "
            "sqrt nth 1 cache miss under setup/init_params 0.700 s") in err
    assert ("setup_span: step nth 1: trace 1.000 s, lower 1.000 s, backend "
            "3.000 s (cache hit)") in err
    assert "step nth 2: trace 1.000 s, lower 1.000 s, backend 2.000 s" in err
    assert "follow" not in err


def test_window_open_is_the_first_of_the_windows_pops():
    reader = _reader()
    assert reader.window_open(recorded(), 3) == 139.9
    assert reader.window_open(recorded(), 2) == 143.0
    assert reader.window_open(recorded(), 5) == 121.0
    assert reader.window_open(recorded(), 6) is None
    assert reader.window_open(recorded(), 0) is None


def test_a_shorter_window_moves_what_counts_as_set_up(monkeypatch):
    """Two steps: the window opens at 143.0, so the program compiled at
    141 is set-up's, and a 2 s window holds no compile."""
    got = _read(monkeypatch, recorded(), _ctx(steps=2, seconds=2.0))
    assert got["setup_programs"] == 7
    assert got["setup_cache_misses"] == 3
    assert got["setup_compile_s"] == pytest.approx(15.0)
    assert got["recompiles_in_window"] == 0


@pytest.mark.parametrize("records", [
    [],                                                 # an empty recorder
    [r for r in recorded() if not r["name"].startswith("setup/")],
    [r for r in recorded() if r["name"] != "infeed/pop_wait"],
], ids=["empty", "no_setup", "no_pops"])
def test_read_gives_none_without_a_setup_record_or_the_windows_pops(
        monkeypatch, records):
    assert set(_read(monkeypatch, records).values()) == {None}


def test_a_model_built_after_the_window_is_not_its_setup(monkeypatch):
    records = [r for r in recorded() if r["name"] != "setup/model"] \
        + [_span("setup/model", 160.0, 161.0, loading=False, encoder="bag")]
    assert set(_read(monkeypatch, records).values()) == {None}


def test_union_counts_an_overlap_once():
    union = _reader().union_seconds
    assert union([(3.0, 4.0), (0.0, 2.0), (1.0, 1.5), (1.5, 2.5)]) == 3.5
    assert union([]) == 0.0


def test_the_manifest_holds_the_six_under_the_entry_layer():
    with open(os.path.join(helpers.REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    mine = manifest["per_layer"][-6:]
    assert [m["name"] for m in mine] == list(NEW)
    for m in mine:
        assert "workloads" not in m             # they hold in every cell
        assert (m["layer"], m["source"], m["better"]) == (
            "entry", "program_span", "lower")
        spec = json.load(open(os.path.join(
            helpers.REPO, "benchmark", "layer_metrics",
            m["name"] + ".json")))
        assert (spec["name"], spec["unit"], spec["moves"], spec["reader"]) \
            == (m["name"], m["unit"], m["moves"], "setup_span")
    assert [m["moves"] for m in mine] == ["setup_s"] * 5 + [
        "train_methods_per_s"]


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return helpers.make_copy(str(tmp_path_factory.mktemp("bench") / "c"))


def test_traced_cell_prints_the_six_cold_and_then_warm(
        copy, tmp_path, monkeypatch):
    """The first run over an empty cache compiles, the second reads
    every program from the cache the first one wrote."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    readings = []
    for seed in (5, 6):
        rc, result, err = helpers.run_cell(copy, "tiny-bag-1", 1, trace=1,
                                           seconds=2, seed=seed)
        assert rc == 0, err[-3000:]
        m = result["metrics"]
        assert set(NEW) <= set(m), sorted(m)
        readings.append({k: m[k]["value"] for k in NEW})
        phases = result["facts"]["setup_phases_s"]
        # the harness's `model` phase is the span and a little more (the
        # program's imports, the flags, `check_config_is_run`)
        assert 0 < m["setup_model_s"]["value"] <= phases["model"]
        assert m["recompiles_in_window"]["value"] == \
            m["compiles_in_window"]["value"] == 0
        assert m["setup_step_compile_s"]["value"] \
            <= m["setup_compile_s"]["value"]
        assert "setup_span: setup/model" in err
        assert "longest programs: " in err
    cold, warm = readings
    assert cold["setup_cache_misses"] > 0
    assert warm["setup_cache_misses"] == 0
    assert warm["setup_programs"] == cold["setup_programs"] >= 10
