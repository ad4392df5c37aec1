"""Set-up in the program's own record (ISSUE 36; obs/setup_trace.py):

1. building a model leaves one `setup/model` whose children lie inside
   it, do not overlap and carry their attributes;
2. JAX's compile events become `compile/trace`, `/lower`, `/backend`
   records of one `fun_name`, a cache miss and then a hit, each inside
   a `time.monotonic()` bracket taken around the call, `under` the span
   the compiling thread held open;
3. a nested jit's trace is no program, and what a cache event says
   waits for the backend interval around it;
4. one listener a process, however often it is installed;
5. `summarize`, the operator's line, the `--trace` export and
   `tools/trace_report.py`'s "Set-up" table;
6. `code2vec.py` training logs the line once and leaves
   `setup/backend` and `setup/imports`.
"""

import logging
import time
import types

import jax
import jax.numpy as jnp
import pytest

from code2vec_tpu.obs import setup_trace, trace
from code2vec_tpu.obs.setup_trace import CompileRecorder
from code2vec_tpu.obs.trace import MemoryTracer

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
ASKED = "/jax/compilation_cache/compile_requests_use_cache"
HIT = "/jax/compilation_cache/cache_hits"
MISS = "/jax/compilation_cache/cache_misses"
RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"


@pytest.fixture
def fresh_record(monkeypatch):
    """`memory_tracer()` gives a recorder of this test's own."""
    rec = MemoryTracer()
    monkeypatch.setattr(trace, "_MEMORY_TRACER", rec)
    return rec


# ---- 1. the model's construction ---------------------------------------

@pytest.fixture(scope="module")
def dataset(tmp_path_factory):
    from tests.helpers import build_tiny_dataset
    return build_tiny_dataset(str(tmp_path_factory.mktemp("data")),
                              n_train=256, n_val=8, n_test=8,
                              max_contexts=16, binarize=True)


def model_of(prefix, data_axis):
    from code2vec_tpu.config import Config
    from code2vec_tpu.models.jax_model import Code2VecModel
    cfg = Config(MAX_CONTEXTS=16, MAX_TOKEN_VOCAB_SIZE=1000,
                 MAX_PATH_VOCAB_SIZE=1000, MAX_TARGET_VOCAB_SIZE=1000,
                 DEFAULT_EMBEDDINGS_SIZE=16, TRAIN_BATCH_SIZE=64,
                 TEST_BATCH_SIZE=32, NUM_TRAIN_EPOCHS=1, USE_BF16=False,
                 MESH_DATA_AXIS=data_axis, MESH_MODEL_AXIS=1)
    cfg.train_data_path = prefix
    cfg.VERBOSE_MODE = 0
    return Code2VecModel(cfg)


@pytest.mark.parametrize("data_axis", [1, 4])
def test_model_leaves_one_setup_model_and_its_children(
        dataset, data_axis, fresh_record):
    model = model_of(dataset, data_axis)
    records = fresh_record.records("setup/")
    (whole,) = [r for r in records if r["name"] == "setup/model"]
    assert whole["attrs"] == {"loading": False, "encoder": "bag"}
    children = sorted((r for r in records if r is not whole),
                      key=lambda r: r["t0"])
    names = [r["name"] for r in children]
    expected = ["setup/vocabs", "setup/mesh", "setup/optimizer",
                "setup/init_params", "setup/opt_init"]
    if model.mesh is not None:      # the tests' CPU devices are eight
        expected.append("setup/shard")
    assert names == expected + ["setup/staircase", "setup/steps"]
    for before, after in zip(children, children[1:]):
        assert before["t1"] <= after["t0"]              # no overlap
    assert whole["t0"] <= children[0]["t0"]
    assert children[-1]["t1"] <= whole["t1"]
    attrs = {r["name"]: r["attrs"] for r in children}
    vocabs = model.vocabs
    assert attrs["setup/vocabs"] == {
        "tokens": vocabs.token_vocab.size, "paths": vocabs.path_vocab.size,
        "targets": vocabs.target_vocab.size}
    assert attrs["setup/mesh"] == {"devices": model.mesh.devices.size}
    leaves = jax.tree_util.tree_leaves(model.params)
    assert attrs["setup/init_params"] == {
        "leaves": len(leaves), "bytes": sum(x.nbytes for x in leaves)}
    assert attrs["setup/opt_init"]["bytes"] == sum(
        x.nbytes for x in jax.tree_util.tree_leaves(model.opt_state))
    assert attrs["setup/staircase"] == {
        "rows": 64 // model._stair_groups if model._staircase else 0,
        "rectangles": len(model._staircase or ())}
    assert (model._staircase is None) == (data_axis == 4)
    # the children are the model's set-up: what is left is its own
    summary = setup_trace.summarize(records)
    assert summary["model_s"] == pytest.approx(whole["t1"] - whole["t0"])
    assert dict((n, s) for n, s, _ in summary["phases"])["(self)"] \
        == pytest.approx(summary["model_s"] - sum(
            r["t1"] - r["t0"] for r in children))


def test_varmisuse_model_names_the_same_phases(tmp_path, fresh_record):
    import re

    from code2vec_tpu.data.varmisuse_gen import write_vm_dataset
    from code2vec_tpu.models.vm_model import VarMisuseModel
    from tests.test_varmisuse import vm_config

    def extract(source):        # stands in for the native extractor
        words = re.findall(r"\w+", source)
        return ["m " + " ".join(f"{a},p{k % 7},{b}" for k, (a, b)
                                in enumerate(zip(words, words[1:])))]

    prefix = str(tmp_path / "vm")
    write_vm_dataset(prefix, n_train=40, n_val=4, n_test=4, seed=3,
                     extract=extract)
    VarMisuseModel(vm_config(prefix))
    names = [r["name"] for r in fresh_record.records("setup/")]
    assert names == ["setup/mesh", "setup/vocabs", "setup/optimizer",
                     "setup/init_params", "setup/opt_init", "setup/shard",
                     "setup/steps", "setup/model"]


# ---- 2. JAX's events, for real -----------------------------------------

@pytest.fixture
def listening(tmp_path):
    """A `CompileRecorder` of the test's own registered with
    `jax.monitoring`, over a temporary persistent cache that takes
    every program."""
    from jax._src import monitoring
    from jax.experimental.compilation_cache import compilation_cache as cc
    rec = MemoryTracer()
    recorder = CompileRecorder(rec)
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}
    for k, v in zip(keys, (str(tmp_path / "cache"), 0.0, -1)):
        jax.config.update(k, v)
    cc.reset_cache()
    jax.monitoring.register_event_time_span_listener(recorder.on_time_span)
    jax.monitoring.register_event_listener(recorder.on_event)
    jax.monitoring.register_event_duration_secs_listener(
        recorder.on_duration)
    try:
        yield rec
    finally:
        monitoring.unregister_event_time_span_listener(
            recorder.on_time_span)
        monitoring.unregister_event_listener(recorder.on_event)
        monitoring.unregister_event_duration_listener(recorder.on_duration)
        for k, v in before.items():
            jax.config.update(k, v)
        cc.reset_cache()


def test_a_program_is_three_records_a_miss_and_then_a_hit(listening):
    rec = listening

    def step(x):
        return jnp.tanh(x) @ x + 36.0

    x = jnp.ones((8, 8))
    jax.block_until_ready(x)
    fn = jax.jit(step)
    brackets = []
    for span_name in ("test/first", "test/second"):
        jax.clear_caches()          # the second call compiles anew
        with rec.start_span(span_name):
            t0 = time.monotonic()
            jax.block_until_ready(fn(x))
            brackets.append((t0, time.monotonic()))
    mine = [r for r in rec.records("compile/")
            if r["attrs"]["fun_name"] == "step"]
    assert [r["name"] for r in mine] == [
        "compile/trace", "compile/lower", "compile/backend"] * 2
    for k, (span_name, cache) in enumerate(
            (("test/first", "miss"), ("test/second", "hit"))):
        t0, t1 = brackets[k]
        three = mine[3 * k:3 * k + 3]
        for before, after in zip(three, three[1:]):
            assert before["t1"] <= after["t0"] + 1e-3
        for r in three:
            # JAX's clock is time.time(): carried over to the
            # recorder's by one paired read, good to a millisecond
            assert t0 - 2e-3 <= r["t0"] <= r["t1"] <= t1 + 2e-3, (r, t0, t1)
            assert r["attrs"]["nth"] == k + 1
            assert r["attrs"]["under"] == span_name
        backend = three[2]["attrs"]
        assert backend["cache"] == cache
        assert ("retrieval_s" in backend) == (cache == "hit")
        assert "cache" not in three[0]["attrs"]
    assert mine[5]["attrs"]["retrieval_s"] >= 0.0


def test_every_eager_operation_is_a_program_of_its_own(listening):
    with listening.start_span("test/eager"):
        jax.block_until_ready(jnp.full((3, 5), 36.5) * 2.0)
    programs = [r for r in listening.records("compile/backend")]
    assert len(programs) >= 2           # the fill, the product
    assert all(r["attrs"]["under"] == "test/eager" for r in programs)
    assert all(r["attrs"]["cache"] == "miss" for r in programs)


# ---- 3. the callbacks, by hand -----------------------------------------

def recorder_on_fake_clocks():
    """A recorder whose two clocks stand 1000 s apart."""
    now = [50.0]
    rec = MemoryTracer(clock=lambda: now[0])
    return rec, CompileRecorder(rec, wall=lambda: now[0] + 1000.0)


def test_nested_traces_are_not_programs_and_clocks_are_carried_over():
    rec, recorder = recorder_on_fake_clocks()
    for k in range(40):                 # jnp calls inside step's trace
        recorder.on_time_span(TRACE, 1010.0 + k, 1010.5 + k, fun_name="add")
    recorder.on_time_span(TRACE, 1001.0, 1051.0, fun_name="step")
    recorder.on_time_span(LOWER, 1051.0, 1060.0, fun_name="jit(step)")
    recorder.on_event(ASKED)
    recorder.on_event(MISS)
    recorder.on_time_span(BACKEND, 1060.0, 1100.0, fun_name="jit(step)")
    got = [(r["name"], r["t0"], r["t1"], r["attrs"]["fun_name"])
           for r in rec.records()]
    assert got == [("compile/trace", 1.0, 51.0, "step"),
                   ("compile/lower", 51.0, 60.0, "step"),
                   ("compile/backend", 60.0, 100.0, "step")]
    # an eager `add` later is a program, with its own trace, not the
    # last nested one (which ended after this lowering began)
    recorder.on_time_span(LOWER, 1020.0, 1021.0, fun_name="jit(add)")
    assert [r["name"] for r in rec.records()][3:] == ["compile/lower"]


@pytest.mark.parametrize("events,cache", [
    ((), "off"),                        # the cache was not asked
    ((ASKED,), "miss"),                 # asked, no hit: compiled, too
    ((ASKED, MISS), "miss"),            # small to be written back
    ((ASKED, HIT), "hit")])
def test_cache_state_waits_for_its_backend_interval(events, cache):
    rec, recorder = recorder_on_fake_clocks()
    for event in events:
        recorder.on_event(event)
    if cache == "hit":
        recorder.on_duration(RETRIEVAL, 0.25)
    recorder.on_event("/jax/compilation_cache/tasks_using_cache")
    recorder.on_duration("/jax/other", 3.0)
    recorder.on_time_span("/jax/other_span", 0.0, 1.0)
    recorder.on_time_span(BACKEND, 1001.0, 1002.0, fun_name="jit(f)")
    recorder.on_time_span(BACKEND, 1003.0, 1004.0, fun_name="jit(f)")
    first, second = rec.records()
    assert first["attrs"]["cache"] == cache
    assert first["attrs"].get("retrieval_s") == (
        0.25 if cache == "hit" else None)
    # nothing is left over for the next program, which asked nothing
    assert second["attrs"] == {"fun_name": "f", "nth": 2, "under": None,
                               "cache": "off"}


# ---- 4. one listener a process -----------------------------------------

def test_installing_twice_registers_once(monkeypatch):
    calls = []
    fake = types.SimpleNamespace(
        register_event_time_span_listener=lambda f: calls.append("span"),
        register_event_listener=lambda f: calls.append("event"),
        register_event_duration_secs_listener=lambda f: calls.append(
            "duration"))
    monkeypatch.setattr(setup_trace, "_INSTALLED", None)
    first = setup_trace.install(fake)
    assert setup_trace.install(fake) is first
    assert sorted(calls) == ["duration", "event", "span"]


def test_enable_compile_cache_installs_the_listener_once(monkeypatch):
    from jax._src import monitoring

    from code2vec_tpu.device import enable_compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x")
    enable_compile_cache()
    enable_compile_cache()
    ours = [f for f in monitoring.get_event_time_span_listeners()
            if isinstance(getattr(f, "__self__", None), CompileRecorder)
            and f.__self__ is setup_trace._INSTALLED]
    assert len(ours) == 1


# ---- 5. the summary, the line, the export ------------------------------

def _rec(name, t0, t1, **attrs):
    return {"name": name, "t0": t0, "t1": t1, "tname": "MainThread",
            "attrs": attrs}


def recorded():
    """A loading model: 10 s, of which the children cover 9; four
    programs, one of them `step` compiled after the model stands."""
    def program(fun_name, t0, nth=1, cache="hit", under="setup/init_params"):
        common = dict(fun_name=fun_name, nth=nth, under=under)
        return [_rec("compile/trace", t0, t0 + 0.1, **common),
                _rec("compile/lower", t0 + 0.1, t0 + 0.3, **common),
                _rec("compile/backend", t0 + 0.3, t0 + 1.0, cache=cache,
                     **common)]
    return [
        _rec("setup/backend", 80.0, 88.0, platform="cpu", devices=1),
        _rec("setup/imports", 88.0, 99.0),
        _rec("setup/vocabs", 100.0, 102.0, tokens=7, paths=5, targets=3),
        _rec("setup/restore", 102.0, 102.5),
        *program("_uniform", 103.0), *program("_uniform", 104.0, nth=2),
        *program("sqrt", 105.5, cache="miss"),
        _rec("setup/init_params", 102.5, 106.5, leaves=5, bytes=640),
        _rec("setup/restore", 106.5, 109.0),
        _rec("setup/model", 100.0, 110.0, loading=True, encoder="bag"),
        *program("step", 111.0, under=None),
    ]


def test_summarize_gives_the_split_worked_out_by_hand():
    s = setup_trace.summarize(recorded())
    assert s["model_s"] == pytest.approx(10.0)
    assert [(n, round(x, 6)) for n, x, _ in s["phases"]] == [
        ("init_params", 4.0), ("restore", 3.0), ("vocabs", 2.0),
        ("(self)", 1.0)]
    assert s["phases"][0][2] == {"leaves": 5, "bytes": 640}
    assert s["outside"] == [
        ("backend", 8.0, {"platform": "cpu", "devices": 1}),
        ("imports", 11.0, {})]
    assert (s["programs"], s["from_cache"], s["compiled"]) == (4, 3, 1)
    assert s["compile_s"] == pytest.approx(4.0)
    assert [(n, round(x, 6), c) for n, x, c in s["longest"]] == [
        ("_uniform", 2.0, 2), ("sqrt", 1.0, 1), ("step", 1.0, 1)]
    line = setup_trace.format_line(s, top=2)
    assert line == (
        "set-up: model 10.00 s (init_params 4.00, restore 3.00, vocabs "
        "2.00, (self) 1.00), backend 8.00 s, imports 11.00 s; 4 programs, "
        "3 from the cache, 1 compiled, 4.00 s; longest: _uniform 2.00 s "
        "(2 programs), sqrt 1.00 s")


def test_summarize_takes_the_last_model_and_none_without_one():
    assert setup_trace.summarize([]) is None
    assert setup_trace.summarize(
        [r for r in recorded() if r["name"] != "setup/model"]) is None
    later = [_rec("setup/mesh", 200.0, 200.5, devices=1),
             _rec("setup/model", 200.0, 201.0, loading=False,
                  encoder="transformer")]
    s = setup_trace.summarize(recorded() + later)
    assert s["model_attrs"]["encoder"] == "transformer"
    assert [(n, round(x, 6)) for n, x, _ in s["phases"]] == [
        ("mesh", 0.5), ("(self)", 0.5)]
    # the first model's phases are not the second one's surroundings
    assert [n for n, _, _ in s["outside"]] == ["backend", "imports"]


def test_union_seconds_counts_an_overlap_once():
    assert setup_trace.union_seconds(
        [(3.0, 4.0), (0.0, 2.0), (1.0, 1.5), (1.5, 2.5)]) == 3.5
    assert setup_trace.union_seconds([]) == 0.0


def test_export_goes_through_the_runs_tracer_to_the_setup_table(tmp_path):
    from code2vec_tpu.obs import Telemetry, Tracer
    from tools import trace_report

    assert setup_trace.export(Tracer.disabled(), recorded()) == 0
    run = Telemetry.create(str(tmp_path), component="train")
    tracer = Tracer.create(run)
    assert setup_trace.export(tracer, recorded()) == len(recorded())
    run.close()
    (run_dir,) = trace_report.find_runs(str(tmp_path))
    ((_manifest, spans),) = trace_report.load_spans([run_dir])
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    (whole,) = by_name["setup/model"]
    assert all(s["parent"] == whole["span"] and s["trace"] == whole["trace"]
               for name in ("setup/vocabs", "setup/restore",
                            "setup/init_params") for s in by_name[name])
    assert "parent" not in by_name["setup/backend"][0]
    assert {s["tname"] for s in by_name["compile/backend"]} == {"compile"}
    assert by_name["compile/backend"][2]["attrs"] == {
        "fun_name": "sqrt", "nth": 1, "under": "setup/init_params",
        "cache": "miss"}
    text = trace_report.render([(_manifest, spans)])
    assert "| Set-up | s | |" in text
    assert "| setup/model | 10.00 | loading=True encoder=bag |" in text
    assert "| setup/backend | 8.00 | platform=cpu devices=1 |" in text
    assert "| setup/imports | 11.00 |  |" in text
    assert "| - restore | 3.00 |  |" in text
    assert "| - (self) | 1.00 |  |" in text
    assert ("| compile/* | 4.00 | 4 programs, 3 from the cache, 1 "
            "compiled |") in text
    assert "| - _uniform | 2.00 | 2 programs |" in text


# ---- 6. the entry point ------------------------------------------------

def test_code2vec_training_logs_the_line_once(dataset, tmp_path, caplog,
                                              monkeypatch):
    import code2vec as cli
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "c"))
    started = time.monotonic()
    with caplog.at_level(logging.INFO):
        rc = cli.main(["--backend", "cpu", "--data", dataset,
                       "--epochs", "2", "--batch_size", "64",
                       "--max_contexts", "16", "--telemetry_dir",
                       str(tmp_path / "tele"), "--trace"])
    assert rc == 0
    lines = [r.getMessage() for r in caplog.records
             if r.getMessage().startswith("set-up: model ")]
    assert len(lines) == 1, lines
    assert " programs, " in lines[0] and "longest: " in lines[0]
    # the process's own record (its listener was installed by whichever
    # test came through `enable_compile_cache` first)
    mine = [r for r in trace.memory_tracer().records()
            if r["t0"] >= started]
    (backend,) = [r for r in mine if r["name"] == "setup/backend"]
    assert backend["attrs"] == {"platform": "cpu",
                                "devices": len(jax.devices())}
    (whole,) = [r for r in mine if r["name"] == "setup/model"]
    (imports,) = [r for r in mine if r["name"] == "setup/imports"]
    assert backend["t1"] <= imports["t0"] <= imports["t1"] <= whole["t0"]
    assert ", backend " in lines[0] and ", imports " in lines[0]
    steps = [r for r in mine if r["name"] == "compile/backend"
             and r["attrs"]["fun_name"] == "step"]
    assert steps and steps[0]["attrs"]["under"] is None
    # and the run's --trace log holds the table
    from tools import trace_report
    (run_dir,) = trace_report.find_runs(str(tmp_path / "tele"))
    text = trace_report.render(trace_report.load_spans([run_dir]))
    assert "| Set-up | s | |" in text and "| - init_params |" in text
    assert "| - step |" in text
