"""Traffic kind `train_corpus_ref`: `train_corpus`'s training job for a
configuration that brings its own reference.

What `kinds/train_corpus.py` does, with three differences: the reference
module is the one the configuration names (`reference.module`, a file of
`benchmark/`), the block's stated sizes are checked against what the
program built, and the window's routed rows, read from the program's
`moe/route` records, stand in `window` for the counts and readers. The
configuration's file is also the program's `--lfm_config`: one file holds
the sizes that are stated and the sizes that are run. Everything else is
imported from `train_corpus.py`; only `run` is a copy.

`--readings all` adds the block's own planted faults to the accepted
kind's (one held expert left out, the selection bias added to p, the
causal mask dropped, the tables left unchanged).
"""

from __future__ import annotations

import glob
import importlib
import importlib.util
import os
import shutil
import sys
import time

import numpy as np

import corpus as corpus_mod


def _sibling(name: str):
    spec = importlib.util.spec_from_file_location(
        f"benchmark_kinds_{name}",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


base = _sibling("train_corpus")

# the keys of the model's config.json that the block reads
BLOCK_KEYS = ("layer_types", "num_dense_layers", "hidden_size",
              "intermediate_size", "moe_intermediate_size",
              "num_attention_heads", "num_key_value_heads", "num_experts",
              "num_routed_experts", "first_expert", "num_experts_per_tok",
              "conv_L_cache", "norm_eps")


def block_sizes(config: dict) -> dict:
    sizes = {k: config[k] for k in BLOCK_KEYS}
    sizes["rope_theta"] = float(config["rope_parameters"]["rope_theta"])
    return sizes


def check_config_is_run(config: dict, cfg, dims) -> None:
    """`train_corpus.check_config_is_run` for the sizes the product
    shares, then the block's: each stated size is the one the program
    built, and the depth the file states is the depth it lists."""
    base.check_config_is_run(config, cfg, dims)
    built = dims.lfm
    for k, v in block_sizes(config).items():
        got = getattr(built, k)
        got = list(got) if isinstance(got, tuple) else got
        if got != v:
            raise RuntimeError(f"configuration {config['name']!r} states "
                               f"{k}={v!r} and the program built {got!r}")
    if config["num_hidden_layers"] != len(config["layer_types"]):
        raise RuntimeError("num_hidden_layers is not the length of "
                           "layer_types")
    warm = config["train"].get("warmup_steps", 0)
    if cfg.LR_WARMUP_STEPS != warm:
        raise RuntimeError(f"configuration {config['name']!r} states "
                           f"warmup_steps={warm} and the program built "
                           f"{cfg.LR_WARMUP_STEPS}")


def reference_spec(config: dict, steps_per_epoch: int) -> dict:
    return dict(base.reference_spec(config, steps_per_epoch),
                lr_warmup_steps=config["train"].get("warmup_steps", 0),
                **block_sizes(config))


def routed_rows(steps: int):
    """The window's steps in the program's `moe/route` records, its last
    `steps` (the caller flushed the recorder after the window's last
    sync): step by step the rows routed to held experts and the valid
    tokens. None where the record does not hold them."""
    from code2vec_tpu.obs.trace import memory_tracer

    records = memory_tracer().records("moe/route")
    if not steps or len(records) < steps:
        return None
    attrs = [r["attrs"] for r in records[-steps:]]
    return {"rows_here": [a["rows_here"] for a in attrs],
            "valid_tokens": [a["valid_tokens"] for a in attrs]}


def run(ctx) -> dict:
    import jax

    config, traffic, chips = ctx.config, ctx.traffic, ctx.cell["chips"]
    reference = importlib.import_module(config["reference"]["module"])
    model_sizes = config["model"]
    batch = config["train"]["batch_per_chip"] * chips
    steps_per_epoch = traffic["steps_per_epoch"]
    num_methods = batch * steps_per_epoch
    check_steps = traffic["check_steps"]
    seed = ctx.seed % (2 ** 31 - 1)      # the program's --seed is a key
    compiles = base._count_compiles()
    phases = {}
    t_phase = [time.time()]

    def phase(name):
        now = time.time()
        phases[name] = now - t_phase[0]
        t_phase[0] = now

    phases["process_start_to_kind"] = time.time() - ctx.t_start
    t_phase[0] = time.time()

    # -- corpus --
    prefix = os.path.join(ctx.workdir, "corpus")
    vocab = {k: model_sizes[k] for k in ("tokens", "paths", "targets")}
    base._ensure_dict(ctx, vocab, num_methods, prefix)
    corpus_facts = corpus_mod.write_corpus(
        prefix, seed=seed, num_methods=num_methods, vocab=vocab,
        max_contexts=model_sizes["max_contexts"], ids_law=traffic["ids"],
        lengths_law=traffic["lengths"])
    phase("corpus")

    # -- the program --
    from code2vec_tpu.config import Config
    from code2vec_tpu.models.jax_model import Code2VecModel

    config_file = next(c["file"] for c in ctx.manifest["configs"]
                       if c["name"] == config["name"])
    cfg = Config.load_from_args(
        base.program_argv(config, prefix, batch, seed,
                          ctx.device["platform"])
        + ["--lfm_config", os.path.join(ctx.root, config_file)])
    cfg.VERBOSE_MODE = 0
    model = Code2VecModel(cfg)
    check_config_is_run(config, cfg, model.dims)
    phase("model")

    drive = base.Drive(model, cfg, lag=traffic["steps_in_flight"],
                       count_contexts=ctx.trace)
    grad_norms_of, change_norms_of = base.make_state_readers(model.params)
    before = jax.device_get(model.params)   # waits on the host
    got = {"losses": []}
    host_batches = []
    for i in range(check_steps):
        host, loss = drive.step()
        host_batches.append(tuple(
            np.array(a) for a in model._host_batch_arrays(host)))
        got["losses"].append(loss)
        if i == 0:
            # to the host at once: 1.8 GB of gradient does not ride on
            # the device through the next steps
            got["grad_norms"], got["dense_grads"] = jax.device_get(
                grad_norms_of(model.opt_state, model.params))
    drive.sync()
    before = jax.tree_util.tree_map(
        lambda h, p: jax.device_put(h, p.sharding), before, model.params)
    got["change_norms"] = change_norms_of(model.params, before)
    del before                      # step 4 keeps no copy
    got = jax.device_get(got)
    got = {"losses": [float(x) for x in got["losses"]],
           "grad_norms": {k: float(v) for k, v in got["grad_norms"].items()},
           "change_norms": {k: float(v)
                            for k, v in got["change_norms"].items()},
           "dense_grads": got["dense_grads"]}
    for _ in range(traffic.get("warm_steps", 1)):
        drive.step()
    drive.sync()
    phase("first_steps")

    # -- the window --
    seconds = ctx.seconds
    if ctx.trace:
        seconds = min(seconds, traffic["trace_seconds"])
        trace_dir = os.path.join(ctx.workdir, "trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    drive.reset_counts()
    compiles_before = compiles[0]
    setup_s = time.time() - ctx.t_start
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench/window"):
        while time.perf_counter() - t0 < seconds:
            drive.step()
        with jax.profiler.TraceAnnotation("bench/final_wait"):
            drive.sync()
    window_s = time.perf_counter() - t0
    compiles_in_window = compiles[0] - compiles_before
    if ctx.trace:
        jax.profiler.stop_trace()
    window = {"seconds": window_s, "steps": drive.steps,
              "methods": drive.methods, "contexts": drive.contexts,
              "contexts_sq": drive.contexts_sq,
              "infeed_wait_s": drive.infeed_wait_s,
              "dispatch_s": drive.dispatch_s,
              "compiles": compiles_in_window, "batch": batch,
              "chips": chips}
    # the window is closed and synced: the last steps' route counts are
    # read with no wait of their own
    recorder = getattr(model._train_step, "route_recorder", None)
    if recorder is not None:
        recorder.flush()
    routed = routed_rows(drive.steps)
    if routed is not None:
        window["routed_rows"] = sum(routed["rows_here"])
        window["valid_tokens"] = sum(routed["valid_tokens"])
    memory = base.device_memory(ctx)

    # -- free the program, then the reference --
    drive.close()
    model.params = model.opt_state = None
    del drive, model
    t_ref = time.time()
    spec = reference_spec(config, steps_per_epoch)
    block = config["reference"]["block"]
    ref = reference.follow(seed, spec, host_batches, block=block)
    verdict = base.compare(got, ref)
    limits = config["correct"]["limits"]
    judged = base.judge(verdict["numbers"], limits)
    reference_s = time.time() - t_ref
    facts = {"setup_phases_s": phases, "window": window, "memory": memory,
             "corpus": corpus_facts, "reference_s": reference_s,
             "worst_leaf": verdict["worst_leaf"],
             "left_out_of_change": verdict["left_out_of_change"],
             "not_compared": judged["not_compared"],
             "route_by_step": routed,
             "reference": {k: v for k, v in ref.items()
                           if k != "dense_grads"},
             "program": {k: v for k, v in got.items()
                         if k != "dense_grads"}}

    if ctx.args.readings != "run":
        facts["readings"] = other_readings(
            reference, seed, spec, host_batches, block, ref, limits=limits,
            control=config["correct"]["control"],
            faults=ctx.args.readings == "all")
        for name, r in facts["readings"].items():
            print(f"reading {name}: correct {r['correct']}"
                  f" (over: {', '.join(r['over']) or 'none'})",
                  file=sys.stderr)

    ctx.window = window
    ctx.model_sizes = dict(model_sizes, **block_sizes(config))
    for leftover in glob.glob(prefix + ".train.bin*"):
        os.remove(leftover)

    return {"correct": judged["correct"],
            "attempted": window["steps"] + check_steps,
            "failed": 0, "memory_peak_bytes": memory["peak_bytes"],
            "end_to_end": {
                "train_methods_per_s": window["methods"] / window_s,
                "setup_s": setup_s},
            "compared": judged["compared"], "facts": facts,
            "trace_dir": trace_dir if ctx.trace else None}


def other_readings(reference, seed, spec, host_batches, block, ref, *,
                   limits: dict, control: str, faults: bool) -> dict:
    """`train_corpus.other_readings` with this configuration's reference:
    the control and each planted fault read against the float32 reference
    by the run's own comparison, judge and limits. Every one has to come
    out as not correct."""
    def read(other):
        numbers = base.compare(other, ref)["numbers"]
        verdict = base.judge(numbers, limits)
        return {"numbers": numbers, "correct": verdict["correct"],
                "over": verdict["over"]}

    def follow(**kw):
        return reference.follow(seed, spec, host_batches, block=block, **kw)

    out = {"control_" + control: read(follow(quant=control))}
    if not faults:
        return out
    half = []
    for b in host_batches:
        w = b[5].copy()
        w[len(w) // 2:] = 0.0
        half.append(w)
    out["fault_half_batch"] = read(follow(weights=half))
    for fault in reference.FAULTS:
        out["fault_" + fault] = read(follow(fault=fault))
    out["fault_state_unchanged"] = read(
        dict(ref, change_norms={k: 0.0 for k in ref["change_norms"]}))
    # the tables alone: Adafactor's walk over them skipped
    out["fault_tables_unchanged"] = read(dict(ref, change_norms={
        k: 0.0 if k in reference.TABLES else v
        for k, v in ref["change_norms"].items()}))
    return out
