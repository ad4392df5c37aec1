"""Traffic kind `train_corpus_block`: `train_corpus`'s training job for a
configuration whose encoder is a decoder block with a file of sizes of its
own, whichever block that is.

What `kinds/train_corpus_ref.py` does for LFM2-MoE, with the block's names
taken from the configuration and not written here:

  reference.module   the file of `benchmark/` that holds the plain reference
                     (`follow`, `FAULTS`, `TABLES`)
  block.dims         the attribute of the program's `ModelDims` that holds
                     the block's sizes
  block.keys         the keys of the configuration whose stated values have
                     to be the ones the program built
  block.expert_leaves  the last names of the leaves that stack the held
                     experts along their first axis (none: no such leaf)
  block.choice_leaves  the last names of the leaves whose gradient hangs
                     on the router's choice among near-tied scores: the
                     router, the experts it feeds and what else reads the
                     feed-forward's input (none: every leaf is compared
                     under one limit, as `train_corpus.compare` does)
  counts             the file of `benchmark/counts/` with the step's
                     operations

The configuration's file is also the program's `--block_config`: one file
holds the sizes that are stated and the sizes that are run. The window's
routed rows (the program's `moe/route` records) and scanned chunks (its
`gdn/scan` records, where the block scans a state) stand in `window` for
the counts and readers, and `model_sizes` holds what
`counts/expert_mm.py` reads (`layer_types` as the program built them,
`num_dense_layers` 0 unless the file states one). Everything else is
imported from `train_corpus.py` and `train_corpus_ref.py`; `run` and
`other_readings` are copies (the accepted ones take no comparison of the
caller's).

The comparison is `train_corpus.compare`'s, with its two worst-leaf
numbers of the first gradient taken apart by the kind of leaf
(`compare`): `grad_norm_gap` and `dense_grad_diff` over the leaves
outside `block.choice_leaves`, `choice_norm_gap` and `choice_grad_diff`
over those leaves, each pair under limits of its own. A handful of
tokens carry half of a leaf's gradient here (short bags under a peaked
pool), and a bfloat16 step chooses another tenth expert than the
float32 reference for a few tokens in a hundred: when one of the heavy
ones does, a whole stack of experts moves by a fifth of its norm while
the mixers' leaves move by a twentieth (PERF.md section 2). One number
more, `expert_norm_gap`: the held experts of a layer are stacked leaves,
and one of 32 left out moves such a leaf's whole gradient by sqrt(1/32),
under what a sound bfloat16 step moves it; taken by expert, the missing
one's norm reads about 1.

`--readings all` adds the reference's own planted faults (its `FAULTS`)
to the accepted kind's.
"""

from __future__ import annotations

import glob
import importlib
import os
import shutil
import sys
import time

import numpy as np

import corpus as corpus_mod


def _sibling(name: str):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        f"benchmark_kinds_{name}",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref_kind = _sibling("train_corpus_ref")
base = ref_kind.base


def block_sizes(config: dict) -> dict:
    return {k: config[k] for k in config["block"]["keys"]}


def check_config_is_run(config: dict, cfg, dims) -> None:
    """`train_corpus.check_config_is_run` for the sizes the product
    shares, then the block's: each stated size is the one the program
    built."""
    base.check_config_is_run(config, cfg, dims)
    built = getattr(dims, config["block"]["dims"], None)
    if built is None:
        raise RuntimeError(f"configuration {config['name']!r} names the "
                           f"block's sizes dims.{config['block']['dims']}, "
                           "which the program did not build")
    for k, v in block_sizes(config).items():
        got = getattr(built, k, None)
        got = list(got) if isinstance(got, tuple) else got
        if got != v:
            raise RuntimeError(f"configuration {config['name']!r} states "
                               f"{k}={v!r} and the program built {got!r}")
    warm = config["train"].get("warmup_steps", 0)
    if cfg.LR_WARMUP_STEPS != warm:
        raise RuntimeError(f"configuration {config['name']!r} states "
                           f"warmup_steps={warm} and the program built "
                           f"{cfg.LR_WARMUP_STEPS}")


def reference_spec(config: dict, steps_per_epoch: int) -> dict:
    return dict(base.reference_spec(config, steps_per_epoch),
                lr_warmup_steps=config["train"].get("warmup_steps", 0),
                **block_sizes(config))


def expert_norm_gap(got: dict, ref: dict, last_names) -> tuple:
    """(the number, its layer and expert): `grad_norm_gap` by expert.
    Over the layers whose dense first gradient holds leaves that stack
    the held experts (`last_names`), and over the experts, the gap
    between the two norms of the expert's slices of those leaves taken
    together, against that norm in the reference or the median expert's
    of the layer, whichever is larger. An expert the reference does not
    compute (its slices zero there) reads the program's norm over the
    median: about 1."""
    layers = {}
    for name in ref:
        parent, _, last = name.rpartition("/")
        if last in last_names:
            layers.setdefault(parent, []).append(name)
    worst, where = 0.0, None
    for parent, names in layers.items():
        held = ref[names[0]].shape[0]

        def norms(grads):
            return np.sqrt(sum(
                np.sum(np.square(np.asarray(grads[n], np.float32)
                                 .reshape(held, -1)), axis=1)
                for n in names))

        want, have = norms(ref), norms(got)
        gaps = np.abs(have - want) / np.maximum(want, np.median(want))
        e = int(np.argmax(gaps))
        if gaps[e] >= worst:
            worst, where = float(gaps[e]), f"{parent}[{e}]"
    return worst, where


def by_leaf(got: dict, ref: dict) -> dict:
    """`train_corpus.compare`'s three worst-leaf numbers, leaf by leaf
    (the same measures: a leaf's gap or difference against the
    reference's norm of that leaf or the median leaf's, whichever is
    larger), so that a run over a limit says of every leaf how far it
    stood, not of the worst alone."""
    import statistics

    g_ref, c_ref = ref["grad_norms"], ref["change_norms"]
    g_med = statistics.median(g_ref.values())
    live = [k for k in g_ref if g_ref[k] >= 1e-3 * g_med]
    c_med = statistics.median(c_ref[k] for k in live)
    d_norm = {k: float(np.linalg.norm(v))
              for k, v in ref["dense_grads"].items()}
    d_med = statistics.median(d_norm.values())
    out = {}
    for k in g_ref:
        leaf = {"grad_norm_gap": abs(got["grad_norms"][k] - g_ref[k])
                / max(g_ref[k], g_med)}
        if k in live:
            leaf["change_norm_gap"] = abs(
                got["change_norms"][k] - c_ref[k]) / max(c_ref[k], c_med)
        if k in d_norm:
            leaf["dense_grad_diff"] = float(np.linalg.norm(
                np.asarray(got["dense_grads"][k], np.float32)
                - ref["dense_grads"][k])) / max(d_norm[k], d_med)
        out[k] = leaf
    return out


CHOICE_NUMBERS = {"grad_norm_gap": "choice_norm_gap",
                  "dense_grad_diff": "choice_grad_diff"}


def compare(got: dict, ref: dict, config: dict) -> dict:
    """`train_corpus.compare`; where the configuration names
    `block.choice_leaves`, the two worst-leaf numbers of the first
    gradient are taken over the other leaves and, under
    `CHOICE_NUMBERS`' names, over those (the measure is the same, each
    leaf against the whole model's median leaf); `expert_norm_gap` beside
    them where it names leaves that stack experts."""
    verdict = base.compare(got, ref)
    leaves = verdict["by_leaf"] = by_leaf(got, ref)
    choice = set(config["block"].get("choice_leaves", ()))
    if choice:
        for measure, own in CHOICE_NUMBERS.items():
            for number, chosen in ((measure, False), (own, True)):
                value, where = max(
                    ((leaf[measure], name) for name, leaf in leaves.items()
                     if measure in leaf
                     and (name.rpartition("/")[2] in choice) == chosen),
                    default=(0.0, None))
                verdict["numbers"][number] = value
                verdict["worst_leaf"][number] = where
    names = config["block"].get("expert_leaves")
    if names:
        number, where = expert_norm_gap(got["dense_grads"],
                                        ref["dense_grads"], names)
        verdict["numbers"]["expert_norm_gap"] = number
        verdict["worst_leaf"]["expert_norm_gap"] = where
    return verdict


def other_readings(reference, seed, spec, host_batches, block, ref, *,
                   config: dict, faults: bool) -> dict:
    """`train_corpus_ref.other_readings` under this kind's `compare`: the
    control and each planted fault against the float32 reference by the
    run's own comparison, judge and limits. Every one has to come out as
    not correct."""
    limits = config["correct"]["limits"]

    def read(other):
        compared = compare(other, ref, config)
        numbers = compared["numbers"]
        verdict = base.judge(numbers, limits)
        return {"numbers": numbers, "correct": verdict["correct"],
                "over": verdict["over"], "by_leaf": compared["by_leaf"]}

    def follow(**kw):
        return reference.follow(seed, spec, host_batches, block=block, **kw)

    control = config["correct"]["control"]
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


def scanned_chunks(steps: int):
    """The window's steps in the program's `gdn/scan` records, its last
    `steps`: the chunks the scans ran over and those of them with a valid
    slot. None where the program keeps no such record."""
    from code2vec_tpu.obs.trace import memory_tracer

    records = memory_tracer().records("gdn/scan")
    if not steps or len(records) < steps:
        return None
    attrs = [r["attrs"] for r in records[-steps:]]
    return {"chunk": attrs[0]["chunk"],
            "chunks": sum(a["chunks"] for a in attrs),
            "live_chunks": sum(a["live_chunks"] for a in attrs)}


def model_sizes(config: dict, dims) -> dict:
    """What the counts read: the configuration's `model`, the block's
    stated sizes, and the layers as the program built them."""
    built = getattr(dims, config["block"]["dims"])
    return dict(config["model"], **block_sizes(config),
                layer_types=list(built.layer_types),
                num_dense_layers=config.get("num_dense_layers", 0))


def run(ctx) -> dict:
    import jax

    config, traffic, chips = ctx.config, ctx.traffic, ctx.cell["chips"]
    reference = importlib.import_module(config["reference"]["module"])
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
    vocab = {k: config["model"][k] for k in ("tokens", "paths", "targets")}
    base._ensure_dict(ctx, vocab, num_methods, prefix)
    corpus_facts = corpus_mod.write_corpus(
        prefix, seed=seed, num_methods=num_methods, vocab=vocab,
        max_contexts=config["model"]["max_contexts"],
        ids_law=traffic["ids"], lengths_law=traffic["lengths"])
    phase("corpus")

    # -- the program --
    from code2vec_tpu.config import Config
    from code2vec_tpu.models.jax_model import Code2VecModel

    config_file = next(c["file"] for c in ctx.manifest["configs"]
                       if c["name"] == config["name"])
    cfg = Config.load_from_args(
        base.program_argv(config, prefix, batch, seed,
                          ctx.device["platform"])
        + ["--block_config", os.path.join(ctx.root, config_file)])
    cfg.VERBOSE_MODE = 0
    model = Code2VecModel(cfg)
    check_config_is_run(config, cfg, model.dims)
    sizes = model_sizes(config, model.dims)
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
            # to the host at once: the gradient does not ride on the
            # device through the next steps
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
    # the window is closed and synced: the last steps' counts are read
    # with no wait of their own
    recorder = getattr(model._train_step, "route_recorder", None)
    if recorder is not None:
        recorder.flush()
    routed = ref_kind.routed_rows(drive.steps)
    if routed is not None:
        window["routed_rows"] = sum(routed["rows_here"])
        window["valid_tokens"] = sum(routed["valid_tokens"])
    scanned = scanned_chunks(drive.steps)
    if scanned is not None:
        window["scan_chunk"] = scanned["chunk"]
        window["scanned_chunks"] = scanned["chunks"]
        window["live_chunks"] = scanned["live_chunks"]
    memory = base.device_memory(ctx)

    # -- free the program, then the reference --
    drive.close()
    model.params = model.opt_state = None
    del drive, model
    t_ref = time.time()
    spec = reference_spec(config, steps_per_epoch)
    block = config["reference"]["block"]
    ref = reference.follow(seed, spec, host_batches, block=block)
    verdict = compare(got, ref, config)
    limits = config["correct"]["limits"]
    judged = base.judge(verdict["numbers"], limits)
    reference_s = time.time() - t_ref
    facts = {"setup_phases_s": phases, "window": window, "memory": memory,
             "corpus": corpus_facts, "reference_s": reference_s,
             "worst_leaf": verdict["worst_leaf"],
             "by_leaf": verdict["by_leaf"],
             "left_out_of_change": verdict["left_out_of_change"],
             "not_compared": judged["not_compared"],
             "route_by_step": routed,
             "reference": {k: v for k, v in ref.items()
                           if k != "dense_grads"},
             "program": {k: v for k, v in got.items()
                         if k != "dense_grads"}}

    if ctx.args.readings != "run":
        facts["readings"] = other_readings(
            reference, seed, spec, host_batches, block, ref, config=config,
            faults=ctx.args.readings == "all")
        for name, r in facts["readings"].items():
            print(f"reading {name}: correct {r['correct']}"
                  f" (over: {', '.join(r['over']) or 'none'})",
                  file=sys.stderr)

    ctx.window = window
    ctx.model_sizes = sizes
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
