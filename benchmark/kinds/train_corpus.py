"""Traffic kind `train_corpus`: the job a training user runs.

A corpus made from the seed is read back through the program's own
reader and infeed (`data/reader.open_reader`, `Code2VecModel._train_infeed`,
`data/prefetch.persistent_epochs`) and trained on with the program's own
jitted step, in the loop `Code2VecModel.train()` runs, less telemetry,
checkpoint and eval: that loop cannot stop on a clock, so the harness
owns it.

Set-up: corpus, model (weights from the seed, drawn by the program on
the device), then the first `check_steps` steps through the same drive
and feed the window uses. They compile the cell's one step shape, and
their losses, the first gradient's norms (read out of the optimizer's
state after step 1) and the norms of the parameters' change are what
`correct` compares with `reference.py` once the window has closed.

Window: after a sync, whole steps are dispatched until `--seconds` have
passed; it ends when the last dispatched step is ready.
`train_methods_per_s` is every valid method of every one of those steps
over that whole time.
"""

from __future__ import annotations

import collections
import glob
import os
import shutil
import statistics
import sys
import time

import numpy as np

import corpus as corpus_mod
import reference


# ---- the configuration file -> the program's flags ----------------------

def program_argv(config: dict, prefix: str, batch: int, seed: int,
                 platform: str) -> list:
    return ["--data", prefix, "--batch_size", str(batch), "--seed",
            str(seed), "--backend", platform, *config["flags"]]


def check_config_is_run(config: dict, cfg, dims) -> None:
    """The file holds the configuration as it is run: every size it
    states is the one the program built."""
    m, t = config["model"], config["train"]
    stated = {
        "tokens": dims.token_vocab_size - 2, "paths": dims.path_vocab_size - 2,
        "targets": dims.target_vocab_size - 2,
        "embedding": dims.embeddings_size,
        "code_vector": dims.code_vector_size,
        "max_contexts": dims.max_contexts,
        "dropout_keep": dims.dropout_keep_rate,
        "encoder": dims.encoder_type, "tables_dtype": dims.tables_dtype,
        "num_sampled": cfg.NUM_SAMPLED_CLASSES,
        "compute_dtype": "bfloat16" if cfg.USE_BF16 else "float32",
    }
    if dims.encoder_type == "transformer":
        stated.update(xf_layers=dims.xf_layers, xf_heads=dims.xf_heads,
                      xf_mlp_ratio=dims.xf_mlp_ratio)
    ran = {"lr": cfg.LEARNING_RATE, "lr_schedule": cfg.LR_SCHEDULE,
           "epochs": cfg.NUM_TRAIN_EPOCHS,
           "table_optimizer": cfg.EMBEDDING_OPTIMIZER}
    for group, want in ((m, stated), (t, ran)):
        for k, v in want.items():
            if group.get(k) != v:
                raise RuntimeError(
                    f"configuration {config['name']!r} states {k}="
                    f"{group.get(k)!r} and the program built {v!r}")
    if not cfg.USE_SAMPLED_SOFTMAX:
        raise RuntimeError("the configuration's flags leave the sampled "
                           "softmax off")


def reference_spec(config: dict, steps_per_epoch: int) -> dict:
    spec = dict(config["model"])
    t = config["train"]
    spec.update(lr=t["lr"], lr_schedule=t["lr_schedule"],
                lr_total_steps=steps_per_epoch * t["epochs"])
    return spec


# ---- what the program's state says --------------------------------------

def _leaf_name(path) -> str:
    parts = []
    for k in path:
        parts.append(str(getattr(k, "key", getattr(k, "idx", getattr(
            k, "name", k)))))
    return "/".join(parts)


def _flat(tree) -> dict:
    import jax

    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {_leaf_name(p): v for p, v in leaves}


def make_state_readers(params):
    """Two jitted readers of the program's state: the norm of the first
    gradient as the optimizer got it, leaf by leaf, worked out from the
    optimizer state after one step (Adafactor's second moment after its
    first update is the mean of g^2 + 1e-30 exactly; Adam's first moment
    is 0.1 g, which also gives the dense leaves' whole first gradient),
    and the norm of each leaf's change since a copy."""
    import jax
    import jax.numpy as jnp

    names = list(_flat(params))

    def grad_norms(opt_state, params):
        f32 = jnp.float32
        found = {}
        for path, leaf in jax.tree_util.tree_flatten_with_path(
                opt_state)[0]:
            attrs = [getattr(k, "name", None) for k in path]
            for field in ("v_col", "v", "mu"):
                if field in attrs:
                    tail = _leaf_name(path[attrs.index(field) + 1:])
                    if tail in names:
                        found.setdefault(tail, {})[field] = leaf
        out = {}
        flat_p = _flat(params)
        for name in names:
            st = found.get(name, {})
            size = flat_p[name].size
            if "v_col" in st and st["v_col"].size > 1:
                cols = size // st["v_col"].size
                sq = jnp.sum(st["v_col"].astype(f32)) * cols - size * 1e-30
            elif "v" in st and st["v"].size == size:
                sq = jnp.sum(st["v"].astype(f32)) - size * 1e-30
            elif "mu" in st:
                sq = jnp.sum(jnp.square(st["mu"].astype(f32))) * 100.0
            else:
                raise RuntimeError(f"optimizer state holds no first "
                                   f"moment for leaf {name!r}")
            out[name] = jnp.sqrt(jnp.maximum(sq, 0.0))
        dense = {name: found[name]["mu"].astype(f32) * 10.0
                 for name in names if "mu" in found.get(name, {})}
        return out, dense

    def change_norms(params, before):
        a, b = _flat(params), _flat(before)
        return {k: jnp.sqrt(jnp.sum(jnp.square(
            a[k].astype(jnp.float32) - b[k].astype(jnp.float32))))
            for k in a}

    return jax.jit(grad_norms), jax.jit(change_norms)


# ---- the comparison -----------------------------------------------------

def compare(got: dict, ref: dict) -> dict:
    """The numbers `correct` is decided by: each step's loss as a share
    of the reference's, and by the worst leaf the gap between the two
    norms of the first gradient and of the change over the steps, measured
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger. Leaves whose reference gradient is under a
    thousandth of the median leaf's move by round-off alone and are left
    out of the change."""
    out = {}
    for i, (a, b) in enumerate(zip(got["losses"], ref["losses"])):
        out[f"loss{i + 1}_gap"] = abs(a - b) / abs(b)
    g_ref = ref["grad_norms"]
    g_med = statistics.median(g_ref.values())
    out["grad_norm_gap"], out["change_norm_gap"] = 0.0, 0.0
    worst = {"grad_norm_gap": None, "change_norm_gap": None}
    c_ref = ref["change_norms"]
    live = [k for k in g_ref if g_ref[k] >= 1e-3 * g_med]
    c_med = statistics.median(c_ref[k] for k in live)
    for k in g_ref:
        gap = abs(got["grad_norms"][k] - g_ref[k]) / max(g_ref[k], g_med)
        if gap >= out["grad_norm_gap"]:
            out["grad_norm_gap"], worst["grad_norm_gap"] = gap, k
    for k in live:
        gap = abs(got["change_norms"][k] - c_ref[k]) / max(c_ref[k], c_med)
        if gap >= out["change_norm_gap"]:
            out["change_norm_gap"], worst["change_norm_gap"] = gap, k
    # The two gaps of norms cannot see unbiased rounding noise (it cancels
    # in a norm), so an 8-bit step passes them. The dense leaves' whole
    # first gradient can: the norm of its difference from the
    # reference's, by the worst dense leaf, against that leaf's norm or
    # the median dense leaf's.
    dense = ref["dense_grads"]
    d_norm = {k: float(np.linalg.norm(v)) for k, v in dense.items()}
    d_med = statistics.median(d_norm.values())
    out["dense_grad_diff"] = 0.0
    worst["dense_grad_diff"] = None
    for k, v in dense.items():
        diff = float(np.linalg.norm(np.asarray(got["dense_grads"][k],
                                               np.float32) - v))
        gap = diff / max(d_norm[k], d_med)
        if gap >= out["dense_grad_diff"]:
            out["dense_grad_diff"], worst["dense_grad_diff"] = gap, k
    return {"numbers": out, "worst_leaf": worst,
            "left_out_of_change": sorted(set(g_ref) - set(live))}


def judge(numbers: dict, limits: dict) -> dict:
    """The verdict on one set of compared numbers, the run's own or a
    control's or a planted fault's: each number the configuration gives a
    limit stands beside it, and `correct` says that every one is finite
    and at or under its limit. A number the configuration gives no limit
    is not compared (PERF.md names it, with the readings that left it
    without an upper one)."""
    unknown = sorted(set(limits) - set(numbers))
    if unknown or not limits:
        raise RuntimeError(f"the configuration's limits name {unknown}, "
                           f"and the comparison gives {sorted(numbers)}")
    compared = {k: {"value": v, "limit": limits[k]}
                for k, v in numbers.items() if k in limits}
    over = [k for k, c in compared.items()
            if not (np.isfinite(c["value"]) and c["value"] <= c["limit"])]
    return {"compared": compared, "correct": not over, "over": over,
            "not_compared": {k: v for k, v in numbers.items()
                             if k not in limits}}


# ---- the loop -----------------------------------------------------------

class Drive:
    """`Code2VecModel.train()`'s inner loop, with a clock. One object
    serves the checked steps and the window: the same feed, the same
    compiled step, the same state."""

    def __init__(self, model, cfg, lag: int, count_contexts: bool):
        import jax
        from code2vec_tpu.data.prefetch import persistent_epochs
        from code2vec_tpu.data.reader import open_reader

        self.jax = jax
        self.model = model
        self.lag = lag
        self.count_contexts = count_contexts
        reader = open_reader(cfg.data_path("train"), model.vocabs,
                             cfg.MAX_CONTEXTS, cfg.TRAIN_BATCH_SIZE,
                             shuffle=True, seed=cfg.SEED)
        self._epochs = persistent_epochs(model._train_infeed(reader),
                                         cfg.NUM_TRAIN_EPOCHS)
        self._feed = self._batches()
        self.inflight = collections.deque()
        self.reset_counts()

    def _batches(self):
        for _epoch, batches in self._epochs:
            yield from batches

    def reset_counts(self):
        self.steps = self.methods = 0
        self.contexts = self.contexts_sq = 0
        self.infeed_wait_s = self.dispatch_s = 0.0

    def step(self):
        jax, model = self.jax, self.model
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench/infeed_wait"):
            try:
                dev_batch, batch = next(self._feed)
            except StopIteration:
                raise RuntimeError(
                    "the configuration's epochs ran out inside the run; "
                    "raise --epochs in its flags") from None
        t1 = time.perf_counter()
        self.infeed_wait_s += t1 - t0
        with jax.profiler.TraceAnnotation("bench/dispatch"):
            step_rng = jax.random.fold_in(model.rng, model.step_num)
            model.params, model.opt_state, loss = model._train_step(
                model.params, model.opt_state, dev_batch, step_rng)
        self.dispatch_s += time.perf_counter() - t1
        model.step_num += 1
        self.steps += 1
        self.methods += batch.num_valid_examples
        if self.count_contexts:
            n = batch.context_valid_mask[:batch.num_valid_examples].sum(
                axis=1, dtype=np.float64)
            self.contexts += int(n.sum())
            self.contexts_sq += int((n * n).sum())
        self.inflight.append(loss)
        if len(self.inflight) > self.lag:
            # bound the host's run-ahead, so the window ends near its
            # clock and in-flight batches do not pile up in device memory
            with jax.profiler.TraceAnnotation("bench/run_ahead_wait"):
                jax.block_until_ready(self.inflight.popleft())
        return batch, loss

    def sync(self):
        self.jax.block_until_ready((self.model.params,
                                    self.model.opt_state))
        self.inflight.clear()

    def close(self):
        self._feed.close()
        self._epochs.close()


# ---- the run ------------------------------------------------------------

def _count_compiles():
    import jax

    count = [0]

    def on_duration(event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            count[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    return count


def _ensure_dict(ctx, vocab: dict, num_methods: int, prefix: str) -> None:
    """The `.dict.c2v` does not depend on the seed: written once into the
    checkout's cache and linked beside each run's corpus."""
    name = "dict-{tokens}-{paths}-{targets}-{n}.dict.c2v".format(
        n=num_methods, **vocab)
    cached = os.path.join(ctx.cache_dir, name)
    if not os.path.exists(cached):
        corpus_mod.write_dict(cached, vocab["tokens"], vocab["paths"],
                              vocab["targets"], num_methods)
    link = prefix + ".dict.c2v"
    if os.path.lexists(link):
        os.remove(link)
    os.symlink(cached, link)


def device_memory(ctx) -> dict:
    """The peak on the fullest chip. The TPU runtime keeps two counts:
    `peak_bytes_in_use` for the buffers a program is handed and hands
    back, and `peak_bytes_reserved` for the temporaries a compiled program
    works in (a jitted matmul with a 1 GiB intermediate moves only the
    second, by exactly 1 GiB; my chip run, PR 24). The peak is their sum;
    the first alone reads 1.7 GB under a step that holds 5 GB more."""
    best = {"peak_bytes": 0}
    for d in ctx.devices:
        stats = d.memory_stats() or {}
        peak = int(stats.get("peak_bytes_in_use", 0)) \
            + int(stats.get("peak_bytes_reserved", 0))
        if peak >= best["peak_bytes"]:
            best = {"peak_bytes": peak, "device": str(d),
                    "stats": {k: int(v) for k, v in stats.items()
                              if isinstance(v, (int, float))}}
    return best


def run(ctx) -> dict:
    import jax

    config, traffic, chips = ctx.config, ctx.traffic, ctx.cell["chips"]
    model_sizes = config["model"]
    batch = config["train"]["batch_per_chip"] * chips
    steps_per_epoch = traffic["steps_per_epoch"]
    num_methods = batch * steps_per_epoch
    check_steps = traffic["check_steps"]
    seed = ctx.seed % (2 ** 31 - 1)      # the program's --seed is a key
    compiles = _count_compiles()
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
    _ensure_dict(ctx, vocab, num_methods, prefix)
    corpus_facts = corpus_mod.write_corpus(
        prefix, seed=seed, num_methods=num_methods, vocab=vocab,
        max_contexts=model_sizes["max_contexts"], ids_law=traffic["ids"],
        lengths_law=traffic["lengths"])
    phase("corpus")

    # -- the program --
    from code2vec_tpu.config import Config
    from code2vec_tpu.models.jax_model import Code2VecModel

    cfg = Config.load_from_args(program_argv(
        config, prefix, batch, seed, ctx.device["platform"]))
    cfg.VERBOSE_MODE = 0
    model = Code2VecModel(cfg)
    check_config_is_run(config, cfg, model.dims)
    phase("model")

    drive = Drive(model, cfg, lag=traffic["steps_in_flight"],
                  count_contexts=ctx.trace)
    grad_norms_of, change_norms_of = make_state_readers(model.params)
    # the copy the change is measured from: on the device where it fits
    # beside the step's temporaries, else it waits on the host
    copy_on_host = config["reference"].get("copy_of_weights") == "host"
    before = jax.device_get(model.params) if copy_on_host else \
        jax.tree_util.tree_map(lambda x: x.copy(), model.params)
    got = {"losses": []}
    host_batches = []
    for i in range(check_steps):
        host, loss = drive.step()
        host_batches.append(tuple(
            np.array(a) for a in model._host_batch_arrays(host)))
        got["losses"].append(loss)
        if i == 0:
            got["grad_norms"], got["dense_grads"] = grad_norms_of(
                model.opt_state, model.params)
    if copy_on_host:
        drive.sync()
        before = jax.tree_util.tree_map(
            lambda h, p: jax.device_put(h, p.sharding), before,
            model.params)
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
    memory = device_memory(ctx)

    # -- free the program, then the reference --
    drive.close()
    model.params = model.opt_state = None
    del drive, model
    t_ref = time.time()
    spec = reference_spec(config, steps_per_epoch)
    block = config["reference"]["block"]
    ref = reference.follow(seed, spec, host_batches, block=block)
    verdict = compare(got, ref)
    limits = config["correct"]["limits"]
    judged = judge(verdict["numbers"], limits)
    reference_s = time.time() - t_ref
    facts = {"setup_phases_s": phases, "window": window, "memory": memory,
             "corpus": corpus_facts, "reference_s": reference_s,
             "worst_leaf": verdict["worst_leaf"],
             "left_out_of_change": verdict["left_out_of_change"],
             "not_compared": judged["not_compared"],
             "reference": {k: v for k, v in ref.items()
                           if k != "dense_grads"},
             "program": {k: v for k, v in got.items()
                         if k != "dense_grads"}}

    if ctx.args.readings != "run":
        facts["readings"] = other_readings(
            seed, spec, host_batches, block, ref, chips, limits=limits,
            control=config["correct"]["control"],
            faults=ctx.args.readings == "all")
        for name, r in facts["readings"].items():
            print(f"reading {name}: correct {r['correct']}"
                  f" (over: {', '.join(r['over']) or 'none'})",
                  file=sys.stderr)

    ctx.window = window
    ctx.model_sizes = model_sizes
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


def other_readings(seed, spec, host_batches, block, ref, chips, *,
                   limits: dict, control: str, faults: bool) -> dict:
    """How the limits were set, and what each has to fail: the control
    (the reference in the precision below the configuration's, put in the
    program's place) and each fault a training cell can have, planted in
    the reference, each read against the float32 reference by the same
    comparison and given its verdict by the same `judge` under the same
    limits as the run. Every one has to come out as not correct."""
    def read(other):
        numbers = compare(other, ref)["numbers"]
        verdict = judge(numbers, limits)
        return {"numbers": numbers, "correct": verdict["correct"],
                "over": verdict["over"]}

    def rows_kept(share):
        ws = []
        for b in host_batches:
            w = b[5].copy()
            w[int(len(w) * share):] = 0.0
            ws.append(w)
        return ws

    def follow(**kw):
        return reference.follow(seed, spec, host_batches, block=block, **kw)

    out = {"control_" + control: read(follow(quant=control))}
    if not faults:
        return out
    out["fault_half_batch"] = read(follow(weights=rows_kept(0.5)))
    if chips > 1:
        out["fault_no_exchange"] = read(follow(weights=rows_kept(1.0 / chips)))
    out["fault_state_unchanged"] = read(
        dict(ref, change_norms={k: 0.0 for k in ref["change_norms"]}))
    return out
