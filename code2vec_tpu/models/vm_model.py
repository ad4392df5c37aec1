"""VarMisuse model orchestration (BASELINE.json configs[3]).

Mirrors models/jax_model.py's lifecycle (train / evaluate / save / load /
resume) for the pointer head in models/varmisuse.py, over `.vm.c2v`
datasets (data/varmisuse_gen.py format). Selected via `--head varmisuse`.
"""

from __future__ import annotations

import time
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from code2vec_tpu import device
from code2vec_tpu.config import Config
from code2vec_tpu.data.vm_reader import (VMTextReader, build_vm_vocabs)
from code2vec_tpu.models.encoder import ModelDims
from code2vec_tpu.models.varmisuse import init_vm_params
from code2vec_tpu.obs import memory_tracer, setup_trace
from code2vec_tpu.parallel.distributed import fetch_global
from code2vec_tpu.parallel.mesh import DATA_AXIS, DCN_AXIS
from code2vec_tpu.parallel.sharding import (shard_batch, shard_opt_state,
                                            shard_params)
from code2vec_tpu.training import checkpoint as ckpt
from code2vec_tpu.training.profiler import StepProfiler
from code2vec_tpu.training.vm_steps import (make_vm_eval_step,
                                            make_vm_train_step)


class VMEvalResults(NamedTuple):
    loss: float
    accuracy: float
    num_examples: int

    def __str__(self) -> str:
        return (f"vm loss: {self.loss:.5f}, pointer accuracy: "
                f"{self.accuracy:.5f} over {self.num_examples} examples")


class VarMisuseModel:
    def __init__(self, config: Config):
        # the same set-up spans as Code2VecModel, where the same calls
        # run (obs/setup_trace.py); none waits for the device
        with memory_tracer().start_span(
                "setup/model", loading=config.is_loading,
                encoder="varmisuse"):
            self._build(config)

    def _build(self, cfg: Config) -> None:
        span = memory_tracer().start_span
        self.config = cfg
        self.log = cfg.log
        from code2vec_tpu.obs import Telemetry, Tracer
        self.telemetry = Telemetry.disabled()  # train() swaps it in
        self.tracer = Tracer.disabled()        # ditto (--trace)
        self.compute_dtype = jnp.bfloat16 if cfg.USE_BF16 else jnp.float32
        from code2vec_tpu.models.setup import build_mesh, build_optimizer
        # no context axis: the vm head is bag-encoder-only (Config.verify)
        with span("setup/mesh") as sp:
            self.mesh = build_mesh(cfg, with_context_axis=False)
            sp.attrs["devices"] = (1 if self.mesh is None
                                   else self.mesh.devices.size)
        # The fused pool is a Mosaic kernel, so it exists on a TPU only
        # (code2vec.py has already held the run to --backend: the
        # platform read here is the one the user named). Under a mesh
        # it would have to sit in a shard_map (encoder.encode's
        # `mesh`), which vm_scores does not thread: partitioned vm
        # steps pool with XLA.
        platform = device.platform()
        self.use_pallas = (cfg.USE_PALLAS and platform == "tpu"
                           and self.mesh is None)
        self.log(f"attention pool: "
                 f"{'Pallas kernel' if self.use_pallas else 'XLA'} "
                 f"(platform {platform}, USE_PALLAS={cfg.USE_PALLAS}, "
                 f"mesh={self.mesh})")
        model_axis = max(1, cfg.MESH_MODEL_AXIS)

        if cfg.is_loading:
            with span("setup/restore"):
                self.dims = ckpt.load_dims(cfg.load_path)
                manifest = ckpt.load_manifest(cfg.load_path)
            cfg.MAX_CONTEXTS = self.dims.max_contexts
            cfg.MAX_CANDIDATES = manifest.get("max_candidates",
                                              cfg.MAX_CANDIDATES)
            cfg.TABLES_DTYPE = self.dims.tables_dtype
            # fallback "adam" (the pre-manifest-key default), not the
            # current adafactor default — see jax_model.py
            cfg.EMBEDDING_OPTIMIZER = manifest.get(
                "embedding_optimizer", "adam")
            # opt_state structure follows this exactly like the
            # optimizer choice does (sparse dict vs optax chain)
            cfg.SPARSE_EMBEDDING_UPDATES = manifest.get(
                "sparse_embedding_updates", cfg.SPARSE_EMBEDDING_UPDATES)
            cfg.TRUST_RATIO = manifest.get("trust_ratio", False)
            from code2vec_tpu.training.optimizers import (
                resolve_checkpoint_schedule, resolve_checkpoint_warmup)
            cfg.LR_SCHEDULE = resolve_checkpoint_schedule(
                cfg.LR_SCHEDULE, manifest, cfg.log)
            cfg.LR_WARMUP_STEPS = resolve_checkpoint_warmup(
                cfg.LR_SCHEDULE, cfg.LR_WARMUP_STEPS, manifest, cfg.log)
            with span("setup/vocabs"):
                self.vocabs = ckpt.load_vocabs(cfg.load_path)
        else:
            assert cfg.train_data_path, "varmisuse needs --data or --load"
            with span("setup/vocabs"):
                self.vocabs = build_vm_vocabs(self._vm_path("train"),
                                              cfg.MAX_TOKEN_VOCAB_SIZE,
                                              cfg.MAX_PATH_VOCAB_SIZE)
            self.dims = ModelDims(
                token_vocab_size=self.vocabs.token_vocab.size,
                path_vocab_size=self.vocabs.path_vocab.size,
                target_vocab_size=self.vocabs.target_vocab.size,
                embeddings_size=cfg.DEFAULT_EMBEDDINGS_SIZE,
                max_contexts=cfg.MAX_CONTEXTS,
                dropout_keep_rate=cfg.DROPOUT_KEEP_RATE,
                vocab_pad_multiple=model_axis,
                tables_dtype=cfg.TABLES_DTYPE,
            )
        def n_train_examples() -> int:
            from code2vec_tpu.data.reader import count_examples
            return count_examples(self._vm_path("train"))

        self._n_train_examples = n_train_examples
        with span("setup/optimizer"):
            self.optimizer = build_optimizer(
                cfg, n_train_examples,
                manifest if cfg.is_loading else None)
        with span("setup/init_params"):
            self.rng = jax.random.PRNGKey(cfg.SEED)
            self.rng, init_rng = jax.random.split(self.rng)
            params = init_vm_params(init_rng, self.dims)
        with span("setup/opt_init"):
            if cfg.SPARSE_EMBEDDING_UPDATES:
                # verify() enforces these for CLI runs; assert for
                # programmatic Config users (same contract as jax_model)
                assert cfg.EMBEDDING_OPTIMIZER == "adam", (
                    "SPARSE_EMBEDDING_UPDATES requires "
                    "EMBEDDING_OPTIMIZER='adam'")
                assert cfg.LR_SCHEDULE == "constant", (
                    "SPARSE_EMBEDDING_UPDATES requires "
                    "LR_SCHEDULE='constant'")
                from code2vec_tpu.training.vm_steps import \
                    init_vm_sparse_opt_state
                opt_state = init_vm_sparse_opt_state(params,
                                                     self.optimizer)
            else:
                opt_state = self.optimizer.init(params)
        self.step_num = 0
        if cfg.is_loading:
            with span("setup/restore"):
                full = ckpt.load_checkpoint(
                    cfg.load_path,
                    {"params": params, "opt_state": opt_state, "step": 0})
                params, opt_state = full["params"], full["opt_state"]
                self.step_num = int(full.get("step", 0))
        if self.mesh is not None:
            with span("setup/shard"):
                params = shard_params(self.mesh, params)
                opt_state = shard_opt_state(self.mesh, opt_state, params)
        self.params, self.opt_state = params, opt_state

        # background checkpoint writer (--async_checkpoint, default on);
        # lazy so load/eval-only instances never start the thread
        self._ckpt_writer = None
        from code2vec_tpu.training.sparse_update import \
            resolve_sparse_update_mode
        with span("setup/steps"):
            self._train_step = make_vm_train_step(
                self.dims, self.optimizer,
                compute_dtype=self.compute_dtype,
                use_pallas=self.use_pallas,
                sparse_updates=cfg.SPARSE_EMBEDDING_UPDATES,
                learning_rate=cfg.LEARNING_RATE,
                sparse_update_fused=resolve_sparse_update_mode(
                    cfg.SPARSE_UPDATE_PALLAS),
                mesh=self.mesh)
            self._eval_step = make_vm_eval_step(
                self.dims, compute_dtype=self.compute_dtype,
                use_pallas=self.use_pallas)

    def _vm_path(self, split: str) -> str:
        p = self.config.train_data_path
        assert p
        return f"{p}.{split}.vm.c2v"

    def _host_batch_arrays(self, b):
        weights = np.zeros((b.label.shape[0],), np.float32)
        weights[:b.num_valid_examples] = 1.0
        weights *= b.row_valid   # drop rows whose label was truncated
        return (b.label, b.path_source_token_indices, b.path_indices,
                b.path_target_token_indices, b.context_valid_mask,
                b.cand_ids, b.cand_mask, weights)

    def _device_batch(self, b, process_local: bool = True):
        arrays = self._host_batch_arrays(b)
        if self.mesh is not None:
            return shard_batch(self.mesh, arrays,
                               process_local=process_local)
        # materialize on device HERE (async dispatch) so the prefetch
        # thread really transfers ahead — numpy passed into the jitted
        # step would transfer on the MAIN thread at call time
        return tuple(jnp.asarray(a) for a in arrays)

    def train(self) -> None:
        cfg = self.config
        # auto-resume epoch offset: the ONE shared arithmetic (see
        # models/setup.resume_epoch_offset — the recovery contract)
        from code2vec_tpu.models.setup import (infeed_split,
                                               resume_epoch_offset)
        completed_epochs = resume_epoch_offset(
            cfg, self.step_num, self._n_train_examples, self.log)
        # per-host infeed split from the LIVE process set (ISSUE 13)
        host_shard, num_host_shards = infeed_split()
        reader = VMTextReader(
            self._vm_path("train"), self.vocabs, cfg.MAX_CONTEXTS,
            cfg.MAX_CANDIDATES, cfg.TRAIN_BATCH_SIZE, shuffle=True,
            seed=cfg.SEED, host_shard=host_shard,
            num_host_shards=num_host_shards,
            epoch_offset=completed_epochs)
        self.log(f"varmisuse training: dims={self.dims}, "
                 f"max_candidates={cfg.MAX_CANDIDATES}")
        window, t0 = 0, time.time()
        profiler = StepProfiler(cfg.PROFILE_DIR, cfg.PROFILE_START_STEP,
                                cfg.PROFILE_STEPS, self.log)
        # Unified run telemetry (code2vec_tpu/obs/) — same per-step
        # step_ms/infeed_wait_ms/loss records as the code2vec head; the
        # shared recorder keeps the two loops' metrics comparable.
        from code2vec_tpu.obs import (SpanChannel, Telemetry, Tracer,
                                      TrainStepRecorder, Watchdog,
                                      build_live_plane)
        telemetry = Telemetry.create(
            cfg.TELEMETRY_DIR, config=cfg, mesh=self.mesh,
            component="train", log=self.log)
        if cfg.METRICS_PORT > 0 and not telemetry.enabled:
            # --metrics_port without --telemetry_dir: live exposition
            # over an in-memory registry (same as jax_model)
            telemetry = Telemetry.memory("train")
        self.telemetry = telemetry
        live_plane = cfg.METRICS_PORT > 0 or cfg.ALERTS_MODE != "off"
        if (cfg.ASYNC_CHECKPOINT or cfg.TRACE
                or cfg.WATCHDOG_STALL_S > 0 or live_plane):
            # the checkpoint writer, the infeed producer (trace spans),
            # the watchdog/health monitors and the exposition handler
            # all touch this registry cross-thread
            telemetry.make_threadsafe()
        # per-step tracing + stall watchdog — same wiring as jax_model
        # (shared recorder/obs layer keeps the two loops comparable)
        tracer = Tracer.create(telemetry) if cfg.TRACE \
            else Tracer.disabled()
        self.tracer = tracer
        watchdog = Watchdog.create(
            telemetry, stall_s=cfg.WATCHDOG_STALL_S,
            mode=cfg.WATCHDOG_MODE, tracer=tracer, log=self.log)
        loop_hb = watchdog.register("train_loop")
        self._ckpt_heartbeat = watchdog.register("checkpoint_writer")
        infeed_hb = watchdog.register("infeed_producer")
        # live metrics plane (ISSUE 7) — the ONE shared wiring
        # (obs/exposition.build_live_plane), same as jax_model
        from code2vec_tpu.obs.alerts import default_train_rules
        from code2vec_tpu.obs.health import default_train_monitors
        plane = build_live_plane(
            telemetry, metrics_port=cfg.METRICS_PORT,
            alerts_mode=cfg.ALERTS_MODE,
            alerts_rules=cfg.ALERTS_RULES,
            health_every_s=cfg.HEALTH_EVERY_S, watchdog=watchdog,
            monitors=default_train_monitors(),
            default_rules=default_train_rules,
            # identity block on /vars (ISSUE 17), same as jax_model
            identity={"process_index": jax.process_index(),
                      "process_count": jax.process_count()},
            log=self.log)
        alerts = plane.alerts
        self.metrics_server = plane.metrics
        infeed_channel = SpanChannel() if tracer.enabled else None
        recorder = TrainStepRecorder(
            telemetry, gauge_every=cfg.NUM_BATCHES_TO_LOG_PROGRESS,
            tracer=tracer, infeed_channel=infeed_channel,
            heartbeat=loop_hb if watchdog.enabled else None,
            alerts=alerts if alerts.enabled else None)
        self._trace_recorder = recorder
        watchdog.start()
        plane.start()
        telemetry.gauge("train/max_contexts", cfg.MAX_CONTEXTS,
                        emit=False, static=True)
        loop_hb.busy()  # the first deadline covers step-0 compile too
        steps_into_training = 0
        from code2vec_tpu.data.prefetch import (build_train_infeed,
                                                persistent_epochs)
        from code2vec_tpu.obs import infeed_produce_instrument
        infeed = build_train_infeed(
            reader, chunk=cfg.INFEED_CHUNK, depth=cfg.INFEED_PREFETCH,
            mesh=self.mesh, host_arrays_fn=self._host_batch_arrays,
            device_batch_fn=self._device_batch, log=self.log,
            instrument=infeed_produce_instrument(tracer, infeed_channel),
            heartbeat=infeed_hb if watchdog.enabled else None)
        # chaos failpoints (--faults, ISSUE 10) — disarmed, each is one
        # attribute read per step (same wiring as jax_model)
        from code2vec_tpu.resilience import faults, retry
        if telemetry.enabled:
            retry.set_telemetry(telemetry)
        nan_fp, kill_fp = faults.train_step_points()
        # one warm producer thread across epoch boundaries (same as
        # jax_model): epoch k+1 parses/transfers during the boundary
        # save + eval instead of cold-restarting the double buffer
        try:
            for epoch, epoch_batches in persistent_epochs(
                    infeed, cfg.NUM_TRAIN_EPOCHS,
                    first_epoch=completed_epochs + 1):
                for dev_batch, batch in recorder.wrap(epoch_batches):
                    profiler.tick(steps_into_training, self.params)
                    # absolute-step-keyed rng: auto-resume replays the
                    # uninterrupted run's key stream (see jax_model)
                    k = jax.random.fold_in(self.rng, self.step_num)
                    self.params, self.opt_state, loss = self._train_step(
                        self.params, self.opt_state, dev_batch, k)
                    if nan_fp.armed and nan_fp.hit():
                        loss = loss * float("nan")  # poison the loss
                    if kill_fp.armed:
                        kill_fp.fire(step=self.step_num + 1)
                    self.step_num += 1
                    steps_into_training += 1
                    if steps_into_training == 1:
                        # where set-up went, once (as jax_model)
                        setup_trace.report(self.log, tracer)
                    window += batch.num_valid_examples
                    loss_f = (recorder.end_step(self.step_num, loss,
                                                batch.num_valid_examples,
                                                params=self.params)
                              if recorder.enabled else None)
                    if self.step_num % cfg.NUM_BATCHES_TO_LOG_PROGRESS == 0:
                        if loss_f is None:
                            loss_f = float(loss)
                        dt = time.time() - t0
                        self.log(f"vm epoch {epoch} step {self.step_num}: "
                                 f"loss {loss_f:.4f}, "
                                 f"{window / max(dt, 1e-9):.1f} ex/s")
                        window, t0 = 0, time.time()
                epoch_end_work = False
                if cfg.is_saving and epoch % cfg.SAVE_EVERY_EPOCHS == 0:
                    # async: kick the save first so eval overlaps the
                    # writer tail (same boundary overlap as jax_model)
                    self._save_epoch = epoch  # -> step topology record
                    self.save(block=False)
                    epoch_end_work = True
                if cfg.is_testing and epoch % cfg.SAVE_EVERY_EPOCHS == 0:
                    eval_span = telemetry.span("train/eval_ms")
                    try:
                        results = self.evaluate()
                    except BaseException:
                        eval_span.cancel()  # dead eval: drop, don't leak
                        raise
                    eval_ms = eval_span.stop()
                    self.log(f"vm epoch {epoch}: {results}")
                    telemetry.event("eval", epoch=epoch, step=self.step_num,
                                    loss=results.loss,
                                    accuracy=results.accuracy,
                                    eval_ms=round(eval_ms, 3))
                    epoch_end_work = True
                if epoch_end_work:
                    # boundary work is progress for the loop's deadline
                    loop_hb.beat()
                    # checkpoint/eval wall time must not leak into the next
                    # window's first ex/s figure (same fix as jax_model)
                    window, t0 = 0, time.time()
            if self._ckpt_writer is not None:
                # hard commit barrier: end of training (re-raises a
                # background write failure)
                self._ckpt_writer.wait()
            watchdog.poll()  # raise-mode: a stalled run dies loudly here
            alerts.poll()    # raise-mode: so does a firing alert
        finally:
            loop_hb.idle()
            watchdog.stop()  # no re-raise: must not mask loop errors
            plane.stop()
            if self._ckpt_writer is not None:
                # exception-path teardown: drain without
                # masking the in-flight error (a sticky
                # write failure still re-raises at the next
                # submit/wait/close)
                self._ckpt_writer.drain_quiet()
        profiler.finish(self.params)
        telemetry.close()
        self.log("varmisuse training done")

    def evaluate(self, split_path: Optional[str] = None) -> VMEvalResults:
        cfg = self.config
        path = split_path or cfg.test_data_path
        assert path, "evaluate requires --test"
        multi = jax.process_count() > 1
        # Multi-host: each host parses a DISJOINT shard (global eval
        # batch = H x TEST_BATCH_SIZE). The eval step returns GLOBAL
        # weighted sums (identical on every host), so only the local
        # example count needs cross-host merging.
        reader = VMTextReader(path, self.vocabs, cfg.MAX_CONTEXTS,
                              cfg.MAX_CANDIDATES, cfg.TEST_BATCH_SIZE,
                              host_shard=jax.process_index() if multi
                              else 0,
                              num_host_shards=jax.process_count()
                              if multi else 1)
        loss_sum = correct = total = 0.0
        from code2vec_tpu.data.prefetch import prefetch_to_device
        infeed = prefetch_to_device(
            reader, lambda b: self._device_batch(b, process_local=multi),
            cfg.INFEED_PREFETCH)
        for dev_batch, batch in infeed:
            ls, cs, _pred = self._eval_step(self.params, dev_batch)
            loss_sum += float(ls)
            correct += float(cs)
            total += batch.num_valid_examples
        if multi:
            from code2vec_tpu.parallel.distributed import \
                allreduce_sum_hosts
            total = float(allreduce_sum_hosts([total])[0])
        total = max(total, 1.0)
        return VMEvalResults(loss_sum / total, correct / total,
                             int(total))

    def predict_batch(self, rows) -> np.ndarray:
        """Pointer predictions (candidate indices) for `.vm.c2v` rows."""
        from code2vec_tpu.data.vm_reader import parse_vm_rows

        cfg = self.config
        (labels, src, pth, dst, mask, cand, cand_mask, row_valid,
         _strings) = parse_vm_rows(list(rows), self.vocabs,
                                   cfg.MAX_CONTEXTS, cfg.MAX_CANDIDATES)
        n = labels.shape[0]
        weights = row_valid.copy()
        batch = [labels, src, pth, dst, mask, cand, cand_mask, weights]
        if self.mesh is not None:
            # pad the batch dim to divide the data axis
            dax = self.mesh.shape[DATA_AXIS] * self.mesh.shape[DCN_AXIS]
            padded = -(-n // dax) * dax
            if padded != n:
                for i, a in enumerate(batch):
                    pad = np.zeros((padded - n,) + a.shape[1:], a.dtype)
                    batch[i] = np.concatenate([a, pad], axis=0)
                batch[6][n:, 0] = 1.0  # keep softmax finite on pad rows
            batch = shard_batch(self.mesh, tuple(batch),
                                process_local=False)
        _ls, _cs, pred = self._eval_step(self.params, tuple(batch))
        return fetch_global(pred)[:n]

    def save(self, path: Optional[str] = None, block: bool = True) -> None:
        path = path or self.config.save_path
        assert path
        state = {"params": self.params, "opt_state": self.opt_state,
                 "step": self.step_num}
        extra = {"head": "varmisuse",
                 "max_candidates": self.config.MAX_CANDIDATES,
                 "embedding_optimizer": self.config.EMBEDDING_OPTIMIZER,
                 "sparse_embedding_updates":
                     self.config.SPARSE_EMBEDDING_UPDATES,
                 "trust_ratio": self.config.TRUST_RATIO,
                 "lr_schedule": self.config.LR_SCHEDULE,
                 "lr_warmup_steps": self.config.LR_WARMUP_STEPS}
        # per-step save-time topology (ISSUE 13): epoch consumed and
        # reset — see jax_model.save
        topology = {"epoch": getattr(self, "_save_epoch", None)}
        self._save_epoch = None
        trace_span = None
        if self.tracer.enabled:
            rec = getattr(self, "_trace_recorder", None)
            last = rec.last_step_context if rec is not None else None
            trace_span = self.tracer.start_trace(
                "train/save_blocked", step=int(self.step_num),
                is_async=bool(self.config.ASYNC_CHECKPOINT))
            if last is not None:
                trace_span.links.append(last)
        blocked_span = self.telemetry.span("train/save_blocked_ms")
        try:
            if self.config.ASYNC_CHECKPOINT:
                if self._ckpt_writer is None:
                    self._ckpt_writer = ckpt.AsyncCheckpointWriter(
                        log=self.log,
                        heartbeat=getattr(self, "_ckpt_heartbeat", None))
                self._ckpt_writer.submit(
                    path, state, self.step_num, self.vocabs, self.dims,
                    extra_manifest=extra,
                    max_to_keep=self.config.MAX_TO_KEEP,
                    topology=topology,
                    telemetry=self.telemetry,
                    tracer=self.tracer if trace_span is not None
                    else None,
                    trace_ctx=trace_span.context()
                    if trace_span is not None else None)
                if block:
                    self._ckpt_writer.wait()
                blocked_ms = blocked_span.stop()
                self.log(f"queued varmisuse checkpoint step "
                         f"{self.step_num} -> {path} "
                         f"(loop blocked {blocked_ms:.1f} ms)")
            else:
                ckpt.save_checkpoint(path, state, self.step_num,
                                     self.vocabs, self.dims,
                                     extra_manifest=extra,
                                     max_to_keep=self.config.MAX_TO_KEEP,
                                     topology=topology)
                blocked_ms = blocked_span.stop()
                self.telemetry.record_ms("train/save_total_ms",
                                         blocked_ms)
                self.telemetry.event("save_committed",
                                     step=self.step_num,
                                     total_ms=round(blocked_ms, 3))
                self.log(f"saved varmisuse checkpoint step "
                         f"{self.step_num} -> {path}")
        except BaseException:
            # a failed submit/save must not leak the blocked span or
            # leave the save trace open in the live-span table
            blocked_span.cancel()
            if trace_span is not None:
                trace_span.end(outcome="error")
            raise
        if trace_span is not None:
            trace_span.end(blocked_ms=round(blocked_ms, 3))
        self.telemetry.event("save", step=self.step_num,
                             blocked_ms=round(blocked_ms, 3),
                             is_async=bool(self.config.ASYNC_CHECKPOINT))

    def close_session(self) -> None:
        # stop() commit barrier: no checkpoint may be left half-written
        if self._ckpt_writer is not None:
            self._ckpt_writer.close()
