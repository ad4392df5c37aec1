"""The concrete JAX/TPU code2vec model.

Reference parity target: `tensorflow_model.Code2VecModel`
(SURVEY.md §3, §4.2–§4.5) — training loop with throughput logging,
evaluation with top-k + subtoken metrics, raw-line prediction with
attention output, checkpoint save/load/release, embedding export. The
compute path is the jitted steps in training/steps.py; this class is host
orchestration only.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Iterable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from code2vec_tpu import device
from code2vec_tpu.common import (EvaluationResults, MethodPredictionResults,
                                 SpecialVocabWords)
from code2vec_tpu.config import Config
from code2vec_tpu.data import staircase
from code2vec_tpu.data.reader import (BatchTensors, BinaryShardReader,
                                      _pad_batch, open_reader,
                                      parse_c2v_rows)
from code2vec_tpu.models.encoder import PAD_ID, ModelDims, init_params
from code2vec_tpu.models.registry import spec as encoder_spec
from code2vec_tpu.models.model_base import Code2VecModelBase, MetricAccumulator
from code2vec_tpu.obs import memory_tracer, setup_trace
from code2vec_tpu.parallel.distributed import fetch_global
from code2vec_tpu.parallel.mesh import (CONTEXT_AXIS, DATA_AXIS, DCN_AXIS,
                                        MODEL_AXIS)
from code2vec_tpu.parallel.sharding import (shard_batch, shard_opt_state,
                                            shard_params)
from code2vec_tpu.training import checkpoint as ckpt
from code2vec_tpu.training.profiler import StepProfiler
from code2vec_tpu.training.steps import (TrainBatch, make_encode_step,
                                         make_eval_step, make_predict_step,
                                         make_train_step)
from code2vec_tpu.vocab.vocabularies import Code2VecVocabs, VocabType


@dataclasses.dataclass
class PreparedRows:
    """Pre-parsed predict rows (the host half of `predict`): one row per
    method, un-padded leading dim. The serving micro-batcher coalesces
    several requests' rows with `concat` and runs ONE bucketed device
    call (`predict_prepared`), so parsing stays on the client threads
    and the device sees power-of-two batches only."""

    labels: "np.ndarray"
    src: "np.ndarray"
    pth: "np.ndarray"
    dst: "np.ndarray"
    mask: "np.ndarray"
    target_strings: List[str]
    context_strings: List[List[str]]

    @property
    def n(self) -> int:
        return int(self.labels.shape[0])

    def slice(self, start: int, stop: int) -> "PreparedRows":
        """Row slice [start, stop) — numpy views, no copy. Used to
        chunk an oversized request to the batcher's max_batch."""
        if start == 0 and stop >= self.n:
            return self
        return PreparedRows(
            self.labels[start:stop], self.src[start:stop],
            self.pth[start:stop], self.dst[start:stop],
            self.mask[start:stop], self.target_strings[start:stop],
            self.context_strings[start:stop])

    @staticmethod
    def concat(items: Sequence["PreparedRows"]) -> "PreparedRows":
        assert items
        if len(items) == 1:
            return items[0]
        return PreparedRows(
            labels=np.concatenate([p.labels for p in items]),
            src=np.concatenate([p.src for p in items]),
            pth=np.concatenate([p.pth for p in items]),
            dst=np.concatenate([p.dst for p in items]),
            mask=np.concatenate([p.mask for p in items]),
            target_strings=[s for p in items for s in p.target_strings],
            context_strings=[c for p in items for c in p.context_strings])


def _leaves_and_bytes(tree) -> dict:
    """How many arrays a pytree holds and their bytes, from their
    shapes (no read of the device)."""
    leaves = jax.tree_util.tree_leaves(tree)
    return {"leaves": len(leaves),
            "bytes": int(sum(getattr(x, "nbytes", 0) for x in leaves))}


class Code2VecModel(Code2VecModelBase):
    def __init__(self, config: Config):
        # set-up goes to the program's own record (obs/setup_trace.py):
        # `setup/model` around all of it, the base class's vocabularies
        # included, one child a phase. No span waits for the device:
        # one that ends with device work in flight is the host's time.
        with memory_tracer().start_span(
                "setup/model", loading=config.is_loading) as span:
            super().__init__(config)
            self._build(config)
            span.attrs["encoder"] = self.dims.encoder_type

    def _build(self, cfg: Config) -> None:
        span = memory_tracer().start_span
        self.log = cfg.log
        self.compute_dtype = jnp.bfloat16 if cfg.USE_BF16 else jnp.float32
        # The fused pool is a Mosaic kernel, so it exists on a TPU
        # only. code2vec.py has already held the run to --backend, so
        # the platform read here is the one the user named.
        platform = device.platform()
        self.use_pallas = cfg.USE_PALLAS and platform == "tpu"
        self.log(f"attention pool: "
                 f"{'Pallas kernel' if self.use_pallas else 'XLA'} "
                 f"(platform {platform}, USE_PALLAS={cfg.USE_PALLAS})")

        # ---- mesh (SURVEY.md §3.3): data axis for DP, model axis for
        # sharded vocab tables; single-device runs use no mesh. ----
        from code2vec_tpu.models.setup import build_mesh, build_optimizer
        with span("setup/mesh") as sp:
            self.mesh = build_mesh(cfg)
            sp.attrs["devices"] = (1 if self.mesh is None
                                   else self.mesh.devices.size)
        model_axis = max(1, cfg.MESH_MODEL_AXIS)
        self.shard_contexts = max(1, cfg.MESH_CONTEXT_AXIS) > 1

        if cfg.is_loading:
            # Dims come from the checkpoint manifest, not the CLI: a model
            # trained with different max_contexts / pad multiple must
            # restore bit-exactly regardless of current flags.
            with span("setup/restore"):
                self.dims = ckpt.load_dims(cfg.load_path)
                manifest = ckpt.load_manifest(cfg.load_path)
            cfg.MAX_CONTEXTS = self.dims.max_contexts
            cfg.USE_SAMPLED_SOFTMAX = manifest.get(
                "use_sampled_softmax", cfg.USE_SAMPLED_SOFTMAX)
            cfg.NUM_SAMPLED_CLASSES = manifest.get(
                "num_sampled", cfg.NUM_SAMPLED_CLASSES)
            cfg.SPARSE_EMBEDDING_UPDATES = manifest.get(
                "sparse_embedding_updates", cfg.SPARSE_EMBEDDING_UPDATES)
            cfg.TABLES_DTYPE = self.dims.tables_dtype
            # fallback "adam", NOT the current default: checkpoints
            # predating the manifest key were trained with Adam, and an
            # adafactor template would fail orbax structure matching
            cfg.EMBEDDING_OPTIMIZER = manifest.get(
                "embedding_optimizer", "adam")
            # trust_ratio changes opt_state structure exactly like the
            # optimizer choice does; pre-round-4 checkpoints never had it
            cfg.TRUST_RATIO = manifest.get("trust_ratio", False)
            cfg.TRUST_RATIO_SCOPE = manifest.get("trust_ratio_scope",
                                                 "all")
            from code2vec_tpu.training.optimizers import (
                resolve_checkpoint_schedule, resolve_checkpoint_warmup)
            cfg.LR_SCHEDULE = resolve_checkpoint_schedule(
                cfg.LR_SCHEDULE, manifest, cfg.log)
            cfg.LR_WARMUP_STEPS = resolve_checkpoint_warmup(
                cfg.LR_SCHEDULE, cfg.LR_WARMUP_STEPS, manifest, cfg.log)
        else:
            self.dims = ModelDims(
                token_vocab_size=self.vocabs.token_vocab.size,
                path_vocab_size=self.vocabs.path_vocab.size,
                target_vocab_size=self.vocabs.target_vocab.size,
                embeddings_size=cfg.DEFAULT_EMBEDDINGS_SIZE,
                max_contexts=cfg.MAX_CONTEXTS,
                dropout_keep_rate=cfg.DROPOUT_KEEP_RATE,
                vocab_pad_multiple=model_axis,
                tables_dtype=cfg.TABLES_DTYPE,
                encoder_type=cfg.ENCODER_TYPE,
                xf_layers=cfg.XF_LAYERS,
                xf_heads=cfg.XF_HEADS,
                xf_remat=cfg.XF_REMAT,
                ring_attention=cfg.RING_ATTENTION,
                # the encoder's own sizes, read by its spec
                **encoder_spec(cfg.ENCODER_TYPE).sizes_from_config(cfg),
            )
        if self.dims.tables_dtype == "int8" and self.mesh is not None:
            # data-parallel meshes replicate the quantized tables and
            # psum the carrier grads — supported (tested on the virtual
            # 8-device mesh). Model/context sharding of {q, s} subtrees
            # is not: verify() rejects the explicit flags, this catches
            # an implicit multi-axis mesh. Checked against
            # self.dims.tables_dtype AFTER the is_loading block: the
            # manifest overrides cfg.TABLES_DTYPE there, so a
            # programmatic Config loading an int8 checkpoint (bypassing
            # code2vec.py's manifest pre-read) must not slip past the
            # backstop into shard_params' untested row-sharding
            # (ADVICE r5 finding 1).
            shape = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
            if shape.get("model", 1) > 1 or shape.get("ctx", 1) > 1:
                raise ValueError(
                    "--tables_dtype int8 supports data-parallel meshes "
                    f"only; got mesh {shape}")
        # --sparse_embeddings under a mesh runs the compact
        # dedup/segment-sum/live-row apply inside shard_map
        # (sparse_update.mesh_sparse_apply, round 14) for f32/bf16 AND
        # int8 tables — the round-13 f32-only dense-carrier restriction
        # is gone with the carrier itself. int8 stays fenced to
        # data-parallel meshes by the guard above (shared with the
        # non-sparse quantized step).

        def n_train_examples() -> int:
            # dict pickle already carries the count; rescan the file
            # only for foreign datasets missing it
            n = self.vocabs.num_training_examples
            if not n:
                from code2vec_tpu.data.reader import count_examples
                n = count_examples(cfg.data_path("train"))
            return n

        self._n_train_examples = n_train_examples
        with span("setup/optimizer"):
            self.optimizer = build_optimizer(
                cfg, n_train_examples,
                manifest if cfg.is_loading else None)

        # ---- params: load (--load) or init ----
        self.step_num = 0
        with span("setup/init_params") as sp:
            self.rng = jax.random.PRNGKey(cfg.SEED)
            self.rng, init_rng = jax.random.split(self.rng)
            params = init_params(init_rng, self.dims)
            sp.attrs.update(_leaves_and_bytes(params))
        with span("setup/opt_init") as sp:
            if cfg.SPARSE_EMBEDDING_UPDATES:
                # Config.verify() enforces this for CLI runs; assert
                # here so programmatic Config users get a clear error
                # instead of an optax chain-state mismatch (adafactor
                # became the default table optimizer in round 3,
                # sparse_steps is adam-only).
                assert cfg.EMBEDDING_OPTIMIZER == "adam", (
                    "SPARSE_EMBEDDING_UPDATES requires "
                    "EMBEDDING_OPTIMIZER='adam'")
                assert cfg.LR_SCHEDULE == "constant", (
                    "SPARSE_EMBEDDING_UPDATES requires "
                    "LR_SCHEDULE='constant' (the row-update kernel "
                    "applies a fixed per-row learning rate)")
                from code2vec_tpu.training.sparse_steps import (
                    init_sparse_opt_state)
                opt_state = init_sparse_opt_state(
                    params, self.optimizer, cfg.USE_SAMPLED_SOFTMAX)
            else:
                opt_state = self.optimizer.init(
                    self._opt_param_view(params))
            sp.attrs["bytes"] = _leaves_and_bytes(opt_state)["bytes"]
        if cfg.is_loading:
            with span("setup/restore"):
                if manifest.get("released"):
                    loaded = ckpt.load_checkpoint(cfg.load_path,
                                                  {"params": params})
                    params = loaded["params"]
                    # A released checkpoint carries no optimizer state;
                    # keep the freshly-initialized opt_state built
                    # above — it already matches the train step's
                    # expected structure (sparse dict vs optax Adam,
                    # per the manifest override).
                    self.step_num = int(manifest.get("step", 0))
                else:
                    full = ckpt.load_checkpoint(
                        cfg.load_path, {"params": params,
                                        "opt_state": opt_state,
                                        "step": 0})
                    params, opt_state = full["params"], full["opt_state"]
                    self.step_num = int(full.get("step", 0))
        if self.mesh is not None:
            with span("setup/shard"):
                params = shard_params(self.mesh, params)
                opt_state = shard_opt_state(self.mesh, opt_state, params)
        self.params, self.opt_state = params, opt_state

        with span("setup/staircase") as sp:
            self._stair_groups, self._staircase = \
                self._training_staircase()
            stairs = self._staircase or ()
            sp.attrs.update(
                rows=cfg.TRAIN_BATCH_SIZE // self._stair_groups
                if stairs else 0, rectangles=len(stairs))
        self._full_step_logged = False
        # background checkpoint writer (--async_checkpoint, default on):
        # created lazily at the first save so load/predict-only model
        # instances never start the thread
        self._ckpt_writer: Optional[ckpt.AsyncCheckpointWriter] = None

        # ---- jitted steps (make_train_step owns the sparse-vs-dense
        # dispatch; Config.verify gates the combinations) ----
        with span("setup/steps"):
            augment_fn = None
            if cfg.ADV_RENAME_PROB > 0:
                # adversarial-training defense (attacks/defense.py)
                from code2vec_tpu.attacks.defense import (
                    legal_token_mask, make_rename_augment)
                augment_fn = make_rename_augment(
                    legal_token_mask(self.vocabs.token_vocab, self.dims),
                    cfg.ADV_RENAME_PROB, mode=cfg.ADV_RENAME_MODE)
            from code2vec_tpu.ops.quant import resolve_requant_mode
            from code2vec_tpu.training.sparse_update import \
                resolve_sparse_update_mode
            self._train_step = make_train_step(
                self.dims, self.optimizer,
                use_sampled_softmax=cfg.USE_SAMPLED_SOFTMAX,
                num_sampled=cfg.NUM_SAMPLED_CLASSES,
                compute_dtype=self.compute_dtype,
                use_pallas=self.use_pallas, mesh=self.mesh,
                augment_fn=augment_fn,
                requant_fused=resolve_requant_mode(cfg.REQUANT_PALLAS),
                sparse_updates=cfg.SPARSE_EMBEDDING_UPDATES,
                learning_rate=cfg.LEARNING_RATE,
                sparse_update_fused=resolve_sparse_update_mode(
                    cfg.SPARSE_UPDATE_PALLAS),
                staircase=self._staircase)
            top_k = cfg.TOP_K_WORDS_CONSIDERED_DURING_PREDICTION
            self._eval_step = make_eval_step(
                self.dims, top_k=top_k, compute_dtype=self.compute_dtype,
                use_pallas=self.use_pallas, mesh=self.mesh)
            self._predict_step = make_predict_step(
                self.dims, top_k=top_k, compute_dtype=self.compute_dtype,
                use_pallas=self.use_pallas, mesh=self.mesh)

    # ---- vocabs: dataset dict when training, checkpoint sidecar when
    # loading (SURVEY.md §3.2 "Model checkpoint") ----
    def _load_or_create_vocabs(self) -> Code2VecVocabs:
        cfg = self.config
        if cfg.is_loading:
            return ckpt.load_vocabs(cfg.load_path)
        assert cfg.word_freq_dict_path is not None, (
            "need --data (for its .dict.c2v) or --load")
        return Code2VecVocabs.load_from_dict_file(
            cfg.word_freq_dict_path, cfg.MAX_TOKEN_VOCAB_SIZE,
            cfg.MAX_PATH_VOCAB_SIZE, cfg.MAX_TARGET_VOCAB_SIZE)

    # ---- helpers ----
    def _host_batch_arrays(self, b: BatchTensors):
        """The 6 numpy arrays of one batch (pre-transfer form — shared
        by the per-batch and chunked infeeds)."""
        weights = np.zeros((b.target_index.shape[0],), dtype=np.float32)
        weights[:b.num_valid_examples] = 1.0
        return (b.target_index, b.path_source_token_indices,
                b.path_indices, b.path_target_token_indices,
                b.context_valid_mask, weights)

    def _device_batch(self, b: BatchTensors, process_local: bool = True):
        """process_local=True for training (each host contributes its own
        shard; global batch scales with host count), False for eval and
        predict (all hosts feed the same batch)."""
        arrays = self._host_batch_arrays(b)
        if self.mesh is not None:
            return shard_batch(self.mesh, arrays,
                               process_local=process_local,
                               shard_contexts=self.shard_contexts)
        # materialize on device HERE (async dispatch) — without this
        # the arrays ride into the jitted step as numpy and the
        # transfer happens on the MAIN thread at call time, making the
        # prefetch thread parse-only (round-4 infeed A/B finding)
        return tuple(jnp.asarray(a) for a in arrays)

    def _training_staircase(self):
        """(groups, staircase): the rectangles the train step takes
        table rows over (data/staircase.py), worked out from the
        training shard's bag lengths for one device's rows of a batch,
        and the number of devices a batch is dealt to; (1, None) where
        today's step runs alone: no training data, a text corpus, the
        int8 or sparse step, a mesh that splits tables or contexts, a
        chunked infeed (its batches reach the step as slices, unmarked),
        several processes (each would choose its step by its own rows,
        and one program has to run on all), bags so full that the
        staircase is the whole rectangle."""
        cfg = self.config
        groups = 1
        if self.mesh is not None:
            shape = dict(self.mesh.shape)
            if shape.get(MODEL_AXIS, 1) > 1 or shape.get(CONTEXT_AXIS, 1) > 1:
                return 1, None
            groups = shape.get(DCN_AXIS, 1) * shape.get(DATA_AXIS, 1)
        if (not cfg.is_training or cfg.SPARSE_EMBEDDING_UPDATES
                or self.dims.tables_dtype == "int8"
                or jax.process_count() > 1
                or (cfg.INFEED_CHUNK > 1 and self.mesh is None)
                or cfg.TRAIN_BATCH_SIZE % groups):
            return 1, None
        reader = open_reader(cfg.data_path("train"), self.vocabs,
                             cfg.MAX_CONTEXTS, cfg.TRAIN_BATCH_SIZE)
        if not isinstance(reader, BinaryShardReader) \
                or reader.pad_index != PAD_ID:
            return 1, None
        rows = cfg.TRAIN_BATCH_SIZE // groups
        stairs = staircase.from_lengths(
            staircase.shard_lengths(reader.data, reader.max_contexts,
                                    reader.pad_index),
            rows, reader.max_contexts)
        if stairs == ((0, rows),):      # the whole rectangle
            return 1, None
        self.log(f"embedding rows taken over the staircase {stairs} of "
                 f"{rows} x {reader.max_contexts} slots a device")
        return groups, stairs

    def _train_device_batch(self, b: BatchTensors) -> TrainBatch:
        """`_device_batch` for the training infeed, with the producer's
        answer on it: whether every id of a whole batch outside the
        staircase is PAD (`staircase.fits`; an ordered batch of the
        shard the staircase was sized from does, nearly always), the
        slots the step it chooses takes table rows for and, where the
        encoder is a block that runs over the staircase
        (`models/seq_block.py`), the pairs a head of one of its softmax
        mixers scores and the positions every layer's feed-forward half
        runs over."""
        stairs, groups = self._staircase, self._stair_groups
        whole = b.num_valid_examples == b.target_index.shape[0]
        fits = stairs is not None and whole and staircase.fits(
            stairs, (b.path_source_token_indices, b.path_indices,
                     b.path_target_token_indices), groups)
        contexts = self.dims.max_contexts
        if fits:
            slots = groups * staircase.area(stairs, contexts)
        else:
            slots = b.num_valid_examples * contexts
            if stairs is not None and whole and not self._full_step_logged:
                self._full_step_logged = True
                self.log("a whole batch does not fit the staircase: it "
                         "runs the full step (compiled at its first use)")
        batch = TrainBatch(self._device_batch(b), fits, slots)
        if encoder_spec(self.dims.encoder_type).scores_by_staircase:
            # beside `gather_slots` on the batch's `infeed/transfer`
            # span (data/prefetch.py): what the step `_by_fit` chooses
            # for this batch was compiled to score and to feed forward,
            # by the functions the encoder compiled it by; the host's
            # word, not the device's
            from code2vec_tpu.models.seq_block import (core_blocks,
                                                       ff_rectangles)
            mine = stairs if fits else None
            blocks = core_blocks(mine, self.mesh, contexts)
            batch.attn_pairs = (
                b.num_valid_examples * contexts ** 2 if blocks is None
                else staircase.attn_pairs(blocks))
            batch.ff_slots = (
                b.num_valid_examples * contexts
                if ff_rectangles(mine, self.mesh, contexts) is None
                else staircase.area(stairs, contexts))
        return batch

    def _train_infeed(self, reader, instrument=None, heartbeat=None):
        from code2vec_tpu.data.prefetch import build_train_infeed
        if self._staircase is not None \
                and isinstance(reader, BinaryShardReader):
            reader.order_by_length(self._stair_groups)
        return build_train_infeed(
            reader, chunk=self.config.INFEED_CHUNK,
            depth=self.config.INFEED_PREFETCH, mesh=self.mesh,
            host_arrays_fn=self._host_batch_arrays,
            device_batch_fn=self._train_device_batch, log=self.log,
            instrument=instrument, heartbeat=heartbeat)


    def _ids_to_words(self, topk_ids: np.ndarray) -> List[List[str]]:
        tv = self.vocabs.target_vocab
        return [[tv.lookup_word(int(i)) for i in row] for row in topk_ids]

    # ---- train (SURVEY.md §4.2) ----
    def train(self) -> None:
        cfg = self.config
        # auto-resume (ISSUE 10): the ONE shared epoch-offset
        # arithmetic (models/setup.py — the recovery contract both
        # heads must agree on)
        from code2vec_tpu.models.setup import (infeed_split,
                                               resume_epoch_offset)
        completed_epochs = resume_epoch_offset(
            cfg, self.step_num, self._n_train_examples, self.log)
        # per-host infeed split from the LIVE process set (ISSUE 13):
        # a supervisor-re-formed cohort re-deals the same global
        # stream over however many survivors joined this launch
        host_shard, num_host_shards = infeed_split()
        reader = open_reader(
            cfg.data_path("train"), self.vocabs, cfg.MAX_CONTEXTS,
            cfg.TRAIN_BATCH_SIZE, shuffle=True, seed=cfg.SEED,
            host_shard=host_shard, num_host_shards=num_host_shards,
            epoch_offset=completed_epochs)
        self.log(f"starting training: dims={self.dims}, "
                 f"devices={len(jax.devices())}, mesh={self.mesh}")
        window_examples = 0
        window_start = time.time()
        profiler = StepProfiler(cfg.PROFILE_DIR, cfg.PROFILE_START_STEP,
                                cfg.PROFILE_STEPS, self.log)
        from code2vec_tpu.training.scalars import ScalarWriter
        scalars = ScalarWriter(cfg.TENSORBOARD_DIR
                               if jax.process_index() == 0 else None)
        # Unified run telemetry (code2vec_tpu/obs/): per-step
        # step_ms/infeed_wait_ms/loss events + device-memory gauges when
        # --telemetry_dir is set; the disabled path is one boolean check
        # per step (recorder.enabled) and wrap() returns the infeed
        # unchanged.
        from code2vec_tpu.obs import (SpanChannel, Telemetry, Tracer,
                                      TrainStepRecorder, Watchdog,
                                      build_live_plane)
        telemetry = Telemetry.create(
            cfg.TELEMETRY_DIR, config=cfg, mesh=self.mesh,
            component="train", scalar_writer=scalars, log=self.log)
        if cfg.METRICS_PORT > 0 and not telemetry.enabled:
            # --metrics_port without --telemetry_dir: live pull-based
            # exposition over an in-memory registry (scrapeable run,
            # no JSONL persistence; per-step recording — and its
            # documented device-sync trade — applies either way)
            telemetry = Telemetry.memory("train")
        self.telemetry = telemetry
        live_plane = cfg.METRICS_PORT > 0 or cfg.ALERTS_MODE != "off"
        if (cfg.ASYNC_CHECKPOINT or cfg.TRACE
                or cfg.WATCHDOG_STALL_S > 0 or live_plane):
            # the checkpoint writer, the infeed producer (trace spans),
            # the watchdog/health monitors and the exposition handler
            # all record into / read this registry from other threads
            telemetry.make_threadsafe()
        # request-scoped tracing (--trace) + stall watchdog
        # (--watchdog_stall_s): per-step span trees linking the infeed
        # batch consumed and the async save triggered, and liveness
        # deadlines on the loop / infeed producer / checkpoint writer.
        # Off (the defaults), both are shared no-op singletons.
        tracer = Tracer.create(telemetry) if cfg.TRACE \
            else Tracer.disabled()
        self.tracer = tracer
        watchdog = Watchdog.create(
            telemetry, stall_s=cfg.WATCHDOG_STALL_S,
            mode=cfg.WATCHDOG_MODE, tracer=tracer, log=self.log)
        loop_hb = watchdog.register("train_loop")
        self._ckpt_heartbeat = watchdog.register("checkpoint_writer")
        # live metrics plane (ISSUE 7): health monitors + alert rules
        # swept on a cadence thread OFF the hot path, and the
        # /metrics //healthz //vars exposition server — one shared
        # wiring (obs/exposition.build_live_plane); no-op singletons
        # when the flags are off.
        from code2vec_tpu.obs.alerts import default_train_rules
        from code2vec_tpu.obs.health import default_train_monitors
        plane = build_live_plane(
            telemetry, metrics_port=cfg.METRICS_PORT,
            alerts_mode=cfg.ALERTS_MODE,
            alerts_rules=cfg.ALERTS_RULES,
            health_every_s=cfg.HEALTH_EVERY_S, watchdog=watchdog,
            monitors=default_train_monitors(),
            default_rules=default_train_rules,
            # identity block on /vars (ISSUE 17): the fleet collector
            # labels this member and keys its restart re-handshake on
            # run_id changes
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
        # routed experts: `moe/route` records go to the --trace log too
        route_recorder = getattr(self._train_step, "route_recorder", None)
        if route_recorder is not None:
            route_recorder.tracer = tracer
        watchdog.start()
        plane.start()
        # tools/obs_top.py derives pc/s = examples-rate x this gauge
        # (static: a set-once config echo must not read as stale)
        telemetry.gauge("train/max_contexts", cfg.MAX_CONTEXTS,
                        emit=False, static=True)
        model_shards = 1 if self.mesh is None else \
            int(self.mesh.shape.get(MODEL_AXIS, 1))
        # the floors divide bytes by this chip's published HBM peak;
        # a device_kind the table does not list gets no floor gauge
        peak_gbps = device.hbm_peak_gbps()
        if peak_gbps is None:
            self.log("no analytic floor gauges: device_kind "
                     f"{jax.local_devices()[0].device_kind!r} has no "
                     "entry in code2vec_tpu.device.HBM_PEAK_GBPS")
        if (cfg.SPARSE_EMBEDDING_UPDATES and model_shards == 1
                and peak_gbps is not None):
            # live optimizer-efficiency plane (round 13): publish the
            # [U, E]-aware analytic step floor once; the health
            # engine's opt_efficiency monitor divides it by the
            # observed p50 step time every sweep, so a step-time
            # regression is visible on /metrics and tools/obs_top.py
            # mid-run, not just at bench time. (Static: analytic
            # facts, not heartbeats. Data-parallel meshes publish the
            # PER-DEVICE model — round 14: forward/backward
            # per-occurrence traffic covers the device's batch shard,
            # the apply phase covers the all-gathered GLOBAL list
            # mesh_sparse_apply replicates — which is the standing
            # assertion that no dense [V, E] carrier exists on the
            # data-parallel sparse path. Row-sharded tables
            # (model axis > 1) publish nothing: the window-masked
            # apply is not described by this model, and without the
            # gauge the monitor correctly stays 'unknown' instead of
            # reading false-good/bad.)
            from code2vec_tpu.training.sparse_update import (
                sparse_step_floor_bytes, sparse_update_phase_bytes)
            ns = cfg.NUM_SAMPLED_CLASSES if cfg.USE_SAMPLED_SOFTMAX else 0
            data_shards = 1 if self.mesh is None else max(1, int(
                self.mesh.shape.get(DCN_AXIS, 1)
                * self.mesh.shape.get(DATA_AXIS, 1)))
            procs = jax.process_count()
            step_bytes = sparse_step_floor_bytes(
                self.params, cfg.TRAIN_BATCH_SIZE, cfg.MAX_CONTEXTS,
                num_sampled=ns, data_shards=data_shards,
                processes=procs)
            upd_bytes = sparse_update_phase_bytes(
                self.params, cfg.TRAIN_BATCH_SIZE, cfg.MAX_CONTEXTS,
                num_sampled=ns, processes=procs)
            ceiling = peak_gbps * 1e9
            telemetry.gauge("train/step_floor_ms",
                            step_bytes / ceiling * 1e3, emit=False,
                            static=True)
            telemetry.gauge("train/sparse_update_bytes", upd_bytes,
                            emit=False, static=True)
            telemetry.gauge("train/sparse_update_floor_ms",
                            upd_bytes / ceiling * 1e3, emit=False,
                            static=True)
        loop_hb.busy()  # the first deadline covers step-0 compile too
        steps_into_training = 0
        # Double-buffered infeed (SURVEY.md §3.3): host parse +
        # host->device transfer of batch k+1 overlap step k on a daemon
        # thread; the loop below never blocks on the host between steps.
        # persistent_epochs keeps the SAME producer thread warm across
        # epoch boundaries (it parses/transfers epoch k+1 while the
        # boundary save + eval run) instead of cold-restarting it and
        # re-filling the double buffer each epoch.
        from code2vec_tpu.data.prefetch import persistent_epochs
        from code2vec_tpu.obs import infeed_produce_instrument
        infeed_hb = watchdog.register("infeed_producer")
        infeed = self._train_infeed(
            reader,
            instrument=infeed_produce_instrument(tracer, infeed_channel),
            heartbeat=infeed_hb if watchdog.enabled else None)
        # chaos failpoints (--faults, ISSUE 10): disarmed — the default
        # — each is one attribute read per step (the obs discipline)
        from code2vec_tpu.resilience import faults, retry
        if telemetry.enabled:
            retry.set_telemetry(telemetry)
        nan_fp, kill_fp = faults.train_step_points()
        try:
            for epoch, epoch_batches in persistent_epochs(
                    infeed, cfg.NUM_TRAIN_EPOCHS,
                    first_epoch=completed_epochs + 1):
                for dev_batch, batch in recorder.wrap(epoch_batches):
                    profiler.tick(steps_into_training, self.params)
                    # step rng keyed on the ABSOLUTE step (not a
                    # sequentially split stream): a run killed at step
                    # k and auto-resumed draws the same dropout /
                    # sampling keys the uninterrupted run would —
                    # recovery replays the trajectory bit-for-bit
                    step_rng = jax.random.fold_in(self.rng,
                                                  self.step_num)
                    self.params, self.opt_state, loss = self._train_step(
                        self.params, self.opt_state, dev_batch, step_rng)
                    if nan_fp.armed and nan_fp.hit():
                        loss = loss * float("nan")  # poison the loss
                    if kill_fp.armed:
                        kill_fp.fire(step=self.step_num + 1)
                    self.step_num += 1
                    steps_into_training += 1
                    if steps_into_training == 1:
                        # where set-up went, once: the step's compile
                        # is in the record by now
                        setup_trace.report(self.log, tracer)
                    window_examples += batch.num_valid_examples
                    loss_f = (recorder.end_step(self.step_num, loss,
                                                batch.num_valid_examples,
                                                params=self.params)
                              if recorder.enabled else None)
                    if self.step_num % cfg.NUM_BATCHES_TO_LOG_PROGRESS == 0:
                        if loss_f is None:
                            # device sync only on log steps
                            loss_f = float(loss)
                        dt = time.time() - window_start
                        ex_s = window_examples / max(dt, 1e-9)
                        # path-contexts/sec = examples/sec * MAX_CONTEXTS —
                        # the BASELINE.json metric (SURVEY.md §4.2).
                        self.log(
                            f"epoch {epoch} step {self.step_num}: "
                            f"loss {loss_f:.4f}, {ex_s:.1f} ex/s, "
                            f"{ex_s * cfg.MAX_CONTEXTS:.0f} path-contexts/s")
                        scalars.write(self.step_num, {
                            "train/loss": loss_f,
                            "train/examples_per_sec": ex_s,
                            "train/path_contexts_per_sec":
                                ex_s * cfg.MAX_CONTEXTS})
                        window_examples, window_start = 0, time.time()
                epoch_end_work = False
                if cfg.is_saving and epoch % cfg.SAVE_EVERY_EPOCHS == 0:
                    # kick the save FIRST (async: returns after the
                    # snapshot) so eval below runs while the writer drains —
                    # boundary cost ~ max(eval, save tail), not save + eval
                    self._save_epoch = epoch  # -> step topology record
                    self.save(cfg.save_path, block=False)
                    epoch_end_work = True
                if cfg.is_testing and epoch % cfg.SAVE_EVERY_EPOCHS == 0:
                    eval_span = telemetry.span("train/eval_ms")
                    try:
                        results = self.evaluate()
                    except BaseException:
                        eval_span.cancel()  # dead eval: drop, don't leak
                        raise
                    eval_ms = eval_span.stop()
                    self.log(f"epoch {epoch} evaluation: {results}")
                    scalars.write(self.step_num, {
                        "eval/loss": results.loss,
                        "eval/top1": results.topk_acc[0],
                        "eval/subtoken_f1": results.subtoken_f1,
                        "eval/subtoken_precision": results.subtoken_precision,
                        "eval/subtoken_recall": results.subtoken_recall})
                    telemetry.event("eval", epoch=epoch, step=self.step_num,
                                    loss=results.loss,
                                    subtoken_f1=results.subtoken_f1,
                                    eval_ms=round(eval_ms, 3))
                    epoch_end_work = True
                if epoch_end_work:
                    # boundary work is progress: re-arm the loop's
                    # deadline so a long save/eval doesn't read as a
                    # stall (size --watchdog_stall_s above eval time)
                    loop_hb.beat()
                    # reset the throughput window: checkpoint + eval wall
                    # time must not be silently absorbed into the next
                    # epoch's first ex/s figure
                    window_examples, window_start = 0, time.time()
            if self._ckpt_writer is not None:
                # hard commit barrier: training is not done until the last
                # checkpoint's `state` rename committed (re-raises a
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
        if route_recorder is not None:
            # the loop is over and its state synced: the last steps'
            # counts are read with no wait of their own
            route_recorder.flush()
        telemetry.close()
        scalars.close()
        self.log("training done")

    def _my_global_rows(self, local_batch_size: int) -> np.ndarray:
        """Positions of THIS host's rows inside the global batch built by
        shard_batch(process_local=True), discovered empirically (a tag
        array round-trip) rather than assumed from device order; cached —
        the layout is fixed for a given mesh and batch size."""
        key = (local_batch_size,)
        if getattr(self, "_row_map", None) is None:
            self._row_map = {}
        if key not in self._row_map:
            tags = np.full((local_batch_size,), jax.process_index(),
                           np.int32)
            gtags = fetch_global(shard_batch(
                self.mesh, (tags,), process_local=True)[0])
            pos = np.nonzero(gtags == jax.process_index())[0]
            assert len(pos) == local_batch_size
            self._row_map[key] = pos
        return self._row_map[key]

    # ---- evaluate (SURVEY.md §4.3) ----
    def evaluate(self) -> EvaluationResults:
        cfg = self.config
        assert cfg.test_data_path, "evaluate requires --test"
        multi = jax.process_count() > 1
        # Multi-host: each host parses and feeds a DISJOINT shard of the
        # eval file (global eval batch = H x TEST_BATCH_SIZE), decodes
        # only its own rows, and the metric partials are summed across
        # hosts at the end — no redundant parsing, eval scales with H.
        reader = open_reader(
            cfg.test_data_path, self.vocabs, cfg.MAX_CONTEXTS,
            cfg.eval_batch_size, shuffle=False, keep_strings=True,
            host_shard=jax.process_index() if multi else 0,
            num_host_shards=jax.process_count() if multi else 1)
        acc = MetricAccumulator(
            cfg.TOP_K_WORDS_CONSIDERED_DURING_PREDICTION)
        from code2vec_tpu.data.prefetch import prefetch_to_device
        infeed = prefetch_to_device(
            reader, lambda b: self._device_batch(b, process_local=multi),
            cfg.INFEED_PREFETCH)
        for dev_batch, batch in infeed:
            loss_sum, topk_ids, _ = self._eval_step(self.params, dev_batch)
            nv = batch.num_valid_examples
            names = (batch.target_strings[:nv] if batch.target_strings
                     else [self.vocabs.target_vocab.lookup_word(int(i))
                           for i in batch.target_index[:nv]])
            topk_global = fetch_global(topk_ids)
            if multi:
                mine = self._my_global_rows(batch.target_index.shape[0])
                topk_global = topk_global[mine]
            words = self._ids_to_words(topk_global[:nv])
            # loss_sum is computed over the GLOBAL batch (weights mask
            # padding), identical on every host — count it once.
            acc.update_batch(names, words,
                             float(loss_sum)
                             if (not multi or jax.process_index() == 0)
                             else 0.0)
        if multi:
            acc.merge_across_hosts()
        return acc.results()

    # ---- predict raw extractor lines (SURVEY.md §4.4) ----
    def prepare_predict_rows(self, predict_data_lines: Iterable[str]
                             ) -> PreparedRows:
        """Host half of `predict`: raw extractor lines -> un-padded
        per-method index rows. Pure host work — the serving layer runs
        this on client threads so the batcher thread only touches the
        device. Timed as `serve/parse_ms` (the pre-split `encode_ms`
        covered parse + pad; the phases now report separately)."""
        parse_span = self.telemetry.span("serve/parse_ms")
        try:
            lines = [ln for ln in predict_data_lines if ln.strip()]
            labels, src, pth, dst, mask, tstr, cstr = parse_c2v_rows(
                lines, self.vocabs, self.config.MAX_CONTEXTS,
                keep_strings=True)
        except BaseException:
            # a malformed row must not leak the span, and a dead parse
            # must not land in the parse_ms histogram
            parse_span.cancel()
            raise
        parse_span.stop()
        return PreparedRows(labels, src, pth, dst, mask, tstr, cstr)

    def predict_bucket_size(self, n: int) -> int:
        """Padded leading dim for an `n`-method predict batch: the next
        power of two (the jitted step compiles O(log n) variants instead
        of one per method count), rounded up to a multiple of the data
        axis when a mesh shards the batch."""
        padded_n = max(1, 1 << (n - 1).bit_length())
        if self.mesh is not None:
            # batch dim must divide the data axis to shard over the mesh
            # batch shards over ('dcn','data') jointly
            dax = self.mesh.shape[DATA_AXIS] * self.mesh.shape[DCN_AXIS]
            padded_n = -(-padded_n // dax) * dax
        return padded_n

    def warmup_predict(self, max_batch: int) -> List[int]:
        """Pre-compile the predict step's shape buckets up to (and
        including) `max_batch`'s bucket, so steady-state serving
        triggers zero new jit compilations. Returns the bucket sizes."""
        buckets = sorted({self.predict_bucket_size(n)
                          for n in [1 << i for i in range(
                              max(1, max_batch).bit_length())]
                          + [max(1, max_batch)]})
        # Commit the params to their current placement BEFORE the
        # warmup compiles: a hot weight swap restores COMMITTED arrays
        # (orbax restores to explicit shardings), and jit keys on
        # committedness — warming up against uncommitted init params
        # would make every post-swap batch a recompile.
        self.params = jax.tree_util.tree_map(
            lambda x: jax.device_put(x, x.sharding)
            if hasattr(x, "sharding") else x, self.params)
        for b in buckets:
            batch = (np.zeros((b,), np.int32),
                     np.zeros((b, self.dims.max_contexts), np.int32),
                     np.zeros((b, self.dims.max_contexts), np.int32),
                     np.zeros((b, self.dims.max_contexts), np.int32),
                     np.zeros((b, self.dims.max_contexts), np.float32),
                     np.zeros((b,), np.float32))
            if self.mesh is not None:
                batch = shard_batch(self.mesh, batch, process_local=False)
            out = self._predict_step(self.params, batch)
            jax.block_until_ready(out)
        return buckets

    def predict_compile_count(self) -> int:
        """Number of compiled predict-step variants. Serving asserts
        this stays flat after `warmup_predict` — the
        zero-new-compilations acceptance check, which a sentinel for
        "cannot tell" would pass vacuously, so a jit without the
        counter is an error."""
        cache_size = getattr(self._predict_step, "_cache_size", None)
        if cache_size is None:
            raise RuntimeError(
                "the jitted predict step exposes no _cache_size(): "
                "compilations under load cannot be counted on this JAX")
        return int(cache_size())

    def predict_device(self, prepared: PreparedRows):
        """Device phase of `predict`: pad the rows to their
        power-of-two bucket, run the jitted step once, fetch. Returns
        host arrays `(topk_ids, topk_probs, attention, code)` trimmed
        to `prepared.n` rows — decoding is a separate host phase
        (`decode_predictions`) so the serving batcher can fan it out to
        client threads instead of serializing it after every batch."""
        n = prepared.n
        # host phase: rows -> padded device batch (serve/encode_ms).
        # Trace spans (--trace) parent implicitly to the batcher's
        # serve/batch_flush span (thread-local current — this runs ON
        # the batcher thread when serving); off = one boolean check.
        tracing = self.tracer.enabled
        encode_span = self.telemetry.span("serve/encode_ms")
        t_encode = self.tracer.start_span("serve/encode", n=n) \
            if tracing else None
        try:
            padded_n = self.predict_bucket_size(n)
            weights = np.zeros((padded_n,), dtype=np.float32)
            weights[:n] = 1.0
            labels, src, pth, dst, mask = _pad_batch(
                (prepared.labels, prepared.src, prepared.pth,
                 prepared.dst, prepared.mask), padded_n)
            batch = (labels, src, pth, dst, mask, weights)
            if self.mesh is not None:
                batch = shard_batch(self.mesh, batch,
                                    process_local=False)
        except BaseException:
            # close on the error path too: an un-ended trace span sits
            # in the live-span table forever, and the batcher thread
            # serves many more requests after this one dies
            if t_encode is not None:
                t_encode.end()
            encode_span.cancel()
            raise
        if t_encode is not None:
            t_encode.end()
        encode_span.stop()
        # device phase: jitted step + host fetch (serve/predict_ms; the
        # fetch_global transfers are the device sync)
        predict_span = self.telemetry.span("serve/predict_ms")
        t_device = self.tracer.start_span("serve/device",
                                          padded_n=padded_n) \
            if tracing else None
        try:
            topk_ids, topk_probs, attn, code = self._predict_step(
                self.params, batch)
            topk_ids = fetch_global(topk_ids)[:n]
            topk_probs = fetch_global(topk_probs)[:n]
            attn = fetch_global(attn)[:n]
            code = fetch_global(code)[:n]
        except BaseException:
            if t_device is not None:
                t_device.end()
            predict_span.cancel()
            raise
        if t_device is not None:
            t_device.end()
        predict_span.stop()
        return topk_ids, topk_probs, attn, code

    def decode_predictions(self, prepared: PreparedRows, device_out
                           ) -> List[MethodPredictionResults]:
        """Host decode of `predict_device` output rows (row i of
        `device_out` is row i of `prepared`): vocab lookups + the
        attention-ranked path-contexts for interpretability."""
        cfg = self.config
        topk_ids, topk_probs, attn, code = device_out
        results = []
        for i, original in enumerate(prepared.target_strings):
            res = MethodPredictionResults(original_name=original)
            for j in range(topk_ids.shape[1]):
                word = self.vocabs.target_vocab.lookup_word(
                    int(topk_ids[i, j]))
                if word == SpecialVocabWords.PAD:
                    continue
                res.append_prediction(word, float(topk_probs[i, j]))
            # attention-ranked path-contexts for interpretability
            ctx_fields = prepared.context_strings[i]
            order = np.argsort(-attn[i])
            for j in order:
                if j >= len(ctx_fields) or prepared.mask[i, j] == 0:
                    continue
                parts = ctx_fields[j].split(",")
                if len(parts) != 3:
                    continue
                res.append_attention_path(float(attn[i, j]), parts[0],
                                          parts[1], parts[2])
            if cfg.export_code_vectors:
                res.code_vector = code[i]
            results.append(res)
        return results

    def predict_prepared(self, prepared: PreparedRows
                         ) -> List[MethodPredictionResults]:
        """Single-caller form: device phase + decode in one call.
        Accepts pre-parsed (possibly concatenated) rows."""
        if prepared.n == 0:
            return []
        return self.decode_predictions(prepared,
                                       self.predict_device(prepared))

    def predict(self, predict_data_lines: Iterable[str]
                ) -> List[MethodPredictionResults]:
        prepared = self.prepare_predict_rows(predict_data_lines)
        if prepared.n == 0:
            return []
        return self.predict_prepared(prepared)

    # ---- persistence ----
    def _checkpoint_writer(self) -> "ckpt.AsyncCheckpointWriter":
        if self._ckpt_writer is None:
            self._ckpt_writer = ckpt.AsyncCheckpointWriter(
                log=self.log,
                heartbeat=getattr(self, "_ckpt_heartbeat", None))
        return self._ckpt_writer

    def save(self, path: Optional[str] = None, block: bool = True) -> None:
        # NOTE: orbax save is a collective — every process must call it
        # (orbax coordinates a single logical writer internally); skipping
        # non-zero processes would deadlock cross-host saves. The async
        # writer preserves this: every process runs its own writer
        # thread with one-in-flight FIFO discipline, so the collective
        # sees the same per-process call order as the sync path.
        #
        # block=False (the train loop's epoch save) returns once the
        # snapshot is queued; callers that READ the checkpoint next
        # (tests, tools, end-of-training) keep the default barrier.
        path = path or self.config.save_path
        assert path
        state = {"params": self.params, "opt_state": self.opt_state,
                 "step": self.step_num}
        extra = {"use_sampled_softmax": self.config.USE_SAMPLED_SOFTMAX,
                 "num_sampled": self.config.NUM_SAMPLED_CLASSES,
                 "sparse_embedding_updates":
                     self.config.SPARSE_EMBEDDING_UPDATES,
                 "embedding_optimizer": self.config.EMBEDDING_OPTIMIZER,
                 "trust_ratio": self.config.TRUST_RATIO,
                 "trust_ratio_scope": self.config.TRUST_RATIO_SCOPE,
                 # always the EFFECTIVE schedule: for loaded models the
                 # manifest override already set cfg.LR_SCHEDULE to what
                 # the saved opt_state structure carries
                 "lr_schedule": self.config.LR_SCHEDULE,
                 "lr_warmup_steps": self.config.LR_WARMUP_STEPS,
                 # provenance only (no structural effect on restore)
                 "adv_rename_prob": self.config.ADV_RENAME_PROB,
                 "adv_rename_mode": self.config.ADV_RENAME_MODE}
        # per-step save-time topology (ISSUE 13): epoch set by the
        # train loop at boundary saves and CONSUMED here (reset to
        # None so a later manual save at a further-trained step can't
        # stamp a stale epoch that would make resume re-train it —
        # epoch-less records fall back to the save-topology
        # arithmetic, see models/setup.resume_epoch_offset)
        topology = {"epoch": getattr(self, "_save_epoch", None)}
        self._save_epoch = None
        # trace (--trace): the save's blocked window LINKS the step that
        # triggered it (the per-step trace the recorder keeps current),
        # and the writer thread parents its train/save_write span to
        # this context — the step -> save -> commit chain is one walk
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
                writer = self._checkpoint_writer()
                writer.submit(path, state, self.step_num, self.vocabs,
                              self.dims, extra_manifest=extra,
                              max_to_keep=self.config.MAX_TO_KEEP,
                              topology=topology,
                              telemetry=self.telemetry,
                              tracer=self.tracer
                              if trace_span is not None else None,
                              trace_ctx=trace_span.context()
                              if trace_span is not None else None)
                if block:
                    writer.wait()
                blocked_ms = blocked_span.stop()
                self.log(f"queued checkpoint step {self.step_num} -> "
                         f"{path} (loop blocked {blocked_ms:.1f} ms)")
            else:
                ckpt.save_checkpoint(path, state, self.step_num,
                                     self.vocabs, self.dims,
                                     extra_manifest=extra,
                                     max_to_keep=self.config.MAX_TO_KEEP,
                                     topology=topology)
                blocked_ms = blocked_span.stop()
                # the sync save IS its own writer: total == blocked, and
                # the commit event keeps telemetry_report's boundary
                # table mode-agnostic
                self.telemetry.record_ms("train/save_total_ms",
                                         blocked_ms)
                self.telemetry.event("save_committed",
                                     step=self.step_num,
                                     total_ms=round(blocked_ms, 3))
                self.log(f"saved checkpoint step {self.step_num} -> "
                         f"{path}")
        except BaseException:
            # a failed submit/save (sticky writer error, dead disk)
            # must not leak the blocked span or leave the save trace
            # open in the live-span table
            blocked_span.cancel()
            if trace_span is not None:
                trace_span.end(outcome="error")
            raise
        if trace_span is not None:
            trace_span.end(blocked_ms=round(blocked_ms, 3))
        self.telemetry.event("save", step=self.step_num,
                             blocked_ms=round(blocked_ms, 3),
                             is_async=bool(self.config.ASYNC_CHECKPOINT))

    def release(self) -> None:
        cfg = self.config
        assert cfg.load_path
        if self._ckpt_writer is not None:
            # --load-style read of a dir this process may still be
            # writing: commit barrier first
            self._ckpt_writer.wait()
        dest = cfg.save_path or (cfg.load_path.rstrip("/") + ".release")
        ckpt.release_checkpoint(cfg.load_path, dest, self.params)
        self.log(f"released inference checkpoint -> {dest}")

    def close_session(self) -> None:
        # the reference's session-teardown hook doubles as the stop()
        # commit barrier: no checkpoint may be left half-written
        if self._ckpt_writer is not None:
            self._ckpt_writer.close()

    @staticmethod
    def _opt_param_view(params):
        """See ops/quant.opt_param_view (shared with bench.py so the
        opt_state structure can never drift between them)."""
        from code2vec_tpu.ops.quant import opt_param_view
        return opt_param_view(params)

    def get_embedding_table(self, vocab_type: VocabType) -> np.ndarray:
        key = {VocabType.Token: "token_emb", VocabType.Path: "path_emb",
               VocabType.Target: "target_emb"}[vocab_type]
        from code2vec_tpu.ops.quant import dequantize_table, is_quantized
        table = self.params[key]
        if is_quantized(table):
            table = dequantize_table(table)
        table = np.asarray(jax.device_get(table), dtype=np.float32)
        return table[:self.vocabs.get(vocab_type).size]

    def export_code_vectors_file(self, test_path: str,
                                 dest_path: str) -> None:
        """--export_code_vectors during --test: one code vector per test
        example, in input order (reference writes `<test>.vectors`)."""
        cfg = self.config
        reader = open_reader(test_path, self.vocabs, cfg.MAX_CONTEXTS,
                             cfg.eval_batch_size, shuffle=False,
                             keep_strings=True)
        encode_step = make_encode_step(self.dims,
                                       compute_dtype=self.compute_dtype,
                                       mesh=self.mesh)
        from code2vec_tpu.data.prefetch import prefetch_to_device
        infeed = prefetch_to_device(
            reader, lambda b: self._device_batch(b, process_local=False),
            cfg.INFEED_PREFETCH)
        with open(dest_path, "w", encoding="utf-8") as f:
            for dev_batch, batch in infeed:
                code = encode_step(self.params, dev_batch)
                code = fetch_global(code)[:batch.num_valid_examples]
                for row in code:
                    f.write(" ".join(f"{x:.6f}" for x in row) + "\n")
