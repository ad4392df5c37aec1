"""Configuration for code2vec-tpu.

Mirrors the reference's flat `Config` namespace and CLI flag names
(SURVEY.md §3 "Config/flags": `config.py` in the reference exposes every
hyperparameter as an UPPERCASE class attribute plus an argparse overlay and
derived path properties) so `train.sh`-style invocations run unchanged.

TPU-specific knobs (mesh shape, sampled softmax, binary shards, bf16) are
additive — absent flags keep reference defaults.
"""

from __future__ import annotations

import argparse
import dataclasses
import logging
import os
import sys
from typing import Optional

from code2vec_tpu.models.registry import names as encoder_names
from code2vec_tpu.models.registry import spec as encoder_spec


# Config attrs with NO CLI flag by design: capacity/architecture
# constants (reference parity values a flag would invite mis-tuning
# of) and loop bookkeeping. graftlint's config-drift rule enforces
# that every OTHER UPPERCASE attr is assigned from a --flag in
# load_from_args — adding a new attr forces a conscious choice: wire
# a flag (and document it in README.md) or register it here.
CONFIG_CONSTANTS = frozenset({
    "MAX_TOKEN_VOCAB_SIZE",      # reference java-large capacities
    "MAX_TARGET_VOCAB_SIZE",
    "MAX_PATH_VOCAB_SIZE",
    "DEFAULT_EMBEDDINGS_SIZE",   # model dims are checkpoint-manifest-
    "TARGET_EMBEDDINGS_SIZE",    #   owned, not flag-owned
    "DROPOUT_KEEP_RATE",
    "TEST_BATCH_SIZE",
    "SAVE_EVERY_EPOCHS",
    "MAX_TO_KEEP",
    "NUM_BATCHES_TO_LOG_PROGRESS",
    "TOP_K_WORDS_CONSIDERED_DURING_PREDICTION",
    "PROFILE_START_STEP",        # --profile_steps is the user knob
    "HEALTH_EVERY_S",            # monitor cadence; tests inject tiny
    #                              values directly, production default
    #                              is deliberately not a tuning knob
})


@dataclasses.dataclass
class Config:
    # ---- capacities (reference defaults, SURVEY.md §3 config row) ----
    MAX_CONTEXTS: int = 200
    MAX_TOKEN_VOCAB_SIZE: int = 1301136
    MAX_TARGET_VOCAB_SIZE: int = 261245
    MAX_PATH_VOCAB_SIZE: int = 911417

    # ---- model dims ----
    DEFAULT_EMBEDDINGS_SIZE: int = 128
    # Reference: TARGET_EMBEDDINGS_SIZE == code_vector_size == 3 * 128.
    TARGET_EMBEDDINGS_SIZE: Optional[int] = None  # derived: code_vector_size

    # ---- training hyperparameters ----
    DROPOUT_KEEP_RATE: float = 0.75
    TRAIN_BATCH_SIZE: int = 1024
    TEST_BATCH_SIZE: int = 1024
    NUM_TRAIN_EPOCHS: int = 20
    SAVE_EVERY_EPOCHS: int = 1
    MAX_TO_KEEP: int = 10
    NUM_BATCHES_TO_LOG_PROGRESS: int = 100
    TOP_K_WORDS_CONSIDERED_DURING_PREDICTION: int = 10
    LEARNING_RATE: float = 0.001  # tf.train.AdamOptimizer default (parity)
    # "cosine" (default) | "linear" | "constant" (reference parity).
    # A decaying schedule fixes the sampled-softmax head-class
    # late-training decay (full-LR negative-sampling overshoot; see
    # BASELINE.md round-3 decay study and training/optimizers.make_lr)
    # and lifted EVERY variant's F1 in the 50K-corpus study — the
    # shipped default (sampled+bf16+adafactor+cosine, 0.9273) beats the
    # reference-style constant-LR full softmax (0.9252).
    LR_SCHEDULE: str = "cosine"
    # "warmup_cosine" warmup length; 0 = auto (5% of total steps).
    # Only meaningful with --lr_schedule warmup_cosine (the
    # large-global-batch recipe; BASELINE.md round-4 study).
    LR_WARMUP_STEPS: int = 0
    # LAMB-style per-array trust-ratio rescale on every optimizer
    # branch (training/optimizers.make_optimizer). Changes opt_state
    # structure -> recorded in the checkpoint manifest.
    TRUST_RATIO: bool = False
    # "all" (round-4 behavior; measured harmful on tables) | "dense"
    # (LAMB standard for embedding-dominated models: trust-scale
    # TRANSFORM/ATTENTION/heads only — VERDICT r4 item 8)
    TRUST_RATIO_SCOPE: str = "all"
    SEED: int = 239

    # ---- softmax strategy (TPU addition; SURVEY.md §3.3 requires sampled
    # softmax for the java-large 261K-target config) ----
    USE_SAMPLED_SOFTMAX: bool = False
    NUM_SAMPLED_CLASSES: int = 4096

    # ---- TPU / parallelism (additive) ----
    # The platform the run must be on ('tpu' | 'cpu' | 'gpu'). code2vec.py
    # exits when JAX's platform is another one; 'cpu' pins the CPU
    # explicitly (code2vec_tpu/device.py).
    BACKEND: str = "tpu"
    MESH_DATA_AXIS: int = 0   # 0 → use all devices on the data axis
    MESH_MODEL_AXIS: int = 1  # model-parallel degree for sharded vocab tables
    MESH_CONTEXT_AXIS: int = 1  # context-parallel degree (transformer)
    MESH_DCN_AXIS: int = 1    # multi-slice data axis (batch shards over
    #                           dcn x data; cross-slice psum rides DCN)
    USE_BF16: bool = True     # compute in bfloat16 on the MXU, params f32
    # Touched-rows-only (lazy) Adam for the vocab tables
    # (training/sparse_steps.py + the round-13 sparse_update facade:
    # gathered-row differentiation, dedup + segment-sum into a compact
    # [U, E] gradient, live-rows-only apply — no dense [V, E] carrier).
    # BENCH_r05 (git history at a4bf2f7) pins the dense path at
    # optimizer efficiency 0.786 against its 8.48M pc/s fwd/bwd floor; this is the lever that
    # closes the gap (SPARSE_UPDATE_PALLAS selects the fused kernel).
    # Default off until a TPU driver round lands the measured win:
    # flags-off numerics are the shipped trajectory. Supports
    # float32/bfloat16/int8 tables, adam embedding optimizer,
    # constant LR, bag encoder (verify() gates the rest).
    SPARSE_EMBEDDING_UPDATES: bool = False
    # Storage dtype for the three vocab tables. bf16 halves the
    # gather/scatter/optimizer HBM traffic dominating java-large steps
    # (+~40% throughput measured on v5e-lite) and matched (slightly
    # beat) f32 subtoken-F1 in the 50K-vocab quality study — both in
    # BASELINE.md — so it is the default; --tables_dtype float32
    # restores exact reference numerics.
    # "int8" (ops/quant.py) additionally stores the token/path tables
    # as int8 rows + per-row scales — the sub-bf16 lever BASELINE.md's
    # structural-bound analysis names; single-device bag-encoder
    # training only (verify() gates the unsupported combinations).
    TABLES_DTYPE: str = "bfloat16"  # "float32" | "bfloat16" | "int8"
    # Optimizer for the vocab tables: "adafactor" (factored second
    # moment, no momentum — the standard large-embedding-table practice)
    # or "adam" (reference parity). Adafactor is the default since
    # round 3: it is both the fastest step (26.0 vs 33-35 ms at
    # java-large B=1024) AND the highest-F1 sampled variant on the
    # 50K-corpus study (0.9145 vs 0.9042; BASELINE.md round-3 quality
    # table). `--embedding_optimizer adam` restores reference parity.
    EMBEDDING_OPTIMIZER: str = "adafactor"
    # Fused Pallas attention-pool kernel (ops/pallas_attention.py).
    # Default on. It is a Mosaic kernel: --backend tpu runs it,
    # --backend cpu runs the XLA pool, and the model logs which.
    USE_PALLAS: bool = True
    # int8 requantize implementation (only meaningful with
    # --tables_dtype int8): "auto" = the fused Pallas row-pass
    # (ops/pallas_requant.py) on TPU, the multi-pass XLA reference
    # elsewhere; "fused" forces the kernel (interpret mode off-TPU —
    # the CPU test path); "reference" forces the multi-pass form
    # (the round-5 baseline, kept for A/B attribution).
    REQUANT_PALLAS: str = "auto"  # "auto" | "fused" | "reference"
    # Sparse table-update implementation (only meaningful with
    # --sparse_embeddings): "auto" = the fused
    # Pallas live-row kernel (ops/pallas_sparse_update.py) where it
    # compiles — a TPU and float32 tables — and the XLA segment-sum
    # reference otherwise (bf16/int8 rows cannot be DMA'd singly;
    # sparse_update._resolve_fused has the compiler's message);
    # "fused" forces the kernel (interpret mode off-TPU — the CPU test
    # path; on a TPU with bf16/int8 tables it raises that message);
    # "reference" forces the XLA form (the A/B numerics baseline). Honored under a MESH too (round 14): the compact
    # dedup/segment-sum/live-row apply runs per device inside
    # shard_map (sparse_update.mesh_sparse_apply) — no dense [V, E]
    # carrier on the data-parallel path.
    SPARSE_UPDATE_PALLAS: str = "auto"  # "auto" | "fused" | "reference"
    # Double-buffered device infeed (data/prefetch.py; SURVEY.md §3.3
    # infeed row): how many batches ahead a daemon thread runs the host
    # parse + host->device transfer. 2 = classic double buffering
    # (default); 0 = synchronous transfers in the step loop (the
    # round-3 behavior, kept for A/B measurement).
    INFEED_PREFETCH: int = 2
    # Latency-amortizing chunked infeed (prefetch.py
    # ChunkedDevicePrefetcher): group this many batches into ONE
    # host->device transfer and slice on-device. 1 = off (default).
    # For host->device links whose per-transfer latency dominates;
    # single-device only — ignored with a mesh.
    INFEED_CHUNK: int = 1
    # Async epoch checkpointing (training/checkpoint.py
    # AsyncCheckpointWriter): the train loop snapshots params/opt_state
    # with a cheap on-device copy and a background thread does the
    # device fetch + orbax write + pruning, so the loop's blocked time
    # per checkpoint is a small constant instead of the save wall time
    # (eval overlaps the writer tail; hard commit barrier at end of
    # training). `--async_checkpoint off` restores the synchronous save
    # (identical checkpoint directory layout) for A/B measurement —
    # tools/epoch_overhead.py drives the comparison.
    ASYNC_CHECKPOINT: bool = True

    # ---- batched serving (serving/server.py + serving/batcher.py):
    # a thread-safe request queue feeding a dynamic micro-batcher that
    # coalesces concurrent predict requests into the power-of-two
    # buckets the jitted predict step compiles, an LRU prediction
    # cache, and bounded-queue admission control. ----
    # Max methods per coalesced device batch. Must be a power of two:
    # it is the largest warmed shape bucket, so steady-state serving
    # never triggers a new jit compilation.
    SERVE_BATCH_MAX: int = 64
    # Coalescing window: after the first queued request, wait at most
    # this long for more before flushing (Clipper-style deadline batch).
    # 0 = greedy drain-and-flush (batches still form while the device
    # is busy). Small values keep the idle REPL's latency unchanged.
    SERVE_BATCH_TIMEOUT_MS: float = 2.0
    # Admission control: bounded request queue; submissions beyond this
    # depth are refused immediately with ServerOverloaded.
    SERVE_QUEUE_DEPTH: int = 128
    # Per-request deadline: a request still queued past this is shed
    # with ServerOverloaded instead of growing the tail. 0 = none.
    SERVE_DEADLINE_MS: float = 2000.0
    # LRU prediction cache entries (one per normalized path-context
    # bag); hits skip encode + device entirely. 0 disables.
    SERVE_CACHE_SIZE: int = 1024
    # Persistent extractor worker pool size (serving/extractor.py):
    # in-process libc2v when built, else one subprocess per file but
    # never a fresh pool spawn per request.
    SERVE_EXTRACT_WORKERS: int = 2

    # ---- external serving plane (ISSUE 18, serving/frontend.py +
    # replicas.py + reload.py + autoscale.py): HTTP front-end over a
    # replica fleet with hot weight reload and SLO autoscaling. ----
    # HTTP front-end port (POST /predict, GET /healthz /metrics
    # /pool). 0 = no socket (the in-process surface still works).
    SERVE_PORT: int = 0
    # Initial replica count: N PredictionServers (one model each)
    # behind one shared prediction cache.
    SERVE_REPLICAS: int = 1
    # Autoscaler bounds: the pool never shrinks below min or grows
    # past max, whatever the SLO rules say.
    SERVE_MIN_REPLICAS: int = 1
    SERVE_MAX_REPLICAS: int = 4
    # p99 latency SLO in ms: the autoscaler's serving_p99_slo alert
    # rule threshold (serve/request_ms:p99 > slo -> grow the pool).
    SERVE_SLO_MS: float = 250.0
    # Checkpoint-dir poll cadence for hot weight reload: committed
    # steps are sha256-verified then rolled one replica at a time.
    # 0 = reload off.
    SERVE_RELOAD_POLL_S: float = 0.0
    # Run the SLO autoscaling policy loop (off = fixed-size pool;
    # death/refill still applies either way).
    SERVE_AUTOSCALE: bool = False

    # ---- encoder architecture, a name of models/registry.py: "bag"
    # (reference parity), "transformer" (set transformer over the
    # contexts, models/transformer_encoder.py; BASELINE.json
    # configs[4]), "lfm2_moe" (the LFM2-MoE decoder block over the
    # contexts in reader order, models/lfm2_moe_encoder.py),
    # "qwen3_next" (Qwen3-Next's: gated DeltaNet and gated attention,
    # routed experts beside a shared one,
    # models/qwen3_next_encoder.py) or "joyai_flash"
    # (JoyAI-LLM-Flash's: latent attention in every layer, a scaled
    # sigmoid router beside a shared expert,
    # models/joyai_flash_encoder.py). ----
    ENCODER_TYPE: str = "bag"
    # lfm2_moe, qwen3_next, joyai_flash: the block's sizes, a JSON file
    # under the keys of the model's own config.json (hidden_size,
    # num_experts or n_routed_experts = the experts held HERE,
    # num_routed_experts, first_expert, ...;
    # models/lfm2_moe_encoder.Lfm2Dims,
    # models/qwen3_next_encoder.Qwen3NextDims,
    # models/joyai_flash_encoder.JoyaiDims).
    # benchmark/configs/java-large-lfm2moe.json is one chip's share of
    # LFM2-24B-A2B, java-large-qwen3next.json of Qwen3-Next-80B-A3B,
    # java-large-joyai.json of JoyAI-LLM-Flash.
    BLOCK_CONFIG: Optional[str] = None
    XF_LAYERS: int = 2
    # 3 heads -> head_dim = 384/3 = 128 = one MXU lane width: measured
    # 9% faster through the fused attention kernels at IDENTICAL
    # 12-epoch quality vs 4 heads (F1 0.9277 both; BASELINE.md round-4
    # transformer story). TPU-first default; --xf_heads 4 remains valid.
    XF_HEADS: int = 3
    # Per-layer rematerialization (jax.checkpoint) for deep encoders —
    # required at CodeBERT depth (12 layers) to keep activations O(1).
    XF_REMAT: bool = False
    # Ring attention over the ctx mesh axis (K/V rotate via ppermute;
    # O(C/s) per-device attention memory). Only takes effect with
    # --encoder transformer and --mesh_context > 1.
    RING_ATTENTION: bool = False

    # ---- task head: "code2vec" (method-name prediction, reference
    # parity) or "varmisuse" (pointer-style variable-misuse repair,
    # BASELINE.json configs[3]; models/varmisuse.py). ----
    HEAD: str = "code2vec"
    HEAD_EXPLICIT: bool = False  # True when --head was given on the CLI
    MAX_CANDIDATES: int = 8   # varmisuse pointer-candidate slots

    # ---- multi-host (SURVEY.md §3.3 comm-backend row): explicit
    # coordination flags; auto-detection (Cloud TPU pod / Slurm env)
    # needs no flags. ----
    DIST_COORDINATOR: Optional[str] = None   # host:port of process 0
    DIST_NUM_PROCESSES: Optional[int] = None
    DIST_PROCESS_ID: Optional[int] = None

    # ---- CLI surface (reference flag names, SURVEY.md §2 L6) ----
    train_data_path: Optional[str] = None   # --data <prefix>
    test_data_path: Optional[str] = None    # --test <file>
    save_path: Optional[str] = None         # --save <ckpt>
    load_path: Optional[str] = None         # --load <ckpt>
    is_predict: bool = False                # --predict
    release: bool = False                   # --release
    # --auto_resume: if --save already contains a checkpoint, load it
    # and continue training (preemption-friendly pod runs: the same
    # command line resumes after a restart instead of starting over).
    AUTO_RESUME: bool = False
    export_code_vectors: bool = False       # --export_code_vectors
    save_w2v: Optional[str] = None          # --save_w2v <path>
    save_t2v: Optional[str] = None          # --save_t2v <path>
    # --framework: the reference selects between its two implementations
    # (tensorflow|keras) here. This framework has exactly one
    # implementation (JAX/TPU), so the reference's values are accepted as
    # ALIASES of it — verify() logs a notice so a ported train.sh is
    # never silently ambiguous about what ran.
    DL_FRAMEWORK: str = "jax"
    VERBOSE_MODE: int = 1

    # ---- logging ----
    LOG_PATH: Optional[str] = None

    # ---- profiling (SURVEY.md §6 tracing row): --profile <dir> wraps
    # PROFILE_STEPS training steps in jax.profiler.start_trace /
    # stop_trace; the trace opens in tensorboard-plugin-profile. ----
    PROFILE_DIR: Optional[str] = None
    PROFILE_STEPS: int = 10
    PROFILE_START_STEP: int = 5  # skip compile + warmup steps

    # ---- optional TensorBoard scalars (SURVEY.md §6 metrics row):
    # --tensorboard <dir> streams train loss/throughput + eval metrics
    # as tf.summary scalars (host-side; TF is imported only when set).
    TENSORBOARD_DIR: Optional[str] = None

    # ---- unified run telemetry (code2vec_tpu/obs/): --telemetry_dir
    # <dir> opens a per-run JSONL event log + manifest and turns on
    # per-step step_ms / infeed_wait_ms / loss records, device-memory
    # gauges, and serving latency histograms. Unset (default): the
    # per-step path is a single boolean check, nothing is allocated or
    # written. NOTE: per-step records are device-sync-aware — enabling
    # telemetry serializes step dispatch against the loss transfer
    # (accurate attribution in exchange for pipelining; --profile stays
    # the non-intrusive tool).
    TELEMETRY_DIR: Optional[str] = None

    # ---- request-scoped tracing + stall watchdog (code2vec_tpu/obs/
    # trace.py + watchdog.py, ISSUE 6; both need --telemetry_dir — the
    # spans and stall dumps live in the run dir). ----
    # --trace: per-request span trees (queue -> batch -> device ->
    # decode share one trace id through the serving threads) and
    # per-step span trees (infeed_wait / step, linking the infeed batch
    # consumed and the async save triggered). Export with
    # tools/trace_report.py (--chrome for Perfetto / chrome://tracing).
    # Off (default): one boolean check on every traced path.
    TRACE: bool = False
    # --watchdog_stall_s: per-component progress deadline in seconds
    # for the heartbeating components (train loop, infeed producer,
    # checkpoint writer, serving batcher). A missed deadline emits a
    # `stall` telemetry event and dumps live spans + all thread stacks
    # + a registry snapshot to the run dir. 0 (default) = off. Size it
    # above the slowest legitimate gap (first-step jit compile, epoch
    # eval).
    WATCHDOG_STALL_S: float = 0.0
    # --watchdog_mode: "warn" records the stall and keeps running;
    # "raise" additionally makes it sticky — StallError at the stalled
    # component's next beat / the end-of-run poll (loud death over a
    # silent wedge).
    WATCHDOG_MODE: str = "warn"

    # ---- live metrics plane (code2vec_tpu/obs/exposition.py +
    # health.py + alerts.py, ISSUE 7): pull-based exposition, derived
    # health monitors, and an SLO alert engine. ----
    # --metrics_port: serve /metrics (Prometheus text format),
    # /healthz (watchdog-liveness readiness) and /vars (raw JSON
    # snapshot) from a stdlib daemon-thread HTTP server on this port.
    # 0 (default) = off. Works without --telemetry_dir (the registry
    # then lives in memory only — live scrape, no JSONL persistence).
    METRICS_PORT: int = 0
    # --alerts_mode: "off" (default) | "warn" | "raise". warn/raise
    # start the health monitors (non-finite loss, loss-spike z-score,
    # throughput regression, infeed starvation; serving adds cache-hit
    # collapse + shed burn-rate) and evaluate alert rules on a cadence
    # off the hot path, emitting edge-triggered `alert` JSONL events +
    # stdout lines. "raise" additionally makes a firing alert sticky —
    # AlertError at the training loop's next beat (the watchdog's
    # sticky-error discipline; never raised from the monitor thread).
    ALERTS_MODE: str = "off"
    # --alerts_rules: JSON file replacing the built-in rule set (see
    # README "Live metrics & alerts" for the syntax); None = defaults.
    ALERTS_RULES: Optional[str] = None
    # health-monitor / alert-rule evaluation cadence in seconds (no
    # CLI flag by design: tests inject tiny values, production runs
    # are fine at 1 Hz — the monitors read dict snapshots, so the
    # sweep never touches the hot path either way).
    HEALTH_EVERY_S: float = 1.0

    # ---- deterministic fault injection (code2vec_tpu/resilience/,
    # ISSUE 10): --faults <file-or-inline-json> arms the seeded
    # failpoint registry (sites: ckpt/write, infeed/produce,
    # train/nan_loss, train/kill, serve/extract, serve/kill,
    # dist/init).
    # Unset (default): every site is one attribute/None check, no
    # thread, no allocation. tools/chaos.py drives the scenarios.
    FAULTS: Optional[str] = None

    # ---- adversarial attacks (the noamyft fork delta, SURVEY.md §0
    # item 2; attacks/): --attack {targeted,untargeted} runs the
    # gradient-guided rename attack on --attack_input's source and
    # reports the re-extracted, re-predicted outcome. ----
    ATTACK: Optional[str] = None          # "targeted" | "untargeted"
    ATTACK_TARGET: Optional[str] = None   # target method name (targeted)
    ATTACK_INPUT: str = "Input.java"      # source file to attack
    ATTACK_METHOD_INDEX: int = 0          # which method in the file
    ATTACK_MAX_RENAMES: int = 1           # variables to rename (greedy)
    ATTACK_DEADCODE: bool = False         # insert `int <adv>;` instead
    ATTACK_TOPK: int = 32                 # exact-rescore shortlist size
    ATTACK_ITERS: int = 4                 # rename iterations / variable
    # Adversarial-training defense (attacks/defense.py): with this
    # probability each training example has one variable renamed to a
    # random legal token (occurrences replaced consistently) inside the
    # jitted train step. 0 disables (reference parity).
    ADV_RENAME_PROB: float = 0.0
    # Replacement distribution for the defense: "uniform" (random legal
    # token, round-3 behavior) or "batch" (another example's variable —
    # simulates the attack's wrong-class cue injection; the measured
    # positive-control defense, BASELINE.md round 4).
    ADV_RENAME_MODE: str = "uniform"

    def __post_init__(self) -> None:
        if self.TARGET_EMBEDDINGS_SIZE is None:
            self.TARGET_EMBEDDINGS_SIZE = self.code_vector_size
        self._logger: Optional[logging.Logger] = None

    # ---- derived properties (reference parity) ----
    @property
    def context_vector_size(self) -> int:
        # token + path + token embeddings concatenated
        return 3 * self.DEFAULT_EMBEDDINGS_SIZE

    @property
    def code_vector_size(self) -> int:
        return self.context_vector_size

    @property
    def is_training(self) -> bool:
        return bool(self.train_data_path)

    @property
    def is_testing(self) -> bool:
        return bool(self.test_data_path)

    @property
    def is_loading(self) -> bool:
        return bool(self.load_path)

    @property
    def is_saving(self) -> bool:
        return bool(self.save_path)

    @property
    def eval_batch_size(self) -> int:
        """Methods per evaluation batch. An encoder whose batch is what
        memory allows (lfm2_moe: a 2048-wide layer over every slot)
        evaluates at no more than the training batch (--batch_size)."""
        if encoder_spec(self.ENCODER_TYPE).eval_batch_at_most_train:
            return min(self.TEST_BATCH_SIZE, self.TRAIN_BATCH_SIZE)
        return self.TEST_BATCH_SIZE

    @property
    def train_data_path_prefix(self) -> Optional[str]:
        return self.train_data_path

    def data_path(self, split: str) -> str:
        """Path of one split's `.c2v` file: `<prefix>.<split>.c2v`."""
        assert self.train_data_path is not None
        return f"{self.train_data_path}.{split}.c2v"

    @property
    def word_freq_dict_path(self) -> Optional[str]:
        """The `.dict.c2v` pickle written by preprocessing (SURVEY.md §3.2)."""
        if not self.train_data_path:
            return None
        return f"{self.train_data_path}.dict.c2v"

    @property
    def model_load_dir(self) -> Optional[str]:
        return self.load_path

    @property
    def entire_model_load_path(self) -> Optional[str]:
        return self.load_path

    @property
    def entire_model_save_path(self) -> Optional[str]:
        return self.save_path

    # ---- argparse ingestion (reference flag spelling) ----
    @classmethod
    def arguments_parser(cls) -> argparse.ArgumentParser:
        p = argparse.ArgumentParser(description="code2vec-tpu")
        p.add_argument("--data", dest="data_path", default=None,
                       help="path prefix of {train,val,test}.c2v data")
        p.add_argument("--test", dest="test_path", default=None,
                       help="path to a .c2v test file")
        p.add_argument("--save", dest="save_path", default=None)
        p.add_argument("--load", dest="load_path", default=None)
        p.add_argument("--predict", action="store_true")
        p.add_argument("--release", action="store_true")
        p.add_argument("--auto_resume", action="store_true",
                       help="resume from --save's latest checkpoint "
                            "when one exists (preemption recovery)")
        p.add_argument("--export_code_vectors", action="store_true")
        p.add_argument("--save_w2v", dest="save_w2v", default=None)
        p.add_argument("--save_t2v", dest="save_t2v", default=None)
        p.add_argument("--framework", dest="dl_framework", default="jax",
                       choices=["jax", "tensorflow", "keras"],
                       help="accepted for CLI compatibility; always runs the "
                            "JAX/TPU implementation")
        p.add_argument("--backend", dest="backend", default=None,
                       choices=["tpu", "cpu", "gpu"])
        p.add_argument("--max_contexts", dest="max_contexts", type=int, default=None)
        p.add_argument("--batch_size", dest="batch_size", type=int, default=None)
        p.add_argument("--epochs", dest="epochs", type=int, default=None)
        p.add_argument("--lr", dest="lr", type=float, default=None)
        p.add_argument("--lr_schedule", dest="lr_schedule", default=None,
                       choices=["constant", "cosine", "linear",
                                "warmup_cosine"])
        p.add_argument("--warmup_steps", dest="warmup_steps", type=int,
                       default=None,
                       help="warmup_cosine warmup length "
                            "(0 = auto, 5%% of total steps)")
        p.add_argument("--trust_ratio_scope", dest="trust_ratio_scope",
                       default=None, choices=["all", "dense"])
        p.add_argument("--trust_ratio", dest="trust_ratio",
                       action="store_true",
                       help="LAMB-style per-array trust-ratio rescale "
                            "(large-global-batch recipe)")
        p.add_argument("--infeed_prefetch", dest="infeed_prefetch",
                       type=int, default=None,
                       help="batches of host->device transfer to run "
                            "ahead of the step loop (0 = synchronous)")
        p.add_argument("--infeed_chunk", dest="infeed_chunk",
                       type=int, default=None,
                       help="batches per host->device transfer "
                            "(latency amortization; 1 = off)")
        p.add_argument("--async_checkpoint", dest="async_checkpoint",
                       default=None, choices=["on", "off"],
                       help="background checkpoint writer (default on):"
                            " epoch saves block the train loop only for"
                            " an on-device snapshot; 'off' restores the"
                            " synchronous save for A/B measurement")
        p.add_argument("--sampled_softmax", dest="sampled_softmax",
                       action="store_true")
        p.add_argument("--num_sampled", dest="num_sampled", type=int, default=None)
        p.add_argument("--encoder", dest="encoder", default=None,
                       choices=list(encoder_names()))
        p.add_argument("--block_config", "--lfm_config",
                       dest="block_config", default=None,
                       help="--encoder lfm2_moe | qwen3_next | "
                            "joyai_flash: JSON file "
                            "with the block's sizes under the model's "
                            "config.json keys (num_experts = experts "
                            "held here, num_routed_experts, "
                            "first_expert); --lfm_config is another "
                            "spelling of the same option")
        p.add_argument("--xf_layers", dest="xf_layers", type=int,
                       default=None)
        p.add_argument("--xf_heads", dest="xf_heads", type=int,
                       default=None)
        p.add_argument("--xf_remat", dest="xf_remat",
                       action="store_true")
        p.add_argument("--ring_attention", dest="ring_attention",
                       action="store_true")
        p.add_argument("--head", dest="head", default=None,
                       choices=["code2vec", "varmisuse"])
        p.add_argument("--max_candidates", dest="max_candidates",
                       type=int, default=None)
        p.add_argument("--tables_dtype", dest="tables_dtype", default=None,
                       choices=["float32", "bfloat16", "int8"])
        p.add_argument("--no_bf16", dest="no_bf16", action="store_true",
                       help="compute in float32 on the MXU instead of "
                            "the bfloat16 default (A/B numerics "
                            "control; tables_dtype governs storage)")
        p.add_argument("--no_pallas", dest="no_pallas",
                       action="store_true",
                       help="disable the fused Pallas kernels (XLA "
                            "fallback everywhere; the A/B control for "
                            "the attention-pool and MHA kernels)")
        p.add_argument("--sparse_embeddings", dest="sparse_embeddings",
                       action="store_true",
                       help="touched-rows-only (lazy) Adam for the "
                            "vocab tables via the dedup + segment-sum "
                            "+ live-row sparse-update path — no dense "
                            "[V, E] gradient carrier (requires "
                            "--embedding_optimizer adam "
                            "--lr_schedule constant; float32/bfloat16/"
                            "int8 tables; see --sparse_update_pallas)")
        p.add_argument("--embedding_optimizer", dest="embedding_optimizer",
                       default=None, choices=["adam", "adafactor"])
        p.add_argument("--requant_pallas", dest="requant_pallas",
                       default=None,
                       choices=["auto", "fused", "reference"],
                       help="int8 requantize implementation: fused "
                            "Pallas row-pass (auto on TPU) or the "
                            "multi-pass XLA reference")
        p.add_argument("--sparse_update_pallas",
                       dest="sparse_update_pallas", default=None,
                       choices=["auto", "fused", "reference"],
                       help="sparse table-update implementation under "
                            "--sparse_embeddings: fused Pallas "
                            "live-row kernel (auto on a TPU with "
                            "float32 tables) or the XLA segment-sum "
                            "reference (auto elsewhere); "
                            "honored under a mesh too (the kernel "
                            "runs per device inside shard_map)")
        p.add_argument("--mesh_data", dest="mesh_data", type=int, default=None)
        p.add_argument("--mesh_model", dest="mesh_model", type=int, default=None)
        p.add_argument("--mesh_context", dest="mesh_context", type=int,
                       default=None)
        p.add_argument("--mesh_dcn", dest="mesh_dcn", type=int,
                       default=None)
        p.add_argument("--seed", dest="seed", type=int, default=None)
        p.add_argument("--dist_coordinator", dest="dist_coordinator",
                       default=None,
                       help="host:port of process 0 for multi-host runs")
        p.add_argument("--dist_num_processes", dest="dist_num_processes",
                       type=int, default=None)
        p.add_argument("--dist_process_id", dest="dist_process_id",
                       type=int, default=None)
        p.add_argument("--logs-path", dest="logs_path", default=None)
        p.add_argument("--profile", dest="profile_dir", default=None,
                       help="write a jax.profiler trace of a few "
                            "training steps to this directory")
        p.add_argument("--profile_steps", dest="profile_steps", type=int,
                       default=None)
        p.add_argument("--tensorboard", dest="tensorboard_dir",
                       default=None,
                       help="write loss/throughput/eval scalars as "
                            "TensorBoard summaries to this directory")
        p.add_argument("--telemetry_dir", dest="telemetry_dir",
                       default=None,
                       help="unified run telemetry: per-run manifest + "
                            "JSONL event log (per-step step_ms / "
                            "infeed_wait_ms / loss, device-memory "
                            "gauges, serving latency); summarize with "
                            "tools/telemetry_report.py")
        p.add_argument("--trace", dest="trace", action="store_true",
                       help="request-scoped tracing: span trees for "
                            "serving requests and train steps in the "
                            "telemetry event log (requires "
                            "--telemetry_dir); render with "
                            "tools/trace_report.py")
        p.add_argument("--watchdog_stall_s", dest="watchdog_stall_s",
                       type=float, default=None,
                       help="stall watchdog progress deadline in "
                            "seconds for the train loop / infeed "
                            "producer / checkpoint writer / serving "
                            "batcher (0 = off; requires "
                            "--telemetry_dir)")
        p.add_argument("--watchdog_mode", dest="watchdog_mode",
                       default=None, choices=["warn", "raise"],
                       help="on a missed deadline: warn (record + "
                            "dump diagnostics, keep running) or raise "
                            "(sticky StallError)")
        p.add_argument("--metrics_port", dest="metrics_port",
                       type=int, default=None,
                       help="serve /metrics (Prometheus text), "
                            "/healthz (watchdog liveness) and /vars "
                            "(JSON snapshot) on this port from a "
                            "daemon-thread HTTP server (0 = off; "
                            "works with or without --telemetry_dir)")
        p.add_argument("--alerts_mode", dest="alerts_mode",
                       default=None, choices=["off", "warn", "raise"],
                       help="training-health monitors + SLO alert "
                            "rules evaluated off the hot path: warn "
                            "records edge-triggered alert events, "
                            "raise additionally surfaces a sticky "
                            "AlertError at the train loop's next beat "
                            "(requires --telemetry_dir)")
        p.add_argument("--alerts_rules", dest="alerts_rules",
                       default=None,
                       help="JSON rule file replacing the built-in "
                            "alert rules (threshold + multi-window "
                            "burn-rate; see README)")
        p.add_argument("--serve_batch_max", dest="serve_batch_max",
                       type=int, default=None,
                       help="max methods per coalesced serving batch "
                            "(power of two; the largest warmed predict "
                            "bucket)")
        p.add_argument("--serve_batch_timeout_ms",
                       dest="serve_batch_timeout_ms", type=float,
                       default=None,
                       help="micro-batcher coalescing window in ms "
                            "(0 = greedy flush)")
        p.add_argument("--serve_queue_depth", dest="serve_queue_depth",
                       type=int, default=None,
                       help="bounded request queue depth; beyond it "
                            "submissions shed with ServerOverloaded")
        p.add_argument("--serve_deadline_ms", dest="serve_deadline_ms",
                       type=float, default=None,
                       help="per-request deadline in ms; queued past it "
                            "the request is shed (0 = none)")
        p.add_argument("--serve_cache_size", dest="serve_cache_size",
                       type=int, default=None,
                       help="LRU prediction cache entries keyed by the "
                            "normalized path-context bag (0 = off)")
        p.add_argument("--serve_extract_workers",
                       dest="serve_extract_workers", type=int,
                       default=None,
                       help="persistent extractor worker pool size")
        p.add_argument("--serve_port", dest="serve_port", type=int,
                       default=None,
                       help="HTTP front-end port (POST /predict, GET "
                            "/healthz /metrics /pool); 0 = no socket")
        p.add_argument("--serve_replicas", dest="serve_replicas",
                       type=int, default=None,
                       help="initial replica count behind the serving "
                            "front-end (one model per replica, one "
                            "shared prediction cache)")
        p.add_argument("--serve_min_replicas",
                       dest="serve_min_replicas", type=int,
                       default=None,
                       help="autoscaler floor: the pool never shrinks "
                            "below this")
        p.add_argument("--serve_max_replicas",
                       dest="serve_max_replicas", type=int,
                       default=None,
                       help="autoscaler ceiling: the pool never grows "
                            "past this")
        p.add_argument("--serve_slo_ms", dest="serve_slo_ms",
                       type=float, default=None,
                       help="p99 latency SLO in ms (the autoscaler's "
                            "serving_p99_slo rule threshold)")
        p.add_argument("--serve_reload_poll_s",
                       dest="serve_reload_poll_s", type=float,
                       default=None,
                       help="checkpoint-dir poll cadence for hot "
                            "weight reload (sha256-verified, one "
                            "replica at a time); 0 = off")
        p.add_argument("--serve_autoscale", dest="serve_autoscale",
                       action="store_true",
                       help="run the SLO autoscaling policy loop "
                            "(grow on burn-rate/p99 pages, shrink "
                            "after a sustained quiet window)")
        p.add_argument("--faults", dest="faults", default=None,
                       help="deterministic fault injection: a JSON "
                            "file (or inline JSON) arming named "
                            "failpoints — see README 'Fault "
                            "tolerance' and tools/chaos.py (unset = "
                            "all sites disarmed, zero overhead)")
        p.add_argument("--attack", dest="attack", default=None,
                       choices=["targeted", "untargeted"],
                       help="gradient-guided variable-rename attack on "
                            "--attack_input (needs --load)")
        p.add_argument("--attack_target", dest="attack_target",
                       default=None,
                       help="target method name for --attack targeted "
                            "(camelCase or subtoken|form)")
        p.add_argument("--attack_input", dest="attack_input",
                       default=None, help="source file (default "
                                          "Input.java)")
        p.add_argument("--attack_method_index", dest="attack_method_index",
                       type=int, default=None)
        p.add_argument("--attack_max_renames", dest="attack_max_renames",
                       type=int, default=None)
        p.add_argument("--attack_deadcode", dest="attack_deadcode",
                       action="store_true",
                       help="insert a dead `int <adv>;` declaration and "
                            "adversarially choose its name instead of "
                            "renaming an existing variable")
        p.add_argument("--attack_topk", dest="attack_topk", type=int,
                       default=None)
        p.add_argument("--attack_iters", dest="attack_iters", type=int,
                       default=None)
        p.add_argument("--adv_rename_prob", dest="adv_rename_prob",
                       type=float, default=None,
                       help="adversarial-training defense: probability "
                            "of randomly renaming one variable per "
                            "training example")
        p.add_argument("--adv_rename_mode", dest="adv_rename_mode",
                       default=None, choices=["uniform", "batch"],
                       help="defense replacement distribution: uniform "
                            "legal token, or another batch example's "
                            "variable (wrong-class cue training)")
        p.add_argument("-v", "--verbose", dest="verbose_mode", type=int, default=None)
        return p

    @classmethod
    def load_from_args(cls, args: Optional[list] = None) -> "Config":
        ns = cls.arguments_parser().parse_args(
            args if args is not None else sys.argv[1:])
        cfg = cls()
        cfg.train_data_path = ns.data_path
        cfg.test_data_path = ns.test_path
        cfg.save_path = ns.save_path
        cfg.load_path = ns.load_path
        cfg.is_predict = ns.predict
        cfg.release = ns.release
        cfg.AUTO_RESUME = ns.auto_resume
        cfg.export_code_vectors = ns.export_code_vectors
        cfg.save_w2v = ns.save_w2v
        cfg.save_t2v = ns.save_t2v
        cfg.DL_FRAMEWORK = ns.dl_framework
        if ns.backend is not None:
            cfg.BACKEND = ns.backend
        if ns.max_contexts is not None:
            cfg.MAX_CONTEXTS = ns.max_contexts
        if ns.batch_size is not None:
            cfg.TRAIN_BATCH_SIZE = ns.batch_size
        if ns.epochs is not None:
            cfg.NUM_TRAIN_EPOCHS = ns.epochs
        if ns.lr is not None:
            cfg.LEARNING_RATE = ns.lr
        if ns.lr_schedule is not None:
            cfg.LR_SCHEDULE = ns.lr_schedule
        if ns.warmup_steps is not None:
            cfg.LR_WARMUP_STEPS = ns.warmup_steps
        if ns.trust_ratio:
            cfg.TRUST_RATIO = True
        if ns.trust_ratio_scope is not None:
            cfg.TRUST_RATIO_SCOPE = ns.trust_ratio_scope
        if ns.infeed_prefetch is not None:
            cfg.INFEED_PREFETCH = ns.infeed_prefetch
        if ns.infeed_chunk is not None:
            cfg.INFEED_CHUNK = ns.infeed_chunk
        if ns.async_checkpoint is not None:
            cfg.ASYNC_CHECKPOINT = ns.async_checkpoint == "on"
        if ns.sampled_softmax:
            cfg.USE_SAMPLED_SOFTMAX = True
        if ns.num_sampled is not None:
            cfg.NUM_SAMPLED_CLASSES = ns.num_sampled
        if ns.encoder is not None:
            cfg.ENCODER_TYPE = ns.encoder
        if ns.block_config is not None:
            cfg.BLOCK_CONFIG = ns.block_config
        if ns.xf_layers is not None:
            cfg.XF_LAYERS = ns.xf_layers
        if ns.xf_heads is not None:
            cfg.XF_HEADS = ns.xf_heads
        if ns.xf_remat:
            cfg.XF_REMAT = True
        if ns.ring_attention:
            cfg.RING_ATTENTION = True
        if ns.head is not None:
            cfg.HEAD = ns.head
        cfg.HEAD_EXPLICIT = ns.head is not None
        if ns.max_candidates is not None:
            cfg.MAX_CANDIDATES = ns.max_candidates
        if ns.tables_dtype is not None:
            cfg.TABLES_DTYPE = ns.tables_dtype
        if ns.no_bf16:
            cfg.USE_BF16 = False
        if ns.no_pallas:
            cfg.USE_PALLAS = False
        if ns.sparse_embeddings:
            cfg.SPARSE_EMBEDDING_UPDATES = True
        if ns.embedding_optimizer is not None:
            cfg.EMBEDDING_OPTIMIZER = ns.embedding_optimizer
        if ns.requant_pallas is not None:
            cfg.REQUANT_PALLAS = ns.requant_pallas
        if ns.sparse_update_pallas is not None:
            cfg.SPARSE_UPDATE_PALLAS = ns.sparse_update_pallas
        if ns.mesh_data is not None:
            cfg.MESH_DATA_AXIS = ns.mesh_data
        if ns.mesh_model is not None:
            cfg.MESH_MODEL_AXIS = ns.mesh_model
        if ns.mesh_context is not None:
            cfg.MESH_CONTEXT_AXIS = ns.mesh_context
        if ns.mesh_dcn is not None:
            cfg.MESH_DCN_AXIS = ns.mesh_dcn
        if ns.seed is not None:
            cfg.SEED = ns.seed
        cfg.DIST_COORDINATOR = ns.dist_coordinator
        cfg.DIST_NUM_PROCESSES = ns.dist_num_processes
        cfg.DIST_PROCESS_ID = ns.dist_process_id
        if ns.logs_path is not None:
            cfg.LOG_PATH = ns.logs_path
        if ns.profile_dir is not None:
            cfg.PROFILE_DIR = ns.profile_dir
        if ns.profile_steps is not None:
            cfg.PROFILE_STEPS = ns.profile_steps
        if ns.tensorboard_dir is not None:
            cfg.TENSORBOARD_DIR = ns.tensorboard_dir
        if ns.telemetry_dir is not None:
            cfg.TELEMETRY_DIR = ns.telemetry_dir
        if ns.trace:
            cfg.TRACE = True
        if ns.watchdog_stall_s is not None:
            cfg.WATCHDOG_STALL_S = ns.watchdog_stall_s
        if ns.watchdog_mode is not None:
            cfg.WATCHDOG_MODE = ns.watchdog_mode
        if ns.metrics_port is not None:
            cfg.METRICS_PORT = ns.metrics_port
        if ns.alerts_mode is not None:
            cfg.ALERTS_MODE = ns.alerts_mode
        if ns.alerts_rules is not None:
            cfg.ALERTS_RULES = ns.alerts_rules
        if ns.serve_batch_max is not None:
            cfg.SERVE_BATCH_MAX = ns.serve_batch_max
        if ns.serve_batch_timeout_ms is not None:
            cfg.SERVE_BATCH_TIMEOUT_MS = ns.serve_batch_timeout_ms
        if ns.serve_queue_depth is not None:
            cfg.SERVE_QUEUE_DEPTH = ns.serve_queue_depth
        if ns.serve_deadline_ms is not None:
            cfg.SERVE_DEADLINE_MS = ns.serve_deadline_ms
        if ns.serve_cache_size is not None:
            cfg.SERVE_CACHE_SIZE = ns.serve_cache_size
        if ns.serve_extract_workers is not None:
            cfg.SERVE_EXTRACT_WORKERS = ns.serve_extract_workers
        if ns.serve_port is not None:
            cfg.SERVE_PORT = ns.serve_port
        if ns.serve_replicas is not None:
            cfg.SERVE_REPLICAS = ns.serve_replicas
        if ns.serve_min_replicas is not None:
            cfg.SERVE_MIN_REPLICAS = ns.serve_min_replicas
        if ns.serve_max_replicas is not None:
            cfg.SERVE_MAX_REPLICAS = ns.serve_max_replicas
        if ns.serve_slo_ms is not None:
            cfg.SERVE_SLO_MS = ns.serve_slo_ms
        if ns.serve_reload_poll_s is not None:
            cfg.SERVE_RELOAD_POLL_S = ns.serve_reload_poll_s
        if ns.serve_autoscale:
            cfg.SERVE_AUTOSCALE = True
        if ns.faults is not None:
            cfg.FAULTS = ns.faults
        if ns.attack is not None:
            cfg.ATTACK = ns.attack
        if ns.attack_target is not None:
            cfg.ATTACK_TARGET = ns.attack_target
        if ns.attack_input is not None:
            cfg.ATTACK_INPUT = ns.attack_input
        if ns.attack_method_index is not None:
            cfg.ATTACK_METHOD_INDEX = ns.attack_method_index
        if ns.attack_max_renames is not None:
            cfg.ATTACK_MAX_RENAMES = ns.attack_max_renames
        if ns.attack_deadcode:
            cfg.ATTACK_DEADCODE = True
        if ns.attack_topk is not None:
            cfg.ATTACK_TOPK = ns.attack_topk
        if ns.attack_iters is not None:
            cfg.ATTACK_ITERS = ns.attack_iters
        if ns.adv_rename_prob is not None:
            cfg.ADV_RENAME_PROB = ns.adv_rename_prob
        if ns.adv_rename_mode is not None:
            cfg.ADV_RENAME_MODE = ns.adv_rename_mode
        if ns.verbose_mode is not None:
            cfg.VERBOSE_MODE = ns.verbose_mode
        cfg.verify()
        return cfg

    def verify(self) -> None:
        """Validate flag combinations (reference `Config.verify`)."""
        # an unknown name is refused here, with the names there are
        encoder = encoder_spec(self.ENCODER_TYPE)
        if self.DL_FRAMEWORK not in ("jax", "tensorflow", "keras"):
            raise ValueError(
                f"--framework {self.DL_FRAMEWORK!r} unknown (expected "
                "jax, or the reference aliases tensorflow/keras).")
        if self.DL_FRAMEWORK != "jax":
            # reference CLI compatibility: both of the reference's
            # framework choices map onto the one JAX/TPU implementation
            self.log(f"--framework {self.DL_FRAMEWORK}: running the "
                     "JAX/TPU implementation (this framework's only "
                     "backend; the flag is accepted as an alias for "
                     "reference train.sh compatibility)")
        if not (self.is_training or self.is_loading):
            raise ValueError(
                "Must train (--data) or load a trained model (--load).")
        if self.is_predict and not self.is_loading:
            raise ValueError("--predict requires --load.")
        if self.release and not self.is_loading:
            raise ValueError("--release requires --load.")
        if self.MAX_CONTEXTS <= 0:
            raise ValueError("MAX_CONTEXTS must be positive.")
        if self.USE_SAMPLED_SOFTMAX and self.NUM_SAMPLED_CLASSES <= 0:
            raise ValueError("NUM_SAMPLED_CLASSES must be positive.")
        if self.HEAD == "varmisuse" and (self.is_predict or self.release
                                         or self.save_w2v
                                         or self.save_t2v
                                         or self.export_code_vectors):
            raise ValueError(
                "--predict/--release/--save_w2v/--save_t2v/"
                "--export_code_vectors apply to the code2vec head only.")
        if self.SPARSE_EMBEDDING_UPDATES and \
                self.EMBEDDING_OPTIMIZER != "adam":
            # the live-row update IS row-Adam; adafactor's factored
            # column stats are global over V and cannot be updated at
            # row granularity without a full-table walk
            raise ValueError(
                "SPARSE_EMBEDDING_UPDATES requires the adam embedding "
                "optimizer (the live-row kernel applies row-Adam; "
                "float32/bfloat16/int8 tables are all supported).")
        if self.REQUANT_PALLAS not in ("auto", "fused", "reference"):
            raise ValueError(
                "--requant_pallas must be auto, fused or reference "
                f"(got {self.REQUANT_PALLAS!r}).")
        if self.SPARSE_UPDATE_PALLAS not in ("auto", "fused",
                                             "reference"):
            raise ValueError(
                "--sparse_update_pallas must be auto, fused or "
                f"reference (got {self.SPARSE_UPDATE_PALLAS!r}).")
        if self.TABLES_DTYPE == "int8":
            # the int8 path covers the shipped per-chip training config
            # (bag encoder, single device); the gated combinations read
            # the token/path tables as plain arrays (transformer/vm
            # gathers, attack matvec, LAMB's ||param||) or shard by flat
            # key (mesh rules) and would need the dequantized view.
            if not encoder.table_step_variants:
                raise ValueError(
                    "--tables_dtype int8 supports the bag encoder only "
                    "(the int8 step is written for it).")
            if self.HEAD != "code2vec":
                raise ValueError(
                    "--tables_dtype int8 supports the code2vec head "
                    "only.")
            if self.MESH_MODEL_AXIS > 1 or self.MESH_CONTEXT_AXIS > 1:
                raise ValueError(
                    "--tables_dtype int8 supports data-parallel meshes "
                    "only (model/ctx sharding of {q, s} subtrees is "
                    "untested; tables replicate under DP).")
            if self.TRUST_RATIO:
                raise ValueError(
                    "--tables_dtype int8 is incompatible with "
                    "--trust_ratio (the trust rescale needs ||param|| "
                    "of the flat table the quantized step never "
                    "materializes).")
            if self.ATTACK:
                raise ValueError(
                    "--attack needs float/bf16 tables (the gradient "
                    "attack's candidate matvec reads the table as one "
                    "array); rerun with a bf16 checkpoint.")
        if self.SERVE_BATCH_MAX < 1 or (
                self.SERVE_BATCH_MAX & (self.SERVE_BATCH_MAX - 1)):
            # power of two so the batcher's flush cap IS the largest
            # warmed predict bucket — otherwise steady-state serving
            # would jit-compile an unwarmed shape under load
            raise ValueError(
                "--serve_batch_max must be a power of two "
                f"(got {self.SERVE_BATCH_MAX}).")
        if self.SERVE_BATCH_TIMEOUT_MS < 0:
            raise ValueError("--serve_batch_timeout_ms must be >= 0.")
        if self.SERVE_QUEUE_DEPTH < 1:
            raise ValueError("--serve_queue_depth must be >= 1.")
        if self.SERVE_DEADLINE_MS < 0:
            raise ValueError("--serve_deadline_ms must be >= 0.")
        if self.SERVE_CACHE_SIZE < 0:
            raise ValueError("--serve_cache_size must be >= 0.")
        if self.SERVE_EXTRACT_WORKERS < 1:
            raise ValueError("--serve_extract_workers must be >= 1.")
        if not 0 <= self.SERVE_PORT <= 65535:
            raise ValueError("--serve_port must be in [0, 65535].")
        if self.SERVE_MIN_REPLICAS < 1:
            raise ValueError("--serve_min_replicas must be >= 1.")
        if self.SERVE_MAX_REPLICAS < self.SERVE_MIN_REPLICAS:
            raise ValueError(
                "--serve_max_replicas must be >= --serve_min_replicas "
                f"(got {self.SERVE_MAX_REPLICAS} < "
                f"{self.SERVE_MIN_REPLICAS}).")
        if not (self.SERVE_MIN_REPLICAS <= self.SERVE_REPLICAS
                <= self.SERVE_MAX_REPLICAS):
            raise ValueError(
                "--serve_replicas must sit inside "
                "[--serve_min_replicas, --serve_max_replicas] "
                f"(got {self.SERVE_REPLICAS} outside "
                f"[{self.SERVE_MIN_REPLICAS}, "
                f"{self.SERVE_MAX_REPLICAS}]).")
        if self.SERVE_SLO_MS <= 0:
            raise ValueError("--serve_slo_ms must be > 0.")
        if self.SERVE_RELOAD_POLL_S < 0:
            raise ValueError("--serve_reload_poll_s must be >= 0.")
        if self.TRACE and not self.TELEMETRY_DIR:
            raise ValueError(
                "--trace requires --telemetry_dir (spans are recorded "
                "through the run's JSONL event log).")
        if self.WATCHDOG_STALL_S < 0:
            raise ValueError("--watchdog_stall_s must be >= 0.")
        if self.WATCHDOG_STALL_S > 0 and not self.TELEMETRY_DIR:
            raise ValueError(
                "--watchdog_stall_s requires --telemetry_dir (stall "
                "events and diagnostic dumps live in the run dir).")
        if self.WATCHDOG_MODE not in ("warn", "raise"):
            raise ValueError(
                "--watchdog_mode must be warn or raise "
                f"(got {self.WATCHDOG_MODE!r}).")
        if not 0 <= self.METRICS_PORT <= 65535:
            raise ValueError(
                f"--metrics_port must be in [0, 65535] "
                f"(got {self.METRICS_PORT}).")
        if self.ALERTS_MODE not in ("off", "warn", "raise"):
            raise ValueError(
                "--alerts_mode must be off, warn or raise "
                f"(got {self.ALERTS_MODE!r}).")
        if self.ALERTS_MODE != "off" and not self.TELEMETRY_DIR:
            raise ValueError(
                "--alerts_mode warn/raise requires --telemetry_dir "
                "(alert events are recorded through the run's JSONL "
                "event log; --metrics_port alone works without it).")
        if self.ALERTS_RULES and self.ALERTS_MODE == "off":
            raise ValueError(
                "--alerts_rules without --alerts_mode warn|raise "
                "would be silently ignored.")
        if self.HEALTH_EVERY_S <= 0:
            raise ValueError("HEALTH_EVERY_S must be positive.")
        if self.LR_WARMUP_STEPS < 0:
            raise ValueError("--warmup_steps must be >= 0.")
        if self.INFEED_PREFETCH < 0:
            raise ValueError("--infeed_prefetch must be >= 0.")
        if self.INFEED_CHUNK < 1:
            raise ValueError("--infeed_chunk must be >= 1.")
        if self.INFEED_CHUNK > 1 and self.INFEED_PREFETCH == 0:
            # chunking is inherently threaded (the producer stacks
            # ahead); silently running a thread under the synchronous
            # A/B control flag would confound the measurement
            raise ValueError(
                "--infeed_chunk > 1 requires --infeed_prefetch >= 1 "
                "(chunked infeed always uses the producer thread).")
        if self.LR_WARMUP_STEPS > 0 and self.LR_SCHEDULE != "warmup_cosine":
            raise ValueError(
                "--warmup_steps applies only to "
                "--lr_schedule warmup_cosine (other schedules have no "
                "warmup phase and would silently ignore it).")
        if (self.TRUST_RATIO and self.TRUST_RATIO_SCOPE == "dense"
                and self.EMBEDDING_OPTIMIZER != "adafactor"):
            raise ValueError(
                "--trust_ratio_scope dense requires "
                "--embedding_optimizer adafactor (adam runs one "
                "transform over all params; no table/dense split).")
        if self.TRUST_RATIO and self.SPARSE_EMBEDDING_UPDATES:
            raise ValueError(
                "--trust_ratio is not supported with "
                "SPARSE_EMBEDDING_UPDATES (the sparse row-update kernel "
                "bypasses the optax chain for the tables).")
        if self.SPARSE_EMBEDDING_UPDATES and self.LR_SCHEDULE != "constant":
            # the sparse row-update kernel applies a constant LR; a
            # schedule would be silently ignored
            raise ValueError(
                "SPARSE_EMBEDDING_UPDATES supports constant LR only "
                "(sparse_steps.py applies a fixed per-row learning "
                "rate).")
        if self.SPARSE_EMBEDDING_UPDATES \
                and not encoder.table_step_variants:
            # sparse_steps hard-codes the bag attention pool and would
            # silently leave an encoder's own params untrained while
            # eval runs them — a train/eval architecture mismatch.
            raise ValueError(
                "SPARSE_EMBEDDING_UPDATES supports the bag encoder only "
                "(sparse_steps.py trains no encoder's own params).")
        if not 0.0 <= self.ADV_RENAME_PROB <= 1.0:
            raise ValueError("--adv_rename_prob must be in [0, 1].")
        if self.ADV_RENAME_PROB > 0 and self.SPARSE_EMBEDDING_UPDATES:
            raise ValueError(
                "--adv_rename_prob is not supported with "
                "SPARSE_EMBEDDING_UPDATES (the sparse step has no "
                "augmentation hook).")
        if self.ADV_RENAME_PROB > 0 and self.HEAD == "varmisuse":
            raise ValueError(
                "--adv_rename_prob applies to the code2vec head only "
                "(the varmisuse train step has no augmentation hook).")
        if self.ATTACK and not self.is_loading:
            raise ValueError("--attack requires --load.")
        if self.ATTACK == "targeted" and not self.ATTACK_TARGET:
            raise ValueError(
                "--attack targeted requires --attack_target <name>.")
        if self.ATTACK and self.HEAD == "varmisuse":
            raise ValueError(
                "--attack applies to the code2vec head only.")
        if self.HEAD == "varmisuse" and (not encoder.table_step_variants
                                         or self.MESH_CONTEXT_AXIS > 1):
            # vm_scores calls the bag encode() directly; accepting
            # another --encoder here would silently train the wrong
            # architecture.
            raise ValueError(
                "--head varmisuse supports the bag encoder only "
                "(no other --encoder / --mesh_context > 1).")
        encoder.check_config(self)

    def get_logger(self) -> logging.Logger:
        if self._logger is None:
            logger = logging.getLogger("code2vec-tpu")
            logger.setLevel(logging.INFO if self.VERBOSE_MODE >= 1
                            else logging.WARNING)
            if not logger.handlers:
                sh = logging.StreamHandler(sys.stdout)
                sh.setFormatter(logging.Formatter(
                    "%(asctime)s %(levelname)s %(message)s"))
                logger.addHandler(sh)
                if self.LOG_PATH:
                    os.makedirs(os.path.dirname(self.LOG_PATH) or ".",
                                exist_ok=True)
                    fh = logging.FileHandler(self.LOG_PATH)
                    fh.setFormatter(logging.Formatter(
                        "%(asctime)s %(levelname)s %(message)s"))
                    logger.addHandler(fh)
            self._logger = logger
        return self._logger

    def log(self, msg: str) -> None:
        self.get_logger().info(msg)
