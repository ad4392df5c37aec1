"""Checkpoint / resume via orbax.

Reference parity target (SURVEY.md §6 "Checkpoint / resume"): the reference
saves with tf.train.Saver every SAVE_EVERY_EPOCHS epochs keeping
MAX_TO_KEEP=10, writes a vocab sidecar next to the checkpoint so `--load`
needs no dataset, and `--release` strips optimizer state. Here:

  <ckpt_dir>/
    step_<N>/state/      orbax pytree: params (+ opt_state + step unless released)
    vocab.pkl            Code2VecVocabs sidecar
    manifest.json        ModelDims + softmax config (to rebuild the model
                         without a dataset)

Checkpoints restore with the caller-provided sharding template, so a
checkpoint written on one mesh reloads onto another (or a single chip).

Async path (`--async_checkpoint`, default on): `AsyncCheckpointWriter`
makes the train loop's blocked time per checkpoint a small constant —
`snapshot_state` dispatches on-device copies (the train steps DONATE
params/opt_state, so by the time a background writer serializes, the
originals have been invalidated by the next step; a copy decouples the
snapshot from training for the price of one async device memcpy), and a
single background thread runs the device fetch + orbax write + pruning.
One save in flight at a time; a second submit BLOCKS until the first
commits — never drops or reorders (multi-host: every process runs its
own writer thread, so the collective orbax save keeps the same
per-process call order and write discipline as the sync path). The
torn-write protocol is unchanged: `_step_dirs` counts only step dirs
with a committed (renamed) `state`, so a writer killed mid-save leaves
auto-resume pointing at the last COMMITTED step.

Sidecars are write-once per checkpoint dir: vocabularies never change
within a run, and the manifest only carries structure (its `step` field
is advisory — `--release` derives the true step from the committed step
dirs), so epoch saves skip the re-pickle/rewrite when nothing changed.

Integrity (ISSUE 10): every committed step dir carries a
`checksums.json` per-file sha256 manifest of its `state` tree, written
by process 0 AFTER the commit rename. Restore verifies the files
against it first (`verify_step`); a mismatch — a bit-flipped leaf blob,
a truncated write the rename protocol could not see — quarantines the
step dir under `<ckpt_dir>/quarantine/` and falls back to the previous
committed step instead of feeding corrupt bytes into orbax. Hashing is
file-level rather than pytree-leaf-level on purpose: it is
resharding-proof (a checkpoint written on one mesh reloads onto
another — per-shard leaf digests would not survive that) and catches
exactly the storage-rot failure mode quarantine exists for. A committed
step WITHOUT a checksums file (pre-integrity checkpoints, or a death in
the rename->checksums window) restores as before, unverified.

Elastic resume (ISSUE 13): each committed step also carries a
`topology.json` save-time record ({num_processes, epoch}) written by
process 0 after the commit, so an auto-resume onto a DIFFERENT cohort
size — the supervisor re-forming a mesh at N−1 after peer loss —
converts the restored step into completed epochs under the topology
that counted them (models/setup.resume_epoch_offset), and
`load_checkpoint` reshards the restored tree onto the new mesh via the
caller's template while re-verifying the same per-file checksums.

Transient checkpoint-IO errors retry through the shared
`resilience/retry` policy (single-process only — a multi-host orbax
save is a collective, and one process re-issuing it alone would
deadlock the cohort); ENOSPC is a giveup, surfacing at the commit
barrier immediately, because a full disk does not refill on a backoff
schedule. `faults.fire("ckpt/write")` sits inside the retried write so
chaos scenarios exercise both the retry and the sticky-error path.
"""

from __future__ import annotations

import dataclasses
import errno
import hashlib
import json
import os
import re
import shutil
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import orbax.checkpoint as ocp

from code2vec_tpu.models.encoder import ModelDims
from code2vec_tpu.models.registry import spec as encoder_spec
from code2vec_tpu.resilience import faults
from code2vec_tpu.resilience import retry as retry_mod
from code2vec_tpu.vocab.vocabularies import Code2VecVocabs

_STEP_RE = re.compile(r"^step_(\d+)$")

CHECKSUMS_NAME = "checksums.json"
TOPOLOGY_NAME = "topology.json"
QUARANTINE_DIRNAME = "quarantine"


class CheckpointCorrupt(RuntimeError):
    """A committed step dir failed checksum verification and no
    quarantine fallback was possible (explicit-step restore, or a
    multi-process load where a unilateral quarantine move would race
    the cohort — the supervisor quarantines before relaunch there)."""


def _step_dirs(ckpt_dir: str):
    """COMMITTED step dirs only: a preemption mid-save leaves a torn
    step_N/ holding an orbax temp dir but no renamed `state` — counting
    it would turn auto-resume (and --load latest) into a crash loop on
    exactly the interruption it exists to survive."""
    out = []
    if os.path.isdir(ckpt_dir):
        for name in os.listdir(ckpt_dir):
            m = _STEP_RE.match(name)
            if m and os.path.exists(os.path.join(ckpt_dir, name,
                                                 "state")):
                out.append((int(m.group(1)), os.path.join(ckpt_dir, name)))
    return sorted(out)


def _build_manifest(step: int, dims: ModelDims,
                    extra_manifest: Optional[Dict[str, Any]]
                    ) -> Dict[str, Any]:
    manifest = {
        "token_vocab_size": dims.token_vocab_size,
        "path_vocab_size": dims.path_vocab_size,
        "target_vocab_size": dims.target_vocab_size,
        "embeddings_size": dims.embeddings_size,
        "max_contexts": dims.max_contexts,
        "dropout_keep_rate": dims.dropout_keep_rate,
        "vocab_pad_multiple": dims.vocab_pad_multiple,
        "tables_dtype": dims.tables_dtype,
        "encoder_type": dims.encoder_type,
        "xf_layers": dims.xf_layers,
        "xf_heads": dims.xf_heads,
        "xf_mlp_ratio": dims.xf_mlp_ratio,
        "xf_remat": dims.xf_remat,
        "ring_attention": dims.ring_attention,
        "lfm": dataclasses.asdict(dims.lfm) if dims.lfm else None,
        "qwen": dataclasses.asdict(dims.qwen) if dims.qwen else None,
        "joyai": dataclasses.asdict(dims.joyai) if dims.joyai else None,
        "step": step,
    }
    if extra_manifest:
        manifest.update(extra_manifest)
    return manifest


# ckpt_dir -> weakref to the vocabs object whose pickle THIS process
# last wrote there: epoch saves with the SAME vocabs skip the re-pickle
# (vocabularies are immutable within a run), while a different vocabs
# object aimed at the same dir (a second model trained into a reused
# directory in one long-lived process) — or a stale sidecar from an
# earlier run — still gets written. Identity via weakref, not id():
# a recycled id after GC must not alias a dead object's skip.
_VOCAB_WRITTEN: Dict[str, Any] = {}


def _write_sidecars(ckpt_dir: str, vocabs: Code2VecVocabs,
                    manifest: Dict[str, Any]) -> None:
    """vocab.pkl + manifest.json, write-once semantics: skip when present
    and unchanged. The manifest's `step` field is advisory (readers that
    need the real step use the committed step dirs — see
    `load_manifest`), so a step-only difference does not force a
    rewrite."""
    import weakref

    vocab_path = os.path.join(ckpt_dir, "vocab.pkl")
    ref = _VOCAB_WRITTEN.get(ckpt_dir)
    if (ref is None or ref() is not vocabs
            or not os.path.exists(vocab_path)):
        vocabs.save(vocab_path)
        _VOCAB_WRITTEN[ckpt_dir] = weakref.ref(vocabs)
    manifest_path = os.path.join(ckpt_dir, "manifest.json")
    if os.path.exists(manifest_path):
        try:
            with open(manifest_path, encoding="utf-8") as f:
                old = json.load(f)
        except (OSError, ValueError):
            old = None
        if old is not None and (
                {k: v for k, v in old.items() if k != "step"}
                == {k: v for k, v in manifest.items() if k != "step"}):
            return
    with open(manifest_path, "w") as f:
        json.dump(manifest, f, indent=1)


# lazily built so importing this module costs nothing extra; one shared
# policy, per-call budgets (retry.py's contract)
_CKPT_IO_RETRY: Optional[retry_mod.RetryPolicy] = None


def _ckpt_io_retry() -> retry_mod.RetryPolicy:
    global _CKPT_IO_RETRY
    if _CKPT_IO_RETRY is None:
        _CKPT_IO_RETRY = retry_mod.RetryPolicy(
            "checkpoint-io", max_attempts=3, base_delay_s=0.05,
            max_delay_s=1.0, retry_on=(OSError,),
            # a full disk is not transient: surface it at the commit
            # barrier NOW instead of burning the backoff budget
            giveup=lambda e: getattr(e, "errno", None) == errno.ENOSPC)
    return _CKPT_IO_RETRY


def save_checkpoint(ckpt_dir: str, state: Dict[str, Any], step: int,
                    vocabs: Code2VecVocabs, dims: ModelDims,
                    extra_manifest: Optional[Dict[str, Any]] = None,
                    max_to_keep: int = 10,
                    topology: Optional[Dict[str, Any]] = None) -> str:
    os.makedirs(ckpt_dir, exist_ok=True)
    step_dir = os.path.join(ckpt_dir, f"step_{step}")
    path = os.path.join(step_dir, "state")

    def _write() -> None:
        # failpoint INSIDE the retried callable: slow disk (sleep),
        # ENOSPC (io_error — a giveup, lands at the commit barrier),
        # transient EIO (retried here), crash-before-rename (kill)
        faults.fire("ckpt/write", path=step_dir, step=step)
        with ocp.StandardCheckpointer() as ckptr:
            ckptr.save(os.path.abspath(path), state, force=True)

    if jax.process_count() == 1:
        _ckpt_io_retry().call(_write)
    else:
        # multi-host orbax saves are collectives: one process retrying
        # alone would deadlock its peers — the supervisor's cohort
        # relaunch is the multi-process retry
        _write()
    if jax.process_index() == 0:
        write_step_checksums(ckpt_dir, step)
        write_step_topology(ckpt_dir, step, topology)
    _write_sidecars(ckpt_dir, vocabs,
                    _build_manifest(step, dims, extra_manifest))
    # Retention: keep the newest `max_to_keep` step dirs (reference
    # MAX_TO_KEEP=10 semantics).
    steps = _step_dirs(ckpt_dir)
    for _s, d in steps[:-max_to_keep]:
        shutil.rmtree(d, ignore_errors=True)
    return path


# ---- integrity: per-file checksums, verify-on-restore, quarantine ----

def _hash_file(path: str, chunk: int = 1 << 20) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        while True:
            block = f.read(chunk)
            if not block:
                break
            h.update(block)
    return h.hexdigest()


def _state_file_digests(step_dir: str) -> Dict[str, Dict[str, Any]]:
    """{relpath-under-step_dir: {sha256, bytes}} for every file of the
    committed `state` tree, sorted for a stable manifest."""
    state_dir = os.path.join(step_dir, "state")
    out: Dict[str, Dict[str, Any]] = {}
    for base, _dirs, files in os.walk(state_dir):
        for name in sorted(files):
            p = os.path.join(base, name)
            rel = os.path.relpath(p, step_dir).replace(os.sep, "/")
            out[rel] = {"sha256": _hash_file(p),
                        "bytes": os.path.getsize(p)}
    return dict(sorted(out.items()))


def write_step_checksums(ckpt_dir: str, step: int) -> str:
    """Write `step_<N>/checksums.json` over the committed state tree.
    Runs AFTER the commit rename: a death in the rename->checksums
    window leaves a committed-but-unverified step, which restores like
    a pre-integrity checkpoint (verify_step returns None)."""
    step_dir = os.path.join(ckpt_dir, f"step_{step}")
    payload = {"step": step, "files": _state_file_digests(step_dir)}
    dest = os.path.join(step_dir, CHECKSUMS_NAME)
    tmp = dest + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(payload, f, indent=1)
    os.replace(tmp, dest)
    return dest


def write_step_topology(ckpt_dir: str, step: int,
                        extra: Optional[Dict[str, Any]] = None) -> str:
    """Write `step_<N>/topology.json`: the SAVE-TIME topology of this
    committed step (ISSUE 13 — elastic resume). An auto-resume onto a
    DIFFERENT cohort size must convert the restored step count into
    completed epochs using the topology the steps were counted under,
    not the one restoring; this per-step record is what makes that
    conversion exact across any resize history (the dir-level manifest
    is write-once and can't track per-step topology). `extra` adds
    caller fields — the train loops record the completed `epoch`, which
    makes the conversion a lookup instead of arithmetic. Written by
    process 0 after the commit rename, like the checksums manifest; a
    step WITHOUT one (pre-elastic checkpoints, or a death in the
    rename->sidecar window) resumes via the old steps//spe arithmetic
    under the current topology."""
    step_dir = os.path.join(ckpt_dir, f"step_{step}")
    payload: Dict[str, Any] = {"step": step,
                               "num_processes": jax.process_count()}
    if extra:
        payload.update({k: v for k, v in extra.items()
                        if v is not None})
    dest = os.path.join(step_dir, TOPOLOGY_NAME)
    tmp = dest + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(payload, f, indent=1)
    os.replace(tmp, dest)
    return dest


def load_step_topology(ckpt_dir: str,
                       step: int) -> Optional[Dict[str, Any]]:
    """The step's save-time topology record, or None for pre-elastic
    checkpoints (and unreadable records — resume then falls back to
    current-topology arithmetic rather than dying on a sidecar)."""
    path = os.path.join(ckpt_dir, f"step_{step}", TOPOLOGY_NAME)
    if not os.path.exists(path):
        return None
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def verify_step(ckpt_dir: str, step: int) -> Optional[bool]:
    """True = every state file matches its recorded digest; False = any
    mismatch/missing/extra file (corrupt); None = no checksums manifest
    (pre-integrity checkpoint — nothing to verify against)."""
    step_dir = os.path.join(ckpt_dir, f"step_{step}")
    manifest_path = os.path.join(step_dir, CHECKSUMS_NAME)
    if not os.path.exists(manifest_path):
        return None
    try:
        with open(manifest_path, encoding="utf-8") as f:
            recorded = json.load(f)["files"]
    except (OSError, ValueError, KeyError):
        return False  # an unreadable integrity manifest IS corruption
    actual = _state_file_digests(step_dir)
    if set(actual) != set(recorded):
        return False
    return all(actual[k]["sha256"] == v.get("sha256")
               for k, v in recorded.items())


def quarantine_step(ckpt_dir: str, step: int,
                    log: Optional[Callable[[str], None]] = None) -> str:
    """Move a corrupt step dir under `<ckpt_dir>/quarantine/` (kept for
    the postmortem, invisible to `latest_step`/retention). Returns the
    destination path."""
    qdir = os.path.join(ckpt_dir, QUARANTINE_DIRNAME)
    os.makedirs(qdir, exist_ok=True)
    src = os.path.join(ckpt_dir, f"step_{step}")
    dest = os.path.join(qdir, f"step_{step}")
    n = 0
    while os.path.exists(dest):  # a re-corrupted rewrite of the same step
        n += 1
        dest = os.path.join(qdir, f"step_{step}.{n}")
    os.replace(src, dest)
    if log is not None:
        log(f"checkpoint step {step} failed verification -> "
            f"quarantined at {dest}")
    return dest


def verify_and_resolve(ckpt_dir: str, *, quarantine: bool = True,
                       log: Optional[Callable[[str], None]] = None
                       ) -> Tuple[Optional[int], List[str]]:
    """Walk committed steps newest-first, verifying each; corrupt ones
    are quarantined (when allowed). Returns (first verified-or-
    unverifiable step usable for resume — None when none survive,
    quarantined dir paths). The supervisor runs this before every
    (re)launch so a child only ever resumes from a VERIFIED committed
    step."""
    quarantined: List[str] = []
    for step, _d in reversed(_step_dirs(ckpt_dir)):
        ok = verify_step(ckpt_dir, step)
        if ok is False:
            if not quarantine:
                raise CheckpointCorrupt(
                    f"checkpoint step {step} under {ckpt_dir} failed "
                    f"checksum verification")
            quarantined.append(quarantine_step(ckpt_dir, step, log))
            continue
        if ok is None and log is not None:
            log(f"checkpoint step {step}: no {CHECKSUMS_NAME} "
                "(pre-integrity checkpoint) — restoring unverified")
        return step, quarantined
    return None, quarantined


def snapshot_state(state: Dict[str, Any]) -> Dict[str, Any]:
    """Decouple a state pytree from the train loop: async-dispatched
    on-device copies of every jax.Array leaf. The train steps donate
    their params/opt_state buffers, so handing the ORIGINALS to a
    background writer would read deleted arrays as soon as the next step
    dispatches; the copy costs one device memcpy (dispatch returns
    immediately — the loop does not wait for the bytes) plus transient
    HBM for the duplicate until the writer drains. Non-array leaves
    (the python `step` int) pass through untouched so the saved
    structure is identical to the sync path's."""
    import jax.numpy as jnp

    return jax.tree_util.tree_map(
        lambda x: jnp.copy(x) if isinstance(x, jax.Array) else x, state)


class AsyncCheckpointWriter:
    """Background checkpoint writer: the Check-N-Run / t5x
    AsyncCheckpointer shape. `submit()` returns as soon as the snapshot
    is queued; one daemon thread runs the device fetch + serialization +
    committed-`state` rename + retention pruning. Discipline:

      - ONE save in flight: a second `submit` while the first is still
        writing blocks until it commits (never drops, never reorders —
        the orbax collective needs every process to issue the same save
        sequence).
      - `wait()` is the hard commit barrier (end of training, explicit
        `save(block=True)`, anything about to READ the checkpoint dir).
      - a failed background save is sticky: the error re-raises at the
        next `submit`/`wait`/`close` instead of letting a run train for
        hours past a dead disk.

    `save_fn` is injectable for crash-safety tests (simulate a writer
    killed before the `state` rename commits), and `clock` (default
    `time.perf_counter`) is the duration timebase — the deflaked
    timing tests (tests/test_async_checkpoint.py) drive a fake clock
    through the injected save_fn instead of betting on wall-clock
    ratios under CI contention. `heartbeat` is the obs.watchdog
    liveness hook (--watchdog_stall_s): busy at job pickup, idle after
    commit — a write hung in orbax/disk I/O stops beating and the
    watchdog dumps the writer thread's stack instead of the run going
    silently wedged."""

    def __init__(self, log: Optional[Callable[[str], None]] = None,
                 save_fn: Optional[Callable] = None,
                 heartbeat=None,
                 clock: Callable[[], float] = time.perf_counter):
        self._log = log or (lambda _m: None)
        # None -> module-level save_checkpoint, resolved at WRITE time
        # (tests monkeypatch the module function to inject slow disks
        # and torn writes)
        self._save_fn = save_fn
        self._heartbeat = heartbeat
        self._clock = clock
        self._cond = threading.Condition()
        self._job: Optional[Dict[str, Any]] = None
        self._error: Optional[BaseException] = None
        self._closed = False
        self._thread: Optional[threading.Thread] = None

    def _raise_pending(self) -> None:
        # threading.Condition's default lock is an RLock, so this is
        # safe from call sites already holding _cond
        with self._cond:
            if self._error is not None:
                err, self._error = self._error, None
                raise err

    def submit(self, ckpt_dir: str, state: Dict[str, Any], step: int,
               vocabs: Code2VecVocabs, dims: ModelDims, *,
               extra_manifest: Optional[Dict[str, Any]] = None,
               max_to_keep: int = 10, telemetry=None,
               tracer=None, trace_ctx=None,
               topology: Optional[Dict[str, Any]] = None) -> None:
        """Snapshot `state` and queue the save. Blocks only on the
        snapshot dispatch — unless a previous save is still in flight,
        in which case it blocks until that one commits. `trace_ctx`
        (with its `tracer`) is the cross-thread span handoff: the
        writer parents its `train/save_write` span to the loop-side
        save span that queued this job."""
        snap = snapshot_state(state)
        with self._cond:
            self._raise_pending()
            if self._closed:
                raise RuntimeError("AsyncCheckpointWriter is closed")
            while self._job is not None:
                self._cond.wait()
                self._raise_pending()
            self._job = {
                "ckpt_dir": ckpt_dir, "state": snap, "step": step,
                "vocabs": vocabs, "dims": dims,
                "extra_manifest": extra_manifest,
                "max_to_keep": max_to_keep, "telemetry": telemetry,
                "tracer": tracer, "trace_ctx": trace_ctx,
                "topology": topology,
            }
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._run, daemon=True, name="ckpt-writer")
                self._thread.start()
            self._cond.notify_all()

    def _run(self) -> None:
        while True:
            with self._cond:
                while self._job is None and not self._closed:
                    self._cond.wait()
                if self._job is None:
                    return  # closed and drained
                job = self._job
            hb = self._heartbeat
            try:
                if hb is not None:
                    hb.busy()  # deadline clock runs while writing
                t0 = self._clock()
                tracer = job["tracer"]
                t0_trace = tracer.clock() if tracer is not None else 0.0
                save_fn = self._save_fn or save_checkpoint
                save_fn(job["ckpt_dir"], job["state"], job["step"],
                        job["vocabs"], job["dims"],
                        extra_manifest=job["extra_manifest"],
                        max_to_keep=job["max_to_keep"],
                        topology=job["topology"])
                total_ms = (self._clock() - t0) * 1e3
                if tracer is not None:
                    # writer-side span, parented (cross-thread) to the
                    # loop's save span via the handed-off context
                    tracer.record_span(
                        "train/save_write", t0_trace, tracer.clock(),
                        parent=job["trace_ctx"], step=int(job["step"]))
                tele = job["telemetry"]
                if tele is not None:
                    tele.record_ms("train/save_total_ms", total_ms)
                    tele.event("save_committed", step=int(job["step"]),
                               total_ms=round(total_ms, 3))
                self._log(f"async checkpoint step {job['step']} "
                          f"committed -> {job['ckpt_dir']} "
                          f"({total_ms:.0f} ms in background)")
            except BaseException as e:  # surfaces at next submit/wait
                with self._cond:
                    self._error = e
            finally:
                if hb is not None:
                    hb.idle()
                with self._cond:
                    self._job = None
                    self._cond.notify_all()

    def wait(self) -> None:
        """Hard commit barrier: returns once no save is in flight;
        re-raises a background failure."""
        with self._cond:
            while self._job is not None:
                self._cond.wait()
            self._raise_pending()

    def drain_quiet(self) -> None:
        """Barrier without the re-raise (exception-path teardown: the
        original error must not be masked; a sticky writer error still
        surfaces at the next wait/submit/close)."""
        with self._cond:
            while self._job is not None:
                self._cond.wait()

    def close(self) -> None:
        """Commit barrier + writer-thread shutdown."""
        with self._cond:
            while self._job is not None:
                self._cond.wait()
            self._closed = True
            self._cond.notify_all()
            thread = self._thread
        if thread is not None:
            thread.join()
        with self._cond:
            self._raise_pending()


def latest_step(ckpt_dir: str) -> Optional[int]:
    steps = _step_dirs(ckpt_dir)
    return steps[-1][0] if steps else None


def load_manifest(ckpt_dir: str) -> Dict[str, Any]:
    """Manifest with the EFFECTIVE step: the on-disk `step` field is
    advisory (sidecars are write-once — it freezes at the dir's first
    save), so every consumer that needs the real step — the released
    checkpoint's step, the LR-schedule resume horizon in
    models/setup.py — gets it corrected here from the committed step
    dirs."""
    with open(os.path.join(ckpt_dir, "manifest.json")) as f:
        manifest = json.load(f)
    step = latest_step(ckpt_dir)
    if step is not None:
        manifest["step"] = step
    return manifest


def load_dims(ckpt_dir: str) -> ModelDims:
    m = load_manifest(ckpt_dir)
    return ModelDims(
        token_vocab_size=m["token_vocab_size"],
        path_vocab_size=m["path_vocab_size"],
        target_vocab_size=m["target_vocab_size"],
        embeddings_size=m["embeddings_size"],
        max_contexts=m["max_contexts"],
        dropout_keep_rate=m["dropout_keep_rate"],
        vocab_pad_multiple=m.get("vocab_pad_multiple", 1),
        tables_dtype=m.get("tables_dtype", "float32"),
        encoder_type=m.get("encoder_type", "bag"),
        xf_layers=m.get("xf_layers", 2),
        xf_heads=m.get("xf_heads", 4),
        xf_mlp_ratio=m.get("xf_mlp_ratio", 4),
        xf_remat=m.get("xf_remat", False),
        ring_attention=m.get("ring_attention", False),
        # the encoder's own sizes (`lfm`, `qwen`, `joyai`), read by its spec
        **encoder_spec(m.get("encoder_type", "bag")).sizes_from_manifest(m),
    )


def load_checkpoint(ckpt_dir: str, template: Dict[str, Any],
                    step: Optional[int] = None, *,
                    verify: bool = True,
                    log: Optional[Callable[[str], None]] = None
                    ) -> Dict[str, Any]:
    """Restore the pytree at `step` (default: latest) with the dtype /
    sharding layout of `template` (abstract arrays are fine).

    Verify-on-restore (default on): the step's files are checked
    against its `checksums.json` first. An EXPLICITLY requested corrupt
    step raises `CheckpointCorrupt` — the caller asked for those bytes,
    silently substituting others would be worse. A corrupt LATEST step
    is quarantined (single-process only: a multi-process unilateral
    move would race the cohort, so those raise and let the supervisor
    quarantine before relaunch) and the restore falls back to the
    previous committed step. Steps without a checksums manifest restore
    unverified, as before.

    Resharding (ISSUE 13 — the elastic-resume restore path): the
    restore honors the TEMPLATE's shardings, not the saver's, so a
    checkpoint written by an N-process cohort redistributes its
    row-sharded tables and optimizer slots across whatever mesh the
    surviving cohort rebuilt — orbax reads each process's needed byte
    ranges from the per-leaf blobs directly. Integrity survives the
    move because the checksums are per-FILE over the committed state
    tree (deliberately not per-shard — see the module docstring): the
    same `verify_step` sweep above re-verifies every file regardless
    of which topology wrote it or which will read it. A cross-topology
    restore is logged via the step's save-time `topology.json`."""
    explicit = step is not None
    while True:
        if step is None:
            step = latest_step(ckpt_dir)
        if step is None:
            raise FileNotFoundError(f"no checkpoints under {ckpt_dir}")
        if not verify or verify_step(ckpt_dir, step) is not False:
            break
        if explicit or jax.process_count() > 1:
            raise CheckpointCorrupt(
                f"checkpoint step {step} under {ckpt_dir} failed "
                f"checksum verification"
                + ("" if explicit else
                   " (multi-process load: quarantine via the "
                   "supervisor, not unilaterally)"))
        quarantine_step(ckpt_dir, step, log)
        step = None  # fall back to the previous committed step
    saved = load_step_topology(ckpt_dir, step)
    if (log is not None and saved
            and saved.get("num_processes") is not None
            and int(saved["num_processes"]) != jax.process_count()):
        log(f"checkpoint step {step}: saved by "
            f"{saved['num_processes']} process(es), restoring onto "
            f"{jax.process_count()} — resharding onto the new mesh")
    path = os.path.join(ckpt_dir, f"step_{step}", "state")
    abstract = jax.tree_util.tree_map(ocp.utils.to_shape_dtype_struct,
                                      template)
    with ocp.StandardCheckpointer() as ckptr:
        return ckptr.restore(os.path.abspath(path), abstract)


def load_vocabs(ckpt_dir: str) -> Code2VecVocabs:
    return Code2VecVocabs.load(os.path.join(ckpt_dir, "vocab.pkl"))


def release_checkpoint(load_dir: str, dest_dir: str,
                       params: Dict[str, Any]) -> None:
    """Reference `--release` (SURVEY.md §4.5): write a stripped
    inference-only checkpoint (params, no optimizer slots)."""
    os.makedirs(dest_dir, exist_ok=True)
    manifest = load_manifest(load_dir)  # step already effective
    manifest["released"] = True
    step = manifest.get("step", 0)
    path = os.path.join(dest_dir, f"step_{step}", "state")
    with ocp.StandardCheckpointer() as ckptr:
        ckptr.save(os.path.abspath(path), {"params": params}, force=True)
    shutil.copy(os.path.join(load_dir, "vocab.pkl"),
                os.path.join(dest_dir, "vocab.pkl"))
    with open(os.path.join(dest_dir, "manifest.json"), "w") as f:
        json.dump(manifest, f, indent=1)
