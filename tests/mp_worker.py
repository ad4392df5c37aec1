"""Worker process for the 2-process multi-host test.

Usage: python mp_worker.py <process_id> <port> <out_dir>

Each of the two processes provisions 4 local CPU devices, joins a
2-process distributed runtime (8 global devices), and runs ONE training
step over a global ('data','model') mesh with its own PROCESS-LOCAL half
of the batch — exactly the multi-host feed path of jax_model.train. It
writes the resulting loss and a parameter checksum for the parent test to
compare against a single-process oracle.
"""

import os
import sys

# 4 local CPU devices, pinned BEFORE the jax import (the same
# provisioning as parallel/compat.cpu_worker_env). The parent test
# strips XLA_FLAGS from the spawn env, so this append is authoritative.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4"
                           ).strip()


def main() -> None:
    pid, port, out_dir = int(sys.argv[1]), sys.argv[2], sys.argv[3]

    import jax

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    # Bounded cohort bring-up (ISSUE 14 satellite): on 1-core boxes
    # the loopback-Gloo rendezvous intermittently wedges BOTH workers
    # during bring-up — inside jax.distributed.initialize (it blocks
    # for the peer connect) or at the first collective right after it
    # (PR 12 postscript — it used to eat the module's 300 s
    # communicate() wall per attempt and error 4 tests). The
    # watchdog's deadline covers init + a probe collective and
    # hard-exits this worker on a wedge; the parent fixture's
    # fresh-port transient_distributed retry re-forms the cohort.
    from code2vec_tpu.parallel.compat import (PhaseDeadline,
                                              first_collective_barrier)
    from code2vec_tpu.parallel.distributed import maybe_initialize
    _log = lambda m: print(m, flush=True)  # noqa: E731
    first_collective_barrier(
        timeout_s=90.0,
        setup_fn=lambda: maybe_initialize(
            coordinator_address=f"127.0.0.1:{port}",
            num_processes=2, process_id=pid),
        log=_log)
    # ...and the same protection for every phase AFTER bring-up: the
    # transport race can wedge a later collective too (observed mid-
    # workload on this box). Each beat re-arms a 120 s deadline —
    # ~4x the loaded per-phase cost — so a wedge anywhere surfaces as
    # a fast retryable death, never a burned communicate() wall.
    watchdog = PhaseDeadline(timeout_s=120.0, log=_log)
    # device placement (shard_params/shard_opt_state device_puts cross
    # the process boundary) is wedge-prone but compile-free: default
    # 120 s bound (observed: a real wedge here burned a 240 s phase)
    watchdog.beat("shard-state")

    import jax.numpy as jnp
    import numpy as np
    import optax

    from code2vec_tpu.models.encoder import ModelDims, init_params
    from code2vec_tpu.parallel.distributed import fetch_global
    from code2vec_tpu.parallel.mesh import make_mesh
    from code2vec_tpu.parallel.sharding import (shard_batch,
                                                shard_opt_state,
                                                shard_params)
    from code2vec_tpu.training.steps import make_eval_step, make_train_step
    from helpers import example_batch

    assert jax.process_count() == 2 and jax.device_count() == 8

    dims = ModelDims(token_vocab_size=64, path_vocab_size=48,
                     target_vocab_size=40, embeddings_size=16,
                     max_contexts=8, dropout_keep_rate=1.0,
                     vocab_pad_multiple=2)
    mesh = make_mesh(4, 2)

    params = init_params(jax.random.PRNGKey(0), dims)
    optimizer = optax.adam(1e-2)
    opt_state = optimizer.init(params)
    params = shard_params(mesh, params)
    opt_state = shard_opt_state(mesh, opt_state, params)

    # --- train: process-local half-batch; global batch = 8 + 8 ---
    local = example_batch(seed=pid, dims=dims, batch=8)
    batch = shard_batch(mesh, local, process_local=True)
    assert batch[0].shape[0] == 16, batch[0].shape  # B scales with hosts

    # the step call carries the big XLA compiles: a loaded 1-core box
    # can legitimately take >100 s here (compat docstring), so this
    # phase gets extra headroom — still under the 300 s communicate
    # wall
    watchdog.beat("train-step", timeout_s=240.0)
    step = make_train_step(dims, optimizer, compute_dtype=jnp.float32)
    params, opt_state, loss = step(params, opt_state, batch,
                                   jax.random.PRNGKey(7))

    watchdog.beat("eval-step")
    # --- eval: identical batch on both hosts; global batch stays 8 ---
    eval_local = example_batch(seed=99, dims=dims, batch=8)
    eval_batch = shard_batch(mesh, eval_local, process_local=False)
    assert eval_batch[0].shape[0] == 8, eval_batch[0].shape
    eval_step = make_eval_step(dims, top_k=3, compute_dtype=jnp.float32)
    loss_sum, topk_ids, _ = eval_step(params, eval_batch)
    topk_host = fetch_global(topk_ids)

    watchdog.beat("checkpoint")
    # --- checkpoint save: orbax saves are collectives, every process
    # participates (jax_model.save does the same in train()) ---
    from code2vec_tpu.training import checkpoint as ckpt
    from code2vec_tpu.vocab.vocabularies import Code2VecVocabs, Vocab, \
        VocabType
    vocabs = Code2VecVocabs(
        Vocab(VocabType.Token, ["a", "b"]),
        Vocab(VocabType.Path, ["1"]),
        Vocab(VocabType.Target, ["t"]))
    ckpt_dir = os.path.join(out_dir, "ckpt")
    ckpt.save_checkpoint(ckpt_dir, {"params": params,
                                    "opt_state": opt_state, "step": 1},
                         1, vocabs, dims)
    restored = ckpt.load_checkpoint(ckpt_dir, {"params": params,
                                               "opt_state": opt_state,
                                               "step": 0})
    restored_checksum = float(sum(
        jnp.sum(fetch_global(v).astype(np.float64))
        for v in restored["params"].values()))

    watchdog.beat("async-checkpoint")
    # --- async checkpoint writer: the per-process call-order
    # discipline exercised with REAL processes (ISSUE 9 satellite).
    # Each process runs its OWN writer thread; orbax saves are
    # collectives, so commit requires both writers to issue the same
    # save sequence — two lockstep submits (the second blocks until
    # the first commits: one-in-flight), a wait() barrier, then a
    # crash-before-rename submit whose torn step dir must stay
    # invisible to latest_step on BOTH processes.
    async_dir = os.path.join(out_dir, "ckpt_async")
    writer = ckpt.AsyncCheckpointWriter()
    state = {"params": params, "opt_state": opt_state, "step": 2}
    writer.submit(async_dir, state, 2, vocabs, dims)
    state = {"params": params, "opt_state": opt_state, "step": 3}
    writer.submit(async_dir, state, 3, vocabs, dims)
    writer.wait()
    async_committed = ckpt.latest_step(async_dir)

    def killed_mid_save(ckpt_dir, state, step, vocabs, dims, **kw):
        # a preemption mid-orbax-write: temp content, no renamed state
        os.makedirs(os.path.join(ckpt_dir, f"step_{step}",
                                 "state.orbax-checkpoint-tmp"),
                    exist_ok=True)
        raise RuntimeError("writer killed before commit")

    crash_writer = ckpt.AsyncCheckpointWriter(save_fn=killed_mid_save)
    crash_writer.submit(async_dir, {"params": params,
                                    "opt_state": opt_state, "step": 4},
                        4, vocabs, dims)
    crash_sticky = 0
    try:
        crash_writer.wait()
    except RuntimeError:
        crash_sticky = 1
    crash_writer.close()
    async_latest = ckpt.latest_step(async_dir)
    # collective restore of the last committed async step, both procs
    restored_async = ckpt.load_checkpoint(
        async_dir, {"params": params, "opt_state": opt_state,
                    "step": 0})
    async_restored_step = int(np.asarray(restored_async["step"]))
    async_restored_checksum = float(sum(
        jnp.sum(fetch_global(v).astype(np.float64))
        for v in restored_async["params"].values()))

    checksum = float(sum(jnp.sum(fetch_global(v).astype(np.float64))
                         for v in params.values()))

    watchdog.beat("ring-attention")
    # --- ring attention across the REAL process boundary. Mesh layout
    # matters: jax.devices() reshapes to (dcn, data, ctx, model), and
    # process 0 owns devices 0-3 — with data>1 the ctx pairs would stay
    # intra-process. data=1, ctx=2, model=4 puts ctx shard 0 on process
    # 0's devices and shard 1 on process 1's, so every ppermute K/V hop
    # crosses the Gloo boundary; result must equal the dense oracle.
    from code2vec_tpu.ops.ring_attention import ring_attention
    from test_ring_attention import _inputs, dense_oracle
    q, kk, vv, rmask = _inputs(seed=5)
    ring_mesh = make_mesh(1, 4, 2)
    assert dict(ring_mesh.shape) == {"dcn": 1, "data": 1, "ctx": 2,
                                     "model": 4}
    ring_out = fetch_global(ring_attention(q, kk, vv, rmask, ring_mesh))
    ring_max_err = float(jnp.max(jnp.abs(
        ring_out - dense_oracle(q, kk, vv, rmask))))

    watchdog.beat("sharded-evaluate")
    # --- model-level SHARDED evaluate: each host parses a disjoint shard
    # of the eval file; metric partials allreduce at the end
    # (jax_model.evaluate multi-host path) ---
    from code2vec_tpu.models.jax_model import Code2VecModel
    from helpers import sharded_eval_setup
    ds_dir = os.path.join(out_dir, f"ds{pid}")
    os.makedirs(ds_dir, exist_ok=True)
    # deterministic build: both processes create identical content;
    # config shared with the single-process oracle via helpers
    cfg = sharded_eval_setup(ds_dir)
    model = Code2VecModel(cfg)
    eval_res = model.evaluate()

    watchdog.close()
    np.savez(os.path.join(out_dir, f"proc{pid}.npz"),
             loss=float(loss), checksum=checksum,
             restored_checksum=restored_checksum,
             async_committed=async_committed,
             async_latest=async_latest,
             async_crash_sticky=crash_sticky,
             async_restored_step=async_restored_step,
             async_restored_checksum=async_restored_checksum,
             eval_loss=float(loss_sum), topk=np.asarray(topk_host),
             m_eval_loss=eval_res.loss,
             m_eval_top1=eval_res.topk_acc[0],
             m_eval_f1=eval_res.subtoken_f1,
             ring_max_err=ring_max_err)


if __name__ == "__main__":
    main()
