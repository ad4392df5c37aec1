"""Fused sparse table-update tests (training/sparse_update.py +
ops/pallas_sparse_update.py, round 13).

Covers: the dedup + segment-sum + scatter-back property against the
dense-carrier oracle (bit-for-bit in f32, including heavy-duplicate /
all-same / all-unique extremes), interpret-mode fused-vs-reference
parity (float ulp on f32/bf16 tables — the contract in
ops/pallas_sparse_update.py; q-exact on int8 under the shared dither
salt), the dispatch + config resolution, the mesh path's
(mesh_sparse_apply, round 14) bit-exact agreement with BOTH the
single-device compact apply and the dense-carrier reference on the
8-device virtual mesh, a fused-path train smoke through
make_train_step's sparse dispatch, the analytic traffic model, and the
vm head's rows_from_dense — all on the CPU interpreter (tier-1).

Both paths are compared UNDER JIT (the production context — the train
step jits the whole update): eager XLA contracts multiply-adds
differently than the compiled graph, so eager-vs-jit comparisons
differ in the last ulp while jit-vs-jit is bit-exact.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from code2vec_tpu.models.encoder import ModelDims, init_params
from code2vec_tpu.ops.quant import dequantize_table, quantize_table
from code2vec_tpu.training import sparse_update as su
from code2vec_tpu.training.sparse_adam import (RowAdamState,
                                               init_row_adam,
                                               row_adam_update)
from code2vec_tpu.training.sparse_steps import (init_sparse_opt_state,
                                                make_sparse_train_step)
from code2vec_tpu.training.steps import make_train_step


def _ulps(ref, got, old) -> float:
    """Largest |got - ref| in f32 units in the last place AT THE SCALE
    OF THE OPERANDS: the larger of the value before the update and the
    update itself. (Near a cancellation the result is far smaller than
    its operands, and an ulp of the result would measure nothing.)"""
    ref, got, old = (np.asarray(a, np.float32) for a in (ref, got, old))
    scale = np.maximum(np.abs(old), np.abs(old - ref))
    return float(np.max(np.abs(got - ref)
                        / np.spacing(np.maximum(scale, 1e-30))))


# The kernel's parity contract (ops/pallas_sparse_update.py): float ulp,
# not bits. The interpreter sits a few ulp from XLA:CPU (measured max
# 4); the compiled kernel on a v5e sits closer still (chip_smoke.py).
_MAX_ULP = 8


def _ids_cases(V, N, seed=0):
    """Random id multisets incl. the extremes the property demands."""
    r = np.random.default_rng(seed)
    return {
        "heavy_dup": r.integers(0, max(V // 4, 1), N).astype(np.int32),
        "uniform": r.integers(0, V, N).astype(np.int32),
        "all_same": np.full(N, V - 1, np.int32),
        "all_unique": r.permutation(V)[:min(N, V)].astype(np.int32),
    }


@pytest.mark.parametrize("case", ["heavy_dup", "uniform", "all_same",
                                  "all_unique"])
def test_dedup_segment_sum_matches_dense_carrier_bitwise(case):
    """The compact segment sums must equal the dense [V, E] carrier's
    scatter-add gathered at the unique ids BIT-FOR-BIT in f32: both
    scatters apply the same updates array in the same per-index order,
    so accumulation order per duplicate group is identical."""
    V, E, N = 64, 8, 256
    ids = jnp.asarray(_ids_cases(V, N)[case])
    n = ids.shape[0]
    g = jnp.asarray(np.random.default_rng(1).normal(size=(n, E)),
                    jnp.float32)

    @jax.jit
    def both(ids, g):
        dense = jnp.zeros((V, E), jnp.float32).at[ids].add(g)
        uids, seg = su.dedup_segment_sum(ids, g, V, block_rows=32)
        return dense, uids, seg

    dense, uids, seg = both(ids, g)
    uids, seg, dense = (np.asarray(uids), np.asarray(seg),
                        np.asarray(dense))
    live = uids < V
    assert live.sum() == len(set(np.asarray(ids).tolist()))
    np.testing.assert_array_equal(seg[live], dense[uids[live]])
    # padded slots carry no gradient
    np.testing.assert_array_equal(seg[~live], 0.0)


def test_scatter_back_equals_dense_carrier_path_f32():
    """Full property (ISSUE 8): dedup + segment-sum + live-row apply +
    scatter-back == the dense-carrier scatter-add path bit-for-bit in
    f32 — row_adam_update IS the dense-carrier form, kept as the
    oracle."""
    V, E, N = 48, 8, 192
    r = np.random.default_rng(2)
    oracle = jax.jit(functools.partial(row_adam_update, lr=0.01))
    compact = jax.jit(functools.partial(
        su.sparse_row_adam, lr=0.01, fused=False, block_rows=16))
    for case, ids_np in _ids_cases(V, N, seed=3).items():
        table = jnp.asarray(r.normal(size=(V, E)), jnp.float32)
        state = init_row_adam(table)
        ids = jnp.asarray(ids_np)
        g = jnp.asarray(r.normal(size=(ids.shape[0], E)), jnp.float32)
        count = jnp.asarray(5, jnp.int32)

        t_ref, s_ref = oracle(table, state, ids, g, count=count)
        t_new, s_new = compact(table, state, ids, g, count=count)
        np.testing.assert_array_equal(np.asarray(t_ref),
                                      np.asarray(t_new), err_msg=case)
        np.testing.assert_array_equal(np.asarray(s_ref.m),
                                      np.asarray(s_new.m), err_msg=case)
        np.testing.assert_array_equal(np.asarray(s_ref.v),
                                      np.asarray(s_new.v), err_msg=case)


# shapes cover: multi-block, non-multiple-of-block id counts, a
# single-block table, E > lane width, and a 1-row table
@pytest.mark.parametrize("V,E,N", [(64, 8, 100), (40, 16, 37),
                                   (300, 128, 513), (5, 8, 160),
                                   (1, 256, 7)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_matches_reference(V, E, N, dtype):
    """The kernel IS the reference restructured around per-row DMA:
    same shared row math, compiled twice -> tables AND moments agree
    to float ulp, and rows no id names stay bit-identical."""
    r = np.random.default_rng(V + N)
    table = jnp.asarray(r.normal(size=(V, E)) * 0.3).astype(dtype)
    state = RowAdamState(
        m=jnp.asarray(r.normal(size=(V, E)) * 0.01, jnp.float32),
        v=jnp.asarray(np.abs(r.normal(size=(V, E))) * 1e-3,
                      jnp.float32))
    ids = jnp.asarray(r.integers(0, V, N), jnp.int32)
    g = jnp.asarray(r.normal(size=(N, E)) * 0.1).astype(dtype)
    count = jnp.asarray(3, jnp.int32)

    def run(fused):
        # one-shot compile IS the test  # graftlint: disable=retrace-hazard
        return jax.jit(functools.partial(
            su.sparse_row_adam, lr=0.01, fused=fused, block_rows=32))(
            table, state, ids, g, count=count)

    (t_ref, s_ref), (t_fus, s_fus) = run(False), run(True)
    assert _ulps(t_ref, t_fus, table) <= _MAX_ULP
    assert _ulps(s_ref.m, s_fus.m, state.m) <= _MAX_ULP
    assert _ulps(s_ref.v, s_fus.v, state.v) <= _MAX_ULP
    untouched = np.setdiff1d(np.arange(V), np.asarray(ids))
    for new, old in ((t_fus, table), (s_fus.m, state.m),
                     (s_fus.v, state.v)):
        np.testing.assert_array_equal(
            np.asarray(new, np.float32)[untouched],
            np.asarray(old, np.float32)[untouched])


@pytest.mark.parametrize("V,E,N", [(64, 8, 100), (40, 16, 37),
                                   (300, 128, 513)])
def test_fused_matches_reference_int8(V, E, N):
    """int8 {q, s} live-row requantize-aware update: q bit-exact under
    the shared dither salt (the ISSUE's q-parity contract); s to <= 2
    ulp (float-contraction ordering, same bound as pallas_requant)."""
    r = np.random.default_rng(V + N)
    qt = quantize_table(jnp.asarray(r.normal(size=(V, E)) * 0.3,
                                    jnp.float32))
    state = init_row_adam(qt)
    ids = jnp.asarray(r.integers(0, V, N), jnp.int32)
    g = jnp.asarray(r.normal(size=(N, E)) * 0.1, jnp.float32)
    count = jnp.asarray(2, jnp.int32)
    rng = jax.random.PRNGKey(9)

    def run(fused):
        # one-shot compile IS the test  # graftlint: disable=retrace-hazard
        return jax.jit(functools.partial(
            su.sparse_requant_adam, lr=0.01, fused=fused,
            block_rows=32))(qt, state, ids, g, rng, count=count)

    (q_ref, s_ref), (q_fus, s_fus) = run(False), run(True)
    np.testing.assert_array_equal(np.asarray(q_ref["q"]),
                                  np.asarray(q_fus["q"]))
    ulp = np.abs(np.asarray(q_ref["s"]).ravel().view(np.int32)
                 - np.asarray(q_fus["s"]).ravel().view(np.int32))
    assert ulp.max() <= 2, ulp.max()
    np.testing.assert_array_equal(np.asarray(s_ref.m),
                                  np.asarray(s_fus.m))
    np.testing.assert_array_equal(np.asarray(s_ref.v),
                                  np.asarray(s_fus.v))


def test_int8_untouched_rows_stable_and_touched_rows_move():
    """A live-row pass must leave untouched q/s rows BIT-identical (the
    dense requantize pass re-rounds every row; this path does not
    touch them at all) and move touched rows by the applied update."""
    V, E = 64, 8
    r = np.random.default_rng(4)
    qt = quantize_table(jnp.asarray(r.normal(size=(V, E)) * 0.5,
                                    jnp.float32))
    state = init_row_adam(qt)
    ids = jnp.asarray([3, 3, 17], jnp.int32)
    g = jnp.asarray(r.normal(size=(3, E)), jnp.float32)
    # one-shot compile IS the test  # graftlint: disable=retrace-hazard
    out, _ = jax.jit(functools.partial(
        su.sparse_requant_adam, lr=0.01, fused=True, block_rows=16))(
        qt, state, ids, g, jax.random.PRNGKey(0),
        count=jnp.asarray(1, jnp.int32))
    untouched = [i for i in range(V) if i not in (3, 17)]
    np.testing.assert_array_equal(np.asarray(out["q"])[untouched],
                                  np.asarray(qt["q"])[untouched])
    np.testing.assert_array_equal(np.asarray(out["s"])[untouched],
                                  np.asarray(qt["s"])[untouched])
    moved = np.asarray(dequantize_table(out))[[3, 17]]
    orig = np.asarray(dequantize_table(qt))[[3, 17]]
    assert np.abs(moved - orig).max() > 0


def test_mode_resolution_and_auto_dispatch():
    assert su.resolve_sparse_update_mode("auto") is None
    assert su.resolve_sparse_update_mode("fused") is True
    assert su.resolve_sparse_update_mode("reference") is False
    with pytest.raises(ValueError):
        su.resolve_sparse_update_mode("bogus")
    # CPU backend: auto == reference (bit-identical results)
    V, E, N = 32, 8, 50
    r = np.random.default_rng(0)
    table = jnp.asarray(r.normal(size=(V, E)), jnp.float32)
    state = init_row_adam(table)
    ids = jnp.asarray(r.integers(0, V, N), jnp.int32)
    g = jnp.asarray(r.normal(size=(N, E)), jnp.float32)

    def run(fused):
        # one-shot compile IS the test  # graftlint: disable=retrace-hazard
        return jax.jit(functools.partial(
            su.sparse_row_adam, lr=0.01, fused=fused))(
            table, state, ids, g, count=jnp.asarray(1, jnp.int32))

    (t_auto, _), (t_ref, _) = run(None), run(False)
    np.testing.assert_array_equal(np.asarray(t_auto),
                                  np.asarray(t_ref))


def test_sparse_update_pallas_config_gate():
    from code2vec_tpu.config import Config

    cfg = Config(SPARSE_UPDATE_PALLAS="bogus")
    cfg.train_data_path = "x"
    with pytest.raises(ValueError):
        cfg.verify()
    # the relaxed tables gate: bf16 + sparse now verifies
    cfg2 = Config(SPARSE_EMBEDDING_UPDATES=True,
                  EMBEDDING_OPTIMIZER="adam", LR_SCHEDULE="constant",
                  TABLES_DTYPE="bfloat16")
    cfg2.train_data_path = "x"
    cfg2.verify()
    cfg3 = Config(SPARSE_EMBEDDING_UPDATES=True,
                  EMBEDDING_OPTIMIZER="adafactor",
                  LR_SCHEDULE="constant")
    cfg3.train_data_path = "x"
    with pytest.raises(ValueError):
        cfg3.verify()


DIMS = ModelDims(token_vocab_size=64, path_vocab_size=32,
                 target_vocab_size=24, embeddings_size=8,
                 max_contexts=6, dropout_keep_rate=1.0)


def _batch(seed, dims=DIMS, b=16):
    r = np.random.default_rng(seed)
    C = dims.max_contexts
    return tuple(jnp.asarray(a) for a in (
        r.integers(0, dims.target_vocab_size, (b,)).astype(np.int32),
        r.integers(0, dims.token_vocab_size, (b, C)).astype(np.int32),
        r.integers(0, dims.path_vocab_size, (b, C)).astype(np.int32),
        r.integers(0, dims.token_vocab_size, (b, C)).astype(np.int32),
        np.ones((b, C), np.float32), np.ones((b,), np.float32)))


def _mesh_for_sparse(model=2):
    from code2vec_tpu.parallel.mesh import make_mesh
    return make_mesh(0, model)


def test_mesh_sparse_apply_bitexact_vs_carrier_f32():
    """The round-14 acceptance contract: the mesh sparse-update path
    (dedup + segment-sum + live-row apply inside shard_map on the
    8-device virtual mesh, vocab sharded over 'model') is BIT-exact vs
    BOTH the single-device compact path and the dense-carrier reference
    (row_adam_update — the [V, E] scatter-add form the mesh path no
    longer constructs). Two sharded parts + one replicated part
    exercise the all-gather + caller-order concatenation."""
    V, E, N = 48, 8, 64  # V % model == 0, N % (dcn*data) == 0
    r = np.random.default_rng(11)
    table = jnp.asarray(r.normal(size=(V, E)), jnp.float32)
    state = init_row_adam(table)
    ids_a = jnp.asarray(r.integers(0, V, N), jnp.int32)
    ids_b = jnp.asarray(r.integers(0, V, N), jnp.int32)
    ids_r = jnp.asarray(r.integers(0, V, 8), jnp.int32)  # replicated
    g_a = jnp.asarray(r.normal(size=(N, E)), jnp.float32)
    g_b = jnp.asarray(r.normal(size=(N, E)), jnp.float32)
    g_r = jnp.asarray(r.normal(size=(8, E)), jnp.float32)
    count = jnp.asarray(4, jnp.int32)
    mesh = _mesh_for_sparse(model=2)

    @jax.jit
    def run_mesh(table, m, v, ids_a, g_a, ids_b, g_b, ids_r, g_r,
                 count):
        t, s = su.mesh_sparse_apply(
            mesh, table, RowAdamState(m=m, v=v),
            [(ids_a, g_a, True), (ids_b, g_b, True),
             (ids_r, g_r, False)],
            count=count, lr=0.01, fused=False, block_rows=16)
        return t, s.m, s.v

    t_mesh, m_mesh, v_mesh = run_mesh(table, state.m, state.v, ids_a,
                                      g_a, ids_b, g_b, ids_r, g_r,
                                      count)
    s_mesh = RowAdamState(m=m_mesh, v=v_mesh)

    ids = jnp.concatenate([ids_a, ids_b, ids_r])
    g = jnp.concatenate([g_a, g_b, g_r])
    # one-shot compile IS the test  # graftlint: disable=retrace-hazard
    t_sd, s_sd = jax.jit(functools.partial(
        su.sparse_row_adam, lr=0.01, fused=False, block_rows=16))(
        table, state, ids, g, count=count)
    # one-shot compile IS the test  # graftlint: disable=retrace-hazard
    t_car, s_car = jax.jit(functools.partial(row_adam_update, lr=0.01))(
        table, state, ids, g, count=count)

    for name, (t_ref, s_ref) in {"single-device": (t_sd, s_sd),
                                 "carrier": (t_car, s_car)}.items():
        np.testing.assert_array_equal(np.asarray(t_mesh),
                                      np.asarray(t_ref), err_msg=name)
        np.testing.assert_array_equal(np.asarray(s_mesh.m),
                                      np.asarray(s_ref.m), err_msg=name)
        np.testing.assert_array_equal(np.asarray(s_mesh.v),
                                      np.asarray(s_ref.v), err_msg=name)


def test_fused_step_reproduces_reference_step_exactly():
    """--sparse_update_pallas fused vs reference: the same training
    trajectory to float rounding (the flag-level A/B; the kernel's
    contract is ulp-level, so four steps stay within 1e-5), through
    make_train_step's sparse dispatch — the exact entry point
    jax_model uses."""
    params = init_params(jax.random.PRNGKey(0), DIMS)

    def build(fused):
        return make_train_step(
            DIMS, optax.adam(0.05), use_sampled_softmax=True,
            num_sampled=8, sparse_updates=True, learning_rate=0.05,
            sparse_update_fused=fused, sparse_block_rows=32)

    ref_step, fus_step = build(False), build(True)
    o1 = init_sparse_opt_state(params, optax.adam(0.05), True)
    o2 = init_sparse_opt_state(params, optax.adam(0.05), True)
    p1 = jax.tree_util.tree_map(jnp.copy, params)
    p2 = jax.tree_util.tree_map(jnp.copy, params)
    rng = jax.random.PRNGKey(1)
    batch = _batch(11)
    for _ in range(4):
        rng, k = jax.random.split(rng)
        p1, o1, l1 = ref_step(p1, o1, batch, k)
        p2, o2, l2 = fus_step(p2, o2, batch, k)
    assert float(l1) == pytest.approx(float(l2), rel=1e-5)
    for key in p1:
        np.testing.assert_allclose(np.asarray(p1[key]),
                                   np.asarray(p2[key]), rtol=1e-5,
                                   atol=1e-6, err_msg=key)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mesh_sparse_apply_matches_single_device(dtype):
    """f32 AND bf16 tables run the compact path under the mesh now
    (round 14 removed the f32-only dense-carrier restriction along
    with the carrier): bit-exact vs the single-device compact apply,
    with the vocab dim sharded over 'model'."""
    V, E, N = 40, 16, 32
    r = np.random.default_rng(23)
    table = jnp.asarray(r.normal(size=(V, E)) * 0.3).astype(dtype)
    state = RowAdamState(
        m=jnp.asarray(r.normal(size=(V, E)) * 0.01, jnp.float32),
        v=jnp.asarray(np.abs(r.normal(size=(V, E))) * 1e-3,
                      jnp.float32))
    ids = jnp.asarray(r.integers(0, V, N), jnp.int32)
    g = jnp.asarray(r.normal(size=(N, E)) * 0.1).astype(dtype)
    count = jnp.asarray(3, jnp.int32)
    mesh = _mesh_for_sparse(model=2)

    @jax.jit
    def run_mesh(table, m, v, ids, g, count):
        t, s = su.mesh_sparse_apply(
            mesh, table, RowAdamState(m=m, v=v), [(ids, g, True)],
            count=count, lr=0.01, fused=False, block_rows=16)
        return t, s.m, s.v

    t_mesh, m_mesh, v_mesh = run_mesh(table, state.m, state.v, ids, g,
                                      count)
    # one-shot compile IS the test  # graftlint: disable=retrace-hazard
    t_sd, s_sd = jax.jit(functools.partial(
        su.sparse_row_adam, lr=0.01, fused=False, block_rows=16))(
        table, state, ids, g, count=count)
    np.testing.assert_array_equal(np.asarray(t_mesh, np.float32),
                                  np.asarray(t_sd, np.float32))
    np.testing.assert_array_equal(np.asarray(m_mesh),
                                  np.asarray(s_sd.m))
    np.testing.assert_array_equal(np.asarray(v_mesh),
                                  np.asarray(s_sd.v))


def test_mesh_sparse_apply_int8_q_exact():
    """int8 {q, s} tables under the mesh: the model-sharded blocks draw
    dither from the GLOBAL row index, so q is bit-exact vs the
    single-device compact pass under the same rng (s within 2 ulp —
    the pallas_requant float-contraction bound)."""
    V, E, N = 64, 8, 32
    r = np.random.default_rng(31)
    qt = quantize_table(jnp.asarray(r.normal(size=(V, E)) * 0.3,
                                    jnp.float32))
    state = init_row_adam(qt)
    ids = jnp.asarray(r.integers(0, V, N), jnp.int32)
    g = jnp.asarray(r.normal(size=(N, E)) * 0.1, jnp.float32)
    count = jnp.asarray(2, jnp.int32)
    rng = jax.random.PRNGKey(9)
    mesh = _mesh_for_sparse(model=2)

    @jax.jit
    def run_mesh(qt, m, v, ids, g, count, rng):
        t, s = su.mesh_sparse_apply(
            mesh, qt, RowAdamState(m=m, v=v), [(ids, g, True)],
            count=count, lr=0.01, fused=False, block_rows=16, rng=rng)
        return t, s.m, s.v

    q_mesh, m_mesh, v_mesh = run_mesh(qt, state.m, state.v, ids, g,
                                      count, rng)
    # one-shot compile IS the test  # graftlint: disable=retrace-hazard
    q_sd, s_sd = jax.jit(functools.partial(
        su.sparse_requant_adam, lr=0.01, fused=False, block_rows=16))(
        qt, state, ids, g, rng, count=count)
    np.testing.assert_array_equal(np.asarray(q_mesh["q"]),
                                  np.asarray(q_sd["q"]))
    ulp = np.abs(np.asarray(q_mesh["s"]).ravel().view(np.int32)
                 - np.asarray(q_sd["s"]).ravel().view(np.int32))
    assert ulp.max() <= 2, ulp.max()
    np.testing.assert_array_equal(np.asarray(m_mesh),
                                  np.asarray(s_sd.m))
    np.testing.assert_array_equal(np.asarray(v_mesh),
                                  np.asarray(s_sd.v))


def test_mesh_sparse_apply_honors_fused_flag():
    """SPARSE_UPDATE_PALLAS is honored under the mesh: fused=True runs
    the Pallas live-row kernel per device inside the manual region
    (interpret mode on CPU), bit-exact vs the mesh reference."""
    V, E, N = 32, 8, 16
    r = np.random.default_rng(7)
    table = jnp.asarray(r.normal(size=(V, E)), jnp.float32)
    state = init_row_adam(table)
    ids = jnp.asarray(r.integers(0, V, N), jnp.int32)
    g = jnp.asarray(r.normal(size=(N, E)), jnp.float32)
    count = jnp.asarray(1, jnp.int32)
    mesh = _mesh_for_sparse(model=2)

    def run(fused):
        # one-shot compile IS the test  # graftlint: disable=retrace-hazard
        @jax.jit
        def go(table, m, v, ids, g, count):
            t, s = su.mesh_sparse_apply(
                mesh, table, RowAdamState(m=m, v=v), [(ids, g, True)],
                count=count, lr=0.01, fused=fused, block_rows=16)
            return t, s.m, s.v
        return go(table, state.m, state.v, ids, g, count)

    (t_ref, m_ref, v_ref), (t_fus, m_fus, v_fus) = run(False), run(True)
    np.testing.assert_array_equal(np.asarray(t_ref), np.asarray(t_fus))
    np.testing.assert_array_equal(np.asarray(m_ref), np.asarray(m_fus))
    np.testing.assert_array_equal(np.asarray(v_ref), np.asarray(v_fus))


def test_mesh_sparse_apply_error_paths():
    """Trace-time guards: ctx-sharded meshes are refused (the bag
    encoder's batch never shards over 'ctx'), int8 requires the dither
    rng, and non-model-divisible tables are caught up front."""
    from code2vec_tpu.parallel.mesh import make_mesh
    table = jnp.zeros((8, 4), jnp.float32)
    state = init_row_adam(table)
    part = [(jnp.zeros((8,), jnp.int32),
             jnp.zeros((8, 4), jnp.float32), True)]
    count = jnp.asarray(1, jnp.int32)
    with pytest.raises(ValueError, match="ctx"):
        su.mesh_sparse_apply(make_mesh(0, 1, context=2), table, state,
                             part, count=count, lr=0.01)
    qt = quantize_table(jnp.ones((8, 4), jnp.float32))
    with pytest.raises(ValueError, match="rng"):
        su.mesh_sparse_apply(make_mesh(0, 2), qt, init_row_adam(qt),
                             part, count=count, lr=0.01)
    with pytest.raises(ValueError, match="divisible"):
        su.mesh_sparse_apply(make_mesh(0, 8),
                             jnp.zeros((12, 4), jnp.float32),
                             init_row_adam(jnp.zeros((12, 4))),
                             part, count=count, lr=0.01)


def test_int8_sparse_step_trains_through_fused_path():
    """int8 tables + sparse updates end to end through the fused
    interpret-mode kernel: loss decreases, {q, s} structure preserved,
    moments live."""
    dims = ModelDims(token_vocab_size=64, path_vocab_size=32,
                     target_vocab_size=24, embeddings_size=8,
                     max_contexts=6, tables_dtype="int8",
                     dropout_keep_rate=1.0)
    params = init_params(jax.random.PRNGKey(3), dims)
    step = make_train_step(dims, optax.adam(0.05),
                           use_sampled_softmax=False,
                           sparse_updates=True, learning_rate=0.05,
                           sparse_update_fused=True,
                           sparse_block_rows=32)
    opt_state = init_sparse_opt_state(params, optax.adam(0.05), False)
    batch = _batch(7, dims)
    losses = []
    rng = jax.random.PRNGKey(4)
    for _ in range(40):
        rng, k = jax.random.split(rng)
        params, opt_state, loss = step(params, opt_state, batch, k)
        losses.append(float(loss))
    assert set(params["token_emb"]) == {"q", "s"}
    assert params["token_emb"]["q"].dtype == jnp.int8
    assert losses[-1] < losses[0] * 0.5, (losses[0], losses[-1])


def test_vm_rows_from_dense_matches_dense_rows():
    """The varmisuse entry: unique rows of the DENSE cotangent get one
    row-Adam step; rows outside the id set stay untouched even when
    the dense grad is nonzero there (the sparse contract)."""
    V, E = 32, 8
    r = np.random.default_rng(5)
    table = jnp.asarray(r.normal(size=(V, E)), jnp.float32)
    state = init_row_adam(table)
    dense_grad = jnp.asarray(r.normal(size=(V, E)), jnp.float32)
    ids = jnp.asarray([1, 1, 4, 9, 4], jnp.int32)
    # one-shot compile IS the test  # graftlint: disable=retrace-hazard
    out, _ = jax.jit(functools.partial(
        su.rows_from_dense, lr=0.01, fused=False, block_rows=8))(
        table, state, dense_grad, ids,
        count=jnp.asarray(1, jnp.int32))
    # oracle: one row-Adam step on exactly rows {1, 4, 9}
    oracle = jax.jit(functools.partial(row_adam_update, lr=0.01))
    t_ref, _ = oracle(table, state, jnp.asarray([1, 4, 9], jnp.int32),
                      jnp.take(dense_grad, jnp.asarray([1, 4, 9]),
                               axis=0),
                      count=jnp.asarray(1, jnp.int32))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(t_ref))
    untouched = [i for i in range(V) if i not in (1, 4, 9)]
    np.testing.assert_array_equal(np.asarray(out)[untouched],
                                  np.asarray(table)[untouched])


def test_vm_sparse_train_step_runs_and_trains():
    from code2vec_tpu.models.varmisuse import init_vm_params
    from code2vec_tpu.training.vm_steps import (init_vm_sparse_opt_state,
                                                make_vm_train_step)
    dims = ModelDims(token_vocab_size=32, path_vocab_size=16,
                     target_vocab_size=8, embeddings_size=8,
                     max_contexts=5, dropout_keep_rate=1.0)
    params = init_vm_params(jax.random.PRNGKey(0), dims)
    opt = optax.adam(0.05)
    step = make_vm_train_step(dims, opt, sparse_updates=True,
                              learning_rate=0.05,
                              sparse_update_fused=True)
    opt_state = init_vm_sparse_opt_state(params, opt)
    r = np.random.default_rng(0)
    B, C, K = 8, 5, 4
    batch = tuple(jnp.asarray(a) for a in (
        r.integers(0, K, (B,)).astype(np.int32),
        r.integers(0, 32, (B, C)).astype(np.int32),
        r.integers(0, 16, (B, C)).astype(np.int32),
        r.integers(0, 32, (B, C)).astype(np.int32),
        np.ones((B, C), np.float32),
        r.integers(0, 32, (B, K)).astype(np.int32),
        np.ones((B, K), np.float32),
        np.ones((B,), np.float32)))
    losses = []
    rng = jax.random.PRNGKey(2)
    for _ in range(30):
        rng, k = jax.random.split(rng)
        params, opt_state, loss = step(params, opt_state, batch, k)
        losses.append(float(loss))
    assert np.mean(losses[-5:]) < np.mean(losses[:5])
    assert int(opt_state["count"]) == 30
    # vm + mesh is gated (the dedup-under-GSPMD miscompile)
    with pytest.raises(ValueError):
        make_vm_train_step(dims, opt, sparse_updates=True,
                           learning_rate=0.05, mesh=object())


def test_traffic_model():
    V, E, N, U = 64, 8, 100, 40
    table = jnp.zeros((V, E), jnp.float32)
    b = su.sparse_update_traffic_bytes(table, N, U, block_rows=32)
    slots = -(-N // 32) * 32
    expect = (N * 4 + N * E * 4 + slots * E * 8
              + U * E * 4 * 2 + U * E * 16)
    assert b == expect
    qt = {"q": jnp.zeros((V, E), jnp.int8),
          "s": jnp.zeros((V, 1), jnp.float32)}
    bq = su.sparse_update_traffic_bytes(qt, N, U, grad_itemsize=2,
                                        block_rows=32)
    expect_q = (N * 4 + N * E * 2 + slots * E * 8
                + U * E * 2 + U * 8 + U * E * 16)
    assert bq == expect_q
    # E[U] is monotone, bounded by both N and V
    assert su.expected_unique_rows(10**6, 1000) <= 1000
    assert su.expected_unique_rows(10, 10**6) <= 10 + 1
    assert su.expected_unique_rows(0, 100) == 0
    # the full-step floor model runs on a real params tree, and the
    # phase-alone helper (the live gauge's model) is a strict subset
    params = init_params(jax.random.PRNGKey(0), DIMS)
    full = su.sparse_step_floor_bytes(params, 16, DIMS.max_contexts,
                                      num_sampled=8)
    phase = su.sparse_update_phase_bytes(params, 16, DIMS.max_contexts,
                                         num_sampled=8)
    assert 0 < phase < full
