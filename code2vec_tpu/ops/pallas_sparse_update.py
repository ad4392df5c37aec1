"""Fused Pallas live-row sparse table update (ROADMAP item 1).

The one pass over LIVE ROWS ONLY that training/sparse_update.py
dispatches to on a TPU backend: per block of deduped unique ids, DMA-
gather the named table / optimizer-state rows from HBM into VMEM,
apply the row update vectorized over the block (row-Adam; on int8
additionally the per-row absmax rescale + counter-hash dither of
ops/pallas_requant.py), and DMA-scatter the rows back. The [V, E]
table, moments and (int8) scales stay in HBM (`memory_space=ANY`) and
are ALIASED input->output, so the kernel's HBM traffic is proportional
to the number of unique rows U, not the vocab V — the whole point: the
dense path's optimizer/requantize walk moved table-sized traffic per
step (BENCH_r05, git history at a4bf2f7: optimizer efficiency 0.786 at
15.7% HBM utilization), this moves [U, E].

Contract with the facade (training/sparse_update.py):
  - `uids` is PRE-PADDED to a whole number of `block_rows` blocks with
    the out-of-range sentinel (the table's row count) and `seg` with
    zeros — the kernel must never see Pallas-introduced block padding,
    whose contents are undefined.
  - unique ids never repeat, so grid programs write disjoint rows and
    the sequential-grid in-place aliasing is race-free.
  - the row math IS the facade's `row_adam_math` / `requant_row_math`
    (imported, not copied), so the two paths cannot drift in meaning.
    They are not promised bit-equal: one expression is compiled twice
    (by Mosaic or the interpreter here, by XLA in the reference) and
    each compiler may contract and associate it its own way. The
    parity contract is FLOAT ULP at the scale of the operands —
    max(|value before|, |update|) — and rows no id names stay
    bit-identical:
      compiled on a TPU v5e, f32 rows, V=1,301,136, 409,600 ids
        (chip_smoke.py, PR 21): p, m and v came out bit-equal; the
        smoke holds p to 2 ulp and the moments to bit equality;
      interpret mode against XLA:CPU (tests/test_sparse_update.py):
        p, m and v within 8 ulp (measured: 4 — XLA:CPU fuses the
        multiply-adds the interpreter runs one by one);
      int8, interpret mode: q exact under a shared salt, s within 2
        ulp, m and v bit-equal. The compiled int8 kernel has no chip
        verdict — Mosaic refuses it, see below.

Where it runs: Mosaic compiles the kernel for float32 rows only — it
refuses the single-row DMA of a packed bf16 or int8 row
(`sparse_update._resolve_fused` has the message) — so on a TPU the
facade's auto-select takes this kernel for f32 tables and the XLA
reference for the rest; forcing it raises the compiler's message.
Off-TPU it runs in interpret mode (the CPU
tier-1 tests run the identical kernel). The bias-corrected step size
arrives as an f32 scalar computed outside
(`sparse_adam.adam_step_size`: Mosaic lowers no scalar power). Sentinel rows clamp their gather to row 0
(a wasted but harmless read) and `pl.when` skips their scatter. The
per-row DMAs are issued serially within a block — block size (the
`block_rows` knob, tools/sparse_update_sweep.py) trades grid overhead
against VMEM residency; rows are E-element vectors (E=128 = one lane
width at java-large), so each DMA is one contiguous run.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from code2vec_tpu.ops.quant import QuantTable
from code2vec_tpu.training.sparse_adam import (RowAdamState,
                                               adam_step_size)
from code2vec_tpu.training.sparse_update import (requant_row_math,
                                                 row_adam_math)


def _gather_row(src_any, dst_vmem, slot, rid, sem):
    cp = pltpu.make_async_copy(src_any.at[rid], dst_vmem.at[slot], sem)
    cp.start()
    cp.wait()


def _scatter_row(src_vmem, dst_any, slot, rid, sem):
    cp = pltpu.make_async_copy(src_vmem.at[slot], dst_any.at[rid], sem)
    cp.start()
    cp.wait()


def _row_adam_kernel(ids_ref, seg_ref, lr_t_ref, tbl_any, m_any, v_any,
                     tbl_out, m_out, v_out, p_vmem, m_vmem, v_vmem, sem,
                     *, block_rows: int, vocab: int, b1: float,
                     b2: float, eps: float):
    # tbl_out/m_out/v_out alias tbl_any/m_any/v_any: gather from the
    # OUTPUT refs so re-reads inside one pallas_call (there are none —
    # ids are unique) and the aliasing contract stay coherent.
    def gather(i, _):
        rid = ids_ref[i, 0]
        rid = jnp.where(rid < vocab, rid, 0)
        _gather_row(tbl_out, p_vmem, i, rid, sem)
        _gather_row(m_out, m_vmem, i, rid, sem)
        _gather_row(v_out, v_vmem, i, rid, sem)
        return 0
    jax.lax.fori_loop(0, block_rows, gather, 0)

    p_new, m_new, v_new = row_adam_math(
        p_vmem[:].astype(jnp.float32), m_vmem[:], v_vmem[:],
        seg_ref[:], lr_t_ref[0, 0], b1, b2, eps)
    p_vmem[:] = p_new.astype(p_vmem.dtype)
    m_vmem[:] = m_new
    v_vmem[:] = v_new

    def scatter(i, _):
        rid = ids_ref[i, 0]

        @pl.when(rid < vocab)
        def _():
            _scatter_row(p_vmem, tbl_out, i, rid, sem)
            _scatter_row(m_vmem, m_out, i, rid, sem)
            _scatter_row(v_vmem, v_out, i, rid, sem)
        return 0
    jax.lax.fori_loop(0, block_rows, scatter, 0)


@functools.partial(jax.jit,
                   static_argnames=("block_rows", "interpret", "lr",
                                    "b1", "b2", "eps"))
def _row_adam_impl(table, m, v, uids, seg, count, block_rows, interpret,
                   lr, b1, b2, eps):
    V, E = table.shape
    S = uids.shape[0]
    kernel = functools.partial(_row_adam_kernel, block_rows=block_rows,
                               vocab=V, b1=b1, b2=b2, eps=eps)
    return pl.pallas_call(
        kernel,
        grid=(S // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, E), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=(pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pl.ANY)),
        out_shape=(jax.ShapeDtypeStruct((V, E), table.dtype),
                   jax.ShapeDtypeStruct((V, E), jnp.float32),
                   jax.ShapeDtypeStruct((V, E), jnp.float32)),
        scratch_shapes=[pltpu.VMEM((block_rows, E), table.dtype),
                        pltpu.VMEM((block_rows, E), jnp.float32),
                        pltpu.VMEM((block_rows, E), jnp.float32),
                        pltpu.SemaphoreType.DMA],
        input_output_aliases={3: 0, 4: 1, 5: 2},
        interpret=interpret,
    )(uids.reshape(S, 1), seg,
      adam_step_size(count, lr, b1, b2).reshape(1, 1), table, m, v)


def sparse_row_adam_fused(table: jax.Array, state: RowAdamState,
                          uids: jax.Array, seg: jax.Array, *,
                          count: jax.Array, lr: float, b1: float,
                          b2: float, eps: float, block_rows: int,
                          interpret: bool | None = None):
    """Live-row Adam over pre-deduped `uids` / segment-summed `seg`
    (the facade's dedup_segment_sum output — padded, unique, f32).
    interpret=None auto-selects interpreter mode off-TPU."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    # hyperparams are host-side Python scalars normalized for the
    # static-arg cache key, never device arrays — no sync here
    # graftlint: disable=host-sync-in-hot-path
    hp = (float(lr), float(b1), float(b2), float(eps))
    new_t, new_m, new_v = _row_adam_impl(
        table, state.m, state.v, uids, seg, count, block_rows,
        interpret, *hp)
    return new_t, RowAdamState(m=new_m, v=new_v)


def _requant_adam_kernel(ids_ref, seg_ref, lr_t_ref, salt_ref, q_any,
                         s_any, m_any, v_any, q_out, s_out, m_out,
                         v_out, q_vmem, s_vmem, m_vmem, v_vmem, sem, *,
                         block_rows: int, vocab: int, b1: float,
                         b2: float, eps: float):
    def gather(i, _):
        rid = ids_ref[i, 0]
        rid = jnp.where(rid < vocab, rid, 0)
        _gather_row(q_out, q_vmem, i, rid, sem)
        _gather_row(s_out, s_vmem, i, rid, sem)
        _gather_row(m_out, m_vmem, i, rid, sem)
        _gather_row(v_out, v_vmem, i, rid, sem)
        return 0
    jax.lax.fori_loop(0, block_rows, gather, 0)

    q_new, s_new, m_new, v_new = requant_row_math(
        q_vmem[:], s_vmem[:], m_vmem[:], v_vmem[:], seg_ref[:],
        ids_ref[:, 0], salt_ref[0, 0], lr_t_ref[0, 0], b1, b2, eps)
    q_vmem[:] = q_new
    s_vmem[:] = s_new
    m_vmem[:] = m_new
    v_vmem[:] = v_new

    def scatter(i, _):
        rid = ids_ref[i, 0]

        @pl.when(rid < vocab)
        def _():
            _scatter_row(q_vmem, q_out, i, rid, sem)
            _scatter_row(s_vmem, s_out, i, rid, sem)
            _scatter_row(m_vmem, m_out, i, rid, sem)
            _scatter_row(v_vmem, v_out, i, rid, sem)
        return 0
    jax.lax.fori_loop(0, block_rows, scatter, 0)


@functools.partial(jax.jit,
                   static_argnames=("block_rows", "interpret", "lr",
                                    "b1", "b2", "eps"))
def _requant_adam_impl(q, s, m, v, uids, seg, salt, count, block_rows,
                       interpret, lr, b1, b2, eps):
    V, E = q.shape
    S = uids.shape[0]
    kernel = functools.partial(_requant_adam_kernel,
                               block_rows=block_rows, vocab=V, b1=b1,
                               b2=b2, eps=eps)
    return pl.pallas_call(
        kernel,
        grid=(S // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, E), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=(pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pl.ANY)),
        out_shape=(jax.ShapeDtypeStruct((V, E), jnp.int8),
                   jax.ShapeDtypeStruct((V, 1), jnp.float32),
                   jax.ShapeDtypeStruct((V, E), jnp.float32),
                   jax.ShapeDtypeStruct((V, E), jnp.float32)),
        scratch_shapes=[pltpu.VMEM((block_rows, E), jnp.int8),
                        pltpu.VMEM((block_rows, 1), jnp.float32),
                        pltpu.VMEM((block_rows, E), jnp.float32),
                        pltpu.VMEM((block_rows, E), jnp.float32),
                        pltpu.SemaphoreType.DMA],
        input_output_aliases={4: 0, 5: 1, 6: 2, 7: 3},
        interpret=interpret,
    )(uids.reshape(S, 1), seg,
      adam_step_size(count, lr, b1, b2).reshape(1, 1),
      salt.reshape(1, 1), q, s, m, v)


def sparse_requant_adam_fused(qt: QuantTable, state: RowAdamState,
                              uids: jax.Array, seg: jax.Array,
                              salt: jax.Array, *, count: jax.Array,
                              lr: float, b1: float, b2: float,
                              eps: float, block_rows: int,
                              interpret: bool | None = None):
    """Live-row requantize-aware Adam over pre-deduped uids/seg; `salt`
    is the facade's per-call uint32 draw (shared with the reference so
    q parity is bit-exact). interpret=None auto-selects interpreter
    mode off-TPU."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    # hyperparams are host-side Python scalars normalized for the
    # static-arg cache key, never device arrays — no sync here
    # graftlint: disable=host-sync-in-hot-path
    hp = (float(lr), float(b1), float(b2), float(eps))
    q_new, s_new, m_new, v_new = _requant_adam_impl(
        qt["q"], qt["s"], state.m, state.v, uids, seg, salt, count,
        block_rows, interpret, *hp)
    return {"q": q_new, "s": s_new}, RowAdamState(m=m_new, v=v_new)
