"""Fused sparse table-update facade: dedup + segment-sum + live-row
optimizer update (ROADMAP item 1, round 13).

BENCH_r05 (git history at a4bf2f7) pins the per-chip step at 6.66M
pc/s against an 8.48M fwd/bwd floor (optimizer efficiency 0.786) with
HBM at 15.7% of the 637 GB/s ceiling: the step is
backward-scatter-bound. A batch touches far fewer
than V unique token/path ids, yet the dense-path gradients flow through
a dense [V, E] carrier (the VJP of a gather) and the optimizer/requant
apply walks far more rows than it needs. This module removes the dense
carrier from the sparse path entirely:

  1. `dedup_segment_sum`: sort-dedup the step's gathered ids
     (jnp.unique with a static slot count) and scatter-add their
     cotangents into a COMPACT [S, E] gradient — S ~ the id count, not
     V, so the scatter target is batch-sized. Bit-parity property:
     accumulation order per duplicate group matches the dense-carrier
     scatter-add (same updates array, same per-index order), so the
     compact sums equal `zeros([V, E]).at[ids].add(g)` gathered at the
     unique ids bit-for-bit in f32 (tests/test_sparse_update.py).
  2. A live-row apply touching ONLY the unique rows: row-Adam on
     float/bf16 tables, a requantize-aware row-Adam on int8 {q, s}
     tables (same per-row absmax rescale + counter-hash dither stream
     as ops/quant.requantize — `dither_from_index` is the shared
     primitive, so a live-row pass and a full-table pass draw identical
     dither for the same element index and salt).

Dispatch (`_resolve_fused`): the fused Pallas kernel
(ops/pallas_sparse_update.py — one pass over the live rows, per-row
DMA gather/scatter, no [V, E] materialization) where it compiles — a
TPU and float32 table rows — and the XLA gather/scatter reference
everywhere else, bf16 and int8 tables on a TPU included;
`Config.SPARSE_UPDATE_PALLAS` ("auto" | "fused" | "reference") maps
onto the `fused` argument via `resolve_sparse_update_mode`. Under a
MESH (round 14) `mesh_sparse_apply` runs the SAME compact path per
device inside `shard_map` — the GSPMD partitioner never sees the
dedup composition it miscompiles, and the flag is honored everywhere.
The reference and the kernel share the row-math helpers below (single
source of truth); the parity contract is in the kernel's docstring.

Consumed by training/sparse_steps.py (code2vec head: cotangents arrive
at gathered-row granularity, no dense carrier anywhere) and
training/vm_steps.py (varmisuse head: autodiff still emits the dense
table cotangent, but the optimizer walk is live-rows-only via
`rows_from_dense`). bench.py attributes the phase every round
(`sparse_update_*`) against the analytic traffic model here.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from code2vec_tpu.ops.quant import (_SCALE_FLOOR, QuantTable,
                                    dither_from_index, is_quantized)
from code2vec_tpu.training.sparse_adam import (RowAdamState,
                                               adam_step_size)

# Unique-row slots per kernel program. 512 rows x E=128 keeps the
# per-block VMEM working set (p/m/v or q/s/m/v row blocks + f32 temps)
# small while amortizing the grid; tools/sparse_update_sweep.py is the
# tuning driver for this knob.
_BLOCK_ROWS = 512


def resolve_sparse_update_mode(mode: str):
    """Config.SPARSE_UPDATE_PALLAS -> the `fused` argument below
    (ops/quant.resolve_tristate_mode is the shared mapping)."""
    from code2vec_tpu.ops.quant import resolve_tristate_mode
    return resolve_tristate_mode(mode, "SPARSE_UPDATE_PALLAS")


def _num_slots(n_ids: int, block_rows: int) -> int:
    """Static unique-id capacity: n_ids rounded up to a whole number of
    kernel blocks (>= any possible unique count; the kernel never sees
    Pallas-introduced padding, whose contents are undefined)."""
    return -(-n_ids // block_rows) * block_rows


def dedup_segment_sum(ids: jax.Array, grads: jax.Array, num_rows: int,
                      *, block_rows: int = _BLOCK_ROWS
                      ) -> Tuple[jax.Array, jax.Array]:
    """[N] ids + [N, E] cotangents -> ([S] unique ids padded with the
    out-of-range sentinel `num_rows`, [S, E] f32 per-unique-row sums).

    S is static (= N rounded up to block_rows), so the whole step jits
    once; `num_rows` doubles as the padding sentinel because real ids
    are always < the table's row count. Accumulates in f32 regardless
    of the cotangent dtype (bf16 sums over hundreds of duplicates would
    lose the low bits the optimizer needs)."""
    ids = ids.reshape(-1)
    grads = grads.reshape(ids.shape[0], -1)
    slots = _num_slots(ids.shape[0], block_rows)
    uids, inv = jnp.unique(ids, size=slots, fill_value=num_rows,
                           return_inverse=True)
    seg = jnp.zeros((slots, grads.shape[1]), jnp.float32
                    ).at[inv].add(grads.astype(jnp.float32))
    return uids, seg


# ---- shared row math (the kernel calls EXACTLY these helpers on its
# VMEM blocks — one definition, so fused-vs-reference parity cannot
# drift) ----

def row_adam_math(p, m, v, g, lr_t, b1: float, b2: float, eps: float):
    """One Adam step for a block of rows, all f32, at the
    bias-corrected step size `lr_t` — sparse_adam.adam_step_size,
    computed OUTSIDE the kernel: Mosaic lowers no scalar power
    ("failed to legalize operation 'math.powf'")."""
    m_new = b1 * m + (1.0 - b1) * g
    v_new = b2 * v + (1.0 - b2) * jnp.square(g)
    p_new = p - lr_t * m_new / (jnp.sqrt(v_new) + eps)
    return p_new, m_new, v_new


def requant_row_math(q, s, m, v, g, row_ids, salt, lr_t, b1: float,
                     b2: float, eps: float):
    """Row-Adam + requantize for a block of int8 rows: dequantize,
    Adam in f32, per-row absmax rescale, counter-hash dither over the
    ABSOLUTE [V, E] element index (row id * E + col — the same stream a
    full-table pass draws at those rows), round/clip back to int8.
    `row_ids` are the rows' table indices (int32 [R]); padded sentinel
    rows produce garbage that the caller discards."""
    f = q.astype(jnp.float32) * s
    p_new, m_new, v_new = row_adam_math(f, m, v, g, lr_t, b1, b2, eps)
    absmax = jnp.max(jnp.abs(p_new), axis=1, keepdims=True)
    s_new = jnp.maximum(absmax, _SCALE_FLOOR) / 127.0
    x = p_new / s_new
    emb = q.shape[-1]
    cols = jax.lax.broadcasted_iota(jnp.uint32, x.shape, 1)
    idx = row_ids.astype(jnp.uint32)[:, None] * jnp.uint32(emb) + cols
    q_new = jnp.clip(jnp.round(x + dither_from_index(idx, salt)),
                     -127, 127).astype(jnp.int8)
    return q_new, s_new, m_new, v_new


# ---- reference (XLA gather/scatter) live-row applies ----

def _apply_rows_reference(table, state: RowAdamState, uids, seg, count,
                          lr, b1, b2, eps):
    # sentinel uids gather a clipped garbage row and compute a garbage
    # update; the mode="drop" scatters discard exactly those rows
    p = jnp.take(table, uids, axis=0, mode="clip").astype(jnp.float32)
    m = jnp.take(state.m, uids, axis=0, mode="clip")
    v = jnp.take(state.v, uids, axis=0, mode="clip")
    p_new, m_new, v_new = row_adam_math(
        p, m, v, seg, adam_step_size(count, lr, b1, b2), b1, b2, eps)
    table = table.at[uids].set(p_new.astype(table.dtype), mode="drop")
    m = state.m.at[uids].set(m_new, mode="drop")
    v = state.v.at[uids].set(v_new, mode="drop")
    return table, RowAdamState(m=m, v=v)


def _apply_quant_rows_reference(qt: QuantTable, state: RowAdamState,
                                uids, seg, salt, count, lr, b1, b2,
                                eps, dither_ids=None):
    # `dither_ids` (default: uids) are the rows' GLOBAL table indices
    # for the counter-hash dither stream — they differ from the gather
    # indices only when `qt` is a model-axis-sharded block of a larger
    # table (mesh_sparse_apply), where the dither must still draw from
    # the absolute [V, E] element index a full-table pass would use.
    q = jnp.take(qt["q"], uids, axis=0, mode="clip")
    s = jnp.take(qt["s"], uids, axis=0, mode="clip")
    m = jnp.take(state.m, uids, axis=0, mode="clip")
    v = jnp.take(state.v, uids, axis=0, mode="clip")
    q_new, s_new, m_new, v_new = requant_row_math(
        q, s, m, v, seg, uids if dither_ids is None else dither_ids,
        salt, adam_step_size(count, lr, b1, b2), b1, b2, eps)
    new_q = qt["q"].at[uids].set(q_new, mode="drop")
    new_s = qt["s"].at[uids].set(s_new, mode="drop")
    new_m = state.m.at[uids].set(m_new, mode="drop")
    new_v = state.v.at[uids].set(v_new, mode="drop")
    return {"q": new_q, "s": new_s}, RowAdamState(m=new_m, v=new_v)


# ---- dispatch ----

def _resolve_fused(fused, table) -> bool:
    """`fused=None` (auto) selects the Pallas live-row kernel where it
    compiles: a TPU and 32-bit table rows. The kernel gathers rows by
    single-row DMA, and Mosaic (libtpu 0.0.34) refuses that for packed
    dtypes — a bf16/int8 row shares its 32-bit words with its
    neighbours: "Slice shape along dimension 0 must be aligned to
    tiling (8), but is 1" on `tpu.memref_slice` of
    memref<Vx128xbf16, #tpu.tiled<(8,128)(2,1)>, hbm>. Those tables
    take the XLA reference; `fused=True` on them raises that message
    on a TPU."""
    if fused is None:
        return (jax.default_backend() == "tpu"
                and not is_quantized(table)
                and table.dtype == jnp.float32)
    return bool(fused)


def sparse_row_adam(table: jax.Array, state: RowAdamState,
                    ids: jax.Array, grads: jax.Array, *,
                    count: jax.Array, lr: float, b1: float = 0.9,
                    b2: float = 0.999, eps: float = 1e-8,
                    fused=None, block_rows: int | None = None):
    """Dedup + segment-sum + live-row Adam for a float/bf16 table.

    `ids` [N] (any shape, flattened) with per-occurrence cotangents
    `grads` [N, E]; only the unique rows are read or written — no dense
    [V, E] carrier. `fused=None` auto-selects (`_resolve_fused`).
    Single-device entry: mesh steps route through
    `mesh_sparse_apply`, which runs the same dedup + apply per device
    inside shard_map. Returns (new_table, new_state)."""
    block_rows = block_rows or _BLOCK_ROWS
    uids, seg = dedup_segment_sum(ids, grads, table.shape[0],
                                  block_rows=block_rows)
    if _resolve_fused(fused, table):
        from code2vec_tpu.ops.pallas_sparse_update import \
            sparse_row_adam_fused
        return sparse_row_adam_fused(table, state, uids, seg,
                                     count=count, lr=lr, b1=b1, b2=b2,
                                     eps=eps, block_rows=block_rows)
    return _apply_rows_reference(table, state, uids, seg, count, lr,
                                 b1, b2, eps)


def sparse_requant_adam(qt: QuantTable, state: RowAdamState,
                        ids: jax.Array, grads: jax.Array,
                        rng: jax.Array, *, count: jax.Array, lr: float,
                        b1: float = 0.9, b2: float = 0.999,
                        eps: float = 1e-8, fused=None,
                        block_rows: int | None = None):
    """Dedup + segment-sum + live-row requantize-aware Adam for an int8
    {q, s} table. ONE tiny threefry draw per call (the same salt
    derivation as ops/quant._dither), shared by the fused and reference
    paths so q parity is bit-exact under a fixed rng. Returns
    (new_qt, new_state)."""
    block_rows = block_rows or _BLOCK_ROWS
    salt = jax.random.bits(rng, dtype=jnp.uint32)
    uids, seg = dedup_segment_sum(ids, grads, qt["q"].shape[0],
                                  block_rows=block_rows)
    if _resolve_fused(fused, qt):
        from code2vec_tpu.ops.pallas_sparse_update import \
            sparse_requant_adam_fused
        return sparse_requant_adam_fused(qt, state, uids, seg, salt,
                                         count=count, lr=lr, b1=b1,
                                         b2=b2, eps=eps,
                                         block_rows=block_rows)
    return _apply_quant_rows_reference(qt, state, uids, seg, salt,
                                       count, lr, b1, b2, eps)


def mesh_sparse_apply(mesh, table, state: RowAdamState, parts, *,
                      count: jax.Array, lr: float, b1: float = 0.9,
                      b2: float = 0.999, eps: float = 1e-8, fused=None,
                      block_rows: int | None = None, rng=None):
    """The compact sparse update under a mesh (ROADMAP item 2): no
    dense [V, E] carrier, bit-identical to the single-device compact
    path.

    Why not just run sparse_row_adam under GSPMD: the dedup composition
    (jnp.unique at a static slot count + segment scatter) MISCOMPILES
    when the partitioner shards its inputs (measured, round 13 — wrong
    segment sums). So the whole dedup/segment-sum/apply runs INSIDE
    `shard_map` (manual SPMD — the partitioner never sees it):

      1. all-gather each sharded part's per-occurrence ids and
         cotangents over the composite batch axes ('dcn', 'data'),
         tiled, so every device holds the GLOBAL occurrence list in
         batch order; replicated parts (the shared sampled-softmax
         sample) pass through.
      2. concatenate parts in caller order and run the SAME
         `dedup_segment_sum` a single device would — identical input
         order means identical f32 additions in identical order, which
         is what makes the mesh path bit-exact vs the single-device
         compact path (and, transitively, vs the dense-carrier
         scatter-add in f32 — the round-13 property).
      3. apply live rows on the LOCAL table block: with the vocab dim
         sharded over 'model' each shard translates global unique ids
         into its row window (out-of-window rows become the local
         sentinel and are dropped by the scatter); data/dcn shards hold
         identical replicas and compute the identical update. int8
         blocks draw dither from the GLOBAL row index, so a sharded
         pass and a full-table pass emit identical bits.

    `parts` is a sequence of `(ids, grads, sharded)` triples holding
    GLOBAL-shape arrays ([N] / [N, E]); `sharded=True` marks arrays
    whose leading dim rides the ('dcn', 'data') batch axes (per-example
    gathers), False marks replicated arrays (the shared sample).
    ICI cost: one [N] + [N, E] all-gather per sharded part — the
    per-occurrence cotangents, NOT the [V, E] table; HBM cost per
    device: the single-device compact apply (∝ U live rows).
    `fused` follows resolve_sparse_update_mode exactly like the
    single-device path — SPARSE_UPDATE_PALLAS is honored under the
    mesh (the kernel runs per device inside the manual region).
    Returns (new_table, new_state)."""
    from code2vec_tpu.parallel.compat import shard_map
    from code2vec_tpu.parallel.mesh import (CONTEXT_AXIS, DATA_AXIS,
                                            DCN_AXIS, MODEL_AXIS)

    quant = is_quantized(table)
    block_rows = block_rows or _BLOCK_ROWS
    mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    if mesh_shape.get(CONTEXT_AXIS, 1) != 1:
        raise ValueError(
            "mesh sparse updates require ctx=1 (the bag encoder's "
            f"batch never shards over 'ctx'; got mesh {mesh_shape})")
    model_shards = mesh_shape.get(MODEL_AXIS, 1)
    num_rows = (table["q"] if quant else table).shape[0]
    if num_rows % model_shards:
        raise ValueError(
            f"table rows {num_rows} not divisible by model axis "
            f"{model_shards} (ModelDims.vocab_pad_multiple)")
    salt = jnp.uint32(0)
    if quant:
        if rng is None:
            raise ValueError("int8 mesh sparse update needs `rng` for "
                             "the requantize dither salt")
        salt = jax.random.bits(rng, dtype=jnp.uint32)

    ids_list = [ids.reshape(-1) for ids, _g, _sh in parts]
    grads_list = [g.reshape(ids.shape[0], -1)
                  for ids, (_i, g, _sh) in zip(ids_list, parts)]
    flags = [bool(sh) for _i, _g, sh in parts]

    batch_axes = (DCN_AXIS, DATA_AXIS)
    P = jax.sharding.PartitionSpec
    row_spec = P(MODEL_AXIS, None)
    table_spec = {"q": row_spec, "s": row_spec} if quant else row_spec
    in_specs = (table_spec, row_spec, row_spec, P(), P(),
                *[P(batch_axes) if sh else P(None) for sh in flags],
                *[P(batch_axes, None) if sh else P(None, None)
                  for sh in flags])
    out_specs = (table_spec, row_spec, row_spec)

    def body(tbl, m, v, count_, salt_, *flat):
        k = len(flags)
        g_ids, g_grads = [], []
        for i in range(k):
            ids_i, grads_i = flat[i], flat[k + i]
            if flags[i]:
                ids_i = jax.lax.all_gather(ids_i, batch_axes, axis=0,
                                           tiled=True)
                grads_i = jax.lax.all_gather(grads_i, batch_axes,
                                             axis=0, tiled=True)
            g_ids.append(ids_i)
            g_grads.append(grads_i)
        ids = jnp.concatenate(g_ids) if k > 1 else g_ids[0]
        grads = jnp.concatenate(g_grads) if k > 1 else g_grads[0]
        uids, seg = dedup_segment_sum(ids, grads, num_rows,
                                      block_rows=block_rows)
        r_local = (tbl["q"] if quant else tbl).shape[0]
        if model_shards > 1:
            lo = jax.lax.axis_index(MODEL_AXIS) * r_local
            in_win = (uids >= lo) & (uids < lo + r_local)
            luids = jnp.where(in_win, uids - lo, r_local)
        else:
            luids = uids
        st = RowAdamState(m=m, v=v)
        if quant:
            if model_shards > 1 or not _resolve_fused(fused, tbl):
                # the fused kernel derives dither from its gather ids;
                # a model-sharded block needs the GLOBAL ids for that
                # stream, which only the reference threads through
                new_t, new_st = _apply_quant_rows_reference(
                    tbl, st, luids, seg, salt_, count_, lr, b1, b2,
                    eps, dither_ids=uids)
            else:
                from code2vec_tpu.ops.pallas_sparse_update import \
                    sparse_requant_adam_fused
                new_t, new_st = sparse_requant_adam_fused(
                    tbl, st, luids, seg, salt_, count=count_, lr=lr,
                    b1=b1, b2=b2, eps=eps, block_rows=block_rows)
        elif _resolve_fused(fused, tbl):
            from code2vec_tpu.ops.pallas_sparse_update import \
                sparse_row_adam_fused
            new_t, new_st = sparse_row_adam_fused(
                tbl, st, luids, seg, count=count_, lr=lr, b1=b1,
                b2=b2, eps=eps, block_rows=block_rows)
        else:
            new_t, new_st = _apply_rows_reference(
                tbl, st, luids, seg, count_, lr, b1, b2, eps)
        return new_t, new_st.m, new_st.v

    fn = shard_map(body, mesh=mesh, in_specs=in_specs,
                   out_specs=out_specs)
    new_t, new_m, new_v = fn(table, state.m, state.v, count, salt,
                             *ids_list, *grads_list)
    return new_t, RowAdamState(m=new_m, v=new_v)


def rows_from_dense(table, state: RowAdamState, dense_grad: jax.Array,
                    ids: jax.Array, *, count: jax.Array, lr: float,
                    b1: float = 0.9, b2: float = 0.999,
                    eps: float = 1e-8, fused=None,
                    block_rows: int | None = None):
    """Live-row Adam fed by a DENSE [V, E] cotangent (the varmisuse
    head: its loss gathers inside the differentiated function, so
    autodiff already emits the dense scatter-added carrier). The dense
    rows at the unique ids ARE the segment sums — gathering per
    occurrence and re-summing would multiply each row by its duplicate
    count — so this skips the segment-sum and pays only the [U, E]
    gather out of the carrier. Half the win of the carrier-free path
    (the backward scatter remains dense), all of the optimizer-walk
    win."""
    block_rows = block_rows or _BLOCK_ROWS
    ids = ids.reshape(-1)
    slots = _num_slots(ids.shape[0], block_rows)
    num_rows = table.shape[0]
    uids = jnp.unique(ids, size=slots, fill_value=num_rows)
    seg = jnp.take(dense_grad, uids, axis=0,
                   mode="clip").astype(jnp.float32)
    if _resolve_fused(fused, table):
        from code2vec_tpu.ops.pallas_sparse_update import \
            sparse_row_adam_fused
        return sparse_row_adam_fused(table, state, uids, seg,
                                     count=count, lr=lr, b1=b1, b2=b2,
                                     eps=eps, block_rows=block_rows)
    return _apply_rows_reference(table, state, uids, seg, count, lr,
                                 b1, b2, eps)


# ---- analytic traffic model (bench.py attribution + the live
# opt_efficiency gauge) ----

def sparse_update_traffic_bytes(table, n_ids: int, unique_rows: int,
                                *, grad_itemsize: int = 4,
                                block_rows: int = _BLOCK_ROWS) -> int:
    """Analytic HBM bytes of ONE sparse apply at U live rows: ids read
    once (the sort's log-factor passes are excluded — ids are ~0.1% of
    the row traffic), per-occurrence cotangents read once, the compact
    segment buffer written + read once, and per LIVE row: table rows
    read + written (int8: q AND s) plus both f32 moment rows read +
    written. The [U, E]-aware floor comparator for bench.py's
    `sparse_update_*` attribution — the dense path this replaces moves
    table+moment traffic proportional to V, not U."""
    n_slots = _num_slots(n_ids, block_rows)
    emb = (table["q"] if is_quantized(table) else table).shape[-1]
    total = n_ids * 4                       # ids read
    total += n_ids * emb * grad_itemsize    # cotangent rows read
    total += n_slots * emb * 4 * 2          # segment buffer w + r
    if is_quantized(table):
        total += unique_rows * emb * 1 * 2  # q rows r + w
        total += unique_rows * 4 * 2        # s rows r + w
    else:
        itemsize = table.dtype.itemsize
        total += unique_rows * emb * itemsize * 2   # param rows r + w
    total += unique_rows * emb * 4 * 2 * 2          # m and v rows r + w
    return int(total)


def table_id_counts(batch_size: int, max_contexts: int,
                    num_sampled: int = 0) -> dict:
    """Per-table gathered-id counts of one sparse train step (the
    code2vec head): token rows are gathered for src AND dst, target
    rows (sampled softmax) for the labels plus the shared sample."""
    counts = {"token_emb": 2 * batch_size * max_contexts,
              "path_emb": batch_size * max_contexts}
    if num_sampled:
        counts["target_emb"] = batch_size + num_sampled
    return counts


def sparse_update_phase_bytes(params, batch_size: int,
                              max_contexts: int, *,
                              num_sampled: int = 0,
                              block_rows: int = _BLOCK_ROWS,
                              processes: int = 1) -> int:
    """Analytic PER-DEVICE HBM bytes of the dedup/segment-sum/apply
    phase alone for one step over the three tables — the same
    per-table expected-unique-rows and grad-itemsize rules as
    sparse_step_floor_bytes (single source: bench.py's
    `sparse_update_bytes` attribution and the train loop's live
    `train/sparse_update_bytes` gauge must agree for the same config).
    Under a mesh every device runs the phase over the all-gathered
    GLOBAL occurrence list (mesh_sparse_apply), so `processes` scales
    the per-process `batch_size` up to the global count; the data-axis
    shard count does not appear (the phase is replicated, not
    sharded). Row-sharded tables are not described — see
    sparse_step_floor_bytes."""
    total = 0
    for key, n in table_id_counts(batch_size, max_contexts,
                                  num_sampled).items():
        table = params.get(key)
        if table is None:
            continue
        n_global = n * processes
        if is_quantized(table):
            num_rows, grad_itemsize = table["q"].shape[0], 2
        else:
            num_rows = table.shape[0]
            grad_itemsize = table.dtype.itemsize
        total += sparse_update_traffic_bytes(
            table, n_global, expected_unique_rows(n_global, num_rows),
            grad_itemsize=grad_itemsize, block_rows=block_rows)
    return int(total)


def sparse_step_floor_bytes(params, batch_size: int, max_contexts: int,
                            *, num_sampled: int = 0,
                            block_rows: int = _BLOCK_ROWS,
                            data_shards: int = 1,
                            processes: int = 1) -> int:
    """Analytic PER-DEVICE per-step HBM bytes of the FULL sparse-update
    step — the [U, E]-aware replacement for bench.py's dense
    `_step_hbm_bytes` (which counts a dense [V, E] carrier write+read
    and a table-proportional optimizer walk this path does not
    perform): forward row gathers (per occurrence), backward cotangent
    writes, and the dedup/segment-sum/live-row apply traffic
    (sparse_update_traffic_bytes at the uniform-ids E[U] — the bench
    worst case; real corpora are Zipfian, so this over-counts and the
    derived floor stays conservative). Dense non-table params add their
    usual grad/param/moment sweeps (negligible at java-large). Shared
    by bench.py's sparse floor attribution and the train loops' live
    `train/step_floor_ms` gauge (the health opt_efficiency monitor).

    Mesh model (round 14): `batch_size` stays the PER-PROCESS batch
    and `processes`/`data_shards` describe the topology — per device,
    the forward gathers and backward cotangent writes cover only the
    device's batch shard (global occurrences / data_shards), while the
    dedup/segment-sum/apply phase runs over the all-gathered GLOBAL
    occurrence list on every device (mesh_sparse_apply replicates that
    work rather than paying a second collective round). The defaults
    (1, 1) are the single-device identity. Row-sharded tables
    (model axis > 1) are NOT described — callers skip the gauges
    there (the window-masked apply needs its own model)."""
    counts = table_id_counts(batch_size, max_contexts, num_sampled)
    total = 0
    for key, n in counts.items():
        table = params.get(key)
        if table is None:
            continue
        n_global = n * processes
        n_local = n_global / data_shards
        if is_quantized(table):
            num_rows, emb = table["q"].shape
            row_bytes, grad_itemsize = emb * 1 + 4, 2  # q row + scale
        else:
            num_rows, emb = table.shape
            row_bytes = emb * table.dtype.itemsize
            grad_itemsize = table.dtype.itemsize
        u = expected_unique_rows(n_global, num_rows)
        total += int(n_local * row_bytes)  # forward row gathers
        total += int(n_local * emb * grad_itemsize)  # bwd cotangents
        total += sparse_update_traffic_bytes(
            table, n_global, u, grad_itemsize=grad_itemsize,
            block_rows=block_rows)
    for key, p in params.items():
        if key in counts or is_quantized(p):
            continue  # row-gathered tables: handled above
        for leaf in jax.tree_util.tree_leaves(p):
            b = leaf.size * leaf.dtype.itemsize
            total += b * 4 + b * 4  # grad w+r, param r+w, m/v r+w
    return int(total)


def expected_unique_rows(n_ids: int, num_rows: int) -> int:
    """E[U] for n uniform draws over V rows (the bench worst case):
    V * (1 - (1 - 1/V)^n). Real corpora are Zipfian (fewer uniques),
    so a floor derived from this over-counts live-row traffic and stays
    conservative."""
    import math
    if num_rows <= 0 or n_ids <= 0:
        return 0
    return int(num_rows * (1.0 - math.exp(
        n_ids * math.log1p(-1.0 / num_rows))))
