"""What the decoder-block encoders share (`lfm2_moe_encoder.py`,
`qwen3_next_encoder.py`, `joyai_flash_encoder.py`,
`granite_hybrid_encoder.py`, `smallthinker_encoder.py`): a stack of
residual layers run over a
method's path-contexts in reader order, position = slot index, valid
contexts filling from the left. `x` is [B, C, H], `m` the context mask,
e an input multiplier and r a residual one (both 1 unless the block
gives them).

  input    x = e (c W_in) m               3E -> H; masked slots enter as
                                          zeros (`run_block`)
  layer    x = x + r Mixer(norm(x)) ; x = x + r FF(norm(x))
                                          `residual_layer`: rematerialised,
                                          under `c2v/blk_<i>/...` scopes;
                                          the second half over the
                                          staircase's positions as one
                                          flat sequence where the step
                                          was compiled for one (`pack`,
                                          `lay_back`); a router that
                                          reads the layer's input before
                                          the mixer is `early`
  output   norm ; the product's learned-query pool over valid slots at
           width H ; code = pooled W_out2          H -> 3E (`run_block`)

and the operators more than one block has: rotary over the whole head
or its first part (`rotary`), the masked causal softmax, banded where a
layer has a window (`causal_softmax`), and the core around it
(`causal_core`: scores, softmax, values) under the two softmax mixers,
causal grouped-query attention with the head's width, the q/k norm and
an optional output gate as arguments (`attention`) and multi-head latent
attention (`latent_attention`: norms on two low-rank latents, a key of
two parts of which one is a single rotary head shared by every query
head), the SwiGLU (`swiglu`), the sigmoid routers' fixed selection bias
(`BIAS_SCALE`), and the routed experts' wrapper that makes the counts
which leave the step (`routed_experts`). Which norm, which mixers and
which router a block has is its own module's.

Who passes the training staircase (`data/staircase.py`) to what:
`training/steps.make_train_step` compiles its staircase step with one,
`encode_lfm2_moe`, `encode_qwen3_next`, `encode_joyai_flash`,
`encode_granite_hybrid` and `encode_smallthinker` hand it
to `embed_contexts` (any mesh) and ask `core_blocks` here for the query
blocks it gives their softmax mixers (`attention(blocks=)`,
`latent_attention(blocks=)`), which hand them to `causal_core`: the
core then runs by query block. `core_blocks` is the one place that
says whether it does (a staircase, and the batch's rows on one device):
the producer's count of the pairs a step scores
(`Code2VecModel._train_device_batch`) asks it too. Every other program
(the full step, evaluation, prediction, serving, rows dealt to several
devices) gets None and lowers to the one whole core. The feed-forward
half of every layer (norm, MLP, shared expert, router, routed experts)
goes the same way: the block encoders ask `ff_rectangles`, on
`core_blocks`' condition, for the staircase's own rectangles and hand
them to `residual_layer`, which then runs that half over the
rectangles' positions as one flat `[1, area, H]` sequence; the producer
counts those positions by the same function (`ff_slots`). The mixers'
own projections still see every slot: they feed per-block cuts and pay
a layout an operator (PERF.md section 6, PR 35 and PR 37).
"""

from __future__ import annotations

import math
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from code2vec_tpu.data.staircase import Stairs, query_blocks
from code2vec_tpu.models.transformer_encoder import (learned_query_pool,
                                                     padding_log_mask)
from code2vec_tpu.ops.moe import (busiest_share, counts_zeros,
                                  held_experts_ffn, ran_at_bound)

# the sigmoid routers' selection bias is a seeded buffer of this scale
# x normal, held fixed: small beside the gaps between a token's top
# scores (about 0.016 between the fourth and the fifth of 64), it turns
# near-ties and leaves the load on the experts even, as the trained
# buffer's job is
BIAS_SCALE = 0.005


def rotary(x: jax.Array, theta: float, turned: Optional[int] = None,
           first: int = 0) -> jax.Array:
    """x [B, heads, C, hd], position = `first` + index along C; the
    first `turned` of each head turn (None: the whole head), pairs (i,
    i + turned/2) (rotate-half); the rest pass as they are."""
    hd = x.shape[-1]
    if turned is not None and turned < hd:
        return jnp.concatenate(
            [rotary(x[..., :turned], theta, first=first), x[..., turned:]],
            axis=-1)
    C = x.shape[-2]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    slot = jnp.arange(C, dtype=jnp.float32)
    angle = (first + slot if first else slot)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)
    x32 = x.astype(jnp.float32)
    half = jnp.concatenate([-x32[..., hd // 2:], x32[..., :hd // 2]], -1)
    return (x32 * cos + half * sin).astype(x.dtype)


def causal_softmax(logits: jax.Array, mask: jax.Array, dtype,
                   window: Optional[int] = None) -> jax.Array:
    """Scores [B, ..heads.., Q queries, C keys] in float32 to attention
    weights in `dtype`: the queries are the last Q of the C slots,
    query t sees the valid slots up to t, and where a `window` is given
    only those after t - window (t - window < t' <= t), the softmax runs
    in float32. A window that no query of these keys can outrun (window
    >= C) adds no term: the same program as none."""
    queries, keys = logits.shape[-2:]
    slot = jnp.arange(keys)
    at = slot if queries == keys else slot[keys - queries:]
    causal = slot[None, :] <= at[:, None]
    if window is not None and window < keys:
        causal = causal & (slot[None, :] > at[:, None] - window)
    seen = causal[None] & (mask > 0)[:, None, :]
    heads = (None,) * (logits.ndim - 3)
    logits = jnp.where(seen[(slice(None),) + heads], logits, -1e30)
    return jax.nn.softmax(logits, axis=-1).astype(dtype)


# (first slot, end slot, rows): a query block (`core_blocks`), whose
# queries are its rows' slots first .. end and see, by causality, those
# rows' slots 0 .. end; or a rectangle of the staircase
# (`ff_rectangles`), which holds its rows' slots first .. end
Block = Tuple[int, int, int]
Span = Optional[Tuple[int, int]]


def cut(t: jax.Array, *spans: Span) -> jax.Array:
    """`t[start:end]` along each axis that `spans` gives a (start, end)
    for (None, or none given: the whole axis), in ONE slice of `t`: cut
    in two steps, rows and then slots, XLA lays the rows' every slot out
    again before it cuts (PERF.md section 6, PR 35). `t` itself where
    every span is whole."""
    whole = [(0, size) for size in t.shape]
    spans = [span or axis for span, axis in
             zip(spans + (None,) * (t.ndim - len(spans)), whole)]
    if spans == whole:
        return t
    return jax.lax.slice(t, *zip(*spans))


def _to_rows(t: jax.Array, rows: int) -> jax.Array:
    """t with zero rows below it, up to `rows`."""
    return jnp.pad(t, ((0, rows - t.shape[0]),) + ((0, 0),) * (t.ndim - 1))


def causal_core(logits: Callable, v: Callable, mask: jax.Array, dtype, *,
                values: str, slot_axis: int,
                blocks: Optional[Tuple[Block, ...]] = None,
                window: Optional[int] = None) -> jax.Array:
    """What the two softmax mixers share between their projections: the
    causal and the padding mask (banded by `window` where one is given:
    `causal_softmax`), the softmax in float32 and `values` (an
    einsum of the weights in `dtype` and v). The mixer's own are
    `logits(block)`, its scores [rows, ..heads.., queries, keys] in
    float32 and already scaled, and `v(block)`, each made from the
    block's `cut` of its projections (v is asked for after the softmax,
    where it always was taken). The result holds its slots on
    `slot_axis`, its rows on axis 0.

    `blocks` (`core_blocks`; None: the one core over every row and slot
    that this always was) is the caller's word that the batch is ordered
    longest bag first and PAD outside the blocks (`embed_contexts` has
    who checks it). The core then runs once a query block: the block's
    rows and slots of q against the same rows of k and v up to the
    block's last slot. A valid query's result differs from the whole
    core's by the order of its sums alone; a PAD query outside the
    blocks, which nothing reads, gets zeros."""
    B, C = mask.shape

    def core(block: Block) -> jax.Array:
        _, end, kept = block
        att = causal_softmax(logits(block), cut(mask, (0, kept), (0, end)),
                             dtype, window)
        return jnp.einsum(values, att, v(block))

    if blocks is None:
        return core((0, C, B))
    return jnp.concatenate([_to_rows(core(block), B) for block in blocks],
                           axis=slot_axis)


def attention(h: jax.Array, mask: jax.Array, layer: Dict, *, heads: int,
              kv_heads: int, head_dim: int, theta: Optional[float],
              norm: Optional[Callable], turned: Optional[int] = None,
              gated: bool = False,
              blocks: Optional[Tuple[Block, ...]] = None,
              scale: Optional[float] = None,
              window: Optional[int] = None) -> jax.Array:
    """Causal grouped-query attention over h [B, C, H]: `layer` holds q,
    k, v, o and, where the block norms q and k, the q/k norms' q_norm,
    k_norm (`norm(t, scale)` runs over each head); kv head j serves
    query heads j n/n_kv ..; q and k turn by the rotary term (`theta`;
    None: no position term, NoPE); scores over sqrt(head_dim), or times
    `scale` where it is given, under the causal and the padding mask,
    softmax in float32; `window` (None: every earlier slot) lets query t
    see keys t - window < t' <= t alone. `gated`: q's projection is twice
    as wide, a head's second half a gate, and the heads' output is
    multiplied by its sigmoid before o. `blocks`: `causal_core`."""
    dtype = h.dtype
    B, C, _ = h.shape
    n, n_kv, hd = heads, kv_heads, head_dim

    def split(t, count, scale=None):
        t = t.reshape(B, C, count, -1)
        gate = None
        if t.shape[-1] != hd:
            t, gate = t[..., :hd], t[..., hd:]
        if scale is not None:
            t = norm(t, scale)
        return t.transpose(0, 2, 1, 3), gate           # [B, count, C, hd]

    q, gate = split(h @ layer["q"].astype(dtype), n, layer.get("q_norm"))
    assert (gate is not None) == gated
    turn = theta is not None
    if blocks is None and turn:  # the whole core turns q where it always did
        q = rotary(q, theta, turned)
    k = split(h @ layer["k"].astype(dtype), n_kv, layer.get("k_norm"))[0]
    if turn:
        k = rotary(k, theta, turned)
    v, _ = split(h @ layer["v"].astype(dtype), n_kv)

    def logits(block):
        """By block q turns on the block's rows, from its first slot on
        (on the v5e the whole q turned and then cut costs the layer a
        pass more: PERF.md section 6, PR 35)."""
        first, end, kept = block
        q_b = cut(q, (0, kept), None, (first, end))
        if blocks is not None and turn:
            q_b = rotary(q_b, theta, turned, first=first)
        q_b = q_b.reshape(-1, n_kv, n // n_kv, end - first, hd)
        scores = jnp.einsum("bkgqd,bkcd->bkgqc", q_b,
                            cut(k, (0, kept), None, (0, end))
                            ).astype(jnp.float32)
        return scores / math.sqrt(hd) if scale is None else scores * scale

    out = causal_core(
        logits, lambda block: cut(v, (0, block[2]), None, (0, block[1])),
        mask, dtype, values="bkgqc,bkcd->bkgqd", slot_axis=-2, blocks=blocks,
        window=window)
    out = out.reshape(B, n, C, hd).transpose(0, 2, 1, 3)
    if gated:
        out = out * jax.nn.sigmoid(gate)
    return out.reshape(B, C, n * hd) @ layer["o"].astype(dtype)


def deinterleaved(x: jax.Array) -> jax.Array:
    """The last axis' pairs (2i, 2i + 1) laid out as (i, i + n/2): what
    `rotary` turns after this is what an interleaved rotary turns
    before it, and a product of two heads so laid out is the product of
    the heads."""
    return jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)


def latent_attention(h: jax.Array, mask: jax.Array, layer: Dict, *,
                     heads: int, nope: int, rope: int, v_dim: int,
                     theta: float, norm: Callable,
                     blocks: Optional[Tuple[Block, ...]] = None
                     ) -> jax.Array:
    """Causal multi-head latent attention over h [B, C, H]. `layer`
    holds q_a [H, r_q], q_a_norm, q_b [r_q, heads (nope + rope)], kv_a
    [H, r_kv + rope], kv_a_norm, kv_b [r_kv, heads (nope + v_dim)] and
    o [heads v_dim, H]; `norm(t, scale)` runs over each latent:

      c_q = norm(h q_a) ; a head of q = [q_nope | q_rope] = c_q q_b
      [c_kv | k_rope] = h kv_a ; c_kv = norm(c_kv)
      a head of [k_nope | v] = c_kv kv_b ; k_rope is ONE head, every
      query head's
      q_rope and k_rope turn, pairs (2i, 2i + 1) (interleaved), theta
      scores = (q_nope . k_nope + q_rope . k_rope) / sqrt(nope + rope)

    under the causal and the padding mask, softmax in float32. The
    scores are one einsum over the concatenated head, summed in
    float32, the shared rotary key broadcast beside each head's k_nope
    (105 MB in bfloat16 at the published widths): on the v5e that is
    10.5 ms a layer cheaper, forward and backward, than a second einsum
    for the rotary part, whose float32 scores XLA writes and copies a
    second time (PERF.md section 6, PR 34). `blocks`: `causal_core`."""
    dtype = h.dtype
    B, C, _ = h.shape

    def turned(t, first=0):                     # [B, C, n, rope]
        return rotary(deinterleaved(t).transpose(0, 2, 1, 3), theta,
                      first=first).transpose(0, 2, 1, 3)

    with jax.named_scope("q_lora"):
        c_q = norm(h @ layer["q_a"].astype(dtype), layer["q_a_norm"])
        q = (c_q @ layer["q_b"].astype(dtype)).reshape(B, C, heads,
                                                       nope + rope)
    with jax.named_scope("kv_lora"):
        c_kv, k_rope = jnp.split(h @ layer["kv_a"].astype(dtype),
                                 [layer["kv_a"].shape[1] - rope], axis=-1)
        c_kv = norm(c_kv, layer["kv_a_norm"])
        kv = (c_kv @ layer["kv_b"].astype(dtype)).reshape(B, C, heads,
                                                          nope + v_dim)

    def logits(block):
        """q and k of a query block are made from its rows and slots of
        the projections: q's rotary part turned from the block's first
        slot on, the one rotary key turned and laid out under every
        head's k_nope."""
        first, end, kept = block
        rows, queries, keys = (0, kept), (first, end), (0, end)
        q_b = jnp.concatenate(
            [cut(q, rows, queries, None, (0, nope)),
             turned(cut(q, rows, queries, None, (nope, nope + rope)),
                    first)], axis=-1)
        k_r = turned(cut(k_rope, rows, keys)[:, :, None, :])
        k_r = jnp.broadcast_to(k_r, k_r.shape[:2] + (heads, rope))
        k_b = jnp.concatenate(
            [cut(kv, rows, keys, None, (0, nope)), k_r], axis=-1)
        return jnp.einsum("bqnd,bcnd->bnqc", q_b, k_b,
                          preferred_element_type=jnp.float32) \
            / math.sqrt(nope + rope)

    def v(block):
        return cut(kv, (0, block[2]), (0, block[1]), None,
                   (nope, nope + v_dim))

    with jax.named_scope("core"):
        out = causal_core(logits, v, mask, dtype, values="bnqc,bcnd->bqnd",
                          slot_axis=1, blocks=blocks)
    with jax.named_scope("o"):
        return out.reshape(B, C, heads * v_dim) @ layer["o"].astype(dtype)


def swiglu(h: jax.Array, w1: jax.Array, w3: jax.Array,
           w2: jax.Array) -> jax.Array:
    dtype = h.dtype
    return (jax.nn.silu(h @ w1.astype(dtype))
            * (h @ w3.astype(dtype))) @ w2.astype(dtype)


def routed_experts(h: jax.Array, mask: jax.Array, score: Callable,
                   w1: jax.Array, w3: jax.Array, w2: jax.Array, *,
                   first_expert: int, routed: int, act: str = "silu",
                   wait_for_busiest: bool = False
                   ) -> Tuple[jax.Array, jax.Array]:
    """(the held experts' output [B, C, H]; int32 [1, held + 3]: the
    rows each held expert took, the valid tokens, the rows the layer's
    arrays may hold and whether it ran at that bound
    (`moe.ran_at_bound`), and for a ReGLU (`act` "relu") two columns
    more, the live inner entries that are zero and how many there are
    (`held_experts_ffn`)).
    `score(tokens [N, H])` is the block's router: (chosen [N, K],
    p [N, K]), `ops/moe.route`; `act` is `held_experts_ffn`'s.
    `wait_for_busiest`: the products run over the busiest share's rows
    (`moe.busiest_share`, handed to `held_experts_ffn` as `busiest`)."""
    B, C, H = h.shape
    tokens = h.reshape(B * C, H)
    valid = mask.reshape(B * C) > 0
    held = w1.shape[0]
    with jax.named_scope("router"):
        chosen, p = score(tokens)
    with jax.named_scope("experts"):
        busiest = (busiest_share(chosen, valid, first_expert, held, routed)
                   if wait_for_busiest and routed > held else None)
        out, counted = held_experts_ffn(tokens, valid, chosen, p, w1, w3,
                                        w2, first_expert, routed, act,
                                        busiest)
    rows, inner = ((counted[:held], [counted[held:]]) if counts_zeros(act)
                   else (counted, []))
    bound, at_bound = ran_at_bound(rows, chosen.size, routed, busiest)
    counts = jnp.concatenate([rows, jnp.stack([
        jnp.sum(valid, dtype=jnp.int32), jnp.int32(bound),
        at_bound.astype(jnp.int32)])] + inner)
    return out.reshape(B, C, H), counts[None]


def pack(t: jax.Array, rectangles: Tuple[Block, ...]) -> jax.Array:
    """t [B, C, ...] -> [1, area, ...]: the rectangles' positions as
    one flat sequence, rectangle by rectangle, each row by row, each
    taken in ONE slice of `t` (`cut`)."""
    flat = [cut(t, (0, kept), (first, end)).reshape((-1,) + t.shape[2:])
            for first, end, kept in rectangles]
    return jnp.concatenate(flat)[None]


def lay_back(flat: jax.Array, rectangles: Tuple[Block, ...],
             rows: int) -> jax.Array:
    """`pack`'s way back: flat [1, area, ...] -> [rows, C, ...], zeros
    outside the rectangles (which stand side by side from slot 0 to
    C)."""
    out, start = [], 0
    for first, end, kept in rectangles:
        size = kept * (end - first)
        out.append(_to_rows(flat[0, start:start + size].reshape(
            (kept, end - first) + flat.shape[2:]), rows))
        start += size
    return jnp.concatenate(out, axis=1)


def residual_layer(i: int, *, norm: Callable, mixer_scope: str,
                   mixer: Callable, ff: Callable, mask: jax.Array,
                   ff_scope: Optional[str] = None,
                   rectangles: Optional[Tuple[Block, ...]] = None,
                   residual_multiplier: Optional[float] = None,
                   early: Optional[Callable] = None) -> Callable:
    """Layer i as `run(x, layer) -> (x, counts)`, rematerialised in the
    backward pass (at H = 2048 a layer's activations are the memory).
    `mixer(h, layer)` gives the operator's output; `ff(h, mask, layer)`
    the feed-forward's over positions h [rows, slots, H] under their
    `mask` [rows, slots], and its counts ([devices, n] int32, summed
    here over the devices, or None); `norm(x, scale)` the block's norm,
    over the layer's `op_norm` and `ff_norm`.

    `rectangles` (`ff_rectangles`; None: every slot of every row, as
    this always was) is the caller's word that the batch is ordered
    longest bag first and PAD outside them (`embed_contexts` has who
    checks it). The feed-forward half, norm and `ff`, is position-wise,
    so it then runs over the rectangles' positions as ONE sequence
    [1, area, H] (`pack`), under the mask packed the same way, and its
    output is laid back once (`lay_back`). A slot outside the
    rectangles keeps x: nothing valid reads it (the mixers look left
    and valid slots fill from the left, the pool and the routers mask),
    and no gradient comes back from it.

    `residual_multiplier` (None: 1, as this always was) scales both
    branches before they are added: x = x + m Mixer, x = x + m FF.

    `early(h, layer)` (None: nothing, as this always was) is what the
    feed-forward reads of the mixer's input, a router placed before
    attention: computed from the op-normed h [rows, slots, ...] over
    every slot under `c2v/blk_<i>/router`, before the mixer, packed as
    the mask is, and handed to `ff(h, mask, layer, early)` as its
    fourth argument."""
    ff_scope = f"c2v/blk_{i}" + (f"/{ff_scope}" if ff_scope else "")
    ff_mask = mask if rectangles is None else pack(mask, rectangles)

    def scaled(t):
        return t if residual_multiplier is None else t * residual_multiplier

    def run(x, layer):
        h = norm(x, layer["op_norm"])
        read = ()
        if early is not None:
            with jax.named_scope(f"c2v/blk_{i}/router"):
                read = (early(h, layer),)
            if rectangles is not None:
                read = (pack(read[0], rectangles),)
        with jax.named_scope(f"c2v/blk_{i}/{mixer_scope}"):
            x = x + scaled(mixer(h, layer))
        kept = x if rectangles is None else pack(x, rectangles)
        h = norm(kept, layer["ff_norm"])
        with jax.named_scope(ff_scope):
            out, counts = ff(h, ff_mask, layer, *read)
        out = scaled(out)
        if rectangles is not None:
            out = lay_back(out, rectangles, x.shape[0])
        return x + out, (None if counts is None
                         else jnp.sum(counts, axis=0))

    return jax.checkpoint(run)


def run_block(sub: Dict, emb: jax.Array, mask: jax.Array, compute_dtype,
              *, layer_fn: Callable, norm: Callable, counts_width: int,
              input_multiplier: Optional[float] = None
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The stack over embedded contexts emb [B, C, 3E]: `sub` is the
    encoder's subtree (`in_proj`, `layers`, `ln_f_scale`, `pool_query`,
    `out_proj`), `layer_fn(i)` a `residual_layer`; the stack's input is
    (emb W_in) m, times `input_multiplier` where it is given. Returns
    the encode contract's (code, pool attention, aux): aux int32
    [layers that count, counts_width], empty where none does."""
    counted = []
    with jax.named_scope("c2v/encode"):
        x = emb @ sub["in_proj"].astype(compute_dtype)
        if input_multiplier is not None:
            x = x * input_multiplier
        # masked slots enter as zeros
        x = x * mask[..., None].astype(compute_dtype)
        for i, layer in enumerate(sub["layers"]):
            x, counts = layer_fn(i)(x, layer)
            if counts is not None:
                counted.append(counts)

    with jax.named_scope("c2v/pool"):
        x = norm(x, sub["ln_f_scale"])
        pooled, attn = learned_query_pool(x, sub["pool_query"],
                                          padding_log_mask(mask),
                                          compute_dtype)
        code = pooled @ sub["out_proj"].astype(compute_dtype)
    return code, attn, (jnp.stack(counted) if counted else jnp.zeros(
        (0, counts_width), jnp.int32))


def batch_devices(mesh) -> int:
    """The devices a batch's rows are dealt to (1 with no mesh)."""
    if mesh is None:
        return 1
    from code2vec_tpu.parallel.mesh import DATA_AXIS, DCN_AXIS
    return mesh.shape[DCN_AXIS] * mesh.shape[DATA_AXIS]


def core_blocks(staircase: Optional[Stairs], mesh, max_contexts: int
                ) -> Optional[Tuple[Block, ...]]:
    """The query blocks the softmax mixers' core runs over in a step
    compiled for `staircase` on `mesh` (`staircase.query_blocks`), or
    None where it runs whole: with no staircase, and with the batch's
    rows dealt to several devices (the staircase is one device's rows,
    and no cell runs a block over several). Both who compiles the step
    (the block encoders) and who counts what it scores (the producer's
    `attn_pairs`, `Code2VecModel._train_device_batch`) ask here."""
    if staircase is None or batch_devices(mesh) != 1:
        return None
    return query_blocks(staircase, max_contexts)


def ff_rectangles(staircase: Optional[Stairs], mesh, max_contexts: int
                  ) -> Optional[Tuple[Block, ...]]:
    """The rectangles whose positions the feed-forward half of every
    layer runs over in a step compiled for `staircase` on `mesh`
    (`residual_layer`): the staircase's own, from slot 0 to
    `max_contexts`, or None where it runs over every slot, on
    `core_blocks`' condition (no staircase, or the batch's rows dealt to
    several devices; nor for a staircase that does not start at slot 0,
    which no batch `fits`). Both who compiles the step (the block
    encoders) and who counts the positions it multiplies (the producer's
    `ff_slots`, `Code2VecModel._train_device_batch`) ask here."""
    if staircase is None or batch_devices(mesh) != 1 or staircase[0][0]:
        return None
    ends = [first for first, _ in staircase[1:]] + [max_contexts]
    return tuple((first, end, kept)
                 for (first, kept), end in zip(staircase, ends))


def refuse_context_parallel(cfg, name: str) -> None:
    if cfg.RING_ATTENTION or cfg.MESH_CONTEXT_AXIS > 1:
        raise ValueError(
            f"--encoder {name} has no ring attention and no "
            "context-parallel layout (its causal operators and "
            "mask run over whole sequences).")


def require_block_config(cfg, name: str) -> None:
    """A run that builds a model from scratch names the block's file
    (`--block_config`, also spelled `--lfm_config`)."""
    if not cfg.BLOCK_CONFIG and not cfg.is_loading:
        raise ValueError(
            f"--encoder {name} needs --block_config <json> (also spelled "
            "--lfm_config: the block's sizes; a checkpoint carries its "
            "own).")
