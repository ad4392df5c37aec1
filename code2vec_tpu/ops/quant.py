"""Sub-bf16 embedding tables: int8 storage with per-row scales.

BASELINE.md's round-4 structural-bound analysis ends: the per-chip step
is bound end to end by table *bytes* — the backward scatter and the
optimizer phase both stream the three vocab tables — so "further
per-chip gains need less work (smaller tables, lower-precision states),
not better scheduling". This module is that lever (VERDICT r4 item 3):
the two [V, E] leaf-token tables (token_emb / path_emb — 74% of table
params at java-large capacities; target_emb stays bf16 because the
sampled-softmax head matmuls against it) are stored as

    q : int8  [V, E]   (row value = q * s)
    s : f32   [V, 1]   (per-row absmax / 127)

halving their gather and optimizer-apply traffic vs bf16.

TPU-first design notes:

- **Gather-level dequantization** (`quantized_take`): rows dequantize
  AFTER the [B, C]-row gather — 1 byte/element crosses HBM instead of
  2, and the ``* s`` fuses into the gather consumer. The full table is
  never materialized in float during training.
- **Straight-through gradient via an unused carrier**: the backward
  pass needs the same dense [V, E] float cotangent the bf16 path
  scatter-adds (AD produces it; the optimizer consumes it). A
  `custom_vjp` routes the gather's cotangent to a zeros "carrier"
  argument the primal never reads — XLA dead-code-eliminates the
  carrier in the forward, so the carrier costs NO gather traffic and
  NO HBM residency (it is created as `jnp.zeros` inside the step and
  only its scatter-add materializes, exactly like the bf16 path's
  gradient buffer). The int8 `q` itself is a non-differentiable leaf
  (`allow_int=True` at the step's `value_and_grad`; its float0
  cotangent is dropped).
- **Stochastic-rounding requantize** (`requantize`): the int8 quantum
  (absmax/127 ≈ 3e-3 for unit-scale rows) is larger than a typical
  per-step update (~lr = 1e-3), so round-to-nearest would silently
  drop most updates and the tables would never train (the bf16
  freeze effect, BASELINE.md decay study, at 8x the magnitude).
  Uniform-dither rounding keeps the applied update correct in
  expectation. On TPU the whole update runs as ONE fused Pallas
  row-pass (ops/pallas_requant.py, round 6 — the multi-pass XLA form
  below re-streams the f32 table and cost +6.7 ms/step, BASELINE.md
  round 5); `requantize` dispatches between them.
  Untouched rows (update == 0) requantize stably: a
  freshly quantized row's absmax element is ±127, so the recomputed
  scale reproduces the old one to 1 ulp and round(q + eps + u) == q
  except on a ~1e-5-probability dither tail — no systematic drift
  (property-tested in tests/test_quant.py).
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

QuantTable = Dict[str, jax.Array]  # {"q": int8 [V, E], "s": f32 [V, 1]}

# keys that may be stored quantized under tables_dtype == "int8"
QUANTIZED_TABLE_KEYS = ("token_emb", "path_emb")

_SCALE_FLOOR = 1e-12  # all-zero rows quantize against this, not 1/0


def is_quantized(leaf) -> bool:
    """True for a {"q", "s"} quantized-table subtree."""
    return isinstance(leaf, dict) and set(leaf) == {"q", "s"}


def quantize_table(table: jax.Array) -> QuantTable:
    """f32/bf16 [V, E] -> {"q" int8, "s" f32[V,1]} (per-row absmax)."""
    t = table.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(t), axis=1, keepdims=True)
    s = jnp.maximum(absmax, _SCALE_FLOOR) / 127.0
    q = jnp.round(t / s).astype(jnp.int8)
    return {"q": q, "s": s}


def dequantize_table(qt: QuantTable, dtype=jnp.float32) -> jax.Array:
    """Materialize the full float table (serving/attack/export paths —
    NOT the train step, which dequantizes at gather granularity)."""
    return (qt["q"].astype(jnp.float32) * qt["s"]).astype(dtype)


@functools.lru_cache(maxsize=None)
def _qtake_for(shape: Tuple[int, ...], dtype_name: str):
    """The custom_vjp gather for one carrier (shape, dtype) — cached so
    each table's primitive is defined once (shape/dtype are static
    Python values; residuals stay JAX types)."""
    dtype = jnp.dtype(dtype_name)

    @jax.custom_vjp
    def qtake(carrier, q, s, ids):
        del carrier  # shape-only: DCE'd from the forward
        # dequantize to bf16, not s's f32: q*s carries <= 8 significant
        # bits, so bf16 loses nothing that the quantization did not
        # already drop — and an f32 output would double the [B, C, E]
        # activation AND backward-cotangent traffic.
        # Measured dead end, kept for the record (round 5): gathering
        # the scales as a flat 1-D [V] array instead of [V, 1] slices
        # is 6x faster in a MICRObenchmark (0.57 vs 3.7 ms — [*, 1]
        # f32 slices can't use wide DMA) but reproducibly ~3 ms SLOWER
        # inside the full jitted step (32.8 vs 29.7 ms fwd+bwd) — the
        # in-program fusion/layout differs from the standalone op, so
        # the 2-D form stays.
        rows = jnp.take(q, ids, axis=0).astype(jnp.float32)
        deq = rows * jnp.take(s, ids, axis=0)
        return deq.astype(jnp.bfloat16)

    def fwd(carrier, q, s, ids):
        return qtake(carrier, q, s, ids), ids

    def bwd(ids, g):
        # the dense cotangent the optimizer consumes — same scatter-add
        # the bf16 path's AD emits for its table gradient
        dc = jnp.zeros(shape, dtype).at[ids].add(g.astype(dtype))
        return (dc, None, None, None)

    qtake.defvjp(fwd, bwd)
    return qtake


def quantized_take(carrier: jax.Array, qt: QuantTable,
                   ids: jax.Array) -> jax.Array:
    """Gather + dequantize rows `ids` of a quantized table; gradients
    flow (dense, scatter-added) to `carrier` only."""
    f = _qtake_for(tuple(carrier.shape), str(carrier.dtype))
    return f(carrier, qt["q"], qt["s"], ids)


def opt_param_view(params):
    """The optimizer's view of a params pytree: each quantized table
    appears as one flat [V, E] bf16 stand-in matching the flat gradient
    the quantized train step feeds it (values are never read — shapes
    and dtypes only), everything else as-is. Shared by the model
    (jax_model) and bench so opt_state structure can never drift
    between them."""
    return {k: (jnp.zeros(v["q"].shape, jnp.bfloat16)
                if is_quantized(v) else v)
            for k, v in params.items()}


def dither_from_index(idx: jax.Array, salt: jax.Array) -> jax.Array:
    """Uniform(-0.5, 0.5) dither for uint32 element indices `idx` under
    a uint32 `salt` — THE counter-hash stream (salted xxhash-style
    finalizer; see _dither for why not threefry). Single source of
    truth shared by the dense reference (_dither), the fused requantize
    kernel (ops/pallas_requant.py) and the sparse live-row update
    (training/sparse_update.py + ops/pallas_sparse_update.py): all four
    must draw the SAME value for the same absolute [V, E] element index
    and salt, or fused-vs-reference q parity breaks."""
    h = (idx ^ salt) * jnp.uint32(2654435761)
    h = h ^ (h >> 16)
    h = h * jnp.uint32(2246822519)
    h = h ^ (h >> 13)
    # top 24 bits -> f32: exact in a 24-bit mantissa, so the result
    # stays in [-0.5, 0.5) — a full-32-bit convert would round values
    # near 2^32 up and emit dither of exactly +0.5
    # via int32: Mosaic has no uint32 -> f32 convert, and the 24-bit
    # value is the same number either way
    return ((h >> 8).astype(jnp.int32).astype(jnp.float32)
            * jnp.float32(1.0 / 16777216.0) - 0.5)


def _dither(rng: jax.Array, shape) -> jax.Array:
    """Uniform(-0.5, 0.5) dither from a fused counter hash, NOT
    jax.random.uniform: threefry bits for a [V, E] table are ~283M
    ALU-bound draws per step at java-large scale — measured to blow the
    entire int8 byte saving (step 43.3 ms vs bf16's 30.7; BASELINE.md
    round 5). Rounding dither needs uniformity, not cryptographic
    quality, so a salted xxhash-style finalizer over the element index
    (2 multiplies + 2 xor-shifts, fused into the requantize pass) is
    the right tool — measured: it returns the int8 step to its byte
    advantage (BASELINE.md round-5 int8 section carries both step
    times). The salt is ONE tiny threefry draw from the step's rng, so
    different steps see independent dither streams."""
    salt = jax.random.bits(rng, dtype=jnp.uint32)
    n = 1
    for d in shape:
        n *= d
    idx = jax.lax.iota(jnp.uint32, n).reshape(shape)
    return dither_from_index(idx, salt)


def requantize_reference(qt: QuantTable, update: jax.Array,
                         rng: jax.Array) -> QuantTable:
    """Apply a dense [V, E] additive update to a quantized table with
    stochastic rounding; per-row scales track the new absmax.

    This is the multi-pass XLA form (it materializes the dequantized
    f32 table and streams it several times — BASELINE.md round-5 pins
    +6.7 ms of the int8 step regression on exactly that); it stays as
    the parity oracle for the fused Pallas row-pass and as the CPU
    default, where XLA's fusion beats the interpreted kernel."""
    f = qt["q"].astype(jnp.float32) * qt["s"] + update.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(f), axis=1, keepdims=True)
    s_new = jnp.maximum(absmax, _SCALE_FLOOR) / 127.0
    x = f / s_new
    q_new = jnp.clip(jnp.round(x + _dither(rng, f.shape)),
                     -127, 127).astype(jnp.int8)
    return {"q": q_new, "s": s_new}


def requantize(qt: QuantTable, update: jax.Array, rng: jax.Array, *,
               fused: bool = None) -> QuantTable:
    """The table-update entry point the quantized train step calls.
    `fused=None` (the default) auto-selects the fused Pallas row-pass
    (ops/pallas_requant.py) on a TPU backend and the multi-pass XLA
    reference elsewhere; True forces the kernel (interpret mode
    off-TPU — how the CPU tier-1 tests drive it), False forces the
    reference. Config.REQUANT_PALLAS maps onto this via
    resolve_requant_mode."""
    if fused is None:
        fused = jax.default_backend() == "tpu"
    if fused:
        from code2vec_tpu.ops.pallas_requant import requantize_fused
        return requantize_fused(qt, update, rng)
    return requantize_reference(qt, update, rng)


def resolve_tristate_mode(mode: str, flag: str):
    """The shared auto|fused|reference -> None|True|False mapping for
    kernel-dispatch config flags ("auto" = backend auto-select).
    Config.verify() rejects anything else; this raises for programmatic
    users bypassing verify(). `flag` names the offender in the error."""
    try:
        return {"auto": None, "fused": True, "reference": False}[mode]
    except KeyError:
        raise ValueError(
            f"{flag} must be auto|fused|reference, got {mode!r}")


def resolve_requant_mode(mode: str):
    """Config.REQUANT_PALLAS -> the `fused` argument of requantize()."""
    return resolve_tristate_mode(mode, "REQUANT_PALLAS")
