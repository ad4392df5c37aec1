"""Sharding rules: how the code2vec pytree and batches lay out on a mesh.

SURVEY.md §3.3 (TPU-native equivalents table):
- DP: batch dim sharded over 'data'; XLA inserts the gradient psum over
  ICI automatically during SPMD partitioning of the jitted step.
- TP (embedding sharding): the token table (~1.3M x 128) and target table
  (~261K x 384) shard their VOCAB dim over 'model' so dense embedding
  gradients scale (SURVEY.md §8.4 item 2). XLA turns `jnp.take` on a
  row-sharded table into a dynamic-slice + partial gather + psum, and the
  [B, D] @ [D, V] logits matmul into a reduce-scatter-friendly form.
- TRANSFORM / ATTENTION are tiny: replicated.

Vocab row counts must divide the model axis — ModelDims.vocab_pad_multiple
handles the padding at init time.
"""

from __future__ import annotations

from typing import Dict

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from code2vec_tpu.parallel.mesh import (CONTEXT_AXIS, DATA_AXIS, DCN_AXIS,
                                        MODEL_AXIS)


class _ParamRules(dict):
    """The rules by the parameter tree's keys. A key they do not name
    is an encoder's own subtree (models/registry.EncoderSpec
    .params_key: "xf", "lfm"): one sharding for every leaf of it,
    replicated (the transformer's ~L*12*D^2 floats are tiny next to
    the vocab tables; of lfm2_moe every device holds the same experts
    and routes its own rows, the expert exchange over a mesh axis is
    not built)."""

    def __missing__(self, key: str) -> P:
        return P()


def param_pspecs() -> Dict[str, P]:
    return _ParamRules({
        "token_emb": P(MODEL_AXIS, None),
        "path_emb": P(MODEL_AXIS, None),
        "target_emb": P(MODEL_AXIS, None),
        "transform": P(None, None),
        "attention": P(None),
        "vm_pointer": P(None, None),   # VarMisuse head (tiny: replicated)
    })


def batch_pspec() -> P:
    """Leading (batch) dim over ('dcn', 'data') jointly — within a
    slice the gradient reduction rides ICI, only the final cross-slice
    psum crosses DCN (a no-op composite at dcn=1); everything else
    replicated."""
    return P((DCN_AXIS, DATA_AXIS))


def shard_map_over_batch(fn, mesh: Mesh, batched):
    """`fn` run once per device on that device's rows: arguments whose
    `batched` flag is set (and every output) split their leading dim
    over ('dcn', 'data'), the rest arrive whole. This is how a Pallas
    kernel sits inside a partitioned step — GSPMD will not split one
    ("Mosaic kernels cannot be automatically partitioned. Please wrap
    the call in a shard_map")."""
    from code2vec_tpu.parallel.compat import shard_map
    rows = batch_pspec()
    return shard_map(fn, mesh=mesh,
                     in_specs=tuple(rows if b else P() for b in batched),
                     out_specs=rows)


def context_batch_pspec() -> P:
    """[B, C] tensors with the context dim sharded over 'ctx' — the
    sequence/context-parallel layout for the transformer encoder."""
    return P((DCN_AXIS, DATA_AXIS), CONTEXT_AXIS)


def shard_params(mesh: Mesh, params) -> Dict[str, jax.Array]:
    specs = param_pspecs()

    def put(k, v):
        if isinstance(v, dict) and "q" in v:
            # int8 quantized table (ops/quant.py): rows shard like the
            # flat table would — q [V, E] and s [V, 1] both lead with
            # the vocab dim (data-parallel meshes replicate both)
            spec = specs[k]
            return {"q": jax.device_put(v["q"], NamedSharding(mesh, spec)),
                    "s": jax.device_put(v["s"], NamedSharding(mesh, spec))}
        return jax.device_put(v, NamedSharding(mesh, specs[k]))

    return {k: put(k, v) for k, v in params.items()}


def shard_opt_state(mesh: Mesh, opt_state, params):
    """Optimizer slots mirror their parameter's sharding; scalars/steps
    replicate."""
    specs = param_pspecs()
    # optax states are pytrees whose array leaves either match a param
    # shape (moments) or are scalars (counts). Map by shape. Subtree
    # params (e.g. "xf") contribute every leaf under their one spec.
    shapes_to_spec = {}
    for k, v in params.items():
        for leaf in jax.tree_util.tree_leaves(v):
            shapes_to_spec.setdefault(leaf.shape, specs[k])

    def put(leaf):
        if hasattr(leaf, "shape") and leaf.shape in shapes_to_spec:
            return jax.device_put(
                leaf, NamedSharding(mesh, shapes_to_spec[leaf.shape]))
        if hasattr(leaf, "shape"):
            return jax.device_put(leaf, NamedSharding(mesh, P()))
        return leaf

    return jax.tree_util.tree_map(put, opt_state)


def shard_batch(mesh: Mesh, arrays, *, process_local: bool = True,
                shard_contexts: bool = False):
    """Put a tuple of [B, ...] host arrays onto the mesh with the batch
    dim over 'data'. With shard_contexts=True, [B, C] arrays
    additionally shard their context dim over 'ctx' (context
    parallelism for the transformer encoder).

    Multi-process semantics depend on what the caller's B means:

    - process_local=True (training): every process passes its OWN disjoint
      local batch of size B; the global array has batch B * process_count.
      Built with `jax.make_array_from_process_local_data`, so no process
      needs the others' data — this is what makes the effective global
      batch actually scale with host count.
    - process_local=False (eval/predict): every process passes the SAME
      value; the global batch stays B, sliced across all devices. Built
      with `jax.make_array_from_callback`, which only reads the slices
      owned by this process's devices.
    """
    import numpy as np

    def sharding_for(a):
        if shard_contexts and getattr(a, "ndim", 1) == 2:
            return NamedSharding(mesh, context_batch_pspec())
        return NamedSharding(mesh, batch_pspec())

    if jax.process_count() == 1:
        return tuple(jax.device_put(a, sharding_for(a)) for a in arrays)
    # the np.asarray calls below normalize HOST batches before device
    # placement (the arrays are never on-device yet) — not the
    # device->host fetch graftlint's host-sync rule is hunting
    if process_local:
        return tuple(
            jax.make_array_from_process_local_data(
                sharding_for(a),
                np.asarray(a))  # graftlint: disable=host-sync-in-hot-path
            for a in arrays)
    return tuple(
        jax.make_array_from_callback(
            np.asarray(a).shape,  # graftlint: disable=host-sync-in-hot-path
            sharding_for(a),
            lambda idx, _a=np.asarray(a):  # graftlint: disable=host-sync-in-hot-path
            _a[idx])
        for a in arrays)
