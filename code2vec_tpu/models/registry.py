"""The encoders the program can run, by name.

    models/<encoder>.py  --SPEC-->  models/registry.py  --spec(name)-->
        training/steps.py, config.py, models/jax_model.py,
        training/checkpoint.py

This is the only module that compares an encoder's name. Every other
module asks `spec(dims.encoder_type)` (or `spec(cfg.ENCODER_TYPE)`) for
what it needs and never for which encoder it has. An encoder's module
is imported when its spec is first asked for, so a run pays for the
encoder it runs and no other.

Adding an encoder: its module with a `SPEC`, and its line in `_MODULES`
(README, "Adding an encoder").
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Any, Callable, Dict, Optional, Tuple


def _nothing(_source) -> Dict[str, Any]:
    return {}


@dataclasses.dataclass(frozen=True)
class EncoderSpec:
    """What the rest of the program needs of one encoder.

    `encode(params, source_ids, path_ids, target_ids, mask, *, dims,
    mesh, dropout_rng, dropout_keep_rate, compute_dtype, use_pallas,
    staircase) -> (code [B, 3E] in the compute dtype, attention [B, C]
    float32, aux)`: `aux` is None, or a pytree of arrays the train step
    hands to `recorder()`'s `push` beside the loss."""
    encode: Callable
    # the encoder's own parameters: `params[params_key] = init(rng,
    # dims)`, rng being the model's init key (None: the five shared
    # leaves are all it has). parallel/sharding.py replicates a subtree.
    params_key: Optional[str] = None
    init: Optional[Callable] = None
    # its own sizes as ModelDims keywords, read from a Config (training
    # from scratch) and from a checkpoint's manifest (a stored format:
    # keys and defaults stay as they were written)
    sizes_from_config: Callable = _nothing
    sizes_from_manifest: Callable = _nothing
    # raises ValueError for a Config this encoder cannot run under
    check_config: Callable = _nothing
    # an evaluation batch is held to the training batch's rows (a block
    # whose activations, not its tables, are the memory)
    eval_batch_at_most_train: bool = False
    # the int8, sparse-update and VarMisuse steps are written for it
    # (they call the bag's `encode` and train no subtree)
    table_step_variants: bool = False
    # it is a block of `models/seq_block.py`: its softmax mixers' core
    # runs by query block over a training batch's staircase and its
    # layers' feed-forward half over the staircase's positions, so the
    # producer counts the pairs the chosen step scores and the
    # positions it feeds forward (`attn_pairs`, `ff_slots` on
    # `infeed/transfer`, data/prefetch.py)
    scores_by_staircase: bool = False
    # makes the recorder of `aux` (obs/route.RouteRecorder's contract:
    # `push(aux)`, `flush()`, a `tracer` attribute); None exactly where
    # `aux` is None
    recorder: Optional[Callable] = None


# name (--encoder, ModelDims.encoder_type, the manifest's
# `encoder_type`) -> the module that holds its SPEC
_MODULES = {
    "bag": "code2vec_tpu.models.encoder",
    "transformer": "code2vec_tpu.models.transformer_encoder",
    "lfm2_moe": "code2vec_tpu.models.lfm2_moe_encoder",
    "qwen3_next": "code2vec_tpu.models.qwen3_next_encoder",
    "joyai_flash": "code2vec_tpu.models.joyai_flash_encoder",
}


def names() -> Tuple[str, ...]:
    return tuple(_MODULES)


def spec(name: str) -> EncoderSpec:
    try:
        module = _MODULES[name]
    except KeyError:
        raise ValueError(
            f"unknown encoder {name!r}: models/registry.py knows "
            f"{', '.join(_MODULES)}") from None
    return importlib.import_module(module).SPEC
