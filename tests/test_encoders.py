"""Every encoder of models/registry.py is held to one seam (ISSUE 30):
the encode contract, its own parameter subtree and its sharding, the
manifest round trip, the evaluation / prediction / encode steps, and
the staircase step against the full one. The cases are the registry's
names, so the next encoder is held to them by being registered (and, if
it has sizes of its own, by its line in `SIZES`)."""

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from code2vec_tpu.config import Config
from code2vec_tpu.data import staircase as st
from code2vec_tpu.models import registry
from code2vec_tpu.models.encoder import (ModelDims, get_encode_fn,
                                         init_params)
from code2vec_tpu.models.joyai_flash_encoder import JoyaiDims
from code2vec_tpu.models.lfm2_moe_encoder import Lfm2Dims
from code2vec_tpu.models.qwen3_next_encoder import Qwen3NextDims
from code2vec_tpu.parallel.mesh import make_mesh
from code2vec_tpu.parallel.sharding import (param_pspecs, shard_opt_state,
                                            shard_params)
from code2vec_tpu.training import checkpoint as ckpt
from code2vec_tpu.training.steps import (TrainBatch, make_encode_step,
                                         make_eval_step, make_predict_step,
                                         make_train_step)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAMES = registry.names()
SHARED_LEAVES = {"token_emb", "path_emb", "target_emb", "transform",
                 "attention"}

V, E, C, B = 61, 16, 16, 24
BLOCK = dict(layer_types=["conv", "full_attention"], num_dense_layers=1,
             hidden_size=32, intermediate_size=48, moe_intermediate_size=24,
             num_attention_heads=4, num_key_value_heads=2, num_experts=2,
             num_routed_experts=4, first_expert=1, num_experts_per_tok=2,
             conv_L_cache=3, norm_eps=1e-5,
             rope_parameters={"rope_theta": 1e6})
QWEN_BLOCK = dict(num_hidden_layers=2, full_attention_interval=2,
                  hidden_size=32, num_attention_heads=4,
                  num_key_value_heads=2, head_dim=8,
                  partial_rotary_factor=0.5, rope_theta=1e7,
                  rms_norm_eps=1e-6, linear_conv_kernel_dim=4,
                  linear_key_head_dim=8, linear_value_head_dim=8,
                  linear_num_key_heads=1, linear_num_value_heads=2,
                  num_experts=2, num_routed_experts=4, first_expert=1,
                  num_experts_per_tok=2, moe_intermediate_size=24,
                  shared_expert_intermediate_size=16)
JOYAI_BLOCK = dict(num_hidden_layers=2, hidden_size=32,
                   num_attention_heads=4, q_lora_rank=12, kv_lora_rank=8,
                   qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
                   intermediate_size=48, moe_intermediate_size=24,
                   n_routed_experts=2, num_routed_experts=4, first_expert=1,
                   n_shared_experts=1, num_experts_per_tok=2,
                   first_k_dense_replace=1, routed_scaling_factor=2.5,
                   rope_theta=32e6, rms_norm_eps=1e-6)
# an encoder's own sizes, as ModelDims keywords (none: the defaults)
SIZES = {"transformer": dict(xf_layers=2, xf_heads=4, xf_remat=True),
         "lfm2_moe": dict(lfm=Lfm2Dims.from_config(BLOCK)),
         "qwen3_next": dict(qwen=Qwen3NextDims.from_config(QWEN_BLOCK)),
         "joyai_flash": dict(joyai=JoyaiDims.from_config(JOYAI_BLOCK))}


def dims_of(name: str, **kw) -> ModelDims:
    return ModelDims(token_vocab_size=V, path_vocab_size=V - 8,
                     target_vocab_size=29, embeddings_size=E,
                     max_contexts=C, encoder_type=name,
                     **{**SIZES.get(name, {}), **kw})


# a fifth of the bags longer than 8 slots: 24 rows keep 24 and 16
POPULATION = np.array([1, 2, 3, 4, 5, 6, 7, 8] * 4 + [9, 11, 13, 16] * 2)
STAIRS = st.from_lengths(POPULATION, B, C)
LENGTHS = np.array([16, 13, 12, 11, 10, 9, 9, 9, 8, 8, 7, 6, 5, 5, 4, 4,
                    3, 3, 2, 2, 1, 1, 1, 1])


def ordered_batch(seed: int = 11) -> tuple:
    """The step's six arrays for bags of `LENGTHS` contexts, longest
    first, each filled from slot 0: a batch that fits `STAIRS`."""
    rng = np.random.default_rng(seed)
    live = np.arange(C)[None, :] < LENGTHS[:, None]
    ids = [np.where(live, rng.integers(1, V - 8, (B, C)), 0).astype(np.int32)
           for _ in range(3)]
    assert st.fits(STAIRS, ids)
    return (rng.integers(0, 29, B).astype(np.int32), *ids,
            live.astype(np.float32), np.ones(B, np.float32))


# ---- the encode contract ----------------------------------------------------

@pytest.mark.parametrize("name", NAMES)
def test_encode_contract(name):
    dims = dims_of(name)
    params = init_params(jax.random.PRNGKey(0), dims)
    _labels, src, pth, dst, mask, _w = ordered_batch()
    encode = get_encode_fn(dims)
    for dtype in (jnp.float32, jnp.bfloat16):
        out = encode(params, src, pth, dst, jnp.asarray(mask),
                     dropout_rng=jax.random.PRNGKey(1),
                     dropout_keep_rate=0.75, compute_dtype=dtype,
                     use_pallas=False, staircase=None)
        assert len(out) == 3
        code, attention, aux = out
        assert code.shape == (B, 3 * E) and code.dtype == dtype
        assert attention.shape == (B, C)
        assert attention.dtype == jnp.float32
        np.testing.assert_allclose(np.sum(np.asarray(attention) * mask, -1),
                                   1.0, atol=1e-5)
        assert np.all(np.asarray(attention)[mask == 0] < 1e-6)
        leaves = jax.tree_util.tree_leaves(aux)
        assert all(leaf.dtype == jnp.int32 for leaf in leaves)
        # the spec names a recorder exactly where there is something
        # to record
        assert (registry.spec(name).recorder is not None) == bool(leaves)
    step = make_train_step(dims, optax.sgd(0.1))
    assert hasattr(step, "route_recorder") == bool(leaves)


# ---- its parameters ---------------------------------------------------------

@pytest.mark.parametrize("name", NAMES)
def test_params_subtree_and_sharding(name):
    dims = dims_of(name)
    key = registry.spec(name).params_key
    params = init_params(jax.random.PRNGKey(0), dims)
    assert set(params) == SHARED_LEAVES | ({key} if key else set())
    # the subtree is the spec's `init` of the model's key, so a model
    # built before the registry draws the same weights
    if key:
        want = registry.spec(name).init(jax.random.PRNGKey(0), dims)
        assert jax.tree_util.tree_all(jax.tree_util.tree_map(
            lambda a, b: bool(jnp.array_equal(a, b)), params[key], want))
    rules = param_pspecs()
    assert all(rules[k] is not None for k in params)
    mesh = make_mesh(0, 1, devices=jax.devices()[:2])
    placed = shard_params(mesh, params)
    optimizer = optax.adam(1e-3)
    state = shard_opt_state(mesh, optimizer.init(params), placed)
    for leaf in jax.tree_util.tree_leaves((placed, state)):
        assert set(leaf.sharding.device_set) == set(mesh.devices.flat)
    if key:
        for leaf in jax.tree_util.tree_leaves(placed[key]):
            assert leaf.sharding.is_fully_replicated


# ---- the manifest -----------------------------------------------------------

def _write_manifest(path, manifest) -> str:
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "manifest.json"), "w") as f:
        json.dump(manifest, f)
    return str(path)


@pytest.mark.parametrize("name", NAMES)
def test_manifest_round_trip(name, tmp_path):
    dims = dims_of(name, tables_dtype="bfloat16", vocab_pad_multiple=2)
    manifest = ckpt._build_manifest(7, dims, {"head": "code2vec"})
    # a stored format: these keys are what a checkpoint of any earlier
    # commit holds
    assert {"encoder_type", "xf_layers", "xf_heads", "xf_mlp_ratio",
            "xf_remat", "ring_attention", "lfm", "step"} <= set(manifest)
    assert manifest["encoder_type"] == name
    assert ckpt.load_dims(_write_manifest(tmp_path / "new", manifest)) == dims
    # a manifest from before the newer keys is the bag encoder's
    old = {k: manifest[k] for k in (
        "token_vocab_size", "path_vocab_size", "target_vocab_size",
        "embeddings_size", "max_contexts", "dropout_keep_rate")}
    loaded = ckpt.load_dims(_write_manifest(tmp_path / "old", old))
    assert (loaded.encoder_type, loaded.tables_dtype, loaded.xf_heads,
            loaded.lfm) == ("bag", "float32", 4, None)


# ---- the steps that do not train --------------------------------------------

@pytest.mark.parametrize("name", NAMES)
def test_eval_predict_and_encode_steps(name):
    dims = dims_of(name)
    params = init_params(jax.random.PRNGKey(0), dims)
    batch = tuple(jnp.asarray(a) for a in ordered_batch())
    k = 5
    loss_sum, ids, probs = make_eval_step(dims, top_k=k)(params, batch)
    assert loss_sum.shape == () and np.isfinite(float(loss_sum))
    assert ids.shape == probs.shape == (B, k)
    p_ids, p_probs, attention, code = make_predict_step(
        dims, top_k=k)(params, batch)
    np.testing.assert_array_equal(np.asarray(p_ids), np.asarray(ids))
    assert attention.shape == (B, C)
    assert code.shape == (B, 3 * E) and code.dtype == jnp.float32
    encoded = make_encode_step(dims)(params, batch)
    np.testing.assert_allclose(np.asarray(encoded), np.asarray(code),
                               atol=1e-6)
    for out in (probs, p_probs, attention, code, encoded):
        assert np.all(np.isfinite(np.asarray(out)))


# ---- the train step's two programs ------------------------------------------

@pytest.mark.parametrize("name", NAMES)
def test_staircase_step_equals_full_step(name):
    dims = dims_of(name)
    optimizer = optax.sgd(0.1)
    step = make_train_step(dims, optimizer, use_sampled_softmax=True,
                           num_sampled=8, staircase=STAIRS)
    arrays = ordered_batch()
    key = jax.random.PRNGKey(4)

    def run(fits: bool):
        params = init_params(jax.random.PRNGKey(0), dims)   # donated
        return step(params, optimizer.init(params),
                    TrainBatch(arrays, fits, 0), key)

    stairs_params, _, stairs_loss = run(True)
    full_params, _, full_loss = run(False)
    # an encoder whose softmax mixers score by query block over the
    # staircase sums a valid query's keys in another order (ISSUE 35):
    # its forward is the full step's to float32 rounding, the others'
    # bit for bit
    by_block = registry.spec(name).scores_by_staircase
    if by_block:
        assert float(stairs_loss) == pytest.approx(float(full_loss),
                                                   rel=1e-6)
    else:
        assert float(stairs_loss) == float(full_loss)
    tables = {"token_emb", "path_emb"}
    for k in full_params:
        got, want = stairs_params[k], full_params[k]
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            if by_block:
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-4, atol=1e-6)
            elif k in tables:
                # the gathers' scatters add the same updates in
                # another order
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-6, atol=1e-7)
            else:
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---- one place knows the names ----------------------------------------------

def test_cli_choices_are_the_registry():
    parser = Config.arguments_parser()
    action = next(a for a in parser._actions if a.dest == "encoder")
    assert tuple(action.choices) == NAMES
    for name in NAMES:
        assert parser.parse_args(["--encoder", name]).encoder == name
    with pytest.raises(SystemExit):
        parser.parse_args(["--encoder", "no_such_encoder"])


def test_unknown_encoder_names_the_known_ones():
    for ask in (lambda: registry.spec("no_such_encoder"),
                lambda: Config(ENCODER_TYPE="no_such_encoder",
                               load_path="x").verify(),
                lambda: init_params(jax.random.PRNGKey(0),
                                    dims_of("no_such_encoder"))):
        with pytest.raises(ValueError) as err:
            ask()
        assert all(name in str(err.value) for name in NAMES)


def test_no_encoder_name_compared_outside_the_registry():
    """A test of structure (tests/test_obs_guard.py is the precedent):
    the program asks the registry for what an encoder needs, never
    which encoder it has."""
    quoted = "[\"'](?:" + "|".join(NAMES) + ")[\"']"
    compared = re.compile(
        r"(?:encoder_type|ENCODER_TYPE)\s*(?:[!=]=|\bnot\s+in\b|\bin\b)"
        rf"|(?:[!=]=|\bin)\s*[\[(]?\s*{quoted}|{quoted}\s*[!=]=")
    files = [os.path.join(REPO, "code2vec.py")]
    for root, _dirs, names in os.walk(os.path.join(REPO, "code2vec_tpu")):
        files += [os.path.join(root, n) for n in names if n.endswith(".py")]
    assert len(files) > 50
    found = []
    for path in files:
        if path.endswith(os.path.join("models", "registry.py")):
            continue
        with open(path) as f:
            found += [f"{os.path.relpath(path, REPO)}:{i}: {line.strip()}"
                      for i, line in enumerate(f, 1)
                      if compared.search(line)]
    assert not found, "\n".join(found)
