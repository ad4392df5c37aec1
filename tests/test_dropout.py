"""Dropout's mask is drawn once a training step (`ops/dropout.py`):

1. `dropout` is today's `jnp.where(bernoulli(key, keep, x.shape), x /
   keep, 0)` bit for bit, and so is its gradient, for bf16 and f32, keep
   0.75 and 0.5, a last axis of 384 and one that is no multiple of 32;
2. with no key, or keep 1, nothing is drawn;
3. `encoder.embed_contexts` masks its contexts as it did, on the full
   path, the staircase path and over a mesh of two devices;
4. the bag train step, compiled for a v5e described to the compiler,
   holds one threefry over the mask's B x C x D elements, whose 4-bit
   result the apply and the backward read; no `[B, C, D]` boolean is
   written. Today's expression in its place draws the mask more than
   once there: the check can fail.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from code2vec_tpu.models import encoder
from code2vec_tpu.ops.dropout import dropout


def masked_division(x, key, keep):
    """The expression `dropout` replaces, as the encoder wrote it."""
    return jnp.where(jax.random.bernoulli(key, keep, x.shape), x / keep,
                     0.0)


def raw(a) -> np.ndarray:
    """An array's bits, so that equality is bit for bit (-0.0 too)."""
    a = np.asarray(a)
    return a.view({2: np.uint16, 4: np.uint32}[a.dtype.itemsize])


# ---- 1. the helper ---------------------------------------------------------

@pytest.mark.parametrize("dim", [384, 45])
@pytest.mark.parametrize("keep", [0.75, 0.5])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_dropout_is_the_masked_division_bit_for_bit(dtype, keep, dim):
    key = jax.random.PRNGKey(7)
    kx, kc = jax.random.split(jax.random.PRNGKey(1))
    x = jax.random.normal(kx, (3, 20, dim), dtype)
    ct = jax.random.normal(kc, x.shape, dtype)

    def value_and_cotangent(fn, x, ct):
        out, vjp = jax.vjp(lambda x: fn(x, key, keep), x)
        return out, vjp(ct)[0]

    run = jax.jit(value_and_cotangent, static_argnums=0)
    got, want = run(dropout, x, ct), run(masked_division, x, ct)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype == dtype
        np.testing.assert_array_equal(raw(g), raw(w))
    # the draw is a real one: about `keep` of the elements survive
    assert abs(float(jnp.mean(got[0] != 0)) - keep) < 0.05


# ---- 2. nothing drawn ------------------------------------------------------

@pytest.mark.parametrize("key, keep", [(None, 0.75),
                                       (jax.random.PRNGKey(0), 1.0)])
def test_no_key_or_keep_one_draws_nothing(key, keep):
    x = jnp.ones((2, 5, 6), jnp.bfloat16)
    assert dropout(x, key, keep) is x
    text = str(jax.make_jaxpr(lambda x: dropout(x, key, keep))(x))
    assert "random_bits" not in text and "threefry" not in text


# ---- 3. through the encoder ------------------------------------------------

V, E, C = 61, 8, 16
STAIRS = ((0, 24), (8, 16))


def ids_of(rows: int = 24):
    """(src, pth, dst) `[rows, C]`: every ninth bag longer than 8 slots,
    in a staircase's order (longest first), so `STAIRS` holds them."""
    rng = np.random.default_rng(3)
    lengths = np.sort(np.where(np.arange(rows) % 9 == 0, 16, 1 + np.arange(
        rows) % 8))[::-1]
    out = []
    for _ in range(3):
        ids = rng.integers(1, V, (rows, C)).astype(np.int32)
        ids[np.arange(C)[None, :] >= lengths[:, None]] = 0
        out.append(ids)
    return tuple(out)


def tables():
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    return {"token_emb": jax.random.normal(k1, (V, E), jnp.bfloat16),
            "path_emb": jax.random.normal(k2, (V, E), jnp.bfloat16)}


@pytest.mark.parametrize("path", ["full", "staircase", "mesh"])
def test_embed_contexts_masks_as_before(path):
    key, keep = jax.random.PRNGKey(5), 0.75
    params, ids = tables(), ids_of()
    stairs = None if path == "full" else STAIRS
    mesh = None
    if path == "mesh":
        from code2vec_tpu.parallel.mesh import make_mesh
        from code2vec_tpu.parallel.sharding import shard_batch, shard_params
        mesh = make_mesh(2, 1, 1, devices=jax.devices()[:2])
        params = shard_params(mesh, params)
        ids = shard_batch(mesh, tuple(np.tile(a, (2, 1)) for a in ids))

    def embed(p, ids, rng, rate):
        return encoder.embed_contexts(p, *ids, rng, rate, jnp.bfloat16,
                                      stairs, mesh)

    def now(p, ids):
        return embed(p, ids, key, keep)

    def before(p, ids):
        return masked_division(embed(p, ids, None, 1.0), key, keep)

    w = jax.random.normal(jax.random.PRNGKey(9),
                          jax.eval_shape(now, params, ids).shape)

    def loss(fn, p, ids):
        return jnp.sum(fn(p, ids).astype(jnp.float32) * w)

    forward = jax.jit(lambda fn, p, ids: fn(p, ids), static_argnums=0)
    grad = jax.jit(jax.grad(loss, argnums=1), static_argnums=0)
    np.testing.assert_array_equal(raw(forward(now, params, ids)),
                                  raw(forward(before, params, ids)))
    g_now, g_before = grad(now, params, ids), grad(before, params, ids)
    for k in params:
        np.testing.assert_array_equal(raw(g_now[k]), raw(g_before[k]))


# ---- 4. one draw in the compiled bag step ----------------------------------

B, C_STEP, E_STEP = 64, 32, 128
D_STEP = 3 * E_STEP


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a v5e described to the compiler (no chip is
    touched)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


def bag_programs(on) -> dict:
    """The bag step's two programs at B, C, D = 64, 32, 384, lowered and
    compiled for the chip `on` names: {name: (lowered, compiled)}."""
    import optax

    from code2vec_tpu.models.encoder import ModelDims, init_params
    from code2vec_tpu.training.steps import make_train_step

    dims = ModelDims(token_vocab_size=61, path_vocab_size=61,
                     target_vocab_size=13, embeddings_size=E_STEP,
                     max_contexts=C_STEP)

    def spec(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=on)

    opt = optax.sgd(0.1)
    params = jax.tree.map(spec, jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), dims)))
    state = jax.tree.map(spec, jax.eval_shape(opt.init, params))
    batch = tuple(spec(jax.ShapeDtypeStruct(shape, dtype)) for shape, dtype
                  in [((B,), jnp.int32), *[((B, C_STEP), jnp.int32)] * 3,
                      ((B, C_STEP), jnp.float32), ((B,), jnp.float32)])
    key = spec(jax.ShapeDtypeStruct((2,), jnp.uint32))
    step = make_train_step(dims, opt, use_sampled_softmax=True,
                           num_sampled=8, compute_dtype=jnp.bfloat16,
                           staircase=((0, B), (C_STEP // 2, B // 2)))
    out = {}
    for name in ("full_step", "staircase_step"):
        lowered = getattr(step, name).trace(params, state, batch, key).lower(
            lowering_platforms=("tpu",))
        out[name] = (lowered.as_text(), lowered.compile().as_text())
    return out


def with_todays_expression(x, key, keep):
    if key is None or keep >= 1.0:
        return x
    return masked_division(x, key, keep)


@pytest.fixture(scope="module")
def programs(one_chip):
    """The bag step with `dropout`, and with today's expression in its
    place (the control)."""
    out = {"helper": bag_programs(one_chip)}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(encoder, "dropout", with_todays_expression)
        out["control"] = bag_programs(one_chip)
    return out


def computations(hlo: str) -> dict:
    """name -> the lines of each computation of a compiled HLO text."""
    out, lines = {}, None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) .*\{$", line)
        if head and not line.startswith(" "):
            name = "ENTRY" if line.startswith("ENTRY") else head.group(1)
            lines = out[name] = []
        elif lines is not None:
            lines.append(line)
    return out


def draws(hlo: str, elements: int) -> list:
    """The entry computation's fusions that run a threefry rotation
    (a shift) over `elements` values, anywhere inside them."""
    comps = computations(hlo)
    shape = re.compile(r"= u32\[([\d,]+)\]\{[^}]*\} shift-(?:left|right)")

    def rotates(name, seen):
        if name in seen or name not in comps:
            return False
        seen.add(name)
        for line in comps[name]:
            m = shape.search(line)
            if m and np.prod([int(d) for d in m.group(1).split(",")]) \
                    == elements:
                return True
        return any(rotates(c, seen) for c in re.findall(
            r"calls=%([\w.\-]+)", "\n".join(comps[name])))

    found = []
    for line in comps["ENTRY"]:
        m = re.search(r"%([\w.\-]+) = (\S+) fusion\(.*calls=%([\w.\-]+)",
                      line)
        if m and rotates(m.group(3), set()):
            found.append((m.group(1), m.group(2)))
    return found


MASK = f"{B},{C_STEP},{D_STEP}"


@pytest.mark.parametrize("name", ["full_step", "staircase_step"])
def test_the_bag_step_draws_its_mask_once(programs, name):
    lowered, compiled = programs["helper"][name]
    found = draws(compiled, B * C_STEP * D_STEP)
    # one draw, whose result is the 4-bit mask and nothing else
    assert len(found) == 1 and found[0][1].startswith(f"u4[{MASK}]"), found
    # the apply and the backward read those bits: no `[B, C, D]`
    # boolean is written between fusions
    entry = "\n".join(computations(compiled)["ENTRY"])
    assert f"pred[{MASK}]" not in entry
    # the bits pass an optimization barrier before any use
    barrier = re.findall(r"stablehlo\.optimization_barrier[^\n]*",
                         lowered)
    mask = f"tensor<{B}x{C_STEP}x{D_STEP}xui4>"
    assert any(mask in line for line in barrier), barrier

    # the check can fail: today's expression draws the mask again for
    # the backward
    control = programs["control"][name][1]
    assert len(draws(control, B * C_STEP * D_STEP)) > 1
