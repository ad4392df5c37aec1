"""Optimizer construction.

The reference trains everything with Adam (SURVEY.md §3,
`tensorflow_model.py` training graph). On TPU the dominant step cost at
java-large scale is the optimizer's full-table HBM traffic (measured
15.6 ms of a 40 ms step for f32 Adam on v5e-lite; BASELINE.md), so the
framework also offers a factored second-moment optimizer for the three
vocab tables:

- "adafactor" (DEFAULT since round 3): Adafactor (factored v, no
  momentum) on the vocab tables, Adam on TRANSFORM/ATTENTION. Cuts
  optimizer state for a [V, E] table from 2*V*E to ~V+E and the update
  traffic accordingly — the standard large-embedding practice. Measured
  both fastest (26.0 vs 33-35 ms/step, java-large B=1024) and
  highest-F1 sampled variant (BASELINE.md round-3 quality table).
- "adam": reference parity — Adam on every param, with mu/nu kept f32
  even for bf16 tables (scale_by_adam_f32_moments below).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax

TABLE_PARAMS = ("token_emb", "path_emb", "target_emb")


def scale_by_adam_f32_moments(b1: float = 0.9, b2: float = 0.999,
                              eps: float = 1e-8
                              ) -> optax.GradientTransformation:
    """scale_by_adam that keeps mu AND nu in float32 regardless of the
    parameter dtype.

    With bf16 vocab tables, stock optax.adam inherits bf16 for both
    moments (mu/nu = zeros_like(param)); the second-moment increment
    (1-b2)*g^2 = 1e-3*g^2 underflows bf16's 8-bit mantissa once it drops
    below ~1/256 of the running value, risking a quiet late-training
    stall at java-large scale (round-2 advisor finding). f32 moments are
    measured perf-neutral on v5e-lite (BASELINE.md phase isolation:
    15.6 ms f32 vs 15.9 ms bf16 moment traffic — the update kernel is
    not moment-traffic-bound), so this is the default for "adam".
    Residual caveat: the *applied update* still rounds to the bf16
    table, which the 50K-corpus quality study validates (BASELINE.md).
    """

    def init_fn(params):
        f32_zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
        return optax.ScaleByAdamState(
            count=jnp.zeros([], jnp.int32),
            mu=jax.tree_util.tree_map(f32_zeros, params),
            nu=jax.tree_util.tree_map(f32_zeros, params))

    def update_fn(updates, state, params=None):
        del params
        g32 = jax.tree_util.tree_map(
            lambda g: g.astype(jnp.float32), updates)
        mu = jax.tree_util.tree_map(
            lambda m, g: b1 * m + (1.0 - b1) * g, state.mu, g32)
        nu = jax.tree_util.tree_map(
            lambda v, g: b2 * v + (1.0 - b2) * (g * g), state.nu, g32)
        count = optax.safe_int32_increment(state.count)
        bc1 = 1.0 - b1 ** count.astype(jnp.float32)
        bc2 = 1.0 - b2 ** count.astype(jnp.float32)
        new_updates = jax.tree_util.tree_map(
            lambda m, v, u: ((m / bc1) / (jnp.sqrt(v / bc2) + eps)
                             ).astype(u.dtype),
            mu, nu, updates)
        return new_updates, optax.ScaleByAdamState(count=count, mu=mu,
                                                   nu=nu)

    return optax.GradientTransformation(init_fn, update_fn)


def make_lr(learning_rate: float, schedule: str = "constant",
            total_steps: int = 0, warmup_steps: int = 0):
    """Returns a float or an optax schedule.

    The reference trains at constant LR (TF AdamOptimizer default —
    parity). "cosine" decays to 10% of peak over total_steps: the decay
    study (tools/sampled_decay_study.py, BASELINE.md round 3) shows the
    sampled-softmax head-class top1 decay is full-LR negative-pressure
    overshoot — head rows keep receiving ~every-step negative updates
    after converging, and at lr=1e-3 they drift off their optimum late
    in training (at lr=5e-4 the decay vanishes, Adam nu stays flat so
    it is not an effective-LR spike). A decaying schedule removes the
    pathology without relying on bf16 rounding noise.

    "warmup_cosine" (round 4, the large-global-batch recipe): linear
    0→peak over `warmup_steps` (default 5% of total_steps), then cosine
    to 10% of peak. At B≥8192 the first steps take scaled-LR updates on
    cold Adam/Adafactor second moments — warmup is the standard cure
    (Goyal et al. 2017), and the large-batch study (BASELINE.md round 4)
    measures what it buys here.
    """
    if schedule == "constant":
        return learning_rate
    assert total_steps > 0, f"--lr_schedule {schedule} needs total_steps"
    if schedule == "cosine":
        return optax.cosine_decay_schedule(learning_rate, total_steps,
                                           alpha=0.1)
    if schedule == "linear":
        return optax.linear_schedule(learning_rate, learning_rate * 0.1,
                                     total_steps)
    if schedule == "warmup_cosine":
        w = warmup_length(total_steps, warmup_steps)
        # optax cosine-decays over (decay_steps - warmup_steps), which
        # must stay positive — eval/predict-only loads build the
        # schedule with horizon 1 just for opt_state STRUCTURE
        # (models/setup.build_optimizer), so clamp rather than assert
        return optax.warmup_cosine_decay_schedule(
            init_value=0.0, peak_value=learning_rate, warmup_steps=w,
            decay_steps=max(total_steps, w + 1),
            end_value=0.1 * learning_rate)
    raise ValueError(f"unknown lr schedule {schedule!r}")


def warmup_length(total_steps: int, warmup_steps: int) -> int:
    """The EFFECTIVE warmup length make_lr uses: explicit if given,
    else 5% of the horizon, clamped inside it. Exposed so
    build_optimizer can resolve auto-warmup to a concrete number at
    first training — the checkpoint manifest must record the effective
    value, or a resume would re-derive a different auto length from
    its extended horizon and follow a different LR trajectory."""
    w = warmup_steps if warmup_steps > 0 else max(1, total_steps // 20)
    return min(w, max(1, total_steps - 1))


def schedule_total_steps(num_examples: int, batch_size: int, epochs: int,
                         num_hosts: int = 1,
                         restored_step: int = 0) -> int:
    """Decay horizon for make_lr: steps this run will take (matching the
    reader's per-host ceil-div batch count) plus the restored optimizer
    step for resumes — the restored count leaf already sits at the
    checkpoint's step, so without the extension a resumed run would
    clamp to the schedule floor immediately."""
    per_host = -(-num_examples // num_hosts)
    return -(-per_host // batch_size) * epochs + restored_step


def resolve_checkpoint_schedule(requested: str, manifest: dict,
                                log) -> str:
    """The LR-schedule a loaded model must use: the checkpoint's (the
    opt_state structure is fixed at first training). Warns when a CLI
    request conflicts instead of silently dropping it."""
    ckpt_schedule = manifest.get("lr_schedule", "constant")
    if requested != ckpt_schedule:
        log(f"--lr_schedule {requested!r} ignored: using the "
            f"checkpoint's {ckpt_schedule!r} (the optimizer state "
            "structure is fixed at first training)")
    return ckpt_schedule


def resolve_checkpoint_warmup(schedule: str, requested: int,
                              manifest: dict, log) -> int:
    """Companion to resolve_checkpoint_schedule, with the same logging
    contract: the checkpoint's EFFECTIVE warmup length wins (the LR
    trajectory is fixed at first training), a conflicting CLI
    --warmup_steps is logged rather than silently dropped, and a
    warmup aimed at a non-warmup schedule is logged+zeroed (the
    combination Config.verify rejects on the fresh-training path)."""
    if schedule != "warmup_cosine":
        if requested > 0:
            log(f"--warmup_steps {requested} ignored: the checkpoint's "
                f"schedule is {schedule!r} (no warmup phase)")
        return 0
    ckpt_warmup = int(manifest.get("lr_warmup_steps", 0))
    if ckpt_warmup > 0 and requested > 0 and requested != ckpt_warmup:
        log(f"--warmup_steps {requested} ignored: using the "
            f"checkpoint's effective warmup {ckpt_warmup} (the LR "
            "trajectory is fixed at first training)")
    return ckpt_warmup if ckpt_warmup > 0 else requested


def _scoped(tx: optax.GradientTransformation, scope: str
            ) -> optax.GradientTransformation:
    """`tx` with its update traced under `jax.named_scope(scope)`, so
    a profile names the optimizer's walk over its leaves as a phase of
    the step (`c2v/table_apply`, `c2v/dense_apply`; `apply_updates`
    puts the parameter add under the same names). Metadata only: the
    state's structure and the numbers are `tx`'s."""
    tx = optax.with_extra_args_support(tx)

    def update_fn(updates, state, params=None, **extra_args):
        with jax.named_scope(scope):
            return tx.update(updates, state, params, **extra_args)

    return optax.GradientTransformationExtraArgs(tx.init, update_fn)


def apply_updates(params, updates, skip=()):
    """`optax.apply_updates` key by key, each under its phase's scope:
    `c2v/table_apply` for the vocab tables, `c2v/dense_apply` for the
    rest, in every train step (bag, int8, sparse, varmisuse). Keys in
    `skip` are left out."""
    out = {}
    for k in params:
        if k in skip:
            continue
        scope = "c2v/table_apply" if k in TABLE_PARAMS \
            else "c2v/dense_apply"
        with jax.named_scope(scope):
            out[k] = optax.apply_updates(params[k], updates[k])
    return out


def make_optimizer(learning_rate,
                   embedding_optimizer: str = "adafactor",
                   trust_ratio: bool = False,
                   trust_ratio_scope: str = "all"
                   ) -> optax.GradientTransformation:
    """`learning_rate` is a float or an optax schedule (see make_lr).

    `trust_ratio=True` (round 4, the large-global-batch recipe) inserts
    a LAMB-style per-array trust-ratio rescale (You et al. 2020:
    update *= ||param|| / ||update||, guarded to 1 when either norm is
    0) between the preconditioner and the LR scaling. Per-array
    granularity means each vocab TABLE is one trust group — the same
    granularity LAMB uses per layer. Changes the opt_state STRUCTURE,
    so it is recorded in the checkpoint manifest like
    embedding_optimizer.

    `trust_ratio_scope` (round 5, VERDICT r4 item 8): "all" applies
    the rescale on every branch — measured HARMFUL on this model
    family (BASELINE.md round 4: the rms-clipped update is rescaled by
    the small norm of fresh embedding tables; effective LR collapses,
    F1 0.11). "dense" is the standard LAMB practice for
    embedding-dominated models: trust-scale only the dense params
    (TRANSFORM/ATTENTION/extra heads), plain adafactor on the tables.
    Requires the adafactor branch (the tables need their own
    transform for the scope split to exist).
    """
    assert trust_ratio_scope in ("all", "dense"), trust_ratio_scope
    if embedding_optimizer == "adam":
        if trust_ratio and trust_ratio_scope != "all":
            raise ValueError(
                "--trust_ratio_scope dense requires the adafactor "
                "embedding optimizer (adam runs one transform over "
                "all params, so there is no table/dense split).")
        # one transform over every leaf, tables and dense alike, so
        # the walk has a name of its own: neither phase's
        trust = (optax.scale_by_trust_ratio(),) if trust_ratio else ()
        return _scoped(optax.chain(
            scale_by_adam_f32_moments(), *trust,
            optax.scale_by_learning_rate(learning_rate)),
            "c2v/apply")
    if embedding_optimizer == "adafactor":
        # label by key so extra head params (e.g. vm_pointer) route to
        # adam automatically
        def labels(params):
            return {k: ("table" if k in TABLE_PARAMS else "small")
                    for k in params}

        if not trust_ratio:
            table_tx = optax.adafactor(
                learning_rate, multiply_by_parameter_scale=False,
                momentum=None)
            small_tx = optax.adam(learning_rate)
        elif trust_ratio_scope == "dense":
            # tables keep the plain (measured-best) adafactor path;
            # only the dense params get the LAMB rescale
            table_tx = optax.adafactor(
                learning_rate, multiply_by_parameter_scale=False,
                momentum=None)
            small_tx = optax.chain(
                optax.scale_by_adam(),
                optax.scale_by_trust_ratio(),
                optax.scale_by_learning_rate(learning_rate))
        else:
            # optax.adafactor(lr, multiply_by_parameter_scale=False,
            # momentum=None) == factored_rms + block-rms clip + lr;
            # rebuilt here explicitly so the trust ratio lands between
            # the clip and the LR (after the LR it would cancel the
            # schedule — ||update|| already contains lr).
            table_tx = optax.chain(
                optax.scale_by_factored_rms(),
                optax.clip_by_block_rms(1.0),
                optax.scale_by_trust_ratio(),
                optax.scale_by_learning_rate(learning_rate))
            small_tx = optax.chain(
                optax.scale_by_adam(),
                optax.scale_by_trust_ratio(),
                optax.scale_by_learning_rate(learning_rate))
        return optax.multi_transform(
            {"table": _scoped(table_tx, "c2v/table_apply"),
             "small": _scoped(small_tx, "c2v/dense_apply")}, labels)
    raise ValueError(
        f"unknown embedding_optimizer {embedding_optimizer!r} "
        "(expected 'adam' or 'adafactor')")
