"""Sampled softmax over a large target vocabulary.

SURVEY.md §3.3 / §8.4: the java-large config (261K method-name targets)
requires a TPU-friendly sampled softmax matching
`tf.nn.sampled_softmax_loss` semantics — a log-uniform (Zipfian) candidate
sampler and the log-expected-count bias correction — or subtoken-F1 will
not match the reference.

Semantics implemented (matching TF's defaults):
- candidates ~ log-uniform over [0, V): P(k) = log((k+2)/(k+1)) / log(V+1),
  so frequency-sorted vocabularies (ours are: Vocab.create_from_freq_dict
  sorts by descending count) get Zipf-like negatives;
- candidates are UNIQUE (TF's unique=True): drawn via the Gumbel-top-k
  trick — perturb per-class log-probabilities with Gumbel noise and take
  the top S, which is distributionally exact sampling without
  replacement. With replacement the head class (p~0.056 for java-large)
  would appear ~S*p~230 times and the unique-sampler bias correction
  would overweight it by orders of magnitude;
- one shared candidate set per step (TF shares candidates across the batch);
- bias correction subtracts log(expected_count) from each candidate's and
  the true class's logits. TF computes -expm1(num_tries * log1p(-p)) with
  the sampler's actual with-replacement draw count; we use the
  deterministic equivalent: solve sum_k(-expm1(T*log1p(-p_k))) = S for the
  effective draw count T once on the host (static per (V, S)) and use
  inclusion = -expm1(T*log1p(-p)). Verified within ~2% of the empirical
  Gumbel-top-k inclusion frequencies (tests/test_ops.py);
- accidental hits (a sampled negative equal to the true label) are masked
  to -inf, as with TF's `remove_accidental_hits=True`.

All shapes are static (S = num_sampled) so the step jits once. The gather
of S + B rows from the [V, D] target table is the whole point: the dense
[B, V] logits matmul (the full-softmax path) is replaced by [B, D] @ [D, S].
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _log_uniform_log_probs(vocab_size: int) -> jax.Array:
    """Static per-class log-pmf of the log-uniform distribution; XLA
    constant-folds this inside a jitted step."""
    k = jnp.arange(vocab_size, dtype=jnp.float32)
    return jnp.log(jnp.log1p(1.0 / (k + 1.0)) /
                   jnp.log(float(vocab_size + 1)))


def log_uniform_sample(rng: jax.Array, num_sampled: int,
                       vocab_size: int) -> jax.Array:
    """Draw `num_sampled` UNIQUE class ids from the log-uniform
    distribution over [0, vocab_size) via Gumbel-top-k (exact sampling
    without replacement, matching TF's unique=True candidate sampler)."""
    if num_sampled >= vocab_size:
        return jnp.arange(vocab_size, dtype=jnp.int32)
    gumbel = jax.random.gumbel(rng, (vocab_size,), dtype=jnp.float32)
    scores = _log_uniform_log_probs(vocab_size) + gumbel
    _, ids = jax.lax.top_k(scores, num_sampled)
    return ids.astype(jnp.int32)


@functools.lru_cache(maxsize=None)
def _effective_num_tries(num_sampled: int, vocab_size: int) -> float:
    """Deterministic stand-in for TF's stochastic num_tries: the T such
    that the expected number of distinct classes in T with-replacement
    log-uniform draws equals num_sampled. Newton's method on the host;
    cached per static (S, V)."""
    k = np.arange(vocab_size, dtype=np.float64)
    log1m_p = np.log1p(-(np.log1p(1.0 / (k + 1.0)) /
                         np.log(float(vocab_size + 1))))
    T = float(num_sampled)
    for _ in range(100):
        f = np.sum(-np.expm1(T * log1m_p)) - num_sampled
        df = np.sum(-log1m_p * np.exp(T * log1m_p))
        step = f / df
        T -= step
        if abs(step) < 1e-9:
            break
    return T


def _log_expected_count(ids: jax.Array, num_sampled: int,
                        vocab_size: int) -> jax.Array:
    k = ids.astype(jnp.float32)
    p = jnp.log1p(1.0 / (k + 1.0)) / jnp.log(float(vocab_size + 1))
    if num_sampled >= vocab_size:
        # exhaustive candidate set: every class appears exactly once
        return jnp.zeros_like(p)
    T = _effective_num_tries(num_sampled, vocab_size)
    return jnp.log(-jnp.expm1(T * jnp.log1p(-p)))


def sampled_softmax_from_gathered(
        code_vectors: jax.Array, true_w: jax.Array, samp_w: jax.Array,
        true_corr: jax.Array, samp_corr: jax.Array,
        accidental: jax.Array,
        example_weights: jax.Array | None = None) -> jax.Array:
    """The shared logit/correction/accidental-hit core, taking
    PRE-GATHERED target rows — called both by sampled_softmax_loss and by
    the sparse-embedding train step (which differentiates w.r.t. the
    gathered rows themselves).

    Args: code [B, D]; true_w [B, D]; samp_w [S, D]; log-expected-count
    corrections true_corr [B] / samp_corr [S]; accidental [B, S] mask of
    sampled==label collisions; optional [B] example weights.
    Returns the scalar mean loss.
    """
    dtype = code_vectors.dtype
    true_logits = jnp.sum(code_vectors * true_w.astype(dtype),
                          axis=-1).astype(jnp.float32) - true_corr
    sampled_logits = (code_vectors @ samp_w.astype(dtype).T).astype(
        jnp.float32) - samp_corr[None, :]
    sampled_logits = jnp.where(accidental, -1e9, sampled_logits)
    logits = jnp.concatenate([true_logits[:, None], sampled_logits],
                             axis=1)
    per_example = -jax.nn.log_softmax(logits, axis=-1)[:, 0]
    if example_weights is not None:
        denom = jnp.maximum(jnp.sum(example_weights), 1.0)
        return jnp.sum(per_example * example_weights) / denom
    return jnp.mean(per_example)


def sampled_softmax_loss(
        target_table: jax.Array, code_vectors: jax.Array,
        labels: jax.Array, rng: jax.Array, num_sampled: int,
        example_weights: jax.Array | None = None,
        vocab_size: int | None = None,
) -> Tuple[jax.Array, jax.Array]:
    """Args:
      target_table:  [V_padded, D] target-name embedding table (the softmax
                     weights; reference TARGET_WORDS_VOCAB). May carry dead
                     padding rows for mesh divisibility.
      code_vectors:  [B, D].
      labels:        [B] int32 true class ids.
      rng:           PRNG key for candidate sampling.
      num_sampled:   S, static.
      example_weights: optional [B] 0/1 weights (padded final batch).
      vocab_size:    TRUE vocab size V <= V_padded; candidates are drawn
                     from [0, V) so padding rows are never sampled.

    Returns (mean_loss, sampled_ids).
    """
    if vocab_size is None:
        vocab_size = target_table.shape[0]
    # S > V degenerates to the exhaustive candidate set (full softmax)
    num_sampled = min(num_sampled, vocab_size)
    with jax.named_scope("c2v/loss"):  # the step's loss phase
        sampled = log_uniform_sample(rng, num_sampled, vocab_size)  # [S]
        loss = sampled_softmax_from_gathered(
            code_vectors,
            true_w=target_table[labels],
            samp_w=target_table[sampled],
            true_corr=_log_expected_count(labels, num_sampled,
                                          vocab_size),
            samp_corr=_log_expected_count(sampled, num_sampled,
                                          vocab_size),
            accidental=sampled[None, :] == labels[:, None],
            example_weights=example_weights)
    return loss, sampled
