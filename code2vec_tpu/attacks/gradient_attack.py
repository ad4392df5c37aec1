"""Gradient-guided discrete adversarial attacks: variable renaming.

Reference parity target: the `noamyft/code2vec` fork delta (SURVEY.md §0
item 2). The fork's owner co-authored "Adversarial Examples for Models of
Code" (Yefet, Alon & Yahav, 2020), whose artifact attacks code2vec by
**renaming one variable** so the model predicts an attacker-chosen method
name (targeted) or any wrong name (untargeted), and by **inserting dead
code** (an unused variable declaration whose adversarially-chosen name
flips the prediction; see attacks/source_attack.py for that driver). The
reference mount was empty (SURVEY.md §0), so the published attack
semantics are implemented here from the paper's method, TPU-first.

TPU-first design — the discrete search is dense linear algebra, not a
per-candidate loop:

1. one backward pass yields the gradient g [E] of the attack loss w.r.t.
   a shared free embedding placed at every occurrence slot of the
   attacked variable (the occurrence slots are remapped to a spare vocab
   row so the gradient is exact for ANY encoder — bag or transformer —
   without reimplementing its forward);
2. first-order loss deltas for renaming to EVERY token in the vocabulary
   at once are a single [V,E] @ [E] matvec on the MXU (HotFlip-style
   linearization);
3. the top-K shortlisted candidates are re-scored EXACTLY in one jitted
   forward over a [K, C] variant batch — the linearization alone
   mis-ranks, so success is always decided on true model outputs.

The outer loop (iterations × variables) stays on the host: it is O(5),
data-dependent, and each trip is one jit call (SURVEY.md "XLA
semantics" — no data-dependent control flow inside jit).
"""

from __future__ import annotations

import dataclasses
import re
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from code2vec_tpu.common import SpecialVocabWords
from code2vec_tpu.models.encoder import (ModelDims, full_logits,
                                         get_encode_fn)
from code2vec_tpu.vocab.vocabularies import Vocab

_LETTERS_RE = re.compile(r"^[a-z]+$")
# Java's reserved words (+ `var`/`string`, which would shadow). Used to
# filter Java DECLARATIONS — words like `match`/`value` are legal Java
# identifiers and must stay attackable, so Python's keywords are NOT in
# this set.
JAVA_KEYWORDS = frozenset(
    "abstract assert boolean break byte case catch char class const "
    "continue default do double else enum extends final finally float "
    "for goto if implements import instanceof int interface long native "
    "new package private protected public return short static strictfp "
    "super switch synchronized this throw throws transient try void "
    "volatile while true false null var string".split())
PYTHON_KEYWORDS = frozenset(
    "and as assert async await break class continue def del elif else "
    "except finally for from global if import in is lambda nonlocal "
    "not or pass raise return try while with yield none true false "
    "match self".split())
# The NEW-name candidate pool is shared by both frontends, so a
# replacement must be a valid identifier in either language. Keywords
# are lowercase single words — camelCase renders never collide.
RESERVED_WORDS = JAVA_KEYWORDS | PYTHON_KEYWORDS


def render_identifier(token_word: str) -> Optional[str]:
    """Stored vocab token -> Java identifier, or None if not renderable.

    Vocab tokens are normalized subtoken strings (`array|index`); the
    source-level rename needs a real identifier (`arrayIndex`). Only
    all-letter subtokens render, and reserved words are rejected —
    anything else could not be a plain identifier and is excluded from
    the candidate pool."""
    subs = token_word.split("|")
    if not subs or any(not _LETTERS_RE.match(s) for s in subs):
        return None
    ident = subs[0] + "".join(s.capitalize() for s in subs[1:])
    if ident.lower() in RESERVED_WORDS:
        return None
    return ident


def spare_row(padded_rows: int, *arrays: np.ndarray) -> int:
    """A vocab row not used by any of `arrays` (the occurrence-isolation
    remap target for the gradient trick)."""
    used = set(np.concatenate([np.asarray(a).ravel()
                               for a in arrays]).tolist())
    for cand in range(padded_rows - 1, -1, -1):
        if cand not in used:
            return cand
    raise ValueError("no spare vocab row (vocab smaller than the ids?)")


def attack_succeeded(targeted: bool, pred: int, label: int,
                     original: int) -> bool:
    """Shared success predicate: targeted hits the label; untargeted
    departs from the clean prediction."""
    return pred == label if targeted else pred != original


def build_shortlist(scores: np.ndarray, legal: np.ndarray, tried: set,
                    top_k: int, cur_id: int) -> np.ndarray:
    """First-order scores -> [top_k] candidate ids. Illegal and
    already-tried rows are inf-masked before selection; the LAST slot
    re-evaluates the current id so the caller's acceptance test costs
    no extra jit call. Masked rows can still leak into a short
    selection (vocab barely above top_k) — guard_leaked handles them
    after exact evaluation."""
    scores[~legal] = np.inf
    for t in tried:
        scores[t] = np.inf
    cand = np.empty((top_k,), np.int32)
    # argpartition: O(V) selection beats a full argsort (~8x at the
    # java-large 1.3M-row vocab); order within the shortlist does not
    # matter — every entry is exactly re-scored anyway. Both attack
    # constructors clamp top_k <= vocab rows, making kth valid.
    k = top_k - 1
    assert k < len(scores), "top_k exceeds the vocabulary"
    cand[:-1] = np.argpartition(scores, k)[:k]
    cand[-1] = cur_id
    return cand


def guard_leaked(att_losses: np.ndarray, scores: np.ndarray,
                 shortlist: np.ndarray) -> np.ndarray:
    """Never accept a shortlist row whose first-order score was
    inf-masked (illegal/tried rows that leaked through a short
    argsort)."""
    att_losses[:-1] = np.where(np.isinf(scores[shortlist[:-1]]),
                               np.inf, att_losses[:-1])
    return att_losses


def candidate_mask(token_vocab: Vocab, padded_rows: int) -> np.ndarray:
    """[padded_rows] bool: True where a vocab row is a legal rename
    candidate — a real, identifier-renderable token (no PAD/OOV, no
    padding rows, no tokens with non-letter subtokens)."""
    mask = np.zeros((padded_rows,), dtype=bool)
    for idx, word in enumerate(token_vocab.to_word_list()):
        if word in (SpecialVocabWords.PAD, SpecialVocabWords.OOV):
            continue
        if render_identifier(word) is not None:
            mask[idx] = True
    return mask


@dataclasses.dataclass
class RenameStep:
    """One accepted rename in an attack trajectory."""
    from_token: str
    to_token: str
    loss_before: float
    loss_after: float


@dataclasses.dataclass
class AttackResult:
    success: bool
    targeted: bool
    original_prediction: str
    final_prediction: str
    target_name: Optional[str]
    # per-variable (original_token, final_token) pairs, in rename order
    renames: List[Tuple[str, str]]
    steps: List[RenameStep]       # full accepted-step trajectory
    iterations: int
    # the post-attack tensors (src, pth, dst, mask) — what detectors
    # and further analysis should score (None until attack_method ran)
    final_method: Optional[tuple] = None

    def __str__(self) -> str:
        kind = "targeted" if self.targeted else "untargeted"
        status = "SUCCESS" if self.success else "failed"
        rename = (", ".join(f"{a} -> {b}" for a, b in self.renames)
                  if self.renames else "(no rename)")
        line = (f"[{kind} {status}] rename {rename}: prediction "
                f"'{self.original_prediction}' -> "
                f"'{self.final_prediction}'")
        if self.targeted:
            line += f" (target '{self.target_name}')"
        return line


def make_attack_steps(dims: ModelDims, *,
                      compute_dtype=jnp.float32) -> Tuple[Callable,
                                                          Callable,
                                                          Callable]:
    """Builds the three jitted pieces of the attack.

    Returns (score_fn, eval_fn, predict_fn):
      score_fn(params, ids, occ, spare, label, sign) -> [Vt] f32
        first-order loss delta of renaming the occurrence slots to each
        token row (lower = better for the attacker).
      eval_fn(params, ids, occ, cand_ids [K], label) ->
        (loss [K], top1 [K]) — exact model outputs for each candidate
        rename.
      predict_fn(params, ids) -> top1 on the clean input.

    `ids` is (src [C], pth [C], dst [C], mask [C]) for ONE method;
    `occ` is (occ_src [C], occ_dst [C]) bool occurrence slots;
    `sign` is +1.0 to minimize CE(label) (targeted) or -1.0 to maximize
    it (untargeted). K is cand_ids' static shape."""
    raw_score, raw_eval, raw_predict = _raw_attack_steps(
        dims, compute_dtype=compute_dtype)
    return (jax.jit(raw_score), jax.jit(raw_eval), jax.jit(raw_predict))


def make_batched_attack_steps(dims: ModelDims, *,
                              compute_dtype=jnp.float32,
                              topk_transfer: Optional[int] = None
                              ) -> Tuple[Callable, ...]:
    """vmapped-over-methods variants of make_attack_steps: every array
    argument gains a leading method dim [M, ...] (params stay shared);
    `sign` stays scalar. One dispatch attacks M methods in lockstep —
    per-dispatch overhead dominates the serial sweep of these small
    steps, so batching is what makes test-set-scale sweeps fast.

    Returns (eval_b, predict_b[, score_topk_b]); there is deliberately
    NO batched raw-score function — vmapping the spare-row trick
    materializes M functionally-updated token-table copies (64 x
    333 MB at java-large -> OOM); the lax.map'd top-k form below is the
    only safe batched score path:
      score_topk_b(params, ids, occ, spare, label, sign, legal)
        -> (scores [M, T], token_ids [M, T]), ascending
    — the first-order scores are legality-masked and top-T-selected ON
    DEVICE, so only [M, T] crosses the wire instead of [M, V] (166 MB
    per iteration for a 32-method java-large chunk — the device->host
    transfer, not dispatch, dominates once the batch is formed)."""
    raw_score, raw_eval, raw_predict = _raw_attack_steps(
        dims, compute_dtype=compute_dtype)
    out = [
        jax.jit(jax.vmap(raw_eval, in_axes=(None, 0, 0, 0, 0))),
        jax.jit(jax.vmap(raw_predict, in_axes=(None, 0))),
    ]
    if topk_transfer is not None:
        @jax.jit
        def score_topk_b(params, ids, occ, spare, label, sign, legal):
            def one(args):
                ids_i, occ_i, spare_i, label_i = args
                s = raw_score(params, ids_i, occ_i, spare_i, label_i,
                              sign)
                s = jnp.where(legal, s, jnp.inf)
                neg, idx = jax.lax.top_k(-s, topk_transfer)
                return -neg, idx

            return jax.lax.map(one, (ids, occ, spare, label))

        out.append(score_topk_b)
    return tuple(out)


def _raw_attack_steps(dims: ModelDims, *, compute_dtype=jnp.float32):
    """The un-jitted per-method step functions (see make_attack_steps
    for the contracts); jitted directly for the serial path and under
    vmap for the batched path."""
    encode = get_encode_fn(dims)

    def _loss_from_params(params, src, pth, dst, mask, label):
        code, _, _ = encode(params, src[None], pth[None], dst[None],
                            mask[None], compute_dtype=compute_dtype)
        logits = full_logits(params, code, dims.target_vocab_size)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, label[None])[0]

    def score_fn(params, ids, occ, spare, label, sign):
        src, pth, dst, mask = ids
        occ_src, occ_dst = occ
        table = params["token_emb"]
        # Remap occurrence slots to the spare (unused-in-this-method)
        # row and make that row a free variable: its gradient is exactly
        # the sum of the attack loss's slot gradients, for any encoder.
        src2 = jnp.where(occ_src, spare, src)
        dst2 = jnp.where(occ_dst, spare, dst)
        # occurrences all carry the same id (the attacked variable)
        cur_id = jnp.max(jnp.where(occ_src, src,
                                   jnp.where(occ_dst, dst, -1)))
        e_var = table[cur_id].astype(jnp.float32)

        def loss_of(e):
            t2 = table.at[spare].set(e.astype(table.dtype))
            p2 = dict(params, token_emb=t2)
            return sign * _loss_from_params(p2, src2, pth, dst2, mask,
                                            label)

        g = jax.grad(loss_of)(e_var)
        # First-order delta of moving the shared embedding to row v:
        # (table[v] - e_var) @ g; the -e_var @ g term is constant and
        # kept only so the scores are true deltas (sign-interpretable).
        scores = (table.astype(jnp.float32) @ g) - (e_var @ g)
        return scores

    def eval_fn(params, ids, occ, cand_ids, label):
        src, pth, dst, mask = ids
        occ_src, occ_dst = occ
        K = cand_ids.shape[0]
        srcK = jnp.where(occ_src[None, :], cand_ids[:, None], src[None, :])
        dstK = jnp.where(occ_dst[None, :], cand_ids[:, None], dst[None, :])
        pthK = jnp.broadcast_to(pth[None, :], (K, pth.shape[0]))
        maskK = jnp.broadcast_to(mask[None, :], (K, mask.shape[0]))
        code, _, _ = encode(params, srcK, pthK, dstK, maskK,
                            compute_dtype=compute_dtype)
        logits = full_logits(params, code, dims.target_vocab_size)
        labels = jnp.full((K,), label, dtype=jnp.int32)
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, labels)
        top1 = jnp.argmax(logits, axis=-1)
        return loss, top1

    def predict_fn(params, ids):
        src, pth, dst, mask = ids
        code, _, _ = encode(params, src[None], pth[None], dst[None],
                            mask[None], compute_dtype=compute_dtype)
        logits = full_logits(params, code, dims.target_vocab_size)
        return jnp.argmax(logits[0])

    return score_fn, eval_fn, predict_fn


class GradientRenameAttack:
    """Host orchestration of the iterative rename attack on tensorized
    methods. Works against any trained Code2VecModel-compatible params
    pytree; construct once per model, reuse across methods (the jitted
    pieces compile once)."""

    def __init__(self, dims: ModelDims, token_vocab: Vocab,
                 target_vocab: Vocab, *, top_k_candidates: int = 32,
                 max_iters: int = 4, compute_dtype=jnp.float32):
        self.dims = dims
        self.token_vocab = token_vocab
        self.target_vocab = target_vocab
        self.compute_dtype = compute_dtype
        # the shortlist cannot exceed the vocab itself (tiny test vocabs)
        top_k_candidates = min(top_k_candidates,
                               dims.padded(dims.token_vocab_size))
        self.top_k = top_k_candidates
        self.max_iters = max_iters
        self.score_fn, self.eval_fn, self.predict_fn = make_attack_steps(
            dims, compute_dtype=compute_dtype)
        self._batched = None  # built lazily by attack_batch
        self.legal = candidate_mask(token_vocab,
                                    dims.padded(dims.token_vocab_size))

    # -- helpers ---------------------------------------------------------
    def attackable_tokens(self, src: np.ndarray, dst: np.ndarray,
                          mask: np.ndarray) -> List[Tuple[int, int]]:
        """[(token_id, n_occurrences)] of rename-candidate variables in
        one method, most frequent first. A 'variable' at tensor level is
        a token id occurring in valid src/dst slots (the extractor's
        normalized leaf tokens do not distinguish symbol kinds, so every
        leaf identifier is attackable — same granularity the paper's
        tensor-space search uses before source-level validation)."""
        valid = mask > 0
        ids, counts = np.unique(
            np.concatenate([src[valid], dst[valid]]), return_counts=True)
        out = [(int(i), int(c)) for i, c in zip(ids, counts)
               if i < len(self.legal) and self.legal[i]]
        out.sort(key=lambda ic: -ic[1])
        return out

    # -- single-variable attack -----------------------------------------
    def attack_token(self, params, method: Tuple[np.ndarray, np.ndarray,
                                                 np.ndarray, np.ndarray],
                     token_id: int, *, targeted: bool,
                     label: int, original_top1: int,
                     forbidden: frozenset = frozenset()
                     ) -> Tuple[bool, int, List[RenameStep], int]:
        """Iteratively rename `token_id`'s occurrences in one method.

        `label` is the target name id (targeted) or the clean top-1 id
        (untargeted: maximize its CE, succeed when top-1 changes).
        `forbidden` token ids are never chosen as the new name; tokens
        already PRESENT in the method are always forbidden — renaming a
        variable to an identifier the method already uses would merge
        distinct symbols in the representation (and collide with
        params/locals in real source). Returns (success, final_token_id,
        steps, iters_used)."""
        src, pth, dst, mask = (np.asarray(a) for a in method)
        occ_src = src == token_id
        occ_dst = dst == token_id
        occ = (jnp.asarray(occ_src), jnp.asarray(occ_dst))
        spare = spare_row(self.dims.padded(self.dims.token_vocab_size),
                          src, dst)
        sign = 1.0 if targeted else -1.0
        cur_id = token_id
        steps: List[RenameStep] = []
        tried = ({token_id} | set(forbidden)
                 | set(np.unique(np.concatenate([src, dst])).tolist()))
        cur_src, cur_dst = src.copy(), dst.copy()

        for it in range(1, self.max_iters + 1):
            ids = (jnp.asarray(cur_src), jnp.asarray(pth),
                   jnp.asarray(cur_dst), jnp.asarray(mask))
            scores = np.array(self.score_fn(
                params, ids, occ, jnp.int32(spare), jnp.int32(label),
                sign))
            cand = build_shortlist(scores, self.legal, tried,
                                   self.top_k, cur_id)
            loss_k, top1_k = self.eval_fn(
                params, ids, occ, jnp.asarray(cand), jnp.int32(label))
            att_loss_k = guard_leaked(sign * np.asarray(loss_k),
                                      scores, cand)
            top1_k = np.asarray(top1_k)
            cur_attack_loss = float(att_loss_k[-1])
            best = int(np.argmin(att_loss_k[:-1]))
            tried.update(int(c) for c in cand)
            if att_loss_k[best] >= cur_attack_loss:
                return (attack_succeeded(targeted, int(top1_k[-1]),
                                         label, original_top1),
                        cur_id, steps, it)
            new_id = int(cand[best])
            steps.append(RenameStep(
                from_token=self.token_vocab.lookup_word(cur_id),
                to_token=self.token_vocab.lookup_word(new_id),
                loss_before=cur_attack_loss,
                loss_after=float(att_loss_k[best])))
            cur_src = np.where(occ_src, new_id, cur_src)
            cur_dst = np.where(occ_dst, new_id, cur_dst)
            cur_id = new_id
            if attack_succeeded(targeted, int(top1_k[best]), label,
                                original_top1):
                return True, cur_id, steps, it
        return False, cur_id, steps, self.max_iters

    # -- whole-method attack --------------------------------------------
    def attack_method(self, params, method, *, targeted: bool = False,
                      target_name: Optional[str] = None,
                      max_renames: int = 1,
                      token_ids: Optional[Sequence[int]] = None,
                      forbidden: frozenset = frozenset(),
                      baseline_top1: Optional[int] = None
                      ) -> AttackResult:
        """Attack one tensorized method: greedily rename up to
        `max_renames` variables (most-frequent first, or the explicit
        `token_ids`), carrying successful renames forward. `forbidden`
        ids are never used as new names (the source driver passes every
        identifier already present in the file). `baseline_top1`
        overrides the untargeted reference prediction — the dead-code
        driver passes the PRISTINE file's top-1 so 'flipped' means
        'differs from the original program', not 'differs from the
        placeholder-inserted variant'."""
        src, pth, dst, mask = (np.asarray(a) for a in method)
        ids0 = (jnp.asarray(src), jnp.asarray(pth), jnp.asarray(dst),
                jnp.asarray(mask))
        if baseline_top1 is None:
            original_top1 = int(self.predict_fn(params, ids0))
        else:
            original_top1 = int(baseline_top1)
        if targeted:
            if target_name is None:
                raise ValueError("targeted attack needs a target name")
            label = self.target_vocab.lookup_index(target_name)
            if label == self.target_vocab.oov_index:
                raise ValueError(
                    f"target name '{target_name}' is out of vocabulary")
        else:
            label = original_top1

        if token_ids is None:
            token_ids = [t for t, _ in
                         self.attackable_tokens(src, dst, mask)]
        token_ids = list(token_ids)[:max_renames]

        cur = (src.copy(), pth, dst.copy(), mask)
        all_steps: List[RenameStep] = []
        renamed: List[Tuple[int, int]] = []  # (orig_id, final_id)/var
        iters = 0
        success = False
        for tid in token_ids:
            # a requested token can be absent from the tensorized
            # method (dead-code driver after MAX_CONTEXTS downsampling
            # dropped the inserted declaration's contexts): with no
            # occurrence slots the gradient is identically zero, so
            # skip instead of burning iterations on a no-op
            if not ((cur[0] == tid).any() or (cur[2] == tid).any()):
                continue
            ok, final_id, steps, used = self.attack_token(
                params, cur, tid, targeted=targeted, label=label,
                original_top1=original_top1, forbidden=forbidden)
            iters += used
            if steps:
                all_steps.extend(steps)
                renamed.append((tid, final_id))
                occ_s, occ_d = cur[0] == tid, cur[2] == tid
                cur = (np.where(occ_s, final_id, cur[0]), cur[1],
                       np.where(occ_d, final_id, cur[2]), cur[3])
            if ok:
                success = True
                break

        idsF = (jnp.asarray(cur[0]), jnp.asarray(cur[1]),
                jnp.asarray(cur[2]), jnp.asarray(cur[3]))
        top1_f = self.predict_fn(params, idsF)
        tv = self.target_vocab
        look = self.token_vocab.lookup_word
        return AttackResult(
            success=success, targeted=targeted,
            original_prediction=tv.lookup_word(original_top1),
            final_prediction=tv.lookup_word(int(top1_f)),
            target_name=target_name,
            renames=[(look(a), look(b)) for a, b in renamed],
            steps=all_steps, iterations=iters, final_method=cur)

    # -- lockstep batch attack ------------------------------------------
    def attack_batch(self, params, methods: Sequence[Tuple]
                     ) -> List[AttackResult]:
        """Untargeted single-rename attack on M methods at once —
        semantically identical to `attack_method(m, targeted=False,
        max_renames=1)` per method (same scores, same selections, same
        acceptance), but each of the ~max_iters+2 jit dispatches covers
        the WHOLE batch. Fixed dispatch cost dominates the serial
        sweep, so this is what makes test-set-scale robustness sweeps
        fast. Methods must each have
        at least one attackable token (the sweep filters first).

        Equivalence caveat: the serial path shortlists via argpartition
        (arbitrary order within the partition) while this path uses a
        sorted device top_k, so an EXACT float tie in first-order scores
        at the shortlist boundary can admit different candidate sets —
        and, since acceptance re-scores exactly, potentially a different
        accepted rename. Ties at f32 gradient-score precision do not
        occur on the tested corpora (the equivalence test passes
        bit-for-bit), but the guarantee is "identical absent score
        ties", not unconditional."""
        rows = self.dims.padded(self.dims.token_vocab_size)
        if self._batched is None:
            # top-T transfer bound: the host drops tried ids from the
            # device top list, so T must cover the K-1 picks plus every
            # id that can be in `tried` (initial method tokens <= 2C+1,
            # plus K per prior iteration)
            T = min(rows, (self.top_k - 1)
                    + 2 * self.dims.max_contexts + 1
                    + self.top_k * self.max_iters)
            self._batched = make_batched_attack_steps(
                self.dims, compute_dtype=self.compute_dtype,
                topk_transfer=T)
        eval_b, predict_b, score_topk_b = self._batched
        legal_dev = jnp.asarray(self.legal)
        M = len(methods)
        src = np.stack([np.asarray(m[0]) for m in methods])
        pth = np.stack([np.asarray(m[1]) for m in methods])
        dst = np.stack([np.asarray(m[2]) for m in methods])
        mask = np.stack([np.asarray(m[3]) for m in methods])
        tok_lists = [self.attackable_tokens(src[i], dst[i], mask[i])
                     for i in range(M)]
        for i, tl in enumerate(tok_lists):
            if len(tl) == 0:
                raise ValueError(
                    f"method {i} has no attackable tokens; filter with "
                    "attackable_tokens first (robustness.py's sweep "
                    "does this)")
        tok = np.array([tl[0][0] for tl in tok_lists], np.int32)
        occ_src = src == tok[:, None]
        occ_dst = dst == tok[:, None]
        occ = (jnp.asarray(occ_src), jnp.asarray(occ_dst))
        spare = np.array([spare_row(rows, src[i], dst[i])
                          for i in range(M)], np.int32)
        labels = np.asarray(predict_b(
            params, (jnp.asarray(src), jnp.asarray(pth),
                     jnp.asarray(dst), jnp.asarray(mask)))).astype(
                         np.int32)
        original = labels.copy()

        cur_src, cur_dst = src.copy(), dst.copy()
        cur_id = tok.copy()
        tried = [({int(tok[i])}
                  | set(np.unique(np.concatenate(
                      [src[i], dst[i]])).tolist()))
                 for i in range(M)]
        steps: List[List[RenameStep]] = [[] for _ in range(M)]
        success = np.zeros((M,), bool)
        done = np.zeros((M,), bool)
        iters = np.zeros((M,), np.int32)
        look = self.token_vocab.lookup_word

        for _ in range(self.max_iters):
            ids = (jnp.asarray(cur_src), jnp.asarray(pth),
                   jnp.asarray(cur_dst), jnp.asarray(mask))
            top_scores, top_ids = score_topk_b(
                params, ids, occ, jnp.asarray(spare),
                jnp.asarray(labels), -1.0, legal_dev)
            top_scores = np.asarray(top_scores)
            top_ids = np.asarray(top_ids)
            cand = np.empty((M, self.top_k), np.int32)
            for i in range(M):
                # host-side: first K-1 untried, finite entries of the
                # device top list (legality was masked on device); pad
                # with cur_id when the list runs dry — those re-evaluate
                # the current loss and can never be accepted (>= test)
                cand[i, :] = cur_id[i]
                if done[i]:
                    continue
                w = 0
                for t, s in zip(top_ids[i], top_scores[i]):
                    if w == self.top_k - 1 or np.isinf(s):
                        break
                    if int(t) not in tried[i]:
                        cand[i, w] = int(t)
                        w += 1
            loss_k, top1_k = eval_b(params, ids, occ,
                                    jnp.asarray(cand),
                                    jnp.asarray(labels))
            loss_k = np.asarray(loss_k)
            top1_k = np.asarray(top1_k)
            for i in range(M):
                if done[i]:
                    continue
                att = -loss_k[i]
                iters[i] += 1
                best = int(np.argmin(att[:-1]))
                tried[i].update(int(c) for c in cand[i])
                if att[best] >= float(att[-1]):
                    success[i] = attack_succeeded(
                        False, int(top1_k[i, -1]), int(labels[i]),
                        int(original[i]))
                    done[i] = True
                    continue
                new_id = int(cand[i, best])
                steps[i].append(RenameStep(
                    from_token=look(int(cur_id[i])),
                    to_token=look(new_id),
                    loss_before=float(att[-1]),
                    loss_after=float(att[best])))
                cur_src[i] = np.where(occ_src[i], new_id, cur_src[i])
                cur_dst[i] = np.where(occ_dst[i], new_id, cur_dst[i])
                cur_id[i] = new_id
                if attack_succeeded(False, int(top1_k[i, best]),
                                    int(labels[i]), int(original[i])):
                    success[i] = True
                    done[i] = True
            if done.all():
                break

        final_top1 = np.asarray(predict_b(
            params, (jnp.asarray(cur_src), jnp.asarray(pth),
                     jnp.asarray(cur_dst), jnp.asarray(mask))))
        tv = self.target_vocab
        return [AttackResult(
            success=bool(success[i]), targeted=False,
            original_prediction=tv.lookup_word(int(original[i])),
            final_prediction=tv.lookup_word(int(final_top1[i])),
            target_name=None,
            renames=([(look(int(tok[i])), look(int(cur_id[i])))]
                     if steps[i] else []),
            steps=steps[i], iterations=int(iters[i]),
            final_method=(cur_src[i], pth[i], cur_dst[i], mask[i]))
            for i in range(M)]
