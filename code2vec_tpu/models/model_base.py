"""Framework-agnostic model lifecycle + host-side metric computation.

Reference parity target: `model_base.py` (SURVEY.md §3 "Model base"):
`Code2VecModelBase` with `train()`, `evaluate()` returning
`EvaluationResults(topk_acc, subtoken_precision, subtoken_recall,
subtoken_f1, loss)`, `predict(lines)`, save/load orchestration,
`save_word2vec_format()`. Metric semantics (SURVEY.md §4.3): exact-match
top-k accuracy over legal predictions, and subtoken TP/FP/FN accumulated
from the first legal top-1 prediction vs. the true name.
"""

from __future__ import annotations

import abc
from typing import Iterable, List, Sequence

import numpy as np

from code2vec_tpu.common import (EvaluationResults, SubtokenStatistics,
                                 filter_impossible_names)
from code2vec_tpu.config import Config
from code2vec_tpu.vocab.vocabularies import Code2VecVocabs, Vocab, VocabType


class MetricAccumulator:
    """Accumulates top-k exact-match accuracy + subtoken stats over an
    evaluation run (host-side numpy/string code, as in the reference)."""

    def __init__(self, top_k: int):
        self.top_k = top_k
        self.num_examples = 0
        self.topk_correct = np.zeros((top_k,), dtype=np.int64)
        self.subtoken_stats = SubtokenStatistics()
        self.loss_sum = 0.0

    def update_batch(self, original_names: Sequence[str],
                     predicted_words: Sequence[Sequence[str]],
                     loss_sum: float = 0.0) -> None:
        self.loss_sum += float(loss_sum)
        for original, topk in zip(original_names, predicted_words):
            self.num_examples += 1
            legal = filter_impossible_names(list(topk))
            # top-k exact match: original found at rank r (in the legal
            # list) counts for every k > r.
            if original in legal:
                rank = legal.index(original)
                if rank < self.top_k:
                    self.topk_correct[rank:] += 1
            # subtoken stats vs. the best legal prediction
            top_prediction = legal[0] if legal else ""
            self.subtoken_stats.update(original, top_prediction)

    def merge_across_hosts(self) -> None:
        """Sum this accumulator's partials with every other process's
        (no-op single-process): the multi-host eval path shards the eval
        file per host, so each accumulator holds one host's examples."""
        from code2vec_tpu.parallel.distributed import allreduce_sum_hosts
        vec = np.concatenate([
            [self.num_examples, self.loss_sum,
             self.subtoken_stats.true_positive,
             self.subtoken_stats.false_positive,
             self.subtoken_stats.false_negative],
            self.topk_correct]).astype(np.float64)
        total = allreduce_sum_hosts(vec)
        self.num_examples = int(total[0])
        self.loss_sum = float(total[1])
        self.subtoken_stats.true_positive = int(total[2])
        self.subtoken_stats.false_positive = int(total[3])
        self.subtoken_stats.false_negative = int(total[4])
        self.topk_correct = total[5:].astype(np.int64)

    def results(self) -> EvaluationResults:
        n = max(self.num_examples, 1)
        return EvaluationResults(
            topk_acc=(self.topk_correct / n).tolist(),
            subtoken_precision=self.subtoken_stats.precision,
            subtoken_recall=self.subtoken_stats.recall,
            subtoken_f1=self.subtoken_stats.f1,
            loss=self.loss_sum / n,
        )


class Code2VecModelBase(abc.ABC):
    def __init__(self, config: Config):
        self.config = config
        # run telemetry (code2vec_tpu/obs/): train() replaces this with
        # a file-backed run when --telemetry_dir is set, and the serving
        # REPL injects its always-on latency registry; the disabled
        # singleton keeps predict()'s span calls branch-free. Same deal
        # for the request-scoped tracer (--trace): train() and the
        # PredictionServer install a recording one.
        from code2vec_tpu.obs import Telemetry, Tracer, memory_tracer
        self.telemetry = Telemetry.disabled()
        self.tracer = Tracer.disabled()
        with memory_tracer().start_span("setup/vocabs") as span:
            self.vocabs: Code2VecVocabs = self._load_or_create_vocabs()
            span.attrs.update(tokens=self.vocabs.token_vocab.size,
                              paths=self.vocabs.path_vocab.size,
                              targets=self.vocabs.target_vocab.size)

    # ---- lifecycle ----
    @abc.abstractmethod
    def _load_or_create_vocabs(self) -> Code2VecVocabs: ...

    @abc.abstractmethod
    def train(self) -> None: ...

    @abc.abstractmethod
    def evaluate(self) -> EvaluationResults: ...

    @abc.abstractmethod
    def predict(self, predict_data_lines: Iterable[str]) -> List: ...

    @abc.abstractmethod
    def save(self, path: str) -> None: ...

    @abc.abstractmethod
    def release(self) -> None: ...

    @abc.abstractmethod
    def get_embedding_table(self, vocab_type: VocabType) -> np.ndarray: ...

    def close_session(self) -> None:
        """Reference API compatibility no-op (no TF session)."""

    # ---- word2vec export (SURVEY.md §4.5) ----
    def save_word2vec_format(self, dest_path: str,
                             vocab_type: VocabType) -> None:
        vocab: Vocab = self.vocabs.get(vocab_type)
        table = np.asarray(self.get_embedding_table(vocab_type))
        n, dim = vocab.size, table.shape[1]
        with open(dest_path, "w", encoding="utf-8") as f:
            f.write(f"{n} {dim}\n")
            for idx in range(n):
                word = vocab.lookup_word(idx)
                vec = " ".join(f"{x:.6f}" for x in table[idx])
                f.write(f"{word} {vec}\n")
