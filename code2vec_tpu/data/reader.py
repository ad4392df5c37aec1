"""Host-side input pipeline: `.c2v` text / binary shards -> fixed-shape
int32 batches + padding mask.

Reference parity target: `path_context_reader.py` (SURVEY.md §2 L3, §3):
`PathContextReader` yielding `ReaderInputTensors` (target idx, three
[B, MAX_CONTEXTS] context index tensors, `context_valid_mask`, plus string
fields for eval/predict). TPU-first differences:

- No tf.data graph; the host produces numpy arrays with STATIC shapes
  (the final short batch is padded and carries `num_valid`) so the jitted
  step never re-traces.
- The fast path is pre-binarized int32 shards (data/binarize.py) read via
  np.memmap — CSV/string parsing on the host is the #1 throughput risk for
  the 8x target (SURVEY.md §8.3 step 2).
- Shuffle is a GLOBAL index permutation per epoch, seeded for
  reproducibility; each host then takes its strided slice of the
  permuted order. Host h's batch t is rows perm[h::H][tB:(t+1)B], so
  the union across hosts at step t is the contiguous block
  perm[H·tB : H·(t+1)B] — the global data order is a function of
  (seed, epoch) ALONE, independent of the host count (ISSUE 13: an
  elastically re-formed cohort replays the same global stream a
  same-size uninterrupted run would).
- `host_shard` / `num_host_shards` slice the example space for multi-host
  feeding (each host feeds its local devices; SURVEY.md §3.3 "Infeed").
"""

from __future__ import annotations

import json
import os
import zlib
from typing import Iterator, List, NamedTuple, Optional

import numpy as np

from code2vec_tpu.data.staircase import length_order
from code2vec_tpu.vocab.vocabularies import Code2VecVocabs


class BatchTensors(NamedTuple):
    """One host batch. Shapes are static: [B] / [B, C]."""
    target_index: np.ndarray            # int32 [B]
    path_source_token_indices: np.ndarray  # int32 [B, C]
    path_indices: np.ndarray            # int32 [B, C]
    path_target_token_indices: np.ndarray  # int32 [B, C]
    context_valid_mask: np.ndarray      # float32 [B, C]; 1.0 = real context
    num_valid_examples: int             # <= B; B unless final padded batch
    target_strings: Optional[List[str]] = None   # eval/predict only
    context_strings: Optional[List[List[str]]] = None  # predict only


def parse_c2v_rows(lines: List[str], vocabs: Code2VecVocabs,
                   max_contexts: int, keep_strings: bool = False,
                   sample_seed: int = 0):
    """Vectorized-enough parse of `.c2v` rows into index arrays.

    A context field is `left,path,right`; empty ('' or ',,') fields are
    padding (PAD index, mask 0). OOV words map to the OOV index
    (SURVEY.md §3.2). Rows with more than `max_contexts` contexts (raw
    extractor output on the predict path — preprocessed files are already
    capped) are downsampled uniformly without replacement, matching the
    reference preprocess behavior (SURVEY.md §3 preprocess row: "truncate
    each method's contexts to 200 (random sample when over)"); seeded for
    reproducible predictions.
    """
    n = len(lines)
    tok_v, path_v, tgt_v = (vocabs.token_vocab, vocabs.path_vocab,
                            vocabs.target_vocab)
    labels = np.zeros((n,), dtype=np.int32)
    src = np.full((n, max_contexts), tok_v.pad_index, dtype=np.int32)
    pth = np.full((n, max_contexts), path_v.pad_index, dtype=np.int32)
    dst = np.full((n, max_contexts), tok_v.pad_index, dtype=np.int32)
    mask = np.zeros((n, max_contexts), dtype=np.float32)
    target_strings: List[str] = []
    context_strings: List[List[str]] = []
    for i, line in enumerate(lines):
        parts = line.rstrip("\n").split(" ")
        target = parts[0]
        labels[i] = tgt_v.lookup_index(target)
        ctxs = parts[1:]
        if len(ctxs) > max_contexts:
            # drop pad fields ('' / ',,' — preprocess pads rows to a fixed
            # width) before sampling so only REAL contexts compete for
            # the max_contexts slots
            real = [c for c in ctxs if c and c != ",,"]
            if len(real) > max_contexts:
                # sample from the row's SORTED context bag with a seed
                # derived from that same bag — not from batch position
                # or context order: the same method must keep the same
                # contexts wherever (and however ordered) it appears,
                # so the serving cache — keyed by exactly this
                # normalized bag — stays deterministic. The bag encoder
                # is order-invariant, so emitting the sample in sorted
                # order loses nothing.
                canon = sorted(real)
                rng = np.random.default_rng(
                    (sample_seed,
                     zlib.crc32(" ".join(canon).encode("utf-8"))))
                pick = np.sort(rng.choice(len(canon), size=max_contexts,
                                          replace=False))
                real = [canon[k] for k in pick]
            ctxs = real
        if keep_strings:
            target_strings.append(target)
            context_strings.append(ctxs)
        for j, ctx in enumerate(ctxs):
            if not ctx or ctx == ",,":
                continue
            fields = ctx.split(",")
            if len(fields) != 3 or not fields[1]:
                continue
            src[i, j] = tok_v.lookup_index(fields[0])
            pth[i, j] = path_v.lookup_index(fields[1])
            dst[i, j] = tok_v.lookup_index(fields[2])
            mask[i, j] = 1.0
    return labels, src, pth, dst, mask, target_strings, context_strings


def _aligned_num_batches(global_examples: int, num_host_shards: int,
                         batch_size: int) -> int:
    """Number of batches EVERY host must emit per epoch.

    Round-robin sharding gives hosts shard sizes differing by at most 1,
    so the largest shard has ceil(N/H) examples. Hosts with fewer batches
    pad with empty (all-weight-zero) batches so every host joins the same
    number of collective steps — otherwise the epoch deadlocks on the
    host that runs one extra SPMD step.
    """
    largest_shard = -(-global_examples // num_host_shards)
    return -(-largest_shard // batch_size)


def steps_per_epoch(num_examples: int, batch_size: int,
                    num_host_shards: int = 1) -> int:
    """Train steps one epoch takes on every host — the public form of
    `_aligned_num_batches` (and the same ceil-div the LR-schedule
    horizon uses in training/optimizers.schedule_total_steps). The
    resume path divides a restored step count by this to recover how
    many epochs a killed run had completed."""
    return _aligned_num_batches(num_examples, num_host_shards,
                                batch_size)


def _pad_batch(arrs, batch_size: int):
    """Pad along axis 0 to `batch_size` by repeating zeros/PAD rows."""
    out = []
    for a in arrs:
        pad = batch_size - a.shape[0]
        if pad > 0:
            a = np.concatenate(
                [a, np.zeros((pad,) + a.shape[1:], dtype=a.dtype)], axis=0)
        out.append(a)
    return out


class C2VTextReader:
    """Slow-path reader over a `.c2v` text file (drop-in compatibility
    with reference-produced data)."""

    def __init__(self, path: str, vocabs: Code2VecVocabs, max_contexts: int,
                 batch_size: int, shuffle: bool = False, seed: int = 0,
                 keep_strings: bool = False,
                 host_shard: int = 0, num_host_shards: int = 1,
                 epoch_offset: int = 0):
        self.path = path
        self.vocabs = vocabs
        self.max_contexts = max_contexts
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.seed = seed
        self.keep_strings = keep_strings
        self.host_shard = host_shard
        self.num_host_shards = num_host_shards
        # epoch_offset: an auto-resumed run starts its shuffle stream
        # at the epoch it was killed in, not back at epoch 0 — the
        # permutation is seeded `seed + _epoch`, so resume replays the
        # EXACT data order the uninterrupted run would have used
        self._epoch = epoch_offset
        self._offsets: Optional[np.ndarray] = None

    def _line_offsets(self) -> np.ndarray:
        """Byte offsets of non-empty lines (built once; the file itself is
        never held in memory — reference-scale .c2v files are tens of GB,
        so whole-file reads would OOM the host)."""
        if self._offsets is None:
            offsets = []
            with open(self.path, "rb") as f:
                pos = 0
                for raw in f:
                    if raw.strip():
                        offsets.append(pos)
                    pos += len(raw)
            self._offsets = np.asarray(offsets, dtype=np.int64)
        return self._offsets

    def __iter__(self) -> Iterator[BatchTensors]:
        offsets = self._line_offsets()
        # GLOBAL permutation first, host-shard slice second (ISSUE 13):
        # the epoch's data order is fixed by (seed, epoch) before any
        # host claims its rows, so a resize changes only how the one
        # global stream is dealt out — not what the stream is
        order = np.arange(len(offsets))
        if self.shuffle:
            rng = np.random.default_rng(self.seed + self._epoch)
            rng.shuffle(order)
            self._epoch += 1
        mine = order[self.host_shard::self.num_host_shards]
        emitted = 0
        with open(self.path, "r", encoding="utf-8", errors="replace") as f:
            for start in range(0, len(mine), self.batch_size):
                idx = mine[start:start + self.batch_size]
                batch_lines = []
                for off in offsets[idx]:
                    f.seek(off)
                    batch_lines.append(f.readline())
                emitted += 1
                yield self._parse_batch(batch_lines)
        if self.num_host_shards > 1:
            target = _aligned_num_batches(len(self._line_offsets()),
                                          self.num_host_shards,
                                          self.batch_size)
            for _ in range(target - emitted):
                yield self._empty_batch()

    # Subclasses (e.g. the VarMisuse reader) override these two to reuse
    # the offset-streaming / shuffle / host-shard / aligned-batch loop
    # above with a different row format.
    def _parse_batch(self, batch_lines: List[str]) -> BatchTensors:
        labels, src, pth, dst, mask, tstr, cstr = parse_c2v_rows(
            batch_lines, self.vocabs, self.max_contexts,
            self.keep_strings)
        nv = len(batch_lines)
        labels, src, pth, dst, mask = _pad_batch(
            (labels, src, pth, dst, mask), self.batch_size)
        return BatchTensors(labels, src, pth, dst, mask, nv,
                            tstr if self.keep_strings else None,
                            cstr if self.keep_strings else None)

    def _empty_batch(self) -> BatchTensors:
        B, C = self.batch_size, self.max_contexts
        return BatchTensors(
            np.zeros((B,), np.int32),
            np.full((B, C), self.vocabs.token_vocab.pad_index, np.int32),
            np.full((B, C), self.vocabs.path_vocab.pad_index, np.int32),
            np.full((B, C), self.vocabs.token_vocab.pad_index, np.int32),
            np.zeros((B, C), np.float32), 0,
            [] if self.keep_strings else None,
            [] if self.keep_strings else None)


class BinaryShardReader:
    """Fast-path reader over the pre-tokenized int32 shard written by
    data/binarize.py: a memmapped [N, 1 + 3*C] int32 matrix
    (label, src*C, path*C, tgt*C) + a JSON manifest.

    Row order inside a batch is the reader's to choose (membership is
    the shuffled permutation's). As opened, rows stand in file order.
    After `order_by_length(groups)`, which only the training infeed
    calls (`Code2VecModel._train_infeed`, for a model that takes table
    rows over a staircase: data/staircase.py), every whole batch is
    ordered by bag length instead, longest first. A reader opened for
    evaluation is never asked: its results are matched to rows."""

    def __init__(self, prefix: str, batch_size: int, shuffle: bool = False,
                 seed: int = 0, host_shard: int = 0,
                 num_host_shards: int = 1,
                 expected_max_contexts: Optional[int] = None,
                 keep_strings: bool = False, epoch_offset: int = 0):
        with open(prefix + ".bin.json", "r") as f:
            self.manifest = json.load(f)
        self.target_strings: Optional[List[str]] = None
        if keep_strings:
            # sidecar written by binarize: original target names, needed
            # for subtoken metrics (OOV targets collapse in the vocab)
            with open(prefix + ".bin.targets", encoding="utf-8") as f:
                self.target_strings = [ln.rstrip("\n") for ln in f]
        self.max_contexts = int(self.manifest["max_contexts"])
        if (expected_max_contexts is not None
                and expected_max_contexts != self.max_contexts):
            raise ValueError(
                f"binary shard {prefix}.bin was built with max_contexts="
                f"{self.max_contexts} but the run requests "
                f"{expected_max_contexts}; re-binarize or match the flag")
        self.num_examples = int(self.manifest["num_examples"])
        row_width = 1 + 3 * self.max_contexts
        self.data = np.memmap(prefix + ".bin", dtype=np.int32, mode="r",
                              shape=(self.num_examples, row_width))
        self.pad_index = int(self.manifest["pad_index"])
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.seed = seed
        self.host_shard = host_shard
        self.num_host_shards = num_host_shards
        # see C2VTextReader: resume replays the interrupted epoch's
        # seeded permutation instead of restarting the stream at 0
        self._epoch = epoch_offset
        self._length_groups = 0     # 0: batches keep file order

    def order_by_length(self, groups: int = 1) -> None:
        """From the next pass on, order each whole batch's rows by bag
        length, longest first, in `groups` contiguous blocks of equal
        load (`staircase.length_order`: a mesh's device `g` gets block
        `g`). A short last batch keeps file order: its padding rows
        must stay last (`num_valid_examples` counts from the front)."""
        assert groups >= 1 and self.batch_size % groups == 0, (
            groups, self.batch_size)
        self._length_groups = groups

    def __iter__(self) -> Iterator[BatchTensors]:
        C = self.max_contexts
        # global permutation, then the host's strided slice — see
        # C2VTextReader.__iter__ (the elastic-resume data-order
        # contract is identical on the binary fast path)
        order = np.arange(self.num_examples)
        if self.shuffle:
            rng = np.random.default_rng(self.seed + self._epoch)
            rng.shuffle(order)
            self._epoch += 1
        order = order[self.host_shard::self.num_host_shards]
        emitted = 0
        for start in range(0, len(order), self.batch_size):
            idx = order[start:start + self.batch_size]
            # Within-batch ascending order turns the memmap fancy-index
            # into a forward-only disk read (big win on cold page cache).
            # SGD-safe: batch MEMBERSHIP stays the shuffled permutation;
            # only the order of rows inside one batch changes, which the
            # batch-mean loss is invariant to (target_strings are
            # reindexed identically below).
            sorted_idx = np.sort(idx)
            rows = np.asarray(self.data[sorted_idx])
            labels = rows[:, 0].astype(np.int32)
            src = rows[:, 1:1 + C]
            pth = rows[:, 1 + C:1 + 2 * C]
            dst = rows[:, 1 + 2 * C:1 + 3 * C]
            valid = pth != self.pad_index
            nv = rows.shape[0]
            if self._length_groups and nv == self.batch_size:
                # the permuted copies stand in for the contiguous ones
                # below: about the same bytes moved
                perm = length_order(np.count_nonzero(valid, axis=1),
                                    self._length_groups)
                sorted_idx, labels, valid = (sorted_idx[perm], labels[perm],
                                             valid[perm])
                src, pth, dst = src[perm], pth[perm], dst[perm]
            mask = valid.astype(np.float32)
            tstr = None
            if self.target_strings is not None:
                tstr = [self.target_strings[i] for i in sorted_idx]
            labels, src, pth, dst, mask = _pad_batch(
                (labels, src, pth, dst, mask), self.batch_size)
            emitted += 1
            yield BatchTensors(labels, np.ascontiguousarray(src),
                               np.ascontiguousarray(pth),
                               np.ascontiguousarray(dst), mask, nv,
                               tstr)
        if self.num_host_shards > 1:
            target = _aligned_num_batches(self.num_examples,
                                          self.num_host_shards,
                                          self.batch_size)
            for _ in range(target - emitted):
                B = self.batch_size
                yield BatchTensors(
                    np.zeros((B,), np.int32),
                    np.full((B, C), self.pad_index, np.int32),
                    np.full((B, C), self.pad_index, np.int32),
                    np.full((B, C), self.pad_index, np.int32),
                    np.zeros((B, C), np.float32), 0)


def count_examples(path_or_prefix: str) -> int:
    """Number of examples in a split — from the binary manifest when
    available (O(1)), else a line count. Used to size LR schedules."""
    prefix = path_or_prefix
    if prefix.endswith(".c2v"):
        prefix = prefix[:-len(".c2v")]
    if os.path.exists(prefix + ".bin.json"):
        with open(prefix + ".bin.json") as f:
            return int(json.load(f)["num_examples"])
    n = 0
    with open(path_or_prefix, "rb") as f:
        for raw in f:
            if raw.strip():
                n += 1
    return n


def open_reader(path_or_prefix: str, vocabs: Code2VecVocabs,
                max_contexts: int, batch_size: int, shuffle: bool = False,
                seed: int = 0, keep_strings: bool = False,
                host_shard: int = 0, num_host_shards: int = 1,
                epoch_offset: int = 0):
    """Pick the binary fast path when a `.bin` sibling exists, else text.
    `host_shard`/`num_host_shards` (typically jax.process_index/count)
    slice the example space so each host feeds a disjoint shard.
    `epoch_offset` starts the per-epoch shuffle stream at that epoch
    (auto-resume: replay the killed run's data order, don't restart
    it)."""
    prefix = path_or_prefix
    if prefix.endswith(".c2v"):
        prefix = prefix[:-len(".c2v")]
    have_bin = os.path.exists(prefix + ".bin.json")
    have_targets = os.path.exists(prefix + ".bin.targets")
    if have_bin and (not keep_strings or have_targets):
        return BinaryShardReader(prefix, batch_size, shuffle=shuffle,
                                 seed=seed, host_shard=host_shard,
                                 num_host_shards=num_host_shards,
                                 expected_max_contexts=max_contexts,
                                 keep_strings=keep_strings,
                                 epoch_offset=epoch_offset)
    return C2VTextReader(path_or_prefix, vocabs, max_contexts, batch_size,
                         shuffle=shuffle, seed=seed,
                         keep_strings=keep_strings, host_shard=host_shard,
                         num_host_shards=num_host_shards,
                         epoch_offset=epoch_offset)
