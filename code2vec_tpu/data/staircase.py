"""The staircase of an ordered training batch (ISSUE 29), host side.

Six slots in ten of a java-large batch are PAD, and a PAD slot costs
the embedding gather and its scatter what a real one costs. Ordered by
bag length, longest first, a batch's valid slots form a staircase:
slot column `c` is valid for a PREFIX of the rows. A few rectangles
`rows[:n_k] x columns[c_k:c_{k+1}]` with static `n_k` cover it, and
`models/encoder.embed_contexts` takes table rows for the rectangles
only.

Who does what:

- `from_lengths` works the rectangles out, once, at model build
  (`Code2VecModel`), from the training shard's own bag lengths
  (`shard_lengths`). A static tuple: the jitted step is compiled for it.
- `BinaryShardReader.order_by_length` (data/reader.py) orders each whole
  training batch with `length_order`, once `_train_infeed` has asked;
  evaluation, prediction and serving batches are never ordered (their
  results come back by row).
- `fits` is the producer's check on the batch it is about to transfer
  (`Code2VecModel._train_device_batch`): every id outside the
  rectangles is PAD, read off the three id arrays themselves. The
  device batch carries the answer (`training/steps.TrainBatch`) and
  `_train_step` picks the staircase step or the full one by it, so no
  result depends on the fit, or on the order: a batch that does not fit
  runs the step every batch ran before.

PAD is id 0 here, as in `models/encoder.PAD_ID`: a shard with another
pad index gets no staircase.
"""

from __future__ import annotations

import math
from typing import Tuple

import numpy as np

# ((first column, rows kept), ...): rectangle k spans the columns from
# its first to the next rectangle's first (the last: to max_contexts)
Stairs = Tuple[Tuple[int, int], ...]

# a shard of up to this many rows is scanned whole, so that the same
# multiset of lengths gives the same staircase whatever its order
_WHOLE_SHARD_ROWS = 1 << 21
_SAMPLE_ROWS = 1 << 16
_SCAN_ROWS = 1 << 16       # rows of the memmap looked at at a time
# the least width of a query block of the softmax mixers' core, chosen
# on the chip from times (PERF.md section 6, PR 35: at 128 rows x 200
# slots two to six blocks cost a mixer the same within 2%, and every
# block is a shape more in the step's executable)
_BLOCK_SLOTS = 64


def length_order(lengths: np.ndarray, groups: int = 1) -> np.ndarray:
    """The permutation that orders a batch's rows by bag length, longest
    first (stable), dealt out to `groups` contiguous blocks: block `g`
    holds every `groups`-th row of the sorted batch starting at `g`, so
    each block is longest first, every block sees the same staircase
    and the blocks' total lengths differ by less than one bag."""
    key = int(lengths.max(initial=0)) - lengths
    if key.max(initial=0) < 1 << 16:
        key = key.astype(np.uint16)     # numpy's stable sort is a radix
    order = np.argsort(key, kind="stable")  # sort for 16-bit keys
    if groups == 1:
        return order
    return np.concatenate([order[g::groups] for g in range(groups)])


def _column_bounds(max_contexts: int) -> list:
    """First columns of the rectangles: about six of equal width, at
    multiples of 8 (a bf16 `[rows, width, E]` block then splits on the
    TPU's tiles), the last taking the remainder."""
    width = max(8, max_contexts // 6 // 8 * 8)
    return list(range(0, max_contexts - width + 1, width)) or [0]


def from_lengths(lengths: np.ndarray, rows: int, max_contexts: int
                 ) -> Stairs:
    """The staircase for batches of `rows` bags drawn from `lengths`:
    a rectangle keeps the share of bags longer than its first column,
    plus four standard deviations of a batch's count, rounded up to a
    step of `rows / 32` (at least 8) and capped at `rows`. The rounding
    makes the tuple a function of the multiset of lengths alone, which
    keeps one compiled step across runs on reshuffled corpora.
    Neighbours that keep the same rows are merged, so full bags give
    the whole rectangle, `((0, rows),)`."""
    step = max(8, rows // 32)
    stairs = []
    for first in _column_bounds(max_contexts):
        share = float(np.count_nonzero(lengths > first)) / len(lengths)
        kept = share * rows + 4.0 * math.sqrt(rows * share * (1 - share))
        kept = min(rows, int(math.ceil(kept / step)) * step)
        if kept and (not stairs or stairs[-1][1] != kept):
            stairs.append((first, kept))
    return tuple(stairs) or ((0, rows),)


def shard_lengths(data: np.ndarray, max_contexts: int, pad: int
                  ) -> np.ndarray:
    """Bag lengths of a binary shard (`[N, 1 + 3 C]`, data/binarize.py)
    by its path column: of every row where the shard is small, else of
    every `N // 65536`-th row."""
    n = data.shape[0]
    stride = 1 if n <= _WHOLE_SHARD_ROWS else n // _SAMPLE_ROWS
    paths = data[::stride, 1 + max_contexts:1 + 2 * max_contexts]
    out = np.empty(paths.shape[0], np.int32)
    for start in range(0, len(out), _SCAN_ROWS):
        part = np.asarray(paths[start:start + _SCAN_ROWS])
        out[start:start + _SCAN_ROWS] = np.count_nonzero(part != pad,
                                                         axis=1)
    return out


def area(stairs: Stairs, max_contexts: int) -> int:
    """Slots the rectangles hold."""
    firsts = [c for c, _ in stairs] + [max_contexts]
    return sum(kept * (firsts[k + 1] - firsts[k])
               for k, (_, kept) in enumerate(stairs))


def rows_kept(stairs: Stairs, column: int) -> int:
    """Rows the rectangle that holds slot column `column` keeps.
    `from_lengths`' rectangles keep fewer rows the further right they
    stand, so in a batch that `fits` the rows from there down are PAD
    in that column and in every later one."""
    return [kept for first, kept in stairs if first <= column][-1]


def query_blocks(stairs: Stairs, max_contexts: int
                 ) -> Tuple[Tuple[int, int, int], ...]:
    """(first slot, end slot, rows) of the query blocks the softmax
    mixers' core runs over (`models/seq_block.causal_core`): each
    rectangle a block, or neighbours joined under the first one's rows
    until a block is `_BLOCK_SLOTS` wide (a last, narrower one joins
    the block before it). A block's queries see the keys of its rows
    up to its end slot and no others."""
    firsts = [first for first, _ in stairs] + [max_contexts]
    blocks = []
    for k, (first, kept) in enumerate(stairs):
        if blocks and blocks[-1][1] - blocks[-1][0] < _BLOCK_SLOTS:
            blocks[-1][1] = firsts[k + 1]
        else:
            blocks.append([first, firsts[k + 1], kept])
    if len(blocks) > 1 and blocks[-1][1] - blocks[-1][0] < _BLOCK_SLOTS:
        end = blocks.pop()[1]
        blocks[-1][1] = end
    return tuple(tuple(b) for b in blocks)


def attn_pairs(blocks: Tuple[Tuple[int, int, int], ...]) -> int:
    """Query-key pairs a head of one softmax layer scores over the
    query blocks: rows x queries x keys up to the block's end."""
    return sum(kept * (end - first) * end for first, end, kept in blocks)


def fits(stairs: Stairs, id_arrays, groups: int = 1) -> bool:
    """Whether every id outside the rectangles is PAD (0), in each of
    the `groups` contiguous blocks of rows of the batch's `[B, C]` id
    arrays (source, path, target). It looks at about half a batch's
    ids and does not ask how the rows are ordered: an unordered batch
    of java-large bags fails it by itself, with long bags below every
    rectangle."""
    rows, rem = divmod(id_arrays[0].shape[0], groups)
    if rem or stairs[0][0] != 0 or stairs[0][1] > rows:
        return False                # made for another batch
    firsts = [first for first, _ in stairs] + [id_arrays[0].shape[1]]
    for ids in id_arrays:
        blocks = ids.reshape(groups, rows, -1)
        for k, (first, kept) in enumerate(stairs):
            if np.count_nonzero(blocks[:, kept:, first:firsts[k + 1]]):
                return False
    return True
