"""The training corpus a cell reads: made from the seed on the host,
written as the binary shard `data/binarize.py` produces, with the
`.dict.c2v` that makes the three vocabularies exact.

The one general generator of the `train_corpus` traffic kind. Its laws
are parameters of the traffic file (`benchmark/traffic/<name>.json`):

  ids      Zipfian over the whole vocabulary, weight 1/(rank + shift),
           drawn by the closed-form inverse of the continuous law
           (rank = shift * ((V + shift) / shift) ** u - shift);
  lengths  lognormal (median, sigma) clipped to [1, max_contexts]. Every
           seed gets the SAME multiset of lengths (the law's quantiles
           at N evenly spaced points) in an order drawn from the seed, so
           the seed moves which method is long, never how much work the
           corpus holds.

Word `t<i>` has count `V - i`, so the frequency cut keeps every word in
rank order: vocabulary index = rank + 2 (PAD = 0, OOV = 1) and the shard
can be written as integers with no text pass.
"""

from __future__ import annotations

import json
import math
import os
import pickle

import numpy as np

PAD = 0
FIRST_WORD = 2          # after PAD and OOV
_CHUNK = 65536          # methods made and written at a time


def write_dict(path: str, tokens: int, paths: int, targets: int,
               num_examples: int) -> None:
    """`.dict.c2v`: three count dicts and the example count, pickled in
    that order (vocab/vocabularies.read_count_dicts). Every count is
    distinct, so cutting by frequency keeps all words in this order."""
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        for stem, n in (("t", tokens), ("p", paths)):
            pickle.dump({f"{stem}{i}": n - i for i in range(n)}, f)
        pickle.dump({f"get|m{i}": targets - i for i in range(targets)}, f)
        pickle.dump(num_examples, f)
    os.replace(tmp, path)


def length_multiset(n: int, law: dict, max_contexts: int) -> np.ndarray:
    """The N bag lengths every seed shares: quantiles of the clipped
    lognormal at (i + 0.5) / N."""
    from statistics import NormalDist

    q = (np.arange(n, dtype=np.float64) + 0.5) / n
    # inverse normal CDF on a coarse grid, interpolated: exact enough for
    # integer lengths and three orders faster than N scalar calls
    grid = np.linspace(1e-6, 1 - 1e-6, 20001)
    z = np.interp(q, grid, [NormalDist().inv_cdf(g) for g in grid])
    lengths = np.exp(math.log(law["median"]) + law["sigma"] * z)
    return np.clip(np.rint(lengths), law.get("min", 1),
                   max_contexts).astype(np.int32)


def _zipf(rng, n: int, vocab: int, shift: float) -> np.ndarray:
    """n vocabulary indices, rank drawn with weight 1 / (rank + shift)."""
    u = rng.random(n, dtype=np.float32)
    u *= np.float32(math.log((vocab + shift) / shift))
    rank = np.exp(u, out=u)
    rank *= np.float32(shift)
    rank -= np.float32(shift)
    ids = rank.astype(np.int32)                  # floor: rank >= 0
    np.clip(ids, 0, vocab - 1, out=ids)
    ids += FIRST_WORD
    return ids


def write_corpus(prefix: str, *, seed: int, num_methods: int, vocab: dict,
                 max_contexts: int, ids_law: dict, lengths_law: dict
                 ) -> dict:
    """Write `<prefix>.train.bin` + `.bin.json`; returns the counts of
    what it wrote. Made on the host in bulk, ids for the valid slots
    only."""
    C, shift = max_contexts, float(ids_law["shift"])
    rng = np.random.default_rng(seed)
    lengths = length_multiset(num_methods, lengths_law, C)
    lengths = lengths[rng.permutation(num_methods)]
    buffer = np.empty((min(_CHUNK, num_methods), 1 + 3 * C), np.int32)
    with open(prefix + ".train.bin", "wb") as f:
        for start in range(0, num_methods, _CHUNK):
            part = lengths[start:start + _CHUNK]
            n = len(part)
            live = np.arange(C, dtype=np.int32)[None, :] < part[:, None]
            n_live = int(part.sum())
            rows = buffer[:n]
            rows.fill(PAD)
            rows[:, 0] = _zipf(rng, n, vocab["targets"], shift)
            for j, v in enumerate((vocab["tokens"], vocab["paths"],
                                   vocab["tokens"])):
                rows[:, 1 + j * C:1 + (j + 1) * C][live] = _zipf(
                    rng, n_live, v, shift)
            rows.tofile(f)
    with open(prefix + ".train.bin.json", "w") as f:
        json.dump({"num_examples": num_methods,
                   "max_contexts": max_contexts, "pad_index": PAD,
                   "layout": "label,src*C,path*C,tgt*C",
                   "dtype": "int32"}, f)
    lens64 = lengths.astype(np.int64)
    return {"num_methods": int(num_methods),
            "valid_contexts": int(lens64.sum()),
            "valid_contexts_sq": int((lens64 * lens64).sum()),
            "full_bags_share": float((lengths == max_contexts).mean())}
