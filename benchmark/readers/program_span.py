"""The program's own record of its input pipeline, a mean over the
window's batches. The program keeps the spans of each produced batch in
memory (`code2vec_tpu.obs.trace.memory_tracer()`; `data/prefetch.py`
says what starts and ends each): `infeed/read`, `infeed/transfer` and
`infeed/blocked` on the producer thread, tied by `seq` to the
`infeed/pop_wait` on the consumer that popped the batch. The window's
batches are those of the last `ctx.window["steps"]` pops that name one:
the loop pops one batch a step, and closing the feed pops nothing.

`args["value"]`:
  read_ms        mean `infeed/read` a batch
  transfer_ms    mean `infeed/transfer` a batch
  busy_share     (read + transfer) / (read + transfer + blocked), in
                 percent: near 100 the producer sets the pace
  mb_per_step    the `bytes` of the batches' `infeed/transfer` spans
                 over the steps, in 1e6 bytes

A program that keeps no such record gives None, as does a window longer
than what the record still holds. Says on standard error what the pops
themselves waited, beside the harness's `infeed_wait_s` from outside.
"""

import sys


def reduce(records: list, steps: int):
    """The window's sums from the recorder's span records (dicts with
    `name`, `t0`, `t1`, `attrs`), or None when they do not hold `steps`
    pops, each of a batch with one read, one transfer and one blocked
    span of its own. Seconds and bytes, over all the window's batches.
    (A chunked feed pops and puts several batches at once, so it gives
    None: no cell runs one.)"""
    pops = [r for r in records if r["name"] == "infeed/pop_wait"]
    named = [i for i, r in enumerate(pops) if "seq" in r["attrs"]]
    if not steps or len(named) < steps:
        return None
    first = named[-steps]
    # a pop of an end-of-epoch marker is part of the same wait as the
    # pop of the batch after it
    while first > 0 and "seq" not in pops[first - 1]["attrs"]:
        first -= 1
    seqs = sorted(r["attrs"]["seq"] for r in pops[first:]
                  if "seq" in r["attrs"])
    out = {"read_s": 0.0, "transfer_s": 0.0, "blocked_s": 0.0,
           "pop_wait_s": sum(r["t1"] - r["t0"] for r in pops[first:]),
           "bytes": 0}
    found = {"infeed/read": [], "infeed/transfer": [],
             "infeed/blocked": []}
    wanted = set(seqs)
    for r in records:
        if r["name"] in found and r["attrs"].get("seq") in wanted:
            found[r["name"]].append(r["attrs"]["seq"])
            out[r["name"].split("/")[1] + "_s"] += r["t1"] - r["t0"]
            out["bytes"] += r["attrs"].get("bytes", 0)
    if any(sorted(got) != seqs for got in found.values()):
        return None         # the record no longer holds the whole window
    return out


def read(ctx, args):
    try:
        from code2vec_tpu.obs.trace import memory_tracer
    except ImportError:     # a program that keeps no record
        return None
    steps = ctx.window["steps"]
    got = reduce(memory_tracer().records("infeed/"), steps)
    if got is None:
        return None
    value = args["value"]
    if value == "busy_share":
        busy = got["read_s"] + got["transfer_s"]
        print(f"program_span: a step, read {got['read_s'] * 1e3 / steps:.3f}"
              f" ms, transfer {got['transfer_s'] * 1e3 / steps:.3f} ms, "
              f"blocked {got['blocked_s'] * 1e3 / steps:.3f} ms, pop_wait "
              f"{got['pop_wait_s'] * 1e3 / steps:.3f} ms (the harness's "
              f"infeed_wait {ctx.window['infeed_wait_s'] * 1e3 / steps:.3f}"
              " ms)", file=sys.stderr)
        total = busy + got["blocked_s"]
        return 100.0 * busy / total if total > 0 else None
    if value == "mb_per_step":
        return got["bytes"] / 1e6 / steps
    return got[{"read_ms": "read_s", "transfer_ms": "transfer_s"}[value]] \
        * 1e3 / steps
