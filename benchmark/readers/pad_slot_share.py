"""How often the embedding gather spreads a PAD read: the share of the
window's slots whose path id is PAD, in percent. The producer counts a
batch's PAD slots from its mask and leaves them on the batch's
`infeed/read` span (`pad_slots`, beside `seq` and `rows`;
`data/prefetch.py`); the window's batches are those of its last
`ctx.window["steps"]` pops that name one, as `program_span.py` takes
them; a batch has `rows` x the configuration's `max_contexts` slots.

A program whose reads carry no `pad_slots` gives None, as does a record
that no longer holds a read for each of the window's batches.
"""


def reduce(records: list, steps: int, max_contexts: int):
    """PAD slots over slots, in percent, of the batches that the last
    `steps` pops with a `seq` name; None where a batch's read is not in
    the record or carries no count."""
    seqs = [r["attrs"]["seq"] for r in records
            if r["name"] == "infeed/pop_wait" and "seq" in r["attrs"]]
    if not steps or len(seqs) < steps:
        return None
    wanted = set(seqs[-steps:])
    reads = [r["attrs"] for r in records if r["name"] == "infeed/read"
             and r["attrs"].get("seq") in wanted]
    if len(reads) != len(wanted) or any(
            a.get("pad_slots") is None or not a.get("rows") for a in reads):
        return None
    return 100.0 * sum(a["pad_slots"] for a in reads) / (
        sum(a["rows"] for a in reads) * max_contexts)


def read(ctx, args):
    try:
        from code2vec_tpu.obs.trace import memory_tracer
    except ImportError:     # a program that keeps no record
        return None
    return reduce(memory_tracer().records("infeed/"), ctx.window["steps"],
                  ctx.config["model"]["max_contexts"])
