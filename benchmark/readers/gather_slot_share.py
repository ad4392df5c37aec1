"""How much of each batch the embedding gather takes table rows for:
the window's gathered slots over its slots, in percent. The producer
leaves on each training batch's `infeed/transfer` span the slots the
step chosen for the batch takes rows for (`gather_slots`, beside `seq`
and `bytes`; `data/prefetch.py`): the staircase's area when the batch
fits it (`data/staircase.py`) and `rows` x `max_contexts` when it does
not, which is the answer the step is chosen by. The window's batches
are those of its last `ctx.window["steps"]` pops that name one, as
`program_span.py` takes them; a batch has `rows` (its `infeed/read`
span's) x the configuration's `max_contexts` slots. 100 says that no
batch of the window fitted.

A program whose transfers carry no `gather_slots` gives None, as does a
record that no longer holds a read and a transfer for each of the
window's batches.
"""


def reduce(records: list, steps: int, max_contexts: int):
    """Gathered slots over slots, in percent, of the batches that the
    last `steps` pops with a `seq` name; None where a batch's read or
    transfer is not in the record or the transfer carries no count."""
    seqs = [r["attrs"]["seq"] for r in records
            if r["name"] == "infeed/pop_wait" and "seq" in r["attrs"]]
    if not steps or len(seqs) < steps:
        return None
    wanted = set(seqs[-steps:])
    rows = {r["attrs"]["seq"]: r["attrs"].get("rows") for r in records
            if r["name"] == "infeed/read" and r["attrs"].get("seq") in wanted}
    slots = {r["attrs"]["seq"]: r["attrs"]["gather_slots"] for r in records
             if r["name"] == "infeed/transfer"
             and r["attrs"].get("seq") in wanted
             and r["attrs"].get("gather_slots") is not None}
    if set(rows) != wanted or set(slots) != wanted \
            or not all(rows.values()):
        return None
    return 100.0 * sum(slots.values()) / (sum(rows.values()) * max_contexts)


def read(ctx, args):
    try:
        from code2vec_tpu.obs.trace import memory_tracer
    except ImportError:     # a program that keeps no record
        return None
    return reduce(memory_tracer().records("infeed/"), ctx.window["steps"],
                  ctx.config["model"]["max_contexts"])
