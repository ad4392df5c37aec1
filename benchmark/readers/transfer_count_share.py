"""How much of each batch a step works on, by a count the producer
leaves on the batch's transfer: the window's counts over the window's
whole, in percent. `args["attr"]` names the count, an attribute of each
training batch's `infeed/transfer` span (beside `seq` and `bytes`;
`data/prefetch.py`); the whole of a batch is its `rows` (its
`infeed/read` span's) x the configuration's `max_contexts` to the power
`args["power"]`. A new count of this kind is a metric file, no reader.

`attn_score_share` reads `attn_pairs` with power 2: for an encoder
whose softmax mixers' core runs by query block over a training batch's
staircase (`models/seq_block.causal_core`), the query-key pairs a head
of one softmax layer of the step chosen for the batch scores: the query
blocks' (`data/staircase.attn_pairs`: rows x queries x the keys up to
the block's last slot) when the batch fits its staircase, `rows` x
`max_contexts` squared when it does not. 100 says that no batch of the
window fitted. The count is the producer's, made by the function the
step was compiled by (`seq_block.core_blocks`) from the answer the step
is chosen by: what the host says the step scores, not a measurement of
the device.

The window's batches are those of its last `ctx.window["steps"]` pops
that name one, as `gather_slot_share.py` takes them. A program whose
transfers carry no such count gives None, as does a record that no
longer holds a read and a transfer for each of the window's batches.
"""


def reduce(records: list, steps: int, max_contexts: int, attr: str,
           power: int):
    """The batches' `attr` over their rows x max_contexts ** power, in
    percent, of the batches that the last `steps` pops with a `seq`
    name; None where a batch's read or transfer is not in the record or
    the transfer carries no count."""
    seqs = [r["attrs"]["seq"] for r in records
            if r["name"] == "infeed/pop_wait" and "seq" in r["attrs"]]
    if not steps or len(seqs) < steps:
        return None
    wanted = set(seqs[-steps:])
    rows = {r["attrs"]["seq"]: r["attrs"].get("rows") for r in records
            if r["name"] == "infeed/read" and r["attrs"].get("seq") in wanted}
    counts = {r["attrs"]["seq"]: r["attrs"][attr] for r in records
              if r["name"] == "infeed/transfer"
              and r["attrs"].get("seq") in wanted
              and r["attrs"].get(attr) is not None}
    if set(rows) != wanted or set(counts) != wanted \
            or not all(rows.values()):
        return None
    return 100.0 * sum(counts.values()) / (sum(rows.values())
                                           * max_contexts ** power)


def read(ctx, args):
    try:
        from code2vec_tpu.obs.trace import memory_tracer
    except ImportError:     # a program that keeps no record
        return None
    return reduce(memory_tracer().records("infeed/"), ctx.window["steps"],
                  ctx.config["model"]["max_contexts"], args["attr"],
                  args["power"])
