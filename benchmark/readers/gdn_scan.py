"""How much of what the delta rule's scans ran over held a valid slot,
from the program's own record: one `gdn/scan` span a train step in
`code2vec_tpu.obs.trace.memory_tracer()` (`code2vec_tpu/obs/route.py`:
`chunk`, the slots a chunk; `chunks`, methods x chunks a method x linear
layers that the step scanned; `live_chunks`, those of them with at least
one valid slot, counted on the device from the mask). The window's steps
are the record's last `ctx.window["steps"]`: the kind flushes the recorder
once the window has closed. The value is `live_chunks` over `chunks`, in
percent: 100 says that no chunk the scans ran over was all padding.

A program that keeps no such record gives None, as does a record shorter
than the window.
"""


def reduce(records: list, steps: int):
    if not steps or len(records) < steps:
        return None
    attrs = [r["attrs"] for r in records[-steps:]]
    chunks = sum(a["chunks"] for a in attrs)
    return 100.0 * sum(a["live_chunks"] for a in attrs) / chunks \
        if chunks else None


def read(ctx, args):
    try:
        from code2vec_tpu.obs.trace import memory_tracer
    except ImportError:     # a program that keeps no record
        return None
    return reduce(memory_tracer().records("gdn/scan"), ctx.window["steps"])
